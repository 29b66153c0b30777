"""Child-process environment for a forced CPU JAX backend — the one copy
shared by ``__graft_entry__.py`` and ``tests/conftest.py`` (it must
therefore import nothing heavier than the stdlib)."""

from __future__ import annotations

import os


def cpu_env(n_devices: int | None = None,
            base: dict | None = None) -> dict:
    """A copy of ``base`` (default ``os.environ``) reshaped for the CPU
    backend: ``JAX_PLATFORMS=cpu`` and the virtual-device-count XLA flag
    set to ``n_devices`` (replacing any existing one)."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if n_devices:
        flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env
