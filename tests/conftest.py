"""Test harness: fake an 8-device mesh on CPU in one process (SURVEY.md §4).

Tests must run on the CPU backend with
``--xla_force_host_platform_device_count=8``. If the interpreter was started
with another platform or without the device-count flag, jax may already be
configured by the time this file runs, so we re-exec pytest once with a
corrected environment. The re-exec happens in ``pytest_configure`` with
output capture suspended, otherwise the new process inherits pytest's
capture tempfile as stdout and all output vanishes.
"""

import os
import sys

_WANT_FLAG = "--xla_force_host_platform_device_count=8"


def _needs_reexec() -> bool:
    if os.environ.get("TPUDIST_TEST_REEXEC") == "1":
        return False
    if os.environ.get("JAX_PLATFORMS", "cpu") != "cpu":
        return True
    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        return True
    return False


def pytest_configure(config):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if _needs_reexec():
        from tpudist.cleanenv import cpu_env
        env = cpu_env(8)
        env["TPUDIST_TEST_REEXEC"] = "1"
        # Donated resumed-state buffers corrupt the heap on this gVisor CPU
        # runtime (the PR 1 seed-bug class — see _common.donated_jit). The
        # fault/elastic suites already set this for their subprocess ranks;
        # whether the IN-PROCESS suite trips it depends on allocator state
        # (historically green on a quiet box; deterministic segfault with a
        # warm compilation cache after a long session) — and a segfault
        # aborts the whole pytest process, so the bypass is unconditional
        # for tests. Donation stays on for real runs.
        env["TPUDIST_NO_DONATE"] = "1"
        capman = config.pluginmanager.getplugin("capturemanager")
        if capman is not None:
            capman.suspend_global_capture(in_=True)
        os.execve(sys.executable,
                  [sys.executable, "-m", "pytest"] + sys.argv[1:], env)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " " + _WANT_FLAG).strip()
    os.environ.setdefault("TPUDIST_NO_DONATE", "1")   # see re-exec note
    # Persistent compilation cache: repeat test runs skip XLA recompiles
    # (the dominant cost of this suite). Same resolver as the program
    # (serve/cache.py); exported so the ranks the tests spawn share it.
    from tpudist.serve.cache import ENV_JAX_CACHE, resolve_cache_dir
    os.environ.setdefault(ENV_JAX_CACHE, resolve_cache_dir())
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")


import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_programs():
    """Drop jax's caches of traced and compiled programs after each test
    file. A worker that had run ``test_flash_attention.py``, ``test_ops.py``
    and ``test_ring_attention.py`` segfaulted in XLA's CPU compiler at
    ``test_remat.py``'s first compile, every time and on PR 38's tree too
    (PR 39's new files moved pytest-xdist's schedule onto that order): a
    process that keeps every file's executables alive is what this
    runtime does not survive (the note on donation above is the same
    class)."""
    yield
    import jax
    jax.clear_caches()


@pytest.fixture(scope="session")
def devices():
    import jax
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from tpudist.dist import make_mesh
    return make_mesh((8,), ("data",), devices)


@pytest.fixture()
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def mp_timeout():
    """Contention-adaptive timeout scale for multi-process tests (VERDICT r3
    #5: the 2-proc smoke flaked under 3-way CPU contention and was 'fixed'
    by widening fixed margins — instead, measure what one clean-env jax
    import + trivial jit subprocess costs RIGHT NOW, the same startup price
    every launched child pays, and scale timeouts by it. Under contention
    the calibration run slows down by the same factor as the children)."""
    import subprocess
    import time
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tpudist.cleanenv import cpu_env
    t0 = time.perf_counter()
    subprocess.run(
        [sys.executable, "-c",
         "import jax, jax.numpy as jnp; "
         "jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()"],
        env=cpu_env(1), check=True, timeout=900,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    cal = time.perf_counter() - t0

    def timeout_for(nprocs: int, compile_cost: float = 1.0) -> float:
        # nprocs children each pay ~cal of startup serialized on this core,
        # plus compile_cost x the calibration unit for their jit work, plus
        # fixed headroom. The floor ALSO scales with compile cost: the
        # calibration can undershoot when load spikes after the fixture ran
        # (observed: a 240s floor killed a healthy, connected 2-proc resnet
        # compile while two suites shared the core).
        return max(240.0 * max(1.0, compile_cost),
                   cal * (8.0 + 6.0 * nprocs * compile_cost))

    return timeout_for


# -- environment capability gate (PR 3 satellite) ---------------------------
# Some container images ship a jaxlib whose CPU backend cannot compile
# cross-process programs at all — every multiprocess collective dies with
# "Multiprocess computations aren't implemented on the CPU backend". The
# same environment vintage also shifts numerics a handful of tests pin
# exactly (remat recompute math, optax EMA update order, the compiled-cost
# golden fingerprint): all were verified to fail IDENTICALLY at a clean
# HEAD on such images (see CHANGES.md, PR 2). Probe the capability ONCE per
# session and skip the known-affected tests with an explicit reason, so a
# red tier-1 run means a real regression — not a known environment gap.
#
# On a full-capability jaxlib the probe succeeds and every gated test runs
# exactly as before. Override without probing: TPUDIST_MP_COLLECTIVES=0|1.

_ENV_GATED = {
    ("test_multiprocess_scale", "test_eight_process_full_pipeline"),
    ("test_multiprocess_scale", "test_eight_process_real_data_pipeline"),
    ("test_multiprocess_scale", "test_survivor_blocked_in_collective_is_aborted"),
    ("test_multiprocess_scale", "test_launcher_max_restarts_exhaustion_propagates_failure"),
    ("test_remat", "test_resnet_remat_identical_math"),
    ("test_remat", "test_vit_remat_identical_math"),
    ("test_train", "test_model_ema_tracks_params"),
    ("test_seq_parallel", "test_sp_train_step_updates_ema"),
    ("test_expert_parallel", "test_ep_train_step_updates_ema"),
    ("test_pipeline_parallel", "test_pp_train_step_updates_ema"),
    ("test_compiled_cost", "test_canonical_fingerprint_matches_golden"),
    # Elastic plane (PR 4): the 4-rank reform-and-compare e2e drives real
    # cross-process collectives end to end — same capability gate.
    ("test_elastic", "test_reform_matches_smaller_world_reference"),
}

_ENV_GATE_REASON = (
    "environment jaxlib cannot compile cross-process CPU collectives "
    "('Multiprocess computations aren't implemented') — this test is on the "
    "verified-affected list for that jaxlib vintage (multiprocess e2e / "
    "remat + EMA numerics / cost golden); it fails identically at a clean "
    "HEAD there. Force-run with TPUDIST_MP_COLLECTIVES=1.")

_MP_PROBE_CHILD = r"""
import os
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from tpudist.dist import initialize_runtime, make_mesh, shard_host_batch

initialize_runtime()
mesh = make_mesh((jax.device_count(),), ("data",))
local = np.ones((len(jax.local_devices()),), dtype=np.float32)
(garr,) = shard_host_batch(mesh, (local,))
fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x.sum(), "data"),
                           mesh=mesh, in_specs=P("data"), out_specs=P(),
                           check_vma=False))
assert float(fn(garr)) == 2.0, float(fn(garr))
print("MP_COLLECTIVE_OK", flush=True)
"""

_mp_supported = None


def _mp_collectives_supported() -> bool:
    """One cached 2-process probe: can this jaxlib compile + run a
    cross-process CPU psum? (The exact program shape every gated
    multiprocess test depends on.)"""
    global _mp_supported
    if _mp_supported is not None:
        return _mp_supported
    forced = os.environ.get("TPUDIST_MP_COLLECTIVES", "")
    if forced in ("0", "1"):
        _mp_supported = forced == "1"
        return _mp_supported
    import socket
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from tpudist.cleanenv import cpu_env
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs = []
    for pid in range(2):
        env = cpu_env(1)
        env.update(TPUDIST_COORDINATOR=f"127.0.0.1:{port}",
                   TPUDIST_NUM_PROCESSES="2", TPUDIST_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE_CHILD], cwd=repo, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    ok = True
    for pr in procs:
        try:
            out, _ = pr.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            pr.kill()
            out = ""
        ok = ok and pr.returncode == 0 and "MP_COLLECTIVE_OK" in (out or "")
    _mp_supported = ok
    print(f"[conftest] cross-process CPU collective probe: "
          f"{'supported' if ok else 'UNSUPPORTED (gated tests will skip)'}",
          file=sys.stderr, flush=True)
    return _mp_supported


# -- smoke tier (VERDICT r2 #9) --------------------------------------------
# `pytest -m smoke` must finish <5 min COLD (empty XLA compilation cache) on
# one CPU core, so a reviewer can verify green without the warm cache. The
# tier is module-granular: these modules avoid heavyweight XLA compiles
# (pure-python transforms, ctypes kernels, eval_shape-only zoo checks, tiny
# single-op jits). Anything marked `slow` stays excluded even here.
SMOKE_MODULES = {
    "test_utils", "test_autoaugment", "test_native", "test_data",
    "test_mixup", "test_zoo", "test_ops", "test_bench_overlap",
    "test_check",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.module.__name__, item.name.split("[")[0]) in _ENV_GATED:
            item.add_marker(pytest.mark.env_capability_gated)
        if item.module.__name__ in SMOKE_MODULES \
                and item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.smoke)


def pytest_runtest_setup(item):
    # Probe at SETUP of the first gated test that actually runs, not at
    # collection: `pytest -m obs` collects the whole suite before core's
    # marker deselection, and a run that executes no gated test must not
    # pay the two-subprocess jax probe.
    if item.get_closest_marker("env_capability_gated") is not None \
            and not _mp_collectives_supported():
        pytest.skip(_ENV_GATE_REASON)
