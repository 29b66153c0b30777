"""Chip-independent program-drift guard.

The repo's canonical workload is ONE specific compiled program (resnet18
@224, per-device batch 128, bf16 AMP, direct stem). Chip time is scarce, so
between chip runs nothing else would notice if a stem/remat/fusion/optimizer
change silently shifted that program. This test compiles
the canonical program on the CPU backend (same builder the bench uses —
``bench.build_compiled_step``) and pins its XLA cost-analysis FLOPs and
compiler-side memory against committed goldens.

The goldens are updated DELIBERATELY, together with fresh bench rows, never
implicitly: run with ``TPUDIST_UPDATE_COST_GOLDENS=1`` to rewrite
``tests/goldens/compiled_cost.json``, and commit the new file alongside the
measurement that motivated the program change.

Note the fingerprint is of the 8-virtual-device CPU-mesh build (the test
env), so it additionally covers the SPMD program with its gradient pmean —
per-device shapes match the canonical single-chip program.
"""

import importlib.util
import json
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "goldens", "compiled_cost.json")

# The canonical program plus the two A/B levers bench.py exposes: a
# change to any of the three programs must be deliberate.
_VARIANTS = {
    "canonical": {},
    "s2d": {"s2d": True},
    "remat": {"remat": True},
}


def _bench_module():
    spec = importlib.util.spec_from_file_location(
        "bench_for_cost", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fingerprint(bench, **overrides) -> dict:
    import jax
    assert jax.default_backend() == "cpu", "fingerprints are CPU-backend"
    _, compiled, *_rest = bench.build_compiled_step(
        "resnet18", 128, 224, **overrides)
    ma = compiled.memory_analysis()
    return {
        "flops_per_device": bench.compiled_flops(compiled),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "n_devices": jax.device_count(),
    }


def _check_against_golden(got: dict) -> None:
    assert os.path.exists(GOLDEN_PATH), (
        "no committed golden: run the slow-tier test once with "
        "TPUDIST_UPDATE_COST_GOLDENS=1")
    with open(GOLDEN_PATH) as f:
        want = json.load(f)
    for name, g in got.items():
        w = want[name]
        assert g["n_devices"] == w["n_devices"], (name, g, w)
        # FLOPs are the program's arithmetic identity: exact.
        assert g["flops_per_device"] == w["flops_per_device"], (
            f"{name}: compiled FLOPs changed "
            f"{w['flops_per_device']} -> {g['flops_per_device']} — if "
            f"deliberate, re-run with TPUDIST_UPDATE_COST_GOLDENS=1 and "
            f"commit the golden with fresh bench rows")
        # args/outputs are the state+batch footprint: exact.
        for k in ("argument_bytes", "output_bytes"):
            assert g[k] == w[k], (name, k, w[k], g[k])
        # temp (activation/workspace) memory may wiggle with XLA's scheduler;
        # gate drift beyond 5% — the remat/stem regressions this guard
        # exists for move it by far more.
        if w["temp_bytes"]:
            drift = abs(g["temp_bytes"] - w["temp_bytes"]) / w["temp_bytes"]
            assert drift <= 0.05, (
                f"{name}: compiled temp memory drifted {drift:.1%} "
                f"({w['temp_bytes']} -> {g['temp_bytes']})")


def test_canonical_fingerprint_matches_golden():
    """Fast tier: the ONE program the perf claim describes."""
    bench = _bench_module()
    if os.environ.get("TPUDIST_UPDATE_COST_GOLDENS"):
        pytest.skip("golden update runs via the slow-tier all-variants test")
    _check_against_golden({"canonical": _fingerprint(bench)})


@pytest.mark.slow
def test_ab_lever_fingerprints_match_golden():
    """Slow tier: the s2d/remat A/B programs; also the deliberate
    golden-update entry point (TPUDIST_UPDATE_COST_GOLDENS=1)."""
    bench = _bench_module()
    got = {name: _fingerprint(bench, **kw) for name, kw in _VARIANTS.items()}

    if os.environ.get("TPUDIST_UPDATE_COST_GOLDENS"):
        os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
        with open(GOLDEN_PATH, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
        pytest.skip(f"goldens rewritten at {GOLDEN_PATH} — commit them "
                    f"with the bench rows that motivated the change")
    _check_against_golden(got)


def test_ab_levers_produce_distinct_compiled_programs():
    """Sanity on the committed goldens themselves (no compile): each lever
    must actually CHANGE the compiled program — a refactor that drops the
    flag on the floor would collapse the fingerprints together.

    (The remat trade's DIRECTION — more FLOPs, less temp — is not asserted
    here: the CPU backend's optimizer folds the recompute back out of the
    compiled module (observed r5: remat flops == canonical flops post-opt on
    CPU), so the direction is only visible on TPU. The recompute's presence
    in the lowered program is pinned by
    test_remat.test_resnet_remat_recomputes_backward.)"""
    if not os.path.exists(GOLDEN_PATH):
        pytest.skip("goldens not generated yet")
    with open(GOLDEN_PATH) as f:
        want = json.load(f)
    flops = {name: v["flops_per_device"] for name, v in want.items()}
    assert len(set(flops.values())) == len(flops), flops
