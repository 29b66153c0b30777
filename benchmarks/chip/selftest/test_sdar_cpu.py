"""The harness over the tiny twin of `sdar_30b_ep8` (`tiny/sdar_tiny.json` x
`tiny/staged_tokens_tiny.json`), on the CPU, past its look for a chip: a step
trained by diffusion over blocks, its noise drawn from the leaf the reference
drew, the streaming kernel interpreted under the block-diffusion mask.

Run by path with the rest of this directory (`test_harness_cpu.py` says how
and what a CPU run can and cannot report). What is checked:

- a sound run comes out `correct`, says `kernel: flash` and a key that names
  the mask (`bd4`), the reference prints the noise it drew in the compared
  steps, and the program's counters of the same noise agree with it;
- with the step returning its state unchanged `correct` is false, and so it
  is with the program walking a causal mask over the doubled row;
- the fp8 control fails a limit that the bf16 control passes;
- the readers this configuration brought (`TIER1`: no `Trainer` is built, so
  `tests/test_chip_harness.py` collects them in tier-1): the counts of
  `metrics/attn_bd_roofline.py` at the cell's shape, a share above 100 (which
  `cellrun` refuses), `attn_bd_fill_pct` from the program's plan,
  `bd_noise_ms` on hand-made scopes, and nothing (no metric) from a program
  without them.
"""

import gc
import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_harness_cpu import CPU_PEAKS, _unchanged, load  # noqa: E402
from test_mellum2_cpu import reader, said, scopes_of  # noqa: E402

CELL = "sdar_30b_ep8_staged_8k"


def tiny_run(*, seed=11, trace=False, step_hook=None, seconds=1.0):
    """As `test_mellum2_cpu.tiny_run`: the tiny cell joins the lists the real
    cell is in."""
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": "tiny_sdar", "config": "sdar_tiny",
            "traffic": "staged_tokens_tiny", "chips": 1}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell["name"]]
    return run_cell(
        bench=bench, workload=cell,
        config=load(HERE, "tiny", "sdar_tiny.json"),
        traffic_spec=load(HERE, "tiny", "staged_tokens_tiny.json"),
        peaks=CPU_PEAKS, seed=seed, seconds=seconds, trace=trace,
        chip_dir=CHIP, t_start=time.time(), require_tpu=False,
        step_hook=step_hook)


# --- the cell end to end (each builds a Trainer: run by path) ----------------

def test_sound_run_is_correct(capfd):
    gc.collect()
    result = tiny_run(seed=2 ** 31 + 777, trace=True)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["attention_kernel"] == "flash"
    lines = capfd.readouterr().out.splitlines()
    dispatch = said(lines, "attention_dispatch")[0]
    assert (dispatch["mode"], dispatch["source"]) == ("on", "forced")
    assert "_t64_" in dispatch["key"] and dispatch["key"].endswith("_bd4")
    assert said(lines, "traffic")[0]["rows"] == "tokens"
    assert said(lines, "resident_at_window") == [
        {"parameter_sized_extras": []}]
    theirs = said(lines, "bd_reference")
    assert len(theirs) == 3            # the compared steps
    assert all(0.0 < r["bd_masked_share"] < 1.0 for r in theirs)
    # three different draws: the key advanced on both sides
    assert len({r["bd_masked_share"] for r in theirs}) == 3
    # the program's counters of the same steps are the reference's numbers
    from tpudist import telemetry
    ours = telemetry.counters()
    assert ours["bd_masked_share"][:3] == pytest.approx(
        [r["bd_masked_share"] for r in theirs], abs=1e-6)
    assert ours["bd_weight_sum"][:3] == pytest.approx(
        [r["bd_weight_sum"] for r in theirs], rel=1e-5)
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < result["metrics"]["attn_bd_fill_pct"]["value"] <= 100.0
    assert said(lines, "attn_bd_plan")[0]["mask"] == "block_diffusion"
    # a CPU trace carries no names: the device-trace readers leave theirs out
    assert not {"moe_ms", "lm_head_ms", "attn_bd_roofline",
                "bd_noise_ms"} & set(result["metrics"])


def test_a_step_that_changes_nothing_is_not_correct():
    assert tiny_run(step_hook=_unchanged)["correct"] is False


def test_a_causal_mask_over_the_doubled_row_is_not_correct(monkeypatch):
    """The program walking the wrong mask (causal over 2L, twice the pairs)
    runs, trains, and fails the comparison."""
    import tpudist.ops.pallas as pallas
    real = pallas.flash_attention
    monkeypatch.setattr(
        pallas, "flash_attention",
        lambda q, k, v, **mask: real(q, k, v, causal=True))
    assert tiny_run()["correct"] is False


def test_fp8_control_is_not_correct():
    import jax
    from harness import check
    config = load(HERE, "tiny", "sdar_tiny.json")
    ref = check.load_reference(CHIP, config["reference_module"])
    verdicts = {"bf16": [], "fp8": []}
    for seed in range(2):
        p0, s0 = ref.init(jax.random.PRNGKey(seed), config)
        batches = []
        for i in range(3):
            ids = jax.random.randint(jax.random.PRNGKey(100 + 3 * seed + i),
                                     (2, 33), 0, config["vocab_size"])
            batches.append((ids[:, :-1], ids[:, 1:]))
        sound = check.reference_readings(ref, config, p0, s0, batches,
                                         config["window_lr"])
        names = {"first_grad": check.leaf_names(p0),
                 "param_change": check.leaf_names(p0),
                 "stats_change": check.leaf_names(s0)}
        for quant in verdicts:
            got = check.reference_readings(ref, config, p0, s0, batches,
                                           config["window_lr"], quant=quant)
            verdicts[quant].append(check.compare(
                got, sound, config["control_limits"], names)[0])
    assert verdicts == {"bf16": [True] * 2, "fp8": [False] * 2}


# --- the readers (no Trainer: collected in tier-1 too) -----------------------

def test_attn_bd_counts_at_the_cells_shape():
    m = reader("attn_bd_roofline")
    length, bl = 8192, 4
    assert m.pairs(length, bl) == length * (length + bl) == 67141632
    # by hand, 6 ids in blocks of 4: the copies' rows see 4,4,4,4,6,6 each
    assert m.pairs(6, 4) == 2 * (4 * 4 + 2 * 6)
    assert m.pairs(8, 4) == 8 * 12 and m.pairs(4, 8) == 2 * 4 * 4
    # a quarter of the square, half of a causal mask over 2L
    assert m.pairs(length, bl) * 4 == (2 * length) ** 2 + 4 * length * bl
    shape = (2, length, bl, 32, 4, 128, 2)
    flops, nbytes = m.forward_call(*shape)
    assert flops == 2 * 2 * 2 * 32 * 67141632 * 128
    assert nbytes == ((2 * 32 + 2 * 4) * 2 * 16384 * 128 * 2
                      + 4 * 2 * 32 * 16384)
    flops_b, nbytes_b = m.backward_call(*shape)
    assert flops_b == 7 * 2 * 2 * 32 * 67141632 * 128
    assert nbytes_b == ((4 * 32 + 4 * 4) * 2 * 16384 * 128 * 2
                        + 8 * 2 * 32 * 16384)
    line = ('  %c = bf16[2,4,8,16384,128] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/jvp(f)/x/'
            'attn_fused/jit(flash_attention)/pallas_call"}, backend_config='
            '{"custom_call_config":{"cost_estimate":{"flops":"12","trans'
            'cendentals":"3","bytes_accessed":"40"}}}')
    assert m.program_cost_estimate(line + "\n" + line) == [24, 80, 2]
    assert m.program_cost_estimate("no kernel here") is None


FWD = ("jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/self_attention/"
       "attn_fused/jit(flash_attention)/pallas_call")
BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
       "layer_1/self_attention/attn_fused/jit(flash_attention)/pallas_call")


def _ctx():
    return {"attention_kernel": "flash", "batch": 2, "chips": 1,
            "config": load(CHIP, "configs", "sdar_30b_ep8.json"),
            "peak": load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"],
            "step_hlo": None, "steps": 4}


def test_attn_bd_roofline_reader(monkeypatch, capsys):
    """Four layers' calls over a hand-made step: the forward's 4 x 2.2e12
    operations and the backward's 4 x 7.7e12 at 197e12/s are 44.7 + 156.4
    ms (both compute-bound); over 400 ms under the scope that is 50.3 %. Over
    150 ms it would be 134 %: such a reading `cellrun` refuses, whatever the
    reader says."""
    from harness import scope_reduce
    m = reader("attn_bd_roofline")
    ctx = _ctx()
    ops = [(25.0, "fwd", FWD)] * 4 + [(75.0, "bwd", BWD)] * 4 + [
        (99.0, "fwd", FWD.replace("attn_fused", "q_proj")),
        (9.0, "layout_copy", BWD)]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = m.read(ctx)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["bound_by"] == ["compute", "compute"]
    assert line["device_ms"] == pytest.approx(400.0)
    least = 4 * (2 + 7) * 2 * 2 * 32 * 67141632 * 128 / 197e12 * 1e3
    assert line["least_ms"] == pytest.approx(least)
    assert value == pytest.approx(100 * least / 400.0) and 45 < value < 55
    # too little time under the scope: above 100, which the harness refuses
    fast = [(ms * 150 / 400, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*fast))
    assert m.read(ctx) > 100.0
    entry = next(e for e in load(ROOT, "BENCHMARK.json")["per_layer"]
                 if e["name"] == "attn_bd_roofline")
    assert entry["unit"] == "%" and entry["name"].endswith("_roofline")
    assert entry["workloads"] == [CELL]
    # nothing to read: another kernel, another configuration, no scope
    assert m.read(dict(ctx, attention_kernel="xla")) is None
    assert m.read(dict(ctx, config=load(
        CHIP, "configs", "mellum2_12b_ep4.json"))) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes_of(
        (3.0, "fwd", FWD.replace("attn_fused", "attn_scores"))))
    assert m.read(ctx) is None


def test_attn_bd_fill_reader(monkeypatch, capsys):
    m = reader("attn_bd_fill_pct")
    ctx = _ctx()
    value = m.read(ctx)
    plan = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert plan["mask"] == "block_diffusion" and plan["block_length"] == 4
    assert value == pytest.approx(100.0 * plan["band_fill"])
    # scores the mask allows over scores the forward's tiles hold
    assert value == pytest.approx(
        100.0 * 67141632 / (160 * plan["block_q"] * plan["block_k"]),
        abs=0.01)
    assert 75.0 <= value <= 100.0
    assert m.read(dict(ctx, attention_kernel="xla")) is None
    assert m.read(dict(ctx, config=load(
        CHIP, "configs", "mellum2_12b_ep4.json"))) is None
    # a program whose plan knows no such mask (the parent commit)
    from tpudist.ops import attention_dispatch
    monkeypatch.setattr(
        attention_dispatch, "program",
        lambda seq, heads, head_dim, dtype, *, kv_heads=None, causal=False,
        window=None: {})
    assert m.read(ctx) is None


def test_bd_noise_reader(monkeypatch, capsys):
    from harness import scope_reduce
    from tpudist import telemetry
    m = reader("bd_noise_ms")
    noise = "jit(step)/jvp(tpudist_forward)/MoEDecoder/bd_noise/"
    scopes = scopes_of(
        (0.25, "fwd", noise + "threefry2x32"), (0.5, "fwd", noise + "select_n"),
        (8.0, "layout_copy", noise + "concatenate"),     # not named
        (16.0, "fwd", FWD))
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    monkeypatch.setattr(telemetry, "_counters", {
        "bd_masked_share": [0.5, 0.25, 0.75, 0.5, 0.5, 0.5, 0.5],
        "bd_weight_sum": [1.0] * 7, "moe_pairs.layer_0": [9.0] * 7})
    ctx = _ctx()
    assert m.read(ctx) == 0.75
    line = said(capsys.readouterr().out.splitlines(), "bd_noise")[0]
    assert line["bd_noise_ms"] == 0.75
    assert line["bd_masked_share"] == {"compared": [0.5, 0.25, 0.75],
                                       "window_mean": 0.5}
    assert "moe_pairs.layer_0" not in line
    # a step without the scope (a next-id model, the parent commit)
    plain = scopes_of((3.0, "fwd", FWD))
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: plain)
    assert m.read(ctx) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert m.read(ctx) is None


TIER1 = (test_attn_bd_counts_at_the_cells_shape, test_attn_bd_roofline_reader,
         test_attn_bd_fill_reader, test_bd_noise_reader)
