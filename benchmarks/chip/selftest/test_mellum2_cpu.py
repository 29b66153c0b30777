"""The harness over the tiny twin of `mellum2_12b_ep4`
(`tiny/mellum2_tiny.json` x `tiny/staged_tokens_tiny.json`), on the CPU, past
its look for a chip: the token mix, the reference handed the whole
configuration, the streaming kernel interpreted, the counters.

Run by path with the rest of this directory (`test_harness_cpu.py` says how
and what a CPU run can and cannot report). What is checked:

- a sound run comes out `correct`, says `kernel: flash`, and the reference
  prints the pairs each held expert got in the compared steps;
- with the step returning its state unchanged `correct` is false;
- the fp8 control fails a limit that the bf16 control passes;
- the readers this configuration brought: the counts of
  `metrics/attn_stream_roofline.py` at the cell's shape, `moe_ms` /
  `lm_head_ms` on hand-made scopes, `moe_load_max_over_mean` from the
  program's counters, and nothing (no metric) from a program without them.
"""

import gc
import importlib.util
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

CELL = "mellum2_12b_ep4_staged_8k"


def tiny_run(*, seed=11, trace=False, step_hook=None, seconds=1.0):
    """As `test_harness_cpu.tiny_run`, the tiny cell joining the lists the
    real cell is in (and no other: `attn_fused_roofline` counts ViT's)."""
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": "tiny_mellum2", "config": "mellum2_tiny",
            "traffic": "staged_tokens_tiny", "chips": 1}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell["name"]]
    return run_cell(
        bench=bench, workload=cell,
        config=load(HERE, "tiny", "mellum2_tiny.json"),
        traffic_spec=load(HERE, "tiny", "staged_tokens_tiny.json"),
        peaks=CPU_PEAKS, seed=seed, seconds=seconds, trace=trace,
        chip_dir=CHIP, t_start=time.time(), require_tpu=False,
        step_hook=step_hook)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"selftest_metric_{name}", os.path.join(CHIP, "metrics",
                                                name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def said(lines, what):
    return [json.loads(line.split(" ", 2)[2]) for line in lines
            if line.startswith(f"bench {what} ")]


def test_sound_run_is_correct(capfd):
    gc.collect()
    result = tiny_run(seed=2 ** 31 + 777, trace=True)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["attention_kernel"] == "flash"
    lines = capfd.readouterr().out.splitlines()
    dispatch = said(lines, "attention_dispatch")[0]
    assert (dispatch["mode"], dispatch["source"]) == ("on", "forced")
    assert "_kv2_" in dispatch["key"] and "_w8" in dispatch["key"]
    assert said(lines, "traffic")[0]["rows"] == "tokens"
    assert said(lines, "resident_at_window") == [
        {"parameter_sized_extras": []}]
    routed = said(lines, "moe_route_reference")
    assert len(routed) == 3            # the compared steps
    assert all(len(r["pairs_by_layer_and_held_expert"]) == 2 and
               len(r["pairs_by_layer_and_held_expert"][0]) == 2
               for r in routed)
    # the program's counters reached the reader: pairs a layer and a step
    # beside the reference's totals
    route = said(lines, "moe_route")[0]
    for layer in range(2):
        ours = route[f"moe_pairs.layer_{layer}"]["compared"]
        theirs = [sum(r["pairs_by_layer_and_held_expert"][layer])
                  for r in routed]
        assert len(ours) == 3
        assert all(abs(a - b) <= 0.1 * b + 4 for a, b in zip(ours, theirs))
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    # a CPU trace carries no names: the device-trace readers leave theirs out
    assert not {"moe_ms", "lm_head_ms", "attn_stream_roofline"} & set(
        result["metrics"])


def test_a_step_that_changes_nothing_is_not_correct():
    assert tiny_run(step_hook=_unchanged)["correct"] is False


def test_fp8_control_is_not_correct():
    import jax
    from harness import check
    config = load(HERE, "tiny", "mellum2_tiny.json")
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


def test_attn_stream_counts_at_the_cells_shape():
    m = reader("attn_stream_roofline")
    t, w = 8192, 1024
    assert m.pairs(t) == t * (t + 1) // 2 == 33558528
    assert m.pairs(t, w) == w * t - w * (w - 1) // 2 == 7864832
    assert m.pairs(6, 2) == 1 + 2 * 5          # by hand: rows see 1,2,2,2,2,2
    assert m.pairs(4, 9) == m.pairs(4)         # a window wider than the row
    shape = (2, t, 32, 4, 128, 2)
    flops, nbytes = m.forward_call(*shape)
    assert flops == 2 * 2 * 2 * 32 * 33558528 * 128
    assert nbytes == (2 * 32 + 2 * 4) * 2 * t * 128 * 2 + 4 * 2 * 32 * t
    flops_b, nbytes_b = m.backward_call(*shape, w)
    assert flops_b == 7 * 2 * 2 * 32 * 7864832 * 128
    assert nbytes_b == (4 * 32 + 4 * 4) * 2 * t * 128 * 2 + 8 * 2 * 32 * t
    line = ('  %c = bf16[2,32,8192,128] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/jvp(f)/x/'
            'attn_fused/jit(flash_attention)/pallas_call"}, backend_config='
            '{"custom_call_config":{"cost_estimate":{"flops":"12","trans'
            'cendentals":"3","bytes_accessed":"40"}}}')
    assert m.program_cost_estimate(line + "\n" + line) == [24, 80, 2]
    assert m.program_cost_estimate("no kernel here") is None


def scopes_of(*ops):
    return {"ops": [[f"op.{i}", ms, bucket, name]
                    for i, (ms, bucket, name) in enumerate(ops)]}


def test_scope_readers_on_hand_made_scopes(monkeypatch, capsys):
    from harness import scope_reduce
    fwd = "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/moe/"
    bwd = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/"
           "checkpoint/layer_1/moe/")
    head = "jit(step)/jvp(tpudist_forward)/MoEDecoder/while/body/"
    scopes = scopes_of(
        (1.0, "fwd", fwd + "moe_router/dot_general"),
        (2.0, "fwd", fwd + "moe_dispatch/sort"),
        (4.0, "bwd", bwd + "moe_experts/ragged_dot"),
        (8.0, "bwd", bwd + "moe_combine/gather"),
        (16.0, "layout_copy", fwd + "moe_experts/ragged_dot"),   # not named
        (32.0, "fwd", head + "checkpoint/lm_head/dot_general"),
        (64.0, "bwd", "jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/"
         "while/body/closed_call/tpudist_loss/reduce_sum"),
        (128.0, "fwd", "jit(step)/jvp(tpudist_forward)/MoEDecoder/lm_embed/"
         "embed/gather"),
        (256.0, "fwd", fwd.replace("moe/", "self_attention/") + "q_proj"))
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    assert reader("moe_ms").read({}) == 15.0
    assert reader("lm_head_ms").read({}) == 96.0
    out = capsys.readouterr().out
    assert said(out.splitlines(), "moe_ms")[0]["moe_experts"] == 4.0
    assert said(out.splitlines(), "lm_head_ms")[0]["lm_embed"] == 128.0
    # a step without the scopes (a classifier, the parent commit): no metric
    plain = scopes_of((3.0, "fwd", "jit(step)/jvp(tpudist_forward)/ResNet/x"))
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: plain)
    assert reader("moe_ms").read({}) is None
    assert reader("lm_head_ms").read({}) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert reader("moe_ms").read({}) is None


def test_readers_read_nothing_of_a_program_without_the_counters(monkeypatch):
    from tpudist import telemetry
    monkeypatch.setattr(telemetry, "_counters", {})
    ctx = {"steps": 4, "config": {"compared_steps": 3}}
    assert reader("moe_load_max_over_mean").read(ctx) is None
    monkeypatch.delattr(telemetry, "counters")        # the parent commit
    assert reader("moe_load_max_over_mean").read(ctx) is None
    assert reader("attn_stream_roofline").read(
        {"attention_kernel": "flash", "config": {"image_size": 224}}) is None
    assert reader("attn_stream_roofline").read(
        {"attention_kernel": None, "config": {"layer_types": []}}) is None
