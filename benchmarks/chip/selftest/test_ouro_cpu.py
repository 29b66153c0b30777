"""The harness over the tiny twin of `ouro_2_6b_pp8` (`tiny/ouro_tiny.json` x
`tiny/staged_tokens_tiny.json`), on the CPU, past its look for a chip: three
dense layers under sandwich norms run four times over the same leaves by one
scan, attention at a group of one (the streaming kernel interpreted), a head
and an exit gate after every pass, the loss an expectation over exit steps.

Run by path with the rest of this directory (`test_harness_cpu.py` says how
and what a CPU run can and cannot report). What is checked:

- the cell's files parse, the mix meets the configuration's needs, and the
  benchmark lists the cell where its metrics are read (and not where a
  reader counts a call a layer);
- a sound run comes out `correct`, says `kernel: flash`, the reference
  prints its own exit statistics, and the program's counters agree with them;
- with the step returning its state unchanged `correct` is false (a wrong
  exit distribution against the same comparison: `tests/test_looped.py`);
- the fp8 control fails a limit that the bf16 control passes;
- the readers this configuration brought (`TIER1`: no `Trainer` is built, so
  `tests/test_chip_harness.py` collects them in tier-1): the two rooflines'
  counts at the cell's shape (a call a layer AND a pass), their shares and
  `dense_mlp_ms` / `loop_exit_ms` / `loop_carry_ms` / `loop_unitemised_ms` on
  hand-made scopes,
  `loop_expected_exit` from the program's counters, and nothing (no metric)
  from a program without them, as the five accepted cells' programs are.
"""

import gc
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

CELL = "ouro_2_6b_pp8_staged_8k"
NEW = ("dense_mlp_ms", "loop_exit_ms", "loop_expected_exit",
       "attn_loop_roofline", "qk_rope_loop_roofline", "loop_carry_ms",
       "loop_unitemised_ms")


def tiny_run(*, seed=11, trace=False, step_hook=None, seconds=1.0,
             limits=None):
    """As `test_mellum2_cpu.tiny_run`: the tiny cell joins the lists the real
    cell is in."""
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": "tiny_ouro", "config": "ouro_tiny",
            "traffic": "staged_tokens_tiny", "chips": 1}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell["name"]]
    config = load(HERE, "tiny", "ouro_tiny.json")
    if limits:
        config["correct_limits"] = dict(config["correct_limits"], **limits)
    return run_cell(
        bench=bench, workload=cell, config=config,
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
    assert "_t32_h4_d16_" in dispatch["key"]     # plain multi-head: no _kv
    assert said(lines, "traffic")[0]["rows"] == "tokens"
    assert said(lines, "resident_at_window") == [
        {"parameter_sized_extras": []}]
    theirs = said(lines, "loop_exit_reference")
    assert len(theirs) == 3            # the compared steps
    assert all(len(r["cross_entropy_by_pass"]) == 4 for r in theirs)
    # the program's counters of the same steps are the reference's numbers
    ours = said(lines, "loop_counters")[0]
    for name in ("loop_expected_exit", "loop_exit_entropy"):
        assert ours[name]["compared"] == pytest.approx(
            [r[name] for r in theirs], rel=2e-2)
    assert 1.0 <= result["metrics"]["loop_expected_exit"]["value"] <= 4.0
    # a CPU trace carries no names: the device-trace readers leave theirs out
    assert not {"dense_mlp_ms", "loop_exit_ms", "attn_loop_roofline",
                "qk_rope_loop_roofline", "loop_carry_ms",
                "loop_unitemised_ms", "lm_head_ms"} & set(result["metrics"])


def test_a_step_that_changes_nothing_is_not_correct():
    assert tiny_run(step_hook=_unchanged)["correct"] is False


def test_fp8_control_is_not_correct():
    import jax
    from harness import check
    config = load(HERE, "tiny", "ouro_tiny.json")
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


# --- the cell's files and readers (no Trainer: collected in tier-1 too) ------

def test_the_looped_cells_files_parse_and_its_metrics_are_listed():
    from harness import traffic
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b_pp8", "staged_tokens_8k", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    config = load(ROOT, entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    mix = load(CHIP, "traffic", cell["traffic"] + ".json")
    assert (mix["rows"], mix["seq_len"]) == ("tokens", 8192)
    assert traffic._need(config, "vocab_size", mix) == 49152
    assert os.path.exists(os.path.join(
        CHIP, "refs", config["reference_module"] + ".py"))
    assert str(mix["seq_len"]) in config["trainer_argv"]
    assert config["per_chip_batch"] == 1
    # the passes, the gate and beta are the registered model's: no flag
    assert not [a for a in config["trainer_argv"]
                if "loop" in str(a) or "exit" in str(a) or "beta" in str(a)]
    # where the cell's metrics are read: the generic ones, the head's, the
    # attention blocks' and the seven this configuration brought; not the
    # readers that count a call a layer, nor the expert layers', nor the
    # unitemised reader whose list lacks the two new scopes
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(NEW) <= listed and {
        "lm_head_ms", "fwd_ms", "attn_mixer_ms", "attn_proj_ms",
        "attn_qk_rope_ms", "block_norm_ms", "device_idle_pct"} <= listed
    assert not listed & {"attn_stream_roofline", "attn_qk_rope_roofline",
                         "attn_fused_roofline", "attn_bd_roofline", "moe_ms",
                         "moe_load_max_over_mean", "step_unitemised_ms"}
    assert len(listed - {"mfu_pct"}) == 27
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(NEW)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_img_per_s_chip"
        assert callable(reader(name).read)


def _config():
    return load(CHIP, "configs", "ouro_2_6b_pp8.json")


def _peak():
    return load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]


def test_loop_rooflines_count_a_call_a_layer_and_a_pass():
    """One row of 8,192 positions, 16 heads over 16 of 128, bfloat16, 6
    layers x 4 passes: 24 forward and 24 backward calls of each."""
    from harness import roofline
    config = _config()
    attn, qk = reader("attn_loop_roofline"), reader("qk_rope_loop_roofline")
    once = reader("attn_stream_roofline")
    pairs = 8192 * 8193 // 2
    assert once.pairs(8192) == pairs
    (f_ops, f_bytes), (b_ops, b_bytes) = attn.calls(config, 1)
    assert f_ops == 24 * 2 * 2 * 16 * pairs * 128
    assert b_ops == 24 * 7 * 2 * 16 * pairs * 128
    # q, k, v, o of [8192, 16, 128] bfloat16 and the float32 logsumexp
    assert f_bytes == 24 * (4 * 8192 * 2048 * 2 + 4 * 16 * 8192)
    assert b_bytes == 24 * (8 * 8192 * 2048 * 2 + 2 * 4 * 16 * 8192)
    least, bound_by = roofline.least_ms([(f_ops, f_bytes), (b_ops, b_bytes)],
                                        _peak())
    assert bound_by == ["compute", "compute"]
    assert least == pytest.approx(150.7, abs=0.1)       # ms a step
    # the q / k pass: 32 heads' elements read and written once each way
    n = 8192 * 32 * 128
    (f_ops, f_bytes), backward = qk.calls(config, 1)
    assert backward == (f_ops, f_bytes)
    assert f_bytes == 24 * (2 * n * 2 + 2 * 4 * 8192 * 128)
    least, bound_by = roofline.least_ms(qk.calls(config, 1), _peak())
    assert bound_by == ["memory", "memory"]
    assert least == pytest.approx(8.36, abs=0.01)


_LOOP = "MoEDecoder/loop_carry/while/body/"
_FWD = "jit(step)/jvp(tpudist_forward)/" + _LOOP + "closed_call/"
_BWD = ("jit(step)/transpose(jvp(tpudist_forward))/" + _LOOP
        + "closed_call/checkpoint/")
_MIX = "layer_1/self_attention/attn_mixer/"


def _looped_scopes():
    return scopes_of(
        (1.0, "fwd", _FWD + "layer_1/mlp/dense_mlp/gate_proj/dot_general"),
        (2.0, "bwd", _BWD + "rematted_computation/layer_1/mlp/dense_mlp/mul"),
        (4.0, "bwd", _BWD + "layer_1/mlp/dense_mlp/down_proj/dot_general"),
        (8.0, "fwd", _FWD + "loop_exit/exit_gate/dot_general"),
        (16.0, "bwd", _BWD.replace("checkpoint/", "") + "loop_exit/mul"),
        (32.0, "fwd", _FWD + _MIX + "attn_fused/pallas_call"),
        (64.0, "bwd", _BWD + _MIX + "attn_fused/pallas_call"),
        (128.0, "fwd", _FWD + _MIX + "attn_qk_norm_rope/pallas_call"),
        (256.0, "bwd", _BWD + _MIX + "attn_qk_norm_rope/pallas_call"),
        (512.0, "layout_copy", _FWD + _MIX + "attn_fused/x"),   # not named
        (1024.0, "fwd", _FWD + "layer_1/block_norm/input_norm/rsqrt"),
        (2048.0, "bwd", "jit(step)/transpose(jvp(tpudist_forward))/" + _LOOP
         + "dynamic_slice"),                             # the loop's own
        (4096.0, "fwd", _FWD + "while/body/closed_call/lm_head/dot_general"),
        (8192.0, "opt", "jit(step)/tpudist_optimizer/mul"),
        (16384.0, "fwd", "jit(step)/MoEDecoder.one_pass/layer_1/transpose"))


def test_loop_readers_on_hand_made_scopes(monkeypatch, capsys):
    from harness import scope_reduce
    scopes = dict(_looped_scopes(), busy_step_ms=32767.0)
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    ctx = {"config": _config(), "batch": 1, "chips": 1, "peak": _peak(),
           "attention_kernel": "flash", "step_hlo": None}
    assert reader("dense_mlp_ms").read(ctx) == 7.0
    assert reader("loop_exit_ms").read(ctx) == 24.0
    assert reader("loop_carry_ms").read(ctx) == 2048.0
    assert reader("loop_unitemised_ms").read(ctx) == 16384.0
    # the accepted reader's list lacks the three scopes: theirs read as left
    assert reader("step_unitemised_ms").read(ctx) == (
        16384.0 + 2048.0 + 7.0 + 24.0)
    out = capsys.readouterr().out.splitlines()
    line = said(out, "dense_mlp_ms")[0]
    # 24 layer passes x 3 products x 2 x 8,192 x 2,048 x 5,632, four times
    assert line["products_flops"] == 24 * 3 * 2 * 8192 * 2048 * 5632 * 4
    assert line["products_least_ms"] == pytest.approx(276.3, abs=0.1)
    assert said(out, "loop_unitemised")[0]["operations"] == 1
    assert reader("attn_loop_roofline").read(ctx) == pytest.approx(
        100.0 * 150.7 / 96.0, abs=0.1)
    assert reader("qk_rope_loop_roofline").read(ctx) == pytest.approx(
        100.0 * 8.36 / 384.0, abs=0.01)
    rooflines = said(capsys.readouterr().out.splitlines(), "roofline")
    assert [r["metric"] for r in rooflines] == ["attn_loop_roofline",
                                                "qk_rope_loop_roofline"]
    assert rooflines[0]["layout_copy_behind_ms"] == 512.0
    # the XLA attention path, or a configuration that states no passes (the
    # accepted token cells'): no share
    for other in (dict(ctx, attention_kernel="xla"),
                  dict(ctx, config=load(CHIP, "configs",
                                        "mellum2_12b_ep4.json"))):
        for name in ("attn_loop_roofline", "qk_rope_loop_roofline"):
            assert reader(name).read(other) is None, name
    # a step without the scopes (the accepted cells, the parent commit), or
    # no scopes at all: no metric, and no error
    plain = scopes_of(
        (3.0, "fwd", "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/"
         "block_norm/input_norm/rsqrt"),
        (5.0, "fwd", "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/"
         "moe/moe_experts/pallas_call"))
    for found in (plain, None):
        monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: found)
        for name in NEW:
            if name != "loop_expected_exit":
                assert reader(name).read(ctx) is None, name


def test_expected_exit_reader_reads_the_programs_counters(monkeypatch,
                                                          capsys):
    from tpudist import telemetry
    m = reader("loop_expected_exit")
    ctx = {"steps": 4, "config": {"compared_steps": 3}}
    monkeypatch.setattr(telemetry, "_counters", {
        "loop_expected_exit": [1.875, 1.875, 1.875, 2.0, 2.5, 3.0, 3.5],
        "loop_exit_entropy": [1.2] * 7,
        "moe_pairs.layer_1": [9.0] * 7})
    assert m.read(ctx) == 2.75         # the window's four steps
    line = said(capsys.readouterr().out.splitlines(), "loop_counters")[0]
    assert line["loop_expected_exit"] == {
        "compared": [1.875] * 3, "window_mean": 2.75, "window_min": 2.0,
        "window_max": 3.5}
    assert set(line) == {"loop_expected_exit", "loop_exit_entropy"}
    # a program without the counters (the accepted cells), or without the
    # drain's record at all (an old parent): no metric
    monkeypatch.setattr(telemetry, "_counters", {"moe_pairs.layer_0": [1.0]})
    assert m.read(ctx) is None
    monkeypatch.delattr(telemetry, "counters")
    assert m.read(ctx) is None


TIER1 = (test_the_looped_cells_files_parse_and_its_metrics_are_listed,
         test_loop_rooflines_count_a_call_a_layer_and_a_pass,
         test_loop_readers_on_hand_made_scopes,
         test_expected_exit_reader_reads_the_programs_counters)
