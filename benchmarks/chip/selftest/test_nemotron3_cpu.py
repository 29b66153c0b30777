"""The harness over the tiny twin of `nemotron3_nano_ep16`
(`tiny/nemotron3_tiny.json` x `tiny/staged_tokens_tiny.json`), on the CPU,
past its look for a chip: blocks of one mixer each (Mamba-2 by the chunked
scan, sigmoid-routed `relu^2` experts beside a shared one, attention with a
group of sixteen split over two interpreted programs), trained on the next id.

Run by path with the rest of this directory (`test_harness_cpu.py` says how
and what a CPU run can and cannot report). What is checked:

- the cell's files parse, the mix meets the configuration's needs, and the
  benchmark lists the cell where its metrics are read;
- a sound run comes out `correct`, says `kernel: flash`, the reference
  prints its own `dt` means, and the program's counters agree with them;
- with the step returning its state unchanged `correct` is false, and so it
  is with the scan's decays taken in bfloat16 at a limit tightened to the
  tiny twin's float32 readings (the chip's limits are read on the chip:
  `bf16_decay_on_chip.py`);
- the fp8 control fails a limit that the bf16 control passes;
- the readers this configuration brought (`TIER1`: no `Trainer` is built, so
  `tests/test_chip_harness.py` collects them in tier-1): `ssm_ms`,
  `ssd_scan_ms` and `moe_shared_ms` on hand-made scopes, `ssm_chunk_carry_min`
  from the program's counters, and nothing (no metric) from a program
  without them, as the four accepted cells' programs are.
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

CELL = "nemotron3_nano_ep16_staged_8k"
NEW = ("ssm_ms", "ssd_scan_ms", "moe_shared_ms", "ssm_chunk_carry_min")


def tiny_run(*, seed=11, trace=False, step_hook=None, seconds=1.0,
             limits=None):
    """As `test_mellum2_cpu.tiny_run`: the tiny cell joins the lists the real
    cell is in."""
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": "tiny_nemotron3", "config": "nemotron3_tiny",
            "traffic": "staged_tokens_tiny", "chips": 1}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell["name"]]
    config = load(HERE, "tiny", "nemotron3_tiny.json")
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
    assert "_t32_h16_kv1_" in dispatch["key"]
    assert said(lines, "traffic")[0]["rows"] == "tokens"
    assert said(lines, "resident_at_window") == [
        {"parameter_sized_extras": []}]
    theirs = said(lines, "moe_route_reference")
    assert len(theirs) == 3            # the compared steps
    # the program's counters of the same steps are the reference's numbers
    from tpudist import telemetry
    ours = telemetry.counters()
    for n, layer in enumerate((0, 2)):
        assert ours[f"ssm_dt_mean.layer_{layer}"][:3] == pytest.approx(
            [r["ssm_dt_mean_by_block"][n] for r in theirs], rel=2e-2)
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert 0.0 <= result["metrics"]["ssm_chunk_carry_min"]["value"] <= 1.0
    assert set(said(lines, "ssm_counters")[0]) == {
        f"{name}.layer_{layer}" for layer in (0, 2)
        for name in ("ssm_chunk_carry_min", "ssm_dt_mean")}
    # a CPU trace carries no names: the device-trace readers leave theirs out
    assert not {"moe_ms", "lm_head_ms", "ssm_ms", "ssd_scan_ms",
                "moe_shared_ms"} & set(result["metrics"])


def test_a_step_that_changes_nothing_is_not_correct():
    assert tiny_run(step_hook=_unchanged)["correct"] is False


def test_decays_in_bfloat16_are_told_from_float32_ones(monkeypatch):
    """The tiny twin in float32 (`--no-use_amp`) against the reference,
    the second gradient limit read over a Mamba block's `A_log` (whose
    gradient reaches the loss through the decays alone): sound, every leaf
    of the first gradient is the reference's to under 1e-6; with the running
    sums of `dt A` and their exps in bfloat16 `A_log`'s is off by 4e-3 to
    8e-3 (and `dt_bias`'s; every other leaf by 1e-5), far over a limit two
    hundred times the sound reading. All leaves together (the head's
    gradient outweighs the rest) would read 4e-6 and pass."""
    import jax.numpy as jnp
    from tpudist.ops import ssd
    config = load(HERE, "tiny", "nemotron3_tiny.json")
    argv = config["trainer_argv"]
    i = argv.index("--use_amp")
    tight = dict(first_grad_rel_diff=2e-4, head_grad_rel_diff=2e-4,
                 head_leaves="['layer_0']['mixer']['A_log']")

    def run():
        from harness import cellrun
        real = cellrun.run_cell

        def float32(**kw):
            cfg = dict(kw["config"], compute_dtype="float32",
                       trainer_argv=argv[:i] + ["--no-use_amp"]
                       + argv[i + 3:])
            return real(**dict(kw, config=cfg))
        monkeypatch.setattr(cellrun, "run_cell", float32)
        return tiny_run(limits=tight)["correct"]
    assert run() is True
    monkeypatch.setattr(ssd, "_running_sum", lambda da: jnp.cumsum(
        da.astype(jnp.bfloat16), axis=-1))
    assert run() is False


def test_fp8_control_is_not_correct():
    import jax
    from harness import check
    config = load(HERE, "tiny", "nemotron3_tiny.json")
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

def test_the_cells_files_parse_and_the_mix_meets_the_configurations_needs():
    from harness import traffic
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron3_nano_ep16", "staged_tokens_8k", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts_held",
                                "vocab_size"]
    config = load(ROOT, entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    mix = load(CHIP, "traffic", cell["traffic"] + ".json")
    assert (mix["rows"], mix["seq_len"]) == ("tokens", 8192)
    # what a token mix asks of its configuration, by name
    assert traffic._need(config, "vocab_size", mix) == 16384
    assert "image_size" not in config and "num_classes" not in config
    assert os.path.exists(os.path.join(
        CHIP, "refs", config["reference_module"] + ".py"))
    assert str(mix["seq_len"]) in config["trainer_argv"]
    assert config["per_chip_batch"] == 2
    # where the cell's metrics are read: the generic ones, the expert
    # layers', the head's and the four this configuration brought; not the
    # shares whose readers count another program's work
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert set(NEW) <= listed and {"moe_ms", "lm_head_ms", "fwd_ms",
                                   "moe_load_max_over_mean"} <= listed
    assert not listed & {"mfu_pct", "attn_stream_roofline",
                         "attn_fused_roofline", "attn_bd_roofline"}
    assert len(listed) == 20
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_img_per_s_chip"
        assert callable(reader(name).read)


MIXER = "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_0/mixer/ssm_mixer/"
MIXER_T = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
           "layer_2/mixer/ssm_mixer/")
EXPERTS = "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/mixer/"


def test_mixer_scope_readers_on_hand_made_scopes(monkeypatch, capsys):
    from harness import scope_reduce
    scopes = scopes_of(
        (1.0, "fwd", MIXER + "ssm_in_proj/in_proj/dot_general"),
        (2.0, "fwd", MIXER + "ssm_conv/mul"),
        (4.0, "fwd", MIXER + "ssm_scan/while/body/mul"),
        (8.0, "bwd", MIXER_T + "ssm_scan/dot_general"),
        (16.0, "bwd", MIXER_T + "ssm_gate_norm/rsqrt"),
        (32.0, "bwd", MIXER_T + "ssm_out_proj/out_proj/dot_general"),
        (64.0, "fwd", MIXER + "split"),              # under no part
        (128.0, "layout_copy", MIXER + "ssm_scan/transpose"),   # not named
        (256.0, "fwd", EXPERTS + "moe_shared/dot_general"),
        (512.0, "bwd", EXPERTS.replace("jvp(", "transpose(jvp(").replace(
            "ward)", "ward))") + "moe_shared/dot_general"),
        (1024.0, "fwd", EXPERTS + "moe_experts/pallas_call"),
        (2048.0, "fwd", EXPERTS.replace("layer_1", "layer_5")
         + "attn_fused/pallas_call"))
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    assert reader("ssm_ms").read({}) == 127.0
    assert reader("ssd_scan_ms").read({}) == 12.0
    assert reader("moe_shared_ms").read({}) == 768.0
    assert reader("moe_ms").read({}) == 1024.0       # the routed path alone
    line = said(capsys.readouterr().out.splitlines(), "ssm_ms")[0]
    assert line == {"ssm_in_proj": 1.0, "ssm_conv": 2.0, "ssm_scan": 12.0,
                    "ssm_gate_norm": 16.0, "ssm_out_proj": 32.0,
                    "other_ms": 64.0, "ssm_ms": 127.0}
    # a step without the scopes (the four accepted cells, the parent
    # commit): no metric, and no error
    plain = scopes_of(
        (3.0, "fwd", "jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/"
         "moe/moe_experts/pallas_call"))
    for found in (plain, None):
        monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: found)
        for name in ("ssm_ms", "ssd_scan_ms", "moe_shared_ms"):
            assert reader(name).read({}) is None, name


def test_carry_reader_reads_the_programs_counters(monkeypatch, capsys):
    from tpudist import telemetry
    m = reader("ssm_chunk_carry_min")
    ctx = {"steps": 4, "config": {"compared_steps": 3}}
    monkeypatch.setattr(telemetry, "_counters", {
        "ssm_chunk_carry_min.layer_0": [0.5, 0.5, 0.5, 0.25, 0.5, 0.5, 0.5],
        "ssm_chunk_carry_min.layer_2": [0.0, 0.75, 0.75, 0.75, 0.125, 0.75,
                                        0.75],
        "ssm_dt_mean.layer_0": [0.03] * 7,
        "moe_pairs.layer_1": [9.0] * 7})
    # the least over the blocks and the window's four steps; the compared
    # steps' 0.0 is not the window's
    assert m.read(ctx) == 0.125
    line = said(capsys.readouterr().out.splitlines(), "ssm_counters")[0]
    assert line["ssm_chunk_carry_min.layer_0"] == {
        "compared": [0.5, 0.5, 0.5], "window_mean": 0.4375,
        "window_min": 0.25}
    assert "ssm_dt_mean.layer_0" in line and "moe_pairs.layer_1" not in line
    # a float32 underflow is a reading, not nothing
    monkeypatch.setattr(telemetry, "_counters", {
        "ssm_chunk_carry_min.layer_0": [0.0] * 7})
    assert m.read(ctx) == 0.0
    # a program without the counters (the accepted cells), or without the
    # drain's record at all (an old parent): no metric
    monkeypatch.setattr(telemetry, "_counters", {"moe_pairs.layer_0": [1.0]})
    assert m.read(ctx) is None
    monkeypatch.delattr(telemetry, "counters")
    assert m.read(ctx) is None


TIER1 = (test_the_cells_files_parse_and_the_mix_meets_the_configurations_needs,
         test_mixer_scope_readers_on_hand_made_scopes,
         test_carry_reader_reads_the_programs_counters)
