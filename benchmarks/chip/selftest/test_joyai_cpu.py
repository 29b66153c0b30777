"""The harness over the tiny twin of `joyai_flash_ep16` (`tiny/joyai_tiny.json`
x `tiny/staged_tokens_tiny.json`), on the CPU, past its look for a chip: one
dense layer and two of experts under latent attention (the kernels
interpreted), a shared SwiGLU expert, the multi-token-prediction module that
shares the embedding and the head, the loss a sum of two cross entropies.

Run by path with the rest of this directory (`test_harness_cpu.py` says how
and what a CPU run can and cannot report). What is checked:

- the cell's files parse, the mix meets the configuration's needs, and the
  benchmark lists the cell where its metrics are read (and not where a
  reader counts one head size or lacks the module's scope);
- a sound run comes out `correct`, says `kernel: flash`, the reference
  prints both losses, and the program's counters agree with them;
- with the step returning its state unchanged `correct` is false (a wrong
  second loss against the same comparison: `tests/test_mtp.py`);
- the fp8 control fails a limit that the bf16 control passes;
- the readers this configuration brought (`TIER1`: no `Trainer` is built, so
  `tests/test_chip_harness.py` collects them in tier-1): the roofline's
  counts at the published shape against numbers worked out by hand, its share
  and `mla_latent_ms` / `mtp_ms` / `mtp_unitemised_ms` on a recorded scope
  table, `mtp_loss_over_main` from the program's counters, and nothing (no
  metric) from a program without them, as the six accepted cells' are.
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

CELL = "joyai_flash_ep16_staged_8k"
NEW = ("attn_mla_roofline", "mla_latent_ms", "mtp_ms", "mtp_loss_over_main",
       "mtp_unitemised_ms")
# the lists the cell's name was appended to, beside the 15 of every cell
JOINED = ("moe_ms", "moe_shared_ms", "moe_load_max_over_mean", "lm_head_ms",
          "attn_mixer_ms", "attn_proj_ms", "attn_qk_rope_ms", "block_norm_ms",
          "dense_mlp_ms")


def tiny_run(*, seed=11, trace=False, step_hook=None, seconds=1.0,
             limits=None):
    """As `test_mellum2_cpu.tiny_run`: the tiny cell joins the lists the real
    cell is in."""
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": "tiny_joyai", "config": "joyai_tiny",
            "traffic": "staged_tokens_tiny", "chips": 1}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"] = m["workloads"] + [cell["name"]]
    config = load(HERE, "tiny", "joyai_tiny.json")
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
    assert "_t32_h4_d24_" in dispatch["key"]      # keys of 16 + 8 columns
    assert said(lines, "traffic")[0]["rows"] == "tokens"
    assert said(lines, "resident_at_window") == [
        {"parameter_sized_extras": []}]
    theirs = said(lines, "moe_route_reference")
    assert len(theirs) == 3            # the compared steps
    # the dense layer, two of experts, the module's block: 16 held each
    assert all(len(r["pairs_by_block_and_held_expert"]) == 4 for r in theirs)
    # the program's counters of the same steps are the reference's numbers
    ours = said(lines, "mtp_counters")[0]
    for name in ("mtp_loss", "lm_loss_main"):
        assert ours[name]["compared"] == pytest.approx(
            [r[name] for r in theirs], rel=2e-3)
    assert 0.8 <= result["metrics"]["mtp_loss_over_main"]["value"] <= 1.25
    # a CPU trace carries no names: the device-trace readers leave theirs out
    assert not {"attn_mla_roofline", "mla_latent_ms", "mtp_ms",
                "mtp_unitemised_ms", "lm_head_ms", "dense_mlp_ms"} & set(
                    result["metrics"])


def test_a_step_that_changes_nothing_is_not_correct():
    assert tiny_run(step_hook=_unchanged)["correct"] is False


def test_fp8_control_is_not_correct():
    import jax
    from harness import check
    config = load(HERE, "tiny", "joyai_tiny.json")
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

def test_the_latent_cells_files_parse_and_its_metrics_are_listed():
    from harness import traffic
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai_flash_ep16", "staged_tokens_8k", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    assert len(bench["workloads"]) == 7
    assert not [w for w in bench["workloads"] if w["chips"] != 1]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert bench["configs"][-1] is entry and len(bench["configs"]) == 7
    assert entry["reduced"] == ["num_hidden_layers", "num_experts_held",
                                "vocab_size"]
    config = load(ROOT, entry["file"])
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    mix = load(CHIP, "traffic", cell["traffic"] + ".json")
    assert (mix["rows"], mix["seq_len"]) == ("tokens", 8192)
    assert traffic._need(config, "vocab_size", mix) == 16160
    assert os.path.exists(os.path.join(
        CHIP, "refs", config["reference_module"] + ".py"))
    assert str(mix["seq_len"]) in config["trainer_argv"]
    assert config["per_chip_batch"] == 2
    # where the cell's metrics are read: the 15 of every cell, the nine it
    # joined and the five this configuration brought; not the rooflines
    # that count one head size or a whole head rotated, nor the unitemised
    # readers whose lists lack the module's leaf, nor `mfu_pct`
    listed = [m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]]
    assert set(NEW) | set(JOINED) <= set(listed) and len(listed) == 29
    assert not set(listed) & {
        "attn_stream_roofline", "attn_qk_rope_roofline", "attn_loop_roofline",
        "attn_fused_roofline", "attn_bd_roofline", "step_unitemised_ms",
        "loop_unitemised_ms", "mfu_pct"}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL, m["name"]   # appended
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(NEW)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_img_per_s_chip"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(reader(name).read)


def _config():
    return load(CHIP, "configs", "joyai_flash_ep16.json")


def _peak():
    return load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]


def test_the_latent_roofline_counts_what_any_implementation_must_do():
    """Two rows of 8,192 positions, 32 heads, keys of 128 + 64 against values
    of 128, bfloat16, five layers and the module: six forward and six
    backward calls; every number below worked out by hand from the layer's
    equations."""
    from harness import roofline
    m = reader("attn_mla_roofline")
    shape = (2, 8192, 32, 128, 64, 128, 2)
    pairs = 8192 * 8193 // 2
    assert m.pairs(8192) == pairs == 33558528
    f_ops, f_bytes = m.forward_call(*shape)
    b_ops, b_bytes = m.backward_call(*shape)
    # S over the true 192 columns and P V over 128; the backward's seven
    # products: S twice, dQ and dK over 192; dP twice and dV over 128
    assert f_ops == 2 * 2 * 32 * pairs * (192 + 128) == 1374557306880
    assert b_ops == 2 * 2 * 32 * pairs * (4 * 192 + 3 * 128) == 4948406304768
    a_head = 2 * 8192 * 32 * 2             # bytes a column of [B, T, H]
    once = 2 * 8192 * 64 * 2               # the rotated key, ONE head
    stat = 4 * 2 * 32 * 8192               # a float32 [B, H, T]
    # q 192, k_nope 128, v 128, o 128; the rotated key once; the logsumexp
    assert f_bytes == a_head * (192 + 128 + 128 + 128) + once + stat \
        == 608174080
    # q, k_nope, v, o, dO in; dQ, dK_nope, dV out; the rotated key in and
    # its gradient out, once each; the logsumexp and delta
    assert b_bytes == a_head * (192 + 128 + 128 + 128 + 128
                                + 192 + 128 + 128) + 2 * once + 2 * stat \
        == 1216348160
    # a rotated key fetched a head would be 31 x `once` more a call: not
    # in the least, whatever a kernel does
    calls = [(6 * f_ops, 6 * f_bytes), (6 * b_ops, 6 * b_bytes)]
    assert sum(ops for ops, _ in calls) == pytest.approx(37.94e12, rel=1e-3)
    least, bound_by = roofline.least_ms(calls, _peak())
    assert bound_by == ["compute", "compute"]
    assert least == pytest.approx(192.58, abs=0.01)       # ms a step


_FWD = "jit(step)/jvp(tpudist_forward)/MoEDecoder/"
_BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/"
        "jvp(tpudist_forward)/MoEDecoder/checkpoint/")
_MIX = "layer_1/self_attention/attn_mixer/"
_MTP = "mtp_module/mtp/"
_MTP_BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/mtp_module/"
            "mtp/jvp(tpudist_forward)/MoEDecoder/mtp_module/mtp/checkpoint/")


def _latent_scopes():
    """A scope table as `scope_reduce.step_scopes` hands it, the op_names
    recorded from `joyai_tiny`'s compiled step (`tests/test_scopes.py` holds
    the program to them)."""
    return scopes_of(
        (1.0, "fwd", _FWD + _MIX + "attn_qkv_proj/mla_down/q_a_proj/"
         "dot_general"),
        (2.0, "bwd", _BWD + _MIX + "attn_qkv_proj/mla_down/kv_a_proj/"
         "transpose"),
        (4.0, "bwd", _BWD + "rematted_computation/" + _MIX
         + "attn_qk_norm_rope/mla_latent_norm/q_a_norm/mul"),
        (8.0, "fwd", _FWD + _MIX + "attn_qkv_proj/mla_up/kv_b_proj/"
         "dot_general"),
        (16.0, "fwd", _FWD + _MIX + "attn_qk_norm_rope/concatenate"),
        (32.0, "fwd", _FWD + _MIX + "attn_fused/pallas_call"),
        (64.0, "bwd", _BWD + _MIX + "attn_fused/pallas_call"),
        (128.0, "layout_copy", _FWD + _MIX + "attn_fused/x"),   # not named
        (256.0, "fwd", _FWD + _MTP + "mtp_merge/eh_proj/dot_general"),
        (512.0, "bwd", _MTP_BWD + "block/self_attention/attn_mixer/"
         "attn_fused/pallas_call"),
        (1024.0, "bwd", _MTP_BWD + "block/self_attention/attn_mixer/"
         "attn_qkv_proj/mla_up/q_b_proj/transpose"),
        (2048.0, "fwd", _FWD + "mtp_module/while/body/closed_call/lm_head/"
         "dot_general"),
        (4096.0, "fwd", _FWD + "mtp_module/jit(_roll_static)/concatenate"),
        (8192.0, "fwd", _FWD + "layer_0/mlp/dense_mlp/gate_proj/dot_general"),
        (16384.0, "opt", "jit(step)/tpudist_optimizer/mul"),
        (32768.0, "fwd", _FWD + "layer_1/moe/reduce_max"))


def test_the_new_readers_on_a_recorded_scope_table(monkeypatch, capsys):
    from harness import scope_reduce
    scopes = dict(_latent_scopes(), busy_step_ms=65535.0)
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    ctx = {"config": _config(), "batch": 2, "chips": 1, "peak": _peak(),
           "attention_kernel": "flash", "step_hlo": None}
    # the three inner scopes, in the trunk and in the module's block alike
    assert reader("mla_latent_ms").read(ctx) == 1.0 + 2.0 + 4.0 + 8.0 + 1024.0
    # everything under the module: its merge, its block, its head pass, and
    # what lies under it and under no part
    assert reader("mtp_ms").read(ctx) == (
        256.0 + 512.0 + 1024.0 + 2048.0 + 4096.0)
    # under no part: the module's own remainder and a block's counter
    assert reader("mtp_unitemised_ms").read(ctx) == 4096.0 + 32768.0
    # the older readers' lists lack the module's leaf: the merge reads as left
    assert reader("loop_unitemised_ms").read(ctx) == (
        4096.0 + 32768.0 + 256.0)
    out = capsys.readouterr().out.splitlines()
    assert said(out, "mla_latent_ms")[0] == {
        "mla_down": 3.0, "mla_latent_norm": 4.0, "mla_up": 1032.0,
        "mla_latent_ms": 1039.0}
    assert said(out, "mtp_unitemised")[0]["operations"] == 2
    # the accepted readers read this table unedited: latent attention's
    # operations lie under the four attention parts that exist
    assert reader("attn_proj_ms").read(ctx) == 1.0 + 2.0 + 8.0 + 1024.0
    assert reader("attn_qk_rope_ms").read(ctx) == 4.0 + 16.0
    assert reader("dense_mlp_ms").read(ctx) == 8192.0
    # the kernels' share: 192.58 ms at the peaks over the calls' 608 ms, the
    # unnamed copy behind them printed and not counted
    assert reader("attn_mla_roofline").read(ctx) == pytest.approx(
        100.0 * 192.58 / (32.0 + 64.0 + 512.0), abs=0.01)
    line = said(capsys.readouterr().out.splitlines(), "roofline")[-1]
    assert line["metric"] == "attn_mla_roofline"
    assert line["layout_copy_behind_ms"] == 128.0
    assert line["bound_by"] == ["compute", "compute"]
    # the XLA attention path, or a configuration without latent attention
    # (the accepted token cells'): no share
    for other in (dict(ctx, attention_kernel="xla"),
                  dict(ctx, config=load(CHIP, "configs",
                                        "mellum2_12b_ep4.json")),
                  dict(ctx, config=load(CHIP, "configs",
                                        "ouro_2_6b_pp8.json"))):
        assert reader("attn_mla_roofline").read(other) is None
    # a step without the scopes (the accepted cells, the parent commit), or
    # no scopes at all: no metric, and no error
    plain = scopes_of(
        (3.0, "fwd", _FWD + "layer_1/block_norm/input_norm/rsqrt"),
        (5.0, "fwd", _FWD + "layer_1/moe/moe_experts/pallas_call"))
    for found in (plain, None):
        monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: found)
        for name in NEW:
            if name != "mtp_loss_over_main":
                assert reader(name).read(ctx) is None, name


def test_the_two_losses_reader_reads_the_programs_counters(monkeypatch,
                                                           capsys):
    from tpudist import telemetry
    m = reader("mtp_loss_over_main")
    ctx = {"steps": 2, "config": {"compared_steps": 3}}
    monkeypatch.setattr(telemetry, "_counters", {
        "mtp_loss": [9.0, 9.0, 9.0, 8.0, 6.0],
        "lm_loss_main": [9.0, 9.0, 9.0, 8.0, 8.0],
        "moe_pairs.mtp": [9.0] * 5})
    assert m.read(ctx) == (1.0 + 0.75) / 2          # the window's two steps
    line = said(capsys.readouterr().out.splitlines(), "mtp_counters")[0]
    assert line == {"mtp_loss": {"compared": [9.0] * 3, "window_mean": 7.0},
                    "lm_loss_main": {"compared": [9.0] * 3,
                                     "window_mean": 8.0}}
    # a program that takes no second loss (the accepted cells), or without
    # the drain's record at all (an old parent): no metric
    monkeypatch.setattr(telemetry, "_counters", {"lm_loss_main": [1.0]})
    assert m.read(ctx) is None
    monkeypatch.setattr(telemetry, "_counters", {"moe_pairs.layer_0": [1.0]})
    assert m.read(ctx) is None
    monkeypatch.delattr(telemetry, "counters")
    assert m.read(ctx) is None


TIER1 = (test_the_latent_cells_files_parse_and_its_metrics_are_listed,
         test_the_latent_roofline_counts_what_any_implementation_must_do,
         test_the_new_readers_on_a_recorded_scope_table,
         test_the_two_losses_reader_reads_the_programs_counters)
