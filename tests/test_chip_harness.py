"""The chip benchmark's feed, refusals, bounded readings and roofline
arithmetic, collected in tier-1: the cases of
`benchmarks/chip/selftest/test_feed_cpu.py` (run by path there, in seconds,
with no `Trainer`), loaded from that file so that there is one copy of them,
and the readers' cases of `selftest/test_sdar_cpu.py` (its `TIER1`: the
count of the block-diffusion mask's pairs, a share above 100, the plan's
fill, the noise's scope) and of `selftest/test_nemotron3_cpu.py` (the cell's
files against the mix's needs, the Mamba mixers' and the shared expert's
scopes, the carry counter, and nothing from a program without them) and of
`selftest/test_ssd_roofline_cpu.py` (the scan's counts at the cell's shape,
its share on hand-made scopes, a share above 100) and of
`selftest/test_qk_rope_roofline_cpu.py` (the q / k pass's counts at both
cells' shapes, its share on hand-made scopes, nothing from the hybrid's) and
of `selftest/test_ssm_conv_roofline_cpu.py` (the convolution pass's counts at
the hybrid cell's shape, its share and its time on hand-made scopes) and
of `selftest/test_ouro_cpu.py` (the looped cell's files and lists, the two
looped rooflines' counts a layer AND a pass, the dense feed-forward's, the
exit's and the loop's scopes, the exit counter) and of
`selftest/test_joyai_cpu.py` (the latent-attention cell's files and lists,
`attn_mla_roofline`'s counts at the published shape, the new scopes' readers
on a recorded scope table, the two losses' counter) and of
`selftest/test_mla_qk_rope_roofline_cpu.py` (the latent block's norms' and
rotation's counts at the cell's shape against a hand count, their share on
hand-made scopes).
The rest of `benchmarks/chip/selftest/` builds trainers for minutes and
stays run by path. Below them: what the configurations' `trainer_argv` pins
against the program's defaults."""

import dataclasses
import glob
import importlib.util
import json
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks", "chip", "selftest", "test_feed_cpu.py")
_spec = importlib.util.spec_from_file_location("chipbench_test_feed_cpu",
                                               _PATH)
_feed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_feed)

# every test of the file, under its own name (parametrised cases and all)
globals().update({name: value for name, value in vars(_feed).items()
                  if name.startswith("test_")})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_sdar_cpu", os.path.join(os.path.dirname(_PATH),
                                            "test_sdar_cpu.py"))
_sdar = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_sdar)
globals().update({test.__name__: test for test in _sdar.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_nemotron3_cpu", os.path.join(os.path.dirname(_PATH),
                                                 "test_nemotron3_cpu.py"))
_nemotron3 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_nemotron3)
globals().update({test.__name__: test for test in _nemotron3.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_ssd_roofline_cpu", os.path.join(
        os.path.dirname(_PATH), "test_ssd_roofline_cpu.py"))
_ssd_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ssd_roofline)
globals().update({test.__name__: test for test in _ssd_roofline.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_qk_rope_roofline_cpu", os.path.join(
        os.path.dirname(_PATH), "test_qk_rope_roofline_cpu.py"))
_qk_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_qk_roofline)
globals().update({test.__name__: test for test in _qk_roofline.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_ssm_conv_roofline_cpu", os.path.join(
        os.path.dirname(_PATH), "test_ssm_conv_roofline_cpu.py"))
_conv_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conv_roofline)
globals().update({test.__name__: test for test in _conv_roofline.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_ouro_cpu", os.path.join(os.path.dirname(_PATH),
                                            "test_ouro_cpu.py"))
_ouro = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ouro)
globals().update({test.__name__: test for test in _ouro.TIER1})

_spec = importlib.util.spec_from_file_location(
    "chipbench_test_joyai_cpu", os.path.join(os.path.dirname(_PATH),
                                             "test_joyai_cpu.py"))
_joyai = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_joyai)
globals().update({test.__name__: test for test in _joyai.TIER1})


_spec = importlib.util.spec_from_file_location(
    "chipbench_test_mla_qk_rope_roofline_cpu", os.path.join(
        os.path.dirname(_PATH), "test_mla_qk_rope_roofline_cpu.py"))
_mla_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mla_roofline)
globals().update({test.__name__: test for test in _mla_roofline.TIER1})


def _before_pr_45(bench):
    """`BENCHMARK.json` without the one per-layer entry PR 45 appended,
    `mla_qk_rope_roofline` (`test_mla_qk_rope_roofline_reader` holds its
    fields and its place, last)."""
    assert bench["per_layer"][-1]["name"] == "mla_qk_rope_roofline"
    assert bench["per_layer"][-1]["workloads"] == [_joyai.CELL]
    del bench["per_layer"][-1]
    return bench


def test_the_latent_cells_files_parse_and_its_metrics_are_listed(
        monkeypatch):
    """`selftest/test_joyai_cpu.py`'s case of the same name, which counts
    the per-layer metrics that list the latent cell (29) and holds PR 44's
    five to the end of the list: since PR 45 `mla_qk_rope_roofline` lists
    the cell and lies behind them (new entries go last). That file is the
    benchmark's and is not edited by a `perf_opt` PR (run by path those two
    lines fail: PERF.md section 7); here the case runs whole on the list
    without the entry, which `_before_pr_45` holds to the end of the
    list."""
    bench = _before_pr_45(_joyai.load(_joyai.ROOT, "BENCHMARK.json"))
    load = _joyai.load
    monkeypatch.setattr(
        _joyai, "load",
        lambda *path: bench if path[-1] == "BENCHMARK.json" else load(*path))
    _joyai.TIER1[0]()


def _before_pr_44(bench):
    """`BENCHMARK.json` without what PR 45 (above) and PR 44 appended: the
    latter's configuration, its cell (last of both lists), its five
    per-layer entries (last five) and its cell's name at the end of the
    lists it joined
    (`selftest/test_joyai_cpu.py` holds all of that to the file). The older
    cells' own cases, which count entries and hold theirs to the end of a
    list, run on this."""
    bench = _before_pr_45(bench)
    assert bench["workloads"][-1]["name"] == _joyai.CELL
    assert bench["configs"][-1]["name"] == "joyai_flash_ep16"
    assert [m["name"] for m in bench["per_layer"][-5:]] == list(_joyai.NEW)
    del bench["workloads"][-1], bench["configs"][-1], bench["per_layer"][-5:]
    for m in bench["per_layer"]:
        if _joyai.CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == _joyai.CELL
            del m["workloads"][-1]
    return bench


def test_the_cells_files_parse_and_the_mix_meets_the_configurations_needs(
        monkeypatch):
    """`selftest/test_nemotron3_cpu.py`'s case of the same name, which
    counts the per-layer metrics that list the hybrid's cell (20): since PR
    39 `ssd_scan_roofline` lists it too, since PR 43 `ssm_conv_ms` and
    `ssm_conv_roofline`. That file is the benchmark's and is not edited by a
    `perf_opt` PR (run by path its count fails: PERF.md section 7); here
    the case runs whole on the list without the new entries, and the new
    entries are held beside it."""
    bench = _before_pr_44(_nemotron3.load(_nemotron3.ROOT, "BENCHMARK.json"))
    names = ("ssd_scan_roofline", "ssm_conv_ms", "ssm_conv_roofline")
    new = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in new] == list(names)
    assert [m["workloads"] for m in new] == [[_nemotron3.CELL]] * 3
    for m in new:
        assert m["moves"] == "train_img_per_s_chip"
        assert callable(_nemotron3.reader(m["name"]).read)
        bench["per_layer"].remove(m)
    # PR 40's seven (held below, `test_the_tracing_entries_are_listed`): six
    # list the cell
    later = [m for m in bench["per_layer"] if m["name"] in _TRACING]
    assert sum(_nemotron3.CELL in m["workloads"] for m in later) == 6
    bench["per_layer"] = [m for m in bench["per_layer"] if m not in later]
    load = _nemotron3.load
    monkeypatch.setattr(
        _nemotron3, "load",
        lambda *path: bench if path[-1] == "BENCHMARK.json" else load(*path))
    _nemotron3.TIER1[0]()


def test_the_looped_cells_files_parse_and_its_metrics_are_listed(
        monkeypatch):
    """`selftest/test_ouro_cpu.py`'s case of the same name, which holds PR
    42's seven entries to the end of the per-layer list: since PR 43
    `ssm_conv_ms` and `ssm_conv_roofline` lie behind them (new entries go
    last). That file is the benchmark's and is not edited by a `perf_opt`
    PR (run by path that line fails: PERF.md section 7); here the case runs
    whole on the list without the two, which
    `test_the_cells_files_parse_and_the_mix_meets_the_configurations_needs`
    holds to the end of the list and `test_ssm_conv_readers` to their
    fields."""
    bench = _before_pr_44(_ouro.load(_ouro.ROOT, "BENCHMARK.json"))
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "ssm_conv_ms", "ssm_conv_roofline"]
    del bench["per_layer"][-2:]
    load = _ouro.load
    monkeypatch.setattr(
        _ouro, "load",
        lambda *path: bench if path[-1] == "BENCHMARK.json" else load(*path))
    _ouro.TIER1[0]()


def test_attn_bd_fill_reader(monkeypatch, capsys):
    """`selftest/test_sdar_cpu.py`'s case of the same name, restated here
    since PR 37: that file holds the plan's fill to whole tiles of `block_q`
    x `block_k` (80.04), and since `KERNEL_REV` 7 the program runs, and its
    plan states, the half of an edge tile that the mask reaches (88.93).
    The file is the benchmark's and is not edited by a `perf_opt` PR (run
    by path its case fails: PERF.md section 7); every other statement of it
    is kept."""
    m = _sdar.reader("attn_bd_fill_pct")
    ctx = _sdar._ctx()
    value = m.read(ctx)
    plan = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert plan["mask"] == "block_diffusion" and plan["block_length"] == 4
    assert value == pytest.approx(100.0 * plan["band_fill"])
    # scores the mask allows over scores the forward's programs run: 128
    # whole tiles and 32 squares of 512 x 512 (the diagonal's sixteen, and
    # the near half of sixteen last tiles of a clean prefix)
    run = 128 * plan["block_q"] * plan["block_k"] + 32 * plan["block_q"] ** 2
    assert value == pytest.approx(100.0 * 67141632 / run, abs=0.01)
    assert 88.9 <= value <= 100.0
    assert m.read(dict(ctx, attention_kernel="xla")) is None
    assert m.read(dict(ctx, config=_sdar.load(
        _sdar.CHIP, "configs", "mellum2_12b_ep4.json"))) is None
    # a program whose plan knows no such mask (PR 36's parent commit)
    from tpudist.ops import attention_dispatch
    monkeypatch.setattr(
        attention_dispatch, "program",
        lambda seq, heads, head_dim, dtype, *, kv_heads=None, causal=False,
        window=None: {})
    assert m.read(ctx) is None


# -- PR 40: the readers of the blocks' leaf scopes, of the loop's activities
# and of the compile listener ------------------------------------------------
_TOKENS = ["mellum2_12b_ep4_staged_8k", "sdar_30b_ep8_staged_8k",
           "nemotron3_nano_ep16_staged_8k"]
_ALL = ["resnet18_staged", "vit_b16_staged"] + _TOKENS
_LOOPED = [_ouro.CELL]          # PR 42's cell, behind the accepted ones
_LATENT = [_joyai.CELL]         # PR 44's, behind that
_ATTN = "step program: attention blocks"
# name -> (unit, source, layer, workloads)
_TRACING = {
    "attn_mixer_ms": ("ms", "device_trace", _ATTN,
                      _TOKENS + _LOOPED + _LATENT),
    "attn_proj_ms": ("ms", "device_trace", _ATTN,
                     _TOKENS + _LOOPED + _LATENT),
    "attn_qk_rope_ms": ("ms", "device_trace", _ATTN,
                        _TOKENS[:2] + _LOOPED + _LATENT),
    "block_norm_ms": ("ms", "device_trace", "step program",
                      _TOKENS + _LOOPED + _LATENT),
    # its list of parts is the benchmark's copy and lacks the looped
    # cell's three and the latent cell's one: those cells list
    # `loop_unitemised_ms` and `mtp_unitemised_ms`
    "step_unitemised_ms": ("ms", "device_trace", "step program", _TOKENS),
    "loop_host_max_ms": ("ms", "program_span", "trainer loop",
                         _ALL + _LOOPED + _LATENT),
    "window_compile_count": ("count", "program_counter", "trainer loop",
                             _ALL + _LOOPED + _LATENT),
}
_DEVICE_READERS = [n for n, v in _TRACING.items() if v[1] == "device_trace"]
reader, said, scopes_of = (_nemotron3.reader, _nemotron3.said,
                           _nemotron3.scopes_of)


@pytest.mark.parametrize("name", _TRACING)
def test_the_tracing_entries_are_listed(name):
    """`BENCHMARK.json` lists each of the seven once, behind what PR 39
    had (PR 41's `attn_qk_rope_roofline` follows them, then PR 42's seven,
    PR 43's two, PR 44's five and PR 45's one), with the cells where its
    reader finds something to read."""
    bench = _nemotron3.load(_nemotron3.ROOT, "BENCHMARK.json")
    assert [m["name"] for m in bench["per_layer"][-23:]] == list(
        _TRACING) + ["attn_qk_rope_roofline"] + list(_ouro.NEW) + [
            "ssm_conv_ms", "ssm_conv_roofline"] + list(_joyai.NEW) + [
                "mla_qk_rope_roofline"]
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    unit, source, layer, workloads = _TRACING[name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": source, "layer": layer,
                     "moves": "train_img_per_s_chip", "workloads": workloads}
    assert callable(reader(name).read)


def test_step_unitemised_holds_the_programs_list():
    from tpudist.obs import scopes
    # the benchmark's copy is the list as PR 41 left it; PR 42's three
    # scopes lie behind it, in the program's list and in the new reader's
    assert reader("step_unitemised_ms").STEP_PARTS == scopes.STEP_PARTS[:22]
    assert scopes.STEP_PARTS[22:] == (scopes.DENSE_MLP, scopes.LOOP_EXIT,
                                      scopes.LOOP_CARRY, scopes.MTP_MERGE)
    # PR 44's leaf lies behind those, in the program's list and in the
    # reader its cell lists
    assert reader("loop_unitemised_ms").STEP_PARTS == scopes.STEP_PARTS[:25]
    assert reader("mtp_unitemised_ms").STEP_PARTS == scopes.STEP_PARTS
    assert reader("loop_host_max_ms").ACTIVITIES == scopes.LOOP_ACTIVITIES
    assert reader("attn_mixer_ms").PARTS == (
        scopes.ATTN_QKV_PROJ, scopes.ATTN_QK_NORM_ROPE, scopes.ATTN_FUSED,
        scopes.ATTN_OUT_PROJ)
    from tpudist import telemetry
    assert reader("window_compile_count").COMPILE == telemetry.COMPILE_EVENT


_FWD = "jit(step)/jvp(tpudist_forward)/MoEDecoder/"
_BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/"
        "jvp(tpudist_forward)/MoEDecoder/checkpoint/")
_MIX = "layer_1/self_attention/attn_mixer/"


def _block_scopes():
    return scopes_of(
        (1.0, "fwd", _FWD + _MIX + "attn_qkv_proj/q_proj/dot_general"),
        (2.0, "bwd", _BWD + "rematted_computation/" + _MIX
         + "attn_qkv_proj/k_proj/dot_general"),
        (4.0, "fwd", _FWD + _MIX + "attn_qk_norm_rope/q_norm/rsqrt"),
        (8.0, "bwd", _BWD + _MIX + "attn_qk_norm_rope/mul"),
        (16.0, "fwd", _FWD + _MIX + "attn_fused/pallas_call"),
        (32.0, "bwd", _BWD + _MIX + "attn_out_proj/o_proj/dot_general"),
        (64.0, "fwd", _FWD + _MIX + "transpose"),        # under no part
        (128.0, "layout_copy", _FWD + _MIX + "attn_fused/x"),   # not named
        (256.0, "fwd", _FWD + "layer_1/block_norm/input_norm/rsqrt"),
        (512.0, "bwd", _BWD + "layer_1/block_norm/add_any"),
        (1024.0, "fwd", _FWD + "layer_1/moe/moe_experts/pallas_call"),
        (2048.0, "fwd", _FWD + "while/body/dynamic_slice"),    # unitemised
        (4096.0, "opt", "jit(step)/tpudist_optimizer/mul"),
        (8192.0, "bwd", "jit(step)/transpose(jvp(tpudist_forward))/"
         "MoEDecoder/while/body/closed_call/tpudist_loss/reduce_sum"))


_HAND_MADE = {"attn_mixer_ms": 127.0, "attn_proj_ms": 35.0,
              "attn_qk_rope_ms": 12.0, "block_norm_ms": 768.0,
              "step_unitemised_ms": 2112.0}


@pytest.mark.parametrize("name", _DEVICE_READERS)
def test_block_scope_readers_on_hand_made_scopes(monkeypatch, capsys, name):
    from harness import scope_reduce
    scopes = dict(_block_scopes(), busy_step_ms=16383.0)
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    config = _nemotron3.load(_nemotron3.CHIP, "configs",
                             "mellum2_12b_ep4.json")
    ctx = {"config": config, "batch": 2, "chips": 1,
           "peak": {"flops_per_s_bf16": 197e12}}
    assert reader(name).read(ctx) == _HAND_MADE[name]
    out = capsys.readouterr().out.splitlines()
    if name == "attn_mixer_ms":
        assert said(out, "attn_mixer_ms")[0] == {
            "attn_qkv_proj": 3.0, "attn_qk_norm_rope": 12.0,
            "attn_fused": 16.0, "attn_out_proj": 32.0, "other_ms": 64.0,
            "attn_mixer_ms": 127.0}
    elif name == "attn_proj_ms":
        line = said(out, "attn_proj_ms")[0]
        assert (line["attn_qkv_proj"], line["attn_out_proj"]) == (3.0, 32.0)
        # four layers, forward + rematerialised forward + backward:
        # 2 x 16,384 x 2,304 x 9,216 = 0.70 TFLOP a layer forward
        assert line["products_flops"] == 16 * 2 * 16384 * 2304 * 9216
        assert line["products_least_ms"] == pytest.approx(56.5, abs=0.1)
    elif name == "step_unitemised_ms":
        line = said(out, "step_unitemised")[0]
        assert line["operations"] == 2
        assert [row[1] for row in line["longest"]] == [2048.0, 64.0]
        assert line["longest"][0][2].endswith("while/body/dynamic_slice")
    # a step without the scopes (a classifier, the parent commit's
    # decoder), or no scopes at all: no metric, and no error
    older = scopes_of(
        (3.0, "fwd", _FWD + "layer_1/self_attention/q_proj/dot_general"),
        (5.0, "fwd", _FWD + "layer_1/self_attention/attn_fused/pallas_call"),
        (7.0, "fwd", _FWD + "layer_1/input_norm/rsqrt"))
    for found in (older, None):
        monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: found)
        assert reader(name).read(ctx) is None


@pytest.mark.parametrize("cell,flops", [
    ("mellum2_12b_ep4", 16 * 2 * 16384 * 2304 * 9216),
    ("sdar_30b_ep8", 16 * 2 * 32768 * 2048 * 9216),
    ("nemotron3_nano_ep16", 4 * 2 * 16384 * 2688 * 8704)])
def test_attn_proj_counts_at_the_cells_shapes(cell, flops):
    """The four products of every attention block over the step's
    positions, four times (the forward, the rematerialised forward, about
    twice transposed): 11.1, 19.8 and 3.07 TFLOP a step."""
    config = _nemotron3.load(_nemotron3.CHIP, "configs", cell + ".json")
    assert reader("attn_proj_ms").products_flops(config, 2) == flops


@pytest.mark.parametrize("name", _DEVICE_READERS)
def test_block_scope_readers_on_a_recorded_chip_trace(monkeypatch, name):
    """`selftest/recorded_scopes.json.gz` is a `resnet18_staged` step of a
    program without the scopes: left out, not wrong."""
    import gzip
    from harness import scope_reduce
    with gzip.open(os.path.join(os.path.dirname(_PATH),
                                "recorded_scopes.json.gz"), "rt") as f:
        rec = json.load(f)
    found = scope_reduce.by_scope(rec, rec["scopes"], rec["module"])
    assert found["steps"] == 3 and len(found["ops"]) > 100
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: found)
    assert reader(name).read({"config": {}, "batch": 1200, "chips": 1,
                              "peak": {"flops_per_s_bf16": 197e12}}) is None


def test_loop_host_reader_on_hand_made_rows(capsys):
    m = reader("loop_host_max_ms")
    ms = 1_000_000
    rows = [["bench.window", 10 * ms, 1000 * ms],
            ["tpudist.loop_hooks", 5 * ms, 900 * ms],    # before the window
            ["tpudist.loop_prologue", 11 * ms, 3 * ms],
            ["tpudist.loop_hooks", 20 * ms, 1 * ms],
            ["tpudist.loop_hooks", 40 * ms, 301 * ms],
            ["tpudist.loop_meters", 400 * ms, 2 * ms],
            ["tpudist.loop_log", 410 * ms, 5 * ms],
            ["tpudist.loop_epoch_end", 1005 * ms, 50 * ms]]   # cut at its end
    by_name, value = m.longest(rows)
    assert value == 301.0
    assert by_name["tpudist.loop_hooks"] == [2, 151.0, 301.0]
    assert by_name["tpudist.loop_epoch_end"] == [1, 5.0, 5.0]
    assert list(by_name) == sorted(m.ACTIVITIES)
    # no window span: every span of theirs counts
    assert m.longest(rows[1:])[1] == 900.0


def test_loop_host_reader_on_a_recorded_chip_trace():
    """`selftest/recorded_trace.json.gz` is a window of a program without
    the five spans: left out, not wrong."""
    import gzip
    with gzip.open(os.path.join(os.path.dirname(_PATH),
                                "recorded_trace.json.gz"), "rt") as f:
        rec = json.load(f)
    assert any(r[0] == "tpudist.prefetch" for r in rec["host"])
    assert reader("loop_host_max_ms").longest(rec["host"]) == (None, None)


def test_compile_count_reader_reads_the_programs_listener(monkeypatch,
                                                          capsys):
    from tpudist import telemetry
    m = reader("window_compile_count")
    kept = [
        {"event": telemetry.COMPILE_EVENT, "seconds": 20.0, "t_end": 90.0,
         "step": 0},                                    # before the window
        {"event": telemetry.CACHE_READ_EVENT, "seconds": 0.5, "t_end": 120.0,
         "step": 7},                # inside a compile request: not counted
        {"event": telemetry.COMPILE_EVENT, "seconds": 0.6, "t_end": 120.1,
         "step": 7},
        {"event": telemetry.COMPILE_EVENT, "seconds": 3.0, "t_end": 140.0,
         "step": None}]                                  # the reference's
    monkeypatch.setattr(telemetry, "_compile_events", kept)
    ctx = {"t_open": 100.0, "t_close": 130.0}
    assert m.read(ctx) == 1
    line = said(capsys.readouterr().out.splitlines(), "compiles")[0]
    assert line["events"] == 4 and line["window_compile_count"] == 1
    assert line["by_step"]["7"] == {"cache_read": 1, "cache_read_s": 0.5,
                                    "compile": 1, "compile_s": 0.6}
    assert [ev["step"] for ev in line["in_window"]] == [7, 7]
    assert line["in_window"][1]["t_end"] == pytest.approx(20.1)
    # a window that built nothing reads 0, not nothing
    assert m.read({"t_open": 200.0, "t_close": 230.0}) == 0
    # a program without the listener (the parent commit): no metric
    monkeypatch.delattr(telemetry, "compile_events")
    assert m.read(ctx) is None


# -- the cells measure the default program -----------------------------------
# Every selector a configuration's `trainer_argv` writes out, read from the
# files under `benchmarks/chip/configs/` (none is edited here).

_CONFIGS = sorted(glob.glob(os.path.join(os.path.dirname(_PATH), os.pardir,
                                         "configs", "*.json")))
_TAKES_VALUE = ("--fused-bn", "--flash", "--compress-grads", "--zero",
                "--accum-steps")
_SELECTORS = _TAKES_VALUE + (
    "--remat", "--no-remat", "--no-telemetry", "--no-doctor", "--no-blackbox",
    "--device_prefetch", "--async_drain")
# The pins that are NOT a default written out: (configuration, selector) ->
# the one field that differs, and why the cell needs it.
_NOT_DEFAULTS = {
    ("mellum2_12b_ep4", "--flash"):
        ("flash", "a decoder has no start-up probe, `auto` is XLA's path, and "
                  "XLA's scores at 8,192 tokens would be 17 GB"),
    ("mellum2_12b_ep4", "--remat"):
        ("remat", "15.8 GiB a step without it, 11.8 with: only so it fits"),
    ("resnet18_ref", "--flash"):
        ("flash", "the field differs and the program does not: resnet18 has "
                  "no attention (its model has no `flash` field)"),
    ("sdar_30b_ep8", "--flash"):
        ("flash", "a decoder has no start-up probe, `auto` is XLA's path, and "
                  "XLA's scores over the doubled row of 16,384 positions "
                  "would be 34 GB a row"),
    ("sdar_30b_ep8", "--remat"):
        ("remat", "32,768 positions a step through every layer: only "
                  "rematerialised do the temporaries fit beside the state"),
    ("nemotron3_nano_ep16", "--flash"):
        ("flash", "a decoder has no start-up probe, `auto` is XLA's path, and "
                  "XLA's scores at 8,192 tokens would be 17 GB"),
    ("nemotron3_nano_ep16", "--remat"):
        ("remat", "a Mamba block's backward keeps its decays and chunk "
                  "states: only one block at a time do they fit beside 7.45 "
                  "GiB of state"),
    ("ouro_2_6b_pp8", "--flash"):
        ("flash", "a decoder has no start-up probe, `auto` is XLA's path, and "
                  "XLA's scores at 8,192 tokens would be 4.3 GB a layer pass"),
    ("ouro_2_6b_pp8", "--remat"):
        ("remat", "24 layer passes a step: only rematerialised do their "
                  "temporaries fit beside 6.12 GB of state"),
    ("joyai_flash_ep16", "--flash"):
        ("flash", "a decoder has no start-up probe, `auto` is XLA's path, and "
                  "XLA's scores at 8,192 tokens would be 17 GB"),
    ("joyai_flash_ep16", "--remat"):
        ("remat", "six blocks of latent attention and experts at 16,384 "
                  "positions: only rematerialised do their temporaries fit "
                  "beside 10.14 GiB of state"),
}


def _pins():
    for path in _CONFIGS:
        name = os.path.splitext(os.path.basename(path))[0]
        with open(path) as f:
            argv = [str(a).format(batch=8, seed=0, outpath="unused")
                    for a in json.load(f)["trainer_argv"]]
        for flag in _SELECTORS:
            if flag in argv:
                yield pytest.param(name, argv, flag, id=f"{name}{flag}")


@pytest.mark.parametrize("name,argv,flag", _pins())
def test_a_pin_writes_a_default_out_and_no_more(name, argv, flag):
    """`python -m tpudist` with no selector builds the program the cell
    measures: `from_args` of a configuration's `trainer_argv` equals, field
    for field, `from_args` of the same argv with one selector taken out.
    The exceptions are listed above with their reasons, and for them the
    two DO differ, in exactly the field named, so the list cannot go
    stale."""
    from tpudist.config import from_args
    i = argv.index(flag)
    without = argv[:i] + argv[i + (2 if flag in _TAKES_VALUE else 1):]
    pinned, default = (dataclasses.asdict(from_args(a))
                       for a in (argv, without))
    differs = [k for k in pinned if pinned[k] != default[k]]
    field, _why = _NOT_DEFAULTS.get((name, flag), (None, None))
    assert differs == ([field] if field else [])
    if (name, flag) == ("resnet18_ref", "--flash"):
        from tpudist.models import create_model, model_fields
        assert "flash" not in model_fields(create_model(pinned["arch"]))


def test_every_exception_names_a_pin_that_is_written_out():
    written = {(p.values[0], p.values[2]) for p in _pins()}
    assert set(_NOT_DEFAULTS) <= written
    assert len(written) == 77


def test_fused_bn_takes_off_and_nothing_else(capsys):
    """The configurations still write `--fused-bn off`, so it parses (and
    sets no field); `on` and `auto` are refused with the line that says
    where the kernel went."""
    from tpudist.config import Config, from_args
    assert not hasattr(from_args(["--fused-bn", "off"]), "fused_bn")
    assert "fused_bn" not in {f.name for f in dataclasses.fields(Config)}
    for value in ("on", "auto"):
        with pytest.raises(SystemExit) as refused:
            from_args(["--fused-bn", value])
        assert refused.value.code == 2
        assert f"'{value}': the fused BN kernel left in PR 32" \
            in capsys.readouterr().err
