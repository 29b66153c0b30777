"""`metrics/ssm_conv_roofline.py` and `metrics/ssm_conv_ms.py` on the CPU:
nothing here convolves anything or measures anything on a chip. Run by path
with the rest of this directory; no `Trainer` is built, so
`tests/test_chip_harness.py` collects the cases (`TIER1`) in tier-1 too. What
is checked:

- the two counts at the cell's shape (2 rows x 8,192 positions, 4,096 + 2 x
  1,024 channels, 4 taps, bfloat16 operands): 402.8 MB a block forward and
  604.2 MB backward, both memory-bound, 4.92 ms a step over the four blocks
  kept;
- the share and the time on hand-made scopes: forward, rematerialised and
  transposed operations under `ssm_conv` counted, a kernel's call among
  them, an unnamed copy behind them printed and not counted; the parent's
  41.77 ms reads under 12 %;
- a share above 100 (which `cellrun` refuses) is what too little time reads;
- nothing (no metric) from a configuration without the mixer or a program
  with nothing under the scope.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_harness_cpu import load  # noqa: E402
from test_mellum2_cpu import reader, scopes_of  # noqa: E402

CELL = "nemotron3_nano_ep16_staged_8k"
SHAPE = (2, 8192, 64, 64, 8, 128, 4, 2)
E = 2 * 8192 * (4096 + 2 * 1024)        # elements of x, B and C a block


def _peak():
    return load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]


def test_ssm_conv_counts_at_the_cells_shape():
    m = reader("ssm_conv_roofline")
    assert m.elements(*SHAPE[:6]) == E == 100663296
    ops, nbytes = m.forward_call(*SHAPE)
    # xBC read and x, B and C written once in bfloat16; four float32 taps
    # and a bias a channel
    assert nbytes == 2 * E * 2 + 4 * 5 * 6144 == 402776064
    assert ops == 15 * E
    ops_b, nbytes_b = m.backward_call(*SHAPE)
    # three cotangents and xBC read, xBC's cotangent written; the taps and
    # bias read and their cotangents written
    assert nbytes_b == 3 * E * 2 + 2 * 4 * 5 * 6144 == 604225536
    assert ops_b == 35 * E
    from harness import roofline
    least, bound_by = roofline.least_ms(
        [(4 * ops, 4 * nbytes), (4 * ops_b, 4 * nbytes_b)], _peak())
    assert bound_by == ["memory", "memory"]
    assert least == pytest.approx(4.918, abs=0.002)
    # float32 operands move twice the elements, the taps as before
    assert m.forward_call(*SHAPE[:-1], 4)[1] - nbytes == 2 * E * 2


FWD = ("jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_0/mixer/ssm_mixer/"
       "ssm_conv/")
REMAT = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
         "rematted_computation/layer_2/mixer/ssm_mixer/ssm_conv/")
BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
       "layer_2/mixer/ssm_mixer/ssm_conv/")


def _ctx():
    return {"batch": 2, "chips": 1,
            "config": load(CHIP, "configs", "nemotron3_nano_ep16.json"),
            "peak": _peak(), "step_hlo": None, "steps": 4}


def test_ssm_conv_readers(monkeypatch, capsys):
    """Four blocks' passes over a hand-made step: 4 x (402.8 + 604.2) MB at
    819 GB/s are 4.92 ms; over 12 ms under the scope that is 41.0 %, over
    the parent's 41.77 ms 11.8 %. Over 3 ms it would be 164 %: such a
    reading `cellrun` refuses, whatever the reader says."""
    from harness import scope_reduce
    m, ms = reader("ssm_conv_roofline"), reader("ssm_conv_ms")
    ctx = _ctx()
    ops = [(2.5, "fwd", FWD + "jit(_forward)/pallas_call"),
           (2.5, "bwd", REMAT + "jit(_forward)/pallas_call"),
           (5.0, "bwd", BWD + "jit(_backward)/pallas_call"),
           (1.5, "bwd", BWD + "concatenate"),
           (0.5, "bwd", BWD + "reduce_sum"),
           (30.0, "fwd", FWD.replace("ssm_conv", "ssm_scan")
            + "pallas_call"),
           (80.0, "fwd", FWD.replace("ssm_conv", "ssm_in_proj")
            + "in_proj/dot_general"),
           (2.0, "layout_copy", BWD + "copy")]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    assert ms.read(ctx) == pytest.approx(12.0)
    capsys.readouterr()
    value = m.read(ctx)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["metric"] == "ssm_conv_roofline"
    assert line["scope"] == "ssm_conv"
    assert line["bound_by"] == ["memory", "memory"]
    assert line["device_ms"] == pytest.approx(12.0)
    assert line["operations"] == 5
    assert line["layout_copy_behind_ms"] == pytest.approx(2.0)
    assert line["calls"] == [[4 * 15 * E, 4 * 402776064],
                             [4 * 35 * E, 4 * 604225536]]
    # the counts alone: the program's own statement is not read
    assert "program_cost_estimate" not in line
    least = 4 * (402776064 + 604225536) / 819e9 * 1e3
    assert line["least_ms"] == pytest.approx(least)
    assert value == pytest.approx(100 * least / 12.0) and 40 < value < 42
    # what XLA's fusions took on the parent (PERF.md section 5, PR 40)
    slow = [(ms_ * 41.77 / 12.0, b, n) for ms_, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*slow))
    assert 11.5 < m.read(ctx) < 12.0
    assert ms.read(ctx) == pytest.approx(41.77)
    # too little time under the scope: above 100, which the harness refuses
    fast = [(ms_ / 4, b, n) for ms_, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*fast))
    assert m.read(ctx) > 100.0
    per_layer = load(ROOT, "BENCHMARK.json")["per_layer"]
    for name, unit, better, layer in (
            ("ssm_conv_ms", "ms", "lower", "step program"),
            ("ssm_conv_roofline", "%", "higher", "kernels")):
        entry = next(e for e in per_layer if e["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": "device_trace", "layer": layer,
                         "moves": "train_img_per_s_chip",
                         "workloads": [CELL]}
    # nothing to read: a configuration without the mixer, a program with
    # nothing under the scope, no scopes at all
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    for other in ("mellum2_12b_ep4", "sdar_30b_ep8", "vit_b16"):
        assert m.read(dict(ctx, config=load(
            CHIP, "configs", other + ".json"))) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes_of(
        (3.0, "fwd", FWD.replace("ssm_conv", "ssm_scan") + "pallas_call")))
    assert m.read(ctx) is None and ms.read(ctx) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert m.read(ctx) is None and ms.read(ctx) is None


TIER1 = (test_ssm_conv_counts_at_the_cells_shape, test_ssm_conv_readers)
