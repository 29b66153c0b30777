"""`metrics/ssd_scan_roofline.py` on the CPU: nothing here runs the scan or
measures anything on a chip. Run by path with the rest of this directory;
no `Trainer` is built, so `tests/test_chip_harness.py` collects the cases
(`TIER1`) in tier-1 too. What is checked:

- the two counts at the cell's shape (2 rows x 8,192 positions, 64 heads of
  64, 8 groups, a state of 128, chunks of 128, bfloat16 operands): 55.83
  GFLOP / 473.9 MB a block forward, 111.67 GFLOP / 679.5 MB backward, both
  memory-bound, 5.63 ms a step over the four blocks kept;
- the share on hand-made scopes: forward and transposed operations under
  `ssm_scan` counted, a `while` body among them, a kernel's call among them,
  an unnamed copy behind them printed and not counted;
- a share above 100 (which `cellrun` refuses) is what too little time reads;
- nothing (no metric) from a configuration without the mixer or a program
  without the scope, as the four other cells' programs are.
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
SHAPE = (2, 8192, 64, 64, 8, 128, 128, 2)


def test_ssd_scan_counts_at_the_cells_shape():
    m = reader("ssd_scan_roofline")
    flops, nbytes = m.forward_call(*SHAPE)
    # C B^T a group, mixed x dt x a head, the chunk's state and the carried
    # part a head
    assert flops == 2 * 2 * 8192 * (8 * 128 * 128 + 64 * 128 * 64
                                    + 2 * 64 * 64 * 128) == 55834574848
    # x, B, C in bfloat16; dt read and y written in float32
    assert nbytes == (2 * 8192 * (4096 + 2 * 1024) * 2 + 4 * 2 * 8192 * 64
                      + 4 * 2 * 8192 * 4096) == 473956352
    flops_b, nbytes_b = m.backward_call(*SHAPE)
    assert flops_b == 2 * flops == 111669149696
    assert nbytes_b == (2 * 2 * 8192 * 6144 * 2 + 2 * 4 * 2 * 8192 * 64
                        + 4 * 2 * 8192 * 4096) == 679477248
    # both memory-bound on a v5e; four blocks a step
    peak = load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]
    from harness import roofline
    least, bound_by = roofline.least_ms(
        [(4 * flops, 4 * nbytes), (4 * flops_b, 4 * nbytes_b)], peak)
    assert bound_by == ["memory", "memory"]
    assert least == pytest.approx(5.633, abs=0.002)
    # float32 operands move twice x, B and C
    assert m.forward_call(*SHAPE[:-1], 4)[1] - nbytes == 2 * 8192 * 6144 * 2
    line = ('  %c = f32[2,8192,4096] custom-call(%a), custom_call_target='
            '"tpu_custom_call", metadata={op_name="jit(step)/jvp(f)/mixer/'
            'ssm_mixer/ssm_scan/pallas_call"}, backend_config='
            '{"custom_call_config":{"cost_estimate":{"flops":"12","trans'
            'cendentals":"3","bytes_accessed":"40"}}}')
    other = line.replace("ssm_scan", "attn_fused")
    assert m.program_cost_estimate("\n".join([line, other, line])) == [
        24, 80, 2]
    assert m.program_cost_estimate(other) is None
    assert m.program_cost_estimate(None) is None


FWD = ("jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_0/mixer/ssm_mixer/"
       "ssm_scan/")
BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
       "layer_2/mixer/ssm_mixer/ssm_scan/")


def _ctx():
    return {"batch": 2, "chips": 1,
            "config": load(CHIP, "configs", "nemotron3_nano_ep16.json"),
            "peak": load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"],
            "step_hlo": None, "steps": 4}


def test_ssd_scan_roofline_reader(monkeypatch, capsys):
    """Four blocks' scans over a hand-made step: 4 x (473.9 + 679.5) MB at
    819 GB/s are 5.63 ms; over 50 ms under the scope that is 11.3 %, over
    100 ms (what XLA's fusions and two loops take) 5.6 %. Over 5 ms it would
    be 113 %: such a reading `cellrun` refuses, whatever the reader says."""
    from harness import scope_reduce
    m = reader("ssd_scan_roofline")
    ctx = _ctx()
    ops = [(10.0, "fwd", FWD + "pallas_call"),
           (4.0, "fwd", FWD + "while/body/mul"),
           (1.0, "fwd", FWD + "softplus/log1p"),
           (20.0, "bwd", BWD + "pallas_call"),
           (10.0, "bwd", BWD + "while/body/dot_general"),
           (5.0, "bwd", BWD + "cumsum/reduce_window"),
           (77.0, "fwd", FWD.replace("ssm_scan", "ssm_in_proj")
            + "in_proj/dot_general"),
           (9.0, "layout_copy", BWD + "transpose")]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = m.read(ctx)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["metric"] == "ssd_scan_roofline"
    assert line["bound_by"] == ["memory", "memory"]
    assert line["device_ms"] == pytest.approx(50.0)
    assert line["operations"] == 6
    assert line["layout_copy_behind_ms"] == pytest.approx(9.0)
    assert line["calls"] == [[4 * 55834574848, 4 * 473956352],
                             [4 * 111669149696, 4 * 679477248]]
    assert line["program_cost_estimate"] is None
    least = 4 * (473956352 + 679477248) / 819e9 * 1e3
    assert line["least_ms"] == pytest.approx(least)
    assert value == pytest.approx(100 * least / 50.0) and 11 < value < 12
    slow = [(2 * ms, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*slow))
    assert 5.5 < m.read(ctx) < 5.7
    # too little time under the scope: above 100, which the harness refuses
    fast = [(ms / 10, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*fast))
    assert m.read(ctx) > 100.0
    entry = next(e for e in load(ROOT, "BENCHMARK.json")["per_layer"]
                 if e["name"] == "ssd_scan_roofline")
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("%", "higher", "device_trace", "kernels",
                                "train_img_per_s_chip")
    assert entry["workloads"] == [CELL]
    # nothing to read: a configuration without the mixer, a program without
    # the scope (the parent of a later family), no scopes at all
    for other in ("mellum2_12b_ep4", "sdar_30b_ep8", "vit_b16"):
        assert m.read(dict(ctx, config=load(
            CHIP, "configs", other + ".json"))) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes_of(
        (3.0, "fwd", FWD.replace("ssm_scan", "ssm_conv") + "mul")))
    assert m.read(ctx) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert m.read(ctx) is None


TIER1 = (test_ssd_scan_counts_at_the_cells_shape,
         test_ssd_scan_roofline_reader)
