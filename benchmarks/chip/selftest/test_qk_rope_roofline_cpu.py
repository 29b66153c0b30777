"""`metrics/attn_qk_rope_roofline.py` on the CPU: nothing here norms or
rotates anything or measures anything on a chip. Run by path with the rest of
this directory; no `Trainer` is built, so `tests/test_chip_harness.py`
collects the cases (`TIER1`) in tier-1 too. What is checked:

- the two counts at both cells' shapes (2 rows x 8,192 positions, doubled
  under diffusion over blocks, 32 + 4 heads of 128, bfloat16 operands): 310.4
  MB a layer forward and 461.4 MB backward on mellum2, twice the elements on
  sdar, both memory-bound, 3.77 / 7.54 ms a step over the four layers;
- the share on hand-made scopes: forward, rematerialised and transposed
  operations under `attn_qk_norm_rope` counted, a kernel's call among them,
  an unnamed copy behind them printed and not counted; the parent's 63.9 ms
  reads under 6 %;
- a share above 100 (which `cellrun` refuses) is what too little time reads;
- nothing (no metric) from a program that ran XLA's attention, a
  configuration without grouped heads or a program with nothing under the
  scope (the hybrid's attention neither norms nor rotates).
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

CELLS = {"mellum2_12b_ep4_staged_8k": ("mellum2_12b_ep4", 8192),
         "sdar_30b_ep8_staged_8k": ("sdar_30b_ep8", 16384)}
N = 2 * 8192 * 36 * 128                 # mellum2's elements of q and k


def _peak():
    return load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]


@pytest.mark.parametrize("t", [8192, 16384])
def test_qk_rope_counts_at_the_cells_shapes(t):
    m = reader("attn_qk_rope_roofline")
    n = N * t // 8192
    assert m.elements(2, t, 32, 4, 128) == n
    ops, nbytes = m.forward_call(2, t, 32, 4, 128, 2)
    # q and k read and written once in bfloat16, the two float32 tables
    assert nbytes == 2 * n * 2 + 2 * 4 * t * 128
    assert ops == 15 * n
    ops_b, nbytes_b = m.backward_call(2, t, 32, 4, 128, 2)
    # the laid cotangents and raw q and k read, the raw cotangents written
    assert nbytes_b == 3 * n * 2 + 2 * 4 * t * 128
    assert ops_b == 30 * n
    if t == 8192:
        assert (nbytes, nbytes_b) == (310378496, 461373440)
    from harness import roofline
    least, bound_by = roofline.least_ms(
        [(4 * ops, 4 * nbytes), (4 * ops_b, 4 * nbytes_b)], _peak())
    assert bound_by == ["memory", "memory"]
    assert least == pytest.approx(3.769 * t / 8192, abs=0.005)
    # float32 operands move twice q and k, the tables as before
    assert m.forward_call(2, t, 32, 4, 128, 4)[1] - nbytes == 2 * n * 2


FWD = ("jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/self_attention/"
       "attn_mixer/attn_qk_norm_rope/")
REMAT = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
         "rematted_computation/layer_1/self_attention/attn_mixer/"
         "attn_qk_norm_rope/")
BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
       "layer_1/self_attention/attn_mixer/attn_qk_norm_rope/")


def _ctx(config="mellum2_12b_ep4"):
    return {"batch": 2, "chips": 1, "attention_kernel": "flash",
            "config": load(CHIP, "configs", config + ".json"),
            "peak": _peak(), "step_hlo": None, "steps": 4}


def test_qk_rope_roofline_reader(monkeypatch, capsys):
    """Four layers' passes over a hand-made step: 4 x (310.4 + 461.4) MB at
    819 GB/s are 3.77 ms; over 10 ms under the scope that is 37.7 %, over
    the parent's 63.9 ms 5.9 %. Over 2 ms it would be 188 %: such a reading
    `cellrun` refuses, whatever the reader says."""
    from harness import scope_reduce
    m = reader("attn_qk_rope_roofline")
    ctx = _ctx()
    ops = [(2.0, "fwd", FWD + "jit(_forward)/pallas_call"),
           (2.0, "bwd", REMAT + "jit(_forward)/pallas_call"),
           (4.0, "bwd", BWD + "jit(_backward)/pallas_call"),
           (1.5, "bwd", BWD + "reduce_sum"),
           (0.5, "fwd", FWD + "q_norm/rsqrt"),
           (30.0, "fwd", FWD.replace("attn_qk_norm_rope", "attn_fused")
            + "pallas_call"),
           (3.0, "layout_copy", BWD + "transpose")]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = m.read(ctx)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["metric"] == "attn_qk_rope_roofline"
    assert line["scope"] == "attn_qk_norm_rope"
    assert line["bound_by"] == ["memory", "memory"]
    assert line["device_ms"] == pytest.approx(10.0)
    assert line["operations"] == 5
    assert line["layout_copy_behind_ms"] == pytest.approx(3.0)
    assert line["calls"] == [[4 * 15 * N, 4 * 310378496],
                             [4 * 30 * N, 4 * 461373440]]
    # the counts alone: the program's own statement is not read
    assert "program_cost_estimate" not in line
    least = 4 * (310378496 + 461373440) / 819e9 * 1e3
    assert line["least_ms"] == pytest.approx(least)
    assert value == pytest.approx(100 * least / 10.0) and 37 < value < 38
    # what XLA's fusions took on the parent (ledger, PR 40): under 6 %
    slow = [(6.3875 * ms, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*slow))
    assert 5.8 < m.read(ctx) < 6.0
    # the doubled row: twice the elements, the same four layers
    capsys.readouterr()
    m.read(_ctx("sdar_30b_ep8"))
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["calls"][0] == [4 * 15 * 2 * N,
                                4 * (2 * 2 * N * 2 + 2 * 4 * 16384 * 128)]
    assert line["least_ms"] == pytest.approx(7.538, abs=0.005)
    # too little time under the scope: above 100, which the harness refuses
    fast = [(ms / 5, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*fast))
    assert m.read(ctx) > 100.0
    entry = next(e for e in load(ROOT, "BENCHMARK.json")["per_layer"]
                 if e["name"] == "attn_qk_rope_roofline")
    assert entry == {"name": "attn_qk_rope_roofline", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "train_img_per_s_chip",
                     "workloads": list(CELLS)}
    # nothing to read: XLA's attention, a configuration without grouped
    # heads, a program with nothing under the scope (the hybrid's attention
    # neither norms nor rotates), no scopes at all
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    assert m.read(dict(ctx, attention_kernel="xla")) is None
    for other in ("vit_b16", "resnet18_ref"):
        assert m.read(dict(ctx, config=load(
            CHIP, "configs", other + ".json"))) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes_of(
        (3.0, "fwd", FWD.replace("attn_qk_norm_rope", "attn_qkv_proj")
         + "dot_general")))
    assert m.read(_ctx("nemotron3_nano_ep16")) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert m.read(ctx) is None


TIER1 = (test_qk_rope_counts_at_the_cells_shapes,
         test_qk_rope_roofline_reader)
