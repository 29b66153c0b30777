"""`metrics/mla_qk_rope_roofline.py` on the CPU: nothing here norms or
rotates anything or measures anything on a chip. Run by path with the rest
of this directory; no `Trainer` is built, so `tests/test_chip_harness.py`
collects the cases (`TIER1`) in tier-1 too. What is checked:

- the two counts at the cell's shape (2 rows x 8,192 positions, latents of
  1,536 and 512, 32 heads and ONE key head of 64 rotated columns, bfloat16
  operands), against a count by hand: 276.8 MB a block forward and 343.9 MB
  backward, both memory-bound, 4.55 ms a step over the six blocks (five
  layers and the multi-token-prediction module's); q_nope's move is not in
  them;
- the share on hand-made scopes: forward, rematerialised and transposed
  operations under `attn_qk_norm_rope` counted (the latent norms' inner
  scope among them), a kernel's call among them, an unnamed copy behind them
  printed and not counted; the parent's 68.11 ms reads 6.7 %;
- a share above 100 (which `cellrun` refuses) is what too little time reads;
- nothing (no metric) from a configuration without latent attention or a
  program with nothing under the scope.
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

CELL = "joyai_flash_ep16_staged_8k"
SHAPE = (2, 8192, 32, 64, 1536, 512, 2)
POSITIONS = 2 * 8192
NORMED = POSITIONS * (1536 + 512)        # c_q and c_kv
ROTATED = POSITIONS * (32 * 64 + 64)     # q_rope of every head and k_r
TABLES = 2 * 4 * 8192 * 64               # cos and sin, float32
BLOCKS = 6


def _peak():
    return load(CHIP, "peaks.json")["device_kinds"]["TPU v5 lite"]


def test_mla_qk_rope_counts_at_the_cells_shape():
    m = reader("mla_qk_rope_roofline")
    assert m.elements(*SHAPE[:6]) == (NORMED, ROTATED) == (33554432,
                                                           34603008)
    ops, nbytes = m.forward_call(*SHAPE)
    # every array read and written once in bfloat16, the two tables
    assert nbytes == 2 * (NORMED + ROTATED) * 2 + TABLES == 276824064
    assert ops == 8 * NORMED + 5 * ROTATED
    ops_b, nbytes_b = m.backward_call(*SHAPE)
    # a norm: its operand and its cotangent in, one out; the rotation's
    # transpose: the cotangent in and out
    assert nbytes_b == (3 * NORMED + 2 * ROTATED) * 2 + TABLES == 343932928
    assert ops_b == 16 * NORMED + 5 * ROTATED
    # q_nope [2, 8192, 32, 128] moved once would be 268 MB more a pass: it
    # is not in the least
    assert nbytes < 2 * POSITIONS * 32 * 128 * 2 + TABLES + 2 * NORMED * 2
    from harness import roofline
    least, bound_by = roofline.least_ms(
        [(BLOCKS * ops, BLOCKS * nbytes), (BLOCKS * ops_b, BLOCKS * nbytes_b)],
        _peak())
    assert bound_by == ["memory", "memory"]
    assert least == pytest.approx(4.548, abs=0.002)
    # float32 operands move twice the elements, the tables as before
    assert m.forward_call(*SHAPE[:-1], 4)[1] - nbytes == 2 * (
        NORMED + ROTATED) * 2


FWD = ("jit(step)/jvp(tpudist_forward)/MoEDecoder/layer_1/self_attention/"
       "attn_mixer/attn_qk_norm_rope/")
REMAT = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
         "rematted_computation/layer_1/self_attention/attn_mixer/"
         "attn_qk_norm_rope/")
BWD = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/checkpoint/"
       "layer_1/self_attention/attn_mixer/attn_qk_norm_rope/")
MTP = ("jit(step)/transpose(jvp(tpudist_forward))/MoEDecoder/mtp_module/mtp/"
       "checkpoint/block/self_attention/attn_mixer/attn_qk_norm_rope/")


def _ctx(config="joyai_flash_ep16"):
    return {"batch": 2, "chips": 1, "attention_kernel": "flash",
            "config": load(CHIP, "configs", config + ".json"),
            "peak": _peak(), "step_hlo": None, "steps": 4}


def test_mla_qk_rope_roofline_reader(monkeypatch, capsys):
    """Six blocks' passes over a hand-made step: 6 x (276.8 + 343.9) MB at
    819 GB/s are 4.55 ms; over 12 ms under the scope that is 37.9 %, over
    the parent's 68.11 ms 6.7 %. Over 3 ms it would be 152 %: such a reading
    `cellrun` refuses, whatever the reader says."""
    from harness import scope_reduce
    m = reader("mla_qk_rope_roofline")
    ctx = _ctx()
    ops = [(2.5, "fwd", FWD + "jit(_forward)/pallas_call"),
           (2.5, "bwd", REMAT + "jit(_forward)/pallas_call"),
           (4.0, "bwd", BWD + "jit(_backward)/pallas_call"),
           (1.5, "bwd", MTP + "jit(_backward)/pallas_call"),
           (1.0, "bwd", BWD + "pad"),
           (0.5, "fwd", FWD + "mla_latent_norm/q_a_norm/rsqrt"),
           (50.0, "fwd", FWD.replace("attn_qk_norm_rope", "attn_fused")
            + "pallas_call"),
           (9.0, "fwd", FWD.replace("attn_qk_norm_rope", "attn_qkv_proj")
            + "mla_up/q_b_proj/dot_general"),
           (3.0, "layout_copy", BWD + "transpose")]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = m.read(ctx)
    line = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert line["metric"] == "mla_qk_rope_roofline"
    assert line["scope"] == "attn_qk_norm_rope"
    assert line["bound_by"] == ["memory", "memory"]
    assert line["device_ms"] == pytest.approx(12.0)
    assert line["operations"] == 6
    assert line["layout_copy_behind_ms"] == pytest.approx(3.0)
    assert line["calls"] == [
        [BLOCKS * (8 * NORMED + 5 * ROTATED), BLOCKS * 276824064],
        [BLOCKS * (16 * NORMED + 5 * ROTATED), BLOCKS * 343932928]]
    # the counts alone: the program's own statement is not read
    assert "program_cost_estimate" not in line
    least = BLOCKS * (276824064 + 343932928) / 819e9 * 1e3
    assert line["least_ms"] == pytest.approx(least)
    assert value == pytest.approx(100 * least / 12.0) and 37 < value < 39
    # what XLA's fusions took on the parent (ledger, PR 44): 68.11 ms
    slow = [(ms * 68.11 / 12.0, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*slow))
    assert 6.6 < m.read(ctx) < 6.8
    # too little time under the scope: above 100, which the harness refuses
    fast = [(ms / 4, b, n) for ms, b, n in ops]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*fast))
    assert m.read(ctx) > 100.0
    # the program's attention kernel is not asked: the scope is there
    # whichever runs behind it
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    assert m.read(dict(ctx, attention_kernel="xla")) == pytest.approx(value)
    per_layer = load(ROOT, "BENCHMARK.json")["per_layer"]
    assert per_layer[-1] == {
        "name": "mla_qk_rope_roofline", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_img_per_s_chip", "workloads": [CELL]}
    # nothing to read: a configuration without latent attention, a program
    # with nothing under the scope, no scopes at all
    for other in ("mellum2_12b_ep4", "ouro_2_6b_pp8", "vit_b16"):
        assert m.read(_ctx(other)) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes_of(
        (3.0, "fwd", FWD.replace("attn_qk_norm_rope", "attn_qkv_proj")
         + "dot_general")))
    assert m.read(ctx) is None
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: None)
    assert m.read(ctx) is None


TIER1 = (test_mla_qk_rope_counts_at_the_cells_shape,
         test_mla_qk_rope_roofline_reader)
