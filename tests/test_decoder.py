"""The decoder of tokens (models/decoder.py) and what it brought: the top-k
expert layer with a share of the experts held, windowed grouped-query
attention in the XLA path and in the streaming kernel, RoPE tables, the
chunked head loss, the synthetic token source, the trainer on tokens.

CPU, toy widths (hidden 64, 8 heads over 2, 8 experts top-2 with 2 or 4
held, window 8 in sequences of 32 and 37, two layer types), seeded random
weights. The plain reference is the benchmark's, imported by path: it
imports nothing of the program.
"""

import importlib.util
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import (block_noise, mellum2_12b_a2_5b,
                                    mellum2_tiny, sdar_30b_a3b, sdar_tiny)
from tpudist.ops import rope
from tpudist.parallel.moe import moe_topk_held, route_topk
from tpudist.parallel.ring_attention import attention, block_diffusion_mask

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{name}_for_tests", os.path.join(
            CHIP, "refs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference("mellum2_12b_ep4")
REF_SDAR = _reference("sdar_30b_ep8")
ROPE = {kind: dict(p) for kind, p in
        mellum2_12b_a2_5b().rope_parameters.items()}


def tiny_cfg(held, share):
    """The tiny twin's sizes as the reference reads a configuration."""
    return dict(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, num_hidden_layers=2, vocab_size=64, num_experts=8,
        num_experts_per_tok=2, num_experts_held=held,
        expert_share=f"{share} of {8 // held}", moe_intermediate_size=32,
        sliding_window=8, rms_norm_eps=1e-6, reference_block_rows=16,
        layer_types=["sliding_attention", "full_attention"],
        rope_parameters=ROPE, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
        weight_decay=0.1, decay_min_ndim=2)


def tokens(t, rows=2, vocab=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0,
                             vocab)
    return ids[:, :-1], ids[:, 1:]


def leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


# --- the model against the reference ----------------------------------------

@pytest.mark.parametrize("t,held,share,flash", [
    (32, 2, 1, False), (37, 2, 3, True), (32, 4, 0, True), (37, 4, 1, False)])
def test_loss_and_every_gradient_leaf_match_the_reference(t, held, share,
                                                          flash):
    cfg = tiny_cfg(held, share)
    params, _ = REF.init(jax.random.PRNGKey(0), cfg)
    model = mellum2_tiny(dtype=jnp.float32, expert_share=(share, 8 // held),
                         vocab_share=(0, 4), layers=2, flash=flash,
                         remat=flash)
    x, y = tokens(t)
    ours = model.init(jax.random.PRNGKey(0), x)["params"]
    assert [(jax.tree_util.keystr(k), v.shape, v.dtype)
            for k, v in leaves(ours)] == [
        (jax.tree_util.keystr(k), v.shape, v.dtype)
        for k, v in leaves(params)]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, x, train=True, targets=y).loss)(params)
    (want, _), want_grads = jax.value_and_grad(REF.loss_fn, has_aux=True)(
        params, x, y, cfg)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        gap = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12))
        assert gap < 2e-4, (jax.tree_util.keystr(path), gap)


def test_train_step_takes_the_references_first_step(mesh8):
    """Through `create_train_state` and `make_train_step`, the path a cell
    runs: the step's loss is the reference's and AdamW's first moment is
    (1 - b1) times the reference's gradient, leaf for leaf."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = Config(arch="mellum2_tiny", batch_size=8, seq_len=32,
                 optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                 use_amp=False, seed=0).finalize(8)
    ref_cfg = tiny_cfg(2, 1)
    model = mellum2_tiny(dtype=jnp.float32, expert_share=(1, 4),
                         vocab_share=(0, 4), layers=2)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    params, _ = REF.init(jax.random.PRNGKey(3), ref_cfg)
    state = state.replace(params=params)
    x, y = tokens(32, rows=8)
    with jax.default_matmul_precision("highest"):
        state, metrics = make_train_step(mesh8, model, cfg)(
            state, x, y, jnp.float32(1e-3))
    (want, (routed, _)), want_grads = jax.value_and_grad(
        REF.loss_fn, has_aux=True)(params, x, y, ref_cfg)
    assert abs(float(metrics["loss"]) - float(want)) < 1e-5 * float(want)
    mu = [leaf for path, leaf in leaves(state.opt_state)
          if any(getattr(k, "name", None) == "mu" for k in path)]
    for m, (path, w) in zip(mu, leaves(want_grads)):
        gap = float(jnp.linalg.norm(m / 0.1 - w)
                    / (jnp.linalg.norm(w) + 1e-12))
        assert gap < 5e-4, (jax.tree_util.keystr(path), gap)
    # the counters ride the metrics: a layer's pairs are the mean over the
    # eight shards of what each computed
    for layer in range(2):
        assert float(metrics[f"moe_pairs.layer_{layer}"]) * 8 == float(
            jnp.sum(routed[layer]))
        assert float(metrics[f"moe_load_max_over_mean.layer_{layer}"]) >= 1.0
    assert 0.0 <= float(metrics["acc1"]) <= 100.0


# --- the expert layer -------------------------------------------------------

def _moe_params(key, d=32, f=16, experts=8):
    ks = jax.random.split(key, 4)
    return {"router": jax.random.normal(ks[0], (d, experts)),
            "gate": jax.random.normal(ks[1], (experts, d, f)) * 0.2,
            "up": jax.random.normal(ks[2], (experts, d, f)) * 0.2,
            "down": jax.random.normal(ks[3], (experts, f, d)) * 0.2}


def _share(params, lo, n):
    return {"router": params["router"],
            **{m: params[m][lo:lo + n] for m in ("gate", "up", "down")}}


@pytest.mark.parametrize("ref,experts,held", [
    (REF, 8, 2), (REF, 8, 4), (REF_SDAR, 16, 4), (REF_SDAR, 16, 2)],
    ids=["mellum2_2of8", "mellum2_4of8", "sdar_4of16", "sdar_2of16"])
def test_the_shares_add_up_to_the_uncut_layer(ref, experts, held):
    """The add-up test: every holder's part of the result, summed over the
    holders, is what the reference gives for the whole layer with all the
    experts held in one place (the tiny twins' 8 and 16 experts, a quarter,
    a half and an eighth of them held: the cells hold 16 of 64 and 16 of
    128)."""
    params = _moe_params(jax.random.PRNGKey(0), experts=experts)
    u = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    with jax.default_matmul_precision("highest"):
        whole, routed = ref._moe(u, params, dict(k=2, first=0, held=experts),
                                 None)
        total, computed = 0.0, 0.0
        for lo in range(0, experts, held):
            y, counters = moe_topk_held(_share(params, lo, held), u, top_k=2,
                                        first_expert=lo)
            part, _ = ref._moe(u, _share(params, lo, held),
                               dict(k=2, first=lo, held=held), None)
            np.testing.assert_allclose(y, part, atol=2e-5)
            total, computed = total + y, computed + counters["moe_pairs"]
    np.testing.assert_allclose(total, whole, atol=5e-5)
    pairs = routed[0] if isinstance(routed, tuple) else routed
    assert float(computed) == 64 * 2 == float(jnp.sum(pairs))


def test_no_pair_is_dropped_under_a_routing_skewed_onto_one_expert():
    params = _moe_params(jax.random.PRNGKey(2))
    # every token's largest logit is expert 5's, by a wide margin
    params["router"] = params["router"].at[:, 5].set(0.0)
    u = jax.random.normal(jax.random.PRNGKey(3), (64, 32))
    u = u.at[:, 0].set(10.0)
    params["router"] = params["router"].at[0, 5].set(5.0)
    experts, _ = route_topk(u, params["router"], 2)
    assert bool(jnp.all(jnp.any(experts == 5, axis=-1)))
    with jax.default_matmul_precision("highest"):
        y, counters = moe_topk_held(_share(params, 4, 2), u, top_k=2,
                                    first_expert=4)
        want, (pairs, _) = REF._moe(u, _share(params, 4, 2),
                                    dict(k=2, first=4, held=2), None)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert float(counters["moe_pairs"]) == float(jnp.sum(pairs)) >= 64
    assert float(counters["moe_load_max_over_mean"]) > 1.5


def test_every_pair_has_a_row_when_every_expert_chosen_is_held():
    """The worst case the pair buffer is sized for: all k of every token."""
    params = _moe_params(jax.random.PRNGKey(6), experts=2)
    u = jax.random.normal(jax.random.PRNGKey(7), (40, 32))
    with jax.default_matmul_precision("highest"):
        y, counters = moe_topk_held(params, u, top_k=2)
        want, _ = REF._moe(u, params, dict(k=2, first=0, held=2), None)
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert float(counters["moe_pairs"]) == 80.0


def test_the_reference_counts_the_pairs_rounded_operands_route_otherwise():
    """The by-hand reading of `selftest/read_limits_mix.py`: a share a layer,
    nothing under no rounding, few under bfloat16, more under fp8."""
    cfg = tiny_cfg(2, 1)
    params, _ = REF.init(jax.random.PRNGKey(5), cfg)
    x, y = tokens(32, rows=4)
    assert REF.routed_otherwise(params, x, y, cfg, quant=None) == [0.0, 0.0]
    bf16 = REF.routed_otherwise(params, x, y, cfg)
    fp8 = REF.routed_otherwise(params, x, y, cfg, quant="fp8")
    assert len(bf16) == 2 and all(0.0 <= s < 0.1 for s in bf16)
    assert sum(fp8) > sum(bf16)


def test_the_drain_keeps_a_models_counters():
    from tpudist import telemetry
    from tpudist.trainer import _MetricDrain
    from tpudist.utils import AverageMeter
    drain = _MetricDrain({"loss": AverageMeter("Loss")})
    before = len(telemetry.counters().get("moe_pairs.layer_2", []))
    drain.push({"loss": 1.0, "moe_pairs.layer_2": 60.0}, n=2, step=6)
    drain.push({"loss": 1.0, "moe_pairs.layer_2": 70.0}, n=2, step=7)
    drain.drain()
    assert telemetry.counters()["moe_pairs.layer_2"][before:] == [60.0, 70.0]


def test_the_expert_layers_gradients_match_a_dense_loop():
    params = _moe_params(jax.random.PRNGKey(4))
    u = jax.random.normal(jax.random.PRNGKey(5), (48, 32))

    def ours(p, x):
        return jnp.sum(jnp.square(moe_topk_held(
            _share(p, 2, 4), x, top_k=2, first_expert=2)[0]))

    def dense(p, x):
        return jnp.sum(jnp.square(REF._moe(
            x, _share(p, 2, 4), dict(k=2, first=2, held=4), None)[0]))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(ours, argnums=(0, 1))(params, u)
        want = jax.grad(dense, argnums=(0, 1))(params, u)
    for (path, g), (_, w) in zip(leaves(got), leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * scale, \
            jax.tree_util.keystr(path)


# tokens of 48 whose two chosen experts are named, so that the pairs held by
# experts 2..5 (a buffer of 96 rows, walked in blocks of 32) are counted by
# hand: (held experts of a token's two, how many such tokens)
FILLS = {
    "none_held": [((), 48)],
    "a_whole_block": [((2,), 10), ((5,), 10), ((3, 4), 6), ((), 22)],
    "one_past_a_block": [((2,), 10), ((5,), 11), ((3, 4), 6), ((), 21)],
    "a_quarter": [((4,), 12), ((2, 3), 6), ((), 30)],
    "all_held": [((2, 3), 16), ((4, 5), 16), ((3, 5), 16)],
    "one_held_expert": [((3,), 48)],
}
FILL_PAIRS = {fill: sum(len(held) * count for held, count in spec)
              for fill, spec in FILLS.items()}      # 0, 32, 33, 24, 96, 48
BLOCK = 32


def _filled(fill, seed=8):
    """(params, u): a router that reads a token's first eight features as
    its logits, and tokens whose two largest are the experts the fill
    names (held ones first, the rest from the experts not held)."""
    params = _moe_params(jax.random.PRNGKey(seed))
    params["router"] = jnp.zeros((32, 8)).at[:8].set(jnp.eye(8))
    u = jax.random.normal(jax.random.PRNGKey(seed + 1), (48, 32))
    chosen = [tuple(held) + (0, 7, 1, 6)[:2 - len(held)]
              for held, count in FILLS[fill] for _ in range(count)]
    order = np.random.RandomState(seed).permutation(48)
    logits = np.asarray(u[:, :8]) * 0.3
    for t, (a, b) in zip(order, chosen):
        logits[t, a], logits[t, b] = 4.0, 3.0
    return params, u.at[:, :8].set(logits)


@pytest.mark.parametrize("fill", list(FILLS))
def test_the_block_loops_match_a_dense_loop_at_every_fill(fill, monkeypatch):
    """The result, every gradient and the counters where the loops over the
    pair buffer take no turn, whole turns, one row into the next, a quarter
    of the buffer, all of it, and one expert's rows only. The buffers start
    as NaN here (on the chip: whatever the memory held), so nothing may
    read a row that no pair wrote."""
    monkeypatch.setattr(
        jax.lax, "empty", lambda shape, dtype: jnp.full(shape, jnp.nan, dtype))
    params, u = _filled(fill)

    def ours(p, x):
        y, counters = moe_topk_held(_share(p, 2, 4), x, top_k=2,
                                    first_expert=2)
        return jnp.sum(jnp.square(y)) + jnp.sum(y[:, 0]), (y, counters)

    def dense(p, x):
        y, _ = REF._moe(x, _share(p, 2, 4), dict(k=2, first=2, held=4),
                        None)
        return jnp.sum(jnp.square(y)) + jnp.sum(y[:, 0]), y

    with jax.default_matmul_precision("highest"):
        got, (y, counters) = jax.grad(ours, argnums=(0, 1), has_aux=True)(
            params, u)
        want, y_want = jax.grad(dense, argnums=(0, 1), has_aux=True)(
            params, u)
    np.testing.assert_allclose(y, y_want, atol=2e-5)
    for (path, g), (_, w) in zip(leaves(got), leaves(want)):
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        assert float(jnp.max(jnp.abs(g - w))) < 1e-5 * scale, \
            jax.tree_util.keystr(path)
    pairs = FILL_PAIRS[fill]
    assert float(counters["moe_pairs"]) == pairs
    assert float(counters["moe_rows_walked"]) == -(-pairs // BLOCK) * BLOCK


def _shapes_in(jaxpr):
    """The shape of every value a jaxpr computes, its inner jaxprs' too."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_in(sub)


def test_no_tensor_of_tokens_by_slots_by_width_is_built():
    """Forward and backward hold no [T, k, d] intermediate (a token's rows
    are summed where they lie), and the walked rows reach
    ``telemetry.counters()`` through the drain as the pairs do."""
    from tpudist import telemetry
    from tpudist.trainer import _MetricDrain
    from tpudist.utils import AverageMeter
    params, u = _filled("a_quarter")

    def loss(p, x):
        return jnp.sum(jnp.square(moe_topk_held(
            _share(p, 2, 4), x, top_k=2, first_expert=2)[0]))

    shapes = set(_shapes_in(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u).jaxpr))
    assert (96, 32) in shapes                  # the pair buffer is there
    assert (48, 2, 32) not in shapes and (96, 2, 32) not in shapes

    drain = _MetricDrain({"loss": AverageMeter("Loss")})
    seen = {k: len(telemetry.counters().get(f"{k}.layer_1", []))
            for k in ("moe_pairs", "moe_rows_walked")}
    for step, fill in enumerate(FILLS):
        p, x = _filled(fill)
        _, counters = moe_topk_held(_share(p, 2, 4), x, top_k=2,
                                    first_expert=2)
        drain.push({"loss": 1.0, **{f"{k}.layer_1": v
                                    for k, v in counters.items()}},
                   n=2, step=step)
    drain.drain()
    pairs, walked = (telemetry.counters()[f"{k}.layer_1"][seen[k]:]
                     for k in ("moe_pairs", "moe_rows_walked"))
    assert pairs == [float(FILL_PAIRS[f]) for f in FILLS]
    assert walked == [float(-(-int(n) // BLOCK) * BLOCK) for n in pairs]


# --- attention --------------------------------------------------------------

def test_windowed_attention_sees_the_nearest_keys_only():
    q = jnp.ones((1, 6, 1, 4))
    k = jnp.ones((1, 6, 1, 4))
    v = jnp.arange(6, dtype=jnp.float32)[None, :, None, None] * jnp.ones(
        (1, 6, 1, 4))
    out = attention(q, k, v, causal=True, window=2)[0, :, 0, 0]
    np.testing.assert_allclose(out, [0.0, 0.5, 1.5, 2.5, 3.5, 4.5], atol=1e-6)
    with pytest.raises(ValueError, match="causal"):
        attention(q, k, v, window=2)


def test_a_streaming_call_claims_the_blocks_it_runs():
    from tpudist.ops.pallas.flash_attention import _Band
    long = dict(causal=True, q_len=8192, k_len=8192)
    full = _Band(window=None, block_q=512, block_k=512, **long)
    banded = _Band(window=1024, block_q=512, block_k=512, **long)
    assert full.pairs() == 16 * 17 // 2 and full.steps == 16
    # a q block of 512 rows under a window of 1,024 touches three k blocks
    # (the grid counts the most any block touches), and a k block three q
    # blocks in the dKV pass, where the queries stream
    assert banded.steps == 3 and banded.pairs() == 1 + 2 + 14 * 3
    streamed_q = _Band(window=1024, block_q=512, block_k=512, stream="q",
                       **long)
    assert streamed_q.steps == 3 and streamed_q.pairs() == 14 * 3 + 2 + 1
    # and one of 1,024 rows two of 1,024
    wide = _Band(window=1024, block_q=1024, block_k=1024, **long)
    assert wide.steps == 2 and wide.pairs() == 1 + 7 * 2
    # the steps slide with the band (PR 33): a q block of 256 rows sees
    # 1,279 keys, one step of 1,280 and not the two aligned blocks of 1,024
    # its band straddles
    slid = _Band(window=1024, block_q=256, block_k=1280, **long)
    assert slid.steps == 1 and slid.pairs() == 32
    assert [slid.span(i)[0] for i in (0, 4, 5, 31)] == [0, 0, 256, 6912]
    # a window that shares no edge with the granule needs no more steps
    odd = _Band(window=1000, block_q=512, block_k=512, **long)
    assert odd.steps == 3
    square = _Band(causal=False, window=None, block_q=128, block_k=128,
                   q_len=200, k_len=200)
    assert square.pairs() == 4


def test_dispatch_key_carries_window_and_head_grouping():
    from tpudist.ops.attention_dispatch import shape_key
    plain = shape_key(128, 197, 12, 64, "bfloat16", True, False)
    assert plain == "b128_t197_h12_d64_bfloat16_train_full"      # as ever
    assert shape_key(128, 197, 12, 64, "bfloat16", True, False,
                     kv_heads=12) == plain
    assert shape_key(2, 8192, 32, 128, jnp.bfloat16, True, True, kv_heads=4,
                     window=1024) == \
        "b2_t8192_h32_kv4_d128_bfloat16_train_causal_w1024"


# --- rope -------------------------------------------------------------------

def test_plain_rope_table_is_the_closed_formula():
    freq, factor = rope.inv_freq(ROPE["sliding_attention"], 128)
    want = [500000.0 ** (-2 * i / 128) for i in range(64)]
    np.testing.assert_allclose(freq, want, rtol=1e-12)
    assert factor == 1.0
    cos, sin = rope.tables(ROPE["sliding_attention"], 128, 9)
    assert cos.shape == sin.shape == (9, 128)
    np.testing.assert_allclose(cos[5, 3], math.cos(5 * want[3]), rtol=1e-6)
    np.testing.assert_allclose(sin[5, 64 + 3], math.sin(5 * want[3]),
                               rtol=1e-6)


def test_yarn_rope_table_is_the_closed_formula():
    p = ROPE["full_attention"]
    freq, factor = rope.inv_freq(p, 128)
    assert factor == 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1.0)) < 1e-12

    def correction(rotations):
        return 128 * math.log(8192 / (rotations * 2 * math.pi)) / (
            2 * math.log(500000))
    low, high = math.floor(correction(32)), math.ceil(correction(1))
    for i in range(64):
        plain = 500000.0 ** (-2 * i / 128)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain / 16 * ramp + plain * (1 - ramp)
        assert abs(freq[i] - want) <= 1e-12 * want, i
    # fast dimensions are left alone, slow ones interpolated by the factor
    assert freq[0] == 1.0 and abs(freq[63] * 16 - 500000.0 ** (-126 / 128)) \
        < 1e-15
    ref_cos, ref_sin = REF.rope_tables(p, 128, 33)
    cos, sin = rope.tables(p, 128, 33)
    np.testing.assert_allclose(cos, ref_cos, atol=1e-7)
    np.testing.assert_allclose(sin, ref_sin, atol=1e-7)


# --- loss, data, trainer ----------------------------------------------------

def _head_case(weighted):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(ks[0], (2, 37, 16))
    kernel = jax.random.normal(ks[1], (16, 50))
    targets = jax.random.randint(ks[2], (2, 37), 0, 50)
    t = jax.random.uniform(ks[3], (2, 37), minval=0.1)
    return hidden, kernel, targets, (
        jnp.where(t < 0.6, 1.0 / t, 0.0) if weighted else None)


@pytest.mark.parametrize("weighted,fields", [
    (False, {}), (True, {}), (True, dict(normaliser=148)),
    (True, dict(rematerialised=True)),
    (False, dict(rematerialised=True))],
    ids=["plain", "weights", "weights_normaliser", "rematerialised",
         "rematerialised_plain"])
def test_head_loss_in_chunks_is_the_whole_cross_entropy(weighted, fields):
    """Either form of the chunked head loss against the unchunked float32
    cross entropy: the mean (with weights: sum(w * nll) over the normaliser)
    and the accuracy (over the weighted positions), and at a cotangent other
    than one the gradients of the hidden rows, of the head and of the
    weights, which a looped model differentiates."""
    from tpudist.ops import lm_head_loss
    hidden, kernel, targets, weights = _head_case(weighted)
    over = fields.get("normaliser", 74)
    ones = jnp.ones((2, 37)) if weights is None else weights

    def whole(h, w, weights):
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return 0.37 * jnp.sum(weights * nll) / over

    def chunked(h, w, weights):
        loss, acc = lm_head_loss(
            h, w, targets, chunk=16, **fields,
            **(dict(weights=weights) if weighted else {}))
        return 0.37 * loss, acc

    with jax.default_matmul_precision("highest"):
        (loss, acc), grads = jax.value_and_grad(
            chunked, argnums=(0, 1, 2), has_aux=True)(hidden, kernel, ones)
        want, want_grads = jax.value_and_grad(whole, argnums=(0, 1, 2))(
            hidden, kernel, ones)
        hit = (jnp.argmax(hidden @ kernel, axis=-1) == targets) & (ones > 0)
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(acc) - 100.0 * float(hit.sum())
               / float((ones > 0).sum())) < 1e-4
    if not weighted:
        want_grads = want_grads[:2] + (jnp.zeros((2, 37)),)
    for a, b in zip(grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_head_loss_takes_its_gradients_in_its_forward_loop():
    """The mechanism, read from the compiled program: differentiated, the
    head's loss is ONE loop of three products a chunk (the logits, d hidden,
    d head) where the rematerialised form is two loops of four (the logits
    made twice); in bfloat16 the two forms' gradients agree to rounding."""
    from tpudist.ops import lm_head_loss
    hidden, kernel, targets, _ = _head_case(False)
    hidden = hidden.astype(jnp.bfloat16)

    def grads(form):
        return jax.jit(jax.value_and_grad(
            lambda h, w: lm_head_loss(h, w, targets, chunk=16,
                                      rematerialised=form)[0],
            argnums=(0, 1)))

    def counts(form):
        text = grads(form).lower(hidden, kernel).compile().as_text()
        return (len(re.findall(r" dot\(", text)),
                len(re.findall(r" while\(", text)))

    assert counts(False) == (3, 1)
    assert counts(True) == (4, 2)
    (loss, (dh, dw)), (was, (was_dh, was_dw)) = (
        grads(False)(hidden, kernel), grads(True)(hidden, kernel))
    assert float(loss) == float(was)
    for a, b in ((dh, was_dh), (dw, was_dw)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b)


def test_the_trainer_says_which_form_of_the_head_loss_runs(tmp_path):
    """`MoEDecoder.head_plan` at the cells' shapes and what the trainer's
    constructor logs and emits of it: the form follows from the caller (the
    looped model's scan over passes), nothing is decided."""
    import types
    from tpudist import telemetry
    from tpudist.models import create_model
    from tpudist.trainer import Trainer
    plans = [create_model(arch, **share).head_plan(rows, 8192)
             for arch, rows, share in (
                 ("mellum2_12b_a2_5b", 2, dict(layers=4)),
                 ("joyai_llm_flash", 2, dict(layers=5)),
                 ("ouro_2_6b", 1, dict(layers=6)))]
    assert plans == [
        dict(form="forward_loop", chunk=2048, chunks=8, calls=1),
        dict(form="forward_loop", chunk=2048, chunks=8, calls=2),
        dict(form="rematerialised", chunk=2048, chunks=4, calls=4)]
    # the chunk rule is the loss's own: the largest divisor up to the size
    assert create_model("sdar_tiny", loss_chunk=64).head_plan(2, 37) == dict(
        form="forward_loop", chunk=37, chunks=2, calls=1)
    lines = []
    sink = telemetry.Telemetry(str(tmp_path), heartbeat=False)
    fake = types.SimpleNamespace(log=lines.append, telemetry=sink)
    for plan in plans:
        Trainer._announce_plan(fake, "lm_head", plan)
    sink.close()
    assert lines == [
        "=> lm_head: forward_loop (chunk 2048, chunks 8, calls 1)",
        "=> lm_head: forward_loop (chunk 2048, chunks 8, calls 2)",
        "=> lm_head: rematerialised (chunk 2048, chunks 4, calls 4)"]
    with open(telemetry.events_path(str(tmp_path), 0)) as f:
        events = [e for e in map(json.loads, f) if e["type"] == "lm_head"]
    assert [{k: e[k] for k in telemetry.SCHEMA["lm_head"]}
            for e in events] == plans


def test_cross_entropy_takes_any_leading_dimensions():
    from tpudist.ops import cross_entropy_loss
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7))
    targets = jax.random.randint(jax.random.PRNGKey(1), (3, 5), 0, 7)
    flat = cross_entropy_loss(logits.reshape(15, 7), targets.reshape(15))
    assert abs(float(cross_entropy_loss(logits, targets)) - float(flat)) \
        < 1e-6


def test_synthetic_token_rows():
    from tpudist.data import SyntheticTokens
    ds = SyntheticTokens(16, seq_len=24, vocab_size=100, seed=3)
    x, y = ds[5]
    assert x.shape == y.shape == (24,) and x.dtype == y.dtype == np.int32
    assert np.array_equal(x[1:], y[:-1])
    assert 0 <= x.min() and x.max() < 100
    again, _ = ds[5]
    assert np.array_equal(x, again) and not np.array_equal(x, ds[6][0])


@pytest.mark.parametrize("arch,held,key", [
    ("mellum2_tiny", 2, "_t32_h8_kv2_d16_bfloat16_train_causal_w8"),
    ("sdar_tiny", 4, "_t64_h8_kv2_d16_bfloat16_train_bd4")])
def test_python_m_tpudist_trains_on_synthetic_tokens(tmp_path, arch, held,
                                                     key):
    """The normal entry point's path (`config.from_args` -> `Trainer.fit`)
    on a tiny twin: the loss falls, tokens/s is in the log line, the share
    arrives as statements, and the objective comes with the registered
    model: `sdar_tiny` trains by diffusion over blocks through the same
    trainer, step, prefetcher and drain, its noise key in the state and its
    counters in the drain."""
    from tpudist import telemetry
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    cfg = from_args([
        "--synthetic", "-a", arch, "--seq-len", "32", "-b", "16",
        "--layers", "2", "--epochs", "2", "--step", "5", "--optimizer", "adamw", "--lr",
        "0.01", "--wd", "0.1", "--adam-b2", "0.95", "--expert-share", "1/4",
        "--vocab-share", "0/2", "--flash", "off", "-j", "2", "-p", "2",
        "--no-telemetry", "--outpath", str(tmp_path / "out"), "--overwrite",
        "delete", "--seed", "0"])
    seen = len(telemetry.counters().get("bd_masked_share", []))
    trainer = Trainer(cfg, writer=None)
    assert trainer.model.vocab_held == 128
    assert trainer.flash_decision["kernel"] == "xla"
    assert key in trainer.flash_decision["key"]
    assert trainer.state.params["layer_0"]["moe"]["gate"].shape == (
        held, 64, 32)
    diffusion = arch == "sdar_tiny"
    assert ("noise_key" in trainer.state.batch_stats) == diffusion
    first = jax.device_get(trainer.state.batch_stats)
    trainer.fit()
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    import re
    losses = [float(x) for x in re.findall(
        r"\|\|==> Train: Epoch\[\d+\]\s+Loss ([0-9.e+-]+)", log)]
    # a masked position's loss is weighted 1 / t (up to 1 / eps): its mean is
    # the plain cross entropy's, but a step's reading, and AdamW's first
    # steps on such gradients, swing far more
    assert len(losses) == 2 and losses[1] < losses[0] < math.log(128) * (
        2.0 if diffusion else 1.0) + 0.5
    assert re.search(r"Acc@1\s+[0-9.]+\t[0-9.]+ tokens/s", log)
    shares = telemetry.counters().get("bd_masked_share", [])[seen:]
    if diffusion:
        last = jax.device_get(trainer.state.batch_stats)
        assert not np.array_equal(first["noise_key"], last["noise_key"])
        assert len(shares) >= 4 and 0.3 < sum(shares) / len(shares) < 0.7
    else:
        assert shares == []


def test_the_compiled_initialisation_draws_what_the_eager_one_drew():
    from tpudist.config import Config
    from tpudist.train import create_train_state
    cfg = Config(arch="mellum2_tiny", batch_size=8, seq_len=32,
                 optimizer="adamw", use_amp=False).finalize(8)
    model = mellum2_tiny(dtype=jnp.float32, layers=2)
    key = jax.random.PRNGKey(7)
    state = create_train_state(key, model, cfg)
    eager = model.init(key, model.example_input(), train=False)["params"]
    for (path, a), (_, b) in zip(leaves(state.params), leaves(eager)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_model_of_tokens_needs_a_row_length(tmp_path):
    from tpudist.config import Config
    from tpudist.trainer import Trainer
    cfg = Config(arch="mellum2_tiny", batch_size=8, synthetic=True,
                 outpath=str(tmp_path / "out"), overwrite="delete")
    with pytest.raises(ValueError, match="--seq-len"):
        Trainer(cfg, writer=None)


def test_a_share_is_refused_by_a_model_that_is_not_of_tokens(tmp_path):
    from tpudist.config import Config
    from tpudist.trainer import Trainer
    cfg = Config(arch="resnet18", batch_size=8, synthetic=True, layers=2,
                 outpath=str(tmp_path / "out"), overwrite="delete")
    with pytest.raises(ValueError, match="vocab_share, which 'resnet18'"):
        Trainer(cfg, writer=None)


@pytest.mark.parametrize("name,build,cut", [
    ("mellum2_12b_ep4", mellum2_12b_a2_5b, (4, 16, 24576)),
    ("sdar_30b_ep8", sdar_30b_a3b, (4, 16, 18992))])
def test_the_configurations_file_keeps_every_published_number(name, build,
                                                              cut):
    """`configs/<name>.json` against the registered model: the widths are
    the published ones, the cut is the three keys in `reduced`; for a model
    of the guide's catalog, every number of its entry but those three."""
    cfg = json.load(open(os.path.join(CHIP, "configs", name + ".json")))
    model = build()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    for key, value in dict(
            hidden_size=model.hidden_size, head_dim=model.head_dim,
            num_attention_heads=model.num_heads,
            num_key_value_heads=model.num_kv_heads,
            num_experts=model.num_experts,
            num_experts_per_tok=model.experts_per_token,
            moe_intermediate_size=model.expert_width,
            vocab_size_published=model.vocab_size,
            num_hidden_layers_published=model.num_layers).items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == cut
    assert cfg["arch"] in cfg["trainer_argv"]
    if name == "mellum2_12b_ep4":
        assert cfg["sliding_window"] == model.sliding_window
        assert cfg["layer_types"] == list(model.layer_types[:4])
        assert cfg["rope_parameters"] == ROPE
        return
    # the published config, key for key (a copy of the catalog's entry: the
    # guide is not in the repo)
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in published.items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert cfg[key] == value, key
    rope_p = model.rope_parameters["full_attention"]
    assert (rope_p["rope_type"], rope_p["rope_theta"]) == (
        "default", cfg["rope_theta"])
    assert set(model.layer_types) == {"full_attention"}
    # the objective comes with the registered model, and the file says so
    assert (model.objective, model.block_length, model.noise_eps) == (
        cfg["objective"], cfg["block_length"], cfg["noise_eps"]) == (
        "block_diffusion", 4, 1e-3)
    held = model.clone(vocab_share=(0, 8), expert_share=(0, 8), layers=4)
    assert held.vocab_held == cfg["vocab_size"]
    assert held.mask_id == cfg["mask_token_id"] == 18991
    assert cfg["expert_share"] == cfg["vocab_share"] == "0 of 8"
    assert "8 chips a layer" in cfg["deployment"]
    for said in ("block_length", "noise schedule", "noise derivation",
                 "logit shift", "mask_token_id", "q and k norm"):
        assert len(cfg["assumed"][said]) > 40, said


# --- training by diffusion over blocks (sdar_tiny) ---------------------------

def sdar_cfg(held=4, share=1, layers=2, vocab=128):
    """The tiny twin's sizes as the reference reads a configuration."""
    return dict(
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, num_hidden_layers=layers, vocab_size=vocab,
        num_experts=16, num_experts_per_tok=2, num_experts_held=held,
        expert_share=f"{share} of {16 // held}", moe_intermediate_size=32,
        rms_norm_eps=1e-6, rope_theta=1000000, reference_block_rows=16,
        block_length=4, noise_eps=1e-3, mask_token_id=vocab - 1,
        adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8, weight_decay=0.1,
        decay_min_ndim=2)


def sdar_model(held=4, share=1, **kw):
    return sdar_tiny(dtype=jnp.float32, expert_share=(share, 16 // held),
                     vocab_share=(0, 2), layers=2, **kw)


def sdar_loss(model, params, stats, x):
    out, mutated = model.apply({"params": params, "batch_stats": stats}, x,
                               train=True, targets=x,
                               mutable=["batch_stats"])
    return out.loss, (out, mutated["batch_stats"])


@pytest.mark.parametrize("t,held,share,flash", [
    (32, 4, 1, False), (32, 4, 3, True), (38, 2, 5, True), (38, 8, 0, False)])
def test_diffusion_loss_and_every_gradient_leaf_match_the_reference(
        t, held, share, flash):
    """`sdar_tiny` against `refs/sdar_30b_ep8.py`: the tree, the loss, every
    gradient leaf, the key the state holds next and the counters, through
    the XLA path and through the kernel (interpreted, rematerialised), a
    share of the experts held, at a length the blocks tile and one they do
    not (38 = 9 blocks of 4 and one of 2)."""
    cfg = sdar_cfg(held, share)
    params, stats = REF_SDAR.init(jax.random.PRNGKey(0), cfg)
    model = sdar_model(held, share, flash=flash, remat=flash)
    x, _ = tokens(t, vocab=127)
    ours = model.init(jax.random.PRNGKey(0), x)
    for mine, theirs in ((ours["params"], params),
                         (ours["batch_stats"], stats)):
        assert [(jax.tree_util.keystr(k), v.shape, v.dtype)
                for k, v in leaves(mine)] == [
            (jax.tree_util.keystr(k), v.shape, v.dtype)
            for k, v in leaves(theirs)]
    with jax.default_matmul_precision("highest"):
        (loss, (out, after)), grads = jax.value_and_grad(
            lambda p: sdar_loss(model, p, stats, x), has_aux=True)(params)
    (want, (carry, routed, masked, weight)), want_grads = jax.value_and_grad(
        REF_SDAR.loss_fn, has_aux=True)(params, stats["noise_key"], x, cfg)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        gap = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12))
        assert gap < 2e-4, (jax.tree_util.keystr(path), gap)
    assert np.array_equal(after["noise_key"], carry)
    assert float(out.counters["bd_masked_share"]) == float(masked)
    assert abs(float(out.counters["bd_weight_sum"]) - float(weight)) < 1e-6
    for layer in range(2):
        assert float(out.counters[f"moe_pairs.layer_{layer}"]) == float(
            jnp.sum(routed[layer]))


@pytest.mark.parametrize("length,block", [(8, 4), (10, 4), (12, 3), (6, 8)])
def test_the_block_diffusion_mask_is_its_four_rules(length, block):
    """`block_diffusion_mask` (what `attention` applies, and the kernel's
    oracle) and the reference's `seen`, against the rules written out pair
    by pair; the pairs allowed count `L (L + block)` where the blocks tile
    the row."""
    got = block_diffusion_mask(2 * length, length, block)
    theirs = np.asarray(REF_SDAR.seen(jnp.arange(2 * length), length, block))
    want = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            noisy_i, noisy_j = i < length, j < length
            n_i, n_j = (i % length) // block, (j % length) // block
            if noisy_i and noisy_j:
                want[i, j] = n_j == n_i
            elif noisy_i:
                want[i, j] = n_j < n_i
            elif not noisy_j:
                want[i, j] = n_j <= n_i
    assert np.array_equal(got, want) and np.array_equal(theirs, want)
    if length % block == 0:
        assert want.sum() == length * (length + block)
    # the clean row alone (generation's view) is causal by blocks
    alone = block_diffusion_mask(length, 0, block)
    assert np.array_equal(alone, want[length:, length:])
    q = jnp.ones((1, 2 * length, 1, 4))
    with pytest.raises(ValueError, match="whole mask"):
        attention(q, q, q, causal=True, block_diffusion=(length, block))


def test_the_doubled_row_is_the_models_definition():
    """What one pass over `[x_t ; x_0]` computes is, block by block, what
    the model is defined to compute: the noised half's logits of block n
    equal a plain forward over `[x_0 blocks < n ; x_t block n]` under the
    clean copy's rule alone (causal by blocks), for every n."""
    model = sdar_model()
    rows, length, block = 2, 24, 4
    x0, _ = tokens(length, rows=rows, vocab=127)
    state = model.init(jax.random.PRNGKey(2), x0)
    _, xt, _, masked = block_noise(state["batch_stats"]["noise_key"], x0,
                                   block, 1e-3, model.mask_id)
    assert bool(masked.any()) and not bool(masked.all())
    with jax.default_matmul_precision("highest"):
        doubled = model.apply(state, x0, noised=xt)
        assert doubled.shape == (rows, length, 128)
        for n in range(length // block):
            lo, hi = n * block, (n + 1) * block
            plain = model.apply(
                state, jnp.concatenate([x0[:, :lo], xt[:, lo:hi]], axis=1))
            np.testing.assert_allclose(doubled[:, lo:hi], plain[:, lo:hi],
                                       atol=2e-5, err_msg=f"block {n}")
    # a next-id model has no noised copy to run
    plain_model = mellum2_tiny(dtype=jnp.float32, layers=2)
    with pytest.raises(ValueError, match="objective"):
        plain_model.apply(plain_model.init(jax.random.PRNGKey(0), x0), x0,
                          noised=xt)


def test_program_and_reference_draw_the_same_noise(tmp_path, mesh8):
    """The draws of t and of the masked positions: bit for bit the
    reference's from the same leaf, different by seed and by step, and the
    leaf survives a save and a restore, so a resumed run draws what the
    uninterrupted one would have."""
    from tpudist import checkpoint
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = sdar_cfg()
    z = REF_SDAR._sizes(cfg)
    x0, _ = tokens(30, rows=3, vocab=127)
    keys = [REF_SDAR.init(jax.random.PRNGKey(seed), cfg)[1]["noise_key"]
            for seed in (0, 1)]
    assert keys[0].dtype == jnp.uint32 and keys[0].shape == (2,)
    drawn = []
    for key in keys:
        ours = jax.jit(lambda k: block_noise(k, x0, 4, 1e-3, 127))(key)
        theirs = jax.jit(lambda k: REF_SDAR.noise(k, x0, z))(key)
        for a, b in zip(ours, theirs):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        drawn.append(ours)
    (carry, xt, weights, masked), other = drawn
    assert not np.array_equal(masked, other[3])             # by seed
    again = block_noise(carry, x0, 4, 1e-3, 127)
    assert not np.array_equal(masked, again[3])             # by step
    assert not np.array_equal(carry, again[0])
    # one t a block: a block's weights are 0 or one value, at most 1 / eps
    w = np.asarray(weights).reshape(3, -1)[:, :28].reshape(3, 7, 4)
    assert all(len(set(blk[blk > 0])) <= 1 for row in w for blk in row)
    assert np.array_equal(np.asarray(xt) == 127,
                          np.asarray(masked) | (np.asarray(x0) == 127))
    assert 1.0 <= float(weights[weights > 0].min()) and float(
        weights.max()) <= 1e3

    # through the trainer's step: the leaf advances as the reference's does,
    # is replicated (never averaged) over eight shards, and comes back from
    # a checkpoint as it was saved
    tcfg = Config(arch="sdar_tiny", batch_size=8, seq_len=32,
                  optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                  use_amp=False, seed=0).finalize(8)
    model = sdar_model()
    state = create_train_state(jax.random.PRNGKey(0), model, tcfg)
    assert state.batch_stats["noise_key"].dtype == jnp.uint32
    x, _ = tokens(32, rows=8, vocab=127)
    step = make_train_step(mesh8, model, tcfg)
    first = np.asarray(state.batch_stats["noise_key"])
    state, metrics = step(state, x, x, jnp.float32(1e-3))
    second = np.asarray(state.batch_stats["noise_key"])
    assert np.array_equal(second, np.asarray(jax.random.split(first)[0]))
    assert 0.0 < float(metrics["bd_masked_share"]) < 1.0
    assert 0.0 < float(metrics["bd_weight_sum"]) < 4.0
    checkpoint.save_checkpoint(
        checkpoint.state_to_dict(state, "sdar_tiny", 0, 0.0), False,
        str(tmp_path))
    fresh = create_train_state(jax.random.PRNGKey(9), model, tcfg)
    assert not np.array_equal(fresh.batch_stats["noise_key"], second)
    restored = checkpoint.restore_train_state(
        fresh, checkpoint.load_checkpoint(str(tmp_path)))
    key = restored.batch_stats["noise_key"]
    assert key.dtype == jnp.uint32 and np.array_equal(key, second)
    resumed, _ = step(restored, x, x, jnp.float32(1e-3))
    onward, _ = step(state, x, x, jnp.float32(1e-3))
    assert np.array_equal(resumed.batch_stats["noise_key"],
                          onward.batch_stats["noise_key"])


def test_diffusion_step_takes_the_references_first_step(mesh8):
    """Through `create_train_state` and `make_train_step`, the path the cell
    runs: with one row a shard the step's loss is the mean over the shards
    of the reference's loss of each row under the same key."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    tcfg = Config(arch="sdar_tiny", batch_size=8, seq_len=32,
                  optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                  use_amp=False, seed=0).finalize(8)
    cfg = sdar_cfg()
    model = sdar_model()
    state = create_train_state(jax.random.PRNGKey(0), model, tcfg)
    params, stats = REF_SDAR.init(jax.random.PRNGKey(3), cfg)
    state = state.replace(params=params, batch_stats=stats)
    x, _ = tokens(32, rows=8, vocab=127)
    with jax.default_matmul_precision("highest"):
        state, metrics = make_train_step(mesh8, model, tcfg)(
            state, x, x, jnp.float32(1e-3))
    want = np.mean([float(REF_SDAR.loss_fn(
        params, stats["noise_key"], x[i:i + 1], cfg)[0]) for i in range(8)])
    assert abs(float(metrics["loss"]) - want) < 1e-5 * want


def _readings(loss, grads):
    """A loss and a gradient as `harness/check.py::compare` reads a side."""
    flat = [np.asarray(g) for g in jax.tree_util.tree_leaves(grads)]
    norms = np.array([np.linalg.norm(g) for g in flat])
    return {"loss": [float(loss)], "first_grad_leaves": flat,
            "first_grad": norms, "param_change": norms}


@pytest.mark.parametrize("fault", [None, "causal_over_2L", "clean_half_scored",
                                   "no_1_over_t", "shifted_targets"])
def test_a_wrong_objective_fails_the_comparison(fault, monkeypatch):
    """The comparison that decides `correct` (`harness/check.py::compare`
    under the tiny twin's limits) passes the program against the reference,
    and fails it against a reference that walks a causal mask over the
    doubled row, scores the clean half, drops the 1 / t, or shifts the
    targets by one: each is a different training run, and at least one
    limit says so."""
    import sys
    sys.path.insert(0, CHIP)
    try:
        from harness import check
    finally:
        sys.path.remove(CHIP)
    tiny = json.load(open(os.path.join(CHIP, "selftest", "tiny",
                                       "sdar_tiny.json")))
    cfg = sdar_cfg()
    params, stats = REF_SDAR.init(jax.random.PRNGKey(0), cfg)
    model = sdar_model()
    x, _ = tokens(32, vocab=127)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: sdar_loss(model, p, stats, x), has_aux=True)(params)

    if fault == "causal_over_2L":
        monkeypatch.setattr(
            REF_SDAR, "seen", lambda at, length, bl: (
                jnp.arange(2 * length)[None, :] <= at[:, None]))

    def theirs(p):
        length = x.shape[1]
        _, xt, weights, m = REF_SDAR.noise(stats["noise_key"], x,
                                           REF_SDAR._sizes(cfg))
        hidden, _ = REF_SDAR.hidden_states(p, xt, x, cfg)
        half, y = hidden[:, :length], x
        if fault == "clean_half_scored":
            half = hidden[:, length:]
        if fault == "no_1_over_t":
            weights = m.astype(jnp.float32)
        if fault == "shifted_targets":
            y = jnp.roll(x, -1, axis=1)
        return REF_SDAR.head_loss(p, half, y, weights, cfg)

    want, want_grads = jax.value_and_grad(theirs)(params)
    names = {k: check.leaf_names(params)
             for k in ("first_grad", "param_change")}
    correct, rows = check.compare(_readings(loss, grads),
                                  _readings(want, want_grads),
                                  tiny["correct_limits"], names)
    assert correct is (fault is None), [r for r in rows if not r[3]]


def test_no_square_of_the_doubled_row_is_in_the_step():
    """With the kernel on, forward and backward of the whole model hold no
    [.., 2L, 2L] and no [.., 2L, L] tensor: attention is handed the mask as
    a statement, never as an array (the XLA path, for contrast, builds the
    scores whole)."""
    length = 40                 # 2L = 80 is no width of the tiny twin
    x, _ = tokens(length, vocab=127)

    def shapes(flash):
        model = sdar_model(flash=flash, remat=flash)
        state = model.init(jax.random.PRNGKey(0), x)
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: sdar_loss(
            model, p, state["batch_stats"], x)[0]))(state["params"])
        return set(_shapes_in(jaxpr.jaxpr))

    def squares(found):
        return {s for s in found if len(s) >= 2 and s[-2] == 2 * length
                and s[-1] in (length, 2 * length)}
    assert not squares(shapes(True))
    assert squares(shapes(False))


@pytest.mark.parametrize("held,share", [(4, 1), (2, 5), (8, 0)])
def test_the_reference_seats_the_mask_tokens_experts(held, share):
    """The seeded weights of a configuration trained by diffusion over
    blocks: each router's columns are relabelled so that exactly one of the
    experts the mask token routes to is held here, whatever the seed and
    the share (left to the seed it is 0 to 3 of them and a quarter of all
    positions follows: `configs/sdar_30b_ep8.json`, "mask token's
    experts"). A relabelling: the columns are the drawn ones, each once."""
    cfg = sdar_cfg(held, share)
    z = REF_SDAR._sizes(cfg)
    mine = range(z["first"], z["first"] + held)
    for seed in range(5):
        params, _ = REF_SDAR.init(jax.random.PRNGKey(seed), cfg)
        row = params["embed"]["embedding"][z["mask"]]
        u = row * jax.lax.rsqrt(jnp.mean(row * row) + 1e-6)
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 2 + 8 * 2))
        drawn = [jax.random.normal(k, (64, 16)) * 0.02
                 for i, k in enumerate(keys) if i >= 2 and (i - 2) % 8 == 4]
        for layer, was in zip(range(2), drawn):
            router = params[f"layer_{layer}"]["moe"]["router"]
            order = np.argsort(-np.asarray(u @ router))
            assert order[0] == z["first"]        # its favourite: first held
            assert sum(e in mine for e in order[:z["k"]]) == 1
            # the rest of the held seats: the experts it favours least
            assert set(order[-(held - 1):]) == set(mine) - {z["first"]}
            assert sorted(map(tuple, np.asarray(router.T))) == sorted(
                map(tuple, np.asarray(was.T)))

