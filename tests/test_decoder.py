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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import mellum2_12b_a2_5b, mellum2_tiny
from tpudist.ops import rope
from tpudist.parallel.moe import moe_topk_held, route_topk
from tpudist.parallel.ring_attention import attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_mellum2_for_tests", os.path.join(
            ROOT, "benchmarks", "chip", "refs", "mellum2_12b_ep4.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
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


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The add-up test: every holder's part of the result, summed over the
    holders, is what the reference gives for the whole layer with all the
    experts held in one place."""
    params = _moe_params(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    z = dict(k=2, first=0, held=8)
    with jax.default_matmul_precision("highest"):
        whole, (pairs, _) = REF._moe(u, params, z, None)
        total, computed = 0.0, 0.0
        for lo in range(0, 8, held):
            y, counters = moe_topk_held(_share(params, lo, held), u, top_k=2,
                                        first_expert=lo)
            part, _ = REF._moe(u, _share(params, lo, held),
                               dict(k=2, first=lo, held=held), None)
            np.testing.assert_allclose(y, part, atol=2e-5)
            total, computed = total + y, computed + counters["moe_pairs"]
    np.testing.assert_allclose(total, whole, atol=5e-5)
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

def test_head_loss_in_chunks_is_the_whole_cross_entropy():
    from tpudist.ops import accuracy, cross_entropy_loss, lm_head_loss
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(ks[0], (2, 37, 16))
    kernel = jax.random.normal(ks[1], (16, 50))
    targets = jax.random.randint(ks[2], (2, 37), 0, 50)

    def whole(h, w):
        return cross_entropy_loss(h @ w, targets)

    with jax.default_matmul_precision("highest"):
        (loss, acc), grads = jax.value_and_grad(
            lambda h, w: lm_head_loss(h, w, targets, chunk=16),
            argnums=(0, 1), has_aux=True)(hidden, kernel)
        want, want_grads = jax.value_and_grad(whole, argnums=(0, 1))(
            hidden, kernel)
        want_acc = accuracy(hidden @ kernel, targets)
    assert abs(float(loss) - float(want)) < 1e-5
    assert abs(float(acc) - float(want_acc)) < 1e-4
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-6)


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


def test_python_m_tpudist_trains_on_synthetic_tokens(tmp_path):
    """The normal entry point's path (`config.from_args` -> `Trainer.fit`)
    on the tiny twin: the loss falls, tokens/s is in the log line, the
    share arrives as statements."""
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    cfg = from_args([
        "--synthetic", "-a", "mellum2_tiny", "--seq-len", "32", "-b", "16",
        "--layers", "2", "--epochs", "2", "--step", "5", "--optimizer", "adamw", "--lr",
        "0.01", "--wd", "0.1", "--adam-b2", "0.95", "--expert-share", "1/4",
        "--vocab-share", "0/2", "--flash", "off", "-j", "2", "-p", "2",
        "--no-telemetry", "--outpath", str(tmp_path / "out"), "--overwrite",
        "delete", "--seed", "0"])
    trainer = Trainer(cfg, writer=None)
    assert trainer.model.vocab_held == 128
    assert trainer.flash_decision["kernel"] == "xla"
    assert "_kv2_" in trainer.flash_decision["key"]
    assert trainer.state.params["layer_0"]["moe"]["gate"].shape == (2, 64, 32)
    trainer.fit()
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    import re
    losses = [float(x) for x in re.findall(
        r"\|\|==> Train: Epoch\[\d+\]\s+Loss ([0-9.e+-]+)", log)]
    assert len(losses) == 2 and losses[1] < losses[0] < math.log(128) + 0.5
    assert re.search(r"Acc@1\s+[0-9.]+\t[0-9.]+ tokens/s", log)


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
    with pytest.raises(ValueError, match="model of tokens"):
        Trainer(cfg, writer=None)


def test_the_configurations_file_keeps_every_published_number():
    """`configs/mellum2_12b_ep4.json` against the registered model: the
    widths are the published ones, the cut is the three keys in `reduced`."""
    cfg = json.load(open(os.path.join(
        ROOT, "benchmarks", "chip", "configs", "mellum2_12b_ep4.json")))
    model = mellum2_12b_a2_5b()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    for key, value in dict(
            hidden_size=model.hidden_size, head_dim=model.head_dim,
            num_attention_heads=model.num_heads,
            num_key_value_heads=model.num_kv_heads,
            num_experts=model.num_experts,
            num_experts_per_tok=model.experts_per_token,
            moe_intermediate_size=model.expert_width,
            sliding_window=model.sliding_window,
            vocab_size_published=model.vocab_size,
            num_hidden_layers_published=model.num_layers).items():
        assert cfg[key] == value, key
    assert cfg["layer_types"] == list(model.layer_types[:4])
    assert cfg["rope_parameters"] == ROPE
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (4, 16, 24576)
