"""A decoder of tokens with sparse experts and mixed attention: the
language models of the zoo (the zoo's other families classify images),
trained to predict the next id under a causal mask or by diffusion over
blocks; which, the registered model says (``objective``).

A layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``:
grouped-query attention (RMSNorm over ``head_dim`` on q and k, then RoPE by
the table of the layer's type: ``sliding_attention`` sees the nearest
``sliding_window`` keys, ``full_attention`` all before it), and a top-k
layer of SwiGLU experts (``parallel/moe.py::moe_topk_held``). The model is an
embedding, the layers, a final RMSNorm and an untied head.

**Diffusion over blocks** (``objective="block_diffusion"``; SDAR,
arXiv:2510.06303, trained as BD3-LM's vectorised form, arXiv:2503.09573,
with LLaDA's masking and ``1 / t`` weights, arXiv:2502.09992). A row ``x_0``
of ``L`` ids in blocks of ``block_length``: each block draws ``t ~ U[eps,
1]``, each of its positions is masked with probability ``t`` (``x_t``:
``MASK`` there, the id elsewhere; ``block_noise``). One pass sees ``[x_t ;
x_0]``, ``2 L`` positions that count ``0 .. L - 1`` twice, under the mask
``block_diffusion = (L, block_length)`` that attention is handed as a
statement (``parallel/ring_attention.py::block_diffusion_mask``), never as
an array: a noised position sees its own noised block and the clean blocks
before it. The head reads the noised half only, and the loss is ``sum(m / t
* cross entropy against x_0) / (rows * L)``: a masked position's logits
predict that position's id, no shift. The draws come from a non-trainable
leaf of the state, ``batch_stats["noise_key"]`` (a raw ``uint32[2]`` key,
drawn at initialisation and split every training step), so a restored run
draws the masks it would have drawn. Without ``targets`` such a model reads
its row under the clean copy's rule alone (causal by blocks), as generation
does.

**A deployment's share.** The published widths live with the registered
name (``mellum2_12b_a2_5b``). What one chip of a deployment holds arrives as
statements of its share, never as free widths: ``layers`` kept (the leading
ones of the pattern; the rest are further pipeline stages), ``expert_share``
and ``vocab_share`` as ``(i, n)``: this holder is the i-th of n that divide
each layer's experts and the vocabulary's rows between them. The router
keeps its published width and its experts per token; the layer computes the
held experts' part of the result; ids and logits are over the rows held.

Precision: float32 parameters, norms, router, softmaxes and loss; products
and activations in ``dtype``.

Given ``targets`` the model takes the loss itself (``ops.lm_head_loss``: the
head and the cross entropy a chunk of positions at a time) and returns a
``Scored``; without, logits ``[rows, T, vocabulary held]``. (Under diffusion
the row is its own target: ``targets`` only says that a loss is wanted.)
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.obs import scopes
from tpudist.ops import rope
from tpudist.ops.loss import Scored, lm_head_loss
from tpudist.parallel.moe import moe_topk_held
from tpudist.parallel.ring_attention import attention

_init = nn.initializers.normal(stddev=0.02)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        """float32 in and out of the arithmetic; the caller casts."""
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps
        ) * scale


def _share(count: int, share: tuple[int, int], what: str) -> tuple[int, int]:
    """(first, how many) of ``count`` that holder ``i`` of ``n`` holds."""
    i, n = share
    if not 0 <= i < n or count % n:
        raise ValueError(f"{what}: share {i} of {n} does not divide "
                         f"{count}")
    return i * (count // n), count // n


def block_noise(key: jax.Array, tokens: jax.Array, block: int, eps: float,
                mask_id: int):
    """One step's noise of training by diffusion over blocks, from the raw
    ``uint32[2]`` key the state holds: ``(the key the state holds next, x_t,
    weights, masked)`` for ``tokens`` [rows, L]. ``next, use = split(key)``;
    ``k_t, k_m = split(use)``; a block's ``t = uniform(k_t, [rows, ceil(L /
    block)], eps, 1)``; position ``i`` is masked where ``uniform(k_m, [rows,
    L]) < t`` of its block; ``x_t`` holds ``mask_id`` there; ``weights = 1 /
    t`` there, 0 elsewhere. All float32: a reference that follows this
    derivation draws the same masks bit for bit."""
    rows, length = tokens.shape
    carry, use = jax.random.split(key)
    k_t, k_m = jax.random.split(use)
    t = jax.random.uniform(k_t, (rows, -(-length // block)), jnp.float32,
                           minval=eps, maxval=1.0)
    t = jnp.repeat(t, block, axis=1)[:, :length]
    masked = jax.random.uniform(k_m, (rows, length), jnp.float32) < t
    return (carry, jnp.where(masked, mask_id, tokens),
            jnp.where(masked, 1.0 / t, 0.0), masked)


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_parameters: Any                 # this layer type's entry
    window: Optional[int] = None
    # (L, block): the row is a noised copy of L ids, then the clean ids
    block_diffusion: Optional[tuple] = None
    eps: float = 1e-6
    dtype: Any = None
    flash: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, _ = x.shape
        dt = self.dtype or x.dtype
        positions, mask = t, dict(causal=True, window=self.window)
        if self.block_diffusion is not None:
            mask = dict(block_diffusion=tuple(self.block_diffusion))
            noisy = self.block_diffusion[0]
            # each copy counts its positions from 0
            positions = np.concatenate([np.arange(noisy),
                                        np.arange(t - noisy)])

        def proj(heads, name):
            return nn.Dense(heads * self.head_dim, use_bias=False, dtype=dt,
                            kernel_init=_init, name=name)(x).reshape(
                                b, t, heads, self.head_dim)
        q = proj(self.num_heads, "q_proj")
        k = proj(self.num_kv_heads, "k_proj")
        v = proj(self.num_kv_heads, "v_proj")
        cos, sin = rope.tables(dict(self.rope_parameters), self.head_dim,
                               positions)
        q = rope.apply(RMSNorm(self.eps, name="q_norm")(q).astype(dt),
                       cos, sin)
        k = rope.apply(RMSNorm(self.eps, name="k_norm")(k).astype(dt),
                       cos, sin)
        if self.flash and not self.is_initializing():
            # (initialisation runs eagerly on a short example row: shapes
            # only, so the XLA path, and no kernel is built for that length)
            from tpudist.ops.pallas import flash_attention
            with jax.named_scope(scopes.ATTN_FUSED):
                out = flash_attention(q, k, v, **mask)
        else:
            out = attention(q, k, v, **mask)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=dt,
                        kernel_init=_init, name="o_proj")(
                            out.reshape(b, t, -1))


class SparseExperts(nn.Module):
    """The held experts of a top-k layer and the router over all of them."""
    num_experts: int
    top_k: int
    width: int
    first_expert: int
    held: int
    dtype: Any = None

    @nn.compact
    def __call__(self, normed: jax.Array):
        b, t, d = normed.shape
        dt = self.dtype or normed.dtype
        params = {
            "router": self.param("router", _init, (d, self.num_experts),
                                 jnp.float32),
            "gate": self.param("gate", _init, (self.held, d, self.width),
                               jnp.float32),
            "up": self.param("up", _init, (self.held, d, self.width),
                             jnp.float32),
            "down": self.param("down", _init, (self.held, self.width, d),
                               jnp.float32),
        }
        flat = normed.reshape(b * t, d)
        y, counters = moe_topk_held(
            params, flat.astype(dt), top_k=self.top_k,
            first_expert=self.first_expert, router_input=flat)
        return y.reshape(b, t, d), counters


class DecoderLayer(nn.Module):
    attn: dict
    experts: dict
    eps: float
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array):
        dt = self.dtype or x.dtype
        y = RMSNorm(self.eps, name="input_norm")(x).astype(dt)
        x = x + GroupedQueryAttention(**self.attn, eps=self.eps, dtype=dt,
                                      name="self_attention")(y)
        y = RMSNorm(self.eps, name="post_norm")(x)
        y, counters = SparseExperts(**self.experts, dtype=dt, name="moe")(y)
        return x + y, counters


class MoEDecoder(nn.Module):
    # published
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_per_token: int
    expert_width: int
    layer_types: Sequence[str]
    rope_parameters: Any                 # {layer type: its parameters}
    sliding_window: int
    rms_norm_eps: float = 1e-6
    # this holder's share of a deployment
    layers: int = 0                      # leading layers kept (0: all)
    expert_share: tuple = (0, 1)         # (i, n): the i-th of n holders
    vocab_share: tuple = (0, 1)
    # what it is trained to do
    objective: str = "next_id"           # | "block_diffusion"
    block_length: int = 0                # positions a block (diffusion)
    noise_eps: float = 1e-3              # the least t a block draws
    # how it runs
    dtype: Any = None
    flash: bool = False                  # the Pallas streaming kernel
    remat: bool = False                  # jax.checkpoint each layer
    loss_chunk: int = 2048

    takes_targets = True                 # train.py: the loss is taken here

    @property
    def vocab_held(self) -> int:
        return _share(self.vocab_size, tuple(self.vocab_share),
                      "vocabulary")[1]

    @property
    def mask_id(self) -> int:
        """The id a noised position holds: the last of the slice held."""
        return self.vocab_held - 1

    def example_input(self) -> jax.Array:
        """What ``create_train_state`` initialises on: one short row."""
        return jnp.zeros((1, 16), jnp.int32)

    def attention_workloads(self, seq_len: int) -> list[dict]:
        """The attention shapes a step runs, one a layer type kept."""
        kept = self.layer_types[:self.layers or self.num_layers]
        shape = dict(heads=self.num_heads, kv_heads=self.num_kv_heads,
                     head_dim=self.head_dim)
        if self.objective == "block_diffusion":
            # one mask whatever the layer's type: the doubled row's
            return [dict(shape, seq=2 * seq_len, causal=False, window=None,
                         block_diffusion=(seq_len, self.block_length))]
        return [dict(shape, seq=seq_len, causal=True,
                     window=(self.sliding_window
                             if kind == "sliding_attention" else None))
                for kind in dict.fromkeys(kept)]

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False,
                 targets: Optional[jax.Array] = None,
                 noised: Optional[jax.Array] = None):
        """``noised`` (diffusion only, for a caller that noises the row
        itself): the copy ``x_t`` of ``tokens`` to run beside it; the logits
        returned are the noised half's."""
        dt = self.dtype or jnp.float32
        first_expert, held = _share(self.num_experts,
                                    tuple(self.expert_share), "experts")
        kept = self.layers or self.num_layers
        counters, mask, weights = {}, None, None
        length = tokens.shape[1]
        if self.objective == "block_diffusion":
            key = self.variable(
                "batch_stats", "noise_key", lambda: _raw_key(
                    self.make_rng("params")))
            with jax.named_scope(scopes.BD_NOISE):
                if targets is not None and noised is None:
                    carry, noised, weights, masked = block_noise(
                        key.value, tokens, self.block_length,
                        self.noise_eps, self.mask_id)
                    counters = {
                        scopes.BD_MASKED: jnp.mean(
                            masked.astype(jnp.float32)),
                        scopes.BD_WEIGHT: jnp.sum(weights) / masked.size}
                    if train and self.is_mutable_collection("batch_stats"):
                        key.value = carry
                # [x_t ; x_0] under the doubled row's mask; a row alone
                # under the clean copy's rule
                mask = (0 if noised is None else length, self.block_length)
                if noised is not None:
                    tokens = jnp.concatenate([noised, tokens], axis=1)
        elif self.objective != "next_id" or noised is not None:
            raise ValueError(f"objective {self.objective!r} (next_id | "
                             f"block_diffusion; noised: {noised is not None})")
        with jax.named_scope(scopes.LM_EMBED):
            x = nn.Embed(self.vocab_held, self.hidden_size,
                         embedding_init=_init, dtype=dt, name="embed")(tokens)
        for i, kind in enumerate(self.layer_types[:kept]):
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"layer {i}: unknown layer type {kind!r}")
            layer = DecoderLayer
            if self.remat:
                # everything of a layer is made again in the backward pass
                # but the attention kernel's two results (0.4 GB a layer at
                # two sequences of 8,192): its forward runs once
                from tpudist.ops.pallas.flash_attention import SAVED_BY_NAME
                layer = nn.remat(
                    DecoderLayer,
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *SAVED_BY_NAME))
            x, layer_counters = layer(
                attn=dict(num_heads=self.num_heads,
                          num_kv_heads=self.num_kv_heads,
                          head_dim=self.head_dim,
                          rope_parameters=self.rope_parameters[kind],
                          window=(self.sliding_window
                                  if kind == "sliding_attention" else None),
                          block_diffusion=mask, flash=self.flash),
                experts=dict(num_experts=self.num_experts,
                             top_k=self.experts_per_token,
                             width=self.expert_width,
                             first_expert=first_expert, held=held),
                eps=self.rms_norm_eps, dtype=dt, name=f"layer_{i}")(x)
            counters.update({f"{k}.layer_{i}": v
                             for k, v in layer_counters.items()})
        if noised is not None:
            x = x[:, :length]            # the head reads the noised half
        x = RMSNorm(self.rms_norm_eps, name="norm")(x).astype(dt)
        head = self.param("head", _init,
                          (self.hidden_size, self.vocab_held), jnp.float32)
        if targets is None:
            with jax.named_scope(scopes.LM_HEAD):
                return jnp.dot(x, head.astype(dt),
                               preferred_element_type=jnp.float32)
        if weights is not None:
            # a masked position's logits against that position's clean id,
            # m / t each, over rows x L
            targets = tokens[:, length:]
        loss, acc1 = lm_head_loss(x, head, targets, self.loss_chunk,
                                  weights=weights)
        return Scored(loss, acc1, counters)


def _raw_key(key: jax.Array) -> jax.Array:
    """A key as the raw ``uint32[2]`` a state can hold as numbers."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return key


def _own(kw: dict) -> dict:
    """The zoo's uniform constructor arguments less the classifiers'."""
    return {k: v for k, v in kw.items()
            if k not in ("num_classes", "sync_batchnorm", "bn_axis_name")}


def mellum2_12b_a2_5b(dtype: Any = None, **kw) -> MoEDecoder:
    """Mellum2-12B-A2.5B (JetBrains; ``config.json`` of
    huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type``
    ``mellum``): 28 layers of hidden 2,304, 32 query heads
    over 4 key-value heads of 128, 64 experts of width 896 with 8 a token,
    three sliding-window layers (1,024; plain RoPE) then one full (YaRN),
    seven times; vocabulary 98,304, untied."""
    return MoEDecoder(
        vocab_size=98304, hidden_size=2304, num_layers=28, num_heads=32,
        num_kv_heads=4, head_dim=128, num_experts=64, experts_per_token=8,
        expert_width=896,
        layer_types=(("sliding_attention",) * 3 + ("full_attention",)) * 7,
        rope_parameters={
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000},
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782}},
        sliding_window=1024, rms_norm_eps=1e-6, dtype=dtype, **_own(kw))


def mellum2_tiny(dtype: Any = None, **kw) -> MoEDecoder:
    """The CPU tests' twin of the layer above at toy widths (hidden 64, 8
    heads over 2 of 16, 8 experts of width 32 with 2 a token, window 8,
    two layer types, 256 ids): for `python -m tpudist` and the benchmark's
    harness to run in seconds without a chip. Never a benchmark
    configuration: its numbers measure overheads."""
    published = mellum2_12b_a2_5b()
    kw.setdefault("loss_chunk", 64)
    return MoEDecoder(
        vocab_size=256, hidden_size=64, num_layers=4, num_heads=8,
        num_kv_heads=2, head_dim=16, num_experts=8, experts_per_token=2,
        expert_width=32,
        layer_types=("sliding_attention", "full_attention") * 2,
        rope_parameters=published.rope_parameters, sliding_window=8,
        rms_norm_eps=1e-6, dtype=dtype, **_own(kw))


def sdar_30b_a3b(dtype: Any = None, **kw) -> MoEDecoder:
    """SDAR-30B-A3B-Chat (JetLM; ``config.json`` of
    huggingface.co/JetLM/SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``): 48
    layers of hidden 2,048, 32 query heads over 4 key-value heads of 128,
    every layer 128 experts of width 768 with 8 a token (no dense layer, no
    shared expert), full attention with plain RoPE (theta 1e6), vocabulary
    151,936, untied. Trained by diffusion over blocks (arXiv:2510.06303);
    the block length 4 is the released Chat model's default, ``eps`` 1e-3
    LLaDA's (neither is in the config)."""
    return MoEDecoder(
        vocab_size=151936, hidden_size=2048, num_layers=48, num_heads=32,
        num_kv_heads=4, head_dim=128, num_experts=128, experts_per_token=8,
        expert_width=768, layer_types=("full_attention",) * 48,
        rope_parameters={"full_attention": {"rope_type": "default",
                                            "rope_theta": 1000000}},
        sliding_window=0, rms_norm_eps=1e-6, objective="block_diffusion",
        block_length=4, noise_eps=1e-3, dtype=dtype, **_own(kw))


def sdar_tiny(dtype: Any = None, **kw) -> MoEDecoder:
    """The CPU tests' twin of the model above at toy widths (hidden 64, 8
    heads over 2 of 16, 16 experts of width 32 with 2 a token, blocks of 4,
    256 ids), as ``mellum2_tiny`` is of its model: never a benchmark
    configuration."""
    kw.setdefault("loss_chunk", 64)
    return MoEDecoder(
        vocab_size=256, hidden_size=64, num_layers=4, num_heads=8,
        num_kv_heads=2, head_dim=16, num_experts=16, experts_per_token=2,
        expert_width=32, layer_types=("full_attention",) * 4,
        rope_parameters=sdar_30b_a3b().rope_parameters, sliding_window=0,
        rms_norm_eps=1e-6, objective="block_diffusion", block_length=4,
        noise_eps=1e-3, dtype=dtype, **_own(kw))
