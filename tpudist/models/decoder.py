"""A causal decoder of tokens with sparse experts and mixed attention: the
first language model of the zoo (the zoo's other families classify images).

A layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``:
grouped-query attention (RMSNorm over ``head_dim`` on q and k, then RoPE by
the table of the layer's type: ``sliding_attention`` sees the nearest
``sliding_window`` keys, ``full_attention`` all before it), and a top-k
layer of SwiGLU experts (``parallel/moe.py::moe_topk_held``). The model is an
embedding, the layers, a final RMSNorm and an untied head.

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
``Scored``; without, logits ``[rows, T, vocabulary held]``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
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


class GroupedQueryAttention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_parameters: Any                 # this layer type's entry
    window: Optional[int] = None
    eps: float = 1e-6
    dtype: Any = None
    flash: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, _ = x.shape
        dt = self.dtype or x.dtype

        def proj(heads, name):
            return nn.Dense(heads * self.head_dim, use_bias=False, dtype=dt,
                            kernel_init=_init, name=name)(x).reshape(
                                b, t, heads, self.head_dim)
        q = proj(self.num_heads, "q_proj")
        k = proj(self.num_kv_heads, "k_proj")
        v = proj(self.num_kv_heads, "v_proj")
        cos, sin = rope.tables(dict(self.rope_parameters), self.head_dim, t)
        q = rope.apply(RMSNorm(self.eps, name="q_norm")(q).astype(dt),
                       cos, sin)
        k = rope.apply(RMSNorm(self.eps, name="k_norm")(k).astype(dt),
                       cos, sin)
        if self.flash and not self.is_initializing():
            # (initialisation runs eagerly on a short example row: shapes
            # only, so the XLA path, and no kernel is built for that length)
            from tpudist.ops.pallas import flash_attention
            with jax.named_scope(scopes.ATTN_FUSED):
                out = flash_attention(q, k, v, causal=True,
                                      window=self.window)
        else:
            out = attention(q, k, v, causal=True, window=self.window)
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

    def example_input(self) -> jax.Array:
        """What ``create_train_state`` initialises on: one short row."""
        return jnp.zeros((1, 16), jnp.int32)

    def attention_workloads(self, seq_len: int) -> list[dict]:
        """The attention shapes a step runs, one a layer type kept."""
        kept = self.layer_types[:self.layers or self.num_layers]
        return [dict(seq=seq_len, heads=self.num_heads,
                     kv_heads=self.num_kv_heads, head_dim=self.head_dim,
                     causal=True,
                     window=(self.sliding_window
                             if kind == "sliding_attention" else None))
                for kind in dict.fromkeys(kept)]

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False,
                 targets: Optional[jax.Array] = None):
        dt = self.dtype or jnp.float32
        first_expert, held = _share(self.num_experts,
                                    tuple(self.expert_share), "experts")
        kept = self.layers or self.num_layers
        with jax.named_scope(scopes.LM_EMBED):
            x = nn.Embed(self.vocab_held, self.hidden_size,
                         embedding_init=_init, dtype=dt, name="embed")(tokens)
        counters = {}
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
                          flash=self.flash),
                experts=dict(num_experts=self.num_experts,
                             top_k=self.experts_per_token,
                             width=self.expert_width,
                             first_expert=first_expert, held=held),
                eps=self.rms_norm_eps, dtype=dt, name=f"layer_{i}")(x)
            counters.update({f"{k}.layer_{i}": v
                             for k, v in layer_counters.items()})
        x = RMSNorm(self.rms_norm_eps, name="norm")(x).astype(dt)
        head = self.param("head", _init,
                          (self.hidden_size, self.vocab_held), jnp.float32)
        if targets is None:
            with jax.named_scope(scopes.LM_HEAD):
                return jnp.dot(x, head.astype(dt),
                               preferred_element_type=jnp.float32)
        loss, acc1 = lm_head_loss(x, head, targets, self.loss_chunk)
        return Scored(loss, acc1, counters)


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
