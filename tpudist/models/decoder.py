"""A decoder of tokens with sparse experts and mixed sequence mixers: the
language models of the zoo (the zoo's other families classify images),
trained to predict the next id under a causal mask or by diffusion over
blocks; which, the registered model says (``objective``).

The model is an embedding, the layers, a final RMSNorm and an untied head. A
layer is one of the kinds of ``LAYER_KINDS``, as the registered model's
``layer_types`` lists them:

- a pair, ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``
  (``sliding_attention``, ``full_attention``): grouped-query attention
  (RMSNorm over ``head_dim`` on q and k, then RoPE by the table of the
  layer's type: ``sliding_attention`` sees the nearest ``sliding_window``
  keys, ``full_attention`` all before it), and a top-k layer of experts
  (``parallel/moe.py::moe_topk_held``); in a model that states a
  ``dense_width`` the pair's second half is a dense SwiGLU of that width
  and each half lies between two norms, ``h = x + RMSNorm(Attn(RMSNorm(
  x)))``, ``y = h + RMSNorm(MLP(RMSNorm(h)))`` (``DenseLayer``); in a model
  that states ``latent`` the pair's attention is latent attention
  (``LatentAttention``: two low-rank paths with a norm inside each, a part
  of a head rotated, one rotated key head for all; docs/ATTENTION.md), and
  the first ``leading_dense`` pairs' second half is a dense SwiGLU behind
  the same single norm;
- one mixer behind one norm, ``x + Mixer(RMSNorm(x))`` (``mamba``, ``moe``,
  ``attention``; a model that publishes its layers as a string of letters
  names them ``M``, ``E``, ``*``: ``layer_types_of``): a Mamba-2 mixer
  (``Mamba2Mixer``, the chunked scan of ``ops/ssd.py``), a top-k layer of
  experts, or causal grouped-query attention over all earlier keys.

What the attention does to q and k (q/k RMSNorm, RoPE or neither: a model
whose Mamba layers carry position rotates nothing), the router's rule (a
softmax's, or sigmoid scores with a bias that chooses and a scaling factor),
the experts' body (SwiGLU, or ungated ``relu^2``), a shared expert beside
the routed ones (the same body), a dense feed-forward in their place, latent
attention, a multi-token-prediction module and how often the layers run
(below) are statements of the registered model, never flags.

**A multi-token-prediction module** (``mtp_depth = 1``; DeepSeek-V3,
arXiv:2412.19437; docs/MTP.md). Behind the kept layers, whatever ``layers``
keeps: ``m = [RMSNorm(Emb(y)) ; RMSNorm(h_L)] W_eh`` with ``y`` the targets
(each position's NEXT id) and ``h_L`` the layers' result before the final
norm, one more block of its own leaves, its own norm, then the model's OWN
head (and ``Emb`` is the model's own embedding: shared leaves, one copy).
Position ``i`` predicts ``y_(i + 1)``; the last has none in the row (weight
0). The loss is ``L_main + mtp_weight L_mtp``; accuracy stays the main
head's; both cross entropies ride the counters.

**Layers run several times** (``loop_steps = T > 1``; Ouro,
arXiv:2510.25741; docs/LOOPED.md). The kept layers and the final norm are
one pass, run ``T`` times over the same leaves: ``h_t = Norm_f(Layers(h_{t
- 1}))``, ``h_0`` the embedding's rows, and ``h_t`` is read by the head and
by an exit gate, ``lambda_t = sigmoid(w_g . h_t + b_g)`` a position, AND
goes on into pass ``t + 1``. With ``S_1 = 1``, ``S_{t + 1} = S_t (1 -
lambda_t)`` a position exits at pass ``t`` with probability ``p_t =
lambda_t S_t`` (``p_T = S_T``: what is left), and the loss is the mean over
rows x L of ``sum_t p_t CE(h_t W_head, y) - exit_beta H(p)``, ``H(p) = -
sum_t p_t log p_t``: the expected cross entropy over the exit step, less an
entropy bonus under a uniform prior. One ``nn.scan`` over the passes with
the parameters broadcast: a pass is traced once, ``S_t`` and the sums ride
the carry, and each pass takes its ``lm_head_loss(h_t, head, y, weights =
p_t)`` where its ``h_t`` is made (the weights are differentiated: the gate
learns through them). Top-1 accuracy is the last pass's; without
``targets`` the logits are.

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

Precision: float32 parameters, norms, router, exit gate, softmaxes and loss;
products and activations in ``dtype``.

Given ``targets`` the model takes the loss itself (``ops.lm_head_loss``: the
head and the cross entropy a chunk of positions at a time, its gradients
taken in the same loop; rematerialised under the scan over passes:
``head_plan``) and returns a ``Scored``; without, logits ``[rows, T,
vocabulary held]``. (Under diffusion the row is its own target: ``targets``
only says that a loss is wanted.)
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpudist.obs import scopes
from tpudist.ops import rope, ssd
from tpudist.ops.loss import Scored, head_chunk, lm_head_loss
from tpudist.parallel.moe import moe_topk_held, shared_expert
from tpudist.parallel.ring_attention import attention

_init = nn.initializers.normal(stddev=0.02)

# a layer's kind: the two pairs of attention and experts, and the three
# blocks of one mixer; the letters a published pattern string names them by
PAIR_KINDS = ("sliding_attention", "full_attention")
PATTERN_KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
LAYER_KINDS = PAIR_KINDS + tuple(PATTERN_KINDS.values())


def layer_types_of(pattern: str) -> tuple[str, ...]:
    """The layer kinds of a published pattern string, a letter a block."""
    unknown = sorted(set(pattern) - set(PATTERN_KINDS))
    if unknown:
        raise ValueError(
            f"layer pattern {pattern!r}: unknown letter(s) {unknown} (known: "
            + ", ".join(f"{k!r} = {v}" for k, v in PATTERN_KINDS.items())
            + ")")
    return tuple(PATTERN_KINDS[c] for c in pattern)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array, weight_only: bool = False) -> jax.Array:
        """float32 in and out of the arithmetic; the caller casts.
        ``weight_only``: the weight alone, for a caller whose kernel norms
        (``ops/pallas/qk_norm_rope.py``)."""
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        if weight_only:
            return scale
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
    rope_parameters: Any                 # this layer type's entry; None: no
    #                                      rotation (position comes from
    #                                      elsewhere in the model)
    qk_norm: bool = True                 # RMSNorm over head_dim on q and k
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
        # a part of a block each (`scopes.STEP_PARTS`); the moves into the
        # kernels' layout read under whichever part roots their fusion
        with jax.named_scope(scopes.ATTN_MIXER):
            with jax.named_scope(scopes.ATTN_QKV_PROJ):
                q = proj(self.num_heads, "q_proj")
                k = proj(self.num_kv_heads, "k_proj")
                v = proj(self.num_kv_heads, "v_proj")
            # (initialisation runs eagerly on a short example row: shapes
            # only, so the XLA path, and no kernel is built for that length)
            flash = self.flash and not self.is_initializing()
            # one Pallas pass that writes q and k where the attention
            # kernels read them, where the shape allows (read from it,
            # never chosen); else these lines, and XLA's move behind them
            laid = self.qk_plan(b, t, flash)["kernel"] == "pallas"
            with jax.named_scope(scopes.ATTN_QK_NORM_ROPE):
                norms = [RMSNorm(self.eps, name=f"{n}_norm")
                         for n in "qk"] if self.qk_norm else None
                cos = sin = None
                if self.rope_parameters is not None:
                    cos, sin = rope.tables(dict(self.rope_parameters),
                                           self.head_dim, positions)
                if laid:
                    from tpudist.ops.pallas.qk_norm_rope import qk_norm_rope
                    q_scale, k_scale = ([norm(x, weight_only=True)
                                         for norm, x in zip(norms, (q, k))]
                                        if norms else (None, None))
                    q, k = qk_norm_rope(
                        q.reshape(b, t, -1), k.reshape(b, t, -1),
                        kv_heads=self.num_kv_heads, q_scale=q_scale,
                        k_scale=k_scale, cos=cos, sin=sin, eps=self.eps)
                else:
                    if norms:
                        q, k = norms[0](q).astype(dt), norms[1](k).astype(dt)
                    if cos is not None:
                        q, k = (rope.apply(q, cos, sin),
                                rope.apply(k, cos, sin))
            if flash:
                from tpudist.ops.pallas import (flash_attention,
                                                flash_attention_laid)
                with jax.named_scope(scopes.ATTN_FUSED):
                    out = (flash_attention_laid if laid
                           else flash_attention)(q, k, v, **mask)
            else:
                out = attention(q, k, v, **mask)
            with jax.named_scope(scopes.ATTN_OUT_PROJ):
                return nn.Dense(x.shape[-1], use_bias=False, dtype=dt,
                                kernel_init=_init, name="o_proj")(
                                    out.reshape(b, t, -1))

    def qk_plan(self, rows: int, seq_len: int, flash: bool) -> dict:
        """Which program norms and rotates this layer's q and k at ``rows``
        rows of ``seq_len`` positions (``qk_norm_rope.qk_plan``: read from
        the shape and the layer's fields)."""
        from tpudist.ops.pallas.qk_norm_rope import qk_plan
        return qk_plan(
            rows, seq_len, self.num_heads, self.num_kv_heads, self.head_dim,
            norm=self.qk_norm, rotate=self.rope_parameters is not None,
            flash=flash, window=self.window,
            block_diffusion=(None if self.block_diffusion is None
                             else tuple(self.block_diffusion)))


class SparseExperts(nn.Module):
    """The held experts of a top-k layer, the router over all of them and,
    where the model has one, the shared expert every token visits (whole on
    every holder; ``act``'s body at its own width: ``moe.shared_expert``
    states both). ``router="sigmoid"`` reads a bias that chooses and does
    not weigh, ``e_score_correction_bias``: a non-trainable leaf (in
    ``batch_stats``), zero at initialisation, which a restored state brings
    with it and nothing here updates (the rate of the balancing rule that
    moves it is no part of a published config)."""
    num_experts: int
    top_k: int
    width: int
    first_expert: int
    held: int
    router: str = "softmax"              # | "sigmoid" (parallel/moe.py)
    routed_scaling: float = 1.0
    act: str = "swiglu"                  # | "relu2": ungated, no ``gate``
    shared_width: int = 0                # the shared expert's; 0: none
    dtype: Any = None

    @nn.compact
    def __call__(self, normed: jax.Array):
        b, t, d = normed.shape
        dt = self.dtype or normed.dtype
        if self.act not in ("swiglu", "relu2"):
            raise ValueError(f"expert body {self.act!r} (swiglu | relu2)")

        def held(name, *shape):
            return self.param(name, _init, (self.held, *shape), jnp.float32)
        params = {"router": self.param("router", _init,
                                       (d, self.num_experts), jnp.float32)}
        if self.act == "swiglu":
            params["gate"] = held("gate", d, self.width)
            params["up"] = held("up", d, self.width)
        else:
            # [held, width, d], as a Linear's weight lies ([out, in]): both
            # of an expert's matrices then end in the hidden size (a width
            # of 1,856 is no whole number of lane tiles:
            # ops/pallas/grouped_matmul.py::_transposed says what that cost)
            params["up"] = held("up", self.width, d)
        params["down"] = held("down", self.width, d)
        if self.router == "sigmoid":
            params["router_bias"] = self.variable(
                "batch_stats", "e_score_correction_bias", jnp.zeros,
                (self.num_experts,), jnp.float32).value
        flat = normed.reshape(b * t, d)

        def cast():
            # the cast behind the block's norm (the router reads float32)
            with jax.named_scope(scopes.BLOCK_NORM):
                return flat.astype(dt)
        y, counters = moe_topk_held(
            params, cast(), top_k=self.top_k, first_expert=self.first_expert,
            router_input=flat, rule=self.router, scale=self.routed_scaling)
        if self.shared_width:
            def whole(name, *shape):
                return self.param(f"shared_{name}", _init, shape, jnp.float32)
            shared = {"gate": whole("gate", d, self.shared_width)
                      } if self.act == "swiglu" else {}
            shared["up"] = whole("up", d, self.shared_width)
            shared["down"] = whole("down", self.shared_width, d)
            y = y + shared_expert(shared, cast())
        return y.reshape(b, t, d), counters


def _dt_bias_init(low: float, high: float, floor: float):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in [low,
    high], floored (Mamba's initialisation)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (np.log(high) - np.log(low)) + np.log(low))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _conv_init(taps: int):
    """torch's ``Conv1d`` default for a depthwise kernel of ``taps``: U(-1 /
    sqrt(taps), 1 / sqrt(taps)), kernel and bias alike."""
    bound = 1.0 / np.sqrt(taps)

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class Mamba2Mixer(nn.Module):
    """A Mamba-2 mixer (arXiv:2405.21060; ``ops/ssd.py`` has the equations
    and what is float32). ``in_proj`` -> the gate ``z`` [H P], ``xBC`` [H P
    + 2 G N] and ``dt`` [H]; a causal depthwise convolution with bias over
    ``xBC``, then SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
    a head; the selective recurrence by a chunked scan, plus ``D x``; ``y <-
    RMSNorm_by_group(y * silu(z)) * w``; ``out_proj``. No bias but the
    convolution's. Returns (y, counters): ``ssm_dt_mean``,
    ``ssm_chunk_carry_min``."""
    num_heads: int                       # H
    head_dim: int                        # P
    state: int                           # N
    groups: int                          # G: heads that share B and C
    conv: int                            # the convolution's taps
    chunk: int
    time_step: tuple                     # (min, max, floor) of dt at init
    out_scale: float = 1.0               # on out_proj's initial N(0, 0.02)
    eps: float = 1e-5
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array):
        b, t, d = x.shape
        dt_ = self.dtype or x.dtype
        heads, inner = self.num_heads, self.num_heads * self.head_dim
        gn = self.groups * self.state
        with jax.named_scope(scopes.SSM_MIXER):
            with jax.named_scope(scopes.SSM_IN_PROJ):
                proj = nn.Dense(2 * inner + 2 * gn + heads, use_bias=False,
                                dtype=dt_, kernel_init=_init,
                                name="in_proj")(x)
                z, dt_raw = proj[..., :inner], proj[..., 2 * inner + 2 * gn:]
            kernel = self.param("conv_kernel", _conv_init(self.conv),
                                (self.conv, inner + 2 * gn), jnp.float32)
            bias = self.param("conv_bias", _conv_init(self.conv),
                              (inner + 2 * gn,), jnp.float32)
            with jax.named_scope(scopes.SSM_CONV):
                # columns inner to 2 inner + 2 gn of the projection's
                # result; x, B and C come back apart, as the scan reads them
                xs, bm, cm = ssd.conv_silu_split(proj, kernel, bias, inner,
                                                 (inner, gn, gn))
            dt_bias = self.param("dt_bias", _dt_bias_init(*self.time_step),
                                 (heads,), jnp.float32)
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=dtype)),
                (heads,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (heads,),
                              jnp.float32)
            with jax.named_scope(scopes.SSM_SCAN):
                step = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
                y, carry_min = ssd.ssd_scan(
                    xs.reshape(b, t, heads, self.head_dim), step,
                    -jnp.exp(a_log), bm.reshape(b, t, self.groups, self.state),
                    cm.reshape(b, t, self.groups, self.state), skip,
                    self.chunk)
            weight = self.param("norm_scale", nn.initializers.ones,
                                (inner,), jnp.float32)
            with jax.named_scope(scopes.SSM_GATE_NORM):
                y = ssd.gated_group_norm(y.reshape(b, t, inner), z, weight,
                                         self.groups, self.eps).astype(dt_)
            with jax.named_scope(scopes.SSM_OUT_PROJ):
                out = nn.Dense(
                    d, use_bias=False, dtype=dt_,
                    kernel_init=nn.initializers.normal(
                        stddev=0.02 * self.out_scale), name="out_proj")(y)
        return out, {scopes.SSM_DT: jnp.mean(step),
                     scopes.SSM_CARRY: carry_min}


class DecoderLayer(nn.Module):
    """``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``: ``attn``
    the fields of ``GroupedQueryAttention`` or of ``LatentAttention`` (told
    apart by ``kv_rank``), ``FFN`` the experts, or with ``dense_width`` a
    dense SwiGLU of that width (a model's leading dense layers)."""
    attn: dict
    experts: dict
    eps: float
    dtype: Any = None
    dense_width: int = 0

    @nn.compact
    def __call__(self, x: jax.Array):
        dt = self.dtype or x.dtype
        with jax.named_scope(scopes.BLOCK_NORM):
            y = RMSNorm(self.eps, name="input_norm")(x).astype(dt)
        attention_of = (LatentAttention if "kv_rank" in self.attn
                        else GroupedQueryAttention)
        y = attention_of(**self.attn, eps=self.eps, dtype=dt,
                         name="self_attention")(y)
        with jax.named_scope(scopes.BLOCK_NORM):
            x = x + y
            y = RMSNorm(self.eps, name="post_norm")(x)
            if self.dense_width:
                y = y.astype(dt)
        if self.dense_width:
            y, counters = DenseMLP(self.dense_width, dtype=dt,
                                   name="mlp")(y), {}
        else:
            y, counters = SparseExperts(**self.experts, dtype=dt,
                                        name="moe")(y)
        with jax.named_scope(scopes.BLOCK_NORM):
            return x + y, counters


class DenseMLP(nn.Module):
    """A dense SwiGLU, ``down(silu(gate(x)) * up(x))``, no bias."""
    width: int
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        dt = self.dtype or x.dtype

        def proj(features, name):
            return nn.Dense(features, use_bias=False, dtype=dt,
                            kernel_init=_init, name=name)
        with jax.named_scope(scopes.DENSE_MLP):
            h = jax.nn.silu(proj(self.width, "gate_proj")(x)) * proj(
                self.width, "up_proj")(x)
            return proj(x.shape[-1], "down_proj")(h)


class DenseLayer(nn.Module):
    """Attention and a dense SwiGLU, each between two RMSNorms: ``h = x +
    RMSNorm(Attn(RMSNorm(x)))``, ``y = h + RMSNorm(MLP(RMSNorm(h)))``; four
    norms, each with its own weight. No counters."""
    attn: dict
    width: int
    eps: float
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array):
        dt = self.dtype or x.dtype

        def norm(name, y):
            return RMSNorm(self.eps, name=name)(y)
        with jax.named_scope(scopes.BLOCK_NORM):
            y = norm("input_norm", x).astype(dt)
        y = GroupedQueryAttention(**self.attn, eps=self.eps, dtype=dt,
                                  name="self_attention")(y)
        with jax.named_scope(scopes.BLOCK_NORM):
            # the sum in float32, one rounding into the stream
            x = (x + norm("attn_out_norm", y)).astype(dt)
            y = norm("post_norm", x).astype(dt)
        y = DenseMLP(self.width, dtype=dt, name="mlp")(y)
        with jax.named_scope(scopes.BLOCK_NORM):
            return (x + norm("mlp_out_norm", y)).astype(dt), {}


class MixerBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``: one mixer behind one norm (``kind``:
    ``mamba`` | ``moe`` | ``attention``; ``mixer``: its module's fields)."""
    kind: str
    mixer: dict
    eps: float
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jax.Array):
        dt = self.dtype or x.dtype
        with jax.named_scope(scopes.BLOCK_NORM):
            y = RMSNorm(self.eps, name="norm")(x)
            if self.kind != "moe":       # the router reads the float32 norm
                y = y.astype(dt)
        counters = {}
        if self.kind == "mamba":
            y, counters = Mamba2Mixer(**self.mixer, eps=self.eps, dtype=dt,
                                      name="mixer")(y)
        elif self.kind == "moe":
            y, counters = SparseExperts(**self.mixer, dtype=dt,
                                        name="mixer")(y)
        else:
            y = GroupedQueryAttention(**self.mixer, eps=self.eps, dtype=dt,
                                      name="mixer")(y)
        with jax.named_scope(scopes.BLOCK_NORM):
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
    layer_types: Sequence[str]           # of LAYER_KINDS, a layer each
    rope_parameters: Any                 # {layer type: its parameters};
    #                                      a type without an entry rotates
    #                                      nothing
    sliding_window: int
    rms_norm_eps: float = 1e-6
    qk_norm: bool = True                 # RMSNorm on q and k in attention
    router: str = "softmax"              # | "sigmoid": scores, a bias that
    routed_scaling: float = 1.0          # chooses, this factor on weights
    expert_act: str = "swiglu"           # | "relu2" (ungated)
    shared_width: int = 0                # a shared expert's width; 0: none
    mamba: Any = None                    # a Mamba-2 mixer's published sizes
    #                                      (``Mamba2Mixer``'s fields)
    dense_width: int = 0                 # a pair's second half is a dense
    #                                      SwiGLU of this width between two
    #                                      norms, as its attention then is
    #                                      (``DenseLayer``); 0: the experts
    loop_steps: int = 1                  # T: passes over the kept layers and
    #                                      the final norm, the same leaves;
    #                                      above 1 a head and an exit gate
    #                                      read every pass
    exit_beta: float = 0.0               # on the exit distribution's entropy
    latent: Any = None                   # latent attention's published sizes
    #                                      (``LatentAttention``'s fields) in
    #                                      grouped-query attention's place
    leading_dense: tuple = (0, 0)        # (layers, width): the first pairs'
    #                                      second half is a dense SwiGLU
    mtp_depth: int = 0                   # multi-token-prediction modules
    #                                      behind the kept layers (0 | 1):
    #                                      ``MTPModule``
    mtp_weight: float = 0.0              # lambda on the second head's loss
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
        """The attention shapes a step runs, one a layer type kept that
        has attention (none for a share without such a layer)."""
        kept = [kind for kind in
                self.layer_types[:self.layers or self.num_layers]
                if kind in PAIR_KINDS + ("attention",)]
        shape = dict(heads=self.num_heads, kv_heads=self.num_kv_heads,
                     head_dim=self.head_dim, fused=False)
        if self.mtp_depth:
            kept += ["full_attention"]   # the module's block has one too
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
        embed = nn.Embed(self.vocab_held, self.hidden_size,
                         embedding_init=_init, dtype=dt, name="embed")
        with jax.named_scope(scopes.LM_EMBED):
            x = embed(tokens)
        experts = dict(num_experts=self.num_experts,
                       top_k=self.experts_per_token, width=self.expert_width,
                       first_expert=first_expert, held=held,
                       router=self.router,
                       routed_scaling=self.routed_scaling,
                       act=self.expert_act, shared_width=self.shared_width)
        def run_layers(x):
            """The kept layers, once: (x, their counters). (Called in a pass
            of ``_looped`` too: a layer's parent is the module that runs.)"""
            counters = {}
            for i, kind in enumerate(self.layer_types[:kept]):
                if kind not in LAYER_KINDS:
                    raise ValueError(
                        f"layer {i}: unknown layer type {kind!r} (one of "
                        f"{', '.join(LAYER_KINDS)})")
                of_kind = self._attention_of(kind, mask)
                if kind in PAIR_KINDS and self.dense_width:
                    layer, fields = DenseLayer, dict(attn=of_kind,
                                                     width=self.dense_width)
                elif kind in PAIR_KINDS:
                    layer, fields = DecoderLayer, dict(
                        attn=of_kind, experts=experts)
                    if i < self.leading_dense[0]:
                        fields["dense_width"] = self.leading_dense[1]
                else:
                    layer, fields = MixerBlock, dict(kind=kind, mixer={
                        "mamba": self.mamba, "moe": experts,
                        "attention": of_kind}[kind])
                if self.remat:
                    layer = _rematerialised(layer)
                x, layer_counters = layer(
                    **fields, eps=self.rms_norm_eps, dtype=dt,
                    name=f"layer_{i}")(x)
                counters.update({f"{k}.layer_{i}": v
                                 for k, v in layer_counters.items()})
            return x, counters
        if self.loop_steps > 1:
            return _looped(self, run_layers, x, targets, dt)
        x, layer_counters = run_layers(x)
        counters.update(layer_counters)
        if noised is not None:
            x = x[:, :length]            # the head reads the noised half
        second = None
        if self.mtp_depth and (targets is not None
                               or self.is_initializing()):
            # the module reads the NEXT id's embedding (the targets; at
            # initialisation, which hands none, any ids: shapes only) and
            # the kept layers' result before the final norm
            if self.mtp_depth != 1 or self.objective != "next_id":
                raise ValueError(
                    f"mtp_depth {self.mtp_depth} under {self.objective!r}: "
                    f"one module, behind a model trained on the next id")
            with jax.named_scope(scopes.MTP_MODULE):
                with jax.named_scope(scopes.LM_EMBED):
                    ahead = embed(tokens if targets is None else targets)
                second, mtp_counters = MTPModule(
                    block=dict(attn=self._attention_of("full_attention",
                                                       None),
                               experts=experts),
                    eps=self.rms_norm_eps, remat=self.remat, dtype=dt,
                    name="mtp")(x, ahead)
            counters.update({f"{k}.mtp": v for k, v in mtp_counters.items()})
        with jax.named_scope(scopes.BLOCK_NORM):
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
        if second is not None:
            # position i of the module predicts the id after the next,
            # y_(i + 1): the targets shifted once more; the last position
            # has none in the row (weight 0), so the mean is over T - 1
            rows, t = targets.shape
            with jax.named_scope(scopes.MTP_MODULE):
                with jax.named_scope(scopes.LOSS):
                    after_next = jnp.roll(targets, -1, axis=1)
                    seen = jnp.broadcast_to(
                        (jnp.arange(t) < t - 1).astype(jnp.float32),
                        (rows, t))
                mtp_loss, _ = lm_head_loss(
                    second, head, after_next, self.loss_chunk, weights=seen,
                    normaliser=rows * (t - 1))
            counters[scopes.MTP_LOSS] = mtp_loss
            counters[scopes.LM_LOSS_MAIN] = loss
            loss = loss + self.mtp_weight * mtp_loss
        return Scored(loss, acc1, counters)

    # (below ``__call__``: the lines above are in every compiled step's
    # source locations, and a Mosaic kernel's payload carries them into the
    # compile cache's key)
    def scan_plan(self, rows: int, seq_len: int) -> Optional[dict]:
        """The plan of the Mamba blocks' chunked scan at ``rows`` rows of
        ``seq_len`` ids (``ssd.scan_plan``: which program runs, read from
        the shape); None for a share that keeps no such block."""
        if "mamba" not in self.layer_types[:self.layers or self.num_layers]:
            return None
        m = self.mamba
        return ssd.scan_plan(rows, seq_len, m["num_heads"], m["head_dim"],
                             m["groups"], m["state"], m["chunk"])

    def conv_plan(self, rows: int, seq_len: int) -> Optional[dict]:
        """The plan of the Mamba blocks' convolution and SiLU at ``rows``
        rows of ``seq_len`` ids (``ssd.conv_plan``: which program runs, read
        from the shape); None for a share that keeps no such block."""
        if "mamba" not in self.layer_types[:self.layers or self.num_layers]:
            return None
        m = self.mamba
        inner, gn = m["num_heads"] * m["head_dim"], m["groups"] * m["state"]
        return ssd.conv_plan(rows, seq_len,
                             2 * inner + 2 * gn + m["num_heads"],
                             (inner, gn, gn), m["conv"], inner)

    def head_plan(self, rows: int, seq_len: int) -> dict:
        """Which form of ``ops.lm_head_loss`` a training step of ``rows``
        rows of ``seq_len`` ids runs, by its caller: ``form`` ``forward_loop``
        (the gradients taken in the loop over chunks) or ``rematerialised``
        (under the scan over passes), the ``chunk``, the ``chunks`` a call
        and the ``calls`` a step."""
        chunk = head_chunk(rows * seq_len, self.loss_chunk)
        looped = self.loop_steps > 1
        return dict(form="rematerialised" if looped else "forward_loop",
                    chunk=chunk, chunks=rows * seq_len // chunk,
                    calls=self.loop_steps if looped else 1 + self.mtp_depth)

    def _attention_of(self, kind: str, mask: Optional[tuple]) -> dict:
        """The attention module's fields in a layer of ``kind``:
        ``LatentAttention``'s where the model states ``latent``, else
        ``GroupedQueryAttention``'s."""
        if self.latent:
            if mask is not None or kind == "sliding_attention":
                raise ValueError("latent attention is causal over the whole "
                                 "row: no window, no diffusion over blocks")
            return dict(
                num_heads=self.num_heads, **self.latent, flash=self.flash,
                rope_parameters=dict(self.rope_parameters or {}).get(kind))
        return dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, qk_norm=self.qk_norm,
            block_diffusion=mask, flash=self.flash,
            rope_parameters=dict(self.rope_parameters or {}).get(kind),
            window=(self.sliding_window if kind == "sliding_attention"
                    else None))

    def qk_plans(self, rows: int, seq_len: int) -> list[dict]:
        """The plans of q's and k's norm and rotation in a training step of
        ``rows`` rows of ``seq_len`` ids (``GroupedQueryAttention.qk_plan``,
        ``LatentAttention.latent_plan``: which program runs, read from the
        shape), one a layer type kept that has attention, those that
        differ."""
        t, mask = seq_len, None
        if self.latent:
            # every block's (the layers' and the MTP module's) is the same
            return [LatentAttention(
                **self._attention_of("full_attention", None),
                parent=None).latent_plan(rows, seq_len, self.flash)]
        if self.objective == "block_diffusion":
            t, mask = 2 * seq_len, (seq_len, self.block_length)
        plans = []
        for kind in dict.fromkeys(
                self.layer_types[:self.layers or self.num_layers]):
            if kind in PAIR_KINDS + ("attention",):
                plan = GroupedQueryAttention(
                    **self._attention_of(kind, mask), parent=None).qk_plan(
                        rows, t, self.flash)
                if plan not in plans:
                    plans.append(plan)
        return plans

    def plans(self, rows: int, seq_len: int) -> list[tuple[str, dict]]:
        """Every plan of a training step of ``rows`` rows of ``seq_len``
        ids as ``(telemetry event, plan)``, in the order the trainer says
        them; a share that keeps no such block has no such pair."""
        pairs = [("ssm_scan", self.scan_plan(rows, seq_len)),
                 ("ssm_conv", self.conv_plan(rows, seq_len)),
                 *(("attn_qk", p) for p in self.qk_plans(rows, seq_len)),
                 ("lm_head", self.head_plan(rows, seq_len))]
        return [(event, plan) for event, plan in pairs if plan is not None]


def _rematerialised(layer):
    """``layer`` made again in the backward pass, everything of it but the
    attention kernel's two results (0.4 GB a layer at two sequences of
    8,192): its forward runs once."""
    from tpudist.ops.pallas.flash_attention import SAVED_BY_NAME
    return nn.remat(
        layer, policy=jax.checkpoint_policies.save_only_these_names(
            *SAVED_BY_NAME))


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) in its
    TRAINING form, a head of its own keys: two low-rank paths with an
    RMSNorm inside each, ``c_q = RMSNorm(u W_qa)`` -> ``q = c_q W_qb``,
    heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = u W_kva``, ``c_kv =
    RMSNorm(c_kv)`` -> ``c_kv W_kvb``, heads of ``[k_nope | v]``. ``q_rope``
    (a head) and ``k_r`` (ONE head that all share) are rotated by
    neighbouring pairs (``rope.apply_pairs``); scores ``(q_nope k_nope^T +
    q_rope k_r^T) / sqrt(nope_dim + rope_dim)`` under the causal mask,
    float32 softmax, ``o = P v``, ``o W_o``. No bias. (The absorbed form,
    one latent head of ``kv_rank + rope_dim`` for all, is serving's: 3.4 x
    the products a pair.) With ``flash`` the kernels of
    ``ops/pallas/mla_attention.py``, which take the operands apart: where
    the shape allows (``latent_plan``) one Pallas pass rotates ``q_rope``
    and ``k_r`` and writes q where the kernels read it
    (``ops/pallas/latent_rope.py``), and ``k_nope``, ``v``, ``o`` and their
    cotangents are addressed where ``kv_b_proj`` and ``o_proj`` write and
    read them; at any other length the ``jax.numpy`` rotation and the entry
    that pads. Else the XLA ``attention`` over keys laid whole."""
    num_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_parameters: Any
    eps: float = 1e-6
    dtype: Any = None
    flash: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        dt = self.dtype or x.dtype
        h, dn, dr, dv = (self.num_heads, self.nope_dim, self.rope_dim,
                         self.v_dim)

        def proj(features, name):
            return nn.Dense(features, use_bias=False, dtype=dt,
                            kernel_init=_init, name=name)
        with jax.named_scope(scopes.ATTN_MIXER):
            with jax.named_scope(scopes.ATTN_QKV_PROJ), jax.named_scope(
                    scopes.MLA_DOWN):
                c_q = proj(self.q_rank, "q_a_proj")(x)
                kva = proj(self.kv_rank + dr, "kv_a_proj")(x)
                c_kv, k_r = kva[..., :self.kv_rank], kva[..., self.kv_rank:]
            with jax.named_scope(scopes.ATTN_QK_NORM_ROPE), jax.named_scope(
                    scopes.MLA_LATENT_NORM):
                c_q = RMSNorm(self.eps, name="q_a_norm")(c_q).astype(dt)
                c_kv = RMSNorm(self.eps, name="kv_a_norm")(c_kv).astype(dt)
            with jax.named_scope(scopes.ATTN_QKV_PROJ), jax.named_scope(
                    scopes.MLA_UP):
                q = proj(h * (dn + dr), "q_b_proj")(c_q)
                kv = proj(h * (dn + dv), "kv_b_proj")(c_kv)
            # (initialisation runs eagerly on a short example row: the XLA
            # path, and no kernel is built for that length)
            flash = self.flash and not self.is_initializing()
            cos, sin = rope.tables(dict(self.rope_parameters), dr, t)
            # one Pallas pass that rotates q_rope and k_r and writes q where
            # the kernels read it, which address k_nope, v, o and dO where
            # the projections wrote them: where the shape allows (read from
            # it, never chosen); else the lines below, and XLA's moves
            if self.latent_plan(b, t, flash)["kernel"] == "pallas":
                from tpudist.ops.pallas import flash_attention_latent_laid
                from tpudist.ops.pallas.latent_rope import latent_rope
                with jax.named_scope(scopes.ATTN_QK_NORM_ROPE):
                    q_nope, q_rope, k_r = latent_rope(
                        q, kva, cos, sin, heads=h, kv_rank=self.kv_rank)
                with jax.named_scope(scopes.ATTN_FUSED):
                    out = flash_attention_latent_laid(q_nope, q_rope, kv,
                                                      k_r)
                with jax.named_scope(scopes.ATTN_OUT_PROJ):
                    return proj(d, "o_proj")(out)
            with jax.named_scope(scopes.ATTN_QKV_PROJ), jax.named_scope(
                    scopes.MLA_UP):
                q = q.reshape(b, t, h, dn + dr)
                kv = kv.reshape(b, t, h, dn + dv)
                q_nope, q_rope = q[..., :dn], q[..., dn:]
                k_nope, v = kv[..., :dn], kv[..., dn:]
            with jax.named_scope(scopes.ATTN_QK_NORM_ROPE):
                q_rope = rope.apply_pairs(q_rope, cos, sin)
                k_r = rope.apply_pairs(k_r[:, :, None], cos, sin)
                if not flash:
                    q = jnp.concatenate([q_nope, q_rope], axis=-1)
                    k = jnp.concatenate(
                        [k_nope, jnp.broadcast_to(k_r, q_rope.shape)],
                        axis=-1)
            if flash:
                from tpudist.ops.pallas import flash_attention_latent
                with jax.named_scope(scopes.ATTN_FUSED):
                    out = flash_attention_latent(q_nope, q_rope, k_nope,
                                                 k_r[:, :, 0], v)
            else:
                out = attention(q, k, v, causal=True)
            with jax.named_scope(scopes.ATTN_OUT_PROJ):
                return proj(d, "o_proj")(out.reshape(b, t, h * dv))

    def latent_plan(self, rows: int, seq_len: int, flash: bool) -> dict:
        """Which program rotates this layer's q_rope and k_r and lays its
        kernels' operands at ``rows`` rows of ``seq_len`` positions
        (``latent_rope.latent_plan``: read from the shape and the layer's
        fields)."""
        from tpudist.ops.pallas.latent_rope import latent_plan
        return latent_plan(rows, seq_len, self.num_heads, self.nope_dim,
                           self.rope_dim, self.v_dim, self.kv_rank,
                           flash=flash)


class MTPModule(nn.Module):
    """A multi-token-prediction module of depth 1 (DeepSeek-V3,
    arXiv:2412.19437 section 2.2): ``m = [RMSNorm_e(ahead) ; RMSNorm_h(
    hidden)] W_eh``, ``h' = Block(m)``, returns ``RMSNorm_s(h')`` for the
    caller's head (the model's own, and ``ahead`` rows of its own
    embedding: shared leaves) and the block's counters. ``hidden`` is the
    kept layers' result before the final norm, ``ahead`` the embedding of
    each position's NEXT id; ``block`` a ``DecoderLayer``'s fields (its own
    leaves, router, bias and held experts; positions 0 .. T - 1)."""
    block: dict
    eps: float
    remat: bool = False
    dtype: Any = None

    @nn.compact
    def __call__(self, hidden: jax.Array, ahead: jax.Array):
        dt = self.dtype or hidden.dtype
        with jax.named_scope(scopes.MTP_MERGE):
            merged = jnp.concatenate(
                [RMSNorm(self.eps, name="enorm")(ahead).astype(dt),
                 RMSNorm(self.eps, name="hnorm")(hidden).astype(dt)], axis=-1)
            m = nn.Dense(hidden.shape[-1], use_bias=False, dtype=dt,
                         kernel_init=_init, name="eh_proj")(merged)
        layer = _rematerialised(DecoderLayer) if self.remat else DecoderLayer
        x, counters = layer(**self.block, eps=self.eps, dtype=dt,
                            name="block")(m)
        with jax.named_scope(scopes.BLOCK_NORM):
            return RMSNorm(self.eps, name="norm")(x).astype(dt), counters


# the least probability whose logarithm the exit entropy takes: a gate that
# saturates in float32 leaves 0 of a position, and 0 log 0 is 0
_EXIT_TINY = 1e-30


def _looped(model: "MoEDecoder", run_layers, x: jax.Array,
            targets: Optional[jax.Array], dt):
    """``model.loop_steps`` passes over the kept layers and the final norm,
    the same leaves every pass, from the embedding's rows ``x`` (the
    module's docstring has the equations). One ``nn.scan`` with the
    parameters broadcast, so a pass is traced once; the carry is ``h_t``,
    ``S_t`` [rows, L] and three sums (the loss, the expected exit step, the
    exit distribution's negative entropy), and each pass takes its weighted
    head loss where its ``h_t`` is made."""
    steps = model.loop_steps
    if model.objective != "next_id" or not model.dense_width:
        raise ValueError(
            f"loop_steps {steps}: layers that run several times are dense "
            f"layers trained on the next id (their exit loss has no place "
            f"for a layer's counters or a noised copy)")
    head = model.param("head", _init,
                       (model.hidden_size, model.vocab_held), jnp.float32)

    def one_pass(m, carry, t):
        x, survive, sums = carry
        x, _ = run_layers(x)
        with jax.named_scope(scopes.BLOCK_NORM):
            normed = RMSNorm(m.rms_norm_eps, name="norm")(x)
            x = normed.astype(dt)
        with jax.named_scope(scopes.LOOP_EXIT):
            # the gate reads the float32 norm at full precision, as a
            # router does
            leave = jax.nn.sigmoid(nn.Dense(
                1, dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
                kernel_init=_init, name="exit_gate")(normed)[..., 0])
            p = jnp.where(t == steps, survive, survive * leave)
        if targets is None:
            return (x, survive, sums), None
        # under the scan over passes the other form's d head would be
        # stacked a pass (ops/loss.py)
        loss, acc1 = lm_head_loss(x, head, targets, m.loss_chunk, weights=p,
                                  rematerialised=True)
        with jax.named_scope(scopes.LOOP_EXIT):
            plogp = jnp.mean(p * jnp.log(jnp.maximum(p, _EXIT_TINY)))
            sums = (sums[0] + loss + m.exit_beta * plogp,
                    sums[1] + t * jnp.mean(p), sums[2] + plogp)
            survive = survive * (1.0 - leave)
        return (x, survive, sums), acc1

    zero = jnp.zeros((), jnp.float32)
    # the loop's own work reads under this scope and no other part's: a
    # pass's saved results stacked and taken back, the tied leaves'
    # gradients summed over the passes
    with jax.named_scope(scopes.LOOP_CARRY):
        (x, _, (loss, exit_step, neg_entropy)), acc1 = nn.scan(
            one_pass, variable_broadcast="params",
            split_rngs={"params": False}, length=steps)(
                model, (x, jnp.ones(x.shape[:2], jnp.float32),
                        (zero, zero, zero)), jnp.arange(1, steps + 1))
    if targets is None:
        with jax.named_scope(scopes.LM_HEAD):
            return jnp.dot(x, head.astype(dt),
                           preferred_element_type=jnp.float32)
    return Scored(loss, acc1[-1], {scopes.LOOP_EXPECTED_EXIT: exit_step,
                                   scopes.LOOP_EXIT_ENTROPY: -neg_entropy})


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


NEMOTRON3_NANO_PATTERN = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                          "EMEMEMEME")


def nemotron3_nano_30b_a3b(dtype: Any = None, **kw) -> MoEDecoder:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (``config.json`` of
    huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
    ``nemotron_h``): 52 blocks of hidden 2,688, one mixer each behind one
    RMSNorm (eps 1e-5), in the published pattern (23 ``M`` Mamba-2, 23 ``E``
    experts, 6 ``*`` attention). Mamba-2: 64 heads of 64, state 128, 8
    groups, a width-4 convolution, chunks of 128. Attention: 32 query heads
    over 2 key-value heads of 128, causal, no rotation and no q/k norm (the
    Mamba layers carry position). Experts: 128 of width 1,856 with 6 a
    token, ungated ``relu^2``, routed by sigmoid scores with a correction
    bias that chooses (zero here) and the factor 2.5, beside one shared
    expert of width 3,712. Vocabulary 131,072, untied. Mamba's ``out_proj``
    starts at N(0, 0.02) / sqrt(52) (``rescale_prenorm_residual``)."""
    return MoEDecoder(
        vocab_size=131072, hidden_size=2688, num_layers=52, num_heads=32,
        num_kv_heads=2, head_dim=128, num_experts=128, experts_per_token=6,
        expert_width=1856, layer_types=layer_types_of(NEMOTRON3_NANO_PATTERN),
        rope_parameters=None, sliding_window=0, rms_norm_eps=1e-5,
        qk_norm=False, router="sigmoid", routed_scaling=2.5,
        expert_act="relu2", shared_width=3712,
        mamba=dict(num_heads=64, head_dim=64, state=128, groups=8, conv=4,
                   chunk=128, time_step=(0.001, 0.1, 1e-4),
                   out_scale=52 ** -0.5),
        dtype=dtype, **_own(kw))


def nemotron3_tiny(dtype: Any = None, **kw) -> MoEDecoder:
    """The CPU tests' twin of the model above at toy widths (hidden 64; the
    pattern ``MEM*E``; Mamba-2 with 8 heads of 8, state 16, 2 groups, chunks
    of 8; 16 query heads over 1 key-value head of 16, a group of sixteen as
    the model's; 16 experts of width 32 with 2 a token beside a shared one
    of 64; 256 ids): never a benchmark configuration."""
    kw.setdefault("loss_chunk", 64)
    return MoEDecoder(
        vocab_size=256, hidden_size=64, num_layers=5, num_heads=16,
        num_kv_heads=1, head_dim=16, num_experts=16, experts_per_token=2,
        expert_width=32, layer_types=layer_types_of("MEM*E"),
        rope_parameters=None, sliding_window=0, rms_norm_eps=1e-5,
        qk_norm=False, router="sigmoid", routed_scaling=2.5,
        expert_act="relu2", shared_width=64,
        mamba=dict(num_heads=8, head_dim=8, state=16, groups=2, conv=4,
                   chunk=8, time_step=(0.001, 0.1, 1e-4),
                   out_scale=5 ** -0.5),
        dtype=dtype, **_own(kw))


def ouro_2_6b(dtype: Any = None, **kw) -> MoEDecoder:
    """Ouro-2.6B (ByteDance; ``config.json`` of
    huggingface.co/ByteDance/Ouro-2.6B, ``model_type`` ``ouro``; the looped
    language model of arXiv:2510.25741): 48 layers of hidden 2,048 run
    ``total_ut_steps`` = 4 times over the same weights, 16 query heads over
    16 key-value heads of 128 (a group of one), plain RoPE (theta 1e6), no
    q/k norm, a dense SwiGLU of width 5,632, each half of a layer between
    two RMSNorms (eps 1e-6); vocabulary 49,152, untied; a head and an exit
    gate read every pass. ``exit_beta`` 0.1 is the paper's first stage as
    remembered (no key of the config)."""
    return MoEDecoder(
        vocab_size=49152, hidden_size=2048, num_layers=48, num_heads=16,
        num_kv_heads=16, head_dim=128, num_experts=0, experts_per_token=0,
        expert_width=0, layer_types=("full_attention",) * 48,
        rope_parameters={"full_attention": {"rope_type": "default",
                                            "rope_theta": 1000000}},
        sliding_window=0, rms_norm_eps=1e-6, qk_norm=False, dense_width=5632,
        loop_steps=4, exit_beta=0.1, dtype=dtype, **_own(kw))


def ouro_tiny(dtype: Any = None, **kw) -> MoEDecoder:
    """The CPU tests' twin of the model above at toy widths (hidden 64, 4
    heads over 4 of 16, a dense width of 96, 3 layers run 4 times, 256
    ids): never a benchmark configuration."""
    kw.setdefault("loss_chunk", 64)
    return MoEDecoder(
        vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=16, num_experts=0, experts_per_token=0,
        expert_width=0, layer_types=("full_attention",) * 3,
        rope_parameters=ouro_2_6b().rope_parameters, sliding_window=0,
        rms_norm_eps=1e-6, qk_norm=False, dense_width=96, loop_steps=4,
        exit_beta=0.1, dtype=dtype, **_own(kw))


def joyai_llm_flash(dtype: Any = None, **kw) -> MoEDecoder:
    """JoyAI-LLM-Flash (JD; ``config.json`` of
    huggingface.co/jdopensource/JoyAI-LLM-Flash, ``model_type``
    ``joyai_llm_flash``, 48B-A2.7B; the DeepSeek-V3 family's code): 40
    layers of hidden 2,048; latent attention, ``q_lora_rank`` 1,536,
    ``kv_lora_rank`` 512, 32 heads of 128 + 64 rotated columns (pairs
    interleaved, theta 32e6, no scaling) against values of 128; one leading
    dense layer (SwiGLU 7,168), then 256 routed experts of width 768 with 8
    a token (sigmoid scores, a correction bias that chooses, weights
    normalised over the chosen, factor 2.5) beside one shared expert;
    ``num_nextn_predict_layers`` 1: a multi-token-prediction module that
    shares the embedding and the head; vocabulary 129,280, untied.
    ``mtp_weight`` 0.3 is DeepSeek-V3's first phase (no key of the
    config)."""
    return MoEDecoder(
        vocab_size=129280, hidden_size=2048, num_layers=40, num_heads=32,
        num_kv_heads=32, head_dim=192, num_experts=256, experts_per_token=8,
        expert_width=768, layer_types=("full_attention",) * 40,
        rope_parameters={"full_attention": {"rope_type": "default",
                                            "rope_theta": 32000000}},
        sliding_window=0, rms_norm_eps=1e-6, qk_norm=False, router="sigmoid",
        routed_scaling=2.5, shared_width=768,
        latent=dict(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                    v_dim=128),
        leading_dense=(1, 7168), mtp_depth=1, mtp_weight=0.3, dtype=dtype,
        **_own(kw))


def joyai_tiny(dtype: Any = None, **kw) -> MoEDecoder:
    """The CPU tests' twin of the model above at toy widths (hidden 64;
    latent attention of ranks 48 and 32, 4 heads of 16 + 8 against values of
    16; one dense layer of width 96, then two of 16 experts of width 32 with
    2 a token beside a shared one; the module; 256 ids): never a benchmark
    configuration."""
    kw.setdefault("loss_chunk", 64)
    return MoEDecoder(
        vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
        num_kv_heads=4, head_dim=24, num_experts=16, experts_per_token=2,
        expert_width=32, layer_types=("full_attention",) * 3,
        rope_parameters=joyai_llm_flash().rope_parameters, sliding_window=0,
        rms_norm_eps=1e-6, qk_norm=False, router="sigmoid",
        routed_scaling=2.5, shared_width=32,
        latent=dict(q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16),
        leading_dense=(1, 96), mtp_depth=1, mtp_weight=0.3, dtype=dtype,
        **_own(kw))
