"""The program's names for what it does: one place, names only.

Three kinds of span, all read by `benchmarks/chip/` and by an operator in
XProf (docs/OBSERVABILITY.md, "Labeled XLA traces"), and the counters a
model hands the step:

- **device scopes** (`jax.named_scope`, `tpudist_*`): HLO metadata on every
  operation of a step program. A scope changes no compiled program, FLOP or
  byte (tests/test_compiled_cost.py). jax itself marks the backward pass:
  an op traced under `tpudist_forward` reads `jvp(tpudist_forward)` in the
  forward pass and `transpose(jvp(tpudist_forward))` in the backward.
- **host spans** (`jax.profiler.TraceAnnotation`, `tpudist.*`): every host
  microsecond of a trainer loop turn lies inside exactly one top-level one;
  a span costs a flag test when no trace is live.
- **set-up phases** (`telemetry.record_phase`, `init.*`): seconds of
  `Trainer.__init__`, read back with `telemetry.phases()`.

No jax import here: the names are plain strings.
"""

from __future__ import annotations

from typing import Optional

# -- device scopes ----------------------------------------------------------
FORWARD = "tpudist_forward"            # model.apply in the train step
LOSS = "tpudist_loss"                  # cross entropy, aux heads, loss scaling
GRAD_REDUCE = "tpudist_grad_reduce"    # gradient + BN-statistics collectives
OPTIMIZER = "tpudist_optimizer"        # tx.update, apply_updates, skips, EMA
METRICS = "tpudist_metrics"            # accuracy, metric means, sentinels
EVAL_FORWARD = "tpudist_eval_forward"
SERVE_FORWARD = "tpudist_serve_forward"
# the three stages of the XLA attention path, inside the forward scope
ATTN_SCORES = "attn_scores"
ATTN_SOFTMAX = "attn_softmax"
ATTN_VALUES = "attn_values"
# the Pallas attention kernels, forward and backward, in their place
ATTN_FUSED = "attn_fused"
# a decoder's attention (models/decoder.py::GroupedQueryAttention), inside
# the forward scope under the block's name; the three parts below and
# `attn_fused` (or the XLA path's three stages) lie within the whole
ATTN_MIXER = "attn_mixer"
ATTN_QKV_PROJ = "attn_qkv_proj"        # q, k, v products, the cut into heads
ATTN_QK_NORM_ROPE = "attn_qk_norm_rope"  # q / k RMSNorm, the tables, RoPE
ATTN_OUT_PROJ = "attn_out_proj"
# latent attention (models/decoder.py::LatentAttention): what it adds around
# the kernels, inner scopes of the two parts above (`mla_down` and `mla_up`
# within `attn_qkv_proj`, `mla_latent_norm` within `attn_qk_norm_rope`)
MLA_DOWN = "mla_down"                  # hidden -> the two latents (+ k_rope)
MLA_LATENT_NORM = "mla_latent_norm"    # RMSNorm of each latent, the cast
MLA_UP = "mla_up"                      # latents -> heads (q; k_nope | v)
# a decoder block's own norms (and the decoder's last) with the cast behind
# each, and the residual sums
BLOCK_NORM = "block_norm"
# a top-k expert layer (parallel/moe.py::moe_topk_held), inside the forward
# scope under the layer's name
MOE_ROUTER = "moe_router"              # softmax over all experts, top k
MOE_DISPATCH = "moe_dispatch"          # sort the held pairs, take their rows
MOE_EXPERTS = "moe_experts"            # grouped products over the held
MOE_COMBINE = "moe_combine"            # weighted sum back to tokens
MOE_SHARED = "moe_shared"              # the dense expert every token visits
# a dense feed-forward in the experts' place (models/decoder.py::DenseMLP):
# the gate, up and down products and the SwiGLU between them
DENSE_MLP = "dense_mlp"
# a Mamba-2 mixer (models/decoder.py::Mamba2Mixer, ops/ssd.py), inside the
# forward scope under the block's name; the five parts lie within the whole
SSM_MIXER = "ssm_mixer"
SSM_IN_PROJ = "ssm_in_proj"            # z, xBC and dt in one product
SSM_CONV = "ssm_conv"                  # causal depthwise convolution + SiLU
SSM_SCAN = "ssm_scan"                  # decay, within-chunk products, chunk
#                                        states, the pass between chunks, the
#                                        carried part, D x
SSM_GATE_NORM = "ssm_gate_norm"        # y * silu(z), RMSNorm by group
SSM_OUT_PROJ = "ssm_out_proj"
# a language model's ends
LM_EMBED = "lm_embed"                  # the embedding's rows
LM_HEAD = "lm_head"                    # the output head's product, a chunk
# training by diffusion over blocks, inside the forward scope: the draws of
# t and of the masked positions, x_t, the doubled row, the loss's weights
BD_NOISE = "bd_noise"
# layers that run several times (models/decoder.py::_looped), inside the
# forward scope, a pass: the exit gate's product and sigmoid, the exit
# distribution p_t and what survives it, its entropy, the sums the passes
# carry (each pass's weighted head loss stays under `lm_head` / the loss)
LOOP_EXIT = "loop_exit"
# the loop over the passes itself: every operation of a pass lies within it
# (under its own part as well); what lies under it and under no other part is
# the loop's own work: a pass's saved results stacked and taken back in the
# backward pass, the tied leaves' gradients summed over the passes
LOOP_CARRY = "loop_carry"
# a multi-token-prediction module (models/decoder.py::MTPModule), inside the
# forward scope: every operation of the module, its block, its pass through
# the head and its loss lies within `mtp_module` (under its own part as
# well, as a pass of the loop above does); `mtp_merge` is its one leaf of
# its own: the two norms, the join and the product that merges the next
# id's embedding with the trunk's last hidden state
MTP_MODULE = "mtp_module"
MTP_MERGE = "mtp_merge"

# -- counters a model hands the step ----------------------------------------
# One scalar a layer and a step (`<name>.layer_<l>`), through the step's
# metrics and the trainer's drain to `telemetry.counters()`.
MOE_PAIRS = "moe_pairs"                # pairs the held experts computed
MOE_LOAD = "moe_load_max_over_mean"    # the fullest held expert over the mean
MOE_WALKED = "moe_rows_walked"         # buffer rows the block loops touched
# a Mamba-2 mixer: one scalar a block and a step
SSM_DT = "ssm_dt_mean"                 # mean of softplus(dt + dt_bias)
SSM_CARRY = "ssm_chunk_carry_min"      # least exp(sum of dt A over a chunk)
#                                        over heads and chunks: at 0 nothing
#                                        of a state outlives a chunk there
# one scalar a step (no layer): training by diffusion over blocks
BD_MASKED = "bd_masked_share"          # masked positions over rows x L
BD_WEIGHT = "bd_weight_sum"            # sum of masked / t over rows x L
# one scalar a step (no layer): layers that run several times
LOOP_EXPECTED_EXIT = "loop_expected_exit"  # mean over positions of
#                                        sum_t t p_t, in passes
LOOP_EXIT_ENTROPY = "loop_exit_entropy"    # mean over positions of H(p), nats
# one scalar a step (no layer): a model with a multi-token-prediction module
MTP_LOSS = "mtp_loss"                  # the second head's cross entropy (the
#                                        id after the next), unweighted
LM_LOSS_MAIN = "lm_loss_main"          # the next id's, beside it
MODEL_COUNTERS = (MOE_PAIRS, MOE_LOAD, MOE_WALKED, SSM_DT, SSM_CARRY,
                  BD_MASKED, BD_WEIGHT, LOOP_EXPECTED_EXIT,
                  LOOP_EXIT_ENTROPY, MTP_LOSS, LM_LOSS_MAIN)

DEVICE_SCOPES = (FORWARD, LOSS, GRAD_REDUCE, OPTIMIZER, METRICS,
                 EVAL_FORWARD, SERVE_FORWARD)

# every leaf part a train step's device time may lie under: what a named
# operation of a decoder's step is under none of is unitemised
# (`step_unitemised_ms` of the chip benchmark holds its own copy of the first
# 22; `loop_unitemised_ms` adds the three behind them, `mtp_unitemised_ms`
# the one behind those). A part is a leaf but the passes' loop, which every
# other part of a pass lies within: a reader that sums parts hands an
# operation to the first it is under
STEP_PARTS = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE, MOE_SHARED,
              SSM_IN_PROJ, SSM_CONV, SSM_SCAN, SSM_GATE_NORM, SSM_OUT_PROJ,
              ATTN_QKV_PROJ, ATTN_QK_NORM_ROPE, ATTN_FUSED, ATTN_OUT_PROJ,
              BLOCK_NORM, LM_EMBED, LM_HEAD, LOSS, BD_NOISE, GRAD_REDUCE,
              OPTIMIZER, METRICS, DENSE_MLP, LOOP_EXIT, LOOP_CARRY,
              MTP_MERGE)

# -- host spans of a loop turn ----------------------------------------------
STEP = "train"                          # StepTraceAnnotation: h2d + dispatch
SPAN_LOADER_NEXT = "tpudist.loader_next"
SPAN_H2D = "tpudist.h2d"
SPAN_PREFETCH = "tpudist.prefetch"      # loader_next + h2d of batch N+1
SPAN_DISPATCH = "tpudist.dispatch"
SPAN_DRAIN_READY = "tpudist.drain_ready"
SPAN_METRIC_DRAIN = "tpudist.metric_drain"
SPAN_LOOP_HOST = "tpudist.loop_host"    # what is left of a turn: taking a
#                                         staged batch, the poke, the push
# the loop's own activities, spans beside loop_host (not inside it: the span
# that overlaps an idle gap most is handed it, and a parent outlasts its
# child); none of them waits on the device
SPAN_LOOP_PROLOGUE = "tpudist.loop_prologue"    # meters, drain, prefetcher
SPAN_LOOP_HOOKS = "tpudist.loop_hooks"  # profiler, watchdog, doctor, faults
SPAN_LOOP_METERS = "tpudist.loop_meters"        # counters, telemetry emit
SPAN_LOOP_LOG = "tpudist.loop_log"      # the progress line: file + console
SPAN_LOOP_EPOCH_END = "tpudist.loop_epoch_end"  # summary, scalar writes
LOOP_ACTIVITIES = (SPAN_LOOP_PROLOGUE, SPAN_LOOP_HOOKS, SPAN_LOOP_METERS,
                   SPAN_LOOP_LOG, SPAN_LOOP_EPOCH_END)

# -- set-up phases (init.*: sum = Trainer.__init__'s wall time) -------------
INIT_MESH = "init.mesh"
INIT_MODEL_STATE = "init.model_state"   # create_train_state: eager init
INIT_SHARD_STATE = "init.shard_state"
INIT_DISPATCH = "init.dispatch"         # --flash/--compress-grads
INIT_STEP_BUILD = "init.step_build"
INIT_RESTORE = "init.restore"           # only when resuming
INIT_OTHER = "init.other"
INIT_LOADERS = "init.loaders"           # fit() builds them, after __init__

# always booked, in this order; INIT_RESTORE and INIT_LOADERS when they run
INIT_PHASES = (INIT_MESH, INIT_DISPATCH, INIT_MODEL_STATE, INIT_SHARD_STATE,
               INIT_STEP_BUILD, INIT_OTHER)


def phase_of(op_name: str) -> Optional[str]:
    """Which part of the train step an HLO `op_name` belongs to: "fwd",
    "bwd", "loss", "reduce", "opt", "metrics", or None (no scope of the
    program's). XLA joins the names of operations it merges with `;`: the
    first part that has a phase decides."""
    for part in op_name.split(";"):
        if FORWARD in part or LOSS in part:
            if "transpose(" in part:
                return "bwd"
            return "fwd" if FORWARD in part else "loss"
        if GRAD_REDUCE in part:
            return "reduce"
        if OPTIMIZER in part:
            return "opt"
        if METRICS in part:
            return "metrics"
    return None
