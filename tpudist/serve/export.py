"""Checkpoint → compiled eval-mode inference step (the export half of
``tpudist.serve``).

A training checkpoint (``checkpoint.msgpack``, the trainer's native
format) holds the full TrainState; serving needs exactly two trees —
``params`` and ``batch_stats`` — applied in eval mode. ``load_serve_state``
extracts them (EMA weights win when the checkpoint carries them: they are
the weights ``validate()`` selected 'best' with, i.e. what a user of the
EMA recipe would deploy), builds the arch with a bf16 compute dtype, and
resolves ``--flash`` through the SAME measurement-honest dispatch client
the trainer uses (``ops/attention_dispatch``) — with ``train=False`` in
the workload key, so an eval-mode verdict measured once on a device kind
carries over to every replica that serves that shape.

``make_infer_step`` is the one jitted callable the engine AOT-compiles per
bucket: variables in, logits out, input buffer donated (the padded batch
is dead after the forward — donation halves the step's activation-input
footprint; the ``TPUDIST_NO_DONATE`` escape hatch applies, same as
training).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from tpudist.models import create_model, model_fields
from tpudist.obs import scopes


def make_infer_step(model) -> Callable:
    """The jitted eval forward: ``(variables, images) -> logits``.

    The engine never calls this wrapper blind — it AOT-compiles it per
    bucket shape (``.lower().compile()``) and serves from the compiled
    executables, which structurally cannot recompile. The images buffer is
    donated (argnum 1): a request batch is dead once the logits exist."""
    def step(variables: dict, images: jax.Array) -> jax.Array:
        with jax.named_scope(scopes.SERVE_FORWARD):
            return model.apply(variables, images, train=False)

    from tpudist.parallel._common import donated_jit
    return donated_jit(step, donate_argnums=(1,))


def _extract_serving_variables(ckpt: dict, log=None) -> dict:
    """``{"params", "batch_stats"}`` from a raw checkpoint dict, preferring
    the EMA copy when present (``--model-ema-decay`` runs measured their
    best_acc1 ON the EMA weights — serving the live weights would deploy a
    model that never achieved the recorded metric)."""
    state = ckpt.get("state") or {}
    params = state.get("params")
    if params is None:
        raise ValueError("checkpoint has no state.params — not a tpudist "
                         "training checkpoint")
    batch_stats = state.get("batch_stats") or {}
    ema = state.get("ema_params")
    if isinstance(ema, dict) and ema.get("params"):
        if log is not None:
            log("=> serving the EMA weights (checkpoint carries "
                "ema_params — the copy 'best' was measured on)")
        params = ema["params"]
        batch_stats = ema.get("batch_stats") or batch_stats
    return {"params": params, "batch_stats": batch_stats}


def resolve_serve_flash(model, *, batch: int, image_size: int,
                        mode: str = "auto", telemetry=None,
                        log=None) -> Optional[dict]:
    """Resolve ``--flash`` for the serving workload through
    ``ops/attention_dispatch`` — the trainer's ``_resolve_attention`` on the
    same ``model.attention_workloads``, with ``train=False`` and the
    LARGEST bucket as the batch (the shape that dominates steady-state
    throughput). Returns the decision dict and the possibly-cloned model as
    ``decision["model"]``; ``None`` for a model that states no attention
    the probe can time (conv families)."""
    if "flash" not in model_fields(model):
        return None
    workloads = model.attention_workloads(image_size)
    if len(workloads) != 1 or not workloads[0]["fused"]:
        return None
    from tpudist.ops import attention_dispatch
    w = workloads[0]
    dt = getattr(model, "dtype", jnp.bfloat16)
    try:
        dec = attention_dispatch.decide(
            batch, w["seq"], w["heads"], w["head_dim"], dt,
            train=False, causal=w["causal"], mode=mode)
    except Exception as e:
        if log is not None:
            log(f"=> serve attention dispatch probe failed ({e!r}) — "
                f"model-level lookup decides")
        return None
    out = dict(dec)
    # Clone in EVERY mode, not just auto: a forced --flash on/off must
    # reach the model as the trainer's does (it sets the model's `flash`
    # field, models.model_with) — otherwise the built model keeps
    # flash=None, the trace-time lookup decides on its own, and the
    # emitted attention_dispatch verdict lies about the kernel served.
    out["model"] = model.clone(flash=dec["kernel"] == "flash")
    if log is not None:
        msg = (f"=> serve attention dispatch: {dec['kernel']} attention "
               f"(mode {dec['mode']}, {dec['source']}")
        if dec.get("flash_ms") is not None:
            msg += (f"; flash {dec['flash_ms']:.3f} ms vs "
                    f"xla {dec['xla_ms']:.3f} ms")
        log(msg + ")")
    if telemetry is not None:
        telemetry.emit("attention_dispatch",
                       **attention_dispatch.event_fields(dec))
    return out


def load_serve_state(arch: str, checkpoint: str = "", *,
                     num_classes: int = 1000, image_size: int = 224,
                     max_batch: int = 8, flash: str = "auto",
                     dtype: Any = jnp.bfloat16, seed: int = 0,
                     telemetry=None, log=None) -> tuple[Any, dict]:
    """Build the serving model + variables.

    ``checkpoint`` may be a ``.msgpack`` file or a run dir (the live
    ``checkpoint.msgpack`` inside it); '' initializes fresh weights — the
    bench/smoke path, where serving PERFORMANCE is the measured quantity
    and weights are irrelevant. Compute dtype defaults to bf16 (eval has
    no master-weight concern; the checkpoint's f32 params are cast by the
    model's dtype policy at apply time, exactly like training's forward).
    """
    model = create_model(arch, num_classes=num_classes, dtype=dtype)
    dec = resolve_serve_flash(model, batch=max_batch, image_size=image_size,
                              mode=flash, telemetry=telemetry, log=log)
    if dec is not None:
        model = dec["model"]
    if checkpoint:
        from tpudist import checkpoint as ckpt_lib
        ckpt = ckpt_lib.load_checkpoint(checkpoint)
        if ckpt.get("arch") and ckpt["arch"] != arch:
            raise ValueError(
                f"checkpoint was trained as '{ckpt['arch']}' but serving "
                f"was asked for '{arch}' — refusing to apply mismatched "
                f"weights")
        variables = _extract_serving_variables(ckpt, log=log)
        if log is not None:
            log(f"=> exported '{arch}' from {checkpoint} "
                f"(epoch {ckpt.get('epoch', '?')}, "
                f"best_acc1 {float(ckpt.get('best_acc1', 0.0)):.3f})")
    else:
        init = model.init(jax.random.PRNGKey(seed),
                          jnp.ones((1, image_size, image_size, 3),
                                   jnp.float32), train=False)
        variables = {"params": init["params"],
                     "batch_stats": init.get("batch_stats", {})}
        if log is not None:
            log(f"=> serving fresh-init '{arch}' weights (no checkpoint — "
                f"bench/smoke mode)")
    return model, variables
