"""The single parallelism plane: one mesh, one logical-axis rule table
(ISSUE 12 tentpole; the veScale-style consistent-SPMD programming model,
PAPERS.md arXiv:2509.07003).

Before this module the axes were siloed: the trainer derived dp/tp/sp/pp/ep
roles inline, ``tensor_parallel`` owned the GSPMD rule tables, ``comm.py``
owned zero-full placement, and each could drift against the others. The
plane makes every one of them a CLIENT of the same three facts:

1. **The logical-axis binding** (``AXIS_BINDING``): every parallelism a run
   can compose — dp, tp, sp, pp, ep, zero — is a *logical* axis bound ONCE
   to a concrete mesh-axis name. Rule tables, batch sharding, step builders
   and the static analyzer (``tpudist-check`` SHARD05) all resolve axis
   names through this binding, so a rule table cannot name an axis the mesh
   vocabulary does not contain.
2. **The per-family rule tables** (``tensor_parallel.rules_for``): each
   model family declares its parameter cuts once; ``rules_for_mesh`` is the
   validated resolution against a concrete mesh (the ``require_rules``
   refusal for split axes with empty tables).
3. **The placement function** (``state_specs``): ONE call derives the
   PartitionSpec tree for any combination of TP rules × zero mode
   (off/1/full/comm). The GSPMD step builders, the zero-full shard_map
   steps (``parallel/comm.py``), the compressed-DP residual placement, and
   the elastic reshard plane all read this tree — the specs a step compiles
   against can never drift from where ``shard_state`` put the arrays.

``plan(cfg, mesh)`` derives the whole run topology (which step-builder
path, which axis shards the batch, zero placement) from the mesh's axis
names — the block that previously lived inline in ``Trainer.__init__``.
``validate_mesh_request`` is the loud config-time gate behind
``Config.finalize``: an invalid axis composition is an error at parse
time, never a silent pure-DP no-op.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
from jax.sharding import Mesh

from tpudist.parallel.tensor_parallel import (Rules, require_rules,
                                              rules_for, shard_tree,
                                              tree_shardings, tree_specs)

# The ONE logical→mesh axis binding. Every PartitionSpec axis a family rule
# table names must be a value of this dict (tpudist-check SHARD05 holds
# that statically), and every consumer spells mesh axes through it instead
# of hard-coding strings.
AXIS_BINDING: dict = {
    "dp": "data",       # batch-sharded data parallelism (every path)
    "tp": "model",      # Megatron/channel-sharded tensor parallelism
    "sp": "seq",        # ring-attention sequence parallelism (vit*)
    "pp": "pipe",       # GPipe pipeline parallelism (vit_pipe_*)
    "ep": "expert",     # MoE expert parallelism (vit_moe_*)
    "zero": "data",     # weight-update sharding cuts over the data axis
}

# The mesh-axis vocabulary the plane understands (the binding's range).
KNOWN_MESH_AXES = tuple(dict.fromkeys(AXIS_BINDING.values()))


def mesh_axis(logical: str) -> str:
    """The concrete mesh-axis name a logical parallelism axis binds to."""
    return AXIS_BINDING[logical]


def rule_axes(rules: Rules) -> set:
    """Every mesh-axis name a rule table's specs mention."""
    axes: set = set()
    for _, spec in rules:
        for a in spec:
            if a is None:
                continue
            for name in (a if isinstance(a, tuple) else (a,)):
                axes.add(name)
    return axes


def _check_axis_composition(axes: Sequence[str]) -> None:
    """The one-specialty-axis rule, shared by ``validate_mesh_request``
    (config time) and ``plan`` (mesh time): exactly one of
    model/seq/expert/pipe may join data — or the composed
    data,pipe,model."""
    uses_model = mesh_axis("tp") in axes
    uses_seq = mesh_axis("sp") in axes
    uses_expert = mesh_axis("ep") in axes
    uses_pipe = mesh_axis("pp") in axes
    if sum((uses_model, uses_seq, uses_expert, uses_pipe)) > 1 \
            and not (uses_pipe and uses_model
                     and not uses_seq and not uses_expert):
        raise ValueError("mesh_axes may use ONE of 'model' (tensor "
                         "parallel), 'seq' (sequence parallel), 'expert' "
                         "(expert parallel), or 'pipe' (pipeline "
                         "parallel) alongside 'data' — or the composed "
                         "'data,pipe,model' (pipeline stages with "
                         "Megatron TP inside each stage)")


def validate_mesh_request(mesh_axes: Sequence[str],
                          mesh_shape: Optional[Sequence[int]],
                          num_devices: Optional[int] = None,
                          arch: Optional[str] = None) -> None:
    """Loud config-time validation of an axis composition (ISSUE 12
    satellite): every refusal here was previously either a cryptic numpy
    reshape error, a trace-time failure, or — worst — a silent pure-DP
    run on a fraction of the requested devices. ValueError always (user
    error), never assert."""
    axes = list(mesh_axes)
    if not axes:
        raise ValueError("mesh_axes must name at least one axis "
                         "(e.g. ['data'])")
    if len(set(axes)) != len(axes):
        raise ValueError(f"mesh_axes contains duplicates: {axes}")
    unknown = [a for a in axes if a not in KNOWN_MESH_AXES]
    if unknown:
        raise ValueError(
            f"unknown mesh axis name(s) {unknown}: the parallelism plane "
            f"binds {sorted(set(KNOWN_MESH_AXES))} "
            f"(parallel/plane.py AXIS_BINDING) — a typo'd axis would "
            f"silently become the batch axis")
    _check_axis_composition(axes)
    if mesh_shape is not None:
        shape = list(mesh_shape)
        if len(shape) != len(axes):
            raise ValueError(
                f"mesh_shape {shape} has {len(shape)} dim(s) but "
                f"mesh_axes {axes} names {len(axes)} axis(es)")
        if any(int(s) < 1 for s in shape):
            raise ValueError(f"mesh_shape entries must be >= 1, got {shape}")
        if num_devices is not None:
            prod = 1
            for s in shape:
                prod *= int(s)
            if prod != num_devices:
                raise ValueError(
                    f"mesh_shape {shape} covers {prod} device(s) but "
                    f"{num_devices} are available — the mesh must use "
                    f"exactly the attached devices")
        tp_axis = mesh_axis("tp")
        if arch is not None and tp_axis in axes \
                and int(shape[axes.index(tp_axis)]) > 1 \
                and not rules_for(arch):
            # The Config-level twin of require_rules: fail at parse time,
            # before a mesh or model exists.
            raise ValueError(
                f"mesh splits axis '{tp_axis}' "
                f"×{shape[axes.index(tp_axis)]} but arch '{arch}' has an "
                f"EMPTY tensor-parallel rule table "
                f"(parallel/tensor_parallel.py rules_for): the run would "
                f"silently execute pure data parallelism. Use a ruled "
                f"family (vit*/convnext*/swin*/resnet*/vgg*/densenet*), "
                f"drop the '{tp_axis}' axis, or add sharding rules")


def build_mesh(cfg, devices=None) -> Mesh:
    """Mesh construction as a plane derivation: validate the requested
    axis composition loudly, then build (``dist.make_mesh``)."""
    from tpudist.dist import make_mesh
    n = (len(devices) if devices is not None
         else len(jax.devices()))
    validate_mesh_request(tuple(cfg.mesh_axes), cfg.mesh_shape, n,
                          arch=getattr(cfg, "arch", None))
    return make_mesh(cfg.mesh_shape, tuple(cfg.mesh_axes), devices)


def rules_for_mesh(arch: str, mesh: Mesh) -> Rules:
    """The validated family rule table for a concrete mesh
    (``require_rules``: a split tp axis with an empty table refuses)."""
    return require_rules(arch, mesh, model_axis=mesh_axis("tp"))


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """The derived topology of one run: which logical axes are active,
    which mesh axis shards the batch, and which placement mode the state
    uses. Everything the Trainer previously derived inline."""

    mesh_axes: tuple
    data_axis: str
    batch_axes: Any               # axis (or tuple) the input batch shards on
    uses_model_axis: bool
    uses_seq_axis: bool
    uses_expert_axis: bool
    uses_pipe_axis: bool
    uses_gspmd_path: bool
    uses_wus_path: bool
    zero_mode: str                # off | 1 | full
    zero_axis: Optional[str]      # data axis when zero_mode == "1"
    ep_data_axis: Optional[str]   # 'data' under dp×ep composition
    pp_model_axis: Optional[str]  # 'model' under dp×pp×tp composition


def plan(cfg, mesh: Mesh) -> ParallelPlan:
    """Derive the run's parallelism plan from the mesh axis names + config
    (the single source the Trainer's step-builder selection reads)."""
    axes = tuple(cfg.mesh_axes)
    tp, sp, pp, ep = (mesh_axis("tp"), mesh_axis("sp"), mesh_axis("pp"),
                      mesh_axis("ep"))
    uses_model = tp in axes
    uses_seq = sp in axes
    uses_expert = ep in axes
    uses_pipe = pp in axes
    _check_axis_composition(axes)
    data_axis = next((a for a in axes if a not in (tp, sp, pp)), axes[0])
    ep_data_axis = ("data" if uses_expert and "data" in axes else None)
    batch_axes = (("data", "expert") if ep_data_axis else data_axis)
    zero_mode = getattr(cfg, "zero", "off")
    zero_axis = data_axis if zero_mode == "1" else None
    uses_wus = zero_mode == "full"
    if zero_axis and (uses_seq or uses_pipe or uses_expert):
        raise ValueError(
            "--zero 1 (cross-replica weight-update sharding) runs on "
            "the GSPMD path: it composes with 'data' and 'data,model' "
            "meshes, not the shard_map seq/pipe/expert paths")
    if uses_wus and mesh.shape[data_axis] < 2:
        raise ValueError(
            f"--zero full shards the weight update over the "
            f"'{data_axis}' axis, which has size "
            f"{mesh.shape[data_axis]} here — nothing to "
            f"shard; use --zero off (or 1)")
    pp_model_axis = (tp if uses_pipe and uses_model else None)
    uses_gspmd = (uses_model and not uses_pipe) or bool(zero_axis)
    return ParallelPlan(
        mesh_axes=axes, data_axis=data_axis, batch_axes=batch_axes,
        uses_model_axis=uses_model, uses_seq_axis=uses_seq,
        uses_expert_axis=uses_expert, uses_pipe_axis=uses_pipe,
        uses_gspmd_path=uses_gspmd, uses_wus_path=uses_wus,
        zero_mode=zero_mode, zero_axis=zero_axis,
        ep_data_axis=ep_data_axis, pp_model_axis=pp_model_axis)


# -- placement: the one spec derivation every client reads --------------------

def state_specs(mesh: Mesh, state: Any, rules: Rules = (),
                zero_mode: Optional[str] = None,
                data_axis: Optional[str] = None) -> Any:
    """THE PartitionSpec tree for a TrainState under ``rules`` × zero mode.

    ``zero_mode``: ``None``/``"off"`` = TP rules only; ``"1"`` = optimizer
    moments additionally cut over the data axis (ZeRO-1); ``"full"`` =
    params/moments/EMA/comm_state cut on their largest divisible dim
    (ZeRO-full, the wus shard_map steps); ``"comm"`` = only the
    error-feedback residual (compressed DP). Clients: the GSPMD step
    builders, ``parallel/comm.py``'s wus steps, the Trainer's
    ``shard_state``, and ``elastic/reshard.py`` — one table, no drift."""
    zm = None if zero_mode in (None, "off") else zero_mode
    axis = data_axis or mesh_axis("zero")
    return tree_specs(mesh, state, rules,
                      opt_shard_axis=(axis if zm else None), zero_mode=zm)


def state_shardings(mesh: Mesh, state: Any, rules: Rules = (),
                    zero_mode: Optional[str] = None,
                    data_axis: Optional[str] = None) -> Any:
    """``state_specs`` as NamedShardings (placement form)."""
    zm = None if zero_mode in (None, "off") else zero_mode
    axis = data_axis or mesh_axis("zero")
    return tree_shardings(mesh, state, rules,
                          opt_shard_axis=(axis if zm else None),
                          zero_mode=zm)


def shard_state(mesh: Mesh, state: Any, rules: Rules = (),
                zero_mode: Optional[str] = None,
                data_axis: Optional[str] = None) -> Any:
    """Place a host/replicated TrainState per ``state_specs``."""
    zm = None if zero_mode in (None, "off") else zero_mode
    axis = data_axis or mesh_axis("zero")
    return shard_tree(mesh, state, rules,
                      opt_shard_axis=(axis if zm else None), zero_mode=zm)


# -- host-side layout bridge (elastic reshard, ISSUE 13) ----------------------

def host_rules(rules: Rules) -> tuple:
    """A rule table in ``elastic.reshard.HostRules`` form: the PartitionSpec
    of each rule stripped to a plain per-dim axis-name tuple, so the
    numpy-only reshard module can mirror ``spec_for_leaf``'s resolution
    without importing jax."""
    return tuple((pattern, tuple(spec)) for pattern, spec in rules)


def host_state_layout(mesh: Mesh, state_dict: dict, rules: Rules = (),
                      zero_mode: Optional[str] = None,
                      data_axis: Optional[str] = None) -> dict:
    """``elastic.reshard.state_layout`` derived from the SAME inputs as
    ``state_specs`` — the serializable host-side image of the placement
    this plane gives a TrainState (TP rules × zero mode over this mesh's
    axis sizes). The elastic cut/merge math (``cut_state_mesh`` /
    ``merge_state_mesh``) consumes it, and a test pins that every entry
    agrees with ``state_specs`` leaf for leaf — ONE layout truth, no
    drift between device placement and host-side reshard."""
    from tpudist.elastic.reshard import state_layout
    zm = "off" if zero_mode in (None, "off") else str(zero_mode)
    d_axis = data_axis or mesh_axis("zero")
    tp_axis = mesh_axis("tp")
    tp = mesh.shape[tp_axis] if tp_axis in mesh.shape else 1
    world = mesh.shape[d_axis] if d_axis in mesh.shape else 1
    if zm == "comm":
        # The residual is placed by ZERO_PREFIXES["comm"] but never host-
        # cut (it remaps by mean-fold); layout-wise comm == off.
        zm = "off"
    return state_layout(state_dict, world, mode=zm,
                        tp_rules=host_rules(rules), tp_parts=tp,
                        data_axis=d_axis, model_axis=tp_axis)
