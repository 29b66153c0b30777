"""What decides `correct`: the timed object's first three steps against the
configuration's plain float32 reference.

Program side (read in set-up, from the very trainer the window then drives):
each step's loss, the per-leaf norm of the first gradient as the optimizer
got it (recovered from the optimizer's own state after one step), the
per-leaf norm of the parameters' change after three steps, and the same for
batch-norm running statistics. Reference side (after the window has closed,
the peak has been read and the program's state is freed): the same numbers
from `refs/<config>.py`, fed the same seeded weights and the same batches.

Norms are compared by the worst leaf: |program's norm - reference's norm|
over the larger of the reference's norm of that leaf and of the median leaf.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np


def load_reference(chip_dir: str, name: str):
    path = os.path.join(chip_dir, "refs", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_weights(ref, config: dict, seed: int, sharding):
    """(params, batch statistics) as the configuration's reference draws
    them from the seed, in one jitted call on the device. The reference is
    handed the configuration's file as parsed, lists and nested objects
    included: it imports nothing of the program and has no other way to
    learn a per-layer pattern."""
    import jax
    return jax.jit(lambda k: ref.init(k, config),
                   out_shardings=sharding)(jax.random.PRNGKey(seed))


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


def leaf_names(tree) -> list[str]:
    import jax
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_leaves_with_path(tree)]


def same_structure(ours, theirs, what: str) -> None:
    """The program's tree must be the reference's, leaf for leaf: names,
    shapes and types. That is the check of the published widths."""
    import jax
    a = [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(ours)]
    b = [(jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
         for k, v in jax.tree_util.tree_leaves_with_path(theirs)]
    if a != b:
        diff = [x for x in a if x not in b][:3] + [x for x in b if x not in a][:3]
        raise SystemExit(f"{what}: the program's tree is not the reference's "
                         f"({len(a)} vs {len(b)} leaves); first differences: "
                         f"{diff}")


def parameter_sized_extras(state) -> list:
    """Live device arrays with the shape and type of one of the trainer's
    parameter leaves (of two dimensions or more: a vector's shape is anyone's)
    beyond those the trainer's own state holds: [[shape, dtype, count, bytes],
    ...], empty where the benchmark keeps nothing of that size on the chip."""
    import collections
    import jax

    def key(x):
        return tuple(x.shape), str(x.dtype)

    extra = (collections.Counter(map(key, jax.live_arrays()))
             - collections.Counter(map(key, _leaves(state))))
    sized = {key(x): x.nbytes for x in _leaves(state.params) if x.ndim >= 2}
    return [[list(shape), dtype, extra[shape, dtype],
             extra[shape, dtype] * nbytes]
            for (shape, dtype), nbytes in sorted(sized.items())
            if extra[shape, dtype]]


def optimizer_leaves(opt_state, field: str):
    """Leaves of the optimizer-state member called `field` (optax's
    `TraceState.trace`, `ScaleByAdamState.mu`), in parameter order."""
    import jax
    out = []
    for path, leaf in jax.tree_util.tree_leaves_with_path(opt_state):
        if any(getattr(k, "name", None) == field for k in path):
            out.append(leaf)
    if not out:
        raise SystemExit(f"optimizer state has no member named {field!r}")
    return out


GROUP_BYTES = 512 * 2 ** 20


def _groups(leaves: list) -> list[slice]:
    """Runs of consecutive leaves of at most `GROUP_BYTES` together (a larger
    leaf is a run alone): what one reduction program is handed, so that what
    it makes on the device beside the program's own state stays 3 % of a
    16 GB chip whatever the model's size. A model under the bound is one run,
    one program."""
    runs, start, size = [], 0, 0
    for i, leaf in enumerate(leaves):
        if i > start and size + leaf.nbytes > GROUP_BYTES:
            runs.append(slice(start, i))
            start, size = i, 0
        size += leaf.nbytes
    return runs + [slice(start, len(leaves))] if leaves else []


class Reducers:
    """Small jitted reductions over runs of leaves (`_groups`), one host copy
    each. A leaf that waits on the host is placed for its run's call."""

    def __init__(self):
        import jax
        import jax.numpy as jnp

        def norms(xs):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32)))) for x in xs])

        self._diff_norms = jax.jit(
            lambda a, b: norms([x.astype(jnp.float32) - y.astype(jnp.float32)
                                for x, y in zip(a, b)]))
        # torch SGD keeps v1 = g + wd * p0; AdamW keeps mu1 = (1 - b1) * g
        self._sgd_grad = jax.jit(
            lambda trace, p0, wd: [t - wd * p for t, p in zip(trace, p0)])
        self._adam_grad = jax.jit(
            lambda mu, b1: [m / (1.0 - b1) for m in mu])

    def diff_norms(self, a: list, b: list) -> np.ndarray:
        """||a_i - b_i|| of every leaf (device or host leaves), float32."""
        return np.concatenate([np.asarray(self._diff_norms(a[run], b[run]))
                               for run in _groups(a)])

    def sgd_grad(self, trace: list, p0: list, wd: float) -> list:
        return [np.asarray(g) for run in _groups(trace)
                for g in self._sgd_grad(trace[run], p0[run], wd)]

    def adam_grad(self, mu: list, b1: float) -> list:
        return [np.asarray(g) for run in _groups(mu)
                for g in self._adam_grad(mu[run], b1)]


def first_grad(red: Reducers, opt_state, p0_leaves: list, model_cfg) -> list:
    """The first gradient as the optimizer got it, leaf by leaf, worked out
    from the optimizer's own state after one step; copied to the host, where
    it waits for the reference's. `p0_leaves` may wait on the host."""
    kind = model_cfg["optimizer"]
    if kind == "sgd":
        return red.sgd_grad(optimizer_leaves(opt_state, "trace"), p0_leaves,
                            float(model_cfg["weight_decay"]))
    if kind == "adamw":
        return red.adam_grad(optimizer_leaves(opt_state, "mu"),
                             float(model_cfg["adam_b1"]))
    raise SystemExit(f"no first-gradient recovery for optimizer {kind!r}")


def _norms(leaves) -> np.ndarray:
    return np.array([np.sqrt(np.sum(np.square(x, dtype=np.float64)))
                     for x in leaves])


def _square_sums(prog: list, ref: list) -> tuple[list, list]:
    """Leaf by leaf: ||program - reference||^2 and ||reference||^2."""
    return ([float(np.sum(np.square(a.astype(np.float64) - b)))
             for a, b in zip(prog, ref)],
            [float(np.sum(np.square(b, dtype=np.float64))) for b in ref])


def rel_diff(prog: list, ref: list) -> float:
    """||program - reference|| / ||reference|| over all leaves together:
    first order in a rounding error, where a gap between norms is second."""
    if len(prog) != len(ref) or not ref:
        return float("inf")
    num, den = _square_sums(prog, ref)
    out = (sum(num) / sum(den)) ** 0.5 if sum(den) > 0 else float("inf")
    return out if np.isfinite(out) else float("inf")


def leaf_rel_diffs(prog: list, ref: list) -> list[float]:
    """The same, leaf by leaf (printed, so that a limit can be read again)."""
    return [(n / d) ** 0.5 if d > 0 else float("inf")
            for n, d in zip(*_square_sums(prog, ref))]


def leaf_gap(prog: np.ndarray, ref: np.ndarray,
             stat: str = "worst") -> tuple[float, int]:
    """Per leaf: |program's norm - reference's norm| over the larger of the
    reference's norm of that leaf and of the median leaf. `worst` takes the
    largest leaf's gap, `median` the median leaf's (for a number that one
    all-but-zero leaf would otherwise own; PERF.md says where and why)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape or not len(ref):
        return float("inf"), -1
    gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    gap = np.where(np.isfinite(gap), gap, np.inf)
    if stat == "median":
        i = int(np.argsort(gap)[len(gap) // 2])
    elif stat == "worst":
        i = int(np.argmax(gap))
    else:
        raise ValueError(f"unknown leaf statistic {stat!r}")
    return float(gap[i]), i


def reference_readings(ref, model_cfg, p0, s0, batches, lr, quant=None):
    """Three reference steps from (p0, s0) over `batches` (host or device
    arrays, placed one step at a time); the same numbers the program side
    read."""
    import jax.numpy as jnp
    red = Reducers()
    params, stats, opt = p0, s0, ref.init_opt(p0)
    out = {"loss": []}
    for i, (images, labels) in enumerate(batches):
        images, labels = jnp.asarray(images), jnp.asarray(labels)
        loss, grads, params, stats, opt = ref.step(
            params, stats, opt, images, labels, model_cfg, lr, quant=quant)
        out["loss"].append(float(loss))
        if i == 0:
            out["first_grad_leaves"] = [np.asarray(g) for g in _leaves(grads)]
            out["first_grad"] = _norms(out["first_grad_leaves"])
        del grads, images, labels
    out["param_change"] = red.diff_norms(_leaves(params), _leaves(p0))
    if _leaves(s0):
        out["stats_change"] = red.diff_norms(_leaves(stats), _leaves(s0))
    return out


def compare(prog: dict, ref: dict, limits: dict, names: list[str]):
    """-> (correct, rows). A row is (number, value, limit, ok, note)."""
    rows = []
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        v = abs(a - b) / abs(b) if np.isfinite(a) and np.isfinite(b) and b \
            else float("inf")
        rows.append((f"loss_rel_gap.step{i + 1}", v, limits["loss_rel_gap"],
                     f"program {a:.6f} reference {b:.6f}"))
    if len(prog["loss"]) != len(ref["loss"]) or not prog["loss"]:
        rows.append(("loss_steps", float("inf"), 0.0, "step counts differ"))
    rows.append(("first_grad_rel_diff",
                 rel_diff(prog.get("first_grad_leaves", []),
                          ref["first_grad_leaves"]),
                 limits["first_grad_rel_diff"],
                 "norm of (program's first gradient - reference's) over the "
                 "reference's norm, all leaves together"))
    print("bench leaf_rel_diff " + json.dumps(
        {"number": "first_grad", "values": leaf_rel_diffs(
            prog.get("first_grad_leaves", []), ref["first_grad_leaves"])}),
        flush=True)
    if "head_grad_rel_diff" in limits:
        head = [i for i, n in enumerate(names["first_grad"])
                if n.startswith(limits["head_leaves"])]
        if not head:
            raise SystemExit(f"no leaf is named {limits['head_leaves']}*")
        rows.append(("head_grad_rel_diff",
                     rel_diff([prog["first_grad_leaves"][i] for i in head]
                              if "first_grad_leaves" in prog else [],
                              [ref["first_grad_leaves"][i] for i in head]),
                     limits["head_grad_rel_diff"],
                     f"the same over the leaves {limits['head_leaves']}*: the "
                     "gradient nearest the loss, before the backward chain's "
                     "rounding adds up"))
    for key in ("first_grad", "param_change", "stats_change"):
        if key not in ref:
            continue
        if f"{key}_gap" not in limits:     # read and printed, not compared
            v, leaf = leaf_gap(prog.get(key, ()), ref[key], "worst")
            print(f"correct-info {key}_worst_leaf_gap (not compared): "
                  f"{v:.6g} at {names[key][leaf] if leaf >= 0 else '?'}",
                  flush=True)
            continue
        stat = limits.get(f"{key}_stat", "worst")
        v, leaf = leaf_gap(prog.get(key, ()), ref[key], stat)
        where = names[key][leaf] if leaf >= 0 else "?"
        rows.append((f"{key}_{stat}_leaf_gap", v, limits[f"{key}_gap"],
                     f"leaf {where} program "
                     f"{float(np.asarray(prog.get(key, [np.nan]))[leaf]):.6g} "
                     f"reference {float(ref[key][leaf]):.6g}"))
        print("bench leaf_norms " + json.dumps(
            {"number": key, "program": [float(x) for x in prog.get(key, ())],
             "reference": [float(x) for x in ref[key]]}), flush=True)
    extra = prog.get("rows", [])
    rows.extend(extra)
    checked = [(n, v, lim, bool(v <= lim), note) for n, v, lim, note in rows]
    return all(r[3] for r in checked), checked


def print_rows(rows, correct: bool) -> None:
    """Every number compared beside its limit: to both streams, and last on
    standard error (the driver's record keeps the end of that)."""
    lines = [f"correct-check {n}: value {v:.6g} limit {lim:.6g} "
             f"{'ok' if ok else 'FAILED'} ({note})"
             for n, v, lim, ok, note in rows]
    lines.append(f"correct-check verdict: correct={str(correct).lower()}")
    print("\n".join(lines), flush=True)
    print("\n".join(lines), file=sys.stderr, flush=True)
