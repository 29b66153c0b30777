"""Elastic training plane tests (tpudist/elastic/; run with ``-m elastic``).

Three tiers:

- UNIT: the pure host-side reshard math (zero1 cut/merge round trips,
  reshard planning, membership decisions), the sampler's global-order
  cursor remap (no sample dropped or double-seen across a world change,
  global batches are identical slices of the same order), loader meter
  carry, topology-tagged checkpoint round trips, summarize's topology
  timeline, and the fleet world gauge.
- IN-PROCESS integration: save a real (zero1-sharded) TrainState on an
  8-device mesh, restore it onto 4-, 2-, and 1-device meshes — params
  tree-identical, zero1 partitions re-cut exactly.
- E2E through real ``tpudist.launch`` subprocess ranks: a 2-rank elastic
  gang loses rank 1 to an injected ``rank_exit``; the launcher drains the
  survivor (SIGTERM -> emergency checkpoint with the epoch's sample
  cursor -> exit 75) and REFORMS at world 1, which continues the
  interrupted epoch mid-way; ``events.launcher.jsonl`` records the
  ``topology_change`` and ``tpudist.summarize`` renders the topology
  timeline. The 4-rank cross-process-collective variant sits behind the
  conftest capability gate (this container's jaxlib cannot compile
  multiprocess CPU collectives).
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from tpudist import faults
from tpudist.elastic.membership import (mesh_str, parse_mesh_args,
                                        plan_reform_topology,
                                        reform_eligible, reform_world,
                                        rewrite_mesh_args)
from tpudist.elastic.reshard import (cut_state_mesh, cut_zero1,
                                     merge_state_mesh, merge_zero1,
                                     model_parts, plan_reshard,
                                     state_layout, topology_tag,
                                     tp_cut_dim, zero1_layout)

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_injector():
    faults.configure("")
    yield
    faults.configure("")


def _walk(tree, path=()):
    if isinstance(tree, dict):
        for k in sorted(tree, key=str):
            yield from _walk(tree[k], path + (str(k),))
    else:
        yield path, tree


def _tree_equal(a, b):
    la, lb = list(_walk(a)), list(_walk(b))
    assert [p for p, _ in la] == [p for p, _ in lb]
    for (p, x), (_, y) in zip(la, lb):
        if hasattr(x, "shape") or hasattr(y, "shape"):
            xa, ya = np.asarray(x), np.asarray(y)
            assert xa.dtype == ya.dtype, p
            assert np.array_equal(xa, ya), p
        else:
            assert x == y, p


# -- unit: zero1 cut/merge round trips ---------------------------------------

def _fake_state_dict(dim0=24, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "params": {"conv": {"kernel": rng.standard_normal((3, 3, 4, 8))
                            .astype(np.float32)}},
        "batch_stats": {"bn": {"mean": rng.standard_normal((8,))
                               .astype(np.float32)}},
        "opt_state": {
            "inner_state": {
                "0": {"trace": {
                    "conv": {"kernel": rng.standard_normal((dim0, 7))
                             .astype(np.float32)},
                    "dense": {"bias": rng.standard_normal((dim0,))
                              .astype(np.float32)}}},
            },
            # A leaf whose leading dim divides nothing interesting (prime):
            # must never be cut, at any world.
            "count": rng.standard_normal((13,)).astype(np.float32),
        },
    }


def test_cut_merge_zero1_roundtrip_all_worlds():
    """merge(cut(T, W)) == T bit-for-bit for W in {1, 2, 4}, and re-cutting
    the merged tree at W2 equals cutting the original at W2 — the exact
    save-at-W1/restore-at-W2 guarantee docs/ELASTICITY.md states."""
    tree = _fake_state_dict(dim0=24)
    for w1 in (1, 2, 4):
        shards, cut = cut_zero1(tree, w1)
        assert len(shards) == w1
        merged = merge_zero1(shards, cut)
        _tree_equal(merged, tree)
        for w2 in (1, 2, 4):
            shards_a, cut_a = cut_zero1(merged, w2)
            shards_b, cut_b = cut_zero1(tree, w2)
            assert cut_a == cut_b
            for sa, sb in zip(shards_a, shards_b):
                _tree_equal(sa, sb)


def test_cut_zero1_layout_scope():
    """Only opt_state leaves with a divisible leading dim are cut; params
    and batch_stats are never touched (they re-replicate)."""
    tree = _fake_state_dict(dim0=24)
    shards, cut = cut_zero1(tree, 4)
    assert all(p.startswith("opt_state/") for p in cut), cut
    assert not any("count" in p for p in cut)          # 13 % 4 != 0
    # rank shard holds 24/4 = 6 rows of each cut leaf; replicated leaves
    # are full on every rank.
    k = shards[2]["opt_state"]["inner_state"]["0"]["trace"]["conv"]["kernel"]
    assert k.shape == (6, 7)
    assert np.array_equal(
        k, tree["opt_state"]["inner_state"]["0"]["trace"]["conv"]["kernel"]
        [12:18])
    assert shards[1]["params"]["conv"]["kernel"].shape == (3, 3, 4, 8)
    layout = zero1_layout(tree, 4)
    assert set(layout) == set(cut)


def test_plan_reshard_census_and_fallback():
    tree = _fake_state_dict(dim0=24)      # 24 divides 4, not 5
    t4 = topology_tag(world=4, mesh_shape=(4,), mesh_axes=("data",),
                      n_devices=4, per_device_batch=6, global_batch=24,
                      zero1=True, zero1_axis="data")
    t5 = topology_tag(world=5, mesh_shape=(5,), mesh_axes=("data",),
                      n_devices=5, per_device_batch=4, global_batch=20,
                      zero1=True, zero1_axis="data")
    plan = plan_reshard(t4, t5, state_dict=tree)
    assert plan.changed and plan.world_from == 4 and plan.world_to == 5
    # 24 % 5 != 0: both trace leaves fall back to replicated at world 5.
    assert plan.recut == []
    assert len(plan.fallback) == 2, plan.fallback
    assert "fall back to replicated" in plan.describe()

    t3 = topology_tag(world=3, mesh_shape=(3,), mesh_axes=("data",),
                      n_devices=3, per_device_batch=8, global_batch=24,
                      zero1=True, zero1_axis="data")
    plan = plan_reshard(t4, t3, state_dict=tree)
    assert len(plan.recut) == 2 and plan.fallback == []

    # Unchanged topology / missing tag: explicit no-ops.
    assert not plan_reshard(t4, t4, state_dict=tree).changed
    pre = plan_reshard(None, t4, state_dict=tree)
    assert not pre.changed and "no topology tag" in pre.notes[0]


def test_ef_residual_rides_emergency_checkpoint_and_reshard(tmp_path):
    """PR 11: the --compress-grads error-feedback residual round-trips
    through the emergency-checkpoint plane and the reshard rules at
    W ∈ {1, 2, 4} — same world bit-exact, cross-world mean-folded (the
    pending gradient mass the next reduce consumes is preserved exactly),
    and plan_reshard calls the fold out in its notes."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist.elastic.reshard import remap_comm_state

    rng = np.random.default_rng(7)
    for w_save in (1, 2, 4):
        tree = _fake_state_dict(dim0=24)
        res = rng.standard_normal((w_save, 64)).astype(np.float32)
        tree["comm_state"] = {"residual": res}
        sd = {"epoch": 1, "arch": "resnet18", "best_acc1": 0.0,
              "state": tree,
              "topology": topology_tag(
                  world=w_save, mesh_shape=(w_save,), mesh_axes=("data",),
                  n_devices=w_save, per_device_batch=4,
                  global_batch=4 * w_save, zero="full",
                  zero1_axis="data"),
              "data_cursor": {"epoch": 1, "consumed": 8,
                              "samples_skipped": 0, "samples_retried": 0}}
        out = tmp_path / f"w{w_save}"
        out.mkdir()
        path = ckpt_lib.save_checkpoint(sd, False, str(out), keep=0)
        loaded = ckpt_lib.load_checkpoint(path)
        got = loaded["state"]["comm_state"]["residual"]
        np.testing.assert_array_equal(got, res)       # serialization exact
        for w_to in (1, 2, 4):
            remapped = remap_comm_state(
                dict(loaded["state"]["comm_state"]), w_to)
            assert remapped["residual"].shape == (w_to, 64)
            if w_to == w_save:
                np.testing.assert_array_equal(remapped["residual"], res)
            else:
                np.testing.assert_allclose(
                    remapped["residual"].mean(axis=0), res.mean(axis=0),
                    rtol=1e-6, atol=1e-7)
            t_to = topology_tag(
                world=w_to, mesh_shape=(w_to,), mesh_axes=("data",),
                n_devices=w_to, per_device_batch=4, global_batch=4 * w_to,
                zero="full", zero1_axis="data")
            plan = plan_reshard(loaded["topology"], t_to,
                                state_dict=loaded)
            if w_to != w_save:
                assert any("error-feedback residual mean-folds" in n
                           for n in plan.notes), plan.notes


def test_plan_reshard_full_mode_census():
    """Full-mode plans census the wider cut set (params + EMA + moments,
    largest divisible dim) and report the zero-mode transition."""
    tree = _fake_state_dict(dim0=24)
    t_full = topology_tag(world=4, mesh_shape=(4,), mesh_axes=("data",),
                          n_devices=4, per_device_batch=6, global_batch=24,
                          zero="full", zero1_axis="data")
    t_z1 = topology_tag(world=2, mesh_shape=(2,), mesh_axes=("data",),
                        n_devices=2, per_device_batch=12, global_batch=24,
                        zero1=True, zero1_axis="data")
    plan = plan_reshard(t_full, t_z1, state_dict=tree)
    assert plan.zero_from == "full" and plan.zero_to == "1"
    assert any("zero mode full -> 1" in n for n in plan.notes)
    # full-at-4 cuts params leaves too (conv kernel 3x3x4x8 cuts dim 2/3);
    # zero1-at-2 cuts only opt leaves — params fall out of the cut set.
    assert any(p.startswith("params/") for p in plan.fallback), (
        plan.recut, plan.fallback)
    # legacy tags (zero1 bool only) still plan as mode "1"
    legacy = dict(t_z1)
    legacy.pop("zero")
    plan2 = plan_reshard(legacy, t_z1, state_dict=tree)
    assert plan2.zero_from == "1"


# -- unit: TP-aware host layout + mesh cut/merge (ISSUE 13 tentpole a) -------

# Host-rule form of a tiny conv family: kernel cuts output channels over
# 'model', the per-channel vectors cut dim 0 — the shape of RESNET_RULES.
_TP_RULES = (
    (r"conv\d*/kernel$", (None, None, None, "model")),
    (r"bn\d*/(scale|bias|mean|var)$", ("model",)),
)


def _tp_state_dict(seed=5):
    rng = np.random.default_rng(seed)
    f32 = np.float32
    return {
        "params": {
            "conv1": {"kernel": rng.standard_normal((3, 3, 4, 8))
                      .astype(f32)},
            "bn1": {"scale": rng.standard_normal((8,)).astype(f32),
                    "bias": rng.standard_normal((8,)).astype(f32)},
            "fc": {"kernel": rng.standard_normal((8, 5)).astype(f32)},
        },
        "batch_stats": {"bn1": {"mean": rng.standard_normal((8,))
                                .astype(f32),
                                "var": rng.standard_normal((8,))
                                .astype(f32)}},
        "opt_state": {"inner_state": {"0": {"trace": {
            "conv1": {"kernel": rng.standard_normal((3, 3, 4, 8))
                      .astype(f32)},
            "bn1": {"scale": rng.standard_normal((8,)).astype(f32)},
            "fc": {"kernel": rng.standard_normal((8, 5)).astype(f32)},
        }}}},
    }


def test_tp_cut_dim_mirrors_spec_for_leaf():
    """Rule resolution semantics: first match wins, the model-axis dim is
    returned, indivisible or rank-short leaves fall back to replicated."""
    assert tp_cut_dim(("params", "conv1", "kernel"), (3, 3, 4, 8),
                      _TP_RULES, 2) == 3
    assert tp_cut_dim(("batch_stats", "bn1", "mean"), (8,),
                      _TP_RULES, 2) == 0
    # moments mirror their params (paths contain the same names)
    assert tp_cut_dim(("opt_state", "mu", "conv1", "kernel"), (3, 3, 4, 8),
                      _TP_RULES, 4) == 3
    # 8 % 3 != 0: replicated, never a wrong cut
    assert tp_cut_dim(("params", "conv1", "kernel"), (3, 3, 4, 8),
                      _TP_RULES, 3) is None
    # unruled leaf / tp=1: nothing to cut
    assert tp_cut_dim(("params", "fc", "kernel"), (8, 5),
                      _TP_RULES, 2) is None
    assert tp_cut_dim(("params", "conv1", "kernel"), (3, 3, 4, 8),
                      _TP_RULES, 1) is None
    # A rule naming a second axis would silently diverge from the device
    # placement (host side only knows the model part count): refuse loudly.
    with pytest.raises(ValueError, match="names axis"):
        tp_cut_dim(("params", "conv1", "kernel"), (3, 3, 4, 8),
                   ((r"conv1/kernel$", ("data", None, None, "model")),), 2)


def test_mesh_cut_merge_roundtrip_dp_tp_zero():
    """merge(cut(T, mesh)) == T bit-for-bit for dp×tp meshes with TP rules
    composed with zero1, and re-cutting the merged tree at another
    feasible mesh equals cutting the original there — the guarantee that
    makes a dp4×tp2 checkpoint restore at dp2×tp2 / dp8×tp1 / dp1×tp1."""
    tree = _tp_state_dict()
    meshes = [((4, 2), ("data", "model")), ((2, 2), ("data", "model")),
              ((8,), ("data",)), ((1,), ("data",)),
              ((1, 2), ("data", "model"))]
    for shape, axes in meshes:
        world = shape[axes.index("data")]
        tp = shape[axes.index("model")] if "model" in axes else 1
        lay = state_layout(tree, world, mode="1", tp_rules=_TP_RULES,
                           tp_parts=tp)
        shards = cut_state_mesh(tree, shape, axes, lay)
        assert len(shards) == int(np.prod(shape))
        merged = merge_state_mesh(shards, shape, axes, lay)
        _tree_equal(merged, tree)
        # TP leaves really were cut over 'model', zero leaves over 'data'.
        if tp > 1:
            k = shards[1]["params"]["conv1"]["kernel"]
            assert k.shape == (3, 3, 4, 8 // tp)
        for shape2, axes2 in meshes:
            world2 = shape2[axes2.index("data")]
            tp2 = (shape2[axes2.index("model")]
                   if "model" in axes2 else 1)
            lay2 = state_layout(tree, world2, mode="1",
                                tp_rules=_TP_RULES, tp_parts=tp2)
            a = cut_state_mesh(merged, shape2, axes2, lay2)
            b = cut_state_mesh(tree, shape2, axes2, lay2)
            for sa, sb in zip(a, b):
                _tree_equal(sa, sb)


def test_cross_topology_restore_matrix(tmp_path):
    """ISSUE 13 satellite: save at {dp4×tp2, dp2×tp2 (zero-full data cut),
    dp4 + comm_state} → restore at each feasible other topology, pinned
    bit-identical after merge through REAL checkpoint bytes, with the
    comm_state residual mean-folding (never sliced) and plan_reshard
    reporting the tp transition."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist.elastic.reshard import remap_comm_state

    rng = np.random.default_rng(11)
    saves = {
        "dp4xtp2": dict(shape=(4, 2), axes=("data", "model"), zero="off",
                        comm=False),
        "dp2xtp2_zfull": dict(shape=(2, 2), axes=("data", "model"),
                              zero="full", comm=False),
        "dp4_comm": dict(shape=(4,), axes=("data",), zero="off",
                         comm=True),
    }
    restores = [((2, 2), ("data", "model"), "off"),
                ((8,), ("data",), "off"),
                ((1,), ("data",), "off"),
                ((4,), ("data",), "full"),
                ((2,), ("data",), "1")]
    for name, s in saves.items():
        tree = _tp_state_dict()
        if s["comm"]:
            tree["comm_state"] = {
                "residual": rng.standard_normal((4, 32)).astype(np.float32)}
        world = s["shape"][s["axes"].index("data")]
        tp = (s["shape"][s["axes"].index("model")]
              if "model" in s["axes"] else 1)
        tag = topology_tag(world=world, mesh_shape=s["shape"],
                           mesh_axes=s["axes"],
                           n_devices=int(np.prod(s["shape"])),
                           per_device_batch=4,
                           global_batch=4 * int(np.prod(s["shape"])),
                           zero=s["zero"], zero1_axis="data")
        assert model_parts(tag) == tp
        # The checkpoint holds the FULL tree (the save-side merge of the
        # per-device shards — what np.asarray on a sharded global array
        # gathers); pin that the cut really is invertible through disk.
        lay = state_layout(tree, world, mode=s["zero"],
                           tp_rules=_TP_RULES, tp_parts=tp)
        shards = cut_state_mesh(tree, s["shape"], s["axes"], lay)
        full = merge_state_mesh(shards, s["shape"], s["axes"], lay)
        sd = ckpt_lib.state_to_dict(full, "tiny", epoch=0, best_acc1=0.0,
                                    topology=tag)
        out = tmp_path / name
        out.mkdir()
        ckpt_lib.save_checkpoint(sd, False, str(out))
        loaded = ckpt_lib.load_checkpoint(str(out))
        lt = loaded["state"]
        comm = lt.pop("comm_state", None)
        want = dict(tree)
        want_comm = want.pop("comm_state", None)
        _tree_equal(lt, want)
        for shape2, axes2, zero2 in restores:
            world2 = shape2[axes2.index("data")]
            tp2 = (shape2[axes2.index("model")]
                   if "model" in axes2 else 1)
            tag2 = topology_tag(world=world2, mesh_shape=shape2,
                                mesh_axes=axes2,
                                n_devices=int(np.prod(shape2)),
                                per_device_batch=4,
                                global_batch=4 * int(np.prod(shape2)),
                                zero=zero2, zero1_axis="data")
            plan = plan_reshard(loaded["topology"], tag2, state_dict=loaded)
            assert plan.tp_from == tp and plan.tp_to == tp2
            if tp != tp2:
                assert f"model axis {tp} -> {tp2}" in plan.describe()
            # Restore-side re-cut equals cutting the ORIGINAL tree there.
            lay2 = state_layout(lt, world2, mode=zero2,
                                tp_rules=_TP_RULES, tp_parts=tp2)
            a = cut_state_mesh(lt, shape2, axes2, lay2)
            b = cut_state_mesh(want, shape2, axes2, lay2)
            for sa, sb in zip(a, b):
                _tree_equal(sa, sb)
            if want_comm is not None:
                got = remap_comm_state(dict(comm), world2)
                assert got["residual"].shape == (world2, 32)
                np.testing.assert_allclose(
                    got["residual"].mean(axis=0),
                    want_comm["residual"].mean(axis=0),
                    rtol=1e-6, atol=1e-6)


def test_host_layout_matches_state_specs(devices):
    """THE drift pin (tentpole a): ``plane.host_state_layout`` — what the
    elastic cut/merge consumes — agrees leaf for leaf with
    ``plane.state_specs`` — what the device placement and step builders
    compile against — for TP rules × zero {off, 1} on a dp×tp mesh and
    zero-full on a data mesh. One layout truth, no drift."""
    import jax
    from flax import serialization
    from tpudist.config import Config
    from tpudist.dist import make_mesh
    from tpudist.models import create_model
    from tpudist.parallel import plane
    from tpudist.parallel.tensor_parallel import (RESNET_RULES, _path_str)
    from tpudist.train import create_train_state

    cfg = Config(arch="resnet18", num_classes=4, image_size=16,
                 batch_size=16, use_amp=False, seed=0)
    model = create_model("resnet18", num_classes=4)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                               input_shape=(1, 16, 16, 3))
    sd = serialization.to_state_dict(state)

    def check(mesh, rules, zero_mode):
        specs = plane.state_specs(mesh, state, rules, zero_mode=zero_mode)
        lay = plane.host_state_layout(mesh, sd, rules, zero_mode=zero_mode)
        flat = jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(
                x, jax.sharding.PartitionSpec))
        n_sharded = 0
        for path, spec in flat:
            p = _path_str(path)
            cut = [(d, a) for d, a in enumerate(spec) if a is not None]
            ent = lay.get(p)
            if cut:
                n_sharded += 1
                d, a = cut[0]
                assert ent is not None, (p, spec)
                assert ent["axis"] == d and ent["mesh_axis"] == a \
                    and ent["parts"] == mesh.shape[a], (p, spec, ent)
            else:
                assert ent is None or "comm_state" in p, (p, ent)
        assert n_sharded == len(lay) > 50

    mesh_tp = make_mesh((4, 2), ("data", "model"), devices)
    check(mesh_tp, RESNET_RULES, None)
    check(mesh_tp, RESNET_RULES, "1")
    mesh_dp = make_mesh((8,), ("data",), devices)
    check(mesh_dp, (), "full")


# -- unit: membership decisions ----------------------------------------------

def test_reform_topology_policy():
    """ISSUE 13 tentpole b: keep tp when the surviving world divides it,
    fold the model axis into dp otherwise, pass pure-DP requests through
    untouched — and the command-line rewrite round-trips."""
    # keep: 4-rank dp2xtp2 loses 2 -> world 2 still divides tp 2
    assert plan_reform_topology([2, 2], ["data", "model"], 2) == \
        ([2, 2], ["data", "model"], "keep")
    # fold: world 3 no longer divides tp 2 -> pure data over all devices
    assert plan_reform_topology([2, 2], ["data", "model"], 3) == \
        ([4], ["data"], "fold")
    assert plan_reform_topology([1, 2], ["data", "model"], 1) == \
        ([2], ["data"], "fold")
    # tp=1 / no model axis / no mesh request: keep as-is
    assert plan_reform_topology([4, 1], ["data", "model"], 3) == \
        ([4, 1], ["data", "model"], "keep")
    assert plan_reform_topology([4], ["data"], 3) == ([4], ["data"], "keep")
    assert plan_reform_topology(None, None, 3) == (None, None, "keep")
    # composed data,pipe,model folds model into data, keeps pipe
    assert plan_reform_topology([2, 2, 2], ["data", "pipe", "model"], 3) \
        == ([4, 2], ["data", "pipe"], "fold")
    assert mesh_str([2, 2], ["data", "model"]) == "2x2[data,model]"
    assert mesh_str(None) == "default"

    cmd = ["python", "-m", "tpudist", "--mesh-shape", "2,2",
           "--mesh-axes=data,model", "-b", "24"]
    assert parse_mesh_args(cmd) == ([2, 2], ["data", "model"])
    out = rewrite_mesh_args(cmd, [4], ["data"])
    assert parse_mesh_args(out) == ([4], ["data"])
    assert out[out.index("--mesh-shape") + 1] == "4"
    assert "--mesh-axes=data" in out
    # absent flags are appended, other tokens untouched
    out2 = rewrite_mesh_args(["x"], [4], ["data"])
    assert parse_mesh_args(out2) == ([4], ["data"])
    assert parse_mesh_args(["x"]) == (None, None)


def test_reform_eligibility_and_world_math():
    assert reform_eligible(41) and reform_eligible(75) \
        and reform_eligible(-9)
    assert not reform_eligible(0) and not reform_eligible(130) \
        and not reform_eligible(2)
    # 4-rank gang loses rank 2: reform at 3 while elastic + above the floor.
    assert reform_world(4, {2}, 41, elastic=True, min_ranks=2) == 3
    assert reform_world(4, {1, 2}, 41, elastic=True, min_ranks=2) == 2
    assert reform_world(4, {1, 2, 3}, 41, elastic=True, min_ranks=2) is None
    assert reform_world(4, {2}, 41, elastic=False, min_ranks=1) is None
    assert reform_world(4, set(), 41, elastic=True, min_ranks=1) is None
    assert reform_world(4, {2}, 2, elastic=True, min_ranks=1) is None
    assert reform_world(2, {1}, 75, elastic=True, min_ranks=1) == 1


# -- unit: sampler cursor remap ----------------------------------------------

def _global_order(L, seed, epoch):
    from tpudist.data.sampler import ShardedSampler
    s = ShardedSampler(L, 1, 0, shuffle=True, seed=seed)
    s.set_epoch(epoch)
    return s.global_order()


def test_sampler_default_path_unchanged():
    """cursor == 0 must reproduce the pre-elastic DistributedSampler
    algorithm exactly (pad to a replica multiple from the front, stride)."""
    from tpudist.data.sampler import ShardedSampler
    for L, W in ((101, 4), (32, 8), (7, 3)):
        idx = np.arange(L)
        rng = np.random.default_rng((5, 2))
        rng.shuffle(idx)
        ns = -(-L // W)
        total = ns * W
        padded = np.concatenate([idx, idx[: total - len(idx)]]) \
            if total > len(idx) else idx
        for rank in range(W):
            s = ShardedSampler(L, W, rank, shuffle=True, seed=5)
            s.set_epoch(2)
            assert np.array_equal(s.indices(), padded[rank:total:W])
            assert len(s) == ns


def test_sampler_cursor_remap_no_drop_no_double():
    """After consuming C positions at world W1, the remainder redistributed
    at world W2 covers exactly order[C:] (union over ranks), and each
    continuation global batch is exactly the next B-slice of the same
    order — the 'no sample dropped, none double-seen' guarantee."""
    from tpudist.data.sampler import ShardedSampler
    L, B, seed, epoch = 96, 24, 0, 1
    order = _global_order(L, seed, epoch)
    cursor = 2 * B
    for W2 in (1, 2, 3, 4):
        hb = B // W2
        per_rank = []
        for r in range(W2):
            s = ShardedSampler(L, W2, r, shuffle=True, seed=seed)
            s.set_epoch(epoch)
            s.set_cursor(cursor)
            per_rank.append(s.indices())
            assert len(s) == len(per_rank[-1])
        seen = np.concatenate(per_rank)
        assert sorted(seen.tolist()) == sorted(order[cursor:].tolist()), W2
        n_batches = min(len(p) for p in per_rank) // hb
        assert n_batches == (L - cursor) // B
        for j in range(n_batches):
            batch = np.concatenate(
                [p[j * hb:(j + 1) * hb] for p in per_rank])
            want = order[cursor + j * B: cursor + (j + 1) * B]
            assert sorted(batch.tolist()) == sorted(want.tolist()), (W2, j)


def test_sampler_cursor_edges():
    from tpudist.data.sampler import ShardedSampler
    s = ShardedSampler(10, 2, 0, shuffle=False, seed=0)
    s.set_cursor(10 ** 9)                  # clamped: epoch fully consumed
    assert len(s) == 0 and len(s.indices()) == 0
    s.set_cursor(9)                        # 1 remaining, padded to 2
    assert len(s) == 1 and len(s.indices()) == 1
    s.set_epoch(1)                         # set_epoch clears the cursor
    assert s.cursor == 0 and len(s) == 5


def test_loader_cursor_continuation_and_meter_carry():
    """DataLoader.set_cursor: the continuation's batches are the tail of
    the uninterrupted epoch's batch sequence (same world), and the
    degradation meters seed from the checkpointed counts — once."""
    from tpudist.data.loader import DataLoader
    from tpudist.data.sampler import ShardedSampler

    class Dataset:
        def __len__(self):
            return 48

        def __getitem__(self, i):
            return np.full((2, 2, 3), i, dtype=np.float32), i % 4

    def batches(cursor=None):
        dl = DataLoader(Dataset(), batch_size=8, num_workers=2,
                        sampler=ShardedSampler(48, 1, 0, seed=7),
                        retry_backoff=0.0)
        dl.set_epoch(3)
        if cursor is not None:
            dl.set_cursor(cursor, samples_skipped=5, samples_retried=2)
        out = [(im.copy(), lb.copy()) for im, lb in dl]
        return dl, out

    _, full = batches()
    dl, cont = batches(cursor=16)
    assert len(full) == 6 and len(cont) == 4
    for (fi, fl), (ci, cl) in zip(full[2:], cont):
        assert np.array_equal(fi, ci) and np.array_equal(fl, cl)
    # Meters seeded from the carried counts (and the carry is one-shot).
    assert dl.samples_skipped == 5 and dl.samples_retried == 2
    assert dl._carry_skipped == 0 and dl._carry_retried == 0
    list(dl)                               # next epoch iteration: fresh
    assert dl.samples_skipped == 0


# -- unit: checkpoint topology tag round trip --------------------------------

def test_checkpoint_carries_topology_and_cursor(tmp_path):
    from tpudist import checkpoint as ckpt_lib
    tag = topology_tag(world=2, mesh_shape=(2,), mesh_axes=("data",),
                       n_devices=2, per_device_batch=12, global_batch=24,
                       zero1=False)
    cursor = {"epoch": 1, "consumed": 24, "samples_skipped": 1,
              "samples_retried": 2}
    sd = ckpt_lib.state_to_dict(_fake_state_dict(), "resnet18", epoch=0,
                                best_acc1=0.5, topology=tag,
                                data_cursor=cursor)
    ckpt_lib.save_checkpoint(sd, False, str(tmp_path))
    loaded = ckpt_lib.load_checkpoint(str(tmp_path))
    assert loaded["topology"]["world"] == 2
    assert loaded["topology"]["version"] >= 1
    assert loaded["data_cursor"] == cursor
    # Pre-elastic schema (no tag) stays loadable and untouched.
    sd2 = ckpt_lib.state_to_dict(_fake_state_dict(), "resnet18", 0, 0.0)
    assert "topology" not in sd2 and "data_cursor" not in sd2


# -- unit: summarize topology timeline ---------------------------------------

def test_summarize_topology_timeline():
    from tpudist.summarize import analyze, format_report
    t0 = 1000.0
    events = [
        {"t": t0, "type": "launcher_start", "rank": -1, "attempt": 0,
         "nprocs": 4, "mesh": "2x2[data,model]"},
        {"t": t0 + 8.0, "type": "eviction", "rank": -1, "attempt": 0,
         "straggler_rank": 1, "windows": 3, "factor": 5.0},
        {"t": t0 + 9.0, "type": "rank_exit", "rank": -1, "attempt": 0,
         "exit_rank": 1, "code": 41, "classification": "crash (exit 41)"},
        {"t": t0 + 10.0, "type": "topology_change", "rank": -1, "attempt": 1,
         "from_world": 4, "to_world": 3, "lost_ranks": "1",
         "from_mesh": "2x2[data,model]", "to_mesh": "4[data]",
         "mesh_action": "fold"},
        {"t": t0 + 10.5, "type": "launcher_start", "rank": -1, "attempt": 1,
         "nprocs": 3, "mesh": "4[data]"},
        {"t": t0 + 12.0, "type": "reshard", "rank": 0, "attempt": 1,
         "from_world": 4, "to_world": 3, "zero1_recut": 10,
         "zero1_fallback": 2, "tp_from": 2, "tp_to": 1},
        {"t": t0 + 13.0, "type": "collective_deadline", "rank": -1,
         "attempt": 1, "suspect_rank": 2, "max_age_s": 33.0,
         "deadline_s": 30.0},
    ]
    a = analyze(events)
    kinds = [t["kind"] for t in a["topology"]]
    assert kinds == ["launch", "evict", "reform", "launch", "reshard"]
    report = format_report(a)
    assert "topology timeline" in report
    assert re.search(r"\[launch\].*world 4, mesh 2x2\[data,model\]", report)
    assert re.search(r"\[evict\].*rank 1: persistent straggler", report)
    assert re.search(r"\[reform\].*world 4 -> 3, mesh 2x2\[data,model\] -> "
                     r"4\[data\] fold.*lost rank\(s\) 1", report)
    assert re.search(r"\[reshard\] rank 0: checkpoint world 4 -> 3", report)
    # eviction + collective_deadline ride the fault timeline too
    assert re.search(r"\[eviction\] rank 1.*evicted", report)
    assert re.search(r"\[collective_deadline\] rank 2.*wedged", report)
    # No timeline section for a boring single-launch run.
    boring = analyze(events[:1])
    assert "topology timeline" not in format_report(boring)


def test_fleet_metrics_world_gauge():
    from tpudist.obs.server import FleetMetrics
    fm = FleetMetrics("", nprocs=4, straggler_factor=0)
    fm.observe({"t": 0.0, "type": "launcher_start", "rank": -1,
                "attempt": 0, "nprocs": 4})
    fm.refresh(attempt=0, beats={})
    out = fm.render()
    assert "tpudist_world_size 4" in out
    assert "tpudist_fleet_reforms_total 0" in out
    assert "tpudist_fleet_evictions_total 0" in out
    fm.observe({"t": 0.5, "type": "eviction", "rank": -1, "attempt": 0,
                "straggler_rank": 2, "windows": 3})
    fm.observe({"t": 0.7, "type": "collective_deadline", "rank": -1,
                "attempt": 0, "suspect_rank": 1, "max_age_s": 40.0})
    fm.observe({"t": 1.0, "type": "topology_change", "rank": -1,
                "attempt": 1, "from_world": 4, "to_world": 3,
                "lost_ranks": "2"})
    fm.refresh(attempt=1, beats={})
    out = fm.render()
    assert "tpudist_world_size 3" in out
    assert "tpudist_fleet_reforms_total 1" in out
    assert "tpudist_fleet_evictions_total 1" in out
    assert "tpudist_fleet_collective_deadline_total 1" in out
    assert fm.nprocs == 3                  # endpoint scrape loop follows


# -- in-process: save at W1 -> restore at W2 on real meshes ------------------

def test_zero1_state_restores_across_mesh_sizes(devices):
    """A real zero1-sharded TrainState saved on an 8-device data mesh
    restores onto 4-, 2-, and 1-device meshes: params tree-identical,
    optimizer partitions re-cut by shard_tree onto the new mesh, logical
    values bit-identical throughout."""
    import jax
    from tpudist import checkpoint as ckpt_lib
    from tpudist.config import Config
    from tpudist.dist import make_mesh
    from tpudist.parallel import shard_tree
    from tpudist.train import create_train_state
    from flax import linen as nn

    class Tiny(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(8)(nn.relu(nn.Dense(16)(x)))

    cfg = Config(arch="resnet18", num_classes=8, image_size=4,
                 batch_size=16, use_amp=False, seed=0, zero_opt=True)
    state = create_train_state(jax.random.PRNGKey(0), Tiny(), cfg,
                               input_shape=(1, 4, 4, 3))
    mesh8 = make_mesh((8,), ("data",), devices)
    sharded = shard_tree(mesh8, state, (), opt_shard_axis="data")
    tag8 = topology_tag(world=1, mesh_shape=(8,), mesh_axes=("data",),
                        n_devices=8, per_device_batch=2, global_batch=16,
                        zero1=True, zero1_axis="data")
    ckpt = ckpt_lib.state_to_dict(sharded, "tiny", epoch=0, best_acc1=0.0,
                                  topology=tag8)

    host = jax.device_get
    want = host(state)
    for n in (4, 2, 1):
        mesh = make_mesh((n,), ("data",), devices[:n])
        template = create_train_state(jax.random.PRNGKey(0), Tiny(), cfg,
                                      input_shape=(1, 4, 4, 3))
        logs = []
        restored = ckpt_lib.restore_train_state(
            template, ckpt,
            target_topology=topology_tag(
                world=1, mesh_shape=(n,), mesh_axes=("data",), n_devices=n,
                per_device_batch=16 // n, global_batch=16, zero1=True,
                zero1_axis="data"),
            log=logs.append)
        placed = shard_tree(mesh, restored, (), opt_shard_axis="data")
        assert logs and "cross-topology restore" in logs[0]
        got = host(placed)
        for (pa, a), (pb, b) in zip(
                sorted(jax.tree_util.tree_leaves_with_path(want.params),
                       key=lambda kv: str(kv[0])),
                sorted(jax.tree_util.tree_leaves_with_path(got.params),
                       key=lambda kv: str(kv[0]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(pa))
        for (pa, a), (pb, b) in zip(
                sorted(jax.tree_util.tree_leaves_with_path(want.opt_state),
                       key=lambda kv: str(kv[0])),
                sorted(jax.tree_util.tree_leaves_with_path(got.opt_state),
                       key=lambda kv: str(kv[0]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(pa))
        # The zero1 partition layout actually re-cut: a dim0-divisible
        # optimizer leaf is sharded over the n-device data axis.
        leaf = placed.opt_state.inner_state[1].trace["Dense_0"]["kernel"]
        shard_rows = {s.data.shape[0]
                      for s in leaf.addressable_shards}
        assert shard_rows == {leaf.shape[0] // n}, (n, shard_rows)


# -- e2e: reform through real tpudist.launch ---------------------------------

_TRAINER_FLAGS = ["--synthetic", "--synthetic-size", "96", "-b", "24",
                  "--epochs", "2", "-a", "resnet18", "--image-size", "16",
                  "--num-classes", "4", "--no-use_amp", "--workers", "2",
                  "-p", "1", "--overwrite", "keep", "--resume", "auto",
                  "--keep-checkpoints", "2", "--seed", "0",
                  "--telemetry", "--no-telemetry_mfu"]


def _launch_elastic(outpath, timeout, *, nprocs=2, min_ranks=1, inject="",
                    max_restarts=0, trainer_flags=(), extra_env=None,
                    elastic=True, devices_per_proc=1):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["TPUDIST_NO_DONATE"] = "1"       # see tests/test_faults.py docstring
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "tpudist.launch", "--nprocs", str(nprocs),
           "--devices-per-proc", str(devices_per_proc),
           "--max-restarts", str(max_restarts)]
    if elastic:
        # Wide drain grace: under CI contention the survivor can still be
        # inside its first XLA compile when the SIGTERM lands — it only
        # reaches the preemption boundary (and the emergency checkpoint)
        # after the compile returns, which must not race the SIGKILL.
        cmd += ["--elastic", "--min-ranks", str(min_ranks),
                "--drain-grace", "180"]
    if inject:
        cmd += ["--inject", inject]
    cmd += ["--", sys.executable, "-m", "tpudist",
            "--outpath", str(outpath)] + list(trainer_flags or _TRAINER_FLAGS)
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _launcher_events(outpath):
    with open(os.path.join(outpath, "events.launcher.jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_elastic_reform_on_rank_loss_e2e(tmp_path, mp_timeout):
    """The acceptance chain on the CPU gang simulation: a 2-rank elastic
    gang loses rank 1 mid-epoch-1 (injected rank_exit); the launcher
    drains rank 0 (emergency checkpoint carrying the sample cursor),
    REFORMS at world 1 without touching the restart budget, and the
    reformed run CONTINUES epoch 1 from the cursor and finishes. The
    launcher stream records the topology_change; summarize renders the
    topology timeline."""
    out = tmp_path / "out"
    # Pacing: the ranks run independent jit programs (no lockstep in the
    # CPU sim), and a warm XLA cache lets an unpaced rank blow through the
    # whole run in seconds. A 5 s first-step stall on the DYING rank plus
    # a 500 ms per-step stall on every rank guarantees (a) the survivor is
    # inside fit() — preemption guard armed, >= 1 batch dispatched — when
    # rank 1 dies at its step-5 boundary, and (b) with 3 epochs the
    # survivor cannot have finished first.
    flags = list(_TRAINER_FLAGS)
    flags[flags.index("--epochs") + 1] = "3"
    r = _launch_elastic(
        out, mp_timeout(2, compile_cost=2.0), trainer_flags=flags,
        inject="rank_exit@step=5@rank=1@attempt=0;"
               "slow_peer:ms=5000@rank=1@step=0@attempt=0;"
               "slow_peer:ms=500@attempt=0")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "rank_exit firing at step 5" in r.stdout
    assert "REFORMING gang at world 1" in r.stderr
    assert "restart" not in r.stderr.split("REFORMING")[0]

    # The survivor drained through the preemption path and the reformed
    # run continued the interrupted epoch from the cursor. Two correct
    # outcomes, both exact-continuation: (a) the SIGTERM landed mid-epoch
    # — the cursor is nonzero and the reformed run logs the continuation;
    # (b) it landed in the narrow epoch-boundary window (survivor between
    # set_epoch and its first dispatch) — the cursor is provably 0 and
    # the epoch replays from its start, which consumes the identical
    # order (nothing had been consumed). Pre-hardening this raced: the
    # boundary outcome failed the continuation regex (PR 8's "racy under
    # load" note).
    assert "emergency checkpoint" in r.stdout
    m = re.search(r"elastic continuation: epoch (\d+) resumes at global "
                  r"sample (\d+)", r.stdout)
    if m:
        assert 0 < int(m.group(2)) <= 96, m.group(2)
    else:
        assert re.search(r"emergency checkpoint \(will resume at epoch "
                         r"\d+, global sample cursor 0\)", r.stdout), \
            r.stdout[-4000:]

    evs = _launcher_events(out)
    changes = [e for e in evs if e["type"] == "topology_change"]
    assert len(changes) == 1
    assert changes[0]["from_world"] == 2 and changes[0]["to_world"] == 1
    assert changes[0]["lost_ranks"] == "1"
    exits = {e["classification"] for e in evs if e["type"] == "rank_exit"}
    assert any("crash" in c for c in exits)          # the lost rank
    assert any("preempted" in c for c in exits)      # the drained survivor
    assert not [e for e in evs if e["type"] == "restart"]

    # The final checkpoint is topology-tagged by the world-1 run.
    from tpudist.checkpoint import load_checkpoint
    ckpt = load_checkpoint(str(out))
    assert ckpt["topology"]["world"] == 1

    # summarize: the topology timeline renders the reform.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    s = subprocess.run([sys.executable, "-m", "tpudist.summarize", str(out)],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert s.returncode == 0, s.stderr[-2000:]
    assert "topology timeline" in s.stdout
    assert re.search(r"\[reform\]\s+world 2 -> 1", s.stdout), s.stdout


def test_min_ranks_floor_falls_back_to_restart(tmp_path, mp_timeout):
    """Losing a rank below --min-ranks must NOT reform: with a 2-rank gang
    and --min-ranks 2, the rank loss falls through to the (exhausted)
    restart budget and the launcher exits with the failure."""
    out = tmp_path / "out"
    r = _launch_elastic(
        out, mp_timeout(2, compile_cost=2.0), min_ranks=2,
        inject="rank_exit@step=4@rank=1@attempt=0")
    assert r.returncode == 41, (r.returncode, r.stderr[-2000:])
    assert "REFORMING" not in r.stderr
    assert "restart budget exhausted" in r.stderr
    evs = _launcher_events(out)
    assert not [e for e in evs if e["type"] == "topology_change"]


def test_elastic_smoke_script(tmp_path, mp_timeout):
    """Satellite: tools/elastic_smoke.sh chains inject -> reform ->
    reshard-restore round trip -> summarize topology timeline, and prints
    ELASTIC_SMOKE_OK last."""
    env = dict(os.environ)
    env["TPUDIST_ELASTIC_SMOKE_DIR"] = str(tmp_path / "work")
    r = subprocess.run(["bash", os.path.join(REPO, "tools",
                                             "elastic_smoke.sh")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=mp_timeout(2, compile_cost=2.0))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert r.stdout.strip().splitlines()[-1] == "ELASTIC_SMOKE_OK"


def test_dp_tp_reform_folds_model_axis_e2e(tmp_path, mp_timeout):
    """ISSUE 13 acceptance: a 4-rank dp2×tp2 gang (CPU gang sim: each rank
    simulates the full 2×2 mesh on 4 local devices, data sharded over the
    4 ranks) loses rank 3 mid-epoch-1; the launcher drains the survivors,
    re-plans the topology (world 3 no longer divides tp 2 → the model
    axis FOLDS into dp: mesh 2x2[data,model] → 4[data]), relaunches with
    the rewritten --mesh-shape/--mesh-axes, and the reformed gang resumes
    from the emergency checkpoint — cross-mesh restore (the reshard event
    carries tp 2 → 1) with the data cursor continuing the epoch no-drop/
    no-double. summarize renders the topology timeline WITH mesh shapes."""
    out = tmp_path / "out"
    flags = list(_TRAINER_FLAGS) + ["--mesh-shape", "2,2",
                                    "--mesh-axes", "data,model"]
    flags[flags.index("--epochs") + 1] = "4"
    flags[flags.index("--synthetic-size") + 1] = "144"
    r = _launch_elastic(
        out, mp_timeout(4, compile_cost=3.0), nprocs=4,
        trainer_flags=flags, devices_per_proc=4,
        # Pacing (see test_elastic_reform_on_rank_loss_e2e), tuned for 4
        # concurrent 4-device GSPMD compiles whose variance is real: the
        # DYING rank's 8 s first-step stall covers a survivor compiling
        # slower than it (the cursor needs >= 1 dispatched step before
        # the drain lands), while the 4-epoch / 6-step-per-epoch run is
        # long enough that the survivors cannot FINISH before the death
        # lands even if the dying rank compiles slowest.
        inject="rank_exit@step=7@rank=3@attempt=0;"
               "slow_peer:ms=8000@rank=3@step=0@attempt=0;"
               "slow_peer:ms=500@attempt=0")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "REFORMING gang at world 3" in r.stderr
    assert "mesh 2x2[data,model] -> 4[data]" in r.stderr
    assert "model axis folded into data" in r.stderr

    # The survivors drained with the cursor; the reformed (pure-DP) gang
    # continued the interrupted epoch on the new world. Epoch-boundary
    # drains (cursor 0) are the other exact outcome — see
    # test_elastic_reform_on_rank_loss_e2e.
    assert "emergency checkpoint" in r.stdout
    m = re.search(r"elastic continuation: epoch (\d+) resumes at global "
                  r"sample (\d+)", r.stdout)
    if m:
        assert 0 < int(m.group(2)) <= 144
    else:
        assert re.search(r"emergency checkpoint \(will resume at epoch "
                         r"\d+, global sample cursor 0\)", r.stdout), \
            r.stdout[-4000:]

    evs = _launcher_events(out)
    changes = [e for e in evs if e["type"] == "topology_change"]
    assert len(changes) == 1
    assert changes[0]["from_world"] == 4 and changes[0]["to_world"] == 3
    assert changes[0]["from_mesh"] == "2x2[data,model]"
    assert changes[0]["to_mesh"] == "4[data]"
    assert changes[0]["mesh_action"] == "fold"

    # The rank stream's reshard event records the tp transition, and the
    # final checkpoint is tagged with the folded topology.
    rank_events = []
    for p in out.glob("events.*.jsonl"):
        if "launcher" in p.name:
            continue
        with open(p) as f:
            rank_events += [json.loads(ln) for ln in f if ln.strip()]
    reshards = [e for e in rank_events if e["type"] == "reshard"]
    assert reshards and all(e["tp_from"] == 2 and e["tp_to"] == 1
                            for e in reshards), reshards
    from tpudist.checkpoint import load_checkpoint
    ckpt = load_checkpoint(str(out))
    assert ckpt["topology"]["mesh_shape"] == [4]
    assert ckpt["topology"]["mesh_axes"] == ["data"]

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    s = subprocess.run([sys.executable, "-m", "tpudist.summarize",
                        str(out)], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=120)
    assert s.returncode == 0, s.stderr[-2000:]
    assert re.search(r"\[reform\]\s+world 4 -> 3, "
                     r"mesh 2x2\[data,model\] -> 4\[data\] fold", s.stdout), \
        s.stdout


@pytest.mark.slow
def test_straggler_eviction_drains_and_reforms_e2e(tmp_path, mp_timeout):
    """ISSUE 13 tentpole c: the persistent-straggler signal gains teeth.
    (slow tier: the eviction chain's tier-1 run is the chaos-matrix smoke
    cell straggle×dp, tools/chaos_matrix.sh — this is the richer-assert
    twin.)
    Rank 1 straggles 1.5 s/step from step 2 (``straggle`` injection — the
    deterministic eviction driver); with --evict-stragglers 2 the
    launcher drains it after 2 consecutive flagged windows through the
    normal SIGTERM → emergency-checkpoint → exit-75 path, the gang
    reforms at world 1, and the run finishes. Evictions are counted
    SEPARATELY from crash restarts (an ``eviction`` event, zero
    ``restart`` events) and summarize shows the [evict] timeline entry."""
    out = tmp_path / "out"
    flags = list(_TRAINER_FLAGS)
    flags[flags.index("--epochs") + 1] = "3"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["TPUDIST_NO_DONATE"] = "1"
    cmd = [sys.executable, "-m", "tpudist.launch", "--nprocs", "2",
           "--devices-per-proc", "1", "--max-restarts", "0",
           "--elastic", "--min-ranks", "1", "--drain-grace", "180",
           "--straggler-factor", "3", "--evict-stragglers", "2",
           "--inject", "straggle:ms=1500,from=2@rank=1@attempt=0;"
                       "slow_peer:ms=300@attempt=0",
           "--", sys.executable, "-m", "tpudist",
           "--outpath", str(out)] + flags
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=mp_timeout(2, compile_cost=2.5))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "EVICTING straggler rank 1" in r.stderr
    assert "REFORMING gang at world 1" in r.stderr

    evs = _launcher_events(out)
    evictions = [e for e in evs if e["type"] == "eviction"]
    assert len(evictions) == 1
    assert evictions[0]["straggler_rank"] == 1
    assert evictions[0]["windows"] == 2
    # Counted separately: a reform (topology_change), zero restarts, and
    # the evicted rank's exit classified as the resumable preemption.
    assert [e for e in evs if e["type"] == "topology_change"]
    assert not [e for e in evs if e["type"] == "restart"]
    exits = {e["exit_rank"]: e["classification"] for e in evs
             if e["type"] == "rank_exit"}
    assert "preempted" in exits.get(1, ""), exits

    s = subprocess.run([sys.executable, "-m", "tpudist.summarize",
                        str(out)], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert s.returncode == 0, s.stderr[-2000:]
    assert re.search(r"\[evict\]\s+rank 1: persistent straggler drained "
                     r"after 2 flagged windows", s.stdout), s.stdout


@pytest.mark.slow
def test_collective_deadline_converts_wedge_to_reform_e2e(tmp_path,
                                                          mp_timeout):
    """ISSUE 13 tentpole c (dead-collective watchdog): both ranks wedge at
    step 1 (a 300 s stall — the dead-collective shape: nobody exits, so
    abort-on-peer-loss never fires). With --collective-deadline 12 the
    launcher notices every live rank's heartbeat is stale, emits the loud
    collective_deadline event naming the stalest suspect, SIGTERMs it and
    escalates to SIGKILL after --drain-grace (a wedged rank cannot act on
    SIGTERM), converting the hang into a reform that completes the run."""
    out = tmp_path / "out"
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["TPUDIST_NO_DONATE"] = "1"
    flags = list(_TRAINER_FLAGS)
    flags[flags.index("--synthetic-size") + 1] = "48"
    cmd = [sys.executable, "-m", "tpudist.launch", "--nprocs", "2",
           "--devices-per-proc", "1", "--max-restarts", "0",
           "--elastic", "--min-ranks", "1", "--drain-grace", "15",
           "--collective-deadline", "12",
           "--inject", "slow_peer:ms=300000@step=1@attempt=0",
           "--", sys.executable, "-m", "tpudist",
           "--outpath", str(out)] + flags
    r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=mp_timeout(2, compile_cost=2.5))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "COLLECTIVE DEADLINE" in r.stderr
    assert "REFORMING gang at world 1" in r.stderr
    evs = _launcher_events(out)
    dls = [e for e in evs if e["type"] == "collective_deadline"]
    assert len(dls) == 1 and dls[0]["max_age_s"] > 12.0
    assert dls[0]["suspect_rank"] in (0, 1)
    assert [e for e in evs if e["type"] == "topology_change"]


# -- e2e (env-gated): real cross-process collectives -------------------------

@pytest.mark.slow
def test_reform_matches_smaller_world_reference(tmp_path, mp_timeout):
    """4 distributed ranks lose rank 3 at an epoch boundary; the gang
    reforms at world 3 and replays epoch 1. An UNINTERRUPTED 3-rank gang
    resuming the same checkpoint must print the exact same epoch-1 loss
    trajectory (same deterministic sample order, same compiled program) —
    the continuation is indistinguishable from never having been
    interrupted. Behind the conftest collective-capability gate (a jaxlib
    that cannot compile cross-process CPU collectives skips it).

    ``slow`` since PR 32: the test needs survivors that BLOCK in step 4's
    collective until the drain's SIGKILL (a kill by the launcher does not
    count a rank as lost). The jaxlib of the tier-1 sandbox runs
    cross-process CPU collectives over Gloo/TCP, where a dead peer resets
    its connections: ranks 0 and 1 raise ``Gloo all-reduce failed: ...
    Connection reset by peer`` from the metric drain and exit 1 within a
    second, the launcher counts a survivor that fails on its own as lost
    (``launch.py``, the drain loop), three of four are gone, 1 < --min-ranks
    3, and nothing reforms (exit 41). It failed so in every tier-1 run from
    PR 21 to PR 31. Whether a survivor that dies OF the peer's loss should
    count as lost is the launcher's policy and a debt of its own
    (ROADMAP.md, Reach); run it where collectives block: ``-m slow``."""
    import shutil
    flags = list(_TRAINER_FLAGS) + ["--distributed"]
    out = tmp_path / "elastic"
    # rank 3 dies at its epoch-1 boundary (step 4); the survivors are
    # blocked in step 4's collective (the dead rank never joins), so the
    # drain SIGKILLs them at the deadline and the reform resumes from the
    # epoch-0 boundary checkpoint — the documented coarse path.
    r = _launch_elastic(out, mp_timeout(4, compile_cost=3.0), nprocs=4,
                        min_ranks=3, trainer_flags=flags,
                        inject="rank_exit@step=4@rank=3@attempt=0")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "REFORMING gang at world 3" in r.stderr
    reformed = re.findall(r"Epoch\[1\]:\s+\[(\d+)/\d+\].*?Loss ([0-9.e+-]+) ",
                          r.stdout)
    assert reformed, r.stdout[-3000:]

    # Reference: an uninterrupted 3-rank gang resuming the SAME epoch-0
    # checkpoint the reform resumed (the world-4 attempt's keep-K history
    # copy — the live file was since overwritten by the reformed run's
    # final save), restored cross-world 4 -> 3 exactly like the reform.
    ref = tmp_path / "reference"
    os.makedirs(ref)
    src = out / "checkpoint-ep00001.msgpack"
    assert src.exists(), sorted(os.listdir(out))
    shutil.copyfile(src, ref / "checkpoint.msgpack")
    shutil.copyfile(str(src) + ".sha256",
                    ref / "checkpoint.msgpack.sha256")
    r2 = _launch_elastic(ref, mp_timeout(3, compile_cost=3.0), nprocs=3,
                         min_ranks=1, trainer_flags=flags)
    assert r2.returncode == 0, (r2.stdout[-3000:], r2.stderr[-3000:])
    reference = re.findall(
        r"Epoch\[1\]:\s+\[(\d+)/\d+\].*?Loss ([0-9.e+-]+) ", r2.stdout)
    # The reformed gang's epoch-1 trajectory (its final pass) matches the
    # uninterrupted reference step for step, loss for loss.
    n = len(reference)
    assert n and reformed[-n:] == reference, (reformed, reference)
