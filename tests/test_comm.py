"""Communication-efficient scale-out tests (PR 11): int8 gradient
compression with exact error feedback, ZeRO-full weight-update sharding,
the comm dispatch client's honesty properties, the collective-census byte
gates, and the elastic round trips of the new state.

Everything runs on the 8-device virtual CPU mesh (conftest). The census
assertions are the CPU-sim stand-in for the acceptance criterion: the
byte counts are properties of the compiled HLO, identical in kind to what
a TPU program would show.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tpudist.config import Config
from tpudist.dist import make_mesh, shard_host_batch
from tpudist.obs.xla_introspect import hlo_op_census
from tpudist.parallel import comm
from tpudist.parallel.tensor_parallel import shard_tree
from tpudist.train import (create_train_state, make_eval_step,
                           make_train_step)

pytestmark = pytest.mark.comm

W = 4


class TinyNet:
    """A 4-layer conv/BN/dense net, small enough that every step here
    compiles in seconds (tier-1 budget) yet exercises everything the comm
    paths touch: BN running stats (pmean'd, stays dense), a conv kernel
    whose LARGEST divisible dim is not the leading one (the zero-full cut
    rule), and leaves no dim of which divides the world (replicated
    fallback)."""

    def __new__(cls):
        from flax import linen as nn

        class _Net(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = True):
                x = nn.Conv(16, (3, 3), name="conv1")(x)
                x = nn.BatchNorm(use_running_average=not train,
                                 name="bn")(x)
                x = nn.relu(x)
                x = nn.Conv(12, (3, 3), name="conv2")(x)   # 12 % 4 == 0
                x = jnp.mean(x, axis=(1, 2))
                x = nn.Dense(9, name="odd")(x)             # 9: replicated
                return nn.Dense(8, name="head")(x)

        return _Net()


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((W,), ("data",), jax.devices()[:W])


def _small_cfg(**kw):
    base = dict(arch="resnet18", num_classes=8, image_size=16,
                batch_size=2 * W, use_amp=False, seed=0, lr=0.01)
    base.update(kw)
    return Config(**base).finalize(W)


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal(
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3)).astype(
            np.float32)
    labels = rng.integers(0, cfg.num_classes,
                          size=(cfg.batch_size,)).astype(np.int32)
    return images, labels


def _fresh_state(cfg, model):
    return create_train_state(
        jax.random.PRNGKey(0), model, cfg,
        input_shape=(1, cfg.image_size, cfg.image_size, 3))


# -- quantization primitives -------------------------------------------------

def test_quantize_roundtrip_properties():
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.standard_normal((4, 512)).astype(np.float32)) * 10
    q, s = comm.quantize_chunks(c, chunk=256)
    assert q.dtype == jnp.int8 and q.shape == (4, 2, 256)
    assert s.shape == (4, 2)
    back = comm.dequantize_chunks(q, s)
    # symmetric round-to-nearest: error bounded by half a quantization step
    err = np.abs(np.asarray(back) - np.asarray(c))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    assert (err <= np.broadcast_to(bound, (4, 2, 256)).reshape(4, 512)).all()
    # an all-zero chunk decodes to exact zeros (scale 0 guarded)
    z = jnp.zeros((256,), jnp.float32)
    qz, sz = comm.quantize_chunks(z, chunk=256)
    assert float(jnp.abs(comm.dequantize_chunks(qz, sz)).max()) == 0.0


# -- compressed pmean: correctness + the exact-EF invariant ------------------

def test_compressed_pmean_matches_dense_with_exact_error_feedback(mesh):
    """reduced ≈ pmean(g+e) within one quantization step, identical on
    every rank, and the EF invariant holds to float associativity:
    pmean(g + e) == applied + pmean(e') — every bit of quantization error
    is in somebody's residual."""
    n = 1000                    # deliberately NOT a chunk/world multiple
    rng = np.random.default_rng(0)
    g = rng.standard_normal((W, n)).astype(np.float32)
    e0 = rng.standard_normal((W, n)).astype(np.float32) * 0.01

    def step(gv, ev):
        red, e_new = comm.compressed_pmean_flat(gv[0], ev[0], "data")
        return red[None], e_new[None]

    from jax import shard_map
    fn = jax.jit(shard_map(step, mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           check_vma=False))
    sh = NamedSharding(mesh, P("data"))
    red, enew = fn(jax.device_put(jnp.asarray(g), sh),
                   jax.device_put(jnp.asarray(e0), sh))
    red, enew = np.asarray(red), np.asarray(enew)
    assert (red == red[0:1]).all(), "reduced differs across ranks"
    true_mean = (g + e0).mean(axis=0)
    # quantization error bounded (~1% relative at int8 + EF headroom)
    assert np.abs(red[0] - true_mean).max() \
        <= 0.05 * np.abs(true_mean).max() + 1e-4
    # THE invariant: applied + mean residual reconstructs the true mean
    recon = red[0] + enew.mean(axis=0)
    assert np.abs(recon - true_mean).max() < 1e-5


def test_compressed_pmean_tree_roundtrip(mesh):
    """Tree flatten/unflatten preserves shapes and dtypes and matches the
    flat reduce on the concatenated vector."""
    rng = np.random.default_rng(1)
    tree = {"a": jnp.asarray(rng.standard_normal((3, 5)).astype(np.float32)),
            "b": {"c": jnp.asarray(
                rng.standard_normal((7,)).astype(np.float32))}}
    n = comm.grad_size(tree)
    assert n == 22
    res = jnp.zeros((n,), jnp.float32)

    def one(tr, e):
        red, e2 = comm.compressed_pmean(tr, e[0], "data")
        return red, e2[None]

    from jax import shard_map
    specs = jax.tree_util.tree_map(lambda _: P(), tree)
    fn = jax.jit(shard_map(
        one, mesh=mesh, in_specs=(specs, P("data")),
        out_specs=(specs, P("data")), check_vma=False))
    red, _ = fn(tree, jnp.tile(res, (W, 1)))
    assert jax.tree_util.tree_structure(red) \
        == jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(red),
                    jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        # identical inputs on every rank => mean == input, up to quant err
        assert float(jnp.abs(a - b).max()) \
            <= 0.02 * float(jnp.abs(b).max()) + 1e-6


# -- dense-twin parity + bit-exact off path ----------------------------------

def _run_steps(step, state, batches, lr):
    losses = []
    for im, lb in batches:
        state, m = step(state, im, lb, lr)
        losses.append(float(m["loss"]))
    return state, losses


def _parity_setup(mesh, cfg):
    model = TinyNet()
    batches = []
    for s in range(5):
        im, lb = _batch(cfg, seed=s)
        batches.append(shard_host_batch(mesh, (im, lb)))
    return model, batches


@pytest.mark.parametrize("amp,tol", [(False, 5e-3), (True, 3e-2)],
                         ids=["f32", "bf16"])
def test_dense_twin_loss_parity(mesh, amp, tol):
    """--compress-grads int8 loss trajectory tracks the dense twin over a
    multi-step run: f32 tight, bf16 loose (bf16's own rounding rides on
    top of the quantization error)."""
    cfg = _small_cfg(use_amp=amp)
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    dstate, dlosses = _run_steps(make_train_step(mesh, model, cfg),
                                 _fresh_state(cfg, model), batches, lr)
    cstate0 = _fresh_state(cfg, model)
    cstate0 = cstate0.replace(
        comm_state=comm.init_comm_state(cstate0.params, W))
    cstate, closses = _run_steps(
        make_train_step(mesh, model, cfg, compress="int8"),
        cstate0, batches, lr)
    assert cstate.comm_state["residual"].shape == (W, comm.grad_size(
        dstate.params))
    for d, c in zip(dlosses, closses):
        assert abs(d - c) <= tol * max(1.0, abs(d)), (dlosses, closses)


def test_off_path_bit_exact_and_structurally_dense(mesh):
    """compress=None is the pre-PR dense step bit-for-bit: deterministic
    across two independent builds, and its compiled program contains the
    gradient all-reduce and NO compression collectives."""
    cfg = _small_cfg()
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    s1, l1 = _run_steps(make_train_step(mesh, model, cfg),
                        _fresh_state(cfg, model), batches[:3], lr)
    s2, l2 = _run_steps(make_train_step(mesh, model, cfg, compress=None),
                        _fresh_state(cfg, model), batches[:3], lr)
    assert l1 == l2
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s2.comm_state is None
    step = make_train_step(mesh, model, cfg)
    state = _fresh_state(cfg, model)
    im, lb = batches[0]
    census = hlo_op_census(
        step.lower(state, im, lb, lr).compile().as_text())["collectives"]
    assert "all-reduce" in census
    assert "all-to-all" not in census and "all-gather" not in census


def test_compress_requires_comm_state(mesh):
    cfg = _small_cfg()
    model, batches = _parity_setup(mesh, cfg)
    step = make_train_step(mesh, model, cfg, compress="int8")
    with pytest.raises(ValueError, match="comm_state"):
        step(_fresh_state(cfg, model), *batches[0], jnp.float32(0.01))
    with pytest.raises(ValueError, match="int8"):
        make_train_step(mesh, model, cfg, compress="int4")


# -- the acceptance meter: census bytes --------------------------------------

def test_census_collective_bytes_drop(mesh):
    """The ISSUE acceptance criterion, CPU-sim form: under int8 the
    gradient all-reduce VANISHES from the census (>=10x fewer all-reduce
    bytes — only metric/BN pmeans remain) and the estimated link traffic
    drops >=3x; the raw payload metric halves (two int8 phases vs one f32
    all-reduce — the honest number, documented in COMMUNICATION.md)."""
    cfg = _small_cfg()
    model, batches = _parity_setup(mesh, cfg)
    im, lb = batches[0]
    lr = jnp.float32(cfg.lr)

    def census_of(step, state):
        c = hlo_op_census(step.lower(state, im, lb, lr).compile().as_text())
        return {
            "payload": sum(v["bytes"] for v in c["collectives"].values()),
            "link": sum(c["link_bytes"].values()),
            "ar": c["collectives"].get("all-reduce", {"bytes": 0})["bytes"],
        }

    dense = census_of(make_train_step(mesh, model, cfg),
                      _fresh_state(cfg, model))
    cstate = _fresh_state(cfg, model)
    cstate = cstate.replace(
        comm_state=comm.init_comm_state(cstate.params, W))
    compd = census_of(make_train_step(mesh, model, cfg, compress="int8"),
                      cstate)
    grad_bytes = 4 * comm.grad_size(cstate.params)
    assert dense["ar"] >= grad_bytes          # dense all-reduces the grads
    assert compd["ar"] * 10 <= dense["ar"], (dense, compd)
    assert compd["link"] * 3 <= dense["link"], (dense, compd)
    assert compd["payload"] * 1.5 <= dense["payload"], (dense, compd)


def test_link_bytes_estimation_from_hlo():
    """Group-size parsing (literal + iota forms) and the per-op ring-cost
    factors behind collective_link_bytes."""
    hlo = """
ENTRY %main {
  %p = f32[1024]{0} parameter(0)
  %ar = f32[1024]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%sum
  %rs = f32[256]{0} reduce-scatter(%p), replica_groups=[2,4]<=[8], dimensions={0}, to_apply=%sum
  %ag = s8[1024]{0} all-gather(%q), replica_groups={{0,1},{2,3}}, dimensions={0}
  %cp = f32[64]{0} collective-permute(%x), source_target_pairs={{0,1}}
}
"""
    c = hlo_op_census(hlo)
    lb = c["link_bytes"]
    assert lb["all-reduce"] == int(4096 * 2 * 3 / 4)       # 2(g-1)/g, g=4
    assert lb["reduce-scatter"] == 1024 * 3                # (g-1)x out, g=4
    assert lb["all-gather"] == int(1024 * 1 / 2)           # (g-1)/g, g=2
    assert lb["collective-permute"] == 256                 # payload


# -- ZeRO-full ---------------------------------------------------------------

def test_wus_step_parity_memory_and_census(mesh):
    """--zero full: loss/params bit-close to plain DP, per-device state
    shrinks by ~W on the divisible leaves, grads exchange as
    reduce-scatter + all-gather (no gradient all-reduce), eval step
    matches the dense eval."""
    cfg = _small_cfg(zero="full")
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    dstate, dlosses = _run_steps(make_train_step(mesh, model, cfg),
                                 _fresh_state(cfg, model), batches[:3], lr)
    wstate0 = shard_tree(mesh, _fresh_state(cfg, model), (),
                         opt_shard_axis="data", zero_mode="full")
    wstep = comm.make_wus_train_step(mesh, model, cfg)
    wstate, wlosses = _run_steps(wstep, wstate0, batches[:3], lr)
    for d, w in zip(dlosses, wlosses):
        assert abs(d - w) <= 1e-5 * max(1.0, abs(d)), (dlosses, wlosses)
    for a, b in zip(jax.tree_util.tree_leaves(dstate.params),
                    jax.tree_util.tree_leaves(wstate.params)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    def dev_bytes(tree):
        tot = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "addressable_shards"):
                sh = leaf.addressable_shards[0]
                tot += int(np.prod(sh.data.shape)) * leaf.dtype.itemsize
            elif hasattr(leaf, "nbytes"):
                tot += int(leaf.nbytes)
        return tot

    full_b = dev_bytes({"p": dstate.params, "o": dstate.opt_state})
    wus_b = dev_bytes({"p": wstate.params, "o": wstate.opt_state})
    assert wus_b < full_b / 2, (wus_b, full_b)
    # the acceptance comparison: strictly below the ZERO1 placement too
    # (zero1 shards only leading-dim-divisible moment buffers; full cuts
    # params + moments on their largest divisible dim)
    z1state = shard_tree(mesh, _fresh_state(cfg, model), (),
                         opt_shard_axis="data")
    z1_b = dev_bytes({"p": z1state.params, "o": z1state.opt_state})
    assert wus_b < z1_b, (wus_b, z1_b)

    im, lb = batches[0]
    census = hlo_op_census(wstep.lower(
        wstate, im, lb, lr).compile().as_text())["collectives"]
    grad_bytes = 4 * comm.grad_size(dstate.params)
    assert census.get("all-reduce", {"bytes": 0})["bytes"] < grad_bytes / 10
    assert "reduce-scatter" in census and "all-gather" in census

    em = comm.make_wus_eval_step(mesh, model, cfg)(wstate, im, lb)
    dm = make_eval_step(mesh, model, cfg)(dstate, im, lb)
    assert abs(float(em["loss"]) - float(dm["loss"])) \
        <= 1e-4 * max(1.0, abs(float(dm["loss"])))


def test_wus_compress_composes(mesh):
    """--zero full + --compress-grads int8: the composition trains, the
    state stays sharded, the residual updates, and the loss tracks the
    plain-DP+int8 twin exactly (same exchange, same math)."""
    cfg = _small_cfg(zero="full", compress_grads="int8")
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    c0 = _fresh_state(cfg, model)
    c0 = c0.replace(comm_state=comm.init_comm_state(c0.params, W))
    _, dp_losses = _run_steps(
        make_train_step(mesh, model, cfg, compress="int8"), c0,
        batches[:3], lr)
    w0 = _fresh_state(cfg, model)
    w0 = shard_tree(mesh, w0.replace(
        comm_state=comm.init_comm_state(w0.params, W)), (),
        opt_shard_axis="data", zero_mode="full")
    wstate, w_losses = _run_steps(
        comm.make_wus_train_step(mesh, model, cfg, compress="int8"), w0,
        batches[:3], lr)
    for d, w in zip(dp_losses, w_losses):
        assert abs(d - w) <= 1e-4 * max(1.0, abs(d)), (dp_losses, w_losses)
    assert float(jnp.abs(wstate.comm_state["residual"]).max()) > 0


def test_wus_ema_composes(mesh):
    """--zero full with --model-ema-decay: the EMA's PARAM half shards
    like params, its BUFFER half stays replicated (it averages against
    the replicated batch_stats — a sharded EMA-stats leaf would
    shape-mismatch the update), and both eval paths agree with the dense
    twin."""
    cfg = _small_cfg(zero="full", model_ema_decay=0.9)
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    dstate, _ = _run_steps(make_train_step(mesh, model, cfg),
                           _fresh_state(cfg, model), batches[:2], lr)
    wstate0 = shard_tree(mesh, _fresh_state(cfg, model), (),
                         opt_shard_axis="data", zero_mode="full")
    # buffer half replicated, param half sharded (where divisible)
    assert all(
        len(getattr(leaf, "sharding").spec) == 0
        for leaf in jax.tree_util.tree_leaves(
            wstate0.ema_params["batch_stats"]))
    assert any(
        "data" in tuple(getattr(leaf, "sharding").spec)
        for leaf in jax.tree_util.tree_leaves(wstate0.ema_params["params"]))
    wstate, _ = _run_steps(comm.make_wus_train_step(mesh, model, cfg),
                           wstate0, batches[:2], lr)
    for a, b in zip(jax.tree_util.tree_leaves(dstate.ema_params),
                    jax.tree_util.tree_leaves(wstate.ema_params)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_wus_rejects_fp16_and_tiny_axis(mesh):
    cfg = _small_cfg()
    cfg.use_amp, cfg.amp_dtype = True, "float16"
    model = TinyNet()
    with pytest.raises(ValueError, match="fp16|float16"):
        comm.make_wus_train_step(mesh, model, cfg)
    one = make_mesh((1,), ("data",), jax.devices()[:1])
    cfg2 = _small_cfg()
    with pytest.raises(ValueError, match="nothing to shard"):
        comm.make_wus_train_step(one, model, cfg2)


# -- elastic round trips -----------------------------------------------------

def test_wus_save_merge_restore_roundtrip(mesh, tmp_path):
    """The --zero full e2e acceptance: train at W=4 sharded, checkpoint
    (full host tree), restore at W=2 — params/opt bit-identical after the
    merge implied by saving, partitions re-cut, training continues."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist.elastic.reshard import topology_tag

    cfg = _small_cfg(zero="full")
    model, batches = _parity_setup(mesh, cfg)
    lr = jnp.float32(cfg.lr)
    w0 = shard_tree(mesh, _fresh_state(cfg, model), (),
                    opt_shard_axis="data", zero_mode="full")
    wstate, _ = _run_steps(comm.make_wus_train_step(mesh, model, cfg), w0,
                           batches[:2], lr)

    def tag(world, mesh_shape):
        return topology_tag(world=1, mesh_shape=mesh_shape,
                            mesh_axes=["data"], n_devices=mesh_shape[0],
                            per_device_batch=cfg.per_device_batch_size,
                            global_batch=cfg.batch_size, zero="full",
                            zero1_axis="data")

    # round-trip through real checkpoint bytes (save gathers the sharded
    # leaves to full host arrays via _to_host)
    sd = ckpt_lib.state_to_dict(wstate, cfg.arch, 0, 0.0,
                                topology=tag(1, [W]))
    path = ckpt_lib.save_checkpoint(sd, False, str(tmp_path), keep=0)
    sd = ckpt_lib.load_checkpoint(path)
    for _p, leaf in _walk_arrays(sd["state"]["params"]):
        assert isinstance(leaf, np.ndarray)
    mesh2 = make_mesh((2,), ("data",), jax.devices()[:2])
    cfg2 = _small_cfg(zero="full", batch_size=4)
    template = _fresh_state(cfg2, model)
    restored = ckpt_lib.restore_train_state(template, sd,
                                            target_topology=tag(1, [2]))
    # bit-identical after merge: restored full tree == the trained state
    for a, b in zip(jax.tree_util.tree_leaves(restored.params),
                    jax.tree_util.tree_leaves(wstate.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(restored.opt_state),
                    jax.tree_util.tree_leaves(wstate.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    r2 = shard_tree(mesh2, restored, (), opt_shard_axis="data",
                    zero_mode="full")
    step2 = comm.make_wus_train_step(mesh2, model, cfg2)
    im, lb = shard_host_batch(mesh2, _batch(cfg2, seed=9))
    out, m = step2(r2, im, lb, lr)
    assert np.isfinite(float(m["loss"]))


def _walk_arrays(tree, path=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk_arrays(v, path + (k,))
    else:
        yield path, tree


def test_cut_merge_state_full_mode_roundtrip():
    """merge(cut(T, W)) == T bit-for-bit at W ∈ {1, 2, 4} for the
    full-mode layout (largest-divisible-dim cuts), and re-cutting the
    merged tree at W2 equals cutting the original at W2."""
    from tpudist.elastic import reshard
    rng = np.random.default_rng(0)
    tree = {"params": {"conv": rng.standard_normal((3, 3, 8, 16)).astype(
                np.float32),
                       "scale": rng.standard_normal((12,)).astype(
                np.float32),
                       "odd": rng.standard_normal((5, 7)).astype(
                np.float32)},
            "opt_state": {"mu": {"conv": rng.standard_normal(
                (3, 3, 8, 16)).astype(np.float32)}},
            "batch_stats": {"mean": rng.standard_normal((12,)).astype(
                np.float32)},
            "step": np.int32(7)}
    for w in (1, 2, 4):
        shards, layout = reshard.cut_state(tree, w, mode="full")
        assert len(shards) == w
        merged = reshard.merge_state(shards, layout)
        for (pa, a), (pb, b) in zip(_walk_arrays(tree),
                                    _walk_arrays(merged)):
            assert pa == pb
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the conv kernel cuts its largest dim (16 at axis 3), not the 3-lead
    _, layout = reshard.cut_state(tree, 4, mode="full")
    assert layout["params/conv"]["axis"] == 3
    assert layout["params/scale"]["axis"] == 0      # 12 % 4 == 0
    assert "params/odd" not in layout               # nothing divides 4
    assert "batch_stats/mean" not in layout         # not a zero-full root
    # re-cut equivalence
    shards4, layout4 = reshard.cut_state(tree, 4, mode="full")
    merged = reshard.merge_state(shards4, layout4)
    re2, l2 = reshard.cut_state(merged, 2, mode="full")
    direct2, dl2 = reshard.cut_state(tree, 2, mode="full")
    assert l2 == dl2
    for s_a, s_b in zip(re2, direct2):
        for (pa, a), (pb, b) in zip(_walk_arrays(s_a), _walk_arrays(s_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remap_comm_state_preserves_mean():
    from tpudist.elastic.reshard import remap_comm_state
    rng = np.random.default_rng(0)
    res = rng.standard_normal((4, 100)).astype(np.float32)
    same = remap_comm_state({"residual": res}, 4)
    np.testing.assert_array_equal(same["residual"], res)    # bit-exact
    for w2 in (1, 2, 8):
        out = remap_comm_state({"residual": res}, w2)
        assert out["residual"].shape == (w2, 100)
        np.testing.assert_allclose(out["residual"].mean(axis=0),
                                   res.mean(axis=0), rtol=1e-6)
    assert remap_comm_state(None, 2) is None


@pytest.mark.parametrize("w_save,w_restore", [(4, 4), (4, 2), (2, 4),
                                              (4, 1), (1, 4)])
def test_ef_residual_checkpoint_roundtrip(tmp_path, w_save, w_restore):
    """The EF residual rides the emergency-checkpoint plane across world
    changes W ∈ {1, 2, 4}: same world bit-exact, cross-world
    mean-preserving, and a pre-compression checkpoint seeds zeros."""
    from tpudist import checkpoint as ckpt_lib

    cfg = _small_cfg()
    model = TinyNet()
    st = _fresh_state(cfg, model)
    n = comm.grad_size(st.params)
    rng = np.random.default_rng(3)
    res = rng.standard_normal((w_save, n)).astype(np.float32)
    st = st.replace(comm_state={"residual": jnp.asarray(res)})
    sd = ckpt_lib.state_to_dict(st, cfg.arch, 0, 0.0,
                                data_cursor={"epoch": 0, "consumed": 8,
                                             "samples_skipped": 0,
                                             "samples_retried": 0})
    path = ckpt_lib.save_checkpoint(sd, False, str(tmp_path), keep=0)
    loaded = ckpt_lib.load_checkpoint(path)
    template = _fresh_state(cfg, model).replace(
        comm_state=comm.init_comm_state(st.params, w_restore))
    restored = ckpt_lib.restore_train_state(template, loaded)
    got = np.asarray(restored.comm_state["residual"])
    assert got.shape == (w_restore, n)
    if w_save == w_restore:
        np.testing.assert_array_equal(got, res)
    else:
        np.testing.assert_allclose(got.mean(axis=0), res.mean(axis=0),
                                   rtol=1e-5, atol=1e-7)
    # compression off drops it; newly on seeds zeros
    off = ckpt_lib.restore_train_state(_fresh_state(cfg, model), loaded)
    assert off.comm_state is None
    del loaded["state"]["comm_state"]
    fresh = ckpt_lib.restore_train_state(template, loaded)
    assert float(np.abs(np.asarray(
        fresh.comm_state["residual"])).max()) == 0.0


# -- dispatch client honesty -------------------------------------------------

def test_comm_dispatch_honesty(tmp_path):
    from tpudist.ops import comm_dispatch

    cache = str(tmp_path / "cache")
    # never pick a loser / tie keeps dense
    for int8_ms, dense_ms, want in ((1.0, 2.0, "int8"), (2.0, 1.0, "dense"),
                                    (1.0, 1.0, "dense")):
        dec = comm_dispatch.decide(
            1000, 4, mode="auto", chunk=256, cache_dir=cache,
            platform="tpu", device_kind=f"fake-{int8_ms}-{dense_ms}",
            measure_pair=lambda: (int8_ms, dense_ms))
        assert dec["kernel"] == want, dec
        assert dec["source"] == "measured"
    # cache round trip: second decide never re-measures
    dec = comm_dispatch.decide(
        1000, 4, mode="auto", chunk=256, cache_dir=cache, platform="tpu",
        device_kind="fake-1.0-2.0",
        measure_pair=lambda: (_ for _ in ()).throw(
            AssertionError("re-measured a cached workload")))
    assert dec["kernel"] == "int8" and dec["source"] == "cache"
    # off-TPU auto resolves dense without measuring
    dec = comm_dispatch.decide(
        1000, 4, mode="auto", chunk=256, platform="cpu",
        measure_pair=lambda: (_ for _ in ()).throw(
            AssertionError("auto measured off-TPU")))
    assert dec["kernel"] == "dense" and dec["source"] == "platform"
    # world < 2 is structurally ineligible, even forced
    dec = comm_dispatch.decide(1000, 1, mode="int8", chunk=256,
                               platform="cpu")
    assert dec["kernel"] == "dense" and dec["source"] == "ineligible"
    # forced int8 stays forced (no platform/measure question)
    dec = comm_dispatch.decide(1000, 4, mode="int8", chunk=256,
                               platform="cpu")
    assert dec["kernel"] == "int8" and dec["source"] == "forced"
    with pytest.raises(ValueError, match="compress-grads"):
        comm_dispatch.decide(1000, 4, mode="banana", chunk=256)


def test_comm_dispatch_event_fields_schema_valid():
    from tpudist.ops import comm_dispatch
    from tpudist.telemetry import validate_event

    dec = {"kernel": "int8", "mode": "auto", "source": "measured",
           "int8_ms": 1.25, "dense_ms": 3.5, "margin": 0.64,
           "key": "n100_w4_c256"}
    fields = comm_dispatch.event_fields(dec, world=4, n_grads=100,
                                        dense_bytes=400)
    ev = {"t": 0.0, "type": "comm_dispatch", "rank": 0, "attempt": 0,
          **fields}
    validate_event(ev)
    json.dumps(ev)
    assert ev["dense_bytes"] == 400 and ev["world"] == 4


# -- config validation -------------------------------------------------------

def test_config_mode_interaction_validation():
    with pytest.raises(ValueError, match="--zero must"):
        _small_cfg(zero="2")
    with pytest.raises(ValueError, match="compress-grads must"):
        _small_cfg(compress_grads="fp8")
    with pytest.raises(ValueError, match="evaluate"):
        _small_cfg(compress_grads="int8", evaluate=True)
    with pytest.raises(ValueError, match="float16"):
        _small_cfg(compress_grads="int8", use_amp=True,
                   amp_dtype="float16")
    with pytest.raises(ValueError, match="zero 1"):
        _small_cfg(compress_grads="int8", zero="1")
    with pytest.raises(ValueError, match="model"):
        _small_cfg(compress_grads="int8",
                   mesh_axes=["data", "model"])
    with pytest.raises(ValueError, match="zero full"):
        _small_cfg(zero="full", mesh_axes=["data", "seq"])
    with pytest.raises(ValueError, match="float16"):
        _small_cfg(zero="full", use_amp=True, amp_dtype="float16")
    # the deprecated bool alias folds into the mode
    assert _small_cfg(zero_opt=True).zero == "1"
    assert _small_cfg(compress_grads="int8", zero="full").zero == "full"


# -- regress gate ------------------------------------------------------------

def test_regress_gates_collective_bytes():
    from tpudist.regress import analyze_history

    def row(v, cb):
        return {"metric": "m_int8_w4_ms_tpu", "unit": "ms", "value": v,
                "per_device_batch": None, "collective_bytes_per_step": cb}

    hist = [row(1.0, 1000)] * 4
    ok = analyze_history(hist + [row(1.0, 1000)])
    assert ok["status"] == "pass"
    # bytes rose 50% at equal time: the program re-densified — regression
    bad = analyze_history(hist + [row(1.0, 1500)])
    assert bad["status"] == "regression"
    assert any("collective bytes" in r for r in bad["reasons"])
    # bytes DROPPED (a win) passes
    win = analyze_history(hist + [row(1.0, 400)])
    assert win["status"] == "pass"
    # rows without the field gate exactly as before
    plain = [{"metric": "x", "value": 100.0, "per_device_batch": 8}] * 3
    assert analyze_history(plain)["status"] == "pass"


# -- summarize surfaces ------------------------------------------------------

def test_summarize_compression_ratio_line():
    from tpudist.summarize import analyze, format_report

    base = {"rank": 0, "attempt": 0}
    events = [
        {"t": 0.0, "type": "run_start", "platform": "cpu", "n_devices": 4,
         "arch": "resnet18", "global_batch": 32, **base},
        {"t": 0.5, "type": "comm_dispatch", "kernel": "int8",
         "mode": "int8", "source": "forced", "world": 4, "n_grads": 1000,
         "dense_bytes": 4000, **base},
        {"t": 1.0, "type": "compile", "seconds": 2.0,
         "phase": "cost_analysis", "collective_ops": 4,
         "collective_bytes_per_step": 2000, "collective_link_bytes": 1500,
         "bytes_accessed": 1.0, **base},
        {"t": 2.0, "type": "step", "step": 0, "epoch": 0, "data_s": 0.01,
         "h2d_s": 0.01, "compute_s": 0.1, "drain_s": 0.0, "step_s": 0.2,
         **base},
    ]
    a = analyze(events)
    comp = a["compression"]
    assert comp["payload_ratio"] == 2.0
    # dense ring link = 2*(3/4)*4000 = 6000; actual 1500 -> 4x
    assert comp["link_ratio"] == 4.0
    report = format_report(a)
    assert "comm dispatch: int8 gradient exchange" in report
    assert "gradient compression" in report
    assert "4.00x" in report


# -- trainer e2e -------------------------------------------------------------

@pytest.mark.slow
def test_trainer_compress_zero_full_e2e(tmp_path):
    """Trainer-level composition: --compress-grads int8 --zero full with
    telemetry — the comm_dispatch event lands schema-valid, the state is
    sharded + carries the residual, and summarize reports the compression
    ratio."""
    from tpudist.summarize import analyze, load_events
    from tpudist.trainer import Trainer

    out = str(tmp_path / "run")
    cfg = Config(arch="resnet18", num_classes=8, image_size=32,
                 batch_size=16, epochs=1, synthetic=True, synthetic_size=32,
                 workers=0, use_amp=False, seed=0, outpath=out,
                 overwrite="delete", telemetry=True,
                 compress_grads="int8", zero="full", lr=0.01,
                 device_prefetch=False)
    t = Trainer(cfg)
    assert t.compress == "int8" and t.uses_wus_path
    assert t.state.comm_state is not None
    t.fit()
    a = analyze(load_events(out, strict=True))
    cd = a["comm_dispatch"]
    assert cd and cd["kernel"] == "int8" and cd["source"] == "forced"
    assert a["compression"] is not None
    assert a["compression"]["dense_bytes"] == cd["dense_bytes"]


def test_trainer_rejects_single_device_compress(tmp_path):
    from tpudist.trainer import Trainer
    one = make_mesh((1,), ("data",), jax.devices()[:1])
    cfg = Config(arch="resnet18", num_classes=8, image_size=32,
                 batch_size=4, synthetic=True, workers=0, use_amp=False,
                 compress_grads="int8", outpath=str(tmp_path / "run"),
                 overwrite="keep")
    with pytest.raises(ValueError, match="never reduces"):
        Trainer(cfg, mesh=one, writer=None)


def test_trainer_seeds_residual_and_emits_event(tmp_path):
    """Trainer construction (no fit — cheap) under --compress-grads int8:
    the dispatch resolves forced, the residual is seeded at (data-axis,
    n_grads), and the schema-valid comm_dispatch event is written."""
    from tpudist.trainer import Trainer

    out = str(tmp_path / "run")
    cfg = Config(arch="resnet18", num_classes=8, image_size=32,
                 batch_size=2 * W, synthetic=True, workers=0, use_amp=False,
                 seed=0, outpath=out, overwrite="delete", telemetry=True,
                 compress_grads="int8", device_prefetch=False)
    t = Trainer(cfg, mesh=make_mesh((W,), ("data",), jax.devices()[:W]),
                writer=None)
    try:
        assert t.compress == "int8"
        n = comm.grad_size(t.state.params)
        assert t.state.comm_state["residual"].shape == (W, n)
        evs = [json.loads(line)
               for line in open(os.path.join(out, "events.0.jsonl"))]
        cds = [e for e in evs if e["type"] == "comm_dispatch"]
        assert len(cds) == 1
        assert cds[0]["kernel"] == "int8" and cds[0]["source"] == "forced"
        assert cds[0]["dense_bytes"] == 4 * n and cds[0]["world"] == W
    finally:
        if t.telemetry is not None:
            from tpudist import telemetry as telemetry_lib
            t.telemetry.close()
            telemetry_lib.set_current(None)
