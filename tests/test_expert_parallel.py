"""Expert parallelism as a Trainer config state: an ('expert',) mesh trains
a MoE ViT (Switch top-1 routing, all_to_all dispatch) end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.config import Config
from tpudist.models.vit_moe import MoEVisionTransformer
from tpudist.parallel import make_ep_train_step
from tpudist.train import create_train_state, sgd_torch


def _models(num_experts=8, capacity_factor=8.0):
    kw = dict(patch_size=4, hidden_dim=32, num_layers=2, num_heads=4,
              mlp_dim=64, num_experts=num_experts, num_classes=8,
              flash=False, capacity_factor=capacity_factor)
    return (MoEVisionTransformer(expert_axis="expert", **kw),
            MoEVisionTransformer(**kw))          # dense twin


def _mesh_ep(devices):
    from tpudist.dist import make_mesh
    return make_mesh((8,), ("expert",), devices)


def _batch(n=16, size=16, nc=8, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((n, size, size, 3)).astype(np.float32)
    labels = rng.integers(0, nc, size=(n,)).astype(np.int32)
    return images, labels


def test_moe_dense_twin_forward(rng):
    _, twin = _models()
    images, _ = _batch(n=2)
    variables = twin.init(rng, jnp.asarray(images), train=False)
    assert "moe" in variables["params"]["encoder_layer_1"]
    assert "moe" not in variables["params"]["encoder_layer_0"]
    assert variables["params"]["encoder_layer_1"]["moe"]["w1"].shape == (
        8, 32, 64)
    out = twin.apply(variables, jnp.asarray(images), train=False)
    assert out.shape == (2, 8)
    assert np.all(np.isfinite(np.asarray(out, np.float32)))


def test_ep_train_step_matches_dense_update(devices):
    """One EP train step == dense-twin full-batch step: the split gradient
    reduction (pmean for replicated, local /n for expert leaves) reconstructs
    the exact global-batch gradient when capacity drops nothing."""
    import optax
    from tpudist.dist import shard_host_batch
    from tpudist.parallel.expert_parallel import _moe_loss_fn

    mesh = _mesh_ep(devices)
    # Capacity high enough that no token is dropped on the spmd path — the
    # dense twin never drops, so parity requires no drops.
    sp_model, twin = _models(capacity_factor=64.0)
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    step = make_ep_train_step(mesh, sp_model, cfg)
    new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))

    # Dense reference with the SAME loss (CE + aux), full batch, one device.
    state_ref = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))

    def loss_fn(p):
        loss, _ = _moe_loss_fn(twin, jax.random.PRNGKey(9), p, {},
                               jnp.asarray(images), jnp.asarray(labels))
        return loss

    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(state_ref.params)
    tx = sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    opt_state = state_ref.opt_state
    opt_state.hyperparams["learning_rate"] = jnp.float32(cfg.lr)
    updates, _ = tx.update(grads_ref, opt_state, state_ref.params)
    params_ref = optax.apply_updates(state_ref.params, updates)

    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(new_state.params),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=str(pa))


def test_ep_metrics_report_pure_ce(devices):
    """metrics['loss'] is pure CE (the Trainer logs it as Train_ce_loss,
    comparable with the dense-twin DP path) even though the optimizer trains
    on CE + aux — the aux term's presence in the TRAINING loss is pinned by
    test_ep_train_step_matches_dense_update, whose reference includes it."""
    from tpudist.dist import shard_host_batch
    from tpudist.ops import cross_entropy_loss

    mesh = _mesh_ep(devices)
    sp_model, twin = _models(capacity_factor=64.0)
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.0).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    # Compute the CE reference BEFORE the step: the step donates its input
    # state, deleting the original param buffers.
    out = twin.apply({"params": state.params}, jnp.asarray(images),
                     train=False)
    ce = float(cross_entropy_loss(out, jnp.asarray(labels)))
    step = make_ep_train_step(mesh, sp_model, cfg)
    _, metrics = step(state, gi, gl, jnp.float32(0.0))
    assert float(metrics["loss"]) == pytest.approx(ce, rel=1e-4)


def test_expert_shardings_after_step(devices):
    """Expert FFN leaves come back sharded over 'expert'; router replicated."""
    from jax.sharding import PartitionSpec as P
    from tpudist.dist import shard_host_batch

    mesh = _mesh_ep(devices)
    sp_model, twin = _models()
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    step = make_ep_train_step(mesh, sp_model, cfg)
    new_state, _ = step(state, gi, gl, jnp.float32(0.01))
    moe = new_state.params["encoder_layer_1"]["moe"]
    assert moe["w1"].sharding.spec == P("expert")
    assert moe["router"].sharding.spec == P()


def test_trainer_rejects_ep_for_non_moe(tmp_path):
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_b_16", num_classes=8, image_size=16, batch_size=16,
                 synthetic=True, epochs=1, outpath=str(tmp_path / "out"),
                 overwrite="delete", mesh_shape=(8,), mesh_axes=["expert"])
    with pytest.raises(ValueError,
                       match="'expert' sets the model's fields expert_axis"):
        Trainer(cfg, writer=None)


def test_trainer_rejects_seq_axis_for_moe(tmp_path):
    """vit_moe_* archs have no seq_axis support — the SP guard must reject
    them with the designed error, not a ctor TypeError."""
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, synthetic=True, epochs=1,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(2, 4), mesh_axes=["data", "seq"])
    with pytest.raises(ValueError, match="'seq' sets the model's fields pool, seq_axis"):
        Trainer(cfg, writer=None)


def test_trainer_rejects_ep_with_unsupported_axis_layout(tmp_path):
    """['data','expert'] composes (r3); anything else still fails fast."""
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, synthetic=True, epochs=1,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(4, 2), mesh_axes=["expert", "data"])
    with pytest.raises(ValueError, match="expert"):
        Trainer(cfg, writer=None)


def _register_tiny_moe():
    from tpudist.models import register_model

    def ctor(num_classes=8, dtype=None, expert_axis=None, num_experts=8,
             capacity_factor=2.0, flash=None, **kw):
        return MoEVisionTransformer(
            patch_size=4, hidden_dim=32, num_layers=2, num_heads=4,
            mlp_dim=64, num_experts=num_experts, num_classes=num_classes,
            dtype=dtype, expert_axis=expert_axis,
            capacity_factor=capacity_factor, flash=flash)
    register_model("vit_moe_tiny_test", ctor)


def test_ep_resume_rejects_mismatched_expert_count(devices, tmp_path):
    """A vit_moe checkpoint from an E-expert mesh must fail a resume on an
    N≠E mesh with the topology reason, not a raw shape mismatch."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist.trainer import Trainer

    _register_tiny_moe()
    # Forge a 4-expert checkpoint (twin init with num_experts=4).
    twin4 = MoEVisionTransformer(patch_size=4, hidden_dim=32, num_layers=2,
                                 num_heads=4, mlp_dim=64, num_experts=4,
                                 num_classes=8, flash=False)
    cfg4 = Config(arch="vit_moe_tiny_test", num_classes=8, image_size=16,
                  batch_size=16, use_amp=False, seed=0).finalize(8)
    state4 = create_train_state(jax.random.PRNGKey(0), twin4, cfg4,
                                input_shape=(1, 16, 16, 3))
    ckpt_lib.save_checkpoint(
        ckpt_lib.state_to_dict(state4, "vit_moe_tiny_test", 0, 0.0),
        False, str(tmp_path))

    cfg = Config(arch="vit_moe_tiny_test", num_classes=8, image_size=16,
                 batch_size=16, synthetic=True, epochs=1, use_amp=False,
                 seed=0, outpath=str(tmp_path / "out"), overwrite="delete",
                 resume=str(tmp_path), mesh_shape=(8,), mesh_axes=["expert"])
    with pytest.raises(ValueError, match="bound to the expert-axis size"):
        Trainer(cfg, writer=None)


@pytest.mark.slow
def test_trainer_ep_path_fits_and_resumes(tmp_path):
    from tpudist.trainer import Trainer

    _register_tiny_moe()
    cfg = Config(arch="vit_moe_tiny_test", num_classes=8, image_size=16,
                 batch_size=16, epochs=1, use_amp=False, seed=0,
                 synthetic=True, print_freq=100,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(8,), mesh_axes=["expert"])
    tr = Trainer(cfg, writer=None)
    assert tr.uses_expert_axis
    best = tr.fit()
    assert np.isfinite(best)

    cfg2 = Config(arch="vit_moe_tiny_test", num_classes=8, image_size=16,
                  batch_size=16, epochs=2, use_amp=False, seed=1,
                  synthetic=True, print_freq=100,
                  outpath=str(tmp_path / "out2"), overwrite="delete",
                  resume=str(tmp_path / "out"),
                  mesh_shape=(8,), mesh_axes=["expert"])
    tr2 = Trainer(cfg2, writer=None)
    assert tr2.start_epoch == 1
    np.testing.assert_array_equal(
        jax.device_get(tr.state.params["head"]["kernel"]),
        jax.device_get(tr2.state.params["head"]["kernel"]))


def test_ep_train_step_updates_ema(devices):
    """--model-ema-decay under expert parallelism: the EMA copy (incl. the
    expert-sharded FFN leaves, which inherit the P('expert') spec through
    path matching) tracks d*e + (1-d)*p."""
    from tpudist.dist import shard_host_batch

    mesh = _mesh_ep(devices)
    sp_model, twin = _models(capacity_factor=64.0)
    d = 0.5
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1,
                 model_ema_decay=d).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    assert state.ema_params is not None
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    step = make_ep_train_step(mesh, sp_model, cfg)

    def leaves(tree):
        return {str(p): np.asarray(jax.device_get(x)) for p, x in
                jax.tree_util.tree_leaves_with_path(tree)}

    p0 = leaves(state.params)
    new_state, _ = step(state, gi, gl, jnp.float32(cfg.lr))
    p1 = leaves(new_state.params)
    e1 = leaves(new_state.ema_params["params"])
    checked = 0
    for k in p1:
        np.testing.assert_allclose(e1[k], d * p0[k] + (1 - d) * p1[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)
        checked += 1
    assert checked > 10


def test_dpep_train_step_matches_dense_update(devices):
    """r3 composition: one dp×ep train step on a ('data','expert')=(2,4)
    mesh == dense-twin full-batch step. Exercises the composed gradient
    reduction (expert leaves: local /n_e + pmean over 'data'; replicated:
    pmean over both axes) and the global-batch aux statistics."""
    import optax
    from tpudist.dist import make_mesh, shard_host_batch
    from tpudist.parallel.expert_parallel import _moe_loss_fn
    from tpudist.train import sgd_torch

    mesh = make_mesh((2, 4), ("data", "expert"), devices)
    kw = dict(patch_size=4, hidden_dim=32, num_layers=2, num_heads=4,
              mlp_dim=64, num_experts=4, num_classes=8, flash=False,
              capacity_factor=64.0)
    sp_model = MoEVisionTransformer(expert_axis="expert",
                                    aux_axes=("data", "expert"), **kw)
    twin = MoEVisionTransformer(**kw)
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), ("data", "expert"))
    step = make_ep_train_step(mesh, sp_model, cfg, data_axis="data")
    new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))

    state_ref = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))

    def loss_fn(p):
        loss, _ = _moe_loss_fn(twin, jax.random.PRNGKey(9), p, {},
                               jnp.asarray(images), jnp.asarray(labels))
        return loss

    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(state_ref.params)
    tx = sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    opt_state = state_ref.opt_state
    opt_state.hyperparams["learning_rate"] = jnp.float32(cfg.lr)
    updates, _ = tx.update(grads_ref, opt_state, state_ref.params)
    params_ref = optax.apply_updates(state_ref.params, updates)

    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(new_state.params),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=str(pa))


def test_dpep_rejects_wrong_mesh(devices):
    from tpudist.dist import make_mesh
    mesh = make_mesh((4, 2), ("expert", "data"), devices)   # wrong order
    sp_model = MoEVisionTransformer(
        patch_size=4, hidden_dim=32, num_layers=2, num_heads=4, mlp_dim=64,
        num_experts=4, num_classes=8, flash=False, expert_axis="expert")
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0).finalize(8)
    with pytest.raises(ValueError, match="mesh"):
        make_ep_train_step(mesh, sp_model, cfg, data_axis="data")


@pytest.mark.slow
def test_trainer_dpep_path_fits(tmp_path):
    """The Trainer accepts --mesh-axes data,expert and trains dp×ep end to
    end (4 experts × 2-way data parallel on 8 devices)."""
    from tpudist.trainer import Trainer

    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, epochs=1, use_amp=False, seed=0,
                 synthetic=True, print_freq=100,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(2, 4), mesh_axes=["data", "expert"])
    tr = Trainer(cfg, writer=None)
    assert tr.uses_expert_axis and tr.batch_axes == ("data", "expert")
    assert tr.model.num_experts == 4
    tr.fit()
    moe = tr.state.params["encoder_layer_1"]["moe"]
    assert moe["w1"].shape[0] == 4      # stacked experts preserved


def test_ep_grad_accumulation_matches_manual_microbatch_accum(devices):
    """accum_steps=2 on the EP path == manually accumulating the dense twin
    over the same two microbatches (VERDICT r3 #6). Unlike the BN/aux-free
    paths, MoE accumulation is NOT equivalent to one full-batch step (the
    Switch aux loss is quadratic in per-microbatch routing fractions), so
    the reference here is per-microbatch accumulation — the torch semantics
    the DP path also implements. Each shard holds 2 images, so global
    microbatch i is the stride-2 slice images[i::2] (shard_host_batch shards
    the batch dim contiguously; the in-step reshape halves each shard)."""
    import optax
    from tpudist.dist import shard_host_batch
    from tpudist.parallel.expert_parallel import _moe_loss_fn

    mesh = _mesh_ep(devices)
    sp_model, twin = _models(capacity_factor=64.0)
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1,
                 accum_steps=2).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    step = make_ep_train_step(mesh, sp_model, cfg)
    new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))

    state_ref = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))
    gsum = jax.tree_util.tree_map(jnp.zeros_like, state_ref.params)
    for i in range(2):
        def loss_fn(p):
            loss, _ = _moe_loss_fn(twin, jax.random.PRNGKey(9), p, {},
                                   jnp.asarray(images[i::2]),
                                   jnp.asarray(labels[i::2]))
            return loss
        g_i = jax.grad(loss_fn)(state_ref.params)
        gsum = jax.tree_util.tree_map(jnp.add, gsum, g_i)
    grads_ref = jax.tree_util.tree_map(lambda g: g / 2, gsum)
    tx = sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    opt_state = state_ref.opt_state
    opt_state.hyperparams["learning_rate"] = jnp.float32(cfg.lr)
    updates, _ = tx.update(grads_ref, opt_state, state_ref.params)
    params_ref = optax.apply_updates(state_ref.params, updates)

    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(new_state.params),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=str(pa))


def test_ep_mixup_runs_and_stays_finite(devices):
    """Mixup/cutmix on the EP path (VERDICT r3 #9): per-shard permutation
    like the DP step; the mixed CE flows through the routed experts and the
    split gradient reduction without NaNs, and params actually move."""
    from tpudist.dist import shard_host_batch

    mesh = _mesh_ep(devices)
    sp_model, twin = _models(capacity_factor=64.0)
    cfg = Config(arch="vit_moe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.05,
                 mixup_alpha=0.4, cutmix_alpha=1.0).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    p0 = jax.device_get(state.params)
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels), "expert")
    step = make_ep_train_step(mesh, sp_model, cfg)
    for _ in range(2):
        state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))
        assert np.isfinite(float(metrics["loss"]))
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p0),
                        jax.tree_util.tree_leaves(
                            jax.device_get(state.params))))
    assert moved
