"""Pipeline parallelism as a Trainer config state: a ('data','pipe') mesh
trains a PipelinedViT with the GPipe microbatch schedule, matching the dense
twin's math exactly (the ppermute/psum transpose derivation in
vit_pipe.py/pipeline_parallel.py is pinned here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpudist.config import Config
from tpudist.models.vit_pipe import PipelinedViT
from tpudist.parallel import make_pp_train_step
from tpudist.train import create_train_state, sgd_torch


def _mesh24(devices):
    from tpudist.dist import make_mesh
    return make_mesh((2, 4), ("data", "pipe"), devices)


def _models(num_microbatches=2):
    kw = dict(patch_size=4, hidden_dim=32, num_layers=4, num_heads=4,
              mlp_dim=64, num_classes=8, flash=False)
    return (PipelinedViT(pipe_axis="pipe",
                         num_microbatches=num_microbatches, **kw),
            PipelinedViT(**kw))                    # dense twin


def _batch(n=16, size=16, nc=8, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal((n, size, size, 3)).astype(np.float32)
    labels = rng.integers(0, nc, size=(n,)).astype(np.int32)
    return images, labels


def test_pp_forward_matches_twin(devices):
    """The full pipelined forward (microbatch schedule, ring hops, psum
    re-replication) equals the plain scanned trunk."""
    mesh = _mesh24(devices)
    pp_model, twin = _models()
    images, _ = _batch()
    variables = twin.init(jax.random.PRNGKey(0), jnp.asarray(images[:1]))
    assert variables["params"]["trunk"]["trunk"]["block"][
        "ln_1"]["scale"].shape[0] == 4          # stacked [L] layer dim

    fwd = jax.jit(jax.shard_map(
        lambda v, x: pp_model.apply(v, x, train=False),
        mesh=mesh,
        in_specs=({"params": jax.tree_util.tree_map_with_path(
            lambda p, _: P("pipe") if "trunk" in [
                str(getattr(k, "key", k)) for k in p] else P(),
            variables["params"])}, P("data")),
        out_specs=P("data"), check_vma=False))
    got = fwd(variables, jnp.asarray(images))
    want = twin.apply(variables, jnp.asarray(images), train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_pp_train_step_matches_twin_update(devices):
    """One PP train step == one full-batch step of the twin: the split
    gradient layout (trunk local-exact after the loss/S seed, embed/head
    psum over 'pipe', everything pmean over 'data') reconstructs the exact
    global-batch gradient."""
    import optax
    from tpudist.dist import shard_host_batch
    from tpudist.ops import cross_entropy_loss

    mesh = _mesh24(devices)
    pp_model, twin = _models()
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels))
    step = make_pp_train_step(mesh, pp_model, cfg)
    new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))

    state_ref = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))

    def loss_fn(p):
        out = twin.apply({"params": p}, jnp.asarray(images), train=True)
        return cross_entropy_loss(out, jnp.asarray(labels))

    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(state_ref.params)
    tx = sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    opt_state = state_ref.opt_state
    opt_state.hyperparams["learning_rate"] = jnp.float32(cfg.lr)
    updates, _ = tx.update(grads_ref, opt_state, state_ref.params)
    params_ref = optax.apply_updates(state_ref.params, updates)

    assert float(metrics["loss"]) == pytest.approx(float(loss_ref), rel=1e-4)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(new_state.params),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=str(pa))


def test_pp_trunk_stays_sharded_after_step(devices):
    from tpudist.dist import shard_host_batch

    mesh = _mesh24(devices)
    pp_model, twin = _models()
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels))
    step = make_pp_train_step(mesh, pp_model, cfg)
    new_state, _ = step(state, gi, gl, jnp.float32(0.01))
    trunk_leaf = new_state.params["trunk"]["trunk"]["block"]["ln_1"]["scale"]
    assert trunk_leaf.sharding.spec == P("pipe")
    assert new_state.params["head"]["kernel"].sharding.spec == P()


def test_pp_step_rejects_indivisible_layers(devices):
    mesh = _mesh24(devices)
    model = PipelinedViT(patch_size=4, hidden_dim=32, num_layers=5,
                         num_heads=4, mlp_dim=64, num_classes=8,
                         flash=False, pipe_axis="pipe")
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0).finalize(8)
    with pytest.raises(ValueError, match="divisible by the pipe-axis"):
        make_pp_train_step(mesh, model, cfg)


def test_pp_step_rejects_indivisible_microbatches(devices):
    mesh = _mesh24(devices)
    model = PipelinedViT(patch_size=4, hidden_dim=32, num_layers=4,
                         num_heads=4, mlp_dim=64, num_classes=8,
                         flash=False, pipe_axis="pipe", num_microbatches=3)
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0).finalize(8)
    with pytest.raises(ValueError, match="num_microbatches"):
        make_pp_train_step(mesh, model, cfg)


def test_trainer_rejects_seq_axis_for_pipe_arch(tmp_path):
    """vit_pipe_* archs have no seq_axis support — the SP guard must reject
    them with the designed error, not a ctor TypeError."""
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, synthetic=True, epochs=1,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(2, 4), mesh_axes=["data", "seq"])
    with pytest.raises(ValueError, match="'seq' sets the model's fields pool, seq_axis"):
        Trainer(cfg, writer=None)


def test_trainer_rejects_pp_for_non_pipe_arch(tmp_path):
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_b_16", num_classes=8, image_size=16, batch_size=16,
                 synthetic=True, epochs=1, outpath=str(tmp_path / "out"),
                 overwrite="delete", mesh_shape=(2, 4),
                 mesh_axes=["data", "pipe"])
    with pytest.raises(ValueError,
                       match="'pipe' sets the model's fields .*pipe_axis"):
        Trainer(cfg, writer=None)


def test_trainer_rejects_pipe_only_mesh(tmp_path):
    from tpudist.trainer import Trainer
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, synthetic=True, epochs=1,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(8,), mesh_axes=["pipe"])
    with pytest.raises(ValueError, match="batch axis"):
        Trainer(cfg, writer=None)


def _register_tiny_pipe():
    from tpudist.models import register_model

    def ctor(num_classes=8, dtype=None, pipe_axis=None, num_microbatches=0,
             flash=None, **kw):
        return PipelinedViT(patch_size=4, hidden_dim=32, num_layers=4,
                            num_heads=4, mlp_dim=64, num_classes=num_classes,
                            dtype=dtype, pipe_axis=pipe_axis,
                            num_microbatches=num_microbatches, flash=flash)
    register_model("vit_pipe_tiny_test", ctor)


@pytest.mark.slow
def test_trainer_pp_path_fits_and_resumes(tmp_path):
    from tpudist.trainer import Trainer

    _register_tiny_pipe()
    cfg = Config(arch="vit_pipe_tiny_test", num_classes=8, image_size=16,
                 batch_size=16, epochs=1, use_amp=False, seed=0,
                 synthetic=True, print_freq=100,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(2, 4), mesh_axes=["data", "pipe"])
    tr = Trainer(cfg, writer=None)
    assert tr.uses_pipe_axis
    best = tr.fit()
    assert np.isfinite(best)

    cfg2 = Config(arch="vit_pipe_tiny_test", num_classes=8, image_size=16,
                  batch_size=16, epochs=2, use_amp=False, seed=1,
                  synthetic=True, print_freq=100,
                  outpath=str(tmp_path / "out2"), overwrite="delete",
                  resume=str(tmp_path / "out"),
                  mesh_shape=(2, 4), mesh_axes=["data", "pipe"])
    tr2 = Trainer(cfg2, writer=None)
    assert tr2.start_epoch == 1
    np.testing.assert_array_equal(
        jax.device_get(tr.state.params["head"]["kernel"]),
        jax.device_get(tr2.state.params["head"]["kernel"]))


def test_pp_train_step_updates_ema(devices):
    """--model-ema-decay under pipeline parallelism: the EMA copy — incl. the
    pipe-sharded trunk leaves, which inherit P('pipe') via path matching —
    tracks d*e + (1-d)*p."""
    from tpudist.dist import shard_host_batch

    mesh = _mesh24(devices)
    pp_model, twin = _models()
    d = 0.5
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1,
                 model_ema_decay=d).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels))
    step = make_pp_train_step(mesh, pp_model, cfg)

    def leaves(tree):
        return {str(p): np.asarray(jax.device_get(x)) for p, x in
                jax.tree_util.tree_leaves_with_path(tree)}

    p0 = leaves(state.params)
    new_state, _ = step(state, gi, gl, jnp.float32(cfg.lr))
    p1 = leaves(new_state.params)
    e1 = leaves(new_state.ema_params["params"])
    for k in p1:
        np.testing.assert_allclose(e1[k], d * p0[k] + (1 - d) * p1[k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_ppxtp_train_step_matches_twin_update(devices):
    """r3 three-axis composition: one data×pipe×model (2×2×2) train step ==
    one full-batch step of the dense twin. Pins the Megatron-in-shard_map
    gradient convention (the f-operator psums partial activation cotangents
    so replicated leaves stay exact; TP kernels are local-exact) composed
    with the pipeline's loss/S seed + pipe-psum + data-pmean."""
    import optax
    from tpudist.dist import make_mesh, shard_host_batch
    from tpudist.ops import cross_entropy_loss

    mesh = make_mesh((2, 2, 2), ("data", "pipe", "model"), devices)
    kw = dict(patch_size=4, hidden_dim=32, num_layers=4, num_heads=4,
              mlp_dim=64, num_classes=8, flash=False)
    pp_model = PipelinedViT(pipe_axis="pipe", model_axis="model",
                            num_microbatches=2, **kw)
    twin = PipelinedViT(**kw)
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.1).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels))
    step = make_pp_train_step(mesh, pp_model, cfg, model_axis="model")
    new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))

    state_ref = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))

    def loss_fn(p):
        out = twin.apply({"params": p}, jnp.asarray(images), train=True)
        return cross_entropy_loss(out, jnp.asarray(labels))

    loss_ref, grads_ref = jax.value_and_grad(loss_fn)(state_ref.params)
    tx = sgd_torch(cfg.lr, cfg.momentum, cfg.weight_decay)
    opt_state = state_ref.opt_state
    opt_state.hyperparams["learning_rate"] = jnp.float32(cfg.lr)
    updates, _ = tx.update(grads_ref, opt_state, state_ref.params)
    params_ref = optax.apply_updates(state_ref.params, updates)

    assert float(metrics["loss"]) == pytest.approx(float(loss_ref), rel=1e-4)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(new_state.params),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(b), rtol=2e-3, atol=2e-5,
                                   err_msg=str(pa))
    # TP dims actually sharded: trunk in_proj kernel [L, D, 3D]
    k = new_state.params["trunk"]["trunk"]["block"]["self_attention"][
        "in_proj"]["kernel"]
    assert k.sharding.spec == P("pipe", None, "model")


@pytest.mark.slow
def test_trainer_ppxtp_path_fits(tmp_path):
    """--mesh-axes data,pipe,model trains the pipelined ViT with Megatron TP
    inside each stage, end to end."""
    from tpudist.models import register_model
    from tpudist.trainer import Trainer

    def ctor(num_classes=8, dtype=None, pipe_axis=None, num_microbatches=0,
             model_axis=None, flash=None, **kw):
        return PipelinedViT(patch_size=4, hidden_dim=32, num_layers=4,
                            num_heads=4, mlp_dim=64, num_classes=num_classes,
                            dtype=dtype, pipe_axis=pipe_axis,
                            model_axis=model_axis,
                            num_microbatches=num_microbatches, flash=flash)
    register_model("vit_pipe_tiny3_test", ctor)

    cfg = Config(arch="vit_pipe_tiny3_test", num_classes=8, image_size=16,
                 batch_size=16, epochs=1, use_amp=False, seed=0,
                 synthetic=True, print_freq=100,
                 outpath=str(tmp_path / "out"), overwrite="delete",
                 mesh_shape=(2, 2, 2), mesh_axes=["data", "pipe", "model"])
    tr = Trainer(cfg, writer=None)
    assert tr.uses_pipe_axis and tr.pp_model_axis == "model"
    assert not tr.uses_gspmd_path
    tr.fit()
    k = tr.state.params["trunk"]["trunk"]["block"]["self_attention"][
        "in_proj"]["kernel"]
    assert k.sharding.spec == P("pipe", None, "model")


def test_pp_grad_accumulation_equivalence(devices):
    """accum_steps=2 on the PP path == one full-batch PP step (VERDICT r3
    #6): the pipelined ViT is deterministic and stateless, so the microbatch
    scan's averaged grads match the full batch; the trunk-local/psum/pmean
    reduction commutes with the average. Each accumulation microbatch (8/2=4
    per data shard) still satisfies the pipeline's own num_microbatches=2
    split."""
    from tpudist.dist import shard_host_batch

    mesh = _mesh24(devices)
    pp_model, twin = _models()
    images, labels = _batch()
    results = []
    for accum in (1, 2):
        cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                     batch_size=16, use_amp=False, seed=0, lr=0.1,
                     accum_steps=accum).finalize(8)
        state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                                   input_shape=(1, 16, 16, 3))
        gi, gl = shard_host_batch(mesh, (images, labels))
        step = make_pp_train_step(mesh, pp_model, cfg)
        new_state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))
        results.append((jax.device_get(new_state.params),
                        float(metrics["loss"])))
    (p1, l1), (p2, l2) = results
    assert l1 == pytest.approx(l2, rel=1e-4)
    for (pa, a), (pb, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(p1),
                   key=lambda kv: str(kv[0])),
            sorted(jax.tree_util.tree_leaves_with_path(p2),
                   key=lambda kv: str(kv[0]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-5, err_msg=str(pa))


def test_pp_accum_rejects_indivisible_microbatch(devices):
    """local batch must divide num_microbatches x accum_steps — the guard
    message names both factors."""
    mesh = _mesh24(devices)
    pp_model, _ = _models()
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0,
                 accum_steps=3).finalize(8)
    with pytest.raises(ValueError, match="accum_steps=3"):
        make_pp_train_step(mesh, pp_model, cfg)


def test_pp_mixup_runs_and_stays_finite(devices):
    """Mixup/cutmix on the PP path (VERDICT r3 #9): the mixing draw folds
    (step, data shard) but NOT the pipe index — images replicate over
    'pipe', so every stage mixes identically; the mixed CE rides the
    loss/S + psum transpose. Composes with accumulation."""
    from tpudist.dist import shard_host_batch

    mesh = _mesh24(devices)
    pp_model, twin = _models()
    cfg = Config(arch="vit_pipe_s_16", num_classes=8, image_size=16,
                 batch_size=16, use_amp=False, seed=0, lr=0.05,
                 mixup_alpha=0.4, cutmix_alpha=1.0,
                 accum_steps=2).finalize(8)
    state = create_train_state(jax.random.PRNGKey(0), twin, cfg,
                               input_shape=(1, 16, 16, 3))
    p0 = jax.device_get(state.params)
    images, labels = _batch()
    gi, gl = shard_host_batch(mesh, (images, labels))
    step = make_pp_train_step(mesh, pp_model, cfg)
    for _ in range(2):
        state, metrics = step(state, gi, gl, jnp.float32(cfg.lr))
        assert np.isfinite(float(metrics["loss"]))
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(p0),
                        jax.tree_util.tree_leaves(
                            jax.device_get(state.params))))
    assert moved
