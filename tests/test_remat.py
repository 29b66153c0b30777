"""--remat (block-granular jax.checkpoint, config.py:remat).

No reference equivalent (torch's activation checkpointing is not used by the
reference recipes); this is a TPU HBM lever: recompute block activations in
backward instead of holding them across the graph. The contract under test:

1. remat is a pure memory/FLOPs trade — the param tree, loss, and gradients
   are IDENTICAL to the plain model;
2. the checkpoint boundary is actually in the program: the lowered backward
   recomputes the forward's convs/matmuls (op counts rise), rather than the
   flag silently doing nothing;
3. the trainer rejects unsupported archs at startup (ADVICE r2 #4: no
   config error may crash a run an epoch in).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _grads(model, variables, x):
    def loss(p):
        out, _ = model.apply(
            {"params": p, **{k: v for k, v in variables.items()
                             if k != "params"}},
            x, train=True, mutable=["batch_stats"])
        return (out.astype(jnp.float32) ** 2).mean()
    return jax.value_and_grad(loss)(variables["params"])


def test_resnet_remat_identical_math():
    from tpudist.models import create_model
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32, 32, 3), jnp.float32)
    plain = create_model("resnet18", num_classes=8)
    remat = create_model("resnet18", num_classes=8, remat=True)
    v = plain.init(jax.random.PRNGKey(0), x)
    v_r = remat.init(jax.random.PRNGKey(0), x)
    assert (jax.tree_util.tree_structure(v)
            == jax.tree_util.tree_structure(v_r))
    l0, g0 = _grads(plain, v, x)
    l1, g1 = _grads(remat, v, x)
    assert bool(jnp.allclose(l0, l1)), (l0, l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_resnet_remat_recomputes_backward():
    from tpudist.models import create_model
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    counts = {}
    for flag in (False, True):
        m = create_model("resnet18", num_classes=8, remat=flag)
        v = m.init(jax.random.PRNGKey(0), x)
        def loss(p):
            out, _ = m.apply({"params": p,
                              "batch_stats": v["batch_stats"]},
                             x, train=True, mutable=["batch_stats"])
            return (out ** 2).mean()
        txt = jax.jit(jax.grad(loss)).lower(v["params"]).as_text()
        counts[flag] = txt.count("convolution(")
    # resnet18: 19 block convs recomputed inside the checkpointed backward.
    assert counts[True] > counts[False], counts


def test_vit_remat_identical_math():
    from tpudist.models.vit import VisionTransformer
    x = jnp.asarray(np.random.RandomState(1).randn(2, 16, 16, 3), jnp.float32)
    kw = dict(patch_size=8, hidden_dim=32, num_layers=2, num_heads=4,
              mlp_dim=64, num_classes=8)
    plain = VisionTransformer(**kw)
    remat = VisionTransformer(**kw, remat=True)
    v = plain.init(jax.random.PRNGKey(0), x)
    assert (jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(
        remat.init(jax.random.PRNGKey(0), x)))

    def loss(mdl, p):
        return (mdl.apply({"params": p}, x).astype(jnp.float32) ** 2).mean()

    l0, g0 = jax.value_and_grad(lambda p: loss(plain, p))(v["params"])
    l1, g1 = jax.value_and_grad(lambda p: loss(remat, p))(v["params"])
    assert bool(jnp.allclose(l0, l1)), (l0, l1)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_remat_rejects_unsupported_arch(tmp_path):
    from tpudist.config import Config
    from tpudist.trainer import Trainer
    cfg = Config(arch="alexnet", num_classes=4, image_size=32, batch_size=16,
                 use_amp=False, seed=0, synthetic=True, epochs=1, remat=True,
                 outpath=str(tmp_path / "out"), overwrite="delete")
    with pytest.raises(ValueError, match="--remat sets the model's field remat, which 'alexnet'"):
        Trainer(cfg, writer=None)


@pytest.mark.slow
def test_remat_trainer_end_to_end(tmp_path):
    """One synthetic epoch with --remat on the 8-device mesh: finite loss,
    checkpoint written (the flag composes with the full SPMD step)."""
    import os
    from tpudist.config import Config
    from tpudist.trainer import Trainer
    cfg = Config(arch="resnet18", num_classes=4, image_size=32, batch_size=16,
                 use_amp=False, seed=0, synthetic=True, epochs=1, remat=True,
                 outpath=str(tmp_path / "out"), overwrite="delete")
    tr = Trainer(cfg, writer=None)
    tr.fit()
    assert os.path.exists(os.path.join(cfg.outpath, "checkpoint.msgpack"))
