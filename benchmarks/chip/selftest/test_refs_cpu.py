"""The plain references against `tpudist/models` at a tiny size on the CPU.

Run by path (`python -m pytest benchmarks/chip/selftest -q`); tier-1 does
not collect this directory. Both sides run in float32 here, so they must
agree to float32 rounding: that ties each reference's equations (and its
parameter names) to the program's model, which is what lets the chip run
compare the bf16 program with the float32 reference at the published widths.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_ref(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{name}", os.path.join(CHIP, "refs", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


RESNET = dict(num_classes=8, image_size=64, bn_eps=1e-5, bn_momentum=0.1,
              reference_block_rows=4, weight_decay=1e-4, momentum=0.9,
              label_smoothing=0.0)
VIT = dict(num_classes=8, image_size=32, patch_size=16, hidden_size=768,
           intermediate_size=3072, num_hidden_layers=2,
           num_attention_heads=12, layer_norm_eps=1e-6, label_smoothing=0.11,
           adam_b1=0.9, adam_b2=0.999, adam_eps=1e-8, weight_decay=0.3,
           decay_min_ndim=2)


def _batch(cfg, n=8, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    s = cfg["image_size"]
    return (jax.random.normal(k1, (n, s, s, 3), jnp.float32),
            jax.random.randint(k2, (n,), 0, cfg["num_classes"]))


def _program_loss(arch, cfg, params, stats, images, labels):
    from tpudist.models import create_model
    from tpudist.ops.loss import cross_entropy_loss
    if arch.startswith("vit"):      # the registry's depth is fixed at 12
        from tpudist.models.vit import VisionTransformer
        model = VisionTransformer(
            patch_size=cfg["patch_size"], hidden_dim=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            mlp_dim=cfg["intermediate_size"],
            num_classes=cfg["num_classes"], flash=False)
    else:
        model = create_model(arch, num_classes=cfg["num_classes"], dtype=None)

    def loss(p):
        out, mut = model.apply({"params": p, "batch_stats": stats}, images,
                               train=True, mutable=["batch_stats"])
        return cross_entropy_loss(out, labels,
                                  cfg["label_smoothing"]), mut
    return jax.value_and_grad(loss, has_aux=True)(params)


@pytest.mark.parametrize("name,arch,cfg", [
    ("resnet18_ref", "resnet18", RESNET), ("vit_b16", "vit_b_16", VIT)])
def test_reference_matches_program_model_in_float32(name, arch, cfg):
    ref = load_ref(name)
    params, stats = ref.init(jax.random.PRNGKey(3), cfg)
    images, labels = _batch(cfg)
    with jax.default_matmul_precision("highest"):
        (loss_p, mut), grads_p = _program_loss(arch, cfg, params, stats,
                                               images, labels)
    loss_r, grads_r, new_params, new_stats, _ = ref.step(
        params, stats, ref.init_opt(params), images, labels, cfg, 0.1)
    assert abs(float(loss_p) - float(loss_r)) < 2e-5 * abs(float(loss_r))
    flat_p = jax.tree_util.tree_leaves_with_path(grads_p)
    flat_r = jax.tree_util.tree_leaves_with_path(grads_r)
    assert [k for k, _ in flat_p] == [k for k, _ in flat_r]
    scale = np.median([float(jnp.linalg.norm(g)) for _, g in flat_r])
    for (path, gp), (_, gr) in zip(flat_p, flat_r):
        err = float(jnp.linalg.norm(gp - gr))
        ref_n = max(float(jnp.linalg.norm(gr)), scale)
        assert err < 1e-2 * ref_n, (jax.tree_util.keystr(path), err, ref_n)
    if stats:
        # torch's running statistics: 0.9 * old + 0.1 * batch (var unbiased)
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(mut["batch_stats"]),
                jax.tree_util.tree_leaves_with_path(new_stats)):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6,
                                       err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("name,cfg", [("resnet18_ref", RESNET),
                                      ("vit_b16", VIT)])
def test_blocks_do_not_change_the_reference(name, cfg):
    """Row blocks are a memory device only: 2 rows a block = 8 rows a block."""
    ref = load_ref(name)
    params, stats = ref.init(jax.random.PRNGKey(4), cfg)
    images, labels = _batch(cfg, seed=1)
    out = []
    for rows in (2, 8):
        c = dict(cfg, reference_block_rows=rows)
        loss, grads, *_ = ref.step(params, stats, ref.init_opt(params),
                                   images, labels, c, 0.1)
        out.append((float(loss), grads))
    assert abs(out[0][0] - out[1][0]) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(out[0][1]),
                    jax.tree_util.tree_leaves(out[1][1])):
        assert float(jnp.linalg.norm(a - b)) <= 1e-3 * max(
            float(jnp.linalg.norm(b)), 1e-6)


@pytest.mark.parametrize("name,cfg", [("resnet18_ref", RESNET),
                                      ("vit_b16", VIT)])
def test_optimizer_update_is_torch_semantics(name, cfg):
    """One step of the reference's optimizer against the closed form."""
    ref = load_ref(name)
    params, stats = ref.init(jax.random.PRNGKey(5), cfg)
    images, labels = _batch(cfg, seed=2)
    lr = 0.05
    _, grads, new_params, _, _ = ref.step(
        params, stats, ref.init_opt(params), images, labels, cfg, lr)
    for p, g, q in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (params, grads, new_params))):
        if name == "resnet18_ref":       # v = g + wd*p ; p -= lr*v
            want = p - lr * (g + cfg["weight_decay"] * p)
        else:                            # first AdamW step: m_hat/sqrt(v_hat)
            u = g / (jnp.abs(g) + cfg["adam_eps"])
            if p.ndim >= cfg["decay_min_ndim"]:
                u = u + cfg["weight_decay"] * p
            want = p - lr * u
        np.testing.assert_allclose(q, want, rtol=1e-4, atol=2e-6)
