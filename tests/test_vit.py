"""ViT family tests: param-count parity with torchvision, forward shapes,
and sequence-parallel (ring) attention equivalence inside the encoder."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import create_model, model_names

# torchvision published param counts.
VIT_PARAM_COUNTS = {
    "vit_b_16": 86_567_656,
    "vit_b_32": 88_224_232,
}


def n_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_vits_registered():
    for n in ("vit_b_16", "vit_b_32", "vit_l_16", "vit_l_32"):
        assert n in model_names()


@pytest.mark.parametrize("arch", ["vit_b_16", "vit_b_32"])
def test_vit_param_count_matches_torchvision(arch, rng):
    model = create_model(arch, num_classes=1000)
    variables = jax.eval_shape(lambda r, x: model.init(r, x, train=False),
                               rng, jnp.ones((1, 224, 224, 3)))
    assert n_params(variables["params"]) == VIT_PARAM_COUNTS[arch]


def test_vit_forward_tiny(rng):
    # Tiny ViT config exercises the same code path without big compiles.
    from tpudist.models.vit import VisionTransformer
    model = VisionTransformer(patch_size=8, hidden_dim=32, num_layers=2,
                              num_heads=4, mlp_dim=64, num_classes=10)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(rng, x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_vit_ring_attention_matches_local(rng, mesh8):
    """A 2-layer encoder with the batch replicated and TOKENS sharded over an
    8-way 'seq' axis must produce the same logits as the unsharded model."""
    from jax.sharding import PartitionSpec as P
    from tpudist.dist import make_mesh
    from tpudist.models.vit import EncoderBlock

    mesh = make_mesh((8,), ("seq",), jax.devices()[:8])
    block_local = EncoderBlock(num_heads=4, mlp_dim=64)
    block_ring = EncoderBlock(num_heads=4, mlp_dim=64, seq_axis="seq")

    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 32, 16)),
                    jnp.float32)
    variables = block_local.init(rng, x)

    want = block_local.apply(variables, x)

    ring_fn = jax.jit(jax.shard_map(
        lambda v, xs: block_ring.apply(v, xs),
        mesh=mesh, in_specs=(P(), P(None, "seq")), out_specs=P(None, "seq"),
        check_vma=False))
    got = ring_fn(variables, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("heads,entry", [(2, "whole_seq"), (3, "streaming")])
def test_vit_block_flash_matches_xla(rng, heads, entry):
    """An encoder block at head_dim 64 with the Pallas attention against
    the XLA one, outputs and every parameter gradient: two heads group
    onto lane tiles and the kernel reads the fused projection in place;
    three do not, and the call goes through slices and the split entry.
    Decided from the shape alone."""
    from tpudist.models.vit import EncoderBlock
    from tpudist.ops.pallas.flash_attention import schedule_for

    dim, t = 64 * heads, 50
    assert schedule_for(t, heads, 64, jnp.float32) == entry
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, t, dim)),
                    jnp.float32)
    xla = EncoderBlock(num_heads=heads, mlp_dim=96, flash=False)
    flash = EncoderBlock(num_heads=heads, mlp_dim=96, flash=True)
    variables = xla.init(rng, x)
    np.testing.assert_allclose(np.asarray(flash.apply(variables, x)),
                               np.asarray(xla.apply(variables, x)),
                               rtol=2e-4, atol=2e-4)
    got = jax.grad(lambda v: (flash.apply(v, x) ** 2).sum())(variables)
    want = jax.grad(lambda v: (xla.apply(v, x) ** 2).sum())(variables)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3,
            atol=1e-3 * float(np.abs(np.asarray(b)).max()),
            err_msg=jax.tree_util.keystr(path))
