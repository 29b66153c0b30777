"""Model zoo tests: registry, shapes, param counts vs torchvision's published
counts, and BatchNorm semantics parity with torch.nn.BatchNorm2d."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import create_model, model_names
from tpudist.models.layers import BatchNorm

# Published torchvision param counts (torchvision docs / table):
TORCH_PARAM_COUNTS = {
    "resnet18": 11_689_512,
    "resnet34": 21_797_672,
    "resnet50": 25_557_032,
}


def n_params(tree):
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def test_registry_lists_resnets():
    names = model_names()
    for n in ("resnet18", "resnet34", "resnet50", "resnet101", "resnet152"):
        assert n in names


def test_unknown_arch_raises():
    with pytest.raises(ValueError, match="resnet18"):
        create_model("resnet9000")


@pytest.mark.parametrize("arch", ["resnet18", "resnet34", "resnet50"])
def test_param_count_matches_torchvision(arch, rng):
    model = create_model(arch, num_classes=1000)
    # eval_shape: no compilation — just shape inference (1-core CPU friendly).
    variables = jax.eval_shape(lambda r, x: model.init(r, x, train=False),
                               rng, jnp.ones((1, 32, 32, 3)))
    assert n_params(variables["params"]) == TORCH_PARAM_COUNTS[arch]


def test_forward_shape_and_dtype(rng):
    model = create_model("resnet18", num_classes=10, dtype=jnp.bfloat16)
    variables = model.init(rng, jnp.ones((2, 32, 32, 3)), train=False)
    out = model.apply(variables, jnp.ones((2, 32, 32, 3)), train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.bfloat16
    # params stay fp32 master copies
    assert all(x.dtype == jnp.float32 for x in jax.tree_util.tree_leaves(variables["params"]))


def test_train_mode_mutates_batch_stats(rng):
    model = create_model("resnet18", num_classes=10)
    variables = model.init(rng, jnp.ones((2, 32, 32, 3)), train=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    _, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_batchnorm_matches_torch_training_step():
    """Forward output AND running-stat update must match torch.nn.BatchNorm2d
    (momentum=0.1, eps=1e-5, unbiased running var — the torch quirk)."""
    import torch

    rng_np = np.random.RandomState(0)
    x = rng_np.randn(4, 8, 6, 3).astype(np.float32)      # NHWC

    bn = BatchNorm(momentum=0.1, epsilon=1e-5)
    variables = bn.init(jax.random.PRNGKey(0), jnp.asarray(x),
                        use_running_average=False)
    y, mutated = bn.apply(variables, jnp.asarray(x), use_running_average=False,
                          mutable=["batch_stats"])

    tbn = torch.nn.BatchNorm2d(3, momentum=0.1, eps=1e-5)
    tbn.train()
    ty = tbn(torch.tensor(x).permute(0, 3, 1, 2))        # NCHW

    np.testing.assert_allclose(np.asarray(y),
                               ty.detach().permute(0, 2, 3, 1).numpy(),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mutated["batch_stats"]["mean"]),
                               tbn.running_mean.numpy(), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mutated["batch_stats"]["var"]),
                               tbn.running_var.numpy(), rtol=1e-5, atol=1e-6)


def test_batchnorm_eval_uses_running_stats():
    import torch
    rng_np = np.random.RandomState(1)
    x = rng_np.randn(2, 4, 4, 5).astype(np.float32)

    bn = BatchNorm()
    variables = bn.init(jax.random.PRNGKey(0), jnp.asarray(x),
                        use_running_average=True)
    # seed nontrivial running stats
    stats = {"batch_stats": {"mean": jnp.arange(5, dtype=jnp.float32) * 0.1,
                             "var": jnp.arange(1, 6, dtype=jnp.float32) * 0.5}}
    y = bn.apply({"params": variables["params"], **stats}, jnp.asarray(x),
                 use_running_average=True)

    tbn = torch.nn.BatchNorm2d(5)
    tbn.eval()
    with torch.no_grad():
        tbn.running_mean.copy_(torch.arange(5, dtype=torch.float32) * 0.1)
        tbn.running_var.copy_(torch.arange(1, 6, dtype=torch.float32) * 0.5)
    ty = tbn(torch.tensor(x).permute(0, 3, 1, 2))
    np.testing.assert_allclose(np.asarray(y),
                               ty.detach().permute(0, 2, 3, 1).numpy(),
                               rtol=1e-4, atol=1e-5)


def test_sync_batchnorm_pmean_stats(mesh8):
    """SyncBN: with axis_name set, per-shard stats are pmean-ed — every shard
    normalizes with GLOBAL batch statistics (= nn.SyncBatchNorm,
    distributed_syncBN_amp.py:145)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    x = np.random.RandomState(0).randn(16, 4, 4, 3).astype(np.float32)
    bn_sync = BatchNorm(axis_name="data")
    bn_plain = BatchNorm()
    variables = bn_plain.init(jax.random.PRNGKey(0), jnp.asarray(x[:2]),
                              use_running_average=False)

    def fwd(v, xs):
        y, m = bn_sync.apply(v, xs, use_running_average=False,
                             mutable=["batch_stats"])
        return y, m["batch_stats"]

    y_sharded, stats = jax.jit(shard_map(
        fwd, mesh=mesh8, in_specs=(P(), P("data")), out_specs=(P("data"), P()),
        check_vma=False))(variables, jnp.asarray(x))

    # Global-batch reference: plain BN applied to the whole batch on one device.
    y_ref, m_ref = bn_plain.apply(variables, jnp.asarray(x),
                                  use_running_average=False,
                                  mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(stats["mean"]),
                               np.asarray(m_ref["batch_stats"]["mean"]),
                               rtol=1e-5, atol=1e-6)


def _epilogue_pair(bn, variables, x, res):
    """((value, (y, batch_stats)), grads) twice: of BatchNorm's own epilogue,
    and of the chain the call sites wrote out before ``act=`` /
    ``residual=``: relu(bn(x) + res)."""
    def chain(handed_over):
        def f(params, x, res):
            kw = dict(act="relu", residual=res) if handed_over else {}
            y, mut = bn.apply({"params": params,
                               "batch_stats": variables["batch_stats"]}, x,
                              mutable=["batch_stats"], **kw)
            if not handed_over:
                y = jax.nn.relu(y if res is None else y + res)
            return y.astype(jnp.float32).sum(), (y, mut.get("batch_stats"))
        return f

    argnums = (0, 1) if res is None else (0, 1, 2)
    return [jax.value_and_grad(chain(handed_over), argnums=argnums,
                               has_aux=True)(variables["params"], x, res)
            for handed_over in (True, False)]


def _assert_bit_equal(got, want):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) and got
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


# resnet18's five BN widths (spatial edge at 224 px, channels), the edges cut
# to CPU size: the channel counts are the model's
RESNET18_BN = ((8, 64), (6, 64), (4, 128), (3, 256), (2, 512))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("residual", [False, True], ids=["plain", "residual"])
@pytest.mark.parametrize("edge,channels", RESNET18_BN,
                         ids=[f"{e}x{e}x{c}" for e, c in RESNET18_BN])
def test_batchnorm_epilogue_is_the_written_out_chain(edge, channels, residual,
                                                     dtype):
    """``BatchNorm(act="relu", residual=...)`` IS relu(bn(x) + residual), to
    the bit, in output, running statistics and every gradient (scale, bias,
    x, residual): the hot path of every conv net's blocks, and the order
    (float32 normalise -> cast -> add -> relu) that the compiled-cost
    goldens were taken with."""
    rng = np.random.default_rng(channels + edge)
    shape = (4, edge, edge, channels)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    res = jnp.asarray(rng.standard_normal(shape), dtype) if residual else None
    bn = BatchNorm(use_running_average=False, dtype=dtype)
    variables = bn.init(jax.random.PRNGKey(0), x)
    variables = {**variables, "params": {
        "scale": jnp.asarray(rng.standard_normal(channels), jnp.float32),
        "bias": jnp.asarray(rng.standard_normal(channels), jnp.float32)}}
    got, want = _epilogue_pair(bn, variables, x, res)
    assert got[0][1][0].dtype == dtype
    _assert_bit_equal(got, want)


def test_batchnorm_epilogue_is_the_written_out_chain_in_eval_mode():
    """The same identity on running statistics (what validation runs)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 5, 5, 24)), jnp.bfloat16)
    res = jnp.asarray(rng.standard_normal((4, 5, 5, 24)), jnp.bfloat16)
    bn = BatchNorm(use_running_average=True, dtype=jnp.bfloat16)
    variables = bn.init(jax.random.PRNGKey(0), x)
    variables = {**variables, "batch_stats": {
        "mean": jnp.asarray(rng.standard_normal(24), jnp.float32),
        "var": jnp.asarray(rng.random(24) + 0.5, jnp.float32)}}
    got, want = _epilogue_pair(bn, variables, x, res)
    _assert_bit_equal(got, want)
    # running statistics are read, not written
    _assert_bit_equal(got[0][1][1], variables["batch_stats"])


def test_batchnorm_epilogue_is_the_written_out_chain_under_syncbn(mesh8):
    """And with ``axis_name`` set: statistics pmean-ed over the data axis,
    the epilogue on each shard's rows."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((16, 4, 4, 24)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((16, 4, 4, 24)), jnp.float32)
    bn = BatchNorm(use_running_average=False, axis_name="data")
    variables = BatchNorm(use_running_average=False).init(
        jax.random.PRNGKey(0), x[:2])

    def both(x, res):
        # a leading axis on every leaf, so that each shard's own values
        # (its loss, its gradients) come back side by side
        return jax.tree_util.tree_map(
            lambda a: a[None], _epilogue_pair(bn, variables, x, res))

    got, want = jax.jit(shard_map(
        both, mesh=mesh8, in_specs=(P("data"), P("data")),
        out_specs=P("data"), check_vma=False))(x, res)
    _assert_bit_equal(got, want)


@pytest.mark.parametrize("arch,layers,std,uniform", [
    # torchvision: normal(0, 0.01) for mobilenet v2/v3 Linears
    pytest.param("mobilenet_v2", ["classifier_1"], 0.01, False,
                 marks=pytest.mark.slow),
    pytest.param("mobilenet_v3_small", ["classifier_0", "classifier_3"],
                 0.01, False, marks=pytest.mark.slow),
    # torchvision mnasnet: kaiming_uniform(fan_out, sigmoid) — one fast case
    # keeps the init override path covered in the fast tier.
    ("mnasnet1_0", ["classifier_1"], None, True),
])
def test_classifier_init_matches_torchvision(arch, layers, std, uniform, rng):
    """Classifier Linear init parity (torchvision mobilenetv2.py/
    mobilenetv3.py/mnasnet.py weight-init loops). Advisor finding r1."""
    model = create_model(arch, num_classes=1000)
    variables = model.init(rng, jnp.ones((1, 32, 32, 3)), train=False)
    for layer in layers:
        cls = variables["params"][layer]
        w = np.asarray(cls["kernel"])      # >=576x1000 — plenty of samples
        b = np.asarray(cls["bias"])
        assert np.all(b == 0.0), layer
        if uniform:
            bound = np.sqrt(3.0 / w.shape[1])  # fan_out = out_features
            assert np.abs(w).max() <= bound + 1e-6, layer
            # uniform(-b, b) std = b/sqrt(3)
            np.testing.assert_allclose(w.std(), bound / np.sqrt(3), rtol=0.05)
        else:
            np.testing.assert_allclose(w.std(), std, rtol=0.05, err_msg=layer)
            assert np.abs(w).max() < 6 * std, layer
