"""Expert-parallel MoE (all_to_all dispatch) on the fake 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(scope="module")
def moe_setup():
    from tpudist.parallel.moe import init_moe_params
    d, h, e = 16, 32, 8
    params = init_moe_params(jax.random.PRNGKey(0), d, h, e)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, d)), jnp.float32)
    return params, x, e


def test_expert_parallel_matches_dense(moe_setup):
    """With capacity high enough that nothing drops, the 8-way
    expert-parallel path must equal the single-device reference exactly."""
    params, x, e = moe_setup
    from tpudist.dist import make_mesh
    from tpudist.parallel.moe import make_moe, moe_dense
    mesh = make_mesh((e,), ("expert",), jax.devices())
    # capacity = cf * t_local / e = 8 * 8 / 8 = 8 = t_local → no drops.
    fn = make_moe(mesh, capacity_factor=8.0)
    y, aux = fn(params, x)
    y_ref, aux_ref = moe_dense(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == pytest.approx(float(aux_ref), rel=1e-5)


def test_capacity_drops_are_zero_not_garbage(moe_setup):
    """Overflow tokens must contribute exactly zero (residual passthrough),
    and kept tokens must still match the dense reference."""
    params, x, e = moe_setup
    from tpudist.dist import make_mesh
    from tpudist.parallel.moe import make_moe, moe_dense, _route
    mesh = make_mesh((e,), ("expert",), jax.devices())
    fn = make_moe(mesh, capacity_factor=1.0)    # capacity 1 → heavy dropping
    y, _ = fn(params, x)
    y = np.asarray(y)
    y_ref = np.asarray(moe_dense(params, x)[0])
    # Recompute per-shard routing to know which tokens were kept.
    t_local = x.shape[0] // e
    capacity = max(1, int(1.0 * t_local / e))
    for s in range(e):
        xs = x[s * t_local:(s + 1) * t_local]
        _, _, keep, _, _ = _route(xs, params["router"], capacity)
        keep = np.asarray(keep)
        seg = slice(s * t_local, (s + 1) * t_local)
        np.testing.assert_allclose(y[seg][keep], y_ref[seg][keep],
                                   rtol=1e-5, atol=1e-5)
        assert np.all(y[seg][~keep] == 0.0)


def test_aux_loss_balanced_router_is_near_one():
    """A uniform router gives f_e = p_e = 1/E → aux = E·Σ 1/E² = 1."""
    from tpudist.parallel.moe import init_moe_params, moe_dense
    d, h, e = 8, 16, 4
    params = init_moe_params(jax.random.PRNGKey(1), d, h, e)
    params = dict(params, router=jnp.zeros((d, e)))      # uniform gates
    x = jnp.asarray(np.random.default_rng(1).standard_normal((128, d)),
                    jnp.float32)
    _, aux = moe_dense(params, x)
    assert float(aux) == pytest.approx(1.0, abs=1e-5)


def test_moe_grads_flow_through_dispatch(moe_setup):
    params, x, e = moe_setup
    from tpudist.dist import make_mesh
    from tpudist.parallel.moe import moe_spmd
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((e,), ("expert",), jax.devices())

    def loss(params, x):
        y, aux = moe_spmd(params, x, axis_name="expert", capacity_factor=8.0)
        # Per-device partial loss; psum makes the total global, so each
        # param's cotangent arrives exactly once.
        return jax.lax.psum(jnp.sum(y ** 2), "expert") / x.shape[0] + 0.01 * aux

    param_specs = {"router": P(), "w1": P("expert"), "b1": P("expert"),
                   "w2": P("expert"), "b2": P("expert")}
    g = jax.jit(jax.shard_map(
        jax.grad(loss), mesh=mesh,
        in_specs=(param_specs, P("expert")), out_specs=param_specs,
        check_vma=False))(params, x)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(leaf)).all() for leaf in flat)
    # Expert weights that received tokens must have nonzero grads.
    assert float(jnp.abs(g["w1"]).sum()) > 0
    assert float(jnp.abs(g["router"]).sum()) > 0


# --- the second router rule and expert body (parallel/moe.py) ----------------

def _reference(name):
    """A configuration's plain reference, by path: it imports nothing of the
    program."""
    import importlib.util
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{name}_for_moe_tests", os.path.join(
            root, "benchmarks", "chip", "refs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sigmoid_rule_the_bias_moves_the_choice_and_not_the_weight():
    from tpudist.parallel.moe import route_topk
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (64, 32))
    router = jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision="highest"))
    experts, weights = route_topk(u, router, 4, rule="sigmoid", scale=2.5)
    # the weights are the scores themselves at the chosen, over their sum,
    # times the factor: they sum to 2.5
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.5, rtol=1e-6)
    top = jnp.take_along_axis(scores, experts, axis=-1)
    np.testing.assert_allclose(weights, 2.5 * top / jnp.sum(
        top, axis=-1, keepdims=True), rtol=1e-6)
    assert np.array_equal(np.sort(experts, axis=-1), np.sort(
        jax.lax.top_k(scores, 4)[1], axis=-1))
    # a bias large enough to seat expert 3 with every token: chosen by all,
    # weighed by its own score (without the bias)
    bias = jnp.zeros((16,)).at[3].set(10.0)
    moved, w = route_topk(u, router, 4, rule="sigmoid", bias=bias, scale=2.5)
    assert bool(jnp.all(jnp.any(moved == 3, axis=-1)))
    assert not bool(jnp.all(jnp.any(experts == 3, axis=-1)))
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.5, rtol=1e-6)
    at3 = jnp.sum(jnp.where(moved == 3, w, 0.0), axis=-1)
    chosen = jnp.take_along_axis(scores, moved, axis=-1)
    np.testing.assert_allclose(at3, 2.5 * scores[:, 3] / jnp.sum(
        chosen, axis=-1), rtol=1e-6)
    # the bias takes no gradient: it is no parameter of the loss
    g = jax.grad(lambda b: jnp.sum(route_topk(
        u, router, 4, rule="sigmoid", bias=b)[1] ** 2))(bias)
    assert not np.asarray(g).any()
    with pytest.raises(ValueError, match="router rule 'tanh'.*softmax"):
        route_topk(u, router, 4, rule="tanh")


def test_softmax_rule_is_what_it_was():
    from tpudist.parallel.moe import route_topk
    key = jax.random.PRNGKey(2)
    u = jax.random.normal(key, (32, 16))
    router = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    experts, weights = route_topk(u, router, 2)
    probs = jax.nn.softmax(jnp.dot(u, router, precision="highest"), axis=-1)
    top, want = jax.lax.top_k(probs, 2)
    assert np.array_equal(experts, want)
    np.testing.assert_allclose(weights, top / jnp.sum(
        top, axis=-1, keepdims=True), rtol=1e-6)


@pytest.mark.parametrize("held,body", [(1, "relu2"), (4, "relu2"),
                                       (16, "relu2"), (4, "swiglu")])
def test_sixteen_shares_and_the_shared_expert_once_are_the_uncut_layer(
        held, body):
    """The share test: the routed parts that all the holders of a layer
    compute (16 holders of 1 expert, 4 of 4, 1 of all 16), summed, plus the
    shared expert counted ONCE, equal what the plain reference gives for
    the whole layer with every expert held in one place; and each holder's
    part is the reference's for that share. Both bodies: ungated ``relu^2``
    (the hybrid's reference) and SwiGLU, the shared expert gated too (the
    latent-attention model's)."""
    from tpudist.parallel.moe import moe_topk_held, shared_expert
    gated = body == "swiglu"
    ref = _reference("joyai_flash_ep16" if gated else "nemotron3_nano_ep16")
    d, f, experts, k = 32, 16, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    # a routed expert's ``up`` lies [width, hidden] ungated, [hidden, width]
    # beside a gate
    wide = (experts, d, f) if gated else (experts, f, d)
    params = {"router": jax.random.normal(ks[0], (d, experts)),
              "up": jax.random.normal(ks[1], wide) * 0.2,
              "down": jax.random.normal(ks[2], (experts, f, d)) * 0.2,
              "shared_up": jax.random.normal(ks[3], (d, 2 * f)) * 0.2,
              "shared_down": jax.random.normal(ks[4], (2 * f, d)) * 0.2}
    if gated:
        params["gate"] = jax.random.normal(ks[6], wide) * 0.2
        params["shared_gate"] = jax.random.normal(ks[7], (d, 2 * f)) * 0.2
    bias = jax.random.normal(ks[5], (experts,)) * 0.1
    u = jax.random.normal(jax.random.PRNGKey(1), (64, d))
    z = dict(k=k, scaling=2.5)
    routed = ("gate", "up", "down") if gated else ("up", "down")

    def share(lo, n):
        return dict({name: params[name][lo:lo + n] for name in routed},
                    router=params["router"], router_bias=bias)

    def reference(p, lo, n, shared):
        if gated:
            return ref.experts(u, p, bias, dict(z, first=lo, held=n),
                               shared=shared)
        return (ref._moe if shared else ref._routed)(
            u, p, bias, dict(z, first=lo, held=n), None)
    with jax.default_matmul_precision("highest"):
        whole, pairs = reference(params, 0, experts, True)
        total, computed = 0.0, 0.0
        for lo in range(0, experts, held):
            y, counters = moe_topk_held(share(lo, held), u, top_k=k,
                                        first_expert=lo, rule="sigmoid",
                                        scale=2.5)
            part, _ = reference(share(lo, held), lo, held, False)
            np.testing.assert_allclose(y, part, atol=2e-5)
            total, computed = total + y, computed + counters["moe_pairs"]
        once = shared_expert({name[len("shared_"):]: w for name, w
                              in params.items()
                              if name.startswith("shared_")}, u)
    np.testing.assert_allclose(total + once, whole, atol=5e-5)
    assert float(computed) == 64 * k == float(jnp.sum(pairs))
    # the shared expert is no small part: left out, the sum misses
    assert float(jnp.max(jnp.abs(total - whole))) > 0.05


def test_relu2_experts_gradients_match_a_dense_loop():
    """The ungated body through the pair buffer, grouped products and
    walks: every gradient against a dense loop over the held experts."""
    from tpudist.parallel.moe import moe_topk_held
    d, f, experts, held, lo, k = 16, 24, 8, 4, 2, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    params = {"router": jax.random.normal(ks[0], (d, experts)),
              "up": jax.random.normal(ks[1], (held, f, d)) * 0.3,
              "down": jax.random.normal(ks[2], (held, f, d)) * 0.3}
    u = jax.random.normal(ks[3], (48, d))
    w = jax.random.normal(jax.random.PRNGKey(4), (48, d))

    def dense(params, u):
        scores = jax.nn.sigmoid(jnp.dot(u, params["router"],
                                        precision="highest"))
        top, chosen = jax.lax.top_k(scores, k)
        weights = 2.5 * top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
        y = 0.0
        for e in range(held):
            mine = jnp.sum(jnp.where(chosen == lo + e, weights, 0.0), axis=-1)
            y = y + mine[:, None] * (jnp.square(jax.nn.relu(
                u @ params["up"][e].T)) @ params["down"][e])
        return y

    def ours(params, u):
        return moe_topk_held(params, u, top_k=k, first_expert=lo,
                             rule="sigmoid", scale=2.5)[0]
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(params, u), dense(params, u),
                                   atol=2e-5)
        got = jax.grad(lambda p, x: jnp.sum(ours(p, x) * w), (0, 1))(
            params, u)
        want = jax.grad(lambda p, x: jnp.sum(dense(p, x) * w), (0, 1))(
            params, u)
    for (path, g), (_, r) in zip(jax.tree_util.tree_leaves_with_path(got),
                                 jax.tree_util.tree_leaves_with_path(want)):
        np.testing.assert_allclose(
            g, r, atol=1e-4 * float(jnp.max(jnp.abs(r))) + 1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_grouped_product_tiles_a_width_no_lane_tile_divides():
    """An expert width of 1,856 (14.5 lane tiles) takes tiles of 640 that
    overhang its end; the product and both transposes over such widths are
    a dense loop's on the rows that hold a pair. Widths a multiple of 128
    divides keep the tile they had."""
    from tpudist.ops.pallas.grouped_matmul import _tile, grouped_matmul
    assert [_tile(n) for n in (2304, 896, 1792, 2048, 768, 1536)] == [
        768, 896, 896, 1024, 768, 768]
    assert (_tile(1856), _tile(2688), _tile(32), _tile(192)) == (
        640, 896, 32, 256)
    m, k, n = 64, 192, 320
    sizes = [10, 0, 30]
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (3, k, n))
    g = jnp.where((jnp.arange(m) < 40)[:, None],
                  jax.random.normal(kg, (m, n)), 0.0)

    def dense(x, w):
        out, start = jnp.zeros((m, n)), 0
        for i, size in enumerate(sizes):
            out = out.at[start:start + size].set(
                x[start:start + size] @ w[i])
            start += size
        return out

    def ours(x, w):
        return jnp.where((jnp.arange(m) < 40)[:, None], grouped_matmul(
            x, w, jnp.asarray(sizes, jnp.int32)), 0.0)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(x, w), dense(x, w), atol=1e-4)
        got = jax.grad(lambda x, w: jnp.sum(ours(x, w) * g), (0, 1))(x, w)
        want = jax.grad(lambda x, w: jnp.sum(dense(x, w) * g), (0, 1))(x, w)
    np.testing.assert_allclose(got[0][:40], want[0][:40], atol=1e-3)
    np.testing.assert_allclose(got[1], want[1], atol=1e-3)
