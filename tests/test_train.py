"""Training-step tests on the fake 8-device mesh: loss decreases, replicas
stay consistent, torch-parity SGD/LR-schedule math, AMP, SyncBN flag."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.config import Config
from tpudist.dist import shard_host_batch
from tpudist.models import create_model
from tpudist.train import (compute_dtype, create_train_state, lr_for_epoch,
                           make_eval_step, make_train_step, sgd_torch)


def _tiny_cfg(**kw):
    defaults = dict(arch="resnet18", num_classes=8, image_size=32,
                    batch_size=32, epochs=5, step=[3, 4], lr=0.05,
                    use_amp=False, seed=0)
    defaults.update(kw)
    return Config(**defaults).finalize(8)


def _batch(cfg, seed=0):
    rng = np.random.default_rng(seed)
    images = rng.standard_normal(
        (cfg.batch_size, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
    labels = rng.integers(0, cfg.num_classes, size=(cfg.batch_size,)).astype(np.int32)
    # Plant signal so the loss can drop fast.
    for i in range(cfg.batch_size):
        images[i, :2, :2, :] += labels[i]
    return images, labels


def _setup(cfg, mesh8):
    model = create_model(cfg.arch, num_classes=cfg.num_classes,
                         dtype=compute_dtype(cfg),
                         sync_batchnorm=cfg.sync_batchnorm, bn_axis_name="data")
    state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                               input_shape=(1, cfg.image_size, cfg.image_size, 3))
    return model, state


def test_loss_decreases_over_steps(mesh8):
    cfg = _tiny_cfg(lr=0.02)
    model, state = _setup(cfg, mesh8)
    train_step = make_train_step(mesh8, model, cfg)
    images, labels = _batch(cfg)
    images, labels = shard_host_batch(mesh8, (images, labels))
    lr = jnp.asarray(cfg.lr, jnp.float32)
    losses = []
    for _ in range(8):
        state, metrics = train_step(state, images, labels, lr)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses


@pytest.mark.slow
def test_metrics_are_global_means(mesh8):
    """The in-program pmean must equal the reference's reduce_mean over
    per-shard metrics (distributed.py:78-82)."""
    cfg = _tiny_cfg()
    model, state = _setup(cfg, mesh8)
    eval_step = make_eval_step(mesh8, model, cfg)
    images, labels = _batch(cfg)
    gi, gl = shard_host_batch(mesh8, (images, labels))
    metrics = eval_step(state, gi, gl)

    # Host-side reference: mean of per-shard losses.
    from tpudist.ops import accuracy, cross_entropy_loss
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    per_shard = []
    shard = cfg.batch_size // 8
    for s in range(8):
        out = model.apply(variables, jnp.asarray(images[s * shard:(s + 1) * shard]),
                          train=False)
        per_shard.append(float(cross_entropy_loss(
            out, jnp.asarray(labels[s * shard:(s + 1) * shard]))))
    np.testing.assert_allclose(float(metrics["loss"]), np.mean(per_shard),
                               rtol=1e-5)


def test_sgd_matches_torch():
    """Step-by-step parity with torch.optim.SGD(momentum=0.9, wd=1e-4) on a
    quadratic — including the wd-before-momentum ordering."""
    import torch

    w0 = np.array([1.0, -2.0, 3.0], dtype=np.float32)
    lr, mu, wd = 0.1, 0.9, 0.01

    tw = torch.tensor(w0, requires_grad=True)
    topt = torch.optim.SGD([tw], lr=lr, momentum=mu, weight_decay=wd)

    tx = sgd_torch(lr, mu, wd)
    params = {"w": jnp.asarray(w0)}
    opt_state = tx.init(params)

    for step in range(5):
        # grad of 0.5*||w||^2 is w (plus a step-dependent constant)
        topt.zero_grad()
        loss = 0.5 * (tw ** 2).sum() + (step * 0.1) * tw.sum()
        loss.backward()
        topt.step()

        grads = {"w": params["w"] + step * 0.1}
        opt_state.hyperparams["learning_rate"] = jnp.asarray(lr)
        updates, opt_state = tx.update(grads, opt_state, params)
        import optax
        params = optax.apply_updates(params, updates)

    np.testing.assert_allclose(np.asarray(params["w"]), tw.detach().numpy(),
                               rtol=1e-5, atol=1e-6)


def test_lr_schedule_matches_torch_multisteplr():
    """lr(e) with milestones [3,4], gamma .1, step-at-epoch-start
    (distributed.py:192): epochs 0-2 → lr, 3 → lr*.1, 4 → lr*.01."""
    cfg = Config(lr=0.1, step=[3, 4], gamma=0.1, epochs=5)
    got = [lr_for_epoch(cfg, e) for e in range(5)]
    np.testing.assert_allclose(got, [0.1, 0.1, 0.1, 0.01, 0.001], rtol=1e-9)


def test_lr_scheduler_rejects_unknown():
    cfg = Config(lr_scheduler="cyclic")
    with pytest.raises(AssertionError):
        lr_for_epoch(cfg, 0)     # parity: distributed.py:153-154 asserts


@pytest.mark.slow
def test_amp_bf16_runs_and_trains(mesh8):
    cfg = _tiny_cfg(use_amp=True)
    model, state = _setup(cfg, mesh8)
    train_step = make_train_step(mesh8, model, cfg)
    images, labels = _batch(cfg)
    images, labels = shard_host_batch(mesh8, (images, labels))
    lr = jnp.asarray(cfg.lr, jnp.float32)
    l0 = None
    for _ in range(4):
        state, metrics = train_step(state, images, labels, lr)
        if l0 is None:
            l0 = float(metrics["loss"])
    assert float(metrics["loss"]) < l0
    # master params still fp32
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(state.params))


@pytest.mark.slow
def test_sync_batchnorm_flag_changes_stats(mesh8):
    """SyncBN model must see GLOBAL batch stats: with heterogeneous shards,
    sync vs plain BN give different outputs."""
    cfg_plain = _tiny_cfg(sync_batchnorm=False)
    cfg_sync = _tiny_cfg(sync_batchnorm=True)
    model_p, state_p = _setup(cfg_plain, mesh8)
    model_s, state_s = _setup(cfg_sync, mesh8)
    step_p = make_train_step(mesh8, model_p, cfg_plain)
    step_s = make_train_step(mesh8, model_s, cfg_sync)

    rng = np.random.default_rng(0)
    images = rng.standard_normal((32, 32, 32, 3)).astype(np.float32)
    images[16:] *= 5.0          # make shards statistically different
    labels = rng.integers(0, 8, size=(32,)).astype(np.int32)
    gi, gl = shard_host_batch(mesh8, (images, labels))
    lr = jnp.asarray(0.0, jnp.float32)   # no param movement; isolate BN

    _, mp = step_p(state_p, gi, gl, lr)
    _, ms = step_s(state_s, gi, gl, lr)
    assert abs(float(mp["loss"]) - float(ms["loss"])) > 1e-6


@pytest.mark.slow
def test_grad_accumulation_equivalence(mesh8):
    """accum_steps=4 must produce the same update as one full-batch step for
    a BN/dropout-free model (CE is a mean, so microbatch-averaged grads equal
    full-batch grads exactly)."""
    import jax
    import jax.numpy as jnp
    from tpudist.config import Config
    from tpudist.dist import shard_host_batch
    from tpudist.models.vit import VisionTransformer
    from tpudist.train import create_train_state, make_train_step

    model = VisionTransformer(patch_size=4, hidden_dim=32, num_layers=2,
                              num_heads=4, mlp_dim=64, num_classes=8,
                              flash=False)
    base = dict(arch="vit_b_16", num_classes=8, image_size=16, batch_size=64,
                use_amp=False, seed=0)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((64, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, 8, size=(64,)).astype(np.int32)
    images, labels = shard_host_batch(mesh8, (images, labels))
    lr = jnp.float32(0.05)

    results = []
    for accum in (1, 4):
        cfg = Config(**base, accum_steps=accum).finalize(8)
        state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                                   input_shape=(1, 16, 16, 3))
        step = make_train_step(mesh8, model, cfg)
        state, metrics = step(state, images, labels, lr)
        results.append((jax.device_get(state.params), float(metrics["loss"])))
    (p1, l1), (p4, l4) = results
    assert abs(l1 - l4) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


@pytest.mark.slow
def test_fp16_dynamic_scale_with_accum(mesh8):
    """fp16 dynamic loss scaling composes with gradient accumulation
    (VERDICT r4 next #5) under torch GradScaler-with-accumulation ordering
    (``scaler.scale(loss).backward()`` per microbatch, ONE
    ``scaler.step``/``update``): the scale stays fixed across the scan and
    a single finite-check governs the optimizer step. A clean step trains
    (finite loss, params move, fin_steps advances); an overflow in ONE
    microbatch poisons the accumulated grads, so the whole step is skipped
    and the scale backs off."""
    from flax.training import dynamic_scale as dynamic_scale_lib

    cfg = _tiny_cfg(use_amp=True, amp_dtype="float16", accum_steps=2)
    model, state = _setup(cfg, mesh8)
    assert state.dynamic_scale is not None
    # Start at a scale measured to overflow THIS workload by a little
    # (microbatch-2 resnet BN backward in fp16 overflows at 256, is finite
    # at 1 — verified single-device): the test then exercises the REAL
    # GradScaler opening behavior — back off until a step lands — in a few
    # halvings instead of the ~16 the 65536 default would need.
    state = state.replace(dynamic_scale=dynamic_scale_lib.DynamicScale(
        scale=256.0))

    step = make_train_step(mesh8, model, cfg)
    images, labels = _batch(cfg)
    sharded = shard_host_batch(mesh8, (images, labels))
    lr = jnp.float32(0.01)

    p0 = jax.device_get(state.params["conv1"]["kernel"])
    landed = 0
    for _ in range(12):
        state, metrics = step(state, *sharded, lr)
        assert np.isfinite(float(metrics["loss"]))
        landed = int(jax.device_get(state.dynamic_scale.fin_steps))
        if landed:
            break
    assert landed >= 1, "scale never settled: grads nonfinite at every scale"
    assert not np.allclose(jax.device_get(state.params["conv1"]["kernel"]), p0)

    # Poison only each shard's FIRST microbatch (shards are contiguous
    # blocks of 4 rows; accum=2 splits each into 2+2): the inf must ride
    # the running sum into the averaged grads and skip the WHOLE step.
    bad = images.copy()
    bad[(np.arange(len(bad)) % 4) < 2] = np.inf
    bad_sharded = shard_host_batch(mesh8, (bad, labels))
    p_before = jax.device_get(state.params["conv1"]["kernel"])
    scale_before = float(jax.device_get(state.dynamic_scale.scale))
    state, m_bad = step(state, *bad_sharded, lr)
    np.testing.assert_array_equal(
        jax.device_get(state.params["conv1"]["kernel"]), p_before)
    assert float(jax.device_get(state.dynamic_scale.scale)) == \
        scale_before * 0.5
    assert int(jax.device_get(state.dynamic_scale.fin_steps)) == 0


@pytest.mark.slow
def test_grad_accumulation_with_batchnorm_trains(mesh8):
    """resnet18 with accum: runs, loss finite, BN running stats update."""
    import jax
    import jax.numpy as jnp
    from tpudist.config import Config
    from tpudist.dist import shard_host_batch
    from tpudist.models import create_model
    from tpudist.train import (compute_dtype, create_train_state,
                               make_train_step)

    cfg = Config(arch="resnet18", num_classes=4, image_size=32, batch_size=32,
                 use_amp=False, seed=0, accum_steps=2).finalize(8)
    model = create_model(cfg.arch, num_classes=4)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                               input_shape=(1, 32, 32, 3))
    step = make_train_step(mesh8, model, cfg)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((32, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(32,)).astype(np.int32)
    images, labels = shard_host_batch(mesh8, (images, labels))
    before = jax.device_get(state.batch_stats["bn1"]["mean"])
    state, metrics = step(state, images, labels, jnp.float32(0.01))
    after = jax.device_get(state.batch_stats["bn1"]["mean"])
    assert np.isfinite(float(metrics["loss"]))
    assert not np.allclose(before, after)


def test_aux_head_loss_weighted_in_both_paths():
    """Models that sow aux-classifier logits (googlenet/inception) must have
    them weighted into the training loss in BOTH step paths — shard_map
    (_loss_fn) and GSPMD — or the aux params get zero gradient (ADVICE r1 #2).
    Uses a toy sow-ing module so the mechanism is tested without a heavyweight
    arch."""
    from flax import linen as nn
    from tpudist.ops import cross_entropy_loss
    from tpudist.train import _loss_fn

    class ToyAux(nn.Module):
        aux_loss_weight = 0.3

        @nn.compact
        def __call__(self, x, train=False):
            pooled = x.mean(axis=(1, 2))
            logits = nn.Dense(4, name="fc")(pooled)
            aux = nn.Dense(4, name="aux_fc")(pooled)
            if train:
                self.sow("intermediates", "aux", aux)
            return logits

    model = ToyAux()
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.standard_normal((8, 4, 4, 3)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 4, size=(8,)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images)
    key = jax.random.PRNGKey(1)

    loss, (outputs, _) = _loss_fn(model, key, variables["params"], {},
                                  images, labels)
    aux_logits = model.apply(variables, images, train=True,
                             mutable=["intermediates"])[1][
                                 "intermediates"]["aux"][0]
    want = (cross_entropy_loss(outputs, labels) +
            0.3 * cross_entropy_loss(aux_logits, labels))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-6)

    # Gradient actually reaches the aux head.
    g = jax.grad(lambda p: _loss_fn(model, key, p, {}, images, labels)[0])(
        variables["params"])
    assert float(jnp.abs(g["aux_fc"]["kernel"]).max()) > 0.0


def test_aux_head_loss_weighted_in_gspmd_path(mesh8):
    from flax import linen as nn
    from tpudist.ops import cross_entropy_loss
    from tpudist.parallel.tensor_parallel import make_gspmd_train_step
    from tpudist.train import create_train_state

    class ToyAux(nn.Module):
        aux_loss_weight = 0.5

        @nn.compact
        def __call__(self, x, train=False):
            pooled = x.mean(axis=(1, 2))
            logits = nn.Dense(4, name="fc")(pooled)
            aux = nn.Dense(4, name="aux_fc")(pooled)
            if train:
                self.sow("intermediates", "aux", aux)
            return logits

    cfg = Config(arch="toy", num_classes=4, image_size=4, batch_size=16,
                 use_amp=False, seed=0).finalize(8)
    model = ToyAux()
    state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                               input_shape=(1, 4, 4, 3))
    step = make_gspmd_train_step(mesh8, model, cfg, rules=())
    rng = np.random.default_rng(0)
    images = rng.standard_normal((16, 4, 4, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(16,)).astype(np.int32)
    aux_before = jax.device_get(state.params["aux_fc"]["kernel"]).copy()
    im, lb = shard_host_batch(mesh8, (images, labels))
    state, metrics = step(state, im, lb, jnp.float32(0.1))
    # Aux head moved → its gradient was nonzero through the GSPMD path.
    aux_after = jax.device_get(state.params["aux_fc"]["kernel"])
    assert not np.allclose(aux_before, aux_after)
    assert np.isfinite(float(metrics["loss"]))


def test_adamw_matches_torch():
    """Step-by-step parity with torch.optim.AdamW(lr, wd=0.05) — decoupled
    decay, bias correction, eps outside the sqrt."""
    import torch

    from tpudist.train import adamw_torch

    w0 = np.array([1.0, -2.0, 3.0], dtype=np.float32)
    lr, wd = 0.01, 0.05

    tw = torch.tensor(w0, requires_grad=True)
    topt = torch.optim.AdamW([tw], lr=lr, weight_decay=wd)

    tx = adamw_torch(lr, wd)
    params = {"w": jnp.asarray(w0)}
    opt_state = tx.init(params)

    import optax
    for step in range(6):
        topt.zero_grad()
        loss = 0.5 * (tw ** 2).sum() + (step * 0.1) * tw.sum()
        loss.backward()
        topt.step()

        grads = {"w": params["w"] + step * 0.1}
        opt_state.hyperparams["learning_rate"] = jnp.asarray(lr)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)

        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tw.detach().numpy(), rtol=1e-5, atol=1e-6)


def test_make_optimizer_dispatch():
    from tpudist.train import make_optimizer

    cfg = Config(optimizer="sgd").finalize(1)
    assert make_optimizer(cfg) is not None
    cfg = Config(optimizer="adamw").finalize(1)
    assert make_optimizer(cfg) is not None
    with pytest.raises(ValueError, match="lamb"):
        make_optimizer(Config(optimizer="lamb").finalize(1))


def test_adamw_no_decay_mask_excludes_norms_and_biases():
    """make_optimizer('adamw') must not decay 1-d params (biases, LN/BN
    scales, layer_scale) or swin's relative_position_bias_table — the
    published recipes' param groups."""
    import optax
    from tpudist.train import make_optimizer

    cfg = Config(optimizer="adamw", lr=0.1, weight_decay=0.5).finalize(1)
    tx = make_optimizer(cfg)
    params = {"dense": {"kernel": jnp.ones((2, 2)), "bias": jnp.ones((2,))},
              "ln": {"scale": jnp.ones((2,))},
              "attn": {"relative_position_bias_table": jnp.ones((9, 2)),
                       "logit_scale": jnp.ones((2, 1, 1)),
                       "cpb_mlp_0": {"kernel": jnp.ones((2, 2))}}}
    opt_state = tx.init(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    opt_state.hyperparams["learning_rate"] = jnp.asarray(0.1)
    updates, _ = tx.update(zeros, opt_state, params)
    new = optax.apply_updates(params, updates)
    # zero grads → adam term is 0; only the decay moves params
    assert np.all(np.asarray(new["dense"]["kernel"]) < 1.0)   # decayed
    np.testing.assert_array_equal(np.asarray(new["dense"]["bias"]), 1.0)
    np.testing.assert_array_equal(np.asarray(new["ln"]["scale"]), 1.0)
    np.testing.assert_array_equal(
        np.asarray(new["attn"]["relative_position_bias_table"]), 1.0)
    # swin v2: logit_scale (ndim 3) and the cpb MLP kernels stay undecayed
    np.testing.assert_array_equal(np.asarray(new["attn"]["logit_scale"]), 1.0)
    np.testing.assert_array_equal(
        np.asarray(new["attn"]["cpb_mlp_0"]["kernel"]), 1.0)


def test_lr_warmup_ramp_and_handoff():
    from tpudist.train import lr_for_epoch

    cfg = Config(lr=0.1, warmup_epochs=3, epochs=10, lr_scheduler="cosine")
    # linear ramp: 1/3, 2/3, 3/3 of base lr
    assert lr_for_epoch(cfg, 0) == pytest.approx(0.1 / 3)
    assert lr_for_epoch(cfg, 1) == pytest.approx(0.2 / 3)
    assert lr_for_epoch(cfg, 2) == pytest.approx(0.1)
    # cosine takes over from the END of warmup (full lr at epoch==warm)
    assert lr_for_epoch(cfg, 3) == pytest.approx(0.1)
    assert lr_for_epoch(cfg, 10) == pytest.approx(0.0, abs=1e-9)
    # steplr milestones stay absolute and unaffected when warmup is off
    cfg2 = Config(lr=0.1, epochs=5, step=[3, 4], gamma=0.1)
    assert lr_for_epoch(cfg2, 2) == pytest.approx(0.1)
    assert lr_for_epoch(cfg2, 3) == pytest.approx(0.01)
    # warmup MULTIPLIES the scheduled lr: a milestone inside the warmup
    # window still decays (no spike + cliff at the handoff)
    cfg3 = Config(lr=0.1, epochs=10, step=[3, 4], gamma=0.1, warmup_epochs=5)
    assert lr_for_epoch(cfg3, 2) == pytest.approx(0.1 * 3 / 5)
    assert lr_for_epoch(cfg3, 3) == pytest.approx(0.01 * 4 / 5)
    assert lr_for_epoch(cfg3, 5) == pytest.approx(0.001)


def test_label_smoothing_changes_train_loss_only(mesh8):
    """--label-smoothing raises the train CE floor; eval loss stays plain CE."""
    from tpudist.dist import shard_host_batch
    from tpudist.models import create_model
    from tpudist.train import (create_train_state, make_eval_step,
                               make_train_step)

    rng = np.random.default_rng(0)
    images = rng.standard_normal((16, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 5, size=(16,)).astype(np.int32)

    losses = {}
    evals = {}
    for sm in (0.0, 0.2):
        cfg = Config(arch="resnet18", num_classes=5, image_size=32,
                     batch_size=16, use_amp=False, seed=0,
                     label_smoothing=sm).finalize(8)
        model = create_model(cfg.arch, num_classes=5)
        state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                                   input_shape=(1, 32, 32, 3))
        step = make_train_step(mesh8, model, cfg)
        ev = make_eval_step(mesh8, model, cfg)
        im, lb = shard_host_batch(mesh8, (images, labels))
        # eval first: the train step donates (deletes) its input state
        evals[sm] = float(ev(state, im, lb)["loss"])
        _, m = step(state, im, lb, jnp.float32(0.0))   # lr 0: params fixed
        losses[sm] = float(m["loss"])
    # same params (lr=0, same seed): smoothing must move the train loss
    assert losses[0.2] != pytest.approx(losses[0.0], rel=1e-6)
    # eval path ignores smoothing entirely
    assert evals[0.2] == pytest.approx(evals[0.0], rel=1e-6)


def test_model_ema_tracks_params(mesh8):
    """--model-ema-decay: after each optimizer step, ema = d*ema + (1-d)*p."""
    from tpudist.dist import shard_host_batch
    from tpudist.models import create_model
    from tpudist.train import create_train_state, make_train_step

    d = 0.5
    cfg = Config(arch="resnet18", num_classes=5, image_size=32, batch_size=16,
                 use_amp=False, seed=0, model_ema_decay=d).finalize(8)
    model = create_model(cfg.arch, num_classes=5)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg,
                               input_shape=(1, 32, 32, 3))
    p0 = jax.device_get(state.params["conv1"]["kernel"])
    np.testing.assert_array_equal(
        jax.device_get(state.ema_params["params"]["conv1"]["kernel"]), p0)

    step = make_train_step(mesh8, model, cfg)
    rng = np.random.default_rng(0)
    images = rng.standard_normal((16, 32, 32, 3)).astype(np.float32)
    labels = rng.integers(0, 5, size=(16,)).astype(np.int32)
    im, lb = shard_host_batch(mesh8, (images, labels))
    s0 = jax.device_get(state.batch_stats["bn1"]["mean"])
    state, _ = step(state, im, lb, jnp.float32(0.1))
    p1 = jax.device_get(state.params["conv1"]["kernel"])
    ema1 = jax.device_get(state.ema_params["params"]["conv1"]["kernel"])
    np.testing.assert_allclose(ema1, d * p0 + (1 - d) * p1,
                               rtol=1e-6, atol=1e-7)
    assert not np.allclose(p1, ema1)      # ema lags the live params
    # BN buffers are averaged too (torchvision EMA use_buffers=True)
    s1 = jax.device_get(state.batch_stats["bn1"]["mean"])
    ema_s1 = jax.device_get(state.ema_params["batch_stats"]["bn1"]["mean"])
    np.testing.assert_allclose(ema_s1, d * s0 + (1 - d) * s1,
                               rtol=1e-6, atol=1e-7)


def test_restore_pre_ema_checkpoint_seeds_ema(tmp_path):
    """A checkpoint written before ema_params existed restores onto an
    EMA-enabled state (EMA seeded from the restored params) and onto a
    plain state (ema stays None)."""
    from tpudist import checkpoint as ckpt_lib
    from tpudist.models import create_model
    from tpudist.train import create_train_state

    cfg_off = Config(arch="resnet18", num_classes=3, image_size=32,
                     batch_size=8, use_amp=False, seed=0).finalize(1)
    model = create_model(cfg_off.arch, num_classes=3)
    old = create_train_state(jax.random.PRNGKey(1), model, cfg_off,
                             input_shape=(1, 32, 32, 3))
    ckpt = ckpt_lib.state_to_dict(old, cfg_off.arch, epoch=0, best_acc1=0.0)
    del ckpt["state"]["ema_params"]       # simulate a pre-EMA checkpoint

    cfg_on = Config(arch="resnet18", num_classes=3, image_size=32,
                    batch_size=8, use_amp=False, seed=2,
                    model_ema_decay=0.9).finalize(1)
    tpl = create_train_state(jax.random.PRNGKey(2), model, cfg_on,
                             input_shape=(1, 32, 32, 3))
    restored = ckpt_lib.restore_train_state(tpl, ckpt)
    np.testing.assert_array_equal(
        np.asarray(restored.ema_params["params"]["conv1"]["kernel"]),
        np.asarray(restored.params["conv1"]["kernel"]))
    np.testing.assert_array_equal(
        np.asarray(restored.ema_params["batch_stats"]["bn1"]["mean"]),
        np.asarray(restored.batch_stats["bn1"]["mean"]))

    tpl_off = create_train_state(jax.random.PRNGKey(3), model, cfg_off,
                                 input_shape=(1, 32, 32, 3))
    restored_off = ckpt_lib.restore_train_state(tpl_off, ckpt)
    assert restored_off.ema_params is None

    # New-code checkpoint with EMA OFF serializes ema_params as None: the
    # None value must be treated like a missing key when resuming with EMA.
    ckpt_none = ckpt_lib.state_to_dict(old, cfg_off.arch, epoch=0,
                                       best_acc1=0.0)
    assert ckpt_none["state"]["ema_params"] is None
    restored2 = ckpt_lib.restore_train_state(tpl, ckpt_none)
    np.testing.assert_array_equal(
        np.asarray(restored2.ema_params["params"]["conv1"]["kernel"]),
        np.asarray(restored2.params["conv1"]["kernel"]))

    # EMA-run checkpoint resumed WITHOUT the flag: stale EMA copy dropped.
    ema_state = create_train_state(jax.random.PRNGKey(4), model, cfg_on,
                                   input_shape=(1, 32, 32, 3))
    ckpt_ema = ckpt_lib.state_to_dict(ema_state, cfg_on.arch, epoch=0,
                                      best_acc1=0.0)
    restored3 = ckpt_lib.restore_train_state(tpl_off, ckpt_ema)
    assert restored3.ema_params is None


def test_synthetic_size_validation():
    with pytest.raises(ValueError, match="zero batches"):
        Config(synthetic=True, synthetic_size=100, batch_size=256).finalize(8)
    with pytest.raises(ValueError, match=">= 0"):
        Config(synthetic=True, synthetic_size=-1).finalize(8)
    cfg = Config(synthetic=True, synthetic_size=256, batch_size=256).finalize(8)
    assert cfg.synthetic_size == 256
    # validated against the device-ROUNDED global batch: 100/8 -> 96
    cfg = Config(synthetic=True, synthetic_size=98, batch_size=100).finalize(8)
    assert cfg.batch_size == 96 and cfg.synthetic_size == 98


def test_val_resize_validation():
    with pytest.raises(ValueError, match="val-resize"):
        Config(val_resize=200, image_size=224).finalize(1)
    with pytest.raises(ValueError, match="val-resize"):
        Config(val_resize=0, image_size=32).finalize(1)
    cfg = Config(val_resize=48, image_size=32).finalize(1)
    assert cfg.val_resize == 48


def test_flash_flag_validation(tmp_path):
    """--flash (config.py:flash): vit-only; 'on' composes with GSPMD TP
    since r5 (flash_attention_spmd nests a manual region over the ambient
    mesh)."""
    from tpudist.trainer import Trainer

    base = dict(num_classes=4, image_size=32, batch_size=16, use_amp=False,
                seed=0, synthetic=True, epochs=1, overwrite="delete")
    with pytest.raises(ValueError, match="--flash on sets the model.s field flash, which .resnet18."):
        Trainer(Config(arch="resnet18", flash="on",
                       outpath=str(tmp_path / "a"), **base), writer=None)
    # 'off' is a no-op for convnets (ADVICE r3): a scripted sweep passing a
    # uniform `--flash off` across resnet/vit archs must not crash.
    Trainer(Config(arch="resnet18", flash="off",
                   outpath=str(tmp_path / "a2"), **base), writer=None)
    # r5: --flash on composes with GSPMD TP (flash_attention_spmd nests a
    # manual region over the ambient mesh) — the r4 refusal is gone.
    tr_tp = Trainer(Config(arch="vit_b_16", flash="on",
                           mesh_shape=(4, 2), mesh_axes=("data", "model"),
                           outpath=str(tmp_path / "b"), **base), writer=None)
    assert tr_tp.model.flash is True
    # off on CPU == the auto default; the model must carry flash=False.
    tr = Trainer(Config(arch="vit_b_16", flash="off",
                        outpath=str(tmp_path / "c"), **base), writer=None)
    assert tr.model.flash is False


def test_flash_flag_value_and_seq_conflict(tmp_path):
    with pytest.raises(ValueError, match="auto\\|on\\|off"):
        Config(arch="vit_b_16", flash="true", synthetic=True).finalize(8)
    from tpudist.trainer import Trainer
    with pytest.raises(ValueError, match="sequence parallelism"):
        Trainer(Config(arch="vit_b_16", flash="on", num_classes=4,
                       image_size=32, batch_size=16, use_amp=False, seed=0,
                       synthetic=True, epochs=1, overwrite="delete",
                       mesh_shape=(2, 4), mesh_axes=("data", "seq"),
                       outpath=str(tmp_path / "s")), writer=None)
