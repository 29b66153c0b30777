"""Layers that run several times (models/decoder.py::_looped; docs/LOOPED.md):
dense layers under sandwich norms run `loop_steps` times over the same
leaves, a head and an exit gate after every pass, the loss an expectation
over the exit step.

CPU, `ouro_tiny` (hidden 64, 4 heads over 4 of 16, a dense width of 96, 3
layers run 4 times, 256 ids), seeded random weights. The plain reference is
the benchmark's, imported by path: it imports nothing of the program.
"""

import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import ouro_2_6b, ouro_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_ouro_for_tests", os.path.join(
            CHIP, "refs", "ouro_2_6b_pp8.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
LAYERS, PASSES = 3, 4


def tiny_cfg(**changed):
    """The tiny twin's sizes as the reference reads a configuration."""
    return dict(dict(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, num_hidden_layers=LAYERS, vocab_size=256,
        intermediate_size=96, total_ut_steps=PASSES, exit_beta=0.1,
        rope_theta=1000000, rms_norm_eps=1e-6, reference_block_rows=16,
        embedding_std=1.0, adam_b1=0.9, adam_b2=0.95, adam_eps=1e-8,
        weight_decay=0.1, decay_min_ndim=2), **changed)


def tokens(t, rows=2, vocab=256, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0,
                             vocab)
    return ids[:, :-1], ids[:, 1:]


def leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def scored(model, params, x, y):
    return model.apply({"params": params}, x, train=True, targets=y)


def rel_gap(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


# --- the model against the reference ----------------------------------------

@pytest.mark.parametrize("t,flash", [(32, False), (37, True), (48, True)])
def test_loss_and_every_gradient_leaf_match_the_reference(t, flash):
    cfg = tiny_cfg()
    params, _ = REF.init(jax.random.PRNGKey(0), cfg)
    model = ouro_tiny(dtype=jnp.float32, flash=flash, remat=flash)
    x, y = tokens(t)
    ours = model.init(jax.random.PRNGKey(0), model.example_input())["params"]
    assert [(jax.tree_util.keystr(k), v.shape, v.dtype)
            for k, v in leaves(ours)] == [
        (jax.tree_util.keystr(k), v.shape, v.dtype)
        for k, v in leaves(params)]
    with jax.default_matmul_precision("highest"):
        (loss, out), grads = jax.value_and_grad(
            lambda p: (lambda o: (o.loss, o))(scored(model, p, x, y)),
            has_aux=True)(params)
        (want, (exit_step, entropy, _)), want_grads = jax.value_and_grad(
            REF.loss_fn, has_aux=True)(params, x, y, cfg)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        assert rel_gap(g, w) < 2e-4, jax.tree_util.keystr(path)
    assert float(out.counters["loop_expected_exit"]) == pytest.approx(
        float(exit_step), rel=1e-5)
    assert float(out.counters["loop_exit_entropy"]) == pytest.approx(
        float(entropy), rel=1e-5)


def test_three_adamw_steps_are_the_references(mesh8):
    """Through `create_train_state` and `make_train_step`, the path a cell
    runs: three steps' losses are the reference's, AdamW's first moment
    after one step is (1 - b1) times the reference's gradient, leaf for
    leaf, and the parameters after three moved as the reference's did."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = Config(arch="ouro_tiny", batch_size=8, seq_len=32,
                 optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                 use_amp=False, seed=0).finalize(8)
    ref_cfg = tiny_cfg()
    model = ouro_tiny(dtype=jnp.float32)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    params, _ = REF.init(jax.random.PRNGKey(3), ref_cfg)
    state = state.replace(params=params)
    step = make_train_step(mesh8, model, cfg)
    theirs, opt = params, REF.init_opt(params)
    for i in range(3):
        x, y = tokens(32, rows=8, seed=10 + i)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, x, y, jnp.float32(1e-3))
        want, want_grads, theirs, _, opt = REF.step(
            theirs, {}, opt, x, y, ref_cfg, 1e-3)
        assert abs(float(metrics["loss"]) - float(want)) < 2e-5 * float(want)
        if i == 0:
            mu = [leaf for path, leaf in leaves(state.opt_state)
                  if any(getattr(k, "name", None) == "mu" for k in path)]
            for m, (path, w) in zip(mu, leaves(want_grads)):
                assert rel_gap(m / 0.1, w) < 5e-4, jax.tree_util.keystr(path)
            assert 1.0 <= float(metrics["loop_expected_exit"]) <= PASSES
            assert 0.0 <= float(metrics["loop_exit_entropy"]) <= np.log(PASSES)
    for (path, ours), (_, want), (_, first) in zip(
            leaves(state.params), leaves(theirs), leaves(params)):
        assert rel_gap(ours - first, want - first) < 2e-3, \
            jax.tree_util.keystr(path)


# --- what is tied ------------------------------------------------------------

def test_a_tied_layers_gradient_is_the_sum_of_its_four_copies():
    """The same values held by an untied model of `4 N` layers (the
    reference walking `layer_<t N + l>` in pass `t`): the loss is the same
    number, and the looped model's gradient of `layer_l` is the sum over
    the passes of the untied copies' gradients."""
    cfg = tiny_cfg()
    params, _ = REF.init(jax.random.PRNGKey(5), cfg)
    untied = dict(params)
    for t in range(PASSES):
        for i in range(LAYERS):
            untied[f"layer_{t * LAYERS + i}"] = params[f"layer_{i}"]
    x, y = tokens(32)

    def untied_loss(p):
        return REF.loss_fn(
            p, x, y, cfg,
            layer_params=lambda p, t, i: p[f"layer_{t * LAYERS + i}"])[0]

    model = ouro_tiny(dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: scored(model, p, x, y).loss)(params)
        want, copies = jax.value_and_grad(untied_loss)(untied)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for i in range(LAYERS):
        summed = jax.tree_util.tree_map(
            lambda *g: sum(g), *[copies[f"layer_{t * LAYERS + i}"]
                                 for t in range(PASSES)])
        one_pass = copies[f"layer_{i}"]
        for (path, g), (_, w), (_, first) in zip(
                leaves(grads[f"layer_{i}"]), leaves(summed),
                leaves(one_pass)):
            name = f"layer_{i}" + jax.tree_util.keystr(path)
            assert rel_gap(g, w) < 2e-4, name
            # ... and is not one copy's: the passes do add up
            assert rel_gap(g, first) > 1e-2, name
    # the head, the gate and the final norm are read once a pass too
    for name in ("head", "exit_gate", "norm", "embed"):
        for (path, g), (_, w) in zip(leaves(grads[name]),
                                     leaves(copies[name])):
            assert rel_gap(g, w) < 2e-4, name


# --- the exit distribution ---------------------------------------------------

def test_the_exit_distribution_sums_to_one_at_every_position():
    leave = [jax.random.uniform(jax.random.PRNGKey(i), (2, 32))
             for i in range(PASSES)]
    leave[1] = leave[1].at[0, :4].set(1.0)      # a saturated gate: S = 0
    leave[0] = leave[0].at[1, :4].set(0.0)
    p = REF.exit_distribution(leave)
    assert len(p) == PASSES
    np.testing.assert_allclose(sum(p), 1.0, rtol=0, atol=1e-6)
    assert all(float(jnp.min(q)) >= 0.0 for q in p)
    assert float(jnp.max(p[2][0, :4])) == 0.0
    # the program's distribution is the reference's: its counters are two
    # moments of it (checked against the reference's above), and its loss
    # at beta = 0 with one head is linear in it


def test_with_the_gate_shut_the_loss_is_the_last_passs_cross_entropy():
    """`lambda = 0` everywhere (the gate's weight 0, its bias far below 0):
    `p = (0, 0, 0, 1)`, the entropy is 0, and the loss is the mean cross
    entropy of the last pass's logits, whatever `exit_beta`."""
    cfg = tiny_cfg()
    params, _ = REF.init(jax.random.PRNGKey(2), cfg)
    params["exit_gate"] = {"kernel": jnp.zeros((64, 1)),
                           "bias": jnp.full((1,), -200.0)}
    model = ouro_tiny(dtype=jnp.float32)
    x, y = tokens(32)
    out = scored(model, params, x, y)
    logits = model.apply({"params": params}, x)            # the last pass's
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0])
    assert float(out.loss) == pytest.approx(float(jnp.mean(nll)), rel=1e-6)
    assert float(out.counters["loop_expected_exit"]) == PASSES
    assert float(out.counters["loop_exit_entropy"]) == 0.0
    assert float(out.acc1) == pytest.approx(100.0 * float(jnp.mean(
        jnp.argmax(logits, axis=-1) == y)))
    # every gradient is finite where p is exactly 0 (0 log 0 = 0)
    grads = jax.grad(lambda p: scored(model, p, x, y).loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(g))) for _, g in leaves(grads))
    # wide open (lambda = 1): everything leaves after the first pass
    params["exit_gate"]["bias"] = jnp.full((1,), 200.0)
    out = scored(model, params, x, y)
    assert float(out.counters["loop_expected_exit"]) == 1.0
    grads = jax.grad(lambda p: scored(model, p, x, y).loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(g))) for _, g in leaves(grads))


def test_the_loss_weights_are_differentiated():
    """`lm_head_loss(weights=p_t)`: the gate's gradient reaches the loss
    through `p` alone, so it is zero exactly where the weights are held
    constant and is not where they are not."""
    from tpudist.ops.loss import lm_head_loss
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 16))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (16, 64)) * 0.1
    _, y = tokens(32, vocab=64)
    w = jax.random.uniform(jax.random.PRNGKey(2), (2, 32))
    grad = jax.grad(lambda w: lm_head_loss(h, kernel, y, 16, weights=w)[0])(w)
    logits = h @ kernel
    nll = (jax.nn.logsumexp(logits, axis=-1)
           - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0])
    np.testing.assert_allclose(grad, nll / w.size, rtol=1e-5)


@pytest.mark.parametrize("fault", [None, "last_pass_gated", "no_entropy",
                                   "first_pass_only"])
def test_a_wrong_exit_loss_fails_the_comparison(fault, monkeypatch):
    """The comparison that decides `correct` (`harness/check.py::compare`
    under the tiny twin's limits, one of them over the gate's own gradient)
    passes the program against the reference, and fails it against a
    reference whose last pass keeps the gate's share only (`p` no longer
    sums to one), that drops the entropy term, or that scores the first
    pass alone: each is a different training run."""
    sys.path.insert(0, CHIP)
    try:
        from harness import check
    finally:
        sys.path.remove(CHIP)
    tiny = json.load(open(os.path.join(CHIP, "selftest", "tiny",
                                       "ouro_tiny.json")))
    cfg = tiny_cfg()
    params, _ = REF.init(jax.random.PRNGKey(0), cfg)
    model = ouro_tiny(dtype=jnp.float32)
    x, y = tokens(32)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda p: scored(model, p, x, y).loss)(params)
    sound = REF.exit_distribution
    if fault == "last_pass_gated":
        monkeypatch.setattr(REF, "exit_distribution", lambda leave: (
            lambda p: p[:-1] + [p[-1] * leave[-1]])(sound(leave)))
    elif fault == "no_entropy":
        cfg = tiny_cfg(exit_beta=0.0)
    elif fault == "first_pass_only":
        monkeypatch.setattr(REF, "exit_distribution", lambda leave: (
            [jnp.ones_like(leave[0])]
            + [jnp.zeros_like(leave[0])] * (len(leave) - 1)))
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = jax.value_and_grad(
            REF.loss_fn, has_aux=True)(params, x, y, cfg)

    def readings(loss, grads):
        flat = [np.asarray(g) for g in jax.tree_util.tree_leaves(grads)]
        return {"loss": [float(loss)], "first_grad_leaves": flat,
                "first_grad": check._norms(flat)}
    names = {k: check.leaf_names(params)
             for k in ("first_grad", "param_change")}
    limits = {k: v for k, v in tiny["correct_limits"].items()
              if not k.startswith("param_change")}
    correct, rows = check.compare(readings(loss, grads),
                                  readings(want, want_grads), limits, names)
    assert correct is (fault is None), [r for r in rows if not r[3]]
    if fault == "first_pass_only":
        # the gate's gradient is then zero: only its own limit tells
        assert any(r[0] == "head_grad_rel_diff" and not r[3] for r in rows)


# --- the trainer, the checkpoint, the compiled step --------------------------

def test_python_m_tpudist_trains_a_looped_model(tmp_path):
    """The normal entry point's path (`config.from_args` -> `Trainer.fit`)
    on the tiny twin: the loss falls, the passes, the gate and beta come
    with the registered model (there is no flag for them), the counters
    reach the drain, and a checkpoint brings the gate back."""
    from tpudist import telemetry
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    argv = ["--synthetic", "-a", "ouro_tiny", "--seq-len", "32", "-b", "16",
            "--layers", "3", "--epochs", "2", "--step", "5", "--optimizer",
            "adamw", "--lr", "0.01", "--wd", "0.1", "--adam-b2", "0.95",
            "--flash", "off", "-j", "2", "-p", "2", "--no-telemetry",
            "--outpath", str(tmp_path / "out"), "--overwrite", "delete",
            "--seed", "0"]
    cfg = from_args(argv)
    assert not [f for f in vars(cfg) if re.search("loop|exit|beta", f)]
    seen = len(telemetry.counters().get("loop_expected_exit", []))
    trainer = Trainer(cfg, writer=None)
    model = trainer.model
    assert (model.loop_steps, model.exit_beta, model.dense_width) == (
        PASSES, 0.1, 96)
    assert trainer.flash_decision["kernel"] == "xla"
    assert "_t32_h4_d16_bfloat16_train_causal" in trainer.flash_decision["key"]
    assert set(trainer.state.params) == {
        "embed", "exit_gate", "head", "norm", "layer_0", "layer_1", "layer_2"}
    assert trainer.state.params["exit_gate"]["kernel"].shape == (64, 1)
    trainer.fit()
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    losses = [float(x) for x in re.findall(
        r"\|\|==> Train: Epoch\[\d+\]\s+Loss ([0-9.e+-]+)", log)]
    assert len(losses) == 2 and losses[1] < losses[0] < np.log(256) + 0.5
    exits = telemetry.counters().get("loop_expected_exit", [])[seen:]
    assert len(exits) >= 4 and all(1.0 <= e <= PASSES for e in exits)
    # the gate moved, and a restored trainer holds what was saved
    gate = jax.device_get(trainer.state.params["exit_gate"])
    assert float(np.abs(gate["bias"]).max()) > 0.0
    resumed = Trainer(from_args(
        argv[:-4] + ["--overwrite", "keep", "--resume", os.path.join(
            cfg.outpath, "checkpoint.msgpack"), "--seed", "0"]), writer=None)
    for (path, a), (_, b) in zip(leaves(resumed.state.params),
                                 leaves(trainer.state.params)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def test_the_compiled_step_holds_one_passs_layers(mesh8):
    """The stack's body is traced once and run four times: with the
    streaming kernel on, the lowered step holds `N` attention calls a
    direction (forward, dQ, dKV: `3 N`), not `4 N`, and one loop over the
    passes."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = Config(arch="ouro_tiny", batch_size=8, seq_len=128,
                 optimizer="adamw", use_amp=True, amp_dtype="bfloat16",
                 seed=0).finalize(8)
    model = ouro_tiny(dtype=jnp.bfloat16, flash=True, remat=True)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    x, y = tokens(128, rows=8)
    jaxpr = jax.make_jaxpr(make_train_step(mesh8, model, cfg))(
        state, x, y, jnp.float32(1e-3))

    def walk(jaxpr, calls, loops):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn.params["jaxpr"].debug_info.func_name)
            loops += eqn.primitive.name == "scan" and eqn.params[
                "length"] == PASSES
            for sub in jax.core.jaxprs_in_params(eqn.params):
                loops = walk(sub, calls, loops)
        return loops

    calls = []
    loops = walk(jaxpr.jaxpr, calls, 0)
    by_kernel = {k: calls.count(k) for k in sorted(set(calls))}
    # one pass's layers: forward, dQ and dKV once each (the rematerialised
    # layer reads the forward's saved results: no second forward call)
    assert by_kernel == {"_bwd_dkv_kernel": LAYERS, "_bwd_dq_kernel": LAYERS,
                         "_flash_kernel": LAYERS}, by_kernel
    assert loops == 2                   # the passes, forward and backward


def test_a_looped_model_states_dense_layers_and_the_next_id():
    from tpudist.models.decoder import mellum2_tiny
    x, y = tokens(32, vocab=64)
    looped_experts = mellum2_tiny(dtype=jnp.float32, loop_steps=2)
    with pytest.raises(ValueError, match="dense layers trained on the next"):
        looped_experts.init(jax.random.PRNGKey(0), x)


def test_the_configurations_file_keeps_every_published_number():
    """`configs/ouro_2_6b_pp8.json` against the registered model and the
    catalog's entry (a copy: the guide is not in the repo): every number
    but the depth, which is the one key in `reduced`."""
    cfg = json.load(open(os.path.join(CHIP, "configs",
                                      "ouro_2_6b_pp8.json")))
    model = ouro_2_6b()
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    assert cfg["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) == (
        6, 48)
    assert cfg["layer_types"] == ["full_attention"] * 6
    for key, value in dict(
            hidden_size=model.hidden_size, head_dim=model.head_dim,
            num_attention_heads=model.num_heads,
            num_key_value_heads=model.num_kv_heads,
            intermediate_size=model.dense_width, vocab_size=model.vocab_size,
            num_hidden_layers_published=model.num_layers,
            total_ut_steps=model.loop_steps, exit_beta=model.exit_beta,
            rms_norm_eps=model.rms_norm_eps).items():
        assert cfg[key] == value, key
    rope_p = model.rope_parameters["full_attention"]
    assert (rope_p["rope_type"], rope_p["rope_theta"]) == (
        "default", cfg["rope_theta"])
    assert not model.qk_norm and model.num_experts == 0
    assert cfg["arch"] in cfg["trainer_argv"]
    assert "8 stages of 6 layers" in cfg["deployment"]
    assert cfg["correct_limits"]["head_leaves"] == "['exit_gate']"
    assert len(cfg["correct_limits_why"]) > 200
    for said in ("exit_beta", "final norm inside the loop", "exit gate",
                 "exit distribution", "q and k norm", "sandwich norms",
                 "optimizer", "weights"):
        assert len(cfg["assumed"][said]) > 40, said
    # 509,661,185 parameters at this share
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert 6 * layer + 2 * 49152 * 2048 + 2048 + 2049 == 509661185
