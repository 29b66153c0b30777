"""A multi-token-prediction module behind the kept layers
(models/decoder.py::MTPModule; docs/MTP.md): it reads the NEXT id's embedding
beside the trunk's last hidden state, runs one more block and the model's own
head, and is trained on the id after the next; the loss is `L_main + lambda
L_mtp`.

CPU, `joyai_tiny` (hidden 64; latent attention; one dense layer, two of 16
experts beside a shared one; the module; 256 ids), seeded random weights. The
plain reference is the benchmark's, imported by path: it imports nothing of
the program.
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

from tpudist.models.decoder import joyai_llm_flash, joyai_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def _reference():
    spec = importlib.util.spec_from_file_location(
        "chipbench_ref_joyai_for_tests", os.path.join(
            CHIP, "refs", "joyai_flash_ep16.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
LAMBDA = 0.3


def tiny_cfg(**changed):
    """The tiny twin's sizes as the reference reads a configuration."""
    with open(os.path.join(CHIP, "selftest", "tiny", "joyai_tiny.json")) as f:
        return dict(json.load(f), **changed)


def tokens(t, rows=2, vocab=256, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0,
                             vocab)
    return ids[:, :-1], ids[:, 1:]


def leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def rel_gap(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-12))


def ours(model, params, stats, x, y):
    """((loss, Scored), gradients) of the program."""
    def f(p):
        out = model.apply({"params": p, "batch_stats": stats}, x, train=True,
                          targets=y)
        return out.loss, out
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(f, has_aux=True))(params)


def theirs(params, stats, x, y, cfg, wrong=None):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: REF.loss_fn(p, stats, x, y, cfg, None, wrong),
            has_aux=True))(params)


# --- the model against the reference ----------------------------------------

@pytest.mark.parametrize("t,flash", [(32, False), (37, True), (48, True)])
def test_both_losses_and_every_gradient_leaf_match_the_reference(t, flash):
    cfg = tiny_cfg()
    params, stats = REF.init(jax.random.PRNGKey(0), cfg)
    model = joyai_tiny(dtype=jnp.float32, flash=flash, remat=flash)
    mine = jax.jit(lambda k: model.init(k, model.example_input()))(
        jax.random.PRNGKey(0))
    for ours_, refs in ((mine["params"], params),
                        (mine["batch_stats"], stats)):
        assert [(jax.tree_util.keystr(k), v.shape, v.dtype)
                for k, v in leaves(ours_)] == [
            (jax.tree_util.keystr(k), v.shape, v.dtype)
            for k, v in leaves(refs)]
    x, y = tokens(t)
    (loss, out), grads = ours(model, params, stats, x, y)
    (want, (main, mtp, _)), want_grads = theirs(params, stats, x, y, cfg)
    assert float(out.counters["lm_loss_main"]) == pytest.approx(
        float(main), rel=1e-5)
    assert float(out.counters["mtp_loss"]) == pytest.approx(
        float(mtp), rel=1e-5)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    assert float(loss) == pytest.approx(
        float(main) + LAMBDA * float(mtp), rel=1e-5)
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        assert rel_gap(g, w) < 2e-4, jax.tree_util.keystr(path)
    # the module's block has counters under its own layer name
    assert {"moe_pairs.mtp", "moe_pairs.layer_1", "moe_pairs.layer_2"} <= set(
        out.counters)
    assert "moe_pairs.layer_0" not in out.counters      # the dense layer


def test_three_adamw_steps_are_the_references(mesh8):
    """Through `create_train_state` and `make_train_step`, the path a cell
    runs: three steps' losses are the reference's, AdamW's first moment
    after one step is (1 - b1) times the reference's gradient, leaf for
    leaf, and the parameters after three moved as the reference's did."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = Config(arch="joyai_tiny", batch_size=8, seq_len=32,
                 optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                 use_amp=False, seed=0).finalize(8)
    ref_cfg = tiny_cfg()
    model = joyai_tiny(dtype=jnp.float32)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    params, stats = REF.init(jax.random.PRNGKey(3), ref_cfg)
    state = state.replace(params=params, batch_stats=stats)
    step = make_train_step(mesh8, model, cfg)
    theirs_, opt = params, REF.init_opt(params)
    for i in range(3):
        x, y = tokens(32, rows=8, seed=10 + i)
        with jax.default_matmul_precision("highest"):
            state, metrics = step(state, x, y, jnp.float32(1e-3))
        want, want_grads, theirs_, _, opt = REF.step(
            theirs_, stats, opt, x, y, ref_cfg, 1e-3)
        assert abs(float(metrics["loss"]) - float(want)) < 2e-5 * float(want)
        if i == 0:
            mu = [leaf for path, leaf in leaves(state.opt_state)
                  if any(getattr(k, "name", None) == "mu" for k in path)]
            for m, (path, w) in zip(mu, leaves(want_grads)):
                assert rel_gap(m / 0.1, w) < 5e-4, jax.tree_util.keystr(path)
            assert 0.5 < float(metrics["mtp_loss"]) / float(
                metrics["lm_loss_main"]) < 2.0
    for (path, mine), (_, want), (_, first) in zip(
            leaves(state.params), leaves(theirs_), leaves(params)):
        assert rel_gap(mine - first, want - first) < 2e-3, \
            jax.tree_util.keystr(path)
    # the correction biases are no optimizer's: as they were
    for (_, a), (_, b) in zip(leaves(state.batch_stats), leaves(stats)):
        np.testing.assert_array_equal(a, b)


# --- what the second loss reaches --------------------------------------------

def test_without_its_weight_the_module_learns_nothing():
    """`lambda` 0: the trunk's gradients are the main loss's alone (the
    reference's at weight 0) and every leaf of the module gets none."""
    cfg = tiny_cfg(mtp_loss_weight=0.0)
    params, stats = REF.init(jax.random.PRNGKey(0), cfg)
    x, y = tokens(32)
    (loss, out), grads = ours(
        joyai_tiny(dtype=jnp.float32).clone(mtp_weight=0.0), params, stats,
        x, y)
    (want, (main, _, _)), want_grads = theirs(params, stats, x, y, cfg)
    assert float(loss) == pytest.approx(float(main), rel=1e-6)
    assert float(out.counters["mtp_loss"]) > 1.0       # taken, not weighed
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        if name.startswith("['mtp']"):
            assert float(jnp.max(jnp.abs(g))) == 0.0, name
        else:
            assert rel_gap(g, w) < 2e-4, name


def test_the_shared_leaves_get_gradients_from_both_uses():
    """The embedding's and the head's gradients are the sum of what the
    main loss and `lambda` times the second give them: linear in `lambda`,
    and the second's part is not zero (the module reads the embedding of the
    next id and ends in the head); the module's own leaves get the second's
    alone."""
    params, stats = REF.init(jax.random.PRNGKey(0), tiny_cfg())
    x, y = tokens(32)
    g = {w: ours(joyai_tiny(dtype=jnp.float32).clone(mtp_weight=w), params,
                 stats, x, y)[1] for w in (0.0, LAMBDA, 1.0)}
    for name in ("embed", "head"):
        main, = jax.tree_util.tree_leaves(g[0.0][name])
        both, = jax.tree_util.tree_leaves(g[LAMBDA][name])
        second = jax.tree_util.tree_leaves(g[1.0][name])[0] - main
        assert rel_gap(both, main + LAMBDA * second) < 1e-5, name
        assert rel_gap(both, main) > 1e-2, name
    eh = g[LAMBDA]["mtp"]["eh_proj"]["kernel"]
    assert rel_gap(eh, LAMBDA * g[1.0]["mtp"]["eh_proj"]["kernel"]) < 1e-5


@pytest.mark.parametrize("wrong", [None, "shift_one", "last_weighted",
                                   "embed_x"])
def test_a_wrong_second_loss_fails_the_cells_comparison(wrong):
    """The numbers the harness compares (`harness/check.py::compare`: the
    loss, the first gradient over all leaves and over `eh_proj`), under the
    tiny twin's limits: the sound program passes; targets shifted by one
    instead of two, the last position weighted, or the embedding of `x`
    instead of `y` each fail, the last position's weight by `eh_proj`'s
    gradient ALONE: at 64 positions it moves the loss by 6e-5 and the
    gradient over all leaves by 0.05, inside their limits, and the leaf that
    the second loss alone reaches by 0.14 (2.7 x as far: nothing of the main
    loss dilutes it)."""
    sys.path.insert(0, CHIP)
    try:
        from harness import check
    finally:
        sys.path.remove(CHIP)
    tiny = tiny_cfg()
    params, stats = REF.init(jax.random.PRNGKey(0), tiny)
    x, y = tokens(64)
    (loss, _), grads = ours(joyai_tiny(dtype=jnp.float32), params, stats, x,
                            y)
    (want, _), want_grads = theirs(params, stats, x, y, tiny, wrong)

    def readings(loss, grads):
        flat = [np.asarray(g) for g in jax.tree_util.tree_leaves(grads)]
        return {"loss": [float(loss)], "first_grad_leaves": flat,
                "first_grad": check._norms(flat)}
    names = {"first_grad": check.leaf_names(params)}
    limits = {k: v for k, v in tiny["correct_limits"].items()
              if not k.startswith("param_change")}
    assert limits["head_leaves"] == "['mtp']['eh_proj']"
    correct, rows = check.compare(readings(loss, grads),
                                  readings(want, want_grads), limits, names)
    assert correct is (wrong is None), [r for r in rows if not r[3]]
    if wrong == "last_weighted":
        assert [r[0] for r in rows if not r[3]] == ["head_grad_rel_diff"]


def test_a_module_states_one_depth_and_the_next_id():
    from tpudist.models.decoder import sdar_tiny
    x, _ = tokens(32)
    with pytest.raises(ValueError, match="one module"):
        joyai_tiny(dtype=jnp.float32).clone(mtp_depth=2).init(
            jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="trained on the next id"):
        sdar_tiny(dtype=jnp.float32).clone(mtp_depth=1).init(
            jax.random.PRNGKey(0), x)


# --- the trainer, the checkpoint ---------------------------------------------

def test_python_m_tpudist_trains_saves_and_restores(tmp_path):
    """The normal entry point's path (`config.from_args` -> `Trainer.fit`)
    on the tiny twin: the loss falls, latent attention, the dense first
    layer, the module and its weight come with the registered model (there
    is no flag or `Config` field for them), both losses reach the drain, and
    a checkpoint brings the module back."""
    from tpudist import telemetry
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    argv = ["--synthetic", "-a", "joyai_tiny", "--seq-len", "32", "-b", "16",
            "--layers", "3", "--epochs", "2", "--step", "5", "--optimizer",
            "adamw", "--lr", "0.01", "--wd", "0.1", "--adam-b2", "0.95",
            "--flash", "off", "-j", "2", "-p", "2", "--no-telemetry",
            "--outpath", str(tmp_path / "out"), "--overwrite", "delete",
            "--seed", "0"]
    cfg = from_args(argv)
    assert not [f for f in vars(cfg)
                if re.search("mtp|latent|lora|nextn|dense", f)]
    seen = len(telemetry.counters().get("mtp_loss", []))
    trainer = Trainer(cfg, writer=None)
    model = trainer.model
    assert (model.mtp_depth, model.mtp_weight, model.leading_dense) == (
        1, LAMBDA, (1, 96))
    assert model.latent["kv_rank"] == 32
    assert trainer.flash_decision["kernel"] == "xla"
    assert "_t32_h4_d24_bfloat16_train_causal" in trainer.flash_decision["key"]
    assert set(trainer.state.params) == {
        "embed", "head", "norm", "mtp", "layer_0", "layer_1", "layer_2"}
    assert set(trainer.state.params["mtp"]) == {
        "enorm", "hnorm", "eh_proj", "block", "norm"}
    assert "mlp" in trainer.state.params["layer_0"]
    assert "moe" in trainer.state.params["layer_1"]
    first = jax.device_get(trainer.state.params["mtp"]["eh_proj"]["kernel"])
    trainer.fit()
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    losses = [float(x) for x in re.findall(
        r"\|\|==> Train: Epoch\[\d+\]\s+Loss ([0-9.e+-]+)", log)]
    # the sum of two cross entropies over 256 ids, the second at 0.3
    assert len(losses) == 2 and losses[1] < losses[0] < 1.3 * np.log(
        256) + 0.5
    second = telemetry.counters().get("mtp_loss", [])[seen:]
    assert len(second) >= 4 and second[-1] < second[0]
    # the module moved, and a restored trainer holds what was saved
    moved = jax.device_get(trainer.state.params["mtp"]["eh_proj"]["kernel"])
    assert float(np.abs(moved - first).max()) > 0.0
    resumed = Trainer(from_args(
        argv[:-4] + ["--overwrite", "keep", "--resume", os.path.join(
            cfg.outpath, "checkpoint.msgpack"), "--seed", "0"]), writer=None)
    for (path, a), (_, b) in zip(leaves(resumed.state.params),
                                 leaves(trainer.state.params)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))
    for (path, a), (_, b) in zip(leaves(resumed.state.batch_stats),
                                 leaves(trainer.state.batch_stats)):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


def test_a_share_keeps_the_module_whatever_the_depth():
    """`--layers` cuts the trunk; the module comes with the model."""
    model = joyai_tiny(dtype=jnp.float32, layers=1)
    shapes = jax.eval_shape(lambda k: model.init(k, model.example_input()),
                            jax.random.PRNGKey(0))
    assert set(shapes["params"]) == {"embed", "head", "norm", "mtp",
                                     "layer_0"}
    assert [w["seq"] for w in model.attention_workloads(32)] == [32]


def test_the_configurations_file_keeps_every_published_number():
    """`configs/joyai_flash_ep16.json` against the registered model and the
    catalog's entry (a copy: the guide is not in the repo): every number but
    the depth, the experts held and the vocabulary, the keys in
    `reduced`."""
    cfg = json.load(open(os.path.join(CHIP, "configs",
                                      "joyai_flash_ep16.json")))
    model = joyai_llm_flash()
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    for key, value in published.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) == (
        5, 40)
    assert (cfg["vocab_size"], cfg["vocab_size_published"],
            cfg["vocab_share"]) == (16160, 129280, "0 of 8")
    assert (cfg["num_experts_held"], cfg["expert_share"]) == (16, "0 of 16")
    latent = model.latent
    for key, value in dict(
            hidden_size=model.hidden_size, num_attention_heads=model.num_heads,
            num_key_value_heads=model.num_kv_heads,
            qk_head_dim=model.head_dim, q_lora_rank=latent["q_rank"],
            kv_lora_rank=latent["kv_rank"],
            qk_nope_head_dim=latent["nope_dim"],
            qk_rope_head_dim=latent["rope_dim"], v_head_dim=latent["v_dim"],
            n_routed_experts=model.num_experts,
            num_experts_per_tok=model.experts_per_token,
            moe_intermediate_size=model.expert_width,
            routed_scaling_factor=model.routed_scaling,
            vocab_size_published=model.vocab_size,
            num_hidden_layers_published=model.num_layers,
            num_nextn_predict_layers=model.mtp_depth,
            mtp_loss_weight=model.mtp_weight,
            rms_norm_eps=model.rms_norm_eps).items():
        assert cfg[key] == value, key
    assert model.leading_dense == (cfg["first_k_dense_replace"],
                                   cfg["intermediate_size"])
    assert model.shared_width == cfg["n_shared_experts"] * cfg[
        "moe_intermediate_size"]
    assert (model.router, model.expert_act) == ("sigmoid", "swiglu")
    rope_p = model.rope_parameters["full_attention"]
    assert (rope_p["rope_type"], rope_p["rope_theta"]) == (
        "default", cfg["rope_theta"])
    argv = cfg["trainer_argv"]
    assert cfg["arch"] == argv[argv.index("-a") + 1] == "joyai_llm_flash"
    for flag, value in (("--layers", "5"), ("--expert-share", "0/16"),
                        ("--vocab-share", "0/8"), ("--flash", "on")):
        assert argv[argv.index(flag) + 1] == value
    assert not [a for a in argv if re.search("mtp|latent|lora", str(a))]
    for said in ("16 chips a layer", "vocabulary over 8", "35 layers",
                 "MTP module"):
        assert said in cfg["deployment"], said
    assert cfg["correct_limits"]["head_leaves"] == "['mtp']['eh_proj']"
    assert len(cfg["correct_limits_why"]) > 200
    for said in ("mtp_loss_weight", "MTP module", "MTP depth of h_L",
                 "MTP loss", "softmax scale", "rotation", "correction bias",
                 "optimizer", "weights"):
        assert len(cfg["assumed"][said]) > 40, said
    # the share's parameters, by module, as the file states them
    mla = 2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    expert = 3 * 2048 * 768
    layer = mla + 17 * expert + 2048 * 256 + 2 * 2048
    assert mla == 26347520 and layer == 107091968
    dense = mla + 3 * 2048 * 7168 + 2 * 2048
    mtp = layer + 2 * 2048 * 2048 + 3 * 2048
    assert dense + 4 * layer + mtp + 2 * 16160 * 2048 + 2048 == cfg[
        "parameters_held"] == 680439808
