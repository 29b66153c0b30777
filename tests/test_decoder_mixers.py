"""The decoder's blocks of one mixer each (models/decoder.py: Mamba-2 by the
chunked scan of ops/ssd.py, sigmoid-routed ``relu^2`` experts beside a shared
one, attention without rotation at a group of sixteen) on ``nemotron3_tiny``,
the CPU twin of ``nemotron3_nano_30b_a3b``, against the benchmark's plain
reference (imported by path: it imports nothing of the program). A file of
its own beside ``test_decoder.py`` so that the two run on two workers.
"""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.decoder import (LAYER_KINDS, NEMOTRON3_NANO_PATTERN,
                                    layer_types_of, nemotron3_nano_30b_a3b,
                                    nemotron3_tiny)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_ref_{name}_for_mixer_tests", os.path.join(
            CHIP, "refs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF_NEMOTRON = _reference("nemotron3_nano_ep16")


def tokens(t, rows=2, vocab=64, seed=1):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (rows, t + 1), 0,
                             vocab)
    return ids[:, :-1], ids[:, 1:]


def leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def nemotron_cfg(held=4, share=1, layers=5, vocab=64):
    """The tiny twin's sizes as the reference reads a configuration."""
    return dict(
        hidden_size=64, num_hidden_layers=layers,
        hybrid_override_pattern="MEM*E", vocab_size=vocab,
        layer_norm_epsilon=1e-5, reference_block_rows=8, mamba_num_heads=8,
        mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
        chunk_size=8, num_attention_heads=16, num_key_value_heads=1,
        head_dim=16, n_routed_experts=16, num_experts_per_tok=2,
        num_experts_held=held, expert_share=f"{share} of {16 // held}",
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
        routed_scaling_factor=2.5, time_step_min=0.001, time_step_max=0.1,
        time_step_floor=1e-4, rescale_prenorm_residual=True,
        num_hidden_layers_published=5, embedding_std=1.0, adam_b1=0.9,
        adam_b2=0.95, adam_eps=1e-8, weight_decay=0.1, decay_min_ndim=2,
        # the correction biases are the balancing rule's on a probe row,
        # not zeros: they move choices, and must move no weight
        router_balance=dict(probe_tokens=64, turns=60, rate=0.05,
                            shrink=0.95))


def nemotron_model(held=4, share=1, layers=5, **kw):
    return nemotron3_tiny(dtype=jnp.float32, layers=layers,
                          expert_share=(share, 16 // held),
                          vocab_share=(0, 4), **kw)


def _tree(tree):
    return [(jax.tree_util.keystr(k), v.shape, v.dtype)
            for k, v in leaves(tree)]


# a length the chunk of 8 divides and one it does not (padded on the right
# inside the mixer and cut after it); the streaming kernel with its group of
# sixteen split over two programs, rematerialised, and the XLA path
@pytest.mark.parametrize("t,held,share,flash", [
    (32, 4, 1, False), (37, 2, 3, True), (32, 8, 0, True), (37, 4, 2, False)])
def test_nemotron3_loss_and_every_gradient_leaf_match_the_reference(
        t, held, share, flash):
    cfg = nemotron_cfg(held, share)
    params, stats = REF_NEMOTRON.init(jax.random.PRNGKey(0), cfg)
    model = nemotron_model(held, share, flash=flash, remat=flash)
    x, y = tokens(t)
    ours = model.init(jax.random.PRNGKey(0), x)
    assert _tree(ours["params"]) == _tree(params)
    assert _tree(ours["batch_stats"]) == _tree(stats)
    assert all(float(jnp.max(jnp.abs(b))) > 0.01 for _, b in leaves(stats))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            targets=y).loss)(params)
        (want, _), want_grads = jax.value_and_grad(
            REF_NEMOTRON.loss_fn, has_aux=True)(params, stats, x, y, cfg)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    for (path, g), (_, w) in zip(leaves(grads), leaves(want_grads)):
        gap = float(jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-12))
        assert gap < 2e-4, (jax.tree_util.keystr(path), gap)


def test_nemotron3_train_step_takes_the_references_first_step(mesh8):
    """Through `create_train_state` and `make_train_step`, the path a cell
    runs: the loss, AdamW's first moment leaf for leaf, the counters of the
    Mamba and expert blocks, and the correction bias left as it was."""
    from tpudist.config import Config
    from tpudist.train import create_train_state, make_train_step
    cfg = Config(arch="nemotron3_tiny", batch_size=8, seq_len=32,
                 optimizer="adamw", lr=1e-3, weight_decay=0.1, adam_b2=0.95,
                 use_amp=False, seed=0).finalize(8)
    ref_cfg = nemotron_cfg(4, 1)
    model = nemotron_model(4, 1)
    state = create_train_state(jax.random.PRNGKey(0), model, cfg)
    params, stats = REF_NEMOTRON.init(jax.random.PRNGKey(3), ref_cfg)
    assert _tree(state.batch_stats) == _tree(stats)
    assert not any(np.asarray(b).any() for _, b in leaves(state.batch_stats))
    state = state.replace(params=params, batch_stats=stats)
    x, y = tokens(32, rows=8)
    with jax.default_matmul_precision("highest"):
        state, metrics = make_train_step(mesh8, model, cfg)(
            state, x, y, jnp.float32(1e-3))
        (want, (pairs, ssm)), want_grads = jax.value_and_grad(
            REF_NEMOTRON.loss_fn, has_aux=True)(params, stats, x, y, ref_cfg)
    assert abs(float(metrics["loss"]) - float(want)) < 1e-5 * float(want)
    mu = [leaf for path, leaf in leaves(state.opt_state)
          if any(getattr(k, "name", None) == "mu" for k in path)]
    for m, (path, w) in zip(mu, leaves(want_grads)):
        gap = float(jnp.linalg.norm(m / 0.1 - w)
                    / (jnp.linalg.norm(w) + 1e-12))
        assert gap < 5e-4, (jax.tree_util.keystr(path), gap)
    for n, layer in enumerate((1, 4)):
        assert float(metrics[f"moe_pairs.layer_{layer}"]) * 8 == float(
            jnp.sum(pairs[n]))
    # the mean of dt over the eight shards' rows is the reference's
    for n, layer in enumerate((0, 2)):
        assert float(metrics[f"ssm_dt_mean.layer_{layer}"]) == pytest.approx(
            float(ssm[n, 0]), rel=1e-4)
        assert 0.0 <= float(
            metrics[f"ssm_chunk_carry_min.layer_{layer}"]) <= 1.0
    # no optimizer touches the correction bias: it is as it was restored
    for (_, bias), (_, was) in zip(leaves(state.batch_stats), leaves(stats)):
        np.testing.assert_array_equal(bias, was)


def test_a_pattern_with_an_unknown_letter_is_refused_by_name():
    assert layer_types_of("ME*") == ("mamba", "moe", "attention")
    kinds = layer_types_of(NEMOTRON3_NANO_PATTERN)
    assert len(kinds) == 52
    assert [kinds.count(k) for k in ("mamba", "moe", "attention")] == [
        23, 23, 6]
    assert kinds[:9] == layer_types_of("MEMEM*EME")
    with pytest.raises(ValueError, match=r"unknown letter\(s\) \['-', 'X'\]"
                       r".*'M' = mamba, 'E' = moe, '\*' = attention"):
        layer_types_of("MEX-M")


def test_an_unknown_layer_type_lists_the_tables_kinds():
    model = nemotron_model().clone(
        layer_types=("mamba", "linear_attention", "moe"), layers=3)
    with pytest.raises(ValueError, match="layer 1: unknown layer type "
                       "'linear_attention'.*" + ", ".join(LAYER_KINDS)):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert LAYER_KINDS == ("sliding_attention", "full_attention", "mamba",
                           "moe", "attention")


def test_the_mixer_is_causal_whatever_the_padding():
    """19 ids alone (padded to 24 inside each Mamba mixer) give the logits
    that the first 19 of 24 ids give: the padding writes no state and a
    later position moves no earlier one."""
    model = nemotron_model()
    x, _ = tokens(24)
    variables = model.init(jax.random.PRNGKey(0), x)
    with jax.default_matmul_precision("highest"):
        whole = model.apply(variables, x)
        short = model.apply(variables, x[:, :19])
    np.testing.assert_allclose(short, whole[:, :19], atol=2e-5)


def test_nemotron3_states_its_attention_and_what_is_not_decayed():
    from tpudist.train import no_decay_mask
    published = nemotron3_nano_30b_a3b().clone(
        layers=9, expert_share=(0, 16), vocab_share=(0, 8))
    assert published.attention_workloads(8192) == [dict(
        heads=32, kv_heads=2, head_dim=128, seq=8192, causal=True,
        window=None, fused=False)]
    assert published.vocab_held == 16384
    # a share that keeps no attention block states none
    assert published.clone(layers=5).attention_workloads(8192) == []
    model = nemotron_model()
    params = model.init(jax.random.PRNGKey(0), model.example_input())[
        "params"]
    decayed = {jax.tree_util.keystr(k) for k, v in leaves(
        no_decay_mask(params)) if v}
    mixer = "['layer_0']['mixer']"
    for name in ("['A_log']", "['D']", "['dt_bias']", "['conv_bias']",
                 "['norm_scale']"):
        assert mixer + name not in decayed
    assert "['layer_0']['norm']['scale']" not in decayed
    for name in ("['in_proj']['kernel']", "['out_proj']['kernel']",
                 "['conv_kernel']"):
        assert mixer + name in decayed
    # the correction bias is no parameter: nothing updates it
    assert "e_score_correction_bias" not in str(_tree(params))


def test_python_m_tpudist_trains_nemotron3_tiny(tmp_path):
    """The normal entry point's path on the tiny twin: the share arrives as
    statements, the pattern, router rule and expert body with the registered
    name; the loss falls and the Mamba blocks' counters reach the drain."""
    from tpudist import telemetry
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    cfg = from_args([
        "--synthetic", "-a", "nemotron3_tiny", "--seq-len", "32", "-b", "16",
        "--layers", "4", "--epochs", "2", "--step", "5", "--optimizer",
        "adamw", "--lr", "0.01", "--wd", "0.1", "--adam-b2", "0.95",
        "--expert-share", "1/4", "--vocab-share", "0/2", "--flash", "off",
        "-j", "2", "-p", "2", "--no-telemetry", "--outpath",
        str(tmp_path / "out"), "--overwrite", "delete", "--seed", "0"])
    seen = len(telemetry.counters().get("ssm_dt_mean.layer_0", []))
    trainer = Trainer(cfg, writer=None)
    assert trainer.model.vocab_held == 128
    assert trainer.flash_decision["kernel"] == "xla"
    assert "_t32_h16_kv1_d16_bfloat16_train_causal" in \
        trainer.flash_decision["key"]
    experts = trainer.state.params["layer_1"]["mixer"]
    assert experts["up"].shape == (4, 32, 64) and "gate" not in experts
    assert experts["shared_up"].shape == (64, 64)
    assert trainer.state.batch_stats["layer_1"]["mixer"][
        "e_score_correction_bias"].shape == (16,)
    trainer.fit()
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    import re
    losses = [float(x) for x in re.findall(
        r"\|\|==> Train: Epoch\[\d+\]\s+Loss ([0-9.e+-]+)", log)]
    assert len(losses) == 2 and losses[1] < losses[0] < math.log(128) + 0.5
    dts = telemetry.counters()["ssm_dt_mean.layer_0"][seen:]
    assert len(dts) >= 4 and all(0.0 < v < 1.0 for v in dts)


def test_nemotron3_configuration_keeps_every_published_number():
    """`configs/nemotron3_nano_ep16.json` against the registered model and
    the published config key for key (a copy of the catalog's entry: the
    guide is not in the repo)."""
    cfg = json.load(open(os.path.join(CHIP, "configs",
                                      "nemotron3_nano_ep16.json")))
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": NEMOTRON3_NANO_PATTERN,
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    for key, value in published.items():
        if key not in ("num_hidden_layers", "vocab_size"):
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert (cfg["num_hidden_layers_published"],
            cfg["vocab_size_published"]) == (52, 131072)
    model = nemotron3_nano_30b_a3b()
    assert (model.hidden_size, model.num_layers, model.vocab_size) == (
        2688, 52, 131072)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (
        32, 2, 128)
    assert (model.num_experts, model.experts_per_token, model.expert_width,
            model.shared_width, model.routed_scaling) == (
        128, 6, 1856, 3712, 2.5)
    assert (model.router, model.expert_act, model.qk_norm,
            model.rope_parameters) == ("sigmoid", "relu2", False, None)
    assert model.mamba == dict(
        num_heads=64, head_dim=64, state=128, groups=8, conv=4, chunk=128,
        time_step=(0.001, 0.1, 0.0001), out_scale=52 ** -0.5)
    assert model.rms_norm_eps == cfg["layer_norm_epsilon"]
    assert model.layer_types == layer_types_of(
        cfg["hybrid_override_pattern"])
    assert cfg["arch"] in cfg["trainer_argv"]
    assert cfg["expert_share"] == "0 of 16" and cfg["vocab_share"] == "0 of 8"
    assert "16 chips a layer" in cfg["deployment"]
    held = model.clone(layers=9, expert_share=(0, 16), vocab_share=(0, 8))
    assert held.vocab_held == cfg["vocab_size"]
    for said in ("attention position", "correction bias", "dt limit",
                 "weights", "optimizer", "per_chip_batch"):
        assert len(cfg["assumed"][said]) > 40, said
    assert "GiB" in cfg["pinned"]["per_chip_batch 2"]
