"""What a model says of itself, where it is defined, and the questions the
trainer and the serving plane put to it: its fields (``models.model_with``),
the attention its step runs (``attention_workloads``) and its plans.

The tables here are literals on purpose: they record what every registered
name took when the registry's tables of name prefixes went (PR 47), and a
new family's statement is checked by adding its line.
"""

import math

import jax
import jax.numpy as jnp
import pytest

from tpudist.models import (create_model, model_fields, model_names,
                            model_with)

# name -> what it takes; a name not listed takes none of these
TAKES = {
    "joyai_llm_flash": "remat flash tokens",
    "joyai_tiny": "remat flash tokens",
    "mellum2_12b_a2_5b": "remat flash tokens",
    "mellum2_tiny": "remat flash tokens",
    "nemotron3_nano_30b_a3b": "remat flash tokens",
    "nemotron3_tiny": "remat flash tokens",
    "ouro_2_6b": "remat flash tokens",
    "ouro_tiny": "remat flash tokens",
    "resnet101": "remat",
    "resnet152": "remat",
    "resnet18": "remat",
    "resnet34": "remat",
    "resnet50": "remat",
    "resnext101_32x8d": "remat",
    "resnext50_32x4d": "remat",
    "sdar_30b_a3b": "remat flash tokens",
    "sdar_tiny": "remat flash tokens",
    "vit_b_16": "remat flash probe seq",
    "vit_b_32": "remat flash probe seq",
    "vit_h_14": "remat flash probe seq",
    "vit_l_16": "remat flash probe seq",
    "vit_l_32": "remat flash probe seq",
    "vit_moe_b_16": "flash probe expert",
    "vit_moe_s_16": "flash probe expert",
    "vit_pipe_b_16": "flash probe pipe",
    "vit_pipe_s_16": "flash probe pipe",
    "wide_resnet101_2": "remat",
    "wide_resnet50_2": "remat",
}
# what the trainer asks, by who asks (trainer.py: `asked`)
ASKS = {
    "remat": {"--remat": dict(remat=True)},
    "flash": {"--flash on": dict(flash=True)},
    "seq": {"mesh axis 'seq'": dict(seq_axis="seq", pool="gap")},
    "expert": {"mesh axis 'expert'": dict(
        expert_axis="expert", num_experts=8, aux_axes=("data", "expert"))},
    "pipe": {"mesh axis 'pipe'": dict(
        pipe_axis="pipe", num_microbatches=2, model_axis="model")},
    "tokens": {"--layers / --expert-share / --vocab-share": dict(
        layers=2, expert_share=(0, 2), vocab_share=(0, 2))},
}


def _granted(model, name, asked) -> bool:
    try:
        told = model_with(model, name, asked)
    except ValueError as e:
        (who, fields), = asked.items()
        assert who in str(e) and f"'{name}'" in str(e)
        assert any(f in str(e) for f in fields)
        return False
    (fields,) = asked.values()
    assert all(getattr(told, k) == v for k, v in fields.items())
    return True


def test_the_table_names_registered_models_only():
    assert set(TAKES) <= set(model_names())


@pytest.mark.parametrize("name", model_names())
def test_a_model_takes_what_it_took(name):
    model = create_model(name)
    takes = [what for what, asked in ASKS.items()
             if _granted(model, name, asked)]
    if "flash" in takes:
        # the one fused call of equal head counts is what the probe times
        size = 32 if "tokens" in takes else 224
        workloads = model.attention_workloads(size)
        if [w["fused"] for w in workloads] == [True]:
            takes.insert(takes.index("flash") + 1, "probe")
    else:
        assert "flash" not in model_fields(model)
    assert " ".join(takes) == TAKES.get(name, "")
    # `create_model(remat=True)` asks the same question before it builds
    if "remat" in takes:
        assert create_model(name, remat=True) == model.clone(remat=True)
    else:
        with pytest.raises(ValueError, match="--remat sets the model's "
                                             "field remat"):
            create_model(name, remat=True)


@pytest.mark.parametrize("name,keywords", [
    ("resnet18", dict(remat=True)),
    ("vit_b_16", dict(remat=True, flash=False, seq_axis="seq", pool="gap")),
    ("vit_moe_s_16", dict(flash=True, expert_axis="expert", num_experts=4,
                          aux_axes=("data", "expert"))),
    ("vit_pipe_s_16", dict(pipe_axis="pipe", num_microbatches=2,
                           model_axis="model")),
    ("mellum2_tiny", dict(remat=True, flash=True)),
])
def test_a_model_told_is_the_model_built_with_the_keywords(name, keywords):
    """The trainer builds the model plain and tells it; the constructor
    handed the same keywords builds an equal module (dataclass ``==``)."""
    uniform = dict(num_classes=8, dtype=jnp.bfloat16, sync_batchnorm=True,
                   bn_axis_name="data")
    told = model_with(create_model(name, **uniform), name,
                      {"a test": keywords})
    assert told == create_model(name, **uniform, **keywords)
    assert model_with(told, name, {}) is told


def test_every_asker_that_is_refused_is_named():
    with pytest.raises(ValueError) as e:
        model_with(create_model("alexnet"), "alexnet",
                   {"--remat": dict(remat=True),
                    "--flash on": dict(flash=True)})
    assert str(e.value).startswith(
        "--remat sets the model's field remat, which 'alexnet' (AlexNet) "
        "does not have")


# -- the attention decision -------------------------------------------------

def _stand_in(arch, share=None, *, mesh=(1,), axes=("data",), **cfg_kw):
    """A Trainer holding what ``_resolve_attention`` reads: the built module
    and the configuration. No state is initialised."""
    from tpudist.config import Config
    from tpudist.dist import make_mesh
    from tpudist.trainer import Trainer
    t = Trainer.__new__(Trainer)
    t.cfg = Config(arch=arch, synthetic=True, use_amp=True, outpath="unused",
                   **cfg_kw).finalize(mesh[0])
    t.mesh = make_mesh(mesh, axes, jax.devices()[:math.prod(mesh)])
    asked = {"--layers / --expert-share / --vocab-share": share or {}}
    if t.cfg.flash != "auto":
        asked["--flash"] = dict(flash=t.cfg.flash == "on")
    t.model = model_with(create_model(arch, dtype=jnp.bfloat16), arch, asked)
    t.trains_tokens = "vocab_share" in model_fields(t.model)
    t.logger = t.telemetry = None
    t.primary = True
    t.lines = []
    t.log = t.lines.append
    t.uses_model_axis = "model" in axes
    t.uses_pipe_axis = False
    return t


def _forced(key, *programs):
    from tpudist.ops.pallas.flash_attention import KERNEL_REV
    fields = ("heads_per_program", "block_q", "block_k", "band_fill")
    programs = [dict(zip(fields, p[:4]), schedule="streaming",
                     **(dict(mask="block_diffusion", block_length=p[4])
                        if len(p) > 4 else {})) for p in programs]
    return dict(kernel="flash", mode="on", source="forced", key=key,
                reason=key, kernel_rev=KERNEL_REV, schedule="streaming",
                programs=programs)


def _unmeasured(key, **fields):
    return dict(dict(kernel="xla", key=key, flash_ms=None, xla_ms=None,
                     margin=None, cache_hit=False), **fields)


_TOKENS = dict(seq_len=8192, flash="on", remat=True)
# (arch, the cell's share, the configuration) -> the decision PR 46 resolved
DECISIONS = {
    "vit_b16_staged": (
        "vit_b_16", None, dict(image_size=224, batch_size=128),
        _unmeasured("b128_t197_h12_d64_bfloat16_train_full", mode="auto",
                    source="platform", platform="cpu")),
    "vit_b16_evaluated": (
        "vit_b_16", None, dict(image_size=224, batch_size=128,
                               evaluate=True),
        _unmeasured("b128_t197_h12_d64_bfloat16_eval_full", mode="auto",
                    source="platform", platform="cpu")),
    "vit_b16_forced": (
        "vit_b_16", None, dict(image_size=224, batch_size=128, flash="on"),
        _unmeasured("b128_t197_h12_d64_bfloat16_train_full", mode="on",
                    source="forced", kernel="flash", schedule="whole_seq",
                    programs=[dict(heads_per_program=12, block_q=197,
                                   block_k=197, band_fill=1.0,
                                   schedule="whole_seq")])),
    "vit_b16_heads_over_two": (
        "vit_b_16", None, dict(image_size=224, batch_size=512, flash="off",
                               mesh=(4, 2), axes=("data", "model")),
        _unmeasured("b256_t197_h6_d64_bfloat16_train_full", mode="off",
                    source="forced")),
    "mellum2_12b_ep4_staged_8k": (
        "mellum2_12b_a2_5b",
        dict(layers=4, expert_share=(0, 4), vocab_share=(0, 4)),
        dict(_TOKENS, batch_size=2),
        _forced("b2_t8192_h32_kv4_d128_bfloat16_train_causal_w1024,"
                "b2_t8192_h32_kv4_d128_bfloat16_train_causal",
                (8, 256, 1280, 0.75), (8, 512, 1024, 0.889))),
    "sdar_30b_ep8_staged_8k": (
        "sdar_30b_a3b",
        dict(layers=4, expert_share=(0, 8), vocab_share=(0, 8)),
        dict(_TOKENS, batch_size=2),
        _forced("b2_t16384_h32_kv4_d128_bfloat16_train_bd4",
                (8, 512, 1024, 0.8893, 4))),
    "nemotron3_nano_ep16_staged_8k": (
        "nemotron3_nano_30b_a3b",
        dict(layers=9, expert_share=(0, 16), vocab_share=(0, 8)),
        dict(_TOKENS, batch_size=2),
        _forced("b2_t8192_h32_kv2_d128_bfloat16_train_causal",
                (8, 512, 1024, 0.889))),
    "ouro_2_6b_pp8_staged_8k": (
        "ouro_2_6b", dict(layers=6), dict(_TOKENS, batch_size=1),
        _forced("b1_t8192_h16_d128_bfloat16_train_causal",
                (1, 1024, 1024, 0.889))),
    "joyai_flash_ep16_staged_8k": (
        "joyai_llm_flash",
        dict(layers=5, expert_share=(0, 16), vocab_share=(0, 8)),
        dict(_TOKENS, batch_size=2),
        _forced("b2_t8192_h32_d192_bfloat16_train_causal",
                (1, 1024, 1024, 0.889))),
}


@pytest.mark.parametrize("cell", sorted(DECISIONS))
def test_the_resolved_decision_is_the_parents(cell):
    """One resolver walks the model's workloads; at every cell's shape it
    resolves what the two it replaced did, field for field (the expected
    dicts are a run of PR 46's tree; ``kernel_rev`` is the kernels' own)."""
    arch, share, cfg_kw, expected = DECISIONS[cell]
    t = _stand_in(arch, share, **cfg_kw)
    flash_before = t.model.flash
    assert t._resolve_attention() == expected
    assert len(t.lines) == 1 and t.lines[0].startswith(
        f"=> attention dispatch: {expected['kernel']} attention (mode "
        f"{expected['mode']}, {expected['source']}")
    # `auto` sets the model's field to the verdict; a forced mode was asked
    # of the model before
    assert t.model.flash is (False if expected["mode"] == "auto"
                             else flash_before)


def test_auto_without_a_probe_is_the_xla_path_and_says_so():
    t = _stand_in("mellum2_tiny", seq_len=32, batch_size=2)
    dec = t._resolve_attention()
    assert (dec["kernel"], dec["mode"], dec["source"]) == (
        "xla", "auto", "forced")
    assert dec["reason"].startswith("no start-up probe for grouped-query")
    assert dec["kernel_rev"] is None and "programs" not in dec
    assert t.model.flash is False


@pytest.mark.parametrize("mode", ["auto", "on", "off"])
@pytest.mark.parametrize("arch,size", [("vit_b_32", 64), ("vit_moe_s_16", 32),
                                       ("vit_pipe_s_16", 48)])
def test_serving_and_training_key_the_same_workload(arch, size, mode):
    """``resolve_serve_flash`` and the trainer ask the same model method:
    the same model, batch and mode give the same shape key."""
    from tpudist.serve.export import resolve_serve_flash
    t = _stand_in(arch, image_size=size, batch_size=4, flash=mode,
                  evaluate=True)
    served = resolve_serve_flash(create_model(arch, dtype=jnp.bfloat16),
                                 batch=4, image_size=size, mode=mode)
    trained = t._resolve_attention()
    assert served["key"] == trained["key"]
    assert served["key"].startswith(
        f"b4_t{(size // t.model.patch_size) ** 2 + 1}_")
    assert served["model"].flash is (mode == "on") is t.model.flash
    # a model without attention has nothing to resolve
    assert resolve_serve_flash(create_model("resnet18"), batch=4,
                               image_size=size, mode=mode) is None


def test_a_decoder_states_its_plans_in_the_order_they_are_said():
    hybrid = create_model("nemotron3_tiny", layers=4)
    events = [event for event, _plan in hybrid.plans(16, 32)]
    assert events == ["ssm_scan", "ssm_conv", "attn_qk", "lm_head"]
    assert dict(hybrid.plans(16, 32)) == dict(
        ssm_scan=hybrid.scan_plan(16, 32), ssm_conv=hybrid.conv_plan(16, 32),
        attn_qk=hybrid.qk_plans(16, 32)[0], lm_head=hybrid.head_plan(16, 32))
    # a share without a mixer or without attention says nothing of them,
    # and two layer types whose plan is the same say it once
    assert [e for e, _ in create_model("mellum2_tiny").plans(16, 32)] == [
        "attn_qk", "lm_head"]
    assert [e for e, _ in hybrid.clone(layers=2).plans(16, 32)] == [
        "ssm_scan", "ssm_conv", "lm_head"]
