"""The program names what it does (tpudist/obs/scopes.py): a `tpudist_*`
scope on every device operation of the DP step, a `tpudist.*` span on every
part of a trainer loop turn, `init.*` phases that sum to the constructor.

CPU, tiny widths. The compiled programs are built with the persistent
compilation cache off: its key leaves HLO metadata out, so a cached
executable carries the names of whichever tree compiled it first.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import telemetry
from tpudist.config import Config
from tpudist.obs import scopes
from tpudist.obs.scopes import phase_of

# not operations of the program's own: arguments, constants (and the
# compiler's broadcasts of them), tuple and layout plumbing
PLUMBING = (" parameter(", " constant(", " broadcast(%constant", " tuple(",
            " get-tuple-element(", " bitcast(", " copy(")


def _tiny_model(arch, cfg):
    from tpudist.models import create_model
    from tpudist.models.vit import VisionTransformer
    from tpudist.train import compute_dtype
    if arch == "vit_tiny":
        return VisionTransformer(patch_size=8, hidden_dim=32, num_layers=2,
                                 num_heads=2, mlp_dim=64,
                                 num_classes=cfg.num_classes,
                                 dtype=compute_dtype(cfg), flash=False)
    return create_model(arch, num_classes=cfg.num_classes,
                        dtype=compute_dtype(cfg), bn_axis_name="data")


@pytest.fixture(scope="module")
def compiled_steps(mesh8):
    """{arch: [(opcode-bearing HLO line, op_name)]} of the compiled DP step,
    for the instructions the program's own code produced: those with an
    `op_name` under `jit(step)/` that are not parameter / constant / tuple
    plumbing (a reducer's scalar body is named by its primitive alone)."""
    from jax.experimental.compilation_cache import compilation_cache
    from tpudist.train import create_train_state, make_train_step
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    out = {}
    try:
        for arch, optimizer in (("resnet18", "sgd"), ("vit_tiny", "adamw")):
            cfg = Config(arch="resnet18" if arch == "resnet18" else "vit_b_16",
                         num_classes=8, image_size=32, batch_size=16,
                         optimizer=optimizer, use_amp=True, seed=0).finalize(8)
            model = _tiny_model(arch, cfg)
            state = create_train_state(jax.random.PRNGKey(0), model, cfg)
            step = make_train_step(mesh8, model, cfg)
            text = step.lower(
                state, jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32),
                jax.ShapeDtypeStruct((16,), jnp.int32),
                jnp.float32(0.1)).compile().as_text()
            rows = []
            for line in text.splitlines():
                name = re.search(r'op_name="([^"]*)"', line)
                if " = " not in line or name is None \
                        or "jit(step)/" not in name.group(1) \
                        or any(p in line for p in PLUMBING):
                    continue
                rows.append((line, name.group(1)))
            out[arch] = rows
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return out


def _matrix_ops(rows):
    return [n for line, n in rows
            if " convolution(" in line or " dot(" in line]


@pytest.mark.parametrize("arch", ["resnet18", "vit_tiny"])
@pytest.mark.parametrize("check", ["coverage", "forward", "backward",
                                   "collectives", "optimizer"])
def test_every_device_op_of_the_dp_step_has_a_scope(compiled_steps, arch,
                                                    check):
    rows = compiled_steps[arch]
    assert len(rows) > 200
    phases = [phase_of(n) for _, n in rows]
    if check == "coverage":
        unscoped = sorted({n for (_, n), p in zip(rows, phases) if p is None})
        assert len([p for p in phases if p is None]) <= 0.01 * len(rows), \
            unscoped[:20]
        assert {"fwd", "bwd", "loss", "reduce", "opt", "metrics"} \
            <= set(phases)
    elif check == "forward":
        fwd = [n for n in _matrix_ops(rows) if phase_of(n) == "fwd"]
        assert fwd and all(f"jvp({scopes.FORWARD})" in n for n in fwd)
        assert not any("transpose(" in n for n in fwd)
    elif check == "backward":
        matrix = _matrix_ops(rows)
        bwd = [n for n in matrix if phase_of(n) == "bwd"]
        # every convolution / dot is the model's: forward or its transpose
        assert all(phase_of(n) in ("fwd", "bwd") for n in matrix)
        assert all(f"transpose(jvp({scopes.FORWARD}))" in n for n in bwd)
        assert len(bwd) >= len(matrix) - len(bwd)  # each layer is transposed
    elif check == "collectives":
        psums = [n for line, n in rows if " all-reduce(" in line
                 or n.endswith("/psum")]
        assert psums
        assert all(phase_of(n) in ("reduce", "metrics") for n in psums), psums
        assert any(scopes.GRAD_REDUCE in n for n in psums)
    else:
        # the update itself: nothing of it is left outside its scope
        assert sum(p == "opt" for p in phases) > 50
        assert not any(scopes.OPTIMIZER in n and scopes.FORWARD in n
                       for _, n in rows)


def test_attention_stages_are_named(compiled_steps):
    names = {n for _, n in compiled_steps["vit_tiny"]}
    for stage in (scopes.ATTN_SCORES, scopes.ATTN_SOFTMAX, scopes.ATTN_VALUES):
        assert any(f"/self_attention/{stage}/" in n and phase_of(n) == "fwd"
                   for n in names), stage
        assert any(f"/self_attention/{stage}/" in n and phase_of(n) == "bwd"
                   for n in names), stage


TOKEN_ARCHS = ("mellum2_tiny", "sdar_tiny", "nemotron3_tiny")


def _token_step(mesh8, arch, remat=True, seq_len=32, **fields):
    """(compiled DP step, its HLO text) of a tiny decoder of tokens
    (models/decoder.py): `mellum2_tiny` and `sdar_tiny` (trained to predict
    the next id, and by diffusion over blocks; two layers), `nemotron3_tiny`
    (all five blocks: two Mamba-2 mixers, two expert blocks, one attention);
    attention on the streaming kernel and, as the chip benchmark's cells run
    the large ones, every layer rematerialised; the cache off. ``fields``
    replace the tiny model's own (a published head size)."""
    from jax.experimental.compilation_cache import compilation_cache
    from tpudist.models import create_model
    from tpudist.train import (compute_dtype, create_train_state,
                               make_train_step)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        cfg = Config(arch=arch, batch_size=16, seq_len=seq_len,
                     optimizer="adamw", use_amp=True, seed=0).finalize(8)
        share = {} if arch == "nemotron3_tiny" else {"layers": 2}
        model = create_model(cfg.arch, dtype=compute_dtype(cfg), **share,
                             expert_share=(0, 4), flash=True, remat=remat,
                             loss_chunk=16)   # four turns of the head's loop
        model = model.clone(**fields)
        state = create_train_state(jax.random.PRNGKey(0), model, cfg)
        rows = jax.ShapeDtypeStruct((16, seq_len), jnp.int32)
        compiled = make_train_step(mesh8, model, cfg).lower(
            state, rows, rows, jnp.float32(0.1)).compile()
        return compiled, compiled.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _named_rows(text):
    """[(HLO line, op_name)] of the instructions the program's own code
    produced (as `compiled_steps` keeps them)."""
    out = []
    for line in text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        if " = " in line and name is not None \
                and "jit(step)/" in name.group(1) \
                and not any(p in line for p in PLUMBING):
            out.append((line, name.group(1)))
    return out


@pytest.fixture(scope="module")
def token_steps(mesh8):
    """(arch, remat) -> (compiled DP step, its named rows), compiled once a
    module."""
    kept = {}

    def get(arch, remat=True):
        if (arch, remat) not in kept:
            compiled, text = _token_step(mesh8, arch, remat)
            kept[arch, remat] = (compiled, _named_rows(text))
        return kept[arch, remat]
    return get


@pytest.fixture(scope="module", params=["mellum2_tiny", "sdar_tiny"])
def decoder_step(token_steps, request):
    return token_steps(request.param)[1]


def test_every_device_op_of_the_decoder_step_has_a_scope(decoder_step):
    assert len(decoder_step) > 1000
    unscoped = [n for _, n in decoder_step if phase_of(n) is None]
    assert len(unscoped) <= 0.01 * len(decoder_step), sorted(set(unscoped))[:20]


@pytest.mark.parametrize("scope,where", [
    (scopes.MOE_ROUTER, "/moe/"), (scopes.MOE_DISPATCH, "/moe/"),
    (scopes.MOE_EXPERTS, "/moe/"), (scopes.MOE_COMBINE, "/moe/"),
    (scopes.ATTN_FUSED, "/self_attention/"), (scopes.LM_HEAD, "MoEDecoder/"),
    (scopes.LM_EMBED, "MoEDecoder/"), (scopes.LOSS, "MoEDecoder/")])
def test_decoder_scopes_are_named_forward_and_backward(decoder_step, scope,
                                                       where):
    """A layer's parts lie under the layer's name inside the forward scope,
    plain and transposed: what `moe_ms`, `lm_head_ms` and
    `attn_stream_roofline` of the chip benchmark sum. The head's loss takes
    its gradients in its forward loop (PR 46): all three of its products a
    chunk and `dlogits` are named in the forward pass, and the backward
    pass's scaling by a cotangent of one is no operation."""
    named = [n for _, n in decoder_step
             if f"/{scope}/" in n and where in n and scopes.FORWARD in n]
    assert any(phase_of(n) == "fwd" for n in named), scope
    if scope in (scopes.LM_HEAD, scopes.LOSS):
        assert not any(phase_of(n) == "bwd" for n in named), scope
        dots = [n for line, n in decoder_step if " dot(" in line
                and f"/{scopes.LM_HEAD}/" in n]
        assert len(dots) == 3 and all(phase_of(n) == "fwd" for n in dots)
        return
    assert any(phase_of(n) == "bwd" for n in named), scope
    if where == "/moe/":
        assert any("/layer_0/" in n for n in named)
        assert any("/layer_1/" in n for n in named)


@pytest.fixture(scope="module")
def nemotron_step(token_steps):
    return token_steps("nemotron3_tiny")


def test_every_device_op_of_the_mixer_blocks_step_has_a_scope(nemotron_step):
    named = [n for _, n in nemotron_step[1]]
    assert len(named) > 1000
    unscoped = [n for n in named if phase_of(n) is None]
    assert len(unscoped) <= 0.01 * len(named), sorted(set(unscoped))[:20]


@pytest.mark.parametrize("scope,within", [
    (scopes.SSM_MIXER, "/layer_0/mixer/"),
    (scopes.SSM_IN_PROJ, f"/mixer/{scopes.SSM_MIXER}/"),
    (scopes.SSM_CONV, f"/mixer/{scopes.SSM_MIXER}/"),
    (scopes.SSM_SCAN, f"/mixer/{scopes.SSM_MIXER}/"),
    (scopes.SSM_GATE_NORM, f"/mixer/{scopes.SSM_MIXER}/"),
    (scopes.SSM_OUT_PROJ, f"/mixer/{scopes.SSM_MIXER}/"),
    (scopes.MOE_SHARED, "/layer_1/mixer/"),
    (scopes.MOE_ROUTER, "/layer_4/mixer/"),
    (scopes.MOE_EXPERTS, "/layer_1/mixer/"),
    (scopes.ATTN_FUSED, "/layer_3/mixer/")])
def test_mixer_scopes_are_named_forward_and_backward(nemotron_step, scope,
                                                     within):
    """What `ssm_ms`, `ssd_scan_ms`, `moe_shared_ms` and `moe_ms` of the
    chip benchmark sum: a Mamba mixer's five parts lie inside `ssm_mixer`
    under the block's name, the shared expert beside the routed path's four
    scopes, all inside the forward scope, plain and transposed."""
    named = [n for _, n in nemotron_step[1]
             if f"/{scope}/" in n and within in n and scopes.FORWARD in n]
    assert any(phase_of(n) == "fwd" for n in named), scope
    assert any(phase_of(n) == "bwd" for n in named), scope
    if scope == scopes.SSM_SCAN:
        # the pass between chunks is a loop of the scan's own
        assert any(" while(" in line and f"/{scope}/" in n
                   for line, n in nemotron_step[1])
        # both Mamba blocks
        assert any("/layer_2/" in n for n in named)
    if scope == scopes.MOE_SHARED:
        routed = (scopes.MOE_ROUTER, scopes.MOE_DISPATCH, scopes.MOE_EXPERTS,
                  scopes.MOE_COMBINE)
        assert not any(f"/{r}/" in n for n in named for r in routed)


def test_the_new_scopes_change_no_compiled_flop_or_byte(mesh8, nemotron_step,
                                                        monkeypatch):
    """A scope is HLO metadata: the step compiled with every
    `jax.named_scope` taken out costs what the named one costs."""
    import contextlib
    named = nemotron_step[0].cost_analysis()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, text = _token_step(mesh8, "nemotron3_tiny")
    assert f"/{scopes.SSM_SCAN}/" not in text
    assert f"/{scopes.MOE_SHARED}/" not in text
    bare = bare.cost_analysis()
    for key in ("flops", "bytes accessed", "transcendentals"):
        assert named[key] == bare[key], key
    assert named["flops"] > 0


BLOCK_SCOPES = (scopes.ATTN_MIXER, scopes.ATTN_QKV_PROJ,
                scopes.ATTN_QK_NORM_ROPE, scopes.ATTN_OUT_PROJ,
                scopes.BLOCK_NORM)


def _under(op_name, scope):
    """`scope` is, or is wrapped in, a path element of `op_name` (the chip
    benchmark's `harness/scope_sum.py::under`)."""
    return any(scope == part or f"({scope})" in part
               for part in op_name.replace(";", "/").split("/"))


@pytest.mark.parametrize("scope", BLOCK_SCOPES)
@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("arch", TOKEN_ARCHS)
def test_a_blocks_parts_are_named_forward_and_backward(token_steps, arch,
                                                       remat, scope):
    """What `attn_mixer_ms`, `attn_proj_ms`, `attn_qk_rope_ms` and
    `block_norm_ms` of the chip benchmark sum: everything a decoder block
    does outside its kernels lies under a leaf scope inside the forward
    scope, plain and transposed, rematerialised or not; attention's parts
    lie within `attn_mixer`, and an attention that neither norms nor
    rotates q and k holds nothing under that part."""
    named = [n for _, n in token_steps(arch, remat)[1]
             if _under(n, scope) and scopes.FORWARD in n]
    if arch == "nemotron3_tiny" and scope == scopes.ATTN_QK_NORM_ROPE:
        assert not named
        return
    bwd = [n for n in named if phase_of(n) == "bwd"]
    assert bwd, scope
    if remat:
        # the transposed copy sits behind the layer's checkpoint, and so
        # does the forward made again: of a small part's two equal forwards
        # XLA keeps one, under either name
        assert any("/checkpoint/" in n for n in bwd)
        assert any(phase_of(n) == "fwd" or "/rematted_computation/" in n
                   for n in named), scope
    if not remat or scope in (scopes.ATTN_MIXER, scopes.BLOCK_NORM):
        assert any(phase_of(n) == "fwd" for n in named), scope
    if scope == scopes.BLOCK_NORM:
        norms = (("/norm/",) if arch == "nemotron3_tiny"
                 else ("/input_norm/", "/post_norm/"))
        for norm in norms + (f"MoEDecoder/{scope}/norm/",):
            assert any(norm in n for n in named), norm
        assert not any(_under(n, scopes.ATTN_MIXER) for n in named)
    elif scope == scopes.ATTN_MIXER:
        where = "/mixer/" if arch == "nemotron3_tiny" else "/self_attention/"
        assert all(f"{where}{scope}/" in n for n in named)
        assert any(_under(n, scopes.ATTN_FUSED) for n in named)
    else:
        assert all(_under(n, scopes.ATTN_MIXER) for n in named)
        proj = {scopes.ATTN_QKV_PROJ: ("q_proj", "k_proj", "v_proj"),
                scopes.ATTN_OUT_PROJ: ("o_proj",),
                scopes.ATTN_QK_NORM_ROPE: ("q_norm", "k_norm")}[scope]
        for name in proj:
            assert any(f"/{scope}/{name}/" in n for n in named), name


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("arch", TOKEN_ARCHS + ("ouro_tiny", "joyai_tiny"))
def test_step_parts_cover_the_named_operations(token_steps, arch, remat):
    """`scopes.STEP_PARTS` itemises a decoder's step: at most 3 % of its
    named operations lie under none of the parts (a block's counters, the
    head's loop plumbing, the cotangents' sums at a fan-out), what
    `step_unitemised_ms` of the chip benchmark times (`loop_unitemised_ms`
    in a step whose layers run several times)."""
    named = [n for _, n in token_steps(arch, remat)[1]]
    missed = [n for n in named
              if not any(_under(n, part) for part in scopes.STEP_PARTS)]
    assert len(missed) <= 0.03 * len(named), sorted(set(missed))[:20]
    # a part is a leaf: none lies within another (but the loop over the
    # passes, which a pass's other parts lie within)
    assert len(set(scopes.STEP_PARTS)) == len(scopes.STEP_PARTS)
    assert scopes.ATTN_MIXER not in scopes.STEP_PARTS
    assert scopes.SSM_MIXER not in scopes.STEP_PARTS


# -- layers that run several times (PR 42): `ouro_tiny`, two dense layers
# under sandwich norms run four times by one scan, rematerialised ------------

@pytest.fixture(scope="module")
def looped_step(token_steps):
    return token_steps("ouro_tiny")[1]


def test_every_device_op_of_the_looped_step_has_a_scope(looped_step):
    """Nothing of the new cell's step is unscoped: the passes' loop, the
    dense layers, the exit arithmetic and four weighted head losses all lie
    under the forward scope (plain or transposed), the optimizer's or the
    metrics'."""
    named = [n for _, n in looped_step]
    assert len(named) > 300
    unscoped = [n for n in named if phase_of(n) is None]
    # the weights' casts are made once a step, before the loop (flax takes
    # what a pass computes from broadcast parameters alone out of the scan,
    # outside the trace that carries the forward scope): seven a layer and
    # the head's here, 3 ms of 958 at the cell's size (409 M parameters read
    # in float32, written in bfloat16)
    hoisted = [n for n in unscoped if n.endswith("/convert_element_type")]
    assert all("/MoEDecoder.one_pass/" in n for n in hoisted)
    assert len(unscoped) - len(hoisted) <= 0.01 * len(named), sorted(
        set(unscoped) - set(hoisted))[:20]


@pytest.mark.parametrize("scope,names", [
    (scopes.DENSE_MLP, ("/mlp/", "gate_proj", "up_proj", "down_proj")),
    (scopes.LOOP_EXIT, ("exit_gate",)),
    (scopes.BLOCK_NORM, ("/input_norm/", "/attn_out_norm/", "/post_norm/",
                         "/mlp_out_norm/", f"/{scopes.BLOCK_NORM}/norm/")),
    (scopes.ATTN_QK_NORM_ROPE, ()), (scopes.ATTN_FUSED, ()),
    (scopes.LM_HEAD, ()), (scopes.LOSS, ())])
def test_the_looped_steps_parts_are_named_forward_and_backward(looped_step,
                                                               scope, names):
    """What `dense_mlp_ms`, `loop_exit_ms`, `block_norm_ms`, `lm_head_ms` and
    the two looped rooflines of the chip benchmark sum: a pass's parts lie
    under their leaf scopes inside the passes' loop (`loop_carry/while/
    body`), plain and transposed; both layers' feed-forward, all four norms
    of a layer and the loop's own, the gate."""
    named = [n for _, n in looped_step
             if _under(n, scope) and scopes.FORWARD in n]
    assert any(phase_of(n) == "fwd" for n in named), scope
    assert any(phase_of(n) == "bwd" for n in named), scope
    assert all(f"/{scopes.LOOP_CARRY}/while/body/" in n for n in named), scope
    for name in names:
        assert any(name in n for n in named), name
    if scope == scopes.DENSE_MLP:
        for layer in ("/layer_0/", "/layer_1/"):
            assert any(layer in n for n in named), layer
        assert not any(_under(n, scopes.ATTN_MIXER) for n in named)
    if scope == scopes.LOOP_EXIT:
        # the head's product and its cross entropy are not the exit's
        assert not any(_under(n, scopes.LM_HEAD) or _under(n, scopes.LOSS)
                       for n in named)


def test_the_loops_own_work_reads_under_loop_carry(looped_step):
    """What `loop_carry_ms` times: under `loop_carry` and no other part lie
    the loop's own operations, forward (a pass's saved results stacked) and
    transposed (taken back; the tied leaves' gradients summed), and with
    them the step is itemised: `loop_unitemised_ms` holds under 3 % of the
    named operations."""
    named = [n for _, n in looped_step]
    parts = [p for p in scopes.STEP_PARTS if p != scopes.LOOP_CARRY]
    own = [n for n in named if _under(n, scopes.LOOP_CARRY)
           and not any(_under(n, p) for p in parts)]
    assert any(phase_of(n) == "fwd" and "dynamic_update_slice" in n
               for n in own)
    assert any(phase_of(n) == "bwd" and "dynamic_slice" in n for n in own)
    assert any(phase_of(n) == "bwd" and n.endswith("add_any") for n in own)
    assert 0.02 * len(named) < len(own) < 0.2 * len(named)
    # behind every part a pass holds (a reader hands an operation to the
    # first part it is under); only a later model's leaf lies behind it
    assert scopes.STEP_PARTS[-2:] == (scopes.LOOP_CARRY, scopes.MTP_MERGE)


def test_the_looped_scopes_change_no_compiled_flop_or_byte(mesh8, token_steps,
                                                           monkeypatch):
    import contextlib
    named = token_steps("ouro_tiny")[0].cost_analysis()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, text = _token_step(mesh8, "ouro_tiny")
    for scope in (scopes.DENSE_MLP, scopes.LOOP_EXIT, scopes.LOOP_CARRY):
        assert f"/{scope}/" not in text, scope
    bare = bare.cost_analysis()
    for key in ("flops", "bytes accessed", "transcendentals"):
        assert named[key] == bare[key], key
    assert named["flops"] > 0


# -- latent attention and a multi-token-prediction module (PR 44):
# `joyai_tiny`, one dense layer and one of experts under latent attention,
# the module behind them, rematerialised ----------------------------------

@pytest.fixture(scope="module")
def latent_step(token_steps):
    return token_steps("joyai_tiny")[1]


def test_every_device_op_of_the_latent_step_has_a_scope(latent_step):
    """Nothing of the new cell's step is unscoped: latent attention's two
    low-rank paths, the dense first layer, the module and both passes
    through the head all lie under the forward scope (plain or transposed),
    the optimizer's or the metrics'. (No cast is hoisted here: no layer runs
    in a loop.)"""
    named = [n for _, n in latent_step]
    assert len(named) > 300
    unscoped = [n for n in named if phase_of(n) is None]
    assert len(unscoped) <= 0.01 * len(named), sorted(set(unscoped))[:20]
    assert not [n for n in unscoped if n.endswith("/convert_element_type")]


@pytest.mark.parametrize("scope,within,names", [
    (scopes.MLA_DOWN, scopes.ATTN_QKV_PROJ, ("q_a_proj", "kv_a_proj")),
    (scopes.MLA_UP, scopes.ATTN_QKV_PROJ, ("q_b_proj", "kv_b_proj")),
    (scopes.MLA_LATENT_NORM, scopes.ATTN_QK_NORM_ROPE,
     ("q_a_norm", "kv_a_norm")),
    (scopes.ATTN_QK_NORM_ROPE, scopes.ATTN_MIXER, ()),
    (scopes.ATTN_FUSED, scopes.ATTN_MIXER, ()),
    (scopes.ATTN_OUT_PROJ, scopes.ATTN_MIXER, ("o_proj",)),
    (scopes.DENSE_MLP, None, ("/layer_0/mlp/", "gate_proj")),
    (scopes.MOE_SHARED, None, ("/layer_1/moe/",))])
def test_latent_attentions_parts_are_named_forward_and_backward(
        latent_step, scope, within, names):
    """What `mla_latent_ms`, `attn_proj_ms`, `attn_qk_rope_ms`,
    `attn_mla_roofline`, `dense_mlp_ms` and `moe_shared_ms` of the chip
    benchmark sum: every operation of latent attention lies under one of the
    four attention parts that exist, the three new scopes within two of
    them, plain and transposed, in the trunk's layers and in the module's
    block alike."""
    named = [n for _, n in latent_step
             if _under(n, scope) and scopes.FORWARD in n]
    assert any(phase_of(n) == "bwd" for n in named), scope
    assert any(phase_of(n) == "fwd" or "/rematted_computation/" in n
               for n in named), scope
    if within is not None:
        assert all(_under(n, within) for n in named), scope
        assert all(_under(n, scopes.ATTN_MIXER) for n in named), scope
        for where in ("/layer_0/", "/layer_1/", "/mtp/"):
            assert any(where in n for n in named), (scope, where)
    for name in names:
        assert any(name in n for n in named), name
    mixer = [n for _, n in latent_step if _under(n, scopes.ATTN_MIXER)]
    parts = (scopes.ATTN_QKV_PROJ, scopes.ATTN_QK_NORM_ROPE,
             scopes.ATTN_FUSED, scopes.ATTN_OUT_PROJ)
    assert all(any(_under(n, p) for p in parts) for n in mixer)


@pytest.mark.parametrize("scope,names", [
    (scopes.MTP_MERGE, ("enorm", "hnorm", "eh_proj", "concatenate")),
    (scopes.LM_EMBED, ()), (scopes.ATTN_QKV_PROJ, ()),
    (scopes.ATTN_QK_NORM_ROPE, ()), (scopes.ATTN_FUSED, ()),
    (scopes.ATTN_OUT_PROJ, ()), (scopes.MOE_ROUTER, ()),
    (scopes.MOE_EXPERTS, ()), (scopes.MOE_SHARED, ()),
    (scopes.BLOCK_NORM, ("/input_norm/", "/post_norm/", "/mtp/block_norm/")),
    (scopes.LM_HEAD, ()), (scopes.LOSS, ())])
def test_the_modules_parts_are_named_inside_mtp_module(latent_step, scope,
                                                      names):
    """What `mtp_ms` times and `mtp_unitemised_ms` leaves: the module lies
    whole inside `mtp_module`, forward and backward: the next id's
    embedding, the merge (its one leaf of its own), its block's parts under
    their own names, its pass through the head and its loss. That pass
    takes its gradients in its forward loop (PR 46): the head's three
    products a chunk are forward operations, and what the backward pass
    keeps of it is the scaling by the module's weight, under the loss."""
    inside = [n for _, n in latent_step if _under(n, scopes.MTP_MODULE)]
    named = [n for n in inside if _under(n, scope)]
    assert any(phase_of(n) == "fwd" or "/rematted_computation/" in n
               for n in named), scope
    if scope == scopes.LM_HEAD:
        dots = [n for line, n in latent_step if " dot(" in line
                and _under(n, scope) and _under(n, scopes.MTP_MODULE)]
        assert len(dots) == 3 and all(phase_of(n) == "fwd" for n in named)
    else:
        assert any(phase_of(n) == "bwd" for n in named), scope
    for name in names:
        assert any(name in n for n in named), name
    if scope == scopes.MTP_MERGE:
        # the merge is the module's and nothing else's, and no other part's
        everywhere = [n for _, n in latent_step if _under(n, scope)]
        assert len(everywhere) == len(named)
        others = [p for p in scopes.STEP_PARTS if p != scope]
        assert not any(_under(n, p) for n in named for p in others)
    if scope in (scopes.LM_HEAD, scopes.LOSS):
        # the main head's pass is not the module's
        outside = [n for _, n in latent_step if _under(n, scope)
                   and not _under(n, scopes.MTP_MODULE)]
        assert outside
    # under the module and no part: 3 % of its named operations at most
    left = [n for n in inside
            if not any(_under(n, p) for p in scopes.STEP_PARTS)]
    assert len(left) <= 0.03 * len(inside), sorted(set(left))[:20]


def test_the_latent_scopes_change_no_compiled_flop_or_byte(mesh8, token_steps,
                                                           monkeypatch):
    import contextlib
    named = token_steps("joyai_tiny")[0].cost_analysis()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, text = _token_step(mesh8, "joyai_tiny")
    for scope in (scopes.MLA_DOWN, scopes.MLA_LATENT_NORM, scopes.MLA_UP,
                  scopes.MTP_MODULE, scopes.MTP_MERGE):
        assert f"/{scope}/" not in text, scope
    bare = bare.cost_analysis()
    for key in ("flops", "bytes accessed", "transcendentals"):
        assert named[key] == bare[key], key
    assert named["flops"] > 0


def test_the_modules_counters_are_a_models_counters():
    """`mtp_loss` and `lm_loss_main` ride the step's metrics to the drain as
    the layers' counters do (`trainer._drain` keeps what starts with a name
    of `MODEL_COUNTERS`), and the module's block's under its own layer
    name."""
    assert {scopes.MTP_LOSS, scopes.LM_LOSS_MAIN} <= set(
        scopes.MODEL_COUNTERS)
    assert f"{scopes.MOE_PAIRS}.mtp".startswith(scopes.MODEL_COUNTERS)
    assert scopes.MTP_MERGE in scopes.STEP_PARTS
    assert scopes.MTP_MODULE not in scopes.STEP_PARTS      # it encloses


@pytest.fixture(scope="module")
def laid_step(mesh8):
    """The named rows of `mellum2_tiny`'s step at the published head size
    (128: whole lane tiles) on rows of 128 ids under a window of 32: q's and
    k's norm and rotation run as the Pallas pass (`qk_plan` says so), which
    the tiny twins' heads of 16 never take; rematerialised, as the cells
    run."""
    from tpudist.models import create_model
    fields = dict(head_dim=128, sliding_window=32)
    plans = create_model("mellum2_tiny", flash=True).clone(
        **fields).qk_plans(2, 128)
    assert [p["kernel"] for p in plans] == ["pallas"], plans
    return _named_rows(_token_step(mesh8, "mellum2_tiny", seq_len=128,
                                   **fields)[1])


def _pass_calls(rows):
    """The operations of the pass's two jitted calls (on the chip one Mosaic
    call each; interpreted here, a loop over the grid)."""
    return [n for _, n in rows if "/jit(_forward)" in n
            or "/jit(_backward)" in n]


@pytest.mark.parametrize("call,phase,within", [
    ("jit(_forward)", "fwd", "/layer_"),
    ("jit(_forward)", "bwd", "/checkpoint/rematted_computation/layer_"),
    ("jit(_backward)", "bwd", "/checkpoint/layer_")],
    ids=["forward", "rematerialised", "transposed"])
def test_the_qk_pass_reads_under_its_scope(laid_step, call, phase, within):
    """What `attn_qk_rope_ms` and `attn_qk_rope_roofline` of the chip
    benchmark time: the pass's forward, its forward made again behind the
    layer's checkpoint and its transposed call all lie under
    `attn_qk_norm_rope` within `attn_mixer`, in both layers."""
    named = [n for n in _pass_calls(laid_step)
             if f"/{call}" in n and within in n and phase_of(n) == phase]
    assert named, (call, phase)
    for layer in ("/layer_0/", "/layer_1/"):
        assert any(layer in n for n in named), layer
    assert all(f"/self_attention/{scopes.ATTN_MIXER}/"
               f"{scopes.ATTN_QK_NORM_ROPE}/{call}" in n for n in named)


def test_the_qk_pass_leaves_the_attention_kernels_scope_alone(laid_step):
    """None of the pass lies under `attn_fused` (the two attention
    rooflines' denominators hold what they held, less q's and k's moves),
    the kernels' own calls still do, and the step stays itemised: nothing
    unscoped, `STEP_PARTS` as it was."""
    calls = _pass_calls(laid_step)
    assert calls and all(_under(n, scopes.ATTN_QK_NORM_ROPE)
                         and not _under(n, scopes.ATTN_FUSED) for n in calls)
    fused = [n for _, n in laid_step if _under(n, scopes.ATTN_FUSED)]
    assert any("flash_attention_laid" in n and phase_of(n) == "fwd"
               for n in fused)
    assert any("flash_attention_laid" in n and phase_of(n) == "bwd"
               for n in fused)
    assert not any(_under(n, scopes.ATTN_QK_NORM_ROPE) for n in fused)
    named = [n for _, n in laid_step]
    unscoped = [n for n in named if phase_of(n) is None]
    assert len(unscoped) <= 0.01 * len(named), sorted(set(unscoped))[:20]
    missed = [n for n in named
              if not any(_under(n, part) for part in scopes.STEP_PARTS)]
    assert len(missed) <= 0.03 * len(named), sorted(set(missed))[:20]
    assert len(scopes.STEP_PARTS) == 26          # PR 42: three; PR 44: one
    assert scopes.ATTN_QK_NORM_ROPE in scopes.STEP_PARTS


@pytest.mark.parametrize("arch", ["mellum2_tiny", "sdar_tiny"])
def test_the_block_scopes_change_no_compiled_flop_or_byte(mesh8, token_steps,
                                                          monkeypatch, arch):
    """As the mixer blocks' case above, for the pairs of attention and
    experts: `attn_mixer`, its parts and `block_norm` are metadata."""
    import contextlib
    named = token_steps(arch, False)[0].cost_analysis()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, text = _token_step(mesh8, arch, remat=False)
    for scope in BLOCK_SCOPES:
        assert f"/{scope}/" not in text, scope
    bare = bare.cost_analysis()
    for key in ("flops", "bytes accessed", "transcendentals"):
        assert named[key] == bare[key], key
    assert named["flops"] > 0


def test_the_mixer_counters_are_a_models_counters():
    from tpudist.trainer import _MetricDrain
    from tpudist.utils import AverageMeter
    assert {scopes.SSM_DT, scopes.SSM_CARRY} <= set(scopes.MODEL_COUNTERS)
    assert (scopes.SSM_DT, scopes.SSM_CARRY) == ("ssm_dt_mean",
                                                 "ssm_chunk_carry_min")
    drain = _MetricDrain({"loss": AverageMeter("Loss")})
    name = f"{scopes.SSM_CARRY}.layer_0"
    before = len(telemetry.counters().get(name, []))
    drain.push({"loss": 1.0, name: 0.25}, n=2, step=3)
    drain.drain()
    assert telemetry.counters()[name][before:] == [0.25]


def test_the_noise_of_block_diffusion_has_its_scope(decoder_step):
    """`bd_noise` (what `bd_noise_ms` of the chip benchmark sums) lies
    inside the forward scope of a step trained by diffusion over blocks,
    holds the draws, and is no part of a next-id step; attention's calls
    stay under `attn_fused` and the weighted loss under `tpudist_loss`."""
    named = [(line, n) for line, n in decoder_step
             if f"/{scopes.BD_NOISE}/" in n]
    diffusion = any("noise_key" in line or "/bd_noise/" in n
                    for line, n in decoder_step)
    if not diffusion:
        assert not named
        return
    assert named and all(scopes.FORWARD in n for _, n in named)
    assert all(phase_of(n) == "fwd" for _, n in named)
    # the draws: threefry's bits, or the rng's own instruction
    assert any("threefry" in n or "random_bits" in n or "rng" in line
               for line, n in named)
    assert not any(f"/{scopes.ATTN_FUSED}/" in n for _, n in named)
    # (the kernels are interpreted here: their operations carry the scope)
    fused = [n for _, n in decoder_step if f"/{scopes.ATTN_FUSED}/" in n]
    assert fused and all("/self_attention/" in n for n in fused)


def test_the_noise_counters_are_a_models_counters():
    """`bd_masked_share` and `bd_weight_sum` ride the step's metrics through
    the drain to `telemetry.counters()`, as an expert layer's do."""
    from tpudist import telemetry
    from tpudist.trainer import _MetricDrain
    from tpudist.utils import AverageMeter
    assert {scopes.BD_MASKED, scopes.BD_WEIGHT} <= set(scopes.MODEL_COUNTERS)
    assert (scopes.BD_NOISE, scopes.BD_MASKED, scopes.BD_WEIGHT) == (
        "bd_noise", "bd_masked_share", "bd_weight_sum")
    drain = _MetricDrain({"loss": AverageMeter("Loss")})
    before = {k: len(telemetry.counters().get(k, []))
              for k in (scopes.BD_MASKED, scopes.BD_WEIGHT)}
    drain.push({"loss": 1.0, scopes.BD_MASKED: 0.5, scopes.BD_WEIGHT: 1.25},
               n=2, step=3)
    drain.drain()
    assert telemetry.counters()[scopes.BD_MASKED][
        before[scopes.BD_MASKED]:] == [0.5]
    assert telemetry.counters()[scopes.BD_WEIGHT][
        before[scopes.BD_WEIGHT]:] == [1.25]


def test_the_dispatch_line_names_the_mask(tmp_path, capsys):
    """The trainer's `attention dispatch` line and decision of a model
    trained by diffusion over blocks: the mask kind, the block length, and
    the plan's heads a program, blocks and fill."""
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    cfg = from_args([
        "--synthetic", "-a", "sdar_tiny", "--seq-len", "32", "-b", "16",
        "--layers", "2", "--optimizer", "adamw", "--flash", "on", "--remat",
        "-j", "2", "--no-telemetry", "--outpath", str(tmp_path / "out"),
        "--overwrite", "delete", "--seed", "0"])
    trainer = Trainer(cfg, writer=None)
    dec = trainer.flash_decision
    assert dec["kernel"] == "flash" and dec["key"].endswith("_train_bd4")
    assert "_t64_" in dec["key"]                # the doubled row's positions
    plan, = dec["programs"]
    assert (plan["mask"], plan["block_length"]) == ("block_diffusion", 4)
    assert plan["heads_per_program"] == 4 and 0.0 < plan["band_fill"] <= 1.0
    log = open(os.path.join(cfg.outpath, "experiment.log")).read()
    assert (f"heads_per_program 4 block_q {plan['block_q']} block_k "
            f"{plan['block_k']} band_fill {plan['band_fill']} mask "
            f"block_diffusion block_length 4") in log


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/jvp(tpudist_forward)/ResNet/layer3_0/bn2/mul", "fwd"),
    ("jit(step)/transpose(jvp(tpudist_forward))/ResNet/layer4_1/conv2/"
     "conv_general_dilated", "bwd"),
    ("jit(step)/shard_map/jvp(tpudist_forward)/VisionTransformer/"
     "encoder_layer_3/self_attention/attn_scores/bqhd,bkhd->bhqk/dot_general",
     "fwd"),
    ("jit(step)/jvp(tpudist_loss)/jit(log_softmax)/reduce_max", "loss"),
    ("jit(step)/transpose(jvp(tpudist_loss))/jit(take_along_axis)/scatter-add",
     "bwd"),
    ("jit(step)/shard_map/tpudist_grad_reduce/psum", "reduce"),
    ("jit(step)/tpudist_optimizer/mul;jit(step)/shard_map", "opt"),
    ("jit(step)/shard_map;jit(step)/tpudist_optimizer/add", "opt"),
    ("jit(step)/tpudist_metrics/reduce_sum", "metrics"),
    ("jit(step)/tpudist_eval_forward/ResNet/fc/dot_general", None),
    ("jit(step)/mul", None),
    ("state.params['fc']['kernel']", None),
    ("", None),
])
def test_phase_of(op_name, phase):
    assert phase_of(op_name) == phase


# --- host spans of a loop turn ----------------------------------------------
def _capture_loop(tmp, inject="", loader_of=None):
    """Host annotation rows [name, start_ns, end_ns] of a three-step epoch
    under the profiler (the python thread's line they were on), and the
    trainer that ran it. `loader_of(trainer, batches)` may wrap the list of
    batches; `inject` arms a fault (tpudist/faults.py) for the run."""
    from tpudist import faults
    from tpudist.trainer import Trainer
    cfg = Config(arch="resnet18", num_classes=8, image_size=32, batch_size=16,
                 epochs=1, lr=0.02, workers=0, print_freq=1, synthetic=True,
                 use_amp=False, telemetry=False, outpath=str(tmp / "out"),
                 overwrite="delete", seed=0, inject=inject)
    try:
        trainer = Trainer(cfg, writer=None)
        rng = np.random.default_rng(0)

        class Loader(list):
            pass

        batches = Loader(
            (rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
             rng.integers(0, 8, size=(16,)).astype(np.int32))
            for _ in range(3))
        trainer.train_epoch(batches[:1], 0, 0.02)        # compile outside
        jax.block_until_ready(trainer.state)
        if loader_of is not None:
            batches = loader_of(trainer, batches)
        jax.profiler.start_trace(str(tmp / "trace"))
        try:
            trainer.train_epoch(batches, 0, 0.02)
            jax.block_until_ready(trainer.state)
        finally:
            jax.profiler.stop_trace()
    finally:
        faults.configure("")
    path = next((tmp / "trace").rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            rows = [[e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)]
                    for e in line.events
                    if e.name.startswith("tpudist.") or e.name == scopes.STEP]
            if rows:
                lines[line.name] = sorted(rows, key=lambda r: (r[1], -r[2]))
    assert len(lines) == 1, list(lines)      # the loop runs on one thread
    return next(iter(lines.values())), trainer


@pytest.fixture(scope="module")
def loop_capture(tmp_path_factory):
    return _capture_loop(tmp_path_factory.mktemp("spans"))[0]


@pytest.mark.parametrize("name,at_least", [
    (scopes.SPAN_LOADER_NEXT, 3), (scopes.SPAN_DISPATCH, 3),
    (scopes.SPAN_DRAIN_READY, 3), (scopes.SPAN_LOOP_HOST, 7),
    (scopes.SPAN_PREFETCH, 3), (scopes.SPAN_H2D, 3), (scopes.STEP, 3),
    (scopes.SPAN_LOOP_PROLOGUE, 1), (scopes.SPAN_LOOP_HOOKS, 3),
    (scopes.SPAN_LOOP_METERS, 3), (scopes.SPAN_LOOP_LOG, 3),
    (scopes.SPAN_LOOP_EPOCH_END, 1)])
def test_loop_turn_holds_span(loop_capture, name, at_least):
    assert sum(r[0] == name for r in loop_capture) >= at_least


def _top_level(rows):
    """The rows no other row contains."""
    return [r for r in rows
            if not any(p is not r and p[1] <= r[1] and r[2] <= p[2]
                       for p in rows)]


def test_loop_spans_nest_properly(loop_capture):
    """A child lies inside its parent, siblings are disjoint: self time =
    span minus children. The loop's own activities lie beside `loop_host`,
    inside nothing: the span that overlaps an idle gap most is handed it,
    and a parent would outlast them."""
    stack = []
    for name, start, end in loop_capture:
        while stack and stack[-1][2] <= start:
            stack.pop()
        if stack:
            assert end <= stack[-1][2], (name, stack[-1][0])
        stack.append((name, start, end))
    inside = {scopes.SPAN_DISPATCH: scopes.STEP,
              scopes.SPAN_DRAIN_READY: scopes.SPAN_LOOP_HOST,
              scopes.SPAN_LOADER_NEXT: scopes.SPAN_PREFETCH}
    for child, parent in inside.items():
        for _, start, end in (r for r in loop_capture if r[0] == child):
            assert any(p[0] == parent and p[1] <= start and end <= p[2]
                       for p in loop_capture), (child, parent)
    top = _top_level(loop_capture)
    assert {r[0] for r in top} == {
        scopes.SPAN_LOOP_HOST, scopes.STEP, scopes.SPAN_METRIC_DRAIN,
        *scopes.LOOP_ACTIVITIES}
    assert all(r in top for r in loop_capture
               if r[0] in scopes.LOOP_ACTIVITIES)


def test_loop_turn_is_covered(loop_capture):
    """From the `loop_host` span that takes a turn's batch to the next
    turn's, at least 95 % of the host's time lies inside a top-level span
    of the program's: `loop_host`, the step annotation, the loop's
    activities, the metric drain (the rest nest inside those)."""
    top = _top_level(loop_capture)
    # a turn starts where loop_host hands over to the hooks; the epoch's
    # last loop_host span finds the loader empty
    starts = [i for i, r in enumerate(top[:-1])
              if r[0] == scopes.SPAN_LOOP_HOST
              and top[i + 1][0] == scopes.SPAN_LOOP_HOOKS]
    assert len(starts) == 3
    ends = starts[1:] + [max(i for i, r in enumerate(top)
                             if r[0] == scopes.SPAN_LOOP_HOST)]
    for first, last in zip(starts, ends):
        assert scopes.STEP in [r[0] for r in top[first:last]]
        turn = top[last][1] - top[first][1]
        covered = sum(r[2] - r[1] for r in top[first:last])
        assert covered >= 0.95 * turn, (covered, turn)


@pytest.fixture(scope="module")
def stalled_capture(tmp_path_factory):
    """A captured epoch (global steps 1-3) in which the host stalls once
    (`slow_peer` armed for step 2 alone, 300 ms) and a function is jitted on
    a fresh shape while the loader hands out the third batch."""
    seen = []

    def loader_of(trainer, batches):
        class Loader(list):
            def __iter__(self):
                for k, batch in enumerate(list.__iter__(self)):
                    if k == 2:
                        seen.append(trainer.global_step)
                        jax.jit(lambda x: 2.0 * x + 1.0)(
                            np.zeros((3, 5, 7), np.float32))
                    yield batch
        return Loader(batches)

    before = len(telemetry.compile_events())
    rows, trainer = _capture_loop(tmp_path_factory.mktemp("stall"),
                                  inject="slow_peer:ms=300@step=2",
                                  loader_of=loader_of)
    log = open(os.path.join(trainer.cfg.outpath, "experiment.log")).read()
    return dict(rows=rows, seen=seen, log=log,
                events=telemetry.compile_events()[before:])


def test_a_stall_is_named_by_the_loops_activity(stalled_capture):
    """The longest of the loop's activities in the captured epoch is the
    hooks' span of the stalled turn, and it holds the whole stall: what
    `loop_host_max_ms` of the chip benchmark reads. No span of the
    program's lies inside it or around it, so it is what overlaps the idle
    gap the stall leaves on the device most."""
    rows = stalled_capture["rows"]
    own = [r for r in rows if r[0] in scopes.LOOP_ACTIVITIES]
    name, start, end = stalled = max(own, key=lambda r: r[2] - r[1])
    assert name == scopes.SPAN_LOOP_HOOKS
    assert end - start >= 300e6
    assert max(r[2] - r[1] for r in own if r is not stalled) < 150e6
    assert not [r for r in rows if start < r[1] and r[2] < end]
    assert stalled in _top_level(rows)


@pytest.mark.parametrize("where", ["compile_events", "log"])
def test_a_compile_between_two_steps_is_seen(stalled_capture, where):
    """`telemetry.compile_events()` keeps the compile with the trainer's
    step at the time, and the trainer logs it: it ended after the first
    dispatch."""
    step, = stalled_capture["seen"]
    assert step in (1, 2)            # the prefetcher stages a turn ahead
    if where == "log":
        assert (f"=> compile after step {step}: {telemetry.COMPILE_EVENT} "
                in stalled_capture["log"])
        return
    compiles = [ev for ev in stalled_capture["events"]
                if ev["event"] == telemetry.COMPILE_EVENT
                and ev["step"] == step]
    assert compiles, stalled_capture["events"]
    assert all(ev["seconds"] > 0 and ev["t_end"] <= time.perf_counter()
               for ev in compiles)
    # a copy: the kept events are the module's own
    stalled_capture["events"][0]["step"] = -1
    assert all(ev["step"] != -1 for ev in telemetry.compile_events())


# --- set-up phases -----------------------------------------------------------
@pytest.fixture(scope="module")
def constructed(tmp_path_factory):
    from tpudist.trainer import Trainer
    tmp = tmp_path_factory.mktemp("phases")
    cfg = Config(arch="resnet18", num_classes=8, image_size=32, batch_size=16,
                 epochs=1, synthetic=True, use_amp=False, telemetry=False,
                 outpath=str(tmp / "out"), overwrite="delete", seed=0)
    t0 = time.monotonic()
    Trainer(cfg, writer=None)
    return time.monotonic() - t0, telemetry.phases()


@pytest.mark.parametrize("name", scopes.INIT_PHASES)
def test_constructor_books_phase(constructed, name):
    _, phases = constructed
    assert name in phases and phases[name] >= 0.0


def test_phases_sum_to_the_constructor_and_survive_clear(constructed):
    wall, phases = constructed
    booked = sum(v for k, v in phases.items() if k.startswith("init."))
    assert abs(booked - wall) <= 0.05 * wall, (booked, wall)
    # the eager model.init + tx.init is the constructor's bulk
    assert phases[scopes.INIT_MODEL_STATE] > 0.5 * booked
    telemetry.clear_pending()
    assert telemetry.phases() == phases
    copy = telemetry.phases()
    copy["init.mesh"] = -1.0
    assert telemetry.phases() == phases
