"""`harness/scope_reduce.py`: the protobuf wire reader on an `HloProto` built
by hand and on a real CPU capture, the benchmark's copy of `phase_of` against
the program's, `by_scope` on rows small enough to sum by hand, and on a
recorded chip trace (`recorded_scopes.json.gz`, cut by `record_scopes.py` from
a traced run of `resnet18_staged` on a TPU v5e). Run by path."""

import gzip
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import scope_reduce  # noqa: E402

US = 1000
FWD = "jit(step)/jvp(tpudist_forward)/ResNet/layer1_0/conv1/conv_general_dilated"
BWD = "jit(step)/transpose(jvp(tpudist_forward))/ResNet/layer1_0/conv1/mul"
OPT = "jit(step)/tpudist_optimizer/add"


# --- a protobuf writer of a dozen lines, for the hand-built messages ---------
def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def instruction(name, opcode, op_name="", called=(), uid=0, operands=()):
    msg = field(1, name) + field(2, opcode)
    if op_name:
        msg += field(7, field(1, "op_type") + field(2, op_name))
    if uid:
        msg += field(35, uid)
    for number, ids in ((36, operands), (38, called)):
        if ids:                                   # packed repeated int64
            msg += field(number, b"".join(varint(i) for i in ids))
    return field(2, msg)


def hlo_proto():
    fused = field(1, "fused_computation") + field(5, 7) + \
        instruction("param_0", "parameter") + \
        instruction("mul.1", "multiply", BWD) + \
        instruction("add.2", "add", OPT)
    entry = field(1, "main") + field(5, 9) + \
        instruction("p", "parameter", "state.params['w']", uid=1) + \
        instruction("convolution.3", "convolution", FWD, uid=2,
                    operands=(1,)) + \
        instruction("fusion.4", "fusion", OPT, called=(7,), uid=3,
                    operands=(2,)) + \
        instruction("copy.5", "copy", uid=4, operands=(1,)) + \
        instruction("copy-start.6", "copy-start", uid=5, operands=(2,)) + \
        instruction("copy-done.7", "copy-done", uid=6, operands=(5,))
    return field(1, field(1, "jit_step") + field(3, fused) + field(3, entry))


def xspace(proto):
    other = field(2, "/device:TPU:0") + field(3, b"\x00" * 64)
    stat_md = field(5, field(1, 3) + field(2, field(1, 3) + field(2, "Hlo Proto")))
    event_md = field(4, field(1, 11) + field(2, field(1, 11) + field(
        2, "jit_step(77)") + field(5, field(1, 3) + field(6, proto))))
    unrelated = field(4, field(1, 12) + field(2, field(1, 12) + field(
        2, "jit_other(5)") + field(5, field(1, 3) + field(6, b""))))
    meta = field(2, "/host:metadata") + stat_md + unrelated + event_md
    return field(1, other) + field(1, meta)


def test_wire_reader_on_a_hand_built_hlo_proto(tmp_path):
    want = {"ops": {"param_0": "", "mul.1": BWD, "add.2": OPT,
                    "p": "state.params['w']", "convolution.3": FWD,
                    "fusion.4": OPT, "copy.5": "state.params['w']",
                    "copy-start.6": FWD, "copy-done.7": FWD},
            "members": {"fusion.4": [BWD, OPT]},
            "bare": {"copy.5": "copy", "copy-start.6": "copy-start",
                     "copy-done.7": "copy-done"}}
    assert scope_reduce.parse_hlo_proto(hlo_proto()) == want
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace(hlo_proto()))
    got = scope_reduce.hlo_scopes(str(path), "jit_step")
    assert got == {"jit_step(77)": want}
    assert scope_reduce.hlo_scopes(str(path), "jit_absent") is None
    assert scope_reduce.hlo_scopes(str(path), "jit_other") is None  # empty stat
    path.write_bytes(field(1, field(2, "/device:TPU:0")))
    assert scope_reduce.hlo_scopes(str(path), "jit_step") is None   # no plane


def test_two_step_programs_in_one_trace_stay_apart(tmp_path):
    """Instruction names repeat between programs (`fusion.4` is one thing in
    `jit_step(77)` and another in `jit_step(78)`): the maps are kept by the
    module's full name and each step's rows are named by its own."""
    other = hlo_proto().replace(OPT.encode(), FWD.encode()[:len(OPT)])
    assert len(other) == len(hlo_proto())
    meta = field(2, "/host:metadata") + field(5, field(1, 3) + field(
        2, field(1, 3) + field(2, "Hlo Proto")))
    for mid, proto in ((77, hlo_proto()), (78, other)):
        meta += field(4, field(1, mid) + field(2, field(1, mid) + field(
            2, f"jit_step({mid})") + field(5, field(1, 3) + field(6, proto))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, meta))
    got = scope_reduce.hlo_scopes(str(path), "jit_step")
    assert sorted(got) == ["jit_step(77)", "jit_step(78)"]
    assert got["jit_step(77)"]["ops"]["fusion.4"] == OPT
    assert got["jit_step(78)"]["ops"]["fusion.4"] == FWD[:len(OPT)]
    events = hand_made()
    events["devices"][0]["modules"][2][0] = "jit_step(78)"
    r = scope_reduce.by_scope(events, got, "jit_step")
    assert r["modules"] == ["jit_step(77)", "jit_step(78)"]
    assert r["phase_ms"]["opt"] == pytest.approx(0.060)   # step 77's alone
    assert r["phase_ms"]["fwd"] == pytest.approx(0.100 + 0.060)


def test_wire_reader_on_a_real_cpu_capture(tmp_path):
    import jax
    import jax.numpy as jnp
    from harness import trace_reduce
    jax.config.update("jax_enable_compilation_cache", False)

    def f(x, w):
        with jax.named_scope("tpudist_forward"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("tpudist_optimizer"):
            return w + 0.1 * y.sum()

    jf = jax.jit(f)
    x = w = jnp.ones((64, 64))
    text = jf.lower(x, w).compile().as_text()
    jf(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jf(x, w).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        jax.config.update("jax_enable_compilation_cache", True)
    (got,) = scope_reduce.hlo_scopes(
        trace_reduce.newest_xplane(str(tmp_path)), "jit_f").values()
    want = dict(re.findall(
        r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"', text, re.M))
    assert len(want) > 5
    heirs = set(got["bare"])           # bare instructions of the compiler's
    assert {k: v for k, v in got["ops"].items()
            if v and k not in heirs} == want
    assert all(got["ops"][k] in set(want.values()) | {""} for k in heirs)
    fusions = [k for k in got["members"] if "fusion" in k]
    assert fusions and all(
        m.startswith("jit(f)/tpudist_") for k in fusions
        for m in got["members"][k])


def test_phase_of_agrees_with_the_programs():
    from tpudist.obs import scopes
    names = [f"jit(step)/{wrap.format(scope)}/Model/block/op"
             for scope in scopes.DEVICE_SCOPES + ("other", scopes.ATTN_SCORES)
             for wrap in ("{}", "jvp({})", "transpose(jvp({}))",
                          "shard_map/{}", "{};jit(step)/shard_map",
                          "shard_map;jit(step)/{}")]
    names += ["", "jit(step)/mul", "state.step",
              f"jit(step)/{scopes.OPTIMIZER}/add;jit(step)/"
              f"transpose(jvp({scopes.FORWARD}))/M/b/mul"]
    seen = set()
    for name in names:
        assert scope_reduce.phase_of(name) == scopes.phase_of(name), name
        seen.add(scope_reduce.phase_of(name))
    assert seen == set(scope_reduce.PHASES) | {None}


def test_block_of():
    assert scope_reduce.block_of(FWD) == "layer1_0/conv1"
    assert scope_reduce.block_of(BWD) == "layer1_0/conv1"
    assert scope_reduce.block_of(
        "jit(step)/jvp(tpudist_forward)/VisionTransformer/encoder_layer_3/"
        "self_attention/attn_scores/bqhd,bkhd->bhqk/dot_general") \
        == "encoder_layer_3/self_attention"
    assert scope_reduce.block_of(
        "jit(step)/jvp(tpudist_forward)/ResNet/reduce_window_max") == "(top)"
    assert scope_reduce.block_of(
        "jit(step)/jvp(tpudist_forward)/ResNet/fc/dot_general") == "fc"
    assert scope_reduce.block_of(OPT) is None


def test_label_ops_names_what_is_ranked_and_leaves_the_rest_bare():
    attn = ("jit(step)/transpose(jvp(tpudist_forward))/VisionTransformer/"
            "encoder_layer_3/self_attention/attn_scores/bqhd,bkhd->bhqk/"
            "dot_general")
    scopes = {"top_ops": [["fusion.87", 0.893, "bwd", attn],
                          ["fusion.4", 0.5, "opt", OPT],
                          ["copy.5", 0.2, "layout_copy", FWD],
                          ["fusion.9", 0.1, "fwd", FWD]]}
    ops = [["fusion.87", 0.0259], ["copy.5", 0.006], ["fusion.4", 0.005],
           ["fusion.9", 0.004], ["fusion.1", 0.003]]
    assert scope_reduce.label_ops(ops, scopes) == [
        ["fusion.87 [bwd encoder_layer_3/self_attention/attn_scores]", 0.0259],
        ["copy.5 [layout_copy]", 0.006], ["fusion.4 [opt]", 0.005],
        ["fusion.9 [fwd layer1_0/conv1]", 0.004], ["fusion.1", 0.003]]
    assert scope_reduce.label_ops(ops, None) == ops


def hand_made():
    """One device, a window of [100, 1100) us. Steps (module events):
    [0, 300) starts before the window: not whole; [400, 700) and [750, 1050)
    whole; `jit_other` is another program. In each whole step: a forward
    conv 100 us, a fusion of 120 us whose own name is the optimizer's and
    whose members span backward and optimizer, a bare copy of a parameter
    (no scope even through its operand) 30 us, a bare `copy-done` behind the
    forward conv 5 us, an op the scope map does not know 10 us."""
    # by hand, a step: fwd 100, opt-rooted fusion 120 (mixed bwd+opt), bare
    # copies 30 + 5 (layout_copy, the 5 behind a forward op), unscoped 10
    def step(t0):
        return [["convolution.3", t0 * US, 100 * US, "convolution"],
                ["fusion.4", (t0 + 110) * US, 120 * US, "kind=kLoop"],
                ["copy.5", (t0 + 240) * US, 30 * US, ""],
                ["copy-done.7", (t0 + 272) * US, 5 * US, ""],
                ["mystery.9", (t0 + 280) * US, 10 * US, ""]]
    return {
        "devices": [{"name": "/device:TPU:0",
                     "ops": step(0) + step(400) + step(750) + [
                         ["convolution.3", 1060 * US, 20 * US, ""]],
                     "modules": [["jit_step(77)", 0, 300 * US],
                                 ["jit_step(77)", 400 * US, 300 * US],
                                 ["jit_step(77)", 750 * US, 300 * US],
                                 ["jit_other(5)", 1055 * US, 30 * US]]}],
        "host": [["bench.window", 100 * US, 1000 * US]]}


def test_by_scope_on_hand_made_rows():
    scopes = {"jit_step(77)": scope_reduce.parse_hlo_proto(hlo_proto())}
    r = scope_reduce.by_scope(hand_made(), scopes, "jit_step")
    assert r["steps"] == 2 and r["modules"] == ["jit_step(77)"]
    assert r["phase_ms"]["fwd"] == pytest.approx(0.100)
    assert r["phase_ms"]["opt"] == pytest.approx(0.120)     # its own name's
    assert r["phase_ms"]["bwd"] == 0
    assert r["phase_ms"]["layout_copy"] == pytest.approx(0.035)
    assert r["layout_copy_behind_ms"] == {
        "fwd": pytest.approx(0.005), "unscoped": pytest.approx(0.030)}
    assert r["layout_copy_opcodes_ms"] == {
        "copy": pytest.approx(0.030), "copy-done": pytest.approx(0.005)}
    assert r["phase_ms"]["unscoped"] == pytest.approx(0.010)
    assert r["busy_step_ms"] == pytest.approx(0.265)
    assert sum(r["phase_ms"].values()) == pytest.approx(r["busy_step_ms"])
    assert r["mixed_ms"] == pytest.approx(0.120)
    assert r["mixed_phase_pct"] == pytest.approx(100 * 120 / 265)
    assert r["mixed_by_phases_ms"] == {"bwd+opt": pytest.approx(0.120)}
    assert r["named_pct"] == pytest.approx(100 * 255 / 265)
    assert r["blocks"] == [["layer1_0/conv1", pytest.approx(0.100), 0.0]]
    assert r["top_ops"][0][:3] == ["fusion.4", pytest.approx(0.120), "opt"]
    assert [op[0] for op in r["unscoped_ops"]] == ["mystery.9"]
    assert r["scoped_by_members_ms"] == 0
    # a fusion named after a root of the compiler's own making (no scope in
    # its own name, or no name at all) goes to its first scoped member's phase
    names = scopes["jit_step(77)"]
    for renamed in (dict(names, ops=dict(names["ops"], **{"fusion.4": "copy.7"})),
                    dict(names, bare=dict(names["bare"], **{"fusion.4": "fusion"}))):
        r = scope_reduce.by_scope(hand_made(), {"jit_step(77)": renamed},
                                  "jit_step")
        assert r["phase_ms"]["bwd"] == pytest.approx(0.120)
        assert r["phase_ms"]["opt"] == 0
        assert r["phase_ms"]["layout_copy"] == pytest.approx(0.035)
        assert r["scoped_by_members_ms"] == pytest.approx(0.120)
        assert r["blocks"][0] == ["layer1_0/conv1", pytest.approx(0.100),
                                  pytest.approx(0.120)]
    # forward and loss are one group: a fusion of the two is not mixed
    both = dict(names, members={"fusion.4": [
        FWD, "jit(step)/jvp(tpudist_loss)/reduce_sum"]},
        ops=dict(names["ops"], **{"fusion.4": FWD}))
    r = scope_reduce.by_scope(hand_made(), {"jit_step(77)": both}, "jit_step")
    assert r["mixed_ms"] == 0 and r["mixed_by_phases_ms"] == {}
    # a step of a module the trace holds no names for: all of it unscoped
    r = scope_reduce.by_scope(hand_made(), {"jit_step(1)": names}, "jit_step")
    assert r["phase_ms"]["unscoped"] == pytest.approx(r["busy_step_ms"])
    assert r["named_pct"] == 0
    # no window span: every whole module event counts
    bare = dict(hand_made(), host=[])
    assert scope_reduce.by_scope(bare, scopes, "jit_step")["steps"] == 3
    assert scope_reduce.by_scope({"devices": [], "host": []}, scopes,
                                 "jit_step") == {"steps": 0}


def test_step_scopes_says_why_it_refuses(tmp_path, monkeypatch, capsys):
    """`step_scopes` gives None, and says why on `bench scope_ms`, where more
    than 1 % of a step is under no scope or the names hold no
    `tpudist_forward`; a stale compile-cache entry's names are reported as
    such."""
    names = scope_reduce.parse_hlo_proto(hlo_proto())
    calls = iter(range(100))

    def run(scopes, events):
        monkeypatch.setattr(scope_reduce, "_newest_trace_dir",
                            lambda chip_dir: str(tmp_path))
        monkeypatch.setattr(scope_reduce.trace_reduce, "newest_xplane",
                            lambda d: f"trace{next(calls)}")
        monkeypatch.setattr(scope_reduce.trace_reduce, "extract",
                            lambda *a: events)
        monkeypatch.setattr(scope_reduce, "hlo_scopes", lambda p, m: scopes)
        ctx = {"peak": {"trace_device_plane_prefix": "", "trace_op_lines": [],
                        "trace_module_lines": []},
               "trace": {"device_steps": 2,
                         "idle_gaps": [["host.unattributed", 0.004]]}}
        got = scope_reduce.step_scopes(ctx)
        return got, capsys.readouterr().out

    got, out = run({"jit_step(77)": names}, hand_made())      # 10 of 265 us
    assert got is None and "under no scope" in out and "mystery.9" in out
    known = dict(names, ops=dict(names["ops"], **{"mystery.9": FWD}))
    got, out = run({"jit_step(77)": known}, hand_made())
    assert got["phase_ms"]["unscoped"] == 0
    assert got["idle_unattributed_ms"] == pytest.approx(2.0)
    assert got["scopes_seen"] == ["tpudist_forward", "tpudist_optimizer"]
    assert got["names"] == "stale compile-cache entry"    # no tpudist_loss
    assert json.load(open(tmp_path / "scopes.json"))["steps"] == 2
    bare = {k: v.replace("tpudist_forward", "fwd")
            for k, v in known["ops"].items()}
    got, out = run({"jit_step(77)": dict(known, ops=bare, members={})},
                   hand_made())
    assert got is None and "no tpudist_forward" in out
    got, out = run(None, hand_made())
    assert got is None and "no Hlo Proto" in out


def test_by_scope_keeps_a_loops_self_time():
    """An operation that contains others (a loop around its body) keeps what
    they leave, so the phases still sum to the step's busy time."""
    scopes = {"jit_step(1)": {"ops": {"while.1": OPT, "convolution.3": FWD},
                              "members": {}, "bare": {}}}
    events = {"devices": [{"name": "d", "ops": [
        ["while.1", 0, 100 * US, ""],
        ["convolution.3", 10 * US, 30 * US, ""],
        ["convolution.3", 50 * US, 30 * US, ""]],
        "modules": [["jit_step(1)", 0, 100 * US]]}], "host": []}
    r = scope_reduce.by_scope(events, scopes, "jit_step")
    assert r["phase_ms"]["opt"] == pytest.approx(0.040)
    assert r["phase_ms"]["fwd"] == pytest.approx(0.060)
    assert r["busy_step_ms"] == pytest.approx(0.100)


def test_recorded_chip_trace():
    """0.5 s of a traced `resnet18_staged` run (TPU v5e): three whole steps
    of ~151 ms whose phases sum to the busy time, nearly all of it under the
    program's scopes, backward about twice forward."""
    with gzip.open(os.path.join(HERE, "recorded_scopes.json.gz"), "rt") as f:
        rec = json.load(f)
    r = scope_reduce.by_scope(rec, rec["scopes"], rec["module"])
    assert r["steps"] == 3 and len(r["modules"]) == 1
    phase = r["phase_ms"]
    assert sum(phase.values()) == pytest.approx(r["busy_step_ms"])
    assert 145 < r["busy_step_ms"] < 155
    assert phase["unscoped"] < 0.0001 * r["busy_step_ms"]
    assert 0.05 < phase["layout_copy"] < 0.5      # bare copy-done / copy.N
    copies = sum(v for k, v in r["layout_copy_opcodes_ms"].items()
                 if k in ("copy", "copy-start", "copy-done"))
    assert copies > 0.99 * phase["layout_copy"]   # the rest: iota, slice-*
    assert 0.5 * r["busy_step_ms"] < r["mixed_ms"] < 0.7 * r["busy_step_ms"]
    assert r["named_pct"] > 99.9
    assert 1.5 < phase["bwd"] / (phase["fwd"] + phase["loss"]) < 3.0
    assert phase["reduce"] + phase["opt"] + phase["metrics"] < 10
    blocks = {b[0] for b in r["blocks"]}
    assert {"layer1_0/conv1", "layer4_1/bn2", "conv1", "fc"} <= blocks
