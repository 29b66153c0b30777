"""The resident feed, the configuration's way to the reference, and the
roofline arithmetic: on the CPU, in seconds, with no `Trainer`.

Run by path (`python -m pytest benchmarks/chip/selftest/test_feed_cpu.py -q`);
tier-1 does not collect this directory. What is checked:

- a mix that says no more than `traffic/staged.json` draws the batches it drew
  before the feed learned of tokens (values taken on the parent commit,
  `bede19f`, at a small size, for two seeds);
- token rows (`traffic/staged_tokens_8k.json`, and a short copy of it) have
  the stated shapes and type, ids inside the held vocabulary, next-id targets,
  rows that all differ; one seed repeats and another differs;
- a mix whose needs the configuration does not meet is refused by name;
- a configuration with a list and a nested object reaches a stub reference
  whole, at both of its doors (`init`, `step`);
- the readings of the program's state (`check.Reducers`) are taken over runs
  of leaves under a bound of bytes, and read the same whatever the bound;
- `harness/roofline.py` on hand-made scopes: a compute-bound call, a
  memory-bound one, both together, layout copies behind the kernel left out,
  and nothing under the scope -> no metric;
- `metrics/attn_fused_roofline.py`: its counts at ViT-B/16's shape, the
  program's `cost_estimate` read from an HLO line, and None without the
  kernel.

A CPU run reports no device time: the roofline cases feed hand-made times.
"""

import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import check, roofline, scope_reduce, traffic  # noqa: E402
from harness.errors import Refuse  # noqa: E402


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def one_device():
    import jax
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


def source(mix, config, *, seed, batch=4):
    return traffic.make_source(mix, seed=seed, batch=batch, config=config,
                               sharding=one_device())


# --- today's mix gives today's batches ---------------------------------------
# per seed, per batch: pixels [0, 0, 0, :] and [3, 7, 7, 2], the float64 sum of
# all pixels, the labels; batch 4 at 8 px over 10 classes, on the parent commit
GOLDEN = {
    11: [
        ([-0.40459099411964417, -1.7296923398971558, -0.9046429991722107,
          1.2379120588302612], 6.121300183236599, [9, 4, 4, 9]),
        ([0.2787145674228668, 1.0909849405288696, -1.8780115842819214,
          -0.7384749054908752], -33.97579833341297, [4, 8, 2, 1]),
        ([0.7338086366653442, 0.014073751866817474, -1.109688401222229,
          -0.1482391208410263], 19.48852130724117, [0, 3, 3, 0])],
    2147483000: [
        ([-2.022183656692505, -0.7228928804397583, 1.0655754804611206,
          0.7863519191741943], -48.64996615887503, [0, 4, 8, 1]),
        ([0.07825182378292084, -0.31790629029273987, 0.9415979385375977,
          -0.12179248034954071], 26.08671703597065, [2, 8, 5, 3]),
        ([-0.13415218889713287, -1.056193470954895, 1.1449223756790161,
          0.739202618598938], 0.5436806246289052, [3, 3, 6, 5])],
}


@pytest.mark.parametrize("seed", sorted(GOLDEN))
def test_staged_mix_draws_the_batches_it_drew_before(seed):
    src = source(load(CHIP, "traffic", "staged.json"),
                 {"name": "small", "image_size": 8, "num_classes": 10},
                 seed=seed)
    assert src.info == {"kind": "resident", "rows": "images",
                        "distinct_batches": 3}
    for (images, labels), (px, total, want) in zip(src.first(3), GOLDEN[seed]):
        images, labels = np.asarray(images), np.asarray(labels)
        assert images.shape == (4, 8, 8, 3) and images.dtype == np.float32
        assert labels.shape == (4,) and labels.dtype == np.int32
        assert [float(x) for x in images[0, 0, 0]] + [
            float(images[3, 7, 7, 2])] == px
        assert float(images.astype(np.float64).sum()) == total
        assert labels.tolist() == want
    # and it cycles: the fourth pull is the first batch again
    pulls = [src.next() for _ in range(4)]
    assert pulls[3][0] is pulls[0][0]


# --- token rows --------------------------------------------------------------
SHORT_TOKENS = dict(load(CHIP, "traffic", "staged_tokens_8k.json"),
                    name="staged_tokens_short", seq_len=24)
HELD_VOCAB = {"name": "sliced", "vocab_size": 50, "per_chip_batch": 4}


@pytest.mark.parametrize("mix,vocab,batch", [
    (SHORT_TOKENS, 50, 4), (SHORT_TOKENS, 3, 5),
    (load(CHIP, "traffic", "staged_tokens_8k.json"), 24576, 2)],
    ids=["short", "short_vocab3", "8k"])
def test_token_rows(mix, vocab, batch):
    import jax
    config = dict(HELD_VOCAB, vocab_size=vocab)
    src = source(mix, config, seed=7, batch=batch)
    t = mix["seq_len"]
    assert src.info == {"kind": "resident", "rows": "tokens",
                        "distinct_batches": 3, "seq_len": t,
                        "vocab_size": vocab, "tokens_per_batch": batch * t}
    seen = []
    for inputs, targets in src.first(3):
        assert inputs.sharding == one_device() == targets.sharding
        assert isinstance(inputs, jax.Array) and isinstance(targets, jax.Array)
        inputs, targets = np.asarray(inputs), np.asarray(targets)
        assert inputs.shape == targets.shape == (batch, t)
        assert inputs.dtype == targets.dtype == np.int32
        for ids in (inputs, targets):
            assert ids.min() >= 0 and ids.max() < vocab
        assert np.array_equal(targets[:, :-1], inputs[:, 1:])
        seen.extend(map(bytes, inputs))
    if vocab ** t > 10 ** 9:       # rows of three ids over 24 places may meet
        assert len(set(seen)) == 3 * batch
    again = source(mix, config, seed=7, batch=batch)
    other = source(mix, config, seed=8, batch=batch)
    for mine, same, differs in zip(src.first(3), again.first(3),
                                   other.first(3)):
        assert np.array_equal(mine[0], same[0])
        assert np.array_equal(mine[1], same[1])
        assert not np.array_equal(mine[0], differs[0])


def test_a_sliced_vocabulary_draws_every_id_of_the_slice_and_no_other():
    src = source(dict(SHORT_TOKENS, seq_len=4096), dict(HELD_VOCAB,
                                                         vocab_size=16),
                 seed=3)
    ids = np.concatenate([np.asarray(i).ravel() for i, _ in src.first(3)])
    assert sorted(set(ids.tolist())) == list(range(16))


@pytest.mark.parametrize("mix,config,names", [
    (SHORT_TOKENS, {"name": "images_only", "image_size": 8,
                    "num_classes": 10},
     ["staged_tokens_short", "vocab_size", "images_only"]),
    (SHORT_TOKENS, dict(HELD_VOCAB, vocab_size=0),
     ["staged_tokens_short", "vocab_size"]),
    (dict(SHORT_TOKENS, seq_len=None), HELD_VOCAB,
     ["staged_tokens_short", "seq_len"]),
    (load(CHIP, "traffic", "staged.json"), HELD_VOCAB,
     ["staged", "image_size", "sliced"]),
    (dict(SHORT_TOKENS, rows="audio"), HELD_VOCAB, ["audio", "tokens"]),
    (dict(SHORT_TOKENS, kind="replay"), HELD_VOCAB, ["replay", "resident"]),
    (dict(SHORT_TOKENS, distinct_batches=2), HELD_VOCAB,
     ["distinct_batches"])],
    ids=["tokens_no_vocab", "vocab_zero", "no_seq_len", "images_no_size",
         "unknown_rows", "unknown_kind", "two_batches"])
def test_a_mix_the_configuration_cannot_feed_is_refused_by_name(
        mix, config, names):
    with pytest.raises(Refuse) as refusal:
        source(mix, config, seed=1)
    assert all(name in str(refusal.value) for name in names)


def test_run_py_turns_a_refusal_into_exit_3_and_no_result(monkeypatch, capsys):
    """`run.py`'s own handling, past its imports: `Refuse` from anywhere in
    the run is exit code 3 with the reason on standard error."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", os.path.join(CHIP, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    from harness import cellrun

    def refuse(**_):
        raise Refuse("traffic mix 'm' needs 'vocab_size'")

    monkeypatch.setattr(cellrun, "run_cell", refuse)
    code = run.main(["--workload", "vit_b16_staged", "--seed", "1",
                     "--seconds", "1"])
    out = capsys.readouterr()
    assert code == 3 and out.out == ""
    assert "refused: traffic mix 'm' needs 'vocab_size'" in out.err


# --- the whole configuration reaches the reference ---------------------------
def test_the_reference_is_handed_the_whole_configuration():
    import jax.numpy as jnp
    config = {"name": "patterned", "hidden_size": 8,
              "layer_types": ["sliding_attention", "sliding_attention",
                              "sliding_attention", "full_attention"],
              "rope_parameters": {"full_attention": {"rope_type": "yarn",
                                                     "factor": 16.0},
                                  "sliding_attention": {"rope_theta": 5e5}},
              "tie_word_embeddings": False, "optimizer": "adamw"}
    got = {}

    def init(key, cfg):
        got["init"] = cfg
        return {"w": jnp.ones((len(cfg["layer_types"]), cfg["hidden_size"]))
                * cfg["rope_parameters"]["full_attention"]["factor"]}, {}

    def step(params, stats, opt, inputs, targets, cfg, lr, quant=None):
        got["step"] = cfg
        grads = {"w": jnp.ones_like(params["w"])}
        return (jnp.float32(1.0), grads,
                {"w": params["w"] - lr * grads["w"]}, stats, opt)

    ref = types.SimpleNamespace(init=init, init_opt=lambda p: {}, step=step)
    p0, s0 = check.seeded_weights(ref, config, 5, one_device())
    assert np.asarray(p0["w"]).shape == (4, 8)
    assert float(p0["w"][0, 0]) == 16.0
    batches = [(np.zeros((2, 3), np.int32), np.zeros((2, 3), np.int32))] * 3
    read = check.reference_readings(ref, config, p0, s0, batches, 0.5)
    assert got["init"] == config and got["step"] == config
    assert got["step"]["layer_types"][3] == "full_attention"
    assert read["loss"] == [1.0, 1.0, 1.0]
    # three steps of 0.5 on 32 ones, leaf by leaf
    assert read["param_change"].tolist() == [pytest.approx(1.5 * 32 ** 0.5)]
    assert "stats_change" not in read


# --- readings of the program's state, a bounded run of leaves a program ------
@pytest.mark.parametrize("bound,runs", [
    (10 ** 9, [(0, 5)]),                 # a model under the bound: one program
    (100, [(0, 2), (2, 3), (3, 5)]),     # 40 + 40 | 400 (alone, over it) | 8 + 80
    (40, [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5)])])
def test_readings_are_taken_over_bounded_runs_of_leaves(bound, runs,
                                                        monkeypatch):
    monkeypatch.setattr(check, "GROUP_BYTES", bound)
    rng = np.random.default_rng(0)
    a = [rng.normal(size=n).astype(np.float32) for n in (10, 10, 100, 2, 20)]
    b = [x + np.float32(0.5) for x in a]
    assert [(r.start, r.stop) for r in check._groups(a)] == runs
    assert check._groups([]) == []
    red = check.Reducers()
    want = [0.5 * n ** 0.5 for n in (10, 10, 100, 2, 20)]
    assert red.diff_norms(b, a).tolist() == pytest.approx(want, rel=1e-6)
    # the first gradient from the optimizer's state: AdamW's mu, SGD's trace
    monkeypatch.setattr(check, "optimizer_leaves", lambda opt, field: a)
    got = check.first_grad(red, None, b, {"optimizer": "adamw",
                                          "adam_b1": 0.9})
    assert len(got) == 5 and all(isinstance(g, np.ndarray) for g in got)
    for g, m in zip(got, a):
        assert np.allclose(g, m / np.float32(0.1), rtol=1e-6)
    monkeypatch.setattr(check, "optimizer_leaves", lambda opt, field: b)
    got = check.first_grad(red, None, a, {"optimizer": "sgd",
                                          "weight_decay": 0.5})
    for g, m in zip(got, a):
        assert np.allclose(g, 0.5 + 0.5 * m, rtol=1e-6)


# --- roofline ----------------------------------------------------------------
PEAK = {"flops_per_s_bf16": 200e12, "hbm_bytes_per_s": 800e9}
FWD = ("jit(step)/jvp(tpudist_forward)/VisionTransformer/encoder_layer_0/"
       "self_attention/attn_fused/jit(flash_attention_qkv)/"
       "jit(_qkv_forward)/pallas_call")
BWD = FWD.replace("jvp(tpudist_forward)", "transpose(jvp(tpudist_forward))") \
    .replace("_qkv_forward", "_qkv_backward")
ELSE = ("jit(step)/jvp(tpudist_forward)/VisionTransformer/encoder_layer_0/"
        "self_attention/in_proj/dot_general")


def scopes_of(*ops):
    return {"ops": [list(op) for op in ops]}


@pytest.mark.parametrize("calls,ops,want,bound_by", [
    # 2e9 operations at 200e12/s = 0.01 ms, 1e3 bytes: compute-bound
    ([(2e9, 1e3)], [("k.1", 0.04, "fwd", FWD)], 25.0, ["compute"]),
    # 8e5 bytes at 800e9/s = 0.001 ms, a few operations: memory-bound
    ([(10, 8e5)], [("k.1", 0.004, "fwd", FWD)], 25.0, ["memory"]),
    # both kinds of call in a step, forward and backward operations summed;
    # an operation of another scope and a layout copy behind the kernel are
    # not the kernel's time
    ([(2e9, 1e3), (10, 8e5)],
     [("k.1", 0.012, "fwd", FWD), ("k.2", 0.010, "bwd", BWD),
      ("fusion.3", 5.0, "fwd", ELSE), ("copy.4", 0.5, "layout_copy", BWD)],
     50.0, ["compute", "memory"])],
    ids=["compute_bound", "memory_bound", "both_and_bystanders"])
def test_roofline_share(calls, ops, want, bound_by, monkeypatch, capsys):
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = roofline.share({"peak": PEAK}, "k_roofline", "attn_fused", calls)
    assert value == pytest.approx(want)
    said = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert said["bound_by"] == bound_by
    assert said["device_ms"] == pytest.approx(
        sum(ms for _, ms, b, n in ops
            if b != "layout_copy" and "attn_fused" in n))


@pytest.mark.parametrize("scopes", [
    None, scopes_of(("fusion.3", 5.0, "fwd", ELSE)),
    scopes_of(("copy.4", 0.5, "layout_copy", BWD)),
    # the scope's name inside a longer element is another scope
    scopes_of(("k.9", 1.0, "fwd", FWD.replace("attn_fused", "attn_fused_v2")))],
    ids=["no_scopes", "nothing_under_it", "only_copies_behind", "longer_name"])
def test_roofline_share_reads_nothing_where_nothing_lies_under_the_scope(
        scopes, monkeypatch):
    monkeypatch.setattr(scope_reduce, "step_scopes", lambda ctx: scopes)
    assert roofline.share({"peak": PEAK}, "k_roofline", "attn_fused",
                          [(1e9, 1e6)]) is None


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", os.path.join(CHIP, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_attn_fused_counts_at_the_configurations_shape():
    """ViT-B/16 at batch 128: 197 tokens, 12 heads of 64, bf16. By hand: a
    product is 2 x 128 x 12 x 197^2 x 64 = 7,630,159,872 operations; the
    projection is 128 x 197 x 12 x 3 x 64 x 2 = 116,195,328 bytes, O a third
    of it, the logsumexp 128 x 12 x 197 x 4 = 1,210,368."""
    m = reader("attn_fused_roofline")
    shape = (128, 197, 12, 64, 2)
    assert m.forward_call(*shape) == (
        2 * 7_630_159_872, 116_195_328 + 38_731_776 + 1_210_368)
    assert m.backward_call(*shape) == (
        5 * 7_630_159_872, 2 * 116_195_328 + 38_731_776 + 1_210_368)
    # the program's own estimate, as a compiled step's HLO states it
    line = ('  %_qkv_backward.1 = bf16[128,197,2304]{2,1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", metadata={op_name="jit(step)'
            '/transpose(jvp(tpudist_forward))/V/encoder_layer_0/self_attention/'
            'attn_fused/jit(flash_attention_qkv)/jit(_qkv_backward)/pallas_call"'
            '}, backend_config={"custom_call_config":{"body":"AAAA",'
            '"cost_estimate":{"flops":"30520639488","transcendentals":'
            '"59610624","bytes_accessed":"272332800"},"x":1}}')
    other = line.replace("attn_fused", "attn_other")
    assert m.program_cost_estimate("\n".join([line, other, line])) == [
        2 * 30520639488, 2 * 272332800, 2]
    assert m.program_cost_estimate(other) is None
    assert m.program_cost_estimate(None) is None


def test_attn_fused_reader(monkeypatch, capsys):
    m = reader("attn_fused_roofline")
    config = load(CHIP, "configs", "vit_b16.json")
    ctx = {"attention_kernel": "flash", "config": config, "batch": 128,
           "chips": 1, "peak": load(CHIP, "peaks.json")["device_kinds"][
               "TPU v5 lite"], "step_hlo": None}
    # PR 28's timings, twelve layers: 0.92 ms forward, 0.853 ms backward
    ops = [(f"_qkv_forward.{i}", 0.92, "fwd", FWD) for i in range(12)] + \
          [(f"_qkv_backward.{i}", 0.853, "bwd", BWD) for i in range(12)]
    monkeypatch.setattr(scope_reduce, "step_scopes",
                        lambda ctx: scopes_of(*ops))
    value = m.read(ctx)
    said = json.loads(capsys.readouterr().out.split(" ", 2)[2])
    assert said["bound_by"] == ["memory", "memory"]
    assert said["device_ms"] == pytest.approx(12 * 1.773)
    # (156,137,472 + 272,332,800) bytes a layer at 819e9 over 1.773 ms
    assert value == pytest.approx(100 * 428_470_272 / 819e9 / 1.773e-3)
    assert 20 < value < 40
    # without the kernel there is nothing to read, whatever the trace holds
    assert m.read(dict(ctx, attention_kernel="xla")) is None
    assert m.read(dict(ctx, attention_kernel=None)) is None
