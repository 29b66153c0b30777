"""The harness driven on the CPU at a tiny size, past its look for a chip.

Run by path: `python -m pytest benchmarks/chip/selftest -q` (tier-1 does not
collect this directory and there is no conftest here). What is checked:

- a sound run of each tiny configuration comes out `correct`, and says which
  attention kernel the program chose (`bench attention_dispatch`,
  `attention_kernel`), with the program's verdicts kept under `_work/dispatch/`;
- every `auto|on|off` flag a configuration runs with has its reason under
  `pinned`, in the spelling it runs with;
- with the timed path broken underneath (a step that returns its state
  unchanged; a step that trains on half of the batch) `correct` is false;
- the control: the reference itself, computed with fp8 operands and put in
  the program's place, is not correct under the limits a sound bf16 run
  passes (the chip-size readings of both are in PERF.md);
- a cell on four (virtual) devices, DP + SyncBN, arrives as data files only
  (`selftest/tiny/*.json`) and runs through the same `run_cell`;
- when the window opens nothing of the benchmark's that has a parameter's
  shape is live on the device: its copy of the seeded weights waits on the
  host (`bench resident_at_window`), and the census that says so does find a
  copy that is held.

A CPU run reports no device time: nothing here reads a rate or a share.
"""

import gc
import glob
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (CHIP, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

CPU_PEAKS = {"cpu": {"flops_per_s_bf16": 1e15, "hbm_bytes_per_s": 1e12,
                     "trace_device_plane_prefix": "/host:CPU",
                     "trace_op_lines": ["tf_XLAPjRtCpuClient"],
                     "trace_module_lines": []}}


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_run(config_name, *, traffic="staged", seed=11, chips=1, trace=False,
             step_hook=None, seconds=1.0):
    from harness.cellrun import run_cell
    bench = load(ROOT, "BENCHMARK.json")
    cell = {"name": f"tiny_{config_name}", "config": config_name,
            "traffic": traffic, "chips": chips}
    for m in bench["per_layer"]:           # the tiny cell joins every list
        m["workloads"] = m["workloads"] + [cell["name"]]
    if traffic != "staged":     # a metric arrives as an entry beside its reader
        bench["per_layer"].append(
            {"name": "loader_img_per_s", "unit": "img/s", "better": "higher",
             "source": "host_clock", "layer": "input path",
             "moves": "train_img_per_s_chip", "workloads": [cell["name"]]})
    return run_cell(
        bench=bench, workload=cell,
        config=load(HERE, "tiny", config_name + ".json"),
        traffic_spec=(load(CHIP, "traffic", traffic + ".json")
                      if os.path.exists(os.path.join(CHIP, "traffic",
                                                     traffic + ".json"))
                      else load(HERE, "tiny", traffic + ".json")),
        peaks=CPU_PEAKS, seed=seed, seconds=seconds, trace=trace,
        chip_dir=CHIP, t_start=time.time(), require_tpu=False,
        step_hook=step_hook)


@pytest.mark.parametrize("config_name,attention", [
    ("resnet18_tiny", {"kernel": None}),
    # 5 tokens at 32 px: the program rules the kernel out before it asks for
    # the platform (at the cell's 197 tokens a CPU reads `source: platform`)
    ("vit_tiny", {"kernel": "xla", "mode": "auto", "source": "ineligible"})])
def test_sound_run_is_correct(config_name, attention, capfd):
    gc.collect()       # arrays of earlier tests' runs are not this run's
    result = tiny_run(config_name, seed=2 ** 31 + 12345)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_img_per_s_chip", "hbm_step_gib",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["count"] == 1
    # which attention ran: `--flash auto` is XLA's here, without a probe
    lines = capfd.readouterr().out.splitlines()
    said = [json.loads(line.split(" ", 2)[2]) for line in lines
            if line.startswith("bench attention_dispatch ")]
    assert len(said) == 1
    assert {k: said[0][k] for k in attention} == attention
    # the benchmark's copy of the seeded weights is off the device
    census = [json.loads(line.split(" ", 2)[2]) for line in lines
              if line.startswith("bench resident_at_window ")]
    assert census == [{"parameter_sized_extras": []}]
    assert result["attention_kernel"] == attention["kernel"]
    assert os.environ["TPUDIST_DISPATCH_CACHE"] == os.path.join(
        CHIP, "_work", "dispatch")


def test_census_finds_a_parameter_sized_array_that_is_held():
    """`check.parameter_sized_extras` against a stand-in state: clean while
    only the state's own leaves are live, and not once a copy of the
    parameters is held beside them (what the harness did before PR 29)."""
    import collections
    import jax.numpy as jnp
    from harness import check
    params = {"w": jnp.ones((7, 33, 5)), "b": jnp.ones((5,))}
    state = collections.namedtuple("State", "params mu")(
        params, {"w": jnp.zeros((7, 33, 5))})
    assert check.parameter_sized_extras(state) == []
    held = {k: jnp.array(v) for k, v in params.items()}
    assert check.parameter_sized_extras(state) == [
        [[7, 33, 5], "float32", 1, 7 * 33 * 5 * 4]]
    del held
    assert check.parameter_sized_extras(state) == []


DISPATCH_FLAGS = ("--flash", "--fused-bn", "--compress-grads")


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(CHIP, "configs", "*.json"))), ids=os.path.basename)
def test_every_dispatch_flag_is_pinned_as_it_runs(path):
    """A configuration's text and its flags agree: each `auto|on|off` flag of
    `trainer_argv` has its reason under `pinned`, keyed by the flag and the
    value it runs with (`--flash auto`; keys may join several with ` / `)."""
    config = load(path)
    argv = config["trainer_argv"]
    pinned = {part for key in config["pinned"] for part in key.split(" / ")}
    runs = [f"{flag} {argv[argv.index(flag) + 1]}" for flag in DISPATCH_FLAGS
            if flag in argv]
    assert runs, "no dispatch flag is written out"
    assert all(r.split()[1] in ("auto", "on", "off") for r in runs), runs
    assert [r for r in runs if r not in pinned] == []
    # and no reason is left behind for a value the flag no longer has
    stale = [p for p in pinned if p.split()[0] in DISPATCH_FLAGS
             and p not in runs]
    assert stale == []


def _unchanged(step, state, images, labels, lr):
    """A step that reports its metrics and returns its state as it got it."""
    import jax
    import jax.numpy as jnp
    kept = jax.tree_util.tree_map(jnp.copy, state)      # `state` is donated
    _, metrics = step(state, images, labels, lr)
    return kept, metrics


def _half_batch(step, state, images, labels, lr):
    """A step that leaves half of the batch out: the second half of the rows
    is a copy of the first."""
    import jax.numpy as jnp
    n = images.shape[0] // 2
    return step(state, jnp.concatenate([images[:n], images[:n]]),
                jnp.concatenate([labels[:n], labels[:n]]), lr)


@pytest.mark.parametrize("config_name,hook", [
    ("resnet18_tiny", _unchanged), ("vit_tiny", _unchanged),
    ("vit_tiny", _half_batch)])
def test_broken_timed_path_is_not_correct(config_name, hook):
    assert tiny_run(config_name, step_hook=hook)["correct"] is False


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    result = tiny_run("vit_tiny", trace=True)
    assert {"data_wait_pct", "mfu_pct", "warm_compile_s", "device_idle_pct",
            "h2d_stage_ms"} <= set(result["metrics"])
    assert "loader_img_per_s" not in result["metrics"]     # nothing to read
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    assert result["breakdown"]["device_ops"]
    assert len(result["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("config_name", ["resnet18_tiny", "vit_tiny"])
def test_fp8_control_is_not_correct(config_name):
    """The reference with fp8 operands in the program's place fails a limit;
    with bf16 operands (the configuration's own precision) it passes."""
    import jax
    from harness import check
    model_cfg = config = load(HERE, "tiny", config_name + ".json")
    ref = check.load_reference(CHIP, config["reference_module"])
    b, s = config["per_chip_batch"], config["image_size"]
    verdicts = {"bf16": [], "fp8": []}
    for seed in range(3):
        p0, s0 = ref.init(jax.random.PRNGKey(seed), model_cfg)
        keys = jax.random.split(jax.random.PRNGKey(100 + seed), 6)
        batches = [(jax.random.normal(keys[2 * i], (b, s, s, 3)),
                    jax.random.randint(keys[2 * i + 1], (b,), 0,
                                       config["num_classes"]))
                   for i in range(3)]
        sound = check.reference_readings(ref, model_cfg, p0, s0, batches,
                                         config["window_lr"])
        names = {"first_grad": check.leaf_names(p0),
                 "param_change": check.leaf_names(p0),
                 "stats_change": check.leaf_names(s0)}
        for quant in verdicts:
            got = check.reference_readings(ref, model_cfg, p0, s0, batches,
                                           config["window_lr"], quant=quant)
            verdicts[quant].append(check.compare(
                got, sound, config["control_limits"], names)[0])
    assert verdicts["bf16"] == [True] * 3
    assert verdicts["fp8"] == [False] * 3


def test_jpeg_mix_runs_the_native_loader_and_checks_its_rows():
    result = tiny_run("resnet18_tiny", traffic="jpeg_tiny", trace=True)
    assert result["correct"] is True
    assert result["metrics"]["loader_img_per_s"]["value"] > 0
    assert result["metrics"]["data_wait_pct"]["value"] > 0


def test_four_device_syncbn_cell_arrives_as_data_only():
    """Four virtual CPU devices need their own process (the device count is
    fixed when jax starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, sys; sys.path.insert(0, %r); "
            "import test_harness_cpu as t; "
            "r = t.tiny_run('resnet18_syncbn_tiny', chips=4); "
            "print(json.dumps(r))" % HERE)
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["device"]["count"] == 4


def test_refuses_without_a_tpu_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "resnet18_staged", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), text=True,
        capture_output=True, timeout=600, cwd=ROOT)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
