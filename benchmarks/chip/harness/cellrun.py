"""One run of one cell: load, warm, measure, compare, report.

The window drives `tpudist.trainer.Trainer.train_epoch` of a `Trainer` built
through `tpudist.config.from_args` with the flags `python -m tpudist` would
get: the trainer's own step, prefetcher and metric drain. The benchmark's part
is the feed (`harness/traffic.py`), the seeded weights (the configuration's
plain reference draws them; the trainer's state is set to them as a restore
would, and the benchmark's own copy waits on the host until the program is
freed), a handle on the step's executable (`StepHandle`: the trainer's jitted
step lowered and compiled once, so the program the window runs is the one
whose `memory_analysis()` / `cost_analysis()` are reported) and host spans.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import statistics
import time

from harness import check, scope_reduce, trace_reduce, traffic
from harness.errors import Refuse
from harness.spans import Spans


class StepHandle:
    """The trainer's jitted step behind its compiled executable."""

    def __init__(self, jitted, spans: Spans, hook=None):
        self.jitted, self.spans, self.hook = jitted, spans, hook
        self.compiled = None

    def lower(self, *a, **k):
        return self.jitted.lower(*a, **k)

    def __call__(self, state, images, labels, lr):
        if self.compiled is None:
            with self.spans.span("bench.compile"):
                self.compiled = self.jitted.lower(
                    state, images, labels, lr).compile()
        with self.spans.span("bench.dispatch"):
            if self.hook is not None:     # selftests break the step here
                return self.hook(self.compiled, state, images, labels, lr)
            return self.compiled(state, images, labels, lr)


def _say(msg: str, **fields) -> None:
    print(f"bench {msg} " + json.dumps(fields, default=float), flush=True)


def _rss_gib() -> float:
    """This process's resident memory now (0 where /proc is not there)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 2 ** 20
    except OSError:
        pass
    return 0.0


def _load_reader(chip_dir: str, name: str):
    path = os.path.join(chip_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise Refuse(f"per-layer metric {name!r} has no reader under metrics/")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics_for(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def _flops(compiled):
    """Per-device FLOPs of the executable, from its own cost analysis (a
    list of one dict in some jax versions); None where it has none."""
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return float((cost or {}).get("flops", 0.0)) or None


def _attention_dispatch(trainer) -> dict:
    """Which attention kernel the program chose for this run and on what
    grounds: the trainer's own `flash_decision` (None for a model without
    attention) and the seconds of the constructor's `init.dispatch` phase,
    in which a probe runs where a verdict is measured."""
    dec = trainer.flash_decision
    if dec is None:
        return {"kernel": None}
    from tpudist import telemetry
    fields = {k: dec.get(k) for k in (
        "kernel", "mode", "source", "flash_ms", "xla_ms", "margin",
        "kernel_rev", "key", "cache_path")}
    fields["init_dispatch_s"] = telemetry.phases().get("init.dispatch")
    return fields


def _memory_peak(devices) -> tuple[int, dict]:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats), stats[0]


def run_cell(*, bench: dict, workload: dict, config: dict, traffic_spec: dict,
             peaks: dict, seed: int, seconds: float, trace: bool,
             chip_dir: str, t_start: float, require_tpu: bool = True,
             step_hook=None) -> dict:
    phases = {"imports_s": time.time() - t_start}
    tick = time.time()

    def phase(name):
        nonlocal tick
        now = time.time()
        phases[name] = phases.get(name, 0.0) + now - tick
        tick = now

    import jax
    import numpy as np
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if require_tpu and platform != "tpu":
        raise Refuse(f"jax found platform {platform!r}, not a TPU")
    if len(devices) != int(workload["chips"]):
        raise Refuse(f"cell asks for {workload['chips']} chip(s), jax holds "
                     f"{len(devices)}")
    if kind not in peaks:
        raise Refuse(f"device kind {kind!r} is not in peaks.json")
    peak = peaks[kind]
    phase("device_init_s")

    chips = len(devices)
    batch = int(config["per_chip_batch"]) * chips
    workdir = os.path.join(chip_dir, "_work")
    outpath = os.path.join(workdir, "out", workload["name"])
    # cfg.seed feeds jax PRNG keys and numpy seed tuples: keep it in 31 bits
    seed31 = int(seed) % (2 ** 31 - 1)
    argv = [str(a).format(batch=batch, seed=seed31, outpath=outpath)
            for a in config["trainer_argv"]]
    # a start-up probe's verdict is read only from the checkout that measured
    # it: the program's default (~/.cache/tpudist) would hand a verdict of the
    # parent's kernel to the change wherever both run on one machine
    os.environ["TPUDIST_DISPATCH_CACHE"] = os.path.join(workdir, "dispatch")
    from tpudist.config import from_args
    from tpudist.trainer import Trainer
    cfg = from_args(argv)
    trainer = Trainer(cfg, writer=None)
    attention = _attention_dispatch(trainer)
    _say("attention_dispatch", **attention)
    phase("trainer_init_s")

    # --- seeded weights: the reference draws them, the trainer restores
    # them. The benchmark's own copy waits on the host until the program is
    # freed: nothing of its that is the size of the parameters is on the chip
    # while the step runs.
    from jax.sharding import NamedSharding, PartitionSpec as P
    ref = check.load_reference(chip_dir, config["reference_module"])
    red = check.Reducers()
    replicated = NamedSharding(trainer.mesh, P())
    p0, s0 = check.seeded_weights(ref, config, seed31, replicated)
    check.same_structure(trainer.state.params, p0, "params")
    check.same_structure(trainer.state.batch_stats, s0, "batch_stats")
    names = {"first_grad": check.leaf_names(p0),
             "param_change": check.leaf_names(p0),
             "stats_change": check.leaf_names(s0)}
    host0 = jax.device_get((p0, s0))
    trainer.state = trainer.state.replace(params=p0, batch_stats=s0)
    del p0, s0
    leaves = jax.tree_util.tree_leaves
    p0_leaves, s0_leaves = map(leaves, host0)
    phase("weights_s")

    spans = Spans()
    handle = StepHandle(trainer.train_step, spans, step_hook)
    trainer.train_step = handle
    from tpudist.dist import batch_sharding
    source = traffic.make_source(
        traffic_spec, seed=seed31, batch=batch, config=config,
        sharding=batch_sharding(trainer.mesh, trainer.batch_axes),
        cfg=cfg, workdir=workdir)
    feed = traffic.Feed(source, spans)
    _say("traffic", **source.info)
    phase("data_s")

    # --- warm-up = the compared steps, through the window's own path
    lr = float(config["window_lr"])
    n_compared = int(config["compared_steps"])
    prog = {"loss": []}
    for i in range(n_compared):
        loss, _ = trainer.train_epoch(feed.batches(1), 0, lr)
        jax.block_until_ready(trainer.state)
        prog["loss"].append(float(loss))
        if i == 0:
            phases["compile_s"] = spans.total("bench.compile")
            prog["first_grad_leaves"] = check.first_grad(
                red, trainer.state.opt_state, p0_leaves, config)
            prog["first_grad"] = check._norms(prog["first_grad_leaves"])
    prog["param_change"] = red.diff_norms(
        leaves(trainer.state.params), p0_leaves)
    if s0_leaves:
        prog["stats_change"] = red.diff_norms(
            leaves(trainer.state.batch_stats), s0_leaves)
    phase("warmup_s")
    phases["warmup_s"] -= phases["compile_s"]

    compiled = handle.compiled
    mem = compiled.memory_analysis()
    step_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    flops = _flops(compiled)
    step_hlo = compiled.as_text()
    step_module = step_hlo.split("\n", 1)[0].split(",")[0] \
        .replace("HloModule", "").strip()
    if not trace:              # only a traced run's readers look inside it
        step_hlo = None
    phase("analysis_s")

    loader_rate = None
    trace_dir = os.path.join(workdir, "trace", workload["name"])
    if trace:
        seconds = min(seconds, float(traffic_spec["trace_seconds"]))
        if hasattr(source, "drain_alone"):
            loader_rate = source.drain_alone(
                float(traffic_spec["drain_alone_seconds"]))
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.block_until_ready(trainer.state)
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        spans.annotate = True
        phase("trace_start_s")

    # a mix whose loader runs ahead while set-up computes would open the window
    # with batches in hand that the window did not produce: spend them first
    settle = int(traffic_spec.get("settle_steps", 0))
    if settle:
        trainer.train_epoch(feed.batches(settle), 0, lr)
        phase("settle_s")

    # --- the window ---------------------------------------------------------
    jax.block_until_ready(trainer.state)
    _say("resident_at_window",
         parameter_sized_extras=check.parameter_sized_extras(trainer.state))
    phase("census_s")
    rss = {"before_window": _rss_gib()}
    steps0 = trainer.global_step
    setup_s = time.time() - t_start
    t_open = time.perf_counter()
    with spans.span(trace_reduce.WINDOW_SPAN):
        trainer.train_epoch(feed.until(t_open + seconds), 0, lr)
        jax.block_until_ready(trainer.state)
    t_close = time.perf_counter()
    rss["after_window"] = _rss_gib()
    window_s = t_close - t_open
    steps = trainer.global_step - steps0
    if trace:
        spans.annotate = False
        jax.profiler.stop_trace()
        rss["after_stop_trace"] = _rss_gib()
    alloc_peak, mem_stats = _memory_peak(devices)
    # the runtime allocator does not count a running program's scratch
    # (PERF.md, PR 21): the chip's peak is its peak plus the step's temps
    memory_peak = alloc_peak + int(mem.temp_size_in_bytes)

    starts = sorted(s for n, s, _ in spans.rows
                    if n == "bench.dispatch" and s >= t_open)
    gaps_ms = [1e3 * (b - a) for a, b in zip(starts, starts[1:])]
    if len(gaps_ms) >= 4:
        q = statistics.quantiles(gaps_ms, n=20)
        _say("step_times_ms", n=len(gaps_ms), p50=q[9], p95=q[18],
             max=max(gaps_ms), min=min(gaps_ms))
    # a row is an image or a sequence, as the mix draws it
    img_per_s_chip = steps * batch / window_s / chips
    tokens = ({"tokens_per_s_chip": img_per_s_chip * source.info["seq_len"]}
              if "seq_len" in source.info else {})
    _say("window", steps=steps, window_s=window_s, batch=batch,
         img_per_s_chip=img_per_s_chip, **tokens, step_module=step_module,
         allocator_peak_bytes=alloc_peak, step_temp_bytes=mem.temp_size_in_bytes,
         flops_per_step=flops)
    _say("memory_stats", **{k: v for k, v in mem_stats.items()
                            if isinstance(v, (int, float))})

    # --- free the program, then the reference -------------------------------
    # the compared batches wait on the host: the float32 reference needs the
    # chip's memory to itself
    first = [(np.asarray(im), np.asarray(lb)) for im, lb in source.first(n_compared)]
    input_rows = source.rows_check(n_compared)
    source.close()
    trainer.state = None
    trainer.train_step = None
    handle.compiled = handle.jitted = None
    del trainer, handle, compiled, feed
    gc.collect()
    t_ref = time.time()
    p0, s0 = jax.device_put(host0, replicated)
    del host0, p0_leaves, s0_leaves
    refd = check.reference_readings(ref, config, p0, s0, first, lr)
    if input_rows is not None:
        from harness import input_check
        prog["rows"] = input_check.compare(
            input_rows, cfg, seed31, config["correct_limits"])
    correct, rows = check.compare(prog, refd, config["correct_limits"], names)
    _say("reference", seconds=time.time() - t_ref,
         peak_after_reference=_memory_peak(devices)[0])
    rss["after_reference"] = _rss_gib()
    _say("host_rss_gib", **rss)
    _say("setup_phases", **phases, setup_s=setup_s)

    # --- the result line ------------------------------------------------------
    device = {"platform": platform, "kind": kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": steps, "failed": 0,
              "workload": workload["name"], "seed": seed, "seconds": seconds,
              "attention_kernel": attention["kernel"], "device": device}
    if not trace:
        values = {"train_img_per_s_chip": img_per_s_chip,
                  "hbm_step_gib": step_bytes / 2 ** 30, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in _metrics_for(bench, "end_to_end", workload["name"])}
    else:
        events = trace_reduce.extract(
            trace_reduce.newest_xplane(trace_dir),
            peak["trace_device_plane_prefix"],
            tuple(peak["trace_op_lines"]), tuple(peak["trace_module_lines"]))
        reduced = trace_reduce.reduce(events, step_module)
        with open(os.path.join(trace_dir, "summary.json"), "w") as f:
            json.dump({"seen": events["seen"], "reduced": reduced,
                       "sample_stats": events.get("sample_stats")}, f)
        if not reduced.get("busy_s"):
            raise Refuse("the trace holds no device operation: planes seen "
                         f"{[p for p, _ in events['seen']]}")
        ctx = {"spans": spans, "t_open": t_open, "t_close": t_close,
               "window_s": window_s, "steps": steps, "batch": batch,
               "chips": chips, "trace": reduced, "flops_per_step": flops,
               "peak": peak, "loader_img_per_s": loader_rate,
               "compile_s": phases["compile_s"], "config": config,
               "attention_kernel": attention["kernel"], "step_hlo": step_hlo}
        result["metrics"] = {}
        for m in _metrics_for(bench, "per_layer", workload["name"]):
            value = _load_reader(chip_dir, m["name"])(ctx)
            if value is None:
                continue
            if m["unit"] == "%" and ("mfu" in m["name"]
                                     or m["name"].endswith("_roofline")) \
                    and value > 100.0:
                raise Refuse(f"{m['name']} reads {value} %: a share of a peak "
                             "cannot pass 100, the operations are counted too "
                             "high or the time leaves work out")
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": scope_reduce.label_ops(
                reduced["device_ops"], scope_reduce.step_scopes(ctx)),
            "idle_gaps": reduced["idle_gaps"]}
    check.print_rows(rows, correct)
    return result
