"""Multi-process launcher (reference C18: ``torch.distributed.launch``,
``start.sh:3-4``).

On real TPU pods each HOST runs one process and the TPU runtime supplies the
topology, so no launcher is needed there (``jax.distributed.initialize()``
with no args). This launcher covers the other cases:

- simulating a multi-process (multi-host) run on one machine — N processes on
  the CPU backend with a local coordinator, the moral equivalent of
  ``python -m torch.distributed.launch --nproc_per_node=N`` on one box
  (CPU only: on the TPU platform more than one child per host is refused,
  because a chip belongs to one process and children get no chip binding);
- launching with explicit coordinator/process ids on clusters without TPU
  metadata.

Usage::

    python -m tpudist.launch --nprocs 2 -- python -m tpudist --synthetic ...

Each child gets ``TPUDIST_COORDINATOR``, ``TPUDIST_NUM_PROCESSES``,
``TPUDIST_PROCESS_ID`` (read by ``dist.initialize_runtime``) and, for the
local-simulation case, a CPU device count per process. Rendezvous is the
jax.distributed coordinator (TCP) — the NCCL/TCPStore rendezvous of the
reference (``distributed.py:124``) with the coordinator service instead.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time


def find_free_port() -> int:
    # SO_REUSEADDR so the coordinator can bind even while the probe socket's
    # address lingers in TIME_WAIT. A concurrent process could still claim the
    # port between close and the coordinator's bind; rank 0 then fails to bind
    # and abort-on-peer-loss below tears the job down rather than hanging.
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _terminate_all(procs, grace: float = 10.0) -> None:
    """SIGTERM each child's process group, then SIGKILL stragglers after a
    grace period — a rank blocked in a collective (or its grandchildren)
    must not outlive the job."""
    for pr in procs:
        try:
            os.killpg(pr.pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
    deadline = time.monotonic() + grace
    for pr in procs:
        try:
            pr.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(pr.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            pr.wait()


def _tpu_attached() -> bool:
    """True when this host exposes TPU chips (their device files) — what an
    unforced jax would initialize on. The launcher never imports jax."""
    import glob
    return bool(glob.glob("/dev/accel[0-9]*")
                or glob.glob("/dev/vfio/[0-9]*"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="tpudist multi-process launcher")
    p.add_argument("--nprocs", "-n", type=int, required=True,
                   help="number of processes to launch")
    p.add_argument("--coordinator", default=None,
                   help="host:port (default: 127.0.0.1:<free port>)")
    p.add_argument("--devices-per-proc", type=int, default=1,
                   help="CPU devices each process simulates (local runs)")
    p.add_argument("--platform", default="cpu",
                   help="JAX platform for children (cpu for simulation)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="elastic restarts: after abort-on-peer-loss tears a "
                        "failed job down, relaunch ALL ranks (fresh "
                        "coordinator) up to N times — with the trainer's "
                        "checkpoint-resume this continues from the last "
                        "completed epoch (torchrun --max-restarts analogue; "
                        "the reference's NCCL job just dies, SURVEY.md §5)")
    p.add_argument("--elastic", action="store_true",
                   help="gang reformation on rank loss: instead of a full "
                        "same-size restart, a reform-eligible rank exit "
                        "drains the survivors (SIGTERM -> emergency "
                        "checkpoint with the epoch's sample cursor -> exit "
                        "75) and relaunches the gang at the SURVIVING world "
                        "size, down to --min-ranks (tpudist/elastic/). "
                        "Reforms do not consume the --max-restarts budget "
                        "(they are bounded by the rank count). The command "
                        "should pass --resume auto --overwrite keep so the "
                        "reformed gang resumes the checkpoint; sets "
                        "TPUDIST_ELASTIC=1 so non-distributed CPU sims "
                        "shard data by the launcher-assigned identity")
    p.add_argument("--min-ranks", type=int, default=1, dest="min_ranks",
                   help="with --elastic: smallest world size worth training "
                        "at — losing more ranks than this falls back to the "
                        "same-size restart path (default 1)")
    p.add_argument("--drain-grace", type=float, default=30.0,
                   dest="drain_grace",
                   help="with --elastic: seconds survivors get to drain "
                        "(finish the in-flight step + write the emergency "
                        "checkpoint) after SIGTERM before SIGKILL; a "
                        "survivor blocked in a dead collective is killed at "
                        "the deadline and the reform resumes from the last "
                        "epoch checkpoint instead of the cursor")
    p.add_argument("--inject", default=os.environ.get("TPUDIST_INJECT", ""),
                   help="fault-injection spec propagated to every rank via "
                        "TPUDIST_INJECT (tpudist/faults.py), e.g. "
                        "'rank_exit@step=7@rank=1@attempt=0'; gates on "
                        "rank/attempt select which rank/launch-attempt "
                        "fires, so a restarted job can prove clean recovery")
    p.add_argument("--telemetry-dir", default="", dest="telemetry_dir",
                   help="run dir holding the ranks' telemetry (heartbeats/ + "
                        "events.*.jsonl, written when the trainer runs with "
                        "--telemetry). The launcher aggregates heartbeats "
                        "into straggler detection and appends its own "
                        "events.launcher.jsonl (rank exits with exit "
                        "classification, restarts, stragglers). Explicit "
                        "dir = eager (created immediately — combine with "
                        "--overwrite keep so a delete-mode rank 0 cannot "
                        "unlink the open event file). Default: when the "
                        "command passes --telemetry, its --outpath is used "
                        "LAZILY — the launcher waits for the ranks to set "
                        "the dir up, so --overwrite semantics are "
                        "untouched")
    p.add_argument("--metrics-port", type=int, default=-1,
                   dest="metrics_port",
                   help="serve the launcher's FLEET metrics view on this "
                        "port (0 = pick a free port): supervision counters "
                        "(attempt, restarts, rank exits by classification), "
                        "per-rank heartbeat gauges, straggler flags as "
                        "gauges, and headline samples aggregated from each "
                        "rank's own --metrics-port endpoint. Requires a "
                        "telemetry dir (--telemetry-dir, or a command that "
                        "passes --telemetry with --outpath). -1 = off")
    p.add_argument("--straggler-factor", type=float, default=4.0,
                   dest="straggler_factor",
                   help="flag a rank whose per-step host overhead (p50 over "
                        "a recent window, from its heartbeat) exceeds this "
                        "multiple of the other ranks' median; 0 disables. "
                        "Host overhead — not total step time — because "
                        "lockstep SPMD equalizes step time across ranks "
                        "(healthy ranks absorb a straggler inside the "
                        "collective wait)")
    p.add_argument("--evict-stragglers", type=int, default=0,
                   dest="evict_stragglers", metavar="N",
                   help="with --elastic: proactively DRAIN a rank the "
                        "straggler detector flags for N consecutive ~1s "
                        "supervision windows — SIGTERM its process group so "
                        "it takes the normal preemption path (finish the "
                        "in-flight step, emergency checkpoint with the "
                        "sample cursor, exit 75) and the gang reforms "
                        "without it. Counted separately from crash "
                        "restarts ('eviction' events + the fleet's "
                        "evictions_total counter); never evicts below "
                        "--min-ranks. 0 = off (flag-and-log only, the "
                        "pre-eviction behavior)")
    p.add_argument("--scale-up", default="", dest="scale_up",
                   metavar="W@S",
                   help="elastic SCALE-UP for collective-free replicas "
                        "(the tpudist.serve plane): after S seconds, spawn "
                        "additional ranks up to world W — e.g. '2@10' "
                        "grows a 1-replica serving fleet to 2 under load, "
                        "emitting a 'topology_change' (mesh_action "
                        "scale_up) so the fleet view follows. New ranks "
                        "get the next TPUDIST_PROCESS_ID and share the "
                        "command verbatim (point them at one "
                        "TPUDIST_COMPILE_CACHE so the newcomer serves "
                        "from the warm cache in seconds). Refused for "
                        "--distributed commands: a training gang's "
                        "collectives cannot admit members mid-flight")
    p.add_argument("--collective-deadline", type=float, default=0.0,
                   dest="collective_deadline", metavar="S",
                   help="dead-collective watchdog: when EVERY live rank's "
                        "heartbeat goes stale for more than S seconds (the "
                        "whole gang is wedged — a dead peer inside a "
                        "collective stalls everyone, and no rank exits on "
                        "its own), emit a loud 'collective_deadline' fault "
                        "event and drain the stalest (suspect) rank "
                        "(SIGTERM, SIGKILL after --drain-grace) so the "
                        "wedge converts to a reform/restart instead of a "
                        "hang. Size S above the longest legitimate "
                        "heartbeat gap (validation + checkpoint: "
                        "heartbeats only advance on TRAIN steps). 0 = off")
    p.add_argument("--incident-keep", type=int, default=4,
                   dest="incident_keep", metavar="K",
                   help="keep the newest K incident bundles under "
                        "<rundir>/incidents/ (the checkpoint keep-K "
                        "convention). The bundler arms itself only when a "
                        "rank runs with --blackbox (its blackbox/ dir "
                        "appears); runs without it are untouched")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="command to run (prefix with --)")
    args = p.parse_args(argv)

    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        p.error("no command given (append: -- python -m tpudist ...)")
    if args.max_restarts < 0:
        p.error("--max-restarts must be >= 0 (there is no infinite mode: "
                "an unrecoverable fault would relaunch forever)")
    if args.elastic and not 1 <= args.min_ranks <= args.nprocs:
        p.error(f"--min-ranks must be in [1, --nprocs={args.nprocs}], "
                f"got {args.min_ranks}")
    if args.evict_stragglers < 0:
        p.error("--evict-stragglers must be >= 0")
    if args.evict_stragglers and not args.elastic:
        p.error("--evict-stragglers needs --elastic: draining a straggler "
                "only helps if the gang can reform without it")
    if args.evict_stragglers and args.straggler_factor <= 0:
        p.error("--evict-stragglers needs --straggler-factor > 0 (the "
                "eviction signal IS the straggler detector)")
    if args.incident_keep < 1:
        p.error("--incident-keep must be >= 1 (0 would delete every "
                "bundle the moment it lands)")
    args.scale_target, args.scale_after = 0, 0.0
    if args.scale_up:
        try:
            tgt, after = args.scale_up.split("@", 1)
            args.scale_target, args.scale_after = int(tgt), float(after)
        except ValueError:
            p.error(f"--scale-up must be 'WORLD@SECONDS' (e.g. '2@10'), "
                    f"got '{args.scale_up}'")
        if args.scale_target <= args.nprocs:
            p.error(f"--scale-up target {args.scale_target} must exceed "
                    f"--nprocs {args.nprocs}")
        if args.scale_after < 0:
            p.error("--scale-up delay must be >= 0 seconds")
        if "--distributed" in cmd:
            p.error("--scale-up is for collective-free replicas (serving): "
                    "a --distributed training gang's collectives cannot "
                    "admit members mid-flight — use --elastic reforms "
                    "instead")

    platforms = (args.platform
                 or os.environ.get("JAX_PLATFORMS", "")).lower().split(",")
    on_tpu = "tpu" in platforms or (platforms == [""] and _tpu_attached())
    children = max(args.nprocs, args.scale_target)
    if on_tpu and children > 1:
        # The children get no per-chip binding, so each would claim every
        # local chip and all but the first die at backend init.
        p.error(f"{children} children on the TPU platform: a chip belongs "
                f"to one process at a time and this launcher does not "
                f"bind children to chips. One host = one process "
                f"driving all local chips: run `python -m tpudist ...` "
                f"directly (or --nprocs 1), and one such process per host "
                f"with --distributed on a pod")

    from tpudist.elastic.membership import (mesh_str, parse_mesh_args,
                                            plan_reform_topology,
                                            reform_world, rewrite_mesh_args)
    from tpudist.faults import classify_exit, parse_spec
    if args.inject:
        parse_spec(args.inject)        # fail fast on a typo'd spec
    telemetry = _launcher_telemetry(args, cmd)
    if args.evict_stragglers or args.collective_deadline > 0:
        # Both watchdogs read the RANKS' heartbeat files, which only exist
        # when the trainer command itself runs --telemetry — a launcher
        # stream alone (explicit --telemetry-dir) would leave them
        # silently inert, the no-op shape this repo's validation policy
        # forbids. (A mismatched --telemetry-dir vs the cmd's --outpath is
        # caught at runtime: the poll warns when heartbeats never appear.)
        if telemetry is None:
            p.error("--evict-stragglers/--collective-deadline read rank "
                    "heartbeats: pass --telemetry-dir, or run a command "
                    "with --telemetry and an --outpath")
        if "--telemetry" not in cmd:
            p.error("--evict-stragglers/--collective-deadline need rank "
                    "heartbeats, which only a command running with "
                    "--telemetry writes — add --telemetry to the trainer "
                    "command")
    fleet, fleet_server = _fleet_metrics(args, telemetry, parser=p)
    # Supervision counters: ``attempt`` numbers every supervise pass (it is
    # what TPUDIST_RESTART_COUNT / @attempt injection gates / heartbeat
    # attempt-gating see); restarts and reforms are counted SEPARATELY —
    # a reform shrinks the world instead of burning the restart budget
    # (it is bounded by the rank count, not --max-restarts).
    world = args.nprocs
    mesh_shape, mesh_axes = parse_mesh_args(cmd)
    attempt = restarts_used = reforms = 0
    exit_code = 0
    try:
        while True:
            exit_code, lost = _supervise_once(args, cmd, attempt, telemetry,
                                              fleet, world)
            if exit_code in (0, 130):      # success, or operator interrupt
                break
            new_world = reform_world(world, lost, exit_code,
                                     elastic=args.elastic,
                                     min_ranks=args.min_ranks)
            if new_world is not None:
                reforms += 1
                attempt += 1
                # Topology-aware reform (ISSUE 13): re-plan the mesh for
                # the surviving world — keep the model (tp) axis when the
                # survivors still divide it, else fold it into dp — and
                # relaunch with the rewritten --mesh-shape/--mesh-axes.
                new_shape, new_axes, action = plan_reform_topology(
                    mesh_shape, mesh_axes, new_world)
                mesh_note = ""
                if action == "fold":
                    cmd = rewrite_mesh_args(cmd, new_shape, new_axes)
                    mesh_note = (f"; mesh {mesh_str(mesh_shape, mesh_axes)}"
                                 f" -> {mesh_str(new_shape, new_axes)} "
                                 f"(model axis folded into data: world "
                                 f"{new_world} no longer divides tp)")
                elif mesh_shape and "model" in (mesh_axes or ()):
                    mesh_note = (f"; mesh {mesh_str(mesh_shape, mesh_axes)}"
                                 f" kept (world {new_world} still divides "
                                 f"tp)")
                print(f"[tpudist.launch] rank loss (exit {exit_code}: "
                      f"{classify_exit(exit_code)}; lost "
                      f"{sorted(lost)}) — REFORMING gang at world "
                      f"{new_world} (was {world}; reform {reforms}, restart "
                      f"budget untouched{mesh_note})",
                      file=sys.stderr, flush=True)
                if telemetry is not None:
                    telemetry.emit("topology_change", attempt=attempt,
                                   from_world=world, to_world=new_world,
                                   lost_ranks=",".join(
                                       str(r) for r in sorted(lost)),
                                   prev_exit=exit_code,
                                   from_mesh=mesh_str(mesh_shape, mesh_axes),
                                   to_mesh=mesh_str(new_shape, new_axes),
                                   mesh_action=action)
                world = new_world
                mesh_shape, mesh_axes = new_shape, new_axes
                continue
            if restarts_used < args.max_restarts:
                restarts_used += 1
                attempt += 1
                print(f"[tpudist.launch] job failed (exit {exit_code}: "
                      f"{classify_exit(exit_code)}) — "
                      f"restart {restarts_used}/{args.max_restarts}",
                      file=sys.stderr, flush=True)
                if telemetry is not None:
                    telemetry.emit("restart", attempt=attempt,
                                   prev_exit=exit_code)
                continue
            print(f"[tpudist.launch] job failed (exit {exit_code}: "
                  f"{classify_exit(exit_code)}) — restart budget "
                  f"exhausted", file=sys.stderr, flush=True)
            break
        if hasattr(telemetry, "flush"):
            telemetry.flush(force=True)  # job over: land any buffered events
    finally:
        if fleet_server is not None:
            fleet_server.close()
    return exit_code


def _fleet_metrics(args, telemetry, parser=None):
    """The launcher's live fleet view (``--metrics-port``): a FleetMetrics
    registry observing the launcher's own event stream + a zero-dependency
    HTTP server rendering its cached exposition. The registry refreshes from
    heartbeats/rank endpoints inside the existing ~1 s supervision poll —
    serving a scrape never touches the filesystem."""
    if getattr(args, "metrics_port", -1) < 0:
        return None, None
    if telemetry is None:
        msg = ("--metrics-port needs a telemetry dir: pass --telemetry-dir, "
               "or run a command with --telemetry and an --outpath")
        if parser is not None:
            parser.error(msg)
        raise SystemExit(msg)
    from tpudist.obs.server import FleetMetrics, MetricsServer
    fleet = FleetMetrics(telemetry.outpath, args.nprocs,
                         straggler_factor=args.straggler_factor)
    if hasattr(telemetry, "add_sink"):
        telemetry.add_sink(fleet.observe)
    else:
        telemetry.sink = fleet.observe     # _LazyLauncherTelemetry
    # attempt=0, not None: a relaunch into a still-warm --telemetry-dir
    # must not read the DEAD run's heartbeats with the attempt gate off
    # and publish its phantom straggler flags.
    fleet.refresh(attempt=0)
    # /dashboard: bench-history trend panels + the live tsdb window the
    # supervision poll records. File reads happen per HTTP GET in the
    # handler thread; latest_path resolves lazily so the page works even
    # before the first sample lands.
    from tpudist.obs import dashboard, tsdb
    rundir = telemetry.outpath

    def _render_dashboard() -> str:
        return dashboard.render_history_file(
            live_path=tsdb.latest_path(rundir), refresh_s=5,
            incidents_dir=rundir)

    server = MetricsServer(fleet, port=args.metrics_port,
                           dashboard=_render_dashboard).start()
    print(f"[tpudist.launch] fleet metrics on :{server.port} "
          f"(/metrics, /dashboard)", file=sys.stderr, flush=True)
    return fleet, server


def _maybe_bundler(args, telemetry, bundler):
    """Lazily create the incident bundler once a rank's ``blackbox/`` dir
    exists (i.e. the job opted into ``--blackbox``); until then a launch
    leaves no ``incidents/`` footprint. Idempotent — returns the existing
    bundler untouched."""
    if bundler is not None or telemetry is None:
        return bundler
    from tpudist.blackbox import IncidentBundler, blackbox_dir
    if not os.path.isdir(blackbox_dir(telemetry.outpath)):
        return None
    bundler = IncidentBundler(telemetry.outpath, telemetry=telemetry,
                              keep=getattr(args, "incident_keep", 4))
    # Observe the launcher's own stream for fleet-level triggers
    # (nonzero rank_exit, straggler, eviction, collective_deadline).
    # The lazy launcher telemetry has ONE .sink slot (the fleet view may
    # hold it) — chain rather than replace.
    if hasattr(telemetry, "add_sink"):
        telemetry.add_sink(bundler.observe)
    else:
        prev = getattr(telemetry, "sink", None)

        def _chained(ev, _prev=prev, _obs=bundler.observe):
            if _prev is not None:
                try:
                    _prev(ev)
                except Exception:
                    pass
            _obs(ev)

        telemetry.sink = _chained
    print(f"[tpudist.launch] incident bundler armed "
          f"(keep {bundler.keep}, {bundler.dir})",
          file=sys.stderr, flush=True)
    return bundler


class _LazyLauncherTelemetry:
    """Launcher event stream that defers touching the run dir until a rank
    has finished setting it up (its ``heartbeats/`` subdir exists).

    Creating the outpath eagerly would regress every non-telemetry launch:
    rank 0's ``output_process`` would find a directory that "already
    exists" (failing ``--overwrite prompt`` headlessly) or, under
    ``--overwrite delete``, unlink the launcher's open event file. Events
    emitted before the dir is ready are buffered (bounded) with their
    original timestamps and flushed on the first ready emit."""

    _MAX_BUFFER = 256

    def __init__(self, outpath: str):
        self.outpath = outpath
        self._tel = None
        self._buf: list[tuple[float, str, dict]] = []
        self.sink = None        # fleet-metrics observer (sees events live,
        #                         even while the file stream is still lazy)

    def flush(self, force: bool = False) -> bool:
        """Open the stream and drain the buffer if a rank has created the
        run dir by now; called opportunistically from the supervision loop
        (a clean run may otherwise never emit a second event to trigger
        the drain). ``force=True`` — used once at launcher exit — creates
        the dir itself: the ranks are dead, so there is no --overwrite
        race left, and a job that crash-looped before any rank could set
        the dir up (bad coordinator, init hang) must still leave its
        rank_exit/restart timeline on disk. Returns True once the stream
        is live."""
        from tpudist.telemetry import Telemetry, heartbeat_dir
        if self._tel is None:
            if not force and not os.path.isdir(heartbeat_dir(self.outpath)):
                return False
            self._tel = Telemetry(self.outpath, rank=-1, attempt=0,
                                  name="launcher", heartbeat=False)
            for t0, et, fl in self._buf:
                # "t" in fields overrides the envelope's emit-time stamp.
                self._tel.emit(et, t=t0, **fl)
            self._buf.clear()
        return True

    def emit(self, etype: str, **fields) -> None:
        if self.sink is not None:
            try:
                self.sink(dict(fields, t=time.time(), type=etype, rank=-1))
            except Exception:
                pass
        if not self.flush():
            if len(self._buf) < self._MAX_BUFFER:
                self._buf.append((time.time(), etype, fields))
            return
        self._tel.emit(etype, **fields)


def _launcher_telemetry(args, cmd):
    """The launcher's own event stream (``events.launcher.jsonl``) in the
    run's telemetry dir. An explicit ``--telemetry-dir`` enables it
    eagerly (the operator named the dir). Otherwise it auto-enables ONLY
    when the command itself opts into telemetry (``--telemetry`` present)
    and an ``--outpath`` is found — and lazily, so the launcher never
    creates the run dir out from under rank 0's --overwrite handling.
    None when neither applies: the launcher stays usable (and
    side-effect-free) for arbitrary commands."""
    if args.telemetry_dir:
        from tpudist.telemetry import Telemetry
        return Telemetry(args.telemetry_dir, rank=-1, attempt=0,
                         name="launcher", heartbeat=False)
    if "--telemetry" not in cmd:
        return None
    tdir = ""
    for i, tok in enumerate(cmd):
        if tok == "--outpath" and i + 1 < len(cmd):
            tdir = cmd[i + 1]
            break
        if tok.startswith("--outpath="):
            tdir = tok.split("=", 1)[1]
            break
    return _LazyLauncherTelemetry(tdir) if tdir else None


def _supervise_once(args, cmd, attempt: int, telemetry=None,
                    fleet=None, nprocs: int = None) -> tuple[int, set]:
    """One launch-and-supervise pass over ``nprocs`` ranks (the CURRENT
    world — smaller than ``args.nprocs`` after an elastic reform): start
    every rank, abort-on-peer-loss, return ``(exit_code, lost_ranks)``.
    ``lost_ranks`` holds the ranks whose own nonzero exits triggered/joined
    the failure (the membership the elastic reform subtracts); survivors
    the teardown SIGTERM'd — whether they drained to exit 75 or were
    SIGKILL'd while blocked in a collective — are NOT lost: they relaunch
    as members of the reformed gang. In the default (local) case each pass picks
    a FRESH coordinator port — the previous coordinator (rank 0's service)
    died with the failed job. An EXPLICIT --coordinator is reused verbatim:
    on a cluster the other hosts rendezvous at that fixed address, so
    rotating it here would strand them; the trade-off is that a lingering
    socket from the killed attempt can fail the retry's bind (which then
    counts against the restart budget)."""
    if nprocs is None:
        nprocs = args.nprocs
    coordinator = args.coordinator or f"127.0.0.1:{find_free_port()}"
    if args.coordinator and attempt:
        print(f"[tpudist.launch] reusing explicit coordinator "
              f"{args.coordinator} for restart {attempt}",
              file=sys.stderr, flush=True)
    procs: list[subprocess.Popen] = []

    # Children run in their own sessions (see Popen below), so a signal to the
    # launcher no longer reaches them implicitly — route SIGTERM/SIGINT
    # through the group-aware teardown instead of leaking orphaned ranks.
    # Once teardown has begun, further signals don't interrupt it (a second
    # KeyboardInterrupt raised inside the teardown handler would abandon the
    # SIGKILL-stragglers phase and leak ranks stuck in collectives) — but
    # they are RECORDED: an operator interrupt during a failed attempt's
    # teardown must stop the launcher, not let the retry loop relaunch the
    # job the operator just tried to kill.
    tearing_down = False
    interrupted = False

    def _on_signal(signum, frame):
        nonlocal interrupted
        if not tearing_down:
            raise KeyboardInterrupt
        interrupted = True

    prev_term = signal.signal(signal.SIGTERM, _on_signal)
    # SIGINT too: the default handler raises KeyboardInterrupt even DURING
    # teardown, which would abandon the SIGKILL-stragglers phase on a second
    # Ctrl-C; _on_signal swallows signals once tearing_down is set.
    prev_int = signal.signal(signal.SIGINT, _on_signal)
    exit_code = 0
    if telemetry is not None:
        from tpudist.elastic.membership import mesh_str, parse_mesh_args
        m_shape, m_axes = parse_mesh_args(cmd)
        telemetry.emit("launcher_start", attempt=attempt, nprocs=nprocs,
                       coordinator=coordinator,
                       mesh=mesh_str(m_shape, m_axes))
    rank_of: dict[int, int] = {}
    flagged: set[int] = set()
    lost: set[int] = set()
    # Proactive-eviction state (--evict-stragglers): consecutive flagged
    # windows per rank, and the ranks already being drained (so one
    # straggler is evicted once, not re-signalled every poll).
    streaks: dict[int, int] = {}
    evicting: set[int] = set()
    floor_warned: set[int] = set()
    # Dead-collective state (--collective-deadline): the suspect rank
    # SIGTERM'd when the whole gang's heartbeats went stale, with its
    # drain deadline for the SIGKILL escalation (a rank wedged inside a
    # collective usually cannot act on SIGTERM).
    suspect_pid = None                 # pid of the SIGTERM'd suspect
    suspect_kill_at = 0.0
    # Watchdogs armed but no heartbeat ever seen (e.g. --telemetry-dir
    # pointing somewhere the ranks don't write): warn loudly once instead
    # of staying silently inert.
    beatless_polls = 0
    # Fleet time-series recorder (obs.tsdb): one row per supervision poll,
    # built from the poll's OWN heartbeat read + the fleet view's in-memory
    # scrape samples — zero added filesystem reads. Created lazily (below)
    # once the run dir provably exists, for the same reason the launcher
    # telemetry stream is lazy: creating the dir here would break rank 0's
    # --overwrite handling.
    ts_recorder = None
    # Incident bundler (tpudist/blackbox.py): correlates rank blackbox
    # dumps + fleet-level triggers into incidents/<id>/. Created lazily
    # once a rank's blackbox/ dir exists — a launch without --blackbox
    # ranks stays byte-identical on disk. Its poll self-throttles the one
    # directory scan it adds (~every 2 s, off the heartbeat hot path).
    bundler = None
    beats_warned = False
    last_straggler_check = time.monotonic()
    world = nprocs
    t_pass0 = time.monotonic()
    try:
        for rank in range(nprocs):
            env = _rank_env(args, coordinator, rank, nprocs, attempt)
            # New session per child so teardown can signal whole process groups.
            procs.append(subprocess.Popen(cmd, env=env, start_new_session=True))
            rank_of[procs[-1].pid] = rank

        # Reference behavior: a dead rank hung NCCL forever (SURVEY.md §5
        # "failure detection: none"). Here: first failure tears down the job.
        while procs:
            for pr in list(procs):
                rc = pr.poll()
                if rc is None:
                    continue
                procs.remove(pr)
                if rc != 0 and telemetry is not None:
                    from tpudist.faults import classify_exit
                    telemetry.emit("rank_exit", attempt=attempt,
                                   exit_rank=rank_of.get(pr.pid, -1),
                                   code=rc,
                                   classification=classify_exit(rc))
                if rc != 0 and exit_code == 0:
                    exit_code = rc
                    lost.add(rank_of.get(pr.pid, -1))
                    tearing_down = True
                    survivors = procs
                    procs = []
                    # Abort-on-peer-loss. Under --elastic this teardown IS
                    # the drain: each survivor's preemption guard catches
                    # the SIGTERM, finishes the in-flight step, writes the
                    # emergency checkpoint (with the epoch's sample cursor),
                    # and exits 75 — so the grace window must cover a step
                    # plus a checkpoint write (--drain-grace), not just
                    # process teardown.
                    _terminate_all(survivors,
                                   grace=args.drain_grace if args.elastic
                                   else 10.0)
                    from tpudist.faults import (PREEMPTED_EXIT_CODE,
                                                classify_exit)
                    for sv in survivors:
                        src = sv.returncode
                        # Survivor exits are recorded ONLY under --elastic,
                        # where drain outcomes decide the reformed gang's
                        # membership; the non-elastic path keeps its
                        # one-rank_exit-per-failure event semantics (fault
                        # timelines and fleet exit counters are SLO inputs
                        # — the launcher's own teardown kills must not
                        # inflate them).
                        if args.elastic and src and telemetry is not None:
                            telemetry.emit("rank_exit", attempt=attempt,
                                           exit_rank=rank_of.get(sv.pid, -1),
                                           code=src,
                                           classification=classify_exit(src))
                        if src and src > 0 and src != PREEMPTED_EXIT_CODE:
                            # Crashed on its own during the drain (not our
                            # SIGTERM/SIGKILL, not a clean drain): this rank
                            # is lost too — the reform must subtract it.
                            lost.add(rank_of.get(sv.pid, -1))
                    break
            if procs and time.monotonic() - last_straggler_check >= 1.0:
                last_straggler_check = time.monotonic()
                if hasattr(telemetry, "flush"):
                    telemetry.flush()      # drain lazy buffer once dir exists
                world = _maybe_scale_up(args, telemetry, attempt, cmd,
                                        coordinator, procs, rank_of, world,
                                        t_pass0)
                # ONE heartbeat-dir read per poll, shared by the straggler
                # check, the eviction/deadline watchdogs, and the fleet
                # view (shared-FS listdir+parse per second is the
                # multi-host cost heartbeat throttling exists for — don't
                # pay it twice).
                beats = None
                if telemetry is not None and (args.straggler_factor > 0
                                              or fleet is not None
                                              or args.collective_deadline
                                              > 0):
                    from tpudist.telemetry import (heartbeat_dir,
                                                   read_heartbeats)
                    beats = read_heartbeats(
                        heartbeat_dir(telemetry.outpath))
                if (args.evict_stragglers or args.collective_deadline > 0) \
                        and not beats_warned:
                    if any(b.get("attempt") == attempt
                           for b in (beats or {}).values()):
                        beats_warned = True    # heartbeats flowing: satisfied
                    else:
                        beatless_polls += 1
                        if beatless_polls >= 60:
                            beats_warned = True
                            print(f"[tpudist.launch] WARNING: "
                                  f"--evict-stragglers/--collective-"
                                  f"deadline armed but no rank heartbeat "
                                  f"appeared in ~{beatless_polls}s — both "
                                  f"watchdogs are inert. Is the telemetry "
                                  f"dir ({telemetry.outpath}) the ranks' "
                                  f"--outpath?", file=sys.stderr,
                                  flush=True)
                live = _check_stragglers(args, telemetry, attempt, flagged,
                                         beats)
                _maybe_evict(args, telemetry, attempt, live, streaks,
                             evicting, floor_warned, procs, rank_of, world)
                suspect_pid, suspect_kill_at = _check_collective_deadline(
                    args, telemetry, attempt, beats, procs, rank_of,
                    suspect_pid, suspect_kill_at)
                if fleet is not None:
                    fleet.refresh(attempt=attempt, beats=beats)
                    if ts_recorder is None and telemetry is not None \
                            and (beats
                                 or getattr(telemetry, "_tel", True)
                                 is not None):
                        # Beats flowing (the ranks created the run dir) or
                        # the launcher stream is already live (explicit
                        # --telemetry-dir, or the lazy stream opened):
                        # safe to open our series file without racing
                        # rank 0's --overwrite handling.
                        from tpudist.obs.tsdb import FleetSeriesRecorder
                        ts_recorder = FleetSeriesRecorder(
                            telemetry.outpath, attempt=attempt)
                    if ts_recorder is not None:
                        ts_recorder.sample(fleet, beats)
                bundler = _maybe_bundler(args, telemetry, bundler)
                if bundler is not None:
                    bundler.poll()
            if procs:
                time.sleep(0.2)
    except KeyboardInterrupt:
        tearing_down = True
        _terminate_all(procs)
        exit_code = exit_code or 130
    finally:
        signal.signal(signal.SIGTERM, prev_term)
        signal.signal(signal.SIGINT, prev_int)
        if ts_recorder is not None:
            ts_recorder.sample(fleet, None)   # final counters row
            ts_recorder.close()
        # Final sweep: a dump written between the last poll and teardown
        # (the common case — the anomaly killed the job) must still
        # bundle. Also catches a blackbox/ dir that appeared too late for
        # the lazy in-loop creation.
        bundler = _maybe_bundler(args, telemetry, bundler)
        if bundler is not None:
            bundler.close()
    if interrupted:
        return 130, lost    # operator interrupt outranks the retry budget
    return exit_code, lost


def _rank_env(args, coordinator: str, rank: int, nprocs: int,
              attempt: int) -> dict:
    """One rank's child environment (rendezvous identity + platform
    hygiene) — shared by the initial spawn loop and the --scale-up path,
    so a scaled-in replica is configured exactly like a launched one."""
    env = dict(os.environ)
    env["TPUDIST_COORDINATOR"] = coordinator
    env["TPUDIST_NUM_PROCESSES"] = str(nprocs)
    env["TPUDIST_PROCESS_ID"] = str(rank)
    env["TPUDIST_RESTART_COUNT"] = str(attempt)
    if args.elastic:
        # Ranks (and their data plane) learn the CURRENT world from
        # the env even when jax.distributed is not initialized (the
        # CPU gang simulation) — see dist.data_rank_world.
        env["TPUDIST_ELASTIC"] = "1"
    if args.inject:
        env["TPUDIST_INJECT"] = args.inject
    if args.platform:
        env["JAX_PLATFORMS"] = args.platform
        if args.platform == "cpu":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count="
                                f"{args.devices_per_proc}").strip()
    return env


def _maybe_scale_up(args, telemetry, attempt: int, cmd, coordinator: str,
                    procs: list, rank_of: dict, world: int,
                    t_pass0: float) -> int:
    """Elastic scale-up (``--scale-up W@S``, the serving plane): once the
    delay has elapsed and every current rank is still alive, spawn the
    additional replicas and emit ``topology_change`` (mesh_action
    ``scale_up``) so the fleet view's world follows. Fires once per
    supervise pass (after it, ``world`` == the target). Returns the new
    world."""
    target = getattr(args, "scale_target", 0)
    if not target or world >= target \
            or time.monotonic() - t_pass0 < args.scale_after:
        return world
    print(f"[tpudist.launch] SCALE-UP: growing world {world} -> {target} "
          f"(+{args.scale_after:.0f}s reached; spawning rank(s) "
          f"{list(range(world, target))})", file=sys.stderr, flush=True)
    for rank in range(world, target):
        env = _rank_env(args, coordinator, rank, target, attempt)
        procs.append(subprocess.Popen(cmd, env=env, start_new_session=True))
        rank_of[procs[-1].pid] = rank
    if telemetry is not None:
        telemetry.emit("topology_change", attempt=attempt,
                       from_world=world, to_world=target,
                       mesh_action="scale_up")
    return target


def _check_stragglers(args, telemetry, attempt: int, flagged: set,
                      beats=None) -> list:
    """Aggregate the ranks' heartbeat files into straggler flags
    (``straggler`` events fire once per rank per attempt; the RETURNED
    list is every rank flagged THIS poll, which is what the eviction
    streak counter consumes). Heartbeats exist only when the trainer runs
    with --telemetry; absent files are simply an empty read. ``beats``
    lets the supervision poll share one heartbeat-dir read with the fleet
    view."""
    if telemetry is None or args.straggler_factor <= 0:
        return []
    from tpudist.telemetry import (find_stragglers, heartbeat_dir,
                                   read_heartbeats)
    if beats is None:
        beats = read_heartbeats(heartbeat_dir(telemetry.outpath))
    live = find_stragglers(beats, factor=args.straggler_factor,
                           attempt=attempt)
    for s in live:
        rank = s["straggler_rank"]
        if rank in flagged:
            continue
        flagged.add(rank)
        print(f"[tpudist.launch] straggler: rank {rank} per-step host "
              f"overhead p50 {s['host_p50_s'] * 1e3:.0f}ms vs fleet median "
              f"{s['median_others_s'] * 1e3:.0f}ms ({s['factor']:.1f}x, "
              f"attempt {attempt}) — investigate that host's input "
              f"pipeline/CPU before blaming the collective",
              file=sys.stderr, flush=True)
        telemetry.emit("straggler", attempt=attempt, straggler_rank=rank,
                       factor=s["factor"], host_p50_s=s["host_p50_s"],
                       median_others_s=s["median_others_s"])
    return live


def _maybe_evict(args, telemetry, attempt: int, live: list,
                 streaks: dict, evicting: set, floor_warned: set,
                 procs: list, rank_of: dict, nprocs: int) -> None:
    """Proactive straggler eviction (``--evict-stragglers N``): a rank the
    detector flags for N CONSECUTIVE supervision windows is drained —
    SIGTERM to its process group, so its preemption guard finishes the
    in-flight step, writes the emergency checkpoint (with the epoch's
    sample cursor), and exits 75, which the supervision loop then treats
    as the lost rank of an elastic reform. The persistent-straggler
    gauge grows teeth; a transient blip (streak broken by one healthy
    window) resets to zero."""
    if not args.evict_stragglers or telemetry is None:
        return
    cur = {s["straggler_rank"] for s in live}
    for rank in list(streaks):
        if rank not in cur:
            del streaks[rank]          # streak broken: transient, forgiven
    by_factor = {s["straggler_rank"]: s.get("factor") for s in live}
    for rank in sorted(cur):
        streaks[rank] = streaks.get(rank, 0) + 1
        if rank in evicting or streaks[rank] < args.evict_stragglers:
            continue
        if nprocs - len(evicting) - 1 < max(1, args.min_ranks):
            # Never evict below the --min-ranks floor: a slow gang beats
            # no gang. The rank keeps re-qualifying every N windows, so
            # warn ONCE per rank per attempt, not every requalification.
            if rank not in floor_warned:
                floor_warned.add(rank)
                print(f"[tpudist.launch] straggler rank {rank} qualifies "
                      f"for eviction but the survivors would drop below "
                      f"--min-ranks {args.min_ranks} — keeping it",
                      file=sys.stderr, flush=True)
            streaks[rank] = 0
            continue
        evicting.add(rank)
        print(f"[tpudist.launch] EVICTING straggler rank {rank} (flagged "
              f"{streaks[rank]} consecutive windows, "
              f"{by_factor.get(rank, 0):.1f}x the fleet median) — draining "
              f"it through SIGTERM -> emergency checkpoint -> reform",
              file=sys.stderr, flush=True)
        telemetry.emit("eviction", attempt=attempt, straggler_rank=rank,
                       windows=streaks[rank],
                       factor=float(by_factor.get(rank) or 0.0))
        for pr in procs:
            if rank_of.get(pr.pid) == rank:
                try:
                    os.killpg(pr.pid, signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass


def _check_collective_deadline(args, telemetry, attempt: int, beats,
                               procs: list, rank_of: dict,
                               suspect_pid, suspect_kill_at: float):
    """Dead-collective watchdog (``--collective-deadline S``): when EVERY
    live rank has a current-attempt heartbeat and every one of them is
    older than S seconds, the gang is wedged (one dead-ish peer stalls
    everyone inside a collective; nobody exits, so abort-on-peer-loss
    never triggers). Emit a loud ``collective_deadline`` event naming the
    stalest rank as the suspect, SIGTERM it, and SIGKILL it after
    --drain-grace if it cannot act on the signal (a rank blocked inside a
    collective usually cannot) — its exit then converts the hang into the
    normal drain -> reform/restart path. Fires once per attempt."""
    if args.collective_deadline <= 0 or telemetry is None or not procs:
        return suspect_pid, suspect_kill_at
    if suspect_pid is not None:
        # Escalation phase: the suspect got SIGTERM; if it is still alive
        # past the drain grace, SIGKILL its group.
        if time.monotonic() >= suspect_kill_at \
                and any(pr.pid == suspect_pid for pr in procs):
            try:
                os.killpg(suspect_pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        return suspect_pid, suspect_kill_at
    live_ranks = {rank_of.get(pr.pid, -1): pr for pr in procs}
    cur = {r: b for r, b in (beats or {}).items()
           if b.get("attempt") == attempt and r in live_ranks}
    if len(cur) < len(live_ranks):
        return suspect_pid, suspect_kill_at   # a rank has no beat yet
    now = time.time()
    ages = {r: now - float(b.get("updated_at", 0.0)) for r, b in cur.items()}
    if not ages or min(ages.values()) <= args.collective_deadline:
        return suspect_pid, suspect_kill_at
    suspect = max(ages, key=lambda r: ages[r])
    print(f"[tpudist.launch] COLLECTIVE DEADLINE: no rank has made step "
          f"progress for {min(ages.values()):.0f}s (deadline "
          f"{args.collective_deadline:.0f}s; stalest: rank {suspect} at "
          f"{ages[suspect]:.0f}s) — the gang looks wedged in a dead "
          f"collective; draining rank {suspect} so the job reforms "
          f"instead of hanging", file=sys.stderr, flush=True)
    telemetry.emit("collective_deadline", attempt=attempt,
                   suspect_rank=suspect,
                   max_age_s=round(ages[suspect], 3),
                   deadline_s=args.collective_deadline)
    pr = live_ranks[suspect]
    try:
        os.killpg(pr.pid, signal.SIGTERM)
    except (ProcessLookupError, PermissionError):
        pass
    grace = args.drain_grace if args.elastic else 10.0
    return pr.pid, time.monotonic() + grace


if __name__ == "__main__":
    sys.exit(main())
