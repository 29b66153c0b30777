"""Distributed runtime: the reference's ``torch.distributed``/NCCL layer, TPU-native.

The reference (``distributed.py:123-125``) does::

    args.nprocs = torch.cuda.device_count()
    dist.init_process_group(backend='nccl')
    torch.cuda.set_device(local_rank)

and then synchronizes metrics with ``reduce_mean`` (clone → all_reduce(SUM) →
/nprocs, ``distributed.py:78-82``) behind a per-step ``dist.barrier()``
(``distributed.py:253``).

The TPU-native equivalents here:

- process bootstrap → ``jax.distributed.initialize`` (coordinator service over
  DCN replaces the TCPStore rendezvous of ``torch.distributed.launch``,
  ``start.sh:3``);
- device binding → automatic: each host owns its local chips; no
  ``set_device``;
- NCCL allreduce → XLA collectives (``lax.pmean``) compiled onto ICI/DCN and
  fused into the step program — ``reduce_mean`` below IS ``lax.pmean``;
- ``dist.barrier`` → unnecessary: SPMD programs execute in lockstep, the
  collective itself is the synchronization point. We expose ``barrier()`` for
  host-side coordination (e.g. "rank 0 writes the dir, others wait").
"""

from __future__ import annotations

import os
import time
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpudist.obs import scopes


def initialize_runtime(coordinator_address: str | None = None,
                       num_processes: int | None = None,
                       process_id: int | None = None,
                       timeout_s: float | None = None,
                       retries: int | None = None) -> None:
    """Multi-host bootstrap (replaces ``dist.init_process_group('nccl')``,
    ``distributed.py:124``). On a TPU pod each host calls this once; the
    coordinator address / topology come from args or the environment the
    launcher sets (``TPUDIST_COORDINATOR`` / ``TPUDIST_NUM_PROCESSES`` /
    ``TPUDIST_PROCESS_ID``, see ``launch/``).

    Failure hardening (the reference bug one layer down: a lost coordinator
    hung TCPStore rendezvous forever, SURVEY.md §5):

    - a DEADLINE bounds the coordinator connect + init barrier
      (``timeout_s``, default env ``TPUDIST_INIT_TIMEOUT`` or 300s) — a
      dead/unreachable coordinator raises instead of hanging;
    - BOUNDED retries with linear backoff (``retries``, default env
      ``TPUDIST_INIT_RETRIES`` or 0) cover the transient shape (coordinator
      restarting, DNS blip) without masking a dead cluster;
    - the ``init_hang`` fault point simulates a lost peer sleeping through
      rendezvous, so tests can drive deadline→abort→relaunch end-to-end.
    """
    from tpudist import faults
    kwargs = {}
    if coordinator_address or os.environ.get("TPUDIST_COORDINATOR"):
        kwargs["coordinator_address"] = coordinator_address or os.environ["TPUDIST_COORDINATOR"]
    if num_processes is None and os.environ.get("TPUDIST_NUM_PROCESSES"):
        num_processes = int(os.environ["TPUDIST_NUM_PROCESSES"])
    if process_id is None and os.environ.get("TPUDIST_PROCESS_ID"):
        process_id = int(os.environ["TPUDIST_PROCESS_ID"])
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    if timeout_s is None:
        timeout_s = float(os.environ.get("TPUDIST_INIT_TIMEOUT", 300.0))
    if timeout_s > 0:
        # jax's own deadline on the connect + init barrier (it polls the
        # coordinator; an int is required).
        kwargs["initialization_timeout"] = max(1, int(timeout_s))
    if retries is None:
        retries = int(os.environ.get("TPUDIST_INIT_RETRIES", 0))

    import time as _time
    t_init0 = _time.monotonic()
    faults.maybe_init_hang()
    for attempt in range(retries + 1):
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as e:
            if attempt >= retries:
                raise RuntimeError(
                    f"distributed runtime init failed after "
                    f"{attempt + 1} attempt(s) "
                    f"(deadline {timeout_s:.0f}s per attempt, coordinator "
                    f"{kwargs.get('coordinator_address', '<auto>')}): {e}"
                ) from e
            # Linear backoff, bounded: transient coordinator churn heals in
            # seconds; anything longer is the launcher/restart layer's job.
            import sys
            import time
            wait = min(5.0 * (attempt + 1), 30.0)
            print(f"[tpudist.dist] init attempt {attempt + 1} failed ({e}); "
                  f"retrying in {wait:.0f}s "
                  f"({retries - attempt} retries left)",
                  file=sys.stderr, flush=True)
            time.sleep(wait)
        else:
            # Goodput accounting: runtime init happens before the Trainer
            # (and its Telemetry) exists, so stash the duration for the
            # telemetry layer to pick up. OUTSIDE the try: a broken
            # telemetry sink after a SUCCESSFUL init must not look like an
            # init failure and re-initialize an already-initialized runtime.
            try:
                from tpudist import telemetry
                telemetry.record_phase("init", _time.monotonic() - t_init0)
            except Exception:
                pass
            return


def process_index() -> int:
    """The rank-0 gate (reference ``local_rank == 0`` checks,
    ``distributed.py:117``): on TPU, the per-host process index."""
    return jax.process_index()


def data_rank_world() -> tuple[int, int]:
    """``(rank, world)`` for the DATA plane — what ``ShardedSampler`` shards
    over and what the elastic sample cursor counts in.

    With the jax.distributed runtime up this is just
    ``(process_index, process_count)``. Under the launcher's ELASTIC mode
    (``TPUDIST_ELASTIC=1``) without ``--distributed`` — the CPU gang
    simulation, where ranks are independent jit processes whose
    ``process_count`` is uniformly 1 — the launcher-assigned env identity
    supplies the data topology instead, so each rank loads its 1/W shard
    and the gang's sample accounting matches a real pod's. Env fallback is
    gated on TPUDIST_ELASTIC so non-elastic local sims keep their
    every-rank-sees-all-data behavior."""
    if jax.process_count() > 1:
        return jax.process_index(), jax.process_count()
    if os.environ.get("TPUDIST_ELASTIC") == "1":
        try:
            world = int(os.environ.get("TPUDIST_NUM_PROCESSES", "1"))
            rank = int(os.environ.get("TPUDIST_PROCESS_ID", "0"))
        except ValueError:
            return jax.process_index(), jax.process_count()
        if world > 1 and 0 <= rank < world:
            return rank, world
    return jax.process_index(), jax.process_count()


def replica_rank_world() -> tuple[int, int]:
    """``(rank, world)`` for the REPLICA plane — which processes hold
    nominally bit-identical (dp-replicated) state. This is what the
    doctor's cross-replica SDC probe compares over (tpudist/doctor/).

    With the jax.distributed runtime up, replicas ARE processes:
    ``(process_index, process_count)`` — same as the data plane. Under
    the launcher's CPU gang sims (independent jit ranks), the launcher
    env identity applies REGARDLESS of elastic mode — unlike
    ``data_rank_world``, which is gated on ``TPUDIST_ELASTIC``:

    - NON-elastic sim: every rank trains ALL the data from the same seed,
      so ranks really are bit-identical replicas — the honest CPU stand-in
      for a pod's replication invariant, and the mode the SDC-probe e2es
      run in (``env TPUDIST_ELASTIC=0`` under an elastic launcher).
    - ELASTIC sim: ranks train disjoint shards with no cross-process
      collectives, so their states legitimately differ and a probe reports
      unattributable divergence — probes there belong to real
      ``--distributed`` gangs (docs/DOCTOR.md).
    """
    if jax.process_count() > 1:
        return jax.process_index(), jax.process_count()
    try:
        world = int(os.environ.get("TPUDIST_NUM_PROCESSES", "1"))
        rank = int(os.environ.get("TPUDIST_PROCESS_ID", "0"))
    except ValueError:
        return jax.process_index(), jax.process_count()
    if world > 1 and 0 <= rank < world:
        return rank, world
    return jax.process_index(), jax.process_count()


def is_primary() -> bool:
    return jax.process_index() == 0


def device_count() -> int:
    """Reference ``torch.cuda.device_count()`` (``distributed.py:123``) but
    global: total chips across all hosts (SPMD spans the whole mesh)."""
    return jax.device_count()


def make_mesh(mesh_shape: Sequence[int] | None = None,
              axis_names: Sequence[str] = ("data",),
              devices: Sequence[jax.Device] | None = None) -> Mesh:
    """Build the device mesh the trainer shards over.

    Default is a 1-D ``('data',)`` mesh over all devices — the reference only
    implements data parallelism (SURVEY.md §2.2) — but any shape/axes can be
    given (e.g. ``(4, 2), ('data', 'model')``) so TP/SP/PP axes slot in without
    reshaping the trainer.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    if mesh_shape is None:
        mesh_shape = (devs.size,) + (1,) * (len(axis_names) - 1)
    return Mesh(devs.reshape(tuple(mesh_shape)), tuple(axis_names))


def batch_sharding(mesh: Mesh, data_axis: str = "data") -> NamedSharding:
    """Sharding for a batch: leading dim split over the data axis (the
    DistributedSampler equivalent at the array level, ``distributed.py:167``)."""
    return NamedSharding(mesh, P(data_axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated params — data-parallel training replicates the model,
    like DDP's init broadcast (``distributed.py:144``)."""
    return NamedSharding(mesh, P())


def reduce_mean(tensor: jax.Array, axis_name: str = "data") -> jax.Array:
    """Reference ``reduce_mean`` (``distributed.py:78-82``): allreduce(SUM)/nprocs.
    Inside a shard_map'd/pmapped step this is exactly ``lax.pmean``; XLA fuses
    it into the compiled program (no clone, no barrier, no host sync)."""
    return jax.lax.pmean(tensor, axis_name=axis_name)


def barrier(tag: str = "tpudist_barrier",
            timeout_s: float | None = None) -> None:
    """Host-side barrier (reference ``dist.barrier()``, ``distributed.py:253``).

    NOT needed in the hot loop — SPMD program order synchronizes devices — but
    useful for host-side filesystem coordination across processes ("rank 0
    writes the dir, others wait"). Single-process: no-op. Failures propagate —
    a barrier that silently doesn't synchronize is worse than a crash.

    A DEADLINE bounds the wait (``timeout_s``, default env
    ``TPUDIST_BARRIER_TIMEOUT`` or 600s; <=0 disables): a peer that died
    before reaching the barrier must surface as a raise this process's
    watchdog/launcher can act on, not an indefinite hang. The barrier runs
    on a worker thread so the deadline can fire while the collective is
    blocked; the abandoned thread is daemonic (the process is about to exit
    through the failure chain anyway).
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    if timeout_s is None:
        timeout_s = float(os.environ.get("TPUDIST_BARRIER_TIMEOUT", 600.0))
    if timeout_s <= 0:
        multihost_utils.sync_global_devices(tag)
        return
    import threading
    err: list[BaseException] = []

    def _sync():
        try:
            multihost_utils.sync_global_devices(tag)
        except BaseException as e:          # noqa: BLE001 — re-raised below
            err.append(e)

    t = threading.Thread(target=_sync, daemon=True,
                         name=f"tpudist-barrier-{tag}")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise TimeoutError(
            f"host barrier '{tag}' did not complete within {timeout_s:.0f}s "
            f"— a peer likely died before reaching it; aborting so the "
            f"launcher can tear the job down")
    if err:
        raise err[0]


class DevicePrefetcher:
    """Double-buffered device prefetch: keep up to ``depth`` batches already
    placed on the mesh so batch N+1's host→device copy overlaps step N's
    device compute.

    The trainer's serial loop pays the loader wait AND the ``device_put``
    staging copy on the critical path of every step (the telemetry
    data/h2d buckets PR 5's attribution table names). ``jax.device_put`` is
    asynchronous — the copy engine runs it concurrently with compute — so
    all the host has to do is ISSUE it before blocking on the step. This
    wrapper does exactly that:

    - ``__next__`` pops the oldest device-resident batch; only an EMPTY
      queue blocks (loader slower than the chip), and that exposed wait is
      what the step event's data/h2d fields then show;
    - ``poke()`` — called by the trainer right after dispatching the step —
      tops the queue back up (loader pull + device_put issue) while the
      device is busy; its duration is recorded as ``hidden_s`` and reported
      as the step's ``prefetch_s`` telemetry field, NOT as data/h2d wait
      (overlap-aware phase accounting: summarize must not double-count
      transfer time that compute hid).

    ``last_local_bs`` is the HOST-LOCAL batch size of the batch ``__next__``
    just returned — after ``shard_host_batch`` the arrays are global, so
    the trainer's sample-cursor accounting cannot read it off the shapes
    on a multi-host gang.
    """

    def __init__(self, loader, mesh: Mesh, data_axis="data", depth: int = 2):
        self._it = iter(loader)
        self.mesh = mesh
        self.data_axis = data_axis
        self.depth = max(1, int(depth))
        self._q: list = []
        self._exhausted = False
        # The trainer reads last_local_bs (sample cursor) and books hidden
        # time from poke()'s return value; in a trace the exposed fills are
        # the tpudist.prefetch spans inside the loop's first loop_host span.
        self.last_local_bs = 0

    def _fill_one(self) -> float:
        """Pull one host batch and issue its device placement; returns the
        time spent (0.0 at source exhaustion)."""
        if self._exhausted:
            return 0.0
        t0 = time.perf_counter()
        # One staged batch = one tpudist.prefetch span: the program's own
        # loader wait (tpudist.loader_next) and the placement (tpudist.h2d)
        # nest inside it.
        with jax.profiler.TraceAnnotation(scopes.SPAN_PREFETCH):
            try:
                with jax.profiler.TraceAnnotation(scopes.SPAN_LOADER_NEXT):
                    batch = next(self._it)
            except StopIteration:
                self._exhausted = True
                return 0.0
            local_bs = int(batch[0].shape[0])
            dev = shard_host_batch(self.mesh, batch, self.data_axis)
            self._q.append((dev, local_bs))
        return time.perf_counter() - t0

    def poke(self) -> float:
        """Top the queue up to ``depth`` — the trainer calls this right
        after dispatching the step, so the loader pull + H2D issue overlap
        the in-flight device compute. Returns the time spent."""
        spent = 0.0
        while len(self._q) < self.depth and not self._exhausted:
            spent += self._fill_one()
        return spent

    def __iter__(self):
        return self

    def __next__(self):
        while not self._q and not self._exhausted:
            self._fill_one()             # exposed: the chip is waiting
        if not self._q:
            raise StopIteration
        dev, self.last_local_bs = self._q.pop(0)
        return dev


def shard_host_batch(mesh: Mesh, batch, data_axis: str = "data"):
    """Place a host-local numpy batch onto the mesh, sharded along the batch dim.

    Single-host: a straight device_put with a batch sharding. Multi-host: each
    process provides its local shard and we assemble the global array
    (the DataLoader+DistributedSampler H2D path, ``distributed.py:242-243``).
    """
    sharding = batch_sharding(mesh, data_axis)
    # Label the copy so --profile traces attribute H2D time to this phase
    # (XProf/Perfetto show "tpudist.h2d" rows); no-op when no trace is live.
    with jax.profiler.TraceAnnotation(scopes.SPAN_H2D):
        if jax.process_count() == 1:
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), batch)
        from jax.experimental import multihost_utils
        return jax.tree_util.tree_map(
            lambda x: multihost_utils.host_local_array_to_global_array(
                x, mesh, P(data_axis)),
            batch)
