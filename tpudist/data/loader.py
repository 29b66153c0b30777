"""Batched, prefetching data loader (reference ``DataLoader(num_workers=8,
pin_memory=True)``, ``distributed.py:168-169``).

torch's DataLoader forks worker PROCESSES and pins host memory for async H2D.
The TPU-native shape is different: the hot path is host→TPU transfer of one
fused batch per step, so this loader uses a THREAD pool (PIL/numpy release the
GIL for decode/resize) assembling samples directly into a preallocated batch
buffer, plus a bounded prefetch queue so batch N+1 decodes while N trains —
the same overlap DataLoader's workers + pin_memory provide. A C++ decode/
augment path can be slotted in as ``loader`` without changing this class.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional, Sequence

import numpy as np


class DataLoader:
    def __init__(self, dataset, batch_size: int,
                 sampler=None,
                 transform: Optional[Callable] = None,
                 num_workers: int = 4,
                 prefetch: int = 2,
                 drop_last: bool = True,
                 round_up_to: Optional[int] = None,
                 seed: int = 0,
                 retries: int = 2,
                 retry_backoff: float = 0.05,
                 skip_budget: int = 0):
        """``transform(sample, rng) -> np.ndarray`` runs in worker threads.
        ``sampler`` yields dataset indices (ShardedSampler for DDP parity);
        None = sequential. With ``drop_last=False``, ``round_up_to=k`` pads the
        final partial batch by wrapping to a multiple of k (SPMD needs batches
        divisible by the device count; ≤k-1 duplicate samples — same class of
        skew as DistributedSampler's padding, reference quirk #12 — instead of
        dropping up to batch_size-1 samples).

        Degradation under storage faults (fleet-scale reads WILL hit flaky
        NFS/GCS and the odd corrupt JPEG): a failing read/decode/transform is
        retried ``retries`` times with linear ``retry_backoff`` (transient
        shape), then the sample is SKIPPED — counted in ``samples_skipped``,
        its batch slot refilled with a neighbor from the same batch (the same
        class of duplicate-sample skew as the padding above) — and only past
        ``skip_budget`` skips in one epoch does the loader fail loudly.
        ``skip_budget=0`` (default) means strict: the first persistent
        failure raises. ``samples_retried`` counts retry-healed loads; both
        meters reset per epoch."""
        self.dataset = dataset
        self.batch_size = batch_size
        self.sampler = sampler
        self.transform = transform
        self.num_workers = max(1, num_workers)
        self.prefetch = max(1, prefetch)
        self.drop_last = drop_last
        self.round_up_to = round_up_to
        self.seed = seed
        self.epoch = 0
        self.retries = max(0, retries)
        self.retry_backoff = max(0.0, retry_backoff)
        self.skip_budget = max(0, skip_budget)
        self.samples_skipped = 0
        self.samples_retried = 0
        self._stats_lock = threading.Lock()
        self._failed_keys: set[int] = set()   # distinct bad samples, per epoch
        # Elastic continuation: meter baselines carried over a reform (the
        # pre-reform attempt's skip/retry counts must survive into the
        # resumed epoch's accounting) — consumed by the next __iter__.
        self._carry_skipped = 0
        self._carry_retried = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if self.sampler is not None and hasattr(self.sampler, "set_epoch"):
            self.sampler.set_epoch(epoch)

    def set_cursor(self, consumed: int, samples_skipped: int = 0,
                   samples_retried: int = 0) -> None:
        """Elastic continuation of an interrupted epoch: resume this epoch's
        deterministic global order at position ``consumed`` (delegates to
        ``ShardedSampler.set_cursor``; call AFTER ``set_epoch``) and seed
        the per-epoch degradation meters with the interrupted attempt's
        checkpointed counts so skip/retry accounting spans the reform."""
        if self.sampler is not None and hasattr(self.sampler, "set_cursor"):
            self.sampler.set_cursor(consumed)
        self._carry_skipped = max(0, int(samples_skipped))
        self._carry_retried = max(0, int(samples_retried))

    def set_skip_windows(self, windows) -> None:
        """Doctor rollback replay: excise the poisoned global-position
        windows from this epoch's order (delegates to
        ``ShardedSampler.set_skip_windows``; call AFTER ``set_epoch``)."""
        if self.sampler is not None and hasattr(self.sampler,
                                                "set_skip_windows"):
            self.sampler.set_skip_windows(windows)

    def _index_batches(self) -> list[np.ndarray]:
        if self.sampler is not None:
            idx = np.fromiter(iter(self.sampler), dtype=np.int64)
        else:
            idx = np.arange(len(self.dataset))
        n_full = len(idx) // self.batch_size
        batches = [idx[i * self.batch_size:(i + 1) * self.batch_size]
                   for i in range(n_full)]
        rest = idx[n_full * self.batch_size:]
        if not self.drop_last and len(rest):
            if self.round_up_to and len(rest) % self.round_up_to:
                pad = self.round_up_to - len(rest) % self.round_up_to
                rest = np.concatenate([rest, idx[:pad]])
            batches.append(rest)
        return batches

    def __len__(self) -> int:
        return len(self._index_batches())

    def _load_sample(self, ds_index: int):
        """One sample through read→decode→transform with bounded retry.
        Transient failures (injected via the ``decode_fail`` fault point, or
        real IO flake) heal on retry and count in ``samples_retried``;
        exhausting the budget re-raises the last error for the caller's
        skip-and-count path."""
        from tpudist import faults
        last_err = None
        for attempt in range(self.retries + 1):
            try:
                if faults.decode_should_fail(ds_index):
                    raise IOError(
                        f"injected decode failure (sample {ds_index})")
                sample, label = self.dataset[ds_index]
                if self.transform is not None:
                    rng = np.random.default_rng(
                        (self.seed, self.epoch, ds_index))
                    sample = self.transform(sample, rng)
                sample = np.asarray(sample)
                if sample.dtype != np.int32:      # token ids stay ids
                    sample = sample.astype(np.float32)
                if attempt:
                    with self._stats_lock:
                        self.samples_retried += 1
                return sample, label
            except Exception as e:           # noqa: BLE001 — re-raised below
                last_err = e
                if attempt < self.retries and self.retry_backoff > 0:
                    time.sleep(self.retry_backoff * (attempt + 1))
        raise last_err

    def _assemble(self, batch_idx: np.ndarray, batch_no: int):
        # allocated on the first sample: float32 images [n, H, W, C] with
        # a class each, or int32 token rows [n, T] with a target a position
        images = labels = None
        lock = threading.Lock()
        positions = list(enumerate(batch_idx))
        cursor = [0]
        errors: list[BaseException] = []

        def worker():
            nonlocal images, labels
            while True:
                with lock:
                    if errors or cursor[0] >= len(positions):
                        return
                    pos, ds_index = positions[cursor[0]]
                    cursor[0] += 1
                # Walk the batch starting at this slot's own index: the
                # first loadable sample fills the slot. Each DISTINCT bad
                # sample is charged against the corruption budget exactly
                # once per epoch (a neighbor walking over an already-known-
                # bad index must neither re-charge the budget nor re-pay
                # the retry backoff).
                sample = label = None
                for k in range(len(batch_idx)):
                    cand = int(batch_idx[(pos + k) % len(batch_idx)])
                    with self._stats_lock:
                        if cand in self._failed_keys:
                            continue
                    try:
                        sample, label = self._load_sample(cand)
                        break
                    except Exception as e:   # noqa: BLE001
                        with self._stats_lock:
                            if cand not in self._failed_keys:
                                self._failed_keys.add(cand)
                                self.samples_skipped += 1
                            skipped = self.samples_skipped
                        if skipped > self.skip_budget:
                            with lock:
                                errors.append(RuntimeError(
                                    f"data-path corruption budget exceeded: "
                                    f"{skipped} sample(s) still failing "
                                    f"after {self.retries} retries "
                                    f"(budget {self.skip_budget}); last "
                                    f"error on sample {cand}: {e}"))
                            return
                if sample is None:
                    with lock:
                        errors.append(RuntimeError(
                            f"no loadable sample in batch {batch_no}: all "
                            f"{len(batch_idx)} candidates failed"))
                    return
                with lock:
                    if images is None:
                        images = np.empty((len(batch_idx),) + sample.shape,
                                          dtype=sample.dtype)
                        labels = np.empty(
                            (len(batch_idx),) + np.shape(label), np.int32)
                images[pos] = sample
                labels[pos] = label

        threads = [threading.Thread(target=worker)
                   for _ in range(min(self.num_workers, len(positions)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return images, labels

    def __iter__(self) -> Iterator:
        batches = self._index_batches()
        with self._stats_lock:      # per-epoch meters (carry spans a reform)
            self.samples_skipped = self._carry_skipped
            self.samples_retried = self._carry_retried
            self._carry_skipped = 0
            self._carry_retried = 0
            self._failed_keys = set()
        q: queue.Queue = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def put(item) -> bool:
            # Bounded put that notices consumer abandonment: a plain q.put on
            # a full queue would park this thread forever (leaking it plus the
            # prefetched batches) if the consumer exits mid-epoch.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.5)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            for bno, b in enumerate(batches):
                if stop.is_set():
                    return
                try:
                    batch = self._assemble(b, bno)
                except BaseException as e:   # noqa: BLE001 — crosses threads
                    # Fail LOUDLY on the consumer side: a producer that dies
                    # silently would end the epoch early and silently train
                    # on a truncated dataset.
                    put(e)
                    return
                if not put(batch):
                    return
            put(None)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
