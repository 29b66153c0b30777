"""One general traffic generator, driven by the traffic mix's data file.

A mix's file (`traffic/<name>.json`) has a `kind` and that kind's
parameters; a new mix is a new file. Two kinds:

- `resident`: `distinct_batches` seeded batches made on the device in one
  jitted function and cycled, so the step program does all the work;
- `imagefolder`: the program's own ImageFolder loader
  (`tpudist.data.pipeline.build_train_val_loaders`, `native/` built on this
  machine) over a generated JPEG corpus, epochs chained; order and
  augmentation follow `--seed`.

`Feed` is what the trainer iterates (it wraps it in its own
`DevicePrefetcher`): it hands out a set number of batches, or batches until a
deadline, and records a `bench.loader_next` span around every pull.
"""

from __future__ import annotations

import os
import time


class Feed:
    def __init__(self, source, spans):
        self.source = source
        self.spans = spans
        self.served = 0
        self._left = 0
        self._deadline = None

    def batches(self, n: int) -> "Feed":
        self._left, self._deadline = n, None
        return self

    def until(self, deadline: float) -> "Feed":
        """Serve until `time.perf_counter()` passes `deadline`."""
        self._left, self._deadline = 0, deadline
        return self

    def __len__(self) -> int:          # the trainer's progress meter asks
        return self._left or 10 ** 6

    def __iter__(self):
        return self

    def __next__(self):
        if self._deadline is None:
            if self._left <= 0:
                raise StopIteration
            self._left -= 1
        elif time.perf_counter() >= self._deadline:
            raise StopIteration
        with self.spans.span("bench.loader_next"):
            batch = self.source.next()
        self.served += 1
        return batch


class ResidentSource:
    """`distinct_batches` batches of seeded N(0,1) images and uniform labels,
    made on the device already laid out as the trainer shards a batch."""

    def __init__(self, spec, *, seed, batch, image_size, num_classes,
                 sharding, **_):
        import jax
        import jax.numpy as jnp
        k = int(spec["distinct_batches"])
        if k < 3:
            raise ValueError("the comparison follows three steps on rows "
                             "that all differ: distinct_batches >= 3")

        def make(key):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(
                k1, (batch, image_size, image_size, 3), jnp.float32),
                jax.random.randint(k2, (batch,), 0, num_classes, jnp.int32))

        make = jax.jit(make, out_shardings=(sharding, sharding))
        root = jax.random.fold_in(jax.random.PRNGKey(seed), 0x7AFF1C)
        self._batches = [make(jax.random.fold_in(root, i)) for i in range(k)]
        self._i = 0
        self.info = {"kind": "resident", "distinct_batches": k}

    def next(self):
        b = self._batches[self._i % len(self._batches)]
        self._i += 1
        return b

    def first(self, n):
        return self._batches[:n]

    def rows_check(self, n):
        return None

    def close(self):
        self._batches = []


class FolderSource:
    """The program's train loader over the generated corpus."""

    def __init__(self, spec, *, seed, cfg, workdir, **_):
        from harness import corpus
        from tpudist.data import native
        from tpudist.data.pipeline import build_train_val_loaders
        t0 = time.perf_counter()
        if not native.build() or not native.jpeg_available():
            raise RuntimeError(
                "native/ did not build on this machine (make -C native): the "
                "jpeg mix measures the native decode path and has no fallback")
        t1 = time.perf_counter()
        root = os.path.join(workdir, "corpus", str(spec["corpus"]["name"]))
        made = corpus.ensure(root, spec["corpus"])
        t2 = time.perf_counter()
        cfg.data, cfg.synthetic = root, False
        self.loader, _ = build_train_val_loaders(cfg)
        self._epoch = 0
        self._it = None
        self._kept = []
        self._keep = 3
        self.info = {"kind": "imagefolder", "native_build_s": t1 - t0,
                     "corpus_s": t2 - t1, "corpus": made,
                     "steps_per_epoch": len(self.loader),
                     "workers": cfg.workers}
        if len(self.loader) < 1:
            raise ValueError("corpus smaller than one batch")

    def next(self):
        while True:
            if self._it is None:
                self.loader.set_epoch(self._epoch)
                self._it = iter(self.loader)
            try:
                batch = next(self._it)
                break
            except StopIteration:
                self._it, self._epoch = None, self._epoch + 1
        if len(self._kept) < self._keep:
            self._kept.append((self._epoch, batch))
        return batch

    def first(self, n):
        return [b for _, b in self._kept[:n]]

    def rows_check(self, n):
        """(epoch, dataset indices, batch) of the first kept batches, for the
        input-path comparison. The indices are the loader's own statement of
        which file fills which row; the pixels are recomputed from the file."""
        out = []
        for step, (epoch, batch) in enumerate(self._kept[:n]):
            self.loader.set_epoch(epoch)
            idx = self.loader._index_batches()[step % len(self.loader)]
            out.append((epoch, idx, batch))
        return out

    def drain_alone(self, seconds: float) -> float:
        """Images per second of the loader with no device step behind it."""
        self.loader.set_epoch(10 ** 6)
        n, t0 = 0, time.perf_counter()
        it = iter(self.loader)
        for images, _ in it:
            n += len(images)
            if time.perf_counter() - t0 >= seconds:
                break
        rate = n / (time.perf_counter() - t0)
        it.close()
        self.loader.set_epoch(self._epoch)
        return rate

    def close(self):
        if self._it is not None:
            self._it.close()           # stops the loader's producer thread
            self._it = None
        self._kept = []


KINDS = {"resident": ResidentSource, "imagefolder": FolderSource}


def make_source(spec, **kw):
    try:
        kind = KINDS[spec["kind"]]
    except KeyError:
        raise ValueError(f"traffic kind {spec.get('kind')!r} is not one of "
                         f"{sorted(KINDS)}") from None
    return kind(spec, **kw)
