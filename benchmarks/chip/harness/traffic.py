"""One general traffic generator, driven by the traffic mix's data file.

A mix's file (`traffic/<name>.json`) has a `kind` and that kind's
parameters; a new mix is a new file. Two kinds:

- `resident`: `distinct_batches` seeded batches made on the device in one
  jitted function and cycled, so the step program does all the work. What a
  row is the mix's `rows` says: `images` (the default: float32 NHWC pixels
  and a class label, sized by the configuration's `image_size` and
  `num_classes`) or `tokens` (int32 ids of `seq_len` positions, the mix's
  own, drawn from the configuration's `vocab_size`, with the next id as each
  position's target);
- `imagefolder`: the program's own ImageFolder loader
  (`tpudist.data.pipeline.build_train_val_loaders`, `native/` built on this
  machine) over a generated JPEG corpus, epochs chained; order and
  augmentation follow `--seed`.

What a mix needs of the configuration it is paired with it asks for by name:
a configuration that lacks it is refused (`Refuse`), never a `KeyError`.

`Feed` is what the trainer iterates (it wraps it in its own
`DevicePrefetcher`): it hands out a set number of batches, or batches until a
deadline, and records a `bench.loader_next` span around every pull.
"""

from __future__ import annotations

import os
import time

from harness.errors import Refuse


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


def _need(holder: dict, key: str, mix: dict) -> int:
    """The whole number `key` that `mix` needs of `holder`: the configuration
    it is paired with, or its own file."""
    value = holder.get(key)
    if isinstance(value, bool) or not isinstance(value, int) or value < 1:
        whose = ("its own file" if holder is mix
                 else f"the configuration {holder.get('name')!r}")
        raise Refuse(f"traffic mix {mix.get('name')!r} draws "
                     f"{mix.get('rows', 'images')} rows and needs a whole "
                     f"number {key!r} of {whose}, which has {value!r}")
    return value


def _image_rows(spec: dict, config: dict, batch: int):
    """Seeded N(0,1) pixels, float32 NHWC, and uniform class labels."""
    import jax
    import jax.numpy as jnp
    image_size = _need(config, "image_size", spec)
    num_classes = _need(config, "num_classes", spec)

    def make(key):
        k1, k2 = jax.random.split(key)
        return (jax.random.normal(
            k1, (batch, image_size, image_size, 3), jnp.float32),
            jax.random.randint(k2, (batch,), 0, num_classes, jnp.int32))

    return make, {}


def _token_rows(spec: dict, config: dict, batch: int):
    """One seeded draw of `seq_len + 1` int32 ids a row, uniform over the
    ids the configuration holds (a sliced vocabulary draws from the slice);
    inputs are all but the last, targets all but the first."""
    import jax
    import jax.numpy as jnp
    seq_len = _need(spec, "seq_len", spec)
    vocab_size = _need(config, "vocab_size", spec)

    def make(key):
        ids = jax.random.randint(key, (batch, seq_len + 1), 0, vocab_size,
                                 jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    return make, {"seq_len": seq_len, "vocab_size": vocab_size,
                  "tokens_per_batch": batch * seq_len}


ROWS = {"images": _image_rows, "tokens": _token_rows}


class ResidentSource:
    """`distinct_batches` seeded batches of the mix's `rows`, made on the
    device already laid out as the trainer shards a batch (the leading axis
    of inputs and targets alike)."""

    def __init__(self, spec, *, seed, batch, config, sharding, **_):
        import jax
        k = int(spec["distinct_batches"])
        if k < 3:
            raise Refuse("the comparison follows three steps on rows that "
                         "all differ: distinct_batches >= 3")
        rows = spec.get("rows", "images")
        if rows not in ROWS:
            raise Refuse(f"traffic mix {spec.get('name')!r}: rows {rows!r} "
                         f"is not one of {sorted(ROWS)}")
        make, said = ROWS[rows](spec, config, batch)
        make = jax.jit(make, out_shardings=(sharding, sharding))
        root = jax.random.fold_in(jax.random.PRNGKey(seed), 0x7AFF1C)
        self._batches = [make(jax.random.fold_in(root, i)) for i in range(k)]
        self._i = 0
        self.info = {"kind": "resident", "rows": rows,
                     "distinct_batches": k, **said}

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
    kind = KINDS.get(spec.get("kind"))
    if kind is None:
        raise Refuse(f"traffic mix {spec.get('name')!r}: kind "
                     f"{spec.get('kind')!r} is not one of {sorted(KINDS)}")
    return kind(spec, **kw)
