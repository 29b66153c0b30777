"""Assemble the full train/val input pipeline for a run (reference
``distributed.py:156-179``): datasets + per-process sharding + loaders.

Per-host sharding: with P processes each owning D local devices, process p is
"rank p of P" at the DATA level (its loader yields global_batch/P samples) and
the global SPMD step sees the assembled global batch — the TPU analogue of
DistributedSampler rank/world_size (``distributed.py:167``).
"""

from __future__ import annotations

from functools import partial

import jax

from tpudist.config import Config
from tpudist.data.imagefolder import ImageFolder
from tpudist.data.loader import DataLoader
from tpudist.data.sampler import ShardedSampler
from tpudist.data.synthetic import SyntheticDataset
from tpudist.data import transforms


def build_train_val_loaders(cfg: Config, vocab_size: int | None = None):
    """``vocab_size`` (the ids a model of tokens holds) selects the token
    source: rows of ``cfg.seq_len`` ids behind the same ``--synthetic`` /
    ``--synthetic-size`` flags as the image source (no corpus reader yet)."""
    import os

    # Data rank/world from the distributed runtime, or — in the launcher's
    # elastic CPU simulation (independent jit ranks, TPUDIST_ELASTIC=1) —
    # from the launcher-assigned env identity, so each rank loads its 1/W
    # shard and the elastic sample cursor counts global samples correctly.
    from tpudist.dist import data_rank_world
    pid, nproc = data_rank_world()
    host_batch = cfg.batch_size // nproc
    seed = cfg.seed if cfg.seed is not None else 0

    if vocab_size and not (cfg.synthetic or not cfg.data):
        raise ValueError("a model of tokens trains on --synthetic rows only: "
                         "there is no corpus reader yet")
    if vocab_size:
        from tpudist.data.synthetic import SyntheticTokens
        n_train = cfg.synthetic_size or max(host_batch * nproc * 4, 64)
        train_ds = SyntheticTokens(n_train, cfg.seq_len, vocab_size, seed)
        val_ds = SyntheticTokens(max(n_train // 2, host_batch), cfg.seq_len,
                                 vocab_size, seed + 1)
        train_tf = val_tf = None
    elif cfg.synthetic or not cfg.data:
        n_train = getattr(cfg, "synthetic_size", 0) \
            or max(host_batch * nproc * 4, 256)
        train_ds = SyntheticDataset(n_train, cfg.image_size,
                                    cfg.num_classes, seed)
        val_ds = SyntheticDataset(max(n_train // 2, host_batch),
                                  cfg.image_size, cfg.num_classes, seed + 1)
        train_tf = val_tf = None
    else:
        # Prefer the fused C++ kernels (native/transforms.cc + jpeg.cc); fall
        # back to the pure PIL/numpy stack when the library isn't available.
        from tpudist.data import autoaugment, native
        aa = autoaugment.build(getattr(cfg, "auto_augment", ""))
        re_p = getattr(cfg, "random_erase", 0.0)
        # The fused C++ kernels cover the reference's crop/flip/normalize
        # stack only; auto-augment/random-erasing move the TRAIN transform
        # onto the PIL path. Each split picks its loader independently: val
        # never runs those train-only transforms, so it keeps the fully-
        # native raw-bytes path (fused JPEG decode) regardless.
        train_loader_fn = val_loader_fn = None
        if native.jpeg_available() and aa is None and re_p == 0.0:
            # Fully-native path: the dataset yields raw bytes and JPEG decode
            # happens inside the fused kernel (partial, DCT-scaled decode);
            # the transforms PIL-decode any non-JPEG bytes themselves.
            train_loader_fn = ImageFolder.raw_loader
            train_tf = partial(_native_jpeg_train_tf, size=cfg.image_size)
        elif native.available() and aa is None and re_p == 0.0:
            train_tf = partial(_native_train_tf, size=cfg.image_size)
        else:
            train_tf = partial(_train_tf, size=cfg.image_size, aa=aa,
                               random_erase=re_p)
        if native.jpeg_available():
            val_loader_fn = ImageFolder.raw_loader
            val_tf = partial(_native_jpeg_val_tf, size=cfg.image_size,
                             resize=cfg.val_resize)
        elif native.available():
            val_tf = partial(_native_val_tf, size=cfg.image_size,
                             resize=cfg.val_resize)
        else:
            val_tf = partial(_val_tf, size=cfg.image_size,
                             resize=cfg.val_resize)
        train_ds = ImageFolder(os.path.join(cfg.data, "train"),
                               loader=train_loader_fn)
        val_ds = ImageFolder(os.path.join(cfg.data, "val"),
                             loader=val_loader_fn)

    # DistributedSampler for BOTH train and val, like the reference
    # (distributed.py:167,177 — including the padded-val quirk).
    train_sampler = ShardedSampler(len(train_ds), nproc, pid, shuffle=True, seed=seed)
    val_sampler = ShardedSampler(len(val_ds), nproc, pid, shuffle=False, seed=seed)

    degrade = dict(retries=getattr(cfg, "data_retries", 2),
                   retry_backoff=getattr(cfg, "data_retry_backoff", 0.05),
                   skip_budget=getattr(cfg, "data_skip_budget", 0))
    train_loader = DataLoader(train_ds, host_batch, sampler=train_sampler,
                              transform=train_tf, num_workers=cfg.workers,
                              drop_last=True, seed=seed, **degrade)
    # Val must see EVERY sample (torch DataLoader default drop_last=False):
    # the final partial batch is padded by wrapping to a device-count multiple
    # (≤ local_device_count-1 duplicates) instead of dropping up to
    # host_batch-1 images, which would skew best-model selection.
    val_loader = DataLoader(val_ds, host_batch, sampler=val_sampler,
                            transform=val_tf, num_workers=cfg.workers,
                            drop_last=False,
                            round_up_to=jax.local_device_count(), seed=seed,
                            **degrade)
    return train_loader, val_loader


def _train_tf(img, rng, size, aa=None, random_erase=0.0):
    return transforms.train_transform(img, size, rng, aa=aa,
                                      random_erase=random_erase)


def _val_tf(img, rng, size, resize):
    return transforms.val_transform(img, size, resize)


def _native_train_tf(img, rng, size):
    from tpudist.data import native
    return native.train_transform(img, size, rng)


def _native_val_tf(img, rng, size, resize):
    from tpudist.data import native
    return native.val_transform(img, size, resize)


def _pil_decode(data):
    import io

    from PIL import Image
    return Image.open(io.BytesIO(data)).convert("RGB")


def _native_jpeg_train_tf(data, rng, size):
    from tpudist.data import native
    out = native.decode_train_transform(data, size, rng)
    if out is not None:
        return out
    return native.train_transform(_pil_decode(data), size, rng)


def _native_jpeg_val_tf(data, rng, size, resize):
    from tpudist.data import native
    out = native.decode_val_transform(data, size, resize)
    if out is not None:
        return out
    return native.val_transform(_pil_decode(data), size, resize)
