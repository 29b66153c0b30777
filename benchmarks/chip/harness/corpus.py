"""A JPEG corpus at ImageNet's sizes, generated once into a git-ignored
directory from the traffic file's parameters (never from `--seed`: every
seed reads the same files in another order).

Pictures are a coarse random colour field upsampled smoothly, a mid-scale
texture and a little noise, so that the files compress and decode like
photographs (tens to a hundred-odd KB at quality 90) and not like noise.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _picture(rng: np.random.Generator, w: int, h: int):
    from PIL import Image

    def field(cells, amp):
        g = rng.uniform(0, 255, (cells * h // max(w, h) + 2,
                                 cells * w // max(w, h) + 2, 3))
        img = Image.fromarray(g.astype(np.uint8)).resize((w, h), Image.BICUBIC)
        return (np.asarray(img, np.float32) - 127.5) * amp

    x = 127.5 + field(4, 0.8) + field(24, 0.3) + field(96, 0.12)
    x += rng.normal(0, 4.0, x.shape)
    return Image.fromarray(np.clip(x, 0, 255).astype(np.uint8))


def _write(args):
    path, seed, w, h, quality = args
    _picture(np.random.default_rng(seed), w, h).save(
        path, "JPEG", quality=quality)


def ensure(root: str, spec: dict, threads: int = 8) -> dict:
    """Make `root/train/<class>/*.jpg` (+ a token `val/`) if it is not
    already there for this `spec`. Returns counts and bytes."""
    tag = hashlib.sha256(json.dumps(spec, sort_keys=True).encode()).hexdigest()
    marker = os.path.join(root, "complete.json")
    if os.path.exists(marker):
        with open(marker) as f:
            done = json.load(f)
        if done.get("tag") == tag:
            return dict(done, generated=False)
    shutil.rmtree(root, ignore_errors=True)
    rng = np.random.default_rng(int(spec["seed"]))
    sizes = [tuple(s) for s in spec["sizes"]]
    weights = np.asarray(spec["size_weights"], np.float64)
    jobs = []
    for split, n_files, n_classes in (
            ("train", int(spec["files"]), int(spec["classes"])),
            ("val", int(spec["val_files"]), 1)):
        for c in range(n_classes):
            os.makedirs(os.path.join(root, split, f"c{c:04d}"), exist_ok=True)
        for i in range(n_files):
            w, h = sizes[int(rng.choice(len(sizes), p=weights / weights.sum()))]
            jobs.append((os.path.join(root, split, f"c{i % n_classes:04d}",
                                      f"{i:06d}.jpg"),
                         int(rng.integers(0, 2 ** 31)), w, h,
                         int(spec["quality"])))
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(_write, jobs))
    # an epoch as long as a real data set's, without its bytes: every file is
    # listed `links_per_file` times (symlinks beside it), so that no epoch
    # ends, and no loader restarts, inside a measured window
    links = int(spec.get("links_per_file", 1))
    for path, *_ in jobs:
        if os.sep + "train" + os.sep not in path:
            continue
        stem = path[:-len(".jpg")]
        for k in range(1, links):
            os.symlink(os.path.basename(path), f"{stem}_l{k:02d}.jpg")
    done = {"tag": tag, "files": len(jobs), "links_per_file": links,
            "bytes": sum(os.path.getsize(j[0]) for j in jobs)}
    with open(marker, "w") as f:
        json.dump(done, f)
    return dict(done, generated=True)
