"""The input path against a plain decoder, for mixes that read files.

For a sample of rows (drawn from the seed) of the first three batches the
file is found by an independent listing of the corpus in torchvision's
ImageFolder order, decoded by PIL, cropped to the RandomResizedCrop box that
torchvision's algorithm draws from the row's generator, resized bilinearly,
flipped and normalised: plain PIL + numpy, nothing of the program. The
program's row may differ by its DCT-scaled partial decode and its own resize
taps, so the number compared is the row's mean absolute gap in normalised
units; labels have to be the folder's class index exactly.

Taken from the program: which dataset index fills which row (the loader's
own statement), and the generator's key (seed, epoch, index).
"""

from __future__ import annotations

import math
import os

import numpy as np

MEAN = np.array([0.485, 0.456, 0.406], np.float32)
STD = np.array([0.229, 0.224, 0.225], np.float32)
EXT = (".jpg", ".jpeg", ".png")


def listing(train_root: str):
    classes = sorted(e.name for e in os.scandir(train_root) if e.is_dir())
    out = []
    for ci, c in enumerate(classes):
        for dirpath, _, files in sorted(os.walk(os.path.join(train_root, c))):
            out.extend((os.path.join(dirpath, f), ci) for f in sorted(files)
                       if f.lower().endswith(EXT))
    return out


def rrc_box(w, h, rng, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3)):
    """torchvision.transforms.RandomResizedCrop.get_params."""
    area = w * h
    lo, hi = math.log(ratio[0]), math.log(ratio[1])
    for _ in range(10):
        target = area * rng.uniform(scale[0], scale[1])
        aspect = math.exp(rng.uniform(lo, hi))
        cw = int(round(math.sqrt(target * aspect)))
        ch = int(round(math.sqrt(target / aspect)))
        if 0 < cw <= w and 0 < ch <= h:
            x0 = int(rng.integers(0, w - cw + 1))
            y0 = int(rng.integers(0, h - ch + 1))
            return x0, y0, cw, ch
    r = w / h
    if r < ratio[0]:
        cw, ch = w, int(round(w / ratio[0]))
    elif r > ratio[1]:
        ch, cw = h, int(round(h * ratio[1]))
    else:
        cw, ch = w, h
    return (w - cw) // 2, (h - ch) // 2, cw, ch


def plain_row(path, rng, size):
    from PIL import Image
    with Image.open(path) as im:
        im = im.convert("RGB")
        x0, y0, cw, ch = rrc_box(im.width, im.height, rng)
        flip = bool(rng.random() < 0.5)
        im = im.resize((size, size), Image.BILINEAR,
                       box=(x0, y0, x0 + cw, y0 + ch))
    a = np.asarray(im, np.float32) / 255.0
    if flip:
        a = a[:, ::-1]
    return (a - MEAN) / STD


def gaps(rows, root, seed, size, per_batch=32):
    """-> (worst mean-abs gap over the sampled rows, label mismatches)."""
    files = listing(os.path.join(root, "train"))
    pick = np.random.default_rng((seed, 0x1CE))
    worst, wrong = 0.0, 0
    for epoch, idx, (images, labels) in rows:
        for r in pick.choice(len(idx), size=min(per_batch, len(idx)),
                             replace=False):
            path, label = files[int(idx[r])]
            wrong += int(int(labels[r]) != label)
            want = plain_row(path, np.random.default_rng(
                (seed, epoch, int(idx[r]))), size)
            worst = max(worst, float(np.mean(np.abs(
                np.asarray(images[r], np.float32) - want))))
    return worst, wrong


def compare(rows, cfg, seed, limits):
    worst, wrong = gaps(rows, cfg.data, seed, cfg.image_size)
    return [("input_row_mean_abs_gap", worst,
             limits["input_row_mean_abs_gap"],
             "loader row vs PIL decode + crop + bilinear resize, worst of "
             "the sampled rows, normalised units"),
            ("input_label_mismatches", float(wrong),
             limits["input_label_mismatches"], "row label vs folder class")]
