#!/usr/bin/env python
"""Ingest a real image corpus into the ``write_file_dataset`` record layout.

The file-backed data path (C++ prefetch ring → FileDataset →
training) was measured end to end but only ever fed synthetic stand-ins.
This recipe converts an actual corpus to the on-disk format the pread
workers consume, with a deterministic train/val split:

  --source dir:PATH        a directory of class subdirectories of images
                           (PNG/JPEG via PIL when available, else .npy),
                           the torchvision/ImageFolder convention —
                           the layout the reference's ImageNet example
                           consumed (SURVEY.md §2.9)
  --source npz:PATH        an .npz with ``images (N,H,W[,C])`` float/uint8
                           and ``labels (N,)`` int arrays
  --source sklearn-digits  the 1,797 real 8×8 handwritten digits shipped
                           inside scikit-learn — the one genuinely
                           non-synthetic corpus available in a zero-egress
                           environment; used for the committed convergence
                           artifact (scripts/train_digits.py)

Output: ``OUT/train/{data.bin,meta.json}`` and ``OUT/val/...`` — load with
``chainermn_tpu.FileDataset`` and stream through ``PrefetchIterator``.

Usage:
  python scripts/ingest_images.py --source sklearn-digits --out /tmp/digits
  python scripts/ingest_images.py --source dir:/data/imagenet --out /ssd/inet
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from chainermn_tpu import write_file_dataset  # noqa: E402


def load_sklearn_digits():
    from sklearn.datasets import load_digits

    d = load_digits()
    # real scans, 8×8 grayscale in [0, 16] — scale to [0, 1] and add the
    # channel axis the convnets expect (grayscale replicated to 3)
    images = (d.images.astype(np.float32) / 16.0)[..., None]
    images = np.repeat(images, 3, axis=-1)
    return images, d.target.astype(np.int32)


def load_npz(path):
    z = np.load(path)
    images, labels = z["images"], z["labels"]
    if images.ndim == 3:
        images = np.repeat(images[..., None], 3, axis=-1)
    # dtype is preserved: uint8 stays uint8 (4× smaller records;
    # normalize at train time), floats stay float
    return images, labels.astype(np.int32)


def _read_image(fp, Image):
    if fp.endswith(".npy"):
        arr = np.load(fp)
    elif Image is not None and fp.lower().endswith(
            (".png", ".jpg", ".jpeg", ".bmp")):
        arr = np.asarray(Image.open(fp).convert("RGB"))
    else:
        return None
    if arr.ndim == 2:
        arr = np.repeat(arr[..., None], 3, axis=-1)
    return arr


def load_dir(path):
    """ImageFolder layout: path/<class_name>/*.{png,jpg,npy}.

    Records keep the SOURCE dtype (PIL decodes to uint8 — store uint8,
    normalize at train time): per-image value-based normalization would
    silently put dark images on a different scale, and float32 records
    quadruple disk and RAM.  The corpus is materialized once into a
    preallocated array, so ingest is RAM-bound at the (uint8) corpus
    size — for a corpus bigger than RAM, run per-subset and shard the
    output directories."""
    classes = sorted(d for d in os.listdir(path)
                     if os.path.isdir(os.path.join(path, d)))
    if not classes:
        raise SystemExit(f"no class subdirectories under {path}")
    try:
        from PIL import Image
    except ImportError:
        Image = None
    files = [(os.path.join(path, cls, fn), ci)
             for ci, cls in enumerate(classes)
             for fn in sorted(os.listdir(os.path.join(path, cls)))]
    first = next((a for a in (_read_image(fp, Image) for fp, _ in files)
                  if a is not None), None)
    if first is None:
        raise SystemExit(f"no readable images under {path}")
    images = None
    labels = []
    n = 0
    for fp, ci in files:
        arr = _read_image(fp, Image)
        if arr is None:
            continue
        if arr.shape != first.shape:
            raise SystemExit(
                f"images must share one shape; {fp} is {arr.shape}, "
                f"expected {first.shape} — resize offline first "
                "(records are fixed-size)")
        if arr.dtype != first.dtype:
            # the implicit cast in `images[n] = arr` would silently corrupt
            # mixed corpora (float [0,1] scans truncating to uint8 zeros)
            raise SystemExit(
                f"images must share one dtype; {fp} is {arr.dtype}, "
                f"expected {first.dtype} — convert offline first "
                "(source dtype is preserved in the records)")
        if images is None:
            images = np.empty((len(files),) + first.shape, first.dtype)
        images[n] = arr
        labels.append(ci)
        n += 1
    return images[:n], np.asarray(labels, np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--source", required=True,
                    help="sklearn-digits | dir:PATH | npz:PATH")
    ap.add_argument("--out", required=True)
    ap.add_argument("--val-frac", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.source == "sklearn-digits":
        images, labels = load_sklearn_digits()
    elif args.source.startswith("dir:"):
        images, labels = load_dir(args.source[4:])
    elif args.source.startswith("npz:"):
        images, labels = load_npz(args.source[4:])
    else:
        raise SystemExit(f"unknown --source {args.source!r}")

    rs = np.random.RandomState(args.seed)
    order = rs.permutation(len(images))
    images, labels = images[order], labels[order]
    n_val = int(len(images) * args.val_frac)
    splits = {"val": (images[:n_val], labels[:n_val]),
              "train": (images[n_val:], labels[n_val:])}
    for name, (im, la) in splits.items():
        out = os.path.join(args.out, name)
        write_file_dataset(out, [np.ascontiguousarray(im),
                                 np.ascontiguousarray(la)])
        print(f"{out}: {len(im)} records, image {im.shape[1:]} {im.dtype}, "
              f"{len(np.unique(la))} classes")


if __name__ == "__main__":
    main()
