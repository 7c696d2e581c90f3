"""IID image traffic: every image cell's inputs from its seed.

A configuration file's ``data`` block names the image task (classes, image
size, train-set size, noise) and a workload file names the job (clients,
batch, rounds per chunk).  From those and ``--seed`` this module makes:

  * the training images: class-conditional low-frequency prototypes (an
    8x8 random field per class, upsampled to the image size), each sample
    cyclically shifted by 0-3 pixels on both axes, plus Gaussian noise of
    the configured scale.  This is the semantics of the repository's
    ``SyntheticImageDataset``, kept here so the yardstick cannot move with
    the program;
  * the IID client shards: one seeded permutation split into equal parts
    (``numpy.array_split``), as ``ClientPartitioner`` does;
  * the order in which a client consumes its shard: per epoch one
    permutation from ``numpy.random.default_rng(seed + client)``, cut into
    whole batches.  The training session draws its batches in this order
    (``data.pipeline.batch_iterator``); the plain reference replays it from
    here.

Every seed gives the same sizes: only the pixel values, labels and orders
change.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

#: rows generated per block, so the noise temporary stays small
_BLOCK = 4096


def make_images(seed: int, num_classes: int, image_size: int, n: int,
                noise: float) -> Tuple[np.ndarray, np.ndarray]:
    """``n`` float32 NHWC images with int32 labels."""
    rng = np.random.default_rng(seed)
    low = rng.standard_normal((num_classes, 8, 8, 3), dtype=np.float32)
    reps = image_size // 8
    protos = np.repeat(np.repeat(low, reps, 1), reps, 2)
    # every (row shift, column shift) of every prototype: [C, 4, 4, H, W, 3]
    shifted = np.stack([np.stack([np.roll(np.roll(protos, a, 1), b, 2)
                                  for b in range(4)], 1)
                        for a in range(4)], 1)
    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    shifts = rng.integers(0, 4, size=(n, 2))
    imgs = np.empty((n, image_size, image_size, 3), np.float32)
    for lo in range(0, n, _BLOCK):
        hi = min(n, lo + _BLOCK)
        block = imgs[lo:hi]
        rng.standard_normal(out=block, dtype=np.float32)
        block *= np.float32(noise)
        block += shifted[labels[lo:hi], shifts[lo:hi, 0], shifts[lo:hi, 1]]
    return imgs, labels


def iid_shards(x: np.ndarray, y: np.ndarray, clients: int, seed: int
               ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Uniform random IID split into ``clients`` near-equal shards."""
    perm = np.random.default_rng(seed).permutation(len(x))
    return [(x[s], y[s]) for s in np.array_split(perm, clients)]


def batch_order(n: int, batch: int, seed: int) -> Iterator[np.ndarray]:
    """Row indices of a client's successive batches (epoch after epoch)."""
    rng = np.random.default_rng(seed)
    bs = min(batch, n)
    while True:
        perm = rng.permutation(n)
        for i in range(0, n - bs + 1, bs):
            yield perm[i:i + bs]


def first_batches(shard: Tuple[np.ndarray, np.ndarray], batch: int,
                  seed: int, count: int) -> Tuple[np.ndarray, np.ndarray]:
    """The first ``count`` batches a client trains on: ``[count, B, ...]``."""
    x, y = shard
    order = batch_order(len(x), batch, seed)
    idx = np.stack([next(order) for _ in range(count)])
    return x[idx], y[idx]


def cell_data(seed: int, data: dict, clients: int):
    """The whole training set of a cell and its client shards."""
    x, y = make_images(seed, data["num_classes"], data["image_size"],
                       data["train_size"], data["noise"])
    return iid_shards(x, y, clients, seed)
