"""CIFAR-10 data pipeline.

The reference has no CIFAR experiment — it is this repo's own target
workload. Loader reads the standard "CIFAR-10 python version" pickle batches
(``data_batch_1..5`` + ``test_batch``: dict with ``b"data"`` uint8
[n, 3072] row-major CHW and ``b"labels"``); :func:`synthetic_cifar10` is
the zero-egress stand-in with the same shapes/dtypes (class-coded color
patterns, learnable by the ConvNet).
"""

from __future__ import annotations

import os
import pickle
from typing import Dict, Optional, Tuple

import numpy as np

Split = Tuple[np.ndarray, np.ndarray]  # (imgs uint8 [n,32,32,3], labels uint8 [n])

TRAIN_BATCHES = tuple(f"data_batch_{i}" for i in range(1, 6))
TEST_BATCH = "test_batch"


def _read_batch(path: str) -> Split:
    with open(path, "rb") as f:
        d = pickle.load(f, encoding="bytes")
    data = np.asarray(d[b"data"], np.uint8)  # [n, 3072], CHW row-major
    labels = np.asarray(d[b"labels"], np.uint8)
    imgs = data.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)  # -> NHWC
    return np.ascontiguousarray(imgs), labels


def has_cifar_files(data_dir: Optional[str]) -> bool:
    if not data_dir:
        return False
    return all(
        os.path.exists(os.path.join(data_dir, f))
        for f in TRAIN_BATCHES + (TEST_BATCH,)
    )


def load_cifar10(data_dir: str) -> Dict[str, Split]:
    xs, ys = zip(*(_read_batch(os.path.join(data_dir, f)) for f in TRAIN_BATCHES))
    val = _read_batch(os.path.join(data_dir, TEST_BATCH))
    return {"train": (np.concatenate(xs), np.concatenate(ys)), "val": val}


def synthetic_cifar10(
    n_train: int = 4096, n_val: int = 512, seed: int = 0
) -> Dict[str, Split]:
    """Deterministic CIFAR stand-in: per-class 4x4x3 color pattern upsampled
    to 32x32 plus noise."""
    rng = np.random.RandomState(seed)
    patterns = rng.rand(10, 4, 4, 3)

    def make(n: int) -> Split:
        labels = rng.randint(0, 10, n).astype(np.uint8)
        base = patterns[labels]  # [n, 4, 4, 3]
        imgs = np.repeat(np.repeat(base, 8, axis=1), 8, axis=2)
        imgs = imgs * 200 + rng.rand(n, 32, 32, 3) * 55
        return imgs.astype(np.uint8), labels

    return {"train": make(n_train), "val": make(n_val)}


def to_xy(split: Split, classes: int = 10) -> Tuple[np.ndarray, np.ndarray]:
    imgs, labels = split
    x = imgs.astype(np.float32) / 255.0
    y = np.eye(classes, dtype=np.float32)[labels]
    return x, y


def to_xy_raw(split: Split) -> Tuple[np.ndarray, np.ndarray]:
    """Wire-efficient form: see ``distriflow_tpu.data.prefetch.to_uint8_wire``."""
    from distriflow_tpu.data.prefetch import to_uint8_wire

    return to_uint8_wire(*split)


def load_splits(data_dir: Optional[str] = None, seed: int = 0) -> Dict[str, Split]:
    if has_cifar_files(data_dir):
        return load_cifar10(data_dir)
    return synthetic_cifar10(seed=seed)
