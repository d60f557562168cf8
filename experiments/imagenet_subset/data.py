"""ImageNet-subset data pipeline (the stretch workload).

The reference has no ImageNet experiment — it is this repo's
MobileNetV2 stretch. Loader reads a directory-per-class tree of
pre-decoded ``.npy`` images (the zero-dependency on-disk format this image
supports; no PIL/TFDS here):

    root/<class_name>/<anything>.npy   # uint8 [H, W, 3]

:func:`synthetic_imagenet` is the zero-egress stand-in: per-class color/
frequency patterns at the requested resolution, learnable by MobileNetV2.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import numpy as np

Split = Tuple[np.ndarray, np.ndarray]  # (imgs uint8 [n,s,s,3], labels int32 [n])


def has_imagenet_tree(data_dir: Optional[str]) -> bool:
    if not data_dir or not os.path.isdir(data_dir):
        return False
    classes = sorted(
        d for d in os.listdir(data_dir) if os.path.isdir(os.path.join(data_dir, d))
    )
    return len(classes) >= 2


def _center_resize(img: np.ndarray, size: int) -> np.ndarray:
    """Nearest-neighbor center-crop-to-square then resize — host-side uint8
    preprocessing; the device path stays pure matmul/conv work."""
    h, w = img.shape[:2]
    s = min(h, w)
    img = img[(h - s) // 2 : (h - s) // 2 + s, (w - s) // 2 : (w - s) // 2 + s]
    idx = (np.arange(size) * s // size).clip(0, s - 1)
    return img[idx][:, idx]


def load_imagenet_tree(
    data_dir: str, image_size: int = 224, max_per_class: Optional[int] = None
) -> Dict[str, Split]:
    classes = sorted(
        d for d in os.listdir(data_dir) if os.path.isdir(os.path.join(data_dir, d))
    )
    xs, ys = [], []
    for label, cls in enumerate(classes):
        files = sorted(
            f for f in os.listdir(os.path.join(data_dir, cls)) if f.endswith(".npy")
        )
        if max_per_class:
            files = files[:max_per_class]
        for f in files:
            img = np.load(os.path.join(data_dir, cls, f))
            xs.append(_center_resize(np.asarray(img, np.uint8), image_size))
            ys.append(label)
    x = np.stack(xs)
    y = np.asarray(ys, np.int32)
    # deterministic 90/10 split
    rng = np.random.RandomState(0)
    order = rng.permutation(len(x))
    n_val = max(1, len(x) // 10)
    return {
        "train": (x[order[n_val:]], y[order[n_val:]]),
        "val": (x[order[:n_val]], y[order[:n_val]]),
        "num_classes": len(classes),
    }


def synthetic_imagenet(
    n_train: int = 1024,
    n_val: int = 128,
    num_classes: int = 16,
    image_size: int = 96,
    seed: int = 0,
) -> Dict[str, Split]:
    """Deterministic stand-in: per-class 6x6x3 pattern upsampled + noise."""
    rng = np.random.RandomState(seed)
    patterns = rng.rand(num_classes, 6, 6, 3)
    rep = image_size // 6 + 1

    def make(n: int) -> Split:
        labels = rng.randint(0, num_classes, n).astype(np.int32)
        base = np.repeat(np.repeat(patterns[labels], rep, axis=1), rep, axis=2)
        base = base[:, :image_size, :image_size]
        noise = rng.rand(n, image_size, image_size, 3) * 0.25
        imgs = ((base * 0.75 + noise) * 255).astype(np.uint8)
        return imgs, labels

    return {"train": make(n_train), "val": make(n_val), "num_classes": num_classes}


def load_splits(
    data_dir: Optional[str], image_size: int = 96, seed: int = 0
) -> Dict[str, Split]:
    if data_dir is not None:
        if not has_imagenet_tree(data_dir):
            raise FileNotFoundError(
                f"--data-dir {data_dir!r} is not a class-per-directory tree "
                "with >=2 class subdirs; omit --data-dir for synthetic data"
            )
        return load_imagenet_tree(data_dir, image_size=image_size)
    return synthetic_imagenet(image_size=image_size, seed=seed)


def to_xy(split: Split, num_classes: int) -> Tuple[np.ndarray, np.ndarray]:
    """uint8 images + int labels -> normalized float32 x, one-hot float32 y."""
    imgs, labels = split
    x = imgs.astype(np.float32) / 255.0
    y = np.eye(num_classes, dtype=np.float32)[labels]
    return x, y


def to_xy_raw(split: Split) -> Tuple[np.ndarray, np.ndarray]:
    """Wire-efficient form: see ``distriflow_tpu.data.prefetch.to_uint8_wire``."""
    from distriflow_tpu.data.prefetch import to_uint8_wire

    return to_uint8_wire(*split)
