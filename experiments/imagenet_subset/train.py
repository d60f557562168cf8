"""ImageNet-subset MobileNetV2 training entrypoint.

The stretch workload: MobileNetV2, sync-SGD, batch sharded over the
mesh's data axis with the gradient mean as an in-graph psum. No reference
counterpart (the reference ships only MNIST).

Run:  python -m experiments.imagenet_subset.train --steps 50 --image-size 96
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from distriflow_tpu.data.prefetch import prefetch_to_device, sampling_iterator
from distriflow_tpu.models.base import with_uint8_inputs
from distriflow_tpu.models.mobilenet import mobilenet_v2
from distriflow_tpu.parallel import data_parallel_mesh
from distriflow_tpu.train.loop import evaluate_dataset, run_chunked
from distriflow_tpu.train.sync import SyncTrainer
from distriflow_tpu.utils.compile_cache import enable_compile_cache

from experiments.imagenet_subset.data import load_splits, to_xy, to_xy_raw


def main(argv=None) -> float:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--data-dir", default=None,
                   help="class-per-directory .npy tree; synthetic if absent")
    p.add_argument("--image-size", type=int, default=96)
    p.add_argument("--width", type=float, default=1.0)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--learning-rate", type=float, default=0.05)
    p.add_argument("--optimizer", default="momentum")
    p.add_argument("--bf16", action="store_true",
                   help="compute in bfloat16 (MXU-native)")
    p.add_argument("--wire-format", choices=("u8", "f32"), default="u8",
                   help="u8 ships raw uint8 pixels + int32 labels and "
                        "normalizes on device (4x fewer host->device bytes)")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="K optimizer steps per device dispatch (lax.scan)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    splits = load_splits(args.data_dir, image_size=args.image_size, seed=args.seed)
    num_classes = splits["num_classes"]
    spec = mobilenet_v2(
        image_size=args.image_size,
        classes=num_classes,
        width=args.width,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
    )
    raw_wire = args.wire_format == "u8"
    if raw_wire:
        spec = dataclasses.replace(
            with_uint8_inputs(spec), loss="sparse_softmax_cross_entropy"
        )

    mesh = data_parallel_mesh()
    trainer = SyncTrainer(spec, mesh=mesh, learning_rate=args.learning_rate,
                          optimizer=args.optimizer, verbose=True)
    trainer.init(jax.random.PRNGKey(args.seed))

    x, y = (to_xy_raw(splits["train"]) if raw_wire
            else to_xy(splits["train"], num_classes))
    stream = sampling_iterator(x, y, args.batch_size, steps=args.steps,
                               seed=args.seed)
    if args.steps_per_dispatch <= 1:
        # per-step dispatch: overlap host->device transfer with compute
        stream = prefetch_to_device(stream, mesh)
    res = run_chunked(
        trainer, stream, steps=args.steps,
        steps_per_dispatch=args.steps_per_dispatch,
        log=lambda s, l: print(f"step {s} loss {l:.4f}", file=sys.stderr),
        log_every=10,
    )
    note = res.tail_note(args.steps)
    if note:
        print(note, file=sys.stderr)
    sps = res.steps_per_sec * args.batch_size
    sps_txt = f"{sps:.0f}" if np.isfinite(sps) else "n/a (single dispatch)"

    vx, vy = (to_xy_raw(splits["val"]) if raw_wire
              else to_xy(splits["val"], num_classes))
    val_loss, val_acc = evaluate_dataset(trainer.evaluate, vx, vy, batch_size=256)
    print(
        f"mobilenet_v2/{args.image_size}px: {sps_txt} samples/sec, "
        f"val loss {val_loss:.4f} acc {val_acc:.4f}",
        file=sys.stderr,
    )
    return val_acc


if __name__ == "__main__":
    main()
