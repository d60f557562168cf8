"""CIFAR-10 training entrypoint (sync, async and federated modes).

No reference counterpart exists (the reference ships only the MNIST
experiment); this is the repo's own one-host workload:

- ``--mode sync``      sync-SGD: batch sharded over the mesh's data axis,
  gradient mean as an in-graph psum;
- ``--mode async``     host-coordinated async SGD with bounded staleness
  (``--max-staleness``);
- ``--mode federated`` federated averaging: K local steps per worker +
  periodic weight pmean.

Run:  python -m experiments.cifar10.train --mode sync --steps 100
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

import jax
import numpy as np

from distriflow_tpu.data.dataset import DistributedDataset
from distriflow_tpu.data.prefetch import prefetch_to_device, sampling_iterator
from distriflow_tpu.models import cifar_convnet
from distriflow_tpu.models.base import with_uint8_inputs
from distriflow_tpu.parallel import data_parallel_mesh
from distriflow_tpu.train.async_sgd import AsyncSGDTrainer
from distriflow_tpu.train.federated import FederatedAveragingTrainer
from distriflow_tpu.train.loop import evaluate_dataset, run_chunked
from distriflow_tpu.train.sync import SyncTrainer
from distriflow_tpu.utils.compile_cache import enable_compile_cache

from experiments.cifar10.cifar_data import load_splits, to_xy, to_xy_raw


def run_sync(args, spec, train, val) -> float:
    mesh = data_parallel_mesh()
    raw_wire = args.wire_format == "u8"
    if raw_wire:
        # uint8 pixels + int32 labels over the wire, normalize on device:
        # for this small model the input stream, not compute, can bind
        # throughput
        spec = dataclasses.replace(
            with_uint8_inputs(spec), loss="sparse_softmax_cross_entropy"
        )
    trainer = SyncTrainer(spec, mesh=mesh, learning_rate=args.learning_rate,
                          optimizer=args.optimizer, verbose=True,
                          zero_level=args.zero_level)
    trainer.init(jax.random.PRNGKey(args.seed))
    x, y = (to_xy_raw if raw_wire else to_xy)(train)
    k = args.steps_per_dispatch
    stream = sampling_iterator(x, y, args.batch_size, steps=args.steps,
                               seed=args.seed)
    if k <= 1:
        # per-step dispatch: overlap host->device transfer with compute
        stream = prefetch_to_device(stream, mesh)
    res = run_chunked(
        trainer, stream, steps=args.steps, steps_per_dispatch=k,
        log=lambda s, l: print(f"step {s} loss {l:.4f}", file=sys.stderr),
    )
    note = res.tail_note(args.steps)
    if note:
        print(note, file=sys.stderr)
    # steady-state throughput (first, compiling dispatch excluded); a run
    # that fits in one dispatch has no steady-state window to time
    sps = res.steps_per_sec * args.batch_size
    sps_txt = f"{sps:.0f}" if np.isfinite(sps) else "n/a (single dispatch)"
    vx, vy = (to_xy_raw if raw_wire else to_xy)(val)
    val_loss, val_acc = evaluate_dataset(trainer.evaluate, vx, vy)
    print(f"sync: {sps_txt} samples/sec, val loss {val_loss:.4f} acc {val_acc:.4f}",
          file=sys.stderr)
    return val_acc


def run_async(args, spec, train, val) -> float:
    x, y = to_xy(train)
    n_batches = min(args.steps, len(x) // args.batch_size)  # 1 gradient per batch
    if n_batches < args.steps:
        print(f"warning: only {len(x)} examples available — running {n_batches} "
              f"steps instead of the requested {args.steps}", file=sys.stderr)
    dataset = DistributedDataset(
        x[: n_batches * args.batch_size], y[: n_batches * args.batch_size],
        {"batch_size": args.batch_size, "epochs": 1},
    )
    trainer = AsyncSGDTrainer(
        spec, dataset, learning_rate=args.learning_rate, optimizer=args.optimizer,
        steps_per_upload=args.steps_per_upload,
        hyperparams={"maximum_staleness": args.max_staleness}, verbose=True,
    )
    trainer.init(jax.random.PRNGKey(args.seed))
    stats = trainer.train(num_workers=args.workers)
    vx, vy = to_xy(val)
    val_loss, val_acc = evaluate_dataset(trainer.evaluate, vx, vy)
    print(f"async: {stats}, val loss {val_loss:.4f} acc {val_acc:.4f}",
          file=sys.stderr)
    return val_acc


def run_federated(args, spec, train, val) -> float:
    trainer = FederatedAveragingTrainer(
        spec, local_steps=args.local_steps,
        local_batch_size=args.batch_size, learning_rate=args.learning_rate,
        optimizer=args.optimizer, verbose=True,
    )
    trainer.init(jax.random.PRNGKey(args.seed))
    x, y = to_xy(train)
    rng = np.random.RandomState(args.seed)
    for r in range(args.rounds):
        xs, ys = trainer.pack_round_data(x, y, rng)
        loss = trainer.round(xs, ys)
        if r % 5 == 0:
            print(f"round {r} loss {loss:.4f}", file=sys.stderr)
    vx, vy = to_xy(val)
    val_loss, val_acc = evaluate_dataset(trainer.evaluate, vx, vy)
    print(f"federated: val loss {val_loss:.4f} acc {val_acc:.4f}", file=sys.stderr)
    return val_acc


def main(argv=None) -> float:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("sync", "async", "federated"), default="sync")
    p.add_argument("--data-dir", default=None,
                   help="CIFAR-10 python-version pickle dir; synthetic if absent")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--rounds", type=int, default=20, help="federated rounds")
    p.add_argument("--local-steps", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--learning-rate", type=float, default=0.05)
    p.add_argument("--optimizer", default="momentum")
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--wire-format", choices=("u8", "f32"), default="u8",
                   help="sync mode input stream: u8 ships raw uint8 pixels + "
                        "int32 labels and normalizes on device (4x fewer "
                        "host->device bytes); f32 ships normalized float32 + "
                        "one-hot (the reference-style wire format)")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="sync mode: K optimizer steps per device "
                        "dispatch (lax.scan) — amortizes host/"
                        "transport latency")
    p.add_argument("--max-staleness", type=int, default=4)
    p.add_argument("--steps-per-upload", type=int, default=1,
                   help="async mode: K batches' gradients per snapshot in "
                        "one device dispatch (mean upload) — amortizes the "
                        "host ping-pong")
    p.add_argument("--zero-level", type=int, default=0, choices=(0, 1, 2),
                   help="sync mode: ZeRO memory sharding over the data axis")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    splits = load_splits(args.data_dir, seed=args.seed)
    spec = cifar_convnet()
    runner = {"sync": run_sync, "async": run_async, "federated": run_federated}
    return runner[args.mode](args, spec, splits["train"], splits["val"])


if __name__ == "__main__":
    main()
