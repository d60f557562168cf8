"""MNIST server entrypoint.

Parity with the reference ``experiment/mnist/mnist_server.ts:24-35``: build
the 2-dense MLP (``createDenseModel``, ``:16-22``), wrap it in an in-memory
server model, serve an :class:`AsynchronousSGDServer` over the dataset with
an ``on_upload`` metrics logger, and listen. ``--mode federated`` swaps in
the :class:`FederatedServer` (the reference imports both; only async is
wired in its ``main``).

Run:  python -m experiments.mnist.mnist_server --port 8080 [--data-dir DIR]
"""

from __future__ import annotations

import argparse
import signal
import threading

from distriflow_tpu.models import mnist_mlp
from distriflow_tpu.models.base import SpecModel
from distriflow_tpu.server import (
    AbstractServer,
    AsynchronousSGDServer,
    DistributedServerConfig,
    DistributedServerInMemoryModel,
    FederatedServer,
)
from distriflow_tpu.utils.compile_cache import enable_compile_cache

from experiments.mnist.mnist_data import load_dataset


def create_dense_model(learning_rate: float = 0.001) -> SpecModel:
    """The reference's ``createDenseModel`` (``mnist_server.ts:16-22``):
    flatten -> dense(10, relu) -> dense(10); softmax lives in the loss."""
    return SpecModel(mnist_mlp(hidden=10), learning_rate=learning_rate)


def build_server(args: argparse.Namespace) -> AbstractServer:
    model = DistributedServerInMemoryModel(create_dense_model(args.learning_rate))
    config = DistributedServerConfig(
        host=args.host, port=args.port, verbose=args.verbose
    )
    server_hp = {}
    if getattr(args, "weight_compression", None):
        # halve every weight broadcast; clients restore their own dtype
        server_hp["weight_compression"] = args.weight_compression
    client_hp = {}
    if getattr(args, "gradient_compression", None):
        # pushed to every client on download (hyperparam precedence:
        # a client's local setting still wins)
        client_hp["gradient_compression"] = args.gradient_compression
        if getattr(args, "topk_fraction", None):
            client_hp["topk_fraction"] = args.topk_fraction
    if client_hp:
        config.client_hyperparams = client_hp
    if args.mode == "async":
        if server_hp:
            config.server_hyperparams = server_hp
        dataset = load_dataset(args.data_dir, {"batch_size": args.batch_size,
                                               "epochs": args.epochs})
        server: AbstractServer = AsynchronousSGDServer(model, dataset, config)
    else:
        config.server_hyperparams = {
            "min_updates_per_version": args.min_updates, **server_hp}
        server = FederatedServer(model, config)

    def log_metrics(msg, _result=None):
        if msg.metrics:  # loss is metrics[0] (the reference logged it twice
            # as both loss and accuracy — a logging bug, mnist_server.ts:31)
            server.log(f"client {msg.client_id[:8]} loss: {msg.metrics[0]:.4f}"
                       + (f" accuracy: {msg.metrics[1]:.4f}" if len(msg.metrics) > 1 else ""))

    server.on_upload(log_metrics)
    return server


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--data-dir", default=None,
                   help="directory holding idx-ubyte files; synthetic data if absent")
    p.add_argument("--mode", choices=("async", "federated"), default="async")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--learning-rate", type=float, default=0.001)
    p.add_argument("--min-updates", type=int, default=20,
                   help="federated mode: gradients buffered per version")
    p.add_argument("--weight-compression", choices=("float16", "bfloat16"),
                   default=None, help="16-bit weight broadcasts")
    p.add_argument("--gradient-compression",
                   choices=("float16", "bfloat16", "int8", "topk",
                            "topk_int8"), default=None,
                   help="push this upload compression to every client "
                        "(topk*: sparse top-k with error feedback, see "
                        "docs/PERFORMANCE.md §8)")
    p.add_argument("--topk-fraction", type=float, default=None,
                   help="fraction of gradient entries the topk modes keep "
                        "per leaf (default 0.01)")
    p.add_argument("--quiet", action="store_true", help="suppress progress logs")
    p.add_argument("--verbose", action="store_true",
                   help="accepted for compatibility (progress logs are on by default)")
    args = p.parse_args(argv)
    args.verbose = not args.quiet
    enable_compile_cache()

    server = build_server(args)
    server.setup()
    server.log(f"mnist {args.mode} server on {server.address}; ctrl-c to stop")
    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    stop.wait()
    server.stop()


if __name__ == "__main__":
    main()
