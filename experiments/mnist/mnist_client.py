"""MNIST worker entrypoint.

Parity with the reference ``experiment/mnist/mnist_client.ts:24-30``: build
the same dense model, connect an :class:`AsynchronousSGDClient` with
``send_metrics=True``, and train until the server signals completion.
``--mode federated`` runs a :class:`FederatedClient` over a local synthetic
shard instead (client-held data; the reference imports both clients).

Run:  python -m experiments.mnist.mnist_client --server 127.0.0.1:8080
"""

from __future__ import annotations

import argparse

from distriflow_tpu.client import (
    AsynchronousSGDClient,
    DistributedClientConfig,
    FederatedClient,
)
from distriflow_tpu.utils.compile_cache import enable_compile_cache

from experiments.mnist.mnist_data import synthetic_mnist, to_xy
from experiments.mnist.mnist_server import create_dense_model


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--server", default="127.0.0.1:8080")
    p.add_argument("--mode", choices=("async", "federated"), default="async")
    p.add_argument("--client-id", default=None)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--seed", type=int, default=1, help="federated-mode local shard seed")
    p.add_argument("--gradient-compression",
                   choices=("none", "float16", "bfloat16", "int8", "topk",
                            "topk_int8"),
                   default=None,
                   help="upload compression (int8 = 4x fewer bytes with "
                        "error feedback; topk/topk_int8 = sparse top-k, "
                        "~50-80x on conv nets); default: whatever the "
                        "server pushes, else none")
    args = p.parse_args(argv)
    enable_compile_cache()

    hp = ({"gradient_compression": args.gradient_compression}
          if args.gradient_compression else None)
    config = DistributedClientConfig(client_id=args.client_id, send_metrics=True,
                                     verbose=True, hyperparams=hp)
    model = create_dense_model()
    if args.mode == "async":
        client = AsynchronousSGDClient(args.server, model, config)
        client.setup()
        done = client.train_until_complete(timeout=args.timeout)
        client.log(f"processed {done} batches")
    else:
        client = FederatedClient(args.server, model, config)
        client.setup()
        x, y = to_xy(synthetic_mnist(n_train=1024, seed=args.seed)["train"])
        uploads = client.distributed_update(x, y)
        client.log(f"sent {uploads} gradient uploads")
    client.dispose()


if __name__ == "__main__":
    main()
