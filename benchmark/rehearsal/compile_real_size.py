#!/usr/bin/env python3
"""Compile the cells' programs at their real size for a described TPU v5e
(no chip attached) and print what the compiler says they need.

    JAX_PLATFORMS=cpu python benchmark/rehearsal/compile_real_size.py train [layers ...]
    JAX_PLATFORMS=cpu python benchmark/rehearsal/compile_real_size.py train-dp4
    JAX_PLATFORMS=cpu python benchmark/rehearsal/compile_real_size.py serve [group sizes ...]

Run by hand; the figures go into the configuration files' ``memory`` group.
They are the compiler's arithmetic, never a chip number: nothing runs. The
script reaches into the trainer's and the server's jitted functions
(``SyncTrainer._step_fn``, ``models.generate._build_*``) because both build
their state on ``jax.devices()``, which here is the CPU.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import distriflow_tpu.ops as ops  # noqa: E402
from benchmark.lib import harness  # noqa: E402

GB = 1e9


def report(label, compiled):
    ma = compiled.memory_analysis()
    args, out, temp = (ma.argument_size_in_bytes, ma.output_size_in_bytes,
                       ma.temp_size_in_bytes)
    alias = ma.alias_size_in_bytes
    print(f"{label}: arguments {args / GB:.2f} GB, outputs {out / GB:.2f} GB "
          f"(aliased {alias / GB:.2f}), temporaries {temp / GB:.2f} GB, "
          f"live at once {(args + out - alias + temp) / GB:.2f} GB", flush=True)


def structs(tree, sharding):
    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding), tree)


def model_config(registry, name, **over):
    config = registry.config(name)
    return harness.transformer_config(
        harness.model_view(config), name_kernels=True, **over), config


def train(registry, topo, n_dev, layers):
    from distriflow_tpu import TRANSFORMER_TP_RULES, SyncTrainer, transformer_lm
    from distriflow_tpu.parallel.mesh import create_mesh
    from distriflow_tpu.train.sync import TrainState
    from distriflow_tpu.utils.config import MeshConfig

    traffic = registry.traffic("markov-b4-s2048")
    seq, batch = traffic["seq"], traffic["batch_per_chip"] * n_dev
    for n_layers in layers:
        cfg, config = model_config(registry, "pythia-1.4b-widths-train",
                                   n_layers=n_layers, max_seq=seq)
        mesh = create_mesh(MeshConfig(data=n_dev), list(topo.devices[:n_dev]))
        spec = transformer_lm(cfg, mesh=mesh, example_seq=seq)
        trainer = SyncTrainer(spec, mesh=mesh, learning_rate=1e-4,
                              optimizer=config["trainer"]["optimizer"],
                              param_rules=TRANSFORMER_TP_RULES)
        rep = NamedSharding(mesh, P())
        params = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
        opt = jax.eval_shape(trainer.optimizer.init, params)
        state = TrainState(structs(params, rep), structs(opt, rep),
                           jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), None)
        data = NamedSharding(mesh, P("data"))
        xy = tuple(jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=data)
                   for _ in range(2))
        with jax.set_mesh(mesh):
            compiled = trainer._step_fn.lower(state, xy).compile()
        n_params = sum(v.size for v in jax.tree.leaves(params))
        report(f"train step L={n_layers} ({n_params / 1e6:.0f} M params) "
               f"{batch}x{seq} on {n_dev} chip(s)", compiled)
        text = compiled.as_text()
        print(f"  all-reduce ops in the program: {text.count(' all-reduce(') + text.count(' all-reduce-start(')}")


def serve(registry, topo, groups):
    from distriflow_tpu import transformer_lm
    from distriflow_tpu.models.generate import (
        _build_paged_fns, _build_prefill, _build_slot_fns, paged_cache)

    cfg, config = model_config(registry, "pythia-1.4b-widths-serve")
    srv = config["serving"]
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    params = structs(jax.eval_shape(
        transformer_lm(cfg, example_seq=128).init, jax.random.PRNGKey(0)), one)
    prefill, _ = _build_prefill(cfg)
    traffic = registry.traffic("chat-mixed-open")
    longest = max(int(k) for k in traffic["prompt_lengths"])
    for n in groups:
        tokens = jax.ShapeDtypeStruct((n, longest), jnp.int32, sharding=one)
        compiled = prefill.lower(params, tokens).compile()
        report(f"prefill {n}x{longest}", compiled)
    cache = structs(jax.eval_shape(
        lambda p: paged_cache(cfg, p, srv["max_slots"], srv["page_size"],
                              srv["page_pool_pages"]), params), one)
    pool = sum(v.size * v.dtype.itemsize for v in jax.tree.leaves(cache))
    print(f"page pool of {srv['page_pool_pages']} pages: {pool / GB:.2f} GB")
    s = srv["max_slots"]
    vec = lambda dt: jax.ShapeDtypeStruct((s,), dt, sharding=one)  # noqa: E731
    _, _, decode = _build_slot_fns(cfg, srv["decode_chunk"], False)
    compiled = decode.lower(
        params, cache, vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.int32), vec(jnp.int32)).compile()
    report(f"decode chunk of {srv['decode_chunk']} over {s} slots", compiled)
    insert, _ = _build_paged_fns(cfg, srv["page_size"])
    n = max(groups)
    row_cache = jax.eval_shape(
        prefill, params, jax.ShapeDtypeStruct((n, longest), jnp.int32))[1]
    table = jax.tree.leaves(
        {k: v for k, v in cache["layers_0"]["attn"].items() if k == "page_table"})[0]
    compiled = insert.lower(
        cache, structs(row_cache, one),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct(table.shape, jnp.int32, sharding=one)).compile()
    report(f"page scatter of a {n}x{longest} group", compiled)


def main(argv):
    ops.default_interpret = lambda: False  # lower the kernels through Mosaic
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    registry = harness.Registry()
    what, rest = argv[0], [int(a) for a in argv[1:]]
    if what == "train":
        train(registry, topo, 1, rest or [registry.config(
            "pythia-1.4b-widths-train")["num_hidden_layers"]])
    elif what == "train-dp4":
        train(registry, topo, 4, rest or [registry.config(
            "pythia-1.4b-widths-train")["num_hidden_layers"]])
    elif what == "serve":
        serve(registry, topo, rest or [1, 4])
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main(sys.argv[1:])
