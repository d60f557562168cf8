"""XLA collectives over the mesh.

The in-graph replacement for the reference's aggregation wire path: where the
reference serializes client gradients, byte-stacks them, and takes ``mean(0)``
on a central server (``src/common/utils.ts:53-75`` +
``src/server/federated_server.ts:96-109``), these run as a single XLA
AllReduce over ICI — weights and gradients never leave the devices.

Most user code never calls these directly: jit + shardings let XLA insert the
collectives. They exist for shard_map code (federated local-epoch training,
ring attention) and for the doctor's collective-latency check.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Sequence[str]]


def pvary(tree: Any, axis: AxisName) -> Any:
    """Mark a replicated-typed pytree as axis-varying inside shard_map.

    Critical for per-worker autodiff: differentiating a varying loss w.r.t.
    unvarying params makes JAX insert an implicit psum over the axis — the
    "local" gradient silently becomes the global sum. Cast params varying
    first and each worker gets its own gradient.
    """
    cast = getattr(lax, "pcast", None)
    if cast is not None:
        return jax.tree.map(lambda x: cast(x, axis, to="varying"), tree)
    if hasattr(lax, "pvary"):
        return jax.tree.map(lambda x: lax.pvary(x, axis), tree)
    # legacy jax (< 0.5): no varying-manual-axes type system, every value
    # inside shard_map is already per-device — the cast is an identity
    return tree


def psum(tree: Any, axis: AxisName) -> Any:
    """Sum-allreduce a pytree over a mesh axis (inside shard_map/pmap)."""
    return jax.tree.map(lambda x: lax.psum(x, axis), tree)


def pmean(tree: Any, axis: AxisName) -> Any:
    """Mean-allreduce — the reference's gradient-mean aggregation, in-graph."""
    return jax.tree.map(lambda x: lax.pmean(x, axis), tree)


def all_gather(x: jnp.ndarray, axis: AxisName, *, gather_axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x: jnp.ndarray, axis: AxisName, *, scatter_axis: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def ppermute_ring(x: jnp.ndarray, axis: str, mesh: Mesh, shift: int = 1) -> jnp.ndarray:
    """Rotate shards around the ``axis`` ring by ``shift`` (ring attention's move)."""
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def allreduce_mean(mesh: Mesh, tree: Any, axis: str = "data") -> Any:
    """Standalone jitted mean-allreduce of a sharded pytree over ``axis``.

    Used by host-coordination paths (async/federated) that aggregate outside
    a single train step; the sync trainer's allreduce is fused into its step.
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(),
    )
    def _mean(stacked):
        # mean over the locally-held slice of the leading dim, then over devices
        return jax.tree.map(lambda v: lax.pmean(jnp.mean(v, axis=0), axis), stacked)

    return jax.jit(_mean)(tree)


def collective_latency_us(mesh: Mesh, nbytes: int = 4 * 1024 * 1024, axis: str = "data",
                          iters: int = 10) -> float:
    """Measured allreduce latency for an ``nbytes`` float32 buffer (doctor)."""
    import time

    n = nbytes // 4
    sharding = NamedSharding(mesh, P(axis))
    x = jax.device_put(
        jnp.arange(n * mesh.shape[axis], dtype=jnp.float32).reshape(mesh.shape[axis], n),
        sharding,
    )

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P())
    def _ar(v):
        return lax.pmean(v, axis)

    jax.block_until_ready(_ar(x))  # compile
    start = time.perf_counter()
    for _ in range(iters):
        out = _ar(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e6
