"""The held experts' terms of a decode step as one Pallas TPU kernel: a
grouped expert matmul over the experts that some row chose.

A decode step of an expert layer has a few tokens (the engine's slots) and
many experts, of which the live rows chose some. Run as one conditional an
expert, each chosen expert is three small matmuls that fill and drain their
own pipeline, and each unchosen one a predicate, a zero fill and an add
(PERF.md §6 PR 36). Here the chosen experts' indices are compacted to the
front of a list in ascending order (a cumsum, no sort) and the kernel's grid
walks the list: the weights' index maps read the expert from the list
(scalar prefetch), so Pallas fetches expert ``i + 1``'s matrices while expert
``i`` is multiplied, and the experts are streamed back to back. The list's
tail repeats the last chosen index: a grid step whose block index did not
change issues no DMA, and ``pl.when`` keeps it from computing, so an expert
nobody chose is neither read nor run and what it holds (NaN included)
cannot reach the result.

One float32 ``[T, d]`` block is the output and the accumulator. The body is
one expert's gated MLP with its roundings where the XLA form
(``models/latent_sparse.py::_expert_term``) has them: operands in the
tokens' dtype, float32 accumulation in the MXU, the products rounded to the
tokens' dtype, the gate applied in float32, the sum over experts in float32
in ascending order. The weights go to the MXU as they are stored: no
transpose, cast or copy of a weight block.

An expert's three matrices are one block each, double-buffered: at ``d``
4096 and ``f`` 768 in bfloat16 that is 37.7 MB of VMEM, which is asked for
by name (``vmem_limit_bytes``; the 16 MiB of the other kernels is the
compiler's default scoped limit, not the chip's 128 MiB). The tokens are
padded to whole tiles; widths that do not keep the chip's tiling, or experts
too large for VMEM, raise.

Inference-only: no VJP.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_BYTES = 128 * 1024 * 1024  # a v5e core's VMEM
LANES = 128


def hit_first(hit: jnp.ndarray):
    """``(order, n_hit)`` of ``hit [count]``: the indices of the experts hit,
    ascending, at the front of ``order`` (a cumsum and a one-hot sum, no
    sort) and the last of them repeated behind (0 where none is hit); and
    how many are hit."""
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    place = jnp.arange(hit.shape[0], dtype=jnp.int32)
    # position i names the hit expert of rank i; the tail, the last one again
    rank = jnp.minimum(place, n_hit - 1)[:, None]
    mine = hit & (jnp.cumsum(hit) - 1 == rank)  # [position, expert]
    return jnp.sum(jnp.where(mine, place, 0), axis=1), n_hit


def _vmem_bytes(t: int, d: int, f: int, count: int, item: int) -> int:
    """What a grid step holds: an expert's three matrices, the tokens and
    the output double-buffered, the gates, and the step's float32 values."""
    weights = 2 * 3 * d * f * item
    tokens = 2 * t * d * (item + 4) + 2 * t * max(count, LANES) * 4
    values = t * (3 * f + 2 * d) * 4
    return weights + tokens + values


def _kernel(order_ref, n_ref, x_ref, gates_ref, wg_ref, wu_ref, wd_ref,
            out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        up = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        gate = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate.astype(x.dtype).astype(jnp.float32))
             * up.astype(x.dtype).astype(jnp.float32)).astype(x.dtype)
        out = jnp.dot(h, wd_ref[0], preferred_element_type=jnp.float32)
        # this expert's column of the gates, [T, 1]: a sum of one value
        # and zeros
        gates = gates_ref[...]
        mine = lax.broadcasted_iota(jnp.int32, gates.shape, 1) == order_ref[i]
        weight = jnp.sum(jnp.where(mine, gates, 0.0), axis=1, keepdims=True)
        out_ref[...] += out.astype(x.dtype).astype(jnp.float32) * weight


def grouped_expert_terms(xc: jnp.ndarray, gates: jnp.ndarray,
                         w_gate: jnp.ndarray, w_up: jnp.ndarray,
                         w_down: jnp.ndarray,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """The sum over the held experts of each one's gated MLP over the tokens
    ``xc [T, d]``, weighed by its column of ``gates [T, count]`` float32 (0
    for a token that did not choose it), float32 ``[T, d]``. ``w_gate``,
    ``w_up``: ``[count, d, f]``, ``w_down``: ``[count, f, d]``, in ``xc``'s
    dtype. Only the experts with a gate above 0 are read."""
    if interpret is None:
        from distriflow_tpu.ops import default_interpret

        interpret = default_interpret()
    t, d = xc.shape
    count, _, f = w_gate.shape
    if (gates.shape != (t, count) or w_up.shape != (count, d, f)
            or w_down.shape != (count, f, d)):
        raise ValueError(
            f"tokens {xc.shape}, gates {gates.shape} and experts "
            f"{w_gate.shape}, {w_up.shape}, {w_down.shape} do not fit")
    if not w_gate.dtype == w_up.dtype == w_down.dtype == xc.dtype:
        raise ValueError("the experts' weights go to the MXU as stored: "
                         f"they must be in the tokens' dtype {xc.dtype}")
    item = jnp.dtype(xc.dtype).itemsize
    # whole tiles of tokens: the rows added choose nothing
    pad = -t % (32 // item)
    if pad:
        xc = jnp.pad(xc, ((0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
    need = _vmem_bytes(t + pad, d, f, count, item)
    if not interpret:
        if d % LANES or f % LANES:
            raise ValueError(f"grouped_expert_terms: widths d {d} and f {f} "
                             f"must be multiples of {LANES}")
        if need > VMEM_BYTES * 3 // 4:  # the compiler's own scratch beside
            raise ValueError(
                f"grouped_expert_terms: one expert's matrices ({d} x {f}) "
                f"double-buffered take {need / 1e6:.1f} MB of VMEM")
    order, n_hit = hit_first(jnp.any(gates > 0, axis=0))

    def whole(i, order, n):
        return (0, 0)

    def expert(i, order, n):
        return (order[i], 0, 0)

    return pl.pallas_call(
        _kernel,
        name="grouped_expert_terms",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count,),
            in_specs=[pl.BlockSpec((t + pad, d), whole),
                      pl.BlockSpec((t + pad, count), whole),
                      pl.BlockSpec((1, d, f), expert),
                      pl.BlockSpec((1, d, f), expert),
                      pl.BlockSpec((1, f, d), expert)],
            out_specs=pl.BlockSpec((t + pad, d), whole),
        ),
        out_shape=jax.ShapeDtypeStruct((t + pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=need + need // 4,
        ),
        interpret=interpret,
    )(order, n_hit.reshape(1), xc, gates, w_gate, w_up, w_down)[:t]
