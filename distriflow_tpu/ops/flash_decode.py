"""Single-token decode attention as a Pallas TPU kernel (flash-decode).

The XLA decode path (``models/transformer.py::_decode_attend``) computes
``softmax(q·K^T)·V`` against the full cache with three separate HLO ops
(QK^T matvec, softmax, PV matvec) — measured at only ~25% of HBM peak on
v5e, because the [B, H, 1, S] f32 score tensor round-trips HBM between
them and the matvecs under-fill the MXU. Decode at long context is
KV-read bandwidth-bound, so the kernel's job is simple: stream K and V
through VMEM exactly once, with the online-softmax recurrence in
scratch, touching HBM only for the inputs and the [B, H*D] output.

**Token-major packed cache layout** (round 5 — the bandwidth unlock):
K/V are stored ``[B, S, H*D]`` — each position's all-head features
contiguous — instead of the head-major ``[B, H, S, D]`` torch-style
layout. With head_dim 64, head-major tiles fill only half of each
128-lane vector register and the DMA engine streams at ~300 GB/s; the
packed layout's ``[BLOCK_K, H*D]`` tiles are full-lane and measure
~690 GB/s (84% of v5e's 819 GB/s peak), 2.3x faster end-to-end
(measured on-chip, this file's kernels, 4k context).

Both contractions ride the MXU via a block-diagonal trick (no batched
matvec needed, which Mosaic cannot lower anyway):

- scores: ``s[j, h] = K_packed[j] · Q_bd[:, h]`` where ``Q_bd [H*D, H]``
  has head h's query in rows ``h*D:(h+1)*D`` of column h, zeros
  elsewhere — ONE [BK, HD] x [HD, H] matmul yields all heads' scores;
- context: ``C = P^T V_packed [H, H*D]`` followed by a block-diagonal
  extraction ``pv[h*D+d] = C[h, h*D+d]`` (multiply by the diagonal-block
  mask, sum over the 8-sublane head axis — cheap).

The online-softmax recurrence (running max ``m``, exp-sum ``l``,
accumulator ``acc [1, H*D]``) lives in VMEM scratch; per-head scalars
broadcast to the packed axis through the same mask matmul. ``valid_len``
rides in as a scalar-prefetch operand: positions past the cache write
index are masked, and a tile that lies wholly past it is neither
computed (``pl.when``) nor fetched (past the row's last live tile the
K/V index maps repeat that tile's block index, and a grid step whose
block index did not change issues no DMA). The slab kernel's grid keeps
its static extent, rows x tiles, where a dead step costs its bookkeeping
alone (0.12 us on a v5e). The paged kernel's steps are the live pages
alone, a work list of (row, page) items whose length is the grid's
extent, and it takes the query full-lane, building each row's
block-diagonal operand in VMEM (see the paged section below).

**int8 cache support**: with ``k_scale``/``v_scale`` operands
(``[B, S, H]`` f32, symmetric absmax per position x head), the scales
fold into the [BK, H] score/prob tensors (``s = (K8 . Q_bd) * ks``,
``pv = (P * vs)^T . V8``) — no dequantized [BK, H*D] tile is ever
materialized, and the int8 tiles feed the MXU as exact bf16 casts. The
XLA path materializes the whole dequantized cache to HBM every token,
which made int8 *slower* than bf16 (measured); in-kernel folded dequant
is what converts the 2x byte saving into a time saving.

**bf16-compute contract for f32 caches**: the MXU contracts in bf16, so
f32 K/V tiles are cast to bf16 at tile load (``.astype(jnp.bfloat16)``
in the kernels) — scores, probabilities, and the accumulator stay f32,
but the K/V *mantissas* see only bf16's 8 bits. An f32 cache therefore
buys VMEM/HBM cost (2x bytes plus the cast copies in the VMEM model)
without buying f32 contraction accuracy; the XLA fallback path is the
only true f32-compute decode. Callers who store f32 caches for
numerical reasons should either accept bf16-equivalent attention
(matches the tolerance tests here, ~1e-2 relative) or disable the
kernel (``use_flash_decode=False``). See docs/PERFORMANCE.md.

**Tile floor**: :func:`pick_block_k` refuses tiles below
``MIN_BLOCK_K`` when the cache is larger than one tile — an awkward
length like 2056 (= 2^3 x 257) only has 8 as a sublane-aligned divisor,
and a [8, HD] tile puts the kernel in its worst per-step-overhead
regime (257 grid steps of sliver DMAs, far below the measured-streaming
tiles the numbers above come from). :func:`supports_seq` returns False
for such shapes (counted in the ``ops_flash_decode_gated_total``
telemetry counter, warned once per shape) and the model layer takes the
XLA decode path instead.

Inference-only: no VJP (decode never backprops).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distriflow_tpu.ops.flop_count import (
    record_pallas_cost,
    record_pallas_count,
)

BLOCK_K = 2048  # KV positions per tile: [2048, 512] bf16 K+V tiles are
# 2 MB each, double-buffered 8 MB — inside the 16 MB scoped-VMEM limit
# with room for the [BK, H] f32 score/prob tensors
VMEM_LIMIT_BYTES = 16 * 1024 * 1024  # TPU scoped-vmem compile limit
MIN_BLOCK_K = 128  # smallest multi-tile we'll run: below this the grid
# degenerates into sliver DMAs (e.g. 2056 -> block_k 8, 257 steps) and
# the per-step overhead regime beats the XLA path anyway
NEG_INF = -1e30

_warned_gated: set = set()  # (s, hd, kv_item) shapes already warned about


def pick_block_k(s: int, hd: int = 512, kv_item: int = 2,
                 limit: int = BLOCK_K) -> Optional[int]:
    """KV tile length for a cache of ``s`` positions, packed feature
    width ``hd``, and cache itemsize ``kv_item`` (1=int8, 2=bf16,
    4=f32): the largest candidate that (a) divides ``s``, (b) is
    sublane-aligned (multiple of 8, or ``s`` itself — Mosaic accepts a
    block equal to the array dim), and (c) fits the scoped-VMEM model —
    wide-head or f32 configs shrink the tile instead of dying in the
    Mosaic compiler. Multi-tile candidates stop at ``MIN_BLOCK_K``:
    a sliver tile (2056 -> 8) lands in the kernel's worst per-step
    overhead regime, so those shapes are gated off rather than run
    slow. None when no candidate qualifies: callers fall back to the
    XLA decode path rather than crash at trace time."""
    def fits(bk):
        return _vmem_estimate_bytes(bk, hd, kv_item) <= VMEM_LIMIT_BYTES

    if s <= limit and fits(s):
        return s  # whole-sequence tile: no grid, the floor doesn't apply
    for bk in range(min((min(limit, s) // 8) * 8, s), MIN_BLOCK_K - 1, -8):
        if s % bk == 0 and fits(bk):
            return bk
    return None


def _note_gated(s: int, hd: int, kv_item: int) -> None:
    from distriflow_tpu.obs import get_telemetry

    get_telemetry().counter(
        "ops_flash_decode_gated_total",
        help="decode calls routed to the XLA fallback by shape gating",
    ).inc()
    key = (s, hd, kv_item)
    if key not in _warned_gated:
        _warned_gated.add(key)
        warnings.warn(
            f"flash_decode gated off for cache length {s} (packed width "
            f"{hd}, itemsize {kv_item}): no sublane-aligned divisor tile "
            f">= {MIN_BLOCK_K} fits scoped VMEM — decoding on the XLA "
            "fallback path. Pad max_seq to a multiple of a power of two "
            "(e.g. 2048 instead of 2056) to re-enable the kernel.",
            stacklevel=3)


def supports_seq(s: int, hd: int = 512, kv_item: int = 2) -> bool:
    """True when :func:`flash_decode` can tile a cache of length ``s``
    at packed width ``hd`` and itemsize ``kv_item`` — the gate
    ``models/transformer.py`` uses before auto-enabling the kernel (an
    unsupported shape falls back to XLA decode instead of raising
    mid-trace). A gated shape bumps ``ops_flash_decode_gated_total`` and
    warns once per (s, hd, kv_item)."""
    if pick_block_k(s, hd, kv_item) is not None:
        return True
    _note_gated(s, hd, kv_item)
    return False


def _vmem_estimate_bytes(block_k: int, hd: int, kv_item: int) -> int:
    """Scoped-VMEM cost for one grid step: double-buffered K/V input
    tiles at the cache's OWN itemsize, the bf16 MXU cast copies the
    non-bf16 tiles pay, and the [BK, H]-class f32 score/prob working set
    (small; folded into a 10% margin). int8 K contracts natively on the
    s8 MXU — only V casts; f32 caches cast both K and V."""
    tiles = 2 * 2 * block_k * hd * kv_item  # K+V, double-buffered
    cast_tiles = {2: 0, 1: 1, 4: 2}.get(kv_item, 2)
    casts = cast_tiles * block_k * hd * 2  # -> bf16 for the MXU
    return int((tiles + casts) * 1.1)


def _bd_mask(h: int, hd: int) -> jnp.ndarray:
    """[H, H*D] f32 block-diagonal mask: ``mask[g, l] = (l // D == g)``.
    Built from iotas in-kernel (constant-folded by Mosaic); used both to
    extract the per-head diagonal blocks of ``P^T V`` and to broadcast
    per-head scalars (corr, 1/l) onto the packed feature axis via a tiny
    matmul."""
    d = hd // h
    return (lax.broadcasted_iota(jnp.int32, (h, hd), 1) // d
            == lax.broadcasted_iota(jnp.int32, (h, hd), 0)).astype(jnp.float32)


def _attend_tile(row_len, v_tile, m_ref, l_ref, acc_ref,
                 j, block_k, h, s2, p_scale=None):
    """Shared online-softmax tile update. The kernels run it only for a
    tile that holds a position below ``row_len`` (``pl.when``): for a
    row with any live position a dead tile's update is exactly the
    identity (``p = exp(NEG_INF - m) = 0``, ``corr = exp(0) = 1``), so
    skipping it changes no bit of a live row's output, and a non-finite
    value in a page that was reserved and never written can no longer
    reach the output through ``0 * NaN``.

    ``row_len``: scalar valid length for THIS batch row (continuous
    batching gives every row its own depth — the callers read it from
    the [B] scalar-prefetch operand at ``pl.program_id(0)``); ``s2``:
    [BK, H] raw scores for this tile (already 1/sqrt(D)-scaled,
    scale-folded for int8); ``v_tile``: [BK, HD] bf16 packed values;
    ``p_scale``: optional [BK, H] per-position weight folded into the PV
    contraction only (the int8 V scales — the softmax normalizer ``l``
    must stay unscaled)."""
    hd = v_tile.shape[-1]
    mask = _bd_mask(h, hd)
    row = j * block_k + lax.broadcasted_iota(jnp.int32, s2.shape, 0)
    s2 = jnp.where(row < row_len, s2, NEG_INF)

    m_prev = m_ref[:]  # [1, H]
    m_new = jnp.maximum(m_prev, jnp.max(s2, axis=0, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s2 - m_new)  # [BK, H] f32
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=0, keepdims=True)
    pw = p if p_scale is None else p * p_scale
    c = jax.lax.dot_general(  # [H, HD] = P^T · V — MXU
        pw.astype(jnp.bfloat16), v_tile,
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    pv = jnp.sum(c * mask, axis=0, keepdims=True)  # [1, HD] diag blocks
    corr_flat = jax.lax.dot_general(  # broadcast corr[h] across head block
        corr, mask, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_ref[:] = acc_ref[:] * corr_flat + pv
    m_ref[:] = m_new


def _init_scratch(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)


def _finalize(j, n_kv, o_ref, l_ref, acc_ref, h):
    """Normalize and write the row's output at its last grid step. A row
    of length 0 (no live tile ran) writes zeros: ``acc`` 0, ``l``
    clamped."""
    @pl.when(j == n_kv - 1)
    def _write():
        inv = 1.0 / jnp.maximum(l_ref[:], 1e-30)
        inv_flat = jax.lax.dot_general(
            inv, _bd_mask(h, acc_ref.shape[-1]), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = (acc_ref[:] * inv_flat).astype(o_ref.dtype)


def _qk_scores(qbd_ref, k_tile, d):
    """[BK, H] all-head scores: one [BK, HD] x [HD, H] MXU matmul against
    the block-diagonal query."""
    scale = 1.0 / (d ** 0.5)
    return jax.lax.dot_general(
        k_tile, qbd_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _kv_refs(refs, quant):
    """(q scale, K, K scales, V, V scales) from a kernel's refs after the
    query; the scales are None for a bf16/f32 cache."""
    if quant:
        return refs[:5]
    k_ref, v_ref = refs[:2]
    return None, k_ref, None, v_ref, None


def _tile_update(row_len, j, block_k, h, qbd_ref, kv, m_ref, l_ref,
                 acc_ref):
    """One live tile: every head's scores against the block-diagonal
    query, then the online-softmax update; ``kv`` is :func:`_kv_refs`'s.
    With K scales (int8 tiles) the scores ride the native s8 MXU without
    a dequantized [BK, HD] tile: ``qbd`` is the per-head absmax int8
    query, so ``K8 . Qbd8`` contracts int8 x int8 -> int32 with NO cast
    copy of K — the int8->bf16 relayout of both tiles was the single
    largest exposed cost of the first packed int8 kernel (measured ~35
    us/call at 4k on v5e against a 41 us DMA floor). All three per-(position, head)
    scales (q, K, V) factor out of the D contraction and fold into the
    [BK, H] score/prob tensors. V still casts to bf16 for the PV matmul:
    quantizing the probabilities as well measured 3.6% error (the
    per-tile absmax under-resolves peaked softmax rows), so exact f32
    probabilities are kept and only V pays a cast."""
    qs_ref, k_ref, ks_ref, v_ref, vs_ref = kv
    d = k_ref.shape[-1] // h
    if ks_ref is None:
        s2 = _qk_scores(qbd_ref, k_ref[0].astype(jnp.bfloat16), d)
        p_scale = None
    else:
        s_i32 = jax.lax.dot_general(
            k_ref[0], qbd_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)  # [BK, H] on the s8 MXU
        scale = 1.0 / (d ** 0.5)
        s2 = s_i32.astype(jnp.float32) * ks_ref[0] * (qs_ref[0] * scale)
        p_scale = vs_ref[0]
    _attend_tile(row_len, v_ref[0].astype(jnp.bfloat16),
                 m_ref, l_ref, acc_ref, j, block_k, h, s2, p_scale=p_scale)


def _decode_kernel(len_ref, qbd_ref, *refs, block_k, n_kv, h, quant):
    """One (row, tile) step of the slab grid; ``refs`` are the K/V tiles
    (int8: q scale, K, K scales, V, V scales), the output and the
    scratch."""
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    j = pl.program_id(1)
    row_len = len_ref[pl.program_id(0)]
    _init_scratch(j, m_ref, l_ref, acc_ref)

    @pl.when(j * block_k < row_len)
    def _live():
        _tile_update(row_len, j, block_k, h, qbd_ref, _kv_refs(refs, quant),
                     m_ref, l_ref, acc_ref)

    _finalize(j, n_kv, o_ref, l_ref, acc_ref, h)


def _record_decode_cost(positions: int, hd: int, kv_item: int,
                        quant: bool, h: int) -> None:
    """One call's cost in the trace-time tally (``ops/flop_count.py``),
    like the other kernels'. ``positions`` is the cache extent the grid
    spans, batch rows x positions per row: the lengths are runtime values,
    so what is known at trace time is the extent, which is the upper
    bound; the kernel visits live pages only. q.K^T and p.V are 2
    FLOPs per position per feature each; K and V are read once (int8
    caches add their f32 per-head scales); queries and outputs are
    negligible. Equals ``benchmark/lib/flops.flash_decode(positions, hd,
    kv_item)`` for an unquantized cache."""
    scale_bytes = 2 * positions * h * 4 if quant else 0
    record_pallas_cost(
        flops=4 * positions * hd,
        bytes_accessed=2 * positions * hd * kv_item + scale_bytes,
        transcendentals=positions * h,
        category="attention_decode",
    )


def _resolve_interpret(interpret):
    if interpret is None:
        from distriflow_tpu.ops import default_interpret

        return default_interpret()
    return interpret


def flash_decode(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid_len: jnp.ndarray,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Decode attention for ONE query token per batch row.

    ``q``: [B, H, D]; ``k``/``v``: token-major packed caches
    ``[B, S, H*D]`` (bf16/f32, or int8 with ``k_scale``/``v_scale``
    ``[B, S, H]`` f32); ``valid_len``: int32 scalar (every row attends
    to [0, valid_len)) or a ``[B]`` vector giving each batch row its own
    window — the continuous-batching slot cache, where rows sit at
    unrelated depths. Returns [B, H, D] in ``q``'s dtype.

    ``block_k=None`` auto-picks via :func:`pick_block_k` and validates
    the tile against the scoped-VMEM model (a too-large explicit
    ``block_k`` raises a Python error with a remedy instead of a Mosaic
    compile crash — round-4's int8 kernel died with a 20 MB > 16 MB
    compiler internal that only surfaced on real hardware).

    For GSPMD/TP contexts use :func:`flash_decode_sharded`, which wraps
    this local kernel in a heads-sharded ``custom_partitioning`` rule.
    """
    interpret = _resolve_interpret(interpret)
    b, h, d = q.shape
    _, s, hd = k.shape
    if hd != h * d:
        raise ValueError(
            f"packed cache feature dim {hd} != n_heads*head_dim {h * d}")
    quant = k_scale is not None
    kv_item = jnp.dtype(k.dtype).itemsize
    if block_k is None:
        block_k = pick_block_k(s, hd, kv_item)
        if block_k is None:
            raise ValueError(
                f"flash_decode: no tile for seq {s} at packed width {hd} "
                "(needs a sublane-aligned divisor whose VMEM working set "
                f"fits {VMEM_LIMIT_BYTES / 1e6:.0f} MB) — pad the cache "
                "to a multiple of 8 or use the XLA decode path "
                "(use_flash_decode=False)")
    else:
        block_k = min(block_k, s)
        if s % block_k:
            raise ValueError(f"seq {s} not a multiple of block_k {block_k}")
    est = _vmem_estimate_bytes(block_k, hd, kv_item)
    if not interpret and est > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"flash_decode: estimated scoped-VMEM {est / 1e6:.1f} MB for "
            f"block_k={block_k}, packed dim {hd}, itemsize {kv_item} "
            f"exceeds the {VMEM_LIMIT_BYTES / 1e6:.0f} MB TPU limit — "
            "pass a smaller block_k (a divisor of the cache length, "
            "multiple of 8), or let block_k=None pick one")
    n_kv = s // block_k
    _record_decode_cost(b * s, hd, kv_item, quant, h)
    # scalar-prefetch lengths, one per batch row (a scalar broadcasts:
    # the homogeneous static-batch callers keep their old semantics)
    lens = jnp.broadcast_to(
        jnp.reshape(valid_len.astype(jnp.int32), (-1,)), (b,))
    return _tiled_decode(q, (k, k_scale, v, v_scale), lens, block_k, n_kv,
                         interpret)


def _quantize_query(q):
    """Per-head symmetric absmax int8 query, as integer-valued float32
    ``q8`` [B, H, D], and its scales [B, 1, H] (the q scale folds into
    the kernels' [BK, H] score multiply)."""
    qf32 = q.astype(jnp.float32)
    qs = jnp.max(jnp.abs(qf32), axis=-1, keepdims=True) / 127.0
    qs = jnp.maximum(qs, 1e-20)  # [B, H, 1]
    q8 = jnp.clip(jnp.round(qf32 / qs), -127, 127)
    return q8, qs[:, :, 0][:, None, :]


def _tiled_decode(q, kv, lens, block_k, n_kv, interpret):
    """The slab's (row, tile) grid. Past a row's last live tile the K/V
    block index repeats, and a grid step whose block index did not change
    issues no DMA."""
    k, k_scale, v, v_scale = kv
    b, h, d = q.shape
    hd = h * d
    quant = k_scale is not None
    last = jnp.maximum(lens - 1, 0) // block_k

    # block-diagonal query [B, HD, H]: head h's query in rows h*D:(h+1)*D
    # of column h — the operand that turns all-head scores into ONE
    # matmul. The int8 path quantizes it per head (symmetric absmax) so
    # the score contraction runs int8 x int8 on the MXU with no K cast.
    eye = jnp.eye(h, dtype=jnp.float32)
    if quant:
        q8, qs_row = _quantize_query(q)
        qbd = jnp.einsum("bhd,hg->bhdg", q8, eye).reshape(
            b, hd, h).astype(jnp.int8)
    else:
        qbd = jnp.einsum("bhd,hg->bhdg", q.astype(jnp.float32), eye).reshape(
            b, hd, h).astype(jnp.bfloat16)

    # index maps under PrefetchScalarGridSpec receive the scalar refs last
    def per_row(bi, j, lens, last):
        return (bi, 0, 0)

    def kv_index(bi, j, lens, last):
        return (bi, jnp.minimum(j, last[bi]), 0)

    in_specs = [pl.BlockSpec((1, hd, h), per_row)]
    arrays = [qbd]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, h), per_row))
        arrays.append(qs_row)
    for pool, scale in ((k, k_scale), (v, v_scale)):
        in_specs.append(pl.BlockSpec((1, block_k, hd), kv_index))
        arrays.append(pool)
        if quant:
            in_specs.append(pl.BlockSpec((1, block_k, h), kv_index))
            arrays.append(scale)

    body = functools.partial(_decode_kernel, block_k=block_k, n_kv=n_kv,
                             h=h, quant=quant)

    def kernel(len_ref, last_ref, *refs):
        body(len_ref, *refs)  # ``last`` serves the index maps

    out = pl.pallas_call(
        kernel,
        name="flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, hd), per_row),
            scratch_shapes=[
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, last, *arrays)
    return out.reshape(b, h, d)


# -- Paged KV cache (round 9) ----------------------------------------------
#
# The continuous-batching server's paged cache replaces the [B, S, H*D]
# per-row slabs with ONE pool of fixed-size pages [n_pages, page_size,
# H*D] plus a per-row page table: row bi's logical KV positions
# [j*page_size, (j+1)*page_size) live in physical page table[bi, j].
#
# A row's live pages are read off the kernel's own two inputs: the table
# says which pages exist (the entries before the first sentinel, an
# entry >= n_pages), the length says how many positions are written, and
# ``eff_len = min(valid_len, n_real * page_size)`` is what the kernel
# runs on. The kernel's steps are those pages and nothing more: one
# (row, page) item a live page, in row-major, ascending-page order, and
# one item for a row with no live page, so that it still writes its
# zeros; :func:`paged_work_list` builds the list and ``eff_len`` from
# the table and the lengths. A caller that runs the kernel on one table
# and one set of lengths several times (the model's layers in a decode
# step) builds the list once and hands it to every call (``work_list``).
# The grid is one axis whose extent is the list's length, a traced
# scalar; the K/V index maps read an item's page, the
# query and output maps its row, the scratch is reset on a row's first
# item and the output written on its last. A call costs a step a live
# page plus one a dead row, not rows x table width, and the pipeline
# fetches the next row's first page behind this row's last. A dead
# row's item names the page of the live item before it, so it fetches
# nothing, and it computes nothing; a page past a row's length or at and
# past its first sentinel is never named at all.
#
# The query comes in full-lane, [B, H, D]: the kernel builds the row's
# [H*D, H] block-diagonal operand in VMEM on the row's first item from
# the same values (:func:`_block_diagonal`), so the score matmul is the
# slab kernel's, bit for bit, and no lane-padded operand is written to
# HBM by XLA on every call.
#
# Accumulation order note: the paged kernel tiles at page_size, the slab
# kernel at pick_block_k(S) — when those differ the online-softmax adds
# run in a different order, so paged-vs-slab flash outputs agree to
# rounding (like slab flash vs the XLA path), not bitwise. The
# bit-identity contract (tests/test_paged_kv.py) is carried by the XLA
# fallback path, which gathers pages back into the exact slab view.
# Pick page_size == pick_block_k(max_seq) to make the kernels tile
# identically. No custom_partitioning rule yet: under TP the paged
# kernel's operands replicate (the auto-gate only enables it unsharded);
# TP serving keeps the slab layout for now — see docs/PERFORMANCE.md.

_warned_paged: set = set()


def paged_fits(page_size: int, hd: int = 512, kv_item: int = 2) -> bool:
    """True when :func:`flash_decode_paged` can run pages of
    ``page_size`` tokens at packed width ``hd``: sublane-aligned, at or
    above the sliver-DMA floor, and one double-buffered page pair fits
    scoped VMEM. The bare test: :func:`supports_paged` also counts and
    warns."""
    return (page_size % 8 == 0 and page_size >= MIN_BLOCK_K
            and _vmem_estimate_bytes(page_size, hd, kv_item)
            <= VMEM_LIMIT_BYTES)


def supports_paged(page_size: int, hd: int = 512, kv_item: int = 2) -> bool:
    """:func:`paged_fits`, where a gated shape bumps
    ``ops_flash_decode_gated_total`` and warns once, mirroring
    :func:`supports_seq`."""
    if paged_fits(page_size, hd, kv_item):
        return True
    from distriflow_tpu.obs import get_telemetry

    get_telemetry().counter(
        "ops_flash_decode_gated_total",
        help="decode calls routed to the XLA fallback by shape gating",
    ).inc()
    key = (page_size, hd, kv_item)
    if key not in _warned_paged:
        _warned_paged.add(key)
        warnings.warn(
            f"flash_decode_paged gated off for page_size {page_size} "
            f"(packed width {hd}, itemsize {kv_item}): pages must be a "
            f"multiple of 8, >= {MIN_BLOCK_K}, and fit scoped VMEM — "
            "decoding on the XLA fallback path. Use page_size 128 (the "
            "flash-decode block floor) or larger.",
            stacklevel=3)
    return False


def paged_work_list(table, lens, page_size: int, n_pages: int) -> Tuple:
    """The work list of :func:`flash_decode_paged` over ``table`` ([B, PP]
    int32, entries >= ``n_pages`` sentinels) and ``lens`` ([B] int32): row
    r contributes ``max(1, ceil(eff_len[r] / page_size))`` (row, page)
    items, rows in order, pages ascending, where ``eff_len = min(lens,
    n_real * page_size)`` and ``n_real`` counts the row's pages before
    its first sentinel. Returns ``(n_items, rows, cols, pages,
    eff_len)``: the list's length ([1], the grid's extent), three
    [B * PP] int32 arrays meaningful below it (an item's row, its column
    in the row's table, the pool page its K/V block index names) and
    ``eff_len``. The list depends on the table and the lengths alone, so
    calls that share both can share one list (``work_list``)."""
    record_pallas_count("paged_work_lists_built")
    with jax.named_scope("paged_work_list"):
        table = table.astype(jnp.int32)
        lens = lens.astype(jnp.int32)
        b, pp = table.shape
        m = b * pp
        col = lax.broadcasted_iota(jnp.int32, table.shape, 1)
        n_real = jnp.min(jnp.where(table < n_pages, pp, col), axis=1)
        eff_len = jnp.minimum(lens, n_real * page_size)
        tab = jnp.minimum(table, n_pages - 1)
        count = jnp.maximum(-(-eff_len // page_size), 1)
        ends = jnp.cumsum(count)
        item = jnp.arange(m, dtype=jnp.int32)
        # an item's flat table index: its own index plus the columns that the
        # rows wholly before it leave out
        before = ends[None, :] <= item[:, None]  # [M, B]
        flat = jnp.minimum(
            item + jnp.sum(jnp.where(before, pp - count, 0), axis=1), m - 1)
        rows, cols = flat // pp, flat % pp
        pages = tab.reshape(-1)[flat]
        # a dead row's item names the page of the live item before it (ahead
        # of the first live item, that item's page): no step fetches for it
        live = cols * page_size < eff_len[rows]
        src = lax.cummax(jnp.where(live, item, jnp.argmax(live)))
        return ends[-1:], rows, cols, pages[src], eff_len


def _block_diagonal(q, dtype):
    """A row's [H, D] query as the [H*D, H] block-diagonal operand of the
    score matmul (head h's query in rows h*D:(h+1)*D of column h, zeros
    elsewhere), built in VMEM. ``rep[l, g] = q[g, l % D]`` is one matmul
    against a 0/1 selection, exact (one nonzero term an output); the
    diagonal blocks are then kept. ``q`` holds bf16 values (the int8
    path: integers of at most 127), so the operand's values are the
    ones the slab path builds in XLA."""
    h, d = q.shape
    hd = h * d
    pos = lax.broadcasted_iota(jnp.int32, (hd, d), 0)
    sel = pos - pos // d * d == lax.broadcasted_iota(jnp.int32, (hd, d), 1)
    rep = jax.lax.dot_general(  # [HD, H]
        sel.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    diag = (lax.broadcasted_iota(jnp.int32, (hd, h), 0) // d
            == lax.broadcasted_iota(jnp.int32, (hd, h), 1))
    return jnp.where(diag, rep, 0.0).astype(dtype)


def _paged_kernel(row_ref, col_ref, page_ref, len_ref, q_ref, *refs,
                  page_size, h, quant):
    """One (row, page) item of the work list; ``refs`` as in
    :func:`_decode_kernel`, then the output and the scratch (the row's
    block-diagonal query first)."""
    o_ref, qbd_ref, m_ref, l_ref, acc_ref = refs[-5:]
    item = pl.program_id(0)
    j = col_ref[item]
    row_len = len_ref[row_ref[item]]
    _init_scratch(j, m_ref, l_ref, acc_ref)

    @pl.when((j == 0) & (row_len > 0))
    def _query():
        qbd_ref[0] = _block_diagonal(q_ref[0], qbd_ref.dtype)

    @pl.when(j * page_size < row_len)
    def _live():
        _tile_update(row_len, j, page_size, h, qbd_ref,
                     _kv_refs(refs, quant), m_ref, l_ref, acc_ref)

    n_items = jnp.maximum((row_len + page_size - 1) // page_size, 1)
    _finalize(j, n_items, o_ref, l_ref, acc_ref, h)


def flash_decode_paged(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    page_table: jnp.ndarray,
    valid_len: jnp.ndarray,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
    work_list: Optional[Tuple] = None,
) -> jnp.ndarray:
    """Decode attention against a PAGED cache, one query token per row.

    ``q``: [B, H, D]; ``k``/``v``: page pools ``[n_pages, page_size,
    H*D]`` (bf16/f32, or int8 with ``k_scale``/``v_scale``
    ``[n_pages, page_size, H]`` f32 pools); ``page_table``: [B, PP]
    int32 — row bi reads physical page ``page_table[bi, j]`` for its
    j-th logical page (entries >= n_pages are sentinels, and the first
    one ends the row: positions at and past it are not attended to,
    whatever the length says); ``valid_len``: scalar or [B] per-row
    window, same contract as :func:`flash_decode`. Returns [B, H, D] in
    ``q``'s dtype; a row with no live position (length 0, or a table
    that starts with a sentinel) returns zeros. Pages past a row's
    length are neither fetched nor computed on, so what they hold
    (NaN included) cannot reach the output.

    ``work_list``: what :func:`paged_work_list` returns for this
    ``page_table``, ``valid_len`` and pool, built once by a caller that
    runs several calls on them (the model's layers in a decode step);
    the call then builds nothing and reads ``page_table`` for its width
    alone. Absent, the call builds its own."""
    interpret = _resolve_interpret(interpret)
    b, h, d = q.shape
    n_pages, ps, hd = k.shape
    if hd != h * d:
        raise ValueError(
            f"packed pool feature dim {hd} != n_heads*head_dim {h * d}")
    if ps % 8 and not interpret:
        raise ValueError(
            f"page_size {ps} must be a multiple of 8 (TPU sublane)")
    quant = k_scale is not None
    kv_item = jnp.dtype(k.dtype).itemsize
    est = _vmem_estimate_bytes(ps, hd, kv_item)
    if not interpret and est > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"flash_decode_paged: estimated scoped-VMEM {est / 1e6:.1f} MB "
            f"for page_size={ps}, packed dim {hd} exceeds the "
            f"{VMEM_LIMIT_BYTES / 1e6:.0f} MB TPU limit — shrink page_size")
    n_kv = page_table.shape[1]
    _record_decode_cost(b * n_kv * ps, hd, kv_item, quant, h)
    if work_list is None:
        lens = jnp.broadcast_to(
            jnp.reshape(valid_len.astype(jnp.int32), (-1,)), (b,))
        # a row's live pages end at its first sentinel, whatever its
        # length says: a retired slot's row is all sentinels under a
        # stale, still growing length
        work_list = paged_work_list(page_table, lens, ps, n_pages)
    else:
        record_pallas_count("paged_work_lists_reused")
    n_items, rows, cols, pages, eff_len = work_list
    if rows.shape != (b * n_kv,) or eff_len.shape != (b,):
        raise ValueError(
            f"work_list of {rows.shape[0]} items over {eff_len.shape[0]} "
            f"rows does not fit a [{b}, {n_kv}] page table")

    # index maps under PrefetchScalarGridSpec receive the scalar refs last
    def per_row(item, rows, cols, pages, lens):
        return (rows[item], 0, 0)

    def per_page(item, rows, cols, pages, lens):
        return (pages[item], 0, 0)

    in_specs = [pl.BlockSpec((1, h, d), per_row)]
    if quant:
        q8, qs_row = _quantize_query(q)
        in_specs.append(pl.BlockSpec((1, 1, h), per_row))
        arrays = [q8, qs_row]
    else:
        arrays = [q]
    for pool, scale in ((k, k_scale), (v, v_scale)):
        in_specs.append(pl.BlockSpec((1, ps, hd), per_page))
        arrays.append(pool)
        if quant:
            in_specs.append(pl.BlockSpec((1, ps, h), per_page))
            arrays.append(scale)

    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, h=h, quant=quant),
        name="flash_decode_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_items[0],),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, hd), per_row),
            scratch_shapes=[
                pltpu.VMEM((1, hd, h), jnp.int8 if quant else jnp.bfloat16),
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, h), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(rows, cols, pages, eff_len, *arrays)
    return out.reshape(b, h, d)


# -- GSPMD partitioning ----------------------------------------------------
#
# Decode attention is HEAD-independent: each head attends to its own slice
# of the packed cache. Under Megatron-style tensor parallelism the q/k/v
# projections are column-sharded, so q arrives [B, H(model), D] and the
# cache [B, S, (H*D)(model)] — exactly a per-shard instance of the same
# kernel. custom_partitioning declares that (mirroring ops/fused_ce.py's
# rows-sharded rule), which is what lets TP-sharded decoding keep the
# flash kernel instead of the round-4 behavior (auto-gate OFF because a
# bare pallas_call has no GSPMD rule and would force an all-gather).


def _head_axis_degree(mesh, axes) -> int:
    if axes is None:
        return 1
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    deg = 1
    for a in names:
        deg *= int(dict(mesh.shape)[a])
    return deg


@functools.lru_cache(maxsize=8)
def _sharded_fd(quant: bool, interpret: bool):
    """custom_partitioning-wrapped local kernel for one (quant, interpret)
    signature. Head-sharded: q's axis-1 sharding drives everything; the
    packed H*D cache axis and the [B, S, H] scale axis co-shard with it
    (whole heads per shard), S stays replicated.

    Like ``ops/flash_attention.py::_sharded_fa``, this does not compile on
    real multi-chip TPUs with the installed jax/libtpu ("Custom emitter for
    CustomSPMDPartitioning not found", PR 21); one device and the CPU
    partitioner are unaffected."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    def fn(q, k, v, len1, *scales):
        ks, vs = scales if quant else (None, None)
        return flash_decode(q, k, v, len1, k_scale=ks, v_scale=vs,
                            interpret=interpret)

    wrapped = custom_partitioning(fn)

    def _q_spec(mesh, arg_infos):
        """(batch_axes, head_axes) from q's sharding — with the
        crooked-head fallback applied HERE so infer and partition can
        never disagree (a mismatch would make the partitioner insert a
        reshard after every decode step)."""
        spec = getattr(arg_infos[0].sharding, "spec", None) or P()
        b = spec[0] if len(spec) >= 1 else None
        hx = spec[1] if len(spec) >= 2 else None
        h_total = arg_infos[0].shape[1]
        if h_total % max(_head_axis_degree(mesh, hx), 1):
            hx = None  # crooked head split: replicate heads instead
        return b, hx

    def infer(mesh, arg_infos, result_infos):
        b, hx = _q_spec(mesh, arg_infos)
        return NamedSharding(mesh, P(b, hx, None))

    def partition(mesh, arg_infos, result_infos):
        b, hx = _q_spec(mesh, arg_infos)
        q_sh = NamedSharding(mesh, P(b, hx, None))
        kv_sh = NamedSharding(mesh, P(b, None, hx))
        # the [B] per-row lengths co-shard with batch (each data shard
        # masks its own rows)
        arg_sh = [q_sh, kv_sh, kv_sh, NamedSharding(mesh, P(b))]
        if quant:
            arg_sh += [kv_sh, kv_sh]  # [B, S, H] scales co-shard on H
        return mesh, fn, NamedSharding(mesh, P(b, hx, None)), tuple(arg_sh)

    rule = ("b h d, b s k, b s k, b -> b h d" if not quant else
            "b h d, b s k, b s k, b, b s j, b s j -> b h d")
    wrapped.def_partition(
        partition=partition, infer_sharding_from_operands=infer,
        sharding_rule=rule)
    return wrapped


def flash_decode_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid_len: jnp.ndarray,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`flash_decode` with a heads-sharded GSPMD partitioning rule —
    safe (and a no-op) on unsharded operands; under tensor parallelism
    each model shard runs the kernel on its own heads with no gather.
    Head counts not divisible by the sharding degree replicate heads
    (correct, just not sharded). ``valid_len`` may be a scalar or a
    ``[B]`` per-row vector (continuous-batching slot cache)."""
    interpret = _resolve_interpret(interpret)
    # materialize the [B] per-row form OUTSIDE the partitioned call so
    # the lengths operand carries a batch dim the rule can co-shard
    lens = jnp.broadcast_to(
        jnp.reshape(valid_len.astype(jnp.int32), (-1,)), (q.shape[0],))
    fn = _sharded_fd(k_scale is not None, bool(interpret))
    if k_scale is not None:
        return fn(q, k, v, lens, k_scale, v_scale)
    return fn(q, k, v, lens)
