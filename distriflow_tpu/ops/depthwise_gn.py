"""Fused depthwise-3x3 + GroupNorm (+ ReLU6) as one Pallas TPU kernel.

Round-5 profiling put MobileNetV2's step time ~38% in the depthwise convs
and ~33% in GroupNorm — both memory-bound: the depthwise conv has nothing
for the MXU to contract over (one input channel per output channel) and
GroupNorm is two more full passes over the activation. The round-5 shift
reformulation (``models/mobilenet.py:_depthwise3x3_shift``) moved the
depthwise onto the VPU but still round-trips the activation through HBM
between conv, norm, and act; PERFORMANCE.md §7b measured that
reformulation alone cannot reach the 0.15 MFU bar. This kernel removes the
round trips instead: one grid step loads an input tile to VMEM once and
writes the conv+norm+act result once — the intermediate conv output and
the GN statistics never touch HBM.

Layout: grid ``(B, C/block_c)``, both parallel — each step owns one batch
element x one channel block at FULL spatial extent, because GroupNorm
statistics need every spatial position of a group. Channel blocks are
multiples of the group size (8), so no group straddles blocks and the
statistics are exact, not block-approximate. MobileNet's depthwise stages
are spatially small (<= 112x112) with <= 960 channels, so a full-spatial
tile is at most ~1.7 MB of f32 — comfortably inside scoped VMEM; the
:func:`depthwise_gn_supported` gate enforces that analytically and routes
oversized or sliver shapes to the unfused composition (mirroring
``flash_decode``'s MIN_BLOCK_K tile-floor pattern).

Backward: ``custom_vjp`` with FlashAttention-style rematerialization — the
residuals are just ``(x_padded, w, scale, bias)``; the backward kernel
re-runs the forward tile *abstractly* through ``jax.vjp`` inside the
kernel body (a trace-time transform of the same pure tile function, so
forward and backward can never drift apart) and emits dx tiles plus
per-batch dw/dscale/dbias partials that a cheap XLA sum reduces outside.

Numerics match the reference composition (shift-MACs + one-pass GroupNorm)
bitwise in f32: same nine-term accumulation order, same
``max(E[x^2]-E[x]^2, 0) + eps`` variance, f32 statistics regardless of the
activation dtype (tests/test_depthwise_gn.py).
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

from distriflow_tpu.ops.flop_count import record_pallas_cost

GROUP_SIZE = 8  # matches the model plane: channels are multiples of 8 by
# construction (_make_divisible), so a fixed group size always divides
MIN_CHANNELS = 8  # sliver floor: below one group there is nothing to
# normalize over and the lane dim degenerates (flash_decode MIN_BLOCK_K
# pattern — gate off, don't run slow)
VMEM_LIMIT_BYTES = 16 * 1024 * 1024  # TPU scoped-vmem compile limit

_warned_gated: set = set()  # (h, w, c, stride) shapes already warned about

# What the TPU compiler said when this kernel first met it (PR 21: TPU v5e,
# jax 0.9.0, libtpu 0.0.34; MobileNetV2/96 px shapes, f32 and bf16). The
# kernel has only ever run in the Pallas interpreter. It is the construct
# Mosaic refuses, not a tile size, so no shape compiles; ROADMAP S3/D3 own
# the rewrite-or-delete decision.
MOSAIC_REFUSAL = (
    "ops.depthwise3x3_groupnorm does not compile on TPU. Mosaic's words — "
    "forward, stride 1: \"INTERNAL: Mosaic failed to compile TPU kernel: "
    "infer-vector-layout: unsupported shape cast ... 'tpu.reshape' "
    "(vector<48x48x32xf32>) -> vector<2304x4x8xf32>\" (the GroupNorm "
    "lane split cb -> (cb/8, 8)); forward, stride 2: \"'vector."
    "extract_strided_slice' op expected strides to be confined to [1, 2)\" "
    "(the stride-2 lax.slice of the padded tile); backward, both strides: "
    "\"The Pallas TPU lowering currently requires that the last two "
    "dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array. Block spec for outputs[2] in pallas_call depthwise_gn_bwd ... "
    "block shape (1, C), array shape (B, C)\" (the per-batch dscale/dbias "
    "partials). Use depthwise_impl='conv' (the default) or 'shift'."
)


def _same_pads(d: int, stride: int) -> Tuple[int, int]:
    """XLA SAME padding for kernel 3 — parity-aware: odd dims at stride 2
    pad (1, 1), even dims (0, 1) (see _depthwise3x3_shift's docstring)."""
    total = max((-(-d // stride) - 1) * stride + 3 - d, 0)
    return (total // 2, total - total // 2)


def _channel_block(c: int) -> int:
    """Channel tile: the whole dim when small (Mosaic accepts a block equal
    to the array dim), else the largest multiple-of-128 divisor; fall back
    to full C — the VMEM gate has already bounded the tile size."""
    if c <= 512:
        return c
    for blk in range(512, 0, -128):
        if c % blk == 0:
            return blk
    return c


def _vmem_estimate_bytes(hp, wp, oh, ow, block_c, itemsize):
    # input tile + conv accumulator + normalized output (+ one spare copy
    # for Mosaic's pipelining headroom)
    est = hp * wp * block_c * itemsize
    est += 2 * oh * ow * block_c * 4  # conv acc + normalize, f32
    est += oh * ow * block_c * itemsize  # output tile
    return int(est * 1.5)


def depthwise_gn_supported(
    h: int,
    w: int,
    c: int,
    stride: int = 1,
    group_size: int = GROUP_SIZE,
    itemsize: int = 4,
) -> bool:
    """True when the fused kernel can run an ``[_, h, w, c]`` activation.

    Requires: channels divisible by the group size and at or above the
    sliver floor, spatial dims that produce at least one output position,
    and a full-spatial channel-block tile that fits scoped VMEM. Gated
    shapes bump ``ops_depthwise_gn_gated_total`` and warn once; callers
    (``models/mobilenet.py``) take the unfused shift+GN composition.
    """
    ok = (
        c >= MIN_CHANNELS
        and c % group_size == 0
        and stride in (1, 2)
        and min(h, w) >= 1
    )
    if ok:
        (pt, pb), (pl_, pr) = _same_pads(h, stride), _same_pads(w, stride)
        hp, wp = h + pt + pb, w + pl_ + pr
        oh, ow = (hp - 3) // stride + 1, (wp - 3) // stride + 1
        ok = oh >= 1 and ow >= 1 and _vmem_estimate_bytes(
            hp, wp, oh, ow, _channel_block(c), itemsize
        ) <= VMEM_LIMIT_BYTES
    if ok:
        return True
    from distriflow_tpu.obs import get_telemetry

    get_telemetry().counter(
        "ops_depthwise_gn_gated_total",
        help="depthwise+GN shapes gated off the fused kernel",
    ).inc()
    key = (h, w, c, stride)
    if key not in _warned_gated:
        _warned_gated.add(key)
        warnings.warn(
            f"depthwise3x3_groupnorm gated off for activation {h}x{w}x{c} "
            f"stride {stride}: channels must be a multiple of {group_size} "
            f"(>= {MIN_CHANNELS}) and the full-spatial channel tile must "
            "fit scoped VMEM — running the unfused shift+GroupNorm "
            "composition instead.",
            stacklevel=3)
    return False


def _tile_fwd(xp, w, scale, bias, *, stride, out_h, out_w, eps, group_size,
              relu6):
    """One (batch, channel-block) tile: conv + GN + act, pure jnp.

    The single source of truth for the kernel math — the forward kernel
    calls it directly and the backward kernel differentiates it with
    ``jax.vjp``, so the VJP can never drift from the primal. Term order
    and dtypes deliberately mirror the unfused reference composition
    (``_depthwise3x3_shift`` then ``_OnePassGroupNorm``) for bitwise f32
    parity: shift-MACs in the activation dtype in (ky, kx) order, f32
    statistics, ``max(E[x^2]-E[x]^2, 0) + eps`` variance, affine in f32,
    cast, then ReLU6.
    """
    hp, wp, cb = xp.shape
    acc = None
    for ky in range(3):
        for kx in range(3):
            sl = lax.slice(
                xp,
                (ky, kx, 0),
                (ky + (out_h - 1) * stride + 1,
                 kx + (out_w - 1) * stride + 1, cb),
                (stride, stride, 1),
            )
            term = sl * w[ky, kx]
            acc = term if acc is None else acc + term
    xg = acc.reshape(out_h * out_w, cb // group_size, group_size).astype(
        jnp.float32
    )
    m = xg.mean(axis=(0, 2), keepdims=True)
    m2 = (xg * xg).mean(axis=(0, 2), keepdims=True)
    inv = lax.rsqrt(jnp.maximum(m2 - m * m, 0.0) + eps)
    y = ((xg - m) * inv).reshape(out_h, out_w, cb)
    y = (y * scale + bias).astype(xp.dtype)
    if relu6:
        y = jnp.minimum(jnp.maximum(y, 0.0), 6.0)
    return y


def _fwd_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, tile):
    o_ref[0] = tile(x_ref[0], w_ref[:], s_ref[0], b_ref[0])


def _bwd_kernel(x_ref, w_ref, s_ref, b_ref, g_ref,
                dx_ref, dw_ref, ds_ref, db_ref, *, tile):
    # jax.vjp of the SAME pure tile function, applied at trace time inside
    # the kernel body: the whole backward (conv transpose, GN statistic
    # gradients, ReLU6 mask) lowers as one fused sweep over the tile that
    # is already resident in VMEM — the FlashAttention remat trade: re-run
    # the cheap forward rather than round-trip residuals through HBM.
    _, vjp_fn = jax.vjp(tile, x_ref[0], w_ref[:], s_ref[0], b_ref[0])
    dxp, dw, dscale, dbias = vjp_fn(g_ref[0])
    dx_ref[0] = dxp.astype(dx_ref.dtype)
    dw_ref[0] = dw.astype(jnp.float32)
    ds_ref[0] = dscale.astype(jnp.float32)
    db_ref[0] = dbias.astype(jnp.float32)


def _resolve_interpret(interpret):
    if interpret is None:
        from distriflow_tpu.ops import default_interpret

        return default_interpret()
    return interpret


def _prep(x, w, stride):
    """Pad to SAME outside the kernel; returns (xp, out_h, out_w, pads)."""
    b, h, wd, c = x.shape
    ph, pw = _same_pads(h, stride), _same_pads(wd, stride)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    out_h = (h + sum(ph) - 3) // stride + 1
    out_w = (wd + sum(pw) - 3) // stride + 1
    return xp, out_h, out_w, (ph, pw)


def _record_cost(b, oh, ow, c, hp, wp, itemsize, backward):
    # model FLOPs: 9 MACs/position (18) + GN statistics/normalize/affine
    # (~10) per element; backward is ~2x the forward's algorithmic work,
    # and the kernel ALSO re-runs the forward (remat) — counted in
    # hw_flops only, per the MFU convention (ops/flop_count.py docstring)
    fwd = 28 * b * oh * ow * c
    record_pallas_cost(
        flops=(2 * fwd) if backward else fwd,
        bytes_accessed=(
            b * hp * wp * c * itemsize + b * oh * ow * c * itemsize
        ) * (2 if backward else 1),
        transcendentals=b * (c // GROUP_SIZE),  # one rsqrt per group
        category="depthwise_gn",
        hw_flops=(3 * fwd) if backward else fwd,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def depthwise3x3_groupnorm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    scale: jnp.ndarray,
    bias: jnp.ndarray,
    stride: int = 1,
    eps: float = 1e-6,
    group_size: int = GROUP_SIZE,
    relu6: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused ``depthwise3x3(SAME) -> GroupNorm -> ReLU6`` over NHWC ``x``.

    ``w`` is the flax depthwise kernel (HWIO with I=1: ``[3, 3, 1, C]``),
    ``scale``/``bias`` the GroupNorm affine (``[C]``, f32). Callers should
    consult :func:`depthwise_gn_supported` first; ``interpret=None``
    auto-selects compiled-on-TPU / interpreter elsewhere.
    """
    return _dwgn_fwd(x, w, scale, bias, stride, eps, group_size, relu6,
                     interpret)[0]


def _dwgn_fwd(x, w, scale, bias, stride, eps, group_size, relu6, interpret):
    interpret = _resolve_interpret(interpret)
    b, h, wd, c = x.shape
    xp, out_h, out_w, _ = _prep(x, w, stride)
    hp, wp = xp.shape[1], xp.shape[2]
    block_c = _channel_block(c)
    _record_cost(b, out_h, out_w, c, hp, wp, x.dtype.itemsize, backward=False)

    tile = functools.partial(
        _tile_fwd, stride=stride, out_h=out_h, out_w=out_w, eps=eps,
        group_size=group_size, relu6=relu6,
    )
    wsq = w.reshape(3, 3, c)  # drop the I=1 dim: [3, 3, C]
    s2 = scale.reshape(1, c).astype(jnp.float32)
    b2 = bias.reshape(1, c).astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile),
        name="depthwise_gn_fwd",
        grid=(b, c // block_c),
        in_specs=[
            pl.BlockSpec((1, hp, wp, block_c), lambda bi, cb: (bi, 0, 0, cb)),
            pl.BlockSpec((3, 3, block_c), lambda bi, cb: (0, 0, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (0, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (0, cb)),
        ],
        out_specs=pl.BlockSpec(
            (1, out_h, out_w, block_c), lambda bi, cb: (bi, 0, 0, cb)
        ),
        out_shape=jax.ShapeDtypeStruct((b, out_h, out_w, c), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(xp, wsq, s2, b2)
    return out, (x, w, scale, bias)


def _dwgn_bwd(stride, eps, group_size, relu6, interpret, res, g):
    x, w, scale, bias = res
    interpret = _resolve_interpret(interpret)
    b, h, wd, c = x.shape
    xp, out_h, out_w, (ph, pw) = _prep(x, w, stride)
    hp, wp = xp.shape[1], xp.shape[2]
    block_c = _channel_block(c)
    _record_cost(b, out_h, out_w, c, hp, wp, x.dtype.itemsize, backward=True)

    tile = functools.partial(
        _tile_fwd, stride=stride, out_h=out_h, out_w=out_w, eps=eps,
        group_size=group_size, relu6=relu6,
    )
    wsq = w.reshape(3, 3, c)
    s2 = scale.reshape(1, c).astype(jnp.float32)
    b2 = bias.reshape(1, c).astype(jnp.float32)
    dxp, dwp, dsp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, tile=tile),
        name="depthwise_gn_bwd",
        grid=(b, c // block_c),
        in_specs=[
            pl.BlockSpec((1, hp, wp, block_c), lambda bi, cb: (bi, 0, 0, cb)),
            pl.BlockSpec((3, 3, block_c), lambda bi, cb: (0, 0, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (0, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (0, cb)),
            pl.BlockSpec(
                (1, out_h, out_w, block_c), lambda bi, cb: (bi, 0, 0, cb)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, hp, wp, block_c), lambda bi, cb: (bi, 0, 0, cb)),
            # dw/dscale/dbias come out as PER-BATCH partials (each grid
            # step owns a unique write-once block — Pallas revisit rule);
            # the cross-batch sum is a cheap XLA reduction outside
            pl.BlockSpec((1, 3, 3, block_c), lambda bi, cb: (bi, 0, 0, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (bi, cb)),
            pl.BlockSpec((1, block_c), lambda bi, cb: (bi, cb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, wp, c), x.dtype),
            jax.ShapeDtypeStruct((b, 3, 3, c), jnp.float32),
            jax.ShapeDtypeStruct((b, c), jnp.float32),
            jax.ShapeDtypeStruct((b, c), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(xp, wsq, s2, b2, g)
    # unpad dx (the pad region's cotangent belongs to constant zeros)
    dx = lax.slice(
        dxp, (0, ph[0], pw[0], 0), (b, ph[0] + h, pw[0] + wd, c)
    ).astype(x.dtype)
    # mirror the primal w's layout: [3,3,1,C] (flax HWIO) or squeezed [3,3,C]
    dw = jnp.sum(dwp, axis=0).reshape(w.shape).astype(w.dtype)
    dscale = jnp.sum(dsp, axis=0).astype(scale.dtype)
    dbias = jnp.sum(dbp, axis=0).astype(bias.dtype)
    return dx, dw, dscale, dbias


depthwise3x3_groupnorm.defvjp(_dwgn_fwd, _dwgn_bwd)
