"""MobileNetV2 (the ImageNet-subset stretch workload).

No reference counterpart exists (the reference ships only the MNIST MLP and a
Keras ConvNet export, ``experiment/mnist/mnist_server.ts:16-22`` /
``model.json``); MobileNetV2 is this repo's own stretch target.

TPU-first design decisions:

- **GroupNorm by default, frozen BatchNorm on request.** Canonical
  MobileNetV2 uses BatchNorm, whose running statistics are mutable state
  and, under data parallelism, require a cross-replica stats sync every
  step. GroupNorm is stateless — the model stays a pure
  ``(params, x) -> logits`` function, so every trainer (sync psum, async
  host-coordinated, federated) consumes it unchanged, and no norm-state
  divergence exists between workers. Channel counts are multiples of 8 by
  construction (``_make_divisible``), so a fixed group size of 8 always
  divides evenly. For **canonical pretrained weights**, pass
  ``norm="batch"``: BatchNorm with the moving statistics stored as
  (stop-gradient) parameters — the standard frozen-BN inference/fine-tune
  semantics, parameter-compatible with stock checkpoints (scale, bias,
  mean, var per conv), still a pure function. Training from scratch
  should keep GroupNorm (frozen BN never updates its statistics).
- **ReLU6 kept** (it is elementwise — XLA fuses it into the preceding
  conv's epilogue; clipping aids low-precision activations).
- **NHWC layout + explicit dtype policy**: pass ``jnp.bfloat16`` to run the
  depthwise/pointwise convs on the MXU at its native precision; params stay
  float32 (flax default ``param_dtype``) so the optimizer math is exact.
- Depthwise convs are expressed with ``feature_group_count`` so XLA lowers
  them to true depthwise convolutions rather than grouped matmuls.
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distriflow_tpu.models.base import ModelSpec
from distriflow_tpu.models.flax_model import spec_from_flax

# (expansion t, out channels c, repeats n, first-block stride s) — the
# standard MobileNetV2 inverted-residual schedule.
V2_SCHEDULE: Tuple[Tuple[int, int, int, int], ...] = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)


def _make_divisible(v: float, divisor: int = 8) -> int:
    """Round channel counts to a multiple of ``divisor``, never dropping
    below 90% of the requested width (standard MobileNet rule)."""
    new_v = max(divisor, int(v + divisor / 2) // divisor * divisor)
    if new_v < 0.9 * v:
        new_v += divisor
    return new_v


class FrozenBatchNorm(nn.Module):
    """BatchNorm with moving statistics as frozen parameters.

    ``y = scale * (x - mean) / sqrt(var + eps) + bias`` with ``mean``/``var``
    under ``stop_gradient``: the optimizer never moves them (zero grads) and
    the module stays a pure function — the canonical-checkpoint-compatible
    norm for pretrained MobileNetV2 (same four per-channel arrays as stock
    BatchNorm layers). Inference / frozen-BN fine-tune semantics only.
    """

    eps: float = 1e-3  # tf.keras BatchNormalization default
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        # the "frozen_" prefix keeps these out of the optimizer entirely
        # (base._optimizer masks them): stop_gradient zeroes their grads,
        # but only the mask stops gradient-independent updates like adamw's
        # decoupled weight decay from eroding pretrained statistics
        mean = self.param("frozen_mean", nn.initializers.zeros, (c,), jnp.float32)
        var = self.param("frozen_var", nn.initializers.ones, (c,), jnp.float32)
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        inv = (scale / jnp.sqrt(var + self.eps)).astype(self.dtype)
        shift = (bias - mean * scale / jnp.sqrt(var + self.eps)).astype(self.dtype)
        return x * inv + shift


class _OnePassGroupNorm(nn.Module):
    """GroupNorm(group_size=8) via single-pass E[x]/E[x^2] statistics.

    flax's GroupNorm computes two passes (mean, then centered variance)
    over the [B, H*W, G, 8] view; the one-pass form halves the stats
    reads and XLA fuses the normalize into the same sweep. Numerics: f32
    accumulation, variance = max(E[x^2] - E[x]^2, 0) + eps — equivalent
    within bf16 activation noise (tests/test_mobilenet.py).
    """

    eps: float = 1e-6  # flax GroupNorm default
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, w, c = x.shape
        scale = self.param("scale", nn.initializers.ones, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        xg = x.reshape(b, h * w, c // 8, 8).astype(jnp.float32)
        m = xg.mean(axis=(1, 3), keepdims=True)
        m2 = (xg * xg).mean(axis=(1, 3), keepdims=True)
        inv = jax.lax.rsqrt(jnp.maximum(m2 - m * m, 0.0) + self.eps)
        y = ((xg - m) * inv).reshape(b, h, w, c)
        return (y * scale + bias).astype(self.dtype)


def _depthwise3x3_shift(x: jnp.ndarray, w: jnp.ndarray, stride: int) -> jnp.ndarray:
    """Depthwise 3x3 as nine shifted multiply-accumulates.

    A depthwise conv carries ~3% of MobileNet's FLOPs but ~38% of its
    step time on the MXU (the systolic array has nothing to contract
    over: one input channel per output channel). Expressed as nine
    shift-MACs the op is pure VPU elementwise work over the NHWC lanes —
    each term is ``x`` shifted by (ky, kx) times a per-channel scalar,
    which XLA fuses into one pass over the activation.

    Matches ``nn.Conv(padding="SAME", feature_group_count=C)`` bitwise in
    f32 (tests/test_mobilenet.py). SAME pads are computed from the input
    parity — ``total = max((ceil(d/s)-1)*s + 3 - d, 0)`` split low/high —
    so odd spatial dims at stride 2 (e.g. a 75-wide stage from
    image_size=150) pad (1, 1) like XLA does, not the even-dim (0, 1).

    ``w``: flax conv kernel, HWIO with I=1 — shape [3, 3, 1, C].
    """
    b, h, wd, c = x.shape

    def same_pads(d):
        total = max((-(-d // stride) - 1) * stride + 3 - d, 0)
        return (total // 2, total - total // 2)

    pads = (same_pads(h), same_pads(wd))
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    out_h = (h + sum(pads[0]) - 3) // stride + 1
    out_w = (wd + sum(pads[1]) - 3) // stride + 1
    acc = None
    for ky in range(3):
        for kx in range(3):
            sl = jax.lax.slice(
                xp,
                (0, ky, kx, 0),
                (b, ky + (out_h - 1) * stride + 1,
                 kx + (out_w - 1) * stride + 1, c),
                (1, stride, stride, 1),
            )
            term = sl * w[ky, kx, 0]
            acc = term if acc is None else acc + term
    return acc


def _onepass_gn_affine(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
                       eps: float = 1e-6) -> jnp.ndarray:
    """_OnePassGroupNorm's math with explicit affine params — the unfused
    fallback for the fused depthwise+GN branch (same params, same numerics
    as ops/depthwise_gn's in-kernel tile, just composed through HBM)."""
    b, h, w, c = x.shape
    xg = x.reshape(b, h * w, c // 8, 8).astype(jnp.float32)
    m = xg.mean(axis=(1, 3), keepdims=True)
    m2 = (xg * xg).mean(axis=(1, 3), keepdims=True)
    inv = jax.lax.rsqrt(jnp.maximum(m2 - m * m, 0.0) + eps)
    y = ((xg - m) * inv).reshape(b, h, w, c)
    return (y * scale + bias).astype(x.dtype)


class _ConvNorm(nn.Module):
    """conv -> norm (GroupNorm | frozen BatchNorm) -> optional relu6."""

    features: int
    kernel: Tuple[int, int] = (1, 1)
    stride: int = 1
    groups: int = 1  # feature_group_count (== in-channels for depthwise)
    act: bool = True
    norm: str = "group"
    dtype: Any = jnp.float32
    depthwise_impl: str = "conv"  # "conv" | "shift" (VPU) | "fused" (Pallas)
    gn_impl: str = "flax"  # "flax" | "onepass" (single-sweep statistics)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        in_ch = x.shape[-1]
        if (self.depthwise_impl == "fused" and self.kernel == (3, 3)
                and self.groups == in_ch and self.features == in_ch
                and self.norm == "group"):
            # one Pallas kernel covers conv + GroupNorm + ReLU6 — the two
            # measured hot spots (depthwise ~38%, GN ~33% of step) in one
            # VMEM-resident sweep (ops/depthwise_gn.py). Params mirror the
            # shift branch's "kernel" plus the GN affine, so the fused and
            # unfused fallback paths share one param structure.
            from distriflow_tpu.ops.depthwise_gn import (
                depthwise3x3_groupnorm,
                depthwise_gn_supported,
            )

            w = self.param(
                "kernel",
                nn.initializers.lecun_normal(),
                (3, 3, 1, in_ch),
                jnp.float32,
            ).astype(self.dtype)
            scale = self.param(
                "scale", nn.initializers.ones, (in_ch,), jnp.float32)
            bias = self.param(
                "bias", nn.initializers.zeros, (in_ch,), jnp.float32)
            xd = x.astype(self.dtype)
            if depthwise_gn_supported(
                    x.shape[1], x.shape[2], in_ch, self.stride,
                    itemsize=jnp.dtype(self.dtype).itemsize):
                y = depthwise3x3_groupnorm(
                    xd, w, scale, bias, self.stride, 1e-6, 8, self.act, None)
                return y
            # gated shape: same math unfused (shift-MACs then one-pass GN)
            y = _depthwise3x3_shift(xd, w, self.stride)
            y = _onepass_gn_affine(y, scale, bias)
            return nn.relu6(y) if self.act else y
        if (self.depthwise_impl == "shift" and self.kernel == (3, 3)
                and self.groups == in_ch and self.features == in_ch):
            w = self.param(
                "kernel",
                nn.initializers.lecun_normal(),
                (3, 3, 1, in_ch),
                jnp.float32,
            ).astype(self.dtype)
            x = _depthwise3x3_shift(x.astype(self.dtype), w, self.stride)
        else:
            x = nn.Conv(
                self.features,
                kernel_size=self.kernel,
                strides=(self.stride, self.stride),
                padding="SAME",
                feature_group_count=self.groups,
                use_bias=False,
                dtype=self.dtype,
            )(x)
        if self.norm == "batch":
            x = FrozenBatchNorm(dtype=self.dtype)(x)
        elif self.norm == "group":
            if self.gn_impl == "onepass":
                x = _OnePassGroupNorm(dtype=self.dtype)(x)
            else:
                x = nn.GroupNorm(num_groups=None, group_size=8,
                                 dtype=self.dtype)(x)
        else:  # validate here too: the module classes are public
            raise ValueError(f"norm must be 'group' or 'batch', got {self.norm!r}")
        return nn.relu6(x) if self.act else x


class InvertedResidual(nn.Module):
    """expand 1x1 -> depthwise 3x3 -> project 1x1, residual when shapes match."""

    out_ch: int
    stride: int = 1
    expand: int = 6
    norm: str = "group"
    dtype: Any = jnp.float32
    depthwise_impl: str = "conv"
    gn_impl: str = "flax"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        in_ch = x.shape[-1]
        h = x
        if self.expand != 1:
            h = _ConvNorm(in_ch * self.expand, norm=self.norm,
                          dtype=self.dtype, gn_impl=self.gn_impl)(h)
        h = _ConvNorm(
            h.shape[-1],
            kernel=(3, 3),
            stride=self.stride,
            groups=h.shape[-1],
            norm=self.norm,
            dtype=self.dtype,
            depthwise_impl=self.depthwise_impl,
            gn_impl=self.gn_impl,
        )(h)
        h = _ConvNorm(self.out_ch, act=False, norm=self.norm,
                      dtype=self.dtype, gn_impl=self.gn_impl)(h)
        if self.stride == 1 and in_ch == self.out_ch:
            h = h + x
        return h


class MobileNetV2(nn.Module):
    classes: int = 1000
    width: float = 1.0
    schedule: Sequence[Tuple[int, int, int, int]] = V2_SCHEDULE
    norm: str = "group"
    dtype: Any = jnp.float32
    depthwise_impl: str = "conv"
    gn_impl: str = "flax"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = x.astype(self.dtype)
        x = _ConvNorm(
            _make_divisible(32 * self.width), kernel=(3, 3), stride=2,
            norm=self.norm, dtype=self.dtype, gn_impl=self.gn_impl
        )(x)
        for t, c, n, s in self.schedule:
            out_ch = _make_divisible(c * self.width)
            for i in range(n):
                x = InvertedResidual(
                    out_ch,
                    stride=s if i == 0 else 1,
                    expand=t,
                    norm=self.norm,
                    dtype=self.dtype,
                    depthwise_impl=self.depthwise_impl,
                    gn_impl=self.gn_impl,
                )(x)
        head = _make_divisible(1280 * max(1.0, self.width))
        x = _ConvNorm(head, norm=self.norm, dtype=self.dtype,
                      gn_impl=self.gn_impl)(x)
        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.classes, dtype=self.dtype)(x)
        return x


def mobilenet_v2(
    image_size: int = 224,
    classes: int = 1000,
    width: float = 1.0,
    norm: str = "group",
    dtype: Any = jnp.float32,
    depthwise_impl: str = "conv",
    gn_impl: str = "flax",
) -> ModelSpec:
    """The ImageNet-subset stretch model (sync-SGD).

    ``norm="group"`` (default) trains from scratch; ``norm="batch"`` is the
    canonical-checkpoint-compatible frozen-BatchNorm variant (see
    :class:`FrozenBatchNorm`).
    """
    if norm not in ("group", "batch"):
        raise ValueError(f"norm must be 'group' or 'batch', got {norm!r}")
    if depthwise_impl not in ("conv", "shift", "fused"):
        raise ValueError(
            "depthwise_impl must be 'conv', 'shift' or 'fused', "
            f"got {depthwise_impl!r}")
    if depthwise_impl == "fused" and norm != "group":
        raise ValueError(
            "depthwise_impl='fused' fuses GroupNorm into the kernel and "
            f"requires norm='group', got norm={norm!r}")
    if depthwise_impl == "fused":
        from distriflow_tpu.ops import default_interpret
        from distriflow_tpu.ops.depthwise_gn import MOSAIC_REFUSAL

        if not default_interpret():
            # say so here, with the compiler's message, instead of minutes
            # later inside a step compile — and never by quietly taking
            # the shift path
            raise NotImplementedError(MOSAIC_REFUSAL)
    if gn_impl not in ("flax", "onepass"):
        raise ValueError(f"gn_impl must be 'flax' or 'onepass', got {gn_impl!r}")
    return spec_from_flax(
        MobileNetV2(classes=classes, width=width, norm=norm, dtype=dtype,
                    depthwise_impl=depthwise_impl, gn_impl=gn_impl),
        input_shape=(image_size, image_size, 3),
        output_shape=(classes,),
        name="mobilenet_v2",
    )
