"""Fused depthwise-3x3 + GroupNorm kernel tests (interpret mode, round 18).

Oracle: the UNFUSED reference composition — shift-MACs then one-pass
GroupNorm, the exact math ``models/mobilenet.py`` runs for gated shapes —
**under jit**. The jit matters: the fused kernel matches the jitted
reference BITWISE in f32; the eager reference differs at ~1e-6 because
XLA's eager mode skips the FMA contraction jit applies, so comparing
against eager would test XLA's fusion heuristics, not the kernel.

Also pins the tile-floor gating (flash_decode's MIN_BLOCK_K pattern), the
exact FLOP tally of the new kernel, and the PR 1 warm-trace-cache
recovery protocol for ``pallas_cost_of``.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.ops.depthwise_gn import (
    GROUP_SIZE,
    MIN_CHANNELS,
    _channel_block,
    _same_pads,
    _warned_gated,
    depthwise3x3_groupnorm,
    depthwise_gn_supported,
)
from distriflow_tpu.ops.flop_count import pallas_cost_of

pytestmark = pytest.mark.kernels


def _args(b=2, h=8, w=8, c=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (b, h, w, c), dtype)
    kern = jax.random.normal(ks[1], (3, 3, 1, c), dtype)
    scale = jax.random.normal(ks[2], (c,), jnp.float32) * 0.1 + 1.0
    bias = jax.random.normal(ks[3], (c,), jnp.float32) * 0.1
    return x, kern, scale, bias


def _reference(x, w, scale, bias, stride=1, eps=1e-6, relu6=True):
    """Whole-batch unfused composition mirroring _tile_fwd term-for-term."""
    b, h, wd, c = x.shape
    ph, pw = _same_pads(h, stride), _same_pads(wd, stride)
    xp = jnp.pad(x, ((0, 0), ph, pw, (0, 0)))
    oh = (h + sum(ph) - 3) // stride + 1
    ow = (wd + sum(pw) - 3) // stride + 1
    wsq = w.reshape(3, 3, c)
    acc = None
    for ky in range(3):
        for kx in range(3):
            sl = jax.lax.slice(
                xp,
                (0, ky, kx, 0),
                (b, ky + (oh - 1) * stride + 1,
                 kx + (ow - 1) * stride + 1, c),
                (1, stride, stride, 1),
            )
            term = sl * wsq[ky, kx]
            acc = term if acc is None else acc + term
    xg = acc.reshape(b, oh * ow, c // GROUP_SIZE, GROUP_SIZE).astype(
        jnp.float32
    )
    m = xg.mean(axis=(1, 3), keepdims=True)
    m2 = (xg * xg).mean(axis=(1, 3), keepdims=True)
    inv = jax.lax.rsqrt(jnp.maximum(m2 - m * m, 0.0) + eps)
    y = ((xg - m) * inv).reshape(b, oh, ow, c)
    y = (y * scale.reshape(1, c).astype(jnp.float32)
         + bias.reshape(1, c).astype(jnp.float32)).astype(x.dtype)
    if relu6:
        y = jnp.minimum(jnp.maximum(y, 0.0), 6.0)
    return y


@pytest.mark.parametrize("stride,h,w", [(1, 8, 8), (2, 8, 8), (2, 9, 7)])
def test_forward_bitwise_vs_jitted_reference(stride, h, w):
    """f32 forward is BITWISE equal to the jitted unfused composition —
    including stride 2 at both spatial parities (the SAME-pad split
    differs for odd vs even dims)."""
    x, kern, scale, bias = _args(h=h, w=w)
    out = depthwise3x3_groupnorm(x, kern, scale, bias, stride,
                                 1e-6, 8, True, True)
    ref = jax.jit(lambda *a: _reference(*a, stride=stride))(
        x, kern, scale, bias)
    assert out.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_forward_multiple_channel_blocks():
    """c > 512 splits into channel blocks; groups never straddle a block
    boundary so the statistics stay exact (and bitwise)."""
    x, kern, scale, bias = _args(b=1, h=4, w=4, c=1024)
    assert _channel_block(1024) == 512  # actually exercises 2 grid blocks
    out = depthwise3x3_groupnorm(x, kern, scale, bias, 1, 1e-6, 8, True, True)
    ref = jax.jit(_reference)(x, kern, scale, bias)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_forward_bf16_and_no_relu6():
    x, kern, scale, bias = _args(dtype=jnp.bfloat16)
    out = depthwise3x3_groupnorm(x, kern, scale, bias, 1, 1e-6, 8, False,
                                 True)
    assert out.dtype == jnp.bfloat16
    ref = jax.jit(lambda *a: _reference(*a, relu6=False))(
        x, kern, scale, bias)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2)


@pytest.mark.parametrize("stride", [1, 2])
def test_grads_match_reference(stride):
    """dx/dw/dscale/dbias against jax.grad of the jitted reference. dx is
    per-tile (same summation structure -> tight); dw/dscale/dbias cross
    the per-batch-partial reduction, whose summation ORDER differs from
    whole-batch autodiff — allclose, not bitwise."""
    x, kern, scale, bias = _args(h=6, w=6)

    def f_fused(*a):
        return jnp.sum(
            depthwise3x3_groupnorm(*a, stride, 1e-6, 8, True, True) ** 2)

    def f_ref(*a):
        return jnp.sum(_reference(*a, stride=stride) ** 2)

    g_fused = jax.grad(f_fused, argnums=(0, 1, 2, 3))(x, kern, scale, bias)
    g_ref = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2, 3)))(
        x, kern, scale, bias)
    for a, b in zip(g_fused, g_ref):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_tile_floor_gating():
    """flash_decode's MIN_BLOCK_K pattern: sliver/misaligned/oversized
    shapes are gated off analytically (counter + warn-once), never run
    slow."""
    from distriflow_tpu.obs import get_telemetry

    assert MIN_CHANNELS >= GROUP_SIZE
    assert depthwise_gn_supported(8, 8, 16)
    assert depthwise_gn_supported(9, 7, 8, stride=2)

    counter = get_telemetry().counter(
        "ops_depthwise_gn_gated_total",
        help="depthwise+GN shapes gated off the fused kernel")
    before = counter.value
    _warned_gated.discard((8, 8, 4, 1))  # test-order independence
    with pytest.warns(UserWarning, match="gated off"):
        assert not depthwise_gn_supported(8, 8, 4)  # below the sliver floor
    assert counter.value == before + 1
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second occurrence: counted, silent
        assert not depthwise_gn_supported(8, 8, 4)
    assert counter.value == before + 2

    _warned_gated.discard((8, 8, 20, 1))
    with pytest.warns(UserWarning):
        assert not depthwise_gn_supported(8, 8, 20)  # not a group multiple
    _warned_gated.discard((8, 8, 16, 3))
    with pytest.warns(UserWarning):
        assert not depthwise_gn_supported(8, 8, 16, stride=3)
    _warned_gated.discard((512, 512, 512, 1))
    with pytest.warns(UserWarning):  # full-spatial tile would blow VMEM
        assert not depthwise_gn_supported(512, 512, 512)


def test_channel_block_rules():
    assert _channel_block(16) == 16
    assert _channel_block(512) == 512
    assert _channel_block(1024) == 512  # largest multiple-of-128 divisor
    assert _channel_block(576) == 576  # no such divisor: full C (VMEM-gated)


def test_flop_tally_exact():
    """The tally is an exact analytic count: 28 flops/output element
    forward, 2x model / 3x hardware (remat) backward, one rsqrt per
    (batch, group)."""
    b, h, w, c = 2, 8, 8, 16
    x, kern, scale, bias = _args(b=b, h=h, w=w, c=c)

    def f(*a):
        return jnp.sum(depthwise3x3_groupnorm(*a, 1, 1e-6, 8, True, True))

    tally = pallas_cost_of(jax.value_and_grad(f), x, kern, scale, bias)
    fwd = 28 * b * h * w * c  # stride 1: oh == h, ow == w
    cat = tally["by_category"]["depthwise_gn"]
    assert cat["flops"] == fwd + 2 * fwd  # fwd trace + bwd trace
    assert cat["hw_flops"] == fwd + 3 * fwd  # bwd re-runs the forward tile
    assert cat["transcendentals"] == 2 * b * (c // GROUP_SIZE)
    assert tally["flops"] == cat["flops"]  # no other kernels in the program


def test_warm_trace_cache_recovery():
    """PR 1 regression, round-18 edition: a warm trace cache can replay
    memoized jaxprs and skip the Python kernel wrappers, zeroing a tally
    for a program KNOWN to contain Pallas calls. Pins the documented
    recovery protocol (pallas_cost_of docstring, the exact sequence
    SyncTrainer.cost_analysis automates): clear_caches + retrace yields
    the true tally."""
    x, kern, scale, bias = _args(b=1, h=4, w=4, c=8)

    def f(*a):
        return jnp.sum(depthwise3x3_groupnorm(*a, 1, 1e-6, 8, True, True))

    jax.clear_caches()
    cold = pallas_cost_of(jax.value_and_grad(f), x, kern, scale, bias)
    assert cold["flops"] > 0

    # heat every cache layer a real trainer would: execute the program
    jax.jit(jax.value_and_grad(f))(x, kern, scale, bias)
    warm = pallas_cost_of(jax.value_and_grad(f), x, kern, scale, bias)
    if warm["flops"] == 0.0:  # the warm-cache symptom — recover, re-tally
        jax.clear_caches()
        warm = pallas_cost_of(jax.value_and_grad(f), x, kern, scale, bias)
    assert warm["flops"] == cold["flops"]
    assert warm["hw_flops"] == cold["hw_flops"]


def test_mobilenet_fused_block_matches_gated_fallback(monkeypatch):
    """models/mobilenet.py wiring: the fused branch and its gated fallback
    (shift-MACs + one-pass affine GN) share one param structure and the
    same math — forcing the gate off must not change the numbers beyond
    jit-vs-composition noise."""
    import distriflow_tpu.models.mobilenet as mm
    import distriflow_tpu.ops.depthwise_gn as dg

    mod = mm._ConvNorm(features=16, kernel=(3, 3), stride=2, groups=16,
                       norm="group", act=True, depthwise_impl="fused")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 9, 16), jnp.float32)
    params = mod.init(jax.random.PRNGKey(1), x)
    fused = mod.apply(params, x)
    monkeypatch.setattr(dg, "depthwise_gn_supported", lambda *a, **k: False)
    fallback = mod.apply(params, x)
    assert fused.shape == fallback.shape
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(fallback), atol=5e-6)


def test_fused_refuses_to_build_where_kernels_are_compiled(monkeypatch):
    """On a TPU backend Mosaic refuses this kernel (chip run, PR 21): the
    spec builder must say so with the compiler's message, not fall back."""
    import distriflow_tpu.ops as ops
    from distriflow_tpu.models.mobilenet import mobilenet_v2

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    with pytest.raises(NotImplementedError, match="Mosaic failed to compile"):
        mobilenet_v2(image_size=32, classes=10, depthwise_impl="fused")
    # the other implementations are untouched by the guard
    mobilenet_v2(image_size=32, classes=10, depthwise_impl="shift")
