"""Flash attention: fused online-softmax attention as Pallas TPU kernels.

The [S, S] score matrix never hits HBM — forward OR backward:

- **Forward**: each grid step holds one Q block and one K/V block in VMEM and
  advances the flash recurrence (running max ``m``, running normalizer ``l``,
  unnormalized accumulator ``acc``) — the same recurrence as the pure-JAX
  ``blockwise_attention`` (``distriflow_tpu/parallel/ring_attention.py``),
  which is this kernel's correctness oracle. The per-row logsumexp is written
  out as a residual.
- **Backward**: ONE fused kernel over the saved (q, k, v, o, lse) —
  probabilities are recomputed per tile as ``exp(s - lse)`` (no second
  softmax pass), and with ``delta = rowsum(do * o)`` the score gradient is
  the closed form ``ds = p * (dp - delta)``. The fused kernel materializes
  P **once per tile pair** and produces dK/dV (accumulated over Q tiles in
  VMEM scratch) and per-KV-block dQ partials (reduced outside the kernel)
  in the same sweep: 5 matmuls + 1 exp per tile pair, versus 7 matmuls +
  2 exps for the pre-round-18 two-kernel layout that recomputed S and P
  independently for dQ and for dK/dV. The dQ partials cost ``n_kv`` f32
  copies of Q in HBM, so the fused path is gated to small KV-block counts
  (``_FUSED_BWD_MAX_KV_BLOCKS``); long-context shapes keep the two-kernel
  layout, whose VMEM and HBM stay O(block · D).

Backward tiles no longer inherit the forward's: the backward's arithmetic
intensity is different (5 matmuls + dq-partial traffic per tile pair) and
is autotuned per dtype/shape by :func:`_bwd_autotune` — callers can still
pin ``bwd_block_q``/``bwd_block_k`` explicitly. ``bwd_compute_dtype``
optionally runs the backward matmuls in a narrower dtype (bf16) with f32
accumulators — opt-in, because the default must preserve the documented
f32 gradient tolerances (tests/test_ops.py pins atol 3e-5 at f32).

Grids put batch*head and the output-tile axis in parallel dimensions (Mosaic
runs them concurrently) and the reduction axis innermost-sequential (VMEM
scratch persists across it). Causal masking predicates away fully-masked
tiles (~half the compute each direction).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distriflow_tpu.ops.flop_count import record_pallas_cost


def _aligned_block(s: int, target: int) -> int:
    """Largest SUBLANE-ALIGNED (multiple-of-8) divisor of ``s`` that is
    ``<= target``, or ``s`` itself when it fits in one block — Mosaic
    requires block dims divisible by 8 or equal to the array dim.
    ring_attention's ``_auto_block`` (any divisor) is fine for its pure-XLA
    blockwise path but produced e.g. 1022 for a 32,704-token prompt here,
    which the Pallas lowering rejects (round-5 32k-context prefill)."""
    if s <= target:
        return s
    for blk in range((target // 8) * 8, 0, -8):
        if s % blk == 0:
            return blk
    # s > target with no aligned divisor (s itself not a multiple of 8):
    # one whole-length block is the only Mosaic-legal tiling left
    return s


def flash_seq_supported(s: int, d: int, itemsize: int = 2,
                        target: int = 1024) -> bool:
    """True when the forward kernel can tile length ``s`` within VMEM.

    Crooked lengths with no sublane-aligned divisor fall back to ONE
    whole-length block — legal, but its q/k/v/o blocks plus the
    ``(block_q, 128)`` f32 m/l/acc scratch scale linearly with ``s`` and
    blow the ~16 MB scoped-VMEM budget somewhere around s~9k at D=64
    (e.g. a 32,700-token prompt would need ~50 MB of scratch alone).
    Callers with arbitrary sequence lengths (the decode-mode prefill)
    consult this gate and use the pure-XLA blockwise path instead of
    crashing in the Mosaic compiler."""
    bq = _aligned_block(s, target)
    est = 3 * bq * 128 * 4 + 4 * bq * d * itemsize  # m/l/acc + q/k/v/o
    return int(est * 1.2) <= 16 * 1024 * 1024


NEG_INF = -1e30
_LANES = 128  # f32 tile width; m/l scratch is replicated across lanes


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, block_q, block_k, n_kv, causal, scale):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate():
        # matmuls run in the INPUT dtype with fp32 accumulation
        # (preferred_element_type): on bf16 inputs that is the MXU's native
        # mode — an fp32 pre-cast would force emulated fp32 matmuls at a
        # fraction of peak (measured 7x slower end-to-end on v5e). The
        # softmax/correction math stays fp32.
        q = q_ref[0]  # [block_q, D]
        k_blk = k_ref[0]  # [block_k, D]
        v_blk = v_ref[0]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k] f32 (scale folded after the dot)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m = m_ref[:, :1]  # [block_q, 1] (lane-replicated store)
        l = l_ref[:, :1]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        safe_m = jnp.where(new_m <= NEG_INF, 0.0, new_m)
        p = jnp.exp(s - safe_m)
        p = jnp.where(s <= NEG_INF, 0.0, p)
        corr = jnp.where(
            m <= NEG_INF, 0.0, jnp.exp(jnp.where(m <= NEG_INF, 0.0, m) - safe_m)
        )
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p in the v dtype (bf16 on MXU), fp32 accumulate — standard FA
        m_ref[:] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(new_l, l_ref.shape)

    if causal:
        # K blocks fully past this Q block's last row are fully masked — skip
        # the compute (their DMA is pipelined regardless)
        @pl.when(kb * block_k < (qi + 1) * block_q)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(kb == n_kv - 1)
    def _finalize():
        l_final = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_final).astype(o_ref.dtype)
        # logsumexp residual for the backward kernels: m + log(l) — the lse
        # of the SCALED scores (scale folds in right after the qk dot)
        safe_m = jnp.where(m_ref[:, :1] <= NEG_INF, 0.0, m_ref[:, :1])
        # lane-replicated store (TPU blocks need a 128-multiple last dim)
        lse_ref[0] = jnp.broadcast_to(safe_m + jnp.log(l_final), lse_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, block_q, block_k, n_kv, causal, scale):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _accumulate():
        # native-dtype matmuls + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # masked: exp(NEG_INF - lse) = 0
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        ds = p * (dp - delta_ref[0][:, :1])
        acc_ref[:] = acc_ref[:] + lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(kb * block_k < (qi + 1) * block_q)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(kb == n_kv - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, block_q, block_k, n_q, causal, scale):
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _accumulate():
        # native-dtype matmuls + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do -> [block_k, D]
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1])
        # q is UNSCALED here (scale folds after the qk dot), so dk needs
        # the explicit scale at finalize: dk = scale * ds^T @ q
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T @ q -> [block_k, D]

    if causal:
        # Q blocks entirely before this K block see none of it
        @pl.when((qi + 1) * block_q > kb * block_k)
        def _():
            _accumulate()
    else:
        _accumulate()

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _dkvq_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dqp_ref, dk_acc, dv_acc,
                 *, block_q, block_k, n_q, causal, scale):
    """Fused backward: dK, dV AND dQ partials in one sweep.

    The two-kernel layout pays the score recompute twice — _dq_kernel and
    _dkv_kernel each rebuild s and p for every tile pair (7 matmuls + 2
    exps per pair). Here P is materialized ONCE per pair and feeds all
    three gradients: 5 matmuls + 1 exp. The catch is the Pallas revisit
    rule — an output block may be written by only one grid slice — and dq
    accumulates over the K axis while dk/dv accumulate over Q. Resolution:
    dk/dv keep the VMEM-scratch recurrence over the innermost-sequential Q
    axis; dq is emitted as PER-KV-BLOCK f32 partials into a
    ``[n_kv, BH, S, D]`` output where each (kv-block, q-block) pair owns a
    unique write-once block, and the cheap cross-KV sum runs outside the
    kernel as ordinary XLA.
    """
    kb = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _tile():
        # native-dtype matmuls + fp32 accumulation (see _fwd_kernel note)
        q = q_ref[0]
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        do = do_ref[0]
        s = lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # the one P per tile pair
        dv_acc[:] = dv_acc[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # p^T @ do -> [block_k, D]
        dp = lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0][:, :1])
        ds_lo = ds.astype(q.dtype)
        # q/k are UNSCALED here (scale folds after the qk dot): dk and dq
        # both carry the explicit scale — dk at finalize, dq in the
        # outside-the-kernel reduction
        dk_acc[:] = dk_acc[:] + lax.dot_general(
            ds_lo, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds^T @ q -> [block_k, D]
        dqp_ref[0, 0] = lax.dot_general(
            ds_lo, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # ds @ k -> [block_q, D] f32 partial

    if causal:
        live = (qi + 1) * block_q > kb * block_k

        @pl.when(live)
        def _():
            _tile()

        # Pallas does NOT zero-init output blocks: a fully-masked pair still
        # owns its dq-partial block and must write the zeros itself, or the
        # outside reduction sums garbage
        @pl.when(jnp.logical_not(live))
        def _():
            dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])
    else:
        _tile()

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _resolve_interpret(interpret):
    if interpret is None:
        from distriflow_tpu.ops import default_interpret

        return default_interpret()
    return interpret


def _flash_forward(q, k, v, causal, block_q, block_k, interpret):
    interpret = _resolve_interpret(interpret)
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    fwd_cap, _ = _block_caps(q.dtype)
    bq = _aligned_block(s, min(block_q, fwd_cap))
    bk = _aligned_block(s, min(block_k, fwd_cap))
    n_q, n_kv = s // bq, s // bk

    # model FLOPs: QK^T + PV, each 2*B*H*S*S*D, halved by causal tile-skip —
    # mirrored into the trace-time tally so mfu() counts custom-call work
    # (XLA's cost analysis reports 0 for custom calls)
    record_pallas_cost(
        flops=4 * b * h * s * s * d // (2 if causal else 1),
        bytes_accessed=4 * b * h * s * d * q.dtype.itemsize,
        transcendentals=b * h * s * s // (2 if causal else 1),
        category="attention_fwd",
    )

    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)

    kernel = functools.partial(
        _fwd_kernel, block_q=bq, block_k=bk, n_kv=n_kv, causal=causal, scale=scale
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            # lane-replicated residual (jax flash-attention convention: TPU
            # output blocks need a 128-multiple last dim). Costs 128x the
            # minimal [BH, S] residual — 0.5 KB/position of f32 — a deliberate
            # trade against per-tile transposes in the backward reads.
            jax.ShapeDtypeStruct((b * h, s, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((bq, _LANES), jnp.float32),  # l
            pltpu.VMEM((bq, d), jnp.float32),  # acc
        ],
        interpret=interpret,
        # batch*head and Q-block axes are independent -> parallel; only the
        # K axis is a sequential reduction (the scratch recurrence)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s * s * d // (2 if causal else 1),
            bytes_accessed=4 * b * h * s * d * q.dtype.itemsize,
            transcendentals=b * h * s * s,
        ),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d), lse  # lse stays [B*H, S, LANES]


# Backward block cap, PER INPUT DTYPE. Round-2 tuning on fp32 measured 512-
# wide backward tiles spilling scoped VMEM (10x slowdown) — fp32 keeps the
# 256 cap. Re-measured round 3 on bf16 at the flagship shapes, the cost
# structure is the OPPOSITE: the kernel is grid-step-overhead-bound, and
# larger tiles win big — B8/H8/S1k/D64 fwd+bwd 2.75 ms @ 256 blocks vs
# 0.63 ms @ 1024 blocks; B2/H8/S4k/D64 6.48 ms vs 0.94 ms (55% of peak).
# 2048-wide tiles fail to compile (scoped VMEM), so 1024 is the bf16 ceiling.
_BWD_BLOCK_CAP = 1024       # <=2-byte input dtypes (bf16/fp16)
_BWD_BLOCK_CAP_WIDE = 256   # 4-byte inputs (f32): VMEM holds double the bytes
_FWD_BLOCK_CAP_WIDE = 512   # f32 forward: half the bf16 tile budget


def _block_caps(dtype):
    """(fwd_cap, bwd_cap) for the input dtype — see _BWD_BLOCK_CAP note."""
    if jnp.dtype(dtype).itemsize <= 2:
        return 1024, _BWD_BLOCK_CAP
    return _FWD_BLOCK_CAP_WIDE, _BWD_BLOCK_CAP_WIDE


# The fused backward's dq partials cost n_kv f32 copies of Q in HBM
# (written once, read once by the outside reduction). At the training
# shapes n_kv is 1-2 and the traffic is noise next to the saved score
# recompute; at 32k context with 1024-wide KV tiles it would be 32x Q in
# f32 — past this many KV blocks the backward falls back to the two-kernel
# layout, which stays O(block * D) in both VMEM and HBM.
_FUSED_BWD_MAX_KV_BLOCKS = 8

# Autotune budget: half the ~16 MB scoped-VMEM window, leaving headroom for
# Mosaic's pipelining (double-buffered input blocks) that the analytic
# estimate below does not model.
_BWD_VMEM_BUDGET = 8 * 1024 * 1024


def _bwd_vmem_estimate(bq, bk, d, itemsize):
    """Analytic per-grid-step VMEM working set of the fused backward."""
    est = 2 * bq * d * itemsize + 2 * bk * d * itemsize  # q/do + k/v blocks
    est += 2 * bq * _LANES * 4                           # lse + delta
    est += 2 * bk * d * 4                                # dk/dv accumulators
    est += bq * d * 4                                    # dq-partial out block
    est += bq * bk * 4                                   # f32 score tile
    return est


def _bwd_autotune(s, d, compute_dtype):
    """Backward tile pick — the backward no longer inherits forward tiles.

    Its arithmetic intensity differs from the forward's (5 matmuls + dq
    partial traffic per tile pair vs 2 matmuls), so the right tile is
    chosen here: the largest sublane-aligned divisor of ``s`` under the
    measured per-dtype cap whose working set passes the VMEM model. The
    measured caps remain HARD ceilings, not starting points the model may
    override upward: the analytic estimate is optimistic exactly where it
    hurt before — round 2's 512-wide f32 tiles passed a naive byte count
    yet spilled scoped VMEM for a real 10x cliff (_BWD_BLOCK_CAP note).
    """
    _, cap = _block_caps(compute_dtype)
    itemsize = jnp.dtype(compute_dtype).itemsize
    target = cap
    while target > 8:
        bq = _aligned_block(s, target)
        bk = _aligned_block(s, target)
        if _bwd_vmem_estimate(bq, bk, d, itemsize) <= _BWD_VMEM_BUDGET:
            return bq, bk
        target //= 2
    return _aligned_block(s, 8), _aligned_block(s, 8)


def _flash_backward(q, k, v, o, lse, do, causal, block_q, block_k, interpret,
                    g_lse=None, bwd_block_q=None, bwd_block_k=None,
                    bwd_compute_dtype=None):
    interpret = _resolve_interpret(interpret)
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)

    # opt-in reduced-precision backward: matmul OPERANDS drop to
    # compute_dtype (bf16 -> native MXU mode + half the block bytes, so the
    # bf16 tile caps apply), accumulators and the softmax/ds math stay f32,
    # and the returned gradients are cast back to the input dtypes. Off by
    # default — f32 inputs keep f32 operands so the documented 3e-5
    # gradient tolerance is undisturbed.
    in_dtype = q.dtype
    compute_dtype = in_dtype if bwd_compute_dtype is None else jnp.dtype(
        bwd_compute_dtype
    )

    _, bwd_cap = _block_caps(compute_dtype)
    auto_q, auto_k = _bwd_autotune(s, d, compute_dtype)
    bq = auto_q if bwd_block_q is None else _aligned_block(
        s, min(bwd_block_q, bwd_cap)
    )
    bk = auto_k if bwd_block_k is None else _aligned_block(
        s, min(bwd_block_k, bwd_cap)
    )
    n_q, n_kv = s // bq, s // bk
    fused = n_kv <= _FUSED_BWD_MAX_KV_BLOCKS

    # model FLOPs of the attention backward: dV = P^T dO, dP = dO V^T,
    # dQ = dS K, dK = dS^T Q — four matmuls, 8*B*H*S*S*D (2x forward). The
    # kernels ALSO recompute the scores, but that is remat overhead,
    # excluded from MFU by convention (see ops/flop_count.py docstring);
    # it IS counted in hw_flops, the work the kernel executes: the fused kernel runs 5 matmuls per tile pair, the two-kernel
    # fallback 7 (s and dp each computed twice).
    causal_div = 2 if causal else 1
    matmul_unit = 2 * b * h * s * s * d // causal_div
    record_pallas_cost(
        flops=4 * matmul_unit,
        bytes_accessed=(
            8 * b * h * s * d * compute_dtype.itemsize
            + (2 * n_kv * b * h * s * d * 4 if fused else 0)
        ),
        transcendentals=(1 if fused else 2) * b * h * s * s // causal_div,
        category="attention_bwd",
        hw_flops=(5 if fused else 7) * matmul_unit,
    )

    # delta_i = rowsum(do_i * o_i): one cheap fused elementwise pass; makes
    # ds = p * (dp - delta) local to each tile (the flash backward identity).
    # Lane-replicated to match the lse layout (TPU block constraint).
    delta_rows = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(b * h, s)
    if g_lse is not None:
        # an lse cotangent folds into delta: dlse/ds_ij = p_ij, so the score
        # gradient becomes ds = p * (dp - (delta - g_lse))
        delta_rows = delta_rows - g_lse.astype(jnp.float32).reshape(b * h, s)
    delta = jnp.broadcast_to(delta_rows[:, :, None], (b * h, s, _LANES))

    qf = q.reshape(b * h, s, d).astype(compute_dtype)
    kf = k.reshape(b * h, s, d).astype(compute_dtype)
    vf = v.reshape(b * h, s, d).astype(compute_dtype)
    dof = do.reshape(b * h, s, d).astype(compute_dtype)
    lsef = lse  # already [B*H, S, LANES]
    shape = (b, h, s, d)

    if fused:
        dk, dv, dqp = pl.pallas_call(
            functools.partial(
                _dkvq_kernel, block_q=bq, block_k=bk, n_q=n_q, causal=causal,
                scale=scale,
            ),
            name="flash_attention_bwd_fused",
            grid=(b * h, n_kv, n_q),
            in_specs=[
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bq, _LANES), lambda bh, j, i: (bh, i, 0)),
                pl.BlockSpec((1, bq, _LANES), lambda bh, j, i: (bh, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
                # dq partials: the KV-block axis leads so each (j, i) pair
                # owns a unique write-once block (Pallas revisit rule)
                pl.BlockSpec((1, 1, bq, d), lambda bh, j, i: (j, bh, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
                jax.ShapeDtypeStruct((n_kv, b * h, s, d), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),  # dk accumulator
                pltpu.VMEM((bk, d), jnp.float32),  # dv accumulator
            ],
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
        )(kf, vf, qf, dof, lsef, delta)
        dq = (jnp.sum(dqp, axis=0) * scale).astype(in_dtype)
        return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=bq, block_k=bk, n_kv=n_kv, causal=causal,
            scale=scale,
        ),
        name="flash_attention_bwd_dq",
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(qf, kf, vf, dof, lsef, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=bq, block_k=bk, n_q=n_q, causal=causal,
            scale=scale,
        ),
        name="flash_attention_bwd_dkv",
        grid=(b * h, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, j, i: (bh, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda bh, j, i: (bh, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),  # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),  # dv accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(kf, vf, qf, dof, lsef, delta)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 1024,  # v5e bf16 optimum (see _BWD_BLOCK_CAP note): the
    block_k: int = 1024,  # kernel is grid-overhead-bound, so max out tiles;
    # causal tile-skipping still operates at tile granularity for S > 1024
    interpret: Optional[bool] = None,
    # backward tiles are autotuned (see _bwd_autotune) unless pinned here;
    # forward block_q/block_k no longer flow into the backward
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    # opt-in reduced-precision backward (e.g. jnp.bfloat16): matmul operands
    # in this dtype, f32 accumulators, gradients cast back to input dtype
    bwd_compute_dtype: Optional[jnp.dtype] = None,
) -> jnp.ndarray:
    """Fused attention over ``[B, H, S, D]`` tensors.

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere.
    """
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _fwd(q, k, v, causal, block_q, block_k, interpret,
         bwd_block_q, bwd_block_k, bwd_compute_dtype):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_with_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    bwd_compute_dtype: Optional[jnp.dtype] = None,
):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    ``[B, H, S]`` (f32) — the residual that lets partial attentions over
    K/V chunks merge exactly (ring attention, sequence parallelism). Fully
    differentiable including through the lse output."""
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    b, h, s, _ = q.shape
    return out, lse[..., 0].reshape(b, h, s)


def _fwd_with_lse(q, k, v, causal, block_q, block_k, interpret,
                  bwd_block_q, bwd_block_k, bwd_compute_dtype):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k, interpret)
    b, h, s, _ = q.shape
    return (out, lse[..., 0].reshape(b, h, s)), (q, k, v, out, lse)


def _bwd_with_lse(causal, block_q, block_k, interpret, bwd_block_q,
                  bwd_block_k, bwd_compute_dtype, res, g):
    q, k, v, o, lse = res
    do, g_lse = g
    return _flash_backward(
        q, k, v, o, lse, do, causal, block_q, block_k, interpret,
        g_lse=g_lse, bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
        bwd_compute_dtype=bwd_compute_dtype,
    )


flash_attention_with_lse.defvjp(_fwd_with_lse, _bwd_with_lse)


def _bwd(causal, block_q, block_k, interpret, bwd_block_q, bwd_block_k,
         bwd_compute_dtype, res, g):
    q, k, v, o, lse = res
    return _flash_backward(
        q, k, v, o, lse, g, causal, block_q, block_k, interpret,
        bwd_block_q=bwd_block_q, bwd_block_k=bwd_block_k,
        bwd_compute_dtype=bwd_compute_dtype,
    )


flash_attention.defvjp(_fwd, _bwd)


# -- GSPMD partitioning (inference forward) --------------------------------


@functools.lru_cache(maxsize=8)
def _sharded_fa(causal: bool, interpret: Optional[bool]):
    """custom_partitioning-wrapped forward for one (causal, interpret)
    signature. Attention is embarrassingly parallel over batch and heads;
    S and D stay replicated. Mirrors ops/flash_decode.py's heads-sharded
    rule — without it, a bare pallas_call under TP-sharded activations
    forces an all-gather and runs the whole prompt's attention replicated
    on every chip.

    Known not to work on real multi-chip TPUs with the installed stack (jax
    0.9.0 / libtpu 0.0.34): the partitioning callback never reaches the TPU
    compiler and the program fails with "Custom emitter for
    CustomSPMDPartitioning not found" (four v5e chips, PR 21). On one device
    the wrapper is transparent, and the CPU partitioner honours it, which is
    all the tests see. Multi-chip decode needs a shard_map form (ROADMAP
    R3/R7); ``ops/fused_ce.py`` shows one."""
    from jax.experimental.custom_partitioning import custom_partitioning
    from jax.sharding import NamedSharding, PartitionSpec as P

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=causal, interpret=interpret)

    wrapped = custom_partitioning(fn)

    def _bh_spec(mesh, arg_infos):
        spec = getattr(arg_infos[0].sharding, "spec", None) or P()
        b = spec[0] if len(spec) >= 1 else None
        hx = spec[1] if len(spec) >= 2 else None
        h_total = arg_infos[0].shape[1]
        deg = 1
        if hx is not None:
            names = (hx,) if isinstance(hx, str) else tuple(hx)
            for a in names:
                deg *= int(dict(mesh.shape)[a])
        if h_total % max(deg, 1):
            hx = None  # crooked head split: replicate heads instead
        return b, hx

    def infer(mesh, arg_infos, result_infos):
        b, hx = _bh_spec(mesh, arg_infos)
        return NamedSharding(mesh, P(b, hx, None, None))

    def partition(mesh, arg_infos, result_infos):
        b, hx = _bh_spec(mesh, arg_infos)
        sh = NamedSharding(mesh, P(b, hx, None, None))
        return mesh, fn, sh, (sh, sh, sh)

    wrapped.def_partition(
        partition=partition, infer_sharding_from_operands=infer,
        sharding_rule="b h s d, b h s d, b h s d -> b h s d")
    return wrapped


def flash_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`flash_attention` with a batch/heads-sharded GSPMD rule —
    a no-op on unsharded operands; under tensor/data parallelism each
    shard runs the kernel on its own batch rows and heads with no
    gather. Inference-only (no VJP through the wrapper): the training
    path uses shard_map via models/transformer.py instead."""
    return _sharded_fa(bool(causal), interpret)(q, k, v)
