"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

No reference equivalent — the reference has no attention or sequence models
at all (SURVEY.md §2.3) — but long-context is first-class here: sequences
longer than one device's memory are sharded over the ``seq`` axis, and
attention runs as a ring:

- each device holds its Q, K, V chunk ``[B, H, S/n, D]``;
- for ``n`` ring steps, every device computes blockwise attention of its Q
  chunk against the currently-held K/V chunk using an online-softmax
  accumulator (the flash-attention recurrence: running max ``m``, running
  normalizer ``l``, unnormalized output ``o``), then rotates K/V one hop
  around the ring via ``ppermute`` — compute overlaps the ICI transfer and
  full attention emerges without any device ever holding the full sequence;
- causal masking works on global positions: chunk offsets are derived from
  each device's ``seq``-axis index and the rotation step.

Also exported: :func:`blockwise_attention` (the single-device reference
implementation used for correctness tests and as the non-distributed path).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distriflow_tpu.parallel.collectives import pvary

NEG_INF = -1e30


def _attend_block(
    q: jnp.ndarray,  # [B, H, Sq, D]
    k: jnp.ndarray,  # [B, H, Sk, D]
    v: jnp.ndarray,  # [B, H, Sk, D]
    m: jnp.ndarray,  # [B, H, Sq]     running max
    l: jnp.ndarray,  # [B, H, Sq]     running normalizer
    o: jnp.ndarray,  # [B, H, Sq, D]  unnormalized output accumulator
    q_offset: jnp.ndarray,  # global position of q[...,0,:]
    k_offset: jnp.ndarray,
    causal: bool,
    scale: float,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One online-softmax accumulation step against a K/V block."""
    # preferred_element_type (not .astype after): the MXU natively emits f32
    # from bf16 operands, and the explicit f32 output dtype stops XLA's
    # bf16-propagation pass from truncating the scores inside the fused loop
    # — with .astype, that truncation made the masked-softmax backward NaN
    # at long sequence lengths under jit
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        q_pos = q_offset + jnp.arange(sq)[:, None]  # [Sq, 1]
        k_pos = k_offset + jnp.arange(sk)[None, :]  # [1, Sk]
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    block_max = jnp.max(s, axis=-1)  # [B, H, Sq]
    new_m = jnp.maximum(m, block_max)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) must not NaN
    safe_m = jnp.where(new_m <= NEG_INF, 0.0, new_m)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(s <= NEG_INF, 0.0, p)
    correction = jnp.exp(jnp.where(m <= NEG_INF, NEG_INF, m - safe_m))
    correction = jnp.where(m <= NEG_INF, 0.0, correction)
    new_l = l * correction + jnp.sum(p, axis=-1)
    new_o = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32
    )
    return new_m, new_l, new_o


def _auto_block(s: int, target: int = 512) -> int:
    """Largest divisor of ``s`` that is <= target (so any length works)."""
    for b in range(min(s, target), 0, -1):
        if s % b == 0:
            return b
    return s


def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_size: Optional[int] = None,
) -> jnp.ndarray:
    """Single-device online-softmax attention over K/V blocks.

    Numerically identical to dense softmax attention; memory is O(S·block)
    instead of O(S²). Inputs/outputs are ``[B, H, S, D]``.
    """
    b, h, s, d = q.shape
    scale = 1.0 / math.sqrt(d)
    block = block_size or _auto_block(s)
    if s % block:
        raise ValueError(f"sequence {s} not divisible by block {block}")
    n_blocks = s // block

    # derive the accumulators from q (not fresh zeros) so they inherit q's
    # varying-mesh-axes type: fresh literals would mismatch the scan carry
    # when this runs inside a shard_map body (e.g. the FedAvg local loop)
    zero = jnp.zeros_like(q, jnp.float32)
    m = zero[..., 0] + NEG_INF
    l = zero[..., 0]
    o = zero

    def body(i, carry):
        m, l, o = carry
        ks = lax.dynamic_slice_in_dim(k, i * block, block, axis=2)
        vs = lax.dynamic_slice_in_dim(v, i * block, block, axis=2)
        new_m, new_l, new_o = _attend_block(
            q, ks, vs, m, l, o,
            q_offset=jnp.int32(0),
            k_offset=i * block,
            causal=causal,
            scale=scale,
        )
        return new_m, new_l, new_o

    m, l, o = lax.fori_loop(0, n_blocks, body, (m, l, o))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def dense_attention(q, k, v, causal: bool = True) -> jnp.ndarray:
    """Plain softmax attention (correctness oracle for tests)."""
    d = q.shape[-1]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(d)
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    use_flash: Optional[bool] = None,
) -> jnp.ndarray:
    """Distributed attention over sequence shards on the ``axis`` ring.

    Inputs are GLOBAL arrays ``[B, H, S, D]`` (sharded or shardable over
    ``axis`` on dim 2); output is sharded the same way. Within shard_map each
    device loops ``n`` times: attend to the held K/V chunk, then ``ppermute``
    K/V to the next device.

    ``use_flash`` (None = auto: on for TPU) runs each chunk-vs-chunk
    attention as the Pallas flash kernel and merges the per-chunk partials
    through their logsumexp residuals — causal=True only, and only for the
    diagonal step (each device's own chunk); earlier chunks attend densely
    and later chunks merge with weight zero.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n:
        raise ValueError(f"sequence {q.shape[2]} not divisible by {axis} axis size {n}")
    chunk = q.shape[2] // n
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]
    names = mesh.axis_names
    vary_axes = tuple(
        a for a in ("data", "model", axis) if a in names
    )  # every axis the q/k/v shards vary over

    def local(qc, kc, vc):
        # qc/kc/vc: [B, H, chunk, D] — this device's shard
        my_index = lax.axis_index(axis)
        q_offset = my_index * chunk
        b, h, s, d = qc.shape
        m = jnp.full((b, h, s), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, s), jnp.float32)
        o = jnp.zeros((b, h, s, d), jnp.float32)
        # accumulators must enter the loop varying over every sharded axis,
        # or the carry types mismatch once they mix with the sharded chunks
        m, l, o = pvary((m, l, o), vary_axes)

        def body(step, carry):
            m, l, o, kc, vc = carry
            # after `step` rotations we hold the chunk originally on
            # device (my_index - step) mod n
            src = jnp.mod(my_index - step, n)
            new_m, new_l, new_o = _attend_block(
                qc, kc, vc, m, l, o,
                q_offset=q_offset,
                k_offset=src * chunk,
                causal=causal,
                scale=scale,
            )
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return new_m, new_l, new_o, kc, vc

        m, l, o, _, _ = lax.fori_loop(0, n, body, (m, l, o, kc, vc))
        return (o / jnp.maximum(l, 1e-30)[..., None]).astype(qc.dtype)

    def local_flash(qc, kc, vc):
        # per-chunk Pallas flash + online lse merge: the chunk partials
        # combine exactly because flash exports each row's logsumexp
        from distriflow_tpu.ops.flash_attention import flash_attention_with_lse
        from distriflow_tpu.ops.flop_count import record_pallas_cost

        my_index = lax.axis_index(axis)

        # FLOP-tally compensation: the ring loop below is a fori_loop whose
        # body traces a fixed number of times but executes n-1 times, so the
        # in-kernel records do not reflect the executed off-diagonal chunk
        # attentions. Under grad on current JAX the scan linearize traces
        # the body's custom-vjp FWD rule twice plus its BWD rule once
        # (measured; tests/test_ring_attention.py is the tripwire), i.e.
        # 2*4u + 8u = 16u recorded per trace for u = bhs²d chunk units,
        # while each of the n-1 executions costs 12u (fwd+bwd, non-causal).
        # Record the difference so the tally equals the true executed
        # model-FLOPs of a TRAIN step (the only cost-analysis consumer);
        # n=2 makes this a small negative correction, which is fine.
        b_c, h_c, s_c, d_c = qc.shape
        u_c = b_c * h_c * s_c * s_c * d_c
        record_pallas_cost(
            flops=((n - 1) * 12 - 16) * u_c,
            bytes_accessed=((n - 1) * 12 - 16) * b_c * h_c * s_c * d_c
            * qc.dtype.itemsize,
            transcendentals=((n - 1) * 3 - 4) * b_c * h_c * s_c * s_c,
        )

        def chunk_attn(kc, vc, chunk_causal):
            o_i, lse_i = flash_attention_with_lse(qc, kc, vc, chunk_causal)
            return o_i.astype(jnp.float32), lse_i

        # step 0 holds this device's own chunk: the causal diagonal
        o_acc, lse_acc = chunk_attn(kc, vc, causal)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)

        def body(step, carry):
            o_acc, lse_acc, kc, vc = carry
            src = jnp.mod(my_index - step, n)
            o_i, lse_i = chunk_attn(kc, vc, False)
            if causal:
                # chunks from later positions contribute nothing; NEG_INF
                # (not -inf) keeps exp/logaddexp free of inf-inf NaNs
                lse_i = jnp.where(src > my_index, NEG_INF, lse_i)
            new_lse = jnp.logaddexp(lse_acc, lse_i)
            o_acc = (
                o_acc * jnp.exp(lse_acc - new_lse)[..., None]
                + o_i * jnp.exp(lse_i - new_lse)[..., None]
            )
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return o_acc, new_lse, kc, vc

        o_acc, _, _, _ = lax.fori_loop(1, n, body, (o_acc, lse_acc, kc, vc))
        return o_acc.astype(qc.dtype)

    # batch rides the data axis and heads ride the model axis when present —
    # mentioning only `axis` would force an all-gather of the full global
    # batch and all heads onto every seq-group device, erasing DP/TP sharding
    spec = P(
        "data" if "data" in names else None,
        "model" if "model" in names else None,
        axis,
        None,
    )
    if use_flash is None:
        from distriflow_tpu.ops import default_use_flash

        use_flash = default_use_flash()
    body = local_flash if use_flash else local
    # pallas_call carries no varying-mesh-axes info, so the flash path must
    # disable shard_map's vma check
    fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=not use_flash)
    return fn(q, k, v)
