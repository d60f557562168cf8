"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The second long-context strategy next to the ring
(``distriflow_tpu/parallel/ring_attention.py``); no reference counterpart
(the reference has no attention or sequence models at all, SURVEY.md §2.3).

Layout dance (DeepSpeed-Ulysses): activations arrive sequence-sharded
``[B, H, S/n, D]`` per device; one all-to-all over the ``seq`` axis
re-shards to head-sharded ``[B, H/n, S, D]``, where every device holds the
FULL sequence for a subset of heads — so plain (blockwise) softmax
attention runs locally with exact causal masking and no per-step ring
latency; a second all-to-all swaps back. Two collectives per attention
call total, each moving the activation once over ICI — cheaper than the
ring's n-step K/V rotation when n is large and sequence chunks are fat;
the ring wins when overlap with compute matters more. Both are exposed;
``TransformerConfig`` picks via the mutually-exclusive flags
``use_ring_attention`` / ``use_ulysses_attention``.

Requires ``n_heads`` divisible by the ``seq`` axis size.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distriflow_tpu.parallel.ring_attention import blockwise_attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis: str = "seq",
    causal: bool = True,
    use_flash: "bool | None" = None,
) -> jnp.ndarray:
    """All-to-all sequence-parallel attention.

    Inputs are GLOBAL ``[B, H, S, D]`` (sharded or shardable over ``axis``
    on the sequence dim); output is sharded the same way — drop-in
    signature parity with :func:`ring_attention`. After the all-to-all each
    device attends over the FULL sequence for its head subset — exactly the
    shape the Pallas flash kernel wants, so ``use_flash`` (None = auto on
    TPU) runs the local attention as flash.
    """
    n = mesh.shape[axis]
    b, h, s, d = q.shape
    if s % n:
        raise ValueError(f"sequence {s} not divisible by {axis} axis size {n}")
    # heads ride the model axis when present: the all-to-all splits the
    # LOCAL head count across the seq group
    local_heads = h // (mesh.shape["model"] if "model" in mesh.axis_names else 1)
    if local_heads % n:
        raise ValueError(
            f"local head count {local_heads} (n_heads {h} / model axis) not "
            f"divisible by {axis} axis size {n} — Ulysses shards heads "
            "across the seq group; use ring attention for head counts below "
            "the axis size"
        )

    if use_flash is None:
        from distriflow_tpu.ops import default_use_flash

        use_flash = default_use_flash()

    def local(qc, kc, vc):
        # [B, H, S/n, D] -> all-to-all -> [B, H/n, S, D]: scatter heads,
        # gather sequence. tiled=True keeps the axis in place (no new dim).
        def swap_in(t):
            return lax.all_to_all(t, axis, split_axis=1, concat_axis=2, tiled=True)

        def swap_out(t):
            return lax.all_to_all(t, axis, split_axis=2, concat_axis=1, tiled=True)

        if use_flash:
            from distriflow_tpu.ops import flash_attention

            out = flash_attention(swap_in(qc), swap_in(kc), swap_in(vc), causal)
        else:
            out = blockwise_attention(
                swap_in(qc), swap_in(kc), swap_in(vc), causal=causal
            )
        return swap_out(out).astype(qc.dtype)

    names = mesh.axis_names
    spec = P(
        "data" if "data" in names else None,
        "model" if "model" in names else None,
        axis,
        None,
    )
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # blockwise_attention's fresh accumulators don't carry the varying-
        # axes type of the swapped chunks; the body is collective-free local
        # compute between the two all-to-alls, so vma checking adds nothing
        check_vma=False,
    )
    return fn(q, k, v)
