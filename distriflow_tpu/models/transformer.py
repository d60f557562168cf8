"""Transformer LM: the long-context / multi-axis-parallel flagship.

No reference equivalent (the reference stops at MLP/ConvNet classifiers,
SURVEY.md §2.3) — this model exists because long-context and multi-axis
parallelism are first-class in this framework. The parameter layout is
designed for the sharding rule table (``distriflow_tpu/parallel/sharding.py``):

- ``q_proj/k_proj/v_proj`` and ``wi`` kernels column-shard over ``model`` (TP);
- ``o_proj`` and ``wo`` kernels row-shard over ``model``;
- MoE expert kernels carry a leading experts dim sharded over ``expert`` (EP);
- activations seq-shard over ``seq`` and attention runs as a ring
  (``distriflow_tpu/parallel/ring_attention.py``) when a mesh is attached (SP);
- the batch dim shards over ``data`` (DP) as everywhere else;
- layers are grouped into ``pipe``-many stages for pipeline scheduling
  (``distriflow_tpu/parallel/pipeline.py``).

Compute dtype defaults to bfloat16 (MXU-native); accumulation and softmax
stay float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distriflow_tpu.models.base import ModelSpec
from distriflow_tpu.parallel.ring_attention import (
    _auto_block,
    blockwise_attention,
    ring_attention,
)


# int8-KV-cache latency crossover (satellite of the continuous-batching
# round; BENCH_r05 decode row): int8 decode measured SLOWER than bf16 at
# 1k context (0.474 vs 0.296 ms/tok) and 4k (1.014 vs 0.927) — the scale
# reads plus per-token quantization overhead beat the halved KV bytes at
# short context — and faster only by ~16k (3.03 vs 3.09, builder-measured,
# docs/PERFORMANCE.md §7e). Caches shorter than this keep bf16 under
# kv_cache_dtype="int8"; "int8_force" overrides (capacity > latency).
# Those numbers come from a machine that is gone (BENCH_r05 was deleted in
# PR 21): on the current TPU v5e the crossover is not measured (ROADMAP S4).
INT8_KV_DECODE_CROSSOVER_SEQ = 8192


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    d_ff: int = 2048
    max_seq: int = 2048
    n_experts: int = 0  # 0 = dense FFN; >0 = MoE with EP-shardable experts
    # experts per token: 1 = Switch (combine scaled by the raw chosen
    # prob), 2 = GShard top-2 (pair-normalized weights; first choices
    # claim capacity before any second choice). Capacity scales with
    # moe_top_k (GShard's k * factor * tokens / E), so capacity_factor
    # keeps its per-choice meaning.
    moe_top_k: int = 1
    capacity_factor: float = 1.25  # expert buffer = factor * group / E
    router_aux_weight: float = 0.01  # Switch load-balance loss weight
    moe_group_size: int = 1024  # routing-group tokens (bounds dispatch size)
    moe_dense_dispatch: bool = False  # True: exact all-experts dispatch
    dtype: Any = jnp.bfloat16
    use_ring_attention: bool = False
    use_ulysses_attention: bool = False  # all-to-all SP (parallel/ulysses.py)
    # Pallas flash kernels (distriflow_tpu/ops): None = auto (on for TPU,
    # off elsewhere — the kernel interpreter is test-only). Measured on v5e:
    # matches XLA at S=1k, 2.4-2.8x faster at S=4k-8k, and the only
    # non-OOM path at S=16k (XLA autodiff saves per-block score residuals)
    use_flash_attention: Optional[bool] = None
    causal: bool = True
    # rotary position embeddings on q/k (parameter-free, TPU-friendly:
    # two VPU multiplies fused into the attention prologue). Applied before
    # the attention dispatch, so it composes with every path — dense,
    # blockwise, flash, ring, Ulysses — positions are global iota
    use_rope: bool = True
    rope_base: float = 10000.0
    # rematerialize each block in backward (jax.checkpoint): activation
    # memory drops from O(layers * S * d) to O(S * d) at ~1/3 extra FLOPs —
    # the standard trade for long context / deep stacks
    remat: bool = False
    # pipeline backward schedule (pipelined_transformer_lm only):
    # None -> "remat" when remat=True else "gpipe";
    # "gpipe"  = autodiff through the schedule (fastest, O(M) internals),
    # "remat"  = input-only residuals + per-stage recompute (O(M) inputs),
    # "1f1b"   = interleaved one-forward-one-backward (O(P) live inputs)
    pipeline_schedule: Optional[str] = None
    # integer-label CE by default: LM targets are the [B, S] int32 next-token
    # ids, never a [B, S, V] one-hot (HBM + wire cost scales with V otherwise).
    # None = auto: the Pallas fused CE on TPU (online-logsumexp over vocab
    # tiles, no [N, V] log-softmax intermediate in HBM — ops/fused_ce.py),
    # plain optax CE elsewhere (the kernel interpreter is test-only-slow).
    loss: Optional[str] = None
    # decode-time KV cache precision. None = cfg.dtype. "int8" halves the
    # cache's HBM footprint AND the per-token read traffic — decode at long
    # context is KV-read bandwidth-bound (docs/PERFORMANCE.md §8), so this
    # is the lever that moves per-token latency there. Symmetric
    # per-(position, head) absmax quantization; scales stored alongside in
    # float32. Pays off through the flash-decode kernel (in-VMEM dequant);
    # the XLA fallback materializes the dequantized cache and loses.
    # "int8" auto-gates to the bf16 cache below
    # INT8_KV_DECODE_CROSSOVER_SEQ positions: at short context the scale
    # reads + per-token quantization overhead outweigh the halved KV
    # traffic (measured slower at 1k AND 4k, BENCH_r05), so short caches
    # silently keep cfg.dtype and the int8 request only takes effect where
    # it wins. "int8_force" always quantizes (kernel unit tests, capacity-
    # bound deployments that want 2x context per HBM byte regardless).
    kv_cache_dtype: Optional[str] = None
    # single-token decode attention via the Pallas flash-decode kernel
    # (ops/flash_decode.py): one fused pass over the KV cache instead of
    # XLA's matvec/softmax/matvec round trips (~25% of HBM peak measured).
    # None = auto: on where the flash kernels compile (TPU), off for
    # mesh-sharded params (pallas_call has no GSPMD rule — generate()
    # auto-detects and disables so TP decode keeps its collective layout).
    use_flash_decode: Optional[bool] = None

    def __post_init__(self):
        if self.n_experts > 0 and not 1 <= self.moe_top_k <= self.n_experts:
            raise ValueError(
                f"moe_top_k must be in [1, n_experts={self.n_experts}], "
                f"got {self.moe_top_k}"
            )
        if self.use_ring_attention and self.use_ulysses_attention:
            raise ValueError(
                "use_ring_attention and use_ulysses_attention are mutually "
                "exclusive sequence-parallel strategies; pick one"
            )
        if self.kv_cache_dtype not in (None, "int8", "int8_force"):
            raise ValueError(
                f"kv_cache_dtype must be None, 'int8', or 'int8_force', "
                f"got {self.kv_cache_dtype!r}"
            )

    def kv_cache_dtype_for(self, context_len: int) -> Optional[str]:
        """The cache precision a decode that will READ ``context_len``
        positions should store: "int8" only when quantization pays —
        i.e. forced, or the context at/above the measured crossover
        (docs/PERFORMANCE.md §7e). Below it, int8's per-token quantize +
        scale reads cost more than the halved KV traffic saves, so the
        cache stays ``cfg.dtype``.

        The crossover is about traffic actually read, not capacity
        allocated: a ``max_seq=16384`` config decoding a 1k-context
        request streams 1k positions per token, and int8 loses there just
        as it does for a short ``max_seq`` (BENCH_r05 measured int8
        SLOWER at 1k and 4k context). Callers that know the real request
        shape (``generate()``: prompt + n_tokens) gate on it; callers
        that only know the allocation bound (the serving engine's shared
        slab) fall back to :attr:`resolved_kv_cache_dtype`."""
        if self.kv_cache_dtype == "int8_force":
            return "int8"
        if (self.kv_cache_dtype == "int8"
                and context_len >= INT8_KV_DECODE_CROSSOVER_SEQ):
            return "int8"
        return None

    @property
    def resolved_kv_cache_dtype(self) -> Optional[str]:
        """The cache precision decode stores when only the allocation
        bound is known: :meth:`kv_cache_dtype_for` at ``max_seq`` — the
        conservative upper bound on how much KV a token could read."""
        return self.kv_cache_dtype_for(self.max_seq)

    def resolved_loss_for(self, mesh: Optional[Mesh]) -> str:
        """The loss name the model spec actually trains with. An explicit
        ``loss`` is always honored; ``loss=None`` resolves at spec-build
        time (not config-construction time, so a config built on the host
        composes with whatever backend runs it): the fused Pallas sparse
        CE on TPU when the logits' vocab dim stays unsharded — i.e. on a
        single device or a pure data-parallel mesh (the kernel runs per
        data shard of the trainer's context mesh, ``ops/fused_ce.py``).
        Meshes with model/pipe axes column-shard the lm_head (vocab-sharded
        logits) and seq axes shard a middle dim the flat [tokens, V] view
        cannot represent — those fall back to the sharded XLA loss, which
        GSPMD handles for free. Opting in explicitly remains possible.
        """
        if self.loss is not None:
            return self.loss
        if mesh is not None and any(
            dict(mesh.shape).get(ax, 1) > 1 for ax in ("model", "pipe", "seq")
        ):
            return "sparse_softmax_cross_entropy"
        return (
            "fused_sparse_softmax_cross_entropy"
            if _default_use_flash()
            else "sparse_softmax_cross_entropy"
        )

    @property
    def resolved_loss(self) -> str:
        """Meshless resolution (single-device semantics)."""
        return self.resolved_loss_for(None)


def apply_rope(
    q: jnp.ndarray,
    k: jnp.ndarray,
    base: float = 10000.0,
    offset: Any = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rotary position embeddings over ``[B, H, S, D]`` q/k (D even).

    Rotation runs in float32 (angle precision matters at long context) and
    casts back to the input dtype; the attention score then depends only on
    the relative position ``i - j``. ``offset`` shifts the absolute
    positions (e.g. for decode-time caches): a scalar shifts every row the
    same way; a ``[B]`` vector gives each batch row its own absolute
    position (slot-partitioned continuous-batching decode, where rows sit
    at unrelated depths in their sequences)."""
    d = q.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head dim, got {d}")
    half = d // 2
    off = jnp.asarray(offset, dtype=jnp.float32)
    steps = jnp.arange(q.shape[2], dtype=jnp.float32)  # [S]
    if off.ndim == 0:
        pos = off + steps  # [S]
    elif off.ndim == 1:
        pos = off[:, None] + steps[None, :]  # [B, S]
    else:
        raise ValueError(f"RoPE offset must be scalar or [B], got ndim={off.ndim}")
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)  # [half]
    angles = pos[..., None] * freqs  # [S, half] or [B, S, half]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if off.ndim == 1:
        # insert the heads axis so the rotation broadcasts over [B, H, S, half]
        cos, sin = cos[:, None], sin[:, None]

    def rot(x):
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :half], xf[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).astype(x.dtype)

    return rot(q), rot(k)


def _default_use_flash() -> bool:
    from distriflow_tpu.ops import default_use_flash

    return default_use_flash()


def _flash_enabled(cfg) -> bool:
    """THE flash-attention enable predicate — every attention site
    (training __call__, prefill) resolves the tri-state config through
    this one helper so the auto-enable policy cannot fork."""
    return cfg.use_flash_attention or (
        cfg.use_flash_attention is None and _default_use_flash())


def _sharded_flash_attention(q, k, v, causal, mesh):
    """Flash attention that stays partitioned on a multi-device mesh.

    ``pallas_call`` has no GSPMD partitioning rule: under plain jit on a
    sharded mesh its operands would be all-gathered and the kernel run
    replicated on every device. Batch and heads are embarrassingly parallel
    in attention, so on a data/model-sharded mesh we shard_map the kernel
    over those axes — each device runs flash on its own [B/dp, H/tp, S, D]
    shard, no collectives. Requires B % dp == 0 and H % tp == 0 (the same
    constraint Megatron TP already imposes on heads).
    """
    import functools as _ft

    from distriflow_tpu.ops import flash_attention  # lazy: pallas import

    fn = _ft.partial(flash_attention, causal=causal)
    ambient = jax.sharding.get_abstract_mesh()
    if mesh is None or (not ambient.empty and ambient.are_all_axes_manual):
        # no mesh, or already inside a shard_map body (FedAvg's local
        # loop): q/k/v are this device's shard, and a second shard_map over
        # the concrete mesh is an error there
        return fn(q, k, v)
    parallel_axes = tuple(
        ax for ax in ("data", "model")
        if dict(mesh.shape).get(ax, 1) > 1
    )
    if not parallel_axes:
        return fn(q, k, v)
    spec = P(
        "data" if "data" in parallel_axes else None,
        "model" if "model" in parallel_axes else None,
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


class Attention(nn.Module):
    config: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False  # KV-cache autoregressive mode (mutable 'cache')

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        b, s, _ = x.shape
        head_dim = cfg.d_model // cfg.n_heads
        dense = lambda name: nn.DenseGeneral(
            (cfg.n_heads, head_dim), axis=-1, name=name, dtype=cfg.dtype,
            use_bias=False,
        )
        q = dense("q_proj")(x)  # [B, S, H, D]
        k = dense("k_proj")(x)
        v = dense("v_proj")(x)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, S, D]
        if self.decode:
            return self._decode_attend(q, k, v, b, s, head_dim)
        if cfg.use_rope:
            q, k = apply_rope(q, k, base=cfg.rope_base)
        seq_size = (
            dict(self.mesh.shape).get("seq", 1) if self.mesh is not None else 1
        )
        if cfg.use_ring_attention and seq_size > 1:
            # thread the flash preference: an explicit use_flash_attention
            # opt-out must also disable the flash kernels inside the ring
            out = ring_attention(q, k, v, self.mesh, axis="seq",
                                 causal=cfg.causal,
                                 use_flash=cfg.use_flash_attention)
        elif cfg.use_ulysses_attention and seq_size > 1:
            from distriflow_tpu.parallel.ulysses import ulysses_attention

            out = ulysses_attention(q, k, v, self.mesh, axis="seq",
                                    causal=cfg.causal,
                                    use_flash=cfg.use_flash_attention)
        elif _flash_enabled(cfg):
            out = _sharded_flash_attention(q, k, v, cfg.causal, self.mesh)
        else:
            out = blockwise_attention(q, k, v, causal=cfg.causal)
        out = out.transpose(0, 2, 1, 3)  # [B, S, H, D]
        return self._o_proj()(out)

    def _o_proj(self):
        cfg = self.config
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), name="o_proj", dtype=cfg.dtype,
            use_bias=False)

    def _decode_attend(self, q, k, v, b, s, head_dim):
        """Incremental attention against the mutable KV cache.

        The first call (prefill, any ``s``) fills positions ``[0, s)``; each
        later call appends at the running index. q/k get RoPE at their
        absolute positions.

        **Token-major packed cache** (round 5): K/V are stored
        ``[B, max_seq, H*D]`` — each position's all-head features
        contiguous — not the torch-style ``[B, H, S, D]``. At head_dim 64
        the head-major layout half-fills every 128-lane TPU vector
        register and capped the decode kernel's DMA at ~300 GB/s; the
        packed tiles stream at ~690 GB/s (measured on v5e — see
        ops/flash_decode.py). It is also write-natural: the projections
        produce ``[B, S, H, D]``, so appending a token is one contiguous
        ``[B, s, H*D]`` dynamic_update_slice with no transpose.

        Long-context per-token cost is KV-read-bound, so the second lever
        is ``kv_cache_dtype="int8"``: symmetric per-(position, head)
        absmax-quantized K/V (``[B, max_seq, H]`` f32 scales), halving
        footprint and read traffic; the flash kernel folds the scales
        into its score/prob tensors in VMEM.
        """
        cfg = self.config
        quant = cfg.resolved_kv_cache_dtype == "int8"
        hd = cfg.n_heads * head_dim
        cache_shape = (b, cfg.max_seq, hd)
        store_dtype = jnp.int8 if quant else cfg.dtype
        # STATIC initial-prefill signal: the apply() that CREATES the cache
        # variables (generate's prefill) sees has_variable == False at
        # trace time — so the prompt-wide attention below can statically
        # take the flash/blockwise path over the PROMPT instead of the
        # dense einsum over max_seq (which materializes [B, H, s, max_seq]
        # f32 — 68 GB at 16k context; the OOM that capped long-context
        # serving). Continuations (decode steps, chunked prefill against a
        # pre-existing cache) see True and keep the exact cache-wide paths.
        fresh_cache = not self.has_variable("cache", "cached_k")
        ck = self.variable("cache", "cached_k", jnp.zeros, cache_shape,
                           store_dtype)
        cv = self.variable("cache", "cached_v", jnp.zeros, cache_shape,
                           store_dtype)
        if quant:
            scale_shape = (b, cfg.max_seq, cfg.n_heads)
            sk = self.variable("cache", "k_scale", jnp.zeros, scale_shape,
                               jnp.float32)
            sv = self.variable("cache", "v_scale", jnp.zeros, scale_shape,
                               jnp.float32)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        # Paged mode (round 9): the engine swaps the per-row slabs for ONE
        # shared pool of fixed-size pages ([n_pages, page_size, H*D]) plus
        # a per-slot page table ([max_slots, pages_per_slot + 1] int32,
        # last column pinned at the sentinel n_pages). The module never
        # creates the table itself — models/generate.py::paged_cache
        # injects it, so has_variable is a STATIC signal exactly like
        # slot_mode below. Writes indirect through the table; a logical
        # position past the table range, or a sentinel entry (retired or
        # unallocated page), maps to a flattened index >= n_pages *
        # page_size, which the scatter DROPS under jit — the same
        # out-of-bounds contract the slab's retired-slot parking relies
        # on. Reads gather the row's pages back into the exact
        # [B, max_seq, ...] slab view before any score math, so every
        # downstream shape, mask, and reduction order — and therefore
        # every decoded bit on this path — matches the slab cache.
        paged = self.has_variable("cache", "page_table")
        pt = (self.variable("cache", "page_table",
                            lambda: jnp.zeros((0, 0), jnp.int32))
              if paged else None)
        # Slot mode (continuous batching): the engine swaps the scalar
        # cache_index for a [B] vector — each batch row is an independent
        # request at its own depth. Detected statically from the cache
        # pytree's shape, so both modes share one module and each jit
        # program sees exactly one branch. Per-row RoPE offsets, scatter
        # writes (OOB rows — retired slots parked at max_seq — drop), and
        # per-row visibility replace their scalar counterparts below.
        slot_mode = idx.ndim == 1
        if cfg.use_rope:
            q, k = apply_rope(q, k, base=cfg.rope_base, offset=idx)
        # q/k/v arrive [B, H, s, D]; the cache wants token-major [B, s, H*D]
        k_tok = k.transpose(0, 2, 1, 3).reshape(b, s, hd)
        v_tok = v.transpose(0, 2, 1, 3).reshape(b, s, hd)

        def _store(buf, upd):
            """Append ``upd`` [B, s, ...] at each row's own position."""
            if paged:
                n_pg, ps = buf.shape[0], buf.shape[1]
                pp = pt.value.shape[1] - 1  # last column is the sentinel
                cols = idx[:, None] + jnp.arange(s)[None, :]  # [B, s]
                pg = jnp.minimum(cols // ps, pp)  # OOB logical -> sentinel
                phys = pt.value[jnp.arange(b)[:, None], pg]  # [B, s]
                flat = phys * ps + cols % ps  # sentinel -> OOB -> dropped
                out = buf.reshape(n_pg * ps, buf.shape[-1]).at[flat].set(upd)
                return out.reshape(buf.shape)
            if slot_mode:
                rows = jnp.arange(b)[:, None]
                cols = idx[:, None] + jnp.arange(s)[None, :]
                return buf.at[rows, cols].set(upd)
            return jax.lax.dynamic_update_slice(buf, upd, (0, idx, 0))

        def _view(buf):
            """Slab-shaped [B, max_seq, F] view of every row's cache: the
            slab IS that view; paged gathers each row's pages (sentinel
            entries clamp to a real page — garbage the per-row visibility
            mask turns into exact 0.0 softmax mass) and statically slices
            to max_seq so reduction shapes match the slab bit-for-bit."""
            if not paged:
                return buf
            n_pg, ps = buf.shape[0], buf.shape[1]
            pp = pt.value.shape[1] - 1
            tab = jnp.minimum(pt.value[:, :pp], n_pg - 1)
            g = buf[tab]  # [B, PP, ps, F]
            return g.reshape(b, pp * ps, buf.shape[-1])[:, :cfg.max_seq]

        def _quantize(t):  # t: [B, s, H*D] -> int8 + [B, s, H] scales
            tf = t.astype(jnp.float32).reshape(b, s, cfg.n_heads, head_dim)
            scale = jnp.max(jnp.abs(tf), axis=-1) / 127.0  # [B, s, H]
            safe = jnp.maximum(scale, 1e-20)
            q8 = jnp.clip(jnp.round(tf / safe[..., None]), -127, 127)
            return q8.astype(jnp.int8).reshape(b, s, hd), scale

        if quant:
            k8, ks = _quantize(k_tok)
            v8, vs = _quantize(v_tok)
            ck.value = _store(ck.value, k8)
            cv.value = _store(cv.value, v8)
            sk.value = _store(sk.value, ks)
            sv.value = _store(sv.value, vs)
            # dequantize in f32 and cast the PRODUCT, matching the flash
            # kernel's in-VMEM dequant — casting the scales to bf16 first
            # would diverge the two decode paths' numerics
            keys = (_view(ck.value).astype(jnp.float32).reshape(
                b, cfg.max_seq, cfg.n_heads, head_dim)
                * _view(sk.value)[..., None]).astype(cfg.dtype)
            vals = (_view(cv.value).astype(jnp.float32).reshape(
                b, cfg.max_seq, cfg.n_heads, head_dim)
                * _view(sv.value)[..., None]).astype(cfg.dtype)
        else:
            ck.value = _store(ck.value, k_tok.astype(cfg.dtype))
            cv.value = _store(cv.value, v_tok.astype(cfg.dtype))
            keys = _view(ck.value).reshape(
                b, cfg.max_seq, cfg.n_heads, head_dim)
            vals = _view(cv.value).reshape(
                b, cfg.max_seq, cfg.n_heads, head_dim)
        ci.value = idx + s

        if s > 1 and fresh_cache:
            # initial prefill: the cache held only zeros, so attention
            # over the prompt tokens IS the full answer — run the
            # training-path kernels (O(s * block) VMEM tiles) on the
            # exact pre-quantization projections. The dense einsum below
            # would build [B, H, s, max_seq] f32 scores: 68 GB at 16k
            # context. int8 configs quantize for STORAGE only — prefill
            # quality is full-precision, like production engines. The
            # _sharded kernel wrapper carries the batch/heads GSPMD rule
            # so TP-sharded prefill stays sharded (a bare pallas_call
            # would all-gather and replicate the whole prompt's
            # attention on every chip). Crooked prompt lengths the
            # kernel cannot tile within VMEM (no sublane-aligned block
            # divisor) take the pure-XLA blockwise path instead.
            from distriflow_tpu.ops.flash_attention import (
                flash_attention_sharded,
                flash_seq_supported,
            )

            if _flash_enabled(cfg) and flash_seq_supported(
                    s, head_dim, jnp.dtype(cfg.dtype).itemsize):
                out = flash_attention_sharded(q, k, v, causal=cfg.causal)
            else:
                out = blockwise_attention(q, k, v, causal=cfg.causal)
            out = out.transpose(0, 2, 1, 3)  # [B, s, H, D]
            return self._o_proj()(out)

        use_fd = cfg.use_flash_decode
        if use_fd is None:
            # auto-enable only when the kernel can actually tile this
            # cache shape (no sublane-aligned divisor fitting VMEM ->
            # XLA fallback instead of raising mid-trace)
            from distriflow_tpu.ops.flash_decode import (
                supports_paged,
                supports_seq,
            )

            if paged:
                use_fd = _default_use_flash() and supports_paged(
                    ck.value.shape[1], hd=hd,
                    kv_item=jnp.dtype(store_dtype).itemsize)
            else:
                use_fd = _default_use_flash() and supports_seq(
                    cfg.max_seq, hd=hd,
                    kv_item=jnp.dtype(store_dtype).itemsize)
        if use_fd and s == 1 and paged:
            # paged flash-decode: same recurrence, K/V tile index maps
            # dereference the page table (second scalar-prefetch operand)
            from distriflow_tpu.ops.flash_decode import flash_decode_paged

            qf = q[:, :, 0, :]  # [B, H, D]
            tab = pt.value[:, :-1]  # drop the pinned sentinel column
            if quant:
                ctx = flash_decode_paged(
                    qf, ck.value, cv.value, tab, idx + s,
                    k_scale=sk.value, v_scale=sv.value,
                )
            else:
                ctx = flash_decode_paged(qf, ck.value, cv.value, tab, idx + s)
            out = ctx[:, None, :, :].astype(cfg.dtype)  # [B, 1, H, D]
            return self._o_proj()(out)
        if use_fd and s == 1:
            # flash-decode kernel: one fused full-lane pass over the
            # packed cache (online softmax in VMEM scratch); int8 scales
            # fold in-kernel. The _sharded wrapper carries the
            # heads-sharded GSPMD rule, so TP-sharded decode runs the
            # kernel per model shard with no gather — see
            # ops/flash_decode.py
            from distriflow_tpu.ops.flash_decode import flash_decode_sharded

            qf = q[:, :, 0, :]  # [B, H, D]
            if quant:
                ctx = flash_decode_sharded(
                    qf, ck.value, cv.value, idx + s,
                    k_scale=sk.value, v_scale=sv.value,
                )
            else:
                ctx = flash_decode_sharded(qf, ck.value, cv.value, idx + s)
            out = ctx[:, None, :, :].astype(cfg.dtype)  # [B, 1, H, D]
            return self._o_proj()(out)

        if quant and s == 1:
            # mirror the flash kernel's per-head absmax q quantization
            # (ops/flash_decode.py scores int8 x int8 on the MXU): the
            # XLA fallback is the kernel's reference implementation, so
            # the two single-token paths stay numerically aligned —
            # without this the kernel quantizes q and the fallback does
            # not, a systematic divergence rather than rounding noise
            # (tests assert argmax-stable token equality between them)
            qf32 = q.astype(jnp.float32)
            qsc = jnp.maximum(
                jnp.max(jnp.abs(qf32), axis=-1, keepdims=True) / 127.0,
                1e-20)
            q = (jnp.clip(jnp.round(qf32 / qsc), -127, 127) * qsc).astype(
                q.dtype)
        scores = jnp.einsum(
            "bhqd,bkhd->bhqk", q, keys, preferred_element_type=jnp.float32
        ) / math.sqrt(head_dim)  # [B, H, s, max_seq]
        k_pos = jnp.arange(cfg.max_seq)[None, :]
        if slot_mode:
            # per-row windows: row i sees [0, idx[i] + q) — other slots'
            # depths never leak into the mask, and masked scores at -1e30
            # underflow to exactly 0.0 in softmax, so a row's output is
            # bit-identical whatever garbage its batchmates left behind.
            # This s > 1 branch is ALSO the speculative verify pass
            # (models/generate.py::_build_spec_fns): the target scores a
            # [tok, d_1..d_k] window in one dispatch, and because each
            # position's window here is exactly the window s sequential
            # s=1 steps would have seen, greedy acceptance over these
            # logits reproduces the solo token stream bit-for-bit
            q_pos = idx[:, None] + jnp.arange(s)[None, :]  # [B, s]
            if cfg.causal:
                visible = k_pos[None] <= q_pos[..., None]  # [B, s, K]
            else:
                visible = jnp.broadcast_to(
                    k_pos[None] < (idx + s)[:, None, None],
                    (b, s, cfg.max_seq))
            visible = visible[:, None]  # [B, 1, s, K] over heads
        else:
            q_pos = idx + jnp.arange(s)[:, None]
            if cfg.causal:
                visible = k_pos <= q_pos
            else:
                # non-causal configs still must not attend to empty cache
                # slots
                visible = jnp.broadcast_to(k_pos < idx + s, (s, cfg.max_seq))
        scores = jnp.where(visible, scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", p, vals, preferred_element_type=jnp.float32
        ).astype(cfg.dtype)  # [B, s, H, D]
        return self._o_proj()(out)


class DenseFFN(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        h = nn.Dense(cfg.d_ff, name="wi", dtype=cfg.dtype, use_bias=False)(x)
        h = nn.gelu(h)
        return nn.Dense(cfg.d_model, name="wo", dtype=cfg.dtype, use_bias=False)(h)


class MoEFFN(nn.Module):
    """Capacity-dispatched MoE: Switch top-1 (default) or GShard top-2.

    ``moe_top_k=1``: each token routes to its argmax expert, combine scaled
    by the raw chosen prob (Switch). ``moe_top_k=2``: each token routes to
    its two highest-prob experts with pair-normalized combine weights
    (GShard); capacity scales with k, and every token's FIRST choice claims
    its slot before any second choice competes.
    Each token routes to its chosen expert(s); each expert processes at most
    ``capacity = capacity_factor * tokens / E`` tokens (overflow tokens pass
    through the residual unchanged — standard Switch semantics). Dispatch
    and combine are one-hot einsum contractions, the Mesh-TensorFlow
    formulation GSPMD partitions well: with the expert dim of ``experts_wi``
    / ``experts_wo`` sharded over the ``expert`` mesh axis and tokens over
    ``data``, XLA lowers the dispatch/combine einsums to the expert
    all-to-all. Compute per token is ONE expert FFN (the previous dense
    dispatch ran every token through every expert: E-fold FLOPs).

    The router gets gradients through the gate-probability scaling of the
    combine, and sows the Switch load-balancing loss
    ``E * sum_e f_e * P_e`` into the ``aux`` collection (a no-op when the
    caller does not request it — e.g. the pipelined path).
    ``moe_dense_dispatch=True`` restores the exact all-experts path.
    """

    config: TransformerConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        e = cfg.n_experts
        wi = self.param(
            "experts_wi",
            nn.initializers.lecun_normal(),
            (e, cfg.d_model, cfg.d_ff),
            jnp.float32,
        ).astype(cfg.dtype)
        wo = self.param(
            "experts_wo",
            nn.initializers.lecun_normal(),
            (e, cfg.d_ff, cfg.d_model),
            jnp.float32,
        ).astype(cfg.dtype)
        gates = nn.Dense(e, name="router", dtype=jnp.float32)(x.astype(jnp.float32))
        probs = jax.nn.softmax(gates, axis=-1)  # [B, S, E] f32

        k = cfg.moe_top_k
        if cfg.moe_dense_dispatch:
            # exact all-experts path: every token's true top-k experts,
            # combined with the SAME gate weights as the capacity path
            # below (k=1: raw chosen prob, Switch; k>=2: top-k-normalized,
            # GShard), so dense dispatch is exactly its no-drop limit
            # (capacity output == dense output wherever no token
            # overflowed; the decode path relies on this). Router
            # gradients flow through the prob factors.
            topv, topi = jax.lax.top_k(probs, k)  # [B, S, K]
            w = topv if k == 1 else topv / jnp.sum(topv, -1, keepdims=True)
            dispatch = jnp.sum(
                jax.nn.one_hot(topi, e, dtype=probs.dtype) * w[..., None], axis=-2
            )  # [B, S, E]: gate weight on each chosen expert
            h = jnp.einsum("bsd,edf->bsef", x, wi)
            h = nn.gelu(h)
            out = jnp.einsum("bsef,efd->bsed", h, wo)
            return jnp.einsum("bsed,bse->bsd", out, dispatch.astype(cfg.dtype))

        b, s, d = x.shape
        n_tok = b * s
        # tokens are routed within fixed-size groups (Mesh-TF "group_size"):
        # the dispatch/combine tensors are [G, g, E, C] with C = factor*g/E,
        # so their size is factor * T * g — LINEAR in total tokens (a single
        # global group would make them quadratic)
        g = _auto_block(n_tok, cfg.moe_group_size)
        n_grp = n_tok // g
        capacity = max(1, int(cfg.capacity_factor * cfg.moe_top_k * g / e))
        grp_x = x.reshape(n_grp, g, d)
        grp_probs = probs.reshape(n_grp, g, e)
        # top-k choices per token; k=1 reduces exactly to Switch argmax
        topv, topi = jax.lax.top_k(grp_probs, k)  # [G, g, K]
        onehot = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [G, g, K, E]
        gate = topv if k == 1 else topv / jnp.sum(topv, -1, keepdims=True)
        # load-balancing aux on the FIRST choice (Switch/GShard convention)
        f_frac = jnp.mean(onehot[:, :, 0, :], axis=(0, 1))
        p_mean = jnp.mean(grp_probs, axis=(0, 1))
        self.sow("aux", "load_balance", e * jnp.sum(f_frac * p_mean))
        # position of each (token, choice) pair within its expert's buffer.
        # Pairs flatten CHOICE-MAJOR (all first choices, then all second
        # choices): GShard fills every token's primary expert before any
        # secondary claims a slot, so an early token's 2nd choice can
        # never evict a later token's 1st. pos=0 (not routed) and
        # pos>capacity (overflow) land outside [0, C) and one_hot yields
        # all-zero rows — no extra mask needed.
        oh_flat = onehot.transpose(0, 2, 1, 3).reshape(n_grp, k * g, e)
        pos = jnp.cumsum(oh_flat, axis=1) * oh_flat  # [G, K*g, E], 1-based
        dispatch = jax.nn.one_hot(pos.astype(jnp.int32) - 1, capacity,
                                  dtype=jnp.float32)  # [G, K*g, E, C] 0/1
        # capacity-overflow observability: fraction of (token, choice) pairs
        # that found no slot. Sown into its OWN collection so it never mixes
        # with the 'aux' losses; invisible (flax no-op) unless the caller
        # applies with mutable=["moe_stats"] — the bench's capacity sweep does.
        self.sow("moe_stats", "dropped_fraction",
                 1.0 - jnp.sum(dispatch) / (k * n_tok))
        gate_flat = gate.transpose(0, 2, 1).reshape(n_grp, k * g)
        combine = dispatch * gate_flat[..., None, None]
        # tokens tiled choice-major to match: [all tokens (choice 0), ...]
        x_rep = grp_x if k == 1 else jnp.tile(grp_x, (1, k, 1))
        expert_in = jnp.einsum(
            "xtec,xtd->xecd", dispatch.astype(cfg.dtype), x_rep
        )  # [G, E, C, d] — the expert all-to-all under GSPMD
        h = nn.gelu(jnp.einsum("xecd,edf->xecf", expert_in, wi))
        expert_out = jnp.einsum("xecf,efd->xecd", h, wo)
        out = jnp.einsum(
            "xtec,xecd->xtd", combine.astype(cfg.dtype), expert_out
        )  # overflow pairs get zeros: they ride the residual connection
        if k > 1:
            out = out.reshape(n_grp, k, g, d).sum(axis=1)
        return out.reshape(b, s, d)


class Block(nn.Module):
    config: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        h = nn.LayerNorm(name="ln_attn", dtype=jnp.float32)(x)
        x = x + Attention(cfg, self.mesh, self.decode, name="attn")(h)
        h = nn.LayerNorm(name="ln_mlp", dtype=jnp.float32)(x)
        ffn = MoEFFN(cfg, name="moe") if cfg.n_experts > 0 else DenseFFN(cfg, name="mlp")
        return x + ffn(h)


class TransformerLM(nn.Module):
    config: TransformerConfig
    mesh: Optional[Mesh] = None
    decode: bool = False

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="embed",
                     dtype=cfg.dtype)(tokens)
        block_cls = nn.remat(Block) if (cfg.remat and not self.decode) else Block
        for i in range(cfg.n_layers):
            x = block_cls(cfg, self.mesh, self.decode, name=f"layers_{i}")(x)
        x = nn.LayerNorm(name="ln_f", dtype=jnp.float32)(x)
        logits = nn.Dense(cfg.vocab_size, name="lm_head", dtype=cfg.dtype,
                          use_bias=False)(x)
        return _cast_logits(
            logits, cfg.resolved_loss_for(self.mesh), decode=self.decode
        )


def _cast_logits(logits, loss_name, decode=False):
    """f32 logits for XLA losses and decode; native dtype for the fused CE.

    The f32-materialized ``[tokens, V]`` logits are the single biggest HBM
    array in the training step (~1 GB at the bench config): the fused Pallas
    CE reads the compute dtype directly and upcasts per-tile in VMEM, so the
    cast (and its backward twin on the gradient) is pure wasted bandwidth
    there — measured 8-9% of flagship step time on v5e. ``loss_name`` must
    be the RESOLVED name the spec trains with (same mesh!) so dtype and loss
    choice never diverge. Decode always gets f32 (sampling numerics are
    host-visible API surface)."""
    if not decode and loss_name.startswith("fused_"):
        return logits
    return logits.astype(jnp.float32)


#: the modules above that are built with ``dtype=cfg.dtype``: flax casts
#: their ``kernel`` to the compute dtype at every use. Everything else
#: (the LayerNorms, the MoE ``router``) computes in float32 from float32
#: leaves, and casting those would change the result.
_COMPUTE_DTYPE_MODULES = frozenset(
    {"q_proj", "k_proj", "v_proj", "o_proj", "wi", "wo", "lm_head"})
#: leaves cast to ``cfg.dtype`` whatever module holds them (``nn.Embed``'s
#: table; ``MoEFFN``'s expert stacks, cast by hand at their use)
_COMPUTE_DTYPE_LEAVES = frozenset({"embedding", "experts_wi", "experts_wo"})


@functools.partial(jax.jit, static_argnames="dtype")
def _cast_leaves(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def compute_view(config: TransformerConfig, params: Any) -> Any:
    """``params`` as the block consumes them: every leaf a module casts to
    ``config.dtype`` at its use comes back in ``config.dtype``, every
    other leaf as the array it is. ``TransformerLM.apply`` over the view
    is bit-equal to ``apply`` over ``params`` (flax's ``promote_dtype`` is
    the identity on a leaf already in the module's dtype), and a program
    that takes the view reads the narrow weights instead of re-making
    them: XLA hoists the casts out of a decode loop and materialises a
    compute-dtype copy of all weights at the head of every dispatch.

    A leaf already in ``config.dtype`` is returned as the same array, so a
    tree served in the compute dtype costs no copy and runs no program.
    The rest are cast by one jitted call; an elementwise cast keeps its
    operand's sharding, so TP-sharded params stay sharded. A caller whose
    weights change (``InferenceServer.set_params``) builds the view again.
    """
    dtype = jnp.dtype(config.dtype)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [leaf for _, leaf in flat]
    todo = []
    for i, (path, leaf) in enumerate(flat):
        names = [getattr(k, "key", None) for k in path]
        consumed = names[-1] in _COMPUTE_DTYPE_LEAVES or (
            names[-1] == "kernel" and len(names) > 1
            and names[-2] in _COMPUTE_DTYPE_MODULES)
        if consumed and jnp.dtype(leaf.dtype) != dtype:
            todo.append(i)
    if not todo:
        return params
    for i, cast in zip(todo, _cast_leaves([leaves[i] for i in todo], dtype)):
        leaves[i] = cast
    return jax.tree_util.tree_unflatten(treedef, leaves)


class StageBlocks(nn.Module):
    """One pipeline stage: ``per`` consecutive transformer blocks."""

    config: TransformerConfig
    per: int = 1
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for i in range(self.per):
            x = Block(self.config, self.mesh, name=f"block_{i}")(x)
        return x


class _EmbedIn(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        return nn.Embed(cfg.vocab_size, cfg.d_model, name="embed",
                        dtype=cfg.dtype)(tokens)


class _HeadOut(nn.Module):
    config: TransformerConfig
    # resolved loss of the enclosing spec (the pipelined builder resolves
    # against its mesh); None = meshless resolution
    loss_name: Optional[str] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = nn.LayerNorm(name="ln_f", dtype=jnp.float32)(x)
        logits = nn.Dense(cfg.vocab_size, name="lm_head", dtype=cfg.dtype,
                          use_bias=False)(x)
        return _cast_logits(logits, self.loss_name or cfg.resolved_loss)


def pipelined_transformer_lm(
    config: Optional[TransformerConfig] = None,
    mesh: Optional[Mesh] = None,
    num_microbatches: Optional[int] = None,
    example_seq: int = 128,
    example_batch: Optional[int] = None,
    **overrides: Any,
) -> ModelSpec:
    """Pipeline-parallel causal LM over the mesh's ``pipe`` axis
    (DP x PP x TP — Megatron sharding inside stages rides the automatic
    ``model`` axis through the pipeline's hybrid shard_map).

    The layer stack splits into P = ``mesh.shape['pipe']`` stages of
    ``n_layers / P`` blocks; stage params carry a leading stages dim sharded
    over ``pipe`` and the batch runs through the GPipe schedule
    (``distriflow_tpu.parallel.pipeline.gpipe``) in ``num_microbatches``
    microbatches (default P), each microbatch's rows sharded over ``data``.
    Embedding and head live outside the pipeline (standard practice: they
    are not shape-preserving). Attention inside stages is dense/flash — ring
    (seq) attention composes with the non-pipelined ``transformer_lm`` path.

    Shard params with ``PIPELINED_TRANSFORMER_RULES``
    (``distriflow_tpu/parallel/sharding.py``).
    """
    from distriflow_tpu.parallel.pipeline import (  # lazy: layer order
        gpipe,
        gpipe_1f1b,
        gpipe_remat,
    )

    if config is None:
        config = TransformerConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    if mesh is None or "pipe" not in mesh.shape or mesh.shape["pipe"] < 2:
        raise ValueError("pipelined_transformer_lm needs a mesh with pipe >= 2")
    # Backward-schedule choice. remat=True routes through gpipe_remat: an
    # input-only-residual custom backward recomputing each stage under
    # jax.vjp inside the backward shard_map (jax.checkpoint inside the
    # stage body does NOT compose with the hybrid manual/auto shard_map —
    # checkpoint residuals of auto-sharded stage params would need specs
    # over auto axes — so rematerialization is built into the schedule).
    # "1f1b" bounds live activations at P instead of M (many-microbatch /
    # long-context runs).
    schedules = {"gpipe": gpipe, "remat": gpipe_remat, "1f1b": gpipe_1f1b}
    schedule = config.pipeline_schedule or ("remat" if config.remat else "gpipe")
    if schedule not in schedules:
        raise ValueError(
            f"pipeline_schedule must be one of {sorted(schedules)}, "
            f"got {schedule!r}"
        )
    pipeline_fn = schedules[schedule]
    n_stages = mesh.shape["pipe"]
    if config.n_layers % n_stages:
        raise ValueError(
            f"n_layers {config.n_layers} not divisible by pipe axis {n_stages}"
        )
    per = config.n_layers // n_stages
    m = num_microbatches or n_stages

    resolved_loss = config.resolved_loss_for(mesh)
    embed_mod = _EmbedIn(config)
    head_mod = _HeadOut(config, loss_name=resolved_loss)
    stage_mod = StageBlocks(config, per=per)  # mesh=None: dense attn in-stage
    if example_batch is None:
        example_batch = mesh.shape["data"] * m

    def init(rng: jax.Array) -> Any:
        r_embed, r_head, *r_stages = jax.random.split(rng, 2 + n_stages)
        tokens = jnp.zeros((example_batch, example_seq), jnp.int32)
        embed_params = embed_mod.init(r_embed, tokens)
        h = jnp.zeros((example_batch, example_seq, config.d_model), config.dtype)
        # filter to trainable params: with MoE stages, init also creates the
        # sown 'aux' collection, which must not enter optimizer state
        stages = [{"params": stage_mod.init(r, h)["params"]} for r in r_stages]
        stacked = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stages)
        return {
            "embed": embed_params,
            "stages": stacked,
            "head": head_mod.init(r_head, h),
        }

    def apply(params: Any, tokens: jnp.ndarray) -> jnp.ndarray:
        h = embed_mod.apply(params["embed"], tokens)
        h = pipeline_fn(stage_mod.apply, params["stages"], h, mesh, m)
        return head_mod.apply(params["head"], h)

    return ModelSpec(
        init=init,
        apply=apply,
        loss=resolved_loss,
        input_shape=(example_seq,),
        output_shape=(config.vocab_size,),
        name="pipelined_transformer_lm",
    )


def transformer_lm(
    config: Optional[TransformerConfig] = None,
    mesh: Optional[Mesh] = None,
    example_seq: int = 128,
    example_batch: Optional[int] = None,
    **overrides: Any,
) -> ModelSpec:
    """ModelSpec for the causal LM. ``x`` = int32 tokens ``[B, S]``; ``y`` =
    int32 next-token ids ``[B, S]`` (sparse CE by default; set
    ``config.loss="softmax_cross_entropy"`` for one-hot ``[B, S, V]`` targets).

    ``example_batch`` sizes the init-trace dummy; with ring attention on a
    mesh it must be divisible by the ``data`` axis (defaults to exactly that).
    """
    if config is None:
        config = TransformerConfig(**overrides)
    elif overrides:
        config = dataclasses.replace(config, **overrides)
    module = TransformerLM(config, mesh)
    if example_batch is None:
        example_batch = mesh.shape["data"] if mesh is not None else 1

    def init(rng: jax.Array) -> Any:
        dummy = jnp.zeros((example_batch, example_seq), jnp.int32)
        variables = module.init(rng, dummy)
        # keep only trainable params: sown collections (MoE aux losses)
        # must not leak into the optimizer state
        return {"params": variables["params"]}

    apply_with_aux = None
    if config.n_experts > 0 and config.router_aux_weight > 0 and not config.moe_dense_dispatch:
        def apply_with_aux(params, tokens):
            logits, aux_vars = module.apply(params, tokens, mutable=["aux"])
            sown = jax.tree.leaves(aux_vars.get("aux", {}))
            aux = sum(sown) * (config.router_aux_weight / max(len(sown), 1))
            return logits, aux

    return ModelSpec(
        init=init,
        apply=module.apply,
        loss=config.resolved_loss_for(mesh),
        input_shape=(example_seq,),
        output_shape=(config.vocab_size,),
        name="transformer_lm",
        apply_with_aux=apply_with_aux,
    )
