"""A second model family for the decoding paths and the paged server:
latent attention (MLA) under a learned sparse selector, with a sigmoid-
routed expert layer of which this program holds a share.

The layer (pre-norm, RMSNorm, two residual adds; ``glm_moe_dsa`` /
DeepSeek-V3.2 lineage):

- **Latent attention, every layer.** ``c_q = RMSNorm(W_qa x)``, ``q = W_qb
  c_q`` per head ``[q_nope | q_rope]``; ``[c_kv | k_rope] = W_kva x``,
  ``c_kv = RMSNorm(c_kv)``, rotary (interleaved pairs) on ``q_rope`` and on
  the one shared ``k_rope``. The cache holds, per token, ``c_kv`` after its
  norm and ``k_rope`` after its rotation (``cached_latent``,
  ``kv_lora_rank + qk_rope_head_dim`` values, padded to a 128-lane tile). Attention runs in the
  absorbed form: ``W_kvb``'s key half is folded into the query, its value
  half is applied to the attended latent, so a cached token is never
  expanded per head.
- **Selector** in layers whose ``indexer_types`` entry is ``"full"``:
  ``q_I = W_Iq c_q`` per selector head, ``k_I = LayerNorm(W_Ik x)`` (one
  head; cached per token as ``cached_index_k``), rotary on their first
  ``index_rope_dim`` values, ``w = W_Iw x`` scaled by ``heads^-1/2
  dim^-1/2``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s
  <= t``; a token attends over the ``index_topk`` cached tokens of largest
  ``I`` (exactly ``lax.top_k``'s set, found without its sort:
  :func:`top_positions`), or over all of them while fewer exist. A
  ``"shared"`` layer attends over the set the nearest earlier ``"full"``
  layer chose for the same token, and has no selector weights or cache.
- **FFN.** ``"dense"`` layers: SwiGLU. ``"sparse"`` layers: ``s =
  sigmoid(W_r x)`` in float32 over all ``n_routed_experts``; the
  ``n_experts_per_tok`` largest of ``s + b`` are chosen (``b`` only
  chooses), gates are ``routed_scaling_factor * s / sum_chosen s``; plus a
  shared expert. No token is dropped. **The share:** ``experts_held =
  (first, count)`` are the experts this program holds; the router stays
  ``n_routed_experts`` wide, and the terms of experts not held are left
  out (they are the other chips' to add). An expert no live token chose
  is not run, so its weights are not read.

One routine serves prefill from an empty cache, continuation of ``s >= 1``
tokens (``extend``) and single-token decode: it writes the new tokens'
cache entries, then selects and attends against the cache, in blocks of
``query_block`` queries. The cache contract is ``TransformerLM``'s
(``models/generate.py``): ``cache_index`` scalar or per row, paged when a
``page_table`` leaf is present; retired rows (an all-sentinel table row)
are not routed, and where a paged call holds more than ``ROWS`` rows, as
the engine's decode step over its slots does, the selector and the
attention run over the rows the table backs and no others
(:func:`_over_live`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distriflow_tpu.models.generate import DecodeFamily
from distriflow_tpu.ops.expert_grouped import grouped_expert_terms

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class LatentSparseConfig:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    indexer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    d_ff: int
    moe_d_ff: int
    n_routed_experts: int
    n_experts_per_tok: int
    routed_scaling_factor: float
    experts_held: Tuple[int, int]
    max_seq: int
    index_rope_dim: int = 64
    rope_base: float = 8e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    #: what the RMSNorms and the router's affinities are computed in,
    #: whatever ``dtype`` is. Lowered only by a control that shows what a
    #: comparison with a reference can tell (benchmark/drivers/
    #: serve_sessions.py::precision_control).
    norm_router_dtype: Any = jnp.float32
    query_block: int = 128

    #: what :class:`ExpertShare` reads of its family (:data:`SCORING`): how it
    #: scores and weighs, and whether the held experts are one leaf a matrix
    #: kind (``ExpertShare._stacked``) or, as here, one a matrix an expert
    scoring_func = "sigmoid"
    experts_stacked = False

    def __post_init__(self):
        if not (len(self.indexer_types) == len(self.mlp_layer_types)
                == self.n_layers):
            raise ValueError("one indexer_types and one mlp_layer_types "
                             "entry per layer")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer has no earlier selection to "
                             "share: its indexer_types entry must be 'full'")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"[0, {self.n_routed_experts})")

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """``latent_dim`` rounded up to the chip's 128-lane tile: what a
        cached token takes. The chip tiles the minor axis by 128 either
        way; a 576-wide pool is handed over with its token axis minor, and
        every dispatch re-lays it out twice (PERF.md §6 PR 33)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def shared_d_ff(self) -> int:
        """Width of the shared expert: one routed expert's."""
        return self.moe_d_ff

    @property
    def decode_family(self) -> DecodeFamily:
        return _FAMILY

    def decode_work(self, ctx: Sequence[int], steps: int,
                    slots: int) -> Dict[str, int]:
        """What one decode dispatch of ``steps`` steps over live rows with
        ``ctx`` cached tokens each, among ``slots`` rows, selects and
        routes: ``sel_tokens`` (the tokens attention reads per step, summed
        over rows), ``assignments`` ((token, expert) choices over all
        sparse layers and steps, held here or not) and ``rows_run`` (the
        rows whose selection and attention the program computes, summed
        over steps: the live ones in whole groups of ``ROWS``, every layer
        of a step the same). The engine annotates and counts them."""
        sparse = sum(kind == "sparse" for kind in self.mlp_layer_types)
        run = slots if slots <= ROWS else -(-len(ctx) // ROWS) * ROWS
        return {"sel_tokens": sum(min(c, self.index_topk) for c in ctx),
                "assignments": len(ctx) * steps * sparse
                * self.n_experts_per_tok,
                "rows_run": run * steps}


def rope_interleaved(x: jnp.ndarray, pos: jnp.ndarray,
                     base: float) -> jnp.ndarray:
    """Rotary embedding on interleaved pairs ``(x0, x1), (x2, x3), ...``
    of the last axis. ``x`` is ``[B, s, ..., d]``, ``pos`` ``[B, s]``."""
    d = x.shape[-1]
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv  # [B, s, d/2]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _blocked(fn, block: int, *arrays):
    """``fn`` over blocks of ``block`` along axis 1 of every array (the
    query axis), results joined along axis 1: bounds the ``[queries,
    cached tokens]`` temporaries of a long prefill. A ragged tail is
    padded with zeros and its results dropped."""
    s = arrays[0].shape[1]
    if s <= block:
        return fn(*arrays)
    n = -(-s // block)
    pad = n * block - s
    split = [jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (a.shape[0], n, block) + a.shape[2:]), 1, 0) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return jax.tree.map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(
            (o.shape[1], n * block) + o.shape[3:])[:, :s], out)


ROWS = 4  # rows a trip of _over_live selects and attends for


def live_first(live: jnp.ndarray):
    """``(order, n_live)`` of ``live [B]``: the rows in an order that puts
    the live ones first, each kind in its own order (a cumsum, no sort),
    and how many are live."""
    n_live = jnp.sum(live, dtype=jnp.int32)
    at = jnp.where(live, jnp.cumsum(live) - 1,
                   n_live + jnp.cumsum(~live) - 1)
    order = jnp.zeros_like(at).at[at].set(
        jnp.arange(live.shape[0], dtype=at.dtype), unique_indices=True)
    return order, n_live


def _over_live(fn, rows, *arrays):
    """``fn(ids, *(a[ids] for a in arrays))`` for the first ``trips``
    groups of ``ROWS`` rows ``ids`` of ``order``, ``rows = (order,
    trips)``: a run-time trip count, one loop body whatever is live. Each
    result lands at its rows' places, and the rows of groups not run read
    zeros. What ``fn`` closes over (the pools) is the loop's operand, not
    its carry. A last group that would pass the end is moved back over
    rows already done."""
    order, trips = rows
    like = jax.eval_shape(fn, *(
        jax.ShapeDtypeStruct((ROWS,) + a.shape[1:], a.dtype)
        for a in (order,) + arrays))
    out = jax.tree.map(
        lambda o: jnp.zeros(order.shape + o.shape[1:], o.dtype), like)

    def trip(t, out):
        ids = jax.lax.dynamic_slice(order, (t * ROWS,), (ROWS,))
        got = fn(ids, *(a[ids] for a in arrays))
        return jax.tree.map(
            lambda o, g: o.at[ids].set(g, unique_indices=True), out, got)

    return jax.lax.fori_loop(0, trips, trip, out)


CHUNK = 128  # positions compacted together in top_positions: a lane tile


def _kth_largest(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per row of ``keys`` (uint32 ``[..., K]``) the largest ``t`` with at
    least ``k`` keys ``>= t``, ``[..., 1]``; 0 where a row has fewer than
    ``k`` keys above 0. Bit by bit from the top: 32 counts, no sort."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << jnp.asarray(31 - i, jnp.uint32))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, t)

    return jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(keys.shape[:-1] + (1,), jnp.uint32))


def top_positions(score: jnp.ndarray, visible: jnp.ndarray, k: int):
    """``lax.top_k``'s set without its sort of the whole row (8.8 ms for 32
    rows of 33,792 on a v5e, half of a decode step): the ``k`` visible
    positions of largest ``score`` (float32 ``[..., K]``), equal scores to
    the earlier position, as ``(idx, valid)``, both ``[..., k]``, in
    ascending position and not by score; ``valid`` is False past the
    visible positions of a row that has fewer than ``k``. Exact: the k-th
    largest score is found bit by bit, the chosen positions are compacted
    within chunks of ``CHUNK`` by a small sort and the chunks joined by
    one-hot sums (tests/test_latent_sparse.py holds it to ``top_k``'s set
    at 33,792 positions and k 2,048). One departure: ``-0.0`` ranks below
    ``0.0``, which ``top_k`` holds equal."""
    width = score.shape[-1]
    lead = score.shape[:-1]
    bits = jax.lax.bitcast_convert_type(score, jnp.uint32)
    # order-preserving: negative floats reversed, positive above them
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    keys = jnp.where(visible, keys, jnp.uint32(0))
    kth = _kth_largest(keys, k)
    above = keys > kth
    ties = (keys == kth) & visible
    need = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1) <= need))

    n_c = -(-width // CHUNK)
    chosen = jnp.pad(chosen, [(0, 0)] * len(lead) + [(0, n_c * CHUNK - width)])
    chosen = chosen.reshape(lead + (n_c, CHUNK))
    lane = jnp.arange(CHUNK, dtype=jnp.int32)
    # each chunk's chosen lanes first, in order; CHUNK marks the rest
    local = jnp.sort(jnp.where(chosen, lane, CHUNK), axis=-1)
    count = jnp.sum(chosen, axis=-1, dtype=jnp.int32)  # [..., n_c]
    upto = jnp.cumsum(count, axis=-1)
    slot = jnp.arange(k, dtype=jnp.int32)
    # slot j lies in the chunk that as many chunks end at or before
    chunk_of = jnp.sum(upto[..., None, :] <= slot[:, None], axis=-1,
                       dtype=jnp.int32)  # [..., k]
    valid = slot < upto[..., -1:]
    onehot = chunk_of[..., None] == jnp.arange(n_c, dtype=jnp.int32)
    first = jnp.sum(jnp.where(onehot, (upto - count)[..., None, :], 0), -1)
    # the chunk's list for every slot (lanes <= 128: exact in bfloat16)
    lists = jnp.einsum("...kc,...cl->...kl", onehot.astype(jnp.bfloat16),
                       local.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    within = jnp.sum(jnp.where((slot - first)[..., None] == lane, lists, 0.0),
                     axis=-1).astype(jnp.int32)
    idx = jnp.where(valid, chunk_of * CHUNK + within, 0)
    return idx, valid


def select_tokens(q_idx, w, keys, q_pos, topk: int):
    """The selector's choice. ``q_idx [B, s, J, d]``, ``w [B, s, J]``
    float32, ``keys [B, K, d]`` (every cached selector key of the row, in
    logical order), ``q_pos [B, s]``. Returns ``(idx, valid)``, both ``[B,
    s, min(topk, K)]``: the logical positions of the largest scores among
    ``k <= q_pos``, and which of them are such positions at all."""
    dots = jnp.einsum("bsjd,bkd->bsjk", q_idx, keys,
                      preferred_element_type=jnp.float32)
    score = jnp.einsum("bsjk,bsj->bsk", jax.nn.relu(dots), w)
    visible = jnp.arange(keys.shape[1])[None, None, :] <= q_pos[..., None]
    return top_positions(score, visible, min(topk, keys.shape[1]))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.float32  # computed in; the result is cast to x's

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        xf = x.astype(self.dtype)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = (xf * jax.lax.rsqrt(var + jnp.asarray(self.eps, self.dtype))
               * scale.astype(self.dtype)).astype(x.dtype)
        # read only by a caller that asks for "intermediates"
        self.sow("intermediates", "io", (x, out))
        return out


def _norm(cfg: LatentSparseConfig, name: str) -> RMSNorm:
    return RMSNorm(cfg.rms_eps, cfg.norm_router_dtype, name=name)


def _dense(cfg: Any, features, name: str, **kw):
    return nn.DenseGeneral(features, name=name, use_bias=False,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype, **kw)


class LatentSparseAttention(nn.Module):
    config: LatentSparseConfig
    layer: int

    @nn.compact
    def __call__(self, x, selection, rows):
        """``selection`` is the ``(idx, valid, where)`` of the nearest
        earlier ``full`` layer, or None: the chosen logical positions,
        which of them are positions at all, and where each lies in the
        (flattened) cache. ``rows`` is the first layer's ``(order,
        trips)`` for :func:`_over_live`, or None in the first layer and
        wherever all rows are run at once. Returns ``(out, selection, rows, live)``: ``live
        [B]`` says which rows the page table backs (None unless paged)."""
        cfg = self.config
        b, s, _ = x.shape
        full = cfg.indexer_types[self.layer] == "full"
        nope, rope, lat = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.latent_dim)

        latent_var = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (b, cfg.max_seq, cfg.latent_width), cfg.dtype)
        if full:
            index_var = self.variable(
                "cache", "cached_index_k", jnp.zeros,
                (b, cfg.max_seq, cfg.index_head_dim), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        # the engine's two switches, read from the cache's structure as in
        # models/transformer.py::_decode_attend: a page_table leaf means
        # pools of pages, a [B] cache_index means one depth per row
        paged = self.has_variable("cache", "page_table")
        table = (self.variable("cache", "page_table",
                               lambda: jnp.zeros((0, 0), jnp.int32)).value
                 if paged else None)
        # queries a block, fewer for more rows: the [rows, queries, cached
        # tokens] temporaries of a group of turns stay those of one
        block = max(8, cfg.query_block >> (b - 1).bit_length())
        live = (table[:, 0] < latent_var.value.shape[0]) if paged else None
        if self.layer == 0 and paged and b > ROWS:
            # the step's rows, once for every layer and shared index set:
            # more rows than a group means slots, of which some are retired
            order, n_live = live_first(live)
            rows = (order, -(-n_live // ROWS))  # groups that hold a live row
            # read only by a caller that asks for "intermediates"
            self.sow("intermediates", "rows_run", rows[1] * ROWS)
        pos0 = idx if idx.ndim == 1 else jnp.broadcast_to(idx, (b,))
        q_pos = pos0[:, None] + jnp.arange(s)[None, :]  # [B, s]

        c_q = _norm(cfg, "q_a_norm")(
            _dense(cfg, cfg.q_lora_rank, "q_a_proj")(x))
        q = _dense(cfg, (cfg.n_heads, nope + rope), "q_b_proj")(c_q)
        kv = _dense(cfg, lat, "kv_a_proj")(x)
        c_kv = _norm(cfg, "kv_a_norm")(kv[..., :cfg.kv_lora_rank])
        k_rope = rope_interleaved(kv[..., cfg.kv_lora_rank:], q_pos,
                                  cfg.rope_base)
        q_rope = rope_interleaved(q[..., nope:], q_pos, cfg.rope_base)
        # W_kvb in its two halves, as the absorbed form consumes them
        init_b = nn.initializers.lecun_normal(in_axis=0, out_axis=(1, 2))
        w_kb = self.param("k_b_proj", init_b, (
            cfg.kv_lora_rank, cfg.n_heads, nope), cfg.param_dtype).astype(
                cfg.dtype)
        w_vb = self.param("v_b_proj", init_b, (
            cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim),
            cfg.param_dtype).astype(cfg.dtype)

        def store(buf, new):
            """``new [B, s, F]`` at each row's own positions."""
            if paged:
                n_pg, ps = buf.shape[0], buf.shape[1]
                pp = table.shape[1] - 1  # the last column is the sentinel
                pg = jnp.minimum(q_pos // ps, pp)
                phys = table[jnp.arange(b)[:, None], pg]
                flat = phys * ps + q_pos % ps  # sentinel: past the pool, dropped
                return buf.reshape(n_pg * ps, -1).at[flat].set(new).reshape(
                    buf.shape)
            if idx.ndim == 1:
                return buf.at[jnp.arange(b)[:, None], q_pos].set(new)
            return jax.lax.dynamic_update_slice(buf, new, (0, idx, 0))

        def per_row(fn, *arrays):
            """``fn(ids, *arrays)`` over all rows at once (``ids`` None),
            or over the live ones in groups (``ids`` the group's rows)."""
            if rows is None:
                return fn(None, *arrays)
            return _over_live(fn, rows, *arrays)

        def entries(buf, tab):
            """Every cached entry of the rows in logical order, ``[b, K,
            F]``: the slab itself, or the rows' pages by their table
            rows ``tab``."""
            if not paged:
                return buf
            tab = jnp.minimum(tab[:, :-1], buf.shape[0] - 1)
            return buf[tab].reshape(tab.shape[0], -1, buf.shape[-1])

        def locate(sel, tab):
            """Logical positions ``sel [b, q, T]`` as rows of the pool
            flattened over pages (paged), else as they are. The page
            table is read by a one-hot sum: a gather of one number a
            position costs more than the attention it feeds."""
            if not paged:
                return sel
            n_pg, ps = latent_var.value.shape[:2]
            page = sel // ps
            phys = jnp.sum(jnp.where(
                page[..., None] == jnp.arange(tab.shape[1]),
                tab[:, None, None, :], 0), axis=-1)
            return jnp.minimum(phys, n_pg - 1) * ps + sel % ps

        def pick(buf, where):
            """The cache entries at ``where [B, q, T]`` (see locate)."""
            if paged:
                return buf.reshape(-1, buf.shape[-1])[where]
            return jnp.take_along_axis(
                buf, where.reshape(b, -1, 1), axis=1).reshape(
                    where.shape + (buf.shape[-1],))

        lane_pad = jnp.zeros((b, s, cfg.latent_width - lat), cfg.dtype)
        latent_var.value = store(latent_var.value, jnp.concatenate(
            [c_kv, k_rope, lane_pad], axis=-1).astype(cfg.dtype))

        if full:
            with jax.named_scope("dsa_indexer"):
                rd = cfg.index_rope_dim
                q_idx = _dense(cfg, (cfg.index_n_heads, cfg.index_head_dim),
                               "index_q_proj")(c_q)
                k_idx = nn.LayerNorm(
                    epsilon=1e-6, name="index_k_norm", dtype=cfg.dtype,
                    param_dtype=jnp.float32)(
                        _dense(cfg, cfg.index_head_dim, "index_k_proj")(x))
                q_idx = jnp.concatenate(
                    [rope_interleaved(q_idx[..., :rd], q_pos, cfg.rope_base),
                     q_idx[..., rd:]], axis=-1)
                k_idx = jnp.concatenate(
                    [rope_interleaved(k_idx[..., :rd], q_pos, cfg.rope_base),
                     k_idx[..., rd:]], axis=-1)
                w = _dense(cfg, cfg.index_n_heads, "index_w_proj")(x).astype(
                    jnp.float32) * (cfg.index_n_heads ** -0.5
                                    * cfg.index_head_dim ** -0.5)
                index_var.value = store(index_var.value,
                                        k_idx.astype(cfg.dtype))

                def select(ids, *queries):
                    tab = table if ids is None else table[ids]
                    keys = entries(index_var.value, tab)

                    def choose(qi, wi, pi):
                        sel, valid = select_tokens(qi, wi, keys, pi,
                                                   cfg.index_topk)
                        return sel, valid, locate(sel, tab)

                    return _blocked(choose, block, *queries)

                selection = per_row(select, q_idx, w, q_pos)
                # read only by a caller that asks for "intermediates"
                self.sow("intermediates", "selected", selection[:2])
        ci.value = idx + s

        with jax.named_scope("dsa_attend"):
            # absorbed: the query in latent space, one key/value per token
            q_lat = jnp.einsum("bshd,chd->bshc", q[..., :nope], w_kb)
            q_cat = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros(
                    q_rope.shape[:-1] + (cfg.latent_width - lat,),
                    q_rope.dtype)], axis=-1)  # [B, s, H, latent_width]
            scale = 1.0 / math.sqrt(nope + rope)
            pool = latent_var.value

            def attend(qc, _sel, valid, where):
                got = pick(pool, where)  # [B, q, T, latent_width]
                scores = jnp.einsum(
                    "bqhl,bqtl->bqht", qc, got,
                    preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(
                    jnp.where(valid[:, :, None, :], scores, NEG), axis=-1)
                return jnp.einsum("bqht,bqtc->bqhc", p.astype(cfg.dtype),
                                  got[..., :cfg.kv_lora_rank],
                                  preferred_element_type=jnp.float32)

            o_lat = per_row(
                lambda _ids, *group: _blocked(attend, block, *group),
                q_cat, *selection)
            out = jnp.einsum("bshc,chv->bshv", o_lat.astype(cfg.dtype), w_vb)
        out = _dense(cfg, cfg.d_model, "o_proj", axis=(-2, -1))(out)
        return out, selection, rows, live


class SwiGLU(nn.Module):
    config: Any  # ``dtype`` and ``param_dtype`` are read
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate = _dense(cfg, self.width, "gate_proj")(x)
        up = _dense(cfg, self.width, "up_proj")(x)
        return _dense(cfg, cfg.d_model, "down_proj")(jax.nn.silu(gate) * up)


def router_affinity(x: jnp.ndarray, router: jnp.ndarray,
                    dtype: Any = jnp.float32) -> jnp.ndarray:
    """``sigmoid(x W_r)`` in float32 whatever the compute dtype: a choice
    among 256 near-equal scores does not survive bfloat16."""
    return jax.nn.sigmoid(jnp.dot(
        x.astype(dtype), router.astype(dtype),
        precision=jax.lax.Precision.HIGHEST)).astype(jnp.float32)


def router_logits(x: jnp.ndarray, router: jnp.ndarray,
                  dtype: Any = jnp.float32) -> jnp.ndarray:
    """``x W_r`` in float32, as :func:`router_affinity` and for its reason."""
    return jnp.dot(x.astype(dtype), router.astype(dtype),
                   precision=jax.lax.Precision.HIGHEST).astype(jnp.float32)


def route(scores: jnp.ndarray, bias: jnp.ndarray, k: int, scaling: float):
    """``scores [T, E]`` float32 sigmoid affinities. The ``k`` largest of
    ``scores + bias`` are chosen; their gates are ``scaling * score / sum
    of the chosen scores``. Returns the dense ``[T, E]`` gate matrix."""
    _, chosen = jax.lax.top_k(scores + bias, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(chosen, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("tk,tke->te", gates, onehot)


def route_softmax_topk(logits: jnp.ndarray, k: int):
    """``logits [T, E]`` float32. The ``k`` largest are chosen; their gates
    are the softmax over those ``k`` values alone (no bias, no scaling).
    Returns the dense ``[T, E]`` gate matrix."""
    top, chosen = jax.lax.top_k(logits, k)
    onehot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=logits.dtype)
    return jnp.einsum("tk,tke->te", jax.nn.softmax(top, axis=-1), onehot)


#: tokens from which :class:`ExpertShare` runs stacked experts in one loop
DENSE_TOKENS = 64

#: the scoring rules of :class:`ExpertShare`, a configuration's
#: ``scoring_func``: sigmoid affinities, a bias that only chooses, gates
#: normalised over the chosen and scaled (:func:`route`); or the top-k of
#: the logits and a softmax over the chosen (:func:`route_softmax_topk`)
SCORING = ("sigmoid", "softmax_topk")


def _expert_term(xc, w_gate, w_up, w_down, gate):
    """One expert's gated MLP over the tokens ``xc``, weighed by its
    ``gate [T]`` (0 for a token that did not choose it), float32."""
    h = jax.nn.silu(xc @ w_gate.astype(xc.dtype)) * (
        xc @ w_up.astype(xc.dtype))
    out = (h @ w_down.astype(xc.dtype)).astype(jnp.float32)
    return out * gate[:, None]


class ExpertShare(nn.Module):
    """The sparse FFN as the holder of ``experts_held`` computes it: the
    shared expert plus its own experts' terms, dropless. ``config`` is any
    family's that has ``d_model`` wide tokens and the attributes read here
    (``experts_held``, ``moe_d_ff``, ``shared_d_ff``, ``n_routed_experts``,
    ``n_experts_per_tok``, ``scoring_func`` and, for ``"sigmoid"``,
    ``routed_scaling_factor``; ``experts_stacked``; ``dtype``,
    ``param_dtype``, ``norm_router_dtype``)."""
    config: Any

    def _leaves(self, shapes, count):
        """One leaf per matrix per expert: a stacked array handed to the
        conditional in slices would be sliced (copied) ahead of it, chosen
        or not. They are drawn stacked, one draw a matrix kind, and cut into
        leaves: a draw a leaf (192 of them) was two minutes of compiling the
        seeded init."""
        cfg = self.config
        drawn = {}
        if self.is_initializing():
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            keys = jax.random.split(self.make_rng("params"), len(shapes))
            for key, (kind, shape) in zip(keys, shapes.items()):
                drawn[kind] = init(key, (count,) + shape, cfg.param_dtype)

        def cut(e, kind):  # flax also asks, outside init, for the shape alone
            return lambda _: (drawn[kind][e] if drawn else
                              jnp.zeros(shapes[kind], cfg.param_dtype))

        return [tuple(self.param(f"expert_{e}_{kind}", cut(e, kind))
                      for kind in shapes) for e in range(count)]

    def _stacked(self, stack, xc, gates, y):
        """``y`` plus the held experts' terms from stacked weights ``stack =
        (gate, up, down)``, each ``[count, ...]``. A few tokens (a decode
        step): one kernel that streams the experts some token chose back to
        back, so that an expert nobody chose is not read
        (``ops/expert_grouped.py``). ``DENSE_TOKENS`` or more (a prefill,
        where every expert is chosen by some token): one loop body for all
        experts, each run over every token with its gates (0 where it was
        not chosen)."""
        if xc.shape[0] >= DENSE_TOKENS:
            return jax.lax.scan(
                lambda y, one: (y + _expert_term(xc, *one), None), y,
                stack + (gates.T,))[0]
        return y + grouped_expert_terms(xc, gates, *stack)

    @nn.compact
    def __call__(self, x, live):
        cfg = self.config
        b, s, d = x.shape
        first, count = cfg.experts_held
        f = cfg.moe_d_ff
        flat = x.reshape(b * s, d)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, cfg.n_routed_experts), jnp.float32)
        if cfg.scoring_func not in SCORING:
            raise ValueError(f"scoring_func {cfg.scoring_func!r} is not one "
                             f"of {SCORING}")
        sigmoid = cfg.scoring_func == "sigmoid"
        if sigmoid:
            bias = self.param("e_score_correction_bias",
                              nn.initializers.normal(0.05),
                              (cfg.n_routed_experts,), jnp.float32)
        shapes = {"gate": (d, f), "up": (d, f), "down": (f, d)}
        if cfg.experts_stacked:
            # one leaf per matrix kind, ``[count, ...]``: an expert's slice is
            # a block of the kernel's grid or a trip of the loop
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            stack = tuple(self.param(f"experts_{kind}", init, (count,) + shape,
                                     cfg.param_dtype)
                          for kind, shape in shapes.items())
            experts = None
        else:
            experts = self._leaves(shapes, count)
        if sigmoid:
            affinity = router_affinity(flat, router, cfg.norm_router_dtype)
            gates = route(affinity, bias, cfg.n_experts_per_tok,
                          cfg.routed_scaling_factor)
        else:  # what is sown below as the affinity is then the logits
            affinity = router_logits(flat, router, cfg.norm_router_dtype)
            gates = route_softmax_topk(affinity, cfg.n_experts_per_tok)
        gates = gates[:, first:first + count]
        if live is not None:  # a retired row routes nowhere
            gates = gates * jnp.repeat(live, s)[:, None]
        hit = jnp.any(gates > 0, axis=0)  # [count]
        # read only by a caller that asks for "intermediates"
        self.sow("intermediates", "routed", gates > 0)
        self.sow("intermediates", "router_io", (flat, affinity))
        # what the share did, for the engine's counters: experts run and
        # (token, expert) assignments that landed here, summed over calls
        stats = self.variable("cache", "expert_stats",
                              lambda: jnp.zeros((2,), jnp.int32))
        stats.value = stats.value + jnp.stack(
            [jnp.sum(hit), jnp.sum(gates > 0)]).astype(jnp.int32)

        y = SwiGLU(cfg, cfg.shared_d_ff, name="shared_expert")(flat).astype(
            jnp.float32)
        xc = flat.astype(cfg.dtype)
        if experts is None:
            y = self._stacked(stack, xc, gates, y)
        else:
            for e, (w_gate, w_up, w_down) in enumerate(experts):
                # an expert nobody chose is not run: its weights are not read
                y = y + jax.lax.cond(
                    hit[e], lambda *one: _expert_term(xc, *one),
                    lambda *_: jnp.zeros((b * s, d), jnp.float32),
                    w_gate, w_up, w_down, gates[:, e])
        return y.astype(x.dtype).reshape(b, s, d)


class LatentSparseBlock(nn.Module):
    config: LatentSparseConfig
    layer: int

    @nn.compact
    def __call__(self, x, selection, rows):
        cfg = self.config
        attn, selection, rows, live = LatentSparseAttention(
            cfg, self.layer, name="attn")(
                _norm(cfg, "input_norm")(x), selection, rows)
        x = x + attn
        h = _norm(cfg, "post_attn_norm")(x)
        if cfg.mlp_layer_types[self.layer] == "dense":
            y = SwiGLU(cfg, cfg.d_ff, name="mlp")(h)
        else:
            with jax.named_scope("moe_experts"):
                y = ExpertShare(cfg, name="mlp")(h, live)
        return x + y, selection, rows


class LatentSparseLM(nn.Module):
    """Decode-mode only: ``apply(..., mutable=["cache"])``; called on an
    empty cache it is the teacher-forced forward."""
    config: LatentSparseConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="embed",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)(tokens)
        selection = rows = None
        for i in range(cfg.n_layers):
            x, selection, rows = LatentSparseBlock(
                cfg, i, name=f"layers_{i}")(x, selection, rows)
        x = _norm(cfg, "norm")(x)
        return _dense(cfg, cfg.vocab_size, "lm_head")(x).astype(jnp.float32)


def _score_logits(config: LatentSparseConfig):
    module = LatentSparseLM(config)
    return lambda params, tokens: module.apply(
        params, tokens, mutable=["cache"])[0]


_FAMILY = DecodeFamily(("cached_latent", "cached_index_k"), LatentSparseLM,
                       _score_logits, work_leaf="expert_stats")


def init_params(config: LatentSparseConfig, rng: jax.Array) -> Any:
    """``{"params": ...}`` from ``rng``, made in one jitted call."""
    module = LatentSparseLM(config)
    return jax.jit(lambda key: {"params": module.init(
        key, jnp.zeros((1, 2), jnp.int32))["params"]})(rng)
