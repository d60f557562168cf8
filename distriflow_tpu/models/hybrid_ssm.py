"""A third model family for the decoding paths and the paged server:
Mamba-2 state-space layers around grouped-KV attention layers without
positional embedding, every layer followed by a softmax-routed expert layer
of which this program holds a share, beside a shared gated MLP
(``granitemoehybrid`` lineage; four scalar multipliers).

The layer, with ``r = residual_multiplier`` (pre-norm, RMSNorm, scale only):

    x <- x + r * Mixer(RMSNorm(x));  u = RMSNorm(x);  x <- x + r * (MoE(u) + SharedMLP(u))

embedding output times ``embedding_multiplier``; final RMSNorm; logits ``h
E^T / logits_scaling`` with the tied embedding ``E``.

- **Mamba-2 mixer** (``H`` heads of ``P``, state ``N``, one group, causal
  depthwise conv of width ``K``): ``[z | xBC | dt] = in_proj(u)``; ``xBC <-
  SiLU(conv_K(xBC) + b)`` split ``x [H, P] | B [N] | C [N]``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; per head in
  ``ssm_dtype`` (float32): ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer)
  B_t``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * SiLU(z)) w``;
  ``out_proj``. A row's cache is ``S`` (``ssm_state``, ``[H, P, N]``) and
  the last ``K - 1`` pre-conv ``xBC`` rows (``conv_state``): fixed size, no
  token axis (``DecodeFamily.slot_leaves``). Several tokens at once (prefill,
  ``extend``) run the **chunked form**: within a chunk of
  ``mamba_chunk_size`` the masked ``C B^T`` product, weighted by the decay
  between the two positions, applied to ``dt x``, and the carried state's
  part; across chunks the state, in a ``lax.scan`` over chunks. The state's
  own update is a float32 matmul at ``HIGHEST`` precision. One token is the
  recurrence's one step, and where a paged call holds more than ``ROWS``
  rows (the engine's decode step over its slots) it reads and writes the
  state of the rows the page table backs and no others.
- **Attention**: ``n_heads`` query heads over ``n_kv_heads`` cached heads
  (each serving ``n_heads / n_kv_heads`` queries; K and V are never
  repeated in memory), no rotation, causal softmax of ``q k^T *
  attention_multiplier``. Pools ``cached_k`` / ``cached_v`` of ``n_kv_heads
  * head_dim`` values a token, paged as ``TransformerLM``'s.
- **FFN**: :class:`~distriflow_tpu.models.latent_sparse.ExpertShare` under
  ``scoring_func = "softmax_topk"``: the ``n_experts_per_tok`` largest
  float32 router logits, gates their softmax, the terms of ``experts_held``
  added and the other experts' left out; plus the shared MLP.

One routine serves prefill from an empty cache, continuation (``extend``)
and single-token decode. The cache contract is ``TransformerLM``'s
(``models/generate.py``) plus the per-row state: ``cache_index`` scalar or
per row in every layer, paged when a ``page_table`` leaf is present (a
retired row's table row is all sentinel: such a row routes to no expert).
The state is not a function of cached pages, so the family is not
``prefix_reusable``: no prefix hit, no speculative rollback.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distriflow_tpu.models.generate import DecodeFamily
from distriflow_tpu.models.latent_sparse import (
    NEG,
    ROWS,
    ExpertShare,
    RMSNorm,
    _blocked,
    _dense,
    _over_live,
    live_first,
)

LAYER_TYPES = ("mamba", "attention")
#: what the conv's weight is drawn with (normal, zero bias, as the published
#: ``_init_weights`` draws a Conv1d). Small enough that the conv's output
#: stays in SiLU's linear range and ``x``, ``B`` and ``C`` are zero-mean: at
#: torch's Conv1d default (uniform in +-1/2, the bias too) they carry a
#: positive mean, ``C . B`` sums coherently over the state's dimensions and
#: over the sequence, and every layer adds one and the same vector to every
#: token's residual: ten layers on, all rows of a decode step choose the
#: same experts (PERF.md 6, PR 35).
CONV_INIT_STD = 0.05


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    attention_multiplier: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    moe_d_ff: int
    shared_d_ff: int
    n_routed_experts: int
    n_experts_per_tok: int
    experts_held: Tuple[int, int]
    max_seq: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    #: what the RMSNorms and the router's logits are computed in
    norm_router_dtype: Any = jnp.float32
    #: what ``dt``, the decay and the state are computed and kept in. Both
    #: are lowered only by a control that shows what a comparison with a
    #: reference can tell (benchmark/drivers/serve_chat_hybrid.py).
    ssm_dtype: Any = jnp.float32
    #: standard deviation the tied embedding is drawn with; None is flax's
    embed_init_std: Any = None
    query_block: int = 256

    def __post_init__(self):
        if not self.layer_types or any(
                kind not in LAYER_TYPES for kind in self.layer_types):
            raise ValueError(f"layer_types entries are of {LAYER_TYPES}")
        if "attention" not in self.layer_types:
            # the engine counts pages by the attention layers' pools
            raise ValueError("at least one layer_types entry is 'attention'")
        if self.n_heads % self.n_kv_heads or self.d_model % self.n_heads:
            raise ValueError("n_heads divides d_model and n_kv_heads n_heads")
        first, count = self.experts_held
        if not 0 <= first < first + count <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"[0, {self.n_routed_experts})")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """``x | B | C``: what the conv runs over (one group)."""
        return self.d_inner + 2 * self.mamba_d_state

    #: what :class:`~distriflow_tpu.models.latent_sparse.ExpertShare` reads:
    #: the top-k of the logits and a softmax over the chosen; the held
    #: experts one leaf a matrix kind (``ExpertShare._stacked``)
    scoring_func = "softmax_topk"
    experts_stacked = True

    @property
    def decode_family(self) -> DecodeFamily:
        return _FAMILY

    def row_state_bytes(self) -> int:
        """What one row's recurrent state takes, over the Mamba layers."""
        n = sum(kind == "mamba" for kind in self.layer_types)
        state = (self.mamba_n_heads * self.mamba_d_head * self.mamba_d_state
                 * jnp.dtype(self.ssm_dtype).itemsize)
        conv = ((self.mamba_d_conv - 1) * self.conv_dim
                * jnp.dtype(self.dtype).itemsize)
        return n * (state + conv)

    def decode_work(self, ctx: Sequence[int], steps: int,
                    slots: int) -> Dict[str, int]:
        """What one decode dispatch of ``steps`` steps over ``len(ctx)`` live
        rows among ``slots`` does: ``assignments`` ((token, expert) choices
        over all layers and steps, held here or not) and ``rows_run`` (the
        rows whose state a Mamba layer reads and writes, summed over steps:
        the live ones in whole groups of ``ROWS``, every layer of a step
        the same). The engine annotates and counts them."""
        run = slots if slots <= ROWS else -(-len(ctx) // ROWS) * ROWS
        return {"assignments": len(ctx) * steps * self.n_layers
                * self.n_experts_per_tok, "rows_run": run * steps}


def _norm(cfg: HybridSSMConfig, name: str) -> RMSNorm:
    return RMSNorm(cfg.rms_eps, cfg.norm_router_dtype, name=name)


def _live_rows(module: nn.Module, b: int, first: bool):
    """``(table, live, rows)`` of a layer's cache: the page table (None
    unless paged), which rows it backs (the table's last column is pinned
    at the sentinel, so a row whose first entry equals it holds no page)
    and, in the ``first`` layer of a paged call of more than ``ROWS`` rows,
    the step's ``(order, trips)`` for the live-row loops."""
    if not module.has_variable("cache", "page_table"):
        return None, None, None
    table = module.variable("cache", "page_table",
                            lambda: jnp.zeros((0, 0), jnp.int32)).value
    live = table[:, 0] != table[:, -1]
    rows = None
    if first and b > ROWS:
        order, n_live = live_first(live)
        rows = (order, -(-n_live // ROWS))  # groups that hold a live row
        # read only by a caller that asks for "intermediates"
        module.sow("intermediates", "rows_run", rows[1] * ROWS)
    return table, live, rows


def ssm_chunk_scan(x, dt, b_in, c_in, a, state, chunk: int, dtype: Any):
    """The chunked form of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer)
    B_t``, ``y_t = S_t C_t``. ``x [B, L, H, P]``, ``dt [B, L, H]`` (after its
    softplus), ``b_in`` / ``c_in [B, L, N]``, ``a [H]``, ``state [B, H, P,
    N]``; ``dt``, ``a`` and ``state`` in the dtype the recurrence is kept in.
    Returns ``(y [B, L, H, P] float32, final state)``. Within a chunk the
    work is matmuls in ``dtype`` with float32 accumulation; the state's own
    update is a matmul of its dtype at ``HIGHEST`` precision."""
    bsz, length, heads, p = x.shape
    keep = state.dtype
    q = min(chunk, length)
    n_c = -(-length // q)
    pad = n_c * q - length

    def chunks(v):
        # a padded position has dt 0: it decays nothing and adds nothing
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return jnp.moveaxis(v.reshape((bsz, n_c, q) + v.shape[2:]), 1, 0)

    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]  # [t, s]

    def one(s_prev, inp):
        xq, dtq, bq, cq = inp
        log_decay = dtq * a  # [B, q, H]
        cs = jnp.cumsum(log_decay, axis=1)  # falling from 0
        xdt = xq.astype(keep) * dtq[..., None]  # [B, q, H, P]
        # within the chunk: y_t += sum_{s<=t} exp(cs_t - cs_s) (C_t.B_s) dt_s x_s
        g = jnp.einsum("btn,bsn->bts", cq, bq,
                       preferred_element_type=jnp.float32)
        seg = cs[:, :, None, :] - cs[:, None, :, :]  # [B, t, s, H]
        weigh = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        m = (g[..., None] * weigh).astype(dtype)
        y = jnp.einsum("btsh,bshp->bthp", m, xdt.astype(dtype),
                       preferred_element_type=jnp.float32)
        # the carried state's part: y_t += exp(cs_t) S_prev C_t
        y = y + jnp.einsum("bhpn,btn->bthp", s_prev.astype(dtype), cq,
                           preferred_element_type=jnp.float32) * jnp.exp(
                               cs).astype(jnp.float32)[..., None]
        # the state at the chunk's end. A position's decay up to there is
        # summed from the end: the difference of two running sums from the
        # start loses, for the recent positions that weigh most, what the
        # sums' size costs (1e-4 of the state at 256 positions in float32)
        to_end = jnp.exp(jnp.flip(jnp.cumsum(jnp.flip(log_decay, 1), axis=1),
                                  1) - log_decay)  # [B, q, H]
        s_new = s_prev * jnp.exp(cs[:, -1])[:, :, None, None] + jnp.einsum(
            "bshp,bsn->bhpn", xdt * to_end[..., None], bq.astype(keep),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=keep)
        return s_new, y

    state, y = jax.lax.scan(one, state, tuple(
        chunks(v) for v in (x, dt, b_in, c_in)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, n_c * q, heads, p)[:, :length]
    return y, state


def ssm_step(x, dt, b_in, c_in, a, state):
    """One token of the recurrence: ``x [B, H, P]``, ``dt [B, H]``, ``b_in``
    / ``c_in [B, N]``, ``state [B, H, P, N]``. Returns ``(y [B, H, P]
    float32, new state)``."""
    keep = state.dtype
    decay = jnp.exp(dt * a)[:, :, None, None]
    add = (x.astype(keep) * dt[..., None])[..., None] * b_in.astype(
        keep)[:, None, None, :]
    state = state * decay + add
    y = jnp.sum(state * c_in.astype(keep)[:, None, None, :], axis=-1)
    return y.astype(jnp.float32), state


class Mamba2Mixer(nn.Module):
    config: HybridSSMConfig
    layer: int

    @nn.compact
    def __call__(self, u, rows):
        """``rows`` is the first layer's ``(order, trips)`` or None (see
        :func:`_live_rows`). Returns ``(out, rows, live)``."""
        cfg = self.config
        b, s, _ = u.shape
        heads, p, n, k = (cfg.mamba_n_heads, cfg.mamba_d_head,
                          cfg.mamba_d_state, cfg.mamba_d_conv)
        keep = cfg.ssm_dtype
        state_var = self.variable("cache", "ssm_state", jnp.zeros,
                                  (b, heads, p, n), keep)
        conv_var = self.variable("cache", "conv_state", jnp.zeros,
                                 (b, k - 1, cfg.conv_dim), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        _table, live, found = _live_rows(self, b, self.layer == 0)
        rows = found if found is not None else rows

        proj = _dense(cfg, cfg.d_inner + cfg.conv_dim + heads, "in_proj")(u)
        z = proj[..., :cfg.d_inner]
        xbc = proj[..., cfg.d_inner:cfg.d_inner + cfg.conv_dim]
        dt = proj[..., cfg.d_inner + cfg.conv_dim:]
        with jax.named_scope("ssm_conv"):
            w = self.param("conv_weight", nn.initializers.normal(CONV_INIT_STD),
                           (k, cfg.conv_dim), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros,
                              (cfg.conv_dim,), jnp.float32)
            hist = jnp.concatenate([conv_var.value, xbc.astype(cfg.dtype)],
                                   axis=1)  # [B, K - 1 + s, C]
            conv = bias + sum(hist[:, i:i + s].astype(jnp.float32) * w[i]
                              for i in range(k))
            xbc = jax.nn.silu(conv).astype(cfg.dtype)
            conv_var.value = hist[:, s:]
        x = xbc[..., :cfg.d_inner].reshape(b, s, heads, p)
        b_in = xbc[..., cfg.d_inner:cfg.d_inner + n]
        c_in = xbc[..., cfg.d_inner + n:]

        def inv_softplus_dt(key, shape):
            # the published initialisation: dt log-uniform in [0.001, 0.1]
            lo, hi = math.log(1e-3), math.log(1e-1)
            d = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            return d + jnp.log(-jnp.expm1(-d))

        dt_bias = self.param("dt_bias", inv_softplus_dt, (heads,))
        a_log = self.param("A_log", lambda _key, shape: jnp.log(
            jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), (heads,))
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(keep) + dt_bias.astype(keep))
        a = -jnp.exp(a_log.astype(keep))

        if s > 1:
            with jax.named_scope("ssm_scan"):
                y, new = ssm_chunk_scan(x, dt, b_in, c_in, a, state_var.value,
                                        cfg.mamba_chunk_size, cfg.dtype)
            # read only by a caller that asks for "intermediates"
            self.sow("intermediates", "ssm_io",
                     (x, dt, b_in, c_in, state_var.value, new))
        else:
            with jax.named_scope("ssm_step"):
                args = (x[:, 0], dt[:, 0], b_in[:, 0], c_in[:, 0])
                if rows is None:
                    y, new = ssm_step(*args, a, state_var.value)
                else:
                    y, new = _step_live(rows, args, a, state_var.value)
                y = y[:, None]
        state_var.value = new
        ci.value = ci.value + s

        y = y + skip[:, None] * x.astype(jnp.float32)
        nd = cfg.norm_router_dtype
        gated = y.reshape(b, s, cfg.d_inner).astype(nd) * jax.nn.silu(
            z.astype(nd))
        y = _norm(cfg, "norm")(gated).astype(cfg.dtype)
        return _dense(cfg, cfg.d_model, "out_proj")(y), rows, live


def _step_live(rows, args, a, state):
    """:func:`ssm_step` for the first ``trips`` groups of ``ROWS`` rows of
    ``order``, ``rows = (order, trips)``: a run-time trip count, the state
    the loop's carry, read and written at the group's rows alone. The
    other rows' state stays and their ``y`` reads zeros."""
    order, trips = rows
    # an index past the rows fills the last group: read clamped, its write
    # dropped, so that no row is stepped twice
    order = jnp.concatenate([order, jnp.full(
        (-order.shape[0] % ROWS,), order.shape[0], order.dtype)])
    y0 = jnp.zeros(state.shape[:3], jnp.float32)

    def trip(t, carry):
        state, y = carry
        ids = jax.lax.dynamic_slice(order, (t * ROWS,), (ROWS,))
        got, new = ssm_step(*(v[ids] for v in args), a, state[ids])
        return state.at[ids].set(new), y.at[ids].set(got)

    state, y = jax.lax.fori_loop(0, trips, trip, (state, y0))
    return y, state


class GroupedAttention(nn.Module):
    config: HybridSSMConfig
    layer: int

    @nn.compact
    def __call__(self, x, rows):
        cfg = self.config
        b, s, _ = x.shape
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        rep = cfg.n_heads // kv
        k_var = self.variable("cache", "cached_k", jnp.zeros,
                              (b, cfg.max_seq, kv * hd), cfg.dtype)
        v_var = self.variable("cache", "cached_v", jnp.zeros,
                              (b, cfg.max_seq, kv * hd), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        table, live, found = _live_rows(self, b, self.layer == 0)
        rows = found if found is not None else rows
        paged = table is not None
        pos0 = idx if idx.ndim == 1 else jnp.broadcast_to(idx, (b,))
        q_pos = pos0[:, None] + jnp.arange(s)[None, :]  # [B, s]

        q = _dense(cfg, (kv, rep, hd), "q_proj")(x)  # [B, s, kv, rep, hd]
        k_new = _dense(cfg, kv * hd, "k_proj")(x)
        v_new = _dense(cfg, kv * hd, "v_proj")(x)

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

        k_var.value = store(k_var.value, k_new.astype(cfg.dtype))
        v_var.value = store(v_var.value, v_new.astype(cfg.dtype))
        ci.value = idx + s

        with jax.named_scope("gqa_attend"):
            k_buf, v_buf = k_var.value, v_var.value

            def entries(buf, ids):
                """Every cached entry of the rows in logical order, ``[b,
                K, kv, hd]``: the slab, or the rows' pages by their table
                rows."""
                if paged:
                    tab = table if ids is None else table[ids]
                    tab = jnp.minimum(tab[:, :-1], buf.shape[0] - 1)
                    buf = buf[tab].reshape(tab.shape[0], -1, buf.shape[-1])
                return buf.reshape(buf.shape[:2] + (kv, hd))

            def attend(ids, qr, pr):
                keys, values = entries(k_buf, ids), entries(v_buf, ids)

                def block(qb, pb):
                    scores = jnp.einsum(
                        "bsgrd,bkgd->bgrsk", qb, keys,
                        preferred_element_type=jnp.float32
                    ) * cfg.attention_multiplier
                    seen = jnp.arange(keys.shape[1]) <= pb[..., None]  # [b, s, K]
                    prob = jax.nn.softmax(jnp.where(
                        seen[:, None, None], scores, NEG), axis=-1)
                    return jnp.einsum("bgrsk,bkgd->bsgrd",
                                      prob.astype(cfg.dtype), values,
                                      preferred_element_type=jnp.float32)

                return _blocked(block, cfg.query_block, qr, pr)

            if rows is None or not paged:
                out = attend(None, q, q_pos)
            else:
                out = _over_live(attend, rows, q, q_pos)
        out = out.astype(cfg.dtype).reshape(b, s, cfg.n_heads * hd)
        return _dense(cfg, cfg.d_model, "o_proj")(out), rows, live


class HybridBlock(nn.Module):
    config: HybridSSMConfig
    layer: int

    @nn.compact
    def __call__(self, x, rows):
        cfg = self.config
        mixer = (Mamba2Mixer if cfg.layer_types[self.layer] == "mamba"
                 else GroupedAttention)
        mix, rows, live = mixer(cfg, self.layer, name="mixer")(
            _norm(cfg, "input_norm")(x), rows)
        x = x + (cfg.residual_multiplier * mix).astype(x.dtype)
        h = _norm(cfg, "post_mixer_norm")(x)
        with jax.named_scope("moe_experts"):
            y = ExpertShare(cfg, name="mlp")(h, live)
        return x + (cfg.residual_multiplier * y).astype(x.dtype), rows


class HybridSSMLM(nn.Module):
    """Decode-mode only: ``apply(..., mutable=["cache"])``; called on an
    empty cache it is the teacher-forced forward."""
    config: HybridSSMConfig

    @nn.compact
    def __call__(self, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        drawn = ({"embedding_init": nn.initializers.normal(cfg.embed_init_std)}
                 if cfg.embed_init_std else {})
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="embed",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype, **drawn)
        x = embed(tokens) * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
        rows = None
        for i in range(cfg.n_layers):
            x, rows = HybridBlock(cfg, i, name=f"layers_{i}")(x, rows)
        x = _norm(cfg, "norm")(x)
        return embed.attend(x).astype(jnp.float32) / cfg.logits_scaling


def _score_logits(config: HybridSSMConfig):
    module = HybridSSMLM(config)
    return lambda params, tokens: module.apply(
        params, tokens, mutable=["cache"])[0]


_FAMILY = DecodeFamily(
    ("cached_k", "cached_v"), HybridSSMLM, _score_logits,
    work_leaf="expert_stats", slot_leaves=("ssm_state", "conv_state"),
    prefix_reusable=False)


def init_params(config: HybridSSMConfig, rng: jax.Array) -> Any:
    """``{"params": ...}`` from ``rng``, made in one jitted call."""
    module = HybridSSMLM(config)
    return jax.jit(lambda key: {"params": module.init(
        key, jnp.zeros((1, 2), jnp.int32))["params"]})(rng)
