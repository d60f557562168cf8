"""Plain reference of the ``glm_moe_dsa`` layer as one chip's share of an
expert-parallel deployment serves it (GLM-5.2 widths; the layer DeepSeek-V3.2
published, whose selector this is).

Written from the equations, in ``jax.numpy`` and float32 at "highest" matmul
precision, with no cache, no kernel, no batching and nothing imported from
the program: one sequence, every position attends from scratch, and the
selection is computed per query from that query's own scores. It reads the
program's parameter tree by its names, since both sides share the seeded
weights, and upcasts one tensor at a time, in blocks of queries, so that a
33k-token reply fits beside the bfloat16 weights once the server is closed.

Hidden ``d``, RMSNorm eps 1e-5, pre-norm, two residual adds a layer:

    h   = x + MLA(RMSNorm(x));   out = h + FFN(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * scale

MLA, every layer (``H`` heads; rotary on interleaved pairs ``(2i, 2i+1)``,
angle ``pos * theta^(-2i/64)``):

    c_q = RMSNorm(W_qa a);   q_i = W_qb c_q = [q_nope_i 192 | q_rope_i 64]
    [c_kv 512 | k_rope 64] = W_kva a;  c_kv = RMSNorm(c_kv);  k_rope = RoPE(k_rope)
    [k_nope_i 192 | v_i 256] = W_kvb c_kv          per head, expanded here (the
                               tree holds W_kvb's two halves, k_b_proj and v_b_proj)
    q_i = [q_nope_i | RoPE(q_rope_i)];  k_i = [k_nope_i | k_rope]   (k_rope shared)
    attn_t = W_o concat_i sum_{s in S_t} softmax_s(q_i,t . k_i,s / sqrt(256)) v_i,s

Selector, in layers whose ``indexer_types`` entry is ``full`` (``J`` heads of
128; rotary on the first 64 values of both sides):

    qI_j = W_Iq c_q;  kI = LayerNorm_1e-6(W_Ik a);  w = W_Iw a * J^-1/2 * 128^-1/2
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])       for s <= t
    S_t = the index_topk positions of largest I[t, .], or every s <= t while
          fewer than index_topk exist
    a ``shared`` layer uses the S_t of the nearest earlier ``full`` layer

FFN: ``dense`` layers ``W_d (silu(W_g m) * W_u m)``. ``sparse`` layers, with
``s = sigmoid(W_r m)`` in float32 over all E experts: chosen = the k largest of
``s + b`` (``b`` chooses only); ``g_e = scaling * s_e / sum_chosen s``;

    y = SwiGLU_shared(m) + sum_{e chosen, e held} g_e SwiGLU_e(m)

**The share**: only the terms of ``experts_held = [first, first + count)`` are
added; the other experts' terms are the other chips' and are left out, here as
in the program. Final RMSNorm, untied head.

Readings that the published config does not settle (the configuration
file's ``assumed``): a ``shared`` layer reuses the nearest earlier ``full``
layer's set for the same token; the selector rotates 64 values; ``kI`` goes
through a LayerNorm with bias. Departures from the published inference code
(``departures``): its fp8 cast and Hadamard rotation of ``qI`` and ``kI``
are left out (the rotation is orthogonal and changes no score); the
multi-token-prediction layer is not served.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-5
LN_EPS = 1e-6
QUERY_BLOCK = 512


def _f32(v: jax.Array) -> jax.Array:
    return v.astype(jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * _f32(scale)


def _layer_norm(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * _f32(p["scale"]) + _f32(p["bias"])


def _rotary(x: jax.Array, theta: float) -> jax.Array:
    """x: [S, ..., d], position = row number; interleaved pairs."""
    s, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(p: Dict[str, Any], m: jax.Array) -> jax.Array:
    gate = m @ _f32(p["gate_proj"]["kernel"])
    up = m @ _f32(p["up_proj"]["kernel"])
    return (jax.nn.silu(gate) * up) @ _f32(p["down_proj"]["kernel"])


def _by_blocks(fn, s: int, *arrays):
    """``fn(start, *block)`` over blocks of ``QUERY_BLOCK`` queries (axis 0
    of every array), joined: bounds the ``[queries, S]`` temporaries. One
    traced body, so a long sequence compiles as fast as a short one."""
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    starts = jnp.arange(0, s, qb)
    split = tuple(a.reshape((s // qb, qb) + a.shape[1:]) for a in arrays)
    out = jax.lax.map(lambda xs: fn(xs[0], *xs[1:]), (starts,) + split)
    return out.reshape((s,) + out.shape[2:])


@functools.partial(jax.jit, static_argnames=("topk", "rd"))
def selection_mask(p: Dict[str, Any], a: jax.Array, c_q: jax.Array,
                   theta: float, topk: int, rd: int) -> jax.Array:
    """``[S, S]`` bool: row t holds S_t."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        q = jnp.einsum("sr,rjd->sjd", c_q, _f32(p["index_q_proj"]["kernel"]))
        k = _layer_norm(a @ _f32(p["index_k_proj"]["kernel"]), p["index_k_norm"])
        q = jnp.concatenate([_rotary(q[..., :rd], theta), q[..., rd:]], -1)
        k = jnp.concatenate([_rotary(k[..., :rd], theta), k[..., rd:]], -1)
        j, d = q.shape[1], q.shape[2]
        w = (a @ _f32(p["index_w_proj"]["kernel"])) * (j ** -0.5 * d ** -0.5)

        def block(start, qb, wb):
            def head(score, qw):  # one selector head at a time
                q_j, w_j = qw
                return score + w_j[:, None] * jax.nn.relu(q_j @ k.T), None

            score, _ = jax.lax.scan(
                head, jnp.zeros((qb.shape[0], s), jnp.float32),
                (jnp.moveaxis(qb, 1, 0), wb.T))
            rows = start + jnp.arange(qb.shape[0])
            causal = rows[:, None] >= jnp.arange(s)[None, :]
            if s <= topk:
                return causal
            # equal scores: the earlier position, as top_k does
            top, idx = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
            chosen = jnp.zeros_like(causal).at[
                jnp.arange(qb.shape[0])[:, None], idx].set(top > -jnp.inf)
            return causal & chosen

        return _by_blocks(block, s, q, w)


def _query_latent(p: Dict[str, Any], x: jax.Array):
    """``(a, c_q)``: the normed input and the query's low-rank latent."""
    a = rms_norm(x, p["input_norm"]["scale"])
    return a, rms_norm(a @ _f32(p["attn"]["q_a_proj"]["kernel"]),
                        p["attn"]["q_a_norm"]["scale"])


@jax.jit
def _selector_inputs(p: Dict[str, Any], x: jax.Array):
    with jax.default_matmul_precision("highest"):
        return _query_latent(p, x)


@jax.jit
def _attention(p: Dict[str, Any], x: jax.Array, mask: jax.Array,
               theta: float) -> jax.Array:
    """``x + attn`` given S_t as the rows of ``mask``; one head at a time."""
    with jax.default_matmul_precision("highest"):
        pa = p["attn"]
        a, c_q = _query_latent(p, x)
        kv = a @ _f32(pa["kv_a_proj"]["kernel"])
        rank = pa["k_b_proj"].shape[0]
        c_kv = rms_norm(kv[:, :rank], pa["kv_a_norm"]["scale"])
        k_rope = _rotary(kv[:, rank:], theta)  # [S, 64], one head for all
        rope = k_rope.shape[-1]
        s = x.shape[0]

        def head(out, w):
            w_q, w_k, w_v, w_o = w  # [r_q, nope + rope], [rank, nope], [rank, v], [v, d]
            q_h = c_q @ _f32(w_q)
            nope = q_h.shape[-1] - rope
            q_h = jnp.concatenate([q_h[:, :nope], _rotary(q_h[:, nope:], theta)], -1)
            k_h = jnp.concatenate([c_kv @ _f32(w_k), k_rope], -1)
            v_h = c_kv @ _f32(w_v)

            def block(_, qb, mb):
                scores = (qb @ k_h.T) / jnp.sqrt(jnp.float32(nope + rope))
                return jax.nn.softmax(jnp.where(mb, scores, -jnp.inf), -1) @ v_h

            return out + _by_blocks(block, s, q_h, mask) @ _f32(w_o), None

        out, _ = jax.lax.scan(head, jnp.zeros_like(x), (
            jnp.moveaxis(pa["q_b_proj"]["kernel"], 1, 0),
            jnp.moveaxis(pa["k_b_proj"], 1, 0),
            jnp.moveaxis(pa["v_b_proj"], 1, 0), pa["o_proj"]["kernel"]))
        return x + out


def affinity(p: Dict[str, Any], m: jax.Array) -> jax.Array:
    """``s = sigmoid(W_r m)``, ``[S, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(_f32(m) @ _f32(p["router"]))


def routing(p: Dict[str, Any], m: jax.Array, k: int, scaling: float):
    """``(chosen [S, k], gates [S, k])`` of a sparse layer's router."""
    with jax.default_matmul_precision("highest"):
        s = affinity(p, m)
        _, chosen = jax.lax.top_k(s + _f32(p["e_score_correction_bias"]), k)
        picked = jnp.take_along_axis(s, chosen, -1)
        return chosen, scaling * picked / jnp.sum(picked, -1, keepdims=True)


@jax.jit
def _dense_ffn(norm: jax.Array, mlp: Dict[str, Any], x: jax.Array) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return x + _swiglu(mlp, rms_norm(x, norm))


@functools.partial(jax.jit, static_argnames=("k", "first", "count"))
def _shared_and_routing(norm: jax.Array, shared: Dict[str, Any],
                        router: Dict[str, jax.Array], x: jax.Array, k: int,
                        scaling: float, first: int, count: int):
    """``(m, x + SwiGLU_shared(m), gate, routed)``: ``gate [count, S]`` is
    ``g_e`` of each held expert for each token, 0 where it was not chosen;
    ``routed [S, count]`` says which tokens chose which held expert."""
    with jax.default_matmul_precision("highest"):
        m = rms_norm(x, norm)
        chosen, gates = routing(router, m, k, scaling)
        held = first + jnp.arange(count)
        gate = jnp.sum(jnp.where(chosen[None] == held[:, None, None],
                                 gates[None], 0.0), -1)
        return (m, x + _swiglu(shared, m), gate,
                jnp.any(chosen[:, :, None] == held, axis=1))


@functools.partial(jax.jit, static_argnames=("cap",), donate_argnums=(0,))
def _add_expert(out: jax.Array, m: jax.Array, gate: jax.Array, e: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                cap: int) -> jax.Array:
    """``out + g_e SwiGLU_e(m)`` for held expert ``e``, run on the tokens
    that chose it, in a pass of ``cap`` (the caller counts: they are at most
    ``cap``)."""
    with jax.default_matmul_precision("highest"):
        g = gate[e]
        # its tokens, then row 0 again and again at weight 0
        rows = jnp.nonzero(g > 0, size=cap, fill_value=0)[0]
        g = jnp.where(jnp.arange(cap) < jnp.sum(g > 0), g[rows], 0.0)
        mb = m[rows]
        h = jax.nn.silu(mb @ _f32(w_gate)) * (mb @ _f32(w_up))
        return out.at[rows].add(g[:, None] * (h @ _f32(w_down)))


def _room(n: int) -> int:
    """Tokens one pass of an expert holds, of a sequence of ``n``: an expert
    is chosen by n * k / E tokens on average, and a quarter of a long
    sequence is eight times that."""
    return n if n <= 2048 else n // 4


def _ffn(p: Dict[str, Any], x: jax.Array, k: int, scaling: float,
         first: int, count: int):
    """``(x + FFN, routed)`` of one layer ``p``; the held experts one at a
    time, so that one small program serves them all."""
    norm, mlp = p["post_attn_norm"]["scale"], p["mlp"]
    if "router" not in mlp:
        return _dense_ffn(norm, mlp, x), None
    m, out, gate, routed = _shared_and_routing(
        norm, mlp["shared_expert"],
        {name: mlp[name] for name in ("router", "e_score_correction_bias")},
        x, k, scaling, first, count)
    n = x.shape[0]
    load = np.asarray(jnp.sum(gate > 0, axis=-1))
    for e in range(count):  # this chip's experts: first + e of the 256
        # an expert chosen by more than its room takes the whole sequence
        out = _add_expert(out, m, gate, np.int32(e), mlp[f"expert_{e}_gate"],
                          mlp[f"expert_{e}_up"], mlp[f"expert_{e}_down"],
                          _room(n) if load[e] <= _room(n) else n)
    return out, routed


@jax.jit
def _embed(table, tokens):
    return _f32(table)[tokens]


@jax.jit
def _head_logprobs(norm, head, x, positions):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[positions], norm)
        return jax.nn.log_softmax(h @ _f32(head), axis=-1)


def log_probs(params: Any, tokens: jax.Array, positions: jax.Array,
              model: Dict[str, Any], return_sets: bool = False,
              set_rows: Any = None):
    """Next-token log-probabilities after ``positions`` of one sequence
    ``tokens [S]``: ``[len(positions), V]`` float32. ``model`` holds
    ``indexer_types``, ``index_topk``, ``index_rope_dim``, ``rope_theta``,
    ``num_experts_per_tok``, ``routed_scaling_factor`` and ``experts_held``
    (first, count). With ``return_sets`` also, by layer number, the ``[S,
    S]`` selection of every ``full`` layer and the ``[S, count]`` routing to
    the held experts of every sparse layer, or of both only the rows
    (tokens) ``set_rows``."""
    p = params["params"]
    theta = float(model["rope_theta"])
    first, count = model["experts_held"]
    x = _embed(p["embed"]["embedding"], tokens)
    mask, masks, routes = None, {}, {}
    for i, kind in enumerate(model["indexer_types"]):
        layer = p[f"layers_{i}"]
        if kind == "full":
            a, c_q = _selector_inputs(layer, x)
            mask = selection_mask(layer["attn"], a, c_q, theta,
                                  int(model["index_topk"]),
                                  int(model["index_rope_dim"]))
            if return_sets:
                masks[i] = np.asarray(mask if set_rows is None
                                      else mask[set_rows])
        x = _attention(layer, x, mask, theta)
        x, routed = _ffn(layer, x, int(model["num_experts_per_tok"]),
                         float(model["routed_scaling_factor"]), first, count)
        if return_sets and routed is not None:
            routes[i] = np.asarray(routed)[
                slice(None) if set_rows is None else set_rows]
    out = _head_logprobs(p["norm"]["scale"], p["lm_head"]["kernel"], x,
                         positions)
    return (out, masks, routes) if return_sets else out
