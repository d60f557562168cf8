"""Plain reference of the ``granitemoehybrid`` layer as one chip's share of
an expert-parallel deployment serves it (granite-4.0-h-small's widths).

Written from the published implementation (``transformers`` 4.57.6,
``models/granitemoehybrid/modeling_granitemoehybrid.py``:
``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridTopKGating``,
``GraniteMoeHybridMoE``, ``GraniteMoeHybridMLP``,
``GraniteMoeHybridRMSNormGated``, ``GraniteMoeHybridDecoderLayer``), in
``jax.numpy`` and float32 at "highest" matmul precision, with no cache, no
chunk, no kernel, no batching and nothing imported from the program: one
sequence, **the recurrence a sequential ``lax.scan`` over tokens**, every
position attends from scratch, a loop over the held experts. It reads the
program's parameter tree by its names, since both sides share the seeded
weights, and upcasts one tensor at a time, a layer at a time, so that a
1,920-token reply fits beside the bfloat16 weights.

With ``r = residual_multiplier``, RMSNorm eps 1e-5, scale only:

    x_0 = embedding_multiplier * E[tokens]
    x <- x + r * Mixer(RMSNorm(x));  u = RMSNorm(x);  x <- x + r * (MoE(u) + SharedMLP(u))
    logits = RMSNorm(x) E^T / logits_scaling            (tied embedding)

Mamba-2 mixer (``d_inner = H P``, state ``N``, one group, conv width ``K``):

    [z | xBC | dt] = W_in u                        split d_inner | d_inner + 2N | H
    xBC_t <- SiLU(sum_k w_k xBC_{t-(K-1-k)} + b)   causal, depthwise, zeros before 0
    [x | B | C] = xBC                              x as [H, P]; B, C shared by heads
    dt = softplus(dt + dt_bias);  A = -exp(A_log)  per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t      S is [H, P, N], S_{-1} = 0
    y_t = S_t C_t + D x_t
    out = W_out (RMSNorm(y * SiLU(z)) w)           the gate inside the norm

Attention: ``q`` ``n_heads`` of ``hd``, ``k`` / ``v`` ``n_kv_heads`` of ``hd``,
no bias, **no rotation**; KV head ``g`` serves query heads ``g n/n_kv ..``;
causal softmax of ``q k^T * attention_multiplier``; ``W_o``.

MoE: ``l = W_r u`` in float32 over all ``E`` experts; chosen = the ``k``
largest ``l``; gates = softmax over those ``k`` values;

    y = SharedMLP(u) + sum_{e chosen, e held} g_e W_out,e (SiLU(a) * b),  [a | b] = W_in,e u

**The share**: only the terms of ``experts_held = [first, first + count)`` are
added; the other experts' terms are the other chips' and are left out, here as
in the program. The program keeps an expert's ``W_in`` as its two halves
(``gate``, ``up``), the held experts stacked (``experts_gate [count, d, f]``),
and the shared MLP's likewise.

Readings the published config does not settle are the configuration file's
``assumed`` (the seeded initialisation of ``A_log``, ``D``, ``dt_bias``, the
embedding's spread).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-5


def _f32(v: jax.Array) -> jax.Array:
    return v.astype(jnp.float32)


def rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * _f32(scale)


def recurrence(x: jax.Array, dt: jax.Array, b: jax.Array, c: jax.Array,
               a: jax.Array, state: jax.Array):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t = S_t C_t``,
    token by token. ``x [L, H, P]``, ``dt [L, H]``, ``b`` / ``c [L, N]``, ``a
    [H]``, ``state [H, P, N]``; all float32. Returns ``(y [L, H, P], final
    state)``."""
    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return s, jnp.sum(s * c_t[None, None, :], axis=-1)

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


@jax.jit
def final_state(x, dt, b, c, a_log, state):
    """The state after a sequence, from a layer's own ``x, dt, B, C`` (``dt``
    after its softplus) and ``A_log``, float32 throughout."""
    return recurrence(_f32(x), _f32(dt), _f32(b), _f32(c),
                      -jnp.exp(_f32(a_log)), _f32(state))[1]


@functools.partial(jax.jit, static_argnames=("heads", "n"))
def _mamba(p: Dict[str, Any], norm: jax.Array, x: jax.Array, r: float,
           heads: int, n: int):
    """``(x + r * Mixer(RMSNorm(x)), final state)`` of one sequence ``x [S, d]``."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, norm)
        s = x.shape[0]
        w_conv = _f32(p["conv_weight"])  # [K, C]
        k, conv_dim = w_conv.shape
        d_inner = conv_dim - 2 * n
        proj = u @ _f32(p["in_proj"]["kernel"])
        z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:d_inner + conv_dim],
                      proj[:, d_inner + conv_dim:])
        padded = jnp.concatenate([jnp.zeros((k - 1, conv_dim)), xbc])
        xbc = jax.nn.silu(_f32(p["conv_bias"]) + sum(
            padded[i:i + s] * w_conv[i] for i in range(k)))
        xs = xbc[:, :d_inner].reshape(s, heads, d_inner // heads)
        dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))
        y, state = recurrence(
            xs, dt, xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:],
            -jnp.exp(_f32(p["A_log"])),
            jnp.zeros((heads, d_inner // heads, n)))
        y = y + _f32(p["D"])[:, None] * xs
        y = rms_norm(y.reshape(s, d_inner) * jax.nn.silu(z), p["norm"]["scale"])
        return x + r * (y @ _f32(p["out_proj"]["kernel"])), state


@jax.jit
def _attention(p: Dict[str, Any], norm: jax.Array, x: jax.Array, r: float,
               mult: float) -> jax.Array:
    """``x + r * Attention(RMSNorm(x))``; one KV head at a time (a
    ``lax.map`` over the KV heads: one body to compile, not 32 heads
    unrolled), the ``n_heads / n_kv`` query heads it serves together."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(x, norm)
        s = x.shape[0]
        w_q = _f32(p["q_proj"]["kernel"])  # [d, kv, rep, hd]
        kv, rep, hd = w_q.shape[1:]
        keys = (u @ _f32(p["k_proj"]["kernel"])).reshape(s, kv, hd)
        values = (u @ _f32(p["v_proj"]["kernel"])).reshape(s, kv, hd)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

        def kv_head(args):  # query heads g * rep + j, j < rep, read KV head g
            w_g, k_g, v_g = args  # [d, rep, hd], [s, hd], [s, hd]
            q = jnp.einsum("sd,drh->rsh", u, w_g)
            scores = jnp.einsum("rsh,th->rst", q, k_g) * mult
            prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
            return jnp.einsum("rst,th->rsh", prob, v_g)

        heads = jax.lax.map(kv_head, (jnp.moveaxis(w_q, 1, 0),
                                      jnp.moveaxis(keys, 1, 0),
                                      jnp.moveaxis(values, 1, 0)))
        out = jnp.moveaxis(heads, 2, 0).reshape(s, kv * rep * hd)
        return x + r * (out @ _f32(p["o_proj"]["kernel"]))


def router_logits(router: jax.Array, m: jax.Array) -> jax.Array:
    """``l = W_r m``, ``[S, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        return _f32(m) @ _f32(router)


def routing(router: jax.Array, m: jax.Array, k: int):
    """``(chosen [S, k], gates [S, k])``: the ``k`` largest logits, and the
    softmax over those values."""
    top, chosen = jax.lax.top_k(router_logits(router, m), k)
    return chosen, jax.nn.softmax(top, axis=-1)


def _gated(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ _f32(w_gate)) * (m @ _f32(w_up))) @ _f32(w_down)


@functools.partial(jax.jit, static_argnames=("k", "first", "count"))
def _shared_and_routing(norm, shared, router, x, k: int, first: int,
                        count: int):
    """``(m, SharedMLP(m), gate, routed)``: ``gate [count, S]`` is ``g_e`` of
    each held expert for each token, 0 where it was not chosen; ``routed [S,
    count]`` says which tokens chose which held expert."""
    with jax.default_matmul_precision("highest"):
        m = rms_norm(x, norm)
        chosen, gates = routing(router, m, k)
        held = first + jnp.arange(count)
        gate = jnp.sum(jnp.where(chosen[None] == held[:, None, None],
                                 gates[None], 0.0), -1)
        out = _gated(m, shared["gate_proj"]["kernel"],
                     shared["up_proj"]["kernel"], shared["down_proj"]["kernel"])
        return m, out, gate, jnp.any(chosen[:, :, None] == held, axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_expert(out, m, gate, e, w_gate, w_up, w_down):
    """``out + g_e Expert_e(m)`` for held expert ``e``, over every token (the
    gate of a token that did not choose it is 0)."""
    with jax.default_matmul_precision("highest"):
        return out + gate[e][:, None] * _gated(m, w_gate, w_up, w_down)


def ffn(p: Dict[str, Any], x: jax.Array, r: float, k: int, first: int,
        count: int):
    """``(x + r * (MoE + SharedMLP)(RMSNorm(x)), routed)`` of one layer ``p``,
    the experts ``[first, first + count)`` of the tree's own numbering added
    one at a time."""
    mlp = p["mlp"]
    m, out, gate, routed = _shared_and_routing(
        p["post_mixer_norm"]["scale"], mlp["shared_expert"], mlp["router"], x,
        k, first, count)
    for e in range(count):
        out = _add_expert(out, m, gate, np.int32(e), mlp["experts_gate"][e],
                          mlp["experts_up"][e], mlp["experts_down"][e])
    return x + r * out, routed


@jax.jit
def _embed(table, tokens, mult):
    return _f32(table)[tokens] * mult


@jax.jit
def _head_logprobs(norm, table, x, positions, scaling):
    with jax.default_matmul_precision("highest"):
        h = rms_norm(x[positions], norm)
        return jax.nn.log_softmax(h @ _f32(table).T / scaling, axis=-1)


def forward(params: Any, tokens: jax.Array, model: Mapping[str, Any]):
    """The residual stream after the last layer of one sequence ``tokens
    [S]``, ``[S, d]`` float32, with each Mamba layer's final state and each
    layer's ``[S, count]`` routing to the held experts, by layer number.
    ``model`` is the configuration file's dict (``layer_types``,
    ``mamba_n_heads``, ``mamba_d_state``, ``num_experts_per_tok``,
    ``experts_held``, the four multipliers). The tree's experts are numbered
    from 0: they are ``experts_held[0] + e`` of the router's."""
    p = params["params"]
    r = float(model["residual_multiplier"])
    first, count = model["experts_held"]
    x = _embed(p["embed"]["embedding"], tokens,
               float(model["embedding_multiplier"]))
    states, routes = {}, {}
    for i, kind in enumerate(model["layer_types"]):
        layer = p[f"layers_{i}"]
        norm = layer["input_norm"]["scale"]
        if kind == "mamba":
            x, states[i] = _mamba(layer["mixer"], norm, x, r,
                                  int(model["mamba_n_heads"]),
                                  int(model["mamba_d_state"]))
        else:
            x = _attention(layer["mixer"], norm, x, r,
                           float(model["attention_multiplier"]))
        x, routes[i] = ffn(layer, x, r, int(model["num_experts_per_tok"]),
                           int(first), int(count))
    return x, states, routes


def log_probs(params: Any, tokens: jax.Array, positions: jax.Array,
              model: Mapping[str, Any], return_parts: bool = False):
    """Next-token log-probabilities after ``positions`` of one sequence
    ``tokens [S]``: ``[len(positions), V]`` float32; with ``return_parts``
    also :func:`forward`'s states and routes."""
    p = params["params"]
    x, states, routes = forward(params, tokens, model)
    out = _head_logprobs(p["norm"]["scale"], p["embed"]["embedding"], x,
                         positions, float(model["logits_scaling"]))
    return (out, states, routes) if return_parts else out
