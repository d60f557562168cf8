"""Plain reference of the LM block: forward, loss and gradients.

Written from the equations, in ``jax.numpy`` and float32 at "highest" matmul
precision, with no kernel, no cache, no batching trick and nothing imported
from the program. It reads the program's parameter tree by its names, since
both sides must share the seeded weights.

The block (what ``pythia-*-widths`` runs; departures from Pythia itself are
listed in the configuration files):

    h   = x + Attn(LN(x))             pre-LayerNorm, sequential residual
    out = h + W_o gelu_tanh(W_i LN(h))      two-matrix FFN, no biases
    Attn: q, k, v = W_q a, W_k a, W_v a per head; rotary on the whole head
          (pairs (i, i + D/2), angle pos * base^(-i / (D/2))); causal
          softmax(q k^T / sqrt(D)) v; W_o over the concatenated heads
    LN(x) = (x - mean) / sqrt(var + 1e-6) * scale + bias
    logits = W_head LN_f(x_L);  embedding and head untied
    loss = mean over positions of -log softmax(logits)[target]
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x: jax.Array, p: Dict[str, jax.Array]) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rotary(x: jax.Array, base: float) -> jax.Array:
    """x: [S, H, D]."""
    s, _, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x: jax.Array) -> jax.Array:
    c = 0.7978845608028654  # sqrt(2 / pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def layer(p: Dict[str, Any], x: jax.Array, rope_base: float) -> jax.Array:
    """One block on one sequence. x: [S, d] float32."""
    a = _layer_norm(x, p["ln_attn"])
    q = jnp.einsum("sd,dhk->shk", a, p["attn"]["q_proj"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", a, p["attn"]["k_proj"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", a, p["attn"]["v_proj"]["kernel"])
    q, k = _rotary(q, rope_base), _rotary(k, rope_base)
    scores = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    s = x.shape[0]
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(mask[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("hqt,thk->qhk", probs, v)
    x = x + jnp.einsum("qhk,hkd->qd", ctx, p["attn"]["o_proj"]["kernel"])
    m = _layer_norm(x, p["ln_mlp"])
    hidden = _gelu_tanh(m @ p["mlp"]["wi"]["kernel"])
    return x + hidden @ p["mlp"]["wo"]["kernel"]


def _f32(tree: Any) -> Any:
    return jax.tree.map(lambda v: v.astype(jnp.float32), tree)


@functools.partial(jax.jit, static_argnames=("rope_base",))
def _layer_jit(p, x, rope_base):
    with jax.default_matmul_precision("highest"):
        return layer(_f32(p), x, rope_base)


@jax.jit
def _embed_jit(table, tokens):
    return table.astype(jnp.float32)[tokens]


@jax.jit
def _head_logprobs_jit(ln_f, head, x, positions):
    """log softmax of the logits at ``positions`` only ([P, V])."""
    with jax.default_matmul_precision("highest"):
        h = _layer_norm(x[positions], _f32(ln_f))
        return jax.nn.log_softmax(h @ head.astype(jnp.float32), axis=-1)


def log_probs(params: Any, tokens: jax.Array, positions: jax.Array,
              n_layers: int, rope_base: float = 10000.0) -> jax.Array:
    """Next-token log-probabilities after ``positions`` of one sequence
    ``tokens [S]``: ``[len(positions), V]`` float32. Runs one jitted block
    at a time, so it compiles one small program whatever the depth."""
    p = params["params"]
    x = _embed_jit(p["embed"]["embedding"], tokens)
    for i in range(n_layers):
        x = _layer_jit(p[f"layers_{i}"], x, rope_base)
    return _head_logprobs_jit(p["ln_f"], p["lm_head"]["kernel"], x, positions)


def loss_fn(params: Any, tokens: jax.Array, targets: jax.Array,
            n_layers: int, rope_base: float = 10000.0) -> jax.Array:
    """Mean next-token cross-entropy of one sequence, differentiable."""
    p = _f32(params["params"])
    x = p["embed"]["embedding"][tokens]
    # checkpointing each block bounds the float32 score matrices the
    # backward pass keeps alive to one block's; the mathematics is unchanged
    block = jax.checkpoint(functools.partial(layer, rope_base=rope_base))
    for i in range(n_layers):
        x = block(p[f"layers_{i}"], x)
    logits = _layer_norm(x, p["ln_f"]) @ p["lm_head"]["kernel"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def loss_and_grads(params: Any, tokens: jax.Array, targets: jax.Array,
                   n_layers: int, wrt: Tuple[Tuple[str, ...], ...],
                   rope_base: float = 10000.0) -> Tuple[jax.Array, Any]:
    """Loss and its gradient with respect to the leaves named by ``wrt``
    (paths under ``params["params"]``), e.g. ``("embed", "embedding")``."""
    def get(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    def put(tree, path, value):
        if not path:
            return value
        out = dict(tree)
        out[path[0]] = put(tree[path[0]], path[1:], value)
        return out

    def f(leaves, inner, tokens, targets):
        for path, leaf in zip(wrt, leaves):
            inner = put(inner, path, leaf)
        return loss_fn({"params": inner}, tokens, targets, n_layers, rope_base)

    leaves = tuple(get(params["params"], path) for path in wrt)
    # tokens go in as arguments, not as constants of the program: one
    # compiled program serves every seed, so the persistent cache hits
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(f))(
            leaves, params["params"], jnp.asarray(tokens), jnp.asarray(targets))
