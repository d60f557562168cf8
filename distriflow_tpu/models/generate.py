"""Autoregressive decoding for the transformer LM (KV cache).

No reference counterpart (the reference has no sequence models,
SURVEY.md §2.3). TPU-shaped decoding:

- **prefill**: one forward over the whole prompt fills every layer's KV
  cache (``TransformerLM(decode=True)`` + flax mutable ``cache``);
- **decode loop**: a jit-compiled ``lax.scan`` over single-token steps —
  the cache is carried functionally through the scan (static shapes,
  no per-token dispatch from the host).

Greedy (``temperature=0``) or temperature sampling, optionally truncated
to the top-k logits and/or a top-p (nucleus) cumulative-probability mass.
The cache holds ``max_seq`` positions per layer; ``prompt_len + n_tokens``
must fit.

MoE configs decode with **dense dispatch** (see
:func:`_transformer_decode_module`):
every token goes to its true top-1 expert, no capacity drops — decode is
group-independent and matches the dense-dispatch training forward exactly.
Divergence from a *capacity-routed* training forward is bounded by the
tokens training itself dropped: zero with ample ``capacity_factor``,
quantified in tests/test_generate.py for tight capacity. Dense-FFN configs
decode exactly (teacher-forcing logits match the training forward).

**TP-sharded decoding** (round 3; flash under TP round 5): pass
Megatron-sharded params (the ``TRANSFORMER_TP_RULES`` layout) and the
SAME jit-cached programs decode tensor-parallel — no bespoke path.
GSPMD propagates the column-sharded q/k/v projections into a
heads-sharded KV cache, keeps the attention einsums head-parallel, and
row-shards + psums ``o_proj``; the flash-decode kernel participates via
its own heads-sharded ``custom_partitioning`` rule
(``ops/flash_decode.py::flash_decode_sharded``). Output is
token-for-token identical to single-device decode (greedy, sampled,
beam, and flash — tests/test_tp_decode.py). The ``InferenceServer``
therefore serves model-sharded params unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distriflow_tpu.models.transformer import TransformerConfig, TransformerLM


def _truncate_logits(
    logits: jnp.ndarray, top_k: Optional[int], top_p: Optional[float]
) -> jnp.ndarray:
    """Mask logits outside the top-k set and/or the top-p nucleus to -inf.

    Standard (HF-style) composition: k first, then p over the distribution
    *renormalized within* the surviving top-k set — the -inf-masked entries
    contribute zero mass to the nucleus cumsum. Static shapes, scan-friendly.
    """
    neg = jnp.finfo(logits.dtype).min
    if top_k is not None:
        k = min(int(top_k), logits.shape[-1])
        kth = jax.lax.top_k(logits, k)[0][..., -1:]  # k-th largest value
        logits = jnp.where(logits < kth, neg, logits)
    if top_p is not None:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)  # masked entries -> ~0
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative mass >= top_p (always
        # keeps the argmax: cum is shifted so position 0 sees mass 0)
        keep_sorted = (cum - probs) < top_p
        n_keep = jnp.sum(keep_sorted, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, n_keep - 1, axis=-1)
        logits = jnp.where(logits < cutoff, neg, logits)
    return logits


class DecodeFamily(NamedTuple):
    """What a model family declares to the decoding paths and the serving
    engine, which otherwise walk the cache by structure and know no model:
    the cache leaves that hold the bytes, per token (``[B, max_seq, F]``
    slabs that become ``[n_pages, page_size, F]`` pools under the paged
    layout, and that the engine's programs donate), the decode-mode module
    (``apply`` with a mutable ``cache`` collection: prefill from an empty
    cache, continuation against one, ``cache_index`` scalar or per row,
    paged when a ``page_table`` leaf is present), and the teacher-forced
    forward ``score`` uses. ``work_leaf``, if a family has one, names a
    cache leaf in which a layer adds up, over its calls, small integer
    counts of the work it chose to do (which counts is the family's to
    say, in its configuration's ``decode_work``); with telemetry on the
    engine fetches those leaves with a decode dispatch's tokens.
    ``slot_leaves`` names cache leaves that hold a fixed-size state per row
    and no token axis (``[B, ...]``; a recurrent layer's state): they stay
    ``[max_slots, ...]`` under either layout, ``insert`` writes a prefilled
    row's state at its slot, and the engine's programs donate them with the
    pools. A family whose cache is more than its tokens' entries cannot be
    rebuilt from cached pages, nor rolled back by moving an index: it says
    ``prefix_reusable=False``, ``gather_rows`` raises for it, and the
    engine runs it without prefix reuse and refuses speculation. A
    configuration class names its family in a ``decode_family`` attribute;
    one without it is ``TransformerConfig``'s.
    """

    pool_leaves: Tuple[str, ...]
    decode_module: Callable[[Any], Any]
    score_logits: Callable[[Any], Callable[[Any, jnp.ndarray], jnp.ndarray]]
    work_leaf: Optional[str] = None
    slot_leaves: Tuple[str, ...] = ()
    prefix_reusable: bool = True

    @property
    def donated_leaves(self) -> Tuple[str, ...]:
        """The leaves that hold the cache's bytes: what a program that
        returns the cache's successor donates."""
        return self.pool_leaves + self.slot_leaves


def _transformer_decode_module(config: TransformerConfig) -> TransformerLM:
    """The decode-mode module all decoding paths share: sharded-attention
    variants never apply to incremental decoding.

    MoE configs switch to **dense dispatch** for decoding: capacity-based
    routing groups tokens and drops over-capacity ones, so its output for a
    given token depends on which tokens happen to share its group — at
    decode time the "group" is one position's batch slice, nothing like the
    training grouping, and with a small decode batch the per-expert
    capacity rounds down to ~1, dropping most tokens. Dense dispatch routes
    every token to its true top-1 expert with no capacity limit: decode
    output is group-independent and matches the dense-dispatch training
    forward exactly (tests/test_generate.py); divergence from a
    capacity-routed training forward is bounded by the tokens that training
    itself dropped (zero when capacity_factor is ample). The extra cost —
    every expert runs on the decode step's B tokens — is negligible at
    decode batch sizes.
    """
    cfg = dataclasses.replace(
        config, use_ring_attention=False, use_ulysses_attention=False,
        moe_dense_dispatch=config.n_experts > 0 or config.moe_dense_dispatch,
    )
    return TransformerLM(cfg, mesh=None, decode=True)


def _transformer_score_logits(config: TransformerConfig):
    cfg = dataclasses.replace(
        config, use_ring_attention=False, use_ulysses_attention=False
    )
    return TransformerLM(cfg, mesh=None).apply  # training-mode forward


#: the per-layer cache leaves that move from [max_slots, max_seq, F]
#: slabs to [n_pages, page_size, F] pools under the paged layout
_POOL_LEAVES = ("cached_k", "cached_v", "k_scale", "v_scale")

_TRANSFORMER_FAMILY = DecodeFamily(
    _POOL_LEAVES, _transformer_decode_module, _transformer_score_logits)


def decode_family(config: Any) -> DecodeFamily:
    return getattr(config, "decode_family", _TRANSFORMER_FAMILY)


def _decode_module(config: Any) -> Any:
    """The decode-mode module of ``config``'s family."""
    return decode_family(config).decode_module(config)


def _check_fits(p: int, n_tokens: int, config: TransformerConfig) -> None:
    if p + n_tokens > config.max_seq:
        raise ValueError(
            f"prompt ({p}) + n_tokens ({n_tokens}) exceeds max_seq "
            f"({config.max_seq}); raise config.max_seq"
        )


def _gate_kv_dtype(config: TransformerConfig,
                   context_len: int) -> TransformerConfig:
    """Re-gate an int8 KV request on the context this call will actually
    read. ``generate()``/``beam_search()`` know the true decode context
    (prompt + n_tokens), so the int8-vs-bf16 crossover decides on READ
    traffic, not the ``max_seq`` allocation bound — a 16k-``max_seq``
    config serving a 1k request keeps the bf16 cache it measures faster
    with (``kv_cache_dtype_for``). ``int8_force`` is never demoted, and
    the replace is a no-op (same hashable config, same ``_build_fns``
    cache entry) whenever the two gates agree."""
    if (getattr(config, "kv_cache_dtype", None) == "int8"
            and config.kv_cache_dtype_for(context_len) is None
            and config.resolved_kv_cache_dtype == "int8"):
        return dataclasses.replace(config, kv_cache_dtype=None)
    return config


@functools.lru_cache(maxsize=32)
def _build_fns(
    config: TransformerConfig,
    n_tokens: int,
    temperature: float,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
):
    """Jit-compiled prefill + decode scan, cached so repeated generate()
    calls with the same config/shape hit the jit cache instead of paying
    full XLA recompilation per call."""
    module = _decode_module(config)

    @jax.jit
    def prefill(params, prompt):
        logits, vars_ = module.apply(params, prompt, mutable=["cache"])
        return logits[:, -1], vars_["cache"]

    def pick(logits, key):
        if temperature > 0:
            logits = logits / temperature
            if top_k is not None or top_p is not None:
                logits = _truncate_logits(logits, top_k, top_p)
            return jax.random.categorical(key, logits, axis=-1)
        return jnp.argmax(logits, axis=-1)

    @jax.jit
    def decode_steps(params, cache, first_tok, rng):
        def step(carry, key):
            cache, tok, done = carry
            logits, vars_ = module.apply(
                {**params, "cache": cache}, tok[:, None], mutable=["cache"]
            )
            nxt = pick(logits[:, -1], key).astype(jnp.int32)
            if eos_id is not None:
                # finished rows keep emitting eos (static shapes: the scan
                # still runs n_tokens ticks; the output is frozen)
                nxt = jnp.where(done, jnp.int32(eos_id), nxt)
                done = done | (nxt == eos_id)
            return (vars_["cache"], nxt, done), nxt

        done0 = (first_tok == eos_id) if eos_id is not None else jnp.zeros(
            first_tok.shape, bool)
        keys = jax.random.split(rng, n_tokens - 1)
        (_, _, _), toks = jax.lax.scan(step, (cache, first_tok, done0), keys)
        return toks.T  # [B, n_tokens - 1]

    return prefill, pick, decode_steps


@functools.lru_cache(maxsize=16)
def _build_beam_fns(
    config: TransformerConfig,
    n_tokens: int,
    beam_size: int,
    length_penalty: float,
    eos_id: Optional[int],
):
    """Jit-compiled prefill + beam-scan. Cached per decode signature."""
    module = _decode_module(config)
    vocab = config.vocab_size
    neg = jnp.float32(-1e30)

    def _reorder(cache, flat_idx, rows):
        """Gather cache rows (leading dim == rows) by flat_idx; leave
        scalars (cache_index) untouched."""
        return jax.tree.map(
            lambda v: v[flat_idx] if (v.ndim >= 1 and v.shape[0] == rows) else v,
            cache,
        )

    def _penalize(scores, lengths):
        # GNMT length penalty ((5+len)/6)^alpha; alpha=0 -> raw scores
        if length_penalty == 0.0:
            return scores
        return scores / (((5.0 + lengths) / 6.0) ** length_penalty)

    @jax.jit
    def search(params, prompt):
        b, p = prompt.shape
        beam = beam_size
        logits, vars_ = module.apply(params, prompt, mutable=["cache"])
        logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [B, V]
        scores, first = jax.lax.top_k(logp0, beam)  # [B, beam]
        # tile the prefix cache: batch row i serves beams i*beam..i*beam+beam-1
        tile = jnp.repeat(jnp.arange(b), beam)
        cache = _reorder(vars_["cache"], tile, b)
        rows = b * beam
        seqs = jnp.zeros((rows, n_tokens), jnp.int32)
        seqs = seqs.at[:, 0].set(first.reshape(rows))
        flat_scores = scores.reshape(rows)
        finished = (
            (first.reshape(rows) == eos_id) if eos_id is not None
            else jnp.zeros((rows,), bool)
        )
        lengths = jnp.ones((rows,), jnp.float32)

        def step(carry, t):
            cache, seqs, flat_scores, finished, lengths = carry
            last = jax.lax.dynamic_index_in_dim(seqs.T, t - 1, 0, keepdims=False)
            logits, vars_ = module.apply(
                {**params, "cache": cache}, last[:, None], mutable=["cache"]
            )
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))  # [rows, V]
            if eos_id is not None:
                # a finished beam may only repeat eos at zero added score
                only_eos = jnp.full_like(logp, neg).at[:, eos_id].set(0.0)
                logp = jnp.where(finished[:, None], only_eos, logp)
            total = flat_scores[:, None] + logp  # [rows, V] raw cumulative
            # prune by the SAME objective the final winner is ranked with:
            # penalize each candidate by its length (finished beams keep
            # their frozen length, live ones grow by this token)
            cand_len = lengths + jnp.where(finished, 0.0, 1.0)
            ranked_view = _penalize(total, cand_len[:, None]).reshape(
                b, beam * vocab
            )
            _, idx = jax.lax.top_k(ranked_view, beam)  # [B, beam]
            new_scores = jnp.take_along_axis(  # carry RAW scores forward
                total.reshape(b, beam * vocab), idx, axis=-1
            )
            parent = idx // vocab  # beam index within batch row
            token = (idx % vocab).astype(jnp.int32)
            flat_parent = (
                jnp.arange(b)[:, None] * beam + parent
            ).reshape(rows)
            cache = _reorder(vars_["cache"], flat_parent, rows)
            seqs = seqs[flat_parent].at[:, t].set(token.reshape(rows))
            was_finished = finished[flat_parent]
            lengths = lengths[flat_parent] + jnp.where(was_finished, 0.0, 1.0)
            if eos_id is not None:
                finished = was_finished | (token.reshape(rows) == eos_id)
            return (cache, seqs, new_scores.reshape(rows), finished, lengths), None

        if n_tokens > 1:
            (cache, seqs, flat_scores, finished, lengths), _ = jax.lax.scan(
                step,
                (cache, seqs, flat_scores, finished, lengths),
                jnp.arange(1, n_tokens),
            )
        ranked = _penalize(flat_scores.reshape(b, beam), lengths.reshape(b, beam))
        best = jnp.argmax(ranked, axis=-1)  # [B]
        pick = jnp.arange(b) * beam + best
        out = jnp.concatenate([prompt, seqs[pick]], axis=1)
        return out, ranked[jnp.arange(b), best]

    return search


def beam_search(
    config: TransformerConfig,
    params,
    prompt: jnp.ndarray,
    n_tokens: int,
    beam_size: int = 4,
    length_penalty: float = 0.0,
    eos_id: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Beam-search decode: returns ``(tokens [B, P+n_tokens], scores [B])``.

    The KV cache is tiled to ``B x beam_size`` rows after prefill and
    re-gathered along the batch axis at every step as beams reorder — the
    whole search (prefill + ``lax.scan`` over steps) is one jit-compiled
    program per ``(config, n_tokens, beam_size, ...)`` signature.
    ``eos_id`` freezes finished beams (they repeat eos at zero added
    score); ``length_penalty`` is the GNMT ``((5+len)/6)^alpha`` form,
    only meaningful when beams can finish at different lengths.
    """
    b, p = prompt.shape
    if not 1 <= beam_size <= config.vocab_size:
        raise ValueError(
            f"beam_size must be in [1, vocab_size={config.vocab_size}], "
            f"got {beam_size}"
        )
    if eos_id is not None and not 0 <= eos_id < config.vocab_size:
        # an out-of-range id would silently never freeze any beam (oob
        # scatter is dropped under jit) — fail loudly instead
        raise ValueError(
            f"eos_id {eos_id} out of range for vocab_size {config.vocab_size}"
        )
    if n_tokens <= 0:
        return prompt, jnp.zeros((b,), jnp.float32)
    _check_fits(p, n_tokens, config)
    config = _gate_kv_dtype(config, p + n_tokens)
    search = _build_beam_fns(
        config, n_tokens, beam_size, length_penalty, eos_id)
    return search(params, jnp.asarray(prompt, jnp.int32))


@functools.lru_cache(maxsize=16)
def _build_score_fn(config: TransformerConfig):
    forward = decode_family(config).score_logits(config)

    @jax.jit
    def score(params, tokens, from_pos):
        logits = forward(params, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        target = jnp.take_along_axis(
            logp, tokens[:, 1:, None].astype(jnp.int32), axis=-1
        )[..., 0]  # [B, S-1]: log P(tokens[t+1] | tokens[:t+1])
        pos = jnp.arange(tokens.shape[1] - 1)[None, :]
        mask = pos >= (from_pos[:, None] - 1)  # first scored token = from_pos
        return jnp.sum(target * mask, axis=-1)

    return score


def sequence_logprob(
    config: TransformerConfig,
    params,
    tokens: jnp.ndarray,
    from_pos: int = 1,
) -> jnp.ndarray:
    """Teacher-forced log-probability of ``tokens[:, from_pos:]`` given the
    prefix — one training-mode forward, jit-cached per config.

    ``tokens``: ``[B, S] int32``. Returns ``[B] float32`` sums of
    ``log P(tokens[t] | tokens[:t])`` for ``t >= from_pos`` — raw,
    unpenalized log-probability. With default knobs
    (``length_penalty=0``, no ``eos_id``) this equals the scores
    :func:`beam_search` reports at ``from_pos = prompt_len``; a nonzero
    length penalty (GNMT-scaled) or EOS freezing (post-EOS positions add
    nothing to a beam's score but are real tokens here) makes the two
    intentionally differ. Exposed for reranking/perplexity use.
    """
    tokens = np.asarray(tokens, dtype=np.int64)
    b, s = tokens.shape
    if not 1 <= from_pos < s:
        raise ValueError(f"from_pos must be in [1, {s - 1}], got {from_pos}")
    if s > config.max_seq:
        raise ValueError(
            f"sequence length {s} exceeds max_seq ({config.max_seq})"
        )
    lo, hi = int(tokens.min()), int(tokens.max())
    if lo < 0 or hi >= config.vocab_size:
        # take_along_axis clamps out-of-bounds ids under jit — a vocab
        # mismatch would return plausible-looking scores for the WRONG
        # token; fail loudly instead (same reasoning as beam_search's
        # eos_id check)
        raise ValueError(
            f"token ids span [{lo}, {hi}] but vocab_size is "
            f"{config.vocab_size}"
        )
    tokens = jnp.asarray(tokens, jnp.int32)
    fn = _build_score_fn(config)
    return fn(params, tokens, jnp.full((b,), from_pos, jnp.int32))


# ---------------------------------------------------------------------------
# Continuous batching: the slot-partitioned decode engine (device half).
#
# The inference server's scheduler keeps a fixed-capacity KV cache of
# ``max_slots`` independent rows and advances ALL live rows one decode
# iteration at a time — requests of different prompt lengths, sampling
# settings, and budgets share the same jit program. These are the device
# functions it drives:
#
# - :func:`slot_cache` allocates the ``[max_slots, max_seq, ...]`` cache
#   pytree with each layer's scalar ``cache_index`` generalized to a
#   ``[max_slots]`` vector — the static shape signal that flips
#   ``models/transformer.py::_decode_attend`` into its per-row slot mode
#   (per-row RoPE offsets, scatter writes, per-row visibility windows,
#   per-row flash-decode lengths);
# - ``prefill``/``extend`` run the SAME module + math as the solo
#   :func:`generate` path, so an admitted row's cache contents are
#   bit-identical to a solo request's — greedy parity is inherited from the
#   solo path rather than re-proven;
# - ``insert`` scatters R freshly prefilled rows (plus their lengths) into
#   free slots in one dispatch;
# - ``decode`` is a ``lax.scan`` of ``chunk`` single-token iterations over
#   the whole slot batch. Multi-token chunks amortize the per-dispatch host
#   round-trip floor that would otherwise dominate per-token serving
#   latency; finished rows freeze to eos inside the scan exactly like the
#   solo loop, so the host can retire them at any chunk boundary and pad
#   deterministically.
#
# Sampled rows stay deterministic per (request, seed) INDEPENDENT of batch
# composition: row keys are ``fold_in(PRNGKey(seed), absolute_position)``,
# and a row's absolute position depends only on its own progress — not on
# which other requests happen to share the batch, nor on the chunk size.


def _as_dict(tree):
    """Plain-dict view of a (possibly frozen) variable collection, so slot
    caches built here and row caches returned by flax apply always carry
    the same pytree structure."""
    if hasattr(tree, "items"):
        return {k: _as_dict(v) for k, v in tree.items()}
    return tree


def _cache_positions(cache):
    """The [max_slots] per-row write positions — every layer agrees, so
    the first ``cache_index`` leaf found is THE position vector."""
    if hasattr(cache, "items"):
        for name, sub in cache.items():
            if name == "cache_index":
                return sub
            found = _cache_positions(sub)
            if found is not None:
                return found
    return None


def slot_cache(config: TransformerConfig, params, max_slots: int):
    """Allocate the engine's zeroed slot cache: the decode module's cache
    pytree at batch ``max_slots``, with every ``cache_index`` leaf widened
    to a ``[max_slots]`` int32 vector. Built from ``jax.eval_shape`` (no
    forward pass runs); K/V rows start zeroed and positions at 0 — a free
    slot's garbage stays confined to its own row because every row only
    ever attends within its own visibility window."""
    module = _decode_module(config)
    dummy = jnp.zeros((max_slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda p: module.apply(p, dummy, mutable=["cache"])[1]["cache"],
        params)

    def build(node):
        if hasattr(node, "items"):
            return {
                name: (jnp.zeros((max_slots,), jnp.int32)
                       if name == "cache_index" else build(sub))
                for name, sub in node.items()
            }
        return jnp.zeros(node.shape, node.dtype)

    return build(_as_dict(shapes))


def _split_pools(cache, leaves=_POOL_LEAVES):
    """``(pools, rest)``: the cache's pool ``leaves`` (its family's
    ``pool_leaves``; ``TransformerConfig``'s by default) and everything
    else, each under the cache's own nesting. The pools are the bytes
    and are always distinct buffers; the rest (``page_table``,
    ``cache_index``: a few KB) is what :func:`set_page_tables` and
    :func:`_set_cache_positions` put ONE array into for every layer."""
    pools, rest = {}, {}
    for name, sub in cache.items():
        if name in leaves:
            pools[name] = sub
        elif hasattr(sub, "items"):
            pools[name], rest[name] = _split_pools(sub, leaves)
        else:
            rest[name] = sub
    return pools, rest


def _join_pools(pools, rest):
    """Inverse of :func:`_split_pools`."""
    out = dict(rest)
    for name, sub in pools.items():
        out[name] = (_join_pools(sub, rest[name])
                     if hasattr(sub, "items") else sub)
    return out


class _CacheProgram:
    """``jax.jit`` of an engine program that takes the resident cache as
    positional argument ``cache_arg`` and returns its successor, with the
    cache's pools DONATED: XLA aliases each pool to its output and updates
    it in place, so a dispatch neither copies the pool on the device nor
    allocates a second one. The caller's cache is consumed: rebind to the
    result and never touch the pools passed in again.

    Only the pools are donated. A tree that holds one array under several
    leaves cannot be donated whole (``Attempt to donate the same buffer
    twice``), and the tables and indices are exactly that; they ride along
    undonated, and the program itself sees the one cache pytree whose
    structure switches ``_decode_attend``'s mode. ``lower`` takes the
    same arguments as the call; ``body`` is the undecorated function
    (the tests jit it plainly as the undonated oracle)."""

    def __init__(self, fn, cache_arg: int, pool_leaves=_POOL_LEAVES):
        self.body = fn
        self._cache_arg = cache_arg
        self._pool_leaves = pool_leaves

        def program(pools, *args):
            args = list(args)
            args[cache_arg] = _join_pools(pools, args[cache_arg])
            return fn(*args)

        # the program's name in traces and in the compile cache's key
        program.__name__ = program.__qualname__ = fn.__name__
        self._jit = jax.jit(program, donate_argnums=0)

    def _split(self, args):
        args = list(args)
        pools, args[self._cache_arg] = _split_pools(
            args[self._cache_arg], self._pool_leaves)
        return pools, args

    def __call__(self, *args):
        pools, args = self._split(args)
        return self._jit(pools, *args)

    def lower(self, *args):
        pools, args = self._split(args)
        return self._jit.lower(pools, *args)


def _donates_cache(cache_arg: int, config: Any):
    """Decorator form of :class:`_CacheProgram`, for ``config``'s pools."""
    return functools.partial(_CacheProgram, cache_arg=cache_arg,
                             pool_leaves=decode_family(config).donated_leaves)


def pages_per_slot(max_seq: int, page_size: int) -> int:
    """Logical pages a full-depth row spans: ``ceil(max_seq / page_size)``
    — the page-table width (plus one pinned sentinel column)."""
    return -(-max_seq // page_size)


def paged_cache(config: TransformerConfig, params, max_slots: int,
                page_size: int, n_pages: int):
    """Allocate the engine's PAGED cache: like :func:`slot_cache` but
    every K/V (and int8 scale) slab is replaced by one shared pool of
    ``n_pages`` pages of ``page_size`` tokens, and each layer gains a
    ``page_table`` leaf ``[max_slots, pages_per_slot + 1]`` int32 whose
    entries start at the sentinel ``n_pages`` (no pages allocated; the
    last column is PINNED at the sentinel so out-of-range logical
    positions clamp onto it and their writes drop — see
    ``models/transformer.py::_decode_attend``). The table is duplicated
    per layer with identical values; the host updates all copies via
    :func:`set_page_tables`."""
    if page_size <= 0:
        raise ValueError(f"page_size must be positive, got {page_size}")
    if n_pages <= 0:
        raise ValueError(f"n_pages must be positive, got {n_pages}")
    pp = pages_per_slot(config.max_seq, page_size)
    module = _decode_module(config)
    pool_leaves = decode_family(config).pool_leaves
    dummy = jnp.zeros((max_slots, 1), jnp.int32)
    shapes = jax.eval_shape(
        lambda p: module.apply(p, dummy, mutable=["cache"])[1]["cache"],
        params)

    def build(node):
        if hasattr(node, "items"):
            out = {}
            for name, sub in node.items():
                if name == "cache_index":
                    out[name] = jnp.zeros((max_slots,), jnp.int32)
                    out["page_table"] = jnp.full(
                        (max_slots, pp + 1), n_pages, jnp.int32)
                elif name in pool_leaves:
                    out[name] = jnp.zeros(
                        (n_pages, page_size) + sub.shape[2:], sub.dtype)
                else:
                    out[name] = build(sub)
            return out
        return jnp.zeros(node.shape, node.dtype)

    return build(_as_dict(shapes))


def set_page_tables(cache, table):
    """Replace every layer's ``page_table`` leaf with ``table``
    (``[max_slots, pages_per_slot + 1]`` int32, host-authoritative) —
    one upload covers all layers since the copies are identical."""
    t = jnp.asarray(table, jnp.int32)

    def walk(node):
        if hasattr(node, "items"):
            return {name: (t if name == "page_table" else walk(sub))
                    for name, sub in node.items()}
        return node

    return walk(cache)


@functools.lru_cache(maxsize=16)
def _build_paged_fns(config: TransformerConfig, page_size: int):
    """Jit programs for the paged layout's host<->pool boundary:

    - ``insert(cache, row_cache, slots, length, start, table)`` scatters
      freshly prefilled DENSE rows (the [R, max_seq, ...] caches
      ``prefill``/``extend`` return) into the page pool through
      ``table`` ([max_slots, pages_per_slot+1], the host's authoritative
      copy, written to every layer's ``page_table`` leaf in the same
      dispatch). Only positions in ``[start, length)`` are written:
      positions below ``start`` are prefix pages SHARED with other
      requests (already populated, must not be re-written) and positions
      at/above ``length`` carry no data — both are routed to a flattened
      index past the pool so the scatter drops them.
    - ``gather_rows(cache, tables, start)`` materializes a dense
      solo-structured row cache ([R, max_seq, ...], scalar
      ``cache_index = start``, NO page_table leaf) from shared prefix
      pages, so ``extend`` can run the prompt SUFFIX through the exact
      chunked-prefill continuation path — prefix reuse inherits the
      solo path's numerics instead of re-proving them.

    ``decode``/``pick_rows`` need no paged variants: the cache pytree's
    own structure flips ``_decode_attend`` into paged mode, so the
    :func:`_build_slot_fns` programs serve both layouts."""
    max_seq = config.max_seq
    pp = pages_per_slot(max_seq, page_size)
    family = decode_family(config)
    pool_leaves, slot_leaves = family.pool_leaves, family.slot_leaves

    @_donates_cache(0, config)
    def insert(cache, row_cache, slots, length, start, table):
        row_cache = _as_dict(row_cache)
        r = slots.shape[0]

        def scatter_pool(pool, src):
            n_pg, ps = pool.shape[0], pool.shape[1]
            cols = jnp.broadcast_to(
                jnp.arange(max_seq)[None, :], (r, max_seq))
            pg = jnp.minimum(cols // ps, pp)
            phys = table[slots][jnp.arange(r)[:, None], pg]  # [R, S]
            live = (cols >= start) & (cols < length)
            flat = jnp.where(live, phys * ps + cols % ps, n_pg * ps)
            out = pool.reshape(n_pg * ps, pool.shape[-1]).at[flat].set(
                src[:, :max_seq])
            return out.reshape(pool.shape)

        def walk(dst, src):
            out = {}
            for name, d in dst.items():
                if name == "page_table":
                    out[name] = table.astype(d.dtype)
                elif name == "cache_index":
                    out[name] = d.at[slots].set(
                        jnp.broadcast_to(length, slots.shape).astype(d.dtype))
                elif name in pool_leaves:
                    out[name] = scatter_pool(d, src[name].astype(d.dtype))
                elif name in slot_leaves:  # a row's whole state, at its slot
                    out[name] = d.at[slots].set(src[name].astype(d.dtype))
                elif hasattr(d, "items"):
                    out[name] = walk(d, src[name])
                else:
                    out[name] = d
            return out

        return walk(cache, row_cache)

    if not family.prefix_reusable:
        def no_gather(cache, tables, start):
            raise ValueError(
                f"{type(config).__name__}'s cache holds per-row state "
                f"({', '.join(slot_leaves)}) that is not a function of cached "
                "pages: a row cannot be rebuilt from a shared prefix")

        return insert, no_gather

    @jax.jit
    def gather_rows(cache, tables, start):
        def walk(node):
            out = {}
            for name, sub in node.items():
                if name == "page_table":
                    continue
                if name == "cache_index":
                    out[name] = jnp.asarray(start, jnp.int32)
                elif name in pool_leaves:
                    n_pg, ps = sub.shape[0], sub.shape[1]
                    tab = jnp.minimum(tables[:, :pp], n_pg - 1)
                    g = sub[tab].reshape(
                        tables.shape[0], pp * ps, sub.shape[-1])[:, :max_seq]
                    # zero the tail beyond the shared prefix: extend's
                    # visibility mask never reads it, but a zeroed tail
                    # keeps the row cache byte-identical to a fresh
                    # prefill stopped at ``start``
                    pos = jnp.arange(max_seq)[None, :, None]
                    out[name] = jnp.where(pos < start, g, jnp.zeros_like(g))
                elif hasattr(sub, "items"):
                    out[name] = walk(sub)
                else:
                    out[name] = sub
            return out

        return walk(cache)

    return insert, gather_rows


@functools.lru_cache(maxsize=16)
def _build_prefill(config: TransformerConfig):
    """Admission prefill, cached per config ALONE (unlike
    :func:`_build_fns`, whose key drags in the whole decode signature):
    ``prefill`` fills a fresh cache over the whole prompt, ``extend``
    continues an existing one — the chunked-prefill path, which bounds
    how long admission can stall the running batch at the price of the
    continuation branch's dense attention."""
    module = _decode_module(config)

    @jax.jit
    def prefill(params, prompt):
        logits, vars_ = module.apply(params, prompt, mutable=["cache"])
        return logits[:, -1], vars_["cache"]

    @jax.jit
    def extend(params, cache, tokens):
        logits, vars_ = module.apply(
            {**params, "cache": cache}, tokens, mutable=["cache"])
        return logits[:, -1], vars_["cache"]

    return prefill, extend


def _truncate_logit_rows(logits, top_ks, top_ps):
    """Per-row :func:`_truncate_logits`: ``top_ks``/``top_ps`` arrive as
    [S] vectors (0 / 1.0 = off for that row) so ONE program serves every
    sampling mix in the batch. Same HF-style composition as the solo
    path — k first, then p over the k-renormalized survivors — with the
    static ``min(k, V)`` clamp replaced by a per-row clip + gather."""
    neg = jnp.finfo(logits.dtype).min
    v = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        srt, jnp.clip(top_ks, 1, v)[:, None] - 1, axis=-1)
    logits = jnp.where((top_ks[:, None] > 0) & (logits < kth), neg, logits)
    srt2 = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)  # masked entries -> ~0 mass
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    n_keep = jnp.sum(keep, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(srt2, n_keep - 1, axis=-1)
    return jnp.where(
        (top_ps[:, None] < 1.0) & (logits < cutoff), neg, logits)


@functools.lru_cache(maxsize=16)
def _build_slot_fns(config: TransformerConfig, chunk: int,
                    with_sampling: bool):
    """Jit programs for one (config, chunk size, sampling?) engine
    signature: ``insert(cache, row_cache, slots, length)``,
    ``pick_rows(logits, temps, top_ks, top_ps, seeds, positions)`` and
    ``decode(params, cache, tok, done, temps, top_ks, top_ps, seeds,
    eos)``. ``with_sampling=False`` is the greedy-only fast path — no
    vocab sort per step; the scheduler switches programs whenever a
    sampled request joins or leaves the batch (both operate on the same
    cache, so switching mid-flight is free)."""
    module = _decode_module(config)

    pool_leaves = decode_family(config).donated_leaves  # slabs: rows alike

    @_donates_cache(0, config)
    def insert(cache, row_cache, slots, length):
        row_cache = _as_dict(row_cache)

        def walk(dst, src):
            out = {}
            for name, d in dst.items():
                if name == "cache_index":  # scalar -> one entry per slot
                    out[name] = d.at[slots].set(
                        jnp.broadcast_to(length, slots.shape).astype(d.dtype))
                elif name in pool_leaves:
                    out[name] = d.at[slots].set(src[name].astype(d.dtype))
                elif hasattr(d, "items"):
                    out[name] = walk(d, src[name])
                else:  # the engine's own leaf, not a row's
                    out[name] = d
            return out

        return walk(cache, row_cache)

    def _pick(logits, temps, top_ks, top_ps, seeds, positions):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not with_sampling:
            return greedy
        t = jnp.where(temps > 0, temps, 1.0)[:, None]
        lg = _truncate_logit_rows(logits / t, top_ks, top_ps)

        def one(seed, pos, row_logits):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
            return jax.random.categorical(key, row_logits)

        sampled = jax.vmap(one)(seeds, positions, lg).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)

    @jax.jit
    def pick_rows(logits, temps, top_ks, top_ps, seeds, positions):
        return _pick(logits, temps, top_ks, top_ps, seeds, positions)

    @_donates_cache(1, config)
    def decode(params, cache, tok, done, temps, top_ks, top_ps, seeds, eos):
        def step(carry, _):
            cache, tok, done = carry
            logits, vars_ = module.apply(
                {**params, "cache": cache}, tok[:, None], mutable=["cache"])
            cache = _as_dict(vars_["cache"])
            pos = _cache_positions(cache)  # post-apply: the position of nxt
            nxt = _pick(logits[:, -1], temps, top_ks, top_ps, seeds, pos)
            # finished rows keep emitting eos, exactly like the solo scan
            # (eos = -1 means "no eos for this row": tokens are >= 0, so
            # done can never trip and the max() filler is never surfaced)
            nxt = jnp.where(done, jnp.maximum(eos, 0), nxt)
            done = done | (nxt == eos)
            return (cache, nxt, done), nxt

        (cache, tok, done), toks = jax.lax.scan(
            step, (cache, tok, done), None, length=chunk)
        return cache, tok, done, toks.T  # toks [max_slots, chunk]

    return insert, pick_rows, decode


# ---------------------------------------------------------------------------
# Speculative decoding: draft/verify device programs (docs/PERFORMANCE.md
# §7g). A small draft model proposes k tokens per round; the target scores
# all k+1 positions in ONE multi-token pass over the slot batch — the same
# per-row visibility-mask einsum path chunked prefill uses, so the target's
# logits at each position are computed by the same math as solo decode and
# greedy acceptance reproduces the solo token stream exactly. Sampled rows
# use the Leviathan et al. rejection-sampling correction, keyed by the
# engine's fold_in(seed, absolute_position) determinism (distinct subkey
# tags per decision so the draft sample, the accept coin and the residual
# sample never share a key).

#: fold_in tags under the per-position key: one stream per decision kind
_SPEC_DRAFT_TAG = 1   # the draft model's own sample
_SPEC_ACCEPT_TAG = 2  # the accept/reject uniform
_SPEC_RESID_TAG = 3   # the residual (correction) sample


def _set_cache_positions(cache, pos):
    """Replace every ``cache_index`` leaf with ``pos`` ([B] int32) — the
    per-row rollback/commit primitive speculative rounds use."""
    p = jnp.asarray(pos, jnp.int32)

    def walk(node):
        if hasattr(node, "items"):
            return {name: (p if name == "cache_index" else walk(sub))
                    for name, sub in node.items()}
        return node

    return walk(cache)


def _find_cache_leaf(cache, wanted):
    if hasattr(cache, "items"):
        for name, sub in cache.items():
            if name == wanted:
                return sub
            found = _find_cache_leaf(sub, wanted)
            if found is not None:
                return found
    return None


def _oob_write_position(cache, max_seq: int) -> int:
    """A logical position whose cache write is GUARANTEED to drop, for
    diverting per-row writes we must suppress (static, from the cache's
    own geometry). Paged: ``pages_per_slot * page_size`` — that position
    maps through the pinned sentinel column, so the scatter lands past
    the pool and JAX drops it (positions in ``[max_seq, pp*ps)`` would
    land in a real page's tail when max_seq isn't page-aligned, which is
    why plain ``max_seq`` is NOT safe here). Slab slot mode: ``max_seq``
    itself is out of bounds and drops."""
    pt = _find_cache_leaf(cache, "page_table")
    if pt is None:
        return max_seq
    ck = _find_cache_leaf(cache, "cached_k")
    return (pt.shape[1] - 1) * ck.shape[1]


@functools.lru_cache(maxsize=8)
def _build_spec_fns(config: TransformerConfig,
                    draft_config: TransformerConfig,
                    k: int, with_sampling: bool):
    """Jit programs for one speculative round over the slot batch:

    - ``draft_k(d_params, d_cache, tok, temps, top_ks, top_ps, seeds)``
      -> ``(d_cache, drafts [B,k], qprobs [B,k,V])`` — k sequential
      single-token draft-model steps from each row's committed position
      (the draft cache writes ride its OWN page tables over the shared
      pool). ``qprobs`` are the draft's post-truncation proposal
      distributions (a [B,k,1] placeholder on the greedy-only build).
    - ``verify(params, cache, tok, drafts, qprobs, temps, top_ks,
      top_ps, seeds, done, eos)`` -> ``(cache, emit [B,k+1], n_emit,
      n_acc, new_tok, new_done, catch_up, new_idx)`` — ONE target pass
      over ``[tok, d_1..d_k]`` (s = k+1; per-row visibility masks keep
      every position's attention window exact), greedy prefix-match or
      rejection-sampling acceptance, correction/bonus token, in-round
      eos freezing, and the per-row cache_index rollback to the
      committed length. Writes at rejected positions are left in place:
      they are invisible (behind the rolled-back index) and overwritten
      by the next round's writes at those positions.
    - ``commit(d_params, d_cache, last_draft, catch_up, new_idx)`` ->
      ``d_cache`` — re-syncs the draft cache: rows that accepted all k
      drafts are missing d_k's OWN KV entry (the draft scan wrote only
      its inputs), so one extra draft apply writes it; other rows divert
      that write out of bounds. Both then commit to ``new_idx``.

    Greedy bit-identity: accepted tokens are exactly the target's argmax
    at their position, and the correction token is the target's argmax
    after the accepted prefix — by induction the emitted stream equals
    solo target greedy decode, whatever the draft proposes (the draft
    only controls HOW MANY tokens each round yields, 1..k+1)."""
    target = _decode_module(config)
    draft = _decode_module(draft_config)

    def _keyed(seed, pos, tag):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), pos), tag)

    @_donates_cache(1, draft_config)
    def draft_k(d_params, d_cache, tok, temps, top_ks, top_ps, seeds):
        def dstep(carry, _):
            cache, tk = carry
            logits, vars_ = draft.apply(
                {**d_params, "cache": cache}, tk[:, None], mutable=["cache"])
            cache = _as_dict(vars_["cache"])
            pos = _cache_positions(cache)  # post-apply: position of nxt
            lg = logits[:, -1]
            greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if with_sampling:
                t = jnp.where(temps > 0, temps, 1.0)[:, None]
                tl = _truncate_logit_rows(lg / t, top_ks, top_ps)

                def one(seed, p_, row):
                    return jax.random.categorical(
                        _keyed(seed, p_, _SPEC_DRAFT_TAG), row)

                sampled = jax.vmap(one)(seeds, pos, tl).astype(jnp.int32)
                nxt = jnp.where(temps > 0, sampled, greedy)
                q = jax.nn.softmax(tl.astype(jnp.float32), axis=-1)
            else:
                nxt = greedy
                q = jnp.zeros((lg.shape[0], 1), jnp.float32)
            return (cache, nxt), (nxt, q)

        (d_cache, _), (drafts, qs) = jax.lax.scan(
            dstep, (d_cache, tok), None, length=k)
        return d_cache, drafts.T, jnp.transpose(qs, (1, 0, 2))

    @_donates_cache(1, config)
    def verify(params, cache, tok, drafts, qprobs, temps, top_ks, top_ps,
               seeds, done, eos):
        b = tok.shape[0]
        p = _cache_positions(cache)  # committed per-row positions
        seq = jnp.concatenate([tok[:, None], drafts], axis=1)  # [B, k+1]
        logits, vars_ = target.apply(
            {**params, "cache": cache}, seq, mutable=["cache"])
        cache = _as_dict(vars_["cache"])
        tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
        if with_sampling:
            v = logits.shape[-1]
            t = jnp.where(temps > 0, temps, 1.0)
            flat = (logits / t[:, None, None]).reshape(b * (k + 1), v)
            tl = _truncate_logit_rows(
                flat, jnp.repeat(top_ks, k + 1), jnp.repeat(top_ps, k + 1))
            pprobs = jax.nn.softmax(
                tl.astype(jnp.float32), axis=-1).reshape(b, k + 1, v)
            # draft token j (0-based) sits at absolute position p + 1 + j;
            # accept with prob min(1, p(d)/q(d)) under that position's key
            dpos = p[:, None] + 1 + jnp.arange(k)[None, :]

            def urow(seed, posr):
                def u1(pp_):
                    return jax.random.uniform(
                        _keyed(seed, pp_, _SPEC_ACCEPT_TAG), ())
                return jax.vmap(u1)(posr)

            us = jax.vmap(urow)(seeds, dpos)  # [B, k]
            pd = jnp.take_along_axis(
                pprobs[:, :k], drafts[..., None], axis=-1)[..., 0]
            qd = jnp.take_along_axis(
                qprobs, drafts[..., None], axis=-1)[..., 0]
            acc_sampled = us < jnp.minimum(pd / jnp.maximum(qd, 1e-20), 1.0)
            acc = jnp.where(
                (temps > 0)[:, None], acc_sampled, drafts == tgt[:, :k])
        else:
            acc = drafts == tgt[:, :k]
        n_acc = jnp.sum(
            jnp.cumprod(acc.astype(jnp.int32), axis=1), axis=1)  # [B] 0..k
        corr_greedy = jnp.take_along_axis(tgt, n_acc[:, None], axis=1)[:, 0]
        if with_sampling:
            # correction at the first rejection: sample the residual
            # norm(max(p - q, 0)); full acceptance (n_acc == k) pads q
            # with zeros so the "residual" is exactly the target's bonus
            # distribution p_k — one code path serves both cases
            qpad = jnp.concatenate(
                [qprobs, jnp.zeros((b, 1, qprobs.shape[-1]),
                                   qprobs.dtype)], axis=1)
            sel_p = jnp.take_along_axis(
                pprobs, n_acc[:, None, None], axis=1)[:, 0]
            sel_q = jnp.take_along_axis(
                qpad, n_acc[:, None, None], axis=1)[:, 0]
            resid = jnp.maximum(sel_p - sel_q, 0.0)
            rs = jnp.sum(resid, axis=-1, keepdims=True)
            # rs == 0 can only arise numerically (p <= q pointwise means
            # every token accepts); fall back to p itself
            dist = jnp.where(rs > 1e-20, resid / jnp.maximum(rs, 1e-20),
                             sel_p)

            def c1(seed, pos_, row):
                return jax.random.categorical(
                    _keyed(seed, pos_, _SPEC_RESID_TAG),
                    jnp.log(jnp.maximum(row, 1e-30)))

            corr_sampled = jax.vmap(c1)(
                seeds, p + 1 + n_acc, dist).astype(jnp.int32)
            corr = jnp.where(temps > 0, corr_sampled, corr_greedy)
        else:
            corr = corr_greedy
        # emitted tokens this round: d_1..d_{n_acc}, then the correction
        cols = jnp.arange(k + 1)[None, :]
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
        emit = jnp.where(
            cols < n_acc[:, None], drafts_pad,
            jnp.where(cols == n_acc[:, None], corr[:, None], jnp.int32(0)))
        # in-round eos freeze: cut at the first emitted eos, exactly where
        # the solo scan would freeze (the host pads the remaining budget)
        hit = (eos >= 0)[:, None] & (emit == eos[:, None]) \
            & (cols <= n_acc[:, None])
        hit_any = jnp.any(hit, axis=1)
        first_eos = jnp.argmax(hit, axis=1)
        n_emit = jnp.where(
            hit_any, jnp.minimum(n_acc + 1, first_eos + 1), n_acc + 1)
        new_done = done | hit_any
        new_tok = jnp.where(new_done, jnp.maximum(eos, 0), corr)
        # rows done at entry stay frozen (their slot is retired — writes
        # drop through the sentinel table; host reads nothing from them)
        emit = jnp.where(done[:, None], jnp.maximum(eos, 0)[:, None], emit)
        n_emit = jnp.where(done, k + 1, n_emit)
        n_acc = jnp.where(done, 0, n_acc)
        new_idx = p + n_acc + 1  # rollback: rejected positions invisible
        catch_up = (n_acc == k) & (~done)
        return (_set_cache_positions(cache, new_idx), emit, n_emit, n_acc,
                new_tok, new_done, catch_up, new_idx)

    @_donates_cache(1, draft_config)
    def commit(d_params, d_cache, last_draft, catch_up, new_idx):
        cur = _cache_positions(d_cache)  # p + k after the draft scan
        divert = jnp.where(
            catch_up, cur,
            jnp.int32(_oob_write_position(d_cache, draft_config.max_seq)))
        d_cache = _set_cache_positions(d_cache, divert)
        _, vars_ = draft.apply(
            {**d_params, "cache": d_cache}, last_draft[:, None],
            mutable=["cache"])
        return _set_cache_positions(_as_dict(vars_["cache"]), new_idx)

    return draft_k, verify, commit


def generate(
    config: TransformerConfig,
    params,
    prompt: jnp.ndarray,
    n_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
) -> jnp.ndarray:
    """Generate ``n_tokens`` continuations of ``prompt`` ``[B, P] int32``.

    Returns ``[B, P + n_tokens]`` (prompt + generated). ``temperature=0``
    is greedy argmax; otherwise softmax sampling at the given temperature
    (``rng`` required), optionally restricted to the ``top_k`` highest
    logits and/or the ``top_p`` nucleus (smallest set of tokens whose
    probability mass reaches ``top_p``; both given = k first, then p over
    the top-k-renormalized distribution). With ``eos_id``, a row that emits
    the end token keeps emitting it — the output stays ``[B, P+n_tokens]``
    (static shapes), finished rows are simply frozen, same as
    ``beam_search``'s EOS handling.
    """
    b, p = prompt.shape
    if n_tokens <= 0:
        return prompt
    _check_fits(p, n_tokens, config)
    if temperature > 0 and rng is None:
        raise ValueError("temperature sampling needs rng=jax.random.PRNGKey(...)")
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if eos_id is not None and not 0 <= eos_id < config.vocab_size:
        raise ValueError(f"eos_id {eos_id} outside vocab [0, {config.vocab_size})")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    config = _gate_kv_dtype(config, p + n_tokens)
    prefill, pick, decode_steps = _build_fns(
        config, n_tokens, temperature, top_k, top_p, eos_id
    )

    last_logits, cache = prefill(params, prompt)
    key0, key_rest = jax.random.split(rng)
    first = pick(last_logits, key0).astype(jnp.int32)
    out = [prompt, first[:, None]]
    if n_tokens > 1:
        out.append(decode_steps(params, cache, first, key_rest))
    return jnp.concatenate(out, axis=1)
