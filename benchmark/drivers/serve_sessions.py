"""Serving cells whose load is further turns of long sessions that are
already resident: a paged ``InferenceServer`` of the latent-sparse family
(``distriflow_tpu/models/latent_sparse.py``) on one chip, driven over
loopback as ``serve_loop.py`` drives its server, whose callers, open loop,
profiler thread and latency rule these are.

Set-up makes every session of the traffic file resident through the normal
``generate`` path, one request each (the session's context plus a first
turn, so that every page of the context is full and registered in the
prefix map), then warms the programs a window's admissions use (a turn is a
gather of the shared pages, one ``extend`` and one page scatter; their
shapes depend on the group size alone), all of it ``setup_s``. A window
request is one further turn (``lib/loadgen_sessions.py``). No prefix entry
may be evicted in the window: a run in which one was did other work, and is
not ``correct``.

Traffic file keys beside ``lib/loadgen_sessions.py``'s and
``serve_loop.py``'s: ``session_out_tokens`` (what the set-up requests
generate), ``score_tokens`` (``score()`` against the reference, past
``index_topk``, where the program's selected sets are also compared with
the reference's), ``check_replies_per_length`` (replies re-scored after
the window, per context length).

``correct`` has three kinds of comparison with the plain reference, each
with its limits below: the whole model on the same tokens (``score()``, the
chosen sets, the replies' logits), which bfloat16 matmuls already move by
hundredths of a nat; each float32 piece (a norm, the router) on the
program's own inputs to it, which nothing upstream moves and which is what
tells float32 from bfloat16 there (:func:`precision_control` runs the
program lowered and has to come out not ``correct``); and the sets one
reply's turn and generated tokens chose on the path the window timed
(``extend`` against the resident context, then decode from pages).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.drivers import serve_loop as base
from benchmark.lib import corpus as corpus_lib
from benchmark.lib import flops_glm_dsa, harness, loadgen_sessions, stats
from benchmark.lib import reference_glm_dsa as reference
from benchmark.lib.harness import Run, say

_latencies = base._latencies  # what rehearsal/knee_sweep.py asks a driver for

# -- limits, each with its reason (readings: PERF.md §6 PR 33) ----------------
# Both sides hold the same bfloat16 weights; the program's matmuls run in
# bfloat16 (router and norms in float32), the reference in float32 at
# "highest". Selection and routing are discrete: a near-tie that falls the
# other way changes one position's set or one token's expert, so single
# positions differ by tenths of a nat while the mean does not move.
# score(): the mean difference per token. Read 5.8e-5 to 1.3e-3 over eighteen
# seeds on the chip; a wrong mask, scale or rotary pairing shifts every
# position one way, by 0.1 nats and more (planted at toy widths in
# tests/test_latent_sparse_benchmark.py).
SCORE_NATS_PER_TOKEN = 4e-3
# Replies are judged on the reference's logits, not on token identity (the
# near-tie rule of serve_loop.py): a generated token's reference
# log-probability lies within the margin of the position's best. Read on the
# chip: over 4,607 teacher-forced positions a seed 93.8-94.0% are the
# reference's argmax, the 99th percentile of the gap 0.14-0.16, the largest
# 0.60 and 0.84; over the 473-904 generated tokens a run re-scores, 88-94%
# are the argmax, 0-1.45% lie over 0.25 (those near-ties falling the other
# way; a model that continues its own text meets more of them), the largest
# 0.19-0.80. So: at most MARGIN_MISSES of the checked tokens beyond the
# margin, none beyond GREEDY_WORST_NATS. A token read from a wrong page or
# position is off by the spread of the logits, a nat or more, at most
# positions.
GREEDY_MARGIN_NATS = 0.25
MARGIN_MISSES = 0.05
GREEDY_WORST_NATS = 2.0
# The program's chosen sets against the reference's, over score()'s tokens:
# the selected positions (past index_topk) read 0.9909-0.9920, the (token,
# held expert) routing 0.9808-0.9825; what differs are the tails, ranked
# within less than bfloat16 resolves. A wrong rotation, norm or scale
# scrambles the ranking.
SELECTION_OVERLAP_MIN = 0.95
ROUTING_OVERLAP_MIN = 0.95
# The limits above have sound readings only: the same weights with the norms
# and the router computed in bfloat16 read inside every one of them (PERF.md
# §6 PR 33), because the bfloat16 matmuls upstream move each logit by more.
# What tells the two apart is each float32 piece on the program's own input
# to it, over score()'s tokens (readings: PERF.md §6 PR 33, stated /
# lowered). A norm's output is the float32 result rounded once to the
# compute dtype: the share of its values that are (to 1e-5, room for a
# float32 sum in another order), lowest of the 21 norms, reads 1.00000 /
# 0.00013.
NORM_MATCH_MIN = 0.99
# The router's affinities against sigmoid(W_r m) at "highest" on the same m:
# the largest difference reads 0.0 / 2.0e-3 (bfloat16 resolves 2e-3 to 4e-3
# under 1).
AFFINITY_ERR_MAX = 1e-4
# Its choice of held experts against the k largest of those affinities plus
# the bias, as intersection over union: 1.00000 / 0.99607 (a bfloat16
# affinity moves the eighth and ninth of 256 past one another in one token
# of ten, and one choice in sixteen is of a held expert).
OWN_ROUTING_MIN = 0.998
# One reply's sets on the served path (its turn through extend, its
# generated tokens through decode steps from pages) against the
# reference's: the limits above, but the routing of some 250 tokens is
# some 500 (token, held expert) pairs, and a share of them scatters by 0.7
# points; read 0.9751-0.9831 (selection) and 0.9446-0.9906 (routing) on
# 150-330 positions behind 8,192, thirteen seeds. Rows read from a wrong
# page scramble both.
SERVED_ROUTING_MIN = 0.92


def program_config(c: Dict[str, Any]) -> Any:
    """The program's ``LatentSparseConfig`` at the file's sizes."""
    import jax.numpy as jnp

    from distriflow_tpu.models.latent_sparse import LatentSparseConfig

    return LatentSparseConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        index_n_heads=c["index_n_heads"], index_head_dim=c["index_head_dim"],
        index_topk=c["index_topk"], indexer_types=tuple(c["indexer_types"]),
        mlp_layer_types=tuple(c["mlp_layer_types"]),
        d_ff=c["intermediate_size"], moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["n_routed_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        routed_scaling_factor=c["routed_scaling_factor"],
        experts_held=tuple(c["experts_held"]),
        max_seq=c["max_position_embeddings"],
        index_rope_dim=c["index_rope_dim"],
        rope_base=float(c["rope_parameters"]["rope_theta"]),
        rms_eps=c["rms_norm_eps"], dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]),
        norm_router_dtype=getattr(jnp, c["norm_router_dtype"]))


def reference_model(c: Dict[str, Any]) -> Dict[str, Any]:
    return {"indexer_types": c["indexer_types"], "index_topk": c["index_topk"],
            "index_rope_dim": c["index_rope_dim"],
            "rope_theta": c["rope_parameters"]["rope_theta"],
            "num_experts_per_tok": c["num_experts_per_tok"],
            "routed_scaling_factor": c["routed_scaling_factor"],
            "experts_held": tuple(c["experts_held"])}


def _turn_prompt(corpus: np.ndarray, s: loadgen_sessions.Session, at: int,
                 turn: int) -> np.ndarray:
    """A session's whole context, then the turn's tokens from ``at``."""
    return np.concatenate([corpus[s.offset:s.offset + s.context_len],
                           corpus[at:at + turn]])


class _Callers(base._Callers):
    """``serve_loop``'s callers; a prompt is a session's context plus the
    request's turn."""

    sessions: List[loadgen_sessions.Session] = []
    turn_tokens = 0

    def prompt(self, req: Any) -> np.ndarray:
        return _turn_prompt(self._held_out, self.sessions[req.session],
                            req.offset, self.turn_tokens)


def _picked(cfg: Any, sown: Any) -> Dict[str, Any]:
    """What a forward chose, from the values it sows (traced): per ``full``
    layer the selected positions ``(idx, valid)``, per sparse layer the
    held experts routed to."""
    out: Dict[str, Any] = {"selected": {}, "routed": {}}
    for i, layer in enumerate(cfg.indexer_types):
        if layer == "full":
            out["selected"][i] = sown[f"layers_{i}"]["attn"]["selected"][0]
        if cfg.mlp_layer_types[i] == "sparse":
            out["routed"][i] = sown[f"layers_{i}"]["mlp"]["routed"][0]
    return out


def _as_sets(cfg: Any, picked: Dict[str, Any], n: int):
    """``_picked`` of ``n`` tokens on the host: ``[n, max_seq]`` bool per
    ``full`` layer and ``[n, held]`` bool per sparse layer."""
    selected, routed = {}, {}
    for i, (idx, valid) in picked["selected"].items():
        mask = np.zeros((n, cfg.max_seq), bool)
        np.put_along_axis(mask, np.asarray(idx).reshape(n, -1),
                          np.asarray(valid).reshape(n, -1), axis=1)
        selected[i] = mask
    for i, r in picked["routed"].items():
        routed[i] = np.asarray(r).reshape(n, -1)
    return selected, routed


def _own_inputs(cfg: Any, p: Any, sown: Any) -> Dict[str, Any]:
    """Each float32 piece of the program against the reference's on the
    program's own input to it (traced): ``norm_match``, ``affinity_err``,
    ``own_routing`` as the limits above define them."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    match, err, both, either = [], [], [], []

    def walk(node, pnode):
        for name, sub in node.items():
            if name == "io":  # an RMSNorm's input and output
                x, y = sub[0]
                want = reference.rms_norm(x.astype(f32), pnode["scale"])
                want = want.astype(y.dtype).astype(f32)
                match.append(jnp.mean(jnp.abs(y.astype(f32) - want)
                                      <= 1e-5 * jnp.abs(want) + 1e-7))
            elif name == "router_io":  # the router's input and affinities
                m, got = sub[0]
                want = reference.affinity(pnode, m)
                err.append(jnp.max(jnp.abs(got - want)))
                _, chosen = jax.lax.top_k(
                    want + pnode["e_score_correction_bias"],
                    cfg.n_experts_per_tok)
                first, count = cfg.experts_held
                held = jnp.any(chosen[..., None] == first + jnp.arange(count),
                               axis=-2)
                routed = node["routed"][0]
                both.append(jnp.sum(held & routed))
                either.append(jnp.sum(held | routed))
            elif hasattr(sub, "items"):
                walk(sub, pnode[name])

    walk(sown, p)
    return {"norm_match": jnp.min(jnp.stack(match)),
            "affinity_err": jnp.max(jnp.stack(err)),
            "own_routing": sum(both) / sum(either)}


def _own_within(own: Dict[str, float]) -> bool:
    return (own["norm_match"] >= NORM_MATCH_MIN
            and own["affinity_err"] <= AFFINITY_ERR_MAX
            and own["own_routing"] >= OWN_ROUTING_MIN)


def _system_sets(cfg: Any, params: Any, tokens: np.ndarray):
    """What the program's teacher-forced forward chooses on ``tokens``
    (``_as_sets``) and how its float32 pieces read (``_own_inputs``)."""
    import jax

    from distriflow_tpu.models.latent_sparse import LatentSparseLM

    module = LatentSparseLM(cfg)

    def forward(p, t):
        _, state = module.apply(p, t, mutable=["cache", "intermediates"])
        sown = state["intermediates"]
        return _picked(cfg, sown), _own_inputs(cfg, p["params"], sown)

    picked, own = jax.jit(forward)(params, tokens[None])
    selected, routed = _as_sets(cfg, picked, len(tokens))
    n = len(tokens)
    return ({i: m[:, :n] for i, m in selected.items()}, routed,
            {k: float(v) for k, v in own.items()})


def _held(got: Dict[int, np.ndarray], want: Dict[int, np.ndarray],
          rows: Any = slice(None)) -> float:
    """Share of the reference's choices on ``rows``, over the layers, that
    the program's choices hold."""
    return float(sum((got[i][rows] & want[i][rows]).sum() for i in want)
                 / sum(want[i][rows].sum() for i in want))


def _served_sets(cfg: Any, serving: Any, params: Any, toks: np.ndarray,
                 ctx_len: int, prompt_len: int, most_out: int):
    """What the program chose for one reply ``toks`` (context, turn,
    generated tokens) on the path the window timed, from position
    ``ctx_len`` on: the context prefilled in the engine's chunks by the
    engine's own programs, the turn through ``extend`` against that row
    cache, the row scattered into a page pool by the engine's ``insert``
    (physical pages in reverse order, so that the table is read), and every
    generated token but the last fed through a decode step against the
    pool. Pool and steps are sized for the longest reply, ``most_out``
    tokens, whatever this one's length: one set of programs for every
    seed, which the compile cache then holds. Returns ``(selected, routed,
    greedy)`` for those ``len(toks) - 1 - ctx_len`` positions; ``greedy``
    are the program's tokens after each position from the turn's last on."""
    import jax
    import jax.numpy as jnp

    from distriflow_tpu.models.generate import (
        _as_dict, _build_paged_fns, _build_prefill, decode_family,
        paged_cache, pages_per_slot)

    module = decode_family(cfg).decode_module(cfg)
    prefill, extend = _build_prefill(cfg)
    pc = serving.prefill_chunk or ctx_len
    _, row = prefill(params, toks[None, :min(pc, ctx_len)])
    for i in range(pc, ctx_len, pc):
        _, row = extend(params, row, toks[None, i:min(i + pc, ctx_len)])

    def served_turn(p, cache, t):
        logits, state = module.apply({**p, "cache": cache}, t,
                                     mutable=["cache", "intermediates"])
        return (_as_dict(state["cache"]), _picked(cfg, state["intermediates"]),
                jnp.argmax(logits[0], axis=-1))

    def served_steps(p, cache, fed):
        def step(cache, tok):
            cache, picked, nxt = served_turn(p, cache, tok[None, None])
            return cache, (picked, nxt[0])
        return jax.lax.scan(step, cache, fed)[1]

    ps = serving.page_size
    n_pages = -(-(prompt_len + most_out) // ps)
    insert, _ = _build_paged_fns(cfg, ps)
    row, turn, nxt = jax.jit(served_turn)(params, row,
                                          toks[None, ctx_len:prompt_len])
    pool = paged_cache(cfg, params, 1, ps, n_pages)
    table = np.full((1, pages_per_slot(cfg.max_seq, ps) + 1), n_pages,
                    np.int32)
    table[0, :n_pages] = np.arange(n_pages)[::-1]
    pool = insert(pool, row, np.zeros((1,), np.int32),
                  np.int32(prompt_len), np.int32(0), table)
    n_fed = len(toks) - 1 - prompt_len
    fed = np.zeros((most_out - 1,), toks.dtype)
    fed[:n_fed] = toks[prompt_len:-1]  # the steps past n_fed are not read
    steps, after = jax.tree.map(
        lambda v: np.asarray(v)[:n_fed],
        jax.jit(served_steps)(params, pool, fed))
    by_turn = _as_sets(cfg, turn, prompt_len - ctx_len)
    by_step = _as_sets(cfg, steps, n_fed)
    selected, routed = ({i: np.concatenate([a[i], b[i]]) for i in a}
                        for a, b in zip(by_turn, by_step))
    return selected, routed, np.concatenate([np.asarray(nxt)[-1:], after])


def _check_score(run: Run, cfg: Any, client: Any, params: Any,
                 held_out: np.ndarray) -> bool:
    import jax.numpy as jnp

    tokens = held_out[:run.traffic["score_tokens"]]
    n = len(tokens)
    got = float(client.score(tokens[None], from_pos=1)[0])
    logp, masks, routes = reference.log_probs(
        params, jnp.asarray(tokens), jnp.arange(n - 1),
        reference_model(run.config), return_sets=True)
    want = float(np.take_along_axis(
        np.asarray(logp), tokens[1:, None].astype(np.int64), axis=-1).sum())
    per_token = abs(got - want) / (n - 1)
    selected, routed, own = _system_sets(cfg, params, tokens)
    past = np.arange(min(cfg.index_topk, n - 1), n)
    overlap = _held(selected, masks, past)
    routing = _held(routed, routes)
    say(f"  reference: score() {got:.3f} vs {want:.3f} nats over {n - 1} "
        f"tokens, {per_token:.2e} per token (tol {SCORE_NATS_PER_TOKEN}); "
        f"selected sets at the {len(past)} positions past index_topk hold "
        f"{overlap:.4f} of the reference's (at least {SELECTION_OVERLAP_MIN}); "
        f"the routing to the held experts holds {routing:.4f} of the "
        f"reference's (at least {ROUTING_OVERLAP_MIN})")
    say(f"  reference on the program's own inputs: the norms' outputs are the "
        f"float32 result in {own['norm_match']:.5f} of their values (at least "
        f"{NORM_MATCH_MIN}), the router's affinities differ by at most "
        f"{own['affinity_err']:.2e} (tol {AFFINITY_ERR_MAX}), its choice of "
        f"held experts agrees in {own['own_routing']:.5f} (at least "
        f"{OWN_ROUTING_MIN})")
    return (per_token <= SCORE_NATS_PER_TOKEN
            and overlap >= SELECTION_OVERLAP_MIN
            and routing >= ROUTING_OVERLAP_MIN and _own_within(own))


def _check_served_sets(cfg: Any, serving: Any, params: Any, toks: np.ndarray,
                       ctx_len: int, prompt_len: int, most_out: int,
                       masks: Dict[int, np.ndarray],
                       routes: Dict[int, np.ndarray]) -> bool:
    """One reply's sets on the served path (:func:`_served_sets`) against
    the reference's on the same rows, ``ctx_len`` to the last token fed."""
    selected, routed, greedy = _served_sets(cfg, serving, params, toks,
                                            ctx_len, prompt_len, most_out)
    width = next(iter(masks.values())).shape[1]
    overlap = _held({i: m[:, :width] for i, m in selected.items()}, masks)
    routing = _held(routed, routes)
    same = float(np.mean(greedy == toks[prompt_len:]))
    say(f"  served path, {prompt_len - ctx_len} turn + {len(greedy) - 1} "
        f"decoded positions behind {ctx_len}: selected sets hold "
        f"{overlap:.4f} of the reference's (at least {SELECTION_OVERLAP_MIN}), "
        f"the routing {routing:.4f} (at least {SERVED_ROUTING_MIN}); "
        f"{same:.3f} of the reply's tokens are this replay's greedy ones")
    return overlap >= SELECTION_OVERLAP_MIN and routing >= SERVED_ROUTING_MIN


def _check_replies(run: Run, cfg: Any, serving: Any, params: Any,
                   records: List[Dict[str, Any]], callers: _Callers,
                   reqs: List[Any]) -> bool:
    """Every reply echoes its prompt at the asked length; per context
    length a seeded sample is re-scored by the reference, token by token.
    A reply of the longest context is padded to the longest reply that
    context can have, every other to the longest of the next context down:
    two sets of programs whatever the seed, and a short context does not
    pay for the longest one's attention (a set costs a minute of compiling
    on a first run, so not one a context length). The first of the shortest
    is also replayed on the served path, and its sets compared with the
    reference's."""
    import jax.numpy as jnp

    t = run.traffic
    by_index = {r.index: r for r in reqs}
    done = [r for r in records if r["ok"]]
    ok = True
    for rec in done:
        req = by_index[rec["index"]]
        if (rec["tokens"].shape != (req.prompt_len + req.out_tokens,)
                or not np.array_equal(rec["tokens"][:req.prompt_len],
                                      callers.prompt(req))):
            say(f"  reply {rec['index']}: wrong length or prompt not echoed")
            ok = False
    rng = np.random.default_rng(run.seed)
    most_out = int(t["output_tokens"]["max"])
    prompts = sorted({r.prompt_len for r in reqs})
    shared = prompts[-2] if len(prompts) > 1 else prompts[-1]
    pad_to = {p: (p if p == prompts[-1] else shared) + most_out
              for p in prompts}
    model = reference_model(run.config)
    worst, hits, misses, total, checked = 0.0, 0, 0, 0, 0
    turn = int(t["turn_tokens"])
    for plen in sorted({r["prompt_len"] for r in done}):
        mine = [r for r in done if r["prompt_len"] == plen]
        n_check = min(int(t["check_replies_per_length"]), len(mine))
        for i in rng.choice(len(mine), size=n_check, replace=False):
            toks = mine[int(i)]["tokens"]
            began = time.monotonic()
            padded = np.zeros((pad_to[plen],), np.int32)
            padded[:len(toks)] = toks  # causal: the tail cannot reach back
            positions = np.arange(plen - 1, len(toks) - 1)
            asked = np.full((most_out,), positions[-1])
            asked[:len(positions)] = positions
            if checked:
                logp = reference.log_probs(params, jnp.asarray(padded),
                                           jnp.asarray(asked), model)
            else:
                # the turn's rows and the longest reply's: one gather program
                rows = np.arange(plen - turn, plen + most_out - 1)
                logp, masks, routes = reference.log_probs(
                    params, jnp.asarray(padded), jnp.asarray(asked), model,
                    return_sets=True, set_rows=rows)
                fed = len(toks) - 1 - (plen - turn)
                ok = _check_served_sets(
                    cfg, serving, params, toks, plen - turn, plen, most_out,
                    {i: m[:fed] for i, m in masks.items()},
                    {i: r[:fed] for i, r in routes.items()}) and ok
            logp = np.asarray(logp)[:len(positions)]
            gap = logp.max(-1) - logp[np.arange(len(positions)),
                                      toks[positions + 1]]
            say(f"  reply {mine[int(i)]['index']} ({plen} + "
                f"{len(positions)} tokens): worst gap {gap.max():.4f}, "
                f"{int((gap > 0).sum())} not the reference's argmax, "
                f"{time.monotonic() - began:.1f}s")
            worst = max(worst, float(gap.max()))
            hits += int((gap == 0).sum())
            misses += int((gap > GREEDY_MARGIN_NATS).sum())
            total += len(positions)
            checked += 1
    if total:
        say(f"  reference: {checked} replies, {total} generated tokens: "
            f"{hits / total:.3f} are the reference's argmax, {misses} lie over "
            f"{GREEDY_MARGIN_NATS} nats under the best (at most "
            f"{MARGIN_MISSES:.0%}), the worst {worst:.4f} (at most "
            f"{GREEDY_WORST_NATS})")
        ok = (ok and misses <= MARGIN_MISSES * total
              and worst <= GREEDY_WORST_NATS)
    return ok and bool(done)


class Session:
    """A server with its weights, checked, its sessions resident and its
    window shapes warmed, and its callers. ``rehearsal/knee_sweep.py``
    opens one and measures several windows; :func:`run` measures one."""

    def __init__(self, run: Run):
        import jax

        from distriflow_tpu import InferenceClient, InferenceServer, ServingConfig
        from distriflow_tpu.models.latent_sparse import init_params
        from distriflow_tpu.obs.telemetry import Telemetry
        from distriflow_tpu.obs.tracing import Tracer

        self.run = run
        t, c = run.traffic, run.config
        setup_mark = run.meter.mark()
        self.cfg = program_config(c)
        self.serving = ServingConfig(**c["serving"])
        with run.phase("weights from the seed"):
            self.params = init_params(self.cfg, harness.prng_key(run.seed))
            jax.block_until_ready(self.params)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(self.params))
        pool = self.serving.pool_pages(self.cfg.max_seq)
        say(f"  model: {n_params / 1e6:.1f} M parameters "
            f"({flops_glm_dsa.parameters(c)['total'] / 1e6:.1f} M by the "
            f"arithmetic, norms and bias left out), pool {pool} pages of "
            f"{self.serving.page_size} = "
            f"{pool * self.serving.page_size * flops_glm_dsa.cache_bytes_per_token(c) / 1e9:.3f}"
            f" GB, {self.serving.max_slots} slots")

        self.telemetry = Telemetry(enabled=run.trace)
        if run.trace:
            self.telemetry.tracer = Tracer(enabled=True, max_spans=1_000_000)
        with run.phase("corpus"):
            self.corpus = corpus_lib.generate_corpus(t["corpus_tokens"], seed=0)
        turn = int(t["turn_tokens"])
        self.sessions = loadgen_sessions.sessions(t, run.seed, t["score_tokens"])
        # after the sessions: their first turns, the warm-up's turns, then
        # the windows' turns
        self._turns_from = loadgen_sessions.sessions_end(t, t["score_tokens"])
        g = int(t["warm_group_sizes"])
        self._window_from = self._turns_from + turn * (
            len(self.sessions) + g * (g + 1) // 2)
        self.server = InferenceServer(self.cfg, self.params, port=0,
                                      serving=self.serving,
                                      telemetry=self.telemetry)
        self.log: List[str] = []
        self.server.logger.log = lambda *a: self.log.append(
            " ".join(str(x) for x in a))
        self.server.setup()
        self.callers: Optional[_Callers] = None
        try:
            with InferenceClient(self.server.address, timeout=1100.0,
                                 telemetry=self.telemetry,
                                 report_interval_s=0.0) as client:
                with run.phase("score() against the reference"):
                    self.correct = _check_score(run, self.cfg, client,
                                                self.params, self.corpus)
                with run.phase("sessions made resident"):
                    self._make_resident(client)
                with run.phase("warm-up"):
                    self._warm_up(client)
            with run.phase("callers"):
                self.callers = _Callers(self.server.address, t["clients"],
                                        self.telemetry, self.corpus)
                self.callers.sessions = self.sessions
                self.callers.turn_tokens = turn
        except BaseException:
            self.close()
            raise
        self.n_warm_log = len(self.log)
        self.resident_pages = len(self.server._prefix_map)
        run.compile_setup = run.meter.since(setup_mark)

    def _turn(self, s: loadgen_sessions.Session, at: int) -> np.ndarray:
        return _turn_prompt(self.corpus, s, at,
                            int(self.run.traffic["turn_tokens"]))

    def _make_resident(self, client: Any) -> None:
        t = self.run.traffic
        turn = int(t["turn_tokens"])
        t0 = time.monotonic()
        for s in self.sessions:
            client.generate(self._turn(s, self._turns_from + s.index * turn)[None],
                            int(t["session_out_tokens"]))
        pages = len(self.server._prefix_map)
        tokens = sum(s.context_len for s in self.sessions)
        say(f"  {len(self.sessions)} sessions resident: {tokens} tokens, "
            f"{pages} pages in the prefix map, {self.server._pool.free_pages} "
            f"pages free, {time.monotonic() - t0:.1f}s")
        if pages * self.serving.page_size != tokens:
            raise SystemExit("the sessions' pages are not all registered: "
                             f"{pages} pages for {tokens} tokens")

    def _warm_up(self, client: Any) -> None:
        """Groups of 1..G turns on sessions of one length, as one request of
        n rows: the programs of an admission depend on the group size only."""
        t = self.run.traffic
        turn = int(t["turn_tokens"])
        chunk = self.serving.decode_chunk
        at = self._turns_from + turn * len(self.sessions)
        for n in range(1, int(t["warm_group_sizes"]) + 1):
            rows = []
            for s in self.sessions[:n]:
                rows.append(self._turn(s, at))
                at += turn
            client.generate(np.stack(rows), chunk + 1)
        say(f"  warmed turns for group sizes 1..{t['warm_group_sizes']} on "
            f"{self.sessions[0].context_len}-token sessions")

    def requests(self, seed: int, seconds: float,
                 rate: Optional[float] = None) -> List[loadgen_sessions.Request]:
        traffic = dict(self.run.traffic)
        if rate is not None:
            traffic["rate_per_s"] = rate
        return loadgen_sessions.requests(traffic, seconds, seed,
                                         self._window_from, len(self.corpus))

    def open_window(self, reqs: List[Any]) -> float:
        t0 = time.monotonic()
        base._open_loop(self.callers, reqs, t0)
        return t0

    def evicted(self) -> int:
        """Prefix entries lost since set-up ended."""
        return self.resident_pages - len(self.server._prefix_map) + len(
            self.server._evicted_prefixes)

    def close(self) -> None:
        if self.callers is not None:
            self.callers.close()
        if self.server is not None:
            self.server.stop()
        self.server = None  # frees the page pool
        gc.collect()


def run(run: Run) -> None:
    t = run.traffic
    session = Session(run)
    callers = session.callers
    reqs = session.requests(run.seed, run.seconds)
    say(f"  traffic: open loop, {loadgen_sessions.describe(reqs)}")
    try:
        run.end_to_end["setup_s"] = time.monotonic() - run.t_process
        window_mark = run.meter.mark()
        counters0 = session.telemetry.snapshot()["counters"]
        profiler = base._traced(run) if run.trace else None
        t0 = session.open_window(reqs)
        t1 = t0 + run.seconds
        run.window = (t0, t1)
        run.compile_window = run.meter.since(window_mark)
        # the traced seconds end before anything else reaches the chip
        while (profiler is not None and profiler.is_alive()
               and run.trace_window == (0.0, 0.0)):
            time.sleep(0.05)
        counters1 = session.telemetry.snapshot()["counters"]
        evicted = session.evicted()
        free_pages = session.server._pool.free_pages
        run.memory_peak_bytes = (run.devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0)
    finally:
        session.close()
    engine_errors = [line for line in session.log if "engine error" in line]
    admits = [line.split(" took")[0] for line in session.log[session.n_warm_log:]
              if line.startswith("admit[")]
    groups = sorted({a.split("x")[0] for a in admits})
    say(f"  admit groups since warm-up ({len(admits)}): "
        + " ".join(f"{g}]x{sum(a.startswith(g + 'x') for a in admits)}"
                   for g in groups))
    run.spans = session.telemetry.tracer.finished() if run.trace else []

    measured = callers.records  # every request was due inside the window
    for rec in measured:
        _latencies(rec)
    run.requests = measured
    run.attempted = len(measured)
    run.failed = sum(1 for r in measured if not r["ok"])
    for rec in measured:
        if not rec["ok"]:
            say(f"  request {rec['index']} failed: {rec.get('error')}")
            break
    late = [(r["sent"] - r["due"]) * 1e3 for r in measured]
    say("  " + stats.describe("generator lateness (sent - due)", late))
    ttft = [r["ttft"] for r in measured if "ttft" in r]
    tpot = [r["tpot"] for r in measured if "tpot" in r]
    say("  " + stats.describe("ttft", ttft))
    say("  " + stats.describe("tpot", tpot))
    out_tokens = sum(r["out_tokens"] for r in measured if r["ok"])
    hit = [r.get("prefix_tokens", 0) for r in measured if r["ok"]]
    say(f"  window {t1 - t0:.3f}s: {len(measured)} requests, {run.failed} "
        f"failed, {out_tokens} output tokens; prefix hits {min(hit, default=0)}"
        f"..{max(hit, default=0)} tokens a request; {evicted} prefix entries "
        f"evicted, {free_pages} pages free at the end; programs compiled or "
        f"loaded in the window: {run.compile_window['programs']}")
    if ttft and tpot:
        for name, values in (("serve_ttft_p90_ms", ttft),
                             ("serve_tpot_p90_ms", tpot)):
            tail = stats.percentile(values + [base.NEVER_MS] * run.failed, 90.0)
            run.end_to_end[name] = tail if tail < base.NEVER_MS / 2 else None
        run.end_to_end["serve_out_tok_s"] = out_tokens / (t1 - t0)
    run.shapes = {"admit_shapes": sorted(set(admits)),
                  "max_slots": session.serving.max_slots,
                  "decode_chunk": session.serving.decode_chunk,
                  "page_size": session.serving.page_size,
                  "counters": {k: v - counters0.get(k, 0)
                               for k, v in counters1.items()}}
    with run.phase("replies against the reference (after the window)"):
        replies_ok = _check_replies(run, session.cfg, session.serving,
                                    session.params, measured, callers, reqs)
    if profiler is not None:
        # stop_trace() has been writing the trace out on the host since the
        # traced seconds ended, beside the check above
        profiler.join(timeout=120.0)
    turn = int(t["turn_tokens"])
    by_index = {r.index: r for r in reqs}
    # every page of the session's context was a prefix hit, and no more
    hits_ok = all(r.get("prefix_tokens", 0) == by_index[r["index"]].prompt_len - turn
                  for r in measured if r["ok"])
    engine_path = all(r.get("path") == "slots" for r in measured if r["ok"])
    say(f"  correct: score() {session.correct}, replies {replies_ok}, engine "
        f"errors {len(engine_errors)}, failed requests {run.failed}, all "
        f"served by the engine {engine_path}, every context a prefix hit "
        f"{hits_ok}, prefix entries evicted {evicted}")
    run.correct = bool(session.correct and replies_ok and not engine_errors
                       and run.failed == 0 and engine_path and hits_ok
                       and evicted == 0)


def precision_control(run: Run) -> Dict[str, bool]:
    """``_check_score`` twice on one set of weights: the program as the
    configuration states it, and with its norms and router computed in
    bfloat16, the nearest precision below (``rehearsal/
    precision_control.py``). Limits that can tell the two apart give
    ``{"stated": True, "lowered": False}``."""
    import jax
    import jax.numpy as jnp

    from distriflow_tpu import InferenceClient, InferenceServer, ServingConfig
    from distriflow_tpu.models.latent_sparse import init_params

    stated = program_config(run.config)
    params = init_params(stated, harness.prng_key(run.seed))
    jax.block_until_ready(params)
    corpus = corpus_lib.generate_corpus(run.traffic["corpus_tokens"], seed=0)
    out = {}
    for name, cfg in (("stated", stated), ("lowered", dataclasses.replace(
            stated, norm_router_dtype=jnp.bfloat16))):
        say(f"{name}: norms and router in "
            f"{jnp.dtype(cfg.norm_router_dtype).name}")
        server = InferenceServer(cfg, params, port=0,
                                 serving=ServingConfig(**run.config["serving"]))
        server.setup()
        try:
            with InferenceClient(server.address, timeout=1100.0) as client:
                out[name] = _check_score(run, cfg, client, params, corpus)
        finally:
            server.stop()
        say(f"{name}: correct {out[name]}")
    return out
