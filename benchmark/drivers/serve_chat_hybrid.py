"""Serving cells of the hybrid state-space family
(``distriflow_tpu/models/hybrid_ssm.py``): a paged ``InferenceServer`` on
one chip under ``serve_loop.py``'s chat traffic, whose callers, warm-up, open
loop, profiler thread and latency rule these are. What differs is the model
(built from the configuration file by :func:`program_config`, weights in
bfloat16 from the seed), its reference
(``benchmark/lib/reference_granite_hybrid.py``) and what ``correct`` compares.

Traffic file keys beside ``serve_loop.py``'s: ``score_rows`` x ``score_len``
(``score()`` against the reference), ``state_prompt_len`` (the prompt whose
float32 pieces are compared on the program's own inputs),
``replay_prompt_len``, ``replay_group``, ``replay_stagger`` and
``replay_dispatches`` (the served-path replay), ``lead_seconds`` (how much
of the traffic's cycle is sent, unmeasured, before and after the window:
:meth:`Session.open_window`).

``correct`` has four kinds of comparison with the plain reference, each with
its limits below: (a) the whole model on the same tokens (``score()``), which
bfloat16 matmuls already move; (b) replies of the window re-scored by the
reference at their own lengths (prefill, then decode through state and
pages, must be one full forward): they hold the pages, the masks and the
expert layer, and not the recurrent state, whose part of a seeded mixer's
output is a hundredth of the skip path's (PERF.md 6, PR 35 (6)); (c) each
float32 piece on the program's own input to it (every norm, every router's
logits and choice, every Mamba layer's final state after a long prompt
given the program's own ``x, dt, B, C``), which nothing upstream moves and
which is what tells float32 from bfloat16 there (:func:`precision_control`
runs the program lowered and has to come out not ``correct``); (d) what
holds the per-row state on the engine's path at the window's load: six
rows in two staggered groups prefilled, written by the engine's three-row
``insert`` into scattered slots and stepped 256 times by the engine's
decode chunk, every Mamba layer's state and conv rows of every row against
one prefill of the longer sequence (:func:`replay_readings`).
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.drivers import serve_loop as base
from benchmark.lib import corpus as corpus_lib
from benchmark.lib import flops_granite_hybrid, harness, loadgen, stats
from benchmark.lib import reference_granite_hybrid as reference
from benchmark.lib.harness import Run, say

_latencies = base._latencies  # what rehearsal/knee_sweep.py asks a driver for

# -- limits, each with its reason (readings: PERF.md §6 PR 35) ----------------
# Both sides hold the same bfloat16 weights; the program's matmuls run in
# bfloat16 (norms, router, dt, decay and state in float32), the reference in
# float32 at "highest". The tied head of seeded weights is nearly flat (the
# embedding is drawn at 0.001, configuration file's ``assumed``): a token's
# logit has a spread of 0.004 nats over the vocabulary, so every limit on
# logits is a small number, and routing is discrete (a near-tie that falls
# the other way changes one token's expert).
# score(): the largest difference of a row's sum, per token, over score_rows
# x score_len tokens. Read 0 to 2.5e-6 (stated and lowered alike: a gross
# check, that the program computes this model at all).
SCORE_NATS_PER_TOKEN = 5e-5
# Replies are judged on the reference's logits, not on token identity (the
# near-tie rule of serve_loop.py): a generated token's reference
# log-probability lies within the margin of the position's best for all but
# MARGIN_MISSES of the checked tokens, and none lies further than
# GREEDY_WORST_NATS. Read over 628-1,088 generated tokens a run: 93.0-99.6%
# are the reference's argmax, the worst 1e-4 to 7e-4 under its best. A token
# decoded from a wrong page, mask or expert is off by the spread of the
# logits (0.004 nats) at most positions; one decoded from a wrong recurrent
# state is not (the state's part of a seeded mixer's output is a hundredth of
# the skip path's): the state is held by (c) and (d), not here.
GREEDY_MARGIN_NATS = 1e-3
MARGIN_MISSES = 0.05
GREEDY_WORST_NATS = 0.01
# (c) on the program's own inputs, over state_prompt_len tokens (readings
# stated / lowered, PERF.md §6 PR 35). A norm's output is the float32 result
# rounded once to its output's dtype: the share of its values that are (to
# 1e-5), lowest of the 30 norms (20 layer norms, 9 gated ones, the last):
# 1.00000 / 0.00149.
NORM_MATCH_MIN = 0.99
# The router's logits against W_r m at "highest" on the same m: the largest
# difference, 0.0 / 8.1e-3 (logits are of unit size; bfloat16 resolves 4e-3
# to 8e-3 there).
LOGITS_ERR_MAX = 1e-4
# Its choice of held experts against the k largest of those logits, as
# intersection over union: 1.00000 / 0.99696.
OWN_ROUTING_MIN = 0.998
# A Mamba layer's final state after the prompt against the sequential
# float32 recurrence over the program's own x, dt, B, C, largest difference
# over the state's largest value, worst layer: 1.3e-4 to 5.0e-4 / 1.2e-1. On
# the CPU the two agree to 2.5e-7; on the chip the reference multiplies
# 1,536 rounded exp() values where the chunked form takes the exp of their
# sum, which is the suspected source of the 1e-4. A bfloat16 state, decay or
# dt is three hundred times off.
STATE_ERR_MAX = 5e-3
# (d) the served path at the window's load (:func:`replay_readings`): six
# rows after prefill, a three-row insert and 256 decode steps against one
# prefill of the same tokens, same measure, worst row. Both sides are the
# program's. **The first Mamba layer** is fed the same embedded tokens on
# both paths, so what differs is the state's own arithmetic (the order of the
# sums and, lowered, a state that rounds to bfloat16 256 times): 6.0e-6 to
# 8.0e-4 over four runs of six rows; lowered it was read on one row only (the
# first build: 2.0e-5 to 1.05e-4 / 1.1e-1 to 1.7e-1).
REPLAY_ERR_MAX = 5e-3
# **Every Mamba layer**: from the second on, a layer's input is a residual
# stream that the two paths computed in bfloat16 in another order (a token
# whose router's choice fell the other way among them), and a head that
# forgets within a token or two holds that one token's difference: 5.7e-2 to
# 8.0e-2 over thirteen runs, worst layer (8, 9 or 4) and row / 9.0e-2
# lowered. This limit guards the path against gross faults only, not the
# precision. At the toy size in float32 (clean 1.3e-6;
# tests/test_hybrid_ssm_benchmark.py) the later layers' rows stepped from
# one another's state read 2.1 and rows not stepped 1.8; a row stepped twice
# a step reads 0.40 in the first Mamba layer and 0.06 planted in the last
# alone, which this limit does not see. Every layer steps through one
# routine on one order of rows made once a step, so the first layer's limit
# is what holds the finer faults of the stepping; what insert did not write
# is seen exactly in every layer, right after it (insert_err).
REPLAY_DEEP_ERR_MAX = 0.3
# The conv's last three input rows, bfloat16 on both sides, largest
# difference over the leaf's largest value, worst layer and row: 2.9e-2 to
# 5.6e-2 / 3.6e-2 (the same upstream differences; one rounding of a bfloat16
# value is 4e-3 to 8e-3 of the largest). Guards the path too: rows not
# rolled on, or another row's, read 1 or more.
REPLAY_CONV_ERR_MAX = 0.25
#: the family's per-row state leaves, by name (what (d) compares; not read
#: from the family, which a fault may have changed)
STATE_LEAVES = ("ssm_state", "conv_state")


def program_config(c: Dict[str, Any], **over: Any) -> Any:
    """The program's ``HybridSSMConfig`` at the file's sizes."""
    import jax.numpy as jnp

    from distriflow_tpu.models.hybrid_ssm import HybridSSMConfig

    if (c["mamba_n_groups"] != 1 or c["position_embedding_type"] != "nope"
            or c["scoring_func"] != HybridSSMConfig.scoring_func):
        raise SystemExit("the family has one B/C group, no rotation and "
                         f"{HybridSSMConfig.scoring_func} scoring")
    return HybridSSMConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(c["layer_types"]),
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        attention_multiplier=c["attention_multiplier"],
        mamba_n_heads=c["mamba_n_heads"], mamba_d_head=c["mamba_d_head"],
        mamba_d_state=c["mamba_d_state"], mamba_d_conv=c["mamba_d_conv"],
        mamba_chunk_size=c["mamba_chunk_size"],
        moe_d_ff=c["intermediate_size"],
        shared_d_ff=c["shared_intermediate_size"],
        n_routed_experts=c["num_local_experts"],
        n_experts_per_tok=c["num_experts_per_tok"],
        experts_held=tuple(c["experts_held"]),
        max_seq=c["max_position_embeddings"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]), rms_eps=c["rms_norm_eps"],
        dtype=getattr(jnp, c["compute_dtype"]),
        param_dtype=getattr(jnp, c["param_dtype"]),
        norm_router_dtype=getattr(jnp, c["norm_router_dtype"]),
        ssm_dtype=getattr(jnp, c["ssm_dtype"]),
        embed_init_std=c["embed_init_std"]),
        **over})


def _padded(tokens: np.ndarray, length: int) -> np.ndarray:
    """``tokens`` in a row of ``length``: causal, the tail cannot reach back.
    One length for every sequence the reference sees: one set of programs."""
    out = np.zeros((length,), np.int32)
    out[:len(tokens)] = tokens
    return out


def _check_score(run: Run, cfg: Any, client: Any, params: Any,
                 held_out: np.ndarray) -> bool:
    import jax.numpy as jnp

    t = run.traffic
    rows, length = int(t["score_rows"]), int(t["score_len"])
    tokens = held_out[:rows * length].reshape(rows, length)
    got = np.asarray(client.score(tokens, from_pos=1), np.float64)
    want = []
    for row in tokens:
        logp = reference.log_probs(
            params, jnp.asarray(_padded(row, cfg.max_seq)),
            jnp.arange(length - 1), run.config)
        want.append(float(np.take_along_axis(
            np.asarray(logp), row[1:, None].astype(np.int64), axis=-1).sum()))
    per_token = float(np.abs(got - np.asarray(want)).max()) / (length - 1)
    say(f"  reference: score() {got.round(3).tolist()} vs "
        f"{np.round(want, 3).tolist()} nats over {rows} x {length - 1} "
        f"tokens, at most {per_token:.2e} per token "
        f"(tol {SCORE_NATS_PER_TOKEN})")
    return per_token <= SCORE_NATS_PER_TOKEN


def _own_inputs(cfg: Any, p: Any, sown: Any) -> Dict[str, Any]:
    """Each float32 piece of the program against the reference's on the
    program's own input to it (traced): ``norm_match``, ``logits_err``,
    ``own_routing``, ``state_err`` as the limits above define them."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    match, err, both, either, state = [], [], [], [], []

    def walk(node, pnode):
        for name, sub in node.items():
            if name == "io":  # an RMSNorm's input and output
                x, y = sub[0]
                want = reference.rms_norm(x.astype(f32), pnode["scale"])
                want = want.astype(y.dtype).astype(f32)
                match.append(jnp.mean(jnp.abs(y.astype(f32) - want)
                                      <= 1e-5 * jnp.abs(want) + 1e-7))
            elif name == "router_io":  # the router's input and logits
                m, got = sub[0]
                want = reference.router_logits(pnode["router"], m)
                err.append(jnp.max(jnp.abs(got - want)))
                _, chosen = jax.lax.top_k(want, cfg.n_experts_per_tok)
                first, count = cfg.experts_held
                held = jnp.any(chosen[..., None] == first + jnp.arange(count),
                               axis=-2)
                routed = node["routed"][0]
                both.append(jnp.sum(held & routed))
                either.append(jnp.sum(held | routed))
            elif name == "ssm_io":  # a mixer's x, dt, B, C and its states
                x, dt, b, c, before, after = sub[0]
                want = reference.final_state(x[0], dt[0], b[0], c[0],
                                             pnode["A_log"], before[0])
                state.append(jnp.max(jnp.abs(after[0].astype(f32) - want))
                             / jnp.max(jnp.abs(want)))
            elif hasattr(sub, "items"):
                walk(sub, pnode[name])

    walk(sown, p)
    return {"norm_match": jnp.min(jnp.stack(match)),
            "logits_err": jnp.max(jnp.stack(err)),
            "own_routing": sum(both) / sum(either),
            "state_err": jnp.max(jnp.stack(state))}


def _own_within(own: Dict[str, float]) -> bool:
    return (own["norm_match"] >= NORM_MATCH_MIN
            and own["logits_err"] <= LOGITS_ERR_MAX
            and own["own_routing"] >= OWN_ROUTING_MIN
            and own["state_err"] <= STATE_ERR_MAX)


def own_input_readings(cfg: Any, params: Any,
                       tokens: np.ndarray) -> Dict[str, float]:
    """:func:`_own_inputs` of the program's teacher-forced forward on one
    prompt ``tokens``."""
    import jax

    from distriflow_tpu.models.hybrid_ssm import HybridSSMLM

    module = HybridSSMLM(cfg)

    def own_inputs(p, t):
        _, state = module.apply(p, t, mutable=["cache", "intermediates"])
        return _own_inputs(cfg, p["params"], state["intermediates"])

    return {k: float(v) for k, v in
            jax.jit(own_inputs)(params, tokens[None]).items()}


def _check_own_inputs(run: Run, cfg: Any, params: Any,
                      held_out: np.ndarray) -> bool:
    n = int(run.traffic["state_prompt_len"])
    own = own_input_readings(cfg, params, held_out[-n:])
    say(f"  reference on the program's own inputs ({n} tokens): the norms' "
        f"outputs are the float32 result in {own['norm_match']:.5f} of their "
        f"values (at least {NORM_MATCH_MIN}), the routers' logits differ by "
        f"at most {own['logits_err']:.2e} (tol {LOGITS_ERR_MAX}), their choice "
        f"of held experts agrees in {own['own_routing']:.5f} (at least "
        f"{OWN_ROUTING_MIN}), the Mamba layers' final states differ from the "
        f"sequential float32 recurrence by at most {own['state_err']:.2e} of "
        f"their largest value (tol {STATE_ERR_MAX})")
    return _own_within(own)


def _slot_leaves(cache: Any, names: Any, path: str = "") -> Dict[str, Any]:
    """``{"<layer path>/<name>": leaf}`` for every cache leaf called one of
    ``names``: each Mamba layer's per-row state."""
    out = {}
    for name, sub in cache.items():
        if name in names:
            out[f"{path}{name}"] = sub
        elif hasattr(sub, "items"):
            out.update(_slot_leaves(sub, names, f"{path}{name}/"))
    return out


def replay_readings(t: Dict[str, Any], cfg: Any, serving: Any, params: Any,
                    held_out: np.ndarray) -> Dict[str, float]:
    """(d): the engine's own programs (the ones the window ran: nothing
    compiles) at the window's load. Two groups of ``replay_group`` different
    prompts of ``replay_prompt_len`` tokens are prefilled as the engine
    prefills a group and written by the group's one ``insert`` into a cache
    of the engine's own shape, at slots that are neither adjacent nor in
    order, their pages in reverse order; the second group joins
    ``replay_stagger`` dispatches after the first, while the first is live
    (so the rows of a step stand at different positions and a step over
    both groups takes more than one trip of ``ROWS``). Each group is stepped
    ``replay_dispatches`` decode chunks and then retired as the engine
    retires (its table rows at the sentinel), the other going on. Every
    Mamba layer's ``ssm_state`` and ``conv_state`` of every row, read when
    its group's last chunk returns, against one prefill of the prompt and
    the tokens those steps consumed: the largest difference over the
    leaf's largest value, worst row and layer (``state_err``,
    ``conv_err``) and worst row of the first Mamba layer
    (``first_state_err``); and, right after each insert, the same leaves at the
    group's slots against the prefill's row cache, which insert copies
    (``insert_err``, the largest difference: 0)."""
    from distriflow_tpu.models.generate import (
        _build_paged_fns, _build_prefill, _build_slot_fns, paged_cache,
        pages_per_slot, set_page_tables)

    plen, n_disp = int(t["replay_prompt_len"]), int(t["replay_dispatches"])
    group, stagger = int(t["replay_group"]), int(t["replay_stagger"])
    slots, chunk, ps = serving.max_slots, serving.decode_chunk, serving.page_size
    n_pages = serving.pool_pages(cfg.max_seq)
    names = STATE_LEAVES
    prefill, _ = _build_prefill(cfg)
    insert, _ = _build_paged_fns(cfg, ps)
    _, _, decode = _build_slot_fns(cfg, chunk, False)

    stride = max(slots // (2 * group), 1)
    spread = slots - 1 - stride * np.arange(2 * group)  # from the last slot
    need = -(-(plen + n_disp * chunk) // ps)
    rows = []  # one dict a row; a group is `group` consecutive entries
    for j, slot in enumerate(np.concatenate([spread[0::2], spread[1::2]])):
        lo = len(held_out) - (j + 1) * plen
        rows.append({"slot": int(slot), "prompt": held_out[lo:lo + plen],
                     "pages": np.arange(n_pages - (j + 1) * need,
                                        n_pages - j * need)[::-1]})
    groups = [(0, rows[:group]), (stagger, rows[group:])]  # (joins at, rows)

    cache = paged_cache(cfg, params, slots, ps, n_pages)
    table = np.full((slots, pages_per_slot(cfg.max_seq, ps) + 1), n_pages,
                    np.int32)
    tok = np.zeros((slots,), np.int32)
    done = np.ones((slots,), bool)
    zeros, ones = np.zeros((slots,), np.int32), np.ones((slots,), np.float32)
    got, copied = {}, 0.0
    for d in range(stagger + n_disp + 1):
        for joins, members in groups:
            at = np.array([r["slot"] for r in members], np.int32)
            if d == joins + n_disp:  # the group's last chunk has returned
                for name, leaf in _slot_leaves(cache, names).items():
                    got[joins, name] = np.asarray(leaf[at], np.float32)
                table[at] = n_pages
                cache = set_page_tables(cache, table.copy())
                done[at] = True
            if d == joins:
                logits, row_cache = prefill(params, np.stack(
                    [r["prompt"] for r in members]))
                for r in members:
                    table[r["slot"], :need] = r["pages"]
                cache = insert(cache, row_cache, at, np.int32(plen),
                               np.int32(0), table.copy())
                wrote = _slot_leaves(cache, names)
                for name, leaf in _slot_leaves(row_cache, names).items():
                    copied = max(copied, float(np.abs(
                        np.asarray(wrote[name][at], np.float32)
                        - np.asarray(leaf, np.float32)).max()))
                tok[at] = np.argmax(np.asarray(logits), axis=-1)
                done[at] = False
                for r, first in zip(members, tok[at]):
                    r["fed"] = [int(first)]
        if d == stagger + n_disp:
            break
        cache, tok, done, toks = decode(
            params, cache, tok, done, zeros.astype(np.float32), zeros, ones,
            zeros, np.full((slots,), -1, np.int32))
        tok, done, toks = np.array(tok), np.array(done), np.asarray(toks)
        for joins, members in groups:
            if joins <= d < joins + n_disp:
                for r in members:
                    r["fed"].extend(toks[r["slot"]].tolist())
    del cache
    worst = {name: (0.0, "") for name in names}
    first, first_err = f"layers_{cfg.layer_types.index('mamba')}/", 0.0
    for joins, members in groups:
        # the steps consumed fed[:-1]; the last token fed out is not yet in
        whole = np.stack([np.concatenate(
            [r["prompt"], np.asarray(r["fed"][:-1], np.int32)])
            for r in members])
        _, want = prefill(params, whole)
        for name, leaf in _slot_leaves(want, names).items():
            leaf = np.asarray(leaf, np.float32)
            for j, r in enumerate(members):
                err = float(np.abs(got[joins, name][j] - leaf[j]).max()
                            / np.abs(leaf[j]).max())
                kind = name.rsplit("/", 1)[-1]
                if err >= worst[kind][0]:
                    worst[kind] = (err, f"{name} of slot {r['slot']}")
                if name.startswith(first) and kind == "ssm_state":
                    first_err = max(first_err, err)
    say(f"  served path: {2 * group} rows of {plen} prompt tokens in two "
        f"groups of {group} (slots {[r['slot'] for r in rows]}, pages "
        f"reversed, the second group {stagger} dispatches after the first), "
        f"{n_disp * chunk} decode steps each by the engine's programs, "
        f"against one prefill of the {plen + n_disp * chunk} tokens: worst "
        + ", ".join(f"{kind} {err:.2e} of its largest value ({where})"
                    for kind, (err, where) in worst.items())
        + f" (tol {REPLAY_DEEP_ERR_MAX}, {REPLAY_CONV_ERR_MAX}), the first "
        f"Mamba layer's ssm_state {first_err:.2e} (tol {REPLAY_ERR_MAX}); a "
        f"group's state and conv rows at its slots right after insert "
        f"differ from the prefill's by {copied:.1e} (a copy: 0)")
    return {"first_state_err": first_err, "state_err": worst["ssm_state"][0],
            "conv_err": worst["conv_state"][0], "insert_err": copied}


def _replay_within(got: Dict[str, float]) -> bool:
    return (got["first_state_err"] <= REPLAY_ERR_MAX
            and got["state_err"] <= REPLAY_DEEP_ERR_MAX
            and got["conv_err"] <= REPLAY_CONV_ERR_MAX
            and got["insert_err"] == 0.0)


def _check_replay(run: Run, cfg: Any, serving: Any, params: Any,
                  held_out: np.ndarray) -> bool:
    return _replay_within(replay_readings(run.traffic, cfg, serving, params,
                                          held_out))


def _check_replies(run: Run, cfg: Any, params: Any,
                   records: List[Dict[str, Any]], callers: Any,
                   reqs: List[loadgen.Request]) -> bool:
    """Every reply echoes its prompt at the asked length; a seeded sample,
    at least one of every prompt length, is re-scored by the reference,
    token by token."""
    import jax.numpy as jnp

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
    n_check = min(int(run.traffic["check_replies"]), len(done))
    order = rng.permutation(len(done))
    seen, sample = set(), []
    for i in order:  # one of each prompt length first, then whoever comes
        if done[int(i)]["prompt_len"] not in seen:
            seen.add(done[int(i)]["prompt_len"])
            sample.append(int(i))
    sample += [int(i) for i in order if int(i) not in sample]
    most_out = int(run.traffic["output_tokens"]["max"])
    worst, hits, misses, total = 0.0, 0, 0, 0
    for i in sample[:n_check]:
        rec = done[i]
        toks = rec["tokens"]
        positions = np.arange(rec["prompt_len"] - 1, len(toks) - 1)
        asked = np.full((most_out,), positions[-1])
        asked[:len(positions)] = positions
        logp = np.asarray(reference.log_probs(
            params, jnp.asarray(_padded(toks, cfg.max_seq)),
            jnp.asarray(asked), run.config))[:len(positions)]
        gap = logp.max(-1) - logp[np.arange(len(positions)), toks[positions + 1]]
        worst = max(worst, float(gap.max()))
        hits += int((gap == 0).sum())
        misses += int((gap > GREEDY_MARGIN_NATS).sum())
        total += len(positions)
    if total:
        say(f"  reference: {min(n_check, len(sample))} replies (prompts of "
            f"{sorted(seen)}), {total} generated tokens: {hits / total:.3f} "
            f"are the reference's argmax, {misses} lie over "
            f"{GREEDY_MARGIN_NATS} nats under the best (at most "
            f"{MARGIN_MISSES:.0%}), the worst {worst:.4f} (at most "
            f"{GREEDY_WORST_NATS})")
        ok = (ok and misses <= MARGIN_MISSES * total
              and worst <= GREEDY_WORST_NATS)
    return ok and bool(done)


class Session:
    """A server with its weights, checked and warmed, and its callers.
    ``rehearsal/knee_sweep.py`` opens one and measures several windows;
    :func:`run` measures one."""

    def __init__(self, run: Run):
        import jax

        from distriflow_tpu import InferenceClient, InferenceServer, ServingConfig
        from distriflow_tpu.obs.telemetry import Telemetry
        from distriflow_tpu.obs.tracing import Tracer

        try:
            from distriflow_tpu.models.hybrid_ssm import init_params
        except ImportError:
            raise SystemExit(
                "this checkout has no distriflow_tpu/models/hybrid_ssm.py: it "
                "cannot run a granitemoehybrid configuration") from None

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
        state = flops_granite_hybrid.state_bytes_per_slot(c)
        say(f"  model: {n_params / 1e6:.1f} M parameters "
            f"({flops_granite_hybrid.parameters(c)['total'] / 1e6:.1f} M by the "
            f"arithmetic), {self.serving.max_slots} slots of "
            f"{state / 1e6:.1f} MB of state = "
            f"{self.serving.max_slots * state / 1e9:.3f} GB, pool {pool} pages "
            f"of {self.serving.page_size} = "
            f"{pool * self.serving.page_size * flops_granite_hybrid.cache_bytes_per_token(c) / 1e9:.3f} GB")

        self.telemetry = Telemetry(enabled=run.trace)
        if run.trace:
            self.telemetry.tracer = Tracer(enabled=True, max_spans=1_000_000)
        with run.phase("corpus"):
            self.corpus = corpus_lib.generate_corpus(t["corpus_tokens"], seed=0)
        self.server = InferenceServer(self.cfg, self.params, port=0,
                                      serving=self.serving,
                                      telemetry=self.telemetry)
        self.log: List[str] = []
        self.server.logger.log = lambda *a: self.log.append(
            " ".join(str(x) for x in a))
        self.server.setup()
        self.callers: Optional[base._Callers] = None
        try:
            # before the state and the page pool exist (they are allocated at
            # the first admission): the reference has the memory
            with run.phase("score() against the reference"):
                with InferenceClient(self.server.address, timeout=1100.0,
                                     telemetry=self.telemetry,
                                     report_interval_s=0.0) as client:
                    self.correct = _check_score(run, self.cfg, client,
                                                self.params, self.corpus)
            with run.phase("float32 pieces on the program's own inputs"):
                self.correct = _check_own_inputs(
                    run, self.cfg, self.params, self.corpus) and self.correct
            with run.phase("warm-up"):
                base._warm_up(run, self.server.address, self.telemetry,
                              self.corpus)
            with run.phase("callers"):
                self.callers = base._Callers(self.server.address, t["clients"],
                                             self.telemetry, self.corpus)
        except BaseException:
            self.close()
            raise
        self.n_warm_log = len(self.log)
        run.compile_setup = run.meter.since(setup_mark)

    def requests(self, seed: int, seconds: float,
                 rate: Optional[float] = None) -> List[loadgen.Request]:
        traffic = dict(self.run.traffic)
        if rate is not None:
            traffic["rate_per_s"] = rate
        # the tail of the corpus is the checks' (state prompt, replay)
        tail = max(int(traffic["state_prompt_len"]) + 1,
                   2 * int(traffic["replay_group"])
                   * int(traffic["replay_prompt_len"]))
        return loadgen.requests(traffic, seconds, seed,
                                base._warm_tokens(traffic),
                                len(self.corpus) - tail)

    def open_window(self, reqs: List[loadgen.Request], lead: float = 0.0,
                    at_start: Any = None) -> float:
        """Send ``reqs`` on their schedule and wait for every reply; returns
        the window's start. With ``lead`` the window is a slice of the
        traffic's cycle repeated, not a cold start and a drain: the cycle's
        last ``lead`` seconds are sent before the window and its first
        ``lead`` seconds again after it (copies of the requests due then,
        numbered from ``len(reqs)`` and from ``2 len(reqs)``, not measured),
        so that the load a measured request meets does not depend on where
        in the cycle ``--seed`` put the window's edge. ``at_start()`` runs
        when the window opens."""
        n, period = len(reqs), self.run.seconds
        plan = [(r.due_s - period, r._replace(index=n + r.index))
                for r in reqs if r.due_s >= period - lead]
        plan += [(r.due_s, r) for r in reqs]
        plan += [(r.due_s + period, r._replace(index=2 * n + r.index))
                 for r in reqs if r.due_s < lead]
        t0 = time.monotonic() + lead
        for offset, req in plan:
            delay = t0 + offset - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if req is reqs[0] and at_start is not None:
                at_start()
            self.callers.todo.put((req, t0 + offset))
        self.callers.todo.join()
        return t0

    def close(self) -> None:
        if self.callers is not None:
            self.callers.close()
        if self.server is not None:
            self.server.stop()
        self.server = None  # frees the state and the page pool
        gc.collect()


def run(run: Run) -> None:
    t = run.traffic
    session = Session(run)
    callers = session.callers
    reqs = session.requests(run.seed, run.seconds)
    say(f"  traffic: open loop, {loadgen.describe(reqs)}")
    opened: Dict[str, Any] = {}

    def at_start() -> None:  # the lead-in is set-up; nothing compiles in it
        run.end_to_end["setup_s"] = time.monotonic() - run.t_process
        opened["counters"] = session.telemetry.snapshot()["counters"]
        opened["profiler"] = base._traced(run) if run.trace else None

    try:
        window_mark = run.meter.mark()
        t0 = session.open_window(reqs, float(t["lead_seconds"]), at_start)
        t1 = t0 + run.seconds
        run.window = (t0, t1)
        run.compile_window = run.meter.since(window_mark)
        counters0, profiler = opened["counters"], opened["profiler"]
        # the traced seconds end before anything else reaches the chip
        while (profiler is not None and profiler.is_alive()
               and run.trace_window == (0.0, 0.0)):
            time.sleep(0.05)
        counters1 = session.telemetry.snapshot()["counters"]
        run.memory_peak_bytes = (run.devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0)
    finally:
        session.close()
    engine_errors = [line for line in session.log if "engine error" in line]
    admits = [line.split(" took")[0] for line in session.log[session.n_warm_log:]
              if line.startswith("admit[")]
    shapes = sorted(set(admits))
    say(f"  admit shapes since warm-up ({len(admits)} groups): "
        + " ".join(f"{s}x{admits.count(s)}" for s in shapes))
    run.spans = session.telemetry.tracer.finished() if run.trace else []

    # every request of reqs was due inside the window; the lead's are not
    measured = [r for r in callers.records if r["index"] < len(reqs)]
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
    say(f"  window {t1 - t0:.3f}s: {len(measured)} requests, {run.failed} "
        f"failed, {out_tokens} output tokens; programs compiled or loaded "
        f"in the window: {run.compile_window['programs']}")
    if ttft and tpot:
        for name, values in (("serve_ttft_p90_ms", ttft),
                             ("serve_tpot_p90_ms", tpot)):
            tail = stats.percentile(values + [base.NEVER_MS] * run.failed, 90.0)
            run.end_to_end[name] = tail if tail < base.NEVER_MS / 2 else None
        run.end_to_end["serve_out_tok_s"] = out_tokens / (t1 - t0)
    run.shapes = {"admit_shapes": shapes,
                  "max_slots": session.serving.max_slots,
                  "decode_chunk": session.serving.decode_chunk,
                  "page_size": session.serving.page_size,
                  "counters": {k: v - counters0.get(k, 0)
                               for k, v in counters1.items()}}
    with run.phase("replies against the reference (after the window)"):
        replies_ok = _check_replies(run, session.cfg, session.params, measured,
                                    callers, reqs)
    with run.phase("served-path replay (after the window)"):
        replay_ok = _check_replay(run, session.cfg, session.serving,
                                  session.params, session.corpus)
    if profiler is not None:
        # stop_trace() has been writing the trace out on the host since the
        # traced seconds ended, beside the checks above
        profiler.join(timeout=120.0)
    engine_path = all(r.get("path") == "slots" for r in measured if r["ok"])
    no_hits = all(not r.get("prefix_tokens") for r in measured if r["ok"])
    say(f"  correct: score() and own inputs {session.correct}, replies "
        f"{replies_ok}, served-path replay {replay_ok}, engine errors "
        f"{len(engine_errors)}, failed requests {run.failed}, all served by "
        f"the engine {engine_path}, no prefix hit {no_hits}")
    run.correct = bool(session.correct and replies_ok and replay_ok
                       and not engine_errors and run.failed == 0
                       and engine_path and no_hits)


def precision_control(run: Run) -> Dict[str, bool]:
    """The comparisons that need no window (``score()``, the float32 pieces
    on the program's own inputs, the served-path replay) twice on one set of
    weights: the program as the configuration states it, and with ``dt``,
    decay and state, norms and router computed in bfloat16, the nearest
    precision below (``rehearsal/precision_control.py``). Limits that can
    tell the two apart give ``{"stated": True, "lowered": False}``."""
    import jax
    import jax.numpy as jnp

    from distriflow_tpu import InferenceClient, InferenceServer, ServingConfig
    from distriflow_tpu.models.hybrid_ssm import init_params

    stated = program_config(run.config)
    params = init_params(stated, harness.prng_key(run.seed))
    jax.block_until_ready(params)
    corpus = corpus_lib.generate_corpus(run.traffic["corpus_tokens"], seed=0)
    serving = ServingConfig(**run.config["serving"])
    out = {}
    for name, cfg in (("stated", stated), ("lowered", dataclasses.replace(
            stated, norm_router_dtype=jnp.bfloat16, ssm_dtype=jnp.bfloat16))):
        say(f"{name}: norms and router in "
            f"{jnp.dtype(cfg.norm_router_dtype).name}, dt, decay and state in "
            f"{jnp.dtype(cfg.ssm_dtype).name}")
        server = InferenceServer(cfg, params, port=0, serving=serving)
        server.setup()
        try:
            with InferenceClient(server.address, timeout=1100.0) as client:
                score = _check_score(run, cfg, client, params, corpus)
        finally:
            server.stop()
        own = _check_own_inputs(run, cfg, params, corpus)
        replay = _check_replay(run, cfg, serving, params, corpus)
        out[name] = score and own and replay
        say(f"{name}: score() {score}, own inputs {own}, replay {replay}: "
            f"correct {out[name]}")
    return out
