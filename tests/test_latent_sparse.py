"""``models/latent_sparse.py`` against the plain reference
(``benchmark/lib/reference_glm_dsa.py``) at toy widths, float32, seeded
weights: prefill, ``extend`` and decode through paged pools, the shared
selection, the expert share and the router's two uses of its scores.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distriflow_tpu.models.latent_sparse as ls
from benchmark.lib import reference_glm_dsa as ref
from distriflow_tpu.models.generate import (
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    _find_cache_leaf,
    _split_pools,
    decode_family,
    generate,
    paged_cache,
    sequence_logprob,
    set_page_tables,
)
from distriflow_tpu.models.latent_sparse import (
    ExpertShare,
    LatentSparseConfig,
    LatentSparseLM,
    SwiGLU,
    init_params,
    route,
)

TOPK = 16
CFG = LatentSparseConfig(
    vocab_size=97, d_model=64, n_layers=3, n_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=8, index_head_dim=16, index_topk=TOPK,
    indexer_types=("full", "shared", "full"),
    mlp_layer_types=("dense", "sparse", "sparse"), d_ff=128, moe_d_ff=32,
    n_routed_experts=8, n_experts_per_tok=2, routed_scaling_factor=2.5,
    experts_held=(0, 2), max_seq=64, index_rope_dim=8, rope_base=10000.0,
    dtype=jnp.float32, param_dtype=jnp.float32, query_block=8)
MODEL = dict(indexer_types=CFG.indexer_types, index_topk=TOPK,
             index_rope_dim=8, rope_theta=10000.0, num_experts_per_tok=2,
             routed_scaling_factor=2.5, experts_held=CFG.experts_held)
TOL = 2e-4  # float32 both sides; the selection agrees, the sums reorder


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(1))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 97, (n,)).astype(np.int32)


def _want(params, tokens, positions):
    return np.asarray(ref.log_probs(params, jnp.asarray(tokens),
                                    jnp.asarray(positions), MODEL))


@pytest.mark.parametrize("n", [12, 40], ids=["under_topk", "past_topk"])
def test_prefill_matches_reference(params, n):
    toks = _tokens(n)
    logits, _ = LatentSparseLM(CFG).apply(params, toks[None],
                                          mutable=["cache"])
    got = np.asarray(jax.nn.log_softmax(logits[0], -1))
    assert np.abs(got - _want(params, toks, np.arange(n))).max() < TOL


@pytest.mark.parametrize("room", [3, 39], ids=["overflows", "holds"])
def test_reference_expert_pass_does_not_depend_on_its_room(params, room,
                                                           monkeypatch):
    """An expert runs on the tokens that chose it, gathered into a pass of
    ``_room`` tokens, or on the whole sequence when more chose it."""
    toks = _tokens(40, seed=7)
    want = _want(params, toks, np.arange(40))
    monkeypatch.setattr(ref, "_room", lambda n: room)
    assert np.abs(_want(params, toks, np.arange(40)) - want).max() < 1e-5


@pytest.mark.parametrize("first,more", [(8, 4), (12, 9), (24, 1), (32, 17)],
                         ids=["under_topk", "across_topk", "one_token",
                              "past_topk_odd_block"])
def test_prefill_then_extend_matches_reference(params, first, more):
    toks = _tokens(first + more, seed=first)
    prefill, extend = _build_prefill(CFG)
    _, cache = prefill(params, toks[None, :first])
    last, cache = extend(params, cache, toks[None, first:])
    got = np.asarray(jax.nn.log_softmax(last[0], -1))
    want = _want(params, toks, [first + more - 1])[0]
    assert np.abs(got - want).max() < TOL
    assert int(_find_cache_leaf(cache, "cache_index")) == first + more


def test_score_and_solo_generate_run_the_family(params):
    toks = _tokens(30, seed=3)
    got = float(sequence_logprob(CFG, params, toks[None], from_pos=5)[0])
    logp = _want(params, toks, np.arange(29))
    want = logp[np.arange(4, 29), toks[5:]].sum()
    assert abs(got - want) < 25 * TOL
    out = np.asarray(generate(CFG, params, toks[None, :20], 6))[0]
    logp = _want(params, out, np.arange(19, 25))
    assert np.all(logp.max(-1) - logp[np.arange(6), out[20:]] < TOL)


def test_paged_decode_matches_reference(params):
    """Three slots: a short row, a retired slot, a long row whose pages lie
    scattered in the pool; then a row admitted on the long row's first two
    pages (prefix hit: gather, ``extend``, scatter). Pages of 8, selection
    of 16: every selection past 16 tokens spans page boundaries."""
    ps, n_pages, slots = 8, 20, 4
    family = decode_family(CFG)
    assert family.pool_leaves == ("cached_latent", "cached_index_k")
    cache = paged_cache(CFG, params, slots, ps, n_pages)
    pools, rest = _split_pools(cache, family.pool_leaves)
    assert {k.key for path, _ in jax.tree_util.tree_flatten_with_path(pools)[0]
            for k in path[-1:]} == {"cached_latent", "cached_index_k"}
    assert _find_cache_leaf(cache, "cached_latent").shape == (n_pages, ps, 128)
    assert "cached_index_k" not in cache["layers_1"]["attn"]  # shared layer
    prefill, extend = _build_prefill(CFG)
    insert, gather_rows = _build_paged_fns(CFG, ps)
    _, pick_rows, decode = _build_slot_fns(CFG, 4, False)
    prompts = {0: _tokens(10, 1), 2: _tokens(30, 2)}
    prompts[3] = np.concatenate([prompts[2][:16], _tokens(5, 3)])
    tables = np.full((slots, CFG.max_seq // ps + 1), n_pages, np.int32)
    tables[0, :3] = [3, 7, 1]
    tables[2, :5] = [0, 2, 9, 5, 6]
    tables[3, :4] = [0, 2, 11, 12]
    tok = np.zeros((slots,), np.int32)
    for slot in (0, 2):
        logits, row = prefill(params, prompts[slot][None])
        old = _find_cache_leaf(cache, "cached_latent")
        cache = insert(cache, row, np.array([slot], np.int32),
                       np.int32(len(prompts[slot])), np.int32(0), tables)
        assert old.is_deleted()  # the pools were donated
        tok[slot] = int(jnp.argmax(logits[0]))
    row = gather_rows(cache, tables[[3]], np.int32(16))
    logits, row = extend(params, row, prompts[3][None, 16:])
    cache = insert(cache, row, np.array([3], np.int32), np.int32(21),
                   np.int32(16), tables)
    tok[3] = int(jnp.argmax(logits[0]))
    done = np.array([False, True, False, False])
    out = {s: [int(tok[s])] for s in prompts}
    zeros = np.zeros((slots,), np.int32)
    for _ in range(2):
        cache, tok, done, toks = decode(
            params, cache, tok, done, np.zeros((slots,), np.float32), zeros,
            np.ones((slots,), np.float32), zeros, zeros - 1)
        for s in prompts:
            out[s] += [int(t) for t in np.asarray(toks)[s]]
    for s, prompt in prompts.items():
        seq = np.concatenate([prompt, out[s]]).astype(np.int32)
        at = np.arange(len(prompt) - 1, len(seq) - 1)
        logp = _want(params, seq, at)
        gap = logp.max(-1) - logp[np.arange(len(at)), seq[at + 1]]
        assert gap.max() < TOL, (s, gap)
    # the engine's counters: a retired slot routes nowhere, the others do
    stats = np.asarray(cache["layers_1"]["mlp"]["expert_stats"])
    assert 0 < stats[1] <= 8 * 3 * 2 and 0 < stats[0] <= 8 * 2


# -- the selector and the attention run over live rows only -----------------

PS, N_PAGES = 8, 48
FULL = [i for i, kind in enumerate(CFG.indexer_types) if kind == "full"]


def _all_rows(fn):
    """``fn`` as it is traced with ``ROWS`` past any batch: the form that
    runs every row at once, the oracle of the grouped one."""
    jitted = jax.jit(lambda *args: fn(*args))  # a trace of its own

    def run(*args):
        old, ls.ROWS = ls.ROWS, 1 << 20
        try:
            return jitted(*args)
        finally:
            ls.ROWS = old

    return run


def _one_step(p, cache, tok):
    """A decode step with what its ``full`` layers selected."""
    logits, state = LatentSparseLM(CFG).apply(
        {**p, "cache": cache}, tok[:, None],
        mutable=["cache", "intermediates"])
    sown = state["intermediates"]
    picked = {i: sown[f"layers_{i}"]["attn"]["selected"][0] for i in FULL}
    # the program's own trip count x ROWS; all rows where it takes no loop
    run = sown["layers_0"]["attn"].get("rows_run", (tok.shape[0],))[0]
    return logits[:, 0], state["cache"], picked, run


def _resident(params, slots):
    """``slots`` rows of 10..38 tokens resident in a paged pool, their pages
    interleaved: ``(cache, tables, next tokens)``."""
    cache = paged_cache(CFG, params, slots, PS, N_PAGES)
    prefill, _ = _build_prefill(CFG)
    insert, _ = _build_paged_fns(CFG, PS)
    tables = np.full((slots, CFG.max_seq // PS + 1), N_PAGES, np.int32)
    tok = np.zeros((slots,), np.int32)
    for slot in range(slots):
        n = 10 + 4 * slot
        # a row's pages lie ``slots`` apart: no two rows share one, and
        # room for a chunk of steps past the prompt
        tables[slot, :-(-(n + 8) // PS)] = slot + slots * np.arange(
            -(-(n + 8) // PS))
        logits, row = prefill(params, _tokens(n, seed=20 + slot)[None])
        cache = insert(cache, row, np.array([slot], np.int32), np.int32(n),
                       np.int32(0), tables)
        tok[slot] = int(jnp.argmax(logits[0]))
    return cache, tables, tok


@pytest.fixture(scope="module")
def resident(params):
    return {slots: _resident(params, slots) for slots in (6, 8)}


@pytest.fixture(scope="module")
def step_forms():
    return jax.jit(_one_step), _all_rows(_one_step)


def _retire(cache, tables, live):
    """A copy of ``cache`` in which only the slots ``live`` keep their
    table rows."""
    tables = tables.copy()
    tables[[s for s in range(len(tables)) if s not in live]] = N_PAGES
    return set_page_tables(jax.tree.map(jnp.copy, cache), tables), tables


LIVE_SETS = [(8, (5,)), (8, (0, 1, 2)), (8, (1, 4, 6)), (8, (0, 2, 3, 5, 7)),
             (8, tuple(range(8))), (6, (0, 1, 2, 4, 5)), (6, ())]


@pytest.mark.parametrize("slots,live", LIVE_SETS,
                         ids=[f"{s}slots_live_{'_'.join(map(str, l)) or 'none'}"
                              for s, l in LIVE_SETS])
def test_a_step_over_live_rows_is_the_step_over_all_rows(
        params, resident, step_forms, slots, live):
    """More slots than ``ROWS``: the served step runs the live rows in
    groups of ``ROWS`` and gives each of them the set and the output that
    the step over all rows gives it; a retired row writes no page."""
    cache, tables, tok = resident[slots]
    grouped, at_once = step_forms
    live = list(live)
    mine, tables = _retire(cache, tables, live)
    before = {name: np.asarray(_find_cache_leaf(mine, name))
              for name in ("cached_latent", "cached_index_k")}
    theirs = jax.tree.map(jnp.copy, mine)
    got, got_cache, got_sets, got_run = grouped(params, mine, tok)
    want, want_cache, want_sets, want_run = at_once(params, theirs, tok)
    for layer in FULL:
        (idx, valid), (want_idx, want_valid) = (
            [np.asarray(v)[live] for v in sets[layer]]
            for sets in (got_sets, want_sets))
        assert np.array_equal(valid, want_valid)
        assert np.array_equal(idx[valid], want_idx[valid])
        assert valid.sum() >= len(live) * 10  # every row chose something
    assert np.abs(np.asarray(got)[live] - np.asarray(want)[live]).max(
        initial=0) < TOL
    written = np.unique(tables[tables < N_PAGES])
    for name, old in before.items():
        for layer in range(CFG.n_layers):
            if name not in got_cache[f"layers_{layer}"]["attn"]:
                continue  # a shared layer keeps no selector keys
            new = np.asarray(got_cache[f"layers_{layer}"]["attn"][name])
            ref = np.asarray(want_cache[f"layers_{layer}"]["attn"][name])
            assert np.abs(new - ref)[written].max(initial=0) < TOL
            if layer == 0:  # `before` is the first layer's leaf
                rest = np.setdiff1d(np.arange(N_PAGES), written)
                assert np.array_equal(new[rest], old[rest])
    # the program's own trip count is what the engine's arithmetic says
    n_run = -(-len(live) // ls.ROWS) * ls.ROWS
    assert (int(got_run), int(want_run)) == (n_run, slots)
    assert CFG.decode_work([12] * len(live), 3, slots)["rows_run"] == 3 * n_run


@pytest.mark.parametrize("live", [(1, 4, 6), (0, 1, 2, 3, 4)],
                         ids=["scattered_3", "contiguous_5"])
def test_a_chunk_of_steps_over_live_rows(params, resident, live):
    """The engine's decode program, four steps: the live rows' tokens are
    those of the all-rows form and of the reference."""
    cache, tables, tok = resident[8]
    live = list(live)
    _, _, decode = _build_slot_fns(CFG, 4, False)
    mine, _ = _retire(cache, tables, live)
    theirs = jax.tree.map(jnp.copy, mine)
    done = np.array([s not in live for s in range(8)])
    zeros = np.zeros((8,), np.int32)
    args = (tok, done, np.zeros((8,), np.float32), zeros,
            np.ones((8,), np.float32), zeros, zeros - 1)
    _, _, _, got = decode(params, mine, *args)
    _, _, _, want = _all_rows(decode.body)(params, theirs, *args)
    assert np.array_equal(np.asarray(got)[live], np.asarray(want)[live])
    s = live[1]
    seq = np.concatenate([_tokens(10 + 4 * s, seed=20 + s), [tok[s]],
                          np.asarray(got)[s]]).astype(np.int32)
    at = np.arange(10 + 4 * s, len(seq) - 1)
    logp = _want(params, seq, at)
    assert (logp.max(-1) - logp[np.arange(len(at)), seq[at + 1]]).max() < TOL


def test_live_first_is_stable_and_counts():
    live = jnp.asarray([False, True, True, False, False, True, False, True])
    order, n_live = ls.live_first(live)
    assert int(n_live) == 4
    assert np.asarray(order).tolist() == [1, 2, 5, 7, 0, 3, 4, 6]
    order, n_live = ls.live_first(jnp.zeros((5,), bool))
    assert int(n_live) == 0 and np.asarray(order).tolist() == [0, 1, 2, 3, 4]


def test_shared_layer_uses_the_preceding_full_layers_set(params, monkeypatch):
    calls = []
    real = ls.select_tokens

    def spy(*args):
        calls.append(real(*args))
        return calls[-1]

    monkeypatch.setattr(ls, "select_tokens", spy)
    seen = []
    real_call = ls.LatentSparseAttention.__call__

    def call(self, x, selection, rows):
        seen.append((self.layer, selection))
        return real_call(self, x, selection, rows)

    monkeypatch.setattr(ls.LatentSparseAttention, "__call__", call)
    with jax.disable_jit():
        LatentSparseLM(dataclasses.replace(CFG, query_block=64)).apply(
            params, _tokens(40)[None], mutable=["cache"])
    seen = dict(seen)
    assert len(calls) == 2  # layers 0 and 2 select, layer 1 does not
    assert seen[0] is None
    assert seen[1][0] is calls[0][0] and seen[1][1] is calls[0][1]
    assert "index_q_proj" not in params["params"]["layers_1"]["attn"]
    assert "index_q_proj" in params["params"]["layers_2"]["attn"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    cfg = dataclasses.replace(CFG, n_routed_experts=32, n_experts_per_tok=8,
                              experts_held=(0, 32))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 64), jnp.float32)
    whole = ExpertShare(cfg)
    variables = whole.init(jax.random.PRNGKey(1), x, None)
    p = variables["params"]
    uncut = whole.apply({"params": p}, x, None, mutable=["cache"])[0]
    shared = SwiGLU(cfg, cfg.moe_d_ff).apply(
        {"params": p["shared_expert"]}, x)
    routed = jnp.zeros_like(uncut)
    for share in range(16):
        held = dataclasses.replace(cfg, experts_held=(2 * share, 2))
        mine = {k: v for k, v in p.items() if not k.startswith("expert_")}
        for e in range(2):  # the share's experts under its own numbering
            for part in ("gate", "up", "down"):
                mine[f"expert_{e}_{part}"] = p[
                    f"expert_{2 * share + e}_{part}"]
        out = ExpertShare(held).apply({"params": mine}, x, None,
                                      mutable=["cache"])[0]
        routed = routed + (out - shared)
    assert float(jnp.abs(shared + routed - uncut).max()) < 1e-5
    assert float(jnp.abs(routed).max()) > 0.1  # the routed part is not nothing


def test_router_chooses_by_score_plus_bias_and_gates_by_score():
    scores = jnp.array([[0.9, 0.8, 0.3, 0.2]], jnp.float32)
    bias = jnp.array([0.0, -0.7, 0.0, 0.65], jnp.float32)
    gates = np.asarray(route(scores, bias, 2, 2.5))[0]
    # s + b = [0.9, 0.1, 0.3, 0.85]: experts 0 and 3, not the two best scores
    assert gates[1] == 0 and gates[2] == 0
    np.testing.assert_allclose(gates[[0, 3]],
                               2.5 * np.array([0.9, 0.2]) / 1.1, rtol=1e-6)
    plain = np.asarray(route(scores, jnp.zeros(4), 2, 2.5))[0]
    np.testing.assert_allclose(plain[[0, 1]],
                               2.5 * np.array([0.9, 0.8]) / 1.7, rtol=1e-6)


def _top_k_sets(score, visible, k):
    """``lax.top_k``'s set per row, as sorted position lists."""
    top, idx = jax.lax.top_k(jnp.where(visible, score, -jnp.inf), k)
    return [sorted(np.asarray(i)[np.asarray(t) > -np.inf].tolist())
            for t, i in zip(top, idx)]


@pytest.mark.parametrize("case,width", [
    ("normal", 33792), ("ties_at_the_threshold", 33792),
    ("fewer_than_k_visible", 33792), ("ragged_last_chunk", 33700),
    ("all_negative", 33792), ("chosen_in_few_chunks", 33792)])
def test_top_positions_is_top_ks_set_at_the_served_width(case, width):
    """At the cell's width (33,792 cached positions, 264 chunks) and k
    (2,048), not only the toy's: the same set as ``lax.top_k``, equal
    scores to the earlier position, ascending, ``valid`` exactly the
    chosen."""
    k = 2048
    rng = np.random.default_rng(7)
    score = rng.standard_normal((5, width)).astype(np.float32)
    seen = np.array([width, 20000, 4097, 2049, 2048])  # visible positions
    if case == "ties_at_the_threshold":
        score = np.round(score * 4) / 4  # ~25 values: thousands of equals
        score[0] = 0.0  # one value throughout: the first k positions
    elif case == "fewer_than_k_visible":
        seen = np.array([2047, 1000, 129, 1, 128])
    elif case == "all_negative":
        score = -np.abs(score) - 1.0
        score[1] = -0.0
    elif case == "chosen_in_few_chunks":
        score[:, 5000:7300] += 100.0  # the set is 18 whole chunks' worth
        score[4, :] = np.arange(width)  # the last k positions
    visible = np.arange(width)[None, :] < seen[:, None]
    idx, valid = jax.jit(lambda s, v: ls.top_positions(s, v, k))(
        jnp.asarray(score), jnp.asarray(visible))
    idx, valid = np.asarray(idx), np.asarray(valid)
    want = _top_k_sets(jnp.asarray(score), jnp.asarray(visible), k)
    for row in range(len(seen)):
        got = idx[row][valid[row]]
        assert got.tolist() == want[row], (case, row)  # ascending, no repeat
        assert valid[row].sum() == min(k, seen[row])
        assert not valid[row][valid[row].sum():].any()  # the chosen come first


def test_top_positions_keeps_leading_axes():
    rng = np.random.default_rng(3)
    score = jnp.asarray(rng.standard_normal((2, 3, 300)).astype(np.float32))
    visible = jnp.arange(300)[None, None, :] <= jnp.asarray(
        [[10, 150, 299], [299, 40, 200]])[..., None]
    idx, valid = ls.top_positions(score, visible, 16)
    want = _top_k_sets(score.reshape(6, 300), visible.reshape(6, 300), 16)
    for row in range(6):
        got = np.asarray(idx).reshape(6, 16)[row][np.asarray(valid).reshape(6, 16)[row]]
        assert got.tolist() == want[row]


@pytest.mark.parametrize("slots,rows_run", [(2, 2), (6, 4)],
                         ids=["all_rows", "live_groups"])
def test_engine_counts_the_familys_work_through_its_declared_leaf(
        params, slots, rows_run):
    """With telemetry on, a decode dispatch's spans and the counters carry
    what the layers counted in the family's ``work_leaf``; the server names
    no leaf of its own. One row is live: with two slots the step runs both,
    with six one group of ``ROWS``."""
    import inspect

    from distriflow_tpu import InferenceClient, InferenceServer, ServingConfig
    from distriflow_tpu.obs.telemetry import Telemetry
    from distriflow_tpu.obs.tracing import Tracer
    from distriflow_tpu.server import inference_server

    assert decode_family(CFG).work_leaf == "expert_stats"
    assert "expert_stats" not in inspect.getsource(inference_server)
    tel = Telemetry(enabled=True)
    tel.tracer = Tracer(enabled=True, max_spans=10_000)
    serving = ServingConfig(max_slots=slots, decode_chunk=4,
                            kv_layout="paged", page_size=8,
                            page_pool_pages=16)
    server = InferenceServer(CFG, params, port=0, serving=serving,
                             telemetry=tel)
    server.setup()
    try:
        with InferenceClient(server.address, timeout=300.0, telemetry=tel,
                             report_interval_s=0.0) as client:
            client.generate(_tokens(20, seed=5)[None], 9)
    finally:
        server.stop()
    spans = [s for s in tel.tracer.finished() if s["name"] == "decode_iter"]
    assert len(spans) == 2  # 8 tokens after the first, 4 a dispatch
    for attrs in spans:
        assert attrs["ctx_tokens"] >= 20 and attrs["sel_tokens"] == TOPK
        # one row, 4 steps, 2 sparse layers, 2 held experts, top-2 of 8
        assert 0 <= attrs["local_assignments"] <= 4 * 2 * 2
        assert attrs["experts_hit"] <= attrs["local_assignments"]
        assert attrs["rows_run"] == 4 * rows_run  # steps x rows a step
    counters = tel.snapshot()["counters"]
    assert counters["serving_sparse_rows_run_total"] == 2 * 4 * rows_run
    assert counters["serving_sparse_rows_live_total"] == 2 * 4
    held = {k: v for k, v in counters.items()
            if k.startswith("serving_expert_assignments_total")}
    assert sum(held.values()) == 2 * 4 * 2 * 2  # dispatches x steps x layers x k
    assert sum(s["local_assignments"] for s in spans) == next(
        v for k, v in held.items() if "yes" in k)
