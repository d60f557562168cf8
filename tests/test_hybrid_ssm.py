"""``models/hybrid_ssm.py`` against the plain reference
(``benchmark/lib/reference_granite_hybrid.py``) at toy widths, float32,
seeded weights: prefill, chunked prefill and ``extend``, decode through the
per-row state and the paged pools of ``InferenceServer``, rows of unequal
length, a slot reused, planted faults, the expert share, the engine's guards
for a family that cannot reuse a prefix. (The reference itself is held to the
published ``transformers`` implementation in ``test_hybrid_ssm_reference.py``.)
"""

import dataclasses
import importlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distriflow_tpu.models.hybrid_ssm as hs
import distriflow_tpu.models.latent_sparse as ls
from benchmark.lib import reference_granite_hybrid as ref
from distriflow_tpu.models.hybrid_ssm import (
    HybridSSMConfig,
    HybridSSMLM,
    init_params,
)
from distriflow_tpu.models.latent_sparse import ROWS, ExpertShare, SwiGLU

gen = importlib.import_module("distriflow_tpu.models.generate")

VOCAB = 211
CFG = HybridSSMConfig(
    vocab_size=VOCAB, d_model=64, layer_types=("mamba", "attention", "mamba"),
    n_heads=4, n_kv_heads=2, attention_multiplier=1 / 32, mamba_n_heads=8,
    mamba_d_head=16, mamba_d_state=16, moe_d_ff=32, shared_d_ff=48,
    n_routed_experts=8, n_experts_per_tok=3, experts_held=(0, 4), max_seq=160,
    mamba_chunk_size=16, embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=4.0, dtype=jnp.float32, param_dtype=jnp.float32,
    embed_init_std=0.005, query_block=32)
MODEL = dict(layer_types=CFG.layer_types, mamba_n_heads=8, mamba_d_state=16,
             num_experts_per_tok=3, experts_held=CFG.experts_held,
             residual_multiplier=0.22, embedding_multiplier=12.0,
             attention_multiplier=1 / 32, logits_scaling=4.0)
# float32 both sides; the routing agrees, the sums reorder. The tied head of
# seeded weights gives every token's own embedding the logit 12 d s^2 / (4
# rms(x)); the embedding is drawn narrow (s = 0.005) so that it stays within
# the spread the layers give (2 s = 0.01 nats) and the layers decide a reply:
# drawn wider, every reply repeats one token whatever the state holds
TOL = 4e-6  # one float32 step of a log-probability of 5 is 5e-7
GAP = 1e-4  # a generated token under the reference's best, nats


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the conv's drawn wide (uniform in +-1/2, bias too):
    the seeded init keeps the conv inside SiLU's linear range, where the
    state's part of a mixer's output is a hundredth of the skip path's and a
    wrong state would hide from the logits."""
    tree = init_params(CFG, jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    for name, layer in tree["params"].items():
        if "conv_weight" in layer.get("mixer", {}):
            for leaf in ("conv_weight", "conv_bias"):
                key, sub = jax.random.split(key)
                layer["mixer"][leaf] = jax.random.uniform(
                    sub, layer["mixer"][leaf].shape, jnp.float32, -0.5, 0.5)
    return tree


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (n,)).astype(np.int32)


def _want(params, tokens, positions, model=MODEL):
    return np.asarray(ref.log_probs(params, jnp.asarray(tokens),
                                    jnp.asarray(positions), model))


def _prefill_err(cfg, params, n=70, model=MODEL):
    toks = _tokens(n)
    logits, _ = HybridSSMLM(cfg).apply(params, toks[None], mutable=["cache"])
    got = np.asarray(jax.nn.log_softmax(logits[0], -1))
    return float(np.abs(got - _want(params, toks, np.arange(n), model)).max())


def _gaps(params, out, plen):
    """How far under the reference's best each generated token of ``out``
    (prompt then reply) lies, in nats."""
    positions = np.arange(plen - 1, len(out) - 1)
    logp = _want(params, out, positions)
    return logp.max(-1) - logp[np.arange(len(positions)), out[plen:]]


def _serving(**over):
    from distriflow_tpu import ServingConfig

    return ServingConfig(**{**dict(
        max_slots=3, decode_chunk=4, kv_layout="paged", page_size=16,
        page_pool_pages=30), **over})


class _Served:
    """An ``InferenceServer`` of the family and one client."""

    def __init__(self, params, cfg=CFG, telemetry=None, **serving):
        from distriflow_tpu import InferenceClient, InferenceServer

        self.server = InferenceServer(cfg, params, port=0,
                                      serving=_serving(**serving),
                                      telemetry=telemetry)
        self.server.setup()
        self._client = InferenceClient

    def __enter__(self):
        self.client = self.another()
        return self

    def __exit__(self, *exc):
        self.client.close()
        self.server.stop()

    def another(self):
        return self._client(self.server.address, timeout=300.0,
                            report_interval_s=0.0).setup()


# -- (i) prefill -----------------------------------------------------------------


@pytest.mark.parametrize("n", [9, 16, 70], ids=["under_a_chunk", "one_chunk",
                                                "padded_chunks"])
def test_prefill_matches_reference(params, n):
    assert _prefill_err(CFG, params, n) < TOL


# -- (ii) prefill, then decode through state and pages ----------------------------


@pytest.mark.parametrize("serving", [
    {}, {"prefill_chunk": 16}, {"kv_layout": "slab"}],
    ids=["paged", "paged_chunked_prefill", "slab"])
def test_server_prefill_then_decode_is_one_full_forward(params, serving):
    prompt = _tokens(41, seed=3)
    with _Served(params, **serving) as s:
        out = s.client.generate(prompt[None], 25)[0]
        assert (s.client.last_serving_meta or {}).get("path") == "slots"
    assert np.array_equal(out[:41], prompt) and out.shape == (66,)
    assert _gaps(params, out, 41).max() < GAP


def test_solo_generate_score_and_beam_run_the_family(params):
    prompt = _tokens(20, seed=4)
    out = np.asarray(gen.generate(CFG, params, prompt[None], 12))[0]
    assert _gaps(params, out, 20).max() < GAP
    got = float(gen.sequence_logprob(CFG, params, out[None], from_pos=20)[0])
    logp = _want(params, out, np.arange(19, 31))
    assert got == pytest.approx(
        float(logp[np.arange(12), out[20:]].sum()), abs=1e-3)
    # beams reorder the rows' state with their pages: width 1 is greedy
    beam, _ = gen.beam_search(CFG, params, prompt[None], 12, beam_size=1)
    assert np.array_equal(np.asarray(beam)[0], out)
    wide, scores = gen.beam_search(CFG, params, prompt[None], 6, beam_size=3)
    assert float(gen.sequence_logprob(
        CFG, params, np.asarray(wide), from_pos=20)[0]) == pytest.approx(
            float(scores[0]), abs=1e-3)


# -- (iii) chunked prefill and extend ----------------------------------------------


@pytest.mark.parametrize("first,more", [(16, 16), (24, 9), (5, 40), (33, 1)],
                         ids=["chunk_chunk", "ragged", "short_long", "one"])
def test_prefill_then_extend_matches_unchunked(params, first, more):
    toks = _tokens(first + more, seed=7)
    prefill, extend = gen._build_prefill(CFG)
    whole_logits, whole = prefill(params, toks[None])
    _, row = prefill(params, toks[None, :first])
    logits, row = extend(params, row, toks[None, first:])
    assert float(jnp.abs(logits - whole_logits).max()) < TOL
    for name in ("ssm_state", "conv_state"):
        a = gen._find_cache_leaf(row, name)
        b = gen._find_cache_leaf(whole, name)
        assert float(jnp.abs(a - b).max()) < TOL * float(jnp.abs(b).max() + 1)
    want = _want(params, toks, [first + more - 1])
    got = np.asarray(jax.nn.log_softmax(logits[0], -1))
    assert np.abs(got - want[0]).max() < TOL


def test_state_after_prefill_and_steps_is_the_longer_prefills(params):
    """Chunked prefill, then k one-token steps, against one prefill of the
    longer sequence and against the reference's sequential states."""
    toks = _tokens(45, seed=8)
    prefill, extend = gen._build_prefill(CFG)
    _, row = prefill(params, toks[None, :37])
    for i in range(37, 45):
        _, row = extend(params, row, toks[None, i:i + 1])
    _, whole = prefill(params, toks[None])
    _, states, _ = ref.forward(params, jnp.asarray(toks), MODEL)
    for i in (0, 2):
        got = row[f"layers_{i}"]["mixer"]["ssm_state"][0]
        one = whole[f"layers_{i}"]["mixer"]["ssm_state"][0]
        scale = float(jnp.abs(states[i]).max())
        assert float(jnp.abs(got - states[i]).max()) < 1e-5 * scale
        assert float(jnp.abs(one - states[i]).max()) < 1e-5 * scale


def test_chunk_scan_is_the_sequential_recurrence():
    """``ssm_chunk_scan`` from a carried state, a ragged length and heads of
    memories from one token to thousands, against the reference's scan."""
    rng = np.random.default_rng(0)
    b, length, heads, p, n = 2, 37, 8, 4, 16
    x = jnp.asarray(rng.normal(size=(b, length, heads, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (b, length, heads)), jnp.float32)
    b_in, c_in = (jnp.asarray(rng.normal(size=(b, length, n)), jnp.float32)
                  for _ in range(2))
    a = -jnp.arange(1, heads + 1, dtype=jnp.float32) * 16
    state = jnp.asarray(rng.normal(size=(b, heads, p, n)), jnp.float32)
    y, new = hs.ssm_chunk_scan(x, dt, b_in, c_in, a, state, 16, jnp.float32)
    for r in range(b):
        want_y, want_s = ref.recurrence(x[r], dt[r], b_in[r], c_in[r], a,
                                        state[r])
        assert float(jnp.abs(y[r] - want_y).max()) < 1e-4
        assert float(jnp.abs(new[r] - want_s).max()) < 1e-5


# -- (iv) rows of unequal length, (v) a slot reused ------------------------------


def test_rows_of_unequal_length_decode_together(params):
    """Three requests of three lengths in flight at once: one prefill group
    each, one decode step for all, each row at its own position."""
    prompts = [_tokens(n, seed=20 + n) for n in (9, 33, 57)]
    outs = [None] * 3
    with _Served(params) as s:
        clients = [s.another() for _ in prompts]

        def call(i):
            outs[i] = clients[i].generate(prompts[i][None], 30)[0]

        threads = [threading.Thread(target=call, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for c in clients:
            c.close()
        assert s.server.decode_batches < 3 * 8  # they did share dispatches
    for prompt, out in zip(prompts, outs):
        assert _gaps(params, out, len(prompt)).max() < GAP


def test_a_reused_slot_keeps_nothing_of_the_row_before(params):
    first, second = _tokens(60, seed=31), _tokens(12, seed=32)
    with _Served(params, max_slots=1) as s:
        s.client.generate(first[None], 20)
        out = s.client.generate(second[None], 20)[0]
    assert _gaps(params, out, 12).max() < GAP


# -- planted faults ---------------------------------------------------------------


def _fault_state_not_inserted(monkeypatch, params):
    """``insert`` that leaves the slots' state as it was."""
    family = hs._FAMILY._replace(slot_leaves=())
    monkeypatch.setattr(hs, "_FAMILY", family)
    for built in (gen._build_paged_fns, gen._build_slot_fns):
        built.cache_clear()
    prompt = _tokens(41, seed=3)
    try:
        with _Served(params) as s:
            out = s.client.generate(prompt[None], 25)[0]
    finally:
        monkeypatch.undo()
        for built in (gen._build_paged_fns, gen._build_slot_fns):
            built.cache_clear()
    return float(_gaps(params, out, 41).max()), GAP


def _fault_bfloat16_decay(monkeypatch, params):
    cfg = dataclasses.replace(CFG, ssm_dtype=jnp.bfloat16)
    return _prefill_err(cfg, params), TOL


def _fault_sqrt_d_scale(monkeypatch, params):
    cfg = dataclasses.replace(CFG, attention_multiplier=CFG.head_dim ** -0.5)
    return _prefill_err(cfg, params), TOL


def _fault_softmax_over_all(monkeypatch, params):
    def over_all(logits, k):
        _, chosen = jax.lax.top_k(logits, k)
        onehot = jax.nn.one_hot(chosen, logits.shape[-1], dtype=logits.dtype)
        return jax.nn.softmax(logits, -1) * jnp.sum(onehot, axis=1)

    monkeypatch.setattr(ls, "route_softmax_topk", over_all)
    return _prefill_err(CFG, params), TOL


def _fault_b_c_swapped(monkeypatch, params):
    scan = hs.ssm_chunk_scan
    monkeypatch.setattr(
        hs, "ssm_chunk_scan",
        lambda x, dt, b, c, *rest: scan(x, dt, c, b, *rest))
    return _prefill_err(CFG, params), TOL


@pytest.mark.parametrize("fault", [
    _fault_state_not_inserted, _fault_bfloat16_decay, _fault_sqrt_d_scale,
    _fault_softmax_over_all, _fault_b_c_swapped],
    ids=lambda f: f.__name__[len("_fault_"):])
def test_a_planted_fault_fails_the_comparison(params, monkeypatch, fault):
    err, limit = fault(monkeypatch, params)
    assert err > 5 * limit, err


# -- the live rows of a decode step -------------------------------------------------


@pytest.mark.parametrize("live", [(4,), (0, 2, 5), (0, 1, 2, 3, 4)],
                         ids=["one", "three", "two_groups"])
def test_a_step_over_live_rows_steps_them_as_the_step_over_all(params, live):
    """Six slots (more than ``ROWS``): the state and logits of the live rows
    after a paged decode step are those of the same rows stepped with all
    rows at once (a cache of their own), and a retired row's state stays."""
    slots, ps, pages = 6, 16, 24
    prefill, _ = gen._build_prefill(CFG)
    insert, _ = gen._build_paged_fns(CFG, ps)
    cache = gen.paged_cache(CFG, params, slots, ps, pages)
    table = np.full((slots, gen.pages_per_slot(CFG.max_seq, ps) + 1), pages,
                    np.int32)
    module = HybridSSMLM(CFG)
    step = jax.jit(lambda cache, tok: module.apply(
        {**params, "cache": cache}, tok, mutable=["cache", "intermediates"]))
    want, rows = {}, {}
    for j, s in enumerate(live):
        toks = _tokens(10 + 7 * j, seed=40 + s)
        table[s, :3] = 3 * j + np.arange(3)
        _, rows[s] = prefill(params, toks[None])
        cache = insert(cache, rows[s], np.array([s], np.int32),
                       np.int32(len(toks)), np.int32(0), table)
        want[s] = step(rows[s], jnp.full((1, 1), 5 + s, jnp.int32))
    before = gen._find_cache_leaf(cache, "ssm_state")
    tok = jnp.asarray([[5 + s] for s in range(slots)], jnp.int32)
    logits, state = step(cache, tok)
    sown = state["intermediates"]["layers_0"]["mixer"]["rows_run"][0]
    assert int(sown) == -(-len(live) // ROWS) * ROWS
    after = gen._find_cache_leaf(gen._as_dict(state["cache"]), "ssm_state")
    order, _ = ls.live_first(jnp.asarray([s in live for s in range(slots)]))
    idle = set(np.asarray(order)[int(sown):].tolist())  # in no group that ran
    for s in range(slots):
        if s in live:
            got = gen._find_cache_leaf(gen._as_dict(want[s][1]["cache"]),
                                       "ssm_state")
            assert float(jnp.abs(after[s] - got[0]).max()) < 1e-5
            assert float(jnp.abs(logits[s] - want[s][0][0]).max()) < TOL
        elif s in idle:
            assert np.array_equal(np.asarray(after[s]), np.asarray(before[s]))


# -- the share and the router ------------------------------------------------------


@pytest.mark.parametrize("tokens", [18, 72], ids=["kernel", "scan"])
def test_the_two_shares_add_up_to_the_uncut_layer_of_the_reference(tokens):
    """Shares ``[0, 4)`` and ``[4, 8)`` of the expert layer, the shared MLP
    counted once, against the reference's layer given all eight experts:
    through the grouped kernel (fewer tokens than ``DENSE_TOKENS``) and
    through the loop over all experts."""
    assert (tokens < ls.DENSE_TOKENS) == (tokens == 18)
    cfg = dataclasses.replace(CFG, experts_held=(0, 8))
    flat = jax.random.normal(jax.random.PRNGKey(0), (tokens, 64), jnp.float32)
    ones = jnp.ones((64,))
    # what a layer feeds its FFN
    u = ref.rms_norm(flat, ones).reshape(2, tokens // 2, 64)
    p = ExpertShare(cfg).init(jax.random.PRNGKey(1), u, None)["params"]
    shared = SwiGLU(cfg, cfg.shared_d_ff).apply(
        {"params": p["shared_expert"]}, u)
    routed = jnp.zeros_like(u)
    for share in range(2):
        held = dataclasses.replace(cfg, experts_held=(4 * share, 4))
        # the share's experts under its own numbering
        mine = {k: (v[4 * share:4 * share + 4] if k.startswith("experts_")
                    else v) for k, v in p.items()}
        out = ExpertShare(held).apply({"params": mine}, u, None,
                                      mutable=["cache"])[0]
        routed = routed + (out - shared)
    # the reference's uncut layer: x + r (MoE + SharedMLP)(RMSNorm(x)), r = 1
    uncut, _ = ref.ffn({"post_mixer_norm": {"scale": ones}, "mlp": p}, flat,
                       1.0, 3, 0, 8)
    whole = flat + (shared + routed).reshape(tokens, 64)
    assert float(jnp.abs(whole - uncut).max()) < 1e-5
    assert float(jnp.abs(routed).max()) > 0.05  # the routed part is not nothing


@pytest.mark.parametrize("live", [None, (1, 0, 1)], ids=["all", "a_row_retired"])
def test_the_stacked_share_of_a_few_tokens_is_the_loops(live):
    """The same weights and tokens through the grouped kernel (a decode
    step's few tokens) and, among other tokens that fill the call up to
    ``DENSE_TOKENS``, through the loop over all experts: a token's result
    does not depend on its company, and both count the same experts."""
    few = jax.random.normal(jax.random.PRNGKey(2), (3, 2, 64), jnp.float32)
    more = jax.random.normal(jax.random.PRNGKey(3), (3, 30, 64), jnp.float32)
    both = jnp.concatenate([few, more], axis=1)
    assert few.shape[0] * few.shape[1] < ls.DENSE_TOKENS <= both.size // 64
    share = ExpertShare(CFG)
    p = share.init(jax.random.PRNGKey(4), few, None)["params"]
    rows = None if live is None else jnp.asarray(live, jnp.float32)
    got, sown = share.apply({"params": p}, few, rows, mutable=["cache"])
    want, _ = share.apply({"params": p}, both, rows, mutable=["cache"])
    assert float(jnp.abs(got - want[:, :2]).max()) < 1e-6
    assert float(jnp.abs(got - SwiGLU(CFG, CFG.shared_d_ff).apply(
        {"params": p["shared_expert"]}, few)).max()) > 0.01
    gates = ls.route_softmax_topk(ls.router_logits(
        few.reshape(6, 64), p["router"]), CFG.n_experts_per_tok)[:, :4]
    if live is not None:
        gates = gates * jnp.repeat(rows, 2)[:, None]
    assert sown["cache"]["expert_stats"].tolist() == [
        int((gates > 0).any(axis=0).sum()), int((gates > 0).sum())]


def test_router_takes_the_top_k_logits_and_softmaxes_the_chosen():
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 3.0]])
    gates = ls.route_softmax_topk(logits, 2)
    want = jax.nn.softmax(jnp.asarray([3.0, 2.0]))
    assert np.allclose(np.asarray(gates),
                       [[float(want[1]), 0, 0, 0, float(want[0])]])


# -- the engine's guards for a family that cannot reuse a prefix ---------------------


def test_engine_runs_the_family_without_the_prefix_map(params):
    prompt = _tokens(48, seed=50)  # three full pages
    with _Served(params, prefix_sharing=True) as s:
        a = s.client.generate(prompt[None], 6)[0]
        b = s.client.generate(prompt[None], 6)[0]
        meta = s.client.last_serving_meta or {}
        stats = s.server._on_fleet_stats("test", None)
        assert not s.server._prefix_map and s.server.prefix_hits == 0
    assert stats["prefix_sharing"] is False
    assert meta.get("prefix_tokens", 0) == 0 and np.array_equal(a, b)


def test_engine_refuses_speculation_and_gather_rows_raises(params):
    from distriflow_tpu import InferenceServer

    with pytest.raises(ValueError, match="per-row state"):
        InferenceServer(CFG, params, port=0, serving=_serving(
            speculate_k=1, draft_model="self"))
    family = gen.decode_family(CFG)
    assert not family.prefix_reusable
    assert family.slot_leaves == ("ssm_state", "conv_state")
    _, gather_rows = gen._build_paged_fns(CFG, 16)
    with pytest.raises(ValueError, match="per-row state"):
        gather_rows(None, np.zeros((1, 11), np.int32), np.int32(16))


def test_the_other_families_declare_no_row_state():
    for family in (gen._TRANSFORMER_FAMILY, ls._FAMILY):
        assert family.slot_leaves == () and family.prefix_reusable
        assert family.donated_leaves == family.pool_leaves


def test_engine_counts_state_rows_and_donates_the_state(params):
    """With telemetry on: ``rows_run`` and the experts' counts on the decode
    spans, the new counter and gauge, and every dispatch updated the cache
    (state leaves included) in place."""
    from distriflow_tpu.obs.telemetry import Telemetry
    from distriflow_tpu.obs.tracing import Tracer

    tel = Telemetry(enabled=True)
    tel.tracer = Tracer(enabled=True, max_spans=10_000)
    with _Served(params, telemetry=tel, max_slots=6) as s:
        client = s._client(s.server.address, timeout=300.0, telemetry=tel,
                           report_interval_s=0.0).setup()
        client.generate(_tokens(20, seed=5)[None], 9)
        client.close()
        pools, _ = gen._split_pools(
            s.server._slot_cache, gen.decode_family(CFG).donated_leaves)
        donated = {path[-1].key for path, _ in
                   jax.tree_util.tree_leaves_with_path(pools)}
    assert donated == {"cached_k", "cached_v", "ssm_state", "conv_state"}
    spans = [x for x in tel.tracer.finished() if x["name"] == "decode_iter"]
    assert len(spans) == 2  # 8 tokens after the first, 4 a dispatch
    for attrs in spans:
        assert attrs["rows_run"] == 4 * ROWS and "sel_tokens" not in attrs
        # one row, 4 steps, 3 layers, top-3 of 8 with 4 held
        assert 0 < attrs["local_assignments"] <= 4 * 3 * 3
        assert 0 < attrs["experts_hit"] <= attrs["local_assignments"]
    snap = tel.snapshot()
    counters = snap["counters"]
    assert counters["serving_ssm_rows_stepped_total"] == 2 * 4 * ROWS
    assert not any(k.startswith("serving_cache_copies_total") and v
                   for k, v in counters.items())
    held = {k: v for k, v in counters.items()
            if k.startswith("serving_expert_assignments_total")}
    assert sum(held.values()) == 2 * 4 * 3 * 3
    gauge = next(v for k, v in snap["gauges"].items()
                 if k.startswith("serving_row_state_bytes"))
    assert gauge == 6 * CFG.row_state_bytes()
    assert CFG.row_state_bytes() == 2 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
