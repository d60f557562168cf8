"""The engine's decode loop one chunk ahead of the host
(``InferenceServer._decode_iteration``): chunk n+1 is enqueued on chunk n's
device outputs before the host fetches chunk n's tokens.

Held, for each of the three model families at toy widths, to the strict
order (``_lookahead`` off: each chunk fetched before the next is enqueued)
on requests of mixed lengths that arrive mid-decode, retire by budget and
by eos, and are cancelled, over a page pool small enough that pages are
reused while a chunk is in flight; to the order in which the device
must see a reused page; and to a chunk whose fetch fails.
"""

import concurrent.futures
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu import (
    InferenceClient,
    InferenceServer,
    ServingConfig,
    TransformerConfig,
    transformer_lm,
)
from distriflow_tpu.models import hybrid_ssm as hs
from distriflow_tpu.models import latent_sparse as ls
from distriflow_tpu.models.generate import _find_cache_leaf
from distriflow_tpu.obs.telemetry import Telemetry
from distriflow_tpu.server import inference_server as srv_mod

CHUNK = 4
PAGE = 8
SLOTS = 5
POOL = 20  # the traffic below holds 33 pages over its life

FAMILIES = {
    "transformer": TransformerConfig(
        vocab_size=97, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=64, dtype=jnp.float32),
    "latent_sparse": ls.LatentSparseConfig(
        vocab_size=97, d_model=64, n_layers=3, n_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=8, index_head_dim=16, index_topk=16,
        indexer_types=("full", "shared", "full"),
        mlp_layer_types=("dense", "sparse", "sparse"), d_ff=128,
        moe_d_ff=32, n_routed_experts=8, n_experts_per_tok=2,
        routed_scaling_factor=2.5, experts_held=(0, 2), max_seq=64,
        index_rope_dim=8, rope_base=10000.0, dtype=jnp.float32,
        param_dtype=jnp.float32, query_block=8),
    "hybrid_ssm": hs.HybridSSMConfig(
        vocab_size=97, d_model=64, layer_types=("mamba", "attention", "mamba"),
        n_heads=4, n_kv_heads=2, attention_multiplier=1 / 32,
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, moe_d_ff=32,
        shared_d_ff=48, n_routed_experts=8, n_experts_per_tok=3,
        experts_held=(0, 4), max_seq=64, mamba_chunk_size=16,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=4.0, dtype=jnp.float32, param_dtype=jnp.float32,
        embed_init_std=0.005, query_block=32),
}

# name: (prompt length, budget); "c" stops at eos, "d" is cancelled
REQUESTS = {"a": (20, 30), "b": (9, 9), "c": (14, 20), "d": (11, 30),
            "e": (17, 12), "f": (5, 25), "g": (22, 7)}


def _params(family):
    cfg = FAMILIES[family]
    key = jax.random.PRNGKey(1)
    if family == "transformer":
        return transformer_lm(cfg, example_seq=16).init(key)
    return (ls if family == "latent_sparse" else hs).init_params(cfg, key)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    return request.param, FAMILIES[request.param], _params(request.param)


def _prompts(cfg):
    rng = np.random.default_rng(7)
    return {k: rng.integers(0, cfg.vocab_size, (1, plen)).astype(np.int32)
            for k, (plen, _) in REQUESTS.items()}


def _server(cfg, params, tel, lookahead=True):
    server = InferenceServer(cfg, params, port=0, telemetry=tel, serving=ServingConfig(
        max_slots=SLOTS, decode_chunk=CHUNK, kv_layout="paged",
        page_size=PAGE, page_pool_pages=POOL, prefix_sharing=False,
        batch_window_s=0.0))
    server._lookahead = lookahead
    return server.setup()


def _until(cond, timeout=120.0):
    t_end = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t_end, "timed out"
        time.sleep(0.002)


def _eos(server, prompts):
    """c's eos: the token its reply reaches at its sixth step."""
    with InferenceClient(server.address, timeout=300.0,
                         report_interval_s=0.0) as probe:
        return int(probe.generate(prompts["c"], 20)[0, 14 + 5])


def _traffic(server, prompts, eos):
    """Every request of ``REQUESTS``; b and c arrive while a decodes, d is
    cancelled once it decodes, e, f and g wait for pages. Returns each
    answered request's tokens."""
    clients = {k: InferenceClient(server.address, timeout=300.0,
                                  report_interval_s=0.0).setup()
               for k in REQUESTS}

    def ask(k, **kw):
        return pool.submit(clients[k].generate, prompts[k], REQUESTS[k][1],
                           **kw)

    try:
        with concurrent.futures.ThreadPoolExecutor(len(REQUESTS)) as pool:
            futs = {"a": ask("a")}
            _until(lambda: server.decode_batches >= 2)
            futs["b"] = ask("b")
            futs["c"] = ask("c", eos_id=eos)
            futs["d"] = ask("d", request_id="gone")
            _until(lambda: any(r is not None and r.request_id == "gone"
                               for r in server._slot_req))
            mark = server.decode_batches
            _until(lambda: server.decode_batches > mark)
            server._on_hedge_cancel("test", {"request_id": "gone"})
            for k in "efg":
                futs[k] = ask(k)
            with pytest.raises(Exception):
                futs.pop("d").result(timeout=300)
            return {k: f.result(timeout=300) for k, f in futs.items()}
    finally:
        for c in clients.values():
            c.close()


def test_pipelined_streams_are_the_strict_orders(family, monkeypatch):
    """Identical greedy token streams, the pool conserved at quiescence, no
    pool copied, and more than half of a steady stretch's chunks enqueued
    while the one before was in flight."""
    monkeypatch.setenv("DISTRIFLOW_POOL_WITNESS", "1")
    _name, cfg, params = family
    prompts = _prompts(cfg)
    got = {}
    for lookahead in (False, True):
        tel = Telemetry()
        server = _server(cfg, params, tel, lookahead)
        try:
            if not lookahead:
                eos = _eos(server, prompts)
            got[lookahead] = _traffic(server, prompts, eos)
            with InferenceClient(server.address, timeout=300.0,
                                 report_interval_s=0.0) as alone:
                b0 = tel.counter_value("serving_decode_batches_total")
                o0 = tel.counter_value("serving_decode_overlapped_total")
                alone.generate(prompts["a"], 30)
                batches = tel.counter_value("serving_decode_batches_total") - b0
                overlapped = (tel.counter_value("serving_decode_overlapped_total")
                              - o0)
        finally:
            server.stop()
        assert server._pool_witness.checks >= 1
        assert server._pool_witness.trips == 0
        assert not any(tel.counter_value("serving_cache_copies_total",
                                         program=p)
                       for p in ("decode", "insert", "spec"))
        if lookahead:
            assert batches == 8 and overlapped > batches / 2
        else:
            assert overlapped == 0
    assert got[True].keys() == set(REQUESTS) - {"d"}
    for k, out in got[False].items():
        np.testing.assert_array_equal(got[True][k], out, err_msg=k)
    stopped = got[True]["c"][0, 14:]
    assert (stopped == eos).any() and stopped[-1] == eos


def _record_programs(monkeypatch, log):
    """Wrap the engine's decode and paged insert so that each call leaves,
    in the order the host enqueued them, the page table the program runs
    with: the decode's from the cache it reads (page tables are never
    donated), the insert's as passed."""
    slot_fns, paged_fns = srv_mod._build_slot_fns, srv_mod._build_paged_fns

    def slot(*a):
        insert, pick, decode = slot_fns(*a)

        def logged(params, cache, *rest):
            log.append(("decode", None, _find_cache_leaf(cache, "page_table")))
            return decode(params, cache, *rest)

        return insert, pick, logged

    def paged(*a):
        insert, gather = paged_fns(*a)

        def logged(cache, rows, slots, length, start, table):
            log.append(("insert", np.asarray(slots), table.copy()))
            return insert(cache, rows, slots, length, start, table)

        return logged, gather

    monkeypatch.setattr(srv_mod, "_build_slot_fns", slot)
    monkeypatch.setattr(srv_mod, "_build_paged_fns", paged)


def test_a_reused_page_is_written_only_after_its_last_chunk(family,
                                                              monkeypatch):
    """Page by page, in the order the programs were enqueued: every chunk's
    table maps a page to the slot the last insert gave it to. A page a
    retirement freed is therefore never handed to an insert enqueued ahead
    of a chunk that can still write it for its old row, though pages are
    reused right behind a chunk in flight."""
    _name, cfg, params = family
    log = []
    _record_programs(monkeypatch, log)
    prompts = _prompts(cfg)
    server = _server(cfg, params, Telemetry())
    try:
        _traffic(server, prompts, _eos(server, prompts))
    finally:
        server.stop()
    # a row is its slot and the insert that filled it: a page's owner is the
    # row the last insert of it gave it to, and a chunk may map it to that
    # row alone
    owner, row_of = {}, {}
    behind_flight = 0
    last_decode = {}
    for i, (kind, slots, table) in enumerate(log):
        table = np.asarray(table)
        if kind == "insert":
            for s in slots[slots < SLOTS].tolist():
                row_of[s] = (s, i)
                for p in table[s][table[s] < POOL].tolist():
                    if last_decode.get(p, row_of[s]) != row_of[s]:
                        behind_flight += 1  # the chunk before still had it
                    owner[p] = row_of[s]
            continue
        last_decode = {}
        for s in range(SLOTS):
            for p in table[s][table[s] < POOL].tolist():
                assert owner.get(p) == row_of.get(s), (p, s)
                last_decode[p] = row_of[s]
    assert behind_flight > 0
    assert sum(k == "decode" for k, _, _ in log) >= 10


class _FetchFails:
    """The ``jax`` the server module sees, but for its ``device_get``: the
    ``n``-th call from now raises, as the fetch of a chunk whose program
    failed on the device does."""

    def __init__(self, n):
        self.left = n

    def device_get(self, x):
        self.left -= 1
        if self.left == 0:
            raise RuntimeError("planted device fault")
        return jax.device_get(x)

    def __getattr__(self, name):
        return getattr(jax, name)


def test_a_failed_chunk_fails_its_rows_and_the_engine_serves_on(monkeypatch):
    """A chunk whose tokens fail to arrive, found at the fetch after the
    next chunk was enqueued on its outputs: its rows fail, the pools are
    dropped and the next request is served on fresh ones, bit for bit as
    before."""
    monkeypatch.setenv("DISTRIFLOW_POOL_WITNESS", "1")
    cfg = FAMILIES["transformer"]
    params = _params("transformer")
    prompt = _prompts(cfg)["a"]
    tel = Telemetry()
    server = _server(cfg, params, tel)

    def launched():
        return tel.counter_value("serving_cache_donations_total",
                                 program="decode")

    try:
        with InferenceClient(server.address, timeout=300.0,
                             report_interval_s=0.0) as client:
            want = client.generate(prompt, 30)
            before = launched()
            # the third chunk's fetch: chunk 4 went out on its outputs first
            monkeypatch.setattr(srv_mod, "jax", _FetchFails(3))
            with pytest.raises(Exception):
                client.generate(prompt, 30)
            assert launched() - before == 4
            monkeypatch.setattr(srv_mod, "jax", jax)
            np.testing.assert_array_equal(client.generate(prompt, 30), want)
    finally:
        server.stop()
    assert server._pool_witness.trips == 0
    assert server._pool.free_pages == POOL
    assert not any(tel.counter_value("serving_cache_copies_total", program=p)
                   for p in ("decode", "insert", "spec"))


def test_a_late_fetch_never_lengthens_the_chunk_estimate():
    """A chunk that had ended before the host asked for its tokens is timed
    by a host that came late: that reading may lower the estimate of a
    chunk's end but never raise it, while a chunk the host waited for is
    its true time and moves the estimate either way."""
    server = _server(FAMILIES["transformer"], _params("transformer"),
                     Telemetry())
    try:
        server._chunk_times.clear()
        server._time_chunk(0.050, ended=True)   # the first reading is kept
        for _ in range(srv_mod.TIMING_WINDOW):
            server._time_chunk(0.060, ended=True)
        assert list(server._chunk_times) == [0.050]
        server._time_chunk(0.045, ended=True)
        assert min(server._chunk_times) == 0.045
        for _ in range(srv_mod.TIMING_WINDOW):
            server._time_chunk(0.070, ended=False)
        assert min(server._chunk_times) == 0.070
    finally:
        server.stop()
