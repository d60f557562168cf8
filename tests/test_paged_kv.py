"""Paged KV cache + prefix sharing (round 9; docs/PERFORMANCE.md §7f).

Pins the contracts the page-pool serving layout makes:

- GREEDY decode through the paged cache is bit-identical to the slab
  layout AND to the solo generate() path, for arbitrary (disjoint) page
  placements — the page table is pure indirection, never numerics;
- a prefix-shared admission (prompt pages found in the reuse map) emits
  token-identical output to a cold admission of the same prompt;
- sharing is copy-on-write: a request diverging after the shared prefix
  never perturbs the requests it borrowed pages from;
- every page acquired for a request is returned exactly once — retire,
  instant-eos, and mid-decode disconnect all reconcile the pool and the
  allocated/released counters to zero leakage;
- the pool allocator itself refuses double-frees and over-allocation.

Everything runs on a tiny CPU transformer; the module is deliberately
NOT in conftest's slow set — tier-1 exercises the paged path every run.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distriflow_tpu.models.transformer as transformer
from distriflow_tpu.client import InferenceClient
from distriflow_tpu.models.generate import (
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    generate,
    paged_cache,
    pages_per_slot,
    set_page_tables,
    slot_cache,
)
from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
from distriflow_tpu.ops.flop_count import pallas_cost_of
from distriflow_tpu.obs import get_telemetry
from distriflow_tpu.server import InferenceServer
from distriflow_tpu.server.inference_server import _PagePool
from distriflow_tpu.utils.config import ServingConfig

pytestmark = pytest.mark.paging

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.float32, use_flash_attention=False,
)
PS = 16  # 3 pages per slot


@pytest.fixture(scope="module")
def params():
    return transformer_lm(CFG, example_seq=16).init(jax.random.PRNGKey(0))


@pytest.fixture()
def paged_server(params):
    server = InferenceServer(
        CFG, params, port=0,
        serving=ServingConfig(batch_window_s=0.2, decode_chunk=4,
                              kv_layout="paged", page_size=PS),
    ).setup()
    yield server
    server.stop()


def _client(server):
    return InferenceClient(server.address).setup()


# -- allocator -------------------------------------------------------------


def test_page_pool_allocator_contracts():
    pool = _PagePool(4)
    a = pool.alloc(3)
    assert len(set(a)) == 3 and pool.free_pages == 1
    with pytest.raises(RuntimeError):
        pool.alloc(2)  # only 1 free
    pool.ref(a[:1])
    assert pool.refcount(a[0]) == 2
    assert pool.unref(a[:1]) == 0  # still referenced
    assert pool.unref(a) == 3  # now everything frees
    assert pool.free_pages == 4
    with pytest.raises(RuntimeError):
        pool.unref(a[:1])  # double-free
    with pytest.raises(RuntimeError):
        pool.ref(a[:1])  # ref of a free page


def test_serving_config_paged_knobs():
    with pytest.raises(ValueError):
        ServingConfig(kv_layout="ring").validate()
    with pytest.raises(ValueError):
        ServingConfig(page_size=0).validate()
    with pytest.raises(ValueError):
        ServingConfig(page_pool_pages=0).validate()
    srv = ServingConfig(max_slots=4, page_size=16).validate()
    # default pool == the slab budget: max_slots worst-case slots
    assert srv.pool_pages(48) == 4 * 3
    assert ServingConfig(page_pool_pages=7).pool_pages(48) == 7


# -- device half: bit-identity across layouts ------------------------------


def _drive(params, cache, insert_cache, first, slot, n_tokens, max_slots):
    """Greedy-decode one occupied slot n_tokens-1 steps; returns tokens."""
    _, _, decode = _build_slot_fns(CFG, 1, False)
    tok = jnp.zeros((max_slots,), jnp.int32).at[slot].set(first)
    done = jnp.ones((max_slots,), bool).at[slot].set(False)
    z = jnp.zeros((max_slots,), jnp.float32)
    zi = jnp.zeros((max_slots,), jnp.int32)
    eos = jnp.full((max_slots,), -1, jnp.int32)
    out = [int(first)]
    cache = insert_cache
    for _ in range(n_tokens - 1):
        cache, tok, done, toks = decode(dict(params), cache, tok, done,
                                        z, zi, z + 1.0, zi, eos)
        out.append(int(np.asarray(toks)[slot, 0]))
    return out, cache


def test_paged_equals_slab_equals_solo_bitwise(params):
    """The tri-modal identity: same prompt through (a) solo generate,
    (b) the slab slot cache, (c) the paged pool at scattered, unordered
    physical pages — token streams must agree exactly (greedy argmax
    makes any numeric divergence visible as a token flip)."""
    max_slots, n_pages, n_tokens = 4, 12, 10
    prompt = jnp.asarray(
        np.random.RandomState(1).randint(0, 64, (1, 5)), jnp.int32)
    solo = list(np.asarray(
        generate(CFG, dict(params), prompt, n_tokens))[0, 5:])

    prefill, _ = _build_prefill(CFG)
    logits, row_cache = prefill(dict(params), prompt)
    first = int(jnp.argmax(logits, axis=-1)[0])
    slots = jnp.array([2], jnp.int32)

    insert_slab, _, _ = _build_slot_fns(CFG, 1, False)
    slab0 = insert_slab(slot_cache(CFG, params, max_slots), row_cache,
                        slots, jnp.int32(5))
    slab, _ = _drive(params, None, slab0, first, 2, n_tokens, max_slots)

    insert_paged, _ = _build_paged_fns(CFG, PS)
    pp = pages_per_slot(CFG.max_seq, PS)
    table = np.full((max_slots, pp + 1), n_pages, np.int32)
    table[2, :pp] = [5, 0, 7]  # scattered, unordered placement
    paged0 = insert_paged(paged_cache(CFG, params, max_slots, PS, n_pages),
                          row_cache, slots, jnp.int32(5), jnp.int32(0),
                          table)
    paged, _ = _drive(params, None, paged0, first, 2, n_tokens, max_slots)

    assert slab == solo
    assert paged == solo


def test_gather_extend_matches_cold_prefill_tokens(params):
    """The prefix-shared admission path (gather shared pages into a dense
    row cache, extend over the suffix) must emit the same tokens as a
    cold full prefill of the identical prompt."""
    max_slots, n_pages, n_gen = 4, 12, 8
    prompt = jnp.asarray(
        np.random.RandomState(2).randint(0, 64, (1, 20)), jnp.int32)
    solo = list(np.asarray(
        generate(CFG, dict(params), prompt, n_gen))[0, 20:])

    prefill, extend = _build_prefill(CFG)
    insert_paged, gather_rows = _build_paged_fns(CFG, PS)
    pp = pages_per_slot(CFG.max_seq, PS)
    cache = paged_cache(CFG, params, max_slots, PS, n_pages)

    # cold admission of the donor row at slot 0
    logits, row_cache = prefill(dict(params), prompt)
    table = np.full((max_slots, pp + 1), n_pages, np.int32)
    table[0, :pp] = [3, 8, 1]
    cache = insert_paged(cache, row_cache, jnp.array([0], jnp.int32),
                         jnp.int32(20), jnp.int32(0), table)

    # shared admission at slot 1: page 3 borrowed read-only, 9/2 owned
    table[1, :pp] = [3, 9, 2]
    rows = gather_rows(cache, table[1:2], jnp.int32(PS))
    lg, row_cache2 = extend(dict(params), rows, prompt[:, PS:])
    cache = insert_paged(cache, row_cache2, jnp.array([1], jnp.int32),
                         jnp.int32(20), jnp.int32(PS), table)
    first = int(jnp.argmax(lg, axis=-1)[0])
    shared, _ = _drive(params, None, cache, first, 1, n_gen, max_slots)
    assert shared == solo


def test_flash_decode_paged_matches_dense_reference():
    """The Pallas paged-decode kernel (interpret mode) against a dense
    f32 reference assembled by gathering the page pool through the same
    table — scattered pages, per-row valid lengths, sentinel tail."""
    from distriflow_tpu.ops.flash_decode import flash_decode_paged

    b, h, d, ps, n_pages, pp = 2, 8, 64, 128, 5, 2
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
    k_pool = jnp.asarray(rng.randn(n_pages, ps, h * d), jnp.bfloat16)
    v_pool = jnp.asarray(rng.randn(n_pages, ps, h * d), jnp.bfloat16)
    table = np.array([[3, 1], [4, n_pages]], np.int32)  # row 1: 1 live page
    valid = np.array([200, 96], np.int32)
    out = flash_decode_paged(q, k_pool, v_pool, jnp.asarray(table),
                             jnp.asarray(valid), interpret=True)

    kp = np.asarray(k_pool, np.float32)
    vp = np.asarray(v_pool, np.float32)
    for row in range(b):
        tab = np.minimum(table[row], n_pages - 1)
        kd = kp[tab].reshape(1, pp * ps, h * d)
        vd = vp[tab].reshape(1, pp * ps, h * d)
        kf = kd.reshape(1, pp * ps, h, d).transpose(0, 2, 1, 3)
        vf = vd.reshape(1, pp * ps, h, d).transpose(0, 2, 1, 3)
        qf = np.asarray(q, np.float32)[row:row + 1]
        scores = np.einsum("bhd,bhsd->bhs", qf, kf) / np.sqrt(d)
        scores[:, :, valid[row]:] = -1e30
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhs,bhsd->bhd", p, vf)
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[row], ref[0], rtol=0, atol=3e-2)


# -- one work list a decode step, shared by the layers ---------------------
#
# In a paged single-token step the model builds the decode kernel's work
# list once, from layer 0's table and index, and every layer's kernel call
# runs on it (models/transformer.py::_step_work_list). What a layer would
# have built is the same list, so the decode chunk is the per-layer form
# bit for bit; that rests on every layer holding the same table and index.

CHUNK = 4


def _flash_cfg(n_layers):
    return dataclasses.replace(CFG, n_layers=n_layers, use_flash_decode=True)


@pytest.fixture(scope="module")
def flash_params():
    return {n: transformer_lm(_flash_cfg(n), example_seq=16).init(
        jax.random.PRNGKey(n)) for n in (2, 4)}


def _engine_cache(cfg, params, max_slots=4, n_pages=12):
    """A paged cache as the engine leaves it: rows of 3, 16 (a whole page)
    and 21 tokens at scattered pages, the 16-token row's table ending in a
    sentinel; slot 2 never admitted (all sentinels, an index the decode
    steps move on). Returns the cache and each row's first token."""
    prefill, _ = _build_prefill(cfg)
    insert, _ = _build_paged_fns(cfg, PS)
    pp = pages_per_slot(cfg.max_seq, PS)
    cache = paged_cache(cfg, params, max_slots, PS, n_pages)
    table = np.full((max_slots, pp + 1), n_pages, np.int32)
    placed = {0: ([5, 0, 7], 3), 1: ([2, 9, n_pages], PS),
              3: ([4, 1, 3], PS + 5)}
    rng = np.random.RandomState(6)
    first = np.zeros(max_slots, np.int32)
    for slot, (pages, n) in placed.items():
        prompt = jnp.asarray(rng.randint(0, 64, (1, n)), jnp.int32)
        logits, row = prefill(dict(params), prompt)
        table[slot, :pp] = pages
        cache = insert(cache, row, jnp.array([slot], jnp.int32),
                       jnp.int32(n), jnp.int32(0), table)
        first[slot] = int(jnp.argmax(logits, axis=-1)[0])
    return cache, jnp.asarray(first)


def _decode_args(first):
    b = first.shape[0]
    z, zi = jnp.zeros((b,), jnp.float32), jnp.zeros((b,), jnp.int32)
    done = jnp.zeros((b,), bool).at[2].set(True)
    return first, done, z, zi, z + 1.0, zi, jnp.full((b,), -1, jnp.int32)


def _two_chunks(cfg, params, cache, first):
    """Two decode chunks through the served program's body (undonated, so
    the caller's cache serves again). Returns the tokens and the cache."""
    _, _, decode = _build_slot_fns(cfg, CHUNK, False)
    tok, done, *rest = _decode_args(first)
    out = []
    for _ in range(2):
        cache, tok, done, toks = decode.body(dict(params), cache, tok, done,
                                             *rest)
        out.append(np.asarray(toks))
    return np.concatenate(out, axis=1), cache


def _layers(cache, name):
    return [np.array(cache[key]["attn"][name])
            for key in sorted(cache) if key.startswith("layers_")]


@pytest.mark.parametrize("n_layers", [2, 4])
def test_decode_chunk_shared_work_list_matches_per_layer(
        flash_params, monkeypatch, n_layers):
    cfg = _flash_cfg(n_layers)
    params = flash_params[n_layers]
    cache, first = _engine_cache(cfg, params)
    toks, got = _two_chunks(cfg, params, cache, first)
    # the per-layer form: no list a step, each kernel call builds its own
    monkeypatch.setattr(transformer, "_step_work_list", lambda *a: None)
    want_toks, want = _two_chunks(cfg, params, cache, first)
    np.testing.assert_array_equal(toks, want_toks)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (toks[2] == 0).all() and len(set(toks[[0, 1, 3]].ravel())) > 1


def _chunk_structs(cfg, max_slots=4, n_pages=12):
    params = jax.eval_shape(transformer_lm(cfg, example_seq=16).init,
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda p: paged_cache(cfg, p, max_slots, PS, n_pages), params)
    vec = lambda dt: jax.ShapeDtypeStruct((max_slots,), dt)  # noqa: E731
    return (params, cache, vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.int32), vec(jnp.int32))


@pytest.mark.parametrize("n_layers", [2, 4])
def test_decode_chunk_tally_one_list_build_and_a_reuse_a_layer(n_layers):
    cfg = _flash_cfg(n_layers)
    _, _, decode = _build_slot_fns(cfg, CHUNK, False)
    counts = pallas_cost_of(decode.body, *_chunk_structs(cfg))["counts"]
    assert counts == {"paged_work_lists_built": 1,
                      "paged_work_lists_reused": n_layers}


def _list_locations(cfg):
    """Source locations under the work list's scope in the decode chunk's
    lowered text: the list's ops, each place once."""
    _, _, decode = _build_slot_fns(cfg, CHUNK, False)
    jax.clear_caches()  # the trace, not a cached one of another form
    text = jax.jit(decode.body).lower(*_chunk_structs(cfg)).as_text(
        debug_info=True)
    return sum(1 for line in text.splitlines()
               if line.startswith("#loc") and "paged_work_list" in line)


def test_decode_chunk_list_ops_do_not_grow_with_depth(monkeypatch):
    try:
        shared = [_list_locations(_flash_cfg(n)) for n in (2, 4)]
        monkeypatch.setattr(transformer, "_step_work_list", lambda *a: None)
        per_layer = [_list_locations(_flash_cfg(n)) for n in (2, 4)]
    finally:
        jax.clear_caches()
    assert shared[0] == shared[1] > 0
    assert per_layer[1] > per_layer[0] > shared[0]


def test_every_layer_holds_the_same_table_and_index(flash_params):
    """What the shared list rests on: the paged ``insert`` and
    ``set_page_tables`` write each layer's ``page_table`` from one array,
    and ``insert`` and the decode chunk move each layer's
    ``cache_index`` alike."""
    cfg = _flash_cfg(4)
    cache, first = _engine_cache(cfg, flash_params[4])

    def same(cache, name):
        each = _layers(cache, name)
        assert len(each) == 4
        for leaf in each[1:]:
            np.testing.assert_array_equal(leaf, each[0])
        return each[0]

    assert same(cache, "cache_index").tolist() == [3, PS, 0, PS + 5]
    table = same(cache, "page_table")
    table[2, 0] = 11  # admit slot 2 onto a fresh page
    cache = set_page_tables(cache, table)
    np.testing.assert_array_equal(same(cache, "page_table"), table)
    _, cache = _two_chunks(cfg, flash_params[4], cache, first)
    assert same(cache, "cache_index").tolist() == [
        3 + 2 * CHUNK, PS + 2 * CHUNK, 2 * CHUNK, PS + 5 + 2 * CHUNK]
    np.testing.assert_array_equal(same(cache, "page_table"), table)


# -- server half -----------------------------------------------------------


def _concurrent(server, calls):
    results = [None] * len(calls)
    errors = []
    barrier = threading.Barrier(len(calls))

    def run(i, kwargs):
        try:
            with _client(server) as c:
                barrier.wait()
                results[i] = c.generate(**kwargs)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(i, kw))
        for i, kw in enumerate(calls)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return results


def test_server_paged_greedy_bit_identical_to_solo(params, paged_server):
    """Batched greedy decode through the paged server == solo generate,
    across mixed prompt lengths sharing one admission (the acceptance
    bar of the round-9 refactor)."""
    rs = np.random.RandomState(3)
    lens = [5, 20, 33, 20]
    prompts = [rs.randint(0, 64, (1, p)).astype(np.int32) for p in lens]
    solos = [np.asarray(generate(CFG, dict(params), jnp.asarray(p), 9))
             for p in prompts]
    outs = _concurrent(paged_server,
                       [dict(prompt=p, n_tokens=9) for p in prompts])
    for got, want in zip(outs, solos):
        np.testing.assert_array_equal(got, want)


def test_prefix_hit_identical_output_and_counters(params, paged_server):
    """Second serving of an identical prompt rides the prefix map (hits
    and saved-token counters move) and still emits identical tokens."""
    tel = get_telemetry()
    prompt = np.random.RandomState(4).randint(0, 64, (1, 37)).astype(np.int32)
    solo = np.asarray(generate(CFG, dict(params), jnp.asarray(prompt), 8))
    h0 = tel.counter_value("serving_prefix_hits_total")
    s0 = tel.counter_value("serving_prefix_tokens_saved_total")
    with _client(paged_server) as c:
        cold = c.generate(prompt, n_tokens=8)
        warm = c.generate(prompt, n_tokens=8)
        meta = c.last_serving_meta
    np.testing.assert_array_equal(cold, solo)
    np.testing.assert_array_equal(warm, solo)
    # 37-token prompt shares its (37-1)//16 = 2 full pages on the replay
    assert tel.counter_value("serving_prefix_hits_total") - h0 >= 1
    assert tel.counter_value("serving_prefix_tokens_saved_total") - s0 >= 32
    assert meta.get("prefix_tokens", 0) >= 32


def test_copy_on_write_divergence(params, paged_server):
    """Requests sharing a prompt prefix but diverging after it must each
    match their own solo stream — and serving the divergent request must
    not corrupt the donor's shared pages (re-serving the donor afterwards
    still matches)."""
    base = np.random.RandomState(5).randint(0, 64, (1, 33)).astype(np.int32)
    fork = base.copy()
    fork[0, 20:] = (fork[0, 20:] + 7) % 64  # diverge INSIDE page 2
    solo_base = np.asarray(generate(CFG, dict(params), jnp.asarray(base), 8))
    solo_fork = np.asarray(generate(CFG, dict(params), jnp.asarray(fork), 8))
    with _client(paged_server) as c:
        np.testing.assert_array_equal(
            c.generate(base, n_tokens=8), solo_base)
        # fork shares page 0 (tokens 0..15), owns its divergent pages
        np.testing.assert_array_equal(
            c.generate(fork, n_tokens=8), solo_fork)
        # donor unharmed: its shared page was read-only to the fork
        np.testing.assert_array_equal(
            c.generate(base, n_tokens=8), solo_base)


@pytest.mark.chaos
def test_disconnect_mid_decode_reclaims_pages(paged_server):
    """A client that vanishes mid-decode must have its pages returned at
    the next chunk boundary, with exactly-once accounting: after the
    engine settles and the prefix map is flushed, allocated == released
    and the pool is back to all-free with zero refcounts."""
    tel = get_telemetry()
    a0 = tel.counter_value("serving_pages_allocated_total")
    r0 = tel.counter_value("serving_pages_released_total")
    prompt = np.random.RandomState(6).randint(0, 64, (1, 20)).astype(np.int32)

    c = _client(paged_server)
    t = threading.Thread(
        target=lambda: c.generate(prompt, n_tokens=25), daemon=True)
    t.start()
    deadline = time.time() + 30
    while paged_server._pool.used_pages == 0 and time.time() < deadline:
        time.sleep(0.01)  # wait until the request actually holds pages
    assert paged_server._pool.used_pages > 0
    c.close()  # mid-decode disconnect
    # settle: admission may still be mid-compile when the close lands, so
    # "slots all free" alone is trivially true too early — wait until the
    # only pages still referenced are the prefix map's own
    deadline = time.time() + 30
    while time.time() < deadline:
        if (all(r is None for r in paged_server._slot_req)
                and paged_server._pool.used_pages
                == len(paged_server._prefix_map)):
            break
        time.sleep(0.02)
    paged_server.release_prefix_cache()
    pool = paged_server._pool
    assert pool.free_pages == pool.n_pages
    assert (pool._refs == 0).all()
    alloc = tel.counter_value("serving_pages_allocated_total") - a0
    freed = tel.counter_value("serving_pages_released_total") - r0
    assert alloc > 0 and alloc == freed


def test_fleet_row_tracks_pages_held(paged_server):
    """The serving FleetTable row carries the pages a connection holds,
    and drops back to 0 once its requests retire."""
    prompt = np.random.RandomState(7).randint(0, 64, (1, 20)).astype(np.int32)
    with _client(paged_server) as c:
        c.generate(prompt, n_tokens=6)
    rows = paged_server.fleet.snapshot()
    assert rows, "no fleet row recorded for the serving client"
    assert all(row["pages"] == 0 for row in rows.values())


def test_slab_layout_still_selectable(params):
    """kv_layout="slab" keeps the legacy layout fully working — it is the
    bit-identity oracle for one release (ROADMAP round 9)."""
    server = InferenceServer(
        CFG, params, port=0,
        serving=ServingConfig(batch_window_s=0.1, decode_chunk=4,
                              kv_layout="slab"),
    ).setup()
    try:
        prompt = np.asarray([[7, 3, 11, 2]], np.int32)
        solo = np.asarray(generate(CFG, dict(params), jnp.asarray(prompt), 6))
        with _client(server) as c:
            np.testing.assert_array_equal(
                c.generate(prompt, n_tokens=6), solo)
        assert server._pool is None  # no pool machinery on the slab path
    finally:
        server.stop()
