"""The engine's KV pools are donated to every program that returns the
cache's successor (``models/generate.py::_CacheProgram``).

Pins, in order: (a) a donating call consumes the pools it was passed, and
only them, and computes what the same body computes undonated; (b) a cache
whose ``page_table`` / ``cache_index`` leaves are ONE shared array (what
``set_page_tables`` / ``_set_cache_positions`` leave behind) goes through
consecutive donating calls, which a whole-tree donation cannot; (c) the
compiled programs alias every pool to its output, with jax's "donated
buffers were not usable" warning made an error; (d) an ``InferenceServer``
at the benchmark's rehearsal size serves a mixed batch without one pool
copy; (e) a call that fails before execution leaves the pools alive, one
that fails after consuming them makes the engine start over on a fresh
cache.
"""

import os
import re
import sys
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.client import InferenceClient
from distriflow_tpu.models.generate import (
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    _build_spec_fns,
    _set_cache_positions,
    _split_pools,
    generate,
    paged_cache,
    pages_per_slot,
    set_page_tables,
    slot_cache,
)
from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
from distriflow_tpu.obs.telemetry import Telemetry
from distriflow_tpu.server import InferenceServer
from distriflow_tpu.server import inference_server as server_mod
from distriflow_tpu.utils.config import ServingConfig

pytestmark = pytest.mark.paging

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.float32, use_flash_attention=False,
)
PS = 16
SLOTS, N_PAGES, CHUNK, PLEN = 4, 12, 3, 5
PP = pages_per_slot(CFG.max_seq, PS)


@pytest.fixture(scope="module")
def params():
    return dict(transformer_lm(CFG, example_seq=16).init(jax.random.PRNGKey(0)))


@pytest.fixture(autouse=True)
def unusable_donation_is_an_error():
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message="Some donated buffers were not usable")
        yield


def _table():
    table = np.full((SLOTS, PP + 1), N_PAGES, np.int32)
    table[2, :PP] = [5, 0, 7]
    return table


def _row(params):
    prompt = jnp.asarray(
        np.random.RandomState(1).randint(0, 64, (1, PLEN)), jnp.int32)
    logits, row_cache = _build_prefill(CFG)[0](params, prompt)
    return prompt, int(jnp.argmax(logits, axis=-1)[0]), row_cache


def _insert_call(params, layout):
    """``(program, args)`` of the page scatter (``paged``) or the slab
    scatter of one prefilled row into slot 2 of a fresh cache."""
    _, _, row_cache = _row(params)
    slots = jnp.array([2], jnp.int32)
    if layout == "paged":
        return _build_paged_fns(CFG, PS)[0], (
            paged_cache(CFG, params, SLOTS, PS, N_PAGES), row_cache, slots,
            jnp.int32(PLEN), jnp.int32(0), _table())
    return _build_slot_fns(CFG, CHUNK, False)[0], (
        slot_cache(CFG, params, SLOTS), row_cache, slots, jnp.int32(PLEN))


def _decode_call(params, layout):
    """``(program, args)`` of one decode chunk over a cache that holds one
    prefilled row in slot 2."""
    insert, args = _insert_call(params, layout)
    _, first, _ = _row(params)
    z, zi = jnp.zeros((SLOTS,), jnp.float32), jnp.zeros((SLOTS,), jnp.int32)
    return _build_slot_fns(CFG, CHUNK, False)[2], (
        params, insert(*args), zi.at[2].set(first),
        jnp.ones((SLOTS,), bool).at[2].set(False), z, zi, z + 1.0, zi,
        jnp.full((SLOTS,), -1, jnp.int32))


CALLS = {"decode-paged": (_decode_call, "paged", 1),
         "decode-slab": (_decode_call, "slab", 1),
         "insert-paged": (_insert_call, "paged", 0),
         "insert-slab": (_insert_call, "slab", 0)}


def _call(params, case):
    build, layout, cache_arg = CALLS[case]
    program, args = build(params, layout)
    return program, list(args), cache_arg


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _assert_trees_equal(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- (a) the pools are consumed, the result is the undonated body's ---------


@pytest.mark.parametrize("case", sorted(CALLS))
def test_donating_call_consumes_the_pools_and_equals_the_plain_jit(
        params, case):
    program, args, cache_arg = _call(params, case)
    oracle_args = list(args)
    oracle_args[cache_arg] = _copy(args[cache_arg])
    want = jax.jit(program.body)(*oracle_args)
    pools, rest = _split_pools(args[cache_arg])
    assert len(jax.tree.leaves(pools)) == 2 * CFG.n_layers  # K and V a layer
    got = program(*args)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pools))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(rest))
    others = jax.tree.leaves([a for i, a in enumerate(args) if i != cache_arg])
    assert not any(leaf.is_deleted() for leaf in others
                   if isinstance(leaf, jax.Array))
    _assert_trees_equal(got, want)  # the cache, and for decode the tokens


def test_donated_decode_stream_equals_solo_generate(params):
    """The identity the engine lives on, through donating programs alone:
    insert, then chunk after chunk, each on the last one's result."""
    prompt, first, _ = _row(params)
    n_tokens = 1 + 3 * CHUNK
    solo = list(np.asarray(generate(CFG, params, prompt, n_tokens))[0, PLEN:])
    for layout in ("paged", "slab"):
        decode, args = _decode_call(params, layout)
        params_, cache, tok, done, *rest = args
        out = [first]
        for _ in range(3):
            cache, tok, done, toks = decode(params_, cache, tok, done, *rest)
            out.extend(int(t) for t in np.asarray(toks)[2])
        assert out == solo, layout


# -- (b) leaves that are one shared array ------------------------------------


def _shared(cache, name):
    found = []

    def walk(node):
        for key, sub in node.items():
            if key == name:
                found.append(sub)
            elif hasattr(sub, "items"):
                walk(sub)

    walk(cache)
    return found


@pytest.mark.parametrize("case", ["decode-paged", "insert-paged"])
def test_one_table_under_every_layer_survives_consecutive_donations(
        params, case):
    program, args, cache_arg = _call(params, case)
    for _ in range(2):
        args[cache_arg] = set_page_tables(args[cache_arg], _table())
        tables = _shared(args[cache_arg], "page_table")
        assert len(tables) == CFG.n_layers
        assert all(t is tables[0] for t in tables)
        out = program(*args)
        args[cache_arg] = out if cache_arg == 0 else out[0]
    jax.block_until_ready(args[cache_arg])


def test_one_index_under_every_layer_survives_the_spec_programs(params):
    """``verify`` and ``commit`` return caches whose ``cache_index`` leaves
    are one traced value; fed back eagerly through ``_set_cache_positions``
    they are one ARRAY, which is what a second round is handed."""
    k = 2
    draft_k, verify, commit = _build_spec_fns(CFG, CFG, k, False)
    decode, args = _decode_call(params, "paged")
    _, cache, tok, done, temps, top_ks, top_ps, seeds, eos = args
    d_cache = _copy(cache)
    for _ in range(2):
        pos = jnp.asarray(np.full((SLOTS,), PLEN, np.int32))
        cache = _set_cache_positions(cache, pos)
        d_cache = _set_cache_positions(d_cache, pos)
        for c in (cache, d_cache):
            idx = _shared(c, "cache_index")
            assert len(idx) == CFG.n_layers and all(i is idx[0] for i in idx)
        d_cache, drafts, qprobs = draft_k(
            params, d_cache, tok, temps, top_ks, top_ps, seeds)
        cache, _emit, _n_emit, _n_acc, _tok, _done, catch, new_idx = verify(
            params, cache, tok, drafts, qprobs, temps, top_ks, top_ps, seeds,
            done, eos)
        d_cache = commit(params, d_cache, drafts[:, -1], catch, new_idx)
    jax.block_until_ready((cache, d_cache))


# -- (c) the compiled programs alias every pool -------------------------------


@pytest.mark.parametrize("case", sorted(CALLS))
def test_compiled_program_aliases_every_pool_to_an_output(params, case):
    program, args, cache_arg = _call(params, case)
    pools, _ = _split_pools(args[cache_arg])
    n_pools = len(jax.tree.leaves(pools))
    text = program.lower(*args).compile().as_text()
    header = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert header, "the compiled module lists no input-output alias"
    # "{out}: (parameter, {}, may-alias)": the pools are the program's first
    # arguments (models/generate.py::_CacheProgram donates argument 0)
    aliased = {int(p) for p in re.findall(r"\((\d+), \{\}", header.group(1))}
    assert aliased == set(range(n_pools))


# -- (d) the engine, at the benchmark's rehearsal size ------------------------


def _rehearsal_server(telemetry, monkeypatch):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.lib import harness

    config = harness.toy(harness.Registry().config("pythia-1.4b-widths-serve"))
    cfg = harness.transformer_config(harness.model_view(config),
                                     name_kernels=True)
    params = jax.jit(transformer_lm(cfg, example_seq=128).init)(
        jax.random.PRNGKey(0))
    monkeypatch.setenv("DISTRIFLOW_POOL_WITNESS", "1")
    server = InferenceServer(
        cfg, params, port=0, telemetry=telemetry,
        serving=ServingConfig(batch_window_s=0.0, **config["serving"]))
    return cfg, server.setup()


def _counts(tel, name):
    return {p: tel.counter_value(name, program=p)
            for p in ("decode", "insert", "spec")}


def test_engine_serves_a_mixed_batch_without_one_pool_copy(monkeypatch):
    """Admission during decode, a retire, a prefix-shared admission and a
    sampled request: every dispatch updates the pools in place."""
    import concurrent.futures

    tel = Telemetry()
    cfg, server = _rehearsal_server(tel, monkeypatch)
    ps = server.serving.page_size
    rs = np.random.RandomState(7)
    long_prompt = rs.randint(0, cfg.vocab_size, (1, ps + 40)).astype(np.int32)
    try:
        with concurrent.futures.ThreadPoolExecutor(3) as pool, \
                InferenceClient(server.address, telemetry=tel) as a, \
                InferenceClient(server.address, telemetry=tel) as b, \
                InferenceClient(server.address, telemetry=tel) as c:
            slow = pool.submit(a.generate, long_prompt, 40)
            while server.decode_batches < 2:  # `slow` is mid-decode
                assert not slow.done()
                time.sleep(0.01)
            # admitted beside it; retires first (5 tokens against 40)
            quick = pool.submit(
                b.generate,
                rs.randint(0, cfg.vocab_size, (1, 12)).astype(np.int32), 5)
            # the same first page as `slow`: rides its prefix, then sampled
            sampled = pool.submit(
                c.generate, long_prompt[:, :ps + 8], 9, temperature=0.8,
                seed=11)
            outs = [f.result(timeout=300) for f in (slow, quick, sampled)]
            assert [o.shape[1] for o in outs] == [ps + 80, 17, ps + 17]
            assert server.prefix_hits == 1
            # each alone, by the same path (cold; then on its prefix): what
            # the others did to the pools meanwhile changed no token
            server.release_prefix_cache()
            np.testing.assert_array_equal(
                a.generate(long_prompt, 40), outs[0])
            np.testing.assert_array_equal(
                c.generate(long_prompt[:, :ps + 8], 9, temperature=0.8,
                           seed=11), outs[2])
        assert server.prefix_hits == 2
        assert server.batched_requests == 5
    finally:
        server.stop()  # verify_pool_conservation("stop"), witness on
    assert server._pool_witness.checks >= 1
    assert server._pool_witness.trips == 0
    copies = _counts(tel, "serving_cache_copies_total")
    donations = _counts(tel, "serving_cache_donations_total")
    assert copies == {"decode": 0, "insert": 0, "spec": 0}
    assert donations == {"decode": server.decode_batches, "insert": 5,
                         "spec": 0}
    assert server.decode_batches >= 10


def test_spec_rounds_donate_both_pools(params):
    tel = Telemetry()
    server = InferenceServer(
        CFG, params, port=0, telemetry=tel,
        serving=ServingConfig(batch_window_s=0.0, decode_chunk=4,
                              kv_layout="paged", page_size=PS,
                              speculate_k=2, draft_model="self")).setup()
    prompt = np.random.RandomState(3).randint(0, 64, (1, 20)).astype(np.int32)
    try:
        with InferenceClient(server.address, telemetry=tel) as client:
            got = client.generate(prompt, n_tokens=12)
    finally:
        server.stop()
    np.testing.assert_array_equal(
        got, np.asarray(generate(CFG, params, jnp.asarray(prompt), 12)))
    assert _counts(tel, "serving_cache_copies_total") == {
        "decode": 0, "insert": 0, "spec": 0}
    # the target's scatter and the draft's; draft_k, verify, commit a round
    assert _counts(tel, "serving_cache_donations_total") == {
        "decode": 0, "insert": 2, "spec": 3 * server.decode_batches}


def test_a_copy_is_counted_and_warned_once(params):
    """What the second counter is for: a program that returns a new cache
    and leaves the one it was passed alive."""
    tel = Telemetry()
    server = InferenceServer(
        CFG, params, port=0, telemetry=tel,
        serving=ServingConfig(kv_layout="paged", page_size=PS))
    cache = paged_cache(CFG, params, SLOTS, PS, N_PAGES)
    with pytest.warns(RuntimeWarning, match="the decode program copied"):
        with server._donating("decode", cache):
            _copy(cache)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with server._donating("decode", cache):
            _copy(cache)
    assert _counts(tel, "serving_cache_copies_total")["decode"] == 2
    assert _counts(tel, "serving_cache_donations_total")["decode"] == 0


# -- (e) failed calls ----------------------------------------------------------


def _paged_server(params, tel=None):
    return InferenceServer(
        CFG, params, port=0, telemetry=tel,
        serving=ServingConfig(batch_window_s=0.0, decode_chunk=4,
                              kv_layout="paged", page_size=PS)).setup()


def _pools_alive(server):
    pools, _ = _split_pools(server._slot_cache)
    return not any(leaf.is_deleted() for leaf in jax.tree.leaves(pools))


def test_failed_prefill_leaves_the_pools_alive(params, monkeypatch):
    server = _paged_server(params)
    prompt = np.arange(1, 8, dtype=np.int32)[None]
    real = server_mod._build_prefill
    try:
        with InferenceClient(server.address) as client:
            want = client.generate(prompt, 6)  # allocates the cache
            cache_before = server._slot_cache

            def raising(config):
                def prefill(params, tokens):
                    raise RuntimeError("prefill refused")
                return prefill, real(config)[1]

            monkeypatch.setattr(server_mod, "_build_prefill", raising)
            with pytest.raises(Exception, match="server failed to handle"):
                client.generate(prompt + 1, 6)
            monkeypatch.setattr(server_mod, "_build_prefill", real)
            assert server._slot_cache is cache_before and _pools_alive(server)
            np.testing.assert_array_equal(client.generate(prompt, 6), want)
        assert server._pool.used_pages == len(server._prefix_map)
    finally:
        server.stop()


@pytest.mark.parametrize("program", ["insert", "decode"])
def test_pools_lost_to_a_failed_call_are_reallocated(
        params, monkeypatch, program):
    """A donating call that dies after the runtime took its buffers: the
    engine fails who was resident, forgets the prefix pages and serves the
    next request from a fresh cache."""
    server = _paged_server(params)
    prompt = np.arange(1, PS + 4, dtype=np.int32)[None]  # one full page
    state = {"armed": False}

    def dying(real):
        def call(*args):
            if not state["armed"]:
                return real(*args)
            state["armed"] = False
            cache = args[0 if program == "insert" else 1]
            for leaf in jax.tree.leaves(_split_pools(cache)[0]):
                leaf.delete()
            raise RuntimeError("device lost mid-call")
        return call

    if program == "insert":
        real_paged = server_mod._build_paged_fns
        monkeypatch.setattr(
            server_mod, "_build_paged_fns",
            lambda *a: (dying(real_paged(*a)[0]), real_paged(*a)[1]))
    else:
        real_slot = server_mod._build_slot_fns
        monkeypatch.setattr(
            server_mod, "_build_slot_fns",
            lambda *a: (*real_slot(*a)[:2], dying(real_slot(*a)[2])))
    try:
        with InferenceClient(server.address) as client:
            want = client.generate(prompt, 6)
            assert len(server._prefix_map) == 1
            state["armed"] = True
            with pytest.raises(Exception, match="server failed to handle"):
                client.generate(prompt, 6)
            assert server._slot_cache is None
            assert not server._prefix_map and server._pool.used_pages == 0
            assert (server._tables == server._n_pages).all()
            # a cold admission again (no prefix to ride), on a new cache
            np.testing.assert_array_equal(client.generate(prompt, 6), want)
            assert _pools_alive(server) and server.prefix_hits == 1
    finally:
        server.stop()
