"""The program's hooks on the profiler's clock (PR 25; docs/OBSERVABILITY.md
§5, §11).

Pins, in order: a traced toy engine leaves ``df/engine/<phase>`` events on
the host plane of the profiler's trace, nested as the code nests them, the
``decode_iter`` one carrying the live rows and their context; a disabled
``Telemetry`` hands out the shared no-op phase and opens no annotation; the
transport emits one ``handler_wait`` span per traced frame, in the request's
trace and the tracer's row schema, and the wait grows once blocked handlers
fill the pool; the train step's scopes are in its HLO and change nothing it
computes; ``ops/flash_decode.py`` records the cost the benchmark's own
arithmetic gives.
"""

import concurrent.futures
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distriflow_tpu import (
    TRANSFORMER_TP_RULES,
    SyncTrainer,
    data_parallel_mesh,
)
from distriflow_tpu.client import InferenceClient
from distriflow_tpu.comm.transport import ClientTransport, ServerTransport
from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
from distriflow_tpu.obs import profiler as profiler_mod
from distriflow_tpu.obs.profiler import NOOP_PHASE, NOOP_PROFILER
from distriflow_tpu.obs.telemetry import Telemetry
from distriflow_tpu.ops.flash_decode import flash_decode, flash_decode_paged
from distriflow_tpu.ops.flop_count import pallas_cost_of
from distriflow_tpu.server import InferenceServer
from distriflow_tpu.utils.config import ServingConfig

from test_trace_assembler import GOLDEN_KEYS

pytestmark = pytest.mark.obs

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.float32, use_flash_attention=False,
)


# -- engine phases in the profiler's trace -----------------------------------


def _host_events(trace_dir, prefix):
    """{name: [(start_ns, end_ns, stats)]} of the host planes' events whose
    name starts with ``prefix``, read back with ``ProfileData``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = int(ev.start_ns)
                    out.setdefault(ev.name[len(prefix):], []).append(
                        (start, start + int(ev.duration_ns), dict(ev.stats)))
    return out


def _inside(events, inner, outer):
    return all(any(o[0] <= i[0] and i[1] <= o[1] for o in events[outer])
               for i in events[inner])


def test_engine_phases_land_on_the_profilers_host_plane(tmp_path):
    params = transformer_lm(CFG, example_seq=16).init(jax.random.PRNGKey(0))
    tel = Telemetry()
    server = InferenceServer(
        CFG, params, port=0, telemetry=tel,
        serving=ServingConfig(batch_window_s=0.01, decode_chunk=2,
                              max_slots=2, kv_layout="paged", page_size=16,
                              page_pool_pages=8)).setup()
    prompt = np.arange(1, 7, dtype=np.int32)[None]
    try:
        with InferenceClient(server.address, telemetry=tel) as client:
            client.generate(prompt, 3)  # compiles, outside the trace
            jax.profiler.start_trace(str(tmp_path))
            try:
                # two requests: the engine's wait between them is the one
                # ``gather`` that opens and closes inside the trace
                client.generate(prompt + 1, 5)
                client.generate(prompt + 1, 5)
            finally:
                jax.profiler.stop_trace()
    finally:
        server.stop()
    events = _host_events(str(tmp_path), "df/engine/")
    assert {"gather", "admission", "prefill", "page_insert",
            "first_token_fetch", "decode_iter", "decode_dispatch",
            "token_fetch", "emit", "retire"} <= set(events)
    # 5 tokens a request: 1 with the prefill + two chunks of 2
    assert len(events["decode_iter"]) == 4
    for inner, outer in (("prefill", "admission"), ("page_insert", "prefill"),
                         ("first_token_fetch", "prefill"),
                         ("decode_dispatch", "decode_iter"),
                         ("token_fetch", "decode_iter"),
                         ("emit", "decode_iter"), ("retire", "emit")):
        assert _inside(events, inner, outer), (inner, outer)
    # gather is the engine without work: no other phase is open inside it
    for name, rows in events.items():
        if name not in ("gather", "batch_window"):
            assert not _inside({"a": rows, "b": events["gather"]}, "a", "b"), name
    # the dispatch's live rows and their cached context ride the event:
    # prompt 6 + the first token, then two more
    stats = [s for _, _, s in sorted(events["decode_iter"])]
    assert [(s["n_active"], s["ctx_tokens"]) for s in stats] == [
        (1, 7), (1, 9)] * 2
    # and the pages that context fills, against the 2 slots x 3 pages the
    # paged decode kernel's grid spans
    assert [(s["live_pages"], s["table_pages"]) for s in stats] == [(1, 6)] * 4
    # the server's transport shares its telemetry: each generate frame left
    # one handler_wait row in its request's trace
    waits = tel.tracer.finished("handler_wait")
    assert [w["event"] for w in waits] == ["generate"] * 3
    assert {w["trace_id"] for w in waits} == {
        r["trace_id"] for r in tel.tracer.finished("request")}
    # and the request spans carry the dispatch's two parts, not the old share
    spans = tel.tracer.finished("decode_iter")
    assert spans and all(
        "share" not in s and s["dispatch_ms"] >= 0 and s["fetch_ms"] >= 0
        and s["dispatch_ms"] + s["fetch_ms"] <= s["dur_ms"] + 1e-3
        for s in spans)


@pytest.mark.parametrize("profiling", [True, False])
def test_decode_iter_stats_count_live_pages_only_while_profiling(profiling):
    """``live_pages`` / ``table_pages`` say how much of the paged decode
    kernel's grid is live. They are host arithmetic inside the engine's
    ``if self._prof.enabled`` block: with profiling off the phase opens with
    no stats at all."""
    params = transformer_lm(CFG, example_seq=16).init(jax.random.PRNGKey(0))
    server = InferenceServer(
        CFG, params, port=0, telemetry=Telemetry(enabled=profiling),
        serving=ServingConfig(batch_window_s=0.01, decode_chunk=2,
                              max_slots=2, kv_layout="paged", page_size=16,
                              page_pool_pages=8)).setup()
    prof, seen = server._prof, []
    assert prof.enabled is profiling

    class Spy:
        enabled = prof.enabled

        def phase(self, name, **stats):
            if name == "decode_iter":
                seen.append(stats)
            return prof.phase(name, **stats)

        def __getattr__(self, name):
            return getattr(prof, name)

    server._prof = Spy()
    try:
        with InferenceClient(server.address) as client:
            # 15 prompt tokens: the context crosses a page edge (16) between
            # the first chunk of 2 and the second
            client.generate(np.arange(1, 16, dtype=np.int32)[None], 5)
    finally:
        server.stop()
    if profiling:
        assert seen == [
            {"n_active": 1, "ctx_tokens": 16, "live_pages": 1, "table_pages": 6},
            {"n_active": 1, "ctx_tokens": 18, "live_pages": 2, "table_pages": 6}]
    else:
        assert seen == [{}, {}]


def test_disabled_telemetry_opens_no_annotation(monkeypatch):
    opened = []

    class Counting:
        def __init__(self, name, **stats):
            opened.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(profiler_mod, "TraceAnnotation", Counting)
    off = Telemetry(enabled=False).profiler("engine")
    assert off is NOOP_PROFILER and not off.enabled
    assert off.phase("decode_iter", n_active=3) is NOOP_PHASE
    assert off.step() is NOOP_PHASE
    with off.phase("gather"), off.step():
        pass
    assert opened == []
    on = Telemetry().profiler("engine")
    assert on.enabled
    with on.step():
        with on.phase("decode_iter", n_active=3):
            pass
    assert opened == [("df/engine/step", {}),
                      ("df/engine/decode_iter", {"n_active": 3})]
    assert on.digests()["decode_iter"]["count"] == 1


# -- handler_wait: the span before the enqueue stamp -------------------------


def test_handler_wait_span_per_frame_and_grows_when_the_pool_is_full():
    tel = Telemetry()
    release = threading.Event()
    server = ServerTransport(port=0, telemetry=tel)
    server.on("echo", lambda cid, p: {"x": p["x"]})
    server.on("block", lambda cid, p: release.wait(30.0) and None)
    server.start()
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=2)
    server._loop.call_soon_threadsafe(server._loop.set_default_executor, pool)
    clients = [ClientTransport(server.address, telemetry=tel).connect()
               for _ in range(3)]
    try:
        ack = clients[0].request(
            "echo", {"x": 1, "trace_id": "t" * 32, "span_id": "s" * 16,
                     "request_id": "r-1"})
        assert ack == {"x": 1}
        clients[0].request("echo", {"x": 2})  # no trace id: no row
        rows = tel.tracer.finished("handler_wait")
        assert len(rows) == 1
        row = rows[0]
        assert GOLDEN_KEYS <= set(row)
        assert (row["trace_id"], row["parent_id"]) == ("t" * 32, "s" * 16)
        assert (row["event"], row["request_id"], row["tier"]) == ("echo", "r-1", 0)
        assert 0.0 <= row["dur_ms"] < 1000.0 and row["status"] == "ok"
        hist = "transport_handler_wait_ms{event=echo}"
        assert tel.snapshot()["histograms"][hist]["count"] == 2

        # two blocked handlers fill the pool of two: the third frame waits
        # for a thread, and only handler_wait sees that wait
        threads = [threading.Thread(
            target=c.request, args=("block", {"trace_id": f"{i}" * 32}, 30.0))
            for i, c in enumerate(clients)]
        for t in threads[:2]:
            t.start()
        deadline = time.monotonic() + 10.0
        while (tel.snapshot()["gauges"]["transport_handlers_busy"] < 2
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert tel.snapshot()["gauges"]["transport_handlers_busy"] == 2
        threads[2].start()
        time.sleep(0.4)
        release.set()
        for t in threads:
            t.join(timeout=30.0)
        waits = {r["trace_id"][0]: r["dur_ms"]
                 for r in tel.tracer.finished("handler_wait")
                 if r["event"] == "block"}
        assert set(waits) == {"0", "1", "2"}
        assert waits["2"] >= 300.0 and max(waits["0"], waits["1"]) < 300.0
        assert tel.snapshot()["gauges"]["transport_handlers_busy"] == 0
    finally:
        release.set()
        for c in clients:
            c.close()
        server.stop()
        pool.shutdown(wait=False)


def test_handler_wait_costs_nothing_with_telemetry_off():
    tel = Telemetry(enabled=False)
    server = ServerTransport(port=0, telemetry=tel)
    server.on("echo", lambda cid, p: p)
    server.start()
    client = ClientTransport(server.address, telemetry=tel).connect()
    try:
        assert client.request("echo", {"trace_id": "t" * 32}) == {
            "trace_id": "t" * 32}
        assert tel.tracer.finished() == [] and server._h_wait == {}
    finally:
        client.close()
        server.stop()


# -- the train step's scopes ---------------------------------------------------


def _toy_trainer():
    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=32, dtype=jnp.bfloat16)
    mesh = data_parallel_mesh(jax.devices()[:1])
    spec = transformer_lm(cfg, mesh=mesh, example_seq=32)
    trainer = SyncTrainer(spec, mesh=mesh, learning_rate=1e-3,
                          optimizer="adam", param_rules=TRANSFORMER_TP_RULES)
    trainer.init(jax.random.PRNGKey(7))
    return trainer, spec, mesh


def test_step_scopes_are_in_the_hlo_and_change_no_number():
    import re

    trainer, spec, mesh = _toy_trainer()
    rng = np.random.default_rng(0)
    batches = [tuple(rng.integers(0, 128, size=(2, 32)).astype(np.int32)
                     for _ in range(2)) for _ in range(3)]
    text = trainer.lower_step(batches[0]).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any("jvp(forward)" in n and "transpose(" not in n for n in names)
    assert any("transpose(jvp(forward))" in n for n in names)
    assert any("/optimizer/" in n for n in names)
    assert "jit_train_step" in text

    # the parent's step, written out without a scope: the same three losses,
    # bit for bit, from the same state and batches
    optimizer = trainer.optimizer
    params = jax.tree.map(jnp.copy, trainer.state.params)
    opt_state = jax.tree.map(jnp.copy, trainer.state.opt_state)

    @jax.jit
    def plain_step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(spec.loss_fn)(params, x, y, None)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    want = []
    with jax.set_mesh(mesh):
        for x, y in batches:
            params, opt_state, loss = plain_step(params, opt_state, x, y)
            want.append(float(loss))
    got = [trainer.step(batch) for batch in batches]
    assert got == want
    assert trainer._steps_dispatched == 3


# -- the decode kernels' cost record -------------------------------------------


@pytest.mark.parametrize("b,h,d,s", [(2, 4, 32, 64), (3, 2, 64, 128)])
def test_flash_decode_records_what_the_benchmark_counts(b, h, d, s):
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.lib import flops

    q = jnp.zeros((b, h, d), jnp.bfloat16)
    kv = jnp.zeros((b, s, h * d), jnp.bfloat16)
    jax.clear_caches()
    got = pallas_cost_of(
        lambda q, kv: flash_decode(q, kv, kv, jnp.int32(s), interpret=True),
        q, kv)
    want = flops.flash_decode(b * s, h * d, 2)
    assert (got["flops"], got["bytes_accessed"]) == (want["flops"], want["bytes"])
    assert set(got["by_category"]) == {"attention_decode"}

    # paged: the grid visits every page of every row's table
    ps, pp, n_pages = 16, s // 16, 2 * b * s // 16
    pool = jnp.zeros((n_pages, ps, h * d), jnp.bfloat16)
    table = jnp.zeros((b, pp), jnp.int32)
    jax.clear_caches()
    got = pallas_cost_of(
        lambda q, pool: flash_decode_paged(
            q, pool, pool, table, jnp.full((b,), s, jnp.int32),
            interpret=True), q, pool)
    want = flops.flash_decode(b * pp * ps, h * d, 2)
    assert (got["flops"], got["bytes_accessed"]) == (want["flops"], want["bytes"])
