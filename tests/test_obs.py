"""Unified telemetry: registry semantics, span export, and wire-level
trace propagation under chaos.

The design contract pinned here (see docs/OBSERVABILITY.md):

- disabled telemetry is ZERO-COST — every factory returns the one shared
  no-op handle, nothing is allocated per call site, the snapshot stays
  empty;
- enabled handles are cached by (name, labels) so hot paths pay one dict
  hit at construction and one attribute bump per event;
- trace ids ride the wire (UploadMsg/DownloadMsg headers) and survive
  retries, reconnects, and dedup — every applied update's server span
  links back to the client upload span that produced it;
- the continuous phase profiler (§5) keeps the same bargain: disabled ->
  shared no-ops within a pinned tight-loop budget; enabled -> rolling
  digests plus per-step wall/overlap/idle attribution;
- the health sentinel (§6) is edge-triggered: one counter increment and
  one flight bundle per breach ENTRY, never per check.
"""

import json
import os
import time

import numpy as np
import pytest

from distriflow_tpu.client.abstract_client import DistributedClientConfig
from distriflow_tpu.client.async_client import AsynchronousSGDClient
from distriflow_tpu.comm.transport import FaultPlan, ScriptedFault
from distriflow_tpu.data.dataset import DistributedDataset
from distriflow_tpu.obs import (
    NOOP_HANDLE,
    NOOP_SPAN,
    Telemetry,
    render_prometheus,
)
from distriflow_tpu.obs.jax_hooks import install_jax_hooks
from distriflow_tpu.obs.tracing import SPANS_FILENAME
from distriflow_tpu.server.abstract_server import DistributedServerConfig
from distriflow_tpu.server.async_server import AsynchronousSGDServer
from distriflow_tpu.server.models import DistributedServerInMemoryModel
from distriflow_tpu.utils.config import RetryPolicy
from tests.mock_model import MockModel

pytestmark = pytest.mark.obs


# -- registry ---------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    t = Telemetry()
    c = t.counter("reqs_total", role="client")
    c.inc()
    c.inc(2)
    assert c.value == 3
    assert t.counter_value("reqs_total", role="client") == 3
    assert t.counter_value("reqs_total", role="server") == 0  # unregistered
    t.counter("reqs_total", role="server").inc(5)
    assert t.total("reqs_total") == 8  # sums across label sets

    g = t.gauge("clients")
    g.set(4)
    g.dec()
    assert g.value == 3

    h = t.histogram("lat_ms")
    for v in range(1, 101):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 1 and s["max"] == 100
    # nearest-rank over the 0-based sorted window: data[round(q*(n-1))]
    assert s["p50"] == 51 and s["p95"] == 95 and s["p99"] == 99


def test_histogram_window_bounds_memory():
    t = Telemetry(histogram_window=8)
    h = t.histogram("w")
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100  # exact count/sum survive the window
    assert s["p50"] >= 92  # percentiles come from the last 8 samples


def test_snapshot_and_prometheus_render():
    t = Telemetry()
    t.counter("frames_total", role="client").inc(7)
    t.gauge("version").set(3)
    t.histogram("ms").observe(1.5)
    snap = t.snapshot()
    assert snap["counters"]['frames_total{role=client}'] == 7
    assert snap["gauges"]["version"] == 3
    assert snap["histograms"]["ms"]["count"] == 1
    text = t.prometheus()
    assert 'frames_total{role="client"} 7' in text
    assert "# TYPE frames_total counter" in text
    assert 'ms{quantile="0.5"}' in text
    assert render_prometheus(t.registry) == text


def test_disabled_telemetry_is_shared_noop():
    """The tier-1 cheapness contract: disabled telemetry allocates NOTHING
    per call site — every factory returns the module singletons, the
    registry stays empty, spans are the shared no-op."""
    t = Telemetry(enabled=False)
    assert t.counter("a") is NOOP_HANDLE
    assert t.counter("b", role="x") is NOOP_HANDLE
    assert t.gauge("c") is NOOP_HANDLE
    assert t.histogram("d") is NOOP_HANDLE
    NOOP_HANDLE.inc()
    NOOP_HANDLE.set(3)
    NOOP_HANDLE.observe(1.0)  # all no-ops, no state
    assert t.registry._metrics == {}  # nothing registered
    assert t.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    with t.span("upload", client_id="c1") as span:
        span.set(attempts=1)
    assert span is NOOP_SPAN and span.trace_id == ""
    assert t.tracer.finished() == []
    assert t.export_snapshot() is None


def test_enabled_handles_are_cached_identities():
    t = Telemetry()
    assert t.counter("x") is t.counter("x")
    assert t.counter("x", role="a") is t.counter("x", role="a")
    assert t.counter("x", role="a") is not t.counter("x", role="b")
    assert t.histogram("h") is t.histogram("h")


# -- tracing ----------------------------------------------------------------


def test_span_linkage_and_error_status():
    t = Telemetry()
    with t.span("upload", client_id="c1") as up:
        pass
    with t.span("apply", trace_id=up.trace_id, parent_id=up.span_id) as ap:
        ap.set(accepted=True)
    rows = t.tracer.finished()
    assert [r["name"] for r in rows] == ["upload", "apply"]
    assert rows[1]["trace_id"] == rows[0]["trace_id"]
    assert rows[1]["parent_id"] == rows[0]["span_id"]
    assert t.tracer.traces()[up.trace_id] == rows
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    assert t.tracer.finished("boom")[0]["status"] == "error:RuntimeError"


def test_spans_export_jsonl(tmp_path):
    t = Telemetry(save_dir=str(tmp_path))
    with t.span("upload"):
        pass
    path = tmp_path / SPANS_FILENAME
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert rows and rows[0]["name"] == "upload"
    assert rows[0]["trace_id"] and rows[0]["span_id"]
    t.counter("n").inc()
    row = t.export_snapshot(step=3)
    assert row["counter:n"] == 1 and row["step"] == 3
    metrics = (tmp_path / "metrics.jsonl").read_text()
    assert "telemetry_snapshot" in metrics


def test_dump_cli_renders_and_exits_zero(tmp_path, capsys):
    from distriflow_tpu.obs import dump

    t = Telemetry(save_dir=str(tmp_path))
    t.counter("transport_frames_sent_total", role="client").inc(4)
    with t.span("upload", client_id="c1") as up:
        up.set(reconnects_spanned=1)
    with t.span("apply", trace_id=up.trace_id, parent_id=up.span_id):
        pass
    t.export_snapshot()
    assert dump.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "transport_frames_sent_total" in out
    assert "upload" in out
    assert dump.main([str(tmp_path / "empty")]) == 2


# -- trace propagation under chaos (the satellite acceptance test) ----------


@pytest.mark.chaos
def test_trace_propagation_under_chaos(tmp_path):
    """Loopback async-SGD under drops + a scripted mid-upload reset + a
    dropped ack (forcing a deduped retry), with ONE Telemetry shared by
    both endpoints. Every applied update's server apply span must link to
    a client upload span with the same trace_id; the dedup'd duplicate
    must share its original's trace; at least one upload trace spans the
    reconnect."""
    x = np.arange(8, dtype=np.float32).reshape(8, 1)
    y = np.eye(2, dtype=np.float32)[np.arange(8) % 2]
    dataset = DistributedDataset(x, y, {"batch_size": 2, "epochs": 1})
    tel = Telemetry()
    server_plan = FaultPlan(
        seed=5, duplicate=0.1,
        # drop the first ack: the client MUST retry that update and the
        # server MUST dedup it — the shared-trace-through-dedup case
        schedule=[ScriptedFault(event="__ack__", nth=1, action="drop")],
    )
    client_plan = FaultPlan(
        seed=3, drop=0.1, duplicate=0.1,
        schedule=[ScriptedFault(event="uploadVars", nth=2, action="reset")],
    )
    server = AsynchronousSGDServer(
        DistributedServerInMemoryModel(MockModel()),
        dataset,
        DistributedServerConfig(
            save_dir=str(tmp_path / "m"),
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=2.0,
            fault_plan=server_plan,
            telemetry=tel,
        ),
    )
    server.setup()
    applied = []
    server.on_upload(lambda m: applied.append(m.update_id))
    client = AsynchronousSGDClient(
        server.address,
        MockModel(),
        DistributedClientConfig(
            heartbeat_interval_s=0.1,
            heartbeat_timeout_s=2.0,
            upload_timeout_s=0.5,
            upload_retry=RetryPolicy(max_retries=8, initial_backoff_s=0.05,
                                     max_backoff_s=0.5, seed=3),
            fault_plan=client_plan,
            telemetry=tel,
        ),
    )
    try:
        client.setup(timeout=10.0)
        done = client.train_until_complete(timeout=120.0)
        # the ack-dropped upload retries in background; wait for its dedup
        # AND for every apply's parent upload span to finish (client spans
        # close on the retry's ack, a beat after the server-side counters)
        def _quiesced():
            if server.duplicate_uploads < 1:
                return False
            span_ids = {s["span_id"] for s in tel.tracer.finished("upload")}
            done = [s for s in tel.tracer.finished("apply")
                    if not s.get("dedup")]
            return len(done) >= 4 and all(
                a["parent_id"] in span_ids for a in done)

        deadline = time.monotonic() + 30.0
        while not _quiesced() and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        client.dispose()
        server.stop()
    assert done == 4 and server.applied_updates == 4
    assert len(applied) == len(set(applied)) == 4
    assert server.duplicate_uploads >= 1, "dropped ack's retry never deduped"
    assert client.reconnects >= 1, "scripted reset never forced a reconnect"

    uploads = tel.tracer.finished("upload")
    by_span_id = {s["span_id"]: s for s in uploads}
    upload_tids = {s["trace_id"] for s in uploads}
    applies = [s for s in tel.tracer.finished("apply") if not s.get("dedup")]
    assert len(applies) == 4, "one apply span per applied update"
    for a in applies:
        parent = by_span_id.get(a["parent_id"])
        assert parent is not None, f"apply {a} has no upload parent span"
        assert a["trace_id"] == parent["trace_id"]
    # the deduped duplicate shares the ORIGINAL upload's trace (retries
    # resend the same wire bytes, trace header included)
    dedups = [s for s in tel.tracer.finished("apply") if s.get("dedup")]
    assert dedups, "the deduped retry must still emit a (dedup) apply span"
    apply_tids = {a["trace_id"] for a in applies}
    for d in dedups:
        assert d["trace_id"] in apply_tids, "dedup span lost its trace"
    # the scripted reset tore the connection mid-upload: that upload's
    # span must record that it survived a reconnect
    spanning = [s for s in uploads if s.get("reconnects_spanned", 0) > 0]
    assert spanning, "no upload span recorded reconnects_spanned > 0"
    assert upload_tids >= apply_tids
    # and the transport counters reconcile with the fault plans exactly
    for role, plan in (("client", client_plan), ("server", server_plan)):
        assert tel.counter_value(
            "transport_frames_dropped_total", role=role
        ) == plan.injected.get("drop", 0)
        assert tel.counter_value(
            "transport_resets_total", role=role
        ) == plan.injected.get("reset", 0)
        assert tel.counter_value(
            "transport_frames_offered_total", role=role
        ) == sum(plan.seen().values())


# -- continuous phase profiler (docs/OBSERVABILITY.md §5) -------------------


def test_profiler_digests_and_step_attribution():
    from distriflow_tpu.obs.profiler import STEP_IDLE, STEP_OVERLAP, STEP_WALL

    t = Telemetry()
    prof = t.profiler("client")
    assert prof is t.profiler("client")  # cached per role
    assert prof is not t.profiler("server")

    with prof.step():
        with prof.phase("fit"):
            time.sleep(0.002)
        with prof.phase("submit"):
            # nested phase: gets its own digest but must NOT double-count
            # in step busy (outermost-only attribution)
            with prof.phase("ack_wait"):
                time.sleep(0.001)
    d = prof.digests()
    assert set(d) >= {"fit", "submit", "ack_wait"}
    assert d["fit"]["count"] == 1 and d["fit"]["p50"] >= 1.0
    sd = prof.step_digest()
    assert sd["wall"]["count"] == 1
    wall = sd["wall"]["sum"]
    # busy == fit + submit (ack_wait folded into submit): overlap ~ 0
    assert sd["overlap"]["sum"] < 0.5 * wall
    # everything flows through the one registry -> snapshot/prometheus free
    snap = t.snapshot()
    assert "phase_ms{phase=fit,role=client}" in snap["histograms"]
    assert f"{STEP_WALL}{{role=client}}" in snap["histograms"]
    assert f"{STEP_OVERLAP}{{role=client}}" in snap["histograms"]
    assert f"{STEP_IDLE}{{role=client}}" in snap["histograms"]


def test_profiler_record_books_async_overlap():
    """record() is the dispatch-time path (async trainer): booked busy can
    exceed the step's wall, and the digest must attribute it as overlap."""
    t = Telemetry()
    prof = t.profiler("trainer")
    with prof.step():
        prof.record("fit", 100.0)  # 100 ms of booked work, ~0 ms of wall
    sd = prof.step_digest()
    assert sd["overlap"]["sum"] > 80.0
    assert sd["idle"]["sum"] < 20.0
    assert prof.digests()["fit"]["count"] == 1


def test_profiler_idle_attribution():
    t = Telemetry()
    prof = t.profiler("trainer")
    with prof.step():
        time.sleep(0.005)  # wall with no booked phase -> pure idle
    sd = prof.step_digest()
    assert sd["idle"]["sum"] >= 3.0
    assert sd["overlap"]["sum"] < 1.0


def test_profiler_disabled_is_shared_noop_and_cheap():
    from distriflow_tpu.obs import NOOP_FLIGHT, NOOP_PHASE, NOOP_PROFILER

    t = Telemetry(enabled=False)
    prof = t.profiler("client")
    assert prof is NOOP_PROFILER
    assert prof.phase("fit") is NOOP_PHASE
    assert prof.step() is NOOP_PHASE or prof.step() is not None  # no-op ctx
    assert t.flight is NOOP_FLIGHT
    t.register_fleet("k", dict)  # must not leak into the snapshot
    assert t.registry._metrics == {}
    assert t.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}
    # the pinned overhead budget: the disabled hot path is two context
    # managers over shared singletons — 100k step+phase rounds must stay
    # comfortably inside 1 s even on a loaded CI box
    t0 = time.perf_counter()
    for _ in range(100_000):
        with prof.step():
            with prof.phase("fit"):
                pass
    assert time.perf_counter() - t0 < 1.0


# -- fleet health table (docs/OBSERVABILITY.md §6) --------------------------


def test_fleet_table_rows_and_snapshot_merge():
    from distriflow_tpu.obs import FleetTable

    t = Telemetry()
    fleet = FleetTable()
    t.register_fleet("srv", fleet.snapshot)
    fleet.connect("c1")
    fleet.note_download("c1", 100)
    fleet.note_upload("c1", 40)
    fleet.note_staleness("c1", 2)
    fleet.note_quarantine("c1")
    snap = t.snapshot()
    row = snap["fleet"]["c1"]
    assert row["connected"] and row["uploads"] == 1
    assert row["up_bytes"] == 40 and row["down_bytes"] == 100
    assert row["staleness"] == 2 and row["quarantine_hits"] == 1
    assert row["round_ms"] is not None  # download -> upload latency
    assert not any(k.startswith("_") for k in row)  # internals stripped
    fleet.disconnect("c1")
    assert not t.snapshot()["fleet"]["c1"]["connected"]
    t.unregister_fleet("srv")
    assert "fleet" not in t.snapshot()


def test_fleet_table_evicts_longest_gone_disconnected():
    from distriflow_tpu.obs import FleetTable

    fleet = FleetTable(capacity=2)
    fleet.connect("a")
    fleet.disconnect("a")
    fleet.connect("b")
    fleet.disconnect("b")
    fleet.connect("c")  # at capacity: evicts "a" (longest gone)
    rows = fleet.snapshot()
    assert set(rows) == {"b", "c"}


# -- health sentinel (docs/OBSERVABILITY.md §6) -----------------------------


def test_health_sentinel_edge_trigger_and_bundle(tmp_path):
    from distriflow_tpu.obs.flight_recorder import read_bundles
    from distriflow_tpu.obs.health import HealthSentinel, default_bands

    t = Telemetry()
    h = t.histogram("transport_ack_latency_ms", role="client")
    watch = HealthSentinel(
        t, bands=default_bands(ack_p99_ms=250.0, mfu_floor=0.05),
        dump_dir=str(tmp_path))
    # unknown metric (train_mfu never set) must not breach
    assert watch.check() == []
    for _ in range(20):
        h.observe(500.0)
    entered = watch.check()
    assert [e["band"] for e in entered] == ["ack_latency_p99"]
    assert entered[0]["observed"] == 500.0
    assert watch.check() == []  # still in breach: edge-triggered
    assert t.counter_value("obs_slo_breach_total",
                           band="ack_latency_p99") == 1
    assert watch.breached() == ["ack_latency_p99"]
    bundles = read_bundles(str(tmp_path))
    assert len(bundles) == 1
    assert bundles[0]["trigger"] == "slo_ack_latency_p99"
    assert any(e["kind"] == "slo_breach" for e in bundles[0]["events"])
    # recovery then relapse re-fires (window pushes p99 back under)
    for _ in range(2000):
        h.observe(1.0)
    assert watch.check() == [] and watch.breached() == []
    for _ in range(2000):
        h.observe(500.0)
    assert [e["band"] for e in watch.check()] == ["ack_latency_p99"]
    assert t.counter_value("obs_slo_breach_total",
                           band="ack_latency_p99") == 2


def test_health_sentinel_min_count_gate():
    from distriflow_tpu.obs.health import HealthSentinel, SLOBand

    t = Telemetry()
    t.histogram("lat", role="x").observe(999.0)
    band = SLOBand("lat_p99", "lat", "p99", {"role": "x"},
                   upper=10.0, min_count=5)
    watch = HealthSentinel(t, bands=[band])
    assert watch.check() == []  # 1 sample < min_count: not judged
    for _ in range(5):
        t.histogram("lat", role="x").observe(999.0)
    assert [e["band"] for e in watch.check()] == ["lat_p99"]


# -- dump --watch -----------------------------------------------------------


def test_dump_watch_smoke(tmp_path, capsys):
    from distriflow_tpu.obs import dump

    t = Telemetry(save_dir=str(tmp_path))
    t.counter("frames_total").inc(3)
    t.export_snapshot()
    assert dump.main([str(tmp_path), "--watch", "--iterations", "2",
                      "--interval", "0.01"]) == 0
    out = capsys.readouterr().out
    assert "watch[1]" in out and "frames_total=3" in out
    assert "watch[2]" in out and "no change" in out
    empty = tmp_path / "empty"
    empty.mkdir()
    assert dump.main([str(empty), "--watch", "--iterations", "1"]) == 2


# -- dump --critical-path, malformed lines ----------------------------------


def _span_row(name, t0, dur_ms, **attrs):
    return {"name": name, "trace_id": "f" * 32, "span_id": f"s-{name}",
            "start": t0 + 500.0, "mono": t0, "pid": 1, "dur_ms": dur_ms,
            "status": "ok", **attrs}


def test_dump_critical_path_cli(tmp_path, capsys):
    from distriflow_tpu.obs import dump

    rows = [
        _span_row("upload", 0.0, 80.0, update_id="u1", serialize_ms=5.0),
        _span_row("apply", 0.05, 10.0, update_id="u1", accepted=True),
    ]
    spans = tmp_path / SPANS_FILENAME
    spans.write_text("".join(json.dumps(r) + "\n" for r in rows)
                     + "{torn\n")
    rc = dump.main([str(tmp_path), "--critical-path"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "1 applied" in out and "bound_by=submit" in out
    assert "1 malformed jsonl line(s) skipped" in out
    # no spans file: distinct exit code, no traceback
    rc = dump.main([str(tmp_path / "empty"), "--critical-path"])
    assert rc == 2


def test_dump_counts_malformed_metric_lines(tmp_path, capsys):
    from distriflow_tpu.obs import dump

    (tmp_path / "metrics.jsonl").write_text(
        json.dumps({"time": 1.0, "loss": 2.0}) + "\n{half a row\n")
    (tmp_path / SPANS_FILENAME).write_text(
        json.dumps(_span_row("upload", 0.0, 5.0)) + "\nnot json at all\n")
    assert dump.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert out.count("1 malformed line(s) skipped") == 2


# -- jax runtime hooks ------------------------------------------------------


def test_register_sampler_runs_at_snapshot():
    tel = Telemetry()
    calls = []
    tel.register_sampler(lambda: calls.append(1))

    def bad():
        raise RuntimeError("sampler must never break a snapshot")

    tel.register_sampler(bad)
    snap = tel.snapshot()
    assert calls == [1] and isinstance(snap, dict)
    tel.snapshot()
    assert calls == [1, 1]


def test_jax_hooks_count_recompiles_not_cache_hits():
    import jax
    import jax.numpy as jnp

    tel = Telemetry()
    assert install_jax_hooks(tel) is True
    assert install_jax_hooks(tel) is True  # idempotent per telemetry

    @jax.jit
    def f(a):
        return a * 2.0 + 1.0

    f(jnp.ones((3, 5))).block_until_ready()
    after_compile = tel.counter_value("jit_recompiles_total")
    assert after_compile >= 1, "backend compile did not bump the counter"
    # steady state: the executable cache serves the same shape — flat
    f(jnp.ones((3, 5))).block_until_ready()
    assert tel.counter_value("jit_recompiles_total") == after_compile
    # shape churn recompiles
    f(jnp.ones((4, 5))).block_until_ready()
    assert tel.counter_value("jit_recompiles_total") > after_compile
    # the memory sampler is wired into snapshot() and must tolerate CPU
    # backends reporting no stats (gauge simply absent there)
    snap = tel.snapshot()
    assert isinstance(snap, dict)


def test_install_without_telemetry_uses_global(monkeypatch):
    # disabled telemetry: nothing to install into, still no crash
    tel = Telemetry(enabled=False)
    assert install_jax_hooks(tel) in (True, False)
