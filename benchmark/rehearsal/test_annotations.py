"""Unit checks of ``lib/annotations.py`` and the readers built on it (PR 25),
on synthetic traces in ``test_benchmark.py``'s fixture style.

    JAX_PLATFORMS=cpu python -m pytest benchmark/rehearsal -q -p no:cacheprovider

The protobuf wire reader was checked by hand against a real trace of
``train-1chip-s2048`` (PERF.md, PR 25); here it reads messages this file
encodes itself, field numbers from the two ``.proto`` files it names.
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import annotations, harness, xplane  # noqa: E402

US = 1_000


def ev(name, start, dur, **stats_):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats_.items()))


# -- idle gaps under the engine's phases ----------------------------------------


def serving_trace():
    """1,000 us of one device. Gaps: [100,200] under decode_dispatch (inside
    decode_iter), [300,450] under gather, [550,600] under nothing, [700,710]
    under token_fetch but shorter than MIN_GAP_NS, [800,900] half under
    admission."""
    ops = [ev("%fusion.1 = f32[2]{0} fusion()", 0, 100 * US),
           ev("%fusion.2 = f32[2]{0} fusion()", 200 * US, 100 * US),
           ev("%fusion.3 = f32[2]{0} fusion()", 450 * US, 100 * US),
           ev("%fusion.4 = f32[2]{0} fusion()", 600 * US, 100 * US),
           ev("%fusion.5 = f32[2]{0} fusion()", 710 * US, 90 * US),
           ev("%fusion.6 = f32[2]{0} fusion()", 900 * US, 100 * US)]
    engine = [
        ev("df/engine/decode_iter", 90 * US, 200 * US, n_active=3, ctx_tokens=700),
        ev("df/engine/decode_dispatch", 95 * US, 110 * US),
        ev("df/engine/token_fetch", 205 * US, 80 * US),
        ev("df/engine/gather", 295 * US, 160 * US),
        ev("df/engine/decode_iter", 600 * US, 150 * US, n_active=2, ctx_tokens=100),
        ev("df/engine/token_fetch", 690 * US, 30 * US),
        ev("df/engine/admission", 850 * US, 100 * US),
        ev("df/client/fit", 540 * US, 70 * US),   # another role: not the engine
        ev("PjitFunction(decode)", 96 * US, 100 * US)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="engine", events=engine)])])


def test_idle_time_splits_by_phase():
    profile = serving_trace()
    split = annotations.idle_by_phase(profile, xplane.device_ops(profile))
    assert split["window"] == pytest.approx(1000e-6)
    assert split["idle"] == pytest.approx(400e-6)      # the 10 us hole is no gap
    assert split["scheduler"] == pytest.approx(150e-6)  # 100 dispatch + 50 admission
    assert split["gather"] == pytest.approx(150e-6)
    assert split["unannotated"] == pytest.approx(100e-6)  # 50 + the other 50 of [800,900]
    assert split["df/engine/decode_iter"] == pytest.approx(100e-6)
    assert split["df/engine/decode_dispatch"] == pytest.approx(100e-6)
    assert split["df/engine/token_fetch"] == 0.0
    assert split["df/engine/admission"] == pytest.approx(50e-6)
    assert "df/client/fit" not in split
    # a program without the annotations: nothing to read
    bare = NS(planes=[profile.planes[0], NS(name="/host:CPU", lines=[
        NS(name="engine", events=[ev("PjitFunction(decode)", 0, 5)])])])
    assert annotations.idle_by_phase(bare, xplane.device_ops(bare)) is None


def test_context_token_steps_from_the_annotations():
    total, dispatches = annotations.context_token_steps(serving_trace(), 8)
    # chunk * ctx + n * chunk * (chunk - 1) / 2, per dispatch
    assert total == (8 * 700 + 3 * 28) + (8 * 100 + 2 * 28)
    assert dispatches == 2


def fake_run(tmp_path, profile, **over):
    path = tmp_path / "plugins" / "profile" / "x"
    path.mkdir(parents=True, exist_ok=True)
    red = xplane.reduce(profile)
    fields = dict(trace=True, profile=red, trace_dir=str(tmp_path), spans=[],
                  window=(0.0, 100.0), trace_window=(0.0, 100.0),
                  traffic={"steps_per_dispatch": 1}, shapes={"decode_chunk": 8})
    fields.update(over)
    return NS(**fields), path / "t.xplane.pb"


def test_idle_sched_share_reader(tmp_path, monkeypatch):
    profile = serving_trace()
    run, file = fake_run(tmp_path, profile)
    file.write_bytes(b"")
    monkeypatch.setattr(xplane, "load", lambda path: profile)
    reader = harness.load_module("layer_metrics", "idle_sched_share.serve")
    share = reader.read(run)
    assert share == pytest.approx(15.0)
    assert share <= 100.0 * run.profile.idle_share
    # an untraced run and a rehearsal (no device plane) read nothing
    assert reader.read(NS(trace=False, profile=None)) is None
    assert reader.read(NS(trace=True, profile=None)) is None


# -- span readers -----------------------------------------------------------------


def test_span_readers_and_a_parent_without_the_spans():
    rows = [{"name": "handler_wait", "mono": 1.0 + i, "dur_ms": float(i),
             "event": "generate"} for i in range(11)]
    rows.append({"name": "handler_wait", "mono": 2.0, "dur_ms": 999.0,
                 "event": "score"})
    rows.append({"name": "handler_wait", "mono": 200.0, "dur_ms": 999.0,
                 "event": "generate"})          # after the window
    for k in range(3):  # three dispatches, two live requests each
        for tid in ("a", "b"):
            rows.append({"name": "decode_iter", "mono": 10.0 + k, "dur_ms": 160.0,
                         "trace_id": tid, "take": 8, "dispatch_ms": 0.5 + k,
                         "fetch_ms": 150.0})
    run = NS(spans=rows, window=(0.0, 100.0), trace_window=(10.5, 11.5),
             shapes={"decode_chunk": 8})
    wait = harness.load_module("layer_metrics", "handler_wait_p90_ms.serve")
    dispatch = harness.load_module("layer_metrics", "decode_dispatch_ms_p50.serve")
    assert wait.read(run) == pytest.approx(9.0)
    assert dispatch.read(run) == pytest.approx(1.5)
    parent = NS(spans=[{"name": "decode_iter", "mono": 10.0, "dur_ms": 160.0,
                        "trace_id": "a", "take": 8, "share": 80.0}],
                window=(0.0, 100.0), shapes={"decode_chunk": 8})
    assert wait.read(parent) is None and dispatch.read(parent) is None


# -- scopes: the wire reader and the step's split -----------------------------------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    """One protobuf field: an int as a varint, bytes/str length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(number << 3 | 2) + varint(len(value)) + value


def hlo_proto(module, instructions):
    """``HloProto`` with one computation; ``instructions`` are (name,
    op_name or None) pairs."""
    body = b"".join(
        field(2, field(1, name) + field(2, "fusion") + field(35, 7 + i)
              + (field(7, field(1, "x") + field(2, op_name)) if op_name else b""))
        for i, (name, op_name) in enumerate(instructions))
    return field(1, field(1, module) + field(3, field(1, "main") + body))


def xspace(modules):
    events = b"".join(
        field(4, field(1, i + 1) + field(2, field(1, i + 1) + field(2, name)
                                         + field(5, field(1, 3) + field(6, proto))
                                         + field(5, field(1, 4) + field(5, "text"))))
        for i, (name, proto) in enumerate(modules))
    return (field(1, field(1, 1) + field(2, "/device:TPU:0"))
            + field(1, field(1, 2) + field(2, "/host:metadata") + events))


STEP = [("fusion.1", "jit(train_step)/jvp(forward)/TransformerLM/dot_general"),
        ("fusion.2", "jit(train_step)/transpose(jvp(forward))/TransformerLM/mul"),
        ("fusion.3", "jit(train_step)/optimizer/add"),
        ("copy.4", None),
        ("flash_attention_bwd_fused.5",
         "jit(train_step)/transpose(jvp(forward))/pallas_call"),
        ("while.6", "jit(train_step)/jvp(forward)/while")]


def test_wire_reader_finds_every_instructions_scope(tmp_path):
    file = tmp_path / "t.xplane.pb"
    file.write_bytes(xspace([
        ("jit_train_step(1)", hlo_proto("jit_train_step", STEP)),
        ("jit_other(2)", hlo_proto("jit_other", [("fusion.9", "jit(other)/add")]))]))
    scopes = annotations.trace_scopes(str(file))
    assert set(scopes) == {"jit_train_step", "jit_other"}
    assert scopes["jit_train_step"] == {k: v or "" for k, v in STEP}
    assert [annotations.scope_of(v or "") for _, v in STEP] == [
        "forward", "backward", "optimizer", None, "backward", "forward"]
    assert list(annotations.fields(field(1, 300) + field(2, "ab"))) == [
        (1, 0, 300), (2, 2, b"ab")]


def training_trace():
    """Two steps of 500 us: forward 100, backward 200 (a kernel among it),
    optimizer 50, an unscoped copy 50, under an enclosing while."""
    ops, markers = [], []
    for k in range(2):
        t = k * 500 * US
        ops += [ev("%fusion.1 = f32[2]{0} fusion()", t, 100 * US),
                ev("%fusion.2 = f32[2]{0} fusion()", t + 100 * US, 120 * US),
                ev("%flash_attention_bwd_fused.5 = bf16[4]{0} custom-call()",
                   t + 220 * US, 80 * US),
                ev("%fusion.3 = f32[2]{0} fusion()", t + 300 * US, 50 * US),
                ev("%copy.4 = f32[2]{0} copy()", t + 350 * US, 50 * US),
                ev("%while.6 = (s32[]) while()", t, 400 * US)]
        markers.append(ev("train_step", t - 5 * US, 450 * US, step_num=6 + k))
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="main", events=markers)])])


def test_step_splits_by_scope(tmp_path, monkeypatch):
    profile = training_trace()
    run, file = fake_run(tmp_path, profile)
    file.write_bytes(xspace([("jit_train_step(1)",
                              hlo_proto("jit_train_step", STEP))]))
    monkeypatch.setattr(xplane, "load", lambda path: profile)
    got = {k: harness.load_module("layer_metrics", f"{k}_device_ms.train").read(run)
           for k in annotations.SCOPES}
    assert got == {"forward": pytest.approx(0.1), "backward": pytest.approx(0.2),
                   "optimizer": pytest.approx(0.05)}
    ms = annotations.scoped_device_ms(run)
    assert ms["unscoped"] == pytest.approx(0.05)
    # the parts sum to the busy time per step (here: no two ops overlap)
    assert sum(ms.values()) == pytest.approx(run.profile.busy_s * 1e3 / 2)

    # the parent's program: no marker, no scope -> the readers leave it out
    bare = NS(planes=[profile.planes[0], NS(name="/host:CPU", lines=[])])
    other = tmp_path / "parent"
    run2, file2 = fake_run(other, bare)
    file2.write_bytes(xspace([("jit_one_step(1)", hlo_proto(
        "jit_one_step", [(k, None) for k, _ in STEP]))]))
    monkeypatch.setattr(xplane, "load", lambda path: bare)
    assert annotations.scoped_device_ms(run2) is None
    # markers but an executable from a cache filled before the scopes existed
    run3, file3 = fake_run(tmp_path / "stale", profile)
    file3.write_bytes(file2.read_bytes())
    monkeypatch.setattr(xplane, "load", lambda path: profile)
    assert annotations.scoped_device_ms(run3) is None


# -- the table ---------------------------------------------------------------------


def test_the_new_cell_and_metrics_are_appended():
    table = harness.Registry().table
    cell = table["workloads"][-1]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (
        "train-dp4-s2048", 4, "markov-b4-s2048-dp4")
    assert sum(c["chips"] == 4 for c in table["workloads"]) == 1
    names = [m["name"] for m in table["per_layer"]]
    assert names[-7:] == [
        "handler_wait_p90_ms.serve", "idle_sched_share.serve",
        "decode_dispatch_ms_p50.serve", "forward_device_ms.train",
        "backward_device_ms.train", "optimizer_device_ms.train",
        "allreduce_exposed_share.train"]
    for metric in table["per_layer"][-7:]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric["name"] + ".py"))
