"""Rehearsal and unit checks of the benchmark, on the CPU at toy sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/rehearsal -q -p no:cacheprovider

Kept here and not under ``tests/``: the benchmark's directories hold the
benchmark and nothing else, and ``tests/`` is the program's. Nothing here
touches a TPU topology; the real-size compiles are
``compile_real_size.py``, run by hand.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import flops, harness, loadgen, stats, xplane  # noqa: E402

REGISTRY = harness.Registry()
CELLS = [c["name"] for c in REGISTRY.table["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}


def run_cell(root, cell, trace, chips, seconds=3):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", str(seconds),
         "--trace", str(trace), "--rehearsal"],
        capture_output=True, text=True, env=env, timeout=900, cwd=root)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_line(registry, cell, trace, line):
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = "per_layer" if trace else "end_to_end"
    listed = registry.metrics(group, cell)
    assert set(line["metrics"]) == {m["name"] for m in listed}
    for metric in listed:
        got = line["metrics"][metric["name"]]
        assert got["unit"] == metric["unit"]
        if metric["source"] != "program_counter":
            # a time, a rate or a share from a CPU run is not a number
            assert got["value"] is None, metric["name"]
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell, trace):
    chips = REGISTRY.cell(cell)["chips"]
    check_line(REGISTRY, cell, trace, run_cell(ROOT, cell, trace, chips))


def test_no_tpu_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert not proc.stdout.strip().endswith("}")


def test_bare_directory_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--rehearsal"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0 and "{" not in proc.stdout


def test_added_files_are_picked_up(tmp_path):
    """A later PR's cell, traffic mix and per-layer metric: new files and
    new entries only, no edit to a file that is there."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    os.symlink(os.path.join(ROOT, "distriflow_tpu"), tmp_path / "distriflow_tpu")
    table = json.loads(json.dumps(REGISTRY.table))
    traffic = REGISTRY.traffic("markov-b4-s2048")
    traffic["rehearsal"]["batch_per_chip"] = 1
    (tmp_path / "benchmark" / "traffic" / "toy-b1.json").write_text(
        json.dumps(traffic))
    (tmp_path / "benchmark" / "layer_metrics" / "steps_run.toy.py").write_text(
        "def read(run):\n    return len(run.steps)\n")
    table["workloads"].append({
        "name": "toy-cell", "config": "pythia-1.4b-widths-train",
        "traffic": "toy-b1", "chips": 1, "why": "rehearsal only"})
    for metric in table["end_to_end"]:
        if metric["name"] == "train_tok_s_chip":
            metric["workloads"].append("toy-cell")
    table["per_layer"].append({
        "name": "steps_run.toy", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tok_s_chip", "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(table))
    line = run_cell(str(tmp_path), "toy-cell", 1, 1, seconds=2)
    assert line["metrics"]["steps_run.toy"]["value"] > 0
    assert set(line["metrics"]) == {
        "compile_s", "compiles_in_window", "steps_run.toy"}


# -- lib/stats ---------------------------------------------------------------


def test_percentile_rule_and_counts():
    values = list(range(1, 201))  # 200 samples
    assert stats.median(values) == 100.5
    assert stats.percentile(values, 95.0) == pytest.approx(190.05)
    assert stats.percentile([7.0], 95.0) == 7.0
    # ten samples beyond: p95 needs 200, p99 needs 1000, p90 needs 100
    assert stats.highest_supported(199) == 90.0
    assert stats.highest_supported(200) == 95.0
    assert stats.highest_supported(999) == 95.0
    assert stats.highest_supported(1000) == 99.0
    assert stats.highest_supported(19) is None
    assert "n=200" in stats.describe("x", values)
    assert stats.spread([10, 10, 10, 10, 11, 9]) == pytest.approx(0.05)


# -- lib/loadgen -------------------------------------------------------------


@pytest.mark.parametrize("name", ["chat-mixed-open", "chat-mixed-closed64"])
def test_every_seed_same_work_other_order(name):
    traffic = REGISTRY.traffic(name)
    a = loadgen.requests(traffic, 30.0, 1, 1000, 600000)
    b = loadgen.requests(traffic, 30.0, 3000000019, 1000, 600000)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.out_tokens for r in a) == sorted(r.out_tokens for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert len({r.offset for r in a}) == len(a) and min(r.offset for r in a) >= 1000
    # a rotation: the same neighbours in the same order, from another start
    shapes = lambda rs: [(r.prompt_len, r.out_tokens) for r in rs]  # noqa: E731
    k = shapes(a).index(shapes(b)[0])
    assert any(shapes(a)[k:] + shapes(a)[:k] == shapes(b)
               for k in range(len(a)) if shapes(a)[k] == shapes(b)[0])
    if traffic["loop"] == "open":
        assert a[0].due_s == 0.0 and max(r.due_s for r in a) < 30.0
        assert len(a) == round(traffic["rate_per_s"] * 30)
    shares = {int(k): v for k, v in traffic["prompt_lengths"].items()}
    for length, share in shares.items():
        count = sum(1 for r in a if r.prompt_len == length)
        assert abs(count - share * len(a)) <= 1


# -- lib/flops against the program's own tally -----------------------------------


def test_flops_equal_the_programs_tally():
    import jax
    import jax.numpy as jnp

    from distriflow_tpu.ops.flop_count import pallas_cost_of
    import importlib

    fa = importlib.import_module("distriflow_tpu.ops.flash_attention")
    ce = importlib.import_module("distriflow_tpu.ops.fused_ce")
    b, h, s, d = 2, 2, 256, 64
    q = jnp.zeros((b, h, s, d), jnp.float32)
    jax.clear_caches()
    fwd = pallas_cost_of(lambda q: fa.flash_attention(q, q, q, causal=True), q)
    mine = flops.flash_attention_fwd(b, h, s, d, 4)
    assert fwd["flops"] == mine["flops"] and fwd["bytes_accessed"] == mine["bytes"]

    jax.clear_caches()
    both = pallas_cost_of(
        lambda q: jax.grad(lambda q: fa.flash_attention(
            q, q, q, causal=True).sum())(q), q)
    bwd = flops.flash_attention_bwd(b, h, s, d, 4)
    assert both["flops"] - fwd["flops"] == bwd["flops"]
    # the tally adds the kernel's own float32 dq partials (one per KV block),
    # which the algorithm does not require
    extra = both["bytes_accessed"] - fwd["bytes_accessed"] - bwd["bytes"]
    assert extra >= 0 and extra % (2 * b * h * s * d * 4) == 0

    n, v = 256, 512
    logits = jnp.zeros((n, v), jnp.float32)
    labels = jnp.zeros((n,), jnp.int32)
    jax.clear_caches()
    loss = lambda lg: ce.fused_sparse_softmax_cross_entropy(  # noqa: E731
        lg, labels, None)
    cf = pallas_cost_of(loss, logits)
    mine = flops.fused_ce_fwd(n, v, 4)
    assert cf["flops"] == mine["flops"] and cf["bytes_accessed"] == mine["bytes"]
    jax.clear_caches()
    cb = pallas_cost_of(lambda lg: jax.grad(loss)(lg), logits)
    mine_b = flops.fused_ce_bwd(n, v, 4)
    assert cb["flops"] - cf["flops"] == mine_b["flops"]
    assert cb["bytes_accessed"] - cf["bytes_accessed"] == mine_b["bytes"]
    # ops/flash_decode.py records no cost: nothing to compare
    # flash_decode() with; its arithmetic stands on its docstring


def test_model_arithmetic_matches_the_issue():
    m = {"vocab_size": 50304, "d_model": 2048, "n_heads": 16, "n_layers": 24,
         "d_ff": 8192}
    assert flops.layer_matmul_params(m) == 50331648
    assert round(flops.n_params(m) / 1e6) == 1414
    assert flops.kv_bytes_per_token(m, 2) == 196608
    eight = dict(m, n_layers=8)
    assert flops.train_flops_per_token(eight, 2048) / 1e9 == pytest.approx(3.24, abs=0.01)
    # decode reads the float32 weights once a step: 5.25 GB without the embedding
    assert flops.decode_step_bytes(m, [], 4, 2) / 1e9 == pytest.approx(5.24, abs=0.02)
    least = flops.least_seconds({"flops": 197e12, "bytes": 1e9},
                                {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert least == {"seconds": 1.0, "bound": "compute"}


# -- lib/xplane on a synthetic trace ----------------------------------------


def ev(name, start, dur, **stats_):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats_.items()))


def synthetic():
    ops = [ev("fusion.1", 0, 100_000),
           ev("%flash_attention_bwd_fused.7 = bf16[4,16]{1,0} custom-call("
              "bf16[4]{0} %fusion.1)", 100_000, 200_000),
           ev("fusion.2", 250_000, 100_000),            # overlaps the kernel
           ev("all-reduce.3", 500_000, 100_000),        # exposed
           ev("all-reduce-start.4", 700_000, 50_000),
           ev("fusion.5", 720_000, 80_000),             # hides 30 of the 50
           ev("fusion.6", 1_000_000, 100_000),
           ev("%while.9 = (s32[]) while(%tuple.1)", 700_000, 100_000)]
    modules = [ev("jit_step", 0, 1_100_000)]
    host = [ev("run", 0, 2_000_000), ev("fetch loss", 380_000, 100_000),
            ev("build batch", 820_000, 170_000)]
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=modules),
                                        NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="main", events=host)])])


def test_xplane_reduction():
    red = xplane.reduce(synthetic())
    assert red.devices == 1
    assert red.window_s == pytest.approx(1.1e-3)
    # busy union: [0,350] [500,600] [700,800] [1000,1100] us
    assert red.busy_s == pytest.approx(650e-6)
    assert red.idle_share == pytest.approx(1 - 650 / 1100)
    assert red.collective_s == pytest.approx(150e-6)
    assert red.collective_exposed_s == pytest.approx(120e-6)
    secs, calls = red.kernel_seconds("flash_attention_bwd_fused")
    assert (secs, calls) == (pytest.approx(200e-6), 1)
    gaps = dict(red.idle_gaps)
    # [350,500] is mostly the loss fetch, [800,1000] mostly the batch build,
    # [600,700] has only the enclosing event
    assert gaps["fetch loss"] == pytest.approx(150e-6)
    assert gaps["build batch"] == pytest.approx(200e-6)
    assert gaps["run"] == pytest.approx(100e-6)
    ranked = dict(red.op_seconds)
    assert ranked["flash_attention_bwd_fused bf16[4,16]"] == pytest.approx(200e-6)
    assert ranked["fusion"] == pytest.approx(380e-6)  # all instances as one
    assert not any(label.startswith("while") for label, _ in red.op_seconds)
    assert xplane.kind_of("%fusion.9 = f32[2]{0} fusion(%x.1)") == "fusion"
    assert xplane.reduce(NS(planes=[synthetic().planes[1]])) is None


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.total([(0, 3), (5, 7)]) == 5


def test_xplane_on_a_trimmed_chip_trace():
    """Half a second (two optimizer steps, 9 layers, 4 x 2048 tokens) of the
    device and host planes of a real trace of train-1chip-s2048 on one v5e
    (PR 23), event names cut to 80 characters, kept as JSON: what the
    profiler really calls things, so a change to the reduction's parsing
    shows here."""
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "train_trace_trimmed.json")) as f:
        raw = json.load(f)
    profile = NS(planes=[NS(name=p["name"], lines=[NS(name=ln["name"], events=[
        NS(name=n, start_ns=s, duration_ns=d, stats=[]) for n, s, d in ln["events"]])
        for ln in p["lines"]]) for p in raw["planes"]])
    red = xplane.reduce(profile)
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.498494811)
    assert red.busy_s == pytest.approx(0.489117299)
    assert red.collective_s == 0.0
    assert red.kernel_seconds("flash_attention_bwd_fused") == (
        pytest.approx(0.030151295), 18)           # 2 steps x 9 layers
    assert red.kernel_seconds("flash_attention_fwd")[1] == 20
    assert red.kernel_seconds("fused_ce_fwd")[1] == 2
    assert red.kernel_seconds("fused_ce_bwd")[1] == 2
    assert red.idle_gaps[0][0] == "$array.py:631 _value"  # the loss fetch
    assert all(not label.startswith(("while", "call")) for label, _ in red.op_seconds)
