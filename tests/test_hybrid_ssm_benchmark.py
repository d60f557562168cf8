"""The benchmark's files for the hybrid state-space configuration: the
arithmetic of ``benchmark/lib/flops_granite_hybrid.py`` (ISSUE 35's counts),
the configuration file against the catalog's row, the cell's traffic, and
the cell's own comparison failing the precision below the stated one; at no
device's cost.
"""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_chat_hybrid
from benchmark.lib import flops_granite_hybrid as flops
from benchmark.lib import harness, loadgen
import distriflow_tpu.models.hybrid_ssm as hs
from benchmark.lib import corpus as corpus_lib
from distriflow_tpu.models.hybrid_ssm import init_params
from distriflow_tpu.models.latent_sparse import ROWS

gen = importlib.import_module("distriflow_tpu.models.generate")

REGISTRY = harness.Registry()
CELL = "serve-granite4h-chat-rate"
CONFIG = REGISTRY.config("granite-4.0-h-small-ep2-share-serve")
TRAFFIC = REGISTRY.traffic("chat-mixed-open-granite4h")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.parametrize("part,millions", [
    ("mamba", 9 * 102.29), ("attention", 41.94), ("shared_mlp", 10 * 18.87),
    ("router", 10 * 0.295), ("routed_experts", 10 * 339.74),
    ("embedding", 205.5), ("total", 4757.0)])
def test_parameter_counts_are_the_issues(part, millions):
    got = flops.parameters(CONFIG)[part] / 1e6
    assert got == pytest.approx(millions, rel=2e-3), (part, got)


def test_the_program_holds_the_parameters_the_arithmetic_counts():
    cfg = serve_chat_hybrid.program_config(CONFIG)
    from distriflow_tpu.models.hybrid_ssm import HybridSSMLM

    shapes = jax.eval_shape(lambda k: HybridSSMLM(cfg).init(
        k, jnp.zeros((1, 2), jnp.int32))["params"], jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert n == flops.parameters(CONFIG)["total"]
    assert cfg.row_state_bytes() == flops.state_bytes_per_slot(CONFIG)


def test_state_cache_and_step_bytes_are_the_issues():
    # 128 x 64 x 128 float32 a layer, nine layers, and the conv's three rows
    assert flops.state_bytes_per_slot(CONFIG) == 9 * (
        1048576 * 4 + 3 * 8448 * 2)
    assert 32 * flops.state_bytes_per_slot(CONFIG) == pytest.approx(
        1.22e9, rel=5e-3)
    assert flops.cache_bytes_per_token(CONFIG) == 4096
    serving = CONFIG["serving"]
    assert serving["page_pool_pages"] * serving["page_size"] * 4096 == (
        pytest.approx(0.25e9, rel=1e-2))
    # every slot can hold the longest request
    longest = max(int(k) for k in TRAFFIC["prompt_lengths"])
    assert longest + TRAFFIC["output_tokens"]["max"] == CONFIG[
        "max_position_embeddings"] == 15 * serving["page_size"]
    assert serving["page_pool_pages"] == serving["max_slots"] * 15
    # ten live rows a step: 0.75 GB of state; all 32 slots: 2.4 GB
    assert flops.ssm_step_bytes(10, CONFIG) == pytest.approx(0.76e9, rel=1e-2)
    assert flops.ssm_step_bytes(32, CONFIG) == pytest.approx(2.44e9, rel=1e-2)
    expert = 3 * 4096 * 768 * 2
    assert flops.experts_bytes(7, 1, CONFIG) == 7 * expert + 10 * (
        3 * 4096 * 1536 * 2 + 4096 * 72 * 4)
    assert flops.attend_bytes(1000, CONFIG) == 1000 * 4096
    cost = flops.ssm_scan_cost(1536, 1, CONFIG)
    assert cost["flops"] == 9 * 1536 * (256 * 128 + 256 * 8192 + 4 * 1048576)
    assert cost["bytes"] == 9 * (1536 * (8448 * 2 + 128 * 4 + 8192 * 4)
                                 + 2 * 1048576 * 4)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_file_is_the_catalogs_row_but_for_reduced():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-small")
    entry = next(c for c in REGISTRY.table["configs"]
                 if c["name"] == "granite-4.0-h-small-ep2-share-serve")
    assert entry["source"] == row["source_url"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    differing = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differing == set(CONFIG["reduced"]) - {"num_local_experts"}
    assert CONFIG["num_local_experts"] == 72 and CONFIG["experts_held"] == [0, 36]
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:10]
    assert set(CONFIG["reduced_how"]) == set(CONFIG["reduced"])


def test_traffic_is_serve_rate_mixeds_mix_at_its_own_rate():
    base = REGISTRY.traffic("chat-mixed-open")
    for key in ("loop", "prompt_lengths", "output_tokens", "clients",
                "corpus_tokens", "trace_seconds"):
        assert TRAFFIC[key] == base[key], key
    a = loadgen.requests(TRAFFIC, 30.0, 1, 10000, 590000)
    b = loadgen.requests(TRAFFIC, 30.0, 3000000019, 10000, 590000)
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.out_tokens for r in a) == sorted(r.out_tokens for r in b)
    assert len(a) == round(TRAFFIC["rate_per_s"] * 30) == 90  # 0.6 x the knee
    cell = REGISTRY.cell(CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert f"{TRAFFIC['rate_per_s']:g}/s" in cell["why"]
    # the replay's prefills and its insert are warmed shapes, and a step
    # over its two groups takes more than one trip of ROWS
    assert TRAFFIC["replay_group"] <= TRAFFIC["warm_group_sizes"]
    assert 2 * TRAFFIC["replay_group"] > ROWS < CONFIG["serving"]["max_slots"]
    assert 0 < TRAFFIC["replay_stagger"] < TRAFFIC["replay_dispatches"]
    lengths = {int(k) for k in TRAFFIC["prompt_lengths"]}
    chunk = CONFIG["serving"]["decode_chunk"]
    assert TRAFFIC["replay_prompt_len"] in lengths
    assert (TRAFFIC["replay_prompt_len"]
            + TRAFFIC["replay_dispatches"] * chunk) in lengths
    assert TRAFFIC["score_rows"] * TRAFFIC["score_len"] >= 2048


def test_the_cells_check_fails_the_precision_below_the_stated_one():
    """The driver's own comparisons (``score()`` through a server, the
    float32 pieces on the program's own inputs, the served-path replay) on
    one set of weights: ``correct`` as stated, not ``correct`` with ``dt``,
    decay, state, norms and router in bfloat16."""
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearsal/precision_control.py", CELL,
         "3000000019"],
        cwd=harness.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "stated correct True, lowered correct False" in proc.stdout


@pytest.mark.parametrize("lowered", ["ssm_dtype", "norm_router_dtype"])
def test_own_input_readings_separate_under_bfloat16_matmuls(lowered):
    """Where everything else computes in bfloat16, as at the published size,
    what tells a float32 state, norm or router from a bfloat16 one are the
    readings on the program's own inputs: inside the limits as stated, far
    outside with either lowered."""
    toy = harness.toy(CONFIG)
    cfg = serve_chat_hybrid.program_config({
        **toy, "compute_dtype": "bfloat16", "param_dtype": "bfloat16",
        "hidden_size": 256, "num_local_experts": 64, "experts_held": [0, 32],
        "num_experts_per_tok": 8})
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = np.random.default_rng(1).integers(
        0, toy["vocab_size"], 380).astype(np.int32)
    own = serve_chat_hybrid.own_input_readings(cfg, params, tokens)
    assert serve_chat_hybrid._own_within(own), own
    low = serve_chat_hybrid.own_input_readings(
        dataclasses.replace(cfg, **{lowered: jnp.bfloat16}), params, tokens)
    assert not serve_chat_hybrid._own_within(low), low
    if lowered == "ssm_dtype":
        assert low["state_err"] > 30 * max(own["state_err"], 1e-6)
    else:
        assert low["norm_match"] < 0.9 and low["logits_err"] > 1e-3


# -- the served-path replay, (d) of the driver's ``correct`` --------------------


def _replay(monkeypatch=None, plant=None):
    """The driver's replay at the toy size (float32, 8 slots, six rows in
    two groups of three: a step over both takes two trips of ``ROWS``), with
    ``plant(monkeypatch)`` applied to the programs it builds."""
    from distriflow_tpu import ServingConfig

    toy, traffic = harness.toy(CONFIG), harness.toy(TRAFFIC)
    cfg = serve_chat_hybrid.program_config(toy)
    serving = ServingConfig(**toy["serving"])
    assert serving.max_slots > ROWS < 2 * traffic["replay_group"]
    params = init_params(cfg, jax.random.PRNGKey(5))
    corpus = corpus_lib.generate_corpus(traffic["corpus_tokens"], seed=0)
    built = (gen._build_paged_fns, gen._build_slot_fns, gen._build_prefill)
    for b in built:
        b.cache_clear()
    try:
        if plant is not None:
            plant(monkeypatch)
        return serve_chat_hybrid.replay_readings(traffic, cfg, serving,
                                                 params, corpus)
    finally:
        if monkeypatch is not None:
            monkeypatch.undo()
        for b in built:
            b.cache_clear()


def _plant_no_leaf(*kept):
    def plant(monkeypatch):
        monkeypatch.setattr(hs, "_FAMILY", hs._FAMILY._replace(
            slot_leaves=kept))
    return plant


def _plant_second_trip_index(monkeypatch):
    """The live rows' second group of ``ROWS`` taken one place early: a row
    of the first group is stepped twice."""
    def step_live(rows, args, a, state):
        order, trips = rows
        order = jnp.concatenate([order, jnp.full(
            (-order.shape[0] % ROWS,), order.shape[0], order.dtype)])
        y0 = jnp.zeros(state.shape[:3], jnp.float32)

        def trip(t, carry):
            state, y = carry
            ids = jax.lax.dynamic_slice(order, (t * (ROWS - 1),), (ROWS,))
            got, new = hs.ssm_step(*(v[ids] for v in args), a, state[ids])
            return state.at[ids].set(new), y.at[ids].set(got)

        state, y = jax.lax.fori_loop(0, trips, trip, (state, y0))
        return y, state

    monkeypatch.setattr(hs, "_step_live", step_live)


def _plant_later_layers(kind):
    """A fault of the stepping in every Mamba layer but the first (layers
    call ``_step_live`` in order, once a trace): a group's rows stepped
    from one another's state (``"swapped"``), or their new state dropped
    (``"not_stepped"``)."""
    def plant(monkeypatch):
        genuine, calls = hs._step_live, []
        n_mamba = serve_chat_hybrid.program_config(
            harness.toy(CONFIG)).layer_types.count("mamba")

        def faulty(rows, args, a, state):
            order, trips = rows
            order = jnp.concatenate([order, jnp.full(
                (-order.shape[0] % ROWS,), order.shape[0], order.dtype)])
            y0 = jnp.zeros(state.shape[:3], jnp.float32)

            def trip(t, carry):
                state, y = carry
                ids = jax.lax.dynamic_slice(order, (t * ROWS,), (ROWS,))
                read = jnp.roll(ids, 1) if kind == "swapped" else ids
                got, new = hs.ssm_step(*(v[ids] for v in args), a, state[read])
                if kind == "swapped":
                    state = state.at[ids].set(new)
                return state, y.at[ids].set(got)

            state, y = jax.lax.fori_loop(0, trips, trip, (state, y0))
            return y, state

        def step_live(*args):
            calls.append(1)
            return (genuine if len(calls) % n_mamba == 1 else faulty)(*args)

        monkeypatch.setattr(hs, "_step_live", step_live)
    return plant


def test_the_replay_holds_the_program_as_built():
    got = _replay()
    assert serve_chat_hybrid._replay_within(got), got
    assert got["state_err"] < 1e-5 and got["conv_err"] < 1e-5, got


@pytest.mark.parametrize("plant,leaf", [
    (_plant_no_leaf("conv_state"), "first_state_err"),
    (_plant_no_leaf("ssm_state"), "insert_err"),
    (_plant_second_trip_index, "first_state_err"),
    (_plant_later_layers("swapped"), "state_err"),
    (_plant_later_layers("not_stepped"), "state_err"),
], ids=["state_not_inserted", "conv_rows_not_inserted", "second_trip_index",
        "later_layers_swapped", "later_layers_not_stepped"])
def test_the_replay_fails_a_planted_fault(monkeypatch, plant, leaf):
    """Faults at the window's load, the last two of a kind that a replay of
    one row in one slot cannot see: each comes out not ``correct`` by the
    limit on what it breaks."""
    got = _replay(monkeypatch, plant)
    assert not serve_chat_hybrid._replay_within(got), got
    # the conv's rows are three steps' memory: left out of insert they show
    # at insert, and 128 steps on only in what the state made of them
    limit = {"first_state_err": serve_chat_hybrid.REPLAY_ERR_MAX,
             "state_err": serve_chat_hybrid.REPLAY_DEEP_ERR_MAX,
             "insert_err": 0.0}[leaf]
    assert got[leaf] > 2 * limit, got
    if leaf == "state_err":  # the first layer is as built: the others' limit
        assert got["first_state_err"] < 1e-5, got
