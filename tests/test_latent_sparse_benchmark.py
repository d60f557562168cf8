"""The benchmark's files for the latent-sparse configuration: the session
traffic generator and the arithmetic of ``benchmark/lib/flops_glm_dsa.py``
(ISSUE 33's counts), at no device's cost.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_sessions
from benchmark.lib import flops_glm_dsa, harness, loadgen_sessions
from distriflow_tpu.models.latent_sparse import init_params

REGISTRY = harness.Registry()
TRAFFIC = REGISTRY.traffic("agent-sessions-open")
CONFIG = REGISTRY.config("glm-5.2-ep16-share-serve")


def test_sessions_every_seed_same_work_other_order():
    a = loadgen_sessions.requests(TRAFFIC, 30.0, 1, 400000, 480000)
    b = loadgen_sessions.requests(TRAFFIC, 30.0, 3000000019, 400000, 480000)
    shapes = lambda rs: [(r.session, r.out_tokens) for r in rs]  # noqa: E731
    assert sorted(shapes(a)) == sorted(shapes(b)) and shapes(a) != shapes(b)
    # a rotation: the same neighbours in the same order, from another start
    assert any(shapes(a)[k:] + shapes(a)[:k] == shapes(b) for k in range(len(a)))
    assert a[0].due_s == 0.0 and max(r.due_s for r in a) < 30.0
    assert len(a) == round(TRAFFIC["rate_per_s"] * 30)
    assert len({r.offset for r in a}) == len(a) and min(r.offset for r in a) >= 400000
    # the sessions are taken in one fixed cycle: each as often as any other
    counts = [sum(r.session == s for r in a) for s in range(24)]
    assert max(counts) - min(counts) <= 1
    lengths = loadgen_sessions.session_lengths(TRAFFIC)
    assert all(r.prompt_len == lengths[r.session] + 128 for r in a)


def test_sessions_are_the_issues():
    lengths = loadgen_sessions.session_lengths(TRAFFIC)
    assert [lengths.count(n) for n in (8192, 16384, 32768)] == [12, 8, 4]
    assert sum(lengths) == 360448 == 2816 * 128
    one = loadgen_sessions.sessions(TRAFFIC, 1, TRAFFIC["score_tokens"])
    two = loadgen_sessions.sessions(TRAFFIC, 3000000019, TRAFFIC["score_tokens"])
    assert [s.context_len for s in one] == [s.context_len for s in two] == lengths
    assert [s.offset for s in one] != [s.offset for s in two]
    end = loadgen_sessions.sessions_end(TRAFFIC, TRAFFIC["score_tokens"])
    for group in (one, two):
        assert group[0].offset >= TRAFFIC["score_tokens"]
        assert all(a.offset + a.context_len == b.offset
                   for a, b in zip(group, group[1:]))
        assert group[-1].offset + group[-1].context_len <= end
    # room for every window's turns behind the sessions' and the warm-up's
    assert end + 128 * (24 + 6) + 128 < TRAFFIC["corpus_tokens"]
    # the longest reply fits the cache
    assert 32768 + 128 + TRAFFIC["output_tokens"]["max"] <= CONFIG[
        "max_position_embeddings"]
    # set-up leaves 768 pages free, and a window takes far fewer
    serving = CONFIG["serving"]
    assert serving["page_pool_pages"] - 2816 == 768
    assert serving["max_slots"] * 5 < 768


@pytest.mark.parametrize("part,millions", [
    ("mla", 5 * 165.0), ("selector", 2 * 9.4), ("dense_ffn", 226.5),
    ("shared_expert", 4 * 37.75), ("router", 4 * 1.57),
    ("routed_experts", 4 * 604.0), ("embedding_and_head", 237.9),
    ("total", 3881.0)])
def test_parameter_counts_are_the_issues(part, millions):
    got = flops_glm_dsa.parameters(CONFIG)[part] / 1e6
    assert got == pytest.approx(millions, rel=4e-3), (part, got)


def test_cache_and_step_bytes_are_the_issues():
    assert flops_glm_dsa.cache_bytes_per_token(CONFIG) == 6272
    pool = CONFIG["serving"]["page_pool_pages"] * 128 * 6272
    assert pool == pytest.approx(2.88e9, rel=2e-3)
    # one step of one row at 32k: 2 full layers of 128-value keys; 2,048
    # selected latents of 576 values in 5 layers; one expert is 75.5 MB
    assert flops_glm_dsa.indexer_bytes(32768, CONFIG) == 32768 * 128 * 2 * 2
    assert flops_glm_dsa.attend_bytes(2048, CONFIG) == 2048 * 576 * 2 * 5
    expert = 3 * 6144 * 2048 * 2
    assert flops_glm_dsa.experts_bytes(3, 1, CONFIG) == 3 * expert + 4 * (
        expert + 6144 * 256 * 4)


def test_the_cells_check_fails_the_precision_below_the_stated_one():
    """The driver's own comparison (``_check_score``, through a server) on
    one set of weights: ``correct`` as stated, not ``correct`` with the
    norms and the router in bfloat16."""
    proc = subprocess.run(
        [sys.executable, "benchmark/rehearsal/precision_control.py",
         "serve-glm52-sessions-long", "3000000019"],
        cwd=harness.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "stated correct True, lowered correct False" in proc.stdout


@pytest.mark.parametrize("seed", [1, 2])
def test_own_input_readings_separate_under_bfloat16_matmuls(seed):
    """Where everything else computes in bfloat16, as at the published
    size, what tells float32 norms and router from bfloat16 ones are the
    readings on the program's own inputs: exact as stated, far outside the
    limits when lowered."""
    toy = harness.toy(CONFIG)
    cfg = serve_sessions.program_config({
        **toy, "compute_dtype": "bfloat16", "param_dtype": "bfloat16",
        "hidden_size": 256, "n_routed_experts": 64, "experts_held": [0, 8],
        "num_experts_per_tok": 8})
    params = init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        0, toy["vocab_size"], 512).astype(np.int32)
    own = serve_sessions._system_sets(cfg, params, tokens)[2]
    assert serve_sessions._own_within(own), own
    assert own["norm_match"] > 0.9999 and own["affinity_err"] < 1e-6
    low = serve_sessions._system_sets(dataclasses.replace(
        cfg, norm_router_dtype=jnp.bfloat16), params, tokens)[2]
    assert not serve_sessions._own_within(low), low
    assert low["norm_match"] < 0.9 and low["affinity_err"] > 1e-3


@pytest.mark.parametrize("fault,what", [
    ({"rope_base": 2500.0}, "rotary angles"),
    ({"index_rope_dim": 4}, "the selector rotates other values"),
    ({"routed_scaling_factor": 2.0}, "the gates' scale"),
    ({"rms_eps": 1e-2}, "the norms' epsilon"),
    ({"index_topk": 12}, "a smaller selection")])
def test_whole_model_limits_catch_a_planted_fault(fault, what):
    """The limits on ``score()`` and on the chosen sets have no reading from
    a lower precision (bfloat16 matmuls hide it); what they guard is a
    program that computes something else. Each fault planted in the
    program, against the reference of the true model, breaks one of them;
    the true program breaks none."""
    from distriflow_tpu.models.generate import sequence_logprob

    toy = harness.toy(CONFIG)
    true = serve_sessions.program_config(toy)
    model = serve_sessions.reference_model(toy)
    # a seed at which the held experts see a token in two (two held of
    # eight, top-2): at one where they see few, the gates' scale hides
    params = init_params(true, jax.random.PRNGKey(6))
    tokens = np.random.default_rng(3).integers(
        0, toy["vocab_size"], 192).astype(np.int32)
    n = len(tokens)
    logp, masks, routes = serve_sessions.reference.log_probs(
        params, jnp.asarray(tokens), jnp.arange(n - 1), model,
        return_sets=True)
    want = float(np.take_along_axis(
        np.asarray(logp), tokens[1:, None].astype(np.int64), axis=-1).sum())
    past = np.arange(true.index_topk, n)

    def within(cfg):
        got = float(sequence_logprob(cfg, params, tokens[None], from_pos=1)[0])
        selected, routed, _ = serve_sessions._system_sets(cfg, params, tokens)
        return (abs(got - want) / (n - 1) <= serve_sessions.SCORE_NATS_PER_TOKEN
                and serve_sessions._held(selected, masks, past)
                >= serve_sessions.SELECTION_OVERLAP_MIN
                and serve_sessions._held(routed, routes)
                >= serve_sessions.ROUTING_OVERLAP_MIN)

    assert within(true)
    assert not within(dataclasses.replace(true, **fault)), what
