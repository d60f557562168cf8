"""The benchmark's files for the latent-sparse configuration: the session
traffic generator and the arithmetic of ``benchmark/lib/flops_glm_dsa.py``
(ISSUE 33's counts), at no device's cost.
"""

import pytest

from benchmark.lib import flops_glm_dsa, harness, loadgen_sessions

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
