"""Serving fleet router (round 13; docs/PERFORMANCE.md §7h).

Pins the contracts the multi-replica front door makes:

- the prompt chain hash is ONE implementation (``fleet/prefix_hash.py``)
  shared by the server's prefix map and the router's affinity scoring —
  golden digests pin the chain itself, so a silent change that would
  zero the affinity win (router hashing one thing, server another)
  fails loudly;
- routed greedy decode is bit-identical to solo ``generate()`` across
  2 replicas, under affinity and round-robin alike, and a WRONG
  affinity hint (poisoned shadow map) still returns identical bits —
  affinity is a hint, never correctness;
- affinity routing beats round-robin on shared-prefix traffic (the
  per-replica prefix-hit counters prove it: round-robin spreads each
  group over both replicas and pays two cold admissions per group,
  affinity pays one);
- SLO-tiered admission sheds under queue pressure and admits again once
  the queue drains; a tier with no threshold is never shed;
- a replica killed mid-decode (seeded FaultPlan reset on the router's
  forward connection) loses zero requests: in-flight work fails over to
  the survivor with the SAME request_id and completes exactly once —
  replaying a completed id against the survivor returns the cached ack
  without re-entering the engine;
- replica-side prefix evictions (``release_prefix_cache``) propagate to
  the router's shadow map on the next stats poll.

Tiny CPU transformer; deliberately NOT in conftest's slow set — tier-1
exercises the fleet path every run.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.client import InferenceClient, RequestRefused, RequestShed
from distriflow_tpu.comm.transport import FaultPlan, ScriptedFault
from distriflow_tpu.fleet import FleetRouter, RouterClient, page_hashes, shareable_pages
from distriflow_tpu.models.generate import generate
from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
from distriflow_tpu.obs.telemetry import Telemetry
from distriflow_tpu.server import InferenceServer
from distriflow_tpu.utils.config import ServingConfig

pytestmark = pytest.mark.fleetserve

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.float32, use_flash_attention=False,
)
PS = 16  # 3 pages per slot


@pytest.fixture(scope="module")
def params():
    return transformer_lm(CFG, example_seq=16).init(jax.random.PRNGKey(0))


def _replica(params, telemetry, **serving_kw):
    # max_slots=2 keeps queue pressure cheap to create (shed test), but
    # the equal-memory default pool (2 slots x 3 pages) would thrash the
    # prefix map across 3 groups — size the pool for warm prefixes
    kw = dict(batch_window_s=0.05, decode_chunk=4, kv_layout="paged",
              page_size=PS, max_slots=2, page_pool_pages=24)
    kw.update(serving_kw)
    return InferenceServer(CFG, params, port=0, telemetry=telemetry,
                           serving=ServingConfig(**kw)).setup()


@pytest.fixture()
def fleet(params):
    """Two paged replicas with PRIVATE telemetry registries (per-replica
    counters must not contaminate each other) plus a router factory."""
    tel_a, tel_b = Telemetry(), Telemetry()
    sa = _replica(params, tel_a)
    sb = _replica(params, tel_b)
    made = []

    def mk_router(**kw):
        plan_a = kw.pop("fault_plan_a", None)
        kw.setdefault("stats_interval_s", 0.0)  # tests drive refresh_stats
        kw.setdefault("redial", False)
        kw.setdefault("telemetry", Telemetry())
        router = FleetRouter(port=0, **kw)
        router.add_replica(sa.address, name="A", fault_plan=plan_a)
        router.add_replica(sb.address, name="B")
        made.append(router)
        return router.setup()

    yield sa, sb, tel_a, tel_b, mk_router
    for router in made:
        router.stop()
    sa.stop()
    sb.stop()


def _prompt(seed, plen=33, batch=1):
    rng = np.random.default_rng(seed)
    return rng.integers(1, CFG.vocab_size, size=(batch, plen)).astype(np.int32)


def _solo(params, prompt, n):
    return np.asarray(generate(CFG, dict(params), prompt, n))


# -- satellite 1: the hoisted chain hash -----------------------------------


def test_golden_chain_hash():
    """The chain is a wire-visible protocol (every warm cache in a fleet
    depends on router and server hashing identical bytes): pin the
    digests themselves, not just self-consistency."""
    hashes = page_hashes(np.arange(40, dtype=np.int32), 16)
    assert [h.hex() for h in hashes] == [
        "0e084ffc26a48083caf4f0c48b4f4750fd4e4cb2",
        "960bd526e93cb085d008d0d285ffba8aa18df024",
    ]
    # dtype coercion: the router may hold prompts in any integer dtype
    assert page_hashes(np.arange(40, dtype=np.int64), 16) == hashes


def test_shareable_pages_cap():
    # the final token never shares: its page must run through prefill
    assert shareable_pages(16, 16) == 0
    assert shareable_pages(17, 16) == 1
    assert shareable_pages(32, 16) == 1
    assert shareable_pages(33, 16) == 2


def test_server_row_plan_uses_shared_hash(fleet):
    """Server-side ``_row_plan`` and the hoisted hash agree hash-for-hash
    (the drift the golden test guards against, checked at the live
    integration point)."""
    sa, *_ = fleet
    tokens = _prompt(7)[0]
    _shared, hashes = sa._row_plan(tokens)
    assert hashes == page_hashes(tokens, PS)
    assert len(hashes) == shareable_pages(len(tokens), PS)


# -- routed decode: bit-identity and affinity ------------------------------


def test_two_replica_bit_identity_vs_solo(fleet, params):
    _sa, _sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    with RouterClient(router.address) as c:
        for seed, n in ((1, 6), (2, 3), (3, 8)):
            prompt = _prompt(seed)
            out = c.generate(prompt, n)
            assert np.array_equal(out, _solo(params, prompt, n)), seed
            assert c.last_route is not None and c.last_replica in ("A", "B")


def test_affinity_beats_round_robin_on_shared_prefix(fleet, params):
    """Same traffic (3 prefix groups x 4 repeats), both policies. Round
    robin interleaves 3 groups over 2 replicas, so every group lands on
    BOTH and pays two cold admissions (12 requests - 6 colds = 6 hits);
    affinity pins each group to one replica (12 - 3 colds = 9 hits).
    The per-replica prefix-hit counters must show exactly that gap."""
    sa, sb, *_rest, mk_router = fleet

    def run_leg(policy):
        before = sa.prefix_hits + sb.prefix_hits
        router = mk_router(policy=policy)
        with RouterClient(router.address) as c:
            for _rep in range(4):
                for group in (10, 11, 12):
                    prompt = _prompt(group)  # 33 tokens = 2 shareable pages
                    out = c.generate(prompt, 4)
                    assert np.array_equal(out, _solo(params, prompt, 4))
        router.stop()
        return sa.prefix_hits + sb.prefix_hits - before

    hits_rr = run_leg("round_robin")
    # flush every warm page so the affinity leg replays identical traffic
    sa.release_prefix_cache()
    sb.release_prefix_cache()
    hits_aff = run_leg("affinity")
    assert hits_aff > hits_rr, (hits_aff, hits_rr)
    assert hits_aff == 9 and hits_rr == 6, (hits_aff, hits_rr)


def test_wrong_affinity_hint_is_harmless(fleet, params):
    """Poison the shadow map: claim replica B holds a prefix it has never
    seen. The router routes there (hint honored), B admits cold, and the
    output is still bit-identical — affinity is advisory, period."""
    _sa, _sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    prompt = _prompt(21)
    router.registry.learn("B", page_hashes(prompt[0], PS))
    with RouterClient(router.address) as c:
        out = c.generate(prompt, 5)
        assert c.last_replica == "B"
        assert c.last_route["affinity_depth"] == 2
        assert np.array_equal(out, _solo(params, prompt, 5))


# -- satellite 2: eviction propagates to the shadow map --------------------


def test_release_prefix_cache_evicts_router_shadow(fleet):
    sa, sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    prompt = _prompt(31)
    hashes = page_hashes(prompt[0], PS)
    with RouterClient(router.address) as c:
        c.generate(prompt, 4)
        warm = c.last_replica
    assert router.registry.warmth(warm, hashes) == len(hashes) == 2
    # the replica flushes its prefix map; the next stats poll ships the
    # evicted hashes and the router must forget the warmth
    (sa if warm == "A" else sb).release_prefix_cache()
    router.refresh_stats()
    assert router.registry.warmth(warm, hashes) == 0


# -- SLO tiers: shed under pressure, admit after ---------------------------


def test_shed_then_admit_under_queue_pressure(fleet, params):
    sa, sb, *_rest, mk_router = fleet
    router = mk_router(policy="least_loaded", shed_depth={2: 0})

    def block(server, i):
        with InferenceClient(server.address) as c:
            c.generate(_prompt(40 + i, plen=16), 30)

    # saturate BOTH replicas directly: 2 slots busy + 2 queued each
    blockers = []
    for server in (sa, sb):
        for i in range(sa.serving.max_slots + 2):
            t = threading.Thread(target=block, args=(server, i))
            t.start()
            blockers.append(t)
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if (sa._queue.qsize() + len(sa._backlog) > 0
                and sb._queue.qsize() + len(sb._backlog) > 0):
            break
        time.sleep(0.005)
    router.refresh_stats()
    with RouterClient(router.address, tier=2) as c:
        prompt = _prompt(50)
        with pytest.raises(RequestShed) as exc:
            c.generate(prompt, 3)
        assert exc.value.tier == 2 and exc.value.queue_depth > 0
        # tier 0 (interactive) has no shed threshold: it queues, it runs
        out = c.generate(prompt, 3, tier=0)
        assert np.array_equal(out, _solo(params, prompt, 3))
        for t in blockers:
            t.join(timeout=120.0)
        router.refresh_stats()  # queues drained: tier 2 admits again
        out = c.generate(prompt, 3)
        assert np.array_equal(out, _solo(params, prompt, 3))
        shed = router._tel.counter_value("router_shed_total", tier="2")
        assert shed == 1.0, shed


# -- drain and failover ----------------------------------------------------


def test_drain_refusal_and_failover(fleet, params):
    sa, sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    prompt = _prompt(60)
    with RouterClient(router.address) as c:
        c.generate(prompt, 4)
        warm = c.last_replica
        warm_server = sa if warm == "A" else sb
        warm_server.begin_drain()
        try:
            # direct client: structured refusal, not an opaque handler error
            with InferenceClient(warm_server.address) as direct:
                with pytest.raises(RequestRefused):
                    direct.generate(prompt, 4)
            # routed client: the refusal fails over to the peer, same bits
            out = c.generate(prompt, 4)
            assert c.last_replica != warm
            assert c.last_route["failovers"] == 1
            assert np.array_equal(out, _solo(params, prompt, 4))
        finally:
            warm_server.end_drain()


def test_whole_fleet_drain_is_structured_refusal(fleet, params):
    """With EVERY replica draining, the router passes the structured
    drain refusal through (typed RequestRefused client-side, and not
    counted as an accepted request) instead of surfacing an opaque
    no-live-replica handler error; ending the drain restores service
    with identical bits."""
    sa, sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    prompt = _prompt(65)
    sa.begin_drain()
    sb.begin_drain()
    try:
        with RouterClient(router.address) as c:
            with pytest.raises(RequestRefused):
                c.generate(prompt, 4)
    finally:
        sa.end_drain()
        sb.end_drain()
    assert router._tel.counter_value("router_requests_total", tier="1") == 0.0
    router.refresh_stats()  # pick up the cleared drain flags
    with RouterClient(router.address) as c:
        out = c.generate(prompt, 4)
    assert np.array_equal(out, _solo(params, prompt, 4))
    assert router._tel.counter_value("router_requests_total", tier="1") == 1.0


def test_faultplan_kill_mid_decode_exactly_once(fleet, params, monkeypatch):
    """Seeded FaultPlan tears the router->A connection on A's 3rd
    forwarded generate, while A is mid-decode on the 2nd: both requests
    complete exactly once on survivor B with bit-identical output, and
    replaying a completed request_id against B returns the cached ack
    without re-entering the engine."""
    sa, sb, _ta, _tb, mk_router = fleet
    # A fetches each decode chunk 10 ms late, so that its ~8 chunks keep
    # it mid-decode until the scripted reset lands: at this size a chunk
    # takes well under a millisecond, and the engine enqueues the next
    # chunk before it fetches the running one
    settle = sa._settle

    def late_settle(chunk):
        time.sleep(0.01)
        return settle(chunk)

    monkeypatch.setattr(sa, "_settle", late_settle)
    plan = FaultPlan(seed=13, schedule=[
        ScriptedFault(event="generate", nth=3, action="reset")])
    router = mk_router(policy="affinity", fault_plan_a=plan)
    shared = _prompt(70)
    with RouterClient(router.address) as c:
        # 1st generate on A (cold fleet routes to the first replica) —
        # warms A so the two kill-phase requests both prefer it
        c.generate(shared, 3)
        assert c.last_replica == "A"
        results = {}
        # one shared page (17 tokens) leaves decode room for 31 tokens:
        # ~8 engine dispatches, each fetched late (above), keep A
        # mid-decode while the scripted reset lands
        long_prompt = shared[:, :17]

        def long_decode():
            with RouterClient(router.address) as cl:
                results["long"] = (cl.generate(long_prompt, 31, seed=0),
                                   cl.last_route)

        t = threading.Thread(target=long_decode)
        t.start()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:  # wait until A is mid-decode
            if any(r is not None for r in sa._slot_req):
                break
            time.sleep(0.002)
        # 3rd generate on A: the scripted reset fires at send, tearing
        # the connection out from under the in-flight long decode too
        out = c.generate(shared, 5)
        t.join(timeout=120.0)
        assert not t.is_alive()
        assert c.last_replica == "B" and c.last_route["failovers"] >= 1
        assert np.array_equal(out, _solo(params, shared, 5))
        long_out, long_route = results["long"]
        assert long_route["replica"] == "B"
        assert np.array_equal(long_out, _solo(params, long_prompt, 31))
        failovers = router._tel.counter_value("router_failovers_total")
        assert failovers >= 2.0, failovers
        # exactly-once: replay a completed request_id on the survivor —
        # cached ack, identical bits, no new engine admission
        with InferenceClient(sb.address) as direct:
            first = direct.generate(shared, 5, request_id="replay-proof")
            admitted = sb.batched_requests
            again = direct.generate(shared, 5, request_id="replay-proof")
            assert np.array_equal(first, again)
            assert sb.batched_requests == admitted  # served from cache


def test_request_id_dedup_in_flight_gating(fleet, params):
    """Two concurrent generates with the SAME request_id produce one
    engine admission: the duplicate parks on the original's in-flight
    gate and both answer identical bits."""
    sa, *_ = fleet
    prompt = _prompt(80, plen=16)
    outs = []

    def call():
        with InferenceClient(sa.address) as c:
            outs.append(c.generate(prompt, 24, request_id="dup-1"))

    before = sa.batched_requests
    threads = [threading.Thread(target=call) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert len(outs) == 2
    assert np.array_equal(outs[0], outs[1])
    assert np.array_equal(outs[0], _solo(params, prompt, 24))
    assert sa.batched_requests - before == 1


def test_router_snapshot_and_metrics(fleet):
    _sa, _sb, _ta, _tb, mk_router = fleet
    router = mk_router(policy="affinity")
    with RouterClient(router.address) as c:
        prompt = _prompt(90)
        c.generate(prompt, 3)
        c.generate(prompt, 3)
    snap = router.registry.snapshot()
    assert set(snap) == {"A", "B"}
    assert sum(r["routed"] for r in snap.values()) == 2
    tel = router._tel
    assert tel.counter_value("router_requests_total", tier="1") == 2.0
    assert tel.counter_value("router_affinity_hits_total") == 1.0
    assert tel.gauge("router_replicas_live").value == 2
