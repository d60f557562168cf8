"""Inference server: serve KV-cache decoding over the wire transport.

The reference's architecture is a training hub (server owns the model,
workers push gradients); this extends the same server/client split to
inference — a host that owns device-resident params answers generate /
beam-search requests from remote clients over the framework's native
transport (length-prefixed binary frames + acks, ``comm/transport.py``),
reusing ``DownloadMsg``-style dict payloads with packed int32 token
buffers.

Events (arrays travel as ``pack_bytes``/``SerializedArray`` buffers, the
same encoding every other message type uses):

- ``model_info``  {} -> {vocab_size, max_seq, d_model, n_layers, n_heads,
  name}
- ``generate``    {prompt: <packed {tokens}>, n_tokens, temperature?,
  top_k?, top_p?, eos_id?, seed?} -> {result: <packed {tokens}>,
  serving: {path, queue_ms?}}
- ``beam``        {prompt: <packed {tokens}>, n_tokens, beam_size?,
  length_penalty?, eos_id?} -> {result: <packed {tokens, scores}>}
- ``score``       {prompt: <packed {tokens}>, from_pos} ->
  {result: <packed {scores}>} — teacher-forced log P(tokens[from_pos:])

**Continuous batching** (this round, replacing the round-3 same-signature
window batcher): ``generate`` requests are served by a persistent decode
loop over a fixed-capacity, slot-partitioned KV cache
(``[max_slots, max_seq, ...]``; device half in ``models/generate.py``).
Each slot carries its own length, eos flag, remaining-token budget and
per-request RNG seed, so requests of *different* prompt lengths, budgets
and sampling settings share every decode iteration:

- **admission**: between decode iterations, queued requests are prefilled
  (grouped by prompt length, optionally in ``prefill_chunk`` pieces) and
  scattered into free slots in one dispatch;
- **iteration**: one jit program advances ALL live rows ``decode_chunk``
  tokens; finished rows freeze to eos inside the scan exactly like the
  solo path;
- **retirement**: rows that hit eos or their budget retire at the next
  chunk boundary and their caller is answered immediately — nobody waits
  for the slowest member of a "group", because there are no groups.

Greedy decoding is row-independent, so each caller gets bit-identical
output to a solo request. Sampled requests batch too (new): a row's keys
are ``fold_in(PRNGKey(seed), position)`` where the position depends only
on the request's own progress, so the per-request ``seed`` determinism
contract holds regardless of batch composition. Requests that cannot use
the engine (``B`` rows > free capacity ever possible, i.e. ``B >
max_slots``, or multi-row sampled prompts whose historical contract ties
all rows to ONE key stream) fall back to the serialized solo path.

**Speculative decoding** (round 12, ``ServingConfig.speculate_k``; design
in docs/PERFORMANCE.md §7g): under the paged layout a small draft model
proposes ``k`` tokens per round and the target verifies all ``k + 1``
positions in one multi-token pass, so a round emits 1..k+1 tokens for one
target dispatch. Greedy rows stay bit-identical to solo decode; sampled
rows use the rejection-sampling correction under the same per-row
``fold_in(seed, position)`` determinism. The draft's KV rides its own
page tables over the SAME ``_PagePool``, so admission reserves — and
retirement/disconnect reclaims — both models' pages through one
allocator, exactly once.

**Mesh-aware serving** (round 3): ``params`` may be Megatron/TP-sharded
device arrays — the decode programs GSPMD-partition from the param
shardings (heads-sharded KV cache, psum'd o_proj; see
``models/generate.py``), so a server can serve straight from a trainer's
``get_params()`` on a multi-device mesh without replicating anything
(tests/test_tp_decode.py::test_inference_server_serves_tp_sharded_params).
"""

from __future__ import annotations

import contextlib
import queue as queue_mod
import threading
import time as time_mod
import warnings
from collections import OrderedDict, deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

from distriflow_tpu.analysis.witness import PoolWitness
from distriflow_tpu.comm.transport import ServerTransport
from distriflow_tpu.fleet.prefix_hash import page_hashes
from distriflow_tpu.models.generate import (
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    _build_spec_fns,
    _check_fits,
    _find_cache_leaf,
    beam_search,
    decode_family,
    generate,
    paged_cache,
    pages_per_slot,
    sequence_logprob,
    set_page_tables,
    slot_cache,
)
from distriflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    compute_view,
)
from distriflow_tpu.models.zoo import draft_config_for
from distriflow_tpu.obs import FleetTable, get_telemetry
from distriflow_tpu.utils.config import ServingConfig
from distriflow_tpu.utils.logging import VerboseLogger
from distriflow_tpu.utils.serialization import (
    deserialize_array,
    pack_bytes,
    serialize_array,
    unpack_bytes,
)

# Compatibility defaults: ``ServingConfig`` fields left ``None`` read these
# at USE time, so tests (and soaks) that monkeypatch the module constants
# keep working unchanged.
MAX_PROMPT_BATCH = 64  # refuse absurd wire batches before touching the device
BATCH_WINDOW_S = 0.004  # collection window after the first idle-state request


class _Request:
    """One queued ``generate`` request awaiting the engine."""

    __slots__ = (
        "prompt", "n_tokens", "temperature", "top_k", "top_p", "eos",
        "seed", "client_id", "enq_t", "admit_t", "rows_out", "rows_left",
        "cancelled", "done", "result", "error", "page_plan",
        "trace_id", "parent_span", "request_id", "tier", "first_tok_t",
        "ttft_ms", "tpot_ms",
    )

    def __init__(self, prompt: np.ndarray, n_tokens: int, temperature: float,
                 top_k: int, top_p: float, eos: int, seed: int,
                 client_id: str):
        self.prompt = prompt
        self.n_tokens = n_tokens
        self.temperature = temperature
        self.top_k = top_k          # 0 = off
        self.top_p = top_p          # 1.0 = off
        self.eos = eos              # -1 = no eos
        self.seed = seed
        self.client_id = client_id
        self.enq_t = time_mod.monotonic()
        self.admit_t: Optional[float] = None
        self.rows_out: List[Optional[np.ndarray]] = [None] * prompt.shape[0]
        self.rows_left = prompt.shape[0]
        self.cancelled = False
        self.done = threading.Event()
        self.result: Optional[np.ndarray] = None
        self.error: Optional[Exception] = None
        # paged layout: per-row page reservation ({"shared", "owned",
        # "hashes", "committed"}), made when the admission gate accepts
        # the request and released either at slot retirement (committed)
        # or by _release_plan (admission failure)
        self.page_plan: Optional[List[Dict[str, Any]]] = None
        # request-trace plane (docs/OBSERVABILITY.md §11): wire headers
        # parsed off the payload (empty = untraced, all span emission
        # short-circuits), plus the SLO anchors the retire span and the
        # ack's serving_meta report back
        self.trace_id = ""
        self.parent_span = ""
        self.request_id: Optional[str] = None
        self.tier = 0
        self.first_tok_t: Optional[float] = None
        self.ttft_ms: Optional[float] = None
        self.tpot_ms: Optional[float] = None


class _PagePool:
    """Host-side allocator for the paged KV cache's physical pages.

    Pure bookkeeping — the device never sees this object, only the page
    tables it produces. ``alloc`` hands out free pages at refcount 1;
    ``ref``/``unref`` move shared prefix pages between owners (the
    prefix map holds its own reference, so a page stays warm after its
    original request retires until pool pressure evicts it). All methods
    run on the single scheduler thread; no locking needed."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self._refs = np.zeros((n_pages,), np.int32)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def refcount(self, page: int) -> int:
        return int(self._refs[page])

    # dfcheck: pairs acquire=alloc release=unref
    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    # dfcheck: pairs acquire=ref release=unref mode=state
    def ref(self, pages: List[int]) -> None:
        for p in pages:
            if self._refs[p] <= 0:
                raise RuntimeError(f"ref of free page {p}")
            self._refs[p] += 1

    def unref(self, pages: List[int]) -> int:
        """Drop one reference per page; returns how many hit zero and
        went back on the free list."""
        freed = 0
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] == 0:
                self._free.append(p)
                freed += 1
            elif self._refs[p] < 0:
                raise RuntimeError(f"unref of free page {p}")
        return freed


def _prompt_from(payload: Dict[str, Any], limit: Optional[int] = None) -> np.ndarray:
    cap = MAX_PROMPT_BATCH if limit is None else limit
    arr = deserialize_array(unpack_bytes(payload["prompt"])["tokens"])
    if arr.ndim != 2:
        raise ValueError(f"prompt must be [B, P], got shape {arr.shape}")
    if not 1 <= arr.shape[0] <= cap:
        raise ValueError(
            f"prompt batch {arr.shape[0]} outside [1, {cap}]"
        )
    if not np.issubdtype(arr.dtype, np.integer):
        raise ValueError(f"prompt must be integer tokens, got {arr.dtype}")
    return arr.astype(np.int32)


class InferenceServer:
    """Serve a trained LM's decoding over the native transport."""

    def __init__(
        self,
        config: TransformerConfig,
        params: Any,
        host: str = "127.0.0.1",
        port: int = 0,
        verbose: Optional[bool] = None,
        serving: Optional[ServingConfig] = None,
        telemetry: Any = None,
        draft_params: Any = None,
    ):
        self.config = config
        self.params = params
        self.serving = (serving or ServingConfig()).validate()
        self.logger = VerboseLogger("InferenceServer", verbose)
        self._device_lock = threading.Lock()  # one device program at a time
        # the server's telemetry, so the transport's frame counters and its
        # handler_wait spans land where the engine's request spans do
        self.transport = ServerTransport(host, port, telemetry=telemetry)
        self.transport.on("model_info", self._on_info)
        self.transport.on("generate", self._on_generate)
        self.transport.on("beam", self._on_beam)
        self.transport.on("score", self._on_score)
        self.transport.on("fleet_stats", self._on_fleet_stats)
        self.transport.on("drain", self._on_drain)
        self.transport.on("hedge_cancel", self._on_hedge_cancel)
        self.transport.on_disconnect = self._on_client_disconnect
        # fleet-router plane (round 13; docs/PERFORMANCE.md §7h):
        # draining refuses NEW generates with a structured ack (in-flight
        # work completes; the router fails refused requests over to a
        # peer); request-id dedup is the PR 1 idempotency pattern applied
        # to serving — a replayed id returns the cached ack (bounded LRU)
        # and a duplicate of an IN-FLIGHT id rides the original compute
        self._draining = False
        self._dedup_lock = threading.Lock()
        self._req_results: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()  # guarded-by: _dedup_lock
        self._req_live: Dict[str, threading.Event] = {}  # guarded-by: _dedup_lock
        self._dedup_cap = 256
        # prefix hashes evicted from _prefix_map since the last stats
        # poll, shipped (hex) in the fleet_stats ack so the router's
        # shadow map forgets them too. Bounded deque; single-consumer
        # (one router) — appends on the scheduler thread, drains on a
        # handler thread, both ends atomic on a deque.
        self._evicted_prefixes: Deque[bytes] = deque(maxlen=512)
        # per-prefix-page hit counters (round 19): chain hash -> times an
        # admission reused it. Single-writer (scheduler thread, in
        # _reserve); pruned when the entry leaves _prefix_map. The top
        # entries ship as fleet_stats v2 ``warm_prefixes`` so router
        # shadow maps rebuild from replica truth, not routing history.
        self._prefix_hit_counts: Dict[bytes, int] = {}
        # per-server plain stat fields for the stats ack: the obs
        # registry may be process-shared across in-process replicas
        # (tests/bench), so fleet routing signals must not read it
        self.prefix_hits = 0  # single-writer: scheduler thread
        self.spec_accept_per_step = 0.0  # single-writer: scheduler thread
        # continuous-batching engine (module docstring): queue + one
        # scheduler thread; plain-int counters kept for tests/soaks that
        # read them directly, mirrored into the obs registry below
        self._queue: "queue_mod.Queue[Optional[_Request]]" = queue_mod.Queue()
        self._backlog: Deque[_Request] = deque()  # pulled, awaiting a slot
        self._dispatcher: Optional[threading.Thread] = None
        # an Event, not a bare bool: stop() flips it from a control thread
        # while handler threads re-check it post-enqueue (the TOCTOU close
        # in _on_generate) — the Event makes the publish explicit instead
        # of leaning on the GIL for visibility
        self._stopped = threading.Event()
        # single-writer counters: mutated ONLY on the scheduler thread,
        # read cross-thread by tests/soaks (GIL-atomic int loads)
        self.decode_batches = 0  # engine decode iterations dispatched
        self.batched_requests = 0  # requests admitted into the engine
        # requests owned by each live connection, so a disconnect can
        # cancel its queued work and free its slots (chaos-reset tests)
        self._inflight_lock = threading.Lock()
        self._inflight: Dict[str, List[_Request]] = {}  # guarded-by: _inflight_lock
        # slot state (host side; device cache allocated lazily on first
        # admission). Free slots sit with done=True so the decode scan
        # leaves them frozen; their writes stay confined to their own row.
        s = self.serving.max_slots
        self._slot_cache: Any = None
        self._tok = np.zeros((s,), np.int32)
        self._done = np.ones((s,), bool)
        self._temps = np.zeros((s,), np.float32)
        self._top_ks = np.zeros((s,), np.int32)
        self._top_ps = np.ones((s,), np.float32)
        self._seeds = np.zeros((s,), np.int32)
        self._eos = np.full((s,), -1, np.int32)
        self._slot_req: List[Optional[_Request]] = [None] * s
        self._slot_row = np.zeros((s,), np.int32)
        self._slot_emitted = np.zeros((s,), np.int64)
        # paged KV layout (round 9; kv_layout="slab" keeps the legacy
        # worst-case slabs as the bit-identity oracle). The host owns the
        # authoritative page table; every mutation marks it dirty and the
        # next insert/decode dispatch re-uploads it, so a retired slot's
        # frozen writes can never land in a page the pool has re-issued.
        self._paged = self.serving.kv_layout == "paged"
        family = decode_family(config)
        # a cache leaf some layer of every family holds: the one whose
        # buffer says whether a donating call took the pools over
        self._pool_leaf = family.pool_leaves[0]
        # per-row state leaves (a recurrent family's): a row's cache is then
        # more than its pages, so a prefix hit cannot rebuild it and a
        # speculative round cannot roll it back
        self._slot_leaves = family.slot_leaves
        if self.serving.speculate_k and not family.prefix_reusable:
            raise ValueError(
                f"speculate_k={self.serving.speculate_k} with "
                f"{type(config).__name__}: its cache holds per-row state "
                f"({', '.join(family.slot_leaves)}) that a rejected draft "
                "token cannot be rolled out of by moving cache_index back; "
                "serve this family with speculate_k=0")
        #: whether admissions look prompts up in the prefix map; off for a
        #: family that is not prefix_reusable, whatever the config asks
        self._prefix_sharing = bool(
            self._paged and self.serving.prefix_sharing
            and family.prefix_reusable)
        self._pp = pages_per_slot(config.max_seq, self.serving.page_size)
        self._n_pages = self.serving.pool_pages(config.max_seq)
        self._pool = _PagePool(self._n_pages) if self._paged else None
        # pool-conservation witness (docs/ANALYSIS.md §6): with
        # DISTRIFLOW_POOL_WITNESS=1 every quiescence point asserts
        # free + referenced + shared == pool size; off, verify() is a no-op
        self._pool_witness = (
            PoolWitness(self._n_pages) if self._paged else None)
        self._tables = np.full((s, self._pp + 1), self._n_pages, np.int32)
        self._tables_dirty = False
        self._slot_pages: List[List[int]] = [[] for _ in range(s)]
        # prefix-reuse map: chain hash of a prompt's j-th full page ->
        # physical page id. The map holds one reference per entry;
        # insertion order doubles as LRU (move_to_end on hit), and pool
        # pressure evicts from the cold end.
        self._prefix_map: "OrderedDict[bytes, int]" = OrderedDict()
        # speculative decoding (round 12; docs/PERFORMANCE.md §7g): the
        # draft model keeps its OWN paged cache but draws page ids from
        # the SAME _PagePool — one allocator, so draft KV competes with
        # target KV for the pool honestly and every occupancy metric
        # already accounts for it. ``draft_model="self"`` shares the
        # target's params (self-speculation: the mechanical ceiling the
        # bench measures); otherwise a zoo draft config, with ``params``
        # passed in or deterministically initialised at seed 0.
        self._spec_k = self.serving.speculate_k
        self._self_draft = False
        self.draft_config: Optional[TransformerConfig] = None
        self.draft_params: Any = None
        self._draft_view: Any = None
        self._draft_cache: Any = None
        self._draft_tables = np.zeros((0, 0), np.int32)
        self._draft_tables_dirty = False
        self._draft_pages: List[List[int]] = [[] for _ in range(s)]
        if self._spec_k:
            name = self.serving.draft_model or "lm_draft"
            self.draft_config = draft_config_for(name, config)
            self._self_draft = name == "self"
            if self._self_draft:
                self.draft_params = None  # always read self.params live
            elif draft_params is not None:
                self.draft_params = draft_params
            else:
                variables = TransformerLM(self.draft_config, mesh=None).init(
                    jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
                self.draft_params = {"params": variables["params"]}
            if not self._self_draft:
                # a separate draft tree is never swapped: its view is
                # made once, here
                self._draft_view = compute_view(
                    self.draft_config, self.draft_params)
            self._draft_tables = np.full(
                (s, self._pp + 1), self._n_pages, np.int32)
        # serving metrics (contract table in docs/OBSERVABILITY.md §1)
        tel = telemetry if telemetry is not None else get_telemetry()
        self._m_batches = tel.counter(
            "serving_decode_batches_total",
            help="decode batches dispatched by the engine loop")
        self._m_admitted = tel.counter(
            "serving_batched_requests_total",
            help="requests admitted into a decode slot")
        self._m_tokens = tel.counter(
            "serving_tokens_generated_total",
            help="output tokens committed across all slots")
        self._m_slots = tel.gauge(
            "serving_slots_active", help="decode slots currently occupied")
        self._m_qwait = tel.histogram(
            "serving_queue_wait_ms",
            help="enqueue-to-admission wait per request (ms)")
        # per-tier SLO surfaces (docs/OBSERVABILITY.md §11): TTFT is the
        # enqueue -> first-token wall per request; TPOT is per-SLOT
        # decode-interval time per emitted token (satellite 1: the old
        # single histogram divided one batch dispatch across all active
        # slots, conflating every co-resident request)
        self._m_ttft = {t: tel.histogram(
            "serving_ttft_ms", tier=str(t),
            help="enqueue-to-first-token wall per request (ms), by tier")
            for t in (0, 1, 2)}
        self._m_tpot = {t: tel.histogram(
            "serving_time_per_output_token_ms", tier=str(t),
            help="per-slot decode interval per emitted token (ms), by tier")
            for t in (0, 1, 2)}
        # running per-tier worst-request watermarks: a new maximum drops
        # a ttft_high/tpot_high flight event naming the request, so the
        # sentinel's breach bundle carries the offending trace (§11)
        self._ttft_peak = {0: 0.0, 1: 0.0, 2: 0.0}
        self._tpot_peak = {0: 0.0, 1: 0.0, 2: 0.0}
        # per-slot clock of the last token-emission event (first token at
        # admission, then every decode/spec commit) — the denominator
        # anchor for per-slot TPOT intervals
        self._slot_emit_t = [0.0] * s
        self._m_pages = tel.gauge(
            "serving_page_occupancy",
            help="fraction of KV-cache pages currently allocated")
        self._m_prefix_hits = tel.counter(
            "serving_prefix_hits_total",
            help="admissions that reused a cached prefix")
        self._m_dedup_hits = tel.counter(
            "serving_dedup_hits_total",
            help="duplicate request_ids suppressed by the dedup gate "
                 "(cached-ack returns + in-flight parks)")
        self._m_hedge_cancelled = tel.counter(
            "serving_hedge_cancelled_total",
            help="in-flight requests flagged cancelled by hedge_cancel")
        self._m_prefix_tokens = tel.counter(
            "serving_prefix_tokens_saved_total",
            help="prompt tokens skipped via prefix-cache reuse")
        self._m_pages_alloc = tel.counter(
            "serving_pages_allocated_total", help="KV-cache pages allocated")
        self._m_pages_freed = tel.counter(
            "serving_pages_released_total", help="KV-cache pages released")
        self._m_spec_proposed = tel.counter(
            "serving_spec_proposed_total",
            help="draft tokens proposed by speculative decoding")
        self._m_spec_accepted = tel.counter(
            "serving_spec_accepted_total",
            help="draft tokens accepted by the target model")
        self._m_spec_rate = tel.gauge(
            "serving_spec_accepted_per_step",
            help="accepted draft tokens per speculative step")
        # every program that returns the cache's successor donates the
        # pools (models/generate.py::_CacheProgram); these say, per call,
        # whether the runtime took them over or the call copied the pool
        self._m_cache_donated = {p: tel.counter(
            "serving_cache_donations_total", program=p,
            help="engine dispatches that updated the KV pools in place")
            for p in ("decode", "insert", "spec")}
        self._m_cache_copied = {p: tel.counter(
            "serving_cache_copies_total", program=p,
            help="engine dispatches whose donated KV pools were not "
                 "usable, so the whole pool was copied")
            for p in ("decode", "insert", "spec")}
        self._copy_warned: set = set()  # single-writer: scheduler thread
        # a family whose decode step reads a chosen part of the context and
        # of its experts says how much (``config.decode_work``); the engine
        # counts it per dispatch. TransformerConfig declares none.
        self._decode_work = getattr(config, "decode_work", None)
        self._work_leaf = decode_family(config).work_leaf
        self._work_seen = np.zeros((2,), np.int64)
        if self._decode_work is not None:
            self._m_ctx_tokens = tel.counter(
                "serving_context_tokens_total",
                help="cached tokens of the live rows, summed over decode "
                     "dispatches (at each dispatch's start)")
            self._m_sel_tokens = tel.counter(
                "serving_sparse_selected_tokens_total",
                help="of those, the tokens sparse attention selects")
            self._m_experts_run = tel.counter(
                "serving_experts_run_total",
                help="held experts that ran, summed over sparse layers and "
                     "decode steps")
            self._m_assignments = {held: tel.counter(
                "serving_expert_assignments_total", held=held,
                help="(token, expert) choices of live rows in decode steps, "
                     "by whether this server holds the expert")
                for held in ("yes", "no")}
            self._m_rows_run = tel.counter(
                "serving_sparse_rows_run_total",
                help="rows the sparse selector and attention run, summed "
                     "over decode steps (every layer of a step runs the "
                     "same rows)")
            self._m_rows_live = tel.counter(
                "serving_sparse_rows_live_total",
                help="of those, the rows that are live")
        if self._slot_leaves:
            self._m_ssm_rows = tel.counter(
                "serving_ssm_rows_stepped_total",
                help="rows whose recurrent state a decode step read and "
                     "wrote, summed over decode steps (every state-space "
                     "layer of a step steps the same rows)")
            self._m_row_state = tel.gauge(
                "serving_row_state_bytes",
                help="bytes the per-row state leaves of the slot cache hold "
                     "(all slots, every layer that keeps one)")
        # what the decode loop reads: ``params`` with every weight the
        # block consumes in ``config.dtype`` already cast (compute_view),
        # made here and at each set_params, never at a dispatch
        self._m_view_builds = tel.counter(
            "serving_param_view_builds_total",
            help="compute-dtype views made of the served weights: 1 at "
                 "construction, +1 per set_params, never per dispatch")
        self._m_view_bytes = tel.gauge(
            "serving_param_view_bytes",
            help="bytes the compute-dtype view holds beyond the served "
                 "weights (0 for weights already in the compute dtype)")
        self._param_view: Any = None
        self._build_param_view()
        # continuous phase profiler (docs/OBSERVABILITY.md §5): serving
        # records phases only — the engine loop mostly idles in _gather, so
        # a per-iteration step() would drown the digests in idle wall time
        self._prof = tel.profiler("engine")
        # fleet rows for the serving side: under the paged layout each
        # client's row carries the KV pages it currently holds, so a soak
        # operator can spot the connection pinning the pool
        self.fleet = FleetTable()
        self._tel = tel
        tel.register_fleet(id(self), self.fleet.snapshot)

    # -- lifecycle ---------------------------------------------------------

    def setup(self) -> "InferenceServer":
        self._stopped.clear()
        # restart hygiene: a request that raced a previous stop() was
        # error-completed but may still sit in the queue — the new
        # scheduler must not serve orphans whose callers already errored
        self._drain_and_error()
        self.transport.start()
        self._dispatcher = threading.Thread(
            target=self._engine_loop, daemon=True,
            name="inference-batcher")
        self._dispatcher.start()
        self.logger.log(f"serving on {self.address}")
        return self

    def stop(self) -> None:
        self._stopped.set()  # before the drain: closes the enqueue race
        self.transport.stop()
        if self._dispatcher is not None:
            self._queue.put(None)  # wake + exit sentinel
            self._dispatcher.join(timeout=5.0)
            self._dispatcher = None
        # a handler may have enqueued between the scheduler's final drain
        # and _stopped landing in its view; sweep once more so no waiter is
        # left to the 600 s backstop
        self._drain_and_error()
        self._tel.unregister_fleet(id(self))
        # scheduler joined: pool state is quiescent and safe to audit here
        self.verify_pool_conservation("stop")

    @property
    def address(self) -> str:
        return self.transport.address

    def set_params(self, params: Any) -> None:
        """Swap serving weights (e.g. after a training round). Requests
        mid-decode continue on the NEW params from their next chunk — the
        engine re-reads ``self.params`` and the view of them every
        dispatch, and the one cast that makes the view runs here; the KV
        cache is config-shaped only, so it survives the swap. Under
        ``draft_model="self"`` the draft follows automatically —
        :meth:`_live_draft_params` and :meth:`_live_draft_view` read the
        served tree and its view at dispatch."""
        with self._device_lock:
            self.params = params
            self._build_param_view()

    def _build_param_view(self) -> None:
        """Rebind ``_param_view`` to the compute-dtype view of
        ``self.params``. The programs that run the block in a loop take it
        (the decode chunk, the speculative round, the one-shot ``generate``
        and ``beam_search``): XLA hoists a loop's weight casts to the head
        of the call and re-makes a copy of every weight each dispatch.
        The programs that run the block once (prefill, extend, ``score``)
        take ``self.params``: there the cast fuses into the matmul's
        operand, and on the TPU their float32 form is the one that
        compiles small (PERF.md §6 PR 31)."""
        self._param_view = None  # the old copy goes before the new is made
        self._param_view = compute_view(self.config, self.params)
        self._m_view_builds.inc()
        self._m_view_bytes.set(sum(
            v.nbytes for p, v in zip(jax.tree.leaves(self.params),
                                     jax.tree.leaves(self._param_view))
            if v is not p))

    def lower_decode(self, sampling: bool = False) -> "jax.stages.Lowered":
        """The engine's decode-chunk program lowered at the live cache's
        shapes (nothing runs). The cache is allocated at the first
        admission, so serve a request first. ``chip_smoke.py`` reads the
        compiled text for the paged flash-decode Mosaic call."""
        if self._slot_cache is None:
            raise RuntimeError(
                "no KV cache yet: it is allocated at the first admission")
        _insert, _pick, decode = _build_slot_fns(
            self.config, self.serving.decode_chunk, sampling)
        with self._device_lock:
            return decode.lower(
                self._param_view, self._slot_cache, self._tok,
                self._done, self._temps, self._top_ks, self._top_ps,
                self._seeds, self._eos)

    def _live_draft_params(self) -> Any:
        return self.params if self._self_draft else self.draft_params

    def _live_draft_view(self) -> Any:
        """What the draft's loop programs read (``_build_param_view``)."""
        return self._param_view if self._self_draft else self._draft_view

    # -- config accessors (None -> module constant, read at use time so
    #    tests that monkeypatch the constants keep working) ----------------

    def _window_s(self) -> float:
        w = self.serving.batch_window_s
        return BATCH_WINDOW_S if w is None else w

    def _prompt_cap(self) -> int:
        cap = self.serving.max_prompt_batch
        return MAX_PROMPT_BATCH if cap is None else cap

    # -- handlers (run in the transport's executor; return value = ack) ----

    def _on_info(self, client_id: str, payload: Any) -> Dict[str, Any]:
        cfg = self.config
        return {
            "name": "transformer_lm",
            "vocab_size": cfg.vocab_size,
            "max_seq": cfg.max_seq,
            "d_model": cfg.d_model,
            "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads,
        }

    def _on_client_disconnect(self, client_id: str) -> None:
        """Transport callback: cancel the departed client's work. Queued
        requests are skipped at admission; live slots retire at the next
        chunk boundary — a dead socket must not hold capacity."""
        with self._inflight_lock:
            for req in self._inflight.get(client_id, ()):
                req.cancelled = True
        self.fleet.disconnect(client_id)

    # -- fleet-router plane (round 13) -------------------------------------

    def begin_drain(self) -> None:
        """Refuse NEW generates with ``{"refused": "draining"}`` while
        in-flight work completes. The fleet router reads the flag from
        ``fleet_stats`` (and from the refusal itself) and fails new
        traffic over to peers; ``end_drain`` re-admits."""
        self._draining = True
        self.logger.log("draining: refusing new generates")

    def end_drain(self) -> None:
        self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def _on_drain(self, client_id: str, payload: Any) -> Dict[str, Any]:
        enable = bool((payload or {}).get("enable", True))
        if enable:
            self.begin_drain()
        else:
            self.end_drain()
        return {"draining": self._draining}

    # dfcheck: payload -> fleet_stats
    def _on_fleet_stats(self, client_id: str, payload: Any) -> Dict[str, Any]:
        """Routing signals for the fleet router, served as a direct ack
        on the same transport the heartbeat plane rides. Values are
        advisory snapshots (racy reads of scheduler-thread state are
        fine); ``evicted_prefixes`` is a drain — each evicted chain hash
        is shipped exactly once, to the single router this server
        assumes (satellite 2: the router forgets what the replica
        evicted, so affinity never chases cold pages)."""
        evicted: List[str] = []
        while True:
            try:
                evicted.append(self._evicted_prefixes.popleft().hex())
            except IndexError:
                break
        # v2 warm set: the hottest prefix pages by replica-side hit
        # count, as [chain_hash_hex, hits] pairs. The dict is mutated on
        # the scheduler thread; a resize mid-iteration raises
        # RuntimeError, in which case this poll ships an empty warm set
        # (advisory — the next poll catches up)
        try:
            counts = list(self._prefix_hit_counts.items())
        except RuntimeError:
            counts = []
        counts.sort(key=lambda kv: -kv[1])
        warm = [[h.hex(), int(n)] for h, n in counts[:256]]
        paged = self._paged
        return {
            "queue_depth": self._queue.qsize() + len(self._backlog),
            "slots_active": sum(
                1 for r in self._slot_req if r is not None),
            "max_slots": self.serving.max_slots,
            "draining": self._draining,
            "page_size": self.serving.page_size,
            "prefix_sharing": self._prefix_sharing,
            "page_occupancy": (
                self._pool.used_pages / self._n_pages) if paged else 0.0,
            "free_pages": self._pool.free_pages if paged else -1,
            "prefix_hits": self.prefix_hits,
            "speculate_k": self._spec_k,
            "spec_accept_per_step": self.spec_accept_per_step,
            "evicted_prefixes": evicted,
            "warm_prefixes": warm,
            "prefix_entries": len(self._prefix_map),
        }

    # dfcheck: payload payload=hedge_cancel -> hedge_cancel_ack
    def _on_hedge_cancel(self, client_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Cancel the LOSING attempt of a hedged request (round 19): flag
        every in-flight admission carrying this request_id so it is
        skipped at the backlog head or retired at the next decode-chunk
        boundary — the same cancel path a client disconnect takes.
        Correctness never depends on this ack: the dedup/in-flight gate
        already guarantees at-most-one compute per replica; cancelling
        just stops a lost race from finishing a result nobody reads."""
        rid = str(payload.get("request_id"))
        cancelled = 0
        with self._inflight_lock:
            for reqs in self._inflight.values():
                for req in reqs:
                    if req.request_id == rid and not req.cancelled:
                        req.cancelled = True
                        cancelled += 1
        if cancelled:
            self._m_hedge_cancelled.inc(cancelled)
        return {"request_id": rid, "cancelled": cancelled}

    # dfcheck: payload payload=generate_request -> generate_ack
    def _on_generate(self, client_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Generate front: drain refusal + request-id idempotency around
        :meth:`_generate_ack` (the actual decode).

        With a ``request_id`` (stamped by the fleet router, or by any
        client wanting end-to-end retry safety): a completed id returns
        its cached ack without touching the engine; an id currently
        computing parks this duplicate on the original's event and both
        answer from one compute (in-flight gating); only a novel id runs.
        The cache is a bounded LRU — far deeper than the router's
        failover window needs — and drain refusals are structured acks,
        never exceptions, because a raising handler reaches the client
        as an opaque ``None`` ack."""
        rid = payload.get("request_id")
        if rid is None:
            if self._draining:
                return {"refused": "draining"}
            return self._generate_ack(client_id, payload)
        rid = str(rid)
        with self._dedup_lock:
            cached = self._req_results.get(rid)
            if cached is not None:
                self._req_results.move_to_end(rid)
                self._m_dedup_hits.inc()
                return cached
            gate = self._req_live.get(rid)
            if gate is None and not self._draining:
                self._req_live[rid] = threading.Event()
        if gate is not None:
            # duplicate of an in-flight request: ride the original
            self._m_dedup_hits.inc()
            gate.wait(timeout=600.0)
            with self._dedup_lock:
                cached = self._req_results.get(rid)
            if cached is not None:
                return cached
            # the original errored — fall through and compute fresh
            # (deterministic decode: same bits either way)
        if self._draining:
            return {"refused": "draining"}
        try:
            ack = self._generate_ack(client_id, payload)
            with self._dedup_lock:
                self._req_results[rid] = ack
                while len(self._req_results) > self._dedup_cap:
                    self._req_results.popitem(last=False)
            return ack
        finally:
            with self._dedup_lock:
                evt = self._req_live.pop(rid, None)
            if evt is not None:
                evt.set()

    # dfcheck: payload payload=generate_request -> generate_ack
    def _generate_ack(self, client_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        prompt = _prompt_from(payload, self._prompt_cap())
        n_tokens = int(payload["n_tokens"])
        temperature = float(payload.get("temperature", 0.0))
        top_k = payload.get("top_k")
        top_p = payload.get("top_p")
        eos_id = payload.get("eos_id")
        seed = int(payload.get("seed", 0))
        rows = prompt.shape[0]
        # the engine serves single requests and row-independent (greedy)
        # multi-row prompts; multi-row SAMPLED prompts keep the solo path —
        # their historical contract derives every row from one key stream
        use_engine = (
            self._dispatcher is not None
            and n_tokens >= 1
            and rows <= self.serving.max_slots
            and (temperature == 0.0 or rows == 1)
        )
        if use_engine:
            # mirror generate()'s argument validation BEFORE enqueueing so
            # bad requests fail in this handler, not inside the engine
            _check_fits(prompt.shape[1], n_tokens, self.config)
            if top_k is not None and int(top_k) < 1:
                raise ValueError(f"top_k must be >= 1, got {top_k}")
            if top_p is not None and not 0.0 < float(top_p) <= 1.0:
                raise ValueError(f"top_p must be in (0, 1], got {top_p}")
            if eos_id is not None and not 0 <= int(eos_id) < self.config.vocab_size:
                raise ValueError(
                    f"eos_id {eos_id} outside vocab [0, {self.config.vocab_size})")
            item = _Request(
                prompt, n_tokens, temperature,
                int(top_k) if top_k is not None else 0,
                float(top_p) if top_p is not None else 1.0,
                int(eos_id) if eos_id is not None else -1,
                seed, client_id,
            )
            # trace headers (docs/OBSERVABILITY.md §11): absent on the
            # wire for untraced callers, so every engine span emission
            # below short-circuits on the empty trace_id
            item.trace_id = str(payload.get("trace_id") or "")
            item.parent_span = str(payload.get("span_id") or "")
            rid = payload.get("request_id")
            item.request_id = str(rid) if rid is not None else None
            item.tier = min(max(int(payload.get("tier", 0) or 0), 0), 2)
            with self._inflight_lock:
                self._inflight.setdefault(client_id, []).append(item)
            self._queue.put(item)
            # re-check AFTER enqueueing (TOCTOU vs stop(): the scheduler
            # may have drained and exited between the liveness check above
            # and the put) — error the item now rather than letting the
            # waiter ride the 600 s backstop
            if self._stopped.is_set() and not item.done.is_set():
                item.error = RuntimeError("inference server stopped")
                item.done.set()
            # generous last-resort bound (cold compiles can take minutes);
            # normal completion/shutdown sets the event long before this
            if not item.done.wait(timeout=600.0):
                self._unregister(item)
                raise RuntimeError(
                    "batched generate timed out awaiting the scheduler")
            self._unregister(item)
            # prefer result over error: the stop()-race path above can set
            # error while a still-draining scheduler concurrently serves
            # the item — a request that actually computed must not be
            # reported as "server stopped"
            if item.result is None and item.error is not None:
                raise item.error
            out = item.result
            meta = {"path": "slots"}  # dfcheck: payload serving_meta
            if item.admit_t is not None:
                meta["queue_ms"] = round(
                    (item.admit_t - item.enq_t) * 1000.0, 3)
            if item.page_plan is not None:
                saved = sum(len(p["shared"]) for p in item.page_plan)
                if saved:
                    meta["prefix_tokens"] = saved * self.serving.page_size
            # replica-measured SLO latencies ride the ack so the router's
            # route span (and dump --requests on the router's run dir)
            # can attribute them without reading this replica's spans
            if item.ttft_ms is not None:
                meta["ttft_ms"] = item.ttft_ms
            if item.tpot_ms is not None:
                meta["tpot_ms"] = item.tpot_ms
        else:
            with self._device_lock, self.logger.time(
                f"generate[{prompt.shape[0]}x{prompt.shape[1]}+{n_tokens}]"
            ):
                out = generate(
                    self.config, self._param_view, prompt, n_tokens,
                    temperature=temperature,
                    top_k=int(top_k) if top_k is not None else None,
                    top_p=float(top_p) if top_p is not None else None,
                    eos_id=int(eos_id) if eos_id is not None else None,
                    rng=jax.random.PRNGKey(seed),
                )
            meta = {"path": "direct"}  # dfcheck: payload serving_meta
        ack = {"result": pack_bytes({"tokens": serialize_array(out)}),
               "serving": meta}
        tid = payload.get("trace_id")
        if tid:
            ack["trace_id"] = tid  # echo: the ack joins the request trace
        return ack

    # -- continuous-batching engine ----------------------------------------

    def _engine_loop(self) -> None:
        """The scheduler: pull requests into the backlog (blocking when
        idle, with a short collection window so concurrent arrivals share
        the first admission; non-blocking between iterations), admit into
        free slots, advance every live row one ``decode_chunk``, retire.
        On shutdown every waiter — queued, backlogged, or mid-decode — is
        errored; nobody is left to the 600 s backstop."""
        while True:
            try:
                if self._gather():
                    self._shutdown_engine()
                    return
                self._admit()
                if any(r is not None for r in self._slot_req):
                    self._decode_iteration()
            except Exception as e:  # device failure: fail loud, stay up
                self.logger.log(f"engine error: {e!r}")
                self._drop_dead_cache(e)
                self._abort_all(e)

    def _gather(self) -> bool:
        """Queue -> backlog. Returns True on the shutdown sentinel."""
        idle = not self._backlog and all(r is None for r in self._slot_req)
        if idle:
            # quiescence: no backlog, no live slot, no uncommitted plan —
            # every pool page must be free, slot-held, or prefix-shared
            self.verify_pool_conservation("engine idle")
            # phase("gather"): the engine has no work, so a chip idle under
            # it waits for traffic, not for the scheduler
            with self._prof.phase("gather"):
                item = self._queue.get()
            if item is None:
                return True
            self._backlog.append(item)
            deadline = time_mod.monotonic() + self._window_s()
            # the collection window is the scheduler's own choice to wait
            with self._prof.phase("batch_window"):
                while True:
                    remaining = deadline - time_mod.monotonic()
                    if remaining <= 0:
                        return False
                    try:
                        nxt = self._queue.get(timeout=remaining)
                    except queue_mod.Empty:
                        return False
                    if nxt is None:
                        return True
                    self._backlog.append(nxt)
        while True:
            try:
                nxt = self._queue.get_nowait()
            except queue_mod.Empty:
                return False
            if nxt is None:
                return True
            self._backlog.append(nxt)

    # -- paged-layout bookkeeping (scheduler thread only) ------------------

    def _pages_needed(self, plen: int, n_tokens: int) -> int:
        """Logical pages one row holds over its FULL horizon, reserved up
        front so a live row can never hit mid-decode pool exhaustion:
        prompt plus generated tokens, rounded up to the chunk boundary
        (a row frozen at eos keeps appending until retirement). Under
        speculation a verify pass writes the whole ``[tok, d_1..d_k]``
        window, so the final round overshoots the committed horizon by up
        to ``speculate_k + 1`` positions — reserve them; positions past
        ``pages_per_slot * page_size`` drop through the table sentinel and
        never need backing pages (the ``min`` cap)."""
        chunk = self.serving.decode_chunk
        written = plen
        if self._spec_k:
            written += (n_tokens - 1) + self._spec_k + 1
        elif n_tokens > 1:
            written += -(-(n_tokens - 1) // chunk) * chunk
        ps = self.serving.page_size
        return min(-(-written // ps), self._pp)

    def _row_plan(self, tokens: np.ndarray) -> Tuple[List[int], List[bytes]]:
        """(shared leading pages, per-page chain hashes) for one prompt
        row. Hash j covers pages 0..j, so a hit guarantees the whole
        prefix matches, not just page j. The chain itself lives in
        ``fleet/prefix_hash.py`` — ONE implementation for this map and
        the fleet router's affinity scoring, so the two can never drift
        (the golden-hash test pins the chain)."""
        shared: List[int] = []
        if not self._prefix_sharing:
            return shared, []
        hashes = page_hashes(tokens, self.serving.page_size)
        for hj in hashes:
            pg = self._prefix_map.get(hj)
            if pg is None:
                break
            shared.append(pg)
            self._prefix_map.move_to_end(hj)
        return shared, hashes

    def _evict_prefix(self, shortfall: int) -> None:
        """Drop cold prefix-map entries until ``shortfall`` pages came
        free or the map is empty. An entry whose page other requests
        still reference is dropped from the map without freeing the
        page — it stops being discoverable, nothing more."""
        while shortfall > 0 and self._prefix_map:
            _h, pg = self._prefix_map.popitem(last=False)
            self._evicted_prefixes.append(_h)
            self._prefix_hit_counts.pop(_h, None)
            shortfall -= self._pool.unref([pg])

    # dfcheck: pairs acquire=_reserve release=_release_plan|_retire_slot counter=_m_pages_freed mode=state
    def _reserve(self, req: _Request) -> bool:
        """THE paged admission gate: plan every row's pages (prefix hits
        first, owned pages for the rest of the full horizon) and commit
        the reservation. False = not enough free pages even after
        evicting cold prefix entries — the caller keeps FIFO order by
        blocking on this head rather than skipping it."""
        plen = req.prompt.shape[1]
        need = self._pages_needed(plen, req.n_tokens)
        # the draft's KV is never prefix-shared (its pages hold DRAFT
        # activations — a different model — so target prefix hashes say
        # nothing about them): every draft page is owned, full horizon
        dneed = need if self._spec_k else 0
        plans: List[Dict[str, Any]] = []
        for row in range(req.prompt.shape[0]):
            shared, hashes = self._row_plan(req.prompt[row])
            plans.append({"shared": shared, "hashes": hashes,
                          "owned": None, "draft": [], "committed": False})
        # ref shared pages FIRST so eviction below can never free them
        for plan in plans:
            self._pool.ref(plan["shared"])
        total_owned = sum(need + dneed - len(p["shared"]) for p in plans)
        if total_owned > self._pool.free_pages:
            self._evict_prefix(total_owned - self._pool.free_pages)
        if total_owned > self._pool.free_pages:
            for plan in plans:
                self._pool.unref(plan["shared"])
            return False
        for plan in plans:
            plan["owned"] = self._pool.alloc(need - len(plan["shared"]))
            plan["draft"] = self._pool.alloc(dneed)
            if plan["shared"]:
                self.prefix_hits += 1
                self._m_prefix_hits.inc()
                self._m_prefix_tokens.inc(
                    len(plan["shared"]) * self.serving.page_size)
                # round-19 warm-set counters: every chain hash this row
                # reused gets a hit (scheduler thread, single writer)
                for hj in plan["hashes"][:len(plan["shared"])]:
                    self._prefix_hit_counts[hj] = (
                        self._prefix_hit_counts.get(hj, 0) + 1)
            self._m_pages_alloc.inc(
                len(plan["shared"]) + len(plan["owned"]) + len(plan["draft"]))
        req.page_plan = plans
        return True

    def _release_plan(self, plan: Optional[Dict[str, Any]]) -> None:
        """Return an UNCOMMITTED row reservation to the pool (admission
        failed before the row reached a slot). Committed plans are owned
        by their slot and released by :meth:`_retire_slot`."""
        if plan is None or plan["committed"]:
            return
        pages = plan["shared"] + plan["owned"] + plan.get("draft", [])
        self._pool.unref(pages)
        self._m_pages_freed.inc(len(pages))
        plan["committed"] = True  # never release twice

    def _register_prefix(self, plan: Dict[str, Any]) -> None:
        """Publish a freshly admitted row's full prompt pages into the
        prefix map (each new entry takes its own pool reference)."""
        pages = plan["shared"] + plan["owned"]
        for j, hj in enumerate(plan["hashes"]):
            if hj not in self._prefix_map:
                self._pool.ref([pages[j]])
                self._prefix_map[hj] = pages[j]
            else:
                self._prefix_map.move_to_end(hj)

    def _note_occupancy(self) -> None:
        if self._pool is not None:
            self._m_pages.set(self._pool.used_pages / self._n_pages)

    def _note_client_pages(self, client_id: str) -> None:
        """Refresh one connection's fleet row with the KV pages its live
        slots currently hold (0 once everything retired)."""
        held = sum(
            len(self._slot_pages[s]) + len(self._draft_pages[s])
            for s, r in enumerate(self._slot_req)
            if r is not None and r.client_id == client_id)
        self.fleet.note_pages(client_id, held)

    def _req_span(self, req: _Request, name: str, mono0: float,
                  dur_ms: float, **attrs: Any) -> None:
        """One per-request engine span (docs/OBSERVABILITY.md §11),
        externally timed via ``tracer.emit`` so the scheduler thread's
        phase accounting stays the single clock. ``start`` is derived
        from the monotonic anchor so the assembler's per-(host,pid) skew
        domain sees consistent epoch/mono pairs. Short-circuits for
        untraced requests (empty ``trace_id``) — the engine pays two
        attribute reads per call when tracing is off."""
        if not req.trace_id or not self._tel.tracer.enabled:
            return
        start = time_mod.time() - (time_mod.monotonic() - mono0)
        self._tel.tracer.emit(
            name, trace_id=req.trace_id, parent_id=req.parent_span,
            dur_ms=dur_ms, start=start, mono=mono0,
            request_id=req.request_id, tier=req.tier, **attrs)

    @contextlib.contextmanager
    def _donating(self, program: str, cache: Any):
        """Around ONE call that donates ``cache``'s pools and rebinds the
        engine's reference to the result: afterwards the pools passed in
        must be deleted (the runtime took them over and the program wrote
        in place). One still alive means XLA could not alias it and the
        call copied the whole pool. ``is_deleted`` reads a flag: no device
        sync. A call that raises is not counted."""
        pool = _find_cache_leaf(cache, self._pool_leaf)
        yield
        if pool.is_deleted():
            self._m_cache_donated[program].inc()
            return
        self._m_cache_copied[program].inc()
        if program not in self._copy_warned:
            self._copy_warned.add(program)
            warnings.warn(
                f"InferenceServer: the {program} program copied the KV "
                "pool instead of updating it in place (its donated buffers "
                "were not usable); serving_cache_copies_total counts every "
                "such dispatch", RuntimeWarning, stacklevel=3)

    def _work_leaves(self) -> List[Any]:
        """The slot cache's ``work_leaf`` leaves (``DecodeFamily``), one per
        layer that counts its work: ``(experts run, assignments held
        here)`` so far."""
        found: List[Any] = []

        def walk(node):
            for name, sub in node.items():
                if name == self._work_leaf:
                    found.append(sub)
                elif hasattr(sub, "items"):
                    walk(sub)

        walk(self._slot_cache)
        return found

    def _drop_dead_cache(self, err: Exception) -> bool:
        """After a failed device call: a donating program that fails once
        it has started executing has consumed the pools it was passed, and
        the engine must not dispatch on deleted buffers. If that happened,
        fail every resident request with ``err``, forget the prefix map
        (its pages' contents went with the pool) and drop both caches so
        the next admission allocates fresh ones. An error raised before
        execution (trace, compile, argument check) leaves the pools alive
        and this a no-op. Returns whether the caches were dropped."""
        caches = (self._slot_cache, self._draft_cache)
        if not any(c is not None
                   and _find_cache_leaf(c, self._pool_leaf).is_deleted()
                   for c in caches):
            return False
        self.logger.log(f"engine error: KV pools lost to a failed call, "
                        f"re-allocating at the next admission: {err!r}")
        with self._device_lock:
            self._slot_cache = self._draft_cache = None
        self._fail_residents(err)
        if self._paged:
            self._flush_prefix_map()
            self._tables[:] = self._n_pages
            self._draft_tables[:] = self._n_pages
        return True

    def _admit(self) -> None:
        """Move backlog requests into free slots (strict FIFO — a wide
        request blocks later ones rather than being starved), prefill
        grouped by prompt length (and shared-prefix depth under the
        paged layout), scatter into the cache, emit first tokens, retire
        rows already finished (n_tokens=1 or instant eos).

        Under the paged layout admission is gated on FREE PAGES, not on
        worst-case slots: a request enters when its rows fit the slot
        batch axis AND its full-horizon page reservation fits the pool —
        short requests no longer reserve ``max_seq`` positions they will
        never touch, which is where the mixed 1k/16k capacity win comes
        from (docs/PERFORMANCE.md)."""
        admit: List[_Request] = []
        free = sum(1 for r in self._slot_req if r is None)
        while self._backlog:
            head = self._backlog[0]
            if head.cancelled:
                self._backlog.popleft()
                self._finish_error(head, RuntimeError("client disconnected"))
                continue
            if head.prompt.shape[0] > free:
                break
            if self._paged and not self._reserve(head):
                break
            free -= head.prompt.shape[0]
            admit.append(self._backlog.popleft())
        if not admit:
            # phase("admission") opens only when there is work: the engine
            # loop polls here continuously and near-zero idle samples would
            # bury the digest's real admission cost
            return
        with self._prof.phase("admission"):
            if self._slot_cache is None:
                self._work_seen[:] = 0  # a fresh cache counts from 0
                with self._device_lock:
                    if self._paged:
                        self._slot_cache = paged_cache(
                            self.config, self.params,
                            self.serving.max_slots,
                            self.serving.page_size, self._n_pages)
                        if self._spec_k:
                            # draft pool: own KV arrays (different model
                            # dims) but the SAME page-id space as the
                            # target's, so one host allocator covers both
                            self._draft_cache = paged_cache(
                                self.draft_config,
                                self._live_draft_params(),
                                self.serving.max_slots,
                                self.serving.page_size, self._n_pages)
                    else:
                        self._slot_cache = slot_cache(
                            self.config, self.params, self.serving.max_slots)
                if self._slot_leaves:
                    self._m_row_state.set(sum(
                        leaf.nbytes for path, leaf in
                        jax.tree_util.tree_leaves_with_path(self._slot_cache)
                        if path[-1].key in self._slot_leaves))
            now = time_mod.monotonic()
            # group key: (prompt length, shared-prefix tokens) — rows with
            # the same plen but different prefix depths run different
            # suffix lengths through prefill/extend, so they cannot share
            # a dispatch. The slab layout always groups at depth 0.
            groups: Dict[Tuple[int, int], List[Tuple[_Request, int]]] = {}
            ps = self.serving.page_size
            for req in admit:
                req.admit_t = now
                self._m_qwait.observe((now - req.enq_t) * 1000.0)
                self._req_span(req, "queue_wait", req.enq_t,
                               (now - req.enq_t) * 1000.0)
                for row in range(req.prompt.shape[0]):
                    shared_len = 0
                    if self._paged and req.page_plan is not None:
                        shared_len = len(req.page_plan[row]["shared"]) * ps
                    groups.setdefault(
                        (req.prompt.shape[1], shared_len), []).append(
                            (req, row))
            lost: Optional[Exception] = None
            for (plen, shared_len), members in sorted(groups.items()):
                try:
                    if lost is not None:
                        # the pools died under an earlier group, and the
                        # shared prefix pages this group planned on with them
                        raise lost
                    self._admit_group(plen, shared_len, members)
                except Exception as e:
                    # contain a failed prefill to its own group: any slots
                    # the group already claimed stay unrecorded (free), so
                    # the next insert simply overwrites those cache rows;
                    # under the paged layout uncommitted reservations go
                    # back to the pool and claimed table rows re-sentinel
                    if self._paged:
                        for req, row in members:
                            if req.page_plan is not None:
                                self._release_plan(req.page_plan[row])
                        for s, r in enumerate(self._slot_req):
                            if r is None:
                                self._tables[s, :] = self._n_pages
                                if self._spec_k:
                                    self._draft_tables[s, :] = self._n_pages
                        self._tables_dirty = True
                        if self._spec_k:
                            self._draft_tables_dirty = True
                    if lost is None and self._drop_dead_cache(e):
                        lost = e
                    for req in {id(r): r for r, _ in members}.values():
                        self._finish_error(req, e)
            self.batched_requests += len(admit)
            self._m_admitted.inc(len(admit))
            self._m_slots.set(
                sum(1 for r in self._slot_req if r is not None))
            self._note_occupancy()

    def _admit_group(self, plen: int, shared_len: int,
                     members: List[Tuple[_Request, int]]) -> None:
        """Prefill + insert + first-token for all rows of one prompt
        length (and, under the paged layout, one shared-prefix depth).

        Slab layout: the batch axis is padded to a power-of-two bucket
        (repeat row 0) so arbitrary admission sizes don't each compile a
        fresh XLA program — same rationale as the round-3 batcher; padded
        scatter indices point one past the last slot, which JAX's
        FILL_OR_DROP scatter mode silently drops.

        Paged layout: groups run at EXACT size — admission is already
        gated on free pages rather than worst-case slot reservations, so
        the bucketing that existed to bound recompiles of huge slab
        scatters is retired here (retrace cost is one prefill trace per
        distinct group shape, and the page scatter is length-indexed, not
        slot-count-indexed). Rows with ``shared_len > 0`` skip prefill of
        the shared prefix entirely: their page tables already point at
        the shared pages, so we gather those rows into dense row caches
        and run ``extend`` over just the suffix — same chunked-prefill
        continuation the slab path uses past ``prefill_chunk``."""
        srv = self.serving
        n = len(members)
        bucket = n if self._paged else 1 << (n - 1).bit_length()
        stacked = np.stack([req.prompt[row] for req, row in members])
        free_ids = [i for i, r in enumerate(self._slot_req) if r is None]
        slots = np.array(free_ids[:n], np.int32)
        if bucket > n:
            pad = np.broadcast_to(stacked[:1], (bucket - n, plen))
            stacked = np.concatenate([stacked, pad], axis=0)
            slots = np.concatenate(
                [slots, np.full((bucket - n,), srv.max_slots, np.int32)])
        temps = np.zeros((bucket,), np.float32)
        top_ks = np.zeros((bucket,), np.int32)
        top_ps = np.ones((bucket,), np.float32)
        seeds = np.zeros((bucket,), np.int32)
        eos = np.full((bucket,), -1, np.int32)
        for j, (req, _row) in enumerate(members):
            temps[j] = req.temperature
            top_ks[j] = req.top_k
            top_ps[j] = req.top_p
            seeds[j] = req.seed & 0x7FFFFFFF
            eos[j] = req.eos
        sampling = bool((temps > 0).any())
        prefill, extend = _build_prefill(self.config)
        insert, pick_rows, _ = _build_slot_fns(
            self.config, srv.decode_chunk, sampling)
        if self._paged:
            insert_paged, gather_rows = _build_paged_fns(
                self.config, srv.page_size)
            for j, (req, row) in enumerate(members):
                plan = req.page_plan[row]
                pages = plan["shared"] + plan["owned"]
                s = int(slots[j])
                self._tables[s, :] = self._n_pages
                self._tables[s, :len(pages)] = pages
                if self._spec_k:
                    dpages = plan["draft"]
                    self._draft_tables[s, :] = self._n_pages
                    self._draft_tables[s, :len(dpages)] = dpages
        pf0 = time_mod.monotonic()
        with self._prof.phase("prefill"), self._device_lock, self.logger.time(
            f"admit[{n}->{bucket}x{plen}]"
        ):
            pc = srv.prefill_chunk
            if shared_len > 0:
                row_cache = gather_rows(
                    self._slot_cache, self._tables[slots],
                    np.int32(shared_len))
                logits = None
                for i in range(shared_len, plen, pc or plen):
                    logits, row_cache = extend(
                        self.params, row_cache,
                        stacked[:, i:i + (pc or plen)])
            elif pc is None or pc >= plen:
                logits, row_cache = prefill(self.params, stacked)
            else:
                logits, row_cache = prefill(self.params, stacked[:, :pc])
                for i in range(pc, plen, pc):
                    logits, row_cache = extend(
                        self.params, row_cache, stacked[:, i:i + pc])
            with self._prof.phase("page_insert"):
                if self._paged:
                    with self._donating("insert", self._slot_cache):
                        self._slot_cache = insert_paged(
                            self._slot_cache, row_cache, slots,
                            np.int32(plen), np.int32(shared_len),
                            self._tables.copy())
                    # insert carries the FULL host table to the device, so
                    # any pending sentinel edits from retired slots ride
                    # along
                    self._tables_dirty = False
                else:
                    with self._donating("insert", self._slot_cache):
                        self._slot_cache = insert(
                            self._slot_cache, row_cache, slots,
                            np.int32(plen))
            with self._prof.phase("first_token_fetch"):
                first = np.asarray(pick_rows(
                    logits, temps, top_ks, top_ps, seeds,
                    np.full((bucket,), plen, np.int32)))[:n]
        pf1 = time_mod.monotonic()  # first tokens are on the host now
        if self._spec_k:
            # the draft prefills the FULL prompt: even when the target rode
            # shared prefix pages, the draft cache holds no KV for them
            # (different model), so there is nothing for it to reuse
            d_prefill, d_extend = _build_prefill(self.draft_config)
            d_insert, _ = _build_paged_fns(self.draft_config, srv.page_size)
            dparams = self._live_draft_params()
            with self._prof.phase("spec_draft"), self._device_lock:
                pc = srv.prefill_chunk
                if pc is None or pc >= plen:
                    _, d_row = d_prefill(dparams, stacked)
                else:
                    _, d_row = d_prefill(dparams, stacked[:, :pc])
                    for i in range(pc, plen, pc):
                        _, d_row = d_extend(
                            dparams, d_row, stacked[:, i:i + pc])
                with self._donating("insert", self._draft_cache):
                    self._draft_cache = d_insert(
                        self._draft_cache, d_row, slots, np.int32(plen),
                        np.int32(0), self._draft_tables.copy())
                self._draft_tables_dirty = False
        for j, (req, row) in enumerate(members):
            s = int(slots[j])
            self._slot_req[s] = req
            self._slot_row[s] = row
            self._slot_emitted[s] = 1
            self._slot_emit_t[s] = pf1
            if req.first_tok_t is None:
                # first row of this request to land a token: the TTFT
                # anchor is enqueue -> token on host, so queue wait and
                # cold-compile stalls show up where the caller felt them
                req.first_tok_t = pf1
                req.ttft_ms = round((pf1 - req.enq_t) * 1000.0, 3)
                self._m_ttft[req.tier].observe(req.ttft_ms)
                if req.ttft_ms > self._ttft_peak[req.tier]:
                    # worst-request watermark: the sentinel's breach
                    # bundle ring then names the offending trace (§11)
                    self._ttft_peak[req.tier] = req.ttft_ms
                    self._tel.flight.record(
                        "ttft_high", request_id=req.request_id,
                        trace_id=req.trace_id, tier=req.tier,
                        ttft_ms=req.ttft_ms)
                self._req_span(req, "admission", req.admit_t,
                               (pf0 - req.admit_t) * 1000.0)
            self._req_span(req, "prefill", pf0, (pf1 - pf0) * 1000.0,
                           slot=s, row=row, plen=plen, shared=shared_len)
            if self._paged:
                plan = req.page_plan[row]
                plan["committed"] = True
                self._slot_pages[s] = plan["shared"] + plan["owned"]
                self._draft_pages[s] = plan["draft"]
                self._register_prefix(plan)
                self._note_client_pages(req.client_id)
            self._tok[s] = first[j]
            self._temps[s] = temps[j]
            self._top_ks[s] = top_ks[j]
            self._top_ps[s] = top_ps[j]
            self._seeds[s] = seeds[j]
            self._eos[s] = eos[j]
            hit_eos = req.eos >= 0 and int(first[j]) == req.eos
            self._done[s] = hit_eos
            self._m_tokens.inc()
            out = np.asarray([first[j]], np.int32)
            if hit_eos and req.n_tokens > 1:
                # instant eos: the rest of the budget is frozen repeats,
                # exactly what the solo path returns
                out = np.concatenate(
                    [out, np.full((req.n_tokens - 1,), req.eos, np.int32)])
            req.rows_out[row] = out
            if req.n_tokens == 1 or hit_eos:
                self._complete_row(s)

    def _decode_iteration(self) -> None:
        """Advance every live slot ``decode_chunk`` tokens in ONE device
        dispatch, then retire finished/cancelled rows."""
        srv = self.serving
        active = [i for i, r in enumerate(self._slot_req) if r is not None]
        # cancelled rows retire before the dispatch, not after it
        for s in active:
            req = self._slot_req[s]
            if req.cancelled:
                self._retire_slot(s)
                self._finish_error(req, RuntimeError("client disconnected"))
        active = [i for i, r in enumerate(self._slot_req) if r is not None]
        if not active:
            self._m_slots.set(0)
            return
        if self._spec_k:
            self._spec_round(active)
            return
        stats: Dict[str, int] = {}
        if self._prof.enabled:
            # what single-query attention reads this dispatch, on the
            # annotation: live rows and their cached context (prompt plus
            # emitted), so a trace reader needs no second clock for it;
            # under the paged layout also the pages that context fills
            # against the pages the kernel's grid spans
            ctx = [self._slot_req[s].prompt.shape[1]
                   + int(self._slot_emitted[s]) for s in active]
            stats = {"n_active": len(active), "ctx_tokens": sum(ctx)}
            if self._paged:
                ps = srv.page_size
                stats["live_pages"] = sum(-(-c // ps) for c in ctx)
                stats["table_pages"] = len(self._slot_req) * self._pp
            if self._decode_work is not None:
                work = self._decode_work(ctx, srv.decode_chunk,
                                         len(self._slot_req))
                if "sel_tokens" in work:  # a family with sparse attention
                    stats["sel_tokens"] = work["sel_tokens"]
                stats["rows_run"] = work["rows_run"]
        with self._prof.phase("decode_iter", **stats):
            sampling = bool((self._temps[active] > 0).any())
            _insert, _pick, decode = _build_slot_fns(
                self.config, srv.decode_chunk, sampling)
            t0 = time_mod.monotonic()
            with self._device_lock:
                if (self._paged and self._tables_dirty
                        and self._slot_cache is not None):
                    # retired slots re-sentineled their table rows on the
                    # host; push the table before dispatch so a frozen
                    # row's continued appends drop instead of landing in
                    # pages the pool may already have re-issued
                    with self._prof.phase("table_push"):
                        self._slot_cache = set_page_tables(
                            self._slot_cache, self._tables.copy())
                    self._tables_dirty = False
                td0 = time_mod.monotonic()
                with self._prof.phase("decode_dispatch"), \
                        self._donating("decode", self._slot_cache):
                    self._slot_cache, tok, done, toks = decode(
                        self._param_view, self._slot_cache, self._tok,
                        self._done, self._temps, self._top_ks, self._top_ps,
                        self._seeds, self._eos)
                td1 = time_mod.monotonic()
                with self._prof.phase("token_fetch"):
                    counts = (self._work_leaves()
                              if stats and self._decode_work is not None
                              else [])
                    if counts:  # the layers' counts ride with the tokens
                        tok, done, toks, *counts = jax.device_get(
                            [tok, done, toks] + counts)
                    # np.array, not np.asarray: device outputs arrive as
                    # read-only views, and the slot state is mutated in
                    # place below
                    tok = np.array(tok)
                    done = np.array(done)
                    toks = np.array(toks)
                    if counts:
                        seen = np.sum(counts, axis=0, dtype=np.int64)
                        run_now, local = seen - self._work_seen
                        self._work_seen = seen
                        stats.update(experts_hit=int(run_now),
                                     local_assignments=int(local))
            t1 = time_mod.monotonic()
            elapsed_ms = (t1 - t0) * 1000.0
            dispatch_ms = round((td1 - td0) * 1000.0, 3)
            fetch_ms = round((t1 - td1) * 1000.0, 3)
            sparse = {}
            if "experts_hit" in stats:
                sparse = {k: stats[k] for k in (
                    "ctx_tokens", "sel_tokens", "rows_run", "experts_hit",
                    "local_assignments") if k in stats}
                self._m_ctx_tokens.inc(stats["ctx_tokens"])
                self._m_experts_run.inc(stats["experts_hit"])
                self._m_assignments["yes"].inc(stats["local_assignments"])
                self._m_assignments["no"].inc(
                    work["assignments"] - stats["local_assignments"])
                if "sel_tokens" in stats:
                    self._m_sel_tokens.inc(stats["sel_tokens"])
                    self._m_rows_run.inc(stats["rows_run"])
                    self._m_rows_live.inc(len(active) * srv.decode_chunk)
                if self._slot_leaves:
                    self._m_ssm_rows.inc(stats["rows_run"])
            self.decode_batches += 1
            self._m_batches.inc()
            self._tok = tok
            self._done = done
            emitted_now = 0
            with self._prof.phase("emit"):
                for s in active:
                    req = self._slot_req[s]
                    row = int(self._slot_row[s])
                    have = int(self._slot_emitted[s])
                    take = min(srv.decode_chunk, req.n_tokens - have)
                    chunk_toks = toks[s, :take].astype(np.int32)
                    emitted_now += take
                    self._slot_emitted[s] = have + take
                    # per-slot decode-interval TPOT (satellite 1): time since
                    # THIS slot last emitted, per token it emitted now — the
                    # old batch-level observe divided one dispatch across all
                    # active slots and conflated every co-resident request
                    if take > 0:
                        self._m_tpot[req.tier].observe(
                            (t1 - self._slot_emit_t[s]) * 1000.0 / take)
                    self._slot_emit_t[s] = t1
                    self._req_span(req, "decode_iter", t0, elapsed_ms,
                                   slot=s, n_active=len(active), take=take,
                                   dispatch_ms=dispatch_ms, fetch_ms=fetch_ms,
                                   **sparse)
                    req.rows_out[row] = np.concatenate(
                        [req.rows_out[row], chunk_toks])
                    if done[s]:
                        # row froze to eos inside the scan; pad the remaining
                        # budget with eos — bit-identical to the solo path's
                        # frozen-row output — and answer the caller NOW
                        pad = req.n_tokens - have - take
                        if pad:
                            req.rows_out[row] = np.concatenate([
                                req.rows_out[row],
                                np.full((pad,), req.eos, np.int32)])
                        self._complete_row(s)
                    elif have + take >= req.n_tokens:
                        self._complete_row(s)
            self._m_tokens.inc(emitted_now)
            self._m_slots.set(
                sum(1 for r in self._slot_req if r is not None))

    def _spec_round(self, active: List[int]) -> None:
        """One speculative round over every live slot: draft k tokens,
        verify all k+1 positions in ONE target pass, commit the accepted
        prefix (docs/PERFORMANCE.md §7g; device programs in
        ``models/generate.py::_build_spec_fns``). Each round yields 1 to
        ``k + 1`` tokens per row — the host clips to the row's remaining
        budget and retires rows exactly like the plain chunk path. The
        three dispatches stay separate (each synced before its phase
        closes) so ``spec_draft``/``spec_verify``/``spec_commit`` attribute
        wall time honestly in the profiler digest and trace assembler."""
        srv = self.serving
        k = self._spec_k
        sampling = bool((self._temps[active] > 0).any())
        draft_k, verify, commit = _build_spec_fns(
            self.config, self.draft_config, k, sampling)
        t0 = time_mod.monotonic()
        with self._device_lock:
            if self._tables_dirty and self._slot_cache is not None:
                self._slot_cache = set_page_tables(
                    self._slot_cache, self._tables.copy())
                self._tables_dirty = False
            if self._draft_tables_dirty and self._draft_cache is not None:
                self._draft_cache = set_page_tables(
                    self._draft_cache, self._draft_tables.copy())
                self._draft_tables_dirty = False
            dparams = self._live_draft_view()
            with self._prof.phase("spec_draft"):
                with self._donating("spec", self._draft_cache):
                    self._draft_cache, drafts, qprobs = draft_k(
                        dparams, self._draft_cache, self._tok, self._temps,
                        self._top_ks, self._top_ps, self._seeds)
                drafts.block_until_ready()
            td = time_mod.monotonic()
            with self._prof.phase("spec_verify"):
                with self._donating("spec", self._slot_cache):
                    (self._slot_cache, emit, n_emit, n_acc, new_tok,
                     new_done, catch, new_idx) = verify(
                        self._param_view, self._slot_cache, self._tok,
                        drafts, qprobs, self._temps, self._top_ks, self._top_ps,
                        self._seeds, self._done, self._eos)
                emit = np.array(emit)
                n_emit = np.array(n_emit)
                n_acc = np.array(n_acc)
                new_tok = np.array(new_tok)
                new_done = np.array(new_done)
            tv = time_mod.monotonic()
            with self._prof.phase("spec_commit"):
                with self._donating("spec", self._draft_cache):
                    self._draft_cache = commit(
                        dparams, self._draft_cache, drafts[:, -1], catch,
                        new_idx)
                jax.block_until_ready(self._draft_cache)
        tc = time_mod.monotonic()
        self.decode_batches += 1
        self._m_batches.inc()
        self._tok = new_tok
        self._done = new_done
        emitted_now = 0
        accepted_now = 0
        for s in active:
            req = self._slot_req[s]
            row = int(self._slot_row[s])
            have = int(self._slot_emitted[s])
            take = min(int(n_emit[s]), req.n_tokens - have)
            emitted_now += take
            accepted_now += int(n_acc[s])
            self._slot_emitted[s] = have + take
            # per-slot decode-interval TPOT (satellite 1), spec flavor:
            # a round yields 1..k+1 tokens per row, so the interval is
            # normalized by what THIS slot actually committed
            if take > 0:
                self._m_tpot[req.tier].observe(
                    (tc - self._slot_emit_t[s]) * 1000.0 / take)
                self._slot_emit_t[s] = tc
            self._req_span(req, "spec_draft", t0, (td - t0) * 1000.0,
                           slot=s)
            self._req_span(req, "spec_verify", td, (tv - td) * 1000.0,
                           slot=s)
            self._req_span(req, "spec_commit", tv, (tc - tv) * 1000.0,
                           slot=s, accepted=int(n_acc[s]), take=take)
            req.rows_out[row] = np.concatenate(
                [req.rows_out[row], emit[s, :take].astype(np.int32)])
            if new_done[s]:
                pad = req.n_tokens - have - take
                if pad:
                    req.rows_out[row] = np.concatenate([
                        req.rows_out[row],
                        np.full((pad,), req.eos, np.int32)])
                self._complete_row(s)
            elif have + take >= req.n_tokens:
                self._complete_row(s)
        self._m_tokens.inc(emitted_now)
        self._m_spec_proposed.inc(k * len(active))
        self._m_spec_accepted.inc(accepted_now)
        self.spec_accept_per_step = accepted_now / len(active)
        self._m_spec_rate.set(self.spec_accept_per_step)
        self._m_slots.set(sum(1 for r in self._slot_req if r is not None))

    def _complete_row(self, s: int) -> None:
        """Finish one slot's row (its tokens already sit in ``rows_out``):
        retire the slot and resolve the request once every row is in."""
        req = self._slot_req[s]
        self._retire_slot(s)
        req.rows_left -= 1
        if req.rows_left == 0 and not req.done.is_set():
            req.result = np.concatenate(
                [req.prompt, np.stack(req.rows_out)], axis=1)
            now = time_mod.monotonic()
            if req.first_tok_t is not None:
                # per-request TPOT: wall from first token to completion
                # over the remaining token budget — what the caller
                # experienced, regardless of who shared the batch
                req.tpot_ms = round((now - req.first_tok_t) * 1000.0
                                    / max(req.n_tokens - 1, 1), 3)
                if req.tpot_ms > self._tpot_peak[req.tier]:
                    self._tpot_peak[req.tier] = req.tpot_ms
                    self._tel.flight.record(
                        "tpot_high", request_id=req.request_id,
                        trace_id=req.trace_id, tier=req.tier,
                        tpot_ms=req.tpot_ms)
            self._req_span(req, "retire", now, 0.0, outcome="complete",
                           emitted=int(req.n_tokens),
                           ttft_ms=req.ttft_ms, tpot_ms=req.tpot_ms)
            self._unregister(req)
            req.done.set()

    def _retire_slot(self, s: int) -> None:
        """Park a slot: frozen (done=True, eos filler 0) so the decode
        scan leaves it inert; its cache row is fully overwritten by the
        next insert, and any writes past max_seq are dropped by the
        scatter's FILL_OR_DROP mode. Under the paged layout the slot's
        pages go back to the pool immediately (shared pages just drop a
        reference) and the slot's table row re-sentinels so the frozen
        row's writes land nowhere — the device table catches up at the
        next insert or decode dispatch (``_tables_dirty``)."""
        with self._prof.phase("retire"):
            req = self._slot_req[s]
            self._slot_req[s] = None
            self._done[s] = True
            self._temps[s] = 0.0
            self._eos[s] = -1
            if self._paged and (self._slot_pages[s] or self._draft_pages[s]):
                pages = self._slot_pages[s]
                self._slot_pages[s] = []
                self._pool.unref(pages)
                self._tables[s, :] = self._n_pages
                dpages = self._draft_pages[s]
                self._draft_pages[s] = []
                if dpages:
                    self._pool.unref(dpages)
                    self._draft_tables[s, :] = self._n_pages
                    self._draft_tables_dirty = True
                self._m_pages_freed.inc(len(pages) + len(dpages))
                self._tables_dirty = True
                self._note_occupancy()
                if req is not None:
                    self._note_client_pages(req.client_id)

    def _finish_error(self, req: _Request, err: Exception) -> None:
        if not req.done.is_set():
            req.error = err
            self._req_span(
                req, "retire", time_mod.monotonic(), 0.0,
                outcome="cancelled" if req.cancelled else "error",
                error=type(err).__name__)
            self._unregister(req)
            req.done.set()

    def _unregister(self, req: _Request) -> None:
        with self._inflight_lock:
            lst = self._inflight.get(req.client_id)
            if lst is not None:
                try:
                    lst.remove(req)
                except ValueError:
                    pass
                if not lst:
                    self._inflight.pop(req.client_id, None)

    def release_prefix_cache(self) -> int:
        """Drop every prefix-map reference and return how many pool pages
        that actually freed. Map references are bookkeeping the server
        holds on its own behalf — they are excluded from the request
        allocate/release counters, so after a full drain plus this flush
        ``serving_pages_allocated_total == serving_pages_released_total``
        and the pool is back to all-free (the chaos reclamation test and
        the paged bench reconcile on exactly that identity)."""
        freed = 0
        if self._paged:
            freed = self._flush_prefix_map()
            self.verify_pool_conservation("release_prefix_cache")
        return freed

    def _flush_prefix_map(self) -> int:
        """Drop every prefix-map entry; returns the pages that freed."""
        freed = 0
        while self._prefix_map:
            _h, pg = self._prefix_map.popitem(last=False)
            self._evicted_prefixes.append(_h)
            self._prefix_hit_counts.pop(_h, None)
            freed += self._pool.unref([pg])
        self._note_occupancy()
        return freed

    def verify_pool_conservation(self, context: str = "") -> None:
        """Assert ``free + referenced + shared == pool size`` when the
        pool witness is enabled (``DISTRIFLOW_POOL_WITNESS=1``), else a
        no-op.  *referenced* = pages held by live slots (target or draft;
        a page both slot-held and prefix-shared counts once, here);
        *shared* = pages held only by the prefix map.  Only meaningful at
        quiescence points where no uncommitted reservation is in flight —
        the callers (idle scheduler tick, ``stop`` after the join, the
        prefix flush) are exactly those points."""
        if (self._pool is None or self._pool_witness is None
                or not self._pool_witness.enabled):
            return
        held: set = set()
        for pages in self._slot_pages:
            held.update(pages)
        for pages in self._draft_pages:
            held.update(pages)
        shared_only = set(self._prefix_map.values()) - held
        self._pool_witness.verify(
            self._pool.free_pages, len(held), len(shared_only),
            context=context)

    def _abort_all(self, err: Exception) -> None:
        """Device failure mid-engine: error every waiter (active slots and
        backlog) and reset slot state so the engine can keep serving."""
        self._fail_residents(err)
        while self._backlog:
            self._finish_error(self._backlog.popleft(), err)

    def _fail_residents(self, err: Exception) -> None:
        for s, req in enumerate(self._slot_req):
            if req is not None:
                self._retire_slot(s)
                self._finish_error(req, err)
        self._m_slots.set(0)

    def _shutdown_engine(self) -> None:
        self._abort_all(RuntimeError("inference server stopped"))
        self._drain_and_error()

    def _drain_and_error(self) -> None:
        """Error out every request still queued at shutdown (stop() may
        race a handler that passed the scheduler-alive check but had not
        yet enqueued)."""
        while True:
            try:
                item = self._queue.get_nowait()
            except queue_mod.Empty:
                return
            if item is not None:
                self._finish_error(
                    item, RuntimeError("inference server stopped"))

    # -- direct-path handlers ----------------------------------------------

    # dfcheck: payload payload=beam_request -> direct_ack
    def _on_beam(self, client_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        prompt = _prompt_from(payload, self._prompt_cap())
        n_tokens = int(payload["n_tokens"])
        # .get with a default, NOT `or`: an explicit beam_size=0 must reach
        # beam_search's validation, not silently become the default
        beam_size = int(payload.get("beam_size", 4))
        length_penalty = float(payload.get("length_penalty", 0.0))
        eos_id = payload.get("eos_id")
        with self._device_lock, self.logger.time(
            f"beam[{prompt.shape[0]}x{prompt.shape[1]}+{n_tokens} k={beam_size}]"
        ):
            out, scores = beam_search(
                self.config, self._param_view, prompt, n_tokens,
                beam_size=beam_size, length_penalty=length_penalty,
                eos_id=int(eos_id) if eos_id is not None else None,
            )
        ack = {
            "result": pack_bytes(
                {"tokens": serialize_array(out), "scores": serialize_array(scores)}
            )
        }
        tid = payload.get("trace_id")
        if tid:
            ack["trace_id"] = tid
        return ack

    # dfcheck: payload payload=score_request -> direct_ack
    def _on_score(self, client_id: str, payload: Dict[str, Any]) -> Dict[str, Any]:
        tokens = _prompt_from(payload, self._prompt_cap())
        from_pos = int(payload.get("from_pos", 1))
        with self._device_lock, self.logger.time(
            f"score[{tokens.shape[0]}x{tokens.shape[1]} from={from_pos}]"
        ):
            scores = sequence_logprob(self.config, self.params, tokens, from_pos)
        ack = {"result": pack_bytes({"scores": serialize_array(scores)})}
        tid = payload.get("trace_id")
        if tid:
            ack["trace_id"] = tid
        return ack
