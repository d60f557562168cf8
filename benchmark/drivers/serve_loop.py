"""Serving cells: a paged ``InferenceServer`` on one chip, driven over
loopback by ``InferenceClient`` callers in this process (a chip belongs to
one process, and the server is the one that needs it).

Traffic file keys: those of ``lib/loadgen.py``, and ``clients`` (caller
threads, each with its own connection), ``warm_group_sizes`` (prefill is
warmed for every prompt length at group sizes 1..G), ``ramp_s`` (closed
loop: callers start spread over this long, before the window),
``trace_seconds`` / ``trace_after_s`` (the profiler's part of a traced
window), ``check_replies`` (how many completed replies are re-scored).

Per request, on the client's clock: ``due`` (open loop: the schedule;
closed loop: when the caller was free), ``sent``, ``recv``. The server does
not stream, so the first token's time is its own stamp, carried in the
reply: ``ttft = (sent - due) + reply.ttft_ms`` and
``tpot = (recv - due - ttft) / (tokens_out - 1)``. Whatever the request
waited before the server stamped its arrival therefore lands in ``tpot``.
"""

from __future__ import annotations

import gc
import queue
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark.lib import corpus as corpus_lib
from benchmark.lib import harness, loadgen, reference_lm, stats
from benchmark.lib.harness import Run, say

# -- tolerances, each with its reason ----------------------------------------
# score() sums teacher-forced log-probabilities over SCORE_TOKENS - 1
# positions from a bfloat16 forward of float32 weights; the reference is
# float32 at "highest" precision. A sum is all score() returns, so what is
# compared is the mean difference per token: the bf16 rounding of single
# logits (a few 2^-8 of a magnitude near 1) mostly cancels in it, and what
# is left measured 3.5e-4 nats per token at these widths on the chip (PR 23).
# A wrong mask, scale or rotary pairing shifts every position the same way,
# by 0.1 nats and more; float32 compute would agree to 1e-5.
SCORE_TOKENS = 1024
SCORE_NATS_PER_TOKEN = 3e-3
# Greedy decoding through the paged cache is judged on the reference's
# logits, not on token identity: with random weights the two best logits are
# often closer than bf16 rounding. A generated token's reference
# log-probability must be within this margin of the position's best: two
# bf16 ulps of a logit of magnitude 8 to 16 (2 x 2^-4), the near-tie rule of
# chip_smoke.py. Measured worst on the chip 0.03 nats over 1,053 tokens
# (PR 23); a token read from a wrong page or position is off by the spread
# of the logits, a nat or more.
GREEDY_MARGIN_NATS = 0.125
REFERENCE_LEN = 2048  # replies are padded to one length: one program
CALL_TIMEOUT_S = 60.0  # a request that takes longer has failed
NEVER_MS = 1e12


def _serving_params(run: Run, cfg: Any) -> Any:
    """Weights from the seed, made on the device in one jitted call, in
    the float32 the server holds them in."""
    import jax

    from distriflow_tpu import transformer_lm

    spec = transformer_lm(cfg, example_seq=128)
    return jax.jit(spec.init)(harness.prng_key(run.seed))


class _Callers:
    """``n`` caller threads, each with its own connection, taking requests
    from a queue and recording what the client's clock saw."""

    def __init__(self, address: str, n: int, telemetry: Any,
                 held_out: np.ndarray):
        from distriflow_tpu import InferenceClient

        self.records: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        self._held_out = held_out
        self.todo: "queue.Queue[Any]" = queue.Queue()
        self._clients = [
            InferenceClient(address, timeout=CALL_TIMEOUT_S, telemetry=telemetry,
                            report_interval_s=0.0).setup() for _ in range(n)]
        self._threads = [
            threading.Thread(target=self._work, args=(c,), daemon=True,
                             name=f"bench-caller-{i}")
            for i, c in enumerate(self._clients)]
        for thread in self._threads:
            thread.start()

    def prompt(self, req: loadgen.Request) -> np.ndarray:
        return self._held_out[req.offset:req.offset + req.prompt_len]

    def call(self, client: Any, req: loadgen.Request,
             due: Optional[float]) -> Dict[str, Any]:
        prompt = self.prompt(req)
        sent = time.monotonic()
        rec: Dict[str, Any] = {
            "index": req.index, "due": sent if due is None else due,
            "sent": sent, "prompt_len": req.prompt_len,
            "out_tokens": req.out_tokens, "ok": False}
        try:
            out = client.generate(prompt[None], req.out_tokens)
            rec["recv"] = time.monotonic()
            meta = client.last_serving_meta or {}
            rec.update(ok=True, tokens=out[0], ttft_ms=meta.get("ttft_ms"),
                       queue_ms=meta.get("queue_ms"), path=meta.get("path"),
                       prefix_tokens=meta.get("prefix_tokens", 0))
        except Exception as e:  # a failed request is counted, not raised
            rec["recv"] = time.monotonic()
            rec["error"] = f"{type(e).__name__}: {e}"
        with self._lock:
            self.records.append(rec)
        return rec

    def _work(self, client: Any) -> None:
        while True:
            item = self.todo.get()
            if item is None:
                return
            if callable(item):
                item(client)  # closed loop: the caller's own loop
            else:
                self.call(client, *item)
            self.todo.task_done()

    def close(self) -> None:
        for _ in self._threads:
            self.todo.put(None)
        for thread in self._threads:
            thread.join(timeout=30.0)
        for client in self._clients:
            client.close()


def _latencies(rec: Dict[str, Any]) -> None:
    """ttft / tpot of one answered request, as the module doc defines."""
    if not rec["ok"] or rec.get("ttft_ms") is None:
        return
    rec["ttft"] = (rec["sent"] - rec["due"]) * 1e3 + rec["ttft_ms"]
    if rec["out_tokens"] > 1:
        rec["tpot"] = ((rec["recv"] - rec["due"]) * 1e3 - rec["ttft"]) / (
            rec["out_tokens"] - 1)


def _check_score(run: Run, client: Any, params: Any,
                 held_out: np.ndarray) -> bool:
    import jax.numpy as jnp

    tokens = held_out[:min(SCORE_TOKENS, run.model["max_seq"])]
    got = float(client.score(tokens[None], from_pos=1)[0])
    logp = reference_lm.log_probs(
        params, jnp.asarray(tokens), jnp.arange(len(tokens) - 1),
        run.model["n_layers"], run.model["rope_base"])
    want = float(np.take_along_axis(
        np.asarray(logp), tokens[1:, None].astype(np.int64), axis=-1).sum())
    per_token = abs(got - want) / (len(tokens) - 1)
    say(f"  reference: score() {got:.3f} vs {want:.3f} nats over "
        f"{len(tokens) - 1} tokens, {per_token:.2e} per token "
        f"(tol {SCORE_NATS_PER_TOKEN})")
    return per_token <= SCORE_NATS_PER_TOKEN


def _check_replies(run: Run, params: Any, records: List[Dict[str, Any]],
                   callers: _Callers, reqs: List[loadgen.Request]) -> bool:
    """Every reply echoes its prompt at the asked length; a seeded sample
    is re-scored by the reference, token by token."""
    import jax.numpy as jnp

    by_index = {r.index: r for r in reqs}
    done = [r for r in records if r["ok"]]
    ok = True
    for rec in done:
        req = by_index[rec["index"]]
        if (rec["tokens"].shape != (req.prompt_len + req.out_tokens,)
                or not np.array_equal(rec["tokens"][:req.prompt_len],
                                      callers.prompt(req))):
            say(f"  reply {rec['index']}: wrong length or prompt not echoed")
            ok = False
    rng = np.random.default_rng(run.seed)
    n_check = min(run.traffic["check_replies"], len(done))
    sample = rng.choice(len(done), size=n_check, replace=False) if done else []
    length = min(REFERENCE_LEN, run.model["max_seq"])
    most_out = int(run.traffic["output_tokens"]["max"])
    worst, argmax_hits, total = 0.0, 0, 0
    for i in sample:
        rec = done[int(i)]
        toks = rec["tokens"]
        padded = np.zeros((length,), np.int32)
        padded[:len(toks)] = toks  # causal: the tail cannot reach back
        positions = np.arange(rec["prompt_len"] - 1, len(toks) - 1)
        # one shape for every reply (one program): the longest output asked
        asked = np.full((most_out,), positions[-1])
        asked[:len(positions)] = positions
        logp = np.asarray(reference_lm.log_probs(
            params, jnp.asarray(padded), jnp.asarray(asked),
            run.model["n_layers"], run.model["rope_base"]))[:len(positions)]
        chosen = logp[np.arange(len(positions)), toks[positions + 1]]
        gap = logp.max(axis=-1) - chosen
        worst = max(worst, float(gap.max()))
        argmax_hits += int((gap == 0).sum())
        total += len(positions)
    if total:
        share = argmax_hits / total
        say(f"  reference: {n_check} replies, {total} generated tokens: "
            f"{share:.3f} are the reference's argmax, worst gap to the best "
            f"{worst:.4f} nats (margin {GREEDY_MARGIN_NATS})")
        ok = ok and worst <= GREEDY_MARGIN_NATS
    return ok and bool(done)


def _warm_tokens(t: Dict[str, Any]) -> int:
    """Corpus tokens the warm-up's prompts take, from the start."""
    g = t["warm_group_sizes"]
    return sum(int(k) for k in t["prompt_lengths"]) * g * (g + 1) // 2


def _warm_up(run: Run, address: str, telemetry: Any,
             held_out: np.ndarray) -> None:
    """Every (group size, prompt length) prefill the traffic can produce,
    the decode chunk and the page scatter, by real requests: one request of
    n rows of one length is a group of exactly n."""
    from distriflow_tpu import InferenceClient

    t = run.traffic
    chunk = run.config["serving"]["decode_chunk"]
    lengths = sorted(int(k) for k in t["prompt_lengths"])
    cursor = 0
    with InferenceClient(address, timeout=1100.0, telemetry=telemetry,
                         report_interval_s=0.0) as client:
        for plen in lengths:
            for n in range(1, t["warm_group_sizes"] + 1):
                # distinct slices: a repeated page would ride the prefix
                # cache and warm the extend path instead of prefill
                rows = np.stack([held_out[cursor + i * plen:
                                          cursor + (i + 1) * plen]
                                 for i in range(n)])
                cursor += n * plen
                client.generate(rows, chunk + 1)
    say(f"  warmed prefill for lengths {lengths} x group sizes "
        f"1..{t['warm_group_sizes']} ({cursor} prompt tokens)")


def _open_loop(callers: _Callers, reqs: List[loadgen.Request],
               t0: float) -> None:
    for req in reqs:
        due = t0 + req.due_s
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        callers.todo.put((req, due))
    callers.todo.join()


def _closed_loop(callers: _Callers, reqs: List[loadgen.Request],
                 stop: threading.Event, start_at: List[float]) -> None:
    """Each caller sends its next request when its last returned."""
    cursor = {"i": 0}
    lock = threading.Lock()

    def loop(k: int):
        def body(client: Any) -> None:
            delay = start_at[k] - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            while not stop.is_set():
                with lock:
                    i = cursor["i"]
                    cursor["i"] += 1
                callers.call(client, reqs[i % len(reqs)], None)
        return body

    for k in range(len(start_at)):
        callers.todo.put(loop(k))


class Session:
    """A server with its weights, checked and warmed, and its callers:
    everything a window needs. ``rehearsal/knee_sweep.py`` opens one and
    measures several windows; :func:`run` measures one."""

    def __init__(self, run: Run):
        import jax

        from distriflow_tpu import (
            InferenceClient,
            InferenceServer,
            ServingConfig,
        )
        from distriflow_tpu.obs.telemetry import Telemetry
        from distriflow_tpu.obs.tracing import Tracer

        self.run = run
        t, m = run.traffic, run.model
        on_tpu = run.devices[0].platform == "tpu"
        setup_mark = run.meter.mark()
        cfg = harness.transformer_config(m, name_kernels=not on_tpu)
        self.serving = ServingConfig(**run.config["serving"])
        with run.phase("weights from the seed"):
            self.params = _serving_params(run, cfg)
            jax.block_until_ready(self.params)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(self.params))
        say(f"  model: {n_params / 1e6:.1f} M parameters in "
            f"{jax.tree.leaves(self.params)[0].dtype}, pool "
            f"{self.serving.pool_pages(cfg.max_seq)} pages of "
            f"{self.serving.page_size}, {self.serving.max_slots} slots")

        # the program's tracing is on in the traced run only; its ring keeps
        # 4096 spans by default, fewer than a window emits
        self.telemetry = Telemetry(enabled=run.trace)
        if run.trace:
            self.telemetry.tracer = Tracer(enabled=True, max_spans=1_000_000)
        with run.phase("corpus"):
            self.corpus = corpus_lib.generate_corpus(t["corpus_tokens"], seed=0)
        self.server = InferenceServer(cfg, self.params, port=0,
                                      serving=self.serving,
                                      telemetry=self.telemetry)
        self.log: List[str] = []
        self.server.logger.log = lambda *a: self.log.append(
            " ".join(str(x) for x in a))
        self.server.setup()
        self.callers: Optional[_Callers] = None
        try:
            with run.phase("score() against the reference"):
                with InferenceClient(self.server.address, timeout=1100.0,
                                     telemetry=self.telemetry,
                                     report_interval_s=0.0) as client:
                    # before the page pool exists (it is allocated at the
                    # first admission): the reference has the memory
                    self.correct = _check_score(run, client, self.params,
                                                self.corpus)
            with run.phase("warm-up"):
                _warm_up(run, self.server.address, self.telemetry, self.corpus)
            with run.phase("callers"):
                self.callers = _Callers(self.server.address, t["clients"],
                                        self.telemetry, self.corpus)
        except BaseException:
            self.close()
            raise
        self.n_warm_log = len(self.log)
        run.compile_setup = run.meter.since(setup_mark)

    def requests(self, seed: int, seconds: float,
                 rate: Optional[float] = None) -> List[loadgen.Request]:
        traffic = dict(self.run.traffic)
        if rate is not None:
            traffic["rate_per_s"] = rate
        return loadgen.requests(traffic, seconds, seed,
                                _warm_tokens(traffic), len(self.corpus))

    def open_window(self, reqs: List[loadgen.Request]) -> float:
        """Sends ``reqs`` on their schedule; returns the window's start
        once every one of them has been answered or has failed."""
        t0 = time.monotonic()
        _open_loop(self.callers, reqs, t0)
        return t0

    def close(self) -> None:
        if self.callers is not None:
            self.callers.close()
        if self.server is not None:
            self.server.stop()
        self.server = None  # frees the page pool
        gc.collect()


def _traced(run: Run):
    """A thread that profiles ``trace_seconds`` of the window."""
    import jax

    t = run.traffic

    def body() -> None:
        time.sleep(t["trace_after_s"])
        jax.profiler.start_trace(run.trace_dir)
        a = time.monotonic()
        time.sleep(t["trace_seconds"])
        run.trace_window = (a, time.monotonic())
        jax.profiler.stop_trace()

    shutil.rmtree(run.trace_dir, ignore_errors=True)
    thread = threading.Thread(target=body, daemon=True, name="bench-profiler")
    thread.start()
    return thread


def run(run: Run) -> None:
    t = run.traffic
    session = Session(run)
    callers = session.callers
    reqs = session.requests(run.seed, run.seconds)
    say(f"  traffic: {t['loop']} loop, {loadgen.describe(reqs)}")
    try:
        stop = threading.Event()
        if t["loop"] == "closed":
            now = time.monotonic()
            start_at = [now + t["ramp_s"] * k / t["clients"]
                        for k in range(t["clients"])]
            _closed_loop(callers, reqs, stop, start_at)
            time.sleep(t["ramp_s"] + t["settle_s"])
        run.end_to_end["setup_s"] = time.monotonic() - run.t_process
        window_mark = run.meter.mark()
        profiler = _traced(run) if run.trace else None
        if t["loop"] == "open":
            t0 = session.open_window(reqs)
            t1 = t0 + run.seconds
        else:
            t0 = time.monotonic()
            time.sleep(run.seconds)
            t1 = time.monotonic()
            stop.set()
            callers.todo.join()
        run.window = (t0, t1)
        run.compile_window = run.meter.since(window_mark)
        if profiler is not None:
            profiler.join(timeout=120.0)
        run.memory_peak_bytes = (run.devices[0].memory_stats() or {}).get(
            "peak_bytes_in_use", 0)
    finally:
        session.close()
    engine_errors = [line for line in session.log if "engine error" in line]
    window_admits = [line.split(" took")[0]
                     for line in session.log[session.n_warm_log:]
                     if line.startswith("admit[")]
    shapes = sorted(set(window_admits))
    say(f"  admit shapes since warm-up ({len(window_admits)} groups): "
        + " ".join(f"{s}x{window_admits.count(s)}" for s in shapes))
    run.spans = session.telemetry.tracer.finished() if run.trace else []

    records = callers.records
    if t["loop"] == "open":
        measured = records  # every request was due inside the window
    else:
        measured = [r for r in records if t0 <= r["recv"] <= t1]
    for rec in measured:
        _latencies(rec)
    run.requests = measured
    run.attempted = len(measured)
    run.failed = sum(1 for r in measured if not r["ok"])
    for rec in measured:
        if not rec["ok"]:
            say(f"  request {rec['index']} failed: {rec.get('error')}")
            break
    late = [(r["sent"] - r["due"]) * 1e3 for r in measured]
    say("  " + stats.describe("generator lateness (sent - due)", late))
    ttft = [r["ttft"] for r in measured if "ttft" in r]
    tpot = [r["tpot"] for r in measured if "tpot" in r]
    say("  " + stats.describe("ttft", ttft))
    say("  " + stats.describe("tpot", tpot))
    out_tokens = sum(r["out_tokens"] for r in measured if r["ok"])
    say(f"  window {t1 - t0:.3f}s: {len(measured)} requests, {run.failed} "
        f"failed, {out_tokens} output tokens; programs compiled or loaded "
        f"in the window: {run.compile_window['programs']}")
    if ttft and tpot:
        # a failed request misses every limit: it counts as an endless wait,
        # and a tail that reaches into the failures is not a number
        for name, values in (("serve_ttft_p90_ms", ttft),
                             ("serve_tpot_p90_ms", tpot)):
            tail = stats.percentile(values + [NEVER_MS] * run.failed, 90.0)
            run.end_to_end[name] = tail if tail < NEVER_MS / 2 else None
        run.end_to_end["serve_out_tok_s"] = out_tokens / (t1 - t0)
    run.shapes = {"admit_shapes": shapes,
                  "max_slots": session.serving.max_slots,
                  "decode_chunk": session.serving.decode_chunk,
                  "page_size": session.serving.page_size}
    with run.phase("replies against the reference (after the window)"):
        replies_ok = _check_replies(run, session.params, records, callers, reqs)
    engine_path = all(r.get("path") == "slots" for r in measured if r["ok"])
    say(f"  correct: score() {session.correct}, replies {replies_ok}, engine "
        f"errors {len(engine_errors)}, failed requests {run.failed}, all "
        f"served by the engine {engine_path}")
    run.correct = bool(session.correct and replies_ok and not engine_errors
                       and run.failed == 0 and engine_path)
