#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one command, no arguments:

    python chip_smoke.py

It drives the main path once through the entry points a user calls
(``SyncTrainer`` / ``run_chunked`` / ``InferenceServer`` / ``InferenceClient``,
as ``experiments/lm/train.py`` uses them), at the full width of the widest LM
the repo runs (vocab 32000, d_model 1024, 8 heads of 128, 12 layers, d_ff
4096, bf16, S 1024, batch 8 per chip, Adam), on random weights made from a
seed:

1. **kernels** — every Pallas kernel on that path, compiled by Mosaic, against
   the pure-XLA oracle the repo already has, at the shapes the smoke uses;
   and the grouped expert matmul of the third model family's decode step
   (``ops/expert_grouped.py``) at that family's widths;
2. **train** — a few ``step()`` calls and three ``step_many`` dispatches via
   ``run_chunked`` on the seeded Markov corpus; finite falling loss, no
   compilation after each warm-up dispatch, the Mosaic custom calls present
   in the compiled step; on several chips also placement, the batch split,
   the all-reduce and loss parity with a one-device run at the same batch;
3. **serve** — the trained parameters behind a paged ``InferenceServer``;
   ``model_info``, six concurrent greedy ``generate`` calls (two sharing a
   two-page prefix), a sampled request, ``beam_search`` and ``score`` over
   loopback, greedy output token-identical to ``generate()``.

Any failed check exits non-zero. There is no retry and no fallback: a kernel
that silently took an XLA path is a failure here. Without a TPU the script
exits 2 before it builds anything. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, with the device as JAX reports it.

``python chip_smoke.py --rehearsal`` runs the same code at a tiny size on
whatever backend JAX has (the CPU, Pallas in interpret mode). It exists to
debug the script before spending chip time and proves nothing about the chip.

The wall seconds it prints per phase are set-up observations (how long a cold
or warm start takes), not performance: rates, MFU and roofline shares belong
to the benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import re
import sys
import threading
import time
from typing import Any, Dict, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Size:
    vocab: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    seq: int                # training sequence length
    batch_per_device: int
    lr: float
    serve_max_seq: int
    # greedy requests (prompt_len, new_tokens); [1] is admitted first and
    # [2] repeats its first ``shared_prefix`` tokens
    greedy: Tuple[Tuple[int, int], ...]
    shared_prefix: int
    # prompt length of the sampled and beam requests. Shorter than a page on
    # purpose: the sampled request is sent twice, and a repeat that fills a
    # page rides the prefix cache, which prefills the suffix through the
    # dense continuation path instead of the flash kernel. The two paths'
    # logits differ by rounding, and on the chip a sampled token then
    # differed (PR 21) — a property of the prefix cache, not of the seed.
    side_prompt: int
    # the two-kernel attention backward runs past 8 KV blocks at the tile cap
    # (1024 for 2-byte inputs, 256 for f32): (S, dtype name)
    long_attention: Tuple[int, str]
    # the grouped expert matmul: (tokens, d, f, experts held, live tokens)
    experts: Tuple[int, int, int, int, int]
    # the paged decode kernel at the serving cell's geometry: (heads,
    # head_dim, page_size, rows, pages a row)
    paged_decode: Tuple[int, int, int, int, int]


FULL = Size(
    vocab=32000, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
    seq=1024, batch_per_device=8, lr=1e-3, serve_max_seq=2048,
    greedy=((100, 32), (384, 64), (384, 64), (640, 32), (1024, 64),
            (1500, 32)),
    shared_prefix=256, side_prompt=100, long_attention=(16384, "bfloat16"),
    experts=(32, 4096, 768, 36, 5),  # granite-4.0-h-small's, a decode step
    paged_decode=(16, 128, 128, 32, 16),  # serve-rate-mixed's (PERF.md §4)
)
# same code, toy dims: the interpreter is slow and the test suite has a budget
REHEARSAL = Size(
    vocab=512, d_model=64, n_heads=2, n_layers=2, d_ff=128,
    seq=128, batch_per_device=2, lr=3e-3, serve_max_seq=512,
    greedy=((24, 8), (300, 16), (300, 16), (96, 8), (160, 16), (380, 8)),
    shared_prefix=256, side_prompt=40, long_attention=(2304, "float32"),
    experts=(16, 128, 256, 6, 2),
    paged_decode=(2, 64, 16, 8, 4),
)

SINGLE_STEPS = 4          # trainer.step() calls
CHUNK = 4                 # optimizer steps per step_many dispatch
CHUNKED_DISPATCHES = 3    # run_chunked dispatches (the first one compiles)

# -- tolerances, each with its reason ---------------------------------------
# Kernel-vs-oracle error is max|kernel - oracle| / max|oracle|. The oracle
# runs in float32 at "highest" matmul precision; the kernel runs in the
# dtype the smoke uses. bf16 keeps 8 significant bits (eps 2^-7 = 7.8e-3):
# a kernel rounds its probabilities once and its output once, so a correct
# one lands within a few eps of the truth, while a wrong mask, scale or
# tile index is off by O(1). Gradients pass through two more rounded
# matmuls, hence the wider multiple. Float32 inputs (rehearsal only) get a
# floor for summation order.
FWD_EPS_MULTIPLE = 4.0
BWD_EPS_MULTIPLE = 8.0
F32_FLOOR = 1e-4
# Fused CE reads the logits in their own dtype and does its arithmetic in
# float32, exactly as the oracle does on the same (already rounded) logits:
# the loss agrees to summation order; the gradient is written back in the
# logits' dtype, one rounding.
CE_LOSS_RTOL = 1e-4
# Decode kernels are compared with the XLA decode branch of the same
# Attention module in the same dtype, so both sides carry bf16 rounding:
# twice the forward multiple.
DECODE_EPS_MULTIPLE = 8.0
# A greedy token may differ between the paged engine and generate() only at
# a near-tie: the two decode kernels tile the cache differently (128-token
# pages vs one 2048 tile), so their logits differ by rounding. Logits are
# bf16 values of magnitude < 16 there, where one ulp is 2^-4 = 0.0625; a
# flip needs the two candidates' teacher-forced log-probabilities within
# two ulps.
NEAR_TIE_NATS = 0.125
# Beam scores sum one bf16-rounded log-probability per generated token and
# are compared with a teacher-forced re-score through the training forward:
# one ulp (0.0625) per token.
BEAM_NATS_PER_TOKEN = 0.0625
# score() and the trainer's eval loss run the same training forward on the
# same tokens; only the float32 CE reduction differs (log_softmax vs the
# fused kernel). Nats per token.
SCORE_VS_EVAL_NATS = 0.02
# Data-parallel vs one device at the same global batch: identical math up to
# the order gradients are summed in (all-reduce over chips vs a scan over
# micro-batches), in bf16, over SINGLE_STEPS Adam steps. Relative. Four v5e
# chips measured 2.1e-5 (PR 21); a wrong mean, a dropped shard or a doubled
# gradient is off by far more than a hundred times that.
DP_PARITY_RTOL = 2e-3

TRAIN_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_fused",
                 "fused_ce_fwd", "fused_ce_bwd")
DECODE_KERNEL = "flash_decode_paged"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*args: Any) -> None:
    print(*args, flush=True)


def mosaic_kernels(compiled_text: str) -> Dict[str, List[str]]:
    """Pallas kernel name -> the ``tpu_custom_call`` lines of a compiled
    program that run it. Each pallas_call in ``distriflow_tpu/ops`` carries a
    ``name=``, which XLA keeps in the custom call's ``op_name`` metadata."""
    found: Dict[str, List[str]] = {}
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        for name in re.findall(r"[A-Za-z_0-9]+", op.group(1) if op else ""):
            if name.startswith(("flash_", "fused_ce_", "depthwise_gn_",
                                "grouped_expert_")):
                found.setdefault(name, []).append(line)
    return found


class CompileMeter:
    """Counts what jax compiles, per phase, from jax.monitoring events: how
    many programs were asked for, how many of those the persistent cache
    served, and the seconds spent in the backend compiler or loading from
    the cache (threads overlap, so this can exceed the wall time)."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.programs = 0
        self.cache_hits = 0
        self.backend_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == self._CACHE_HIT:
            self.cache_hits += 1

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == self._BACKEND:
            self.programs += 1
            self.backend_s += secs

    def mark(self) -> Tuple[int, int, float, float]:
        return (self.programs, self.cache_hits, self.backend_s,
                time.perf_counter())

    def report(self, phase: str, since: Tuple[int, int, float, float]) -> None:
        programs = self.programs - since[0]
        hits = self.cache_hits - since[1]
        say(f"[{phase}] set-up observation: wall "
            f"{time.perf_counter() - since[3]:.1f}s, of which backend "
            f"compile or cache load {self.backend_s - since[2]:.1f}s; "
            f"programs loaded {programs} = persistent-cache hits {hits} + "
            f"backend compiles {programs - hits}")


# -- phase 1: kernels vs their XLA oracles -----------------------------------


def rel_err(got: Any, want: Any) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(bool(np.isfinite(got).all()), "kernel output is not finite")
    return float(np.max(np.abs(got - want)) / max(float(np.max(np.abs(want))),
                                                   1e-30))


def eps_tol(dtype: Any, multiple: float) -> float:
    import jax.numpy as jnp

    return max(multiple * float(jnp.finfo(dtype).eps), F32_FLOOR)


def check_attention(b: int, h: int, s: int, d: int, dtype: Any,
                    kernels: Sequence[str], failures: List[str]) -> None:
    """flash_attention forward and gradients vs blockwise_attention in
    float32; ``kernels`` are the Mosaic calls this shape must lower to."""
    import jax
    import jax.numpy as jnp

    from distriflow_tpu.ops import default_interpret, flash_attention
    from distriflow_tpu.parallel.ring_attention import blockwise_attention

    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(s), 4)
    q, k, v, w = (jax.random.normal(key, (b, h, s, d), jnp.float32).astype(dtype)
                  for key in (kq, kk, kv, kw))

    def run(attend, *qkv):
        def loss(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out

        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(*qkv)
        return (out, *grads)

    compiled = jax.jit(
        lambda q, k, v: run(flash_attention, q, k, v)).lower(q, k, v).compile()
    if not default_interpret():
        names = mosaic_kernels(compiled.as_text())
        for name in kernels:
            check(name in names, f"attention at S={s}: no Mosaic call "
                                 f"{name!r} (found {sorted(names)})")
    got = compiled(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: run(blockwise_attention, q, k, v))(
            *(t.astype(jnp.float32) for t in (q, k, v)))
    for label, g, t, mult in zip(
            ("out", "dq", "dk", "dv"), got, want,
            (FWD_EPS_MULTIPLE,) + (BWD_EPS_MULTIPLE,) * 3):
        err, tol = rel_err(g, t), eps_tol(dtype, mult)
        say(f"  attention S={s} {jnp.dtype(dtype).name} {kernels[-1]} "
            f"{label}: err {err:.2e} (tol {tol:.2e})")
        if err > tol:
            failures.append(f"attention S={s} {label}: {err:.3e} > {tol:.3e}")


def check_fused_ce(n: int, vocab: int, dtype: Any, failures: List[str]) -> None:
    """Fused sparse CE value and gradient vs optax on the same logits."""
    import jax
    import jax.numpy as jnp
    import optax

    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy

    kl, kt = jax.random.split(jax.random.PRNGKey(vocab))
    logits = (4.0 * jax.random.normal(kl, (n, vocab), jnp.float32)).astype(dtype)
    labels = jax.random.randint(kt, (n,), 0, vocab)
    got_l, got_g = jax.jit(jax.value_and_grad(
        fused_sparse_softmax_cross_entropy))(logits, labels)

    def oracle(lg):
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            lg.astype(jnp.float32), labels))

    want_l, want_g = jax.jit(jax.value_and_grad(oracle))(logits)
    l_err = abs(float(got_l) - float(want_l)) / abs(float(want_l))
    g_err, g_tol = rel_err(got_g, want_g), eps_tol(dtype, FWD_EPS_MULTIPLE)
    say(f"  fused CE [{n}, {vocab}] {jnp.dtype(dtype).name}: loss rel err "
        f"{l_err:.2e} (tol {CE_LOSS_RTOL:.0e}), grad err {g_err:.2e} "
        f"(tol {g_tol:.2e})")
    if l_err > CE_LOSS_RTOL:
        failures.append(f"fused CE loss: {l_err:.3e} > {CE_LOSS_RTOL}")
    if g_err > g_tol:
        failures.append(f"fused CE grad: {g_err:.3e} > {g_tol:.3e}")


def check_decode(cfg: Any, page_size: int, max_slots: int, paged: bool,
                 failures: List[str]) -> None:
    """One decode step of the model's own Attention layer over a populated
    KV cache: the flash-decode kernel vs the XLA branch of
    ``_decode_attend``, same parameters, same cache, same dtype."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.transformer import Attention

    quant = cfg.resolved_kv_cache_dtype == "int8"
    hd, b = cfg.d_model, max_slots
    rng = np.random.RandomState(page_size + paged + 2 * quant)
    # every row at its own depth, one of them at the last position
    lens = rng.randint(1, cfg.max_seq - 1, (b,)).astype(np.int32)
    lens[0] = cfg.max_seq - 1
    keys = jax.random.split(jax.random.PRNGKey(int(lens.sum())), 4)
    if paged:
        pp = -(-cfg.max_seq // page_size)
        n_pages = b * pp
        store = (n_pages, page_size)
        table = np.full((b, pp + 1), n_pages, np.int32)  # sentinel-filled
        order = rng.permutation(n_pages)
        for row in range(b):  # scattered pages, as many as the row needs
            need = -(-(int(lens[row]) + 1) // page_size)
            table[row, :need] = order[row * pp:row * pp + need]
    else:
        store = (b, cfg.max_seq)

    def filled(key, feat):
        if quant:
            return jax.random.randint(key, store + (feat,), -127, 128, jnp.int8)
        return jax.random.normal(key, store + (feat,), jnp.float32).astype(cfg.dtype)

    cache = {"cached_k": filled(keys[0], hd), "cached_v": filled(keys[1], hd),
             "cache_index": jnp.asarray(lens)}
    if quant:
        scales = jax.random.uniform(
            keys[2], store + (cfg.n_heads,), jnp.float32, 1e-3, 2e-2)
        cache.update(k_scale=scales, v_scale=scales[::-1])
    if paged:
        cache["page_table"] = jnp.asarray(table)
    x = jax.random.normal(keys[3], (b, 1, cfg.d_model), jnp.float32).astype(cfg.dtype)
    # (the training-mode attention kernels have no business with a
    # one-token input: plain XLA attention serves the parameter init)
    params = Attention(dataclasses.replace(cfg, use_flash_attention=False)).init(
        jax.random.PRNGKey(0), x)["params"]

    def step(use_kernel):
        layer = Attention(dataclasses.replace(cfg, use_flash_decode=use_kernel),
                          None, True)
        return jax.jit(lambda p, c, x: layer.apply(
            {"params": p, "cache": c}, x, mutable=["cache"])[0])(params, cache, x)

    err = rel_err(step(True), step(False))
    tol = eps_tol(cfg.dtype, DECODE_EPS_MULTIPLE)
    label = (f"{'paged' if paged else 'slab'} "
             f"{'int8' if quant else jnp.dtype(cfg.dtype).name}")
    say(f"  flash-decode {label} [{b} rows, {cfg.max_seq} positions, "
        f"width {hd}]: err {err:.2e} (tol {tol:.2e})")
    if err > tol:
        failures.append(f"flash-decode {label}: {err:.3e} > {tol:.3e}")


def check_paged_live_rows(h: int, d: int, page: int, rows: int, width: int,
                          dtype: Any, failures: List[str]) -> None:
    """``flash_decode_paged`` alone at the serving cell's geometry, whose
    steps are the live pages: 1 to all ``rows`` live at ragged lengths
    beside retired rows (all sentinels under a stale length), against
    attention in float32 through the table. A retired row must read
    zeros; the compiled call must be the Mosaic kernel of that name."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.ops import default_interpret
    from distriflow_tpu.ops.flash_decode import flash_decode_paged

    hd, n_pages = h * d, rows * width
    keys = jax.random.split(jax.random.PRNGKey(hd + page), 3)
    k, v = (jax.random.normal(key, (n_pages, page, hd), jnp.float32).astype(dtype)
            for key in keys[:2])
    q = jax.random.normal(keys[2], (rows, h, d), jnp.float32).astype(dtype)
    compiled = jax.jit(flash_decode_paged).lower(
        q, k, v, jnp.zeros((rows, width), jnp.int32),
        jnp.zeros((rows,), jnp.int32)).compile()
    if not default_interpret():
        check(DECODE_KERNEL in mosaic_kernels(compiled.as_text()),
              f"paged decode: no Mosaic call {DECODE_KERNEL!r}")
    rng = np.random.RandomState(rows)
    for n_live in sorted({min(n, rows) for n in (1, 5, 12, 32)}):
        table = np.full((rows, width), n_pages, np.int32)
        lens = np.full((rows,), width * page - 5, np.int32)  # stale
        live = rng.permutation(rows)[:n_live]
        order = rng.permutation(n_pages)
        for i, row in enumerate(live):
            lens[row] = rng.randint(1, width * page + 1)
            used = min(-(-int(lens[row]) // page) + 1, width)
            table[row, :used] = order[i * width:i * width + used]
        got = np.asarray(compiled(q, k, v, jnp.asarray(table),
                                  jnp.asarray(lens)), np.float32)
        qf, kf, vf = (np.asarray(a, np.float32) for a in (q, k, v))
        want = np.zeros_like(got)
        for row in live:
            n = int(lens[row])
            pages = table[row, :-(-n // page)]
            kr = kf[pages].reshape(-1, h, d)[:n]
            vr = vf[pages].reshape(-1, h, d)[:n]
            s = np.einsum("hd,shd->hs", qf[row], kr) / np.sqrt(d)
            p = np.exp(s - s.max(-1, keepdims=True))
            want[row] = np.einsum("hs,shd->hd", p / p.sum(-1, keepdims=True), vr)
        dead = np.setdiff1d(np.arange(rows), live)
        check(not got[dead].any(), f"paged decode: a retired row is not zero "
              f"({n_live} live)")
        err = rel_err(got[live], want[live])
        tol = eps_tol(dtype, DECODE_EPS_MULTIPLE)
        say(f"  paged decode alone [{n_live} of {rows} rows live, {width} "
            f"pages of {page}, {h} heads of {d}] {jnp.dtype(dtype).name}: "
            f"err {err:.2e} (tol {tol:.2e})")
        if err > tol:
            failures.append(f"paged decode, {n_live} live: {err:.3e} > "
                            f"{tol:.3e}")


def check_grouped_experts(t: int, d: int, f: int, count: int, live: int,
                          dtype: Any, failures: List[str]) -> None:
    """The grouped expert matmul vs the summed per-expert MLPs in float32:
    ``live`` of ``t`` tokens choose 3 experts each, and an expert nobody
    chose holds NaN, which the kernel must not read into its sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.latent_sparse import _expert_term
    from distriflow_tpu.ops import default_interpret
    from distriflow_tpu.ops.expert_grouped import grouped_expert_terms

    keys = jax.random.split(jax.random.PRNGKey(d + f), 4)
    xc = jax.random.normal(keys[0], (t, d), jnp.float32).astype(dtype)
    stack = [(jax.random.normal(key, (count,) + shape, jnp.float32)
              / shape[0] ** 0.5).astype(dtype) for key, shape in zip(
                  keys[1:], ((d, f), (d, f), (f, d)))]
    rng = np.random.RandomState(count)
    gates = np.zeros((t, count), np.float32)
    for row in range(live):
        gates[row, rng.choice(count, 3, replace=False)] = rng.dirichlet(
            np.ones(3))
    unhit = np.flatnonzero(~(gates > 0).any(axis=0))
    check(0 < len(unhit) < count, "the check needs experts hit and not hit")
    poisoned = [stack[0].at[unhit[0]].set(jnp.nan)] + stack[1:]
    compiled = jax.jit(grouped_expert_terms).lower(
        xc, jnp.asarray(gates), *poisoned).compile()
    if not default_interpret():
        check("grouped_expert_terms" in mosaic_kernels(compiled.as_text()),
              "grouped expert matmul: no Mosaic call")
    got = compiled(xc, jnp.asarray(gates), *poisoned)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, g, *ws: sum(
            _expert_term(x, *(w[e] for w in ws), g[:, e])
            for e in range(count)))(
                xc.astype(jnp.float32), jnp.asarray(gates),
                *(w.astype(jnp.float32) for w in stack))
    err, tol = rel_err(got, want), eps_tol(dtype, FWD_EPS_MULTIPLE)
    say(f"  grouped expert matmul [{t} tokens, {count - len(unhit)} of "
        f"{count} experts hit, {d} x {f}] {jnp.dtype(dtype).name}: err "
        f"{err:.2e} (tol {tol:.2e})")
    if err > tol:
        failures.append(f"grouped expert matmul: {err:.3e} > {tol:.3e}")


def phase_kernels(size: Size, serve_cfg: Any, serving: Any) -> None:
    import jax.numpy as jnp

    failures: List[str] = []
    d = size.d_model // size.n_heads
    check_attention(size.batch_per_device, size.n_heads, size.seq, d,
                    serve_cfg.dtype,
                    ("flash_attention_fwd", "flash_attention_bwd_fused"),
                    failures)
    long_s, long_dtype = size.long_attention
    check_attention(1, 2, long_s, d, jnp.dtype(long_dtype),
                    ("flash_attention_fwd", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv"), failures)
    check_fused_ce(size.batch_per_device * size.seq, size.vocab,
                   serve_cfg.dtype, failures)
    int8_cfg = dataclasses.replace(serve_cfg, kv_cache_dtype="int8_force")
    for cfg, paged in ((serve_cfg, True), (int8_cfg, True), (serve_cfg, False)):
        check_decode(cfg, serving.page_size, serving.max_slots, paged, failures)
    check_paged_live_rows(*size.paged_decode, serve_cfg.dtype, failures)
    check_grouped_experts(*size.experts, serve_cfg.dtype, failures)
    check(not failures, "kernels disagree with their oracles: "
          + "; ".join(failures))


# -- phase 2: the trainer ------------------------------------------------------


def addressable_devices(leaf: Any) -> set:
    return {shard.device for shard in leaf.addressable_shards}


def phase_train(size: Size, train_cfg: Any, recompiles: Any,
                on_tpu: bool) -> Tuple[Any, Any, Any]:
    import jax
    import numpy as np

    from distriflow_tpu import (
        TRANSFORMER_TP_RULES,
        SyncTrainer,
        data_parallel_mesh,
        run_chunked,
        shard_batch,
        transformer_lm,
    )
    from experiments.lm.data import batches, generate_corpus

    devices = jax.devices()
    n_dev = len(devices)
    batch = size.batch_per_device * n_dev
    total_steps = SINGLE_STEPS + CHUNK * CHUNKED_DISPATCHES
    corpus = generate_corpus(100_000, seed=0)
    split = len(corpus) - 8 * (size.serve_max_seq + 1)
    stream = list(batches(corpus[:split], batch, size.seq, total_steps, seed=0))

    def make_trainer(mesh, grad_accum=1):
        spec = transformer_lm(train_cfg, mesh=mesh, example_seq=size.seq)
        trainer = SyncTrainer(
            spec, mesh=mesh, learning_rate=size.lr, optimizer="adam",
            param_rules=TRANSFORMER_TP_RULES, grad_accum=grad_accum)
        trainer.init(jax.random.PRNGKey(0))
        return spec, trainer

    one_device_losses = None
    if n_dev > 1:
        # the same global batch on ONE device, as micro-batches: what the
        # data-parallel losses below must reproduce. Run first and dropped,
        # so its state never shares device 0 with the real trainer's.
        _, ref = make_trainer(data_parallel_mesh(devices[:1]), grad_accum=n_dev)
        one_device_losses = [ref.step(b) for b in stream[:SINGLE_STEPS]]
        ref.close()
        del ref

    mesh = data_parallel_mesh()
    spec, trainer = make_trainer(mesh)
    if on_tpu:
        # loss=None must have resolved to the Pallas CE; anything else is
        # the silent XLA fallback this script exists to catch
        check(spec.loss == "fused_sparse_softmax_cross_entropy",
              f"loss resolved to {spec.loss!r}, not the fused Pallas CE")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(trainer.get_params()))
    say(f"  model: {n_params / 1e6:.1f} M parameters, loss {spec.loss}, "
        f"mesh data={n_dev}, global batch {batch} x {size.seq}")

    # single steps: the first compiles, the rest must not
    losses = []
    for i in range(SINGLE_STEPS):
        before = recompiles()
        # one batch goes in already sharded, the way a user's input pipeline
        # would hand it over; the others are placed by the trainer
        b = shard_batch(mesh, stream[i]) if i == 1 else stream[i]
        if i == 1 and n_dev > 1:
            for arr in b:
                shapes = {s.data.shape for s in arr.addressable_shards}
                check(addressable_devices(arr) == set(devices)
                      and shapes == {(size.batch_per_device, size.seq)},
                      f"batch not split {n_dev} ways: {shapes}")
        losses.append(trainer.step(b))
        if i > 0:
            check(recompiles() == before,
                  f"step {i + 1} compiled {recompiles() - before} program(s) "
                  "after the warm-up step")
    say("  step() losses: " + " ".join(f"{l:.4f}" for l in losses))

    # chunked: one dispatch per CHUNK steps; only the first may compile
    chunk_marks: List[Tuple[int, float, float]] = []
    result = run_chunked(
        trainer, iter(stream[SINGLE_STEPS:]), steps=CHUNK * CHUNKED_DISPATCHES,
        steps_per_dispatch=CHUNK, log_every=CHUNK,
        log=lambda step, loss: chunk_marks.append((step, loss, recompiles())))
    check(result.steps_run == CHUNK * CHUNKED_DISPATCHES
          and len(chunk_marks) == CHUNKED_DISPATCHES,
          f"run_chunked ran {result.steps_run} steps in {len(chunk_marks)} "
          "logged dispatches")
    check(chunk_marks[-1][2] == chunk_marks[0][2],
          f"step_many compiled {chunk_marks[-1][2] - chunk_marks[0][2]} "
          "program(s) after its warm-up dispatch")
    losses += [loss for _, loss, _ in chunk_marks]
    say("  step_many chunk-end losses: "
        + " ".join(f"{loss:.4f}" for _, loss, _ in chunk_marks))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall over {total_steps} steps: "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    check(trainer.version == total_steps,
          f"trainer.version {trainer.version} != {total_steps}")

    # the step that ran IS the one with the kernels in it
    compiled = trainer.lower_step(stream[0]).compile().as_text()
    if on_tpu:
        kernels = mosaic_kernels(compiled)
        for name in TRAIN_KERNELS:
            check(name in kernels, f"compiled train step has no Mosaic call "
                                   f"{name!r} (found {sorted(kernels)})")
        say(f"  compiled step: Mosaic calls {sorted(kernels)}")
    else:
        say("  rehearsal: kernels ran in the Pallas interpreter, so the "
            "compiled step has no Mosaic custom calls to look for")

    if n_dev > 1:
        for name, tree in (("parameter", trainer.state.params),
                           ("optimizer", trainer.state.opt_state)):
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
                check(addressable_devices(leaf) == set(devices),
                      f"{name} leaf {jax.tree_util.keystr(path)} is not on "
                      f"all {n_dev} devices")
        check("all-reduce" in compiled,
              "compiled data-parallel step contains no all-reduce")
        check(bool(jax.config.jax_use_shardy_partitioner),
              "the Shardy partitioner is off")
        if on_tpu:
            # run per chip on its own rows, not gathered: each chip's CE
            # kernel sees batch_per_device * seq rows of the full vocabulary
            rows = f"[{size.batch_per_device * size.seq},{size.vocab}]"
            check(any(rows in line for line in kernels["fused_ce_fwd"]),
                  f"fused_ce_fwd does not run on per-chip rows {rows}")
        worst = max(abs(a - b) / abs(b)
                    for a, b in zip(losses, one_device_losses))
        say(f"  data-parallel vs one device, same global batch: losses "
            + " ".join(f"{l:.4f}" for l in one_device_losses)
            + f"; worst relative difference {worst:.2e} "
            f"(tol {DP_PARITY_RTOL:.0e})")
        check(worst <= DP_PARITY_RTOL,
              f"data-parallel losses differ from one-device by {worst:.3e}")
    return trainer, corpus[split:], stream[0]


# -- phase 3: the server -------------------------------------------------------


def phase_serve(size: Size, serve_cfg: Any, serving: Any, trainer: Any,
                held_out: Any, train_batch: Any, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu import (
        InferenceClient,
        InferenceServer,
        generate,
        sequence_logprob,
    )
    from distriflow_tpu.ops.flash_attention import flash_seq_supported

    params = trainer.get_params()
    if len(jax.devices()) > 1:
        # a one-chip replica, as experiments/lm/train.py --serve makes it:
        # the decode kernels have no working multi-device form on this
        # stack (PR 21), and serving four-fold replicated would prove
        # nothing more. Multi-chip serving is ROADMAP R7.
        params = jax.device_put(params, jax.devices()[0])
        say(f"  serving from {jax.devices()[0]} only")
    head_dim = size.d_model // size.n_heads
    for plen, _ in size.greedy:
        # the prefill gate falls back to XLA attention without a word
        check(flash_seq_supported(plen, head_dim,
                                  jnp.dtype(serve_cfg.dtype).itemsize),
              f"prefill length {plen} would not take the flash kernel")

    # prompts from held-out corpus text; [2] repeats the head of [1]
    span = size.serve_max_seq + 1
    prompts = [np.asarray(held_out[i * span:i * span + plen], np.int32)
               for i, (plen, _) in enumerate(size.greedy)]
    prompts[2] = np.concatenate(
        [prompts[1][:size.shared_prefix], prompts[2][size.shared_prefix:]])
    side = np.asarray(held_out[6 * span:6 * span + size.side_prompt], np.int32)

    server = InferenceServer(
        serve_cfg, params, port=0, serving=serving, verbose=True)
    log_lines: List[str] = []
    plain_log = server.logger.log

    def recording_log(*args: Any) -> None:
        log_lines.append(" ".join(str(a) for a in args))
        plain_log(*args)

    server.logger.log = recording_log
    server.setup()
    results: Dict[str, Any] = {}
    errors: List[str] = []

    def call(name: str, fn: Any) -> threading.Thread:
        def run() -> None:
            try:
                # every call dials its own connection, like separate users;
                # the timeout covers cold compiles queued behind each other
                with InferenceClient(server.address, timeout=900.0) as client:
                    results[name] = fn(client)
            except Exception as e:  # reported below; the run then fails
                errors.append(f"{name}: {type(e).__name__}: {e}")

        thread = threading.Thread(target=run, name=f"smoke-{name}")
        thread.start()
        return thread

    def greedy(i: int) -> Any:
        plen, n_new = size.greedy[i]

        def fn(client: Any) -> Any:
            out = client.generate(prompts[i][None], n_new)
            return out, client.last_serving_meta

        return fn

    sampled = dict(temperature=0.8, top_k=40, seed=7)
    try:
        with InferenceClient(server.address, timeout=900.0) as client:
            info = client.model_info()
        check(info == {"name": "transformer_lm", "vocab_size": size.vocab,
                       "max_seq": size.serve_max_seq, "d_model": size.d_model,
                       "n_layers": size.n_layers, "n_heads": size.n_heads},
              f"model_info answered {info}")

        # the prefix donor goes first; once it holds a slot (its pages are
        # then in the prefix map) the rest arrive together, while it decodes
        threads = [call("greedy1", greedy(1))]
        deadline = time.monotonic() + 900.0
        while server.batched_requests < 1 and not errors:
            check(time.monotonic() < deadline, "donor request never admitted")
            time.sleep(0.005)
        threads += [call(f"greedy{i}", greedy(i)) for i in (0, 2, 3, 4, 5)]
        threads.append(call("sampled", lambda c: c.generate(
            side[None], size.greedy[0][1], **sampled)))
        for thread in threads:
            thread.join(timeout=1000.0)
            check(not thread.is_alive(), f"{thread.name} did not finish")
        check(not errors, "requests failed: " + "; ".join(errors))

        # greedy output must be generate()'s, token for token
        flips = 0
        for i, (plen, n_new) in enumerate(size.greedy):
            got, meta = results[f"greedy{i}"]
            check(meta and meta.get("path") == "slots",
                  f"greedy{i} was not served by the engine: {meta}")
            want = np.asarray(generate(serve_cfg, params, prompts[i][None], n_new))
            check(got.shape == want.shape == (1, plen + n_new)
                  and np.array_equal(got[0, :plen], prompts[i]),
                  f"greedy{i}: shape {got.shape} or prompt echo wrong")
            diff = np.flatnonzero(got[0] != want[0])
            if diff.size:
                # one legitimate cause only: see NEAR_TIE_NATS
                t = int(diff[0])
                gap = abs(float(np.diff(np.asarray(sequence_logprob(
                    serve_cfg, params,
                    np.stack([got[0, :t + 1], want[0, :t + 1]]), from_pos=t)))[0]))
                say(f"  greedy{i} (prompt {plen}): first difference at new "
                    f"token {t - plen} of {n_new}, candidates' log-prob gap "
                    f"{gap:.4f} nats (near-tie tol {NEAR_TIE_NATS})")
                check(gap <= NEAR_TIE_NATS,
                      f"greedy{i}: engine and generate() disagree at token "
                      f"{t - plen} with a {gap:.4f}-nat gap: not a near-tie")
                flips += 1
        say(f"  {len(size.greedy)} concurrent greedy requests: "
            f"{len(size.greedy) - flips} token-identical to generate(), "
            f"{flips} differing only at a near-tie")
        shared_meta = results["greedy2"][1]
        check(shared_meta.get("prefix_tokens") == size.shared_prefix
              and server.prefix_hits >= 1,
              f"greedy2 did not reuse the {size.shared_prefix}-token prefix: "
              f"{shared_meta}, prefix_hits={server.prefix_hits}")

        with InferenceClient(server.address, timeout=900.0) as client:
            # sampled: in range, and the same seed alone gives the same
            # tokens as it did inside the concurrent batch
            again = client.generate(side[None], size.greedy[0][1], **sampled)
            first = results["sampled"]
            check(first.shape == (1, size.side_prompt + size.greedy[0][1])
                  and int(first.min()) >= 0 and int(first.max()) < size.vocab,
                  f"sampled output malformed: shape {first.shape}")
            check(np.array_equal(first, again),
                  "sampled request is not reproducible from its seed")

            n_beam = 8
            beams, beam_scores = client.beam_search(side[None], n_beam, beam_size=4)
            check(beams.shape == (1, size.side_prompt + n_beam)
                  and np.array_equal(beams[0, :size.side_prompt], side)
                  and bool(np.isfinite(beam_scores).all()),
                  f"beam output malformed: {beams.shape} {beam_scores}")
            rescored = client.score(beams, from_pos=size.side_prompt)
            beam_gap = float(np.max(np.abs(rescored - beam_scores)))
            say(f"  beam score {float(beam_scores[0]):.4f} vs teacher-forced "
                f"re-score {float(rescored[0]):.4f} (tol "
                f"{BEAM_NATS_PER_TOKEN * n_beam})")
            check(beam_gap <= BEAM_NATS_PER_TOKEN * n_beam,
                  f"beam score and its re-score differ by {beam_gap:.4f} nats")

            # score() vs the trainer's own eval loss on one training batch
            x, y = (np.asarray(v)[:size.batch_per_device] for v in train_batch)
            windows = np.concatenate([x, y[:, -1:]], axis=1)
            scores = client.score(windows, from_pos=1)
            rows = len(train_batch[0])
            weight = (np.arange(rows) < size.batch_per_device).astype(np.float32)
            (eval_loss,) = trainer.evaluate(
                *train_batch, metrics=("loss",), weight=weight)
            served_loss = float(-np.mean(scores) / size.seq)
            say(f"  score(): {served_loss:.4f} nats/token vs trainer eval "
                f"loss {eval_loss:.4f} (tol {SCORE_VS_EVAL_NATS})")
            check(abs(served_loss - eval_loss) <= SCORE_VS_EVAL_NATS,
                  "score() and the trainer's eval loss disagree: "
                  f"{served_loss:.4f} vs {eval_loss:.4f}")

        kernels = mosaic_kernels(server.lower_decode().compile().as_text())
        if on_tpu:
            check(DECODE_KERNEL in kernels,
                  f"compiled decode dispatch has no Mosaic call "
                  f"{DECODE_KERNEL!r} (found {sorted(kernels)})")
            say(f"  compiled decode dispatch: Mosaic calls {sorted(kernels)}")
    finally:
        server.stop()
    check(not any(t.name == "inference-batcher" for t in threading.enumerate()),
          "the engine thread outlived server.stop()")
    engine_errors = [line for line in log_lines if "engine error:" in line]
    check(not engine_errors, f"the server logged {engine_errors}")
    say(f"  server: {server.batched_requests} requests admitted, "
        f"{server.decode_batches} decode dispatches, "
        f"{server.prefix_hits} prefix hit(s); stopped cleanly")


# -- the run -------------------------------------------------------------------


def main(argv: Any = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearsal", action="store_true",
        help="tiny size on whatever backend is there (CPU, interpret mode): "
             "debugs this script, proves nothing about the chip")
    args = parser.parse_args(argv)

    import jax

    device = jax.devices()[0]
    report = {"platform": device.platform, "kind": device.device_kind,
              "count": len(jax.devices())}
    say(f"jax {jax.__version__}, platform {report['platform']}, device kind "
        f"{report['kind']!r}, {report['count']} device(s)")
    on_tpu = device.platform == "tpu"
    if args.rehearsal:
        say("REHEARSAL: tiny dims"
            + ("" if on_tpu else ", Pallas in interpret mode")
            + ". This proves nothing about the chip.")
    elif not on_tpu:
        # jax falls back to the CPU with a warning when libtpu cannot
        # start; this script does not
        say(f"no TPU: jax reports platform {device.platform!r}; nothing was "
            "built or run")
        return 2

    import jax.numpy as jnp

    from distriflow_tpu import (
        ServingConfig,
        SyncTrainer,
        TransformerConfig,
        enable_compile_cache,
        get_telemetry,
        install_jax_hooks,
    )

    if on_tpu and not any(key in device.device_kind.lower()
                          for key in SyncTrainer.PEAK_BF16_FLOPS):
        say(f"device kind {device.device_kind!r} is in no peak table "
            f"({sorted(SyncTrainer.PEAK_BF16_FLOPS)}); nothing was built")
        return 2

    size = REHEARSAL if args.rehearsal else FULL
    say(f"compile cache: {enable_compile_cache()}")
    telemetry = get_telemetry()
    # install_jax_hooks swallows every failure to install and says so only
    # through its return value
    check(install_jax_hooks(telemetry) is True,
          "install_jax_hooks() did not install the compile listener")
    meter = CompileMeter()

    def recompiles() -> int:
        return int(telemetry.counter_value("jit_recompiles_total"))

    def gated() -> int:
        return int(telemetry.counter_value("ops_flash_decode_gated_total"))

    thread_errors: List[str] = []
    plain_excepthook = threading.excepthook

    def on_thread_exception(hook_args: Any) -> None:
        thread_errors.append(f"{hook_args.thread.name}: "
                             f"{hook_args.exc_type.__name__}: {hook_args.exc_value}")
        plain_excepthook(hook_args)

    threading.excepthook = on_thread_exception

    # the auto choices (None) are what a user gets and what the chip run
    # must exercise; off-TPU they would pick the XLA paths, so the
    # rehearsal turns the kernels on by name to run them in the interpreter
    force = None if on_tpu else True
    train_cfg = TransformerConfig(
        vocab_size=size.vocab, d_model=size.d_model, n_heads=size.n_heads,
        n_layers=size.n_layers, d_ff=size.d_ff, max_seq=size.seq,
        dtype=jnp.bfloat16, use_flash_attention=force, use_flash_decode=force,
        loss=None if on_tpu else "fused_sparse_softmax_cross_entropy")
    # RoPE, no learned positions: the same weights serve a longer context
    serve_cfg = dataclasses.replace(train_cfg, max_seq=size.serve_max_seq)
    serving = ServingConfig()  # paged pool, 128-token pages, 8 slots, sharing
    gated_before = gated()
    t0 = time.perf_counter()
    try:
        mark = meter.mark()
        say("[kernels] each Pallas kernel vs its XLA oracle")
        phase_kernels(size, serve_cfg, serving)
        meter.report("kernels", mark)

        mark = meter.mark()
        say(f"[train] {SINGLE_STEPS} step() + {CHUNKED_DISPATCHES} x {CHUNK} "
            "step_many")
        trainer, held_out, train_batch = phase_train(
            size, train_cfg, recompiles, on_tpu)
        meter.report("train", mark)

        mark = meter.mark()
        say("[serve] paged InferenceServer over loopback")
        phase_serve(size, serve_cfg, serving, trainer, held_out, train_batch,
                    on_tpu)
        trainer.close()
        meter.report("serve", mark)

        check(gated() == gated_before,
              f"flash-decode was gated off {gated() - gated_before} time(s): "
              "a decode shape fell back to XLA")
        check(not thread_errors,
              "threads died with exceptions: " + "; ".join(thread_errors))
    except SmokeFailure as failure:
        say(f"FAILED: {failure}")
        return 1
    finally:
        threading.excepthook = plain_excepthook
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s (set-up "
        "observation)")
    result: Dict[str, Any] = {"ok": True, "device": report}
    if args.rehearsal:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
