"""Benchmark harness: the full BASELINE.md config matrix on real hardware.

Prints ONE JSON line. The top-level ``metric/value/unit/vs_baseline`` keys
carry the primary metric (BASELINE config #2 — CIFAR-10 ConvNet sync-SGD
samples/sec/chip); the ``matrix`` key embeds every other BASELINE.md row
measured in the same run:

  #1 MNIST MLP       sync-SGD           samples/sec/chip + step latency
  #2 CIFAR-10 ConvNet sync-SGD          samples/sec/chip + step latency
  #3 CIFAR-10 ConvNet async bounded-staleness (maximum_staleness>0)
  #4 FedAvg           local steps + weight pmean
  #5 MobileNetV2      sync-SGD (synthetic ImageNet-subset shapes)
  +  flagship transformer LM — tokens/sec/chip and **measured MFU**
  +  serving micro-batching speedup + decode latency rows

**The record channel is ~2,000 characters** (round-5, verdict #1: the
round-3 and round-4 records both lost their flagship rows to stdout
overflow — the driver keeps a ~2k tail of the result line). Every row is
therefore FLAT — config, value, mfu, and at most a handful of scalars;
phase breakdowns, capacity sweeps, per-context decode tables, and notes
go to **stderr**. ``_fit_line()`` enforces the budget mechanically
(progressive field-dropping, then a hard assert) and is unit-tested
(tests/test_bench_record.py).

- **vs_baseline**: ratio against a measured stand-in for the reference's
  single-host path. The reference is tfjs-node (CPU kernels); nothing is
  published (BASELINE.md) and node/tfjs is not installed here, so the
  stand-in is the same model/loss/optimizer/batch implemented in torch on
  CPU — the closest honest proxy available in this image. Configs without a
  meaningful reference counterpart report ``vs_baseline: null``.

Set ``BENCH_FAST=1`` for a quick smoke run (fewer steps, skips the
non-BASELINE extras).
"""

from __future__ import annotations

import json
from functools import partial
import os
import sys
import time
import traceback

FAST = bool(int(os.environ.get("BENCH_FAST", "0")))
# round-18 kernel-round plumbing (docs/PERFORMANCE.md §4d):
#  - BENCH_LEGS="cifar_sync,transformer,mobilenet" runs only the named legs
#    (exact bench_* suffix) — the ledger-recording runs for the kernel
#    round re-measure the three training rows without paying for the
#    serving matrix;
#  - BENCH_CPU_SCALE=1 shrinks the training legs to sizes a TPU-less host
#    can time and unlocks the host-matmul-peak MFU basis (rows say so via
#    mfu_basis — never comparable with a TPU row);
#  - BENCH_RUN_ID pins the ledger run id so baseline-then-best sequencing
#    is auditable (bench-r18-kernel-baseline / bench-r18-kernel-fused);
#  - BENCH_ROOFLINE=pre18 projects the PRE-round-18 kernel cost model
#    (two-kernel spilled-tile attention backward, unfused depthwise+GN)
#    so the ledger carries a BEFORE row for the bound_by flip.
LEGS = {s.strip() for s in os.environ.get("BENCH_LEGS", "").split(",")
        if s.strip()}
CPU_SCALE = bool(int(os.environ.get("BENCH_CPU_SCALE", "0")))
ROOFLINE_MODE = os.environ.get("BENCH_ROOFLINE", "post18")
# wall-clock budget for the whole matrix. Round-4 discipline: legs SHRINK
# when behind schedule (time_left() below), never silently skip; failures
# retry once and embed a short traceback tail in the row itself. Round-5
# (verdict #8): a squeezed leg keeps the SAME row schema — sub-measurements
# shrink rep counts, they do not drop fields.
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "450"))
HIDDEN = 10  # reference parity arch: flatten -> dense(10, relu) -> dense(10)
FLAGSHIP_LAYERS = 8  # shared by bench_transformer and bench_moe's
# per-layer routing-overhead normalization — resize in ONE place
RECORD_LIMIT = 1900  # driver record window (~2k chars; BENCH_r02-r04 tails)
_T0 = time.monotonic()

def time_left() -> float:
    """Seconds left in the matrix budget; legs consult this to size
    reps/steps (shrink-not-skip)."""
    return BUDGET_S - (time.monotonic() - _T0)


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _fetch(v):
    """Value fetch of one element: the host cannot hold the value before
    the device has produced it, so the fetch doubles as the barrier that
    ends a timed region."""
    import jax.numpy as jnp

    return float(jnp.reshape(v, (-1,))[0])


def _one_hot(rng, n, k, classes=10):
    import numpy as np

    return np.eye(classes, dtype=np.float32)[rng.randint(0, classes, (n, k))]


def _device_chunk(trainer, k, b, x_shape, classes, one_hot=True, seed=0):
    """Generate a [K, B, ...] synthetic chunk ON DEVICE (jitted PRNG).

    Round-3: the round-2 bench built chunks on the host and paid the
    host->device transfer for them — up to ~400 MB per leg, inside the
    timed budget.
    Synthetic data carries no information worth uploading; generating it
    device-side leaves the timing to what the row measures."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(trainer.mesh, P(None, "data"))

    @partial(jax.jit, static_argnums=0, out_shardings=(sharding, sharding))
    def make(shape, key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (k, b) + tuple(shape), jnp.float32)
        labels = jax.random.randint(ky, (k, b), 0, classes)
        y = (jax.nn.one_hot(labels, classes, dtype=jnp.float32)
             if one_hot else labels.astype(jnp.int32))
        return x, y

    chunk = make(tuple(x_shape), jax.random.PRNGKey(seed))
    for v in chunk:
        _fetch(v)
    return chunk


def _timed_chunked(trainer, make_chunk, steps, rounds, batch, reps=3,
                   device_chunk=None, warm_rounds=1):
    """Stage a K-step chunk on device, warm/compile at the measured scan
    length, then time a 1-dispatch leg and a ``rounds``-dispatch leg —
    each as the MIN over ``reps`` repetitions — and difference them:
    per-step = (min t_R - min t_1) / ((R-1)*K). The differencing cancels
    the constant dispatch+fetch round trip and the min suppresses
    per-trip jitter (which would otherwise swamp small models; its size on
    the current machine: not measured). ``dispatch_ms`` reports the min-of-reps single-dispatch
    time. ``device_chunk`` (already device-resident, from
    :func:`_device_chunk`) skips the host->device upload entirely.
    ``warm_rounds``: throwaway many-dispatch reps before the measured
    ones — round-5 (verdict #6): the CIFAR floor's slowest sample was
    consistently the FIRST timed many-rep (dispatch-path cold effects the
    single warm dispatch does not cover), so the floor reported cold
    state, not steady state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if device_chunk is not None:
        measured = device_chunk
    else:
        sharding = NamedSharding(trainer.mesh, P(None, "data"))
        measured = jax.tree.map(
            lambda v: jax.device_put(v, sharding), make_chunk(steps))
        for v in measured:  # device_put can be lazy: force the transfer NOW
            _fetch(v)
    losses = trainer.step_many(measured)  # compile at the MEASURED length
    _fetch(losses[-1])

    def timed(n):
        start = time.perf_counter()
        out = None
        for _ in range(n):
            out = trainer.step_many(measured)
        v = _fetch(out[-1])
        return time.perf_counter() - start, v

    t_one = min(timed(1)[0] for _ in range(reps))
    for _ in range(warm_rounds):
        timed(rounds)
    manys = [timed(rounds) for _ in range(reps)]
    t_many = min(t for t, _ in manys)
    final = manys[-1][1]

    if rounds > 1 and t_many > t_one:
        step_s = (t_many - t_one) / ((rounds - 1) * steps)
        # one step-time sample per many-rep (same differencing against the
        # min single-dispatch): the in-row spread the round-3 verdict asked
        # for — reported, not averaged away
        samples = [max((t - t_one) / ((rounds - 1) * steps), 1e-9)
                   for t, _ in manys]
    else:  # degenerate (rounds=1 or noise): fall back to the raw mean
        step_s = t_many / (rounds * steps)
        samples = [t / (rounds * steps) for t, _ in manys]
    return {
        "samples_per_sec": batch / step_s,
        "step_ms": step_s * 1e3,
        "step_ms_samples": [s * 1e3 for s in samples],
        "final_loss": final,
        "dispatch_ms": round(t_one * 1e3, 1),
    }


_HOST_PEAK = []  # measured once per process


def _host_peak_flops():
    """Measured host matmul throughput (jitted bf16 1024^3, best of 5) —
    the per-chip peak MFU denominator on hosts whose device kind has no
    published figure (BENCH_CPU_SCALE runs). Rows computed against it say
    so via ``mfu_basis``: a host-basis MFU is comparable across CPU runs
    of this bench, never with a TPU row."""
    if not _HOST_PEAK:
        import jax
        import jax.numpy as jnp

        n = 1024
        f = jax.jit(lambda a, b: (a @ b).astype(jnp.float32))
        a = jnp.ones((n, n), jnp.bfloat16)
        _fetch(f(a, a))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            _fetch(f(a, a))
            best = min(best, time.perf_counter() - t0)
        _HOST_PEAK.append(2 * n ** 3 / best)
        log(f"host matmul peak: {_HOST_PEAK[0] / 1e9:.1f} GFLOP/s "
            f"(mfu_basis=host_matmul_peak)")
    return _HOST_PEAK[0]


def _mfu_basis():
    """Which peak the row's mfu divides by — None on device kinds with a
    published figure (the default basis needs no label)."""
    import jax

    from distriflow_tpu.train.sync import SyncTrainer

    kind = jax.devices()[0].device_kind.lower()
    if any(k in kind for k in SyncTrainer.PEAK_BF16_FLOPS):
        return None
    return "host_matmul_peak"


def _mfu_or_none(trainer, batch, step_seconds, mode="sync"):
    try:
        mfu = round(
            trainer.mfu(batch, step_seconds=step_seconds, gauge_mode=mode), 4)
    except ValueError as e:  # unknown device kind (CPU runs) / no flop counts
        if not CPU_SCALE:
            log(f"mfu unavailable: {e}")
            return None
        try:  # CPU recording runs: measured host-peak basis (labeled)
            mfu = round(
                trainer.mfu(batch, step_seconds=step_seconds,
                            peak_flops_per_chip=_host_peak_flops(),
                            gauge_mode=mode), 4)
        except ValueError as e2:
            log(f"mfu unavailable even at host peak: {e2}")
            return None
    # live-gauge cross-check (docs/OBSERVABILITY.md §6): mfu() mirrors its
    # result into train_mfu{mode=<mode>} for the health sentinel — the bench
    # reads the gauge back so a drift between the row and the SLO surface
    # cannot go unnoticed. ``mode`` keys the per-workload series (sync /
    # async / mobilenet): the round-18 fix — previously this found ONLY
    # mode="sync", so non-sync rows were never gauge-audited and concurrent
    # rows clobbered one label
    from distriflow_tpu.obs.telemetry import get_telemetry

    g = get_telemetry().registry.find("train_mfu", mode=mode)
    live = getattr(g, "value", None) if g is not None else None
    if live is None or abs(live - mfu) > 1e-3:
        log(f"WARN live train_mfu{{mode={mode}}} gauge {live!r} != row mfu {mfu}")
    return mfu


def _pre18_cost_model(cats):
    """Rewind the kernel-family tally to the PRE-round-18 schedules so a
    ``BENCH_ROOFLINE=pre18`` run records the BEFORE projection the
    ``bound_by`` flip is measured against. Model flops are identical by
    construction (the reworks change schedule, not math); what moves is
    executed work and traffic:

    - ``attention_bwd`` -> ``attention_bwd_unfused``: the two-kernel
      backward re-derives P per pass (7 matmul units + 2 exps vs the
      fused kernel's 5 + 1) and, pre-18, inherited the FORWARD tile
      sizes — which spill VMEM at backward arithmetic (the measured 10x
      cliff now pinned at the ``_BWD_BLOCK_CAP`` comment). The renamed
      category picks up the spilled-tile efficiency from
      ``PHASE_EFFICIENCY`` instead of the fused kernel's.
    - ``depthwise_gn`` -> ``depthwise_gn_unfused``: three XLA ops
      (depthwise conv, GN stats+affine, relu6) round-trip the activation
      through HBM ~3x per direction vs the fused single sweep, and the
      backward keeps residuals instead of the remat recompute (hw_flops
      = model flops). Bytes scale 3x; efficiency drops to the measured
      unfused VPU figure.
    """
    out = {}
    for name, cat in cats.items():
        cat = dict(cat)
        if name == "attention_bwd":
            unit = cat["flops"] / 4.0
            cat["hw_flops"] = 7.0 * unit
            cat["transcendentals"] = cat.get("transcendentals", 0.0) * 2.0
            name = "attention_bwd_unfused"
        elif name == "depthwise_gn":
            cat["hw_flops"] = cat["flops"]
            cat["bytes_accessed"] = cat.get("bytes_accessed", 0.0) * 3.0
            name = "depthwise_gn_unfused"
        out[name] = cat
    return out


def _emit_modeled_round(report, workload):
    """Mirror a roofline projection into the trace stream as ONE modeled
    step round — a ``round`` root plus flat per-phase children sharing a
    trace_id, the exact shape the assembler's step-round path consumes —
    then read the assembled attribution back. The projected ``bound_by``
    therefore flows through the SAME taxonomy and code path as a measured
    round's (docs/OBSERVABILITY.md §5); spans carry ``modeled=true`` so a
    timeline reader can never mistake projection for measurement."""
    from distriflow_tpu.obs.telemetry import get_telemetry

    tracer = get_telemetry().tracer
    tid = f"roofline-{workload}-{ROOFLINE_MODE}"
    mark = _trace_mark()
    tracer.emit("round", trace_id=tid,
                dur_ms=report["step_time_s"] * 1e3, modeled=True)
    for name, ph in report["phases"].items():
        tracer.emit(name, trace_id=tid, dur_ms=ph["time_s"] * 1e3,
                    modeled=True, bound=ph["bound"])
    return _assemble_since(mark).attribution().get("bound_by")


def _publish_structs(batch, published_b):
    """ShapeDtypeStructs of ``batch`` with the leading dim rescaled to the
    PUBLISHED batch size. CPU_SCALE shrinks the *timed* batch, but the
    roofline must project the TPU workload's flop/byte ratio, not the
    sliver's — a B=64 conv step is HBM-bound on weight reads that B=2048
    amortizes 32x, which would misattribute ``bound_by``. Shapes only:
    ``cost_analysis`` lowers and ``pallas_cost_of`` eval_shapes, so
    nothing is allocated or executed at the published size."""
    import jax

    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(
            (published_b,) + tuple(v.shape[1:]), v.dtype), batch)


def _roofline_fields(trainer, batch, step_s, workload, extra_categories=None):
    """Projected-v5e roofline fields for a training row (round 18): the
    step program's cost analysis drives ``ops/roofline.py`` and the row
    gains ``mfu_roofline`` (projected MFU at v5e peak) + ``bound_by``
    (the phase owning the largest projected time slice).

    On TPU the Pallas categories come straight from the trainer's
    analysis and the projection is cross-checked against the measured
    step (``roofline_err``). On CPU hosts two corrections keep it honest:
    interpret mode lowers kernel bodies to plain HLO that XLA's analysis
    already counted, so the Pallas hw share leaves the XLA remainder; and
    kernels too slow to RUN interpreted at bench scale (flash attention,
    the fused depthwise+GN — interpret unrolls the grid at trace time)
    contribute through ``extra_categories``, a trace-time tally of the
    kernel-enabled step (costs are recorded at trace time,
    ops/flop_count.py, so eval_shape suffices) whose model flops move out
    of the XLA remainder they replace."""
    try:
        from distriflow_tpu.ops import default_interpret
        from distriflow_tpu.ops.roofline import roofline_report

        analysis = trainer.cost_analysis(batch)
        by_cat = {k: dict(v) for k, v
                  in (analysis.get("pallas_by_category") or {}).items()}
        interp = default_interpret()
        xla_rem = float(analysis.get("xla_flops", 0.0))
        if interp:
            xla_rem -= float(analysis.get("pallas_hw_flops", 0.0))
        for name, cat in (extra_categories or {}).items():
            if name not in by_cat:  # already a Pallas phase -> not in xla
                xla_rem -= float(cat.get("flops", 0.0))
            by_cat[name] = dict(cat)
        if ROOFLINE_MODE == "pre18":
            by_cat = _pre18_cost_model(by_cat)
        xla_rem = max(xla_rem, 0.0)
        model_flops = xla_rem + sum(
            float(c.get("flops", 0.0)) for c in by_cat.values())
        xla_bytes = max(
            float(analysis.get("bytes accessed", 0.0))
            - sum(float(c.get("bytes_accessed", 0.0))
                  for c in by_cat.values()), 0.0)
        if interp:
            # CPU-compiled "bytes accessed" counts im2col materialization
            # and unfused temporaries that TPU lowering keeps on-chip (a
            # MobileNet step claims 61 GB where real param+batch traffic
            # is ~2 GB) — that memory leg would drown every compute phase.
            # Floor the XLA remainder analytically instead: optimizer
            # param traffic (~3 passes: read params + grads, write
            # update) plus batch I/O. Kernel-phase activation traffic —
            # the dominant activation term in these models — stays exact
            # through the tally's own bytes columns above.
            import jax as _jax
            import numpy as _np
            p_bytes = sum(
                int(_np.prod(v.shape)) * _np.dtype(v.dtype).itemsize
                for v in _jax.tree.leaves(trainer.get_params()))
            b_bytes = sum(
                int(_np.prod(v.shape)) * _np.dtype(v.dtype).itemsize
                for v in _jax.tree.leaves(batch))
            xla_bytes = 3.0 * p_bytes + b_bytes
        rep = roofline_report(by_cat, model_flops, xla_flops=xla_rem,
                              xla_bytes=xla_bytes,
                              measured_step_s=None if interp else step_s)
        bound = _emit_modeled_round(rep, workload) or rep["bound_by"]
        log(f"{workload} roofline[{ROOFLINE_MODE}]: "
            f"mfu_roofline={rep['mfu_roofline']:.4f} bound_by={bound} "
            + " ".join(f"{n}={p['time_s'] * 1e3:.3f}ms({p['bound'][0]})"
                       for n, p in sorted(rep["phases"].items())))
        fields = {"mfu_roofline": round(rep["mfu_roofline"], 4),
                  "bound_by": bound}
        if "model_error" in rep:
            fields["roofline_err"] = round(rep["model_error"], 3)
        return fields
    except Exception:
        log(f"--- roofline projection failed for {workload} ---\n"
            f"{traceback.format_exc()}")
        return {}


def _phase_digest(role):
    """(count, sum_ms) per phase/step digest of ``role``'s continuous
    profiler (docs/OBSERVABILITY.md §5) — (0, 0.0) for digests with no
    samples yet, so callers can diff before/after a timed section."""
    from distriflow_tpu.obs.telemetry import get_telemetry

    reg = get_telemetry().registry
    out = {}
    probes = [("fit", ("phase_ms",), {"phase": "fit", "role": role}),
              ("submit", ("phase_ms",), {"phase": "submit", "role": role}),
              ("wall", ("phase_step_wall_ms",), {"role": role}),
              ("overlap", ("phase_step_overlap_ms",), {"role": role}),
              ("idle", ("phase_step_idle_ms",), {"role": role})]
    for key, (metric,), labels in probes:
        h = reg.find(metric, **labels)
        s = h.summary() if h is not None else None
        out[key] = (s["count"], s["sum"]) if s else (0, 0.0)
    return out


def _trace_mark():
    """Current length of the global tracer's finished-span deque — a
    cursor for assembling only the rounds a timed section emits."""
    from distriflow_tpu.obs.telemetry import get_telemetry

    return len(get_telemetry().tracer.finished())


def _assemble_since(mark):
    """Assemble the trace rows emitted after ``mark`` (the deque is
    bounded, so a wrapped window assembles what survived)."""
    from distriflow_tpu.obs.telemetry import get_telemetry
    from distriflow_tpu.obs.trace_assembler import assemble

    rows = get_telemetry().tracer.finished()
    return assemble(rows[mark:] if mark <= len(rows) else rows)


# -- config #1: MNIST MLP sync-SGD ----------------------------------------


def bench_mnist_sync(n_chips):
    import jax

    from distriflow_tpu.models import mnist_mlp
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    B = 1024
    mesh = data_parallel_mesh(jax.devices())
    trainer = SyncTrainer(mnist_mlp(hidden=HIDDEN), mesh=mesh, learning_rate=0.01)
    trainer.init(jax.random.PRNGKey(0))

    steps = 50 if FAST else 120
    chunk = _device_chunk(trainer, steps, B, (28, 28, 1), 10)
    r = _timed_chunked(trainer, None, steps=steps,
                       rounds=3 if FAST else 30, batch=B, device_chunk=chunk)
    # step_ms is the sync-SGD allreduce step latency (BASELINE.md primary
    # metric): the device-side per-step time of the full fwd+bwd ->
    # XLA-allreduced grads -> update program. The per-dispatch wall time
    # (stderr) includes the host->device round trip (not measured on the
    # current machine).
    log(f"#1 mnist sync: {r['samples_per_sec']:.0f} samples/s "
        f"({r['step_ms']:.3f} ms/step device, {r['dispatch_ms']} ms/dispatch, "
        f"batch {B}, final_loss {r['final_loss']:.4f})")
    return {
        "config": "mnist_mlp_sync",
        "metric": "samples/sec/chip",
        "value": round(r["samples_per_sec"] / n_chips, 1),
        "step_ms": round(r["step_ms"], 4),
    }


def bench_torch_mlp():
    import torch

    B = 1024
    torch.manual_seed(0)
    model = torch.nn.Sequential(
        torch.nn.Flatten(), torch.nn.Linear(784, HIDDEN), torch.nn.ReLU(),
        torch.nn.Linear(HIDDEN, 10))
    opt = torch.optim.SGD(model.parameters(), lr=0.01)
    loss_fn = torch.nn.CrossEntropyLoss()
    x = torch.randn(B, 28, 28, 1)
    y = torch.randint(0, 10, (B,))

    def step():
        opt.zero_grad()
        loss_fn(model(x), y).backward()
        opt.step()

    for _ in range(5):
        step()
    n = 30 if FAST else 60
    start = time.perf_counter()
    for _ in range(n):
        step()
    sps = B * n / (time.perf_counter() - start)
    log(f"torch-cpu MLP baseline: {sps:.0f} samples/sec")
    return sps


# -- config #2: CIFAR-10 ConvNet sync-SGD ---------------------------------


def bench_cifar_sync(n_chips):
    import jax
    import numpy as np

    from distriflow_tpu.models import cifar_convnet
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    # round-3 tuned config (docs/PERFORMANCE.md §conv rows): bf16 compute +
    # batch 2048. bf16 at the old B=512 is LOSS-making (3.9 ms vs 2.1 f32 —
    # too little work per conv to amortize), but at B=2048 it is the clear
    # winner: 6.2 ms vs 12.6 f32. r02 ran f32 @ B=512: 200k samples/s, 0.22.
    import jax.numpy as jnp

    # CPU_SCALE: a B=256 bf16 conv step measures ~32 s on a single-core
    # XLA:CPU host (B=8 ~1 s) — B=64 x 2-step chunks keep the whole leg
    # within ~2 min while the roofline fields stay shape-exact
    B = 64 if CPU_SCALE else 2048
    mesh = data_parallel_mesh(jax.devices())
    trainer = SyncTrainer(cifar_convnet(dtype=jnp.bfloat16), mesh=mesh,
                          learning_rate=0.01)
    trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    # round-4 (verdict #7): more reps, and the row carries the measured
    # SPREAD (mfu floor/median) so the floor is auditable. steps stays at
    # 12: a 16-step chunk re-crosses the lane-padding cliff (the
    # [K, B, 32, 32, 3] copy tiles T(8,128) and pads channels 3 -> 128 —
    # 42.7x HBM blowup, 16 GB, compile fails)
    steps = 2 if CPU_SCALE else (8 if FAST else 12)
    reps = 1 if CPU_SCALE else (3 if FAST else 6)
    chunk = _device_chunk(trainer, steps, B, (32, 32, 3), 10)
    # rounds=6: each differenced sample then spans 60 steps, so per-trip
    # dispatch jitter averages down.
    # warm_rounds=1 (round-5): the first timed many-rep was consistently
    # the slowest — cold dispatch-path effects, not steady state — and it
    # alone set the r03/r04 mfu floor below the 0.30 bar.
    r = _timed_chunked(trainer, None, steps=steps,
                       rounds=2 if CPU_SCALE else (3 if FAST else 6),
                       batch=B, reps=reps, device_chunk=chunk,
                       warm_rounds=0 if CPU_SCALE else 2)
    lat_x = rng.randn(B, 32, 32, 3).astype(np.float32)
    lat_y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, B)]
    mfu = _mfu_or_none(trainer, (lat_x, lat_y), r["step_ms"] / 1e3)
    ss = sorted(r["step_ms_samples"])
    med = ss[len(ss) // 2]
    mfu_min = mfu_med = None
    if mfu is not None:
        # min step time -> max MFU; the FLOOR is the slowest rep
        mfu_min = round(mfu * r["step_ms"] / ss[-1], 4)
        mfu_med = round(mfu * r["step_ms"] / med, 4)
    log(f"#2 cifar sync: {r['samples_per_sec']:.0f} samples/s "
        f"({r['step_ms']:.2f} ms/step, mfu={mfu}, floor={mfu_min}, "
        f"med={mfu_med}, step_ms samples={[round(s, 3) for s in ss]}, "
        f"dispatch {r['dispatch_ms']} ms, batch {B} bf16, "
        f"final_loss {r['final_loss']:.4f})")
    row = {
        "config": "cifar10_convnet_sync",
        "metric": "samples/sec/chip",
        "value": round(r["samples_per_sec"] / n_chips, 1),
        "step_ms": round(r["step_ms"], 3),
        "mfu": mfu,
        "mfu_min": mfu_min,
        "mfu_med": mfu_med,
    }
    if mfu is not None and _mfu_basis():
        row["mfu_basis"] = _mfu_basis()
    # round-18 leg (c): the row names its projected binding phase so the
    # 0.30-floor gap is attributed, not just observed (PERFORMANCE.md §4d)
    rl_batch = (_publish_structs((lat_x, lat_y), 2048) if CPU_SCALE
                else (lat_x, lat_y))
    row.update(_roofline_fields(trainer, rl_batch, r["step_ms"] / 1e3,
                                "cifar10_convnet_sync"))
    return row


def bench_torch_cifar():
    import torch

    B = 512
    torch.manual_seed(0)
    layers = []
    cin = 3
    for f in (64, 128, 256):  # same arch as models/zoo.py cifar_convnet
        layers += [torch.nn.Conv2d(cin, f, 3, padding=1), torch.nn.ReLU(),
                   torch.nn.MaxPool2d(2)]
        cin = f
    layers += [torch.nn.Flatten(), torch.nn.Linear(256 * 4 * 4, 256),
               torch.nn.ReLU(), torch.nn.Linear(256, 10)]
    model = torch.nn.Sequential(*layers)
    opt = torch.optim.SGD(model.parameters(), lr=0.01)
    loss_fn = torch.nn.CrossEntropyLoss()
    x = torch.randn(B, 3, 32, 32)
    y = torch.randint(0, 10, (B,))

    def step():
        opt.zero_grad()
        loss_fn(model(x), y).backward()
        opt.step()

    for _ in range(2):
        step()
    n = 3 if FAST else 5
    start = time.perf_counter()
    for _ in range(n):
        step()
    sps = B * n / (time.perf_counter() - start)
    log(f"torch-cpu ConvNet baseline: {sps:.0f} samples/sec")
    return sps


# -- wire-cost accounting (docs/PERFORMANCE.md §8) -------------------------


def _wire_cost(params, gradient_compression="none", topk_fraction=0.01,
               weight_compression="none"):
    """(up_bytes_per_update, down_bytes_per_broadcast) for a param-shaped
    tree under the given wire modes, computed with the REAL serialization
    helpers (the in-process trainers never serialize, so the wire cost is
    modeled from the exact same code path the multi-process plane ships
    through — payload bytes + sparse index bytes, headers excluded)."""
    import jax
    import numpy as np

    from distriflow_tpu.utils.serialization import (
        cast_tree,
        quantize_array,
        serialize_tree,
        topk_array,
        tree_wire_nbytes,
    )

    host = [np.asarray(l) for l in jax.tree.leaves(params)]
    if gradient_compression in ("topk", "topk_int8"):
        up = {str(i): topk_array(l, topk_fraction,
                                 quantize=gradient_compression == "topk_int8")
              for i, l in enumerate(host)}
    elif gradient_compression == "int8":
        up = {str(i): quantize_array(l) for i, l in enumerate(host)}
    else:
        up = serialize_tree(
            host if gradient_compression == "none"
            else cast_tree(host, gradient_compression)
        )
    down_tree = host if weight_compression == "none" else cast_tree(
        host, weight_compression)
    return tree_wire_nbytes(up), tree_wire_nbytes(serialize_tree(down_tree))


# -- config #3: CIFAR-10 async-SGD, bounded staleness ----------------------


def bench_cifar_async(matrix):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.data.dataset import DistributedDataset
    from distriflow_tpu.models import cifar_convnet
    from distriflow_tpu.train.async_sgd import AsyncSGDTrainer

    # round-3: steps_per_upload amortizes the host ping-pong (the r02 bench
    # measured an 89x penalty at one dispatch per batch). Round-4: SSP
    # admission control bounds staleness by construction (rejected=0) and
    # batches stage to the device as taken. Round-5 (verdict #3): the
    # accounting must SUM — the row carries wall_ms, the per-worker phase
    # sum, and the unattributed remainder, plus the measured per-dispatch
    # host-latency floor that sets this backend's async ceiling.
    B, K = 256, 8
    n_batches = 32 if FAST else 96
    max_stale = 2

    # the per-dispatch floor: min wall time of dispatch->fetch of a
    # TRIVIAL jitted op. Every upload serializes >= 3 such round trips
    # (snapshot put, fit, grad put + apply) through the host link, so
    # K*B / (3 * floor) bounds async samples/sec no matter how fast the
    # chip is. Its size on the current machine: not measured.
    tiny = jax.jit(lambda a: a + 1)
    _fetch(tiny(jnp.float32(0)))
    floors = []
    for _ in range(5):
        t0 = time.perf_counter()
        _fetch(tiny(jnp.float32(t0)))
        floors.append(time.perf_counter() - t0)
    dispatch_floor_ms = min(floors) * 1e3

    rng = np.random.RandomState(0)
    x = rng.randn(n_batches * B, 32, 32, 3).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n_batches * B)]
    dataset = DistributedDataset(x, y, {"batch_size": B, "epochs": 1})
    trainer = AsyncSGDTrainer(
        cifar_convnet(), dataset,
        learning_rate=0.01,
        steps_per_upload=K,
        hyperparams={"maximum_staleness": max_stale,
                     "staleness_decay": 0.7},
        stage_dataset=True,
        # round-6: double-buffered upload pipeline — each worker's
        # EF-compress/serialize/submit rides its comm thread while the
        # train thread fits the next K-group; depth 2 keeps effective
        # staleness within max_stale (window is clamped server-side too)
        inflight_window=2,
    )
    trainer.init(jax.random.PRNGKey(0))
    trainer.pre_stage(trainer.devices[0])
    # warm TWO K-groups through one worker: the first compiles the
    # scan-grad + apply at init-params layouts, the second at apply-OUTPUT
    # layouts — they differ, and skipping the second means a surprise
    # ~47 s recompile inside the timed run
    trainer.worker_loop(0, max_steps=2 * K)
    warm_uploads = trainer.applied_updates + trainer.rejected_updates
    for k in trainer.phase_ms:
        trainer.phase_ms[k] = 0.0
    # the continuous profiler kept recording through the warm-up; diff its
    # digests across the timed train() only (docs/OBSERVABILITY.md §5)
    prof_base = _phase_digest("trainer")
    trace_mark = _trace_mark()

    workers = 4
    start = time.perf_counter()
    trainer.train(num_workers=workers)
    elapsed = time.perf_counter() - start
    processed = n_batches - 2 * K  # minus warm batches
    sps = processed * B / elapsed
    # MFU for the async row (round-18 satellite): per-batch grad flops over
    # the per-batch wall — host-coordination-bound by design, but now the
    # row mirrors into train_mfu{mode=async} and is gauge-audited like
    # every other MFU row
    mfu = _mfu_or_none(trainer, B, elapsed / max(processed, 1), mode="async")
    uploads = max(
        trainer.applied_updates + trainer.rejected_updates - warm_uploads, 1)

    # accounting that must sum (verdict #3): everything the workers
    # dispatch is async, so the wall decomposes into (a) per-worker
    # host-side dispatch time (the thread phase clocks, averaged over
    # workers), (b) the device-queue DRAIN the run ends on (measured in
    # train() with a value-fetch barrier), and (c) the unattributed
    # remainder (thread scheduling/GIL + queue waits between dispatches):
    # wall == dispatch/workers + drain + unattributed by construction.
    wall_ms = elapsed * 1e3
    drain_ms = trainer.phase_ms["drain"]
    dispatch_sum_ms = sum(v for k, v in trainer.phase_ms.items()
                          if k != "drain")
    unattributed_ms = wall_ms - drain_ms - dispatch_sum_ms / workers
    phases = {k: round(v / uploads, 1) for k, v in trainer.phase_ms.items()}

    # profiler digest deltas: per-upload phase means plus the step-level
    # overlap/idle attribution, and the reconciliation the acceptance gate
    # checks — per-worker step wall + drain must land within 5% of wall
    prof_now = _phase_digest("trainer")

    def _delta_mean(key):
        c = prof_now[key][0] - prof_base[key][0]
        s = prof_now[key][1] - prof_base[key][1]
        return round(s / c, 1) if c else None

    def _delta_sum(key):
        return prof_now[key][1] - prof_base[key][1]

    fit_ms = _delta_mean("fit")
    submit_ms = _delta_mean("submit")
    idle_ms = _delta_mean("idle")
    # overlap per ROUND, not per digest observation: the comm threads
    # observe the overlap digest once per booked phase (admission_wait,
    # submit) on top of the per-step busy-wall excess, so the digest's own
    # mean would understate how much comm time each round actually hid.
    # Sum-over-uploads is the per-round figure the assembler's overlap_ms
    # (mean over applied rounds) is compared against below.
    overlap_sum_ms = _delta_sum("overlap")
    overlap_ms = round(overlap_sum_ms / uploads, 1)
    submit_sum_ms = _delta_sum("submit")
    # pipeline efficiency: the fraction of submit-phase time hidden behind
    # fit. Serial client: 0 (submit rides the step thread, nothing in the
    # overlap digest). Perfect depth-2 pipeline: -> 1 (every submit ms is
    # also an overlap ms). Can exceed 1 when admission_wait also hides.
    pipe_eff = (round(overlap_sum_ms / submit_sum_ms, 2)
                if submit_sum_ms > 0 else None)
    inflight_depth = trainer._effective_window()
    # recon stays honest under the comm thread by construction:
    # record_overlap never feeds any step's busy sum or wall, so
    # per-worker step wall + drain still tiles the run's wall clock
    step_wall_sum = _delta_sum("wall")
    recon_est_ms = step_wall_sum / workers + drain_ms
    recon_pct = round(100.0 * abs(recon_est_ms - wall_ms) / wall_ms, 1)
    log(f"#3p profiler: fit {fit_ms} submit {submit_ms} overlap {overlap_ms} "
        f"idle {idle_ms} ms/step; pipe depth {inflight_depth} eff "
        f"{pipe_eff} (overlap {overlap_sum_ms:.0f}/submit "
        f"{submit_sum_ms:.0f} ms); step-wall {step_wall_sum:.0f}/{workers} "
        f"workers + drain {drain_ms:.0f} = {recon_est_ms:.0f} vs wall "
        f"{wall_ms:.0f} ms ({recon_pct}% off)")

    # round-trip assembly (docs/OBSERVABILITY.md §9): the same rounds the
    # profiler digested, rebuilt from their trace rows — bound_by names the
    # phase that owned the most critical-path time, and the assembler's
    # overlap must agree with the profiler's (both are busy - wall per
    # round; the acceptance gate pins them within 10%)
    asm = _assemble_since(trace_mark).attribution()
    bound_by = asm["bound_by"]
    asm_overlap_ms = asm["overlap_ms"]
    prof_overlap = overlap_ms if overlap_ms is not None else 0.0
    tol = max(abs(prof_overlap) * 0.10, 1.0)  # 10%, 1 ms noise floor
    agree = abs(asm_overlap_ms - prof_overlap) <= tol
    log(f"#3t assembler: {asm['applied']}/{asm['rounds']} rounds, "
        f"bound_by={bound_by}, overlap {asm_overlap_ms} vs profiler "
        f"{prof_overlap} ms/step "
        f"({'consistent' if agree else 'INCONSISTENT'})")

    # wire-cost columns (docs/PERFORMANCE.md §8): what ONE update/broadcast
    # of this model costs on the multi-process wire, dense f32 vs 1% top-k
    up_dense, down_dense = _wire_cost(trainer.params)
    up_topk, _ = _wire_cost(trainer.params, gradient_compression="topk",
                            topk_fraction=0.01)
    up_topk8, _ = _wire_cost(trainer.params, gradient_compression="topk_int8",
                             topk_fraction=0.01)
    matrix.append({
        "config": "cifar10_convnet_async_topk",
        "metric": "up_bytes_per_update",
        "value": up_topk,
        "dense_bytes": up_dense,
        "reduction_x": round(up_dense / up_topk, 1),
        "topk_int8_bytes": up_topk8,
        "topk_int8_reduction_x": round(up_dense / up_topk8, 1),
        "topk_fraction": 0.01,
        "down_bytes_per_broadcast": down_dense,
    })
    log(f"#3w wire: dense {up_dense} B/update vs topk(1%) {up_topk} B "
        f"({up_dense / up_topk:.0f}x) vs topk_int8 {up_topk8} B "
        f"({up_dense / up_topk8:.0f}x); broadcast {down_dense} B")

    sync_row = next(
        (e for e in matrix if e.get("config") == "cifar10_convnet_sync"), {})
    pct = (round(100.0 * sps / (sync_row["value"] * len(jax.devices())), 1)
           if sync_row.get("value") else None)
    # round-6: the throughput floor/ceiling come from the SAME profiler
    # digests as the rest of the row, not the 3x-tiny-op hand math of r05.
    # Pipelined steady state is bounded by the slower stage: fit
    # parallelizes across the workers' train threads; submit (which holds
    # the version-locked apply) is conservatively treated as serialized
    # across the per-worker comm threads. The tiny-op dispatch probe stays
    # as a logged backend diagnostic only.
    fit_sum_ms = _delta_sum("fit")
    floor_ms = max(fit_sum_ms / workers, submit_sum_ms) / uploads
    ceiling = K * B / (floor_ms / 1e3) if floor_ms > 0 else None
    log(f"#3 cifar async: {sps:.0f} samples/s ({processed} batches, K={K}, "
        f"applied={trainer.applied_updates} rejected={trainer.rejected_updates}, "
        f"{pct}% of sync; wall {wall_ms:.0f} ms = dispatch "
        f"{dispatch_sum_ms:.0f}/{workers} workers + drain {drain_ms:.0f} + "
        f"unattributed {unattributed_ms:.0f}; phases/upload {phases}; "
        f"digest floor {floor_ms:.1f} ms/upload -> ceiling ~{ceiling:.0f} "
        f"samples/s; tiny-op dispatch {dispatch_floor_ms:.1f} ms)")
    return {
        "config": "cifar10_convnet_async_bounded_staleness",
        "metric": "samples/sec",
        "value": round(sps, 1),
        "mfu": mfu,
        "pct_of_sync": pct,
        "applied": trainer.applied_updates,
        "rejected": trainer.rejected_updates,
        "wall_ms": round(wall_ms, 0),
        "drain_ms": round(drain_ms, 0),
        "dispatch_ms": round(dispatch_sum_ms / workers, 0),
        "unattributed_ms": round(unattributed_ms, 0),
        "fit_ms": fit_ms,
        "submit_ms": submit_ms,
        "overlap_ms": overlap_ms,
        "idle_ms": idle_ms,
        "recon_pct": recon_pct,
        "bound_by": bound_by,
        "asm_overlap_ms": asm_overlap_ms,
        "inflight_depth": inflight_depth,
        "pipe_eff": pipe_eff,
        "floor_ms": round(floor_ms, 1),
        "ceiling_sps": round(ceiling, 0) if ceiling else None,
        "up_bytes_per_update": up_dense,
        "down_bytes_per_broadcast": down_dense,
    }


# -- config #4: federated averaging ---------------------------------------


def bench_fedavg():
    import jax
    import numpy as np

    from distriflow_tpu.models import cifar_convnet
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.federated import FederatedAveragingTrainer

    mesh = data_parallel_mesh(jax.devices())
    k, b = 8, 128
    trainer = FederatedAveragingTrainer(
        cifar_convnet(), mesh=mesh, local_steps=k, local_batch_size=b,
        learning_rate=0.01)
    trainer.init(jax.random.PRNGKey(0))
    w = trainer.num_workers
    rng = np.random.RandomState(0)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P("data"))
    x = jax.device_put(
        rng.randn(w, k, b, 32, 32, 3).astype(np.float32), sharding)
    y = jax.device_put(
        np.eye(10, dtype=np.float32)[rng.randint(0, 10, (w, k, b))], sharding)
    _fetch(x), _fetch(y)  # stage the round data on device before timing
    trainer.round(x, y)  # compile + warm
    trace_mark = _trace_mark()  # assemble only the timed rounds below
    rounds = 2 if FAST else 5
    start = time.perf_counter()
    for _ in range(rounds):
        loss = trainer.round(x, y)
    elapsed = time.perf_counter() - start
    asm = _assemble_since(trace_mark).attribution()
    sps = w * k * b * rounds / elapsed
    # honesty note (round-2 verdict weak item 4): with one physical chip,
    # workers == 1 and the round's defining weight-pmean is a no-op — this
    # row measures the local-steps scan only. The multi-worker round
    # (8 workers, one pmean/round) is proven on the 8-device virtual mesh
    # by the driver dryrun and tests, not here.
    log(f"#4 fedavg: {sps:.0f} samples/s ({elapsed*1e3/rounds:.1f} ms/round, "
        f"{w} workers x {k} local steps, final_loss {loss:.4f}; single-chip: "
        "weight-pmean is a no-op at workers=1, multi-worker semantics "
        "covered by dryrun/tests)")
    up_dense, down_dense = _wire_cost(trainer.params)
    return {
        "config": "fedavg_cifar10",
        "metric": "samples/sec",
        "value": round(sps, 1),
        "round_ms": round(elapsed * 1e3 / rounds, 2),
        "workers": w,
        "bound_by": asm["bound_by"],
        "up_bytes_per_update": up_dense,
        "down_bytes_per_broadcast": down_dense,
    }


def bench_obs_overhead():
    """Fleet-plane overhead row: the SAME loopback async-CIFAR smoke run
    twice — telemetry + report shipping fully on (tiny report interval,
    so ~every upload carries one) vs fully off — and the per-round delta
    pinned in the ledger (docs/OBSERVABILITY.md §10). The report path is
    snapshot-diff + JSON on the upload metadata, so the honest budget is
    ~a millisecond; the band is wide because loopback rounds on a shared
    CPU host jitter far more than that."""
    import jax
    import numpy as np

    from distriflow_tpu.client.abstract_client import DistributedClientConfig
    from distriflow_tpu.client.async_client import AsynchronousSGDClient
    from distriflow_tpu.data.dataset import DistributedDataset
    from distriflow_tpu.models import cifar_convnet
    from distriflow_tpu.models.base import SpecModel
    from distriflow_tpu.obs import Telemetry
    from distriflow_tpu.server.abstract_server import DistributedServerConfig
    from distriflow_tpu.server.async_server import AsynchronousSGDServer
    from distriflow_tpu.server.models import DistributedServerInMemoryModel

    B = 32
    n_batches = 6 if FAST else 12
    rng = np.random.RandomState(0)
    x = rng.randn(n_batches * B, 32, 32, 3).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n_batches * B)]

    def one_run(obs_on):
        tel_s = Telemetry(enabled=obs_on)
        tel_c = Telemetry(enabled=obs_on)
        dataset = DistributedDataset(x, y, {"batch_size": B, "epochs": 1})
        client_model = SpecModel(cifar_convnet(), rng=jax.random.PRNGKey(0))
        server_model = SpecModel(cifar_convnet(), rng=jax.random.PRNGKey(0))
        # warm the jit caches OUTSIDE the timed window (both modes pay
        # compilation identically, but pulling it out kills the noise)
        for m in (client_model, server_model):
            m.setup()
            m.update(m.fit(x[:B], y[:B]))
        server = AsynchronousSGDServer(
            DistributedServerInMemoryModel(server_model), dataset,
            DistributedServerConfig(
                heartbeat_interval_s=0.5, heartbeat_timeout_s=20.0,
                telemetry=tel_s),
        )
        server.setup()
        client = AsynchronousSGDClient(
            server.address, client_model,
            DistributedClientConfig(
                hyperparams={
                    "telemetry_report_interval_s": 0.001 if obs_on else 0},
                heartbeat_interval_s=0.5, heartbeat_timeout_s=20.0,
                upload_timeout_s=60.0, telemetry=tel_c),
        )
        try:
            client.setup(timeout=20.0)
            start = time.perf_counter()
            client.train_until_complete(timeout=600.0)
            elapsed = time.perf_counter() - start
        finally:
            client.dispose()
            server.stop()
        applied = max(server.applied_updates, 1)
        return elapsed * 1e3 / applied, server.collector.reports_ingested

    off_ms, _ = one_run(False)
    on_ms, reports = one_run(True)
    overhead_ms = on_ms - off_ms
    log(f"#obs obs_overhead: {on_ms:.1f} ms/round on vs {off_ms:.1f} off "
        f"({overhead_ms:+.2f} ms, {reports} reports over {n_batches} rounds)")
    return {
        "config": "obs_overhead",
        "metric": "telemetry+report overhead per async round",
        "value": round(overhead_ms, 2),
        "obs_on_round_ms": round(on_ms, 2),
        "obs_off_round_ms": round(off_ms, 2),
        "overhead_ms": round(overhead_ms, 2),
        "reports": reports,
    }


def bench_obs_timeline():
    """Timeline-sampler overhead row (docs/OBSERVABILITY.md §12): the
    SAME loopback async-CIFAR smoke run twice with telemetry fully on —
    once with the background TimelineStore sampling the registry every
    50 ms and persisting ``timeline.jsonl``, once without — and the
    per-round delta pinned in the ledger. The sampler is a snapshot +
    bucket-state copy + one JSONL append per tick off the hot path, so
    the honest budget is noise-level; the row exists so a regression
    (say, a sampler that starts holding the registry lock across I/O)
    shows up as a number, not a vibe."""
    import os
    import tempfile

    import jax
    import numpy as np

    from distriflow_tpu.client.abstract_client import DistributedClientConfig
    from distriflow_tpu.client.async_client import AsynchronousSGDClient
    from distriflow_tpu.data.dataset import DistributedDataset
    from distriflow_tpu.models import cifar_convnet
    from distriflow_tpu.models.base import SpecModel
    from distriflow_tpu.obs import TIMELINE_FILENAME, Telemetry
    from distriflow_tpu.server.abstract_server import DistributedServerConfig
    from distriflow_tpu.server.async_server import AsynchronousSGDServer
    from distriflow_tpu.server.models import DistributedServerInMemoryModel

    B = 32
    n_batches = 6 if FAST else 12
    rng = np.random.RandomState(0)
    x = rng.randn(n_batches * B, 32, 32, 3).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, n_batches * B)]

    def one_run(sampler_on, save_dir):
        tel = Telemetry()
        if sampler_on:
            tel.start_timeline(interval_s=0.05, save_dir=save_dir)
        dataset = DistributedDataset(x, y, {"batch_size": B, "epochs": 1})
        client_model = SpecModel(cifar_convnet(), rng=jax.random.PRNGKey(0))
        server_model = SpecModel(cifar_convnet(), rng=jax.random.PRNGKey(0))
        for m in (client_model, server_model):
            m.setup()
            m.update(m.fit(x[:B], y[:B]))
        server = AsynchronousSGDServer(
            DistributedServerInMemoryModel(server_model), dataset,
            DistributedServerConfig(
                heartbeat_interval_s=0.5, heartbeat_timeout_s=20.0,
                telemetry=tel),
        )
        server.setup()
        client = AsynchronousSGDClient(
            server.address, client_model,
            DistributedClientConfig(
                heartbeat_interval_s=0.5, heartbeat_timeout_s=20.0,
                upload_timeout_s=60.0, telemetry=tel),
        )
        try:
            client.setup(timeout=20.0)
            start = time.perf_counter()
            client.train_until_complete(timeout=600.0)
            elapsed = time.perf_counter() - start
        finally:
            client.dispose()
            server.stop()
        tel.stop_timeline()
        samples = len(tel.timeline.samples()) if sampler_on else 0
        applied = max(server.applied_updates, 1)
        return elapsed * 1e3 / applied, samples

    with tempfile.TemporaryDirectory() as d:
        off_ms, _ = one_run(False, None)
        on_ms, samples = one_run(True, d)
        jsonl_kib = os.path.getsize(
            os.path.join(d, TIMELINE_FILENAME)) / 1024.0
    overhead_ms = on_ms - off_ms
    log(f"#obs obs_timeline: {on_ms:.1f} ms/round sampled vs {off_ms:.1f} "
        f"unsampled ({overhead_ms:+.2f} ms; {samples} samples, "
        f"{jsonl_kib:.1f} KiB timeline.jsonl)")
    return {
        "config": "obs_timeline",
        "metric": "50 ms timeline sampler overhead per async round",
        "value": round(overhead_ms, 2),
        "sampler_on_round_ms": round(on_ms, 2),
        "sampler_off_round_ms": round(off_ms, 2),
        "timeline_samples": samples,
        "timeline_jsonl_kib": round(jsonl_kib, 1),
    }


def bench_fleet_soak():
    """Fleet soak row (docs/ROBUSTNESS.md §10): the churn+chaos soak
    harness at a fixed seed — goodput (applies/sec of wall), the fleet
    p99 round time, and the adaptive-controller action count. The run
    itself enforces exactness (exactly-once accounting, fleet-vs-local
    telemetry reconciliation, convergence vs the serial baseline) and
    raises on any violation, so a row existing at all certifies the
    invariants; the ledger then pins the PERFORMANCE of surviving the
    abuse. Numpy-only clients — no jit, so the numbers move with host
    scheduling, not compilation."""
    from distriflow_tpu.fleet import SoakConfig, run_soak

    n_clients = 24 if FAST else 64
    result = run_soak(SoakConfig(
        n_clients=n_clients,
        n_batches=60 if FAST else 150,
        epochs=2, churn_kills=4 if FAST else 8,
        timeout_s=min(180.0, max(60.0, time_left())),
    ))
    log(f"#soak fleet_soak: {result.applied} applies over "
        f"{result.n_clients} clients in {result.wall_s:.1f}s "
        f"({result.goodput_applies_per_s:.0f}/s), {result.kills} kills, "
        f"{result.deduped} dedup, {result.suppressed} suppressed, "
        f"{result.adaptations} adaptations")
    return {
        "config": "fleet_soak",
        "metric": "soak goodput under churn+chaos (applies/sec)",
        "value": round(result.goodput_applies_per_s, 1),
        "clients": result.n_clients,
        "goodput_applies_per_s": round(result.goodput_applies_per_s, 1),
        "round_p99_ms": round(result.round_p99_ms, 2),
        "ack_p99_ms": round(result.ack_p99_ms, 2),
        "kills": result.kills,
        "rejoins": result.rejoins,
        "deduped": result.deduped,
        "suppressed": result.suppressed,
        "adaptations": result.adaptations,
        "final_loss": round(result.final_loss, 5),
    }


# -- config #5: MobileNetV2 (synthetic ImageNet-subset) --------------------


def bench_mobilenet(n_chips):
    import jax
    import numpy as np

    from distriflow_tpu.models.mobilenet import mobilenet_v2
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    # round-3 tuned config (docs/PERFORMANCE.md §conv rows): bf16 compute
    # (params stay f32), batch 256 — the measured optimum; 384+ falls off a
    # working-set cliff (12+ ms) and img sizes that don't halve cleanly
    # through the five stride-2 stages (96 -> 48/24/12/6/3) tile worse than
    # they look. Round-5 (verdict #5): the depthwise/groupnorm levers built
    # in round 4 are now actually exercised — the leg measures
    # {conv, shift} x {flax, onepass} and reports the winner as the row.
    # CPU_SCALE: one bf16 MobileNet step measures ~4.3 s/sample on
    # XLA:CPU (34.5 s at B=8) — B=2 single-step chunks or the leg alone
    # blows the budget
    B, size, classes = (2 if CPU_SCALE else 256), 96, 100  # experiments/
    pub_b = 256  # published batch: roofline projects the TPU workload
    import jax.numpy as jnp

    mesh = data_parallel_mesh(jax.devices())
    rng = np.random.RandomState(0)
    x1 = rng.randn(B, size, size, 3).astype(np.float32)
    y1 = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, B)]

    best = None
    results = {}
    # the fused Pallas depthwise+GN block is not a candidate: Mosaic
    # refuses the kernel on TPU (ops/depthwise_gn.py MOSAIC_REFUSAL, PR 21)
    # and interpret mode cannot be timed. Off-TPU it still contributes
    # through the roofline tally below.
    if CPU_SCALE:
        combos = [("conv", "flax"), ("shift", "onepass")]
    elif time_left() < 120:
        combos = [("conv", "flax"), ("shift", "onepass")]
    else:
        combos = [("conv", "flax"), ("shift", "flax"), ("conv", "onepass"),
                  ("shift", "onepass")]
    trainers = {}
    for dw, gn in combos:
        trainer = SyncTrainer(
            mobilenet_v2(image_size=size, classes=classes, dtype=jnp.bfloat16,
                         depthwise_impl=dw, gn_impl=gn),
            mesh=mesh, learning_rate=0.01)
        trainer.init(jax.random.PRNGKey(0))
        # steps=8 is a hard ceiling here: a 16-step chunk's jit-output copy
        # picks a (8,128)-tiled layout that lane-pads the trailing channel
        # dim 3 -> 128 (a 42x HBM blowup, >19 GB — compile fails); reps=4
        # to suppress dispatch jitter in the differencing at short chunks
        steps = 1 if CPU_SCALE else 8
        chunk = _device_chunk(trainer, steps, B, (size, size, 3), classes)
        r = _timed_chunked(trainer, None, steps=steps,
                           rounds=2 if CPU_SCALE else 3, batch=B,
                           reps=1 if CPU_SCALE else
                           (3 if time_left() < 90 else 4),
                           device_chunk=chunk,
                           warm_rounds=0 if CPU_SCALE else 1)
        mfu = _mfu_or_none(trainer, (x1, y1), r["step_ms"] / 1e3,
                           mode="mobilenet")
        results[f"{dw}+{gn}"] = (r, mfu)
        trainers[f"{dw}+{gn}"] = trainer
        log(f"#5 mobilenet_v2[{dw}+{gn}]: {r['samples_per_sec']:.0f} "
            f"samples/s ({r['step_ms']:.2f} ms/step, mfu={mfu})")
        if best is None or r["step_ms"] < results[best][0]["step_ms"]:
            best = f"{dw}+{gn}"
    r, mfu = results[best]
    log(f"#5 mobilenet_v2 winner: {best} "
        f"(all: {({k: round(v[0]['step_ms'], 2) for k, v in results.items()})})")
    row = {
        "config": "mobilenet_v2_sync",
        "metric": "samples/sec/chip",
        "value": round(r["samples_per_sec"] / n_chips, 1),
        "step_ms": round(r["step_ms"], 3),
        "mfu": mfu,
        "impl": best,
    }
    if mfu is not None and _mfu_basis():
        row["mfu_basis"] = _mfu_basis()
    extra = None
    from distriflow_tpu.ops import default_interpret

    if default_interpret():
        # the winner's analysis carries no depthwise_gn category: cost the
        # fused spec by trace alone — eval_shape records the tally without
        # compiling anything (on TPU the fused spec refuses to build)
        from distriflow_tpu.ops.flop_count import pallas_cost_of

        fspec = mobilenet_v2(image_size=size, classes=classes,
                             dtype=jnp.bfloat16, depthwise_impl="fused",
                             gn_impl="flax")
        tally = pallas_cost_of(
            jax.value_and_grad(fspec.loss_fn),
            jax.eval_shape(fspec.init, jax.random.PRNGKey(0)),
            *_publish_structs((x1, y1), pub_b))
        extra = {k: v for k, v in tally["by_category"].items()
                 if k == "depthwise_gn"}
    rl_batch = (_publish_structs((x1, y1), pub_b) if pub_b != B
                else (x1, y1))
    row.update(_roofline_fields(trainers[best], rl_batch,
                                r["step_ms"] / 1e3, "mobilenet_v2_sync",
                                extra_categories=extra))
    return row


# -- serving: InferenceServer micro-batching speedup -----------------------


def _serving_client(address, timeout=600.0):
    """Co-located bench client: both heartbeat watchdogs are useless here
    (server tracing/compiling holds the GIL, starving echoes in BOTH
    directions past the 10 s timeouts) and the first mixed-length round
    can pay several cold compiles back to back, so the watchdogs and the
    120 s decode timeout only add flakiness to the measurement."""
    from distriflow_tpu.client import InferenceClient

    c = InferenceClient(address, timeout=timeout)
    c.transport.heartbeat_timeout = 0
    return c.setup()


def bench_serving():
    """8 concurrent greedy clients vs the same 8 requests serialized —
    the micro-batcher folds the concurrent ones into ~1 device program.
    Round-5 (verdict #7): its own leg, run BEFORE the decode context
    sweep, so two rounds of budget-squeezed nulls become a number."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.client import InferenceClient
    from distriflow_tpu.models.generate import generate as _gen
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.server import InferenceServer

    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
        max_seq=1024, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))
    server = InferenceServer(cfg, params, port=0)
    # co-located client: its heartbeats starve under the GIL while the
    # server traces/compiles, so the 10 s reaper would evict it mid-compile
    server.transport.heartbeat_timeout = 0
    server.setup()
    try:
        prompts = [rng.randint(0, 32000, (1, 64)).astype(np.int32)
                   for _ in range(8)]
        with _serving_client(server.address) as c:
            c.generate(prompts[0], n_tokens=32)  # compile/warm bucket-1 shape
        # warm the full bucket-8 shape (the throwaway concurrent round
        # below compiles any other bucket pattern that forms); a cold
        # bucket compile (~20 s over a remote backend) would otherwise
        # swamp the serving measurement
        stackp = np.concatenate(prompts)
        _fetch(_gen(cfg, params, jnp.asarray(stackp), 32))

        start = time.perf_counter()
        with _serving_client(server.address) as c:
            for p in prompts:
                c.generate(p, n_tokens=32)
        t_seq = time.perf_counter() - start

        # connections are NOT part of the serving measurement: set up all 8
        # clients first, then time only the barrier-released generate calls
        clients = [_serving_client(server.address) for _ in range(8)]
        try:
            def one_round():
                results = [None] * 8
                barrier = threading.Barrier(8)

                def call(i):
                    barrier.wait()
                    results[i] = clients[i].generate(prompts[i], n_tokens=32)

                threads = [threading.Thread(target=call, args=(i,))
                           for i in range(8)]
                start = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert all(r is not None for r in results)
                return time.perf_counter() - start

            one_round()  # warm: the first batched dispatch from the server
            # context pays a one-time ~600 ms retrace/session cost
            t_conc = min(one_round() for _ in range(2))
        finally:
            for c in clients:
                c.close()
        speedup = t_seq / t_conc
        log(f"serving: 8 sequential {t_seq*1e3:.0f} ms vs concurrent "
            f"{t_conc*1e3:.0f} ms -> {speedup:.2f}x "
            f"(batches={server.decode_batches}, reqs={server.batched_requests})")
    finally:
        server.stop()
    return {
        "config": "serving_microbatch",
        "metric": "speedup (8 clients, concurrent vs serial)",
        "value": round(speedup, 2),
        "seq_ms": round(t_seq * 1e3, 0),
        "conc_ms": round(t_conc * 1e3, 0),
    }


def bench_serving_continuous():
    """8 concurrent clients with MIXED prompt lengths vs the same requests
    serialized. The round-3 signature batcher could not co-batch different
    lengths at all (~1x); the continuous-batching engine admits them into
    independent slots of one shared decode loop, so the concurrent side
    should approach the same-length leg's scaling."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.client import InferenceClient
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.server import InferenceServer

    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
        max_seq=1024, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))
    server = InferenceServer(cfg, params, port=0)
    server.transport.heartbeat_timeout = 0  # see bench_serving
    server.setup()
    try:
        lengths = [16, 32, 48, 64, 80, 96, 112, 128]
        prompts = [rng.randint(0, 32000, (1, p)).astype(np.int32)
                   for p in lengths]

        start = time.perf_counter()
        with _serving_client(server.address) as c:
            for p in prompts:
                c.generate(p, n_tokens=32)
        t_seq_cold = time.perf_counter() - start  # pays per-length compiles

        clients = [_serving_client(server.address) for _ in range(8)]
        try:
            def one_round():
                results = [None] * 8
                barrier = threading.Barrier(8)

                def call(i):
                    barrier.wait()
                    results[i] = clients[i].generate(prompts[i], n_tokens=32)

                threads = [threading.Thread(target=call, args=(i,))
                           for i in range(8)]
                start = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert all(r is not None for r in results)
                return time.perf_counter() - start

            one_round()  # warm: grouped-admission prefill buckets compile
            t_conc = min(one_round() for _ in range(2))
        finally:
            for c in clients:
                c.close()

        # warm serial pass AFTER the compiles above, for a fair ratio
        start = time.perf_counter()
        with _serving_client(server.address) as c:
            for p in prompts:
                c.generate(p, n_tokens=32)
        t_seq = time.perf_counter() - start
        speedup = t_seq / t_conc
        log(f"serving_continuous: 8 mixed-length serial {t_seq*1e3:.0f} ms "
            f"(cold {t_seq_cold*1e3:.0f} ms) vs concurrent "
            f"{t_conc*1e3:.0f} ms -> {speedup:.2f}x "
            f"(batches={server.decode_batches}, reqs={server.batched_requests})")
    finally:
        server.stop()
    return {
        "config": "serving_continuous",
        "metric": "speedup (8 mixed-length clients, concurrent vs serial)",
        "value": round(speedup, 2),
        "seq_ms": round(t_seq * 1e3, 0),
        "conc_ms": round(t_conc * 1e3, 0),
        "prompt_lens": "16..128",
    }


# -- serving: paged KV pool + prefix sharing under mixed-length traffic ----


def bench_serving_paged_mixed(short_len=1024, long_len=8192, max_seq=16384,
                              n_short=10, n_long=2, n_tokens=64):
    """Mixed short/long-context clients against the SAME KV HBM budget
    twice: the legacy slab layout (concurrency capped at ``max_slots``
    worst-case ``max_seq`` slabs) vs the round-9 paged pool, which admits
    on free PAGES — short requests stop reserving context they never
    touch. Headline: peak concurrent in-flight requests, paged/slab, at
    byte-identical KV budgets (the >= 2x acceptance bar). The second
    wave replays the same prompts, so the prefix map's hit rate, tokens
    saved, and peak page occupancy land in the row too."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.generate import pages_per_slot
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.obs import get_telemetry
    from distriflow_tpu.server import InferenceServer
    from distriflow_tpu.utils.config import ServingConfig

    if FAST or time_left() < 150:
        short_len, long_len, max_seq = short_len // 4, long_len // 4, max_seq // 4

    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=max_seq, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))

    SLAB_SLOTS = 3  # the equal-HBM budget: 3 worst-case max_seq slabs
    PAGE_SIZE = 128
    pool_pages = SLAB_SLOTS * pages_per_slot(max_seq, PAGE_SIZE)
    n_clients = n_short + n_long
    prompts = ([rng.randint(0, 32000, (1, short_len)).astype(np.int32)
                for _ in range(n_short)]
               + [rng.randint(0, 32000, (1, long_len)).astype(np.int32)
                  for _ in range(n_long)])

    def run_layout(serving):
        server = InferenceServer(cfg, params, port=0, serving=serving)
        server.transport.heartbeat_timeout = 0  # see bench_serving
        server.setup()
        peak = {"slots": 0, "occ": 0.0}
        stop_sampler = threading.Event()

        def sample():
            while not stop_sampler.wait(0.004):
                live = sum(1 for r in server._slot_req if r is not None)
                peak["slots"] = max(peak["slots"], live)
                if server._pool is not None:
                    peak["occ"] = max(
                        peak["occ"],
                        server._pool.used_pages / server._pool.n_pages)

        try:
            clients = [_serving_client(server.address)
                       for _ in range(n_clients)]
            try:
                def one_round():
                    results = [None] * n_clients
                    barrier = threading.Barrier(n_clients)

                    def call(i):
                        barrier.wait()
                        results[i] = clients[i].generate(
                            prompts[i], n_tokens=n_tokens)

                    threads = [threading.Thread(target=call, args=(i,))
                               for i in range(n_clients)]
                    start = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    assert all(r is not None for r in results)
                    return time.perf_counter() - start

                one_round()  # cold: prefill/decode compiles serialize it
                sampler = threading.Thread(target=sample, daemon=True)
                sampler.start()
                wall = one_round()  # warm + prefix map primed by round 1
                stop_sampler.set()
                sampler.join(timeout=2.0)
            finally:
                for c in clients:
                    c.close()
        finally:
            server.stop()
        return wall, peak["slots"], peak["occ"]

    tel = get_telemetry()
    wall_slab, peak_slab, _ = run_layout(ServingConfig(
        kv_layout="slab", max_slots=SLAB_SLOTS, batch_window_s=0.05))
    hits0 = tel.counter_value("serving_prefix_hits_total")
    saved0 = tel.counter_value("serving_prefix_tokens_saved_total")
    wall_paged, peak_paged, occ = run_layout(ServingConfig(
        kv_layout="paged", max_slots=n_clients + 4, page_size=PAGE_SIZE,
        page_pool_pages=pool_pages, batch_window_s=0.05))
    hits = tel.counter_value("serving_prefix_hits_total") - hits0
    saved = tel.counter_value("serving_prefix_tokens_saved_total") - saved0

    ratio = peak_paged / max(peak_slab, 1)
    log(f"serving_paged_mixed: peak concurrency slab={peak_slab} "
        f"paged={peak_paged} ({ratio:.1f}x @ {pool_pages} pages), "
        f"wall slab={wall_slab:.1f}s paged={wall_paged:.1f}s, "
        f"prefix hits={hits:.0f} saved={saved:.0f} tok, "
        f"peak occupancy={occ:.2f}")
    return {
        "config": "serving_paged_mixed",
        "metric": "peak concurrent requests, paged vs slab @ equal KV HBM",
        "value": round(ratio, 2),
        "peak_slab": peak_slab,
        "peak_paged": peak_paged,
        "tok_s_user_slab": round(n_tokens / wall_slab, 2),
        "tok_s_user_paged": round(n_tokens / wall_paged, 2),
        "page_occupancy": round(occ, 3),
        "prefix_hit_rate": round(hits / (2.0 * n_clients), 3),
        "prefix_tokens_saved": int(saved),
        "traffic": f"{n_short}x{short_len}+{n_long}x{long_len}"
                   f" (+{n_tokens} tok, max_seq {max_seq})",
    }


# -- serving: speculative decoding (draft/verify) vs plain paged decode ----


def bench_serving_speculative(ctx_short=1024, ctx_long=16384, n_tokens=96,
                              k=4):
    """Round-12 row (docs/PERFORMANCE.md §7g): draft/verify speculative
    decoding (``ServingConfig.speculate_k``) against plain paged decode
    at the SAME page-pool budget, greedy, B=1 — speculation's target
    regime (per-user decode latency; batch too small to fill the chip).

    The zoo's ``lm_draft`` is distilled in-leg on the target's own greedy
    trajectory for the short serving prompt — the offline step a real
    deployment runs once over its traffic. With a random-weight target
    there is no transferable draft (its greedy attractors are
    prompt-specific), so the short-context acceptance sits near the
    ceiling BY CONSTRUCTION and the row measures the serving-plane
    mechanics (draft dispatch, batched verify, dual-pool commit) at a
    pinned, *measured* acceptance; the long context serves the SAME
    draft, so its acceptance shows the honest no-transfer floor — the
    "when speculation loses" regime §7g documents. Decode ms/token is
    differenced (an ``n_tokens`` call minus a 1-token call, prefix map
    primed) so prefill/admission cost cancels, and both servers' outputs
    are asserted bit-identical — the §7g greedy contract, re-proven at
    bench dims every run."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distriflow_tpu.models.generate import generate, pages_per_slot
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        transformer_lm,
    )
    from distriflow_tpu.models.zoo import draft_config_for
    from distriflow_tpu.obs import get_telemetry
    from distriflow_tpu.server import InferenceServer
    from distriflow_tpu.utils.config import ServingConfig

    squeeze = FAST or time_left() < 150
    if squeeze:
        ctx_short, ctx_long = ctx_short // 4, ctx_long // 4
    labels = {ctx_short: "1k", ctx_long: "16k"}  # ledger keys stay nominal

    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=ctx_long, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))
    dcfg = draft_config_for("lm_draft", cfg)
    prompts = {c: rng.randint(0, 32000, (1, c - n_tokens)).astype(np.int32)
               for c in (ctx_short, ctx_long)}

    # -- distill: fit lm_draft to the target's short-context trajectory ---
    t0 = time.perf_counter()
    steps = 30 if squeeze else 50
    corpus = jnp.asarray(np.asarray(generate(
        cfg, dict(params), jnp.asarray(prompts[ctx_short]), n_tokens)))
    # teacher labels from the SERVED (bf16) target: label[i] is the argmax
    # the server emits after consuming corpus[:, :i+1]
    teach = jnp.argmax(TransformerLM(cfg).apply(dict(params), corpus), -1)
    # train under f32 compute (CPU-friendly; converges in tens of steps);
    # the server re-applies the same weights under the bf16 draft config
    drf = TransformerLM(dataclasses.replace(dcfg, dtype=jnp.float32))
    dparams = transformer_lm(
        dataclasses.replace(dcfg, dtype=jnp.float32), example_seq=16,
    ).init(jax.random.PRNGKey(1))
    x, y = corpus[:, :-1], teach[:, :-1]
    plen = prompts[ctx_short].shape[1]
    mask = jnp.zeros(x.shape, jnp.float32).at[:, plen - 1:].set(1.0)
    opt = optax.adam(4e-3)

    def distill_loss(p):
        lg = drf.apply(p, x).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(lg, y)
        return (ce * mask).sum() / mask.sum()

    @jax.jit
    def distill_step(p, st):
        loss, g = jax.value_and_grad(distill_loss)(p)
        up, st = opt.update(g, st)
        return optax.apply_updates(p, up), st, loss

    st = opt.init(dparams)
    for _ in range(steps):
        dparams, st, loss = distill_step(dparams, st)
    distill_secs = time.perf_counter() - t0
    log(f"serving_speculative: distilled lm_draft {steps} steps on the "
        f"{labels[ctx_short]} trajectory ({n_tokens} tok), final CE "
        f"{float(loss):.3f} ({distill_secs:.1f}s)")

    PAGE_SIZE = 128
    pool_pages = 4 * pages_per_slot(cfg.max_seq, PAGE_SIZE)
    tel = get_telemetry()

    def run_layout(spec):
        extra = ({"speculate_k": k, "draft_model": "lm_draft"}
                 if spec else {})
        server = InferenceServer(
            cfg, params, port=0,
            serving=ServingConfig(
                kv_layout="paged", max_slots=4, page_size=PAGE_SIZE,
                page_pool_pages=pool_pages, batch_window_s=0.02, **extra),
            draft_params=dparams if spec else None)
        server.transport.heartbeat_timeout = 0  # see bench_serving
        server.setup()
        out = {}
        try:
            client = _serving_client(server.address)
            try:
                for ctx in (ctx_short, ctx_long):
                    prompt = prompts[ctx]
                    client.generate(prompt, n_tokens=3)  # compile + prime
                    p0 = tel.counter_value("serving_spec_proposed_total")
                    a0 = tel.counter_value("serving_spec_accepted_total")
                    t = time.perf_counter()
                    client.generate(prompt, n_tokens=1)
                    t1 = time.perf_counter() - t
                    t = time.perf_counter()
                    full = client.generate(prompt, n_tokens=n_tokens)
                    tn = time.perf_counter() - t
                    prop = tel.counter_value(
                        "serving_spec_proposed_total") - p0
                    acc = tel.counter_value(
                        "serving_spec_accepted_total") - a0
                    out[ctx] = {
                        "ms_tok": (tn - t1) * 1e3 / (n_tokens - 1),
                        "out": full,
                        "accept": acc / prop if prop else None,
                        "acc_per_round": acc * k / prop if prop else None,
                    }
            finally:
                client.close()
        finally:
            server.stop()
        return out

    spec_out = run_layout(True)
    plain_out = run_layout(False)
    for ctx in (ctx_short, ctx_long):
        # the §7g contract at bench dims: greedy spec == greedy plain, bit
        # for bit, regardless of what the draft proposed
        np.testing.assert_array_equal(spec_out[ctx]["out"],
                                      plain_out[ctx]["out"])

    row = {
        "config": "serving_speculative",
        "metric": (f"decode speedup, spec k={k} distilled draft vs plain "
                   f"@ equal KV pool (greedy B=1, {labels[ctx_short]} ctx)"),
        "value": round(plain_out[ctx_short]["ms_tok"]
                       / spec_out[ctx_short]["ms_tok"], 3),
        "accepted_per_step": round(
            spec_out[ctx_short]["acc_per_round"], 2),
        "distill_secs": round(distill_secs, 1),
        "traffic": (f"B=1 +{n_tokens} tok, k={k}, pool {pool_pages} pages,"
                    f" ctx {ctx_short}/{ctx_long}"),
    }
    for ctx in (ctx_short, ctx_long):
        lab = labels[ctx]
        row[f"spec_ms_tok_{lab}"] = round(spec_out[ctx]["ms_tok"], 3)
        row[f"plain_ms_tok_{lab}"] = round(plain_out[ctx]["ms_tok"], 3)
        if spec_out[ctx]["accept"] is not None:
            row[f"accept_rate_{lab}"] = round(spec_out[ctx]["accept"], 3)
    log(f"serving_speculative: spec/plain ms/tok "
        f"{labels[ctx_short]}={row[f'spec_ms_tok_{labels[ctx_short]}']}"
        f"/{row[f'plain_ms_tok_{labels[ctx_short]}']} "
        f"{labels[ctx_long]}={row[f'spec_ms_tok_{labels[ctx_long]}']}"
        f"/{row[f'plain_ms_tok_{labels[ctx_long]}']}, "
        f"accept {row.get(f'accept_rate_{labels[ctx_short]}')}"
        f"/{row.get(f'accept_rate_{labels[ctx_long]}')}, "
        f"speedup {row['value']}x @ {labels[ctx_short]}")
    return row


# -- serving fleet: prefix-affinity routing vs round-robin over 2 replicas -


def bench_serving_fleet(ctx=1024, n_tokens=64, n_groups=6, warm_waves=2):
    """Round-13 row (docs/PERFORMANCE.md §7h): the fleet router's
    prefix-affinity policy against round-robin over TWO replicas, same
    model, same page-pool budget, same traffic.

    Traffic is ``n_groups`` users, each re-sending its own shared-prefix
    prompt every wave (the agent/chat regime the router targets). Each
    replica's pool is sized so affinity's partition (half the groups per
    replica) fits warm, but round-robin's duplication (every group's
    prefix on BOTH replicas) overflows and churns the prefix maps —
    the capacity-level cost of ignoring placement, on top of the extra
    cold prefills. Headline: aggregate warm-wave tok/s/user, affinity
    over round-robin; the per-replica prefix-hit counters land in the
    row as hit rates so the ledger also pins WHY the wall time moved."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.fleet import FleetRouter
    from distriflow_tpu.models.generate import pages_per_slot
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.obs.telemetry import Telemetry
    from distriflow_tpu.server import InferenceServer
    from distriflow_tpu.utils.config import ServingConfig

    if FAST or time_left() < 150:
        ctx = ctx // 4

    PAGE_SIZE = 128
    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=ctx + n_tokens, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))
    prompts = [rng.randint(0, 32000, (1, ctx)).astype(np.int32)
               for _ in range(n_groups)]

    # pool budget: affinity steady state is n_groups/2 warm prefixes per
    # replica plus two in-flight working sets; round-robin needs ALL
    # n_groups prefixes resident on BOTH replicas and does not fit
    prefix_pages = (ctx - 1) // PAGE_SIZE
    need = pages_per_slot(ctx + n_tokens, PAGE_SIZE)
    pool_pages = (n_groups // 2) * prefix_pages + 2 * need

    def run_leg(policy):
        replicas = [InferenceServer(
            cfg, params, port=0, telemetry=Telemetry(),
            serving=ServingConfig(
                kv_layout="paged", max_slots=n_groups, page_size=PAGE_SIZE,
                page_pool_pages=pool_pages, batch_window_s=0.05))
            for _ in range(2)]
        for server in replicas:
            server.transport.heartbeat_timeout = 0  # see bench_serving
            server.setup()
        router = FleetRouter(port=0, policy=policy, telemetry=Telemetry())
        for i, server in enumerate(replicas):
            router.add_replica(server.address, name=f"replica-{i}")
        router.setup()
        try:
            clients = [_serving_client(router.address)
                       for _ in range(n_groups)]
            try:
                def one_wave():
                    barrier = threading.Barrier(n_groups)

                    def call(i):
                        barrier.wait()
                        clients[i].generate(prompts[i], n_tokens=n_tokens)

                    threads = [threading.Thread(target=call, args=(i,))
                               for i in range(n_groups)]
                    start = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    return time.perf_counter() - start

                one_wave()  # cold: compiles + first prefills serialize it
                wall = sum(one_wave() for _ in range(warm_waves))
            finally:
                for c in clients:
                    c.close()
            hits = sum(s.prefix_hits for s in replicas)
        finally:
            router.stop()
            for server in replicas:
                server.stop()
        # hits counted over every wave; only warm-wave requests CAN hit
        hit_rate = hits / float(warm_waves * n_groups)
        tok_s_user = warm_waves * n_tokens / wall
        return tok_s_user, hit_rate

    rr_tok_s_user, rr_hit_rate = run_leg("round_robin")
    aff_tok_s_user, aff_hit_rate = run_leg("affinity")
    speedup = aff_tok_s_user / rr_tok_s_user
    log(f"serving_fleet: affinity {aff_tok_s_user:.2f} tok/s/user "
        f"(hit rate {aff_hit_rate:.2f}) vs round-robin "
        f"{rr_tok_s_user:.2f} (hit rate {rr_hit_rate:.2f}) "
        f"-> {speedup:.2f}x @ pool {pool_pages} pages/replica")
    return {
        "config": "serving_fleet",
        "metric": "warm tok/s/user, affinity vs round-robin (2 replicas)",
        "value": round(speedup, 2),
        "affinity_tok_s_user": round(aff_tok_s_user, 2),
        "rr_tok_s_user": round(rr_tok_s_user, 2),
        "affinity_hit_rate": round(aff_hit_rate, 3),
        "rr_hit_rate": round(rr_hit_rate, 3),
        "traffic": (f"{n_groups} users x {warm_waves} warm waves, "
                    f"ctx {ctx} +{n_tokens} tok, pool "
                    f"{pool_pages} pages/replica"),
    }


def bench_serving_slo(ctx=512, n_tokens=32, n_users=6, warm_waves=2):
    """Round-15 row (docs/OBSERVABILITY.md §11): mixed-tier serving SLOs
    over TWO replicas behind the fleet router, plus the cost of the
    request-trace plane itself.

    Traffic is ``n_users`` concurrent users pinned to tiers 0/1/2 (two
    each), one request per wave. The traced leg shares ONE Telemetry
    across clients, router, and both replicas, so every request leaves a
    full client-root -> route -> replica-engine span set; per-tier
    TTFT/TPOT p50/p99 come from assembling those spans — the SAME
    numbers ``dump --requests`` prints from the router's run dir. The
    untraced leg replays identical traffic with telemetry disabled;
    ``trace_overhead_ms`` is the per-wave wall delta, absolute-guarded
    in the ledger like the obs_overhead row. Headline ``value`` is fleet
    goodput (answered / accepted) on the traced leg."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.client import InferenceClient
    from distriflow_tpu.fleet import FleetRouter
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.obs.telemetry import Telemetry
    from distriflow_tpu.obs.trace_assembler import assemble
    from distriflow_tpu.server import InferenceServer
    from distriflow_tpu.utils.config import ServingConfig

    if FAST or time_left() < 150:
        ctx = ctx // 4

    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=ctx + n_tokens, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))
    prompts = [rng.randint(0, 32000, (1, ctx)).astype(np.int32)
               for _ in range(n_users)]
    tiers = [i % 3 for i in range(n_users)]

    def run_leg(traced):
        tel = Telemetry(enabled=traced)
        replicas = [InferenceServer(
            cfg, params, port=0, telemetry=tel,
            serving=ServingConfig(max_slots=n_users, decode_chunk=8,
                                  batch_window_s=0.05))
            for _ in range(2)]
        for server in replicas:
            server.transport.heartbeat_timeout = 0  # see _serving_client
            server.setup()
        router = FleetRouter(port=0, policy="least_loaded", telemetry=tel)
        for i, server in enumerate(replicas):
            router.add_replica(server.address, name=f"replica-{i}")
        router.setup()
        try:
            clients = []
            for _ in range(n_users):
                c = InferenceClient(router.address, timeout=600.0,
                                    telemetry=tel)
                c.transport.heartbeat_timeout = 0
                clients.append(c.setup())
            try:
                def one_wave():
                    barrier = threading.Barrier(n_users)

                    def call(i):
                        barrier.wait()
                        clients[i].generate(prompts[i], n_tokens=n_tokens,
                                            tier=tiers[i])

                    threads = [threading.Thread(target=call, args=(i,))
                               for i in range(n_users)]
                    start = time.perf_counter()
                    for t in threads:
                        t.start()
                    for t in threads:
                        t.join()
                    return time.perf_counter() - start

                one_wave()  # cold: compiles + first prefills serialize it
                # SLO quantiles cover WARM waves only — the cold wave's
                # TTFT is XLA compile seconds, not a serving surface
                cold = {r["trace_id"] for r in tel.tracer.finished()}
                wall = sum(one_wave() for _ in range(warm_waves))
            finally:
                for c in clients:
                    c.close()
            wave_ms = wall / warm_waves * 1e3
            if not traced:
                return wave_ms, None, None
            accepted = sum(
                tel.counter_value("router_requests_total", tier=str(t))
                for t in (0, 1, 2))
            answered = sum(
                tel.counter_value("router_goodput_total", tier=str(t))
                for t in (0, 1, 2))
            goodput = answered / accepted if accepted else 0.0
            warm_rows = [r for r in tel.tracer.finished()
                         if r["trace_id"] not in cold]
            agg = assemble(warm_rows).request_attribution()
            return wave_ms, goodput, agg
        finally:
            router.stop()
            for server in replicas:
                server.stop()

    trace_on_ms, goodput, agg = run_leg(True)
    trace_off_ms, _, _ = run_leg(False)
    overhead_ms = trace_on_ms - trace_off_ms
    log(f"serving_slo: goodput {goodput:.3f} over {agg['requests']} "
        f"requests ({agg['committed']} committed, {agg['orphans']} "
        f"orphans), wave {trace_on_ms:.1f}ms traced vs "
        f"{trace_off_ms:.1f}ms untraced ({overhead_ms:+.1f}ms)")
    row = {
        "config": "serving_slo",
        "metric": "fleet goodput (answered/accepted, traced leg)",
        "value": round(goodput, 3),
        "requests": agg["requests"],
        "shed": sum(t["shed"] for t in agg["tiers"].values()),
        "failovers": sum(t["failovers"] for t in agg["tiers"].values()),
        "trace_on_ms": round(trace_on_ms, 2),
        "trace_off_ms": round(trace_off_ms, 2),
        "trace_overhead_ms": round(overhead_ms, 2),
        "traffic": (f"{n_users} users over tiers 0/1/2 x "
                    f"{warm_waves} warm waves, ctx {ctx} +{n_tokens} tok, "
                    f"2 replicas"),
    }
    for t, stats in agg["tiers"].items():
        for k in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                  "tpot_p99_ms"):
            v = stats.get(k)
            if v is not None:
                row[f"{k}_tier{t}"] = v
    return row


def bench_serving_elastic(ctx=512, n_tokens=16, n_requests=8):
    """Round-19 row (docs/ROBUSTNESS.md §11): tier-0 tail hedging over a
    3-replica hash-ring fleet with a scripted straggler, plus the ring's
    structural churn costs.

    The straggler leg stretches the arc owner's admission window to
    1 s (the idle engine's gather window — a deterministic queue-side
    stall, not a jittery sleep, and sized to dominate CPU-host compute
    so the hedge race has one winner) and replays the same owner-routed
    prompt ``n_requests`` times unhedged, then hedged with the 25 ms
    tier-0 watermark. Unhedged, every request eats the stretched window;
    hedged, the duplicate lands on the second arc owner and wins while
    the loser retires unadmitted via hedge_cancel. Headline ``value`` is
    the unhedged/hedged p99 ratio — how much tail the watermark buys. A
    drain/undrain churn wave then checks goodput stays 1.0 while a
    replica leaves and rejoins the ring, and the join/leave remap
    fractions come from ``ring.assignment`` diffs over a fixed key set —
    sha1-deterministic, so the ledger pins them exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.fleet import FleetRouter, HashRing, page_hashes
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )
    from distriflow_tpu.obs.telemetry import Telemetry
    from distriflow_tpu.server import InferenceServer
    from distriflow_tpu.utils.config import ServingConfig

    if FAST or time_left() < 120:
        ctx = ctx // 4

    PAGE_SIZE = 64
    rng = np.random.RandomState(0)
    cfg = TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=ctx + n_tokens, dtype=jnp.bfloat16)
    params = transformer_lm(cfg, example_seq=128).init(jax.random.PRNGKey(0))

    tel = Telemetry()
    servers = {}
    for name in ("A", "B", "C"):
        s = InferenceServer(
            cfg, params, port=0, telemetry=Telemetry(),
            serving=ServingConfig(
                kv_layout="paged", max_slots=2, page_size=PAGE_SIZE,
                page_pool_pages=4 * ((ctx + n_tokens) // PAGE_SIZE + 1),
                batch_window_s=0.02, decode_chunk=8))
        s.transport.heartbeat_timeout = 0  # see _serving_client
        servers[name] = s.setup()
    router = FleetRouter(port=0, policy="ring", stats_interval_s=0.0,
                         redial=False, telemetry=tel)
    for name, s in servers.items():
        router.add_replica(s.address, name=name)
    router.setup()

    def owned(owner):
        for seed in range(4096):
            p = np.random.default_rng(seed).integers(
                1, 32000, size=(1, ctx)).astype(np.int32)
            if router.ring.primary(page_hashes(p[0], PAGE_SIZE)[0]) == owner:
                return p
        raise AssertionError(f"no prompt owned by {owner}")

    try:
        prompts = {n: owned(n) for n in servers}
        # compile prefill AND the measured decode-chunk path on every
        # replica (unrouted) so no measured wall pays XLA
        for name, s in servers.items():
            with _serving_client(s.address) as w:
                w.generate(prompts[name], n_tokens=n_tokens)
        sa = servers["A"]

        STRAGGLE_S = 1.0

        def straggler_leg(hedged):
            walls = []
            with _serving_client(router.address) as c:
                for _ in range(n_requests):
                    t0 = time.perf_counter()
                    c.generate(prompts["A"], n_tokens=n_tokens, tier=0)
                    walls.append((time.perf_counter() - t0) * 1e3)
                    if hedged:
                        # hedged walls end while A is still inside its
                        # stretched gather window holding the cancelled
                        # copy; wait it out so the next request finds A
                        # idle and pays the FULL window again — otherwise
                        # it joins the open batch and A can win the race
                        time.sleep(STRAGGLE_S)
            return (float(np.percentile(walls, 50)),
                    float(np.percentile(walls, 99)))

        sa.serving.batch_window_s = STRAGGLE_S  # read at use time
        try:
            unhedged_p50, unhedged_p99 = straggler_leg(False)
            router.hedge_ms[0] = 25.0
            hedged_p50, hedged_p99 = straggler_leg(True)
        finally:
            router.hedge_ms.clear()
            sa.serving.batch_window_s = 0.02
        hedges = tel.counter_value("router_hedges_total")
        wins = tel.counter_value("router_hedge_wins_total")

        # churn wave: B leaves the ring (drain) and rejoins; its arcs'
        # traffic fails over and comes home, nothing is dropped
        with _serving_client(router.address) as c:
            router.drain_replica("B")
            for p in prompts.values():
                c.generate(p, n_tokens=4, tier=1)
            router.undrain_replica("B")
            for p in prompts.values():
                c.generate(p, n_tokens=4, tier=1)
        accepted = sum(tel.counter_value("router_requests_total",
                                         tier=str(t)) for t in (0, 1, 2))
        answered = sum(tel.counter_value("router_goodput_total",
                                         tier=str(t)) for t in (0, 1, 2))
        goodput = answered / accepted if accepted else 0.0
    finally:
        router.stop()
        for s in servers.values():
            s.stop()

    # structural remap cost, no servers involved: assignment diffs over a
    # fixed key set are pure sha1 — exact today, exact forever
    ring = HashRing(256)
    ring.sync(["A", "B", "C"])
    keys = [f"warmset-{i}".encode() for i in range(2000)]
    base = ring.assignment(keys)
    ring.add("D")
    after_join = ring.assignment(keys)
    join_frac = sum(1 for k in keys
                    if after_join[k] != base[k]) / float(len(keys))
    ring.remove("D")
    assert ring.assignment(keys) == base, "join+leave did not round-trip"
    ring.remove("A")
    after_leave = ring.assignment(keys)
    leave_frac = sum(1 for k in keys
                     if after_leave[k] != base[k]) / float(len(keys))

    # the median is the deterministic quantity here — every request is
    # identically straggled — so it carries the gated headline; the p99s
    # ride along as loosely-guarded diagnostics
    ratio = unhedged_p50 / hedged_p50 if hedged_p50 else 0.0
    log(f"serving_elastic: straggler p50 {unhedged_p50:.0f}ms unhedged vs "
        f"{hedged_p50:.0f}ms hedged -> {ratio:.2f}x (p99 "
        f"{unhedged_p99:.0f} vs {hedged_p99:.0f}ms, {hedges:g} hedges, "
        f"{wins:g} wins), churn goodput {goodput:.3f}, remap join "
        f"{join_frac:.3f} / leave {leave_frac:.3f}")
    return {
        "config": "serving_elastic",
        "metric": "straggler TTFT p50, unhedged/hedged (3-replica ring)",
        "value": round(ratio, 2),
        "unhedged_p50_ms": round(unhedged_p50, 1),
        "hedged_p50_ms": round(hedged_p50, 1),
        "unhedged_p99_ms": round(unhedged_p99, 1),
        "hedged_p99_ms": round(hedged_p99, 1),
        "hedges": int(hedges),
        "hedge_wins": int(wins),
        "churn_goodput": round(goodput, 3),
        "join_remap_frac": round(join_frac, 4),
        "leave_remap_frac": round(leave_frac, 4),
        "traffic": (f"{n_requests} tier-0 requests/leg on the straggler's "
                    f"arc, ctx {ctx} +{n_tokens} tok, 1s scripted "
                    f"window, 25ms watermark, 3 replicas"),
    }


# -- long context: 16k/32k chunked prefill + decode latency ----------------


def bench_long_context(ctxs=(16384, 32768)):
    """Driver-record row for long-context decoding: chunked prefill
    seconds and per-token decode latency at 16k and 32k context (B=1,
    bf16 KV), with the implied HBM-read fraction at the largest context.
    Prefill runs through the same _build_prefill chunk loop the serving
    engine uses, so the number tracks what admission actually pays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.generate import _build_fns, _build_prefill
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )

    GEN = 64
    CHUNK = 1024
    reps = 1 if time_left() < 120 else 2
    rng = np.random.RandomState(0)
    mk_cfg = lambda s: TransformerConfig(
        vocab_size=32000, d_model=256, n_heads=4, n_layers=4, d_ff=1024,
        max_seq=s, dtype=jnp.bfloat16)
    params = transformer_lm(mk_cfg(max(ctxs)), example_seq=128).init(
        jax.random.PRNGKey(0))

    HBM_PEAK_GBPS = 819.0  # v5e; the implied column is device-agnostic
    n_layers, n_heads, d_model = 4, 4, 256

    def kv_gb_per_token(s_ctx):
        return (n_layers * n_heads * s_ctx * (d_model // n_heads)
                * 2 * 2) / 1e9  # K+V, bf16, B=1

    out = {}
    for s_ctx in ctxs:
        cfg = mk_cfg(s_ctx)
        plen = s_ctx - GEN
        prompt = jnp.asarray(rng.randint(0, 32000, (1, plen)), jnp.int32)
        prefill, extend = _build_prefill(cfg)
        chunk = min(CHUNK, plen)

        def chunked_prefill():
            logits, cache = prefill(params, prompt[:, :chunk])
            for i in range(chunk, plen, chunk):
                logits, cache = extend(params, cache, prompt[:, i:i + chunk])
            _fetch(logits)
            return logits, cache

        logits, cache = chunked_prefill()  # compile
        t0 = time.perf_counter()
        logits, cache = chunked_prefill()
        prefill_secs = time.perf_counter() - t0

        _, pick, decode_steps = _build_fns(cfg, GEN, 0.0, None, None, None)
        first = pick(logits, jax.random.PRNGKey(0)).astype(jnp.int32)
        key = jax.random.PRNGKey(1)
        _fetch(jax.tree.leaves(decode_steps(params, cache, first, key))[0])

        def timed():
            t0 = time.perf_counter()
            o = decode_steps(params, cache, first, key)
            _fetch(jax.tree.leaves(o)[0])
            return time.perf_counter() - t0

        per_tok_ms = min(timed() for _ in range(reps)) * 1e3 / (GEN - 1)
        out[s_ctx] = (prefill_secs, per_tok_ms)
        log(f"long_context ctx={s_ctx}: prefill {prefill_secs:.2f} s "
            f"({plen} tok, chunk {chunk}), decode {per_tok_ms:.3f} ms/tok, "
            f"{kv_gb_per_token(s_ctx) / (per_tok_ms / 1e3):.0f} GB/s implied")

    top = max(ctxs)
    row = {
        "config": "long_context",
        "metric": f"tokens/sec (decode, B=1, ctx {top // 1024}k bf16)",
        "value": round(1e3 / out[top][1], 1),
        "hbm_frac": round(
            kv_gb_per_token(top) / (out[top][1] / 1e3) / HBM_PEAK_GBPS, 2),
    }
    for s_ctx in ctxs:
        k = f"{s_ctx // 1024}k"
        row[f"prefill_secs_{k}"] = round(out[s_ctx][0], 2)
        row[f"ms_per_token_{k}"] = round(out[s_ctx][1], 3)
    return row


# -- decode: prefill + per-token latency at 1k/4k, bf16 + int8 -------------


def bench_decode(n_chips):
    """Decode row: per-token ms and decode tokens/s at ~1k and ~4k context
    on flagship dims (greedy, KV-cache scan), bf16 AND int8 caches.
    Round-5: the packed token-major cache + MXU flash-decode kernel
    (ops/flash_decode.py) — and the leg ALWAYS attempts int8 (verdict #8:
    feature coverage must not depend on upstream timing; a tight budget
    shrinks reps, never the schema)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.generate import _build_fns, _gate_kv_dtype
    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm

    B, GEN = 8, 128
    reps = 2 if time_left() < 100 else 3
    rng = np.random.RandomState(0)
    mk_cfg = lambda s: TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=8, d_ff=2048,
        max_seq=s, dtype=jnp.bfloat16)
    # params are max_seq-independent: one init serves both context lengths
    params = transformer_lm(mk_cfg(4096), example_seq=128).init(
        jax.random.PRNGKey(0))

    HBM_PEAK_GBPS = 819.0  # v5e; the implied column is device-agnostic
    n_layers, n_heads, d_model = 8, 8, 512

    def kv_gb_per_token(s_ctx, itemsize):
        gb = (n_layers * B * n_heads * s_ctx * (d_model // n_heads)
              * 2 * itemsize) / 1e9
        if itemsize == 1:  # int8 rows also read an f32 scale per
            # (position, head) for K and for V — +6.25% at head_dim=64
            gb += n_layers * B * n_heads * s_ctx * 2 * 4 / 1e9
        return gb

    out = {}
    for kv_dtype, itemsize in ((None, 2), ("int8", 1)):
        for s_ctx in (1024, 4096):
            cfg = mk_cfg(s_ctx)
            if kv_dtype is not None:
                import dataclasses as _dc

                cfg = _dc.replace(cfg, kv_cache_dtype=kv_dtype)
            prompt = jnp.asarray(
                rng.randint(0, 32000, (B, s_ctx - GEN)), jnp.int32)
            # same re-gate generate() applies: the int8 crossover decides
            # on the context this decode actually READS (prompt + GEN =
            # s_ctx), not the max_seq allocation — the row measures and
            # labels the path a real generate() call would take
            cfg = _gate_kv_dtype(cfg, s_ctx)
            prefill, pick, decode_steps = _build_fns(cfg, GEN, 0.0, None,
                                                     None, None)
            last, cache = prefill(params, prompt)
            first = pick(last, jax.random.PRNGKey(0)).astype(jnp.int32)
            key = jax.random.PRNGKey(1)
            _fetch(jax.tree.leaves(decode_steps(params, cache, first, key))[0])

            def timed(n):
                t0 = time.perf_counter()
                o = None
                for _ in range(n):
                    o = decode_steps(params, cache, first, key)
                _fetch(jax.tree.leaves(o)[0])
                return time.perf_counter() - t0

            t1 = min(timed(1) for _ in range(reps))
            t3 = min(timed(3) for _ in range(reps))
            per_tok_ms = max((t3 - t1) / 2, 1e-9) * 1e3 / (GEN - 1)
            kv_gb = kv_gb_per_token(s_ctx, itemsize)
            name = kv_dtype or "bf16"
            if kv_dtype == "int8" and cfg.kv_cache_dtype_for(s_ctx) is None:
                # below INT8_KV_DECODE_CROSSOVER_SEQ the decode context
                # auto-gates to the bf16 cache (the round-5
                # i8-slower-than-bf16 regression fix) — the row measures
                # and labels the gated reality
                name = "int8(auto->bf16)"
                out[("int8", s_ctx)] = per_tok_ms
            else:
                out[(name, s_ctx)] = per_tok_ms
            log(f"decode ctx={s_ctx} kv={name}: {per_tok_ms:.3f} ms/token, "
                f"{B / per_tok_ms * 1e3:.0f} tok/s (B={B}, "
                f"{kv_gb / (per_tok_ms / 1e3):.0f} GB/s implied, "
                f"{kv_gb / (per_tok_ms / 1e3) / HBM_PEAK_GBPS:.2f} of peak)")

    kv4 = kv_gb_per_token(4096, 2)
    return {
        "config": "decode_flagship",
        "metric": "tokens/sec (decode, B=8, ctx 1k bf16)",
        "value": round(B * 1e3 / out[("bf16", 1024)], 1),
        "ms_tok_1k": round(out[("bf16", 1024)], 3),
        "ms_tok_4k": round(out[("bf16", 4096)], 3),
        "i8_ms_tok_1k": round(out[("int8", 1024)], 3),
        "i8_ms_tok_4k": round(out[("int8", 4096)], 3),
        "i8_gated": "auto-bf16 below decode-context crossover 8192",
        "hbm_frac_4k": round(
            kv4 / (out[("bf16", 4096)] / 1e3) / HBM_PEAK_GBPS, 2),
    }


# -- flagship MoE: Switch top-1 / GShard top-2 on the real chip ------------


def _moe_phase_fwd_flops(cfg, n_tok):
    """Exact analytic fwd FLOPs of ONE MoE layer's phases, mirroring the
    einsums in models/transformer.py::MoEFFN: router Dense(E) over every
    token; dispatch "xtec,xtd->xecd" and combine "xtec,xecd->xtd" over
    the CHOICE-MAJOR t = k*g axis; expert = two [E,C,d]x[d,f] matmuls.
    Unit-tested against einsum contraction math in
    tests/test_bench_record.py."""
    from distriflow_tpu.parallel.ring_attention import _auto_block

    k, E = cfg.moe_top_k, cfg.n_experts
    g = _auto_block(n_tok, cfg.moe_group_size)
    G = n_tok // g
    C = max(1, int(cfg.capacity_factor * k * g / E))
    d, f = cfg.d_model, cfg.d_ff
    return {
        "router": 2.0 * n_tok * d * E,
        "dispatch": 2.0 * G * k * g * E * C * d,
        "expert": 4.0 * G * E * C * d * f,
        "combine": 2.0 * G * k * g * E * C * d,
    }


def bench_moe(n_chips, matrix):
    """MoE rows (round-3): tokens/s + exact MFU for Switch top-1 and GShard
    top-2 at flagship dims, a routing-overhead ratio vs the dense flagship
    row measured in the same run, and a capacity_factor sweep with MEASURED
    drop rates (the ``moe_stats`` collection) — sweep details on stderr."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        TransformerLM,
        transformer_lm,
    )
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    B, S, E = 8, 1024, 8
    MOE_LAYERS = 2  # a quarter of the flagship depth: the routing cost is
    # per-layer (overhead reported per-layer-normalized below); halves the
    # leg's compile wall time, which dominates under the driver budget
    mesh = data_parallel_mesh(jax.devices())
    rng = np.random.RandomState(0)
    dense = next(
        (e for e in matrix if e.get("config") == "transformer_lm_flagship"), {})
    variants = {}
    top2_phases = {}  # router/dispatch/expert/combine split of the top2 row
    shared_params = None  # top-1/top-2 share the SAME param tree (the
    # router is Dense(E) either way) — init once, skip a jitted-init compile
    for k, name in ((1, "top1"), (2, "top2")):
        cfg = TransformerConfig(
            vocab_size=32000, d_model=512, n_heads=8, n_layers=MOE_LAYERS,
            d_ff=2048, max_seq=S, n_experts=E, moe_top_k=k,
            dtype=jnp.bfloat16)
        spec = transformer_lm(cfg, mesh=mesh, example_seq=S)
        trainer = SyncTrainer(spec, mesh=mesh, learning_rate=1e-3,
                              optimizer="adam")
        if shared_params is None:
            trainer.init(jax.random.PRNGKey(0))
            import jax.numpy as _jnp

            # COPY before training: step_many donates the trainer state,
            # which would delete the initial buffers we hand to variant 2
            shared_params = jax.tree.map(_jnp.copy, trainer.get_params())
        else:
            trainer.set_params(shared_params)

        def make_chunk(kk):
            t = rng.randint(0, 32000, (kk, B, S + 1))
            return (np.asarray(t[:, :, :-1], np.int32),
                    np.asarray(t[:, :, 1:], np.int32))

        # rounds=3/reps=3: with rounds=2/reps=2 a single slow t_one outlier
        # once produced an impossible MFU 1.84 row — the differenced signal
        # must dominate the ~±50 ms dispatch jitter (reps drop to 2 only
        # under a squeezed budget; rounds stay at 3)
        r = _timed_chunked(trainer, make_chunk, steps=6, rounds=3, batch=B,
                           reps=2 if time_left() < 120 else 3)
        x1, y1 = (v[0] for v in make_chunk(1))
        mfu = _mfu_or_none(trainer, (x1, y1), r["step_ms"] / 1e3)
        toks = r["samples_per_sec"] * S
        variants[name] = {"tok_s": round(toks / n_chips, 1), "mfu": mfu}
        if k == 2:
            # round-12 satellite: name the top2-vs-dense MFU gap's culprit.
            # Exact analytic model-FLOPs per MoE phase (fwd only — backward
            # is a uniform 2x, so fwd shares equal total shares), divided
            # by the step program's exact-FLOP tally (the same numerator
            # mfu uses) and apportioned over the measured step at uniform
            # achieved FLOP/s. Uniform-throughput attribution is a LOWER
            # bound for dispatch/combine: the one-hot contractions run at
            # far lower arithmetic intensity than the expert matmuls, so
            # their real wall share can only be higher.
            fwd = _moe_phase_fwd_flops(cfg, B * S)
            try:
                # per-device step FLOPs; the analytic tally above is
                # whole-batch, so scale it down by the mesh degree
                total = trainer.cost_analysis((x1, y1))["flops"]
            except Exception as e:
                total = 0.0
                log(f"moe phase split: cost_analysis unavailable ({e!r})")
            if total > 0:
                top2_phases = {
                    f"top2_{p}_ms": round(
                        r["step_ms"] * (v * MOE_LAYERS * 3 / max(n_chips, 1))
                        / total, 3)
                    for p, v in fwd.items()
                }
                top2_phases["top2_other_ms"] = round(
                    r["step_ms"] - sum(top2_phases.values()), 3)
                log(f"moe top2 phase split (exact-FLOP shares of "
                    f"{r['step_ms']:.1f} ms): " + ", ".join(
                        f"{p.removeprefix('top2_').removesuffix('_ms')}="
                        f"{v}" for p, v in top2_phases.items()))
        overhead = None
        if dense.get("step_ms"):
            # per-LAYER ratio vs the dense flagship (depths differ): >1 =
            # routing/dispatch cost. Slightly flattering to MoE (the dense
            # row amortizes its embed/lm_head over more layers).
            overhead = round((r["step_ms"] / MOE_LAYERS)
                             / (dense["step_ms"] / FLAGSHIP_LAYERS), 3)
        log(f"moe {name}: {toks:.0f} tokens/s ({r['step_ms']:.2f} ms/step, "
            f"mfu={mfu}, routing_overhead_per_layer={overhead}, "
            f"final_loss {r['final_loss']:.4f})")

    # capacity_factor sweep with MEASURED drop rates. Drop rate is a
    # property of the router balance and capacity formula — deterministic
    # math, not a hardware number — so the sweep runs on the in-process
    # CPU backend (depth-1 f32 model): zero TPU wall clock.
    base = TransformerConfig(
        vocab_size=32000, d_model=512, n_heads=8, n_layers=1, d_ff=2048,
        max_seq=S, n_experts=E, moe_top_k=2, dtype=jnp.float32,
        use_flash_attention=False)
    cpu = jax.local_devices(backend="cpu")[0]
    sweep = []
    with jax.default_device(cpu):
        spec2 = transformer_lm(base, example_seq=S)
        params2 = spec2.init(jax.random.PRNGKey(0))
        xs = jnp.asarray(rng.randint(0, 32000, (B, S)), jnp.int32)
        for f in (1.0, 1.25, 2.0):
            cfg_f = dataclasses.replace(base, capacity_factor=f)
            mod = TransformerLM(cfg_f)
            stats = jax.jit(
                lambda p, x, m=mod: m.apply(p, x, mutable=["moe_stats"])[1]
            )(params2, xs)
            drop = float(np.mean([np.asarray(v).mean()
                                  for v in jax.tree.leaves(stats)]))
            sweep.append({"capacity_factor": f,
                          "dropped_fraction": round(drop, 4)})
    log(f"moe capacity sweep (top-2, cpu-exact): {sweep} "
        f"(E={E}, d512 x {MOE_LAYERS}L, S={S}, B={B}, bf16)")
    return {
        "config": "transformer_moe_flagship",
        "metric": "tokens/sec/chip",
        "value": variants["top1"]["tok_s"],
        "mfu": variants["top1"]["mfu"],
        "top2_tok_s": variants["top2"]["tok_s"],
        "top2_mfu": variants["top2"]["mfu"],
        **top2_phases,
    }


# -- flagship: transformer LM with measured MFU ----------------------------


def _bench_lm(n_chips, *, name, d_model, n_layers, d_ff, batch, steps, rounds,
              reps, publish_batch=None):
    """Shared transformer-LM leg body (flagship + large share everything
    but the dims). ``publish_batch``: the row's published TPU batch when
    the TIMED batch was CPU-scaled down — the roofline fields project at
    this size (shapes only, nothing executes there)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
    from distriflow_tpu.parallel import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    B, S = batch, 1024
    pub_b = publish_batch or B
    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=8, n_layers=n_layers,
        d_ff=d_ff, max_seq=S, dtype=jnp.bfloat16)
    mesh = data_parallel_mesh(jax.devices())
    # pass the trainer's mesh so loss=None auto-resolution sees it: the
    # fused Pallas CE stays the default on pure data-parallel meshes (its
    # rows-sharded custom_partitioning rule); model/pipe/seq meshes that
    # shard the vocab or sequence fall back to the sharded XLA CE
    spec = transformer_lm(cfg, mesh=mesh, example_seq=S)
    trainer = SyncTrainer(spec, mesh=mesh, learning_rate=1e-3, optimizer="adam")
    trainer.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)

    def make_chunk(k):
        t = rng.randint(0, cfg.vocab_size, (k, B, S + 1))
        return (np.asarray(t[:, :, :-1], np.int32),
                np.asarray(t[:, :, 1:], np.int32))

    r = _timed_chunked(trainer, make_chunk, steps=steps, rounds=rounds,
                       batch=B, reps=reps,
                       warm_rounds=0 if CPU_SCALE else 1)
    x1, y1 = (v[0] for v in make_chunk(1))
    # EXACT mfu: Pallas custom-call model-FLOPs (flash attention fwd+bwd,
    # fused CE) are tallied analytically into the numerator
    # (ops/flop_count.py). Loss is the TPU default: Pallas fused sparse CE
    # consuming bf16 logits directly (no f32 [tokens, V] materialization).
    mfu = _mfu_or_none(trainer, (x1, y1), r["step_ms"] / 1e3)
    toks = r["samples_per_sec"] * S
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(trainer.get_params()))
    log(f"{name} transformer: {toks:.0f} tokens/s "
        f"({r['step_ms']:.2f} ms/step, mfu={mfu}, {n_params/1e6:.0f}M params, "
        f"loss={spec.loss}, d{d_model} x {n_layers}L ff{d_ff}, S={S}, B={B}, "
        f"bf16, final_loss {r['final_loss']:.4f})")
    row = {
        "config": f"transformer_lm_{name}",
        "metric": "tokens/sec/chip",
        "value": round(toks / n_chips, 1),
        "step_ms": round(r["step_ms"], 3),
        "mfu": mfu,
        "params_m": round(n_params / 1e6, 1),
    }
    if mfu is not None and _mfu_basis():
        row["mfu_basis"] = _mfu_basis()
    extra = None
    from distriflow_tpu.ops import default_interpret

    if default_interpret():
        # flash never RUNS on this host (interpret unrolls the grid at
        # trace time — minutes of compile at S=1024) but its analytic cost
        # tally is a trace-time artifact: eval_shape of the flash-enabled
        # step is enough to cost the kernels this row runs on TPU
        import dataclasses

        from distriflow_tpu.ops.flop_count import pallas_cost_of

        fspec = transformer_lm(
            dataclasses.replace(cfg, use_flash_attention=True),
            mesh=mesh, example_seq=S)
        tally = pallas_cost_of(jax.value_and_grad(fspec.loss_fn),
                               trainer.get_params(),
                               *_publish_structs((x1, y1), pub_b))
        extra = {k: v for k, v in tally["by_category"].items()
                 if k.startswith("attention")}
    rl_batch = (_publish_structs((x1, y1), pub_b) if pub_b != B
                else (x1, y1))
    row.update(_roofline_fields(trainer, rl_batch, r["step_ms"] / 1e3,
                                f"transformer_lm_{name}",
                                extra_categories=extra))
    return row


def bench_transformer(n_chips):
    # rounds=3: a longer differenced span rides out a transient slowdown
    # of one dispatch
    if CPU_SCALE:  # smallest differenceable config that keeps S/L/d intact
        # (B=1 steps at S=1024 measure ~12.5 s each on XLA:CPU — four
        # dispatches is the budget, and S must NOT shrink: the projected
        # bound_by rides on the attention/xla flop ratio at the real S)
        return _bench_lm(n_chips, name="flagship", d_model=512,
                         n_layers=FLAGSHIP_LAYERS, d_ff=2048, batch=1,
                         steps=1, rounds=2, reps=1, publish_batch=8)
    return _bench_lm(n_chips, name="flagship", d_model=512,
                     n_layers=FLAGSHIP_LAYERS, d_ff=2048, batch=8,
                     steps=3 if FAST else 6, rounds=2 if FAST else 3,
                     reps=3)


def bench_transformer_large(n_chips):
    """Round-4 (verdict #8): one driver-record row from the MFU-vs-size
    table (docs/PERFORMANCE.md §4c) — d1024/L12/ff4096 at 217M params —
    so the "flagship is small, the framework scales" argument is
    auditable. Sized down when the budget is tight (shrink-not-skip),
    never below one differenced rep."""
    squeeze = time_left() < 90
    return _bench_lm(n_chips, name="large", d_model=1024, n_layers=12,
                     d_ff=4096, batch=8, steps=3 if squeeze else 4,
                     rounds=2, reps=2 if squeeze else 3)


# headline legs with a pinned MFU floor (round-12 satellite; the round-5
# verdict's named fix for the CIFAR 0.2865-vs-0.30 floor noise): a leg
# landing under its floor re-runs ONCE and the surviving row records
# retried=true, so the ledger can tell "one bad window" from "regressed".
# Floors sit under the worst healthy run on record, not at the typical
# value — they trip on pathology, not jitter.
_MFU_FLOORS = {
    "cifar10_convnet_sync": 0.30,   # round-4/5 floor bar (mfu_min gates)
    "transformer_lm_flagship": 0.45,  # r05 slow-window 248k vs 309k tok/s
}


def _floor_retry(matrix, fn, args):
    """Degradation retry (round-12): a headline leg under its pinned
    MFU floor re-runs once; the better row survives and carries
    ``retried: true`` (a bool, so the ledger's numeric filter skips
    it). The floor reads ``mfu_min`` (the measured spread floor)
    where the leg reports one, else ``mfu``; CPU runs report neither
    and never retry. Unit-tested in tests/test_bench_record.py."""
    row = matrix[-1]
    floor = _MFU_FLOORS.get(row.get("config"))
    measured = row.get("mfu_min") or row.get("mfu")
    if row.get("mfu_basis"):  # host-basis MFU: the floors are TPU bars
        return
    if not floor or not measured or measured >= floor:
        return
    if time_left() < 45:
        log(f"{row['config']}: mfu {measured} under floor {floor}, "
            f"but no budget to retry ({time_left():.0f}s left)")
        row["retried"] = False
        return
    log(f"{row['config']}: mfu {measured} under floor {floor} — "
        f"re-running the leg once")
    row["retried"] = True
    try:
        rerun = fn(*args)
    except Exception:
        log(f"--- {row['config']} floor retry FAILED (keeping the "
            f"original row) ---\n{traceback.format_exc()}")
        return
    rerun["retried"] = True
    if (rerun.get("mfu_min") or rerun.get("mfu") or 0) > measured:
        matrix[-1] = rerun


# -- record assembly -------------------------------------------------------

# optional row fields, in drop order, should the line exceed the record
# window (never expected — the flat schema sits well under it — but the
# window must be enforced mechanically, not hoped about)
_DROP_ORDER = [
    "recon_pct", "pipe_eff", "inflight_depth", "asm_overlap_ms",
    "distill_secs", "top2_router_ms", "top2_other_ms", "top2_combine_ms",
    "top2_dispatch_ms", "top2_expert_ms",
    "idle_ms", "overlap_ms", "submit_ms",
    "fit_ms", "drain_ms", "dispatch_ms", "ceiling_sps", "seq_ms", "conc_ms",
    "roofline_err", "mfu_basis",
    "params_m", "round_ms", "workers", "step_ms", "mfu_med", "top2_mfu",
    "top2_tok_s", "i8_ms_tok_1k", "hbm_frac_4k", "wall_ms",
    "unattributed_ms", "topk_int8_bytes", "topk_int8_reduction_x",
    "topk_fraction", "down_bytes_per_broadcast", "dense_bytes",
    "up_bytes_per_update", "reduction_x",
    # mfu_roofline and bound_by drop dead last: they are the columns the
    # ROADMAP-4 overlap work and the round-18 kernel bars pin their
    # before/after on
    "mfu_roofline",
    "bound_by",
]


def _fit_line(result: dict, limit: int = RECORD_LIMIT) -> str:
    """Serialize ``result`` to the one stdout line, guaranteed under
    ``limit`` chars: drop optional fields progressively (logging each to
    stderr so nothing vanishes silently), then truncate error rows, then
    drop whole matrix rows from the end, then — never expected — emit a
    hard-truncated core record. A pathological result must cost fields,
    not the whole record (crashing here would lose every number of the
    run). Unit-tested in tests/test_bench_record.py."""
    line = json.dumps(result)
    for field in _DROP_ORDER:
        if len(line) <= limit:
            break
        for row in result.get("matrix", []):
            if field in row:
                log(f"record trim: dropped {row.get('config')}.{field}="
                    f"{row.pop(field)}")
        line = json.dumps(result)
    if len(line) > limit:  # error rows are the only unbounded text left
        for row in result.get("matrix", []):
            if "error" in row and len(row["error"]) > 80:
                row["error"] = row["error"][-80:]
        line = json.dumps(result)
    # hard-truncation ladder: losing tail rows beats losing the record
    matrix = result.get("matrix")
    while len(line) > limit and matrix:
        dropped = matrix.pop()
        result["truncated"] = True
        log(f"record trim: dropped whole row {dropped.get('config')!r} "
            f"(line still over the {limit}-char window)")
        line = json.dumps(result)
    if len(line) > limit:
        # headline fields alone exceed the window (absurd but possible, e.g.
        # an enormous injected value): keep the identity + headline metric
        core = {k: result[k] for k in
                ("metric", "value", "unit", "device", "n_chips")
                if k in result}
        core["truncated"] = True
        log(f"record trim: hard-truncated to core fields ({len(line)} chars "
            f"> {limit})")
        line = json.dumps(core)[:limit]
    return line


def main() -> None:
    import jax

    from distriflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    n_chips = len(jax.devices())
    log(f"devices: {jax.devices()}")
    matrix = []

    def run(fn, *args):
        if LEGS and fn.__name__.removeprefix("bench_") not in LEGS:
            return  # kernel-round recording runs name their legs
        t0 = time.monotonic()
        # shrink-not-skip: every leg runs (sized down via time_left());
        # one retry absorbs transients, and a double failure embeds a
        # SHORT traceback tail in the row — stderr does not survive the
        # driver, but neither does a row-bloated record (round-4: the
        # 1500-char tails helped blow the 2k window).
        # emergency stop: only a pathological overrun (>3 min past budget)
        # skips a leg — and the row says so explicitly.
        if time_left() < -180:
            matrix.append({
                "config": fn.__name__,
                "error": f"not run: budget exhausted ({-time_left():.0f}s over)",
            })
            log(f"--- {fn.__name__} NOT RUN (budget {-time_left():.0f}s over) ---")
            return
        for attempt in (1, 2):
            try:
                matrix.append(fn(*args))
                _floor_retry(matrix, fn, args)
                break
            except Exception:
                tb = traceback.format_exc()
                log(f"--- {fn.__name__} FAILED (attempt {attempt}) ---\n{tb}")
                # retry only when there's budget to pay for it
                if attempt == 2 or time_left() < 30:
                    tail = "".join(tb.splitlines(keepends=True)[-3:])
                    matrix.append({
                        "config": fn.__name__,
                        "error": tail[-200:],
                    })
                    break
        log(f"[{fn.__name__}: {time.monotonic() - t0:.0f}s, "
            f"total {time.monotonic() - _T0:.0f}s, left {time_left():.0f}s]")

    # importance order under the budget: the real-model rows lead (the
    # round-2 verdict: the MNIST dispatch-arithmetic number is the easiest
    # possible config and should not headline), then serving + decode —
    # the rows two past rounds lost to budget accidents (verdict #7) —
    # then the remaining BASELINE matrix, with the MobileNet impl grid
    # (the most discretionary ~100 s) LAST so a drifting budget squeezes
    # it, never the headline rows.
    run(bench_cifar_sync, n_chips)
    if not FAST:
        run(bench_transformer, n_chips)
        run(bench_transformer_large, n_chips)
        run(bench_moe, n_chips, matrix)  # reads the flagship row above
        run(bench_serving)
        run(bench_serving_continuous)
        run(bench_serving_paged_mixed)
        run(bench_serving_speculative)
        run(bench_serving_fleet)
        run(bench_serving_slo)
        run(bench_serving_elastic)
        run(bench_decode, n_chips)
        run(bench_long_context)
    run(bench_mnist_sync, n_chips)
    run(bench_cifar_async, matrix)  # reads the cifar sync row for pct
    run(bench_fedavg)
    run(bench_obs_overhead)
    run(bench_obs_timeline)
    run(bench_fleet_soak)
    if not FAST:
        run(bench_mobilenet, n_chips)

    baselines = {}
    for name, fn in (("mnist_mlp_sync", bench_torch_mlp),
                     ("cifar10_convnet_sync", bench_torch_cifar)):
        if not any(e.get("config") == name for e in matrix):
            continue  # leg filtered out (BENCH_LEGS) or failed rowless
        try:
            baselines[name] = fn()
        except Exception as e:  # torch missing/broken must not kill the bench
            log(f"torch baseline {name} failed: {e!r}")
            baselines[name] = None
    for entry in matrix:
        base = baselines.get(entry.get("config"))
        if base and "value" in entry:
            entry["vs_baseline"] = round(entry["value"] * n_chips / base, 3)

    # bench regression ledger (docs/PERFORMANCE.md §9): every successful
    # row is verdict-checked against history (ok/warn/regress to stderr)
    # and then appended to BENCH_LEDGER.jsonl with its tolerance band
    # pinned — the BENCH_r*.json eyeballing, mechanized
    try:
        from distriflow_tpu.obs.ledger import BenchLedger

        ledger = BenchLedger()
        # BENCH_RUN_ID pins the id for the kernel-round's baseline-then-
        # best sequencing (the two recordings must be tellable apart)
        run_id = os.environ.get("BENCH_RUN_ID") or f"bench-{int(_T0)}"
        for entry in matrix:
            cfg = entry.get("config")
            if not cfg or "error" in entry:
                continue
            numbers = {k: v for k, v in entry.items()
                       if isinstance(v, (int, float))
                       and not isinstance(v, bool)}
            if not numbers:
                continue
            verdict = ledger.compare(cfg, numbers)
            log(ledger.summary(verdict))
            ledger.record(cfg, numbers, run_id=run_id)
    except Exception as e:  # the ledger must never cost the record line
        log(f"ledger update failed: {e!r}")

    # headline: the CIFAR sync row — a real model with a real measured
    # torch baseline (the round-2 verdict: don't headline the MNIST
    # dispatch-arithmetic number). The transformer MFU story is row #2.
    primary = next(
        (e for e in matrix
         if "value" in e and e.get("config") == "cifar10_convnet_sync"), {})
    result = {
        "metric": "CIFAR-10 ConvNet sync-SGD throughput (bf16, batch 2048)",
        "value": primary.get("value"),
        "unit": "samples/sec/chip",
        "vs_baseline": primary.get("vs_baseline"),
        "device": jax.devices()[0].device_kind,
        "n_chips": n_chips,
        "matrix": matrix,
    }
    print(_fit_line(result))
    failed = [e.get("config") for e in matrix if "error" in e]
    if failed:
        # the record line above still carries every row; the exit code says
        # the run is not a clean one
        log(f"legs failed: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
