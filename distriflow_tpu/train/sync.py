"""Synchronous data-parallel trainer.

The TPU-native collapse of the reference's entire sync round
(``src/server/federated_server.ts:92-117``): where the reference buffers N
clients' serialized gradients, byte-stacks them, means on the server, applies
SGD, checkpoints, and re-broadcasts weights over websockets, here the whole
round is ONE jit-compiled SPMD step:

- the global batch is sharded over the mesh's ``data`` axis (each device is
  a "client" holding its shard — the DistriWorker role),
- ``value_and_grad`` runs the fused fwd+bwd per shard on the MXU,
- the gradient mean is an XLA AllReduce over ICI, inserted by sharding
  propagation (params replicated x batch sharded -> psum of grads),
- the optimizer update happens in the same program; weights never leave the
  devices and there is no serialize/broadcast step to pay for.

Version/checkpoint/callback semantics are preserved at the host level:
``version`` increments per aggregation step, ``on_new_version`` callbacks
fire (reference ``abstract_server.ts:67-79``), and the checkpoint store
writes versioned directories with a ``current`` pointer.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distriflow_tpu.models.base import ModelSpec, _optimizer, init_params
from distriflow_tpu.parallel.mesh import batch_sharding, data_parallel_mesh
from distriflow_tpu.parallel.sharding import (
    REPLICATED_RULES,
    Rules,
    opt_state_shardings,
    tree_shardings,
)
from distriflow_tpu.obs.telemetry import get_telemetry
from distriflow_tpu.utils.logging import CallbackRegistry, VerboseLogger
from distriflow_tpu.utils.profiling import device_timer

Params = Any
Batch = Tuple[jnp.ndarray, jnp.ndarray]


@dataclasses.dataclass
class TrainState:
    """Device-resident training state pytree."""

    params: Params
    opt_state: Any
    step: jnp.ndarray  # int32 scalar — the 'version' of the reference, on device
    # exponential moving average of params (None unless ema_decay is set);
    # the eval/serving weights of choice for noisy small-batch training
    ema: Any = None

    def tree_flatten(self):
        return (self.params, self.opt_state, self.step, self.ema), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)


class _SaveItem:
    """One queued checkpoint write: carries its own completion + error."""

    __slots__ = ("version", "host_state", "done", "error")

    def __init__(self, version: str, host_state: Any):
        self.version = version
        self.host_state = host_state
        self.done = threading.Event()
        self.error: Optional[Exception] = None


def _publish_mfu(
    analysis: Dict[str, float],
    step_seconds: float,
    peak_flops_per_chip: Optional[float],
    gauge_mode: str,
) -> float:
    """``analysis["flops"]`` / (step time x per-chip peak), mirrored into the
    ``train_mfu{mode=gauge_mode}`` gauge; the tail both trainers' ``mfu()``
    share. The peak is looked up from the device kind
    (``SyncTrainer.PEAK_BF16_FLOPS``) when not given."""
    if peak_flops_per_chip is None:
        kind = jax.devices()[0].device_kind
        for key, peak in SyncTrainer.PEAK_BF16_FLOPS.items():
            if key in kind.lower():
                peak_flops_per_chip = peak
                break
        else:
            raise ValueError(
                f"unknown device kind {kind!r}; pass peak_flops_per_chip="
            )
    if not analysis.get("flops"):
        # a 0.0 here would read as "fully dispatch-bound", not "backend
        # reports no flop counts" — fail loudly like the unknown-kind path
        raise ValueError(
            "compiled-step cost analysis reports no 'flops' on this "
            f"backend (keys: {sorted(analysis)}); MFU unavailable"
        )
    value = float(analysis["flops"]) / (step_seconds * peak_flops_per_chip)
    # live MFU surface: the health sentinel's mfu_floor band reads this
    # gauge (docs/OBSERVABILITY.md §6); set only on success so a backend
    # without flop counts leaves the gauge unregistered rather than pinned
    # at a stale value. ``gauge_mode`` keys the per-workload series (sync /
    # async...) so concurrent trainers don't clobber one label
    get_telemetry().gauge(
        "train_mfu", mode=gauge_mode,
        help="model FLOPs utilization vs peak chip FLOPs",
    ).set(value)
    return value


class SyncTrainer:
    """One-jit-step synchronous trainer over a device mesh.

    ``grad_accum`` micro-batching folds the reference's
    ``min_updates_per_version`` semantics into the step: K gradient
    contributions are averaged before one weight update — on the mesh the K
    contributions are the data-axis shards (plus optional sequential
    micro-steps via ``lax.scan`` when the global batch exceeds device memory).
    """

    def __init__(
        self,
        spec: ModelSpec,
        mesh: Optional[Mesh] = None,
        learning_rate: Optional[float] = None,  # None -> 0.001 (reference default)
        optimizer: str = "sgd",
        param_rules: Rules = REPLICATED_RULES,
        grad_accum: int = 1,
        donate: bool = True,
        verbose: Optional[bool] = None,
        checkpoint_dir: Optional[str] = None,
        save_every: int = 0,
        max_checkpoints: Optional[int] = None,
        sharded_checkpoints: bool = False,
        zero_optimizer_sharding: bool = False,
        ema_decay: Optional[float] = None,
        zero_level: Optional[int] = None,
    ):
        self.spec = spec
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.optimizer = _optimizer(optimizer, learning_rate)
        self.param_rules = param_rules
        self.grad_accum = grad_accum
        # EMA of params, updated inside the jit step: e <- d*e + (1-d)*p.
        # Initialized AT the initial params (no bias-correction debiasing);
        # read via ema_params / evaluate(use_ema=True), checkpointed with
        # the state when enabled.
        if ema_decay is not None and not (0.0 < ema_decay < 1.0):
            raise ValueError(f"ema_decay must be in (0, 1), got {ema_decay}")
        self.ema_decay = ema_decay
        self.logger = VerboseLogger(f"SyncTrainer[{spec.name}]", verbose)
        self.callbacks = CallbackRegistry("new_version", "step")
        self.state: Optional[TrainState] = None
        self._donate = donate
        # ZeRO levels over the data axis (memory / dp):
        #   1 — moment buffers shard (ZeRO-1); XLA inserts the
        #       reduce-scatter/all-gather pair around the update;
        #   2 — gradients TOO: a with_sharding_constraint right after
        #       value_and_grad turns the gradient psum into a reduce-scatter
        #       (each device only ever materializes its grad shard), the
        #       sharded optimizer update consumes it directly, and the
        #       updated params all-gather back to replicated. EMA buffers
        #       shard like the moments.
        # zero_optimizer_sharding=True is the round-2 spelling of level 1.
        if zero_level is None:
            zero_level = 1 if zero_optimizer_sharding else 0
        if zero_level not in (0, 1, 2):
            raise ValueError(f"zero_level must be 0, 1 or 2, got {zero_level}")
        self.zero_level = zero_level
        self._zero_opt = zero_level >= 1
        self._zero_grad_shardings = None  # built in init() (needs params)
        self._param_shardings = None
        self._step_fn = self._build_step(donate)
        # observability (reference time()/log wrappers, abstract_server.ts:92-103)
        self.last_step_ms: Optional[float] = None
        self._step_times: List[float] = []  # rolling window
        # steps this trainer has dispatched, counted on the host: the
        # ``train_step`` trace marker's step_num (state.step is a device
        # value, and reading it would sync the pipeline)
        self._steps_dispatched = 0
        self._h_step = get_telemetry().histogram(
            "train_step_ms", mode="sync",
            help="wall time per training step/round (ms), by mode")
        self._cost_cache: Dict[Any, Dict[str, float]] = {}  # per batch signature
        # checkpointing (reference saves on every update, server/models.ts:132-138;
        # here save_every is explicit and the write happens off-thread)
        from distriflow_tpu.checkpoint import make_store

        self.save_every = save_every
        # sharded: each process writes only its owned shards (multi-host)
        self.store = make_store(checkpoint_dir, max_checkpoints,
                                sharded=sharded_checkpoints)
        self._save_queue: Optional[queue.Queue] = None
        self._save_thread: Optional[threading.Thread] = None
        self._save_errors: List[Exception] = []

    # -- state ------------------------------------------------------------

    def init(self, rng: Optional[jax.Array] = None) -> TrainState:
        """Initialize params on host, place onto the mesh per the rule table.

        Optimizer state is built by a jitted ``optimizer.init`` over the
        *already-sharded* params, so XLA propagates the param shardings into
        the moment buffers (mu/nu mirror the params; counters replicate) —
        per-device optimizer memory scales down with TP instead of
        replicating.
        """
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        with self.logger.time("model setup"):
            params = init_params(self.spec, rng)
            param_sh = tree_shardings(params, self.mesh, self.param_rules)
            self._param_shardings = param_sh
            params = jax.tree.map(jax.device_put, params, param_sh)
            opt_shape = jax.eval_shape(self.optimizer.init, params)
            opt_sh = opt_state_shardings(
                opt_shape, params, param_sh, self.mesh,
                zero_axis="data" if self._zero_opt else None,
            )
            opt_state = jax.jit(self.optimizer.init, out_shardings=opt_sh)(params)
            step = jax.device_put(jnp.int32(0), NamedSharding(self.mesh, P()))
            ema = jax.tree.map(jnp.copy, params) if self.ema_decay else None
            if self.zero_level >= 2:
                from distriflow_tpu.parallel.sharding import _zero_extend

                # grads (and the EMA, which mirrors params) shard over data:
                # the constraint in the step body makes XLA produce grad
                # SHARDS via reduce-scatter instead of full grads via psum
                self._zero_grad_shardings = jax.tree.map(
                    lambda sh, p: _zero_extend(
                        sh, np.shape(p), self.mesh, "data"),
                    param_sh, params,
                )
                if ema is not None:
                    ema = jax.tree.map(
                        jax.device_put, ema, self._zero_grad_shardings)
            self.state = TrainState(params=params, opt_state=opt_state,
                                    step=step, ema=ema)
        return self.state

    @property
    def version(self) -> int:
        """Host-visible model version (the reference's version token is a
        timestamp string; here it is the device step counter)."""
        if self.state is None:
            return 0
        return int(self.state.step)

    # -- the step ---------------------------------------------------------

    def _build_step(self, donate: bool) -> Callable[[TrainState, Batch], Tuple[TrainState, jnp.ndarray]]:
        spec = self.spec
        optimizer = self.optimizer
        accum = self.grad_accum
        ema_decay = self.ema_decay

        def loss_fn(params: Params, x, y, w) -> jnp.ndarray:
            # scopes are HLO metadata only (op_name): a device trace reads
            # "forward" for these ops and "transpose(...forward...)" for
            # their backward; the compiled code does not change
            with jax.named_scope("forward"):
                return spec.loss_fn(params, x, y, w)

        def constrain_grads(grads):
            # ZeRO-2: pin the gradient sharding so XLA materializes only
            # each device's shard (reduce-scatter, not psum-to-replicated).
            # Read at TRACE time (first step, after init built the
            # shardings) — not at build time.
            if self.zero_level >= 2 and self._zero_grad_shardings is not None:
                return jax.lax.with_sharding_constraint(
                    grads, self._zero_grad_shardings)
            return grads

        def train_step(state: TrainState, batch):
            # named ``train_step`` since the scopes below came in: jax's
            # persistent compile cache keys a program on its name and code
            # but not on op metadata, so under the old name a cache filled
            # before the scopes existed would hand back an executable
            # without them, and a device trace would read no scope
            x, y, w = batch if len(batch) == 3 else (*batch, None)
            if accum > 1 and x.shape[0] % accum:
                raise ValueError(
                    f"global batch size {x.shape[0]} not divisible by grad_accum={accum}"
                )
            if accum > 1:
                # sequential micro-batching: scan over accum slices; weight each
                # micro-grad by its weight-sum so the result equals one big
                # weighted-mean step (exact min_updates_per_version semantics)
                def split(v):
                    return v.reshape((accum, v.shape[0] // accum) + v.shape[1:])

                xs, ys = split(x), split(y)
                ws = split(w) if w is not None else jnp.ones((accum, x.shape[0] // accum))

                def micro(carry, xyw):
                    gacc, lacc, wacc = carry
                    mx, my, mw = xyw
                    # re-pin each micro-slice to the batch sharding: the
                    # [B] -> [accum, B/accum] reshape above splits the
                    # data-axis tiling across the two new dims; the
                    # constraint keeps every micro-batch's rows sharded over
                    # ``data`` and the micro-step fully data-parallel
                    sh = batch_sharding(self.mesh)
                    mx, my, mw = (
                        jax.lax.with_sharding_constraint(v, sh)
                        for v in (mx, my, mw))
                    l, g = jax.value_and_grad(loss_fn)(state.params, mx, my, mw)
                    g = constrain_grads(g)
                    wsum = jnp.sum(mw)
                    gacc = jax.tree.map(lambda a, b: a + wsum * b, gacc, g)
                    return (gacc, lacc + wsum * l, wacc + wsum), None

                zeros = constrain_grads(
                    jax.tree.map(jnp.zeros_like, state.params))
                (gsum, lsum, wtot), _ = jax.lax.scan(micro, (zeros, 0.0, 0.0), (xs, ys, ws))
                grads = jax.tree.map(lambda g: g / wtot, gsum)
                loss = lsum / wtot
            else:
                loss, grads = jax.value_and_grad(loss_fn)(state.params, x, y, w)
                grads = constrain_grads(grads)
            with jax.named_scope("optimizer"):
                updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
                new_params = optax.apply_updates(state.params, updates)
                if self.zero_level >= 2 and self._param_shardings is not None:
                    # ZeRO-2 contract: the sharded update all-gathers back to
                    # the param layout (otherwise XLA propagates the grad
                    # sharding into the params and every consumer sees sharded
                    # weights — a layout change, not a memory win)
                    new_params = jax.lax.with_sharding_constraint(
                        new_params, self._param_shardings)
                new_ema = state.ema
                if ema_decay is not None:
                    new_ema = jax.tree.map(
                        lambda e, p: ema_decay * e + (1.0 - ema_decay) * p.astype(e.dtype),
                        state.ema, new_params,
                    )
            return TrainState(new_params, new_opt, state.step + 1, new_ema), loss

        self._one_step = train_step  # raw (unjitted) body, reused by step_many
        # Every trace and dispatch of the step programs happens under
        # ``jax.set_mesh(self.mesh)``: kernels that must run per shard (the
        # fused CE, ops/fused_ce.py) find the mesh in the trace context
        # instead of having it threaded through the loss registry.
        return jax.jit(train_step, donate_argnums=(0,) if donate else ())

    def step(self, batch: Batch) -> float:
        """Run one global step; returns the (replicated) loss.

        The batch should already be device-resident and sharded over the
        ``data`` axis (``shard_batch``); a host batch is placed automatically.
        """
        if self.state is None:
            self.init()
        batch = self._ensure_placed(batch)
        with device_timer() as timing, jax.set_mesh(self.mesh), \
                jax.profiler.StepTraceAnnotation(
                    "train_step", step_num=self._steps_dispatched):
            self.state, loss = self._step_fn(self.state, batch)
            loss = float(loss)  # blocks: the step really finished
        self._steps_dispatched += 1
        self.last_step_ms = timing["ms"]
        self._h_step.observe(self.last_step_ms)
        self._step_times.append(self.last_step_ms)
        if len(self._step_times) > 100:
            del self._step_times[:-100]
        if self.save_every and self.store is not None and self.version % self.save_every == 0:
            self.save(drop_if_busy=True)
        self.callbacks.fire("step", self)
        self.callbacks.fire("new_version", str(int(self.state.step)))
        return loss

    @property
    def mean_step_ms(self) -> Optional[float]:
        """Rolling mean step wall time (last 100 steps)."""
        if not self._step_times:
            return None
        return sum(self._step_times) / len(self._step_times)

    def profile(self, log_dir: str):
        """Context manager capturing a ``jax.profiler`` trace of the enclosed
        steps (the TPU-native upgrade of the reference's wall-clock ``time``
        logging, ``abstract_server.ts:98-103``). View with TensorBoard."""
        from distriflow_tpu.utils.profiling import trace

        return trace(log_dir)

    # rough per-chip peak dense bf16 FLOP/s by device kind, for mfu();
    # public figures, matched by substring of jax's device_kind string
    PEAK_BF16_FLOPS = {
        "v6 lite": 918e12,  # Trillium / v6e
        "v6e": 918e12,
        "v5p": 459e12,
        "v5 lite": 197e12,  # v5e
        "v5e": 197e12,
        "v4": 275e12,
        "v3": 123e12,
    }

    def _batch_structs(self, batch: Batch) -> Any:
        """``batch`` as data-sharded ShapeDtypeStructs (shapes/dtypes only)."""
        sharding = batch_sharding(self.mesh)
        return jax.tree.map(
            lambda v: jax.ShapeDtypeStruct(
                jnp.shape(v), jnp.asarray(v).dtype if not hasattr(v, "dtype") else v.dtype,
                sharding=sharding),
            batch,
        )

    def lower_step(self, batch: Batch) -> jax.stages.Lowered:
        """The jitted step program lowered for ``batch``'s shapes: nothing
        runs and no data moves. :meth:`cost_analysis` compiles it;
        ``chip_smoke.py`` reads the compiled text for the Mosaic custom
        calls and the gradient all-reduce."""
        if self.state is None:
            self.init()
        with jax.set_mesh(self.mesh):
            return self._step_fn.lower(self.state, self._batch_structs(batch))

    def cost_analysis(self, batch: Batch) -> Dict[str, float]:
        """Cost analysis of the **per-device** step program (flops, bytes
        accessed, ...). Multiply by the mesh size for whole-mesh totals.

        XLA's compiled-program analysis reports zero FLOPs for custom calls,
        so the Pallas kernels' analytic model-FLOPs are tallied separately
        (an abstract re-trace under ``tally_pallas_cost`` — each kernel
        wrapper records its cost at trace time, ``ops/flop_count.py``) and
        folded into ``'flops'``; the kernel share is also reported as
        ``'pallas_flops'``. Analysis only — the batch contributes
        shapes/dtypes (no data ever moves to the device) and results are
        cached per batch signature.

        The tally follows the same per-device convention as XLA's
        analysis, with two corrections applied here (round-3 ADVICE —
        both were documented caveats before): (a) the fused CE records
        GLOBAL row counts (its split over the data axis happens inside its
        own shard_map, after the record) while the
        shard_map'd kernels trace per-shard — the CE's category share is
        divided by the mesh's ``data``-axis degree; (b) a ``lax.scan``
        body is traced once but executes ``grad_accum`` times — with
        micro-batching every model Pallas call sits inside the scan body
        (and traces at micro-batch shapes), so the whole tally is
        multiplied by ``grad_accum``. Both corrections are
        equality-tripwire-tested (tests/test_sync_train.py)."""
        structs = self._batch_structs(batch)
        key = tuple((s.shape, str(s.dtype)) for s in jax.tree.leaves(structs))
        if key not in self._cost_cache:
            analysis = dict(
                self.lower_step(batch).compile().cost_analysis())
            from distriflow_tpu.ops.flop_count import tally_pallas_cost

            state_structs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.state
            )
            with tally_pallas_cost() as tally, jax.set_mesh(self.mesh):
                # eval_shape re-traces the raw step body, but inner
                # custom_vjp/jit sub-traces are memoized — a warm cache
                # (a prior step() or the compile above) replays the cached
                # jaxpr and skips the Python kernel wrappers entirely
                jax.eval_shape(self._one_step, state_structs, structs)
            if tally["flops"] == 0.0:
                # either a genuinely Pallas-free program or a poisoned
                # trace cache — clearing and retracing once disambiguates
                # (cost: the next step() recompiles; analysis is cached
                # per batch signature so this happens at most once each)
                jax.clear_caches()
                with tally_pallas_cost() as tally, jax.set_mesh(self.mesh):
                    jax.eval_shape(self._one_step, state_structs, structs)
            # correction (a): the fused CE's rows are split over the data
            # axis at compile time but recorded at global N — rescale its
            # category share to the per-device convention
            data_degree = dict(
                zip(self.mesh.axis_names, self.mesh.devices.shape)
            ).get("data", 1)
            ce = tally["by_category"].get("fused_ce")
            if ce is not None and data_degree > 1:
                for field in ("flops", "bytes_accessed", "transcendentals",
                              "hw_flops"):
                    tally[field] -= ce[field] * (1.0 - 1.0 / data_degree)
                    # keep the category breakdown consistent with the
                    # corrected top-level tally (round-4 advisor: a
                    # by_category consumer saw pre-correction numbers)
                    ce[field] /= data_degree
            # correction (b): with grad_accum > 1 every model Pallas call
            # sits inside the micro-step scan body — traced once (at
            # micro-batch shapes), executed grad_accum times
            if self.grad_accum > 1:
                for field in ("flops", "bytes_accessed", "transcendentals",
                              "hw_flops"):
                    tally[field] *= self.grad_accum
                    for cat in tally["by_category"].values():
                        cat[field] *= self.grad_accum
            analysis["xla_flops"] = float(analysis.get("flops", 0.0))
            analysis["pallas_flops"] = tally["flops"]
            # hardware-FLOPs + per-kernel-family breakdown: hw_flops counts
            # recompute that the MFU numerator deliberately excludes
            analysis["pallas_hw_flops"] = tally["hw_flops"]
            analysis["pallas_by_category"] = {
                k: dict(v) for k, v in tally["by_category"].items()
            }
            from distriflow_tpu.ops import default_interpret

            if not default_interpret():
                # compiled custom calls: XLA counted 0 for them — fold the
                # analytic tally in (flops AND bytes, so derived arithmetic
                # intensity stays consistent)
                analysis["flops"] = analysis["xla_flops"] + tally["flops"]
                analysis["bytes accessed"] = (
                    float(analysis.get("bytes accessed", 0.0))
                    + tally["bytes_accessed"]
                )
                analysis["transcendentals"] = (
                    float(analysis.get("transcendentals", 0.0))
                    + tally["transcendentals"]
                )
            # else: interpret mode lowers the kernel bodies to ordinary HLO
            # that XLA's analysis already counted — folding would double-count
            self._cost_cache[key] = analysis
        return self._cost_cache[key]

    def mfu(
        self,
        batch: Batch,
        step_seconds: Optional[float] = None,
        peak_flops_per_chip: Optional[float] = None,
        gauge_mode: str = "sync",
    ) -> float:
        """Model FLOPs utilization of one step: per-device analyzed flops /
        (step time x per-chip peak).

        ``step_seconds`` defaults to the rolling mean of :meth:`step` wall
        times — which includes dispatch latency, so for honest MFU on small
        models measure through ``step_many``/``run_chunked`` and pass the
        per-step time explicitly. ``peak_flops_per_chip`` is looked up from
        the device kind (dense bf16 peak) when not given.

        The numerator counts Pallas custom-call model-FLOPs too: flash
        attention fwd+bwd and fused CE are tallied analytically and added
        to XLA's count (see :meth:`cost_analysis`) — the round-2 "lower
        bound" caveat no longer applies. Exact for the straight-line kernel
        paths (tested to equality); the ring-attention loop is corrected
        for trace-vs-execution multiplicity (tripwire-tested), the fused
        CE for the row-shard degree on data meshes, and the ``grad_accum``
        scan for trace-once/execute-K multiplicity (both in
        :meth:`cost_analysis`, equality-tripwire-tested).
        """
        if step_seconds is None:
            if self.mean_step_ms is None:
                raise ValueError("no steps timed yet; pass step_seconds=")
            step_seconds = self.mean_step_ms / 1e3
        return _publish_mfu(self.cost_analysis(batch), step_seconds,
                           peak_flops_per_chip, gauge_mode)

    # -- checkpointing -----------------------------------------------------

    def save(self, wait: bool = False, drop_if_busy: bool = False) -> Optional[str]:
        """Checkpoint the full TrainState (params + opt state + step).

        The device->host gather happens on the caller's thread (cheap,
        overlaps with nothing the devices need); the file write runs on a
        background writer so the training loop never stalls on disk. The
        queue is bounded (pending host snapshots are full state copies):
        ``save()`` blocks for a slot (backpressure), auto-saves pass
        ``drop_if_busy`` and skip instead. With ``wait`` the call blocks
        until the write lands and raises that write's own error, if any.
        """
        if self.store is None:
            raise RuntimeError("no checkpoint_dir configured")
        if self.state is None:
            raise RuntimeError("trainer not initialized")
        version = str(self.version)
        self._ensure_writer()
        if drop_if_busy and hasattr(self.store, "snapshot") and jax.process_count() > 1:
            # sharded saves are collective: every process must call save for
            # every version or peers hang waiting at the commit exchange. A
            # per-process skip decision (local queue fullness) would violate
            # that, so fall back to backpressure — same decision everywhere.
            drop_if_busy = False
        if drop_if_busy and self._save_queue.full():
            # check BEFORE the gather: a skipped autosave must not pay a
            # full device->host copy of the state just to discard it
            self.logger.log(f"skipping checkpoint {version}: writer busy")
            return None
        state_tree = {"params": self.state.params, "opt_state": self.state.opt_state,
                      "step": self.state.step}
        if self.state.ema is not None:
            state_tree["ema"] = self.state.ema
        if hasattr(self.store, "snapshot"):
            # sharded store: host copy of only the shards this process owns;
            # the writer thread then does pure file IO on the snapshot
            host_state = self.store.snapshot(state_tree)
        else:
            host_state = jax.device_get(state_tree)
        item = _SaveItem(version, host_state)
        if drop_if_busy:
            try:
                self._save_queue.put_nowait(item)
            except queue.Full:
                self.logger.log(f"skipping checkpoint {version}: writer busy")
                return None
        else:
            self._save_queue.put(item)
        if wait:
            item.done.wait()
            if item.error is not None:
                raise item.error
        return version

    def flush_saves(self) -> None:
        """Block until every queued checkpoint write has landed; raises the
        most recent failure since the last flush (then clears it)."""
        if self._save_queue is not None:
            self._save_queue.join()
        if self._save_errors:
            # clear in place: the writer closure holds a reference to this
            # exact list — rebinding would hide all subsequent failures
            errors = list(self._save_errors)
            self._save_errors.clear()
            raise errors[-1]

    def close(self) -> None:
        """Stop the checkpoint writer thread (flushes queued saves first)."""
        if self._save_thread is not None and self._save_thread.is_alive():
            self._save_queue.put(None)
            self._save_thread.join(timeout=30)
        self._save_thread = None

    def restore(self, version: Optional[str] = None) -> bool:
        """Resume from a checkpoint (latest by default). Returns False when
        the store is empty (reference ``setup()`` resume, models.ts:98-111)."""
        if self.store is None:
            raise RuntimeError("no checkpoint_dir configured")
        if self.state is None:
            self.init()
        version = version or self.store.last()
        if version is None:
            return False
        like = {"params": self.state.params, "opt_state": self.state.opt_state,
                "step": self.state.step}
        want_ema = self.state.ema is not None
        if want_ema:
            like["ema"] = self.state.ema
        # `like` is only read for tree structure and leaf shapes — device
        # arrays serve directly, no device->host copy of the current state
        try:
            host = self.store.load(version, like)
        except KeyError:
            if not want_ema:
                raise
            # checkpoint predates EMA being enabled: load without it and
            # seed the average from the restored params (init()'s semantics)
            like.pop("ema")
            host = self.store.load(version, like)
        placed = jax.tree.map(
            lambda v, cur: jax.device_put(v, cur.sharding),
            host,
            like,
        )
        ema = placed.get("ema")
        if want_ema and ema is None:
            ema = jax.tree.map(jnp.copy, placed["params"])
        self.state = TrainState(placed["params"], placed["opt_state"],
                                placed["step"], ema)
        return True

    def _ensure_writer(self) -> None:
        if self._save_thread is not None and self._save_thread.is_alive():
            return
        # pending items are full host state snapshots: keep the queue tiny
        self._save_queue = queue.Queue(maxsize=2)
        # the closure captures only what the writer needs — not self — so a
        # dropped trainer's device state is not pinned by the thread
        q, store, errors, logger = self._save_queue, self.store, self._save_errors, self.logger

        def writer():
            while True:
                item = q.get()
                try:
                    if item is None:
                        return
                    try:
                        store.save(item.host_state, version=item.version)
                    except Exception as e:  # surface on save(wait)/flush
                        item.error = e
                        errors.append(e)
                        logger.log(f"checkpoint save failed: {e!r}")
                    item.host_state = None  # release the snapshot promptly
                    item.done.set()
                finally:
                    q.task_done()

        self._save_thread = threading.Thread(target=writer, daemon=True)
        self._save_thread.start()

    def step_async(self, batch: Batch) -> jnp.ndarray:
        """Like :meth:`step` but does not block on the loss (keeps the device
        pipeline full; use in throughput-critical loops)."""
        if self.state is None:
            self.init()
        batch = self._ensure_placed(batch)
        with jax.set_mesh(self.mesh):
            self.state, loss = self._step_fn(self.state, batch)
        return loss

    def step_many(self, batches: Batch) -> jnp.ndarray:
        """Run K chained optimizer steps in ONE dispatch.

        ``batches`` is the usual ``(x, y[, w])`` tuple with an extra leading
        step axis: ``x`` is ``[K, B, ...]`` etc. The K steps run as a
        device-side ``lax.scan`` — the TPU-idiomatic inner loop: one launch
        amortizes host dispatch (and any transport latency between host and
        device) over K real parameter updates, which dominates wall-clock
        for small models. Semantically identical to K :meth:`step` calls
        (the step counter advances K times); callbacks fire once per chunk.
        Returns the ``[K]`` per-step losses (device array, not fetched).
        """
        if self.state is None:
            self.init()
        k = jax.tree.leaves(batches)[0].shape[0]
        batches = self._ensure_placed(
            batches, NamedSharding(self.mesh, P(None, "data")))
        if getattr(self, "_multi_fn", None) is None:
            one = self._one_step

            def train_steps(state, bt):
                return jax.lax.scan(one, state, bt)

            self._multi_fn = jax.jit(
                train_steps, donate_argnums=(0,) if self._donate else ())
        # NB: no wall-clock recording here — the jitted scan returns on
        # dispatch (async), so timing it would measure launch cost, not the
        # K device steps; honest timing belongs to the caller's value fetch
        with jax.set_mesh(self.mesh), jax.profiler.StepTraceAnnotation(
                "train_step", step_num=self._steps_dispatched):
            self.state, losses = self._multi_fn(self.state, batches)
        self._steps_dispatched += k
        self.callbacks.fire("step", self)
        need_version = self.callbacks.has("new_version") or (
            self.save_every and self.store is not None
        )
        if need_version:
            # int(step) is a device fetch (a full pipeline sync on remote
            # backends) — only pay it when someone is listening
            version = self.version
            if self.save_every and self.store is not None and any(
                (version - i) % self.save_every == 0 for i in range(k)
            ):
                self.save(drop_if_busy=True)
            self.callbacks.fire("new_version", str(version))
        return losses

    def _ensure_placed(self, batch, sharding: Optional[NamedSharding] = None) -> Any:
        sharding = sharding if sharding is not None else batch_sharding(self.mesh)
        def place(v):
            if isinstance(v, jax.Array) and v.sharding == sharding:
                return v
            return jax.device_put(v, sharding)
        return jax.tree.map(place, batch)

    # -- evaluation -------------------------------------------------------

    def evaluate(self, x: jnp.ndarray, y: jnp.ndarray, metrics: Tuple[str, ...] = ("loss", "accuracy"), use_ema: bool = False, weight=None) -> List[float]:
        """Example-mean metrics on one batch. ``weight`` (per-row, 0 for
        padding) makes padded partial batches exact on a sharded mesh —
        how ``train.evaluate_dataset`` handles non-divisible tails."""
        from distriflow_tpu.models.base import jitted_metrics

        if self.state is None:
            self.init()
        fn = jitted_metrics(self, self.spec, metrics)
        params = self.ema_params if use_ema else self.state.params
        batch = (x, y) if weight is None else (
            x, y, jnp.asarray(weight, jnp.float32))
        with jax.set_mesh(self.mesh):
            return [float(v) for v in fn(params, *self._ensure_placed(batch))]

    def get_params(self) -> Params:
        if self.state is None:
            raise RuntimeError("trainer not initialized; call init() first")
        return self.state.params

    @property
    def ema_params(self) -> Params:
        """The EMA weights (requires ``ema_decay``)."""
        if self.state is None or self.state.ema is None:
            raise RuntimeError("no EMA state; construct with ema_decay=")
        return self.state.ema

    def set_params(self, params: Params) -> None:
        if self.state is None:
            self.init()
        param_sh = tree_shardings(params, self.mesh, self.param_rules)
        self._param_shardings = param_sh
        placed = jax.tree.map(jax.device_put, params, param_sh)
        # rebuild the optimizer state with the SAME sharding policy as
        # init() — a plain eager init would silently replicate ZeRO-sharded
        # moment buffers (memory regression + step recompilation)
        opt_shape = jax.eval_shape(self.optimizer.init, placed)
        opt_sh = opt_state_shardings(
            opt_shape, placed, param_sh, self.mesh,
            zero_axis="data" if self._zero_opt else None,
        )
        opt_state = jax.jit(self.optimizer.init, out_shardings=opt_sh)(placed)
        # EMA restarts at the newly-installed params (same as init): the old
        # average describes weights that no longer exist
        ema = jax.tree.map(jnp.copy, placed) if self.ema_decay else None
        if self.zero_level >= 2:
            from distriflow_tpu.parallel.sharding import _zero_extend

            self._zero_grad_shardings = jax.tree.map(
                lambda sh, p: _zero_extend(sh, np.shape(p), self.mesh, "data"),
                param_sh, placed,
            )
            if ema is not None:
                ema = jax.tree.map(
                    jax.device_put, ema, self._zero_grad_shardings)
        self.state = TrainState(placed, opt_state, self.state.step, ema)
