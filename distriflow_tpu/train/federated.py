"""Federated averaging: local epochs + periodic weight allreduce.

The reference's "FederatedServer" is really a gradient-mean server — clients
push per-chunk *gradients*, not locally-trained weights (SURVEY.md §3.2;
``src/client/federated_client.ts:95-121``). True FedAvg
("per-worker local epochs + periodic weight allreduce") is implemented here
the TPU way:

- every mesh device on the ``data`` axis is one federated worker;
- a round = each worker runs K local optimizer steps on its own shard
  (``lax.scan`` inside ``shard_map`` — per-worker local state, SURVEY.md §7
  hard part (c)) followed by ONE weight ``pmean`` over ICI;
- the whole round — K·W local steps plus the averaging — is a single
  jit-compiled program; weights cross no host boundary.

The gradient-mean mode of the reference is exactly ``local_steps=1`` with
SGD (mean of one-step weight deltas == step along mean gradient), so this
engine subsumes the reference's federated semantics while adding the real
thing.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distriflow_tpu.models.base import ModelSpec, _optimizer, init_params
from distriflow_tpu.parallel.collectives import pvary
from distriflow_tpu.parallel.mesh import data_parallel_mesh
from distriflow_tpu.obs.telemetry import get_telemetry
from distriflow_tpu.obs.tracing import new_trace_id
from distriflow_tpu.utils.logging import CallbackRegistry, VerboseLogger
from distriflow_tpu.utils.profiling import device_timer

Params = Any


class FederatedAveragingTrainer:
    """FedAvg over the mesh's ``data`` axis: one device = one worker."""

    def __init__(
        self,
        spec: ModelSpec,
        mesh: Optional[Mesh] = None,
        local_steps: int = 1,
        local_batch_size: int = 32,
        learning_rate: Optional[float] = None,  # None -> 0.01 (FedAvg-typical)
        optimizer: str = "sgd",
        verbose: Optional[bool] = None,
        checkpoint_dir: Optional[str] = None,
        save_every: int = 0,  # rounds between auto-saves (0 = manual only)
        max_checkpoints: Optional[int] = None,
    ):
        self.spec = spec
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.local_steps = local_steps
        self.local_batch_size = local_batch_size
        self.optimizer = _optimizer(optimizer, learning_rate, default_rate=0.01)
        # checkpoint/resume (reference persistence semantics, C10): FedAvg
        # state is the averaged params + the round counter — per-worker
        # optimizer state is transient inside the round and never persists
        from distriflow_tpu.checkpoint import make_store

        self.save_every = save_every
        self.store = make_store(checkpoint_dir, max_checkpoints)
        self.logger = VerboseLogger(f"FedAvg[{spec.name}]", verbose)
        self.callbacks = CallbackRegistry("new_version", "round")
        self.params: Optional[Params] = None
        self.round_index = 0
        self.num_workers = self.mesh.shape["data"]
        self._round_fn = self._build_round()
        _t = get_telemetry()
        self._h_round = _t.histogram(
            "train_step_ms", mode="federated",
            help="wall time per training step/round (ms), by mode")
        # phase profiler + per-round trace (docs/OBSERVABILITY.md §5/§9):
        # a fedavg round decomposes into stage (host->device placement) and
        # fit (the jitted K-local-steps + allreduce), so bench rows can name
        # what bounds a round the same way the async trainer's do
        self._prof = _t.profiler("fedavg")
        self._tracer = _t.tracer

    def init(self, rng: Optional[jax.Array] = None) -> Params:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        params = init_params(self.spec, rng)
        self.params = jax.device_put(params, NamedSharding(self.mesh, P()))
        return self.params

    def _build_round(self) -> Callable[[Params, jnp.ndarray, jnp.ndarray], Tuple[Params, jnp.ndarray]]:
        spec = self.spec
        optimizer = self.optimizer
        k = self.local_steps

        def local_train(params: Params, xs: jnp.ndarray, ys: jnp.ndarray):
            """K local steps on this worker's shard. xs: [1, K, B, ...]
            (leading worker dim of the shard), scanned over K."""
            xs = xs[0]
            ys = ys[0]
            # params arrive replicated-typed; cast varying so each worker's
            # autodiff stays local (else JAX psums grads across workers)
            params = pvary(params, "data")
            opt_state = optimizer.init(params)

            def step(carry, xy):
                p, o = carry
                x, y = xy
                loss, grads = jax.value_and_grad(spec.loss_fn)(p, x, y)
                updates, o = optimizer.update(grads, o, p)
                return (optax.apply_updates(p, updates), o), loss

            (p, _), losses = lax.scan(step, (params, opt_state), (xs, ys))
            # periodic weight allreduce: the ONE collective of the round
            p = jax.tree.map(lambda v: lax.pmean(v, "data"), p)
            return p, lax.pmean(jnp.mean(losses), "data")

        sharded = shard_map(
            local_train,
            mesh=self.mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()),
            # Pallas kernels in the model (flash attention, fused CE: the
            # auto choices on TPU) declare no varying-axes type on their
            # outputs, which the check requires of every op in the body
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0,))

    def round(self, x: jnp.ndarray, y: jnp.ndarray) -> float:
        """One FedAvg round.

        ``x``/``y`` hold every worker's local data for the round, shaped
        ``[num_workers, local_steps, local_batch_size, ...]`` (leading dim
        sharded over workers).
        """
        if self.params is None:
            self.init()
        w, k, b = self.num_workers, self.local_steps, self.local_batch_size
        expect = (w, k, b)
        if tuple(x.shape[:3]) != expect:
            raise ValueError(
                f"round data must be [workers={w}, local_steps={k}, batch={b}, ...]; "
                f"got {tuple(x.shape[:3])}"
            )
        tid = new_trace_id() if self._tracer.enabled else None
        t0_wall, t0_mono = time.time(), time.monotonic()
        with self._prof.step():
            t_stage = time.perf_counter()
            with self._prof.phase("stage"):
                x = jax.device_put(jnp.asarray(x),
                                   NamedSharding(self.mesh, P("data")))
                y = jax.device_put(jnp.asarray(y),
                                   NamedSharding(self.mesh, P("data")))
                jax.block_until_ready((x, y))
            stage_ms = (time.perf_counter() - t_stage) * 1e3
            with device_timer() as timing, self._prof.phase("fit"):
                self.params, loss = self._round_fn(self.params, x, y)
                loss = float(loss)  # blocks: the round (and its allreduce) finished
        self._h_round.observe(timing["ms"])
        if tid is not None:
            # same decomposition as the profiler step, as one trace: a
            # "round" root plus stage/fit children (bench's bound_by column
            # assembles these)
            self._tracer.emit("stage", trace_id=tid, dur_ms=stage_ms,
                              start=t0_wall, mono=t0_mono)
            self._tracer.emit("fit", trace_id=tid, dur_ms=timing["ms"],
                              start=t0_wall + stage_ms / 1e3,
                              mono=t0_mono + stage_ms / 1e3)
            self._tracer.emit(
                "round", trace_id=tid,
                dur_ms=(time.monotonic() - t0_mono) * 1e3,
                start=t0_wall, mono=t0_mono, role="fedavg")
        self.round_index += 1
        if (self.store is not None and self.save_every
                and self.round_index % self.save_every == 0):
            self.save()
        self.callbacks.fire("round", self.round_index)
        self.callbacks.fire("new_version", str(self.round_index))
        return loss

    def pack_round_data(self, x, y, rng=None):
        """Convenience: sample a round's [W, K, B, ...] layout from arrays."""
        import numpy as np

        w, k, b = self.num_workers, self.local_steps, self.local_batch_size
        need = w * k * b
        if len(x) < need:
            raise ValueError(f"need at least {need} examples per round, got {len(x)}")
        idx = (rng or np.random.RandomState(self.round_index)).permutation(len(x))[:need]
        from distriflow_tpu.data.dataset import sample_batch

        xs, ys = sample_batch(x, y, idx)
        xs = xs.reshape((w, k, b) + xs.shape[1:])
        ys = ys.reshape((w, k, b) + ys.shape[1:])
        return xs, ys

    def save(self) -> str:
        """Checkpoint the averaged params + round counter (synchronous)."""
        if self.store is None:
            raise RuntimeError("no checkpoint_dir configured")
        if self.params is None:
            raise RuntimeError("trainer not initialized")
        return self.store.save(
            {"params": jax.device_get(self.params),
             "round_index": jnp.int32(self.round_index)},
            version=str(self.round_index),
        )

    def restore(self, version: Optional[str] = None) -> bool:
        """Resume from the latest (or a named) round. False when empty."""
        if self.store is None:
            raise RuntimeError("no checkpoint_dir configured")
        if self.params is None:
            self.init()
        version = version or self.store.last()
        if version is None:
            return False
        like = {"params": self.params, "round_index": jnp.int32(0)}
        host = self.store.load(version, like)
        self.params = jax.device_put(
            host["params"], NamedSharding(self.mesh, P()))
        self.round_index = int(host["round_index"])
        return True

    def evaluate(self, x, y, metrics=("loss", "accuracy"), weight=None) -> List[float]:
        from distriflow_tpu.models.base import jitted_metrics

        fn = jitted_metrics(self, self.spec, metrics)
        args = [jnp.asarray(x), jnp.asarray(y)]
        if weight is not None:
            args.append(jnp.asarray(weight, jnp.float32))
        return [float(v) for v in fn(self.params, *args)]
