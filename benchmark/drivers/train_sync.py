"""Training cells: ``SyncTrainer`` driven by ``run_chunked``, the way
``experiments/lm/train.py`` does it, on a data-parallel mesh over the
cell's chips.

Traffic file keys: ``batch_per_chip``, ``seq``, ``steps_per_dispatch``,
``corpus_tokens``, ``warm_steps``, ``trace_steps`` (how many steps the
profiler sees in a traced run) and ``trace_after_steps``.

The window: ``run_chunked`` over an endless seeded batch stream that ends
when ``--seconds`` have passed since the first dispatch returned. Its own
rule gives the rate: first dispatch excluded, the loss fetch is the device
barrier, every later step and all the time up to the last fetch counted.
"""

from __future__ import annotations

import time
from typing import Any, Iterator, List, Tuple

import numpy as np

from benchmark.lib import corpus as corpus_lib
from benchmark.lib import compile_meter, harness, reference_lm
from benchmark.lib.harness import Run, say

# -- tolerances, each with its reason ----------------------------------------
# The trainer computes in bfloat16 (eps 2^-8 = 3.9e-3 per rounding) from
# float32 parameters; the reference is float32 at "highest" precision. The
# loss is a mean over 2048 positions of a float32 cross-entropy whose logits
# carry a few bf16 roundings each: the errors average out, and the bias that
# remains measured 7e-5 of the loss at these widths on the chip (PR 23).
# Float32 compute would agree to 1e-6; a wrong mask, scale or rotary pairing
# moves the loss by percents.
LOSS_RTOL = 1e-3
# A gradient passes through every block twice in bf16. Compared as
# |g - g_ref| / |g_ref| over the whole leaf (Frobenius norm), the rounding
# noise of independent elements adds in quadrature to a few eps: measured
# 9.6e-3 (embedding) and 8.0e-3 (block 0's first FFN matrix) on the chip
# (PR 23). Float32 compute would give 1e-5, so the check also fails a step
# that is *more* exact than the configuration states only if it is wrong.
GRAD_RTOL = 4e-2
# the direction agrees far better than the noise's size (measured 0.99995)
GRAD_COSINE = 0.9995


def _stream(corpus: np.ndarray, batch: int, seq: int, seed: int,
            deadline: List[float]) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Seeded batches until ``deadline[0]`` (set once the window opens)."""
    for item in corpus_lib.batches(corpus, batch, seq, seed):
        if time.monotonic() >= deadline[0]:
            return
        yield item


def _rel(got: Any, want: Any) -> Tuple[float, float]:
    got = np.asarray(got, np.float32).ravel()
    want = np.asarray(want, np.float32).ravel()
    err = float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))
    cos = float(got @ want / max(np.linalg.norm(got) * np.linalg.norm(want),
                                 1e-30))
    return err, cos


def _check_against_reference(run: Run, trainer: Any, x: np.ndarray,
                             y: np.ndarray) -> bool:
    """One seeded sequence: the trainer's loss function (the very function
    its step differentiates: model, flash attention, fused CE) against the
    plain reference, loss and two gradient leaves."""
    import jax

    from distriflow_tpu import shard_batch

    n_dev = len(run.devices)
    n_layers = run.model["n_layers"]
    # the same sequence on every chip: the mean and its gradient are those
    # of one sequence, and the batch still splits over the mesh
    bx, by = np.tile(x[:1], (n_dev, 1)), np.tile(y[:1], (n_dev, 1))
    params = trainer.get_params()
    wrt = (("embed", "embedding"), ("layers_0", "mlp", "wi", "kernel"))
    with jax.set_mesh(trainer.mesh):
        loss, grads = jax.jit(jax.value_and_grad(trainer.spec.loss_fn))(
            params, *shard_batch(trainer.mesh, (bx, by)), None)
    ref_loss, ref_grads = reference_lm.loss_and_grads(
        params, x[0], y[0], n_layers, wrt,
        run.model["rope_base"])
    loss, ref_loss = float(loss), float(ref_loss)
    ok = abs(loss - ref_loss) <= LOSS_RTOL * abs(ref_loss)
    say(f"  reference: loss {loss:.6f} vs {ref_loss:.6f} "
        f"(rel {abs(loss - ref_loss) / abs(ref_loss):.2e}, tol {LOSS_RTOL})")
    for path, ref in zip(wrt, ref_grads):
        leaf = grads["params"]
        for key in path:
            leaf = leaf[key]
        err, cos = _rel(leaf, ref)
        say(f"  reference: grad {'/'.join(path)} rel err {err:.3e} "
            f"(tol {GRAD_RTOL}), cosine {cos:.6f} (min {GRAD_COSINE})")
        ok = ok and err <= GRAD_RTOL and cos >= GRAD_COSINE
    return ok


def run(run: Run) -> None:
    import jax

    from distriflow_tpu import (
        TRANSFORMER_TP_RULES,
        SyncTrainer,
        data_parallel_mesh,
        run_chunked,
        shard_batch,
        transformer_lm,
    )

    t = run.traffic
    m = run.model
    trainer_cfg = run.config["trainer"]
    n_dev = len(run.devices)
    on_tpu = run.devices[0].platform == "tpu"
    batch, seq = t["batch_per_chip"] * n_dev, t["seq"]
    setup_mark = run.meter.mark()

    cfg = harness.transformer_config(m, name_kernels=not on_tpu, max_seq=seq)
    mesh = data_parallel_mesh(run.devices)
    spec = transformer_lm(cfg, mesh=mesh, example_seq=seq)
    trainer = SyncTrainer(
        spec, mesh=mesh, learning_rate=trainer_cfg["learning_rate"],
        optimizer=trainer_cfg["optimizer"], param_rules=TRANSFORMER_TP_RULES)
    seed = run.seed
    with run.phase("trainer state from the seed"):
        trainer.init(harness.prng_key(seed))
        jax.block_until_ready(trainer.state)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(trainer.get_params()))
    say(f"  model: {n_params / 1e6:.1f} M parameters, loss {spec.loss}, "
        f"mesh data={n_dev}, global batch {batch} x {seq}")

    with run.phase("corpus"):
        corpus = corpus_lib.generate_corpus(t["corpus_tokens"], seed=0)
    warm = corpus_lib.batches(corpus, batch, seq, seed + 1)
    x0, y0 = next(warm)

    with run.phase("loss and gradients against the reference"):
        correct = _check_against_reference(run, trainer, x0, y0)

    # warm-up: the first step compiles (or loads) the step program
    with run.phase("warm-up steps"):
        warm_losses = [trainer.step(next(warm))
                       for _ in range(t["warm_steps"])]
    say("  warm-up losses: " + " ".join(f"{v:.4f}" for v in warm_losses))
    if n_dev > 1:
        with run.phase("compiled step's text"):
            compiled = trainer.lower_step((x0, y0)).compile().as_text()
        shards = {s.data.shape for s in
                  shard_batch(mesh, (x0, y0))[0].addressable_shards}
        split_ok = shards == {(t["batch_per_chip"], seq)}
        say(f"  data parallel: batch shards {shards}, all-reduce in the "
            f"compiled step: {'all-reduce' in compiled}")
        correct = correct and split_ok and "all-reduce" in compiled
        if on_tpu:
            kernels = compile_meter.mosaic_kernels(compiled)
            say(f"  compiled step: Mosaic calls {sorted(kernels)}")
    run.compile_setup = run.meter.since(setup_mark)

    # -- the window ------------------------------------------------------------
    deadline = [float("inf")]
    steps: List[Tuple[float, float]] = []
    trace_from = t["trace_after_steps"]
    trace_to = trace_from + t["trace_steps"]
    tracing = {"on": False}

    def log(step: int, loss: float) -> None:
        now = time.monotonic()
        steps.append((now, loss))
        if step == 1:
            # run_chunked restarts its clock here: the window opens
            deadline[0] = now + run.seconds
            run.window = (now, now)
            run.end_to_end["setup_s"] = now - run.t_process
            window_mark.append(run.meter.mark())
        if run.trace and step == trace_from:
            jax.profiler.start_trace(run.trace_dir)
            tracing["on"] = True
            run.trace_window = (time.monotonic(), 0.0)
        elif tracing["on"] and step == trace_to:
            jax.profiler.stop_trace()
            tracing["on"] = False
            run.trace_window = (run.trace_window[0], time.monotonic())

    window_mark: List[Any] = []
    if run.trace:
        import shutil

        shutil.rmtree(run.trace_dir, ignore_errors=True)
    result = run_chunked(
        trainer, _stream(corpus, batch, seq, seed, deadline), steps=10 ** 9,
        steps_per_dispatch=t["steps_per_dispatch"], log=log, log_every=1)
    if tracing["on"]:
        jax.profiler.stop_trace()
        run.trace_window = (run.trace_window[0], time.monotonic())
    run.window = (run.window[0], steps[-1][0])
    run.compile_window = run.meter.since(window_mark[0])
    run.steps = steps
    run.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in run.devices)
    trainer.close()

    losses = [loss for _, loss in steps]
    finite = [bool(np.isfinite(v)) for v in losses]
    run.attempted = result.steps_run
    run.failed = finite.count(False)
    falling = losses[-1] < warm_losses[0]
    tokens = result.timed_steps * batch * seq
    say(f"  window: {result.timed_steps} timed steps in {result.elapsed_s:.3f}s "
        f"({result.steps_run} run), loss {warm_losses[0]:.4f} -> "
        f"{losses[-1]:.4f}, programs compiled or loaded in the window: "
        f"{run.compile_window['programs']}")
    run.correct = bool(correct and all(finite) and falling
                       and result.timed_steps > 0)
    run.end_to_end["train_tok_s_chip"] = (
        tokens / result.elapsed_s / n_dev if result.timed_steps else None)
    run.shapes = {"batch_per_chip": t["batch_per_chip"], "seq": seq,
                  "tokens_per_step": batch * seq, "n_dev": n_dev}
