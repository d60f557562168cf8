"""Pipeline parallelism over the ``pipe`` mesh axis (GPipe-style SPMD).

No reference equivalent (model parallelism is explicitly out of scope there,
``README.md:4``); provided as a first-class strategy here. The implementation
is the SPMD collective-permute pipeline:

- stage parameters carry a leading stages dim sharded over ``pipe`` — every
  device holds one stage's weights;
- the input batch is split into M microbatches; the schedule runs
  ``M + P - 1`` ticks. Each tick, every device runs the (identical) stage
  function on the activation it holds, then ``ppermute``s its output one hop
  down the ring; stage 0 injects microbatch ``t`` and the last stage banks
  its outputs. Bubbles (ticks where a stage has no real work) execute with
  zeros — the standard SPMD trade for lockstep scheduling;
- activations must keep one shape through stages (true for transformer
  blocks), which is what lets a single jitted program express the schedule.

Three backward strategies (``TransformerConfig.pipeline_schedule``):

- :func:`gpipe` — plain autodiff through the schedule. JAX saves every
  tick's stage *internals* (attention scores, FFN intermediates, ...) as
  scan residuals: per-device activation memory is
  O(ticks x microbatch x per-stage internals) — the deep/long-context
  memory wall. Fastest when memory is not binding.
- :func:`gpipe_remat` — a custom-VJP schedule that saves ONLY each tick's
  stage *input* ([mb, ...] activations, one tensor per tick) and re-runs
  the stage under ``jax.vjp`` during a mirrored reverse schedule. This is
  per-stage rematerialization that *composes with the pipeline by
  construction*: the recompute happens inside the backward shard_map, so no
  ``jax.checkpoint`` residuals ever cross the hybrid manual/auto boundary
  (the round-1 failure mode). Cost: one extra stage forward per
  microbatch-stage (the standard remat trade); memory: internals shrink to
  one live microbatch per device regardless of pipeline depth.
- :func:`gpipe_1f1b` — the interleaved one-forward-one-backward order as a
  single combined tick loop in the backward: live stage inputs are bounded
  by P (a ring buffer) instead of remat's M, and the custom VJP keeps no
  residuals beyond (params, xs). The winner when activations dominate —
  many microbatches x long sequences.

Gradients are exact for all three (equivalence-tested).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distriflow_tpu.parallel.collectives import pvary


def _pipeline_setup(stacked_params, x, mesh, num_microbatches, axis, data_axis):
    """Shared validation + schedule constants for both pipeline variants:
    (p, m, mb, d, xs, batch_spec, manual_axes, perm_down)."""
    p = mesh.shape[axis]
    m = num_microbatches
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_stages != p:
        raise ValueError(
            f"stacked_params has {n_stages} stages but the {axis!r} axis has "
            f"{p} devices — shard_map would silently drop stages"
        )
    mb = b // m
    d = mesh.shape.get(data_axis, 1) if data_axis else 1
    if mb % max(d, 1):
        raise ValueError(
            f"microbatch size {mb} not divisible by the {data_axis!r} axis ({d})"
        )
    xs = x.reshape((m, mb) + x.shape[1:])
    batch_spec = P(None, data_axis) if d > 1 else P()
    manual = {axis} | ({data_axis} if d > 1 else set())
    # an axis of size 1 has nothing to partition, so manual and automatic
    # mean the same for it — but a Mosaic kernel in the stage body (flash
    # attention, the auto choice on TPU) lowers only when EVERY mesh axis is
    # manual. Real automatic axes (model > 1: TP inside the stages) stay
    # automatic, and there the kernels are still refused (ROADMAP R3).
    manual |= {a for a in mesh.axis_names if mesh.shape[a] == 1}
    perm_down = [(i, (i + 1) % p) for i in range(p)]
    return p, m, mb, d, xs, batch_spec, manual, perm_down



def _make_forward_local(stage_fn, p, m, axis, perm_down, save_inputs):
    """The one forward-schedule body all three variants share: stage 0
    injects microbatch t, everyone runs the stage, the last stage banks
    slot t-(P-1), activations rotate one hop down the ring. With
    ``save_inputs`` each tick's stage input is also returned (leading
    stages dim) — gpipe_remat's only residual."""

    def local(params, xs):
        params = jax.tree.map(lambda v: v[0], params)  # my stage's slice
        idx = lax.axis_index(axis)
        state0 = pvary(jnp.zeros_like(xs[0]), axis)
        outputs0 = pvary(jnp.zeros_like(xs), axis)

        def tick(carry, t):
            state, outputs = carry
            x_in = lax.dynamic_index_in_dim(xs, jnp.minimum(t, m - 1), 0,
                                            keepdims=False)
            state = jnp.where((idx == 0) & (t < m), x_in, state)
            saved = state
            out = stage_fn(params, state)
            out_slot = t - (p - 1)
            bank = (idx == p - 1) & (out_slot >= 0)
            outputs = lax.cond(
                bank,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(out_slot, 0), 0),
                lambda o: o,
                outputs,
            )
            state = lax.ppermute(out, axis, perm_down)
            return (state, outputs), (saved if save_inputs else None)

        (_, outputs), saved = lax.scan(tick, (state0, outputs0),
                                       jnp.arange(m + p - 1))
        outputs = lax.psum(
            jnp.where(idx == p - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        if save_inputs:
            return outputs, saved[:, None]  # [ticks, 1(stage), mb_local, ...]
        return outputs

    return local


def gpipe(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pipe",
    data_axis: str = "data",
) -> jnp.ndarray:
    """Run ``x`` through P pipeline stages of ``stage_fn``.

    ``stacked_params``: pytree whose leaves have leading dim P (stage i's
    params at index i), sharded (or shardable) over ``axis``. ``x``:
    ``[B, ...]`` with ``B`` divisible by ``num_microbatches``; output has
    ``x``'s shape (activation shape is stage-invariant).

    Composes with data parallelism: when the mesh has a ``data_axis``, each
    microbatch's rows shard over it (DP x PP — the ring permute moves
    activations within each data slice), so the per-device activation is
    ``[mb / data, ...]``, not the full microbatch.
    """
    b = x.shape[0]
    p, m, mb, d, xs, batch_spec, manual, perm = _pipeline_setup(
        stacked_params, x, mesh, num_microbatches, axis, data_axis)

    local = _make_forward_local(stage_fn, p, m, axis, perm, save_inputs=False)

    # Hybrid manual/auto: only the pipe (and data) axes are manual in the
    # body. Every other mesh axis stays automatic, so e.g. Megatron TP
    # sharding on stage weights is preserved through the pipeline — XLA
    # partitions the in-stage einsums and inserts the TP collectives itself
    # instead of all-gathering the weights at the shard_map boundary.
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), batch_spec),
        out_specs=batch_spec,
        axis_names=manual,
        check_vma=False,  # outputs are made uniform by the final psum
    )
    out = fn(stacked_params, xs)
    return out.reshape((b,) + x.shape[1:])


def gpipe_remat(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pipe",
    data_axis: str = "data",
) -> jnp.ndarray:
    """:func:`gpipe` with an input-only-residual custom backward.

    Forward is the same M+P-1-tick schedule; the only residual kept per
    tick is the stage *input* activation. Backward runs the mirrored
    schedule in reverse: each tick re-linearizes the stage at its saved
    input (``jax.vjp`` = recompute + transpose), consumes the output
    cotangent arriving from downstream (or the loss cotangent at the last
    stage's banked slots), accumulates parameter gradients locally, and
    ``ppermute``s the input cotangent one hop UP the ring. Gradients are
    exact — bubbles carry zero cotangents, so masked ticks contribute
    nothing.

    Memory per device: O(ticks x microbatch) activations saved vs
    autodiff-:func:`gpipe`'s O(ticks x microbatch x stage internals) scan
    residuals; stage internals exist only for the one microbatch being
    recomputed. Cost: one extra stage forward per tick (standard remat).
    Composes with the hybrid manual/auto shard_map exactly like the
    forward — in-stage Megatron TP stays on the automatic ``model`` axis
    in both directions.
    """
    b = x.shape[0]
    p, m, mb, d, xs, batch_spec, manual, perm_down = _pipeline_setup(
        stacked_params, x, mesh, num_microbatches, axis, data_axis)
    saved_spec = P(None, axis, data_axis) if d > 1 else P(None, axis)
    ticks = m + p - 1
    perm_up = [(i, (i - 1) % p) for i in range(p)]

    fwd_local = _make_forward_local(
        stage_fn, p, m, axis, perm_down, save_inputs=True)

    def bwd_local(params, saved, dys):
        params = jax.tree.map(lambda v: v[0], params)
        saved = saved[:, 0]  # [ticks, mb_local, ...]
        idx = lax.axis_index(axis)
        cot0 = pvary(jnp.zeros_like(dys[0]), axis)
        grads0 = jax.tree.map(jnp.zeros_like, params)
        dxs0 = pvary(jnp.zeros_like(dys), axis)

        def rtick(carry, t):
            cot_in, grads, dxs = carry
            slot = t - (p - 1)
            dy_t = lax.dynamic_index_in_dim(dys, jnp.maximum(slot, 0), 0,
                                            keepdims=False)
            # my tick-t output's cotangent: the banked slot's loss cotangent
            # on the last stage, else whatever downstream sent up the ring
            cot_out = jnp.where((idx == p - 1) & (slot >= 0), dy_t, cot_in)
            state_t = lax.dynamic_index_in_dim(saved, t, 0, keepdims=False)
            _, vjp_fn = jax.vjp(stage_fn, params, state_t)
            dp, dstate = vjp_fn(cot_out)
            grads = jax.tree.map(jnp.add, grads, dp)
            inject = (idx == 0) & (t < m)
            # bank dx for the microbatch stage 0 injected at tick t; the
            # pre-injection state's cotangent is zero (it was overwritten),
            # so nothing continues up the ring from an inject tick
            dxs = lax.cond(
                inject,
                lambda a: lax.dynamic_update_index_in_dim(
                    a, dstate, jnp.minimum(t, m - 1), 0),
                lambda a: a,
                dxs,
            )
            dstate_pass = jnp.where(inject, jnp.zeros_like(dstate), dstate)
            cot_next = lax.ppermute(dstate_pass, axis, perm_up)
            return (cot_next, grads, dxs), None

        (_, grads, dxs), _ = lax.scan(
            rtick, (cot0, grads0, dxs0), jnp.arange(ticks - 1, -1, -1))
        if d > 1:
            # microbatch rows are sharded over data: partial param grads
            grads = jax.tree.map(lambda g: lax.psum(g, data_axis), grads)
        dxs = lax.psum(jnp.where(idx == 0, dxs, jnp.zeros_like(dxs)), axis)
        return jax.tree.map(lambda g: g[None], grads), dxs

    fwd_sm = shard_map(
        fwd_local, mesh=mesh,
        in_specs=(P(axis), batch_spec),
        out_specs=(batch_spec, saved_spec),
        axis_names=manual, check_vma=False,
    )
    bwd_sm = shard_map(
        bwd_local, mesh=mesh,
        in_specs=(P(axis), saved_spec, batch_spec),
        out_specs=(P(axis), batch_spec),
        axis_names=manual, check_vma=False,
    )

    @jax.custom_vjp
    def run(params, xs):
        y, _ = fwd_sm(params, xs)  # saved is dead here: XLA DCEs it
        return y

    def run_fwd(params, xs):
        y, saved = fwd_sm(params, xs)
        return y, (params, saved)

    def run_bwd(res, dy):
        params, saved = res
        return bwd_sm(params, saved, dy)

    run.defvjp(run_fwd, run_bwd)
    out = run(stacked_params, xs)
    return out.reshape((b,) + x.shape[1:])


def gpipe_1f1b(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pipe",
    data_axis: str = "data",
) -> jnp.ndarray:
    """Interleaved 1F1B pipeline: O(P) live activations, any microbatch count.

    The backward pass runs the classic one-forward-one-backward schedule as
    a single SPMD tick loop: stage ``s`` runs the *forward* of microbatch
    ``m`` at tick ``2m + s`` and its *backward* at tick ``2m + 2P - 1 - s``
    — per device, forward and backward ticks strictly alternate (the 1F1B
    steady state), forward activations flow down the ring on even-offset
    ticks while cotangents flow up on the odd ones, and a microbatch's
    stage input is freed ``2(P - s) - 1`` ticks after it is produced. Live
    stage inputs per device therefore never exceed P — a ring buffer of P
    microbatch activations — versus O(M) for :func:`gpipe_remat`'s saved
    schedule and O(M x stage internals) for autodiff :func:`gpipe`. The
    custom VJP keeps **no residuals at all** beyond (params, xs): the
    backward loop recomputes the forward wave itself, interleaved with
    consumption, which is what bounds the window to P.

    Gradients are exact (equivalence-tested against autodiff
    :func:`gpipe`). Cost: the primal forward plus a 2(M+P-1)-tick combined
    loop whose per-tick work is one stage forward OR one stage
    re-linearization (``jax.vjp``), selected by a per-device
    ``lax.cond`` — collectives stay outside the conditional, so lockstep
    ppermutes are preserved. Prefer this schedule for long training runs
    with many microbatches where even gpipe_remat's O(M) stage-input
    buffer binds; prefer :func:`gpipe_remat` when M is small (its loop is
    shorter and branch-free).
    """
    b = x.shape[0]
    p, m, mb, d, xs, batch_spec, manual, perm_down = _pipeline_setup(
        stacked_params, x, mesh, num_microbatches, axis, data_axis)
    perm_up = [(i, (i - 1) % p) for i in range(p)]
    bwd_ticks = 2 * m + 2 * p - 2
    ring_size = p

    # primal forward: the plain schedule, nothing saved (the 1F1B backward
    # recomputes the forward wave itself)
    fwd_local = _make_forward_local(
        stage_fn, p, m, axis, perm_down, save_inputs=False)

    def bwd_local(params, xs, dys):
        params = jax.tree.map(lambda v: v[0], params)
        idx = lax.axis_index(axis)
        fwd0 = pvary(jnp.zeros_like(xs[0]), axis)
        cot0 = pvary(jnp.zeros_like(dys[0]), axis)
        ring0 = pvary(jnp.zeros((ring_size,) + xs[0].shape, xs.dtype), axis)
        dxs0 = pvary(jnp.zeros_like(dys), axis)
        grads0 = jax.tree.map(jnp.zeros_like, params)

        def tick(carry, t):
            fwd_state, cot_in, ring, dxs, grads = carry
            # forward slot: stage idx runs microbatch m_f at tick 2*m_f+idx
            tf = t - idx
            m_f = jnp.clip(tf // 2, 0, m - 1)
            f_active = (tf >= 0) & (tf % 2 == 0) & (tf // 2 < m)
            # backward slot: tick 2*m_b + 2P-1 - idx
            tb = t - (2 * p - 1 - idx)
            m_b = jnp.clip(tb // 2, 0, m - 1)
            b_active = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < m)

            zero_state = jnp.zeros_like(fwd_state)

            def f_branch(ops):
                fwd_state, cot_in, ring, dxs = ops
                x_in = lax.dynamic_index_in_dim(xs, m_f, 0, keepdims=False)
                state = jnp.where(idx == 0, x_in, fwd_state)
                out = stage_fn(params, state)
                # save this microbatch's stage input; ring slot m_f mod P is
                # free again by schedule construction. Inactive (idle) ticks
                # run this branch too — suppress their garbage write.
                slot = m_f % ring_size
                old = lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)
                ring = lax.dynamic_update_index_in_dim(
                    ring, jnp.where(f_active, state, old), slot, 0)
                return out, zero_state, jax.tree.map(jnp.zeros_like, grads), ring, dxs

            def b_branch(ops):
                fwd_state, cot_in, ring, dxs = ops
                state_t = lax.dynamic_index_in_dim(ring, m_b % ring_size, 0,
                                                   keepdims=False)
                dy_t = lax.dynamic_index_in_dim(dys, m_b, 0, keepdims=False)
                # last stage consumes the loss cotangent of its banked slot;
                # everyone else consumes what downstream sent up the ring
                cot_out = jnp.where(idx == p - 1, dy_t, cot_in)
                _, vjp_fn = jax.vjp(stage_fn, params, state_t)
                dp, dstate = vjp_fn(cot_out)
                # stage 0 banks dx (its input was the injected microbatch);
                # nothing real continues above stage 0
                old = lax.dynamic_index_in_dim(dxs, m_b, 0, keepdims=False)
                dxs = lax.dynamic_update_index_in_dim(
                    dxs, jnp.where(idx == 0, dstate, old), m_b, 0)
                dstate_pass = jnp.where(idx == 0, jnp.zeros_like(dstate), dstate)
                return zero_state, dstate_pass, dp, ring, dxs

            out, dstate_pass, dp, ring, dxs = lax.cond(
                b_active, b_branch, f_branch,
                (fwd_state, cot_in, ring, dxs))
            grads = jax.tree.map(jnp.add, grads, dp)
            # both waves advance every tick, branch-independent (collectives
            # never sit inside the cond)
            fwd_next = lax.ppermute(out, axis, perm_down)
            cot_next = lax.ppermute(dstate_pass, axis, perm_up)
            return (fwd_next, cot_next, ring, dxs, grads), None

        (_, _, _, dxs, grads), _ = lax.scan(
            tick, (fwd0, cot0, ring0, dxs0, grads0), jnp.arange(bwd_ticks))
        if d > 1:
            grads = jax.tree.map(lambda g: lax.psum(g, data_axis), grads)
        dxs = lax.psum(jnp.where(idx == 0, dxs, jnp.zeros_like(dxs)), axis)
        return jax.tree.map(lambda g: g[None], grads), dxs

    fwd_sm = shard_map(
        fwd_local, mesh=mesh,
        in_specs=(P(axis), batch_spec), out_specs=batch_spec,
        axis_names=manual, check_vma=False,
    )
    bwd_sm = shard_map(
        bwd_local, mesh=mesh,
        in_specs=(P(axis), batch_spec, batch_spec),
        out_specs=(P(axis), batch_spec),
        axis_names=manual, check_vma=False,
    )

    @jax.custom_vjp
    def run(params, xs):
        return fwd_sm(params, xs)

    def run_fwd(params, xs):
        return fwd_sm(params, xs), (params, xs)

    def run_bwd(res, dy):
        params, xs = res
        return bwd_sm(params, xs, dy)

    run.defvjp(run_fwd, run_bwd)
    return run(stacked_params, xs).reshape((b,) + x.shape[1:])
