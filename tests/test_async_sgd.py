"""Async-SGD engine tests: staleness bounds, decay, concurrent workers."""

import jax
import numpy as np
import pytest

from distriflow_tpu.data.dataset import DistributedDataset
from distriflow_tpu.models import mnist_mlp
from distriflow_tpu.train.async_sgd import AsyncSGDTrainer


def _data(n=256, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 28, 28, 1).astype(np.float32)
    labels = rng.randint(0, 10, n)
    x[np.arange(n), 0, labels, 0] += 4.0
    y = np.eye(10, dtype=np.float32)[labels]
    return x, y


def _trainer(n=256, bs=32, epochs=1, seed=0, **kw):
    x, y = _data(n, seed)
    ds = DistributedDataset(x, y, {"batch_size": bs, "epochs": epochs})
    t = AsyncSGDTrainer(mnist_mlp(hidden=16), ds, learning_rate=0.05, **kw)
    t.init()
    return t, (x, y)


def test_single_worker_processes_all_batches(devices):
    t, _ = _trainer(n=128, bs=32, epochs=2)
    counters = t.train(num_workers=1)
    assert counters["applied"] == 8  # 4 batches x 2 epochs
    assert counters["rejected"] == 0
    assert t.version == 8


def test_multi_worker_all_batches_consumed(devices):
    t, (x, y) = _trainer(n=256, bs=16, epochs=2, hyperparams={"maximum_staleness": 100})
    counters = t.train(num_workers=8)
    # with a generous staleness bound nothing is rejected, every batch applies
    assert counters["applied"] == 32
    assert counters["rejected"] == 0


def test_staleness_zero_rejects_concurrent_updates(devices):
    # strict staleness-0 (the reference federated path's drop rule) with
    # 8 racing workers and the SSP admission gate OFF must reject most
    # overlapping updates — the legacy discard semantics stay available
    t, _ = _trainer(n=256, bs=16, epochs=2,
                    hyperparams={"maximum_staleness": 0},
                    admission_control=False)
    counters = t.train(num_workers=8)
    assert counters["applied"] + counters["rejected"] == 32
    assert counters["applied"] == t.version


def test_admission_control_prevents_all_rejections(devices):
    """Round-4 (verdict #3): the SSP admission window bounds staleness by
    construction — 8 racing workers under a tight bound discard NOTHING
    (r03 discarded 25% of computed work), and every batch still applies."""
    t, _ = _trainer(n=256, bs=16, epochs=2,
                    hyperparams={"maximum_staleness": 1})
    counters = t.train(num_workers=8)
    assert counters["rejected"] == 0
    assert counters["applied"] == 32
    assert t.version == 32


def test_phase_accounting_accumulates(devices):
    """phase_ms carries the per-phase breakdown (stage/snapshot/fit/
    submit/admission_wait — round 3) plus the device-queue drain the
    round-5 bench accounting sums against the wall clock."""
    t, _ = _trainer(n=128, bs=32, profile_phases=True)
    t.train(num_workers=2)
    assert set(t.phase_ms) == {"stage", "snapshot", "fit", "submit",
                               "admission_wait", "pipeline_wait", "drain"}
    assert t.phase_ms["fit"] > 0
    assert t.phase_ms["stage"] > 0
    assert t.phase_ms["drain"] >= 0


def test_stale_submit_rejected_manually(devices):
    t, (x, y) = _trainer(n=64, bs=32, hyperparams={"maximum_staleness": 1})
    params, v0 = t.snapshot()
    import jax

    grads = jax.tree.map(lambda p: np.ones_like(p) * 0.01, params)
    assert t.submit(grads, v0)          # staleness 0: ok
    assert t.submit(grads, v0)          # staleness 1: ok (bound is 1)
    assert not t.submit(grads, v0)      # staleness 2: rejected
    assert t.applied_updates == 2 and t.rejected_updates == 1


def test_future_version_raises(devices):
    t, _ = _trainer()
    params, v = t.snapshot()
    import jax

    grads = jax.tree.map(np.zeros_like, params)
    with pytest.raises(ValueError, match="future"):
        t.submit(grads, v + 5)


def test_staleness_decay_scales_update(devices):
    import jax

    t, _ = _trainer(hyperparams={"maximum_staleness": 4, "staleness_decay": 0.5})
    params0, v0 = t.snapshot()
    p0 = jax.tree.map(np.asarray, params0)
    ones = jax.tree.map(lambda p: np.ones_like(p), params0)
    t.submit(ones, v0)  # staleness 0: full lr (0.05)
    p1 = jax.tree.map(np.asarray, t.snapshot()[0])
    t.submit(ones, v0)  # staleness 1: decayed by 0.5
    p2 = jax.tree.map(np.asarray, t.snapshot()[0])
    d1 = jax.tree.leaves(jax.tree.map(lambda a, b: (a - b).ravel()[0], p0, p1))[0]
    d2 = jax.tree.leaves(jax.tree.map(lambda a, b: (a - b).ravel()[0], p1, p2))[0]
    assert d1 == pytest.approx(0.05, rel=1e-4)
    assert d2 == pytest.approx(0.025, rel=1e-4)


def test_async_training_learns(devices):
    t, (x, y) = _trainer(n=512, bs=32, epochs=6, hyperparams={"maximum_staleness": 8})
    before = t.evaluate(x, y)
    t.train(num_workers=4)
    after = t.evaluate(x, y)
    assert after[0] < before[0]
    assert after[1] > 0.8, after


def test_async_checkpoint_resume(devices, tmp_path):
    """Async trainer checkpoints under the apply lock and resumes with
    params + optimizer state + version intact."""
    t, dataset = _trainer(checkpoint_dir=str(tmp_path))
    t.train(num_workers=2)
    assert t.version > 0
    v = t.save()
    params_before = jax.device_get(t.params)

    t2, _ = _trainer(checkpoint_dir=str(tmp_path))
    assert t2.restore()
    assert t2.version == int(v)
    for a, b in zip(jax.tree.leaves(jax.device_get(t2.params)),
                    jax.tree.leaves(params_before)):
        np.testing.assert_array_equal(a, b)


def test_steps_per_upload_matches_superbatch(devices):
    """K-batches-per-upload uploads the MEAN gradient of K batches at one
    snapshot — exactly the gradient of the K*B super-batch. With one worker
    and SGD, params after one K-group upload equal params after one upload
    of the concatenated batch."""
    x, y = _data(128)
    ds_k = DistributedDataset(x, y, {"batch_size": 32, "epochs": 1})
    t_k = AsyncSGDTrainer(mnist_mlp(hidden=16), ds_k, learning_rate=0.05,
                          steps_per_upload=4)
    t_k.init(jax.random.PRNGKey(7))
    ds_1 = DistributedDataset(x, y, {"batch_size": 128, "epochs": 1})
    t_1 = AsyncSGDTrainer(mnist_mlp(hidden=16), ds_1, learning_rate=0.05)
    t_1.init(jax.random.PRNGKey(7))

    ck = t_k.train(num_workers=1)
    c1 = t_1.train(num_workers=1)
    assert ck == {"applied": 1, "rejected": 0, "version": 1}
    assert c1 == {"applied": 1, "rejected": 0, "version": 1}
    for a, b in zip(jax.tree.leaves(t_k.params), jax.tree.leaves(t_1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_steps_per_upload_ragged_tail(devices):
    """A group smaller than K (dataset tail) still uploads (per-batch
    fallback path); every batch is consumed exactly once."""
    t, _ = _trainer(n=6 * 32, bs=32, epochs=1, steps_per_upload=4)
    counters = t.train(num_workers=1)
    # 6 batches -> one group of 4, one tail group of 2 -> 2 uploads
    assert counters["applied"] == 2
    assert counters["version"] == 2


def test_steps_per_upload_trains(devices):
    t, (x, y) = _trainer(n=512, bs=32, epochs=3, steps_per_upload=4)
    before = t.evaluate(x, y)[0]
    t.train(num_workers=2)
    after = t.evaluate(x, y)[0]
    assert after < before


def test_steps_per_upload_validation():
    x, y = _data(64)
    ds = DistributedDataset(x, y, {"batch_size": 32, "epochs": 1})
    with pytest.raises(ValueError, match="steps_per_upload"):
        AsyncSGDTrainer(mnist_mlp(hidden=16), ds, steps_per_upload=0)


def test_stage_dataset_matches_host_path(devices):
    """stage_dataset=True (device-resident dataset, round-4) must be a
    pure data-path change: same batches, same updates, same final params
    as the host-streaming path."""
    import jax.numpy as jnp

    def run(staged):
        t, _ = _trainer(n=128, bs=32, epochs=2, stage_dataset=staged)
        if staged:
            t.pre_stage()
        t.train(num_workers=1)
        return t.snapshot()[0]

    a, b = run(False), run(True)
    for pa, pb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))


def test_stage_dataset_rejects_preprocess(devices):
    t, _ = _trainer(n=64, bs=32, stage_dataset=True)
    t.dataset.add_preprocess(lambda x, y: (x * 2, y))
    with pytest.raises(RuntimeError, match="preprocess"):
        t.worker_loop(0, max_steps=1)


def test_mfu_is_the_sync_trainers_rule(devices):
    """``AsyncSGDTrainer.mfu`` is flops / (t * peak) through the helper it
    shares with ``SyncTrainer.mfu``; a device kind in no peak table (the
    CPU's here) is an error, never a default."""
    t, _ = _trainer(n=64, bs=32)
    flops = t.cost_analysis(32)["flops"]
    assert flops > 0
    got = t.mfu(32, step_seconds=2.0, peak_flops_per_chip=flops)
    np.testing.assert_allclose(got, 0.5, rtol=1e-6)
    with pytest.raises(ValueError, match="unknown device kind"):
        t.mfu(32, step_seconds=2.0)
