"""FedAvg engine tests: shard_map local epochs + weight pmean."""

import jax
import numpy as np
import pytest

from distriflow_tpu.models import mnist_mlp
from distriflow_tpu.parallel import data_parallel_mesh
from distriflow_tpu.train.federated import FederatedAveragingTrainer
from distriflow_tpu.train.sync import SyncTrainer


def _data(n=1024, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 28, 28, 1).astype(np.float32)
    labels = rng.randint(0, 10, n)
    x[np.arange(n), 0, labels, 0] += 4.0
    y = np.eye(10, dtype=np.float32)[labels]
    return x, y


def test_fedavg_learns(devices):
    mesh = data_parallel_mesh(devices)
    t = FederatedAveragingTrainer(
        mnist_mlp(hidden=16), mesh=mesh, local_steps=4, local_batch_size=16,
        learning_rate=0.15,
    )
    t.init(jax.random.PRNGKey(0))
    x, y = _data(2048)
    before = t.evaluate(x, y)
    rng = np.random.RandomState(0)
    for _ in range(12):
        xs, ys = t.pack_round_data(x, y, rng)
        t.round(xs, ys)
    after = t.evaluate(x, y)
    assert after[0] < before[0]
    assert after[1] > 0.7, after


def test_fedavg_params_stay_in_sync(devices):
    """After the round's pmean, every worker holds identical weights."""
    mesh = data_parallel_mesh(devices)
    t = FederatedAveragingTrainer(
        mnist_mlp(hidden=8), mesh=mesh, local_steps=2, local_batch_size=8
    )
    t.init()
    x, y = _data(512)
    xs, ys = t.pack_round_data(x, y)
    t.round(xs, ys)
    for leaf in jax.tree.leaves(t.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)


def test_fedavg_local_steps_1_equals_sync_sgd(devices):
    """K=1 FedAvg with SGD == one sync-SGD step on the same global batch:
    mean of one-step weight deltas is a step along the mean gradient."""
    mesh = data_parallel_mesh(devices)
    x, y = _data(64, seed=3)

    fed = FederatedAveragingTrainer(
        mnist_mlp(hidden=8), mesh=mesh, local_steps=1, local_batch_size=8,
        learning_rate=0.1,
    )
    fed.init(jax.random.PRNGKey(5))
    xs = x.reshape(8, 1, 8, 28, 28, 1)
    ys = y.reshape(8, 1, 8, 10)
    fed.round(xs, ys)

    sync = SyncTrainer(mnist_mlp(hidden=8), mesh=mesh, learning_rate=0.1)
    sync.init(jax.random.PRNGKey(5))
    sync.step((x, y))

    for a, b in zip(jax.tree.leaves(fed.params), jax.tree.leaves(sync.get_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_round_shape_validation(devices):
    mesh = data_parallel_mesh(devices)
    t = FederatedAveragingTrainer(mnist_mlp(hidden=8), mesh=mesh, local_steps=2, local_batch_size=8)
    t.init()
    with pytest.raises(ValueError, match="round data"):
        t.round(np.zeros((4, 2, 8, 28, 28, 1), np.float32), np.zeros((4, 2, 8, 10), np.float32))


def test_pack_round_data_insufficient(devices):
    mesh = data_parallel_mesh(devices)
    t = FederatedAveragingTrainer(mnist_mlp(hidden=8), mesh=mesh, local_steps=4, local_batch_size=32)
    x, y = _data(64)
    with pytest.raises(ValueError, match="at least"):
        t.pack_round_data(x, y)


def test_callbacks(devices):
    mesh = data_parallel_mesh(devices)
    t = FederatedAveragingTrainer(mnist_mlp(hidden=8), mesh=mesh, local_steps=1, local_batch_size=8)
    t.init()
    rounds = []
    t.callbacks.register("round", rounds.append)
    x, y = _data(64)
    xs, ys = t.pack_round_data(x, y)
    t.round(xs, ys)
    assert rounds == [1]


def test_fedavg_checkpoint_resume(devices, tmp_path):
    """FedAvg rounds checkpoint (params + round counter) and resume."""
    from distriflow_tpu.models import mnist_mlp

    mesh = data_parallel_mesh(devices)

    def make():
        t = FederatedAveragingTrainer(
            mnist_mlp(hidden=8), mesh=mesh, local_steps=2,
            local_batch_size=4, learning_rate=0.05,
            checkpoint_dir=str(tmp_path), save_every=1)
        t.init(jax.random.PRNGKey(0))
        return t

    t1 = make()
    rng = np.random.RandomState(0)
    x, y = t1.pack_round_data(
        rng.rand(256, 28, 28, 1).astype(np.float32),
        np.eye(10, dtype=np.float32)[rng.randint(0, 10, 256)])
    t1.round(x, y)
    t1.round(x, y)
    before = jax.device_get(t1.params)

    t2 = make()
    assert t2.restore()
    assert t2.round_index == 2
    for a, b in zip(jax.tree.leaves(jax.device_get(t2.params)),
                    jax.tree.leaves(before)):
        np.testing.assert_array_equal(a, b)
    assert np.isfinite(t2.round(x, y))


def test_fedavg_round_with_the_tpu_auto_kernels(devices):
    """The LM with flash attention and the fused CE — what the auto choices
    pick on a TPU — inside FedAvg's shard_map body. On four real chips
    (PR 21) this failed twice over: the model wrapped the attention kernel
    in a second shard_map over the concrete mesh, and the kernels' outputs
    carry no varying-axes type for check_vma. Interpret mode here, same
    trace."""
    import jax.numpy as jnp

    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm

    mesh = data_parallel_mesh(devices[:2])
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_seq=16,
        dtype=jnp.float32, use_flash_attention=True,
        loss="fused_sparse_softmax_cross_entropy")
    t = FederatedAveragingTrainer(
        transformer_lm(cfg, mesh=mesh, example_seq=16), mesh=mesh,
        local_steps=2, local_batch_size=2, learning_rate=1e-2)
    t.init(jax.random.PRNGKey(0))
    tokens = np.random.RandomState(0).randint(0, 64, (2, 2, 2, 17))
    xs, ys = tokens[..., :-1].astype(np.int32), tokens[..., 1:].astype(np.int32)
    losses = [t.round(xs, ys) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
