"""Parallel layer tests on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distriflow_tpu.parallel import (
    allreduce_mean,
    axis_size,
    create_mesh,
    data_parallel_mesh,
    local_batch_size,
    pmean,
    ppermute_ring,
    replicate,
    shard_batch,
    shard_params,
    spec_for_path,
    tree_shardings,
)
from distriflow_tpu.parallel.sharding import TRANSFORMER_TP_RULES
from distriflow_tpu.utils.config import MeshConfig


def test_create_mesh_sizes(devices):
    mesh = create_mesh(MeshConfig(data=4, model=2), devices)
    assert axis_size(mesh, "data") == 4
    assert axis_size(mesh, "model") == 2
    assert axis_size(mesh, "seq") == 1


def test_create_mesh_size_mismatch(devices):
    with pytest.raises(ValueError):
        create_mesh(MeshConfig(data=3), devices)


def test_shard_batch_places_across_devices(devices):
    mesh = data_parallel_mesh(devices)
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    sharded = shard_batch(mesh, x)
    assert len(sharded.sharding.device_set) == 8
    np.testing.assert_array_equal(np.asarray(sharded), np.asarray(x))


def test_replicate(devices):
    mesh = data_parallel_mesh(devices)
    tree = {"w": jnp.ones((3, 3))}
    rep = replicate(mesh, tree)
    assert rep["w"].sharding.is_fully_replicated


def test_local_batch_size(devices):
    mesh = data_parallel_mesh(devices)
    assert local_batch_size(64, mesh) == 8
    with pytest.raises(ValueError):
        local_batch_size(65, mesh)


def test_allreduce_mean_matches_numpy(devices):
    mesh = data_parallel_mesh(devices)
    x = jnp.arange(8 * 3, dtype=jnp.float32).reshape(8, 3)
    sharded = shard_batch(mesh, x)
    out = allreduce_mean(mesh, sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).mean(0), rtol=1e-6)


def test_pmean_inside_shard_map(devices):
    mesh = data_parallel_mesh(devices)

    def f(x):
        return pmean(x, "data")

    x = jnp.arange(8.0).reshape(8, 1)
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P()))(x)
    np.testing.assert_allclose(np.asarray(out), 3.5)


def test_ppermute_ring_rotates(devices):
    mesh = data_parallel_mesh(devices)

    def f(x):
        return ppermute_ring(x, "data", mesh, shift=1)

    x = jnp.arange(8.0).reshape(8, 1)
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data")))(x)
    # device i's value moves to device i+1: output shard i holds value i-1
    np.testing.assert_array_equal(np.asarray(out).ravel(), np.roll(np.arange(8.0), 1))


def test_sharding_rules_resolution():
    assert spec_for_path("['layers_0']['attn']['q_proj']['kernel']", TRANSFORMER_TP_RULES) == P(None, "model")
    assert spec_for_path("['layers_0']['attn']['o_proj']['kernel']", TRANSFORMER_TP_RULES) == P("model", None)
    assert spec_for_path("['layers_0']['ln']['scale']", TRANSFORMER_TP_RULES) == P()


def test_shard_params_tp(devices):
    mesh = create_mesh(MeshConfig(data=4, model=2), devices)
    params = {"mlp": {"wi": {"kernel": jnp.ones((16, 32))}, "wo": {"kernel": jnp.ones((32, 16))}}}
    sharded = shard_params(params, mesh, TRANSFORMER_TP_RULES)
    # column-sharded wi: each device holds (16, 16); row-sharded wo: (16, 16)
    wi_shard = sharded["mlp"]["wi"]["kernel"].addressable_shards[0]
    wo_shard = sharded["mlp"]["wo"]["kernel"].addressable_shards[0]
    assert wi_shard.data.shape == (16, 16)
    assert wo_shard.data.shape == (16, 16)


def test_rank_clipping_scalar_params(devices):
    mesh = create_mesh(MeshConfig(data=4, model=2), devices)
    params = {"wi": {"kernel": jnp.ones((8, 8))}, "step": jnp.float32(0.0)}
    sharded = shard_params(params, mesh, TRANSFORMER_TP_RULES)
    assert sharded["step"].shape == ()
