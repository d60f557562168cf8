"""The decode kernels and the grouped expert matmul pass the TPU's own compiler
at their cells' widths, and the engine's programs alias the KV pools there.

Interpret mode says a kernel computes the right thing; it does not say that
Mosaic accepts it (an unaligned slice, too much VMEM, a scalar op the core
lacks). libtpu is installed here, so the kernels are compiled for a described
v5e with no chip attached: nothing runs, no time is read. The topology is
described inside a fixture, never at import: every xdist worker imports this
file, and only the one that runs it may load the library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import distriflow_tpu.ops as ops
from distriflow_tpu.models.generate import (
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    _split_pools,
    paged_cache,
)
from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
from distriflow_tpu.ops.expert_grouped import grouped_expert_terms
from distriflow_tpu.ops.flash_decode import flash_decode, flash_decode_paged

pytestmark = pytest.mark.kernels

B, H, D, PAGE, WIDTH, N_PAGES = 32, 16, 128, 128, 16, 128  # PERF.md §4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("layout", ["paged", "slab"])
def test_decode_kernel_compiles_for_v5e(one_chip, layout, kv):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    store = jnp.bfloat16 if kv == "bf16" else jnp.int8
    rows = (N_PAGES, PAGE) if layout == "paged" else (B, WIDTH * PAGE)
    pool = shape(rows + (H * D,), store)
    scales = [shape(rows + (H,), jnp.float32)] * 2 if kv == "int8" else []
    q, lens = shape((B, H, D), jnp.bfloat16), shape((B,), jnp.int32)

    if layout == "paged":
        def call(q, k, v, lens, table, *s):
            return flash_decode_paged(q, k, v, table, lens, *s,
                                      interpret=False)
        args = (q, pool, pool, lens, shape((B, WIDTH), jnp.int32), *scales)
    else:
        def call(q, k, v, lens, *s):
            return flash_decode(q, k, v, lens, *s, interpret=False)
        args = (q, pool, pool, lens, *scales)
    compiled = jax.jit(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_grouped_expert_kernel_compiles_for_v5e(one_chip):
    """At granite-4.0-h-small's widths, a decode step's 32 rows: whole-expert
    blocks, double-buffered, are 37.7 MB of VMEM, over the default limit."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    t, d, f, count = 32, 4096, 768, 36
    compiled = jax.jit(
        lambda *a: grouped_expert_terms(*a, interpret=False)).lower(
            shape((t, d), jnp.bfloat16), shape((t, count), jnp.float32),
            shape((count, d, f), jnp.bfloat16),
            shape((count, d, f), jnp.bfloat16),
            shape((count, f, d), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["decode", "insert"])
def test_engine_program_aliases_the_pools_on_v5e(one_chip, monkeypatch,
                                                 program):
    """The serving cell's decode chunk and page scatter, two layers deep:
    the TPU compiler aliases every byte of the donated pools to an output,
    so neither program holds the pool twice (PERF.md §4)."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = TransformerConfig(
        vocab_size=512, d_model=H * D, n_heads=H, n_layers=2, d_ff=H * D,
        max_seq=WIDTH * PAGE, dtype=jnp.bfloat16, use_flash_attention=True,
        use_flash_decode=True)

    def placed(tree):
        return jax.tree.map(lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=one_chip), tree)

    def shape(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = placed(jax.eval_shape(
        transformer_lm(cfg, example_seq=PAGE).init, jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        lambda p: paged_cache(cfg, p, B, PAGE, N_PAGES), params))
    pool_bytes = sum(v.size * v.dtype.itemsize
                     for v in jax.tree.leaves(_split_pools(cache)[0]))
    if program == "decode":
        compiled = _build_slot_fns(cfg, 8, False)[2].lower(
            params, cache, shape((B,)), shape((B,), jnp.bool_),
            shape((B,), jnp.float32), shape((B,)), shape((B,), jnp.float32),
            shape((B,)), shape((B,))).compile()
        assert "tpu_custom_call" in compiled.as_text()
    else:
        rows = jax.eval_shape(_build_prefill(cfg)[0], params,
                              jax.ShapeDtypeStruct((2, 3 * PAGE), jnp.int32))[1]
        compiled = _build_paged_fns(cfg, PAGE)[0].lower(
            cache, placed(rows), shape((2,)), shape(()), shape(()),
            shape((B, WIDTH + 1))).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
