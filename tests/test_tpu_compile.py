"""The decode kernels pass the TPU's own compiler at the serving cell's widths.

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
