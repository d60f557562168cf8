"""The seam between the decoding paths and a model family
(``models/generate.py::DecodeFamily``): ``TransformerConfig`` declares
nothing and gets today's module, pool leaves, cache tree and program names.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.models.generate import (
    _POOL_LEAVES,
    _build_paged_fns,
    _build_prefill,
    _build_slot_fns,
    _decode_module,
    _split_pools,
    decode_family,
    paged_cache,
    slot_cache,
)
from distriflow_tpu.models.transformer import TransformerConfig, TransformerLM

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_seq=32, dtype=jnp.float32,
                        use_flash_attention=False, use_flash_decode=False)


@pytest.fixture(scope="module")
def params():
    variables = TransformerLM(CFG, mesh=None).init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    return {"params": variables["params"]}


def _tree(cache):
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    return sorted(("/".join(str(k.key) for k in path), leaf.shape,
                   str(leaf.dtype)) for path, leaf in flat)


def test_transformer_config_declares_no_family():
    assert not hasattr(CFG, "decode_family")
    family = decode_family(CFG)
    assert family.pool_leaves == _POOL_LEAVES == (
        "cached_k", "cached_v", "k_scale", "v_scale")
    module = _decode_module(CFG)
    assert isinstance(module, TransformerLM) and module.decode


@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_transformer_cache_tree_unchanged(params, layout):
    if layout == "slab":
        cache = slot_cache(CFG, params, 3)
        per_layer = [("cache_index", (3,), "int32"),
                     ("cached_k", (3, 32, 32), "float32"),
                     ("cached_v", (3, 32, 32), "float32")]
    else:
        cache = paged_cache(CFG, params, 3, page_size=8, n_pages=5)
        per_layer = [("cache_index", (3,), "int32"),
                     ("cached_k", (5, 8, 32), "float32"),
                     ("cached_v", (5, 8, 32), "float32"),
                     ("page_table", (3, 5), "int32")]
    want = sorted((f"layers_{i}/attn/{name}", shape, dtype)
                  for i in range(2) for name, shape, dtype in per_layer)
    assert _tree(cache) == want
    pools, rest = _split_pools(cache)
    assert {p.split("/")[-1] for p, _, _ in _tree(pools)} == {
        "cached_k", "cached_v"}
    assert {p.split("/")[-1] for p, _, _ in _tree(rest)} == (
        {"cache_index", "page_table"} if layout == "paged"
        else {"cache_index"})


@pytest.mark.parametrize("program", ["decode", "insert", "paged_insert",
                                     "gather_rows", "prefill", "extend"])
def test_transformer_program_names_unchanged(params, program):
    """The name is the compile cache's key and what a trace shows."""
    cache = paged_cache(CFG, params, 3, page_size=8, n_pages=5)
    insert, _pick, decode = _build_slot_fns(CFG, 2, False)
    paged_insert, gather_rows = _build_paged_fns(CFG, 8)
    prefill, extend = _build_prefill(CFG)
    tok = np.zeros((3,), np.int32)
    row = np.zeros((1, 8), np.int32)
    _, row_cache = prefill(params, row)
    tables = np.full((3, 5), 5, np.int32)
    lowered = {
        "decode": lambda: decode.lower(
            params, cache, tok, np.ones((3,), bool),
            np.zeros((3,), np.float32), tok, np.ones((3,), np.float32),
            tok, tok - 1),
        "insert": lambda: insert.lower(
            slot_cache(CFG, params, 3), row_cache,
            np.zeros((1,), np.int32), np.int32(8)),
        "paged_insert": lambda: paged_insert.lower(
            cache, row_cache, np.zeros((1,), np.int32), np.int32(8),
            np.int32(0), tables),
        "gather_rows": lambda: gather_rows.lower(
            cache, tables[:1], np.int32(8)),
        "prefill": lambda: prefill.lower(params, row),
        "extend": lambda: extend.lower(params, row_cache, row[:, :1]),
    }[program]()
    name = "insert" if program == "paged_insert" else program
    assert f"module @jit_{name} " in lowered.as_text()[:200]
