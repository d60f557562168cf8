"""flash-decode kernel: packed-layout math, tiling gate, VMEM model.

The kernel itself is exercised end-to-end (vs the XLA decode path) in
tests/test_generate.py; these tests pin the pieces that failed silently
in round 4 — tile selection, the VMEM budget gate, and the auto-enable
fallback for shapes the kernel cannot tile (round-5 review finding: a
wide-head config passed the old gate and then raised mid-trace).
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.ops.flash_decode import (
    BLOCK_K,
    MIN_BLOCK_K,
    NEG_INF,
    VMEM_LIMIT_BYTES,
    _bd_mask,
    _vmem_estimate_bytes,
    _warned_gated,
    flash_decode,
    flash_decode_paged,
    pick_block_k,
    supports_seq,
)


def _dense_reference(q, k, v, valid_len):
    """f32 dense decode attention on packed [B, S, H*D] caches."""
    b, h, d = q.shape
    s = k.shape[1]
    kf = np.asarray(k, np.float32).reshape(b, s, h, d).transpose(0, 2, 1, 3)
    vf = np.asarray(v, np.float32).reshape(b, s, h, d).transpose(0, 2, 1, 3)
    qf = np.asarray(q, np.float32)
    scores = np.einsum("bhd,bhsd->bhs", qf, kf) / np.sqrt(d)
    scores[:, :, valid_len:] = -1e30
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhs,bhsd->bhd", p, vf)


def test_kernel_matches_dense_reference_bf16():
    b, h, s, d = 2, 8, 256, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h * d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h * d), jnp.bfloat16)
    out = flash_decode(q, k, v, jnp.int32(s), interpret=True)
    ref = _dense_reference(q, k, v, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=0, atol=3e-2)


def test_kernel_masks_past_valid_len():
    """Positions >= valid_len (the cache tail past the write index) must
    not contribute — fill them with huge values and compare against the
    reference truncated at valid_len."""
    b, h, s, d = 1, 8, 128, 64
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
    k = np.asarray(rng.randn(b, s, h * d), np.float32)
    v = np.asarray(rng.randn(b, s, h * d), np.float32)
    k[:, 77:] = 1e4  # poison the tail
    v[:, 77:] = -1e4
    kb, vb = jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)
    out = flash_decode(q, kb, vb, jnp.int32(77), interpret=True)
    ref = _dense_reference(q, kb, vb, 77)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref, rtol=0, atol=3e-2)


def test_kernel_int8_scales_fold_correctly():
    b, h, s, d = 2, 8, 256, 64
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, h, d), jnp.bfloat16)
    k8 = jnp.asarray(rng.randint(-127, 128, (b, s, h * d)), jnp.int8)
    v8 = jnp.asarray(rng.randint(-127, 128, (b, s, h * d)), jnp.int8)
    ks = jnp.asarray(rng.rand(b, s, h) * 0.01 + 1e-3, jnp.float32)
    vs = jnp.asarray(rng.rand(b, s, h) * 0.01 + 1e-3, jnp.float32)
    out = flash_decode(q, k8, v8, jnp.int32(s), k_scale=ks, v_scale=vs,
                       interpret=True)
    kf = (np.asarray(k8, np.float32).reshape(b, s, h, d)
          * np.asarray(ks)[..., None]).reshape(b, s, h * d)
    vf = (np.asarray(v8, np.float32).reshape(b, s, h, d)
          * np.asarray(vs)[..., None]).reshape(b, s, h * d)
    ref = _dense_reference(q, kf, vf, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), ref,
        rtol=0, atol=3e-2 * np.abs(ref).max())


def test_pick_block_k_divisor_and_vmem_rules():
    # whole-sequence tile when it fits (Mosaic allows block == array dim)
    assert pick_block_k(1024) == 1024
    assert pick_block_k(1100) == 1100  # crooked but <= BLOCK_K: one tile
    # beyond one tile: largest sublane-aligned divisor
    assert pick_block_k(4096) == BLOCK_K
    assert pick_block_k(1536 * 2) == 1536
    # no aligned divisor above one tile -> unsupported (4100 = 2^2*5^2*41)
    assert pick_block_k(4100) is None
    # wide heads shrink the tile to fit scoped VMEM instead of crashing
    bk = pick_block_k(2048, hd=2048)
    assert bk is not None and bk < 2048
    assert _vmem_estimate_bytes(bk, 2048, 2) <= VMEM_LIMIT_BYTES
    # f32 caches pay 2x the tile bytes AND the bf16 cast copies — the
    # round-5 review caught the gate assuming bf16 itemsize for all
    # non-quant caches, which left the round-4 Mosaic crash reachable
    bk32 = pick_block_k(2048, hd=2048, kv_item=4)
    assert bk32 is not None and bk32 < bk
    assert _vmem_estimate_bytes(bk32, 2048, 4) <= VMEM_LIMIT_BYTES
    assert _vmem_estimate_bytes(bk, 2048, 4) > VMEM_LIMIT_BYTES
    assert supports_seq(2048, hd=2048)
    assert not supports_seq(4100)


def test_min_tile_floor_gates_sliver_shapes():
    """2056 = 2^3 x 257: the only sublane-aligned divisor above one tile
    is 8 — 257 grid steps of sliver DMAs, the kernel's worst per-step
    overhead regime. The floor gates it to the XLA fallback, counted in
    telemetry and warned once per shape."""
    from distriflow_tpu.obs import Telemetry, set_telemetry

    assert MIN_BLOCK_K >= 8 and MIN_BLOCK_K % 8 == 0
    assert pick_block_k(2056) is None
    # one-tile caches are exempt: the floor only guards the grid regime
    assert pick_block_k(136) == 136
    tel = Telemetry()
    prev = set_telemetry(tel)
    _warned_gated.discard((2056, 512, 2))  # test-order independence
    try:
        with pytest.warns(UserWarning, match="gated off"):
            assert not supports_seq(2056)
        assert tel.counter_value("ops_flash_decode_gated_total") == 1
        # second gate counts again but does NOT re-warn
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert not supports_seq(2056)
        assert tel.counter_value("ops_flash_decode_gated_total") == 2
    finally:
        set_telemetry(prev)


def test_explicit_oversized_block_k_raises_python_error():
    """A tile the VMEM model rejects must fail with a remedy BEFORE
    reaching the Mosaic compiler (round-4: a 20 MB > 16 MB compiler
    internal only surfaced on real hardware)."""
    b, h, s, d = 1, 16, 2048, 128  # hd = 2048: one 2048-tile needs ~37 MB
    q = jnp.zeros((b, h, d), jnp.bfloat16)
    k = jnp.zeros((b, s, h * d), jnp.bfloat16)
    v = jnp.zeros((b, s, h * d), jnp.bfloat16)
    with pytest.raises(ValueError, match="VMEM"):
        flash_decode(q, k, v, jnp.int32(s), block_k=2048, interpret=False)


def test_wide_head_config_auto_tiles_in_model():
    """The round-5 review scenario: head_dim 128 x 16 heads (packed width
    2048, f32 cache) at a cache length where the whole-sequence tile
    busts VMEM — the kernel must decode with a genuinely shrunken tile,
    not raise mid-trace, not silently fall back."""
    from distriflow_tpu.models.generate import generate
    from distriflow_tpu.models.transformer import (
        TransformerConfig,
        transformer_lm,
    )

    cfg = TransformerConfig(
        vocab_size=128, d_model=2048, n_heads=16, n_layers=1, d_ff=128,
        max_seq=2048, dtype=jnp.float32, use_flash_attention=False,
        use_flash_decode=True)
    # the shape this test exists for: the tile REALLY shrinks
    bk = pick_block_k(2048, hd=2048, kv_item=4)
    assert bk is not None and bk < 2048, bk
    params = transformer_lm(cfg, example_seq=8).init(jax.random.PRNGKey(0))
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = generate(cfg, params, prompt, 4)
    assert out.shape == (1, 7)
    ref = generate(dataclasses.replace(cfg, use_flash_decode=False),
                   params, prompt, 4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- work follows the row's live pages (PR 26) -------------------------------
#
# The kernels run a tile's score matmul and online-softmax update only
# where the tile holds a position below the row's length, and their K/V
# index maps stop at the row's last live tile. The oracle below is the
# (row, tile) step as it stood before: every tile of the grid is fetched
# and computed, and only then masked by the length. For a row with any
# live position the two must agree bit for bit.

HEADS, HEAD_DIM, TILE, WIDTH = 4, 32, 128, 3  # hd 128; a full row is 384
FULL = WIDTH * TILE
EDGES = (1, TILE - 1, TILE, TILE + 1, FULL)


def _oracle_step(quant, n_kv):
    from jax.experimental import pallas as pl

    h = HEADS

    def kernel(tab_ref, len_ref, qbd_ref, *refs):
        if quant:
            qs_ref, k_ref, ks_ref, v_ref, vs_ref = refs[:5]
        else:
            k_ref, v_ref = refs[:2]
        o_ref, m_ref, l_ref, acc_ref = refs[-4:]
        j = pl.program_id(1)
        row_len = len_ref[pl.program_id(0)]

        @pl.when(j == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

        scale = 1.0 / (HEAD_DIM ** 0.5)
        if quant:
            s_i32 = jax.lax.dot_general(
                k_ref[0], qbd_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            s2 = s_i32.astype(jnp.float32) * ks_ref[0] * (qs_ref[0] * scale)
        else:
            s2 = jax.lax.dot_general(
                k_ref[0].astype(jnp.bfloat16), qbd_ref[0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        v_tile = v_ref[0].astype(jnp.bfloat16)
        mask = _bd_mask(h, v_tile.shape[-1])
        row = j * TILE + jax.lax.broadcasted_iota(jnp.int32, s2.shape, 0)
        s2 = jnp.where(row < row_len, s2, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s2, axis=0, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s2 - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=0, keepdims=True)
        pw = p * vs_ref[0] if quant else p
        c = jax.lax.dot_general(
            pw.astype(jnp.bfloat16), v_tile, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        pv = jnp.sum(c * mask, axis=0, keepdims=True)
        corr_flat = jax.lax.dot_general(
            corr, mask, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr_flat + pv
        m_ref[:] = m_new

        @pl.when(j == n_kv - 1)
        def _finalize():
            inv = 1.0 / jnp.maximum(l_ref[:], 1e-30)
            inv_flat = jax.lax.dot_general(
                inv, mask, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0] = (acc_ref[:] * inv_flat).astype(o_ref.dtype)

    return kernel


def _oracle_paged(q, k, v, table, lens, k_scale=None, v_scale=None):
    """``flash_decode_paged`` before the gate, interpret mode: sentinels
    clamped to the last page, every table column visited."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    n_pages, ps, hd = k.shape
    n_kv = table.shape[1]
    quant = k_scale is not None
    eye = jnp.eye(h, dtype=jnp.float32)
    qf32 = q.astype(jnp.float32)
    arrays = []
    if quant:
        qs = jnp.maximum(
            jnp.max(jnp.abs(qf32), axis=-1, keepdims=True) / 127.0, 1e-20)
        q8 = jnp.clip(jnp.round(qf32 / qs), -127, 127)
        arrays.append(jnp.einsum("bhd,hg->bhdg", q8, eye).reshape(
            b, hd, h).astype(jnp.int8))
        arrays.append(qs[:, :, 0][:, None, :])
    else:
        arrays.append(jnp.einsum("bhd,hg->bhdg", qf32, eye).reshape(
            b, hd, h).astype(jnp.bfloat16))
    row_spec = lambda bi, j, tab, lens: (bi, 0, 0)  # noqa: E731
    page_spec = lambda bi, j, tab, lens: (tab[bi, j], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, hd, h), row_spec)]
    if quant:
        in_specs.append(pl.BlockSpec((1, 1, h), row_spec))
    for pool, scale in ((k, k_scale), (v, v_scale)):
        in_specs.append(pl.BlockSpec((1, ps, hd), page_spec))
        arrays.append(pool)
        if quant:
            in_specs.append(pl.BlockSpec((1, ps, h), page_spec))
            arrays.append(scale)
    out = pl.pallas_call(
        _oracle_step(quant, n_kv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n_kv), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, hd), row_spec),
            scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                            pltpu.VMEM((1, h), jnp.float32),
                            pltpu.VMEM((1, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        interpret=True,
    )(jnp.minimum(jnp.asarray(table, jnp.int32), n_pages - 1),
      jnp.asarray(lens, jnp.int32), *arrays)
    return out.reshape(b, h, d)


def _case(kv, b, seed, n_pages=None):
    """q and K/V (+ scales for int8) as page pools [n_pages, TILE, hd]."""
    n_pages = n_pages or b * WIDTH + 1
    hd = HEADS * HEAD_DIM
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, HEADS, HEAD_DIM), jnp.bfloat16)
    if kv == "int8":
        pools = [jnp.asarray(rng.randint(-127, 128, (n_pages, TILE, hd)),
                             jnp.int8) for _ in range(2)]
        scales = [jnp.asarray(rng.rand(n_pages, TILE, HEADS) * 0.01 + 1e-3,
                              jnp.float32) for _ in range(2)]
    else:
        pools = [jnp.asarray(rng.randn(n_pages, TILE, hd), jnp.bfloat16)
                 for _ in range(2)]
        scales = [None, None]
    return q, pools, scales


def _run(layout, q, pools, scales, table, lens, oracle=False):
    """One call of the kernel under test (or of the oracle). The slab
    layout is the paged one with row ``bi``'s tiles at pages
    ``bi*WIDTH ..``: the same tiles in the same order."""
    table = np.asarray(table, np.int32)
    lens = jnp.asarray(lens, jnp.int32)
    ks, vs = scales
    if oracle:
        return _oracle_paged(q, pools[0], pools[1], table, lens, ks, vs)
    if layout == "paged":
        return flash_decode_paged(q, pools[0], pools[1], jnp.asarray(table),
                                  lens, k_scale=ks, v_scale=vs,
                                  interpret=True)
    b = q.shape[0]
    assert (table == np.arange(b * WIDTH).reshape(b, WIDTH)).all()

    def slab(a):
        return None if a is None else a[:b * WIDTH].reshape(
            b, FULL, a.shape[-1])

    return flash_decode(q, slab(pools[0]), slab(pools[1]), lens,
                        k_scale=slab(ks), v_scale=slab(vs), block_k=TILE,
                        interpret=True)


def _dense_rows(q, pools, scales, table, lens):
    """f32 dense reference, row by row through the table."""
    out = []
    for row, n in enumerate(lens):
        kd, vd = (np.asarray(p, np.float32)[table[row]].reshape(1, -1, p.shape[-1])
                  for p in pools)
        if scales[0] is not None:
            ksd, vsd = (np.asarray(s)[table[row]].reshape(1, -1, HEADS)
                        for s in scales)
            kd = (kd.reshape(1, -1, HEADS, HEAD_DIM) * ksd[..., None]).reshape(kd.shape)
            vd = (vd.reshape(1, -1, HEADS, HEAD_DIM) * vsd[..., None]).reshape(vd.shape)
        out.append(_dense_reference(q[row:row + 1], kd, vd, int(n))[0])
    return np.stack(out)


def _bits(x):
    return np.asarray(x).view(np.uint16)


LAYOUTS_KV = [(layout, kv) for layout in ("paged", "slab")
              for kv in ("bf16", "int8")]


@pytest.mark.parametrize("length", EDGES)
@pytest.mark.parametrize("layout,kv", LAYOUTS_KV)
def test_ragged_lengths_match_dense_reference(layout, kv, length):
    """A row at and around a page edge beside a full row: the gate and the
    clamped index maps change which tiles are touched, never the result."""
    q, pools, scales = _case(kv, 2, seed=length)
    table = np.arange(2 * WIDTH).reshape(2, WIDTH)
    lens = np.array([length, FULL])
    out = np.asarray(_run(layout, q, pools, scales, table, lens), np.float32)
    ref = _dense_rows(q, pools, scales, table, lens)
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=3e-2 * max(np.abs(ref).max(), 1.0))


@pytest.mark.parametrize("layout,kv", LAYOUTS_KV)
def test_live_rows_bit_identical_to_unconditional_update(layout, kv):
    lens = np.array(EDGES + (200, 300))
    b = len(lens)
    q, pools, scales = _case(kv, b, seed=26)
    table = np.arange(b * WIDTH).reshape(b, WIDTH)
    got = _run(layout, q, pools, scales, table, lens)
    want = _run(layout, q, pools, scales, table, lens, oracle=True)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def _poison(pools, scales, pages):
    """NaN in ``pages``: in the values of a float pool, in the scales of an
    int8 one."""
    idx = np.asarray(pages)
    if scales[0] is None:
        return [p.at[idx].set(jnp.nan) for p in pools], scales
    return pools, [s.at[idx].set(jnp.nan) for s in scales]


@pytest.mark.parametrize("layout,kv", LAYOUTS_KV)
def test_nan_in_dead_tiles_never_reaches_a_live_row(layout, kv):
    """Pages a row has reserved and not yet written, and the page the
    sentinels clamp to, may hold anything: before the gate a NaN there came
    through ``0 * NaN`` in the p.V product."""
    lens = np.array([100, 130, 384])
    q, pools, scales = _case(kv, 3, seed=7)
    n_pages = pools[0].shape[0]
    table = np.arange(3 * WIDTH).reshape(3, WIDTH)
    dead = [1, 2, 5, n_pages - 1]  # row 0's pages 1-2, row 1's page 2
    if layout == "paged":
        table[0, 2] = n_pages  # a sentinel tail beside a reserved page
    clean = _run(layout, q, pools, scales, table, lens)
    bad_pools, bad_scales = _poison(pools, scales, dead)
    got = _run(layout, q, bad_pools, bad_scales, table, lens)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(_bits(got), _bits(clean))
    was = np.asarray(_run(layout, q, bad_pools, bad_scales, table, lens,
                          oracle=True), np.float32)
    assert np.isnan(was[:2]).any() and np.isfinite(was[2]).all()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_all_sentinel_row_with_a_stale_length_gives_zeros(kv):
    """A retired slot: its table row is all sentinels while its length goes
    on growing. It costs nothing and writes zeros; its neighbours are
    untouched."""
    q, pools, scales = _case(kv, 3, seed=3, n_pages=8)
    n_pages = pools[0].shape[0]
    table = np.array([[0, 1, 2], [n_pages] * WIDTH, [3, 4, n_pages]])
    lens = np.array([FULL, 1900, 200])
    bad_pools, bad_scales = _poison(pools, scales, [n_pages - 1])
    got = _run("paged", q, bad_pools, bad_scales, table, lens)
    assert not _bits(got[1]).any()
    live = np.array([0, 2])
    ref = _dense_rows(q[live], pools, scales,
                      np.minimum(table[live], n_pages - 1), lens[live])
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], ref, rtol=0,
                               atol=3e-2 * max(np.abs(ref).max(), 1.0))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_first_sentinel_caps_the_row(kv):
    """The table, not the length, says which pages exist: a sentinel before
    ``ceil(len / page)`` ends the row there, whatever follows it."""
    q, pools, scales = _case(kv, 2, seed=11, n_pages=8)
    n_pages = pools[0].shape[0]
    lens = np.array([300, 300])
    holed = np.array([[0, n_pages, 2], [3, 4, 5]])
    capped = np.array([[0, n_pages, n_pages], [3, 4, 5]])
    got = _run("paged", q, pools, scales, holed, lens)
    want = _run("paged", q, pools, scales, capped, np.array([TILE, 300]))
    np.testing.assert_array_equal(_bits(got), _bits(want))
    ref = _dense_rows(q, pools, scales, np.minimum(capped, n_pages - 1),
                      np.array([TILE, 300]))
    np.testing.assert_allclose(np.asarray(got, np.float32), ref, rtol=0,
                               atol=3e-2 * max(np.abs(ref).max(), 1.0))
