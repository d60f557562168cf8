"""Pallas op-layer tests (interpret mode on the CPU mesh).

Oracles: ``dense_attention`` (plain softmax attention) for the flash kernel;
``optax.softmax_cross_entropy`` for the fused CE kernel. Both values and
gradients must match.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distriflow_tpu.models.losses import get_loss
from distriflow_tpu.ops import flash_attention, fused_softmax_cross_entropy
from distriflow_tpu.ops.fused_ce import fused_softmax_cross_entropy_per_example
from distriflow_tpu.parallel.ring_attention import dense_attention


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_dense(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, 32, 16, True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_odd_sizes():
    # S=48 forces non-128 blocks; D=8 is sub-lane — interpret handles both
    q, k, v = _qkv(b=1, h=1, s=48, d=8)
    out = flash_attention(q, k, v, True, 128, 128, True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grad_matches_dense():
    q, k, v = _qkv(b=1, h=2, s=32, d=8)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 16, 16, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


@pytest.mark.parametrize("causal,bq,bk", [(False, 16, 16), (True, 16, 32), (True, 32, 16)])
def test_flash_attention_grad_noncausal_and_uneven_blocks(causal, bq, bk):
    """Backward kernels: non-causal path and asymmetric q/k tiles (the
    causal tile-skip predicates differ per kernel and must stay exact)."""
    q, k, v = _qkv(b=1, h=2, s=64, d=8, seed=3)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, bq, bk, True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_flash_attention_bf16():
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv(s=32, d=8))
    out = flash_attention(q, k, v, True, 16, 16, True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


# -- fused cross-entropy -----------------------------------------------------


def test_fused_ce_matches_optax():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(37, 50).astype(np.float32))  # non-divisible N
    labels = rng.randint(0, 50, 37)
    onehot = jnp.eye(50, dtype=jnp.float32)[labels]
    got = fused_softmax_cross_entropy(logits, onehot)
    want = jnp.mean(optax.softmax_cross_entropy(logits, onehot))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_fused_ce_weighted_and_3d():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(4, 6, 11).astype(np.float32))
    labels = rng.randint(0, 11, (4, 6))
    onehot = jnp.eye(11, dtype=jnp.float32)[labels]
    w = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    got = fused_softmax_cross_entropy(logits, onehot, w)
    per = optax.softmax_cross_entropy(logits, onehot)  # [4, 6]
    want = jnp.sum(per * w[:, None]) / jnp.sum(w * 6)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_fused_ce_grad_matches_optax():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(8, 16).astype(np.float32))
    onehot = jnp.eye(16, dtype=jnp.float32)[rng.randint(0, 16, 8)]

    g_fused = jax.grad(lambda l: fused_softmax_cross_entropy(l, onehot))(logits)
    g_ref = jax.grad(lambda l: jnp.mean(optax.softmax_cross_entropy(l, onehot)))(logits)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref), atol=1e-6)


def test_fused_ce_registered_in_registry():
    fn = get_loss("fused_softmax_cross_entropy")
    logits = jnp.asarray(np.random.RandomState(3).randn(5, 7).astype(np.float32))
    onehot = jnp.eye(7, dtype=jnp.float32)[np.arange(5)]
    np.testing.assert_allclose(
        float(fn(logits, onehot)),
        float(jnp.mean(optax.softmax_cross_entropy(logits, onehot))),
        rtol=1e-6,
    )


def test_sparse_ce_matches_onehot():
    """Integer-label registry loss == one-hot loss on the same rows."""
    from distriflow_tpu.models.losses import (
        softmax_cross_entropy,
        sparse_softmax_cross_entropy,
    )

    rng = np.random.RandomState(4)
    logits = jnp.asarray(rng.randn(6, 9, 13).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 13, (6, 9)), jnp.int32)
    onehot = jnp.eye(13, dtype=jnp.float32)[labels]
    np.testing.assert_allclose(
        float(sparse_softmax_cross_entropy(logits, labels)),
        float(softmax_cross_entropy(logits, onehot)),
        rtol=1e-6,
    )


def test_fused_sparse_ce_matches_optax():
    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy

    rng = np.random.RandomState(5)
    logits = jnp.asarray(rng.randn(37, 50).astype(np.float32))  # non-divisible N
    labels = jnp.asarray(rng.randint(0, 50, 37), jnp.int32)
    got = fused_sparse_softmax_cross_entropy(logits, labels)
    want = jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, labels))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_fused_sparse_ce_grad_and_weighted():
    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy

    rng = np.random.RandomState(6)
    logits = jnp.asarray(rng.randn(4, 6, 11).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 11, (4, 6)), jnp.int32)
    w = jnp.asarray([1.0, 0.0, 1.0, 1.0])

    def fused(l):
        return fused_sparse_softmax_cross_entropy(l, labels, w)

    def ref(l):
        per = optax.softmax_cross_entropy_with_integer_labels(l, labels)
        return jnp.sum(per * w[:, None]) / jnp.sum(w * 6)

    np.testing.assert_allclose(float(fused(logits)), float(ref(logits)), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.grad(fused)(logits)),
        np.asarray(jax.grad(ref)(logits)),
        atol=1e-6,
    )


def test_fused_ce_multi_vocab_tile():
    """Force n_v > 1 (small block_v) so the cross-tile online-logsumexp and
    label accumulation actually run — the default BLOCK_V covers any test
    vocab in one tile, which would leave the streaming path untested."""
    from distriflow_tpu.ops.fused_ce import _per_row_loss, _per_row_sparse_loss

    rng = np.random.RandomState(8)
    n, v = 37, 300  # non-divisible by both block dims
    logits = jnp.asarray(rng.randn(n, v).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, v, n), jnp.int32)
    onehot = jnp.eye(v, dtype=jnp.float32)[labels]
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)

    got_sparse = _per_row_sparse_loss(logits, labels, 8, 128, True)
    got_dense = _per_row_loss(logits, onehot, 8, 128, True)
    np.testing.assert_allclose(np.asarray(got_sparse), np.asarray(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_dense), np.asarray(want), rtol=1e-5)

    # gradients through the tiled backward (lse-residual path)
    g_sparse = jax.grad(lambda l: jnp.mean(_per_row_sparse_loss(l, labels, 8, 128, True)))(logits)
    g_dense = jax.grad(lambda l: jnp.mean(_per_row_loss(l, onehot, 8, 128, True)))(logits)
    g_ref = jax.grad(lambda l: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(l, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g_sparse), np.asarray(g_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_dense), np.asarray(g_ref), atol=1e-6)


def test_sparse_ce_registered_in_registry():
    fn = get_loss("fused_sparse_softmax_cross_entropy")
    logits = jnp.asarray(np.random.RandomState(7).randn(5, 7).astype(np.float32))
    labels = jnp.asarray(np.arange(5) % 7, jnp.int32)
    np.testing.assert_allclose(
        float(fn(logits, labels)),
        float(jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, labels))),
        rtol=1e-6,
    )


def test_sharded_flash_attention_matches_dense(devices):
    """Flash through shard_map on a data x model mesh == the dense oracle
    (this is the auto-TPU path for multi-device meshes: pallas_call has no
    GSPMD rule, so partitioning must come from shard_map over batch/heads)."""
    from jax.sharding import Mesh

    from distriflow_tpu.models.transformer import _sharded_flash_attention

    mesh = Mesh(np.array(devices).reshape(4, 2), ("data", "model"))
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 2, 64, 16).astype(np.float32))
               for _ in range(3))
    # interpret=None auto-selects interpret mode on the CPU test backend
    out = jax.jit(
        lambda q, k, v: _sharded_flash_attention(q, k, v, True, mesh)
    )(q, k, v)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_transformer_with_flash_attention():
    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=32, dtype=jnp.float32, use_flash_attention=True,
    )
    spec = transformer_lm(cfg, example_seq=16)
    params = spec.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = spec.apply(params, tokens)
    assert logits.shape == (2, 16, 64)
    assert np.isfinite(np.asarray(logits)).all()


def test_pallas_flop_tally_exact():
    """The trace-time tally (ops/flop_count.py) records exactly the analytic
    model-FLOPs of each kernel call — fwd 4BHSSD/2 (causal), bwd 2x fwd."""
    from distriflow_tpu.ops.flop_count import tally_pallas_cost

    b, h, s, d = 2, 2, 64, 16
    q, k, v = _qkv(b, h, s, d)

    def loss(q):
        return jnp.sum(flash_attention(q, k, v, True, 32, 32, True))

    with tally_pallas_cost() as tally:
        jax.eval_shape(jax.grad(loss), q)
    fwd = 4 * b * h * s * s * d // 2
    assert tally["flops"] == fwd + 2 * fwd
    # no active tally -> recording is a no-op (normal tracing unaffected)
    with tally_pallas_cost() as empty:
        pass
    assert empty["flops"] == 0


def test_cost_analysis_counts_pallas_flops(devices):
    """SyncTrainer.cost_analysis reports Pallas kernel model-FLOPs: with
    flash shard_map'd over the data mesh, pallas_flops is the exact
    per-device analytic count. On this interpret-mode (CPU) backend the
    kernels lower to ordinary HLO that XLA already counts, so the tally is
    reported but NOT folded into 'flops' (folding happens only where the
    kernels compile to custom calls — TPU — where XLA counts them as 0)."""
    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
    from distriflow_tpu.parallel.mesh import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    mesh = data_parallel_mesh(devices)
    b, s = 8, 64
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=s, dtype=jnp.float32, use_flash_attention=True,
        loss="sparse_softmax_cross_entropy",  # keep CE out of the tally
    )
    spec = transformer_lm(cfg, mesh=mesh, example_seq=s)
    trainer = SyncTrainer(spec, mesh=mesh)
    trainer.init()
    x = jnp.zeros((b, s), jnp.int32)
    y = jnp.zeros((b, s), jnp.int32)
    analysis = trainer.cost_analysis((x, y))
    # per-device slice: shard_map gives each device b/8 rows
    u_fwd = 4 * (b // 8) * cfg.n_heads * s * s * (cfg.d_model // cfg.n_heads) // 2
    expected = cfg.n_layers * (u_fwd + 2 * u_fwd)
    assert analysis["pallas_flops"] == expected
    # interpret backend: no fold (XLA already counted the kernel HLO)
    assert analysis["flops"] == analysis["xla_flops"]
    assert analysis["flops"] > analysis["pallas_flops"]  # XLA part present
    # mfu() consumes the numerator without raising
    mfu = trainer.mfu((x, y), step_seconds=1.0, peak_flops_per_chip=1e12)
    assert mfu > 0


def test_cost_analysis_ce_per_device_and_grad_accum(devices):
    """Equality tripwires for the round-3 ADVICE corrections: (a) the
    fused CE's tally share is divided by the data-mesh degree (it records
    global rows; every other kernel records per-shard), and (b) the
    grad_accum scan's trace-once/execute-K multiplicity is multiplied
    back, so pallas_flops is invariant to micro-batching."""
    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
    from distriflow_tpu.parallel.mesh import data_parallel_mesh
    from distriflow_tpu.train.sync import SyncTrainer

    mesh = data_parallel_mesh(devices)
    # b=32 keeps every micro-batch divisible by the 8-device data axis
    # at the grad_accum values below
    b, s, v = 32, 32, 64
    cfg = TransformerConfig(
        vocab_size=v, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_seq=s, dtype=jnp.float32, use_flash_attention=False,
        loss="fused_sparse_softmax_cross_entropy",  # CE is the only kernel
    )
    x = jnp.zeros((b, s), jnp.int32)
    y = jnp.zeros((b, s), jnp.int32)

    def analyzed(grad_accum):
        spec = transformer_lm(cfg, mesh=mesh, example_seq=s)
        t = SyncTrainer(spec, mesh=mesh, grad_accum=grad_accum)
        t.init()
        return t.cost_analysis((x, y))

    base = analyzed(1)
    # (a) per-device CE share: (5 fwd + 3 bwd) ops/element over the
    # device's row slice (global b*s rows / 8 devices)
    n_rows = b * s
    assert base["pallas_flops"] == 8 * n_rows * v / len(devices)
    # (b) micro-batching must not change the analyzed model FLOPs
    assert analyzed(2)["pallas_flops"] == base["pallas_flops"]
    assert analyzed(4)["pallas_flops"] == base["pallas_flops"]


def test_flagship_loss_resolution(devices, monkeypatch):
    """loss=None resolves per-backend at spec-build time: fused sparse CE
    when the Pallas kernels compile (TPU) AND the mesh is single-device
    (pallas has no GSPMD rule — a multi-device mesh would all-gather the
    global logits), plain optax CE elsewhere; an explicit loss is always
    honored."""
    import distriflow_tpu.models.transformer as tmod
    from distriflow_tpu.models.transformer import TransformerConfig, transformer_lm
    from distriflow_tpu.parallel.mesh import data_parallel_mesh

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=1,
                            d_ff=64, dtype=jnp.float32)
    assert cfg.resolved_loss == "sparse_softmax_cross_entropy"  # CPU backend
    monkeypatch.setattr(tmod, "_default_use_flash", lambda: True)
    assert cfg.resolved_loss == "fused_sparse_softmax_cross_entropy"
    assert transformer_lm(cfg, example_seq=8).loss == (
        "fused_sparse_softmax_cross_entropy"
    )
    # pure data-parallel mesh: fused stays the default (the kernel runs
    # per data shard of the context mesh)
    mesh = data_parallel_mesh(devices)
    assert cfg.resolved_loss_for(mesh) == "fused_sparse_softmax_cross_entropy"
    # ... but meshes that shard the vocab (model/pipe) or the seq dim back
    # off to the sharded XLA loss
    from distriflow_tpu.parallel import create_mesh
    from distriflow_tpu.utils.config import MeshConfig

    tp_mesh = create_mesh(MeshConfig(data=2, model=2), devices[:4])
    assert cfg.resolved_loss_for(tp_mesh) == "sparse_softmax_cross_entropy"
    assert transformer_lm(cfg, mesh=tp_mesh, example_seq=8).loss == (
        "sparse_softmax_cross_entropy"
    )
    # ... but an explicit fused choice is honored even on a mesh
    fused_cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        dtype=jnp.float32, loss="fused_sparse_softmax_cross_entropy")
    assert fused_cfg.resolved_loss_for(mesh) == "fused_sparse_softmax_cross_entropy"
    explicit = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, dtype=jnp.float32,
                                 loss="softmax_cross_entropy")
    assert explicit.resolved_loss == "softmax_cross_entropy"


def test_fused_ce_partitioned_no_allgather(devices):
    """Under a context mesh the fused sparse CE runs per data shard and
    keeps row-sharded logits sharded: values and grads match the unfused
    oracle, the grad stays row-sharded, and the compiled program contains
    NO all-gather (the failure mode the partitioning exists to prevent)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy

    mesh = Mesh(np.array(devices), ("data",))
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(64, 300).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 300, 64), jnp.int32)
    logits_s = jax.device_put(logits, NamedSharding(mesh, P("data", None)))
    labels_s = jax.device_put(labels, NamedSharding(mesh, P("data")))

    def loss(lg, lb):
        return fused_sparse_softmax_cross_entropy(lg, lb)

    f = jax.jit(loss)
    ref = float(jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(logits, labels)))
    with jax.set_mesh(mesh):  # what SyncTrainer does around its step
        assert abs(float(f(logits_s, labels_s)) - ref) < 1e-5
        g = jax.jit(jax.grad(loss))(logits_s, labels_s)
        hlo = f.lower(logits_s, labels_s).compile().as_text()
    g_ref = jax.grad(lambda lg: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(lg, labels)))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)
    assert tuple(g.sharding.spec)[:1] == ("data",)  # rows stay sharded
    assert "all-gather" not in hlo
    # rows that do not divide over the data axis still compute: replicated
    with jax.set_mesh(mesh):
        odd = float(f(logits[:60], labels[:60]))
    assert abs(odd - float(jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits[:60], labels[:60])))) < 1e-5


def test_fused_sparse_ce_vmap_still_works():
    """The kernel wrapper's custom_vmap rule collapses the batch axis into rows, so
    vmap over the public op keeps working — including the jit
    compositions in both orders (round-3 sniffed batch tracers and
    failed under ``vmap(jit(f))``)."""
    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy_per_example

    rng = np.random.RandomState(9)
    logits = jnp.asarray(rng.randn(4, 16, 30).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 30, (4, 16)), jnp.int32)
    got = jax.vmap(fused_sparse_softmax_cross_entropy_per_example)(logits, labels)
    want = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)
    # grads under vmap too
    def per_batch_loss(l, y):
        return jnp.mean(fused_sparse_softmax_cross_entropy_per_example(l, y))
    g = jax.vmap(jax.grad(per_batch_loss))(logits, labels)
    g_ref = jax.vmap(jax.grad(lambda l, y: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(l, y))))(logits, labels)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)


def test_fused_sparse_ce_vmap_jit_compositions():
    """The round-3 hole: ``vmap(jit(loss))`` hid the batch trace from the
    tracer probe and the partitioned kernel call failed under vmap.
    The batching rule makes every composition order work, values AND
    grads, plus nested vmap."""
    from distriflow_tpu.ops import fused_sparse_softmax_cross_entropy_per_example

    fn = fused_sparse_softmax_cross_entropy_per_example
    rng = np.random.RandomState(11)
    logits = jnp.asarray(rng.randn(4, 16, 30).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 30, (4, 16)), jnp.int32)
    want = np.asarray(
        optax.softmax_cross_entropy_with_integer_labels(logits, labels))

    for f in (jax.vmap(jax.jit(fn)), jax.jit(jax.vmap(fn))):
        np.testing.assert_allclose(np.asarray(f(logits, labels)), want,
                                   rtol=1e-5)

    def per_batch_loss(l, y):
        return jnp.mean(fn(l, y))

    g = jax.vmap(jax.jit(jax.grad(per_batch_loss)))(logits, labels)
    g_ref = jax.vmap(jax.grad(lambda l, y: jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(l, y))))(logits, labels)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)
    g2 = jax.jit(jax.vmap(jax.grad(per_batch_loss)))(logits, labels)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g_ref), atol=1e-6)

    # nested vmap collapses recursively (one more leading dim)
    nl = jnp.stack([logits, logits + 0.5])
    ny = jnp.stack([labels, labels])
    got_n = jax.vmap(jax.vmap(fn))(nl, ny)
    want_n = optax.softmax_cross_entropy_with_integer_labels(nl, ny)
    np.testing.assert_allclose(np.asarray(got_n), np.asarray(want_n),
                               rtol=1e-5)

    # unbatched-operand broadcast inside the rule: labels shared across
    # the vmap axis
    got_b = jax.vmap(fn, in_axes=(0, None))(logits, labels[0])
    want_b = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.broadcast_to(labels[0], labels.shape))
    np.testing.assert_allclose(np.asarray(got_b), np.asarray(want_b),
                               rtol=1e-5)


def test_fused_ce_no_private_jax_imports():
    """Tripwire (round-3 ADVICE): the kernel module must not import
    private ``jax._src`` modules — a JAX upgrade moving one would break
    every training step that uses the default LM loss."""
    import inspect

    from distriflow_tpu.ops import fused_ce

    src = inspect.getsource(fused_ce)
    assert "jax._src" not in src


def test_fused_dense_ce_partitioned_and_vmap(devices):
    """Dense-target fused CE: same rows-sharded partitioning (targets ride
    with the logits) and the same batch-collapsing vmap rule."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("data",))
    rng = np.random.RandomState(10)
    logits = jnp.asarray(rng.randn(64, 40).astype(np.float32))
    onehot = jnp.eye(40, dtype=jnp.float32)[rng.randint(0, 40, 64)]
    sh2 = NamedSharding(mesh, P("data", None))
    f = jax.jit(lambda l, t: fused_softmax_cross_entropy(l, t))
    with jax.set_mesh(mesh):
        got = float(f(jax.device_put(logits, sh2), jax.device_put(onehot, sh2)))
        hlo = f.lower(jax.device_put(logits, sh2),
                      jax.device_put(onehot, sh2)).compile().as_text()
    want = float(jnp.mean(optax.softmax_cross_entropy(logits, onehot)))
    assert abs(got - want) < 1e-5
    assert "all-gather" not in hlo
    # vmap fallback
    bl = jnp.asarray(rng.randn(3, 8, 12).astype(np.float32))
    bt = jnp.eye(12, dtype=jnp.float32)[rng.randint(0, 12, (3, 8))]
    got_v = jax.vmap(fused_softmax_cross_entropy_per_example)(bl, bt)
    np.testing.assert_allclose(
        np.asarray(got_v),
        np.asarray(optax.softmax_cross_entropy(bl, bt)), rtol=1e-5)


def test_flash_attention_crooked_length_blocks_are_sublane_aligned():
    """Round-5 regression: a 32,704-token prompt (32k minus the generate
    budget) made the old any-divisor block picker choose 1022, which the
    Pallas lowering rejects (blocks must be multiples of 8 or the whole
    dim). The aligned picker must find a multiple-of-8 divisor — and the
    kernel must run end to end on such lengths."""
    from distriflow_tpu.ops.flash_attention import (
        _aligned_block,
        flash_attention,
    )

    assert _aligned_block(32704, 1024) == 584  # 8*73, not 1022
    assert _aligned_block(16256, 1024) == 1016
    assert _aligned_block(4096, 1024) == 1024
    assert _aligned_block(1000, 1024) == 1000  # one whole block
    assert _aligned_block(2044, 1024) == 2044  # no aligned divisor: whole

    from distriflow_tpu.ops.flash_attention import flash_seq_supported
    from distriflow_tpu.parallel.ring_attention import blockwise_attention

    rng = np.random.RandomState(0)
    # whole-block fallback path (1022: no aligned divisor, fits VMEM)
    q = jnp.asarray(rng.randn(1, 2, 1022, 32), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    want = blockwise_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # aligned MULTI-block path at a crooked length — the actual round-5
    # bug shape class: 1168 -> two 584-wide tiles (review follow-up: the
    # first regression test only exercised the whole-block fallback)
    assert _aligned_block(1168, 1024) == 584
    q2 = jnp.asarray(rng.randn(1, 2, 1168, 32), jnp.float32)
    out2 = flash_attention(q2, q2, q2, causal=True, block_q=584,
                           block_k=584, interpret=True)
    want2 = blockwise_attention(q2, q2, q2, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)
    # VMEM gate: huge crooked lengths are unsupported -> callers (the
    # prefill path) fall back to blockwise instead of a Mosaic crash
    assert flash_seq_supported(32704, 64)   # aligned divisor exists
    assert not flash_seq_supported(32700, 64)  # whole-block would be 50 MB
    assert flash_seq_supported(5001, 64)    # small whole-block: fine
