"""``ops/expert_grouped.py`` in the Pallas interpreter against the summed
per-expert MLPs (``models/latent_sparse.py::_expert_term``), at toy widths
that keep the chip's tiling: over sets of experts hit and of live rows, both
scoring rules' gates, float32 and bfloat16; what an expert nobody chose
holds cannot reach the result; the list the grid walks names only experts
hit and changes as often as there are experts to fetch.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distriflow_tpu.models.latent_sparse as ls

eg = importlib.import_module("distriflow_tpu.ops.expert_grouped")

T, D, F = 16, 128, 256
HELD, EXPERTS, TOP_K = (1, 6), 8, 4  # experts [1, 7) of 8, four a token
ALLOWED = {"none": (), "one": (2,), "last_only": (5,), "gaps": (0, 2, 5),
           "all": tuple(range(6))}


def _weights(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    xc = jax.random.normal(keys[0], (T, D), jnp.float32).astype(dtype)
    stack = tuple(
        (jax.random.normal(key, (HELD[1],) + shape, jnp.float32)
         / shape[0] ** 0.5).astype(dtype)
        for key, shape in zip(keys[1:], ((D, F), (D, F), (F, D))))
    return xc, stack


def _gates(scoring, allowed, live, seed=1):
    """The held experts' columns of a router's gates over ``T`` tokens, the
    rows past ``live`` retired and the experts outside ``allowed`` chosen
    by nobody."""
    logits = jax.random.normal(jax.random.PRNGKey(seed), (T, EXPERTS))
    if scoring == "sigmoid":
        gates = ls.route(jax.nn.sigmoid(logits), jnp.zeros((EXPERTS,)),
                         TOP_K, 2.5)
    else:
        gates = ls.route_softmax_topk(logits, TOP_K)
    gates = gates[:, HELD[0]:HELD[0] + HELD[1]]
    rows = (jnp.arange(T) < live)[:, None]
    cols = jnp.zeros((HELD[1],), bool).at[jnp.asarray(allowed, int)].set(True)
    return jnp.where(rows & cols[None], gates, 0.0)


def _oracle(xc, gates, stack):
    return sum(ls._expert_term(xc, *(w[e] for w in stack), gates[:, e])
               for e in range(gates.shape[1]))


kernel = jax.jit(eg.grouped_expert_terms)


@pytest.mark.parametrize("scoring", ls.SCORING)
@pytest.mark.parametrize("live", [0, 1, 5, T])
@pytest.mark.parametrize("allowed", list(ALLOWED), ids=list(ALLOWED))
def test_kernel_is_the_sum_of_the_experts_terms(allowed, live, scoring):
    xc, stack = _weights(jnp.float32)
    gates = _gates(scoring, ALLOWED[allowed], live)
    hit = np.asarray((gates > 0).any(axis=0))
    assert set(np.flatnonzero(hit)) <= set(ALLOWED[allowed])
    if live == T:  # sixteen tokens of four choices reach every expert
        assert set(np.flatnonzero(hit)) == set(ALLOWED[allowed])
    # every expert not hit holds NaN: it is never read into the sum
    poisoned = tuple(jnp.where(hit[:, None, None], w, jnp.nan) for w in stack)
    got = kernel(xc, gates, *poisoned)
    want = _oracle(xc, gates, tuple(
        jnp.where(hit[:, None, None], w, 0.0) for w in stack))
    assert got.dtype == jnp.float32 and got.shape == (T, D)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) <= 1e-5 * max(
        float(jnp.abs(want).max()), 1.0)
    if not hit.any():
        assert not np.asarray(got).any()


@pytest.mark.parametrize("tokens", [T, 3], ids=["whole_tiles", "padded"])
def test_kernel_in_bfloat16_rounds_where_the_xla_form_does(tokens):
    xc, stack = _weights(jnp.bfloat16)
    xc, gates = xc[:tokens], _gates("softmax_topk", ALLOWED["gaps"], 5)[:tokens]
    got = kernel(xc, gates, *stack)
    want = _oracle(xc, gates, stack)
    assert got.shape == (tokens, D)
    # each side rounds the two projections, their product and the down
    # projection to bfloat16 (eps 2^-7): a few roundings of values under the
    # largest, where a wrong expert or gate is off by the value itself
    assert float(jnp.abs(got - want).max()) < 4 * 2.0 ** -7 * float(
        jnp.abs(want).max())


def _fetches(order):
    """Expert blocks the grid fetches: a step whose block index did not
    change issues no DMA."""
    order = np.asarray(order)
    return 1 + int((order[1:] != order[:-1]).sum())


def _check_list(hit, order, n_hit):
    """What the kernel needs of its list: the experts hit first, ascending,
    and nothing behind them that would fetch another expert."""
    hit, order, n_hit = np.asarray(hit), np.asarray(order), int(n_hit)
    assert n_hit == hit.sum()
    assert order[:n_hit].tolist() == np.flatnonzero(hit).tolist()
    assert _fetches(order) == max(n_hit, 1)
    if n_hit:
        assert set(order.tolist()) == set(np.flatnonzero(hit).tolist())


@pytest.mark.parametrize("hit", [
    (0, 0, 0, 0, 0, 0), (0, 0, 1, 0, 0, 0), (0, 0, 0, 0, 0, 1),
    (1, 0, 1, 0, 0, 1), (0, 1, 1, 0, 1, 0), (1, 1, 1, 1, 1, 1)])
def test_the_list_names_the_experts_hit_and_repeats_the_last(hit):
    hit = jnp.asarray(hit, bool)
    _check_list(hit, *eg.hit_first(hit))


def _unguarded(hit, hit_first=eg.hit_first):
    """Planted: every grid step computes."""
    return hit_first(hit)[0], jnp.int32(hit.shape[0])


def _padded_with_the_others(hit):
    """Planted: the experts not hit fill the list's tail, ``live_first``'s
    order, so each of them is fetched."""
    order, n_hit = ls.live_first(hit)
    return order.astype(jnp.int32), n_hit


def test_a_planted_fault_in_the_list_is_seen(monkeypatch):
    hit = jnp.asarray((1, 0, 1, 0, 0, 1), bool)
    with pytest.raises(AssertionError):
        _check_list(hit, *_padded_with_the_others(hit))
    # and without the guard an expert nobody chose reaches the sum
    xc, stack = _weights(jnp.float32)
    gates = _gates("softmax_topk", (), T)  # nobody chooses: expert 0 is named
    poisoned = tuple(w.at[0].set(jnp.nan) for w in stack)
    assert not np.asarray(eg.grouped_expert_terms(xc, gates, *poisoned)).any()
    monkeypatch.setattr(eg, "hit_first", _unguarded)
    assert not bool(jnp.isfinite(
        eg.grouped_expert_terms(xc, gates, *poisoned)).all())


@pytest.mark.parametrize("shape,why", [
    ((16, 64, 256), "multiples"), ((16, 128, 96), "multiples"),
    ((16, 8192, 4096), "VMEM")])
def test_a_shape_the_chip_cannot_tile_raises(shape, why):
    t, d, f = shape
    struct = jax.ShapeDtypeStruct
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(
            lambda *a: eg.grouped_expert_terms(*a, interpret=False),
            struct((t, d), jnp.bfloat16), struct((t, 6), jnp.float32),
            struct((6, d, f), jnp.bfloat16), struct((6, d, f), jnp.bfloat16),
            struct((6, f, d), jnp.bfloat16))


def test_weights_in_another_dtype_than_the_tokens_raise():
    xc, stack = _weights(jnp.float32)
    with pytest.raises(ValueError, match="dtype"):
        eg.grouped_expert_terms(xc.astype(jnp.bfloat16),
                                _gates("sigmoid", (0,), T), *stack)
