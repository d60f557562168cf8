"""The served weights are cast to the compute dtype once per weight swap
(``models/transformer.py::compute_view``), not once per dispatch.

Pins, in order: (a) which leaves the view narrows, dense and MoE, and that
TP-sharded leaves stay sharded; (b) a leaf already in the compute dtype
comes back as the same array; (c) ``TransformerLM.apply`` over the view is
bit-equal to ``apply`` over the float32 tree; (d) an ``InferenceServer``
answers ``generate``, ``score`` and ``beam`` as one whose programs all take
the float32 tree (the parent's behaviour: ``compute_view`` patched to the
identity), over the paged layout, the slab layout and both speculative
set-ups, and which program takes which tree; (e) ``set_params`` rebuilds
the view and no dispatch does; (f) the lowered decode chunk casts no
weight: the regression test of what the chip's op table showed
(``convert_element_type bf16[8192,2048]`` in every chunk).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distriflow_tpu.client import InferenceClient
from distriflow_tpu.models.generate import generate, sequence_logprob
from distriflow_tpu.models.transformer import (
    TransformerConfig,
    TransformerLM,
    compute_view,
    transformer_lm,
)
from distriflow_tpu.obs.telemetry import Telemetry
from distriflow_tpu.parallel import create_mesh
from distriflow_tpu.parallel.sharding import TRANSFORMER_TP_RULES, tree_shardings
from distriflow_tpu.server import InferenceServer
from distriflow_tpu.server import inference_server as server_mod
from distriflow_tpu.utils.config import MeshConfig, ServingConfig

DENSE = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.bfloat16, use_flash_attention=False,
)
MOE = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=48,
    dtype=jnp.bfloat16, use_flash_attention=False, n_experts=4,
    moe_dense_dispatch=True,
)
CONFIGS = {"dense": DENSE, "moe": MOE}
PS = 16
#: leaf names the block reads in float32, whatever the compute dtype
F32_MODULES = ("ln_attn", "ln_mlp", "ln_f", "router")


def _init(cfg, seed=0):
    return transformer_lm(cfg, example_seq=16).init(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def params():
    return _init(DENSE)


def _named_leaves(tree):
    return [("/".join(str(k.key) for k in path), leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _view_bytes(params, view):
    return sum(v.nbytes for p, v in zip(jax.tree.leaves(params),
                                        jax.tree.leaves(view)) if v is not p)


# -- (a) which leaves the view narrows ----------------------------------------


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_view_dtypes_leaf_by_leaf(name):
    cfg = CONFIGS[name]
    params = _init(cfg)
    view = compute_view(cfg, params)
    assert jax.tree.structure(view) == jax.tree.structure(params)
    narrowed = 0
    for (path, leaf), (_, got) in zip(_named_leaves(params),
                                      _named_leaves(view)):
        assert leaf.dtype == jnp.float32 and got.shape == leaf.shape
        if any(f"/{m}/" in path for m in F32_MODULES):
            assert got is leaf, path  # casting these would change the result
        else:
            assert got.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(leaf.astype(jnp.bfloat16)))
            narrowed += leaf.size
    # everything but the LayerNorms (and the router) is narrowed
    kept = sum(leaf.size for path, leaf in _named_leaves(params)
               if any(f"/{m}/" in path for m in F32_MODULES))
    assert narrowed + kept == sum(
        leaf.size for leaf in jax.tree.leaves(params))
    assert _view_bytes(params, view) == 2 * narrowed
    if name == "moe":
        paths = [p for p, _ in _named_leaves(view)]
        assert any(p.endswith("experts_wi") for p in paths)
        assert any("/router/" in p for p in paths)


def test_view_keeps_tp_shardings(devices):
    params = _init(DENSE)
    mesh = create_mesh(MeshConfig(data=2, model=2), devices[:4])
    sharded = jax.tree.map(
        jax.device_put, params,
        tree_shardings(params, mesh, TRANSFORMER_TP_RULES))
    view = compute_view(DENSE, sharded)
    on_model = 0
    for (path, leaf), (_, got) in zip(_named_leaves(sharded),
                                      _named_leaves(view)):
        assert got.sharding.is_equivalent_to(leaf.sharding, leaf.ndim), path
        on_model += "model" in jax.tree.leaves(tuple(leaf.sharding.spec))
    assert on_model >= 6 * DENSE.n_layers  # q, k, v, o, wi, wo of each block


# -- (b) a leaf already in the compute dtype is passed through ---------------


@pytest.mark.parametrize("case", ["bf16-tree", "f32-compute", "view-of-view"])
def test_tree_in_the_compute_dtype_is_the_same_arrays(params, case):
    if case == "f32-compute":
        cfg, tree = dataclasses.replace(DENSE, dtype=jnp.float32), params
    elif case == "bf16-tree":
        cfg = DENSE
        tree = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    else:
        cfg, tree = DENSE, compute_view(DENSE, params)
    view = compute_view(cfg, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(view)):
        assert a is b
    assert _view_bytes(tree, view) == 0


# -- (c) the forward over the view is the forward over the tree --------------


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_logits_bit_equal_over_the_view(name):
    cfg = CONFIGS[name]
    params = _init(cfg, seed=3)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (2, 16)), jnp.int32)
    apply = jax.jit(TransformerLM(cfg).apply)
    want = np.asarray(apply(params, tokens))
    got = np.asarray(apply(compute_view(cfg, params), tokens))
    assert want.dtype == got.dtype
    np.testing.assert_array_equal(got, want)
    assert np.abs(want).max() > 0


# -- (d) the server answers as the float32 programs do -----------------------

SERVINGS = {
    "paged": dict(kv_layout="paged", page_size=PS),
    "slab": dict(kv_layout="slab"),
    "spec-self": dict(kv_layout="paged", page_size=PS, speculate_k=2,
                      draft_model="self"),
    "spec-draft": dict(kv_layout="paged", page_size=PS, speculate_k=2,
                       draft_model="lm_draft"),
}


def _serve(params, tel=None, **serving):
    return InferenceServer(
        DENSE, params, port=0, telemetry=tel,
        serving=ServingConfig(batch_window_s=0.0, decode_chunk=4,
                              **serving)).setup()


def _answers(params, serving):
    """What one server says: two greedy rows through the engine, their
    ``score``, two sampled rows through the one-shot ``generate``, a beam."""
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, 64, (2, 9)).astype(np.int32)
    server = _serve(params, **serving)
    try:
        with InferenceClient(server.address) as client:
            engine = client.generate(prompt, n_tokens=11)
            assert client.last_serving_meta["path"] == "slots"
            scores = client.score(engine, from_pos=1)
            direct = client.generate(prompt, n_tokens=5, temperature=0.8,
                                     seed=3)
            assert client.last_serving_meta["path"] == "direct"
            beams, _ = client.beam_search(prompt[:1], n_tokens=4, beam_size=2)
        return server, engine, np.asarray(scores), direct, beams
    finally:
        server.stop()


@pytest.mark.parametrize("layout", sorted(SERVINGS))
def test_server_answers_as_the_float32_programs(params, monkeypatch, layout):
    server, engine, scores, direct, beams = _answers(
        params, SERVINGS[layout])
    assert server._param_view is not server.params
    assert server.params is params  # the float32 tree stays readable
    for leaf in jax.tree.leaves(server.params):
        assert leaf.dtype == jnp.float32
    if layout == "spec-draft":
        assert server._draft_view is not server.draft_params
        assert {v.dtype for v in jax.tree.leaves(server._draft_view)} == {
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)}
    # the parent's behaviour: every program takes the float32 tree
    monkeypatch.setattr(server_mod, "compute_view", lambda cfg, p: p)
    oracle, want_engine, want_scores, want_direct, want_beams = _answers(
        params, SERVINGS[layout])
    assert oracle._param_view is oracle.params
    np.testing.assert_array_equal(engine, want_engine)
    np.testing.assert_array_equal(scores, want_scores)  # float32, bit-equal
    np.testing.assert_array_equal(direct, want_direct)
    np.testing.assert_array_equal(beams, want_beams)
    # and the library's own one-shot programs over the float32 tree
    np.testing.assert_array_equal(scores, np.asarray(sequence_logprob(
        DENSE, params, jnp.asarray(engine), 1)))
    if not layout.startswith("spec"):
        np.testing.assert_array_equal(engine, np.asarray(generate(
            DENSE, params, jnp.asarray(engine[:, :9]), 11)))


def test_loops_take_the_view_and_single_passes_take_params(
        params, monkeypatch):
    """The decode chunk reads the view; prefill reads ``params``, where the
    cast fuses into the operand (and, on the TPU, whose float32 form is the
    one that compiles small: PERF.md §6 PR 31)."""
    seen = {}

    def recording(name, build, pick):
        def builder(*args):
            fns = list(build(*args))
            real = fns[pick]

            def program(tree, *rest):
                seen[name] = tree
                return real(tree, *rest)
            program.lower = getattr(real, "lower", None)
            fns[pick] = program
            return tuple(fns)
        return builder

    monkeypatch.setattr(server_mod, "_build_prefill", recording(
        "prefill", server_mod._build_prefill, 0))
    monkeypatch.setattr(server_mod, "_build_slot_fns", recording(
        "decode", server_mod._build_slot_fns, 2))
    server = _serve(params, kv_layout="paged", page_size=PS)
    try:
        with InferenceClient(server.address) as client:
            client.generate(np.arange(1, 8, dtype=np.int32)[None], 6)
        assert seen["prefill"] is server.params
        assert seen["decode"] is server._param_view is not server.params
    finally:
        server.stop()


# -- (e) set_params rebuilds the view; a dispatch never does ------------------


def test_set_params_rebuilds_the_view_and_no_dispatch_does(params):
    tel = Telemetry()
    builds = lambda: tel.counter_value("serving_param_view_builds_total")
    gauge = lambda: tel.snapshot()["gauges"]["serving_param_view_bytes"]
    other = _init(DENSE, seed=123)
    prompt = np.random.RandomState(2).randint(0, 64, (1, 7)).astype(np.int32)
    server = _serve(params, tel, kv_layout="paged", page_size=PS)
    narrowed = _view_bytes(params, compute_view(DENSE, params))
    try:
        assert builds() == 1 and gauge() == narrowed > 0
        with InferenceClient(server.address) as client:
            before = client.generate(prompt, n_tokens=8)
            server.set_params(other)
            assert builds() == 2 and gauge() == narrowed
            assert server.params is other
            after = client.generate(prompt, n_tokens=8)
            np.testing.assert_array_equal(after, np.asarray(generate(
                DENSE, other, jnp.asarray(prompt), 8)))
            assert not np.array_equal(before, after)
            start = server.decode_batches
            while server.decode_batches < start + 20:
                client.generate(prompt, n_tokens=40)
            assert builds() == 2
            # weights served in the compute dtype cost no second copy
            server.set_params(compute_view(DENSE, other))
            assert builds() == 3 and gauge() == 0
            np.testing.assert_array_equal(
                client.generate(prompt, n_tokens=8), after)
    finally:
        server.stop()


# -- (f) the decode chunk casts no weight -------------------------------------

_CONVERT = re.compile(
    r"stablehlo\.convert[^\n]*tensor<([0-9x]+)xf32>\)? -> tensor<\1xbf16>")


def _weight_casts(text, params):
    shapes = {"x".join(str(d) for d in leaf.shape)
              for leaf in jax.tree.leaves(params) if leaf.ndim >= 2}
    return [s for s in _CONVERT.findall(text) if s in shapes]


def test_lowered_decode_chunk_casts_no_weight(params, monkeypatch):
    prompt = np.arange(1, 8, dtype=np.int32)[None]

    def lowered():
        # three slots: no activation of the chunk has a weight's shape
        server = _serve(params, kv_layout="paged", page_size=PS, max_slots=3)
        try:
            with InferenceClient(server.address) as client:
                client.generate(prompt, n_tokens=6)  # allocates the cache
            return server.lower_decode().as_text()
        finally:
            server.stop()

    assert _weight_casts(lowered(), params) == []
    # the same reader over the parent's program finds every matrix cast
    monkeypatch.setattr(server_mod, "compute_view", lambda cfg, p: p)
    found = _weight_casts(lowered(), params)
    assert len(found) >= 6 * DENSE.n_layers + 2
