"""The plain reference of the hybrid state-space family
(``benchmark/lib/reference_granite_hybrid.py``) held to the published
implementation: a tiny ``GraniteMoeHybridForCausalLM`` of ``transformers``
with its random weights copied across, logits equal in float32; and the
program, given that tree, says the same. A file of its own: importing torch
and building the model take most of a minute.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_granite_hybrid as ref
from distriflow_tpu.models.hybrid_ssm import HybridSSMConfig, HybridSSMLM


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_reference_is_the_published_implementation():
    """A tiny ``GraniteMoeHybridForCausalLM`` with its random weights (and
    ``dt_bias``, ``A_log``, ``D`` spread out) copied into the reference's
    tree: logits equal in float32."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_cfg = transformers.GraniteMoeHybridConfig(
        vocab_size=128, hidden_size=32, intermediate_size=16,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        layer_types=["mamba", "attention", "mamba"], mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
        mamba_n_groups=1, mamba_chunk_size=8, mamba_conv_bias=True,
        mamba_proj_bias=False, num_local_experts=6, num_experts_per_tok=2,
        shared_intermediate_size=24, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.1,
        logits_scaling=4.0, position_embedding_type="nope",
        tie_word_embeddings=True, rms_norm_eps=1e-5, attention_bias=False,
        initializer_range=0.3, attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.GraniteMoeHybridForCausalLM(hf_cfg).float().eval()
    with torch.no_grad():
        for layer in model.model.layers:
            if layer.mamba is not None:
                layer.mamba.dt_bias.uniform_(-4.0, 1.0)
                layer.mamba.A_log.uniform_(-1.0, 3.0)
                layer.mamba.D.uniform_(0.5, 1.5)
                layer.mamba.norm.weight.uniform_(0.5, 1.5)
            layer.input_layernorm.weight.uniform_(0.5, 1.5)
    sd = {k: v.detach().numpy() for k, v in model.state_dict().items()}
    tokens = np.random.default_rng(0).integers(0, 128, 29)
    with torch.no_grad():
        want = model(torch.tensor(tokens[None])).logits[0].numpy()

    def t(name):
        return jnp.asarray(sd[name].T)

    tree = {"embed": {"embedding": jnp.asarray(sd["model.embed_tokens.weight"])},
            "norm": {"scale": jnp.asarray(sd["model.norm.weight"])}}
    f, sf = 16, 24
    for i, kind in enumerate(hf_cfg.layer_types):
        pre = f"model.layers.{i}."
        w_in = sd[pre + "block_sparse_moe.input_linear.weight"]  # [E, 2f, d]
        w_out = sd[pre + "block_sparse_moe.output_linear.weight"]  # [E, d, f]
        s_in = sd[pre + "shared_mlp.input_linear.weight"]  # [2 sf, d]
        mlp = {"router": t(pre + "block_sparse_moe.router.layer.weight"),
               "shared_expert": {
                   "gate_proj": {"kernel": jnp.asarray(s_in[:sf].T)},
                   "up_proj": {"kernel": jnp.asarray(s_in[sf:].T)},
                   "down_proj": {"kernel": t(
                       pre + "shared_mlp.output_linear.weight")}}}
        mlp["experts_gate"] = jnp.asarray(w_in[:, :f].transpose(0, 2, 1))
        mlp["experts_up"] = jnp.asarray(w_in[:, f:].transpose(0, 2, 1))
        mlp["experts_down"] = jnp.asarray(w_out.transpose(0, 2, 1))
        if kind == "mamba":
            m = pre + "mamba."
            mixer = {"in_proj": {"kernel": t(m + "in_proj.weight")},
                     "conv_weight": jnp.asarray(
                         sd[m + "conv1d.weight"][:, 0, :].T),
                     "conv_bias": jnp.asarray(sd[m + "conv1d.bias"]),
                     "dt_bias": jnp.asarray(sd[m + "dt_bias"]),
                     "A_log": jnp.asarray(sd[m + "A_log"]),
                     "D": jnp.asarray(sd[m + "D"]),
                     "norm": {"scale": jnp.asarray(sd[m + "norm.weight"])},
                     "out_proj": {"kernel": t(m + "out_proj.weight")}}
        else:
            a = pre + "self_attn."
            mixer = {"q_proj": {"kernel": t(a + "q_proj.weight").reshape(
                         32, 2, 2, 8)},
                     "k_proj": {"kernel": t(a + "k_proj.weight")},
                     "v_proj": {"kernel": t(a + "v_proj.weight")},
                     "o_proj": {"kernel": t(a + "o_proj.weight")}}
        tree[f"layers_{i}"] = {
            "input_norm": {"scale": jnp.asarray(
                sd[pre + "input_layernorm.weight"])},
            "post_mixer_norm": {"scale": jnp.asarray(
                sd[pre + "post_attention_layernorm.weight"])},
            "mixer": mixer, "mlp": mlp}
    model_dict = dict(
        layer_types=hf_cfg.layer_types, mamba_n_heads=4, mamba_d_state=8,
        num_experts_per_tok=2, experts_held=(0, 6), residual_multiplier=0.22,
        embedding_multiplier=12.0, attention_multiplier=0.1,
        logits_scaling=4.0)
    x, _, _ = ref.forward({"params": tree}, jnp.asarray(tokens), model_dict)
    got = np.asarray(ref.rms_norm(x, tree["norm"]["scale"])
                     @ tree["embed"]["embedding"].T / 4.0)
    assert np.abs(got - want).max() < 1e-4, np.abs(got - want).max()
    assert np.abs(want).max() > 1.0  # logits of some spread, not a flat row
    # and the program, given that tree, says the same
    cfg = HybridSSMConfig(
        vocab_size=128, d_model=32, layer_types=tuple(hf_cfg.layer_types),
        n_heads=4, n_kv_heads=2, attention_multiplier=0.1, mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=8, moe_d_ff=16, shared_d_ff=24,
        n_routed_experts=6, n_experts_per_tok=2, experts_held=(0, 6),
        max_seq=64, mamba_chunk_size=8, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=4.0, dtype=jnp.float32,
        param_dtype=jnp.float32)
    logits, _ = HybridSSMLM(cfg).apply({"params": tree}, tokens[None],
                                       mutable=["cache"])
    assert np.abs(np.asarray(logits[0]) - want).max() < 1e-4
