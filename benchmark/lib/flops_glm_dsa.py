"""Arithmetic of the ``glm_moe_dsa`` share: parameters, cache bytes, and the
bytes a decode step *must* read, counted from the work (what the selector
scores, what attention attends over, which experts were chosen) and not
from how the program does it, so that a later kernel is read on the same
yardstick. ``c`` is the configuration file's dict.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

BF16 = 2
F32 = 4


def _layers(c: Mapping[str, Any]) -> Dict[str, int]:
    return {"all": len(c["indexer_types"]),
            "full": sum(k == "full" for k in c["indexer_types"]),
            "sparse": sum(k == "sparse" for k in c["mlp_layer_types"]),
            "dense": sum(k == "dense" for k in c["mlp_layer_types"])}


def parameters(c: Mapping[str, Any]) -> Dict[str, int]:
    """Parameters by part, of the share this chip holds (``experts_held``);
    norms' scales and the router's bias left out (under 0.1 M)."""
    d, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    mla = (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk + d * latent
           + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"] + c["v_head_dim"])
           + heads * c["v_head_dim"] * d)
    selector = (c["q_lora_rank"] * c["index_n_heads"] * c["index_head_dim"]
                + d * c["index_head_dim"] + d * c["index_n_heads"])
    expert = 3 * d * c["moe_intermediate_size"]
    n = _layers(c)
    parts = {
        "mla": n["all"] * mla,
        "selector": n["full"] * selector,
        "dense_ffn": n["dense"] * 3 * d * c["intermediate_size"],
        "shared_expert": n["sparse"] * expert,
        "router": n["sparse"] * d * c["n_routed_experts"],
        "routed_experts": n["sparse"] * c["experts_held"][1] * expert,
        "embedding_and_head": 2 * c["vocab_size"] * d,
    }
    parts["total"] = sum(parts.values())
    return parts


def cache_bytes_per_token(c: Mapping[str, Any]) -> int:
    """The latent in every layer, the selector's key in ``full`` layers."""
    n = _layers(c)
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return (n["all"] * latent + n["full"] * c["index_head_dim"]) * BF16


def indexer_bytes(context_token_steps: int, c: Mapping[str, Any]) -> int:
    """Every cached selector key of every live row, each step, in each
    ``full`` layer: the selector scores them all."""
    return context_token_steps * c["index_head_dim"] * BF16 * _layers(c)["full"]


def attend_bytes(selected_token_steps: int, c: Mapping[str, Any]) -> int:
    """The selected tokens' latents, each step, in every layer."""
    latent = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return selected_token_steps * latent * BF16 * _layers(c)["all"]


def experts_bytes(experts_run: int, steps: int, c: Mapping[str, Any]) -> int:
    """The weights of the routed experts that were chosen (``experts_run``:
    summed over sparse layers and steps), and each step, in each sparse
    layer, the shared expert and the router."""
    expert = 3 * c["hidden_size"] * c["moe_intermediate_size"] * BF16
    router = c["hidden_size"] * c["n_routed_experts"] * F32
    return experts_run * expert + steps * _layers(c)["sparse"] * (expert + router)


def traced_decode_work(run: Any) -> Dict[str, int]:
    """What the decode dispatches that lie wholly inside the profiler's
    part of the window had to read, from their ``decode_iter`` spans: the
    context scored and the tokens attended over (token-steps, a row's
    context growing by one a step), the routed experts run, and the steps.
    Dispatches cut by an edge are left out, so a share worked out against
    the trace's device time errs low by about one dispatch in all of them."""
    from benchmark.lib import spans

    chunk = run.shapes["decode_chunk"]
    grow = chunk * (chunk - 1) // 2
    work = {"ctx": 0, "sel": 0, "experts_run": 0, "steps": 0, "dispatches": 0}
    lo, hi = run.trace_window
    for row in spans.decode_iterations(run, run.trace_window):
        if "sel_tokens" not in row or row["mono"] + row["dur_ms"] / 1e3 > hi:
            continue
        work["ctx"] += chunk * int(row["ctx_tokens"]) + int(row["n_active"]) * grow
        work["sel"] += chunk * int(row["sel_tokens"])
        work["experts_run"] += int(row["experts_hit"])
        work["steps"] += chunk
        work["dispatches"] += 1
    return work
