"""Arithmetic of the ``granitemoehybrid`` share: parameters, per-slot state and
cache bytes, and the bytes and operations each named scope *must* move,
counted from the work (which rows were stepped, which experts were chosen,
how long the prompts were) and not from how the program does it, so that a
later kernel is read on the same yardstick. ``c`` is the configuration
file's dict.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

BF16 = 2
F32 = 4


def _layers(c: Mapping[str, Any]) -> Dict[str, int]:
    kinds = c["layer_types"]
    return {"all": len(kinds), "mamba": sum(k == "mamba" for k in kinds),
            "attention": sum(k == "attention" for k in kinds)}


def _mixer(c: Mapping[str, Any]) -> Dict[str, int]:
    heads, p, n = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    d_inner = heads * p
    return {"heads": heads, "p": p, "n": n, "d_inner": d_inner,
            "conv_dim": d_inner + 2 * c["mamba_n_groups"] * n,
            "state": heads * p * n}


def parameters(c: Mapping[str, Any]) -> Dict[str, int]:
    """Parameters by part, of the share this chip holds (``experts_held``,
    the vocabulary slice; the embedding is tied and counted once)."""
    d, m, n = c["hidden_size"], _mixer(c), _layers(c)
    hd = d // c["num_attention_heads"]
    mamba = (d * (m["d_inner"] + m["conv_dim"] + m["heads"]) + m["d_inner"] * d
             + (c["mamba_d_conv"] + 1) * m["conv_dim"] + 3 * m["heads"]
             + m["d_inner"])
    attention = 2 * d * d + 2 * d * c["num_key_value_heads"] * hd
    expert = 3 * d * c["intermediate_size"]
    parts = {
        "mamba": n["mamba"] * mamba,
        "attention": n["attention"] * attention,
        "shared_mlp": n["all"] * 3 * d * c["shared_intermediate_size"],
        "router": n["all"] * d * c["num_local_experts"],
        "routed_experts": n["all"] * c["experts_held"][1] * expert,
        "norms": (2 * n["all"] + 1) * d,
        "embedding": c["vocab_size"] * d,
    }
    parts["total"] = sum(parts.values())
    return parts


def state_bytes_per_slot(c: Mapping[str, Any]) -> int:
    """A row's recurrent state over the Mamba layers: ``S`` in float32 and
    the conv's last ``K - 1`` inputs in bfloat16."""
    m = _mixer(c)
    return _layers(c)["mamba"] * (
        m["state"] * F32 + (c["mamba_d_conv"] - 1) * m["conv_dim"] * BF16)


def cache_bytes_per_token(c: Mapping[str, Any]) -> int:
    """K and V of the KV heads, in the attention layers."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return _layers(c)["attention"] * 2 * c["num_key_value_heads"] * hd * BF16


def ssm_step_bytes(rows_stepped: int, c: Mapping[str, Any]) -> int:
    """A row's state read and written once a step in every Mamba layer
    (``rows_stepped``: rows summed over steps)."""
    return 2 * rows_stepped * state_bytes_per_slot(c)


def ssm_scan_cost(prompt_tokens: int, prompts: int,
                  c: Mapping[str, Any]) -> Dict[str, float]:
    """The chunked form's work over ``prompt_tokens`` tokens of ``prompts``
    prompts, all Mamba layers. Operations a token: the causal half of the
    chunk's ``C B^T`` (``Q N``) and of its product with ``dt x`` (``Q H P``),
    the carried state's part and the state's update (``2 H P N`` each). Bytes:
    ``x, B, C`` read in bfloat16 and ``dt`` in float32, ``y`` written in
    float32, and a prompt's state read and written once."""
    m, q = _mixer(c), c["mamba_chunk_size"]
    layers = _layers(c)["mamba"]
    flops = prompt_tokens * (q * m["n"] + q * m["d_inner"] + 4 * m["state"])
    per_token = m["conv_dim"] * BF16 + m["heads"] * F32 + m["d_inner"] * F32
    nbytes = prompt_tokens * per_token + prompts * 2 * m["state"] * F32
    return {"flops": layers * flops, "bytes": layers * nbytes}


def experts_bytes(experts_run: int, steps: int, c: Mapping[str, Any]) -> int:
    """The weights of the routed experts that were chosen (``experts_run``:
    summed over layers and steps), and each step, in each layer, the shared
    MLP and the router."""
    d = c["hidden_size"]
    expert = 3 * d * c["intermediate_size"] * BF16
    always = (3 * d * c["shared_intermediate_size"] * BF16
              + d * c["num_local_experts"] * F32)
    return experts_run * expert + steps * _layers(c)["all"] * always


def attend_bytes(context_token_steps: int, c: Mapping[str, Any]) -> int:
    """Every cached key and value of the live rows, each step."""
    return context_token_steps * cache_bytes_per_token(c)


def traced_decode_work(run: Any) -> Dict[str, int]:
    """What the decode dispatches that lie wholly inside the profiler's part
    of the window did, from their ``decode_iter`` spans: live rows over the
    dispatch's steps (``n_active`` x the chunk: the rows whose state the
    mathematics steps, whatever groups the program steps them in; the
    span's ``rows_run`` counts those groups and is the counter's), routed
    experts run, steps, and the context attended over (token-steps, a row's
    context growing by one a step).
    Dispatches cut by an edge are left out, so a share worked out against
    the trace's device time errs low by about one dispatch in all of them.
    Empty where the program emits no such spans."""
    from benchmark.lib import spans

    chunk = run.shapes["decode_chunk"]
    grow = chunk * (chunk - 1) // 2
    work = {"rows": 0, "experts_run": 0, "steps": 0, "ctx": 0, "dispatches": 0}
    hi = run.trace_window[1]
    for row in spans.decode_iterations(run, run.trace_window):
        if "experts_hit" not in row or row["mono"] + row["dur_ms"] / 1e3 > hi:
            continue
        work["rows"] += chunk * int(row["n_active"])
        work["experts_run"] += int(row["experts_hit"])
        work["ctx"] += chunk * int(row["ctx_tokens"]) + int(row["n_active"]) * grow
        work["steps"] += chunk
        work["dispatches"] += 1
    return work


def traced_prefill_work(run: Any) -> Dict[str, int]:
    """Prompts and prompt tokens of the ``prefill`` spans that lie wholly
    inside the profiler's part of the window (one span a request)."""
    from benchmark.lib import spans

    hi = run.trace_window[1]
    rows = [s for s in spans.in_window(run.spans, "prefill", run.trace_window)
            if s["mono"] + s["dur_ms"] / 1e3 <= hi]
    return {"prompts": len(rows), "tokens": sum(int(s["plen"]) for s in rows)}
