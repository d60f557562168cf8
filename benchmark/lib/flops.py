"""Operations and bytes the algorithm requires, from shapes alone.

The benchmark's own arithmetic: MFU and every ``*_roofline`` metric divide
by these numbers, so no later PR can move a share by recounting. Recomputed
work (flash backward's second look at the scores, rematerialised blocks)
never counts: a share says how close the *required* work ran to the chip's
peak. ``cfg`` is a configuration file's ``model`` group (a dict).
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping

Model = Mapping[str, int]


def layer_matmul_params(m: Model) -> int:
    """q, k, v, o projections and the two FFN matrices of one block."""
    return 4 * m["d_model"] * m["d_model"] + 2 * m["d_model"] * m["d_ff"]


def head_params(m: Model) -> int:
    return m["d_model"] * m["vocab_size"]


def n_params(m: Model) -> int:
    """All parameters: blocks (with two LayerNorms), embedding, final
    LayerNorm, untied head."""
    d = m["d_model"]
    per_layer = layer_matmul_params(m) + 4 * d
    return m["n_layers"] * per_layer + 2 * head_params(m) + 2 * d


def train_flops_per_token(m: Model, seq: int) -> float:
    """Forward + backward of one token at sequence length ``seq``: 6 per
    matmul parameter (head included, embedding lookup excluded) plus causal
    attention, 2*seq*d forward per layer per token (QK^T and PV, halved by
    the mask) and twice that backward."""
    matmul = m["n_layers"] * layer_matmul_params(m) + head_params(m)
    attention = m["n_layers"] * 6 * seq * m["d_model"]
    return 6.0 * matmul + attention


def prefill_flops(m: Model, rows: int, plen: int) -> float:
    """Forward of ``rows`` prompts of ``plen``; the head runs on the last
    position only (that is all the first token needs)."""
    tokens = rows * plen
    blocks = 2.0 * m["n_layers"] * layer_matmul_params(m) * tokens
    attention = m["n_layers"] * 2.0 * plen * m["d_model"] * tokens
    return blocks + attention + 2.0 * head_params(m) * rows


def decode_step_flops(m: Model, context_lens: Iterable[int]) -> float:
    """One token for each live row: every matmul parameter twice, plus
    attention over the row's cached context."""
    lens = list(context_lens)
    matmul = m["n_layers"] * layer_matmul_params(m) + head_params(m)
    attention = m["n_layers"] * 4.0 * m["d_model"] * sum(lens)
    return 2.0 * matmul * len(lens) + attention


def kv_bytes_per_token(m: Model, kv_itemsize: int) -> int:
    """K and V of one cached position across all layers."""
    return 2 * m["n_layers"] * m["d_model"] * kv_itemsize


def decode_step_bytes(m: Model, context_lens: Iterable[int],
                      param_itemsize: int, kv_itemsize: int) -> float:
    """One decode step reads every block and head weight once (as stored)
    and each live row's cached keys and values."""
    weights = (m["n_layers"] * (layer_matmul_params(m) + 4 * m["d_model"])
               + head_params(m)) * param_itemsize
    return weights + kv_bytes_per_token(m, kv_itemsize) * sum(context_lens)


# -- one kernel call ----------------------------------------------------------
# Each returns {"flops", "bytes"}: what one pallas_call must compute and move.


def flash_attention_fwd(b: int, h: int, s: int, d: int, itemsize: int,
                        causal: bool = True) -> Dict[str, float]:
    div = 2 if causal else 1
    return {"flops": 4 * b * h * s * s * d // div,        # QK^T and PV
            "bytes": 4 * b * h * s * d * itemsize}        # read q,k,v; write o


def flash_attention_bwd(b: int, h: int, s: int, d: int, itemsize: int,
                        causal: bool = True) -> Dict[str, float]:
    """dV, dP, dQ, dK: four matmuls of 2*b*h*s*s*d each (halved by the
    mask). The kernel's fifth, the recomputed QK^T, is not required work."""
    div = 2 if causal else 1
    return {"flops": 4 * (2 * b * h * s * s * d // div),
            # read q,k,v,o,do; write dq,dk,dv
            "bytes": 8 * b * h * s * d * itemsize}


def fused_ce_fwd(n: int, v: int, itemsize: int) -> Dict[str, float]:
    """Online log-sum-exp over [n, v] logits read once: max, subtract, exp,
    add, and the gather compare: 5 per element."""
    return {"flops": 5 * n * v, "bytes": n * v * itemsize}


def fused_ce_bwd(n: int, v: int, itemsize: int) -> Dict[str, float]:
    """softmax - onehot, scaled: 3 per element; logits in, gradient out."""
    return {"flops": 3 * n * v, "bytes": 2 * n * v * itemsize}


def flash_decode(context_tokens: int, d_model: int,
                 kv_itemsize: int) -> Dict[str, float]:
    """One layer's single-query attention over ``context_tokens`` cached
    positions in total (summed over the live rows): q.K^T and p.V, and one
    read of those keys and values. Queries and outputs are negligible."""
    return {"flops": 4 * context_tokens * d_model,
            "bytes": 2 * context_tokens * d_model * kv_itemsize}


def least_seconds(cost: Mapping[str, float],
                  peaks: Mapping[str, float]) -> Dict[str, object]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak HBM bytes/s, and which of the two it is."""
    compute = cost["flops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
