"""Least time to read the cached keys and values (8 KV heads, never
repeated) of the live rows' contexts, each decode step, in the attention
layers (``ctx_tokens`` of the ``decode_iter`` spans inside the profiler's
part of the window), over the device time of the scope ``gqa_attend`` in
the decode program. Memory bound."""
from benchmark.lib import flops_granite_hybrid, scope_time_hybrid


def read(run):
    if "decode_chunk" not in run.shapes:
        return None
    work = flops_granite_hybrid.traced_decode_work(run)
    return scope_time_hybrid.roofline(
        run, "gqa_attend", scope_time_hybrid.DECODE,
        {"bytes": flops_granite_hybrid.attend_bytes(work["ctx"], run.config)})
