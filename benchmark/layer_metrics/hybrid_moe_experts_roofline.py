"""Least time to read the weights of the held experts that live rows chose
(``experts_hit`` of the ``decode_iter`` spans inside the profiler's part of
the window), and the shared MLP and the router each step in each layer,
over the device time of the scope ``moe_experts`` in the decode program, at
this configuration's sizes (``flops_granite_hybrid.experts_bytes``). Memory
bound at decode batch sizes."""
from benchmark.lib import flops_granite_hybrid, scope_time_hybrid


def read(run):
    if "decode_chunk" not in run.shapes:
        return None
    work = flops_granite_hybrid.traced_decode_work(run)
    return scope_time_hybrid.roofline(
        run, "moe_experts", scope_time_hybrid.DECODE,
        {"bytes": flops_granite_hybrid.experts_bytes(
            work["experts_run"], work["steps"], run.config)})
