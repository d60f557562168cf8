"""Least time to read the weights of the held experts that live rows chose
(``experts_hit`` of the ``decode_iter`` spans inside the profiler's part of
the window), and the shared expert and the router each step, over the
device time of the scope ``moe_experts`` in the decode program. Memory
bound at decode batch sizes."""
from benchmark.lib import flops_glm_dsa, scope_time


def read(run):
    work = flops_glm_dsa.traced_decode_work(run)
    return scope_time.decode_roofline(
        run, "moe_experts", flops_glm_dsa.experts_bytes(
            work["experts_run"], work["steps"], run.config))
