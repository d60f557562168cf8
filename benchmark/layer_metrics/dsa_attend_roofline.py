"""Least time to read the latents of the tokens the selector chose, each
decode step, in every layer (``sel_tokens`` of the ``decode_iter`` spans
inside the profiler's part of the window) over the device time of the scope
``dsa_attend`` in the decode program. Memory bound."""
from benchmark.lib import flops_glm_dsa, scope_time


def read(run):
    work = flops_glm_dsa.traced_decode_work(run)
    return scope_time.decode_roofline(
        run, "dsa_attend",
        flops_glm_dsa.attend_bytes(work["sel"], run.config))
