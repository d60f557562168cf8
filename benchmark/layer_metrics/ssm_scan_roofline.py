"""Least time for the chunked recurrence over the prompts prefilled inside
the profiler's part of the window (the ``prefill`` spans' ``plen``): the
larger of its operations at the chip's bfloat16 peak and its bytes at the
HBM peak (``flops_granite_hybrid.ssm_scan_cost``), over the device time of
the scope ``ssm_scan`` in the prefill programs."""
from benchmark.lib import flops_granite_hybrid, scope_time_hybrid


def read(run):
    work = flops_granite_hybrid.traced_prefill_work(run)
    if not work["tokens"]:
        return None
    return scope_time_hybrid.roofline(
        run, "ssm_scan", scope_time_hybrid.PREFILL,
        flops_granite_hybrid.ssm_scan_cost(work["tokens"], work["prompts"],
                                           run.config))
