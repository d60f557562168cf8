"""Least time to read and write the recurrent state (``S`` in float32, the
conv's inputs) of the live rows of a decode step, in every Mamba layer
(``n_active`` of the ``decode_iter`` spans inside the profiler's part of the
window, times the chunk's steps: not ``rows_run``, which counts the padded
groups the program steps), over the device time of the scope ``ssm_step``
in the decode program. Memory bound: a megabyte of state a head-row moves for a few
thousand operations."""
from benchmark.lib import flops_granite_hybrid, scope_time_hybrid


def read(run):
    if "decode_chunk" not in run.shapes:
        return None
    work = flops_granite_hybrid.traced_decode_work(run)
    return scope_time_hybrid.roofline(
        run, "ssm_step", scope_time_hybrid.DECODE,
        {"bytes": flops_granite_hybrid.ssm_step_bytes(work["rows"], run.config)})
