"""Least time to read every cached selector key of the live rows, each
decode step, in each ``full`` layer (from the ``decode_iter`` spans inside
the profiler's part of the window) over the device time of the scope
``dsa_indexer`` in the decode program. Memory bound: the selector scores
every cached key of a row."""
from benchmark.lib import flops_glm_dsa, scope_time


def read(run):
    work = flops_glm_dsa.traced_decode_work(run)
    return scope_time.decode_roofline(
        run, "dsa_indexer",
        flops_glm_dsa.indexer_bytes(work["ctx"], run.config))
