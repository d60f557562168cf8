"""Share of the device's busy time, over every program of the traced part
of the window, spent under the scopes ``ssm_conv``, ``ssm_scan`` (the
chunked recurrence of prefill) and ``ssm_step`` (the one-token update of the
rows' state in a decode step): what the state-space mixers cost beside
their projections."""
from benchmark.lib import scope_time_hybrid


def read(run):
    return scope_time_hybrid.share_of_busy(run, scope_time_hybrid.SSM)
