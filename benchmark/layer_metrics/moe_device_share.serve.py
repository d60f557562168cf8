"""Share of the device's busy time, over every program of the traced part
of the window, spent under the scope ``moe_experts``: the router, the held
experts that were chosen and the shared expert."""
from benchmark.lib import scope_time


def read(run):
    return scope_time.share_of_busy(run, ("moe_experts",))
