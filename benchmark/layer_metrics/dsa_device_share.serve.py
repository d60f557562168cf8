"""Share of the device's busy time, over every program of the traced part
of the window, spent under the scopes ``dsa_indexer`` (the selector's
scores and top-k) and ``dsa_attend`` (the gather of the selected latents and
the absorbed attention over them): what context length costs."""
from benchmark.lib import scope_time


def read(run):
    return scope_time.share_of_busy(run, ("dsa_indexer", "dsa_attend"))
