"""Mean live slots per decode dispatch (``n_active`` of the ``decode_iter``
spans) over ``max_slots``."""
from benchmark.lib import spans


def read(run):
    its = spans.decode_iterations(run)
    if not its:
        return None
    mean = sum(r["n_active"] for r in its) / len(its)
    return 100.0 * mean / run.shapes["max_slots"]
