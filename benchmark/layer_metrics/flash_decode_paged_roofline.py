"""Least time to read the cached keys and values that the decode steps of
the traced part of the window had to attend over (from the request spans:
prompt plus emitted tokens of every live request at every step) over the
measured time of ``flash_decode_paged``. Memory bound. The two windows are
matched on the host clock, so a dispatch cut by an edge is an error of
about one in the number of dispatches traced."""
from benchmark.lib import flops, spans
from benchmark.lib.harness import say


def read(run):
    if run.profile is None:
        return None
    secs, calls = run.profile.kernel_seconds("flash_decode_paged")
    tokens = spans.context_token_steps(run, run.trace_window)
    if not calls or not tokens:
        return None
    m = run.model
    per_layer = flops.flash_decode(tokens, m["d_model"], 2)
    cost = {k: v * m["n_layers"] for k, v in per_layer.items()}
    least = flops.least_seconds(cost, run.peaks)
    say(f"  flash_decode_paged: {calls} calls, {secs:.4f} s, context read "
        f"{tokens} token-steps, least {least['seconds']:.4f} s "
        f"({least['bound']} bound)")
    return 100.0 * least["seconds"] / secs
