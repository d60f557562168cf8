"""Percent of the live rows' cached tokens that sparse attention selects,
over the window's decode dispatches: ``serving_sparse_selected_tokens_total``
over ``serving_context_tokens_total`` (the server's counters, kept by the
driver as the server stops)."""


def read(run):
    counters = run.shapes.get("counters", {})
    ctx = counters.get("serving_context_tokens_total")
    if not ctx:
        return None
    return 100.0 * counters["serving_sparse_selected_tokens_total"] / ctx
