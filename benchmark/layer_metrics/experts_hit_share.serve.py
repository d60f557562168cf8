"""Percent of the held experts that a decode step runs, over the window's
decode steps and sparse layers: ``serving_experts_run_total`` over (decode
dispatches x steps a dispatch x sparse layers x experts held). The rest
were chosen by no live row and their weights were not read."""


def read(run):
    counters = run.shapes.get("counters", {})
    dispatches = counters.get("serving_decode_batches_total")
    if not dispatches or "serving_experts_run_total" not in counters:
        return None
    c = run.config
    sparse = sum(kind == "sparse" for kind in c["mlp_layer_types"])
    possible = (dispatches * run.shapes["decode_chunk"] * sparse
                * c["experts_held"][1])
    return 100.0 * counters["serving_experts_run_total"] / possible
