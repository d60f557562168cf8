"""Least time the chip could take for one fused attention backward at the
cell's shapes (lib/flops.py, required work only) over its measured time."""
from benchmark.lib import flops
from benchmark.lib.harness import say


def read(run):
    if run.profile is None:
        return None
    secs, calls = run.profile.kernel_seconds("flash_attention_bwd_fused")
    if not calls:
        return None
    m = run.model
    cost = flops.flash_attention_bwd(
        run.shapes["batch_per_chip"], m["n_heads"], run.shapes["seq"],
        m["d_model"] // m["n_heads"], 2)
    least = flops.least_seconds(cost, run.peaks)
    say(f"  flash_attention_bwd_fused: {calls} calls, {secs / calls * 1e6:.1f} "
        f"us each, least {least['seconds'] * 1e6:.1f} us ({least['bound']} bound)")
    return 100.0 * least["seconds"] * calls / secs
