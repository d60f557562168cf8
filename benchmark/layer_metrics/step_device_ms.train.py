"""Device busy time per optimizer step in the traced part of the window.
Steps are counted on the device's side: one fused attention backward per
layer per step."""


def read(run):
    if run.profile is None:
        return None
    _, calls = run.profile.kernel_seconds("flash_attention_bwd_fused")
    steps = calls / run.model["n_layers"]
    return run.profile.busy_s / steps * 1e3 if steps else None
