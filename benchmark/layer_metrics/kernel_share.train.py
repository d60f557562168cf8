"""Share of device busy time spent in the Pallas kernels of the step."""
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_fused",
           "fused_ce_fwd", "fused_ce_bwd")


def read(run):
    if run.profile is None or not run.profile.busy_s:
        return None
    secs = sum(run.profile.kernel_seconds(k)[0] for k in KERNELS)
    return 100.0 * secs / run.profile.busy_s
