"""Share of the traced window in which a collective ran on a chip and no
other operation did."""


def read(run):
    if run.profile is None or not run.profile.window_s:
        return None
    return 100.0 * run.profile.collective_exposed_s / run.profile.window_s
