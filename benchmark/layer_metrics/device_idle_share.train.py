"""1 - (union of the device's op intervals) / traced window, mean over the
chips used (lib/xplane.py)."""


def read(run):
    return None if run.profile is None else 100.0 * run.profile.idle_share
