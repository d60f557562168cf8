"""Seconds in the backend compiler or loading from the persistent cache
during set-up (jax.monitoring duration events; threads overlap)."""


def read(run):
    return run.compile_setup.get("backend_s")
