"""Programs compiled or loaded inside the measured window; expected 0."""


def read(run):
    return run.compile_window.get("programs")
