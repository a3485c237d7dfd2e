"""Programs compiled inside the measured window (the target is 0)."""


def read(run):
    return run.compile_window[1]
