"""Seconds the XLA backend compiled during set-up (warm-up included);
near 0 when the compilation cache serves every program."""


def read(run):
    return run.compile_setup[0]
