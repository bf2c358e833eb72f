"""Seconds from the start of the process to the opening of the window:
imports, weights, the engine, compiling or loading every program, the
warm-up pass and the ramp."""


def read(run):
    return run.setup_s
