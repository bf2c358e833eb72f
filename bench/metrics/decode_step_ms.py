"""Device time of the engine's decode-step program, ``jit_decode_step``,
per execution in the traced window, read from each chip's ``XLA Modules``
line by the program's name and averaged over chips."""
from devtrace import ms_per_execution

PROGRAM = "jit_decode_step"


def read(run):
    return ms_per_execution(run.trace, PROGRAM)
