"""Device time of the engine's decode-step program per execution, in the
traced window.  Programs are attributed by the
harness's span around each decode launch."""
from devtrace import ms_per_execution


def read(run):
    return ms_per_execution(run.trace, "decode")
