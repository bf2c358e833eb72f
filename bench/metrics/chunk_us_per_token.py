"""Device time of the engine's prefill-chunk programs over the real prompt
tokens they ran (pads excluded), in the traced window."""
from devtrace import us_per_token


def read(run):
    return us_per_token(run.trace, run.chunk_calls)
