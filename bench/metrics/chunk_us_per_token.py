"""Device time of the engine's prompt-chunk program, ``jit_prefill_chunk``,
in the traced window (read from each chip's ``XLA Modules`` line by the
program's name and averaged over chips), over the real prompt tokens of
the chunk launches the harness recorded (``Run.chunk_calls``; pads
excluded).  Verify launches run the same program, through the same
``_run_chunk``, so their draft tokens would count as well; no cell carries
drafts."""
from devtrace import us_per_token

PROGRAM = "jit_prefill_chunk"


def read(run):
    return us_per_token(run.trace, PROGRAM, sum(n for _, n in run.chunk_calls))
