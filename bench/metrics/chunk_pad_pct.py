"""Share of the prompt-chunk width run that is padding, over the traced
window: each chunk launch's real tokens (``Run.chunk_calls``) against the
width it ran at, the program's own ``chunk_bucket`` of them.  The engine
keeps the same two sums as ``chunk_bucket_tokens_total`` and
``prefill_tokens_total``.  Silent for a program without ``chunk_bucket``."""
from model import load_config


def read(run):
    try:
        from repro.serving.engine import chunk_bucket
    except ImportError:
        return None
    if not run.chunk_calls:
        return None
    max_seq = load_config(run.cell["config"])["engine"]["max_seq"]
    real = sum(n for _, n in run.chunk_calls)
    width = sum(chunk_bucket(n, max_seq) for _, n in run.chunk_calls)
    return (width - real) / width * 100.0
