"""Share of the decode step's rows that held a decoding sequence, over the
traced window: the decoding rows of each decode launch (the harness's
record of the launch, ``Run.decode_calls``) over the rows it ran, every
one of the cell's ``n_slots``.  The engine keeps the same two sums as
``decode_tokens_total`` and ``decode_rows_total``."""
from model import load_config


def read(run):
    if not run.decode_calls:
        return None
    n_slots = load_config(run.cell["config"])["engine"]["n_slots"]
    rows = sum(len(kv_lens) for kv_lens in run.decode_calls)
    return rows / (n_slots * len(run.decode_calls)) * 100.0
