"""Share of the pages the paged decode kernel's grid visits that hold a
decoding sequence's keys and values, over the traced window: for each
decode launch (``Run.decode_calls``), ``ceil(kv_len / page_size)`` pages
per decoding row, over the grid's ``n_slots x ceil(max_seq / page_size)``
pages (the engine's block table).  The engine keeps the same two sums as
``decode_live_pages_total`` and ``decode_grid_pages_total``."""
from model import load_config


def read(run):
    if not run.decode_calls:
        return None
    eng = load_config(run.cell["config"])["engine"]
    ps = eng["page_size"]
    live = sum(-(-kv // ps) for kv_lens in run.decode_calls for kv in kv_lens)
    grid = eng["n_slots"] * -(-eng["max_seq"] // ps)
    return live / (grid * len(run.decode_calls)) * 100.0
