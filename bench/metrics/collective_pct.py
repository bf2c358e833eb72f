"""Share of each chip's device-busy time in the traced window spent in
collective operations (the tensor-parallel all-reduces and any other
cross-chip exchange XLA put in: all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all, and the ``-start`` / ``-done`` halves of
their asynchronous forms), as the union of their intervals over the union
of all the chip's op intervals, averaged over the chips.  None where no
collective ran (one chip)."""
from devtrace import length, op_kind, union

_BASE = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
COLLECTIVES = frozenset(b + s for b in _BASE for s in ("", "-start", "-done"))


def read(run):
    lo, hi = run.trace.window()
    shares = []
    for ops in run.trace.ops.values():
        busy = length(union([(s, e) for _, s, e in ops], lo, hi))
        coll = length(union([(s, e) for n, s, e in ops
                             if op_kind(n) in COLLECTIVES], lo, hi))
        if busy > 0 and coll > 0:
            shares.append(coll / busy)
    if not shares:
        return None
    return sum(shares) / len(shares) * 100.0
