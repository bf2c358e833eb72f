"""Host time per engine tick: the harness's span around each
``ContinuousEngine.step`` less the device's busy time inside it, averaged
over the traced window's ticks."""
from devtrace import busy, overlap


def read(run):
    tr = run.trace
    ticks = tr.span_list("bench.tick")
    if not ticks or not tr.ops:
        return None
    lo, hi = tr.window()
    merged = busy(tr, sorted(tr.ops)[0], lo, hi)
    host = [(e - s) - overlap(merged, s, e) for s, e in ticks]
    return sum(host) / len(host) * 1e-6
