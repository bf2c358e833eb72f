"""The engine's own spans in a traced window.

``ContinuousEngine`` opens ``engine.step`` around each ``step`` and
``engine.read`` around each blocking device-to-host read of its tokens
(``src/repro/serving/engine.py``).  ``devtrace.read_xplane`` keeps them in
``Trace.python`` with the harness thread's other host events.  A program
without these spans leaves the readers silent.
"""
from __future__ import annotations

import bisect

from devtrace import busy, overlap

STEP, READ = "engine.step", "engine.read"


def idle_split(trace) -> tuple | None:
    """(number of ``engine.step`` spans in the window, device-idle ns inside
    them, device-idle ns inside the ``engine.read`` spans that lie in
    them).  Device-idle is the complement of the first chip's busy
    intervals, clipped to the window, as ``tick_host_ms`` reads it.  None
    where the window holds no step span or the trace no device op."""
    if not trace.ops:
        return None
    lo, hi = trace.window()
    steps = sorted((s, e) for n, s, e in trace.python
                   if n == STEP and lo <= s and e <= hi)
    if not steps:
        return None
    starts = [s for s, _ in steps]

    def in_step(s, e) -> bool:
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and e <= steps[i][1]

    merged = busy(trace, sorted(trace.ops)[0], lo, hi)

    def idle(s, e) -> float:
        return (e - s) - overlap(merged, s, e)

    return (len(steps), sum(idle(s, e) for s, e in steps),
            sum(idle(s, e) for n, s, e in trace.python
                if n == READ and in_step(s, e)))
