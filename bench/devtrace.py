"""Reading the profiler's trace of a window into what the metric readers use.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Host and device events there share one clock (nanoseconds from
the start of the trace).  What is kept:

* ``ops``: device operations of each chip's ``XLA Ops`` line, as
  (name, start, end).  Loops nest their bodies' operations, so busy time
  is a union of intervals and an operation's own time is its duration
  less its children's.
* ``modules``: program executions of each chip's ``XLA Modules`` line.
* ``execs``: host launches of programs (``PJRT_LoadedExecutable_Execute``),
  one per module execution and in the same order.
* ``spans``: the harness's own ``TraceAnnotation`` spans (``bench.*``).
* ``python``: the other host events of the harness's own thread (Python
  calls, where the profiler's Python tracer records them, and JAX's
  dispatches), to name idle gaps.

A module is attributed to the engine's decode step or chunk step when its
launch lies inside the harness's ``bench.decode`` or ``bench.chunk`` span.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

EXEC_EVENT = "PJRT_LoadedExecutable_Execute"
SPAN_PREFIX = "bench."
NAMED_GAPS = 2000          # longest idle gaps named by the host span


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # chip -> [(name, s, e)]
    modules: dict = field(default_factory=dict)   # chip -> [(name, s, e)]
    execs: list = field(default_factory=list)     # [start]
    spans: list = field(default_factory=list)     # [(name, s, e)]
    python: list = field(default_factory=list)    # [(name, s, e)]

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "execs": self.execs,
                "spans": self.spans, "python": self.python}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda xs: [tuple(x) for x in xs]          # noqa: E731
        return cls(ops={k: tup(v) for k, v in d["ops"].items()},
                   modules={k: tup(v) for k, v in d["modules"].items()},
                   execs=list(d["execs"]), spans=tup(d["spans"]),
                   python=tup(d["python"]))

    # -- windows -----------------------------------------------------------
    def span_list(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == name]

    def window(self) -> tuple:
        """(start, end) of the traced window: the harness's ticks."""
        ticks = self.span_list("bench.tick")
        if not ticks:
            raise ValueError("trace holds no bench.tick span")
        return ticks[0][0], ticks[-1][1]


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def read_xplane(log_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":          # "%name.N = <HLO>"
                    tr.ops[chip] = [(e.name.split(" = ", 1)[0], e.start_ns,
                                     e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    tr.modules[chip] = [(e.name, e.start_ns, e.end_ns)
                                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                tr.execs.extend(s for n, s, _ in evs if n == EXEC_EVENT)
                spans = [x for x in evs if x[0].startswith(SPAN_PREFIX)]
                if spans:               # the harness's own (Python) thread
                    tr.spans.extend(spans)
                    tr.python.extend(x for x in evs
                                     if not x[0].startswith(SPAN_PREFIX))
    tr.execs.sort()
    tr.spans.sort(key=lambda x: x[1])
    tr.python.sort(key=lambda x: x[1])
    for v in tr.ops.values():
        v.sort(key=lambda x: x[1])
    for v in tr.modules.values():
        v.sort(key=lambda x: x[1])
    return tr


# -- interval arithmetic ----------------------------------------------------

def union(intervals, lo=None, hi=None) -> list:
    """Merged intervals, clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def overlap(merged, s, e) -> float:
    """Length of [s, e] covered by sorted merged intervals."""
    tot = 0.0
    for a, b in merged:
        if b <= s:
            continue
        if a >= e:
            break
        tot += min(b, e) - max(a, s)
    return tot


def busy(trace: Trace, chip: str, lo, hi) -> list:
    return union([(s, e) for _, s, e in trace.ops.get(chip, [])], lo, hi)


# -- names ------------------------------------------------------------------

_OP = re.compile(r"^%?([A-Za-z_][\w\-]*?)(\.\d+)?(\s*=.*)?$", re.S)


def op_kind(name: str) -> str:
    """``%paged_decode_attention_kernel.9 = bf16[...] custom-call(...)`` ->
    ``paged_decode_attention_kernel``."""
    m = _OP.match(name.split(" = ", 1)[0].strip())
    return m.group(1) if m else name.split(" ", 1)[0]


def self_times(events) -> list:
    """(name, own time) of properly nested events on one line."""
    out = []
    stack = []                      # [name, start, end, child_time]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n0, s0, e0, c0 = stack.pop()
            out.append((n0, (e0 - s0) - c0))
            if stack:
                stack[-1][3] += e0 - s0
        stack.append([name, s, e, 0.0])
    while stack:
        n0, s0, e0, c0 = stack.pop()
        out.append((n0, (e0 - s0) - c0))
        if stack:
            stack[-1][3] += e0 - s0
    return out


# -- programs ---------------------------------------------------------------

def module_kinds(trace: Trace, chip: str) -> list | None:
    """[(kind, start, end)] for each module execution on ``chip``: kind is
    the harness span (``decode``, ``chunk``) whose launch it is, else None.
    A span can launch small programs besides the step (the conversion of
    its scalar arguments); the longest module a span launched is its
    step.  Returns None where launches and executions do not pair up."""
    mods = trace.modules.get(chip, [])
    lo, hi = trace.window()
    mods = [m for m in mods if lo <= m[1] <= hi]
    execs = [t for t in trace.execs if lo <= t <= hi]
    if not mods or len(execs) != len(mods):
        return None
    calls = sorted([(s, e, n[len(SPAN_PREFIX):]) for n, s, e in trace.spans
                    if n in ("bench.decode", "bench.chunk")])
    step = {}                       # span index -> longest module index
    j = 0
    for i, (t, (_, s, e)) in enumerate(zip(execs, mods)):
        while j < len(calls) and calls[j][1] < t:
            j += 1
        if j < len(calls) and calls[j][0] <= t:
            k = step.get(j)
            if k is None or e - s > mods[k][2] - mods[k][1]:
                step[j] = i
    kinds = {i: calls[j][2] for j, i in step.items()}
    return [(kinds.get(i), s, e) for i, (_, s, e) in enumerate(mods)]


def program_time(trace: Trace, kind: str) -> tuple | None:
    """(seconds of device time, executions) of the ``kind`` programs,
    averaged over chips; None when the trace cannot attribute them."""
    per_chip = []
    for chip in trace.modules:
        mk = module_kinds(trace, chip)
        if mk is None:
            return None
        sel = [(s, e) for k, s, e in mk if k == kind]
        per_chip.append((sum(e - s for s, e in sel) * 1e-9, len(sel)))
    if not per_chip:
        return None
    return (sum(p[0] for p in per_chip) / len(per_chip), per_chip[0][1])


def ms_per_execution(trace: Trace, kind: str) -> float | None:
    """Device milliseconds per execution of the ``kind`` program."""
    pt = program_time(trace, kind)
    if pt is None or pt[1] == 0:
        return None
    return pt[0] / pt[1] * 1e3


def us_per_token(trace: Trace, chunk_calls) -> float | None:
    """Device microseconds of the chunk programs per real prompt token
    they ran; ``chunk_calls`` are the launches' (pos_offset, n_valid)."""
    pt = program_time(trace, "chunk")
    tokens = sum(n for _, n in chunk_calls)
    if pt is None or pt[1] == 0 or tokens == 0:
        return None
    return pt[0] / tokens * 1e6


def kernel_time(trace: Trace, kernel: str) -> float | None:
    """Seconds of device time in ops named ``kernel`` (averaged over
    chips), inside the window; None when no such op ran."""
    lo, hi = trace.window()
    per_chip = []
    for ops in trace.ops.values():
        t = [e - s for n, s, e in ops if lo <= s <= hi and op_kind(n) == kernel]
        if t:
            per_chip.append(sum(t) * 1e-9)
    return sum(per_chip) / len(per_chip) if per_chip else None


def device_busy(trace: Trace) -> tuple:
    """(busy seconds averaged over chips, window seconds)."""
    lo, hi = trace.window()
    chips = list(trace.ops) or ["0"]
    b = [length(busy(trace, c, lo, hi)) for c in chips]
    return sum(b) / len(b) * 1e-9, (hi - lo) * 1e-9


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (own time, by kind) and the
    longest idle gaps, each named by the innermost host Python span open
    at its middle."""
    lo, hi = trace.window()
    chip = sorted(trace.ops)[0] if trace.ops else None
    if chip is None:
        return {"device_ops": [], "idle_gaps": []}
    ops = [o for o in trace.ops[chip] if lo <= o[1] <= hi]
    own = defaultdict(float)
    for n, t in self_times(ops):
        own[op_kind(n)] += t
    dev = sorted(own.items(), key=lambda kv: -kv[1])[:top]
    merged = busy(trace, chip, lo, hi)
    gaps, prev = [], lo
    for s, e in merged:
        if s > prev:
            gaps.append((prev, s))
        prev = e
    if hi > prev:
        gaps.append((prev, hi))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    starts = [p[1] for p in trace.python]
    named = defaultdict(float)
    for s, e in gaps[:NAMED_GAPS]:
        named[_host_at(trace, starts, (s + e) / 2)] += e - s
    idle = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t * 1e-9] for n, t in dev],
            "idle_gaps": [[n, t * 1e-9] for n, t in idle]}


def _host_at(trace: Trace, starts: list, t) -> str:
    """The innermost host Python span open at ``t``: spans nest, so it is
    the latest-starting one that has not ended."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        n, s, e = trace.python[i]
        if e >= t:
            return n
    return "no host span"


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
