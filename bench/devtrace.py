"""Reading the profiler's trace of a window into what the metric readers use.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Host and device events there share one clock (nanoseconds from
the start of the trace).  What is kept:

* ``ops``: device operations of each chip's ``XLA Ops`` line, as
  (name, start, end).  Loops nest their bodies' operations, so busy time
  is a union of intervals and an operation's own time is its duration
  less its children's.
* ``modules``: program executions of each chip's ``XLA Modules`` line,
  named ``<program>(<fingerprint>)``: the engine's jitted steps are named
  functions, so its decode step runs as ``jit_decode_step`` and its
  prompt chunks as ``jit_prefill_chunk``.
* ``spans``: the harness's own ``TraceAnnotation`` spans (``bench.*``).
* ``python``: the other host events of the harness's own thread (Python
  calls, where the profiler's Python tracer records them, and JAX's
  dispatches, and the engine's own spans), to name idle gaps.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
NAMED_GAPS = 2000          # longest idle gaps named by the host span


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # chip -> [(name, s, e)]
    modules: dict = field(default_factory=dict)   # chip -> [(name, s, e)]
    spans: list = field(default_factory=list)     # [(name, s, e)]
    python: list = field(default_factory=list)    # [(name, s, e)]

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "spans": self.spans,
                "python": self.python}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda xs: [tuple(x) for x in xs]          # noqa: E731
        return cls(ops={k: tup(v) for k, v in d["ops"].items()},
                   modules={k: tup(v) for k, v in d["modules"].items()},
                   spans=tup(d["spans"]), python=tup(d["python"]))

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
                spans = [x for x in evs if x[0].startswith(SPAN_PREFIX)]
                if spans:               # the harness's own (Python) thread
                    tr.spans.extend(spans)
                    tr.python.extend(x for x in evs
                                     if not x[0].startswith(SPAN_PREFIX))
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

def program_name(module: str) -> str:
    """``jit_decode_step(8264366344727610541)`` -> ``jit_decode_step``."""
    return module.split("(", 1)[0]


def program_runs(trace: Trace, program: str) -> list:
    """For each chip that ran ``program`` in the window, (seconds of device
    time, executions) of its modules of that name."""
    lo, hi = trace.window()
    out = []
    for mods in trace.modules.values():
        t = [e - s for n, s, e in mods
             if lo <= s <= hi and program_name(n) == program]
        if t:
            out.append((sum(t) * 1e-9, len(t)))
    return out


def ms_per_execution(trace: Trace, program: str) -> float | None:
    """Device milliseconds per execution of ``program``, averaged over
    chips; None where it did not run."""
    runs = program_runs(trace, program)
    if not runs:
        return None
    return sum(t / n for t, n in runs) / len(runs) * 1e3


def us_per_token(trace: Trace, program: str, tokens: int) -> float | None:
    """Device microseconds of ``program`` (averaged over chips) per token
    of the ``tokens`` it ran; None where it did not run or ran none."""
    runs = program_runs(trace, program)
    if not runs or tokens == 0:
        return None
    return sum(t for t, _ in runs) / len(runs) / tokens * 1e6


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
