"""One run of one cell: set up, warm up, measure, check, report.

The system under test is ``repro.serving.engine.ContinuousEngine``,
driven through ``submit`` and ``step`` as a deployment drives it.  Each
``step`` ends in a host read of the step's tokens, so it returns when the
device has finished, and the host clock around it times real work.  The
mix's backlog is submitted at the start; the engine admits from it as
slots and pool pages free up.

The run, in order:

1. set-up (``setup_s``): weights from the seed, the engine, one pass of
   warm-up requests that runs every program shape the cell's traffic
   uses, and the ramp until every request admitted at the first tick
   has its first token;
2. the window: ``seconds`` of serving (with ``trace``, under the
   profiler, the harness's span around each tick, and a record of the
   work each decode and chunk launch carries);
3. the peak memory of each of the cell's devices is read and the
   engine's state freed;
4. the check: the float32 reference runs over a sample of the requests
   finished in the window, one from each slot that finished one
   (``bench/reference.py``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
RAMP_CAP_S = 120.0
REFERENCE_BATCH = 8            # sequences per reference pass
SAMPLE_TOKENS = 512            # served tokens the check aims for
MIN_SAMPLE_TOKENS = 128


class NoAccelerator(RuntimeError):
    pass


@dataclass
class RequestLog:
    rid: int
    prompt: np.ndarray
    max_new: int
    slot: int = -1                               # engine slot that served it
    admitted_tick: int = -1
    times: list = field(default_factory=list)    # host time of each token
    finished: float | None = None
    tokens: np.ndarray | None = None


@dataclass
class Run:
    """What the metric readers see."""
    cell: dict
    dims: dict
    peak: dict | None                # one chip's published peaks
    chips: int                       # the cell's chips
    seconds: float
    setup_s: float
    window: tuple
    ticks: list                      # (start, end) of each window tick
    requests: list                   # RequestLog, every request submitted
    decode_calls: list = field(default_factory=list)   # kv_lens per call
    chunk_calls: list = field(default_factory=list)    # (pos_offset, n_valid)
    trace: object = None


# -- specification ----------------------------------------------------------

def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: dict, per_layer: bool) -> list:
    """The metrics this cell reports: end-to-end ones (without trace) or
    per-layer ones (with), each where its ``workloads`` names the cell or,
    without that key, wherever the end-to-end metric it moves is reported."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_limits(cell_name: str, data: Path = BENCH) -> dict:
    return json.loads((data / "limits" / f"{cell_name}.json").read_text())


def reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or, for a
    metric split by cell such as ``output_tok_s.qwen1.5-4b``, the reader of
    the part before the first dot."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- device -----------------------------------------------------------------

def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileCounter:
    """Counts programs traced for the first time while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0

        def listen(event, duration, **kw):
            if self.on and event == "/jax/core/compile/jaxpr_trace_duration":
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# -- the engine -------------------------------------------------------------

def build_engine(spec: dict, seed: int, chips: int):
    """The engine over the cell's weights.  A cell on one chip runs on the
    first device.  A cell on several chips is served tensor-parallel over
    the first ``chips`` devices (the program's serving mesh, every device
    on the ``model`` axis): its weights are made in place on them and the
    engine shards its pool and steps over them."""
    import jax

    from model import model_config, served_params
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.engine import ContinuousEngine

    cfg = model_config(spec)
    mesh = None if chips == 1 else make_serving_mesh(chips)
    params = served_params(spec, seed, mesh)
    jax.block_until_ready(params)
    return ContinuousEngine(cfg, params, queue_capacity=None, kv_layout="paged",
                            mesh=mesh, **spec["engine"])


def warm_up(eng, spec: dict) -> None:
    """Run every program shape the cell's traffic can use: one decode step
    (its shape is the slot count) and each chunk bucket up to the prefill
    budget, each ending a prompt, then a decode that finishes."""
    from repro.serving.batching import Request

    budget = spec["engine"]["prefill_budget_tokens"]
    rng = np.random.default_rng(0)
    b, reqs = 8, []
    while b <= budget:
        reqs.append(Request(prompt=rng.integers(0, eng.cfg.vocab_size, b,
                                                dtype=np.int32), max_new=2))
        b *= 2
    eng.run(reqs)
    eng.results.clear()
    eng.finish_order.clear()


class Observer:
    """Follows the engine's slots after each step: which request emitted
    how many tokens when."""

    def __init__(self, eng):
        self.eng = eng
        self.logs: dict = {}
        self._n_finished = 0
        self.tick = 0

    def add(self, log: RequestLog) -> None:
        self.logs[log.rid] = log

    def after_step(self, now: float) -> None:
        eng = self.eng
        for i, st in enumerate(eng.slots.states):
            if st is not None:
                self.logs[st.request.rid].slot = i
                self._seen(st.request.rid, len(st.emitted), now)
        for rid in eng.finish_order[self._n_finished:]:
            res = eng.results[rid]
            self._seen(rid, len(res.tokens), now)
            log = self.logs[rid]
            log.finished = now
            log.tokens = res.tokens
        self._n_finished = len(eng.finish_order)
        self.tick += 1

    def _seen(self, rid: int, n: int, now: float) -> None:
        log = self.logs[rid]
        if log.admitted_tick < 0:
            log.admitted_tick = self.tick
        k = n - len(log.times)
        if k > 0:
            log.times.extend([now] * k)


class Tracer:
    """With ``--trace 1``: the work each decode and chunk launch carries."""

    def __init__(self, eng):
        self.decode_calls, self.chunk_calls = [], []
        self.recording = False
        decode, run_chunk = eng._decode, eng._run_chunk
        states = eng.slots.states

        def traced_decode(*args, **kw):
            if self.recording:
                self.decode_calls.append(
                    [st.pos + 1 for st in states
                     if st is not None and st.phase == "decode"])
            return decode(*args, **kw)

        def traced_chunk(toks, n_valid, pos_offset, bt):
            if self.recording:
                self.chunk_calls.append((int(pos_offset), int(n_valid)))
            return run_chunk(toks, n_valid, pos_offset, bt)

        eng._decode = traced_decode
        eng._run_chunk = traced_chunk


def free_engine(eng) -> None:
    import jax

    for leaf in jax.tree.leaves((eng.params, eng.slots.cache)):
        leaf.delete()
    gc.collect()


# -- the run ----------------------------------------------------------------

def run_cell(bench: dict, name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, device: dict | None, data: Path = BENCH,
             stage=None, gaps=None) -> dict:
    """One run of cell ``name``.  ``device`` is what ``check_device``
    found (None only where a test drives the harness without a chip);
    ``data`` holds the configs, traffic and limits (a test's own tiny ones
    elsewhere); ``stage`` lets a test break the engine after its warm-up;
    ``gaps`` puts another reading in the reference's place in the check
    (the control, ``bench/control.py``)."""
    import jax

    from model import dims, load_config
    from traffic import generate, load_traffic

    cell = find_cell(bench, name)
    spec = load_config(cell["config"], data)
    mix = load_traffic(cell["traffic"], data)
    limits = load_limits(name, data)
    m = dims(spec)
    counter = CompileCounter()

    eng = build_engine(spec, seed, cell["chips"])
    warm_up(eng, spec)
    if stage is not None:
        stage(eng)
    obs = Observer(eng)
    tracer = Tracer(eng) if trace else None
    planned = generate(mix, m["V"], seed)
    from repro.serving.batching import Request

    def step() -> tuple:
        a = time.perf_counter()
        eng.step()
        b = time.perf_counter()
        obs.after_step(b)
        return a, b

    for p in planned:
        req = Request(prompt=p.prompt, max_new=p.max_new, arrival_t=eng.clock)
        obs.add(RequestLog(rid=req.rid, prompt=p.prompt, max_new=p.max_new))
        eng.submit(req)
    step()
    first = [rid for rid, g in obs.logs.items() if g.admitted_tick >= 0]
    t_ramp = time.perf_counter()
    while (any(not obs.logs[r].times for r in first)
           and time.perf_counter() - t_ramp < RAMP_CAP_S):
        step()
    setup_s = time.perf_counter() - t_start
    print(f"setup_s {setup_s:.3f}", file=sys.stderr)

    # -- the window ---------------------------------------------------------
    if trace:
        import devtrace
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        devtrace.start(str(TRACE_DIR))
        tracer.recording = True
    counter.on = True
    ticks = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while time.perf_counter() < deadline:
        if trace:
            with jax.profiler.TraceAnnotation("bench.tick"):
                ticks.append(step())
        else:
            ticks.append(step())
    t1 = max(ticks[-1][1], deadline) if ticks else deadline
    counter.on = False
    compiles = counter.count
    if trace:
        tracer.recording = False
        jax.profiler.stop_trace()
    print(f"window_s {t1 - t0:.3f} ticks {len(ticks)} compiles_in_window "
          f"{compiles}", file=sys.stderr)

    # -- memory, then free the program's state ------------------------------
    per_device = []
    if device is not None:
        per_device = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                      for d in jax.devices()[:cell["chips"]]]
    logs = list(obs.logs.values())
    free_engine(eng)
    del eng

    # -- metrics ------------------------------------------------------------
    peak = None
    if device is not None:
        from counts import peaks
        peak = peaks(device["kind"])
    run = Run(cell=cell, dims=m, peak=peak, chips=cell["chips"], seconds=seconds,
              setup_s=setup_s, window=(t0, t1), ticks=ticks, requests=logs)
    out_dev = dict(device or {"platform": "none", "kind": "none", "count": 0})
    out_dev["memory_peak_bytes"] = (max(per_device) if per_device
                                    and None not in per_device else None)
    out_dev["memory_peak_bytes_per_device"] = per_device
    breakdown = None
    if trace:
        from devtrace import breakdown as make_breakdown, device_busy, read_xplane
        run.trace = read_xplane(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run.decode_calls, run.chunk_calls = tracer.decode_calls, tracer.chunk_calls
        busy_s, window_s = device_busy(run.trace)
        out_dev["busy_s"], out_dev["window_s"] = busy_s, window_s
        breakdown = make_breakdown(run.trace)
    metrics = {}
    for spec_m in cell_metrics(bench, cell, per_layer=trace):
        v = reader(spec_m["name"])(run)
        if v is not None:
            metrics[spec_m["name"]] = {"value": float(v), "unit": spec_m["unit"]}

    # -- the check ----------------------------------------------------------
    checks, attempted, failed = check(run, spec, seed, limits, gaps)
    correct = all(c["ok"] for c in checks.values())
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['rule']} {c['limit']})"
              f"{'' if c['ok'] else '  FAILED'}", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": out_dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"],
                            "rule": c["rule"]} for k, c in checks.items()}
    return result


def sample(logs: list, window: tuple, seed: int) -> list:
    """Requests finished in the window: the longest, then, in an order
    drawn from the seed, one from each slot not yet in the sample, so that
    every slot that finished a request is checked; then others while the
    sample holds fewer than ``REFERENCE_BATCH`` requests and
    ``SAMPLE_TOKENS`` served tokens."""
    t0, t1 = window
    done = [g for g in logs if g.finished is not None and t0 < g.finished <= t1]
    if not done:
        return []
    done.sort(key=lambda g: (-(len(g.prompt) + len(g.tokens)), g.rid))
    rest = done[1:]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    seen, first, more = {done[0].slot}, [], []
    for i in order:
        g = rest[i]
        (more if g.slot in seen else first).append(g)
        seen.add(g.slot)
    picked = [done[0]] + first
    for g in more:
        if (len(picked) >= REFERENCE_BATCH
                or sum(len(p.tokens) for p in picked) >= SAMPLE_TOKENS):
            break
        picked.append(g)
    return picked


def check(run: Run, spec: dict, seed: int, limits: dict, gaps=None) -> tuple:
    """The comparison that decides ``correct``.  ``gaps(spec, seed,
    samples, pad_to=, batch=)`` reads each sampled token's gap below the
    float32 reference's best: by default the served tokens'
    (``reference.served_gaps``)."""
    from reference import served_gaps

    gaps = gaps or served_gaps
    admitted = [g for g in run.requests if g.admitted_tick >= 0]
    wrong_len = [g for g in admitted
                 if g.tokens is not None and len(g.tokens) != g.max_new]
    picked = sample(run.requests, run.window, seed)
    n_tok = sum(len(g.tokens) for g in picked)
    print(f"sample: {len(picked)} requests from {len({g.slot for g in picked})} "
          f"slots, {n_tok} served tokens", file=sys.stderr)
    gap = float("inf")
    if picked:
        gap = float(np.max(gaps(spec, seed, [(g.prompt, g.tokens) for g in picked],
                                pad_to=spec["engine"]["max_seq"],
                                batch=REFERENCE_BATCH)))
    lim = limits["max_logit_gap"]["limit"]
    checks = {
        "max_logit_gap": {"value": gap, "limit": lim, "rule": "<=",
                          "ok": gap <= lim},
        "sampled_tokens": {"value": n_tok, "limit": MIN_SAMPLE_TOKENS,
                           "rule": ">=", "ok": n_tok >= MIN_SAMPLE_TOKENS},
        "wrong_length": {"value": len(wrong_len), "limit": 0, "rule": "<=",
                         "ok": not wrong_len},
    }
    return checks, len(admitted), len(wrong_len)
