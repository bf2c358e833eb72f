"""Serving throughput: fixed-slot vs continuous batching (paged KV),
plus the contact-window preemption replay.

Replays ONE Poisson arrival trace (mixed prompt lengths, heterogeneous
decode budgets) through three configurations and reports useful tokens
per second plus KV-cache memory:

  * ``fixed_slot`` — the seed baseline: every request padded to the
    longest prompt in its batch, the batch decoded to its max max_new.
  * ``continuous`` — continuous batching over the PAGED KV layout (the
    default): a global page pool + per-sequence block tables, so cache
    memory is ``pool_pages * page_size`` positions instead of
    ``n_slots * max_seq``.
  * ``continuous_contiguous`` — continuous batching over the contiguous
    per-slot layout (memory baseline the paged gate compares against).

The paged run must stay token-exact with the contiguous run, hold the
>= 1.5x fixed-slot speedup, and use strictly less KV-cache memory —
all three are CI-gated on ``BENCH_serving.json``.

The CONTACT-WINDOW replay then reruns the same trace under a periodic
downlink schedule (every ``CW_PERIOD`` decode ticks the compute is
yielded for ``CW_DURATION`` ticks — the paper's ground-station pass):

  * ``preemptive`` — ``serving.scheduler.PreemptiveScheduler`` spills
    every in-flight sequence at window open and resumes it token-exactly
    after (reports preemption counts, resume latency, goodput);
  * ``restart`` — the no-preemption baseline: in-flight sequences are
    ABORTED at window open and re-decoded from scratch afterwards.

CI gates: the preemptive replay's tokens equal the uninterrupted run's
for every request, its goodput (useful tokens per clock tick) is >= the
restart baseline's, and the page pool fully drains (no leak).

The OVERLAP replay (``contact_window.overlap``) then reruns the trace
under a denser window schedule twice:

  * ``stop_the_world`` — PR 3 behavior: every pass preempts all decode
    for its whole duration;
  * ``overlapped`` — the contact pipeline: decode continues through the
    pass; only the transmit lane's staging reserve
    (``OV_RESERVE_PAGES`` held via ``hold_pages``) can spill sequences,
    and re-preempted sequences ship only KV-delta pages.

CI gates: overlapped goodput >= stop-the-world goodput on the SAME
schedule, delta spills observed with delta bytes < full-spill bytes,
both replays token-exact with the uninterrupted run, pools drained.

The CHUNKED-PREFILL replay (``chunked_prefill``) serves a heavy-tail
prompt mix (mostly short prompts, a fat tail near max_seq) through the
unified token-budget step twice: budgeted (``PREFILL_BUDGET`` prompt
tokens per tick) vs unbounded (each prompt lands as one chunk — the
monolithic comparator).  Every tick is wall-timed; the section reports
``tick_latency_p50/p99`` and TTFT.  CI gates: the runs are token-exact,
the chunked run's p99 tick latency is STRICTLY below the monolithic
run's on the same trace, per-tick prefill tokens never exceed the
budget, and both pools drain.

The SHARED-PREFIX replay (``shared_prefix``) serves a trace where many
requests repeat a handful of long system headers (the paper's
millions-of-users-per-system-prompt shape) twice through the paged
engine: with ``prefix_cache=True`` (refcounted page sharing +
copy-on-write) and without.  CI gates (GATE_VERSION 4): the shared run
is token-exact with the unshared run, its peak KV pool bytes AND its
total prefill tokens are STRICTLY below the unshared run's, and the
pool/refcounts fully drain once the prefix index is cleared.

The FAULT replay (``fault_replay``) reruns a contended space-ground
trace under an adversarial ``core.faults.FaultPlan`` — per-frame
downlink loss AND bit-flip corruption, early-LOS window truncation,
periodic spill-record corruption, and one scheduled satellite crash
mid-run — against the fault-free replay of the same trace.  CI gates
(GATE_VERSION 5): every final token stream is IDENTICAL to the
fault-free run's (faults cost time and bytes, never answers); every
injected corruption is detected (``n_corruptions_detected ==
n_corruptions_injected``, zero silent acceptances); retransmitted and
lost bytes are metered in the ledger; the framed lane's byte ledger
conserves (attempted == delivered + lost + corrupt); goodput efficiency
is bounded below by the injected loss; the crash is survived via
checkpoint/restore (``n_reboots == 1``) with pools and spill store
drained after.  ``--chaos SEED...`` sweeps FaultPlan seeds and asserts
the same invariants per seed (the CI chaos step).

The SPECULATIVE section (``speculative``) exercises draft–verify
decoding in the unified step twice.  The VERIFY micro-bench serves one
trace through the continuous engine plain, then again with each request
carrying its own plain-run output as a draft stream (perfect
acceptance), so every accepted token rides a chunked verify pass
instead of a decode dispatch.  The CASCADE replay reruns a space-ground
trace whose prompts dwarf the answers twice — raw-prompt escalation vs
draft-id escalation (``payload_bytes_draft``) with ground-side batched
verification.  CI gates (GATE_VERSION 6): both speculative replays are
token-exact with their plain comparators, accepted-token throughput is
>= plain decode's tokens/s in fewer engine ticks, drafts are actually
verified (passes > 0, accepted == drafted under self-drafts), the
draft escalation ships STRICTLY fewer bytes per escalation than the
raw path on the same trace, the ground tier answers escalations in
strictly fewer ticks, and all pools drain.

The CONSTELLATION section (``constellation``) replays one trace — all
of it uplinked through a window-poor satellite — across K=3 satellites
and 2 ground stations twice: the ``ContactPlanner``'s priority-to-value
pass assignment with token-exact inter-satellite handover
(``serving.constellation``) vs the K-independent-pairs comparator
(static home stations, no coordination) on the SAME window sets and
energy model.  CI gates (GATE_VERSION 7): the pooled replay's goodput
is >= the independent pairs' at equal energy/byte budget (both within
the per-satellite bus cap, no extra downlink payload bytes), handovers
actually happened, every answer is token-exact with a solo replay of
the same requests, and every pool, spill store and lane drains.
``--chaos-constellation SEED...`` reruns the pooled replay under a
lossy/corrupting fault plan per seed (the CI chaos step's
constellation lane).

The SHARDED section (``sharded``) replays one trace through the paged
continuous engine twice — single-device vs ``ContinuousEngine(mesh=
make_serving_mesh())``, a tensor-parallel mesh over EVERY visible
device (attention heads + per-device KV page pools sharded on the
``model`` axis, all-gather only at the logits) — plus a MoE replay
whose expert dispatch is expert-parallel over the same axis.  Configs
are fp32 so cross-device reduction order cannot perturb greedy argmax.
On the default 1-device CI lane the mesh is the trivial ``(1, 1)`` and
the section degenerates to an A/A parity check; the ``sharded-smoke``
CI job reruns it 4-way via ``--sharded`` (which forces
``--xla_force_host_platform_device_count=4`` before JAX initializes)
and asserts the 4-shard invariants inline.  CI gates (GATE_VERSION 8):
both replays token-exact with their single-device comparators,
``kv_bytes_per_device * n_kv_shards == kv_cache_bytes`` (page pools
shard only head/latent axes, never page axes, so the per-device ledger
IS the global ledger: ``peak_pages_in_use_per_device ==
peak_pages_in_use``), sharded tokens/s >= ``SHARDED_MIN_RATIO`` x the
single-device run's at equal batch, pools drained, and the MoE run's
``experts_per_device * n_expert_shards == n_experts`` (per-device
dispatch really metered).

The gates live in ``scripts/check_bench.py`` (run it locally after the
benchmark: ``python scripts/check_bench.py BENCH_serving.json``).

    PYTHONPATH=src python -m benchmarks.serving_throughput
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

N_REQUESTS = 24
N_SLOTS = 4
MAX_SEQ = 64
ARRIVAL_RATE = 0.5          # mean arrivals per decode step
PROMPT_LENS = (4, 16)
MAX_NEW = (2, 24)
PAGE_SIZE = 16
CW_PERIOD = 40              # decode ticks between window opens
CW_DURATION = 8             # ticks per window (gap > max max_new so the
                            # restart baseline cannot livelock)
CW_MAX_STEPS = 20_000       # replay safety valve
BENCH_VERSION = 8           # bumped when gated keys change (check_bench)

# overlap replay: denser passes (so long sequences straddle several and
# re-preemption exercises the KV-delta format) + a staging reserve that
# actually contends with the decode working set
OV_PERIOD = 16              # decode ticks between overlap-window opens
OV_DURATION = 4             # ticks per overlap window
OV_RESERVE_PAGES = 8        # pages held for the transmit lane per pass
                            # (2/3 of the default 12-page pool: enough
                            # contention that long sequences re-spill
                            # across passes and exercise delta spills)

# chunked-prefill replay: a HEAVY-TAIL prompt mix (mostly short prompts
# with a fat tail of near-max_seq ones) served twice — with the unified
# step's prefill budget bounding every tick, and with the budget
# removed (each prompt lands as ONE chunk: the monolithic comparator).
# The tail is what the gate is about: a monolithic admission stalls the
# whole tick for the prompt length, so its tail tick latency blows up
# while the chunked run's stays near the decode floor.
HT_N_REQUESTS = 16
HT_MAX_SEQ = 512
HT_RATE = 0.35              # arrivals per tick (slower: long decodes)
HT_LIGHT_PROMPTS = (4, 16)
HT_HEAVY_PROMPTS = (360, 480)
HT_HEAVY_EVERY = 4          # every 4th request draws from the heavy tail
HT_MAX_NEW = (4, 16)
PREFILL_BUDGET = 16         # per-tick prompt-token budget (chunked run)

# shared-prefix replay: SP_N_REQUESTS requests drawn over SP_HEADERS
# distinct system headers of SP_HEADER_PAGES full pages each — the
# prefix index can only share FULL prompt pages, so headers are sized
# in pages.  The pool is deliberately roomy: peak pages then measure
# the working-set footprint, not the pool cap.
SP_N_REQUESTS = 16
SP_HEADERS = 2              # distinct system headers in the trace
SP_HEADER_PAGES = 2         # header length = 2 full pages (32 tokens)
SP_TAIL_LENS = (2, 8)       # per-request unique suffix length
SP_MAX_NEW = (2, 8)
SP_RATE = 0.6               # arrivals per decode step
SP_POOL_PAGES = 48

# fault replay: a contended satellite engine (small pool, big staging
# reserve — spills are constant, so spill corruption has records to
# hit) under a dense pass schedule, with every fault class armed at
# once.  Rates are high enough that a short replay still draws several
# losses AND corruptions from the seeded stream; frames are small so
# even compact result payloads span frames.
FR_N_REQUESTS = 8
FR_SEED = 0                 # FaultPlan seed for the gated section
FR_FRAME_LOSS = 0.25        # per-frame transmit erasure probability
FR_FRAME_CORRUPT = 0.2      # per-frame bit-flip probability
FR_TRUNCATE_EVERY = 3       # every 3rd pass ends early (LOS)
FR_SPILL_CORRUPT_EVERY = 2  # every 2nd spill-store merge lands corrupted
FR_CRASH_AT_TICK = 25       # scheduled onboard reboot
FR_FRAME_BYTES = 32         # downlink ARQ frame size
FR_MAX_RETRIES = 6          # per-frame retry budget
FR_CHECKPOINT_EVERY = 8     # onboard ticks between checkpoints
FR_SAT_SLOTS = 2
FR_SAT_POOL_PAGES = 9
FR_SAT_PAGE_SIZE = 8
FR_RESERVE_PAGES = 4
FR_GATE_THRESHOLD = 0.6     # mixed escalation (raw + compact payloads)

# speculative replay: (a) the VERIFY micro-bench serves the same trace
# twice through the continuous engine — plain decode vs requests
# carrying their own plain-run output as a draft stream (perfect
# acceptance), so the accepted-token throughput gate measures exactly
# the one-chunk-pass-vs-k-decode-dispatches win; (b) the CASCADE
# replay reruns a space-ground trace with prompts much longer than
# answers twice — raw-prompt escalation vs draft-id escalation
# (speculative=True) — and gates bytes-per-escalation plus ground-tier
# verify latency.  Both tiers share params, so the satellite's answers
# are exactly the ground's greedy continuations and every shipped
# draft is accepted (the repo's preempt/chunk exactness gates are what
# make that guarantee hold under contention).
SD_N_REQUESTS = 6
SD_SLOTS = 2
SD_PROMPTS = (8, 16)
SD_MAX_NEW = 32             # fixed decode budget per request
SD_DRAFT_K = 8              # drafts verified per slot per tick
SC_N_REQUESTS = 6
SC_PROMPTS = (24, 40)       # prompts longer than answers: the raw
SC_MAX_NEW = (6, 12)        # escalation payload dwarfs the draft ids
SC_GATE_THRESHOLD = 0.9     # escalate (nearly) everything: the section
                            # is about the escalated path's cost

# constellation replay: K=3 satellites on one shared tick clock, M=2
# ground stations, ALL load uplinked via satellite 0 — whose plane sees
# its home station once (~t=189 of the 600 s horizon at these
# densities) while its peers get a pass every minute or two.  The value
# planner + handover move satellite 0's backlog over the ISL and
# deliver inside the peers' early passes; the static independent-pairs
# comparator parks every answer until the lone home-station pass.
CN_N_SATS = 3
CN_N_STATIONS = 2
CN_N_REQUESTS = 8
CN_PROMPTS = (6, 12)
CN_MAX_NEW = (4, 10)
CN_HORIZON_S = 600.0
CN_CONTACT_DURATION_S = 6.0
CN_CONTACTS_PER_DAY = (144, 2400, 2400)
CN_SCHEDULE_SEED = 3
CN_MARGIN_TICKS = 16        # peer's pass must beat the owner's by this
CN_SLOTS = 2
CN_PAGE_SIZE = 8
CN_POOL_PAGES = 12
CN_FRAME_BYTES = 256        # chaos lane: framed ARQ on downlink + ISL
CN_MAX_RETRIES = 6
CN_FRAME_LOSS = 0.2
CN_FRAME_CORRUPT = 0.15
CN_SPILL_CORRUPT_EVERY = 3
CN_FAULT_SEED = 11          # the CI chaos step's constellation seed

# sharded replay: fp32 configs (cross-device psum must not reorder a
# reduction into a different greedy argmax) with head counts that
# divide a 4-way model axis.  The dense lane is timed A/B (warmed jit
# caches) for the throughput gate; the MoE lane is about expert
# dispatch accounting, not wall time, so it runs cold.
SH_N_REQUESTS = 12
SH_TIMED_REPS = 3           # best-of-N walls for the parity gate: the
                            # replays are sub-second, so a single rep
                            # is scheduler-noise-limited
SH_MOE_N_REQUESTS = 6
SH_SEED = 11                # dense-lane poisson trace seed
SH_MOE_SEED = 13
SH_FORCED_DEVICES = 4       # --sharded lane's forced host device count


def _make_engine_inputs():
    from repro.config import get_reduced_config
    from repro.serving.batching import poisson_trace

    cfg = get_reduced_config("smollm-360m")
    trace = poisson_trace(N_REQUESTS, rate=ARRIVAL_RATE,
                          prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                          vocab_size=cfg.vocab_size, seed=7)
    return cfg, trace


def _clone(trace):
    return [r.clone() for r in trace]


def _serve_fixed(cfg, params, trace):
    """Fixed-slot baseline: the seed ``RequestQueue.next_batch``
    discipline (FIFO, pad to the batch's longest prompt) with each batch
    decoded for its max ``max_new``.  The clock (in decode steps) only
    advances while the batch drains, so a new batch forms from whatever
    has arrived by then.  Returns (useful_tokens, wall_seconds, kv_stats,
    emitted_tokens) like ``_serve_continuous`` — the last two are empty/
    None placeholders (no KV accounting or exactness check here)."""
    from repro.serving.batching import RequestQueue
    from repro.serving.engine import ServingEngine

    eng = ServingEngine(cfg, params, max_seq=MAX_SEQ)
    queue = RequestQueue(max_batch=N_SLOTS)
    pending = sorted(trace, key=lambda r: r.arrival_t)
    clock, useful = 0.0, 0
    t0 = time.perf_counter()
    while pending or len(queue):
        while pending and pending[0].arrival_t <= clock:
            queue.submit(pending.pop(0))
        batch = queue.next_batch()
        if batch is None:
            clock += 1.0                       # idle tick
            continue
        steps = max(r.max_new for r in batch.requests)
        eng.generate(batch.tokens, max_new=steps)
        useful += sum(r.max_new for r in batch.requests)
        clock += steps
    return useful, time.perf_counter() - t0, {}, None


def _serve_continuous(cfg, params, trace, kv_layout):
    from repro.serving.engine import ContinuousEngine

    kw = {"page_size": PAGE_SIZE} if kv_layout == "paged" else {}
    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout=kv_layout, **kw)
    t0 = time.perf_counter()
    results = eng.run(_clone(trace))
    wall = time.perf_counter() - t0
    useful = sum(len(r.tokens) for r in results.values())
    tokens_by_order = [results[k].tokens for k in sorted(results)]
    return useful, wall, eng.kv_cache_stats(), tokens_by_order


def _in_window(clock: int) -> bool:
    return clock % CW_PERIOD < CW_DURATION


def _serve_preemptive(cfg, params, trace):
    """Contact-window replay: spill every in-flight sequence at window
    open, resume token-exactly after the pass."""
    from repro.serving.engine import ContinuousEngine
    from repro.serving.scheduler import PreemptiveScheduler

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE)
    sched = PreemptiveScheduler(eng, preempt_mode="spill")
    for r in sorted(trace, key=lambda r: r.arrival_t):
        sched.submit(r)
    t0 = time.perf_counter()
    while sched.has_work():
        if _in_window(eng.clock):
            sched.preempt_all()
            sched.step(decode=False)
        else:
            sched.step()
        if eng.clock > CW_MAX_STEPS:
            raise RuntimeError("contact-window replay did not drain")
    wall = time.perf_counter() - t0
    alloc = eng.slots.allocator
    return {
        "results": eng.results,
        "wall_s": wall,
        "clock_steps": eng.clock,
        "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
        **sched.stats(),
    }


def _serve_restart(cfg, params, trace):
    """No-preemption baseline: in-flight sequences are aborted at window
    open (pages released, progress discarded) and re-decoded from
    scratch after the pass."""
    from repro.serving.engine import ContinuousEngine

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE)
    for r in sorted(trace, key=lambda r: r.arrival_t):
        eng.submit(r)
    n_aborts = wasted_tokens = 0
    t0 = time.perf_counter()
    while len(eng.queue) or eng.slots.any_active():
        if _in_window(eng.clock):
            aborted = [eng.slots.detach(slot, release_pages=True)
                       for slot in eng.slots.active_slots()]
            for st in reversed(aborted):              # keep admission order
                eng.queue.requeue_front(st.request)   # redo from prefill
                n_aborts += 1
                wasted_tokens += len(st.emitted)
            eng._idle_tick()                          # pass holds the compute
        else:
            eng.step()
        if eng.clock > CW_MAX_STEPS:
            raise RuntimeError("restart replay did not drain")
    wall = time.perf_counter() - t0
    alloc = eng.slots.allocator
    return {
        "results": eng.results,
        "wall_s": wall,
        "clock_steps": eng.clock,
        "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
        "n_aborts": n_aborts,
        "wasted_tokens": wasted_tokens,
    }


def _in_ov_window(clock: int) -> bool:
    return clock % OV_PERIOD < OV_DURATION


def _serve_overlap(cfg, params, trace, *, overlap):
    """Overlap replay under the dense window schedule.  ``overlap=False``
    is the stop-the-world comparator: all decode preempted for every
    pass.  ``overlap=True`` keeps decoding through passes and only
    spills the sequences whose pages must cover the transmit lane's
    staging reserve — with KV-delta re-spills."""
    from repro.serving.engine import ContinuousEngine
    from repro.serving.scheduler import PreemptiveScheduler

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE)
    sched = PreemptiveScheduler(eng, preempt_mode="spill", delta_spill=True)
    for r in sorted(trace, key=lambda r: r.arrival_t):
        sched.submit(r)
    decode_steps_in_window = 0
    t0 = time.perf_counter()
    while sched.has_work():
        if _in_ov_window(eng.clock):
            if overlap:
                sched.hold_pages(OV_RESERVE_PAGES)
                finished = sched.step()    # compute lane keeps running
            else:
                sched.preempt_all()
                finished = sched.step(decode=False)
            # counted for BOTH branches, AFTER the step (it may
            # resume/admit and then decode in the same tick): the
            # stop-the-world run must measure 0 here, not skip the
            # measurement — the gate then really tests the comparator
            decode_steps_in_window += int(bool(finished)
                                          or eng.slots.any_active())
        else:
            sched.release_hold()
            sched.step()
        if eng.clock > CW_MAX_STEPS:
            raise RuntimeError("overlap replay did not drain")
    sched.release_hold()
    wall = time.perf_counter() - t0
    alloc = eng.slots.allocator
    return {
        "results": eng.results,
        "wall_s": wall,
        "clock_steps": eng.clock,
        "decode_steps_in_window": decode_steps_in_window,
        "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
        "spill_store_empty": sched.store is None or len(sched.store) == 0,
        **sched.stats(),
    }


def _overlap_report(cfg, params, trace, reference_tokens):
    """Overlapped vs stop-the-world on the SAME dense schedule, both
    compared token-for-token against the uninterrupted run."""
    ov = _serve_overlap(cfg, params, _clone(trace), overlap=True)
    stw = _serve_overlap(cfg, params, _clone(trace), overlap=False)

    def summarize(run):
        results = run.pop("results")
        tokens = [results[k].tokens for k in sorted(results)]
        useful = sum(len(t) for t in tokens)
        run["useful_tokens"] = useful
        run["goodput_tokens_per_step"] = round(useful / run["clock_steps"], 4)
        run["tokens_per_s"] = round(useful / run["wall_s"], 2)
        run["wall_s"] = round(run["wall_s"], 4)
        return tokens

    ov_tokens = summarize(ov)
    stw_tokens = summarize(stw)
    exact = lambda toks: (len(toks) == len(reference_tokens) and all(
        np.array_equal(a, b) for a, b in zip(toks, reference_tokens)))
    return {
        "windows": {"period_steps": OV_PERIOD, "duration_steps": OV_DURATION,
                    "comm_reserve_pages": OV_RESERVE_PAGES},
        "overlapped": ov,
        "stop_the_world": stw,
        "token_exact_vs_uninterrupted": exact(ov_tokens),
        "stop_the_world_token_exact": exact(stw_tokens),
        "goodput_ratio_vs_stop_the_world": round(
            ov["goodput_tokens_per_step"] / stw["goodput_tokens_per_step"],
            3),
        "delta_spill_bytes": ov["spill_bytes"],
        "full_spill_bytes_equiv": ov["spill_bytes_full_equiv"],
    }


def _contact_window_report(cfg, params, trace, reference_tokens):
    """Run both replays and compare against the uninterrupted tokens
    (keyed by submission order, rids differ across engines)."""
    pre = _serve_preemptive(cfg, params, _clone(trace))
    res = _serve_restart(cfg, params, _clone(trace))

    def summarize(run):
        results = run.pop("results")
        tokens = [results[k].tokens for k in sorted(results)]
        useful = sum(len(t) for t in tokens)
        run["useful_tokens"] = useful
        run["goodput_tokens_per_step"] = round(useful / run["clock_steps"], 4)
        run["tokens_per_s"] = round(useful / run["wall_s"], 2)
        run["wall_s"] = round(run["wall_s"], 4)
        return tokens

    pre_tokens = summarize(pre)
    res_tokens = summarize(res)
    exact = lambda toks: (len(toks) == len(reference_tokens) and all(
        np.array_equal(a, b) for a, b in zip(toks, reference_tokens)))
    return {
        "windows": {"period_steps": CW_PERIOD, "duration_steps": CW_DURATION},
        "preemptive": pre,
        "restart": res,
        "token_exact_vs_uninterrupted": exact(pre_tokens),
        "restart_token_exact": exact(res_tokens),
        "goodput_ratio": round(pre["goodput_tokens_per_step"]
                               / res["goodput_tokens_per_step"], 3),
    }


def _heavy_tail_trace(cfg):
    """Poisson arrivals with a heavy-tail prompt-length mix: every
    ``HT_HEAVY_EVERY``-th request carries a near-max_seq prompt."""
    from repro.serving.batching import Request

    rng = np.random.default_rng(23)
    t, out = 0.0, []
    for i in range(HT_N_REQUESTS):
        t += float(rng.exponential(1.0 / HT_RATE))
        lens = (HT_HEAVY_PROMPTS if i % HT_HEAVY_EVERY == HT_HEAVY_EVERY - 1
                else HT_LIGHT_PROMPTS)
        S = int(rng.integers(lens[0], lens[1] + 1))
        out.append(Request(
            prompt=rng.integers(1, cfg.vocab_size, S).astype(np.int32),
            max_new=int(rng.integers(HT_MAX_NEW[0], HT_MAX_NEW[1] + 1)),
            arrival_t=t))
    return out


def _serve_budgeted(cfg, params, trace, budget):
    """Replay the heavy-tail trace through one engine, timing EVERY
    unified-step tick.  budget=None is the monolithic comparator (whole
    prompts land in a single chunk, stalling their tick)."""
    from repro.serving.engine import ContinuousEngine

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=HT_MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE,
                           prefill_budget_tokens=budget)
    by_rid = {}
    for r in sorted(trace, key=lambda r: r.arrival_t):
        eng.submit(r)
        by_rid[r.rid] = r
    import jax

    tick_s = []
    max_prefill = 0
    while len(eng.queue) or eng.slots.any_active():
        t0 = time.perf_counter()
        eng.step()
        # async dispatch would bill a tick's model work to whichever
        # later tick first syncs on a result — block so each tick's
        # latency is its own
        jax.block_until_ready(eng.slots.cache)
        tick_s.append(time.perf_counter() - t0)
        max_prefill = max(max_prefill, eng.last_tick_prefill_tokens)
    results = eng.results
    tokens = [results[k].tokens for k in sorted(results)]
    ttft = [results[r.rid].first_token_step - r.arrival_t
            for r in by_rid.values()]
    lat = np.asarray(tick_s)
    alloc = eng.slots.allocator
    return {
        "n_ticks": len(tick_s),
        "useful_tokens": int(sum(len(t) for t in tokens)),
        "tick_latency_p50_s": round(float(np.percentile(lat, 50)), 6),
        "tick_latency_p99_s": round(float(np.percentile(lat, 99)), 6),
        "tick_latency_max_s": round(float(lat.max()), 6),
        "ttft_mean_steps": round(float(np.mean(ttft)), 2),
        "ttft_p99_steps": round(float(np.percentile(ttft, 99)), 2),
        "max_prefill_tokens_per_tick": int(max_prefill),
        "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
    }, tokens


def _chunked_prefill_report(cfg, params):
    """Chunked (budgeted) vs monolithic (unbounded) unified step on the
    SAME heavy-tail trace: token-exact, with the chunked run's tail tick
    latency strictly below the monolithic run's."""
    trace = _heavy_tail_trace(cfg)
    runs = {}
    tokens = {}
    for name, budget in (("chunked", PREFILL_BUDGET), ("monolithic", None)):
        _serve_budgeted(cfg, params, _clone(trace), budget)   # warm jit
        runs[name], tokens[name] = _serve_budgeted(cfg, params,
                                                   _clone(trace), budget)
    return {
        "trace": {"n_requests": HT_N_REQUESTS, "max_seq": HT_MAX_SEQ,
                  "light_prompts": list(HT_LIGHT_PROMPTS),
                  "heavy_prompts": list(HT_HEAVY_PROMPTS),
                  "heavy_every": HT_HEAVY_EVERY,
                  "prefill_budget_tokens": PREFILL_BUDGET},
        "chunked": runs["chunked"],
        "monolithic": runs["monolithic"],
        "token_exact": (len(tokens["chunked"]) == len(tokens["monolithic"])
                        and all(np.array_equal(a, b)
                                for a, b in zip(tokens["chunked"],
                                                tokens["monolithic"]))),
        "tick_p99_ratio": round(
            runs["chunked"]["tick_latency_p99_s"]
            / max(runs["monolithic"]["tick_latency_p99_s"], 1e-12), 4),
    }


def _shared_prefix_trace(cfg):
    """Poisson arrivals where every prompt = one of ``SP_HEADERS``
    shared system headers (``SP_HEADER_PAGES`` full pages) + a short
    unique tail.  Request 0 of each header is the cold miss that seeds
    the index; every later reuse is a page-granular hit."""
    from repro.serving.batching import Request

    rng = np.random.default_rng(11)
    headers = [rng.integers(1, cfg.vocab_size,
                            SP_HEADER_PAGES * PAGE_SIZE).astype(np.int32)
               for _ in range(SP_HEADERS)]
    t, out = 0.0, []
    for i in range(SP_N_REQUESTS):
        t += float(rng.exponential(1.0 / SP_RATE))
        tail = rng.integers(
            1, cfg.vocab_size,
            int(rng.integers(SP_TAIL_LENS[0],
                             SP_TAIL_LENS[1] + 1))).astype(np.int32)
        out.append(Request(
            prompt=np.concatenate([headers[i % SP_HEADERS], tail]),
            max_new=int(rng.integers(SP_MAX_NEW[0], SP_MAX_NEW[1] + 1)),
            arrival_t=t))
    return out


def _serve_shared(cfg, params, trace, *, prefix_cache):
    """One replay of the shared-prefix trace; returns (summary dict,
    emitted tokens).  Peak KV bytes are the high-water page count times
    the per-page byte cost — both runs size the pool identically, so
    the pool-allocation bytes cancel and the peak measures footprint."""
    from repro.serving.engine import ContinuousEngine

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE,
                           pool_pages=SP_POOL_PAGES,
                           prefix_cache=prefix_cache)
    t0 = time.perf_counter()
    results = eng.run(_clone(trace))
    wall = time.perf_counter() - t0
    tokens = [results[k].tokens for k in sorted(results)]
    stats = eng.kv_cache_stats()
    alloc = eng.slots.allocator
    live_refs = alloc.n_live_refs()
    if eng.slots.prefix_index is not None:
        eng.slots.prefix_index.clear()     # end of life: drop cached pages
    out = {
        "useful_tokens": int(sum(len(t) for t in tokens)),
        "wall_s": round(wall, 4),
        "prefill_tokens_total": eng.prefill_tokens_total,
        "kv_peak_bytes": (stats["peak_pages_in_use"]
                          * (stats["kv_cache_bytes"]
                             // (SP_POOL_PAGES + 1))),
        "live_refs_before_clear": live_refs,
        "pool_drained": (alloc.in_use == 0 and alloc.reserved == 0
                         and alloc.n_live_refs() == 0),
        **{k: v for k, v in stats.items() if k != "kv_cache_bytes"},
    }
    return out, tokens


def _shared_prefix_report(cfg, params):
    """prefix_cache=True vs =False on the SAME header-heavy trace:
    token-exact, with the shared run's peak KV bytes and prefill tokens
    both strictly below the unshared run's."""
    trace = _shared_prefix_trace(cfg)
    runs, tokens = {}, {}
    for name, pc in (("shared", True), ("unshared", False)):
        _serve_shared(cfg, params, _clone(trace), prefix_cache=pc)  # warm jit
        runs[name], tokens[name] = _serve_shared(cfg, params, _clone(trace),
                                                 prefix_cache=pc)
    return {
        "trace": {"n_requests": SP_N_REQUESTS, "n_headers": SP_HEADERS,
                  "header_pages": SP_HEADER_PAGES,
                  "tail_lens": list(SP_TAIL_LENS),
                  "max_new": list(SP_MAX_NEW),
                  "pool_pages": SP_POOL_PAGES},
        "shared": runs["shared"],
        "unshared": runs["unshared"],
        "token_exact": (len(tokens["shared"]) == len(tokens["unshared"])
                        and all(np.array_equal(a, b)
                                for a, b in zip(tokens["shared"],
                                                tokens["unshared"]))),
        "kv_peak_bytes_ratio": round(
            runs["shared"]["kv_peak_bytes"]
            / max(runs["unshared"]["kv_peak_bytes"], 1), 4),
        "prefill_tokens_ratio": round(
            runs["shared"]["prefill_tokens_total"]
            / max(runs["unshared"]["prefill_tokens_total"], 1), 4),
    }


def _fault_trace(cfg):
    from repro.serving.batching import Request

    rng = np.random.default_rng(3)
    return [Request(
        prompt=rng.integers(1, cfg.vocab_size,
                            int(rng.integers(8, 14))).astype(np.int32),
        max_new=int(rng.integers(10, 18)), arrival_t=float(i * 2))
        for i in range(FR_N_REQUESTS)]


def _serve_fault(cfg, params, trace, *, plan_seed=None):
    """One space-ground replay; ``plan_seed=None`` is the fault-free
    comparator (same engines/schedule/gate, no injector, unframed
    lane).  Returns (summary dict, final tokens by submission order)."""
    from repro.core.faults import FaultInjector, FaultPlan
    from repro.core.gating import ConfidenceGate
    from repro.core.link import ContactSchedule
    from repro.serving.engine import ContinuousEngine
    from repro.serving.scheduler import SpaceGroundScheduler

    sat = ContinuousEngine(cfg, params, n_slots=FR_SAT_SLOTS,
                           max_seq=MAX_SEQ, kv_layout="paged",
                           page_size=FR_SAT_PAGE_SIZE,
                           pool_pages=FR_SAT_POOL_PAGES,
                           prefill_budget_tokens=8)
    gnd = ContinuousEngine(cfg, params, n_slots=FR_SAT_SLOTS,
                           max_seq=MAX_SEQ)
    kw = dict(schedule=ContactSchedule(contact_duration_s=4.0,
                                       contacts_per_day=8640, seed=3),
              gate=ConfidenceGate("max_prob", FR_GATE_THRESHOLD),
              s_per_step=1.0, horizon_s=7200.0,
              comm_reserve_pages=FR_RESERVE_PAGES)
    inj = None
    if plan_seed is not None:
        inj = FaultInjector(FaultPlan(
            seed=plan_seed, frame_loss_rate=FR_FRAME_LOSS,
            frame_corrupt_rate=FR_FRAME_CORRUPT,
            truncate_every=FR_TRUNCATE_EVERY,
            spill_corrupt_every=FR_SPILL_CORRUPT_EVERY,
            crash_at_tick=FR_CRASH_AT_TICK))
        kw.update(faults=inj, frame_bytes=FR_FRAME_BYTES,
                  link_max_retries=FR_MAX_RETRIES,
                  checkpoint_every=FR_CHECKPOINT_EVERY)
    sg = SpaceGroundScheduler(sat, gnd, **kw)
    t0 = time.perf_counter()
    rep = sg.run([r.clone() for r in trace])
    wall = time.perf_counter() - t0
    tokens = [rep.tokens[k] for k in sorted(rep.tokens)]
    sat_tokens = [rep.sat_results[k].tokens for k in sorted(rep.sat_results)]
    alloc = sg.sat.engine.slots.allocator
    ls = rep.lane_stats
    store_stats = (sg.sat.store.stats() if sg.sat.store is not None
                   else {})
    out = {
        "wall_s": round(wall, 4),
        "clock_steps": sg.sat.clock,
        "n_answers": len(tokens),
        "n_escalated": len(rep.escalated),
        "n_undelivered": len(rep.undelivered),
        "n_reboots": rep.n_reboots,
        "n_redo_from_corruption": rep.sat_stats["n_redo_from_corruption"],
        "pool_drained": (alloc.in_use == 0 and alloc.reserved == 0
                         and alloc.n_live_refs() == 0),
        "spill_store_empty": (sg.sat.store is None
                              or len(sg.sat.store) == 0),
        "lane": ls,
        "ledger": {k: round(v, 4) for k, v in
                   rep.ledger.counters.items()},
    }
    if inj is not None:
        attempted = max(ls["frame_bytes_attempted"], 1e-9)
        out["injected"] = {
            "n_frames_lost": inj.n_frames_lost,
            "n_frame_corruptions": inj.n_frame_corruptions,
            "n_spill_corruptions": inj.n_spill_corruptions,
            "n_corruptions_injected": inj.n_corruptions_injected,
            "n_windows_truncated": inj.n_windows_truncated,
            "n_crashes": inj.n_crashes,
        }
        out["n_corruptions_detected"] = (
            ls["n_corruptions_detected"]
            + store_stats.get("n_spill_corruptions_detected", 0))
        out["frame_ledger_conserved"] = bool(
            abs(ls["frame_bytes_attempted"]
                - (ls["bytes_sent"] + ls["bytes_lost"]
                   + ls["bytes_corrupt"])) < 1e-6)
        out["goodput_efficiency"] = round(ls["bytes_sent"] / attempted, 4)
    return out, tokens, sat_tokens


def _fault_replay_report(cfg, params, *, plan_seed=FR_SEED):
    """Fault-free vs all-faults-armed replay of the same trace: the
    fault plan must cost bytes and time, never answers."""
    trace = _fault_trace(cfg)
    ref, ref_tokens, ref_sat = _serve_fault(cfg, params, trace)
    flt, flt_tokens, flt_sat = _serve_fault(cfg, params, trace,
                                            plan_seed=plan_seed)
    exact = lambda a, b: (len(a) == len(b)
                          and all(np.array_equal(x, y)
                                  for x, y in zip(a, b)))
    return {
        "plan": {"seed": plan_seed, "frame_loss_rate": FR_FRAME_LOSS,
                 "frame_corrupt_rate": FR_FRAME_CORRUPT,
                 "truncate_every": FR_TRUNCATE_EVERY,
                 "spill_corrupt_every": FR_SPILL_CORRUPT_EVERY,
                 "crash_at_tick": FR_CRASH_AT_TICK,
                 "frame_bytes": FR_FRAME_BYTES,
                 "max_retries": FR_MAX_RETRIES,
                 "checkpoint_every": FR_CHECKPOINT_EVERY},
        "fault_free": ref,
        "faulted": flt,
        "token_exact_vs_fault_free": exact(flt_tokens, ref_tokens),
        "sat_token_exact_vs_fault_free": exact(flt_sat, ref_sat),
    }


def _spec_verify_requests(cfg, drafts=None):
    from repro.serving.batching import Request

    rng = np.random.default_rng(17)
    reqs = []
    for i in range(SD_N_REQUESTS):
        S = int(rng.integers(SD_PROMPTS[0], SD_PROMPTS[1] + 1))
        reqs.append(Request(
            prompt=rng.integers(1, cfg.vocab_size, S).astype(np.int32),
            max_new=SD_MAX_NEW,
            draft_toks=None if drafts is None else drafts[i]))
    return reqs


def _spec_verify_run(cfg, params, drafts=None):
    from repro.serving.engine import ContinuousEngine

    eng = ContinuousEngine(cfg, params, n_slots=SD_SLOTS, max_seq=MAX_SEQ,
                           page_size=PAGE_SIZE, draft_k=SD_DRAFT_K)
    reqs = _spec_verify_requests(cfg, drafts)
    t0 = time.perf_counter()
    results = eng.run(reqs)
    wall = time.perf_counter() - t0
    toks = [results[k].tokens for k in sorted(results)]
    useful = sum(len(t) for t in toks)
    alloc = eng.slots.allocator
    return {"useful_tokens": useful, "wall_s": round(wall, 4),
            "tokens_per_s": round(useful / wall, 2),
            "clock_steps": eng.clock,
            "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
            **eng.spec_stats()}, toks


def _spec_cascade_trace(cfg):
    from repro.serving.batching import Request

    rng = np.random.default_rng(5)
    return [Request(
        prompt=rng.integers(
            1, cfg.vocab_size,
            int(rng.integers(SC_PROMPTS[0], SC_PROMPTS[1] + 1)),
        ).astype(np.int32),
        max_new=int(rng.integers(SC_MAX_NEW[0], SC_MAX_NEW[1] + 1)),
        arrival_t=float(i * 2)) for i in range(SC_N_REQUESTS)]


def _serve_spec_cascade(cfg, params, trace, *, speculative):
    """One space-ground replay of the cascade trace; ``speculative``
    switches the escalation payload (raw prompt re-decode vs draft-id
    verification) and NOTHING else — same engines, schedule, gate."""
    from repro.core.gating import ConfidenceGate
    from repro.core.link import ContactSchedule
    from repro.serving.engine import ContinuousEngine
    from repro.serving.scheduler import SpaceGroundScheduler

    sat = ContinuousEngine(cfg, params, n_slots=SD_SLOTS, max_seq=MAX_SEQ,
                           prefill_budget_tokens=8)
    gnd = ContinuousEngine(cfg, params, n_slots=SD_SLOTS, max_seq=MAX_SEQ,
                           draft_k=SD_DRAFT_K)
    sg = SpaceGroundScheduler(
        sat, gnd,
        schedule=ContactSchedule(contact_duration_s=4.0,
                                 contacts_per_day=8640, seed=3),
        gate=ConfidenceGate("max_prob", SC_GATE_THRESHOLD),
        s_per_step=1.0, horizon_s=7200.0,
        comm_reserve_pages=FR_RESERVE_PAGES, speculative=speculative)
    t0 = time.perf_counter()
    rep = sg.run([r.clone() for r in trace])
    wall = time.perf_counter() - t0
    tokens = [rep.tokens[k] for k in sorted(rep.tokens)]
    led = rep.ledger
    n_esc = max(int(led.get("items_escalated")), 1)
    esc_key = ("bytes_draft_escalated" if speculative
               else "bytes_raw_escalated")
    glat = [r.finished_step - r.admitted_step
            for r in rep.ground_results.values()]
    sat_alloc, gnd_alloc = sat.slots.allocator, gnd.slots.allocator
    return {
        "wall_s": round(wall, 4),
        "n_escalated": len(rep.escalated),
        "n_undelivered": len(rep.undelivered),
        "bytes_escalated": round(led.get(esc_key), 1),
        "bytes_per_escalation": round(led.get(esc_key) / n_esc, 2),
        "ground_latency_mean_steps": round(float(np.mean(glat)), 3)
        if glat else 0.0,
        "pool_drained": all(a.in_use == 0 and a.reserved == 0
                            for a in (sat_alloc, gnd_alloc)),
        "spec": rep.spec_stats,
        "ledger": {k: round(v, 4) for k, v in led.counters.items()},
    }, tokens


def _speculative_report(cfg, params):
    """The GATE_VERSION 6 section: draft-verify in the unified step.

    verify: same engine, same trace, plain decode vs perfect
    self-drafts — token-exact, and accepted-token throughput must not
    fall below plain decode's tokens/s (one chunk pass replaces up to
    ``SD_DRAFT_K + 1`` decode dispatches).
    cascade: raw-prompt vs draft-id escalation on one space-ground
    trace — token-exact, strictly fewer bytes per escalation, and the
    ground tier answers escalations in strictly fewer ticks."""
    exact = lambda a, b: (len(a) == len(b)
                          and all(np.array_equal(x, y)
                                  for x, y in zip(a, b)))
    _spec_verify_run(cfg, params)                  # warmup (jit)
    plain, plain_toks = _spec_verify_run(cfg, params)
    drafts = [np.asarray(t, np.int32) for t in plain_toks]
    _spec_verify_run(cfg, params, drafts)          # warmup verify chunks
    spec, spec_toks = _spec_verify_run(cfg, params, drafts)

    trace = _spec_cascade_trace(cfg)
    raw_cas, raw_toks = _serve_spec_cascade(cfg, params, trace,
                                            speculative=False)
    spec_cas, spec_cas_toks = _serve_spec_cascade(cfg, params, trace,
                                                  speculative=True)
    return {
        "draft_k": SD_DRAFT_K,
        "verify": {
            "plain": plain,
            "speculative": spec,
            "token_exact": exact(spec_toks, plain_toks),
            "throughput_ratio": round(spec["tokens_per_s"]
                                      / plain["tokens_per_s"], 3),
        },
        "cascade": {
            "trace": {"n_requests": SC_N_REQUESTS,
                      "prompt_lens": list(SC_PROMPTS),
                      "max_new": list(SC_MAX_NEW),
                      "gate_threshold": SC_GATE_THRESHOLD},
            "raw": raw_cas,
            "speculative": spec_cas,
            "token_exact_vs_raw": exact(spec_cas_toks, raw_toks),
        },
    }


def _constellation_trace(cfg):
    from repro.serving.batching import Request

    rng = np.random.default_rng(9)
    return [Request(
        prompt=rng.integers(1, cfg.vocab_size,
                            int(rng.integers(*CN_PROMPTS))).astype(np.int32),
        max_new=int(rng.integers(CN_MAX_NEW[0], CN_MAX_NEW[1] + 1)),
        arrival_t=float(i)) for i in range(CN_N_REQUESTS)]


def _constellation_engine(cfg, params):
    from repro.serving.engine import ContinuousEngine

    return ContinuousEngine(cfg, params, n_slots=CN_SLOTS, max_seq=MAX_SEQ,
                            kv_layout="paged", page_size=CN_PAGE_SIZE,
                            pool_pages=CN_POOL_PAGES,
                            prefill_budget_tokens=16)


def _constellation_reference(cfg, params, trace):
    """Solo comparator: the same requests through ONE unconstrained
    engine — the token streams every constellation replay (with or
    without handovers) must reproduce exactly."""
    from repro.serving.scheduler import PreemptiveScheduler

    sched = PreemptiveScheduler(_constellation_engine(cfg, params))
    for r in trace:
        sched.submit(r.clone())
    while sched.has_work():
        sched.step()
    return [np.asarray(sched.results[k].tokens)
            for k in sorted(sched.results)]


def _serve_constellation(cfg, params, trace, *, policy, handover,
                         fault_seed=None):
    """One constellation replay of ``trace`` (every request uplinked
    via the window-poor satellite 0).  ``policy="static",
    handover=False`` is the K-independent-pairs comparator;
    ``fault_seed`` arms a lossy/corrupting fault plan on every framed
    lane (the chaos sweep).  Returns (summary, tokens in rid order)."""
    from repro.core.faults import FaultInjector, FaultPlan
    from repro.core.link import ContactSchedule
    from repro.serving.constellation import ConstellationScheduler

    engines = [_constellation_engine(cfg, params)
               for _ in range(CN_N_SATS)]
    ws = ContactSchedule(contact_duration_s=CN_CONTACT_DURATION_S,
                         contacts_per_day=CN_CONTACTS_PER_DAY[-1],
                         seed=CN_SCHEDULE_SEED).step_window_sets(
        1.0, CN_HORIZON_S, n_satellites=CN_N_SATS,
        n_stations=CN_N_STATIONS,
        contacts_per_day=list(CN_CONTACTS_PER_DAY))
    inj, kw = None, {}
    if fault_seed is not None:
        inj = FaultInjector(FaultPlan(
            seed=fault_seed, frame_loss_rate=CN_FRAME_LOSS,
            frame_corrupt_rate=CN_FRAME_CORRUPT,
            spill_corrupt_every=CN_SPILL_CORRUPT_EVERY))
        kw.update(faults=inj, frame_bytes=CN_FRAME_BYTES,
                  link_max_retries=CN_MAX_RETRIES)
    cs = ConstellationScheduler(engines, window_sets=ws,
                                n_stations=CN_N_STATIONS, s_per_step=1.0,
                                horizon_s=CN_HORIZON_S, policy=policy,
                                handover=handover,
                                handover_margin_ticks=CN_MARGIN_TICKS, **kw)
    assignments = [[r.clone() for r in trace]]
    assignments += [[] for _ in range(CN_N_SATS - 1)]
    t0 = time.perf_counter()
    rep = cs.run(assignments)
    wall = time.perf_counter() - t0
    toks = [rep.tokens[rid] for rid in sorted(rep.tokens)]
    out = {
        "wall_s": round(wall, 4),
        "policy": policy, "handover": handover,
        "final_clock": rep.final_clock,
        "delivered_tokens": rep.delivered_tokens,
        "goodput_tokens_per_tick": round(rep.goodput, 4),
        "n_undelivered": len(rep.undelivered),
        "n_handovers": rep.n_handovers,
        "n_result_forwards": rep.n_result_forwards,
        "n_handover_redos": rep.n_handover_redos,
        "assigned_pass_ticks": rep.assigned_pass_ticks,
        "pool_drained": all(e.slots.allocator.in_use == 0
                            and e.slots.allocator.reserved == 0
                            for e in engines),
        "spill_store_empty": all(len(s.store) == 0 for s in cs.sats),
        "lanes_empty": all(len(l) == 0 for l in [*cs.lanes, *cs.isl]),
        "within_energy_budget": rep.within_energy_budget,
        "energy_j": [round(cs.fleet.energy_j(k), 2)
                     for k in range(CN_N_SATS)],
        "fleet_totals": {k: round(v, 4)
                         for k, v in rep.fleet_totals.items()},
    }
    if inj is not None:
        out["injected"] = {
            "n_frames_lost": inj.n_frames_lost,
            "n_frame_corruptions": inj.n_frame_corruptions,
            "n_spill_corruptions": inj.n_spill_corruptions,
            "n_corruptions_injected": inj.n_corruptions_injected,
        }
        out["n_corruptions_detected"] = (
            sum(l["n_corruptions_detected"]
                for l in [*rep.lane_stats, *rep.isl_stats])
            + sum(s.store.stats().get("n_spill_corruptions_detected", 0)
                  for s in cs.sats if s.store is not None))
        out["n_silent_corruptions"] = sum(
            l["n_silent_corruptions"]
            for l in [*rep.lane_stats, *rep.isl_stats])
    return out, toks


def _constellation_report(cfg, params):
    """The GATE_VERSION 7 section: contact planning + token-exact
    handover vs K independent onboard/ground pairs on the same window
    sets.  Goodput is measured in delivered tokens per drain tick, so
    both replays are compared on schedule time, not wall time."""
    exact = lambda a, b: (len(a) == len(b)
                          and all(np.array_equal(x, y)
                                  for x, y in zip(a, b)))
    trace = _constellation_trace(cfg)
    want = _constellation_reference(cfg, params, trace)
    pooled, pooled_toks = _serve_constellation(
        cfg, params, trace, policy="value", handover=True)
    indep, indep_toks = _serve_constellation(
        cfg, params, trace, policy="static", handover=False)
    dl_pooled = pooled["fleet_totals"].get("bytes_downlinked", 0.0)
    dl_indep = indep["fleet_totals"].get("bytes_downlinked", 0.0)
    return {
        "trace": {"n_satellites": CN_N_SATS,
                  "n_stations": CN_N_STATIONS,
                  "n_requests": CN_N_REQUESTS,
                  "prompt_lens": list(CN_PROMPTS),
                  "max_new": list(CN_MAX_NEW),
                  "horizon_s": CN_HORIZON_S,
                  "contacts_per_day": list(CN_CONTACTS_PER_DAY),
                  "contact_duration_s": CN_CONTACT_DURATION_S,
                  "handover_margin_ticks": CN_MARGIN_TICKS,
                  "schedule_seed": CN_SCHEDULE_SEED},
        "pooled": pooled,
        "independent_pairs": indep,
        "token_exact_vs_solo": exact(pooled_toks, want),
        "independent_token_exact_vs_solo": exact(indep_toks, want),
        "goodput_ratio": round(
            pooled["goodput_tokens_per_tick"]
            / max(indep["goodput_tokens_per_tick"], 1e-9), 3),
        "downlink_bytes_ratio": round(dl_pooled / max(dl_indep, 1e-9), 4),
    }


def run_constellation_chaos(seeds):
    """The CI chaos sweep's constellation lane: handover under a lossy,
    corrupting fault plan (ARQ re-ships frames, corrupt spill records
    redo from prefill) must still deliver token-exact answers and drain
    every pool, store and lane."""
    import jax
    from repro.models import transformer as T

    cfg, _ = _make_engine_inputs()
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_SEQ)
    trace = _constellation_trace(cfg)
    want = _constellation_reference(cfg, params, trace)
    failures = []
    for seed in seeds:
        flt, toks = _serve_constellation(cfg, params, trace,
                                         policy="value", handover=True,
                                         fault_seed=seed)
        inj = flt["injected"]
        checks = {
            "token_exact": (len(toks) == len(want) and all(
                np.array_equal(a, b) for a, b in zip(toks, want))),
            "handovers": flt["n_handovers"] > 0,
            "all_delivered": flt["n_undelivered"] == 0,
            "detected": (inj["n_corruptions_injected"] == 0
                         or flt["n_corruptions_detected"] > 0),
            "no_silent": flt["n_silent_corruptions"] == 0,
            "drained": (flt["pool_drained"] and flt["spill_store_empty"]
                        and flt["lanes_empty"]),
        }
        bad = [k for k, ok in checks.items() if not ok]
        status = "ok" if not bad else f"FAIL({','.join(bad)})"
        print(f"constellation chaos seed={seed}: {status} "
              f"handovers={flt['n_handovers']} "
              f"redo={flt['n_handover_redos']} "
              f"injected={inj['n_corruptions_injected']} "
              f"detected={flt['n_corruptions_detected']} "
              f"clock={flt['final_clock']}")
        if bad:
            failures.append((seed, bad))
    return failures


def run_chaos(seeds):
    """The CI chaos sweep: replay the fault section under several
    FaultPlan seeds, holding the full invariant set for each."""
    import jax
    from repro.models import transformer as T

    cfg, _ = _make_engine_inputs()
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_SEQ)
    trace = _fault_trace(cfg)
    _, ref_tokens, ref_sat = _serve_fault(cfg, params, trace)
    failures = []
    for seed in seeds:
        flt, toks, sat_toks = _serve_fault(cfg, params, trace,
                                           plan_seed=seed)
        inj = flt["injected"]
        checks = {
            "token_exact": (len(toks) == len(ref_tokens) and all(
                np.array_equal(a, b) for a, b in zip(toks, ref_tokens))),
            "sat_token_exact": (len(sat_toks) == len(ref_sat) and all(
                np.array_equal(a, b)
                for a, b in zip(sat_toks, ref_sat))),
            "all_detected": (flt["n_corruptions_detected"]
                             == inj["n_corruptions_injected"]),
            "no_silent": flt["lane"]["n_silent_corruptions"] == 0,
            "conserved": flt["frame_ledger_conserved"],
            "rebooted": flt["n_reboots"] == 1 == inj["n_crashes"],
            "drained": flt["pool_drained"] and flt["spill_store_empty"],
            "all_delivered": flt["n_undelivered"] == 0,
        }
        bad = [k for k, ok in checks.items() if not ok]
        status = "ok" if not bad else f"FAIL({','.join(bad)})"
        print(f"chaos seed={seed}: {status} "
              f"injected={inj['n_corruptions_injected']} "
              f"detected={flt['n_corruptions_detected']} "
              f"lost={inj['n_frames_lost']} "
              f"retx={flt['lane']['n_retransmits']} "
              f"reboots={flt['n_reboots']} "
              f"redo={flt['n_redo_from_corruption']} "
              f"eff={flt['goodput_efficiency']}")
        if bad:
            failures.append((seed, bad))
    return failures


def _serve_mesh(cfg, params, trace, mesh):
    """One paged continuous replay, optionally on a device mesh.
    Returns (report_dict, tokens_by_rid_order) — the report carries the
    engine's full KV accounting (per-device bytes/pages, mesh axes,
    expert dispatch) so the gates read one flat dict per run."""
    from repro.serving.engine import ContinuousEngine

    eng = ContinuousEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           kv_layout="paged", page_size=PAGE_SIZE,
                           mesh=mesh)
    t0 = time.perf_counter()
    results = eng.run(_clone(trace))
    wall = time.perf_counter() - t0
    useful = sum(len(r.tokens) for r in results.values())
    alloc = eng.slots.allocator
    report = {"useful_tokens": useful, "wall_s": round(wall, 4),
              "tokens_per_s": round(useful / wall, 2),
              "pool_drained": alloc.in_use == 0 and alloc.reserved == 0,
              **eng.kv_cache_stats()}
    return report, [results[k].tokens for k in sorted(results)]


def _token_exact(a, b):
    return bool(len(a) == len(b)
                and all(np.array_equal(x, y) for x, y in zip(a, b)))


def _sharded_report():
    """Single-device vs mesh-sharded A/B on the same traces.

    The mesh spans every visible device (``make_serving_mesh()``): one
    device on the default bench lane, ``SH_FORCED_DEVICES`` under the
    ``--sharded`` CI lane.  The dense lane is warmed then timed for the
    throughput-parity gate; the MoE lane demonstrates expert-parallel
    serving prefill (per-device dispatch counts in the stats)."""
    import jax
    from repro.config import get_reduced_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as T
    from repro.serving.batching import poisson_trace

    cfg = get_reduced_config("smollm-360m").with_(
        param_dtype="float32", activation_dtype="float32",
        n_heads=8, n_kv_heads=4, head_dim=32)
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_SEQ)
    trace = poisson_trace(SH_N_REQUESTS, rate=ARRIVAL_RATE,
                          prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                          vocab_size=cfg.vocab_size, seed=SH_SEED)
    mesh = make_serving_mesh()

    runs, toks = {}, {}
    for name, m in (("single_device", None), ("sharded", mesh)):
        _serve_mesh(cfg, params, trace, m)     # warmup: populate jit caches
        for _ in range(SH_TIMED_REPS):
            rep, toks[name] = _serve_mesh(cfg, params, trace, m)
            if name not in runs or rep["wall_s"] < runs[name]["wall_s"]:
                runs[name] = rep
    sh, sd = runs["sharded"], runs["single_device"]

    # expert-parallel MoE serving: same A/B, dispatch accounting gated
    moe_cfg = get_reduced_config("qwen3-moe-30b-a3b").with_(
        param_dtype="float32", activation_dtype="float32", n_kv_heads=4)
    moe_params = T.init_params(jax.random.PRNGKey(1), moe_cfg,
                               max_seq=MAX_SEQ)
    moe_trace = poisson_trace(SH_MOE_N_REQUESTS, rate=ARRIVAL_RATE,
                              prompt_lens=PROMPT_LENS, max_new=MAX_NEW,
                              vocab_size=moe_cfg.vocab_size,
                              seed=SH_MOE_SEED)
    moe_runs, moe_toks = {}, {}
    for name, m in (("single_device", None), ("sharded", mesh)):
        moe_runs[name], moe_toks[name] = _serve_mesh(
            moe_cfg, moe_params, moe_trace, m)
    msh = moe_runs["sharded"]

    return {
        "n_devices": len(jax.devices()),
        "single_device": sd,
        "sharded": sh,
        "token_exact": _token_exact(toks["sharded"],
                                    toks["single_device"]),
        "throughput_ratio": round(sh["tokens_per_s"]
                                  / sd["tokens_per_s"], 3),
        "kv_bytes_conserved": bool(
            sh["kv_bytes_per_device"] * sh["n_kv_shards"]
            == sh["kv_cache_bytes"]),
        "peak_pages_match_ledger": bool(
            sh["peak_pages_in_use_per_device"] == sh["peak_pages_in_use"]),
        "moe": {
            "single_device": moe_runs["single_device"],
            "sharded": msh,
            "token_exact": _token_exact(moe_toks["sharded"],
                                        moe_toks["single_device"]),
            "n_experts": moe_cfg.moe.n_experts,
            "expert_dispatch_conserved": bool(
                msh["experts_per_device"] * msh["n_expert_shards"]
                == moe_cfg.moe.n_experts),
        },
        "trace": {"n_requests": SH_N_REQUESTS,
                  "moe_n_requests": SH_MOE_N_REQUESTS,
                  "n_slots": N_SLOTS, "max_seq": MAX_SEQ,
                  "page_size": PAGE_SIZE,
                  "arrival_rate": ARRIVAL_RATE,
                  "prompt_lens": list(PROMPT_LENS),
                  "max_new": list(MAX_NEW)},
    }


def run_sharded_smoke() -> bool:
    """The ``--sharded`` CI lane: ``__main__`` forces
    ``SH_FORCED_DEVICES`` host devices BEFORE JAX initializes, then this
    asserts the real multi-device invariants the 1-device bench lane
    cannot exercise (4-way KV shards, 1 expert per device)."""
    sh = _sharded_report()
    n = SH_FORCED_DEVICES
    checks = {
        "dense_token_exact": sh["token_exact"] is True,
        "moe_token_exact": sh["moe"]["token_exact"] is True,
        "n_devices": sh["n_devices"] == n,
        "kv_shards": sh["sharded"]["n_kv_shards"] == n,
        "kv_bytes_conserved": sh["kv_bytes_conserved"],
        "peak_pages_match_ledger": sh["peak_pages_match_ledger"],
        "expert_shards": sh["moe"]["sharded"]["n_expert_shards"] == n,
        "expert_dispatch_conserved": sh["moe"]["expert_dispatch_conserved"],
        "pools_drained": (sh["sharded"]["pool_drained"]
                          and sh["moe"]["sharded"]["pool_drained"]),
    }
    for name, ok in checks.items():
        print(f"{'PASS' if ok else 'FAIL'}  sharded-smoke {name}")
    print(json.dumps({"throughput_ratio": sh["throughput_ratio"],
                      "kv_bytes_per_device":
                      sh["sharded"]["kv_bytes_per_device"],
                      "experts_per_device":
                      sh["moe"]["sharded"]["experts_per_device"]},
                     sort_keys=True))
    return all(checks.values())


def run():
    import jax
    from repro.models import transformer as T

    cfg, trace = _make_engine_inputs()
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_SEQ)

    serves = (
        ("fixed_slot", lambda: _serve_fixed(cfg, params, _clone(trace))),
        ("continuous", lambda: _serve_continuous(cfg, params, trace,
                                                 "paged")),
        ("continuous_contiguous",
         lambda: _serve_continuous(cfg, params, trace, "contiguous")),
    )
    rows = []
    out = {}
    tokens_seen = {}
    for name, serve in serves:
        serve()                            # warmup: populate jit caches
        tokens, wall, kv_stats, emitted = serve()
        tps = tokens / wall
        out[name] = {"useful_tokens": tokens, "wall_s": round(wall, 4),
                     "tokens_per_s": round(tps, 2), **kv_stats}
        tokens_seen[name] = emitted
        rows.append((f"serving_{name}", wall * 1e6 / max(tokens, 1),
                     {"tokens_per_s": round(tps, 2)}))

    out["speedup"] = round(out["continuous"]["tokens_per_s"]
                           / out["fixed_slot"]["tokens_per_s"], 3)
    paged_toks = tokens_seen["continuous"]
    contig_toks = tokens_seen["continuous_contiguous"]
    out["paged_token_exact"] = (
        len(paged_toks) == len(contig_toks)
        and all(np.array_equal(a, b)
                for a, b in zip(paged_toks, contig_toks)))
    out["paged_vs_contiguous_kv_bytes"] = round(
        out["continuous"]["kv_cache_bytes"]
        / out["continuous_contiguous"]["kv_cache_bytes"], 4)
    out["trace"] = {"n_requests": N_REQUESTS, "n_slots": N_SLOTS,
                    "max_seq": MAX_SEQ,
                    "arrival_rate": ARRIVAL_RATE,
                    "prompt_lens": list(PROMPT_LENS),
                    "max_new": list(MAX_NEW),
                    "page_size": PAGE_SIZE}
    cw = _contact_window_report(cfg, params, trace, tokens_seen["continuous"])
    cw["overlap"] = _overlap_report(cfg, params, trace,
                                    tokens_seen["continuous"])
    out["contact_window"] = cw
    out["chunked_prefill"] = _chunked_prefill_report(cfg, params)
    out["shared_prefix"] = _shared_prefix_report(cfg, params)
    out["fault_replay"] = _fault_replay_report(cfg, params)
    out["speculative"] = _speculative_report(cfg, params)
    out["constellation"] = _constellation_report(cfg, params)
    out["sharded"] = _sharded_report()
    out["bench_version"] = BENCH_VERSION
    rows.append(("serving_contact_window_preemptive",
                 cw["preemptive"]["wall_s"] * 1e6
                 / max(cw["preemptive"]["useful_tokens"], 1),
                 {"goodput_ratio": cw["goodput_ratio"],
                  "n_preemptions": cw["preemptive"]["n_preemptions"],
                  "token_exact": cw["token_exact_vs_uninterrupted"]}))
    ov = cw["overlap"]
    rows.append(("serving_contact_window_overlap",
                 ov["overlapped"]["wall_s"] * 1e6
                 / max(ov["overlapped"]["useful_tokens"], 1),
                 {"goodput_ratio_vs_stop_the_world":
                  ov["goodput_ratio_vs_stop_the_world"],
                  "n_delta_spills": ov["overlapped"]["n_delta_spills"],
                  "delta_spill_bytes": ov["delta_spill_bytes"],
                  "full_spill_bytes_equiv": ov["full_spill_bytes_equiv"],
                  "token_exact": ov["token_exact_vs_uninterrupted"]}))
    cp = out["chunked_prefill"]
    rows.append(("serving_chunked_prefill_tick_p99",
                 cp["chunked"]["tick_latency_p99_s"] * 1e6,
                 {"tick_p99_ratio": cp["tick_p99_ratio"],
                  "monolithic_p99_us": round(
                      cp["monolithic"]["tick_latency_p99_s"] * 1e6, 1),
                  "token_exact": cp["token_exact"],
                  "ttft_mean_steps": cp["chunked"]["ttft_mean_steps"]}))
    fr = out["fault_replay"]
    rows.append(("serving_fault_replay",
                 fr["faulted"]["wall_s"] * 1e6
                 / max(fr["faulted"]["n_answers"], 1),
                 {"token_exact": fr["token_exact_vs_fault_free"],
                  "n_corruptions_detected":
                  fr["faulted"]["n_corruptions_detected"],
                  "n_corruptions_injected":
                  fr["faulted"]["injected"]["n_corruptions_injected"],
                  "n_reboots": fr["faulted"]["n_reboots"],
                  "goodput_efficiency":
                  fr["faulted"]["goodput_efficiency"]}))
    sp = out["shared_prefix"]
    rows.append(("serving_shared_prefix",
                 sp["shared"]["wall_s"] * 1e6
                 / max(sp["shared"]["useful_tokens"], 1),
                 {"prefill_tokens_ratio": sp["prefill_tokens_ratio"],
                  "kv_peak_bytes_ratio": sp["kv_peak_bytes_ratio"],
                  "prefix_hits": sp["shared"]["prefix_hits"],
                  "cow_page_copies": sp["shared"]["cow_page_copies"],
                  "token_exact": sp["token_exact"]}))
    sd = out["speculative"]
    rows.append(("serving_speculative",
                 sd["verify"]["speculative"]["wall_s"] * 1e6
                 / max(sd["verify"]["speculative"]["useful_tokens"], 1),
                 {"throughput_ratio": sd["verify"]["throughput_ratio"],
                  "token_exact": sd["verify"]["token_exact"],
                  "accepted": sd["verify"]["speculative"]["accepted"],
                  "cascade_token_exact":
                  sd["cascade"]["token_exact_vs_raw"],
                  "bytes_per_escalation_raw":
                  sd["cascade"]["raw"]["bytes_per_escalation"],
                  "bytes_per_escalation_spec":
                  sd["cascade"]["speculative"]["bytes_per_escalation"]}))
    cn = out["constellation"]
    rows.append(("serving_constellation",
                 cn["pooled"]["wall_s"] * 1e6
                 / max(cn["pooled"]["delivered_tokens"], 1),
                 {"goodput_ratio": cn["goodput_ratio"],
                  "n_handovers": cn["pooled"]["n_handovers"],
                  "token_exact": cn["token_exact_vs_solo"],
                  "independent_goodput":
                  cn["independent_pairs"]["goodput_tokens_per_tick"],
                  "within_energy_budget":
                  cn["pooled"]["within_energy_budget"]}))
    shd = out["sharded"]
    rows.append(("serving_sharded",
                 shd["sharded"]["wall_s"] * 1e6
                 / max(shd["sharded"]["useful_tokens"], 1),
                 {"n_devices": shd["n_devices"],
                  "n_kv_shards": shd["sharded"]["n_kv_shards"],
                  "throughput_ratio": shd["throughput_ratio"],
                  "token_exact": shd["token_exact"],
                  "moe_expert_shards":
                  shd["moe"]["sharded"]["n_expert_shards"],
                  "moe_token_exact": shd["moe"]["token_exact"]}))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_serving.json"), "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    rows.append(("serving_speedup", 0.0, {"speedup": out["speedup"]}))
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "--sharded":
        # must land in XLA_FLAGS before JAX starts a backend (importing
        # jax does not start one, so this is still early enough here)
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{SH_FORCED_DEVICES}").strip()
        ok = run_sharded_smoke()
        print(f"sharded smoke {'ok' if ok else 'FAILED'}")
        sys.exit(0 if ok else 1)
    if len(sys.argv) > 1 and sys.argv[1] == "--chaos-constellation":
        seeds = [int(s) for s in sys.argv[2:]] or [CN_FAULT_SEED]
        failures = run_constellation_chaos(seeds)
        if failures:
            print(f"constellation chaos sweep FAILED: {failures}")
            sys.exit(1)
        print(f"constellation chaos sweep ok across seeds {seeds}")
        sys.exit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "--chaos":
        seeds = [int(s) for s in sys.argv[2:]] or [0, 1, 2, 3, 4]
        failures = run_chaos(seeds)
        if failures:
            print(f"chaos sweep FAILED: {failures}")
            sys.exit(1)
        print(f"chaos sweep ok across seeds {seeds}")
        sys.exit(0)
    for name, us, derived in run():
        print(f"{name},{us:.1f},{json.dumps(derived, sort_keys=True)}")
