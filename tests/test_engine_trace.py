"""The engine tick's observability: ``ContinuousEngine.counters`` against
the per-tick token accounting, the ``engine.step`` / ``engine.read``
spans as a profiler session records them, and the names the jitted steps
give their programs."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.serving.batching import Request
from repro.serving.engine import DECODING, ContinuousEngine
from repro.serving.scheduler import PreemptiveScheduler

from helpers import f32_cfg

N_SLOTS, MAX_SEQ, PAGE = 3, 64, 8


@pytest.fixture(scope="module")
def cfg():
    return f32_cfg("smollm-360m")


@pytest.fixture(scope="module")
def params(cfg):
    return T.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_SEQ)


def _engine(cfg, params, **kw):
    kw = {"n_slots": N_SLOTS, "max_seq": MAX_SEQ, "page_size": PAGE,
          "prefill_budget_tokens": 16, **kw}
    return ContinuousEngine(cfg, params, **kw)


def _requests(cfg, n=7, seed=0):
    """Prompts across chunk-bucket edges (3-30 tokens under a budget of
    16), answers of 1-9 tokens."""
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, int(S),
                                        dtype=np.int32),
                    max_new=int(m))
            for S, m in zip(rng.integers(3, 31, n), rng.integers(1, 10, n))]


def _drain(eng, step):
    n = 0
    while len(eng.queue) or eng.slots.any_active():
        step()
        n += 1
    return n


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_counters_match_tick_accounting(cfg, params, layout):
    eng = _engine(cfg, params, kv_layout=layout)
    chunks, live = [], []               # (bucket, real tokens); pages/launch
    run_chunk, decode = eng._run_chunk, eng._decode

    def recording_chunk(toks, n_valid, pos_offset, bt):
        chunks.append((toks.shape[1], int(n_valid)))
        return run_chunk(toks, n_valid, pos_offset, bt)

    def recording_decode(*args, **kw):
        live.append(sum(-(-(st.pos + 1) // PAGE) for st in eng.slots.states
                        if st is not None and st.phase == DECODING))
        return decode(*args, **kw)

    eng._run_chunk, eng._decode = recording_chunk, recording_decode
    for r in _requests(cfg):
        eng.submit(r)
    decode_tokens = 0
    while len(eng.queue) or eng.slots.any_active():
        eng.step()
        decode_tokens += eng.last_tick_decode_tokens
    c = eng.counters()

    assert c["decode_tokens_total"] == decode_tokens > 0
    assert c["decode_rows_total"] == N_SLOTS * len(live)
    assert c["decode_tokens_total"] <= c["decode_rows_total"]
    if layout == "paged":
        assert c["decode_live_pages_total"] == sum(live)
        assert c["decode_grid_pages_total"] == \
            N_SLOTS * eng.slots.max_bt * len(live)
        assert 0 < c["decode_live_pages_total"] <= c["decode_grid_pages_total"]
        assert c["prefill_tokens_total"] == sum(n for _, n in chunks)
        assert c["chunk_bucket_tokens_total"] == sum(b for b, _ in chunks)
        assert c["prefill_tokens_total"] <= c["chunk_bucket_tokens_total"]
        assert chunks and all(b < 2 * n or b == 8 for b, n in chunks)
    else:                               # no pages, no chunks
        assert not chunks
        assert c["decode_live_pages_total"] == c["decode_grid_pages_total"] == 0
        assert c["chunk_bucket_tokens_total"] == 0


def test_counters_are_plain_ints_and_not_cloned(cfg, params):
    eng = _engine(cfg, params)
    eng.run(_requests(cfg, n=3))
    c = eng.counters()
    assert set(c) == set(ContinuousEngine.COUNTERS)
    assert all(type(v) is int for v in c.values())
    assert all(c[k] > 0 for k in c)
    fresh = eng.clone_fresh().counters()
    assert fresh == {k: 0 for k in c}


def _host_spans(log_dir):
    """{line: [(name, start, end)]} of the ``engine.*`` events on the
    profile's host plane."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                   if e.name.startswith("engine.")]
            if evs:
                out[i] = evs
    return out


@pytest.mark.parametrize("stepper", ["engine", "scheduler"])
def test_spans_nest_on_one_host_line(cfg, params, tmp_path, stepper):
    eng = _engine(cfg, params)
    sched = PreemptiveScheduler(eng) if stepper == "scheduler" else None
    step = sched.step if sched is not None else eng.step
    for r in _requests(cfg, n=4, seed=1):
        eng.submit(r)
    step()                              # compile outside the session
    with jax.profiler.trace(str(tmp_path)):
        if sched is not None:
            sched.step(decode=False)    # an idle tick opens no span
        n = _drain(eng, step)
    lines = _host_spans(str(tmp_path))
    assert len(lines) == 1
    (evs,) = lines.values()
    steps = sorted((s, e) for name, s, e in evs if name == "engine.step")
    reads = [(s, e) for name, s, e in evs if name == "engine.read"]
    assert len(steps) == n > 0
    assert reads
    for s, e in reads:
        assert any(a <= s and e <= b for a, b in steps), (s, e)


@pytest.mark.parametrize("layout,jit_attr,module", [
    ("paged", "_decode", "jit_decode_step"),
    ("paged", "_chunk", "jit_prefill_chunk"),
    ("contiguous", "_decode", "jit_decode_step"),
    ("contiguous", "_prefill", "jit_prefill")])
def test_jitted_steps_are_named(cfg, params, layout, jit_attr, module):
    eng = _engine(cfg, params, kv_layout=layout)
    toks, pos = eng.slots.decode_inputs()
    cache = eng.slots.cache
    args = {
        "_decode": (params, cache, toks, pos) + (
            (eng.slots.block_tables(),) if layout == "paged" else ()),
        "_chunk": (params, cache, np.zeros((1, 8), np.int32), jnp.int32(5),
                   jnp.int32(0), np.zeros((1, MAX_SEQ // PAGE), np.int32),
                   None),
        "_prefill": (params, np.zeros((1, 8), np.int32), None),
    }[jit_attr]
    text = getattr(eng, jit_attr).lower(*args).as_text()
    assert f"module @{module} " in text
