"""The readers of the engine's spans and of its launches' work:
``read_wait_ms`` and ``host_prep_ms`` against a hand-made trace whose idle
time inside and outside the reads is known, the three launch ratios
against known launches and against a tiny engine's own counters, and
silence where a program records neither."""
import numpy as np
import pytest

import cell
import devtrace
import engine_spans
from tests_common import DATA

# device busy [10, 40], [60, 70], [120, 180]; two ticks, each holding one
# engine.step; idle inside the steps' reads: 20 + 20 and 10 (the last read
# starts inside a busy interval); idle in the steps outside reads: 5 + 5
# and 10 + 5 + 5 (100-105 and 195-200 lie in ticks, outside steps)
SYNTH = devtrace.Trace(
    ops={"0": [("%fusion.1 = f32[] fusion()", 10, 40),
               ("%fusion.2 = f32[] fusion()", 60, 70),
               ("%paged_decode_attention_kernel.9 = bf16[] custom-call()", 120, 180)]},
    modules={"0": [("jit_decode_step(1)", 10, 70), ("jit_decode_step(2)", 120, 180)]},
    spans=[("bench.tick", 0, 100), ("bench.tick", 100, 200)],
    python=[("engine.step", 5, 95), ("engine.read", 40, 60),
            ("engine.read", 70, 90), ("engine.step", 105, 195),
            ("engine.read", 175, 190),
            ("engine.read", 196, 199)])     # outside any step: not counted


def _run(trace=None, decode_calls=(), chunk_calls=(), config="smollm-360m"):
    return cell.Run(cell={"config": config}, dims={}, peak=None, chips=1,
                    seconds=1,
                    setup_s=1.0, window=(0, 1), ticks=[], requests=[],
                    decode_calls=list(decode_calls),
                    chunk_calls=list(chunk_calls), trace=trace)


def _read(name, run):
    return cell.reader(name)(run)


def test_span_readers_on_known_idle():
    run = _run(SYNTH)
    assert engine_spans.idle_split(SYNTH) == (2, pytest.approx(80.0),
                                              pytest.approx(50.0))
    read_wait, host_prep = _read("read_wait_ms", run), _read("host_prep_ms", run)
    assert read_wait == pytest.approx(50 / 2 * 1e-6)
    assert host_prep == pytest.approx(30 / 2 * 1e-6)
    # the engine's steps lie inside the harness's ticks
    tick_host = _read("tick_host_ms", run)
    assert tick_host == pytest.approx(100 / 2 * 1e-6)
    assert read_wait + host_prep <= tick_host
    assert _read("read_wait_ms.qwen1.5-4b", run) == read_wait


def test_launch_readers():
    # smollm-360m's engine: 64 slots, pages of 16, max_seq 2048 (128 pages)
    decode_calls = [[1, 16, 17], [2, 17, 18, 33]]     # pages 1+1+2, 1+2+2+3
    chunk_calls = [(0, 512), (512, 100), (0, 5)]      # widths 512, 128, 8
    run = _run(decode_calls=decode_calls, chunk_calls=chunk_calls)
    assert _read("decode_slot_pct", run) == pytest.approx(7 / 128 * 100)
    assert _read("decode_live_page_pct", run) == \
        pytest.approx(12 / (2 * 64 * 128) * 100)
    assert _read("chunk_pad_pct", run) == pytest.approx(31 / 648 * 100)
    assert _read("decode_slot_pct.qwen1.5-4b", run) == \
        _read("decode_slot_pct", run)


@pytest.mark.parametrize("name", ["decode_slot_pct", "decode_live_page_pct",
                                  "chunk_pad_pct"])
def test_launch_readers_silent_without_launches(name):
    assert _read(name, _run()) is None


def test_chunk_pad_silent_without_program_bucket(monkeypatch):
    import repro.serving.engine as engine

    monkeypatch.delattr(engine, "chunk_bucket")
    assert _read("chunk_pad_pct", _run(chunk_calls=[(0, 5)])) is None


def test_launch_readers_match_engine_counters(monkeypatch):
    """On a tiny engine driven as a traced window drives it, the readers
    of the harness's launch records give the ratios of the engine's own
    counters' growth."""
    import model
    from repro.serving.batching import Request

    load = model.load_config
    monkeypatch.setattr(model, "load_config",
                        lambda name, data=DATA: load(name, DATA))
    spec = load("tiny-gqa", DATA)
    eng = cell.build_engine(spec, seed=3, chips=1)
    cell.warm_up(eng, spec)
    tracer = cell.Tracer(eng)
    rng = np.random.default_rng(0)
    for S, m in zip(rng.integers(3, 90, 9), rng.integers(1, 12, 9)):
        eng.submit(Request(prompt=rng.integers(0, 256, int(S), dtype=np.int32),
                           max_new=int(m)))
    eng.step()
    c0 = eng.counters()
    tracer.recording = True
    while len(eng.queue) or eng.slots.any_active():
        eng.step()
    tracer.recording = False
    c = {k: v - c0[k] for k, v in eng.counters().items()}
    run = _run(decode_calls=tracer.decode_calls, chunk_calls=tracer.chunk_calls,
               config="tiny-gqa")
    assert c["decode_rows_total"] and c["chunk_bucket_tokens_total"]
    assert _read("decode_slot_pct", run) == pytest.approx(
        c["decode_tokens_total"] / c["decode_rows_total"] * 100)
    assert _read("decode_live_page_pct", run) == pytest.approx(
        c["decode_live_pages_total"] / c["decode_grid_pages_total"] * 100)
    assert _read("chunk_pad_pct", run) == pytest.approx(
        (c["chunk_bucket_tokens_total"] - c["prefill_tokens_total"])
        / c["chunk_bucket_tokens_total"] * 100)


@pytest.mark.parametrize("name", ["read_wait_ms", "host_prep_ms"])
def test_span_readers_silent_without_engine_spans(name):
    # recorded before the engine had spans
    tr = devtrace.load(str(DATA / "trace_v5e_smollm.json"))
    assert not [n for n, _, _ in tr.python if n.startswith("engine.")]
    assert _read(name, _run(tr)) is None
    no_ops = devtrace.Trace(ops={}, spans=[("bench.tick", 0, 10)],
                            python=[("engine.step", 1, 9)])
    assert _read(name, _run(no_ops)) is None
