"""``collective_pct``: the share of each chip's device-busy time spent in
collective ops, against a hand-made two-chip trace with a known answer,
silent on one chip's trace, and on two ticks of granite-20b's four-chip
cell recorded on a TPU v5e host, with ``paged_attn_roofline`` beside it."""
import json

import pytest

import cell
import devtrace
from counts import peaks
from model import dims, load_config
from tests_common import DATA

PEAK = peaks("TPU v5 lite")

# chip 0: busy [10, 60] and [100, 140]; collectives [20, 30] (all-reduce),
# [25, 35] (its -done half, overlapping), [100, 110] (all-gather-start):
# 25 of 90.  Chip 1: busy [10, 50]; one all-reduce [40, 50]: 10 of 40.
# Ops before or after the ticks do not count.
SYNTH = devtrace.Trace(
    ops={"0": [("%fusion.1 = f32[] fusion()", 10, 60),
               ("%all-reduce.5 = bf16[] all-reduce()", 20, 30),
               ("%all-reduce-done.2 = bf16[] all-reduce-done()", 25, 35),
               ("%fusion.2 = f32[] fusion()", 100, 140),
               ("%all-gather-start.1 = bf16[] all-gather-start()", 100, 110),
               ("%all-reduce.9 = bf16[] all-reduce()", 300, 320)],
         "1": [("%fusion.1 = f32[] fusion()", 10, 50),
               ("%all-reduce.5 = bf16[] all-reduce()", 40, 50)]},
    modules={}, spans=[("bench.tick", 0, 100), ("bench.tick", 100, 200)],
    python=[])


def _run(trace, decode_calls=(), dims=None, chips=4):
    return cell.Run(cell={}, dims=dims or {}, peak=PEAK, chips=chips,
                    seconds=1, setup_s=1.0, window=(0, 1), ticks=[],
                    requests=[], decode_calls=list(decode_calls),
                    chunk_calls=[], trace=trace)


def test_collective_share_of_busy_time():
    got = cell.reader("collective_pct")(_run(SYNTH))
    assert got == pytest.approx((25 / 90 + 10 / 40) / 2 * 100)
    assert cell.reader("collective_pct.granite-20b")(_run(SYNTH)) == got


def test_silent_without_collectives():
    one = devtrace.load(str(DATA / "trace_v5e_qwen.json"))
    assert cell.reader("collective_pct")(_run(one, chips=1)) is None


def test_recorded_four_chip_trace():
    """Two ticks of granite-20b.chat-saturated-4chip (seed 3160000021),
    each with a decode step of 48 rows and prompt chunks: on every chip
    the paged kernel runs once a layer in each decode step, the decode
    step holds no gather of the pool (its only gathers are the kernel's
    fetch table, read from the block tables), and the tensor-parallel
    all-reduces take a share of the busy time."""
    raw = json.loads((DATA / "trace_v5e_granite.json").read_text())
    tr = devtrace.Trace.from_json(raw)
    assert sorted(tr.ops) == ["0", "1", "2", "3"]
    for chip, ops in tr.ops.items():
        steps = [(s, e) for n, s, e in tr.modules[chip]
                 if devtrace.program_name(n) == "jit_decode_step"]
        assert len(steps) == 2
        in_step = lambda s: any(a <= s <= b for a, b in steps)  # noqa: E731
        kinds = [(devtrace.op_kind(n), e - s) for n, s, e in ops
                 if in_step(s)]
        assert sum(k == "paged_decode_attention_kernel"
                   for k, _ in kinds) == 2 * 52
        assert any(k == "all-reduce" for k, _ in kinds)
        gather = sum(t for k, t in kinds if k == "gather")
        assert gather < 1e-3 * sum(e - s for s, e in steps)
    run = _run(tr, raw["decode_calls"], dims(load_config("granite-20b")))
    coll = cell.reader("collective_pct.granite-20b")(run)
    roof = cell.reader("paged_attn_roofline.granite-20b")(run)
    assert 5 < coll < 25
    assert 0.5 < roof < 105
