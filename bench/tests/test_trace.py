"""The trace reducers and the per-layer metric readers, against a
hand-made trace with known answers and a small trace recorded on a TPU v5e
(two ticks of smollm-360m.chat-saturated)."""
import numpy as np
import pytest

import cell
import devtrace
from counts import least_time, paged_attention_work, peaks
from tests_common import DATA

SYNTH = devtrace.Trace(
    ops={"0": [("%while.5 = (...) while(...)", 10, 60),
               ("%paged_decode_attention_kernel.9 = bf16[...] custom-call(...)", 12, 30),
               ("%paged_decode_attention_kernel.9 = bf16[...] custom-call(...)", 32, 50),
               ("%copy.77 = bf16[...] copy(...)", 50, 58),
               ("%fusion.3 = f32[...] fusion(...)", 150, 200)]},
    modules={"0": [("jit__lambda(1)", 10, 60), ("jit__lambda(2)", 150, 200)]},
    execs=[5, 140],
    spans=[("bench.tick", 0, 100), ("bench.decode", 4, 6),
           ("bench.tick", 100, 250), ("bench.chunk", 139, 141)],
    python=[("$engine.py:1258 step", 0, 130), ("np.asarray(jax.Array)", 60, 120),
            ("$engine.py:1258 step", 130, 250)])
DIMS = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "D": 2, "ff": 16, "V": 10}
PEAK = peaks("TPU v5 lite")


def _run(trace, decode_calls, chunk_calls, dims=DIMS):
    return cell.Run(cell={}, dims=dims, peak=PEAK, seconds=1,
                    setup_s=1.0, window=(0, 1), ticks=[], requests=[],
                    decode_calls=decode_calls, chunk_calls=chunk_calls,
                    trace=trace)


def _read(name, run):
    return cell.reader(name)(run)


def test_op_kind():
    assert devtrace.op_kind(SYNTH.ops["0"][1][0]) == "paged_decode_attention_kernel"
    assert devtrace.op_kind("%constant_dynamic-slice_fusion.11 = x") == \
        "constant_dynamic-slice_fusion"
    assert devtrace.op_kind("copy-start.14") == "copy-start"


def test_synthetic_reductions():
    assert devtrace.module_kinds(SYNTH, "0") == [("decode", 10, 60),
                                                 ("chunk", 150, 200)]
    assert devtrace.program_time(SYNTH, "decode") == (pytest.approx(50e-9), 1)
    assert devtrace.kernel_time(SYNTH, "paged_decode_attention_kernel") == \
        pytest.approx(36e-9)
    assert devtrace.device_busy(SYNTH) == (pytest.approx(100e-9),
                                           pytest.approx(250e-9))
    bd = devtrace.breakdown(SYNTH)
    assert bd["device_ops"] == [["fusion", pytest.approx(50e-9)],
                                ["paged_decode_attention_kernel", pytest.approx(36e-9)],
                                ["copy", pytest.approx(8e-9)],
                                ["while", pytest.approx(6e-9)]]
    # gaps [0, 10], [60, 150], [200, 250], each named by the innermost
    # host span open at its middle
    assert bd["idle_gaps"] == [["np.asarray(jax.Array)", pytest.approx(90e-9)],
                               ["$engine.py:1258 step", pytest.approx(60e-9)]]


def test_synthetic_step_readers():
    run = _run(SYNTH, [[3, 5]], [(4, 10)])
    assert _read("decode_step_ms", run) == pytest.approx(50e-6)
    assert _read("chunk_us_per_token", run) == pytest.approx(50e-9 / 10 * 1e6)


def test_split_metric_reads_with_its_base_reader():
    run = _run(SYNTH, [[3, 5]], [(4, 10)])
    assert _read("device_idle_pct.qwen1.5-4b", run) == \
        _read("device_idle_pct", run)


def test_synthetic_metric_readers():
    run = _run(SYNTH, [[3, 5]], [(4, 10)])
    assert _read("device_idle_pct", run) == pytest.approx(60.0)
    assert _read("tick_host_ms", run) == pytest.approx((50 + 100) / 2 * 1e-6)
    need = least_time(*paged_attention_work(DIMS, [3, 5]), PEAK)[0]
    assert _read("paged_attn_roofline", run) == pytest.approx(need / 36e-9 * 100)
    assert _read("step_mfu", run) > 0


def test_silent_where_nothing_to_read():
    empty = devtrace.Trace(ops={"0": []}, modules={"0": []}, execs=[],
                           spans=[("bench.tick", 0, 10)], python=[])
    run = _run(empty, [], [])
    for name in ("decode_step_ms", "chunk_us_per_token",
                 "paged_attn_roofline", "step_mfu"):
        assert _read(name, run) is None


def test_recorded_trace():
    tr = devtrace.load(str(DATA / "trace_v5e_smollm.json"))
    kinds = devtrace.module_kinds(tr, "0")
    assert kinds is not None
    n_decode = len(tr.span_list("bench.decode"))
    n_chunk = len(tr.span_list("bench.chunk"))
    assert sum(k == "decode" for k, _, _ in kinds) == n_decode > 0
    assert sum(k == "chunk" for k, _, _ in kinds) == n_chunk > 0
    dec_s, _ = devtrace.program_time(tr, "decode")
    kern = devtrace.kernel_time(tr, "paged_decode_attention_kernel")
    assert 0 < kern < dec_s
    busy_s, window_s = devtrace.device_busy(tr)
    assert 0 < busy_s <= window_s
    bd = devtrace.breakdown(tr)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert "paged_decode_attention_kernel" in [n for n, _ in bd["device_ops"]]
    from model import dims, load_config
    m = dims(load_config("smollm-360m"))
    # every one of 64 slots live at the longest context: the most work a
    # decode step can need, still far under the kernel's time
    run = _run(tr, [[2048] * 64] * n_decode, [(0, 512)] * n_chunk, dims=m)
    assert 0 < _read("paged_attn_roofline", run) < 100
    assert 0 < _read("step_mfu", run) < 100
    assert 0 <= _read("device_idle_pct", run) < 100
    assert _read("tick_host_ms", run) > 0


def _log(rid, times):
    return cell.RequestLog(rid=rid, prompt=None, max_new=0, times=list(times))


def test_host_clock_readers():
    logs = [_log(0, [1.0, 2.0, 4.0]),    # gaps 1, 2
            _log(1, [3.0, 3.5]),         # gap 0.5
            _log(2, []),                 # not served yet
            _log(3, [10.5, 11.0])]       # after the window
    run = cell.Run(cell={}, dims=DIMS, peak=PEAK, seconds=10,
                   setup_s=7.5, window=(0.0, 10.0), ticks=[], requests=logs)
    assert _read("output_tok_s", run) == pytest.approx(5 / 10)
    gaps = [1.0, 2.0, 0.5]
    assert _read("itl_mean_ms", run) == pytest.approx(np.mean(gaps) * 1e3)
    assert _read("setup_s", run) == 7.5
