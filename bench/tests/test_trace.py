"""The trace reducers and the per-layer metric readers, against hand-made
traces with known answers and a small trace recorded on a TPU v5e (two
ticks of smollm-360m.chat-saturated, before the engine named its
programs)."""
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
    modules={"0": [("jit_decode_step(1)", 10, 60),
                   ("jit_prefill_chunk(2)", 150, 200)]},
    spans=[("bench.tick", 0, 100), ("bench.tick", 100, 250)],
    python=[("$engine.py:1258 step", 0, 130), ("np.asarray(jax.Array)", 60, 120),
            ("$engine.py:1258 step", 130, 250)])
DIMS = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "D": 2, "ff": 16, "V": 10,
        "matmul_params": 1232}
PEAK = peaks("TPU v5 lite")

# A window as the profiler records it where launches and device modules
# do not pair one to one: five host launches
# (``PJRT_LoadedExecutable_Execute``, which ``Trace`` no longer keeps)
# against four modules in the window, as in qwen1.5-4b's cell, where one
# launch a tick had no module.  Pairing them by order read nothing here.
# Two chips; chip 1 ran each program twice as long.  Per chip: decode
# steps 50 + 30 (chip 1: 100 + 60), a chunk of 30 (60), an argmax module
# of 2, and a decode step after the window.
UNPAIRED = {
    "ops": {c: [("%fusion.1 = f32[] fusion()", 10, 60 * k)]
            for c, k in (("0", 1), ("1", 2))},
    "modules": {c: [("jit_decode_step(11)", 10, 10 + 50 * k),
                    ("jit_argmax(5)", 120, 122),
                    ("jit_prefill_chunk(7)", 150, 150 + 30 * k),
                    ("jit_decode_step(11)", 185, 185 + 30 * k),
                    ("jit_decode_step(11)", 300, 400)]
                for c, k in (("0", 1), ("1", 2))},
    "execs": [5, 61, 118, 140, 184],
    "spans": [("bench.tick", 0, 100), ("bench.tick", 100, 250)],
    "python": [],
}


def _run(trace, decode_calls, chunk_calls, dims=DIMS, chips=1):
    return cell.Run(cell={}, dims=dims, peak=PEAK, chips=chips, seconds=1,
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
    assert devtrace.program_name("jit_decode_step(8264366344727610541)") == \
        "jit_decode_step"
    assert devtrace.program_runs(SYNTH, "jit_decode_step") == \
        [(pytest.approx(50e-9), 1)]
    assert devtrace.program_runs(SYNTH, "jit_prefill") == []
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


def test_step_readers_by_name_where_launches_do_not_pair():
    tr = devtrace.Trace.from_json(UNPAIRED)
    run = _run(tr, [[3, 5], [4, 6]], [(0, 7), (7, 3)])
    # per chip the mean of its decode steps in the window, then the mean
    # over chips: (40 + 80) / 2 ns
    assert _read("decode_step_ms", run) == pytest.approx(60e-6)
    assert _read("decode_step_ms.qwen1.5-4b", run) == pytest.approx(60e-6)
    # (30 + 60) / 2 ns over the 10 real prompt tokens of the chunk launches
    assert _read("chunk_us_per_token", run) == pytest.approx(45e-9 / 10 * 1e6)
    assert _read("chunk_us_per_token", _run(tr, [], [])) is None


def test_shares_of_peak_scale_with_the_cells_chips():
    tr = devtrace.Trace.from_json(dict(UNPAIRED, ops={"0": [
        ("%paged_decode_attention_kernel.9 = bf16[] custom-call()", 20, 40)]}))
    one = _run(tr, [[3, 5], [4, 6]], [(0, 7)], chips=1)
    four = _run(tr, [[3, 5], [4, 6]], [(0, 7)], chips=4)
    for name in ("step_mfu", "paged_attn_roofline"):
        assert _read(name, one) > 0
        assert _read(name, four) == pytest.approx(_read(name, one) / 4)


def test_split_metric_reads_with_its_base_reader():
    run = _run(SYNTH, [[3, 5]], [(4, 10)])
    assert _read("device_idle_pct.qwen1.5-4b", run) == \
        _read("device_idle_pct", run)


def test_synthetic_metric_readers():
    run = _run(SYNTH, [[3, 5]], [(4, 10)])
    assert _read("device_idle_pct", run) == pytest.approx(60.0)
    assert _read("tick_host_ms", run) == pytest.approx((50 + 100) / 2 * 1e-6)
    need = least_time(*paged_attention_work(DIMS, [3, 5]), PEAK, 1)[0]
    assert _read("paged_attn_roofline", run) == pytest.approx(need / 36e-9 * 100)
    assert _read("step_mfu", run) > 0


def test_silent_where_nothing_to_read():
    empty = devtrace.Trace(ops={"0": []}, modules={"0": []},
                           spans=[("bench.tick", 0, 10)], python=[])
    run = _run(empty, [], [])
    for name in ("decode_step_ms", "chunk_us_per_token",
                 "paged_attn_roofline", "step_mfu"):
        assert _read(name, run) is None


def test_recorded_trace():
    tr = devtrace.load(str(DATA / "trace_v5e_smollm.json"))
    n_decode = len(tr.span_list("bench.decode"))
    n_chunk = len(tr.span_list("bench.chunk"))
    assert n_decode > 0 and n_chunk > 0
    # its programs were all ``jit__lambda``: no module bears a step's name
    assert devtrace.program_runs(tr, "jit_decode_step") == []
    assert _read("decode_step_ms", _run(tr, [], [])) is None
    kern = devtrace.kernel_time(tr, "paged_decode_attention_kernel")
    busy_s, window_s = devtrace.device_busy(tr)
    assert 0 < kern < busy_s <= window_s
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


def test_recorded_trace_with_named_programs():
    """Two ticks of qwen1.5-4b.chat-saturated recorded on a TPU v5e after
    the engine named its programs: a decode step in each, a prompt chunk in
    the second, and the small programs of the token reads between them."""
    tr = devtrace.load(str(DATA / "trace_v5e_qwen.json"))
    names = {devtrace.program_name(n) for n, _, _ in tr.modules["0"]}
    assert {"jit_decode_step", "jit_prefill_chunk", "jit__argmax"} <= names
    (dec_s, n_dec), = devtrace.program_runs(tr, "jit_decode_step")
    assert n_dec == len(tr.span_list("bench.tick")) == 2
    (chunk_s, n_chunk), = devtrace.program_runs(tr, "jit_prefill_chunk")
    assert n_chunk == 1
    run = _run(tr, [], [(0, 500)])
    assert _read("decode_step_ms", run) == pytest.approx(dec_s / 2 * 1e3)
    assert 20 < _read("decode_step_ms", run) < 40
    assert _read("chunk_us_per_token", run) == pytest.approx(chunk_s / 500 * 1e6)
    # the paged kernel runs inside the decode steps
    kern = devtrace.kernel_time(tr, "paged_decode_attention_kernel")
    assert 0 < kern < dec_s


def _log(rid, times):
    return cell.RequestLog(rid=rid, prompt=None, max_new=0, times=list(times))


def test_host_clock_readers():
    logs = [_log(0, [1.0, 2.0, 4.0]),    # gaps 1, 2
            _log(1, [3.0, 3.5]),         # gap 0.5
            _log(2, []),                 # not served yet
            _log(3, [10.5, 11.0])]       # after the window
    run = cell.Run(cell={}, dims=DIMS, peak=PEAK, chips=1, seconds=10,
                   setup_s=7.5, window=(0.0, 10.0), ticks=[], requests=logs)
    assert _read("output_tok_s", run) == pytest.approx(5 / 10)
    gaps = [1.0, 2.0, 0.5]
    assert _read("itl_mean_ms", run) == pytest.approx(np.mean(gaps) * 1e3)
    assert _read("setup_s", run) == 7.5
