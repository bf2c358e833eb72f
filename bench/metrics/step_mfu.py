"""Model FLOP/s utilization of the whole step: forward FLOPs of every real
token run in the traced window (decode tokens at their kv_len, prompt
chunk tokens at their positions), over the window's seconds times the
cell's chips times one chip's published bf16 peak."""
from counts import chunk_flops, token_flops


def read(run):
    if run.peak is None or (not run.decode_calls and not run.chunk_calls):
        return None
    lo, hi = run.trace.window()
    flops = sum(token_flops(run.dims, n) for c in run.decode_calls for n in c)
    flops += sum(chunk_flops(run.dims, off, n) for off, n in run.chunk_calls)
    peak = run.peak["bf16_flops_per_s"] * run.chips
    return flops / ((hi - lo) * 1e-9 * peak) * 100.0
