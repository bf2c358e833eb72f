"""Operations and bytes the model's work needs, from its sizes alone.

These count what the algorithm requires, not what an implementation runs:
padding, empty decode slots, dead pages past a sequence's length and
recomputation count for nothing.  So a share of a peak computed from them
cannot pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
BF16_BYTES = 2


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table["devices"][device_kind]


def token_flops(m: dict, context: int) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included): 2 per weight it multiplies (``matmul_params``, from
    the configuration's family module), and 4 H D per attended position
    per layer (scores and the weighted sum of values)."""
    return 2.0 * m["matmul_params"] + 4.0 * m["L"] * m["H"] * m["D"] * context


def chunk_flops(m: dict, pos_offset: int, n_valid: int) -> float:
    """Forward FLOPs of a prompt chunk: positions pos_offset .. +n_valid,
    each attending causally to everything before it and itself."""
    ctx = n_valid * pos_offset + n_valid * (n_valid + 1) / 2
    return 2.0 * m["matmul_params"] * n_valid + 4.0 * m["L"] * m["H"] * m["D"] * ctx


def paged_attention_work(m: dict, kv_lens) -> tuple:
    """(FLOPs, bytes) the decode attention needs for one step over
    sequences of the given lengths, all layers: for each sequence q and
    the output (H x D each), and the keys and values of its ``kv_len``
    live positions (Hkv x D each) read once; 4 H D FLOPs per position."""
    H, Hkv, D, L = m["H"], m["Hkv"], m["D"], m["L"]
    flops = bytes_ = 0.0
    for n in kv_lens:
        flops += 4.0 * H * D * n
        bytes_ += BF16_BYTES * (2 * H * D + 2 * Hkv * D * n)
    return flops * L, bytes_ * L


def least_time(flops: float, bytes_: float, peak: dict, chips: int) -> tuple:
    """(seconds, bound) of the roofline: the larger of the compute and the
    memory time at ``chips`` times one chip's published peaks."""
    tc = flops / (peak["bf16_flops_per_s"] * chips)
    tm = bytes_ / (peak["hbm_bytes_per_s"] * chips)
    return (tc, "compute") if tc >= tm else (tm, "memory")
