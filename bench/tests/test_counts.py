"""The FLOP and byte counts against a hand count at one shape."""
import pytest

from counts import (chunk_flops, least_time, matmul_params,
                    paged_attention_work, peaks, token_flops)

# L=2, d=8, H=4, Hkv=2, D=2, ff=16, V=10
M = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "D": 2, "ff": 16, "V": 10}


def test_matmul_params():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 64+64+64+384
    assert matmul_params(M) == 2 * 576 + 80


def test_token_and_chunk_flops():
    assert token_flops(M, 5) == 2 * 1232 + 4 * 2 * 4 * 2 * 5
    # positions 3,4,5 attend 4,5,6 positions
    want = sum(token_flops(M, c) for c in (4, 5, 6))
    assert chunk_flops(M, 3, 3) == pytest.approx(want)


def test_paged_attention_work():
    flops, bytes_ = paged_attention_work(M, [3, 10])
    # per layer and sequence: 4 H D n flops; bf16 q + out (2 H D) and
    # K, V of n positions (2 Hkv D n)
    assert flops == 2 * (4 * 4 * 2 * 3 + 4 * 4 * 2 * 10)
    assert bytes_ == 2 * 2 * ((2 * 8 + 2 * 4 * 3) + (2 * 8 + 2 * 4 * 10))


def test_least_time_and_peaks():
    p = peaks("TPU v5 lite")
    t, bound = least_time(197e12, 1.0, p)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = least_time(1.0, 819e9, p)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
