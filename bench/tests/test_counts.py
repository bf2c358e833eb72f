"""The FLOP and byte counts against a hand count at one shape."""
import pytest

from counts import (chunk_flops, least_time, paged_attention_work, peaks,
                    token_flops)

# L=2, d=8, H=4, Hkv=2, D=2, ff=16, V=10; the llama family's count of
# multiplied weights (test_families.test_matmul_params)
M = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "D": 2, "ff": 16, "V": 10,
     "matmul_params": 1232}


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
    t, bound = least_time(197e12, 1.0, p, 1)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = least_time(1.0, 819e9, p, 1)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    # four chips: a quarter of the time, bound by the same resource
    assert least_time(197e12, 1.0, p, 4) == (pytest.approx(0.25), "compute")
    assert least_time(1.0, 819e9, p, 4) == (pytest.approx(0.25), "memory")
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
