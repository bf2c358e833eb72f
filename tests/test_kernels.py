"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret=True on
CPU) against its pure-jnp oracle in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 256, 4, 2, 64),
    (1, 512, 2, 1, 128),
    (2, 256, 8, 8, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128), (False, 0)])
def test_flash_attention_kernel(B, S, H, Hkv, D, dtype, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=128, block_k=128)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,Hkv,D", [(2, 512, 8, 2, 64), (1, 256, 4, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kv_len", [1, 100, 512])
def test_decode_attention_kernel(B, S, H, Hkv, D, dtype, kv_len):
    if kv_len > S:
        pytest.skip("kv_len beyond cache")
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(kv_len), block_k=128)
    want = ref.decode_attention_ref(q, k, v, jnp.int32(kv_len))
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,Hkv,D", [(2, 512, 8, 2, 64)])
@pytest.mark.parametrize("S_odd", [100, 129, 500])
def test_decode_attention_kernel_unaligned_cache(B, S, H, Hkv, D, S_odd):
    """Any cache length works: S is padded up to a block_k multiple and
    the pad positions stay masked."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, S_odd, Hkv, D))
    v = jax.random.normal(ks[2], (B, S_odd, Hkv, D))
    lens = jnp.asarray([1, S_odd], jnp.int32)[:B]
    out = ops.decode_attention(q, k, v, lens, block_k=64)
    want = ref.decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def _paged_case(B, H, Hkv, D, ps, max_bt, lens=None, id=None):
    return pytest.param(B, H, Hkv, D, ps, max_bt, lens,
                        id=id or f"{B}-{H}-{Hkv}-{D}-{ps}-{max_bt}")


def _engine_tables(lens, ps, max_bt, rng):
    """Block tables as the engine builds them: each row's first
    ceil(kv_len / ps) entries on distinct pool pages, the rest on the
    scratch page 0; a row with kv_len 1 (a non-decoding row) is page 0
    throughout."""
    bt = np.zeros((len(lens), max_bt), np.int32)
    free = iter(rng.permutation(np.arange(1, len(lens) * max_bt + 1)))
    for b, n in enumerate(lens):
        if n > 1:
            bt[b, :-(-n // ps)] = [next(free) for _ in range(-(-n // ps))]
    return bt


@pytest.mark.parametrize("B,H,Hkv,D,ps,max_bt,lens", [
    _paged_case(2, 4, 2, 64, 16, 4),
    _paged_case(3, 8, 1, 32, 8, 6),
    _paged_case(1, 2, 2, 128, 16, 2),
    _paged_case(3, 15, 5, 64, 16, 4),             # smollm-360m heads (g=3)
    _paged_case(2, 8, 4, 48, 16, 3),              # tiansuan-ground heads
    # 8-page blocks below (128 positions); 20 entries leave a last
    # block of 4, and row 0 ends inside it
    _paged_case(2, 4, 2, 64, 16, 20, (300, 17), id="table-not-block-multiple"),
    _paged_case(3, 4, 2, 64, 16, 40, (256, 512, 255), id="block-boundary"),
    _paged_case(2, 8, 4, 48, 16, 48, (700, 33), id="several-blocks"),
    _paged_case(4, 15, 5, 64, 16, 24, (1, 290, 1, 1), id="idle-rows"),
    _paged_case(3, 15, 5, 64, 16, 128, (2048, 1100, 1),
                id="smollm-360m-served-width"),
    _paged_case(2, 20, 20, 128, 16, 128, (1500, 1),
                id="qwen1.5-4b-served-width"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_kernel(B, H, Hkv, D, ps, max_bt, lens, dtype):
    """Interpret-mode paged kernel vs the ref.py gather reference, with
    shuffled (non-contiguous) block tables and ragged lengths: random
    ones, or given ones on engine-built tables."""
    n_pages = B * max_bt + 1                      # + scratch page 0
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (n_pages, ps, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (n_pages, ps, Hkv, D), dtype)
    rng = np.random.default_rng(0)
    if lens is None:
        bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(B, max_bt), jnp.int32)
        lens = jnp.asarray(rng.integers(1, max_bt * ps + 1, B), jnp.int32)
    else:
        bt = jnp.asarray(_engine_tables(lens, ps, max_bt, rng))
        lens = jnp.asarray(lens, jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, bt, lens)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))
    # cross-check the gather reference itself against the contiguous
    # oracle on the gathered layout
    kg = kp[bt].reshape(B, -1, Hkv, D)
    vg = vp[bt].reshape(B, -1, Hkv, D)
    np.testing.assert_allclose(want.astype(jnp.float32),
                               ref.decode_attention_ref(
                                   q, kg, vg, lens).astype(jnp.float32),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_reads_no_dead_page(dtype):
    """Pages that no row lists inside ceil(kv_len / page_size) hold NaN,
    and every dead table entry points at one: the output stays finite
    and matches the reference on a clean pool.  Positions past kv_len in
    each last live page hold huge finite values, then NaN: the output
    does not change by a single bit."""
    B, H, Hkv, D, ps, max_bt = 3, 15, 5, 64, 16, 40
    lens = np.array([300, 256, 17])
    n_live = -(-lens // ps)
    n_pages = 1 + int(n_live.sum()) + 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (n_pages, ps, Hkv, D), dtype)
    vp = jax.random.normal(ks[2], (n_pages, ps, Hkv, D), dtype)
    rng = np.random.default_rng(1)
    pages = rng.permutation(np.arange(1, n_pages))
    live, dead = pages[:n_live.sum()], pages[n_live.sum():]
    bt = rng.choice(dead, (B, max_bt)).astype(np.int32)
    starts = np.concatenate([[0], np.cumsum(n_live)])
    for b in range(B):
        bt[b, :n_live[b]] = live[starts[b]:starts[b + 1]]
    bt, kv_len = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    want = ref.paged_decode_attention_ref(q, kp, vp, bt, kv_len)

    poisoned = jnp.zeros(n_pages, bool).at[jnp.asarray(dead)].set(True)
    nan = lambda x: jnp.where(poisoned[:, None, None, None], jnp.nan, x)
    got = ops.paged_decode_attention(q, nan(kp), nan(vp), bt, kv_len)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))

    for k_tail, v_tail in ((3e38, -3e38), (jnp.nan, jnp.nan)):
        kt, vt = nan(kp), nan(vp)
        for b in range(B):
            last = int(bt[b, n_live[b] - 1])
            tail = lens[b] - (n_live[b] - 1) * ps
            kt = kt.at[last, tail:].set(k_tail)
            vt = vt.at[last, tail:].set(v_tail)
        tailed = ops.paged_decode_attention(q, kt, vt, bt, kv_len)
        np.testing.assert_array_equal(np.asarray(tailed.astype(jnp.float32)),
                                      np.asarray(got.astype(jnp.float32)))


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (2, 256, 2, 32, 16, 64),
    (1, 512, 3, 64, 64, 128),
    (2, 128, 1, 16, 8, 128),   # chunk == S
])
def test_ssm_scan_kernel(B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.0))
    Bm = jax.random.normal(ks[3], (B, S, H, N))
    Cm = jax.random.normal(ks[4], (B, S, H, N))
    y, h = ops.ssm_chunk_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, h_ref = ref.ssm_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(y, y_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(h, h_ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("B,V", [(8, 1000), (16, 4096), (4, 50257)])
@pytest.mark.parametrize("scale", [1.0, 8.0])
def test_conf_gate_kernel(B, V, scale):
    logits = jax.random.normal(KEY, (B, V)) * scale
    got = ops.confidence_gate(logits, block_b=4, block_v=1024)
    want = ref.confidence_gate_ref(logits)
    for k in ("max_prob", "entropy", "margin"):
        np.testing.assert_allclose(got[k], want[k], atol=2e-4, rtol=1e-3)
    assert bool(jnp.all(got["argmax"] == want["argmax"]))


@pytest.mark.parametrize("N,D", [(256, 128), (512, 384), (128, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_quant_kernel(N, D, dtype):
    x = jax.random.normal(KEY, (N, D), dtype) * 3.0
    q, s = ops.int8_quantize(x, block_rows=128)
    qr, sr = ref.int8_quantize_ref(x)
    np.testing.assert_allclose(s, sr, rtol=1e-5)
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32) - qr.astype(jnp.int32)))) <= 1
    # reconstruction error bounded by scale/2 (+1 ulp grace)
    rec = ref.int8_dequantize_ref(q, s)
    err = jnp.max(jnp.abs(rec - x.astype(jnp.float32)))
    assert float(err) <= float(jnp.max(s)) * 1.51
