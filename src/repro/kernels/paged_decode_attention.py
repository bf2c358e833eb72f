"""Pallas TPU paged decode-attention kernel: ONE query token per
sequence against a paged KV cache (flash-decoding online softmax over
block-table-indexed pages).

The KV pool is ``(n_pages, page_size, Hkv, D)`` — a sequence's keys live
in the pages named by its block table, page ``j`` holding absolute
positions ``[j*page_size, (j+1)*page_size)``.  The block tables and
per-sequence lengths are **scalar-prefetched**
(``pltpu.PrefetchScalarGridSpec``) so the kv BlockSpec ``index_map`` can
dereference the table: grid step ``(b, j)`` DMAs page
``block_tables[b, j]`` — all of its KV heads — straight from the pool;
the gather happens in the DMA engine, never materializing a contiguous
copy of the sequence.

Grid: (batch, max_pages) — the page axis is minor-most, so the
online-softmax scratch (running max / denominator / accumulator, one
row per query head) persists across it.  A static loop over the KV
heads runs inside each step.  Table entries past
``ceil(kv_len/page_size)`` point at the scratch page 0; their positions
fail the ``kpos < kv_len`` mask, so stale data there (or in a freshly
allocated page's tail) is never read — the paged layout's
overwrite-before-read guarantee.

BlockSpec tiling (VMEM).  The TPU lowering needs each block's two
minor dimensions to be multiples of (8, 128) or the array's own, so
every block keeps the array's trailing dimensions whole:
    q:     (1, Hkv, g, D)         — the g = H/Hkv query heads per kv head
    k, v:  (1, page_size, Hkv, D) — one streamed KV page, every kv head
    out:   (1, Hkv, g, D)         — written on the last page
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            page_size: int, n_pages_grid: int, n_kv_heads: int, g: int,
            scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    kv_len = len_ref[b]
    kpos = (j * page_size
            + jax.lax.broadcasted_iota(jnp.int32, (g, page_size), 1))
    valid = kpos < kv_len
    for h in range(n_kv_heads):
        q = q_ref[0, h].astype(F32) * scale                  # (g, D)
        k = k_ref[0, :, h, :].astype(F32)                    # (ps, D)
        v = v_ref[0, :, h, :].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, ps)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_s[h]                                      # (g, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[h] = (acc_s[h] * corr
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
        m_s[h] = m_new

    @pl.when(j == n_pages_grid - 1)
    def _finish():
        l_safe = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_kernel(q, k_pages, v_pages, block_tables, kv_len,
                                  *, interpret: bool = False):
    """q: (B, H, D); k_pages, v_pages: (n_pages, page_size, Hkv, D);
    block_tables: (B, max_pages) int32 page ids (unused entries 0);
    kv_len: () or (B,) int32 valid positions per sequence.
    Returns (B, H, D)."""
    B, H, D = q.shape
    page_size, Hkv = k_pages.shape[1], k_pages.shape[2]
    g = H // Hkv
    max_pages = block_tables.shape[1]

    kernel = functools.partial(_kernel, page_size=page_size,
                               n_pages_grid=max_pages, n_kv_heads=Hkv, g=g,
                               scale=D ** -0.5)
    qg = q.reshape(B, Hkv, g, D)
    bt = jnp.asarray(block_tables, jnp.int32)
    kv_len_arr = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))

    kv_spec = pl.BlockSpec((1, page_size, Hkv, D),     # page j of sequence
                           lambda b, j, bt, kl: (bt[b, j], 0, 0, 0))  # b
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block tables + kv lengths
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, D), lambda b, j, bt, kl: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, D),
                               lambda b, j, bt, kl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, 1), F32),          # running max
            pltpu.VMEM((Hkv, g, 1), F32),          # denominator
            pltpu.VMEM((Hkv, g, D), F32),          # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention_kernel",   # the op's name in a trace
    )(bt, kv_len_arr, qg, k_pages, v_pages)
    return out.reshape(B, H, D)
