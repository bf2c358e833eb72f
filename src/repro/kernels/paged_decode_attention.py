"""Pallas TPU paged decode-attention kernel: ONE query token per
sequence against a paged KV cache (flash-decoding online softmax over
block-table-indexed pages).

The KV pool is ``(n_pages, page_size, Hkv, D)`` — a sequence's keys live
in the pages named by its block table, page ``j`` holding absolute
positions ``[j*page_size, (j+1)*page_size)``.  The block tables and
per-sequence lengths decide which pages the kv BlockSpecs stream, by a
**scalar-prefetched** table (``pltpu.PrefetchScalarGridSpec``): the
gather happens in the DMA engine, never materializing a contiguous copy
of the sequence.

Grid: (batch, ceil(max_pages / P)) — step ``(b, j)`` covers block ``j``
of row ``b``: table entries ``[j*P, (j+1)*P)``, ``P * page_size``
positions.  Each pool is passed P times, once per page slot of a block;
slot ``i`` streams page ``block_tables[b, j*P + i]`` (all of its KV
heads), and the pipeline double-buffers every slot.  (A manual page DMA
from an un-blocked pool is refused by the TPU compiler: the pool's
``(Hkv, D)`` minor dims are tile-padded in HBM, and a one-page slice of
them is not tile-aligned.  Whole-page blocks are.)

Work stops at each row's ``kv_len``: only its first
``n_live = ceil(kv_len / page_size)`` entries are read (at least one).
A block at or past ``n_live`` — a dead block — skips its compute.  Which
page each slot holds at each step is a table computed before the call
(``_fetch_table``, scalar-prefetched), and a slot whose page does not
change from one step to the next fetches nothing: slots past ``n_live``
keep a page they already hold, and a dead block holds the pages of the
next row's first block, so the pipeline fetches them while this row's
last live block computes.  Table entries past ``n_live`` (the scratch
page 0 in the engine) are never dereferenced.

Masking: positions ``>= kv_len`` get a score of -inf and a value of 0,
so garbage past ``kv_len`` in a row's last page never reaches the
output (``0 * NaN`` is NaN).

Per block and KV head: one ``(g, D) x (D, P*ps)`` score, its softmax
update, and one ``(g, P*ps) x (P*ps, D)`` product, in float32.  The
block axis is minor-most and runs in order, so the online-softmax
scratch (running max / denominator / accumulator, one row per query
head) persists across it; the output is written at the last block index
whether or not that block was live.

P adapts to the shapes (no knob): the largest power of two with
``P * page_size <= 128`` whose double-buffered K and V pages, with the
VMEM tile padding of ``(Hkv, D)``, fit ``_BUFFER_BUDGET``; never more
than ``max_pages``.  A ``max_pages`` that P does not divide leaves a
last block whose missing entries count as dead.

BlockSpec tiling (VMEM).  The TPU lowering needs each block's two
minor dimensions to be multiples of (8, 128) or the array's own, so
every block keeps the array's trailing dimensions whole:
    q:     (1, Hkv, g, D)         — the g = H/Hkv query heads per kv head
    k, v:  (1, page_size, Hkv, D) — one page, every kv head; P of each
    out:   (1, Hkv, g, D)         — written at the last block index
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
_MAX_BLOCK_POSITIONS = 128
_BUFFER_BUDGET = 8 * 1024 * 1024      # K and V pages, both buffers


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pages_per_block(page_size: int, n_kv_heads: int, head_dim: int,
                    dtype, max_pages: int) -> int:
    """Pages per grid step: the most (a power of two) that keep a block
    within ``_MAX_BLOCK_POSITIONS`` positions and the four block
    buffers, tile padding included, within ``_BUFFER_BUDGET``."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)           # 8 rows of f32, 16 of bf16
    page_bytes = (page_size * _round_up(n_kv_heads, sublanes)
                  * _round_up(head_dim, 128) * itemsize)
    p = 1
    while (2 * p * page_size <= _MAX_BLOCK_POSITIONS
           and 4 * 2 * p * page_bytes <= _BUFFER_BUDGET):
        p *= 2
    return max(1, min(p, max_pages))


def _kernel(fetch_ref, len_ref, q_ref, *refs, page_size: int, pages: int,
            n_blocks: int, n_kv_heads: int, g: int, scale: float):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, m_s, l_s, acc_s = refs[2 * pages:]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    kv_len = len_ref[b]
    n_pos = pages * page_size

    @pl.when(j * n_pos < kv_len)
    def _block():
        kpos = j * n_pos + jax.lax.broadcasted_iota(jnp.int32, (g, n_pos), 1)
        s_valid = kpos < kv_len                                  # (g, T)
        vpos = j * n_pos + jax.lax.broadcasted_iota(jnp.int32, (n_pos, 1), 0)
        v_valid = vpos < kv_len                                  # (T, 1)
        # whole pages to float32 once, then each head's rows from them:
        # cheaper than a strided bf16 load per head
        k_f32 = [r[0].astype(F32) for r in k_refs]           # (ps, Hkv, D)
        v_f32 = [r[0].astype(F32) for r in v_refs]
        for h in range(n_kv_heads):
            q = q_ref[0, h].astype(F32) * scale                  # (g, D)
            k = jnp.concatenate([x[:, h, :] for x in k_f32])     # (T, D)
            v = jnp.concatenate([x[:, h, :] for x in v_f32])
            v = jnp.where(v_valid, v, 0.0)                       # (T, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
            s = jnp.where(s_valid, s, NEG_INF)                   # (g, T)

            m_prev = m_s[h]                                      # (g, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[h] = (acc_s[h] * corr
                        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
            m_s[h] = m_new

    @pl.when(j == n_blocks - 1)
    def _finish():
        l_safe = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l_safe).astype(o_ref.dtype)


def _fetch_table(block_tables, kv_len, page_size: int, pages: int,
                 n_blocks: int):
    """(B * n_blocks * pages,) int32: the pool page that page slot ``i``
    holds at grid step ``(b, j)``, at ``(b * n_blocks + j) * pages + i``.

    A live block names its live pages; a slot past the row's last live
    page keeps the page it named in the block before (in block 0, the
    last live page).  A dead block names what the next row's block 0
    names (the last row's: what its last live block names), so the
    pipeline fetches those pages while this row's last live block
    computes.  A slot whose page equals the step before fetches nothing."""
    B, max_pages = block_tables.shape
    n = jnp.clip((kv_len + page_size - 1) // page_size, 1, max_pages)
    n = n[:, None, None]
    j = jnp.arange(n_blocks)[None, :, None]
    blk = jnp.minimum(j, (n - 1) // pages)       # dead: the last live block
    e = blk * pages + jnp.arange(pages)[None, None, :]
    e = jnp.where(e < n, e, jnp.where(blk > 0, e - pages, n - 1))
    live = jnp.take_along_axis(block_tables, e.reshape(B, -1), axis=1)
    live = live.reshape(B, n_blocks, pages)
    nxt = jnp.concatenate(
        [jnp.broadcast_to(live[1:, :1], (B - 1, n_blocks, pages)), live[-1:]])
    return jnp.where(j * pages < n, live, nxt).reshape(-1)


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
    pages = _pages_per_block(page_size, Hkv, D, k_pages.dtype, max_pages)
    n_blocks = -(-max_pages // pages)

    kernel = functools.partial(_kernel, page_size=page_size, pages=pages,
                               n_blocks=n_blocks, n_kv_heads=Hkv, g=g,
                               scale=D ** -0.5)
    qg = q.reshape(B, Hkv, g, D)
    bt = jnp.asarray(block_tables, jnp.int32)
    kv_len_arr = jnp.broadcast_to(
        jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))

    fetch = _fetch_table(bt, kv_len_arr, page_size, pages, n_blocks)

    def page_spec(i):
        return pl.BlockSpec(
            (1, page_size, Hkv, D),
            lambda b, j, fetch, kl: (fetch[(b * n_blocks + j) * pages + i],
                                     0, 0, 0))

    page_specs = [page_spec(i) for i in range(pages)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # page fetches + kv lengths
        grid=(B, n_blocks),
        in_specs=[pl.BlockSpec((1, Hkv, g, D),
                               lambda b, j, fetch, kl: (b, 0, 0, 0)),
                  *page_specs,            # K: one pool operand per slot
                  *page_specs],           # V
        out_specs=pl.BlockSpec((1, Hkv, g, D),
                               lambda b, j, fetch, kl: (b, 0, 0, 0)),
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
        # steps run in order: a dead block holds the next row's pages
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention_kernel",   # the op's name in a trace
    )(fetch, kv_len_arr, qg, *[k_pages] * pages, *[v_pages] * pages)
    return out.reshape(B, H, D)
