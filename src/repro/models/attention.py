"""Attention: GQA/MQA, sliding-window, MLA (DeepSeek-V3), cross-attention.

Three execution paths:
  * ``chunked_attention`` — pure-jnp flash-style attention: a
    ``lax.scan`` over query blocks with fp32 softmax, bounding peak
    activation memory to (block_q x seq) instead of (seq x seq).  It
    serves every platform: prefill chunks, speculative verify, and the
    decode paths the Pallas paged kernel does not take (CPU, sliding
    window).  No model path calls a Pallas flash kernel.
  * ``triangular`` — causal block-skipping variant (perf pass): query
    blocks are unrolled and each attends only keys ``<= block_end``,
    halving attention FLOPs vs the chunked path.
  * decode — one query token against a (possibly ring-buffered) KV cache.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models.flash import flash_attention
from repro.models.pspec import shard

NEG_INF = -1e30


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, d_in: Optional[int] = None) -> dict:
    """Standard GQA attention params. d_in overrides the input width
    (zamba2's shared block consumes concat(hidden, embedding))."""
    dt = L.dtype_of(cfg.param_dtype)
    d = d_in or cfg.d_model
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "w_q": L.dense_init(ks[0], (d, cfg.n_heads * hd), dt),
        "w_k": L.dense_init(ks[1], (d, cfg.n_kv_heads * hd), dt),
        "w_v": L.dense_init(ks[2], (d, cfg.n_kv_heads * hd), dt),
        "w_o": L.dense_init(ks[3], (cfg.n_heads * hd, cfg.d_model), dt),
    }
    if cfg.qkv_bias:
        p["b_q"] = jnp.zeros((cfg.n_heads * hd,), dt)
        p["b_k"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
        p["b_v"] = jnp.zeros((cfg.n_kv_heads * hd,), dt)
    if cfg.attn_out_bias:
        p["b_o"] = jnp.zeros((cfg.d_model,), dt)
    if cfg.qk_norm:
        p["q_norm"] = L.init_rmsnorm(hd, dt)
        p["k_norm"] = L.init_rmsnorm(hd, dt)
    return p


def init_mla(key, cfg: ModelConfig) -> dict:
    """DeepSeek-V3 Multi-head Latent Attention [arXiv:2412.19437]."""
    m = cfg.mla
    dt = L.dtype_of(cfg.param_dtype)
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 6)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": L.dense_init(ks[0], (d, m.q_lora_rank), dt),
        "q_norm": L.init_rmsnorm(m.q_lora_rank, dt),
        "w_uq": L.dense_init(ks[1], (m.q_lora_rank, H * qk_head), dt),
        # down-projection to the compressed latent + the shared rope key
        "w_dkv": L.dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": L.init_rmsnorm(m.kv_lora_rank, dt),
        # up-projections from the latent: k_nope and v per head
        "w_uk": L.dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dt),
        "w_uv": L.dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dt),
        "w_o": L.dense_init(ks[5], (H * m.v_head_dim, d), dt),
    }


# --------------------------------------------------------------------------
# qkv projection helpers
# --------------------------------------------------------------------------

def _project_qkv(p: dict, cfg: ModelConfig, x, xkv=None):
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    xkv = x if xkv is None else xkv
    q = x @ p["w_q"]
    k = xkv @ p["w_k"]
    v = xkv @ p["w_v"]
    if "b_q" in p:
        q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    q = q.reshape(B, -1, cfg.n_heads, hd)
    k = k.reshape(B, -1, cfg.n_kv_heads, hd)
    v = v.reshape(B, -1, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    v = shard(v, "batch", None, "model", None)
    return q, k, v


def out_proj(p: dict, o):
    """The attention output projection, with its bias where the config
    has one (``attn_out_bias``)."""
    out = o @ p["w_o"]
    return out + p["b_o"] if "b_o" in p else out


# --------------------------------------------------------------------------
# chunked (flash-style) attention over full sequences
# --------------------------------------------------------------------------

def _grouped_scores(q, k):
    """q: (B, Sq, Hkv, g, D), k: (B, Skv, Hkv, D) -> (B, Hkv, g, Sq, Skv)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def chunked_attention(q, k, v, *, causal: bool, q_offset=0,
                      window: int = 0, kv_len: Optional[jax.Array] = None,
                      kv_start: Optional[jax.Array] = None,
                      block_q: int = 1024) -> jax.Array:
    """Memory-bounded attention.  q: (B,Sq,H,D); k,v: (B,Skv,Hkv,D).

    q_offset: absolute position of q[0] (prefill continuation / decode).
    window: sliding-window size (0 = full).
    kv_len: optional dynamic number of valid kv positions (decode);
        scalar, or (B,) for per-sequence lengths (continuous batching
        steps slots whose sequences are at different positions).
    kv_start: optional first valid kv position, scalar or (B,) — the
        paged decode path enforces a sliding window by lower bound
        (kv positions there are absolute, not ring-buffered).
    """
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = D ** -0.5
    qg = q.reshape(B, Sq, Hkv, g, D)
    kv_pos = jnp.arange(Skv)

    def block(qb, qpos):
        # qb: (B, bq, Hkv, g, D); qpos: (bq,) absolute positions
        s = _grouped_scores(qb, k) * scale            # (B,Hkv,g,bq,Skv)
        mask = jnp.ones((qb.shape[1], Skv), bool)
        if causal:
            mask &= qpos[:, None] >= kv_pos[None, :]
        if window:
            mask &= (qpos[:, None] - kv_pos[None, :]) < window
        mask = mask[None, None, None]                 # (1,1,1,bq,Skv)
        if kv_len is not None:
            kl = jnp.asarray(kv_len)
            if kl.ndim == 0:
                mask = mask & (kv_pos < kl)
            else:                                     # (B,) ragged lengths
                mask = mask & (kv_pos[None, :] < kl[:, None]
                               )[:, None, None, None]
        if kv_start is not None:
            ks = jnp.asarray(kv_start)
            if ks.ndim == 0:
                mask = mask & (kv_pos >= ks)
            else:                                     # (B,) ragged starts
                mask = mask & (kv_pos[None, :] >= ks[:, None]
                               )[:, None, None, None]
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
        return o.astype(q.dtype)

    if Sq <= block_q:
        out = block(qg, q_offset + jnp.arange(Sq))
    else:
        nb = -(-Sq // block_q)
        pad = nb * block_q - Sq
        qp = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        qp = qp.reshape(B, nb, block_q, Hkv, g, D).transpose(1, 0, 2, 3, 4, 5)
        pos = (q_offset + jnp.arange(nb * block_q)).reshape(nb, block_q)

        def body(_, xs):
            qb, pb = xs
            return None, block(qb, pb)

        _, out = jax.lax.scan(body, None, (qp, pos))
        Dv = out.shape[-1]
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nb * block_q, Hkv, g, Dv)
        out = out[:, :Sq]
    return out.reshape(B, Sq, H, -1)


def triangular_attention(q, k, v, *, window: int = 0) -> jax.Array:
    """Causal attention with static block skipping: query block i only
    computes scores against keys [lo_i, (i+1)*bq) where lo_i honors the
    sliding window.  Unrolled (static shapes per block) — ~2x fewer
    attention FLOPs than ``chunked_attention`` for full causal, more for
    windowed.  Used by the perf pass."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    block_q = min(1024, Sq)
    assert Sq % block_q == 0 and Sq == Skv, "triangular path needs aligned blocks"
    nb = Sq // block_q
    scale = D ** -0.5
    qg = q.reshape(B, Sq, Hkv, g, D)
    outs = []
    for i in range(nb):
        hi = (i + 1) * block_q
        lo = 0
        if window:
            lo = max(0, (i * block_q + 1) - window)
            lo = (lo // block_q) * block_q          # align to blocks
        qb = qg[:, i * block_q:hi]
        kb, vb = k[:, lo:hi], v[:, lo:hi]
        qpos = jnp.arange(i * block_q, hi)
        kpos = jnp.arange(lo, hi)
        s = _grouped_scores(qb, kb) * scale
        mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vb.astype(jnp.float32))
        outs.append(o.astype(q.dtype).reshape(B, block_q, H, -1))
    return jnp.concatenate(outs, axis=1)


# --------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# --------------------------------------------------------------------------

def attention_fwd(p: dict, cfg: ModelConfig, x, positions, *,
                  causal: bool = True, window: int = 0,
                  mode: str = "flash", xkv=None, rope: bool = True,
                  return_kv: bool = False):
    """Full-sequence attention.  Returns (out, (k, v) if return_kv).

    mode="flash" (default): custom-vjp flash attention — O(S.D)
    residuals, static causal block skipping.  mode="naive": the
    reference softmax path (tests / ablation baseline)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, xkv)
    if rope:
        sections = cfg.mrope_sections if cfg.mrope else None
        q = L.apply_rope(q, positions, cfg.rope_theta, sections)
        k = L.apply_rope(k, positions, cfg.rope_theta, sections)
    if mode == "flash":
        o = flash_attention(q, k, v, causal=causal, window=window)
    else:
        o = chunked_attention(q, k, v, causal=causal, window=window)
    o = shard(o, "batch", None, "model", None)
    out = out_proj(p, o.reshape(B, S, -1))
    if return_kv:
        return out, (k, v)
    return out


# --------------------------------------------------------------------------
# single-token decode against a KV cache
# --------------------------------------------------------------------------

def attention_decode(p: dict, cfg: ModelConfig, x, cache_k, cache_v,
                     pos, *, window: int = 0, xkv=None, rope: bool = True,
                     rope_pos=None):
    """x: (B, 1, d).  cache_k/v: (B, S_cache, Hkv, D) where S_cache is
    ``window`` for sliding-window archs (ring buffer) else max_seq.
    pos: scalar int32 — cache slot index (absolute sequence position) —
    or a (B,) vector of per-sequence positions (continuous batching:
    each slot is at its own depth in the sequence).
    rope_pos: rotary position if it differs from the slot index (VLM:
    M-RoPE text positions restart after the patch grid)."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    q, k, v = _project_qkv(p, cfg, x, xkv)
    if rope:
        rp = pos if rope_pos is None else rope_pos
        if per_slot:
            posv = jnp.reshape(rp, (B, 1))
        else:
            posv = jnp.full((B, 1), rp, jnp.int32)
        sections = cfg.mrope_sections if cfg.mrope else None
        if sections is not None:
            posv = jnp.broadcast_to(posv, (3, B, 1))
        q = L.apply_rope(q, posv, cfg.rope_theta, sections)
        k = L.apply_rope(k, posv, cfg.rope_theta, sections)
    S_cache = cache_k.shape[1]
    slot = jnp.where(window > 0, pos % S_cache, pos) if window else pos
    if per_slot:
        upd = jax.vmap(
            lambda ck, cv, kk, vv, s: (
                jax.lax.dynamic_update_slice(ck, kk, (s, 0, 0)),
                jax.lax.dynamic_update_slice(cv, vv, (s, 0, 0))))
        cache_k, cache_v = upd(cache_k, cache_v, k, v, slot)
    else:
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    kv_len = jnp.minimum(pos + 1, S_cache)
    # ring buffers hold an unordered window; softmax is order-invariant
    # so masking by validity is sufficient (rope already encoded order).
    o = chunked_attention(q, cache_k, cache_v, causal=False,
                          kv_len=kv_len)
    out = out_proj(p, o.reshape(B, 1, -1))
    return out, cache_k, cache_v


def _chunk_page_targets(pos_offset, C, n_valid, page_size, block_table):
    """Scatter targets for one prefill chunk: position ``pos_offset + i``
    lands in page ``bt[pos // page_size]`` at offset ``pos % page_size``;
    pad positions (``i >= n_valid`` — chunk shapes are bucketed for jit
    reuse) land on the scratch page 0, which no live sequence reads."""
    pos = pos_offset + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(C) < n_valid
    page = jnp.where(valid, block_table.reshape(-1)[pos // page_size], 0)
    return pos, page, pos % page_size


def paged_prefill_attention(p: dict, cfg: ModelConfig, x, pool_k, pool_v,
                            pos_offset, n_valid, block_tables, *,
                            window: int = 0):
    """One prompt chunk of a single sequence, straight into the paged
    KV pool — the admission path of the unified token-budget step.

    x: (1, C, d) chunk activations (positions ``pos_offset ..
    pos_offset + C``, of which the first ``n_valid`` are real prompt
    tokens and the rest jit-bucketing pads).  pool_k/pool_v:
    (n_pages, page_size, Hkv, D) — the layer's slice of the global
    pool.  block_tables: (1, max_pages) int32 covering at least
    positions [0, pos_offset + n_valid).

    Each position's k/v is scattered into its absolute-position page
    (pads to the scratch page 0), then the chunk's queries attend
    causally over the gathered page set via ``chunked_attention``'s
    ``q_offset``/``kv_len`` masking — numerically the paged decode
    path applied C positions at a time, so no contiguous prefix cache
    (and no graft) ever exists.  The per-position outputs (and hence
    per-position logits upstream) are exact for EVERY chunk position,
    not just the last: speculative draft-verify replays a chunk of
    draft tokens mid-decode and reads all C next-token predictions
    from one pass."""
    B, C, _ = x.shape
    ps = pool_k.shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    pos, page, off = _chunk_page_targets(pos_offset, C, n_valid, ps,
                                         block_tables)
    if cfg.position_kind == "rope":
        posv = jnp.broadcast_to(pos[None], (B, C))
        q = L.apply_rope(q, posv, cfg.rope_theta)
        k = L.apply_rope(k, posv, cfg.rope_theta)
    pool_k = pool_k.at[page, off].set(k[0].astype(pool_k.dtype))
    pool_v = pool_v.at[page, off].set(v[0].astype(pool_v.dtype))
    kg = pool_k[block_tables.reshape(-1)].reshape(1, -1, *pool_k.shape[2:])
    vg = pool_v[block_tables.reshape(-1)].reshape(1, -1, *pool_v.shape[2:])
    kg = shard(kg, "batch", None, "model", None)
    vg = shard(vg, "batch", None, "model", None)
    o = chunked_attention(q, kg, vg, causal=True, q_offset=pos_offset,
                          window=window, kv_len=pos_offset + n_valid)
    out = out_proj(p, o.reshape(B, C, -1))
    return out, pool_k, pool_v


def paged_attention_decode(p: dict, cfg: ModelConfig, x, pool_k, pool_v,
                           pos, block_tables, *, window: int = 0,
                           rope: bool = True, rope_pos=None):
    """Single-token decode against a paged KV pool.

    x: (B, 1, d).  pool_k/pool_v: (n_pages, page_size, Hkv, D) — the
    layer's slice of the global page pool.  pos: (B,) absolute write
    positions.  block_tables: (B, max_pages) int32 — entry j of row b is
    the page holding positions [j*page_size, (j+1)*page_size) of
    sequence b; unused entries point at the scratch page 0.

    The new k/v land in page ``bt[b, pos//page_size]`` at offset
    ``pos % page_size``; attention gathers the table's pages back into
    position order, masked to ``pos+1`` valid positions (and, for
    sliding-window archs, lower-bounded at ``pos+1-window`` — pages here
    hold absolute positions, not a ring buffer).  Freshly allocated
    pages may hold a stale sequence's KV beyond ``pos``; the kv_len mask
    keeps the overwrite-before-read guarantee of the contiguous layout.
    """
    B = x.shape[0]
    ps = pool_k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    q, k, v = _project_qkv(p, cfg, x)
    if rope:
        rp = pos if rope_pos is None else rope_pos
        posv = jnp.reshape(rp, (B, 1))
        sections = cfg.mrope_sections if cfg.mrope else None
        if sections is not None:
            posv = jnp.broadcast_to(posv, (3, B, 1))
        q = L.apply_rope(q, posv, cfg.rope_theta, sections)
        k = L.apply_rope(k, posv, cfg.rope_theta, sections)
    page = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]                   # (B,)
    off = pos % ps
    pool_k = pool_k.at[page, off].set(k[:, 0].astype(pool_k.dtype))
    pool_v = pool_v.at[page, off].set(v[:, 0].astype(pool_v.dtype))
    from repro.kernels import ops              # local: models stay
    # importable without touching the Pallas toolchain at module load
    if window == 0 and ops.paged_kernel_ok():
        # the Pallas kernel streams pages by block-table lookup in the
        # DMA index_map — no contiguous gather is ever materialized;
        # under a serving mesh each device runs it on its own heads
        o = ops.paged_decode_attention(q[:, 0], pool_k, pool_v,
                                       block_tables, pos + 1)[:, None]
    else:
        # CPU lowering / sliding window: gather the tables back into
        # position order and reuse the masked reference attention
        kg = pool_k[block_tables]            # (B, max_pages, ps, Hkv, D)
        vg = pool_v[block_tables]
        kg = shard(kg.reshape(B, -1, *pool_k.shape[2:]),
                   "batch", None, "model", None)
        vg = shard(vg.reshape(B, -1, *pool_v.shape[2:]),
                   "batch", None, "model", None)
        kv_start = jnp.maximum(pos + 1 - window, 0) if window else None
        o = chunked_attention(q, kg, vg, causal=False, kv_len=pos + 1,
                              kv_start=kv_start)
    out = out_proj(p, o.reshape(B, 1, -1))
    return out, pool_k, pool_v


# --------------------------------------------------------------------------
# MLA forward (expanded for train/prefill, absorbed for decode)
# --------------------------------------------------------------------------

def _mla_qkv(p, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ql = L.rmsnorm(p["q_norm"], x @ p["w_dq"], cfg.norm_eps)
    q = (ql @ p["w_uq"]).reshape(B, S, H, qk_head)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    dkv = x @ p["w_dkv"]
    ckv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = L.rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return q_nope, q_rope, ckv, k_rope[:, :, 0, :]


def mla_fwd(p: dict, cfg: ModelConfig, x, positions, *, mode="flash",
            return_cache: bool = False):
    """Expanded MLA for train/prefill: reconstruct per-head k/v."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, cfg, x, positions)
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
        axis=-1)
    q = shard(q, "batch", None, "model", None)
    k = shard(k, "batch", None, "model", None)
    o = (flash_attention(q, k, v, causal=True) if mode == "flash"
         else chunked_attention(q, k, v, causal=True))
    out = o.reshape(B, S, -1) @ p["w_o"]
    if return_cache:
        return out, (ckv, k_rope)
    return out


def mla_decode(p: dict, cfg: ModelConfig, x, cache_ckv, cache_krope, pos):
    """Absorbed MLA decode [arXiv:2412.19437 §2.1.1]: the k up-projection
    is folded into the query and the v up-projection into the output, so
    attention runs directly in the compressed (kv_lora_rank + rope) space
    — the cache stores only (ckv, k_rope) per token."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1                   # (B,) continuous-batching path
    posv = (jnp.reshape(pos, (B, 1)) if per_slot
            else jnp.full((B, 1), pos, jnp.int32))
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, cfg, x, posv)
    # cache update
    if per_slot:
        upd = jax.vmap(lambda c, u, s: jax.lax.dynamic_update_slice(
            c, u, (s, 0)))
        cache_ckv = upd(cache_ckv, ckv, pos)
        cache_krope = upd(cache_krope, k_rope, pos)
    else:
        cache_ckv = jax.lax.dynamic_update_slice(cache_ckv, ckv, (0, pos, 0))
        cache_krope = jax.lax.dynamic_update_slice(cache_krope, k_rope,
                                                   (0, pos, 0))
    kv_pos = jnp.arange(cache_ckv.shape[1])
    if per_slot:
        valid = kv_pos[None, :] <= pos[:, None]          # (B, S)
    else:
        valid = jnp.broadcast_to(kv_pos[None, :] <= pos,
                                 (B, cache_ckv.shape[1]))
    out = _mla_absorbed_attend(p, cfg, q_nope, q_rope, cache_ckv,
                               cache_krope, valid).astype(x.dtype)
    return out @ p["w_o"], cache_ckv, cache_krope


def _mla_absorbed_attend(p, cfg, q_nope, q_rope, ckv_seq, krope_seq, valid):
    """Absorbed MLA attention core.  q_nope/q_rope: (B,Sq,H,*);
    ckv_seq: (B,S,r); krope_seq: (B,S,rope_d); valid: (B,S) bool
    (broadcast over queries) or (B,Sq,S) per-query (the chunked-prefill
    causal mask).  Returns the flattened per-head context
    (B, Sq, H*v_head_dim) in f32 (the caller applies w_o)."""
    m = cfg.mla
    H = cfg.n_heads
    B, Sq = q_nope.shape[:2]
    # absorb w_uk into q: (B,1,H,nope) x (lora,H,nope) -> (B,1,H,lora)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat,
                       ckv_seq.astype(jnp.float32))
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                        krope_seq.astype(jnp.float32))
    s = (s_lat + s_rope) * scale
    mask = (valid[:, None, None, :] if valid.ndim == 2
            else valid[:, None, :, :])
    s = jnp.where(mask, s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqk,bkr->bqhr", prob, ckv_seq.astype(jnp.float32))
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv.astype(jnp.float32))
    return o.reshape(B, Sq, -1)


def mla_paged_prefill(p: dict, cfg: ModelConfig, x, pool_ckv, pool_krope,
                      pos_offset, n_valid, block_tables):
    """One prompt chunk straight into the paged MLA latent cache (see
    ``paged_prefill_attention`` for the chunk/page layout): the chunk's
    (ckv, k_rope) land in their absolute-position pages, pads on the
    scratch page, and attention runs the absorbed decode path with a
    per-query causal mask — C positions at a time, every position's
    output exact (the speculative verify pass reads all of them, not
    just the final chunk position)."""
    B, C, _ = x.shape
    ps = pool_ckv.shape[1]
    pos, page, off = _chunk_page_targets(pos_offset, C, n_valid, ps,
                                         block_tables)
    posv = jnp.broadcast_to(pos[None], (B, C))
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, cfg, x, posv)
    pool_ckv = pool_ckv.at[page, off].set(ckv[0].astype(pool_ckv.dtype))
    pool_krope = pool_krope.at[page, off].set(
        k_rope[0].astype(pool_krope.dtype))
    bt = block_tables.reshape(-1)
    ckv_seq = shard(pool_ckv[bt].reshape(1, -1, pool_ckv.shape[-1]),
                    "batch", None, "model")
    krope_seq = shard(pool_krope[bt].reshape(1, -1, pool_krope.shape[-1]),
                      "batch", None, "model")
    kv_pos = jnp.arange(ckv_seq.shape[1])
    valid = ((kv_pos[None, None, :] <= pos[None, :, None])
             & (kv_pos[None, None, :] < pos_offset + n_valid))
    out = _mla_absorbed_attend(p, cfg, q_nope, q_rope, ckv_seq,
                               krope_seq, valid).astype(x.dtype)
    return out @ p["w_o"], pool_ckv, pool_krope


def mla_paged_decode(p: dict, cfg: ModelConfig, x, pool_ckv, pool_krope,
                     pos, block_tables):
    """Absorbed MLA decode against a paged latent cache.

    pool_ckv: (n_pages, page_size, kv_lora_rank); pool_krope:
    (n_pages, page_size, qk_rope_head_dim).  pos: (B,) absolute write
    positions; block_tables: (B, max_pages) int32 (see
    ``paged_attention_decode`` for the page layout and the
    overwrite-before-read argument)."""
    B = x.shape[0]
    ps = pool_ckv.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    posv = jnp.reshape(pos, (B, 1))
    q_nope, q_rope, ckv, k_rope = _mla_qkv(p, cfg, x, posv)
    page = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]
    off = pos % ps
    pool_ckv = pool_ckv.at[page, off].set(ckv[:, 0].astype(pool_ckv.dtype))
    pool_krope = pool_krope.at[page, off].set(
        k_rope[:, 0].astype(pool_krope.dtype))
    ckv_seq = pool_ckv[block_tables].reshape(B, -1, pool_ckv.shape[-1])
    krope_seq = pool_krope[block_tables].reshape(B, -1, pool_krope.shape[-1])
    kv_pos = jnp.arange(ckv_seq.shape[1])
    valid = kv_pos[None, :] <= pos[:, None]
    out = _mla_absorbed_attend(p, cfg, q_nope, q_rope, ckv_seq,
                               krope_seq, valid).astype(x.dtype)
    return out @ p["w_o"], pool_ckv, pool_krope
