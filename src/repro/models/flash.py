"""Flash attention in pure JAX with a flash backward (custom_vjp).

The naive softmax(QK^T)V under autodiff saves the O(S^2) probability
tensor as a residual — at 32k context that is gigabytes per layer per
device and dominates both HBM traffic and live memory (it would not fit
the 16 GB v5e target at all).  This module implements the
FlashAttention-2 scheme in plain jnp:

  * forward: python-unrolled query blocks; per block a lax.scan over key
    blocks with online softmax.  Causal block skipping is STATIC (query
    block i only visits key blocks <= i), so causal attention costs
    ~S^2/2 + diagonal, not S^2.
  * residuals: (q, k, v, out, lse) — O(S*D), no probability tensor.
  * backward: one lax.scan over key blocks with an inner scan over query
    blocks, recomputing probabilities from the stored LSE.  dQ
    accumulates via dynamic-update-slice-add into the outer carry.

This is the lowering on every platform, the TPU included.
``repro.kernels.flash_attention`` is a Pallas twin of the forward pass
that only the kernel tests call; the v5e compiler refuses its block
shapes as written.

Layout: grouped GQA — q: (B, Sq, Hkv, g, D); k/v: (B, Skv, Hkv, D).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
NEG_INF = -1e30


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_seq(x, target: int):
    if x.shape[1] == target:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, target - x.shape[1])
    return jnp.pad(x, pad)


def _block_mask(qpos, kpos, causal, window, kv_limit):
    m = (kpos[None, :] < kv_limit)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m


# ==========================================================================
# forward
# ==========================================================================

def _fwd_qblock(cfg, qb, k, v, i, Skv_real, kv_len):
    """One query block against its (statically skipped) key range."""
    causal, q_offset, window, bq, bk = cfg
    B, bq_, Hkv, g, D = qb.shape
    Skv_pad = k.shape[1]
    q_lo = q_offset + i * bq
    q_hi = q_offset + (i + 1) * bq
    hi = min(Skv_pad, _ceil_to(min(q_hi, Skv_real) if causal else Skv_real, bk))
    lo = 0
    if window:
        lo = max(0, (q_lo + 1 - window) // bk * bk)
    hi = max(hi, lo + bk)
    nkb = (hi - lo) // bk

    kseg = jax.lax.slice_in_dim(k, lo, hi, axis=1)
    vseg = jax.lax.slice_in_dim(v, lo, hi, axis=1)
    kb = kseg.reshape(B, nkb, bk, Hkv, D).swapaxes(0, 1)
    vb = vseg.reshape(B, nkb, bk, Hkv, vseg.shape[-1]).swapaxes(0, 1)
    qpos = q_lo + jnp.arange(bq)
    scale = D ** -0.5
    qf = qb.astype(F32) * scale
    kv_limit = jnp.minimum(kv_len, Skv_real)

    def body(carry, inp):
        acc, m, l = carry
        kbj, vbj, j = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kbj.astype(F32))
        kpos = lo + j * bk + jnp.arange(bk)
        msk = _block_mask(qpos, kpos, causal, window, kv_limit)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bhgqk,bkhd->bhgqd", p, vbj.astype(F32)))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Hkv, g, bq, v.shape[-1]), F32)
    m0 = jnp.full((B, Hkv, g, bq), NEG_INF, F32)
    l0 = jnp.zeros((B, Hkv, g, bq), F32)
    (acc, m, l), _ = jax.lax.scan(body, (acc0, m0, l0),
                                  (kb, vb, jnp.arange(nkb)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4)  # (B,bq,Hkv,g,Dv)
    lse = m + jnp.log(l_safe)                                  # (B,Hkv,g,bq)
    return out, lse


def _flash_fwd_impl(cfg, q, k, v, kv_len):
    causal, q_offset, window, bq, bk = cfg
    B, Sq, Hkv, g, D = q.shape
    Skv_real = k.shape[1]
    Skv_pad = _ceil_to(Skv_real, bk)
    k = _pad_seq(k, Skv_pad)
    v = _pad_seq(v, Skv_pad)
    Sq_pad = _ceil_to(Sq, bq)
    qp = _pad_seq(q, Sq_pad)
    outs, lses = [], []
    for i in range(Sq_pad // bq):
        ob, lseb = _fwd_qblock(cfg, qp[:, i * bq:(i + 1) * bq], k, v, i,
                               Skv_real, kv_len)
        outs.append(ob)
        lses.append(lseb)
    out = jnp.concatenate(outs, axis=1)[:, :Sq]
    lse = jnp.concatenate(lses, axis=-1)[..., :Sq]             # (B,Hkv,g,Sq)
    return out.astype(q.dtype), lse


# ==========================================================================
# backward
# ==========================================================================

def _flash_bwd_impl(cfg, q, k, v, out, lse, dout):
    causal, q_offset, window, bq, bk = cfg
    B, Sq, Hkv, g, D = q.shape
    Dv = v.shape[-1]
    Skv_real = k.shape[1]
    Skv_pad = _ceil_to(Skv_real, bk)
    Sq_pad = _ceil_to(Sq, bq)
    kp = _pad_seq(k, Skv_pad).astype(F32)
    vp = _pad_seq(v, Skv_pad).astype(F32)
    scale = D ** -0.5
    qp = _pad_seq(q, Sq_pad).astype(F32) * scale
    dop = _pad_seq(dout, Sq_pad).astype(F32)
    lsep = jnp.pad(lse, [(0, 0)] * 3 + [(0, Sq_pad - Sq)],
                   constant_values=0.0)
    # delta_i = rowsum(dO_i * O_i)
    delta = jnp.sum(dop * _pad_seq(out, Sq_pad).astype(F32), axis=-1)
    delta = delta.transpose(0, 2, 3, 1)                        # (B,Hkv,g,Sq)

    nqb = Sq_pad // bq
    nkb = Skv_pad // bk
    qb = qp.reshape(B, nqb, bq, Hkv, g, D).swapaxes(0, 1)
    dob = dop.reshape(B, nqb, bq, Hkv, g, Dv).swapaxes(0, 1)
    lseb = lsep.reshape(B, Hkv, g, nqb, bq).transpose(3, 0, 1, 2, 4)
    deltab = delta.reshape(B, Hkv, g, nqb, bq).transpose(3, 0, 1, 2, 4)
    kb = kp.reshape(B, nkb, bk, Hkv, D).swapaxes(0, 1)
    vb = vp.reshape(B, nkb, bk, Hkv, Dv).swapaxes(0, 1)

    def kv_block(dq_acc, inp):
        kbj, vbj, j = inp
        kpos = j * bk + jnp.arange(bk)

        def q_block(carry, qinp):
            dkj, dvj, dq_acc = carry
            qbi, dobi, lsei, deli, i = qinp
            qpos = q_offset + i * bq + jnp.arange(bq)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qbi, kbj)
            msk = _block_mask(qpos, kpos, causal, window, Skv_real)
            p = jnp.where(msk[None, None, None],
                          jnp.exp(s - lsei[..., None]), 0.0)
            dvj = dvj + jnp.einsum("bhgqk,bqhgd->bkhd", p, dobi)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", dobi, vbj)
            ds = p * (dp - deli[..., None])
            # qbi carries the softmax scale, so ds^T.qbi == ds^T.q * scale
            dkj = dkj + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qbi)
            dqi = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kbj) * scale
            dq_acc = jax.lax.dynamic_update_slice_in_dim(
                dq_acc,
                jax.lax.dynamic_slice_in_dim(dq_acc, i * bq, bq, 1) + dqi,
                i * bq, axis=1)
            return (dkj, dvj, dq_acc), None

        dk0 = jnp.zeros((B, bk, Hkv, D), F32)
        dv0 = jnp.zeros((B, bk, Hkv, Dv), F32)
        (dkj, dvj, dq_acc), _ = jax.lax.scan(
            q_block, (dk0, dv0, dq_acc),
            (qb, dob, lseb, deltab, jnp.arange(nqb)))
        return dq_acc, (dkj, dvj)

    dq0 = jnp.zeros((B, Sq_pad, Hkv, g, D), F32)
    dq, (dks, dvs) = jax.lax.scan(kv_block, dq0, (kb, vb, jnp.arange(nkb)))
    dk = dks.swapaxes(0, 1).reshape(B, Skv_pad, Hkv, D)[:, :Skv_real]
    dv = dvs.swapaxes(0, 1).reshape(B, Skv_pad, Hkv, Dv)[:, :Skv_real]
    dq = dq[:, :Sq]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


# ==========================================================================
# public API
# ==========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg, q, k, v):
    out, _ = _flash_fwd_impl(cfg, q, k, v, jnp.int32(k.shape[1]))
    return out


def _flash_fwd_rule(cfg, q, k, v):
    out, lse = _flash_fwd_impl(cfg, q, k, v, jnp.int32(k.shape[1]))
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(cfg, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_impl(cfg, q, k, v, out, lse, dout)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    window: int = 0, kv_len: Optional[jax.Array] = None,
                    block_q: int = 1024, block_k: int = 1024) -> jax.Array:
    """Grouped-GQA flash attention.

    q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D).  When ``kv_len`` is given
    (decode against a partially filled cache) the non-vjp path is used —
    no gradients flow through serving.
    """
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    bq = min(block_q, _ceil_to(Sq, 128))
    bk = min(block_k, _ceil_to(k.shape[1], 128))
    cfg = (causal, q_offset, window, bq, bk)
    if kv_len is None:
        out = _flash(cfg, qg, k, v)
    else:
        out, _ = _flash_fwd_impl(cfg, qg, k, v, kv_len)
    return out.reshape(B, Sq, H, -1)
