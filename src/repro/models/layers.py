"""Common layers: norms, MLPs, embeddings, rotary embeddings (RoPE/M-RoPE).

Everything is functional: ``init_*`` returns a param dict, ``*_fwd``
applies it.  Norm/softmax math runs in fp32; matmuls in the activation
dtype (bf16 by default).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.pspec import shard


def dtype_of(name: str):
    return jnp.dtype(name)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in init (what llama-family models use)."""
    fan_in = shape[0] if len(shape) > 1 else shape[-1]
    std = scale / (fan_in ** 0.5)
    return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def norm(params: dict, x: jax.Array, eps: float) -> jax.Array:
    return layernorm(params, x, eps) if "bias" in params else rmsnorm(params, x, eps)


def init_norm(d: int, dtype, use_layernorm: bool = False) -> dict:
    return init_layernorm(d, dtype) if use_layernorm else init_rmsnorm(d, dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    g = x @ params["w_gate"]
    u = x @ params["w_up"]
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard(h, "batch", None, "model") if h.ndim == 3 else h
    return h @ params["w_down"]


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    """Bias'd GELU MLP (whisper / GPT-style)."""
    k1, k2 = jax.random.split(key)
    return {
        "w_up": dense_init(k1, (d_model, d_ff), dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": dense_init(k2, (d_ff, d_model), dtype),
        "b_down": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params: dict, x: jax.Array,
             approximate: bool = True) -> jax.Array:
    """``approximate``: the tanh form of GELU; False, the exact erf form."""
    h = x @ params["w_up"] + params["b_up"]
    h = jax.nn.gelu(h.astype(jnp.float32),
                    approximate=approximate).astype(x.dtype)
    h = shard(h, "batch", None, "model") if h.ndim == 3 else h
    return h @ params["w_down"] + params["b_down"]


# --------------------------------------------------------------------------
# rotary embeddings
# --------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[tuple] = None) -> jax.Array:
    """Rotate ``x`` of shape (..., seq, heads, head_dim).

    positions: (batch, seq) int32 — or (3, batch, seq) for M-RoPE, where
    the leading axis is the (temporal, height, width) position triple
    [arXiv:2409.12191].  ``mrope_sections`` gives the split of the
    head_dim/2 frequency slots across the three position streams.
    """
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)                 # (hd/2,)
    if positions.ndim == 3:                                    # M-RoPE
        assert mrope_sections is not None
        t, h, w = positions.astype(jnp.float32)
        ang_t = t[..., None] * freqs                           # (b, s, hd/2)
        ang_h = h[..., None] * freqs
        ang_w = w[..., None] * freqs
        st, sh, sw = mrope_sections
        assert st + sh + sw == head_dim // 2, (mrope_sections, head_dim)
        angles = jnp.concatenate(
            [ang_t[..., :st], ang_h[..., st:st + sh], ang_w[..., st + sh:]],
            axis=-1)
    else:
        angles = positions.astype(jnp.float32)[..., None] * freqs  # (b, s, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (b, s, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, d_model: int) -> jax.Array:
    """Whisper-style sinusoidal position table (n_pos, d_model)."""
    pos = jnp.arange(n_pos, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    inv = jnp.exp(-dim * (jnp.log(10000.0) / max(d_model // 2 - 1, 1)))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# embedding / unembedding
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype) -> jax.Array:
    return embed_init(key, (vocab, d_model), dtype)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    out = jnp.take(table, tokens, axis=0)
    return shard(out, "batch", None, None)


def unembed(table_or_head: jax.Array, x: jax.Array, transpose: bool) -> jax.Array:
    """Project hidden states to vocab logits (fp32 for a stable softmax)."""
    w = table_or_head.astype(jnp.bfloat16)
    logits = jnp.einsum("bsd,vd->bsv" if transpose else "bsd,dv->bsv", x, w)
    return shard(logits.astype(jnp.float32), "batch", None, "model")
