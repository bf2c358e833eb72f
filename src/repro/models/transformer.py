"""Model assembly for every assigned architecture family.

One functional API across dense / moe / hybrid / ssm / audio / vlm:

    params            = init_params(key, cfg, max_seq)
    logits, aux       = forward(params, cfg, batch)            # train/prefill
    logits, aux, cache= forward(..., return_cache=True)        # prefill
    cache             = init_cache(cfg, batch, max_seq)
    logits, cache     = decode_step(params, cfg, cache, tok, pos)

Layers are **scanned** (stacked params) to keep compile time and HLO size
tractable at 48–88 layers; heterogeneous archs scan over repeat units
(zamba2: 6 mamba + 1 shared attn; xlstm: 7 mLSTM + 1 sLSTM).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X
from repro.models.pspec import shard

F32 = jnp.float32


# ==========================================================================
# per-block init / fwd / decode
# ==========================================================================

def _init_attn_block(key, cfg: ModelConfig, use_moe: bool,
                     dense_ff: Optional[int] = None, gelu: bool = False,
                     cross: bool = False, d_in: Optional[int] = None) -> dict:
    dt = L.dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    ln = _layernorm(cfg)
    p = {"ln1": L.init_norm(d_in or cfg.d_model, dt, ln)}
    if cfg.mla is not None:
        p["attn"] = A.init_mla(ks[0], cfg)
    else:
        p["attn"] = A.init_attention(ks[0], cfg, d_in=d_in)
    if cross:
        p["ln_x"] = L.init_norm(cfg.d_model, dt, ln)
        p["xattn"] = A.init_attention(ks[3], cfg)
    p["ln2"] = L.init_norm(cfg.d_model, dt, ln)
    gelu = gelu or cfg.mlp_type == "gelu"
    if use_moe:
        p["moe"] = M.init_moe(ks[1], cfg)
    elif gelu:
        p["mlp"] = L.init_gelu_mlp(ks[2], cfg.d_model, dense_ff or cfg.d_ff, dt)
    else:
        p["mlp"] = L.init_swiglu(ks[2], cfg.d_model, dense_ff or cfg.d_ff, dt)
    return p


def _attn_block_fwd(p, cfg, x, positions, *, causal=True, window=0,
                    mode="flash", moe_dispatch="einsum", moe_drop_free=False,
                    moe_capacity=None, rope=True, enc_out=None,
                    return_kv=False, x_extra=None):
    """Pre-norm residual block.  Returns (x, aux, kv or None)."""
    eps = cfg.norm_eps
    rope = rope and cfg.position_kind == "rope"
    if x_extra is not None:                    # zamba2 shared block: concat
        h_in = L.norm(p["ln1"], jnp.concatenate([x, x_extra], axis=-1), eps)
    else:
        h_in = L.norm(p["ln1"], x, eps)
    kv = None
    if cfg.mla is not None:
        if return_kv:
            a, kv = A.mla_fwd(p["attn"], cfg, h_in, positions, mode=mode,
                              return_cache=True)
        else:
            a = A.mla_fwd(p["attn"], cfg, h_in, positions, mode=mode)
    else:
        r = A.attention_fwd(p["attn"], cfg, h_in, positions, causal=causal,
                            window=window, mode=mode, rope=rope,
                            return_kv=return_kv)
        a, kv = r if return_kv else (r, None)
    x = x + a
    if enc_out is not None:                    # whisper cross-attention
        cx = A.attention_fwd(p["xattn"], cfg, L.norm(p["ln_x"], x, eps),
                             positions, causal=False, rope=False,
                             xkv=enc_out, return_kv=return_kv)
        ca, ckv = cx if return_kv else (cx, None)
        x = x + ca
        kv = (kv, ckv) if return_kv else None
    aux = jnp.zeros((), F32)
    h = L.norm(p["ln2"], x, eps)
    if "moe" in p:
        y, aux = M.moe_fwd(p["moe"], cfg, h, dispatch=moe_dispatch,
                           drop_free=moe_drop_free, capacity=moe_capacity)
    elif "b_up" in p.get("mlp", {}):
        y = L.gelu_mlp(p["mlp"], h, cfg.gelu_approximate)
    else:
        y = L.swiglu(p["mlp"], h)
    return x + y, aux, kv


def _attn_block_decode(p, cfg, x, cache, pos, *, window=0, x_extra=None,
                       rope=True, rope_pos=None, block_tables=None):
    """Decode step for an attention block.  cache: dict with k/v or MLA
    leaves — per-slot rows when ``block_tables`` is None, else the
    layer's slice of the paged pool, indexed through the tables."""
    eps = cfg.norm_eps
    rope = rope and cfg.position_kind == "rope"
    if x_extra is not None:
        h_in = L.norm(p["ln1"], jnp.concatenate([x, x_extra], axis=-1), eps)
    else:
        h_in = L.norm(p["ln1"], x, eps)
    if cfg.mla is not None:
        if block_tables is not None:
            a, ckv, krope = A.mla_paged_decode(p["attn"], cfg, h_in,
                                               cache["ckv"], cache["krope"],
                                               pos, block_tables)
        else:
            a, ckv, krope = A.mla_decode(p["attn"], cfg, h_in,
                                         cache["ckv"], cache["krope"], pos)
        new_cache = {"ckv": ckv, "krope": krope}
    else:
        if block_tables is not None:
            a, k, v = A.paged_attention_decode(p["attn"], cfg, h_in,
                                               cache["k"], cache["v"], pos,
                                               block_tables, window=window,
                                               rope=rope, rope_pos=rope_pos)
        else:
            a, k, v = A.attention_decode(p["attn"], cfg, h_in,
                                         cache["k"], cache["v"], pos,
                                         window=window, rope=rope,
                                         rope_pos=rope_pos)
        new_cache = {"k": k, "v": v}
    x = x + a
    if "xattn" in p:                           # whisper: static cross cache
        q = L.norm(p["ln_x"], x, eps)
        ca = _cross_decode(p["xattn"], cfg, q, cache["xk"], cache["xv"])
        x = x + ca
        new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]
    h = L.norm(p["ln2"], x, eps)
    if "moe" in p:
        # decode is a serving path: never drop tokens (determinism)
        y, _ = M.moe_fwd(p["moe"], cfg, h, dispatch="einsum",
                         drop_free=True)
    elif "b_up" in p.get("mlp", {}):
        y = L.gelu_mlp(p["mlp"], h, cfg.gelu_approximate)
    else:
        y = L.swiglu(p["mlp"], h)
    return x + y, new_cache


def _cross_decode(p, cfg, q_in, xk, xv):
    """Cross-attention decode: static precomputed K/V (B,F,H,D)."""
    B = q_in.shape[0]
    hd = cfg.resolved_head_dim
    q = (q_in @ p["w_q"])
    if "b_q" in p:
        q = q + p["b_q"]
    q = q.reshape(B, 1, cfg.n_heads, hd)
    o = A.chunked_attention(q, xk, xv, causal=False)
    return A.out_proj(p, o.reshape(B, 1, -1))


# ==========================================================================
# stack descriptions
# ==========================================================================

def _stack_layout(cfg: ModelConfig):
    """Describe the scanned stacks for this config."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return [("attn", cfg.n_layers, {})]
    if fam == "moe":
        m = cfg.moe
        out = []
        if m.n_dense_layers:
            out.append(("attn_dense_ff", m.n_dense_layers, {}))
        out.append(("attn_moe", cfg.n_layers - m.n_dense_layers, {}))
        return out
    if fam == "hybrid":
        k = cfg.shared_attn_every
        units, tail = divmod(cfg.n_layers, k)
        return [("zamba_units", units, {"per_unit": k}),
                ("mamba_tail", tail, {})]
    if fam == "ssm":
        k = cfg.xlstm.slstm_every
        assert cfg.n_layers % k == 0
        return [("xlstm_units", cfg.n_layers // k, {"per_unit": k - 1})]
    if fam == "audio":
        return [("enc", cfg.n_encoder_layers, {}), ("dec", cfg.n_layers, {})]
    raise ValueError(fam)


def _stacked_init(key, n: int, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


def _layernorm(cfg: ModelConfig) -> bool:
    """LayerNorm with weight and bias (whisper, GPT-BigCode), else RMSNorm."""
    return cfg.norm_kind == "layernorm"


def _add_positions(params, x, positions):
    """Learned absolute positions: add row ``positions[b, s]`` of the
    ``(n_positions, d_model)`` table to ``x[b, s]``.  A position past the
    table reads its last row (only chunk pads reach one)."""
    tab = params["pos_embed"]
    rows = jnp.take(tab, jnp.minimum(positions, tab.shape[0] - 1), axis=0)
    return x + rows.astype(x.dtype)


# ==========================================================================
# init
# ==========================================================================

def init_params(key, cfg: ModelConfig, max_seq: int = 4096) -> dict:
    dt = L.dtype_of(cfg.param_dtype)
    keys = jax.random.split(key, 16)
    p: dict = {
        "embed": L.init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": L.init_norm(cfg.d_model, dt, _layernorm(cfg)),
    }
    if cfg.position_kind == "learned":
        p["pos_embed"] = L.embed_init(keys[6], (cfg.n_positions, cfg.d_model),
                                      dt)
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(keys[1], (cfg.d_model, cfg.vocab_size), dt)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["blocks"] = _stacked_init(
            keys[2], cfg.n_layers, lambda k: _init_attn_block(k, cfg, False))
    elif fam == "moe":
        m = cfg.moe
        if m.n_dense_layers:
            p["blocks_dense"] = _stacked_init(
                keys[2], m.n_dense_layers,
                lambda k: _init_attn_block(k, cfg, False, dense_ff=m.dense_d_ff))
        p["blocks_moe"] = _stacked_init(
            keys[3], cfg.n_layers - m.n_dense_layers,
            lambda k: _init_attn_block(k, cfg, True))
    elif fam == "hybrid":
        k_every = cfg.shared_attn_every
        units, tail = divmod(cfg.n_layers, k_every)
        p["mamba_units"] = jax.vmap(
            lambda ku: _stacked_init(ku, k_every,
                                     lambda kk: S.init_mamba2(kk, cfg))
        )(jax.random.split(keys[2], units))
        if tail:
            p["mamba_tail"] = _stacked_init(
                keys[3], tail, lambda kk: S.init_mamba2(kk, cfg))
        # single weight-shared attention block over concat(h, emb) -> 2d
        p["shared_attn"] = _init_attn_block(keys[4], cfg, False,
                                            d_in=2 * cfg.d_model)
        # per-application output adapters (Zamba2-style per-depth LoRA,
        # realized as full d->d linears here)
        p["shared_adapters"] = L.dense_init(
            keys[5], (units, cfg.d_model, cfg.d_model), dt, scale=0.1)
    elif fam == "ssm":
        k_every = cfg.xlstm.slstm_every
        units = cfg.n_layers // k_every
        p["mlstm_units"] = jax.vmap(
            lambda ku: _stacked_init(ku, k_every - 1,
                                     lambda kk: X.init_mlstm_block(kk, cfg))
        )(jax.random.split(keys[2], units))
        p["slstm_units"] = _stacked_init(
            keys[3], units, lambda kk: X.init_slstm_block(kk, cfg))
    elif fam == "audio":
        p["enc_blocks"] = _stacked_init(
            keys[2], cfg.n_encoder_layers,
            lambda k: _init_attn_block(k, cfg, False, gelu=True))
        p["dec_blocks"] = _stacked_init(
            keys[3], cfg.n_layers,
            lambda k: _init_attn_block(k, cfg, False, gelu=True, cross=True))
        p["enc_ln"] = L.init_layernorm(cfg.d_model, dt)
        p["dec_pos"] = L.embed_init(keys[4], (max_seq, cfg.d_model), dt)
    if cfg.use_mtp:
        # DeepSeek-V3 MTP module [arXiv:2412.19437 §2.2]: combine the
        # trunk's hidden state with the NEXT token's embedding, run one
        # extra transformer block, share the unembedding.
        mk = jax.random.split(keys[15], 2)
        p["mtp"] = {
            "norm_h": L.init_rmsnorm(cfg.d_model, dt),
            "norm_e": L.init_rmsnorm(cfg.d_model, dt),
            "proj": L.dense_init(mk[0], (2 * cfg.d_model, cfg.d_model), dt),
            "block": _init_attn_block(
                mk[1], cfg, False,
                dense_ff=(cfg.moe.dense_d_ff if cfg.moe
                          and cfg.moe.dense_d_ff else cfg.d_ff)),
            "final_norm": L.init_rmsnorm(cfg.d_model, dt),
        }
    return p


# ==========================================================================
# position helpers
# ==========================================================================

def mrope_positions(cfg: ModelConfig, B: int, n_patches: int, s_text: int,
                    offset: int = 0):
    """Qwen2-VL M-RoPE position triples (3, B, S) for [patches | text]."""
    grid = int(n_patches ** 0.5) or 1
    pi = jnp.arange(n_patches)
    pt = jnp.zeros((n_patches,), jnp.int32)
    ph = (pi // grid).astype(jnp.int32)
    pw = (pi % grid).astype(jnp.int32)
    t0 = grid  # text starts after the max spatial position
    ti = t0 + jnp.arange(s_text, dtype=jnp.int32)
    pos = jnp.stack([
        jnp.concatenate([pt, ti]),
        jnp.concatenate([ph, ti]),
        jnp.concatenate([pw, ti]),
    ])                                            # (3, S)
    return jnp.broadcast_to(pos[:, None, :] + offset, (3, B, pos.shape[-1]))


# ==========================================================================
# forward (train / prefill)
# ==========================================================================

def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            mode: str = "flash", moe_dispatch: str = "einsum",
            moe_drop_free: bool = False, moe_capacity=None, window: int = 0,
            return_cache: bool = False, return_hidden: bool = False,
            remat: bool = True):
    """Returns (logits, aux_loss[, cache][, hidden]).

    moe_drop_free: route MoE tokens with drop-free expert capacity —
    REQUIRED on serving forwards (prefill, reference logits compared
    against decode) so results are batch-composition independent; leave
    False for training (capacity-bounded GShard throughput).
    moe_capacity: optional static per-batch expert-capacity bound for
    drop-free serving prefill — when set, aux_loss reports the number of
    OVERFLOWED routings instead of the balance loss (0.0 == the result
    is token-exact with the unbounded drop-free path; the engines retry
    with a larger bound otherwise).  See ``moe.moe_fwd``."""
    window = window or cfg.sliding_window
    fam = cfg.family
    if fam == "audio":
        return _whisper_forward(params, cfg, batch, mode=mode,
                                return_cache=return_cache, remat=remat)

    tokens = batch["tokens"]
    B, S_text = tokens.shape
    x = L.embed(params["embed"], tokens)
    if fam == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)         # (B, P, d)
        x = jnp.concatenate([pe, x], axis=1)
        positions = mrope_positions(cfg, B, pe.shape[1], S_text)
    else:
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
        if cfg.position_kind == "learned":
            x = _add_positions(params, x, positions)
    x = shard(x, "batch", None, None)
    aux_total = jnp.zeros((), F32)
    cache = {}

    def run_stack(x, aux_total, stack_params, block_fn):
        def body(carry, lp):
            xc, aux = carry
            xn, a, kv = block_fn(xc, lp)
            return (xn, aux + a), kv
        body = jax.checkpoint(body) if remat else body
        (x, aux_total), kvs = jax.lax.scan(body, (x, aux_total), stack_params)
        return x, aux_total, kvs

    if fam in ("dense", "vlm"):
        fn = lambda xc, lp: _attn_block_fwd(
            lp, cfg, xc, positions, window=window, mode=mode,
            return_kv=return_cache)
        x, aux_total, kvs = run_stack(x, aux_total, params["blocks"], fn)
        if return_cache:
            cache["blocks"] = {"k": kvs[0], "v": kvs[1]}
    elif fam == "moe":
        if "blocks_dense" in params:
            fn = lambda xc, lp: _attn_block_fwd(
                lp, cfg, xc, positions, window=window, mode=mode,
                return_kv=return_cache)
            x, aux_total, kvs = run_stack(x, aux_total,
                                          params["blocks_dense"], fn)
            if return_cache:
                cache["blocks_dense"] = _kv_cache_entry(cfg, kvs)
        fn = lambda xc, lp: _attn_block_fwd(
            lp, cfg, xc, positions, window=window, mode=mode,
            moe_dispatch=moe_dispatch, moe_drop_free=moe_drop_free,
            moe_capacity=moe_capacity, return_kv=return_cache)
        x, aux_total, kvs = run_stack(x, aux_total, params["blocks_moe"], fn)
        if return_cache:
            cache["blocks_moe"] = _kv_cache_entry(cfg, kvs)
    elif fam == "hybrid":
        x, aux_total, cache = _zamba_forward(
            params, cfg, x, positions, aux_total, mode=mode, window=window,
            return_cache=return_cache, remat=remat)
    elif fam == "ssm":
        x, cache = _xlstm_forward(params, cfg, x, return_cache=return_cache,
                                  remat=remat)
    else:
        raise ValueError(fam)

    hidden = x
    x = L.norm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    if return_cache and return_hidden:
        return logits, aux_total, cache, hidden
    if return_cache:
        return logits, aux_total, cache
    if return_hidden:
        return logits, aux_total, hidden
    return logits, aux_total


def mtp_logits(params: dict, cfg: ModelConfig, hidden, tokens, *,
               mode: str = "flash"):
    """MTP head: h'_t = proj([norm(h_t); norm(emb(tok_{t+1}))]) for
    t in [0, S-2), one extra block, shared unembedding -> predicts
    tok_{t+2}.  Returns logits (B, S-2, V)."""
    p = params["mtp"]
    B, S = tokens.shape
    h = L.rmsnorm(p["norm_h"], hidden[:, :S - 2], cfg.norm_eps)
    e = L.rmsnorm(p["norm_e"],
                  L.embed(params["embed"], tokens[:, 1:S - 1]), cfg.norm_eps)
    x = jnp.concatenate([h, e], axis=-1) @ p["proj"]
    positions = jnp.broadcast_to(jnp.arange(S - 2)[None], (B, S - 2))
    x, _, _ = _attn_block_fwd(p["block"], cfg, x, positions, mode=mode)
    x = L.rmsnorm(p["final_norm"], x, cfg.norm_eps)
    return _lm_logits(params, cfg, x)


def _lm_logits(params, cfg, x):
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, transpose=True)
    return L.unembed(params["lm_head"], x, transpose=False)


def _kv_cache_entry(cfg, kvs):
    if cfg.mla is not None:
        return {"ckv": kvs[0], "krope": kvs[1]}
    return {"k": kvs[0], "v": kvs[1]}


# --------------------------------------------------------------------------
# zamba2 / xlstm / whisper forward bodies
# --------------------------------------------------------------------------

def _zamba_forward(params, cfg, x, positions, aux_total, *, mode, window,
                   return_cache, remat):
    emb0 = x                                       # original embedding stream
    cache: dict = {}

    def mamba_one(carry, lp):
        xc = carry
        if return_cache:
            y, st = S.mamba2_fwd(lp, cfg, xc, return_state=True)
            return xc + y, st
        return xc + S.mamba2_fwd(lp, cfg, xc), None

    mamba_one_ck = jax.checkpoint(mamba_one) if remat else mamba_one

    def unit(carry, inp):
        xc = carry
        unit_params, adapter = inp
        xc, sts = jax.lax.scan(mamba_one_ck, xc, unit_params)
        y, _, kv = _attn_block_fwd(params["shared_attn"], cfg, xc, positions,
                                   window=window, mode=mode,
                                   return_kv=return_cache, x_extra=emb0)
        xc = xc + (y - xc) @ adapter               # per-application adapter
        return xc, (sts, kv)

    unit_ck = jax.checkpoint(unit) if remat else unit
    x, (mamba_sts, attn_kvs) = jax.lax.scan(
        unit_ck, x, (params["mamba_units"], params["shared_adapters"]))
    if return_cache:
        cache["mamba_units"] = mamba_sts
        cache["shared_attn"] = _kv_cache_entry(cfg, attn_kvs)
    if "mamba_tail" in params:
        x, tail_sts = jax.lax.scan(mamba_one_ck, x, params["mamba_tail"])
        if return_cache:
            cache["mamba_tail"] = tail_sts
    return x, aux_total, cache


def _xlstm_forward(params, cfg, x, *, return_cache, remat):
    cache: dict = {}

    def mlstm_one(carry, lp):
        if return_cache:
            y, st = X.mlstm_block_fwd(lp, cfg, carry, return_state=True)
            return y, st
        return X.mlstm_block_fwd(lp, cfg, carry), None

    mlstm_one_ck = jax.checkpoint(mlstm_one) if remat else mlstm_one

    def unit(carry, inp):
        xc = carry
        m_params, s_params = inp
        xc, msts = jax.lax.scan(mlstm_one_ck, xc, m_params)
        if return_cache:
            xc, sst = X.slstm_block_fwd(s_params, cfg, xc, return_state=True)
        else:
            xc, sst = X.slstm_block_fwd(s_params, cfg, xc), None
        return xc, (msts, sst)

    unit_ck = jax.checkpoint(unit) if remat else unit
    x, (msts, ssts) = jax.lax.scan(
        unit_ck, x, (params["mlstm_units"], params["slstm_units"]))
    if return_cache:
        cache["mlstm_units"] = msts
        cache["slstm_units"] = ssts
    return x, cache


def _whisper_forward(params, cfg, batch, *, mode, return_cache, remat):
    frames = batch["audio_frames"]                 # (B, F, d) frontend stub
    tokens = batch["tokens"]                       # (B, S)
    B, S = tokens.shape
    dt = L.dtype_of(cfg.activation_dtype)

    # ---- encoder (non-causal, sinusoidal positions)
    enc = frames.astype(dt) + L.sinusoidal_positions(
        frames.shape[1], cfg.d_model).astype(dt)[None]
    zero_pos = jnp.zeros((B, frames.shape[1]), jnp.int32)

    def enc_body(carry, lp):
        y, _, _ = _attn_block_fwd(lp, cfg, carry, zero_pos, causal=False,
                                  rope=False, mode=mode)
        return y, None
    enc_body = jax.checkpoint(enc_body) if remat else enc_body
    enc, _ = jax.lax.scan(enc_body, enc, params["enc_blocks"])
    enc = L.layernorm(params["enc_ln"], enc, cfg.norm_eps)

    # ---- decoder (causal self-attn + cross-attn, learned positions)
    pos_tab = params["dec_pos"]
    x = L.embed(params["embed"], tokens) + pos_tab[None, :S].astype(dt)
    dpos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def dec_body(carry, lp):
        y, _, kv = _attn_block_fwd(lp, cfg, carry, dpos, causal=True,
                                   rope=False, mode=mode, enc_out=enc,
                                   return_kv=return_cache)
        return y, kv
    dec_body = jax.checkpoint(dec_body) if remat else dec_body
    x, kvs = jax.lax.scan(dec_body, x, params["dec_blocks"])

    x = L.norm(params["final_norm"], x, cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    aux = jnp.zeros((), F32)
    if return_cache:
        (selfkv, crosskv) = kvs
        cache = {"dec": {"k": selfkv[0], "v": selfkv[1],
                         "xk": crosskv[0], "xv": crosskv[1]}}
        return logits, aux, cache
    return logits, aux


# ==========================================================================
# loss
# ==========================================================================

def loss_fn(params, cfg: ModelConfig, batch: dict, *, mode="flash",
            moe_dispatch="einsum", remat=True):
    """Next-token cross-entropy (text positions only for VLM) + the MTP
    auxiliary loss when the config carries an MTP head (deepseek-v3).
    Returns (loss, metrics)."""
    mtp_loss = jnp.zeros((), F32)
    if cfg.use_mtp:
        logits, aux, hidden = forward(params, cfg, batch, mode=mode,
                                      moe_dispatch=moe_dispatch,
                                      return_hidden=True, remat=remat)
        toks = batch["tokens"]
        ml = mtp_logits(params, cfg, hidden, toks, mode=mode)
        mlp_ = jax.nn.log_softmax(ml.astype(F32), axis=-1)
        mtp_nll = -jnp.take_along_axis(
            mlp_, toks[:, 2:][..., None], axis=-1)[..., 0]
        mtp_loss = cfg.mtp_weight * jnp.mean(mtp_nll)
    else:
        logits, aux = forward(params, cfg, batch, mode=mode,
                              moe_dispatch=moe_dispatch, remat=remat)
    tokens = batch["tokens"]
    if cfg.family == "vlm":
        logits = logits[:, -tokens.shape[1]:]      # text tail only
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        loss = jnp.mean(nll)
    total = loss + aux + mtp_loss
    return total, {"loss": loss, "aux_loss": aux, "mtp_loss": mtp_loss,
                   "perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


# ==========================================================================
# KV-cache init + decode step
# ==========================================================================

def _attn_cache_struct(cfg, n_layers, B, max_seq, dtype):
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((n_layers, B, max_seq, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((n_layers, B, max_seq, m.qk_rope_head_dim), dtype),
        }
    S_c = min(max_seq, cfg.sliding_window) if cfg.sliding_window else max_seq
    return {
        "k": jnp.zeros((n_layers, B, S_c, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((n_layers, B, S_c, cfg.n_kv_heads, hd), dtype),
    }


def init_cache(cfg: ModelConfig, B: int, max_seq: int) -> dict:
    """Zero-initialized cache pytree for decoding up to max_seq tokens."""
    dt = L.dtype_of(cfg.activation_dtype)
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"blocks": _attn_cache_struct(cfg, cfg.n_layers, B, max_seq, dt)}
    if fam == "moe":
        m = cfg.moe
        c = {}
        if m.n_dense_layers:
            c["blocks_dense"] = _attn_cache_struct(
                cfg, m.n_dense_layers, B, max_seq, dt)
        c["blocks_moe"] = _attn_cache_struct(
            cfg, cfg.n_layers - m.n_dense_layers, B, max_seq, dt)
        return c
    if fam == "hybrid":
        s = cfg.ssm
        d_inner, nh = S._dims(cfg)
        conv_ch = d_inner + 2 * s.n_groups * s.d_state
        k_every = cfg.shared_attn_every
        units, tail = divmod(cfg.n_layers, k_every)
        c = {
            "mamba_units": {
                "ssm": jnp.zeros((units, k_every, B, nh, s.head_dim, s.d_state), F32),
                "conv": jnp.zeros((units, k_every, B, s.d_conv - 1, conv_ch), dt),
            },
            "shared_attn": _attn_cache_struct(cfg, units, B, max_seq, dt),
        }
        if tail:
            c["mamba_tail"] = {
                "ssm": jnp.zeros((tail, B, nh, s.head_dim, s.d_state), F32),
                "conv": jnp.zeros((tail, B, s.d_conv - 1, conv_ch), dt),
            }
        return c
    if fam == "ssm":
        xl = cfg.xlstm
        d_inner, nh, dh = X.mlstm_dims(cfg)
        units = cfg.n_layers // xl.slstm_every
        per = xl.slstm_every - 1
        d = cfg.d_model
        nh_s, dh_s = cfg.n_heads, d // cfg.n_heads
        return {
            "mlstm_units": {
                "C": jnp.zeros((units, per, B, nh, dh, dh), F32),
                "n": jnp.zeros((units, per, B, nh, dh), F32),
                "m": jnp.full((units, per, B, nh), -1e30, F32),
                "conv": jnp.zeros((units, per, B, xl.d_conv - 1, d_inner), dt),
            },
            "slstm_units": {
                "h": jnp.zeros((units, B, d), F32),
                "c": jnp.zeros((units, B, nh_s, dh_s), F32),
                "n": jnp.full((units, B, nh_s, dh_s), 1e-6, F32),
                "m": jnp.zeros((units, B, nh_s, dh_s), F32),
                "conv_win": jnp.zeros((units, B, xl.d_conv - 1, d), dt),
            },
        }
    if fam == "audio":
        hd = cfg.resolved_head_dim
        c = _attn_cache_struct(cfg, cfg.n_layers, B, max_seq, dt)
        c["xk"] = jnp.zeros((cfg.n_layers, B, cfg.n_audio_frames,
                             cfg.n_kv_heads, hd), dt)
        c["xv"] = jnp.zeros_like(c["xk"])
        return {"dec": c}
    raise ValueError(fam)


def graft_slot_cache(cache: dict, prefix_cache: dict, slot) -> dict:
    """Write a single-sequence prefix cache (batch axis of size 1) into
    slot ``slot`` of a multi-slot cache, leaf by leaf.  The batch axis of
    each leaf is the first axis where the two shapes differ; any trailing
    mismatch (the sequence axis, shorter in the prefix) starts at 0, so
    stale cache beyond the prefix stays in place and must be masked by
    the caller's per-slot lengths until overwritten."""
    def graft(big, small):
        start = [0] * big.ndim
        for i, (a, b) in enumerate(zip(big.shape, small.shape)):
            if a != b:
                start[i] = slot
                break
        return jax.lax.dynamic_update_slice(
            big, small.astype(big.dtype), tuple(start))
    return jax.tree.map(graft, cache, prefix_cache)


# --------------------------------------------------------------------------
# paged KV cache (dense / moe attention families)
# --------------------------------------------------------------------------

def _paged_attn_cache_struct(cfg, n_layers, n_pages, page_size, dtype):
    hd = cfg.resolved_head_dim
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "ckv": jnp.zeros((n_layers, n_pages, page_size, m.kv_lora_rank),
                             dtype),
            "krope": jnp.zeros((n_layers, n_pages, page_size,
                                m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((n_layers, n_pages, page_size, cfg.n_kv_heads, hd),
                       dtype),
        "v": jnp.zeros((n_layers, n_pages, page_size, cfg.n_kv_heads, hd),
                       dtype),
    }


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int) -> dict:
    """Zero-initialized paged KV pool: pages replace the per-slot
    ``(B, max_seq)`` reservation of ``init_cache``; which sequence owns
    which page lives in the engine's block tables.  Attention-cached
    families only — recurrent state (hybrid/ssm) is fixed-size per slot
    and keeps the contiguous layout.  Sliding-window archs store full
    absolute positions per page (the window is enforced by masking, not
    by a ring buffer)."""
    dt = L.dtype_of(cfg.activation_dtype)
    fam = cfg.family
    if fam == "dense":
        return {"blocks": _paged_attn_cache_struct(
            cfg, cfg.n_layers, n_pages, page_size, dt)}
    if fam == "moe":
        m = cfg.moe
        c = {}
        if m.n_dense_layers:
            c["blocks_dense"] = _paged_attn_cache_struct(
                cfg, m.n_dense_layers, n_pages, page_size, dt)
        c["blocks_moe"] = _paged_attn_cache_struct(
            cfg, cfg.n_layers - m.n_dense_layers, n_pages, page_size, dt)
        return c
    raise NotImplementedError(
        f"paged KV cache unsupported for family {cfg.family!r} "
        "(recurrent families keep their fixed-size state path)")


def graft_paged_cache(cache: dict, prefix_cache: dict, page_ids,
                      since: int = 0) -> dict:
    """Scatter a single-sequence prefix cache (leaves (L, 1, S_b, ...))
    into pages ``page_ids`` ((n0,) int32) of the paged pool.  The prefix
    is padded/clamped to ``n0 * page_size`` positions, so every written
    page is fully overwritten — positions beyond the true prompt length
    hold prefill padding garbage and stay masked by the per-slot
    ``kv_len`` exactly as in the contiguous layout.

    ``since`` (static) skips the first ``since`` entries of ``page_ids``:
    the delta half of the KV-delta spill format — a re-resumed sequence
    whose leading pages are already device-resident (or already grafted
    from a base snapshot) grafts only the pages dirtied since the last
    spill, and base + delta reassemble token-exactly.  A shared-prefix
    resume passes only its private page ids here: the shared prefix
    never left the pool, so nothing is grafted over it."""
    if since:
        page_ids = page_ids[since:]
    def graft(pool, small):
        ps = pool.shape[2]
        n0 = page_ids.shape[0]
        need = n0 * ps
        sm = small[:, 0]                          # (L, S_b, ...)
        Sb = sm.shape[1]
        if Sb < need:
            pad = [(0, 0)] * sm.ndim
            pad[1] = (0, need - Sb)
            sm = jnp.pad(sm, pad)
        else:
            sm = sm[:, :need]
        sm = sm.reshape(sm.shape[0], n0, ps, *sm.shape[2:])
        return pool.at[:, page_ids].set(sm.astype(pool.dtype))
    return jax.tree.map(graft, cache, prefix_cache)


def extract_paged_cache(cache: dict, page_ids, since: int = 0) -> dict:
    """Gather pages ``page_ids`` ((n,) int32) of the paged pool back into
    a single-sequence prefix cache (leaves (L, 1, n * page_size, ...)) —
    the exact inverse of ``graft_paged_cache``.  Preemption snapshots a
    live sequence's KV with this, releases its pages, and later resumes
    by grafting the snapshot into freshly allocated pages; because the
    snapshot length is a whole number of pages, the graft pads nothing
    and the round trip is bit-exact.

    ``since`` (static) gathers only ``page_ids[since:]`` — the pages
    dirtied since a previous spill epoch.  Re-preempting a long sequence
    then ships only its new pages; the host store keeps the clean prefix
    from the earlier spill (``serving.paging.DeltaSpillStore``).  The
    same slicing marks a SHARED-prefix boundary: a sequence holding
    prefix-index pages spills with ``since >= shared_pages`` so pages
    still referenced elsewhere are never re-shipped — they stay pinned
    in the pool and the resume grafts only the private tail after
    them."""
    if since:
        page_ids = page_ids[since:]
    def gather(pool):
        sm = pool[:, page_ids]                    # (L, n, ps, ...)
        L, n, ps = sm.shape[:3]
        return sm.reshape(L, 1, n * ps, *sm.shape[3:])
    return jax.tree.map(gather, cache)


def copy_paged_pages(cache: dict, src_ids, dst_ids) -> dict:
    """Duplicate pages ``src_ids`` of the paged pool into ``dst_ids``
    (both (n,) int32) — the device-side half of copy-on-write forking.
    A sequence about to write into a page it shares with the prefix
    index (refcount > 1) first copies the page into a private one drawn
    from its own reservation, then redirects its block table; whole
    pages move, so the fork is bit-exact with the shared original and
    no other holder ever observes the write."""
    def cp(pool):
        return pool.at[:, dst_ids].set(pool[:, src_ids])
    return jax.tree.map(cp, cache)


def extract_slot_cache(cache: dict, template: dict, slot) -> dict:
    """Slice slot ``slot`` of a multi-slot cache into a single-sequence
    cache shaped like ``template`` (a batch-1 pytree from ``init_cache``)
    — the inverse of ``graft_slot_cache``.  The batch axis of each leaf
    is the first axis where the two shapes differ."""
    def gather(big, tmpl):
        start = [0] * big.ndim
        for i, (a, b) in enumerate(zip(big.shape, tmpl.shape)):
            if a != b:
                start[i] = slot
                break
        return jax.lax.dynamic_slice(big, tuple(start), tmpl.shape)
    return jax.tree.map(gather, cache, template)


def decode_step(params: dict, cfg: ModelConfig, cache: dict,
                tokens: jax.Array, pos,
                block_tables=None) -> Tuple[jax.Array, dict]:
    """One decode step.  tokens: (B, 1) int32.  pos is either a scalar
    int32 (all sequences at the same absolute position — the fixed-slot
    engine) or a (B,) vector of per-sequence positions (continuous
    batching: each cache slot is at its own depth; cache writes and
    attention masks are resolved per slot).  block_tables: optional
    (B, max_pages) int32 per-slot page ids (scratch page 0 for inactive
    slots / unused entries) — when given, cache leaves are
    ``init_paged_cache`` pools and reads/writes go through the tables
    (dense/moe only).  Returns (logits (B,1,V), new_cache)."""
    fam = cfg.family
    window = cfg.sliding_window
    per_slot = jnp.asarray(pos).ndim == 1
    if per_slot and fam == "audio":
        raise NotImplementedError(
            "per-slot decode positions unsupported for encoder-decoder "
            "audio (learned positions are looked up with a scalar index)")
    if block_tables is not None and fam not in ("dense", "moe"):
        raise NotImplementedError(
            f"paged decode unsupported for family {cfg.family!r} "
            "(recurrent families keep their fixed-size state path)")
    x = L.embed(params["embed"], tokens)
    if cfg.position_kind == "learned":
        x = _add_positions(params, x, jnp.broadcast_to(
            jnp.reshape(jnp.asarray(pos, jnp.int32), (-1, 1)), tokens.shape))
    rope = fam != "audio"
    rope_pos = None
    if fam == "audio":
        x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"], pos, 1, 0)[None]
    if fam == "vlm":
        # M-RoPE: text rotary positions restart after the patch grid —
        # slot index pos counts [patches | text], rotary counts grid + i
        grid = int(cfg.n_patches ** 0.5) or 1
        rope_pos = pos - cfg.n_patches + grid
    new_cache: dict = {}

    def scan_attn(x, stack_params, stack_cache):
        def body(xc, inp):
            lp, lc = inp
            xn, nc = _attn_block_decode(lp, cfg, xc, lc, pos, window=window,
                                        rope=rope, rope_pos=rope_pos,
                                        block_tables=block_tables)
            return xn, nc
        return jax.lax.scan(body, x, (stack_params, stack_cache))

    if fam in ("dense", "vlm"):
        x, new_cache["blocks"] = scan_attn(x, params["blocks"], cache["blocks"])
    elif fam == "moe":
        if "blocks_dense" in params:
            x, new_cache["blocks_dense"] = scan_attn(
                x, params["blocks_dense"], cache["blocks_dense"])
        x, new_cache["blocks_moe"] = scan_attn(
            x, params["blocks_moe"], cache["blocks_moe"])
    elif fam == "hybrid":
        x, new_cache = _zamba_decode(params, cfg, x, cache, pos, window)
    elif fam == "ssm":
        x, new_cache = _xlstm_decode(params, cfg, x, cache)
    elif fam == "audio":
        x, new_cache["dec"] = scan_attn(x, params["dec_blocks"], cache["dec"])
    x = L.norm(params["final_norm"], x, cfg.norm_eps)
    return _lm_logits(params, cfg, x), new_cache


def _zamba_decode(params, cfg, x, cache, pos, window):
    emb0 = x

    def mamba_one(xc, inp):
        lp, lc = inp
        y, nc = S.mamba2_decode(lp, cfg, xc, lc)
        return xc + y, nc

    def unit(xc, inp):
        (u_params, adapter), (u_mcache, u_acache) = inp
        xc, mnc = jax.lax.scan(mamba_one, xc, (u_params, u_mcache))
        y, anc = _attn_block_decode(params["shared_attn"], cfg, xc, u_acache,
                                    pos, window=window, x_extra=emb0)
        xc = xc + (y - xc) @ adapter
        return xc, (mnc, anc)

    x, (mnc, anc) = jax.lax.scan(
        unit, x,
        ((params["mamba_units"], params["shared_adapters"]),
         (cache["mamba_units"], cache["shared_attn"])))
    new_cache = {"mamba_units": mnc, "shared_attn": anc}
    if "mamba_tail" in params:
        x, tnc = jax.lax.scan(mamba_one, x,
                              (params["mamba_tail"], cache["mamba_tail"]))
        new_cache["mamba_tail"] = tnc
    return x, new_cache


def _xlstm_decode(params, cfg, x, cache):
    def mlstm_one(xc, inp):
        lp, lc = inp
        return X.mlstm_block_decode(lp, cfg, xc, lc)

    def unit(xc, inp):
        (m_params, s_params), (m_cache, s_cache) = inp
        xc, mnc = jax.lax.scan(mlstm_one, xc, (m_params, m_cache))
        xc, snc = X.slstm_block_decode(s_params, cfg, xc, s_cache)
        return xc, (mnc, snc)

    x, (mnc, snc) = jax.lax.scan(
        unit, x,
        ((params["mlstm_units"], params["slstm_units"]),
         (cache["mlstm_units"], cache["slstm_units"])))
    return x, {"mlstm_units": mnc, "slstm_units": snc}


# ==========================================================================
# chunked prefill into the paged cache (unified token-budget step)
# ==========================================================================

def _attn_block_prefill_chunk(p, cfg, x, cache, pos_offset, n_valid,
                              block_tables, *, window=0, moe_capacity=None):
    """Prefill-chunk step for an attention block: the chunk's KV goes
    straight into the block's slice of the paged pool (no contiguous
    prefix cache exists at any point).  Returns (x, new_cache, aux)
    where aux is the MoE overflow count under ``moe_capacity`` (0 for
    dense blocks / unbounded capacity)."""
    eps = cfg.norm_eps
    h_in = L.norm(p["ln1"], x, eps)
    if cfg.mla is not None:
        a, ckv, krope = A.mla_paged_prefill(p["attn"], cfg, h_in,
                                            cache["ckv"], cache["krope"],
                                            pos_offset, n_valid, block_tables)
        new_cache = {"ckv": ckv, "krope": krope}
    else:
        a, k, v = A.paged_prefill_attention(
            p["attn"], cfg, h_in, cache["k"], cache["v"], pos_offset, n_valid,
            block_tables, window=window)
        new_cache = {"k": k, "v": v}
    x = x + a
    aux = jnp.zeros((), F32)
    h = L.norm(p["ln2"], x, eps)
    if "moe" in p:
        # serving path: drop-free routing; a bounded capacity reports
        # overflow through aux so the engine can retry with a larger one
        y, aux = M.moe_fwd(p["moe"], cfg, h, dispatch="einsum",
                           drop_free=True, capacity=moe_capacity)
    elif "b_up" in p.get("mlp", {}):
        y = L.gelu_mlp(p["mlp"], h, cfg.gelu_approximate)
    else:
        y = L.swiglu(p["mlp"], h)
    return x + y, new_cache, aux


def prefill_chunk(params: dict, cfg: ModelConfig, cache: dict, tokens,
                  n_valid, pos_offset, block_tables, *,
                  moe_capacity=None) -> Tuple[jax.Array, jax.Array, dict]:
    """One prompt chunk of a single sequence, written DIRECTLY into the
    paged KV pool — the admission contract of the unified token-budget
    step (dense / moe incl. MLA; recurrent families keep monolithic
    prefill on their contiguous state).

    tokens: (1, C) int32 — chunk positions ``[pos_offset, pos_offset+C)``
    of the prompt, of which the first ``n_valid`` (dynamic) are real and
    the rest are jit-bucketing pads whose KV lands on the scratch page.
    cache: an ``init_paged_cache`` pool.  block_tables: (1, max_pages)
    int32 covering at least positions [0, pos_offset + n_valid).

    Returns (logits (1, C, V), moe_overflow, new_cache).  The logits
    are PER-POSITION next-token distributions — ``logits[0, i]``
    predicts the token after ``tokens[0, i]`` given everything up to
    ``pos_offset + i`` — and that contract is load-bearing twice over:
    prompt admission takes ``logits[0, n_valid-1]`` of the final chunk
    as the first emitted token's distribution, and speculative
    draft-verify (``serving.engine.ContinuousEngine._verify_slot``)
    runs a chunk of ``[last_token, d_1..d_k]`` mid-decode and compares
    every position's argmax against the next draft to accept the
    longest agreeing prefix in one pass.  ``moe_overflow`` is nonzero
    when ``moe_capacity`` dropped routings (the engine doubles and
    retries — the same dynamic-capacity discipline as monolithic
    serving prefill, applied per chunk)."""
    fam = cfg.family
    if fam not in ("dense", "moe"):
        raise NotImplementedError(
            f"chunked paged prefill unsupported for family {cfg.family!r} "
            "(recurrent families keep their monolithic prefill path)")
    window = cfg.sliding_window
    x = L.embed(params["embed"], tokens)
    if cfg.position_kind == "learned":
        x = _add_positions(params, x,
                           pos_offset + jnp.arange(tokens.shape[1])[None])
    aux_total = jnp.zeros((), F32)
    new_cache: dict = {}

    def scan_chunk(x, aux_total, stack_params, stack_cache):
        def body(carry, inp):
            xc, aux = carry
            lp, lc = inp
            xn, nc, a = _attn_block_prefill_chunk(
                lp, cfg, xc, lc, pos_offset, n_valid, block_tables,
                window=window, moe_capacity=moe_capacity)
            return (xn, aux + a), nc
        (x, aux_total), nc = jax.lax.scan(body, (x, aux_total),
                                          (stack_params, stack_cache))
        return x, aux_total, nc

    if fam == "dense":
        x, aux_total, new_cache["blocks"] = scan_chunk(
            x, aux_total, params["blocks"], cache["blocks"])
    else:
        if "blocks_dense" in params:
            x, aux_total, new_cache["blocks_dense"] = scan_chunk(
                x, aux_total, params["blocks_dense"], cache["blocks_dense"])
        x, aux_total, new_cache["blocks_moe"] = scan_chunk(
            x, aux_total, params["blocks_moe"], cache["blocks_moe"])
    x = L.norm(params["final_norm"], x, cfg.norm_eps)
    return _lm_logits(params, cfg, x), aux_total, new_cache


# ==========================================================================
# prefill convenience
# ==========================================================================

def prefill(params, cfg: ModelConfig, batch: dict, *, mode="flash",
            moe_dispatch: str = "einsum", moe_capacity=None):
    """Run the full prompt, returning (last-position logits, cache)."""
    logits, aux, cache = forward(params, cfg, batch, mode=mode,
                                 moe_dispatch=moe_dispatch,
                                 moe_drop_free=True,
                                 moe_capacity=moe_capacity,
                                 return_cache=True, remat=False)
    return logits[:, -1:], cache
