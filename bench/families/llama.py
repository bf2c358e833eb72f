"""The llama decoder, as published: pre-norm blocks, RMSNorm, rotary
embeddings on the rotate-half convention, grouped-query attention (MHA when
the head counts match), a SwiGLU MLP, and a tied or untied output head.
``qwen2`` adds q/k/v biases (``families/qwen2.py``).

The reference pass imports nothing of the program; every matrix product
runs under ``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from reference import F32, fp8, mm


def matmul_params(m: dict) -> int:
    """Weights that every token multiplies: all layers' projections and
    the output head (embedding lookups multiply nothing)."""
    d, H, Hkv, D, ff = m["d"], m["H"], m["Hkv"], m["D"], m["ff"]
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 3 * d * ff
    return m["L"] * per_layer + d * m["V"]


def dims(spec: dict) -> dict:
    d = spec["hidden_size"]
    H = spec["num_attention_heads"]
    m = {
        "L": spec["num_hidden_layers"], "d": d, "H": H,
        "Hkv": spec["num_key_value_heads"], "D": spec.get("head_dim", d // H),
        "ff": spec["intermediate_size"], "V": spec["vocab_size"],
        "eps": spec["rms_norm_eps"], "theta": spec["rope_theta"],
        "tied": spec["tie_word_embeddings"],
        "qkv_bias": False,
    }
    m["matmul_params"] = matmul_params(m)
    return m


def model_config(spec: dict, m: dict):
    from repro.config import ModelConfig

    if spec["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: only the silu (SwiGLU) MLP is served")
    return ModelConfig(
        name=spec["name"], family="dense", citation=spec["source"],
        n_layers=m["L"], d_model=m["d"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["D"],
        qkv_bias=m["qkv_bias"], rope_theta=float(m["theta"]),
        tie_embeddings=m["tied"], norm_eps=float(m["eps"]),
        param_dtype="bfloat16", activation_dtype="bfloat16")


def layer_leaves(m: dict) -> dict:
    """Kinds: 'proj' (std 1/sqrt(fan_in)), 'scale' (1 + 0.1 N), 'bias'
    (0.02 N)."""
    d, H, Hkv, D, ff = m["d"], m["H"], m["Hkv"], m["D"], m["ff"]
    leaves = {
        "ln1.scale": ((d,), "scale"),
        "attn.w_q": ((d, H * D), "proj"),
        "attn.w_k": ((d, Hkv * D), "proj"),
        "attn.w_v": ((d, Hkv * D), "proj"),
        "attn.w_o": ((H * D, d), "proj"),
        "ln2.scale": ((d,), "scale"),
        "mlp.w_gate": ((d, ff), "proj"),
        "mlp.w_up": ((d, ff), "proj"),
        "mlp.w_down": ((ff, d), "proj"),
    }
    if m["qkv_bias"]:
        leaves.update({"attn.b_q": ((H * D,), "bias"),
                       "attn.b_k": ((Hkv * D,), "bias"),
                       "attn.b_v": ((Hkv * D,), "bias")})
    return leaves


def top_leaves(m: dict) -> dict:
    leaves = {"embed": ((m["V"], m["d"]), "embed"),
              "final_norm.scale": ((m["d"],), "scale")}
    if not m["tied"]:
        leaves["lm_head"] = ((m["d"], m["V"]), "proj")
    return leaves


# -- the reference ----------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, D) at positions 0..S-1 (rotate-half)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]          # (S, D/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def embed(top, toks):
    return jnp.take(top["embed"], toks, axis=0)


@lru_cache(maxsize=8)
def layer_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)
    H, Hkv, D, eps, theta = m["H"], m["Hkv"], m["D"], m["eps"], m["theta"]
    g = H // Hkv

    @jax.jit
    def layer(w, x):
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            h = _rmsnorm(x, w["ln1.scale"], eps)
            q = mm(h, w["attn.w_q"], control)
            k = mm(h, w["attn.w_k"], control)
            v = mm(h, w["attn.w_v"], control)
            if "attn.b_q" in w:
                q, k, v = q + w["attn.b_q"], k + w["attn.b_k"], v + w["attn.b_v"]
            q = _rope(q.reshape(B, S, H, D), theta)
            k = _rope(k.reshape(B, S, Hkv, D), theta)
            v = v.reshape(B, S, Hkv, D)
            if control:
                k, v = fp8(k, -1), fp8(v, -1)
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
            causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * D)
            x = x + mm(a, w["attn.w_o"], control)
            h = _rmsnorm(x, w["ln2.scale"], eps)
            u = jax.nn.silu(mm(h, w["mlp.w_gate"], control)) * mm(
                h, w["mlp.w_up"], control)
            return x + mm(u, w["mlp.w_down"], control)
    return layer


@lru_cache(maxsize=8)
def head_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)

    @jax.jit
    def head(top, h, probes):
        """h: (R, d) final hidden rows; probes: (R, P) token ids.
        Returns (max logit, argmax, logits at probes)."""
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(h, top["final_norm.scale"], m["eps"])
            w = top["embed"].T if m["tied"] else top["lm_head"]
            logits = mm(h, w, control)
            return (jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32),
                    jnp.take_along_axis(logits, probes, axis=-1))
    return head
