"""GPTBigCode (``model_type`` ``gpt_bigcode``; IBM Granite-20B-Code,
arXiv:2405.04324), as published: pre-LayerNorm blocks (weight and bias),
learned absolute positions added to the token embedding, multi-query
attention (every query head reads one shared key/value head) with a bias
on q, k, v and the output projection, an exact (erf) GELU MLP with
biases, a final LayerNorm, and the output head tied to the token
embedding.

Departures from the published description: none in the arithmetic.  The
weights are random (``model._draw``) and held in bfloat16, the published
``torch_dtype`` being float32; attention dropout, residual dropout and
embedding dropout are inference no-ops and left out.  The attention is
computed in blocks of query rows so that a 2048-position batch fits one
chip; every row still attends to all earlier positions.

The reference pass imports nothing of the program; every matrix product
runs under ``jax.default_matmul_precision("highest")``.  Where the host
has several devices and the batch divides over them, the sequences of a
batch are spread over the devices (``embed``): the reference is the same,
and each device runs its share of it.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from reference import fp8, mm

Q_BLOCK = 512                   # query rows per attention block


def matmul_params(m: dict) -> int:
    """Weights that every token multiplies: all layers' projections (q,
    the shared k and v, the output, up and down) and the tied head."""
    d, H, Hkv, D, ff = m["d"], m["H"], m["Hkv"], m["D"], m["ff"]
    per_layer = d * H * D + 2 * d * Hkv * D + H * D * d + 2 * d * ff
    return m["L"] * per_layer + d * m["V"]


PUBLISHED_KEYS = ("n_embd", "n_head", "n_inner", "n_layer", "n_positions",
                  "layer_norm_epsilon", "multi_query", "activation_function",
                  "vocab_size")


def dims(spec: dict) -> dict:
    missing = [k for k in PUBLISHED_KEYS if k not in spec]
    if missing:
        raise ValueError(f"{spec['name']}: no {', '.join(missing)}: the "
                         "GPTBigCode keys this family reads")
    if spec["activation_function"] != "gelu":
        raise ValueError(f"{spec['name']}: only the exact GELU (\"gelu\") "
                         "is served")
    d = spec["n_embd"]
    H = spec["n_head"]
    m = {
        "L": spec["n_layer"], "d": d, "H": H,
        "Hkv": 1 if spec["multi_query"] else H, "D": d // H,
        "ff": spec["n_inner"], "V": spec["vocab_size"],
        "P": spec["n_positions"], "eps": spec["layer_norm_epsilon"],
    }
    m["matmul_params"] = matmul_params(m)
    return m


def model_config(spec: dict, m: dict):
    from repro.config import ModelConfig

    return ModelConfig(
        name=spec["name"], family="dense", citation=spec["source"],
        n_layers=m["L"], d_model=m["d"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["D"], qkv_bias=True,
        attn_out_bias=True, mlp_type="gelu", gelu_approximate=False,
        norm_kind="layernorm",
        norm_eps=float(m["eps"]), position_kind="learned",
        n_positions=m["P"], tie_embeddings=True,
        param_dtype="bfloat16", activation_dtype="bfloat16")


def layer_leaves(m: dict) -> dict:
    """Kinds: 'proj' (std 1/sqrt(fan_in)), 'scale' (1 + 0.1 N), 'bias'
    (0.02 N)."""
    d, H, Hkv, D, ff = m["d"], m["H"], m["Hkv"], m["D"], m["ff"]
    return {
        "ln1.scale": ((d,), "scale"), "ln1.bias": ((d,), "bias"),
        "attn.w_q": ((d, H * D), "proj"), "attn.b_q": ((H * D,), "bias"),
        "attn.w_k": ((d, Hkv * D), "proj"), "attn.b_k": ((Hkv * D,), "bias"),
        "attn.w_v": ((d, Hkv * D), "proj"), "attn.b_v": ((Hkv * D,), "bias"),
        "attn.w_o": ((H * D, d), "proj"), "attn.b_o": ((d,), "bias"),
        "ln2.scale": ((d,), "scale"), "ln2.bias": ((d,), "bias"),
        "mlp.w_up": ((d, ff), "proj"), "mlp.b_up": ((ff,), "bias"),
        "mlp.w_down": ((ff, d), "proj"), "mlp.b_down": ((d,), "bias"),
    }


def top_leaves(m: dict) -> dict:
    """The token and position tables (kind 'embed', 0.02 N) and the final
    LayerNorm; the head is the token table."""
    d = m["d"]
    return {"embed": ((m["V"], d), "embed"),
            "pos_embed": ((m["P"], d), "embed"),
            "final_norm.scale": ((d,), "scale"),
            "final_norm.bias": ((d,), "bias")}


# -- the reference ----------------------------------------------------------

def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _spread(x):
    """``x`` with its batch spread over the host's devices, where it
    divides; as it is otherwise."""
    devs = jax.devices()
    if len(devs) == 1 or x.shape[0] % len(devs):
        return x
    mesh = Mesh(np.asarray(devs), ("b",))
    return jax.device_put(x, NamedSharding(mesh, P("b")))


def embed(top, toks):
    """Token rows plus the position rows 0 .. S-1."""
    S = toks.shape[1]
    return _spread(jnp.take(top["embed"], toks, axis=0) + top["pos_embed"][:S])


def _attention(q, k, v):
    """Causal multi-query attention.  q: (B, S, H, D); k, v: (B, S, Hkv,
    D).  Scores at 1/sqrt(D), softmax over every earlier position, in
    blocks of ``Q_BLOCK`` query rows."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, H // Hkv, D)
    kpos = jnp.arange(S)
    out = []
    for lo in range(0, S, Q_BLOCK):
        qb = qg[:, lo:lo + Q_BLOCK]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) * D ** -0.5
        qpos = lo + jnp.arange(qb.shape[1])
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v))
    return jnp.concatenate(out, 1).reshape(B, S, H * D)


@lru_cache(maxsize=8)
def layer_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)
    H, Hkv, D, eps = m["H"], m["Hkv"], m["D"], m["eps"]

    @jax.jit
    def layer(w, x):
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            h = _layernorm(x, w["ln1.scale"], w["ln1.bias"], eps)
            q = mm(h, w["attn.w_q"], control) + w["attn.b_q"]
            k = mm(h, w["attn.w_k"], control) + w["attn.b_k"]
            v = mm(h, w["attn.w_v"], control) + w["attn.b_v"]
            k = k.reshape(B, S, Hkv, D)
            v = v.reshape(B, S, Hkv, D)
            if control:
                k, v = fp8(k, -1), fp8(v, -1)
            a = _attention(q.reshape(B, S, H, D), k, v)
            x = x + mm(a, w["attn.w_o"], control) + w["attn.b_o"]
            h = _layernorm(x, w["ln2.scale"], w["ln2.bias"], eps)
            u = jax.nn.gelu(mm(h, w["mlp.w_up"], control) + w["mlp.b_up"],
                            approximate=False)
            return x + mm(u, w["mlp.w_down"], control) + w["mlp.b_down"]
    return layer


@lru_cache(maxsize=8)
def head_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)

    @jax.jit
    def head(top, h, probes):
        """h: (R, d) final hidden rows; probes: (R, P) token ids.
        Returns (max logit, argmax, logits at probes)."""
        with jax.default_matmul_precision("highest"):
            h = _layernorm(h, top["final_norm.scale"], top["final_norm.bias"],
                           m["eps"])
            logits = mm(h, top["embed"].T, control)
            return (jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32),
                    jnp.take_along_axis(logits, probes, axis=-1))
    return head
