"""Plain float32 forward pass of the dense decoder family, and its fp8 control.

The architecture as published (llama / qwen2): pre-norm blocks, RMSNorm,
rotary embeddings on the rotate-half convention, grouped-query attention
(MHA when the head counts match), optional q/k/v biases, a SwiGLU MLP, and
a tied or untied output head.  Nothing here imports the program.  Every
matrix product runs under ``jax.default_matmul_precision("highest")``.

The pass runs one layer at a time over a fixed batch of padded sequences,
drawing each layer's weights from the seed as it goes, so it fits on the
chip once the program's state is freed.  Logits are formed only at the
rows asked for, in blocks.

``control=True`` computes the same pass one precision step below the
served bfloat16: every weight and activation entering a matrix product is
rounded to float8 e4m3 (weights scaled per output channel, activations
per row), and keys and values are stored in e4m3 too.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from model import ReferenceWeights

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
ROW_BLOCK = 256


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(FP8).astype(F32) * s


def _mm(a, w, control):
    if control:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return a @ w


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


@lru_cache(maxsize=8)
def _layer_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)
    H, Hkv, D, eps, theta = m["H"], m["Hkv"], m["D"], m["eps"], m["theta"]
    g = H // Hkv

    @jax.jit
    def layer(w, x):
        with jax.default_matmul_precision("highest"):
            B, S, _ = x.shape
            h = _rmsnorm(x, w["ln1.scale"], eps)
            q = _mm(h, w["attn.w_q"], control)
            k = _mm(h, w["attn.w_k"], control)
            v = _mm(h, w["attn.w_v"], control)
            if "attn.b_q" in w:
                q, k, v = q + w["attn.b_q"], k + w["attn.b_k"], v + w["attn.b_v"]
            q = _rope(q.reshape(B, S, H, D), theta)
            k = _rope(k.reshape(B, S, Hkv, D), theta)
            v = v.reshape(B, S, Hkv, D)
            if control:
                k, v = _fp8(k, -1), _fp8(v, -1)
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
            causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, H * D)
            x = x + _mm(a, w["attn.w_o"], control)
            h = _rmsnorm(x, w["ln2.scale"], eps)
            u = jax.nn.silu(_mm(h, w["mlp.w_gate"], control)) * _mm(
                h, w["mlp.w_up"], control)
            return x + _mm(u, w["mlp.w_down"], control)
    return layer


@lru_cache(maxsize=8)
def _head_fn(dims_key: tuple, control: bool):
    m = dict(dims_key)

    @jax.jit
    def head(top, h, probes):
        """h: (R, d) final hidden rows; probes: (R, P) token ids.
        Returns (max logit, argmax, logits at probes)."""
        with jax.default_matmul_precision("highest"):
            h = _rmsnorm(h, top["final_norm.scale"], m["eps"])
            w = top["embed"].T if m["tied"] else top["lm_head"]
            logits = _mm(h, w, control)
            return (jnp.max(logits, -1), jnp.argmax(logits, -1).astype(jnp.int32),
                    jnp.take_along_axis(logits, probes, axis=-1))
    return head


def logits_at(spec: dict, seed: int, seqs, rows, probes, *, pad_to: int,
              batch: int = 8, control: bool = False) -> dict:
    """Run the reference over ``seqs`` and read logits at chosen rows.

    seqs: token arrays (each at most ``pad_to`` long); rows[i]: positions
    of seqs[i] whose next-token logits are read; probes[i]: (len(rows[i]),
    P) token ids whose logits are returned.  Sequences are padded at the
    end to ``pad_to`` and run ``batch`` at a time (a fixed shape, so the
    layer compiles once).  Returns numpy arrays over all rows in order:
    ``best`` (max logit), ``argmax``, ``probe`` (R, P).
    """
    weights = ReferenceWeights(spec, seed)
    m = weights.dims
    dkey = tuple(sorted(m.items()))
    layer, head = _layer_fn(dkey, control), _head_fn(dkey, control)
    n = len(seqs)
    n_pad = -(-n // batch) * batch
    toks = np.zeros((n_pad, pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to {pad_to}")
        toks[i, :len(s)] = s
    top = weights.top()
    xs = [jnp.take(top["embed"], jnp.asarray(toks[b:b + batch]), axis=0)
          for b in range(0, n_pad, batch)]
    for i in range(m["L"]):
        w = weights.layer(i)
        xs = [layer(w, x) for x in xs]
        del w
    x = jnp.concatenate(xs, 0)
    del xs
    seq_idx = np.concatenate([np.full(len(r), i, np.int32)
                              for i, r in enumerate(rows)])
    pos_idx = np.concatenate([np.asarray(r, np.int32) for r in rows])
    prb = np.concatenate([np.asarray(p, np.int32).reshape(len(r), -1)
                          for p, r in zip(probes, rows)])
    R = len(seq_idx)
    R_pad = -(-R // ROW_BLOCK) * ROW_BLOCK
    seq_idx = np.pad(seq_idx, (0, R_pad - R))
    pos_idx = np.pad(pos_idx, (0, R_pad - R))
    prb = np.pad(prb, ((0, R_pad - R), (0, 0)))
    best, arg, probe = [], [], []
    for b in range(0, R_pad, ROW_BLOCK):
        h = x[jnp.asarray(seq_idx[b:b + ROW_BLOCK]),
              jnp.asarray(pos_idx[b:b + ROW_BLOCK])]
        mx, am, pv = head(top, h, jnp.asarray(prb[b:b + ROW_BLOCK]))
        best.append(np.asarray(mx))
        arg.append(np.asarray(am))
        probe.append(np.asarray(pv))
    return {"best": np.concatenate(best)[:R], "argmax": np.concatenate(arg)[:R],
            "probe": np.concatenate(probe)[:R]}


def served_rows(prompt, served):
    """The sequence the reference runs for one served request, and the rows
    at which each served token was predicted: token j of ``served`` is the
    greedy choice at position len(prompt) - 1 + j."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq, rows


def served_gaps(spec, seed, samples, *, pad_to, batch=8):
    """Per served token, how far its reference logit lies below the
    reference's best.  samples: [(prompt, served tokens)]."""
    seqs, rows, probes = [], [], []
    for prompt, served in samples:
        s, r = served_rows(prompt, served)
        seqs.append(s)
        rows.append(r)
        probes.append(np.asarray(served, np.int32)[:, None])
    out = logits_at(spec, seed, seqs, rows, probes, pad_to=pad_to, batch=batch)
    return out["best"] - out["probe"][:, 0]


def control_gaps(spec, seed, samples, *, pad_to, batch=8):
    """The control's reading on the same prompts and served tokens: at each
    row, how far below the float32 reference's best lies the token that the
    fp8 pass puts first."""
    seqs, rows = [], []
    for prompt, served in samples:
        s, r = served_rows(prompt, served)
        seqs.append(s)
        rows.append(r)
    zero = [np.zeros((len(r), 1), np.int32) for r in rows]
    ctrl = logits_at(spec, seed, seqs, rows, zero, pad_to=pad_to, batch=batch,
                     control=True)
    splits = np.cumsum([len(r) for r in rows])[:-1]
    probes = [a[:, None] for a in np.split(ctrl["argmax"], splits)]
    ref = logits_at(spec, seed, seqs, rows, probes, pad_to=pad_to, batch=batch)
    return ref["best"] - ref["probe"][:, 0]
