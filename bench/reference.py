"""The plain float32 reference and its fp8 control, over served requests.

The forward pass of each architecture is its family module's
(``bench/families/<model_type>.py``); nothing here or there imports the
program.  The pass runs one layer at a time over a fixed batch of padded
sequences, drawing each layer's weights from the seed as it goes, so it
fits on the chip once the program's state is freed.  Logits are formed
only at the rows asked for, in blocks.

``control=True`` computes the same pass one precision step below the
served bfloat16: every weight and activation entering a matrix product is
rounded to float8 e4m3 (weights scaled per output channel, activations
per row, ``mm``), and keys and values are stored in e4m3 too (``fp8``).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from model import ReferenceWeights

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
ROW_BLOCK = 256


def fp8(x, axis):
    """``x`` rounded to e4m3, scaled so that its largest magnitude along
    ``axis`` is the format's largest value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(FP8).astype(F32) * s


def mm(a, w, control):
    """``a @ w``; under the control, both operands rounded to e4m3 first
    (activations per row, weights per output channel)."""
    if control:
        a, w = fp8(a, -1), fp8(w, 0)
    return a @ w


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
    fam, m = weights.family, weights.dims
    dkey = tuple(sorted(m.items()))
    layer, head = fam.layer_fn(dkey, control), fam.head_fn(dkey, control)
    n = len(seqs)
    n_pad = -(-n // batch) * batch
    toks = np.zeros((n_pad, pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens > pad_to {pad_to}")
        toks[i, :len(s)] = s
    top = weights.top()
    xs = [fam.embed(top, jnp.asarray(toks[b:b + batch]))
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
