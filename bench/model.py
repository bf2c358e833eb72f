"""Model configurations and weights, built from ``bench/configs/<name>.json``.

The configuration file holds the published sizes (the keys of the model's
public ``config.json``) and the engine's knobs.  The program's
``ModelConfig`` is built from it here, so an edit to the program's own
config modules does not move the benchmark.

Weights are random and drawn from ``--seed``.  Every leaf of every layer
comes from its own key, ``fold_in(fold_in(seed, leaf), layer)``, so the
served pytree (all layers stacked, bfloat16, made on the device in one
jitted call) and the reference (one layer at a time, float32 of the same
bfloat16 values) hold the same numbers without the reference reading
anything the program holds.
"""
from __future__ import annotations

import json
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent
SERVED_DTYPE = jnp.bfloat16


def load_config(name: str, data: Path = BENCH) -> dict:
    return json.loads((data / "configs" / f"{name}.json").read_text())


def dims(spec: dict) -> dict:
    """The sizes the benchmark computes with, from the published keys."""
    d = spec["hidden_size"]
    H = spec["num_attention_heads"]
    return {
        "L": spec["num_hidden_layers"], "d": d, "H": H,
        "Hkv": spec["num_key_value_heads"], "D": spec.get("head_dim", d // H),
        "ff": spec["intermediate_size"], "V": spec["vocab_size"],
        "eps": spec["rms_norm_eps"], "theta": spec["rope_theta"],
        "tied": spec["tie_word_embeddings"],
        "qkv_bias": spec["model_type"] == "qwen2",
    }


def model_config(spec: dict):
    """The program's ``ModelConfig`` for this configuration."""
    from repro.config import ModelConfig

    m = dims(spec)
    if spec["hidden_act"] != "silu":
        raise ValueError(f"{spec['name']}: only the silu (SwiGLU) MLP is served")
    return ModelConfig(
        name=spec["name"], family="dense", citation=spec["source"],
        n_layers=m["L"], d_model=m["d"], n_heads=m["H"], n_kv_heads=m["Hkv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["D"],
        qkv_bias=m["qkv_bias"], rope_theta=float(m["theta"]),
        tie_embeddings=m["tied"], norm_eps=float(m["eps"]),
        param_dtype="bfloat16", activation_dtype="bfloat16")


def seed_key_data(seed: int) -> np.ndarray:
    """Threefry key data for any seed up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def _layer_leaves(m: dict) -> dict:
    """Per-layer leaves: name -> (shape, kind).  Kinds: 'proj' (std
    1/sqrt(fan_in)), 'scale' (1 + 0.1 N), 'bias' (0.02 N)."""
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


def _top_leaves(m: dict) -> dict:
    leaves = {"embed": ((m["V"], m["d"]), "embed"),
              "final_norm.scale": ((m["d"],), "scale")}
    if not m["tied"]:
        leaves["lm_head"] = ((m["d"], m["V"]), "proj")
    return leaves


def _draw(key, name: str, shape, kind: str, layer):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    key = jax.random.fold_in(key, layer)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "proj":
        w = z * (shape[0] ** -0.5)
    elif kind == "scale":
        w = 1.0 + 0.1 * z
    else:                                   # bias, embed
        w = 0.02 * z
    return w.astype(SERVED_DTYPE)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for name, v in flat.items():
        node = out
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = v
    return out


def _key(key_data):
    return jax.random.wrap_key_data(key_data, impl="threefry2x32")


def served_params(spec: dict, seed: int):
    """The program's parameter pytree, bfloat16, made on the device in one
    jitted call."""
    m = dims(spec)
    layer_leaves, top_leaves = _layer_leaves(m), _top_leaves(m)

    def make(key_data):
        key = _key(key_data)
        flat = {n: _draw(key, n, s, k, 0) for n, (s, k) in top_leaves.items()}
        layers = jnp.arange(m["L"], dtype=jnp.uint32)
        blocks = {n: jax.vmap(lambda i, n=n, s=s, k=k: _draw(key, n, s, k, i))(layers)
                  for n, (s, k) in layer_leaves.items()}
        out = _nest(flat)
        out["blocks"] = _nest(blocks)
        return out

    return jax.jit(make)(jnp.asarray(seed_key_data(seed)))


def _make_layer(spec_key: tuple):
    m = dict(spec_key)
    leaves = _layer_leaves(m)

    @jax.jit
    def make(key_data, layer):
        key = _key(key_data)
        return {n: _draw(key, n, s, k, layer).astype(jnp.float32)
                for n, (s, k) in leaves.items()}
    return make


def _make_top(spec_key: tuple):
    m = dict(spec_key)
    leaves = _top_leaves(m)

    @jax.jit
    def make(key_data):
        key = _key(key_data)
        return {n: _draw(key, n, s, k, 0).astype(jnp.float32)
                for n, (s, k) in leaves.items()}
    return make


class ReferenceWeights:
    """Float32 copies of the served weights, one layer at a time."""

    def __init__(self, spec: dict, seed: int):
        m = dims(spec)
        key = tuple(sorted(m.items()))
        self.dims = m
        self._key_data = jnp.asarray(seed_key_data(seed))
        self._layer = _make_layer(key)
        self._top = _make_top(key)

    def top(self) -> dict:
        return self._top(self._key_data)

    def layer(self, i: int) -> dict:
        return self._layer(self._key_data, jnp.uint32(i))
