"""Model configurations and weights, built from ``bench/configs/<name>.json``.

The configuration file holds the published sizes (the keys of the model's
public ``config.json``) and the engine's knobs.  Its ``model_type`` names the module of its
architecture, ``bench/families/<model_type>.py`` (``family``), which
builds the program's ``ModelConfig``, names the leaves and runs the
reference's forward pass; so an edit to the program's own config modules
does not move the benchmark, and a new architecture is one new file.

Weights are random and drawn from ``--seed``.  Every leaf of every layer
comes from its own key, ``fold_in(fold_in(seed, leaf), layer)``, so the
served pytree (all layers stacked, bfloat16, made on the device in one
jitted call) and the reference (one layer at a time, float32 of the same
bfloat16 values) hold the same numbers without the reference reading
anything the program holds.
"""
from __future__ import annotations

import importlib
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


def family(spec: dict):
    """The module of the configuration's architecture,
    ``bench/families/<model_type>.py``."""
    name = spec["model_type"]
    path = BENCH / "families" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"{spec['name']}: model_type {name!r} has no family "
                         f"module {path.relative_to(BENCH.parent)}")
    return importlib.import_module(f"families.{name}")


def dims(spec: dict) -> dict:
    """The sizes the benchmark computes with, from the published keys."""
    return family(spec).dims(spec)


def model_config(spec: dict):
    """The program's ``ModelConfig`` for this configuration."""
    fam = family(spec)
    return fam.model_config(spec, fam.dims(spec))


def seed_key_data(seed: int) -> np.ndarray:
    """Threefry key data for any seed up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


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


def served_params(spec: dict, seed: int, mesh=None):
    """The program's parameter pytree, bfloat16, made on the device in one
    jitted call.  With a ``mesh``, each leaf is made where the engine
    places it (``repro.launch.sharding.params_pspecs`` under the serving
    rules), so no device holds more than its share."""
    fam = family(spec)
    m = fam.dims(spec)
    layer_leaves, top_leaves = fam.layer_leaves(m), fam.top_leaves(m)

    def make(key_data):
        key = _key(key_data)
        flat = {n: _draw(key, n, s, k, 0) for n, (s, k) in top_leaves.items()}
        layers = jnp.arange(m["L"], dtype=jnp.uint32)
        blocks = {n: jax.vmap(lambda i, n=n, s=s, k=k: _draw(key, n, s, k, i))(layers)
                  for n, (s, k) in layer_leaves.items()}
        out = _nest(flat)
        out["blocks"] = _nest(blocks)
        return out

    key_data = jnp.asarray(seed_key_data(seed))
    if mesh is None:
        return jax.jit(make)(key_data)
    from repro.launch.sharding import SERVING_LOGICAL_MAP, params_pspecs

    shardings = params_pspecs(mesh, jax.eval_shape(make, key_data),
                              SERVING_LOGICAL_MAP)
    return jax.jit(make, out_shardings=shardings)(key_data)


def _make_leaves(leaves: dict):
    """A jitted draw of ``leaves`` in float32 from the key data and a layer
    index."""
    @jax.jit
    def make(key_data, layer):
        key = _key(key_data)
        return {n: _draw(key, n, s, k, layer).astype(jnp.float32)
                for n, (s, k) in leaves.items()}
    return make


class ReferenceWeights:
    """Float32 copies of the served weights, one layer at a time."""

    def __init__(self, spec: dict, seed: int):
        self.family = family(spec)
        self.dims = m = self.family.dims(spec)
        self._key_data = jnp.asarray(seed_key_data(seed))
        self._layer = _make_leaves(self.family.layer_leaves(m))
        self._top = _make_leaves(self.family.top_leaves(m))

    def top(self) -> dict:
        return self._top(self._key_data, jnp.uint32(0))

    def layer(self, i: int) -> dict:
        return self._layer(self._key_data, jnp.uint32(i))
