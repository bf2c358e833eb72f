"""Architectures live in ``bench/families/<model_type>.py``: the weights and
the reference of the llama and qwen2 families are bit for bit what they
were before the split, and a configuration of an architecture that has no
module fails with the name of the file it needs."""
import hashlib

import jax
import numpy as np
import pytest

import model
from families import llama
from model import load_config, served_params
from reference import logits_at
from tests_common import DATA

SEED = 2 ** 33 + 29

# sha256 (first 16 hex digits) of the served weights, the reference's
# logits and the fp8 control's logits at SEED, computed on the CPU backend
# (jax 0.9.0) from the code before the family modules existed
PINNED = {
    "tiny-gqa": {"weights": "da6501fc40e0e4ce", "reference": "f7f74fab36f5a60b",
                 "control": "73cb44ef4a2efc2e"},
    "tiny-mha-bias": {"weights": "98c1c78ac78f259b",
                      "reference": "18f88458b56f2289",
                      "control": "4108aaec08394ef1"},
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for name, a in arrays:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_and_reference_unchanged(name):
    spec = load_config(name, DATA)
    params = served_params(spec, SEED)
    got = {"weights": _digest(
        (jax.tree_util.keystr(p), np.asarray(x.astype(np.float32)))
        for p, x in jax.tree_util.tree_leaves_with_path(params))}
    rng = np.random.default_rng(7)
    V = spec["vocab_size"]
    seqs = [rng.integers(0, V, n, dtype=np.int32) for n in (41, 64, 9)]
    rows = [np.arange(len(s)) for s in seqs]
    probes = [np.tile(np.arange(V, dtype=np.int32), (len(s), 1)) for s in seqs]
    for control in (False, True):
        out = logits_at(spec, SEED, seqs, rows, probes, pad_to=64, batch=2,
                        control=control)
        got["control" if control else "reference"] = _digest(sorted(out.items()))
    assert got == PINNED[name]


def test_unknown_model_type_names_missing_file():
    spec = dict(load_config("tiny-gqa", DATA), model_type="gpt_bigcode")
    for fn in (model.dims, model.model_config,
               lambda s: served_params(s, 1)):
        with pytest.raises(ValueError, match=r"bench/families/gpt_bigcode\.py"):
            fn(spec)


@pytest.mark.parametrize("name,bias", [("tiny-gqa", False),
                                       ("tiny-mha-bias", True)])
def test_family_by_model_type(name, bias):
    spec = load_config(name, DATA)
    fam = model.family(spec)
    assert fam.__name__ == f"families.{spec['model_type']}"
    m = model.dims(spec)
    assert m["qkv_bias"] is bias
    assert model.model_config(spec).qkv_bias is bias
    assert ("attn.b_q" in fam.layer_leaves(m)) is bias
    # biases add no multiplied weights
    assert m["matmul_params"] == llama.matmul_params(m)


def test_matmul_params():
    # L=2, d=8, H=4, Hkv=2, D=2, ff=16, V=10; per layer: q 8*8 + k,v 2*8*4
    # + o 8*8 + mlp 3*8*16 = 64+64+64+384
    m = {"L": 2, "d": 8, "H": 4, "Hkv": 2, "D": 2, "ff": 16, "V": 10}
    assert llama.matmul_params(m) == 2 * 576 + 80
