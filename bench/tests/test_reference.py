"""The plain float32 reference against the program's own forward pass, and
the weights the reference draws against the ones the program serves."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model import ReferenceWeights, load_config, model_config, served_params
from reference import logits_at, served_rows
from tests_common import DATA

CONFIGS = ["tiny-gqa", "tiny-mha-bias"]


@pytest.mark.parametrize("name", CONFIGS)
def test_layer_weights_match_served(name):
    spec = load_config(name, DATA)
    seed = 2 ** 33 + 17
    served = served_params(spec, seed)
    ref = ReferenceWeights(spec, seed)
    for i in range(spec["num_hidden_layers"]):
        w = ref.layer(i)
        for key, v in w.items():
            node = served["blocks"]
            for part in key.split("."):
                node = node[part]
            np.testing.assert_array_equal(np.asarray(node[i], np.float32),
                                          np.asarray(v))
    top = ref.top()
    np.testing.assert_array_equal(np.asarray(served["embed"], np.float32),
                                  np.asarray(top["embed"]))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_forward(name):
    from repro.models import transformer as T

    spec = load_config(name, DATA)
    seed = 5
    cfg = model_config(spec).with_(param_dtype="float32",
                                   activation_dtype="float32")
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          served_params(spec, seed))
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, spec["vocab_size"], n, dtype=np.int32)
            for n in (37, 50)]
    rows = [np.arange(len(s)) for s in seqs]
    V = spec["vocab_size"]
    probes = [np.tile(np.arange(V, dtype=np.int32), (len(s), 1)) for s in seqs]
    got = logits_at(spec, seed, seqs, rows, probes, pad_to=64, batch=2)
    with jax.default_matmul_precision("highest"):
        want = np.concatenate([
            np.asarray(T.forward(params, cfg, {"tokens": jnp.asarray(s[None])},
                                 remat=False)[0][0], np.float32)
            for s in seqs])
    np.testing.assert_allclose(got["probe"], want, atol=2e-4, rtol=1e-4)
    np.testing.assert_array_equal(got["argmax"], want.argmax(-1))
    np.testing.assert_allclose(got["best"], want.max(-1), atol=2e-4)


def test_control_departs_from_reference():
    spec = load_config("tiny-gqa", DATA)
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, spec["vocab_size"], 60, dtype=np.int32)]
    rows = [np.arange(60)]
    probes = [np.zeros((60, 1), np.int32)]
    ref = logits_at(spec, 3, seqs, rows, probes, pad_to=64, batch=1)
    ctl = logits_at(spec, 3, seqs, rows, probes, pad_to=64, batch=1,
                    control=True)
    assert np.max(np.abs(ref["best"] - ctl["best"])) > 1e-3


def test_served_rows():
    seq, rows = served_rows(np.array([5, 6, 7]), np.array([8, 9]))
    np.testing.assert_array_equal(seq, [5, 6, 7, 8])
    np.testing.assert_array_equal(rows, [2, 3])
