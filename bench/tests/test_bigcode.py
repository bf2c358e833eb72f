"""The GPTBigCode family (``families/gpt_bigcode.py``) at a test size, on
the CPU: its leaves are the program's parameter pytree, its
``matmul_params`` the program's count of multiplied weights, and the
logits the program serves after chunked prefill and paged decode are the
float32 reference's; ``cell.run_cell`` comes out correct on one device and
on four (tensor-parallel, one KV head replicated), and the fp8 control
does not.  The four-device runs go in a subprocess, because the device
count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/test_bigcode.py <seed>
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH_DIR = Path(__file__).resolve().parents[1]
CONFIG = "tiny-bigcode"
ONE, FOUR = "tiny-bigcode.saturated", "tiny-bigcode.saturated-4chip"
BENCH = {
    "workloads": [
        {"name": ONE, "config": CONFIG, "traffic": "tiny-saturated", "chips": 1},
        {"name": FOUR, "config": CONFIG, "traffic": "tiny-saturated", "chips": 4},
    ],
    "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                   {"name": "itl_mean_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
SEEDS = [3, 2 ** 33 + 1]
# served bfloat16 against the float32 reference over two layers of width
# 64: rounding of weights, activations and the cache moves the logits by
# 0.0047-0.0066 (4 seeds, one device); positions read one row off move
# them by 0.42.  0.02 leaves three times the rounding and is far under that
LOGIT_ATOL = 0.02


def _run(name, seed, gaps=None):
    import cell
    from tests_common import DATA

    return cell.run_cell(BENCH, name, seed, 2.0, False,
                         t_start=time.perf_counter(), device=None, data=DATA,
                         gaps=gaps)


def _program_then_control(got):
    from reference import control_gaps, served_gaps

    def gaps(spec, s, samples, **kw):
        got["program"] = float(np.max(served_gaps(spec, s, samples, **kw)))
        return control_gaps(spec, s, samples, **kw)
    return gaps


def served_against_reference(chips: int, seed: int) -> dict:
    """Serve two prompts through the cell's engine (chunks of 32 tokens,
    then paged decode) and read, at every position that chose a token,
    the largest distance between the served logits and the float32
    reference's over the whole vocabulary."""
    import jax

    import cell
    from model import load_config
    from reference import logits_at
    from repro.serving.batching import Request
    from tests_common import DATA

    spec = load_config(CONFIG, DATA)
    eng = cell.build_engine(spec, seed, chips)
    rows = {}                      # rid -> [logits row per emitted token]
    decode, chunk = eng._decode, eng._run_chunk

    def on_chunk(toks, n_valid, pos_offset, bt):
        logits, cache = chunk(toks, n_valid, pos_offset, bt)
        st, = [s for s in eng.slots.states if s is not None
               and s.request.prefill_pos == pos_offset and np.array_equal(
                   s.request.prompt[pos_offset:pos_offset + n_valid],
                   toks[0, :n_valid])]
        if pos_offset + n_valid == len(st.request.prompt):
            rows.setdefault(st.request.rid, []).append(
                np.asarray(logits[0, n_valid - 1], np.float32))
        return logits, cache

    def on_decode(p, c, t, pos, bt):
        logits, cache = decode(p, c, t, pos, bt)
        for i, st in enumerate(eng.slots.states):
            if st is not None and st.phase == "decode":
                rows[st.request.rid].append(np.asarray(logits[i, 0],
                                                       np.float32))
        return logits, cache

    eng._run_chunk, eng._decode = on_chunk, on_decode
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, spec["vocab_size"], n,
                                        dtype=np.int32), max_new=m)
            for n, m in ((45, 9), (70, 5))]
    out = eng.run(reqs)
    seqs, at, served = [], [], []
    V = spec["vocab_size"]
    for r in reqs:
        toks = out[r.rid].tokens
        seqs.append(np.concatenate([r.prompt, toks[:-1]]))
        at.append(np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(toks)))
        served.append(np.stack(rows[r.rid][:len(toks)]))
    ref = logits_at(spec, seed, seqs, at,
                    [np.tile(np.arange(V, dtype=np.int32), (len(a), 1))
                     for a in at], pad_to=128, batch=2)["probe"]
    w_k = eng.params["blocks"]["attn"]["w_k"]
    return {"max_abs": float(np.max(np.abs(np.concatenate(served) - ref))),
            "rows": int(ref.shape[0]),
            "w_k_whole": [int(s.data.size) == w_k.size
                          for s in w_k.addressable_shards],
            "devices": len(jax.devices())}


def test_family_without_module_names_its_file():
    """A configuration whose family has no module fails at once, naming
    the file it looked for."""
    import model
    from tests_common import DATA

    spec = dict(model.load_config(CONFIG, DATA), model_type="no_such_family")
    for fn in (model.family, model.dims, model.model_config):
        with pytest.raises(ValueError,
                           match=r"bench/families/no_such_family\.py"):
            fn(spec)


def test_missing_published_key_is_named():
    import model
    from tests_common import DATA

    spec = model.load_config(CONFIG, DATA)
    del spec["n_inner"]
    with pytest.raises(ValueError, match=r"no n_inner"):
        model.dims(spec)


def test_leaves_are_the_program_pytree():
    import jax

    from model import load_config, model_config, served_params
    from repro.models import transformer as T
    from tests_common import DATA

    spec = load_config(CONFIG, DATA)
    cfg = model_config(spec)
    assert (cfg.norm_kind, cfg.position_kind, cfg.n_kv_heads) == \
        ("layernorm", "learned", 1)
    assert cfg.attn_out_bias and cfg.qkv_bias and not cfg.gelu_approximate

    def paths(tree):
        return {jax.tree_util.keystr(p): x.shape
                for p, x in jax.tree_util.tree_leaves_with_path(tree)}

    served = paths(jax.eval_shape(lambda: served_params(spec, 1)))
    program = paths(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    assert served == program


def test_matmul_params_is_the_programs_count():
    import jax

    from families import gpt_bigcode
    from model import dims, load_config, model_config
    from repro.models import transformer as T
    from tests_common import DATA

    spec = load_config(CONFIG, DATA)
    m = dims(spec)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0),
                                                  model_config(spec)))
    # every projection matrix, and the token table once as the tied head;
    # the position table is looked up and multiplies nothing
    mult = sum(int(np.prod(x.shape)) for p, x in
               jax.tree_util.tree_leaves_with_path(shapes)
               if p[-1].key.startswith("w_") or p[-1].key == "embed")
    assert m["matmul_params"] == mult
    # L=2, d=64, H=4, Hkv=1, D=16, ff=128, V=256: q 64*64 + k,v 2*64*16
    # + o 64*64 + up, down 2*64*128 = 26624 a layer
    assert gpt_bigcode.matmul_params(m) == 2 * 26624 + 64 * 256


def test_served_logits_match_reference_on_one_device():
    out = served_against_reference(1, SEEDS[1])
    assert out["rows"] == 14
    assert out["max_abs"] < LOGIT_ATOL, out


@pytest.mark.parametrize("seed", SEEDS)
def test_one_device_run_is_correct_and_control_is_not(seed):
    res = _run(ONE, seed)
    assert res["correct"], res["checks"]
    got = {}
    ctrl = _run(ONE, seed, _program_then_control(got))
    assert ctrl["correct"] is False
    assert ctrl["checks"]["max_logit_gap"]["value"] > \
        ctrl["checks"]["max_logit_gap"]["limit"] >= got["program"]


def test_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, str(SEEDS[1])], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert out["control_correct"] is False
    assert out["control_gap"] > out["limit"] >= out["program_gap"]
    served = out["served"]
    assert served["devices"] == 4 and served["w_k_whole"] == [True] * 4
    assert served["max_abs"] < LOGIT_ATOL, served


def main(seed: int) -> None:
    res = _run(FOUR, seed)
    got = {}
    ctrl = _run(FOUR, seed, _program_then_control(got))
    print(json.dumps({
        "correct": res["correct"], "checks": res["checks"],
        "control_correct": ctrl["correct"],
        "control_gap": ctrl["checks"]["max_logit_gap"]["value"],
        "limit": ctrl["checks"]["max_logit_gap"]["limit"],
        "program_gap": got["program"],
        "served": served_against_reference(4, seed)}))


if __name__ == "__main__":
    sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR / "tests"),
                    str(BENCH_DIR.parent / "src")]
    main(int(sys.argv[1]))
