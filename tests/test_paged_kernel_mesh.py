"""The Pallas paged kernel under the serving mesh: inside ``shard_map``
each device runs it on its own query heads against its own shard of the
pool.  Against the gather path on the same sharded operands, on four
host devices (interpret mode), for a replicated multi-query pool, a
head-sharded MHA pool, and a KV group spread over devices (every device
then runs the whole batch); and through ``ContinuousEngine`` on the
1 x 4 serving mesh for a GPTBigCode block (LayerNorm, learned positions,
biases, one KV head), token for token with one device.

The device count is fixed when JAX starts, so the checks run in a
subprocess:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python tests/test_paged_kernel_mesh.py <case>
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

# (H, Hkv, D): how the model axis meets the pool
ATTN_CASES = {
    "mqa-replicated-pool": (8, 1, 32),
    "mha-head-sharded-pool": (8, 8, 32),
    "gqa-group-spans-devices": (6, 2, 32),
}


def _attention(case: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import ModelConfig
    from repro.kernels import ops
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.sharding import (SERVING_LOGICAL_MAP, paged_cache_pspecs,
                                       params_pspecs)
    from repro.models import attention as A
    from repro.models import transformer as T
    from repro.models.pspec import mesh_rules

    H, Hkv, D = ATTN_CASES[case]
    cfg = ModelConfig(name=case, family="dense", citation="test", n_layers=1,
                      d_model=64, n_heads=H, n_kv_heads=Hkv, d_ff=128,
                      vocab_size=64, head_dim=D, qkv_bias=True,
                      attn_out_bias=True, param_dtype="float32",
                      activation_dtype="float32")
    mesh = make_serving_mesh(4)
    B, ps, max_bt = 5, 8, 6
    n_pages = B * max_bt + 1
    key = jax.random.PRNGKey(7)
    attn = A.init_attention(key, cfg)
    attn = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(
        jax.random.fold_in(key, a.size), a.shape, a.dtype), attn)
    ks = jax.random.split(key, 3)
    x = jax.random.normal(ks[0], (B, 1, cfg.d_model), jnp.float32)
    pool = T.init_paged_cache(cfg, n_pages, ps)["blocks"]
    pool = {n: jax.random.normal(k, v.shape[1:], v.dtype)
            for (n, v), k in zip(sorted(pool.items()), ks[1:])}
    rng = np.random.default_rng(3)
    bt = rng.permutation(np.arange(1, n_pages)).reshape(B, max_bt)
    pos = np.array([0, 5, 17, 40, 47], np.int32)      # kv_len 1 .. 48

    p_sh = params_pspecs(mesh, {"attn": attn}, SERVING_LOGICAL_MAP,
                         cfg)["attn"]
    c_sh = paged_cache_pspecs(mesh, cfg, {"k": pool["k"][None],
                                          "v": pool["v"][None]},
                              SERVING_LOGICAL_MAP)
    c_sh = {n: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        *s.spec[1:])) for n, s in c_sh.items()}
    attn = jax.device_put(attn, p_sh)
    pool = jax.device_put(pool, c_sh)

    out = {}
    for kernel in (True, False):
        def step(p, x, pk, pv, pos, bt):      # a new function: a new trace
            with mesh_rules(mesh, SERVING_LOGICAL_MAP):
                return A.paged_attention_decode(p, cfg, x, pk, pv, pos, bt)

        ops.paged_kernel_ok = lambda k=kernel: k
        args = (attn, x, pool["k"], pool["v"], jnp.asarray(pos),
                jnp.asarray(bt))
        o, pk, pv = jax.jit(step)(*args)
        out[kernel] = [np.asarray(a) for a in (o, pk, pv)]
        text = jax.jit(step).lower(*args).as_text()
        out[f"shard_map_{kernel}"] = "sdy.manual_computation" in text
    return {
        "max_abs_diff": float(np.max(np.abs(out[True][0] - out[False][0]))),
        "pools_equal": all(np.array_equal(a, b) for a, b in
                           zip(out[True][1:], out[False][1:])),
        "kernel_manual": out["shard_map_True"],
        "gather_manual": out["shard_map_False"],
        "pool_spec": str(pool["k"].sharding.spec),
        "w_k_spec": str(attn["w_k"].sharding.spec),
    }


def _engine() -> dict:
    import jax
    import numpy as np

    from repro.config import get_reduced_config
    from repro.kernels import ops
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as T
    from repro.serving.batching import Request
    from repro.serving.engine import ContinuousEngine

    ops.paged_kernel_ok = lambda: True
    cfg = get_reduced_config("granite-20b").with_(
        n_heads=8, head_dim=32, d_model=256, param_dtype="float32",
        activation_dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)
    kw = dict(n_slots=3, max_seq=64, page_size=8, prefill_budget_tokens=16)

    def reqs():
        rng = np.random.default_rng(5)
        return [Request(prompt=rng.integers(0, cfg.vocab_size, n).astype(
            np.int32), max_new=m, rid=i)
            for i, (n, m) in enumerate([(5, 8), (23, 6), (11, 12), (30, 4)])]

    one = ContinuousEngine(cfg, params, **kw).run(reqs())
    eng = ContinuousEngine(cfg, params, mesh=make_serving_mesh(4), **kw)
    four = eng.run(reqs())
    return {
        "same_tokens": all(np.array_equal(one[r].tokens, four[r].tokens)
                           for r in one) and one.keys() == four.keys(),
        "n_requests": len(four),
        "pool_replicated": all(str(x.sharding.spec).count("model") == 0
                               for x in jax.tree.leaves(eng.slots.cache)),
        "w_k_fractions": [float(np.prod(s.data.shape) /
                                eng.params["blocks"]["attn"]["w_k"].size)
                          for s in eng.params["blocks"]["attn"]["w_k"]
                          .addressable_shards],
    }


def _run(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, case], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(ATTN_CASES))
def test_kernel_under_shard_map_matches_gather_path(case):
    out = _run(case)
    # float32 throughout: the kernel's online softmax against the gather
    # path's one-pass softmax differ by summation order only
    assert out["max_abs_diff"] < 1e-5, out
    assert out["pools_equal"], out
    assert out["kernel_manual"] and not out["gather_manual"], out
    H, Hkv, _ = ATTN_CASES[case]
    if Hkv % 4:
        # one KV head (or a group that 4 does not divide): the pool and
        # the k/v projections that fill it are whole on every device
        assert "model" not in out["pool_spec"], out
        assert "model" not in out["w_k_spec"], out
    else:
        assert "model" in out["pool_spec"], out
        assert "model" in out["w_k_spec"], out


def test_gpt_bigcode_engine_on_four_devices_matches_one():
    out = _run("engine")
    assert out["same_tokens"], out
    assert out["n_requests"] == 4
    assert out["pool_replicated"], out
    assert out["w_k_fractions"] == [1.0] * 4, out


if __name__ == "__main__":
    case = sys.argv[1]
    print(json.dumps(_engine() if case == "engine" else _attention(case)))
