"""Sharding rules: divisibility-aware logical->physical mapping, the
per-preset parameter specs, and the mesh-sharded serving engine.

The sharded-engine tests run wherever >= 2 devices are visible — the CI
``sharded-smoke`` lane forces a 4-device CPU host platform via
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — and skip on the
single-device tier-1 run (where the mesh-keyed jit-cache and accounting
tests still execute against a trivial 1-device mesh)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_config, get_reduced_config
from repro.launch import sharding as SH
from repro.launch.mesh import make_serving_mesh
from repro.models import pspec as PS
from repro.models import transformer as T
from repro.serving.batching import Request
from repro.serving.engine import ContinuousEngine
from repro.serving.paging import BlockAllocator, per_device_pool_stats
from repro.serving.scheduler import PreemptiveScheduler


@pytest.fixture
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


def _abstract_mesh(shape, names):
    """jax >= 0.4.38 takes (shape, names); 0.4.37 takes (name, size) pairs."""
    try:
        return jax.sharding.AbstractMesh(shape, names)
    except TypeError:
        return jax.sharding.AbstractMesh(tuple(zip(names, shape)))


def test_pspec_divisibility_fallback(mesh):
    with PS.mesh_rules(mesh):
        # model axis size 1 divides everything -> sharded entries appear
        spec = PS.pspec_for((16, 15), [None, "model"])
        assert spec == P(None, "model")
    big = _abstract_mesh((1, 16), ("data", "model"))
    with PS.mesh_rules(big):
        # 15 heads cannot shard over model=16 -> dropped
        spec = PS.pspec_for((4, 15), [None, "model"])
        assert spec == P(None, None)
        # 32 can
        spec = PS.pspec_for((4, 32), [None, "model"])
        assert spec == P(None, "model")


def test_pspec_duplicate_axis_guard():
    big = _abstract_mesh((2, 2), ("data", "model"))
    with PS.mesh_rules(big, {"a": ("data", "model"), "b": ("data",)}):
        spec = PS.pspec_for((4, 4), ["a", "b"])
        # "b" would reuse "data" -> dropped
        assert spec == P(("data", "model"), None)


def test_shard_noop_without_rules():
    PS.set_mesh_rules(None)
    x = jax.numpy.ones((4, 4))
    assert PS.shard(x, "batch", "model") is x


def test_param_logical_axes_rules():
    import jax.tree_util as jtu
    cfg = get_config("qwen3-moe-30b-a3b")
    from repro.launch.specs import params_specs
    shapes = params_specs(cfg, max_seq=64)
    flat = jtu.tree_flatten_with_path(shapes)[0]
    by_name = {}
    for path, leaf in flat:
        names = SH._path_names(path)
        by_name["/".join(names)] = (path, leaf)
    # expert weights: (L, E, d, f) -> expert on dim 1
    for key, (path, leaf) in by_name.items():
        la = SH.param_logical_axes(path, leaf)
        if key.endswith("moe/w_gate"):
            assert la == [None, "expert", "fsdp", None]
        if key.endswith("moe/w_down"):
            assert la == [None, "expert", None, "fsdp"]
        if key.endswith("attn/w_o"):
            assert la == [None, "model", "fsdp"]
        if key.endswith("router"):
            assert la == [None] * leaf.ndim       # replicated
        if key == "embed":
            assert la == ["model", "fsdp"]


@pytest.mark.parametrize("preset", list(SH.SHARDING_PRESETS))
def test_presets_produce_valid_specs(preset, mesh):
    cfg = get_config("smollm-360m")
    from repro.launch.specs import params_specs
    shapes = params_specs(cfg, max_seq=64)
    specs = SH.params_pspecs(mesh, shapes, SH.SHARDING_PRESETS[preset])
    # every leaf got a NamedSharding on the mesh
    for s in jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "spec")):
        assert s.mesh.shape == mesh.shape


def test_cache_axes_mqa_seq_sharding():
    """granite (kv=1): cache heads cannot shard over model=16, the rule
    falls to sequence sharding."""
    cfg = get_config("granite-34b")
    import jax.numpy as jnp
    leaf = jax.ShapeDtypeStruct((88, 8, 4096, 1, 128), jnp.bfloat16)

    class E:   # fake path entry
        def __init__(self, k):
            self.key = k
    la = SH.cache_logical_axes(cfg, (E("blocks"), E("k")), leaf)
    assert la == [None, "batch", "seq", None, None]
    cfg2 = get_config("zamba2-7b")     # kv=32 -> heads shard
    la2 = SH.cache_logical_axes(cfg2, (E("shared_attn"), E("k")), leaf)
    assert la2 == [None, "batch", None, "model", None]


def test_mqa_serving_placement_over_four():
    """granite-20b (one KV head) on the 1 x 4 serving mesh: the k/v
    projections and their biases are whole on every device, as is the
    paged pool they fill (no per-layer gather of a column-split head);
    the query heads split 12 to a device, and w_q, w_o, w_up, w_down and
    the vocabulary of the (tied) embedding are quartered."""
    cfg = get_config("granite-20b")
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(lambda: T.init_paged_cache(cfg, 17, 16))
    mesh = _abstract_mesh((1, 4), ("data", "model"))
    with PS.mesh_rules(mesh, SH.SERVING_LOGICAL_MAP):
        specs = {"/".join(SH._path_names(path)): PS.pspec_for(
            leaf.shape, SH.param_logical_axes(path, leaf, cfg))
            for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)}
        old_w_k = PS.pspec_for(shapes["blocks"]["attn"]["w_k"].shape,
                               [None, "fsdp", "model"])
        pool_specs = [PS.pspec_for(leaf.shape, SH.paged_cache_logical_axes(
            cfg, path, leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(pool)]
    quartered = {"blocks/attn/w_q": P(None, None, "model"),
                 "blocks/attn/w_o": P(None, "model", None),
                 "blocks/mlp/w_up": P(None, None, "model"),
                 "blocks/mlp/w_down": P(None, "model", None),
                 "embed": P("model", None)}
    for name, spec in specs.items():
        assert spec == quartered.get(name, P(*[None] * len(spec))), name
    assert shapes["blocks"]["attn"]["w_q"].shape[-1] // 4 == 12 * 128
    # without the config the column rule would cut the one head in four
    assert old_w_k == P(None, None, "model")
    assert specs["blocks/attn/w_k"] == P(None, None, None)
    assert pool_specs == [P(None, None, None, None, None)] * 2


# ---------------------------------------------------------------------------
# mesh-sharded serving engine
# ---------------------------------------------------------------------------

needs_multi = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="sharded serving needs >= 2 devices (run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def _serving_cfg(arch: str):
    """Reduced fp32 config whose KV heads divide a 4-way model axis
    (fp32 so sharded contractions — which reorder reductions — stay
    bit-identical with the single-device run)."""
    over = dict(param_dtype="float32", activation_dtype="float32")
    if arch == "smollm-360m":
        over.update(n_heads=8, n_kv_heads=4, head_dim=32)
    elif arch == "qwen3-moe-30b-a3b":
        over.update(n_kv_heads=4)
    return get_reduced_config(arch).with_(**over)


def _trace(cfg, n=6):
    r = np.random.default_rng(3)
    lens = [5, 17, 9, 30, 12, 3][:n]
    news = [8, 6, 12, 4, 10, 16][:n]
    return [Request(prompt=r.integers(0, cfg.vocab_size,
                                      size=s).astype(np.int32),
                    max_new=m, rid=i, arrival_t=float(i // 2))
            for i, (s, m) in enumerate(zip(lens, news))]


_ENGINE_KW = dict(n_slots=3, max_seq=64, page_size=8,
                  prefill_budget_tokens=16)


def _params_for(cfg):
    return T.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)


def _sweep(arch: str, n=6):
    """Run the same trace through a single-device and a mesh-sharded
    engine; return (single results, sharded results, sharded engine)."""
    cfg = _serving_cfg(arch)
    params = _params_for(cfg)
    e0 = ContinuousEngine(cfg, params, **_ENGINE_KW)
    out0 = e0.run(_trace(cfg, n))
    e1 = ContinuousEngine(cfg, params, mesh=make_serving_mesh(),
                          **_ENGINE_KW)
    out1 = e1.run(_trace(cfg, n))
    return out0, out1, e1


def _assert_token_exact(out0, out1):
    assert out0.keys() == out1.keys()
    for rid in out0:
        np.testing.assert_array_equal(out0[rid].tokens, out1[rid].tokens)


def test_jit_cache_keyed_on_mesh():
    """A sharded and an unsharded engine serving the SAME config must
    not share jitted callables (the sharded trace bakes
    with_sharding_constraint ops in); same-mesh engines must."""
    cfg = _serving_cfg("smollm-360m")
    params = _params_for(cfg)
    mesh = make_serving_mesh()          # trivial (1, 1) on tier-1: still
    #                                     a distinct cache key vs None
    plain = ContinuousEngine(cfg, params, **_ENGINE_KW)
    sharded = ContinuousEngine(cfg, params, mesh=mesh, **_ENGINE_KW)
    sharded2 = ContinuousEngine(cfg, params, mesh=mesh, **_ENGINE_KW)
    assert plain._decode is not sharded._decode
    assert plain._chunk is not sharded._chunk
    assert plain._prefill is not sharded._prefill
    assert sharded._decode is sharded2._decode
    assert sharded._chunk is sharded2._chunk


@needs_multi
def test_sharded_dense_token_exact():
    out0, out1, eng = _sweep("smollm-360m")
    _assert_token_exact(out0, out1)
    s = eng.kv_cache_stats()
    n_dev = len(jax.devices())
    assert s["n_kv_shards"] == n_dev
    assert s["kv_bytes_per_device"] * n_dev == s["kv_cache_bytes"]
    # page axes are never cut: per-device ledger IS the global ledger
    assert s["peak_pages_in_use_per_device"] == s["peak_pages_in_use"]
    # the per-device byte claim against the REAL placement: one
    # addressable shard of each pool leaf
    dev0 = jax.devices()[0]
    real = sum(
        next(sh.data.size for sh in leaf.addressable_shards
             if sh.device == dev0) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(eng.slots.cache))
    assert real == s["kv_bytes_per_device"]


@pytest.mark.slow
@needs_multi
def test_sharded_moe_token_exact():
    """Expert-parallel MoE serving: experts split over the model axis,
    per-device dispatch slices replacing the global scatter — still
    token-exact with single-device."""
    out0, out1, eng = _sweep("qwen3-moe-30b-a3b", n=4)
    _assert_token_exact(out0, out1)
    s = eng.kv_cache_stats()
    E = eng.cfg.moe.n_experts
    assert s["n_expert_shards"] > 1
    assert s["experts_per_device"] * s["n_expert_shards"] == E


@pytest.mark.slow
@needs_multi
def test_sharded_mla_token_exact():
    """MLA paged serving with the latent rank sharded over the mesh."""
    out0, out1, eng = _sweep("deepseek-v3-671b", n=4)
    _assert_token_exact(out0, out1)
    assert eng.kv_cache_stats()["n_kv_shards"] > 1


@needs_multi
def test_sharded_preempt_spill_resume_token_exact(tmp_path):
    """Preempt -> spill -> resume on the SHARDED engine: snapshots
    device_get token-exact global pages off the head-sharded pool and
    graft back under the mesh; a mid-flight checkpoint restores into a
    fresh sharded engine; a mesh-shape mismatch is refused."""
    cfg = _serving_cfg("smollm-360m")
    params = _params_for(cfg)
    mesh = make_serving_mesh()
    prompt = np.arange(1, 15, dtype=np.int32)
    kw = dict(n_slots=2, max_seq=64, page_size=8, prefill_budget_tokens=4)
    ref = ContinuousEngine(cfg, params, **kw)
    want = list(ref.run([Request(prompt=prompt.copy(),
                                 max_new=6)]).values())[0].tokens

    eng = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    sched = PreemptiveScheduler(eng)
    probe = Request(prompt=prompt.copy(), max_new=6)
    sched.submit(probe)
    sched.step(); sched.step()          # admit + land the first chunks
    (slot,) = [s for s in eng.slots.active_slots()
               if eng.slots.states[s].request.rid == probe.rid]
    sched.preempt(slot)
    sched.submit(Request(prompt=prompt[:5].copy(), max_new=3))
    sched.step(); sched.step()          # filler recycles released pages
    res = sched.run()
    np.testing.assert_array_equal(res[probe.rid].tokens, want)
    assert res[probe.rid].n_preemptions == 1

    # checkpoint mid-flight, restore into a clone of the sharded engine
    eng2 = ContinuousEngine(cfg, params, mesh=mesh, **kw)
    sched2 = PreemptiveScheduler(eng2)
    p2 = Request(prompt=prompt.copy(), max_new=6)
    sched2.submit(p2)
    for _ in range(4):
        sched2.step()
    path = str(tmp_path / "sharded.ckpt")
    sched2.checkpoint(path)
    sched3 = PreemptiveScheduler(eng2.clone_fresh())
    sched3.restore(path)
    np.testing.assert_array_equal(sched3.run()[p2.rid].tokens, want)

    # an unsharded engine must refuse the sharded checkpoint
    with pytest.raises(RuntimeError, match="mesh"):
        PreemptiveScheduler(
            ContinuousEngine(cfg, params, **kw)).restore(path)


def test_per_device_pool_accounting_matches_ledger():
    """Hypothesis invariant: the per-device pool view always agrees
    with the global BlockAllocator ledger — identical page counts
    (page axes are never sharded) and bytes that multiply back to the
    global total when the head dim divides."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(1, 8), st.integers(1, 64), st.data())
    @settings(max_examples=40, deadline=None)
    def run(n_shards, unit, data):
        a = BlockAllocator(24)
        live = []
        for _ in range(data.draw(st.integers(0, 40))):
            if a.available() > 0 and data.draw(st.booleans()):
                a.reserve(1)
                live.extend(a.alloc(1))
            elif live:
                i = data.draw(st.integers(0, len(live) - 1))
                a.release([live.pop(i)])
        page_bytes = unit * n_shards           # divisible head dim
        per_dev = a.n_pages * page_bytes // n_shards
        s = per_device_pool_stats(a, n_shards=n_shards,
                                  kv_bytes_per_device=per_dev)
        assert s["kv_bytes_per_device"] * n_shards == a.n_pages * page_bytes
        assert s["pages_in_use_per_device"] == a.in_use
        assert s["peak_pages_in_use_per_device"] == a.peak_in_use
        assert a.in_use == a.n_pages - len(a._free)
        assert a.peak_in_use >= a.in_use

    run()
