"""Ahead-of-time compiles for a described TPU v5e chip.

Interpret mode runs a Pallas kernel's body in Python and cannot see
what the chip's compiler refuses (block shapes that break the (8, 128)
tiling rule, layouts Mosaic cannot lower).  These tests hand the real
compiler the served widths through ``jax.experimental.topologies``:
nothing runs and nothing is timed; a test passes when the program
compiles and the kernel is in it.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.config import get_config
from repro.configs import tiansuan_pair as TP
from repro.launch.sharding import (SERVING_LOGICAL_MAP, paged_cache_pspecs,
                                   params_pspecs)
from repro.models.pspec import mesh_rules
from repro.kernels import ops
from repro.kernels.conf_gate import confidence_gate_kernel
from repro.kernels.paged_decode_attention import paged_decode_attention_kernel
from repro.models import transformer as T
from repro.serving.paging import default_pool_pages, pages_for

PALLAS_OP = "tpu_custom_call"

# (B, H, Hkv, D) of the configurations the paged kernel serves
PAGED_WIDTHS = {
    "smollm-360m": (8, 15, 5, 64),
    "tiansuan-ground": (8, 8, 4, 48),
    "tiansuan-onboard": (8, 4, 2, 48),
    "qwen1.5-4b": (8, 20, 20, 128),
    # 48 query heads over one KV head: all on one chip, or 12 on each of
    # four under the serving mesh
    "granite-20b": (8, 48, 1, 128),
    "granite-20b-per-chip": (8, 12, 1, 128),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a described chip is written to the cache but cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch,max_pages", [
    *[pytest.param(a, 32, id=a) for a in sorted(PAGED_WIDTHS)],
    # the served table width: max_seq 2048 over 16-position pages
    *[pytest.param(a, 128, id=f"{a}-128-pages") for a in sorted(PAGED_WIDTHS)],
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_decode_kernel_compiles(one_chip, arch, max_pages, dtype):
    B, H, Hkv, D = PAGED_WIDTHS[arch]
    ps, n_pages = 16, 193
    pool = _spec((n_pages, ps, Hkv, D), dtype, one_chip)
    compiled = paged_decode_attention_kernel.lower(
        _spec((B, H, D), dtype, one_chip), pool, pool,
        _spec((B, max_pages), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert PALLAS_OP in text
    # the op's name in a device trace, which paged_attn_roofline reads
    assert re.search(r"%paged_decode_attention_kernel\.\d+ = \S+ custom-call\(",
                     text)


def test_confidence_gate_kernel_compiles(one_chip):
    compiled = confidence_gate_kernel.lower(
        _spec((8, 49152), jnp.float32, one_chip)).compile()
    assert PALLAS_OP in compiled.as_text()


@pytest.mark.parametrize("cfg", [get_config("smollm-360m"), TP.GROUND],
                         ids=lambda c: c.name)
def test_paged_decode_step_compiles_with_kernel(one_chip, monkeypatch, cfg):
    """The whole jitted decode step of the continuous engine at full
    width, with the kernel gate steered as it reads on a TPU host: the
    compiled step must carry the Pallas kernel, not the gather path."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.paged_kernel_ok()
    n_slots, max_seq, ps = 8, 512, 16
    n_pages = default_pool_pages(n_slots, max_seq, ps) + 1

    def placed(tree):
        return jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    params = placed(jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), cfg, max_seq=max_seq)))
    cache = placed(jax.eval_shape(
        lambda: T.init_paged_cache(cfg, n_pages, ps)))
    step = jax.jit(lambda p, c, t, pos, bt: T.decode_step(
        p, cfg, c, t, pos, block_tables=bt))
    compiled = step.lower(
        params, cache, _spec((n_slots, 1), jnp.int32, one_chip),
        _spec((n_slots,), jnp.int32, one_chip),
        _spec((n_slots, pages_for(max_seq, ps)), jnp.int32, one_chip),
    ).compile()
    assert PALLAS_OP in compiled.as_text()


@pytest.fixture(scope="module")
def four_chips(topo, one_chip):
    """The serving mesh (1 x 4, every chip on "model") over the described
    host; ``one_chip`` holds the compilation cache off."""
    return Mesh([topo.devices[:4]], ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def test_granite_decode_step_compiles_per_chip_on_four(four_chips,
                                                       monkeypatch):
    """granite-20b's decode step at published widths on the 1 x 4 serving
    mesh, with the kernel gate as it reads on a TPU host: every chip runs
    the Pallas kernel on its 12 query heads against its replica of the
    pool (no gather path), the layers' all-reduces are in, and one step's
    arguments, outputs and temporaries fit a chip's 16 GiB."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("granite-20b")
    n_slots, ps, n_pages, max_bt = 48, 16, 3841, 128
    mesh = four_chips

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: _spec(a.shape, a.dtype, s),
                            tree, shardings)

    pshape = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    cshape = jax.eval_shape(lambda: T.init_paged_cache(cfg, n_pages, ps))
    params = placed(pshape, params_pspecs(mesh, pshape, SERVING_LOGICAL_MAP,
                                          cfg))
    cache = placed(cshape, paged_cache_pspecs(mesh, cfg, cshape,
                                              SERVING_LOGICAL_MAP))
    rep = NamedSharding(mesh, jax.sharding.PartitionSpec())

    def step(p, c, t, pos, bt):
        with mesh_rules(mesh, SERVING_LOGICAL_MAP):
            return T.decode_step(p, cfg, c, t, pos, block_tables=bt)

    compiled = jax.jit(step).lower(
        params, cache, _spec((n_slots, 1), jnp.int32, rep),
        _spec((n_slots,), jnp.int32, rep),
        _spec((n_slots, max_bt), jnp.int32, rep)).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_decode_attention_kernel\.\d+ = bf16\[48,1,12,128\]",
                     text)
    assert "all-reduce" in text
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < 16 * 2 ** 30, used
