"""jit'd dispatch wrappers: Pallas/Mosaic on TPU, interpret=True (the
kernel body executed in Python) on CPU, with the pure-jnp oracle in
``ref.py`` always available for testing."""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.kernels.conf_gate import confidence_gate_kernel
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_decode_attention import paged_decode_attention_kernel
from repro.kernels.int8_quant import int8_quantize_kernel
from repro.kernels.ssm_scan import ssm_chunk_scan_kernel
from repro.kernels import ref  # noqa: F401  (re-exported for tests)
from repro.models.pspec import current_mesh, pspec_for


@functools.lru_cache(maxsize=1)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def paged_kernel_ok() -> bool:
    """Whether the Pallas paged-decode kernel may serve this trace: on a
    TPU, with or without a serving mesh (under one it runs per device,
    ``paged_decode_attention``)."""
    return on_tpu()


def flash_attention(q, k, v, *, causal=True, window=0, **kw):
    return flash_attention_kernel(q, k, v, causal=causal, window=window,
                                  interpret=not on_tpu(), **kw)


def decode_attention(q, k, v, kv_len, **kw):
    return decode_attention_kernel(q, k, v, kv_len,
                                   interpret=not on_tpu(), **kw)


def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len, **kw):
    """The paged kernel over the whole batch.  Under the serving mesh's
    rules (``models.pspec``) it runs inside ``shard_map``: each device
    calls it on its own query heads against its own shard of the pool,
    which is ``Hkv / n`` KV heads where the ``model`` axis divides them
    and the whole (replicated) pool where one KV head serves every query
    head.  Otherwise (a KV group spread over devices) every device runs
    the whole batch on replicated operands.  Block tables and lengths
    are replicated."""
    call = functools.partial(paged_decode_attention_kernel,
                             interpret=not on_tpu(), **kw)
    mesh = current_mesh()
    if mesh is None:
        return call(q, k_pages, v_pages, block_tables, kv_len)
    kv_spec = pspec_for(k_pages.shape, [None, None, "model", None])
    q_spec = pspec_for(q.shape, [None, "model", None])
    if kv_spec[2] is None and k_pages.shape[2] != 1:
        q_spec = P()                  # a device would hold part of a group
    return jax.shard_map(call, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(), P()),
                         out_specs=q_spec, check_vma=False)(
        q, k_pages, v_pages, block_tables, kv_len)


def ssm_chunk_scan(x, dt, A, Bm, Cm, *, chunk=256, **kw):
    return ssm_chunk_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk,
                                 interpret=not on_tpu(), **kw)


def confidence_gate(logits, **kw):
    return confidence_gate_kernel(logits, interpret=not on_tpu(), **kw)


def int8_quantize(x, **kw):
    return int8_quantize_kernel(x, interpret=not on_tpu(), **kw)
