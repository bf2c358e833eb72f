"""Sharding rules: map parameter / cache / batch pytrees to NamedShardings.

Strategy (DESIGN.md §4):
  * tensor parallel over "model": attention heads, FFN hidden, vocab,
    experts;
  * FSDP over "data" (+"pod"): the non-TP dimension of every matmul
    weight, gathered per-layer inside the scan by GSPMD;
  * batch over ("pod", "data");
  * KV caches: kv-heads over "model" when divisible, else cache sequence
    over "model" (MQA archs — flash-decoding-style partial softmax);
    the serving engine's paged pool is replicated instead, with the k/v
    projections that fill it (``params_pspecs(..., cfg=)``).

All assignments are divisibility-aware (models.pspec): a rule that does
not divide a concrete dim falls back to replication for that dim.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig
from repro.models.pspec import pspec_for, mesh_rules, set_mesh_rules, shard_count

# Sharding presets (hillclimbed in EXPERIMENTS.md §Perf):
#   baseline  — TP over "model" + FSDP over "data", batch over (pod, data)
#   dp        — pure data parallel: batch over EVERY axis, params FSDP over
#               "data" only.  Right for small models whose head counts do
#               not divide the model axis (smollm 15H, qwen1.5 20H): the
#               baseline replicates their attention 16x over "model".
#   infer-tp  — serving: params TP over "model", REPLICATED over "data"
#               (no per-step FSDP all-gathers), batch over (pod, data).
SHARDING_PRESETS = {
    "baseline": None,
    "dp": {
        "batch": ("pod", "data", "model"),
        "fsdp": ("data",),
        "model": (),
        "expert": ("model",),
        "seq": (),
    },
    "infer-tp": {
        "batch": ("pod", "data"),
        "fsdp": (),
        "model": ("model",),
        "expert": ("model",),
        "seq": ("model",),
    },
    # true expert parallelism: one expert per chip (256 experts over
    # data x model = 256); token all-to-all replaces per-layer expert
    # weight all-gathers.  Non-expert params keep baseline TP+FSDP.
    "ep": {
        "batch": ("pod", "data"),
        "fsdp": ("data",),
        "model": ("model",),
        "expert": ("data", "model"),
        "seq": ("model",),
    },
    # serving for giant MoE: 256-way tensor parallel — weights sharded
    # over BOTH axes and never gathered; small per-layer activation
    # all-reduces replace per-step FSDP weight all-gathers.
    "infer-tp2": {
        "batch": ("pod",),
        "fsdp": (),
        "model": ("data", "model"),
        "expert": ("data", "model"),
        "seq": (),
    },
}

# The continuous engine's mesh (launch.mesh.make_serving_mesh): params
# tensor-parallel over "model", replicated elsewhere (no FSDP — serving
# never pays per-step weight all-gathers), experts expert-parallel over
# "model".  "batch" and "seq" stay REPLICATED: slots are few, decode
# scatters index the paged pool per batch row, and the page axis carries
# block-table semantics no mesh axis may cut.
SERVING_LOGICAL_MAP = {
    "batch": (),
    "fsdp": (),
    "model": ("model",),
    "expert": ("model",),
    "seq": (),
}

# weights whose LAST dim is the contraction output fed back to d_model
_DOWN_STYLE = ("w_o", "w_down", "out_proj")
_REPLICATED = ("A_log", "D", "dt_bias", "b_if", "b_gates", "conv_w", "conv_b",
               "scale", "bias", "b_q", "b_k", "b_v", "b_o", "b_up", "b_down",
               "router", "skip", "r_gates")


def _path_names(path) -> list:
    out = []
    for e in path:
        if hasattr(e, "key"):
            out.append(str(e.key))
        elif hasattr(e, "name"):
            out.append(str(e.name))
    return out


def param_logical_axes(path, leaf, cfg: Optional[ModelConfig] = None) -> list:
    """Return the logical axis names for one parameter leaf.  Given the
    ``cfg`` (and installed mesh rules), the k/v projections of a model
    whose KV heads the "model" axis cannot split are replicated: every
    device then computes the KV heads whole, as its replica of the paged
    pool needs, instead of a column slice that cuts through a head."""
    names = _path_names(path)
    last = names[-1] if names else ""
    nd = leaf.ndim
    if last in _REPLICATED or nd <= 1:
        return [None] * nd
    if (cfg is not None and cfg.mla is None and last in ("w_k", "w_v")
            and shard_count("model", cfg.n_kv_heads) == 1):
        return [None] * nd
    if last == "embed":
        return [None] * (nd - 2) + ["model", "fsdp"]      # (vocab, d)
    if last in ("lm_head",):
        return [None] * (nd - 2) + ["fsdp", "model"]      # (d, vocab)
    if last in ("dec_pos", "pos_embed"):
        return [None] * nd
    in_moe = "moe" in names and last in ("w_gate", "w_up", "w_down")
    if in_moe:
        # stacked (L, E, d, f) or (E, d, f).  When the "expert" logical
        # axis maps onto the axes fsdp would use (the "ep" preset),
        # pspec_for's duplicate guard drops the fsdp entry automatically.
        core = (["expert", None, "fsdp"] if last == "w_down"
                else ["expert", "fsdp", None])
        return [None] * (nd - 3) + core
    if last in _DOWN_STYLE:
        return [None] * (nd - 2) + ["model", "fsdp"]
    # generic "up-style" matmul weight (d_in, d_out)
    return [None] * (nd - 2) + ["fsdp", "model"]


def params_pspecs(mesh: Mesh, params_shape, logical_map=None,
                  cfg: Optional[ModelConfig] = None) -> object:
    """NamedSharding tree for a params pytree of ShapeDtypeStructs (see
    ``param_logical_axes`` for what ``cfg`` changes)."""
    with mesh_rules(mesh, logical_map):
        def one(path, leaf):
            spec = pspec_for(leaf.shape, param_logical_axes(path, leaf, cfg))
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree_util.tree_map_with_path(one, params_shape)


def cache_logical_axes(cfg: ModelConfig, path, leaf) -> list:
    names = _path_names(path)
    last = names[-1]
    nd = leaf.ndim
    model_divides_kv = cfg.n_kv_heads and cfg.n_kv_heads % 16 == 0
    if last in ("k", "v", "xk", "xv"):
        # (L, B, S, Hkv, D)
        if model_divides_kv:
            return [None, "batch", None, "model", None]
        return [None, "batch", "seq", None, None]
    if last in ("ckv", "krope"):
        # (L, B, S, rank) — shard the latent rank over model (krope's 64
        # rank falls back to replication automatically if indivisible)
        return [None, "batch", None, "model"]
    if last == "ssm":
        # (..., B, H, P, N)
        return [None] * (nd - 4) + ["batch", "model", None, None]
    if last == "conv":
        return [None] * (nd - 3) + ["batch", None, "model"]
    if last == "C":
        # mLSTM matrix memory (..., B, H, dqk, dv)
        return [None] * (nd - 4) + ["batch", None, "model", None]
    if last in ("n",):
        return [None] * (nd - 3) + ["batch", None, "model"]
    if last in ("m", "h"):
        return [None] * (nd - 2) + ["batch", None]
    if last == "c":
        return [None] * (nd - 3) + ["batch", None, None]
    if last == "conv_win":
        return [None] * (nd - 3) + ["batch", None, None]
    return [None] * nd


def cache_pspecs(mesh: Mesh, cfg: ModelConfig, cache_shape, logical_map=None):
    with mesh_rules(mesh, logical_map):
        def one(path, leaf):
            spec = pspec_for(leaf.shape, cache_logical_axes(cfg, path, leaf))
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree_util.tree_map_with_path(one, cache_shape)


def paged_cache_logical_axes(cfg: ModelConfig, path, leaf) -> list:
    """Logical axes for one PAGED KV pool leaf (``init_paged_cache``):
    k/v pools (L, n_pages, page_size, Hkv, hd) shard their KV heads over
    "model"; MLA latent pools (L, n_pages, page_size, rank) shard the
    latent rank.  The layer/page/offset axes are never cut — a page is
    whole on every device along them, so extract/graft snapshots (and
    hence spills, checkpoints and constellation handovers) reassemble
    token-exactly from a plain ``device_get``.  Indivisible head counts
    fall back to replication through ``pspec_for``."""
    last = _path_names(path)[-1]
    nd = leaf.ndim
    if last in ("k", "v"):
        return [None, None, None, "model", None]
    if last in ("ckv", "krope"):
        return [None, None, None, "model"]
    return [None] * nd


def paged_cache_pspecs(mesh: Mesh, cfg: ModelConfig, cache_shape,
                       logical_map=None):
    """NamedSharding tree for an ``init_paged_cache`` pool."""
    with mesh_rules(mesh, logical_map):
        def one(path, leaf):
            spec = pspec_for(leaf.shape,
                             paged_cache_logical_axes(cfg, path, leaf))
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree_util.tree_map_with_path(one, cache_shape)


def batch_pspecs(mesh: Mesh, batch_shape, logical_map=None):
    """Shard every batch input over the "batch" logical axes on dim 0."""
    with mesh_rules(mesh, logical_map):
        def one(leaf):
            spec = pspec_for(leaf.shape,
                             ["batch"] + [None] * (leaf.ndim - 1))
            return NamedSharding(mesh, spec if spec is not None else P())
        return jax.tree.map(one, batch_shape)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
