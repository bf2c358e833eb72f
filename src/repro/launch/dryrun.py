"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes with 512 placeholder host devices, and extract
memory / cost / collective statistics for EXPERIMENTS.md.

The dry-run is written for the CPU host platform: ``dryrun_one`` pins
JAX to it (``pin_host_platform``) before the first backend starts, so
on a machine with an accelerator the placeholder mesh is still built
from host devices.  Importing this module touches no device state.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
        --shape train_4k [--multi-pod] [--mode triangular] \
        [--moe-dispatch scatter] [--json out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --json results/
"""
import argparse
import json
import os
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.analysis.hlo import analyze_hlo
from repro.config import (ARCH_IDS, INPUT_SHAPES, get_config,
                          supports_shape)
from repro.launch import sharding as SH
from repro.launch import specs as SP
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh
from repro.models.pspec import set_mesh_rules
from repro.training import optim


HOST_DEVICES = 512
_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def pin_host_platform() -> None:
    """Select the CPU platform with ``HOST_DEVICES`` placeholder devices.
    Appends to ``XLA_FLAGS`` (a count already there wins) and only takes
    effect if no JAX backend has started in this process yet."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_COUNT_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} {_DEVICE_COUNT_FLAG}={HOST_DEVICES}".strip())
    jax.config.update("jax_platforms", "cpu")


def _moment_dtype(cfg) -> str:
    # deepseek-scale optimizer state cannot hold fp32 moments on a 256-chip
    # v5e pod; use bf16 moments for >=100B-param configs (DESIGN.md §4)
    return "bfloat16" if cfg.param_count() > 100e9 else "float32"


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               mode: str = "flash", moe_dispatch: str = "einsum",
               window_override: int | None = None,
               sharding: str = "baseline", remat: bool = True,
               save_hlo: str | None = None,
               verbose: bool = True) -> dict:
    shape = INPUT_SHAPES[shape_name]
    cfg = SP.variant_for_shape(get_config(arch), shape)
    if window_override is not None:
        cfg = cfg.with_(sliding_window=window_override)
    if not supports_shape(cfg, shape):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "unsupported pair (DESIGN.md §6)"}

    pin_host_platform()
    lmap = SH.SHARDING_PRESETS[sharding]
    mesh = make_production_mesh(multi_pod=multi_pod)
    set_mesh_rules(mesh, lmap)
    t0 = time.time()

    params_sh = SP.params_specs(cfg, max_seq=shape.seq_len)
    p_spec = SH.params_pspecs(mesh, params_sh, lmap)
    rep = SH.replicated(mesh)

    if shape.kind == "train":
        opt_cfg = optim.OptimConfig(moment_dtype=_moment_dtype(cfg))
        opt_sh = jax.eval_shape(lambda p: optim.adamw_init(p, opt_cfg),
                                params_sh)
        o_spec = {"mu": p_spec, "nu": p_spec, "step": rep}
        batch_sh = SP.batch_specs(cfg, shape)
        b_spec = SH.batch_pspecs(mesh, batch_sh, lmap)
        fn = ST.make_train_step(cfg, opt_cfg, mode=mode,
                                moe_dispatch=moe_dispatch, remat=remat)
        jitted = jax.jit(fn, in_shardings=(p_spec, o_spec, b_spec),
                         out_shardings=(p_spec, o_spec, rep),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_sh, opt_sh, batch_sh)
    elif shape.kind == "prefill":
        batch_sh = SP.batch_specs(cfg, shape)
        b_spec = SH.batch_pspecs(mesh, batch_sh, lmap)
        fn = ST.make_prefill_step(cfg, mode=mode, moe_dispatch=moe_dispatch)
        jitted = jax.jit(fn, in_shardings=(p_spec, b_spec))
        lowered = jitted.lower(params_sh, batch_sh)
    else:  # decode
        d = SP.decode_specs(cfg, shape)
        c_spec = SH.cache_pspecs(mesh, cfg, d["cache"], lmap)
        t_spec = SH.batch_pspecs(mesh, {"tokens": d["tokens"]}, lmap)["tokens"]
        fn = ST.make_serve_step(cfg)
        jitted = jax.jit(fn, in_shardings=(p_spec, c_spec, t_spec, rep),
                         out_shardings=(None, c_spec), donate_argnums=(1,))
        lowered = jitted.lower(params_sh, d["cache"], d["tokens"], d["pos"])

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    # trip-count-aware accounting (repro.analysis.hlo); the raw
    # cost_analysis numbers are kept for comparison — XLA counts while
    # bodies once, so they undercount scanned-layer models ~n_layers x.
    hlo_text = compiled.as_text()
    if save_hlo:
        import zstandard as zstd
        with open(save_hlo, "wb") as f:
            f.write(zstd.ZstdCompressor(level=3).compress(hlo_text.encode()))
    hlo = analyze_hlo(hlo_text)

    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.devices.size,
        "kind": shape.kind, "mode": mode, "moe_dispatch": moe_dispatch,
        "sharding": sharding,
        "sliding_window": cfg.sliding_window,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": hlo["flops"],
        "bytes_per_device": hlo["bytes"],
        "collectives": {**hlo["coll"],
                        "total_link_bytes": hlo["total_link_bytes"]},
        "xla_cost_analysis": {
            "flops": float(cost.get("flops", -1)),
            "bytes_accessed": float(cost.get("bytes accessed", -1)),
        },
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem,
                                            "generated_code_size_in_bytes",
                                            None),
        },
        "params_total": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    if verbose:
        print(json.dumps(res, indent=2))
    return res


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="flash",
                    choices=["flash", "naive"])
    ap.add_argument("--moe-dispatch", default="einsum",
                    choices=["einsum", "scatter"])
    ap.add_argument("--sharding", default="baseline",
                    choices=list(SH.SHARDING_PRESETS))
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--json", default=None,
                    help="output file (single) or directory (--all)")
    args = ap.parse_args()

    if args.all:
        assert args.json, "--all requires --json DIR"
        os.makedirs(args.json, exist_ok=True)
        failures = []
        for arch in ARCH_IDS:
            for shape in INPUT_SHAPES:
                tag = f"{arch}__{shape}__{'multi' if args.multi_pod else 'single'}"
                out = os.path.join(args.json, tag + ".json")
                if os.path.exists(out):
                    print("skip (exists):", tag)
                    continue
                print("=== ", tag, flush=True)
                try:
                    res = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                                     mode=args.mode,
                                     moe_dispatch=args.moe_dispatch,
                                     save_hlo=out.replace(".json", ".hlo.zst"),
                                     verbose=False)
                except Exception as e:
                    traceback.print_exc()
                    failures.append(tag)
                    res = {"arch": arch, "shape": shape, "error": str(e)[:2000]}
                with open(out, "w") as f:
                    json.dump(res, f, indent=2)
        print("FAILURES:", failures)
        sys.exit(1 if failures else 0)
    else:
        res = dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod,
                         mode=args.mode, moe_dispatch=args.moe_dispatch,
                         sharding=args.sharding, remat=not args.no_remat,
                         window_override=args.window)
        if args.json:
            with open(args.json, "w") as f:
                json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
