"""Serving launcher: batched generation with a KV cache on the host
devices (reduced configs), or --dry-run to lower the full config's
serve_step on the production mesh.

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch xlstm-1.3b \
        --reduced --batch 4 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the continuous-batching engine "
                         "(batch = number of requests, slots = --batch)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.dry_run:
        from repro.launch.dryrun import dryrun_one
        dryrun_one(args.arch, args.shape)
        return

    from repro.config import get_config, get_reduced_config
    from repro.serving.engine import ServingEngine

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    rng = np.random.default_rng(0)
    if args.continuous:
        from repro.serving.batching import Request
        from repro.serving.engine import ContinuousEngine
        eng = ContinuousEngine.init(cfg, n_slots=args.batch,
                                    max_seq=args.max_seq)
        reqs = [Request(prompt=rng.integers(
                    0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                        max_new=args.max_new, arrival_t=float(i))
                for i in range(2 * args.batch)]
        results = eng.run(reqs)
        print("generated tokens (continuous, finish order "
              f"{eng.finish_order}):")
        for rid in sorted(results):
            print(f"  rid={rid}", results[rid].tokens.tolist())
        return
    eng = ServingEngine.init(cfg, max_seq=args.max_seq)
    prompts = rng.integers(0, cfg.vocab_size,
                           size=(args.batch, args.prompt_len)).astype(np.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["patch_embeds"] = 0.01 * np.ones(
            (args.batch, cfg.n_patches, cfg.d_model), np.float32)
    if cfg.family == "audio":
        extra["audio_frames"] = 0.01 * np.ones(
            (args.batch, cfg.n_audio_frames, cfg.d_model), np.float32)
    res = eng.generate(prompts, max_new=args.max_new,
                       extra_inputs=extra or None)
    print("generated tokens:")
    for row in res.tokens:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
