"""Training launcher.

Two modes:
  * local (default): run REAL steps of a reduced config on the host
    devices — this is what examples/train_100m.py drives;
  * --dry-run: lower + compile the FULL config on the production mesh
    (delegates to repro.launch.dryrun).

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
        --steps 50 --batch 8 --seq 256 [--reduced]
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized config")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.dry_run:
        from repro.launch.dryrun import dryrun_one
        dryrun_one(args.arch, args.shape)
        return

    import jax.numpy as jnp
    from repro.config import get_config, get_reduced_config
    from repro.data.tokens import TokenStream, TokenStreamConfig
    from repro.training import optim
    from repro.training.loop import init_state, train

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    opt_cfg = optim.OptimConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps)
    stream = TokenStream(TokenStreamConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        batch_size=args.batch))

    def add_extras(it):
        for b in it:
            if cfg.family == "vlm":
                b["patch_embeds"] = 0.01 * jnp.ones(
                    (args.batch, cfg.n_patches, cfg.d_model), jnp.bfloat16)
            if cfg.family == "audio":
                b["audio_frames"] = 0.01 * jnp.ones(
                    (args.batch, cfg.n_audio_frames, cfg.d_model),
                    jnp.bfloat16)
            yield b

    state = init_state(cfg, opt_cfg, max_seq=args.seq)
    state = train(cfg, state, add_extras(iter(stream)), opt_cfg,
                  steps=args.steps, log_every=10,
                  callback=lambda row: print(json.dumps(row)))
    if args.checkpoint:
        from repro.checkpoint import save_checkpoint
        n = save_checkpoint(args.checkpoint, state.params,
                            {"arch": cfg.name, "step": state.step})
        print(f"checkpoint: {args.checkpoint} ({n/1e6:.1f} MB)")


if __name__ == "__main__":
    main()
