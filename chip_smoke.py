"""Smoke run of the serving path on TPU: the quickest proof that the
system still starts on the chip.

One process, run from the root of a checkout:

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the sharded path, four chips

Phases on one chip (weights are random, made from ``--seed``):

  device   JAX must report a TPU.  Anything else fails: there is no CPU
           fallback and no interpret mode.
  kernel   ``ops.paged_decode_attention`` at smollm-360m widths (bf16,
           page 16, shuffled block tables, ragged lengths) against
           ``ref.paged_decode_attention_ref``.
  served   ``ContinuousEngine`` serving smollm-360m at full width
           (32 layers, d_model 960, vocab 49152, bf16, paged KV,
           chunked prefill): every request finishes, the page pool
           drains, the compiled decode step holds the Pallas kernel, and
           the same requests replayed through the gather path agree.
  pair     A ``SpaceGroundScheduler`` replay of the Tiansuan
           onboard/ground pair under ``configs/tiansuan_pair`` knobs:
           every request answered, escalations shipped as draft tokens
           and answered on the ground tier as its greedy decode would,
           both pools drained; then the ground tier verifies its own
           answers as drafts, and the verified streams agree with them.

``--four-chips`` runs only ``ContinuousEngine(mesh=make_serving_mesh())``
at the full width of qwen1.5-4b over four chips, and its comparator: the
same requests on one chip.

Two greedy runs of one model agree when every request's tokens match
and its final-step logits lie within ``LOGIT_TOL``.  A token may differ
only where the reference's logits for the two candidate tokens at that
step lie within ``LOGIT_TOL["atol"]`` of each other (a near-tie that a
bf16 rounding can flip); the streams part there, so their later logits
are not compared.

Lines before the last report set-up facts (compile and wall seconds),
not metrics.  The last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises, and the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_SLOTS = 8
MAX_SEQ = 512
PAGE_SIZE = 16
N_REQUESTS = 12
PROMPT_LENS = (16, 256)
MAX_NEW = 16
# bf16 comparison of the kernel against its reference (tests/test_kernels)
KERNEL_TOL = dict(atol=5e-2, rtol=5e-2)
# final-step logits of two greedy bf16 runs of the same weights whose
# decode attention differs only in summation order
LOGIT_TOL = dict(atol=0.25, rtol=0.05)


class SmokeFailure(RuntimeError):
    """A smoke check did not hold."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# -- device -----------------------------------------------------------------

def tpu_devices():
    import jax
    devs = jax.devices()
    check(devs and devs[0].platform == "tpu",
          f"no TPU: JAX reports {devs[0].platform if devs else 'no'} "
          "devices (this script never falls back to the CPU)")
    say(f"device: {devs[0].device_kind} x{len(devs)}")
    return devs


# -- shared helpers ---------------------------------------------------------

def make_requests(cfg, seed: int, n: int = N_REQUESTS,
                  prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW):
    from repro.serving.batching import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(
                1, cfg.vocab_size,
                int(rng.integers(prompt_lens[0], prompt_lens[1] + 1))
            ).astype(np.int32), max_new=max_new, arrival_t=float(i))
            for i in range(n)]


def serve(eng, reqs):
    """Serve clones of ``reqs`` (draft streams kept); results in the
    order of ``reqs``."""
    clones = [r.clone() for r in reqs]
    for c, r in zip(clones, reqs):
        c.draft_toks = r.draft_toks
    t0 = time.perf_counter()
    res = eng.run(clones)
    wall = time.perf_counter() - t0
    out = [res.get(c.rid) for c in clones]
    check(all(r is not None for r in out),
          f"{sum(r is None for r in out)} of {len(out)} requests "
          "never finished")
    for r, req in zip(out, reqs):
        check(len(r.tokens) == req.max_new,
              f"request emitted {len(r.tokens)} tokens, wanted "
              f"{req.max_new}")
    return out, wall


def check_drained(eng, what: str) -> None:
    a = eng.slots.allocator
    check(a.in_use == 0 and a.reserved == 0 and a.n_live_refs() == 0,
          f"{what}: page pool not drained (in_use {a.in_use}, reserved "
          f"{a.reserved})")


def next_token_logits(eng, prompt):
    """Logits for the token after ``prompt``, as ``eng`` computes them."""
    from repro.serving.batching import Request
    req = Request(prompt=np.asarray(prompt, np.int32), max_new=1)
    return eng.run([req])[req.rid].logits_last


def compare(name: str, reqs, got, want, ref_eng) -> None:
    """Hold two greedy runs of one model to the agreement stated in the
    module docstring; ``ref_eng`` recomputes logits where tokens part."""
    n_same, worst = 0, 0.0
    for req, g, w in zip(reqs, got, want):
        diff = np.nonzero(g.tokens != w.tokens)[0]
        if diff.size == 0:
            n_same += 1
            a, b = g.logits_last, w.logits_last
            check(np.allclose(a, b, **LOGIT_TOL),
                  f"{name}: final logits differ by "
                  f"{float(np.max(np.abs(a - b)))} beyond {LOGIT_TOL}")
            worst = max(worst, float(np.max(np.abs(a - b))))
            continue
        k = int(diff[0])
        ref = next_token_logits(
            ref_eng, np.concatenate([req.prompt, w.tokens[:k]]))
        gap = abs(float(ref[g.tokens[k]]) - float(ref[w.tokens[k]]))
        check(gap <= LOGIT_TOL["atol"],
              f"{name}: token {k} differs ({g.tokens[k]} vs "
              f"{w.tokens[k]}) where the logits are {gap} apart")
        say(f"{name}: request {req.rid} parts at token {k} on a near-tie "
            f"(logit gap {gap:.4g})")
    say(f"{name}: token agreement {n_same}/{len(reqs)} requests; max "
        f"final-logit difference {worst:.4g} (tolerance {LOGIT_TOL})")


# -- phases -----------------------------------------------------------------

def phase_kernel(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    check(ops.on_tpu(), "kernel dispatch is not on the TPU")
    B, H, Hkv, D = N_SLOTS, cfg.n_heads, cfg.n_kv_heads, \
        cfg.resolved_head_dim
    max_bt = MAX_SEQ // PAGE_SIZE
    n_pages = B * max_bt + 1                      # + scratch page 0
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (n_pages, PAGE_SIZE, Hkv, D), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_pages, PAGE_SIZE, Hkv, D), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                     .reshape(B, max_bt), jnp.int32)
    lens = jnp.asarray(rng.integers(1, MAX_SEQ + 1, B), jnp.int32)
    got = np.asarray(ops.paged_decode_attention(q, kp, vp, bt, lens),
                     np.float32)
    want = np.asarray(ref.paged_decode_attention_ref(q, kp, vp, bt, lens),
                      np.float32)
    err = float(np.max(np.abs(got - want)))
    check(np.allclose(got, want, **KERNEL_TOL),
          f"paged kernel differs from the reference by {err}")
    say(f"kernel: paged decode attention (B {B}, H {H}, Hkv {Hkv}, D {D}) "
        f"matches the reference, max abs difference {err:.4g}")


def phase_served(cfg, seed: int) -> None:
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.serving import engine as E

    check(ops.paged_kernel_ok(), "the paged kernel gate is closed on TPU")
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    eng = E.ContinuousEngine.init(cfg, seed=seed, n_slots=N_SLOTS,
                                  max_seq=MAX_SEQ, page_size=PAGE_SIZE)
    say(f"served: {cfg.name} engine built in "
        f"{time.perf_counter() - t0:.1f} s")
    kernel_res, wall = serve(eng, reqs)
    check_drained(eng, "served")
    say(f"served: {len(reqs)} requests through the kernel path in "
        f"{wall:.1f} s wall (compiles included)")

    # the engine's decode step, compiled for the shapes it served, must
    # hold the Pallas kernel and not the gather path
    toks = jnp.zeros((N_SLOTS, 1), jnp.int32)
    pos = jnp.zeros((N_SLOTS,), jnp.int32)
    bt = jnp.zeros((N_SLOTS, eng.slots.max_bt), jnp.int32)
    hlo = eng._decode.lower(eng.params, eng.slots.cache, toks, pos,
                            bt).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the compiled decode step holds no Pallas kernel")
    say("served: the compiled decode step holds the Pallas paged kernel")

    # the same requests with the kernel steered off: the gather path
    decode_key = ("cont_decode_paged", cfg, None)
    kernel_ok = ops.paged_kernel_ok
    ops.paged_kernel_ok = lambda: False
    E._JIT_CACHE.pop(decode_key, None)
    try:
        gather = E.ContinuousEngine(cfg, eng.params, n_slots=N_SLOTS,
                                    max_seq=MAX_SEQ, page_size=PAGE_SIZE)
        gather_res, wall = serve(gather, reqs)
        check_drained(gather, "served (gather path)")
        say(f"served: {len(reqs)} requests through the gather path in "
            f"{wall:.1f} s wall (compiles included)")
        compare("served kernel vs gather", reqs, kernel_res, gather_res,
                gather)
    finally:
        ops.paged_kernel_ok = kernel_ok
        E._JIT_CACHE.pop(decode_key, None)


def phase_pair(seed: int) -> None:
    from repro.configs import tiansuan_pair as TP
    from repro.core.gating import ConfidenceGate
    from repro.core.link import ContactSchedule
    from repro.serving.engine import ContinuousEngine
    from repro.serving.scheduler import SpaceGroundScheduler

    S = TP.SCHEDULER
    sat = ContinuousEngine.init(
        TP.ONBOARD, seed=seed, n_slots=4, max_seq=128,
        prefill_budget_tokens=S["prefill_budget_tokens"])
    gnd = ContinuousEngine.init(TP.GROUND, seed=seed + 1, n_slots=4,
                                max_seq=128, draft_k=S["draft_k"])
    reqs = make_requests(TP.ONBOARD, seed, n=8, prompt_lens=(8, 48),
                         max_new=12)
    for i, r in enumerate(reqs):
        r.arrival_t = float(4 * i)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        sg = SpaceGroundScheduler(
            sat, gnd,
            schedule=ContactSchedule(
                contact_duration_s=S["contact_duration_s"],
                contacts_per_day=S["contacts_per_day"], seed=seed),
            gate=ConfidenceGate(TP.CASCADE["confidence_metric"],
                                S["escalate_threshold"]),
            s_per_step=S["s_per_step"], overlap=S["overlap"],
            comm_reserve_pages=S["comm_reserve_pages"],
            delta_spill=S["delta_spill"], frame_bytes=S["frame_bytes"],
            link_max_retries=S["link_max_retries"],
            checkpoint_every=S["checkpoint_every"],
            checkpoint_path=os.path.join(ckpt_dir, "sat.ckpt"),
            speculative=S["speculative"])
        t0 = time.perf_counter()
        rep = sg.run(reqs)
        wall = time.perf_counter() - t0
    check(sorted(rep.tokens) == sorted(r.rid for r in reqs),
          f"pair: {len(rep.tokens)} of {len(reqs)} requests answered")
    check(not rep.undelivered, f"pair: undelivered {rep.undelivered}")
    check(rep.escalated, "pair: nothing escalated to the ground tier")
    check(all(rid in rep.ground_results for rid in rep.escalated),
          "pair: an escalation has no ground answer")
    led, spec = rep.ledger, rep.spec_stats
    check(led.get("bytes_draft_escalated") > 0
          and led.get("bytes_raw_escalated") == 0,
          "pair: escalations did not ship draft tokens")
    # the ground tier checks each draft stream: a stream whose head
    # disagrees with the ground's own first token is dropped, the rest
    # run verify passes
    check(spec["verify_passes"] > 0
          or spec["draft_streams_dropped"] == len(rep.escalated),
          f"pair: draft streams were neither verified nor dropped {spec}")
    check_drained(sg.sat.engine, "pair (onboard)")
    check_drained(gnd, "pair (ground)")
    # the ground's answers are its own greedy decode of the escalated
    # prompts (every draft stream above was dropped or verified)
    by_rid = {r.rid: r for r in reqs}
    esc = [by_rid[rid] for rid in rep.escalated]
    plain, _ = serve(gnd, esc)
    answers = [rep.ground_results[r.rid] for r in esc]
    compare("pair escalations vs ground greedy", esc, answers, plain, gnd)
    check_drained(gnd, "pair (ground, greedy replay)")
    say(f"pair: {len(reqs)} requests answered, {len(rep.escalated)} "
        f"escalated and answered on the ground ({spec['verify_passes']} "
        f"verify passes, {spec['accepted']}/{spec['drafted']} drafts "
        f"accepted, {spec['draft_streams_dropped']} draft streams dropped "
        f"at the head), {wall:.1f} s wall")

    # drafts from two independently random tiers rarely agree, so also
    # hand the ground tier its own greedy answers as drafts.  The verify
    # pass scores them with the chunk path, not the decode step, so in
    # bf16 a near-tie can still reject one; the verified streams must
    # then agree with plain decode as two runs of one model do
    before = gnd.spec_stats()
    drafted = [r.clone() for r in esc]
    for r, p in zip(drafted, plain):
        r.draft_toks = p.tokens
    verified, _ = serve(gnd, drafted)
    after = gnd.spec_stats()
    n_passes = after["verify_passes"] - before["verify_passes"]
    n_drafted = after["drafted"] - before["drafted"]
    n_accepted = after["accepted"] - before["accepted"]
    check(n_passes > 0 and n_accepted > 0,
          f"pair: the ground tier verified none of its own drafts {after}")
    compare("pair draft-verified vs ground greedy", esc, verified, plain,
            gnd)
    check_drained(gnd, "pair (ground, draft verification)")
    say(f"pair: ground tier accepted {n_accepted}/{n_drafted} of its own "
        f"drafts in {n_passes} verify passes")


def phase_sharded(cfg, seed: int, n_devices: int) -> None:
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.models import transformer as T
    from repro.serving.engine import ContinuousEngine

    check(len(jax.devices()) >= n_devices,
          f"need {n_devices} devices, JAX reports {len(jax.devices())}")
    reqs = make_requests(cfg, seed)
    params = T.init_params(jax.random.PRNGKey(seed), cfg, max_seq=MAX_SEQ)
    kw = dict(n_slots=N_SLOTS, max_seq=MAX_SEQ, page_size=PAGE_SIZE)
    single = ContinuousEngine(cfg, params, **kw)
    single_res, wall = serve(single, reqs)
    check_drained(single, "one chip")
    say(f"sharded: {len(reqs)} requests of {cfg.name} on one chip in "
        f"{wall:.1f} s wall (compiles included)")
    sharded = ContinuousEngine(cfg, params,
                               mesh=make_serving_mesh(n_devices), **kw)
    sharded_res, wall = serve(sharded, reqs)
    check_drained(sharded, "mesh")
    stats = sharded.kv_cache_stats()
    check(stats["n_kv_shards"] == n_devices,
          f"KV pool split {stats['n_kv_shards']} ways, wanted {n_devices}")
    say(f"sharded: {len(reqs)} requests over a {n_devices}-chip mesh in "
        f"{wall:.1f} s wall (compiles included); n_kv_shards "
        f"{stats['n_kv_shards']}, kv_bytes_per_device "
        f"{stats['kv_bytes_per_device']} of kv_cache_bytes "
        f"{stats['kv_cache_bytes']}")
    compare("sharded vs one chip", reqs, sharded_res, single_res, single)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path (qwen1.5-4b over a "
                         "4-chip mesh) and its one-chip comparator")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.config import get_config
    from repro.launch.compile_cache import enable_compile_cache

    t0 = time.perf_counter()
    say(f"compile cache: {enable_compile_cache()}")
    devs = tpu_devices()
    if args.four_chips:
        phase_sharded(get_config("qwen1.5-4b"), args.seed, 4)
    else:
        cfg = get_config("smollm-360m")
        phase_kernel(cfg, args.seed)
        phase_served(cfg, args.seed)
        phase_pair(args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
