"""Readings that set a cell's limit on ``max_logit_gap``, taken on the chip.

    python3 bench/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...]

Runs the cell once per seed in one process, as ``bench/run.py`` runs it,
and prints one JSON line per seed with the program's widest gap on its
sample (the lower reading is the largest over the seeds).  On a control
seed the control takes the program's place in the run's own check: on the
same prompts and served tokens it reads the float32 reference's gap to the
token that the fp8 pass puts first, the check judges that reading against
the cell's limit, and the line gives the control's widest gap (the upper
reading is the smallest) and the ``correct`` it came to, which has to be
false.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import numpy as np

    import cell
    from reference import control_gaps, served_gaps

    bench = cell.load_benchmark(ROOT)
    spec = cell.find_cell(bench, args.workload)
    try:
        device = cell.check_device(spec["chips"])
    except cell.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cell.enable_cache()
    limit = cell.load_limits(args.workload)["max_logit_gap"]["limit"]
    for seed in args.seeds:
        got = {}

        def program_gaps(cfg, s, samples, **kw):
            g = served_gaps(cfg, s, samples, **kw)
            got["program_gap"] = float(np.max(g))
            return g

        def program_and_control_gaps(cfg, s, samples, **kw):
            program_gaps(cfg, s, samples, **kw)
            return control_gaps(cfg, s, samples, **kw)

        control = seed in args.control_seeds
        t = time.perf_counter()
        res = cell.run_cell(bench, args.workload, seed, args.seconds, False,
                            t_start=t, device=device,
                            gaps=program_and_control_gaps if control else program_gaps)
        checks = dict(res["checks"], max_logit_gap={
            "value": got.get("program_gap", float("inf")), "limit": limit,
            "rule": "<="})
        line = {"seed": seed, "program_gap": checks["max_logit_gap"]["value"],
                "program_correct": all(
                    c["value"] <= c["limit"] if c["rule"] == "<="
                    else c["value"] >= c["limit"] for c in checks.values())}
        if control:
            line["control_gap"] = res["checks"]["max_logit_gap"]["value"]
            line["control_correct"] = res["correct"]
        line.update(sampled_tokens=checks["sampled_tokens"]["value"],
                    run_s=time.perf_counter() - t,
                    metrics={k: v["value"] for k, v in res["metrics"].items()})
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
