"""Run one cell of the benchmark once and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration and its traffic
are found by name through ``BENCHMARK.json``.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number compared, with its limit).  The checks are also
the last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it prints no result and exits with code 2.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell

    bench = cell.load_benchmark(ROOT)
    spec = cell.find_cell(bench, args.workload)
    try:
        device = cell.check_device(spec["chips"])
    except cell.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cell.enable_cache()
    result = cell.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START, device=device)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
