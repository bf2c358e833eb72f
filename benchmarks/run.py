"""Benchmark harness — one benchmark per paper table/figure (+ kernel
microbenchmarks).  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig7,...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

BENCHES = ("fig6_filter_rate", "fig7_accuracy", "table1_link_budget",
           "table23_energy", "data_reduction", "kernel_conf_gate",
           "serving_throughput")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark module names")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import importlib
    print("name,us_per_call,derived")
    failures = []
    for mod_name in BENCHES:
        if only and mod_name not in only:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows = mod.run()
        except Exception as e:      # pragma: no cover
            import traceback
            traceback.print_exc()
            failures.append(mod_name)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{json.dumps(derived, sort_keys=True)}")
        print(f"# {mod_name} wall {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
