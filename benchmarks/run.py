"""Benchmark driver: one module per paper table/figure group.

Prints ``name,us_per_call,derived`` CSV (plus a header comment per
suite).  Roofline rows appear when artifacts/dryrun/ exists (run
``python -m repro.launch.dryrun --all`` first).

``--json [DIR]`` additionally writes one machine-readable
``BENCH_<suite>.json`` per suite run — {suite, name, us_per_call,
wire_bits, dispatch_path, derived} rows — the format the committed
``benchmarks/BENCH_operators.json`` baseline and the CI regression
gate (``benchmarks/check_regression.py``) consume.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

SUITES = [
    ("operators", "benchmarks.bench_operators"),     # Fig 1a/4a companions
    ("convex", "benchmarks.bench_convex"),           # Fig 4-6
    ("async", "benchmarks.bench_async"),             # Fig 7
    ("nonconvex", "benchmarks.bench_nonconvex"),     # Fig 1-3
    ("scaled", "benchmarks.bench_scaled"),           # Fig 8 / App D
    ("scenarios", "benchmarks.bench_scenarios"),     # fleet scenario lab (§8)
    ("serve", "benchmarks.bench_serve"),             # serving engine (§11)
    ("roofline", "benchmarks.roofline"),             # deliverable (g)
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default=None,
                    choices=[s for s, _ in SUITES])
    ap.add_argument("--json", nargs="?", const=".", default=None,
                    metavar="DIR",
                    help="also write BENCH_<suite>.json into DIR "
                         "(default: current directory)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, mod_name in SUITES:
        if args.suite and name != args.suite:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.run()
            for r in rows:
                print(r.csv(), flush=True)
            print(f"# suite {name} done in {time.time() - t0:.1f}s",
                  flush=True)
            if args.json is not None:
                os.makedirs(args.json, exist_ok=True)
                path = os.path.join(args.json, f"BENCH_{name}.json")
                with open(path, "w") as f:
                    json.dump({"suite": name,
                               "rows": [r.to_json(name) for r in rows]},
                              f, indent=1)
                    f.write("\n")
                print(f"# wrote {path}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# suite {name} FAILED: {type(e).__name__}: {e}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
