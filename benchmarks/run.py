"""Benchmark driver: one module per paper table/figure + the roofline
report.  ``python -m benchmarks.run [--full] [--only name,name]``."""
from __future__ import annotations

import argparse
import sys
import time

SUITES = [
    ("freq_estimation", "Fig. 12 — frequency-estimation error vs memory"),
    ("entropy", "Fig. 13 — UnivMon entropy estimation"),
    ("heterogeneity", "Fig. 14/15 — heterogeneity heatmap"),
    ("path_length", "Fig. 16 — path-length effects + mitigation"),
    ("equalization", "§4.2 — Eq. 6 control-loop convergence"),
    ("kernel_bench", "§5 — sketch_update kernel harness"),
    ("resilience", "churn — query error vs failed-switch fraction"),
    ("compression", "beyond-paper — DiSketch gradient compression"),
    ("roofline", "§Roofline — dry-run derived terms"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale workloads (slow)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of suites")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    only = set(filter(None, args.only.split(",")))
    t0 = time.time()
    failures = []
    for name, desc in SUITES:
        if only and name not in only:
            continue
        print(f"\n#### {name}: {desc}")
        t = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run(quick=not args.full)
            print(f"[{name} done in {time.time() - t:.1f}s]")
        except Exception as e:  # keep the suite going
            failures.append((name, repr(e)))
            print(f"[{name} FAILED: {e!r}]")
    print(f"\ntotal {time.time() - t0:.1f}s; "
          f"{len(failures)} failures: {failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
