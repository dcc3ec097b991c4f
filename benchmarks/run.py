"""Benchmark harness — one module per paper table/figure. Prints
``name,us_per_call,derived`` CSV lines (common.emit contract).

  enumeration   — Figures 8-11 (RADS vs PSgL/TwinTwig/SEED/Crystal)
  compression   — Tables 3-4 (EL vs ET)
  plan_effect   — Figure 13 (RanS / RanM / full plan)
  scalability   — Figure 12
  kernels       — kernel micro-benchmarks
  roofline      — §Roofline terms from the dry-run artifacts
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: enumeration,compression,plan,scale,"
                         "kernels,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset (enumeration + scale honor this): "
                         "one dataset/query per group")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.runtime.compile_cache import configure_jax_cache
    configure_jax_cache()

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    failures = []
    if want("kernels"):
        from benchmarks import kernels_bench
        _safe(kernels_bench.run, failures, "kernels")
    if want("enumeration"):
        from benchmarks import enumeration
        _safe(lambda: enumeration.run(smoke=args.smoke), failures,
              "enumeration")
    if want("compression"):
        from benchmarks import compression
        _safe(compression.run, failures, "compression")
    if want("plan"):
        from benchmarks import plan_effect
        _safe(plan_effect.run, failures, "plan")
    if want("scale"):
        from benchmarks import scalability
        _safe(lambda: scalability.run(smoke=args.smoke), failures, "scale")
    if want("roofline"):
        from benchmarks import roofline
        _safe(roofline.run, failures, "roofline")
        _safe(lambda: roofline.run("multi"), failures, "roofline-multi")
    if failures:
        print(f"# {len(failures)} benchmark groups failed: {failures}",
              file=sys.stderr)
        sys.exit(1)


def _safe(fn, failures, name):
    try:
        fn()
    except Exception:
        traceback.print_exc()
        failures.append(name)


if __name__ == "__main__":
    main()
