# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. ``--quick`` shrinks twins/steps for CI; results used in
# EXPERIMENTS.md come from the default scale. Every suite runs in this one
# process. The multi-device engine sweep starts one child per forced
# device count, so it runs in its own invocation instead
# (``benchmarks/engine_bench.py``) and never under a parent holding a chip.
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=None,
                    help="dataset-twin scale (default per-suite)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of suites to run")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (fig5_batch_vs_inc, fig6_queries, fig7_adaptive,
                            fig9_patterns, fig_backends, kernels_bench,
                            roofline_table, scaling, serving_bench,
                            table2_compat)
    suites = {
        "fig5": fig5_batch_vs_inc.run,
        "fig6": fig6_queries.run,
        "fig7": fig7_adaptive.run,
        "fig9": fig9_patterns.run,
        "backends": fig_backends.run,
        "table2": table2_compat.run,
        "kernels": kernels_bench.run,
        "roofline": roofline_table.run,
        "scaling": scaling.run,
        "serving": serving_bench.run,
    }
    picked = args.only or list(suites)
    unknown = sorted(set(picked) - set(suites))
    if unknown:
        raise SystemExit(f"unknown suites {unknown} (have: {sorted(suites)};"
                         " the engine sweep is benchmarks/engine_bench.py)")
    kw = {}
    if args.scale is not None:
        kw["scale"] = args.scale
    elif args.quick:
        kw["scale"] = 0.01
    if args.steps is not None:
        kw["steps"] = args.steps
    elif args.quick:
        kw["steps"] = 4

    print("name,us_per_call,derived")
    ok = True
    for name in picked:
        t0 = time.time()
        try:
            skw = dict(kw)
            if name in ("kernels", "roofline"):
                skw = {}
            for row in suites[name](**skw):
                print(row.csv(), flush=True)
        except Exception as e:  # keep the harness going, fail at exit
            ok = False
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
