"""Benchmark driver: one experiment per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --only fig2,tiled
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced epochs
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,fig3,fig4,fig5,tiled,kernels,"
                         "kbench,roofline,serve")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--check-against", default=None, metavar="BASELINE.json",
                    help="bench regression guard: after the kbench suite, "
                         "fail if any kernel's *_us time exceeds "
                         "--tolerance x the committed baseline row")
    ap.add_argument("--check-serving-against", default=None,
                    metavar="BASELINE.json",
                    help="serving regression guard: after the serve suite, "
                         "fail if any mode's tokens_per_s drops below "
                         "baseline / --tolerance")
    ap.add_argument("--tolerance", type=float, default=1.5,
                    help="allowed slowdown factor vs the baseline "
                         "(default 1.5)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.check_against and only is not None and "kbench" not in only:
        ap.error("--check-against needs the kbench suite in the run "
                 "(drop --only or include kbench in it)")
    if args.check_serving_against and only is not None and "serve" not in only:
        ap.error("--check-serving-against needs the serve suite in the run "
                 "(drop --only or include serve in it)")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        kernel_bench,
        overflow_profile,
        pareto_accum,
        pq_vs_qp_lowrank,
        pq_vs_qp_nets,
        roofline,
        serving_throughput,
        tiled_sort,
    )

    epochs = 6 if args.quick else 12
    suites = [
        ("fig2", lambda: overflow_profile.run(epochs=epochs)),
        ("fig3", lambda: pq_vs_qp_lowrank.run(epochs=max(epochs - 2, 6))),
        ("fig4", lambda: pq_vs_qp_nets.run(epochs=max(epochs - 2, 6))),
        ("fig5", lambda: pareto_accum.run(epochs=epochs)),
        ("tiled", lambda: tiled_sort.run(epochs=max(epochs - 2, 6))),
        ("kernels", kernel_bench.run),
        ("kbench", lambda: kernel_bench.bench_kernels(quick=args.quick)),
        ("roofline", roofline.run),
        ("serve", lambda: serving_throughput.run(quick=args.quick)),
    ]

    t0 = time.time()
    failures = []
    results = {}
    for name, fn in suites:
        if only and name not in only:
            continue
        print(f"\n===== {name} =====", flush=True)
        try:
            results[name] = fn()
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
    if args.check_against and "kbench" in results:
        regs = kernel_bench.check_against(
            results["kbench"], args.check_against, args.tolerance)
        if regs:
            print(f"\n[bench-guard] {len(regs)} regression(s) vs "
                  f"{args.check_against} (tolerance {args.tolerance}x):")
            for key, field, base_us, now_us in regs:
                ratio = (f"{now_us / base_us:.2f}x"
                         if isinstance(now_us, (int, float))
                         else "no longer runs")
                print(f"  {key} {field}: {base_us} -> {now_us} us ({ratio})")
            failures.append(("bench-guard", f"{len(regs)} regressions"))
        else:
            print(f"\n[bench-guard] ok — all kernel times within "
                  f"{args.tolerance}x of {args.check_against}")
    if args.check_serving_against and "serve" in results:
        regs = serving_throughput.check_against(
            results["serve"], args.check_serving_against, args.tolerance)
        if regs:
            print(f"\n[serve-guard] {len(regs)} regression(s) vs "
                  f"{args.check_serving_against} "
                  f"(tolerance {args.tolerance}x):")
            for mode, field, base, now in regs:
                ratio = (f"{now / base:.2f}x" if isinstance(now, (int, float))
                         else "no longer runs")
                print(f"  {mode} {field}: {base} -> {now} tok/s ({ratio})")
            failures.append(("serve-guard", f"{len(regs)} regressions"))
        else:
            print(f"\n[serve-guard] ok — all modes within "
                  f"{args.tolerance}x of {args.check_serving_against}")
    print(f"\n[benchmarks] total {time.time() - t0:.0f}s; "
          f"{len(failures)} failures: {failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
