"""Run every benchmark: one per paper table/figure + the roofline summary.

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None) -> int:
    from repro import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the MARL accuracy sweep (slowest)")
    args = ap.parse_args(argv)

    from benchmarks import (fig10_osel, fig11_throughput, fig12_breakdown,
                            fig13_speedup, fig14_serving, table1_balance)
    jobs = [
        ("fig10_osel (OSEL cycles/memory)", fig10_osel.main),
        ("table1_balance (workload deviation)", table1_balance.main),
        # --no-write: the committed BENCH_fig11_throughput.json carries the
        # --async overlap sweep; only an explicit --async run refreshes it
        ("fig11_throughput (accelerator model)",
         lambda: fig11_throughput.main(write=False)),
        ("fig12_breakdown (sparse-gen share)", fig12_breakdown.main),
        ("fig13_speedup (sparse vs dense)", fig13_speedup.main),
        # --no-write: the committed BENCH_serving.json is refreshed only
        # by an explicit benchmarks.fig14_serving run
        ("fig14_serving (continuous batching)",
         lambda: fig14_serving.main(["--no-write"])),
    ]
    if not args.fast:
        from benchmarks import fig9_accuracy
        jobs.append(("fig9_accuracy (MARL accuracy vs sparsity)",
                     lambda: fig9_accuracy.main(["--no-write"])))

    failures = 0
    for name, fn in jobs:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"=== done in {time.time() - t0:.1f}s ===")
        except Exception:
            failures += 1
            traceback.print_exc()
    print(f"\n{len(jobs) - failures}/{len(jobs)} benchmarks succeeded")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
