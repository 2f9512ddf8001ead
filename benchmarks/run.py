"""Benchmark harness entry: one module per paper figure + roofline +
kernel micro-bench. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig3 fig4  # subset
  BENCH_ROUNDS=100 ... python -m benchmarks.run      # longer runs
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (channel_bench, contention_bench, faults_bench,
                        fig2_iid, fig3_noniid, fig4_fairness,
                        fig5_counter_acc, fig6_cw_size, objectives_bench,
                        roofline, kernel_bench, round_bench,
                        sparse_bench, sweep_bench)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig2": fig2_iid.run,
    "fig3": fig3_noniid.run,
    "fig4": fig4_fairness.run,
    "fig5": fig5_counter_acc.run,
    "fig6": fig6_cw_size.run,
    "csma": contention_bench.run,
    "channel": channel_bench.run,
    "faults": faults_bench.run,
    "objectives": objectives_bench.run,
    "round": round_bench.run,
    "sparse": sparse_bench.run,
    "sweep": sweep_bench.run,
    "kernels": kernel_bench.run,
    "roofline": roofline.run,
}


def main() -> None:
    enable_compile_cache()
    picks = [a for a in sys.argv[1:] if not a.startswith("-")] or list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for name in picks:
        t0 = time.time()
        try:
            for line in SUITES[name]():
                print(line, flush=True)
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"{name}/ERROR,0,{type(e).__name__}", flush=True)
        print(f"{name}/suite_wall,{(time.time() - t0) * 1e6:.0f},done",
              flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
