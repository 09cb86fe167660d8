# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One harness per paper artifact (Table 1, Fig. 5, Fig. 6, Table 2), plus the
sync-vs-async round-engine comparison, the kernel microbenches and the
roofline report over the dry-run artifacts.  REPRO_BENCH_FAST=0 switches to
the paper-scale (overnight) configuration.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (async_bench, fig5_energy, fig6_scalability,
                            fleet_bench, fleet_shard_bench, kernels_bench,
                            roofline, table1_accuracy, table2_valratio)
    print("name,us_per_call,derived")
    suites = [
        ("table1", table1_accuracy.main),
        ("fig5", fig5_energy.main),
        ("fig6", fig6_scalability.main),
        ("table2", table2_valratio.main),
        ("async", async_bench.main),
        ("kernels", kernels_bench.main),
        ("fleet", fleet_bench.main),
        # smoke only here (and a 1-device mesh unless XLA_FLAGS forced a
        # virtual multi-device runtime before this process started); the
        # recorded full-scale rows come from running the module directly
        ("fleet_shard", lambda: fleet_shard_bench.main(
            ["--smoke", "--no-write"])),
        ("roofline", roofline.main),
    ]
    failures = 0
    for name, fn in suites:
        t0 = time.time()
        try:
            fn()
            print(f"suite/{name},{(time.time() - t0) * 1e6:.1f},ok")
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"suite/{name},{(time.time() - t0) * 1e6:.1f},"
                  f"FAILED:{type(e).__name__}:{e}")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
