"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and
writes one machine-readable ``BENCH_<module>.json`` per bench (per-row
timing, QPS where applicable, and QueryCost breakdowns) so the perf
trajectory is tracked across PRs.
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_arch_dims, bench_distortion,
                            bench_kernels, bench_refinement, bench_serving,
                            bench_storage, bench_streaming,
                            bench_throughput, bench_tiered, common)

    print("name,us_per_call,derived")
    failures = 0
    for mod in [bench_storage, bench_arch_dims, bench_kernels,
                bench_distortion, bench_throughput, bench_refinement,
                bench_streaming, bench_tiered, bench_serving]:
        short = mod.__name__.rsplit(".", 1)[-1]
        try:
            mod.run()
            common.write_json(short)
        except Exception:
            common.take_records()    # drop partial records of the failure
            failures += 1
            print(f"# FAILED {mod.__name__}", file=sys.stderr)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
