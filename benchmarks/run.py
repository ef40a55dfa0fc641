"""Benchmark orchestrator: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per the harness contract.
  table1              Table I (three input regimes)
  fig23               Figs 2/3 monotonicity
  bench_head_units    unit cost vs class count k (the paper's size claim)
  bench_kernels       fused reduced head vs unfused pipeline

``bench_serve`` (engine tokens/sec, reduced vs softmax head over the
paged-KV engine) is intentionally not in the default sweep — it takes a
few minutes; run it directly: python benchmarks/bench_serve.py
"""
import sys
import traceback


def main() -> None:
    from benchmarks import (bench_head_units, bench_kernels,
                            fig23_monotonicity, table1)
    sections = [
        ("table1", table1.main),
        ("fig23", fig23_monotonicity.main),
        ("bench_head_units", bench_head_units.main),
        ("bench_kernels", bench_kernels.main),
    ]
    failures = []
    for name, fn in sections:
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:
            failures.append(name)
            traceback.print_exc()
            print(f"{name},0,FAILED={e!r}")
    if failures:
        sys.exit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
