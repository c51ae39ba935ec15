"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  bench_fig1      Fig. 1  KV size + capacity/bandwidth scaling
  bench_fig4      Fig. 4  batch capability + throughput, 5 methods
  bench_fig5      Fig. 5  disaggregated node MFU/memory utilization
  bench_kernels   Fig. 2a GEMV->GEMM intensity + kernel timings
  bench_serving   measured engine throughput vs recompute baseline
  bench_roofline  §Roofline terms from dry-run records
"""
import sys

from repro.launch.compile_cache import init_compile_cache


def main() -> None:
    init_compile_cache()
    mods = ["bench_fig1", "bench_fig4", "bench_fig5", "bench_kernels",
            "bench_router", "bench_serving", "bench_roofline"]
    if len(sys.argv) > 1:
        mods = [m for m in mods if any(a in m for a in sys.argv[1:])]
    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        try:
            mod.run(lambda n, us, d: print(f"{n},{us:.2f},{d}", flush=True))
        except Exception as e:  # keep the harness going
            failures += 1
            print(f"{name}/ERROR,0.00,{type(e).__name__}:{e}", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
