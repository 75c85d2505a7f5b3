"""Benchmark harness entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus a summary footer on stderr).

  bench_latency     -> Fig. 5  (TTFT + E2E, 4 models x 2 datasets x 2 HW)
  bench_tail        -> Fig. 6  (P50/P95)
  bench_throughput  -> Fig. 7  (tokens/s vs batch 1..12)
  bench_memory      -> Table II (peak memory + GPU-only reference)
  bench_predictor   -> Table III (Top-k / at-least-half accuracy)
  roofline          -> §Roofline terms from the dry-run artifacts

--quick runs a reduced matrix (used by CI/pytest).
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (bench_latency, bench_memory, bench_predictor,
                            bench_tail, bench_throughput, roofline)
    benches = {
        "latency": bench_latency.run,
        "tail": bench_tail.run,
        "throughput": bench_throughput.run,
        "memory": bench_memory.run,
        "predictor": bench_predictor.run,
        "roofline": roofline.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    print("name,us_per_call,derived")
    n = 0
    for bname, fn in benches.items():
        t0 = time.time()
        try:
            rows = fn(quick=args.quick)
        except Exception as e:  # keep the harness running
            print(f"{bname}/ERROR,0.0,{type(e).__name__}:{e}",
                  file=sys.stdout)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
            n += 1
        print(f"# {bname} done in {time.time() - t0:.1f}s", file=sys.stderr)
    print(f"# total rows: {n}", file=sys.stderr)


if __name__ == "__main__":
    main()
