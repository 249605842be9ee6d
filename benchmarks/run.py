# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver — one module per paper table/figure:

  Table 2  -> bench_model_caching       Table 7  -> bench_comm_operators
  Table 3  -> bench_prefill_throughput  Table 8/9-> bench_mla_operator
  Table 4  -> bench_decode_throughput   Table 10 -> bench_gemm_operator
  Table 5  -> bench_tpot_slo            Fig 20/21-> bench_microbatch
  Table 6  -> bench_quant_accuracy      Fig 22   -> bench_mtp
  Fig 23   -> bench_context_caching     §Roofline-> bench_roofline

Run: PYTHONPATH=src python -m benchmarks.run [--only NAME]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    "bench_model_caching",
    "bench_comm_operators",
    "bench_mla_operator",
    "bench_gemm_operator",
    "bench_quant_accuracy",
    "bench_microbatch",
    "bench_mtp",
    "bench_context_caching",
    "bench_prefill_throughput",
    "bench_decode_throughput",
    "bench_tpot_slo",
    "bench_roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    mods = [m for m in MODULES if args.only is None or args.only in m]
    failures = []
    for name in mods:
        print(f"\n=== {name} " + "=" * max(0, 60 - len(name)), flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()[-1500:]}",
                  flush=True)
    if failures:
        print(f"\n# FAILURES: {failures}")
        sys.exit(1)
    print("\n# all benchmarks completed")


if __name__ == "__main__":
    main()
