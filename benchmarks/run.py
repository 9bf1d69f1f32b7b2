"""Benchmark harness: one benchmark per paper table/figure + the roofline
aggregate. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table1,fig1,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (bench_async_engine, bench_client_store,
                        bench_cohort_source, bench_compression,
                        bench_roofline, bench_round_engine, fig1_quadratic,
                        fig3_bias_variance, fig4_ess, table1_client_cost,
                        table3_benchmark_sim, table3_lr_sim)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = {
    "table1": table1_client_cost,
    "fig1": fig1_quadratic,
    "fig3": fig3_bias_variance,
    "fig4": fig4_ess,
    "table3": table3_benchmark_sim,
    "table3lr": table3_lr_sim,
    "roofline": bench_roofline,
    "round_engine": bench_round_engine,
    "async_engine": bench_async_engine,
    "cohort_source": bench_cohort_source,
    "client_store": bench_client_store,
    "compression": bench_compression,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args(argv)
    enable_compile_cache()

    names = list(BENCHES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        mod = BENCHES[name]
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,,{e!r}")
            traceback.print_exc(file=sys.stderr)
            failures += 1
            continue
        for r in rows:
            us = r.get("us_per_call", "")
            us = f"{us:.1f}" if isinstance(us, float) else us
            print(f"{r['name']},{us},\"{r['derived']}\"")
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
