"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig4,...]``
prints ``table,name,us_per_call,derived`` CSV rows.

All groups run in this one process.  The groups that fake w workers
(``fig5``, ``delta_stream``) do so in child processes pinned to the CPU
backend (``JAX_PLATFORMS=cpu``), so no child ever needs a chip this process
holds.  These groups measure CPU-backend or interpret-mode behaviour; none
of their numbers is a chip measurement.
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: fig4,tab2_3,fig5,fig6,tab5,tab4,"
                    "intersect,delta_stream,multi_query,epoch_latency,"
                    "nary_stream,serve_load,composite_sweep")
    args = ap.parse_args()

    from benchmarks import (baseline_compare, batch_size, composite_sweep,
                            cost_table, delta_stream, epoch_latency,
                            intersect_bench, multi_query, nary_stream,
                            optimizations, scaling, serve_load, throughput)
    table = {
        "fig4": cost_table.main,
        "tab2_3": baseline_compare.main,
        "fig5": scaling.main,
        "fig6": batch_size.main,
        "tab5": optimizations.main,
        "tab4": throughput.main,
        "intersect": intersect_bench.main,  # -> BENCH_intersect.json
        "delta_stream": delta_stream.main,  # -> BENCH_delta_stream.json
        "multi_query": multi_query.main,  # -> BENCH_multi_query.json
        "epoch_latency": epoch_latency.main,  # -> BENCH_epoch_latency.json
        "nary_stream": nary_stream.main,  # -> BENCH_nary_stream.json
        "serve_load": serve_load.main,  # -> BENCH_serve_load.json
        "composite_sweep": composite_sweep.main,
        # ^ -> BENCH_composite_sweep.json
    }
    picks = list(table) if args.only == "all" else args.only.split(",")
    print("table,name,us_per_call,derived")
    failures = 0
    for name in picks:
        t0 = time.time()
        try:
            table[name]()
        except Exception:
            traceback.print_exc()
            failures += 1
            print(f"{name},FAILED,0,", flush=True)
        print(f"# {name} finished in {time.time() - t0:.1f}s",
              file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark groups failed")


if __name__ == '__main__':
    main()
