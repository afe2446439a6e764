"""Benchmark driver — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (common.emit).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig9 fig13  # subset
"""

from __future__ import annotations

import sys
import time

SUITES = {
    "fig8": ("bench_paths", "latency breakdown by lookup step"),
    "fig9": ("bench_datasets", "datasets: wisckey vs bourbon vs level"),
    "fig10": ("bench_load_orders", "sequential vs random load"),
    "fig11": ("bench_distributions", "request distributions"),
    "fig12": ("bench_range", "range queries"),
    "fig13": ("bench_mixed", "mixed writes: cba vs always vs offline + table1"),
    "fig14": ("bench_ycsb", "YCSB A-F"),
    "ycsb": ("bench_ycsb",
             "filter plane: zipf lookups at 0/25/50/75% miss ratios, "
             "filters on vs off (probe counts + FPR in the artifact)",
             "run_miss"),
    "fig15": ("bench_sosd", "SOSD datasets"),
    "fig17": ("bench_error_bound", "delta sweep + space overheads"),
    "table2": ("bench_storage", "fast-storage + limited-memory tier model"),
    "recovery": ("bench_recovery",
                 "durable engine: reopen w/ persisted models vs relearn; "
                 "value-log GC"),
    "gc": ("bench_gc_policy",
           "manual vs CBA-scheduled value-log GC under sustained "
           "overwrites"),
    "dist_recovery": ("bench_dist_recovery",
                      "sharded store killed mid-write: reopen from shard "
                      "dirs vs rebuild from scratch"),
    "serve": ("bench_serve",
              "batched request-serving front end vs naive per-request "
              "loop; pipelined (multi-batch in-flight) vs synchronous "
              "tick loop at 16/64/256 clients; fleet-stall time with vs "
              "without the maintenance coordinator; obs-on vs obs-off "
              "tracing overhead"),
    # obs-only subset of serve: the CI overhead gate reruns just this
    "serve_obs": ("bench_serve",
                  "per-stage latency breakdown + obs-on within 5% of "
                  "obs-off throughput at 64 clients", "run_obs_only"),
}


def main() -> None:
    from benchmarks import common
    from repro.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}")

    want = sys.argv[1:] or [k for k in SUITES if k != "serve_obs"]
    print("name,us_per_call,derived")
    for key in want:
        entry = SUITES[key]
        mod_name, desc = entry[0], entry[1]
        fn_name = entry[2] if len(entry) > 2 else "run"
        mod = __import__(f"benchmarks.{mod_name}", fromlist=[fn_name])
        t0 = time.time()
        print(f"# {key}: {desc}")
        getattr(mod, fn_name)()
        art = common.write_artifact(key)
        if art:
            print(f"# {key} artifact: {art}")
        print(f"# {key} done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
