#!/usr/bin/env python
"""CI gate over the serve_obs artifact (BENCH_serve_obs.json).

Passes iff the obs-on arm held its throughput (within_5pct on the
``serve/obs_overhead.*`` record), the causal-tracing arm held its
throughput too (``serve/obs_trace_overhead.*`` at the default
``trace_sample_every``, with spans actually recorded and zero epoch
violations), AND the traced run produced a sampled observation for
every read-path stage — a breakdown with silent stages would mean the
tracer is wired to the wrong call sites.

    python scripts/check_obs_overhead.py bench_artifacts/BENCH_serve_obs.json
"""

from __future__ import annotations

import json
import sys

# the read-path stages, the first eight of repro.obs.READ_STAGES (the
# script must stay runnable without PYTHONPATH=src, so no import); the
# gated arms only read, so the write-side stages have nothing to observe
STAGES = ("admission", "coalesce", "cache_probe", "filter_probe", "dispatch",
          "compute", "resolve", "value_fetch")


def main() -> int:
    path = sys.argv[1] if len(sys.argv) > 1 else \
        "bench_artifacts/BENCH_serve_obs.json"
    with open(path) as f:
        art = json.load(f)
    results = {r["name"]: r for r in art["results"]}

    overhead = [r for n, r in results.items()
                if n.startswith("serve/obs_overhead.")]
    if not overhead:
        print(f"FAIL: no serve/obs_overhead record in {path}")
        return 1
    rec = overhead[0]
    ratio = rec["fields"].get("ratio")
    if rec["fields"].get("within_5pct") != "True":
        print(f"FAIL: obs-on throughput ratio {ratio} below 0.95 "
              f"({rec['derived']})")
        return 1

    tr = [r for n, r in results.items()
          if n.startswith("serve/obs_trace_overhead.")]
    if not tr:
        print(f"FAIL: no serve/obs_trace_overhead record in {path}")
        return 1
    trec = tr[0]
    tratio = trec["fields"].get("ratio")
    if trec["fields"].get("within_5pct") != "True":
        print(f"FAIL: causal-tracing throughput ratio {tratio} below "
              f"0.95 ({trec['derived']})")
        return 1
    if float(trec["fields"].get("traced", 0)) <= 0 \
            or float(trec["fields"].get("spans", 0)) <= 0:
        print(f"FAIL: tracing arm recorded no spans ({trec['derived']})")
        return 1
    if float(trec["fields"].get("epoch_violations", 0)) != 0:
        print(f"FAIL: tracing arm saw epoch violations "
              f"({trec['derived']})")
        return 1

    missing = [s for s in STAGES
               if results.get(f"serve/obs_stage.{s}", {})
               .get("fields", {}).get("count", 0) <= 0]
    if missing:
        print(f"FAIL: stages with no sampled observations: {missing}")
        return 1

    snap = art.get("obs", {}).get("snapshot", {})
    if "server_stage_us" not in snap:
        print("FAIL: artifact carries no obs snapshot")
        return 1

    print(f"OK: obs overhead ratio={ratio}, tracing ratio={tratio}, "
          f"all {len(STAGES)} stages observed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
