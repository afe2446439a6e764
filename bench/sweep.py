"""Find the highest open-loop rate a cell sustains: one set-up, then a
short window at each rate, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 2000,4000,8000

A rate is sustained when no submit was rejected and the backlog (due but
not yet accepted, queued, or in flight) did not grow over its window.
Each rate prints one JSON line; a cell's ``rate_per_s`` is set once, from
such a sweep, at about 70% of the highest sustained rate.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import configure_cache
    configure_cache()
    from bench.harness import (Recorder, RequestMaker, Setup, Spans, System,
                               _now, drive, percentile_ms)
    from bench.oracle import Checker, Oracle
    from bench.spec import generator_kind, load_cell
    cell = load_cell(ROOT, args.workload)
    mix = dict(cell.traffic)
    system = System(cell, args.seed, Setup())
    try:
        srv = system.srv
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix["rate_per_s"] = rate
            rng = np.random.default_rng([args.seed, 10 + i])
            make_req = RequestMaker(mix, system.by_index, rng, system.values)
            make_req.version = 1_000_000 * (i + 1)
            gen_ = generator_kind(mix["kind"]).make(mix, rng, make_req)
            rec = Recorder(Checker(Oracle(system.sorted_keys[:1]),
                                   system.values), rng, 0)
            pending: collections.deque = collections.deque()
            t = _now()
            gen_.start(t)
            drive(srv, gen_, rec, t + 1.0, pending, Spans(False))
            rej0 = srv.queue.rejected
            back0 = len(pending) + len(srv.queue) + srv.inflight
            rec.t0 = _now()
            rec.t1 = rec.t0 + args.seconds
            drive(srv, gen_, rec, rec.t1, pending, Spans(False))
            back1 = len(pending) + len(srv.queue) + srv.inflight
            rejected = srv.queue.rejected - rej0
            row = {"rate_per_s": rate, "due": rec.attempted + len(pending),
                   "rejected": rejected, "backlog_start": back0,
                   "backlog_end": back1,
                   "sustained": rejected == 0 and back1 <= max(back0, 64),
                   "get_p50_ms": percentile_ms(rec.get_lat, 50),
                   "get_p99_ms": percentile_ms(rec.get_lat, 99),
                   "put_p99_ms": percentile_ms(rec.put_lat, 99),
                   "gen_lag_p99_ms": percentile_ms(rec.gen_lag, 99)}
            print(json.dumps(row), flush=True)
            drive(srv, gen_, rec, _now() + 60.0, pending, Spans(False),
                  accept_new=False)
            time.sleep(0.5)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
