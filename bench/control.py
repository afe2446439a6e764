"""The control: the plain reference put in the program's place, with one
guarantee broken, so that the comparison can be seen to fail.

The configurations state exact answers, and each names the guarantee its
control breaks (``control``), the step that would tempt a later change:

* ``approximate_index`` -- a loaded key is answered the way a learned
  index would without its last-mile search: the record at the slot a
  piecewise-linear model of the sorted keys predicts (knots every
  ``KNOT`` keys, float64), without looking for the key around it (the
  program reads 2 delta + 3 keys per probe to make the answer exact);
* ``partial_record`` -- a GET returns only the first half of the
  record's bytes (YCSB reads all fields), the rest zero: a value fetch
  of half the bytes;
* ``stale_after_roll`` -- a memtable roll drops the rolled writes and
  keeps serving the snapshot as it was: a device state that is not
  rebuilt after the roll, so an acknowledged write is lost to later GETs.

Otherwise it keeps writes exactly, and ``roll`` keeps them all.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

runs one cell with the control in the program's place and prints the
same result line as ``run.py``; ``correct`` has to come out false.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import collections
import sys
from pathlib import Path

import numpy as np

KNOT = 64


class ReferenceServer:
    """``submit``/``tick`` over the reference: each tick answers every
    queued request in submission order."""

    def __init__(self, sorted_keys: np.ndarray, values, mode: str,
                 shards: int, capacity: int = 256) -> None:
        if mode not in ("exact", "approximate_index", "partial_record",
                        "stale_after_roll"):
            raise ValueError(f"unknown control {mode!r}")
        self.keys = np.asarray(sorted_keys, np.int64)
        self.values = values
        self.mode = mode
        self.shards = shards
        self.capacity = capacity
        self.queue: collections.deque = collections.deque()
        self.inflight = 0
        self.later: dict = {}                 # key -> value bytes or None
        self.knot_keys = self.keys[::KNOT].astype(np.float64)
        self.knot_pos = np.arange(0, self.keys.shape[0], KNOT,
                                  dtype=np.float64)

    def roll(self) -> int:
        """The memtable roll: the writes so far join the snapshot (or, in
        ``stale_after_roll``, are lost).  Returns the shards rolled."""
        if self.mode == "stale_after_roll":
            self.later = {}
        return self.shards

    def submit(self, req) -> bool:
        if len(self.queue) >= self.capacity:
            return False
        self.queue.append(req)
        return True

    def _loaded(self, probes: np.ndarray):
        n = self.keys.shape[0]
        if self.mode == "approximate_index":
            pos = np.rint(np.interp(probes.astype(np.float64),
                                    self.knot_keys, self.knot_pos))
            pos = np.clip(pos.astype(np.int64), 0, n - 1)
            found = np.ones(probes.shape[0], bool)
        else:
            pos = np.clip(np.searchsorted(self.keys, probes), 0, n - 1)
            found = self.keys[pos] == probes
        vals = self.values(self.keys[pos], 0)
        vals[~found] = 0
        if self.mode == "partial_record":
            vals[:, vals.shape[1] // 2:] = 0
        return found, vals

    def tick(self) -> list:
        done = []
        while self.queue:
            r = self.queue.popleft()
            if r.op == "get":
                found, vals = self._loaded(r.keys)
                for i, k in enumerate(r.keys.tolist()):
                    if k in self.later:
                        v = self.later[k]
                        found[i] = v is not None
                        vals[i] = 0 if v is None else v
                        if self.mode == "partial_record":
                            vals[i, vals.shape[1] // 2:] = 0
                r.found, r.result = found, vals
            elif r.op == "put":
                for k, v in zip(r.keys.tolist(), r.values):
                    self.later[k] = v.copy()
            else:
                for k in r.keys.tolist():
                    self.later[k] = None
            r.done = True
            done.append(r)
        return done


def factory(exact: bool = False):
    """The control the configuration names, or with ``exact`` the plain
    reference itself."""
    def make(cfg, sorted_keys, values):
        return ReferenceServer(sorted_keys, values,
                               "exact" if exact else cfg["control"],
                               int(cfg["shards"]))
    return make


def main(argv=None) -> int:
    import argparse
    import json
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root))
    from bench.harness import run_cell
    from bench.run import print_check
    from bench.spec import load_cell
    out = run_cell(load_cell(root, args.workload), args.seed, args.seconds,
                   False, t_start, server_factory=factory())
    print_check(out["check"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
