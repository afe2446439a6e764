"""The work a device GET must do, counted from the cell's probes, its
shards' PLR segment counts, the PLR error bound and the filter's hash
count -- not from the compiled program, so it reads the same whatever
implements the GET.

Per probe, on its owning shard only (the least a learned-index GET with
a bloom screen needs):

* read the probe key                          8 B
* read one 64-bit filter word per hash         8 B x k
* bisect the shard's segment starts           8 B x ceil(log2(nseg + 1))
* read the segment's slope and intercept      16 B
* read the key window around the prediction    8 B x (2 delta + 3)
* read the value pointer                       8 B
* write found and the value pointer           1 B + 8 B

Operations: k hashes of 4 integer ops each, one compare per bisect step,
one multiply-add (2), one compare per window key.  Padding probes are
not counted, so padding shows as a lower roofline share.
"""

from __future__ import annotations

import math

import numpy as np


def per_probe(nseg: int, delta: int, k_hashes: int, has_filter: bool):
    """(bytes, ops) of one probe on a shard of ``nseg`` PLR segments."""
    steps = math.ceil(math.log2(nseg + 1)) if nseg > 0 else 0
    window = 2 * delta + 3
    k = k_hashes if has_filter else 0
    nbytes = 8 + 8 * k + 8 * steps + 16 + 8 * window + 8 + 1 + 8
    ops = 4 * k + steps + 2 + window
    return nbytes, ops


def get_work(n_probes: list, nseg: np.ndarray, delta: int, k_hashes: int,
             has_filter: bool) -> dict:
    """Work of the dispatched device GETs: ``n_probes`` real probes per
    dispatch, spread over shards as evenly as the range split spreads the
    keys (the per-probe cost is averaged over the shards' segment counts)."""
    per = [per_probe(int(s), delta, k_hashes, has_filter)
           for s in np.asarray(nseg).tolist()]
    b = sum(p[0] for p in per) / len(per)
    o = sum(p[1] for p in per) / len(per)
    total = int(sum(n_probes))
    return {"batches": len(n_probes), "probes": total,
            "bytes": b * total, "ops": o * total}
