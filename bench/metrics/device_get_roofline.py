"""Device GET's share of its roofline: the least time the chip could take
for the GET work (``bench/work.py``: bytes over HBM bandwidth, or
operations over the highest compute peak, whichever is larger), over the
device time the GET programs took in the trace.  Peaks from
``bench/peaks.json`` by ``device_kind``; a kind not listed is an error."""

import json
from pathlib import Path

from bench.tracing import get_device_s

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.get("get_work") or not tr["get_work"]["probes"]:
        return None
    dev = get_device_s(tr)
    if dev <= 0:
        return None
    peaks = json.loads(PEAKS.read_text())
    if ctx.device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {ctx.device_kind!r} "
                       f"in {PEAKS}")
    p = peaks[ctx.device_kind]
    w = tr["get_work"]
    least = max(w["bytes"] / p["hbm_bytes_per_s"],
                w["ops"] / max(p["bf16_flop_per_s"], p["int8_op_per_s"]))
    return 100.0 * least / dev
