"""Device GET: device time of the GET programs (the fused every-shard
lookup and its filter probe) per dispatched batch, from the profiler
trace of the traced slice of the window."""

from bench.tracing import get_device_s


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.get("get_work") or not tr["get_work"]["batches"]:
        return None
    dev = get_device_s(tr)
    if dev <= 0:
        return None
    return dev / tr["get_work"]["batches"] * 1e6
