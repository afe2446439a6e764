"""Median latency of every GET request due in the window: from its due
time (open loop) or its submission (closed loop) to its answer."""

from bench.harness import percentile_ms


def read(ctx):
    return percentile_ms(ctx.rec.get_lat, 50)
