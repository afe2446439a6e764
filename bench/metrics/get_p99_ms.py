"""99th percentile of every GET request due in the window, on the same
clock as ``get_p50_ms``."""

from bench.harness import percentile_ms


def read(ctx):
    return percentile_ms(ctx.rec.get_lat, 99)
