"""Load generator: 99th percentile of how late a request was sent (the
server's accepting submit minus its due time).  A starved generator or a
full queue shows here, not as a fast server."""

from bench.harness import percentile_ms


def read(ctx):
    return percentile_ms(ctx.rec.gen_lag, 99)
