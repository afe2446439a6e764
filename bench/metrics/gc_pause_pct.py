"""Python runtime (collector): share of the window in which the garbage
collector paused the process, from the server's collector hook
(``server_gc_pause_us_total``).  The window is the measured one -- the
traced slice in a traced run."""


def read(ctx):
    us = ctx.delta("server_gc_pause_us_total")
    window_s = ctx.rec.t1 - ctx.rec.t0
    if us is None or window_s <= 0:
        return None
    return 100.0 * us / 1e6 / window_s
