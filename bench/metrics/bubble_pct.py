"""Maintenance bubble: share of the window the server spent in
maintenance rounds (store ticks and the coordinator round), from
``server_bubble_us_total``.  The window is the measured one -- the
traced slice in a traced run."""


def read(ctx):
    us = ctx.delta("server_bubble_us_total")
    window_s = ctx.rec.t1 - ctx.rec.t0
    if us is None or window_s <= 0:
        return None
    return 100.0 * us / 1e6 / window_s
