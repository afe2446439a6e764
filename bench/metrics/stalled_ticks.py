"""Server tick loop: ticks in the window that took longer than the
server's stall threshold (50 ms), from ``server_stalled_ticks_total``."""


def read(ctx):
    return ctx.delta("server_stalled_ticks_total")
