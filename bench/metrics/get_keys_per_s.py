"""GET keys answered inside the window, over the window's seconds."""


def read(ctx):
    n = ctx.rec.window_keys_done
    return n / ctx.seconds if n else None
