"""Hot-key cache: host time the server spent inside the cache (probe,
fill and write invalidation) per batch over the window, from
``server_cache_host_us_total`` and ``server_batches_total``."""


def read(ctx):
    us = ctx.delta("server_cache_host_us_total")
    batches = ctx.delta("server_batches_total")
    if us is None or not batches:
        return None
    return us / batches
