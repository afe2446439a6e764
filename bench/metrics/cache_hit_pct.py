"""Hot-key cache: share of GET keys answered from the cache, of those
answered from the cache or probed in the store, over the window."""


def read(ctx):
    hit = ctx.delta("server_served_from_cache_total")
    probe = ctx.delta("server_store_probe_keys_total")
    if hit is None or probe is None or hit + probe == 0:
        return None
    return 100.0 * hit / (hit + probe)
