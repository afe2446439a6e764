"""Write-ahead log: mean host time of one durability barrier (the tick's
``wal_sync``) over the window, from ``server_wal_sync_us_total`` and
``server_wal_syncs_total``."""


def read(ctx):
    us = ctx.delta("server_wal_sync_us_total")
    syncs = ctx.delta("server_wal_syncs_total")
    if us is None or not syncs:
        return None
    return us / syncs / 1e3
