"""Value fetch: value-log read time the tick loop waited out, per store
dispatch, over the window (``fleet_value_fetch_exposed_us_total``)."""


def read(ctx):
    us = ctx.delta("fleet_value_fetch_exposed_us_total")
    batches = ctx.delta("server_pipeline_dispatched")
    if us is None or not batches:
        return None
    return us / batches / 1e3
