"""Device state: how many times ``ShardedStore.device_state`` rebuilt and
re-uploaded the snapshot inside the window (``fleet_state_epoch``)."""


def read(ctx):
    return ctx.delta("fleet_state_epoch")
