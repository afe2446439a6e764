"""Admission and batching: keys per coalesced batch over the window, from
the server's ``batch_keys`` and ``batches`` counts."""


def read(ctx):
    keys = ctx.delta("server_batch_keys")
    batches = ctx.delta("server_batches_total")
    return keys / batches if keys is not None and batches else None
