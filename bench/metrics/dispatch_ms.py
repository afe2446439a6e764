"""Sharded store, host half: mean host time of ``dispatch_get`` (memtable
overlay, padding, transfer, enqueue) per sampled batch, from the
``StageTracer`` stage ``dispatch``.  Host time, not device time."""


def read(ctx):
    st = ctx.stage("dispatch")
    if st is None or st[1] <= 0:
        return None
    return st[0] / st[1] / 1e3
