"""Set-up: process start to the start of the measured window (data, load,
device state, every compile, traffic warm-up), on the host clock."""


def read(ctx):
    return ctx.setup_s
