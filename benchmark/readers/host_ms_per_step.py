"""Host time inside the Module calls of a step (forward, update,
update_metric), from the benchmark's own clock around them: the time in
the closing barrier and in the wait for the device is outside it."""


def read(ctx):
    win = ctx["window"]
    return win["host_s"] * 1e3 / win["steps"]
