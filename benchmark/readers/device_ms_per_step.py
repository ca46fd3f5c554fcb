"""Device time of a step: the union of the intervals in which an
operation ran on the busiest device, over the steps of the window."""


def read(ctx):
    return ctx["trace"].device_ms_per_step()
