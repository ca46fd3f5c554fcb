"""Peak device memory after the window, fullest device."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 1e9
