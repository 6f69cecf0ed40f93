"""Device: `memory_stats()["peak_bytes_in_use"]` read when the window closed."""


def read(ctx):
    return ctx["memory_peak_bytes"]
