"""Device: share of the traced query in which no operation ran on the chip:
1 - union of the device operations' intervals over the traced interval."""


def read(ctx):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
