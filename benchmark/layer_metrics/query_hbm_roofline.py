"""Kernels / device: the least time the chip's HBM needs for the bytes the
query has to move (`least_bytes` of the query's file: parquet bytes of the
columns read, their decoded bytes once, the result), over the device's busy
seconds in the traced query. The same work whatever implements it."""


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    if not trace or not peaks or not trace["busy_s"]:
        return None
    least_s = ctx["least_bytes"] / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
