"""Compile: `TaskMetrics.compile_count` summed over the window's queries.
Anything but 0 means a program compiled inside the measured window."""


def read(ctx):
    reads = [r["compiles"] for r in ctx["window"] if "compiles" in r]
    return sum(reads) if reads else None
