"""Compile: `TaskMetrics.compile_ns` of the first `collect()` of each query of
the mix (compiles and reloads from the persistent cache alike), summed."""


def read(ctx):
    reads = [r["compile_s"] for r in ctx["firsts"] if "compile_s" in r]
    return sum(reads) if reads else None
