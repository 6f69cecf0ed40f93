"""Compile: `CompileStats.totals()` `trace_ns + lower_ns` when the window
closes: of every compile of the process, the seconds spent running the
kernels' Python bodies into jaxprs and lowering them to StableHLO. With
`window_compiles` 0 all of it is set-up's (the first query's)."""


def read(ctx):
    from spark_rapids_tpu.compile.service import CompileService
    totals = CompileService.get().stats.totals()
    if "trace_ns" not in totals:
        return None
    return (totals["trace_ns"] + totals["lower_ns"]) / 1e9
