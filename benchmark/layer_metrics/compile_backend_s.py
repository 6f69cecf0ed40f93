"""Compile: `CompileStats.totals()` `backend_ns` when the window closes: of
every compile of the process, the seconds inside `Lowered.compile()`: XLA's
compile, or the load from JAX's persistent cache where the program is
cached. With `window_compiles` 0 all of it is set-up's (the first query's)."""


def read(ctx):
    from spark_rapids_tpu.compile.service import CompileService
    totals = CompileService.get().stats.totals()
    if "backend_ns" not in totals:
        return None
    return totals["backend_ns"] / 1e9
