"""Entry (`collect()`): the slowest single `collect()` among the window's
untraced queries. A stall (a recompile, a spill, a host pause) shows here
where the window's mean dilutes it; every run, traced or not, also prints it
under `window` in its result line. Per-layer and not end-to-end because the
queries of one process differ by 0.1% while processes differ by percents
(PERF.md): as an end-to-end metric it was `query_s` twice."""


def read(ctx):
    reads = [r["seconds"] for r in ctx["window"] if "seconds" in r]
    return max(reads) if reads else None
