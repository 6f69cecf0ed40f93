"""Plan rewrite: median of 20 `Overrides(conf).apply(plan)` calls on
the cell's first query, timed by the benchmark after the window."""


def read(ctx):
    return ctx["plan_rewrite_ms"]
