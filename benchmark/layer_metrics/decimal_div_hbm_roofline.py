"""Kernels / device: the least time the chip's HBM needs for the bytes the
exact decimal division has to move whatever implements it
(`division_least_bytes` of the cell's query file: per row of the answer, two
128-bit operands read and one 128-bit quotient written), over the device
seconds of the `exec.project*` programs in the traced query (the projection
that holds the division and the dimensions' two renaming projections, which
run for microseconds: the share is understated by them, never overstated).
The files are those the harness's generator wrote for this run
(`<checkout>/.bench_work/data/<config>/<table>.parquet`); without them, or
without a trace, nothing is read."""
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", os.path.join(os.path.dirname(HERE), kind,
                                             name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    peaks = ctx.get("peaks")
    eng = _load("layer_metrics", "_engine_trace")
    project_s = eng.tagged_seconds(ctx, "exec.project")
    if not peaks or not project_s:
        return None
    cell = ctx["cell"]
    data = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_work",
                        "data", cell["config"]["name"])
    queries = [_load("queries", q) for q in cell["traffic"]["queries"]]
    least = [getattr(q, "division_least_bytes", None) for q in queries]
    paths = {t: os.path.join(data, t + ".parquet")
             for q in queries for t in q.TABLES}
    if None in least or not all(os.path.exists(p) for p in paths.values()):
        return None
    least_s = sum(f(paths) for f in least) / len(least) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / project_s
