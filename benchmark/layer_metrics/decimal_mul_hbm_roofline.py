"""Kernels / device: the least time the chip's HBM needs for the bytes the
decimal projection has to move whatever implements it (`projection_least_bytes`
of the cell's query file: per row that passes the filter, three 8-byte
operands read and two 128-bit products written), over the device seconds of
the `exec.project*` programs in the traced query. The file is the one the
harness's generator wrote for this run (`<checkout>/.bench_work/data/<config>/
<fact table>.parquet`); without it, or without a trace, nothing is read."""
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
    project_s = _load("layer_metrics", "project_device_s").read(ctx)
    if not peaks or not project_s:
        return None
    cell = ctx["cell"]
    config = cell["config"]
    path = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_work",
                        "data", config["name"],
                        config["fact_table"] + ".parquet")
    least = [getattr(_load("queries", q), "projection_least_bytes", None)
             for q in cell["traffic"]["queries"]]
    if not os.path.exists(path) or None in least:
        return None
    least_s = sum(f(path) for f in least) / len(least) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / project_s
