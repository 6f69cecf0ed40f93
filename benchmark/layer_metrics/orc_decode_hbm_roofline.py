"""Scan decode: the least time the chip's HBM needs for the bytes the ORC
decode has to move whatever implements it (`decode_bytes` of the cell's query
file: the seven columns' uncompressed streams, counted from the file's stripe
footers, read once, and the batch they become written once), over the device
seconds of the `io.orc.*` programs in the traced query (`orc_scan_device_s`).
The file is the one the harness's generator wrote for this run
(`<checkout>/.bench_work/data/<config>/<fact table>.orc`); without it, without
a trace or without such programs nothing is read."""
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
    scan_s = _load("layer_metrics", "orc_scan_device_s").read(ctx)
    if not peaks or not scan_s:
        return None
    cell = ctx["cell"]
    config = cell["config"]
    path = os.path.join(os.path.dirname(os.path.dirname(HERE)), ".bench_work",
                        "data", config["name"], config["fact_table"] + ".orc")
    least = [getattr(_load("queries", q), "decode_bytes", None)
             for q in cell["traffic"]["queries"]]
    if not os.path.exists(path) or None in least:
        return None
    least_s = sum(f(path) for f in least) / len(least) \
        / peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / scan_s
