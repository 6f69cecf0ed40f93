"""Scan decode: host seconds a window query spent on its scans' signatures,
buckets and packed buffers (the `scan.pack` spans), mean of
`TaskMetrics.scan_pack_ns` over the window's untraced queries in the
engine's ring."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    return S.mean_seconds(ctx, "scan_pack_ns")
