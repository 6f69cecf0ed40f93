"""Scan decode: host seconds a window query spent walking its scans' chunks
and stripes (the `scan.walk` spans: page headers, deframing, run walks), mean
of `TaskMetrics.scan_walk_ns` over the window's untraced queries in the
engine's ring. Thread-seconds: an ORC stripe's columns are walked side by side
on several threads, so this can exceed the scan's wall time."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    return S.mean_seconds(ctx, "scan_walk_ns")
