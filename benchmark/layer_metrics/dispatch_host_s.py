"""Operator programs: host seconds a window query spent in the compile
service's dispatches (the `dispatch.<op>` annotations: digest, lookup,
launch), mean of `TaskMetrics.dispatch_ns` over the window's untraced
queries in the engine's ring. Over `dispatches_per_query` it is the host
cost of one launch."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    return S.mean_seconds(ctx, "dispatch_ns")
