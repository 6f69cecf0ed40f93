"""Device: share of the traced query's idle-gap time (busiest chip) that some
engine span covers, the benchmark's own `bench.collect` root not counted:
what the engine can say about why the chip waited. Under 80, a seam of the
engine has no span."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    under = E.idle_under(ctx)
    if under is None or not under[1]:
        return None
    return 100.0 * under[0] / under[1]
