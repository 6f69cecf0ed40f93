"""Operator programs: program executions ("XLA Modules" events) that started
inside the traced query, per chip of the cell, launched through the compile
service or not. `dispatches_per_query` counts the service's alone; the gap
between the two is the eager launches."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    ev = E.events(ctx)
    if not ev or not any(ev["device_modules"].values()):
        return None
    lo, hi = E.window(ev["host_events"])
    used = E.devices_used(ctx, lo, hi)
    return sum(lo <= start < hi for dev in used
               for _, start, _ in ev["device_modules"].get(dev, [])
               ) / len(used)
