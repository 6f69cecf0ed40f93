"""Scan decode: seconds of the traced query in which the cell's busiest chip
ran nothing while the scan's host side worked: the idle gaps covered by the
engine's `scan.walk` (chunk walk), `scan.pack` (signatures and the packed
buffer) and `scan.h2d` (the transfer) spans."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    under = E.idle_under(ctx, E.SCAN_SPANS)
    return None if under is None else under[0] / 1e9
