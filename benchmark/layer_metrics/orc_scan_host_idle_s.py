"""Scan decode: seconds of the traced query in which the cell's busiest chip
ran nothing while the ORC scan's host side worked: the idle gaps covered by
the engine's `scan.walk` (read, deframe, the RLEv2 run walk), `scan.pack`
(padding the tables to their buckets, the packed buffer) and `scan.h2d` (the
transfer) spans. The cell's one scan is the ORC one. An engine whose ORC scan
opens none of them reads 0 here, and `orc_scan_device_s` nothing."""
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
