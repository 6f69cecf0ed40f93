"""Scan decode: device seconds, in the traced query, of the programs the
compile service runs under an `io.parquet.*` op tag (the decode programs of
the fact table and the dimensions)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    return E.tagged_seconds(ctx, E.SCAN_OPS)
