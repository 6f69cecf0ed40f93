"""Operator programs: device seconds, in the traced query, of the programs the
compile service runs under an `exec.project*` op tag: the expressions of a
projection, in TPC-H query 1 the two exact decimal multiplies a row."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    return E.tagged_seconds(ctx, "exec.project")
