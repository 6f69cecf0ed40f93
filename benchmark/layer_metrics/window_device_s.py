"""Operator programs: device seconds, in the traced query, of the programs the
compile service runs under an `exec.window*` op tag: the sort by the partition
keys, the partitions' ends, and the window functions (in TPC-DS query 98 one
exact decimal sum over the whole partition)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    return E.tagged_seconds(ctx, "exec.window")
