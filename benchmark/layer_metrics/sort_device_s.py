"""Operator programs: device seconds, in the traced query, of the programs the
compile service runs under an `exec.sort*` or `exec.topk*` op tag: the key
sorts and the gathers of every column by their permutation."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    parts = [E.tagged_seconds(ctx, family)
             for family in ("exec.sort", "exec.topk")]
    return None if None in parts else sum(parts)
