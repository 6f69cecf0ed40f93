"""Operator programs: device seconds, in the traced query, of the programs the
compile service runs under an `exec.join.*` op tag, in a cell whose joins
carry wide strings: the probes' searches and, above all, the expands' gathers
of every payload column for every joined row. The same reading as
`join_device_s`, which lists the cell whose joins carry a 22-byte brand for
258 rows; here a 200-byte description rides with 628,023."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    return E.tagged_seconds(ctx, E.JOIN_OPS)
