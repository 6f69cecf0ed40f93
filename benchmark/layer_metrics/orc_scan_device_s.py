"""Scan decode: device seconds, in the traced query, of the programs the
compile service runs under an `io.orc.*` op tag: the ORC stripe decode (the
RLEv2 run expansion, the bit-window unpack, the varint fold, the dictionary
gather), whatever number of programs implements it."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    return E.tagged_seconds(ctx, "io.orc.")
