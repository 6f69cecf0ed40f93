"""Kernels / device: device seconds, in the traced query, that run under no
engine name: programs whose name is no `program_name(op)` of an op tag the
compile service has run (eager `jnp` indexing at the sink and in the decode's
helpers, anything launched past the service), and operations inside no
program at all."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_engine_trace", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_engine_trace.py"))
E = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(E)


def read(ctx):
    eng = E.engine()
    if not E.events(ctx) or eng is None:
        return None
    program_name, _, ops = eng
    tagged = {program_name(op) for op in ops}
    return E.program_seconds(
        ctx, lambda op: op in tagged).get(False, 0.0)
