"""Operator programs: idle-gap seconds of the cell's busiest chip in the
traced query whose innermost engine span is a `dispatch.<op>`: the chip
waiting while the host digests, looks up and launches a program. Innermost
is the shortest `srt:` host event over the nanosecond, across threads
(`_stages.py`)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    split = S.idle_by_stage(ctx)
    return None if split is None else split.get("dispatch.*", 0) / 1e9
