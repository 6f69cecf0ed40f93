"""Device: idle-gap seconds of the cell's busiest chip in the traced query
that no stage span explains: under no `srt:` host event, or whose innermost
one is not in the engine's stage table (`op.*`, `sink.rows`,
`pipeline:prefetch`, `scan:<fmt>`). With the stages' idle it adds up to
the query's idle time (`_stages.py`)."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    split = S.idle_by_stage(ctx)
    return None if split is None else split.get(S.UNSTAGED, 0) / 1e9
