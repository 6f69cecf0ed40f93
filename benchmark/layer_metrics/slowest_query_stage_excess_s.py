"""Entry: for the slowest of the window's untraced queries (largest
`wall_s` in the engine's ring), the largest excess over the window's median
of any stage field (`scan_walk_ns`, `scan_pack_ns`, `h2d_ns`,
`dispatch_ns`, `host_sync_ns`, `d2h_ns`, `plan_rewrite_ns`). Beside
`slowest_query_s` less the median query: close to it, the stall was in a
stage the engine names; far short of it, outside every one."""
import importlib.util
import os
import statistics

_spec = importlib.util.spec_from_file_location(
    "bench_stages", os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "_stages.py"))
S = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(S)


def read(ctx):
    recs = S.window_records(ctx)
    if recs is None:
        return None
    names = set(S.table()[0].values())
    if any(f not in tm for _, tm in recs for f in names):
        return None
    _, slow = max(recs, key=lambda r: r[0])
    return max(slow[f] - statistics.median(tm[f] for _, tm in recs)
               for f in names) / 1e9
