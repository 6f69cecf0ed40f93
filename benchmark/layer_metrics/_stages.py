"""Shared by the readers of the engine's stage spans and the counters they
charge: the table `utils/tracing.STAGES` (stage span -> `TaskMetrics`
field) and `stage_of` are imported from the engine, as `_engine_trace.py`
imports `program_name` and the prefix, so what a reader attributes and what
the engine counts cannot drift apart. Where the engine has no such table (an
older commit), `table()` is None and every reader reports nothing.

Not a metric: no entry of `BENCHMARK.json` names this file.

Ring readers take the window's untraced queries from the engine's ring of
recent queries (`TpuSession.recent_queries()`), its last
`len(ctx["window"])` entries, as `slowest_query_device_wait_s.py` does.
Stage times there are thread-seconds: walks that run side by side add up.

Trace readers put each idle nanosecond of the cell's busiest chip in the
traced query to the innermost `srt:` host event over it, innermost being
the shortest. The trace's host events carry no thread, so this holds across
threads: a short span on one thread outranks a long one on another. The
benchmark's own `bench.collect` has no prefix and counts for nothing; idle
under no `srt:` event, or whose innermost event is no stage (`op.*`,
`sink.rows`, `pipeline:prefetch`, `scan:<fmt>`), is `UNSTAGED`. The stages
and `UNSTAGED` add up to the traced query's idle time to the nanosecond.
`trace_reduce.read_xplane` keeps host events of 20 us or more, so a shorter
span leaves its idle to the span around it.

    python benchmark/layer_metrics/_stages.py <trace.xplane.pb> [chips]

prints that split for a trace `run.py --trace 1` left under
`.bench_work/trace/`."""

from __future__ import annotations

import heapq
import importlib.util
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
UNSTAGED = "unstaged"


def _engine_trace():
    spec = importlib.util.spec_from_file_location(
        "bench_engine_trace", os.path.join(HERE, "_engine_trace.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


E = _engine_trace()


def table():
    """`(STAGES, stage_of, SPAN_PREFIX)` of the engine in this process, or
    None where it has no stage table."""
    try:
        from spark_rapids_tpu.utils.tracing import (SPAN_PREFIX, STAGES,
                                                    stage_of)
    except ImportError:
        return None
    return STAGES, stage_of, SPAN_PREFIX


def window_records(ctx):
    """`(wall_s, task_metrics)` of the window's untraced queries from the
    engine's ring, or None where the engine has no stage table or ring."""
    if table() is None or not ctx.get("window"):
        return None
    from spark_rapids_tpu.plugin import TpuSession
    if not hasattr(TpuSession, "recent_queries"):
        return None
    recent = TpuSession.recent_queries()[-len(ctx["window"]):]
    return [(wall, tm) for wall, _, tm in recent] or None


def mean_seconds(ctx, field: str):
    """Mean of `field` (nanoseconds) per window query, in seconds."""
    recs = window_records(ctx)
    if recs is None or any(field not in tm for _, tm in recs):
        return None
    return statistics.mean(tm[field] for _, tm in recs) / 1e9


def attribute(gaps: list, host: list, prefix: str, stage_of) -> dict:
    """Nanoseconds of `gaps` (disjoint `(start, stop)`, in order) by the
    stage of the shortest `prefix` host event over each nanosecond, the
    rest under `UNSTAGED`. A sweep over the cuts that events and gaps make:
    in each piece the events open over it are the same."""
    evs = sorted((s, s + d, d, n[len(prefix):]) for n, s, d in host
                 if n.startswith(prefix) and d > 0)
    out: dict = {}
    heap: list = []  # (duration, stop, name) of events opened so far
    i = 0
    cuts = sorted({x for s, e, _, _ in evs for x in (s, e)})
    j = 0
    for a, b in gaps:
        while j < len(cuts) and cuts[j] <= a:
            j += 1
        k = j
        points = [a]
        while k < len(cuts) and cuts[k] < b:
            points.append(cuts[k])
            k += 1
        points.append(b)
        for x, y in zip(points, points[1:]):
            while i < len(evs) and evs[i][0] <= x:
                heapq.heappush(heap, (evs[i][2], evs[i][1], evs[i][3]))
                i += 1
            while heap and heap[0][1] <= x:
                heapq.heappop(heap)
            key = (stage_of(heap[0][2]) if heap else None) or UNSTAGED
            out[key] = out.get(key, 0) + y - x
    return out


def idle_by_stage(ctx):
    """Idle nanoseconds of the traced query by stage (`UNSTAGED` for the
    rest), or None without a trace or a stage table."""
    tab = table()
    if tab is None or not E.events(ctx):
        return None
    gaps, host = E.idle_gaps(ctx)
    return attribute(gaps, host, tab[2], tab[1])


def _main(argv) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    path, chips = argv[1], int(argv[2]) if len(argv) > 2 else 1
    device_ops, device_modules, host, _ = E.T.read_xplane(path)
    ctx = {"cell": {"chips": chips},
           "trace": {"events": {"device_ops": device_ops,
                                "device_modules": device_modules,
                                "host_events": host}}}
    split = idle_by_stage(ctx)
    if split is None:
        print("no stage table in this engine")
        return 1
    red = E.T.reduce_events(device_ops, host, device_modules=device_modules,
                            chips=chips)
    for key, ns in sorted(split.items(), key=lambda kv: -kv[1]):
        print(f"{key:16s} {ns / 1e9:.6f} s")
    print(f"{'sum':16s} {sum(split.values()) / 1e9:.6f} s; "
          f"window_s - busy_s {red['window_s'] - red['busy_s']:.6f} s")
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv))
