"""Shared by the readers of the engine's own names in a traced query: its
programs, which `compile/service.py` jits under `program_name(op)`, and its
host spans, which `utils/spans.py` annotates under `SPAN_PREFIX`. Both names
are imported from the engine, so a reader cannot drift from them; where the
engine has neither (a commit before ISSUE 27), `engine()` is None and a
reader reports nothing.

Not a metric: no entry of `BENCHMARK.json` names this file. The arithmetic on
intervals is `trace_reduce.py`'s, loaded by path; like it, everything here is
clipped to the `bench.collect` annotation's span and reads the cell's `chips`
busiest devices, so a one-chip cell on a host of four reads its own chip."""

from __future__ import annotations

import bisect
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SCAN_OPS, JOIN_OPS = "io.parquet.", "exec.join."
SCAN_SPANS = ("scan.walk", "scan.pack", "scan.h2d")


def _trace_reduce():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_reduce", os.path.join(os.path.dirname(HERE),
                                           "trace_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


T = _trace_reduce()


def engine():
    """`(program_name, SPAN_PREFIX, ops)` of the engine in this process,
    `ops` being every op tag its compile service has run; None where the
    engine names neither programs nor spans."""
    try:
        from spark_rapids_tpu.compile.service import (CompileService,
                                                      program_name)
        from spark_rapids_tpu.utils.tracing import SPAN_PREFIX
    except ImportError:
        return None
    return program_name, SPAN_PREFIX, set(CompileService.get().stats.per_op())


def events(ctx):
    """The traced query's raw events, or None without a trace."""
    trace = ctx.get("trace")
    return trace["events"] if trace else None


def window(host_events) -> tuple:
    """The traced query: the span of the benchmark's own annotation."""
    marks = [e for e in host_events if e[0] == T.MARK]
    if not marks:
        raise ValueError(f"the trace holds no {T.MARK!r} host event")
    return min(e[1] for e in marks), max(e[1] + e[2] for e in marks)


def clip(evs, lo: int, hi: int) -> list:
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in evs if s < hi and s + d > lo]


def devices_used(ctx, lo: int, hi: int) -> list:
    """The cell's `chips` busiest devices in the window, busiest first."""
    ops = events(ctx)["device_ops"]
    busy = {dev: T.union_ns(clip(evs, lo, hi)) for dev, evs in ops.items()}
    return sorted(busy, key=busy.get, reverse=True)[:ctx["cell"]["chips"]]


def module_op(name: str) -> str:
    """`jit_exec.join.expand(8267282574824651666)` -> `exec.join.expand`."""
    name = name.split("(")[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def program_seconds(ctx, classify) -> dict:
    """Device seconds per class of program in the traced query, a class
    being what `classify(module_op(name))` returns: the union of the
    operations ("XLA Ops") that started inside the class's program
    executions ("XLA Modules"), per device, averaged over the devices used.
    Unions of operations and not the executions' own lengths, so the
    classes add up to no more than the device's busy time; an operation
    inside no program is classified under the name `""`."""
    ev = events(ctx)
    lo, hi = window(ev["host_events"])
    used = devices_used(ctx, lo, hi)
    out: dict = {}
    for dev in used:
        modules = sorted(ev["device_modules"].get(dev, []),
                         key=lambda e: e[1])
        starts = [m[1] for m in modules]
        groups: dict = {}
        for op in ev["device_ops"][dev]:
            i = bisect.bisect_right(starts, op[1]) - 1
            inside = i >= 0 and op[1] < modules[i][1] + modules[i][2]
            cls = classify(module_op(modules[i][0]) if inside else "")
            groups.setdefault(cls, []).append(op)
        for cls, evs in groups.items():
            out[cls] = out.get(cls, 0) + T.union_ns(clip(evs, lo, hi))
    return {cls: ns / 1e9 / len(used) for cls, ns in out.items()}


def tagged_seconds(ctx, family: str):
    """Device seconds of the programs whose op tag starts with `family`."""
    eng = engine()
    if not events(ctx) or eng is None:
        return None
    head = eng[0](family)
    return program_seconds(
        ctx, lambda op: op.startswith(head)).get(True, 0.0)


def idle_gaps(ctx) -> tuple:
    """(the idle gaps of the cell's busiest chip inside the traced query as
    (start, stop), the host events)."""
    ev = events(ctx)
    lo, hi = window(ev["host_events"])
    dev = devices_used(ctx, lo, hi)[0]
    return T.gaps(ev["device_ops"][dev], lo, hi), ev["host_events"]


def covered_ns(gaps: list, spans: list) -> int:
    """How much of `gaps` (disjoint, in order) the union of `spans` covers."""
    merged = []
    for _, start, dur in sorted(spans, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    stops = [m[1] for m in merged]
    total = 0
    for a, b in gaps:
        i = bisect.bisect_right(stops, a)
        while i < len(merged) and merged[i][0] < b:
            total += min(b, merged[i][1]) - max(a, merged[i][0])
            i += 1
    return total


def idle_under(ctx, names=None):
    """(idle nanoseconds under the engine spans called `names`, or under any
    engine span; all idle nanoseconds) of the traced query, or None."""
    eng = engine()
    if not events(ctx) or eng is None:
        return None
    prefix = eng[1]
    gaps, host = idle_gaps(ctx)
    if names is None:
        spans = [e for e in host if e[0].startswith(prefix)]
    else:
        wanted = {prefix + n for n in names}
        spans = [e for e in host if e[0] in wanted]
    return covered_ns(gaps, spans), sum(b - a for a, b in gaps)
