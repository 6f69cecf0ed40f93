"""From a profiler trace to device busy and idle time, the device operations
that took most of it, and the longest idle gaps by what the host was doing.

The arithmetic works on plain lists of `(name, start_ns, duration_ns)`, so a
hand-built trace tests it; `reduce_dir` reads the `.xplane.pb` that
`jax.profiler` wrote, with nothing but JAX."""

from __future__ import annotations

import glob
import os

TOP = 10
# a device plane's lines that hold one event per executed operation; the
# other lines ("XLA Modules", "Steps", ...) span whole programs, waits and all
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
MARK = "bench.collect"
# host events shorter than this label no gap worth listing
MIN_HOST_NS = 20_000


def union_ns(events) -> int:
    """Total length of the union of the events' intervals."""
    busy, end = 0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def gaps(events, lo: int, hi: int) -> list:
    """The intervals of [lo, hi] that no event covers, as (start, stop)."""
    out, end = [], lo
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if start > end:
            out.append((end, min(start, hi)))
        end = max(end, start + dur)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


def label_gap(gap, host_events) -> str:
    """What the host was doing through an idle gap: the shortest host event
    that covers at least half of it, else the one that overlaps it most.
    `MARK`, the benchmark's own annotation around `collect()`, labels a gap
    only when no finer event does, as "in collect"."""
    a, b = gap
    best, best_key = None, None
    for name, start, dur in host_events:
        over = min(b, start + dur) - max(a, start)
        if over <= 0:
            continue
        covers = over * 2 >= b - a
        key = (not covers, name == MARK, dur if covers else -over)
        if best_key is None or key < best_key:
            best, best_key = name, key
    if best is None:
        return "no host event"
    return "in collect" if best == MARK else best


def short_op(name: str) -> str:
    """`%while.7 = (u32[], ...) while(...)` -> `%while.7 while`: the trace
    names an operation by its whole HLO text."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:80]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch in "([{") - (ch in ")]}")
        if ch == " " and depth == 0:
            rest = rest[i + 1:]
            break
    return f"{head} {rest.split('(')[0]}"[:80]


def by_program(ops: list, modules: list) -> dict:
    """Seconds per program (its executions' own lengths) and per (program,
    operation): an operation belongs to the program execution ("XLA Modules"
    event) it started in. An operation's time includes the operations nested
    in it (a `while` and its body are both events), so the pairs do not add
    up to the program."""
    import bisect
    modules = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in modules]
    progs: dict = {}
    for name, _, dur in modules:
        progs[name] = progs.get(name, 0) + dur
    pairs: dict = {}
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < modules[i][1] + modules[i][2]
        prog = modules[i][0] if inside else "no program"
        key = f"{prog}/{short_op(name)}"
        pairs[key] = pairs.get(key, 0) + dur
    return progs, pairs


def reduce_events(device_ops: dict, host_events: list, window=None,
                  device_modules=None, chips=None) -> dict:
    """`device_ops`: per device, its operations' events. `window`: (lo, hi)
    in the trace's clock; by default the span of the `MARK` host events, the
    benchmark's own annotation around `collect()`: both the busy time and
    the window are then on the trace's one clock, and a trace without `MARK`
    is refused. Busy time is averaged over the cell's `chips` busiest
    devices (a host may hold more chips than the cell uses). `device_ops` of
    the result: the programs that took most device time, then the
    operations inside programs that did."""
    everything = [e for evs in device_ops.values() for e in evs]
    if not everything:
        raise ValueError("no operation ran on a device in the traced window")
    if window is None:
        marks = [e for e in host_events if e[0] == MARK]
        if not marks:
            raise ValueError(f"the trace holds no {MARK!r} host event: "
                             "nothing says where the traced query began")
        window = (min(e[1] for e in marks), max(e[1] + e[2] for e in marks))
    lo, hi = window
    busy = {dev: union_ns([(n, max(s, lo), min(s + d, hi) - max(s, lo))
                           for n, s, d in evs if s < hi and s + d > lo])
            for dev, evs in device_ops.items()}
    used = sorted(busy, key=busy.get, reverse=True)[:chips or len(busy)]
    modules = [e for evs in (device_modules or {}).values() for e in evs]
    progs, pairs = by_program(everything, modules)
    by_label: dict = {}
    for gap in sorted(gaps(device_ops[used[0]], lo, hi),
                      key=lambda g: g[0] - g[1])[:200]:
        label = label_gap(gap, host_events)
        by_label[label] = by_label.get(label, 0) + gap[1] - gap[0]

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    half = TOP // 2 if modules else 0
    return {"busy_s": sum(busy[d] for d in used) / len(used) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "device_ops": top(progs)[:half] + top(pairs)[:TOP - half],
            "idle_gaps": top(by_label)}


def programs_in_order(device_modules: dict, least_ns: int = 5_000_000) -> list:
    """The program executions of `least_ns` or longer as `[name, start_s,
    seconds]` in the order they ran, from the first one's start: which
    operator a `jit_dyn_fn` is can be read off the plan's order only."""
    runs = sorted((e for evs in device_modules.values() for e in evs),
                  key=lambda e: e[1])
    return [[n, (s - runs[0][1]) / 1e9, d / 1e9] for n, s, d in runs
            if d >= least_ns]


def read_xplane(path: str) -> tuple:
    """(device_ops, device_modules, host_events, lines) of one `.xplane.pb`;
    `lines` lists every plane and line with its event count, for a look by
    hand."""
    from jax.profiler import ProfileData
    device_ops, device_modules, host_events, lines = {}, {}, [], []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            lines.append([plane.name, line.name, len(events)])
            if on_device and line.name in OP_LINES:
                device_ops.setdefault(plane.name, []).extend(events)
            elif on_device and line.name in MODULE_LINES:
                device_modules.setdefault(plane.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host_events.extend(e for e in events if e[2] >= MIN_HOST_NS)
    return device_ops, device_modules, host_events, lines


def reduce_dir(trace_dir: str, chips: int) -> dict:
    """Reduce the one trace under `trace_dir` for a cell of `chips` chips.
    Besides the reduction the result carries what it was made from, for
    readers of their own: `events` (per device its operations and its
    programs, and the host's events, each `(name, start_ns, duration_ns)`),
    `xplane` (the file) and `lines` (every plane and line with its count)."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    device_ops, device_modules, host_events, lines = read_xplane(found[0])
    ran = [d for d, evs in device_ops.items() if evs]
    if len(ran) < chips:
        raise RuntimeError(f"operations ran on {len(ran)} device(s), the "
                           f"cell uses {chips}: {lines}")
    out = reduce_events(device_ops, host_events,
                        device_modules=device_modules, chips=chips)
    out.update(lines=lines, xplane=found[0],
               programs=programs_in_order(device_modules),
               events={"device_ops": device_ops,
                       "device_modules": device_modules,
                       "host_events": host_events})
    return out
