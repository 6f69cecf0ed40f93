"""The benchmark's one command: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload star.q3 --seed 7 --seconds 30 --trace 0

One process, no child that touches JAX. Finds the cell in `BENCHMARK.json`,
its configuration, traffic mix, queries, generator and per-layer readers as
files under `benchmark/` by their names (nothing here names one of them), makes
the data from `--seed`, runs each query of the mix twice (set-up: compile or
reload, then once warm), then drives `df.collect()` through `TpuSession` in a closed loop of
the mix's `clients` for `--seconds`, and compares every answer the loop
returned with the query's plain reference once the window has closed. The
last stdout line is the result; every earlier line is a note. Off the chip it
prints no result and exits 3, unless `--rehearse-rows N` asks for a rehearsal:
every phase at N fact rows on whatever platform there is, no metric, and
`correct` false."""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")
sys.path.insert(0, ROOT)  # the system under test: `spark_rapids_tpu`


def note(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(here: str, kind: str, name: str):
    """`<here>/<kind>/<name>.py`, found by name: a new query, generator or
    reader is a new file."""
    path = os.path.join(here, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: str, workload: str) -> dict:
    """The cell with its configuration and traffic mix, and the metrics it
    reports, all from `BENCHMARK.json` and the files it names."""
    bench = load_json(root, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    here = os.path.join(root, bench["paths"][0])

    def mine(metric):
        return workload in metric.get("workloads", [workload])
    return {"name": workload, "chips": cell["chips"], "here": here,
            "config": load_json(root, entry["file"]),
            "traffic": load_json(here, "traffic", cell["traffic"] + ".json"),
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def peaks_for(here: str, kind: str) -> dict:
    table = load_json(here, "peaks.json")
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json: add its "
                       "published peaks with their source; there is no "
                       "default")
    return table[kind]


def place_compile_cache(jax) -> str:
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR says
    (JAX reads it; nothing is set in code), else `<checkout>/.jax_cache`. The
    path is part of the cache key, so it is never a temporary name."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build_native() -> None:
    """`make -C native` from the committed sources; without the library the
    scan quietly takes numpy paths."""
    proc = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native runtime did not build:\n{proc.stderr}")


def plan_names(node) -> list:
    out = [node.name]
    for child in node.children:
        out.extend(plan_names(child))
    return out


def scan_read_seconds(node) -> float:
    from spark_rapids_tpu.utils import metrics as M
    own = node.metrics.snapshot().get(M.READ_TIME, 0) / 1e9
    return own + sum(scan_read_seconds(c) for c in node.children)


def collect_once(session, jax, name: str, df) -> dict:
    """One timed `collect()` with what the engine counted for it. A query
    that did not run as this engine's device query is `faults`, not an
    answer: a CPU plan section, a nested-loop join, a whole-query CPU rerun,
    a compile degraded to direct jit, or no device dispatch at all (an
    answer served from a cache is not a query run)."""
    from spark_rapids_tpu.utils.metrics import TaskMetrics
    rec = {"query": name, "faults": []}
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench.collect"):
            rec["answer"] = df.collect()
    except Exception as e:  # noqa: BLE001 — a failed operation is counted
        rec["faults"].append(f"{type(e).__name__}: {e}")
        rec["seconds"] = time.perf_counter() - t0
        return rec
    rec["seconds"] = time.perf_counter() - t0
    tm = TaskMetrics.get()
    plan = session.last_plan
    names = plan_names(plan) if plan is not None else []
    rec.update(dispatches=tm.device_dispatches, compiles=tm.compile_count,
               compile_s=tm.compile_ns / 1e9, plan=names,
               scan_read_s=scan_read_seconds(plan) if names else 0.0)
    if any(n.startswith("Cpu") or "FromCpu" in n for n in names):
        rec["faults"].append(f"a plan section ran on the CPU engine: {names}")
    if "TpuNestedLoopJoinExec" in names:
        rec["faults"].append("nested-loop join in the plan")
    if tm.cpu_fallback_reruns:
        rec["faults"].append("whole-query CPU rerun")
    if tm.compile_fallbacks:
        rec["faults"].append("a compile degraded to direct jit")
    if not names or not tm.device_dispatches:
        rec["faults"].append("no device plan or dispatch: not a query run")
    return rec


def scans_off_device(session, scans: dict, device) -> list:
    """The query's scans whose first batch does not sit on the chip."""
    from spark_rapids_tpu.plan.overrides import Overrides
    off = []
    for name, df in scans.items():
        stream = Overrides(session.conf).apply(df.plan).execute()
        batch = next(stream)
        stream.close()
        where = set()
        for c in batch.columns:
            where |= set(c.data.devices())
        if where != {device}:
            off.append(f"{name}: scanned batch on {where}, not on {device}")
    return off


def plan_rewrite_ms(session, df, calls: int = 20) -> float:
    from spark_rapids_tpu.plan.overrides import Overrides
    reads = []
    for _ in range(calls):
        t0 = time.perf_counter()
        Overrides(session.conf).apply(df.plan)
        reads.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(reads)


def drive(jax, clients: list, order: list, seconds: float,
          trace_dir=None) -> tuple:
    """The closed loop: each client (a session with its frames, on a thread
    of its own past the first) starts its next `collect()` when its last
    returned, whole queries until `seconds` have passed. With `trace_dir`
    the profiler records the first client's first query."""
    import threading
    t0 = time.perf_counter()

    def client(i: int, session, frames: dict, recs: list) -> None:
        while not recs or time.perf_counter() - t0 < seconds:
            name = order[(i + len(recs)) % len(order)]
            tracing = trace_dir is not None and i == 0 and not recs
            if tracing:
                # Python calls are traced too: they label the idle gaps
                jax.profiler.start_trace(trace_dir)
            try:
                recs.append(collect_once(session, jax, name, frames[name]))
            finally:
                if tracing:
                    jax.profiler.stop_trace()
            recs[-1].update(traced=tracing, client=i)

    done = [[] for _ in clients]
    threads = [threading.Thread(target=client, args=(i, *c, done[i]))
               for i, c in enumerate(clients)][1:]
    for t in threads:
        t.start()
    client(0, *clients[0], done[0])
    for t in threads:
        t.join()
    return [r for recs in done for r in recs], time.perf_counter() - t0


def judge(queries: dict, recs: list, paths: dict, extra: dict) -> dict:
    """Every answer against its query's plain reference: per number compared,
    the worst reading over the answers, beside its limit."""
    checks = {k: {"value": v, "limit": 0} for k, v in extra.items()}
    wants = {}
    for rec in recs:
        if "answer" not in rec:
            continue
        q = queries[rec["query"]]
        if rec["query"] not in wants:
            wants[rec["query"]] = q.reference(paths)
        try:
            read = q.compare(rec["answer"], wants[rec["query"]])
        except Exception as e:  # noqa: BLE001 — an answer of the wrong
            # shape or type cannot be compared: it is wrong, not a crash
            note(compare_raised=f"{type(e).__name__}: {e}")
            read = {k: 1 for k in q.LIMITS}
        for k, v in read.items():
            slot = checks.setdefault(k, {"value": 0, "limit": q.LIMITS[k]})
            slot["value"] = max(slot["value"], v)
    return checks


def prepare(workload: str, rehearse_rows: int = 0):
    """A run's set-up before its data: the cell's files, `native/`, JAX with
    its compile cache, the look for the chip, the engine. `None` where the
    cell's chips are not there and no rehearsal was asked for."""
    cell = find_cell(ROOT, workload)
    here, traffic = cell["here"], cell["traffic"]
    build_native()
    import jax
    cache_dir = place_compile_cache(jax)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    on_chip = dev.platform == "tpu" and device["count"] >= cell["chips"]
    if not on_chip and not rehearse_rows:
        print(f"needs {cell['chips']} tpu chip(s), found {device}",
              file=sys.stderr)
        return None
    import warnings

    import spark_rapids_tpu  # noqa: F401 — enables x64
    from spark_rapids_tpu.compile.service import CompileServiceWarning
    from spark_rapids_tpu.native import runtime as native
    warnings.simplefilter("error", CompileServiceWarning)
    if not native.available():
        raise RuntimeError("native runtime built but did not load")
    note(device=device, compile_cache_dir=cache_dir,
         rehearsal=bool(rehearse_rows),
         compile_cache_warm=os.path.isdir(cache_dir)
         and any(os.scandir(cache_dir)))
    config = cell["config"]
    if rehearse_rows:
        config["tables"][config["fact_table"]]["rows"] = rehearse_rows
    queries = {q: load_module(here, "queries", q) for q in traffic["queries"]}
    return {**cell, "jax": jax, "dev": dev, "device": device,
            "on_chip": on_chip, "queries": queries,
            "peaks": peaks_for(here, dev.device_kind) if on_chip else None,
            "generator": load_module(here, "generators", config["generator"]),
            "tables_needed": sorted({t for q in queries.values()
                                     for t in q.TABLES})}


def deal(env: dict, seed: int) -> tuple:
    """One seed's data and the mix's clients: (tables, paths, clients), a
    client being a session of its own with the mix's frames."""
    from spark_rapids_tpu.plugin import TpuSession
    config = env["config"]
    t0 = time.perf_counter()
    tables = env["generator"].write(
        os.path.join(WORK, "data", config["name"]), seed, config,
        env["tables_needed"])
    paths = {k: v["path"] for k, v in tables.items()}
    note(data_s=time.perf_counter() - t0, seed=seed,
         tables={k: {"rows": v["rows"], "bytes": v["bytes"]}
                 for k, v in tables.items()})
    clients = []
    for _ in range(env["traffic"]["clients"]):
        session = TpuSession(dict(config.get("session_conf", {})))
        session.initialize_device()
        clients.append((session, {name: q.build(session, paths)
                                  for name, q in env["queries"].items()}))
    return tables, paths, clients


def run(args) -> int:
    rehearsal = args.rehearse_rows > 0
    env = prepare(args.workload, args.rehearse_rows)
    if env is None:
        return 3
    jax, dev, device, here = env["jax"], env["dev"], env["device"], env["here"]
    queries, order = env["queries"], env["traffic"]["queries"]
    tables, paths, clients = deal(env, args.seed)
    session, frames = clients[0]
    firsts = [collect_once(session, jax, name, frames[name])
              for name in queries]
    note(first=[{k: v for k, v in r.items() if k != "answer"}
                for r in firsts])
    off_device = [line for q in queries.values() for line in
                  scans_off_device(session, q.scans(session, paths), dev)]
    # once more, warm: a process that has just compiled for minutes ran its
    # next query at twice the time (PERF.md), and that belongs to set-up
    warm = [collect_once(session, jax, name, frames[name])
            for name in queries]
    note(warm_s=[r["seconds"] for r in warm])
    setup_s = time.perf_counter() - _T0

    trace_dir = os.path.join(WORK, "trace") if args.trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    recs, window_s = drive(jax, clients, order, args.seconds, trace_dir)
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")
    note(window_s=window_s, queries=[
        {k: r.get(k) for k in ("query", "client", "seconds", "dispatches",
                               "compiles", "scan_read_s", "traced", "faults")}
        for r in recs])

    everything = firsts + warm + recs
    failed = [r for r in everything if r["faults"]]
    for r in failed:
        note(failed_query=r["query"], faults=r["faults"])
    checks = judge(queries, everything, paths, {
        "failed_queries": len(failed), "scans_off_device": len(off_device),
        "not_on_tpu": 0 if env["on_chip"] else 1})
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    e2e = {"query_s": window_s / len(recs),
           "first_query_s": sum(r["seconds"] for r in firsts),
           "setup_s": setup_s}
    # in every run, traced or not: a stall shows here where the mean hides it
    window = {"queries": len(recs), "window_s": window_s,
              "slowest_query_s": max(r["seconds"] for r in recs)}
    metrics, breakdown = {}, None
    if rehearsal:
        note(rehearsal_readings_not_metrics={**e2e, **window})
        window = None
    elif not args.trace:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in env["end_to_end"]}
    if args.trace:
        trace = None
        if env["on_chip"]:
            trace = load_module(here, "", "trace_reduce").reduce_dir(
                trace_dir, env["chips"])
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
            note(trace_lines=trace["lines"], trace_window_s=trace["window_s"],
                 host_window_s=recs[0]["seconds"],
                 trace_programs=trace["programs"])
        untraced = [r for r in recs if not r["traced"] and not r["faults"]]
        # `trace` holds the reduction and, under "events", the trace's own
        # per-device operation, program and host event lists, and under
        # "xplane" the file: a new reader needs no edit here
        ctx = {"firsts": firsts, "window": untraced or recs, "trace": trace,
               "peaks": env["peaks"], "memory_peak_bytes": memory_peak,
               "cell": {k: env[k] for k in ("name", "chips", "config",
                                            "traffic")},
               "plan_rewrite_ms": plan_rewrite_ms(session, frames[order[0]]),
               "least_bytes": statistics.mean(
                   queries[r["query"]].least_bytes(tables) for r in recs)}
        for m in env["per_layer"]:
            value = load_module(here, "layer_metrics", m["name"]).read(ctx)
            if value is not None and not rehearsal:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            elif value is not None:
                note(rehearsal_reading_not_a_metric={m["name"]: value})
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": correct, "attempted": len(everything),
              "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    if window:
        result["window"] = window
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0 if correct else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0,
                    help="rehearsal only: skip the look for a chip, cut the "
                         "fact table to this many rows, report no metric")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
