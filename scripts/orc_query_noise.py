"""Where a warm query's seconds vary: one process of a benchmark cell, its
set-up as `benchmark/run.py` makes it, then N `collect()`s, each with what
the process spent beside its wall time (CPU seconds of all threads, the cyclic
collector's pauses, the host's time by stage from the ring of recent queries:
every field of `utils/tracing.STAGES`) and the scan's `readTime`. Prints one
JSON line a query and a summary. Not a run of the benchmark.

    python3 scripts/orc_query_noise.py --workload lineitem.q1_orc --seed 7 -n 40"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="lineitem.q1_orc")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("-n", type=int, default=40)
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--serial-walk", action="store_true",
                    help="a scan's column chunks walked one at a time")
    ap.add_argument("--no-settle", action="store_true",
                    help="without `settle_host_heap` after a compile")
    args = ap.parse_args(argv)
    env = R.prepare(args.workload, args.rehearse_rows)
    if env is None:
        return 3
    if args.no_settle:
        import spark_rapids_tpu
        spark_rapids_tpu.settle_host_heap = lambda: False
    if args.serial_walk:
        from spark_rapids_tpu.io import walkers
        walkers.walker_pool = lambda: None
    _, _, clients = R.deal(env, args.seed)
    session, frames = clients[0]
    name = env["traffic"]["queries"][0]
    print(json.dumps({"cores": len(os.sched_getaffinity(0)),
                      "cpu_count": os.cpu_count()}), flush=True)
    rows, pauses, began = [], [], {}

    def collector(phase, info):
        if phase == "start":
            began[info["generation"]] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - began[info["generation"]]))
    gc.callbacks.append(collector)
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.tracing import STAGES
    kept = set(STAGES.values()) | {"prefetch_stall_ns", "semaphore_wait_ns"}
    for i in range(args.n + 2):
        del pauses[:]
        c0 = time.process_time()
        rec = R.collect_once(session, env["jax"], name, frames[name])
        c1 = time.process_time()
        row = {"i": i, "s": round(rec["seconds"], 4),
               "scan_s": round(rec.get("scan_read_s", 0.0), 4),
               "cpu_s": round(c1 - c0, 4),
               "gc_ms": round(1e3 * sum(p[1] for p in pauses), 1),
               "gc_gen": max((p[0] for p in pauses), default=-1),
               **{k[:-3] + "_ms": round(v / 1e6, 1) for k, v in
                  TpuSession.recent_queries()[-1][2].items() if k in kept},
               "compiles": rec.get("compiles"), "faults": rec["faults"]}
        print(json.dumps(row), flush=True)
        if i >= 2:
            rows.append(row)
    print(json.dumps({k: {"mean": round(statistics.mean(r[k] for r in rows), 4),
                          "median": statistics.median(r[k] for r in rows),
                          "sd": round(statistics.pstdev(r[k] for r in rows), 4)}
                      for k in ("s", "scan_s", "cpu_s", "gc_ms",
                                *sorted(f[:-3] + "_ms" for f in kept))}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
