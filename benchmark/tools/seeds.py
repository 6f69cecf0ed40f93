"""Many seeds of one cell in one process, for setting and re-reading the
limits of `correct` where a run's set-up is long: `run.py`'s own set-up once
(`prepare`), then per seed `run.py`'s own data and clients (`deal`), a few
`collect()`s through the timed path compared as `run.py` compares them, and
the control (the query's reference with the money column carried in a lower
precision, put in the program's place) read by the same comparison. Programs
compile once: every seed's files have the same layout. Not a run of the
benchmark: it prints no result line and no metric.

    python3 benchmark/tools/seeds.py --workload star.q3 --seeds 11,12,13 \\
        --collects 2 --controls bfloat16,float32"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--collects", type=int, default=2)
    ap.add_argument("--controls", default="bfloat16,float32")
    ap.add_argument("--rehearse-rows", type=int, default=0)
    args = ap.parse_args(argv)
    env = R.prepare(args.workload, args.rehearse_rows)
    if env is None:
        return 3
    queries, bad = env["queries"], 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        _, paths, clients = R.deal(env, seed)
        session, frames = clients[0]
        recs = [R.collect_once(session, env["jax"], n, frames[n])
                for _ in range(args.collects) for n in queries]
        checks = R.judge(queries, recs, paths, {
            "failed_queries": sum(bool(r["faults"]) for r in recs)})
        ok = all(c["value"] <= c["limit"] for c in checks.values())
        bad += not ok
        controls = {}
        for dtype in filter(None, args.controls.split(",")):
            for name, q in queries.items():
                controls[f"{name}.{dtype}"] = q.compare(
                    q.control(paths, dtype), q.reference(paths))
        R.note(seed=seed, correct=ok,
               checks={k: c["value"] for k, c in checks.items()},
               controls=controls,
               answer_rows=[r["answer"].num_rows for r in recs
                            if "answer" in r],
               seconds=[round(r["seconds"], 3) for r in recs],
               compiles=[r.get("compiles") for r in recs],
               faults=[r["faults"] for r in recs if r["faults"]],
               seed_s=time.perf_counter() - t0)
    R.note(seeds_not_correct=bad, device=str(env["dev"]))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
