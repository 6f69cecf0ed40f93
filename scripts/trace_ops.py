"""Every program of a traced benchmark run with its operations, for PERF.md
section 5 (`breakdown.device_ops` of the result line lists the top five only).

    python scripts/trace_ops.py .bench_work/trace chiprun_out/q1_ops.json

Reads the one `.xplane.pb` under the directory with the benchmark's own
`trace_reduce.py` and writes, per program of 1 ms or more, its seconds, its
executions and its operations by time (an operation's time includes those
nested in it)."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(trace_dir: str, out_path: str, top: int = 60) -> None:
    spec = importlib.util.spec_from_file_location(
        "trace_reduce", os.path.join(ROOT, "benchmark", "trace_reduce.py"))
    tr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tr)
    red = tr.reduce_dir(trace_dir, 1)
    ops = [e for evs in red["events"]["device_ops"].values() for e in evs]
    modules = [e for evs in red["events"]["device_modules"].values()
               for e in evs]
    progs, pairs = tr.by_program(ops, modules)
    runs: dict = {}
    for name, _, _ in modules:
        runs[name] = runs.get(name, 0) + 1
    out = {"busy_s": red["busy_s"], "window_s": red["window_s"],
           "idle_gaps": red["idle_gaps"], "programs": []}
    for prog, ns in sorted(progs.items(), key=lambda kv: -kv[1]):
        if ns < 1_000_000:
            continue
        mine = sorted(((k[len(prog) + 1:], v) for k, v in pairs.items()
                       if k.startswith(prog + "/")), key=lambda kv: -kv[1])
        out["programs"].append({
            "program": prog, "s": ns / 1e9, "runs": runs[prog],
            "n_ops": len(mine),
            "ops": [[k, round(v / 1e9, 6)] for k, v in mine[:top]]})
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    for p in out["programs"]:
        print(f'{p["s"]:9.4f} s  x{p["runs"]}  {p["program"]}  '
              f'({p["n_ops"]} operation names)')


if __name__ == "__main__":
    main(*sys.argv[1:3])
