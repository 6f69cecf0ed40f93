"""The row-gather metrics of every operator of one benchmark query, after the
query ran once on whatever device there is: `numPackedGatherArrays` and
`numSingleGatherArrays` per exec of the executed plan (PERF.md, PR 36).

    python scripts/gather_metrics.py q1_pricing_summary lineitem=<file.parquet>
"""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from spark_rapids_tpu.plugin import TpuSession  # noqa: E402
from spark_rapids_tpu.utils import metrics as M  # noqa: E402


def main(query: str, *tables: str) -> None:
    spec = importlib.util.spec_from_file_location(
        query, os.path.join(ROOT, "benchmark", "queries", query + ".py"))
    q = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(q)
    session = TpuSession({})
    got = q.build(session, dict(t.split("=", 1) for t in tables)).collect()

    def walk(node):
        snap = node.metrics.snapshot()
        if M.NUM_PACKED_GATHER_ARRAYS in snap:
            print(json.dumps({"exec": node.name,
                              "packed": snap[M.NUM_PACKED_GATHER_ARRAYS],
                              "alone": snap[M.NUM_SINGLE_GATHER_ARRAYS]}))
        for c in node.children:
            walk(c)
    walk(session.last_plan)
    print(json.dumps({"rows": got.num_rows}))


if __name__ == "__main__":
    main(*sys.argv[1:])
