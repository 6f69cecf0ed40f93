"""Benchmark: fused scan->filter->join->aggregate query step on one chip.

The BASELINE metric family is "GB/s/chip scan+hash-join" / "speedup vs CPU Spark"
(reference claims 3-7x, typical 4x — docs/FAQ.md:105-109). This runs the q5-ish
pipeline (BASELINE workload #1) as one XLA program on the real chip, validates it
against a numpy oracle, and reports speedup vs that oracle (a *vectorized-C* CPU
stand-in — far faster than row-based CPU Spark, so conservative).

TPU-native choices (measured on chip, see commit history):
  * join = dense-table gather (build dim table via scatter, probe via gather):
    3.4x faster than XLA's searchsorted lowering at 4M probes.
  * grouped agg = segment_sum; f64 (Spark DoubleType semantics) is the dominant
    cost on TPU (emulated f64 scatter-add) — the standing kernel-optimization
    target (Pallas segmented reduce).
  * timing: a dispatch has a fixed cost and block_until_ready may return
    early, so the step is iterated K times INSIDE one program (lax.scan) and
    D2H forces completion; per-step = (total - noop) / K.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} —
detail now carries achieved FLOP/s, MFU vs bf16 peak, pipeline GB/s, and a
device-parquet scan-decode GB/s companion metric (round-3 verdict item 1b).

Hardening: a device runtime can fail at init (UNAVAILABLE) or hang at first
touch. The parent process therefore never imports JAX: it runs the whole
measurement in ONE child process under a watchdog timeout, retries on
failure/timeout with backoff, and on final failure prints a single parseable
{"metric": ..., "error": ...} JSON line and exits non-zero (reference bar:
fail fast + loud, Plugin.scala:365-389,436-459). The child starts no process
of its own: a process that has touched JAX holds the chip.

The default entry times the device and refuses any platform but `tpu`.
`chip_smoke.py` is the proof that the engine runs on the chip; this file is
due to be replaced by the benchmark (ROADMAP S0, D1).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

N_FACT = 4_194_304
N_DIM = 65_536
N_GROUPS = 1_024
KEY_SPACE = 131_072
BYTES_PER_ROW = 8 + 4 + 8  # fact: key i64, grp i32, val f64
K_STEPS = 8

# FLOP accounting (round-3 verdict item 1b: emit achieved FLOP/s + MFU).
#   * algorithmic: what the query semantically needs per fact row —
#     1 compare + 1 mul + 1 select + 1 add.
#   * executed: what actually runs on the MXU — the Pallas segmented sum
#     computes, per row, a [LANES]x[LANES,G] one-hot dot contribution
#     (G MACs = 2G flops) twice (hi/lo f64 split), so N*G*4.
ALGO_FLOPS_PER_STEP = 4 * N_FACT
MXU_FLOPS_PER_STEP = N_FACT * N_GROUPS * 4

# Peak bf16 FLOP/s per chip by jax device_kind substring (public specs:
# cloud.google.com/tpu/docs/system-architecture-tpu-vm). MFU is reported
# against bf16 peak — the standard convention — even though this pipeline
# runs f32/f64 work, so the number is conservative.
_PEAK_BF16_BY_KIND = [
    ("v6", 918e12),        # Trillium / v6e
    ("v5p", 459e12),
    ("v5", 197e12),        # v5e reports device_kind "TPU v5 lite" / "v5litepod"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device_kind: str):
    k = device_kind.lower()
    for sub, peak in _PEAK_BF16_BY_KIND:
        if sub in k:
            return peak
    return None


def make_data(seed: int = 0):
    rng = np.random.default_rng(seed)
    fact_key = rng.integers(0, KEY_SPACE, size=N_FACT).astype(np.int64)
    fact_grp = rng.integers(0, N_GROUPS, size=N_FACT).astype(np.int32)
    fact_val = rng.uniform(0.5, 1.5, size=N_FACT).astype(np.float64)
    dim_key = np.sort(rng.permutation(KEY_SPACE)[:N_DIM]).astype(np.int64)
    dim_w = rng.uniform(0.5, 1.5, size=N_DIM).astype(np.float64)
    return fact_key, fact_grp, fact_val, dim_key, dim_w


def tpu_many_steps():
    """One program running the query step K_STEPS times (amortizes the
    fixed dispatch cost).

    The grouped aggregation runs through the Pallas MXU segmented-sum kernel
    (ops/pallas_segsum.py): XLA's f64 segment_sum lowers to an emulated-f64
    scatter-add measured at 0.300s/step for this shape; the Pallas kernel does
    the same reduction in 0.019s/step at ~1e-9 relative error (one-hot MXU
    matmuls on a hi/lo split, per-chunk f32 partials combined in f64)."""
    import jax
    import jax.numpy as jnp
    import spark_rapids_tpu  # noqa: F401  (x64 on)
    from spark_rapids_tpu.ops.pallas_segsum import segment_sum_f64

    @jax.jit
    def many(fact_key, fact_grp, fact_val, dim_key, dim_w):
        tw = jnp.zeros(KEY_SPACE, jnp.float64).at[dim_key].set(dim_w)
        tm = jnp.zeros(KEY_SPACE, bool).at[dim_key].set(True)

        def step(acc, _):
            keep = fact_val > 0.6
            w = tw[fact_key]
            matched = tm[fact_key] & keep
            contrib = jnp.where(matched, fact_val * w, 0.0)
            sums = segment_sum_f64(contrib, fact_grp, N_GROUPS)
            rows = jnp.sum(matched).astype(jnp.int64)
            return (acc[0] + sums, acc[1] + rows), None

        init = (jnp.zeros(N_GROUPS, jnp.float64), jnp.int64(0))
        (sums, rows), _ = jax.lax.scan(step, init, jnp.arange(K_STEPS))
        return sums / K_STEPS, rows // K_STEPS

    return many


def cpu_pipeline(fact_key, fact_grp, fact_val, dim_key, dim_w,
                 lo: int = 0, hi: int = None):
    fk = fact_key[lo:hi]
    keep = fact_val[lo:hi] > 0.6
    ix = np.clip(np.searchsorted(dim_key, fk), 0, len(dim_key) - 1)
    matched = (dim_key[ix] == fk) & keep
    contrib = np.where(matched, fact_val[lo:hi] * dim_w[ix], 0.0)
    sums = np.bincount(fact_grp[lo:hi], weights=contrib,
                       minlength=N_GROUPS)
    return sums, int(matched.sum())


# fork-inherited by oracle worker processes (copy-on-write, no pickling)
_ORACLE_DATA = None


def _oracle_shard(bounds):
    lo, hi = bounds
    return cpu_pipeline(*_ORACLE_DATA, lo=lo, hi=hi)


def cpu_oracle_parallel(data, workers: int):
    """Row-sharded CPU oracle across `workers` forked processes — the
    honest multi-core CPU baseline (round-4 verdict weak #3: the single-
    process oracle slows with machine load, swinging the headline 23.9 ->
    56.4). Returns (sums, rows, best wall seconds of 3 timed parallel
    runs); pool spin-up and a warm pass are excluded, per-map scatter/
    gather overhead is included (it is part of a real parallel oracle)."""
    import multiprocessing as mp
    global _ORACLE_DATA
    _ORACLE_DATA = data
    bounds = np.linspace(0, N_FACT, workers + 1).astype(int)
    shards = list(zip(bounds[:-1], bounds[1:]))
    ctx = mp.get_context("fork")
    with ctx.Pool(workers) as pool:
        parts = pool.map(_oracle_shard, shards)  # warm: faults, imports
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            parts = pool.map(_oracle_shard, shards)
            best = min(best, time.perf_counter() - t0)
    sums = np.sum([p[0] for p in parts], axis=0)
    rows = sum(p[1] for p in parts)
    return sums, rows, best


def _force(x):
    return np.asarray(x)


SCAN_ROWS = 2_097_152
SCAN_ROW_GROUP = SCAN_ROWS // 8   # 8 chunks: the multi-chunk fusion unit
SCAN_CHUNKS_PER_DISPATCH = 4


def scan_decode_bench(tmpdir: str):
    """Device parquet decode throughput (io/parquet_device.py) vs the
    HOST pyarrow decode of the SAME file, measured in the same process —
    round-4 verdict item 2 ("prove the device path beats the thing it
    replaced"). Two corpora: snappy (decompression-bound for any decoder
    — both paths pay it) and uncompressed (the decode paths themselves).
    Both device paths are measured: the serial per-row-group decode (the
    r05 unit, `_serial` keys) and the pipelined fused MULTI-CHUNK decode
    (packed single-transfer, N row groups per dispatch) that is the
    headline — with TaskMetrics dispatch accounting beside each so the
    dispatch amortization (dispatches-per-scan-batch, ISSUE-6 acceptance)
    is in the JSON, not inferred. GB/s are file-relative; raw decoded
    bytes ride along. May raise; the caller guards (main() prints the
    primary metric line first)."""
    import jax
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io.parquet_device import (
        device_decode_file, file_supported)
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    rng = np.random.default_rng(7)
    n = SCAN_ROWS
    t = pa.table({
        "k": pa.array(rng.integers(0, 1 << 40, n)),
        "v": pa.array(rng.uniform(0.0, 1.0, n)),
        "g": pa.array(rng.integers(0, 1024, n).astype(np.int32)),
    })
    raw_bytes = n * (8 + 8 + 4)
    session = TpuSession({"spark.rapids.sql.enabled": True,
                          "spark.rapids.sql.explain": "NONE"})
    session.initialize_device()
    out = {"scan_rows": n, "scan_row_groups": n // SCAN_ROW_GROUP,
           "scan_chunks_per_dispatch": SCAN_CHUNKS_PER_DISPATCH}

    for tag, comp in (("", "snappy"), ("_plain", "none")):
        path = os.path.join(tmpdir, f"scanbench{tag}.parquet")
        pq.write_table(t, path, compression=comp,
                       row_group_size=SCAN_ROW_GROUP)
        file_bytes = os.path.getsize(path)
        schema = session.read_parquet(path).plan.output

        def run(chunks):
            tm = TaskMetrics.get()
            tm.scan_dispatches = tm.scan_chunks = 0
            leaves = []
            batches = 0
            pf = file_supported(path, schema)
            for batch, _rows in device_decode_file(
                    pf, path, schema, chunks_per_dispatch=chunks):
                batches += 1
                for col in batch.columns:
                    leaves.append(col.data)
            jax.block_until_ready(leaves)
            return tm.scan_dispatches, tm.scan_chunks, batches

        def measure(chunks):
            # compile separated from execute: the first call pays
            # trace+compile (or a persistent-cache load on a warm
            # process); steady-state execute is measured warm. BENCH json
            # carries both so warm-path wins stay trackable per round.
            t0 = time.perf_counter()
            dispatches, chnks, batches = run(chunks)
            compile_s = time.perf_counter() - t0
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                run(chunks)
                best = min(best, time.perf_counter() - t0)
            return compile_s, best, dispatches, chnks, batches

        comp_m, best_m, disp_m, chnk_m, batch_m = \
            measure(SCAN_CHUNKS_PER_DISPATCH)
        comp_s, best_s, disp_s, chnk_s, batch_s = measure(1)
        host = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            pq.read_table(path)
            host = min(host, time.perf_counter() - t0)
        out.update({
            # headline: the pipelined fused multi-chunk path
            f"scan_compile_s{tag}": round(max(comp_m - best_m, 0.0), 5),
            f"scan_decode_gbps_raw{tag}": round(raw_bytes / best_m / 1e9,
                                                3),
            f"scan_decode_gbps_file{tag}":
                round(file_bytes / best_m / 1e9, 3),
            f"scan_decode_s{tag}": round(best_m, 5),
            f"dispatches_per_scan_batch{tag}":
                round(disp_m / max(batch_m, 1), 2),
            f"dispatches_per_chunk{tag}":
                round(disp_m / max(chnk_m, 1), 2),
            # the r05 serial per-row-group unit, same file, same process
            f"scan_decode_gbps_file_serial{tag}":
                round(file_bytes / best_s / 1e9, 3),
            f"scan_decode_s_serial{tag}": round(best_s, 5),
            f"dispatches_per_scan_batch_serial{tag}":
                round(disp_s / max(batch_s, 1), 2),
            f"dispatch_reduction_x{tag}":
                round((disp_s / max(chnk_s, 1))
                      / (disp_m / max(chnk_m, 1)), 2),
            # the thing the device path replaced
            f"host_pyarrow_gbps_file{tag}":
                round(file_bytes / host / 1e9, 3),
            f"host_pyarrow_s{tag}": round(host, 5),
            f"scan_vs_host{tag}": round(host / best_m, 3),
        })
    try:
        out.update(pipeline_query_bench(tmpdir))
    except Exception as e:  # must not sink the scan numbers
        out["pipeline_bench_error"] = f"{type(e).__name__}: {e}"
    try:
        out.update(scan_pushdown_bench(tmpdir))
    except Exception as e:  # must not sink the scan numbers
        out["pushdown_bench_error"] = f"{type(e).__name__}: {e}"
    return out


PIPE_DIM = 4096


def pipeline_query_bench(tmpdir: str) -> dict:
    """End-to-end pipeline-on vs pipeline-off on the scan+join bench
    (ISSUE-6 acceptance): the SAME engine query — parquet scan -> filter
    -> hash join -> grouped agg — runs with pipelined execution on and
    off, results must be bit-identical, and both wall times land in the
    JSON. The aggregation sums an INTEGER column and counts rows so the
    equality gate is exact: f64 sums regroup across the pipeline's larger
    merged batches (the documented variableFloatAgg grouping caveat) and
    would reduce the gate to approx."""
    import pyarrow as pa
    from spark_rapids_tpu.expr import Count, Sum, col
    from spark_rapids_tpu.plugin import TpuSession

    rng = np.random.default_rng(11)
    path = os.path.join(tmpdir, "pipebench.parquet")
    if not os.path.exists(path):
        import pyarrow.parquet as pq
        n = SCAN_ROWS // 2
        t = pa.table({
            "k": pa.array(rng.integers(0, PIPE_DIM, n)),
            "g": pa.array(rng.integers(0, 1024, n).astype(np.int32)),
            "v": pa.array(rng.uniform(0.0, 1.0, n)),
            "c": pa.array(rng.integers(0, 1 << 30, n)),
        })
        pq.write_table(t, path, row_group_size=SCAN_ROW_GROUP)
    dim = pa.table({
        "k": pa.array(np.arange(PIPE_DIM)),
        "w": pa.array(rng.integers(0, 1000, PIPE_DIM)),
    })

    def run(pipeline: bool):
        sess = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.explain": "NONE",
            "spark.rapids.tpu.pipeline.enabled": pipeline,
        })
        sess.initialize_device()
        q = (sess.read_parquet(path)
             .filter(col("v") > 0.25)
             .join(sess.from_arrow(dim), on="k")
             .group_by("g").agg(total=Sum(col("c") + col("w")),
                                cnt=Count(col("v"))))
        q.collect()  # warm (compiles)
        best = float("inf")
        res = None
        for _ in range(3):
            t0 = time.perf_counter()
            res = q.collect()
            best = min(best, time.perf_counter() - t0)
        return res.sort_by("g"), best

    res_off, t_off = run(False)
    res_on, t_on = run(True)
    return {
        "pipeline_on_s": round(t_on, 5),
        "pipeline_off_s": round(t_off, 5),
        "pipeline_speedup": round(t_off / t_on, 3),
        "pipeline_identical": bool(res_on.equals(res_off)),
    }


def scan_pushdown_bench(tmpdir: str, full: bool = False) -> dict:
    """Scan-pushdown sweep (ISSUE-12): the SAME engine query — parquet
    scan -> filter (-> aggregate) — with pushdown on vs off, across
    selectivity x predicate type, reporting file-relative GB/s, device
    ROW-DATA bytes materialised and rows pruned pre-materialisation (the
    machine-independent proxies), plus the aggregate-only shape that must
    materialise zero row data. Results are equality-gated per shape.
    Footer row-group pruning stays ON (it is part of the shipped path);
    the uniformly-shuffled string column defeats it, so `str_eq` isolates
    the in-dispatch dictionary-domain win while `int_*` shapes also bank
    clustered-predicate row-group skips — both appear in real scans.
    `full=False` keeps the sweep inside the --scan-only child budget."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.expr import Count, Max, Min, Sum, col
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    rng = np.random.default_rng(19)
    n = SCAN_ROWS // 2
    path = os.path.join(tmpdir, "pdbench.parquet")
    if not os.path.exists(path):
        t = pa.table({
            "k": pa.array(np.arange(n, dtype=np.int64)),
            "g": pa.array(rng.integers(0, 1024, n).astype(np.int32)),
            "s": pa.array([f"name{v:03d}" for v in
                           rng.integers(0, 100, n)]),
            "v": pa.array(rng.uniform(0.0, 1.0, n)),
        })
        pq.write_table(t, path, row_group_size=SCAN_ROW_GROUP)
    file_bytes = os.path.getsize(path)

    shapes = [
        ("int_sel1", lambda df: df.filter(col("k") < n // 100), None),
        ("str_eq", lambda df: df.filter(col("s") == "name007"), None),
        ("agg_only", lambda df: df.filter(col("k") < n // 20).agg(
            cnt=Count(), mn=Min(col("k")), mx=Max(col("g")),
            sm=Sum(col("k"))), "k"),
    ]
    if full:
        shapes[1:1] = [
            ("int_sel50", lambda df: df.filter(col("k") < n // 2), None),
            ("int_sel100", lambda df: df.filter(col("k") >= 0), None),
        ]

    out = {"pushdown_rows": n, "pushdown_file_bytes": file_bytes}

    def run(build, pushdown):
        sess = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.explain": "NONE",
            "spark.rapids.tpu.scan.pushdown.enabled": pushdown,
        })
        sess.initialize_device()
        q = build(sess.read_parquet(path))
        q.collect()  # warm (compiles)
        best, res = float("inf"), None
        for _ in range(3):
            TaskMetrics.reset()  # metrics report ONE run, not the sum
            t0 = time.perf_counter()
            res = q.collect()
            best = min(best, time.perf_counter() - t0)
        tm = TaskMetrics.get()
        return res, best, tm.scan_bytes_materialized, tm.scan_rows_pruned

    for name, build, sort_col in shapes:
        res_on, t_on, bytes_on, pruned_on = run(build, True)
        res_off, t_off, _, _ = run(build, False)
        a, b = res_on, res_off
        if sort_col is None and a.num_rows and "k" in a.schema.names:
            a = a.sort_by([("k", "ascending")])
            b = b.sort_by([("k", "ascending")])
        out.update({
            f"pushdown_{name}_gbps_on": round(file_bytes / t_on / 1e9, 3),
            f"pushdown_{name}_gbps_off": round(file_bytes / t_off / 1e9,
                                               3),
            f"pushdown_{name}_s_on": round(t_on, 5),
            f"pushdown_{name}_s_off": round(t_off, 5),
            f"pushdown_{name}_speedup": round(t_off / t_on, 3),
            f"pushdown_{name}_bytes_materialized": int(bytes_on),
            f"pushdown_{name}_rows_pruned": int(pruned_on),
            f"pushdown_{name}_identical": bool(a.equals(b)),
        })
    return out


def fusion_query_bench() -> dict:
    """Whole-stage fusion sweep (ISSUE-16): the SAME engine query with
    fusion on vs off across three chain shapes — filter->project,
    project->broadcast-probe->project, and an expression-heavy
    filter + stacked-projection chain — reporting wall, device-dispatch
    counts per run (the machine-independent win: one dispatch per fused
    stage per batch) and the per-shape bit-identical gate. The gates the
    matrix script enforces: >=2x fewer dispatches overall, wall no worse
    on any shape, faster on the expression-heavy shape."""
    import pyarrow as pa
    from spark_rapids_tpu.expr import col
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    rng = np.random.default_rng(23)
    n = SCAN_ROWS // 4
    fact = pa.table({
        "k": pa.array(rng.integers(0, 4096, n).astype(np.int64)),
        "a": pa.array(rng.integers(-1000, 1000, n).astype(np.int64)),
        "b": pa.array(rng.integers(1, 100, n).astype(np.int64)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(4096, dtype=np.int64)),
        "w": pa.array(rng.integers(1, 9, 4096).astype(np.int64)),
    })

    def fp(df, _):  # filter -> project
        return df.filter(col("a") > 0).select(
            (col("a") * 2 + col("b")).alias("x"), col("k"))

    def join(df, sess):  # project -> broadcast probe -> project
        d = sess.from_arrow(dim)
        return df.select(col("k"), (col("a") + col("b")).alias("v")) \
            .join(d, on="k", how="inner") \
            .select((col("v") * col("w")).alias("x"), col("k"))

    def exprheavy(df, _):  # long chain: per-op dispatch overhead dominates
        q = df.filter(col("a") > -900)
        for i in range(1, 7):
            q = q.select(col("k"), (col("a") + i).alias("a"),
                         (col("b") * 2 - col("a")).alias("b"))
        return q.select((col("a") + col("b")).alias("x"), col("k"))

    def prep(build, fusion):
        sess = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.explain": "NONE",
            "spark.rapids.tpu.fusion.enabled": fusion,
        })
        sess.initialize_device()
        q = build(sess.from_arrow(fact), sess)
        q.collect()  # warm (compiles)
        return q

    def measure(q):
        TaskMetrics.reset()  # dispatches report ONE run, not the sum
        t0 = time.perf_counter()
        res = q.collect()
        return res, time.perf_counter() - t0, \
            TaskMetrics.get().device_dispatches

    def run(build):
        # interleave the on/off reps so clock-speed / cache drift within
        # the process cancels instead of biasing whichever ran first
        q_on, q_off = prep(build, True), prep(build, False)
        t_on = t_off = float("inf")
        for _ in range(5):
            res_on, t, d_on = measure(q_on)
            t_on = min(t_on, t)
            res_off, t, d_off = measure(q_off)
            t_off = min(t_off, t)
        return res_on, t_on, d_on, res_off, t_off, d_off

    out = {"fusion_rows": n}
    tot_on = tot_off = 0
    for name, build in [("fp", fp), ("join", join),
                        ("exprheavy", exprheavy)]:
        res_on, t_on, d_on, res_off, t_off, d_off = run(build)
        a = res_on.sort_by([("k", "ascending"), ("x", "ascending")])
        b = res_off.sort_by([("k", "ascending"), ("x", "ascending")])
        tot_on += d_on
        tot_off += d_off
        out.update({
            f"fusion_{name}_s_on": round(t_on, 5),
            f"fusion_{name}_s_off": round(t_off, 5),
            f"fusion_{name}_speedup": round(t_off / t_on, 3),
            f"fusion_{name}_dispatches_on": int(d_on),
            f"fusion_{name}_dispatches_off": int(d_off),
            f"fusion_{name}_identical": bool(a.equals(b)),
        })
    out["fusion_dispatch_reduction_x"] = round(tot_off / max(tot_on, 1), 3)
    return out


ATTEMPTS = 3
# A first compile takes tens of seconds per program and the measured
# sections are seconds; a healthy cold run (pipeline + scan-decode compiles)
# fits in ~3 min. A hung backend init eats the whole window, so keep it
# bounded — 3 attempts must stay well under the driver's round budget.
ATTEMPT_TIMEOUT_S = 300
_CHILD_ENV = "SPARK_RAPIDS_TPU_BENCH_CHILD"
_MARK = "@BENCH_RESULT@"


def _enable_compilation_cache():
    """JAX's persistent compile cache, placed from outside (one rule for
    the root scripts: chip_smoke.place_compile_cache)."""
    from chip_smoke import place_compile_cache
    place_compile_cache()


def _apply_platform_override():
    """Hook for the count-only modes the matrix scripts run on the CPU mesh:
    SPARK_RAPIDS_TPU_BENCH_PLATFORM=cpu forces the platform. The default
    entry refuses it (main): a CPU time is never a device metric."""
    plat = os.environ.get("SPARK_RAPIDS_TPU_BENCH_PLATFORM")
    if plat:
        import jax
        jax.config.update("jax_platforms", plat)


def main():
    t_start = time.perf_counter()
    _enable_compilation_cache()
    _apply_platform_override()
    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench.py times the device: platform {platform!r} "
                         "is not 'tpu', refusing to write its times under "
                         "device metric names")

    data = make_data()
    dev_args = [jnp.asarray(a) for a in data]

    # fixed dispatch cost: noop program, D2H-forced
    noop = jax.jit(lambda x: x + 1)
    _force(noop(jnp.float32(0)))
    t0 = time.perf_counter()
    for _ in range(10):
        _force(noop(jnp.float32(0)))
    overhead = (time.perf_counter() - t0) / 10

    many = tpu_many_steps()
    t0 = time.perf_counter()
    _force(many(*dev_args)[0])  # compile (or persistent-cache load)
    t_compile_wall = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        sums, rows = many(*dev_args)
        _force(sums)
        best = min(best, time.perf_counter() - t0)
    t_tpu = max((best - overhead) / K_STEPS, 1e-9)

    t_cpu_1p = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        cpu_sums, cpu_rows = cpu_pipeline(*data)
        t_cpu_1p = min(t_cpu_1p, time.perf_counter() - t0)
    # headline oracle: multi-process (all cores), so `vs_baseline` stops
    # swinging with machine load starving one python process; the
    # single-process number rides along for cross-round continuity
    workers = min(os.cpu_count() or 1, 8)
    if workers > 1:
        try:
            par_sums, par_rows, t_cpu = cpu_oracle_parallel(data, workers)
        except OSError:  # fork-hostile environment: single-proc oracle
            workers, t_cpu = 1, t_cpu_1p
        else:
            # correctness of the parallel oracle must fail LOUDLY — only
            # environment errors above may downgrade to single-process
            assert par_rows == cpu_rows, (par_rows, cpu_rows)
            np.testing.assert_allclose(par_sums, cpu_sums, rtol=1e-9)
    else:
        t_cpu = t_cpu_1p
    assert int(rows) == cpu_rows, (int(rows), cpu_rows)
    # K-step accumulate/divide reorders f64 additions; this is a sanity check,
    # exactness is the differential suite's job
    np.testing.assert_allclose(np.asarray(sums), cpu_sums, rtol=1e-6)

    speedup = t_cpu / t_tpu
    gbps = N_FACT * BYTES_PER_ROW / t_tpu / 1e9
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", str(dev))
    peak = _peak_flops(kind)
    mxu_flops = MXU_FLOPS_PER_STEP / t_tpu
    # compile vs execute split: compile_s is the first-call wall minus one
    # steady-state execution — ~0 on a warm persistent cache, tens of
    # seconds cold — so BENCH rounds can track warm-path wins separately
    # from kernel-time regressions.
    try:  # per-attempt machine-load context (VERDICT weak #3: the
        loadavg = [round(x, 2) for x in os.getloadavg()]  # oracle swings
    except OSError:                                       # with load)
        loadavg = None
    detail = {"device": str(dev), "device_kind": kind,
              "tpu_step_s": round(t_tpu, 5), "cpu_s": round(t_cpu, 5),
              "cpu_s_singleproc": round(t_cpu_1p, 5),
              "cpu_oracle_workers": workers,
              "loadavg": loadavg,
              "compile_s": round(max(t_compile_wall - best, 0.0), 4),
              "execute_s": round(best, 5),
              "pipeline_gbps": round(gbps, 3), "rows": N_FACT,
              "rpc_overhead_s": round(overhead, 4),
              "executed_mxu_flops_per_s": round(mxu_flops, 1),
              "algo_flops_per_s": round(ALGO_FLOPS_PER_STEP / t_tpu, 1),
              "mfu_vs_bf16_peak": (round(mxu_flops / peak, 6)
                                   if peak else None),
              "peak_bf16_flops": peak}

    def emit(d):
        print(_MARK + json.dumps({
            "metric": "scan_join_agg_speedup_vs_cpu",
            "value": round(speedup, 3),
            "unit": "x",
            "vs_baseline": round(speedup / 4.0, 3),
            "detail": d,
        }), flush=True)

    # Primary metric FIRST: if the scan bench hangs and the watchdog kills
    # this child, the supervisor still salvages this line from partial
    # stdout. A successful scan bench re-emits with the extra fields; the
    # supervisor takes the LAST marked line. The scan bench runs IN this
    # process (it holds the chip; a child could not have it) and its
    # failure ends the run non-zero.
    emit(detail)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        detail.update(scan_decode_bench(td))
    emit(detail)


def scan_only() -> None:
    _enable_compilation_cache()
    _apply_platform_override()
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        print(_MARK + json.dumps(scan_decode_bench(td)), flush=True)


PROFILE_ROWS = 32_768
PROFILE_DIM = 512
PROFILE_GROUPS = 16


def profile_query(log_dir: str, force_spill: bool = True) -> dict:
    """Run two representative engine queries with the profiler's JSONL
    event log enabled (ISSUE-4 flag: `--profile-query DIR`):

      1. scan -> filter -> shuffle repartition -> hash join -> ORDER BY a
         detail column — small batches force the out-of-core sort (runs
         parked spillable) and, with `force_spill`, a tight device budget
         makes parked runs spill to host for real;
      2. scan -> grouped aggregation.

    Together the emitted profile exercises every phase the report tool
    breaks down (op/sort/join/agg/spill/shuffle timers all nonzero) and
    gives the per-query comparison table two rows. Returns a summary
    dict; the caller prints it as one JSON line."""
    _apply_platform_override()
    import pyarrow as pa
    from spark_rapids_tpu.expr import Sum, col
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.spans import validate_record

    rng = np.random.default_rng(11)
    n = PROFILE_ROWS
    fact = pa.table({
        "k": pa.array(rng.integers(0, PROFILE_DIM, n)),
        "g": pa.array(rng.integers(0, PROFILE_GROUPS, n).astype(np.int32)),
        "v": pa.array(rng.uniform(0.0, 1.0, n)),
    })
    dim = pa.table({
        "k": pa.array(np.arange(PROFILE_DIM)),
        "w": pa.array(rng.uniform(0.0, 1.0, PROFILE_DIM)),
    })
    session = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.explain": "NONE",
        "spark.rapids.sql.metrics.level": "DEBUG",
        "spark.rapids.tpu.metrics.eventLog.dir": log_dir,
        # many small batches: the sort takes its out-of-core path (runs
        # parked spillable) and the exchange really partitions
        "spark.rapids.sql.batchSizeRows": 4096,
        "spark.rapids.sql.batchSizeBytes": 1 << 20,
    })
    session.initialize_device()
    if force_spill:
        # tight budget: parked sort runs / join builds exceed it, so the
        # park-time accounting (MemoryBudget.note_parked) spills older
        # runs to host — spillTime/readSpill are real measurements
        from spark_rapids_tpu.memory.budget import MemoryBudget
        MemoryBudget.initialize(1 << 20, session.conf)

    q1 = (session.from_arrow(fact)
          .filter(col("v") > 0.1)
          .repartition(4, "k")
          .join(session.from_arrow(dim), on="k")
          .sort("v"))
    out1 = q1.collect()
    prof1 = session.last_profile

    q2 = (session.from_arrow(fact)
          .group_by("g").agg(total=Sum(col("v"))))
    out2 = q2.collect()
    prof2 = session.last_profile

    timers: dict = {}
    bad = 0
    n_recs = 0
    spilled_ns = 0
    for prof in (prof1, prof2):
        if prof is None:
            continue
        recs = prof.to_records()
        n_recs += len(recs)
        bad += sum(1 for r in recs if validate_record(r))
        for r in recs:
            if r["type"] == "operator":
                for k, v in r["metrics"].items():
                    if k.lower().endswith("time") and v:
                        timers[k] = timers.get(k, 0) + v
        tm = prof.task_metrics
        spilled_ns += tm.get("spill_to_host_ns", 0) + \
            tm.get("spill_to_disk_ns", 0)
    return {
        "metric": "profile_query",
        "rows_out": out1.num_rows + out2.num_rows,
        "event_log_dir": log_dir,
        "records": n_recs,
        "invalid_records": bad,
        "wall_ms": round(sum((p.wall_ns if p else 0)
                             for p in (prof1, prof2)) / 1e6, 1),
        "spill_ms": round(spilled_ns / 1e6, 3),
        "nonzero_timers": sorted(timers),
        "task_metrics": {k: v for k, v in (prof2.task_metrics if prof2
                                           else {}).items() if v},
    }


SCHED_LOW_QUERIES = 8
SCHED_HIGH_QUERIES = 2
SCHED_ROWS = 200_000


def sched_bench() -> dict:
    """Overloaded mixed-priority workload (ISSUE-7 flag: `bench.py
    --sched`): N_low low-priority queries flood a concurrentGpuTasks=1
    engine, then N_high high-priority queries arrive late. The SAME
    workload runs twice — FIFO baseline (sched.enabled=false; queries
    still carry contexts so admission is per-query and waits are
    measurable) and scheduler-on (strict priority + fair share) — and the
    JSON reports per-mode admission-wait p50/p99 and the high-priority
    latency the scheduler exists to protect. Acceptance: sched-on
    high-pri p99 < FIFO high-pri p99 under overload."""
    _apply_platform_override()
    import pyarrow as pa
    from spark_rapids_tpu.expr import Count, Sum, col
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.sched import QueryContext
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    from spark_rapids_tpu.tools.profile_report import _percentile

    rng = np.random.default_rng(17)
    n = SCHED_ROWS
    t = pa.table({
        "k": pa.array(rng.integers(0, 4096, n)),
        "g": pa.array(rng.integers(0, 256, n).astype(np.int32)),
        "v": pa.array(rng.uniform(size=n)),
    })

    def percentile(vals, p):
        return _percentile(sorted(vals), p)

    def run_mode(sched_on: bool) -> dict:
        import threading
        import time as _t
        sess = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.explain": "NONE",
            "spark.rapids.sql.concurrentGpuTasks": 1,
            "spark.rapids.tpu.sched.enabled": sched_on,
        })
        sess.initialize_device()
        TpuSemaphore.initialize(1, sess.conf)

        def make_plan():
            return (sess.from_arrow(t).filter(col("v") > 0.2)
                    .group_by("g").agg(total=Sum(col("v")),
                                       cnt=Count(col("k")))).plan

        # warm: compiles out of the measurement
        sess.execute_plan(make_plan(), sched_ctx=QueryContext())
        lat = {}
        wait = {}
        errs = []

        def worker(name, priority):
            try:
                ctx = QueryContext(priority=priority)
                t0 = _t.perf_counter()
                sess.execute_plan(make_plan(), sched_ctx=ctx)
                lat[name] = _t.perf_counter() - t0
                wait[name] = TaskMetrics.get().semaphore_wait_ns / 1e9
            except Exception as e:  # noqa: BLE001 — reported in JSON
                errs.append(f"{name}: {type(e).__name__}: {e}")

        low = [threading.Thread(target=worker, args=(f"low{i}", 0))
               for i in range(SCHED_LOW_QUERIES)]
        high = [threading.Thread(target=worker, args=(f"high{i}", 10))
                for i in range(SCHED_HIGH_QUERIES)]
        for th in low:
            th.start()
        _t.sleep(0.05)  # the overload is standing when high-pri arrives
        for th in high:
            th.start()
        for th in low + high:
            th.join(timeout=600)
        TpuSemaphore._instance = None
        waits = list(wait.values())
        high_lat = [lat[k] for k in lat if k.startswith("high")]
        return {
            "queries": len(lat),
            "errors": errs,
            "wait_p50_s": round(percentile(waits, 50), 4),
            "wait_p99_s": round(percentile(waits, 99), 4),
            "highpri_mean_s": round(float(np.mean(high_lat)), 4)
            if high_lat else None,
            "highpri_p99_s": round(percentile(high_lat, 99), 4)
            if high_lat else None,
        }

    fifo = run_mode(False)
    sched = run_mode(True)
    out = {
        "metric": "sched_bench",
        "low_queries": SCHED_LOW_QUERIES,
        "high_queries": SCHED_HIGH_QUERIES,
        "rows_per_query": SCHED_ROWS,
        "fifo": fifo,
        "sched": sched,
    }
    if fifo.get("highpri_p99_s") and sched.get("highpri_p99_s"):
        out["highpri_p99_speedup_x"] = round(
            fifo["highpri_p99_s"] / sched["highpri_p99_s"], 3)
    return out


RESCACHE_ROWS = 400_000
RESCACHE_REPEATS = 21  # 1 cold + 20 warm => 20/21 ≈ 0.95 hit rate


def rescache_bench() -> dict:
    """Repeated-dashboard-query workload (ISSUE-9 flag: `bench.py
    --rescache`): the SAME scan->filter->aggregate query over a parquet
    file runs RESCACHE_REPEATS times with the result cache on. Reports
    the whole-query hit rate, cold-vs-warm latency (a warm hit is a host
    reply — no decode, no kernels, no admission), the bit-identical gate
    across every repetition, and the no-admission-token assertion
    (scheduler enabled; warm runs must record sched_admissions == 0).
    Acceptance: hit rate > 0.9 and measured warm speedup with identical
    results."""
    _apply_platform_override()
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import rescache
    from spark_rapids_tpu.expr import Count, Sum, col
    from spark_rapids_tpu.memory.semaphore import TpuSemaphore
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    rng = np.random.default_rng(23)
    n = RESCACHE_ROWS
    t = pa.table({
        "k": pa.array(rng.integers(0, 4096, n)),
        "g": pa.array(rng.integers(0, 256, n).astype(np.int32)),
        "v": pa.array(rng.uniform(size=n)),
    })
    tmp = tempfile.mkdtemp(prefix="srtpu_rescache_bench_")
    path = os.path.join(tmp, "fact.parquet")
    pq.write_table(t, path, row_group_size=65_536)

    sess = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.explain": "NONE",
        "spark.rapids.tpu.rescache.enabled": True,
        "spark.rapids.tpu.sched.enabled": True,
    })
    sess.initialize_device()
    TpuSemaphore.initialize(sess.conf.concurrent_tpu_tasks, sess.conf)

    def q():
        return (sess.read_parquet(path).filter(col("v") > 0.25)
                .group_by("g").agg(total=Sum(col("v")),
                                   cnt=Count(col("k")))
                ).collect().sort_by("g")

    # one throwaway compile-warm pass on a DIFFERENT (uncached) shape so
    # the cold measurement is decode+execute, not XLA compilation
    (sess.from_arrow(t.slice(0, 8192)).filter(col("v") > 0.25)
     .group_by("g").agg(total=Sum(col("v")),
                        cnt=Count(col("k")))).collect()

    lat = []
    admissions = []
    hits = []
    reference = None
    identical = True
    for _ in range(RESCACHE_REPEATS):
        t0 = time.perf_counter()
        r = q()
        lat.append(time.perf_counter() - t0)
        tm = TaskMetrics.get()
        admissions.append(tm.sched_admissions)
        hits.append(tm.rescache_hits)
        if reference is None:
            reference = r
        elif not r.equals(reference):
            identical = False
    stats = rescache.stats() or {}
    cold_s = lat[0]
    warm = lat[1:]
    warm_mean = float(np.mean(warm)) if warm else None
    hit_runs = sum(1 for h in hits[1:] if h >= 1)
    hit_rate = hit_runs / max(len(lat) - 1, 1)
    warm_admissions = sum(admissions[1:])
    TpuSemaphore._instance = None
    out = {
        "metric": "rescache_bench",
        "rows": n,
        "repeats": RESCACHE_REPEATS,
        "cold_s": round(cold_s, 5),
        "warm_mean_s": round(warm_mean, 6) if warm_mean else None,
        "warm_p50_s": round(sorted(warm)[len(warm) // 2], 6)
        if warm else None,
        "speedup_warm_vs_cold_x": round(cold_s / warm_mean, 2)
        if warm_mean else None,
        "hit_rate": round(hit_rate, 4),
        "bit_identical": identical,
        "warm_admissions_total": warm_admissions,
        "cache_stats": {k: stats.get(k) for k in
                        ("entries", "bytes", "hits", "misses", "stores",
                         "evictions")},
        "ok": bool(identical and hit_rate > 0.9
                   and warm_admissions == 0),
    }
    return out


MULTICHIP_NDEV = 8
MULTICHIP_ROWS = 400_000
MULTICHIP_DIM = 4_096


def multichip_bench() -> dict:
    """Sharded mesh execution end-to-end (ISSUE-15 flag: `bench.py
    --multichip`): the SAME scan->filter->exchange->join->agg query over
    one parquet fact file runs three ways on the same data —

      * single : one device, no exchanges (the BASELINE engine path);
      * host   : explicit 8-way hash repartition of both join inputs
                 through the MULTITHREADED shuffle manager (the host TCP
                 data plane's serialized bytes);
      * mesh   : `spark.rapids.tpu.mesh.*` sharded execution — scans
                 sharded across the 8 chips, exchanges as ICI
                 collectives, partitions device-resident between stages.

    Reports per-stage wall (scan / scan+filter / full pipeline, warm of
    two runs), bytes moved over ICI vs the host shuffle, collective and
    shard counts, and the bit-identical gate across all three legs.
    Acceptance: identical results, MESH_EXCHANGES > 0 on the mesh leg,
    ZERO host-shuffle bytes on the mesh leg. Feeds the next TPU run
    alongside MULTICHIP_rNN."""
    _apply_platform_override()
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.exec import exchange as EX
    from spark_rapids_tpu.expr import Count, Max, Min, Sum, col
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    import jax
    ndev = MULTICHIP_NDEV
    if len(jax.devices()) < ndev:
        return {"metric": "multichip_bench", "ndev": ndev,
                "error": f"only {len(jax.devices())} devices present "
                         "(hint: SPARK_RAPIDS_TPU_BENCH_PLATFORM=cpu "
                         "forces the 8-virtual-device mesh)"}

    rng = np.random.default_rng(15)
    n = MULTICHIP_ROWS
    fact = pa.table({
        "id": pa.array(rng.integers(0, 50_000, n), type=pa.int64()),
        "val": pa.array(rng.uniform(-1, 1, n), type=pa.float64()),
        "small": pa.array(rng.integers(-100, 100, n).astype(np.int32)),
    })
    dim_keys = rng.permutation(50_000)[:MULTICHIP_DIM]
    dim = pa.table({
        "id": pa.array(dim_keys, type=pa.int64()),
        "tag": pa.array([f"t{int(k) % 31}" for k in dim_keys]),
    })
    tmp = tempfile.mkdtemp(prefix="srtpu_multichip_bench_")
    fact_path = os.path.join(tmp, "fact.parquet")
    dim_path = os.path.join(tmp, "dim.parquet")
    pq.write_table(fact, fact_path, row_group_size=n // 16)
    pq.write_table(dim, dim_path)

    base_conf = {
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.explain": "NONE",
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
    }
    mesh_conf = dict(base_conf)
    mesh_conf.update({
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.shape": f"shuffle={ndev}",
        "spark.rapids.tpu.mesh.enabled": True,
    })

    def queries(sess, repartition):
        scan = sess.read_parquet(fact_path)
        filt = scan.filter(col("val") > -0.5)
        left, right = filt, sess.read_parquet(dim_path)
        if repartition:
            left = left.repartition(ndev, "id")
            right = right.repartition(ndev, "id")
        full = (left.join(right, on="id", how="inner")
                .group_by("tag").agg(n=Count(col("val")),
                                     s=Sum(col("small")),
                                     mx=Max(col("id")),
                                     mn=Min(col("small"))))
        return {"scan": scan, "scan_filter": filt, "full": full}

    def run_leg(conf, repartition=False):
        sess = TpuSession(dict(conf))
        qs = queries(sess, repartition)
        stages = {}
        for name, q in qs.items():
            walls = []
            for _ in range(2):  # second run is compile-warm
                t0 = time.perf_counter()
                result = q.collect()
                walls.append(time.perf_counter() - t0)
            stages[name + "_s"] = round(min(walls), 4)
            stages[name + "_cold_s"] = round(walls[0], 4)
        TaskMetrics.reset()
        before = EX.MESH_EXCHANGES
        result = qs["full"].collect().sort_by("tag")
        tm = TaskMetrics.get()
        return result, stages, {
            "mesh_exchanges": EX.MESH_EXCHANGES - before,
            "mesh_shards": tm.mesh_shards,
            "ici_bytes": tm.mesh_ici_bytes,
            "host_shuffle_bytes": tm.shuffle_bytes_written,
        }

    r_single, st_single, m_single = run_leg(base_conf)
    r_host, st_host, m_host = run_leg(base_conf, repartition=True)
    r_mesh, st_mesh, m_mesh = run_leg(mesh_conf)

    identical = r_single.equals(r_host) and r_single.equals(r_mesh)
    out = {
        "metric": "multichip_bench",
        "ndev": ndev,
        "rows": n,
        "single": st_single,
        "host_shuffle": {**st_host,
                         "shuffle_bytes": m_host["host_shuffle_bytes"]},
        "mesh": {**st_mesh, **m_mesh},
        "bytes_over_ici": m_mesh["ici_bytes"],
        "bytes_over_host_shuffle": m_host["host_shuffle_bytes"],
        "speedup_mesh_vs_single_x": round(
            st_single["full_s"] / st_mesh["full_s"], 3)
        if st_mesh["full_s"] else None,
        "bit_identical": bool(identical),
        "ok": bool(identical and m_mesh["mesh_exchanges"] > 0
                   and m_mesh["host_shuffle_bytes"] == 0
                   and m_mesh["mesh_shards"] >= ndev),
    }
    return out


STATS_ROWS = 300_000


def stats_bench() -> dict:
    """Runtime-statistics feedback bench (ISSUE-11 flag: `bench.py
    --stats`): a deliberately misestimate-prone join — the build side is
    an equality filter whose static selectivity heuristic (5%) is ~3000x
    off — runs cold (static estimates) then warm (history feedback).
    Reports the worst per-operator q-error before/after feedback, the
    plan-choice flip (shuffled join cold -> broadcast join warm, since
    the build side's OBSERVED size sits under the broadcast threshold),
    and the adaptive coalesce decision flipping from observed-bytes to
    history (picked before the stage runs). Acceptance: cold q-error
    >= 10, warm q-error <= 1.5, both flips happen, results identical."""
    _apply_platform_override()
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import stats
    from spark_rapids_tpu.expr import Sum, col, lit
    from spark_rapids_tpu.plugin import TpuSession

    rng = np.random.default_rng(41)
    n = STATS_ROWS
    b = rng.integers(0, 10_000_000, n)
    b[:100] = 777  # the filter's ACTUAL survivors
    rng.shuffle(b)
    tmp = tempfile.mkdtemp(prefix="srtpu_stats_bench_")
    fpath = os.path.join(tmp, "fact.parquet")
    dpath = os.path.join(tmp, "dim.parquet")
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 4096, n)),
        "v": pa.array(rng.uniform(size=n))}), fpath,
        row_group_size=65_536)
    pq.write_table(pa.table({
        "k": pa.array(rng.integers(0, 4096, n)),
        "b": pa.array(b)}), dpath, row_group_size=65_536)

    sess = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.explain": "NONE",
        "spark.rapids.tpu.stats.enabled": True,
        "spark.rapids.tpu.stats.feedback.enabled": True,
        # between the ACTUAL filtered build side (~2KB) and the static
        # 5%-selectivity estimate (~250KB)
        "spark.rapids.sql.autoBroadcastJoinThreshold": 64 << 10,
    })

    def q():
        f = sess.read_parquet(fpath)
        d = sess.read_parquet(dpath).filter(col("b") == lit(777))
        return (f.join(d, on="k").group_by("k")
                .agg(s=Sum(col("v")))).collect().sort_by("k")

    def run():
        t0 = time.perf_counter()
        r = q()
        dt = time.perf_counter() - t0
        worst = sess.last_stats.worst()
        joins = [o["name"] for o in sess.last_stats.ops if "Join" in
                 o["name"]]
        return r, dt, worst, joins

    r_cold, t_cold, worst_cold, joins_cold = run()
    r_warm, t_warm, worst_warm, joins_warm = run()
    flip = "TpuShuffledHashJoinExec" in joins_cold and \
        "TpuBroadcastHashJoinExec" in joins_warm

    # adaptive coalesce: observed-bytes cold, history warm (decided
    # before the stage executes)
    sess2 = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.explain": "NONE",
        "spark.rapids.sql.adaptive.enabled": True,
        "spark.rapids.tpu.stats.enabled": True,
        "spark.rapids.tpu.stats.feedback.enabled": True,
    })
    t2 = pa.table({
        "k": pa.array(rng.integers(0, 512, 100_000)),
        "v": pa.array(rng.uniform(size=100_000))})
    aq = sess2.from_arrow(t2).repartition(32, "k") \
        .group_by("k").agg(s=Sum(col("v")))
    a1 = aq.collect().sort_by("k")
    co_cold = [e for e in sess2._adaptive_log
               if e["rule"] == "coalescePartitions"]
    a2 = aq.collect().sort_by("k")
    co_warm = [e for e in sess2._adaptive_log
               if e["rule"] == "coalescePartitions"]
    coalesce_flip = bool(
        co_cold and co_cold[0]["source"] == "observed"
        and co_warm and co_warm[0]["source"] == "history"
        and co_cold[0]["to"] == co_warm[0]["to"])

    hist = stats.stats() or {}
    out = {
        "metric": "stats_bench",
        "rows": n,
        "cold_s": round(t_cold, 4),
        "warm_s": round(t_warm, 4),
        "q_error_cold": round(float(worst_cold["q_error"]), 2)
        if worst_cold else None,
        "q_error_warm": round(float(worst_warm["q_error"]), 2)
        if worst_warm else None,
        "join_cold": joins_cold,
        "join_warm": joins_warm,
        "broadcast_flip": flip,
        "coalesce_cold": co_cold[0] if co_cold else None,
        "coalesce_warm": co_warm[0] if co_warm else None,
        "coalesce_flip": coalesce_flip,
        "bit_identical": bool(r_cold.equals(r_warm)
                              and a1.equals(a2)),
        "history": {k: hist.get(k) for k in
                    ("entries", "hits", "misses", "records")},
        "ok": bool(worst_cold and worst_warm
                   and worst_cold["q_error"] >= 10
                   and worst_warm["q_error"] <= 1.5
                   and flip and coalesce_flip
                   and r_cold.equals(r_warm) and a1.equals(a2)),
    }
    return out


FLEET_WORKERS = 3
FLEET_PLANS = 4          # distinct dashboard queries in the mix
FLEET_ROUNDS = 7         # repeats of the mix: 4 cold + 24 warm chances
FLEET_ROWS = 200_000


def fleet_bench() -> dict:
    """Fleet-gateway routing bench (ISSUE-10 flag: `bench.py --fleet`):
    a repeated mixed dashboard workload (FLEET_PLANS distinct queries x
    FLEET_ROUNDS) dispatched through a gateway over FLEET_WORKERS real
    `TpuDeviceService` processes, once with forced-random routing and
    once with cache-affinity routing. Workers run the result cache; XLA
    compiles are pre-warmed on every worker so the two modes differ only
    in PLACEMENT. Reports per-mode warm hit rate and p50/p99 latency —
    affinity should approach hit_rate 1.0 where random sits near 1/N.
    Workers are pinned to the CPU backend (N processes cannot share one
    TPU); the routing/caching effects measured here are
    placement-layer."""
    import tempfile
    import threading

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.fleet.gateway import FleetGateway
    from spark_rapids_tpu.service import TpuServiceClient
    from spark_rapids_tpu.tools.profile_report import _percentile

    repo = os.path.dirname(os.path.abspath(__file__))
    d = tempfile.mkdtemp(prefix="srtpu_fleet_bench_")
    rng = np.random.default_rng(13)
    t = pa.table({"k": pa.array(rng.integers(0, 4096, FLEET_ROWS)),
                  "v": pa.array(rng.uniform(size=FLEET_ROWS))})
    path = os.path.join(d, "fact.parquet")
    pq.write_table(t, path, row_group_size=65_536)
    paths = {"t": [path]}

    def attr(name, dt):
        return [{"class": "org.apache.spark.sql.catalyst.expressions."
                 "AttributeReference", "num-children": 0, "name": name,
                 "dataType": dt, "nullable": True, "metadata": {},
                 "exprId": {"id": 1, "jvmId": "x"}, "qualifier": []}]

    def plan(thr):
        filt = {"class": "org.apache.spark.sql.execution.FilterExec",
                "num-children": 1,
                "condition": [
                    {"class": "org.apache.spark.sql.catalyst.expressions."
                     "GreaterThan", "num-children": 2}]
                + attr("v", "double")
                + [{"class": "org.apache.spark.sql.catalyst.expressions."
                    "Literal", "num-children": 0, "value": str(thr),
                    "dataType": "double"}]}
        scan = {"class": "org.apache.spark.sql.execution."
                "FileSourceScanExec", "num-children": 0,
                "relation": "HadoopFsRelation(parquet)",
                "output": [attr("k", "long"), attr("v", "double")],
                "tableIdentifier": "t"}
        return json.dumps([filt, scan])

    plans = [plan(0.1 + 0.17 * i) for i in range(FLEET_PLANS)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    socks = {f"w{i}": os.path.join(d, f"w{i}.sock")
             for i in range(FLEET_WORKERS)}
    procs = {n: subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.service.server",
         "--socket", s, "--platform", "cpu",
         "--conf", "spark.rapids.tpu.rescache.enabled=true"],
        cwd=repo, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL) for n, s in socks.items()}
    try:
        for s in socks.values():
            TpuServiceClient(s, deadline_s=120.0).connect().close()
        # compile-warm EVERY plan on EVERY worker so random's extra XLA
        # compiles don't masquerade as routing cost
        for s in socks.values():
            with TpuServiceClient(s, deadline_s=300.0) as cli:
                for p in plans:
                    cli.run_plan(p, paths)

        def pool_hits(cli) -> int:
            stats = cli.cache_stats()
            return sum(w.get("hits", {}).get("query", 0)
                       for w in stats.values() if isinstance(w, dict))

        def pool_entries(cli) -> int:
            stats = cli.cache_stats()
            return sum(w.get("entries", 0)
                       for w in stats.values() if isinstance(w, dict))

        def run_mode(routing: str) -> dict:
            for s in socks.values():
                with TpuServiceClient(s, deadline_s=30.0) as cli:
                    cli.cache_invalidate()
            gw_sock = os.path.join(d, f"gw_{routing}.sock")
            gw = FleetGateway(
                list(socks.items()),
                {"spark.rapids.tpu.fleet.routing": routing,
                 "spark.rapids.tpu.fleet.probe.intervalMs": 500},
                gw_sock)
            th = threading.Thread(target=gw.serve_forever, daemon=True)
            th.start()
            lat = []
            reference = [None] * len(plans)
            identical = True
            with TpuServiceClient(gw_sock, deadline_s=300.0) as cli:
                hits0 = pool_hits(cli)   # lifetime counters: delta them
                hits_round2 = None
                for rnd_ix in range(FLEET_ROUNDS):
                    for i, p in enumerate(plans):
                        t0 = time.perf_counter()
                        r = cli.run_plan(p, paths)
                        lat.append(time.perf_counter() - t0)
                        if reference[i] is None:
                            reference[i] = r
                        elif not r.equals(reference[i]):
                            identical = False
                    if rnd_ix == 1:
                        hits_round2 = pool_hits(cli) - hits0
                hits = pool_hits(cli) - hits0
                entries = pool_entries(cli)
                cli.shutdown()
            th.join(timeout=10)
            warm_chances = len(plans) * (FLEET_ROUNDS - 1)
            lat_sorted = sorted(lat)
            return {
                "queries": len(lat),
                "warm_hit_rate": round(hits / warm_chances, 4),
                # round 2 isolates the 1/N story: under random routing a
                # repeat only hits when it lands on the one worker that
                # saw it; affinity pins it there by construction
                "round2_hit_rate": round((hits_round2 or 0) / len(plans),
                                         4),
                "p50_s": round(_percentile(lat_sorted, 50), 5),
                "p99_s": round(_percentile(lat_sorted, 99), 5),
                "bit_identical": identical,
                "cache_entries_pool": entries,
                "route_decisions": gw._fleet_stats()["route_decisions"],
            }

        rnd = run_mode("random")
        aff = run_mode("affinity")
    finally:
        for n, p in procs.items():
            try:
                with TpuServiceClient(socks[n], deadline_s=3.0) as cli:
                    cli.shutdown()
            except Exception:
                pass
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    out = {
        "metric": "fleet_bench",
        "workers": FLEET_WORKERS,
        "plans": FLEET_PLANS,
        "rounds": FLEET_ROUNDS,
        "rows": FLEET_ROWS,
        "random": rnd,
        "affinity": aff,
        "ok": bool(aff["bit_identical"] and rnd["bit_identical"]
                   and aff["warm_hit_rate"] > rnd["warm_hit_rate"]),
    }
    if rnd["p50_s"]:
        out["p50_speedup_affinity_vs_random_x"] = round(
            rnd["p50_s"] / max(aff["p50_s"], 1e-9), 2)
    return out


PROBE_TIMEOUT_S = 35
PROBE_ATTEMPTS = 2


# probe_backend/supervise start children that need the chip. That is sound
# only because THIS process never imports JAX: each child is the one process
# on the chip while it lives, and they run one after the other.
def probe_backend() -> "tuple[bool, str]":
    """~30s-bounded subprocess probe of the device backend BEFORE burning a
    full attempt window: a dead runtime costs 2x35s, not 3x300s. Returns
    (ok, detail)."""
    plat = os.environ.get("SPARK_RAPIDS_TPU_BENCH_PLATFORM")
    cfg = (f"jax.config.update('jax_platforms', {plat!r}); " if plat else "")
    code = f"import jax; {cfg}print(jax.devices()[0])"
    last = ""
    for i in range(1, PROBE_ATTEMPTS + 1):
        try:
            proc = subprocess.run([sys.executable, "-c", code],
                                  capture_output=True, text=True,
                                  timeout=PROBE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            last = (f"probe {i}: no backend response in {PROBE_TIMEOUT_S}s "
                    "(runtime hung at first touch)")
            continue
        if proc.returncode == 0 and proc.stdout.strip():
            return True, proc.stdout.strip().splitlines()[-1]
        tail = (proc.stderr or "").strip().splitlines()[-1:] or ["<no output>"]
        last = f"probe {i}: rc={proc.returncode} {tail[0]}"
    return False, last


def supervise() -> int:
    """Probe the backend, then run main() in a child under a watchdog;
    retry; emit error JSON if all fail."""
    ok, detail = probe_backend()
    if not ok:
        print(json.dumps({
            "metric": "scan_join_agg_speedup_vs_cpu",
            "value": None,
            "unit": "x",
            "vs_baseline": None,
            "error": f"backend probe failed, skipping attempts: {detail}",
            "detail": {"probe": detail},
        }), flush=True)
        return 1
    errors = [f"probe ok: {detail}"]

    def last_marked(stdout):
        lines = [ln for ln in (stdout or "").splitlines()
                 if ln.startswith(_MARK)]
        return lines[-1][len(_MARK):] if lines else None

    for attempt in range(1, ATTEMPTS + 1):
        env = dict(os.environ, **{_CHILD_ENV: "1"})
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__)],
                capture_output=True, text=True, timeout=ATTEMPT_TIMEOUT_S,
                env=env)
        except subprocess.TimeoutExpired as te:
            # salvage the primary-metric line from partial stdout: main()
            # emits it before the scan bench, so a scan-bench hang still
            # yields the headline number
            out = te.stdout
            if isinstance(out, bytes):
                out = out.decode("utf-8", "replace")
            line = last_marked(out)
            if line:
                print(line, flush=True)
                return 0
            errors.append(f"attempt {attempt}: timeout after "
                          f"{ATTEMPT_TIMEOUT_S}s (backend init hang?)")
            continue
        line = last_marked(proc.stdout)
        if line:
            print(line, flush=True)
            return 0
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-3:]
        errors.append(f"attempt {attempt}: rc={proc.returncode} "
                      + " | ".join(tail))
        if attempt < ATTEMPTS:
            time.sleep(5 * attempt)
    print(json.dumps({
        "metric": "scan_join_agg_speedup_vs_cpu",
        "value": None,
        "unit": "x",
        "vs_baseline": None,
        "error": f"all {ATTEMPTS} attempts failed",
        "detail": {"attempts": errors},
    }), flush=True)
    return 1


if __name__ == "__main__":
    if "--profile-query" in sys.argv:
        # bench flag (ISSUE-4): emit the JSONL profile event log for one
        # engine query into the given dir and print a one-line summary
        ix = sys.argv.index("--profile-query")
        if ix + 1 >= len(sys.argv):
            print("usage: bench.py --profile-query LOG_DIR [--no-spill]",
                  file=sys.stderr)
            sys.exit(2)
        _enable_compilation_cache()
        print(json.dumps(profile_query(
            sys.argv[ix + 1],
            force_spill="--no-spill" not in sys.argv)), flush=True)
    elif "--sched" in sys.argv:
        # bench flag (ISSUE-7): overloaded mixed-priority workload, FIFO
        # baseline vs scheduler, one JSON line (appended to BENCH detail)
        _enable_compilation_cache()
        print(json.dumps(sched_bench()), flush=True)
    elif "--fleet" in sys.argv:
        # bench flag (ISSUE-10): repeated mixed workload over a worker
        # pool — affinity vs forced-random routing: warm hit rate and
        # p50/p99 latency per mode; one JSON line
        print(json.dumps(fleet_bench()), flush=True)
    elif "--stats" in sys.argv:
        # bench flag (ISSUE-11): misestimate-prone join cold vs warm-
        # history — q-error before/after feedback, broadcast-vs-shuffle
        # and coalesce-count plan flips; one JSON line
        _enable_compilation_cache()
        print(json.dumps(stats_bench()), flush=True)
    elif "--multichip" in sys.argv:
        # bench flag (ISSUE-15): sharded mesh execution — single-device
        # vs host-shuffle vs ICI-collective legs on the same data, with
        # per-stage wall, bytes over ICI vs host shuffle, and the
        # bit-identical gate; one JSON line
        if os.environ.get("SPARK_RAPIDS_TPU_BENCH_PLATFORM") == "cpu":
            # must land before jax initializes a backend
            import re as _re
            _f = os.environ.get("XLA_FLAGS", "")
            _f = _re.sub(r"--xla_force_host_platform_device_count=\d+",
                         "", _f)
            os.environ["XLA_FLAGS"] = (
                _f + f" --xla_force_host_platform_device_count="
                     f"{MULTICHIP_NDEV}").strip()
            os.environ["JAX_PLATFORMS"] = "cpu"
        _enable_compilation_cache()
        print(json.dumps(multichip_bench()), flush=True)
    elif "--rescache" in sys.argv:
        # bench flag (ISSUE-9): repeated-query workload through the
        # result cache — hit rate, warm-vs-cold speedup, bit-identical
        # gate, zero-admission warm runs; one JSON line
        _enable_compilation_cache()
        print(json.dumps(rescache_bench()), flush=True)
    elif "--scan-pushdown" in sys.argv:
        # bench flag (ISSUE-12): full pushdown sweep (selectivity x
        # predicate type + aggregate-only), GB/s + bytes-materialised +
        # rows-pruned per shape; one JSON line
        _enable_compilation_cache()
        _apply_platform_override()
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            print(json.dumps(scan_pushdown_bench(td, full=True)),
                  flush=True)
    elif "--fusion" in sys.argv:
        # bench flag (ISSUE-16): whole-stage fusion sweep — the same
        # chains with fusion on vs off: wall, device-dispatch counts and
        # the overall dispatch-reduction factor, bit-identical gate per
        # shape; one JSON line
        _enable_compilation_cache()
        _apply_platform_override()
        print(json.dumps(fusion_query_bench()), flush=True)
    elif "--scan-only" in sys.argv:
        scan_only()
    elif os.environ.get(_CHILD_ENV):
        main()
    else:
        sys.exit(supervise())
