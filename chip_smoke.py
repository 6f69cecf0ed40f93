"""Chip smoke: the engine's main path, once, on one TPU chip.

    python chip_smoke.py                 # one chip, TPC-DS SF10 star, fact cut
    python chip_smoke.py --multichip     # four chips, the mesh exchange only
    python chip_smoke.py --rows 100000   # rehearsal size (CPU or chip)

One process, no child that touches JAX. Builds the native host runtime from
the committed sources, writes the star from `--seed`, and runs q3/q7/q96 and
the per-customer rank through `TpuSession` twice each (cold, warm) against a
pyarrow reference that shares no code with the device path. Every stand-in
that could let a run pass without the chip doing the work is a hard failure:
no chip, a CPU plan section, a nested-loop join, a whole-query CPU rerun, a
compile degraded to direct jit, an assumed HBM size, an interpreted Pallas
kernel, an unbuilt native library. On a platform other than `tpu` it still
runs every phase (that is the rehearsal) and then fails.

Earlier stdout lines are one JSON object each (notes, not metrics); the last
line is `{"ok": ..., "device": {"platform", "kind", "count"}}`."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# store_sales is cut from SF10's 28,800,991 rows to 2^21: two 1M-row row
# groups, so the scan takes the multi-row-group path every real table takes
# (`io.parquet.fused_multi_decode`, default `chunksPerDispatch=4`), in one
# 2M-row batch shape, and the whole script fits its 1,200 s on a machine with
# no compile cache. Measured on the v5e (PR 23, PERF.md): a warm 1M-row batch
# takes 3-7.5 s per query, so eight runs at SF10 need ~1,300 s with no
# compile at all; at 4 x 2^20 rows the four-chunk decode program alone
# compiled for 678 s and the cold script took ~1,370 s. Dimensions stay at
# SF10 size.
FACT_ROWS = 1 << 21
PALLAS_ROWS = 4 << 20
MULTICHIP_FACT, MULTICHIP_DIM = 4 << 20, 1 << 20


def say(**note) -> None:
    print(json.dumps(note, default=str), flush=True)


def place_compile_cache() -> str:
    """JAX's persistent compile cache, placed from outside: where
    JAX_COMPILATION_CACHE_DIR says (JAX reads it; nothing is set in code),
    else `<checkout>/.jax_cache`. The path is part of the cache key, so it
    is never a temp name. Returns the directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build_native() -> None:
    """`make -C native` from the committed sources; `native/build/` is not
    in git, and without the library the scan quietly takes numpy paths."""
    proc = subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native runtime did not build:\n{proc.stderr}")


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def device_note(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def noop_dispatch_seconds(jax) -> dict:
    """Cost of one D2H-forced no-op dispatch (ROADMAP S2 hangs on it)."""
    import numpy as np
    noop = jax.jit(lambda x: x + 1)
    x = jax.numpy.float32(0)
    np.asarray(noop(x))
    reads = []
    for _ in range(200):
        t0 = time.perf_counter()
        np.asarray(noop(x))
        reads.append(time.perf_counter() - t0)
    q = np.percentile(reads, [25, 50, 75])
    return {"p25": q[0], "median": q[1], "p75": q[2], "readings": len(reads)}


# --------------------------------------------------------------- references
def _cents(arr):
    """decimal(p, 2) arrow array -> int64 unscaled numpy (nulls -> 0)."""
    import decimal

    import pyarrow as pa
    import pyarrow.compute as pc
    # narrowed first: pyarrow's sum is decimal(38, 2), and x100 of that has
    # no room; both casts are checked, so nothing is lost silently
    wide = pc.multiply(arr.cast(pa.decimal128(20, 2)),
                       pa.scalar(decimal.Decimal(100)))
    return pc.fill_null(wide.cast(pa.int64()), 0).to_numpy()


def _frame(table, money=()):
    """Result table -> pandas, decimal columns as exact int64 cents plus a
    null mask (so equality is exact and cheap at 500k rows)."""
    import pandas as pd
    cols = {}
    for name in table.schema.names:
        col = table.column(name).combine_chunks()
        if name in money:
            cols[name] = _cents(col)
            cols[name + "_null"] = col.is_null().to_numpy(
                zero_copy_only=False)
        else:
            cols[name] = col.to_numpy(zero_copy_only=False)
    return pd.DataFrame(cols)


def _same(got, want, keys, exact, close=()) -> bool:
    import numpy as np
    if len(got) != len(want):
        return False
    if keys:
        got = got.sort_values(keys).reset_index(drop=True)
        want = want.sort_values(keys).reset_index(drop=True)
    for c in list(keys) + list(exact):
        if not np.array_equal(got[c].to_numpy(), want[c].to_numpy()):
            return False
    return all(np.allclose(got[c].to_numpy(float), want[c].to_numpy(float),
                           rtol=1e-10, atol=0) for c in close)


def references(paths: dict) -> dict:
    """The four answers from pyarrow's own reader, join and group-by."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    ss = pq.read_table(paths["store_sales"])
    dd = pq.read_table(paths["date_dim"])
    it = pq.read_table(paths["item"])
    st = pq.read_table(paths["store"])

    def join(left, right, lkey, rkey, *keep):
        return left.join(right.select([rkey, *keep]), keys=lkey,
                         right_keys=rkey, join_type="inner")

    nov = dd.filter(pc.equal(dd["d_moy"], 11))
    q3 = (join(join(ss.select(["ss_sold_date_sk", "ss_item_sk",
                               "ss_sales_price"]),
                    nov, "ss_sold_date_sk", "d_date_sk", "d_year"),
               it, "ss_item_sk", "i_item_sk", "i_brand")
          .group_by(["d_year", "i_brand"])
          .aggregate([("ss_sales_price", "sum")])
          .rename_columns(["d_year", "i_brand", "sum_agg"]))
    tn = st.filter(pc.equal(st["s_state"], "TN"))
    q7 = (join(join(ss.select(["ss_item_sk", "ss_store_sk", "ss_quantity"]),
                    tn, "ss_store_sk", "s_store_sk"),
               it, "ss_item_sk", "i_item_sk", "i_category")
          .group_by(["i_category"])
          .aggregate([("ss_quantity", "mean"), ("ss_quantity", "count")])
          .rename_columns(["i_category", "q", "n"]))
    q68 = (ss.select(["ss_customer_sk", "ss_sales_price", "ss_quantity"])
           .group_by(["ss_customer_sk"])
           .aggregate([("ss_sales_price", "sum"), ("ss_quantity", "sum")])
           .rename_columns(["ss_customer_sk", "spend", "qty"]))
    sat = dd.filter(pc.equal(dd["d_dow"], 6))
    busy = ss.select(["ss_sold_date_sk", "ss_store_sk", "ss_quantity"])
    busy = busy.filter(pc.greater(busy["ss_quantity"], 50))
    q96 = join(join(busy, sat, "ss_sold_date_sk", "d_date_sk"),
               st, "ss_store_sk", "s_store_sk").num_rows
    return {"q3_brand_report": q3, "q7_star_avg": q7,
            "q68_window_rank": q68, "q96_selective_count": q96}


def agrees(name: str, got, want) -> bool:
    import numpy as np
    if name == "q3_brand_report":
        return _same(_frame(got, ["sum_agg"]), _frame(want, ["sum_agg"]),
                     ["d_year", "i_brand"], ["sum_agg", "sum_agg_null"])
    if name == "q7_star_avg":
        return _same(_frame(got), _frame(want), ["i_category"], ["n"], ["q"])
    if name == "q96_selective_count":
        return got.num_rows == 1 and got.column("cnt")[0].as_py() == want
    # per-customer rank: sums exact per customer; rnk is 1..N in output
    # order, spend never rising along it, null spends last (ties in spend
    # may order either way, so rnk is not compared row by row)
    g = _frame(got, ["spend"])
    if not _same(g, _frame(want, ["spend"]), ["ss_customer_sk"],
                 ["spend", "spend_null", "qty"]):
        return False
    g = g.sort_values("rnk")
    null = g["spend_null"].to_numpy()
    spend = g["spend"].to_numpy()[~null]
    return (np.array_equal(g["rnk"].to_numpy(), np.arange(1, len(g) + 1))
            and not null[:len(spend)].any()
            and bool(np.all(np.diff(spend) <= 0)))


# ------------------------------------------------------------------ phases
def plan_names(node) -> list:
    out = [node.name]
    for child in node.children:
        out.extend(plan_names(child))
    return out


def scan_read_seconds(node) -> float:
    from spark_rapids_tpu.utils import metrics as M
    own = node.metrics.snapshot().get(M.READ_TIME, 0) / 1e9
    return own + sum(scan_read_seconds(c) for c in node.children)


def run_query(session, name: str, df, want) -> None:
    from spark_rapids_tpu.utils.metrics import TaskMetrics
    note = {"query": name}
    for leg in ("cold", "warm"):
        t0 = time.perf_counter()
        got = df.collect()
        note[leg + "_s"] = time.perf_counter() - t0
        tm = TaskMetrics.get()
        plan = session.last_plan
        names = plan_names(plan)
        check(not any(n.startswith("Cpu") or "FromCpu" in n for n in names),
              f"{name}: a plan section ran on the CPU engine: {names}")
        check("TpuNestedLoopJoinExec" not in names,
              f"{name}: nested-loop join in the plan: {names}")
        check(tm.cpu_fallback_reruns == 0, f"{name}: whole-query CPU rerun")
        check(tm.compile_fallbacks == 0,
              f"{name}: a compile degraded to direct jit")
        check(agrees(name, got, want),
              f"{name} ({leg}): result differs from the pyarrow reference")
        note.update({
            leg + "_dispatches": tm.device_dispatches,
            leg + "_compile_count": tm.compile_count,
            leg + "_compile_s": tm.compile_ns / 1e9,
            leg + "_scan_read_s": scan_read_seconds(plan),
        })
    note.update({"plan": names, "rows_out": got.num_rows,
                 "reference_agreed": True})
    say(**note)


def scan_on_device(session, paths: dict, device) -> None:
    """The first scanned batch of every table the queries read sits on the
    chip."""
    from spark_rapids_tpu.plan.overrides import Overrides
    t0 = time.perf_counter()
    for name, path in paths.items():
        plan = Overrides(session.conf).apply(
            session.read_parquet(path).plan)
        stream = plan.execute()
        batch = next(stream)
        stream.close()
        where = set()
        for c in batch.columns:
            where |= set(c.data.devices())
        check(where == {device},
              f"{name}: scanned batch on {where}, not on {device}")
    say(scan_batches_on=str(device), tables=sorted(paths),
        scan_first_batches_s=time.perf_counter() - t0)


def _murmur3_long(low, high, seed):
    """Spark's Murmur3 hashLong over int32 words, in plain numpy uint32."""
    import numpy as np

    def rotl(x, r):
        return (x << np.uint32(r)) | (x >> np.uint32(32 - r))

    def mix(h, k):
        k = rotl(k * np.uint32(0xcc9e2d51), 15) * np.uint32(0x1b873593)
        return rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xe6546b64)

    h = mix(mix(seed.view(np.uint32), low.view(np.uint32)),
            high.view(np.uint32)) ^ np.uint32(8)
    h = (h ^ (h >> np.uint32(16))) * np.uint32(0x85ebca6b)
    h = (h ^ (h >> np.uint32(13))) * np.uint32(0xc2b2ae35)
    return (h ^ (h >> np.uint32(16))).view(np.int32)


def pallas_kernels(jax, rows: int, seed: int) -> None:
    """The three Pallas entry points against numpy, compiled on the chip
    (interpreted only when the platform is cpu — `ops.pallas_mode`)."""
    import numpy as np
    from spark_rapids_tpu.ops import pallas_mode
    from spark_rapids_tpu.ops.pallas_groupby import segment_sum_i64
    from spark_rapids_tpu.ops.pallas_probe import hash_long_rows
    from spark_rapids_tpu.ops.pallas_segsum import segment_sum_f64
    check(pallas_mode.interpret() == (jax.default_backend() != "tpu"),
          "Pallas interpret mode does not follow the platform")
    rng = np.random.default_rng(seed)
    g = 1024
    ids = rng.integers(0, g, rows, dtype=np.int32)
    put = jax.numpy.asarray

    ints = rng.integers(-2**62, 2**62, rows, dtype=np.int64)
    want = np.zeros(g, np.int64)
    np.add.at(want, ids, ints)
    got = np.asarray(segment_sum_i64(put(ints), put(ids), g))
    check(np.array_equal(got, want), "segment_sum_i64 is not bit-equal")

    vals = rng.normal(0.0, 1e3, rows)
    got = np.asarray(segment_sum_f64(put(vals), put(ids), g))
    exact = np.bincount(ids, weights=vals, minlength=g)
    mass = np.bincount(ids, weights=np.abs(vals), minlength=g) + 1.0
    f64_err = float(np.max(np.abs(got - exact) / mass))
    check(f64_err <= 1e-6, "segment_sum_f64 is outside its 1e-6 * mass bound")

    low, high, sd = (rng.integers(-2**31, 2**31, rows, dtype=np.int64)
                     .astype(np.int32) for _ in range(3))
    with np.errstate(over="ignore"):
        want = _murmur3_long(low, high, sd)
    got = np.asarray(hash_long_rows(put(low), put(high), put(sd)))
    check(np.array_equal(got, want), "hash_long_rows is not bit-equal")
    say(pallas_rows=rows, groups=g, interpreted=pallas_mode.interpret(),
        segment_sum_i64="bit-equal", hash_long_rows="bit-equal",
        segment_sum_f64_max_err_over_mass=f64_err)


def multi_chip(args, jax) -> None:
    """Four chips: the planned query of `__graft_entry__.dryrun_multichip`
    (filter -> hash exchange -> shuffled join -> grouped agg -> sort) over
    the ICI mesh, against pandas. Only this phase and its comparison."""
    import numpy as np
    import pandas as pd
    import pyarrow as pa
    from spark_rapids_tpu.exec import exchange as EX
    from spark_rapids_tpu.expr import Count, Max, Min, Sum, col
    from spark_rapids_tpu.plugin import TpuSession
    from spark_rapids_tpu.utils.metrics import TaskMetrics

    check(jax.device_count() == 4, f"{jax.device_count()} devices, not 4")
    n_fact = args.rows or MULTICHIP_FACT
    n_dim = max(n_fact // (MULTICHIP_FACT // MULTICHIP_DIM), 1)
    rng = np.random.default_rng(args.seed)
    fact = pd.DataFrame({
        "id": rng.integers(0, 2 * n_dim, n_fact, dtype=np.int64),
        "val": rng.uniform(-1.0, 1.0, n_fact),
        "small": rng.integers(-100, 100, n_fact, dtype=np.int32)})
    dim_keys = rng.permutation(2 * n_dim)[:n_dim].astype(np.int64)
    dim = pd.DataFrame({
        "id": dim_keys, "tag": np.char.add("t", (dim_keys % 7).astype(str))})
    say(seed=args.seed, fact_rows=n_fact, dim_rows=n_dim)

    session = TpuSession({
        "spark.rapids.shuffle.mode": "ICI",
        # a broadcast join would skip the collective under test
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.tpu.mesh.shape": "shuffle=4"})
    q = (session.from_arrow(pa.Table.from_pandas(fact, preserve_index=False))
         .filter(col("val") > -0.5)
         .join(session.from_arrow(
             pa.Table.from_pandas(dim, preserve_index=False)), on="id")
         .group_by("tag")
         .agg(n=Count(col("val")), s=Sum(col("small")),
              mx=Max(col("id")), mn=Min(col("small")))
         .sort("tag"))
    kept = fact[fact["val"] > -0.5].merge(dim, on="id")
    want = (kept.groupby("tag").agg(n=("val", "count"), s=("small", "sum"),
                                    mx=("id", "max"), mn=("small", "min"))
            .reset_index().sort_values("tag"))

    note = {}
    for leg in ("cold", "warm"):
        before = EX.MESH_EXCHANGES
        t0 = time.perf_counter()
        got = q.collect()
        note[leg + "_s"] = time.perf_counter() - t0
        tm = TaskMetrics.get()
        names = plan_names(session.last_plan)
        check(not any(n.startswith("Cpu") or "FromCpu" in n for n in names),
              f"a plan section ran on the CPU engine: {names}")
        check(EX.MESH_EXCHANGES > before, "no mesh collective ran")
        check(tm.shuffle_bytes_written == 0,
              f"{tm.shuffle_bytes_written} bytes went through the host "
              "shuffle")
        check(tm.cpu_fallback_reruns == 0 and tm.compile_fallbacks == 0,
              "CPU rerun or degraded compile")
        check(len(tm.mesh_out_devices) == 4,
              f"exchanged shards sit on devices {tm.mesh_out_devices}")
        g = got.to_pandas()
        check(list(g.columns) == list(want.columns) and all(
            np.array_equal(g[c].to_numpy(), want[c].to_numpy())
            for c in want.columns), "result differs from pandas")
        note.update({leg + "_mesh_exchanges": EX.MESH_EXCHANGES - before,
                     leg + "_ici_bytes": tm.mesh_ici_bytes,
                     leg + "_dispatches": tm.device_dispatches,
                     leg + "_compile_s": tm.compile_ns / 1e9})
    say(query="mesh_exchange_join_agg", plan=names, rows_out=got.num_rows,
        shard_devices=tm.mesh_out_devices, reference_agreed=True,
        **note)


def single_chip(args, jax) -> None:
    import benchcorpus
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    from spark_rapids_tpu.plugin import TpuSession

    say(noop_dispatch_s=noop_dispatch_seconds(jax))

    rows = args.rows or FACT_ROWS
    sf10 = benchcorpus.SF10_ROWS["store_sales"]
    if rows < sf10:
        say(fact_rows=rows, sf10_fact_rows=sf10, cut="store_sales rows only",
            reason="eight query runs at SF10 rows need ~1,300 s warm on one "
                   "v5e, and at four row groups the scan's 4-chunk decode "
                   "program alone compiles for 678 s; the script has 1,200 s "
                   "cold (PERF.md, PR 23)")
    t0 = time.perf_counter()
    tables = benchcorpus.write_star(
        os.path.join(args.data_dir, f"star_s{args.seed}_r{rows}"),
        args.seed, rows)
    say(data_s=time.perf_counter() - t0, seed=args.seed,
        tables={k: {"rows": v["rows"], "bytes": v["bytes"]}
                for k, v in tables.items()})
    paths = {k: v["path"] for k, v in tables.items()}

    session = TpuSession()
    session.initialize_device()
    dev = jax.devices()[0]
    check(DeviceManager.device == dev, "DeviceManager bound another device")
    if dev.platform == "tpu":
        limit = dev.memory_stats()["bytes_limit"]
        check(DeviceManager.hbm_total == limit,
              f"hbm_total {DeviceManager.hbm_total} is not the device's "
              f"bytes_limit {limit}")
    say(hbm_total=DeviceManager.hbm_total,
        budget_bytes=DeviceManager.budget_bytes,
        hbm_from="memory_stats" if dev.platform == "tpu" else "cpu default")
    # customer is written (SF10 key domain of ss_customer_sk) but no query
    # scans it, and a decode program compiled for it alone buys nothing
    scan_on_device(session, {k: p for k, p in paths.items()
                             if k != "customer"}, dev)

    t0 = time.perf_counter()
    want = references(paths)
    say(reference_s=time.perf_counter() - t0, reference="pyarrow")
    for name, df in benchcorpus.star_queries(session, paths).items():
        run_query(session, name, df, want[name])

    pallas_kernels(jax, min(PALLAS_ROWS, args.rows or PALLAS_ROWS), args.seed)
    from spark_rapids_tpu.compile import CompileService
    by_op = CompileService.get().stats.per_op()
    if rows > benchcorpus.ROW_GROUP:
        check("io.parquet.fused_multi_decode" in by_op,
              "the fact scan did not take the multi-row-group decode path")
    say(compile_by_op={
        op: {"compiles": d["compiles"], "compile_s": d["compile_ns"] / 1e9,
             "fallbacks": d["fallbacks"]}
        for op, d in sorted(by_op.items())})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0,
                    help=f"fact rows (default {FACT_ROWS}; 28800991 is SF10)")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: the mesh exchange phase only")
    ap.add_argument("--data-dir",
                    default=os.path.join(ROOT, "chip_smoke_data"))
    args = ap.parse_args(argv)

    device = None
    failures: list = []
    try:
        build_native()
        import jax
        cache_dir = place_compile_cache()
        cache_warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
        import warnings

        import spark_rapids_tpu  # noqa: F401 — enables x64
        from spark_rapids_tpu.compile.service import CompileServiceWarning
        from spark_rapids_tpu.native import runtime as native
        warnings.simplefilter("error", CompileServiceWarning)
        check(native.available(), "native runtime built but did not load")

        device = device_note(jax)
        if device["platform"] != "tpu":
            failures.append(f"platform is {device['platform']!r}, not 'tpu'")
        import jaxlib
        say(device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
            libtpu=_libtpu_version(), compile_cache_dir=cache_dir,
            compile_cache_warm=cache_warm, native_runtime=True)
        want_count = 4 if args.multichip else 1
        if device["platform"] == "tpu":
            check(device["count"] == want_count,
                  f"{device['count']} devices, this mode needs {want_count}")
        if args.multichip:
            multi_chip(args, jax)
        else:
            single_chip(args, jax)
    except Exception as e:  # noqa: BLE001 — reported, then exit != 0
        import traceback
        traceback.print_exc()
        failures.append(f"{type(e).__name__}: {e}")
    if failures:
        say(failures=failures)
    print(json.dumps({"ok": not failures, "device": device}), flush=True)
    return 1 if failures else 0


def _libtpu_version():
    from importlib import metadata
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
