"""Standalone prices of row gathers on the attached device (PERF.md price
list, PR 36): single arrays, stacked word matrices, and `gather_vecs` on
batches shaped like the cells' (TPC-H Q1's filter and sort at 2,097,152
rows, TPC-DS q98's join expand at 1,048,576 slots out of a 32,768-row build
side). Each variant is its own jitted program; prints one JSON line each
(`ms` the median of 8 runs after the compile) and writes them to
`chiprun_out/price_packed_gather.jsonl`.

    python scripts/price_packed_gather.py [--rows 2097152] [--only PREFIX]
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import spark_rapids_tpu  # noqa: E402,F401  (x64 on)
from spark_rapids_tpu import types as T  # noqa: E402
from spark_rapids_tpu.expr.base import Vec  # noqa: E402
from spark_rapids_tpu.ops import rowops  # noqa: E402

OUT = []


def price(name, fn, *args, runs=8):
    t0 = time.perf_counter()
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*args))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*args))
        times.append(time.perf_counter() - t0)
    line = {"name": name, "ms": round(statistics.median(times) * 1e3, 3),
            "min_ms": round(min(times) * 1e3, 3),
            "compile_s": round(compile_s, 2)}
    OUT.append(line)
    print(json.dumps(line), flush=True)


def per_array(vecs, idx):
    return [v.gather(jnp, idx) for v in vecs]


def packed(vecs, idx):
    return rowops.gather_vecs(jnp, vecs, idx)


def packed_at(width):
    def fn(vecs, idx):
        was = rowops.PACK_MAX_ROW_BYTES
        rowops.PACK_MAX_ROW_BYTES = width
        try:
            return rowops.gather_vecs(jnp, vecs, idx)
        finally:
            rowops.PACK_MAX_ROW_BYTES = was
    return fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2_097_152)
    ap.add_argument("--only", default="")
    a = ap.parse_args()
    n = a.rows
    rng = np.random.default_rng(36)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind,
                      "rows": n}), flush=True)

    def want(name):
        return name.startswith(a.only)

    perm = jnp.asarray(rng.permutation(n).astype(np.int32))
    keep = rng.random(n) < 0.986
    comp = jnp.asarray(np.argsort(~keep, kind="stable").astype(np.int32))
    orders = {"perm": perm, "comp": comp}

    def col(dtype, *tail):
        if np.dtype(dtype) == np.bool_:
            return jnp.asarray(rng.random((n,) + tail) < 0.9)
        if np.dtype(dtype).kind == "f":
            return jnp.asarray(rng.standard_normal((n,) + tail).astype(dtype))
        info = np.iinfo(dtype)
        return jnp.asarray(rng.integers(info.min, info.max, (n,) + tail,
                                        dtype=dtype))

    # A. one array, one gather
    singles = {"bool": col(np.bool_), "i8": col(np.int8), "i32": col(np.int32),
               "i64": col(np.int64), "f64": col(np.float64),
               "i64x2": col(np.int64, 2), "u8x8": col(np.uint8, 8),
               "u8x16": col(np.uint8, 16)}
    for oname, idx in orders.items():
        for k, arr in singles.items():
            name = f"single.{k}.{oname}"
            if want(name):
                price(name, lambda x, i: x[i], arr, idx)

    # B. stacked (K, n) word matrices along n; C. int64 rows; D. rows first
    for k in (1, 2, 4, 8, 16, 32):
        name = f"stack.u32.K{k}.perm"
        if want(name):
            m = jnp.asarray(rng.integers(0, 2 ** 32, (k, n), dtype=np.uint32))
            price(name, lambda x, i: x[:, i], m, perm)
    m8 = jnp.asarray(rng.integers(0, 2 ** 32, (8, n), dtype=np.uint32))
    if want("stack.u32.K8.comp"):
        price("stack.u32.K8.comp", lambda x, i: x[:, i], m8, comp)
    if want("stack.u32.2xK8.perm"):
        price("stack.u32.2xK8.perm", lambda x, y, i: (x[:, i], y[:, i]),
              m8, m8 + np.uint32(1), perm)
    if want("stack.u32.K8.sorted_unique_promise.perm"):
        price("stack.u32.K8.sorted_unique_promise.perm",
              lambda x, i: x.at[:, i].get(mode="promise_in_bounds",
                                          unique_indices=True), m8, perm)
    if want("stack.i64.K8.perm"):
        price("stack.i64.K8.perm", lambda x, i: x[:, i], col(np.int64, 8).T,
              perm)
    for k in (8, 16):
        name = f"rows.u32.nx{k}.perm"
        if want(name):
            m = jnp.asarray(rng.integers(0, 2 ** 32, (n, k), dtype=np.uint32))
            price(name, lambda x, i: x[i], m, perm)

    # E. whole batches through one jitted program
    def vec(dt, data, lengths=None):
        return Vec(dt, data, col(np.bool_), lengths)

    def string(width, rows=None):
        return vec(T.STRING, col(np.uint8, width),
                   jnp.asarray(rng.integers(0, width, n, dtype=np.int32)))

    dec64 = T.DecimalType(12, 2)
    dec128 = T.DecimalType(38, 6)
    q1_filter = [vec(dec64, col(np.int64)) for _ in range(4)] + \
        [vec(T.DATE, col(np.int32)), string(8), string(8)]
    q1_sort = [string(8), string(8)] + \
        [vec(dec128, col(np.int64, 2)) for _ in range(4)] + \
        [vec(T.DecimalType(16, 6), col(np.int64)) for _ in range(3)] + \
        [vec(T.LONG, col(np.int64))]
    for bname, batch in (("q1_filter", q1_filter), ("q1_sort", q1_sort)):
        for oname, idx in orders.items():
            for fname, fn in (("per_array", per_array), ("packed", packed)):
                name = f"batch.{bname}.{fname}.{oname}"
                if want(name):
                    price(name, fn, batch, idx)
            # the two ways agree on this device, leaf by leaf, bit by bit
            same = jax.jit(lambda vs, i: [
                jnp.array_equal(a, b) for a, b in zip(
                    jax.tree_util.tree_leaves(packed(vs, i)),
                    jax.tree_util.tree_leaves(per_array(vs, i)))])(batch, idx)
            line = {"name": f"batch.{bname}.{oname}.packed_equals_per_array",
                    "ok": all(bool(x) for x in same), "leaves": len(same)}
            OUT.append(line)
            print(json.dumps(line), flush=True)
    # F. a narrow string alone, cut into words or gathered whole
    for width in (8, 16, 32):
        s = [string(width)]
        for fname, fn in (("whole", packed_at(0)), ("words", packed_at(64))):
            name = f"string.w{width}.{fname}.perm"
            if want(name):
                price(name, fn, s, perm)

    # G. the join's shapes: 1,048,576 slots out of a 32,768-row build side
    # (random rows, repeated) and out of the 2,097,152-row probe side
    # (non-decreasing), then the compaction of the 1,048,576-slot output
    slots, build = n // 2, n // 64
    bi = jnp.asarray(rng.integers(0, build, slots, dtype=np.int32))
    pi = jnp.asarray(np.sort(rng.integers(0, n, slots, dtype=np.int32)))
    keep = rng.random(slots) < 0.6
    comp_s = jnp.asarray(np.argsort(~keep, kind="stable").astype(np.int32))
    for width in (16, 64, 256):
        name = f"join.u8x{width}"
        b = jnp.asarray(rng.integers(0, 255, (build, width), dtype=np.uint8))
        o = jnp.asarray(rng.integers(0, 255, (slots, width), dtype=np.uint8))
        bs = [Vec(T.STRING, b, jnp.ones(build, bool),
                  jnp.full(build, width, np.int32))]
        os_ = [Vec(T.STRING, o, jnp.ones(slots, bool),
                   jnp.full(slots, width, np.int32))]
        for fname, fn in (("whole", packed_at(0)), ("words", packed_at(256))):
            if want(f"{name}.build.{fname}"):
                price(f"{name}.build.{fname}", fn, bs, bi)
            if want(f"{name}.compact.{fname}"):
                price(f"{name}.compact.{fname}", fn, os_, comp_s)

    def at(rows, v):
        return jax.tree_util.tree_map(lambda x: x[:rows], v)

    item = [at(build, v) for v in (
        vec(T.LONG, col(np.int64)), string(16), string(256), string(16),
        string(16), vec(T.DecimalType(7, 2), col(np.int64)))]
    fact = [vec(T.LONG, col(np.int64)), vec(T.LONG, col(np.int64)),
            vec(T.DecimalType(7, 2), col(np.int64))]
    joined = [at(slots, v) for v in fact] + [
        at(slots, v) for v in (
            vec(T.LONG, col(np.int64)), string(16), string(256), string(16),
            string(16), vec(T.DecimalType(7, 2), col(np.int64)))]
    for bname, batch, idx in (("join.item_side", item, bi),
                              ("join.fact_side", fact, pi),
                              ("join.compact", joined, comp_s)):
        for fname, fn in (("per_array", per_array), ("packed", packed),
                          ("packed_all", packed_at(256))):
            name = f"batch.{bname}.{fname}"
            if want(name):
                price(name, fn, batch, idx)

    # H. float32 bit patterns through both ways: NaN payloads, -0.0, subnormals
    # (it is gathered alone like float64 since call 1 read them unequal; a
    # `packed` reading is then the lone gather's own)
    bits = np.array([0x7fc00000, 0x7fc00001, 0xffc12345, 0x7f800001,
                     0x80000000, 0x00000001, 0x7f800000, 0xff800000],
                    dtype=np.uint32)
    f = np.tile(bits, 16).view(np.float32)
    fv = [Vec(T.FLOAT, jnp.asarray(f), jnp.ones(f.shape[0], bool))]
    take = jnp.asarray(rng.integers(0, f.shape[0], 256, dtype=np.int32))
    want = f.view(np.uint32)[np.asarray(take)]
    for fname, fn in (("packed", packed), ("per_array", per_array)):
        got = jax.jit(lambda v, i: jax.lax.bitcast_convert_type(
            fn(v, i)[0].data, np.uint32))(fv, take)
        back = np.asarray(got)
        bad = sorted({(hex(int(w)), hex(int(b)))
                      for w, b in zip(want, back) if w != b})
        line = {"name": f"float32_bits_survive.{fname}", "ok": not bad,
                "differ": bad}
        OUT.append(line)
        print(json.dumps(line), flush=True)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/price_packed_gather.jsonl", "w") as fh:
        for line in OUT:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
