"""Standalone prices of the hash join's position finding on the attached
device (PERF.md price list): each probe row's candidate range start, and each
output slot's probe row, found by `jnp.searchsorted`'s scan (a `while` loop of
dependent gathers) against a merge and a prefix sum (`exec/joins._merge_rank`,
`ops/rowops.slot_runs`), at the shapes of the star cells' joins, then the two
join programs whole, each way, at the cells' shapes. Each variant is its own
jitted program; prints one JSON line each (`ms` the median of 8 runs after
the compile, `compile_s` the first call's trace, compile and run) and writes
them to `chiprun_out/price_join_search.jsonl`. A merge that disagrees with
the scan on the device raises.

    python scripts/price_join_search.py [--only PREFIX[,PREFIX...]]
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
import pyarrow as pa  # noqa: E402

import spark_rapids_tpu  # noqa: E402,F401  (x64 on)
from spark_rapids_tpu.columnar import batch_from_arrow  # noqa: E402
from spark_rapids_tpu.exec import joins  # noqa: E402
from spark_rapids_tpu.ops.rowops import (prefix_sum, slot_runs,  # noqa: E402
                                         stable_lexsort)

OUT = []
INT32_MAX = np.iinfo(np.int32).max


def price(name, fn, *args, runs=8, check=None):
    t0 = time.perf_counter()
    jitted = jax.jit(fn)
    got = jax.block_until_ready(jitted(*args))
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
    if check is not None:
        check(got)
    return got


# -- the probe's range start: searchsorted(keys_sorted, queries, "left")

def rank_scan(q, k):
    """What `_probe_counts` ran before the merge."""
    return jnp.searchsorted(k, q, side="left").astype(jnp.int32)


def rank_merge_scatter(q, k):
    """`joins._merge_rank` with its ranks put back in query order by one
    scatter (the key entries' dropped) in place of its second sort."""
    nq = q.shape[0]
    src = stable_lexsort(jnp, [jnp.concatenate([q, k])])
    is_key = (src >= nq).astype(np.int32)
    return jnp.zeros(nq, jnp.int32).at[src].set(
        prefix_sum(is_key) - is_key, mode="drop", unique_indices=True)


def probe_inputs(rng, nq, nk):
    """Sorted build hashes with a tenth of the capacity exiled under
    INT32_MAX, as `_probe_counts` lays them out, and probe hashes of which
    three in ten are build hashes (the ties) and the rest anything."""
    live = nk - nk // 10
    k = np.sort(rng.integers(-2 ** 31, 2 ** 31, live, dtype=np.int64))
    k = np.concatenate([k, np.full(nk - live, INT32_MAX)]).astype(np.int32)
    q = rng.integers(-2 ** 31, 2 ** 31, nq, dtype=np.int64).astype(np.int32)
    hit = rng.random(nq) < 0.3
    q[hit] = k[rng.integers(0, live, int(hit.sum()))]
    return jnp.asarray(q), jnp.asarray(k)


# -- the expand's slot map: searchsorted(offsets, arange(cap), "right")

def slots_scan(counts, cap):
    """What `_expand_join` ran before: a flat cumsum (int64 under x64) and
    the scan search, clipped to the last row."""
    offsets = jnp.cumsum(counts)
    pi = jnp.searchsorted(offsets, jnp.arange(cap, dtype=jnp.int32),
                          side="right").astype(jnp.int32)
    return jnp.clip(pi, 0, counts.shape[0] - 1)


def slots_runs(counts, cap):
    return slot_runs(prefix_sum(counts), cap)


# -- the two join programs whole, the tree's and with the scans put back

def scan_probe(patch):
    patch.setattr(joins, "_merge_rank", rank_scan)


def scan_expand(patch):
    patch.setattr(joins, "prefix_sum", jnp.cumsum)
    patch.setattr(joins, "slot_runs", lambda offsets, cap: jnp.clip(
        jnp.searchsorted(offsets, jnp.arange(cap, dtype=jnp.int32),
                         side="right").astype(jnp.int32),
        0, offsets.shape[0] - 1))


class _Patch:
    """Module attributes set for one trace and put back after it."""

    def __init__(self):
        self.saved = []

    def setattr(self, mod, name, value):
        self.saved.append((mod, name, getattr(mod, name)))
        setattr(mod, name, value)

    def undo(self):
        for mod, name, value in reversed(self.saved):
            setattr(mod, name, value)
        self.saved.clear()


def _batches(rng, nq, nk):
    """A probe batch of nq rows (the key and two int64 columns) and a build
    batch of nk rows (unique keys and one column); three probe rows in ten
    match one build row."""
    bkeys = rng.permutation(nk).astype(np.int64)
    pkeys = rng.integers(10 ** 9, 2 * 10 ** 9, nq)
    hit = rng.random(nq) < 0.3
    pkeys[hit] = rng.integers(0, nk, int(hit.sum()))
    probe = batch_from_arrow(pa.table({
        "k": pkeys, "a": rng.integers(0, 10 ** 6, nq),
        "b": rng.integers(0, 10 ** 6, nq)}))
    build = batch_from_arrow(pa.table({
        "k": bkeys, "c": rng.integers(0, 10 ** 6, nk)}))
    return probe, build


def _both_ways(name, setup, label, fn, *args):
    """Price `fn` with the scan search put back and as the tree has it
    (`label`), a new function each time: jax.jit keeps one trace a
    function, and each variant must be traced under its own patch. The two
    must agree."""
    got = {}
    for variant, patch_in in (("scan", setup), (label, None)):
        patch = _Patch()
        if patch_in is not None:
            patch_in(patch)
        try:
            got[variant] = price(f"{name}.{variant}",
                                 lambda *a: fn(*a), *args)
        finally:
            patch.undo()
    a, b = (jax.tree_util.tree_leaves(g) for g in got.values())
    if not all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b)):
        raise AssertionError(f"{name}: the two ways differ")
    return got[label]


def price_programs(rng, want):
    """The probe program at the star cells' four probe shapes (the fact
    batch against `date_dim`'s 8,192-row and `item`'s 32,768-row build
    sides; the first join's output against the other dimension's 128-row
    side, the least capacity a batch has), and the expand at `star.q98`'s
    first join, which runs at 1,048,576 slots."""
    m = 1024 * 1024
    for nq, nk in ((2 * m, 8192), (2 * m, 32768), (m // 4, 128), (m, 128)):
        name = f"program.probe_counts.q{nq}.k{nk}"
        if not want(name):
            continue
        probe, build = _batches(rng, nq, nk)
        _both_ways(name, scan_probe, "merge",
                   lambda p, b: joins._probe_counts.fn(p, b, (0,), (0,)),
                   probe, build)
    name = "program.expand.q2097152.k32768"
    if not want(name):
        return
    probe, build = _batches(rng, 2 * m, 32768)
    phase1 = jax.jit(lambda p, b: joins._probe_counts.fn(
        p, b, (0,), (0,)))(probe, build)
    total = int(jnp.sum(phase1[0]))
    out_cap = joins.row_bucket(total, op="join")
    print(json.dumps({"expand_total": total, "out_cap": out_cap}), flush=True)
    _both_ways(name, scan_expand, "slot_runs",
               lambda p, b, *arrays: joins._expand_join.fn(
                   p, b, *arrays, (0,), (0,), out_cap, "inner", None,
                   False)[:2],
               probe, build, *phase1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    a = ap.parse_args()
    rng = np.random.default_rng(40)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}),
          flush=True)

    def want(name):
        return any(name.startswith(p) for p in a.only.split(","))

    # A. the probe's range start: the cells' probes against a 131,072-row
    # build and the extremes of the build side, both ways back to probe
    # order; then where the scan and the merge cross: the scan against
    # builds between the extremes (the merge's price barely moves with the
    # build while it is small beside the probe), and small probe batches
    # against a large build
    m = 1024 * 1024
    every = (("scan", rank_scan), ("merge_sort", joins._merge_rank),
             ("merge_scatter", rank_merge_scatter))
    shapes = [((2 * m, 128 * 1024), every), ((m, 128 * 1024), every),
              ((m // 4, 128 * 1024), every), ((2 * m, 16), every),
              ((2 * m, m), every)]
    shapes += [((2 * m, nk), every[:1])
               for nk in (64, 256, 1024, 4096, 16384, 65536)]
    shapes += [((nq, 128 * 1024), every[:2]) for nq in (8192, 65536)]
    for (nq, nk), variants in shapes:
        q, k = probe_inputs(rng, nq, nk)
        want_lo = np.searchsorted(np.asarray(k), np.asarray(q), side="left")

        def same(got, label=f"rank.q{nq}.k{nk}"):
            if not np.array_equal(np.asarray(got), want_lo):
                raise AssertionError(f"{label} differs from numpy")
        for vname, fn in variants:
            name = f"rank.q{nq}.k{nk}.{vname}"
            if want(name):
                price(name, fn, q, k, check=same)

    # B. the expand's slot map out of 2,097,152 probe rows: q3's second-join
    # sized output and q98's first join (three rows in ten match one row)
    for cap, p in ((m // 4, 0.08), (m, 0.3)):
        counts = jnp.asarray((rng.random(2 * m) < p).astype(np.int32))
        want_pi = np.asarray(jax.jit(slots_scan, static_argnums=1)(counts,
                                                                   cap))
        for vname, fn in (("scan", slots_scan), ("slot_runs", slots_runs)):
            name = f"slots.cap{cap}.{vname}"
            if want(name):
                price(name, lambda c, fn=fn, cap=cap: fn(c, cap), counts,
                      check=lambda got: np.testing.assert_array_equal(
                          np.asarray(got), want_pi))

    # C. the two join programs whole
    price_programs(rng, want)

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/price_join_search.jsonl", "w") as f:
        for line in OUT:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
