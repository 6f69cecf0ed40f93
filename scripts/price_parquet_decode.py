"""Standalone prices of one parquet run-table expansion on the attached
device (PERF.md, PR 38): dictionary indices of widths 2, 17 and 20 and def
levels (width 1), each into 1,048,576 slots (one row group of the cells),
three ways: the parent commit's kernel (kept below word for word for this
comparison only: four single 32-bit gathers by run and 1-4 single-byte
gathers of the packed stream a slot), this commit's (`_expand_rle_u32` /
`_expand_def_levels`: the run's three words by one stacked gather, two
packed words by one more) and the telescoped alternative (each run word a
scatter of its difference from the run before onto the run's first slot and
a prefix sum, then the same two-word gather; PERF.md fault 13). Run tables
are synthetic in the cells' shapes (index streams: bit-packed runs of 504
values with a repeated run of 8-40 values in ten; def levels of a column 4.5%
null: 65,536 runs), made from `--seed`, and every kernel's values are
checked against the values encoded before it is timed. Each variant is its
own jitted program; prints one JSON line each (`ms` the median of 8 runs
after the compile) and writes them to `chiprun_out/price_parquet_decode.jsonl`.

    python scripts/price_parquet_decode.py [--slots 1048576] [--only PREFIX]
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
from spark_rapids_tpu.io import parquet_device as P  # noqa: E402
from spark_rapids_tpu.ops.rowops import (ahead, gather_rows,  # noqa: E402
                                         prefix_sum)

OUT = []


def price(name, fn, *args, runs=8, check=None):
    t0 = time.perf_counter()
    jitted = jax.jit(fn)
    first = jax.block_until_ready(jitted(*args))
    compile_s = time.perf_counter() - t0
    if check is not None:
        check(first)
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


# -- the parent commit's kernels (5e527ef `io/parquet_device.py`), for the
# -- price comparison only

def parent_run_of_slot(counts, cap: int):
    from jax import lax
    ends = jnp.cumsum(counts.astype(jnp.int32))
    marks = jnp.zeros(cap, jnp.int32).at[ends].add(
        1, mode="drop", indices_are_sorted=True)
    run = jnp.clip(prefix_sum(marks), 0, counts.shape[0] - 1)
    return lax.optimization_barrier((run, ends))


def parent_slot_in_packed(run, ends, bitoffs, packed, cap: int, bw: int):
    narrow = packed.shape[0] * 8 + cap * bw < 2 ** 31
    idt = jnp.int32 if narrow else jnp.int64
    j = jnp.arange(cap, dtype=jnp.int32)
    base = jnp.where(run > 0, ends[jnp.maximum(run - 1, 0)], 0)
    bitpos = bitoffs.astype(idt)[run] + (j - base).astype(idt) * bw
    return j, bitpos


def parent_def_levels(kinds, counts, values, bitoffs, packed, cap: int):
    run, ends = parent_run_of_slot(counts, cap)
    j, bitpos = parent_slot_in_packed(run, ends, bitoffs, packed, cap, 1)
    byte = packed[jnp.clip(bitpos // 8, 0, packed.shape[0] - 1)]
    bit = (byte >> (bitpos % 8).astype(jnp.uint8)) & 1
    lvl = jnp.where(kinds[run] == 1, bit, values[run])
    return (lvl == 1) & (j < ends[-1])


def parent_rle_u32(kinds, counts, values, bitoffs, packed, cap: int,
                   bw: int):
    run, ends = parent_run_of_slot(counts, cap)
    j, bitpos = parent_slot_in_packed(run, ends, bitoffs, packed, cap, bw)
    b0 = bitpos // 8
    window = jnp.zeros(cap, jnp.uint64)
    for k in range((bw + 7) // 8 + 1):
        byte = packed[jnp.clip(b0 + k, 0, packed.shape[0] - 1)]
        window = window | (byte.astype(jnp.uint64) << jnp.uint64(8 * k))
    sh = (bitpos % 8).astype(jnp.uint64)
    pv = ((window >> sh) & jnp.uint64((1 << bw) - 1)).astype(jnp.uint32)
    out = jnp.where(kinds[run] == 1, pv, values[run])
    return jnp.where(j < ends[-1], out, 0)


def parent_pad_runs(runs):
    kinds, counts, values, bitoffs, packed = runs
    rb = P._pow2(max(len(kinds), 1))
    pad = rb - len(kinds)
    if pad:
        kinds = np.pad(kinds, (0, pad))
        counts = np.pad(counts, (0, pad))
        values = np.pad(values, (0, pad))
        bitoffs = np.pad(bitoffs, (0, pad))
    pb = P._pow2(max(len(packed), 1))
    if pb > len(packed):
        packed = np.pad(packed, (0, pb - len(packed)))
    return kinds, counts, values, bitoffs, packed


# -- the telescoped alternative: no run index, the run words by a scatter of
# -- differences and a prefix sum each

def telescoped(ends, table, words, cap: int):
    u32 = jnp.uint32
    first = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    diff = table - jnp.concatenate([jnp.zeros((3, 1), u32), table[:, :-1]],
                                   axis=1)
    rows = []
    for k in range(3):
        d = jax.lax.bitcast_convert_type(diff[k], jnp.int32)
        at = jnp.zeros(cap, jnp.int32).at[first].add(
            d, mode="drop", indices_are_sorted=True)
        rows.append(jax.lax.bitcast_convert_type(prefix_sum(at), u32))
    base, value, width = rows
    bitpos = base + jnp.arange(cap, dtype=u32) * width
    r = bitpos & u32(31)
    lo, hi = gather_rows([words, ahead(words, 1)],
                         (bitpos >> u32(5)).astype(jnp.int32))
    bits = (lo >> r) | jnp.where(r > 0, hi << ((u32(32) - r) & u32(31)),
                                 u32(0))
    mask = jnp.where(width >= 32, ~u32(0),
                     (u32(1) << (width & u32(31))) - u32(1))
    out = (bits & mask) | value
    return jnp.where(jnp.arange(cap, dtype=jnp.int32) < ends[-1], out, 0)


# -- synthetic run tables of the cells' shapes

def run_table(rng, slots: int, width: int, def_levels: bool):
    """(`_rle_runs`' five arrays, the values they stand for): an index
    stream of bit-packed runs of 504 values (63 groups, the writer's
    longest) with a repeated run of 8-40 values in ten, or a def-level
    stream 4.5% null whose nulls break it into 65,536 runs per 1M slots."""
    kinds, counts, values, bitoffs, packed, want = [], [], [], [], [], []
    nbits = 0
    top = 1 << width
    out = 0
    while out < slots:
        if def_levels:
            rep = rng.random() < 0.5
            n = int(rng.integers(8, 24)) if rep else 8
        else:
            rep = rng.random() < 0.1
            n = int(rng.integers(8, 41)) if rep else 504
        n = min(n, slots - out)
        if rep:
            v = 1 if def_levels else int(rng.integers(0, top))
            kinds.append(0)
            values.append(v)
            bitoffs.append(0)
            want.append(np.full(n, v, np.uint64))
        else:
            vals = (rng.random(n) >= 0.045).astype(np.uint64) if def_levels \
                else rng.integers(0, top, n, dtype=np.uint64)
            groups = -(-n // 8)
            padded = np.zeros(groups * 8, np.uint64)
            padded[:n] = vals
            bits = ((padded[:, None] >> np.arange(width, dtype=np.uint64))
                    & np.uint64(1)).astype(np.uint8).reshape(-1)
            kinds.append(1)
            values.append(0)
            bitoffs.append(nbits)
            packed.append(np.packbits(bits, bitorder="little"))
            nbits += groups * width * 8
            want.append(vals)
        counts.append(n)
        out += n
    runs = (np.array(kinds, np.uint8), np.array(counts, np.int64),
            np.array(values, np.uint32), np.array(bitoffs, np.int64),
            np.concatenate(packed) if packed else np.zeros(1, np.uint8))
    return runs, np.concatenate(want).astype(np.uint32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    cap = args.slots
    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "kind": dev.device_kind,
                      "slots": cap}), flush=True)

    for label, width in (("def_levels", 1), ("index_w2", 2),
                         ("index_w17", 17), ("index_w20", 20)):
        if not label.startswith(args.only):
            continue
        is_def = width == 1
        runs, want = run_table(rng, cap, width, is_def)
        new = jax.device_put(list(P._run_words([(runs, width, cap)])))
        old = jax.device_put(list(parent_pad_runs(runs)))
        meta = {"runs": int(runs[0].shape[0]),
                "packed_bytes": int(runs[4].shape[0])}
        print(json.dumps({"table": label, **meta}), flush=True)
        expect = (want == 1) if is_def else want

        def same(got, expect=expect, label=label):
            assert np.array_equal(np.asarray(got)[:cap], expect), label
        if is_def:
            price(f"{label}.parent_single_gathers",
                  lambda *a: parent_def_levels(*a, cap), *old, check=same)
            price(f"{label}.stacked_gathers",
                  lambda *a: P._expand_def_levels(*a, cap), *new,
                  check=same)
            price(f"{label}.telescoped",
                  lambda *a: telescoped(*a, cap) == 1, *new, check=same)
        else:
            price(f"{label}.parent_single_gathers",
                  lambda *a: parent_rle_u32(*a, cap, width), *old,
                  check=same)
            price(f"{label}.stacked_gathers",
                  lambda *a: P._expand_rle_u32(*a, cap), *new, check=same)
            price(f"{label}.telescoped",
                  lambda *a: telescoped(*a, cap), *new, check=same)

    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "price_parquet_decode.jsonl"),
              "w") as f:
        for line in OUT:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
