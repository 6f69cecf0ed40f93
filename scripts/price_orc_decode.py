"""Standalone prices of the ORC decode kernels on the attached device
(PERF.md, PR 37): the varint fold at 2 / 4 / 8 M stream bytes, the RLEv2 run
expansion at 4,209 / 270,139 / 387,064 runs into 2,097,152 slots, the
bit-window unpack alone, and the dictionary rows, each beside the kernel it
replaced (the parent commit's, kept below word for word for this comparison
only: `searchsorted` per slot, eight single-byte gathers a window, a flat
`cumsum`, a `cummax` and a `segment_sum` over the byte stream). Operands are
synthetic streams of the cell's shapes (TPC-H `lineitem` as ORC:
`benchmark/configs/tpch-sf10-lineitem-orc.json`), made from `--seed`, and
every new kernel's values are checked against numpy's before it is timed.
Each variant is its own jitted program; prints one JSON line each (`ms` the
median of 8 runs after the compile) and writes them to
`chiprun_out/price_orc_decode.jsonl`.

    python scripts/price_orc_decode.py [--slots 2097152] [--only PREFIX]
        [--parent varint_2m,rle_4209]   # which parent kernels to price too
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
from spark_rapids_tpu.io import orc_device as O  # noqa: E402
from spark_rapids_tpu.ops import rowops as R  # noqa: E402

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


# -- the parent commit's kernels (12c2649 `io/orc_device.py`), for the price
# -- comparison only

def parent_varint(stream, cap: int):
    b = stream.astype(jnp.uint64)
    term = stream < 128
    n = stream.shape[0]
    i = jnp.arange(n, dtype=jnp.int64)
    vid = jnp.cumsum(term.astype(jnp.int64)) - term.astype(jnp.int64)
    is_start = jnp.concatenate([jnp.ones(1, bool), term[:-1]])
    seg_start = jax.lax.cummax(jnp.where(is_start, i, -1))
    within = (i - seg_start).astype(jnp.uint64)
    contrib = (b & jnp.uint64(0x7F)) << (jnp.uint64(7) *
                                         jnp.minimum(within, jnp.uint64(9)))
    u = jax.ops.segment_sum(contrib, vid, num_segments=cap)
    return ((u >> jnp.uint64(1)) ^
            (jnp.uint64(0) - (u & jnp.uint64(1)))).astype(jnp.int64)


def parent_rlev2(kinds, counts, base, step, offs, width, packed, aux,
                 cap: int, signed: bool):
    ends = jnp.cumsum(counts)
    j = jnp.arange(cap, dtype=jnp.int64)
    run = jnp.clip(jnp.searchsorted(ends, j, side="right"),
                   0, counts.shape[0] - 1)
    within = j - (ends[run] - counts[run])
    va = base[run] + within * step[run]
    vl = aux[jnp.clip(offs[run] + within, 0, aux.shape[0] - 1)]
    W = width[run].astype(jnp.uint64)
    bitpos = offs[run] + within * width[run].astype(jnp.int64)
    b0 = bitpos // 8
    window = jnp.zeros(cap, jnp.uint64)
    for k in range(8):
        byte = packed[jnp.clip(b0 + k, 0, packed.shape[0] - 1)]
        window = window | (byte.astype(jnp.uint64)
                           << jnp.uint64(8 * (7 - k)))
    sh = (bitpos % 8).astype(jnp.uint64)
    shift = jnp.where(W >= 64, jnp.uint64(0), jnp.uint64(64) - sh - W)
    pv = window >> shift
    mask = jnp.where(W >= 64, ~jnp.uint64(0),
                     (jnp.uint64(1) << jnp.minimum(W, jnp.uint64(63)))
                     - jnp.uint64(1))
    pv = pv & mask
    if signed:
        pv = (pv >> jnp.uint64(1)) ^ (jnp.uint64(0) - (pv & jnp.uint64(1)))
    pvs = jax.lax.bitcast_convert_type(pv, jnp.int64)
    v = jnp.where(kinds[run] == 2, pvs,
                  jnp.where(kinds[run] == 3, vl, va))
    return jnp.where(j < ends[-1], v, 0)


# -- synthetic streams of the cell's shapes

def varint_stream(rng, values: int, nbytes: int):
    """`values` zigzag varints in about `nbytes` bytes: lengths 1-4 mixed
    as `l_extendedprice`'s are (3.86 bytes a value), or all 1 or 2."""
    per = nbytes / values
    if per <= 1:
        u = rng.integers(0, 128, values)
    elif per <= 2:
        u = rng.integers(128, 1 << 14, values)
    else:
        three = rng.random(values) < (4 - per)
        u = np.where(three, rng.integers(1 << 14, 1 << 21, values),
                     rng.integers(1 << 21, 1 << 28, values))
    u = u.astype(np.uint64)
    nb = np.maximum((np.floor(np.log2(np.maximum(u, 1))).astype(int)) // 7
                    + 1, 1)
    cols = [(u >> np.uint64(7 * k)) & np.uint64(0x7F) for k in range(4)]
    mat = np.stack(cols, axis=1).astype(np.uint8)
    cont = (np.arange(4)[None, :] < (nb - 1)[:, None])
    mat = mat | (cont.astype(np.uint8) << 7)
    keep = np.arange(4)[None, :] < nb[:, None]
    out = mat[keep]
    want = ((u >> np.uint64(1)) ^ (np.uint64(0) - (u & np.uint64(1)))) \
        .view(np.int64)
    return out.tobytes(), want


def run_table(rng, runs: int, slots: int, signed: bool):
    """An RLEv2 stream's run table of `runs` runs over `slots` values in the
    flag columns' mix (half SHORT_REPEAT, a tenth fixed DELTA, the rest
    DIRECT at 2 bits, one run in a thousand decoded by the host), or for
    few runs the date column's (DIRECT at 16 bits, ~500 values a run), and
    the values it stands for."""
    counts = np.full(runs, slots // runs, np.int64)
    counts[:slots % runs] += 1
    few = runs < 10_000
    w = 16 if few else 2
    pick = rng.random(runs)
    kinds = np.where(pick < 0.5, 0, np.where(pick < 0.599, 1,
                                             np.where(pick < 0.6, 3, 2)))
    if few:
        kinds[:] = 2
    base = np.where(kinds <= 1, rng.integers(0, 3, runs), 0)
    step = np.where(kinds == 1, rng.integers(0, 2, runs), 0)
    starts = np.cumsum(counts) - counts
    run_of = np.repeat(np.arange(runs), counts)
    within = np.arange(slots) - starts[run_of]
    rnd = rng.integers(0, 1 << (w - 1), slots)
    small = rng.integers(0, 3, slots)
    is_packed, is_lit = kinds[run_of] == 2, kinds[run_of] == 3
    want = np.where(is_packed, rnd, np.where(
        is_lit, small, base[run_of] + step[run_of] * within))
    u = rnd[is_packed].astype(np.uint64) << np.uint64(1 if signed else 0)
    nbits = counts * (kinds == 2) * w
    rt = O._RunTable()
    rt.kinds, rt.counts = kinds.astype(np.uint8), counts
    rt.base, rt.step = base.astype(np.int64), step.astype(np.int64)
    rt.width = np.where(kinds == 2, w, 0).astype(np.uint8)
    rt.offs = np.where(kinds == 2, np.cumsum(nbits) - nbits, 0)
    rt.packed = bytearray(O._pack_be(u, w))
    rt.total = slots
    for i in np.flatnonzero(kinds == 3):
        rt.offs[i] = rt.aux_len
        rt.aux.append(small[starts[i]:starts[i] + counts[i]]
                      .astype(np.int64))
        rt.aux_len += int(counts[i])
    return rt, want.astype(np.int64)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=2_097_152)
    ap.add_argument("--seed", type=int, default=37)
    ap.add_argument("--only", default="")
    ap.add_argument("--parent", default="varint_2m,rle_4209",
                    help="comma-separated names whose parent kernel is "
                         "priced too; `all` for every one (minutes of "
                         "compile each on the v5e)")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(args.seed)
    cap = args.slots
    with_parent = set(args.parent.split(","))

    def wanted(name):
        return name.startswith(args.only)

    def parent_too(name):
        return "all" in with_parent or name in with_parent

    dev = jax.devices()[0]
    print(json.dumps({"device": str(dev), "kind": dev.device_kind,
                      "slots": cap}), flush=True)

    for label, nbytes in (("varint_2m", cap), ("varint_4m", 2 * cap),
                          ("varint_8m", int(3.86 * cap))):
        if not wanted(label):
            continue
        raw, want = varint_stream(rng, cap, nbytes)
        stream = np.frombuffer(raw, np.uint8)
        words = O._padded(stream, O._bucket(stream.size, 128)).view("<u4")

        def same(got, want=want):
            assert np.array_equal(np.asarray(got), want), label
        price(f"{label}.value_ends", lambda w: O._varint_zigzag(w, cap),
              jax.device_put(words), check=same)
        was, R._GATHER_MIN_COLS = R._GATHER_MIN_COLS, 0
        price(f"{label}.value_ends_short_tables_unpadded",
              lambda w: O._varint_zigzag(w, cap), jax.device_put(words),
              check=same)
        R._GATHER_MIN_COLS = was
        if parent_too(label):
            price(f"{label}.parent_segment_sum",
                  lambda s: parent_varint(s, cap), jax.device_put(stream),
                  check=same)

    for at_2m in (4_209, 270_139, 387_064):
        label = f"rle_{at_2m}"
        if not wanted(label):
            continue
        runs = max(at_2m * cap // 2_097_152, 1)
        signed = at_2m < 10_000
        rt, want = run_table(rng, runs, cap, signed)
        ends, table, words, wide = rt.device_arrays(signed, cap)

        def same(got, want=want):
            assert np.array_equal(np.asarray(got)[:len(want)], want), label
        price(f"{label}.marks_and_stacked_gathers",
              lambda e, t, w: O._expand_rlev2(e, t, w, cap, signed, wide),
              *jax.device_put([ends, table, words]), check=same)
        price(f"{label}.slot_runs_alone",
              lambda e: R.slot_runs(e, cap), jax.device_put(ends))
        if ends.shape[0] < R._GATHER_MIN_COLS:
            # the compiler gathers out of a table of under ~2 MB another
            # way, with 1 GB of temporaries at 2,097,152 slots (sandbox
            # v5e compiler, PR 37): `gather_rows` pads it on the device
            was, R._GATHER_MIN_COLS = R._GATHER_MIN_COLS, 0
            price(f"{label}.short_tables_unpadded",
                  lambda e, t, w: O._expand_rlev2(e, t, w, cap, signed,
                                                  wide),
                  *jax.device_put([ends, table, words]), check=same)
            R._GATHER_MIN_COLS = was
        if parent_too(label):
            arrs = [jnp.asarray(a) for a in rt.arrays()]
            price(f"{label}.parent_searchsorted",
                  lambda *a: parent_rlev2(*a, cap, signed), *arrs,
                  check=same)

    if wanted("window"):
        # the bit-window unpack alone: 16-bit values, every slot packed
        vals = rng.integers(0, 1 << 16, cap).astype(np.uint16)
        words = O._be_words([vals.astype(">u2").view(np.uint8)], 2 * cap)
        bitpos = jnp.arange(cap, dtype=jnp.uint32) * jnp.uint32(16)

        def two_words(w, bp):
            g = R.gather_rows([w, R.ahead(w, 1)], bp >> jnp.uint32(5))
            hi = O._u64(g[1], g[0])
            r = (bp & jnp.uint32(31)).astype(jnp.uint64)
            return (hi >> (jnp.uint64(48) - r)) & jnp.uint64(0xFFFF)

        def eight_bytes(p, bp):
            b0 = (bp >> jnp.uint32(3)).astype(jnp.int32)
            window = jnp.zeros(cap, jnp.uint64)
            for k in range(8):
                byte = p[jnp.clip(b0 + k, 0, p.shape[0] - 1)]
                window = window | (byte.astype(jnp.uint64)
                                   << jnp.uint64(8 * (7 - k)))
            return window >> jnp.uint64(48)

        def same(got):
            assert np.array_equal(np.asarray(got), vals.astype(np.uint64))
        price("window.two_words_one_gather", two_words,
              jax.device_put(words), bitpos, check=same)
        price("window.parent_eight_byte_gathers", eight_bytes,
              jax.device_put(np.frombuffer(vals.astype(">u2").tobytes(),
                                           np.uint8)), bitpos, check=same)

    if wanted("dictionary"):
        idx = jnp.asarray(rng.integers(0, 3, cap).astype(np.int32))
        blob = jnp.asarray(O._blob(b"ANR"))
        dst = jnp.asarray(O._padded(np.arange(3, dtype=np.int64), 8))
        dln = jnp.asarray(O._padded(np.ones(3, np.int32), 8))
        valid = jnp.ones(cap, bool)
        was, O._TINY_DICTIONARY = O._TINY_DICTIONARY, 0
        price("dictionary.rows_by_one_stacked_gather",
              lambda b, s, n, i, v: O._dictionary_rows(b, s, n, i, v, 8),
              blob, dst, dln, idx, valid)
        O._TINY_DICTIONARY = was
        big = rng.integers(0, 1 << 15, cap).astype(np.int32)
        words8 = rng.integers(97, 123, (1 << 15) * 8).astype(np.uint8)
        args = (jnp.asarray(words8),
                jnp.asarray(np.arange(1 << 15, dtype=np.int64) * 8),
                jnp.asarray(np.full(1 << 15, 8, np.int32)),
                jnp.asarray(big), valid)
        price("dictionary_32768.rows_by_one_stacked_gather",
              lambda b, s, n, i, v: O._dictionary_rows(b, s, n, i, v, 8),
              *args)
        price("dictionary_32768.byte_gather",
              lambda b, s, n, i, v: O._string_matrix_tail(b, s[i], n[i],
                                                          v, 8), *args)
        price("dictionary.parent_byte_gather",
              lambda b, s, n, i, v: O._string_matrix_tail(b, s[i], n[i],
                                                          v, 8),
              blob, dst, dln, idx, valid)

    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "price_orc_decode.jsonl"),
              "w") as f:
        for line in OUT:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
