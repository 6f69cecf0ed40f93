"""Row-level device kernels shared by exec operators: stable compaction, gather,
multi-key sorting, segmented reduction.

These are the TPU counterparts of libcudf's gather/scatter/sort/groupby kernels (the
reference's L0, consumed via `ai.rapids.cudf.Table` JNI). All are xp-generic where
practical so the CPU engine shares semantics; the sort/segment ops use jax-specific
primitives (lexsort/segment_sum) with numpy equivalents behind the same signature.

Design notes (ARCHITECTURE.md #4):
  * rows move together: `gather_vecs` takes a batch's vecs and ONE index vector and,
    on the device, moves every row-aligned array of the flat vecs as rows of a few
    stacked (8, n) uint32 matrices, one gather a matrix (`_RowMover`): flags and
    sub-word integers as bit fields, 32-bit integers as they are, 64-bit ones as
    halves, narrow byte matrices as words; floats and wide matrices move alone. The
    chip gathers eight 32-bit rows for the price of one index pass, so a gather an
    array (what `Vec.gather` and the numpy path do) costs a batch many times more. Callers hand over everything that moves by the same
    indices in one call; a `GatherTally` counts what went which way;
  * compaction keeps the padded capacity and returns a new logical count — one stable
    sort of the one-byte keep flag, then `gather_vecs` by its permutation;
  * multi-key sort builds a key list per SortOrder (null indicator + transformed
    data) and lexsorts; descending integer keys use bitwise-not (no INT_MIN
    overflow), descending floats negate, strings contribute big-endian words;
  * every device sort is a chain of single-key stable sorts with int32 indices
    (`stable_lexsort`): the chip's compiler takes minutes over one variadic sort;
  * grouping = sort by keys + boundary detection + segmented reductions over the
    static capacity: integer sums and counts as a prefix sum and a difference at
    the group ends (`SortedSegments`, no scatter), floating sums and min/max as
    segment_{sum,min,max} with the capacity as num_segments.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..expr.base import Vec, vec_map_arrays

BIG_I32 = np.int32(2 ** 31 - 1)
LANE = 128


class GatherTally:
    """What the row gathers of one kernel did, counted while it is traced:
    distinct arrays that rode in a stacked word matrix, the matrices
    gathered for them, and arrays gathered alone."""

    def __init__(self):
        self.packed = 0
        self.matrices = 0
        self.alone = 0


# 32-bit rows of a (K, n) matrix that the chip gathers along n for the price
# of one index pass (PERF.md price list: 11 ms for 8 rows of 2,097,152, 19 ms
# for one row alone)
PACK_ROWS = 8
# widest row of an (n, W) matrix that is cut into word planes, the words of
# one matrix; a wider one (a 256-byte string) is gathered as it is, a whole
# row per index, which costs per index whatever the width (PERF.md price
# list, PR 36: at 64 bytes the two ways tie or words lose)
PACK_MAX_ROW_BYTES = 4 * PACK_ROWS


def _as_u32(a):
    """A (n,) array of a 1-, 2- or 4-byte dtype or bool, each value's bits
    in the low end of a uint32."""
    import jax
    if a.dtype == np.bool_:
        return a.astype(np.uint32)
    size = a.dtype.itemsize
    bits = jax.lax.bitcast_convert_type(a, np.dtype(f"uint{8 * size}"))
    return bits.astype(np.uint32)


def _from_u32(w, dtype):
    """Inverse of `_as_u32`; `w` holds nothing above the dtype's bits."""
    import jax
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return w != 0
    bits = w.astype(np.dtype(f"uint{8 * dtype.itemsize}"))
    return jax.lax.bitcast_convert_type(bits, dtype)


class _RowMover:
    """Moves row-aligned arrays (leading dim n) by one index vector as rows
    of stacked (K, n) uint32 matrices, K <= PACK_ROWS, one gather a matrix.

    `add` files an array once (by identity): bools and 1- and 2-byte
    integers as bit fields of shared words, 32-bit integers as they are,
    64-bit integers as two halves, an (n, W) matrix of those column by
    column, a narrow byte matrix as W / 4 words. Floats (the chip emulates
    float64, and float32 bit patterns did not come back equal from it:
    PERF.md, PR 36) and everything wider or deeper are gathered alone, as
    `arr[idx]`. `move` gathers; `got` cuts an array's planes back into its
    dtype and shape. Indices mean what they mean to `arr[idx]`."""

    def __init__(self, idx, tally: Optional[GatherTally] = None):
        self.idx = idx
        self.tally = tally or GatherTally()
        self.words: List = []     # uint32 (n,) planes
        self.fields: List = []    # (uint32 (n,) values, bits) to share words
        self.where: dict = {}     # field -> (its word, its shift)
        # id(array) -> (how to rebuild it, the array: its id stays its own)
        self.plans: dict = {}
        self.out_words: List = []

    def _file_1d(self, a):
        """Plan for one (n,) array, or None if it does not pack."""
        kind, size = a.dtype.kind, a.dtype.itemsize
        if kind == "b" or (kind in "iu" and size < 4):
            self.fields.append((_as_u32(a), 1 if kind == "b" else 8 * size))
            return ("field", len(self.fields) - 1)
        if kind in "iu" and size == 4:
            self.words.append(_as_u32(a))
            return ("word", len(self.words) - 1)
        if kind in "iu" and size == 8:
            import jax
            u = jax.lax.bitcast_convert_type(a, np.uint64)
            self.words.append(u.astype(np.uint32))
            self.words.append((u >> np.uint64(32)).astype(np.uint32))
            return ("halves", len(self.words) - 2)
        return None

    def add(self, a) -> None:
        if id(a) in self.plans:
            return
        plan = None
        narrow = a.ndim == 2 and \
            a.shape[1] * a.dtype.itemsize <= PACK_MAX_ROW_BYTES
        if a.ndim == 1:
            plan = self._file_1d(a)
        elif narrow and a.dtype == np.uint8 and a.shape[1] % 4 == 0:
            import jax.numpy as jnp
            first = len(self.words)
            self.words.extend(_string_words(jnp, a))
            plan = ("bytes", first, a.shape[1] // 4)
        elif narrow and a.dtype.kind in "iu" and a.dtype.itemsize >= 4:
            plan = ("cols", [self._file_1d(a[:, j])     # decimal128 limbs
                             for j in range(a.shape[1])])
        if plan is None:
            self.plans[id(a)] = (None, a, self.lone(a))
        else:
            self.plans[id(a)] = (plan, a)
            self.tally.packed += 1

    def lone(self, a):
        """`a` gathered alone, as `arr[idx]` along its first axis."""
        self.tally.alone += 1
        return a[self.idx]

    def move(self) -> None:
        import jax.numpy as jnp
        # widest fields first: 16, 8 and 1 divide 32, so words fill exactly
        order = sorted(range(len(self.fields)),
                       key=lambda i: -self.fields[i][1])
        used = 32
        for i in order:
            vals, bits = self.fields[i]
            if used + bits > 32:
                self.words.append(jnp.zeros_like(vals))
                used = 0
            self.words[-1] = self.words[-1] | (vals << np.uint32(used))
            self.where[i] = (len(self.words) - 1, used)
            used += bits
        for lo in range(0, len(self.words), PACK_ROWS):
            m = jnp.stack(self.words[lo:lo + PACK_ROWS])
            self.out_words.extend(m[:, self.idx])
            self.tally.matrices += 1

    def _rebuild(self, plan, dtype):
        import jax
        import jax.numpy as jnp
        kind = plan[0]
        if kind == "field":
            w, shift = self.where[plan[1]]
            bits = self.fields[plan[1]][1]
            v = (self.out_words[w] >> np.uint32(shift)) & \
                np.uint32((1 << bits) - 1)
            return _from_u32(v, dtype)
        if kind == "word":
            return _from_u32(self.out_words[plan[1]], dtype)
        lo, hi = self.out_words[plan[1]], self.out_words[plan[1] + 1]
        u = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
        return jax.lax.bitcast_convert_type(u, dtype)

    def got(self, a):
        import jax.numpy as jnp
        plan = self.plans[id(a)][0]
        if plan is None:
            return self.plans[id(a)][2]
        if plan[0] == "bytes":
            ws = jnp.stack(self.out_words[plan[1]:plan[1] + plan[2]], axis=1)
            shifts = np.array([24, 16, 8, 0], dtype=np.uint32)
            return ((ws[:, :, None] >> shifts) & np.uint32(0xFF)).astype(
                np.uint8).reshape(ws.shape[0], 4 * plan[2])
        if plan[0] == "cols":
            return jnp.stack([self._rebuild(p, a.dtype) for p in plan[1]],
                             axis=1)
        return self._rebuild(plan, a.dtype)


def gather_vecs(xp, vecs: Sequence[Vec], idx,
                tally: Optional[GatherTally] = None) -> List[Vec]:
    """Gather rows by index across columns (JoinGatherer analog).

    On the device the columns move together: every row-aligned array of
    the flat vecs rides in a few stacked word matrices (`_RowMover`), each
    gathered once by `idx`, and is cut back bit for bit; an array that
    several vecs share moves once (a long string's blob is not row-aligned
    and passes through). Nested vecs move down their children one array at
    a time, as the CPU engine's numpy path moves everything. `tally` counts
    what went which way."""
    if xp is np or np.ndim(idx) != 1:
        return [v.gather(xp, idx) for v in vecs]
    mover = _RowMover(idx, tally)
    for v in vecs:
        if v.children is None:
            vec_map_arrays(v, mover.add)
    mover.move()
    return [vec_map_arrays(v, mover.got if v.children is None else mover.lone)
            for v in vecs]


def gather_arrays(arrays: Sequence, idx,
                  tally: Optional[GatherTally] = None) -> List:
    """Device arrays of one leading length gathered by one index vector,
    moved together as `gather_vecs` moves a batch's columns (`_RowMover`)."""
    mover = _RowMover(idx, tally)
    for a in arrays:
        mover.add(a)
    mover.move()
    return [mover.got(a) for a in arrays]


# The v5e compiler gathers out of a table of under ~2 MB another way, with
# 1 GB of temporaries for 2,097,152 indices, and that way costs by the row:
# 37.9 ms for the date column's expansion, whose 7-row table has 8,192 runs,
# against 18.0 with the table zero-padded to 524,288, and 37.8 against 22.0
# for the 8 MB varint stream's two rows of 262,144; but two rows of 65,536 or
# 131,072 are 2.5 ms cheaper left short (sandbox v5e compiler and my chip
# runs, PERF.md, PR 37). So a short table is padded, on the device, to this
# many columns before a long gather unless it is two rows of under a megabyte.
_GATHER_MIN_COLS = 1 << 19
_GATHER_SHORT_OK = (2, 1 << 17)      # at most (rows, columns)


def gather_rows(rows, idx):
    """uint32[K, len(idx)]: K <= 8 uint32 rows of one length (a list of
    them, or a (K, n) matrix) gathered along it by one index vector,
    stacked so that the chip pays per index and not per row
    (`ops/rowops.PACK_ROWS`; PERF.md price list)."""
    import jax.numpy as jnp
    m = jnp.stack(rows) if isinstance(rows, (list, tuple)) else rows
    assert m.shape[0] <= PACK_ROWS, m.shape
    k, n = m.shape
    short = min(_GATHER_MIN_COLS, idx.shape[0]) - n
    if short > 0 and not (k <= _GATHER_SHORT_OK[0]
                          and n <= _GATHER_SHORT_OK[1]):
        m = jnp.pad(m, ((0, 0), (0, short)))
    return m[:, jnp.clip(idx, 0, n - 1)]


def ahead(words, k: int):
    """`words` read `k` places ahead (zeros past the end)."""
    import jax.numpy as jnp
    return jnp.concatenate([words[k:], jnp.zeros(k, words.dtype)])


def stable_lexsort(xp, keys: Sequence):
    """Stable sort permutation over `keys`, MOST-significant first.

    On device this is NOT one variadic sort: the v5e compiler's time over a
    sort grows steeply with its operand count and with 64-bit operands (a
    10-operand sort of 131,072 rows took it 574 s, a 64-bit iota alone doubles
    a one-key sort). A chain of single-key stable sorts, least significant
    key first, each carrying one int32 index operand, is the same permutation
    and compiled in 22 s for nine keys."""
    if xp is np:
        return np.lexsort(tuple(keys[::-1]))
    import jax
    perm = None
    for key in reversed(keys):
        if perm is None:
            perm = jax.lax.iota(np.int32, key.shape[0])
        else:
            key = key[perm]
        _, perm = jax.lax.sort((key, perm), num_keys=1, is_stable=True)
    return perm


def compaction_order(xp, keep_mask):
    """Stable permutation that moves the rows where keep_mask to the front."""
    return stable_lexsort(xp, [(~keep_mask).astype(np.int8)])


def compact_vecs(xp, vecs: Sequence[Vec], keep_mask,
                 tally: Optional[GatherTally] = None) -> Tuple[List[Vec], any]:
    """Stable-move rows where keep_mask (bool[cap]) to the front; returns
    (columns, new_count). Padding tail contents are unspecified."""
    order = compaction_order(xp, keep_mask)
    new_count = xp.sum(keep_mask).astype(np.int32)
    return gather_vecs(xp, vecs, order, tally), new_count


def _string_words(xp, data) -> List:
    """A [n, width] byte matrix as big-endian uint32 words, most significant
    first: unsigned word order IS byte-lexicographic order, with a quarter
    of the sort operands. One operand per byte made the chip's compiler take
    minutes over a 16-byte key (a variadic sort's cost grows with its
    operand count)."""
    n, width = data.shape
    pad = -width % 4
    if pad:
        data = xp.pad(data, ((0, 0), (0, pad)))
    # explicit word count: -1 cannot be inferred for a zero-row batch
    q = data.reshape(n, (width + pad) // 4, 4).astype(np.uint32)
    words = ((q[:, :, 0] << np.uint32(24)) | (q[:, :, 1] << np.uint32(16))
             | (q[:, :, 2] << np.uint32(8)) | q[:, :, 3])
    return [words[:, i] for i in range(words.shape[1])]


def sort_keys_for(xp, v: Vec, ascending: bool, nulls_first: bool) -> List:
    """Build lexsort key arrays for one SortOrder over a column, MOST-significant
    first: [null-position, (nan-position), value keys...]."""
    from ..expr.base import require_flat_strings
    require_flat_strings(v, "sort key over string")
    dt = v.dtype
    # ascending lexsort: nulls-first wants null rows to carry the SMALLER
    # key (valid=1 > null=0); nulls-last the larger (round-4 golden-oracle
    # fix — the flag was inverted identically on both engines, which the
    # differential harness cannot see)
    null_key = (v.validity if nulls_first else ~v.validity).astype(np.int8)
    keys: List = [null_key]
    if v.is_string:
        lens = v.lengths.astype(np.int32)
        words = _string_words(xp, v.data)
        if ascending:
            keys.extend(words)
            keys.append(lens)  # trailing-NUL tiebreak (cf. string_compare)
        else:
            keys.extend(~w for w in words)
            keys.append(~lens)
    elif isinstance(dt, T.DecimalType) and \
            dt.precision > T.DecimalType.MAX_LONG_DIGITS:
        from ..expr.decimal128 import cmp_keys
        hi_k, lo_k = cmp_keys(xp, v.data[:, 0], v.data[:, 1])
        if ascending:
            keys.extend([hi_k, lo_k])
        else:
            keys.extend([~hi_k, ~lo_k])
    elif T.is_floating(dt):
        nan = xp.isnan(v.data)
        zero = dt.np_dtype.type(0)
        if ascending:
            keys.append(nan.astype(np.int8))     # NaN sorts greatest
            keys.append(xp.where(nan, zero, v.data))
        else:
            keys.append((~nan).astype(np.int8))  # NaN first when descending
            keys.append(xp.where(nan, zero, -v.data))
    else:
        data = v.data
        if isinstance(dt, T.BooleanType):
            data = data.astype(np.int8)
        keys.append(data if ascending else ~data)
    return keys


def lexsort_indices(xp, key_groups: Sequence[List], cap: int):
    """keys given MOST-significant first; returns stable sort permutation."""
    flat: List = []
    for grp in key_groups:
        flat.extend(grp)
    return stable_lexsort(xp, flat)


def sort_batch_vecs(xp, vecs: Sequence[Vec], sort_cols: Sequence[int],
                    ascending: Sequence[bool], nulls_first: Sequence[bool],
                    row_mask) -> List[Vec]:
    """Sort all columns by the given sort orders; padding rows sort last."""
    groups = [[(~row_mask).astype(np.int8)]]  # padding after everything
    for ci, asc, nf in zip(sort_cols, ascending, nulls_first):
        groups.append(sort_keys_for(xp, vecs[ci], asc, nf))
    order = lexsort_indices(xp, groups, row_mask.shape[0])
    return gather_vecs(xp, vecs, order)


def key_change_flags(xp, key_vecs: Sequence[Vec], n: int):
    """True at rows whose key values differ from the previous row (row 0 is
    False). Spark equality semantics: two nulls are equal (garbage data under
    null slots must not split groups), two NaNs are equal."""
    change = xp.zeros(n, dtype=bool)
    for v in key_vecs:
        both_valid = v.validity[1:] & v.validity[:-1]
        if v.is_string:
            d = v.data
            neq = xp.any(d[1:] != d[:-1], axis=1) | \
                (v.lengths[1:] != v.lengths[:-1])
        elif v.data.ndim == 2:  # decimal128 limb pairs
            neq = xp.any(v.data[1:] != v.data[:-1], axis=1)
        else:
            neq = v.data[1:] != v.data[:-1]
            if np.issubdtype(np.dtype(v.data.dtype), np.floating):
                neq = neq & ~(xp.isnan(v.data[1:]) & xp.isnan(v.data[:-1]))
        neq = (neq & both_valid) | (v.validity[1:] != v.validity[:-1])
        change = change | xp.concatenate([xp.zeros(1, dtype=bool), neq])
    return change


def prefix_sum(x):
    """Inclusive prefix sum along the last axis of an int32 or int64 array,
    (n,) or (K, n): log2(128) shifted adds inside rows of a (n / 128, 128)
    view, then the row totals the same way. Device only. The same function
    as `jnp.cumsum`, whose reduce-window costs the v5e compiler 24-33 s for
    each 1M-slot call (PERF.md) where this costs it half a second and
    runs as fast (0.66 against 0.81 ms at 1M int32 slots on a v5e)."""
    import jax.numpy as jnp
    n = x.shape[-1]
    if n == 0:
        return x
    lead = [(0, 0)] * (x.ndim - 1)
    rows = jnp.pad(x, lead + [(0, -n % LANE)]).reshape(
        x.shape[:-1] + (-1, LANE))
    step = 1
    while step < min(n, LANE):
        rows = rows + jnp.pad(rows[..., :-step], lead + [(0, 0), (step, 0)])
        step *= 2
    if rows.shape[-2] > 1:
        before = jnp.pad(prefix_sum(rows[..., -1])[..., :-1], lead + [(1, 0)])
        rows = rows + before[..., None]
    return rows.reshape(x.shape[:-1] + (-1,))[..., :n]


def slot_runs(ends, cap: int):
    """run int32[cap]: for every output slot the run of the table that holds
    it, `searchsorted(ends, j, side="right")` clipped to R - 1, from the
    runs' exclusive end slots (non-decreasing int32[R]). Device only. The
    query is every slot in order, so nothing is searched: a run ends before
    slot j exactly when its end marks a slot <= j, which is one mark per RUN
    scattered into the slots and one prefix sum over them. Zero-count runs
    (real empty runs, and padding runs, which end where the last real one
    does) stack their marks on one slot and are stepped over as side="right"
    steps over them; ends at or past `cap` mark no slot. The parquet and ORC
    decoders map slots to RLE runs with it, the join's expand output slots
    to probe rows.

    The barrier makes the map one array, computed once. The searches it
    replaced were loops, which XLA fuses nothing into; without them it
    fuses the prefix sum into each gather that reads the map, and the
    decode programs' code, which lives in device memory, grows from 182 to
    279 MB (`star.q3`) and from 189 to 500 MB (`lineitem.q1`), by the v5e
    compiler's count."""
    import jax.numpy as jnp
    from jax import lax
    assert 0 < cap < 2 ** 31, cap
    marks = jnp.zeros(cap, jnp.int32).at[ends].add(
        1, mode="drop", indices_are_sorted=True)
    run = jnp.clip(prefix_sum(marks), 0, ends.shape[0] - 1)
    return lax.optimization_barrier(run)


def group_ids_from_sorted(xp, key_vecs: Sequence[Vec], row_mask):
    """After sorting by keys, compute (group_id[cap], num_groups, starts_mask).
    Padding rows get group_id == cap-1 sentinel region handled by callers via
    row_mask."""
    n = row_mask.shape[0]
    change = key_change_flags(xp, key_vecs, n)
    starts = change | (xp.arange(n) == 0)
    starts = starts & row_mask
    # rows beyond the live region belong to no group
    flags = starts.astype(np.int32)
    gid = (np.cumsum(flags) if xp is np else prefix_sum(flags)) - 1
    gid = xp.where(row_mask, gid, n - 1)
    num_groups = xp.sum(starts).astype(np.int32)
    return gid, num_groups, starts


def segment_ends(start_order, num_groups, live):
    """int32[cap]: the position of the last row of every group of rows that
    are sorted by group with the `live` live ones first. `start_order` is
    the stable permutation that compacts the group-start rows
    (`compaction_order(starts)`), so `start_order[g]` is where group g
    starts and group g ends one row before group g + 1 does; the last group
    ends at row live - 1. `None` means one group over all live rows. Entries
    at g >= num_groups repeat the last live row (row 0 of an empty batch),
    so the vector is non-decreasing."""
    import jax
    import jax.numpy as jnp
    last = jnp.maximum(live.astype(np.int32) - 1, 0)
    if start_order is None:
        return last
    cap = start_order.shape[0]
    next_start = jnp.pad(start_order[1:], (0, 1))
    return jnp.where(jax.lax.iota(np.int32, cap) + 1 < num_groups,
                     next_start - 1, last)


def sorted_segment_sum(contrib, ends, num_groups):
    """Per-group sums of integer `contrib`, (cap,) or (K, cap) with the rows
    along the last axis, as `jax.ops.segment_sum(contrib, gid, cap)` returns
    them (zero at g >= num_groups), without a scatter: an inclusive prefix
    sum P, its value E at every group's end (`segment_ends`), and
    total[g] = E[g] - E[g - 1].

    Contract: the rows are sorted so that `gid` is non-decreasing over a
    live prefix, and dead rows contribute zero. Then group g is the rows
    ends[g - 1] + 1 .. ends[g] and the difference is its sum.

    Exact: two's-complement addition wraps, so E[g] - E[g - 1] is the
    segment's true sum modulo 2^64 (2^32) even where the running prefix has
    wrapped; wherever the scatter-add's total was exact (43-bit decimal
    chunks, counts, int64 sums under Spark's own wrap / ANSI rule) this is
    the same value bit for bit. Not for floats: a difference of float
    prefixes cancels and carries the earlier groups' magnitude."""
    import jax
    import jax.numpy as jnp
    assert np.issubdtype(contrib.dtype, np.integer), contrib.dtype
    at_ends = prefix_sum(contrib).at[..., ends].get(
        mode="promise_in_bounds", indices_are_sorted=True)
    lead = [(0, 0)] * (contrib.ndim - 1)
    totals = at_ends - jnp.pad(at_ends[..., :-1], lead + [(1, 0)])
    live_group = jax.lax.iota(np.int32, ends.shape[0]) < num_groups
    return jnp.where(live_group, totals, contrib.dtype.type(0))


class SortedSegments:
    """Rows sorted by group key, as the segmented reductions of one
    aggregate kernel share them: `gid` (non-decreasing over the live prefix
    of `row_mask`, cap - 1 on dead rows), `num_groups`, and on the device
    the groups' end positions, computed once for every column. The two
    tallies count, while a kernel is traced, the reductions that took the
    prefix route and those that lowered to a scatter."""

    def __init__(self, xp, gid, num_groups, row_mask, start_order=None):
        self.gid = gid
        self.num_groups = num_groups
        self.cap = row_mask.shape[0]
        self.ends = None
        if xp is not np:
            live = xp.sum(row_mask).astype(np.int32)
            self.ends = xp.broadcast_to(
                segment_ends(start_order, num_groups, live), row_mask.shape)
        self.prefix_routed = 0
        self.scattered = 0

    def sum(self, contrib):
        """Per-group sums of `contrib`, (cap,) or (cap, K), zero at dead
        rows (device): integers by `sorted_segment_sum`, floats by
        `jax.ops.segment_sum`."""
        import jax
        if not np.issubdtype(contrib.dtype, np.integer):
            self.scattered += 1
            return jax.ops.segment_sum(contrib, self.gid,
                                       num_segments=self.cap)
        self.prefix_routed += 1
        if contrib.ndim == 2:
            return sorted_segment_sum(contrib.T, self.ends,
                                      self.num_groups).T
        return sorted_segment_sum(contrib, self.ends, self.num_groups)

    def count(self, valid):
        """Per-group count of the rows where `valid` (which excludes dead
        rows), int64[cap]. A count is at most cap < 2^31 and the chip
        emulates 64-bit integers: summed in int32, widened after."""
        return self.sum(valid.astype(np.int32)).astype(np.int64)

    def sums(self, *contribs):
        """Per-group int64 totals of several integer or bool (cap,)
        contributions over the same rows (zero or false at dead rows), as
        the rows of one (K, cap) int64 matrix and one reduction. The
        gather at the group ends costs the chip per index, not per row, up
        to 8 rows: 22.6 ms for 2 to 8 int64 rows of 2,097,152, 34 ms for
        one row alone and 19 ms for a lone int32 (PERF.md, PR 33), so a
        count rides with the sums it belongs to for nothing."""
        import jax.numpy as jnp
        self.prefix_routed += len(contribs)
        rows = jnp.stack([c.astype(np.int64) for c in contribs])
        return tuple(sorted_segment_sum(rows, self.ends, self.num_groups))

    def minmax(self, op: str, contrib):
        """Per-group extremum of `contrib` (rows along axis 0, invalid rows
        at the neutral value): a scatter, as before."""
        import jax
        self.scattered += 1
        seg = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        return seg(contrib, self.gid, num_segments=self.cap)


# Whole-stage fusion hook (exec/fused.py): while a fused stage traces an
# aggregate member with the pallas group-by enabled, this holds
# ops.pallas_groupby.fused_segment_sum (bit-exact, self-fallback outside its
# int64 window). None — always, outside that trace — means the plain paths
# below run untouched.
_FUSED_SEGMENT_SUM = None


def segment_reduce(xp, op: str, data, segs: SortedSegments, valid=None):
    """Segmented reduction over rows sorted by group. Invalid rows are
    excluded (null-skipping aggregate semantics); `valid` excludes the dead
    rows. Returns per-group array of length cap."""
    gid, cap = segs.gid, segs.cap
    if valid is None:
        valid = xp.ones(data.shape[0], dtype=bool)
    if op == "count":
        if xp is np:
            return np.bincount(gid, weights=valid.astype(np.int64),
                               minlength=cap).astype(np.int64)
        if _FUSED_SEGMENT_SUM is not None:
            return _FUSED_SEGMENT_SUM(valid.astype(np.int64), gid, cap)
        return segs.count(valid)
    if op == "sum":
        contrib = xp.where(valid, data, data.dtype.type(0))
        if xp is np:
            out = np.zeros(cap, dtype=data.dtype)
            np.add.at(out, gid, contrib)
            return out
        if _FUSED_SEGMENT_SUM is not None and contrib.ndim == 1:
            return _FUSED_SEGMENT_SUM(contrib, gid, cap)
        return segs.sum(contrib)
    if op in ("min", "max"):
        if np.issubdtype(data.dtype, np.floating):
            neutral = data.dtype.type(np.inf if op == "min" else -np.inf)
        else:
            info = np.iinfo(data.dtype) if data.dtype != np.bool_ else None
            if info is None:
                neutral = np.bool_(True) if op == "min" else np.bool_(False)
            else:
                neutral = data.dtype.type(info.max if op == "min" else info.min)
        contrib = xp.where(valid, data, neutral)
        if xp is np:
            out = np.full(cap, neutral, dtype=data.dtype)
            fn = np.minimum if op == "min" else np.maximum
            getattr(fn, "at")(out, gid, contrib)
            return out
        return segs.minmax(op, contrib)
    raise ValueError(f"unknown segmented op {op}")


def segment_sum_count(xp, data, segs: SortedSegments, valid):
    """(per-group sum of `data` over its valid rows, their count): for
    integers on the device one stacked reduction (`SortedSegments.sums`),
    else a `segment_reduce` each."""
    if xp is np or _FUSED_SEGMENT_SUM is not None or \
            not np.issubdtype(data.dtype, np.integer):
        return (segment_reduce(xp, "sum", data, segs, valid),
                segment_reduce(xp, "count", data, segs, valid))
    total, count = segs.sums(xp.where(valid, data, data.dtype.type(0)), valid)
    return total.astype(data.dtype), count


def sample_mask(xp, n: int, row_offset, fraction: float, seed: int):
    """Deterministic Bernoulli sample mask over global row ordinals
    (GpuSampleExec analog). splitmix64 of (offset+i) ^ f(seed) -> uniform
    [0,1) — identical bits on numpy and jax, so both engines select the
    SAME rows for a given seed (the differential harness depends on it)."""
    mask64 = np.uint64(0xFFFFFFFFFFFFFFFF)
    idx = xp.arange(n, dtype=np.uint64) + xp.asarray(row_offset,
                                                     dtype=np.uint64)
    # pre-mix the seed with PYTHON ints (numpy scalar multiply warns on wrap)
    seed_mix = ((seed & 0xFFFFFFFFFFFFFFFF) * 0x9E3779B97F4A7C15) \
        & 0xFFFFFFFFFFFFFFFF
    z = idx ^ np.uint64(seed_mix)
    z = (z + np.uint64(0x9E3779B97F4A7C15)) & mask64
    z = ((z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) & mask64
    z = ((z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) & mask64
    z = z ^ (z >> np.uint64(31))
    u = (z >> np.uint64(11)).astype(np.float64) * (2.0 ** -53)
    return u < fraction
