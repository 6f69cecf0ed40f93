"""Hash join exec (reference `GpuHashJoin.doJoin` `GpuHashJoin.scala:950`,
`GpuShuffledHashJoinExec.scala`, gather-map composition `JoinGatherer.scala:54-641`).

TPU lowering (ARCHITECTURE.md #4): equi-joins run as hash-sorted probe —
  1. hash the build-side keys (Spark murmur3), sort build rows by hash, and give
     each sorted position the length of the run of equal hashes it starts
     (elementwise + one reversed running minimum over the small build side);
  2. per probe row, locate the start of its candidate range (`side=left` in
     the sorted 32-bit hashes) by a merge: one stable sort of the probe and
     build hashes together and a prefix sum over the build entries' marks;
     the range's length is the run length at that position if the hash there
     is the probe's, else 0;
  3. expand matches into (probe_idx, build_idx) pairs at a host-chosen output
     capacity (the JoinGatherer chunking analog: counts are computed on device,
     summed, synced once to pick the bucket — data-dependent sizes never reach
     XLA); each output slot's probe row comes from one mark per probe row and
     a prefix sum (`rowops.slot_runs`). Phase 2 consumes phase 1's arrays
     (counts, range starts, build order, validity masks): nothing of the probe
     is computed twice;
  4. gather both sides, verify true key equality (hash collisions + null keys),
     compact away false positives.
Left/right/full outer rows are emitted via the unmatched masks; semi/anti reduce the
counts instead of expanding. Build side defaults to the right child like the
reference's GpuShuffledHashJoinExec with BuildRight."""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema, join_output_schema
from ..columnar.padding import row_bucket
from ..compile import sjit
from ..expr.base import Expression, Vec, bind_references
from ..expr.hashing import hash_vecs
from ..expr.predicates import string_equal
from ..ops.rowops import (compact_vecs, gather_vecs, prefix_sum, slot_runs,
                          stable_lexsort)
from ..utils import metrics as M
from ..utils import spans
from .base import (StaticExpr as _StaticExpr, TpuExec, batch_vecs,
                   device_ctx, vecs_to_batch)
from .coalesce import concat_batches


def _keys_valid(xp, keys: List[Vec]):
    ok = None
    for k in keys:
        ok = k.validity if ok is None else (ok & k.validity)
    return ok


def _keys_equal(xp, a: List[Vec], b: List[Vec]):
    eq = None
    for ka, kb in zip(a, b):
        if ka.is_string:
            e = string_equal(xp, ka, kb)
        elif T.is_floating(ka.dtype):
            e = (ka.data == kb.data) | (xp.isnan(ka.data) & xp.isnan(kb.data))
        else:
            e = ka.data == kb.data
        eq = e if eq is None else (eq & e)
    return eq


def _slot_counts(xp, counts, pmask, join_type: str):
    """Output slots per probe row: its candidate count, and one for a live
    row without candidates where the join keeps unmatched probe rows. The
    host sums these to size the expand; the expand lays its pairs out by
    them."""
    if join_type in ("left", "full"):
        counts = xp.maximum(counts, 1)
    return xp.where(pmask, counts, 0)


_HASH_MAX = np.int32(np.iinfo(np.int32).max)


def _run_lengths(xp, keys_sorted, n_valid):
    """For each position of a sorted key array whose first `n_valid` entries
    count: how many entries from it to the end of its run of equal keys (0
    past the valid prefix). Elementwise ops and one reversed running minimum;
    a probe that lands on a run's first position reads the run's length."""
    n = keys_sorted.shape[0]
    idx = xp.arange(n, dtype=np.int32)
    starts = xp.concatenate([xp.ones(1, dtype=bool),
                             keys_sorted[1:] != keys_sorted[:-1]])
    # nearest run start at or after each position, then strictly after it
    nxt = lax.cummin(xp.where(starts, idx, np.int32(n)), reverse=True)
    nxt = xp.concatenate([nxt[1:], xp.full(1, n, dtype=np.int32)])
    return xp.maximum(xp.minimum(nxt, n_valid) - idx, 0)


def _merge_rank(queries, keys_sorted):
    """int32[len(queries)]: `searchsorted(keys_sorted, queries, side="left")`
    by counting, not searching (device only). The queries and the sorted
    keys are sorted together, stably and queries first, so at equal values a
    query lands before the keys and its rank counts only the smaller ones;
    a query's rank is then the number of key entries before it, an exclusive
    prefix sum over their marks, and one more sort by source position puts
    the ranks back in query order. `jnp.searchsorted`'s scan is a `while`
    loop of log2(n) dependent gathers that XLA fuses nothing into; its sort
    method does two argsorts and two scatters and took the v5e compiler 31 s
    for the probe at 2M rows (PERF.md)."""
    nq = queries.shape[0]
    src = stable_lexsort(jnp, [jnp.concatenate([queries, keys_sorted])])
    is_key = (src >= nq).astype(np.int32)
    before = prefix_sum(is_key) - is_key
    _, rank = lax.sort((src, before), num_keys=1)
    return rank[:nq]


@sjit(op="exec.join.probe_counts", static_argnums=(2, 3))
def _probe_counts(probe: ColumnarBatch, build: ColumnarBatch,
                  probe_key_ix: Tuple[int, ...], build_key_ix: Tuple[int, ...],
                  hash_rows=None):
    """Phase 1: per-probe candidate counts and range starts (by hash) in the
    sorted build order; the range's length comes from the build side's run
    lengths. `hash_rows` stands in for `hash_vecs` (bit-identical; the fused
    stage's Pallas row hash).

    A range start is `searchsorted(bh_sorted, ph, side="left")`, taken as
    a merge rank (`_merge_rank`): the program holds no loop and four sorts,
    two for the build side's two-key order and the merge's two. One way
    for every shape (v5e, median of 8, `scripts/price_join_search.py`;
    PERF.md): 2,097,152 probe hashes take the merge 9.4 ms against 131,072
    build rows and the scan search 271 ms. The scan wins only out of a
    table of at most 64 entries (1.7 ms), which no batch is: at the least
    capacity a batch has, 128, this program took 6.5 ms merged and 23.3 ms
    searched for 262,144 probe rows."""
    xp = jnp
    hash_rows = hash_rows or hash_vecs
    pvecs = batch_vecs(probe)
    bvecs = batch_vecs(build)
    pkeys = [pvecs[i] for i in probe_key_ix]
    bkeys = [bvecs[i] for i in build_key_ix]
    pmask = probe.row_mask()
    bmask = build.row_mask()
    pvalid = _keys_valid(xp, pkeys) & pmask
    bvalid = _keys_valid(xp, bkeys) & bmask
    bcap = build.capacity

    ph = hash_rows(xp, pkeys)
    bh = hash_rows(xp, bkeys)
    # valid rows by hash, then the invalid rows, ties in row order (two 32-bit
    # keys: a 64-bit sort costs the chip's compiler twice as long)
    order = stable_lexsort(xp, [(~bvalid).astype(np.int8),
                                xp.where(bvalid, bh, 0)])
    n_valid = xp.sum(bvalid).astype(np.int32)
    # invalid build rows are exiled past the valid prefix under the largest
    # hash: the array stays sorted, a side=left search never lands beyond the
    # first of them, and their run length is 0, so no probe can reach them
    bh_sorted = xp.where(xp.arange(bcap, dtype=np.int32) < n_valid,
                         bh[order], _HASH_MAX)
    run_len = _run_lengths(xp, bh_sorted, n_valid)
    lo = _merge_rank(ph, bh_sorted)
    at = xp.minimum(lo, bcap - 1)
    hit = pvalid & (bh_sorted[at] == ph)
    counts = xp.where(hit, run_len[at], 0).astype(np.int32)
    return counts, lo, order.astype(np.int32), pvalid, bvalid


@sjit(op="exec.join.expand", static_argnums=(7, 8, 9, 10, 11, 12))
def _expand_join(probe: ColumnarBatch, build: ColumnarBatch,
                 counts, lo, order, pvalid, bvalid,
                 probe_key_ix: Tuple[int, ...], build_key_ix: Tuple[int, ...],
                 out_cap: int, join_type: str, condition=None,
                 ansi: bool = False):
    """Phase 2: expand phase 1's candidate ranges (`_probe_counts`' five
    arrays, passed in) to pairs, equality-check (plus the optional non-equi
    join condition evaluated on the gathered pair), compact; attach outer
    rows. Returns (out_vecs, n, bmatched, cond_errs)."""
    xp = jnp
    pvecs = batch_vecs(probe)
    bvecs = batch_vecs(build)
    pkeys = [pvecs[i] for i in probe_key_ix]
    bkeys = [bvecs[i] for i in build_key_ix]
    pmask = probe.row_mask()
    pcap = probe.capacity
    bcap = build.capacity

    outer_left = join_type in ("left", "full")
    offsets = prefix_sum(_slot_counts(xp, counts, pmask, join_type))
    total = offsets[-1] if pcap > 0 else xp.asarray(0, np.int32)
    j = xp.arange(out_cap, dtype=np.int32)
    live = j < total
    # probe row for output slot j: the rows whose slots end at or before j
    pi = slot_runs(offsets, out_cap)
    base = xp.where(pi > 0, offsets[xp.maximum(pi - 1, 0)], 0)
    k = j - base
    bidx_sorted = xp.clip(lo[pi] + k, 0, bcap - 1)
    bi = order[bidx_sorted]

    # each side moves once, keys and payload in the same word matrices; a
    # join that returns probe rows only and has no condition moves its keys
    # alone (a row of a stacked matrix that nothing reads is still gathered)
    if condition is None and join_type in ("semi", "anti", "existence"):
        gp = gather_vecs(xp, pkeys, pi)
        gb = gather_vecs(xp, bkeys, bi)
    else:
        left_out = gather_vecs(xp, pvecs, pi)
        right_out = gather_vecs(xp, bvecs, bi)
        gp = [left_out[i] for i in probe_key_ix]
        gb = [right_out[i] for i in build_key_ix]
    # true equality check (hash collision + sentinel guard)
    eq = _keys_equal(xp, gp, gb) & pvalid[pi] & bvalid[bi] & (k < counts[pi])

    cond_errs = ()
    if condition is not None:
        # join condition over the combined row; NULL counts as no-match.
        # ANSI arithmetic inside the condition reports through the same
        # traced-flag channel projections use; rows outside live candidate
        # pairs are masked out of the flags (they're gather artifacts).
        from ..expr.base import EvalContext
        from .base import kernel_errors
        # the box is safe to share across traces: `ansi` is constant for a
        # given exec instance (conf-derived), and non-ANSI traces still
        # record unconditional signals (raise_error/assert_true)
        cctx = EvalContext(xp, ansi=ansi, errors=[],
                           row_mask=eq & live)
        cvec = condition.expr.eval(cctx, left_out + right_out)
        eq = eq & cvec.data.astype(bool) & cvec.validity
        cond_errs = kernel_errors(cctx, condition.err_msgs)

    matched = eq & live
    # per-probe-row "any true match" — candidate ranges can be pure hash
    # collisions, so counts[pi] > 0 alone must NOT suppress the outer null row
    pmatched = xp.zeros(pcap, dtype=bool)
    pmatched = pmatched.at[xp.where(matched, pi, pcap - 1)].max(matched)
    keep = live & (matched | (outer_left & ~pmatched[pi] & (k == 0)))

    # build matched flags for right/full outer (scatter-or: value False where not
    # matched, so redirecting those slots is harmless)
    bmatched = xp.zeros(bcap, dtype=bool)
    if join_type in ("right", "full"):
        bmatched = bmatched.at[xp.where(matched, bi, bcap - 1)].max(matched)

    if join_type in ("semi", "anti", "existence"):
        if join_type == "existence":
            # all live probe rows, plus the exists flag column
            exists = Vec(T.BooleanType(), pmatched,
                         xp.ones(pcap, dtype=bool))
            out_vecs, n = compact_vecs(xp, pvecs + [exists], pmask)
            return out_vecs, n, bmatched, cond_errs
        want = pmatched if join_type == "semi" else (~pmatched & pmask)
        out_vecs, n = compact_vecs(xp, pvecs, want & pmask)
        return out_vecs, n, bmatched, cond_errs

    if join_type in ("left", "full"):
        # null out the right side where no match (outer fill)
        right_out = [dataclasses.replace(v, validity=v.validity & matched)
                     for v in right_out]
    out_vecs = left_out + right_out
    compacted, n = compact_vecs(xp, out_vecs, keep)
    return compacted, n, bmatched, cond_errs


@sjit(op="exec.join.unmatched_build", static_argnums=(1,))
def _unmatched_build(build: ColumnarBatch, ncols_left: int, bmatched):
    """full/right outer: build rows never matched -> rows with null left side."""
    xp = jnp
    bvecs = batch_vecs(build)
    want = build.row_mask() & ~bmatched
    compacted, n = compact_vecs(xp, bvecs, want)
    return compacted, n


class TpuShuffledHashJoinExec(TpuExec):
    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = "inner", conf=None,
                 condition: Expression = None):
        super().__init__([left, right], conf)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        # set by the distribution pass (exec/requirements.py) when both
        # children are co-partitioned key-exchanges: join batch p with batch p
        # instead of concatenating the streams (per-shard join)
        self.zip_partitions = False
        lo, ro = left.output, right.output
        self._schema = join_output_schema(lo, ro, join_type)
        # optional non-equi condition over the combined (left ++ right) row
        # (reference: condition joins filtered post-gather, GpuHashJoin.scala)
        self.condition = condition
        self._bcond = None if condition is None else _StaticExpr(
            bind_references(condition,
                            Schema(lo.names + ro.names, lo.types + ro.types)))
        self.join_time = self.metrics.create(M.JOIN_TIME, M.ESSENTIAL)
        self.build_time = self.metrics.create(M.BUILD_TIME, M.MODERATE)
        # probe-side stream accounting (reference streamTime /
        # numInputRows on the streamed side of a hash join)
        self.stream_time = self.metrics.create(M.STREAM_TIME, M.MODERATE)
        self.num_input_rows = self.metrics.create(M.NUM_INPUT_ROWS,
                                                  M.MODERATE)
        self.num_input_batches = self.metrics.create(M.NUM_INPUT_BATCHES,
                                                     M.MODERATE)
        # keys must be simple column refs after planning; planner projects
        # complex keys into columns first (reference does the same)
        self._lk_ix = tuple(self._key_ordinal(e, left.output)
                            for e in self.left_keys)
        self._rk_ix = tuple(self._key_ordinal(e, right.output)
                            for e in self.right_keys)
        # (build key ordinal, DynamicKeyFilter) pairs wired by the planner
        # for probe-side scan pruning (GpuSubqueryBroadcastExec analog):
        # filled with the build side's distinct keys right after build
        # materialization, strictly before the probe stream is pulled
        self.dpp_filters: list = []

    @staticmethod
    def _key_ordinal(e: Expression, schema: Schema) -> int:
        from ..expr.base import AttributeReference, BoundReference
        b = bind_references(e, schema)
        if isinstance(b, BoundReference):
            return b.ordinal
        raise ValueError("join keys must be column references after planning")

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        if self.zip_partitions:
            yield from self._zipped_execute()
            return
        # the probe side starts now, on a thread of its own, and this one
        # builds meanwhile: a scan's page walk and its decode program run
        # beside the build side's, where the chip used to wait for each
        # host step in turn (PERF.md, section 5). Not where the probe
        # side's scan waits for the build side's keys (dpp_filters).
        from .base import start_prefetch
        ahead = None if self.dpp_filters else start_prefetch(
            self._stream_batches(), self.conf, name="join-probe")
        try:
            yield from self._build_then_stream(
                self._stream_batches() if ahead is None else ahead)
        finally:
            if ahead is not None:
                ahead.close()

    def _build_then_stream(self, probes) -> Iterator[ColumnarBatch]:
        with self.build_time.timed():
            build_batches = list(self.children[1].execute())
            if not build_batches and self.join_type in ("inner", "right", "semi"):
                return
            if build_batches:
                build = concat_batches(build_batches)
            else:
                from ..columnar.batch import empty_batch
                build = empty_batch(self.children[1].output, 1)
            del build_batches

        if self.dpp_filters:
            n_build = int(build.row_count())
            for ordinal, filt in self.dpp_filters:
                vals, valid = build.columns[ordinal].to_numpy(n_build)
                if vals.dtype == object:  # strings
                    filt.set_values([v for v, ok in zip(vals, valid) if ok])
                else:
                    filt.set_values(vals[valid])

        threshold = self.conf.get("spark.rapids.sql.join.subPartition.rows")
        if int(build.row_count()) > threshold:
            yield from self._streamed_sub_partition(build, threshold, probes)
        else:
            yield from self._streamed_join(build, probes)

    def _stream_batches(self) -> Iterator[ColumnarBatch]:
        """Probe-side stream with streamTime/numInput accounting: the wait
        for each upstream batch is the streamed side's cost, distinct from
        joinTime (the probe kernels)."""
        for b in M.timed_pulls(self.children[0].execute(),
                               self.stream_time):
            self.num_input_batches.add(1)
            self.num_input_rows.add(b.row_count())
            yield b

    def _streamed_join(self, build: ColumnarBatch,
                       probes) -> Iterator[ColumnarBatch]:
        """Stream probe batches against the built table (`GpuHashJoin.doJoin`
        `GpuHashJoin.scala:950`): only one probe batch is device-resident at a
        time; the build side parks spillable between batches and the per-batch
        join runs under the OOM-retry seam (split halves the probe batch)."""
        from ..memory.retry import split_batch_halves, with_retry
        from ..memory.spillable import SpillableColumnarBatch
        sp_build = SpillableColumnarBatch(build)
        del build
        bmatched = None
        try:
            for probe in probes:
                if int(probe.row_count()) == 0:
                    continue

                def run(sp_probe):
                    b = sp_build.get_batch()
                    p = sp_probe.get_batch()
                    res = self._join_pair_core(p, b)
                    sp_probe.close()
                    return res

                sp = SpillableColumnarBatch(probe)
                try:
                    for out, bm in with_retry(sp, run, split_batch_halves):
                        if bm is not None:
                            bmatched = bm if bmatched is None \
                                else (bmatched | bm)
                        if int(out.row_count()) > 0:
                            self.num_output_rows.add(out.row_count())
                            yield self._count_output(out)
                finally:
                    sp.close()  # no-op on the success path (run closed it)
            if self.join_type in ("right", "full"):
                extra = self._unmatched_batch(sp_build.get_batch(), bmatched)
                if extra is not None:
                    self.num_output_rows.add(extra.row_count())
                    yield self._count_output(extra)
        finally:
            sp_build.close()

    def _streamed_sub_partition(self, build: ColumnarBatch, threshold: int,
                                probes) -> Iterator[ColumnarBatch]:
        """Oversized build side with a streamed probe
        (`GpuSubPartitionHashJoin.scala` analog): hash-split the build ONCE
        into P spillable key-aligned sub-partitions; each probe batch is split
        the same way and joined part-to-part. Matching keys land in the same
        part, so per-part joins compose exactly; right/full unmatched flags
        accumulate per part across the whole probe stream."""
        from ..memory.spillable import SpillableColumnarBatch
        n_build = int(build.row_count())
        p = 1
        while n_build // p > threshold and p < 64:
            p *= 2
        build_parts = [SpillableColumnarBatch(bb)
                       for bb in _hash_split(build, self._rk_ix, p)]
        del build
        bmatched = [None] * p
        try:
            for probe in probes:
                if int(probe.row_count()) == 0:
                    continue
                for i, pp in enumerate(_hash_split(probe, self._lk_ix, p)):
                    if int(pp.row_count()) == 0:
                        continue  # unmatched build rows surface at the end
                    bb = build_parts[i].get_batch()
                    out, bm = self._join_pair_core(pp, bb)
                    if bm is not None:
                        bmatched[i] = bm if bmatched[i] is None \
                            else (bmatched[i] | bm)
                    if int(out.row_count()) > 0:
                        self.num_output_rows.add(out.row_count())
                        yield self._count_output(out)
            if self.join_type in ("right", "full"):
                for i in range(p):
                    extra = self._unmatched_batch(build_parts[i].get_batch(),
                                                  bmatched[i])
                    if extra is not None:
                        self.num_output_rows.add(extra.row_count())
                        yield self._count_output(extra)
        finally:
            for sp in build_parts:
                sp.close()

    def _zipped_execute(self) -> Iterator[ColumnarBatch]:
        """Co-partitioned per-shard join: children are key-exchanges over the
        same mesh, so matching keys land in the same positional batch — join
        batch p with batch p (the distributed engine's shard-local join,
        `GpuShuffledHashJoinExec.scala:151` fed by the exchange). Shards
        stream INCREMENTALLY: one probe + one build batch device-resident
        at a time, never both whole exchange outputs (peak residency would
        otherwise be the entire exchange per chip)."""
        import itertools
        _END = object()
        threshold = self.conf.get("spark.rapids.sql.join.subPartition.rows")
        probe_it = self._stream_batches()

        def timed_build():
            it = self.children[1].execute()
            while True:
                with self.build_time.timed():
                    b = next(it, _END)
                if b is _END:
                    return
                yield b

        build_it = timed_build()
        for probe, build in itertools.zip_longest(probe_it, build_it,
                                                  fillvalue=_END):
            if probe is _END or build is _END:
                raise RuntimeError(
                    "zip_partitions requires positionally-aligned exchange "
                    "outputs (one stream ended early)")
            n_probe, n_build = int(probe.row_count()), int(build.row_count())
            if n_build == 0 and self.join_type in ("inner", "right", "semi"):
                continue
            if n_probe == 0:
                if n_build and self.join_type in ("right", "full"):
                    yield self._right_only(build)
                continue
            if n_build > threshold:
                yield from self._sub_partition_join(probe, build, threshold)
            else:
                yield from self._join_pair(probe, build)

    def _join_pair_core(self, probe: ColumnarBatch, build: ColumnarBatch):
        """One probe batch vs the built table. Returns (out_batch, bmatched)
        where bmatched is the device build-row matched mask (None unless
        right/full) — callers accumulate it across the probe stream."""
        # mesh shard batches are committed each to their own chip; a spill/
        # unspill cycle (or a broadcast build) can leave the two sides on
        # different devices, which jit rejects — align explicitly (no-op
        # probe for uniformly-placed inputs)
        from .coalesce import colocate_batches
        build, probe = colocate_batches([build, probe])
        with self.join_time.timed():
            counts, lo, order, pvalid, bvalid = _probe_counts(
                probe, build, self._lk_ix, self._rk_ix)
            total = jnp.sum(_slot_counts(
                jnp, counts, probe.row_mask(), self.join_type))
            # the host blocks here until the probe has run
            with spans.span("sync.join_size", add={"host_sync_count": 1}):
                total = int(total)
            if self.join_type in ("semi", "anti", "existence"):
                out_cap = max(row_bucket(max(total, 1), op="join"), probe.capacity)
            else:
                out_cap = row_bucket(max(total, 1), op="join")
            out_vecs, n, bmatched, cond_errs = _expand_join(
                probe, build, counts, lo, order, pvalid, bvalid,
                self._lk_ix, self._rk_ix, out_cap,
                self.join_type, self._bcond, self.conf.is_ansi)
            if self._bcond is not None:
                from .base import raise_kernel_errors
                raise_kernel_errors(cond_errs, self._bcond.err_msgs)
            out = vecs_to_batch(self._schema, out_vecs, n)
        if self.join_type not in ("right", "full"):
            bmatched = None
        return out, bmatched

    def _join_pair(self, probe: ColumnarBatch,
                   build: ColumnarBatch) -> Iterator[ColumnarBatch]:
        """Join one disjoint (probe, build) pair and emit its unmatched build
        rows immediately — correct only when this build slice meets no other
        probe rows (zipped per-shard and sub-partition pair joins)."""
        out, bmatched = self._join_pair_core(probe, build)
        self.num_output_rows.add(out.row_count())
        yield self._count_output(out)

        if self.join_type in ("right", "full"):
            extra = self._unmatched_batch(build, bmatched)
            if extra is not None:
                self.num_output_rows.add(extra.row_count())
                yield self._count_output(extra)

    def _sub_partition_join(self, probe: ColumnarBatch, build: ColumnarBatch,
                            threshold: int) -> Iterator[ColumnarBatch]:
        """Oversized build side (GpuSubPartitionHashJoin.scala analog): hash
        both sides into P key-aligned sub-partitions and join pairwise —
        matching keys land in the same sub-partition, so pair joins compose
        exactly (including outer/semi/anti, which are per-key-group). Each
        pair's working set is ~1/P of the whole, parked spillable between
        pairs."""
        from ..memory.spillable import SpillableColumnarBatch
        n_build = int(build.row_count())
        p = 1
        while n_build // p > threshold and p < 64:
            p *= 2
        probe_parts = _hash_split(probe, self._lk_ix, p)
        build_parts = _hash_split(build, self._rk_ix, p)
        pairs = [(SpillableColumnarBatch(pb), SpillableColumnarBatch(bb))
                 for pb, bb in zip(probe_parts, build_parts)]
        for sp_probe, sp_build in pairs:
            pb = sp_probe.get_batch()
            bb = sp_build.get_batch()
            if int(pb.row_count()) == 0 and int(bb.row_count()) == 0:
                sp_probe.close()
                sp_build.close()
                continue
            yield from self._join_pair(pb, bb)
            sp_probe.close()
            sp_build.close()

    def _unmatched_batch(self, build, bmatched):
        if bmatched is None:  # no probe batch ever touched this build slice
            bmatched = jnp.zeros(build.capacity, dtype=bool)
        rvecs, n = _unmatched_build(build, len(self.children[0].output.types),
                                    bmatched)
        if int(n) == 0:
            return None
        return self._null_left_batch(rvecs, n, build.capacity)

    def _right_only(self, build: ColumnarBatch) -> ColumnarBatch:
        rvecs = batch_vecs(build)
        return self._null_left_batch(rvecs, build.num_rows, build.capacity)

    def _null_left_batch(self, rvecs: List[Vec], n, cap: int) -> ColumnarBatch:
        lvecs = _null_vecs(self.children[0].output, cap)
        return vecs_to_batch(self._schema, lvecs + rvecs, n)

    def _arg_string(self):
        return f"[{self.join_type}, keys={[repr(e) for e in self.left_keys]}]"


@sjit(op="exec.join.hash_pid", static_argnums=(1, 2))
def _hash_pid(batch: ColumnarBatch, key_ix: Tuple[int, ...], p: int):
    vecs = batch_vecs(batch)
    keys = [vecs[i] for i in key_ix]
    h = hash_vecs(jnp, keys).astype(jnp.uint32)
    return jnp.where(batch.row_mask(), (h % p).astype(jnp.int32),
                     jnp.int32(-1))


def _hash_split(batch: ColumnarBatch, key_ix: Tuple[int, ...],
                p: int) -> List[ColumnarBatch]:
    from .exchange import _slice_partition
    pid = _hash_pid(batch, key_ix, p)
    return [_slice_partition(batch, pid, q) for q in range(p)]


def _slice_rows(batch: ColumnarBatch, lo: int, hi: int) -> ColumnarBatch:
    """Host-slice a device batch to rows [lo, hi); logical count clamps."""
    n = int(batch.row_count())
    vecs = [v.slice_rows(lo, hi) for v in batch_vecs(batch)]
    return vecs_to_batch(batch.schema, vecs, max(0, min(n - lo, hi - lo)))


def _null_vecs(schema: Schema, cap: int) -> List[Vec]:
    """All-null columns for one side of an outer join at the given capacity."""
    from ..expr.base import zero_vec
    return [zero_vec(jnp, dt, (cap,)) for dt in schema.types]


@sjit(op="exec.join.nl_matched", static_argnums=(2, 3))
def _nl_matched(probe: ColumnarBatch, bchunk: ColumnarBatch, cond,
                ansi: bool = False):
    """All-pairs tile: matched mask over the P x C grid (flattened row-major),
    plus per-probe-row / per-build-row any-match, the total, and the ANSI
    error flags from the condition (live pairs only)."""
    xp = jnp
    P, C = probe.capacity, bchunk.capacity
    pi = xp.repeat(xp.arange(P, dtype=np.int32), C)
    bi = xp.tile(xp.arange(C, dtype=np.int32), P)
    m = probe.row_mask()[pi] & bchunk.row_mask()[bi]
    cond_errs = ()
    if cond is not None:
        from ..expr.base import EvalContext
        from .base import kernel_errors
        gp = gather_vecs(xp, batch_vecs(probe), pi)
        gb = gather_vecs(xp, batch_vecs(bchunk), bi)
        cctx = EvalContext(xp, ansi=ansi, errors=[], row_mask=m)
        cv = cond.expr.eval(cctx, gp + gb)
        m = m & cv.data.astype(bool) & cv.validity
        cond_errs = kernel_errors(cctx, cond.err_msgs)
    grid = m.reshape(P, C)
    return m, grid.any(axis=1), grid.any(axis=0), \
        xp.sum(m).astype(np.int32), cond_errs


@sjit(op="exec.join.nl_expand", static_argnums=(2,))
def _nl_expand(probe: ColumnarBatch, bchunk: ColumnarBatch, out_cap: int,
               matched):
    """Gather the surviving pairs of an all-pairs tile into output columns."""
    xp = jnp
    P, C = probe.capacity, bchunk.capacity
    pi = xp.repeat(xp.arange(P, dtype=np.int32), C)
    bi = xp.tile(xp.arange(C, dtype=np.int32), P)
    order = stable_lexsort(xp, [(~matched).astype(np.int8)])[:out_cap]
    n = xp.sum(matched).astype(np.int32)
    left_out = gather_vecs(xp, batch_vecs(probe), pi[order])
    right_out = gather_vecs(xp, batch_vecs(bchunk), bi[order])
    return left_out + right_out, n


@sjit(op="exec.join.compact_rows")
def _compact_rows(batch: ColumnarBatch, want):
    return compact_vecs(jnp, batch_vecs(batch), want & batch.row_mask())


class TpuNestedLoopJoinExec(TpuExec):
    """Nested-loop / cartesian join (reference
    `GpuBroadcastNestedLoopJoinExecBase.scala:1`, `GpuCartesianProductExec.scala:1`,
    ExistenceJoin in `GpuHashJoin.scala`): every probe row meets every build row,
    filtered by an optional condition. TPU shape: the build (right) side is
    materialized once (broadcast analog) and host-sliced into fixed-capacity
    chunks; each streamed probe batch is joined against each chunk as a bounded
    P x C all-pairs tile, so XLA only ever sees static tile shapes. Matched
    flags accumulate per probe batch (left/semi/anti/existence) and per build
    chunk across the stream (right/full)."""

    TILE_BUDGET = 1 << 20   # max pairs per tile
    PROBE_TILE_ROWS = 4096  # probe rows per tile; C = TILE_BUDGET / this

    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Expression = None, join_type: str = "inner",
                 conf=None):
        super().__init__([left, right], conf)
        self.join_type = "inner" if join_type == "cross" else join_type
        self.condition = condition
        lo, ro = left.output, right.output
        combined = Schema(lo.names + ro.names, lo.types + ro.types)
        self._schema = join_output_schema(lo, ro, self.join_type)
        self._bcond = None if condition is None else _StaticExpr(
            bind_references(condition, combined))
        self.join_time = self.metrics.create(M.JOIN_TIME, M.ESSENTIAL)
        self.build_time = self.metrics.create(M.BUILD_TIME, M.MODERATE)

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self) -> Iterator[ColumnarBatch]:
        from ..columnar.batch import empty_batch
        from ..memory.spillable import SpillableColumnarBatch
        with self.build_time.timed():
            build_batches = list(self.children[1].execute())
            if not build_batches and self.join_type in ("inner", "right", "semi"):
                return
            build = concat_batches(build_batches) if build_batches else \
                empty_batch(self.children[1].output, 1)
            del build_batches
        chunks = [SpillableColumnarBatch(c) for c in self._slice_build(build)]
        del build
        bmatched = [None] * len(chunks)
        jt = self.join_type
        pt = self.PROBE_TILE_ROWS
        try:
            for whole_probe in self.children[0].execute():
                if int(whole_probe.row_count()) == 0:
                    continue
                # tile the probe side too: each row-slice is an independent
                # probe unit (tails are per-row, rows are disjoint), keeping
                # every P x C tile within TILE_BUDGET regardless of how the
                # upstream coalesce sized the batch
                pcap = whole_probe.capacity
                probes = [whole_probe] if pcap <= pt else \
                    [_slice_rows(whole_probe, lo, min(lo + pt, pcap))
                     for lo in range(0, pcap, pt)]
                for probe in probes:
                    pmatched = None
                    for ci, sp in enumerate(chunks):
                        bchunk = sp.get_batch()
                        with self.join_time.timed():
                            m, pm, bm, total, cerrs = _nl_matched(
                                probe, bchunk, self._bcond,
                                self.conf.is_ansi)
                            if self._bcond is not None:
                                from .base import raise_kernel_errors
                                raise_kernel_errors(cerrs,
                                                    self._bcond.err_msgs)
                            pmatched = pm if pmatched is None \
                                else (pmatched | pm)
                            if jt in ("right", "full"):
                                bmatched[ci] = bm if bmatched[ci] is None \
                                    else (bmatched[ci] | bm)
                            if jt in ("semi", "anti", "existence"):
                                continue  # only flags needed
                            n_total = int(total)
                            if n_total == 0:
                                continue
                            out_vecs, n = _nl_expand(probe, bchunk,
                                                     row_bucket(n_total, op="join"), m)
                        yield self._emit(vecs_to_batch(self._schema,
                                                       out_vecs, n))
                    yield from self._emit_probe_tail(probe, pmatched)
            if jt in ("right", "full"):
                for ci, sp in enumerate(chunks):
                    extra = self._unmatched_chunk(sp.get_batch(), bmatched[ci])
                    if extra is not None:
                        yield self._emit(extra)
        finally:
            for sp in chunks:
                sp.close()

    def _slice_build(self, build: ColumnarBatch) -> List[ColumnarBatch]:
        """Host-slice the build table into capacity-C chunks; C is sized so a
        PROBE_TILE_ROWS x C tile stays within TILE_BUDGET pairs."""
        bcap = build.capacity
        c = max(1, min(bcap, self.TILE_BUDGET // self.PROBE_TILE_ROWS))
        return [_slice_rows(build, lo, min(lo + c, bcap))
                for lo in range(0, max(bcap, 1), c)]

    def _emit_probe_tail(self, probe: ColumnarBatch,
                         pmatched) -> Iterator[ColumnarBatch]:
        """Per-probe-batch epilogue once every build chunk was seen."""
        xp = jnp
        jt = self.join_type
        pcap = probe.capacity
        if pmatched is None:
            pmatched = xp.zeros(pcap, dtype=bool)
        if jt in ("left", "full"):
            vecs, n = _compact_rows(probe, ~pmatched)
            if int(n) == 0:
                return
            rschema = self.children[1].output
            yield self._emit(vecs_to_batch(
                self._schema, vecs + _null_vecs(rschema, pcap), n))
        elif jt in ("semi", "anti"):
            want = pmatched if jt == "semi" else ~pmatched
            vecs, n = _compact_rows(probe, want)
            if int(n) == 0:
                return
            yield self._emit(vecs_to_batch(self._schema, vecs, n))
        elif jt == "existence":
            exists = Vec(T.BooleanType(), pmatched, xp.ones(pcap, dtype=bool))
            vecs, n = compact_vecs(xp, batch_vecs(probe) + [exists],
                                   probe.row_mask())
            yield self._emit(vecs_to_batch(self._schema, vecs, n))

    def _unmatched_chunk(self, bchunk: ColumnarBatch, bmatched):
        xp = jnp
        if bmatched is None:
            bmatched = xp.zeros(bchunk.capacity, dtype=bool)
        vecs, n = _compact_rows(bchunk, ~bmatched)
        if int(n) == 0:
            return None
        lschema = self.children[0].output
        return vecs_to_batch(self._schema,
                             _null_vecs(lschema, bchunk.capacity) + vecs, n)

    def _emit(self, out: ColumnarBatch) -> ColumnarBatch:
        self.num_output_rows.add(out.row_count())
        return self._count_output(out)

    def _arg_string(self):
        cond = "" if self.condition is None else f", cond={self.condition!r}"
        return f"[{self.join_type}{cond}]"


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Broadcast variant (reference GpuBroadcastHashJoinExecBase): identical device
    join; the build child is a broadcast exchange that replicates the build table
    (in-process in local mode; all_gather over the mesh in distributed mode)."""
