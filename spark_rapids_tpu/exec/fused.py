"""Whole-stage fused execution (ISSUE-16 tentpole): N fusible operators,
ONE device program per batch.

`plan/fusion.py` replaces a maximal chain of filter / project / broadcast-
join-probe / terminal-partial-aggregate operators with one
`TpuFusedStageExec`. Its kernel calls each member's EXISTING kernel
function inline inside one trace, so the whole chain lowers to a single
XLA program: bit-identity with the unfused chain holds by construction
(same expression evaluators, same compaction, same join expand, same
aggregate math), while intermediates stay traced values instead of
materialising as per-operator ColumnarBatches, and a batch pays ONE
dispatch instead of one per operator.

Mechanics worth knowing:

  * ANSI boxes ride the compile service's StaticExpr seam: each member's
    host message box is wrapped in a StaticExpr passed as a static arg of
    the fused program, so the persistent tier snapshots and restores every
    member's messages with the ONE fused entry (`service._split`), and the
    host re-raises member errors in member (stream) order after each run.
  * Join expand needs a static output capacity. The fused program computes
    the exact slot total IN-trace and returns it; the host checks
    `total <= cap` after the (single) dispatch — the same one-sync-per-
    batch the unfused join pays — and on overflow re-dispatches with a
    grow-only capacity (a new program keyed by the new caps).
  * Project row offsets thread through the program as dynamic int64
    scalars and come back updated, so global-ordinal expressions
    (monotonically_increasing_id style) see the same stream offsets as the
    unfused exec.
  * Runtime shapes the plan could not see (oversized broadcast build that
    needs the sub-partition host loop) degrade the WHOLE stage to the
    original member chain — members keep their child links; the fused node
    only replaced them in the plan.
  * Pallas kernels (`ops/pallas_probe.py`, `ops/pallas_groupby.py`) serve
    the two hot inner loops when engaged (`spark.rapids.tpu.fusion.pallas
    .mode`): the murmur3 hash feeding the join's one probe, and the
    exact int64 group-by accumulate. Both are bit-exact integer paths with
    jnp fallbacks, so fusion on/off identity is preserved either way.
"""

from __future__ import annotations

from typing import Iterator, List

import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch, Schema, empty_batch
from ..columnar.padding import row_bucket
from ..compile import instance_jit, kernel_key
from ..utils.metrics import TaskMetrics
from .aggregate import TpuHashAggregateExec
from .base import StaticExpr, TpuExec, raise_kernel_errors, vecs_to_batch
from .basic import TpuFilterExec, TpuProjectExec
from .coalesce import colocate_batches, concat_batches
from .joins import (TpuBroadcastHashJoinExec, _expand_join, _probe_counts,
                    _slot_counts)

__all__ = ["TpuFusedStageExec"]


def _raw(fn):
    """The undecorated kernel function of a ServiceJit (members are always
    jitted — the planner excludes eager/black-box members). Calling the raw
    function traces the member body inline into the fused program with no
    nested-jit cache whose trace could have been taken under different
    module state (the pallas group-by hook)."""
    return getattr(fn, "fn", fn)


class TpuFusedStageExec(TpuExec):
    """One fused pipeline stage. children = [source] + build exchanges (in
    member order), so planner walks, distribution bookkeeping and rescache
    fingerprints see the real dataflow; the member execs stay linked
    beneath as the degrade path."""

    def __init__(self, members: List[TpuExec], spec, conf=None):
        source = members[0].children[0]
        builds = [m.children[1] for m in members
                  if isinstance(m, TpuBroadcastHashJoinExec)]
        super().__init__([source] + builds, conf)
        self._members = list(members)
        self.spec = spec
        # public expression surface: the result-relevant expressions of
        # every member, so fingerprint._check_deterministic fails closed on
        # rand()/UDF-bearing members exactly as it does unfused
        self.member_exprs = [self._exprs_of(m) for m in members]
        self._schema = members[-1].output
        self._join_members = [m for m in members
                              if isinstance(m, TpuBroadcastHashJoinExec)]
        self._proj_members = [m for m in members
                              if isinstance(m, TpuProjectExec)]
        # grow-only expand capacity per join member (None = size off the
        # first batch); a grown cap keys a new fused program
        self._join_caps: list = [None] * len(self._join_members)
        self._kernels: dict = {}  # caps tuple -> ServiceJit
        self._statics, self._boxes = self._build_statics(members)
        from ..plan.fusion import KEY_PALLAS
        mode = str(self.conf.get(KEY_PALLAS))
        import jax
        self._pallas = mode == "force" or (
            mode == "auto" and jax.default_backend() == "tpu")
        # under pallas mode the probe's murmur3 row hash runs through
        # ops/pallas_probe (bit-exact); None keeps `expr.hashing.hash_vecs`
        self._hash_rows = None
        if self._pallas:
            from ..ops.pallas_probe import hash_vecs_pallas
            self._hash_rows = hash_vecs_pallas

    @staticmethod
    def _exprs_of(m) -> list:
        if isinstance(m, TpuProjectExec):
            return list(m.exprs)
        if isinstance(m, TpuFilterExec):
            return [m.condition]
        if isinstance(m, TpuBroadcastHashJoinExec):
            cond = [m.condition] if m.condition is not None else []
            return list(m.left_keys) + list(m.right_keys) + cond
        return list(m.group_exprs) + [a.func.child for a in m.aggs
                                      if a.func.child is not None]

    @staticmethod
    def _build_statics(members):
        """Per-member (static identity, ANSI box) pairs. The StaticExprs'
        err_msgs ARE the members' live boxes, so the compile service
        persists/restores them with the fused entry; boxes align 1:1 with
        the kernel's per-member error-flag tuples."""
        statics, boxes = [], []
        for m in members:
            if isinstance(m, TpuBroadcastHashJoinExec):
                if m._bcond is not None:
                    statics.append(m._bcond)
                    boxes.append(m._bcond.err_msgs)
                else:
                    boxes.append([])
                continue
            if isinstance(m, TpuProjectExec):
                ident, box = tuple(m._bound), m._err_msgs
            elif isinstance(m, TpuFilterExec):
                ident, box = m._bound, m._err_msgs
            else:  # partial aggregate
                ident = m._agg_kernel_key(False, True)
                box = m._kernel_boxes.get(m._kernel, m._err_msgs)
            se = StaticExpr(ident)
            se.err_msgs = box  # share the member's live box
            statics.append(se)
            boxes.append(box)
        return tuple(statics), boxes

    @property
    def members(self) -> List[TpuExec]:
        return list(self._members)

    @property
    def output(self) -> Schema:
        return self._schema

    def _arg_string(self):
        return f"[{self.spec!r}]"

    # ---- the fused program -------------------------------------------------

    def _agg_kernel(self, m, batch):
        """Trace the member aggregate kernel; with pallas engaged, the
        exact int64 segmented sum (ops/pallas_groupby) is installed for the
        duration of THIS trace only — the unfused/degrade traces never see
        it."""
        if not self._pallas:
            return _raw(m._kernel)(batch)
        from ..ops import rowops
        from ..ops.pallas_groupby import fused_segment_sum
        prev = rowops._FUSED_SEGMENT_SUM
        rowops._FUSED_SEGMENT_SUM = fused_segment_sum
        try:
            return _raw(m._kernel)(batch)
        finally:
            rowops._FUSED_SEGMENT_SUM = prev

    def _make_kernel(self, caps):
        members = self._members
        ns = len(self._statics)
        n_proj = len(self._proj_members)

        def kernel(*args):
            # args[:ns] are the member StaticExprs — identity + persistent
            # ANSI-box carriers only; the live objects are in the closure
            batch = args[ns]
            offsets = list(args[ns + 1: ns + 1 + n_proj])
            builds = list(args[ns + 1 + n_proj:])
            out = batch
            new_offsets, totals, errs_all = [], [], []
            pi = ji = 0
            for m in members:
                if isinstance(m, TpuBroadcastHashJoinExec):
                    probe, build = out, builds[ji]
                    # one probe per join: its arrays size the expand (the
                    # total the host checks) and feed it
                    phase1 = _raw(_probe_counts)(
                        probe, build, m._lk_ix, m._rk_ix, self._hash_rows)
                    totals.append(jnp.sum(_slot_counts(
                        jnp, phase1[0], probe.row_mask(),
                        m.join_type)).astype(jnp.int32))
                    out_vecs, n, _bm, cond_errs = _raw(_expand_join)(
                        probe, build, *phase1, m._lk_ix, m._rk_ix, caps[ji],
                        m.join_type, m._bcond, m.conf.is_ansi)
                    out = vecs_to_batch(m._schema, out_vecs, n)
                    errs_all.append(tuple(cond_errs))
                    ji += 1
                elif isinstance(m, TpuProjectExec):
                    # advance by the member's INPUT batch rows (a traced
                    # value here), like the unfused host loop does
                    in_rows = jnp.asarray(out.num_rows, jnp.int64)
                    out, errs = _raw(m._kernel)(out, offsets[pi])
                    new_offsets.append(offsets[pi] + in_rows)
                    errs_all.append(tuple(errs))
                    pi += 1
                elif isinstance(m, TpuFilterExec):
                    out, errs = _raw(m._kernel)(out)
                    errs_all.append(tuple(errs))
                else:  # terminal partial aggregate
                    out, errs = self._agg_kernel(m, out)
                    errs_all.append(tuple(errs))
            return out, tuple(new_offsets), tuple(totals), tuple(errs_all)

        return instance_jit(
            kernel, op="exec.fused_stage",
            key=kernel_key(self.spec, caps, self._pallas, conf=self.conf),
            static_argnums=tuple(range(ns)))

    # ---- host loop ---------------------------------------------------------

    def _materialize_build(self, i: int, m):
        """Build side of join member i, once per stage (the broadcast
        exchange's blob is shared with any unfused consumer). Mirrors the
        unfused empty-build semantics. Returns None when the stage provably
        emits nothing (inner/semi on an empty build)."""
        bb = list(self.children[1 + i].execute())
        if not bb and m.join_type in ("inner", "semi"):
            return None
        if not bb:
            return empty_batch(self.children[1 + i].output, 1)
        return concat_batches(bb) if len(bb) > 1 else bb[0]

    def _degraded(self) -> Iterator[ColumnarBatch]:
        # exact unfused chain: members kept their original child links
        yield from self._members[-1].execute()

    def _caps_for(self, batch) -> tuple:
        # members below a join preserve batch capacity, so the source cap
        # is the probe cap for the first-batch guess; overflow re-dispatch
        # corrects optimistic guesses and never shrinks
        for i in range(len(self._join_caps)):
            if self._join_caps[i] is None:
                self._join_caps[i] = row_bucket(max(int(batch.capacity), 1),
                                                op="join")
        return tuple(self._join_caps)

    def do_execute(self) -> Iterator[ColumnarBatch]:
        joins = self._join_members
        builds = []
        threshold = self.conf.get("spark.rapids.sql.join.subPartition.rows")
        for i, m in enumerate(joins):
            build = self._materialize_build(i, m)
            if build is None:
                return
            if int(build.row_count()) > threshold:
                # the sub-partition join is a host-iterative loop by
                # design — run this stage through the unfused members
                yield from self._degraded()
                return
            builds.append(build)

        tm = TaskMetrics.get()
        tm.fused_stages += 1
        tm.fused_ops += len(self._members)

        offsets = [jnp.asarray(0, jnp.int64)] * len(self._proj_members)
        for b in self.children[0].execute():
            if builds:
                placed = colocate_batches(builds + [b])
                builds, b = placed[:-1], placed[-1]
            while True:
                caps = self._caps_for(b)
                kern = self._kernels.get(caps)
                if kern is None:
                    kern = self._make_kernel(caps)
                    self._kernels[caps] = kern
                with self.op_time.timed():
                    out, new_offsets, totals, errs = kern(
                        *self._statics, b, *offsets, *builds)
                # the one per-batch host sync joins always pay: expand
                # capacities. Overflow re-dispatches at a grown cap (same
                # inputs -> same lower-member results and error flags).
                grown = False
                for i, t in enumerate(totals):
                    t = int(t)
                    if t > self._join_caps[i]:
                        self._join_caps[i] = max(
                            row_bucket(max(t, 1), op="join"),
                            self._join_caps[i])
                        grown = True
                if not grown:
                    break
            offsets = list(new_offsets)
            # member (stream) order, like the unfused chain raises
            for flags, box in zip(errs, self._boxes):
                raise_kernel_errors(flags, box)
            if joins and int(out.row_count()) == 0:
                # unfused joins drop empty probe batches and empty join
                # outputs; join-free chains keep 1:1 batch alignment
                continue
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)
