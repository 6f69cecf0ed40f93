"""Pallas TPU kernel: EXACT int64 segmented sum via 16-bit limb MXU
matmuls (ISSUE-16; follows the ops/pallas_segsum.py idiom).

The fused stage's terminal partial aggregate spends its inner loop in
`segment_sum` over int64 contributions (sums, counts, count-if). XLA
lowers that to an emulated-i64 scatter-add; this kernel reformulates it as
one-hot MXU matmuls — but unlike the f64 sibling it must be BIT-exact
(fusion on/off identity is a hard gate), so the value split is four 16-bit
limbs, not hi/lo floats:

  * each limb is an integer in [0, 65535]; one dot accumulates LANES=256
    of them in f32, maxing at 256 * 65535 = 16,776,960 < 2^24 — every
    partial is an exactly-representable f32 integer;
  * per-block partials are combined OUTSIDE the kernel in int64, then the
    limbs recombine with uint64 shifts — modular wraparound matching
    jnp int64 semantics exactly.

Engaged only while the fused stage traces an aggregate member (the
`ops.rowops._FUSED_SEGMENT_SUM` hook); `fused_segment_sum` falls back to
`jax.ops.segment_sum` outside the kernel's applicability window (segment
count above MAX_SEGMENTS, non-int64, x64 disabled), so engagement is
always safe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compile import sjit
from . import pallas_mode

__all__ = ["segment_sum_i64", "fused_segment_sum", "MAX_SEGMENTS"]

SUB = 8        # sublanes per DMA block
LANES = 256    # rows per dot
CHUNK = SUB * LANES
MAX_SEGMENTS = 4096  # one-hot tile [LANES, G] must fit VMEM comfortably

_TWO = np.int32(2)
_ONE = np.int32(1)


def _make_kernel(n_blocks: int, g: int):
    def kernel(g_hbm, l0_hbm, l1_hbm, l2_hbm, l3_hbm, out_hbm):
        def body(gbuf, l0buf, l1buf, l2buf, l3buf, obuf, insem, outsem):
            iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, g), 1)
            lrefs = [l0buf, l1buf, l2buf, l3buf]

            def in_dma(slot, b):
                return [pltpu.make_async_copy(
                    r.at[pl.ds(b * np.int32(SUB), SUB), :],
                    buf.at[slot], insem.at[slot, np.int32(k)])
                    for k, (r, buf) in enumerate(
                        [(g_hbm, gbuf), (l0_hbm, l0buf), (l1_hbm, l1buf),
                         (l2_hbm, l2buf), (l3_hbm, l3buf)])]

            for d in in_dma(np.int32(0), np.int32(0)):
                d.start()

            def step(b):
                slot = jax.lax.rem(b, _TWO)

                @pl.when(b + _ONE < np.int32(n_blocks))
                def _():
                    for d in in_dma(jax.lax.rem(b + _ONE, _TWO), b + _ONE):
                        d.start()

                for d in in_dma(slot, b):
                    d.wait()
                rows = []
                for j in range(SUB):
                    oh = (gbuf[slot, np.int32(j), :][:, None] == iota
                          ).astype(jnp.float32)
                    v4 = jnp.concatenate(
                        [lr[slot, np.int32(j), :][None, :] for lr in lrefs],
                        axis=0)
                    rows.append(jax.lax.dot_general(
                        v4, oh, (((1,), (0,)), ((), ())),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32))

                @pl.when(b >= _TWO)
                def _():
                    pltpu.make_async_copy(obuf.at[slot],
                                          out_hbm.at[b - _TWO],
                                          outsem.at[slot]).wait()

                obuf[slot] = jnp.concatenate(rows, axis=0)
                pltpu.make_async_copy(obuf.at[slot], out_hbm.at[b],
                                      outsem.at[slot]).start()
                return b + _ONE

            jax.lax.while_loop(lambda b: b < np.int32(n_blocks), step,
                               jnp.int32(0))
            for off in (2, 1):
                if n_blocks - off >= 0:
                    i = np.int32(n_blocks - off)
                    pltpu.make_async_copy(obuf.at[i % 2], out_hbm.at[i],
                                          outsem.at[i % 2]).wait()

        pl.run_scoped(
            body,
            gbuf=pltpu.VMEM((2, SUB, LANES), jnp.int32),
            l0buf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            l1buf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            l2buf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            l3buf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            obuf=pltpu.VMEM((2, 4 * SUB, g), jnp.float32),
            insem=pltpu.SemaphoreType.DMA((2, 5)),
            outsem=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


@sjit(op="ops.pallas_groupby.segment_sum", static_argnums=(2,))
def segment_sum_i64(values, segment_ids, num_segments: int):
    """Bit-exact int64 segmented sum of `values` by `segment_ids`
    (unsorted). num_segments must be static and <= MAX_SEGMENTS; rows with
    ids outside [0, num_segments) contribute nothing — exactly
    `jax.ops.segment_sum` semantics including int64 wraparound."""
    if num_segments > MAX_SEGMENTS:
        raise ValueError(f"num_segments {num_segments} > {MAX_SEGMENTS}")
    g = max(128, -(-num_segments // 128) * 128)  # lane-pad the one-hot
    n = values.shape[0]
    nb = max(1, -(-n // CHUNK))
    pad = nb * CHUNK - n
    # range-check ids BEFORE narrowing (an id >= 2^31 must drop, not wrap)
    in_range = (segment_ids >= 0) & (segment_ids < num_segments)
    ids = jnp.where(in_range, segment_ids, -1).astype(jnp.int32)
    u = values.astype(jnp.uint64)
    limbs = [((u >> np.uint64(16 * k)) & np.uint64(0xFFFF))
             .astype(jnp.float32) for k in range(4)]
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=-1)  # no one-hot match
        limbs = [jnp.pad(l, (0, pad)) for l in limbs]
    parts = pl.pallas_call(
        _make_kernel(nb, g),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nb, 4 * SUB, g), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(ids.reshape(nb * SUB, LANES),
      *[l.reshape(nb * SUB, LANES) for l in limbs])
    # per-dot f32 partials are exact integers < 2^24; everything after is
    # integer arithmetic
    per_limb = parts.astype(jnp.int64).reshape(nb, SUB, 4, g).sum(axis=(0, 1))
    tot = jnp.zeros((g,), jnp.uint64)
    for k in range(4):
        tot = tot + (per_limb[k].astype(jnp.uint64) << np.uint64(16 * k))
    return tot[:num_segments].astype(jnp.int64)


def fused_segment_sum(contrib, gid, cap: int):
    """`ops.rowops._FUSED_SEGMENT_SUM` target: the pallas kernel inside
    its exactness window, `jax.ops.segment_sum` outside it."""
    if (not jax.config.jax_enable_x64 or cap > MAX_SEGMENTS
            or contrib.ndim != 1 or contrib.dtype != jnp.int64):
        return jax.ops.segment_sum(contrib, gid, num_segments=cap)
    return segment_sum_i64(contrib, gid, cap)
