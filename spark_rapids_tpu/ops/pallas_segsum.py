"""Pallas TPU kernel: float64 segmented sum via MXU one-hot matmuls.

The motivating cost (bench.py): XLA lowers `segment_sum` on f64 to an
emulated-f64 scatter-add — measured 2.40s for 8 passes over 4M rows on a
v5e chip. This kernel reformulates the reduction as MXU matmuls against
per-chunk one-hot matrices with a two-float (hi/lo) value split, writing
per-chunk f32 partials that are combined in f64 OUTSIDE the kernel:

  * per 2048-row chunk, each group receives only ~chunk/num_groups values,
    so the f32 MXU accumulation within a chunk is near-exact;
  * cross-chunk combination happens in f64 (dense adds — fast even emulated);
  * measured: 0.15s for the same 8 passes (16x) at ~1e-9 relative error
    (the pure-XLA f32 one-hot alternative is 2e-6).

Kernel structure notes (hard-won against an earlier TPU toolchain):
  * gridded pallas_call does not legalize through this toolchain — the kernel
    is a SINGLE invocation with an internal while_loop and double-buffered
    manual DMA (HBM -> VMEM in, VMEM -> HBM out);
  * every scalar index must be int32: under jax x64, python ints become i64
    scalars which Mosaic's memref_slice rejects (and an i64 fori_loop index
    sends the MLIR lowering into infinite recursion);
  * dots need precision=HIGHEST or Mosaic emits low-pass bf16 matmuls
    (observed 8e-5 relative error).

Applicability: num_segments must be a small static bound (the one-hot tile is
[LANES, G] in VMEM) — the shape of plan-level aggregations with known small
group counts and of the benchmark pipeline; the general aggregate exec keeps
the sort+segmented path for unbounded group counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compile import sjit
from . import pallas_mode

__all__ = ["segment_sum_f64", "MAX_SEGMENTS"]

SUB = 8        # sublanes per DMA block
LANES = 256    # rows per dot
CHUNK = SUB * LANES
MAX_SEGMENTS = 4096  # one-hot tile [LANES, G] must fit VMEM comfortably

_TWO = np.int32(2)
_ONE = np.int32(1)


def _make_kernel(n_blocks: int, g: int):
    def kernel(g_hbm, hi_hbm, lo_hbm, out_hbm):
        def body(gbuf, hibuf, lobuf, obuf, insem, outsem):
            iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, g), 1)

            def in_dma(slot, b):
                return [pltpu.make_async_copy(
                    r.at[pl.ds(b * np.int32(SUB), SUB), :],
                    buf.at[slot], insem.at[slot, np.int32(k)])
                    for k, (r, buf) in enumerate(
                        [(g_hbm, gbuf), (hi_hbm, hibuf), (lo_hbm, lobuf)])]

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
                    v2 = jnp.concatenate(
                        [hibuf[slot, np.int32(j), :][None, :],
                         lobuf[slot, np.int32(j), :][None, :]], axis=0)
                    rows.append(jax.lax.dot_general(
                        v2, oh, (((1,), (0,)), ((), ())),
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
            hibuf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            lobuf=pltpu.VMEM((2, SUB, LANES), jnp.float32),
            obuf=pltpu.VMEM((2, 2 * SUB, g), jnp.float32),
            insem=pltpu.SemaphoreType.DMA((2, 3)),
            outsem=pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


@sjit(op="ops.segment_sum_f64", static_argnums=(2,))
def segment_sum_f64(values, segment_ids, num_segments: int):
    """f64 segmented sum of `values` by int32 `segment_ids` (unsorted).
    num_segments must be static and <= MAX_SEGMENTS. Rows with ids outside
    [0, num_segments) contribute nothing. Accuracy ~1e-9 relative (two-float
    split + per-chunk f32 MXU accumulation + f64 cross-chunk combine)."""
    if num_segments > MAX_SEGMENTS:
        raise ValueError(f"num_segments {num_segments} > {MAX_SEGMENTS}")
    g = max(128, -(-num_segments // 128) * 128)  # lane-pad the one-hot
    n = values.shape[0]
    nb = max(1, -(-n // CHUNK))
    pad = nb * CHUNK - n
    v64 = values.astype(jnp.float64)
    # range-check ids BEFORE narrowing: an int64 id >= 2^31 must drop, not
    # wrap onto a valid segment
    in_range = (segment_ids >= 0) & (segment_ids < num_segments)
    ids = jnp.where(in_range, segment_ids, -1).astype(jnp.int32)
    # values beyond f32 range (or NaN) would poison every segment in their
    # chunk through the one-hot matmul (inf*0.0 = NaN, NaN*0.0 = NaN): run
    # the kernel on a finite f32-clamped value and route the (rare) residual
    # through the exact scatter path, taken at runtime only when one exists
    # (lax.cond skips the expensive branch otherwise). NaN rows become
    # residual NaN, which segment_sum confines to their own segment.
    f32max = jnp.float64(3.4028234663852886e38)
    nan = jnp.isnan(v64)
    clamped = jnp.clip(jnp.where(nan, 0.0, v64), -f32max, f32max)
    residual = jnp.where(nan, v64, v64 - clamped)
    correction = jax.lax.cond(
        jnp.any(residual != 0.0),
        lambda: jax.ops.segment_sum(
            residual, jnp.where(in_range, segment_ids, num_segments)
            .astype(jnp.int32), num_segments=num_segments + 1)[:num_segments],
        lambda: jnp.zeros(num_segments, jnp.float64))
    v64 = clamped
    if pad:
        v64 = jnp.pad(v64, (0, pad))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)  # no one-hot match
    hi = v64.astype(jnp.float32)
    lo = (v64 - hi.astype(jnp.float64)).astype(jnp.float32)
    parts = pl.pallas_call(
        _make_kernel(nb, g),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nb, 2 * SUB, g), jnp.float32),
        interpret=pallas_mode.interpret(),
    )(ids.reshape(nb * SUB, LANES), hi.reshape(nb * SUB, LANES),
      lo.reshape(nb * SUB, LANES))
    return parts.astype(jnp.float64).sum(axis=(0, 1))[:num_segments] + \
        correction
