"""Pallas TPU kernel: Spark-exact murmur3 row hashing for the fused join
probe (ISSUE-16; follows the ops/pallas_segsum.py idiom).

The fused stage's join sizing path hashes the probe and build keys every
batch (expr/hashing.py, a long chain of elementwise u32 mixes). This
kernel runs that chain on-chip over double-buffered DMA blocks. All
arithmetic is int32 two's-complement with logical right shifts — bit-for-
bit the uint32 wraparound semantics of `expr.hashing` (Mosaic's int32 ops
are the safe lowering; uint32 is not), so the counts derived from these
hashes are EXACTLY the counts `exec.joins._probe_counts` computes and
fusion on/off identity is preserved by construction.

Kernel structure mirrors pallas_segsum (hard-won constraints): single
non-gridded invocation, internal while_loop, double-buffered manual DMA,
every scalar index int32, interpret mode off-TPU. Unsupported key types
(strings, floats, wide decimals) fall back per-column to the jnp hash —
the chain seed threads through either path unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import types as T
from ..compile import sjit
from . import pallas_mode

__all__ = ["hash_int_rows", "hash_long_rows", "hash_vecs_pallas",
           "candidate_counts"]

SUB = 8        # sublanes per DMA block
LANES = 256    # lanes per block row
CHUNK = SUB * LANES

_TWO = np.int32(2)
_ONE = np.int32(1)


def _i32(x) -> np.int32:
    return np.uint32(x).astype(np.int32)


_C1 = _i32(0xcc9e2d51)
_C2 = _i32(0x1b873593)
_M5 = _i32(0xe6546b64)
_F1 = _i32(0x85ebca6b)
_F2 = _i32(0xc2b2ae35)


def _srl(x, r: int):
    return jax.lax.shift_right_logical(x, np.int32(r))


def _rotl(x, r: int):
    return (x << np.int32(r)) | _srl(x, 32 - r)


def _mix_k1(k1):
    return _rotl(k1 * _C1, 15) * _C2


def _mix_h1(h1, k1):
    return _rotl(h1 ^ k1, 13) * np.int32(5) + _M5


def _fmix(h1, length: np.int32):
    h1 = h1 ^ length
    h1 = h1 ^ _srl(h1, 16)
    h1 = h1 * _F1
    h1 = h1 ^ _srl(h1, 13)
    h1 = h1 * _F2
    return h1 ^ _srl(h1, 16)


def _make_kernel(n_blocks: int, nwords: int):
    """nwords=1: (v, seed) -> int hash; nwords=2: (low, high, seed) ->
    long hash. One elementwise block per step, double-buffered both ways."""
    n_in = nwords + 1

    def kernel(*refs):
        ins, out_hbm = refs[:-1], refs[-1]

        def body(*scoped):
            bufs = scoped[:n_in]
            obuf, insem, outsem = scoped[n_in], scoped[n_in + 1], \
                scoped[n_in + 2]

            def in_dma(slot, b):
                return [pltpu.make_async_copy(
                    r.at[pl.ds(b * np.int32(SUB), SUB), :],
                    buf.at[slot], insem.at[slot, np.int32(k)])
                    for k, (r, buf) in enumerate(zip(ins, bufs))]

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
                seed = bufs[nwords][slot]
                h1 = _mix_h1(seed, _mix_k1(bufs[0][slot]))
                if nwords == 2:
                    h1 = _mix_h1(h1, _mix_k1(bufs[1][slot]))
                h = _fmix(h1, np.int32(4 * nwords))

                @pl.when(b >= _TWO)
                def _():
                    pltpu.make_async_copy(obuf.at[slot],
                                          out_hbm.at[b - _TWO],
                                          outsem.at[slot]).wait()

                obuf[slot] = h
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
            *[pltpu.VMEM((2, SUB, LANES), jnp.int32) for _ in range(n_in)],
            pltpu.VMEM((2, SUB, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA((2, n_in)),
            pltpu.SemaphoreType.DMA((2,)),
        )

    return kernel


def _run(n: int, words, seed):
    nb = max(1, -(-n // CHUNK))
    pad = nb * CHUNK - n
    arrs = list(words) + [seed]
    if pad:
        arrs = [jnp.pad(a, (0, pad)) for a in arrs]
    out = pl.pallas_call(
        _make_kernel(nb, len(words)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(arrs),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((nb, SUB, LANES), jnp.int32),
        interpret=pallas_mode.interpret(),
    )(*[a.reshape(nb * SUB, LANES) for a in arrs])
    return out.reshape(nb * CHUNK)[:n]


@sjit(op="ops.pallas_probe.hash_int")
def hash_int_rows(v, seed):
    """murmur3 of one 4-byte block per row (int32 v, int32 seed)."""
    return _run(v.shape[0], [v], seed)


@sjit(op="ops.pallas_probe.hash_long")
def hash_long_rows(low, high, seed):
    """murmur3 of one 8-byte value per row as two 4-byte blocks."""
    return _run(low.shape[0], [low, high], seed)


def _hash_one(xp, v, seed_u32):
    """One column into the running row hash: pallas for the integral
    layouts, `expr.hashing.hash_vec` (identical bits) otherwise. Null rows
    pass the seed through (Spark semantics)."""
    dt = v.dtype
    seed_i = seed_u32.astype(np.int32)
    if isinstance(dt, (T.BooleanType, T.ByteType, T.ShortType,
                       T.IntegerType, T.DateType)):
        h = hash_int_rows(v.data.astype(np.int32), seed_i)
    elif isinstance(dt, (T.LongType, T.TimestampType)) or \
            (isinstance(dt, T.DecimalType) and dt.precision <= 18):
        u = v.data.astype(np.int64)
        low = (u & np.int64(0xFFFFFFFF)).astype(np.int32)
        high = (u >> np.int64(32)).astype(np.int32)
        h = hash_long_rows(low, high, seed_i)
    else:
        from ..expr.hashing import hash_vec
        return hash_vec(xp, v, seed_u32)
    return xp.where(v.validity, h.astype(np.uint32), seed_u32)


def hash_vecs_pallas(xp, vecs, seed: int = 42):
    """Drop-in for expr.hashing.hash_vecs (bit-identical int32 result)."""
    n = vecs[0].validity.shape[0]
    h = xp.full((n,), np.uint32(seed), dtype=np.uint32)
    for v in vecs:
        h = _hash_one(xp, v, h)
    return h.astype(np.int32)


def _keys_valid(xp, keys):
    ok = None
    for k in keys:
        ok = k.validity if ok is None else (ok & k.validity)
    return ok


def candidate_counts(xp, pkeys, bkeys, pmask, bmask):
    """Per-probe-row candidate counts by two searches of the 64-bit hashes:
    the plain statement of what `exec.joins._probe_counts` computes with
    one search and run lengths, here with the row hash routed through the
    pallas kernel. The engine's fused stage hands `hash_vecs_pallas` to
    `_probe_counts` itself; this stays as the counts it is tested against."""
    pvalid = _keys_valid(xp, pkeys) & pmask
    bvalid = _keys_valid(xp, bkeys) & bmask
    ph = hash_vecs_pallas(xp, pkeys).astype(np.int64)
    bh = hash_vecs_pallas(xp, bkeys).astype(np.int64)
    # exile invalid build rows to a hash bucket no valid probe can hit
    bh = xp.where(bvalid, bh, np.int64(2 ** 62))
    bh_sorted = xp.sort(bh)
    lo = xp.searchsorted(bh_sorted, ph, side="left")
    hi = xp.searchsorted(bh_sorted, ph, side="right")
    return xp.where(pvalid, hi - lo, 0).astype(np.int32)
