"""The v5e compiler, asked without the chip (on-chip-measurement guide §2):
the three Pallas kernels at 4M rows, compiled not interpreted, and two engine
programs of `chip_smoke.py` at a 1M-row batch shape. A compile that passes is
not a chip run; it only says the chip's compiler accepts the program.

All of it lives in this one file: the topology is described inside a
module-scoped fixture, so only the xdist worker that is handed this file
loads the TPU library, and every worker collects the same tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest
from jax.sharding import SingleDeviceSharding

from spark_rapids_tpu.ops import pallas_mode

ROWS = 4 << 20
BATCH = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with JAX's persistent cache off: an
    executable for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """`jax.default_backend()` is still cpu here, so steer the kernels'
    one interpret switch to what it says on the chip."""
    monkeypatch.setattr(pallas_mode, "interpret", lambda: False)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


@pytest.mark.parametrize("groups", [1024, 4096])
def test_segment_sum_f64_compiles(one_chip, compiled_kernels, groups):
    from spark_rapids_tpu.ops.pallas_segsum import segment_sum_f64
    exe = _compile(lambda v, i: segment_sum_f64.fn(v, i, groups),
                   _shape(one_chip, (ROWS,), jnp.float64),
                   _shape(one_chip, (ROWS,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("groups", [1024, 4096])
def test_segment_sum_i64_compiles(one_chip, compiled_kernels, groups):
    from spark_rapids_tpu.ops.pallas_groupby import segment_sum_i64
    exe = _compile(lambda v, i: segment_sum_i64.fn(v, i, groups),
                   _shape(one_chip, (ROWS,), jnp.int64),
                   _shape(one_chip, (ROWS,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


def test_hash_long_rows_compiles(one_chip, compiled_kernels):
    from spark_rapids_tpu.ops.pallas_probe import hash_long_rows
    word = _shape(one_chip, (ROWS,), jnp.int32)
    exe = _compile(hash_long_rows.fn, word, word, word)
    assert "tpu_custom_call" in exe.as_text()


@pytest.fixture(scope="module")
def smoke_programs():
    """Run a keyed join + grouped decimal Sum over one 1M-row batch on the
    CPU backend and keep what the compile service was asked to compile:
    {op: (dynamic-only function, dynamic arguments)}."""
    from spark_rapids_tpu.compile.service import CompileService
    from spark_rapids_tpu.expr import Sum, col
    from spark_rapids_tpu.plugin import TpuSession
    import benchcorpus
    rng = np.random.default_rng(0)
    fact = pa.table({
        "k": rng.integers(0, 1000, BATCH, dtype=np.int64),
        "price": benchcorpus.decimal_array(
            rng.integers(0, 20_001, BATCH, dtype=np.int64),
            rng.random(BATCH) < 0.02, 7, 2)})
    dim = pa.table({"k": np.arange(1000, dtype=np.int64),
                    "g": (np.arange(1000) % 37).astype(np.int32)})
    seen = {}
    real = CompileService._do_compile

    def keep(self, digest, sj, statics, dyn, boxes):
        seen.setdefault(sj.op, (self._dyn_fn(sj, statics), dyn))
        return real(self, digest, sj, statics, dyn, boxes)

    mp = pytest.MonkeyPatch()
    mp.setattr(CompileService, "_do_compile", keep)
    try:
        s = TpuSession()
        out = (s.from_arrow(fact).join(s.from_arrow(dim), on="k")
               .group_by("g").agg(total=Sum(col("price"))).collect())
    finally:
        mp.undo()
    assert out.num_rows == 37
    return seen


@pytest.mark.parametrize("op", ["exec.join.probe_counts", "exec.join.expand",
                                "exec.aggregate"])
def test_smoke_program_compiles(one_chip, smoke_programs, op):
    fn, dyn = smoke_programs[op]
    avals = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, np.shape(x), x.dtype), dyn)
    assert max(np.shape(x)[0] for x in jax.tree_util.tree_leaves(dyn)
               if np.ndim(x)) >= BATCH
    _compile(fn, *avals)


# the ORC column programs at `lineitem.q1_orc`'s shapes (PERF.md, PR 37):
# (signature, the shapes and dtypes of the arrays the host phase ships)
_ORC_CAP = 2 << 20
_RLE = lambda runs, words: [((runs,), np.int32), ((7, runs), np.uint32),  # noqa: E731
                            ((words,), np.uint32)]
_ORC_PROGRAMS = {
    "decimal_2m_bytes": (("decimal", False), [((1 << 19,), np.uint32)]),
    "decimal_8m_bytes": (("decimal", False), [((1 << 21,), np.uint32)]),
    "date_4209_runs": (("int", False, False, "int32"),
                       _RLE(8192, 1 << 20)),
    "flag_387064_runs": (("string_dict", False, False, 8, 8),
                         _RLE(1 << 19, 1 << 16) + [
                             ((8,), np.int64), ((8,), np.int32),
                             ((16,), np.uint8)]),
    "nullable_wide_long": (("int", True, True, "int64"),
                           _RLE(1024, 128) + _RLE(8192, 1 << 21)),
}


@pytest.mark.parametrize("name", sorted(_ORC_PROGRAMS))
def test_orc_column_program_compiles_without_a_loop(one_chip, name):
    """A decode program holds no `while` (a search per slot, a scan over
    the bytes) and no sort: marks, prefix sums, stacked gathers, shifts."""
    from spark_rapids_tpu.io import orc_device as O
    sig, arrays = _ORC_PROGRAMS[name]
    if sig[1]:    # a PRESENT stream's byte-RLE table comes first
        arrays = [((1024,), np.int32), ((3, 1024), np.uint32),
                  ((1 << 18,), np.uint8)] + arrays[3:]
    fn = O._column_program(sig, _ORC_CAP).fn
    exe = _compile(fn, _shape(one_chip, (), jnp.int32),
                   *[_shape(one_chip, s, d) for s, d in arrays])
    text = exe.as_text()
    assert " while(" not in text and " sort(" not in text
    assert text.count(" gather(") <= 6
