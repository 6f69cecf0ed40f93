"""The package settles Python's cyclic collector after a query that compiled
(PERF.md, fault 23): what is alive then moves to the permanent generation, so
a full collection in the middle of a warm query walks only what was made
since; what was frozen and has died is collected at the next settle, and a
settle works at most once a minute."""

import gc
import weakref

import pytest

import spark_rapids_tpu
from spark_rapids_tpu.plugin import TpuSession


@pytest.fixture()
def unsettled(monkeypatch):
    """The process as no settle had touched it, before and after."""
    gc.unfreeze()
    monkeypatch.setattr(spark_rapids_tpu, "_settled_at", None)
    yield
    gc.unfreeze()


class _Ring:
    def __init__(self):
        self.me = self


def test_a_settle_freezes_what_is_alive_and_the_next_one_waits(unsettled):
    assert gc.get_freeze_count() == 0
    assert spark_rapids_tpu.settle_host_heap() is True
    frozen = gc.get_freeze_count()
    assert frozen > 10_000
    # a full collection now walks what was made since, not the modules
    assert len(gc.get_objects()) < frozen // 10
    assert spark_rapids_tpu.settle_host_heap() is False
    assert gc.get_freeze_count() == frozen


def test_what_died_frozen_goes_at_the_next_settle(unsettled, monkeypatch):
    monkeypatch.setattr(spark_rapids_tpu, "_SETTLE_EVERY_S", 0.0)
    ring = _Ring()
    seen = weakref.ref(ring)
    assert spark_rapids_tpu.settle_host_heap() is True
    del ring
    gc.collect()
    assert seen() is not None      # frozen: no collection looks at it
    assert spark_rapids_tpu.settle_host_heap() is True
    assert seen() is None


def test_a_collector_that_is_off_stays_untouched(unsettled):
    gc.disable()
    try:
        assert spark_rapids_tpu.settle_host_heap() is False
        assert gc.get_freeze_count() == 0
    finally:
        gc.enable()


@pytest.mark.parametrize("compiles", [True, False])
def test_a_query_settles_only_where_it_compiled(unsettled, monkeypatch,
                                                compiles):
    import pyarrow as pa

    from spark_rapids_tpu.expr import col, lit
    from spark_rapids_tpu.compile.service import CompileService
    session = TpuSession({})
    df = session.from_arrow(
        pa.table({"a": list(range(100))})).filter(col("a") < lit(417_417))
    calls = []
    df.collect()
    if compiles:
        # a restart: the next lookup compiles, or reloads, its program
        CompileService.get().clear_memory()
    monkeypatch.setattr(spark_rapids_tpu, "settle_host_heap",
                        lambda: calls.append(1))
    assert df.collect().num_rows == 100
    assert len(calls) == (1 if compiles else 0)
