"""Engine-wide fail-fast on a wedged device backend (reference
`Plugin.scala:436-459`: inspect executor startup failure, log diagnostics,
exit fast). A device runtime can HANG (not raise) inside client init at
first touch; a planned query must raise a typed error within the
configured deadline instead of blocking forever."""

import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.errors import DeviceStartupError
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.memory import device_manager as dm
from spark_rapids_tpu.plugin import TpuSession


@pytest.fixture
def fresh_device_manager():
    dm.DeviceManager.shutdown()
    yield
    dm.DeviceManager.shutdown()


def _session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE",
                       "spark.rapids.tpu.device.startupTimeoutSec": 1.0})


def _df(session):
    t = pa.table({"a": pa.array(range(10), type=pa.int64())})
    return session.from_arrow(t).filter(col("a") > lit(3))


class TestFailFast:
    def test_hanging_backend_raises_within_deadline(
            self, monkeypatch, fresh_device_manager):
        monkeypatch.setattr(dm, "_backend_touch",
                            lambda: time.sleep(3600))
        t0 = time.monotonic()
        with pytest.raises(DeviceStartupError, match="did not respond"):
            _df(_session()).collect()
        elapsed = time.monotonic() - t0
        assert elapsed < 10, f"fail-fast took {elapsed:.1f}s"

    def test_error_backend_raises_typed(self, monkeypatch,
                                        fresh_device_manager):
        def boom():
            raise RuntimeError("UNAVAILABLE: connection reset")
        monkeypatch.setattr(dm, "_backend_touch", boom)
        with pytest.raises(DeviceStartupError, match="UNAVAILABLE") as ei:
            _df(_session()).collect()
        assert "cause" in ei.value.diagnostics

    def test_second_query_fails_immediately(self, monkeypatch,
                                            fresh_device_manager):
        # the fatal startup error is remembered: later queries must not
        # re-arm a fresh deadline against the same wedged runtime
        monkeypatch.setattr(dm, "_backend_touch",
                            lambda: time.sleep(3600))
        s = _session()
        with pytest.raises(DeviceStartupError):
            _df(s).collect()
        t0 = time.monotonic()
        with pytest.raises(DeviceStartupError):
            _df(s).collect()
        assert time.monotonic() - t0 < 0.5

    def test_cpu_engine_unaffected(self, monkeypatch,
                                   fresh_device_manager):
        monkeypatch.setattr(dm, "_backend_touch",
                            lambda: time.sleep(3600))
        out = _df(_session()).collect_cpu()
        assert out.column("a").to_pylist() == [4, 5, 6, 7, 8, 9]

    def test_disabled_guard_passes_through(self, fresh_device_manager):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.tpu.device.startupTimeoutSec": -1.0})
        out = _df(s).collect()
        assert out.column("a").to_pylist() == [4, 5, 6, 7, 8, 9]
