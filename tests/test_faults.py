"""Fault-injection matrix and recovery-path tests.

The reference proves its robustness claims with RmmSpark OOM injection
(*RetrySuite) and a mocked droppable transport (RapidsShuffleClientSuite);
here the deterministic injector (spark_rapids_tpu/faults.py) drives full
queries and subsystem flows through every registered injection point and
asserts the documented contract: a correct result after recovery for
transient faults, a typed error within the deadline for permanent ones —
never a hang, never wrong rows.

Run standalone via scripts/fault_matrix.sh (pytest -m faults)."""

import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.errors import (AdmissionTimeoutError, DeviceStartupError,
                                     InjectedFault, RetryOOM,
                                     ShuffleCorruptionError,
                                     ShuffleFetchFailedError,
                                     SplitAndRetryOOM)
from spark_rapids_tpu.expr import Count, Sum, col
from spark_rapids_tpu.faults import FaultInjector, FaultRule, inject
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils.metrics import TaskMetrics

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _clean_slate():
    """Every test starts with no installed rules and fresh task metrics."""
    FaultInjector.reset()
    TaskMetrics.reset()
    yield
    FaultInjector.reset()


@pytest.fixture
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def _table(rng, n=600):
    return pa.table({
        "id": pa.array(rng.integers(0, 40, n), type=pa.int64()),
        "val": pa.array(rng.normal(0, 100, n), type=pa.float64()),
        "small": pa.array(rng.integers(-100, 100, n), type=pa.int32()),
    })


def _assert_same(df, sort_by):
    tpu = df.collect().sort_by([(k, "ascending") for k in sort_by])
    cpu = df.collect_cpu().sort_by([(k, "ascending") for k in sort_by])
    assert tpu.num_rows == cpu.num_rows
    for name in tpu.schema.names:
        assert tpu.column(name).to_pylist() == cpu.column(name).to_pylist(), \
            name
    return tpu


# ---------------------------------------------------------------------------
# The injector itself
# ---------------------------------------------------------------------------


class TestInjector:
    def test_nth_schedule_fires_once(self):
        with inject(faults.ALLOC, "error", nth=2, error=RetryOOM) as rule:
            faults.fire(faults.ALLOC)           # call 1: no fire
            with pytest.raises(RetryOOM):
                faults.fire(faults.ALLOC)       # call 2: fires
            faults.fire(faults.ALLOC)           # call 3: budget spent
            assert rule.calls == 3 and rule.fired == 1

    def test_every_call_unlimited(self):
        with inject(faults.FETCH, "error", nth=0, times=0) as rule:
            for _ in range(3):
                with pytest.raises(InjectedFault):
                    faults.fire(faults.FETCH)
            assert rule.fired == 3

    def test_probability_is_seeded_deterministic(self):
        def run():
            FaultInjector.reset()
            FaultInjector.get().reseed(7)
            fired = []
            with inject(faults.TCP_RECV, "error", probability=0.5, times=0):
                for i in range(32):
                    try:
                        faults.fire(faults.TCP_RECV)
                        fired.append(0)
                    except InjectedFault:
                        fired.append(1)
            return fired
        a, b = run(), run()
        assert a == b and 0 < sum(a) < 32

    def test_corrupt_default_flips_one_byte(self):
        payload = bytes(range(64))
        with inject(faults.BLOCK_READ, "corrupt"):
            out = faults.fire(faults.BLOCK_READ, payload)
        assert out != payload and len(out) == len(payload)
        assert sum(x != y for x, y in zip(out, payload)) == 1

    def test_disabled_passthrough(self):
        assert faults.fire(faults.ALLOC, b"x") == b"x"

    def test_spec_parsing(self):
        r = FaultRule.parse("shuffle.fetch:error,nth=3,times=2,err=conn")
        assert (r.point, r.kind, r.nth, r.times) == \
            ("shuffle.fetch", "error", 3, 2)
        assert r.error is ConnectionResetError
        r = FaultRule.parse("tcp.recv:delay,nth=0,times=0,delay=0.25")
        assert r.kind == "delay" and r.delay_s == 0.25
        r = FaultRule.parse("service.admission:wedge")
        assert r.kind == "wedge" and r.delay_s == 3600.0
        with pytest.raises(ValueError):
            FaultRule.parse("no-kind-here")
        with pytest.raises(ValueError):
            FaultRule.parse("p:zap,nth=1")

    def test_install_from_conf(self):
        conf = TpuConf({"spark.rapids.tpu.test.faults":
                        "memory.alloc:error,nth=1,err=oom; "
                        "shuffle.fetch:corrupt,nth=2"})
        rules = faults.install_from_conf(conf)
        assert len(rules) == 2
        with pytest.raises(RetryOOM):
            faults.fire(faults.ALLOC)


# ---------------------------------------------------------------------------
# Shuffle frame integrity (CRC32C satellite)
# ---------------------------------------------------------------------------


class TestChecksum:
    def _frame(self, rng, codec="zstd", checksum=True):
        from spark_rapids_tpu.columnar import batch_from_arrow
        from spark_rapids_tpu.shuffle import serialize_batch
        return serialize_batch(batch_from_arrow(_table(rng, 100)), codec,
                               checksum=checksum)

    def test_clean_frame_verifies_and_deserializes(self, rng):
        from spark_rapids_tpu.shuffle import deserialize_table, verify_frame
        blob = self._frame(rng)
        verify_frame(blob)
        table, consumed = deserialize_table(blob)
        assert consumed == len(blob) and table.num_rows == 100

    def test_flipped_payload_byte_raises_typed(self, rng):
        from spark_rapids_tpu.shuffle import deserialize_table, verify_frame
        blob = bytearray(self._frame(rng))
        blob[-10] ^= 0xFF  # payload corruption (tail is compressed bytes)
        with pytest.raises(ShuffleCorruptionError):
            verify_frame(bytes(blob), block="b1", source="peer-x")
        with pytest.raises(ShuffleCorruptionError):
            deserialize_table(bytes(blob))

    def test_smashed_header_raises_typed(self, rng):
        from spark_rapids_tpu.shuffle import verify_frame
        blob = bytearray(self._frame(rng))
        blob[0] ^= 0xFF  # magic
        with pytest.raises(ShuffleCorruptionError):
            verify_frame(bytes(blob))

    def test_checksum_disabled_frames_are_unchecked(self, rng):
        from spark_rapids_tpu.shuffle import decode_meta, verify_frame
        blob = self._frame(rng, codec="none", checksum=False)
        assert decode_meta(blob)[0].checksum == 0
        corrupted = bytearray(blob)
        corrupted[-10] ^= 0xFF
        verify_frame(bytes(corrupted))  # no checksum -> no verification


# ---------------------------------------------------------------------------
# with_retry mechanics (deque + backoff metrics satellite)
# ---------------------------------------------------------------------------


class TestRetryMechanics:
    def test_split_preserves_order_depth_first(self):
        from spark_rapids_tpu.memory.retry import with_retry
        split_once = {"done": False}

        def fn(x):
            if x == "ab" and not split_once["done"]:
                raise SplitAndRetryOOM("too big")
            return x

        def split(x):
            split_once["done"] = True
            return [x[:1], x[1:]]

        assert list(with_retry("ab", fn, split)) == ["a", "b"]

    def test_backoff_recorded_per_attempt(self):
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        TaskMetrics.reset()
        calls = {"n": 0}

        def fn(x):
            calls["n"] += 1
            if calls["n"] < 4:
                raise RetryOOM("pressure")
            return x

        assert with_retry_no_split(41, fn) == 41
        tm = TaskMetrics.get()
        assert tm.retry_count == 3
        assert len(tm.retry_backoff_ms) == 3
        # exponential schedule: each wait doubles (2ms, 4ms, 8ms)
        assert tm.retry_backoff_ms[1] == pytest.approx(
            2 * tm.retry_backoff_ms[0])
        line = tm.explain_string()
        assert "oomRetries=3" in line and "backoffsMs=" in line

    def test_shuffle_counters_in_explain_string(self):
        TaskMetrics.reset()
        tm = TaskMetrics.get()
        tm.shuffle_retry_count = 2
        tm.shuffle_failover_count = 1
        s = tm.explain_string()
        assert "shuffleFetchRetries=2" in s and "shuffleFailovers=1" in s


# ---------------------------------------------------------------------------
# HeartbeatManager (satellite): expiry, re-registration, fetch-path skip
# ---------------------------------------------------------------------------


class TestHeartbeat:
    def _hb(self, expiry=10.0):
        from spark_rapids_tpu.shuffle import HeartbeatManager
        clock = [0.0]
        hb = HeartbeatManager(expiry_seconds=expiry,
                              clock=lambda: clock[0])
        return hb, clock

    def test_peer_expiry_after_missed_heartbeats(self):
        hb, clock = self._hb()
        hb.register_executor("a", "addr-a")
        hb.register_executor("b", "addr-b")
        clock[0] = 5.0
        hb.executor_heartbeat("a")     # b misses its beats
        clock[0] = 12.0                # b last seen at 0, expiry 10
        assert [p.executor_id for p in hb.known_peers()] == ["a"]
        with pytest.raises(KeyError):
            hb.executor_heartbeat("b")  # aged out: must re-register

    def test_returning_executor_reregisters(self):
        hb, clock = self._hb()
        hb.register_executor("a", "addr-a")
        hb.register_executor("b", "addr-b")
        clock[0] = 8.0
        hb.executor_heartbeat("a")     # a stays fresh
        clock[0] = 16.0                # b (last seen 0) ages out
        hb.executor_heartbeat("a")
        assert [p.executor_id for p in hb.known_peers()] == ["a"]
        peers_seen = hb.register_executor("b", "addr-b2")  # b comes back
        assert [p.executor_id for p in peers_seen] == ["a"]
        back = {p.executor_id: p for p in hb.known_peers()}["b"]
        assert back.endpoint == "addr-b2"
        # the new registration is ordered after the survivor
        assert back.registration_order > \
            {p.executor_id: p for p in hb.known_peers()}["a"].registration_order

    def _two_managers(self, rng, hb=None):
        """Manager A (reader, empty store) + manager B (holds map output),
        connected over one LocalTransport."""
        from spark_rapids_tpu.columnar import batch_from_arrow
        from spark_rapids_tpu.shuffle import LocalTransport
        from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
        conf = TpuConf({"spark.rapids.shuffle.fetch.retryWaitMs": 1,
                        "spark.rapids.shuffle.fetch.maxRetries": 2})
        transport = LocalTransport()
        a = TpuShuffleManager(conf, executor_id="exec-a",
                              transport=transport, heartbeat=hb)
        b = TpuShuffleManager(conf, executor_id="exec-b",
                              transport=transport)
        writer = b.get_writer(shuffle_id=9, map_id=0)
        self._expected = _table(rng, 300)
        writer.write(0, batch_from_arrow(self._expected))
        writer.close()
        return a, b

    def test_fetch_path_skips_aged_out_peer(self, rng):
        """An aged-out peer gets NO fetch attempt (no retries, no backoff,
        no timeout wait) — but because it may hold rows nobody else can
        enumerate, the read fails fast with the typed error instead of
        silently returning without its blocks."""
        hb, clock = self._hb()
        a, b = self._two_managers(rng, hb)
        try:
            a.register_with_heartbeat(hb)
            hb.register_executor("exec-b", "exec-b")
            clock[0] = 8.0
            hb.executor_heartbeat("exec-a")  # a beats; b goes silent
            clock[0] = 16.0                  # b (last seen 0) ages out
            hb.executor_heartbeat("exec-a")
            t0 = time.monotonic()
            with pytest.raises(ShuffleFetchFailedError) as ei:
                list(a.read_partition(9, 0, remote_peers=["exec-b"]))
            assert time.monotonic() - t0 < 1.0  # no fetch, no backoff
            assert ei.value.peer == "exec-b" and ei.value.attempts == 0
            assert "aged out" in str(ei.value)
            # b re-registers -> the same fetch now works
            hb.register_executor("exec-b", "exec-b")
            out = list(a.read_partition(9, 0, remote_peers=["exec-b"]))
            assert sum(int(o.row_count()) for o in out) == 300
        finally:
            a.shutdown()
            b.shutdown()


# ---------------------------------------------------------------------------
# Shuffle fetch retry / refetch / failover (tentpole)
# ---------------------------------------------------------------------------


class TestFetchRecovery:
    def _peer_pair(self, rng, **conf_extra):
        from spark_rapids_tpu.columnar import batch_from_arrow
        from spark_rapids_tpu.shuffle import LocalTransport
        from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
        conf = TpuConf({"spark.rapids.shuffle.fetch.retryWaitMs": 1,
                        **conf_extra})
        transport = LocalTransport()
        a = TpuShuffleManager(conf, executor_id="exec-a",
                              transport=transport)
        b = TpuShuffleManager(conf, executor_id="exec-b",
                              transport=transport)
        writer = b.get_writer(shuffle_id=11, map_id=0)
        self._expected = _table(rng, 400)
        writer.write(0, batch_from_arrow(self._expected))
        writer.close()
        return a, b

    def _collect(self, mgr, sid=11, rid=0, peers=("exec-b",)):
        from spark_rapids_tpu.columnar import batch_to_arrow
        out = list(mgr.read_partition(sid, rid, remote_peers=list(peers)))
        assert len(out) == 1
        return batch_to_arrow(out[0])

    def test_transient_fetch_error_retried(self, rng):
        a, b = self._peer_pair(rng)
        try:
            with inject(faults.FETCH, "error", nth=1, times=1,
                        error=ConnectionResetError) as rule:
                got = self._collect(a)
            assert rule.fired == 1
            assert got.equals(self._expected)
            assert TaskMetrics.get().shuffle_retry_count >= 1
        finally:
            a.shutdown()
            b.shutdown()

    def test_corrupt_frame_refetched_once(self, rng):
        a, b = self._peer_pair(rng)
        try:
            with inject(faults.FETCH, "corrupt", nth=1, times=1) as rule:
                got = self._collect(a)
            assert rule.fired == 1
            assert got.equals(self._expected)
            assert TaskMetrics.get().shuffle_refetch_count == 1
        finally:
            a.shutdown()
            b.shutdown()

    def test_persistent_corruption_is_typed_error(self, rng):
        a, b = self._peer_pair(rng)
        try:
            with inject(faults.FETCH, "corrupt", nth=0, times=0):
                with pytest.raises(ShuffleCorruptionError) as ei:
                    self._collect(a)
            assert "exec-b" in str(ei.value)
        finally:
            a.shutdown()
            b.shutdown()

    def test_dead_peer_exhausts_budget_with_typed_error(self, rng):
        a, b = self._peer_pair(
            rng, **{"spark.rapids.shuffle.fetch.maxRetries": 2})
        try:
            t0 = time.monotonic()
            with inject(faults.FETCH, "error", nth=0, times=0,
                        error=ConnectionResetError):
                with pytest.raises(ShuffleFetchFailedError) as ei:
                    self._collect(a)
            assert time.monotonic() - t0 < 10.0  # bounded, never hangs
            err = ei.value
            assert err.peer == "exec-b" and err.attempts == 3
            assert err.blocks  # listing succeeded, so blocks are known
            assert TaskMetrics.get().shuffle_retry_count == 2
        finally:
            a.shutdown()
            b.shutdown()

    def test_failover_to_replica_peer(self, rng):
        """Peer that lists blocks but fails every byte transfer; a replica
        holds the same blocks — the fetch fails over and recovers all rows
        exactly once."""
        from spark_rapids_tpu.shuffle import LocalTransport, ShuffleServer
        from spark_rapids_tpu.shuffle.manager import TpuShuffleManager
        from spark_rapids_tpu.columnar import batch_from_arrow
        conf = TpuConf({"spark.rapids.shuffle.fetch.retryWaitMs": 1,
                        "spark.rapids.shuffle.fetch.maxRetries": 1})
        transport = LocalTransport()
        a = TpuShuffleManager(conf, executor_id="exec-a",
                              transport=transport)
        c = TpuShuffleManager(conf, executor_id="exec-c",
                              transport=transport)
        writer = c.get_writer(shuffle_id=13, map_id=0)
        expected = _table(rng, 250)
        writer.write(0, batch_from_arrow(expected))
        writer.close()

        # exec-b: advertises the same blocks but every read explodes (a
        # half-dead executor; its listing still answers)
        def dead_resolver(bid):
            raise IOError("disk gone")

        transport.register(ShuffleServer(
            "exec-b", dead_resolver,
            c.block_store.blocks_for_reduce))
        try:
            got = self._collect(a, sid=13, peers=("exec-b", "exec-c"))
            assert got.equals(expected)
            assert TaskMetrics.get().shuffle_failover_count == 1
        finally:
            a.shutdown()
            c.shutdown()

    def test_local_corruption_refetches_from_store(self, rng, session):
        """End-to-end repartition query with a corrupted local block read:
        the CRC catches it, the store read retries, rows stay correct."""
        df = session.from_arrow(_table(rng, 500)).repartition(4, "id")
        with inject(faults.BLOCK_READ, "corrupt", nth=1, times=1) as rule:
            _assert_same(df, sort_by=["id", "val", "small"])
        assert rule.fired == 1


# ---------------------------------------------------------------------------
# TCP transport faults (reset / delay) against a real socket server
# ---------------------------------------------------------------------------


class TestTcpFaults:
    def _tcp_rig(self, rng, deadline_s=0.5):
        from spark_rapids_tpu.columnar import batch_from_arrow
        from spark_rapids_tpu.shuffle.manager import (ShuffleBlockStore,
                                                      TpuShuffleManager)
        from spark_rapids_tpu.shuffle.serializer import serialize_batch
        from spark_rapids_tpu.shuffle.tcp_transport import (TcpShuffleServer,
                                                            TcpTransport)
        from spark_rapids_tpu.shuffle.transport import BlockId, ShuffleServer
        store = ShuffleBlockStore()
        self._expected = _table(rng, 200)
        store.put(BlockId(21, 0, 0),
                  serialize_batch(batch_from_arrow(self._expected), "zstd"))
        srv = TcpShuffleServer(ShuffleServer("exec-remote", store.get,
                                             store.blocks_for_reduce)).start()
        transport = TcpTransport(deadline_s=deadline_s)
        transport.register_peer("exec-remote", srv.address)
        conf = TpuConf({"spark.rapids.shuffle.fetch.retryWaitMs": 1,
                        "spark.rapids.shuffle.fetch.maxRetries": 2})
        mgr = TpuShuffleManager(conf, executor_id="exec-local",
                                transport=transport)
        return mgr, srv, store

    def test_connection_reset_retried_on_fresh_socket(self, rng):
        from spark_rapids_tpu.columnar import batch_to_arrow
        mgr, srv, store = self._tcp_rig(rng, deadline_s=5.0)
        try:
            with inject(faults.TCP_RECV, "error", nth=1, times=1,
                        error=ConnectionResetError) as rule:
                out = list(mgr.read_partition(21, 0,
                                              remote_peers=["exec-remote"]))
            assert rule.fired == 1
            assert batch_to_arrow(out[0]).equals(self._expected)
            assert TaskMetrics.get().shuffle_retry_count >= 1
        finally:
            mgr.shutdown()
            srv.close()
            store.close()

    def test_wedged_peer_hits_deadline_not_hang(self, rng):
        """Server-side reads wedge (slow disk); the client deadline converts
        every attempt into an error and the typed failure surfaces inside a
        bounded wall-clock window."""
        mgr, srv, store = self._tcp_rig(rng, deadline_s=0.4)
        try:
            t0 = time.monotonic()
            with inject(faults.BLOCK_READ, "delay", nth=0, times=0,
                        delay_s=1.0):
                with pytest.raises(ShuffleFetchFailedError):
                    list(mgr.read_partition(21, 0,
                                            remote_peers=["exec-remote"]))
            assert time.monotonic() - t0 < 15.0
        finally:
            mgr.shutdown()
            srv.close()
            store.close()


# ---------------------------------------------------------------------------
# Memory-pressure matrix: alloc OOM + spill I/O through real queries
# ---------------------------------------------------------------------------


class TestMemoryFaultMatrix:
    def test_sort_survives_retry_oom(self, rng, session):
        df = session.from_arrow(_table(rng)).sort("val")
        with inject(faults.ALLOC, "error", nth=1, times=1,
                    error=RetryOOM) as rule:
            _assert_same(df, sort_by=["val", "id", "small"])
        assert rule.fired == 1
        assert TaskMetrics.get().retry_count >= 1

    def test_window_survives_retry_oom(self, rng, session):
        from spark_rapids_tpu.expr.windowexprs import RowNumber
        df = session.from_arrow(_table(rng)).window(
            partition_by=["id"], order_by=["val"], rn=RowNumber())
        with inject(faults.ALLOC, "error", nth=1, times=1,
                    error=RetryOOM) as rule:
            _assert_same(df, sort_by=["id", "val", "rn"])
        assert rule.fired == 1

    def test_aggregate_survives_split_and_retry(self, rng, session):
        df = session.from_arrow(_table(rng)).group_by("id").agg(
            n=Count(col("val")), total=Sum(col("small")))
        with inject(faults.ALLOC, "error", nth=1, times=1,
                    error=SplitAndRetryOOM) as rule:
            _assert_same(df, sort_by=["id"])
        assert rule.fired == 1
        assert TaskMetrics.get().split_retry_count >= 1

    def test_exchange_survives_split_and_retry(self, rng, session):
        """Memory pressure during the shuffle write splits the input and
        writes each half under its own map id — rows land exactly once."""
        df = session.from_arrow(_table(rng, 500)).repartition(3, "id")
        with inject(faults.ALLOC, "error", nth=1, times=1,
                    error=SplitAndRetryOOM) as rule:
            _assert_same(df, sort_by=["id", "val", "small"])
        assert rule.fired == 1
        assert TaskMetrics.get().split_retry_count >= 1

    def test_spill_write_failure_degrades_not_dies(self):
        from spark_rapids_tpu.columnar import batch_from_arrow, batch_to_arrow
        from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier
        cat = BufferCatalog(host_limit=1, spill_codec="none")
        t = pa.table({"a": pa.array(np.arange(64, dtype=np.int64))})
        h = cat.add_batch(batch_from_arrow(t))
        with inject(faults.SPILL_WRITE, "error", nth=1, times=1,
                    error=IOError) as rule:
            cat.synchronous_spill(1)  # disk overflow fails -> stays HOST
        assert rule.fired == 1
        assert cat.tier_of(h) == StorageTier.HOST
        assert batch_to_arrow(cat.acquire_batch(h)).equals(t)
        cat.remove(h)

    def test_spill_read_transient_error_retried(self):
        from spark_rapids_tpu.columnar import batch_from_arrow, batch_to_arrow
        from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier
        cat = BufferCatalog(host_limit=1, spill_codec="none")
        t = pa.table({"a": pa.array(np.arange(64, dtype=np.int64))})
        h = cat.add_batch(batch_from_arrow(t))
        cat.synchronous_spill(1)
        assert cat.tier_of(h) == StorageTier.DISK
        with inject(faults.SPILL_READ, "error", nth=1, times=1,
                    error=IOError) as rule:
            back = cat.acquire_batch(h)  # first read fails, retry lands
        assert rule.fired == 1
        assert batch_to_arrow(back).equals(t)
        cat.remove(h)

    def test_spill_read_persistent_error_is_typed(self):
        from spark_rapids_tpu.columnar import batch_from_arrow
        from spark_rapids_tpu.memory.catalog import BufferCatalog
        cat = BufferCatalog(host_limit=1, spill_codec="none")
        t = pa.table({"a": pa.array(np.arange(64, dtype=np.int64))})
        h = cat.add_batch(batch_from_arrow(t))
        cat.synchronous_spill(1)
        with inject(faults.SPILL_READ, "error", nth=0, times=0,
                    error=IOError):
            with pytest.raises(OSError):
                cat.acquire_batch(h)
        cat.remove(h)


# ---------------------------------------------------------------------------
# Device-decode buffer lifetime: spill churn must never corrupt a scan
# ---------------------------------------------------------------------------


class TestDecodeLifetime:
    def test_parquet_decode_survives_spill_churn(self, rng, tmp_path):
        """Regression: the device parquet decode shipped zero-copy views of
        _ChunkHold-owned native memory to asynchronously-dispatched jax
        programs; the hold was freed when the decode returned, so catalog
        spill churn recycling that memory corrupted decoded columns (wrong
        values, all-null validity) nondeterministically. _chunk_from_native
        now copies the walk's views into owning arrays, making the decode
        bit-stable under allocation pressure."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar import batch_from_arrow, batch_to_arrow
        from spark_rapids_tpu.columnar.batch import Schema
        from spark_rapids_tpu.io import parquet_device as PD
        from spark_rapids_tpu.memory.catalog import BufferCatalog

        n = 2000
        t = pa.table({
            "k": pa.array(rng.integers(0, 20, n).astype(np.int64)),
            "v": pa.array(rng.normal(0.0, 10.0, n)),
        })
        path = str(tmp_path / "t.parquet")
        pq.write_table(t, path)
        schema = Schema(("k", "v"), (T.LONG, T.DOUBLE))
        truth_k = t.column("k").to_numpy()

        def churn():
            # spill/unspill cycles recycle freshly-freed allocations, which
            # is what exposed reads of dead decode buffers
            cat = BufferCatalog(host_limit=1, spill_codec="none")
            tt = pa.table({"a": pa.array(rng.integers(0, 9, 512))})
            hh = cat.add_batch(batch_from_arrow(tt))
            cat.synchronous_spill(1)
            batch_to_arrow(cat.acquire_batch(hh))
            cat.remove(hh)

        for _ in range(3):
            pf = pq.ParquetFile(path)
            with open(path, "rb") as f:
                batch, nrows = PD.decode_row_group(pf, f, 0, schema)
            assert nrows == n
            kcol = batch.columns[0]
            assert (np.asarray(kcol.data)[:n] == truth_k).all()
            assert int(np.asarray(kcol.validity).sum()) == n
            churn()


# ---------------------------------------------------------------------------
# Wedged backend init -> DeviceStartupError within the deadline
# ---------------------------------------------------------------------------


class TestDeviceInitFaults:
    def _fresh(self):
        from spark_rapids_tpu.memory.device_manager import DeviceManager
        DeviceManager.shutdown()
        return DeviceManager

    def test_wedged_backend_fails_fast(self):
        DeviceManager = self._fresh()
        conf = TpuConf({"spark.rapids.tpu.device.startupTimeoutSec": 0.4})
        t0 = time.monotonic()
        try:
            with inject(faults.DEVICE_INIT, "wedge", delay_s=3.0):
                with pytest.raises(DeviceStartupError) as ei:
                    DeviceManager.initialize(conf)
            assert time.monotonic() - t0 < 3.0
            assert "did not respond" in str(ei.value)
            # the failure is remembered: later queries fail fast, no re-arm
            with pytest.raises(DeviceStartupError):
                DeviceManager.initialize(conf)
        finally:
            DeviceManager.shutdown()  # clear for the rest of the suite

    def test_failing_backend_is_typed_with_diagnostics(self):
        DeviceManager = self._fresh()
        conf = TpuConf({"spark.rapids.tpu.device.startupTimeoutSec": 5.0})
        try:
            with inject(faults.DEVICE_INIT, "error",
                        error=RuntimeError("runtime down")):
                with pytest.raises(DeviceStartupError) as ei:
                    DeviceManager.initialize(conf)
            assert "runtime down" in str(ei.value.diagnostics.get("cause", ""))
        finally:
            DeviceManager.shutdown()


# ---------------------------------------------------------------------------
# Service admission: typed timeout + injected admission faults
# ---------------------------------------------------------------------------


class TestAdmissionFaults:
    @pytest.fixture
    def service(self, tmp_path):
        from spark_rapids_tpu.service.server import TpuDeviceService
        sock = str(tmp_path / "svc.sock")
        svc = TpuDeviceService(
            {"spark.rapids.sql.concurrentGpuTasks": 1}, sock)
        th = threading.Thread(target=svc.serve_forever, daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        import os
        while not os.path.exists(sock) and time.monotonic() < deadline:
            time.sleep(0.02)
        yield sock
        svc._stop.set()
        th.join(timeout=5)

    def test_admission_timeout_is_typed_with_diagnostics(self, service):
        from spark_rapids_tpu.service.client import TpuServiceClient
        with TpuServiceClient(service, deadline_s=10.0) as holder:
            holder.acquire()  # takes the single token
            with TpuServiceClient(service, deadline_s=10.0) as waiter:
                with pytest.raises(AdmissionTimeoutError) as ei:
                    waiter.acquire(timeout=0.1)
                err = ei.value
                assert err.held == 1 and err.waiting >= 0
                assert isinstance(err, TimeoutError)  # legacy contract
            holder.release()

    def test_injected_admission_fault_surfaces_typed(self, service):
        from spark_rapids_tpu.service.client import TpuServiceClient
        with inject(faults.ADMISSION, "error", nth=1, times=1):
            with TpuServiceClient(service, deadline_s=10.0) as cli:
                with pytest.raises(AdmissionTimeoutError):
                    cli.acquire(timeout=5.0)
                cli.acquire(timeout=5.0)  # injection budget spent: admitted
                cli.release()

    def test_wedged_admission_hits_client_deadline(self, service):
        from spark_rapids_tpu.service.client import TpuServiceClient
        t0 = time.monotonic()
        with inject(faults.ADMISSION, "wedge", delay_s=3.0):
            with TpuServiceClient(service, deadline_s=0.5) as cli:
                with pytest.raises(DeviceStartupError):
                    cli.acquire(timeout=10.0)
        assert time.monotonic() - t0 < 3.0


# ---------------------------------------------------------------------------
# persist point: durable-dir faults degrade tiers to memory-only (PR 14)
# ---------------------------------------------------------------------------


class TestPersistFaults:
    def test_stats_history_append_fault_degrades_not_raises(self, tmp_path):
        import os
        import warnings
        from spark_rapids_tpu.errors import PersistenceDegradedWarning
        from spark_rapids_tpu.stats.history import OpStats, StatsHistory
        from spark_rapids_tpu.utils import durable
        durable.reset_for_tests()
        try:
            h = StatsHistory(max_entries=16, persist_dir=str(tmp_path))
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                with inject(faults.PERSIST, "error", nth=1, times=1,
                            error=IOError) as rule:
                    h.record(OpStats(digest="d1", op="Scan", rows=10.0),
                             persistable=True)
            assert rule.fired == 1
            assert any(isinstance(w.message, PersistenceDegradedWarning)
                       for w in caught)
            # memory tier unharmed; later appends no-op instead of raising
            assert h.lookup("d1").rows == 10.0
            h.record(OpStats(digest="d2", op="Scan", rows=5.0),
                     persistable=True)
            assert h.lookup("d2").rows == 5.0
            assert not os.listdir(str(tmp_path))
        finally:
            durable.reset_for_tests()

    def test_event_log_append_fault_degrades_silently(self, tmp_path):
        import os
        import warnings
        from spark_rapids_tpu.errors import PersistenceDegradedWarning
        from spark_rapids_tpu.utils import durable, spans
        durable.reset_for_tests()
        try:
            rec = spans.client_op_record("run_plan", "t" * 32, 1000)
            log_dir = str(tmp_path / "events")
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                with inject(faults.PERSIST, "error", nth=1, times=1,
                            error=IOError) as rule:
                    spans.write_client_record(log_dir, rec)  # degrades
                spans.write_client_record(log_dir, rec)      # no-ops
            assert rule.fired == 1
            assert any(isinstance(w.message, PersistenceDegradedWarning)
                       for w in caught)
            assert not os.path.isdir(log_dir) or not os.listdir(log_dir)
        finally:
            durable.reset_for_tests()

    def test_persist_point_registered(self):
        assert faults.PERSIST in faults.ALL_POINTS
