"""Shuffle subsystem tests (reference model: RapidsShuffleClientSuite /
RapidsShuffleServerSuite / WindowedBlockIteratorSuite run the client/server
state machines entirely in-process over a mocked transport —
`tests/.../shuffle/RapidsShuffleTestHelper.scala`)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.shuffle import (BlockId, BlockRange, BounceBufferManager,
                                      HeartbeatManager, LocalTransport,
                                      ShuffleClient, ShuffleServer,
                                      TpuShuffleManager, WindowedBlockIterator,
                                      concat_host_tables, decode_meta,
                                      deserialize_table, get_codec,
                                      serialize_batch)
from spark_rapids_tpu.shuffle.manager import next_shuffle_id


def sample_table(rng, n=500):
    nulls = rng.random(n) < 0.2
    cats = np.array(["x", "medium", "a-much-longer-string", None],
                    dtype=object)[rng.integers(0, 4, n)]
    return pa.table({
        "a": pa.array(np.where(nulls, 0, rng.integers(-10**9, 10**9, n)),
                      type=pa.int64(), mask=nulls),
        "b": pa.array(rng.normal(0, 1, n), type=pa.float64()),
        "s": pa.array(list(cats)),
        "c": pa.array(rng.integers(0, 2, n), type=pa.bool_()),
    })


class TestSerializer:
    @pytest.mark.parametrize("codec", ["none", "zstd", "lz4xla"])
    def test_round_trip(self, rng, codec):
        t = sample_table(rng)
        batch = batch_from_arrow(t)
        blob = serialize_batch(batch, codec)
        table, consumed = deserialize_table(blob)
        assert consumed == len(blob)
        out = batch_to_arrow(concat_host_tables([table]))
        assert out.equals(t)

    def test_concat_many(self, rng):
        tables = [sample_table(rng, n) for n in (100, 1, 257, 64)]
        blobs = [serialize_batch(batch_from_arrow(t), "zstd") for t in tables]
        hts = [deserialize_table(b)[0] for b in blobs]
        merged = batch_to_arrow(concat_host_tables(hts))
        expected = pa.concat_tables(tables)
        assert merged.equals(expected)

    def test_metadata_header(self, rng):
        t = sample_table(rng, 50)
        blob = serialize_batch(batch_from_arrow(t), "zstd")
        meta, _ = decode_meta(blob)
        assert meta.num_rows == 50
        # the frame stamps the ACTUAL codec: zstd, or the zlib fallback
        # when the zstandard wheel is absent in this environment
        assert meta.codec == get_codec("zstd").name
        assert [c.name for c in meta.columns] == ["a", "b", "s", "c"]
        assert isinstance(meta.columns[2].dtype, T.StringType)
        assert meta.columns[2].string_width > 0
        assert meta.compressed_len <= meta.uncompressed_len


class TestCodecs:
    @pytest.mark.parametrize("codec", ["none", "zstd", "lz4xla"])
    def test_codec_round_trip(self, codec, rng):
        c = get_codec(codec)
        for data in (b"", b"abc" * 10000, rng.bytes(10000)):
            comp = c.compress(data)
            assert c.decompress(comp, len(data)) == data

    @pytest.mark.parametrize("codec", ["zstd", "lz4xla"])
    def test_shared_codec_under_concurrent_use(self, codec):
        """`get_codec` hands every caller ONE cached object and the shuffle
        writer's pool calls it from several threads at once. zstandard
        contexts are not thread-safe: a shared compressor gave 'Src size is
        incorrect' or another thread's bytes."""
        from concurrent.futures import ThreadPoolExecutor
        c = get_codec(codec)

        def work(seed):
            r = np.random.default_rng(seed)
            for _ in range(60):
                data = r.bytes(int(r.integers(1, 200_000))) + \
                    b"pad" * int(r.integers(0, 50_000))
                comp = c.compress(data)
                if c.decompress(comp, len(data)) != data:
                    return False
            return True

        with ThreadPoolExecutor(8) as pool:
            assert all(pool.map(work, range(8)))


class TestWindowedBlockIterator:
    def test_splits_large_block(self):
        bid = BlockId(1, 0, 0)
        windows = list(WindowedBlockIterator([(bid, 1000)], 300))
        assert len(windows) == 4
        assert [w[0].length for w in windows] == [300, 300, 300, 100]
        assert windows[-1][0].is_final
        assert not windows[0][0].is_final

    def test_packs_small_blocks(self):
        blocks = [(BlockId(1, m, 0), 100) for m in range(10)]
        windows = list(WindowedBlockIterator(blocks, 350))
        assert len(windows) == 3
        assert sum(len(w) for w in windows) >= 10
        total = sum(r.length for w in windows for r in w)
        assert total == 1000

    def test_block_spanning_windows(self):
        blocks = [(BlockId(1, 0, 0), 250), (BlockId(1, 1, 0), 500)]
        windows = list(WindowedBlockIterator(blocks, 300))
        ranges = [r for w in windows for r in w]
        per_block = {}
        for r in ranges:
            per_block.setdefault(r.block.map_id, []).append(r)
        for m, rs in per_block.items():
            assert rs[0].offset == 0
            for a, b in zip(rs, rs[1:]):
                assert a.offset + a.length == b.offset
            assert rs[-1].is_final


class TestBounceBuffers:
    def test_pool_blocks_and_releases(self):
        mgr = BounceBufferManager(count=2, buf_size=128)
        b1, b2 = mgr.acquire(), mgr.acquire()
        assert mgr.num_free == 0
        with pytest.raises(TimeoutError):
            mgr.acquire(timeout=0.05)
        b1.close()
        b3 = mgr.acquire(timeout=1)
        assert b3 is not None
        b2.close()
        b3.close()
        assert mgr.num_free == 2


class TestClientServer:
    def _make_peer(self, rng, blocks):
        store = {}
        for (sid, mid, rid), table in blocks.items():
            store[BlockId(sid, mid, rid)] = serialize_batch(
                batch_from_arrow(table), "zstd")
        server = ShuffleServer("peer-1", store.get)
        transport = LocalTransport()
        transport.register(server)
        return transport, store

    def test_fetch_blocks_end_to_end(self, rng):
        tables = {(7, m, 0): sample_table(rng, 100 + m) for m in range(4)}
        transport, store = self._make_peer(rng, tables)
        client = ShuffleClient(transport.connect("peer-1"),
                               BounceBufferManager(2, 1 << 12))  # tiny windows
        got = {}
        errors = []
        n = client.fetch_blocks(
            [BlockId(7, m, 0) for m in range(6)],  # 2 don't exist
            on_block=lambda bid, data: got.__setitem__(bid.map_id, data),
            on_error=lambda bid, e: errors.append(bid))
        assert n == 4
        # absent blocks are reported as per-block failures, never dropped
        assert sorted(b.map_id for b in errors) == [4, 5]
        for m in range(4):
            assert got[m] == store[BlockId(7, m, 0)]
            ht, _ = deserialize_table(got[m])
            assert batch_to_arrow(concat_host_tables([ht])).equals(
                tables[(7, m, 0)])

    def test_fetch_error_surfaces_per_block(self, rng):
        tables = {(7, 0, 0): sample_table(rng, 50)}
        transport, store = self._make_peer(rng, tables)

        class FlakyConnection:
            def __init__(self, inner):
                self._inner = inner
                self.calls = 0

            def request_metadata(self, ids):
                metas = self._inner.request_metadata(ids)
                # lie about a block the server cannot serve
                from spark_rapids_tpu.shuffle.metadata import TableMeta
                metas.append((BlockId(9, 9, 9),
                              TableMeta(0, "none", 0, 0, []), 64))
                return metas

            def fetch_range(self, r):
                return self._inner.fetch_range(r)

        client = ShuffleClient(FlakyConnection(transport.connect("peer-1")),
                               BounceBufferManager(1, 1 << 16))
        errors = []
        got = []
        n = client.fetch_blocks([BlockId(7, 0, 0)],
                                on_block=lambda b, d: got.append(d),
                                on_error=lambda b, e: errors.append((b, e)))
        assert n == 1 and len(got) == 1
        assert len(errors) == 1 and errors[0][0] == BlockId(9, 9, 9)

    def test_unknown_peer_raises(self):
        with pytest.raises(ConnectionError):
            LocalTransport().connect("nobody")

    def test_fetch_partition_discovers_blocks(self, rng):
        tables = {(5, m, 2): sample_table(rng, 30 + m) for m in range(3)}
        tables[(5, 0, 1)] = sample_table(rng, 10)  # different reduce id
        transport, store = self._make_peer_with_lister(rng, tables)
        client = ShuffleClient(transport.connect("peer-1"),
                               BounceBufferManager(2, 1 << 16))
        got = {}
        n = client.fetch_partition(
            5, 2, on_block=lambda bid, data: got.__setitem__(bid.map_id,
                                                             data))
        assert n == 3 and sorted(got) == [0, 1, 2]

    def _make_peer_with_lister(self, rng, blocks):
        store = {}
        for (sid, mid, rid), table in blocks.items():
            store[BlockId(sid, mid, rid)] = serialize_batch(
                batch_from_arrow(table), "zstd")

        def lister(sid, rid):
            return sorted((b for b in store
                           if b.shuffle_id == sid and b.reduce_id == rid),
                          key=lambda b: b.map_id)

        server = ShuffleServer("peer-1", store.get, lister)
        transport = LocalTransport()
        transport.register(server)
        return transport, store

    def test_midblock_failure_never_delivers_truncated(self, rng):
        # one large block spanning many windows; a transient failure on an
        # early range must poison the whole block, not deliver a tail-only
        # reassembly as success
        t = sample_table(rng, 5000)
        transport, store = self._make_peer(rng, {(3, 0, 0): t})

        class OneFailure:
            def __init__(self, inner):
                self._inner = inner
                self._failed = False

            def request_metadata(self, ids):
                return self._inner.request_metadata(ids)

            def fetch_range(self, r):
                if not self._failed and r.offset > 0:
                    self._failed = True
                    raise IOError("transient")
                return self._inner.fetch_range(r)

        client = ShuffleClient(OneFailure(transport.connect("peer-1")),
                               BounceBufferManager(1, 1 << 12))
        got, errors = [], []
        n = client.fetch_blocks([BlockId(3, 0, 0)],
                                on_block=lambda b, d: got.append(d),
                                on_error=lambda b, e: errors.append(e))
        assert n == 0 and got == [] and len(errors) == 1


class TestHeartbeat:
    def test_register_and_discover(self):
        clock = [0.0]
        hb = HeartbeatManager(expiry_seconds=10, clock=lambda: clock[0])
        assert hb.register_executor("e1", "host1:1") == []
        peers = hb.register_executor("e2", "host2:1")
        assert [p.executor_id for p in peers] == ["e1"]
        peers = hb.executor_heartbeat("e1")
        assert [p.executor_id for p in peers] == ["e2"]

    def test_expiry(self):
        clock = [0.0]
        hb = HeartbeatManager(expiry_seconds=10, clock=lambda: clock[0])
        hb.register_executor("e1", "h1")
        hb.register_executor("e2", "h2")
        clock[0] = 5.0
        hb.executor_heartbeat("e2")
        clock[0] = 12.0  # e1 silent for 12s -> dead
        assert [p.executor_id for p in hb.known_peers()] == ["e2"]
        with pytest.raises(KeyError):
            hb.executor_heartbeat("e1")


class TestShuffleManager:
    def _round_trip(self, rng, mode, codec="zstd"):
        conf = TpuConf({"spark.rapids.shuffle.mode": mode,
                        "spark.rapids.shuffle.compression.codec": codec})
        mgr = TpuShuffleManager(conf)
        try:
            t = sample_table(rng, 300)
            batch = batch_from_arrow(t)
            sid = next_shuffle_id()
            writer = mgr.get_writer(sid, map_id=0)
            writer.write(0, batch)
            writer.close()
            out = list(mgr.read_partition(sid, 0))
            assert len(out) == 1
            assert batch_to_arrow(out[0]).equals(t)
            mgr.unregister_shuffle(sid)
            if mode == "MULTITHREADED":
                assert mgr.block_store.total_bytes() == 0
        finally:
            mgr.shutdown()

    def test_multithreaded_mode(self, rng):
        self._round_trip(rng, "MULTITHREADED")

    def test_multithreaded_lz4(self, rng):
        self._round_trip(rng, "MULTITHREADED", codec="lz4xla")

    def test_cache_only_mode(self, rng):
        self._round_trip(rng, "CACHE_ONLY")

    def test_query_repartition_through_manager(self, rng):
        # default mode is MULTITHREADED: df.repartition routes device batches
        # through serialize/compress/store/read (the full reference path)
        from spark_rapids_tpu.plugin import TpuSession
        from spark_rapids_tpu.expr import col
        sess = TpuSession({"spark.rapids.sql.enabled": True,
                           "spark.rapids.sql.explain": "NONE"})
        t = sample_table(rng, 400)
        df = sess.from_arrow(t).repartition(4, "a")
        out = df.collect()
        keys = [(k, "ascending") for k in ("a", "b")]
        assert out.sort_by(keys).equals(
            pa.Table.from_arrays(t.columns, names=t.column_names)
            .sort_by(keys))

    def test_multi_map_concat(self, rng):
        conf = TpuConf({"spark.rapids.shuffle.mode": "MULTITHREADED"})
        mgr = TpuShuffleManager(conf)
        try:
            tables = [sample_table(rng, n) for n in (64, 100, 3)]
            sid = next_shuffle_id()
            for m, t in enumerate(tables):
                w = mgr.get_writer(sid, map_id=m)
                w.write(0, batch_from_arrow(t))
                w.close()
            out = list(mgr.read_partition(sid, 0))
            assert len(out) == 1  # single H2D after host concat
            assert batch_to_arrow(out[0]).equals(pa.concat_tables(tables))
        finally:
            mgr.shutdown()


class TestShuffleDiskTier:
    def test_overflow_to_disk_and_back(self, rng, tmp_path):
        # budget far below the shuffle size: most blocks must land on disk
        # and reads must still reassemble exactly (RapidsDiskBlockManager
        # analog)
        conf = TpuConf({"spark.rapids.shuffle.mode": "MULTITHREADED",
                        "spark.rapids.shuffle.hostStoreSize": 4096,
                        "spark.rapids.shuffle.spillPath": str(tmp_path),
                        "spark.rapids.shuffle.compression.codec": "none"})
        mgr = TpuShuffleManager(conf)
        try:
            tables = [sample_table(rng, 500) for _ in range(6)]
            sid = next_shuffle_id()
            for m, t in enumerate(tables):
                w = mgr.get_writer(sid, map_id=m)
                w.write(0, batch_from_arrow(t))
                w.close()
            assert mgr.block_store.disk_block_count() >= 4
            assert mgr.block_store.mem_bytes() <= 4096 or \
                len(tables) == mgr.block_store.disk_block_count() + 1
            out = list(mgr.read_partition(sid, 0))
            got = pa.concat_tables(batch_to_arrow(b) for b in out)
            assert got.equals(pa.concat_tables(tables))
            mgr.unregister_shuffle(sid)
            assert mgr.block_store.total_bytes() == 0
            import os
            assert not [f for f in os.listdir(tmp_path)
                        if f.endswith(".blk")]
        finally:
            mgr.shutdown()

    def test_query_shuffle_over_tiny_budget(self, rng):
        # end-to-end repartition whose blocks exceed the configured host
        # store: the disk tier must keep the query green and exact.
        # Exchange uses the process-singleton manager whose FIRST caller's
        # conf wins — reset around so the tiny budget actually applies and
        # does not leak into later tests.
        from spark_rapids_tpu.plugin import TpuSession
        TpuShuffleManager.reset()
        try:
            sess = TpuSession({"spark.rapids.sql.enabled": True,
                               "spark.rapids.sql.explain": "NONE",
                               "spark.rapids.shuffle.hostStoreSize": 2048})
            t = sample_table(rng, 2000)
            df = sess.from_arrow(t).repartition(8, "a")
            out = df.collect()
            mgr = TpuShuffleManager.get(sess.conf)
            assert mgr.block_store._budget == 2048
            keys = [(k, "ascending") for k in ("a", "b")]
            assert out.sort_by(keys).equals(
                pa.Table.from_arrays(t.columns, names=t.column_names)
                .sort_by(keys))
        finally:
            TpuShuffleManager.reset()
