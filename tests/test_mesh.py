"""Sharded-execution suite (spark_rapids_tpu/mesh/, marker `mesh`).

Every query-level test compares the 8-virtual-device mesh run against the
CPU engine and asserts the specific mesh mechanism under test actually
engaged (collectives executed, shards produced, residency held, or —
for the mismatch cases — that the host path took over CLEANLY). The
off-path tests pin the established contract: mesh disabled means
byte-identical plans, zero new threads, zero mesh plan activity.
"""

import threading

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec import exchange as EX
from spark_rapids_tpu.expr import Count, Max, Min, Sum, col
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils.metrics import TaskMetrics

from test_queries import assert_same, make_table

pytestmark = pytest.mark.mesh

NDEV = 8

MESH_CONF = {
    "spark.rapids.sql.enabled": True,
    "spark.rapids.sql.explain": "NONE",
    "spark.rapids.shuffle.mode": "ICI",
    # pin the shuffled-exchange path — a small dim would otherwise
    # broadcast and skip the collective under test
    "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
    "spark.rapids.tpu.mesh.shape": f"shuffle={NDEV}",
    "spark.rapids.tpu.mesh.enabled": True,
}


@pytest.fixture(scope="module")
def session():
    return TpuSession(dict(MESH_CONF))


def make_dim(rng, n=120, key_space=300):
    keys = rng.permutation(key_space)[:n]
    return pa.table({
        "id": pa.array(keys, type=pa.int64()),
        "w": pa.array(rng.uniform(0.5, 1.5, n), type=pa.float64()),
        "tag": pa.array([f"t{k % 7}" for k in keys]),
    })


def make_fact(rng, n=2500, key_space=300):
    return pa.table({
        "id": pa.array(rng.integers(0, key_space, n), type=pa.int64()),
        "val": pa.array(rng.uniform(-1, 1, n), type=pa.float64()),
        "small": pa.array(rng.integers(-100, 100, n), type=pa.int32()),
    })


def find_exec(node, cls):
    if isinstance(node, cls):
        return node
    for c in node.children:
        r = find_exec(c, cls)
        if r is not None:
            return r
    return None


class TestShardedScan:
    def test_parquet_rowgroup_shards_end_to_end(self, session, rng,
                                                tmp_path):
        """The acceptance shape: planned scan->filter->exchange->join->agg
        with mesh.shape=8 executes its exchanges as mesh collectives —
        MESH_EXCHANGES > 0, zero host-shuffle bytes — bit-identical to
        the CPU engine, with the parquet scan sharded at row-group
        granularity across the chips."""
        import pyarrow.parquet as pq
        path = str(tmp_path / "fact.parquet")
        pq.write_table(make_fact(rng, n=3000), path, row_group_size=256)
        dim = session.from_arrow(make_dim(rng))
        q = (session.read_parquet(path).filter(col("val") > -0.5)
             .join(dim, on="id", how="inner")
             .group_by("tag").agg(n=Count(col("val")), s=Sum(col("small")),
                                  mx=Max(col("id")), mn=Min(col("small"))))
        before = EX.MESH_EXCHANGES
        TaskMetrics.reset()
        assert_same(q, sort_by=["tag"])
        tm = TaskMetrics.get()
        assert EX.MESH_EXCHANGES > before, "no mesh collective executed"
        assert tm.mesh_exchanges > 0
        assert tm.mesh_shards >= NDEV, "scan was not sharded"
        assert tm.mesh_ici_bytes > 0
        assert len(tm.mesh_out_devices) == NDEV, \
            f"exchange output sits on devices {tm.mesh_out_devices}"
        assert tm.shuffle_bytes_written == 0, \
            "mesh run moved bytes over the host shuffle data plane"
        assert "meshExchanges=" in tm.explain_string()

    def test_scan_shards_are_per_device_and_complete(self, session, rng):
        """MeshShardedScanExec yields exactly ndev batches, one committed
        to each mesh device, whose union is the input table."""
        import jax
        from spark_rapids_tpu.mesh.shard import MeshShardedScanExec
        from spark_rapids_tpu.plan.overrides import Overrides
        t = make_fact(rng, n=2000)
        session.initialize_device()
        q = (session.from_arrow(t)
             .join(session.from_arrow(make_dim(rng)), on="id", how="inner"))
        plan = Overrides(session.conf).apply(q.plan)
        scan = find_exec(plan, MeshShardedScanExec)
        assert scan is not None, "plan pass did not shard the scan"
        batches = list(scan.execute())
        assert len(batches) == NDEV
        devs = set()
        total = 0
        for b in batches:
            d = b.columns[0].data.devices()
            assert len(d) == 1 and b.columns[0].data.committed
            devs.add(next(iter(d)))
            total += int(b.row_count())
        assert len(devs) == NDEV, "shards not spread across the mesh"
        assert total == t.num_rows

    def test_resident_exchange_output_devices(self, session, rng):
        """The exchange feeding a zipped join is marked device-resident
        and hands out one committed single-device batch per chip — the
        'partitions stay on-device between exchange and join' contract
        (no gather to a replicated layout, no host concat)."""
        from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
        from spark_rapids_tpu.plan.overrides import Overrides
        session.initialize_device()
        q = (session.from_arrow(make_fact(rng, n=1500))
             .join(session.from_arrow(make_dim(rng)), on="id", how="inner"))
        plan = Overrides(session.conf).apply(q.plan)
        ex = find_exec(plan, TpuShuffleExchangeExec)
        assert ex is not None and ex.mesh_resident_out
        outs = list(ex.execute())
        assert len(outs) == NDEV
        devs = set()
        for b in outs:
            d = b.columns[0].data.devices()
            assert len(d) == 1 and b.columns[0].data.committed
            devs.add(next(iter(d)))
        assert len(devs) == NDEV

    def test_host_fallback_honors_shard_ranges(self, session, rng,
                                               tmp_path):
        """deviceDecode flipped off AFTER planning: shard clones fall to
        the host decode, which must still honor the row-group
        restriction — 8 shards re-reading the whole file would be a
        duplicated (wrong) split, not a slow one."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu.mesh.shard import MeshShardedScanExec
        from spark_rapids_tpu.plan.overrides import Overrides
        n = 2000
        path = str(tmp_path / "fact.parquet")
        pq.write_table(make_fact(rng, n=n), path, row_group_size=128)
        session.initialize_device()
        q = session.read_parquet(path).repartition(NDEV, "id")
        plan = Overrides(session.conf).apply(q.plan)
        scan = find_exec(plan, MeshShardedScanExec)
        assert scan is not None
        key = "spark.rapids.sql.format.parquet.deviceDecode.enabled"
        session.conf.set(key, False)
        try:
            total = sum(int(b.row_count()) for b in scan.execute())
        finally:
            session.conf.set(key, True)
        assert total == n, \
            f"host fallback duplicated the shard split: {total} != {n}"

    @pytest.mark.slow
    def test_string_keys_ride_the_mesh(self, session, rng):
        """String group keys (lengths plane, no overflow) flow through
        the collective and the aligned per-shard assembly."""
        df = session.from_arrow(make_table(rng, n=1200))
        q = df.group_by("cat").agg(n=Count(col("id")),
                                   mx=Max(col("small")))
        before = EX.MESH_EXCHANGES
        assert_same(q, sort_by=["cat"])
        assert EX.MESH_EXCHANGES > before

    @pytest.mark.slow
    def test_parallel_shard_decode_one_admission_door(self, rng, tmp_path):
        """8 concurrent shard decode workers, ONE admission: workers
        adopt the query's hold (mesh/admission.py) — sched_admissions
        stays 1 and every worker thread is joined before the query
        returns."""
        import pyarrow.parquet as pq
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        path = str(tmp_path / "fact.parquet")
        pq.write_table(make_fact(rng, n=2400), path, row_group_size=256)
        conf = dict(MESH_CONF)
        conf["spark.rapids.tpu.mesh.scan.parallel"] = True
        conf["spark.rapids.tpu.sched.enabled"] = True
        sess = TpuSession(conf)
        sess.initialize_device()
        TpuSemaphore.initialize(sess.conf.concurrent_tpu_tasks, sess.conf)
        try:
            threads0 = threading.active_count()
            q = (sess.read_parquet(path).filter(col("val") > 0)
                 .group_by("id").agg(s=Sum(col("small"))))
            TaskMetrics.reset()
            tpu = q.collect().sort_by("id")
            tm = TaskMetrics.get()
            cpu = q.collect_cpu().sort_by("id")
            assert tpu.equals(cpu)
            assert tm.sched_admissions == 1, \
                f"per-shard token storm: {tm.sched_admissions} admissions"
            assert threading.active_count() <= threads0, \
                "mesh shard worker threads leaked"
        finally:
            TpuSemaphore._instance = None


class TestPartitionCountMismatch:
    def test_hash_repartition_resized_to_mesh(self, session, rng):
        """repartition(5, key) under the mesh: the plan pass resizes the
        hash exchange to mesh.size so it rides the collective."""
        df = session.from_arrow(make_fact(rng, n=1500))
        q = df.repartition(5, "id").group_by("id").agg(
            s=Sum(col("small")), n=Count(col("val")))
        before = EX.MESH_EXCHANGES
        TaskMetrics.reset()
        assert_same(q, sort_by=["id"])
        assert EX.MESH_EXCHANGES > before
        assert TaskMetrics.get().mesh_degraded == 0

    def test_roundrobin_mismatch_degrades_cleanly(self, session, rng):
        """repartition(5) (round-robin — partition membership is
        positional, never resized) must degrade to the host data plane:
        correct results, degrade counted, no wrong split."""
        df = session.from_arrow(make_fact(rng, n=1200))
        q = df.repartition(5).group_by("id").agg(s=Sum(col("small")))
        TaskMetrics.reset()
        assert_same(q, sort_by=["id"])
        assert TaskMetrics.get().mesh_degraded >= 1

    def test_range_mismatch_degrades_cleanly(self, session, rng):
        df = session.from_arrow(make_fact(rng, n=1200))
        q = df.repartition_by_range(5, "id")
        TaskMetrics.reset()
        assert_same(q, sort_by=["id", "val"])
        assert TaskMetrics.get().mesh_degraded >= 1

    @pytest.mark.slow
    def test_resize_off_degrades_cleanly(self, rng):
        """With resizeExchanges off a mismatched hash exchange keeps its
        partition count and takes the host path — never a wrong split."""
        conf = dict(MESH_CONF)
        conf["spark.rapids.tpu.mesh.resizeExchanges"] = False
        sess = TpuSession(conf)
        df = sess.from_arrow(make_fact(rng, n=1200))
        q = df.repartition(5, "id").group_by("id").agg(
            s=Sum(col("small")))
        TaskMetrics.reset()
        assert_same(q, sort_by=["id"])
        assert TaskMetrics.get().mesh_degraded >= 1


class TestPerChipMemory:
    def _conf(self, per_chip):
        conf = TpuSession(dict(MESH_CONF)).conf
        conf.set("spark.rapids.tpu.mesh.hbmPerChip", per_chip)
        return conf

    def test_chip_ledger_spills_one_chip_only(self, rng):
        """Chip-tagged parked buffers charge their OWN chip; overflowing
        chip 3's sub-budget spills only chip-3 buffers — chip 0's stay
        device-resident (the per-chip half of the PR-6 quota model)."""
        from spark_rapids_tpu.columnar.batch import batch_from_dict
        from spark_rapids_tpu.memory.budget import MemoryBudget
        from spark_rapids_tpu.memory.catalog import (BufferCatalog,
                                                     StorageTier)
        from spark_rapids_tpu.memory.spillable import SpillableColumnarBatch

        def mk_batch():
            return batch_from_dict(
                {"v": rng.normal(size=4096)})

        probe = mk_batch().device_memory_size()
        old_budget = MemoryBudget._instance
        old_catalog = BufferCatalog._instance
        try:
            BufferCatalog._instance = BufferCatalog()
            MemoryBudget.initialize(1 << 40, self._conf(int(probe * 2.5)))
            assert MemoryBudget.get().chip_budgets, \
                "per-chip budgets not configured"
            chip0 = SpillableColumnarBatch(mk_batch(), chip=0)
            chip3 = [SpillableColumnarBatch(mk_batch(), chip=3)
                     for _ in range(4)]  # ~4x a 2.5x budget => must spill
            cat = BufferCatalog.get()
            assert cat.tier_of(chip0._handle) == StorageTier.DEVICE, \
                "chip-0 buffer evicted by chip-3 pressure"
            spilled3 = sum(cat.tier_of(sp._handle) != StorageTier.DEVICE
                           for sp in chip3)
            assert spilled3 >= 1, "chip-3 overflow did not spill"
            b = MemoryBudget.get()
            assert b.chip_used.get(3, 0) <= b.chip_budgets[3]
            assert b.chip_used.get(0, 0) == probe
            for sp in [chip0] + chip3:
                sp.close()
            assert b.chip_used.get(0, 0) == 0
            assert b.chip_used.get(3, 0) == 0
        finally:
            MemoryBudget._instance = old_budget
            BufferCatalog._instance = old_catalog

    @pytest.mark.slow
    def test_mesh_query_under_tenant_quota(self, rng):
        """A mesh-active query under a PR-6 tenant sub-quota completes
        bit-identically (over-quota steps split, never evict neighbours)
        and drains its tenant ledger."""
        from spark_rapids_tpu.memory.budget import MemoryBudget
        conf = dict(MESH_CONF)
        conf["spark.rapids.tpu.sched.tenant"] = "t1"
        conf["spark.rapids.tpu.sched.tenant.quotas"] = "t1=0.5"
        sess = TpuSession(conf)
        old_budget = MemoryBudget._instance
        try:
            sess.initialize_device()
            MemoryBudget.initialize(1 << 30, sess.conf)
            q = (sess.from_arrow(make_fact(rng, n=1500))
                 .join(sess.from_arrow(make_dim(rng)), on="id",
                       how="inner")
                 .group_by("tag").agg(n=Count(col("val"))))
            tpu = q.collect().sort_by("tag")
            cpu = q.collect_cpu().sort_by("tag")
            assert tpu.equals(cpu)
            b = MemoryBudget.get()
            assert b.tenant_used.get("t1", 0) == 0, \
                "tenant ledger not drained after the mesh query"
        finally:
            MemoryBudget._instance = old_budget


class TestRescacheIciSeam:
    @pytest.mark.slow
    def test_exchange_fragments_replay_on_mesh(self, rng):
        """The rescache exchange seam is un-gated for ICI under mesh
        execution: a repeated subplan replays its mesh-exchanged
        partitions from chip-tagged spillables — second run answers with
        cache hits, zero new collectives, identical bytes."""
        from spark_rapids_tpu import rescache
        conf = dict(MESH_CONF)
        conf["spark.rapids.tpu.rescache.enabled"] = True
        conf["spark.rapids.tpu.rescache.exchange.enabled"] = True
        conf["spark.rapids.tpu.rescache.query.enabled"] = False
        conf["spark.rapids.tpu.rescache.scan.enabled"] = False
        sess = TpuSession(conf)
        try:
            fact = make_fact(rng, n=1500)
            dim = make_dim(rng)

            def q():
                return (sess.from_arrow(fact)
                        .join(sess.from_arrow(dim), on="id", how="inner")
                        .group_by("tag").agg(n=Count(col("val")),
                                             s=Sum(col("small"))))
            cold = q().collect().sort_by("tag")
            before = EX.MESH_EXCHANGES
            TaskMetrics.reset()
            warm = q().collect().sort_by("tag")
            tm = TaskMetrics.get()
            assert warm.equals(cold)
            assert tm.rescache_hits > 0, "exchange seam did not replay"
            assert EX.MESH_EXCHANGES == before, \
                "warm run re-executed the collective"
        finally:
            rescache.shutdown()


class TestMeshOffPath:
    def test_off_plans_and_results_byte_identical(self, rng):
        """mesh.enabled=false (even with a mesh shape configured) is the
        established off contract: plans byte-identical to a no-mesh
        session, zero new threads, zero mesh plan activity."""
        import spark_rapids_tpu.mesh as mesh
        from spark_rapids_tpu.plan.overrides import Overrides
        fact = make_fact(rng, n=1000)
        dim = make_dim(rng)

        def tree(s):
            q = (s.from_arrow(fact).join(s.from_arrow(dim), on="id",
                                         how="inner")
                 .group_by("tag").agg(n=Count(col("val"))))
            return Overrides(s.conf).apply(q.plan).tree_string(), q
        plans_before = mesh.MESH_PLANS
        threads0 = threading.active_count()
        s_plain = TpuSession({"spark.rapids.sql.enabled": True,
                              "spark.rapids.sql.explain": "NONE"})
        off_conf = {"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.explain": "NONE",
                    "spark.rapids.tpu.mesh.shape": f"shuffle={NDEV}",
                    "spark.rapids.tpu.mesh.enabled": False}
        s_off = TpuSession(off_conf)
        t_plain, _ = tree(s_plain)
        t_off, q_off = tree(s_off)
        assert t_plain == t_off, "mesh-off plan differs from no-mesh plan"
        assert "MeshShardedScanExec" not in t_off
        assert mesh.MESH_PLANS == plans_before, \
            "mesh plan pass engaged while disabled"
        assert threading.active_count() <= threads0
        assert_same(q_off, sort_by=["tag"])

    def test_mesh_needs_ici_mode(self, rng):
        """mesh.enabled with a non-ICI shuffle mode never engages the
        pass (the data plane IS the point)."""
        import spark_rapids_tpu.mesh as mesh
        conf = dict(MESH_CONF)
        conf["spark.rapids.shuffle.mode"] = "MULTITHREADED"
        sess = TpuSession(conf)
        before = mesh.MESH_PLANS
        q = (sess.from_arrow(make_fact(rng, n=800))
             .group_by("id").agg(s=Sum(col("small"))))
        assert_same(q, sort_by=["id"])
        assert mesh.MESH_PLANS == before


class TestConfMeshCache:
    def test_mesh_from_conf_invalidates_on_set(self):
        """The `_CONF_MESH` memo drops whenever a mesh conf key changes
        via TpuConf.set — the same conf-generation invalidation the
        padding memo got in PR 3 (no stale mesh mid-session)."""
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.parallel import mesh as pmesh
        conf = TpuConf({"spark.rapids.tpu.mesh.shape": f"shuffle={NDEV}"})
        m1 = pmesh.mesh_from_conf(conf)
        assert m1 is not None and pmesh._CONF_MESH
        conf.set("spark.rapids.tpu.mesh.shape", "shuffle=4")
        assert not pmesh._CONF_MESH, \
            "conf.set on a mesh key did not invalidate the mesh cache"
        m2 = pmesh.mesh_from_conf(conf)
        assert m2 is not None and m2.size == 4
        conf.set("spark.rapids.tpu.mesh.enabled", True)
        assert not pmesh._CONF_MESH


class TestSurfacing:
    @pytest.mark.slow
    def test_telemetry_counters_and_chip_gauge(self, rng):
        """tpu_mesh_exchanges_total / tpu_mesh_ici_bytes_total move on
        the scrape surface for a mesh query; the per-chip HBM gauge
        renders from the budget singleton."""
        from spark_rapids_tpu import telemetry
        conf = dict(MESH_CONF)
        conf["spark.rapids.tpu.telemetry.enabled"] = True
        conf["spark.rapids.tpu.telemetry.http.port"] = -1
        sess = TpuSession(conf)
        try:
            telemetry.configure(sess.conf)
            q = (sess.from_arrow(make_fact(rng, n=1200))
                 .group_by("id").agg(s=Sum(col("small"))))
            q.collect()
            text = telemetry.render_prometheus()
            assert "tpu_mesh_exchanges_total" in text
            ln = [l for l in text.splitlines()
                  if l.startswith("tpu_mesh_exchanges_total")]
            assert ln and float(ln[0].rsplit(" ", 1)[1]) >= 1
            assert "tpu_mesh_ici_bytes_total" in text
        finally:
            telemetry.shutdown()

    def test_report_mesh_summary(self):
        from spark_rapids_tpu.tools.profile_report import mesh_summary
        model = {"queries": [
            {"task_metrics": {"mesh_exchanges": 3, "mesh_ici_bytes": 1024,
                              "mesh_shards": 16, "mesh_degraded": 1}},
            {"task_metrics": {}},
        ]}
        s = mesh_summary(model)
        assert s == {"queries": 1, "exchanges": 3, "ici_bytes": 1024,
                     "shards": 16, "degraded": 1}
        assert mesh_summary({"queries": [{"task_metrics": {}}]}) == {}
