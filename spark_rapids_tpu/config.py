"""Typed configuration registry.

Design mirrors the reference's `RapidsConf.scala` (ConfBuilder/ConfEntry, reference
`sql-plugin/.../RapidsConf.scala:120-307`; registry `:310+`; docs generation
`RapidsConf.help` `:1874`): every knob is a declared, typed `ConfEntry` with a doc string,
default, optional value-check, `internal` and `startup_only` flags; `TpuConf` wraps a plain
dict of user settings and exposes typed accessors; `generate_docs()` emits
`docs/configs.md`. Per-operator and per-expression enable keys are auto-registered by the
planning layer (`spark.rapids.sql.exec.*` / `.expression.*`), as in the reference.

Key namespace intentionally matches the reference (`spark.rapids.*`) so that reference
users' configs translate 1:1; TPU-specific keys live under `spark.rapids.tpu.*`.
"""

from __future__ import annotations

import os
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

__all__ = ["ConfEntry", "TpuConf", "register", "entries", "generate_docs", "get_default_conf"]

_REGISTRY: Dict[str, "ConfEntry"] = {}
_LOCK = threading.Lock()

_SIZE_RE = re.compile(r"^(\d+(?:\.\d+)?)\s*([kmgt]?i?b?)$", re.IGNORECASE)
_SIZE_MULT = {
    "": 1, "b": 1,
    "k": 1 << 10, "kb": 1 << 10, "kib": 1 << 10,
    "m": 1 << 20, "mb": 1 << 20, "mib": 1 << 20,
    "g": 1 << 30, "gb": 1 << 30, "gib": 1 << 30,
    "t": 1 << 40, "tb": 1 << 40, "tib": 1 << 40,
}


def parse_bytes(v) -> int:
    if isinstance(v, (int, float)):
        return int(v)
    m = _SIZE_RE.match(str(v).strip())
    if not m:
        raise ValueError(f"cannot parse byte size: {v!r}")
    return int(float(m.group(1)) * _SIZE_MULT[m.group(2).lower()])


def _convert(value: Any, typ: str) -> Any:
    if typ == "bool":
        if isinstance(value, bool):
            return value
        return str(value).strip().lower() in ("true", "1", "yes")
    if typ == "int":
        return int(value)
    if typ == "double":
        return float(value)
    if typ == "bytes":
        return parse_bytes(value)
    return str(value)


class ConfEntry:
    def __init__(self, key: str, typ: str, default: Any, doc: str,
                 internal: bool = False, startup_only: bool = False,
                 check_values: Optional[Sequence[Any]] = None,
                 checker: Optional[Callable[[Any], bool]] = None):
        self.key = key
        self.typ = typ
        self.default = default
        self.doc = doc
        self.internal = internal
        self.startup_only = startup_only
        self.check_values = tuple(check_values) if check_values else None
        self.checker = checker

    def convert(self, raw: Any) -> Any:
        v = _convert(raw, self.typ)
        if self.check_values is not None and v not in self.check_values:
            raise ValueError(
                f"{self.key}={v!r} not in allowed values {self.check_values}")
        if self.checker is not None and not self.checker(v):
            raise ValueError(f"{self.key}={v!r} failed validation")
        return v


def register(key: str, typ: str, default: Any, doc: str, **kw) -> ConfEntry:
    with _LOCK:
        if key in _REGISTRY:
            return _REGISTRY[key]
        e = ConfEntry(key, typ, default, doc, **kw)
        _REGISTRY[key] = e
        return e


def entries() -> Dict[str, ConfEntry]:
    return dict(_REGISTRY)


# --------------------------------------------------------------------------------------
# Core key registry. Names follow the reference where a counterpart exists.
# --------------------------------------------------------------------------------------

register("spark.rapids.sql.enabled", "bool", True,
         "Enable the TPU columnar rewrite of SQL physical plans.")
register("spark.rapids.sql.mode", "string", "executeOnGPU",
         "executeOnGPU runs converted plans on TPU; explainOnly only tags and reports "
         "what would run on TPU without converting.",
         check_values=("executeOnGPU", "explainOnly"))
register("spark.rapids.sql.explain", "string", "NONE",
         "Explain output for the plan rewrite: NONE, NOT_ON_GPU (only fallback reasons), "
         "ALL.", check_values=("NONE", "NOT_ON_GPU", "ALL"))
register("spark.rapids.sql.batchSizeBytes", "bytes", 1 << 30,
         "Target device batch size for coalescing (reference default 1GiB).")
register("spark.rapids.sql.batchSizeRows", "int", 1 << 20,
         "Target max rows per device batch.")
register("spark.rapids.sql.concurrentGpuTasks", "int", 2,
         "Number of tasks admitted concurrently to the TPU (GpuSemaphore analog).")
register("spark.rapids.sql.metrics.level", "string", "MODERATE",
         "Operator metric verbosity: ESSENTIAL, MODERATE, DEBUG.",
         check_values=("ESSENTIAL", "MODERATE", "DEBUG"))
register("spark.rapids.tpu.metrics.eventLog.dir", "string", "",
         "Directory for the per-query JSONL profile event log (one "
         "schema-versioned record per query/operator/span, append-only). "
         "Setting it activates the query profiler; empty disables both "
         "the log and all span overhead. scripts/profile_report.sh "
         "consumes these logs offline.")
register("spark.rapids.tpu.metrics.profile.enabled", "bool", False,
         "Collect the in-memory query profile (span tree + per-operator "
         "metric deltas, TpuSession.explain_profile()) without writing an "
         "event log. Implied by spark.rapids.tpu.metrics.eventLog.dir.")
register("spark.rapids.tpu.metrics.eventLog.maxBytes", "bytes", 0,
         "Size cap for the live per-process event-log file: an append "
         "that would push it past this rotates the file to '.1' "
         "(shifting older generations to '.2', ...), bounding a long-"
         "lived server's log on disk. 0 (default) keeps the historical "
         "unbounded append. profile_report reads rotated generations "
         "alongside live files.")
register("spark.rapids.tpu.metrics.eventLog.maxFiles", "int", 10,
         "Rotated event-log generations kept per process ('.1'..'.N'); "
         "the oldest falls off at the next rotation.")

# Live telemetry ---------------------------------------------------------------------
register("spark.rapids.tpu.telemetry.enabled", "bool", False,
         "Live telemetry: the process-wide metrics registry (scheduler "
         "depth/wait, memory, spill tiers, compile cache, shuffle data "
         "plane, per-op throughput), the /metrics + /healthz surface "
         "(HTTP and the service-protocol stats/health ops), and the "
         "incident flight recorder. Off (default) spawns zero threads "
         "and keeps every hot-path hook at one module-global check "
         "(scripts/telemetry_matrix.sh gates it).")
register("spark.rapids.tpu.telemetry.http.port", "int", -1,
         "Port for the stdlib HTTP scrape thread serving /metrics "
         "(Prometheus text) and /healthz (JSON). -1 (default) disables "
         "the HTTP thread entirely — socket-only deployments use the "
         "service-protocol stats/health ops instead; 0 binds an "
         "ephemeral port (tests read it back).")
register("spark.rapids.tpu.telemetry.http.host", "string", "127.0.0.1",
         "Bind address for the telemetry HTTP thread.")
register("spark.rapids.tpu.telemetry.labels.maxCardinality", "int", 64,
         "Max distinct label sets per metric family; further label "
         "values collapse into the '__overflow__' series (totals stay "
         "exact, attribution coarsens) so no label feed can grow the "
         "registry without bound.")
register("spark.rapids.tpu.telemetry.flightRecorder.capacity", "int", 2048,
         "Events held in the incident flight-recorder ring (the most "
         "recent N engine events dumped when a query dies terminally).")
register("spark.rapids.tpu.telemetry.flightRecorder.dir", "string", "",
         "Directory for incident dumps (schema-validated JSONL, one "
         "'incident' header + the ring's 'event' records). Empty falls "
         "back to spark.rapids.tpu.metrics.eventLog.dir; with neither "
         "set, dumps are disabled (the ring still records).")
register("spark.rapids.tpu.telemetry.flightRecorder.rejectStormThreshold",
         "int", 8,
         "Admission rejections within rejectStormWindowSec that count as "
         "a storm and trigger an incident dump (shed queries die without "
         "profiles; the storm dump is their evidence).")
register("spark.rapids.tpu.telemetry.flightRecorder.rejectStormWindowSec",
         "double", 10.0,
         "Sliding window for rejection-storm detection.")
register("spark.rapids.sql.castFloatToString.enabled", "bool", True,
         "Enable float->string cast (Spark-format float printing on host path).")
register("spark.rapids.sql.castStringToFloat.enabled", "bool", True,
         "Enable string->float cast.")
register("spark.rapids.sql.improvedFloatOps.enabled", "bool", True,
         "Allow float ops whose results may differ from CPU Spark in ULPs.")
register("spark.rapids.sql.variableFloatAgg.enabled", "bool", True,
         "Allow float aggregation (non-deterministic ordering => non-bit-identical sums).")
register("spark.rapids.sql.hasNans", "bool", True,
         "Assume float data may contain NaNs (affects agg/join support).")
register("spark.rapids.sql.ansi.enabled", "bool", False,
         "ANSI mode: overflow/invalid-cast raise instead of null/wrap.")
register("spark.sql.ansi.enabled", "bool", False,
         "Host Spark's ANSI switch (honored like the rapids-namespace key).")
register("spark.rapids.sql.tieredProject.enabled", "bool", True,
         "Evaluate projection as tiers of common subexpressions.")
register("spark.rapids.sql.stableSort.enabled", "bool", True,
         "Use stable device sort (required for Spark-identical ordering ties).")
register("spark.rapids.sql.test.enabled", "bool", False,
         "Strict test mode: any CPU fallback in a converted plan raises.")
register("spark.rapids.sql.test.injectRetryOOM", "int", 0,
         "Fault injection: force a RetryOOM on the Nth tracked device allocation "
         "(reference RapidsConf.scala:1250).", internal=True)
register("spark.rapids.sql.test.injectSplitAndRetryOOM", "int", 0,
         "Fault injection: force a SplitAndRetryOOM on the Nth tracked allocation.",
         internal=True)
register("spark.rapids.tpu.test.faults", "string", "",
         "Fault-injection rule specs, ';'-separated `point:kind,k=v...` "
         "(see faults.py for the point catalog and grammar). Installed by "
         "TpuSession.initialize_device; empty disables injection.",
         internal=True)
register("spark.rapids.tpu.test.faults.seed", "int", 42,
         "Seed for probabilistic fault-injection rules, so fault schedules "
         "are reproducible.", internal=True)

# Memory runtime --------------------------------------------------------------------
register("spark.rapids.memory.gpu.allocFraction", "double", 0.9,
         "Fraction of per-chip HBM given to the arena budget "
         "(reference GpuDeviceManager.computeRmmPoolSize).")
register("spark.rapids.memory.gpu.minAllocFraction", "double", 0.25,
         "Minimum HBM fraction; startup fails below this.")
register("spark.rapids.memory.gpu.maxAllocFraction", "double", 1.0,
         "Maximum HBM fraction allowed.")
register("spark.rapids.memory.gpu.reserve", "bytes", 640 << 20,
         "HBM held back from the arena for XLA scratch/fragmentation.")
register("spark.rapids.memory.spill.compression.codec", "string", "zstd",
         "Codec for host-spilled device batches (TableCompressionCodec "
         "analog): none, zstd, or lz4xla (needs the native runtime). Host "
         "accounting uses the compressed size.",
         check_values=("none", "zstd", "lz4xla"))
register("spark.rapids.memory.host.spillStorageSize", "bytes", 1 << 30,
         "Host-RAM spill store capacity before overflowing to disk.")
register("spark.rapids.memory.host.pageablePool.enabled", "bool", True,
         "Allow pageable host fallback when the pinned staging pool is exhausted.")
register("spark.rapids.memory.pinnedPool.size", "bytes", 0,
         "Pinned host staging pool for device transfers (0 = disabled).")
register("spark.rapids.memory.gpu.oomDumpDir", "string", "",
         "If set, dump allocator state to this dir on unrecoverable OOM.")
register("spark.rapids.memory.gpu.state.debug", "string", "",
         "Log allocator state on OOM: stdout/stderr/path.", internal=True)

# Shuffle ---------------------------------------------------------------------------
register("spark.rapids.shuffle.hostStoreSize", "bytes", 1 << 30,
         "Host-memory budget for the MULTITHREADED shuffle block store; "
         "blocks beyond it overflow (FIFO) to files under "
         "spark.rapids.shuffle.spillPath (RapidsDiskBlockManager analog) "
         "so a shuffle larger than host RAM completes.")
register("spark.rapids.shuffle.spillPath", "string", "",
         "Directory for overflowed shuffle blocks (empty = a fresh temp "
         "dir per manager).")
register("spark.rapids.shuffle.mode", "string", "MULTITHREADED",
         "MULTITHREADED: host-serialized threaded shuffle (reference default); "
         "ICI: device-resident collective all-to-all exchange over the mesh "
         "(UCX-mode analog); CACHE_ONLY: device-resident local-only cache.",
         check_values=("MULTITHREADED", "ICI", "CACHE_ONLY"))
register("spark.rapids.shuffle.multiThreaded.writer.threads", "int", 4,
         "Threads parallelizing shuffle serialization/compression/IO on write.")
register("spark.rapids.shuffle.multiThreaded.reader.threads", "int", 4,
         "Threads parallelizing shuffle fetch/decompression on read.")
register("spark.rapids.shuffle.compression.codec", "string", "zstd",
         "Batch compression codec for shuffle buffers: none, zstd, lz4xla (native).",
         check_values=("none", "zstd", "lz4xla"))
register("spark.rapids.shuffle.checksum.enabled", "bool", True,
         "Frame every shuffle block with a CRC32C over its payload, verified "
         "on fetch; a corrupt frame raises ShuffleCorruptionError and is "
         "refetched once before failing the task.")
register("spark.rapids.shuffle.fetch.maxRetries", "int", 3,
         "Retries per peer for a failed remote shuffle fetch (exponential "
         "backoff between attempts) before failing over to another live "
         "peer or raising ShuffleFetchFailedError.")
register("spark.rapids.shuffle.fetch.retryWaitMs", "int", 10,
         "Base backoff between shuffle fetch retries; attempt k waits "
         "2^k times this (capped at 1s).")
register("spark.rapids.shuffle.ici.chunkBytes", "bytes", 64 << 20,
         "Per-step all-to-all chunk size over ICI.")
register("spark.rapids.shuffle.ici.slotRows", "int", 0,
         "Per-destination slot rows for the ICI all-to-all (0 = auto: the "
         "per-device capacity, which can never overflow). Smaller values bound "
         "skew memory; overflow is detected on device and retried larger.")

register("spark.rapids.sql.join.subPartition.rows", "int", 4 << 20,
         "Build sides larger than this hash-split into key-aligned "
         "sub-partitions joined pairwise (GpuSubPartitionHashJoin analog).")

register("spark.rapids.sql.autoBroadcastJoinThreshold", "int", 10 << 20,
         "Build sides estimated at or below this many bytes join via a "
         "host-serialized broadcast exchange (GpuBroadcastExchangeExec "
         "analog) instead of a shuffled join; -1 disables broadcast joins.")

# I/O -------------------------------------------------------------------------------
register("spark.rapids.sql.format.parquet.enabled", "bool", True,
         "Enable TPU parquet scan/write.")
register("spark.rapids.sql.format.parquet.deviceWrite.enabled", "bool", True,
         "Encode parquet writes on device (PLAIN pages; value compaction + "
         "byte marshalling run on TPU, host writes thrift framing). Falls "
         "back to the host writer for strings/nested/partitioned writes.")
register("spark.rapids.sql.format.parquet.reader.type", "string", "AUTO",
         "Reader strategy: AUTO, PERFILE, COALESCING, MULTITHREADED "
         "(reference GpuParquetScan three strategies).",
         check_values=("AUTO", "PERFILE", "COALESCING", "MULTITHREADED"))
register("spark.rapids.sql.format.parquet.multiThreadedRead.numThreads", "int", 20,
         "Global multi-file reader pool size (reference MultiFileReaderThreadPool).")
register("spark.rapids.sql.format.parquet.multiThreadedRead.maxNumFilesParallel", "int",
         2147483647, "Max files fetched in parallel per task.")
register("spark.rapids.sql.format.csv.deviceDecode.enabled", "bool", True,
         "Parse unquoted CSV on device: host frames line boundaries, the "
         "device gathers rows into the byte matrix, splits fields, and "
         "types them through the device cast kernels "
         "(GpuTextBasedPartitionReader analog). Quoted files and "
         "unsupported shapes keep the host reader.")
register("spark.rapids.sql.format.parquet.deviceDecode.enabled", "bool", True,
         "Decode PLAIN-encoded flat numeric parquet pages on device (RLE "
         "def-level expansion + byte bitcast); unsupported chunks fall back "
         "to the pyarrow host path per file.")
register("spark.rapids.sql.format.orc.deviceWrite.enabled", "bool", True,
         "Encode ORC on device (GpuOrcFileFormat analog): PRESENT bitmaps, "
         "RLEv2 DIRECT integer/length runs, IEEE754 lanes and string "
         "blobs render with device kernels; the host writes protobuf "
         "scaffolding only. Unsupported schemas keep the pyarrow writer.")
register("spark.rapids.sql.format.csv.deviceWrite.enabled", "bool", True,
         "Format CSV on device: columns render through the cast-to-string "
         "kernels, rows assemble and flatten with positional gathers, one "
         "D2H ships the finished blob. Cells needing quoting and float "
         "columns keep the host writer.")
register("spark.rapids.delta.checkpointInterval", "int", 10,
         "Write a parquet checkpoint + _last_checkpoint pointer every Nth "
         "Delta commit so log replay is O(commits since checkpoint); 0 "
         "disables periodic checkpointing.")
register("spark.rapids.sql.format.json.deviceDecode.enabled", "bool", True,
         "Parse flat json-lines on device: host frames lines and proves "
         "flatness (no escapes/arrays/nesting) with one vectorized quote-"
         "parity pass, the device splits fields on structural commas, "
         "matches keys to schema names order-independently, and types the "
         "value spans through the device cast kernels (GPU JSON reader "
         "analog). Unsupported files keep the pyarrow host reader.")
register("spark.rapids.sql.format.hiveText.deviceDecode.enabled", "bool",
         True,
         "Parse Hive delimited text on device with LazySimpleSerDe "
         "semantics: \\x01 field splits, \\N nulls, blank lines as rows, "
         "short rows null-padded — the device CSV parse parameterized for "
         "the serde (GpuHiveTableScanExec analog).")
register("spark.rapids.sql.format.orc.enabled", "bool", True, "Enable TPU ORC scan.")
register("spark.rapids.sql.format.orc.deviceDecode.enabled", "bool", True,
         "Decode flat ORC stripes on device, one compile-service program "
         "a column (io.orc.*): RLEv2 runs expand by one mark per run and a "
         "prefix sum with big-endian bit windows read from 32-bit words, "
         "decimals of at most 18 digits fold their zigzag varints from the "
         "value ends, present streams bit-unpack msb-first, dictionary "
         "strings gather from the dictionary's matrix (GpuOrcScan analog). "
         "Unsupported columns and stripes fall back to the pyarrow host "
         "path per column and per stripe, each counted in "
         "TaskMetrics.scan_host_decoded.")
register("spark.rapids.sql.format.csv.enabled", "bool", True, "Enable TPU CSV scan.")
register("spark.rapids.sql.format.json.enabled", "bool", True, "Enable TPU JSON scan.")
register("spark.rapids.sql.format.iceberg.enabled", "bool", True,
         "Enable iceberg table scans (metadata walked natively, data files "
         "ride the TPU parquet scan; row-level deletes unsupported).")
register("spark.rapids.sql.format.avro.enabled", "bool", True,
         "Enable TPU Avro scan (built-in host object-container-file decoder, "
         "io/avro.py; null + deflate codecs).")
register("spark.rapids.cloudSchemes", "string", "s3,s3a,s3n,wasbs,gs,abfs,abfss",
         "URI schemes treated as cloud stores; selects MULTITHREADED reader under AUTO.")

# Planning --------------------------------------------------------------------------
register("spark.rapids.sql.adaptive.enabled", "bool", False,
         "AQE analog: materialize each exchange stage, observe its row count, "
         "and re-run the override planning (and CBO) on the remaining plan.")
register("spark.rapids.sql.adaptive.coalescePartitions.enabled", "bool", True,
         "Under AQE, shrink a staged exchange's partition count toward "
         "advisoryPartitionSizeInBytes using the OBSERVED stage size "
         "(Spark's post-shuffle partition coalescing).")
register("spark.rapids.sql.adaptive.advisoryPartitionSizeInBytes", "bytes",
         64 << 20,
         "Target size of one post-shuffle partition for AQE coalescing and "
         "skew-join splitting.")
register("spark.rapids.sql.adaptive.skewJoin.enabled", "bool", True,
         "Under AQE, split a skewed probe-side hash partition of a staged "
         "join into chunks joined pairwise against the matching build "
         "partition (Spark's OptimizeSkewedJoin).")
register("spark.rapids.sql.adaptive.skewJoin.skewedPartitionFactor",
         "double", 5.0,
         "A partition is skewed when its rows exceed this multiple of the "
         "median partition's rows (and the row threshold).")
register("spark.rapids.sql.adaptive.skewJoin.skewedPartitionRowThreshold",
         "int", 100_000,
         "Minimum rows before a partition can be considered skewed.")
register("spark.rapids.sql.optimizer.enabled", "bool", False,
         "Cost-based optimizer: may move plan sections back to CPU to avoid "
         "transition thrash (reference CostBasedOptimizer).")
register("spark.rapids.sql.optimizer.cpuExecCost", "double", 1.0,
         "Relative per-row CPU operator cost.", internal=True)
register("spark.rapids.sql.optimizer.gpuExecCost", "double", 0.3,
         "Relative per-row TPU operator cost.", internal=True)
register("spark.rapids.sql.optimizer.transitionCost", "double", 10.0,
         "Relative per-row cost of a CPU<->TPU transition.", internal=True)
register("spark.rapids.sql.incompatibleOps.enabled", "bool", True,
         "Allow ops marked incompat (minor semantic differences) on TPU.")
register("spark.rapids.sql.incompatibleDateFormats.enabled", "bool", False,
         "Allow date formats with known corner-case differences.")
register("spark.rapids.sql.regexp.enabled", "bool", True,
         "Enable regular-expression offload via the transpiler (falls back per-pattern).")

# TPU-specific ----------------------------------------------------------------------
register("spark.rapids.sql.dynamicFilePruning.enabled", "bool", True,
         "Prune probe-side parquet files/row groups of a broadcast hash "
         "join using the build side's distinct keys against footer min/max "
         "statistics (the GpuSubqueryBroadcastExec / dynamic partition "
         "pruning analog at file granularity).")
register("spark.rapids.sql.topK.enabled", "bool", True,
         "Rewrite limit-over-sort into a top-k exec (per-batch k-select + "
         "running merge) instead of a full out-of-core sort "
         "(TakeOrderedAndProjectExec analog, GpuOverrides.scala:3705).")
register("spark.rapids.sql.topK.threshold", "int", 10000,
         "Largest LIMIT+OFFSET rewritten into the top-k exec (the "
         "spark.sql.execution.topKSortFallbackThreshold analog). Above it "
         "the planner keeps sort+limit: top-k holds an O(k) candidate "
         "batch device-resident and re-sorts ~2k rows per input batch, "
         "losing the out-of-core sort's spill behavior at large k.")
register("spark.rapids.tpu.string.headWidth", "int", 256,
         "Head width (bytes) of the chunked long-string device layout: "
         "strings longer than this keep their first headWidth bytes in the "
         "rectangular byte matrix and the rest in a shared tail blob with "
         "per-row (offset) spans, so ONE long value no longer widens the "
         "whole column to cap x width (the libcudf offset+data strings "
         "analog). Byte-inspecting kernels on such columns fall back per "
         "op; row-moving ops (filter/join/sort gathers) stay on device.")
register("spark.rapids.tpu.device.ordinal", "int", -1,
         "Which local TPU device to bind (-1 = first).", startup_only=True)
register("spark.rapids.tpu.device.startupTimeoutSec", "double", 60.0,
         "Deadline (seconds) for the FIRST backend touch (device enumeration / "
         "client init). A wedged device runtime raises DeviceStartupError with "
         "diagnostics instead of hanging the query indefinitely (the reference "
         "inspects and fail-fasts executor startup, Plugin.scala:436-459). "
         "<= 0 disables the guard.", startup_only=True)
register("spark.rapids.tpu.padding.minRows", "int", 128,
         "Minimum padded row bucket (lane-aligned).")
register("spark.rapids.tpu.padding.growth", "double", 2.0,
         "Row bucket growth factor (powers of this between min and max).")
register("spark.rapids.tpu.string.maxWidth", "int", 8192,
         "Max per-batch string width for the fixed-width byte-matrix layout; longer "
         "strings fall the batch back to host processing.")
register("spark.rapids.tpu.f64.emulation", "bool", True,
         "Keep float64 math exact (XLA f64 on TPU); if false, DOUBLE computes as f32.")
register("spark.rapids.tpu.mesh.shape", "string", "",
         "Logical device mesh as 'name=N,name=M' (empty = single device).",
         startup_only=True)

# Sharded execution over the ICI mesh (spark_rapids_tpu/mesh/) ------------------------
register("spark.rapids.tpu.mesh.enabled", "bool", False,
         "Sharded execution subsystem (mesh/): with an active "
         "spark.rapids.tpu.mesh.shape and spark.rapids.shuffle.mode=ICI, a "
         "plan pass partitions file/in-memory scans across mesh positions "
         "(row-group/file/row ranges per chip, riding the existing io/ "
         "decoders per shard), resizes safe hash-exchange boundaries to the "
         "mesh, and keeps post-exchange partitions resident on their own "
         "device between pipeline stages (zero-copy per-chip shard handoff "
         "instead of a host-side concat between exchange and join/agg). Off "
         "(default): one conf read per plan, zero mesh modules imported, "
         "byte-identical plans and results.")
register("spark.rapids.tpu.mesh.resizeExchanges", "bool", True,
         "With mesh execution enabled, rewrite plan-level HASH exchange "
         "boundaries whose partition count differs from the mesh size to "
         "mesh-sized exchanges so they ride the ICI collective (partition "
         "count of an internal hash exchange is an engine knob, like AQE "
         "coalescing). Round-robin/range/single specs are never resized — "
         "a mismatched count degrades that exchange to the host data plane "
         "(never a wrong split).")
register("spark.rapids.tpu.mesh.scan.parallel", "bool", False,
         "Decode mesh scan shards on concurrent worker threads (one per "
         "shard). Workers adopt the query's ONE admission hold (the single "
         "mesh-wide door) — they never take per-chip tokens of their own — "
         "and park finished shards as budget-visible, chip-tagged "
         "spillables until the consumer drains them in mesh order.")
register("spark.rapids.tpu.mesh.hbmPerChip", "bytes", 0,
         "Per-chip HBM sub-budget for mesh-resident shard buffers (0 "
         "disables per-chip accounting). Chip-tagged parked buffers charge "
         "their OWN chip's ledger; overflowing one chip spills only that "
         "chip's buffers — a shard spilling on chip 3 never charges or "
         "evicts chip 0.")

# Pipelined execution ----------------------------------------------------------------
register("spark.rapids.tpu.pipeline.enabled", "bool", True,
         "Pipelined execution: bounded-depth background prefetch of "
         "upstream batches at the scan, coalesce-input and result-sink "
         "seams (host-side work overlaps device execution) plus the "
         "fused multi-chunk parquet scan decode and the side-by-side walk "
         "of an ORC stripe's columns. Off restores the strictly "
         "serial pre-pipeline paths — zero prefetch threads, one decode "
         "dispatch group per row-group chunk, one ORC column walked at a "
         "time on the scan's thread.")
register("spark.rapids.tpu.pipeline.prefetch.depth", "int", 2,
         "Max batches a pipeline prefetch thread may run ahead of its "
         "consumer. Prefetched batches are parked as spillable (budget-"
         "visible, spillable under pressure) until the consumer "
         "materializes them, so depth bounds device residency, not just "
         "queue length.")
register("spark.rapids.tpu.pipeline.scan.chunksPerDispatch", "int", 4,
         "Row-group chunks the device parquet scan decodes per fused "
         "dispatch: their control-plane arrays pack into ONE host "
         "buffer, ship in ONE transfer, and expand in ONE compiled "
         "program that emits one merged batch — O(1) dispatches per "
         "scan batch instead of O(columns x chunks). 1 disables chunk "
         "batching (per-row-group decode, the pre-pipeline unit); "
         "ignored when spark.rapids.tpu.pipeline.enabled is false.")

# Scan pushdown ----------------------------------------------------------------------
register("spark.rapids.tpu.scan.pushdown.enabled", "bool", False,
         "Compute on compressed data: fuse supported filter predicates, "
         "pure column projections and global count/min/max/sum aggregates "
         "from the plan into the file scan. The device parquet decode "
         "evaluates pushed predicates directly on dictionary values and "
         "RLE-expanded indices inside the fused multi-chunk program and "
         "late-materializes only surviving rows of projected columns "
         "(aggregate-only queries materialize no row data at all); every "
         "other decode path applies the same predicate/projection exactly "
         "on the decoded batch before emitting. Off (default) leaves "
         "plans byte-identical to the non-pushdown planner with zero "
         "extra state.")
register("spark.rapids.tpu.scan.pushdown.aggregate.enabled", "bool", True,
         "Allow pushing global (non-grouped) count/min/max/sum "
         "aggregates over scan columns into the scan as per-dispatch "
         "partial values merged by a rewritten upstream aggregate. "
         "Integral/date/timestamp/boolean min/max and integral sums "
         "only (exact, order-independent merges); disabled automatically "
         "under ANSI mode. Ignored unless "
         "spark.rapids.tpu.scan.pushdown.enabled is on.")
register("spark.rapids.tpu.scan.pushdown.rowgroup.enabled", "bool", True,
         "Prune whole parquet row groups on the device decode path by "
         "testing the pushed predicate against footer min/max/null-count "
         "statistics before any page bytes are read (conservative: a row "
         "group is skipped only when provably no row can match). Counted "
         "on tpu_scan_rowgroups_pruned_total. Ignored unless "
         "spark.rapids.tpu.scan.pushdown.enabled is on.")

# Whole-stage fusion -----------------------------------------------------------------
register("spark.rapids.tpu.fusion.enabled", "bool", False,
         "Whole-stage fusion: a planner pass (plan/fusion.py) replaces "
         "maximal chains of batch-shape-compatible operators — "
         "expression-only project/filter, broadcast hash-join probe "
         "(inner/left/semi/anti/existence, non-dpp, non-zip), and a "
         "stage-terminal partial hash aggregate — with one fused stage "
         "that compiles through the compile service as a SINGLE device "
         "program: one dispatch per stage per batch, member "
         "intermediates never materialise as ColumnarBatches. Sorts, "
         "windows, exchanges, UDFs, right/full joins and chains under "
         "mesh-resident exchanges break the chain and run unfused. Off "
         "(default) never imports the fusion modules and leaves plans "
         "and results byte-identical to the per-operator paths.")
register("spark.rapids.tpu.fusion.minOps", "int", 2,
         "Minimum member count for a chain to be worth fusing (clamped "
         "to >= 2): shorter chains keep the per-operator kernels, whose "
         "compile cache is warmer across queries. Ignored unless "
         "spark.rapids.tpu.fusion.enabled is on.")
register("spark.rapids.tpu.fusion.pallas.mode", "string", "auto",
         "Backend for the fused stage's hot inner loops (hash-probe "
         "sizing, group-by accumulate): auto uses the hand-written "
         "Pallas kernels (ops/pallas_probe.py, ops/pallas_groupby.py) "
         "on TPU backends and the stock jit lowerings elsewhere; off "
         "forces the jit lowerings everywhere; force runs the Pallas "
         "kernels in interpret mode off-TPU (testing). Both paths are "
         "bit-identical by construction.",
         check_values=("auto", "off", "force"))

# Query scheduler --------------------------------------------------------------------
register("spark.rapids.tpu.sched.enabled", "bool", False,
         "Query scheduler: route device admission (TpuSemaphore and the "
         "device-service token pool) through the priority-weighted fair "
         "admission queue (sched/) with load shedding, per-tenant "
         "weights, deadlines and cooperative cancellation. Off keeps the "
         "exact FIFO paths: a bare BoundedSemaphore in process, FIFO "
         "token grants in the service, zero scheduler state.")
register("spark.rapids.tpu.sched.priority", "int", 0,
         "Default priority for this session's queries (higher = admitted "
         "first under contention; strict priority across levels). "
         "Per-query contexts and the service run_plan header override it.")
register("spark.rapids.tpu.sched.tenant", "string", "default",
         "Tenant id this session's queries are accounted under (fair-"
         "share weights, memory sub-quotas).")
register("spark.rapids.tpu.sched.deadlineMs", "int", 0,
         "Default per-query deadline. A query running (or queued, or "
         "sleeping in a retry backoff) past it unwinds with the typed "
         "DeadlineExceededError; 0 = no deadline.")
register("spark.rapids.tpu.sched.maxQueueDepth", "int", 0,
         "Admission load shedding: a query arriving when this many are "
         "already queued is rejected immediately with QueryRejectedError "
         "(it never touches the device); 0 = unbounded queue.")
register("spark.rapids.tpu.sched.maxQueueWaitMs", "int", 0,
         "Admission load shedding: a query queued longer than this is "
         "rejected in place with QueryRejectedError; 0 = unbounded wait.")
register("spark.rapids.tpu.sched.tenant.weights", "string", "",
         "Per-tenant fair-share weights as 'tenantA=4,tenantB=1' (unlisted "
         "tenants weigh 1). Within a priority level, admission grants are "
         "proportional to weight under sustained contention (stride "
         "scheduling over a per-tenant virtual pass).")
register("spark.rapids.tpu.sched.tenant.quotas", "string", "",
         "Per-tenant device-memory sub-quotas as fractions of the budget, "
         "'tenantA=0.5,tenantB=0.25'. The quota is a hard sub-limit: a "
         "tenant reserving beyond it gets SplitAndRetryOOM immediately — "
         "no spill, since spilling frees other tenants' buffers without "
         "shrinking this tenant's pinned ledger — even while the global "
         "budget has room, so one tenant's out-of-core sort splits down "
         "to its share instead of evicting another tenant's working set. "
         "Empty = no sub-quotas (global budget only).")

# Result & fragment cache ------------------------------------------------------------
register("spark.rapids.tpu.rescache.enabled", "bool", False,
         "Result & fragment cache: transparently reuse materialized "
         "columnar fragments (scan output, shuffle-exchange output, "
         "broadcast payloads) and whole-query results across queries, "
         "keyed by a canonical plan fingerprint (exec tree + bound-"
         "expression reprs + output schema + source-file identity + "
         "result-affecting confs). A whole-query hit answers without "
         "touching the device (no admission token). Off (default) keeps "
         "every execution path byte-for-byte pre-cache: zero threads, "
         "zero state (scripts/rescache_matrix.sh gates it).")
register("spark.rapids.tpu.rescache.maxBytes", "bytes", 512 << 20,
         "Cache capacity across all entries (device fragments count "
         "their batch bytes, host results/blobs their host bytes). "
         "Inserting past it evicts by cost-aware LRU: lowest "
         "(recompute-time x (1+hits)) / bytes goes first, so cheap-to-"
         "recompute bulk leaves before expensive small results. Device "
         "fragments additionally ride the spill catalog's device->host->"
         "disk tiers under memory pressure, independent of this cap.")
register("spark.rapids.tpu.rescache.query.enabled", "bool", True,
         "Cache whole-query results (TpuSession.execute_plan seam). A "
         "hit takes the fast path: the reply is served from the host "
         "copy without device admission.")
register("spark.rapids.tpu.rescache.scan.enabled", "bool", True,
         "Cache file-scan output fragments (TpuFileScanExec seam), "
         "keyed by (path, mtime, size) per file so a rewritten source "
         "recomputes. Scans carrying runtime dynamic-pruning filters "
         "are never cached (their output depends on the join's build "
         "keys).")
register("spark.rapids.tpu.rescache.exchange.enabled", "bool", True,
         "Cache shuffle-exchange output fragments (TpuShuffleExchange"
         "Exec seam; local shuffle modes only — ICI mesh exchanges "
         "produce sharded arrays the spill catalog cannot own).")
register("spark.rapids.tpu.rescache.broadcast.enabled", "bool", True,
         "Cache broadcast payload blobs (TpuBroadcastExchangeExec "
         "seam): the host-serialized build side is reused across "
         "queries, skipping child re-execution and re-serialization.")
register("spark.rapids.tpu.rescache.minRecomputeMs", "double", 0.0,
         "Only store a fragment/result whose recompute cost was at "
         "least this many milliseconds — keeps trivially cheap "
         "fragments from churning the capacity. 0 stores everything.")
register("spark.rapids.tpu.rescache.persist.dir", "string", "",
         "Directory for the persistent whole-query result tier "
         "(CRC32C-framed Arrow blobs, compile-cache discipline: a torn "
         "or poisoned entry is a miss + delete, never a wrong result). "
         "Only entries whose fingerprints carry pure file/delta "
         "identity (no in-memory table ids) persist; staleness is "
         "inside the fingerprint (file mtime/size, delta version), so "
         "rewritten sources miss naturally. A restarted worker answers "
         "previously-hot fingerprints from this tier with zero device "
         "admissions. IO failures degrade the tier to memory-only "
         "(typed PersistenceDegradedWarning + telemetry counter + "
         "flight-recorder incident) — never a failed query. Empty "
         "disables persistence; the in-memory cache still runs.")
register("spark.rapids.tpu.rescache.persist.maxBytes", "bytes", 1 << 30,
         "Capacity of the persistent result tier's directory; storing "
         "past it deletes oldest entries (file mtime) first. One entry "
         "larger than the whole budget is never persisted.")
register("spark.rapids.tpu.rescache.persist.warmup.enabled", "bool", True,
         "Background-reload every persisted result into the in-memory "
         "cache at device init (one `rescache-warmup` thread), so the "
         "first post-restart dashboard hit needs no disk read. Off, "
         "persisted entries still serve lazily on first lookup.")

# Runtime statistics -----------------------------------------------------------------
register("spark.rapids.tpu.stats.enabled", "bool", False,
         "Runtime query statistics: a per-query observer derives per-"
         "operator actuals (output rows/batches, filter selectivity, "
         "join build size and fan-out, per-partition exchange bytes) "
         "from the existing metrics seams, pairs each with the CBO's "
         "plan-time estimate (q-error), and records actuals into a "
         "cardinality history keyed by canonical subplan fingerprints. "
         "Enables TpuSession.explain_analyze() and the profile_report "
         "--stats section. Off (default) creates zero state, spawns "
         "zero threads, and leaves planning byte-identical "
         "(scripts/stats_matrix.sh gates it).")
register("spark.rapids.tpu.stats.feedback.enabled", "bool", False,
         "Optimizer feedback from the statistics history: "
         "cbo.row_estimate / filter selectivity consult observed "
         "actuals before falling back to heuristics (broadcast-vs-"
         "shuffle decisions track real build sizes), and adaptive "
         "execution picks post-shuffle coalesce counts and pre-flags "
         "skewed joins from historical stage sizes without first "
         "staging. Requires spark.rapids.tpu.stats.enabled; off keeps "
         "estimates byte-identical to the static heuristics.")
register("spark.rapids.tpu.stats.history.maxEntries", "int", 4096,
         "In-memory LRU capacity of the cardinality history (one entry "
         "per fingerprinted subtree).")
register("spark.rapids.tpu.stats.history.dir", "string", "",
         "Directory for the persistent statistics tier (CRC32C-framed "
         "JSONL, one record per line; a torn or corrupt line is a miss, "
         "never a wrong stat) so a restarted worker keeps its learned "
         "cardinalities. Only fingerprints without process-local "
         "identity (no in-memory table ids) persist. Empty disables "
         "persistence; the in-memory tier still runs.")
register("spark.rapids.tpu.stats.misestimate.incidentThreshold", "double",
         100.0,
         "q-error at or above which the worst misestimate of a query "
         "dumps a flight-recorder incident (reason 'misestimate') — "
         "evidence for plans that ran with catastrophically wrong "
         "cardinalities. 0 disables the incident hook.")

# Live query introspection -----------------------------------------------------------
register("spark.rapids.tpu.live.enabled", "bool", False,
         "Live query introspection: a per-process registry of in-flight "
         "queries (tenant, trace id, current operator, per-operator "
         "rows/batches sampled from the existing metrics seams) with "
         "progress/ETA estimated against the runtime-statistics history, "
         "a slow-query watchdog thread, and exposure on /queries (HTTP), "
         "the `queries` service op, the fleet-gateway fan-out, and the "
         "tpu_live_* telemetry gauges. Off (default) spawns zero "
         "threads, creates zero state, and keeps every hook at one "
         "module-global check (scripts/liveview_matrix.sh gates it). "
         "Progress fractions and ETAs need spark.rapids.tpu.stats."
         "enabled so fingerprint history exists; without it queries "
         "report rows-only progress.")
register("spark.rapids.tpu.live.slowFactor", "double", 3.0,
         "A query running longer than this multiple of its HISTORICAL "
         "wall time (same statistics-history fingerprint) is flagged by "
         "the watchdog as a flight-recorder `slow_query` incident "
         "carrying the live operator snapshot. Queries with no history "
         "are never flagged (fail-closed, no false positives).")
register("spark.rapids.tpu.live.watchdog.intervalMs", "int", 500,
         "Slow-query watchdog scan cadence over the in-flight registry.")
register("spark.rapids.tpu.live.watchdog.cancel", "bool", False,
         "Let the watchdog CANCEL a flagged slow query through its "
         "CancelToken (the engine unwinds with the typed "
         "QueryCancelledError at its next cooperative checkpoint). Off "
         "(default) only flags and raises the incident.")
register("spark.rapids.tpu.live.debugSignal", "bool", False,
         "Install a SIGUSR2 handler that dumps the flight-recorder ring "
         "plus the live query registry as a schema-valid JSONL incident "
         "(reason `debug_signal`) — a wedged process becomes debuggable "
         "without killing it. Requires the main thread to run "
         "initialize_device.")
register("spark.rapids.tpu.live.recentQueries", "int", 32,
         "Recently finished queries kept (terminal snapshots) in the "
         "live registry's ring for the /queries `recent` section.")

# Compile service --------------------------------------------------------------------
register("spark.rapids.tpu.compile.enabled", "bool", True,
         "Route every kernel compile through the centralized compile "
         "service (keyed program cache + single-flight dedup + compile "
         "accounting). Off = direct per-call-site jax.jit, no caching "
         "policy or metrics.")
register("spark.rapids.tpu.compile.cache.maxPrograms", "int", 512,
         "In-memory LRU capacity of the compile service's program cache "
         "(one entry per op x static-args x input-shape signature).")
register("spark.rapids.tpu.compile.cache.dir", "string", "",
         "Directory for the persistent compile-cache tier (serialized "
         "programs, CRC32C-framed; a corrupt entry is a miss + delete). "
         "Empty disables persistence; the in-memory tier still runs.")
register("spark.rapids.tpu.compile.warmup.enabled", "bool", False,
         "Precompile hot operator programs on a background thread at "
         "device init: preload every persistent-tier entry, then compile "
         "the generic row-movement kernels over warmup.schema x the "
         "padding bucket ladder, so the first query hits warm "
         "executables.")
register("spark.rapids.tpu.compile.warmup.ops", "string",
         "concat,sortpos,slice",
         "Synthetic warmup kernel families: concat (coalesce/exchange "
         "batch concat), sortpos (out-of-core merge position sort), "
         "slice (partition slice).")
register("spark.rapids.tpu.compile.warmup.schema", "string", "long,double",
         "Schema template for synthetic warmup batches (csv of "
         "long,int,double,float,bool,string).")
register("spark.rapids.tpu.compile.warmup.maxRows", "int", 1 << 20,
         "Top of the padding-bucket ladder the synthetic warmup walks.")
register("spark.rapids.tpu.compile.tuner.enabled", "bool", False,
         "Adaptive bucket tuner auto mode: learn a padding-bucket ladder "
         "from observed batch row counts and re-install it every "
         "tuner.interval observations (observation/manual retune() is "
         "always available; auto mode costs one recompile wave per ladder "
         "change).")
register("spark.rapids.tpu.compile.tuner.maxBuckets", "int", 8,
         "Maximum rungs in the learned bucket ladder.")
register("spark.rapids.tpu.compile.tuner.minSamples", "int", 64,
         "Observations required before the tuner's auto mode may retune.")
register("spark.rapids.tpu.compile.tuner.interval", "int", 256,
         "Auto-mode retune cadence (every N observed batches).")

# ---- fleet gateway (spark_rapids_tpu/fleet/) -----------------------------
register("spark.rapids.tpu.fleet.probe.intervalMs", "int", 1000,
         "Fleet gateway: background health-probe cadence per worker. A "
         "crashed worker trips its circuit breaker within roughly this "
         "interval even with zero query traffic; a restarted one is "
         "re-admitted through the breaker's half-open trial probe.")
register("spark.rapids.tpu.fleet.probe.timeoutSec", "double", 2.0,
         "Fleet gateway: per-probe (and per-dispatch connect) socket "
         "timeout. A worker that accepts but never answers within this "
         "counts as a probe failure.")
register("spark.rapids.tpu.fleet.breaker.failures", "int", 3,
         "Fleet gateway: consecutive probe/dispatch failures that trip a "
         "worker's circuit breaker OPEN (no traffic until the cooldown "
         "elapses and a half-open trial succeeds).")
register("spark.rapids.tpu.fleet.breaker.cooldownMs", "int", 5000,
         "Fleet gateway: how long an OPEN breaker blocks all traffic to "
         "its worker before admitting one half-open trial.")
register("spark.rapids.tpu.fleet.maxOutstanding", "int", 0,
         "Fleet gateway: per-worker cap on concurrently dispatched "
         "queries. When EVERY routable worker is at the cap the gateway "
         "sheds at its own door (typed rejected reply) before touching "
         "any worker socket. 0 = uncapped.")
register("spark.rapids.tpu.fleet.failover.maxAttempts", "int", 3,
         "Fleet gateway: total workers tried per run_plan (first "
         "dispatch + failovers) within the caller's deadline. Write "
         "plans never failover once a request may have started "
         "executing, regardless of this budget.")
register("spark.rapids.tpu.fleet.dispatch.timeoutSec", "double", 600.0,
         "Fleet gateway: upstream wait bound for a dispatched run_plan "
         "when the caller supplied no deadline; expiry counts as a "
         "worker connection failure (wedged worker).")
register("spark.rapids.tpu.fleet.routing", "string", "affinity",
         "Fleet gateway routing policy: 'affinity' (default) rendezvous-"
         "hashes the plan fingerprint to a preferred worker, falling "
         "back to power-of-two-choices load routing for "
         "unfingerprintable plans; 'random' disables affinity entirely "
         "(load-only — the CI/bench baseline that shows what affinity "
         "buys).", check_values=("affinity", "random"))
register("spark.rapids.tpu.fleet.drain.timeoutSec", "double", 30.0,
         "Fleet gateway: upper bound on how long a `drain` op with "
         "wait_s may block for the worker's in-flight queries to "
         "finish.")
register("spark.rapids.tpu.fleet.failoverStorm.threshold", "int", 5,
         "Fleet gateway: failovers within failoverStorm.windowSec that "
         "dump one flight-recorder incident (a flapping worker churning "
         "the pool leaves evidence even though individual queries "
         "succeed).")
register("spark.rapids.tpu.fleet.failoverStorm.windowSec", "double", 10.0,
         "Fleet gateway: sliding window for failover-storm detection; "
         "also the per-window incident rate limit.")
register("spark.rapids.tpu.fleet.supervisor.enabled", "bool", False,
         "Fleet supervisor mode: the gateway process spawns and "
         "SUPERVISES its workers — a crashed worker is respawned at the "
         "same socket address with exponential backoff, the prober's "
         "half-open trial re-admits it, and its persistent tiers "
         "(compile cache, result tier, stats history) bring it back "
         "warm. Off (default), the gateway only routes around dead "
         "workers (external process management owns restarts).")
register("spark.rapids.tpu.fleet.supervisor.maxRestarts", "int", 5,
         "Fleet supervisor: lifetime respawn budget per worker. A "
         "worker crashing past it is marked FAILED (flight-recorder "
         "incident; no further respawns) — a crash loop must page "
         "someone, not burn CPU forever.")
register("spark.rapids.tpu.fleet.supervisor.backoffMs", "int", 200,
         "Fleet supervisor: respawn backoff base; doubles per "
         "consecutive restart up to supervisor.backoffMaxMs.")
register("spark.rapids.tpu.fleet.supervisor.backoffMaxMs", "int", 5000,
         "Fleet supervisor: respawn backoff ceiling.")
register("spark.rapids.tpu.fleet.supervisor.checkIntervalMs", "int", 100,
         "Fleet supervisor: how often the monitor thread polls worker "
         "processes for unexpected exits.")


class TpuConf:
    """Instance view over a settings dict, with typed accessors (reference
    `RapidsConf(conf)` `RapidsConf.scala:1973`)."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings: Dict[str, Any] = dict(settings or {})
        # environment overrides, dots->underscores upper (SPARK_RAPIDS_SQL_ENABLED...)
        for key, entry in _REGISTRY.items():
            env = key.upper().replace(".", "_")
            if env in os.environ and key not in self._settings:
                self._settings[key] = os.environ[env]

    def get(self, key: str) -> Any:
        e = _REGISTRY.get(key)
        if e is None:
            # unregistered keys pass through raw (operator enable keys register lazily)
            return self._settings.get(key)
        if key in self._settings:
            return e.convert(self._settings[key])
        return e.default

    def set(self, key: str, value: Any) -> "TpuConf":
        self._settings[key] = value
        if key.startswith("spark.rapids.tpu.padding."):
            # padding params are memoized on the hot bucket path; drop the
            # memo so the next row_bucket sees the new value
            from .columnar import padding
            padding.invalidate_cache()
        elif key.startswith("spark.rapids.tpu.mesh."):
            # the conf->Mesh memo in parallel/mesh.py must not serve a
            # stale mesh after a mid-session conf change (same conf-
            # generation discipline as the padding memo above). Guarded
            # via sys.modules: if the module was never imported there is
            # no cache to invalidate — and importing jax from a bare
            # conf.set would be absurd
            import sys
            m = sys.modules.get("spark_rapids_tpu.parallel.mesh")
            if m is not None:
                m.invalidate_cache()
        return self

    def get_bool(self, key: str, default: bool = True) -> bool:
        v = self.get(key)
        return default if v is None else _convert(v, "bool")

    # Frequently used typed views ----------------------------------------------------
    @property
    def is_sql_enabled(self) -> bool:
        return self.get("spark.rapids.sql.enabled")

    @property
    def is_test_enabled(self) -> bool:
        return self.get("spark.rapids.sql.test.enabled")

    @property
    def explain(self) -> str:
        return self.get("spark.rapids.sql.explain")

    @property
    def is_ansi(self) -> bool:
        return self.get("spark.rapids.sql.ansi.enabled") or \
            self.get("spark.sql.ansi.enabled")

    @property
    def batch_size_bytes(self) -> int:
        return self.get("spark.rapids.sql.batchSizeBytes")

    @property
    def batch_size_rows(self) -> int:
        return self.get("spark.rapids.sql.batchSizeRows")

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get("spark.rapids.sql.concurrentGpuTasks")

    @property
    def shuffle_mode(self) -> str:
        return self.get("spark.rapids.shuffle.mode")

    @property
    def string_max_width(self) -> int:
        return self.get("spark.rapids.tpu.string.maxWidth")

    def is_operator_enabled(self, key: str, incompat: bool = False,
                            disabled_by_default: bool = False) -> bool:
        v = self._settings.get(key)
        if v is not None:
            return _convert(v, "bool")
        if disabled_by_default:
            return False
        if incompat:
            return self.get("spark.rapids.sql.incompatibleOps.enabled")
        return True


_default_conf: Optional[TpuConf] = None


def get_default_conf() -> TpuConf:
    global _default_conf
    if _default_conf is None:
        _default_conf = TpuConf()
    return _default_conf


def generate_docs() -> str:
    """Emit docs/configs.md content (reference RapidsConf.help)."""
    lines: List[str] = [
        "# Configuration\n",
        "All configuration keys, their defaults and meaning. Generated by "
        "`spark_rapids_tpu.config.generate_docs()`.\n",
        "| Key | Default | Meaning |", "|---|---|---|",
    ]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        doc = e.doc.replace("|", "\\|")
        lines.append(f"| `{key}` | {e.default!r} | {doc} |")
    return "\n".join(lines) + "\n"
