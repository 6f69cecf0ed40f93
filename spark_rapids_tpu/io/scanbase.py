"""File scan plan nodes + TPU scan exec shared across formats.

Reference counterparts: `GpuFileSourceScanExec.scala` (exec), format readers
(`GpuParquetScan.scala`, `GpuOrcScan.scala`, `GpuCSVScan.scala`, JSON under
`catalyst/json/rapids`). Host decode is Arrow; device transfer per batch. Column
pruning is pushed into the decode; row-group/predicate pushdown where the format
library supports it (parquet filters)."""

from __future__ import annotations

from struct import error as struct_error
from typing import Callable, Iterator, List, Optional, Sequence

import pyarrow as pa

from ..columnar.batch import Schema
from ..config import TpuConf, get_default_conf
from ..cpu.hostbatch import HostBatch, host_batch_from_arrow
from ..plan.nodes import PhysicalPlan
from .multifile import FileBatchIterator


class CpuFileScanExec(PhysicalPlan):
    """CPU plan node for a file scan; format subclasses provide decode_fn and the
    schema. The TPU conversion wraps the same iterator with device transfer."""

    format_name = "file"

    def __init__(self, paths: Sequence[str], conf: TpuConf = None,
                 columns: Optional[List[str]] = None, **options):
        super().__init__([])
        self.paths = [str(p) for p in paths]
        self.conf = conf or get_default_conf()
        self.columns = columns
        self.options = options
        schema = self._infer_schema()
        if columns and list(schema.names) != list(columns):
            # prune the declared schema too, not just the data — downstream
            # expression binding uses plan.output ordinals
            idx = [schema.names.index(c) for c in columns]
            schema = Schema(tuple(schema.names[i] for i in idx),
                            tuple(schema.types[i] for i in idx))
        self._schema = schema

    # -- format hooks ---------------------------------------------------------
    def _infer_schema(self) -> Schema:
        raise NotImplementedError

    def decode_file(self, path: str) -> pa.Table:
        raise NotImplementedError

    # -------------------------------------------------------------------------
    @property
    def output(self) -> Schema:
        return self._schema

    def _postprocess(self, t: pa.Table) -> pa.Table:
        """Shared post-decode fixups for ALL formats: column pruning (so the
        data always matches self.output) and Spark timestamp normalization
        (us/UTC) — format decoders may skip either."""
        if self.columns and t.schema.names != list(self.columns):
            t = t.select([c for c in self.columns if c in t.schema.names])
        return normalize_timestamps(t)

    # -- footer statistics (CBO seam; reference CostBasedOptimizer reads
    # Spark's relation stats, this engine reads the format footers) -------
    def footer_row_count(self) -> Optional[int]:
        """EXACT total row count from file metadata when the format is
        cheap to ask (parquet/orc footers); None otherwise. Cached."""
        if not hasattr(self, "_footer_rows"):
            self._footer_rows = self._read_footer_rows()
        return self._footer_rows

    def _footer_metas(self):
        """Parsed parquet FileMetaData per path, read ONCE (row-count and
        column-stats both consume it); None on any failure."""
        if not hasattr(self, "_footer_meta_cache"):
            try:
                import pyarrow.parquet as pq
                self._footer_meta_cache = [pq.ParquetFile(p).metadata
                                           for p in self.paths]
            except Exception:
                self._footer_meta_cache = None
        return self._footer_meta_cache

    def _read_footer_rows(self) -> Optional[int]:
        try:
            if self.format_name == "parquet":
                metas = self._footer_metas()
                return None if metas is None else \
                    sum(m.num_rows for m in metas)
            if self.format_name == "orc":
                from pyarrow import orc
                return sum(orc.ORCFile(p).nrows for p in self.paths)
        except Exception:
            return None
        return None

    def column_stats(self) -> dict:
        """{column: (min, max)} merged across files/row groups from parquet
        footer statistics (empty for other formats / missing stats).
        Cached; errors yield no stats — estimation only."""
        if hasattr(self, "_col_stats"):
            return self._col_stats
        stats: dict = {}
        try:
            if self.format_name == "parquet":
                for meta in (self._footer_metas() or ()):
                    sch = meta.schema
                    for i in range(len(sch)):
                        name = sch.column(i).path
                        for rg in range(meta.num_row_groups):
                            st = meta.row_group(rg).column(i).statistics
                            if st is None or not st.has_min_max:
                                continue
                            cur = stats.get(name)
                            if cur is None:
                                stats[name] = (st.min, st.max)
                            else:
                                stats[name] = (min(cur[0], st.min),
                                               max(cur[1], st.max))
        except Exception:
            stats = {}
        self._col_stats = stats
        return stats

    def host_tables(self, paths: Optional[Sequence[str]] = None
                    ) -> Iterator[pa.Table]:
        for t in FileBatchIterator(self.paths if paths is None else paths,
                                   self.decode_file, self.conf,
                                   format_name=self.format_name):
            yield self._postprocess(t)

    def execute_cpu(self) -> Iterator[HostBatch]:
        for t in self.host_tables():
            yield host_batch_from_arrow(t)

    def _arg_string(self):
        return f"[{self.format_name}, {len(self.paths)} files]"


def normalize_timestamps(t: pa.Table) -> pa.Table:
    """Any-unit/any-tz timestamps -> us/UTC (Spark TimestampType semantics)."""
    new_cols = []
    changed = False
    for f in t.schema:
        col = t.column(f.name)
        if pa.types.is_timestamp(f.type) and (f.type.unit != "us"
                                              or f.type.tz != "UTC"):
            col = col.cast(pa.timestamp("us", tz="UTC"))
            changed = True
        new_cols.append(col)
    if not changed:
        return t
    return pa.table(new_cols, names=t.schema.names)


from ..exec.base import TpuExec as _TpuExec  # noqa: E402


class TpuFileScanExec(_TpuExec):
    """Device exec over a file scan (GpuFileSourceScanExec analog)."""

    # Pushed-down predicate/projection/aggregates, set by
    # plan/scan_pushdown.install_pushdown. CLASS attribute: un-pushed
    # scans carry zero extra state and unchanged fingerprints; a pushed
    # scan's instance attribute renders its param-faithful repr into the
    # rescache/fleet fingerprint and every pushdown program key.
    pushed = None

    # Mesh shard restriction ({path: frozenset(row_group)}), set only on
    # the per-shard clones mesh/shard.MeshShardedScanExec builds: each
    # mesh position decodes its own row-group range of the file. CLASS
    # attribute: ordinary scans carry zero extra state.
    shard_rgs = None

    def __init__(self, plan: CpuFileScanExec, conf: TpuConf):
        super().__init__([], conf)
        self.cpu_scan = plan
        # DynamicKeyFilter list wired in by the planner (DPP analog); the
        # broadcast join fills values before this exec's stream is pulled
        self.dynamic_filters: list = []
        from ..utils import metrics as M
        self.files_pruned = self.metrics.create("filesPruned", M.MODERATE)
        # per-column host fallbacks chosen by the footer sweep (one count
        # per file x column) — makes silent device-path disengagement
        # visible in explain/metrics
        self.cols_host_decoded = self.metrics.create("colsHostDecoded",
                                                     M.MODERATE)
        if plan.format_name == "orc":
            # the ORC path's fallbacks and host walk, per executed stripe:
            # stripes pyarrow decoded whole, every unit it decoded (a file,
            # a stripe, a stripe's column), RLE runs the host walked and
            # bytes of decimal varint streams it checked and shipped
            self.stripes_host_decoded = self.metrics.create(
                "stripesHostDecoded", M.MODERATE)
            self.host_decoded_units = self.metrics.create(
                "hostDecodedUnits", M.MODERATE)
            self.orc_runs_walked = self.metrics.create("orcRunsWalked",
                                                       M.MODERATE)
            self.orc_varint_bytes = self.metrics.create("orcVarintBytes",
                                                        M.MODERATE)
        if plan.format_name == "parquet":
            # the parquet decode programs' per-slot gathers, per executed
            # dispatch group: stacked gathers lowered into them, and
            # gathers by an index known to be the slot itself not made
            # (`parquet_device._Tally`)
            self.parquet_stacked_gathers = self.metrics.create(
                "parquetStackedGathers", M.MODERATE)
            self.parquet_gathers_elided = self.metrics.create(
                "parquetGathersElided", M.MODERATE)
        # decode/read wall time per produced batch (host or device path)
        self.read_time = self.metrics.create(M.READ_TIME, M.MODERATE)

    @property
    def output(self) -> Schema:
        if self.pushed is not None:
            return self._pushed_schema
        return self.cpu_scan.output

    @property
    def name(self):
        return f"TpuFileScanExec({self.cpu_scan.format_name})"

    # -- scan pushdown (plan/scan_pushdown.py) -----------------------------
    def _pushdown_applier(self):
        """Exact batch-level applier, built lazily once per scan."""
        ap = getattr(self, "_pd_applier", None)
        if ap is None:
            from ..plan.scan_pushdown import PushdownApplier
            ap = PushdownApplier(self.cpu_scan.output, self.pushed,
                                 self.conf)
            self._pd_applier = ap
        return ap

    def _device_pushdown(self):
        """Device-form spec for the parquet compressed-domain decode."""
        if self.pushed is None:
            return None
        dev = getattr(self, "_pd_device", None)
        if dev is None:
            from ..plan.scan_pushdown import DevicePushdown
            dev = DevicePushdown(self.pushed, self.cpu_scan.output,
                                 self._pushdown_applier())
            self._pd_device = dev
        return dev

    def _pd_record(self, in_rows: int, kept: int, bytes_mat: int) -> None:
        """Per-unit pushdown accounting: rows pruned before downstream
        operators, and ROW DATA bytes the decode actually materialized
        on device (the machine-independent proxy for the decode-path
        win)."""
        from .. import telemetry
        from ..utils.metrics import TaskMetrics
        tm = TaskMetrics.get()
        pruned = max(in_rows - kept, 0)
        self.rows_pruned.add(pruned)
        self.bytes_materialized.add(bytes_mat)
        tm.scan_rows_pruned += pruned
        tm.scan_bytes_materialized += bytes_mat
        if pruned:
            telemetry.inc("tpu_scan_pushdown_rows_pruned_total", pruned)

    def _apply_pushdown(self, batch, in_rows: int):
        """Exact fallback for any decode path that could not evaluate on
        the compressed form: the fully materialized batch is counted,
        then filtered/projected/aggregated with the engine's own kernels.
        Returns (pushed-output batch, output row count)."""
        bytes_mat = int(batch.device_memory_size())
        out, kept = self._pushdown_applier().apply(batch)
        self._pd_record(in_rows, kept, bytes_mat)
        return out, (1 if self.pushed.aggs else kept)

    def _agg_partial_guard(self, it):
        """Aggregate-mode scans must emit at least one partial row even
        when no decode unit produced one (empty file, every row group
        pruned): counts 0 (valid), min/max/sum null — the merged
        aggregate then matches the un-pushed plan's empty-input answer."""
        any_out = False
        for b in it:
            any_out = True
            yield b
        if not any_out:
            b = self._pushdown_applier().empty_partials()
            self.num_output_rows.add(1)
            yield self._count_output(b)

    def _effective_paths(self):
        """Apply ready dynamic filters to the file list (parquet footers);
        other formats pass through untouched."""
        paths = self.cpu_scan.paths
        if not self.dynamic_filters or \
                self.cpu_scan.format_name != "parquet":
            return paths
        from .dynamic_pruning import prune_parquet_paths
        kept, pruned = prune_parquet_paths(paths, self.dynamic_filters)
        if pruned:
            self.files_pruned.add(pruned)
        return kept

    def do_execute(self):
        """Scan-output rescache seam: with the fragment cache on, an
        identical scan (same files at the same (mtime, size), columns,
        options and decode confs) streams the cached fragments back from
        the spill catalog instead of re-reading and re-decoding; scans
        carrying dynamic-pruning filters never cache. Off (default) this
        is the produce path verbatim."""
        from .. import rescache
        yield from rescache.fragment_stream(self, "scan",
                                            self._do_execute_produce)

    def _do_execute_produce(self):
        """Time every batch-producing pull into readTime, each under its
        own io span: a span per PULL, not per stream, so time the scan
        iterator spends suspended (downstream sort/join work) never
        inflates the profile's io phase and downstream spans cannot
        mis-parent under a long-lived scan span. The format-specific
        generators below stay untouched.

        Pipelined execution wraps the decode stream in the bounded
        prefetch iterator (exec/base.py): a background thread runs the
        host half of the NEXT batch's decode (page prep, RLE scans,
        pyarrow fallbacks) while downstream operators compute — the
        host<->device overlap half of the pipeline; pipeline-off keeps
        the exact serial stream."""
        from ..exec.base import maybe_prefetch
        from ..utils import spans
        fmt = self.cpu_scan.format_name
        inner = self._decode_batches()
        if self.pushed is not None and self.pushed.aggs:
            inner = self._agg_partial_guard(inner)
        it = maybe_prefetch(inner, self.conf, name=f"scan-{fmt}")
        live = spans.current_profile() is not None
        while True:
            with self.read_time.timed(), \
                    spans.span(f"scan:{fmt}", kind=spans.KIND_IO) as sp:
                b = next(it, None)
                if b is not None and live:
                    # attr computation syncs; skip when disabled
                    sp.inc(batches=1, rows=int(b.row_count()),
                           bytes=int(b.device_memory_size()))
            if b is None:
                return
            yield b

    def _decode_batches(self):
        from ..columnar.batch import batch_from_arrow
        if self.cpu_scan.format_name == "parquet" and \
                not self.cpu_scan.options.get("filters") and \
                self.conf.get(
                    "spark.rapids.sql.format.parquet.deviceDecode.enabled"):
            yield from self._parquet_batches()
            return
        if self.cpu_scan.format_name == "orc" and self.conf.get(
                "spark.rapids.sql.format.orc.deviceDecode.enabled"):
            yield from self._orc_batches()
            return
        if self.cpu_scan.format_name == "csv" and self.conf.get(
                "spark.rapids.sql.format.csv.deviceDecode.enabled"):
            from .csv_device import (csv_device_supported,
                                     device_decode_csv_file)
            if csv_device_supported(self.cpu_scan):
                yield from self._text_device_batches(device_decode_csv_file)
                return
        if self.cpu_scan.format_name == "hiveText" and self.conf.get(
                "spark.rapids.sql.format.hiveText.deviceDecode.enabled"):
            from .csv_device import (device_decode_hive_file,
                                     hive_device_supported)
            if hive_device_supported(self.cpu_scan):
                yield from self._text_device_batches(
                    device_decode_hive_file)
                return
        if self.cpu_scan.format_name == "json" and self.conf.get(
                "spark.rapids.sql.format.json.deviceDecode.enabled"):
            from .json_device import (device_decode_json_file,
                                      json_device_supported)
            if json_device_supported(self.cpu_scan):
                yield from self._text_device_batches(
                    device_decode_json_file)
                return
        if self.shard_rgs is not None and \
                self.cpu_scan.format_name == "parquet":
            # mesh shard clone forced off the device path (deviceDecode
            # conf flipped since planning): the row-group restriction
            # must still hold on host
            for path in self._effective_paths():
                for b, nrows in self._host_rg_batches(
                        path, self.shard_rgs.get(path)):
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)
            return
        for t in self.cpu_scan.host_tables(self._effective_paths()):
            b = batch_from_arrow(t)
            if self.pushed is not None:
                b, n = self._apply_pushdown(b, t.num_rows)
            else:
                n = t.num_rows
            self.num_output_rows.add(n)
            yield self._count_output(b)

    def _text_device_batches(self, decode_file):
        """Device text parse (csv / hive text / json-lines) with PER-FILE
        host fallback: every fallback condition validates before the
        generator's FIRST yield, so pulling one chunk decides the path and
        the rest stream one batch at a time (no whole-file
        materialization, no double-yield). With a pushed spec the decoder
        applies mask-based late materialization per chunk (the `pushed`
        seam); host fallbacks apply the same spec post-decode."""
        from .parquet_device import DeviceDecodeUnsupported
        scan = self.cpu_scan
        pushed_cb = self._apply_pushdown if self.pushed is not None else None
        for path in scan.paths:
            gen = decode_file(scan, path, pushed=pushed_cb)
            try:
                first = next(gen, None)
            except (DeviceDecodeUnsupported, OSError):
                for b, nrows in self._host_file_batches(path):
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)
                continue
            if first is None:
                continue  # empty file
            b, nrows = first
            self.num_output_rows.add(nrows)
            yield self._count_output(b)
            for b, nrows in gen:
                self.num_output_rows.add(nrows)
                yield self._count_output(b)

    def _host_rg_batches(self, path: str, allowed):
        """Host (pyarrow) decode of ONE parquet file restricted to a mesh
        shard's row groups — the host-path twin of the `shard_rgs` filter
        in `_parquet_batches`. Every host fallback a shard clone can take
        must honor the restriction: a clone decoding its WHOLE file would
        duplicate rows across shards (a wrong split, not a slow one).
        `allowed=None` means the shard owns the whole file."""
        import pyarrow.parquet as pq
        from ..columnar.batch import batch_from_arrow
        scan = self.cpu_scan
        pf = pq.ParquetFile(path)
        try:
            for rg in range(pf.metadata.num_row_groups):
                if allowed is not None and rg not in allowed:
                    continue
                t = scan._postprocess(pf.read_row_group(
                    rg, columns=list(scan.output.names)))
                b = batch_from_arrow(t)
                if self.pushed is not None:
                    b, n = self._apply_pushdown(b, t.num_rows)
                else:
                    n = t.num_rows
                yield b, n
        finally:
            close = getattr(pf, "close", None)
            if close is not None:
                close()

    def _host_file_batches(self, path: str):
        """Host decode of ONE file through FileBatchIterator so batchSizeRows
        slicing still applies (a multi-GB file must not become one batch).
        Applies the pushed spec (exact batch applier) when present."""
        from ..columnar.batch import batch_from_arrow
        scan = self.cpu_scan
        for t in FileBatchIterator([path], scan.decode_file, scan.conf,
                                   format_name=scan.format_name):
            t = scan._postprocess(t)
            b = batch_from_arrow(t)
            if self.pushed is not None:
                b, n = self._apply_pushdown(b, t.num_rows)
            else:
                n = t.num_rows
            yield b, n

    def _orc_batches(self):
        """Device decode per STRIPE with per-COLUMN and per-stripe host
        fallback — the parquet path's discipline applied to ORC's stripe
        unit. The footer decides per column (an exotic column host-decodes
        and merges while its siblings ride the device path); a stripe-level
        surprise (RLEv1 runs, missing streams, over-wide strings, non-UTC
        writer timezones) falls just THAT stripe back to pyarrow's
        read_stripe. No fallback is silent: every file, stripe and
        stripe's column that pyarrow decoded is one unit of
        `TaskMetrics.scan_host_decoded` and of this scan's
        `hostDecodedUnits` (`stripesHostDecoded`, `colsHostDecoded` say
        which)."""
        from ..columnar.batch import batch_from_arrow
        from ..utils.metrics import TaskMetrics
        from .orc_device import (DeviceDecodeUnsupported, _ScanStats,
                                 columns_supported, decode_stripe)
        from .walkers import walker_pool
        scan = self.cpu_scan
        tm = TaskMetrics.get()
        # pipelined: a stripe's columns are walked side by side, ahead of
        # the chip; off, one after the other on this thread
        walkers = walker_pool() if self.conf.get(
            "spark.rapids.tpu.pipeline.enabled") else None
        pushed_cb = self._apply_pushdown if self.pushed is not None else None

        def host_decoded(units: int) -> None:
            tm.scan_host_decoded += units
            self.host_decoded_units.add(units)

        for path in scan.paths:
            try:
                info, bad = columns_supported(path, scan.output)
                if len(bad) >= len(scan.output.names):
                    raise DeviceDecodeUnsupported("no device column")
            except (DeviceDecodeUnsupported, OSError, struct_error):
                host_decoded(1)
                for b, nrows in self._host_file_batches(path):
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)
                continue
            if bad:
                self.cols_host_decoded.add(len(bad))
            from pyarrow import orc as pa_orc
            ofile = None
            with open(path, "rb") as f:
                for si in range(len(info.stripes)):
                    stats = _ScanStats()
                    try:
                        b, nrows = decode_stripe(info, f, si, scan.output,
                                                 host_cols=bad,
                                                 pushed=pushed_cb,
                                                 stats=stats,
                                                 walkers=walkers)
                        tm.scan_batches += 1
                        if bad:
                            host_decoded(len(bad))
                    except (DeviceDecodeUnsupported, OSError,
                            struct_error):
                        self.stripes_host_decoded.add(1)
                        host_decoded(1)
                        if ofile is None:
                            ofile = pa_orc.ORCFile(path)
                        t = scan._postprocess(pa.Table.from_batches(
                            [ofile.read_stripe(
                                si, columns=list(scan.output.names))]))
                        b, nrows = batch_from_arrow(t), t.num_rows
                        if pushed_cb is not None:
                            b, nrows = pushed_cb(b, nrows)
                    self.orc_runs_walked.add(stats.runs)
                    self.orc_varint_bytes.add(stats.varint_bytes)
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)

    def _parquet_batches(self):
        """Device decode per ROW GROUP with per-COLUMN and per-row-group
        host fallback.

        The footer gates each file cheaply up front (its ParquetFile is
        reused by the decode) and decides PER COLUMN: an unsupported column
        (exotic physical type, nested, unknown codec) host-decodes via one
        pyarrow read and merges into the device batch, while its siblings
        still decode on device — one odd column no longer evicts the file.
        Supported files stream one row group at a time — one device batch
        live at once — and a page-level surprise the footer can't reveal
        (e.g. v2 pages) falls just THAT row group back to pyarrow
        (pf.read_row_group), so nothing is ever decoded twice or yielded
        twice. If NO file has any device-decodable column, the whole scan
        delegates to the plain host path, preserving the COALESCING /
        MULTITHREADED multi-file strategies. The fallback net is narrow by
        design: only DeviceDecodeUnsupported (incl. malformed page streams,
        wrapped in parquet_device) and I/O errors — a genuine code bug in
        the decoder must crash, not silently degrade to the host path."""
        from ..columnar.batch import batch_from_arrow
        from .parquet_device import (DeviceDecodeUnsupported,
                                     columns_supported, decode_row_group)
        scan = self.cpu_scan

        import pyarrow.parquet as pq
        scan_names = list(scan.output.names)

        def check(path):
            """Footer support sweep, run ONCE per file; only the fallback
            column-name set is kept, so no fd outlives its file (a scan
            over more files than ulimit -n must not exhaust descriptors).
            Returns the host-column set, or None when nothing in the file
            can device-decode (whole-file host path)."""
            try:
                pf, bad = columns_supported(path, scan.output)
            except (DeviceDecodeUnsupported, OSError, struct_error):
                return None
            close = getattr(pf, "close", None)
            if close is not None:
                close()
            if len(bad) >= len(scan.output.names):
                return None
            return frozenset(bad)

        paths = self._effective_paths()
        supported = {}
        for p in paths:
            host_cols = check(p)
            if host_cols is not None:
                supported[p] = host_cols
                if host_cols:
                    self.cols_host_decoded.add(len(host_cols))
        if not supported:
            if self.shard_rgs is not None:
                # mesh shard clone whose file lost device decodability
                # since planning: the row-group restriction must still
                # hold on host or every shard re-reads the whole file
                for path in paths:
                    for b, nrows in self._host_rg_batches(
                            path, self.shard_rgs.get(path)):
                        self.num_output_rows.add(nrows)
                        yield self._count_output(b)
                return
            # nothing is device-decodable: the plain host path keeps the
            # COALESCING / MULTITHREADED multi-file strategies
            for t in scan.host_tables(paths):
                b = batch_from_arrow(t)
                if self.pushed is not None:
                    b, n = self._apply_pushdown(b, t.num_rows)
                else:
                    n = t.num_rows
                self.num_output_rows.add(n)
                yield self._count_output(b)
            return
        from .dynamic_pruning import row_group_filter
        for path in paths:
            if path not in supported:
                if self.shard_rgs is not None:
                    it = self._host_rg_batches(path,
                                               self.shard_rgs.get(path))
                else:
                    it = self._host_file_batches(path)
                for b, nrows in it:
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)
                continue
            # re-open WITHOUT re-running the support sweep (the flag above
            # answered that); if the file changed on disk since, the decode
            # raises DeviceDecodeUnsupported and falls back per row group
            pf = pq.ParquetFile(path)
            try:
                meta = pf.metadata
                from .dynamic_pruning import schema_col_index
                keep_rgs = row_group_filter(meta, schema_col_index(meta),
                                            self.dynamic_filters) \
                    if self.dynamic_filters else None
                rgs = [rg for rg in range(meta.num_row_groups)
                       if keep_rgs is None or rg in keep_rgs]
                if self.shard_rgs is not None:
                    allowed = self.shard_rgs.get(path)
                    if allowed is not None:
                        rgs = [rg for rg in rgs if rg in allowed]
                rgs = self._pushdown_prune_rgs(meta, rgs)
                yield from self._decode_rgs_pipelined(
                    pf, path, rgs, supported[path], scan, scan_names)
            finally:
                close = getattr(pf, "close", None)
                if close is not None:
                    close()

    def _pushdown_prune_rgs(self, meta, rgs):
        """Device-path row-group pruning: drop whole row groups the pushed
        predicate PROVABLY eliminates via footer min/max/null-count stats,
        before any page bytes are read (the host pyarrow path has had this
        via filters= all along; this closes the gap for the device
        decode). Conservative by construction — see
        plan/scan_pushdown.prune_row_groups."""
        if self.pushed is None or self.pushed.predicate is None or \
                not rgs or not self.conf.get(
                    "spark.rapids.tpu.scan.pushdown.rowgroup.enabled"):
            return rgs
        from .. import telemetry
        from ..plan.scan_pushdown import prune_row_groups
        from ..utils.metrics import TaskMetrics
        from .dynamic_pruning import schema_col_index
        dead = prune_row_groups(meta, schema_col_index(meta),
                                self.cpu_scan.output,
                                self.pushed.predicate)
        if not dead:
            return rgs
        kept = [rg for rg in rgs if rg not in dead]
        n = len(rgs) - len(kept)
        if n:
            self.rowgroups_pruned.add(n)
            TaskMetrics.get().scan_rowgroups_pruned += n
            telemetry.inc("tpu_scan_rowgroups_pruned_total", n)
        return kept

    def _note_decode(self, box) -> None:
        """`note` of the parquet decode: one executed program's box."""
        self.parquet_stacked_gathers.add(box[0])
        self.parquet_gathers_elided.add(box[1])

    def _decode_rgs_pipelined(self, pf, path, rgs, host_cols, scan,
                              scan_names):
        """Stream row groups, one dispatch group live at a time. With
        pipelining on, `spark.rapids.tpu.pipeline.scan.chunksPerDispatch`
        row-group chunks decode per FUSED dispatch (packed
        single-transfer, one compiled program, one merged batch —
        O(1) dispatches per scan batch); a group the fast path declines,
        and pipeline-off entirely, take the per-row-group path. Host- or
        device-phase surprises fall just that row group back to pyarrow —
        the same narrow net as before. Pipelined, every path walks its
        column chunks side by side on the process's walkers; off, one
        after the other on this thread."""
        from ..columnar.batch import batch_from_arrow
        from ..utils.metrics import TaskMetrics
        from .parquet_device import (DeviceDecodeUnsupported, _device_phase,
                                     _host_phase, decode_row_groups_fused)
        from .walkers import walker_pool
        tm = TaskMetrics.get()
        group, walkers = 1, None
        if self.conf.get("spark.rapids.tpu.pipeline.enabled"):
            group = max(self.conf.get(
                "spark.rapids.tpu.pipeline.scan.chunksPerDispatch"), 1)
            walkers = walker_pool()

        def host_fallback(rg):
            t = scan._postprocess(pf.read_row_group(rg,
                                                    columns=scan_names))
            return batch_from_arrow(t), t.num_rows

        dev = self._device_pushdown()
        with open(path, "rb") as f:
            i = 0
            while i < len(rgs):
                chunk_rgs = rgs[i:i + group]
                i += len(chunk_rgs)
                if dev is not None:
                    # compute on compressed data: predicate on dictionary
                    # values / RLE indices inside the decode dispatch,
                    # survivors-only late materialisation (or aggregate
                    # partials with no row data at all); any decline
                    # degrades to full decode + the exact batch applier
                    # inside decode_row_groups_pushdown itself — the
                    # except net here is only for malformed row groups
                    from .parquet_device import decode_row_groups_pushdown
                    try:
                        outs = decode_row_groups_pushdown(
                            pf, f, chunk_rgs, scan.output, host_cols, dev,
                            walkers)
                    except (DeviceDecodeUnsupported, OSError,
                            struct_error):
                        pass  # per-row-group decode below
                    else:
                        for b, out_rows, in_rows, kept, bytes_mat in outs:
                            tm.scan_batches += 1
                            self._pd_record(in_rows, kept, bytes_mat)
                            self.num_output_rows.add(out_rows)
                            yield self._count_output(b)
                        continue
                elif len(chunk_rgs) > 1:
                    try:
                        outs = decode_row_groups_fused(
                            pf, f, chunk_rgs, scan.output, host_cols,
                            self._note_decode, walkers)
                    except (DeviceDecodeUnsupported, OSError,
                            struct_error):
                        pass  # per-row-group decode below
                    else:
                        for b, nrows in outs:
                            tm.scan_batches += 1
                            self.num_output_rows.add(nrows)
                            yield self._count_output(b)
                        continue
                for rg in chunk_rgs:
                    try:
                        works, nrows = _host_phase(
                            pf, f, rg, scan.output, host_cols, walkers)
                        b, nrows = _device_phase(pf, rg, scan.output,
                                                 works, nrows, host_cols,
                                                 self._note_decode)
                        tm.scan_batches += 1
                    except (DeviceDecodeUnsupported, OSError,
                            struct_error):
                        b, nrows = host_fallback(rg)
                    if dev is not None:
                        b, nrows = self._apply_pushdown(b, nrows)
                    self.num_output_rows.add(nrows)
                    yield self._count_output(b)


def make_tpu_file_scan(plan: CpuFileScanExec, conf: TpuConf) -> TpuFileScanExec:
    return TpuFileScanExec(plan, conf)
