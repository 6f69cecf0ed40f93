"""Scan decode: stripes and columns of the window's queries that pyarrow
decoded for the engine instead of the chip, `TaskMetrics.scan_host_decoded`
summed over the last `len(ctx["window"])` entries of the engine's ring of
recent queries. The configuration guarantees 0; anything else is a finding.
An engine without the counter reports nothing."""


def read(ctx):
    from spark_rapids_tpu.plugin import TpuSession
    if not hasattr(TpuSession, "recent_queries") or not ctx["window"]:
        return None
    recent = TpuSession.recent_queries()[-len(ctx["window"]):]
    reads = [tm["scan_host_decoded"] for _, _, tm in recent
             if "scan_host_decoded" in tm]
    return sum(reads) if reads else None
