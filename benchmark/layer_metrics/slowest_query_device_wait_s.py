"""Entry: how long the slowest query of the window (largest `wall_s` among
the last `len(ctx["window"])` entries of the engine's ring of recent queries)
had its host wait for the chip: `host_sync_ns + d2h_ns` of its
`TaskMetrics`. About the median query's wait unless the slow one waited on the
host. The ring keeps 64 queries, so a longer window is read in its last 64."""


def read(ctx):
    from spark_rapids_tpu.plugin import TpuSession
    if not hasattr(TpuSession, "recent_queries") or not ctx["window"]:
        return None
    recent = TpuSession.recent_queries()[-len(ctx["window"]):]
    if not recent:
        return None
    _, _, tm = max(recent, key=lambda q: q[0])
    return (tm["host_sync_ns"] + tm["d2h_ns"]) / 1e9
