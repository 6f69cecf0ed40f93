"""Operator programs: `TaskMetrics.device_dispatches`, mean per query of the
window."""
import statistics


def read(ctx):
    reads = [r["dispatches"] for r in ctx["window"] if "dispatches" in r]
    return statistics.mean(reads) if reads else None
