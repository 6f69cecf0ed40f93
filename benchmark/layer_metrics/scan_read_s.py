"""Scan decode: the scans' `readTime` summed over the plan, mean per query of
the window. Host wall time of chunk walk + H2D + decode, not device time."""
import statistics


def read(ctx):
    reads = [r["scan_read_s"] for r in ctx["window"] if "scan_read_s" in r]
    return statistics.mean(reads) if reads else None
