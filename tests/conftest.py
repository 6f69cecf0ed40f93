"""Test configuration: run everything on a virtual 8-device CPU mesh so sharding and
collective paths are exercised without TPU hardware (`chip_smoke.py` is the run on
the real chip)."""

import os


def _build_native():
    """`native/build/` is not in git: build the host runtime once per checkout,
    so the suite tests the paths a deployment runs instead of their numpy
    stand-ins (test_native.py skips without the library). Every xdist worker
    imports this file, so the build is serialised on a lock. Only a machine
    with no `make` or no `g++` runs on the stand-ins; where the tools are
    there, a build that fails ends the session instead of quietly switching
    what the suite tests."""
    import fcntl
    import shutil
    import subprocess
    if not (shutil.which("make") and shutil.which("g++")):
        return
    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    os.makedirs(os.path.join(native, "build"), exist_ok=True)
    with open(os.path.join(native, "build", ".lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        done = subprocess.run(["make", "-C", native], capture_output=True,
                              text=True)
    if done.returncode != 0:
        raise RuntimeError(
            f"`make -C {native}` failed (rc {done.returncode}):\n"
            f"{done.stdout[-2000:]}\n{done.stderr[-2000:]}")


_build_native()

# Must be set before jax initializes. Forced (not setdefault): the session may point
# JAX_PLATFORMS at real TPU hardware, but tests always run on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

# config.update before first backend use is authoritative: the tests run on
# the CPU backend whatever the environment says.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers",
        "faults: deterministic fault-injection matrix "
        "(scripts/fault_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "compile: compile-service suite (program cache / persistent tier / "
        "warmup / bucket tuner; scripts/compile_cache_matrix.sh runs these "
        "standalone)")
    config.addinivalue_line(
        "markers",
        "observability: query-profiler suite (span tracer / metrics "
        "wiring / event log / report tool; scripts/profile_matrix.sh runs "
        "these standalone)")
    config.addinivalue_line(
        "markers",
        "pipeline: pipelined-execution suite (bounded async prefetch / "
        "fused multi-chunk scan decode / pipeline on-off equality; "
        "scripts/pipeline_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "telemetry: live-telemetry suite (metrics registry / scrape "
        "surface / flight recorder / trace correlation; "
        "scripts/telemetry_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "sched: query-scheduler suite (priority-weighted fair admission / "
        "deadlines / cooperative cancellation / tenant quotas; "
        "scripts/sched_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "rescache: result/fragment-cache suite (plan fingerprints / "
        "cross-query reuse seams / single-flight / eviction / fault "
        "degrade; scripts/rescache_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "fleet: fleet-gateway suite (worker registry / breakers / "
        "affinity routing / failover / drain / cancel-through-gateway; "
        "scripts/fleet_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "stats: runtime-statistics suite (cardinality history / "
        "estimate-vs-actual q-error / optimizer feedback / skew "
        "histograms; scripts/stats_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "pushdown: scan-pushdown suite (compute on compressed data: "
        "golden on/off equality / planner rewrites / key+fingerprint "
        "non-aliasing / row-group pruning / aggregate-only shapes; "
        "scripts/scan_pushdown_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "live: live query-introspection suite (in-flight registry / "
        "progress+ETA from stats history / slow-query watchdog / "
        "queries surfaces / gateway fan-out / tpu_top console; "
        "scripts/liveview_matrix.sh runs these standalone)")
    config.addinivalue_line(
        "markers",
        "chaos: crash-recovery suite (durable-tier degradation / fleet "
        "supervisor / chaos campaigns over real gateway + supervised "
        "worker processes; scripts/chaos_matrix.sh runs these "
        "standalone — campaign tests are also `slow`)")
    config.addinivalue_line(
        "markers",
        "mesh: sharded-execution suite (scan sharding across mesh "
        "positions / device-resident exchange seams / partition-count "
        "mismatch degrades / per-chip HBM ledgers / one admission door / "
        "rescache ICI seam; scripts/mesh_matrix.sh runs these "
        "standalone)")
    config.addinivalue_line(
        "markers",
        "fusion: whole-stage fusion suite (planner chains / fused-stage "
        "on-off bit-identity / ANSI parity / pallas kernel exactness / "
        "dispatch accounting; scripts/fusion_matrix.sh runs these "
        "standalone)")


@pytest.fixture
def rng():
    return np.random.default_rng(42)
