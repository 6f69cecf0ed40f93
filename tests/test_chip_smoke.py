"""`chip_smoke.py` rehearsed on the CPU backend: every phase runs and agrees
with its reference, and the run still FAILS because the platform is not
`tpu` (the no-fallback rule). Plus the two rules it leans on: the compile
cache is placed from outside, and a real device that will not report its
memory is a startup error, not a 16 GiB guess."""

import json
import os

import jax
import pytest

import chip_smoke
from spark_rapids_tpu.errors import DeviceStartupError
from spark_rapids_tpu.memory import device_manager as dm
from spark_rapids_tpu.native import runtime as native


def test_cpu_rehearsal_runs_every_phase_and_fails(tmp_path, monkeypatch,
                                                  capsys):
    # an earlier test in this worker may have looked for the library before
    # the smoke built it; and the cache directory comes from outside
    monkeypatch.setattr(native, "_TRIED", False)
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    # three row groups, as the default size has two: the scan's
    # multi-row-group decode is the path every real table takes
    import benchcorpus
    monkeypatch.setattr(benchcorpus, "ROW_GROUP", 8192)
    rc = chip_smoke.main(["--rows", "20000",
                          "--data-dir", str(tmp_path / "data")])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert rc != 0
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": jax.device_count()}}
    # the platform is the ONLY thing that failed
    assert lines[-2] == {"failures": ["platform is 'cpu', not 'tpu'"]}
    queries = {ln["query"]: ln for ln in lines if "query" in ln}
    assert sorted(queries) == ["q3_brand_report", "q68_window_rank",
                               "q7_star_avg", "q96_selective_count"]
    for q in queries.values():
        assert q["reference_agreed"] and q["warm_compile_count"] == 0
        assert not [n for n in q["plan"] if not n.startswith("Tpu")]
    for q in ("q3_brand_report", "q7_star_avg", "q96_selective_count"):
        assert queries[q]["plan"].count("TpuBroadcastHashJoinExec") == 2
    assert any(ln.get("native_runtime") is True for ln in lines)
    by_op = next(ln["compile_by_op"] for ln in lines if "compile_by_op" in ln)
    assert "io.parquet.fused_multi_decode" in by_op
    pallas = next(ln for ln in lines if "pallas_rows" in ln)
    assert pallas["interpreted"] is True  # cpu: the one place it may be


@pytest.mark.parametrize("env_dir", ["/somewhere/outside", None])
def test_compile_cache_is_placed_from_outside(monkeypatch, env_dir):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert chip_smoke.place_compile_cache() == env_dir
        assert "jax_compilation_cache_dir" not in calls
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(chip_smoke.ROOT, ".jax_cache")
        assert chip_smoke.place_compile_cache() == want
        assert calls["jax_compilation_cache_dir"] == want


class _Device:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


@pytest.mark.parametrize("stats", [RuntimeError("no answer"), None, {}])
def test_query_hbm_raises_on_a_silent_tpu(stats):
    with pytest.raises(DeviceStartupError, match="HBM budget"):
        dm.DeviceManager._query_hbm(_Device("tpu", stats))


def test_query_hbm_reads_the_device_and_defaults_only_on_cpu():
    assert dm.DeviceManager._query_hbm(
        _Device("tpu", {"bytes_limit": 123})) == 123
    assert dm.DeviceManager._query_hbm(_Device("cpu", None)) == \
        dm._DEFAULT_HBM


def test_pallas_interpret_follows_the_platform(monkeypatch):
    from spark_rapids_tpu.ops import pallas_mode
    for backend, want in (("tpu", False), ("cpu", True)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert pallas_mode.interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="Pallas"):
        pallas_mode.interpret()
