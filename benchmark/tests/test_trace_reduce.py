"""The trace reduction on a hand-built trace, and the table of peaks."""
import pytest
import run as R
import trace_reduce as T

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


DEVICE = {"/device:TPU:0": [ev("fusion.1", 10, 20), ev("sort.2", 25, 15),
                            ev("fusion.1", 70, 10), ev("copy.3", 95, 5)]}
HOST = [ev(T.MARK, 0, 100), ev("$scan.py:1 walk_chunks", 40, 28),
        ev("$array.py:9 __int__", 80, 15), ev("$other", 0, 1)]


def test_busy_is_the_union_not_the_sum():
    assert T.union_ns(DEVICE["/device:TPU:0"]) == 45 * MS


def test_busy_idle_and_window_from_the_mark():
    out = T.reduce_events(DEVICE, HOST)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.045)
    assert out["device_ops"][0] == ["no program/fusion.1",
                                    pytest.approx(0.030)]


def test_operations_are_listed_under_their_program():
    modules = {"/device:TPU:0": [ev("jit_fn(1)", 10, 31), ev("jit_k(2)", 69, 32)]}
    out = T.reduce_events(DEVICE, HOST, device_modules=modules)
    assert out["device_ops"][:2] == [["jit_k(2)", pytest.approx(0.032)],
                                     ["jit_fn(1)", pytest.approx(0.031)]]
    assert ["jit_fn(1)/fusion.1", pytest.approx(0.020)] in out["device_ops"]


def test_programs_come_in_the_order_they_ran():
    modules = {"/device:TPU:0": [ev("jit_k(2)", 69, 32), ev("jit_fn(1)", 10, 31),
                                 ev("jit_tiny(3)", 50, 1)]}
    assert T.programs_in_order(modules) == [
        ["jit_fn(1)", 0.0, pytest.approx(0.031)],
        ["jit_k(2)", pytest.approx(0.059), pytest.approx(0.032)]]


def test_an_operation_is_named_by_its_head_and_opcode():
    text = ("%while.7 = (u32[]{:T(128)}, s32[2097152]{0:T(1024)S(1)}) "
            "while((u32[]{:T(128)}) %tuple.190), condition=%c, body=%b")
    assert T.short_op(text) == "%while.7 while"
    assert T.short_op("%fusion.4 = u32[8]{0} fusion(u32[8] %p), kind=kLoop"
                      ) == "%fusion.4 fusion"
    assert T.short_op("copy.3") == "copy.3"


def test_gaps_are_labelled_by_what_the_host_did():
    out = dict(T.reduce_events(DEVICE, HOST)["idle_gaps"])
    # 40-70 under the chunk walk, 80-95 under __int__, 0-10 only in collect
    assert out["$scan.py:1 walk_chunks"] == pytest.approx(0.030)
    assert out["$array.py:9 __int__"] == pytest.approx(0.015)
    assert out["in collect"] == pytest.approx(0.010)


def test_gap_with_no_host_event():
    assert T.label_gap((0, 10), []) == "no host event"


def test_busy_is_averaged_over_devices_and_clipped_to_the_window():
    two = dict(DEVICE)
    two["/device:TPU:1"] = [ev("fusion.1", -50, 60), ev("fusion.1", 90, 50)]
    out = T.reduce_events(two, HOST)
    assert out["busy_s"] == pytest.approx((0.045 + 0.020) / 2)


def test_a_cell_of_one_chip_on_a_host_of_four_reads_its_own_chip():
    four = dict(DEVICE)
    four["/device:TPU:1"] = [ev("copy.9", 5, 1)]
    four["/device:TPU:2"] = []
    four["/device:TPU:3"] = []
    out = T.reduce_events(four, HOST, chips=1)
    assert out["busy_s"] == pytest.approx(0.045)


def test_a_trace_without_the_mark_is_refused():
    with pytest.raises(ValueError, match="bench.collect"):
        T.reduce_events(DEVICE, [e for e in HOST if e[0] != T.MARK])


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_events({"/device:TPU:0": []}, HOST)


def test_unknown_device_kind_raises():
    assert R.peaks_for(R.HERE, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        R.peaks_for(R.HERE, "TPU v9 imaginary")
