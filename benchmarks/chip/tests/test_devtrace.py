"""The trace reduction on a synthetic trace whose answers are known."""
import pytest

import devtrace


def _events():
    ms = 1e6
    ops = [(0 * ms, 10 * ms, "%while.1 = (...) while(...)"),
           (1 * ms, 4 * ms, "%fusion.1 = bf16[4] fusion(...)"),
           (5 * ms, 9 * ms, "%fusion.2 = bf16[4] fusion(...)"),
           (30 * ms, 35 * ms, "%fusion.1 = bf16[4] fusion(...)"),
           (80 * ms, 90 * ms, "%fusion.3 = bf16[4] fusion(...)")]
    programs = [(0 * ms, 10 * ms, "jit__unknown(1)"),
                (30 * ms, 35 * ms, "jit__unknown(1)"),
                (80 * ms, 90 * ms, "jit__unknown(2)")]
    spans = [(-1 * ms, 100 * ms, "window"),
             (0.5 * ms, 1 * ms, "decode"),      # device starts before it
             (12 * ms, 28 * ms, "snapshot"),
             (13 * ms, 27 * ms, "multi_write"),
             (29.5 * ms, 30 * ms, "decode"),
             (40 * ms, 79 * ms, "restore"),
             (79 * ms, 80 * ms, "prefill")]
    return {"devices": {"/device:TPU:0": {"ops": ops, "programs": programs}},
            "spans": spans}


def test_reduce_synthetic():
    r = devtrace.reduce(_events())
    assert r["window_s"] == pytest.approx(0.101)
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["program_s"] == pytest.approx({"decode": 0.015, "prefill": 0.01})
    # the while op holds fusions 1 and 2: only leaves are ranked
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.008, "fusion.2": 0.004, "fusion.3": 0.010})
    gaps = dict(r["idle_gaps"])
    assert gaps["snapshot/multi_write"] == pytest.approx(0.020)  # 10..30
    assert gaps["restore"] == pytest.approx(0.045)    # 35..80 ms
    assert gaps["host"] == pytest.approx(0.011)       # -1..0 and 90..100
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_union_and_gaps():
    assert devtrace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert devtrace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce({"devices": {}, "spans": []})
