"""The trace reduction on a small trace recorded on a TPU v5e (one
fused round of the toy rehearsal cell, PR 23's first chip call) and on
hand-made intervals."""

import os

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_train_v5e.xplane.pb")


def test_leaves_drop_containers_and_keep_overlapping_neighbours():
    evs = [(0, 100, "while"), (10, 20, "a"), (30, 60, "fusion"), (35, 40, "b"),
           (90, 120, "async"), (200, 210, "c")]
    assert [e[2] for e in trace_reduce.leaf_events(evs)] == ["a", "b", "async", "c"]


def test_union_merges_and_clips():
    assert trace_reduce.union([(0, 10), (5, 20), (30, 40), (50, 60)], 8, 35) == [
        [8, 20], [30, 35]]


def test_short_names():
    full = ("%fusion.484 = (f32[8,15]{1,0:T(8,128)S(1)}, f32[8,15,2048]{2,1,0}) "
            "fusion(bf16[8,2048]{1,0} %bitcast.1), kind=kOutput")
    assert trace_reduce.short_name(full) == "fusion.484 (f32[8,15], f32[8,15,2048])"
    assert trace_reduce.short_name("bench_window") == "bench_window"
    assert trace_reduce.module_name("jit__round_step(99)") == "jit__round_step"


def test_recorded_v5e_trace():
    """One jit__round_step of 154 us inside a window of 1.76 ms: the
    device planes are found, containers dropped, the window taken from
    the harness's annotation, and the idle time handed to what the host
    was doing (dispatching, then waiting for the loss)."""
    got = trace_reduce.reduce_trace(TRACE, ("round_step", "fetch_loss"))
    assert list(got["busy_s_by_device"]) == ["/device:TPU:0"]
    assert abs(got["window_s"] - 0.00175676) < 1e-9
    assert 0.00010 < got["busy_s"] < 0.000154
    assert abs(got["idle_share_worst"] - (1 - got["busy_s"] / got["window_s"])) < 1e-12
    assert len(got["device_ops"]) == 10
    assert all(" = " not in name and secs > 0 for name, secs in got["device_ops"])
    secs = [s for _, s in got["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    gaps = dict(got["idle_gaps"])
    assert set(gaps) == {"fetch_loss", "round_step", "in_program:jit__round_step"}
    assert abs(sum(gaps.values()) - (got["window_s"] - got["busy_s"])) < 1e-9
    assert gaps["in_program:jit__round_step"] < 0.000154 - got["busy_s"] + 1e-9


def test_a_trace_without_device_planes_reads_nothing(tmp_path):
    assert trace_reduce.find_xplane(str(tmp_path)) is None
