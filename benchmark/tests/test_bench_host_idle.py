"""The idle between programs put down to the host's work, on hand-made
intervals and on the small serving trace recorded on a TPU v5e
(``host_idle.py``, ``test_bench_span_reduce.py``)."""

import os

import pytest

from benchmark import host_idle, span_reduce
from benchmark.readers import (
    fetch_idle_ms_per_tick,
    host_idle_ms_per_tick,
    launch_idle_ms_per_tick,
    stage_idle_ms_per_tick,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BARE = os.path.join(DATA, "tiny_train_v5e.xplane.pb")          # no spans
SERVE = os.path.join(DATA, "tiny_serve_spans_v5e.xplane.pb")   # toy serving ticks
READERS = {
    "engine.launch_idle_ms_per_tick": launch_idle_ms_per_tick,
    "engine.fetch_idle_ms_per_tick": fetch_idle_ms_per_tick,
    "engine.stage_idle_ms_per_tick": stage_idle_ms_per_tick,
    "sched.host_idle_ms_per_tick": host_idle_ms_per_tick,
}

# the serving thread: a tick with a chunk and a decode, the loop asleep,
# a tick with a decode alone (ns)
TICKS = [
    (0, 100, "sched.tick"), (5, 15, "sched.admit"), (8, 12, "engine.start_prefill"),
    (20, 40, "sched.prefill"), (20, 25, "engine.stage_chunk"),
    (25, 38, "engine.prefill_chunk"), (30, 36, "engine.wait"),
    (36, 38, "engine.fetch_counts"),
    (40, 50, "engine.stage"), (50, 55, "engine.decode_dispatch"),
    (55, 90, "engine.fetch_tokens"), (55, 85, "engine.wait"),
    (88, 90, "engine.fetch_counts"), (90, 95, "engine.advance"),
    (95, 100, "sched.deliver"),
    (100, 110, "sched.idle"),
    (110, 160, "sched.tick"), (115, 120, "engine.stage"),
    (120, 125, "engine.decode_dispatch"), (125, 150, "engine.fetch_tokens"),
    (125, 145, "engine.wait"), (150, 160, "sched.deliver"),
]
# the chip: a chunk [27, 35), a tick [53, 83) with a gap [60, 62) inside
# it, a tick [123, 143); the window is [0, 170)
BETWEEN = [(0, 27), (35, 53), (83, 123), (143, 170)]
IN_PROGRAM_NS = 2


def reduced():
    """What ``span_reduce.reduce_spans`` gives for the intervals above."""
    between_s = sum(e - s for s, e in BETWEEN) / 1e9
    return {
        "spans": {"engine.decode_dispatch": {"count": 2}},
        "idle": {"idle_s": between_s + IN_PROGRAM_NS / 1e9,
                 "in_program_s": IN_PROGRAM_NS / 1e9, "between_s": between_s,
                 "by_span": span_reduce.attribute(
                     BETWEEN, span_reduce.self_segments(TICKS))},
    }


def test_the_groups_on_hand_made_intervals():
    """Launch: chunk 2 + dispatches 3 + 3. Fetch: waits 1 + 2 + 2,
    copies 3 + 5, counters 2 + 2. Stage: chunk upload 5, tick uploads
    10 + 5. Host: the ticks' own 10 + 5, admit 6, start_prefill 4, the
    prefill step's own 2, advance 5, deliver 5 + 10, no span 10."""
    got = host_idle.split(reduced())
    ns = {m: round(s * 1e9) for m, s in got["seconds"].items()}
    assert ns == {"engine.launch_idle_ms_per_tick": 8,
                  "engine.fetch_idle_ms_per_tick": 17,
                  "engine.stage_idle_ms_per_tick": 20,
                  "sched.host_idle_ms_per_tick": 57,
                  "sched.idle": 10, "in_program": 2}
    assert got["ticks"] == 2
    assert got["ms_per_tick"]["engine.stage_idle_ms_per_tick"] == pytest.approx(20e-6 / 2)
    # the four, the loop's sleep and the gaps inside programs are the idle
    assert sum(got["seconds"].values()) == pytest.approx(got["idle_s"])
    assert sum(got["host_by_span"].values()) == pytest.approx(57e-9)
    assert got["unsplit_share"] == pytest.approx((10 + 5 + 10) / 112)


def test_no_decode_tick_reads_nothing():
    doc = reduced()
    doc["spans"] = {}
    assert host_idle.split(doc) is None


def test_the_readers_on_hand_made_intervals(monkeypatch):
    monkeypatch.setattr(span_reduce, "of_run", lambda obs: reduced())
    want = host_idle.split(reduced())["ms_per_tick"]
    for metric, reader in READERS.items():
        assert reader.read({"trace": {"busy_s": 1.0}}) == pytest.approx(want[metric])


def test_the_readers_on_recorded_traces(monkeypatch):
    """The toy serving ticks, recorded before ``engine.wait`` and
    ``engine.fetch_counts`` existed: the copies and the wait are one
    ``engine.fetch_tokens`` there, in the same group."""
    for reader in READERS.values():              # a CPU run: no device plane
        assert reader.read({"trace": None}) is None and reader.read({}) is None
    monkeypatch.setattr(span_reduce.tr, "find_xplane", lambda root: BARE)
    for reader in READERS.values():              # a program without spans
        assert reader.read({"trace": {"busy_s": 1.0}}) is None
    monkeypatch.setattr(span_reduce.tr, "find_xplane", lambda root: SERVE)
    run = {"trace": {"busy_s": 1.0}}
    pinned = {"engine.launch_idle_ms_per_tick": 1.0807873,
              "engine.fetch_idle_ms_per_tick": 0.9635984,
              "engine.stage_idle_ms_per_tick": 5.0661975,
              "sched.host_idle_ms_per_tick": 0.7629031}
    for metric, reader in READERS.items():
        assert reader.read(run) == pytest.approx(pinned[metric], rel=1e-6), metric
    got = host_idle.split(span_reduce.reduce_spans(SERVE))
    assert got["ticks"] == 19
    assert sum(got["seconds"].values()) == pytest.approx(got["idle_s"])
    # a tick's own time and the gaps under no span: under a twentieth
    assert got["unsplit_share"] == pytest.approx(0.0381807, rel=1e-5)


def test_the_command_prints_the_four(capsys):
    assert host_idle.main([SERVE]) == 0
    out = capsys.readouterr().out
    assert all(metric in out for metric in host_idle.METRICS)
    assert host_idle.main([BARE]) == 1
