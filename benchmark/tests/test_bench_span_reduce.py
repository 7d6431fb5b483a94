"""The span and scope reduction on hand-made intervals and on small
traces recorded on a TPU v5e: PR 23's toy training round (a program
without spans or scopes: what the parent commit gives) and PR 24's toy
serving ticks (0.15 s, 18 ticks) and toy training round, each with its
``/host:metadata`` plane (the programs' HLO protos, a megabyte that no
reduction reads) taken out of the file (``span_reduce.py``)."""

import os

import pytest

from benchmark import span_reduce, trace_reduce
from benchmark.readers import (
    attention_device_pct,
    host_ms_per_tick,
    itl_p50_ms,
    itl_p99_ms,
    kv_move_device_pct,
    loss_device_pct,
    unscoped_pct,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BARE = os.path.join(DATA, "tiny_train_v5e.xplane.pb")          # PR 23: no spans
SERVE = os.path.join(DATA, "tiny_serve_spans_v5e.xplane.pb")   # PR 24
TRAIN = os.path.join(DATA, "tiny_train_spans_v5e.xplane.pb")   # PR 24

TICKS = [
    (0, 100, "sched.tick"), (10, 30, "sched.admit"), (15, 25, "engine.start_prefill"),
    (40, 70, "engine.decode_dispatch"), (70, 90, "engine.fetch_tokens"),
    (100, 108, "sched.idle"),
    (110, 200, "sched.tick"), (120, 140, "engine.prefill_chunk"),
    (150, 180, "engine.decode_dispatch"), (180, 195, "engine.fetch_tokens"),
    (200, 230, "sched.tick"), (205, 215, "engine.prefill_chunk"),
    (230, 300, "sched.tick"), (240, 290, "engine.decode_dispatch"),
]


def test_self_segments_give_every_instant_to_the_innermost_span():
    segs = span_reduce.self_segments(TICKS[:5])
    assert segs == [
        (0, 10, "sched.tick"), (10, 15, "sched.admit"),
        (15, 25, "engine.start_prefill"), (25, 30, "sched.admit"),
        (30, 40, "sched.tick"), (40, 70, "engine.decode_dispatch"),
        (70, 90, "engine.fetch_tokens"), (90, 100, "sched.tick")]
    # self time: a span's duration less its children's
    self_ns = {}
    for s, e, name in segs:
        self_ns[name] = self_ns.get(name, 0) + e - s
    assert self_ns["sched.tick"] == 100 - 20 - 30 - 20
    assert self_ns["sched.admit"] == 20 - 10


def test_tick_host_is_start_to_start_less_device_calls_and_idle():
    """Tick 1: 110 to the next start, less dispatch 30, fetch 20, idle 8
    = 52. Tick 2: 90 less chunk 20, dispatch 30, fetch 15 = 25. Tick 3
    holds no decode dispatch; tick 4 has no successor."""
    count, mean_s = span_reduce.tick_host(TICKS)
    assert count == 2 and mean_s == pytest.approx((52 + 25) / 2 / 1e9)
    assert span_reduce.tick_host(TICKS[:5]) is None
    assert span_reduce.tick_host([]) is None


def test_gaps_go_to_the_innermost_span_and_the_rest_to_no_span():
    segs = span_reduce.self_segments(TICKS)
    got = span_reduce.attribute([(5, 12), (95, 112), (300, 310)], segs)
    assert got == pytest.approx({
        "sched.tick": (5 + 5 + 2) / 1e9, "sched.admit": 2 / 1e9,
        "sched.idle": 8 / 1e9, span_reduce.NO_SPAN: (2 + 10) / 1e9})
    assert sum(got.values()) == pytest.approx((7 + 17 + 10) / 1e9)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_round_step)/while/body/transpose(jvp(attention))/dot_general:", "attention"),
    ("jit(f)/jvp(loss)/loss/while/body/checkpoint/jit(take_along_axis)/select_n:", "loss"),
    ("jit(f)/vmap(inner_opt)/jit(norm)/sqrt:", "inner_opt"),    # jit(norm): a function
    ("jit(f)/while/body/jvp(norm)/reduce_sum:", "norm"),
    ("jit(run)/while/body/attn_proj/jit(outer)/mul:", "attn_proj"),
    ("jit(run)/while/body/kv_gather/gather:", "kv_gather"),
    ("jit(_round_step)/while:", None), ("", None),
    ("jit(f)/normalize/add:", None),                              # whole words only
])
def test_scope_of_an_op_name(op_name, scope):
    assert span_reduce.scope_of(op_name) == scope


def test_a_program_without_spans_or_scopes_reads_nothing():
    """PR 23's recorded round: ``tf_op`` is on the metadata of half the
    operations and names no scope; no host event has a ``layer``."""
    names = span_reduce.op_names(BARE)
    assert len(names) == 254
    assert all(v.startswith("jit(_round_step)") for v in names.values())
    got = span_reduce.reduce_spans(BARE)
    assert got["spans"] == {} and got["ticks"] is None and got["scopes"] is None
    assert got["window_s"] == pytest.approx(0.00175676)
    want = trace_reduce.reduce_trace(BARE)
    assert got["idle"]["idle_s"] == pytest.approx(want["window_s"] - want["busy_s"])
    assert got["idle"]["by_span"] == {span_reduce.NO_SPAN: got["idle"]["between_s"]}
    assert any("metadata.tf_op" in line for line in span_reduce.describe(BARE, 50))


def test_recorded_serving_ticks():
    got = span_reduce.reduce_spans(SERVE)
    spans = got["spans"]
    for name in ("sched.tick", "sched.control", "sched.expire", "sched.admit",
                 "engine.stage", "engine.decode_dispatch", "engine.fetch_tokens",
                 "engine.advance", "sched.deliver"):
        assert spans[name]["count"] >= 2, name
        assert 0 <= spans[name]["self_s"] <= spans[name]["total_s"] + 1e-12
    assert {s["layer"] for s in spans.values()} == {"sched", "engine"}
    # a tick in flight when the capture starts or stops leaves its
    # finished children and no event of its own
    assert 0 <= spans["engine.decode_dispatch"]["count"] - spans["sched.tick"]["count"] <= 1
    # a tick's time is its steps': next to none of it is the tick's own
    assert spans["sched.tick"]["self_s"] < 0.05 * spans["sched.tick"]["total_s"]
    assert spans["sched.prefill"]["self_s"] < spans["sched.prefill"]["total_s"]
    ticks = got["ticks"]
    assert ticks["count"] >= 2 and 0 < ticks["host_ms"] < 1e3 * got["window_s"]
    idle = got["idle"]
    assert idle["between_s"] + idle["in_program_s"] == pytest.approx(idle["idle_s"])
    assert sum(idle["by_span"].values()) == pytest.approx(idle["between_s"])
    # the host between programs is in the program's spans, not outside them
    assert idle["by_span"].get(span_reduce.NO_SPAN, 0) < 0.1 * idle["between_s"]
    scopes = got["scopes"]
    assert {"kv_write", "kv_gather", "attention", "attn_proj", "mlp"} <= set(scopes["by_scope"])
    assert sum(scopes["by_scope"].values()) + scopes["unscoped_s"] == pytest.approx(
        scopes["leaf_s"])


def test_recorded_training_round():
    got = span_reduce.reduce_spans(TRAIN)
    assert got["spans"]["diloco.round"]["layer"] == "diloco"
    assert got["ticks"] is None
    scopes = got["scopes"]
    assert {"attention", "attn_proj", "mlp", "loss", "norm", "inner_opt",
            "outer"} <= set(scopes["by_scope"])
    assert scopes["unscoped_s"] < 0.5 * scopes["leaf_s"]


def test_the_readers_on_what_a_run_observed(monkeypatch):
    obs = {"requests": [
        {"timing": {"token_s": [0.30, 0.38, 0.46, 0.60]}},
        {"timing": {"token_s": [0.50, 0.59]}},
        {"timing": {"ttft_s": 0.2}},             # a server that stamps no token
    ]}
    assert itl_p50_ms.token_gaps(obs) == pytest.approx([0.08, 0.08, 0.14, 0.09])
    assert itl_p50_ms.read(obs) == pytest.approx(80.0)
    assert itl_p99_ms.read(obs) == pytest.approx(140.0)
    assert itl_p50_ms.read({"requests": obs["requests"][2:]}) is None
    trace_readers = (attention_device_pct, loss_device_pct, kv_move_device_pct,
                     unscoped_pct, host_ms_per_tick)
    for reader in trace_readers:                 # a CPU run: no device plane
        assert reader.read({"trace": None}) is None and reader.read({}) is None
    monkeypatch.setattr(span_reduce.tr, "find_xplane", lambda root: BARE)
    for reader in trace_readers:                 # the parent's program
        assert reader.read({"trace": {"busy_s": 1.0}}) is None
    monkeypatch.setattr(span_reduce.tr, "find_xplane", lambda root: SERVE)
    run = {"trace": {"busy_s": 1.0}}
    assert 0 < kv_move_device_pct.read(run) < 100
    assert 0 < unscoped_pct.read(run) < 100
    assert host_ms_per_tick.read(run) == span_reduce.reduce_spans(SERVE)["ticks"]["host_ms"]
    assert loss_device_pct.read(run) > 0         # the toy's head runs under `head`
