"""Observability stack (nanodiloco_tpu/obs): span tracer, watchdog
sentinels, comm byte accounting, the report-compare regression gate,
and the end-to-end train() wiring (trace JSON, per-phase JSONL keys,
status.json)."""

import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from nanodiloco_tpu.models.config import LlamaConfig
from nanodiloco_tpu.obs.tracer import SpanTracer, current_tracer, set_tracer, trace_span
from nanodiloco_tpu.obs.watchdog import Watchdog, WatchdogConfig

SMALL_MODEL = LlamaConfig(
    vocab_size=384, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- tracer -----------------------------------------------------------------


def test_tracer_nesting_and_depth():
    clk = FakeClock()
    tr = SpanTracer(clock=clk)
    with tr.span("round"):
        clk.t += 1.0
        with tr.span("inner"):
            clk.t += 2.0
        clk.t += 0.5
    events = {e["name"]: e for e in tr.events}
    assert events["round"]["depth"] == 0
    assert events["inner"]["depth"] == 1
    assert events["round"]["dur"] == pytest.approx(3.5)
    assert events["inner"]["dur"] == pytest.approx(2.0)
    # only depth-0 spans enter the phase budget (no double counting)
    totals = tr.phase_totals()
    assert totals == {"round": pytest.approx(3.5)}
    assert tr.phase_totals() == {}  # reset happened


def test_tracer_chrome_export_is_valid_and_nested(tmp_path):
    clk = FakeClock(10.0)
    tr = SpanTracer(clock=clk)
    with tr.span("outer_sync", round=3):
        clk.t += 0.25
        with tr.span("allreduce"):
            clk.t += 0.1
        clk.t += 0.05
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.load(open(path))  # must be VALID json
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in evs} == {"outer_sync", "allreduce"}
    for e in evs:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
    # process/thread metadata: Perfetto lane names, not raw tid ints
    assert any(e["name"] == "process_name" for e in meta)
    tnames = [e for e in meta if e["name"] == "thread_name"]
    assert tnames and tnames[0]["args"]["name"] == "MainThread"
    assert tnames[0]["tid"] == evs[0]["tid"]
    parent = next(e for e in evs if e["name"] == "outer_sync")
    child = next(e for e in evs if e["name"] == "allreduce")
    # nested containment on the same tid is what Perfetto renders as a
    # flame graph
    assert child["tid"] == parent["tid"]
    assert child["ts"] >= parent["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-6
    assert parent["args"] == {"round": 3}


def test_trace_span_uses_installed_tracer():
    tr = SpanTracer(clock=FakeClock())
    prev = set_tracer(tr)
    try:
        with trace_span("phase"):
            pass
        assert [e["name"] for e in tr.events] == ["phase"]
        assert current_tracer() is tr
    finally:
        set_tracer(prev)
    # after restore, trace_span records nothing new on tr
    with trace_span("phase2"):
        pass
    assert [e["name"] for e in tr.events] == ["phase"]


# -- watchdog sentinels ------------------------------------------------------


def _wd(alarms, cfg=None, **kw):
    return Watchdog(cfg or WatchdogConfig(), emit=alarms.append, **kw)


def test_watchdog_nan_alarm_fires_once_per_episode():
    alarms = []
    wd = _wd(alarms)
    wd.observe_loss(1, float("nan"))
    wd.observe_loss(2, float("nan"))  # same episode: no second alarm
    assert len(alarms) == 1
    assert alarms[0]["alarm"] == "nan_loss" and alarms[0]["step"] == 1
    wd.observe_loss(3, 2.0)           # healthy: re-arms
    wd.observe_loss(4, float("inf"))
    assert [a["alarm"] for a in alarms] == ["nan_loss", "nan_loss"]
    assert alarms[1]["step"] == 4


def test_watchdog_loss_spike_zscore():
    alarms = []
    wd = _wd(alarms, WatchdogConfig(loss_zscore=4.0, loss_window=16))
    for i in range(16):
        wd.observe_loss(i, 2.0 + 0.01 * (i % 3))
    wd.observe_loss(100, 50.0)  # massive upward spike
    assert [a["alarm"] for a in alarms] == ["loss_spike"]
    assert alarms[0]["zscore"] > 4.0
    # a downward outlier is good news, never an alarm
    wd.observe_loss(101, 0.5)
    assert len(alarms) == 1


def test_watchdog_throughput_collapse():
    alarms = []
    wd = _wd(alarms, WatchdogConfig(tps_collapse_frac=0.5, loss_window=32))
    for i in range(8):
        wd.observe_throughput(i, 1000.0)
    wd.observe_throughput(9, 100.0)  # 10% of the median
    assert [a["alarm"] for a in alarms] == ["throughput_collapse"]
    assert alarms[0]["rolling_median"] == pytest.approx(1000.0)


def test_watchdog_stall_via_injected_clock():
    alarms = []
    clk = FakeClock()
    wd = _wd(
        alarms,
        WatchdogConfig(stall_factor=3.0, min_stall_s=5.0),
        clock=clk,
    )
    for step, t in enumerate([0.0, 10.0, 20.0]):  # mean beat: 10 s
        clk.t = t
        wd.heartbeat(step)
    clk.t = 25.0
    assert not wd.check_stall()      # 5 s silent < limit (30 s)
    clk.t = 51.0
    assert wd.check_stall()          # 31 s silent > 3 x 10 s
    assert wd.check_stall()          # still stalled...
    assert len(alarms) == 1          # ...but one alarm per episode
    assert alarms[0]["alarm"] == "stall"
    clk.t = 52.0
    wd.heartbeat(4)                  # loop came back: re-arms
    clk.t = 120.0
    assert wd.check_stall()
    assert [a["alarm"] for a in alarms] == ["stall", "stall"]


def test_watchdog_status_file(tmp_path):
    path = str(tmp_path / "status.json")
    wd = _wd([], status_path=path)
    wd.heartbeat(7, loss=3.25, tokens_per_sec=123.4)
    doc = json.load(open(path))
    assert doc["state"] == "running"
    assert doc["step"] == 7 and doc["loss"] == 3.25
    wd.stop("finished")
    assert json.load(open(path))["state"] == "finished"


def test_watchdog_alarm_lands_in_metrics_jsonl(tmp_path):
    """The injected-NaN acceptance path: an alarm emitted through
    MetricsLogger.log becomes a structured JSONL record in the same
    stream as the metrics."""
    from nanodiloco_tpu.training.metrics import MetricsLogger

    logger = MetricsLogger("wdrun", out_dir=str(tmp_path), quiet=True,
                           process_index=0)
    wd = Watchdog(WatchdogConfig(), emit=logger.log)
    wd.observe_loss(5, float("nan"))
    logger.finish()
    recs = [json.loads(l) for l in open(tmp_path / "wdrun.jsonl")]
    assert recs == [{"alarm": "nan_loss", "step": 5, "loss": "nan"}]


# -- comm byte accounting ----------------------------------------------------


def test_sync_wire_bytes_raw_vs_int4():
    from nanodiloco_tpu.parallel.diloco import Diloco, DilocoConfig
    from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(diloco=2))
    raw_dl = Diloco(SMALL_MODEL, DilocoConfig(num_workers=2), mesh)
    int4_dl = Diloco(
        SMALL_MODEL,
        DilocoConfig(num_workers=2, outer_comm_dtype="int4",
                     outer_wire_collective=True),
        mesh,
    )
    n = SMALL_MODEL.num_params()
    raw = raw_dl.sync_wire_bytes()
    assert raw["wire_bytes_per_sync"] == raw["raw_bytes_per_sync"] == 4 * n
    assert raw["wire_compression"] == 1.0
    i4 = int4_dl.sync_wire_bytes()
    # int4 payload rides an int8 accumulator at W=2: 1 byte/element,
    # plus the f32 scale-per-leaf + survivor-count overhead
    assert i4["raw_bytes_per_sync"] == 4 * n
    assert n < i4["wire_bytes_per_sync"] < 4 * n
    assert i4["wire_bytes_per_sync"] == n + i4["wire_overhead_bytes"]
    assert 3.5 < i4["wire_compression"] <= 4.0
    # the ACTUAL tree wins over the config-derived count
    state = raw_dl.init_state(jax.random.key(0))
    from_state = raw_dl.sync_wire_bytes(state.snapshot)
    n_actual = sum(
        int(np.prod(l.shape)) for l in jax.tree.leaves(state.snapshot)
    )
    assert from_state["raw_bytes_per_sync"] == 4 * n_actual


# -- report compare gate -----------------------------------------------------


def _write_run(path, tps, final_loss):
    with open(path, "w") as f:
        for i, loss in enumerate([final_loss + 1.0, final_loss], start=1):
            f.write(json.dumps({
                "loss": loss, "tokens_per_sec": tps, "step": i,
                "outer_synced": 1,
            }) + "\n")


def test_report_compare_ok_and_regression_exit_codes(tmp_path):
    from nanodiloco_tpu.cli import report_main

    base = str(tmp_path / "base.jsonl")
    good = str(tmp_path / "good.jsonl")
    slow = str(tmp_path / "slow.jsonl")
    _write_run(base, tps=1000.0, final_loss=3.0)
    _write_run(good, tps=990.0, final_loss=2.95)   # within thresholds
    _write_run(slow, tps=500.0, final_loss=3.0)    # seeded tps regression
    report_main(["compare", base, good])           # must NOT raise
    with pytest.raises(SystemExit) as e:
        report_main(["compare", base, slow])
    assert e.value.code == 1
    # threshold is configurable: a 60% allowed drop passes the same pair
    report_main(["compare", base, slow, "--max-tps-drop", "0.6"])


def test_report_compare_loss_regression_and_json(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_main

    base = str(tmp_path / "base.jsonl")
    worse = str(tmp_path / "worse.jsonl")
    _write_run(base, tps=100.0, final_loss=3.0)
    _write_run(worse, tps=100.0, final_loss=3.5)
    with pytest.raises(SystemExit):
        report_main(["compare", base, worse, "--json"])
    diff = json.loads(capsys.readouterr().out)
    assert "final_loss" in diff["regressions"]
    assert diff["metrics"]["final_loss"]["regressed"] is True


def test_report_compare_against_baseline_json(tmp_path):
    from nanodiloco_tpu.cli import report_main
    from nanodiloco_tpu.training.metrics import load_comparable

    run = str(tmp_path / "run.jsonl")
    _write_run(run, tps=100.0, final_loss=3.0)
    baseline = str(tmp_path / "BASELINE.json")
    with open(baseline, "w") as f:
        json.dump({"published": {"final_loss": 3.0,
                                 "tokens_per_sec_last": 90.0}}, f)
    report_main(["compare", baseline, run])  # candidate faster + equal loss
    # a baseline without any comparable metric is rejected loudly
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        json.dump({"metric": "prose only"}, f)
    with pytest.raises(ValueError, match="none of the comparable"):
        load_comparable(empty)


def test_summarize_run_surfaces_obs_keys(tmp_path):
    from nanodiloco_tpu.training.metrics import summarize_run

    path = tmp_path / "r.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"loss": 3.0, "step": 1, "t_inner": 0.5,
                            "t_sync": 0.1}) + "\n")
        f.write(json.dumps({"alarm": "nan_loss", "step": 2}) + "\n")
        f.write(json.dumps({"loss": 2.5, "step": 3, "t_inner": 0.7,
                            "t_sync": 0.3, "outer_synced": 1,
                            "wire_bytes_per_sync": 1000,
                            "wire_bytes_total": 2000,
                            "wire_compression": 4.0}) + "\n")
    s = summarize_run(str(path))
    assert s["alarms"] == 1 and s["alarm_kinds"] == {"nan_loss": 1}
    assert s["wire_bytes_total"] == 2000
    assert s["wire_compression"] == 4.0
    assert s["t_inner_mean_s"] == pytest.approx(0.6)
    assert s["t_sync_mean_s"] == pytest.approx(0.2)


def test_summarize_tolerates_unknown_keys_and_surfaces_drift(tmp_path):
    """The JSONL schema grows (the dynamics records added list- and
    string-valued keys); summarize_run and the compare gate must digest
    records carrying ARBITRARY unknown keys — lists, dicts, strings —
    and surface the drift summary keys when present."""
    from nanodiloco_tpu.cli import report_main
    from nanodiloco_tpu.training.metrics import summarize_run

    path = str(tmp_path / "r.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({
            "loss": 3.0, "step": 1, "tokens_per_sec": 10.0,
            "future_list_key": [1, 2, 3],
            "future_dict_key": {"nested": True},
            "future_str_key": "prose",
        }) + "\n")
        f.write(json.dumps({
            "loss": 2.5, "step": 2, "tokens_per_sec": 11.0,
            "outer_synced": 1,
            "pg_norm": [0.5, 0.6], "drift_max": 0.02, "drift_mean": 0.015,
            "outer_momentum_norm": 1.1, "outer_update_cos": 0.97,
        }) + "\n")
    s = summarize_run(path)
    assert s["final_loss"] == 2.5
    assert s["drift_max_last"] == 0.02
    assert s["drift_max_peak"] == 0.02
    assert s["outer_update_cos_last"] == 0.97
    # the gate digests the same file (unknown keys never break compare)
    report_main(["compare", path, path])


def test_report_drift_timeline(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_main

    path = str(tmp_path / "r.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"loss": 3.0, "step": 1}) + "\n")
        f.write(json.dumps({
            "loss": 2.5, "step": 2, "outer_synced": 1,
            "pg_norm": [0.5, 0.6], "drift_max": 0.02, "drift_mean": 0.015,
            "outer_momentum_norm": 1.1, "outer_update_cos": 0.97,
        }) + "\n")
        f.write(json.dumps({"alarm": "divergence", "step": 4,
                            "drift": 0.9, "threshold": 0.5}) + "\n")
        f.write(json.dumps({
            "loss": 2.4, "step": 4, "outer_synced": 1,
            "pg_norm": [0.7, 0.8], "drift_max": 0.9, "drift_mean": 0.4,
            "outer_momentum_norm": 1.2, "outer_update_cos": -0.2,
            "quarantined_workers": 1,
        }) + "\n")
        # keys PRESENT but null (older writer, torn record) — step
        # included: the human renderer must print "?", not TypeError
        f.write(json.dumps({
            "step": None, "outer_synced": 1, "drift_max": 0.03,
            "drift_mean": None, "pg_norm": [0.5, None],
        }) + "\n")
    report_main(["drift", path, "--json"])
    events = json.loads(capsys.readouterr().out)
    assert [e["event"] for e in events] == ["sync", "alarm", "sync", "sync"]
    assert events[0]["drift_max"] == 0.02
    assert events[2]["quarantined_workers"] == 1
    report_main(["drift", path])  # human form renders without tracebacks
    out = capsys.readouterr().out
    assert "drift_max=0.02" in out and "ALARM divergence" in out
    assert "drift_mean=?" in out  # null sibling key renders, not crashes
    # a dynamics-free run reports that, not an empty screen
    bare = str(tmp_path / "bare.jsonl")
    with open(bare, "w") as f:
        f.write(json.dumps({"loss": 3.0, "step": 1}) + "\n")
    report_main(["drift", bare])
    assert "no dynamics records" in capsys.readouterr().out


# -- allreduce wire audit (exact-shape classification) -----------------------


def test_allreduce_wire_report_exact_shapes():
    from nanodiloco_tpu.utils import allreduce_wire_report

    hlo = "\n".join([
        "  %a = s8[1000]{0} all-reduce(s8[1000]{0} %x), to_apply=%sum",
        "  %b = (f32[3]{0}, f32[]) all-reduce(f32[3]{0} %s, f32[] %c), to_apply=%max",
    ])
    ints, wide = allreduce_wire_report(hlo, scale_leaves=3)
    assert len(ints) == 1 and "s8[1000]" in ints[0]
    assert wide == []  # scale vector + survivor scalar are legitimate
    # a leaked f32 payload is flagged even when SMALLER than the leaf
    # count (the old size threshold would have passed it)
    leak = "  %c = f32[64]{0} all-reduce(f32[64]{0} %p), to_apply=%sum"
    _, wide = allreduce_wire_report(leak, scale_leaves=128)
    assert wide and "f32[64]" in wide[0]
    # a non-f32 float vector is never a legitimate scale op
    bf = "  %d = bf16[3]{0} all-reduce(bf16[3]{0} %p), to_apply=%sum"
    _, wide = allreduce_wire_report(bf, scale_leaves=3)
    assert wide


# -- chip_agenda child-mode validation ---------------------------------------


def test_chip_agenda_child_rejects_unknown_phase(tmp_path):
    script = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "chip_agenda.py"
    )
    env = {**os.environ, "NANODILOCO_AGENDA_OUT": str(tmp_path / "o.jsonl")}
    r = subprocess.run(
        [sys.executable, script, "--child", "nope"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r.returncode != 0
    assert "phase name" in r.stderr
    assert not os.path.exists(tmp_path / "o.jsonl")  # no bogus crash record
    r2 = subprocess.run(
        [sys.executable, script, "--child"],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert r2.returncode != 0 and "phase name" in r2.stderr


# -- end-to-end train() wiring ----------------------------------------------


def _obs_cfg(tmp_path, **kw):
    from nanodiloco_tpu.training.train_loop import TrainConfig

    defaults = dict(
        seed=1337, batch_size=4, per_device_batch_size=2, seq_length=32,
        warmup_steps=2, total_steps=6, inner_steps=3, lr=1e-3,
        num_workers=2, model=SMALL_MODEL, log_dir=str(tmp_path),
        quiet=True, use_wandb=False, checkpoint_dir=None,
        trace_out=str(tmp_path / "trace.json"),
        status_file=str(tmp_path / "status.json"),
    )
    defaults.update(kw)
    return TrainConfig(**defaults)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "stepwise"])
def test_train_emits_trace_phases_and_wire_metrics(tmp_path, fused):
    from nanodiloco_tpu.training.train_loop import train

    run = f"obs_{'fused' if fused else 'step'}"
    out = train(_obs_cfg(tmp_path, fused_rounds=fused, run_name=run))
    assert out["alarms"] == 0
    assert out["wire_bytes_total"] == 2 * out["wire_bytes_per_sync"] > 0

    # Chrome trace: valid JSON, the expected phases, and span coverage
    # of the round wall-clock (the acceptance bar is >=95%; asserted a
    # little lower to keep CI noise out of the gate)
    doc = json.load(open(tmp_path / "trace.json"))
    evs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert {"data", "inner"} <= names
    assert ("sync" in names) != fused  # fused rounds contain their sync
    t0 = min(e["ts"] for e in evs)
    t1 = max(e["ts"] + e["dur"] for e in evs)
    covered = sum(
        e["dur"] for e in evs
        if not any(  # count only depth-0 spans (avoid double counting)
            o is not e and o["tid"] == e["tid"]
            and o["ts"] <= e["ts"] and e["ts"] + e["dur"] <= o["ts"] + o["dur"]
            for o in evs
        )
    )
    assert covered / (t1 - t0) >= 0.90, f"spans cover {covered / (t1 - t0):.0%}"

    # JSONL: sync records carry the per-phase budget + wire ledger
    recs = [json.loads(l) for l in open(tmp_path / f"{run}.jsonl")]
    syncs = [r for r in recs if r.get("outer_synced")]
    assert len(syncs) == 2

    # the one-time XLA cost record (obs/costs): captured from the
    # program each mode actually dispatches, per-token normalized, hand
    # formula embedded at the same shapes
    cost = [r["cost_analysis"] for r in recs
            if isinstance(r.get("cost_analysis"), dict)]
    assert len(cost) == 1
    assert cost[0]["program"] == ("fused_round" if fused else "inner_step")
    assert cost[0]["flops"] > 0 and cost[0]["flops_per_token"] > 0
    assert cost[0]["flops_per_token_hand"] > 0
    # and which implementation the program's attention takes: on the CPU
    # every layer runs dense blocks (models/llama.py:attention_paths)
    paths = cost[0]["attention_paths"]
    assert paths["fused"] == 0 and paths["dense"] > 0
    for r in syncs:
        assert r["t_inner"] > 0 and "t_data" in r
        assert r["wire_bytes_per_sync"] > 0 and r["wire_compression"] == 1.0
    assert syncs[-1]["wire_bytes_total"] == out["wire_bytes_total"]

    # status.json reached its terminal state
    status = json.load(open(tmp_path / "status.json"))
    assert status["state"] == "finished"
    assert status["step"] == 6 and status["alarms"] == 0


def test_train_cli_flags_reach_config():
    from nanodiloco_tpu.cli import build_parser, config_from_args

    args = build_parser().parse_args([
        "--trace-out", "/tmp/t.json", "--status-file", "/tmp/s.json",
        "--watch-loss-zscore", "4.5", "--watch-stall-factor", "0",
        "--watch-tps-collapse", "0.25", "--watch-loss-window", "64",
        "--metrics-port", "0", "--no-cost-analysis",
    ])
    cfg = config_from_args(args)
    assert cfg.trace_out == "/tmp/t.json"
    assert cfg.status_file == "/tmp/s.json"
    assert cfg.watch_loss_zscore == 4.5
    assert cfg.watch_stall_factor == 0.0
    assert cfg.watch_tps_collapse == 0.25
    assert cfg.watch_loss_window == 64
    assert cfg.metrics_port == 0
    assert cfg.cost_analysis is False
    # both default OFF/ON respectively
    dflt = config_from_args(build_parser().parse_args([]))
    assert dflt.metrics_port is None and dflt.cost_analysis is True


# -- metrics logger path contract --------------------------------------------


def test_metrics_logger_path_is_none_without_out_dir(tmp_path):
    from nanodiloco_tpu.training.metrics import MetricsLogger

    fileless = MetricsLogger("r", out_dir=None, quiet=True, process_index=0)
    assert fileless.path is None           # was AttributeError before
    nonwriter = MetricsLogger("r", out_dir=str(tmp_path), quiet=True,
                              process_index=1)
    assert nonwriter.path is None          # non-writer ranks never open one
    writer = MetricsLogger("r", out_dir=str(tmp_path), quiet=True,
                           process_index=0)
    assert writer.path == str(tmp_path / "r.jsonl")
    for lg in (fileless, nonwriter, writer):
        lg.finish()


# -- watchdog live status document -------------------------------------------


def test_watchdog_status_doc_and_alarm_kinds():
    alarms = []
    wd = _wd(alarms)
    wd.heartbeat(3, loss=2.0)
    doc = wd.status_doc()
    assert doc["state"] == "running" and doc["step"] == 3
    assert "alarm_kinds" not in doc
    wd.observe_loss(4, float("nan"))
    wd.observe_loss(5, 2.0)               # re-arm
    wd.observe_loss(6, float("nan"))      # second episode
    doc = wd.status_doc()
    assert doc["alarm_kinds"] == {"nan_loss": 2}
    assert wd.alarm_kinds == {"nan_loss": 2}
    wd.stop("finished")
    assert wd.status_doc()["state"] == "finished"


# -- trace shards + merge ----------------------------------------------------


def _shard(process_index, wall0, spans):
    """Synthetic rank shard: spans = [(name, t0, dur)], a fixed wall
    anchor standing in for the per-host clock."""
    clk = FakeClock()
    tr = SpanTracer(clock=clk, process_index=process_index)
    for name, t0, dur in spans:
        clk.t = t0
        with tr.span(name):
            clk.t = t0 + dur
    doc = tr.to_chrome()
    doc["otherData"]["wall_start_unix"] = wall0
    return doc


def test_trace_shard_path():
    from nanodiloco_tpu.obs.tracer import trace_shard_path

    assert trace_shard_path("/x/trace.json", 0) == "/x/trace.json"
    assert trace_shard_path("/x/trace.json", 2) == "/x/trace.rank2.json"
    assert trace_shard_path("/x/trace", 1) == "/x/trace.rank1.json"


def test_merge_chrome_traces_aligns_and_separates_pids():
    from nanodiloco_tpu.obs.tracer import merge_chrome_traces

    # rank 1's wall clock starts 2 s after rank 0's; both record a sync
    # span at local t0=1.0 — after merging, rank 1's must sit 2 s later
    s0 = _shard(0, wall0=100.0, spans=[("sync", 1.0, 0.5)])
    s1 = _shard(1, wall0=102.0, spans=[("sync", 1.0, 0.5)])
    merged = merge_chrome_traces([s0, s1])
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2
    pids = {e["pid"] for e in xs}
    assert len(pids) == 2  # one lane per process
    by_pid = {e["pid"]: e for e in xs}
    p0, p1 = sorted(by_pid)
    skew_us = by_pid[p1]["ts"] - by_pid[p0]["ts"]
    assert skew_us == pytest.approx(2.0 * 1e6)
    # every pid carries a process_name metadata event
    meta_pids = {
        e["pid"] for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert meta_pids == pids
    # pid collision (two shards both claiming rank 0) must NOT overlay
    dup = merge_chrome_traces([s0, _shard(0, wall0=101.0,
                                          spans=[("sync", 0.0, 0.1)])])
    assert len({e["pid"] for e in dup["traceEvents"]}) == 2


def test_merge_mixed_train_and_serve_shards():
    """A serve-side trace (process_index 0, distinct process name,
    retroactive record_span events) merged with a 2-host training trace:
    every shard gets its own pid lane, the serve shard's process-name
    metadata survives verbatim, span args (request ids) are preserved,
    and no two shards overlay (the serve shard's pid-0 claim collides
    with train rank 0 and must fall back to an ordinal pid)."""
    from nanodiloco_tpu.obs.tracer import merge_chrome_traces

    t0 = _shard(0, wall0=100.0, spans=[("inner", 0.0, 1.0), ("sync", 1.0, 0.2)])
    t1 = _shard(1, wall0=100.5, spans=[("inner", 0.0, 1.0), ("sync", 1.1, 0.2)])
    clk = FakeClock()
    serve = SpanTracer(clock=clk, process_index=0,
                       process_name="nanodiloco serve")
    serve.record_span("queued", 0.0, 0.3, request_id="req-0")
    serve.record_span("prefill", 0.3, 0.5, request_id="req-0", slot=1)
    serve.record_span("decode", 0.5, 2.0, request_id="req-0", tokens=8)
    sdoc = serve.to_chrome()
    sdoc["otherData"]["wall_start_unix"] = 101.0

    merged = merge_chrome_traces([t0, t1, sdoc])
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 7  # 2+2 train spans, 3 serve spans — none dropped
    pids = {e["pid"] for e in xs}
    assert len(pids) == 3  # serve's rank-0 collision fell back, no overlay
    names = {
        e["pid"]: e["args"]["name"]
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert set(names) == pids
    assert "nanodiloco serve" in names.values()  # metadata preserved
    decode = next(e for e in xs if e["name"] == "decode")
    assert decode["args"] == {"request_id": "req-0", "tokens": 8}
    assert decode["dur"] == pytest.approx(1.5e6)
    # the serve shard re-anchored onto the earliest wall clock: its
    # queued span (local t=0 at wall 101.0) sits 1 s after train t0's
    # local t=0 (wall 100.0)
    queued = next(e for e in xs if e["name"] == "queued")
    assert queued["ts"] == pytest.approx(1.0e6)


def test_record_span_feeds_phase_totals():
    clk = FakeClock()
    tr = SpanTracer(clock=clk)
    tr.record_span("decode", 1.0, 3.5, request_id="r1")
    tr.record_span("decode", 4.0, 4.5, request_id="r2")
    totals = tr.phase_totals()
    assert totals["decode"] == pytest.approx(3.0)
    # negative intervals clamp to zero rather than corrupting the trace
    tr.record_span("weird", 5.0, 4.0)
    assert tr.phase_totals()["weird"] == 0.0


def test_profiler_window_released_when_start_trace_fails(monkeypatch, tmp_path):
    """The startup-profile helper must not leak the process-global
    profiler lock when jax's start_trace raises — a leaked lock turns
    every later /debug/profile into a 409 and a later profiled train()
    into a silent hang on acquire."""
    from nanodiloco_tpu.obs import telemetry as tmod
    from nanodiloco_tpu.training import train_loop as tl

    def boom(_dir, **_options):
        raise RuntimeError("profiler broken")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    with pytest.raises(RuntimeError, match="profiler broken"):
        tl._profiler_start(str(tmp_path))
    assert not tmod._PROFILE_LOCK.locked()


def test_watchdog_divergence_sentinel():
    """The drift alarm: fires past the threshold (or on non-finite
    drift), once per episode, re-arming on a healthy observation —
    and stays silent when disabled."""
    from nanodiloco_tpu.obs.watchdog import Watchdog, WatchdogConfig

    recs = []
    wd = Watchdog(WatchdogConfig(drift_threshold=0.5), emit=recs.append)
    wd.observe_drift(2, 0.1)
    assert recs == []
    wd.observe_drift(4, 0.6)
    assert len(recs) == 1
    assert recs[0]["alarm"] == "divergence" and recs[0]["step"] == 4
    assert recs[0]["drift"] == 0.6 and recs[0]["threshold"] == 0.5
    wd.observe_drift(6, 0.7)  # same episode: no second alarm
    assert len(recs) == 1
    wd.observe_drift(8, 0.2)   # healthy: re-arms
    wd.observe_drift(10, float("nan"))  # a blown-up replica is alarming
    assert len(recs) == 2 and recs[1]["drift"] == "nan"

    off = Watchdog(WatchdogConfig(drift_threshold=0.0), emit=recs.append)
    off.observe_drift(2, 1e9)
    assert len(recs) == 2


def test_report_merge_trace_cli(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_main

    paths = []
    for k, wall in ((0, 50.0), (1, 50.25)):
        doc = _shard(k, wall, spans=[("inner", 0.0, 1.0), ("sync", 1.0, 0.2)])
        p = str(tmp_path / f"trace.rank{k}.json")
        with open(p, "w") as f:
            json.dump(doc, f)
        paths.append(p)
    out = str(tmp_path / "merged.json")
    report_main(["merge-trace", *paths, "-o", out])
    assert "2 process(es)" in capsys.readouterr().out
    merged = json.load(open(out))  # valid JSON on disk
    xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 4 and len({e["pid"] for e in xs}) == 2


# -- XLA cost analytics ------------------------------------------------------


def test_cost_analysis_probe_matches_hand_formula():
    """The unrolled one-microbatch probe's FLOPs/token must land within
    2x of bench.py's hand formula — the reconciliation `report cost`
    performs, asserted at the source. Also pins the XLA loop-once
    behaviour the probe exists to work around: the dispatched round
    executable's billed FLOPs must NOT change with H or grad_accum (if
    this starts failing, a jax upgrade began multiplying trip counts —
    revisit obs/costs' caveat before trusting new numbers)."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from nanodiloco_tpu.obs.costs import train_flops_per_token
    from nanodiloco_tpu.parallel.diloco import Diloco, DilocoConfig
    from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh

    # loss_chunk=0: the chunked CE pads B*S rows up to the 512-row chunk
    # — real counted work at these tiny shapes that the hand formula
    # (useful tokens only) can't see; the reconciliation runs unchunked
    model = _dc.replace(SMALL_MODEL, loss_chunk=0)
    W, B, S = 2, 2, 64
    mesh = build_mesh(MeshConfig(diloco=W))

    def build(H, accum):
        dl = Diloco(
            model,
            DilocoConfig(num_workers=W, inner_steps=H, grad_accum=accum),
            mesh,
        )
        return dl, dl.init_state(jax.random.key(0))

    dl, state = build(2, 1)
    probe = dl.microbatch_cost_analysis(state, (B, S))
    assert probe and probe["flops"] > 0
    hand = train_flops_per_token(model, S)
    ratio = (probe["flops"] / (B * S)) / hand
    assert 0.5 < ratio < 2.0, f"probe/hand FLOPs ratio {ratio:.3f}"

    def round_billed(H, accum):
        dl, state = build(H, accum)
        tok = jax.random.randint(
            jax.random.key(1), (H, W, accum, B, S), 0, model.vocab_size
        )
        analysis = dl.round_cost_analysis(state, tok, jnp.ones_like(tok))
        assert analysis and analysis["flops"] > 0
        return analysis["flops"]

    assert round_billed(2, 1) == round_billed(4, 2)  # loop-once pinned


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_build_cost_record_and_analytic_mfu(monkeypatch):
    from nanodiloco_tpu.obs import costs
    from nanodiloco_tpu.obs.costs import analytic_mfu, build_cost_record

    # the table is keyed by the exact device_kind string jax reports
    monkeypatch.setitem(costs.PEAK_TFLOPS_BY_KIND, "TPU test", 100.0)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU test")]
    )
    rec = build_cost_record(
        program="fused_round",
        billed={"flops": 5e8, "bytes_accessed": 1e9},
        probe={"flops": 2e9}, probe_tokens=1000, num_devices=2,
        model_cfg=SMALL_MODEL, seq=64,
    )
    assert rec["flops_per_token"] == pytest.approx(2e6)
    assert rec["flops_billed"] == 5e8
    assert rec["bytes_accessed_billed"] == 1e9
    assert rec["flops_per_token_hand"] > 0
    assert rec["peak_tflops"] == 100.0
    # 1e6 tok/s x 2e6 flops/tok = 2e12 flop/s over 2 chips x 100 TF = 1%
    assert analytic_mfu(rec, 1e6) == pytest.approx(0.01)
    # an accelerator the table does not know is an error — a substring
    # match ("v5" catching anything) is how a wrong ceiling gets in
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v5 litest")]
    )
    with pytest.raises(ValueError, match="no bf16 peak known"):
        build_cost_record(program="x", billed={"flops": 2e9})
    # the CPU backend (a run asked for by name) has no peak and no MFU,
    # never a fake ceiling; a probe-less record (the loss path the probe
    # can't lower) still carries the billed numbers
    monkeypatch.undo()
    rec_cpu = build_cost_record(
        program="x", billed={"flops": 2e9},
    )
    assert "flops_per_token" not in rec_cpu
    assert "peak_tflops" not in rec_cpu
    assert rec_cpu["flops_billed"] == 2e9
    assert analytic_mfu(rec_cpu, 1e6) is None


def _write_cost_run(path, tps, final_loss, peak=0.1):
    with open(path, "w") as f:
        f.write(json.dumps({"cost_analysis": {
            "program": "fused_round", "flops": 1e9,
            "tokens_counted": 1000, "flops_per_token": 1e6,
            "flops_per_token_hand": 9e5, "peak_tflops": peak,
            "num_devices": 1, "device_kind": "test",
        }, "step": 0}) + "\n")
        for i, loss in enumerate([final_loss + 1.0, final_loss], start=1):
            f.write(json.dumps({
                "loss": loss, "tokens_per_sec": tps, "step": i,
                "outer_synced": 1, "wire_bytes_per_sync": 1000,
                "wire_bytes_total": 1000 * i,
            }) + "\n")


def test_summarize_and_compare_gate_mfu_analytic(tmp_path):
    from nanodiloco_tpu.training.metrics import compare_runs, summarize_run

    base = str(tmp_path / "base.jsonl")
    slow = str(tmp_path / "slow.jsonl")
    _write_cost_run(base, tps=1000.0, final_loss=3.0)
    _write_cost_run(slow, tps=500.0, final_loss=3.0)
    sb, sc = summarize_run(base), summarize_run(slow)
    assert sb["mfu_analytic"] == pytest.approx(1000.0 * 1e6 / (0.1 * 1e12))
    assert sb["flops_per_token_analytic"] == pytest.approx(1e6)
    diff = compare_runs(sb, sc)
    assert "mfu_analytic" in diff["regressions"]  # halved tps = halved MFU
    # a summary without the metric never gates (missing-metric rule)
    sc2 = dict(sc)
    del sc2["mfu_analytic"]
    diff2 = compare_runs(sb, sc2)
    assert diff2["metrics"]["mfu_analytic"]["gated"] is False


def test_report_cost_cli(tmp_path, capsys):
    from nanodiloco_tpu.cli import report_main

    run = str(tmp_path / "run.jsonl")
    _write_cost_run(run, tps=1000.0, final_loss=3.0)
    report_main(["cost", run, "--json"])
    out = json.loads(capsys.readouterr().out)
    assert out["program"] == "fused_round"
    assert out["mfu_analytic"] == pytest.approx(0.01)
    assert out["analytic_vs_hand_ratio"] == pytest.approx(1e6 / 9e5, abs=1e-3)
    assert out["wire_bytes_per_sync_analytic"] == 1000
    assert out["wire_bytes_per_sync_ledger"] == 1000
    assert out["wire_match"] is True
    # a run without the record fails loudly, not with a zero MFU
    bare = str(tmp_path / "bare.jsonl")
    _write_run(bare, tps=10.0, final_loss=1.0)
    with pytest.raises(SystemExit):
        report_main(["cost", bare])
