"""Device-time attribution tests (obs/devtime + scheduler attribution):
the DispatchAccountant's two-ledger partition, the scheduler's
per-request apportionment CONSERVATION LAW (every measured tick second
lands on exactly one request — decode splits by emitted positions,
verify by its wider vectors, a prefill chunk bills wholly to its
request), the per-class cost rollup, the interference-ratio split, the
exposition round trip for the new counter families, and — against the
REAL engine with the accountant armed — the cross-plane reconciliation
the chip drill asserts over the wire. The scheduler half is
deterministic and model-free (FakeBackend + injected clock); the engine
half reuses the tiny serve-parity model."""

import threading

import pytest

from nanodiloco_tpu.obs.devtime import (
    DispatchAccountant,
    devtime_families,
    program_key,
)
from nanodiloco_tpu.serve.scheduler import GenRequest, Scheduler

from test_serve_scheduler import FakeBackend, FakeClock, _drain


# -- DispatchAccountant unit --------------------------------------------------


def test_program_key_matches_compile_counts_scheme():
    assert program_key("decode", 1, "paged-int8") == "decode:1:paged-int8"
    assert program_key("prefill_chunk", 16.0, "dense") == "prefill_chunk:16:dense"


def test_first_dispatch_books_to_compile_ledger():
    """The partition: first section of a key = trace+compile, every
    later one = warm dispatch; no second lands in both ledgers."""
    acct = DispatchAccountant()
    acct.record("decode", 1, "dense", 2.0)   # first: compile
    acct.record("decode", 1, "dense", 0.25)  # warm
    acct.record("decode", 1, "dense", 0.25)
    snap = acct.snapshot()
    assert snap["compile_seconds_by_program"] == {"decode:1:dense": 2.0}
    assert snap["device_seconds_by_program"] == {"decode:1:dense": 0.5}
    assert snap["dispatches_by_program"] == {"decode:1:dense": 3}
    assert acct.total_device_seconds() == pytest.approx(0.5)


def test_first_is_compile_false_never_compiles():
    """Sites that never trace (weight swap = device_put + validation)
    opt out: every dispatch, including the first, is warm."""
    acct = DispatchAccountant()
    acct.record("swap", 0, "dense", 1.5, first_is_compile=False)
    acct.record("swap", 0, "dense", 1.5, first_is_compile=False)
    snap = acct.snapshot()
    assert snap["compile_seconds_by_program"] == {}
    assert snap["device_seconds_by_program"] == {"swap:0:dense": 3.0}


def test_section_uses_injected_clock_and_clamps_negative():
    clock = FakeClock()
    acct = DispatchAccountant(clock=clock)
    with acct.section("decode", 1, "dense"):
        clock.advance(0.5)
    with acct.section("decode", 1, "dense"):
        clock.advance(0.25)
    snap = acct.snapshot()
    assert snap["compile_seconds_by_program"]["decode:1:dense"] == 0.5
    assert snap["device_seconds_by_program"]["decode:1:dense"] == 0.25
    # a clock running backwards (ntp step) books zero, not negative
    acct.record("decode", 1, "dense", -3.0)
    assert acct.total_device_seconds() == pytest.approx(0.25)


def test_reset_device_seconds_keeps_compile_state():
    """warm_spec's contract: the warmup ramp is exactly when programs
    compile — those seconds STAY — while its throwaway warm ticks are
    wiped, and the first-dispatch memory survives (a post-warmup tick
    must not be misbooked as a compile)."""
    acct = DispatchAccountant()
    acct.record("verify", 4, "paged", 3.0)   # compile
    acct.record("verify", 4, "paged", 0.1)   # warmup warm tick
    acct.reset_device_seconds()
    acct.record("verify", 4, "paged", 0.2)   # measured traffic
    snap = acct.snapshot()
    assert snap["compile_seconds_by_program"] == {"verify:4:paged": 3.0}
    assert snap["device_seconds_by_program"] == {"verify:4:paged": 0.2}
    # full reset drops everything including the memory
    acct.reset()
    acct.record("verify", 4, "paged", 1.0)
    assert acct.snapshot()["compile_seconds_by_program"] == {
        "verify:4:paged": 1.0
    }


def test_accountant_concurrent_records_lose_nothing():
    acct = DispatchAccountant()
    acct.record("decode", 1, "dense", 0.0)  # burn the compile slot

    def worker():
        for _ in range(500):
            acct.record("decode", 1, "dense", 0.001)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = acct.snapshot()
    assert snap["dispatches_by_program"]["decode:1:dense"] == 2001
    assert acct.total_device_seconds() == pytest.approx(2.0, rel=1e-6)


def test_devtime_families_shape_and_empty():
    assert devtime_families(None) == []
    assert devtime_families({}) == []
    fams = devtime_families({
        "device_seconds_by_program": {"decode:1:dense": 1.5,
                                      "prefill_chunk:16:dense": 0.5},
        "compile_seconds_by_program": {"decode:1:dense": 2.0},
    })
    by_name = {f[0]: f for f in fams}
    assert set(by_name) == {"nanodiloco_device_seconds",
                            "nanodiloco_compile_seconds"}
    name, mtype, _help, samples = by_name["nanodiloco_device_seconds"]
    assert mtype == "counter"
    # labeled per-program samples plus the unlabeled family total
    assert ({"program": "decode:1:dense"}, 1.5) in samples
    assert (None, 2.0) in samples


# -- scheduler attribution: the conservation law ------------------------------


class SteppingClock(FakeClock):
    """Every observation advances the clock: all timed sections measure
    a nonzero duration without any backend cooperation."""

    def __init__(self, step: float = 0.5) -> None:
        super().__init__()
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


class VectorBackend(FakeBackend):
    """Speculative-style emission: ``step()`` returns a token VECTOR per
    slot (whatever remains of the script, capped at ``k``), so one tick
    advances slots by different widths — the weighted-apportionment
    path, not the equal split."""

    def __init__(self, num_slots, scripts, chunks=None, k=3):
        super().__init__(num_slots, scripts, chunks)
        self.k = k

    def step(self):
        self.log.append(("step", tuple(self.seed_at)))
        out = []
        for s in range(self.num_slots):
            seed = self.seed_at[s]
            if seed is None:
                out.append([-1])
                continue
            vec = self.scripts[seed][self.cursor[s]:self.cursor[s] + self.k]
            self.cursor[s] += len(vec)
            out.append(list(vec))
        return out


def _attributed(results):
    return sum(r["prefill_device_s"] + r["decode_device_s"]
               for r in results)


def _measured(sched):
    s = sched.stats()
    return s["prefill_device_s"] + s["decode_s"]


@pytest.mark.parametrize("backend_cls,k", [(FakeBackend, None),
                                           (VectorBackend, 3),
                                           (VectorBackend, 1)])
def test_attributed_seconds_sum_to_measured_tick_time(backend_cls, k):
    """THE conservation law: after the schedule drains, the per-request
    attributed seconds sum EXACTLY to the measured prefill + decode
    wall time — scalar emission (equal split), wide vectors (weighted
    split), and k=1 vectors (the all-reject speculative tick: every
    slot emits one position, degenerating to the equal split)."""
    scripts = {1: list(range(10, 22)), 2: list(range(30, 37)),
               3: list(range(50, 55))}
    kwargs = {} if k is None else {"k": k}
    backend = backend_cls(2, scripts, {1: 3}, **kwargs)
    sched = Scheduler(backend, max_queue=8, clock=SteppingClock())
    tickets = [
        sched.submit(GenRequest(prompt=(5,) * 30, max_new_tokens=12,
                                seed=1, priority=0)),
        sched.submit(GenRequest(prompt=(5,), max_new_tokens=7, seed=2,
                                priority=1)),
        sched.submit(GenRequest(prompt=(5,), max_new_tokens=5, seed=3,
                                priority=3)),
    ]
    _drain(sched, tickets)
    results = [t.result for t in tickets]
    assert all(r["decode_device_s"] > 0 for r in results)
    assert _attributed(results) == pytest.approx(_measured(sched),
                                                 rel=1e-9)
    # the per-class rollup is the same total, split by priority
    by_prio = sched.stats()["device_seconds_by_priority"]
    assert set(by_prio) == {0, 1, 3}
    assert sum(by_prio.values()) == pytest.approx(_attributed(results),
                                                  abs=1e-5)


def test_attribution_survives_mid_tick_retirement():
    """A slot finishing (length bound) inside the very tick being
    apportioned still carries its share — nothing dropped or
    double-billed when requests retire at different times."""
    scripts = {1: [10, 11], 2: list(range(20, 30))}
    sched = Scheduler(FakeBackend(2, scripts), max_queue=4,
                      clock=SteppingClock())
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=2, seed=1))
    t2 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=10, seed=2))
    _drain(sched, (t1, t2))
    assert _attributed([t1.result, t2.result]) == pytest.approx(
        _measured(sched), rel=1e-9)
    # the short request decoded for fewer ticks -> strictly less billed
    assert t1.result["decode_device_s"] < t2.result["decode_device_s"]


def test_expiry_freed_slot_still_bills_its_seconds():
    """A deadline retiring a request mid-decode (and one mid-prefill)
    must not orphan the seconds already attributed: the expired
    requests' shares complete the conservation sum."""
    scripts = {1: list(range(10, 30)), 2: [40]}
    backend = FakeBackend(2, scripts, {2: 10})
    sched = Scheduler(backend, max_queue=4, clock=SteppingClock(0.25))
    # deadline_s generous enough to admit + run a few ticks (the
    # stepping clock burns 0.25 per observation), then expire
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=20, seed=1,
                                 deadline_s=8.0))
    t2 = sched.submit(GenRequest(prompt=(5,) * 100, max_new_tokens=1,
                                 seed=2, deadline_s=8.0))
    for _ in range(40):
        sched.tick()
        if t1.done() and t2.done():
            break
    assert t1.done() and t1.result["finish_reason"] == "deadline"
    assert t2.done() and t2.result["finish_reason"] == "deadline"
    assert t1.result["decode_device_s"] > 0
    assert t2.result["prefill_device_s"] > 0  # chunks ran before expiry
    assert _attributed([t1.result, t2.result]) == pytest.approx(
        _measured(sched), rel=1e-9)
    s = sched.stats()
    assert sum(s["device_seconds_by_priority"].values()) == pytest.approx(
        _attributed([t1.result, t2.result]), abs=1e-5)


def test_kv_block_seconds_bill_residency_by_class():
    """KV cost = blocks held x seconds held, settled at release and
    rolled into the per-class counter — a paged backend exposing
    ``blocks_held`` bills it, a dense one (no attribute) bills zero."""
    clock = FakeClock()
    backend = FakeBackend(1, {1: [10, 11, 12]})
    backend.blocks_held = lambda slot: 4
    sched = Scheduler(backend, max_queue=4, clock=clock)
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=3, seed=1,
                                 priority=2))
    sched.tick()          # admitted at t=0, prefill + first decode
    clock.advance(2.0)
    sched.tick()          # retires at t=2.0 (length)
    assert t1.done()
    assert t1.result["kv_block_seconds"] == pytest.approx(4 * 2.0)
    s = sched.stats()
    assert s["kv_block_seconds_by_priority"] == {
        2: pytest.approx(8.0, abs=1e-5)
    }
    # dense backend: no blocks_held attribute -> zero, key absent
    sched2 = Scheduler(FakeBackend(1, {1: [10]}), max_queue=4,
                       clock=FakeClock())
    t = sched2.submit(GenRequest(prompt=(5,), max_new_tokens=1, seed=1))
    _drain(sched2, (t,))
    assert t.result["kv_block_seconds"] == 0.0
    assert sched2.stats()["kv_block_seconds_by_priority"] == {}


def test_interference_ratio_splits_ticks_by_pending_prefill():
    """The DistServe tier-split signal: decode ticks are windowed into
    with-prefill-pending vs without; both p50s and their ratio surface
    once both windows have samples."""

    class SlowWhenPrefilling(FakeBackend):
        """step() costs 3 clock observations when a prefill is staged
        (the interference), 1 when not."""

        def __init__(self, *a, clock=None, **kw):
            super().__init__(*a, **kw)
            self.clock = clock

        def step(self):
            if any(p is not None for p in self.pending):
                self.clock()
                self.clock()
            return super().step()

    clock = SteppingClock(0.5)
    backend = SlowWhenPrefilling(
        2, {1: list(range(10, 26)), 2: [40, 41]}, {2: 6}, clock=clock)
    sched = Scheduler(backend, max_queue=4, clock=clock)
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=16, seed=1))
    sched.tick()  # t1 decoding alone: no-prefill ticks
    sched.tick()
    t2 = sched.submit(GenRequest(prompt=(5,) * 60, max_new_tokens=2,
                                 seed=2))
    _drain(sched, (t1, t2))
    s = sched.stats()
    # a bare tick is two clock observations (0.5s); an interfered one
    # adds the backend's two extra observations (1.5s) — ratio 3x
    assert s["decode_tick_p50_no_prefill_s"] == pytest.approx(0.5)
    assert s["decode_tick_p50_with_prefill_s"] == pytest.approx(1.5)
    assert s["decode_interference_ratio"] == pytest.approx(3.0)


def test_interference_ratio_absent_without_both_windows():
    """No prefill ever pending at a decode tick -> only the no-prefill
    p50 exists and the ratio stays absent (never a fake 0 or inf)."""
    sched = Scheduler(FakeBackend(1, {1: [10, 11, 12]}), max_queue=4,
                      clock=SteppingClock())
    t = sched.submit(GenRequest(prompt=(5,), max_new_tokens=3, seed=1))
    _drain(sched, (t,))
    s = sched.stats()
    assert "decode_tick_p50_no_prefill_s" in s
    assert "decode_tick_p50_with_prefill_s" not in s
    assert "decode_interference_ratio" not in s


def test_devtime_stats_passthrough():
    """A backend exposing ``devtime_stats`` (the engine's accountant)
    surfaces it under ``stats()["devtime"]``; fakes without it omit the
    key — old stats JSONLs stay parseable."""
    sched = Scheduler(FakeBackend(1, {}), max_queue=4, clock=FakeClock())
    assert "devtime" not in sched.stats()
    sched.backend.devtime_stats = lambda: {
        "device_seconds_by_program": {"decode:1:dense": 1.0},
        "compile_seconds_by_program": {},
        "dispatches_by_program": {"decode:1:dense": 5},
    }
    assert sched.stats()["devtime"]["dispatches_by_program"] == {
        "decode:1:dense": 5
    }


# -- exposition round trip for the new families -------------------------------


def test_devtime_families_round_trip_byte_exact():
    """The new counter families must survive the collector's
    parse->render loop byte-for-byte — the same bar every existing
    family meets (test_obs_collector)."""
    from nanodiloco_tpu.obs.collector import (
        flatten_families,
        parse_exposition,
        render_exposition,
    )

    fams = devtime_families({
        "device_seconds_by_program": {
            "decode:1:paged-int8": 12.345678,
            "prefill_chunk:16:paged-int8": 3.5,
            "verify:4:paged-int8": 0.25,
        },
        "compile_seconds_by_program": {"decode:1:paged-int8": 41.0},
    })
    text = render_exposition(fams)
    assert render_exposition(parse_exposition(text)) == text
    flat = flatten_families(parse_exposition(text))
    assert flat[
        'nanodiloco_device_seconds_total{program="decode:1:paged-int8"}'
    ] == pytest.approx(12.345678)
    # the unlabeled family total rides along
    assert flat["nanodiloco_device_seconds_total"] == pytest.approx(
        12.345678 + 3.5 + 0.25)
    assert flat[
        'nanodiloco_compile_seconds_total{program="decode:1:paged-int8"}'
    ] == pytest.approx(41.0)


# -- real engine: accountant armed, cross-plane reconciliation ----------------


@pytest.mark.parametrize("kv", [
    pytest.param({}, id="default"),   # blocks of 16 rows, clamped to the chunk
    pytest.param({"kv_block_size": 4}, id="bs4"),
])
def test_engine_accountant_reconciles_with_scheduler_attribution(kv):
    """The chip drill's wire assertion, in-process: with the REAL
    engine armed, (a) the dispatch ledger fills under the
    compile-counts keys for every program kind that ran, (b) the
    scheduler's per-request attribution sums to its own measured tick
    time, and (c) the scheduler's wall-clock total BOUNDS the engine's
    fence-timed warm seconds from above (the scheduler clock wraps the
    same dispatches plus Python overhead and the first-dispatch
    compiles the accountant books separately)."""
    import jax

    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.serve import InferenceEngine

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=64,
    )
    params = init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(params, cfg, num_slots=2, max_len=32,
                          chunk_size=8, **kv)
    sched = Scheduler(eng)
    tickets = [
        sched.submit(GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=6,
                                seed=7, priority=0)),
        sched.submit(GenRequest(prompt=tuple(range(1, 13)),
                                max_new_tokens=4, seed=3, priority=1)),
    ]
    _drain(sched, tickets)
    snap = eng.accountant.snapshot()
    kinds = {k.split(":", 1)[0]
             for k in snap["dispatches_by_program"]}
    assert {"prefill_chunk", "decode"} <= kinds
    # every program's first dispatch compiled; later ones ran warm
    assert snap["compile_seconds_by_program"]
    assert sum(snap["compile_seconds_by_program"].values()) > 0
    results = [t.result for t in tickets]
    measured = _measured(sched)
    assert _attributed(results) == pytest.approx(measured, rel=1e-6)
    # scheduler wall time >= engine warm fence time (same dispatches,
    # wrapped wider, compiles booked separately by the accountant)
    assert measured >= eng.accountant.total_device_seconds()
    # the stats flow carries the snapshot (server/telemetry read this)
    s = sched.stats()
    assert s["devtime"]["dispatches_by_program"] == \
        snap["dispatches_by_program"]
    assert set(s["device_seconds_by_priority"]) == {0, 1}


def test_engine_warm_spec_resets_device_not_compile_ledger():
    """warm_spec's throwaway ramp must not leak into the device-second
    budget while its compiles (the real one-off cost) stay booked."""
    import jax

    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.serve import InferenceEngine

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=64,
    )
    params = init_params(jax.random.key(0), cfg)
    eng = InferenceEngine(params, cfg, num_slots=1, max_len=32,
                          spec_k=2)
    eng.warm_spec()
    snap = eng.accountant.snapshot()
    assert snap["device_seconds_by_program"] == {}
    assert sum(snap["compile_seconds_by_program"].values()) > 0


# -- summarize_run: new keys, old JSONLs --------------------------------------


def test_summarize_run_surfaces_devtime_and_tolerates_old_jsonl(tmp_path):
    import json

    from nanodiloco_tpu.training.metrics import summarize_run

    new = tmp_path / "new.jsonl"
    recs = [
        {"serve_stats": True, "served": 3,
         "device_seconds_by_priority": {"0": 1.5, "3": 0.5},
         "kv_block_seconds_by_priority": {"0": 12.0},
         "decode_interference_ratio": 1.7,
         "devtime": {
             "device_seconds_by_program": {"decode:1:dense": 1.25},
             "compile_seconds_by_program": {"decode:1:dense": 4.0},
             "dispatches_by_program": {"decode:1:dense": 9},
         }},
    ]
    new.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    summary = summarize_run(str(new))
    assert summary["device_seconds_by_program"] == {"decode:1:dense": 1.25}
    assert summary["compile_seconds_by_program"] == {"decode:1:dense": 4.0}
    assert summary["device_seconds_by_priority"] == {"0": 1.5, "3": 0.5}
    assert summary["serve_device_seconds_total"] == pytest.approx(2.0)
    assert summary["kv_block_seconds_by_priority"] == {"0": 12.0}
    assert summary["decode_interference_ratio"] == 1.7
    # an old JSONL (pre-attribution) summarizes without the keys and
    # without raising
    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({"serve_stats": True, "served": 1}) + "\n")
    summary = summarize_run(str(old))
    assert "device_seconds_by_program" not in summary
    assert "serve_device_seconds_total" not in summary
    assert "decode_interference_ratio" not in summary


def test_compare_runs_gates_device_seconds_per_token_both_ways():
    """The cost regression gate: device_seconds_per_token regressing in
    EITHER direction (slower = cost bug, implausibly faster = the
    measurement broke) trips the comparison, relative to the baseline
    (no absolute floor — per-token seconds are tiny)."""
    from nanodiloco_tpu.training.metrics import compare_runs

    base = {"device_seconds_per_token": 1e-4}
    out = compare_runs(base, {"device_seconds_per_token": 1.02e-4},
                       max_latency_increase=0.10)
    assert out["ok"]
    out = compare_runs(base, {"device_seconds_per_token": 1.3e-4},
                       max_latency_increase=0.10)
    assert not out["ok"]
    assert "device_seconds_per_token" in out["regressions"]
    out = compare_runs(base, {"device_seconds_per_token": 0.5e-4},
                       max_latency_increase=0.10)
    assert not out["ok"]
    assert "device_seconds_per_token" in out["regressions"]
    # a baseline without the key never gates a candidate that has it
    out = compare_runs({}, {"device_seconds_per_token": 1e-4})
    assert out["ok"]
