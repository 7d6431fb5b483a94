"""Tier-1 regression self-gate: every suite run trains a fresh 6-step
smoke and `report compare`s it against the committed
runs/smoke_baseline.json, exiting non-zero past the thresholds — so
the gate PR 1 built is EXERCISED on every run, not just available.

Gating policy: the loss metrics ride the default relative thresholds
(the seeded smoke is deterministic, so a real change shows up far above
2%); throughput is gated only against catastrophic collapse
(--max-tps-drop 0.95) because CI machines differ — the committed
tokens/sec is one machine's number and must not flake every other.

Regenerate the baseline after an INTENTIONAL change to the smoke
trajectory (optimizer semantics, data order, model defaults):

    JAX_PLATFORMS=cpu python tests/test_smoke_gate.py
"""

import json
import os
import sys

import pytest

# direct-run regeneration entry executes from tests/: put the repo root
# on the path first (no-op under pytest, which runs from the root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from nanodiloco_tpu.models.config import LlamaConfig  # noqa: E402

BASELINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "runs", "smoke_baseline.json",
)

SMOKE_MODEL = LlamaConfig(
    vocab_size=384, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)


def smoke_config(log_dir: str, **kw):
    """The ONE smoke definition both the gate and the baseline
    regenerator run — they must never drift apart. ``kw`` lets variant
    gates (the no-op fault plan below) ride the same definition."""
    from nanodiloco_tpu.training.train_loop import TrainConfig

    return TrainConfig(
        seed=1337, batch_size=4, per_device_batch_size=2, seq_length=32,
        warmup_steps=2, total_steps=6, inner_steps=3, lr=1e-3,
        num_workers=2, model=SMOKE_MODEL, log_dir=log_dir, quiet=True,
        run_name="smoke", measure_comm=False, **kw,
    )


def _run_smoke(log_dir: str, **kw) -> str:
    from nanodiloco_tpu.training.train_loop import train

    train(smoke_config(log_dir, **kw))
    return os.path.join(log_dir, "smoke.jsonl")


def test_smoke_regression_gate(tmp_path):
    from nanodiloco_tpu.cli import report_main

    assert os.path.exists(BASELINE), (
        f"committed baseline missing: {BASELINE} — regenerate with "
        "`JAX_PLATFORMS=cpu python tests/test_smoke_gate.py`"
    )
    jsonl = _run_smoke(str(tmp_path))
    # raises SystemExit(1) on regression — THE gate, live in tier-1
    report_main(["compare", BASELINE, jsonl, "--max-tps-drop", "0.95"])


def test_smoke_gate_under_noop_fault_plan(tmp_path):
    """The resilience hook points (fault plan armed, no fault ever due)
    must not perturb the training trajectory: the same smoke under a
    no-op plan must be STEP-FOR-STEP IDENTICAL to a plan-free smoke and
    still pass the committed-baseline gate — zero-cost-when-unused,
    asserted, not assumed."""
    from nanodiloco_tpu.cli import report_main

    plan = str(tmp_path / "noop_plan.json")
    with open(plan, "w") as f:
        json.dump({"faults": [
            {"kind": "crash", "step": 10_000_000},
            {"kind": "stall", "step": 10_000_000, "seconds": 1.0},
            {"kind": "io_error", "step": 10_000_000, "op": "save"},
            {"kind": "nan_params", "step": 10_000_000, "worker": 0},
            {"kind": "straggler", "step": 10_000_000, "worker": 0,
             "seconds": 1.0, "rounds": 2},
            {"kind": "resize", "step": 10_000_000, "workers": 4},
        ]}, f)
    bare = _run_smoke(str(tmp_path / "bare"))
    hooked = _run_smoke(str(tmp_path / "hooked"), fault_plan=plan)
    bare_losses = [json.loads(l).get("loss") for l in open(bare)]
    hooked_losses = [json.loads(l).get("loss") for l in open(hooked)]
    assert bare_losses == hooked_losses
    report_main(["compare", BASELINE, hooked, "--max-tps-drop", "0.95"])


def test_smoke_gate_dynamics_metrics_side_effect_free(tmp_path):
    """THE dynamics-metrics no-side-effects proof: the same smoke with
    the on-device dynamics readout disabled is STEP-FOR-STEP IDENTICAL
    in losses to the default (dynamics on) run, and the on-run's sync
    records carry non-zero drift / per-worker pseudo-gradient norms —
    free observability, asserted, not assumed. The off-run also rides
    the committed-baseline gate (whose baseline was recorded with
    dynamics ON), pinning that the flag cannot move the trajectory."""
    from nanodiloco_tpu.cli import report_main

    on = _run_smoke(str(tmp_path / "on"))  # dynamics_metrics defaults True
    off = _run_smoke(str(tmp_path / "off"), dynamics_metrics=False)
    on_recs = [json.loads(l) for l in open(on)]
    off_losses = [json.loads(l).get("loss") for l in open(off)]
    assert [r.get("loss") for r in on_recs] == off_losses
    syncs = [r for r in on_recs if r.get("drift_max") is not None]
    assert len(syncs) == 2  # one dynamics record per outer sync
    for r in syncs:
        assert r["drift_max"] > 0 and r["drift_mean"] > 0
        assert len(r["pg_norm"]) == 2 and all(n > 0 for n in r["pg_norm"])
        assert r["outer_momentum_norm"] > 0
        assert -1.0 <= r["outer_update_cos"] <= 1.0
    off_recs = [json.loads(l) for l in open(off)]
    assert not any(r.get("drift_max") is not None for r in off_recs)
    report_main(["compare", BASELINE, off, "--max-tps-drop", "0.95"])


def test_smoke_gate_actually_fires(tmp_path):
    """The gate must be able to fail: the same fresh smoke against a
    baseline whose loss is unreachably low exits non-zero (a gate that
    can only pass is decoration)."""
    from nanodiloco_tpu.cli import report_main

    jsonl = _run_smoke(str(tmp_path))
    rigged = str(tmp_path / "rigged.json")
    with open(rigged, "w") as f:
        json.dump({"published": {"final_loss": 0.001}}, f)
    with pytest.raises(SystemExit) as e:
        report_main(["compare", rigged, jsonl])
    assert e.value.code == 1


if __name__ == "__main__":
    # baseline regeneration entry (never runs under pytest) — mirror
    # conftest's backend exactly (cpu, 8 virtual devices) so the
    # recorded trajectory is the one the gate will reproduce
    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    from nanodiloco_tpu.training.metrics import summarize_run

    with tempfile.TemporaryDirectory() as td:
        summary = summarize_run(_run_smoke(td))
    published = {
        k: summary[k]
        for k in ("final_loss", "best_loss", "tokens_per_sec_last")
        if k in summary
    }
    os.makedirs(os.path.dirname(BASELINE), exist_ok=True)
    with open(BASELINE, "w") as f:
        json.dump(
            {
                "published": published,
                "note": (
                    "6-step CPU smoke baseline for the tier-1 "
                    "report-compare self-gate (tests/test_smoke_gate.py); "
                    "tokens_per_sec is machine-relative and gated only "
                    "against collapse"
                ),
                "config": "tests/test_smoke_gate.py::smoke_config",
            },
            f, indent=1,
        )
    print(f"wrote {BASELINE}: {published}")
