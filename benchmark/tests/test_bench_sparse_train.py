"""The sparse training cell's pieces on the CPU: the count of FLOPs and
bytes against the configuration's arithmetic, the new readers on
observations made by hand, the rule of ``correctness_sparse_train.py``
on losses made by hand, and the driver end to end over the toy cell
under ``rehearsal/`` (``run.py --rehearse``)."""

import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import correctness_sparse_train as check
from benchmark import costs_sparse, costs_sparse_train, scope_times_train
from benchmark.drivers import train_sparse
from benchmark.readers import (
    moe_expert_device_pct_train,
    moe_experts_mxu_pct_train,
    moe_max_over_mean_rows_train,
    moe_rows_per_expert_train,
)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _model() -> SimpleNamespace:
    with open(os.path.join(BENCH, "configs", "mellum2-12b-a2.5b.json")) as f:
        conf = train_sparse.program_config(json.load(f))
    return SimpleNamespace(**{k: conf[k] for k in train_sparse.MODEL_KEYS},
                           first_k_dense_replace=0, num_shared_experts=0)


def test_the_cells_flops_are_the_configurations_arithmetic():
    m = _model()
    assert (m.num_experts, m.vocab_size, len(m.layer_types)) == (64, 12288, 4)
    assert costs_sparse.layer_counts(m) == (0, 4, 1, 3)
    assert costs_sparse.expert_params(m) == 6_193_152
    assert costs_sparse.fixed_params(m) == 4 * (21_233_664 + 147_456) + 12_288 * 2_304
    # a row of a window layer sees min(i + 1, window) keys, of a full layer i + 1
    assert costs_sparse_train.mean_keys(8192, None) == 4096.5
    assert costs_sparse_train.mean_keys(8192, 1024) == pytest.approx(
        sum(min(i + 1, 1024) for i in range(8192)) / 8192)
    assert costs_sparse_train.mean_keys(512, 1024) == 256.5
    got = costs_sparse_train.flops_per_token(m, 8192, 1.0)
    weights = 6 * (4 * (21_233_664 + 147_456 + 6_193_152) + 12_288 * 2_304)
    attention = 12 * 4096 * (4096.5 + 3 * costs_sparse_train.mean_keys(8192, 1024))
    assert got == pytest.approx(weights + attention) and 1.1e9 < got < 1.25e9
    # the grouped products of 16,384 held rows a layer call, four passes
    assert costs_sparse_train.experts_flops(m, 16384) == 4 * 2 * 6_193_152 * 16384
    assert costs_sparse_train.experts_bytes(m, 16384, 1, 8, 2) == 4 * 2 * (
        8 * 6_193_152 + 16384 * (2 * 2304 + 3 * 896))


def test_the_new_readers_read_a_sparse_training_run_and_nothing_else(monkeypatch):
    m = _model()
    other = {"trace": {"busy_s": 1.0}, "moe": {"decode": {"moe_held_pairs": 5}},
             "moe_traced": {"moe_experts_hit": 50}, "device_kind": "TPU v5 lite"}
    readers = (moe_expert_device_pct_train, moe_experts_mxu_pct_train,
               moe_max_over_mean_rows_train, moe_rows_per_expert_train)
    assert all(r.read(other) is None and r.read({}) is None for r in readers)
    counted = {"moe_held_pairs": 4 * 16384 * 8, "moe_max_group_rows": 4 * 2304 * 8,
               "layer_calls": 32}
    run = {**other, "moe_train": counted, "moe_traced": counted, "experts_held": 8,
           "compute_itemsize": 2, "model": vars(m)}
    assert moe_rows_per_expert_train.read(run) == 2048.0
    assert moe_max_over_mean_rows_train.read(run) == 1.125
    monkeypatch.setattr(scope_times_train, "by_scope", lambda path: {
        "leaf_s": 4.0, "by_scope": {"moe_experts": 1.0, "moe_route": 0.2, "moe_aux": 0.05,
                                    "mlp": 0.3, "attention": 2.45}})
    monkeypatch.setattr(scope_times_train.tr, "find_xplane", lambda root: "a.xplane.pb")
    assert moe_expert_device_pct_train.read(run) == pytest.approx(100 * 1.25 / 4.0)
    flops = 4 * 2 * 6_193_152 * 4 * 16384 * 8
    assert moe_experts_mxu_pct_train.read(run) == pytest.approx(100 * flops / 197e12 / 1.0)
    assert moe_experts_mxu_pct_train.read(run) < 100


def test_the_scope_table_knows_the_two_new_scopes():
    of = scope_times_train.scope_of
    assert of("jit(f)/while/body/checkpoint/mlp/moe_aux/reduce_sum") == "moe_aux"
    assert of("jit(f)/attn_proj/rope/cos") == "rope"
    assert of("jit(f)/transpose(jvp(mlp))/moe_experts/gather") == "moe_experts"
    assert of("ragged-dot-none.7") == "moe_experts" and of("jit(f)/add") is None


def _passed(loss, rms, shortfall=1e-3):
    return {"loss": loss, "token_rms": rms, "choice_shortfall": shortfall,
            "choice_shortfall_of_neighbours": 0.05, "choices_agree": 0.98}


def _moment(reading):
    return {"all": reading / 4, "worst_leaf": reading, "worst_leaf_is": "['embed']"}


def test_the_rule_passes_a_good_round_and_refuses_by_one_limit_at_a_time():
    program, ref = [9.5001, 9.5001, 9.4501], [9.5, 9.5, 9.45]
    good = _passed(9.50012, 0.01)
    # a control is refused by the moment the timed round left, whatever
    # its followed pass reads
    passes = dict.fromkeys(check.MUST_REFUSE, good)
    moments = {None: _moment(check.MOMENT_TOL / 4),
               **{name: _moment(2 * check.MOMENT_TOL) for name in check.MUST_REFUSE}}
    got = check.train_round_check(program, 9.5001, ref, good, passes, moments)
    assert got["ok"] and all(got["controls_refused"].values()), got
    cases = {
        "a_loss_off": ([9.5001, 9.5001 + 2 * check.TRAIN_LOSS_TOL, 9.4501], 9.5001, ref, good),
        "the_probe_is_not_the_round": (program, 9.5001 + 2 * check.FOLLOW_TOL, ref, good),
        "the_followed_loss_off": (program, 9.5001, ref, _passed(9.5007, 0.01)),
        "the_tokens_off": (program, 9.5001, ref, _passed(9.50012, 2 * check.TOKEN_RMS_TOL)),
        "the_fall_off": ([9.5001, 9.5001, 9.4509], 9.5001, ref, good),
        "no_fall_to_check": ([9.5, 9.5, 9.49], 9.5, [9.5, 9.5, 9.49], _passed(9.5, 0.01)),
        "a_choice_the_reference_does_not_bear_out": (program, 9.5001, ref,
                                                     _passed(9.50012, 0.01, shortfall=0.03)),
        "not_finite": ([9.5001, math.nan, 9.4501], 9.5001, ref, good),
    }
    for name, args in cases.items():
        assert not check.train_round_check(*args, passes, moments)["ok"], name
    # the state the round left is not the reference loop's (left unchanged it reads 1)
    assert not check.train_round_check(
        program, 9.5001, ref, good, passes, {**moments, None: _moment(1.0)})["ok"]
    # a control the moment does not refuse, though its followed pass is far off
    unmoved = {**moments, "window_ignored": _moment(check.MOMENT_TOL / 2)}
    assert not check.train_round_check(
        program, 9.5001, ref, good, {**passes, "window_ignored": _passed(9.6, 0.9)}, unmoved)["ok"]
    # a control that is read and decides nothing
    also = ({**passes, "balance_left_out": good},
            {**moments, "balance_left_out": _moment(check.MOMENT_TOL / 4)})
    assert check.train_round_check(program, 9.5001, ref, good, *also)["ok"]


def test_the_moments_distance_reads_zero_one_and_the_worst_leaf():
    import numpy as np

    reference = {"a": np.ones(100, np.float32), "b": np.full(4, 0.01, np.float32)}
    assert check.moment_distance(reference, reference)["all"] == 0.0
    unchanged = check.moment_distance({k: np.zeros_like(v) for k, v in reference.items()}, reference)
    assert unchanged["all"] == pytest.approx(1.0) and unchanged["worst_leaf"] == pytest.approx(1.0)
    small_leaf_off = check.moment_distance({**reference, "b": -reference["b"]}, reference)
    assert small_leaf_off["all"] < 0.01 and small_leaf_off["worst_leaf"] == pytest.approx(2.0)
    assert small_leaf_off["worst_leaf_is"] == "b"


def test_the_driver_runs_the_toy_cell_end_to_end():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse", "--workload",
         "tiny.train.sparse", "--seed", "4000000007", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=os.path.dirname(BENCH),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0 and result["rehearsal"]
    assert set(result["metrics"]) == {"rehearsal.train_tokens_per_s_chip", "rehearsal.setup_s"}
    checks = {c["check"]: c for c in next(l for l in lines if "checks" in l)["checks"]}
    first = checks["round_losses_vs_reference"]
    assert first["max_abs_diff"] < 1e-4 and first["followed"]["choices_agree"] > 0.99
    assert first["followed"]["token_rms"] < 1e-4 and first["probe_diff"] < 1e-4
    assert first["moment"]["worst_leaf"] < 1e-4  # float32 against float32
    readings = {name: c["moment"]["worst_leaf"] for name, c in first["controls"].items()}
    assert all(readings[name] > 2 * check.MOMENT_TOL for name in check.MUST_REFUSE), readings
    assert readings["balance_left_out"] < check.MOMENT_TOL
    assert all(first["controls_refused"][name] for name in check.MUST_REFUSE), first
    assert checks["every_pair_counted"]["ok"] and checks["losses_finite"]["ok"]
    counted = next(l for l in lines if "moe_train" in l)
    assert 0.5 < counted["held_pairs_a_token_layer"] < 1.5  # 4 of 16 held, top-4
