"""The data, costs and readers PR 34 added for a stack of sparse and
linear attention layers: the configuration file against its catalog
row, the traffic mix's fixed lengths, bytes and FLOPs against counts
made by hand, and the readers over a made-up ``obs``."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import costs_state, traffic_gen
from benchmark.drivers import serve_state
from benchmark.readers import (
    decode_hbm_pct_state,
    serve_mfu_pct_state,
    sparse_device_pct,
    sparse_hbm_pct,
    sparse_rows_share,
    state_device_pct,
    state_hbm_pct,
)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_key():
    conf = load("configs", "minicpm-sala.json")
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
        assert conf["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if conf.get(k) != v}
        assert differ == set(conf["reduced"]) == {"num_hidden_layers"}
    lo, hi = conf["program"]["layers"]
    assert conf["mixer_types"][lo:hi] == (
        ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"] * 2 + ["lightning-attn"] * 3)
    assert {"sparse_config", "lightning decay", "dense_len per query"} <= set(conf["assumed"])


def test_the_program_runs_the_published_widths():
    from nanodiloco_tpu.models import LlamaConfig

    cfg = LlamaConfig.from_dict(serve_state.program_config(load("configs", "minicpm-sala.json")))
    assert cfg.num_params() == 3_930_008_576
    assert (cfg.first_layer_index, cfg.published_layers) == (9, 32)
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5) and cfg.head_divisor == 16
    assert cfg.layer_types.count("sparse_attention") == 3
    assert (cfg.sparse_block_size, cfg.sparse_topk, cfg.sparse_dense_len) == (64, 64, 8192)


def test_program_config_refuses_heads_the_program_has_not():
    conf = load("configs", "minicpm-sala.json")
    with pytest.raises(ValueError, match="lightning_nkv"):
        serve_state.program_config({**conf, "lightning_nkv": 8})


def test_the_mix_is_the_same_32_lengths_for_every_seed():
    mix = load("traffic", "serve.longdecode32.json")
    shapes = traffic_gen.cycle_shapes(mix)
    prompts = [p for p, _ in shapes]
    assert len(shapes) == 32 == traffic_gen.clients(mix)
    assert prompts[:3] == [16384] * 3 and prompts[-3:] == [65536] * 3
    assert sum(prompts) == 1_148_416 and all(p % 512 == 0 for p in prompts)
    assert {o for _, o in shapes} == {8192}
    cell = load("workloads", "minicpm-sala.serve.longdecode32.json")
    need = sum(-(-(p + o) // 16) for p, o in shapes)
    assert need == 88_160 <= cell["engine"]["kv_pool_blocks"]
    assert max(p + o for p, o in shapes) <= cell["engine"]["max_len"]
    a = traffic_gen.build_requests(mix, 100, 1, 32)
    b = traffic_gen.build_requests(mix, 100, 2, 32)
    assert sorted(len(r["token_ids"]) for r in a) == sorted(len(r["token_ids"]) for r in b)


M = SimpleNamespace(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, vocab_size=128,
                    layer_types=["sparse_attention", "linear_attention", "linear_attention",
                                 "sparse_attention"])


def test_costs_by_hand_at_the_tiny_size():
    wide, narrow = 64 * 4 * 16, 64 * 2 * 16
    sparse, linear, mlp = 3 * wide + 2 * narrow, 5 * wide, 3 * 64 * 128
    assert costs_state.attention_params(M, "sparse_attention") == sparse
    assert costs_state.attention_params(M, "linear_attention") == linear
    fixed = 2 * sparse + 2 * linear + 4 * mlp + 64 * 128
    assert costs_state.fixed_params(M) == fixed
    assert costs_state.kv_row_bytes(M, 2) == 2 * 2 * 16 * 2
    assert costs_state.compressed_row_bytes(M, 2) == 2 * 16 * 2
    assert costs_state.state_bytes(M) == 4 * 16 * 16 * 4
    assert costs_state.decode_tick_bytes(M, 100, 10, 8, 2, 2) == (
        fixed * 2 + 100 * 128 + 10 * 64 + 8 * 2 * 4096)
    assert costs_state.flops(M, 3, 100, 10, 8) == (
        2 * fixed * 3 + 4 * 64 * 100 + 2 * 64 * 10 + 4 * 64 * 16 * 8)


def test_costs_at_the_published_widths():
    conf = load("configs", "minicpm-sala.json")
    m = SimpleNamespace(**{k: conf[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "vocab_size")}, layer_types=serve_state.program_config(conf)["layer_types"])
    assert costs_state.kv_row_bytes(m, 2) == 1024
    assert costs_state.compressed_row_bytes(m, 2) == 512
    assert costs_state.state_bytes(m) == 2_097_152
    # every weight but the embedding's rows and the norm scales (12 layers
    # x (2 x 4096 + 2 x 128), 9 output norms of 4096, the final norm)
    assert costs_state.fixed_params(m) == 3_930_008_576 - 73448 * 4096 - (
        12 * 8448 + 9 * 4096 + 4096)


def obs_of(**more):
    return {"model": vars(M), "weight_itemsize": 2, "kv_itemsize": 2,
            "device_kind": "TPU v5 lite", **more}


def test_readers_read_nothing_from_a_program_without_the_layers():
    for reader in (sparse_device_pct, sparse_hbm_pct, sparse_rows_share, state_device_pct,
                   state_hbm_pct, decode_hbm_pct_state, serve_mfu_pct_state):
        assert reader.read({"model": {}, "moe": {"decode": {}}}) is None
        assert reader.read({}) is None


def test_readers_over_counters():
    ticks = {"sparse_rows_read": 1000, "sparse_rows_held": 4000, "sparse_compressed_rows": 100,
             "sparse_queries": 20, "state_updates": 40}
    obs = obs_of(attn={"decode": ticks, "prefill_chunk": dict.fromkeys(ticks, 0)},
                 tokens=10, window_s=2.0,
                 devtime={"device_seconds": {"decode:1:x": 0.5}, "dispatches": {"decode:1:x": 10}})
    assert sparse_rows_share.read(obs) == 0.25
    need = costs_state.decode_tick_bytes(M, 100, 10, 4, 2, 2)
    assert decode_hbm_pct_state.read(obs) == pytest.approx(100 * need / 819e9 / 0.05)
    flops = costs_state.flops(M, 10, 1000, 100, 40)
    assert serve_mfu_pct_state.read(obs) == pytest.approx(100 * flops / 2.0 / 197e12)


def test_trace_readers_over_scope_seconds(monkeypatch):
    from benchmark import scope_times_state

    got = {"leaf_s": 2.0, "by_scope": {"sparse_select": 0.1, "sparse_attend": 0.3,
                                       "kv_compress": 0.1, "linear_state": 0.4, "mlp": 1.1}}
    monkeypatch.setattr(scope_times_state, "of_run", lambda obs: got)
    obs = obs_of(attn_traced={"sparse_rows_read": 10 ** 6, "sparse_compressed_rows": 10 ** 5,
                              "state_updates": 1000})
    assert sparse_device_pct.read(obs) == pytest.approx(25.0)
    assert state_device_pct.read(obs) == pytest.approx(20.0)
    assert sparse_hbm_pct.read(obs) == pytest.approx(
        100 * (10 ** 6 * 128 + 10 ** 5 * 64) / 819e9 / 0.4)
    assert state_hbm_pct.read(obs) == pytest.approx(100 * 2 * 1000 * 4096 / 819e9 / 0.4)
    monkeypatch.setattr(scope_times_state, "of_run", lambda obs: {
        "leaf_s": 1.0, "by_scope": {"attention": 0.5}})
    assert sparse_device_pct.read(obs) is None and state_hbm_pct.read(obs) is None


def test_scope_of_takes_the_innermost_name():
    from benchmark import scope_times_state

    name = "jit(run)/jit(main)/while/body/attention/sparse_select/dot_general"
    assert scope_times_state.scope_of(name) == "sparse_select"
    assert scope_times_state.scope_of("jit(run)/attention/linear_state/mul") == "linear_state"
    assert scope_times_state.scope_of("jit(run)/attention/dot_general") == "attention"


def test_the_rehearsal_runs_the_driver_end_to_end():
    """``run.py --rehearse`` over ``tiny.serve.state``: the decode-only
    window, the counters' metric and the check against the reference."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse", "--workload",
         "tiny.serve.state", "--seed", "3000000001", "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=os.path.dirname(BENCH))
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    result = lines[-1]
    checks = {c["check"]: c for c in next(x["checks"] for x in lines if "checks" in x)}
    assert result["correct"] and result["rehearsal"] and result["failed"] == 0
    window = checks["decode_only_window"]
    assert window["decoding_min"] == 4 and window["prefill_chunks_in_window"] == 0
    served = checks["served_logits_vs_reference"]
    assert max(served["floors"]) < 0.01 and served["choices_agree_share"] == 1.0
    assert all(served["controls"][name] > 2.0 for name in served["must_refuse"])
    assert "state_in_bf16" in served["controls"] and len(served["must_refuse"]) == 4
    assert checks["state_holds_float32"]["share_of_entries_bf16_cannot_hold"] > 0.9
    assert 0 < result["metrics"]["rehearsal.attn.sparse_rows_share"]["value"] < 1
    assert result["metrics"]["rehearsal.engine.slots_decoding"]["value"] == 4
