"""Serving driver: the engine behind its scheduler and HTTP server on
loopback, in the one process that holds the chip, under load from a
child process that imports no JAX.

The objects are built as ``chip_smoke.py:widths_engine`` builds them
(which is how ``cli.py``'s ``serve`` does): bf16 weights made on the
device from the seed in one jitted call, ``InferenceEngine`` ->
``Scheduler`` -> ``ServeServer``. Warm-up sends, one at a time, the
check prompts and one prompt of every final-chunk width the mix holds,
so every program the window dispatches is compiled before it; the
engine's ``compile_counts()`` before and after the window prove it.

Tokens are counted where they are emitted: the scheduler's cumulative
``tokens_out`` counter, read at the window's two ends (and held, in
set-up, to the tokens the warm-up requests were answered with). A
request is ``attempted`` where it completes inside the window; the
latencies are those of the requests both sent and answered inside it,
so that none carries the burst of the start, when every caller sends
at once. Twice a second the driver also reads how many slots decode
and how many blocks of the pool are taken.

Observations are plain counts and spans under generic keys; which
metric reads which is said by the metric files and their readers.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

GAUGE_EVERY_S = 0.5
ANNOTATIONS = ()  # the engine's thread carries none yet (PERF.md, section 7)
CLIENT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "client_proc.py")


def _warm_lengths(shapes, chunk: int, have) -> list[int]:
    """The shortest prompt of each final-chunk width (length modulo the
    chunk size) the mix holds and ``have`` does not, and the longest."""
    by_rest: dict[int, int] = {}
    for p, _ in shapes:
        by_rest[p % chunk] = min(p, by_rest.get(p % chunk, p))
    for p in have:
        by_rest.pop(p % chunk, None)
    return sorted(by_rest.values()) + [max(p for p, _ in shapes)]


def run(ctx) -> dict:
    import jax
    import numpy as np

    from benchmark import correctness, stats, trace_reduce, traffic_gen as traffic
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    mix, conf, eng = ctx.traffic, ctx.config, ctx.cell["engine"]
    cfg = LlamaConfig.from_dict({**conf, **conf["program"]})
    params = jax.jit(init_params, static_argnums=1)(ctx.key(), cfg)
    jax.block_until_ready(params)
    ctx.mark("weights")
    engine = InferenceEngine(
        params, cfg, num_slots=eng["slots"], max_len=eng["max_len"],
        chunk_size=eng["chunk_size"], prefix_cache_tokens=eng["prefix_cache_tokens"],
        kv_block_size=eng["kv_block_size"], kv_dtype=eng["kv_dtype"],
    )
    sched = Scheduler(engine, max_queue=eng["max_queue"])
    server = ServeServer(
        sched, None, port=0,
        host="127.0.0.1", default_max_new_tokens=mix["output_tokens"]["median"],
        max_new_tokens_cap=mix["output_tokens"]["max"],
    ).start()
    url = f"http://127.0.0.1:{server.port}/v1/generate"
    leaf = jax.tree.leaves(params)[0]
    pool = jax.tree.leaves(engine.pool if engine.pool is not None else engine.cache)[0]
    obs: dict = {"checks": [], "chips": 1, "model": {
        k: conf[k] for k in ("hidden_size", "intermediate_size", "num_hidden_layers",
                             "num_attention_heads", "num_key_value_heads", "vocab_size")},
        "weight_itemsize": leaf.dtype.itemsize, "kv_itemsize": pool.dtype.itemsize}
    child = None
    try:
        # warm-up and correctness, one request at a time
        rng = np.random.default_rng(ctx.seed)
        check = ctx.cell["check"]

        def ask(n_prompt: int, n_new: int):
            prompt = rng.integers(0, cfg.vocab_size, n_prompt).tolist()
            status, out = http_post_json(url, {
                "token_ids": prompt, "max_new_tokens": n_new,
                "temperature": 0.0, "stop": False}, timeout=900)
            if status != 200 or len(out.get("token_ids", ())) != n_new:
                raise RuntimeError(f"warm-up request failed: {status} {out}")
            return prompt, out["token_ids"]

        counted0, asked = sched.stats()["tokens_out"], 0
        engine.capture_prefill_logits = True  # the engine's own debug probe
        prompts, streams, served = [], [], []
        for n_prompt in check["prompt_tokens"]:
            p, s = ask(n_prompt, check["new_tokens"])
            prompts.append(p)
            streams.append(s)
            served.append(np.array(engine.last_prefill_logits[0]))
            asked += check["new_tokens"]
        engine.capture_prefill_logits = False
        ctx.mark("check_requests")
        shapes = traffic.cycle_shapes(mix)
        for n_prompt in _warm_lengths(shapes, engine.chunk_size, check["prompt_tokens"]):
            ask(n_prompt, check["new_tokens"])
            asked += check["new_tokens"]
        # the counter the window's rate is read from counts what callers get
        counted = sched.stats()["tokens_out"] - counted0
        obs["checks"].append({"check": "token_counter_counts_answers",
                              "counted": counted, "answered": asked,
                              "ok": counted == asked})
        # the engine draws a request's decode keys with one split of
        # max_new_tokens - 1 (serve/engine.py:prefill_step): a small
        # program for every output length, which would compile inside
        # the window. The same call here, once for each length the mix
        # holds, puts them into this process's cache (PERF.md, PR 23)
        for n_new in sorted({o for _, o in shapes}):
            np.asarray(jax.random.key_data(jax.random.split(jax.random.key(0), n_new - 1)))
        ctx.mark("warm_requests")
        obs["checks"].append(correctness.served_logits_check(
            params, cfg, prompts, streams, served, engine.kv_block_size))
        ctx.log(obs["checks"][-1])
        ctx.mark("reference_check")

        # the load: made here from the seed, sent by a child without JAX
        count = traffic.request_budget(mix, ctx.seconds + mix["trace_s"],
                                       ctx.cell["expected_requests_per_s"])
        ramp = float(mix["ramp_s"])
        tail = float(mix["trace_s"]) + 1.0 if ctx.trace else 0.0
        plan = {
            "url": url, "timeout_s": 600.0,
            "requests": traffic.build_requests(mix, cfg.vocab_size, ctx.seed, count),
            "clients": traffic.clients(mix),
        }
        child = subprocess.Popen(
            [sys.executable, CLIENT],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        t0 = time.monotonic() + 1.0 + ramp
        t1 = t0 + ctx.seconds
        plan["start_at"], plan["end_at"] = t0 - ramp, t1 + tail
        child.stdin.write(json.dumps(plan))
        child.stdin.close()
        made = len(plan["requests"])
        del plan  # megabytes of token ids the window has no use for
        ctx.mark("load_planned")

        blocks = engine.block_pool

        def gauge() -> dict:
            st = sched.stats()
            return {"t": time.monotonic(), "tokens_out": st["tokens_out"],
                    "decoding": st["slots_busy"] - st["slots_prefilling"],
                    "prefilling": st["slots_prefilling"],
                    "blocks_used": blocks.used_blocks if blocks else None}

        def gauges_until(t_end: float) -> list[dict]:
            out = []
            while (left := t_end - time.monotonic()) > 0:
                time.sleep(min(GAUGE_EVERY_S, left))
                out.append(gauge())
            return out

        ramp_gauges = gauges_until(t0)
        compiles0, dev0 = engine.compile_counts(), engine.devtime_stats()
        obs["window_start_s"] = time.monotonic() - ctx.t_start
        gauges = gauges_until(t1)
        compiles1, dev1 = engine.compile_counts(), engine.devtime_stats()
        obs["window_s"] = gauges[-1]["t"] - ramp_gauges[-1]["t"]
        obs["tokens"] = gauges[-1]["tokens_out"] - ramp_gauges[-1]["tokens_out"]
        ctx.mark("window")
        if ctx.trace:
            # a few seconds of the same steady load, right after the
            # window, so that the profiler is in no counted request
            with ctx.profiler():
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    time.sleep(float(mix["trace_s"]))
            obs["trace"] = ctx.reduce_trace(ANNOTATIONS)
        out = child.stdout.read()
        child.wait(timeout=60)
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
        report = json.loads(out.strip().splitlines()[-1])
        records = report["records"]
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.stop()

    inside = [r for r in records if t0 <= r["t_end"] <= t1]
    answered = lambda r: (r["status"] == 200 and r["n_tokens"] == r["asked_tokens"]
                          and bool(r.get("timing")))
    good = [r for r in inside if answered(r)]
    obs["requests"] = [r for r in good if r["t_send"] >= t0]
    obs["attempted"], obs["failed"] = len(inside), len(inside) - len(good)
    obs["devtime"] = {
        name: {k: v - dev0[key].get(k, 0) for k, v in dev1[key].items()
               if v - dev0[key].get(k, 0) > 0}
        for name, key in (("device_seconds", "device_seconds_by_program"),
                          ("dispatches", "dispatches_by_program"))}
    obs["slots_decoding"] = [g["decoding"] for g in gauges]
    if blocks:
        obs["pool_used_share"] = [g["blocks_used"] / blocks.num_blocks for g in gauges]
    # K and V rows a decoding stream holds, averaged over its life and
    # over the mix's cycle: a request of p prompt and o output tokens is
    # read at p, p + 1, ... over its o ticks
    obs["kv_rows_per_stream"] = (sum(o * (p + o / 2.0) for p, o in shapes)
                                 / sum(o for _, o in shapes))
    ctx.save({"t0": t0, "t1": t1, "records": records,
              "gauges": ramp_gauges + gauges})
    ttft = [r["timing"]["ttft_s"] for r in obs["requests"]]
    ctx.log({"completed_in_window": len(inside), "ok": len(good),
             "sent_and_answered_in_window": len(obs["requests"]),
             "tokens_in_window": obs["tokens"],
             "prefilling_at_window_start": ramp_gauges[-1]["prefilling"],
             "ttft_s_p50_p95_max": [stats.pct(ttft, 0.5), stats.pct(ttft, 0.95),
                                    max(ttft, default=None)],
             "errors": sorted({str(r.get("error")) for r in inside
                               if not answered(r)})[:5],
             "devtime": obs["devtime"]})
    obs["checks"].append({"check": "nothing_compiled_in_window",
                          "before": compiles0, "after": compiles1,
                          "ok": compiles0 == compiles1})
    obs["checks"].append({"check": "requests_answered", "in_window": len(inside),
                          "ok": len(good) > 0 and len(good) == len(inside)})
    # a closed loop that used up its list would have offered less load
    obs["checks"].append({"check": "load_never_ran_dry", "made": made,
                          "sent": report["sent"], "ok": report["sent"] < made})
    return obs
