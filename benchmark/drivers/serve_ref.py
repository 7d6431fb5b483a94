"""Serving driver for a configuration that names its reference:
``drivers/serve.py``'s procedure (the engine behind its scheduler and
HTTP server on loopback, load from a child process without JAX, tokens
counted where they are emitted, the same ``obs`` keys, so every existing
reader reads it) for a model that is no dense decoder.

What differs from ``serve.py``, and why it is a file of its own (that
one hard-wires ``LlamaConfig.from_dict(conf)``, ``init_params`` over the
whole vocabulary and ``reference/llama_ref.py``; folding the two is a
later ``benchmark`` issue, ROADMAP.md):

- the program's configuration is built from the file's share of a
  deployment (``program_config``): the router keeps its published
  width while ``num_experts`` in the file counts the experts HELD, the
  vocabulary is the slice ``vocab_held``, the layer kinds are the first
  ``num_hidden_layers`` of the published list;
- ``correct`` is decided by ``correctness_sparse.served_check`` against
  the reference module the configuration names (``"reference"``), over
  the engine's own prefill logits, decoded tokens and chosen experts
  (the engine's ``capture_routing`` probe) for the check requests, which
  are served in set-up, each beside a full house of decoding streams;
  the reference's passes run after the window, once the engine has been
  dropped, because the chip has no room for them beside it;
- the expert layer's counters (``engine.moe_stats()``) are read at the
  window's and the trace's two ends;
- a traced run keys the compile cache on metadata too (a cached
  executable hands back the scopes it was compiled with: PERF.md, PR
  24) and hands the program's own spans to the idle-gap reduction.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

GAUGE_EVERY_S = 0.5
# the program's own spans (obs/tracer.trace_span: serve/scheduler.py,
# serve/server.py, serve/engine.py), for the idle gaps' attribution
ANNOTATIONS = (
    "sched.tick", "sched.control", "sched.expire", "sched.admit", "sched.prefill",
    "sched.deliver", "sched.retire", "sched.idle", "engine.start_prefill",
    "engine.keys", "engine.stage_chunk", "engine.prefill_chunk", "engine.stage",
    "engine.decode_dispatch", "engine.fetch_tokens", "engine.advance")
MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "layer_types", "first_k_dense_replace",
    "num_experts", "num_experts_per_tok", "num_shared_experts", "sliding_window",
    "vocab_size")
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


def program_config(conf: dict) -> dict:
    """The configuration as the program runs it on this chip, from the
    file's share of its deployment (the file's ``changed`` says the
    same in words)."""
    n = int(conf["num_hidden_layers"])
    held = conf["program"]["experts_held"]
    if held[1] != conf["num_experts"]:
        raise ValueError(f"num_experts {conf['num_experts']} is not the experts held {held}")
    sparse = ["dense" if i < conf["first_k_dense_replace"] else "sparse" for i in range(n)]
    if conf["mlp_layer_types"][:n] != sparse:
        raise ValueError("mlp_layer_types is not first_k_dense_replace dense layers, then sparse")
    windows = [conf["sliding_window"] if k == "sliding_attention" else 0
               for k in conf["layer_types"][:n]]
    if conf["sliding_windows"][:n] != windows:
        raise ValueError("sliding_windows disagrees with layer_types and sliding_window")
    return {**conf, **conf["program"],
            "num_experts": conf["published"]["num_experts"],  # the router's width
            "vocab_size": conf["vocab_held"],
            "layer_types": conf["layer_types"][:n]}


def run(ctx) -> dict:
    import importlib

    import jax
    import numpy as np

    from benchmark import correctness_sparse, stats, trace_reduce, traffic_gen as traffic
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.serve import (
        InferenceEngine,
        Scheduler,
        ServeServer,
        http_post_json,
    )

    jax.config.update("jax_compilation_cache_include_metadata_in_key", ctx.trace)
    mix, conf, eng = ctx.traffic, ctx.config, ctx.cell["engine"]
    program = program_config(conf)
    cfg = LlamaConfig.from_dict(program)
    if not getattr(cfg, "mixed", False):
        # a program from before PR 26 drops the keys it does not know and
        # would build a dense decoder of 128 whole experts: fail at once
        raise SystemExit(f"{ctx.cell['config']}: this program's LlamaConfig has no mixed "
                         "layer stack (window and full layers, a held share of experts)")
    reference = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    params = jax.jit(init_params, static_argnums=1)(ctx.key(), cfg)
    jax.block_until_ready(params)
    ctx.mark("weights")

    def held(at: str) -> None:
        """Where the process's peak of held bytes comes from: a line a phase."""
        st = jax.local_devices()[0].memory_stats() or {}
        ctx.log({"memory_at": at, "bytes_in_use": st.get("bytes_in_use"),
                 "peak_bytes_in_use": st.get("peak_bytes_in_use")})

    held("weights")
    engine = InferenceEngine(
        params, cfg, num_slots=eng["slots"], max_len=eng["max_len"],
        chunk_size=eng["chunk_size"], prefix_cache_tokens=eng["prefix_cache_tokens"],
        kv_block_size=eng["kv_block_size"], kv_dtype=eng["kv_dtype"],
    )
    sched = Scheduler(engine, max_queue=eng["max_queue"])
    server = ServeServer(
        sched, None, port=0,
        host="127.0.0.1", default_max_new_tokens=mix["output_tokens"]["median"],
        max_new_tokens_cap=max(mix["output_tokens"]["max"],
                               ctx.cell["check"]["busy_new_tokens"]),
    ).start()
    url = f"http://127.0.0.1:{server.port}/v1/generate"
    leaf = params["embed"]
    pool = jax.tree.leaves(engine.pool)[0]
    obs: dict = {"checks": [], "chips": 1, "model": {k: program[k] for k in MODEL_KEYS},
                 "weight_itemsize": leaf.dtype.itemsize, "kv_itemsize": pool.dtype.itemsize}
    kv = engine.kv_stats()
    ctx.log({"kv_stats": {k: kv[k] for k in (
        "num_blocks", "block_size", "kv_bytes", "kv_bytes_by_kind", "layers_by_kind",
        "ring_rows_per_slot")}})
    child = None
    try:
        # warm-up and correctness. The check requests are served one
        # after the other into the last free slot while every other
        # slot decodes a request of the mix's own shapes: the rings are
        # addressed by slot number, and a row read from or written to
        # another slot shows only where that slot holds a stream
        rng = np.random.default_rng(ctx.seed)
        check = ctx.cell["check"]

        def ask(prompt: list, n_new: int):
            status, out = http_post_json(url, {
                "token_ids": prompt, "max_new_tokens": n_new,
                "temperature": 0.0, "stop": False}, timeout=900)
            if status != 200 or len(out.get("token_ids", ())) != n_new:
                raise RuntimeError(f"warm-up request failed: {status} {out}")
            return out["token_ids"]

        counted0, asked = sched.stats()["tokens_out"], 0
        shapes = traffic.cycle_shapes(mix)
        # every final-chunk width and the longest prompt first, then the cycle's
        lengths = _warm_lengths(shapes, engine.chunk_size, check["prompt_tokens"])
        lengths = (lengths + [p for p, _ in shapes])[:eng["slots"] - 1]
        drawn = [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]
        with ThreadPoolExecutor(len(lengths)) as others:
            busy = [others.submit(ask, p, check["busy_new_tokens"]) for p in drawn]
            asked += len(lengths) * check["busy_new_tokens"]
            # until every one of them decodes (or one has ended: the
            # count of those still decoding beside a check request decides)
            while not any(f.done() for f in busy):
                st = sched.stats()
                if st["slots_busy"] - st["slots_prefilling"] == len(lengths):
                    break
                time.sleep(0.02)
            ctx.mark("slots_busy")
            # the engine's own debug probes: a request's prefill logits
            # and the experts each slot's tokens chose
            engine.capture_prefill_logits = engine.capture_routing = True
            prompts, streams, served, routing, beside = [], [], [], [], []
            for n_prompt in check["prompt_tokens"]:
                engine.routing_log.clear()
                p = rng.integers(0, cfg.vocab_size, n_prompt).tolist()
                s = ask(p, check["new_tokens"])
                beside.append(sum(not f.done() for f in busy))
                prompts.append(p)
                streams.append(s)
                served.append(np.array(engine.last_prefill_logits[0]))
                # the one slot that prefilled since: whole chunks, then ticks
                (pieces,) = [v for v in list(engine.routing_log.values())
                             if v[0].shape[1] > 1]
                routing.append(np.concatenate(pieces, axis=1))
                asked += check["new_tokens"]
            engine.capture_prefill_logits = engine.capture_routing = False
            for f in busy:
                f.result()
        engine.routing_log.clear()
        obs["checks"].append({"check": "checked_beside_busy_slots", "others_decoding": beside,
                              "wanted": check["busy_slots_min"],
                              "ok": min(beside) >= check["busy_slots_min"]})
        ctx.mark("check_requests")
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
        held("warm_requests")

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
                    "blocks_used": blocks.used_blocks}

        def gauges_until(t_end: float) -> list[dict]:
            out = []
            while (left := t_end - time.monotonic()) > 0:
                time.sleep(min(GAUGE_EVERY_S, left))
                out.append(gauge())
            return out

        def moe_delta(before: dict, after: dict) -> dict:
            return {kind: {k: v - before["by_program"][kind][k] for k, v in c.items()}
                    for kind, c in after["by_program"].items()}

        ramp_gauges = gauges_until(t0)
        compiles0, dev0 = engine.compile_counts(), engine.devtime_stats()
        moe0 = engine.moe_stats()
        obs["window_start_s"] = time.monotonic() - ctx.t_start
        gauges = gauges_until(t1)
        compiles1, dev1 = engine.compile_counts(), engine.devtime_stats()
        obs["moe"] = moe_delta(moe0, engine.moe_stats())
        obs["window_s"] = gauges[-1]["t"] - ramp_gauges[-1]["t"]
        obs["tokens"] = gauges[-1]["tokens_out"] - ramp_gauges[-1]["tokens_out"]
        ctx.mark("window")
        if ctx.trace:
            # a few seconds of the same steady load, right after the
            # window, so that the profiler is in no counted request
            with ctx.profiler():
                moe0 = engine.moe_stats()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    time.sleep(float(mix["trace_s"]))
                traced = moe_delta(moe0, engine.moe_stats())
            obs["moe_traced"] = {k: sum(c[k] for c in traced.values())
                                 for k in next(iter(traced.values()))}
            obs["trace"] = ctx.reduce_trace(ANNOTATIONS)
            from benchmark import scope_times

            ctx.log({"device_seconds_by_scope": scope_times.of_run(obs),
                     "moe_traced": obs["moe_traced"]})
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

    # the reference's side of the check, AFTER the window and once the
    # engine is gone: beside the engine's cache and its programs' working
    # memory the chip has about 1 GB free. The passes themselves take a
    # compiled program a layer (0.3-0.4 GB of temporaries each), but the
    # reference's own layout of the weights is a copy of every stacked
    # layer: it fits only in place of the program's tree, which is
    # consumed (PERF.md, PR 26). It judges what the engine served before
    # the window: the logits, tokens and choices kept above
    held("window")
    num_blocks = blocks.num_blocks
    del engine.pool, engine, sched, server, blocks
    jax.clear_caches()
    ctx.mark("engine_dropped")
    obs["checks"].insert(1, correctness_sparse.served_check(
        params, cfg, prompts, streams, served, routing, reference, consume=True))
    ctx.log(obs["checks"][1])
    ctx.mark("reference_check")
    held("reference_check")

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
    obs["pool_used_share"] = [g["blocks_used"] / num_blocks for g in gauges]
    # K and V rows a decoding stream holds, averaged over its life and
    # over the mix's cycle: a request of p prompt and o output tokens is
    # read at p, p + 1, ... over its o ticks
    obs["kv_rows_per_stream"] = (sum(o * (p + o / 2.0) for p, o in shapes)
                                 / sum(o for _, o in shapes))
    # keys a prompt token attends to on a full layer, averaged over the
    # cycle's prompt tokens: token i of a prompt sees i + 1
    obs["prompt_context_rows"] = (sum(p * (p + 1) / 2.0 for p, _ in shapes)
                                  / sum(p for p, _ in shapes))
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
             "devtime": obs["devtime"], "moe": obs["moe"]})
    obs["checks"].append({"check": "nothing_compiled_in_window",
                          "before": compiles0, "after": compiles1,
                          "ok": compiles0 == compiles1})
    obs["checks"].append({"check": "requests_answered", "in_window": len(inside),
                          "ok": len(good) > 0 and len(good) == len(inside)})
    # a closed loop that used up its list would have offered less load
    obs["checks"].append({"check": "load_never_ran_dry", "made": made,
                          "sent": report["sent"], "ok": report["sent"] < made})
    return obs
