"""Serving driver for a configuration whose caches are built in set-up
and measured while they decode: ``drivers/serve_ref.py``'s objects (the
engine behind its scheduler and HTTP server on loopback, tokens counted
where they are emitted, the same ``obs`` keys where a reader is shared)
for a stack of block-sparse and linear attention layers, with a window
that holds decode ticks alone. ``DRIVERS.serve_state.md`` says what it
adds and why it is a file of its own.

- the program's configuration is built from the file's published keys
  (``program_config``): the layers run are ``program.layers`` of the
  published ``mixer_types``, with their published indices;
- the cell's requests (one cycle of the mix: the same lengths for every
  seed, token ids from the seed) are sent over HTTP from threads of
  this process, all but one at once; the check requests are then served
  one after the other into the last free slot, each beside a full house
  of decoding streams; then the last request is sent;
- **the ramp is a condition**: the window opens once every slot decodes
  and no prefill chunk is pending. It is ``--seconds`` of decode ticks,
  its rate read from the scheduler's ``tokens_out`` at its two ends. A
  check (``decode_only_window``) holds that every gauge reading saw all
  slots decoding and none prefilling, and that neither a prefill chunk
  nor a request's end fell inside it: a window that saw either is not
  this cell;
- ``engine.attn_stats()`` is read at the window's and the trace's ends;
- ``correct`` by ``correctness_state.served_check`` against the
  reference the configuration names, over the engine's own logits (last
  prompt position and every decoded step) and chosen blocks for the
  check requests, after the window, once the engine has been dropped.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

from benchmark.drivers.serve_ref import ANNOTATIONS, GAUGE_EVERY_S

MIXERS = {"minicpm4": "sparse_attention", "lightning-attn": "linear_attention"}
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "vocab_size", "layer_types")


def program_config(conf: dict) -> dict:
    """The configuration as the program runs it, from the file's
    published keys (the file's ``changed`` and ``assumed`` say the same
    in words). What the program's one set of heads cannot express is
    checked, not assumed."""
    lo, hi = conf["program"]["layers"]
    if hi - lo != conf["num_hidden_layers"]:
        raise ValueError(f"program.layers {lo, hi} is not num_hidden_layers layers")
    n_pub = conf["published"]["num_hidden_layers"]
    same = (("lightning_nh", "num_attention_heads"), ("lightning_nkv", "num_attention_heads"),
            ("lightning_head_dim", "head_dim"))
    for a, b in same:
        if conf[a] != conf[b]:
            raise ValueError(f"{a} {conf[a]} differs from {b} {conf[b]}: the program has one")
    if (conf["lightning_scale"] != "1/sqrt(d)" or conf["mup_denominator"] != n_pub
            or len(conf["mixer_types"]) != n_pub or conf["attn_use_rope"]
            or not conf["lightning_use_rope"]):
        raise ValueError("the published keys are not the ones this driver was written for")
    sparse = conf["sparse_config"]
    return {
        **{k: conf[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "max_position_embeddings", "rms_norm_eps", "rope_theta", "initializer_range",
            "tie_word_embeddings", "qk_norm", "scale_emb", "scale_depth", "dim_model_base")},
        "dtype": conf["program"]["dtype"], "param_dtype": conf["program"]["param_dtype"],
        "layer_types": [MIXERS[m] for m in conf["mixer_types"][lo:hi]],
        "rope_layers": "linear",
        "attn_output_gate": conf["attn_use_output_gate"],
        "linear_output_gate": conf["use_output_gate"],
        "linear_output_norm": conf["use_output_norm"],
        "published_layers": n_pub, "first_layer_index": lo,
        "linear_decay_exponent": conf["lightning_decay_exponent"],
        **{f"sparse_{k}": v for k, v in sparse.items()},
    }


def bf16_cannot_hold(state):
    """Which entries of one slot's float32 state (the first slot's; a
    state stacked over periods: the first period's) have mantissa bits
    past a bf16's 8."""
    import jax.numpy as jnp
    import numpy as np

    s = np.asarray(state.reshape((-1,) + state.shape[-3:])[0])
    return s != np.asarray(jnp.asarray(s).astype(jnp.bfloat16).astype(jnp.float32))


def run(ctx) -> dict:
    import importlib

    import jax
    import numpy as np

    from benchmark import correctness_state, trace_reduce, traffic_gen as traffic
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.serve import InferenceEngine, Scheduler, ServeServer, http_post_json

    jax.config.update("jax_compilation_cache_include_metadata_in_key", ctx.trace)
    mix, conf, eng, check = ctx.traffic, ctx.config, ctx.cell["engine"], ctx.cell["check"]
    program = program_config(conf)
    cfg = LlamaConfig.from_dict(program)
    if not getattr(cfg, "state_layers", False):
        # a program from before PR 34 drops the keys it does not know
        raise SystemExit(f"{ctx.cell['config']}: this program's LlamaConfig has no "
                         "sparse_attention / linear_attention layers")
    reference = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    params = jax.jit(init_params, static_argnums=1)(ctx.key(), cfg)
    jax.block_until_ready(params)
    ctx.mark("weights")

    def held(at: str) -> None:
        st = jax.local_devices()[0].memory_stats() or {}
        ctx.log({"memory_at": at, "bytes_in_use": st.get("bytes_in_use"),
                 "peak_bytes_in_use": st.get("peak_bytes_in_use")})

    held("weights")
    slots = eng["slots"]
    engine = InferenceEngine(
        params, cfg, num_slots=slots, max_len=eng["max_len"], chunk_size=eng["chunk_size"],
        prefix_cache_tokens=eng["prefix_cache_tokens"], kv_block_size=eng["kv_block_size"],
        kv_dtype=eng["kv_dtype"], kv_pool_blocks=eng["kv_pool_blocks"])
    sched = Scheduler(engine, max_queue=eng["max_queue"])
    n_new = mix["output_tokens"]["max"]
    server = ServeServer(sched, None, port=0, host="127.0.0.1", default_max_new_tokens=n_new,
                         max_new_tokens_cap=n_new, request_timeout_s=3600.0).start()
    base = f"http://127.0.0.1:{server.port}"
    obs: dict = {"checks": [], "chips": 1,
                 "model": {**{k: program[k] for k in MODEL_KEYS}},
                 "weight_itemsize": params["embed"].dtype.itemsize,
                 "kv_itemsize": jax.tree.leaves(engine.pool)[0].dtype.itemsize}
    kv = engine.kv_stats()
    ctx.log({"kv_layout": engine.kv_layout, "kv_stats": {k: kv[k] for k in (
        "num_blocks", "block_size", "kv_bytes", "kv_bytes_by_kind", "layers_by_kind")}})
    held("caches")
    blocks = engine.block_pool

    def gauge() -> dict:
        st = sched.stats()
        return {"t": time.monotonic(), "tokens_out": st["tokens_out"],
                "decoding": st["slots_busy"] - st["slots_prefilling"],
                "prefilling": st["slots_prefilling"],
                "chunks_pending": st["prefill_chunks_pending"],
                "chunks_total": st["prefill_chunks_total"],
                "ended": st["served"] + st["expired"] + st["cancelled"] + st["errors"],
                "blocks_used": blocks.used_blocks}

    def all_decode(n: int) -> dict:
        """Wait until ``n`` slots decode and no chunk is pending."""
        while True:
            g = gauge()
            if g["decoding"] == n and not g["prefilling"] and not g["chunks_pending"]:
                return g
            if server._loop_error:
                raise RuntimeError(f"the serving loop died: {server._loop_error}")
            time.sleep(0.05)

    def ask(name: str, prompt: list, new_tokens: int):
        return http_post_json(f"{base}/v1/generate", {
            "token_ids": prompt, "max_new_tokens": new_tokens, "temperature": 0.0,
            "stop": False, "request_id": name}, timeout=3600)

    def delta(before: dict, after: dict) -> dict:
        return {kind: {k: v - before["by_program"][kind][k] for k, v in c.items()}
                for kind, c in after["by_program"].items()}

    # the engine draws a request's decode keys with one split of
    # max_new_tokens - 1: a small program for every output length, which
    # this puts into the process's cache before any request needs it
    for n in {n_new, check["new_tokens"]}:
        np.asarray(jax.random.key_data(jax.random.split(jax.random.key(0), n - 1)))
    requests = traffic.build_requests(mix, cfg.vocab_size, ctx.seed, traffic.clients(mix))
    if len(requests) != slots:
        raise ValueError(f"the mix sends {len(requests)} streams, the engine has {slots} slots")
    # the one held back while the check requests take the last slot: a shortest
    last = min(range(slots), key=lambda i: len(requests[i]["token_ids"]))
    rng = np.random.default_rng(ctx.seed)
    pool = ThreadPoolExecutor(slots + 1)
    streams = {}
    try:
        t_ramp = time.monotonic()
        dev_ramp = engine.devtime_stats()
        for i, r in enumerate(requests):
            if i != last:
                streams[i] = pool.submit(ask, f"stream-{i}", r["token_ids"], r["max_new_tokens"])
        all_decode(slots - 1)
        ctx.mark("all_but_one_decode")
        held("all_but_one_decode")
        # the check requests, each beside slots - 1 decoding streams: the
        # engine's own probes keep the last prompt position's logits, each
        # tick's logits and the blocks every query of the request chose
        engine.capture_prefill_logits = engine.capture_decode_logits = True
        engine.capture_routing = True
        prompts, answers, served, chosen, beside = [], [], [], [], []
        for j, n_prompt in enumerate(check["prompt_tokens"]):
            engine.routing_log.clear()
            engine.decode_logits_log.clear()
            p = rng.integers(0, cfg.vocab_size, n_prompt).tolist()
            status, out = ask(f"check-{j}", p, check["new_tokens"])
            if status != 200 or len(out.get("token_ids", ())) != check["new_tokens"]:
                raise RuntimeError(f"check request failed: {status} {out}")
            beside.append(sum(not f.done() for f in streams.values()))
            (slot,) = engine.decode_logits_log
            prompts.append(p)
            answers.append(out["token_ids"])
            served.append(np.concatenate([np.asarray(engine.last_prefill_logits),
                                          np.stack(engine.decode_logits_log[slot])]))
            chosen.append(np.concatenate(engine.routing_log[slot], axis=1))
        engine.capture_prefill_logits = engine.capture_decode_logits = False
        engine.capture_routing = False
        engine.routing_log.clear()
        engine.decode_logits_log.clear()
        obs["checks"].append({"check": "checked_beside_busy_slots", "others_decoding": beside,
                              "wanted": check["busy_slots_min"],
                              "ok": min(beside) >= check["busy_slots_min"]})
        ctx.mark("check_requests")
        streams[last] = pool.submit(ask, f"stream-{last}", requests[last]["token_ids"],
                                    requests[last]["max_new_tokens"])
        del requests
        first = all_decode(slots)
        dev0, compiles0, attn0 = (engine.devtime_stats(), engine.compile_counts(),
                                  engine.attn_stats())
        # the ramp's own figure: seconds in prefill chunks over the tokens they took
        chunk_s = sum(v - dev_ramp["device_seconds_by_program"].get(k, 0.0)
                      for k, v in dev0["device_seconds_by_program"].items()
                      if k.startswith("prefill_chunk:"))
        ctx.log({"ramp_s": first["t"] - t_ramp, "prefill_chunks": first["chunks_total"],
                 "prefill_ms_per_ktok": 1e3 * chunk_s / (
                     first["chunks_total"] * engine.chunk_size / 1e3),
                 "tokens_out_in_ramp": first["tokens_out"]})
        ctx.mark("ramp")
        held("ramp")

        obs["window_start_s"] = first["t"] - ctx.t_start
        gauges, t1 = [first], first["t"] + ctx.seconds
        while (left := t1 - time.monotonic()) > 0:
            time.sleep(min(GAUGE_EVERY_S, left))
            gauges.append(gauge())
        dev1, compiles1 = engine.devtime_stats(), engine.compile_counts()
        obs["attn"] = delta(attn0, engine.attn_stats())
        obs["window_s"] = gauges[-1]["t"] - first["t"]
        obs["tokens"] = gauges[-1]["tokens_out"] - first["tokens_out"]
        ctx.mark("window")
        if ctx.trace:
            # a few seconds of the same decode ticks, right after the window
            with ctx.profiler():
                a0 = engine.attn_stats()
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    time.sleep(float(mix["trace_s"]))
                traced = delta(a0, engine.attn_stats())
            obs["attn_traced"] = {k: sum(c[k] for c in traced.values())
                                  for k in next(iter(traced.values()))}
            obs["trace"] = ctx.reduce_trace(ANNOTATIONS)
            from benchmark import scope_times_state

            ctx.log({"device_seconds_by_scope": scope_times_state.of_run(obs),
                     "attn_traced": obs["attn_traced"]})
        after = gauge()
        held("window")
        # what the engine kept between ticks in one lightning layer's
        # state, read on the tick thread: float32 that a bf16 could not hold
        kept = sched.call_on_tick(lambda: float(np.mean(bf16_cannot_hold(next(
            e["s"] for e in engine.pool["lead"] + engine.pool["period"] if "s" in e)))))
        kept.wait(60)
        obs["checks"].append({"check": "state_holds_float32", "error": kept.error,
                              "share_of_entries_bf16_cannot_hold": kept.result,
                              "ok": kept.result is not None and kept.result > 0.5})
        # the streams have thousands of tokens to go: end them
        for i in streams:
            http_post_json(f"{base}/v1/cancel", {"request_id": f"stream-{i}"}, timeout=60)
        ended = [f.result(timeout=120) for f in streams.values()]
    finally:
        pool.shutdown(wait=False, cancel_futures=True)
        server.stop()

    inside = gauges[1:]
    obs["slots_decoding"] = [g["decoding"] for g in inside]
    obs["pool_used_share"] = [g["blocks_used"] / blocks.num_blocks for g in inside]
    obs["checks"].append({
        "check": "decode_only_window", "slots": slots,
        "decoding_min": min(obs["slots_decoding"]),
        "prefilling_max": max(g["prefilling"] for g in gauges),
        "prefill_chunks_in_window": gauges[-1]["chunks_total"] - first["chunks_total"],
        "requests_ended_in_window": gauges[-1]["ended"] - first["ended"],
        "decoding_after_trace": after["decoding"],
        "ok": (min(obs["slots_decoding"]) == slots == after["decoding"]
               and gauges[-1]["chunks_total"] == first["chunks_total"]
               and gauges[-1]["ended"] == first["ended"] == after["ended"])})
    # a stream that was cut answers with what it had: every one must have
    # outlived the window (cancelled, not finished or failed)
    cut = [out.get("finish_reason") for status, out in ended if status == 200]
    obs["attempted"] = slots
    obs["failed"] = slots - sum(r == "cancelled" for r in cut)
    obs["checks"].append({"check": "streams_outlived_the_window", "ended_as": sorted(set(
        str(r) for r in cut)), "answers": len(cut), "ok": obs["failed"] == 0})
    obs["devtime"] = {
        name: {k: v - dev0[key].get(k, 0) for k, v in dev1[key].items()
               if v - dev0[key].get(k, 0) > 0}
        for name, key in (("device_seconds", "device_seconds_by_program"),
                          ("dispatches", "dispatches_by_program"))}
    obs["checks"].append({"check": "nothing_compiled_in_window",
                          "before": compiles0, "after": compiles1,
                          "ok": compiles0 == compiles1})
    ctx.save({"gauges": gauges})
    ctx.log({"tokens_in_window": obs["tokens"], "window_s": obs["window_s"],
             "devtime": obs["devtime"], "attn": obs["attn"]})

    # the reference's side of the check, once the engine is gone: its
    # passes run a compiled program a layer over 16k and 41k rows at the
    # published widths and have no room beside the caches
    del engine.pool, engine, sched, server, blocks
    jax.clear_caches()
    ctx.mark("engine_dropped")
    obs["checks"].insert(1, correctness_state.served_check(
        params, cfg, prompts, answers, served, chosen, reference, consume=True))
    ctx.log(obs["checks"][1])
    ctx.mark("reference_check")
    held("reference_check")
    return obs
