"""Training driver over a mesh: one DiLoCo worker a chip, whole fused
rounds for ``--seconds``.

``drivers/train.py`` is one worker on one chip; this is the same job
with ``cell["chips"]`` workers under ``MeshConfig(diloco=chips)``: each
worker takes the mix's microbatch at every inner step, the fused
``round_step`` ends in the pseudo-gradient all-reduce over the chips'
interconnect, and the staged rounds are made on the devices with the
sharding the program's own feeder would give them
(``dl.feed_round.sharding``). The timed structure, the warm-up round
that is also the correctness check's, and the outer step timed alone
after the window are ``train.py``'s.

Checks, all on the timed executable and before the window: every
worker's first losses against the plain float32 AdamW loop over the
reference (each worker is handed the same check microbatch, so each runs
the one-chip cell's check round); then, from the initial weights again
and on a staged round of DISTINCT data, on which the workers part, the
new snapshot against a plain outer step over the four workers' deltas
(``correctness_mesh.py``: the exchange left out reads far over its
limit) and every worker's weights, on its own chip, bit-equal to worker
0's and to the snapshot.

Observations are plain counts and spans under generic keys; which
metric reads which is said by the metric files and their readers.
"""

from __future__ import annotations

import time

ANNOTATIONS = ("stage", "round_step", "fetch_loss", "outer_step",
               "diloco.round", "diloco.outer")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import correctness, correctness_mesh, costs, trace_reduce
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

    # a cached executable hands back the scopes it was compiled with
    # (PERF.md, PR 24): a traced run keys the cache on the metadata too
    jax.config.update("jax_compilation_cache_include_metadata_in_key", ctx.trace)
    job, conf, workers = ctx.traffic, ctx.config, int(ctx.cell["chips"])
    seq, micro, accum = int(job["seq"]), int(job["microbatch"]), int(job["grad_accum"])
    inner, opt = int(conf["inner_steps"]), job["inner_optimizer"]
    model = LlamaConfig.from_dict({**conf, **conf["program"]})
    mesh = build_mesh(MeshConfig(diloco=workers), devices=jax.devices()[:workers])
    dl = Diloco(model, DilocoConfig(
        num_workers=workers, inner_steps=inner, grad_accum=accum, lr=opt["lr"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"]), mesh)
    k_init, k_check, k_data = jax.random.split(ctx.key(), 3)
    make_params = jax.jit(init_params, static_argnums=1)
    params = make_params(k_init, model)
    jax.block_until_ready(params)
    ctx.mark("weights")
    obs: dict = {"checks": [], "chips": workers, "inner_steps": inner,
                 "flops_per_token": costs.train_flops_per_token(model, seq),
                 "tokens_per_round": inner * workers * accum * micro * seq,
                 "wire_bytes_per_sync": dl.sync_wire_bytes()["wire_bytes_per_sync"]}

    # the reference's side of the check, on chip 0, while the chips hold
    # nothing but the weights (correctness.py says why one microbatch,
    # repeated)
    steps = int(job["check_steps"])
    check_tok = jax.random.randint(k_check, (micro, seq), 0, model.vocab_size, jnp.int32)
    reference = correctness.reference_losses(params, model, check_tok, opt, steps)
    ctx.mark("reference_losses")
    held = correctness_mesh.sampled(params)
    del params

    def fresh_state():
        """The initial state, from the seed's weights (made again: a copy
        kept on chip 0 would stand in the cell's peak of memory)."""
        state = dl.init_state(k_init, params=make_params(k_init, model))
        jax.block_until_ready(state)
        return state

    state = fresh_state()
    ctx.mark("init_state")

    # a ring of seeded rounds, made on the devices a worker a chip
    shape = (inner, workers, accum, micro, seq)
    placed = dl.feed_round.sharding
    make = jax.jit(lambda k: jax.random.randint(k, shape, 0, model.vocab_size, jnp.int32),
                   out_shardings=placed)
    with jax.profiler.TraceAnnotation("stage"):
        staged = [make(k) for k in jax.random.split(k_data, int(job["staged_rounds"]))]
        mask = jax.device_put(jnp.ones(shape, jnp.int32), placed)
        check_round = jax.device_put(jnp.broadcast_to(check_tok, shape), placed)
        jax.block_until_ready((staged, mask, check_round))
    ctx.mark("staged")

    if ctx.trace:
        state = dl.outer_step(state)  # compiled here, timed after the window
    compiled = dl._round_jit._cache_size
    # the check round: the timed executable, every worker on the check's
    # microbatch at every inner step
    state, loss, _ = dl.round_step(state, check_round, mask)
    loss = np.asarray(loss)
    for w in range(workers):
        program = [float(x) for x in loss[:steps, w]]
        obs["checks"].append({**correctness.train_round_check(program, reference),
                              "worker": w})
    ctx.log({"round_losses_vs_reference": obs["checks"][-workers:],
             "round_losses": loss.tolist()})
    del state, check_round
    ctx.mark("check_round")

    # the sync's check, from the initial weights again, on distinct data:
    # the inner steps alone give each worker's weights before the sync,
    # the fused round the snapshot after it; this round is also the one
    # that shows that a second call compiles nothing
    state, _, _ = dl.inner_round_step(fresh_state(), staged[0], mask)
    before = correctness_mesh.sampled(state.params)
    del state
    state = fresh_state()
    laid = [x.sharding for x in jax.tree.leaves(state)]
    state, loss, _ = dl.round_step(state, staged[0], mask)
    obs["checks"].append(correctness_mesh.sync_check(
        held, before, correctness_mesh.sampled(
            jax.tree.map(lambda x: x.addressable_shards[0].data, state.snapshot)),
        dl.cfg.outer_lr, dl.cfg.outer_momentum))
    same = jax.jit(lambda p, s: jnp.stack(
        [jnp.all(x == x[:1]) & jnp.all(x == y[None])
         for x, y in zip(jax.tree.leaves(p), jax.tree.leaves(s))]))
    equal = np.asarray(same(state.params, state.snapshot))
    parted = float(np.ptp(np.asarray(loss)[-1]))
    obs["checks"].append({"check": "workers_equal_after_sync_on_distinct_data",
                          "leaves": int(equal.size), "last_losses_apart": parted,
                          "ok": bool(equal.all() and parted > 0.0)})
    del held, before
    # a program that hands its state back laid otherwise than init_state
    # made it compiles the round again at the next call (the parent of PR
    # 26 does, and the benchmark's files are laid over the parent's
    # checkout to compare): that call is made here and not in the window.
    # Where the layouts agree this costs nothing
    if not all(a.is_equivalent_to(x.sharding, x.ndim)
               for a, x in zip(laid, jax.tree.leaves(state))):
        state, loss, _ = dl.round_step(state, staged[1 % len(staged)], mask)
        jax.block_until_ready(loss)
    ctx.log({"sync_checks": obs["checks"][-2:], "round_executables": compiled()})
    losses = []
    ctx.mark("sync_check")

    def one_round(state, i):
        with jax.profiler.TraceAnnotation("round_step"):
            state, loss, _ = dl.round_step(state, staged[i % len(staged)], mask)
        with jax.profiler.TraceAnnotation("fetch_loss"):
            jax.block_until_ready(loss)
        losses.append(loss)
        return state

    n = 0
    if ctx.trace:
        with ctx.profiler():
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                for _ in range(int(job["trace_rounds"])):
                    state = one_round(state, n)
                    n += 1
        obs["trace"] = ctx.reduce_trace(ANNOTATIONS)

    # the measured window: whole rounds until --seconds is over
    round_s = []
    executables = compiled()
    t0 = time.perf_counter()
    obs["window_start_s"] = time.monotonic() - ctx.t_start
    while True:
        t = time.perf_counter()
        state = one_round(state, n)
        n += 1
        now = time.perf_counter()
        round_s.append(now - t)
        if now - t0 >= ctx.seconds:
            break
    obs["window_s"] = now - t0
    ctx.mark("window")
    obs["round_s"] = round_s
    obs["tokens"] = len(round_s) * obs["tokens_per_round"]
    obs["checks"].append({"check": "nothing_compiled_in_window", "before": executables,
                          "after": compiled(), "ok": compiled() == executables})

    if ctx.trace:
        sync_s = []
        for _ in range(int(job["sync_repeats"])):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("outer_step"):
                state = dl.outer_step(state)
                jax.block_until_ready(state)
            sync_s.append(time.perf_counter() - t)
        obs["sync_s"] = sync_s

    finite = [bool(np.isfinite(np.asarray(l)).all()) for l in losses]
    obs["attempted"], obs["failed"] = len(finite), finite.count(False)
    obs["checks"].append({"check": "losses_finite", "rounds": len(finite),
                          "last": float(np.mean(np.asarray(losses[-1]))),
                          "ok": all(finite)})
    return obs
