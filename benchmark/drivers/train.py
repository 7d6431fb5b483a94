"""Training driver: whole fused DiLoCo rounds for ``--seconds``.

The timed structure is ``bench.py:run_workload``'s, copied: batches are
staged on the device before the timed region, warm-up ends on the
executable that is timed, and the clock stops after
``block_until_ready``. What it does not copy is that function's sync
share by differencing, which holds a second copy of the training state:
impossible at a size that fills the chip. The outer step is timed alone
after the window instead (traced runs only).

The warm-up round is also the correctness check's: the timed executable
runs one seeded microbatch at every inner step, and its first losses
are held to a plain float32 AdamW loop over the reference
(``correctness.py``), which runs first, beside nothing but the weights.

Observations are plain counts and spans under generic keys; which
metric reads which is said by the metric files and their readers.
"""

from __future__ import annotations

import time

ANNOTATIONS = ("stage", "round_step", "fetch_loss", "outer_step")


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import correctness, costs, trace_reduce
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

    job, conf = ctx.traffic, ctx.config
    seq, micro, accum = int(job["seq"]), int(job["microbatch"]), int(job["grad_accum"])
    inner, opt = int(conf["inner_steps"]), job["inner_optimizer"]
    model = LlamaConfig.from_dict({**conf, **conf["program"]})
    # one worker on one chip: the worker axis and its collective come
    # with the cell that runs them on four (PERF.md, section 7)
    mesh = build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1])
    dl = Diloco(model, DilocoConfig(
        num_workers=1, inner_steps=inner, grad_accum=accum, lr=opt["lr"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"]), mesh)
    k_init, k_check, k_data = jax.random.split(ctx.key(), 3)
    # weights from the seed as a jit *argument*: Diloco.init_state(rng)
    # closes over its key, so every new seed would be a new program and
    # a compile of 25 s (PERF.md, PR 23)
    params = jax.jit(init_params, static_argnums=1)(k_init, model)
    jax.block_until_ready(params)
    ctx.mark("weights")
    obs: dict = {"checks": [], "chips": 1, "inner_steps": inner,
                 "flops_per_token": costs.train_flops_per_token(model, seq),
                 "tokens_per_round": inner * accum * micro * seq}

    # the reference's side of the correctness check, while the chip
    # holds nothing but the weights: plain float32 AdamW on one seeded
    # microbatch, repeated (correctness.py says why)
    steps = int(job["check_steps"])
    check_tok = jax.random.randint(k_check, (micro, seq), 0, model.vocab_size, jnp.int32)
    reference = correctness.reference_losses(params, model, check_tok, opt, steps)
    ctx.mark("reference_losses")
    state = dl.init_state(k_init, params=params)
    del params
    jax.block_until_ready(state)
    ctx.mark("init_state")

    # stage a ring of seeded rounds on the device; one mask of ones
    # serves every round
    shape = (inner, 1, accum, micro, seq)
    make = jax.jit(lambda k: jax.random.randint(k, shape, 0, model.vocab_size, jnp.int32))
    with jax.profiler.TraceAnnotation("stage"):
        staged = [make(k) for k in jax.random.split(k_data, int(job["staged_rounds"]))]
        mask = jnp.ones(shape, jnp.int32)
        jax.block_until_ready((staged, mask))
    ctx.mark("staged")

    # the warm-up round is the program's side of the check: the timed
    # executable on the check's microbatch at every inner step. Warm-up
    # so ends on the executable that is timed
    if ctx.trace:
        state = dl.outer_step(state)  # compiled here, timed after the window
    state, loss, _ = dl.round_step(state, jnp.broadcast_to(check_tok, shape), mask)
    program = [float(x) for x in np.asarray(loss)[:steps, 0]]
    obs["checks"].append(correctness.train_round_check(program, reference))
    ctx.log({**obs["checks"][-1], "round_losses": np.asarray(loss)[:, 0].tolist()})
    losses = []
    ctx.mark("check_round")

    def one_round(state, i):
        with jax.profiler.TraceAnnotation("round_step"):
            state, loss, _ = dl.round_step(state, staged[i % len(staged)], mask)
        with jax.profiler.TraceAnnotation("fetch_loss"):
            jax.block_until_ready(loss)
        losses.append(loss)
        return state

    n = 0
    if ctx.trace:
        # a traced span of whole rounds ahead of the timed window, so
        # that starting and stopping the profiler is in no round's time
        with ctx.profiler():
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                for _ in range(int(job["trace_rounds"])):
                    state = one_round(state, n)
                    n += 1
        obs["trace"] = ctx.reduce_trace(ANNOTATIONS)

    # the measured window: whole rounds until --seconds is over
    round_s = []
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

    if ctx.trace:
        sync_s = []
        for _ in range(int(job["sync_repeats"])):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("outer_step"):
                state = dl.outer_step(state)
                jax.block_until_ready(state)
            sync_s.append(time.perf_counter() - t)
        obs["sync_s"] = sync_s

    # every round of the window: every loss finite
    finite = [bool(np.isfinite(np.asarray(l)).all()) for l in losses]
    obs["attempted"], obs["failed"] = len(finite), finite.count(False)
    obs["checks"].append({"check": "losses_finite", "rounds": len(finite),
                          "last": float(np.mean(np.asarray(losses[-1]))),
                          "ok": all(finite)})
    return obs
