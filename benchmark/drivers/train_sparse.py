"""Training driver for a sparse model held as one chip's share: whole
fused DiLoCo rounds for ``--seconds``, with ``drivers/train.py``'s timed
structure and ``obs`` keys (so every reader of a training cell reads
it), for a configuration that names its reference.

What differs from ``train.py``, and why it is a file of its own (that
one hard-wires ``LlamaConfig.from_dict(conf)`` over the whole vocabulary,
``costs.train_flops_per_token`` and ``reference/llama_ref.py``; folding
the two is a later ``benchmark`` issue):

- the program's configuration is built from the file's share of a
  deployment (``program_config``): the router keeps its published width
  while ``num_experts`` in the file counts the experts HELD, the
  vocabulary is the slice ``vocab_held`` and the tokens are drawn from
  it, the layer kinds are the first ``num_hidden_layers`` of the
  published list;
- ``correct`` is decided by ``correctness_sparse_train.train_round_check``
  against the reference module the configuration names: the warm-up
  round's first losses and the first moment it leaves in its state
  against the reference's float32 AdamW loop, the negative controls by
  that moment, and the program's probe pass (its logits and its choices
  of experts) token by token against the reference following those
  choices;
- every round hands out what its expert layers did (``round_step``'s
  last output): the counters are summed over the window's rounds and
  over the traced ones, and ``flops_per_token`` takes the held pairs a
  token met from them (``costs_sparse_train.py``);
- a traced run keys the compile cache on metadata too (a cached
  executable hands back the scopes it was compiled with: PERF.md, PR 24).
"""

from __future__ import annotations

import dataclasses
import time

ANNOTATIONS = ("stage", "round_step", "fetch_loss", "outer_step")
MODEL_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "layer_types", "num_experts", "num_experts_per_tok",
    "sliding_window", "vocab_size")


def program_config(conf: dict) -> dict:
    """The configuration as the program runs it on this chip, from the
    file's share of its deployment (the file's ``changed`` says the same
    in words)."""
    n = int(conf["num_hidden_layers"])
    held = conf["program"]["experts_held"]
    if held[1] != conf["num_experts"]:
        raise ValueError(f"num_experts {conf['num_experts']} is not the experts held {held}")
    if set(conf["mlp_layer_types"][:n]) != {"sparse"}:
        raise ValueError("mlp_layer_types names a layer that is not sparse")
    return {**conf, **conf["program"],
            "num_experts": conf["published"]["num_experts"],  # the router's width
            "vocab_size": conf["vocab_held"],
            "layer_types": conf["layer_types"][:n]}


def run(ctx) -> dict:
    import importlib
    from types import SimpleNamespace

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from nanodiloco_tpu.models import LlamaConfig, init_params

    if "rope_parameters" not in {f.name for f in dataclasses.fields(LlamaConfig)}:
        # a program from before PR 32 drops the rotary sections it does
        # not know and would train another model: fail at once
        raise SystemExit(f"{ctx.cell['config']}: this program's LlamaConfig has no rotary "
                         "parameters by layer kind (rope_parameters)")
    from benchmark import correctness_sparse_train as check
    from benchmark import costs_sparse_train, trace_reduce
    from nanodiloco_tpu.models.llama import forward
    from nanodiloco_tpu.models.moe import TRAIN_COUNTERS
    from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh
    jax.config.update("jax_compilation_cache_include_metadata_in_key", ctx.trace)
    job, conf = ctx.traffic, ctx.config
    seq, micro, accum = int(job["seq"]), int(job["microbatch"]), int(job["grad_accum"])
    inner, opt = int(conf["inner_steps"]), job["inner_optimizer"]
    model = LlamaConfig.from_dict(program_config(conf))
    held = model.held_experts
    reference = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    # one worker on one chip: the worker axis and the exchange between
    # shares come with a cell that runs them on four (ROADMAP B5)
    mesh = build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1])
    dl = Diloco(model, DilocoConfig(
        num_workers=1, inner_steps=inner, grad_accum=accum, lr=opt["lr"],
        warmup_steps=opt["warmup_steps"], total_steps=opt["total_steps"],
        weight_decay=opt["weight_decay"], clip_norm=opt["clip_norm"]), mesh)
    k_init, k_check, k_data = jax.random.split(ctx.key(), 3)
    # weights from the seed as a jit *argument* (PERF.md, PR 23)
    params = jax.jit(init_params, static_argnums=1)(k_init, model)
    jax.block_until_ready(params)
    ctx.mark("weights")
    sparse_layers = sum(kind[1] for kind in map(model.layer_kind,
                                                 range(model.num_hidden_layers)))
    calls_per_round = inner * accum * sparse_layers
    obs: dict = {"checks": [], "chips": 1, "inner_steps": inner,
                 "tokens_per_round": inner * accum * micro * seq,
                 "experts_held": held[1],
                 "compute_itemsize": jnp.dtype(model.dtype).itemsize,
                 "model": {**{k: getattr(model, k) for k in MODEL_KEYS},
                           "first_k_dense_replace": 0, "num_shared_experts": 0}}

    # the reference's side of the correctness check, while the chip holds
    # nothing but the weights: the program's own choices of experts on
    # the check's microbatch (a forward pass, its probe), then plain
    # float32 AdamW over the reference, clean and with each control
    steps = int(job["check_steps"])
    check_tok = jax.random.randint(k_check, (micro, seq), 0, model.vocab_size, jnp.int32)
    def probe(p, t):  # the timed executable's model, once forward
        logits, chosen = forward(p, t, model, with_choices=True)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        return -jnp.take_along_axis(logp, t[:, 1:, None], axis=-1)[..., 0], chosen

    probe_nll, chosen = jax.jit(probe)(params, check_tok)
    w, hp = check.reference_weights(params), check.hyper(model)
    reference_losses, moments = check.reference_loop(
        reference, w, hp, check_tok, opt, steps, inner, held, check.CONTROLS)
    ctx.mark("reference_loop")
    passed = check.followed_pass(reference, hp, held)
    followed = passed(w, check_tok, chosen, probe_nll)
    controls = {name: passed(w, check_tok, chosen, probe_nll, fault)
                for name, fault in check.CONTROLS.items()}
    probe_ce = float(jnp.mean(probe_nll))
    del w, chosen, probe_nll
    ctx.mark("reference_controls")
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
    state, loss, _, stats = dl.round_step(state, jnp.broadcast_to(check_tok, shape), mask)
    program = [float(x) for x in np.asarray(loss)[:steps, 0]]
    probe_loss = probe_ce + model.router_aux_coef * float(stats["router_aux"][0, 0])
    # what the timed round left in its state: the one worker's first
    # moment after the round's H inner steps, against each loop's
    left = check.on_host(check.reference_weights(jax.tree.map(
        lambda x: x[0], optax.tree_utils.tree_get(state.inner_opt_state, "mu"))))
    distances = {name: check.moment_distance(left, m) for name, m in moments.items()}
    del left, moments
    obs["checks"].append(check.train_round_check(
        program, probe_loss, reference_losses, followed, controls, distances))
    ctx.log({**obs["checks"][-1], "round_losses": np.asarray(loss)[:, 0].tolist(),
             "round_router_aux": np.asarray(stats["router_aux"])[:, 0].tolist()})
    losses, counted = [], []
    ctx.mark("check_round")

    def one_round(state, i):
        with jax.profiler.TraceAnnotation("round_step"):
            state, loss, _, stats = dl.round_step(state, staged[i % len(staged)], mask)
        with jax.profiler.TraceAnnotation("fetch_loss"):
            jax.block_until_ready(loss)
        losses.append(loss)
        counted.append(stats["moe_counters"])
        return state

    def counters(rounds) -> dict:
        """The rounds' counters summed over inner steps and rounds, and
        the sparse-layer calls they are summed over."""
        total = np.sum([np.asarray(c, np.int64) for c in rounds], axis=(0, 1, 2))
        return {**dict(zip(TRAIN_COUNTERS, map(int, total))),
                "layer_calls": len(rounds) * calls_per_round}

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
        obs["moe_traced"] = counters(counted)
        del counted[:]

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
    obs["moe_train"] = counters(counted)
    pairs_a_token_layer = obs["moe_train"]["moe_held_pairs"] / (obs["tokens"] * sparse_layers)
    obs["flops_per_token"] = costs_sparse_train.flops_per_token(
        SimpleNamespace(**obs["model"]), seq, pairs_a_token_layer)
    ctx.log({"moe_train": obs["moe_train"], "moe_traced": obs.get("moe_traced"),
             "held_pairs_a_token_layer": pairs_a_token_layer,
             "flops_per_token": obs["flops_per_token"],
             "round_s": [round(x, 4) for x in round_s],
             "short_path_calls_by_round": [int(np.asarray(c)[..., 3].sum()) for c in counted]})

    if ctx.trace:
        sync_s = []
        for _ in range(int(job["sync_repeats"])):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("outer_step"):
                state = dl.outer_step(state)
                jax.block_until_ready(state)
            sync_s.append(time.perf_counter() - t)
        obs["sync_s"] = sync_s

    # every round of the window: every loss finite, no pair dropped
    # (every token's k pairs counted) and the short path's rows enough
    finite = [bool(np.isfinite(np.asarray(l)).all()) for l in losses]
    obs["attempted"], obs["failed"] = len(finite), finite.count(False)
    obs["checks"].append({"check": "losses_finite", "rounds": len(finite),
                          "last": float(np.mean(np.asarray(losses[-1]))),
                          "ok": all(finite)})
    want = len(round_s) * calls_per_round * micro * seq * model.num_experts_per_tok
    obs["checks"].append({"check": "every_pair_counted", "pairs": obs["moe_train"]["moe_pairs"],
                          "expected": want, "ok": obs["moe_train"]["moe_pairs"] == want})
    return obs
