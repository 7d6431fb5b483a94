"""The comparison that decides ``correct`` for a sparse model trained as
one chip's share, against ``reference/mellum_ref.py``.

``correctness.py``'s rule for training (the timed executable's first
losses on one microbatch repeated at every inner step, against a plain
float32 AdamW loop over the reference from the same weights: each loss,
and the fall from the first to the last; that file says why the batch is
repeated) with what a top-8 of 64 forces and what a mean hides.

A choice of experts turns on rounding as an argmax does, so the forward
pass is held to the reference FOLLOWING the program's choices (the
program's own probe, ``forward(with_choices=True)``: the timed
executable's code on the check's microbatch, which also hands out its
logits), and every expert the program chose has, in that float32 pass, a
probability within ``CHOICE_EPS`` of the pass's k-th best (the choice is
legitimate up to rounding; the chosen experts' neighbours, read by the
same rule, must fail it).

A mean over 16,382 tokens hides what moves single tokens either way: on
the chip a reference with YaRN left out, or with the weights formed over
the held experts alone, read within 0.0003 to 0.004 of the program's
mean loss from seed to seed (PERF.md section 6, PR 32), a few times the
program's own distance. So what decides is what the TIMED round hands
back beside its losses, its state: Adam's first moment after the
round's H inner steps (``state.inner_opt_state``: a decaying sum of the
H clipped gradients, linear in them where the parameters' own change
under Adam is their signs) against the moment the reference's loop
holds after the same H steps, as the norm of the difference over the
reference's norm, by the leaf that reads worst (``MOMENT_TOL``;
``moment_distance``). A state the round left unchanged reads 1; a fault
in one mechanism reads about 1 on the leaves it reaches, however little
it moves a mean.

The program's probe (``forward(with_choices=True)``: the timed
executable's model once forward, which hands out its logits and its
choices) is the second witness: held token by token to the reference
FOLLOWING its choices (the root mean square over the tokens of the
cross-entropies' difference, ``TOKEN_RMS_TOL``; the mean,
``FOLLOW_TOL``) and, by its mean, to the timed round's first loss
(``FOLLOW_TOL``: the probe and the round run one model). The fall is
held to the reference's own loop, which chooses for itself at every
step.

The negative controls run through the reference by the same rule: the
loop's moment with one mechanism switched off (``reference_loop``: to
first order, one more gradient a control from the one compiled
program), read against the timed round's as the sound loop's is. Each
of ``MUST_REFUSE`` has to come out refused by that number, or the rule
so read refuses nothing and the run is not ``correct``. Each control's
followed pass is read and printed beside it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.correctness import learning_rate
from benchmark.correctness_sparse import reference_weights  # noqa: F401  (the driver's)

# Readings on the chip at the cell's size (PERF.md section 6, PR 32, my
# chip runs), each limit between its two readings:
# MOMENT_TOL, by the worst leaf, over six seeds (call 8's one and call
# 9's five, the final tree): the program 0.033-0.053 (an expert's
# projection each time: 2% of the choices differ from the reference's
# own; 0.009-0.011 over all parameters); half of the batch left out
# 0.88-0.98, the weights over the held experts alone 1.01-1.09, YaRN
# left out 1.09-1.19, the window ignored 1.38-1.47, the reference's
# matmuls in an 8-bit float 2.94-3.16; the balance term left out
# 0.21-0.29 at a router (0.018-0.025 over all parameters): it decides
# nothing. The limit stands 3.8 times over the program's largest and 4.4
# under the least control.
# TOKEN_RMS_TOL, the root mean square over the tokens of the program's
# cross-entropy less the followed reference's: the program 0.0067-0.0070
# (0.0067-0.0071 with call 9's; bf16 over float32 weights); YaRN left out 0.166-0.182, the weights
# over the held experts alone 0.50-0.58, the reference's matmuls in an
# 8-bit float 0.76-0.79, the window ignored 0.88-0.95; the balance term
# left out 0.0069, as the sound pass (it is no part of a token's loss).
# FOLLOW_TOL, on a mean loss: the probe's against the followed
# reference's 0.00001-0.00013, against the timed first loss under
# 0.00004; the controls read 0.0003-0.06 from seed to seed, which is why
# no mean decides them.
# TRAIN_LOSS_TOL, on each compared loss against the reference's own
# loop, which chooses another expert at a near-tie (97.6-98.1% of the
# choices agree): 0.00003-0.00016. A sanity limit with no reading above
# it: the faults read from 0.0003 up, and the moment decides them.
# CHOICE_EPS, in units of the router's probability (1/64 on average,
# some 0.03 at the 8th best): the program's largest shortfall
# 0.0008-0.0014, the chosen experts' neighbours 0.047-0.054.
# TRAIN_FALL_TOL, on the fall as a share of the reference's: the program
# 0.03-0.67%; the 8-bit reference's own fall is 99% off, a state left
# unchanged 100%. TRAIN_FALL_MIN, the least fall of the reference's own
# that checks anything: it read 0.0367-0.0385.
MOMENT_TOL = 0.2
MOMENT_BY = "worst_leaf"
TOKEN_RMS_TOL = 0.035
FOLLOW_TOL = 0.0006
TRAIN_LOSS_TOL = 0.0008
CHOICE_EPS = 0.008
TRAIN_FALL_TOL = 0.02
TRAIN_FALL_MIN = 0.02
# The controls that decide ``correct``. "balance_left_out" is read and
# printed and decides nothing: its followed pass reads the coefficient
# times the term on the mean (0.001 x some 5.3 over four layers) and its
# moment 0.21-0.29 at a router, which a smaller coefficient would put under
# any limit.
MUST_REFUSE = ("window_ignored", "yarn_left_out", "gate_over_held_only",
               "reference_in_fp8", "half_the_batch_left_out")
# control -> the reference's fault (``mellum_ref.FAULTS``) that makes it
CONTROLS = {
    "window_ignored": "window_ignored",
    "yarn_left_out": "yarn_left_out",
    "gate_over_held_only": "gate_over_held_only",
    "reference_in_fp8": "matmul_inputs_in_fp8",
    "balance_left_out": "balance_left_out",
    "half_the_batch_left_out": "half_the_batch_left_out",
}


def hyper(cfg) -> dict:
    """The reference's hyper-parameters from the program's configuration."""
    kinds = list(cfg.layer_types or ["full_attention"] * cfg.num_hidden_layers)
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads or cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "sliding_window": cfg.sliding_window, "layer_types": kinds,
            "rope_parameters": {kind: cfg.rope_for(kind) for kind in set(kinds)},
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": cfg.norm_topk_prob,
            "router_aux_coef": cfg.router_aux_coef}


def clipped(g, opt):
    """``g`` after clipping to the recipe's global norm."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / norm)
    return jax.tree.map(lambda x: x * clip, g)


def adamw_update(w, m, v, g, lr, t, opt):
    """One step of plain AdamW over the clipped gradient ``g`` (the
    recipe of ``correctness.reference_losses``): decoupled decay on
    every parameter, bias-corrected moments."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    w = jax.tree.map(
        lambda w, m, v: w - lr * ((m / (1 - b1 ** t)) / (
            jnp.sqrt(v / (1 - b2 ** t)) + eps) + opt["weight_decay"] * w), w, m, v)
    return w, m, v


def on_host(tree) -> dict:
    """A tree's leaves on the host by their path: the chip holds the
    program's state and a round's temporaries by the time the round's
    state is compared."""
    return {jax.tree_util.keystr(path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def reference_loop(ref, w, hp, tokens, opt, steps: int, inner_steps: int, held,
                   faults: dict) -> tuple[list[float], dict]:
    """Plain float32 AdamW over ``ref`` (the reference module) on
    ``tokens`` [B, S], the same batch at every step, from the weights
    ``w`` (the reference's layout; left as they are), each pass choosing
    its own experts. Returns the losses before updates 0..steps-1 and
    what the timed round's state is held to, on the host (``on_host``):
    Adam's first moment after ``inner_steps`` updates, under ``None``
    for the sound loop and under each name of ``faults`` (control -> the
    reference's fault) as a loop with that fault reads it to first
    order: the sound moment moved by (1 - b1^H) times the faulted first
    clipped gradient less the sound one. (Every step's gradient is the
    first's but for what H - 1 updates at a warming rate moved, which
    the sound loop carries; a control's reading of tenths does not turn
    on the fault's own drift over those steps.) One compiled gradient
    serves the loop and every control, the fault a run-time flag."""

    @jax.jit
    def gradient(w, fault):
        value, g = jax.value_and_grad(
            lambda w: ref.loss(w, tokens, hp, held=held, remat=True, fault=fault))(w)
        return value, clipped(g, opt)

    update = jax.jit(lambda *a: adamw_update(*a, opt), donate_argnums=(1, 2))
    moments = {name: on_host(gradient(w, jnp.int32(1 + ref.FAULTS.index(fault)))[1])
               for name, fault in faults.items()}
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses: list[float] = []
    for k in range(inner_steps):
        # an update at rate 0 moved nothing: the same loss and gradient
        if k == 0 or learning_rate(opt, k - 1) != 0.0:
            value, g = gradient(w, jnp.int32(0))
        if k == 0:
            first = on_host(g)
        if k < steps:
            losses.append(float(value))
        w, m, v = update(w, m, v, g, jnp.float32(learning_rate(opt, k)), jnp.float32(k + 1))
    moments[None] = on_host(m)
    share = 1.0 - opt["b1"] ** inner_steps
    for name in faults:
        for leaf, g_fault in moments[name].items():
            moments[name][leaf] = moments[None][leaf] + share * (g_fault - first[leaf])
    return losses, moments


def moment_distance(program: dict, reference: dict) -> dict:
    """How far the first moment the timed round left in its state
    (``program``: ``on_host`` of it in the reference's layout) lies from
    a reference loop's: the norm of the difference over the reference's
    norm, over all parameters and for the leaf that reads worst. A state
    left unchanged (a moment of zeros) reads 1."""
    off = {leaf: float(np.linalg.norm(program[leaf] - r)) for leaf, r in reference.items()}
    size = {leaf: float(np.linalg.norm(r)) for leaf, r in reference.items()}
    worst = max(off, key=lambda leaf: off[leaf] / max(size[leaf], 1e-30))
    return {"all": math.sqrt(sum(x * x for x in off.values()))
            / max(math.sqrt(sum(x * x for x in size.values())), 1e-30),
            "worst_leaf": off[worst] / max(size[worst], 1e-30), "worst_leaf_is": worst}


def followed_pass(ref, hp, held):
    """``passed(w, tokens, choice, program_nll, fault=None) -> dict``:
    one float32 forward pass of the reference following ``choice``
    [L, B, S, k], with the mechanism ``fault`` names switched off (a
    run-time flag: ONE compiled program serves the clean pass and every
    control), read against the program's per-token cross-entropy
    ``program_nll`` [B, S - 1]: the pass's loss, the root mean square of
    the tokens' differences, and the choices against the pass's own
    probabilities (the largest shortfall from the k-th best, the share
    that agree outright, and the shortfall of the chosen experts'
    neighbours: the reading a wrong choice gives)."""
    k, e = hp["num_experts_per_tok"], hp["num_experts"]

    @jax.jit
    def run(w, tokens, choice, program_nll, fault):
        each, balance, probs = ref.token_losses(
            w, tokens, hp, held=held, choice=choice, fault=fault, with_probs=True)

        def shortfall(p, c):
            return jnp.max(jax.lax.top_k(p, k)[0][..., -1:] - jnp.take_along_axis(p, c, axis=-1))

        agree = [jnp.mean(jnp.sort(jax.lax.top_k(p, k)[1], axis=-1) == jnp.sort(c, axis=-1))
                 for p, c in zip(probs, choice)]
        return {"loss": jnp.mean(each) + hp["router_aux_coef"] * balance,
                "token_rms": jnp.sqrt(jnp.mean((each - program_nll) ** 2)),
                "choice_shortfall": jnp.max(jnp.stack(list(map(shortfall, probs, choice)))),
                "choice_shortfall_of_neighbours": jnp.max(jnp.stack(
                    [shortfall(p, (c + 1) % e) for p, c in zip(probs, choice)])),
                "choices_agree": jnp.mean(jnp.stack(agree))}

    def passed(w, tokens, choice, program_nll, fault=None) -> dict:
        index = jnp.int32(0 if fault is None else 1 + ref.FAULTS.index(fault))
        return {name: float(x) for name, x in run(w, tokens, choice, program_nll, index).items()}

    return passed


def train_round_check(program: list[float], probe_loss: float, reference: list[float],
                      followed: dict, controls: dict, moments: dict) -> dict:
    """The timed executable's first losses ``program`` on the repeated
    batch against the reference's loop (``reference_loop``) and the
    first moment its round left in the state against that loop's
    (``moments``: ``moment_distance`` under ``None`` for the sound loop
    and under each control's name for the faulted one); its probe's pass
    (``probe_loss``: the probe's mean cross-entropy plus the round's
    first balance term) against the timed first loss and against the
    followed reference (``followed_pass``, sound). A control is refused
    by what the timed round handed back, its moment; its followed pass
    is read beside it (``controls``) and decides nothing."""
    diffs = [abs(p - r) for p, r in zip(program, reference)]
    fall_p, fall_r = program[0] - program[-1], reference[0] - reference[-1]
    refused = {name: moments[name][MOMENT_BY] > MOMENT_TOL for name in controls}
    ok = (len(program) == len(reference) and all(map(math.isfinite, program))
          and max(diffs) <= TRAIN_LOSS_TOL and abs(probe_loss - program[0]) <= FOLLOW_TOL
          and moments[None][MOMENT_BY] <= MOMENT_TOL
          and abs(probe_loss - followed["loss"]) <= FOLLOW_TOL
          and followed["token_rms"] <= TOKEN_RMS_TOL
          and fall_r >= TRAIN_FALL_MIN and abs(fall_p - fall_r) <= TRAIN_FALL_TOL * fall_r
          and followed["choice_shortfall"] <= CHOICE_EPS
          and followed["choice_shortfall_of_neighbours"] > CHOICE_EPS
          and all(refused[name] for name in MUST_REFUSE))
    return {"check": "round_losses_vs_reference", "program": program,
            "reference_float32": reference, "max_abs_diff": max(diffs),
            "tolerance": TRAIN_LOSS_TOL, "probe_loss": probe_loss,
            "probe_diff": abs(probe_loss - program[0]),
            "moment": moments[None], "moment_tolerance": MOMENT_TOL, "moment_by": MOMENT_BY,
            "followed": followed, "followed_diff": abs(probe_loss - followed["loss"]),
            "followed_tolerance": FOLLOW_TOL, "token_rms_tolerance": TOKEN_RMS_TOL,
            "fall_program": fall_p, "fall_reference": fall_r,
            "fall_tolerance": TRAIN_FALL_TOL, "fall_min": TRAIN_FALL_MIN,
            "choice_eps": CHOICE_EPS,
            "controls": {name: {"moment": moments[name],
                                "followed_diff": abs(probe_loss - c["loss"]),
                                "token_rms": c["token_rms"]} for name, c in controls.items()},
            "controls_refused": refused, "must_refuse": list(MUST_REFUSE), "ok": bool(ok)}
