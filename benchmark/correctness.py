"""The comparisons that decide ``correct``, against the plain reference.

Both run on the chip, outside the measured window, at the published
widths. The tolerances stand here beside their reasons.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import llama_ref

# Training: the losses the *timed executable* returns (the fused round:
# bf16 activations over float32 weights, chunked cross-entropy, every
# layer recomputed, backward, clipping, AdamW) against a plain float32
# AdamW loop over the reference, on one microbatch repeated at every
# inner step of the round, from the same initial weights. The batch is
# repeated because uniform random tokens teach nothing that carries to
# other random tokens: on fresh tokens every loss sits at ln(vocab)
# whatever the optimizer does, while a repeated batch is learned by
# heart and its loss falls step by step, by as much as the backward
# pass and the optimizer are right. The first loss checks the forward
# pass; the fall from the first to the last compared loss checks the
# backward pass and the update, and a fall of the reference's own that
# is not several tolerances deep would check nothing, so it is refused.
#
# TRAIN_LOSS_TOL, on each compared loss: a mean over 16,376 targets, in
# which per-logit bf16 rounding is of either sign and averages out. PR
# 23 read 0.00003-0.00008 between the program's and the reference's
# losses over seven seeds (PERF.md); 0.0004 is five times the largest.
# TRAIN_FALL_TOL, on the fall, as a share of the reference's: bf16
# gradients flip the sign of elements too small to matter; read
# 0.02-0.2%, and 1% is five times the largest. TRAIN_FALL_MIN: the
# reference's own fall read 0.047-0.049 at the cell's size.
TRAIN_LOSS_TOL = 0.0004
TRAIN_FALL_TOL = 0.01
TRAIN_FALL_MIN = 0.02

# Serving: chip_smoke.py's two-floor rule (PR 21, measured on the
# chip). Two bf16 programs that round at different points are neither
# the truth; the yardstick is the reference's float32 pass over the
# same bf16 weights, and the floor at a position is how far the
# reference's own plain bf16 pass sits from it (largest logit
# difference). Served prefill logits may sit at most LOGIT_FLOORS
# floors from the float32 pass, and a decoded token passes where the
# float32 pass puts it within as many floors of its best token. PR 21
# read the engine at 1.15-1.22 floors and a corrupted KV pool at 34.8.
# A request's floor is the largest over its checked positions: the
# largest of 32,768 differences at one position is itself a noisy
# reading (PR 23's first serving runs: 0.19-0.27 from position to
# position). That the rule so read still refuses is shown in every run
# by a negative control: the reference's own bf16 pass in which every
# request attends over another request's K and V (a wrong block-table
# row) must read over LOGIT_FLOORS by the same rule, or the run is not
# ``correct``. Two subtler faults are read beside it and decide nothing,
# because the rule's power against them is a reading, not a given (PR
# 23, PERF.md): one pair of a request's blocks swapped, and K and V
# rounded to an 8-bit float's 4 exponent and 3 mantissa bits.
LOGIT_FLOORS = 2.0
MUST_REFUSE = ("kv_of_another_request",)

_LAYER_NAMES = {
    "attn_norm": "input_layernorm", "wq": "q_proj", "wk": "k_proj",
    "wv": "v_proj", "wo": "o_proj", "mlp_norm": "post_attention_layernorm",
    "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj",
}


def reference_weights(params: dict) -> dict:
    """The program's parameter tree in the reference's own layout (a
    renaming: both store [in, out] and stack layers on a leading axis)."""
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": {_LAYER_NAMES[k]: v for k, v in params["layers"].items()}}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def hyper(cfg) -> dict:
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads or cfg.num_attention_heads,
            "rms_norm_eps": cfg.rms_norm_eps, "rope_theta": cfg.rope_theta}


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up from 0, then a cosine to 0 at ``total_steps``
    (``transformers.get_cosine_schedule_with_warmup``): the rate of the
    update that follows ``step`` completed ones."""
    warm, total = opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return opt["lr"] * step / max(1, warm)
    progress = (step - warm) / max(1, total - warm)
    return opt["lr"] * max(0.0, 0.5 * (1.0 + math.cos(math.pi * progress)))


def reference_losses(params: dict, cfg, tokens, opt: dict, steps: int) -> list[float]:
    """Plain AdamW in float32 over the reference on ``tokens`` [B, S],
    the same batch at every step: the losses before updates 0..steps-1.
    Gradients are clipped to a global norm, the decay is decoupled and
    on every parameter, the moments are bias-corrected (Loshchilov and
    Hutter 2019, as ``torch.optim.AdamW`` does it). One sequence at a
    time, every layer recomputed, so that it fits beside nothing but the
    weights."""
    hp = hyper(cfg)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

    def batch_loss(w, tokens):
        one = jax.checkpoint(lambda t: llama_ref.loss(w, t[None], hp, remat=True))
        return jnp.mean(jax.lax.map(one, tokens))

    def update(w, m, v, g, lr, t):
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["clip_norm"] / norm)
        g = jax.tree.map(lambda x: x * clip, g)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        w = jax.tree.map(
            lambda w, m, v: w - lr * ((m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps) + opt["weight_decay"] * w),
            w, m, v)
        return w, m, v

    value_and_grad = jax.jit(jax.value_and_grad(batch_loss))
    update = jax.jit(update, donate_argnums=(1, 2))
    w = reference_weights(params)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses: list[float] = []
    loss = g = None
    for k in range(steps):
        if k == steps - 1:
            del m, v, g
            losses.append(float(jax.jit(batch_loss)(w, tokens)))
            break
        # an update at rate 0 moved nothing: the same loss and gradient
        if k == 0 or learning_rate(opt, k - 1) != 0.0:
            loss, g = value_and_grad(w, tokens)
        losses.append(float(loss))
        w, m, v = update(w, m, v, g, jnp.float32(learning_rate(opt, k)),
                         jnp.float32(k + 1))
    return losses


def train_round_check(program: list[float], reference: list[float]) -> dict:
    """The timed executable's first losses on the repeated batch against
    the reference's: each loss, and the fall from first to last."""
    diffs = [abs(p - r) for p, r in zip(program, reference)]
    fall_p, fall_r = program[0] - program[-1], reference[0] - reference[-1]
    ok = (len(program) == len(reference) and all(map(math.isfinite, program))
          and max(diffs) <= TRAIN_LOSS_TOL and fall_r >= TRAIN_FALL_MIN
          and abs(fall_p - fall_r) <= TRAIN_FALL_TOL * fall_r)
    return {"check": "round_losses_vs_reference", "program": program,
            "reference_float32": reference, "max_abs_diff": max(diffs),
            "tolerance": TRAIN_LOSS_TOL, "fall_program": fall_p,
            "fall_reference": fall_r, "fall_tolerance": TRAIN_FALL_TOL,
            "fall_min": TRAIN_FALL_MIN, "ok": bool(ok)}


def _faults(block: int) -> dict:
    """The negative controls' faults, each a function of a K or V array
    [B, S, H, hd]."""
    def of_another_request(x):
        return jnp.roll(x, 1, axis=0)

    def two_blocks_swapped(x):
        b = block
        return jnp.concatenate(
            [x[:, :b], x[:, 2 * b:3 * b], x[:, b:2 * b], x[:, 3 * b:]], axis=1)

    def rounded_to_fp8(x):
        # not a cast there and back, which the compiler may drop
        return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)

    return {"kv_of_another_request": of_another_request,
            "kv_two_blocks_swapped": two_blocks_swapped,
            "kv_rounded_to_fp8": rounded_to_fp8}


def served_logits_check(params: dict, cfg, prompts: list, streams: list,
                        prefill_logits: list, block: int) -> dict:
    """The two-floor rule over each request's prefill logits and its
    first decoded tokens, and over the negative controls' prefill
    logits (``block``: the pool's block size, for the swapped pair).
    One right-padded batch of prompt + answer through the reference
    (float32, bf16, and bf16 with each fault); causal attention, so the
    pads change nothing before them."""
    hp = hyper(cfg)
    n = min(len(s) for s in streams)
    width = -(-max(len(p) + n for p in prompts) // 64) * 64
    rows = np.zeros((len(prompts), width), np.int32)
    at = np.zeros((len(prompts), n), np.int32)
    for r, (p, s) in enumerate(zip(prompts, streams)):
        rows[r, : len(p) + n] = list(p) + list(s[:n])
        at[r] = len(p) - 1 + np.arange(n)
    faults = _faults(block)

    def passes(w, rows, at):
        pick = lambda x: jnp.take_along_axis(x, at[:, :, None], axis=1)
        f32 = pick(llama_ref.forward(w, rows, hp, jnp.float32))
        b16 = pick(llama_ref.forward(w, rows, hp, jnp.bfloat16))
        faulty = {name: pick(llama_ref.forward(w, rows, hp, jnp.bfloat16,
                                               kv_fault=f))[:, 0]
                  for name, f in faults.items()}
        return f32, jnp.max(jnp.abs(f32 - b16), axis=-1), faulty

    ref32, floor, faulty = jax.jit(passes)(reference_weights(params), rows, at)
    ref32, floor = np.asarray(ref32), np.asarray(floor)  # [R, n, V], [R, n]
    out = {"check": "served_logits_vs_reference", "floors_allowed": LOGIT_FLOORS,
           "floor_range": [float(floor.min()), float(floor.max())],
           "prefill_floors": 0.0, "prefill_max_abs_diff": 0.0,
           "decode_floors": 0.0, "decode_max_gap": 0.0, "exact_argmax": 0,
           "tokens": 0}
    finite = bool(np.isfinite(ref32).all() and floor.min() > 0.0)
    for r, (s, served) in enumerate(zip(streams, prefill_logits)):
        served = np.asarray(served, np.float32).reshape(-1)
        finite = finite and served.shape == ref32[r, 0].shape and bool(
            np.isfinite(served).all())
        if not finite:
            break
        worst = float(floor[r].max())
        diff = float(np.max(np.abs(served - ref32[r, 0])))
        out["prefill_max_abs_diff"] = max(out["prefill_max_abs_diff"], diff)
        out["prefill_floors"] = max(out["prefill_floors"], diff / worst)
        for i, tok in enumerate(s[:n]):
            row = ref32[r, i]
            gap = float(row.max() - row[tok])
            out["decode_max_gap"] = max(out["decode_max_gap"], gap)
            out["decode_floors"] = max(out["decode_floors"], gap / worst)
            out["exact_argmax"] += int(row.argmax() == tok)
            out["tokens"] += 1
    # the controls, by the same rule: the least floors over the requests
    # is what the rule would have had to refuse
    worst = floor.max(axis=1)  # [R]
    out["controls"] = {
        name: float(np.min(np.max(np.abs(np.asarray(x) - ref32[:, 0]), axis=-1) / worst))
        for name, x in faulty.items()}
    refused = all(out["controls"][name] > LOGIT_FLOORS for name in MUST_REFUSE)
    out["ok"] = bool(finite and refused and out["prefill_floors"] <= LOGIT_FLOORS
                     and out["decode_floors"] <= LOGIT_FLOORS)
    return out
