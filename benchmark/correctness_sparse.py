"""The comparison that decides ``correct`` for a configuration whose
reference is ``reference/exaone_moe_ref.py``: a sparse model with window
and full attention layers, served as one chip's share.

PR 23's two-floor rule (``correctness.py``: LOGIT_FLOORS and its
reasons) over the engine's own prefill logits and decoded tokens, with
two things a top-8 of 128 forces. A choice of experts turns on rounding
as an argmax does, so the comparison is made in two parts and both are
printed: (a) every expert the program chose has, in the reference's
float32 pass, a selection score ``s + b`` within ``CHOICE_EPS`` of that
pass's k-th best (the choice is legitimate up to rounding), with the
share of choices that agree outright; (b) the logits are held to the
reference FOLLOWING the program's choices, so that one flipped near-tie
does not read as a fault of the arithmetic. The negative controls run
through the reference by the same rule, each seen to move.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.correctness import LOGIT_FLOORS
from benchmark.reference import exaone_moe_ref

# (a) CHOICE_EPS, in units of the selection score s + b (a sigmoid's
# value plus a bias of some hundredths). Two readings (PERF.md, PR 26):
# the program's largest shortfall over the builder's seeds, and the
# shortfall of choices made by a rule that is wrong (the selection bias
# left out), which must read over it.
CHOICE_EPS = 0.03
# The controls that decide ``correct``: each must read over LOGIT_FLOORS
# or the rule so read refuses nothing. Over 26 seeds on the chip
# (PERF.md, PR 26) another request's K and V read 74.7-101.6 floors, the
# window ignored 72.6-101.7, the pass with every matmul's inputs in an
# 8-bit float (the precision below the stated one) 59.0-77.7, and the
# gate normalised over the held experts alone 33.3-53.5 with one run of
# 7.4 (where the last prompt token chose no held expert in any layer,
# the fault reaches it through attention alone). RoPE on the one full
# layer is read and decides nothing: 2.21-3.03 (attention over hundreds
# of random keys averages to little, rotated or not), so near the limit
# that a fresh seed would refuse a good run in a hundred or so.
MUST_REFUSE = ("kv_of_another_request", "window_ignored", "gate_over_held_only",
               "reference_in_fp8")

_NAMES = {
    "attn_norm": "input_layernorm", "wq": "q_proj", "wk": "k_proj",
    "wv": "v_proj", "wo": "o_proj", "q_norm": "q_norm", "k_norm": "k_norm",
    "mlp_norm": "post_attention_layernorm", "router": "router",
    "router_bias": "router_bias", "shared_gate": "shared_gate",
    "shared_up": "shared_up", "shared_down": "shared_down",
}
_DENSE = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
_SPARSE = {"w_gate": "experts_gate", "w_up": "experts_up", "w_down": "experts_down"}


def reference_weights(params: dict, consume: bool = False) -> dict:
    """The program's mixed parameter tree (``lead_layers``: one dict a
    leading layer; ``layers``: one dict a layer of the period, stacked
    over the periods) in the reference's own layout: a renaming and an
    unstacking, both store [in, out]. A layer's slice of a stack is a copy;
    ``consume`` deletes each stacked array once its slices are made, for a
    caller that has no further use for the program's tree and no room for
    a second one (the cell's tree is 7.4 GB, all but 1.4 of it stacked)."""
    def renamed(layer):
        names = {**_NAMES, **(_SPARSE if "router" in layer else _DENSE)}
        return {names[k]: v for k, v in layer.items()}

    period = params["layers"]
    n = jax.tree.leaves(period)[0].shape[0] if period else 0
    unstacked = [[{} for _ in period] for _ in range(n)]      # [period][layer of it]
    for j, layer in enumerate(period):
        for k, v in layer.items():
            for i in range(n):
                unstacked[i][j][k] = v[i]
            if consume:
                jax.block_until_ready([u[j][k] for u in unstacked])
                v.delete()
    layers = [renamed(layer) for layer in params["lead_layers"]]
    layers += [renamed(layer) for one in unstacked for layer in one]
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": layers}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def hyper(cfg) -> dict:
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_key_value_heads or cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta, "sliding_window": cfg.sliding_window,
            "layer_types": list(cfg.layer_types or
                                ["full_attention"] * cfg.num_hidden_layers),
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "scoring_func": cfg.scoring_func, "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor}


def _faults() -> dict:
    """The negative controls, each a set of arguments of the reference's
    forward pass: another request's K and V (as in ``correctness.py``),
    one mechanism switched off at a time, and the whole pass with every
    matmul's inputs rounded to an 8-bit float's 4 exponent and 3 mantissa
    bits (the precision below the stated one). MUST_REFUSE says which
    decide ``correct``."""
    return {
        "kv_of_another_request": {"kv_fault": lambda x: jnp.roll(x, 1, axis=0)},
        "window_ignored": {"fault": "window_ignored"},
        "gate_over_held_only": {"fault": "gate_over_held_only"},
        "rope_on_full_layers": {"fault": "rope_on_full_layers"},
        "reference_in_fp8": {"inputs_in": (4, 3)},
    }


def served_check(params: dict, cfg, prompts: list, streams: list, prefill_logits: list,
                 routing: list, reference=exaone_moe_ref, consume: bool = False) -> dict:
    """The two-part comparison over each request's prefill logits, its
    first decoded tokens and the experts the program chose for it
    (``routing[r]``: [L_sparse, positions, k], the engine's own record
    for positions 0..prompt + tokens - 2), and the negative controls'
    prefill logits by the same rule. One right-padded batch of prompt +
    answer through ``reference`` (float32 and bf16 following the
    program's choices, and bf16 with each fault); causal attention, so
    the pads change nothing before them. Not to be called under a trace:
    the reference runs a compiled program a layer. ``consume``: the
    stacked arrays of ``params`` are deleted as they are unstacked
    (``reference_weights``)."""
    hp, held = hyper(cfg), tuple(cfg.held_experts)
    k = cfg.num_experts_per_tok
    n = min(len(s) for s in streams)
    width = -(-max(len(p) + n for p in prompts) // 64) * 64
    n_sparse = routing[0].shape[0]
    rows = np.zeros((len(prompts), width), np.int32)
    at = np.zeros((len(prompts), n), np.int32)
    choice = np.full((n_sparse, len(prompts), width, k), -1, np.int32)
    for r, (p, s, chosen) in enumerate(zip(prompts, streams, routing)):
        rows[r, : len(p) + n] = list(p) + list(s[:n])
        at[r] = len(p) - 1 + np.arange(n)
        seen = min(chosen.shape[1], len(p) + n - 1)
        choice[:, r, :seen] = chosen[:, :seen]
    weights = reference_weights(params, consume)
    pick = lambda x: jnp.take_along_axis(x, at[:, :, None], axis=1)

    def followed(dtype, **fault):
        # a compiled program a layer: one layer's weights at a time stand
        # cast beside the stored ones, so that the passes' peak of memory
        # stays under the serving engine's (PERF.md, PR 26)
        return reference.forward(weights, rows, hp, dtype, held=held, choice=choice,
                                 by_layer=True, **fault)

    logits, scores = followed(jnp.float32, with_scores=True)
    ref32 = pick(logits)
    del logits
    sel = jnp.stack(scores)                                      # [L, R, W, E]
    kth = jax.lax.top_k(sel, k)[0][..., -1]                      # [L, R, W]
    mine = jnp.take_along_axis(sel, jnp.maximum(choice, 0), axis=-1)
    short = jnp.where(choice >= 0, kth[..., None] - mine, 0.0)
    shortfall, agree = jnp.max(short), jnp.sum((short <= 0) & (choice >= 0))
    chosen_n = jnp.sum(choice >= 0)
    # the control: the shortfall of choices made WITHOUT the selection
    # bias (the k largest s alone), at the positions compared
    bias = jnp.stack([l["router_bias"] for l in weights["layers"] if "router" in l])
    plain = jax.lax.top_k(sel - bias[:, None, None, :], k)[1]
    wrong = kth[..., None] - jnp.take_along_axis(sel, plain, axis=-1)
    unbiased = jnp.max(jnp.where(choice[..., :1] >= 0, wrong, 0.0))
    b16 = pick(followed(jnp.bfloat16))
    ref32 = np.asarray(ref32)                                    # [R, n, V]
    floor = np.asarray(jnp.max(jnp.abs(ref32 - b16), axis=-1))   # [R, n]
    out = {"check": "served_logits_vs_reference", "floors_allowed": LOGIT_FLOORS,
           "floor_range": [float(floor.min()), float(floor.max())],
           "prefill_floors": 0.0, "prefill_max_abs_diff": 0.0,
           "decode_floors": 0.0, "decode_max_gap": 0.0, "exact_argmax": 0,
           "tokens": 0,
           "choice_eps": CHOICE_EPS, "choice_shortfall_max": float(shortfall),
           "choices_agree_share": float(agree) / max(1, int(chosen_n)),
           "choices": int(chosen_n),
           "choice_shortfall_if_bias_ignored": float(unbiased)}
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
    out["controls"] = {}
    for name, fault in _faults().items():
        x = pick(followed(jnp.bfloat16, **fault))[:, 0]
        out["controls"][name] = float(np.min(
            np.max(np.abs(np.asarray(x) - ref32[:, 0]), axis=-1) / worst))
    refused = all(out["controls"][name] > LOGIT_FLOORS for name in MUST_REFUSE)
    out["ok"] = bool(finite and refused and out["prefill_floors"] <= LOGIT_FLOORS
                     and out["decode_floors"] <= LOGIT_FLOORS
                     and out["choice_shortfall_max"] <= CHOICE_EPS)
    return out
