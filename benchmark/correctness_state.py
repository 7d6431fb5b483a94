"""The comparison that decides ``correct`` for a configuration whose
reference is ``reference/minicpm_sala_ref.py``: block-sparse attention
layers beside linear-attention layers, served through three kinds of
cache.

Logits against logits (with random weights the largest logit turns on
rounding, so no token is compared): the engine's own logits at the last
prompt position and at every decoded step, against the reference's
float32 pass over the same bf16 weights FOLLOWING the blocks the program
chose, by PR 23's rule (``correctness.py``: LOGIT_FLOORS and its
reasons): the floor at a request is how far the reference's own plain
bf16 pass sits from its float32 pass, and the served logits may sit at
most LOGIT_FLOORS floors away. A choice of 64 blocks turns on rounding
as an argmax does, so the choice is judged apart, and both parts are
printed: the share of (query, KV group) pairs past the dense length
whose chosen set is the float32 pass's own, and for the others how far
the least block the program took lies under that pass's 64th best score
(``CHOICE_EPS``: a bf16 near-tie may flip, a wrong rule may not). The
negative controls run through the reference by the same rule, on the
first (shortest) check request alone, each printed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.correctness import LOGIT_FLOORS

# CHOICE_EPS, in units of a block's score (a sum over a KV group's 16
# heads of softmax probabilities over the compressed keys, so at most 16
# and, spread over some 2,300 compressed keys, some hundredths for a
# block that matters). Two readings (PERF.md, PR 34): the program's
# largest shortfall over the builder's seeds on the chip, and the
# shortfall of a choice that is wrong by one block (every chosen index
# plus one: what a selector that pooled its compressed keys into the
# wrong block would take), read in every run on the first request and
# required to lie over it.
CHOICE_EPS = 0.0007
# The controls that decide ``correct``: each must read over LOGIT_FLOORS
# or the rule so read refuses nothing. On the chip (PERF.md, PR 34: my
# runs' seeds) full attention in place of the choice read 3.07-3.24
# floors, decay 1 41.3-46.0, RoPE on the sparse layers 4.98-5.60, the
# residual scale of the cut's depth 18.7-19.8. The fifth control is read
# in every run and decides nothing: a lightning state rounded to bf16
# after every token (``state_in_bf16``) reads 1.23 floors at the
# published widths and 0.8-1.7 at the toy's. The layers this cell holds
# (published 9-20) decay their slowest head over some 700 tokens, so a
# state holds 20 tokens' worth of any one product and 8 bits of mantissa
# carry it as well as the bf16 activations around it do; the published
# layers 21-31, whose decays reach 1 - 4e-8, are where a bf16 state
# swamps, and they lie on other chips. What holds the state to float32
# here is the driver's ``state_holds_float32`` check: the state the
# engine kept between ticks has bits a bf16 could not hold.
MUST_REFUSE = ("full_attention_for_choice", "decay_one", "rope_on_sparse_layers",
               "scale_by_cut_depth")

_NAMES = {
    "attn_norm": "input_layernorm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
    "wo": "o_proj", "w_og": "o_gate", "q_norm": "q_norm", "k_norm": "k_norm",
    "o_norm": "o_norm", "mlp_norm": "post_attention_layernorm",
    "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj",
}
_MIXERS = {"sparse_attention": "minicpm4", "linear_attention": "lightning-attn"}


def reference_weights(params: dict, consume: bool = False) -> dict:
    """The program's parameter tree (``lead_layers``, and ``layers``
    stacked over the periods) in the reference's own layout: a renaming
    and an unstacking. The program's ``log_decay`` is left behind: the
    reference makes its decays from the published layer index itself.
    ``consume`` deletes each stacked array once its slices are made."""
    period = params["layers"]
    n = jax.tree.leaves(period)[0].shape[0] if period else 0
    unstacked = [[{} for _ in period] for _ in range(n)]
    for j, layer in enumerate(period):
        for k, v in layer.items():
            for i in range(n):
                unstacked[i][j][k] = v[i]
            if consume:
                jax.block_until_ready([u[j][k] for u in unstacked])
                v.delete()
    layers = list(params["lead_layers"]) + [layer for one in unstacked for layer in one]
    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "layers": [{_NAMES[k]: v for k, v in layer.items() if k in _NAMES}
                      for layer in layers]}
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def hyper(cfg) -> dict:
    """The reference's ``hp`` from the program's configuration."""
    n = cfg.num_hidden_layers
    return {"num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_norm_eps,
            "rope_theta": cfg.rope_theta,
            "mixer_types": [_MIXERS[k] for k in cfg.layer_types],
            "layer_indices": [cfg.first_layer_index + i for i in range(n)],
            "published_layers": cfg.published_layers or n,
            "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
            "dim_model_base": cfg.dim_model_base,
            "decay_exponent": cfg.linear_decay_exponent,
            "block_size": cfg.sparse_block_size, "topk": cfg.sparse_topk,
            "kernel_size": cfg.sparse_kernel_size,
            "kernel_stride": cfg.sparse_kernel_stride,
            "init_blocks": cfg.sparse_init_blocks,
            "window_size": cfg.sparse_window_size, "dense_len": cfg.sparse_dense_len}


def padded(prompt: list, stream: list, n: int, multiple: int = 128):
    """One request as the reference takes it: prompt + the first n - 1
    answer tokens, zero-padded on the right to a whole number of
    ``multiple`` rows (causal: the pads change nothing before them), and
    the n positions whose logits are compared."""
    seq = list(prompt) + list(stream[:n - 1])
    width = -(-len(seq) // multiple) * multiple
    rows = np.zeros((1, width), np.int32)
    rows[0, :len(seq)] = seq
    return rows, (len(prompt) - 1 + np.arange(n, dtype=np.int32))[None]


def followed_choice(chosen: np.ndarray, width: int) -> np.ndarray:
    """The engine's record for one request ([L, positions, Hkv, topk])
    as the reference's ``choice`` [L, 1, width, Hkv, topk]: -1 (this
    pass's own) past what the engine recorded."""
    out = np.full((chosen.shape[0], 1, width) + chosen.shape[2:], -1, np.int32)
    seen = min(chosen.shape[1], width)
    out[:, 0, :seen] = chosen[:, :seen]
    return out


def served_check(params: dict, cfg, prompts: list, streams: list, served: list,
                 chosen: list, reference, consume: bool = False,
                 must_refuse=MUST_REFUSE) -> dict:
    """``served[r]``: the engine's logits [n, V] for request r (the last
    prompt position, then each decoded step); ``chosen[r]``: its record
    of the blocks chosen, [L_sparse, positions, Hkv, topk]. One request
    at a time through ``reference`` (float32 and bf16 following the
    program's choice; the controls in bf16 on the first request). Not to
    be called under a trace: the reference runs a compiled program a
    layer. ``must_refuse``: the controls that decide ``ok``."""
    hp = hyper(cfg)
    weights = reference_weights(params, consume)
    out = {"check": "served_logits_vs_reference", "floors_allowed": LOGIT_FLOORS,
           "floors": [], "max_abs_diff": [], "floor": [],
           "choice_eps": CHOICE_EPS, "choice_shortfall_max": 0.0,
           "choices": 0, "choices_agree": 0, "controls": {}}
    finite = True
    for r, (p, s, logits, record) in enumerate(zip(prompts, streams, served, chosen)):
        logits = np.asarray(logits, np.float32)
        rows, at = padded(p, s, logits.shape[0])
        choice = followed_choice(record, rows.shape[1])

        def run(dtype, fault=None, **kw):
            return reference.forward(weights, rows, hp, dtype, at=at, choice=choice,
                                     fault=fault, by_layer=True, **kw)

        ref32, info = run(jnp.float32, with_choice=True)
        ref32 = np.asarray(ref32)[0]                                  # [n, V]
        floor = float(np.max(np.abs(ref32 - np.asarray(run(jnp.bfloat16))[0])))
        diff = float(np.max(np.abs(logits - ref32)))
        finite = finite and bool(np.isfinite(ref32).all() and np.isfinite(logits).all()
                                 and floor > 0.0)
        out["floor"].append(floor)
        out["max_abs_diff"].append(diff)
        out["floors"].append(diff / floor if floor > 0.0 else float("inf"))
        # the choice, at the positions the engine recorded past the dense length
        seen = min(record.shape[1], rows.shape[1])
        past = np.arange(seen) + 1 > cfg.sparse_dense_len              # [positions]
        short = np.asarray(info["shortfall"])[:, 0, :seen][:, past]   # [L, P, Hkv]
        own = np.sort(np.asarray(info["own"])[:, 0, :seen][:, past], axis=-1)
        mine = np.sort(record[:, :seen][:, past], axis=-1)
        out["choices"] += int(short.size)
        out["choices_agree"] += int(np.all(own == mine, axis=-1).sum())
        out["choice_shortfall_max"] = max(out["choice_shortfall_max"],
                                          float(short.max(initial=0.0)))
        if r == 0:
            for fault in reference.FAULTS:
                x = np.asarray(run(jnp.bfloat16, fault))[0]
                out["controls"][fault] = float(np.max(np.abs(x - ref32))) / floor
            choice = np.where(choice >= 0, choice + 1, -1)  # run() follows it
            off = np.asarray(run(jnp.bfloat16, with_choice=True)[1]["shortfall"])
            out["choice_shortfall_if_off_by_one"] = float(off[:, 0, :seen][:, past].max(initial=0.0))
    out["choices_agree_share"] = out["choices_agree"] / max(1, out["choices"])
    out["must_refuse"] = list(must_refuse)
    refused = all(out["controls"][name] > LOGIT_FLOORS for name in must_refuse)
    out["ok"] = bool(finite and refused and max(out["floors"]) <= LOGIT_FLOORS
                     and out["choice_shortfall_max"] <= CHOICE_EPS
                     < out["choice_shortfall_if_off_by_one"])
    return out
