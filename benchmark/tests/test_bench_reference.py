"""The plain reference against ``models/llama.py`` at a tiny size, for
both configurations' traits, and the rule that decides ``correct``."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.reference import llama_ref
from nanodiloco_tpu.models import LlamaConfig, causal_lm_loss, forward, init_params
from nanodiloco_tpu.parallel import Diloco, DilocoConfig, MeshConfig, build_mesh

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny(name: str, **program) -> LlamaConfig:
    with open(os.path.join(HERE, "rehearsal", "configs", name + ".json")) as f:
        conf = json.load(f)
    return LlamaConfig.from_dict({**conf, **conf["program"], "dtype": "float32",
                                  "param_dtype": "float32", **program})


@pytest.mark.parametrize("name", ["tiny-tied", "tiny-untied"])
def test_reference_agrees_with_the_program_in_float32(name):
    """GQA 3:1 with a tied head; GQA 4:1 untied. Two float32
    implementations of one function: they may differ by summation order
    only, 1e-4 on logits of order 1."""
    cfg = tiny(name)
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.vocab_size)
    want = forward(params, tokens, cfg)
    got = llama_ref.forward(correctness.reference_weights(params), tokens,
                            correctness.hyper(cfg))
    assert got.shape == want.shape == (2, 48, cfg.vocab_size)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    loss = float(causal_lm_loss(params, tokens, cfg)[0])
    ref = float(llama_ref.loss(correctness.reference_weights(params), tokens,
                               correctness.hyper(cfg), remat=True))
    assert abs(loss - ref) < 1e-4


def test_reference_is_causal_and_rotates_by_position():
    cfg = tiny("tiny-untied")
    w = correctness.reference_weights(init_params(jax.random.key(0), cfg))
    hp = correctness.hyper(cfg)
    a = jax.random.randint(jax.random.key(2), (1, 32), 0, cfg.vocab_size)
    b = a.at[0, 20:].set(7)
    la, lb = llama_ref.forward(w, a, hp), llama_ref.forward(w, b, hp)
    assert float(jnp.max(jnp.abs(la[0, :20] - lb[0, :20]))) == 0.0
    assert float(jnp.max(jnp.abs(la[0, 20:] - lb[0, 20:]))) > 1e-3
    # two earlier tokens swapped: attention alone cannot tell, RoPE can
    c = a.at[0, 2].set(a[0, 3]).at[0, 3].set(a[0, 2])
    assert int(a[0, 2]) != int(a[0, 3])
    lc = llama_ref.forward(w, c, hp)
    assert float(jnp.max(jnp.abs(la[0, -1] - lc[0, -1]))) > 1e-4


OPT = {"lr": 1e-3, "warmup_steps": 2, "total_steps": 100, "weight_decay": 0.01,
       "clip_norm": 1.0, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


@pytest.mark.parametrize("fault, passes", [
    ({}, True),
    ({"lr": 5e-4}, False),              # half the steps' length
    ({"warmup_steps": 1000}, False),    # an optimizer that hardly moves
    ({"lr": 1.3e-3}, False),            # steps a third too long
])
def test_round_check_passes_the_program_and_refuses_another_optimizer(fault, passes):
    """The fused round's first losses on a repeated microbatch against
    the plain float32 AdamW loop: the program's own recipe passes, and
    the same program under another recipe is refused."""
    cfg = tiny("tiny-tied", dtype="bfloat16")
    params = init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
    reference = correctness.reference_losses(params, cfg, tokens, OPT, 3)
    assert reference[0] == reference[1] > reference[2]  # the first rate is 0
    run = {k: v for k, v in {**OPT, **fault}.items() if k not in ("b1", "b2", "eps")}
    mesh = build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1])
    dl = Diloco(cfg, DilocoConfig(num_workers=1, inner_steps=4, **run), mesh)
    state = dl.init_state(jax.random.key(0), params=params)
    shape = (4, 1, 1) + tokens.shape
    _, loss, _ = dl.round_step(state, jnp.broadcast_to(tokens, shape),
                               jnp.ones(shape, jnp.int32))
    program = [float(x) for x in np.asarray(loss)[:3, 0]]
    check = correctness.train_round_check(program, reference)
    assert check["ok"] == passes, check
    assert not correctness.train_round_check([float("nan")] * 3, reference)["ok"]


def test_two_floor_rule_passes_the_model_and_refuses_another_context():
    """Served logits and greedy tokens of the bf16 program pass; the
    same tokens judged against another prompt's context do not."""
    cfg = dataclasses.replace(tiny("tiny-untied"), dtype="bfloat16",
                              param_dtype="bfloat16")
    params = init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist() for n in (24, 40)]
    # greedy decoding by whole forward passes over one padded width
    # (causal, so the pads after a position change nothing at it)
    fwd = jax.jit(lambda ids: forward(params, ids, cfg))
    streams, served = [], []
    for p in prompts:
        ids, first = list(p), None
        for _ in range(4):
            row = jnp.asarray([ids + [0] * (48 - len(ids))])
            logits = np.asarray(fwd(row))[0, len(ids) - 1]
            first = logits if first is None else first
            ids.append(int(logits.argmax()))
        streams.append(ids[len(p):])
        served.append(first)
    good = correctness.served_logits_check(params, cfg, prompts, streams, served, 8)
    floors = correctness.LOGIT_FLOORS
    assert good["prefill_floors"] <= floors and good["decode_floors"] <= floors, good
    assert good["tokens"] == 8
    # the negative control: another request's K and V are refused
    assert good["ok"] and good["controls"]["kv_of_another_request"] > floors, good
    assert set(good["controls"]) > set(correctness.MUST_REFUSE)
    others = [rng.integers(0, cfg.vocab_size, len(p)).tolist() for p in prompts]
    bad = correctness.served_logits_check(params, cfg, others, streams, served, 8)
    assert not bad["ok"] and bad["prefill_floors"] > correctness.LOGIT_FLOORS
