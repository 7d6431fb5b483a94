"""Operations and bytes from shapes, and the table of peaks.

The yardstick's arithmetic, kept with the benchmark so that no later PR
to the program can change it. ``train_flops_per_token`` started as a
copy of ``nanodiloco_tpu/obs/costs.py:train_flops_per_token`` and
departs from it in two places, each on purpose:

- the vocabulary head is counted whether or not it is tied to the
  embedding (the original subtracts ``vocab x hidden`` once from the
  parameter count, which drops the head's matmul of a tied model);
- attention is counted causally (half of the S x S scores): what the
  algorithm needs, not what a dense kernel computes.

Recomputation (``remat``) is never counted. ``cfg`` is any object with
the HF names as attributes (the program's ``LlamaConfig`` or a
``types.SimpleNamespace`` of the configuration file).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    """No peaks are known for this ``device_kind``."""


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``, from ``peaks.json``.
    A device that is not in the table is an error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "benchmark/peaks.json with its source"
        )
    return table[device_kind]


def _kv_heads(cfg) -> int:
    return cfg.num_key_value_heads or cfg.num_attention_heads


def _head_dim(cfg) -> int:
    return cfg.hidden_size // cfg.num_attention_heads


def layer_matmul_params(cfg) -> int:
    """Weights of one decoder layer that a token is multiplied by:
    q, k, v, o and the three SwiGLU matrices (norm scales excluded)."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    nh, nkv, hd = cfg.num_attention_heads, _kv_heads(cfg), _head_dim(cfg)
    return d * nh * hd + 2 * d * nkv * hd + nh * hd * d + 3 * d * f


def train_flops_per_token(cfg, seq: int) -> float:
    """Matmul FLOPs one trained token needs, forward and backward
    (3 x forward, 2 FLOPs a multiply-add): 6 x (layer weights + the
    vocabulary head) plus causal attention, whose scores and values
    cost 2 x 2 x (seq / 2) x heads x head_dim a token forward."""
    weights = cfg.num_hidden_layers * layer_matmul_params(cfg)
    weights += cfg.hidden_size * cfg.vocab_size  # the head, tied or not
    attn = 6.0 * cfg.num_hidden_layers * seq * cfg.num_attention_heads * _head_dim(cfg)
    return 6.0 * weights + attn


def kv_bytes_per_token(cfg, kv_itemsize: int) -> int:
    """Bytes of K and V rows one cached token holds over all layers."""
    return 2 * cfg.num_hidden_layers * _kv_heads(cfg) * _head_dim(cfg) * kv_itemsize


def decode_tick_bytes(cfg, live_kv_tokens: float, weight_itemsize: int,
                      kv_itemsize: int) -> float:
    """Bytes one decode tick must read from HBM: every layer's matmul
    weights and the head once (the embedding is a gather of a few rows,
    not counted), plus the K and V rows of every live cached token.
    Activations, norm scales, block tables and the rows written are
    left out: megabytes against gigabytes."""
    weights = cfg.num_hidden_layers * layer_matmul_params(cfg)
    weights += cfg.hidden_size * cfg.vocab_size
    return (weights * weight_itemsize
            + live_kv_tokens * kv_bytes_per_token(cfg, kv_itemsize))
