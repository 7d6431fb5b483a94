"""Pallas TPU flash attention: forward + backward kernels, custom VJP.

Design (standard FlashAttention-2 decomposition, shaped for the TPU):

- Arrays are flattened to ``[BH, S, hd]`` (batch*heads leading) and the
  grid is ``(BH, q_blocks, k_blocks)`` with the K axis innermost and
  "arbitrary" (sequential) semantics, so the online-softmax accumulators
  live in VMEM scratch across K iterations while BH and Q blocks run in
  parallel.
- Every matmul is a ``dot_general`` with ``preferred_element_type=f32``
  so the MXU accumulates in float32 regardless of the input dtype; the
  running max/denominator are kept in (block_q, 128)-shaped VMEM scratch
  (lane-replicated scalars — the TPU-native layout for per-row state).
- Causal masking is block-level: K blocks entirely above the diagonal
  are skipped with ``pl.when`` (no wasted MXU work), the diagonal block
  is masked with broadcasted iotas, everything below runs unmasked.
- The backward pass uses the saved ``lse = m + log(l)`` (one [BH, S]
  float32 row-statistic, the only residual beyond q/k/v/o) and two
  kernels: dq accumulates over K blocks; dk/dv accumulate over Q blocks.

The kernels run under ``interpret=True`` on CPU — the test suite
verifies them against dense attention on the virtual-device mesh, and
the same code compiles to Mosaic on a real TPU
(tests/test_tpu_compile.py compiles it for a described v5e).

The reference has no attention kernel of its own (HF eager attention,
ref /root/reference/nanodiloco/main.py:9,98); this is the TPU-native
performance path the rebuild adds.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# BH and Q-block grid axes are embarrassingly parallel; only the K axis
# carries the online-softmax recurrence through scratch.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)

NEG_INF = float("-inf")


def _dot(a, b, trans_a=False, trans_b=False):
    """f32-accumulating matmul with optional transposes."""
    ca = (0,) if trans_a else (1,)
    cb = (1,) if trans_b else (0,)
    return lax.dot_general(
        a, b, ((ca, cb), ((), ())), preferred_element_type=jnp.float32
    )


def _causal_mask_block(qi, ki, block_q, block_k):
    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return qpos >= kpos


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, block_q, block_k, nk,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # K blocks entirely above the causal diagonal contribute nothing.
    should_run = (
        ki * block_k <= qi * block_q + block_q - 1 if causal else ki >= 0
    )

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _dot(q, k, trans_b=True) * sm_scale          # [bq, bk] f32
        if causal:
            s = jnp.where(
                _causal_mask_block(qi, ki, block_q, block_k), s, NEG_INF
            )
        m_prev = m_ref[...][:, :1]                       # [bq, 1]
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked rows keep m=-inf; exp against a 0 stand-in yields
        # p=0 / corr=0 so they contribute nothing and never NaN.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_safe, NEG_INF))
        corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe, NEG_INF))
        l_ref[...] = jnp.broadcast_to(
            l_prev * corr + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
        )
        acc_ref[...] = acc_ref[...] * corr + _dot(p.astype(v.dtype), v)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    last_ki = (
        jnp.minimum(nk - 1, (qi * block_q + block_q - 1) // block_k)
        if causal
        else nk - 1
    )

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_ref[...][:, :1]
        m = m_ref[...][:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        lse_ref[...] = lse.reshape(lse_ref.shape)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale, causal, block_q, block_k, nk,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    should_run = (
        ki * block_k <= qi * block_q + block_q - 1 if causal else ki >= 0
    )

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)
        s = _dot(q, k, trans_b=True) * sm_scale
        if causal:
            s = jnp.where(
                _causal_mask_block(qi, ki, block_q, block_k), s, NEG_INF
            )
        # p: exact softmax probabilities reconstructed from the saved lse
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - lse, NEG_INF))
        dp = _dot(do, v, trans_b=True)                   # [bq, bk]
        ds = p * (dp - delta)
        dq_acc[...] += _dot(ds, k.astype(jnp.float32))

    last_ki = (
        jnp.minimum(nk - 1, (qi * block_q + block_q - 1) // block_k)
        if causal
        else nk - 1
    )

    @pl.when(ki == last_ki)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, block_q, block_k, nq, group, grid_ids,
):
    """``grid_ids`` = grid positions of (ki, bh, qi). MHA (group == 1)
    runs the fully parallel (BH, k_blocks, q_blocks) grid; GQA runs
    (k_blocks, BH, q_blocks) with BH sequential so the VMEM accumulators
    can sum a KV head's gradient over BOTH its q blocks and the ``group``
    query heads sharing it before one write-out per KV head."""
    ki = pl.program_id(grid_ids[0])
    bh = pl.program_id(grid_ids[1])
    qi = pl.program_id(grid_ids[2])

    @pl.when((qi == 0) & (bh % group == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    should_run = (
        qi * block_q + block_q - 1 >= ki * block_k if causal else qi >= 0
    )

    @pl.when(should_run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[...].reshape(block_q, 1)
        delta = delta_ref[...].reshape(block_q, 1)
        s = _dot(q, k, trans_b=True) * sm_scale          # [bq, bk]
        if causal:
            s = jnp.where(
                _causal_mask_block(qi, ki, block_q, block_k), s, NEG_INF
            )
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - lse, NEG_INF))
        dv_acc[...] += _dot(p, do, trans_a=True)         # [bk, hd]
        dp = _dot(do, v, trans_b=True)
        ds = p * (dp - delta)
        dk_acc[...] += _dot(ds, q.astype(jnp.float32), trans_a=True)

    @pl.when((qi == nq - 1) & (bh % group == group - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom-VJP wrapper over [BH, S, hd]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(causal, block_q, block_k, interpret, q, k, v):
    out, _ = _flash_fwd(causal, block_q, block_k, interpret, q, k, v)
    return out


def _flash_fwd(causal, block_q, block_k, interpret, q, k, v):
    out, lse = _fwd_call(causal, block_q, block_k, interpret, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, dout):
    q, k, v, out, lse = res
    bh, s, hd = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    nq, nk = s // block_q, sk // block_k
    sm_scale = 1.0 / math.sqrt(hd)
    # delta_i = sum_d dO_id * O_id — the softmax-jacobian row term
    # ([BH, S, 1] like lse, so the blocks stay TPU-tileable)
    delta = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1, keepdims=True
    )

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel,
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, nk=nk,
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    # dk/dv grid: MHA keeps BH fully parallel (Megacore-partitionable);
    # GQA puts K blocks parallel-outermost and iterates BH sequentially
    # so the VMEM accumulators carry across the `group` query heads of
    # each KV head (consecutive in BH) before the single write to dk/dv.
    if group == 1:
        grid = (bh, nk, nq)
        grid_ids = (1, 0, 2)
        semantics = ("parallel", "parallel", "arbitrary")
        bq_spec = lambda b, j, i: (b, i, 0)      # noqa: E731
        bk_spec = lambda b, j, i: (b, j, 0)      # noqa: E731
    else:
        grid = (nk, bh, nq)
        grid_ids = (0, 1, 2)
        semantics = ("parallel", "arbitrary", "arbitrary")
        bq_spec = lambda j, b, i: (b, i, 0)      # noqa: E731
        bk_spec = lambda j, b, i: (b // group, j, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel,
            sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, nq=nq, group=group,
            grid_ids=grid_ids,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), bq_spec),
            pl.BlockSpec((1, block_k, hd), bk_spec),
            pl.BlockSpec((1, block_k, hd), bk_spec),
            pl.BlockSpec((1, block_q, hd), bq_spec),
            pl.BlockSpec((1, block_q, 1), bq_spec),
            pl.BlockSpec((1, block_q, 1), bq_spec),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), bk_spec),
            pl.BlockSpec((1, block_k, hd), bk_spec),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, hd), jnp.float32),
            pltpu.VMEM((block_k, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fwd_call(causal, block_q, block_k, interpret, q, k, v):
    bh, s, hd = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv  # GQA: query heads per KV head (1 = MHA)
    block_q = min(block_q, s)
    block_k = min(block_k, sk)
    if s % block_q or sk % block_k:
        raise ValueError(
            f"seq lengths ({s}, {sk}) must divide by blocks ({block_q}, {block_k})"
        )
    nq, nk = s // block_q, sk // block_k
    sm_scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, nk=nk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
            # [BH, S, 1]: trailing singleton keeps the block TPU-tileable
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Public API: [B, S, H, hd] in the framework's layout
# ---------------------------------------------------------------------------

def pallas_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """q: [B, S, H, hd]; k, v: [B, S, Hkv, hd] with H % Hkv == 0 (GQA —
    never expanded: the kernel grid maps each query head's K/V block
    fetch to its KV head via ``bh // group``, so K/V HBM traffic and
    VMEM residency stay at Hkv heads). Differentiable.

    ``interpret``: None compiles the kernels on TPU and interprets them
    on any other backend, which is how the CPU tests run them; True on
    TPU is refused. A compile for a described, unattached TPU runs on
    the CPU backend and so must pass ``interpret=False`` itself.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        interpret = not on_tpu
    elif interpret and on_tpu:
        raise ValueError(
            "pallas_flash_attention: interpret mode on tpu would run the "
            "kernel through the interpreter, not Mosaic"
        )
    b, s, h, hd = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"query heads {h} must divide by kv heads {hkv}")

    def flat(x, sl, nh):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * nh, sl, hd)

    out = _flash(
        causal, block_q, block_k, interpret,
        flat(q, s, h), flat(k, sk, hkv), flat(v, sk, hkv),
    )
    return jnp.transpose(out.reshape(b, h, s, hd), (0, 2, 1, 3))
