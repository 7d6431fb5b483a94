"""Block-sparse attention chosen by compressed keys (InfLLM-V2,
arXiv:2509.24663, as MiniCPM4 sets it: arXiv:2506.07900).

The query at position t sees n = t + 1 keys. With n <= ``dense_len`` it
attends to all of them. Past that, per KV group:

    c_j  = mean(k[stride j : stride j + kernel])        every j with stride j + kernel <= n
    p^h  = softmax_j(q^h . c_j / sqrt(hd))              each query head of the group
    r_j  = sum_h p^h_j
    b_m  = max r_j over the j whose keys touch block m = [blk m, blk m + blk); 0 where none
    forced: blocks [0, init_blocks) and every block holding one of keys [n - window, n)
    chosen: forced, and the ``topk`` best of the other blocks that exist, ties to the lower

and the output is softmax attention over the keys <= t of the chosen
blocks. Every query chooses for itself, whatever call it arrives in, so
a chunked prefill and a decode tick equal the full forward pass.

Two implementations of the one rule, picked by the shape of the call:

- ``masked`` (a prefill chunk, the full forward pass): the choice as a
  mask over every key the call can see, in blocks of ``Q_BLOCK``
  queries so that the scores of 512 queries over 64k keys never stand
  at once;
- ``gathered`` (a decode tick): the chosen blocks' rows alone, gathered
  from the pool through the block table; it never reads the rest.

Scopes, all inside ``attention``: ``sparse_select`` (compressed scores,
pooling, top-k), ``sparse_attend`` (the mask or the gather, and the
attention), ``kv_compress`` (making compressed keys). ``COUNTERS`` names
what a layer counts, over queries past ``dense_len`` alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from nanodiloco_tpu.models.config import LlamaConfig

# what a sparse or linear layer returns beside its output, summed over
# layers by ``run_layers``: K/V rows attended (a KV group's mean), rows
# the streams held (what full attention would have read), compressed
# rows scored, queries that chose, and linear layers' state updates
# (one a live row a layer a call)
COUNTERS = ("sparse_rows_read", "sparse_rows_held", "sparse_compressed_rows",
            "sparse_queries", "state_updates")
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
Q_BLOCK = 64


def complete_rows(cfg: LlamaConfig, n):
    """Compressed rows complete once a stream holds ``n`` keys."""
    return jnp.maximum((n - cfg.sparse_kernel_size) // cfg.sparse_kernel_stride + 1, 0)


@jax.named_scope("kv_compress")
def compress_keys(cfg: LlamaConfig, k):
    """k [B, S, Hkv, hd] -> every complete compressed key of the
    sequence [B, J, Hkv, hd], J = (S - kernel) // stride + 1."""
    kern, stride = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    j = max((k.shape[1] - kern) // stride + 1, 0)
    at = jnp.arange(j)[:, None] * stride + jnp.arange(kern)[None, :]     # [J, kern]
    return jnp.mean(k[:, at].astype(jnp.float32), axis=2).astype(k.dtype)


def forced_blocks(cfg: LlamaConfig, n, blocks):
    """Which of ``blocks`` a query that sees ``n`` keys attends to
    whatever their scores: the first ones and the window's (shapes
    broadcast)."""
    return (blocks < cfg.sparse_init_blocks) | (
        blocks >= (n - cfg.sparse_window_size) // cfg.sparse_block_size)


def choose(cfg: LlamaConfig, q, comp, qpos):
    """The top-k choice. q [B, T, H, hd]; ``comp`` [B, Jw, Hkv, hd], row
    j the stream's compressed key j (rows not yet complete hold
    anything); ``qpos`` [B, T]. Returns the chosen blocks [B, T, Hkv,
    topk] int32, best first, -1 where fewer exist (the forced blocks are
    a function of the position and not listed)."""
    b, t, nh, hd = q.shape
    jw, nkv = comp.shape[1], comp.shape[2]
    blk, stride = cfg.sparse_block_size, cfg.sparse_kernel_stride
    per, extra = blk // stride, cfg.sparse_kernel_size // stride - 1
    with jax.named_scope("attention"), jax.named_scope("sparse_select"):
        n = qpos + 1
        qg = q.reshape(b, t, nkv, nh // nkv, hd)
        s = jnp.einsum("btkgd,bjkd->btkgj", qg, comp,
                       preferred_element_type=jnp.float32) * (1.0 / math.sqrt(hd))
        done = (jnp.arange(jw)[None, None, :] < complete_rows(cfg, n)[:, :, None])
        done = done[:, :, None, None, :]                          # [B, T, 1, 1, Jw]
        p = jax.nn.softmax(jnp.where(done, s, MASK_VALUE), axis=-1)
        r = jnp.sum(jnp.where(done, p, 0.0), axis=3)              # [B, T, Hkv, Jw]
        # rows that START in block m, then the rows before that reach into it
        m = max(-(-jw // per), cfg.sparse_topk)
        r = jnp.pad(r, ((0, 0),) * 3 + ((0, m * per - jw),)).reshape(b, t, nkv, m, per)
        score = jnp.max(r, axis=-1)
        for e in range(1, extra + 1):
            before = jnp.pad(r[..., :-1, per - e], ((0, 0),) * 3 + ((1, 0),))
            score = jnp.maximum(score, before)
        blocks = jnp.arange(m)[None, None, :]
        last = (qpos // blk)[:, :, None]
        cand = (blocks <= last) & ~forced_blocks(cfg, n[:, :, None], blocks)   # [B, T, M]
        vals, idx = jax.lax.top_k(jnp.where(cand[:, :, None, :], score, -1.0),
                                  cfg.sparse_topk)
        return jnp.where(vals >= 0.0, idx, -1).astype(jnp.int32)


def chooses(cfg: LlamaConfig, qpos):
    """Whether the query at ``qpos`` is past ``dense_len``."""
    return qpos + 1 > cfg.sparse_dense_len


def block_mask(cfg: LlamaConfig, idx, qpos, m: int):
    """Blocks [B, T, Hkv, m] bool each query may attend to: all of them
    up to ``dense_len``, else the forced ones and ``idx`` (``choose``)."""
    blocks = jnp.arange(m)[None, None, None, :]
    forced = forced_blocks(cfg, (qpos + 1)[:, :, None, None], blocks)
    top = jnp.any(idx[..., :, None] == blocks[..., None, :], axis=-2)
    return jnp.where(chooses(cfg, qpos)[:, :, None, None], forced | top, True)


@jax.named_scope("attention")
def attend(q, ck, cv, mask):
    """q [B, T, H, hd] over ck, cv [B, Hkv, S, hd] under the additive
    ``mask`` [B, Hkv, T, S] (one a KV group), softmax in float32:
    [B, T, H * hd]."""
    b, t, nh, hd = q.shape
    nkv = ck.shape[1]
    qg = q.reshape(b, t, nkv, nh // nkv, hd)
    scores = jnp.einsum("btkgd,bksd->bkgts", qg, ck).astype(jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd)) + mask[:, :, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgts,bksd->btkgd", probs, cv).reshape(b, t, nh * hd)


def _count(cfg: LlamaConfig, qpos, live, rows_read):
    """``COUNTERS`` of one sparse layer's call: ``rows_read`` [B, T] the
    rows each query attended, ``live`` [B, T] the real queries."""
    on = chooses(cfg, qpos) & (live > 0)
    n = qpos + 1
    return jnp.stack([
        jnp.sum(jnp.where(on, rows_read, 0)), jnp.sum(jnp.where(on, n, 0)),
        jnp.sum(jnp.where(on, complete_rows(cfg, n), 0)), jnp.sum(on),
        jnp.zeros((), jnp.int32)]).astype(jnp.int32)


def masked(cfg: LlamaConfig, q, ck, cv, comp, qpos, live, idx=None, q_block: int = Q_BLOCK):
    """Every query's own choice as a mask over the keys ``ck``, ``cv``
    [B, Hkv, S, hd] (key s at position s; rows past a query's position
    hold anything). ``comp`` as in ``choose``; ``idx`` hands the choice
    in (a check that follows another program's). Queries run in blocks
    of ``q_block`` where T is a whole number of them. Returns (attention
    [B, T, H * hd], the choice [B, T, Hkv, topk], COUNTERS)."""
    b, t, nh, hd = q.shape
    s = ck.shape[2]
    blk = cfg.sparse_block_size
    m = -(-s // blk)

    def some(q, qpos, live, idx):
        if idx is None:
            idx = choose(cfg, q, comp, qpos)
        with jax.named_scope("attention"), jax.named_scope("sparse_attend"):
            ok = block_mask(cfg, idx, qpos, m)                    # [B, T', Hkv, m]
            ok = jnp.repeat(ok, blk, axis=-1)[..., :s]
            ok = ok & (jnp.arange(s)[None, None, None, :] <= qpos[:, :, None, None])
            rows = jnp.sum(ok, axis=(2, 3)) // ok.shape[2]
            mask = jnp.where(ok, 0.0, MASK_VALUE).transpose(0, 2, 1, 3)
            out = attend(q, ck, cv, mask)
        return out, idx, _count(cfg, qpos, live, rows)

    if t <= q_block or t % q_block:
        return some(q, qpos, live, idx)
    nb = t // q_block
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, q_block, *a.shape[2:]), 1, 0)
    if idx is None:
        out, idx, counts = jax.lax.map(lambda a: some(*a, None),
                                       (split(q), split(qpos), split(live)))
    else:
        out, idx, counts = jax.lax.map(lambda a: some(*a),
                                       (split(q), split(qpos), split(live), split(idx)))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(b, t, *a.shape[3:])
    return join(out), join(idx), jnp.sum(counts, axis=0)


def gathered(cfg: LlamaConfig, q, pk, pv, comp, tables, pos, live, kv_block: int):
    """One query a row past ``dense_len`` (a decode tick): the choice,
    then the chosen blocks' rows gathered from the pools ``pk``, ``pv``
    [blocks, Hkv, kv_block, hd] through ``tables`` [B, mb], and the
    attention over them. q [B, H, hd]; ``pos`` [B]. Rows with
    ``pos + 1 <= dense_len`` return garbage (the caller takes its dense
    path for them). Returns (attention [B, H * hd], the choice [B, 1,
    Hkv, topk], COUNTERS)."""
    b, nh, hd = q.shape
    nkv = pk.shape[1]
    blk = cfg.sparse_block_size
    per = blk // kv_block
    idx = choose(cfg, q[:, None], comp, pos[:, None])             # [B, 1, Hkv, topk]
    with jax.named_scope("attention"), jax.named_scope("sparse_attend"):
        n = pos + 1
        first = jnp.arange(cfg.sparse_init_blocks)
        near = ((n - cfg.sparse_window_size) // blk)[:, None] + jnp.arange(
            cfg.sparse_window_size // blk + 1)[None, :]           # [B, W]
        sel = jnp.concatenate([
            jnp.broadcast_to(first[None, None, :], (b, nkv, first.shape[0])),
            jnp.broadcast_to(near[:, None, :], (b, nkv, near.shape[1])),
            idx[:, 0]], axis=-1)                                  # [B, Hkv, NS]
        ok = (sel >= 0) & (sel <= (pos // blk)[:, None, None])
        # a sparse block is ``per`` KV blocks that follow each other in
        # the table: one lookup of ``per`` entries a chosen block (looked
        # up one by one, the 25k entries cost as much as a gather of rows)
        mb = tables.shape[1]
        wide = jnp.pad(tables, ((0, 0), (0, -mb % per)), constant_values=pk.shape[0])
        at = jnp.clip(sel, 0, wide.shape[1] // per - 1)
        phys = jnp.take_along_axis(wide.reshape(b, 1, -1, per), at[..., None], axis=2)
        phys = phys.reshape(b, nkv, -1)                               # [B, Hkv, NS * per]
        head = jnp.arange(nkv)[None, :, None]
        ck = pk[phys, head].reshape(b, nkv, -1, hd)               # [B, Hkv, NS * blk, hd]
        cv = pv[phys, head].reshape(b, nkv, -1, hd)
        at = (jnp.maximum(sel, 0)[..., None] * blk + jnp.arange(blk)).reshape(b, nkv, -1)
        seen = jnp.repeat(ok, blk, axis=-1) & (at <= pos[:, None, None])
        rows = jnp.sum(seen, axis=(1, 2)) // nkv
        mask = jnp.where(seen, 0.0, MASK_VALUE)[:, :, None, :]    # [B, Hkv, 1, S]
        out = attend(q[:, None], ck, cv, mask)[:, 0]
    return out, idx, _count(cfg, pos[:, None], live[:, None], rows[:, None])
