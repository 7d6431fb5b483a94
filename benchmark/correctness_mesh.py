"""What decides ``correct`` for the round program on a mesh, beside
``correctness.py``'s losses: the outer step's exchange between the chips.

A round in which every worker sees the same data cannot tell whether the
exchange ran: the workers end equal with or without it. So the sync is
judged on a round of DISTINCT data, from the initial weights, run twice:
once as the program's inner steps alone (``inner_round_step``, no sync),
which gives every worker's weights before the sync, and once as the timed
fused round. The fused round's new snapshot, as chip 0 holds it, must then
be the plain outer step over those four workers' deltas

    delta = mean_w (s0 - p_w)      m = delta              (momentum starts at 0)
    s1 = s0 - lr (delta + mu m) = s0 - lr (1 + mu) delta  (Nesterov, optax.sgd)

computed here in numpy on the host, with no collective and nothing of the
program's. Both sides are read on a sample: every ``SAMPLE``-th column of
every tensor (the whole of it is held to bit-equality on the devices, by
the driver). The distance is the norm of the difference over the norm of
the reference's own step ``s1 - s0``, and the negative control, by the same
rule, is the step a program would make that left the exchange out: worker
0's delta alone.

SYNC_TOL lies between two readings (PERF.md, PR 26): the program's, which
is the rounding between two executables of the same inner steps, and the
control's, which is how far one worker's delta lies from the mean of four
on random tokens.
"""

from __future__ import annotations

import numpy as np

SAMPLE = 16
SYNC_TOL = 0.1


def sampled(tree) -> list:
    """Every ``SAMPLE``-th column of every tensor of ``tree``, on the host
    (a slice along an axis no mesh axis divides: no collective)."""
    import jax

    cut = jax.jit(lambda t: [x[..., ::SAMPLE] for x in jax.tree.leaves(t)])
    return [np.asarray(x) for x in cut(tree)]


def sync_check(s0: list, before: list, after: list, outer_lr: float,
               momentum: float) -> dict:
    """``s0``: the initial weights; ``before``: every worker's weights
    after the round's inner steps, [W, ...] a tensor; ``after``: the fused
    round's new snapshot on chip 0. All sampled alike."""
    step = outer_lr * (1.0 + momentum)
    far = gone = moved = 0.0
    for s, p, got in zip(s0, before, after):
        s, p, got = (np.asarray(x, np.float64) for x in (s, p, got))
        ref = s - step * (s - p.mean(axis=0))
        alone = s - step * (s - p[0])
        far += float(np.sum((got - ref) ** 2))
        gone += float(np.sum((alone - ref) ** 2))
        moved += float(np.sum((ref - s) ** 2))
    distance, control = (far / moved) ** 0.5, (gone / moved) ** 0.5
    return {"check": "snapshot_vs_reference_outer_step", "distance": distance,
            "exchange_left_out": control, "tolerance": SYNC_TOL,
            "step_norm": moved ** 0.5, "sampled_values": int(sum(x.size for x in s0)),
            "ok": bool(np.isfinite(distance) and moved > 0.0
                       and distance <= SYNC_TOL < control)}
