"""One-command evidence capture: every drill below in one sitting.

Superseded as the proof of a chip run by ``chip_smoke.py`` (repo root)
and due for removal with ROADMAP C1; until then this script runs the
agenda and records everything as JSON lines:

  1. the headline bench (``bench.py`` defaults + decode entry), and a
     refresh of ``bench_baseline.json`` when the new number is a real
     chip measurement;
  2. the long-context attention sweep on the mid (414M GQA) model:
     seq 1024/2048/4096/8192 x {dense, flash} — the measurement VERDICT
     r2 asked to set ``attention_impl`` defaults from (the reference
     caps sequence at 1024, ref training_utils/utils.py:45,50; long
     context is this rebuild's differentiator);
  3. a jax.profiler trace of a few steady-state mid-model steps;
  4. a telemetry scrape: a short real run served over --metrics-port,
     /healthz + /metrics pulled over the wire and the gauges recorded —
     the production scrape path proven on the chip.
  4b. a live-profile drill: POST /debug/profile to a RUNNING training
     process's telemetry endpoint and assert the jax.profiler artifact
     lands on disk — on-demand capture proven against a live job.
  4c. an async-overlap drill: a short 2-worker --async-outer run on the
     real backend; the sync JSONL must record an outer_staleness >= 1
     apply (the merge landed a round late) and the staleness/drift
     gauges must scrape over the wire while the delayed path trains.
  5. a resilience drill: launch a live run, SIGTERM it mid-round, assert
     a clean preemption checkpoint + the preempt exit code (75), then
     let `supervise` resume it to completion from that checkpoint — the
     preempt/resume loop proven on the chip, not just in the CPU tests.
  6. a serving drill: train a tiny checkpoint, launch the `serve` CLI
     on it, drive 2 OVERLAPPING requests over a real socket, and scrape
     the serve gauges off /metrics — continuous batching proven on the
     chip end to end.
  7. a serve-interference drill: one LONG prompt plus concurrent short
     streams against the chunked-prefill engine — short-stream TTFT
     must stay bounded while the long prefill is in flight, the shared
     prefix must hit the cache, and the chunk/prefix/priority gauges
     are scraped — the PR-6 serving tier proven on the chip.
  8. a paged-KV drill: the `serve` CLI on a TINY block pool
     (oversubscribed vs the dense footprint) — concurrent + sequential
     traffic recycles blocks through the free list, a shared prefix
     takes copy-on-write hits, the block-pool gauges scrape over the
     wire, and an fp-paged stream is replayed through solo
     ``generate()`` on the same backend for bit-parity.
  9. a speculative-decoding drill: the `serve` CLI with prompt-lookup
     speculation (--spec-k) under greedy repetitive traffic — the
     draft/accept counters must prove real acceptance on the live
     backend, the spec gauges scrape over the wire, and the
     speculative stream is replayed through solo ``generate()`` for
     bit-parity (the CPU record pins correctness + acceptance; this
     sitting pins the on-chip speedup).
  10. a tensor-parallel serving drill: the `serve` CLI with --tp 2 —
     params, the decode/verify programs, and the paged KV pool sharded
     over two devices — under greedy plain + speculative traffic; the
     TP gauges (tp_degree, per-shard kv_blocks_free) must scrape over
     the wire and both streams must replay bit-identically through
     solo ``generate(mesh=...)`` on the same layout (on CPU the drill
     runs on 2 virtual devices: same programs, same parity bar, no
     speedup claim — the chip sitting is what pins serving models
     bigger than one chip).

  11. a continuous-deployment drill: a 2-replica `serve` fleet behind
     the `fleet` router CLI with the canary controller watching a live
     training checkpoint dir — a fresh checkpoint is canaried and
     promoted fleet-wide (traffic 200 throughout, post-promote stream
     bit-matched against solo ``generate()`` on the promoted
     checkpoint), a SIGABRT'd replica is ejected with its black box
     attached to the ejection event, and a poisoned (NaN) checkpoint is
     rolled back by the canary gate — the train->serve loop closed on
     the live backend.

  12. a fleet OBSERVABILITY drill (`slo_watch`): 2 replicas (one an
     injected straggler) + router + `obs-watch` — the TTFT burn-rate
     alert fires, the router routes around the burning replica before
     any ejection, the merged trace joins router and replica spans on
     the request_id key, and the alert counters scrape over the wire.

  13. a device-time ATTRIBUTION drill (`devtime`): one replica under
     mixed-priority traffic — the per-program dispatch counters
     (`nanodiloco_device_seconds_total{program=...}`) and per-class
     cost counters must be live over the wire, the summed per-request
     `timing` attribution must reconcile with the scraped per-class
     counter family, and `report dashboard` must render the offline
     HTML artifact from the collector's series JSONL.

  14. a CHAOS drill (`chaos`): a 3-replica in-process serve fleet with
     every byte crossing ``ChaosProxy`` wires on a deterministic fault
     plan — a blackholed first pick forces a hedge win, a sub-hedge
     ``timeout_s`` forces an honest deadline 504, blackhole aborts and
     an error_500 burst trip two breakers, and the fleet still answers
     200 through the last healthy replica with zero ejections; every
     surviving greedy stream bit-matches solo ``generate()``.

Usage (each phase also runs alone):
    python scripts/chip_agenda.py               # everything
    python scripts/chip_agenda.py bench sweep   # named phases
Results append to ``perf_chip_agenda.jsonl`` (git-ignored); the profile
lands under ``runs/profile-mid/``. Every phase bounds itself and exits
cleanly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# NANODILOCO_AGENDA_OUT moves ONLY the JSONL (tests point it at a tmp
# dir); bench's cwd, bench_baseline.json, and the profile trace dir stay
# anchored to the repo regardless
OUT = os.environ.get(
    "NANODILOCO_AGENDA_OUT", os.path.join(REPO_ROOT, "perf_chip_agenda.jsonl")
)


def record(rec: dict) -> None:
    rec = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), **rec}
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def probe_status() -> int:
    """Liveness contract (0 = live accelerator, 2 = timed out or
    CPU-only, 1 = probe broke): delegates to
    ``nanodiloco_tpu.utils.probe_backend`` — jitted-matmul probe child,
    SIGINT→SIGTERM→SIGKILL escalation. ``require_accelerator``: the agenda is only
    meaningful on the chip; ``strip_jax_platforms``: a cpu-pinned shell
    must read as not-live, never as something to silently measure."""
    from nanodiloco_tpu.utils import probe_backend

    code, _ = probe_backend(
        probe_timeout=150, require_accelerator=True,
        strip_jax_platforms=True,
    )
    return code


def chip_is_live() -> bool:
    return probe_status() == 0


def phase_bench() -> None:
    """Headline bench in a child (it must claim the chip itself), with
    the decode entry; refresh bench_baseline.json on a real-chip win."""
    env = {
        **os.environ,
        "BENCH_DECODE": "1",
        # round-4 additions: the MoE workload and the streaming-vs-
        # classic comparison ride the same chip sitting
        "BENCH_MOE": "1",
        "BENCH_STREAMING": "1",
    }
    proc = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True, env=env,
        cwd=REPO_ROOT,
    )
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    try:
        result = json.loads(line)
    except Exception:
        record({"phase": "bench", "error": (proc.stderr or proc.stdout)[-400:]})
        # exit nonzero so the parent records 'crashed', NOT 'done': a
        # --resume retry must re-attempt the headline bench — marking a
        # benchless run 'done' would skip it for the whole watch session
        raise SystemExit(1)
    record({"phase": "bench", **result})
    base_path = os.path.join(REPO_ROOT, "bench_baseline.json")
    prev = None
    if os.path.exists(base_path):
        with open(base_path) as f:
            prev = json.load(f).get("tokens_per_sec_per_chip")
    if (
        result.get("backend") == "tpu"
        and "degraded" not in result
        # only a WIN refreshes: a noisy/regressed run must not lower the
        # bar and mask itself from every later vs_baseline
        and (prev is None or result["value"] >= prev)
    ):
        with open(base_path, "w") as f:
            json.dump(
                {
                    "tokens_per_sec_per_chip": result["value"],
                    "recorded": f"chip_agenda {time.strftime('%Y-%m-%d')}, "
                    f"{result.get('device_kind')}",
                    "note": "self-measured; reference publishes no numbers "
                    "(BASELINE.md)",
                },
                f, indent=1,
            )
        record({"phase": "bench", "baseline_refreshed": result["value"]})


def phase_sweep() -> None:
    """Mid-model long-context sweep: tokens/s and MFU per (seq, attn).
    Batch shrinks as seq grows to hold tokens/step (and HBM) roughly
    constant. flash at block defaults; a winning flash config is the
    evidence for flipping attention_impl defaults (VERDICT r2 item 2)."""
    import bench
    from nanodiloco_tpu.models import LlamaConfig

    peak, kind = bench._peak_tflops()
    for seq in (1024, 2048, 4096, 8192):
        batch = max(1, 8192 // seq)
        for attn in ("dense", "flash"):
            cfg = LlamaConfig(
                vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                num_hidden_layers=6, num_attention_heads=16,
                num_key_value_heads=8, max_position_embeddings=seq,
                dtype="bfloat16", remat=True, loss_chunk=512,
                attention_impl=attn,
            )
            try:
                r = bench.run_workload(
                    cfg, n_dev=1, grad_accum=1, inner_steps=4, rounds=4,
                    batch=batch, seq=seq, peak_tflops=peak,
                    measure_sync=False,
                )
                record({
                    "phase": "sweep", "seq": seq, "batch": batch,
                    "attention": attn, "device_kind": kind, **r,
                })
            except Exception as e:  # OOM at some config is itself a datum
                record({
                    "phase": "sweep", "seq": seq, "batch": batch,
                    "attention": attn, "error": f"{type(e).__name__}: {e}"[:300],
                })


def phase_profile() -> None:
    """jax.profiler trace of steady-state mid-model steps (the missing
    explanation for the remaining ~60% of MFU, VERDICT r2 weak #2)."""
    import jax

    import bench
    from nanodiloco_tpu.models import LlamaConfig

    peak, _ = bench._peak_tflops()
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=6, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, dtype="bfloat16", remat=True,
        loss_chunk=512,
    )
    trace_dir = os.path.join(REPO_ROOT, "runs", "profile-mid")
    os.makedirs(trace_dir, exist_ok=True)
    # warm once outside the trace, then capture a short timed window
    bench.run_workload(
        cfg, n_dev=1, grad_accum=1, inner_steps=2, rounds=1, batch=8,
        seq=1024, peak_tflops=peak, measure_sync=False,
    )
    with jax.profiler.trace(trace_dir):
        r = bench.run_workload(
            cfg, n_dev=1, grad_accum=1, inner_steps=2, rounds=2, batch=8,
            seq=1024, peak_tflops=peak, measure_sync=False,
        )
    record({"phase": "profile", "trace_dir": trace_dir, **r})


def phase_pallas() -> None:
    """Pallas flash-attention tile sweep on the mid model (VERDICT r3
    item 2: the 128x128 default has no measurement behind it). Each
    (block_q, block_k) point re-runs the workload with the env knobs
    set; run_workload builds a fresh Diloco per call, so the knobs are
    re-read at trace time. Records tokens/s per tile; the winner is the
    evidence for changing the flash_attention defaults."""
    import bench
    from nanodiloco_tpu.models import LlamaConfig

    peak, kind = bench._peak_tflops()
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=6, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=4096, dtype="bfloat16", remat=True,
        loss_chunk=512, attention_impl="flash",
    )
    keys = ("NANODILOCO_PALLAS_BLOCK_Q", "NANODILOCO_PALLAS_BLOCK_K")
    saved = {k: os.environ.get(k) for k in keys}
    try:
        for bq, bk in ((128, 128), (128, 256), (256, 128), (256, 256),
                       (128, 512), (512, 128), (512, 512)):
            os.environ["NANODILOCO_PALLAS_BLOCK_Q"] = str(bq)
            os.environ["NANODILOCO_PALLAS_BLOCK_K"] = str(bk)
            try:
                r = bench.run_workload(
                    cfg, n_dev=1, grad_accum=1, inner_steps=4, rounds=3,
                    batch=2, seq=4096, peak_tflops=peak, measure_sync=False,
                )
                record({
                    "phase": "pallas", "block_q": bq, "block_k": bk,
                    "device_kind": kind, **r,
                })
            except Exception as e:  # a tile that doesn't fit VMEM is a datum
                record({
                    "phase": "pallas", "block_q": bq, "block_k": bk,
                    "error": f"{type(e).__name__}: {e}"[:300],
                })
    finally:
        # restore whatever the operator had exported — later phases in
        # this process (and phase subprocesses via **os.environ) must see
        # the operator's tuning, not this sweep's last point
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def phase_telemetry() -> None:
    """Drive the live telemetry endpoint against a REAL (short) training
    run on this backend: launch the CLI with --metrics-port, scrape
    /healthz and /metrics over the wire while it trains, and record the
    scraped gauges in the agenda ledger — proof the production scrape
    path (server thread + logger mirror + watchdog health) works on the
    chip, not just under the CPU test harness."""
    import socket
    import tempfile
    import urllib.error
    import urllib.request

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    tmp = tempfile.mkdtemp(prefix="nanodiloco-telemetry-")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        # small-but-real shapes; the scrape window spans compile
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "6", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm", "--quiet",
         "--metrics-port", str(port), "--log-dir", tmp,
         "--run-name", "telemetry-probe"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        # HTTPError IS the response for a 503 healthz — the most
        # interesting datum this phase can record; only a refused/
        # timed-out connection means "server not up yet"
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    scraped, healthz = None, None
    deadline = time.time() + float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_TELEMETRY", "900")
    ) - 60
    while time.time() < deadline and proc.poll() is None:
        try:
            if healthz is None:
                healthz = get("/healthz")[0]
            m = parse_metrics_text(get("/metrics")[1])
        except OSError:
            time.sleep(0.2)
            continue
        if "nanodiloco_loss" in m:
            scraped = m
            break
        time.sleep(0.1)
    out, _ = proc.communicate()
    if proc.returncode != 0:
        record({"phase": "telemetry", "error": out[-400:]})
        raise SystemExit(1)
    if scraped is None:
        record({"phase": "telemetry",
                "error": "run finished before /metrics showed a loss"})
        raise SystemExit(1)
    record({
        "phase": "telemetry",
        "healthz": healthz,
        "scraped": {
            k: scraped[k] for k in (
                "nanodiloco_loss", "nanodiloco_step",
                "nanodiloco_tokens_per_sec", "nanodiloco_alarms_total",
                "nanodiloco_outer_syncs_total", "nanodiloco_wire_bytes_total",
                "nanodiloco_flops_per_token",
                "nanodiloco_drift_max", "nanodiloco_outer_update_cos",
                'nanodiloco_worker_pg_norm{worker="0"}',
            ) if k in scraped
        },
    })


def phase_async_overlap() -> None:
    """Async delayed-apply outer step on the real backend: a short
    2-worker --async-outer run (5 rounds, delay 1) with the telemetry
    endpoint live. Asserts the two things the CPU tests cannot prove
    against this backend's real dispatch: the sync JSONL records an
    ``outer_staleness`` >= 1 apply (the merge really landed a round
    late), and the staleness/drift gauges scrape over the wire while
    the delayed path trains. Falls back to a 2-device virtual CPU mesh
    (recorded as degraded) when the backend exposes a single device —
    the 2-worker shape is the point, not the chip count."""
    import socket
    import tempfile
    import urllib.error
    import urllib.request

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    tmp = tempfile.mkdtemp(prefix="nanodiloco-async-")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)

    def launch(extra):
        return subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu",
             "--num-workers", "2", "--async-outer", "--outer-delay", "1",
             "--total-steps", "10", "--inner-steps", "2",
             "--batch-size", "8", "--per-device-batch-size", "4",
             "--seq-length", "256", "--warmup-steps", "2",
             "--llama-config-file", model_cfg, "--no-measure-comm",
             "--quiet", "--metrics-port", str(port), "--log-dir", tmp,
             "--run-name", "async-probe", *extra],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )

    def get(path):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    degraded = False
    proc = launch([])
    deadline = time.time() + float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_ASYNC_OVERLAP", "900")
    ) - 90
    scraped = None
    while time.time() < deadline:
        if proc.poll() is not None:
            break
        try:
            m = parse_metrics_text(get("/metrics")[1])
        except OSError:
            time.sleep(0.2)
            continue
        if "nanodiloco_outer_staleness" in m:
            scraped = m  # the gauge the delayed path exists to emit
            break
        time.sleep(0.1)
    out, _ = proc.communicate()
    if proc.returncode not in (0, None) and "devices" in out and not degraded:
        # single-device backend: the diloco=2 mesh cannot build — rerun
        # on the 2-device virtual CPU mesh so the 2-worker async shape
        # is still proven end to end (recorded honestly as degraded)
        degraded = True
        proc = launch(["--force-cpu-devices", "2"])
        out, _ = proc.communicate()
    if proc.returncode != 0:
        record({"phase": "async_overlap", "error": out[-400:]})
        raise SystemExit(1)
    jsonl = os.path.join(tmp, "async-probe.jsonl")
    stale = []
    with open(jsonl) as f:
        for line in f:
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue
            if r.get("outer_staleness") is not None:
                stale.append((r.get("step"), r["outer_staleness"]))
    if not any(s >= 1 for _, s in stale):
        record({"phase": "async_overlap",
                "error": f"no outer_staleness >= 1 in the sync JSONL "
                         f"(got {stale})"})
        raise SystemExit(1)
    rec = {
        "phase": "async_overlap",
        "outer_staleness_records": stale,
        "rounds": 5, "outer_delay": 1, "workers": 2,
    }
    if degraded:
        rec["degraded"] = "single-device backend; 2-device virtual cpu mesh"
    if scraped is not None:
        rec["scraped"] = {
            k: scraped[k] for k in (
                "nanodiloco_outer_staleness", "nanodiloco_drift_max",
                "nanodiloco_outer_update_cos", "nanodiloco_loss",
                "nanodiloco_step",
            ) if k in scraped
        }
    else:
        # the run can finish between scrapes on a fast backend; the
        # JSONL assert above already proved the delayed path — say so
        # rather than fake a gauge
        rec["scraped"] = None
    record(rec)


def phase_live_profile() -> None:
    """On-demand profiling against a LIVE training run on this backend:
    launch the CLI with --metrics-port, POST /debug/profile?seconds=N
    to it mid-run, and assert the returned jax.profiler artifact
    actually exists on disk — the capture path an operator reaches for
    when a job misbehaves, proven end to end (startup --profile-dir
    cannot do this: it profiles a healthy launch, not the live process
    you need to inspect)."""
    import socket
    import tempfile

    from nanodiloco_tpu.serve.client import http_get, http_post_json

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    tmp = tempfile.mkdtemp(prefix="nanodiloco-live-profile-")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu",
         # long-lived on purpose: the capture must land on a RUNNING
         # process (the finally SIGTERMs it once the evidence is in;
         # a short run racing the POST drops the connection mid-capture)
         "--total-steps", "4000", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--metrics-port", str(port), "--log-dir", tmp,
         "--run-name", "live-profile-probe"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_LIVE_PROFILE", "900")
    )
    captured = None
    try:
        deadline = time.time() + budget - 120
        while time.time() < deadline and proc.poll() is None:
            try:
                if http_get(f"http://127.0.0.1:{port}/healthz",
                            timeout=5)[0] != 200:
                    time.sleep(0.3)
                    continue
                code, out = http_post_json(
                    f"http://127.0.0.1:{port}/debug/profile?seconds=2",
                    {}, timeout=120,
                )
            except OSError:  # server not up / racing teardown: retry
                time.sleep(0.3)
                continue
            if code == 200:
                captured = out
                break
            time.sleep(0.5)  # 409: startup --profile-dir window, retry
    finally:
        if proc.poll() is None:
            import signal as _signal

            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
    if captured is None:
        record({"phase": "live_profile",
                "error": "run ended before a capture succeeded"})
        raise SystemExit(1)
    trace_dir = captured["trace_dir"]
    artifacts = [
        os.path.join(dp, fn)
        for dp, _dn, fns in os.walk(trace_dir) for fn in fns
    ]
    if not artifacts:
        record({"phase": "live_profile",
                "error": f"capture returned {trace_dir} but no artifact "
                         "files exist under it"})
        raise SystemExit(1)
    record({
        "phase": "live_profile",
        "trace_dir": trace_dir,
        "seconds": captured["seconds"],
        "artifact_files": len(artifacts),
        "artifact_bytes": sum(os.path.getsize(a) for a in artifacts),
    })


def phase_resilience() -> None:
    """The preemption drill against a REAL (short) training run on this
    backend: SIGTERM the live CLI mid-round, assert a clean preemption
    checkpoint lands with the distinct preempt exit code, then run
    `supervise` over the same flags and assert it resumes from that
    checkpoint (no restart budget consumed) and completes within one
    round of where the preempt left off."""
    import signal
    import tempfile

    from nanodiloco_tpu.resilience.supervisor import (
        PREEMPT_EXIT_CODE,
        latest_checkpoint_step,
    )

    tmp = tempfile.mkdtemp(prefix="nanodiloco-resilience-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    inner = 2
    args = [
        "--total-steps", "40", "--inner-steps", str(inner),
        "--batch-size", "8", "--per-device-batch-size", "4",
        "--seq-length", "256", "--warmup-steps", "2",
        "--llama-config-file", model_cfg, "--no-measure-comm",
        "--no-cost-analysis", "--quiet",
        "--checkpoint-dir", ckpt, "--log-dir", tmp,
        "--run-name", "resilience-probe",
    ]
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", *args],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    jsonl = os.path.join(tmp, "resilience-probe.jsonl")
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_RESILIENCE", "1200")
    )
    deadline = time.time() + budget * 0.4
    # preempt once the run is demonstrably live (a metric line exists)
    while time.time() < deadline and proc.poll() is None:
        if os.path.exists(jsonl) and os.path.getsize(jsonl) > 0:
            break
        time.sleep(0.2)
    if proc.poll() is not None:
        record({"phase": "resilience",
                "error": proc.communicate()[0][-400:]})
        raise SystemExit(1)
    proc.send_signal(signal.SIGTERM)
    t0 = time.time()
    out, _ = proc.communicate()
    preempt_s = time.time() - t0
    step = latest_checkpoint_step(ckpt)
    if proc.returncode != PREEMPT_EXIT_CODE or step is None or step % inner:
        record({
            "phase": "resilience",
            "error": f"preempt exit {proc.returncode} (want "
                     f"{PREEMPT_EXIT_CODE}), checkpoint step {step}",
            "tail": out[-400:],
        })
        raise SystemExit(1)
    sup = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "supervise",
         "--max-restarts", "1", "--checkpoint-dir", ckpt, "--", *args],
        cwd=REPO_ROOT, capture_output=True, text=True,
        timeout=budget * 0.5,
    )
    if sup.returncode != 0:
        record({"phase": "resilience",
                "error": f"supervised resume exit {sup.returncode}",
                "tail": (sup.stdout or "")[-400:]})
        raise SystemExit(1)
    # the resume record proves the supervised run continued from the
    # preempt checkpoint instead of restarting at step 0
    resumed_from = None
    with open(jsonl) as f:
        for ln in f:
            try:
                r = json.loads(ln)
            except ValueError:
                continue
            if "resume" in r:
                resumed_from = r["resume"]
    record({
        "phase": "resilience",
        "preempt_exit_code": proc.returncode,
        "preempt_checkpoint_step": step,
        "preempt_latency_s": round(preempt_s, 2),
        "resumed_from_step": resumed_from,
        "final_checkpoint_step": latest_checkpoint_step(ckpt),
        "supervised_exit_code": sup.returncode,
    })


def phase_goodput() -> None:
    """The goodput/black-box drill against a REAL (short) supervised run
    on this backend: inject a hard crash (os._exit) mid-run via the
    fault plan, let `supervise` restart it to completion, then assert
    the three contracts — a flight-recorder blackbox dump exists and
    `report blackbox` renders it, the supervisor's crash event carries
    the dump path, and the stitched goodput ledger (`report goodput`)
    accounts restart_downtime > 0 with a sane fraction."""
    import tempfile

    tmp = tempfile.mkdtemp(prefix="nanodiloco-goodput-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    plan = os.path.join(tmp, "plan.json")
    with open(plan, "w") as f:
        # crash AFTER the first checkpointed round so the restart
        # resumes (progress advanced -> budget cost 1) and both
        # lifetimes contribute goodput snapshots
        json.dump({"faults": [{"kind": "crash", "step": 5}]}, f)
    events_jsonl = os.path.join(tmp, "supervise.jsonl")
    args = [
        "--total-steps", "12", "--inner-steps", "2",
        "--batch-size", "8", "--per-device-batch-size", "4",
        "--seq-length", "256", "--warmup-steps", "2",
        "--llama-config-file", model_cfg, "--no-measure-comm",
        "--no-cost-analysis", "--quiet",
        "--checkpoint-dir", ckpt, "--log-dir", tmp,
        "--run-name", "goodput-probe", "--fault-plan", plan,
    ]
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_GOODPUT", "1200"))
    sup = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "supervise",
         "--max-restarts", "3", "--backoff-base", "0.5",
         "--events-jsonl", events_jsonl, "--", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.8,
    )
    if sup.returncode != 0:
        record({"phase": "goodput",
                "error": f"supervised run exit {sup.returncode}",
                "tail": (sup.stdout or "")[-400:]})
        raise SystemExit(1)
    blackbox = os.path.join(tmp, "goodput-probe-blackbox.json")
    sup_events = []
    with open(events_jsonl) as f:
        for ln in f:
            try:
                sup_events.append(json.loads(ln))
            except ValueError:
                continue
    crash_events = [e for e in sup_events if e.get("event") == "crash"]
    if not os.path.exists(blackbox) or not crash_events \
            or crash_events[0].get("blackbox") != blackbox:
        record({"phase": "goodput",
                "error": "blackbox dump missing or not attached to the "
                         "supervisor's crash event",
                "dump_exists": os.path.exists(blackbox),
                "crash_events": crash_events[-2:]})
        raise SystemExit(1)
    # the dump must RENDER (a torn/garbled dump is forensics lost)
    bb = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "blackbox",
         blackbox], cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    if bb.returncode != 0 or "reason=crash_fault" not in bb.stdout:
        record({"phase": "goodput", "error": "report blackbox failed",
                "tail": (bb.stdout + bb.stderr)[-400:]})
        raise SystemExit(1)
    gp = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "goodput",
         os.path.join(tmp, "goodput-probe.jsonl"), "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    ledger = json.loads(gp.stdout) if gp.returncode == 0 else {}
    if (
        gp.returncode != 0
        or ledger.get("lifetimes", 0) < 2
        or not ledger.get("restart_downtime_s", 0) > 0
        or not 0 < (ledger.get("goodput_fraction") or 0) <= 1
    ):
        record({"phase": "goodput",
                "error": "stitched ledger missing restart downtime",
                "ledger": ledger, "tail": (gp.stderr or "")[-300:]})
        raise SystemExit(1)
    record({
        "phase": "goodput",
        "lifetimes": ledger["lifetimes"],
        "goodput_fraction": ledger["goodput_fraction"],
        "restart_downtime_s": ledger["restart_downtime_s"],
        "badput_top_cause": ledger.get("badput_top_cause"),
        "blackbox_events": len(json.load(open(blackbox)).get("events", [])),
        "crash_blackbox_attached": True,
    })


def phase_elastic() -> None:
    """The elastic-DiLoCo drill against a REAL (short) supervised run on
    this backend: a 2-worker run whose injected `resize` fault writes 4
    into the supervisor's workers.target control file and preempt-exits
    at a round boundary; the supervisor emits a scale_up and relaunches
    wide (restore_elastic seeds the join replicas from the snapshot);
    an injected `straggler` fault is then demoted into weighted-merge
    rounds with unequal realized H and restored, with the wait
    attributed as straggler_wait in the stitched goodput ledger. What
    CPU pins is the control-plane math; this phase confirms the same
    path end to end on the chip's wall clock."""
    import tempfile

    tmp = tempfile.mkdtemp(prefix="nanodiloco-elastic-")
    ckpt = os.path.join(tmp, "ckpt")
    target = os.path.join(tmp, "workers.target")
    events_jsonl = os.path.join(tmp, "supervise.jsonl")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    plan = os.path.join(tmp, "plan.json")
    with open(plan, "w") as f:
        # resize at step 4 (round 2 of H=2): control-file scale-up 2->4
        # at the boundary; straggler at step 13 (two rounds after the
        # wide resume's compile rounds) for one round
        json.dump({"faults": [
            {"kind": "resize", "step": 4, "workers": 4},
            {"kind": "straggler", "step": 13, "worker": 1,
             "seconds": 3.0, "rounds": 1},
        ]}, f)
    args = [
        "--total-steps", "20", "--inner-steps", "2",
        "--batch-size", "8", "--per-device-batch-size", "4",
        "--seq-length", "256", "--warmup-steps", "2",
        "--llama-config-file", model_cfg, "--no-measure-comm",
        "--no-cost-analysis", "--quiet",
        "--num-workers", "2", "--straggler-factor", "2.0",
        "--checkpoint-dir", ckpt, "--log-dir", tmp,
        "--run-name", "elastic-probe", "--fault-plan", plan,
        # the widened run needs a 4-way diloco mesh: real devices on the
        # chip; a virtual mesh when this phase is drive-verified with a
        # CPU-pinned environment (the control-plane math is identical)
        *(["--force-cpu-devices", "8"]
          if os.environ.get("JAX_PLATFORMS") == "cpu" else []),
    ]
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_ELASTIC", "1200"))
    sup = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "supervise",
         "--max-restarts", "3", "--max-workers", "4",
         "--workers-target-file", target,
         "--events-jsonl", events_jsonl, "--", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.8,
    )
    if sup.returncode != 0:
        record({"phase": "elastic",
                "error": f"supervised run exit {sup.returncode}",
                "tail": (sup.stdout or "")[-400:]})
        raise SystemExit(1)
    sup_events = []
    with open(events_jsonl) as f:
        for ln in f:
            try:
                sup_events.append(json.loads(ln))
            except ValueError:
                continue
    ups = [e for e in sup_events if e.get("event") == "scale_up"]
    lines = []
    with open(os.path.join(tmp, "elastic-probe.jsonl")) as f:
        for ln in f:
            try:
                lines.append(json.loads(ln))
            except ValueError:
                continue
    demotions = [l for l in lines if l.get("elastic") == "straggler_demote"]
    widens = [l for l in lines if l.get("elastic") == "resize_widen"]
    realized = [tuple(l["inner_steps_realized"]) for l in lines
                if l.get("inner_steps_realized")]
    weighted_rounds = sum(1 for r in realized if len(set(r)) > 1)
    post_join_drift = [l.get("drift_max") for l in lines
                       if l.get("outer_synced") and l.get("step", 0) > 4
                       and l.get("drift_max") is not None]
    gp = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "goodput",
         os.path.join(tmp, "elastic-probe.jsonl"), "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    ledger = json.loads(gp.stdout) if gp.returncode == 0 else {}
    ok = (
        bool(ups) and ups[0].get("workers_from") == 2
        and ups[0].get("workers_to") == 4
        and bool(widens) and bool(demotions)
        and weighted_rounds >= 1
        and bool(post_join_drift)
        and (ledger.get("straggler_wait_s") or 0) > 0
    )
    if not ok:
        record({"phase": "elastic",
                "error": "elastic contract not met",
                "scale_up_events": ups[-2:],
                "widen_records": widens[-2:],
                "demotions": demotions[-2:],
                "weighted_rounds": weighted_rounds,
                "ledger": ledger})
        raise SystemExit(1)
    record({
        "phase": "elastic",
        "scale_up": [ups[0]["workers_from"], ups[0]["workers_to"]],
        "join_resume_step": widens[0].get("step"),
        "first_post_join_drift_max": post_join_drift[0],
        "straggler_demotions": len(demotions),
        "weighted_merge_rounds": weighted_rounds,
        "straggler_wait_s": ledger.get("straggler_wait_s"),
        "goodput_fraction": ledger.get("goodput_fraction"),
        "lifetimes": ledger.get("lifetimes"),
    })


def phase_serve() -> None:
    """The serving path on this backend end to end: train a tiny REAL
    checkpoint, launch the `serve` CLI on it, drive TWO overlapping
    requests over a real socket from concurrent clients, and scrape the
    serve gauges off /metrics into the agenda ledger (same contract as
    the telemetry phase: the production scrape path, proven on the
    chip, not just under the CPU test harness)."""
    import socket
    import tempfile
    import threading

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    tmp = tempfile.mkdtemp(prefix="nanodiloco-serve-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_SERVE", "900"))
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "serve-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.5,
    )
    if train.returncode != 0:
        record({"phase": "serve",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt, "--port", str(port),
         "--host", "127.0.0.1", "--slots", "2", "--max-len", "128",
         "--max-new-tokens-cap", "64"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        return http_get(f"http://127.0.0.1:{port}{path}", timeout=5)

    def post(doc, timeout=120):
        return http_post_json(
            f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
        )

    try:
        deadline = time.time() + budget * 0.4
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = get("/healthz")[0] == 200
            except OSError:
                up = False
            if up:  # keep polling through transient startup 503s
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "serve", "error":
                    "server never answered /healthz"})
            raise SystemExit(1)
        # two OVERLAPPING requests: both in flight at once, both batched
        # into the same decode ticks
        results = {}

        def client(i):
            results[i] = post({
                "prompt": "The quick brown fox" if i == 0 else "Once upon",
                "max_new_tokens": 24, "temperature": 0.8, "top_k": 20,
                "seed": i, "stop": False,
            })

        threads = [threading.Thread(target=client, args=(i,))
                   for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=budget * 0.3)
        bad = {i: r for i, r in results.items() if r[0] != 200}
        if len(results) < 2 or bad:
            record({"phase": "serve",
                    "error": f"requests failed: {bad or 'client hung'}"})
            raise SystemExit(1)
        m = parse_metrics_text(get("/metrics")[1])
        record({
            "phase": "serve",
            "completion_tokens": [
                results[i][1]["completion_tokens"] for i in (0, 1)
            ],
            "ttft_s": [
                round(results[i][1]["timing"]["ttft_s"], 3) for i in (0, 1)
            ],
            "scraped": {
                k: m[k] for k in (
                    "nanodiloco_serve_requests_total",
                    'nanodiloco_serve_requests_total{outcome="served"}',
                    "nanodiloco_serve_tokens_total",
                    "nanodiloco_serve_slots_total",
                    "nanodiloco_serve_decode_tokens_per_sec",
                    "nanodiloco_serve_ttft_p50_seconds",
                ) if k in m
            },
        })
    finally:
        import signal as _signal

        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


def phase_serve_interference() -> None:
    """Chunked-prefill interference drill on this backend: launch the
    `serve` CLI (chunked prefill + prefix cache on), submit ONE long
    prompt and, while it is mid-prefill, concurrent short streams —
    short-stream TTFT must stay under an absolute ceiling (a
    short-vs-long comparison is deliberately NOT asserted: on a fast
    backend the long prefill can finish before the shorts arrive, and
    both TTFTs are recorded in the ledger for inspection), the shorts
    share a primed prefix so the cache takes hits, the long prompt
    provably went through in chunks, and the new gauges (prefill
    chunks, prefix-cache counters, per-priority queue wait) are
    scraped off /metrics into the ledger."""
    import socket
    import tempfile
    import threading

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    tmp = tempfile.mkdtemp(prefix="nanodiloco-serve-intf-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_SERVE_INTERFERENCE", "900")
    )
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "serve-intf-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.4,
    )
    if train.returncode != 0:
        record({"phase": "serve_interference",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt, "--port", str(port),
         "--host", "127.0.0.1", "--slots", "4", "--max-len", "256",
         "--max-new-tokens-cap", "64", "--chunk-size", "16",
         "--prefix-cache-tokens", "1024"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        return http_get(f"http://127.0.0.1:{port}{path}", timeout=5)

    def post(doc, timeout=300):
        return http_post_json(
            f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
        )

    try:
        deadline = time.time() + budget * 0.3
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = get("/healthz")[0] == 200
            except OSError:
                up = False
            if up:
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "serve_interference",
                    "error": "server never answered /healthz"})
            raise SystemExit(1)
        # warm the compile set (chunk buckets for BOTH request shapes +
        # decode) outside the measured window, then fire the pattern
        for warm in (
            {"token_ids": list(range(2, 202)), "max_new_tokens": 2,
             "stop": False, "prefix_cache": False},
            {"token_ids": list(range(2, 20)), "max_new_tokens": 2,
             "stop": False, "prefix_cache": False},
        ):
            code, out = post(warm)
            if code != 200:
                record({"phase": "serve_interference",
                        "error": f"warmup failed {code}: {out.get('error')}"})
                raise SystemExit(1)
        shared = [int(t) for t in range(100, 116)]  # one 16-token chunk
        # prime the shared prefix: lookups happen at ADMISSION, so the
        # burst below only hits if an earlier completed prefill cached
        # the chunk (exactly the system-prompt pattern: first request
        # pays, the fleet reuses)
        code, out = post({"token_ids": shared + [3, 4],
                          "max_new_tokens": 2, "stop": False, "seed": 99})
        if code != 200:
            record({"phase": "serve_interference",
                    "error": f"prefix prime failed {code}: {out.get('error')}"})
            raise SystemExit(1)
        results: dict[str, tuple] = {}

        def fire(name, doc):
            results[name] = post(doc)

        # token ids stay under 256: the trained checkpoint's vocab snaps
        # to the tokenizer's size, smaller than the config file's
        long_doc = {"token_ids": [(i * 11 + 5) % 256 for i in range(200)],
                    "max_new_tokens": 16, "stop": False,
                    "prefix_cache": False, "seed": 1}
        shorts = {
            f"short{i}": {"token_ids": shared + [7 + i, 9 + i],
                          "max_new_tokens": 8, "stop": False,
                          "priority": 0, "seed": 10 + i}
            for i in range(3)
        }
        t_long = threading.Thread(target=fire, args=("long", long_doc))
        t_long.start()
        time.sleep(0.02)  # the long admission goes first; shorts land
        t_shorts = [threading.Thread(target=fire, args=(n, d))
                    for n, d in shorts.items()]
        for t in t_shorts:
            t.start()
        for t in [t_long, *t_shorts]:
            t.join(timeout=budget * 0.3)
        bad = {n: r for n, r in results.items() if r[0] != 200}
        if len(results) < 4 or bad:
            record({"phase": "serve_interference",
                    "error": f"requests failed: {bad or 'client hung'}"})
            raise SystemExit(1)
        long_ttft = results["long"][1]["timing"]["ttft_s"]
        short_ttfts = [results[n][1]["timing"]["ttft_s"] for n in shorts]
        bound = float(
            os.environ.get("NANODILOCO_AGENDA_SHORT_TTFT_BOUND_S", "10")
        )
        m = parse_metrics_text(get("/metrics")[1])
        chunks = m.get("nanodiloco_serve_prefill_chunks_total", 0)
        hits = m.get(
            'nanodiloco_serve_prefix_cache_lookups_total{result="hit"}', 0
        )
        # the contract: short first tokens stay bounded while the long
        # prompt is fed through in chunks (>= 13 for 200 tokens at
        # chunk 16 — whole-prompt prefill would show far fewer), and
        # the shared 16-token prefix was reused, not recomputed
        if max(short_ttfts) > bound or chunks < 13 or hits < 2:
            record({"phase": "serve_interference",
                    "error": "short-stream TTFT not bounded (or the "
                             "engine did not chunk/reuse prefixes)",
                    "short_ttft_s": short_ttfts,
                    "long_ttft_s": long_ttft,
                    "prefill_chunks": chunks, "prefix_hits": hits})
            raise SystemExit(1)
        record({
            "phase": "serve_interference",
            "long_ttft_s": round(long_ttft, 3),
            "short_ttft_s": [round(t, 3) for t in short_ttfts],
            "scraped": {
                k: m[k] for k in (
                    "nanodiloco_serve_prefill_chunks_total",
                    'nanodiloco_serve_prefix_cache_lookups_total{result="hit"}',
                    'nanodiloco_serve_prefix_cache_lookups_total{result="miss"}',
                    "nanodiloco_serve_prefix_cache_hit_tokens_total",
                    "nanodiloco_serve_prefix_cache_tokens",
                    'nanodiloco_serve_queue_wait_by_priority_seconds_count{priority="0"}',
                    "nanodiloco_serve_ttft_p95_seconds",
                ) if k in m
            },
        })
    finally:
        import signal as _signal

        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()


def phase_kv_paging() -> None:
    """Paged-KV serving drill on this backend: launch the `serve` CLI
    with a TINY block pool (oversubscribed vs the dense footprint),
    drive enough concurrent + sequential requests to exercise block
    recycling and one copy-on-write shared-prefix hit, scrape the
    block-pool gauges off /metrics over the wire, then — after the
    server releases the chip — replay one fp-paged stream through solo
    ``generate()`` on the SAME backend and assert bit-parity. The CPU
    tests pin all of this too; this phase proves the block-table
    programs compile and hold parity on the real accelerator."""
    import socket
    import tempfile
    import threading

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    tmp = tempfile.mkdtemp(prefix="nanodiloco-kv-paging-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_KV_PAGING",
                                  "900"))
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "kv-paging-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.4,
    )
    if train.returncode != 0:
        record({"phase": "kv_paging",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    # 14 blocks x 16 tokens = 224 cached tokens, vs the dense footprint
    # of 4 slots x 96 = 384: the pool is the binding resource on purpose
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt, "--port", str(port),
         "--host", "127.0.0.1", "--slots", "4", "--max-len", "96",
         "--max-new-tokens-cap", "64", "--chunk-size", "16",
         "--kv-block-size", "16", "--kv-pool-blocks", "14",
         "--prefix-cache-tokens", "64"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        return http_get(f"http://127.0.0.1:{port}{path}", timeout=5)

    def post(doc, timeout=300):
        return http_post_json(
            f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
        )

    parity_doc = {
        # token ids stay under 256: the trained checkpoint's vocab
        # snaps to the tokenizer's size
        "token_ids": [(i * 13 + 3) % 256 for i in range(18)],
        "max_new_tokens": 12, "temperature": 0.8, "top_k": 20,
        "seed": 7, "stop": False, "prefix_cache": False,
    }
    try:
        deadline = time.time() + budget * 0.3
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = get("/healthz")[0] == 200
            except OSError:
                up = False
            if up:
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "kv_paging",
                    "error": "server never answered /healthz"})
            raise SystemExit(1)
        # warm both chunk buckets + decode outside the measured window
        for warm in (
            {"token_ids": list(range(2, 20)), "max_new_tokens": 2,
             "stop": False, "prefix_cache": False},
        ):
            code, out = post(warm)
            if code != 200:
                record({"phase": "kv_paging",
                        "error": f"warmup failed {code}: {out.get('error')}"})
                raise SystemExit(1)
        # prime the shared prefix (one whole 16-token chunk), then a
        # concurrent burst that must take copy-on-write hits on it
        shared = [int(t) for t in range(100, 116)]
        code, out = post({"token_ids": shared + [3, 4],
                          "max_new_tokens": 2, "stop": False, "seed": 99})
        if code != 200:
            record({"phase": "kv_paging",
                    "error": f"prefix prime failed {code}: {out.get('error')}"})
            raise SystemExit(1)
        results: dict[str, tuple] = {}

        def fire(name, doc):
            results[name] = post(doc)

        burst = {
            f"cow{i}": {"token_ids": shared + [7 + i, 9 + i],
                        "max_new_tokens": 8, "stop": False, "seed": 10 + i}
            for i in range(3)
        }
        threads = [threading.Thread(target=fire, args=(n, d))
                   for n, d in burst.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=budget * 0.2)
        # two sequential waves through the tiny pool: every wave's
        # blocks must be the previous wave's, recycled
        for w in range(4):
            fire(f"wave{w}", {
                "token_ids": [(w * 17 + i * 5 + 1) % 256 for i in range(20)],
                "max_new_tokens": 8, "stop": False,
                "prefix_cache": False, "seed": 200 + w,
            })
        fire("parity", parity_doc)
        bad = {n: r for n, r in results.items() if r[0] != 200}
        if bad or len(results) < 8:
            record({"phase": "kv_paging",
                    "error": f"requests failed: {bad or 'client hung'}"})
            raise SystemExit(1)
        m = parse_metrics_text(get("/metrics")[1])
        hits = m.get(
            'nanodiloco_serve_prefix_cache_lookups_total{result="hit"}', 0
        )
        free = m.get("nanodiloco_kv_blocks_free")
        used = m.get("nanodiloco_kv_blocks_used")
        held = m.get("nanodiloco_kv_blocks_per_request_count", 0)
        # the contract: with every request drained, the ONLY blocks
        # still held are the primed shared-prefix chunk's (one 16-token
        # chunk = 1 block) — anything more is a leak on some release
        # path; blocks were recycled (more requests completed than the
        # pool could ever hold at once); the shared prefix took CoW hits
        if (free is None or used is None or (free, used) != (13, 1)
                or held < 8 or hits < 2):
            record({"phase": "kv_paging",
                    "error": "block-pool gauges missing or inconsistent",
                    "blocks_free": free, "blocks_used": used,
                    "blocks_held_count": held, "prefix_hits": hits})
            raise SystemExit(1)
        scraped = {
            k: m[k] for k in (
                "nanodiloco_kv_blocks_free",
                "nanodiloco_kv_blocks_used",
                "nanodiloco_kv_block_evictions_total",
                "nanodiloco_kv_blocks_per_request_count",
                "nanodiloco_kv_block_size_tokens",
                'nanodiloco_serve_prefix_cache_lookups_total{result="hit"}',
                'nanodiloco_serve_admission_blocked_total{reason="no_blocks"}',
                'nanodiloco_serve_admission_blocked_total{reason="no_slot"}',
            ) if k in m
        }
        served_stream = results["parity"][1]["token_ids"]
    finally:
        import signal as _signal

        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    # bit-parity leg: the server has released the chip; replay the same
    # request through solo generate() on the same backend, same seed
    probe = subprocess.run(
        [sys.executable, "-c", (
            "import json, sys\n"
            "import jax, jax.numpy as jnp, numpy as np\n"
            "from nanodiloco_tpu.cli import _load_checkpoint_snapshot\n"
            "from nanodiloco_tpu.models import generate\n"
            "doc = json.loads(sys.argv[1])\n"
            "cfg, _sc, params = _load_checkpoint_snapshot(sys.argv[2], None)\n"
            "out = generate(params, jnp.asarray([doc['token_ids']],"
            " jnp.int32), cfg, doc['max_new_tokens'],"
            " temperature=doc['temperature'], top_k=doc['top_k'],"
            " key=jax.random.key(doc['seed']))\n"
            "print(json.dumps(np.asarray(out[0]).tolist()))\n"
        ), json.dumps(parity_doc), ckpt],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if probe.returncode != 0:
        record({"phase": "kv_paging",
                "error": f"solo generate probe failed: {probe.stdout[-200:]}"
                         f"{probe.stderr[-200:]}"})
        raise SystemExit(1)
    solo = json.loads(probe.stdout.strip().splitlines()[-1])
    if served_stream != solo:
        record({"phase": "kv_paging",
                "error": "paged-fp stream diverged from solo generate()",
                "served": served_stream, "solo": solo})
        raise SystemExit(1)
    record({
        "phase": "kv_paging",
        "paged_fp_bit_parity": True,
        "parity_tokens": len(served_stream),
        "scraped": scraped,
    })


def phase_spec_decode() -> None:
    """Speculative-decoding drill on this backend: serve a tiny trained
    checkpoint with prompt-lookup speculation enabled (--spec-k), drive
    greedy repetitive traffic (the templated shape where lookup
    accepts), assert the draft/accept counters prove REAL acceptance on
    the live backend, scrape the spec gauges off /metrics over the
    wire, then — after the server releases the chip — replay the spec
    stream through solo ``generate()`` on the SAME backend and assert
    bit-parity. The CPU tests pin the same contracts; this phase proves
    the verify programs compile, accept, and hold parity on the real
    accelerator — and its timed leg is what turns the CPU-pinned
    speedup claim into an on-chip number."""
    import socket
    import tempfile

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    tmp = tempfile.mkdtemp(prefix="nanodiloco-spec-decode-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_SPEC_DECODE",
                                  "900"))
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "spec-decode-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.4,
    )
    if train.returncode != 0:
        record({"phase": "spec_decode",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt, "--port", str(port),
         "--host", "127.0.0.1", "--slots", "2", "--max-len", "192",
         "--max-new-tokens-cap", "96", "--chunk-size", "16",
         "--spec-k", "4", "--spec-ngram", "3"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        return http_get(f"http://127.0.0.1:{port}{path}", timeout=5)

    def post(doc, timeout=300):
        return http_post_json(
            f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
        )

    # greedy + repetitive (templated pattern x3 + unique tail): the
    # traffic prompt-lookup exists for — greedy continuations
    # self-repeat, so drafts accept on the live backend
    pattern = [(i * 37 + 11) % 256 for i in range(8)]
    spec_doc = {
        "token_ids": pattern * 3 + [5, 7],
        "max_new_tokens": 64, "temperature": 0.0,
        "seed": 7, "stop": False, "prefix_cache": False,
    }
    try:
        deadline = time.time() + budget * 0.3
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = get("/healthz")[0] == 200
            except OSError:
                up = False
            if up:
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "spec_decode",
                    "error": "server never answered /healthz"})
            raise SystemExit(1)
        # warmup: compile the prefill buckets + plain decode outside
        # the assertion window (the verify buckets precompiled at boot
        # via the engine's warm_spec)
        code, out = post({"token_ids": list(range(2, 20)),
                          "max_new_tokens": 2, "stop": False,
                          "prefix_cache": False, "speculate": False})
        if code != 200:
            record({"phase": "spec_decode",
                    "error": f"warmup failed {code}: {out.get('error')}"})
            raise SystemExit(1)
        code, out = post(spec_doc)
        if code != 200:
            record({"phase": "spec_decode",
                    "error": f"spec request failed {code}: "
                             f"{out.get('error')}"})
            raise SystemExit(1)
        served_stream = out["token_ids"]
        m = parse_metrics_text(get("/metrics")[1])
        drafted = m.get("nanodiloco_spec_draft_tokens_total", 0)
        accepted = m.get("nanodiloco_spec_accepted_total", 0)
        if not drafted or not accepted:
            record({"phase": "spec_decode",
                    "error": "speculation never accepted on the live "
                             "backend (greedy repetitive stream should "
                             "self-repeat)",
                    "draft_tokens": drafted, "accepted_tokens": accepted})
            raise SystemExit(1)
        scraped = {
            k: m[k] for k in (
                "nanodiloco_spec_draft_tokens_total",
                "nanodiloco_spec_accepted_total",
                "nanodiloco_spec_rejected_total",
                "nanodiloco_spec_acceptance_rate",
                "nanodiloco_spec_tokens_per_tick_count",
                "nanodiloco_serve_decode_tokens_per_sec",
            ) if k in m
        }
    finally:
        import signal as _signal

        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    # bit-parity leg: the chip is free again; the SAME greedy request
    # through solo generate() must reproduce the speculative stream
    probe = subprocess.run(
        [sys.executable, "-c", (
            "import json, sys\n"
            "import jax, jax.numpy as jnp, numpy as np\n"
            "from nanodiloco_tpu.cli import _load_checkpoint_snapshot\n"
            "from nanodiloco_tpu.models import generate\n"
            "doc = json.loads(sys.argv[1])\n"
            "cfg, _sc, params = _load_checkpoint_snapshot(sys.argv[2], None)\n"
            "out = generate(params, jnp.asarray([doc['token_ids']],"
            " jnp.int32), cfg, doc['max_new_tokens'],"
            " temperature=doc['temperature'],"
            " key=jax.random.key(doc['seed']))\n"
            "print(json.dumps(np.asarray(out[0]).tolist()))\n"
        ), json.dumps(spec_doc), ckpt],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if probe.returncode != 0:
        record({"phase": "spec_decode",
                "error": f"solo generate probe failed: {probe.stdout[-200:]}"
                         f"{probe.stderr[-200:]}"})
        raise SystemExit(1)
    solo = json.loads(probe.stdout.strip().splitlines()[-1])
    if served_stream != solo:
        record({"phase": "spec_decode",
                "error": "speculative stream diverged from solo generate()",
                "served": served_stream, "solo": solo})
        raise SystemExit(1)
    record({
        "phase": "spec_decode",
        "spec_bit_parity": True,
        "parity_tokens": len(served_stream),
        "scraped": scraped,
    })


def phase_tp_decode() -> None:
    """Tensor-parallel serving drill on this backend: serve a tiny
    trained checkpoint with ``--tp 2`` (paged KV + speculation riding
    the sharded programs), stream greedy plain AND speculative traffic,
    scrape the new TP gauges (``nanodiloco_serve_tp_degree``, the
    per-shard ``nanodiloco_kv_blocks_free_per_shard`` family) off
    /metrics over the wire, then — after the server releases the chip —
    replay the served stream through solo ``generate(mesh=...)`` on the
    SAME tp=2 layout and assert bit-parity. On a live accelerator the
    mesh spans 2 real chips (and this sitting is what pins the
    serve-bigger-than-one-chip claim); without one the drill runs on 2
    virtual CPU devices — same programs, same parity bar, no speedup
    claim (PERF.md honest-measurement rules)."""
    import socket
    import tempfile

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    tmp = tempfile.mkdtemp(prefix="nanodiloco-tp-decode-")
    ckpt = os.path.join(tmp, "ckpt")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_TP_DECODE",
                                  "1200"))
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "tp-decode-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.4,
    )
    if train.returncode != 0:
        record({"phase": "tp_decode",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cpu_flags = [] if live else ["--force-cpu-devices", "2"]
    proc = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt, "--port", str(port),
         "--host", "127.0.0.1", "--slots", "2", "--max-len", "192",
         "--max-new-tokens-cap", "96", "--chunk-size", "16",
         "--kv-block-size", "16", "--tp", "2",
         "--spec-k", "4", "--spec-ngram", "3", *cpu_flags],
        cwd=REPO_ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    def get(path):
        return http_get(f"http://127.0.0.1:{port}{path}", timeout=5)

    def post(doc, timeout=300):
        return http_post_json(
            f"http://127.0.0.1:{port}/v1/generate", doc, timeout=timeout
        )

    # greedy plain + greedy repetitive (the spec-accepting shape): both
    # streams must replay bit-identically through the same-layout solo
    # generate() below
    pattern = [(i * 37 + 11) % 256 for i in range(8)]
    plain_doc = {
        "token_ids": [(i * 13 + 3) % 256 for i in range(18)],
        "max_new_tokens": 12, "temperature": 0.0,
        "seed": 5, "stop": False, "prefix_cache": False,
        "speculate": False,
    }
    spec_doc = {
        "token_ids": pattern * 3 + [5, 7],
        "max_new_tokens": 48, "temperature": 0.0,
        "seed": 7, "stop": False, "prefix_cache": False,
    }
    try:
        deadline = time.time() + budget * 0.3
        up = False
        while time.time() < deadline and proc.poll() is None:
            try:
                up = get("/healthz")[0] == 200
            except OSError:
                up = False
            if up:
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "tp_decode",
                    "error": "server never answered /healthz (tp=2)"})
            raise SystemExit(1)
        streams = {}
        for name, doc in (("plain", plain_doc), ("spec", spec_doc)):
            code, out = post(doc)
            if code != 200:
                record({"phase": "tp_decode",
                        "error": f"{name} request failed {code}: "
                                 f"{out.get('error')}"})
                raise SystemExit(1)
            streams[name] = out["token_ids"]
        m = parse_metrics_text(get("/metrics")[1])
        tp_deg = m.get("nanodiloco_serve_tp_degree")
        shard0 = m.get('nanodiloco_kv_blocks_free_per_shard{shard="0"}')
        shard1 = m.get('nanodiloco_kv_blocks_free_per_shard{shard="1"}')
        drafted = m.get("nanodiloco_spec_draft_tokens_total", 0)
        accepted = m.get("nanodiloco_spec_accepted_total", 0)
        if tp_deg != 2 or shard0 is None or shard0 != shard1:
            record({"phase": "tp_decode",
                    "error": "TP gauges missing or inconsistent",
                    "tp_degree": tp_deg, "shard0": shard0,
                    "shard1": shard1})
            raise SystemExit(1)
        if not drafted or not accepted:
            # the drill's point is speculation RIDING the sharded verify
            # program — zero drafts means the spec stream degraded to
            # plain ticks and the parity replay below would pass
            # vacuously (same loud check as phase_spec_decode)
            record({"phase": "tp_decode",
                    "error": "speculation never drafted/accepted on the "
                             "tp=2 mesh (greedy repetitive stream should "
                             "self-repeat)",
                    "draft_tokens": drafted, "accepted_tokens": accepted})
            raise SystemExit(1)
        scraped = {
            k: m[k] for k in (
                "nanodiloco_serve_tp_degree",
                "nanodiloco_kv_blocks_free",
                'nanodiloco_kv_blocks_free_per_shard{shard="0"}',
                'nanodiloco_kv_blocks_free_per_shard{shard="1"}',
                "nanodiloco_spec_draft_tokens_total",
                "nanodiloco_spec_accepted_total",
                "nanodiloco_serve_decode_tokens_per_sec",
            ) if k in m
        }
    finally:
        import signal as _signal

        if proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    # bit-parity leg: the chip is free again; replay BOTH streams
    # through solo generate() on the SAME tp=2 mesh layout
    probe = subprocess.run(
        [sys.executable, "-c", (
            "import json, sys\n"
            + ("" if live else
               "from nanodiloco_tpu.utils import force_virtual_cpu_devices\n"
               "force_virtual_cpu_devices(2)\n")
            + "import jax, jax.numpy as jnp, numpy as np\n"
            "from nanodiloco_tpu.cli import _load_checkpoint_snapshot\n"
            "from nanodiloco_tpu.models import generate\n"
            "from nanodiloco_tpu.parallel.mesh import MeshConfig, build_mesh\n"
            "from nanodiloco_tpu.parallel.sharding import named, param_specs\n"
            "docs = json.loads(sys.argv[1])\n"
            "cfg, _sc, params = _load_checkpoint_snapshot(sys.argv[2], None)\n"
            "mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])\n"
            # restored params are committed to device 0; a big-model run
            # device_puts them into the mesh layout before generating
            "params = jax.device_put(params, named(mesh, param_specs(cfg)))\n"
            "outs = {}\n"
            "for name, doc in docs.items():\n"
            "    out = generate(params, jnp.asarray([doc['token_ids']],"
            " jnp.int32), cfg, doc['max_new_tokens'],"
            " temperature=doc['temperature'],"
            " key=jax.random.key(doc['seed']), mesh=mesh)\n"
            "    outs[name] = np.asarray(out[0]).tolist()\n"
            "print(json.dumps(outs))\n"
        ), json.dumps({"plain": plain_doc, "spec": spec_doc}), ckpt],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if probe.returncode != 0:
        record({"phase": "tp_decode",
                "error": f"tp solo generate probe failed: "
                         f"{probe.stdout[-200:]}{probe.stderr[-200:]}"})
        raise SystemExit(1)
    solo = json.loads(probe.stdout.strip().splitlines()[-1])
    for name in ("plain", "spec"):
        if streams[name] != solo[name]:
            record({"phase": "tp_decode",
                    "error": f"tp=2 {name} stream diverged from "
                             "same-layout solo generate()",
                    "served": streams[name], "solo": solo[name]})
            raise SystemExit(1)
    record({
        "phase": "tp_decode",
        "tp_bit_parity": True,
        "backend_live": live,
        "parity_tokens": {k: len(v) for k, v in streams.items()},
        "spec_drafted_on_mesh": drafted,
        "scraped": scraped,
    })


def phase_fleet() -> None:
    """Continuous-deployment drill on this backend: train a tiny model
    (two committed checkpoints), boot a 2-replica `serve` fleet behind
    the `fleet` router CLI with the canary controller watching the
    checkpoint dir, and drive the whole train->serve loop end to end —
    the fresh checkpoint is canaried and PROMOTED fleet-wide (traffic
    through the router stays 200 throughout; a post-promote greedy
    stream is replayed through solo ``generate()`` on the promoted
    checkpoint for bit-parity), a SIGABRT'd replica is EJECTED with its
    flight-recorder black box attached to the ejection event, and a
    deliberately poisoned (NaN-snapshot) checkpoint is ROLLED BACK by
    the canary gate with the verdict in the deploy JSONL. On CPU this
    pins the control plane + correctness; fleet throughput claims
    belong to the chip sitting (PERF.md)."""
    import signal as _signal
    import socket
    import tempfile

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    tmp = tempfile.mkdtemp(prefix="nanodiloco-fleet-")
    ckpt = os.path.join(tmp, "ckpt")
    deploy_jsonl = os.path.join(tmp, "deploy.jsonl")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(os.environ.get("NANODILOCO_AGENDA_TIMEOUT_FLEET", "1800"))
    # two committed checkpoints from ONE run (steps 2 and 4): the fleet
    # boots on step 2, and step 4 is the "fresh checkpoint" the
    # controller discovers and canaries
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "4", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--checkpoint-every", "1",
         "--log-dir", tmp, "--run-name", "fleet-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if train.returncode != 0:
        record({"phase": "fleet",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = [free_port() for _ in range(3)]
    blackboxes = [os.path.join(tmp, f"r{i}-blackbox.json")
                  for i in range(2)]
    replicas = []
    for i in range(2):
        replicas.append(subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "serve",
             "--checkpoint-dir", ckpt, "--step", "2",
             "--port", str(ports[i]), "--host", "127.0.0.1",
             "--slots", "2", "--max-len", "192", "--chunk-size", "16",
             "--kv-block-size", "16", "--prefix-cache-tokens", "256",
             "--max-new-tokens-cap", "96",
             "--blackbox", blackboxes[i]],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        ))
    fleet_proc = None

    def stop(proc, sig=None):
        if proc is not None and proc.poll() is None:
            proc.send_signal(sig or _signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    def events():
        if not os.path.exists(deploy_jsonl):
            return []
        out = []
        with open(deploy_jsonl) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
        return out

    def wait_event(kind, deadline, **match):
        while time.time() < deadline:
            for e in events():
                if e.get("deploy_event") == kind and all(
                    e.get(k) == v for k, v in match.items()
                ):
                    return e
            time.sleep(0.3)
        return None

    try:
        deadline = time.time() + budget * 0.25
        for i, port in enumerate(ports[:2]):
            up = False
            while time.time() < deadline and replicas[i].poll() is None:
                try:
                    up = http_get(f"http://127.0.0.1:{port}/healthz",
                                  timeout=3)[0] == 200
                except OSError:
                    up = False
                if up:
                    break
                time.sleep(0.3)
            if not up:
                record({"phase": "fleet",
                        "error": f"replica {i} never answered /healthz"})
                raise SystemExit(1)
        fleet_port = ports[2]
        fleet_proc = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "fleet",
             "--replica", f"http://127.0.0.1:{ports[0]},{blackboxes[0]}",
             "--replica", f"http://127.0.0.1:{ports[1]},{blackboxes[1]}",
             "--port", str(fleet_port), "--host", "127.0.0.1",
             "--events-jsonl", deploy_jsonl,
             "--watch-checkpoint-dir", ckpt, "--initial-step", "2",
             "--poll-interval-s", "1", "--health-interval-s", "0.3",
             "--drain-timeout-s", "15",
             "--canary-clients", "2", "--canary-requests", "1",
             "--canary-max-new-tokens", "8"],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        url = f"http://127.0.0.1:{fleet_port}"
        # traffic through the router WHILE the canary/promote machinery
        # runs: every request must answer 200 (zero dropped), and each
        # greedy stream must bit-match solo generate() on whichever
        # checkpoint its admission generation carried (step 2 pre-swap,
        # step 4 post-swap — the replay below checks membership)
        racing_doc = {"token_ids": [(i * 13 + 3) % 256 for i in range(18)],
                      "max_new_tokens": 24, "temperature": 0.0,
                      "seed": 5, "stop": False, "prefix_cache": False}
        deadline = time.time() + budget * 0.2
        racing = None
        while racing is None and time.time() < deadline:
            try:
                code, out = http_post_json(url + "/v1/generate",
                                           racing_doc, timeout=120)
            except OSError:
                time.sleep(0.3)
                continue
            if code == 200:
                racing = out
            elif code == 503:
                time.sleep(0.3)  # router still probing replicas up
            else:
                record({"phase": "fleet",
                        "error": f"racing request failed {code}: {out}"})
                raise SystemExit(1)
        if racing is None:
            record({"phase": "fleet",
                    "error": "router never served the racing request"})
            raise SystemExit(1)
        promote = wait_event("promote", time.time() + budget * 0.25,
                             step=4)
        if promote is None:
            tail = "\n".join(json.dumps(e) for e in events()[-8:])
            record({"phase": "fleet",
                    "error": f"no promote event for step 4; tail:\n{tail}"})
            raise SystemExit(1)
        code, post_promote = http_post_json(url + "/v1/generate",
                                            racing_doc, timeout=120)
        if code != 200:
            record({"phase": "fleet",
                    "error": f"post-promote request failed {code}"})
            raise SystemExit(1)

        # bit-parity replay: solo generate() on the step-2 and step-4
        # checkpoints; the racing stream must match ONE of them exactly
        # (its admission generation decides which), the post-promote
        # stream must match step 4
        probe = subprocess.run(
            [sys.executable, "-c", (
                "import json, sys\n"
                "import jax, jax.numpy as jnp, numpy as np\n"
                "from nanodiloco_tpu.cli import _load_checkpoint_snapshot\n"
                "from nanodiloco_tpu.models import generate\n"
                "doc = json.loads(sys.argv[1])\n"
                "outs = {}\n"
                "for step in (2, 4):\n"
                "    cfg, _sc, params = _load_checkpoint_snapshot("
                "sys.argv[2], step)\n"
                "    out = generate(params, jnp.asarray([doc['token_ids']],"
                " jnp.int32), cfg, doc['max_new_tokens'],"
                " temperature=0.0, key=jax.random.key(doc['seed']))\n"
                "    outs[str(step)] = np.asarray(out[0]).tolist()\n"
                "print(json.dumps(outs))\n"
            ), json.dumps(racing_doc), ckpt],
            cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=budget * 0.2,
        )
        if probe.returncode != 0:
            record({"phase": "fleet",
                    "error": f"solo replay failed: "
                             f"{probe.stdout[-200:]}{probe.stderr[-200:]}"})
            raise SystemExit(1)
        solo = json.loads(probe.stdout.strip().splitlines()[-1])
        if racing["token_ids"] not in (solo["2"], solo["4"]):
            record({"phase": "fleet",
                    "error": "racing stream matches NEITHER checkpoint",
                    "served": racing["token_ids"]})
            raise SystemExit(1)
        if post_promote["token_ids"] != solo["4"]:
            record({"phase": "fleet",
                    "error": "post-promote stream is not the promoted "
                             "checkpoint's solo stream",
                    "served": post_promote["token_ids"],
                    "solo": solo["4"]})
            raise SystemExit(1)

        # crash injection: SIGABRT the NON-canary replica — its armed
        # fatal-signal handler dumps the black box, the router's health
        # loop sees the dead socket and ejects with the dump attached
        replicas[1].send_signal(_signal.SIGABRT)
        eject = wait_event("eject", time.time() + budget * 0.15,
                           replica="r1")
        if eject is None:
            record({"phase": "fleet", "error": "no eject event for r1"})
            raise SystemExit(1)
        if not (eject.get("blackbox") or {}).get("path"):
            record({"phase": "fleet",
                    "error": "ejection event has no blackbox attached",
                    "event": eject})
            raise SystemExit(1)
        render = subprocess.run(
            [sys.executable, "-m", "nanodiloco_tpu", "report", "blackbox",
             eject["blackbox"]["path"], "-n", "5"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
        )
        if render.returncode != 0 or "blackbox:" not in render.stdout:
            record({"phase": "fleet",
                    "error": f"report blackbox failed: "
                             f"{render.stdout[-200:]}{render.stderr[-200:]}"})
            raise SystemExit(1)

        # poisoned checkpoint: NaN LM HEAD saved as step 6 — the canary
        # gate must catch it (non-finite eval loss is an automatic
        # regression) and roll the canary back to step 4. The head ONLY,
        # deliberately: NaN logits poison the eval loss while K/V stays
        # finite — a full-NaN snapshot would write NaN rows into the
        # canary's shared KV pool during the canary bench, and NaN
        # defeats causal masking (0 x NaN = NaN) for later
        # sentinel-clamped paged reads, contaminating post-rollback
        # streams (observed on the first CPU dry-run; PERF.md fleet
        # entry).
        poison = subprocess.run(
            [sys.executable, "-c", (
                "import sys\n"
                "import numpy as np\n"
                "from nanodiloco_tpu.training.checkpoint import "
                "CheckpointManager\n"
                "m = CheckpointManager(sys.argv[1])\n"
                "state = m.restore_raw(4)\n"
                "head = np.asarray(state['snapshot']['lm_head'])\n"
                "state['snapshot']['lm_head'] = np.full(\n"
                "    head.shape, np.nan, head.dtype)\n"
                "m.save(6, state)\n"
                "m.wait()\n"
                "m.close()\n"
                "print('poisoned step 6 (NaN lm_head)')\n"
            ), ckpt],
            cwd=REPO_ROOT, capture_output=True, text=True,
            timeout=budget * 0.15,
        )
        if poison.returncode != 0:
            record({"phase": "fleet",
                    "error": f"poison save failed: "
                             f"{poison.stdout[-200:]}{poison.stderr[-300:]}"})
            raise SystemExit(1)
        rollback = wait_event("rollback", time.time() + budget * 0.2,
                              step=6)
        if rollback is None:
            tail = "\n".join(json.dumps(e) for e in events()[-8:])
            record({"phase": "fleet",
                    "error": f"no rollback event for step 6; tail:\n{tail}"})
            raise SystemExit(1)
        # post-rollback: the surviving replica serves step 4 again
        code, after = http_post_json(url + "/v1/generate", racing_doc,
                                     timeout=120)
        if code != 200 or after["token_ids"] != solo["4"]:
            record({"phase": "fleet",
                    "error": "post-rollback stream is not the restored "
                             "checkpoint's solo stream",
                    "code": code})
            raise SystemExit(1)
        m = parse_metrics_text(http_get(url + "/metrics", timeout=5)[1])
        scraped = {k: m[k] for k in (
            "nanodiloco_fleet_replicas_ready",
            "nanodiloco_fleet_replicas_serving",
            'nanodiloco_deploy_generation{replica="r0"}',
            'nanodiloco_fleet_events_total{event="promote"}',
            'nanodiloco_fleet_events_total{event="rollback"}',
            'nanodiloco_fleet_events_total{event="eject"}',
            "nanodiloco_fleet_goodput_fraction",
        ) if k in m}
        if (m.get("nanodiloco_fleet_replicas_ready") != 1
                or not m.get('nanodiloco_fleet_events_total{event="eject"}')
                or not m.get(
                    'nanodiloco_fleet_events_total{event="promote"}')):
            record({"phase": "fleet",
                    "error": "fleet gauges missing or inconsistent",
                    "scraped": scraped})
            raise SystemExit(1)
    finally:
        stop(fleet_proc)
        for proc in replicas:
            stop(proc)

    # the stopped router appended its final fleet_goodput record: the
    # deploy JSONL must summarize with the standard tooling
    from nanodiloco_tpu.training.metrics import summarize_run

    summary = summarize_run(deploy_jsonl)
    if not (summary.get("fleet_promotes") and summary.get("fleet_rollbacks")
            and summary.get("fleet_ejections")):
        record({"phase": "fleet",
                "error": "summarize_run missing fleet keys",
                "summary": {k: v for k, v in summary.items()
                            if k.startswith(("fleet", "deploy"))}})
        raise SystemExit(1)
    record({
        "phase": "fleet",
        "backend_live": live,
        "promote_step": promote["step"],
        "rollback_step": rollback["step"],
        "ejected_replica": eject["replica"],
        "blackbox_attached": eject["blackbox"]["path"],
        "parity_post_promote_tokens": len(post_promote["token_ids"]),
        "fleet_goodput_fraction": summary.get("fleet_goodput_fraction"),
        "scraped": scraped,
    })


def phase_chaos() -> None:
    """Fleet resilience drill on this backend: a 3-replica in-process
    serve fleet, every byte crossing a ``ChaosProxy`` wire, driven
    through the router's OWN HTTP server — the request-level resilience
    stack proven over real sockets, not scripted posts. The schedule is
    deterministic (per-target request ordinals, zero wall-clock
    randomness): a blackholed first pick forces a HEDGE WIN, a client
    ``timeout_s`` shorter than the hedge delay forces a DEADLINE-EXPIRY
    504, the blackhole aborts trip r0's breaker and an error_500 burst
    trips r1's — after which the fleet STILL answers 200 through r2
    (route-around, zero ejections), every surviving greedy stream
    bit-identical to solo ``generate()``. On CPU this pins the policy
    stack; tail-latency wins belong to the chip sitting (PERF.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanodiloco_tpu.fleet import FleetRouter, Replica
    from nanodiloco_tpu.fleet.chaos import ChaosPlan, proxy_fleet
    from nanodiloco_tpu.models import LlamaConfig, generate, init_params
    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve import InferenceEngine, Scheduler, ServeServer
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=128,
    )
    params = init_params(jax.random.key(0), cfg)
    prompt = [(i * 13 + 3) % 256 for i in range(12)]
    max_new = 32
    doc = {"token_ids": prompt, "max_new_tokens": max_new,
           "temperature": 0.0}
    solo = np.asarray(generate(
        params, jnp.asarray([prompt], jnp.int32), cfg, max_new,
        temperature=0.0,
    )[0]).tolist()

    servers = []
    for _ in range(3):
        eng = InferenceEngine(params, cfg, num_slots=2, max_len=96,
                              kv_block_size=16)
        servers.append(ServeServer(Scheduler(eng), port=0,
                                   host="127.0.0.1",
                                   max_new_tokens_cap=64).start())
    router = None
    proxies = []
    try:
        # warm DIRECT to each replica (compile prefill+decode without
        # consuming a chaos ordinal)
        for s in servers:
            code, out = http_post_json(
                f"http://127.0.0.1:{s.port}/v1/generate", doc,
                timeout=600)
            if code != 200 or out["token_ids"] != solo:
                record({"phase": "chaos",
                        "error": f"warmup parity failed ({code})"})
                raise SystemExit(1)
        # r0 requests 0+1 blackholed (2.5s, then an RST): request 0 is
        # the hedge-win leg, request 1 the deadline-expiry leg, and the
        # two aborts are r0's breaker trip. r1's ordinals 0/1 go to
        # those legs' hedges, so the error_500 burst starts at 2.
        plan = ChaosPlan.from_dict({"faults": [
            {"kind": "blackhole", "target": "r0", "requests": [0, 1],
             "seconds": 2.5},
            {"kind": "error_500", "target": "r1",
             "requests": [2, 3, 4, 5]},
        ]})
        replicas = [Replica(f"r{i}", f"http://127.0.0.1:{s.port}")
                    for i, s in enumerate(servers)]
        proxied, proxies = proxy_fleet(replicas, plan)
        router = FleetRouter(
            proxied, port=0, host="127.0.0.1",
            health_interval_s=0.3, probe_timeout_s=2.0,
            hedge_after_s=0.75, retry_budget_min=10.0,
            breaker_window=6, breaker_min_samples=2,
            breaker_failure_rate=0.5, breaker_open_s=300.0,
            quiet=True,
        ).start()
        url = f"http://127.0.0.1:{router.port}"

        # leg 1 — hedge win: r0 blackholed, the 0.75s hedge lands on r1
        code, hedge_out = http_post_json(url + "/v1/generate", doc,
                                         timeout=120)
        if code != 200 or hedge_out.get("served_by") != "r1":
            record({"phase": "chaos", "error":
                    f"hedge leg: {code} via "
                    f"{hedge_out.get('served_by')}"})
            raise SystemExit(1)
        if hedge_out["token_ids"] != solo:
            record({"phase": "chaos",
                    "error": "hedge winner is not bit-identical to "
                             "solo generate()"})
            raise SystemExit(1)

        # wait out the blackhole window: r0's leg-1 attempt holds a
        # router_inflight slot until the RST lands 2.5s after launch,
        # and the pick key orders on load — leg 2 must find the loads
        # level again so the name tiebreak sends it back into r0
        time.sleep(3.0)

        # leg 2 — deadline expiry: timeout_s below the hedge delay, the
        # only candidate answering in time is blackholed -> honest 504
        code, out = http_post_json(url + "/v1/generate",
                                   {**doc, "timeout_s": 0.6},
                                   timeout=120)
        if code != 504:
            record({"phase": "chaos",
                    "error": f"deadline leg answered {code}: {out}"})
            raise SystemExit(1)

        # r0's two blackhole aborts land ~2.5s after each launch; wait
        # for the breaker trip they add up to
        deadline = time.time() + 60
        while time.time() < deadline:
            status = json.loads(http_get(url + "/fleet/status",
                                         timeout=5)[1])
            if status["breaker_state"].get("r0") == "open":
                break
            time.sleep(0.3)
        else:
            record({"phase": "chaos",
                    "error": "r0 breaker never tripped on the "
                             "blackhole aborts",
                    "breaker_state": status.get("breaker_state")})
            raise SystemExit(1)

        # leg 3 — error_500 burst trips r1; both requests still answer
        # 200 through r2 (retry + route-around, zero ejections)
        for i in range(2):
            code, out = http_post_json(url + "/v1/generate", doc,
                                       timeout=120)
            if code != 200 or out.get("served_by") != "r2":
                record({"phase": "chaos", "error":
                        f"route-around leg {i}: {code} via "
                        f"{out.get('served_by')}"})
                raise SystemExit(1)
            if out["token_ids"] != solo:
                record({"phase": "chaos",
                        "error": f"route-around leg {i} lost parity"})
                raise SystemExit(1)

        status = json.loads(http_get(url + "/fleet/status",
                                     timeout=5)[1])
        checks = {
            "hedge_wins": status["hedge_wins"] >= 1,
            "deadline_expired": status["deadline_expired"] >= 1,
            "breaker_opens": status["breaker_opens"] >= 2,
            "retries": status["retries"] >= 2,
            "r1_breaker_open": status["breaker_state"].get("r1") == "open",
            "zero_ejections": status["replicas_ejected"] == 0,
            "breaker_open_seconds_booked":
                status["seconds_by_state"].get("breaker_open", 0) > 0,
        }
        if not all(checks.values()):
            record({"phase": "chaos", "error": "counter checks failed",
                    "checks": checks, "status": {
                        k: status[k] for k in (
                            "hedges", "hedge_wins", "retries",
                            "deadline_expired", "breaker_opens",
                            "breaker_state", "replicas_ejected")}})
            raise SystemExit(1)
        m = parse_metrics_text(http_get(url + "/metrics", timeout=5)[1])
        scraped = {k: m[k] for k in (
            "nanodiloco_router_hedges_total",
            "nanodiloco_router_hedge_wins_total",
            "nanodiloco_router_retries_total",
            "nanodiloco_router_deadline_expired_total",
            "nanodiloco_router_breaker_opens_total",
            'nanodiloco_router_breaker_state{replica="r0"}',
        ) if k in m}
        if (not m.get("nanodiloco_router_hedge_wins_total")
                or not m.get("nanodiloco_router_breaker_opens_total")
                or not m.get("nanodiloco_router_deadline_expired_total")):
            record({"phase": "chaos",
                    "error": "router resilience gauges missing from "
                             "/metrics", "scraped": scraped})
            raise SystemExit(1)
        injected = plan.counts()
        fired = plan.drain_fired()
    finally:
        if router is not None:
            router.stop()
        for p in proxies:
            p.stop()
        for s in servers:
            s.stop()
    record({
        "phase": "chaos",
        "backend_live": live,
        "chaos_injected": injected,
        "chaos_fired": len(fired),
        "hedge_served_by": hedge_out["served_by"],
        "parity_streams": 3,
        "counters": {k: status[k] for k in (
            "hedges", "hedge_wins", "retries", "retry_budget_exhausted",
            "deadline_expired", "breaker_opens")},
        "breaker_state": status["breaker_state"],
        "breaker_open_s": status["seconds_by_state"].get("breaker_open"),
        "scraped": scraped,
    })


def phase_disagg() -> None:
    """Disaggregated prefill/decode drill on this backend: a 1-prefill
    + 2-decode in-process serve fleet behind a ``DisaggRouter``, every
    byte crossing a ``ChaosProxy`` wire. Every admitted request is
    FORCED through the full handoff — prefill_only park on the prefill
    replica, ``/admin/kv/export`` -> ``/admin/kv/import`` ship, stream
    resumed mid-request on a decode replica — and every finished
    stream must be bit-identical to solo ``generate()`` (greedy) or to
    the same seed-derived doc served monolithically (sampled): the
    ship format moves the same bits attention would have read locally.
    The chaos leg blackholes the prefill replica mid-handoff — the
    router must degrade to ONE honest fallback (a monolithic generate
    on the decode tier, re-prefilling there) with zero dropped
    streams, and the tier must heal: the next request hands off again.
    Tier census, handoff counters, and ship-bytes gauges are scraped
    from the real ``/metrics`` expositions on both sides of the wire.
    On CPU this pins the protocol; the interference win the split buys
    belongs to the chip sitting (bench_serve_disagg_baseline.json)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nanodiloco_tpu.fleet import DisaggRouter, Replica
    from nanodiloco_tpu.fleet.chaos import ChaosPlan, proxy_fleet
    from nanodiloco_tpu.models import LlamaConfig, generate, init_params
    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve import InferenceEngine, Scheduler, ServeServer
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=128,
    )
    params = init_params(jax.random.key(0), cfg)
    max_new = 24
    # three prompt lengths straddling the 16-token KV block size: a
    # partial block, one block + 1 (the gather's off-by-one corner),
    # and a multi-block prompt
    prompts = [
        [(i * 13 + 3) % 256 for i in range(12)],
        [(i * 7 + 1) % 256 for i in range(17)],
        [(i * 11 + 5) % 256 for i in range(40)],
    ]
    solo = [
        np.asarray(generate(
            params, jnp.asarray([p], jnp.int32), cfg, max_new,
            temperature=0.0,
        )[0]).tolist()
        for p in prompts
    ]
    sampled_doc = {"token_ids": prompts[0], "max_new_tokens": max_new,
                   "temperature": 0.9, "top_k": 20, "seed": 7}

    roles = ["prefill", "decode", "decode"]
    names = ["pf", "d0", "d1"]
    servers = []
    for role in roles:
        eng = InferenceEngine(params, cfg, num_slots=2, max_len=96,
                              kv_block_size=16)
        servers.append(ServeServer(Scheduler(eng), port=0,
                                   host="127.0.0.1",
                                   max_new_tokens_cap=64,
                                   role=role).start())
    router = None
    proxies = []
    try:
        # warm DIRECT to each replica: every prompt bucket on every
        # replica (the fallback path re-prefills on decode replicas),
        # checking greedy parity without consuming a chaos ordinal
        for s in servers:
            for p, want in zip(prompts, solo):
                code, out = http_post_json(
                    f"http://127.0.0.1:{s.port}/v1/generate",
                    {"token_ids": p, "max_new_tokens": max_new,
                     "temperature": 0.0},
                    timeout=600)
                if code != 200 or out["token_ids"] != want:
                    record({"phase": "disagg",
                            "error": f"warmup parity failed ({code})"})
                    raise SystemExit(1)
        # the sampled reference comes through the SAME serve stack,
        # monolithically on d0 — seed-derived sampling means the
        # handoff boundary must not change a single token
        code, ref = http_post_json(
            f"http://127.0.0.1:{servers[1].port}/v1/generate",
            sampled_doc, timeout=600)
        if code != 200:
            record({"phase": "disagg",
                    "error": f"sampled reference failed ({code})"})
            raise SystemExit(1)
        sampled_solo = ref["token_ids"]

        # pf's generate ordinals 0-3 are the four handoff legs below;
        # ordinal 4 is blackholed mid-handoff (the router's prefill
        # POST dies on an RST after 2.5s), ordinal 5 is the heal check
        plan = ChaosPlan.from_dict({"faults": [
            {"kind": "blackhole", "target": "pf", "requests": [4],
             "seconds": 2.5},
        ]})
        replicas = [Replica(n, f"http://127.0.0.1:{s.port}")
                    for n, s in zip(names, servers)]
        proxied, proxies = proxy_fleet(replicas, plan)
        router = DisaggRouter(
            proxied, port=0, host="127.0.0.1",
            health_interval_s=0.3, probe_timeout_s=2.0,
            handoff_timeout_s=30.0, quiet=True,
        ).start()
        url = f"http://127.0.0.1:{router.port}"
        deadline = time.time() + 30
        while time.time() < deadline:
            if (router.tier_capacity_names("prefill") == ["pf"]
                    and len(router.tier_capacity_names("decode")) == 2):
                break
            time.sleep(0.2)
        else:
            record({"phase": "disagg",
                    "error": "tiers never became ready"})
            raise SystemExit(1)

        # leg 1 — forced handoff on every request, greedy parity
        decode_names = {"d0", "d1"}
        for i, (p, want) in enumerate(zip(prompts, solo)):
            code, out = http_post_json(
                url + "/v1/generate",
                {"token_ids": p, "max_new_tokens": max_new,
                 "temperature": 0.0},
                timeout=600)
            if (code != 200 or out.get("disagg") != "handoff"
                    or out.get("prefilled_by") != "pf"
                    or out.get("served_by") not in decode_names):
                record({"phase": "disagg", "error":
                        f"handoff leg {i}: {code} disagg="
                        f"{out.get('disagg')} via {out.get('served_by')}"})
                raise SystemExit(1)
            if out["token_ids"] != want:
                record({"phase": "disagg", "error":
                        f"handoff leg {i} (prompt len {len(p)}) is not "
                        "bit-identical to solo generate()"})
                raise SystemExit(1)

        # leg 2 — sampled handoff: seed-derived PRNG, so the resumed
        # stream must match the monolithic reference token for token
        code, out = http_post_json(url + "/v1/generate", sampled_doc,
                                   timeout=600)
        if code != 200 or out.get("disagg") != "handoff":
            record({"phase": "disagg", "error":
                    f"sampled handoff: {code} disagg={out.get('disagg')}"})
            raise SystemExit(1)
        if out["token_ids"] != sampled_solo:
            record({"phase": "disagg",
                    "error": "sampled handoff lost parity with the "
                             "monolithic serve of the same seed"})
            raise SystemExit(1)

        # leg 3 — chaos: the prefill POST is blackholed mid-handoff.
        # One honest fallback (monolithic generate on the decode tier,
        # re-prefilling there), still 200, still bit-identical.
        code, out = http_post_json(
            url + "/v1/generate",
            {"token_ids": prompts[0], "max_new_tokens": max_new,
             "temperature": 0.0},
            timeout=600)
        if (code != 200 or out.get("disagg") != "fallback"
                or out.get("served_by") not in decode_names):
            record({"phase": "disagg", "error":
                    f"blackhole leg: {code} disagg={out.get('disagg')} "
                    f"via {out.get('served_by')}"})
            raise SystemExit(1)
        if out["token_ids"] != solo[0]:
            record({"phase": "disagg",
                    "error": "fallback stream lost parity"})
            raise SystemExit(1)

        # leg 4 — the tier heals: the blackhole marked pf not-ready;
        # the health loop must restore it and the next request must
        # hand off again (the fallback is a degradation, not a latch)
        deadline = time.time() + 30
        while time.time() < deadline:
            if router.tier_capacity_names("prefill") == ["pf"]:
                break
            time.sleep(0.2)
        else:
            record({"phase": "disagg",
                    "error": "prefill tier never healed after the "
                             "blackhole"})
            raise SystemExit(1)
        code, out = http_post_json(
            url + "/v1/generate",
            {"token_ids": prompts[1], "max_new_tokens": max_new,
             "temperature": 0.0},
            timeout=600)
        if (code != 200 or out.get("disagg") != "handoff"
                or out["token_ids"] != solo[1]):
            record({"phase": "disagg", "error":
                    f"heal leg: {code} disagg={out.get('disagg')}"})
            raise SystemExit(1)

        # scrape both sides of the wire
        status = json.loads(http_get(url + "/fleet/status",
                                     timeout=5)[1])
        d = status.get("disagg") or {}
        checks = {
            "handoffs": d.get("handoffs", 0) >= 5,
            "one_fallback": d.get("fallbacks", 0) == 1,
            "fallback_reason": d.get("fallbacks_by_reason", {}).get(
                "prefill_unreachable", 0) == 1,
            "ship_bytes": d.get("ship_bytes", 0) > 0,
            "tier_census": status.get("replicas_by_tier", {}).get(
                "prefill") == 1
                and status["replicas_by_tier"].get("decode") == 2,
            "zero_ejections": status["replicas_ejected"] == 0,
        }
        if not all(checks.values()):
            record({"phase": "disagg", "error": "counter checks failed",
                    "checks": checks, "disagg": d,
                    "replicas_by_tier": status.get("replicas_by_tier")})
            raise SystemExit(1)
        m = parse_metrics_text(http_get(url + "/metrics", timeout=5)[1])
        pf_m = parse_metrics_text(http_get(
            f"http://127.0.0.1:{servers[0].port}/metrics", timeout=5)[1])
        dec_m = [parse_metrics_text(http_get(
            f"http://127.0.0.1:{s.port}/metrics", timeout=5)[1])
            for s in servers[1:]]
        scraped = {
            "fleet_handoffs": m.get("nanodiloco_fleet_handoffs_total"),
            "fleet_fallbacks": m.get(
                "nanodiloco_fleet_handoff_fallbacks_total"),
            "fleet_ship_bytes": m.get("nanodiloco_fleet_ship_bytes_total"),
            "handoff_seconds_count": m.get(
                "nanodiloco_fleet_handoff_seconds_count"),
            "tier_prefill": m.get(
                'nanodiloco_fleet_tier_replicas{tier="prefill"}'),
            "tier_decode": m.get(
                'nanodiloco_fleet_tier_replicas{tier="decode"}'),
            "pf_role": pf_m.get('nanodiloco_serve_role{role="prefill"}'),
            "pf_exports": pf_m.get(
                'nanodiloco_kv_ship_requests_total{direction="export"}'),
            "dec_imports": sum(
                dm.get('nanodiloco_kv_ship_requests_total'
                       '{direction="import"}', 0) for dm in dec_m),
        }
        gauge_ok = {
            "tier_gauges": scraped["tier_prefill"] == 1
            and scraped["tier_decode"] == 2,
            "handoff_counters": (scraped["fleet_handoffs"] or 0) >= 5
            and (scraped["fleet_fallbacks"] or 0) >= 1
            and (scraped["fleet_ship_bytes"] or 0) > 0,
            "ship_counters": (scraped["pf_exports"] or 0) >= 5
            and scraped["dec_imports"] >= 5,
            "role_gauge": scraped["pf_role"] == 1,
        }
        if not all(gauge_ok.values()):
            record({"phase": "disagg",
                    "error": "tier/ship gauges missing from /metrics",
                    "gauge_ok": gauge_ok, "scraped": scraped})
            raise SystemExit(1)
        injected = plan.counts()
        fired = plan.drain_fired()
    finally:
        if router is not None:
            router.stop()
        for p in proxies:
            p.stop()
        for s in servers:
            s.stop()
    record({
        "phase": "disagg",
        "backend_live": live,
        "chaos_injected": injected,
        "chaos_fired": len(fired),
        "parity_streams": len(prompts) + 1,   # greedy legs + heal leg
        "sampled_parity": True,
        "fallback_parity": True,
        "handoffs": d.get("handoffs"),
        "fallbacks_by_reason": d.get("fallbacks_by_reason"),
        "ship_bytes": d.get("ship_bytes"),
        "handoff_seconds_sum": d.get("handoff_seconds_sum"),
        "scraped": scraped,
    })


def phase_trace() -> None:
    """Causal-tracing drill on this backend: ONE disaggregated request
    driven through ``ChaosProxy`` wires with a span tracer on every
    process — the ``DisaggRouter`` and each of the three replicas — and
    a 400 ms latency fault injected on the prefill leg. The four
    per-process shards are then stitched with ``report trace``'s own
    machinery into one causal tree rooted at the router's route span,
    with the replicas' queued/prefill/kv_export/kv_import/decode spans
    hanging under the handoff leg that caused them, and the critical
    path must account for the measured client wire latency to within
    10% — the injected wire delay surfacing as honest UNCOVERED time
    (self/residual segments booked to the router's handoff_prefill
    span, which no replica span can claim) inside the prefill leg,
    never silently dropped. On CPU this pins the
    propagation protocol end to end; a chip run puts real kernel time
    under the same spans."""
    import tempfile

    import jax

    from nanodiloco_tpu.cli import report_trace_main
    from nanodiloco_tpu.fleet import DisaggRouter, Replica
    from nanodiloco_tpu.fleet.chaos import ChaosPlan, proxy_fleet
    from nanodiloco_tpu.models import LlamaConfig, init_params
    from nanodiloco_tpu.obs.tracer import (
        SpanTracer,
        critical_path,
        stitch_trace,
    )
    from nanodiloco_tpu.serve import InferenceEngine, Scheduler, ServeServer
    from nanodiloco_tpu.serve.client import http_post_json

    live = chip_is_live()
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=128,
    )
    params = init_params(jax.random.key(0), cfg)
    prompt = [(i * 13 + 3) % 256 for i in range(24)]
    max_new = 16
    names = ["pf", "d0", "d1"]
    roles = ["prefill", "decode", "decode"]
    servers = []
    tracers: dict[str, SpanTracer] = {}
    for name, role in zip(names, roles):
        eng = InferenceEngine(params, cfg, num_slots=2, max_len=96,
                              kv_block_size=16)
        # SAME clock as the scheduler (time.monotonic, its default) so
        # the retroactively recorded request phases land on the
        # tracer's timebase; distinct names keep the stitched tree's
        # process column readable
        tr = SpanTracer(clock=time.monotonic,
                        process_name=f"nanodiloco {role} {name}")
        tracers[name] = tr
        servers.append(ServeServer(Scheduler(eng, tracer=tr), port=0,
                                   host="127.0.0.1",
                                   max_new_tokens_cap=64,
                                   role=role).start())
    rtracer = SpanTracer(clock=time.monotonic,
                         process_name="nanodiloco router")
    router = None
    proxies = []
    try:
        # warm DIRECT to each replica (compile the prefill/decode
        # programs without consuming a chaos ordinal or a trace)
        for s in servers:
            code, _out = http_post_json(
                f"http://127.0.0.1:{s.port}/v1/generate",
                {"token_ids": prompt, "max_new_tokens": max_new,
                 "temperature": 0.0},
                timeout=600)
            if code != 200:
                record({"phase": "trace",
                        "error": f"warmup failed ({code})"})
                raise SystemExit(1)
        # pf's generate ordinal 0 is the traced handoff's prefill leg:
        # the injected 400 ms wire delay must show up inside the
        # router's handoff_prefill span as residual (the replica's own
        # spans cannot cover wire time)
        plan = ChaosPlan.from_dict({"faults": [
            {"kind": "latency", "target": "pf", "requests": [0],
             "seconds": 0.4},
        ]})
        replicas = [Replica(n, f"http://127.0.0.1:{s.port}")
                    for n, s in zip(names, servers)]
        proxied, proxies = proxy_fleet(replicas, plan)
        router = DisaggRouter(
            proxied, port=0, host="127.0.0.1",
            health_interval_s=0.3, probe_timeout_s=2.0,
            handoff_timeout_s=30.0, quiet=True, tracer=rtracer,
        ).start()
        url = f"http://127.0.0.1:{router.port}"
        deadline = time.time() + 30
        while time.time() < deadline:
            if (router.tier_capacity_names("prefill") == ["pf"]
                    and len(router.tier_capacity_names("decode")) == 2):
                break
            time.sleep(0.2)
        else:
            record({"phase": "trace",
                    "error": "tiers never became ready"})
            raise SystemExit(1)

        t0 = time.monotonic()
        code, out = http_post_json(
            url + "/v1/generate",
            {"token_ids": prompt, "max_new_tokens": max_new,
             "temperature": 0.0},
            timeout=600)
        wire_s = time.monotonic() - t0
        if (code != 200 or out.get("disagg") != "handoff"
                or not out.get("trace_id") or not out.get("request_id")):
            record({"phase": "trace", "error":
                    f"traced handoff: {code} disagg={out.get('disagg')} "
                    f"trace_id={out.get('trace_id')!r}"})
            raise SystemExit(1)

        shards = [rtracer.to_chrome()] + [tracers[n].to_chrome()
                                          for n in names]
        stitched = stitch_trace(shards, out["request_id"])
        root = stitched["root"]
        segs = critical_path(root)
        total = root["end_s"] - root["start_s"]
        names_seen = set()

        def _collect(node):
            names_seen.add(node["name"])
            for c in node["children"]:
                _collect(c)

        _collect(root)
        procs = {n["process"] for n in stitched["spans"]}
        residual_s = sum(s["seconds"] for s in segs
                         if s["kind"] == "residual")
        # the wire delay sits BEFORE the replica's first span inside
        # the prefill leg, so critical_path books it to the leg's own
        # leading window ("self"); inter-hop slack lands as "residual".
        # Both are uncovered-by-any-child time on the router's span —
        # that is where an injected wire delay must show up.
        pf_uncovered_s = sum(s["seconds"] for s in segs
                             if s["span"] == "handoff_prefill"
                             and s["kind"] in ("self", "residual"))
        ratio = total / wire_s if wire_s > 0 else 0.0
        by_tid = stitch_trace(shards, out["trace_id"])
        checks = {
            # one causal tree rooted at the router's route span — no
            # synthetic root, no request_id-joined strays
            "rooted_at_route": root["name"] == "route"
            and root["process"] == "nanodiloco router",
            "all_causal": stitched["request_id_joined"] == 0
            and stitched["causal_spans"] == len(stitched["spans"]),
            "three_processes": len(procs) >= 3,
            "full_tree": {"handoff_prefill", "handoff_export",
                          "handoff_import", "queued", "prefill",
                          "kv_export", "kv_import",
                          "decode"} <= names_seen,
            "trace_id_needle_agrees":
                by_tid["root"]["name"] == "route"
                and len(by_tid["spans"]) == len(stitched["spans"]),
            # the critical path accounts for the measured wire latency
            # (the client's HTTP overhead is the only part outside the
            # route span)
            "latency_accounted": 0.90 <= ratio <= 1.05,
            # the injected 400 ms wire delay is visible as uncovered
            # time on the prefill leg's own critical-path segments
            "chaos_delay_is_residual": pf_uncovered_s >= 0.35,
            "segments_partition": abs(
                sum(s["seconds"] for s in segs) - total) < 1e-6,
        }
        injected = plan.counts()
        fired = plan.drain_fired()
        if not all(checks.values()):
            record({"phase": "trace", "error": "stitch checks failed",
                    "checks": checks, "wire_s": round(wire_s, 4),
                    "critical_total_s": round(total, 4),
                    "residual_s": round(residual_s, 4),
                    "prefill_leg_uncovered_s": round(pf_uncovered_s, 4),
                    "span_names": sorted(names_seen)})
            raise SystemExit(1)
        # the operator surface end to end: the same shards through the
        # real `report trace` CLI (file loading + waterfall + critical
        # path render); exits nonzero on a stitch failure
        with tempfile.TemporaryDirectory() as td:
            paths = []
            for i, doc in enumerate(shards):
                p = os.path.join(td, f"shard{i}.json")
                with open(p, "w") as f:
                    json.dump(doc, f)
                paths.append(p)
            report_trace_main([out["request_id"], *paths])
    finally:
        if router is not None:
            router.stop()
        for p in proxies:
            p.stop()
        for s in servers:
            s.stop()
    record({
        "phase": "trace",
        "backend_live": live,
        "chaos_injected": injected,
        "chaos_fired": len(fired),
        "wire_s": round(wire_s, 4),
        "critical_total_s": round(total, 4),
        "residual_s": round(residual_s, 4),
        "prefill_leg_uncovered_s": round(pf_uncovered_s, 4),
        "accounted_ratio": round(ratio, 4),
        "spans": len(stitched["spans"]),
        "processes": len(procs),
        "shards": stitched["shards"],
    })


def phase_slo_watch() -> None:
    """Fleet observability drill on this backend: train a tiny
    checkpoint, boot a 2-replica `serve` fleet behind the `fleet`
    router, point `obs-watch` (scrape collector + multi-window SLO
    burn rates) at the replicas and the router, and INJECT a straggler
    (`--inject-tick-delay-s` on r1 — the serve-side stall hook). The
    drill asserts the operability loop end to end over real sockets:
    the TTFT burn-rate alert FIRES into the alerts JSONL, the router
    ROUTES AROUND the burning replica (served_by=r0 while r1 stays
    serving — route-around before any 503-ejection), the merged
    Perfetto trace JOINS the router's route/forward spans with the
    replica's queued/prefill/decode spans on the request_id key, the
    gauges and alert counters scrape over the wire, and `report
    timeseries` renders the incident from the series JSONL. On CPU
    this pins the alert logic, trace joins, and route-around ordering;
    what burn thresholds mean under REAL load belongs to the chip
    sitting (PERF.md)."""
    import signal as _signal
    import socket
    import tempfile
    import threading

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    tmp = tempfile.mkdtemp(prefix="nanodiloco-slo-")
    ckpt = os.path.join(tmp, "ckpt")
    alerts_jsonl = os.path.join(tmp, "alerts.jsonl")
    series_jsonl = os.path.join(tmp, "series.jsonl")
    deploy_jsonl = os.path.join(tmp, "deploy.jsonl")
    traces = {n: os.path.join(tmp, f"{n}-trace.json")
              for n in ("r0", "r1", "router")}
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_SLO_WATCH", "1500")
    )
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "2", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "slo-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if train.returncode != 0:
        record({"phase": "slo_watch",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = {n: free_port() for n in ("r0", "r1", "router", "watch")}
    procs: dict = {}
    # r1 is the STRAGGLER: every scheduling tick sleeps 0.25 s, so its
    # TTFT sits far above the 0.12 s SLO while r0's (post-warmup) sits
    # far below — alive, routable, and burning
    for name, extra in (("r0", []),
                        ("r1", ["--inject-tick-delay-s", "0.25"])):
        procs[name] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "serve",
             "--checkpoint-dir", ckpt,
             "--port", str(ports[name]), "--host", "127.0.0.1",
             "--slots", "2", "--max-len", "128", "--chunk-size", "16",
             "--max-new-tokens-cap", "64",
             "--trace-out", traces[name]] + extra,
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )

    def stop(proc):
        if proc is not None and proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    def wait_alert(deadline):
        while time.time() < deadline:
            if os.path.exists(alerts_jsonl):
                with open(alerts_jsonl) as f:
                    for line in f:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue
                        if (rec.get("slo_alert") == "short_ttft_p95_s"
                                and rec.get("state") == "firing"
                                and rec.get("target") == "r1"):
                            return rec
            time.sleep(0.3)
        return None

    try:
        deadline = time.time() + budget * 0.25
        for name in ("r0", "r1"):
            up = False
            while time.time() < deadline and procs[name].poll() is None:
                try:
                    up = http_get(
                        f"http://127.0.0.1:{ports[name]}/healthz",
                        timeout=3,
                    )[0] == 200
                except OSError:
                    up = False
                if up:
                    break
                time.sleep(0.3)
            if not up:
                record({"phase": "slo_watch",
                        "error": f"replica {name} never answered /healthz"})
                raise SystemExit(1)
        # WARM-UP before the watcher starts: the first requests compile
        # (one-off TTFT spikes — the first dry-run measured TWO spiked
        # admissions on r0, so its 25-sample p95 was still the 1.4 s
        # spike); r0 gets enough post-compile samples that its rolling
        # nearest-rank p95 skips several outliers (64 warm requests ->
        # p95 is the 3rd-largest sample), r1 just compiles — its gauge
        # SHOULD burn
        warm_doc = {"token_ids": [(i * 7 + 3) % 256 for i in range(8)],
                    "max_new_tokens": 4, "temperature": 0.0,
                    "stop": False, "prefix_cache": False}
        code, _ = http_post_json(
            f"http://127.0.0.1:{ports['r1']}/v1/generate", warm_doc,
            timeout=120,
        )
        if code != 200:
            record({"phase": "slo_watch",
                    "error": f"r1 warmup failed {code}"})
            raise SystemExit(1)
        for i in range(64):
            code, _ = http_post_json(
                f"http://127.0.0.1:{ports['r0']}/v1/generate",
                {**warm_doc, "seed": i}, timeout=120,
            )
            if code != 200:
                record({"phase": "slo_watch",
                        "error": f"r0 warmup request {i} failed {code}"})
                raise SystemExit(1)
        procs["router"] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "fleet",
             "--replica", f"http://127.0.0.1:{ports['r0']}",
             "--replica", f"http://127.0.0.1:{ports['r1']}",
             "--port", str(ports["router"]), "--host", "127.0.0.1",
             "--events-jsonl", deploy_jsonl,
             "--health-interval-s", "0.3",
             "--trace-out", traces["router"], "--quiet"],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        # the router process imports the package (seconds): wait for
        # its socket before the watcher starts, or the first burn
        # transition races the boot (the monitor retries failed hook
        # posts anyway — this just keeps the drill's timeline tight)
        deadline = time.time() + budget * 0.2
        router_up = False
        while time.time() < deadline and procs["router"].poll() is None:
            try:
                http_get(f"http://127.0.0.1:{ports['router']}/healthz",
                         timeout=3)
                router_up = True
                break
            except OSError:
                time.sleep(0.3)
        if not router_up:
            record({"phase": "slo_watch",
                    "error": "router never opened its socket"})
            raise SystemExit(1)
        procs["watch"] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "obs-watch",
             "--target", f"r0=http://127.0.0.1:{ports['r0']}",
             "--target", f"r1=http://127.0.0.1:{ports['r1']}",
             "--target", f"router=http://127.0.0.1:{ports['router']}",
             "--router-url", f"http://127.0.0.1:{ports['router']}",
             "--port", str(ports["watch"]), "--host", "127.0.0.1",
             "--interval-s", "0.4",
             "--ttft-p95-max", "0.12",
             "--fast-window-s", "2", "--slow-window-s", "5",
             "--fast-burn", "0.5", "--slow-burn", "0.3",
             "--alerts-jsonl", alerts_jsonl,
             "--series-jsonl", series_jsonl],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        # burn traffic straight at the straggler: each request's TTFT
        # carries the injected tick delay, poisoning r1's p95 window
        burn_errors = []

        def burn(i):
            try:
                code, _ = http_post_json(
                    f"http://127.0.0.1:{ports['r1']}/v1/generate",
                    {**warm_doc, "seed": 100 + i}, timeout=120,
                )
                if code != 200:
                    burn_errors.append(code)
            except OSError as e:
                burn_errors.append(str(e))

        threads = [threading.Thread(target=burn, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if burn_errors:
            record({"phase": "slo_watch",
                    "error": f"burn traffic failed: {burn_errors[:3]}"})
            raise SystemExit(1)
        alert = wait_alert(time.time() + budget * 0.25)
        if alert is None:
            tail = ""
            if os.path.exists(alerts_jsonl):
                tail = open(alerts_jsonl).read()[-400:]
            record({"phase": "slo_watch",
                    "error": f"TTFT burn alert never fired; tail: {tail}"})
            raise SystemExit(1)
        # the alert record lands in the JSONL BEFORE the hook's POST
        # reaches the router: wait for the route-around mark to apply
        not_preferred: dict = {}
        deadline = time.time() + 30
        while time.time() < deadline:
            code, body = http_get(
                f"http://127.0.0.1:{ports['router']}/fleet/status",
                timeout=5,
            )
            not_preferred = json.loads(body).get("slo_not_preferred", {})
            if "r1" in not_preferred:
                break
            time.sleep(0.3)
        # the burn must be r1's ALONE: if r0's gauge also breached (the
        # warm-up failed to dilute its compile spikes) the route-around
        # assertion below would be meaningless — fail here with the
        # measured series instead of a confusing served_by mix
        if "r0" in not_preferred:
            record({"phase": "slo_watch",
                    "error": "r0 burned the TTFT SLO too (warm-up did "
                             "not clean its p95 window) — the drill "
                             "needs exactly one burning replica",
                    "slo_not_preferred": not_preferred})
            raise SystemExit(1)
        # route-around: post-alert traffic through the ROUTER must land
        # on r0 (served_by echoed), while r1 stays serving — the
        # route-around-before-ejection ordering over the real wire
        served_by = []
        for i in range(4):
            code, out = http_post_json(
                f"http://127.0.0.1:{ports['router']}/v1/generate",
                {**warm_doc, "seed": 200 + i,
                 "request_id": f"drill-join-{i}"}, timeout=120,
            )
            if code != 200:
                record({"phase": "slo_watch",
                        "error": f"post-alert request {i} failed {code}"})
                raise SystemExit(1)
            served_by.append(out.get("served_by"))
        if set(served_by) != {"r0"}:
            record({"phase": "slo_watch",
                    "error": "router did not route around the burning "
                             "replica", "served_by": served_by})
            raise SystemExit(1)
        code, body = http_get(
            f"http://127.0.0.1:{ports['router']}/fleet/status", timeout=5
        )
        status = json.loads(body)
        # r1 must still be SERVING (not ejected): the fleet gauge is
        # the authoritative count
        code, m_text = http_get(
            f"http://127.0.0.1:{ports['router']}/metrics", timeout=5
        )
        m = parse_metrics_text(m_text)
        if m.get("nanodiloco_fleet_replicas_serving") != 2:
            record({"phase": "slo_watch",
                    "error": "burning replica was ejected instead of "
                             "routed around",
                    "metrics": {k: v for k, v in m.items()
                                if "replicas" in k}})
            raise SystemExit(1)
        if "r1" not in status["slo_not_preferred"]:
            record({"phase": "slo_watch",
                    "error": "router never marked r1 not-preferred",
                    "status": status})
            raise SystemExit(1)
        # the watcher's own counters scrape over the wire
        code, w_text = http_get(
            f"http://127.0.0.1:{ports['watch']}/metrics", timeout=5
        )
        w = parse_metrics_text(w_text)
        alerts_total = w.get(
            'nanodiloco_slo_alerts_total{rule="short_ttft_p95_s"}'
        )
        if not alerts_total:
            record({"phase": "slo_watch",
                    "error": "obs-watch /metrics missing the alert "
                             "counter",
                    "scraped": {k: v for k, v in w.items()
                                if "slo" in k or "obs" in k}})
            raise SystemExit(1)
    finally:
        for name in ("watch", "router", "r1", "r0"):
            stop(procs.get(name))

    # artifacts after shutdown: merged trace joins the tiers on the
    # request_id key; report timeseries renders the incident
    merged_path = os.path.join(tmp, "merged-trace.json")
    merge = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "merge-trace",
         traces["router"], traces["r0"], traces["r1"],
         "-o", merged_path],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    if merge.returncode != 0:
        record({"phase": "slo_watch",
                "error": f"merge-trace failed: {merge.stdout[-200:]}"
                         f"{merge.stderr[-200:]}"})
        raise SystemExit(1)
    with open(merged_path) as f:
        merged = json.load(f)
    join_pids = {
        e["pid"] for e in merged["traceEvents"]
        if e.get("ph") == "X"
        and str((e.get("args") or {}).get("request_id", "")
                ).startswith("drill-join-")
    }
    if len(join_pids) < 2:
        record({"phase": "slo_watch",
                "error": "merged trace does not join router and replica "
                         "spans on the drill request_ids",
                "join_pids": sorted(join_pids)})
        raise SystemExit(1)
    ts = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "timeseries",
         series_jsonl, "--key", "ttft"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=60,
    )
    if ts.returncode != 0 or "r1:nanodiloco_serve_ttft_p95_seconds" \
            not in ts.stdout:
        record({"phase": "slo_watch",
                "error": f"report timeseries failed: {ts.stdout[-200:]}"
                         f"{ts.stderr[-200:]}"})
        raise SystemExit(1)
    from nanodiloco_tpu.training.metrics import summarize_run

    summary = summarize_run(alerts_jsonl)
    if not summary.get("slo_alerts_total"):
        record({"phase": "slo_watch",
                "error": "summarize_run missing slo keys",
                "summary": {k: v for k, v in summary.items()
                            if k.startswith("slo")}})
        raise SystemExit(1)
    record({
        "phase": "slo_watch",
        "backend_live": live,
        "alert_rule": alert["slo_alert"],
        "alert_target": alert["target"],
        "served_by_after_alert": served_by,
        "slo_alerts_total": summary.get("slo_alerts_total"),
        "slo_burn_seconds": summary.get("slo_burn_seconds"),
        "slo_worst_rule": summary.get("slo_worst_rule"),
        "trace_join_pids": len(join_pids),
        "obs_watch_alert_counter": alerts_total,
    })


def phase_autoscale_surge() -> None:
    """Predictive-autoscaling drill on this backend: train a tiny
    checkpoint, boot a 2-replica `serve` fleet behind the `fleet` CLI
    with ``--autoscale-template`` armed (embedded collector ->
    CapacityModel -> Autoscaler) plus `obs-watch` holding a class-0
    TTFT SLO rule, then drive a mixed-class open-loop traffic ramp past
    the seed fleet's capacity. The drill asserts the CLOSED loop over
    real processes: the queue-trend exhaustion forecast triggers a
    scale-out (2 -> up to 4 serve subprocesses) BEFORE any SLO alert
    fires, one autoscaled child is SIGTERM'd mid-surge (the spot
    reclaim signal) and relaunched via a preempt_resume event, the
    fleet drains back to 2 after the ramp with hysteresis (no flapping:
    event counts stay flat through a quiet window), and every scale-
    transition second is booked (the scaling_up bucket of
    ``nanodiloco_fleet_state_seconds`` is nonzero). On CPU this pins
    the control loop's ordering and accounting; what the forecast
    horizon should be under real load belongs to the chip sitting
    (PERF.md)."""
    import signal as _signal
    import socket
    import tempfile
    import threading

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    tmp = tempfile.mkdtemp(prefix="nanodiloco-autoscale-")
    ckpt = os.path.join(tmp, "ckpt")
    deploy_jsonl = os.path.join(tmp, "deploy.jsonl")
    alerts_jsonl = os.path.join(tmp, "alerts.jsonl")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_AUTOSCALE_SURGE", "1800")
    )
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "2", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "autoscale-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.25,
    )
    if train.returncode != 0:
        record({"phase": "autoscale_surge",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    # slots=1 keeps each replica's capacity small enough that the CPU
    # ramp below genuinely overloads the 2-replica seed fleet (the
    # forecast can only act on pressure that exists)
    serve_flags = ["--checkpoint-dir", ckpt, "--host", "127.0.0.1",
                   "--slots", "1", "--max-len", "128", "--chunk-size", "16",
                   "--max-new-tokens-cap", "64"]
    ports = {n: free_port() for n in ("r0", "r1", "router", "watch")}
    procs: dict = {}
    for name in ("r0", "r1"):
        procs[name] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "serve",
             "--port", str(ports[name])] + serve_flags,
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
    seed_pids = {procs["r0"].pid, procs["r1"].pid}

    def stop(proc):
        if proc is not None and proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    def events():
        if not os.path.exists(deploy_jsonl):
            return []
        out = []
        with open(deploy_jsonl) as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
        return out

    def wait_event(kind, deadline, **match):
        while time.time() < deadline:
            for e in events():
                if e.get("deploy_event") == kind and all(
                    e.get(k) == v for k, v in match.items()
                ):
                    return e
            time.sleep(0.3)
        return None

    def autoscaled_serve_pids():
        """Serve children the autoscaler launched: processes running
        this checkpoint's serve command that are NOT the seed
        replicas — the preemption-injection surface."""
        pids = set()
        for d in os.listdir("/proc"):
            if not d.isdigit() or int(d) in seed_pids:
                continue
            try:
                with open(f"/proc/{d}/cmdline", "rb") as f:
                    argv = f.read().decode(errors="replace").split("\0")
            except OSError:
                continue
            if "serve" in argv and ckpt in argv:
                pids.add(int(d))
        return pids

    try:
        deadline = time.time() + budget * 0.25
        for name in ("r0", "r1"):
            up = False
            while time.time() < deadline and procs[name].poll() is None:
                try:
                    up = http_get(
                        f"http://127.0.0.1:{ports[name]}/healthz",
                        timeout=3,
                    )[0] == 200
                except OSError:
                    up = False
                if up:
                    break
                time.sleep(0.3)
            if not up:
                record({"phase": "autoscale_surge",
                        "error": f"replica {name} never answered /healthz"})
                raise SystemExit(1)
        # warm both replicas so compile spikes stay out of the surge
        # window (and out of the class-0 TTFT gauge the SLO rule reads)
        warm_doc = {"token_ids": [(i * 7 + 3) % 256 for i in range(12)],
                    "max_new_tokens": 4, "temperature": 0.0,
                    "stop": False, "prefix_cache": False, "priority": 0}
        for name in ("r0", "r1"):
            code, _ = http_post_json(
                f"http://127.0.0.1:{ports[name]}/v1/generate", warm_doc,
                timeout=180,
            )
            if code != 200:
                record({"phase": "autoscale_surge",
                        "error": f"{name} warmup failed {code}"})
                raise SystemExit(1)
        template = " ".join(
            [sys.executable, "-m", "nanodiloco_tpu", "serve",
             "--port", "{port}"] + serve_flags
        )
        procs["router"] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "fleet",
             "--replica", f"http://127.0.0.1:{ports['r0']}",
             "--replica", f"http://127.0.0.1:{ports['r1']}",
             "--port", str(ports["router"]), "--host", "127.0.0.1",
             "--events-jsonl", deploy_jsonl,
             "--health-interval-s", "0.3", "--drain-timeout-s", "15",
             "--autoscale-template", template,
             "--autoscale-min", "2", "--autoscale-max", "4",
             "--autoscale-interval-s", "0.5",
             "--autoscale-cooldown-s", "2",
             "--autoscale-hysteresis", "2",
             "--autoscale-horizon-s", "30",
             "--autoscale-idle-ticks", "4",
             "--autoscale-window-s", "20",
             "--shed-horizon-s", "8", "--quiet"],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        url = f"http://127.0.0.1:{ports['router']}"
        deadline = time.time() + budget * 0.2
        router_up = False
        while time.time() < deadline and procs["router"].poll() is None:
            try:
                http_get(url + "/healthz", timeout=3)
                router_up = True
                break
            except OSError:
                time.sleep(0.3)
        if not router_up:
            record({"phase": "autoscale_surge",
                    "error": "router never opened its socket"})
            raise SystemExit(1)
        # the SLO watcher holds the class-0 TTFT rule the shed ladder
        # protects; the threshold is generous on purpose — the drill's
        # ordering claim is "capacity arrives BEFORE the SLO burns"
        procs["watch"] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "obs-watch",
             "--target", f"r0=http://127.0.0.1:{ports['r0']}",
             "--target", f"r1=http://127.0.0.1:{ports['r1']}",
             "--port", str(ports["watch"]), "--host", "127.0.0.1",
             "--interval-s", "0.5",
             "--class0-ttft-p95-max", "30",
             "--fast-window-s", "2", "--slow-window-s", "5",
             "--alerts-jsonl", alerts_jsonl],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        # mixed-class open-loop ramp: arrivals fire on schedule no
        # matter what's in flight (a closed loop would self-throttle
        # away from the overload the forecast must see)
        results: list = []
        lock = threading.Lock()

        def fire(i, prio):
            try:
                code, out = http_post_json(
                    url + "/v1/generate",
                    {"token_ids": [(i * 11 + 5) % 256 for _ in range(32)],
                     "max_new_tokens": 48, "temperature": 0.0,
                     "seed": i, "stop": False, "prefix_cache": False,
                     "priority": prio},
                    timeout=300,
                )
            except OSError as e:
                code, out = -1, {"error": str(e)}
            with lock:
                results.append((code, prio,
                                out.get("shed") if isinstance(out, dict)
                                else None))

        workers = []
        surge_t0 = time.time()
        i = 0
        preempted_pid = None
        preempt_event = None
        scale_up = None
        surge_deadline = surge_t0 + budget * 0.25
        # keep firing until a scale-out lands AND a preemption has been
        # injected + recovered (or the per-stage deadline passes)
        while time.time() < surge_deadline:
            prio = 0 if i % 2 == 0 else 3
            w = threading.Thread(target=fire, args=(i, prio))
            w.start()
            workers.append(w)
            i += 1
            # ~40 req/s of ~60-80ms requests vs 2 replicas x 1 slot:
            # a real >1.3x overload, so queue depth crosses slots_total
            # and the exhaustion forecast has something to see
            time.sleep(0.025)
            if scale_up is None:
                for e in events():
                    if e.get("deploy_event") == "scale_up":
                        scale_up = e
                        break
                continue
            if preempted_pid is None:
                auto = autoscaled_serve_pids()
                if auto:
                    preempted_pid = sorted(auto)[0]
                    os.kill(preempted_pid, _signal.SIGTERM)
                continue
            if preempt_event is None:
                for e in events():
                    if e.get("deploy_event") == "preempt_resume":
                        preempt_event = e
                        break
                continue
            break  # scale-out seen, preemption injected and recovered
        for w in workers:
            w.join()
        if scale_up is None:
            tail = "\n".join(json.dumps(e) for e in events()[-8:])
            record({"phase": "autoscale_surge",
                    "error": f"no scale_up event under the ramp; "
                             f"tail:\n{tail}",
                    "requests_fired": i})
            raise SystemExit(1)
        if preempt_event is None:
            tail = "\n".join(json.dumps(e) for e in events()[-8:])
            record({"phase": "autoscale_surge",
                    "error": f"preempted child was never relaunched "
                             f"(pid={preempted_pid}); tail:\n{tail}"})
            raise SystemExit(1)
        # scale-in: with the ramp over, sustained headroom must drain
        # the fleet back to the 2-replica floor through the router
        scale_down = wait_event("scale_down", time.time() + budget * 0.25)
        if scale_down is None:
            tail = "\n".join(json.dumps(e) for e in events()[-8:])
            record({"phase": "autoscale_surge",
                    "error": f"no scale_down after the ramp; tail:\n{tail}"})
            raise SystemExit(1)
        deadline = time.time() + budget * 0.25
        m = {}
        while time.time() < deadline:
            try:
                m = parse_metrics_text(
                    http_get(url + "/metrics", timeout=5)[1]
                )
            except OSError:
                time.sleep(0.5)
                continue
            if m.get("nanodiloco_fleet_replicas_serving") == 2:
                break
            time.sleep(0.5)
        if m.get("nanodiloco_fleet_replicas_serving") != 2:
            record({"phase": "autoscale_surge",
                    "error": "fleet never drained back to the floor",
                    "metrics": {k: v for k, v in m.items()
                                if "replicas" in k}})
            raise SystemExit(1)
        # no flapping: through a quiet window the event ledger stays
        # flat (hysteresis + cooldown must hold the floor, not oscillate)
        def scale_counts():
            c = {"scale_up": 0, "scale_down": 0, "preempt_resume": 0}
            for e in events():
                k = e.get("deploy_event")
                if k in c:
                    c[k] += 1
            return c

        before = scale_counts()
        time.sleep(6)
        after = scale_counts()
        if before != after:
            record({"phase": "autoscale_surge",
                    "error": "fleet is flapping after the ramp",
                    "before": before, "after": after})
            raise SystemExit(1)
        # every scale-transition second booked: the scaling_up bucket
        # (boot time of autoscaled replicas) must be nonzero
        scaling_up_s = m.get(
            'nanodiloco_fleet_state_seconds{state="scaling_up"}'
        )
        if not scaling_up_s:
            record({"phase": "autoscale_surge",
                    "error": "no scaling_up seconds booked",
                    "metrics": {k: v for k, v in m.items()
                                if "state_seconds" in k}})
            raise SystemExit(1)
        # ordering: capacity arrived BEFORE the class-0 SLO ever burned
        first_alert_t = None
        if os.path.exists(alerts_jsonl):
            with open(alerts_jsonl) as f:
                for line in f:
                    try:
                        a = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if (a.get("slo_alert") and a.get("state") == "firing"
                            and first_alert_t is None):
                        first_alert_t = a.get("t_unix")
        if first_alert_t is not None and first_alert_t <= scale_up["t_unix"]:
            record({"phase": "autoscale_surge",
                    "error": "SLO alert fired before the scale-out — "
                             "the forecast did not act ahead of the burn",
                    "alert_t": first_alert_t,
                    "scale_up_t": scale_up["t_unix"]})
            raise SystemExit(1)
        ok = sum(1 for c, _, _ in results if c == 200)
        shed = sum(1 for c, _, s in results if c == 429 and s)
        class0_shed = sum(1 for c, p, s in results
                          if c == 429 and s and p == 0)
        if class0_shed:
            record({"phase": "autoscale_surge",
                    "error": f"class 0 was shed {class0_shed} time(s) — "
                             "the protected class must always admit"})
            raise SystemExit(1)
    finally:
        for name in ("watch", "router", "r1", "r0"):
            stop(procs.get(name))
        # the router's provider SIGTERMs its autoscaled children on
        # shutdown; anything still around is a leak — kill, don't leak
        for pid in autoscaled_serve_pids():
            try:
                os.kill(pid, _signal.SIGKILL)
            except OSError:
                pass
    record({
        "phase": "autoscale_surge",
        "backend_live": live,
        "requests_fired": i,
        "requests_ok": ok,
        "requests_shed": shed,
        "scale_up_reason": scale_up.get("reason"),
        "preempted_pid": preempted_pid,
        "preempt_resumed_replica": preempt_event.get("replica"),
        "scale_events": after,
        "scaling_up_seconds": scaling_up_s,
        "first_alert_t": first_alert_t,
        "scale_up_t": scale_up["t_unix"],
    })


def phase_devtime() -> None:
    """Device-time attribution drill on this backend: train a tiny
    checkpoint, boot ONE `serve` replica, drive mixed-priority traffic
    over a real socket, and hold the accounting plane to its ledger
    over the wire: the per-program dispatch counters
    (`nanodiloco_device_seconds_total{program="kind:bucket:layout"}`)
    and the per-class cost counters
    (`nanodiloco_serve_device_seconds_total{priority=...}`) must be
    live on /metrics, the sum of every response's per-request `timing`
    attribution (prefill_device_s + decode_device_s) must RECONCILE
    with the scraped per-class counter total, and `report dashboard`
    must render the offline HTML artifact from the series JSONL a
    short `obs-watch` scrape wrote. On CPU this pins attribution
    correctness and sum reconciliation end to end; absolute
    device-second magnitudes belong to the chip sitting (PERF.md)."""
    import signal as _signal
    import socket
    import tempfile

    from nanodiloco_tpu.obs.telemetry import parse_metrics_text
    from nanodiloco_tpu.serve.client import http_get, http_post_json

    live = chip_is_live()
    tmp = tempfile.mkdtemp(prefix="nanodiloco-devtime-")
    ckpt = os.path.join(tmp, "ckpt")
    series_jsonl = os.path.join(tmp, "series.jsonl")
    dash_html = os.path.join(tmp, "dashboard.html")
    model_cfg = os.path.join(tmp, "model.json")
    with open(model_cfg, "w") as f:
        json.dump({
            "vocab_size": 2048, "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_hidden_layers": 2,
            "max_position_embeddings": 256,
        }, f)
    budget = float(
        os.environ.get("NANODILOCO_AGENDA_TIMEOUT_DEVTIME", "1200")
    )
    train = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu",
         "--total-steps", "2", "--inner-steps", "2",
         "--batch-size", "8", "--per-device-batch-size", "4",
         "--seq-length", "256", "--warmup-steps", "2",
         "--llama-config-file", model_cfg, "--no-measure-comm",
         "--no-cost-analysis", "--quiet",
         "--checkpoint-dir", ckpt, "--log-dir", tmp,
         "--run-name", "devtime-probe"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=budget * 0.3,
    )
    if train.returncode != 0:
        record({"phase": "devtime",
                "error": (train.stderr or train.stdout)[-400:]})
        raise SystemExit(1)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ports = {n: free_port() for n in ("r0", "watch")}
    procs: dict = {}

    def stop(proc):
        if proc is not None and proc.poll() is None:
            proc.send_signal(_signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    procs["r0"] = subprocess.Popen(
        [sys.executable, "-m", "nanodiloco_tpu", "serve",
         "--checkpoint-dir", ckpt,
         "--port", str(ports["r0"]), "--host", "127.0.0.1",
         "--slots", "2", "--max-len", "128", "--chunk-size", "16",
         "--max-new-tokens-cap", "64",
         # paged KV: kv_block_seconds only bills when a block pool
         # exists to hold — the dense path has no blocks to meter
         "--kv-block-size", "16"],
        cwd=REPO_ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
    )
    try:
        deadline = time.time() + budget * 0.3
        up = False
        while time.time() < deadline and procs["r0"].poll() is None:
            try:
                up = http_get(
                    f"http://127.0.0.1:{ports['r0']}/healthz", timeout=3,
                )[0] == 200
            except OSError:
                up = False
            if up:
                break
            time.sleep(0.3)
        if not up:
            record({"phase": "devtime",
                    "error": "replica never answered /healthz"})
            raise SystemExit(1)
        # mixed-priority traffic: every response's timing block carries
        # its attributed share; the ledger must equal their sum
        base_doc = {"token_ids": [(i * 7 + 3) % 256 for i in range(8)],
                    "max_new_tokens": 6, "temperature": 0.0,
                    "stop": False, "prefix_cache": False}
        attributed = 0.0
        kv_block_attr = 0.0
        classes_seen = set()
        for i in range(12):
            prio = (0, 1, 3)[i % 3]
            code, out = http_post_json(
                f"http://127.0.0.1:{ports['r0']}/v1/generate",
                {**base_doc, "seed": i, "priority": prio}, timeout=120,
            )
            if code != 200:
                record({"phase": "devtime",
                        "error": f"request {i} failed {code}"})
                raise SystemExit(1)
            timing = out.get("timing") or {}
            attributed += (timing.get("prefill_device_s", 0.0)
                           + timing.get("decode_device_s", 0.0))
            kv_block_attr += timing.get("kv_block_seconds", 0.0)
            classes_seen.add(prio)
        if attributed <= 0.0:
            record({"phase": "devtime",
                    "error": "response timing blocks carried no "
                             "attributed device seconds"})
            raise SystemExit(1)
        # the ledger over the wire: per-program dispatch counters live,
        # per-class counters reconciling with the per-request sums
        code, m_text = http_get(
            f"http://127.0.0.1:{ports['r0']}/metrics", timeout=5
        )
        m = parse_metrics_text(m_text)
        prog_samples = {k: v for k, v in m.items()
                        if k.startswith("nanodiloco_device_seconds_total{")}
        if not prog_samples or m.get(
                "nanodiloco_device_seconds_total", 0.0) <= 0.0:
            record({"phase": "devtime",
                    "error": "per-program dispatch counters missing or "
                             "zero on /metrics",
                    "scraped": sorted(prog_samples)})
            raise SystemExit(1)
        # every serving program kind must have dispatched: decode and
        # prefill_chunk always; this scrape is the proof the engine call
        # sites are actually fenced, not just that the family renders
        kinds = {k.split('program="', 1)[1].split(":", 1)[0]
                 for k in prog_samples if 'program="' in k}
        for want in ("prefill_chunk", "decode"):
            if want not in kinds:
                record({"phase": "devtime",
                        "error": f"no {want!r} program in the dispatch "
                                 "ledger", "kinds": sorted(kinds)})
                raise SystemExit(1)
        serve_total = m.get("nanodiloco_serve_device_seconds_total", 0.0)
        by_class = {k: v for k, v in m.items() if k.startswith(
            "nanodiloco_serve_device_seconds_total{")}
        if len(by_class) != len(classes_seen):
            record({"phase": "devtime",
                    "error": "per-class cost counters do not cover the "
                             "priority classes served",
                    "classes": sorted(classes_seen),
                    "scraped": sorted(by_class)})
            raise SystemExit(1)
        # reconciliation over the wire: the scraped counter is the same
        # ledger the responses were billed from (stats() rounds each
        # class to 1e-6), so the tolerance is rounding + slack only
        tol = max(1e-3, 0.01 * attributed)
        if abs(serve_total - attributed) > tol:
            record({"phase": "devtime",
                    "error": "attribution does not reconcile: "
                             f"sum(timing)={attributed:.6f} vs "
                             f"counter={serve_total:.6f} (tol {tol:.6f})"})
            raise SystemExit(1)
        if kv_block_attr <= 0.0 or m.get(
                "nanodiloco_serve_kv_block_seconds_total", 0.0) <= 0.0:
            record({"phase": "devtime",
                    "error": "KV block-second billing missing (timing "
                             f"sum {kv_block_attr:.6f}, counter "
                             "absent or zero)"})
            raise SystemExit(1)
        # healthz carries the same total for the router's cost probe
        code, body = http_get(
            f"http://127.0.0.1:{ports['r0']}/healthz", timeout=5
        )
        health_total = json.loads(body).get("device_seconds_total")
        if not health_total:
            record({"phase": "devtime",
                    "error": "healthz missing device_seconds_total"})
            raise SystemExit(1)
        # a short obs-watch sitting scrapes the ledger into the series
        # JSONL the offline dashboard renders from
        procs["watch"] = subprocess.Popen(
            [sys.executable, "-m", "nanodiloco_tpu", "obs-watch",
             "--target", f"r0=http://127.0.0.1:{ports['r0']}",
             "--port", str(ports["watch"]), "--host", "127.0.0.1",
             "--interval-s", "0.4",
             # obs-watch refuses to run ruleless; a deliberately loose
             # ceiling keeps the drill about collection, not alerting
             "--ttft-p95-max", "60",
             "--series-jsonl", series_jsonl],
            cwd=REPO_ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        deadline = time.time() + budget * 0.2

        def series_has_devtime():
            if not os.path.exists(series_jsonl):
                return False
            with open(series_jsonl) as f:
                return "nanodiloco_device_seconds_total" in f.read()

        while time.time() < deadline and not series_has_devtime():
            time.sleep(0.4)
        if not series_has_devtime():
            record({"phase": "devtime",
                    "error": "obs-watch series JSONL never captured the "
                             "dispatch counters"})
            raise SystemExit(1)
        # a couple more requests so the scraped series has a real trend
        for i in range(4):
            http_post_json(
                f"http://127.0.0.1:{ports['r0']}/v1/generate",
                {**base_doc, "seed": 100 + i, "priority": 1}, timeout=120,
            )
        time.sleep(1.0)
    finally:
        for name in ("watch", "r0"):
            stop(procs.get(name))

    # the offline artifact after shutdown: the dashboard must render
    # with nothing running, straight from the series JSONL
    dash = subprocess.run(
        [sys.executable, "-m", "nanodiloco_tpu", "report", "dashboard",
         series_jsonl, "-o", dash_html, "--title", "devtime drill"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    if dash.returncode != 0:
        record({"phase": "devtime",
                "error": f"report dashboard failed: {dash.stdout[-200:]}"
                         f"{dash.stderr[-200:]}"})
        raise SystemExit(1)
    if not os.path.exists(dash_html):
        record({"phase": "devtime",
                "error": "dashboard artifact missing after render"})
        raise SystemExit(1)
    with open(dash_html) as f:
        page = f.read()
    if ("Device-second budget by program" not in page
            or "nanodiloco_device_seconds_total" not in page):
        record({"phase": "devtime",
                "error": "dashboard page missing the device-second "
                         "budget section"})
        raise SystemExit(1)
    record({
        "phase": "devtime",
        "backend_live": live,
        "attributed_device_s": round(attributed, 6),
        "counter_device_s": round(serve_total, 6),
        "kv_block_seconds": round(kv_block_attr, 6),
        "priority_classes": sorted(classes_seen),
        "programs": sorted(prog_samples),
        "healthz_device_seconds_total": health_total,
        "dashboard_bytes": len(page),
    })


PHASES = {
    "bench": phase_bench,
    "sweep": phase_sweep,
    "pallas": phase_pallas,
    "profile": phase_profile,
    "telemetry": phase_telemetry,
    "async_overlap": phase_async_overlap,
    "live_profile": phase_live_profile,
    "resilience": phase_resilience,
    "goodput": phase_goodput,
    "elastic": phase_elastic,
    "serve": phase_serve,
    "serve_interference": phase_serve_interference,
    "kv_paging": phase_kv_paging,
    "spec_decode": phase_spec_decode,
    "tp_decode": phase_tp_decode,
    "fleet": phase_fleet,
    "chaos": phase_chaos,
    "disagg": phase_disagg,
    "trace": phase_trace,
    "slo_watch": phase_slo_watch,
    "autoscale_surge": phase_autoscale_surge,
    "devtime": phase_devtime,
}


if os.environ.get("NANODILOCO_AGENDA_SELFTEST"):
    # Test-only phase (tests/test_chip_agenda.py): the round-5 wedge is a
    # native sleep no in-process watchdog can interrupt, so the recovery
    # mechanics — parent deadline, process-GROUP SIGTERM (bench's
    # grandchild holds the claim), crash-traceback capture — live in the
    # parent and are exercised here with a plain sleep standing in for
    # the wedge. Gated on env so the real agenda surface is unchanged.
    def phase_selftest() -> None:
        mode = os.environ["NANODILOCO_AGENDA_SELFTEST"]
        if mode == "wedge":
            gc = subprocess.Popen(
                [sys.executable, "-c", "import time; time.sleep(600)"]
            )
            record({"phase": "selftest", "grandchild_pid": gc.pid})
            time.sleep(600)
        elif mode == "crash":
            raise RuntimeError("selftest crash")
        record({"phase": "selftest", "status": "ran"})

    PHASES["selftest"] = phase_selftest


# Per-phase wall-clock ceilings for the CHILD process running each
# phase. A phase can hang inside native code where no in-process
# watchdog (SIGALRM included) can fire — Python signal handlers need the
# interpreter loop — so the parent enforces these from outside.
PHASE_TIMEOUT_S = {
    "bench": 2400,
    "sweep": 3600,
    "pallas": 2700,
    "profile": 1200,
    "telemetry": 900,
    "async_overlap": 900,
    "live_profile": 900,
    "resilience": 1200,
    "goodput": 1200,
    "elastic": 1200,
    "serve": 900,
    "serve_interference": 900,
    "kv_paging": 900,
    "spec_decode": 900,
    "tp_decode": 1200,
    "fleet": 1800,
    "chaos": 900,
    "disagg": 1200,
    "trace": 900,
    "slo_watch": 1500,
    "autoscale_surge": 1800,
    "devtime": 1200,
}


def _phase_timeout(name: str) -> float:
    """Deadline for one phase child; ``NANODILOCO_AGENDA_TIMEOUT_<PHASE>``
    overrides (the only way to drive the deadline path in a test
    without a 40-minute wait)."""
    return float(
        os.environ.get(
            f"NANODILOCO_AGENDA_TIMEOUT_{name.upper()}",
            PHASE_TIMEOUT_S.get(name, 600),  # .get: the selftest phase
        )
    )


def _run_phase_child(name: str) -> str:
    """Run one phase in its own process group with a hard deadline.

    Returns "ok" | "wedged" | "crashed". The child appends its own
    records to the shared JSONL as it goes, so partial results survive a
    mid-phase termination. The whole process GROUP is signalled: bench
    spawns a grandchild (bench.py) that holds the chip claim and would
    otherwise survive its parent's death and wedge every later phase.
    SIGTERM-first with a grace period — SIGTERM is the interrupt proven
    to release the claim cleanly; SIGKILL mid-compile is the documented
    claim-wedging event and stays the last resort.
    """
    import signal

    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", name],
        start_new_session=True,
    )
    try:
        proc.wait(timeout=_phase_timeout(name))
        if proc.returncode == 0:
            return "ok"
        if proc.returncode < 0:
            # killed by a signal (segfault, OOM-kill): the child never
            # reached its own crash recorder, so the parent must speak —
            # the JSONL is the only diagnostic in an unattended window
            record({
                "phase": name,
                "status": "crashed",
                "signal": -proc.returncode,
            })
        # sweep the group on ANY failure, not just the timeout path: an
        # OOM-killed bench child leaves its bench.py grandchild alive
        # (start_new_session orphan) holding the single-claimant chip,
        # which would silently wedge every later phase
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        return "crashed"
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
        return "wedged"


def main() -> None:
    args = sys.argv[1:]
    if args[:1] == ["--probe"]:
        # exit-code contract: 0 = live accelerator, 2 = not live, any
        # other nonzero = the probe itself broke
        raise SystemExit(probe_status())
    if args[:1] == ["--child"]:
        # child mode: execute exactly one phase in THIS process (it may
        # claim the chip); the parent owns the deadline. A crash is
        # recorded HERE with its traceback — the JSONL is the only
        # diagnostic hours later in an unattended recovery window.
        # Validate the phase name BEFORE dispatch, mirroring the
        # parent's unknown-phase check: a bare KeyError/IndexError here
        # (e.g. a selftest phase name without NANODILOCO_AGENDA_SELFTEST
        # in the child env) would be recorded as a confusing phase crash
        # (ADVICE r5 low).
        if len(args) < 2 or args[1] not in PHASES:
            raise SystemExit(
                f"--child needs one phase name from {list(PHASES)}; got "
                f"{args[1:] or 'nothing'} (selftest phases require "
                "NANODILOCO_AGENDA_SELFTEST in this process's env)"
            )
        try:
            PHASES[args[1]]()
        except Exception as e:
            import traceback

            record({
                "phase": args[1],
                "status": "crashed",
                "error": f"{type(e).__name__}: {e}"[:400],
                "traceback": traceback.format_exc()[-1200:],
            })
            raise SystemExit(1)
        return
    resume = "--resume" in args
    args = [a for a in args if a != "--resume"]
    names = args or list(PHASES)
    unknown = [n for n in names if n not in PHASES]
    if unknown:
        raise SystemExit(f"unknown phases {unknown}; choose from {list(PHASES)}")
    if resume and os.path.exists(OUT):
        # skip phases whose latest terminal record WITHIN THE CURRENT
        # SESSION is a success — a retried agenda must not spend its
        # window re-measuring
        # 1-2 h of succeeded phases (and must not re-touch
        # bench_baseline.json with a rerun). Scoped to the most recent
        # session marker: the JSONL is a permanent append-only ledger,
        # and a 'done' from LAST week's watch run must not satisfy THIS
        # week's evidence capture.
        last = {}
        with open(OUT) as f:
            for ln in f:
                try:
                    r = json.loads(ln)
                except ValueError:
                    continue
                if r.get("phase") == "agenda" and r.get("status") == "session":
                    last = {}  # newer session: everything before is history
                elif r.get("phase") in PHASES and r.get("status") in (
                    "done", "wedged", "crashed"
                ):
                    last[r["phase"]] = r["status"]
        skipped = [n for n in names if last.get(n) == "done"]
        names = [n for n in names if last.get(n) != "done"]
        if skipped:
            record({"phase": "resume", "skipping_done": skipped})
    elif not resume:
        # fresh (non-resume) run: open a new session scope in the ledger
        record({"phase": "agenda", "status": "session"})
    # canonical order regardless of argv: bench first keeps the headline
    # number ahead of the exploratory sweeps in a short recovery window
    names = [n for n in PHASES if n in names]
    if os.environ.get("NANODILOCO_AGENDA_SKIP_PROBE"):
        # test hook: the liveness probe strips JAX_PLATFORMS by design
        # (it must never declare a cpu-pinned shell "live"), so a test on
        # a machine whose accelerator claim is wedged would hang 150 s
        # per probe; the selftest phases never touch an accelerator
        live = True
    elif os.environ.get("NANODILOCO_AGENDA_ASSUME_LIVE"):
        # the caller probed seconds ago: skip the redundant initial
        # probe; re-probes after a timed-out phase still run
        live = True
    else:
        live = chip_is_live()
    if not live:
        record({"phase": "abort", "reason": "accelerator claim not available"})
        raise SystemExit(1)
    failed = []
    for name in names:
        record({"phase": name, "status": "start"})
        status = _run_phase_child(name)
        if status == "ok":
            record({"phase": name, "status": "done"})
            continue
        failed.append(name)
        if status == "wedged":
            # crashes record themselves (with traceback) in the child;
            # a wedge never reaches Python there, so the parent speaks
            record({
                "phase": name,
                "status": "wedged",
                "timeout_s": _phase_timeout(name),
            })
        if status == "wedged" and not (
            os.environ.get("NANODILOCO_AGENDA_SKIP_PROBE") or chip_is_live()
        ):
            # the claim did not come back after terminating the wedged
            # phase — later phases would wedge identically; hand control
            # back to the watcher instead of burning its agenda window
            record({
                "phase": "abort",
                "reason": f"claim dead after wedged phase {name!r}",
                "remaining": [n for n in names if names.index(n) > names.index(name)],
            })
            raise SystemExit(2)
    if failed:
        raise SystemExit(f"phases failed: {failed} (see {OUT})")


if __name__ == "__main__":
    main()
