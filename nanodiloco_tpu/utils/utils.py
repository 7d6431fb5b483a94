"""Seeding and run naming (≡ ref nanodiloco/training_utils/utils.py).

Note on seeding: JAX threads explicit PRNG keys through everything, so
``set_seed_all`` only pins the host-side generators (numpy/random) used
by the data pipeline — there is no global device RNG to seed, which is
itself a reproducibility upgrade over the torch stack (ref utils.py:11-15).
"""

from __future__ import annotations

import os
import random
import uuid
from datetime import datetime

import numpy as np


def set_seed_all(seed: int = 42) -> None:
    random.seed(seed)
    np.random.seed(seed)


_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str | None:
    """Place JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this function sets no path; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so it
    never derives from a temporary name, pid or time). Called at the
    process entry points (CLI subcommands that compile, ``bench.py``,
    ``chip_smoke.py``, ``scripts/serve_bench.py``) so one command's
    processes share compilations. An unusable directory raises: a run
    that was told where to cache and cannot must say so. Returns None
    only when the cache was switched off from outside with JAX's own
    ``JAX_ENABLE_COMPILATION_CACHE=false``. Touches no backend."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    # cache every program, however small or fast to compile: a serve
    # process dispatches dozens of sub-second programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_memory_stats() -> dict[str, int]:
    """{"hbm_bytes_in_use": ..., "hbm_peak_bytes": ...} from the first
    addressable device, or {} where the backend has no memory_stats
    (CPU). The per-sync observability line the reference never had —
    an OOM trajectory is visible in the JSONL before it kills the run."""
    import jax

    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return {}
    out = {}
    if "bytes_in_use" in stats:
        out["hbm_bytes_in_use"] = int(stats["bytes_in_use"])
    if "peak_bytes_in_use" in stats:
        out["hbm_peak_bytes"] = int(stats["peak_bytes_in_use"])
    return out


def require_accelerator(what: str) -> str:
    """Return the backend's platform, refusing a CPU nobody asked for.

    JAX falls back to the CPU with a warning when it finds no
    accelerator; a measurement taken there would be filed under a
    device metric's name. Measurement entry points (``bench.py``,
    ``scripts/serve_bench.py``) call this first. A CPU run for tests is
    asked for by name: ``JAX_PLATFORMS=cpu`` or ``--force-cpu-devices``.
    Initializes the backend."""
    import jax

    platform = jax.default_backend()
    if platform == "cpu" and not (jax.config.jax_platforms or "").startswith("cpu"):
        raise RuntimeError(
            f"{what}: JAX found no accelerator and fell back to the CPU. "
            "A measurement needs the chip; for a CPU run ask for one "
            "(JAX_PLATFORMS=cpu or --force-cpu-devices N)"
        )
    return platform


def force_virtual_cpu_devices(n: int, strict: bool = True) -> bool:
    """Reconfigure JAX to expose ``n`` virtual CPU devices for sharding
    dev/debug. Must run before ANYTHING initializes a backend (even
    ``jax.devices()``). Returns True on success; if the
    backend is already live, raises (strict) or returns False so callers
    can fall back to whatever devices exist."""
    import jax

    try:
        # num_cpu_devices first: it is the update that detects (and
        # rejects) an already-initialized backend.
        jax.config.update("jax_num_cpu_devices", n)
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        if strict:
            raise RuntimeError(
                f"cannot reconfigure to {n} virtual CPU devices: a JAX "
                f"backend is already initialized — call this before any "
                f"jax operation in the process"
            )
        return False
    return True


def probe_backend(
    probe_timeout: int = 150,
    require_accelerator: bool = False,
    strip_jax_platforms: bool = False,
) -> tuple[int, bytes]:
    """Liveness probe for ``scripts/chip_agenda.py --probe`` (ROADMAP C1
    removes both together): a jitted bf16 matmul run END TO END in a
    child process, through backend init and the first compile, under a
    deadline. A timed-out child is escalated SIGINT (short grace) →
    SIGTERM → SIGKILL, and whatever it wrote to stderr is returned —
    the only record of which phase hung.

    Returns ``(code, stderr)``: 0 = live, 2 = timed out (or CPU-only
    when ``require_accelerator``), 1 = the probe child itself broke.
    ``strip_jax_platforms`` ignores a JAX_PLATFORMS=cpu override in the
    caller's environment, so a cpu-pinned shell never reads as a live
    accelerator."""
    import signal
    import subprocess
    import sys

    env = None
    if strip_jax_platforms:
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    code = (
        "import jax, jax.numpy as jnp, sys; "
        "x = jnp.ones((256, 256), jnp.bfloat16); "
        "(x @ x).block_until_ready(); "
        "sys.exit(0 if jax.default_backend() != 'cpu' else 3)"
        if require_accelerator
        else "import jax, jax.numpy as jnp; "
        "x = jnp.ones((256, 256), jnp.bfloat16); "
        "(x @ x).block_until_ready()"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", code],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
    )
    try:
        _, err = proc.communicate(timeout=probe_timeout)
        if proc.returncode == 0:
            return 0, err
        if require_accelerator and proc.returncode == 3:
            return 2, err  # healthy backend, but it is CPU: not live
        return 1, err
    except subprocess.TimeoutExpired:
        err = b""
        proc.send_signal(signal.SIGINT)
        try:
            _, err = proc.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                _, err = proc.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                _, err = proc.communicate()
        return 2, err or b""


def create_run_name(
    experiment_type: str, node_config: dict | None = None, is_debug: bool = False
) -> str:
    """Hierarchical run name ``{type}_n{N}_{loc}_{MMDD_HHMM}_{uuid8}``
    (≡ ref utils.py:18-39)."""
    node_config = node_config or {}
    parts = [experiment_type]
    if node_config.get("nodes"):
        parts.append(f"n{node_config['nodes']}")
    if node_config.get("location"):
        parts.append(str(node_config["location"]))
    parts.append(datetime.now().strftime("%m%d_%H%M"))
    if is_debug:
        parts.insert(0, "debug")
    return "_".join(parts) + f"_{str(uuid.uuid4())[:8]}"


def resolve_run_name(local_name: str, max_len: int = 128) -> str:
    """Make every host in a multi-process job agree on ONE run name.

    ``create_run_name`` embeds a per-process timestamp and uuid, so on a
    pod each host would derive a different name — N wandb runs and N
    JSONL files for one job. The reference has the same divergence
    (per-rank uuid name, ref utils.py:18-39) and only dodges it by
    initializing wandb on rank 0 (ref main.py:71-73) while still calling
    ``wandb.log`` on every node's local rank 0 (ref main.py:118-127), a
    latent crash. Here the fix is structural: broadcast process 0's name
    bytes to all hosts, so agreement holds by construction.

    Single-process (and the virtual-device test meshes): pass-through.
    """
    import jax

    if jax.process_count() == 1:
        return local_name
    import numpy as np
    from jax.experimental import multihost_utils

    buf = np.zeros(max_len, np.uint8)
    enc = local_name.encode()[:max_len]
    buf[: len(enc)] = np.frombuffer(enc, np.uint8)
    out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return bytes(out).rstrip(b"\x00").decode(errors="replace")


def allreduce_wire_report(
    hlo_text: str, scale_leaves: int = 16
) -> tuple[list[str], list[str]]:
    """Classify a compiled module's all-reduce operands for wire audits.

    Returns ``(integer_results, wide_float_results)``: the result-type
    strings (possibly tuples — XLA's combiner merges per-leaf psums)
    of all-reduce ops that carry a signed-int payload, and of those
    that carry any float tensor OTHER than the integer wire's two
    legitimate bookkeeping shapes: the shared absmax pmax — one f32
    vector of exactly ``[scale_leaves]`` elements (pass the synced
    pytree's leaf count) — and the f32 survivor-count scalar. Matching
    the exact expected shape replaces the old size threshold
    (``> max(16, scale_leaves)``), which let a genuinely leaked f32
    payload of up to ``scale_leaves`` elements escape the audit — a
    false-negative window that GREW with tree size (ADVICE r5 low);
    now only a leak that is f32 of exactly the leaf count could slip
    through. Used by the integer-wire HLO tests (tests/test_diloco.py)
    and the multichip dryrun (__graft_entry__.py) so the parsing lives
    in ONE place — if XLA's text format changes (e.g.
    all-reduce-start/done pairs), fix it here."""
    import re

    results = [
        l.split(" all-reduce(")[0]
        for l in hlo_text.splitlines()
        if " all-reduce(" in l and "=" in l
    ] + [
        l.split(" all-reduce-start(")[0]
        for l in hlo_text.splitlines()
        if " all-reduce-start(" in l and "=" in l
    ]
    int_payload = [r for r in results if re.search(r"s(8|16|32)\[", r)]
    expected = int(scale_leaves)
    wide_float = []
    for r in results:
        for m in re.finditer(r"(f64|f32|f16|bf16)\[([0-9,]*)\]", r):
            dims = [int(d) for d in m.group(2).split(",") if d]
            scalar = not dims
            scale_vec = m.group(1) == "f32" and dims == [expected]
            if not (scalar or scale_vec):
                wide_float.append(r)
                break
    return int_payload, wide_float
