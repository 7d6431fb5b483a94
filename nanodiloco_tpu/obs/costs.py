"""XLA cost analytics: what the compiler says a program costs.

PERF.md's MFU table is hand-derived — a formula multiplied by a
measured tokens/sec. That formula (``train_flops_per_token``, moved
here from bench.py so there is ONE implementation) is an analytic
claim about the model; XLA's own cost model is an analytic claim about
the PROGRAM actually lowered (fusion choices, remat recompute, the
one-hot MoE dispatch einsums — everything the hand formula has to
approximate). Capturing ``cost_analysis()`` from the fused round
program at lowering time and logging it ONCE into the run JSONL turns
"measured MFU vs what the program should cost" into a computed,
regression-gateable artifact (``report cost``, ``mfu_analytic`` in
``report compare``).

Scope honesty: where the backend can walk un-compiled HLO (the CPU)
the numbers come from ``Lowered.cost_analysis()`` — a trace + StableHLO
emission (seconds, host-only), no second XLA compile; matmul/attention
FLOPs — the MFU numerator — are invariant under the optimization passes
that follow, and ``bytes accessed`` is the pre-fusion estimate, which
overstates what the optimized program touches (trend tracking, not an
HBM-traffic truth). The TPU plug-in answers nothing there, so on the
chip ``lowered_cost`` compiles the program and reads the executable's
analysis: the same cost model after optimization, paid for with one
compile of each analysed program at start-up (the persistent compile
cache hands the dispatched program back). A Pallas kernel is a custom
call the cost model bills nothing for either way.

Loop caveat (measured, load-bearing): XLA's cost model counts each
``while``/``scan`` BODY exactly once, whatever the trip count — in
both the pre-optimization (``Lowered``) and compiled analyses. This
codebase scans over layers, CE chunks, grad-accum microbatches, and
the round's H steps, so the dispatched executable's billed FLOPs are
one layer + one chunk + one microbatch worth of compute plus the tails
— NOT normalizable per token. The cost record therefore carries TWO
views: the raw ``flops_billed``/``bytes_accessed_billed`` of the real
executable (trend tracking: a new fusion or an extra collective moves
them), and a per-token ``flops`` from a PROBE lowering of one
microbatch's fwd+bwd with every scan force-unrolled
(``unrolled_scans``), where the cost model genuinely bills all L
layers and every CE chunk. The probe has abstract inputs and is never
executed (nor compiled, where lowering alone yields the analysis).

No jax import at module level (obs/ stays importable host-side
everywhere); functions that need the backend import it lazily.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any


@contextmanager
def unrolled_scans():
    """Force every ``jax.lax.scan`` lowered inside this context to
    fully unroll — so a cost-analysis probe bills ALL loop iterations
    instead of XLA's body-counted-once default (module docstring).
    Analysis-only tool: an unrolled 32-layer stack is a big StableHLO
    module that never runs. Patches the module attribute the
    model code calls (``jax.lax.scan``), restores it on exit; callers
    hold no other tracing in flight (the train loop probes once, before
    round 1's dispatch)."""
    import jax

    orig = jax.lax.scan

    def scan(f, init, xs=None, length=None, **kwargs):
        kwargs["unroll"] = True
        return orig(f, init, xs=xs, length=length, **kwargs)

    jax.lax.scan = scan
    try:
        yield
    finally:
        jax.lax.scan = orig

# bf16 peak TFLOP/s per chip, keyed by the EXACT ``device_kind`` string
# jax reports. Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v5e: 197, v5p: 459, v6e: 918,
# v4: 275); the kind strings are those of jax 0.9.0's own table
# (jax/_src/pallas/mosaic/tpu_info.py). "TPU v5 lite" is the one this
# stack has been seen to report (chip_smoke.py, PR 21). A kind that is
# not here is an error, not a default: add it with its source.
PEAK_TFLOPS_BY_KIND = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5": 459.0,        # v5p
    "TPU v6 lite": 918.0,   # v6e
}


def detect_peak_tflops() -> tuple[float | None, str]:
    """(bf16 peak TFLOP/s per chip, device kind string) for the current
    backend. The peak is None on the CPU backend only — a CPU run is
    asked for by name and has no MFU; an accelerator whose kind is not
    in the table raises, so no MFU is ever computed against a guessed
    ceiling."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None, dev.device_kind
    if dev.device_kind not in PEAK_TFLOPS_BY_KIND:
        raise ValueError(
            f"no bf16 peak known for device kind {dev.device_kind!r}; add "
            "it to obs/costs.py PEAK_TFLOPS_BY_KIND with its source"
        )
    return PEAK_TFLOPS_BY_KIND[dev.device_kind], dev.device_kind


def train_flops_per_token(cfg, seq: int, moe_tokens: int | None = None) -> float:
    """Matmul FLOPs per trained token, fwd+bwd (3x fwd): 6 x matmul
    params (embedding lookup excluded, lm_head included) plus attention
    scores/values 12*L*S*d (non-causal convention). For MoE, executed
    FLOPs means (a) the expert FFN counts the slots actually COMPUTED
    (dense dispatch runs E x C = k x capacity_factor slot-passes per
    token), not all E experts' parameters, and (b) the dense
    dispatch/combine one-hot einsums are counted too — they are real
    MXU matmuls of the same order as the FFN at bench shapes, O(T) per
    token like attention (``moe_tokens`` = the T = batch x seq the
    [T, E, C] routing tensors span; defaults to ``seq``)."""
    matmul_params = cfg.num_params() - cfg.vocab_size * cfg.hidden_size
    out = 12.0 * cfg.num_hidden_layers * seq * cfg.hidden_size
    if cfg.num_experts:
        d, f = cfg.hidden_size, cfg.intermediate_size
        kcf = cfg.num_experts_per_tok * cfg.expert_capacity_factor
        all_experts = 3 * cfg.num_experts * d * f
        matmul_params += cfg.num_hidden_layers * (3 * d * f * kcf - all_experts)
        t = moe_tokens if moe_tokens is not None else seq
        # dispatch ('tec,td->ecd') + combine ('tec,ecd->td'): E*C*d MACs
        # per token each, E*C ~= kcf*T -> 2 einsums x 3 (fwd+bwd) x
        # 2 FLOPs/MAC
        out += 12.0 * cfg.num_hidden_layers * kcf * t * d
    return 6.0 * matmul_params + out


def lowered_cost(lowered) -> dict[str, float] | None:
    """``{"flops", "bytes_accessed"}`` of one ``jax.stages.Lowered``
    program. ``Lowered.cost_analysis()`` where the backend walks
    un-compiled HLO (the CPU does); through the TPU plug-in it returns
    nothing (measured on a v5e, PR 21), and the program is compiled and
    the executable's own analysis read instead — the same cost model
    after optimization, matmul FLOPs unchanged, at the price of one
    compile (a persistent-cache hit when the program is dispatched
    afterwards). None when neither reports anything usable — callers
    must treat that as "no analytics", never as zero cost."""

    def usable(ca) -> dict[str, float] | None:
        # a dict on some releases, a one-element list of dicts on others
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not isinstance(ca, dict):
            return None
        out: dict[str, float] = {}
        flops = ca.get("flops")
        if isinstance(flops, (int, float)) and flops > 0:
            out["flops"] = float(flops)
        ba = ca.get("bytes accessed")
        if isinstance(ba, (int, float)) and ba > 0:
            out["bytes_accessed"] = float(ba)
        return out or None

    try:
        return usable(lowered.cost_analysis()) or usable(
            lowered.compile().cost_analysis()
        )
    except Exception:
        return None


def build_cost_record(
    *,
    program: str,
    billed: dict[str, float] | None = None,
    probe: dict[str, float] | None = None,
    probe_tokens: int = 0,
    num_devices: int = 1,
    model_cfg=None,
    seq: int | None = None,
    moe_tokens: int | None = None,
    attention_paths: dict[str, int] | None = None,
) -> dict[str, Any]:
    """The one-time ``cost_analysis`` JSONL record: the raw XLA numbers
    plus everything a later ``report cost`` needs without re-deriving
    state — per-token normalization, the hand formula captured at the
    SAME shapes (fit_vocab shrinks included), and the chip peak known
    at capture time (a JSONL scraped off a pod must not need the chip
    to compute MFU).

    ``billed`` is the dispatched executable's own analysis (loop bodies
    counted once — module docstring); ``probe`` is the unrolled
    one-microbatch fwd+bwd over ``probe_tokens`` tokens, the basis for
    ``flops_per_token`` and therefore analytic MFU. ``attention_paths``
    (``Diloco.attention_paths``) is the count of the model's layers whose
    attention the program runs through the fused kernel and through dense
    blocks: the FLOPs are the same, the seconds are not."""
    rec: dict[str, Any] = {
        "program": program,
        "num_devices": int(num_devices),
    }
    if attention_paths is not None:
        rec["attention_paths"] = dict(attention_paths)
    if billed:
        if "flops" in billed:
            rec["flops_billed"] = billed["flops"]
        if "bytes_accessed" in billed:
            rec["bytes_accessed_billed"] = billed["bytes_accessed"]
    if probe and probe_tokens > 0 and "flops" in probe:
        rec["flops"] = probe["flops"]
        rec["tokens_counted"] = int(probe_tokens)
        rec["flops_per_token"] = probe["flops"] / probe_tokens
    if model_cfg is not None and seq:
        rec["flops_per_token_hand"] = train_flops_per_token(
            model_cfg, seq, moe_tokens=moe_tokens
        )
    peak, kind = detect_peak_tflops()
    if peak:
        rec["peak_tflops"] = peak
    rec["device_kind"] = kind
    return rec


def analytic_mfu(
    cost: dict[str, Any], tokens_per_sec: float
) -> float | None:
    """Measured global tokens/sec x the program's analytic FLOPs/token,
    against the captured per-chip peak x device count. None when the
    record lacks a peak (a CPU run) — no fake ceilings."""
    fpt = cost.get("flops_per_token")
    peak = cost.get("peak_tflops")
    n_dev = cost.get("num_devices") or 1
    if not (fpt and peak and tokens_per_sec and tokens_per_sec > 0):
        return None
    return tokens_per_sec * fpt / (n_dev * peak * 1e12)
