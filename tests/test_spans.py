"""The program's spans and scopes (ISSUE 24): ``trace_span`` on the
profiler's clock, the serving thread's spans under one ``sched.tick``,
``token_s`` stamps in the scheduler's result, and the named scopes in
the lowered text of the training round and the paged serve programs.
CPU, seconds each; a CPU trace shows host spans, never device scopes."""

import ast
import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.models import LlamaConfig, init_params
from nanodiloco_tpu.models.generate import (
    decode_slots_paged_fn,
    init_kv_pool,
    prefill_chunk_paged_fn,
)
from nanodiloco_tpu.obs import telemetry, tracer
from nanodiloco_tpu.obs.tracer import SpanTracer, set_tracer, trace_span
from nanodiloco_tpu.serve import (
    GenRequest,
    InferenceEngine,
    Scheduler,
    ServeServer,
    http_post_json,
)

# a config of this file's own: LlamaConfig hashes by value, and an equal
# one elsewhere would share its cached jits and their compile counts
CFG = LlamaConfig(
    vocab_size=96, hidden_size=32, intermediate_size=64,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
    max_position_embeddings=64, loss_chunk=16,
)


def _program_spans(trace_dir):
    """(thread, start, end, name, stats) of every host event with a
    ``layer`` stat in the newest trace under ``trace_dir``."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "layer" in stats:
                    out.append((line.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, ev.name, stats))
    return sorted(out, key=lambda e: (e[1], -e[2]))


class _Profile:
    """A CPU profiler capture with the program's own options."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        telemetry.start_profile(self.dir)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


# -- trace_span on two clocks -----------------------------------------------


def test_trace_span_reaches_the_profiler_with_its_layer_and_nests(tmp_path):
    with _Profile(tmp_path):
        with trace_span("sched.tick"):
            with trace_span("engine.start_prefill", rid="req-7", slot=3):
                time.sleep(0.001)
            with trace_span("inner", layer="train", round=4):
                time.sleep(0.001)
    spans = _program_spans(tmp_path)
    assert [s[3] for s in spans] == ["sched.tick", "engine.start_prefill", "inner"]
    tick, start, inner = spans
    assert tick[4]["layer"] == "sched" and inner[4] == {"layer": "train", "round": 4}
    assert start[4] == {"layer": "engine", "rid": "req-7", "slot": 3}
    # parentage is nesting on the thread
    assert tick[1] <= start[1] and start[2] <= inner[1] and inner[2] <= tick[2]
    assert len({s[0] for s in spans}) == 1


def test_trace_span_records_on_the_installed_tracer_too():
    mine = SpanTracer()
    prev = set_tracer(mine)
    try:
        with trace_span("data"):
            with trace_span("diloco.round", step=3):
                pass
    finally:
        set_tracer(prev)
    assert [(e["name"], e["depth"], e.get("args")) for e in mine.events] == [
        ("diloco.round", 1, {"step": 3}), ("data", 0, None)]


def test_trace_span_without_profiler_or_tracer_records_nothing():
    assert tracer.current_tracer().events == []
    with trace_span("sched.tick"):
        with trace_span("sched.admit", rid="x"):
            pass
    with pytest.raises(KeyError):
        with trace_span("sched.deliver"):
            raise KeyError("the span closes, the error goes on")
    assert tracer.current_tracer().events == []
    assert tracer.current_tracer().phase_totals() == {}


def test_tracer_module_imports_no_jax_at_import():
    with open(tracer.__file__) as f:
        tree = ast.parse(f.read())
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = [a.name for n in top if isinstance(n, ast.Import) for a in n.names]
    names += [n.module or "" for n in top if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n == "jax" or n.startswith("jax.")]


def test_round_annotated_groups_units_by_round(tmp_path):
    from nanodiloco_tpu.training.train_loop import _round_annotated

    with _Profile(tmp_path):
        seen = []
        for step in _round_annotated(range(1, 7), lambda s: (s - 1) // 3):
            with trace_span("inner", layer="train", step=step):
                seen.append(step)
    assert seen == [1, 2, 3, 4, 5, 6]
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    rounds = [(ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              for line in plane.lines for ev in line.events
              if ev.name.startswith("round")]
    assert [r[2]["step_num"] for r in sorted(rounds)] == [0, 1]
    inner = _program_spans(tmp_path)
    for lo, hi, stats in rounds:  # each round holds its three steps
        inside = [s[4]["step"] for s in inner if lo <= s[1] and s[2] <= hi]
        assert inside == [3 * stats["step_num"] + i for i in (1, 2, 3)]


def test_live_capture_shows_the_programs_spans(tmp_path):
    stop = threading.Event()

    def work():
        while not stop.is_set():
            with trace_span("sched.tick"):
                time.sleep(0.002)

    t = threading.Thread(target=work)
    t.start()
    try:
        got = telemetry.capture_live_profile(str(tmp_path), 0.2)
    finally:
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive()
    spans = _program_spans(got["trace_dir"])
    assert spans and {s[3] for s in spans} == {"sched.tick"}


# -- a stamp on every served token ------------------------------------------


class _TickingClock:
    """Monotonic fake: every reading is ``step`` later than the last."""

    def __init__(self, step=0.25):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


class _VectorBackend:
    """Scripted backend whose ``step`` hands each live slot the next
    ``width`` tokens of its script at once (a verify tick's vector)."""

    def __init__(self, script, width=1, num_slots=1):
        self.num_slots, self.script, self.width = num_slots, list(script), width
        self.cursor = 0

    def start_prefill(self, slot, request):
        return 1

    def prefill_step(self, slot):
        self.cursor = 1
        return self.script[0]

    def step(self):
        out = self.script[self.cursor:self.cursor + self.width]
        self.cursor += self.width
        return [out]

    def release(self, slot):
        pass


def _finish(sched, ticket):
    for _ in range(50):
        sched.tick()
        if ticket.done():
            return ticket.result
    raise AssertionError("scheduler did not finish the request")


def test_token_s_has_one_nondecreasing_stamp_a_token_from_ttft():
    sched = Scheduler(_VectorBackend([10, 11, 12, 13, 14]), clock=_TickingClock())
    res = _finish(sched, sched.submit(GenRequest(prompt=(5,), max_new_tokens=5)))
    assert res["tokens"] == [10, 11, 12, 13, 14]
    assert len(res["token_s"]) == 5 and res["token_s"][0] == res["ttft_s"]
    gaps = np.diff(res["token_s"])
    assert (gaps > 0).all()  # one decode tick a token, each later than the last
    assert res["token_s"][-1] <= res["total_s"]


def test_token_s_shares_one_stamp_across_a_verify_ticks_vector():
    sched = Scheduler(_VectorBackend([10, 11, 12, 13, 14, 15, 16], width=3),
                      clock=_TickingClock())
    res = _finish(sched, sched.submit(GenRequest(prompt=(5,), max_new_tokens=7)))
    s = res["token_s"]
    assert len(s) == len(res["tokens"]) == 7
    assert s[1] == s[2] == s[3] and s[4] == s[5] == s[6] and s[0] < s[1] < s[4]


def test_token_s_is_cut_with_the_tokens_at_a_stop():
    sched = Scheduler(_VectorBackend([10, 11, 12, 99, 14, 15, 16], width=3),
                      clock=_TickingClock())
    res = _finish(sched, sched.submit(
        GenRequest(prompt=(5,), max_new_tokens=7, stop_token=99)))
    assert res["finish_reason"] == "stop" and res["tokens"] == [10, 11, 12, 99]
    assert len(res["token_s"]) == 4
    dropped = Scheduler(_VectorBackend([1]), clock=_TickingClock())
    t = dropped.submit(GenRequest(prompt=(5,), max_new_tokens=2, deadline_s=0.01))
    assert _finish(dropped, t)["token_s"] == []


# -- the serving thread's spans, on a real engine ---------------------------


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


def test_one_engine_tick_shows_its_spans_in_order_under_one_tick(params, tmp_path):
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, chunk_size=8,
                          kv_block_size=4)
    sched = Scheduler(eng)
    warm = sched.submit(GenRequest(prompt=(1, 2, 3), max_new_tokens=3))
    _finish(sched, warm)  # compiles; the slot is free again
    first = sched.submit(GenRequest(prompt=(4, 5, 6), max_new_tokens=4))
    sched.tick()          # admits and prefills: the slot now decodes
    second = sched.submit(GenRequest(prompt=(7, 8, 9, 1), max_new_tokens=2))
    with _Profile(tmp_path):
        sched.tick()      # the traced tick: admit, a chunk, a decode tick
    _finish(sched, first), _finish(sched, second)
    spans = _program_spans(tmp_path)
    ticks = [s for s in spans if s[3] == "sched.tick"]
    assert len(ticks) == 1
    lo, hi = ticks[0][1], ticks[0][2]
    assert all(lo <= s[1] and s[2] <= hi for s in spans)
    assert [s[3] for s in spans] == [
        "sched.tick", "sched.control", "sched.expire",
        "sched.admit", "engine.start_prefill",
        "sched.prefill", "engine.keys", "engine.stage_chunk",
        "engine.prefill_chunk", "engine.keys",
        "engine.stage", "engine.decode_dispatch", "engine.fetch_tokens",
        "engine.advance", "sched.deliver", "sched.retire"]
    by_name = {s[3]: s for s in spans}
    assert by_name["engine.start_prefill"][4]["rid"] == second.result["request_id"]
    assert by_name["sched.retire"][4]["rid"] == second.result["request_id"]
    assert {s[4]["layer"] for s in spans} == {"sched", "engine"}

    def inside(child, parent):
        return (by_name[parent][1] <= by_name[child][1]
                and by_name[child][2] <= by_name[parent][2])

    assert inside("engine.start_prefill", "sched.admit")
    assert inside("engine.prefill_chunk", "sched.prefill")
    assert inside("sched.retire", "sched.deliver")
    assert not inside("engine.stage", "sched.prefill")


def test_server_timing_carries_token_s(params):
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, chunk_size=8,
                          kv_block_size=4)
    server = ServeServer(Scheduler(eng), None, port=0, host="127.0.0.1").start()
    try:
        status, out = http_post_json(
            f"http://127.0.0.1:{server.port}/v1/generate",
            {"token_ids": [3, 1, 4, 1, 5], "max_new_tokens": 6,
             "temperature": 0.0, "stop": False}, timeout=120)
    finally:
        server.stop()
    assert status == 200 and len(out["token_ids"]) == 6
    stamps = out["timing"]["token_s"]
    assert len(stamps) == 6 and stamps == sorted(stamps)
    assert abs(stamps[0] - out["timing"]["ttft_s"]) < 1e-5  # rounded to 10 us
    assert stamps[-1] <= out["timing"]["total_s"] + 1e-5


# -- named scopes in the lowered programs -----------------------------------


def _scoped(text, scope):
    """Whether ``scope`` stands as a whole word of some location's name
    stack in the lowered text (``jvp(attention)/dot_general``)."""
    return any(re.search(rf"(^|[/(]){scope}([/)]|$)", name)
               for name in re.findall(r'loc\("([^"]+)"', text))


@pytest.fixture(scope="module")
def round_text():
    from nanodiloco_tpu import Diloco, DilocoConfig
    from nanodiloco_tpu.parallel import MeshConfig, build_mesh

    dl = Diloco(CFG, DilocoConfig(num_workers=1, inner_steps=2, warmup_steps=2,
                                  total_steps=20, lr=1e-3, grad_accum=1),
                build_mesh(MeshConfig(diloco=1), devices=jax.devices()[:1]))
    state = dl.init_state(jax.random.key(0))
    tok = jnp.zeros((2, 1, 1, 2, 16), jnp.int32)
    return dl._round_jit.lower(state, tok, jnp.ones_like(tok)).as_text(
        debug_info=True)


@pytest.mark.parametrize("scope", ["embed", "norm", "attn_proj", "attention",
                                   "mlp", "loss", "inner_opt", "outer",
                                   "layer_scan"])
def test_round_step_names_its_layers(round_text, scope):
    assert _scoped(round_text, scope)


@pytest.fixture(scope="module")
def serve_texts(params):
    slots, blocks, bs, width = 2, 8, 4, 4
    pool = init_kv_pool(CFG, blocks, bs)
    f32, i32 = jnp.float32, jnp.int32
    decode = decode_slots_paged_fn(CFG).lower(
        params, pool, jnp.zeros((slots, width), i32), jnp.zeros(slots, i32),
        jnp.zeros(slots, i32), jnp.zeros((slots, 2), jnp.uint32),
        jnp.zeros(slots, f32), jnp.zeros(slots, i32), jnp.ones(slots, f32),
        jnp.ones(slots, i32))
    prefill = prefill_chunk_paged_fn(CFG).lower(
        params, pool, jnp.zeros(width, i32), jnp.zeros((1, 8), i32),
        jnp.ones((1, 8), i32), jnp.int32(0), jnp.int32(7),
        jnp.zeros(2, jnp.uint32), f32(0.0), jnp.int32(0), f32(1.0))
    return {"decode": decode.as_text(debug_info=True),
            "prefill": prefill.as_text(debug_info=True)}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scope", ["embed", "norm", "attn_proj", "kv_write",
                                   "kv_gather", "attention", "mlp", "head",
                                   "sample", "layer_scan"])
def test_paged_serve_programs_name_their_layers(serve_texts, program, scope):
    assert _scoped(serve_texts[program], scope)
