"""Fleet tier tests (nanodiloco_tpu/fleet + the serve hot-swap path).

Three layers, each on its own terms:

- ENGINE hot-swap bit-parity: a swap mid-stream keeps every in-flight
  stream bit-identical to solo ``generate()`` on the OLD weights while
  post-swap admissions are bit-identical on the NEW ones — two block
  sizes, mid-decode and mid-prefill — plus prefix-cache invalidation,
  rollback bit-exactness, and loud shape validation.
- ROUTER/CONTROLLER policy units: scripted probe/post + injected
  clock, no sockets, no model — least-loaded pick from the gauges,
  healthz-503 ejection with the blackbox attached, drain completing
  in-flight before the swap, canary promote/rollback decisions.
- WIRE: a 2-replica in-process fleet over real sockets — the
  CPU acceptance path (zero dropped in-flight requests across a
  fleet-wide push, pre-swap streams on old weights, post-swap on new).
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanodiloco_tpu.fleet import DeployController, FleetRouter, Replica
from nanodiloco_tpu.models import LlamaConfig, generate, init_params
from nanodiloco_tpu.serve import (
    GenRequest,
    InferenceEngine,
    Scheduler,
    ServeServer,
    http_get,
    http_post_json,
)

CFG = LlamaConfig(
    vocab_size=128, hidden_size=64, intermediate_size=128,
    num_attention_heads=4, num_hidden_layers=2, max_position_embeddings=64,
)

KV_MODES = [
    pytest.param({}, id="default"),   # blocks of 16 rows, clamped to the chunk
    pytest.param({"kv_block_size": 4}, id="bs4"),
]


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def params2():
    return init_params(jax.random.key(1), CFG)


def _reference(params, req: GenRequest):
    out = generate(
        params, jnp.asarray([req.prompt], jnp.int32), CFG,
        req.max_new_tokens, temperature=req.temperature, top_k=req.top_k,
        top_p=req.top_p, key=jax.random.key(req.seed),
    )
    return np.asarray(out[0]).tolist()


def _drain_sched(sched, tickets, limit=60):
    for _ in range(limit):
        if sched.tick() == 0 and all(t.done() for t in tickets):
            return
    raise AssertionError("scheduler did not drain")


# -- engine hot-swap bit-parity ----------------------------------------------


@pytest.mark.parametrize("kv", KV_MODES)
def test_swap_mid_decode_old_stream_old_weights_new_admission_new(
    params, params2, kv
):
    """THE hot-swap acceptance: a stream in flight at the swap finishes
    bit-identical to solo generate() on the OLD weights; an admission
    after the swap is bit-identical on the NEW weights — the KV pool
    and the neighbour's slot survive the swap untouched."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32, **kv)
    sched = Scheduler(eng)
    old_req = GenRequest(prompt=(5, 9, 2, 11, 3), max_new_tokens=10,
                         temperature=0.8, top_k=20, seed=7)
    new_req = GenRequest(prompt=(7, 1, 4), max_new_tokens=6,
                         temperature=0.7, top_p=0.9, seed=3)
    with jax.default_matmul_precision("highest"):
        t_old = sched.submit(old_req)
        sched.tick()
        sched.tick()
        sched.tick()            # old stream is mid-decode
        handle = sched.call_on_tick(lambda: eng.swap_weights(params2))
        t_new = sched.submit(new_req)
        _drain_sched(sched, (t_old, t_new))
        refs = (_reference(params, old_req), _reference(params2, new_req))
    assert handle.done() and handle.error is None
    assert handle.result == 1 == eng.deploy_generation
    assert t_old.result["tokens"] == refs[0]
    assert t_new.result["tokens"] == refs[1]
    # the old generation's params were released with its last stream
    assert set(eng._params_by_gen) == {1}


@pytest.mark.parametrize("kv", KV_MODES)
def test_swap_mid_prefill_completes_on_admission_weights(
    params, params2, kv
):
    """A swap landing BETWEEN two prefill chunks: the remaining chunks
    and the whole decode run on the weights the request was ADMITTED
    under — generation is tagged at staging, not per chunk."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, **kv)
    sched = Scheduler(eng)
    req = GenRequest(prompt=tuple((7 * i + 3) % 50 + 1 for i in range(13)),
                     max_new_tokens=4, temperature=0.8, top_k=12, seed=40)
    with jax.default_matmul_precision("highest"):
        ticket = sched.submit(req)
        sched.tick()            # admit + first chunk only
        handle = sched.call_on_tick(lambda: eng.swap_weights(params2))
        _drain_sched(sched, (ticket,))
        ref = _reference(params, req)
    assert handle.error is None
    assert ticket.result["tokens"] == ref


def test_swap_invalidates_prefix_cache(params, params2):
    """Satellite pin: a post-swap prefix lookup is NEVER served from
    pre-swap KV — the cache is cleared at the swap (generation tag),
    and the post-swap stream over the SAME prompt is bit-identical to
    solo generate() on the new weights."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, prefix_cache_tokens=64,
                          kv_block_size=4)
    sched = Scheduler(eng)
    prompt = tuple((3 * i + 1) % 50 + 1 for i in range(10))
    req = GenRequest(prompt=prompt, max_new_tokens=4, seed=0)
    with jax.default_matmul_precision("highest"):
        t1 = sched.submit(req)
        _drain_sched(sched, (t1,))
        # prime check: a second identical prompt would hit
        assert eng.prefix_cache.match(list(prompt) + [9],
                                      record=False) != []
        handle = sched.call_on_tick(lambda: eng.swap_weights(params2))
        t2 = sched.submit(req)
        _drain_sched(sched, (t2,))
        ref_new = _reference(params2, req)
    assert handle.error is None
    pc = eng.prefix_cache.stats()
    assert pc["generation"] == 1 and pc["invalidations"] >= 1
    # the post-swap request MISSED (its lookup found nothing cached)...
    assert pc["hit_tokens"] == 0
    # ...and its stream is pure new-weight compute
    assert t2.result["tokens"] == ref_new
    # cache repopulates under the new generation
    assert eng.prefix_cache.cached_tokens > 0


def test_old_generation_prefill_never_populates_new_cache(params, params2):
    """The subtle half of cache invalidation: a request admitted BEFORE
    the swap that finishes its prefill AFTER it must not insert its
    old-weight K/V into the freshly cleared cache — a later same-prefix
    request would hit stale rows and break bit-parity in the quietest
    possible way."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32,
                          chunk_size=4, prefix_cache_tokens=64,
                          kv_block_size=4)
    sched = Scheduler(eng)
    prompt = tuple((5 * i + 2) % 50 + 1 for i in range(13))
    req = GenRequest(prompt=prompt, max_new_tokens=3, seed=1)
    with jax.default_matmul_precision("highest"):
        t1 = sched.submit(req)
        sched.tick()            # admit + first chunk under gen 0
        sched.call_on_tick(lambda: eng.swap_weights(params2))
        _drain_sched(sched, (t1,))   # prefill completes under gen 1's cache
        # the old-generation prefill must NOT have populated the cache
        assert eng.prefix_cache.cached_tokens == 0
        t2 = sched.submit(req)
        _drain_sched(sched, (t2,))
        ref_new = _reference(params2, req)
    assert eng.prefix_cache.stats()["hit_tokens"] == 0
    assert t2.result["tokens"] == ref_new


def test_swap_rollback_restores_prior_snapshot_bit_exact(params, params2):
    """Satellite pin: swap A->B->A; a post-rollback stream is
    bit-identical to the original pre-swap stream (the rollback path
    the deploy controller takes on a failed canary)."""
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32)
    sched = Scheduler(eng)
    req = GenRequest(prompt=(5, 9, 2), max_new_tokens=8,
                     temperature=0.9, top_k=10, seed=11)
    with jax.default_matmul_precision("highest"):
        t0 = sched.submit(req)
        _drain_sched(sched, (t0,))
        sched.call_on_tick(lambda: eng.swap_weights(params2))
        sched.tick()
        sched.call_on_tick(lambda: eng.swap_weights(params))
        t1 = sched.submit(req)
        _drain_sched(sched, (t1,))
    assert eng.deploy_generation == 2
    assert t1.result["tokens"] == t0.result["tokens"]


def test_swap_validates_tree_and_shapes(params):
    """A checkpoint that does not fit the engine must be a readable
    ValueError at the swap, never a shape error out of the next tick."""
    eng = InferenceEngine(params, CFG, num_slots=1, max_len=16)
    other_cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_attention_heads=4, num_hidden_layers=2,
        max_position_embeddings=64,
    )
    bad = init_params(jax.random.key(2), other_cfg)
    with pytest.raises(ValueError, match="swap_weights"):
        eng.swap_weights(bad)
    assert eng.deploy_generation == 0  # nothing half-swapped


# -- scheduler drain + control queue -----------------------------------------


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeBackend:
    """Minimal scripted slot backend (the scheduler-test pattern)."""

    def __init__(self, num_slots, scripts):
        self.num_slots = num_slots
        self.scripts = scripts
        self.cursor = [0] * num_slots
        self.seed_at = [None] * num_slots

    def start_prefill(self, slot, request):
        self.seed_at[slot] = request.seed
        return 1

    def prefill_step(self, slot):
        self.cursor[slot] = 1
        return self.scripts[self.seed_at[slot]][0]

    def step(self):
        out = []
        for s in range(self.num_slots):
            seed = self.seed_at[s]
            if seed is None:
                out.append(-1)
                continue
            out.append(self.scripts[seed][self.cursor[s]])
            self.cursor[s] += 1
        return out

    def release(self, slot):
        self.seed_at[slot] = None


def test_drain_stops_admission_completes_in_flight_resume_admits():
    sched = Scheduler(FakeBackend(1, {1: [10, 11], 2: [20, 21]}),
                      clock=FakeClock())
    t1 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=2, seed=1))
    sched.tick()                       # t1 admitted, prefilling
    sched.drain()
    t2 = sched.submit(GenRequest(prompt=(5,), max_new_tokens=2, seed=2))
    for _ in range(6):
        sched.tick()
    # in-flight finished; the queued request was NOT admitted
    assert t1.done() and t1.result["tokens"] == [10, 11]
    assert not t2.done()
    assert sched.in_flight() == 0 and sched.queue_depth() == 1
    assert sched.draining and sched.stats()["draining"]
    # a drain is an operator action, not a capacity stall
    assert sched.stats()["admission_blocked_no_slot"] == 0
    sched.resume()
    for _ in range(6):
        sched.tick()
    assert t2.done() and t2.result["tokens"] == [20, 21]


def test_call_on_tick_runs_on_tick_thread_and_captures_errors():
    sched = Scheduler(FakeBackend(1, {}), clock=FakeClock())
    order = []
    ok = sched.call_on_tick(lambda: order.append("ran") or 42)
    boom = sched.call_on_tick(lambda: (_ for _ in ()).throw(
        ValueError("bad checkpoint")
    ))
    assert not ok.done()               # nothing runs off-tick
    sched.tick()
    assert ok.done() and ok.result == 42 and ok.error is None
    assert boom.done() and "bad checkpoint" in boom.error
    # an erroring control fn never killed the loop
    sched.tick()


# -- router policy (scripted probes, injected clock) --------------------------


class ScriptedFleet:
    """Scripted probe/post for a router under test: per-replica health
    docs the test mutates, and a log of every admin/generate post."""

    def __init__(self, names):
        self.docs = {
            n: {"reachable": True, "live": True, "ready": True,
                "stats": {"queue_depth": 0, "slots_busy": 0,
                          "kv_blocks_free": 10, "in_flight": 0}}
            for n in names
        }
        self.posts = []
        self.generate_reply = {}   # name -> (code, doc) override

    def probe(self, replica):
        d = self.docs[replica.name]
        return {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in d.items()}

    def post(self, replica, path, doc, timeout=None):
        self.posts.append((replica.name, path, dict(doc)))
        if path == "/v1/generate":
            code, out = self.generate_reply.get(
                replica.name, (200, {"token_ids": [1], "ok": True})
            )
            return code, dict(out)
        if path == "/admin/swap":
            return 200, {"swapped": True,
                         "deploy_generation": doc.get("step", 0)}
        if path == "/admin/drain":
            self.docs[replica.name]["ready"] = False
            return 200, {"draining": True}
        if path == "/admin/resume":
            self.docs[replica.name]["ready"] = True
            return 200, {"draining": False}
        raise AssertionError(path)


def _router(tmp_path, names=("r0", "r1"), blackbox=None, **kw):
    clock = FakeClock()
    fleet = ScriptedFleet(names)
    reps = [Replica(n, f"http://fake/{n}",
                    blackbox=blackbox.get(n) if blackbox else None)
            for n in names]
    router = FleetRouter(
        reps, probe=fleet.probe, post=fleet.post, clock=clock,
        sleep=lambda s: clock.advance(s),
        events_jsonl=str(tmp_path / "deploy.jsonl"), quiet=True, **kw,
    )
    return router, fleet, clock


def _events(tmp_path):
    path = tmp_path / "deploy.jsonl"
    if not path.exists():
        return []
    return [json.loads(l) for l in path.read_text().splitlines()]


def test_pick_least_loaded_from_gauges(tmp_path):
    router, fleet, _ = _router(tmp_path)
    fleet.docs["r0"]["stats"].update(queue_depth=3, slots_busy=2)
    fleet.docs["r1"]["stats"].update(queue_depth=1, slots_busy=1)
    router.health_tick()
    assert router.pick().replica.name == "r1"
    # equal load: most free KV blocks breaks the tie
    fleet.docs["r0"]["stats"].update(queue_depth=1, slots_busy=1,
                                     kv_blocks_free=50)
    router.health_tick()
    assert router.pick().replica.name == "r0"
    # a draining replica is never a candidate
    fleet.docs["r0"]["ready"] = False
    router.health_tick()
    assert router.pick().replica.name == "r1"


def test_healthz_503_ejects_immediately_with_blackbox(tmp_path):
    """An explicit /healthz 503 = the engine loop died (it never
    recovers): ejected on the FIRST probe, with the replica's flight-
    recorder dump attached to the event."""
    bb = tmp_path / "r1-blackbox.json"
    bb.write_text(json.dumps({
        "blackbox": True, "reason": "serve_loop:RuntimeError",
        "t_unix": 1.0, "events": [{"kind": "serve_finish"}] * 3,
    }))
    router, fleet, _ = _router(tmp_path, blackbox={"r1": str(bb)})
    router.health_tick()
    fleet.docs["r1"].update(live=False, ready=False)  # 503, reachable
    router.health_tick()
    assert router.state_of("r1")["status"] == "ejected"
    ev = [e for e in _events(tmp_path) if e["deploy_event"] == "eject"]
    assert len(ev) == 1
    assert ev[0]["replica"] == "r1" and ev[0]["reason"] == "healthz_503"
    assert ev[0]["blackbox"]["path"] == str(bb)
    assert ev[0]["blackbox"]["reason"] == "serve_loop:RuntimeError"
    assert ev[0]["blackbox"]["events"] == 3
    # an ejected replica never comes back as a candidate
    fleet.docs["r1"].update(live=True, ready=True)
    router.health_tick()
    assert router.state_of("r1")["status"] == "ejected"
    assert router.fleet_stats()["replicas_ejected"] == 1


def test_unreachable_ejects_only_after_failure_budget(tmp_path):
    """A refused socket may be a restart window: ejection waits for
    ``eject_after_failures`` CONSECUTIVE failures, and any live probe
    resets the count."""
    router, fleet, _ = _router(tmp_path, eject_after_failures=3)
    fleet.docs["r0"].update(reachable=False, live=False, ready=False)
    router.health_tick()
    router.health_tick()
    assert router.state_of("r0")["status"] == "serving"  # 2 < 3
    fleet.docs["r0"].update(reachable=True, live=True, ready=True)
    router.health_tick()                                 # recovery resets
    fleet.docs["r0"].update(reachable=False, live=False, ready=False)
    router.health_tick()
    router.health_tick()
    assert router.state_of("r0")["status"] == "serving"
    router.health_tick()
    assert router.state_of("r0")["status"] == "ejected"
    ev = [e for e in _events(tmp_path) if e["deploy_event"] == "eject"]
    assert len(ev) == 1 and ev[0]["reason"] == "unreachable"


def test_push_drains_waits_for_in_flight_then_swaps(tmp_path):
    """Satellite pin: the push posts /admin/swap only AFTER the drained
    replica reports zero in-flight streams — and replicas are pushed
    one at a time, drain->swap->resume each."""
    router, fleet, _ = _router(tmp_path, drain_timeout_s=10.0)
    router.health_tick()
    # r0 has 2 streams in flight; each probe after the drain sees one
    # fewer (the scripted replica finishing them)
    fleet.docs["r0"]["stats"]["in_flight"] = 2
    orig_probe = fleet.probe

    def finishing_probe(replica):
        out = orig_probe(replica)
        fleet.docs[replica.name]["stats"]["in_flight"] = max(
            0, fleet.docs[replica.name]["stats"]["in_flight"] - 1
        )
        return out

    router._probe = finishing_probe
    results = router.push_weights("/ckpt", 4)
    assert [r["ok"] for r in results] == [True, True]
    r0_posts = [(n, p) for n, p, _ in fleet.posts if n == "r0"]
    assert r0_posts == [("r0", "/admin/drain"), ("r0", "/admin/swap"),
                        ("r0", "/admin/resume")]
    # strict one-at-a-time: r0's whole cycle precedes r1's first post
    seq = [(n, p) for n, p, _ in fleet.posts]
    assert seq.index(("r1", "/admin/drain")) > seq.index(
        ("r0", "/admin/resume")
    )
    swaps = [d for n, p, d in fleet.posts if p == "/admin/swap"]
    assert all(d == {"checkpoint_dir": "/ckpt", "step": 4} for d in swaps)
    kinds = [e["deploy_event"] for e in _events(tmp_path)]
    assert kinds == ["drain", "swap", "drain", "swap"]
    gens = router.fleet_stats()["deploy_generations"]
    assert gens == {"r0": 4, "r1": 4}


def test_push_does_not_resurrect_replica_ejected_mid_push(tmp_path):
    """A replica that dies (and is ejected by the health loop) WHILE
    its push is in flight must stay ejected — the push's cleanup paths
    must not put a corpse back into the serving set (which would
    re-route traffic to it and double-count its re-ejection)."""
    router, fleet, _ = _router(tmp_path, drain_timeout_s=0.1)
    router.health_tick()
    orig_post = fleet.post

    def dying_post(replica, path, doc, timeout=None):
        if path == "/admin/swap" and replica.name == "r0":
            # the health loop notices the death first and ejects...
            fleet.docs["r0"].update(reachable=False, live=False,
                                    ready=False)
            with router._lock:
                router._eject_locked(router._by_name["r0"],
                                     "unreachable")
            # ...then the push's own post fails
            raise OSError("connection refused")
        return orig_post(replica, path, doc, timeout)

    router._post = dying_post
    results = router.push_weights("/ckpt", 4, replicas=["r0"])
    assert results[0]["ok"] is False
    assert router.state_of("r0")["status"] == "ejected"   # NOT serving
    ev = [e["deploy_event"] for e in _events(tmp_path)]
    assert ev.count("eject") == 1


def test_non_json_replica_body_is_a_failed_push_not_a_crash(tmp_path):
    """A replica answering plain text (an old serve without /admin
    routes, a proxy error page) raises JSONDecodeError out of the wire
    helper — that must become a swap_failed result, never an exception
    that kills the deploy controller's thread."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    orig_post = fleet.post

    def text_post(replica, path, doc, timeout=None):
        if path == "/admin/swap":
            raise json.JSONDecodeError("not json", "not found\n", 0)
        return orig_post(replica, path, doc, timeout)

    router._post = text_post
    results = router.push_weights("/ckpt", 4, replicas=["r0"])
    assert results[0]["ok"] is False
    ev = [e["deploy_event"] for e in _events(tmp_path)]
    assert "swap_failed" in ev
    # the replica was not ejected (it is alive, just old) and is still
    # a serving candidate
    assert router.state_of("r0")["status"] == "serving"
    # CRITICAL: the failed push still posted /admin/resume — a drained
    # replica left draining admits nothing forever
    assert ("r0", "/admin/resume") in [(n, p) for n, p, _ in fleet.posts]


def test_concurrent_pushes_serialize(tmp_path):
    """The controller thread and an operator /fleet/push must never
    interleave drain/swap/resume cycles on the same replica — whole
    pushes serialize under the push lock."""
    router, fleet, _ = _router(tmp_path, drain_timeout_s=0.01)
    router.health_tick()
    threads = [threading.Thread(target=router.push_weights,
                                args=("/ckpt", s)) for s in (4, 6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # each replica saw two complete drain->swap->resume cycles, never
    # an interleaved one
    for name in ("r0", "r1"):
        seq = [p for n, p, _ in fleet.posts if n == name]
        assert seq == ["/admin/drain", "/admin/swap", "/admin/resume"] * 2
    # and the two pushes' swap steps were not mixed within one replica
    steps = [d["step"] for n, p, d in fleet.posts
             if n == "r0" and p == "/admin/swap"]
    assert sorted(steps) == [4, 6]


def test_generate_retries_429_on_another_replica(tmp_path):
    """A 429 is THAT replica's queue, not fleet-wide backpressure: the
    router tries another ready replica; only when every candidate is
    saturated does the client see the (honest) 429."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    fleet.generate_reply["r0"] = (429, {"error": "queue full"})
    # r0 looks least-loaded (stale view) but answers 429 -> retry on r1
    fleet.docs["r1"]["stats"].update(queue_depth=5)
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 200 and out["replica"] == "r1"
    # both saturated: the client gets 429, never a fake 503
    fleet.generate_reply["r1"] = (429, {"error": "queue full"})
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 429


def test_generate_routes_and_retries_on_503(tmp_path):
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    fleet.docs["r0"]["stats"].update(queue_depth=5)
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 200 and out["replica"] == "r1"
    # r1 starts answering 503: the request retries on r0
    fleet.generate_reply["r1"] = (503, {"error": "loop dead"})
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 200 and out["replica"] == "r0"


def test_generate_request_id_rides_the_retry_and_served_by_is_echoed(
    tmp_path,
):
    """The request_id propagation regression: a client-supplied
    request_id must be forwarded on BOTH attempts — the retry replica
    used to be the one place the join key could vanish, which broke
    the router-span/replica-span trace join for exactly the requests
    that needed diagnosing. The response names the replica that
    actually served it (served_by), not just the first pick."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    # r0 is the pick (least loaded) but answers 503 -> retry on r1
    fleet.docs["r1"]["stats"].update(queue_depth=5)
    router.health_tick()
    fleet.generate_reply["r0"] = (503, {"error": "draining"})
    code, out = router.handle_generate(
        {"token_ids": [1], "request_id": "cli-77"}
    )
    assert code == 200
    assert out["served_by"] == "r1" and out["replica"] == "r1"
    assert out["request_id"] == "cli-77"
    gen_posts = [(n, d) for n, p, d in fleet.posts if p == "/v1/generate"]
    assert [n for n, _ in gen_posts] == ["r0", "r1"]
    assert all(d["request_id"] == "cli-77" for _, d in gen_posts)


def test_generate_stamps_one_request_id_when_client_sent_none(tmp_path):
    """No client id: the router stamps ONE rtr-<n> id that rides every
    attempt and is echoed in the response — the cross-tier join key
    exists for every request, not just the well-behaved clients'."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    fleet.generate_reply["r0"] = (503, {"error": "draining"})
    fleet.docs["r1"]["stats"].update(queue_depth=5)
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 200
    gen_posts = [d for _n, p, d in fleet.posts if p == "/v1/generate"]
    assert len(gen_posts) == 2
    stamped = gen_posts[0]["request_id"]
    assert stamped.startswith("rtr-")
    assert gen_posts[1]["request_id"] == stamped  # SAME id on the retry
    assert out["request_id"] == stamped
    # and the no-replica failure still names the id for client logs
    fleet.generate_reply["r1"] = (503, {"error": "dead"})
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1]})
    assert code == 503 and out["request_id"].startswith("rtr-")


def test_router_records_route_and_forward_spans_with_request_id(tmp_path):
    from nanodiloco_tpu.obs import SpanTracer

    clock = FakeClock()
    fleet = ScriptedFleet(("r0", "r1"))
    tracer = SpanTracer(clock=clock, process_name="nanodiloco router")
    router = FleetRouter(
        [Replica("r0", "http://fake/r0"), Replica("r1", "http://fake/r1")],
        probe=fleet.probe, post=fleet.post, clock=clock,
        sleep=lambda s: clock.advance(s), tracer=tracer, quiet=True,
    )
    router.health_tick()
    fleet.generate_reply["r0"] = (503, {"error": "draining"})
    fleet.docs["r1"]["stats"].update(queue_depth=5)
    router.health_tick()
    code, out = router.handle_generate(
        {"token_ids": [1], "request_id": "trace-me"}
    )
    assert code == 200
    spans = {(e["name"], e["args"].get("replica"))
             for e in tracer.events
             if e.get("args", {}).get("request_id") == "trace-me"}
    # one forward per attempt (the retry flagged), one route envelope
    assert ("forward", "r0") in spans and ("forward", "r1") in spans
    assert ("route", None) in spans
    retry_flags = [e["args"]["retry"] for e in tracer.events
                   if e["name"] == "forward"]
    assert retry_flags == [False, True]


def test_fleet_goodput_partitions_replica_seconds(tmp_path):
    """Every replica-second lands in a state bucket; the fleet goodput
    fraction is ready-seconds / (elapsed x replicas)."""
    router, fleet, clock = _router(tmp_path)
    router.health_tick()     # both ready at t=0
    clock.advance(10.0)
    fleet.docs["r1"].update(live=False, ready=False)  # r1 dies at t=10
    router.health_tick()
    clock.advance(10.0)
    s = router.fleet_stats()
    assert s["elapsed_s"] == pytest.approx(20.0)
    # r0: 20s ready; r1: 10s ready + 10s ejected -> 30/(20*2)
    assert s["fleet_goodput_fraction"] == pytest.approx(0.75)
    assert s["replica_seconds"]["r1"]["ejected"] == pytest.approx(10.0)


# -- class-aware admission + elastic membership (scripted router) -------------


def test_router_sheds_class_above_ceiling_without_touching_replicas(
    tmp_path,
):
    """Front-door shedding: a request above the admission ceiling gets
    the honest terminal 429 — shed:true, its class, the ceiling — and
    NEVER reaches a replica (it is fleet policy, not backpressure)."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    router.set_admission(2, reason="test pressure")
    code, out = router.handle_generate({"token_ids": [1], "priority": 5})
    assert code == 429
    assert out["shed"] is True and out["shed_class"] == 5
    assert out["max_priority"] == 2 and out["request_id"]
    assert fleet.posts == []                       # policy, not forwarding
    # a class AT the ceiling is admitted normally
    code, out = router.handle_generate({"token_ids": [1], "priority": 2})
    assert code == 200
    s = router.fleet_stats()
    assert s["admission_max_priority"] == 2
    assert s["shed_by_class"] == {5: 1}
    # the change itself is an auditable event
    ev = [e for e in _events(tmp_path) if e["deploy_event"] == "shed_level"]
    assert len(ev) == 1 and ev[0]["max_priority"] == 2
    assert ev[0]["reason"] == "test pressure"
    # idempotent sets log nothing new
    router.set_admission(2)
    assert len([e for e in _events(tmp_path)
                if e["deploy_event"] == "shed_level"]) == 1


def test_replica_shed_429_is_terminal_but_busy_429_retries(tmp_path):
    """The satellite retry fix: a replica-side 429 CARRYING shed:true
    is the same fleet policy seen late — propagated verbatim, no retry
    (every replica enforces the same ceiling); a busy 429 (no shed key)
    still tries the other replica."""
    router, fleet, _ = _router(tmp_path)
    router.health_tick()
    fleet.generate_reply["r0"] = (429, {
        "error": "shed", "shed": True, "shed_class": 3, "max_priority": 1,
    })
    fleet.docs["r1"]["stats"].update(queue_depth=5)  # r0 is the pick
    router.health_tick()
    code, out = router.handle_generate({"token_ids": [1], "priority": 3})
    assert code == 429 and out["shed"] is True and out["shed_class"] == 3
    gen_posts = [n for n, p, _ in fleet.posts if p == "/v1/generate"]
    assert gen_posts == ["r0"]                     # terminal: ONE attempt
    assert router.fleet_stats()["shed_by_class"] == {3: 1}
    # contrast: a plain busy 429 from the same pick retries on r1
    fleet.generate_reply["r0"] = (429, {"error": "queue full"})
    code, out = router.handle_generate({"token_ids": [1], "priority": 3})
    assert code == 200 and out["served_by"] == "r1"


def test_fleet_admission_endpoint_sets_and_validates(tmp_path):
    router, fleet, _ = _router(tmp_path)
    code, out = router.handle_admission({"max_priority": 2})
    assert code == 200 and out["max_priority"] == 2
    assert router.admission_max_priority() == 2
    # -1 admits nothing (full shed); out-of-range / non-int are 400s
    code, _ = router.handle_admission({"max_priority": -1})
    assert code == 200
    for bad in (10, -2, "3", True, None):
        code, out = router.handle_admission({"max_priority": bad})
        assert code == 400 and "max_priority" in out["error"]
    assert router.admission_max_priority() == -1   # bad sets changed nothing


def test_elastic_membership_books_every_replica_second(tmp_path):
    """The autoscaler's accounting contract: a joined replica's boot
    seconds land in ``scaling_up`` (no failure budget while booting),
    promotion to serving happens on the first live+ready probe, and a
    removed replica's whole life survives in the departed ledger — the
    goodput denominator never loses a second."""
    router, fleet, clock = _router(tmp_path)
    router.health_tick()                           # r0/r1 ready at t=0
    clock.advance(5.0)
    router.add_replica(Replica("a1", "http://fake/a1"))
    assert router.state_of("a1")["status"] == "scaling_up"
    assert router.fleet_stats()["replicas_scaling_up"] == 1
    # booting: unreachable probes cost nothing, forever
    fleet.docs["a1"] = {"reachable": False, "live": False, "ready": False,
                        "stats": {}}
    for _ in range(10):
        router.health_tick()
    st = router.state_of("a1")
    assert st["status"] == "scaling_up" and st["failures"] == 0
    clock.advance(3.0)                             # 3s of boot
    fleet.docs["a1"].update(reachable=True, live=True, ready=True)
    router.health_tick()                           # first ready probe
    assert router.state_of("a1")["status"] == "serving"
    clock.advance(2.0)                             # 2s of service
    s = router.fleet_stats()
    assert s["replica_seconds"]["a1"]["scaling_up"] == pytest.approx(3.0)
    assert s["replica_seconds"]["a1"]["serving_ready"] == pytest.approx(2.0)
    # retire it: the ledger keeps its life, the fleet forgets the name
    router.remove_replica("a1", drain=False, reason="scale_down")
    s = router.fleet_stats()
    assert "a1" not in s["replica_seconds"]
    assert s["replicas_departed"] == 1
    assert s["seconds_by_state"]["scaling_up"] == pytest.approx(3.0)
    # r0+r1: 10s ready each; a1: 3s boot + 2s ready -> 22/25
    assert s["fleet_goodput_fraction"] == pytest.approx(22.0 / 25.0)
    ev = [e["deploy_event"] for e in _events(tmp_path)]
    assert "replica_added" in ev and "replica_removed" in ev
    removed = next(e for e in _events(tmp_path)
                   if e["deploy_event"] == "replica_removed")
    assert removed["seconds"]["scaling_up"] == pytest.approx(3.0)
    # membership errors are loud
    with pytest.raises(ValueError):
        router.add_replica(Replica("r0", "http://fake/dup"))
    with pytest.raises(ValueError):
        router.remove_replica("a1")


def test_remove_replica_drains_in_flight_before_dropping(tmp_path):
    """Scale-in goes through the drain discipline: /admin/drain first,
    then the drop waits until the replica reports zero in-flight."""
    router, fleet, clock = _router(tmp_path, drain_timeout_s=10.0)
    router.health_tick()
    fleet.docs["r1"]["stats"]["in_flight"] = 2
    orig_probe = fleet.probe

    def finishing_probe(replica):
        out = orig_probe(replica)
        fleet.docs[replica.name]["stats"]["in_flight"] = max(
            0, fleet.docs[replica.name]["stats"]["in_flight"] - 1
        )
        return out

    router._probe = finishing_probe
    router.remove_replica("r1", drain=True)
    assert ("r1", "/admin/drain") in [(n, p) for n, p, _ in fleet.posts]
    assert router.replica_names() == ["r0"]


# -- deploy controller (scripted router + bench) ------------------------------


def _controller(tmp_path, bench_records, initial_step=2):
    """A controller over a scripted 2-replica router; ``bench_records``
    maps step -> canary record (the injected bench)."""
    router, fleet, clock = _router(tmp_path, drain_timeout_s=0.1)
    router.health_tick()
    benched = []

    def bench(url, ckpt, step):
        benched.append(step)
        rec = bench_records[step]
        if isinstance(rec, Exception):
            raise rec
        return dict(rec)

    ctl = DeployController(router, str(tmp_path / "ckpt"),
                           initial_step=initial_step, bench=bench)
    return ctl, router, fleet, benched


GOOD = {"canary_eval_loss": 3.0, "ttft_p50_s": 0.05,
        "client_tokens_per_sec": 100.0, "errors": 0, "requests": 4}
BETTER = {**GOOD, "canary_eval_loss": 2.8}
WORSE = {**GOOD, "canary_eval_loss": 3.5}


def test_controller_promotes_on_passing_verdict(tmp_path):
    ctl, router, fleet, benched = _controller(
        tmp_path, {2: GOOD, 4: BETTER}
    )
    assert ctl.deploy(4) == "promote"
    # baseline benched once (the deployed step), then the candidate
    assert benched == [2, 4]
    assert ctl.deployed_step == 4
    kinds = [e["deploy_event"] for e in _events(tmp_path)]
    assert kinds == ["canary_start", "canary_baseline",
                     "drain", "swap",          # canary push (r0)
                     "canary_verdict",
                     "drain", "swap",          # fleet push (r1)
                     "promote"]
    promote = _events(tmp_path)[-1]
    assert promote["step"] == 4 and promote["replicas"] == ["r0", "r1"]
    # the canary swapped first; the rest of the fleet only after the
    # verdict passed
    seq = [(n, p) for n, p, _ in fleet.posts if p == "/admin/swap"]
    assert seq == [("r0", "/admin/swap"), ("r1", "/admin/swap")]


def test_controller_rolls_back_on_regression(tmp_path):
    """A regressing checkpoint (eval loss up past the gate) reaches the
    CANARY only: the fleet never sees it, the canary is re-swapped to
    the prior snapshot, and the verdict lands in the deploy JSONL."""
    ctl, router, fleet, benched = _controller(
        tmp_path, {2: GOOD, 4: WORSE}
    )
    assert ctl.deploy(4) == "rollback"
    assert ctl.deployed_step == 2
    assert 4 in ctl.failed_steps
    events = _events(tmp_path)
    verdict = next(e for e in events
                   if e["deploy_event"] == "canary_verdict")
    assert verdict["ok"] is False
    assert "canary_eval_loss" in verdict["regressions"]
    rollback = next(e for e in events if e["deploy_event"] == "rollback")
    assert rollback["step"] == 4 and rollback["restored_step"] == 2
    # swaps: canary to 4, canary back to 2 — r1 NEVER swapped
    swaps = [(n, d["step"]) for n, p, d in fleet.posts
             if p == "/admin/swap"]
    assert swaps == [("r0", 4), ("r0", 2)]
    # a rolled-back step is never re-canaried by the watcher
    assert ctl.poll_once() is None or 4 not in [ctl.deployed_step]


def test_controller_first_deploy_verdict_failure_is_rollback_failed(
    tmp_path
):
    """A failed verdict with NO prior deployed step (first-ever
    deployment, no --initial-step) has nothing to restore: the event
    must be rollback_failed — the timeline never claims a rollback
    that did not happen, and the canary is known to still serve the
    rejected weights."""
    ctl, _, _, _ = _controller(
        tmp_path, {4: {**GOOD, "errors": 3}}, initial_step=None,
    )
    assert ctl.deploy(4) == "rollback_failed"
    kinds = [e["deploy_event"] for e in _events(tmp_path)]
    assert "rollback_failed" in kinds and "rollback" not in kinds
    ev = next(e for e in _events(tmp_path)
              if e["deploy_event"] == "rollback_failed")
    assert ev["restored_step"] is None and "error" in ev


def test_controller_nonfinite_eval_loss_is_automatic_regression(tmp_path):
    """NaN compares false against every threshold — without the
    explicit rule a NaN checkpoint would sail through compare_runs."""
    ctl, _, _, _ = _controller(
        tmp_path, {2: GOOD, 4: {**GOOD, "canary_eval_loss": float("nan")}}
    )
    assert ctl.deploy(4) == "rollback"
    verdict = next(e for e in _events(tmp_path)
                   if e["deploy_event"] == "canary_verdict")
    assert "canary_eval_loss_nonfinite" in verdict["regressions"]


def test_controller_failed_rollback_push_is_not_reported_as_rollback(
    tmp_path
):
    """The deploy timeline must never CLAIM a rollback that did not
    happen: when the restore push itself fails (prior checkpoint GC'd,
    canary dead), the event is rollback_failed — the canary is still
    serving the regressing weights and the record says so."""
    ctl, router, fleet, _ = _controller(tmp_path, {2: GOOD, 4: WORSE})
    orig_post = fleet.post

    def failing_restore(replica, path, doc, timeout=None):
        if path == "/admin/swap" and doc.get("step") == 2:
            return 400, {"error": "cannot load checkpoint: GC'd"}
        return orig_post(replica, path, doc, timeout)

    router._post = failing_restore
    assert ctl.deploy(4) == "rollback_failed"
    kinds = [e["deploy_event"] for e in _events(tmp_path)]
    assert "rollback_failed" in kinds and "rollback" not in kinds
    assert 4 in ctl.failed_steps          # still never re-canaried


def test_controller_baseline_failure_does_not_blacklist_candidate(
    tmp_path
):
    """A missing/unloadable BASELINE (deployed checkpoint GC'd by
    retention) is not the candidate's fault: the canary proceeds
    baseline-less (first-deployment semantics) instead of blacklisting
    every future checkpoint and stalling deployment forever."""
    ctl, router, fleet, benched = _controller(
        tmp_path,
        {2: FileNotFoundError("no checkpoint at step 2"), 4: BETTER},
    )
    assert ctl.deploy(4) == "promote"
    assert ctl.deployed_step == 4
    kinds = [e["deploy_event"] for e in _events(tmp_path)]
    assert "canary_baseline_failed" in kinds and "promote" in kinds
    # the candidate's own gate still applies baseline-less: NaN fails
    # (the scripted fleet's rollback push succeeds, so this is a clean
    # "rollback", and the verdict for step 6 is a recorded failure)
    ctl2, _, _, _ = _controller(
        tmp_path,
        {4: FileNotFoundError("gone"),
         6: {**GOOD, "canary_eval_loss": float("nan")}},
        initial_step=4,
    )
    assert ctl2.deploy(6) == "rollback"
    verdicts = [e for e in _events(tmp_path)
                if e["deploy_event"] == "canary_verdict"
                and e["step"] == 6]
    assert verdicts and not verdicts[-1]["ok"]


def test_controller_request_errors_fail_the_canary(tmp_path):
    ctl, _, _, _ = _controller(
        tmp_path, {2: GOOD, 4: {**BETTER, "errors": 2}}
    )
    assert ctl.deploy(4) == "rollback"
    verdict = next(e for e in _events(tmp_path)
                   if e["deploy_event"] == "canary_verdict")
    assert "canary_request_errors" in verdict["regressions"]


def test_controller_transient_push_failure_is_retried_not_blacklisted(
    tmp_path
):
    """A failed canary PUSH is an infrastructure blip, not a judgment
    on the checkpoint: the step is NOT blacklisted and the next poll's
    deploy succeeds."""
    ctl, router, fleet, _ = _controller(tmp_path, {2: GOOD, 4: BETTER})
    orig_post = fleet.post
    state = {"fail": True}

    def flaky_post(replica, path, doc, timeout=None):
        if path == "/admin/swap" and state["fail"]:
            state["fail"] = False
            raise OSError("timeout")
        return orig_post(replica, path, doc, timeout)

    router._post = flaky_post
    assert ctl.deploy(4) == "canary_failed"
    assert 4 not in ctl.failed_steps      # retryable
    assert ctl.deploy(4) == "promote"     # the retry lands


def test_latest_checkpoint_step_none_without_checkpoints(tmp_path):
    from nanodiloco_tpu.fleet import latest_checkpoint_step

    assert latest_checkpoint_step(str(tmp_path / "nope")) is None


# -- replica server surface: readiness split + admin --------------------------


def test_readyz_splits_liveness_from_readiness():
    """Satellite pin: a draining replica answers /healthz 200 (alive —
    the router must NOT eject it) while /readyz and /healthz?ready=1
    answer 503 (not taking traffic)."""
    sched = Scheduler(FakeBackend(1, {1: [10, 11]}), clock=FakeClock())
    server = ServeServer(sched, port=0, host="127.0.0.1").start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        assert http_get(base + "/healthz")[0] == 200
        assert http_get(base + "/readyz")[0] == 200
        code, out = http_post_json(base + "/admin/drain", {})
        assert code == 200 and out["draining"]
        assert http_get(base + "/healthz")[0] == 200      # still ALIVE
        code, body = http_get(base + "/readyz")
        assert code == 503
        doc = json.loads(body)
        assert doc["draining"] and not doc["ready"]
        assert http_get(base + "/healthz?ready=1")[0] == 503
        # parsed, not substring-matched: a query merely CONTAINING the
        # text "ready=1" must stay a LIVENESS probe (a supervisor
        # probing liveness must never be answered with readiness)
        assert http_get(base + "/healthz?thready=1")[0] == 200
        assert http_get(base + "/healthz?x=already=1")[0] == 200
        code, _ = http_post_json(base + "/admin/resume", {})
        assert code == 200
        assert http_get(base + "/readyz")[0] == 200
        # no swap loader configured: the endpoint is a 404, not a crash
        code, out = http_post_json(base + "/admin/swap",
                                   {"checkpoint_dir": "/x"})
        assert code == 404
    finally:
        server.stop()


def test_admin_swap_over_the_wire_swaps_and_rejects_bad_requests(
    params, params2
):
    eng = InferenceEngine(params, CFG, num_slots=2, max_len=32)
    sched = Scheduler(eng)
    store = {"new": params2}
    server = ServeServer(
        sched, port=0, host="127.0.0.1",
        swap_loader=lambda ckpt, step: store[ckpt],
    ).start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        code, out = http_post_json(base + "/admin/swap", {})
        assert code == 400                    # missing checkpoint_dir
        code, out = http_post_json(
            base + "/admin/swap", {"checkpoint_dir": "missing"}
        )
        assert code == 400                    # loader KeyError -> 400
        code, out = http_post_json(
            base + "/admin/swap", {"checkpoint_dir": "new", "step": 4}
        )
        assert code == 200 and out["swapped"]
        assert out["deploy_generation"] == 1
        # the replica now reports the new generation everywhere
        doc = json.loads(http_get(base + "/readyz")[1])
        assert doc["deploy_generation"] == 1
        m = http_get(base + "/metrics")[1]
        assert "nanodiloco_deploy_generation 1" in m
        # and serves the new weights
        req = GenRequest(prompt=(5, 9, 2), max_new_tokens=6, seed=0)
        with jax.default_matmul_precision("highest"):
            code, out = http_post_json(base + "/v1/generate", {
                "token_ids": list(req.prompt), "max_new_tokens": 6,
                "temperature": 0.0, "seed": 0, "stop": False,
            })
            ref = _reference(params2, req)
        assert code == 200 and out["token_ids"] == ref
    finally:
        server.stop()


# -- the wire acceptance: 2-replica fleet, push under load --------------------


def test_fleet_push_over_real_sockets_zero_dropped_requests(
    params, params2
):
    """The CPU acceptance path minus the checkpoint files: 2 real
    replicas behind a real router; a request IN FLIGHT through the
    router while the fleet-wide push runs completes bit-identical to
    solo generate() on the OLD weights, post-push requests on the NEW
    weights, and nothing is dropped."""
    store = {"old": params, "new": params2}

    def make_replica():
        eng = InferenceEngine(params, CFG, num_slots=2, max_len=48,
                              chunk_size=8, kv_block_size=4)
        return ServeServer(
            Scheduler(eng), port=0, host="127.0.0.1",
            swap_loader=lambda ckpt, step: store[ckpt],
        ).start()

    s1, s2 = make_replica(), make_replica()
    router = FleetRouter(
        [Replica("r0", f"http://127.0.0.1:{s1.port}"),
         Replica("r1", f"http://127.0.0.1:{s2.port}")],
        port=0, host="127.0.0.1", health_interval_s=0.2,
        drain_timeout_s=15.0, quiet=True,
    ).start()
    base = f"http://127.0.0.1:{router.port}"
    doc = {"token_ids": [5, 9, 2, 11, 3], "max_new_tokens": 20,
           "temperature": 0.0, "seed": 0, "stop": False}
    results = {}

    def fire(key):
        results[key] = http_post_json(base + "/v1/generate", doc,
                                      timeout=120)

    try:
        with jax.default_matmul_precision("highest"):
            t = threading.Thread(target=fire, args=("pre",))
            t.start()
            time.sleep(0.1)       # in flight before the push begins
            pushed = router.push_weights("new")
            t.join()
            fire("post")
            req = GenRequest(prompt=tuple(doc["token_ids"]),
                             max_new_tokens=20, seed=0)
            ref_old = _reference(params, req)
            ref_new = _reference(params2, req)
        assert [r["ok"] for r in pushed] == [True, True]
        code, pre = results["pre"]
        assert code == 200, pre           # zero dropped in-flight
        assert pre["token_ids"] == ref_old
        code, post = results["post"]
        assert code == 200
        assert post["token_ids"] == ref_new
        m = http_get(base + "/metrics")[1]
        assert 'nanodiloco_deploy_generation{replica="r0"} 1' in m
        assert 'nanodiloco_deploy_generation{replica="r1"} 1' in m
    finally:
        router.stop()
        s1.stop()
        s2.stop()


# -- summarize_run fleet keys -------------------------------------------------


def test_summarize_run_surfaces_fleet_keys_and_tolerates_old_jsonls(
    tmp_path
):
    from nanodiloco_tpu.training.metrics import summarize_run

    path = tmp_path / "deploy.jsonl"
    recs = [
        {"deploy_event": "canary_start", "step": 4, "t_unix": 1.0},
        {"deploy_event": "drain", "replica": "r0", "t_unix": 1.1},
        {"deploy_event": "swap", "replica": "r0", "t_unix": 1.2},
        {"deploy_event": "promote", "step": 4, "t_unix": 1.3},
        {"deploy_event": "eject", "replica": "r1", "t_unix": 2.0,
         "reason": "healthz_503"},
        {"deploy_event": "rollback", "step": 6, "restored_step": 4,
         "t_unix": 3.0},
        {"fleet_goodput": {"replicas_total": 2, "replicas_ejected": 1,
                           "replica_ready_s": 30.0, "elapsed_s": 20.0,
                           "fleet_goodput_fraction": 0.75}},
    ]
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = summarize_run(str(path))
    assert out["deploy_events"] == 6
    assert out["deploy_kinds"]["swap"] == 1
    assert out["fleet_promotes"] == 1
    assert out["fleet_rollbacks"] == 1
    assert out["fleet_ejections"] == 1
    assert out["deployed_step_last"] == 4
    assert out["fleet_goodput_fraction"] == 0.75
    assert out["fleet_replicas"] == 2
    assert out["fleet_replicas_ejected"] == 1
    # an older JSONL without deploy records: none of the keys appear
    old = tmp_path / "old.jsonl"
    old.write_text(json.dumps({"loss": 3.0, "step": 1}) + "\n")
    out_old = summarize_run(str(old))
    assert not any(k.startswith("fleet_") or k.startswith("deploy")
                   for k in out_old)


def test_compare_gates_canary_eval_loss_both_present_only():
    from nanodiloco_tpu.training.metrics import compare_runs

    base = {"canary_eval_loss": 3.0}
    assert compare_runs(base, {"canary_eval_loss": 3.5})["regressions"] \
        == ["canary_eval_loss"]
    assert compare_runs(base, {"canary_eval_loss": 2.9})["ok"]
    # present on one side only: reported, never gated
    diff = compare_runs(base, {"final_loss": 1.0, "canary_eval_loss": 3.0})
    assert diff["ok"]
