"""Chaos harness tests (fleet/chaos.py) + the /v1/cancel hygiene the
hedge path rides on.

Three layers:

- PLAN units: schedule validation, once-per-(fault, ordinal) firing,
  request-vs-probe channel separation, the fired-record/counter
  surfaces (no sockets);
- PROXY wire behaviors against a tiny scripted upstream: every fault
  kind realized on a REAL socket — latency, error_500, garbage_json,
  reset (truncated body), blackhole (client timeout), kill (the
  harness's replica-killer hook + aborted connection), flap_health on
  probe ordinals only, and passthrough for everything else;
- CANCEL hygiene over real serve servers: ``/v1/cancel`` frees the
  slot and the KV blocks of an in-flight stream (two block sizes),
  cancels a QUEUED request before it ever decodes, and the
  router's hedge loser is cancelled over the wire with zero leaked
  slots/blocks — plus the provider discipline that a SIGKILLed (chaos-
  killed) replica is a crash, not a preemption: dropped, never
  relaunched.
"""

import http.client
import json
import os
import signal
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import pytest

from nanodiloco_tpu.fleet import (
    FleetRouter,
    ProcessReplicaProvider,
    Replica,
)
from nanodiloco_tpu.fleet.chaos import (
    DRILL_PLAN,
    KINDS,
    ChaosPlan,
    ChaosProxy,
    chaos_families,
    proxy_fleet,
)
from nanodiloco_tpu.models import LlamaConfig, init_params
from nanodiloco_tpu.serve import (
    InferenceEngine,
    Scheduler,
    ServeServer,
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


# -- plan units ---------------------------------------------------------------


def test_plan_validation_rejects_malformed_faults():
    with pytest.raises(ValueError, match="unknown kind"):
        ChaosPlan([{"kind": "meteor", "target": "r0", "requests": [1]}])
    with pytest.raises(ValueError, match="target"):
        ChaosPlan([{"kind": "latency", "requests": [1]}])
    with pytest.raises(ValueError, match="ordinals"):
        ChaosPlan([{"kind": "latency", "target": "r0", "requests": []}])
    with pytest.raises(ValueError, match="ordinals"):
        ChaosPlan([{"kind": "latency", "target": "r0",
                    "requests": [True]}])
    with pytest.raises(ValueError, match="ordinals"):
        ChaosPlan([{"kind": "reset", "target": "r0", "requests": [-1]}])
    # channel discipline: flap_health keys on PROBE ordinals, the rest
    # on request ordinals — the wrong key is a loud error, not a no-op
    with pytest.raises(ValueError, match="probes"):
        ChaosPlan([{"kind": "flap_health", "target": "r0",
                    "requests": [1]}])
    with pytest.raises(ValueError, match="requests"):
        ChaosPlan([{"kind": "latency", "target": "r0", "probes": [1]}])
    with pytest.raises(ValueError, match="seconds"):
        ChaosPlan([{"kind": "latency", "target": "r0", "requests": [1],
                    "seconds": 0}])
    with pytest.raises(ValueError, match="chunk_bytes"):
        ChaosPlan([{"kind": "slow_drip", "target": "r0",
                    "requests": [1], "chunk_bytes": 0}])
    with pytest.raises(ValueError, match="faults"):
        ChaosPlan.from_dict({"faults": "latency"})


def test_plan_take_fires_each_ordinal_exactly_once():
    plan = ChaosPlan([
        {"kind": "latency", "target": "r0", "requests": [1, 2],
         "seconds": 0.2},
        {"kind": "flap_health", "target": "r0", "probes": [1]},
    ])
    assert plan.take("request", "r0", 0) == []
    assert [f["kind"] for f in plan.take("request", "r0", 1)] == ["latency"]
    assert plan.take("request", "r0", 1) == []      # fired: never again
    # the probe channel is SEPARATE bookkeeping: request ordinal 1
    # firing did not consume probe ordinal 1
    assert [f["kind"] for f in plan.take("probe", "r0", 1)] == [
        "flap_health"]
    assert plan.take("request", "r1", 2) == []      # wrong target
    assert [f["kind"] for f in plan.take("request", "r0", 2)] == ["latency"]
    assert plan.counts() == {"flap_health": 1, "latency": 2}
    fired = plan.drain_fired()
    assert [(r["chaos"], r["ordinal"]) for r in fired] == [
        ("latency", 1), ("flap_health", 1), ("latency", 2)]
    assert all(r["target"] == "r0" for r in fired)
    assert fired[0]["seconds"] == 0.2
    assert plan.drain_fired() == []                 # drained


def test_chaos_families_shape():
    assert chaos_families({}) == []
    [(name, mtype, _, samples)] = chaos_families({"kill": 1, "reset": 2})
    assert name == "nanodiloco_chaos_injected" and mtype == "counter"
    assert ({"kind": "kill"}, 1) in samples
    assert (None, 3) in samples                     # the family total


def test_drill_plan_covers_every_kind():
    plan = ChaosPlan.from_dict(DRILL_PLAN)
    assert sorted({f["kind"] for f in plan.faults}) == sorted(KINDS)


# -- proxy wire behaviors -----------------------------------------------------


class _Upstream:
    """Tiny scripted replica: /healthz, /v1/generate with a padded body
    (so reset/slow_drip have something to truncate/drip)."""

    def __init__(self):
        up = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args):
                pass

            def _json(self, code, doc):
                raw = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(raw)))
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):
                if self.path.startswith("/healthz"):
                    self._json(200, {"alive": True})
                else:
                    self._json(200, {"path": self.path})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                if n:
                    self.rfile.read(n)
                up.hits += 1
                self._json(200, {"ok": True, "pad": "x" * 600})

        self.hits = 0
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def _raw(port, method, path, body=None, timeout=5.0):
    """One raw HTTP exchange; transport faults propagate to the test."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = json.dumps(body).encode() if body is not None else None
        conn.request(method, path, body=payload,
                     headers={"Content-Type": "application/json"}
                     if payload else {})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


@pytest.fixture()
def upstream():
    up = _Upstream()
    yield up
    up.stop()


def _proxy(upstream, faults, **kw):
    plan = ChaosPlan(faults)
    return ChaosProxy(upstream.url, plan, "r0", **kw).start(), plan


def test_proxy_passthrough_and_status(upstream):
    proxy, plan = _proxy(upstream, [
        {"kind": "error_500", "target": "r0", "requests": [0]}])
    try:
        # non-ordinal paths forward untouched and consume NO request
        # ordinal: the fault keyed on request 0 still hits the first
        # /v1/generate even after unrelated traffic
        code, body = _raw(proxy.port, "GET", "/metrics")
        assert code == 200 and json.loads(body)["path"] == "/metrics"
        code, body = _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert code == 500 and "chaos" in json.loads(body)["error"]
        assert upstream.hits == 0                   # never forwarded
        code, body = _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert code == 200 and json.loads(body)["ok"]
        assert upstream.hits == 1
        code, body = _raw(proxy.port, "GET", "/chaos/status")
        assert code == 200
        doc = json.loads(body)
        assert doc["target"] == "r0" and doc["counts"] == {"error_500": 1}
    finally:
        proxy.stop()


def test_proxy_latency_delays_but_answers(upstream):
    proxy, _ = _proxy(upstream, [
        {"kind": "latency", "target": "r0", "requests": [0],
         "seconds": 0.4}])
    try:
        t0 = time.monotonic()
        code, body = _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert code == 200 and json.loads(body)["ok"]
        assert time.monotonic() - t0 >= 0.4         # slow-but-200
    finally:
        proxy.stop()


def test_proxy_garbage_json_is_a_parse_error(upstream):
    proxy, _ = _proxy(upstream, [
        {"kind": "garbage_json", "target": "r0", "requests": [0]}])
    try:
        code, body = _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert code == 200
        with pytest.raises(json.JSONDecodeError):
            json.loads(body)
    finally:
        proxy.stop()


def test_proxy_reset_truncates_mid_body(upstream):
    proxy, _ = _proxy(upstream, [
        {"kind": "reset", "target": "r0", "requests": [0]}])
    try:
        with pytest.raises((http.client.IncompleteRead, ConnectionError,
                            OSError)):
            _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
    finally:
        proxy.stop()


def test_proxy_blackhole_holds_until_client_timeout(upstream):
    proxy, _ = _proxy(upstream, [
        {"kind": "blackhole", "target": "r0", "requests": [0],
         "seconds": 30.0}])
    try:
        t0 = time.monotonic()
        with pytest.raises(OSError):                # timeout or reset
            _raw(proxy.port, "POST", "/v1/generate", {"p": 1},
                 timeout=1.0)
        assert time.monotonic() - t0 < 5.0          # the CLIENT timed out
        assert upstream.hits == 0
    finally:
        proxy.stop()


def test_proxy_kill_invokes_harness_killer_and_aborts(upstream):
    killed = []
    proxy, plan = _proxy(upstream, [
        {"kind": "kill", "target": "r0", "requests": [0]}],
        on_kill=lambda name: (killed.append(name), upstream.stop()))
    try:
        with pytest.raises(OSError):
            _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert killed == ["r0"]
        # the replica behind the proxy is DEAD: later forwards surface
        # as aborted connections, never a synthesized status
        with pytest.raises(OSError):
            _raw(proxy.port, "POST", "/v1/generate", {"p": 1})
        assert plan.counts() == {"kill": 1}
    finally:
        proxy.stop()


def test_proxy_flap_health_keys_on_probe_ordinals(upstream):
    proxy, _ = _proxy(upstream, [
        {"kind": "flap_health", "target": "r0", "probes": [1]}])
    try:
        assert _raw(proxy.port, "GET", "/healthz")[0] == 200
        code, body = _raw(proxy.port, "GET", "/healthz")
        assert code == 503 and json.loads(body)["chaos"] == "flap_health"
        assert _raw(proxy.port, "GET", "/healthz")[0] == 200
        # generate traffic never consumed probe ordinals
        assert _raw(proxy.port, "POST", "/v1/generate", {"p": 1})[0] == 200
    finally:
        proxy.stop()


def test_proxy_fleet_preserves_names_swaps_urls(upstream):
    reps = [Replica("a", upstream.url), Replica("b", upstream.url)]
    proxied, proxies = proxy_fleet(reps, ChaosPlan([]))
    try:
        assert [r.name for r in proxied] == ["a", "b"]
        assert all(p.url == r.url for p, r in zip(proxies, proxied))
        assert all(r.url != upstream.url for r in proxied)
    finally:
        for p in proxies:
            p.stop()


# -- /v1/cancel hygiene over real serve servers -------------------------------


def _serve(params, *, num_slots=2, tick_delay_s=0.0, **kv):
    eng = InferenceEngine(params, CFG, num_slots=num_slots, max_len=64,
                          **kv)
    sched = Scheduler(eng)
    server = ServeServer(sched, port=0, host="127.0.0.1",
                         max_new_tokens_cap=64,
                         tick_delay_s=tick_delay_s).start()
    return eng, sched, server


def _post_async(url, doc):
    box = {}

    def run():
        try:
            box["resp"] = http_post_json(url, doc)
        except Exception as e:  # surfaced by the caller's assert
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def _cancel_until_ok(base, rid, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        code, out = http_post_json(base + "/v1/cancel",
                                   {"request_id": rid})
        if code == 200:
            return out
        assert code == 404                 # not registered yet
        time.sleep(0.01)
    raise AssertionError("cancel never found the request in flight")


@pytest.mark.parametrize("kv", KV_MODES)
def test_cancel_frees_slot_and_kv_blocks(params, kv):
    """THE hygiene audit: cancelling an in-flight stream over the wire
    retires it with finish_reason ``cancelled`` and returns its slot —
    and in paged mode every KV block — to the pool."""
    eng, sched, server = _serve(params, tick_delay_s=0.02, **kv)
    base = f"http://127.0.0.1:{server.port}"
    try:
        t, box = _post_async(base + "/v1/generate", {
            "token_ids": [5, 9, 2, 11], "max_new_tokens": 56,
            "temperature": 0.0, "request_id": "c1",
        })
        out = _cancel_until_ok(base, "c1")
        assert out["cancelled"] is True
        t.join(timeout=30)
        assert "error" not in box
        code, doc = box["resp"]
        assert code == 200
        assert doc["finish_reason"] == "cancelled"
        assert doc["completion_tokens"] < 56       # stopped mid-decode
        s = sched.stats()
        assert s["slots_busy"] == 0 and s["queue_depth"] == 0
        assert s["cancelled"] == 1
        kvs = eng.kv_stats()                       # zero leaked
        assert kvs["blocks_free"] == kvs["num_blocks"]
    finally:
        server.stop()


def test_cancel_queued_request_never_decodes(params):
    eng, sched, server = _serve(params, num_slots=1, tick_delay_s=0.02)
    base = f"http://127.0.0.1:{server.port}"
    try:
        ta, box_a = _post_async(base + "/v1/generate", {
            "token_ids": [1, 2, 3], "max_new_tokens": 40,
            "temperature": 0.0, "request_id": "a",
        })
        # b queues behind the single slot; cancelled there, it must
        # retire with zero output — never admitted, never decoded
        tb, box_b = _post_async(base + "/v1/generate", {
            "token_ids": [4, 5, 6], "max_new_tokens": 40,
            "temperature": 0.0, "request_id": "b",
        })
        _cancel_until_ok(base, "b")
        tb.join(timeout=30)
        code, doc = box_b["resp"]
        assert code == 200 and doc["finish_reason"] == "cancelled"
        assert doc["token_ids"] == []
        ta.join(timeout=60)
        code, doc = box_a["resp"]
        assert code == 200 and doc["finish_reason"] == "length"
        assert len(doc["token_ids"]) == 40         # a was untouched
    finally:
        server.stop()


def test_cancel_unknown_and_malformed(params):
    _, _, server = _serve(params)
    base = f"http://127.0.0.1:{server.port}"
    try:
        code, out = http_post_json(base + "/v1/cancel",
                                   {"request_id": "ghost"})
        assert code == 404 and out == {"cancelled": False,
                               "request_id": "ghost"}
        code, out = http_post_json(base + "/v1/cancel", {"request_id": 7})
        assert code == 400
    finally:
        server.stop()


@pytest.mark.parametrize("kv", KV_MODES)
def test_hedge_loser_cancelled_over_the_wire_zero_leak(params, kv):
    """Satellite pin: a hedged request against two REAL replicas — the
    slow one loses, the router cancels it over the wire, and the loser
    replica ends with zero busy slots and (paged) a full block pool."""
    eng0, sched0, s0 = _serve(params, tick_delay_s=0.03, **kv)  # slow
    eng1, sched1, s1 = _serve(params, **kv)                     # fast
    try:
        # warm both (compile prefill+decode) so the hedge delay races
        # decode speed, not compile time
        for s in (s0, s1):
            code, _ = http_post_json(
                f"http://127.0.0.1:{s.port}/v1/generate",
                {"token_ids": [5, 9, 2, 11], "max_new_tokens": 4,
                 "temperature": 0.0})
            assert code == 200
        router = FleetRouter(
            [Replica("r0", f"http://127.0.0.1:{s0.port}"),
             Replica("r1", f"http://127.0.0.1:{s1.port}")],
            hedge_after_s=0.5, quiet=True,
        )
        router.health_tick()
        code, out = router.handle_generate({
            "token_ids": [5, 9, 2, 11], "max_new_tokens": 40,
            "temperature": 0.0,
        })
        assert code == 200
        assert out["served_by"] == "r1" and out["finish_reason"] == "length"
        s = router.fleet_stats()
        assert s["hedges"] == 1 and s["hedge_wins"] == 1
        # the loser drains through its ticket-cancel path: zero leaked
        # slots/blocks once the fire-and-forget cancel lands
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            st = sched0.stats()
            if st["slots_busy"] == 0 and st["cancelled"] >= 1:
                break
            time.sleep(0.05)
        st = sched0.stats()
        assert st["cancelled"] == 1 and st["slots_busy"] == 0
        kvs = eng0.kv_stats()
        assert kvs["blocks_free"] == kvs["num_blocks"]
        assert sched1.stats()["slots_busy"] == 0
    finally:
        s0.stop()
        s1.stop()


# -- chaos-killed replicas are crashes, not preemptions -----------------------


def test_sigkill_is_a_crash_not_a_preemption():
    """The chaos ``kill`` fault SIGKILLs a replica; the provider must
    report it as nothing (a crash is dropped, never relaunched — the
    min-replicas floor refills), while SIGTERM stays a preemption."""
    provider = ProcessReplicaProvider("sleep 30")
    try:
        r1 = provider.launch()
        r2 = provider.launch()
        pids = provider.pids()
        os.kill(pids[r1.name], signal.SIGKILL)     # chaos kill: crash
        os.kill(pids[r2.name], signal.SIGTERM)     # spot reclaim
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and len(provider.pids()) > 0:
            time.sleep(0.05)
        gone = provider.preempted()
        assert gone == [r2.name]                   # SIGTERM only
        assert provider.preempted() == []          # reported once
        assert provider.pids() == {}               # both dropped
    finally:
        provider.stop_all()
