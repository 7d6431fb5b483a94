"""The load generator: a child process that imports no JAX.

Standard library only, so that its client threads share no interpreter
lock with the server's tick thread and take no chip. The parent makes
the requests from ``--seed`` (``traffic_gen.py``) and writes one JSON
plan to this process's standard input:

    {"url": ..., "requests": [...], "clients": n,
     "start_at": t, "end_at": t, "timeout_s": s}

``start_at`` and ``end_at`` are ``time.monotonic()`` instants, which on
Linux is one clock for every process of the machine. The loop is
closed: ``clients`` threads each take the next request of the one list
when their last is answered. At ``end_at`` the records of every
answered request go to standard output as one JSON line and the process
ends; requests still in flight are dropped (the parent counts tokens by
the server's counter and latencies over answered requests).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
import urllib.parse


def _post(host: str, port: int, path: str, doc: dict, timeout: float):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(doc),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def main() -> int:
    plan = json.loads(sys.stdin.read())
    url = urllib.parse.urlsplit(plan["url"])
    requests = plan["requests"]
    start_at, end_at = float(plan["start_at"]), float(plan["end_at"])
    records: list[dict] = []
    lock = threading.Lock()
    cursor = [0]

    def send(i: int) -> None:
        req = requests[i]
        rec = {"i": i, "t_send": time.monotonic(),
               "prompt_tokens": len(req["token_ids"]),
               "asked_tokens": req["max_new_tokens"]}
        try:
            status, out = _post(url.hostname, url.port, url.path, req,
                                plan["timeout_s"])
            rec["status"] = status
            rec["n_tokens"] = len(out.get("token_ids", ()))
            rec["timing"] = out.get("timing")
            if status != 200:
                rec["error"] = out.get("error")
        except (OSError, ValueError, http.client.HTTPException) as e:
            rec["status"], rec["n_tokens"] = 0, 0
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t_end"] = time.monotonic()
        with lock:
            records.append(rec)

    def client() -> None:
        while time.monotonic() < end_at:
            with lock:
                i = cursor[0]
                cursor[0] += 1
            if i >= len(requests):
                return
            send(i)

    time.sleep(max(0.0, start_at - time.monotonic()))
    for _ in range(int(plan["clients"])):
        threading.Thread(target=client, daemon=True).start()
    time.sleep(max(0.0, end_at - time.monotonic()))
    with lock:
        done = list(records)
    sys.stdout.write(json.dumps({"records": done, "sent": cursor[0]}) + "\n")
    sys.stdout.flush()
    # in-flight requests are dropped, not waited for
    os._exit(0)


if __name__ == "__main__":
    main()
