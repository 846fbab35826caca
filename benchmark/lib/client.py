#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX.

    python client.py <url> <model_id> <plan.json> <seconds> <out.json>

Closed loop: one thread per client, each streaming `POST /generate` and
sending its next request when the reply ends. The window opens once
every client has finished one request, lasts <seconds>, and the process
exits when every request sent inside it has ended. A client never sends
a request twice: one that runs out of its share of the plan ends the
run with exit code 5.

Stamps are `time.monotonic()` (one clock for every process of the
host). stdout carries "WINDOW_OPEN <t>" and "WINDOW_CLOSE <t>" lines
for the harness; the records go to <out.json>.
"""

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def generate(host, port, body, timeout=300.0):
    """One streamed request. Returns the record's measured part."""
    rec = {"sent": time.monotonic(), "arrivals": [], "tokens": None,
           "status": None, "error": None}
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(500).decode("utf-8", "replace")
            return rec
        while True:
            line = resp.readline()
            if not line:
                break
            now = time.monotonic()
            line = line.strip()
            if not line:
                continue
            chunk = json.loads(line)
            if "token" in chunk:
                rec["arrivals"].append(now)
            if "error" in chunk:
                rec["error"] = str(chunk["error"])[:500]
                rec["tokens"] = chunk.get("tokens")
            if chunk.get("done"):
                rec["tokens"] = chunk["tokens"]
        if rec["tokens"] is None and rec["error"] is None:
            rec["error"] = "stream ended without a terminal chunk"
    except Exception as e:  # a failed request is a record, not a crash
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
    finally:
        rec["ended"] = time.monotonic()
        conn.close()
    return rec


def main(argv):
    url, model_id, plan_path, seconds, out_path = argv[1:6]
    seconds = float(seconds)
    u = urlparse(url)
    with open(plan_path) as f:
        plan = json.load(f)
    records, lock = [], threading.Lock()
    state = {"open": None, "close": None, "exhausted": False}
    n_clients = len(plan["clients"])
    warmed = [False] * n_clients
    stop = threading.Event()

    def body_of(req):
        return {"model_id": model_id, "prompt": req["prompt"],
                "max_new_tokens": req["max_new_tokens"],
                "temperature": req.get("temperature", 0.0), "seed": 0,
                "stream": True}

    def note(rec, client, index, body, due):
        rec.update(client=client, index=index, due=due,
                   prompt_tokens=len(body["prompt"]),
                   max_new_tokens=body["max_new_tokens"])
        rec["prompt"] = body["prompt"]
        with lock:
            records.append(rec)

    def open_window():
        with lock:
            if state["open"] is None:
                state["open"] = time.monotonic()
                state["close"] = state["open"] + seconds
                print(f"WINDOW_OPEN {state['open']!r}", flush=True)

    def closed_client(c):
        reqs = plan["clients"][c]
        due = time.monotonic()
        for i, req in enumerate(reqs):
            if stop.is_set():
                return
            body = body_of(req)
            rec = generate(u.hostname, u.port, body)
            note(rec, c, i, body, due)
            due = rec["ended"]
            if not warmed[c]:
                warmed[c] = True
                if all(warmed):
                    open_window()
            if state["close"] is not None and \
                    time.monotonic() >= state["close"]:
                return
        state["exhausted"] = True

    threads = []
    for c in range(n_clients):
        t = threading.Thread(target=closed_client, args=(c,), daemon=True)
        t.start()
        threads.append(t)
    while state["open"] is None and any(t.is_alive() for t in threads):
        time.sleep(0.01)
    if state["open"] is not None:
        time.sleep(max(0.0, state["close"] - time.monotonic()))
        print(f"WINDOW_CLOSE {time.monotonic()!r}", flush=True)
    stop.set()
    deadline = time.monotonic() + 90.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    with lock:
        out = {"open": state["open"], "close": state["close"],
               "records": list(records),
               "unfinished": sum(t.is_alive() for t in threads)}
    with open(out_path, "w") as f:
        json.dump(out, f)
    if state["exhausted"]:
        print("client.py: a client ran out of requests; raise the cell's "
              "traffic.repeats", file=sys.stderr)
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
