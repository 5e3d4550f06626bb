"""The load generator: a process of its own that never imports jax.

    python loadgen.py --url U --traffic FILE --seed N --vocab V
                      --ramp R --seconds S --out FILE [--rate X]

It speaks streamed /generate (chunked ndjson) over loopback with the
standard library only, stamps every streamed token with the monotonic
clock (CLOCK_MONOTONIC: the parent's clock too) and writes one JSON line
per request to --out. A generator that shared the engine's interpreter
would be timing its own GIL waits.

Protocol with the parent: when every request body of the schedule is
built it prints "ready"; the parent answers on stdin with the monotonic
time t0 at which the schedule starts. Requests are offered from t0 to
t0 + ramp + seconds; the window is the last `seconds` of that. At the
window's end requests still in flight are dropped where they stand (their
tokens so far are kept, `cut` is set) — the parent stops the engine.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from urllib.parse import urlparse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.traffic import Mix  # noqa: E402


class Client:
    """Sends request bodies and records what came back, and when."""

    def __init__(self, url: str, t_end: float):
        u = urlparse(url)
        self.host, self.port = u.hostname, u.port
        self.t_end = t_end
        self.samples = []
        self._lock = threading.Lock()

    def send(self, i: int, body: bytes, asked: int, prompt_len: int,
             due: float) -> None:
        rec = {"i": i, "due": due, "asked": asked, "prompt": prompt_len,
               "sent": None, "tokens": [], "done": False, "cut": False,
               "error": None, "status": None, "engine_ttft_ms": None}
        conn = http.client.HTTPConnection(self.host, self.port, timeout=120)
        try:
            rec["sent"] = time.monotonic()
            conn.request("POST", "/generate", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = resp.read(2000).decode("utf-8", "replace")
            else:
                while True:
                    line = resp.readline()
                    now = time.monotonic()
                    if now > self.t_end:
                        rec["cut"] = True
                        break
                    if not line:
                        break
                    obj = json.loads(line)
                    if "token" in obj:
                        rec["tokens"].append(now)
                    elif "error" in obj:
                        rec["error"] = str(obj)[:2000]
                    elif obj.get("done"):
                        rec["done"] = True
                        rec["n_tokens"] = obj.get("n_tokens")
                        rec["engine_ttft_ms"] = obj.get("ttft_ms")
        except (OSError, http.client.HTTPException, ValueError) as e:
            if time.monotonic() > self.t_end:
                rec["cut"] = True
            else:
                rec["error"] = repr(e)[:2000]
        finally:
            conn.close()
        with self._lock:
            self.samples.append(rec)


def closed_loop(client: Client, bodies, t0: float, t_end: float,
                clients: int) -> list:
    """`clients` threads; thread c sends requests c, c + clients, ...
    each when its last one ended. Due time = the time the last ended."""
    def run(c: int):
        i = c
        while i < len(bodies) and time.monotonic() < t_end:
            body, asked, plen = bodies[i]
            client.send(i, body, asked, plen, due=time.monotonic())
            i += clients

    threads = [threading.Thread(target=run, args=(c,), daemon=True,
                                name=f"client-{c}") for c in range(clients)]
    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    for t in threads:
        t.start()
    return threads


def open_loop(client: Client, bodies, dues, t0: float) -> list:
    """One thread per request, all started before t0, each asleep until
    its own due time: an arrival waits neither for the server nor for
    another arrival's thread to be made."""
    def run(i: int, due: float):
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        client.send(i, *bodies[i], due)

    threads = [threading.Thread(target=run, args=(i, t0 + due), daemon=True,
                                name=f"req-{i}")
               for i, due in enumerate(dues)]
    for t in threads:
        t.start()
    return threads


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--ramp", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the file's rate_per_s (the sweep)")
    args = ap.parse_args(argv)

    with open(args.traffic) as fh:
        traffic = json.load(fh)
    mix = Mix(traffic, args.seed, args.vocab, rate_per_s=args.rate)
    horizon = args.ramp + args.seconds
    open_ = traffic["loop"] == "open"
    if open_:
        n = 0
        while mix.due(n) < horizon:
            n += 1
        dues = [mix.due(i) for i in range(n)]
    else:
        # more than any server could finish inside the horizon
        n = int(traffic.get("max_requests",
                            traffic["clients"] * (4 + horizon)))
    bodies = []
    for i in range(n):
        p = mix.payload(i)
        bodies.append((json.dumps(p).encode(), p["max_new_tokens"],
                       len(p["input_ids"])))

    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    t_end = t0 + horizon
    client = Client(args.url, t_end)
    if open_:
        threads = open_loop(client, bodies, dues, t0)
    else:
        threads = closed_loop(client, bodies, t0, t_end,
                              int(traffic["clients"]))
    delay = t_end - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    # a reader blocked in readline() sees the end at its next token or
    # when the parent stops the engine; give the stragglers that long
    for t in threads:
        t.join(max(0.0, t_end + 20.0 - time.monotonic()))
    with client._lock:
        samples = sorted(client.samples, key=lambda r: r["i"])
    with open(args.out, "w") as fh:
        for rec in samples:
            fh.write(json.dumps(rec) + "\n")
    print(json.dumps({"requests": len(samples), "offered": n,
                      "unfinished_threads": sum(t.is_alive()
                                                for t in threads)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
