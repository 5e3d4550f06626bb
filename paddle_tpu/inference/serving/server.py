"""Stdlib-threaded HTTP front-end over the ServingEngine.

Endpoints (reference role: the Paddle Serving HTTP service; here a
zero-dependency http.server so the deployment image needs nothing
beyond the framework):

  POST /predict   application/json:
                    {"inputs": [<input>...], "deadline_ms": optional}
                    <input> = nested list, or
                              {"b64": base64(raw C-order bytes),
                               "dtype": "float32", "shape": [2, 8]}
                    -> {"outputs": [{"b64","dtype","shape"}...]}
  POST /predict   application/octet-stream (raw-binary mode):
                    per input: u64-LE nbytes + raw bytes (dtype/shape
                    per the saved meta spec; the batch dim — and any
                    other single dynamic axis — resolved from the byte
                    count, exactly the serve.py pipe rules)
                    -> u32-LE n_outputs, then per output:
                       u64 dtype-str len + bytes, u32 ndim,
                       i64 dims[ndim], u64 nbytes + raw bytes
  POST /generate  application/json (GenerativeEngine attached):
                    {"input_ids": [...], "max_new_tokens": opt,
                     "eos_token_id": opt, "deadline_ms": opt,
                     "stream": opt bool}
                    stream=false -> {"tokens": [...], "n_tokens",
                                     "ttft_ms", "latency_ms",
                                     "finish_reason"}
                    stream=true  -> chunked application/x-ndjson: one
                                    {"token": id} line per generated
                                    token AS IT DECODES, then a final
                                    {"done": true, ...result} line
  GET  /healthz   engine health JSON (503 while draining)
  GET  /metrics   Prometheus text format (predict + generate families)

With ``admin=True`` (the fabric host plane — inference/fabric drives
these for cross-host scale/drain/revive; keep the port private):

  GET  /admin/replicas  replica rows for every front, each tagged
                        {"front": "predict"|"generate"}
  POST /admin/scale     {"front", "action": add|remove|revive,
                         "rid"?, "device"?, "drain"?, "warm"?}
                        -> the engine's report JSON; an engine
                        ValueError (replica vanished, last-active
                        refusal) maps to 409 so the fleet adapter can
                        re-raise it as ValueError
  POST /admin/drain     graceful host drain on a background thread
                        (healthz flips to draining immediately);
                        {"migrate": true} exports in-flight generation
                        streams as KV-handoff payloads instead of
                        finishing them (the disaggregated-serving live
                        migration path)
  GET  /admin/kv        the generative front's KV digest: per-capacity
                        free-slot counts + prefix-residency hashes
  POST /admin/kv/import raw KV-handoff payload (the handoff.py wire
                        format) -> the stream continues HERE, replied
                        as the same chunked ndjson /generate streams
                        (malformed payload 400, geometry/dtype
                        mismatch 409, queue bound 503)

Errors map ServingError.status to the HTTP status; 503s carry a
Retry-After header so well-behaved clients back off instead of
hammering a shedding server.
"""
from __future__ import annotations

import base64
import io
import json
import struct
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from .engine import ServingEngine, ServingError
from .lifecycle import validate_sampling


def _decode_json_input(obj, spec):
    if isinstance(obj, dict):
        raw = base64.b64decode(obj["b64"])
        dtype = np.dtype(obj.get("dtype", spec["dtype"]))
        arr = np.frombuffer(raw, dtype=dtype)
        if "shape" in obj:
            arr = arr.reshape([int(d) for d in obj["shape"]])
        return arr
    return np.asarray(obj, dtype=np.dtype(spec["dtype"]))


class _Handler(BaseHTTPRequestHandler):
    server_version = "paddle-tpu-serving/1"
    protocol_version = "HTTP/1.1"
    engine: ServingEngine = None  # bound by ServingHTTPServer
    generator = None              # optional GenerativeEngine
    admin = False                 # /admin plane (fabric host mode)
    owner = None                  # the owning ServingHTTPServer
    # request-body byte bound: the engine's circuit breaker caps queue
    # DEPTH, this caps BYTES — without it a handful of huge
    # Content-Lengths exhaust host memory before any validation runs
    max_body_bytes = 256 << 20

    def log_message(self, fmt, *args):  # quiet: metrics are the log
        pass

    # ------------------------------------------------------------ helpers --
    def _send(self, status: int, body: bytes, ctype: str,
              retry_after: Optional[float] = None):
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        if retry_after is not None:
            self.send_header("Retry-After", f"{retry_after:.3f}")
        if self.close_connection:
            # set when the request body was left unread (413/404): the
            # socket is about to close — say so, per HTTP/1.1
            self.send_header("Connection", "close")
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, status: int, obj,
                   retry_after: Optional[float] = None):
        self._send(status, json.dumps(obj).encode(), "application/json",
                   retry_after)

    def _send_error_obj(self, err: Exception):
        if isinstance(err, ServingError):
            self._send_json(err.status, {"error": err.message},
                            retry_after=err.retry_after)
        elif isinstance(err, TimeoutError):
            self._send_json(504, {"error": "request timed out"})
        else:
            self._send_json(500, {"error": repr(err)[:2000]})

    # -------------------------------------------------------------- GETs --
    def do_GET(self):  # noqa: N802 — http.server API
        if self.path.startswith("/healthz"):
            if self.engine is not None:
                h = self.engine.health()
                if self.generator is not None:
                    h["generation"] = self.generator.health()
            else:
                h = self.generator.health()
            # a dual-front tier is healthy only if BOTH fronts are — a
            # draining generator must flip the probe even while predict
            # still answers, or the balancer keeps routing /generate
            ok = h["status"] == "ok" and \
                h.get("generation", {}).get("status", "ok") == "ok"
            status = 200 if ok else 503
            self._send_json(status, h)
        elif self.path.startswith("/metrics"):
            text = ""
            if self.engine is not None:
                text += self.engine.metrics.prometheus_text()
            if self.generator is not None:
                text += self.generator.metrics.prometheus_text()
            self._send(200, text.encode(), "text/plain; version=0.0.4")
        elif self.path.startswith("/admin/kv") and self.admin:
            if self.generator is None:
                self._send_json(400, {"error": "no generative front"})
                return
            rep = self.generator.load_report()
            self._send_json(200, {"kv": rep.get("kv", {}),
                                  "prefix": rep.get("prefix", [])})
        elif self.path.startswith("/admin/replicas") and self.admin:
            rows = []
            for front, eng in (("predict", self.engine),
                               ("generate", self.generator)):
                if eng is None:
                    continue
                for row in eng.replica_states():
                    row["front"] = front
                    rows.append(row)
            self._send_json(200, {"replicas": rows})
        else:
            self._send_json(404, {"error": f"no route {self.path}"})

    # ------------------------------------------------------------- POSTs --
    def do_POST(self):  # noqa: N802
        is_predict = self.path.startswith("/predict")
        is_generate = self.path.startswith("/generate")
        if self.admin and self.path.startswith("/admin/"):
            self._admin_post()
            return
        if not (is_predict or is_generate):
            # body not consumed: the connection must close, or a
            # keep-alive client's unread bytes parse as the next request
            self.close_connection = True
            self._send_json(404, {"error": f"no route {self.path}"})
            return
        try:
            if is_predict and self.engine is None:
                raise ServingError(
                    404, "no predict engine attached (generation-only "
                         "server)")
            if is_generate and self.generator is None:
                raise ServingError(
                    404, "no generative engine attached — construct the "
                         "server with generator=GenerativeEngine(...)")
            length = int(self.headers.get("Content-Length", 0))
            if length > self.max_body_bytes:
                self.close_connection = True  # body stays unread
                raise ServingError(
                    413, f"request body {length} bytes exceeds the "
                         f"{self.max_body_bytes}-byte bound")
            body = self.rfile.read(length)
            if is_generate:
                self._generate(body)
                return
            ctype = (self.headers.get("Content-Type") or
                     "application/json").split(";")[0].strip()
            if ctype == "application/octet-stream":
                self._predict_raw(body)
            else:
                self._predict_json(body)
        except Exception as e:  # noqa: BLE001
            # _send_error_obj keeps the status mapping honest:
            # ServingError carries its own 4xx/5xx, TimeoutError is a
            # server-side 504, anything unexpected a 500 — never a 400
            self._send_error_obj(e)

    # ------------------------------------------------------------- admin --
    def _front(self, name: str):
        eng = {"predict": self.engine,
               "generate": self.generator}.get(name)
        if eng is None:
            raise ServingError(400, f"no {name!r} front on this host")
        return eng

    def _admin_post(self):
        try:
            try:
                length = int(self.headers.get("Content-Length", 0))
            except ValueError:
                length = 0
            if length > self.max_body_bytes:
                self.close_connection = True
                raise ServingError(
                    413, f"request body {length} bytes exceeds the "
                         f"{self.max_body_bytes}-byte bound")
            body = self.rfile.read(length)
            if self.path.startswith("/admin/drain"):
                try:
                    migrate = bool(json.loads(
                        body.decode() or "{}").get("migrate", False))
                except (ValueError, UnicodeDecodeError) as e:
                    raise ServingError(
                        400, f"bad drain body: {e!r}"[:500]) from None
                self.owner.drain_async(migrate=migrate)
                self._send_json(200, {"draining": True,
                                      "migrate": migrate})
                return
            if self.path.startswith("/admin/kv/import"):
                if self.generator is None:
                    raise ServingError(400, "no generative front")
                # raw wire payload in, the continued stream out: the
                # importer's handle streams exactly like /generate —
                # the relaying router splices the lines verbatim
                handle = self.generator.import_handoff(body)
                self._stream_reply(handle)
                return
            if not self.path.startswith("/admin/scale"):
                self.close_connection = True
                self._send_json(404, {"error": f"no route {self.path}"})
                return
            try:
                payload = json.loads(body.decode() or "{}")
                action = payload["action"]
            except (ValueError, KeyError, UnicodeDecodeError) as e:
                raise ServingError(
                    400, f"bad admin body: {e!r}"[:500]) from None
            eng = self._front(payload.get("front", "predict"))
            # field coercion is request validation (400) — only the
            # ENGINE's ValueError below means a replica-state conflict
            try:
                warm = bool(payload.get("warm", True))
                drain = bool(payload.get("drain", True))
                timeout = float(payload.get("timeout", 30.0))
                rid = payload.get("rid")
                if action in ("revive",) or \
                        (action == "remove" and rid is not None):
                    rid = int(rid)
            except (ValueError, TypeError) as e:
                raise ServingError(
                    400, f"bad admin field: {e!r}"[:500]) from None
            if action == "add":
                device = None
                if payload.get("device") is not None:
                    want = str(payload["device"])
                    matches = [d for d in eng._device_pool
                               if str(d) == want]
                    if not matches:
                        raise ServingError(
                            400, f"no device {want!r} on this host")
                    device = matches[0]
                report = eng.add_replica(device=device, warm=warm)
            elif action == "remove":
                report = eng.remove_replica(rid=rid, drain=drain,
                                            timeout=timeout)
            elif action == "revive":
                report = eng.revive_replica(rid)
            else:
                raise ServingError(400, f"unknown action {action!r}")
            self._send_json(200, report)
        except ValueError as e:
            # the engine contract's "replica vanished / last active"
            # surface: 409 so the fleet adapter re-raises ValueError
            self._send_json(409, {"error": str(e)[:2000]})
        except Exception as e:  # noqa: BLE001
            self._send_error_obj(e)

    # ---------------------------------------------------------- generate --
    def _generate(self, body: bytes):
        try:
            payload = json.loads(body.decode())
            input_ids = payload["input_ids"]
            stream = bool(payload.get("stream", False))
            kw = {"max_new_tokens": payload.get("max_new_tokens"),
                  "eos_token_id": payload.get("eos_token_id"),
                  "deadline_ms": payload.get("deadline_ms"),
                  "prefill_only": bool(payload.get("prefill_only",
                                                   False)),
                  "resume_from": payload.get("resume_from", 0)}
            # sampling fields 400 here, BEFORE the submit enqueues —
            # a malformed request must never burn a KV slot
            kw.update(validate_sampling(payload))
        except ServingError:
            raise
        except Exception as e:  # noqa: BLE001
            raise ServingError(400, f"bad request body: {e!r}"[:2000]) \
                from None
        handle = self.generator.submit(input_ids, **kw)
        if not stream or kw["prefill_only"]:
            # prefill_only never streams: its "result" IS the handoff
            # payload the caller re-homes — no tokens belong here
            timeout = 300.0
            if kw["deadline_ms"] is not None and \
                    float(kw["deadline_ms"]) > 0:
                timeout = float(kw["deadline_ms"]) / 1e3 + 60.0
            self._send_json(200, handle.result(timeout))
            return
        self._stream_reply(handle)

    def _stream_reply(self, handle):
        # chunked ndjson: the decode loop feeds the wire token by
        # token. Headers go out before the first token, so a failure
        # mid-generation is surfaced as a terminal {"error": ...} line
        # (the HTTP status is already committed — the error can only
        # ride the stream). Shared by /generate and /admin/kv/import —
        # a relaying router splices either stream into its client's.
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def chunk(obj) -> None:
            data = (json.dumps(obj) + "\n").encode()
            self.wfile.write(f"{len(data):X}\r\n".encode() +
                             data + b"\r\n")
            self.wfile.flush()

        try:
            try:
                for kind, val in handle.events():
                    if kind == "tok":
                        chunk({"token": int(val)})
                    elif kind == "handoff":
                        # migrate-on-drain terminal: NOT done — the
                        # stream is moving hosts; the line carries the
                        # payload the router imports on a survivor
                        chunk(dict(val))
                    else:
                        chunk(dict(val, done=True))
            except OSError:
                raise
            except ServingError as e:
                chunk({"error": e.message, "status": e.status})
            except Exception as e:  # noqa: BLE001
                chunk({"error": repr(e)[:2000], "status": 500})
            self.wfile.write(b"0\r\n\r\n")
        except OSError:
            # the client went away mid-stream: the 200 is already
            # committed, so there is nobody left to tell and nothing
            # valid left to write — drop the connection quietly rather
            # than re-entering do_POST's header-sending error path
            self.close_connection = True

    def _predict_json(self, body: bytes):
        try:
            payload = json.loads(body.decode())
            inputs = [_decode_json_input(o, s)
                      for o, s in zip(payload["inputs"],
                                      self.engine._specs)]
            if len(payload["inputs"]) != len(self.engine._specs):
                raise ValueError(
                    f"expected {len(self.engine._specs)} inputs")
            deadline_ms = payload.get("deadline_ms")
        except ServingError:
            raise
        except Exception as e:  # noqa: BLE001
            raise ServingError(400, f"bad request body: {e!r}"[:2000]) \
                from None
        outs = self._run(inputs, deadline_ms)
        self._send_json(200, {"outputs": [{
            "b64": base64.b64encode(
                np.ascontiguousarray(o).tobytes()).decode(),
            "dtype": str(o.dtype),
            "shape": [int(d) for d in o.shape],
        } for o in outs]})

    def _predict_raw(self, body: bytes):
        # the pipe worker's byte-count decode rules, shared verbatim
        # (at most one dynamic axis resolvable from a size; >1 refuses
        # with guidance toward the JSON mode's explicit shapes)
        from ..serve import decode_input

        buf = io.BytesIO(body)
        inputs = []
        for i, spec in enumerate(self.engine._specs):
            hdr = buf.read(8)
            if len(hdr) < 8:
                raise ServingError(400, "truncated raw body")
            (nbytes,) = struct.unpack("<Q", hdr)
            raw = buf.read(nbytes)
            if len(raw) < nbytes:
                raise ServingError(400, "truncated raw body")
            try:
                inputs.append(decode_input(raw, spec, i))
            except ValueError as e:
                raise ServingError(400, str(e)) from None
        outs = self._run(inputs, None)
        reply = io.BytesIO()
        reply.write(struct.pack("<I", len(outs)))
        for o in outs:
            o = np.ascontiguousarray(o)
            dt = str(o.dtype).encode()
            reply.write(struct.pack("<Q", len(dt)) + dt)
            reply.write(struct.pack("<I", o.ndim))
            reply.write(struct.pack(f"<{o.ndim}q", *o.shape))
            b = o.tobytes()
            reply.write(struct.pack("<Q", len(b)) + b)
        self._send(200, reply.getvalue(), "application/octet-stream")

    def _run(self, inputs, deadline_ms):
        timeout = 120.0
        if deadline_ms is not None and float(deadline_ms) > 0:
            timeout = float(deadline_ms) / 1e3 + 5.0
        return self.engine.predict(inputs, deadline_ms=deadline_ms,
                                   timeout=timeout)


class ServingHTTPServer:
    """ThreadingHTTPServer bound to one engine and/or one generative
    engine; start()/stop() for embedding (tests, serve_bench),
    serve_forever() for the CLI."""

    def __init__(self, engine: Optional[ServingEngine],
                 host: str = "127.0.0.1", port: int = 0,
                 max_body_bytes: Optional[int] = None, generator=None,
                 admin: bool = False):
        if engine is None and generator is None:
            raise ValueError("need an engine, a generator, or both")
        attrs = {"engine": engine, "generator": generator,
                 "admin": bool(admin), "owner": self}
        if max_body_bytes is not None:
            attrs["max_body_bytes"] = int(max_body_bytes)
        handler = type("BoundHandler", (_Handler,), attrs)
        self.engine = engine
        self.generator = generator
        self.admin = bool(admin)
        self._drainer: Optional[threading.Thread] = None
        self.httpd = ThreadingHTTPServer((host, port), handler)
        self.httpd.daemon_threads = True
        self.host, self.port = self.httpd.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        name="serving-http", daemon=True)
        self._thread.start()
        return self

    def load_report(self) -> dict:
        """Compact load digest the fabric heartbeat publishes: total +
        per-front queue depth and replica count (the router's
        least-loaded signal and the fleet autoscaler's front picker)."""
        rep = {"queue_depth": 0, "replicas": 0, "fronts": {}}
        if self.engine is not None:
            rep["fronts"]["predict"] = self.engine.load_report()
        if self.generator is not None:
            rep["fronts"]["generate"] = self.generator.load_report()
        for fr in rep["fronts"].values():
            rep["queue_depth"] += int(fr.get("queue_depth", 0))
            rep["replicas"] += int(fr.get("replicas", 0))
        # hoist the generative front's KV digest to the top level: the
        # fabric heartbeat publishes THIS dict, and the router's
        # KV-aware pick reads "kv"/"prefix" without knowing about
        # fronts (predict-only hosts simply lack the keys)
        gen = rep["fronts"].get("generate")
        if gen is not None:
            for k in ("kv", "prefix"):
                if k in gen:
                    rep[k] = gen[k]
        return rep

    def drain_async(self, migrate: bool = False) -> None:
        """Kick a graceful engine drain on a background thread (the
        /admin/drain verb): /healthz flips to draining immediately via
        the engines' _closing flag; the listener stays up so in-flight
        HTTP threads finish their replies. ``migrate=True`` makes the
        generative engine export its in-flight streams as KV-handoff
        payloads (terminal 'handoff' stream events) instead of
        finishing them."""
        if self._drainer is not None:
            return
        t = threading.Thread(
            target=lambda: self._drain_engines(migrate),
            name="serving-drain", daemon=True)
        self._drainer = t
        t.start()

    def _drain_engines(self, migrate: bool = False) -> None:
        if self.engine is not None:
            self.engine.shutdown(drain=True)
        if self.generator is not None:
            self.generator.shutdown(drain=True, migrate=migrate)

    def serve_forever(self):
        try:
            self.httpd.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.stop()

    def stop(self, drain: bool = True, migrate: bool = False):
        """Graceful stop: engines drain first (in-flight HTTP threads
        get their results — with ``migrate=True`` the generative front's
        in-flight streams end in 'handoff' lines instead of finishing),
        then the listener closes."""
        if self.engine is not None:
            self.engine.shutdown(drain=drain)
        if self.generator is not None:
            self.generator.shutdown(drain=drain, migrate=migrate)
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(10.0)
            self._thread = None


__all__ = ["ServingHTTPServer"]
