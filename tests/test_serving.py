"""Serving engine (inference/serving): dynamic batching, bucketing,
replicas, robustness and metrics — all on the CPU backend.

Determinism note: tests that must PROVE coalescing construct the engine
with auto_start=False, queue requests first, then start the batcher —
no sleep-and-hope about thread interleaving.
"""
import base64
import json
import os
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu import jit  # noqa: E402
from paddle_tpu.inference.serving import (ServingEngine,  # noqa: E402
                                          ServingError, ServingHTTPServer)
from paddle_tpu.static import InputSpec  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _lockcheck_module():
    """Lock-order + data-race detection across the WHOLE module: every
    lock the serving engine (queue, batcher cv, metrics, replicas)
    creates during these tests is shimmed, any acquisition-order cycle
    recorded by ANY test fails here (ISSUE 8 acceptance), and the
    racecheck shim layered on top fails on any unguarded cross-thread
    access to the engine's designated shared state (ISSUE 13). Sites
    inside tests/ are harness observation, not product races."""
    from paddle_tpu.testing import lockcheck, racecheck

    lockcheck.install()
    racecheck.install(ignore_site_parts=(os.sep + "tests" + os.sep,))
    try:
        yield
        lockcheck.assert_clean()
        racecheck.assert_clean()
    finally:
        racecheck.uninstall()
        lockcheck.uninstall()


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    model.eval()
    prefix = str(tmp_path_factory.mktemp("serving") / "model")
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
    return prefix, model


def make_engine(prefix, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("batch_timeout_ms", 20)
    kw.setdefault("replicas", 2)
    return ServingEngine(prefix, **kw)


class TestEngine:
    def test_concurrent_clients_order_matched_and_batched(self,
                                                          saved_model):
        """N concurrent clients each get THEIR result (order-matched
        batch slices), and the batcher provably coalesced (occupancy>1:
        requests are queued before the batcher starts)."""
        prefix, model = saved_model
        eng = make_engine(prefix, auto_start=False)
        xs = [np.random.RandomState(i).randn(1 + i % 3, 8)
              .astype("float32") for i in range(10)]
        futs = [eng.submit([x]) for x in xs]
        eng.start()
        for x, f in zip(xs, futs):
            (out,) = f.result(60)
            want = model(paddle.to_tensor(x)).numpy()
            assert out.shape == want.shape
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
        assert eng.metrics.max_occupancy() > 1
        assert eng.metrics.batches_total < len(xs)
        eng.shutdown()

    def test_threaded_submitters(self, saved_model):
        """The same through real concurrent submitter threads."""
        prefix, model = saved_model
        eng = make_engine(prefix, batch_timeout_ms=10)
        results = {}

        def client(i):
            x = np.random.RandomState(100 + i).randn(1, 8) \
                .astype("float32")
            (out,) = eng.predict([x], timeout=60)
            results[i] = (x, out)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert len(results) == 16
        for x, out in results.values():
            np.testing.assert_allclose(
                out, model(paddle.to_tensor(x)).numpy(), rtol=1e-5,
                atol=1e-6)
        eng.shutdown()

    def test_bad_request_rejected_batchmates_succeed(self, saved_model):
        """Decode/shape failures 4xx at submit — they never enter a
        batch, so concurrent good requests are untouched."""
        prefix, model = saved_model
        eng = make_engine(prefix, auto_start=False)
        good = [eng.submit([np.random.RandomState(i).randn(1, 8)
                            .astype("float32")]) for i in range(3)]
        with pytest.raises(ServingError) as e:
            eng.submit([np.zeros((1, 5), "float32")])  # wrong feature dim
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit([np.zeros((1, 8), "float32"),
                        np.zeros((1, 8), "float32")])  # wrong input count
        assert e.value.status == 400
        with pytest.raises(ServingError) as e:
            eng.submit([np.zeros((99, 8), "float32")])  # > max_batch_size
        assert e.value.status == 400
        eng.start()
        for f in good:
            (out,) = f.result(60)
            assert out.shape == (1, 4)
        assert eng.metrics.snapshot()["rejected_total"] == 3
        eng.shutdown()

    def test_batch_failure_splits_and_isolates_culprit(self, saved_model):
        """A batch-level runtime failure splits once and retries halves:
        the good half completes, only the culprit's requests fail 500."""
        prefix, model = saved_model
        eng = make_engine(prefix, auto_start=False)
        orig = eng._run_on_device

        def poisoned(device, arrays):
            if np.any(arrays[0] == 777.0):
                raise RuntimeError("injected runtime failure")
            return orig(device, arrays)

        eng._run_on_device = poisoned
        x_good = np.random.RandomState(0).randn(1, 8).astype("float32")
        x_bad = np.full((1, 8), 777.0, "float32")
        f_good = eng.submit([x_good])
        f_bad = eng.submit([x_bad])
        eng.start()
        (out,) = f_good.result(60)  # good half survived the split
        np.testing.assert_allclose(
            out, model(paddle.to_tensor(x_good)).numpy(), rtol=1e-5,
            atol=1e-6)
        with pytest.raises(ServingError) as e:
            f_bad.result(60)
        assert e.value.status == 500
        snap = eng.metrics.snapshot()
        assert snap["batch_splits_total"] == 1
        assert snap["failed_total"] == 1
        eng.shutdown()

    def test_transient_batch_failure_retries_halves_ok(self, saved_model):
        """If the halves succeed on retry (transient failure), every
        request still completes."""
        prefix, model = saved_model
        eng = make_engine(prefix, auto_start=False)
        orig = eng._run_on_device
        state = {"failed": False}

        def flaky(device, arrays):
            if arrays[0].shape[0] >= 2 and not state["failed"]:
                state["failed"] = True
                raise RuntimeError("transient")
            return orig(device, arrays)

        eng._run_on_device = flaky
        futs = [eng.submit([np.random.RandomState(i).randn(1, 8)
                            .astype("float32")]) for i in range(4)]
        eng.start()
        for f in futs:
            (out,) = f.result(60)
            assert out.shape == (1, 4)
        assert eng.metrics.snapshot()["batch_splits_total"] == 1
        eng.shutdown()

    def test_worker_survives_assembly_failure(self, saved_model):
        """An exception ANYWHERE in batch handling (even outside the
        replica run) fails the batch 500 but never kills the worker
        thread — the replica keeps serving afterwards."""
        prefix, model = saved_model
        eng = make_engine(prefix, replicas=1, auto_start=False)
        orig = eng._run_group
        state = {"boom": True}

        def exploding(rep, gen, group, allow_split):
            if state["boom"]:
                state["boom"] = False
                raise MemoryError("injected assembly failure")
            return orig(rep, gen, group, allow_split)

        eng._run_group = exploding
        f1 = eng.submit([np.zeros((1, 8), "float32")])
        eng.start()
        with pytest.raises(ServingError) as e:
            f1.result(60)
        assert e.value.status == 500
        # the worker thread is still alive and serving
        x = np.random.RandomState(0).randn(1, 8).astype("float32")
        (out,) = eng.predict([x], timeout=60)
        np.testing.assert_allclose(
            out, model(paddle.to_tensor(x)).numpy(), rtol=1e-5,
            atol=1e-6)
        eng.shutdown()

    def test_shutdown_drains_inflight(self, saved_model):
        """shutdown(drain=True) completes every queued request before
        returning; later submits are refused 503."""
        prefix, model = saved_model
        eng = make_engine(prefix, auto_start=False)
        futs = [eng.submit([np.random.RandomState(i).randn(2, 8)
                            .astype("float32")]) for i in range(8)]
        eng.start()
        eng.shutdown(drain=True)
        assert all(f.done() for f in futs)
        for f in futs:
            (out,) = f.result(0)
            assert out.shape == (2, 4)
        with pytest.raises(ServingError) as e:
            eng.submit([np.zeros((1, 8), "float32")])
        assert e.value.status == 503

    def test_shutdown_no_drain_fails_queued(self, saved_model):
        prefix, _ = saved_model
        eng = make_engine(prefix, auto_start=False)
        futs = [eng.submit([np.zeros((1, 8), "float32")])
                for _ in range(3)]
        eng.shutdown(drain=False)
        for f in futs:
            with pytest.raises(ServingError) as e:
                f.result(5)
            assert e.value.status == 503

    def test_deadline_expiry_503(self, saved_model):
        """A request still queued past its deadline fails 503 instead of
        executing late."""
        import time

        prefix, _ = saved_model
        eng = make_engine(prefix, auto_start=False)
        f_dead = eng.submit([np.zeros((1, 8), "float32")], deadline_ms=10)
        f_live = eng.submit([np.ones((1, 8), "float32")])
        time.sleep(0.08)
        eng.start()
        with pytest.raises(ServingError) as e:
            f_dead.result(30)
        assert e.value.status == 503
        (out,) = f_live.result(30)  # batchmate unaffected
        assert out.shape == (1, 4)
        assert eng.metrics.snapshot()["deadline_expired_total"] == 1
        eng.shutdown()

    def test_circuit_breaker_sheds_with_retry_after(self, saved_model):
        prefix, _ = saved_model
        eng = make_engine(prefix, auto_start=False, max_queue_depth=2)
        f1 = eng.submit([np.zeros((1, 8), "float32")])
        f2 = eng.submit([np.zeros((1, 8), "float32")])
        with pytest.raises(ServingError) as e:
            eng.submit([np.zeros((1, 8), "float32")])
        assert e.value.status == 503
        assert e.value.retry_after is not None and e.value.retry_after > 0
        assert eng.metrics.snapshot()["shed_total"] == 1
        eng.start()
        for f in (f1, f2):
            f.result(60)
        eng.shutdown()

    def test_seq_bucketing_coalesces_near_lengths(self, tmp_path):
        """Dynamic non-batch axes pad to seq buckets so near-length
        requests share one executable (padding-invariant model: row
        sums ignore zero padding)."""

        class RowSum(nn.Layer):
            def forward(self, x):
                return paddle.sum(x, axis=1)

        paddle.seed(0)
        m = RowSum()
        m.eval()
        prefix = str(tmp_path / "rowsum")
        jit.save(m, prefix,
                 input_spec=[InputSpec([None, None], "float32")])
        eng = ServingEngine(prefix, max_batch_size=4, batch_timeout_ms=20,
                            replicas=1, seq_boundaries=[4, 8],
                            auto_start=False)
        x3 = np.random.RandomState(0).randn(1, 3).astype("float32")
        x4 = np.random.RandomState(1).randn(1, 4).astype("float32")
        x7 = np.random.RandomState(2).randn(2, 7).astype("float32")
        futs = [eng.submit([x]) for x in (x3, x4, x7)]
        eng.start()
        for x, f in zip((x3, x4, x7), futs):
            (out,) = f.result(60)
            np.testing.assert_allclose(out, x.sum(axis=1), rtol=1e-5,
                                       atol=1e-6)
        snap = eng.metrics.snapshot()
        # len-3 and len-4 requests shared the seq-4 bucket executable
        assert any(k.endswith(":4") and v["compiles"] + v["hits"] > 0
                   for k, v in snap["buckets"].items())
        occ = snap["occupancy_hist"]
        assert occ.get(2, 0) >= 1  # x3+x4 coalesced despite length skew
        eng.shutdown()

    def test_static_batch_model_rejected(self, tmp_path):
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(4, 2))
        m.eval()
        prefix = str(tmp_path / "static_batch")
        jit.save(m, prefix, input_spec=[InputSpec([2, 4], "float32")])
        with pytest.raises(ValueError, match="STATIC batch dim"):
            ServingEngine(prefix, warmup=False, auto_start=False)

    def test_metrics_in_profiler_summary_dict(self, saved_model):
        import paddle_tpu.profiler as prof

        prefix, _ = saved_model
        eng = make_engine(prefix)
        eng.predict([np.zeros((1, 8), "float32")], timeout=60)
        with prof.profiler_guard(timer_only=True) as p:
            pass
        d = p.summary_dict()
        assert "serving" in d
        assert d["serving"]["requests_total"] >= 1
        assert d["serving"]["batches_total"] >= 1
        eng.shutdown()


class TestHTTPServer:
    @pytest.fixture()
    def server(self, saved_model):
        prefix, model = saved_model
        eng = make_engine(prefix, batch_timeout_ms=5)
        srv = ServingHTTPServer(eng).start()
        yield srv, model
        srv.stop()

    def _post(self, url, body, ctype, timeout=60):
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": ctype})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()

    def test_predict_json_b64(self, server):
        srv, model = server
        X = np.random.RandomState(0).randn(3, 8).astype("float32")
        body = json.dumps({"inputs": [{
            "b64": base64.b64encode(X.tobytes()).decode(),
            "dtype": "float32", "shape": [3, 8]}]}).encode()
        out = json.loads(self._post(
            f"http://127.0.0.1:{srv.port}/predict", body,
            "application/json"))["outputs"][0]
        got = np.frombuffer(base64.b64decode(out["b64"]),
                            out["dtype"]).reshape(out["shape"])
        np.testing.assert_allclose(
            got, model(paddle.to_tensor(X)).numpy(), rtol=1e-5,
            atol=1e-6)

    def test_a_burst_of_connections_is_accepted(self, server):
        """Three hundred clients connect at once, as a closed loop of a
        few hundred streaming callers does at its start: every connection
        is accepted and answered (the listen backlog is no longer
        socketserver's 5, which reset some of them)."""
        import socket
        import threading

        srv, _ = server
        socks = [socket.create_connection(("127.0.0.1", srv.port),
                                          timeout=60) for _ in range(300)]
        answers = [None] * len(socks)

        def ask(i):
            socks[i].sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
                             b"Connection: close\r\n\r\n")
            answers[i] = socks[i].recv(64)
            socks[i].close()

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(socks))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert srv.httpd.request_queue_size >= 300
        assert all(a and a.startswith(b"HTTP/1.") and b" 200 " in a
                   for a in answers), answers[:3]

    def test_predict_json_nested_lists(self, server):
        srv, model = server
        X = np.random.RandomState(1).randn(2, 8).astype("float32")
        body = json.dumps({"inputs": [X.tolist()]}).encode()
        out = json.loads(self._post(
            f"http://127.0.0.1:{srv.port}/predict", body,
            "application/json"))["outputs"][0]
        assert out["shape"] == [2, 4]

    def test_predict_raw_binary(self, server):
        srv, model = server
        X = np.random.RandomState(2).randn(2, 8).astype("float32")
        raw = X.tobytes()
        body = struct.pack("<Q", len(raw)) + raw
        reply = self._post(f"http://127.0.0.1:{srv.port}/predict", body,
                           "application/octet-stream")
        import io as _io

        buf = _io.BytesIO(reply)
        (n,) = struct.unpack("<I", buf.read(4))
        assert n == 1
        (dl,) = struct.unpack("<Q", buf.read(8))
        dtype = buf.read(dl).decode()
        (nd,) = struct.unpack("<I", buf.read(4))
        dims = struct.unpack(f"<{nd}q", buf.read(8 * nd))
        (nb,) = struct.unpack("<Q", buf.read(8))
        got = np.frombuffer(buf.read(nb), dtype).reshape(dims)
        np.testing.assert_allclose(
            got, model(paddle.to_tensor(X)).numpy(), rtol=1e-5,
            atol=1e-6)

    def test_bad_json_400(self, server):
        srv, _ = server
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(f"http://127.0.0.1:{srv.port}/predict",
                       b"{not json", "application/json")
        assert e.value.code == 400

    def test_wrong_shape_400(self, server):
        srv, _ = server
        body = json.dumps(
            {"inputs": [np.zeros((1, 5)).tolist()]}).encode()
        with pytest.raises(urllib.error.HTTPError) as e:
            self._post(f"http://127.0.0.1:{srv.port}/predict", body,
                       "application/json")
        assert e.value.code == 400
        err = json.loads(e.value.read())
        assert "error" in err

    def test_oversized_body_413_no_keepalive_desync(self, saved_model):
        """Oversized bodies 413 BEFORE being read — and because the body
        stays unread, the server must close the connection instead of
        letting a keep-alive client's stale bytes parse as the next
        request."""
        import http.client

        prefix, _ = saved_model
        eng = make_engine(prefix, batch_timeout_ms=5)
        srv = ServingHTTPServer(eng, max_body_bytes=1024).start()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=30)
            conn.request("POST", "/predict", body=b"x" * 4096,
                         headers={"Content-Type": "application/json"})
            r = conn.getresponse()
            assert r.status == 413
            assert r.getheader("Connection") == "close"
            r.read()
            conn.close()
            # a fresh request still works
            X = np.random.RandomState(0).randn(1, 8).astype("float32")
            body = json.dumps({"inputs": [X.tolist()]}).encode()
            out = json.loads(self._post(
                f"http://127.0.0.1:{srv.port}/predict", body,
                "application/json"))
            assert out["outputs"][0]["shape"] == [1, 4]
        finally:
            srv.stop()

    def test_healthz_and_metrics(self, server):
        srv, _ = server
        url = f"http://127.0.0.1:{srv.port}"
        X = np.random.RandomState(0).randn(1, 8).astype("float32")
        body = json.dumps({"inputs": [X.tolist()]}).encode()
        self._post(url + "/predict", body, "application/json")
        h = json.loads(urllib.request.urlopen(
            url + "/healthz", timeout=30).read())
        assert h["status"] == "ok" and h["replicas"] == 2
        m = urllib.request.urlopen(url + "/metrics", timeout=30) \
            .read().decode()
        assert "paddle_serving_requests_total" in m
        assert "paddle_serving_latency_seconds" in m
        assert 'paddle_serving_bucket_executions{bucket="1"' in m

    def test_metrics_show_occupancy_under_concurrency(self, saved_model):
        """Acceptance: /metrics reports batch occupancy > 1 under
        concurrent load (deterministic: queue first, start after)."""
        prefix, _ = saved_model
        eng = make_engine(prefix, auto_start=False)
        srv = ServingHTTPServer(eng).start()
        url = f"http://127.0.0.1:{srv.port}"
        results = []

        def client(i):
            X = np.random.RandomState(i).randn(1, 8).astype("float32")
            body = json.dumps({"inputs": [X.tolist()]}).encode()
            results.append(self._post(url + "/predict", body,
                                      "application/json"))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        # wait until all 6 HTTP handler threads have enqueued
        import time

        for _ in range(200):
            if eng.metrics.snapshot()["requests_total"] >= 6:
                break
            time.sleep(0.01)
        eng.start()
        for t in threads:
            t.join(60)
        assert len(results) == 6
        m = urllib.request.urlopen(url + "/metrics", timeout=30) \
            .read().decode()
        occupancies = [
            int(line.split('occupancy="')[1].split('"')[0])
            for line in m.splitlines()
            if line.startswith("paddle_serving_batch_occupancy_total{")]
        assert occupancies and max(occupancies) > 1, m
        srv.stop()


@pytest.mark.parametrize("runs", [2])
def test_warm_restart_serves_with_zero_fresh_compiles(tmp_path, runs):
    """Acceptance: against a warm FLAGS_compile_cache_dir a fresh
    process's engine warmup + first request deserializes every
    executable (persistent hits > 0, misses == 0)."""
    cache_dir = str(tmp_path / "compile_cache")
    prefix = str(tmp_path / "model")
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    model.eval()
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])

    script = (
        "import json, os\n"
        "import numpy as np\n"
        "from paddle_tpu.inference.serving import ServingEngine\n"
        "from paddle_tpu.core import compile_cache as cc\n"
        f"eng = ServingEngine({prefix!r}, max_batch_size=2,\n"
        "                    batch_timeout_ms=1, replicas=1)\n"
        "out, = eng.predict([np.zeros((1, 8), 'float32')], timeout=120)\n"
        "assert out.shape == (1, 4)\n"
        "eng.shutdown()\n"
        "print(json.dumps({'warmup': eng.warmup_report,\n"
        "                  'stats': {k: cc.stats()[k]\n"
        "                            for k in ('hits', 'misses')}}))\n")
    env = cpu_subprocess_env(FLAGS_compile_cache_dir=cache_dir)
    reports = []
    for _ in range(runs):
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        reports.append(json.loads(r.stdout.strip().splitlines()[-1]))
    cold, warm = reports[0], reports[-1]
    assert cold["warmup"]["persistent_cache_enabled"]
    assert cold["warmup"]["persistent_misses"] > 0  # cold: real compiles
    # warm restart: every executable came from the on-disk cache
    assert warm["warmup"]["persistent_misses"] == 0
    assert warm["warmup"]["persistent_hits"] > 0
    assert warm["stats"]["misses"] == 0
    assert warm["stats"]["hits"] >= warm["warmup"]["persistent_hits"]


# ---------------------------------------------- streamed replies, one writer --
class _ScriptedGenerator:
    """A generative front with no model: `submit` hands the events of
    `script(i, input_ids)` (the i-th request's) to the sink the server
    passes, from a thread of its own, one step of every live request at a
    time as the engine's worker emits them — or before `submit` returns
    (`early`), ahead of the reply's headers."""

    def __init__(self, script, early=False, step_s=0.001):
        self.script, self.early, self.step_s = script, early, step_s
        self.submitted = 0
        self._lock = threading.Lock()

    def submit(self, input_ids, stream=None, **kw):
        with self._lock:
            i = self.submitted
            self.submitted += 1
        events = self.script(i, list(input_ids))
        if isinstance(events, Exception):
            raise events
        if self.early:
            for ev in events:
                stream.put(ev)
            return None

        def play():
            for ev in events:
                stream.put(ev)
                time.sleep(self.step_s)
        threading.Thread(target=play, daemon=True).start()
        return None

    def shutdown(self, drain=True, migrate=False):
        pass


def _stream_lines(port, ids, stop_after=None):
    """POST a streamed /generate -> (status, the ndjson lines read)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/generate", json.dumps(
            {"input_ids": ids, "stream": True}).encode(),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, [json.loads(resp.read())]
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            lines.append(json.loads(line))
            if stop_after is not None and len(lines) >= stop_after:
                break
        return resp.status, lines
    finally:
        conn.close()


def _tokens_then_done(n):
    def script(i, ids):
        toks = [ids[0] * 1000 + j for j in range(n)]
        return [("tok", t) for t in toks] + [
            ("done", {"tokens": toks, "n_tokens": n})]
    return script


@pytest.mark.parametrize("early", [False, True])
def test_streams_are_written_by_one_thread(early):
    """Sixteen streams at once, their events from another thread (or put
    before the reply's headers are out): each client reads its own tokens
    in order, then the done line, in the lines json.dumps writes — and no
    reply thread was woken a token, one pump thread wrote them all."""
    from paddle_tpu.inference.serving import ServingHTTPServer

    n = 40
    srv = ServingHTTPServer(None, generator=_ScriptedGenerator(
        _tokens_then_done(n), early=early)).start()
    try:
        got = [None] * 16

        def ask(r):
            got[r] = _stream_lines(srv.port, [r + 1])

        threads = [threading.Thread(target=ask, args=(r,))
                   for r in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        for r, (status, lines) in enumerate(got):
            assert status == 200
            want = [(r + 1) * 1000 + j for j in range(n)]
            assert [ln["token"] for ln in lines[:-1]] == want
            assert lines[-1] == {"tokens": want, "n_tokens": n,
                                 "done": True}
        pumps = [t for t in threading.enumerate()
                 if t.name == "serving-stream"]
        assert len(pumps) == 1
    finally:
        srv.stop()
    assert not any(t.name == "serving-stream" and t.is_alive()
                   for t in threading.enumerate())


def test_a_stream_chunk_is_the_line_json_writes():
    from paddle_tpu.inference.serving.server import _chunk
    from paddle_tpu.inference.serving import ServingError

    for tok in (0, 7, 131071):
        data = (json.dumps({"token": tok}) + "\n").encode()
        assert _chunk("tok", tok) == b"%X\r\n%s\r\n" % (len(data), data)
    assert b'"done": true' in _chunk("done", {"n_tokens": 1})
    assert b'{"error": "gone", "status": 503}' in _chunk(
        "err", ServingError(503, "gone"))
    assert b'"status": 500' in _chunk("err", RuntimeError("boom"))
    assert b'"done"' not in _chunk("handoff", {"handoff": "x"})


def test_a_refused_stream_answers_with_its_status():
    """A request the engine refuses before it is queued gets its status
    and JSON body, never a chunked 200."""
    from paddle_tpu.inference.serving import ServingError, ServingHTTPServer

    srv = ServingHTTPServer(None, generator=_ScriptedGenerator(
        lambda i, ids: ServingError(503, "queue full"))).start()
    try:
        status, body = _stream_lines(srv.port, [1])
        assert status == 503 and body == [{"error": "queue full"}]
    finally:
        srv.stop()


def test_an_error_ends_its_stream_and_no_other():
    """One stream ends in an error line after two tokens; a client that
    leaves after its first token is dropped; the streams beside them run
    to their done lines through the same pump."""
    from paddle_tpu.inference.serving import ServingError, ServingHTTPServer

    def script(i, ids):
        if ids[0] == 1:
            return [("tok", 5), ("tok", 6),
                    ("err", ServingError(500, "device lost"))]
        return _tokens_then_done(200)(i, ids)

    srv = ServingHTTPServer(None, generator=_ScriptedGenerator(
        script)).start()
    try:
        out = {}

        def ask(first, stop_after=None):
            out[first] = _stream_lines(srv.port, [first], stop_after)

        threads = [threading.Thread(target=ask, args=(1,)),
                   threading.Thread(target=ask, args=(2, 1))] + \
            [threading.Thread(target=ask, args=(r,)) for r in (3, 4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert out[1] == (200, [{"token": 5}, {"token": 6},
                                {"error": "device lost", "status": 500}])
        assert out[2] == (200, [{"token": 2000}])
        for r in (3, 4):
            status, lines = out[r]
            assert status == 200 and lines[-1]["done"] and \
                len(lines) == 201
        # the server still streams after a client left mid-stream
        status, lines = _stream_lines(srv.port, [5])
        assert status == 200 and len(lines) == 201
    finally:
        srv.stop()
