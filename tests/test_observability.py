"""Unified tracing & telemetry (paddle_tpu/observability): span tracer
with cross-thread trace-id propagation, Perfetto/chrome-trace export
correctness, the run-wide metrics bus (provider registry + per-step
series), and the serving latency-buffer bound."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu.observability import bus as obus  # noqa: E402
from paddle_tpu.observability import exporter  # noqa: E402
from paddle_tpu.observability import trace  # noqa: E402


@pytest.fixture()
def tracing(tmp_path):
    """Enable the tracer into a tmp dir; restore the off state after."""
    paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
    trace.reset()
    yield str(tmp_path)
    paddle.set_flags({"FLAGS_trace_dir": ""})
    trace.reset()


@pytest.fixture()
def metrics_dir(tmp_path):
    d = tmp_path / "metrics"
    paddle.set_flags({"FLAGS_metrics_dir": str(d)})
    obus.BUS.reset()
    yield str(d)
    paddle.set_flags({"FLAGS_metrics_dir": ""})
    obus.BUS.reset()


# ---------------------------------------------------------------------------
class TestTracer:
    def test_disabled_by_default_no_spans_no_alloc(self):
        assert not trace.enabled()
        before = len(trace.spans())
        h = trace.span("x")
        assert h is trace.span("y")  # shared no-op handle, no allocation
        with h:
            pass
        assert len(trace.spans()) == before

    def test_nesting_and_parent_links(self, tracing):
        with trace.span("outer") as sp:
            outer_ctx = sp.ctx
            with trace.span("inner"):
                pass
        by_name = {e["name"]: e for e in trace.spans()}
        inner, outer = by_name["inner"], by_name["outer"]
        assert inner["args"]["trace"] == outer["args"]["trace"]
        assert inner["args"]["parent"] == outer_ctx.span_id
        assert "parent" not in outer["args"]  # root
        # distinct root spans get distinct traces
        with trace.span("other"):
            pass
        other = {e["name"]: e for e in trace.spans()}["other"]
        assert other["args"]["trace"] != outer["args"]["trace"]

    def test_cross_thread_context_propagation(self, tracing):
        with trace.span("root") as sp:
            ctx = trace.current_context()
        assert ctx == sp.ctx
        done = threading.Event()

        def work():
            with trace.use_context(ctx), trace.span("remote"):
                pass
            done.set()

        threading.Thread(target=work, name="prop-worker").start()
        assert done.wait(10)
        by_name = {e["name"]: e for e in trace.spans()}
        assert by_name["remote"]["args"]["trace"] == sp.ctx.trace_id
        assert by_name["remote"]["args"]["parent"] == sp.ctx.span_id
        assert by_name["remote"]["tid"] != by_name["root"]["tid"]

    def test_emit_span_explicit_parent(self, tracing):
        with trace.span("root") as sp:
            pass
        t0 = time.perf_counter_ns()
        ctx = trace.emit_span("measured", t0, t0 + 5000, parent=sp.ctx)
        assert ctx.trace_id == sp.ctx.trace_id
        ev = {e["name"]: e for e in trace.spans()}["measured"]
        assert ev["args"]["parent"] == sp.ctx.span_id
        assert ev["dur"] > 0

    def test_runtime_toggle_via_set_flags(self, tmp_path):
        assert not trace.enabled()
        paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
        try:
            assert trace.enabled()
            with trace.span("on"):
                pass
            assert any(e["name"] == "on" for e in trace.spans())
        finally:
            paddle.set_flags({"FLAGS_trace_dir": ""})
            trace.reset()
        assert not trace.enabled()

    def test_off_on_toggle_preserves_recorded_spans(self, tmp_path):
        paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
        try:
            trace.reset()
            with trace.span("before-toggle"):
                pass
            paddle.set_flags({"FLAGS_trace_dir": ""})  # pause recording
            paddle.set_flags({"FLAGS_trace_dir": str(tmp_path)})
            names = {e["name"] for e in trace.spans()}
            assert "before-toggle" in names  # capture survived the toggle
        finally:
            paddle.set_flags({"FLAGS_trace_dir": ""})
            trace.reset()

    def test_disabled_span_overhead_in_noise(self):
        """The off path is one flag check returning a shared handle —
        generous bound so shared-host noise can't flake it, but a real
        regression (allocation, locking) blows straight through."""
        assert not trace.enabled()
        n = 100_000
        t0 = time.perf_counter()
        for _ in range(n):
            with trace.span("hot"):
                pass
        per_call_us = (time.perf_counter() - t0) / n * 1e6
        assert per_call_us < 5.0, f"disabled span cost {per_call_us:.2f}µs"


# ---------------------------------------------------------------------------
class TestExporter:
    def test_export_valid_with_thread_metadata(self, tracing):
        names = ["alpha", 'with "quotes"', "newline\nname", "ctl\x07chr"]

        def worker(nm):
            with trace.span(nm):
                pass

        ts = [threading.Thread(target=worker, args=(nm,),
                               name=f"exp-{i}")
              for i, nm in enumerate(names)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        path = trace.export(include_profiler=False)
        assert exporter.validate_chrome_trace(path) == []
        with open(path) as f:
            data = json.load(f)  # escape-safe: parses despite evil names
        evs = data["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert {e["name"] for e in spans} == set(names)
        # stable small tids, one thread_name metadata event per tid
        tids = {e["tid"] for e in spans}
        assert all(isinstance(t, int) and 0 < t < 10_000 for t in tids)
        named = {e["tid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert tids <= set(named)
        assert any(n.startswith("exp-") for n in named.values())
        assert any(e["ph"] == "M" and e["name"] == "process_name"
                   for e in evs)

    def test_stable_tid_survives_thread_ident_reuse(self):
        """The OS reuses thread idents: a fresh thread must get a FRESH
        stable tid and its own name, never a dead predecessor's row
        (the bug mode: sequential short-lived threads all collapsing
        onto one tid with the first thread's name)."""
        got = {}

        def work(i):
            got[i] = exporter.stable_tid()

        for i in range(4):
            t = threading.Thread(target=work, args=(i,),
                                 name=f"reuse-{i}")
            t.start()
            t.join()
        assert len(set(got.values())) == 4
        names = exporter.thread_names()
        for i, tid in got.items():
            assert names[tid] == f"reuse-{i}"

    def test_validator_flags_broken_spans(self):
        bad = {"traceEvents": [
            {"name": "ok", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0,
             "dur": 1.0},
            {"name": "no_dur", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0},
            {"name": "no_tid", "ph": "X", "pid": 1, "ts": 0.0, "dur": 1.0},
        ]}
        errs = exporter.validate_chrome_trace(bad)
        assert len(errs) == 2
        assert exporter.validate_chrome_trace("not json{") != []

    def test_profiler_export_multithreaded(self, tmp_path):
        """Satellite: Profiler.export now writes M thread-name events,
        stable tids, and every span carries ts/dur/pid/tid."""
        from paddle_tpu import profiler as prof

        p = prof.Profiler(timer_only=True)
        p.start()
        try:
            def work():
                with prof.RecordEvent("threaded-op"):
                    time.sleep(0.001)

            t = threading.Thread(target=work, name="prof-worker")
            with prof.RecordEvent("main-op"):
                t.start()
                t.join()
        finally:
            p.stop()
        path = p.export(str(tmp_path / "prof.chrometrace.json"))
        assert exporter.validate_chrome_trace(path) == []
        with open(path) as f:
            evs = json.load(f)["traceEvents"]
        spans = {e["name"]: e for e in evs if e["ph"] == "X"}
        assert "main-op" in spans and "threaded-op" in spans
        assert spans["main-op"]["tid"] != spans["threaded-op"]["tid"]
        assert all(isinstance(e["tid"], int) and e["tid"] < 10_000
                   for e in spans.values())
        named = {e["tid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {s["tid"] for s in spans.values()} <= named


# ---------------------------------------------------------------------------
class TestProviderRegistry:
    """Satellite: the summary-provider registry (now the metrics bus) —
    direct coverage for raise-tolerance and idempotent registration."""

    def test_raising_provider_skipped_others_survive(self):
        from paddle_tpu.profiler import stats as pstats

        calls = {"n": 0}

        def sick():
            calls["n"] += 1
            raise RuntimeError("boom")

        pstats.register_summary_provider("_t_sick", sick)
        pstats.register_summary_provider("_t_ok", lambda: {"v": 1})
        try:
            got = obus.collect()
            assert "_t_sick" not in got
            assert got["_t_ok"] == {"v": 1}
            assert obus.BUS.provider_error_counts()["_t_sick"] == 1
            # summary_dict (the digest route) survives too
            from paddle_tpu import profiler as prof

            with prof.Profiler(timer_only=True) as p:
                pass
            d = p.summary_dict()
            assert d["_t_ok"] == {"v": 1} and "_t_sick" not in d
            assert calls["n"] >= 2
        finally:
            pstats.unregister_summary_provider("_t_sick")
            pstats.unregister_summary_provider("_t_ok")
        assert "_t_ok" not in obus.BUS.providers()

    def test_duplicate_registration_idempotent(self):
        from paddle_tpu.profiler import stats as pstats

        a = lambda: {"v": "a"}  # noqa: E731
        b = lambda: {"v": "b"}  # noqa: E731
        pstats.register_summary_provider("_t_dup", a)
        pstats.register_summary_provider("_t_dup", a)
        pstats.register_summary_provider("_t_dup", b)  # replace, not add
        try:
            assert obus.collect()["_t_dup"] == {"v": "b"}
            assert list(obus.BUS.providers()).count("_t_dup") == 1
        finally:
            pstats.unregister_summary_provider("_t_dup")

    def test_provider_recovery_clears_error_count(self):
        state = {"bad": True}

        def flaky():
            if state["bad"]:
                raise ValueError("transient")
            return {"v": 2}

        obus.register_provider("_t_flaky", flaky)
        try:
            obus.collect()
            assert obus.BUS.provider_error_counts()["_t_flaky"] == 1
            state["bad"] = False
            assert obus.collect()["_t_flaky"] == {"v": 2}
            assert "_t_flaky" not in obus.BUS.provider_error_counts()
        finally:
            obus.unregister_provider("_t_flaky")

    def test_empty_section_omitted_and_noncallable_rejected(self):
        obus.register_provider("_t_empty", lambda: {})
        try:
            assert "_t_empty" not in obus.collect()
        finally:
            obus.unregister_provider("_t_empty")
        with pytest.raises(TypeError):
            obus.register_provider("_t_bad", 42)


# ---------------------------------------------------------------------------
class TestMetricsBus:
    def test_series_jsonl_and_prometheus_textfile(self, metrics_dir):
        obus.record_step(step=1, loss=1.5, step_time_ms=10.0, mfu=0.01,
                         queue_depth=3, starvation_fraction=0.2,
                         ckpt_stall_s=0.0)
        obus.record_step(step=2, loss=1.2, step_time_ms=9.0, mfu=0.02,
                         queue_depth=1, starvation_fraction=0.1,
                         ckpt_stall_s=0.5)
        prom_path = obus.flush()
        rows = [json.loads(ln) for ln in
                open(os.path.join(metrics_dir, "metrics.jsonl"))]
        assert [r["step"] for r in rows] == [1, 2]
        assert rows[1]["ckpt_stall_s"] == 0.5
        text = open(prom_path).read()
        assert "paddle_train_steps_total 2" in text
        for field in ("step_time_ms", "mfu", "queue_depth",
                      "starvation_fraction", "ckpt_stall_s", "loss"):
            assert f"paddle_train_{field} " in text
        # textfile contract: gauge lines parse as "name value"
        for ln in text.splitlines():
            if ln.startswith("#") or not ln:
                continue
            name, val = ln.rsplit(" ", 1)
            float(val)

    def test_nonfinite_scalars_stay_strict_json(self, metrics_dir):
        """A NaN loss (the FLAGS_skip_nan_steps case) must not write a
        bare `NaN` token — every line stays strict JSON (null)."""
        obus.record_step(step=1, loss=float("nan"),
                         mfu=float("inf"), step_time_ms=1.0)
        obus.flush()
        (line,) = open(os.path.join(metrics_dir,
                                    "metrics.jsonl")).readlines()
        row = json.loads(line, parse_constant=lambda c: pytest.fail(
            f"non-strict JSON constant {c!r} in metrics.jsonl"))
        assert row["loss"] is None and row["mfu"] is None
        assert row["step_time_ms"] == 1.0

    def test_no_dir_no_files(self, tmp_path):
        obus.BUS.reset()
        assert paddle.get_flags("FLAGS_metrics_dir")["FLAGS_metrics_dir"] \
            == ""
        obus.record_step(step=1, loss=0.0)
        assert obus.flush() is None
        assert obus.series()[-1]["step"] == 1
        obus.BUS.reset()


# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served_prefix(tmp_path_factory):
    from paddle_tpu import jit
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    model.eval()
    prefix = str(tmp_path_factory.mktemp("obs_serving") / "model")
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
    return prefix


class TestServingTrace:
    def test_request_spans_share_trace_across_threads(self, tracing,
                                                      served_prefix):
        """Acceptance: one request -> >=3 spans sharing one trace id
        across >=3 threads (client, batcher, replica worker)."""
        from paddle_tpu.inference.serving import ServingEngine

        eng = ServingEngine(served_prefix, max_batch_size=4,
                            batch_timeout_ms=5, replicas=1, warmup=False)
        xs = [np.random.RandomState(i).randn(1, 8).astype("float32")
              for i in range(3)]
        futs = [eng.submit([x]) for x in xs]
        for f in futs:
            f.result(60)
        eng.shutdown()
        serving = [e for e in trace.spans() if e["cat"] == "serving"]
        traces = {}
        for e in serving:
            traces.setdefault(e["args"]["trace"], []).append(e)
        assert len(traces) == len(xs)  # one trace per request
        for tid_, evs in traces.items():
            names = {e["name"] for e in evs}
            assert {"serving.enqueue", "serving.queue_wait",
                    "serving.reply"} <= names
            assert len(evs) >= 3
            assert len({e["tid"] for e in evs}) >= 3
        # execute spans cross-link every batchmate's trace
        ex = [e for e in serving if e["name"] == "serving.execute"]
        assert ex and all(set(e["args"]["traces"]) <= set(traces)
                          for e in ex)
        # and the merged export stays schema-valid
        path = trace.export()
        assert exporter.validate_chrome_trace(path) == []

    def test_tracing_off_leaves_no_request_spans(self, served_prefix):
        from paddle_tpu.inference.serving import ServingEngine

        assert not trace.enabled()
        before = len(trace.spans())
        eng = ServingEngine(served_prefix, max_batch_size=4,
                            batch_timeout_ms=5, replicas=1, warmup=False)
        eng.predict([np.zeros((1, 8), "float32")])
        eng.shutdown()
        assert len(trace.spans()) == before


class TestServingLatencyBuffer:
    """Satellite: the latency/QPS sample buffers stay fixed-size in a
    long-running server, and percentiles stay sane after eviction."""

    def test_ring_bounded_and_percentiles_track_recent(self):
        from paddle_tpu.inference.serving.metrics import ServingMetrics

        m = ServingMetrics(latency_ring=128)
        # old regime: 10s latencies — would dominate percentiles forever
        # if the buffer grew with request count
        for _ in range(1000):
            m.on_complete(10.0)
        # new regime: 1ms..2ms fills the ring
        for i in range(128):
            m.on_complete(0.001 + (i % 10) * 0.0001)
        assert len(m._latencies) == 128
        pct = m.latency_percentiles()
        assert pct["p50"] < 0.01 and pct["p95"] < 0.01 and \
            pct["p99"] < 0.01
        assert pct["p50"] <= pct["p95"] <= pct["p99"]
        assert m.responses_total == 1128  # counter keeps full history

    def test_completions_evicted_outside_qps_window(self):
        from paddle_tpu.inference.serving.metrics import ServingMetrics

        m = ServingMetrics(latency_ring=16, qps_window_s=0.05)
        for _ in range(500):
            m.on_complete(0.001)
        assert len(m._completions) <= 500
        time.sleep(0.1)
        m.on_complete(0.001)  # record triggers eviction of the stale 500
        assert len(m._completions) == 1
        assert m.qps() > 0.0

    def test_bad_ring_size_rejected(self):
        from paddle_tpu.inference.serving.metrics import ServingMetrics

        with pytest.raises(ValueError):
            ServingMetrics(latency_ring=0)


# ---------------------------------------------------------------------------
class _TinyDS:
    def __len__(self):
        return 12

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return rs.randn(4).astype("float32"), np.int64(i % 2)


def _fit_once(tmp_path, **fit_kw):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.hapi import Model
    from paddle_tpu.io import DataLoader

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m = Model(net)
    m.prepare(optimizer=opt.SGD(learning_rate=0.01,
                                parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    loader = DataLoader(_TinyDS(), batch_size=4)
    fit_kw.setdefault("epochs", 1)
    return m.fit(loader, verbose=0, **fit_kw)


class TestTrainingTrace:
    def test_step_chain_links_async_ckpt_writer(self, tracing, tmp_path):
        """Acceptance: a supervised step with async checkpointing shows
        the writer-thread ckpt.write span in the SAME trace as the
        train.step that triggered it, on a different thread."""
        _fit_once(tmp_path, ckpt_dir=str(tmp_path / "ck"),
                  ckpt_save_steps=2)
        sps = trace.spans()
        by_name = {}
        for e in sps:
            by_name.setdefault(e["name"], []).append(e)
        steps = by_name.get("train.step", [])
        writes = by_name.get("ckpt.write", [])
        snaps = by_name.get("ckpt.snapshot", [])
        assert steps and writes and snaps
        assert by_name.get("train.data_wait") and \
            by_name.get("train.dispatch")
        step_traces = {e["args"]["trace"] for e in steps}
        step_tids = {e["tid"] for e in steps}
        for w in writes:
            assert w["args"]["trace"] in step_traces  # linked to a step
            assert w["tid"] not in step_tids          # on the writer thread
        # dispatch + snapshot are children inside the step trace
        for nm in ("train.dispatch", "ckpt.snapshot"):
            for e in by_name[nm]:
                assert e["args"]["trace"] in step_traces
        path = trace.export()
        assert exporter.validate_chrome_trace(path) == []

    def test_no_phantom_step_span_and_clean_context_after_fit(
            self, tracing, tmp_path):
        """One train.step span per EXECUTED step — the exhaustion probe
        of each epoch must not emit a phantom root — and the fit leaves
        no stale step context on the calling thread."""
        hist = _fit_once(tmp_path, epochs=2)
        steps = [e for e in trace.spans() if e["name"] == "train.step"]
        assert len(steps) == len(hist["loss"])  # not steps + epochs
        assert trace.current_context() is None

    def test_break_via_num_iters_closes_root_span(self, tracing,
                                                  tmp_path):
        """Breaking out of the fit loop (num_iters) must still emit the
        in-flight train.step span, bounded at loop exit, and restore the
        thread context."""
        hist = _fit_once(tmp_path, num_iters=1)
        assert len(hist["loss"]) == 1
        steps = [e for e in trace.spans() if e["name"] == "train.step"]
        assert len(steps) == 1
        assert trace.current_context() is None
        # the root's window must cover its own dispatch child
        disp = [e for e in trace.spans()
                if e["name"] == "train.dispatch"][0]
        root = steps[0]
        assert root["ts"] <= disp["ts"]
        assert root["ts"] + root["dur"] >= disp["ts"] + disp["dur"]

    def test_fit_emits_bus_series_with_required_fields(self, metrics_dir,
                                                       tmp_path):
        """Acceptance: FLAGS_metrics_dir alone wires the telemetry
        callback — the JSONL series and the Prometheus textfile carry
        step time, MFU, queue depth, starvation and ckpt stall."""
        hist = _fit_once(tmp_path, ckpt_dir=str(tmp_path / "ck"),
                         ckpt_save_steps=2)
        jsonl = os.path.join(metrics_dir, "metrics.jsonl")
        rows = [json.loads(ln) for ln in open(jsonl)]
        assert len(rows) == len(hist["loss"])
        need = {"step", "loss", "step_time_ms", "mfu", "queue_depth",
                "starvation_fraction", "ckpt_stall_s"}
        for r in rows:
            assert need <= set(r)
        assert all(r["step_time_ms"] > 0 for r in rows)
        # a CPU run has no MFU (no peak is assumed for a host): the
        # series carries the field as null and the textfile omits it
        assert all(r["mfu"] is None for r in rows)
        text = open(os.path.join(metrics_dir, "metrics.prom")).read()
        for field in ("step_time_ms", "queue_depth",
                      "starvation_fraction", "ckpt_stall_s"):
            assert f"paddle_train_{field} " in text
        assert "paddle_train_mfu" not in text

    def test_resume_fast_forward_prefix_records_no_spans(self, tracing,
                                                         tmp_path):
        """A resumed legacy-loader fit must not record junk
        train.step/data_wait spans for the fast-forwarded prefix (a
        150k-step resume would otherwise evict the real capture)."""
        ck = str(tmp_path / "ck")
        _fit_once(tmp_path, ckpt_dir=ck, ckpt_save_steps=2)
        trace.reset()
        hist = _fit_once(tmp_path, ckpt_dir=ck, ckpt_save_steps=2)
        trained = len(hist["loss"])  # only the un-checkpointed tail
        assert trained < 3
        steps = [e for e in trace.spans() if e["name"] == "train.step"]
        waits = [e for e in trace.spans()
                 if e["name"] == "train.data_wait"]
        assert len(steps) == trained
        assert len(waits) == trained

    def test_telemetry_first_in_list_still_rides_profiler(
            self, metrics_dir, tmp_path):
        """User order callbacks=[Telemetry, Profiler] must not
        double-start profilers: the ride decision happens at the first
        batch, after every on_train_begin ran."""
        from paddle_tpu.hapi.callbacks import (ProfilerCallback,
                                               TelemetryCallback)

        tc, pc = TelemetryCallback(), ProfilerCallback(
            print_summary=False)
        hist = _fit_once(tmp_path, callbacks=[tc, pc])
        assert not tc._owns_prof and tc._prof is pc.profiler
        # one step record per batch — no interleaved double-stepping
        assert len(pc.profiler.step_records) == len(hist["loss"])
        rows = [json.loads(ln) for ln in
                open(os.path.join(metrics_dir, "metrics.jsonl"))]
        assert any(r["flops"] > 0 for r in rows)

    def test_telemetry_rides_live_profiler_without_stepping_it(
            self, metrics_dir, tmp_path):
        """With ProfilerCallback already recording, the auto-installed
        TelemetryCallback must read the owner's step records (real MFU,
        not hardwired 0) and must NOT double-step or stop the owner's
        profiler."""
        from paddle_tpu.hapi.callbacks import ProfilerCallback

        pc = ProfilerCallback(print_summary=False)
        _fit_once(tmp_path, callbacks=[pc])
        rows = [json.loads(ln) for ln in
                open(os.path.join(metrics_dir, "metrics.jsonl"))]
        assert rows
        # the owner stepped once per batch; riding must not double it
        assert len(pc.profiler.step_records) == len(rows)
        assert all(r["step_time_ms"] > 0 for r in rows)
        assert any(r["flops"] > 0 for r in rows)
