#!/usr/bin/env python
"""Tracing & telemetry smoke (wired into tools/ci.sh).

Proves the observability layer end to end on every PR:

1. with FLAGS_trace_dir + FLAGS_metrics_dir set, a tiny supervised fit
   (async checkpointing on) and one served request emit ONE
   Perfetto-loadable trace where
     - the request's spans share a single trace id across the
       client/batcher/replica threads (>=3 spans, >=3 threads), and
     - the async checkpoint writer-thread span is linked to the
       training step that queued it;
2. the metrics bus leaves a schema-valid per-step JSONL series and a
   Prometheus textfile carrying step time, MFU, queue depth, starvation
   fraction and checkpoint stall;
3. with tracing OFF, the per-call cost of an instrumentation site is
   within noise (the eager_bench dispatch gate runs separately in CI
   and never sees tracing enabled).

Prints TRACE_SMOKE_OK on success; any failure raises.
"""
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.optimizer as opt  # noqa: E402
from paddle_tpu import jit  # noqa: E402
from paddle_tpu.hapi import Model  # noqa: E402
from paddle_tpu.io import DataLoader  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.observability import bus, exporter, trace  # noqa: E402
from paddle_tpu.static import InputSpec  # noqa: E402


class _DS:
    def __len__(self):
        return 12

    def __getitem__(self, i):
        rs = np.random.RandomState(i)
        return rs.randn(4).astype("float32"), np.int64(i % 2)


def run_traced(trace_dir: str, metrics_dir: str) -> None:
    paddle.set_flags({"FLAGS_trace_dir": trace_dir,
                      "FLAGS_metrics_dir": metrics_dir})
    # --- tiny supervised fit with async checkpointing -----------------
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    m = Model(net)
    m.prepare(optimizer=opt.SGD(learning_rate=0.01,
                                parameters=net.parameters()),
              loss=nn.CrossEntropyLoss())
    ck = os.path.join(trace_dir, "ck")
    hist = m.fit(DataLoader(_DS(), batch_size=4), epochs=1, verbose=0,
                 ckpt_dir=ck, ckpt_save_steps=2)
    assert hist["loss"], "fit produced no steps"

    # --- one served request -------------------------------------------
    sm = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    sm.eval()
    prefix = os.path.join(trace_dir, "model")
    jit.save(sm, prefix, input_spec=[InputSpec([None, 8], "float32")])
    eng = ServingEngine(prefix, max_batch_size=4, batch_timeout_ms=5,
                        replicas=1, warmup=False)
    eng.predict([np.random.RandomState(0).randn(1, 8).astype("float32")])
    eng.shutdown()

    # --- trace JSON: schema + the two linkage contracts ---------------
    path = trace.export()
    errs = exporter.validate_chrome_trace(path)
    assert not errs, f"trace schema-invalid: {errs[:5]}"
    spans = trace.spans()

    serving = {}
    for e in spans:
        if e["cat"] == "serving":
            serving.setdefault(e["args"]["trace"], []).append(e)
    assert serving, "no serving spans recorded"
    req = max(serving.values(), key=len)
    assert len(req) >= 3, f"request trace has {len(req)} spans"
    assert len({e["tid"] for e in req}) >= 3, \
        "request spans did not cross >=3 threads"

    steps = [e for e in spans if e["name"] == "train.step"]
    writes = [e for e in spans if e["name"] == "ckpt.write"]
    assert steps and writes, "missing train.step / ckpt.write spans"
    step_traces = {e["args"]["trace"] for e in steps}
    step_tids = {e["tid"] for e in steps}
    for w in writes:
        assert w["args"]["trace"] in step_traces, \
            "ckpt.write span not linked to its training step"
        assert w["tid"] not in step_tids, \
            "ckpt.write span not on the writer thread"

    # --- metrics bus artifacts ----------------------------------------
    rows = [json.loads(ln) for ln in
            open(os.path.join(metrics_dir, "metrics.jsonl"))]
    need = {"step", "loss", "step_time_ms", "mfu", "queue_depth",
            "starvation_fraction", "ckpt_stall_s"}
    assert rows and all(need <= set(r) for r in rows), \
        f"JSONL series missing fields (need {sorted(need)})"
    prom = open(os.path.join(metrics_dir, "metrics.prom")).read()
    # no "mfu" gauge here: this smoke runs on the CPU, which has no MFU
    for field in ("step_time_ms", "queue_depth",
                  "starvation_fraction", "ckpt_stall_s"):
        assert f"paddle_train_{field} " in prom, \
            f"prometheus textfile missing paddle_train_{field}"
    for ln in prom.splitlines():
        if ln and not ln.startswith("#"):
            float(ln.rsplit(" ", 1)[1])  # every sample line parses

    print(f"trace: {path} ({len(spans)} spans, "
          f"{len(serving)} request traces); "
          f"series: {len(rows)} rows")


def check_disabled_overhead() -> None:
    paddle.set_flags({"FLAGS_trace_dir": "", "FLAGS_metrics_dir": ""})
    assert not trace.enabled()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.span("off"):
            pass
    per_us = (time.perf_counter() - t0) / n * 1e6
    # generous bound (shared-host noise), but a real regression —
    # allocation or locking on the off path — lands far above it
    assert per_us < 5.0, f"disabled-span cost {per_us:.2f}µs/call"
    print(f"tracing-off overhead: {per_us:.3f}µs/span (bound 5µs)")


def main() -> None:
    with tempfile.TemporaryDirectory() as td:
        run_traced(os.path.join(td, "trace"), os.path.join(td, "metrics"))
    check_disabled_overhead()
    print("TRACE_SMOKE_OK")


if __name__ == "__main__":
    main()
