"""paddle.profiler analog (python/paddle/profiler/profiler.py:340).

Host events via RecordEvent spans; device tracing delegates to jax.profiler
(XLA's TPU tracer -> TensorBoard/Perfetto trace, the role the reference's
CUPTI/CustomTracer plays, platform/profiler/cuda_tracer.h:29). Chrome-trace
export of host events is built in.
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from enum import Enum
from typing import Optional

from ..observability import exporter as _exporter


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class TracerEventType(Enum):
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    Communication = 6
    PythonOp = 7
    UserDefined = 8


_events = []
_events_lock = threading.Lock()
_enabled = False


def _emit_event(name, begin_ns, end_ns, cat="UserDefined", args=None):
    """Append one complete chrome-trace span (used by RecordEvent.end and
    by the stats subsystem's dispatch hook)."""
    if not _enabled:
        return
    # stable small tid (exporter registry) instead of the raw 15-digit
    # threading.get_ident(): chrome-trace viewers key rows on tid, and
    # the registry also remembers the thread NAME for the thread_name
    # metadata events the export writes
    e = {
        "name": name, "ph": "X", "pid": os.getpid(),
        "tid": _exporter.stable_tid(),
        "ts": begin_ns / 1000.0,
        "dur": (end_ns - begin_ns) / 1000.0,
        "cat": cat,
    }
    if args:
        e["args"] = args
    with _events_lock:
        _events.append(e)


def live_events():
    """Snapshot of the process-global host-event buffer (the CURRENT
    recording window; a stopped Profiler owns its own capture via
    Profiler.events). observability.trace.export merges this into the
    unified trace."""
    with _events_lock:
        return list(_events)


class RecordEvent:
    """Analog of paddle.profiler.RecordEvent
    (phi/api/profiler/event_tracing.h:31)."""

    def __init__(self, name: str,
                 event_type: TracerEventType = TracerEventType.UserDefined,
                 args: Optional[dict] = None):
        self.name = name
        self.event_type = event_type
        self.args = args
        self._begin = None

    def begin(self):
        self._begin = time.perf_counter_ns()

    def end(self):
        if self._begin is None or not _enabled:
            return
        _emit_event(self.name, self._begin, time.perf_counter_ns(),
                    self.event_type.name, self.args)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


# ------------------------------------------------------- layer name stack
# Thread-local nn.Layer name stack (reference: the forward-event name
# stack profiler_statistic keys its ModelView on). nn.Layer.__call__
# enters layer_scope(<attribute name>) while a profiler is recording; the
# dispatch hook attributes each op to current_layer().
_layer_stack = threading.local()


def _stack():
    s = getattr(_layer_stack, "s", None)
    if s is None:
        s = _layer_stack.s = []
    return s


def current_layer() -> str:
    """Dotted name-stack path of the innermost live Layer.__call__
    ('' outside any layer)."""
    return ".".join(_stack())


@contextmanager
def layer_scope(name: str):
    """Push `name` on the layer name stack and record the span as a
    Forward event named with the full dotted path."""
    s = _stack()
    s.append(name)
    t0 = time.perf_counter_ns()
    path = ".".join(s)
    try:
        yield
    finally:
        _emit_event(path, t0, time.perf_counter_ns(),
                    TracerEventType.Forward.name)
        s.pop()


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    def scheduler(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        cycle = closed + ready + record
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        return ProfilerState.RECORD
    return scheduler


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name,
                            f"{worker_name or 'worker'}.chrometrace.json")
        prof.export(path)
    return handler


class Profiler:
    """Reference-parity profiler: host RecordEvent spans + per-dispatch op
    events (time, FLOPs, layer attribution via the stats subsystem), a
    per-step MFU series, an HBM memory tracer, and the jax.profiler device
    trace (skipped under timer_only)."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, custom_device_types=None):
        self.on_trace_ready = on_trace_ready
        self._scheduler = scheduler
        self._step = 0
        self._jax_profiling = False
        self._jax_dir = None
        self.timer_only = timer_only
        self.record_shapes = record_shapes
        self.profile_memory = profile_memory
        self.with_flops = with_flops
        self._session = None
        self.step_records = []  # per-step {"step","time_ms","flops","mfu"}
        self._step_mark_ns = None
        self._step_flops_mark = 0
        self._captured = None  # event snapshot owned by THIS profiler

    def start(self):
        global _enabled, _events
        _enabled = True
        with _events_lock:
            _events = []
        self.step_records = []
        self._captured = None
        from . import stats as _stats

        self._session = _stats.install(self)
        self._step_mark_ns = time.perf_counter_ns()
        self._step_flops_mark = 0
        if self.timer_only:
            self._jax_profiling = False
            return
        # device-side trace via XLA, if a TPU is attached
        try:
            import jax

            self._jax_dir = os.environ.get("PADDLE_PROFILER_DIR",
                                           "/tmp/paddle_tpu_profile")
            jax.profiler.start_trace(self._jax_dir)
            self._jax_profiling = True
        except Exception:
            self._jax_profiling = False

    def step(self, num_samples=None):
        """Mark a step boundary: closes the current step's time window,
        attributes the FLOPs dispatched inside it, computes per-step MFU
        and (with profile_memory) snapshots the HBM live/peak series."""
        self._step += 1
        now = time.perf_counter_ns()
        if self._session is None:
            return
        from . import stats as _stats

        t0 = self._step_mark_ns or now
        dt_s = max((now - t0) / 1e9, 1e-12)
        flops = self._session.step_flops - self._step_flops_mark
        self._step_flops_mark = self._session.step_flops
        peak = _stats.device_peak_flops()
        rec = {
            "step": self._step,
            "time_ms": (now - t0) / 1e6,
            "flops": int(flops),
            "flops_per_sec": flops / dt_s,
            "mfu": flops / dt_s / peak if peak else None,
        }
        if num_samples is not None:
            rec["num_samples"] = num_samples
        self.step_records.append(rec)
        _emit_event(f"ProfileStep#{self._step}", t0, now,
                    TracerEventType.ProfileStep.name)
        if self.profile_memory:
            self._session.memory.snapshot(self._step)
        self._step_mark_ns = time.perf_counter_ns()

    def stop(self):
        global _enabled
        _enabled = False
        # own the recording from here on: the event buffer is a process
        # global that the NEXT Profiler.start() clears, but this
        # profiler's summary()/events() must keep working after that
        with _events_lock:
            self._captured = list(_events)
        if self._session is not None:
            from . import stats as _stats

            _stats.uninstall(self._session)
        if self._jax_profiling:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_profiling = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def export(self, path: str, format: str = "json"):
        """Write the host-event capture as a valid chrome-trace JSON:
        thread-name/process-name metadata (M) events, stable tids, all
        spans carrying ts/dur/pid/tid, escape-safe serialization
        (observability.exporter owns the format)."""
        return _exporter.write_chrome_trace(path, self.events())

    def events(self):
        """Snapshot of the recorded host event stream (chrome-trace
        dicts): the live buffer while recording, this profiler's own
        capture after stop()."""
        if self._captured is not None:
            return list(self._captured)
        with _events_lock:
            return list(_events)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Reference-style statistic tables (profiler_statistic.py role):
        per-op, per-layer, per-step MFU and memory sections. Prints and
        returns the rendered text."""
        from . import stats as _stats

        out = _stats.build_summary(self, sorted_by=sorted_by,
                                   time_unit=time_unit)
        print(out)
        return out

    def summary_dict(self, top_ops: int = 8):
        """Machine-readable digest of summary() (bench.py embeds this in
        its JSON line)."""
        from . import stats as _stats

        return _stats.build_summary_dict(self, top_ops=top_ops)


@contextmanager
def profiler_guard(**kwargs):
    p = Profiler(**kwargs)
    p.start()
    try:
        yield p
    finally:
        p.stop()


def load_profiler_result(path):
    if path.endswith(".pb"):
        import pickle

        with open(path, "rb") as f:
            return pickle.load(f)
    with open(path) as f:
        return json.load(f)


class SortedKeys(Enum):
    """Summary-table sort keys (reference profiler/profiler_statistic.py
    SortedKeys)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(Enum):
    """Summary views (reference profiler.py SummaryView)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name: str, worker_name: Optional[str] = None):
    """on_trace_ready handler writing a binary (pickled) event dump —
    the serialized-capture role of the reference's protobuf export
    (profiler/dump/serialization.py); load with load_profiler_result."""
    import os
    import pickle
    import time as _time

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"worker_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}_{int(_time.time())}.pb")
        with _events_lock:
            data = {"traceEvents": list(_events)}
        with open(path, "wb") as f:
            pickle.dump(data, f)
        return path

    return handler


def export_pipeline_trace(pp_engine, path: str) -> str:
    """Chrome-trace view of the last pipeline train_batch: one row per
    physical stage, one span per (F|B, chunk, microbatch) duty, from the
    host dispatch timestamps recorded by the engine (XLA dispatch is
    async, so spans measure ISSUE time + host-side blocking — the
    schedule/bubble structure, not on-device kernel time; pair with
    jax.profiler for device timelines). Returns the written path."""
    import json as _json

    sched = getattr(pp_engine, "last_schedule", None)
    times = getattr(pp_engine, "last_timings", None)
    if not sched or not times or len(sched) != len(times):
        raise ValueError(
            "no recorded schedule: run train_batch on a mesh-backed "
            "PipelineParallel first")
    t_base = min(t0 for t0, _ in times)
    events = []
    for duty, (t0, t1) in zip(sched, times):
        if len(duty) == 3:
            kind, s, i = duty
            c = 0
        else:
            kind, s, c, i = duty
        events.append({
            "name": f"{kind} mb{i}" + (f" c{c}" if len(duty) == 4 else ""),
            "ph": "X", "pid": 0, "tid": s,
            "ts": (t0 - t_base) * 1e6,
            "dur": max((t1 - t0) * 1e6, 0.01),
            "cat": "forward" if kind == "F" else "backward",
            "args": {"stage": s, "chunk": c, "microbatch": i},
        })
    for s in range(pp_engine._pp):
        events.append({"name": "thread_name", "ph": "M", "pid": 0,
                       "tid": s, "args": {"name": f"stage {s}"}})
    with open(path, "w") as f:
        _json.dump({"traceEvents": events}, f)
    return path
