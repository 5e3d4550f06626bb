"""Cross-thread span tracer (the request/step correlation layer).

The profiler's RecordEvent stream answers "how long did X take"; it
cannot answer "which request / which training step was that X part of"
once the work hops threads — a serving request crosses the client
thread, the batcher and a replica worker; a training step's checkpoint
write lands on the ckpt writer thread. This module adds exactly that
correlation:

- every span carries an explicit ``trace`` id (one per request / per
  training step) and a ``span``/``parent`` id pair;
- the current context lives in a thread-local and is *explicitly*
  propagated across thread boundaries: capture with
  ``current_context()``, adopt on the other side with
  ``use_context(ctx)`` (the checkpoint writer does this), or hand a
  ``parent=`` to ``span()``/``emit_span()`` (the serving worker does);
- completed spans are chrome-trace ``X`` dicts in a bounded in-memory
  ring; ``export()`` merges them with the profiler's host events into
  one Perfetto-loadable file (stable tids + thread-name metadata via
  observability.exporter).

One clock with the device: while tracing is on, a live ``Span`` also
holds a ``jax.profiler.TraceAnnotation`` of its own name (ids and scalar
args as metadata). Under a running ``jax.profiler`` session the span is
then an event of the ``/host:CPU`` plane of the same ``.xplane.pb`` as
the device's operations, on the thread that ran it — which is how an
idle gap of the device is attributed to what the host was doing
(benchmarks/harness/host_spans.py). With no session running the
annotation costs one check. ``emit_span`` (already measured, possibly on
another thread's behalf) stays host-clock only.

Overhead contract: tracing is off unless ``FLAGS_trace_dir`` is set.
When off, ``span()`` returns a shared no-op handle and every hook site
costs one module-attribute check — nothing allocates, nothing locks,
nothing of jax is imported (tools/trace_smoke.py asserts the
disabled-path cost stays in the noise).
"""
from __future__ import annotations

import itertools
import os
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import NamedTuple, Optional

from ..core.flags import flag
from . import exporter as _exporter


class TraceContext(NamedTuple):
    """Position in a trace: everything a child span needs to attach."""

    trace_id: int
    span_id: int


_ENABLED = False
_DIR: Optional[str] = None
_LOCK = threading.Lock()
_SPANS: "deque[dict]" = deque(maxlen=262144)
_DROPPED = 0
_IDS = itertools.count(1)
_TLS = threading.local()


def _new_id() -> int:
    # itertools.count.__next__ is atomic under the GIL
    return next(_IDS)


def reconfigure(trace_dir: Optional[str]) -> None:
    """(Re)point the tracer at `trace_dir`; empty/None disables. Called
    at import from FLAGS_trace_dir and by set_flags on a runtime
    change. Disabling pauses recording but KEEPS recorded spans (a
    toggle around a noisy section must not eat the capture); re-enabling
    re-applies the ring capacity, preserving contents."""
    global _ENABLED, _DIR, _SPANS
    _DIR = trace_dir or None
    _ENABLED = bool(trace_dir)
    # ring capacity re-latches on every reconfigure while enabled (a
    # trace_buffer_spans change routes here through set_flags too)
    if _ENABLED:
        cap = max(1024, int(flag("trace_buffer_spans")))
        with _LOCK:
            if _SPANS.maxlen != cap:
                _SPANS = deque(_SPANS, maxlen=cap)


# lint: allow[flags-latch] set_flags re-latches via trace.reconfigure()
reconfigure(flag("trace_dir"))


def enabled() -> bool:
    return _ENABLED


def current_context() -> Optional[TraceContext]:
    """The calling thread's active trace position (None outside any
    span). Capture this before handing work to another thread."""
    return getattr(_TLS, "ctx", None)


@contextmanager
def use_context(ctx: Optional[TraceContext]):
    """Adopt a captured context on this thread (no-op for ctx=None):
    spans opened inside become children of `ctx` in its trace."""
    if ctx is None:
        yield
        return
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = ctx
    try:
        yield
    finally:
        _TLS.ctx = prev


def _record(event: dict) -> None:
    global _DROPPED
    with _LOCK:
        if len(_SPANS) == _SPANS.maxlen:
            _DROPPED += 1
        _SPANS.append(event)


def emit_span(name: str, begin_ns: int, end_ns: int,
              parent: Optional[TraceContext] = None, cat: str = "span",
              args: Optional[dict] = None) -> Optional[TraceContext]:
    """Record one already-measured span. With `parent` given it joins
    that trace; otherwise it joins the caller's current context, or
    starts a fresh trace. Returns the span's context (None when tracing
    is off)."""
    if not _ENABLED:
        return None
    ctx = parent if parent is not None else current_context()
    trace_id = ctx.trace_id if ctx is not None else _new_id()
    span_id = _new_id()
    a = {"trace": trace_id, "span": span_id}
    if ctx is not None:
        a["parent"] = ctx.span_id
    if args:
        a.update(args)
    _record({
        "name": name, "ph": "X", "pid": os.getpid(),
        "tid": _exporter.stable_tid(),
        "ts": begin_ns / 1000.0,
        "dur": max((end_ns - begin_ns) / 1000.0, 0.001),
        "cat": cat, "args": a,
    })
    return TraceContext(trace_id, span_id)


class _NoopSpan:
    """Shared disabled-path handle: no allocation per call."""

    __slots__ = ()
    ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kwargs):
        return self


_NOOP = _NoopSpan()

_ANNOTATION = None      # jax.profiler.TraceAnnotation, on the first live span
_SCALARS = (bool, int, float, str)


def _annotate(name: str, ctx: TraceContext,
              parent: Optional[TraceContext], args: Optional[dict]):
    """An entered TraceAnnotation called `name` carrying the span's ids
    and the scalars of `args`. Importing jax.profiler initialises no
    backend (the rule _process_index states)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    meta = {"trace": ctx.trace_id, "span": ctx.span_id}
    if parent is not None:
        meta["parent"] = parent.span_id
    if args:
        # `name` is the annotation's own positional argument
        meta.update((k, v) for k, v in args.items()
                    if isinstance(v, _SCALARS) and k != "name")
    ann = _ANNOTATION(name, **meta)
    ann.__enter__()
    return ann


class Span:
    """Live span: opens on ``__enter__`` (becoming the thread's current
    context), emits its chrome-trace event on ``__exit__``."""

    __slots__ = ("name", "cat", "args", "ctx", "_parent", "_prev",
                 "_begin_ns", "_ann")

    def __init__(self, name: str, cat: str = "span",
                 args: Optional[dict] = None,
                 parent: Optional[TraceContext] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self._parent = parent
        self.ctx: Optional[TraceContext] = None
        self._prev = None
        self._begin_ns = 0
        self._ann = None

    def set(self, **kwargs):
        """Attach/override args on a live span."""
        if self.args is None:
            self.args = {}
        self.args.update(kwargs)
        return self

    def __enter__(self):
        parent = self._parent if self._parent is not None \
            else getattr(_TLS, "ctx", None)
        trace_id = parent.trace_id if parent is not None else _new_id()
        self.ctx = TraceContext(trace_id, _new_id())
        self._parent = parent
        self._prev = getattr(_TLS, "ctx", None)
        _TLS.ctx = self.ctx
        self._ann = _annotate(self.name, self.ctx, parent, self.args)
        self._begin_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        end_ns = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        _TLS.ctx = self._prev
        a = {"trace": self.ctx.trace_id, "span": self.ctx.span_id}
        if self._parent is not None:
            a["parent"] = self._parent.span_id
        if exc_type is not None:
            a["error"] = exc_type.__name__
        if self.args:
            a.update(self.args)
        _record({
            "name": self.name, "ph": "X", "pid": os.getpid(),
            "tid": _exporter.stable_tid(),
            "ts": self._begin_ns / 1000.0,
            "dur": max((end_ns - self._begin_ns) / 1000.0, 0.001),
            "cat": self.cat, "args": a,
        })
        return False


def span(name: str, cat: str = "span", args: Optional[dict] = None,
         parent: Optional[TraceContext] = None):
    """Open a span (context manager). THE hot-path entry point: when
    tracing is off this returns a shared no-op handle immediately."""
    if not _ENABLED:
        return _NOOP
    return Span(name, cat, args, parent)


_DONE = object()


def step_iter(it, name: str = "train.step", cat: str = "train",
              skip_first: int = 0):
    """Wrap a fit-loop iterator so each iteration runs under one root
    `name` span: the data fetch is a ``train.data_wait`` child, and the
    loop BODY (dispatch, checkpoint snapshot, callbacks) inherits the
    root context through the thread-local — work the body hands to
    other threads (the async checkpoint writer) links back to this
    step's trace. With tracing off the wrapper forwards items with no
    span machinery at all. `skip_first` items are forwarded span-free:
    a resume fast-forward prefix is not training work — recording it
    would churn the ring with junk spans (and could evict the real
    capture)."""
    it = iter(it)
    n = 0
    while True:
        if not _ENABLED or n < skip_first:
            item = next(it, _DONE)
            if item is _DONE:
                return
            n += 1
            yield item
            continue
        n += 1
        root = Span(name, cat, {"iter": n})
        root.__enter__()
        got_item = False
        try:
            t0 = time.perf_counter_ns()
            item = next(it, _DONE)
            if item is _DONE:
                return
            emit_span("train.data_wait", t0, time.perf_counter_ns(),
                      parent=root.ctx, cat=cat)
            got_item = True
            yield item
        finally:
            # the finally runs on normal resume, on the consumer
            # breaking/raising (GeneratorExit via close()), and on the
            # exhaustion probe; the probe's root is unwound WITHOUT
            # recording — no phantom per-epoch train.step span
            if got_item:
                root.__exit__(None, None, None)
            else:
                # (its annotation can only be closed, not withdrawn: a
                # profiler capture shows the probe as a short train.step)
                root._ann.__exit__(None, None, None)
                _TLS.ctx = root._prev


# ---------------------------------------------------------------- export --
def spans(trace_id: Optional[int] = None):
    """Snapshot of recorded spans (optionally one trace's)."""
    with _LOCK:
        out = list(_SPANS)
    if trace_id is not None:
        out = [e for e in out if e.get("args", {}).get("trace") == trace_id]
    return out


def stats() -> dict:
    with _LOCK:
        return {"enabled": _ENABLED, "spans": len(_SPANS),
                "dropped": _DROPPED,
                "dir": _DIR or ""}


def _process_index() -> Optional[int]:
    """This process's mesh-runtime rank, or None single-process /
    before jax.distributed initialized. The tracer must never force a
    backend init (jax.process_count() WOULD — and a backend
    instantiated here would land before mesh_runtime can arm the gloo
    collectives config), so the distributed client's existence is the
    gate: no client = single-process naming."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        from jax._src import distributed as _dist

        if _dist.global_state.client is None:
            return None  # single-process or pre-init: pid-only naming
        return jax.process_index() if jax.process_count() > 1 else None
    except Exception:  # noqa: BLE001 — private surface / half-init
        return None


def export(path: Optional[str] = None, profiler_events=None,
           include_profiler: bool = True) -> str:
    """Write the merged trace: tracer spans + the profiler's host
    RecordEvent stream (pass `profiler_events` explicitly — e.g.
    ``prof.events()`` — or the live buffer is snapshotted) as ONE valid
    chrome-trace/Perfetto JSON. Default path:
    ``<FLAGS_trace_dir>/trace-<pid>.json``; under a multi-process mesh
    runtime each rank writes its own ``trace-p<process_index>-<pid>.json``
    and the process_index rides in the pid metadata row, so N per-rank
    files drop into one Perfetto session without colliding."""
    pidx = _process_index()
    if path is None:
        d = _DIR or "."
        name = f"trace-{os.getpid()}.json" if pidx is None else \
            f"trace-p{pidx}-{os.getpid()}.json"
        path = os.path.join(d, name)
    events = spans()
    if profiler_events is not None:
        events = events + list(profiler_events)
    elif include_profiler:
        from .. import profiler as _prof

        events = events + _prof.live_events()
    pname = "paddle_tpu" if pidx is None else f"paddle_tpu rank{pidx}"
    return _exporter.write_chrome_trace(path, events, process_name=pname)


# ------------------------------------------------------ device op scopes --
_OP_NAME_LINE = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.M)
_OP_SCOPES: dict = {}


def note_op_scopes(compiled_text: str) -> None:
    """Keep the map instruction name -> op_name of one compiled program's
    text (`Compiled.as_text()`). A device trace names an operation by its
    instruction but says nothing of the `jax.named_scope` it was traced
    under; a reader of the trace that runs in this process joins the two
    through `op_scopes()`. No-op with tracing off."""
    if _ENABLED:
        found = dict(_OP_NAME_LINE.findall(compiled_text))
        with _LOCK:
            _OP_SCOPES.update(found)


def op_scopes() -> dict:
    with _LOCK:
        return dict(_OP_SCOPES)


def reset() -> None:
    """Drop recorded spans and op scopes (tests; the ring keeps its
    capacity)."""
    global _DROPPED
    with _LOCK:
        _SPANS.clear()
        _OP_SCOPES.clear()
        _DROPPED = 0


__all__ = ["TraceContext", "Span", "span", "emit_span", "current_context",
           "use_context", "enabled", "reconfigure", "step_iter", "spans",
           "stats", "export", "reset", "note_op_scopes", "op_scopes"]
