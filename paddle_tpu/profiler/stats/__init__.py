"""Profiler statistics engine.

The subsystem the reference implements in
`python/paddle/profiler/profiler_statistic.py` (+ mem_tracing.h): consumes
the host RecordEvent stream and produces

- a per-op summary (calls, total/avg/max/min host time, analytic FLOPs,
  MFU),
- a per-layer roll-up keyed on the nn.Layer name stack,
- a per-step time/FLOPs/MFU series,
- a per-step HBM live/peak memory report with allocation events and
  compiled-step buffer-donation metadata.

Wiring: `install()` (called by Profiler.start) puts a hook on
core/dispatch.apply — every eager op dispatch records an Operator event
carrying its duration, analytic FLOPs (core/dispatch.FLOPS_REGISTRY) and
the enclosing layer path; `uninstall()` removes it, restoring zero
dispatch overhead.
"""
from __future__ import annotations

from typing import Optional

from ...core import dispatch as _dispatch
from ...core import state as _st
from . import aggregator, memory
from .aggregator import (OpStat, build_table, fmt_bytes, fmt_flops,
                         layer_stats, op_stats)
from .flops import DEVICE_PEAKS, device_peak_flops, device_peaks
from .memory import MemoryTracer

__all__ = [
    "install", "uninstall", "active", "add_flops", "note_donation",
    "DEVICE_PEAKS", "device_peaks", "device_peak_flops", "build_summary",
    "build_summary_dict",
    "op_stats", "layer_stats",
    "OpStat", "MemoryTracer", "build_table", "fmt_flops", "fmt_bytes",
    "register_summary_provider", "unregister_summary_provider",
]


# Subsystems outside the dispatch stream (e.g. the inference serving
# engine) publish their own digest section into summary_dict via a named
# provider: fn() -> dict | None (None/empty = section omitted). The
# registry itself lives on the run-wide metrics bus
# (observability.bus) — one registry serves summary_dict, the bus's
# Prometheus textfile and any future consumer; these wrappers keep the
# historical call sites working. The bus hardens the contract: a
# raising provider is logged and skipped, duplicate registration is
# idempotent (same key replaces, never duplicates a section).


def register_summary_provider(key: str, fn) -> None:
    from ...observability import bus as _bus

    _bus.register_provider(key, fn)


def unregister_summary_provider(key: str) -> None:
    from ...observability import bus as _bus

    _bus.unregister_provider(key)


class Session:
    """One recording window (Profiler.start .. stop)."""

    def __init__(self, profiler):
        self.profiler = profiler
        self.with_flops = bool(getattr(profiler, "with_flops", True))
        self.profile_memory = bool(getattr(profiler, "profile_memory",
                                           False))
        self.record_shapes = bool(getattr(profiler, "record_shapes", False))
        self.memory = MemoryTracer()
        # FLOPs of ops executed eagerly (counted into the current step)
        self.step_flops = 0
        # FLOPs of ops seen while TRACING a compiled program — counted
        # separately so a program's trace-time pass isn't booked as an
        # executed step (jit.TrainStep re-books 3x its forward count per
        # executed call instead)
        self.trace_flops = 0

    def add_step_flops(self, n: int):
        self.step_flops += int(n)


_SESSION: Optional[Session] = None


def active() -> Optional[Session]:
    return _SESSION


def add_flops(n: int):
    """Book `n` executed FLOPs into the current step (used by compiled
    steps whose ops don't re-dispatch eagerly). No-op when idle."""
    s = _SESSION
    if s is not None:
        s.add_step_flops(n)


def note_donation(report: dict):
    """Record compiled-step buffer-donation metadata. No-op when idle."""
    s = _SESSION
    if s is not None:
        s.memory.note_donation(report)


def _arrays(tree):
    from jax import tree_util

    from ...core.tensor import Tensor

    out = []
    for leaf in tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, Tensor)):
        v = leaf._data if isinstance(leaf, Tensor) else leaf
        if hasattr(v, "shape") and hasattr(v, "dtype"):
            out.append(v)
    return out


def _op_hook(name, begin_ns, end_ns, args, kwargs, out):
    s = _SESSION
    if s is None:
        return
    from ... import profiler as _prof

    invals = _arrays(args)
    outvals = _arrays(out)
    tracing = _st.STATE.func_trace > 0
    ev_args = {"layer": _prof.current_layer()}
    if s.with_flops:
        f = _dispatch.flops_for(name, invals, outvals, kwargs)
        ev_args["flops"] = f
        if tracing:
            s.trace_flops += f
        else:
            s.step_flops += f
    if tracing:
        ev_args["traced"] = True
    if s.record_shapes:
        ev_args["shapes"] = [tuple(int(d) for d in v.shape) for v in invals]
    if s.profile_memory and not tracing:
        nbytes = 0
        for v in outvals:
            try:
                nbytes += int(v.nbytes)
            except Exception:  # noqa: BLE001
                pass
        if nbytes:
            s.memory.on_alloc(name, nbytes)
    _prof._emit_event(name, begin_ns, end_ns, "Operator", ev_args)


def install(profiler) -> Session:
    """Begin recording: install the dispatch hook (and, with
    profile_memory, subscribe the memory tracer to
    device.record_memory_event)."""
    global _SESSION
    sess = Session(profiler)
    _SESSION = sess
    _dispatch.set_profile_hook(_op_hook)
    if sess.profile_memory:
        from ... import device

        device.set_memory_hook(sess.memory.on_alloc)
    return sess


def uninstall(session: Session):
    global _SESSION
    if _SESSION is not session:
        return
    _SESSION = None
    _dispatch.set_profile_hook(None)
    if session.profile_memory:
        from ... import device

        device.set_memory_hook(None)


# ------------------------------------------------------------- summaries --
def _ms(us: float) -> str:
    return f"{us / 1000.0:.3f}"


def _mfu_str(flops: int, seconds: float, peak) -> str:
    if not flops or seconds <= 0 or not peak:
        return "-"
    return f"{flops / seconds / peak * 100:.2f}%"


def build_summary(prof, sorted_by=None, time_unit="ms") -> str:
    """Render every summary section from a (stopped or live) Profiler."""
    events = prof.events()
    ops = op_stats(events)
    peak = device_peak_flops()
    sections = [
        f"Profiler statistics (time unit: ms; FLOPs are analytic forward "
        f"counts; MFU basis "
        f"{fmt_flops(peak) + 'FLOP/s' if peak else 'none (CPU run)'})"
    ]

    rows = []
    for st in sorted(ops.values(), key=lambda s: -s.total):
        rows.append([
            st.name, st.calls, _ms(st.total), _ms(st.avg), _ms(st.max),
            _ms(st.min if st.calls else 0.0),
            fmt_flops(st.flops) if st.flops else "-",
            _mfu_str(st.flops, st.total / 1e6, peak),
        ])
    sections.append(build_table(
        "Operator Summary",
        ["Name", "Calls", "Total", "Avg", "Max", "Min", "FLOPs", "MFU"],
        rows))

    # dispatch-cache health rides with the Operator Summary: a cold or
    # thrashing plan cache is itself the top "operator" on eager traces
    cache = _dispatch.dispatch_cache_stats()
    crows = []
    for layer in ("plan", "jit", "vjp", "persistent"):
        st = cache.get(layer)
        if not st:
            continue
        h, m = st.get("hits", 0), st.get("misses", 0)
        rate = f"{h / (h + m):.1%}" if (h + m) else "-"
        size = st.get("size", st.get("entries", "-"))
        crows.append([layer, h, m, rate, size])
    sections.append(build_table(
        "Dispatch Cache Summary",
        ["Cache", "Hits", "Misses", "HitRate", "Size"], crows))

    from ...observability import bus as _bus

    for key, section in _bus.collect().items():
        prows = [[k, v] for k, v in section.items()
                 if not isinstance(v, (dict, list))]
        sections.append(build_table(
            f"{key.title()} Summary", ["Key", "Value"], prows))

    layers = layer_stats(events)
    lrows = []
    for st in sorted(layers.values(), key=lambda s: s.name):
        lrows.append([
            st.name, st.calls, _ms(st.total), _ms(st.avg),
            fmt_flops(st.flops) if st.flops else "-",
            _mfu_str(st.flops, st.total / 1e6, peak),
        ])
    sections.append(build_table(
        "Layer Summary (nn.Layer name stack)",
        ["Layer", "Calls", "Total", "Avg", "FLOPs", "MFU"], lrows))

    srows = []
    for r in getattr(prof, "step_records", []):
        srows.append([
            r["step"], f"{r['time_ms']:.3f}", fmt_flops(r["flops"]),
            fmt_flops(r["flops_per_sec"]) + "/s",
            "-" if r["mfu"] is None else f"{r['mfu'] * 100:.2f}%",
        ])
    sections.append(build_table(
        "Step Summary",
        ["Step", "Time(ms)", "FLOPs", "FLOP/s", "MFU"], srows))

    sess = getattr(prof, "_session", None)
    if sess is not None and sess.memory.steps:
        mem = sess.memory
        mrows = [[r["step"], r["live_arrays"], fmt_bytes(r["live_bytes"]),
                  fmt_bytes(r["bytes_in_use"]), fmt_bytes(r["peak_bytes"]),
                  r["alloc_events"]] for r in mem.steps]
        sections.append(build_table(
            "Memory Summary (per-step HBM)",
            ["Step", "LiveArrays", "Live", "InUse", "Peak", "AllocEvents"],
            mrows))
        if mem.donation:
            parts = []
            for k, v in mem.donation.items():
                if k.endswith("bytes") and isinstance(v, (int, float)):
                    parts.append(f"{k}={fmt_bytes(v)}")
                else:
                    parts.append(f"{k}={v}")
            sections.append("buffer donation: " + ", ".join(parts))

    return "\n\n".join(sections)


def build_summary_dict(prof, top_ops: int = 8) -> dict:
    """Structured digest for machine consumers (bench.py)."""
    events = prof.events()
    ops = op_stats(events)
    peak = device_peak_flops()
    steps = list(getattr(prof, "step_records", []))
    out = {"device_peak_flops": peak}
    if steps:
        out["steps"] = len(steps)
        out["avg_step_time_ms"] = round(
            sum(r["time_ms"] for r in steps) / len(steps), 3)
        out["flops_per_step"] = int(max(r["flops"] for r in steps))
        if peak:
            out["avg_mfu"] = round(
                sum(r["mfu"] for r in steps) / len(steps), 4)
    out["top_ops"] = [
        {"name": st.name, "calls": st.calls,
         "total_ms": round(st.total / 1000.0, 3), "flops": int(st.flops)}
        for st in sorted(ops.values(), key=lambda s: -s.total)[:top_ops]
    ]
    out["dispatch_cache"] = _dispatch.dispatch_cache_stats()
    sess = getattr(prof, "_session", None)
    if sess is not None and sess.memory.steps:
        last = sess.memory.steps[-1]
        out["memory"] = {
            "peak_bytes": last["peak_bytes"],
            "live_bytes": last["live_bytes"],
            "bytes_in_use": last["bytes_in_use"],
            "alloc_events": last["alloc_events"],
        }
        if sess.memory.donation:
            out["donation"] = sess.memory.donation
    from ...observability import bus as _bus

    # the bus's collect() applies the log-and-skip contract: a sick
    # provider must not sink the whole digest
    out.update(_bus.collect())
    return out
