"""Event aggregation and summary-table rendering.

Analog of the reference's `python/paddle/profiler/profiler_statistic.py`
(`_build_table`, EventSummary/StatisticData at :291): turns the raw host
RecordEvent stream (chrome-trace dicts) into per-op and per-layer
statistic tables. Device time is not here: this jax's profiler leaves an
`.xplane.pb`, which benchmarks/harness/trace_reduce.py reads.

Event categories (the `cat` field):
- ``Operator``     — one dispatch through core/dispatch.apply; carries
  ``args.flops`` (analytic) and ``args.layer`` (name-stack path).
- ``Forward``      — one nn.Layer.__call__ span, named with the dotted
  name-stack path (the ModelView key).
- ``ProfileStep``  — one Profiler.step() window.
- everything else (``UserDefined``/``PythonOp``/...) — user spans, listed
  in the op table without FLOPs.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

_OP_CATS = ("Operator", "PythonOp", "UserDefined", "ProfileStep",
            "Dataloader", "Communication", "Optimization")


class OpStat:
    """Per-key accumulator: calls, host total/max/min (us), analytic
    FLOPs."""

    __slots__ = ("name", "cat", "calls", "total", "max", "min", "flops")

    def __init__(self, name: str, cat: str = "Operator"):
        self.name = name
        self.cat = cat
        self.calls = 0
        self.total = 0.0
        self.max = 0.0
        self.min = float("inf")
        self.flops = 0

    def add(self, dur_us: float, flops: int = 0):
        self.calls += 1
        self.total += dur_us
        self.max = max(self.max, dur_us)
        self.min = min(self.min, dur_us)
        self.flops += int(flops)

    @property
    def avg(self) -> float:
        return self.total / self.calls if self.calls else 0.0


def op_stats(events: Iterable[dict]) -> Dict[str, OpStat]:
    """Aggregate op-class events by name."""
    out: Dict[str, OpStat] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") not in _OP_CATS:
            continue
        name = e["name"]
        st = out.get(name)
        if st is None:
            st = out[name] = OpStat(name, e.get("cat", "Operator"))
        st.add(float(e.get("dur", 0.0)),
               int((e.get("args") or {}).get("flops", 0)))
    return out


def layer_stats(events: Iterable[dict]) -> Dict[str, OpStat]:
    """Aggregate Layer (Forward) spans by dotted name-stack path, then
    attribute op FLOPs to every enclosing layer (prefix match on the op
    event's ``args.layer``)."""
    out: Dict[str, OpStat] = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "Forward":
            continue
        path = e["name"]
        st = out.get(path)
        if st is None:
            st = out[path] = OpStat(path, "Forward")
        st.add(float(e.get("dur", 0.0)))
    for e in events:
        if e.get("ph") != "X" or e.get("cat") != "Operator":
            continue
        layer = (e.get("args") or {}).get("layer")
        if not layer:
            continue
        flops = int((e.get("args") or {}).get("flops", 0))
        if not flops:
            continue
        for path, st in out.items():
            if layer == path or layer.startswith(path + "."):
                st.flops += flops
    return out


# ------------------------------------------------------- table builder --
def build_table(title: str, headers: List[str], rows: List[List],
                widths: Optional[List[int]] = None) -> str:
    """Reference `_build_table`-style fixed-width section."""
    if widths is None:
        widths = []
        for i, h in enumerate(headers):
            w = len(str(h))
            for r in rows:
                w = max(w, len(str(r[i])))
            widths.append(min(w, 60))
    sep = "-" * (sum(widths) + 2 * len(widths))
    pad = max((len(sep) - len(title) - 4) // 2, 2)
    lines = ["-" * pad + f"  {title}  " + "-" * pad]
    fmt_cells = []
    for i, h in enumerate(headers):
        fmt_cells.append(f"{str(h):<{widths[i]}}" if i == 0
                         else f"{str(h):>{widths[i]}}")
    lines.append("  ".join(fmt_cells))
    lines.append(sep)
    for r in rows:
        cells = []
        for i, c in enumerate(r):
            s = str(c)
            if len(s) > 60:
                s = s[:57] + "..."
            cells.append(f"{s:<{widths[i]}}" if i == 0
                         else f"{s:>{widths[i]}}")
        lines.append("  ".join(cells))
    return "\n".join(lines)


def fmt_flops(n: float) -> str:
    n = float(n)
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(n) >= div:
            return f"{n / div:.2f}{unit}"
    return f"{n:.0f}"


def fmt_bytes(n: float) -> str:
    n = float(n)
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if abs(n) >= div:
            return f"{n / div:.1f}{unit}"
    return f"{n:.0f}B"
