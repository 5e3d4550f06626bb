"""The program's own spans and names, read from the same `.xplane.pb` as
the device's operations: what the host was doing in each idle gap of the
device, the train step's device time by scope, the flash kernels by the
names the program gave them.

`run["trace"]` (trace_reduce's dictionary) holds no host plane and no
scopes, so this module opens the cell's profile itself — the newest
`.xplane.pb` under `<checkout>/.bench_tmp/trace/<cell name>`, where run.py
had the profiler write it — with `jax.profiler.ProfileData`, and reuses
trace_reduce's interval arithmetic unchanged. It looks for:

- **Program spans.** While FLAGS_trace_dir is set, every live span of
  `paddle_tpu.observability.trace` also holds a `jax.profiler.TraceAnnotation`
  of its name with its ids as metadata, so it is an event of the
  "/host:CPU" plane on the profiler's clock; an event there with a `span`
  stat is the program's (the runtime's own host events carry none). Host
  lines are named after the native thread ("python" for every Python
  thread), so a thread is its line's name and position in the plane.
- **Scopes.** `TrainStep` traces the loss under `jax.named_scope("train.loss")`
  and the update under "train.optimizer"; jax adds `jvp(…)`,
  `transpose(jvp(…))` and `rematted_computation` itself. A v5e trace does
  not carry them: an "XLA Ops" event's stats are its device offset and
  duration, and the instruction text that is its name ends before
  `metadata={op_name=…}` (PERF.md §3). So the scope of an operation is
  looked up by its instruction name in the map that `TrainStep` parses
  from its compiled text and hands to `observability.trace.op_scopes()`
  — this reader runs in the program's process, after the window.
- **Kernels.** A `pallas_call(name=…)` names its HLO instruction
  (`%flash_fwd.14`), so the flash kernels are the events whose instruction
  name, less its number, is `flash_fwd` or `flash_bwd_dkv` (the one fused
  backward since PR 27).

Against a program that has none of these (the parent of the PR that
brought them) every function here finds nothing and says so with None or
an empty split; none raises.
"""
from __future__ import annotations

import bisect
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from . import cells, common
from . import trace_reduce as tr

HOST_PLANE = "/host:CPU"
SCOPE_CLASSES = ("forward", "backward", "recompute", "optimizer", "other")
KERNELS = ("flash_fwd", "flash_bwd_dkv")
UNATTRIBUTED = "unattributed"

Span = Tuple[str, str, float, float, dict]     # name, thread, start, end, args
_LOADED: dict = {}                              # path -> view, newest only


def trace_dir(run: dict) -> str:
    return os.path.join(cells.ROOT, ".bench_tmp", "trace",
                        run["cell"]["name"])


def scope_class(op_name: str) -> str:
    """The train step's part an operation belongs to, by the scope jax
    recorded for it. In this order: the optimizer's scope; the transposed
    (backward) pass, of which what lies under `rematted_computation` is
    the forward run again; the loss's scope otherwise is the forward."""
    if "train.optimizer" in op_name:
        return "optimizer"
    if "transpose(" in op_name:
        return "recompute" if "rematted_computation" in op_name \
            else "backward"
    if "train.loss" in op_name:
        return "forward"
    return "other"


def kernel_of(text: str, op_name: str = "") -> Optional[str]:
    """"%flash_fwd.14 = (…) custom-call(…)" -> "flash_fwd"; None for an
    operation that is not one of the flash kernels. Where a transform
    around the kernel gave the instruction its own name instead, a
    custom-call traced under "…/flash_fwd/pallas_call" is the kernel."""
    base = re.sub(r"\.\d+$", "", tr.short_name(text))
    if base in KERNELS:
        return base
    if tr.op_class(text) == "custom-call":
        for k in KERNELS:
            if f"/{k}/" in op_name:
                return k
    return None


def program_scopes() -> Dict[str, str]:
    """Instruction name -> op_name as the program's tracer holds it; {}
    where the program has no such map."""
    try:
        from paddle_tpu.observability import trace as tracer
    except ImportError:
        return {}
    return dict(getattr(tracer, "op_scopes", dict)())


def view_of(profile, scopes: Optional[Dict[str, str]] = None) -> dict:
    """A ProfileData -> {"spans": [Span], "ops": {device: [(text, start,
    end, op_name, self time)]}, "modules": {device: [(name, start, end)]},
    "window": (w0, w1)} in nanoseconds of the profile's one clock; the
    window is trace_reduce's (first operation to last, over the devices)."""
    scopes = program_scopes() if scopes is None else scopes
    ops, modules = {}, {}
    for d, lines in tr.device_lines(profile).items():
        rows = [ev for name in tr.OP_LINES for ev in lines.get(name, [])]
        if rows:
            # self_times keeps its argument's order
            ops[d] = [(t, s, e, scopes.get(tr.short_name(t), ""), self_ns)
                      for t, s, e, self_ns, _ in tr.self_times(rows)]
        modules[d] = lines.get("XLA Modules", [])
    spans: List[Span] = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                args = dict(ev.stats)
                if "span" in args:
                    s = float(ev.start_ns)
                    spans.append((ev.name, f"{line.name}#{i}", s,
                                  s + float(ev.duration_ns), args))
    window = None
    if ops:
        window = (min(r[1] for rows in ops.values() for r in rows),
                  max(r[2] for rows in ops.values() for r in rows))
    return {"spans": spans, "ops": ops, "modules": modules,
            "window": window}


def load(run: dict) -> Optional[dict]:
    """The view of the cell's profile; None for a run without a trace,
    without a profile on disk, or with no operation on a device."""
    if not run.get("trace") or "cell" not in run:
        return None
    try:
        path = tr.find_xplane(trace_dir(run))
    except FileNotFoundError:
        return None
    if path not in _LOADED:
        from jax.profiler import ProfileData

        _LOADED.clear()
        _LOADED[path] = view_of(ProfileData.from_file(path))
    view = _LOADED[path]
    return view if view["ops"] else None


def spans_by(view: dict) -> dict:
    """{(name, thread): [(start, end, args)]} of the program's spans."""
    out: dict = {}
    for name, thread, s, e, args in view["spans"]:
        out.setdefault((name, thread), []).append((s, e, args))
    return out


# ------------------------------------------------------------ idle gaps --
def split_by_span(gaps: Sequence[tr.Interval],
                  spans: Sequence[Tuple[str, float, float]]) -> tuple:
    """({span name: ns}, what is left) of the merged intervals `gaps`,
    each instant given to the innermost span that covers it — the one
    opened last, which among the spans of one thread is the most deeply
    nested — and to "unattributed" where none does; what is left are
    those uncovered intervals themselves."""
    left = sorted(gaps)
    out: dict = {}
    for name, s, e in sorted(spans, key=lambda r: -r[1]):
        # the gaps that (s, e) overlaps are one slice of the sorted rest
        i = bisect.bisect_right(left, s, key=lambda iv: iv[1])
        j = bisect.bisect_left(left, e, key=lambda iv: iv[0])
        if i >= j:
            continue
        rest = tr.subtract(left[i:j], [(s, e)])
        out[name] = out.get(name, 0.0) \
            + tr.measure(left[i:j]) - tr.measure(rest)
        left[i:j] = rest
    out[UNATTRIBUTED] = tr.measure(left)
    return out, left


def between(pieces: Sequence[tr.Interval],
            spans: Sequence[Tuple[str, float, float]]) -> dict:
    """{"after A, before B": ns} of uncovered `pieces`: the span that
    ended last before each and the one that began first after it."""
    ends = sorted((e, n) for n, _, e in spans)
    starts = sorted((s, n) for n, s, _ in spans)
    end_t, start_t = [e for e, _ in ends], [s for s, _ in starts]
    out: dict = {}
    for a, b in pieces:
        i = bisect.bisect_right(end_t, a + 1.0) - 1
        j = bisect.bisect_left(start_t, b - 1.0)
        key = (f"after {ends[i][1] if i >= 0 else 'the first span'}, "
               f"before {starts[j][1] if j < len(starts) else 'the end'}")
        out[key] = out.get(key, 0.0) + b - a
    return out


def _seconds(split: dict, top: Optional[int] = None) -> dict:
    return {k: v / 1e9 for k, v in
            sorted(split.items(), key=lambda kv: -kv[1])[:top]}


def idle_by_span(view: dict) -> dict:
    """The lowest device's idle time inside the window, split by program
    span -> {"idle_s", "attributed_share", "by_span": {name: seconds},
    "unattributed_between": {"after A, before B": seconds}, "longest_gaps":
    [{"seconds", "by_span", "after_op", "before_op"}]}; by_span holds
    "unattributed" too, and a gap lies between two of the device's
    operations, named as trace_reduce names them."""
    dev = min(view["ops"])
    ops = view["ops"][dev]

    def op_ending_before(t: float) -> str:
        row = max((r for r in ops if r[2] <= t), key=lambda r: r[2],
                  default=None)
        return tr.short_name(row[0]) if row else "start"

    def op_starting_after(t: float) -> str:
        row = min((r for r in ops if r[1] >= t), key=lambda r: r[1],
                  default=None)
        return tr.short_name(row[0]) if row else "end"

    w0, w1 = view["window"]
    busy = tr.union((max(r[1], w0), min(r[2], w1)) for r in view["ops"][dev])
    gaps = tr.subtract([(w0, w1)], busy)
    idle = tr.measure(gaps)
    spans = [(n, s, e) for n, _, s, e, _ in view["spans"]
             if e > w0 and s < w1]
    split, left = split_by_span(gaps, spans)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    return {
        "idle_s": idle / 1e9,
        "attributed_share": (1.0 - split[UNATTRIBUTED] / idle)
        if idle else None,
        "by_span": _seconds(split),
        "unattributed_between": _seconds(between(left, spans), top=6),
        "longest_gaps": [
            {"seconds": (b - a) / 1e9,
             "by_span": _seconds(split_by_span([(a, b)], spans)[0]),
             "after_op": op_ending_before(a),
             "before_op": op_starting_after(b)}
            for a, b in longest],
    }


def longest_gaps_by_span(run: dict, top: int = 2) -> Optional[list]:
    """The result line's `breakdown.idle_gaps` by what the host was doing:
    [[name, seconds]] of the lowest device's five longest idle gaps, each
    named by the `top` spans that cover most of it with their shares
    ("generate.decode_step.stage 47% + generate.decode_step.wait 32%");
    the part no span covers says between which operations the gap lies
    ("unattributed 100% (after pad_add_fusion, before copy.403)"). None
    where the run has no profile or the profile no program span."""
    view = load(run)
    if view is None or not view["spans"]:
        return None
    out = []
    for gap in idle_by_span(view)["longest_gaps"]:
        parts = [f"{name} {100 * s / gap['seconds']:.0f}%"
                 for name, s in list(gap["by_span"].items())[:top] if s > 0]
        name = " + ".join(parts)
        if UNATTRIBUTED in name:
            name += f" (after {gap['after_op']}, before {gap['before_op']})"
        out.append([name, gap["seconds"]])
    return out


def modules_inside(view: dict, span_names: Sequence[str]) -> dict:
    """Do the two clocks agree? A span that brackets a program's dispatch
    and the blocking read of its results must contain the program's run
    on the device. {"events": n, "inside": {span name: events of the
    lowest device's "XLA Modules" line wholly inside one span of that
    name}, "outside": events inside none} — each event counted under the
    first of `span_names` that holds it."""
    dev = min(view["ops"])
    hosts = {name: [(s, e) for n, _, s, e, _ in view["spans"] if n == name]
             for name in span_names}
    modules = view["modules"].get(dev, [])
    inside = dict.fromkeys(span_names, 0)
    for _, s, e in modules:
        for name in span_names:
            if any(a <= s and e <= b for a, b in hosts[name]):
                inside[name] += 1
                break
    return {"events": len(modules), "inside": inside,
            "outside": len(modules) - sum(inside.values())}


# ---------------------------------------------------- the step by scope --
def busy_by_scope(view: dict) -> Optional[dict]:
    """The lowest device's self time by scope class -> {"busy_s",
    "seconds": {class: s}, "share": {class: share of busy}}; None where
    no operation of the trace carries a train scope (a program without
    the scopes, or a serving cell)."""
    seconds = dict.fromkeys(SCOPE_CLASSES, 0.0)
    for _, _, _, op_name, self_ns in view["ops"][min(view["ops"])]:
        seconds[scope_class(op_name)] += self_ns / 1e9
    busy = sum(seconds.values())
    if not busy or seconds["other"] == busy:
        return None
    return {"busy_s": busy, "seconds": seconds,
            "share": {k: v / busy for k, v in seconds.items()}}


def scope_share(run: dict, cls: str) -> Optional[float]:
    """One class's share of `busy_by_scope` of the cell's profile (None
    as there); the whole split is noted once for the profile."""
    view = load(run)
    if view is None:
        return None
    if "by_scope" not in view:
        view["by_scope"] = busy_by_scope(view)
        if view["by_scope"]:
            note(run, "busy_by_scope.json", busy_by_scope=view["by_scope"])
    return view["by_scope"]["share"][cls] if view["by_scope"] else None


# -------------------------------------------------------- flash kernels --
def kernel_events(view: dict) -> dict:
    """{kernel: {"events", "seconds"}} of the flash kernels on the lowest
    device, found by the instruction names the program gave them."""
    out: dict = {}
    for t, _, _, op_name, self_ns in view["ops"][min(view["ops"])]:
        k = kernel_of(t, op_name)
        if k:
            got = out.setdefault(k, {"events": 0, "seconds": 0.0})
            got["events"] += 1
            got["seconds"] += self_ns / 1e9
    return out


def flash_roofline(kernels: dict, fwd_s: float, bwd_s: float
                   ) -> Optional[dict]:
    """The kernels' share of their roofline: the least time the calls
    seen could take over the time they took. `fwd_s`/`bwd_s`: the least
    time of ONE forward call and of ONE backward pass. A remat'd forward
    is a call; a backward pass is one `flash_bwd_dkv` event."""
    took = sum(k["seconds"] for k in kernels.values())
    if not took:
        return None
    n_fwd = kernels.get("flash_fwd", {}).get("events", 0)
    n_bwd = kernels.get("flash_bwd_dkv", {}).get("events", 0)
    least = n_fwd * fwd_s + n_bwd * bwd_s
    return {"forward_calls": n_fwd, "backward_passes": n_bwd,
            "least_s": least, "took_s": took, "share": least / took}


# ---------------------------------------------------------------- output --
def note(run: dict, file_name: str, **fields) -> None:
    """A reader's split: an earlier free-form line of stdout, and a file
    beside reduced.json."""
    common.log(**fields)
    with open(os.path.join(trace_dir(run), file_name), "w") as fh:
        json.dump(fields, fh, indent=1)
