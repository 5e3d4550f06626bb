"""From the profiler's `.xplane.pb` to numbers: device busy union, idle
gaps, time by operation name and by operation class, and how much of the
collectives' time nothing else ran under.

Reads the file with `jax.profiler.ProfileData` and nothing else. What a
TPU v5e trace holds (looked at by hand, PERF.md §3): one plane per chip,
"/device:TPU:<n>", whose line "XLA Ops" carries one event per executed HLO
operation. An event's name is the instruction's whole text,
"%convolution_add_fusion.11 = bf16[64,1024,4096]{…} fusion(…)": the
instruction's own name comes before " = " and its opcode after the result
shape. The opcode is the class ("fusion", "custom-call" — a Pallas kernel
is `custom-call` with custom_call_target="tpu_custom_call" —, "copy",
"while", "all-reduce", …). Container operations (a `while` around a
scanned body) enclose their body's events, so time by name is SELF time:
an event's duration less that of the events nested in it. The line "Async
XLA Ops" carries asynchronous operations (copy-start, and across chips the
collectives) from their start to their done; it counts for the
collectives' time only. "XLA Modules" and "Steps" repeat the same time at
a coarser grain and are not summed.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
ASYNC_LINES = ("Async XLA Ops",)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")

Interval = Tuple[float, float]          # (start_ns, end_ns)


def short_name(text: str) -> str:
    """"%fusion.12 = bf16[…] fusion(…)" -> "fusion.12"."""
    return text.split(" = ", 1)[0].lstrip("%")


def opcode(text: str) -> str:
    """The opcode of an instruction's text: what follows its result shape
    (a tuple shape is in parentheses and may hold spaces). A bare name
    ("fusion.12") gives its prefix."""
    if " = " not in text:
        return re.sub(r"\.\d+$", "", text.lstrip("%"))
    rest = text.split(" = ", 1)[1]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.strip().split("(", 1)[0]


def op_class(text: str) -> str:
    """The class of an operation: its opcode; every fusion kind is
    "fusion"; an async collective's "-start"/"-done" halves count as the
    collective."""
    code = opcode(text)
    for c in COLLECTIVES:
        if code.startswith(c):
            return c
    if "fusion" in code:
        return "fusion"
    return code


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union `a` that union `b` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: Sequence[Tuple[str, float, float]]):
    """[(name, start, end)] of ONE line -> [(name, start, end, self_ns,
    is_leaf)]: an event that lies inside another is its child."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    leaf = [True] * len(events)
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][2]:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(n, s, e, max(e - s - child[i], 0.0), leaf[i])
            for i, (n, s, e) in enumerate(events)]


def reduce_device(events: Sequence[Tuple[str, float, float]],
                  window: Interval,
                  async_events: Sequence[Tuple[str, float, float]] = ()
                  ) -> dict:
    """One device's operation events (the "XLA Ops" line) inside `window`
    -> its numbers, in seconds. `async_events` (the "Async XLA Ops" line)
    add only to the collectives' intervals: an asynchronous all-reduce
    runs from its start to its done while other operations go on."""
    w0, w1 = window

    def clip(rows):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in rows
                if e > w0 and s < w1]

    evs = clip(events)
    rows = self_times(evs)
    busy = union((s, e) for _, s, e in evs)
    by_name: dict = {}
    by_class: dict = {}
    for n, _, _, self_ns, _ in rows:
        c = op_class(n)
        key = f"{short_name(n)} ({c})"
        by_name[key] = by_name.get(key, 0.0) + self_ns
        by_class[c] = by_class.get(c, 0.0) + self_ns
    coll = union((s, e) for n, s, e in evs + clip(async_events)
                 if op_class(n) in COLLECTIVES)
    compute = union((s, e) for n, s, e, _, is_leaf in rows
                    if is_leaf and op_class(n) not in COLLECTIVES)
    gaps = sorted(((b - a, a, b) for a, b in subtract([(w0, w1)], busy)),
                  reverse=True)[:10]

    def neighbours(a: float, b: float) -> tuple:
        """The operation that ended last before the gap [a, b] and the
        one that started first after it."""
        before = max((ev for ev in evs if ev[2] <= a),
                     key=lambda ev: ev[2], default=("start",))
        after = min((ev for ev in evs if ev[1] >= b),
                    key=lambda ev: ev[1], default=("end",))
        return short_name(before[0]), short_name(after[0])

    def secs(d: dict) -> dict:
        return {k: v / 1e9 for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])}

    return {
        "events": len(evs),
        "busy_s": measure(busy) / 1e9,
        "by_name": secs(by_name), "by_class": secs(by_class),
        "collective_s": measure(coll) / 1e9,
        "exposed_collective_s": measure(subtract(coll, compute)) / 1e9,
        "idle_gaps": [dict(zip(("after_op", "before_op"), neighbours(a, b)),
                           seconds=g / 1e9, at_s=(a - w0) / 1e9)
                      for g, a, b in gaps],
    }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def device_lines(profile) -> dict:
    """{device index: {line name: [(name, start_ns, end_ns)]}} of every
    device plane of a ProfileData."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {}
        for line in plane.lines:
            lines[line.name] = [
                (ev.name, float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events]
        out[int(m.group(1))] = lines
    return out


def reduce_profile(profile) -> dict | None:
    """A ProfileData -> {"window_s", "devices": {index: reduce_device},
    "lines": {index: {line name: events}}}; None where no device plane
    holds an operation. The window runs from the first operation's start
    to the last one's end over all devices: the profiler's own start-up
    is not the device's idle time."""
    per_dev = device_lines(profile)
    ops = {d: [ev for name in OP_LINES for ev in lines.get(name, [])]
           for d, lines in per_dev.items()}
    ops = {d: evs for d, evs in ops.items() if evs}
    if not ops:
        return None
    w0 = min(s for evs in ops.values() for _, s, _ in evs)
    w1 = max(e for evs in ops.values() for _, _, e in evs)
    return {
        "window_s": (w1 - w0) / 1e9,
        "devices": {d: reduce_device(
            evs, (w0, w1), [ev for name in ASYNC_LINES
                            for ev in per_dev[d].get(name, [])])
            for d, evs in sorted(ops.items())},
        "lines": {d: {name: len(evs) for name, evs in lines.items()}
                  for d, lines in sorted(per_dev.items())},
    }


def reduce_file(path: str) -> dict | None:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def lowest_device(reduced: dict) -> dict:
    return reduced["devices"][min(reduced["devices"])]


def idle_share(reduced: dict) -> float:
    """1 - busy union / traced window, averaged over the chips used."""
    return 1.0 - mean_busy_s(reduced) / reduced["window_s"]


def breakdown(reduced: dict, gaps_by_span: list | None = None) -> dict:
    """The `breakdown` of the result line, from the lowest-numbered
    device: the ten operations with the most self time and the longest
    idle gaps. A gap is named by what the host was doing in it where the
    run has the program's spans on the profiler's clock (`gaps_by_span`,
    host_spans.longest_gaps_by_span); a run without spans (the train
    cells) says "unattributed", with the operations on either side."""
    dev = lowest_device(reduced)
    return {
        "device_ops": [[n, s] for n, s in list(dev["by_name"].items())[:10]],
        "idle_gaps": gaps_by_span or [
            [f"unattributed (after {g['after_op']}, before "
             f"{g['before_op']})", g["seconds"]]
            for g in dev["idle_gaps"][:5]],
    }


def mean_busy_s(reduced: dict) -> float:
    devs = reduced["devices"].values()
    return sum(d["busy_s"] for d in devs) / len(devs)
