"""Shared serving-tier lifecycle primitives.

Both serving fronts — the single-shot predict engine (engine.py) and
the continuous-batching generation scheduler (generate.py) — run the
same replica state machine (warming -> active -> draining -> retired,
generation counter superseding hung workers) and complete requests
through the same first-set-wins Future. Factored here so the autoscale
controllers (paddle_tpu/autoscale: ReplicaAutoscaler, HealthWatchdog)
drive ONE contract: ``replica_states()`` rows with monotonic ages,
``add_replica``/``remove_replica``/``revive_replica`` verbs, and error
statuses that map onto HTTP semantics.
"""
from __future__ import annotations

import threading
import time
from queue import Queue
from typing import List, Optional

from ...testing.racecheck import shared_state as _shared_state


class ServingError(Exception):
    """Engine-level request failure; `status` follows HTTP semantics
    (400 decode/shape, 503 shed/deadline/shutdown, 500 runtime)."""

    def __init__(self, status: int, message: str,
                 retry_after: Optional[float] = None):
        super().__init__(message)
        self.status = int(status)
        self.message = message
        self.retry_after = retry_after


class Future:
    """Completion handle for one submitted request.

    Completion is idempotent — the FIRST set wins. The watchdog may
    requeue a hung replica's batch onto a healthy one; if the zombie
    thread later unwedges and reports too, its late completion must not
    clobber the result a client already consumed.
    """

    def __init__(self):
        self._ev = threading.Event()
        self._lock = threading.Lock()
        self._result = None
        self._error: Optional[BaseException] = None

    def set_result(self, result) -> bool:
        with self._lock:
            if self._ev.is_set():
                return False
            self._result = result
            self._ev.set()
            return True

    def set_error(self, err: BaseException) -> bool:
        with self._lock:
            if self._ev.is_set():
                return False
            self._error = err
            self._ev.set()
            return True

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("serving request timed out")
        if self._error is not None:
            raise self._error
        return self._result


@_shared_state("state", "generation", "thread", "last_beat",
               "busy_since", "inflight", "batches", "compiling")
class ReplicaSlot:
    """One worker replica: a device binding, a dispatch queue and a
    worker thread. `state` lifecycle: warming -> active -> draining ->
    retired. `generation` supersedes a hung worker: the loop exits as
    soon as it observes a newer generation (revive_replica).

    The lifecycle fields are racecheck-designated shared state: worker
    threads, the batcher, the watchdog and the autoscaler all touch
    them, and the owning engine's condition variable is their one
    guard (testing/racecheck gates the serving suites at zero race
    findings)."""

    __slots__ = ("rid", "device", "q", "thread", "state", "generation",
                 "last_beat", "busy_since", "inflight", "batches",
                 "compiling", "launched")

    def __init__(self, rid: int, device, queue_depth: int = 2):
        self.rid = rid
        self.device = device
        self.q: Queue = Queue(maxsize=queue_depth)
        self.thread: Optional[threading.Thread] = None
        self.state = "warming"
        self.generation = 0
        self.last_beat = time.monotonic()
        self.busy_since: Optional[float] = None
        self.inflight: List = []
        self.batches = 0
        # True while the current batch is a first-compile of its
        # executable (key not warmed): the watchdog must not read a
        # legitimate XLA compile as a hang
        self.compiling = False
        # decode steps this worker has launched: a step's number from its
        # launch to its read (the generation engine's spans); the worker
        # thread's own
        self.launched = 0

    def state_row(self, now: Optional[float] = None) -> dict:
        """Watchdog's view: one row with monotonic ages (the
        HealthWatchdog contract — busy_s past its exec deadline or a
        stale beat_age_s is a strike)."""
        if now is None:
            now = time.monotonic()
        busy = self.busy_since
        return {
            "rid": self.rid,
            "state": self.state,
            "generation": self.generation,
            "device": str(self.device),
            "beat_age_s": now - self.last_beat,
            "busy_s": (now - busy) if busy is not None else 0.0,
            "inflight": len(self.inflight),
            "batches": self.batches,
            "compiling": self.compiling,
        }


def validate_sampling(obj) -> dict:
    """Request-side validation of the generation sampling fields,
    shared by the engine, the HTTP front, the fabric front door and
    FleetClient — a malformed request 400s at the FIRST hop it touches,
    before it can burn a KV slot anywhere in the fleet.

    Rules: ``temperature`` is a number >= 0, ``top_k`` an int >= 1,
    ``top_p`` in (0, 1], ``seed`` an integer. Returns the four fields
    (None where absent); raises ServingError(400) on violation. Kept in
    this jax-free module so the lightweight fabric client can import it
    without dragging the engine's dependencies in."""
    out = {}
    t = obj.get("temperature")
    if t is not None:
        if isinstance(t, bool) or not isinstance(t, (int, float)) or \
                not (float(t) >= 0.0):
            raise ServingError(
                400, f"temperature must be a number >= 0 (got {t!r})")
        t = float(t)
    out["temperature"] = t
    k = obj.get("top_k")
    if k is not None:
        if isinstance(k, bool) or not isinstance(k, int) or k < 1:
            raise ServingError(
                400, f"top_k must be an integer >= 1 (got {k!r})")
        k = int(k)
    out["top_k"] = k
    p = obj.get("top_p")
    if p is not None:
        if isinstance(p, bool) or not isinstance(p, (int, float)) or \
                not (0.0 < float(p) <= 1.0):
            raise ServingError(
                400, f"top_p must be in (0, 1] (got {p!r})")
        p = float(p)
    out["top_p"] = p
    s = obj.get("seed")
    if s is not None:
        if isinstance(s, bool) or not isinstance(s, int):
            raise ServingError(
                400, f"seed must be an integer (got {s!r})")
        s = int(s)
    out["seed"] = s
    return out


def pick_least_loaded_device(device_pool, replicas) -> object:
    """Least-loaded device in the pool by live-replica count (replicas
    on one device share executables but contend for it)."""
    counts = {id(d): 0 for d in device_pool}
    for rep in replicas:
        if rep.state in ("warming", "active", "draining"):
            counts[id(rep.device)] = counts.get(id(rep.device), 0) + 1
    return min(device_pool, key=lambda d: counts[id(d)])


__all__ = ["ServingError", "Future", "ReplicaSlot",
           "pick_least_loaded_device", "validate_sampling"]
