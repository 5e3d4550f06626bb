"""Embedding fan-out router: the front door's recsys face.

A batched ``/lookup`` (N keys) is split by the consistent-hash vnode
ring (the SAME ``build_ring``/``ring_hosts`` the stream-affinity
router uses, so both tenants agree on ownership) into one hop per
owning shard host, the hops run concurrently on named threads, and the
answers reassemble in RANK ORDER — the caller gets rows[i] for keys[i]
no matter how the ring scattered them.

Failure rules, recsys edition of the fabric's:

- a transport fault on a shard hop (connect refused / reset / hop
  timeout) re-routes ONLY that hop's keys onto the ring REBUILT
  without the dead host — exactly the remap a real eviction would
  produce, so a SIGKILLed shard host costs one hop retry, not a lost
  lookup. Lookups are pure (they never materialize rows) so the retry
  budget is ``lookup_retries``; pushes retry ONCE (re-applying a
  gradient twice is a real, if bounded, skew — one bounded retry
  matches the fabric's non-streamed rule).
- a shard's OWN HTTP answer passes through (it is an answer, not a
  fault) — except 409, the epoch fence: with ``epoch=None`` (auto
  mode) the router re-reads the fleet epoch and retries ONCE; a caller
  that pinned an explicit epoch gets the 409 surfaced (that caller IS
  the deposed writer the fence exists for).
- zero live ``"embed"``-pool members is a 503 with Retry-After = the
  lease window, the soonest membership can change.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ...observability import trace as _tr
from ...testing.racecheck import shared_state as _shared_state
from ..fabric import _http
from ..fabric.membership import DEFAULT_PREFIX, Member, MembershipView
from ..fabric.router import build_ring, ring_hosts
from ..serving.lifecycle import ServingError
from .metrics import RouterMetrics, track
from .shard import StaleEpochError, epoch_key


def _key_bytes(k: int) -> bytes:
    """A key's ring point. Decimal-string hashing (not raw int bytes)
    so the shard map is reproducible from the DESIGN.md walkthrough by
    hand: sha1(b"embed:12345")."""
    return f"embed:{int(k)}".encode()


@_shared_state("_epoch", "_epoch_read_at")
class EmbeddingRouter:
    """Fan-out/reassembly router over the fleet's ``"embed"`` pool."""

    def __init__(self, view: MembershipView, store=None,
                 metrics: Optional[RouterMetrics] = None,
                 hop_timeout_s: float = 10.0, vnodes: int = 32,
                 epoch_ttl_s: float = 0.25, max_keys: int = 65536,
                 lookup_retries: int = 2, prefix: str = DEFAULT_PREFIX):
        self.view = view
        self.store = store            # epoch reads; None = fence off
        self.metrics = metrics or RouterMetrics()
        self.hop_timeout_s = float(hop_timeout_s)
        self.vnodes = int(vnodes)
        self.epoch_ttl_s = float(epoch_ttl_s)
        self.max_keys = int(max_keys)
        self.lookup_retries = int(lookup_retries)
        self.prefix = prefix
        self._lock = threading.Lock()
        self._epoch = 0
        self._epoch_read_at = float("-inf")
        track(self)

    # -------------------------------------------------------------- epoch --
    def epoch(self, force: bool = False) -> int:
        """The fleet's embed epoch, cached for ``epoch_ttl_s``.
        ``force`` bypasses the cache (the 409-refresh path)."""
        if self.store is None:
            return 0
        now = time.monotonic()
        with self._lock:
            cur = self._epoch
            fresh = now - self._epoch_read_at <= self.epoch_ttl_s
        if fresh and not force:
            return cur
        try:
            val = int(self.store.add(epoch_key(self.prefix), 0))
        except Exception:  # noqa: BLE001 — flapping store path costs
            return cur     # freshness, never availability
        now = time.monotonic()
        with self._lock:
            self._epoch = max(self._epoch, val)
            self._epoch_read_at = now
            return self._epoch

    # ------------------------------------------------------------ fan-out --
    def _members(self) -> Dict[str, Member]:
        members = {m.host_id: m for m in self.view.alive("embed")}
        if not members:
            self.metrics.on_no_shard()
            raise ServingError(
                503, "no live embedding-shard hosts in the fleet",
                retry_after=self.view.lease_s)
        return members

    def _fanout(self, members: Dict[str, Member], path: str,
                make_body, keyed: List[Tuple[int, int]], retries: int,
                parent_ctx=None) -> List[Tuple[str, int, dict, list]]:
        """Route ``keyed`` [(position, key)...] pairs to their ring
        owners, hop concurrently, re-shard transport-faulted hops onto
        the ring minus the dead host(s). Returns a list of
        ``(host_id, status, body_obj, [(pos, key)...])`` per ANSWERED
        hop — a LIST, not a per-host map: a retry round re-routes the
        dead host's keys onto a survivor that may already hold an
        answer from round one, and both answers carry rows. Raises 503
        when keys remain unroutable after the budget.

        ``make_body(pairs)`` builds the hop's JSON object from its
        [(pos, key)...] slice.
        """
        live = dict(members)
        pending = list(keyed)
        answered: List[Tuple[str, int, dict, list]] = []
        last_err: Optional[Exception] = None
        ctx = _tr.current_context() if parent_ctx is None else parent_ctx
        for attempt in range(retries + 1):
            if not pending or not live:
                break
            ring = build_ring(sorted(live), self.vnodes)
            groups: Dict[str, list] = {}
            for pos, k in pending:
                owner = ring_hosts(ring, _key_bytes(k), 1)[0]
                groups.setdefault(owner, []).append((pos, k))
            results: Dict[str, Tuple[Optional[Exception],
                                     Optional[Tuple[int, dict]]]] = {}

            def _hop(host_id: str, pairs: list) -> None:
                m = live[host_id]
                self.metrics.on_hop(host_id)
                try:
                    with _tr.use_context(ctx):
                        with _tr.span("embed.fanout", "embedding",
                                      {"host": host_id, "path": path,
                                       "keys": len(pairs),
                                       "attempt": attempt}):
                            status, obj = _http.request_json(
                                m.endpoint, "POST", path,
                                make_body(pairs),
                                timeout=self.hop_timeout_s)
                    results[host_id] = (None, (status, obj))
                except (_http.HopError, TimeoutError, OSError) as e:
                    results[host_id] = (e, None)

            threads = [threading.Thread(
                target=_hop, args=(hid, pairs),
                name=f"embed-fanout-{hid}", daemon=True)
                for hid, pairs in groups.items()]
            for t in threads:
                t.start()
            # one SHARED deadline for the whole hop wave — K hung hops
            # cost one timeout window, not K stacked ones
            deadline = time.monotonic() + self.hop_timeout_s * 2 + 5.0
            for t in threads:
                t.join(max(0.0, deadline - time.monotonic()))
            pending = []
            for hid, pairs in groups.items():
                err, ans = results.get(hid, (None, None))
                if ans is not None:
                    answered.append((hid, ans[0], ans[1], pairs))
                else:
                    # transport fault (or a hung join): the host is
                    # gone from THIS request's ring — its keys remap
                    # exactly as a real eviction would remap them
                    last_err = err or TimeoutError(
                        f"hop to {hid} did not finish")
                    live.pop(hid, None)
                    pending.extend(pairs)
                    self.metrics.on_retry()
        if pending:
            self.metrics.on_failed()
            raise ServingError(
                503, f"embedding fan-out failed for {len(pending)} "
                     f"key(s) after {retries + 1} attempt(s): "
                     f"{last_err!r}"[:2000],
                retry_after=self.view.lease_s)
        return answered

    # -------------------------------------------------------------- faces --
    def lookup(self, table: str, keys: List[int],
               parent_ctx=None) -> dict:
        """Batched gather: ``{"rows": [[f32]*dim] rank-ordered,
        "missing": [pos...], "epoch": E}``."""
        t0 = time.perf_counter()
        if len(keys) > self.max_keys:
            raise ServingError(
                413, f"lookup batch {len(keys)} keys exceeds the "
                     f"{self.max_keys}-key bound")
        members = self._members()
        keyed = [(pos, int(k)) for pos, k in enumerate(keys)]
        answered = self._fanout(
            members, "/lookup",
            lambda pairs: {"table": str(table),
                           "keys": [k for _, k in pairs]},
            keyed, self.lookup_retries, parent_ctx)
        rows: List[Optional[list]] = [None] * len(keys)
        missing: List[int] = []
        epoch = 0
        for hid, status, obj, pairs in answered:
            if status != 200:
                raise ServingError(
                    status, obj.get("error",
                                    f"shard {hid} answered {status}"),
                    retry_after=obj.get("retry_after"))
            shard_rows = obj.get("rows") or []
            if len(shard_rows) != len(pairs):
                raise ServingError(
                    502, f"shard {hid} returned {len(shard_rows)} rows "
                         f"for {len(pairs)} keys")
            shard_missing = set(obj.get("missing") or [])
            for i, (pos, _k) in enumerate(pairs):
                rows[pos] = shard_rows[i]     # rank-order reassembly
                if i in shard_missing:
                    missing.append(pos)
            epoch = max(epoch, int(obj.get("epoch", 0)))
        self.metrics.on_lookup(len(keys), time.perf_counter() - t0)
        return {"rows": rows, "missing": sorted(missing),
                "epoch": epoch}

    def push(self, table: str, keys: List[int], deltas,
             op: str = "grad", lr: float = 1.0,
             epoch: Optional[int] = None, parent_ctx=None) -> dict:
        """Streaming update fan-out. ``epoch=None`` = auto mode: the
        router stamps its cached fleet epoch and, on a 409 fence, re-
        reads and retries ONCE (the ring changed under the cache — the
        router is not a deposed writer, just a stale reader). An
        EXPLICIT epoch is never upgraded: its 409 surfaces as
        :class:`StaleEpochError` — that caller is the deposed writer
        the fence exists to stop."""
        if len(keys) != len(deltas):
            raise ServingError(
                400, f"keys/deltas length mismatch "
                     f"({len(keys)} vs {len(deltas)})")
        if len(keys) > self.max_keys:
            raise ServingError(
                413, f"push batch {len(keys)} keys exceeds the "
                     f"{self.max_keys}-key bound")
        auto = epoch is None
        stamp = self.epoch() if auto else int(epoch)
        dl = [np.asarray(d, np.float32).tolist() for d in deltas]
        by_key = {}
        keyed = []
        for pos, k in enumerate(keys):
            keyed.append((pos, int(k)))
            by_key[pos] = dl[pos]
        for round_ in range(2):
            members = self._members()
            answered = self._fanout(
                members, "/push",
                lambda pairs: {
                    "table": str(table),
                    "keys": [k for _, k in pairs],
                    "deltas": [by_key[pos] for pos, _ in pairs],
                    "op": str(op), "lr": float(lr), "epoch": stamp},
                keyed, 1, parent_ctx)
            fenced_pairs: List[Tuple[int, int]] = []
            cur = 0
            for hid, st, obj, pairs in answered:
                if st == 409:
                    fenced_pairs.extend(pairs)
                    cur = max(cur, int(obj.get("epoch", 0)))
                elif st != 200:
                    raise ServingError(
                        st, obj.get("error",
                                    f"shard {hid} answered {st}"),
                        retry_after=obj.get("retry_after"))
            if not fenced_pairs:
                self.metrics.on_push()
                return {"applied": len(keys), "epoch": stamp}
            self.metrics.on_fenced()
            if not auto or round_ == 1:
                raise StaleEpochError(stamp, max(cur, stamp + 1))
            # auto mode, first fence: the ring changed under our cached
            # epoch — re-read, re-stamp, and retry ONLY the fenced
            # hops' pairs. The 200-answering shards already applied
            # their slices; re-fanning the full batch would apply
            # every non-fenced "grad" delta twice.
            keyed = fenced_pairs
            stamp = max(self.epoch(force=True), cur)
        raise AssertionError("unreachable")

    # JSON faces for the front door
    def lookup_obj(self, obj: dict, parent_ctx=None) -> dict:
        keys = obj.get("keys")
        if not isinstance(keys, list):
            raise ServingError(400, "lookup needs a 'keys' list")
        return self.lookup(obj.get("table", "default"), keys,
                           parent_ctx)

    def push_obj(self, obj: dict, parent_ctx=None) -> dict:
        keys = obj.get("keys")
        deltas = obj.get("deltas")
        if not isinstance(keys, list) or not isinstance(deltas, list):
            raise ServingError(400, "push needs 'keys' and 'deltas' "
                                    "lists")
        epoch = obj.get("epoch")
        return self.push(obj.get("table", "default"), keys, deltas,
                         op=obj.get("op", "grad"),
                         lr=float(obj.get("lr", 1.0)),
                         epoch=None if epoch is None else int(epoch),
                         parent_ctx=parent_ctx)


__all__ = ["EmbeddingRouter"]
