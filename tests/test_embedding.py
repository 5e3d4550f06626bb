"""Sharded sparse-embedding serving tier (inference/embedding): ring
partitioning, DiskRowStore TTL/eviction under concurrency, shard
lookup/push + epoch fence, fan-out reassembly + re-shard retry, and
the pool-routing regressions the embed tenant imposes on the fabric.

Layer split mirrors the subsystem: ring/table/initializer tests are
pure; shard + router tests run real stdlib HTTP servers in-process (no
jax — the tier is pure control plane + numpy); the slow tier replays
the full subprocess chaos smoke (quorum store, SIGKILL, rejoin fence).

The whole module runs under the lockcheck + racecheck shims: the
DiskRowStore gains concurrent readers in this tier, and its cache/
index fields (plus the shard/router epoch caches and metric stores)
are @shared_state-designated — an access outside the owning lock is a
module failure, not a latent corruption.
"""
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from paddle_tpu.autoscale.world import fleet_world_fn  # noqa: E402
from paddle_tpu.distributed.ps.ssd_table import DiskRowStore  # noqa: E402
from paddle_tpu.inference.embedding import (EmbeddingRouter,  # noqa: E402
                                            EmbeddingShardServer,
                                            RowInitializer, ShardAgent,
                                            StaleEpochError, epoch_key)
from paddle_tpu.inference.fabric import (FabricHTTPServer,  # noqa: E402
                                         FabricRouter, FleetEngine,
                                         HostLease, MembershipView,
                                         build_ring, ring_hosts)
from paddle_tpu.inference.serving.lifecycle import ServingError  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def _lockcheck_module():
    from paddle_tpu.testing import lockcheck, racecheck

    lockcheck.install()
    racecheck.install(ignore_site_parts=(os.sep + "tests" + os.sep,))
    try:
        yield
        lockcheck.assert_clean()
        racecheck.assert_clean()
    finally:
        racecheck.uninstall()
        lockcheck.uninstall()


@pytest.fixture(autouse=True)
def _chaos_reset(fabric_threads_stopped):
    chaos.reset()
    yield
    chaos.reset()


class FakeStore:
    """Dict-backed store with the compare_set + add contracts (the
    registry surface membership and the epoch fence ride)."""

    def __init__(self):
        self.kv = {}
        self._lock = threading.Lock()

    def set(self, k, v):
        with self._lock:
            self.kv[k] = v.encode() if isinstance(v, str) else v

    def get(self, k):
        with self._lock:
            return self.kv.get(k)

    def delete_key(self, k):
        with self._lock:
            self.kv.pop(k, None)

    def compare_set(self, k, expected, desired):
        with self._lock:
            cur = self.kv.get(k, b"")
            if cur == expected.encode():
                self.kv[k] = desired.encode()
                return desired.encode()
            return cur

    def add(self, k, delta):
        with self._lock:
            now = int(self.kv.get(k, b"0")) + int(delta)
            self.kv[k] = str(now).encode()
            return now


# ===================================================================
# consistent-hash ring (shared with the fabric's affinity router)
# ===================================================================
class TestRing:
    def test_owner_stable_and_distinct_successors(self):
        ring = build_ring(["a", "b", "c"], vnodes=16)
        assert ring == sorted(ring)
        owners = [ring_hosts(ring, f"k{i}".encode(), 3)
                  for i in range(50)]
        for o in owners:
            assert len(o) == 3 and len(set(o)) == 3
        # deterministic: same inputs, same owners
        assert owners == [ring_hosts(ring, f"k{i}".encode(), 3)
                          for i in range(50)]

    def test_minimal_remap_on_host_loss(self):
        """Removing one host only remaps keys it owned — every other
        key keeps its owner (the property that makes a shard SIGKILL
        cost one segment, not a full reshuffle)."""
        full = build_ring(["a", "b", "c"], vnodes=32)
        less = build_ring(["a", "c"], vnodes=32)
        moved = kept = 0
        for i in range(300):
            key = f"row{i}".encode()
            before = ring_hosts(full, key, 1)[0]
            after = ring_hosts(less, key, 1)[0]
            if before == "b":
                moved += 1
                assert after in ("a", "c")
            else:
                kept += 1
                assert after == before
        assert moved > 0 and kept > 0

    def test_empty_ring(self):
        assert ring_hosts([], b"k", 1) == []


# ===================================================================
# DiskRowStore: TTL + eviction + pop/update, with concurrent readers
# (the ISSUE satellite: the table gains many HTTP threads in this PR)
# ===================================================================
class TestDiskRowStore:
    def _mk(self, tmp_path, **kw):
        return DiskRowStore(os.path.join(str(tmp_path), "t.db"),
                            dim=4, **kw)

    def test_ttl_expires_idle_rows_only(self, tmp_path):
        clock = [100.0]
        st = self._mk(tmp_path, ttl_s=10.0, now_fn=lambda: clock[0])
        st[1] = np.ones(4, np.float32)
        st[2] = np.full(4, 2.0, np.float32)
        clock[0] = 108.0
        _ = st[2]                      # touch: row 2 stays warm
        clock[0] = 112.0               # row 1 idle 12s > ttl 10s
        assert st.evict_expired() == 1
        assert st.get(1) is None and st.get(2) is not None
        assert st.stats()["expired"] == 1
        st.close()

    def test_ttl_survives_flush_and_reopen_conservatively(self, tmp_path):
        clock = [0.0]
        st = self._mk(tmp_path, ttl_s=5.0, now_fn=lambda: clock[0])
        st[7] = np.ones(4, np.float32)
        st.flush()
        st.close()
        # reopen: no touch stamps yet — nothing expires until observed
        # idle for a full ttl in THIS process
        st2 = self._mk(tmp_path, ttl_s=5.0, now_fn=lambda: clock[0])
        clock[0] = 1000.0
        assert st2.evict_expired() == 0
        assert st2.get(7) is not None
        st2.close()

    def test_lru_eviction_writes_back_dirty(self, tmp_path):
        st = self._mk(tmp_path, cache_rows=2)
        for i in range(5):
            st[i] = np.full(4, float(i), np.float32)
        assert st.memory_rows() <= 2
        assert st.stats()["evictions"] >= 3
        # evicted dirty rows reload from disk intact
        for i in range(5):
            assert st[i][0] == float(i)
        st.close()

    def test_pop_update_and_copy_semantics(self, tmp_path):
        st = self._mk(tmp_path)
        st.update({1: np.ones(4), 2: np.full(4, 2.0)})
        got = st[1]
        got += 99.0                    # mutating the copy
        assert st[1][0] == 1.0         # never leaks into the store
        assert st.pop(1)[0] == 1.0
        assert st.pop(1, default=None) is None
        assert sorted(st.keys()) == [2]
        st.close()

    def test_flush_writes_atomic_meta_sidecar(self, tmp_path):
        st = self._mk(tmp_path)
        st[3] = np.ones(4, np.float32)
        st.flush()
        meta = json.load(open(st.path + ".meta.json"))
        assert meta["rows"] == 1 and meta["dim"] == 4
        seq = meta["flush_seq"]
        st.flush()                     # clean: no seq churn
        assert json.load(open(st.path + ".meta.json"))["flush_seq"] \
            == seq
        st[4] = np.ones(4, np.float32)
        st.flush()
        assert json.load(open(st.path + ".meta.json"))["flush_seq"] \
            > seq
        st.close()

    def test_concurrent_readers_writers_under_racecheck(self, tmp_path):
        """Many threads gather/update/expire the same table — the
        serving tier's actual access pattern. Runs under the module's
        racecheck shim: an access to the @shared_state cache/index
        fields outside the table lock fails the module."""
        clock = [0.0]
        st = self._mk(tmp_path, cache_rows=8, ttl_s=50.0,
                      now_fn=lambda: clock[0])
        stop = threading.Event()
        errs = []

        def reader(seed):
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                k = int(rng.randint(0, 32))
                row = st.get(k)
                if row is not None and row.shape != (4,):
                    errs.append(("shape", k))

        def writer(seed):
            rng = np.random.RandomState(seed)
            while not stop.is_set():
                k = int(rng.randint(0, 32))
                st[k] = np.full(4, float(k), np.float32)

        def reaper():
            while not stop.is_set():
                clock[0] += 1.0
                st.evict_expired()
                st.flush()

        threads = [threading.Thread(target=reader, args=(i,),
                                    name=f"ps-reader-{i}")
                   for i in range(3)]
        threads += [threading.Thread(target=writer, args=(10 + i,),
                                     name=f"ps-writer-{i}")
                    for i in range(2)]
        threads.append(threading.Thread(target=reaper,
                                        name="ps-reaper"))
        for t in threads:
            t.start()
        time.sleep(0.8)
        stop.set()
        for t in threads:
            t.join(10)
        assert not errs
        assert st.memory_rows() <= 8
        st.close()


# ===================================================================
# missing-key initializer
# ===================================================================
class TestRowInitializer:
    def test_deterministic_per_key(self):
        init = RowInitializer("normal:0.05")
        a, b = init(42, 8), init(42, 8)
        assert np.array_equal(a, b)
        assert not np.array_equal(init(42, 8), init(43, 8))

    def test_specs(self):
        assert np.all(RowInitializer("zeros")(1, 4) == 0.0)
        assert np.all(RowInitializer("constant:0.5")(1, 4) == 0.5)
        with pytest.raises(ValueError):
            RowInitializer("bogus:1")

    def test_high_bit_keys_do_not_collide(self):
        """64-bit hashed feature ids differing only above bit 31 must
        initialize to DIFFERENT rows (all key bits feed the seed)."""
        init = RowInitializer("normal:0.05")
        assert not np.array_equal(init(1, 8), init(1 + (1 << 40), 8))
        assert not np.array_equal(init(42, 8), init(42 + (1 << 32), 8))


# ===================================================================
# one shard server over HTTP
# ===================================================================
def _post(base, path, obj):
    req = urllib.request.Request(
        base + path, json.dumps(obj).encode(),
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=15) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class TestShardServer:
    @pytest.fixture()
    def shard(self):
        s = EmbeddingShardServer(tempfile.mkdtemp(),
                                 tables={"user": 4}).start()
        yield s
        s.stop()

    def test_lookup_push_roundtrip_http(self, shard):
        base = f"http://{shard.host}:{shard.port}"
        st, obj = _post(base, "/lookup", {"table": "user",
                                          "keys": [1, 2]})
        assert st == 200 and obj["missing"] == [0, 1]
        st, obj = _post(base, "/push", {
            "table": "user", "keys": [1], "deltas": [[1.0] * 4],
            "op": "assign"})
        assert st == 200 and obj["applied"] == 1
        st, obj = _post(base, "/lookup", {"table": "user", "keys": [1]})
        assert obj["missing"] == [] and obj["rows"][0] == [1.0] * 4

    def test_grad_push_initializes_then_applies(self, shard):
        base = f"http://{shard.host}:{shard.port}"
        init_row = shard.init(5, 4)
        st, _ = _post(base, "/push", {
            "table": "user", "keys": [5], "deltas": [[1.0] * 4],
            "op": "grad", "lr": 0.5})
        assert st == 200
        st, obj = _post(base, "/lookup", {"table": "user", "keys": [5]})
        assert np.allclose(obj["rows"][0], init_row - 0.5)

    def test_errors_are_answers(self, shard):
        base = f"http://{shard.host}:{shard.port}"
        assert _post(base, "/lookup", {"table": "nope",
                                       "keys": [1]})[0] == 404
        assert _post(base, "/push", {"table": "user", "keys": [1],
                                     "deltas": []})[0] == 400
        assert _post(base, "/push", {"table": "user", "keys": [1],
                                     "deltas": [[1.0] * 9]})[0] == 400
        assert _post(base, "/lookup", {"keys": "nan"})[0] == 400

    def test_bad_batch_applies_nothing(self, shard):
        """A 400 push must mean NOTHING applied: a bad-shape delta (or
        bad op) late in the batch must not leave earlier rows mutated,
        or a caller retrying the whole batch double-applies them."""
        base = f"http://{shard.host}:{shard.port}"
        st, _ = _post(base, "/push", {
            "table": "user", "keys": [1, 2],
            "deltas": [[1.0] * 4, [1.0] * 9], "op": "assign"})
        assert st == 400
        st, _ = _post(base, "/push", {
            "table": "user", "keys": [1], "deltas": [[1.0] * 4],
            "op": "bogus"})
        assert st == 400
        st, obj = _post(base, "/lookup", {"table": "user",
                                          "keys": [1, 2]})
        assert st == 200 and obj["missing"] == [0, 1]

    def test_epoch_fence_409_carries_current(self, shard):
        shard.set_epoch_source(lambda: 7, seen=7)
        base = f"http://{shard.host}:{shard.port}"
        st, obj = _post(base, "/push", {
            "table": "user", "keys": [1], "deltas": [[1.0] * 4],
            "op": "assign", "epoch": 3})
        assert st == 409 and obj["epoch"] == 7
        assert shard.metrics.snapshot()["shard_stale_rejected_total"] \
            == 1
        st, _ = _post(base, "/push", {
            "table": "user", "keys": [1], "deltas": [[1.0] * 4],
            "op": "assign", "epoch": 7})
        assert st == 200

    def test_push_refreshes_on_higher_floor(self, shard):
        """A push carrying a HIGHER epoch than the shard's cache forces
        a store re-read — acceptance is judged against an epoch at
        least as fresh as the pusher's."""
        cur = [3]
        shard.set_epoch_source(lambda: cur[0], seen=3)
        cur[0] = 9
        # cache says 3 and is fresh, but the pusher proves 9 exists
        assert shard.current_epoch(floor=9) == 9

    def test_metrics_and_health(self, shard):
        base = f"http://{shard.host}:{shard.port}"
        _post(base, "/lookup", {"table": "user", "keys": [1]})
        text = urllib.request.urlopen(base + "/metrics").read().decode()
        assert "paddle_embed_lookups_total 1" in text
        h = json.loads(urllib.request.urlopen(base + "/healthz").read())
        assert h["role"] == "embed"

    def test_chaos_site_fires(self, shard):
        chaos.add_rule("embed.lookup", "raise_n", 1)
        base = f"http://{shard.host}:{shard.port}"
        st, _ = _post(base, "/lookup", {"table": "user", "keys": [1]})
        assert st == 500
        st, _ = _post(base, "/lookup", {"table": "user", "keys": [1]})
        assert st == 200


# ===================================================================
# fan-out router + epoch fence end to end (in-process fleet)
# ===================================================================
class _World:
    """N shard servers + a REAL MembershipView over a FakeStore."""

    def __init__(self, n=2, dim=4, **shard_kw):
        self.store = FakeStore()
        self.shards, self.agents = [], []
        for i in range(n):
            sh = EmbeddingShardServer(tempfile.mkdtemp(),
                                      tables={"user": dim},
                                      **shard_kw).start()
            ag = ShardAgent(sh, self.store, host_id=f"s{i}",
                            heartbeat_s=3600).start()
            self.shards.append(sh)
            self.agents.append(ag)
        self.view = MembershipView(self.store, lease_s=3600.0)
        self.view.poll_once()

    def close(self):
        for ag, sh in zip(self.agents, self.shards):
            try:
                ag.lease.deregister()
            except Exception:  # noqa: BLE001
                pass
            sh.stop()


class TestEmbeddingRouter:
    def test_rank_order_reassembly_across_shards(self):
        w = _World(3)
        try:
            r = EmbeddingRouter(w.view, store=w.store)
            keys = list(range(60))
            out = r.lookup("user", keys)
            assert len(out["rows"]) == 60
            assert out["missing"] == list(range(60))
            # permuted batch serves the SAME rows at permuted ranks
            perm = keys[::-1]
            out2 = r.lookup("user", perm)
            for i, k in enumerate(perm):
                assert out2["rows"][i] == out["rows"][k]
            # every shard took part of the fan-out
            hops = r.metrics.snapshot()["router_fanout_hops_total"]
            assert hops >= 3
        finally:
            w.close()

    def test_dead_shard_reroutes_zero_lost_lookups(self):
        w = _World(2)
        try:
            r = EmbeddingRouter(w.view, store=w.store)
            w.shards[0].stop()    # SIGKILL stand-in: refuses connects
            out = r.lookup("user", list(range(30)))
            assert all(row is not None for row in out["rows"])
            assert r.metrics.snapshot()["router_retries_total"] >= 1
        finally:
            w.close()

    def test_auto_push_relearns_epoch_on_fence(self):
        w = _World(2, epoch_ttl_s=0.0)   # shards re-read every push
        try:
            r = EmbeddingRouter(w.view, store=w.store,
                                epoch_ttl_s=3600.0)
            assert r.epoch() == 2        # prime the router's cache
            w.store.add(epoch_key(), 1)  # ring change it hasn't seen
            out = r.push("user", [1, 2], [[1.0] * 4, [2.0] * 4],
                         op="assign")
            assert out["epoch"] == 3     # re-learned and re-stamped
            assert r.metrics.snapshot()["router_fenced_total"] >= 1
        finally:
            w.close()

    def test_fence_retry_resends_only_fenced_slice(self):
        """Round 2 of an auto-mode fenced push re-fans-out ONLY the
        409-answering shards' key slices: the 200 shards already
        applied theirs, so a full re-send would apply every non-fenced
        'grad' delta twice."""
        from paddle_tpu.inference.embedding.router import _key_bytes
        w = _World(2)
        try:
            r = EmbeddingRouter(w.view, store=w.store,
                                epoch_ttl_s=3600.0)
            assert r.epoch() == 2          # prime the router's cache
            ring = build_ring(["s0", "s1"], r.vnodes)
            k0 = next(k for k in range(256)
                      if ring_hosts(ring, _key_bytes(k), 1)[0] == "s0")
            k1 = next(k for k in range(256)
                      if ring_hosts(ring, _key_bytes(k), 1)[0] == "s1")
            r.push("user", [k0, k1], [[0.0] * 4, [0.0] * 4],
                   op="assign")            # seed both rows to zeros
            # shard 0 is pinned to an epoch source that never learns
            # epoch 3 — it keeps ACCEPTING the router's stale stamp;
            # shard 1 re-reads the store every push and FENCES it
            w.shards[0].set_epoch_source(lambda: 2, seen=2)
            w.shards[0].epoch_ttl_s = 3600.0
            w.shards[1].epoch_ttl_s = 0.0
            w.store.add(epoch_key(), 1)    # ring change -> epoch 3
            out = r.push("user", [k0, k1], [[1.0] * 4, [1.0] * 4],
                         op="grad", lr=1.0)
            assert out["epoch"] == 3
            assert r.metrics.snapshot()["router_fenced_total"] >= 1
            # each grad applied exactly ONCE: 0 - 1.0*1.0 = -1.0
            assert np.allclose(w.shards[0].tables["user"].get(k0), -1.0)
            assert np.allclose(w.shards[1].tables["user"].get(k1), -1.0)
        finally:
            w.close()

    def test_explicit_stale_epoch_surfaces_409(self):
        w = _World(2, epoch_ttl_s=0.0)
        try:
            r = EmbeddingRouter(w.view, store=w.store)
            with pytest.raises(StaleEpochError) as ei:
                r.push("user", [1], [[1.0] * 4], op="assign", epoch=1)
            assert ei.value.status == 409 and ei.value.epoch >= 2
        finally:
            w.close()

    def test_no_shard_hosts_503_with_lease_retry_after(self):
        store = FakeStore()
        view = MembershipView(store, lease_s=3600.0)
        view.poll_once()
        r = EmbeddingRouter(view, store=store)
        with pytest.raises(ServingError) as ei:
            r.lookup("user", [1])
        assert ei.value.status == 503
        assert ei.value.retry_after == 3600.0

    def test_batch_bound_413(self):
        w = _World(1)
        try:
            r = EmbeddingRouter(w.view, store=w.store, max_keys=4)
            with pytest.raises(ServingError) as ei:
                r.lookup("user", list(range(5)))
            assert ei.value.status == 413
        finally:
            w.close()


# ===================================================================
# pool routing regressions: the embed tenant must not swallow decode
# traffic (ISSUE satellite)
# ===================================================================
class TestPoolRouting:
    def _mixed_view(self):
        store = FakeStore()
        decode = HostLease(store, "dec0", "127.0.0.1:1", capacity=4,
                           pools=["predict", "generate"],
                           heartbeat_s=3600)
        embed = HostLease(store, "emb0", "127.0.0.1:2", capacity=4,
                          pools=["embed"], heartbeat_s=3600)
        decode.register()
        embed.register()
        view = MembershipView(store, lease_s=3600.0)
        view.poll_once()
        return store, view

    def test_pick_generate_never_lands_on_embed_only_host(self):
        _, view = self._mixed_view()
        router = FabricRouter(view)
        for key in (None, b"sess-1", b"sess-2"):
            m = router.pick("generate", affinity_key=key)
            assert m is not None and m.host_id == "dec0"
        assert router.pick("predict").host_id == "dec0"
        # the embed pool sees only the shard host
        assert [m.host_id for m in view.alive("embed")] == ["emb0"]

    def test_fleet_add_replica_skips_embed_only_host(self):
        _, view = self._mixed_view()
        eng = FleetEngine(view)
        picked = []
        eng._admin = lambda hid, *a, **k: (picked.append(hid) or
                                           {"rid": "r0"})
        eng.add_replica(warm=False)
        assert picked == ["dec0"]

    def test_fleet_add_replica_503_when_only_embed_hosts(self):
        store = FakeStore()
        HostLease(store, "emb0", "127.0.0.1:2", pools=["embed"],
                  heartbeat_s=3600).register()
        view = MembershipView(store, lease_s=3600.0)
        view.poll_once()
        eng = FleetEngine(view)
        with pytest.raises(ServingError):
            eng.add_replica(warm=False)

    def test_fleet_world_fn_pools_filter(self):
        store, _ = self._mixed_view()
        count_all = fleet_world_fn(store, lease_s=3600.0)
        count_decode = fleet_world_fn(store, lease_s=3600.0,
                                      pools=("predict", "generate"))
        assert count_all() == 2      # historical behavior unchanged
        assert count_decode() == 1   # embed-only host doesn't inflate
        #                              the training world

    def test_fleet_world_fn_embed_only_registry_is_no_opinion(self):
        store = FakeStore()
        HostLease(store, "emb0", "127.0.0.1:2", pools=["embed"],
                  heartbeat_s=3600).register()
        desired = fleet_world_fn(store, lease_s=3600.0,
                                 pools=("predict", "generate"))
        assert desired() is None     # filtered-empty = UNKNOWN, never
        #                              a shrink-to-minimum signal


# ===================================================================
# front door integration: /embed routes
# ===================================================================
class TestFrontDoorEmbed:
    def test_embed_routes_through_door(self):
        w = _World(2)
        door = None
        try:
            er = EmbeddingRouter(w.view, store=w.store)
            door = FabricHTTPServer(FabricRouter(w.view),
                                    embed_router=er).start()
            base = f"http://{door.host}:{door.port}"
            st, obj = _post(base, "/embed/push", {
                "table": "user", "keys": [3], "deltas": [[5.0] * 4],
                "op": "assign"})
            assert st == 200, obj
            st, obj = _post(base, "/embed/lookup", {"table": "user",
                                                    "keys": [3]})
            assert st == 200 and obj["rows"][0] == [5.0] * 4
            # stale explicit epoch surfaces through the door with the
            # current epoch in the body
            st, obj = _post(base, "/embed/push", {
                "table": "user", "keys": [3], "deltas": [[5.0] * 4],
                "op": "assign", "epoch": 1})
            assert st == 409 and obj["epoch"] >= 2
            text = urllib.request.urlopen(base + "/metrics").read() \
                .decode()
            assert "paddle_embed_router_lookups_total" in text
            fleet = json.loads(
                urllib.request.urlopen(base + "/fleet").read())
            assert fleet["embedding"]["epoch"] >= 2
        finally:
            if door is not None:
                door.stop()
            w.close()

    def test_door_without_embed_tier_404s(self):
        store = FakeStore()
        view = MembershipView(store, lease_s=3600.0)
        view.poll_once()
        door = FabricHTTPServer(FabricRouter(view)).start()
        try:
            st, _ = _post(f"http://{door.host}:{door.port}",
                          "/embed/lookup", {"keys": [1]})
            assert st == 404
        finally:
            door.stop()


# ===================================================================
# slow tier: the full subprocess chaos matrix (quorum store, SIGKILL
# mid-traffic, rejoin epoch fence) — the ISSUE's fleet chaos gate
# ===================================================================
@pytest.mark.slow
def test_embed_smoke_subprocess_chaos():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "embed_smoke.py")],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    bench = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("BENCH ")]
    assert bench, proc.stdout
    obj = json.loads(bench[0][len("BENCH "):])
    assert obj["ok"] is True
    assert obj["shard_kill"]["errors"] == 0
    assert obj["fence"]["stale_status"] == 409
