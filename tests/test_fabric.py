"""Cross-host serving fabric (inference/fabric): lease membership,
front-door routing, fleet actuation, and the chaos-proven host-loss
matrix.

Layer split mirrors the subsystem: the membership/router policy tests
run against dict stores and dummy stdlib HTTP members (no jax — the
front door is pure control plane); the integration tests run ONE real
in-process generative host behind the front door (greedy parity is
exact, so token-identical assertions close the routing loop); the slow
matrix runs REAL subprocess hosts and SIGKILLs one mid-traffic.

The whole module runs under the lockcheck shim (ISSUE 8 discipline):
any acquisition-order cycle across router/membership/engine/server
locks fails the module.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402

from paddle_tpu.distributed.store import (TCPStore, index_add,  # noqa: E402
                                          index_discard, index_members)
from paddle_tpu.inference.fabric import (FabricHTTPServer,  # noqa: E402
                                         FabricRouter, FleetEngine,
                                         HostAgent, HostLease,
                                         MembershipView,
                                         merge_expositions)
from paddle_tpu.inference.fabric import handoff  # noqa: E402
from paddle_tpu.inference.serving.lifecycle import ServingError  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402
from paddle_tpu.testing.multihost import free_port, poll_until  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fabric_host_worker.py")


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
    """Dict-backed store with the compare_set contract."""

    def __init__(self, cas: bool = True):
        self.kv = {}
        self._lock = threading.Lock()
        if not cas:
            self.compare_set = None  # fallback path

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


# ===================================================================
# store index helpers
# ===================================================================
class TestIndexHelpers:
    def test_add_discard_members(self):
        st = FakeStore()
        assert index_add(st, "idx", "b") == ["b"]
        assert index_add(st, "idx", "a") == ["a", "b"]
        assert index_add(st, "idx", "a") == ["a", "b"]  # idempotent
        assert index_members(st, "idx") == ["a", "b"]
        assert index_discard(st, "idx", "b") == ["a"]
        assert index_discard(st, "idx", "zz") == ["a"]

    def test_fallback_without_cas(self):
        st = FakeStore(cas=False)
        index_add(st, "idx", "x")
        assert index_members(st, "idx") == ["x"]

    def test_cas_race_converges(self):
        """Two writers racing the index never lose an entry (the
        elastic manager's old read-modify-write bug)."""
        st = FakeStore()
        errs = []

        def add_many(tag):
            try:
                for i in range(20):
                    index_add(st, "idx", f"{tag}{i}")
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=add_many, args=(t,),
                               name=f"idx-{t}") for t in ("a", "b")]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert len(index_members(st, "idx")) == 40


# ===================================================================
# membership state machine (clock-injected, no threads)
# ===================================================================
def _mk_lease(store, hid, ep="127.0.0.1:1", **kw):
    lease = HostLease(store, hid, ep, pools=["generate"],
                      heartbeat_s=3600, **kw)  # no thread races: beats
    return lease                               # are driven manually


class TestMembershipLadder:
    def test_lease_ladder_suspect_probe_evict(self):
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        probes = []
        view = MembershipView(st, lease_s=1.0, drain_s=0.5,
                              max_probes=2,
                              probe_fn=lambda m: probes.append(m.host_id)
                              or False)
        t0 = time.monotonic()
        view.poll_once(t0)
        assert [m.host_id for m in view.alive()] == ["h1"]
        # renewed lease keeps it alive past the window
        lease._beat_once()
        view.poll_once(t0 + 0.9)
        view.poll_once(t0 + 1.5)   # 0.6s after last observed renewal
        assert view.get("h1").state == "alive"
        # silence -> suspect at lease_s (routing stops immediately)
        view.poll_once(t0 + 2.8)
        assert view.get("h1").state == "suspect"
        assert view.alive() == []
        assert view.counters["suspects"] == 1
        # probe ladder burns its bounded strikes, then the drain
        # window expires -> evicted
        view.poll_once(t0 + 2.9)
        assert probes == ["h1", "h1"]   # max_probes, then no more
        view.poll_once(t0 + 3.2)
        assert probes == ["h1", "h1"]
        view.poll_once(t0 + 4.1)        # > lease + drain
        assert view.get("h1") is None
        assert view.counters["evictions"] == 1

    def test_probe_readmits_store_partitioned_host(self):
        """A host whose STORE path is partitioned but whose data path
        still answers /healthz is re-admitted, not evicted — the
        cross-host revive-before-replace rung."""
        st = FakeStore()
        _mk_lease(st, "h1").register()
        view = MembershipView(st, lease_s=1.0, drain_s=5.0,
                              probe_fn=lambda m: True)
        t0 = time.monotonic()
        view.poll_once(t0)
        view.poll_once(t0 + 1.5)
        # suspect fired, but the probe (run in the same poll) won
        assert view.counters["suspects"] == 1
        assert view.get("h1").state == "alive"
        assert [m.host_id for m in view.alive()] == ["h1"]
        # the readmit extended the lease on the INJECTED clock (not the
        # wall thread clock): 0.9s later it is still inside the window
        # and never re-suspects
        view.poll_once(t0 + 2.4)
        assert view.get("h1").state == "alive"
        assert view.counters["suspects"] == 1

    def test_rejoin_needs_bumped_generation(self):
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        view = MembershipView(st, lease_s=0.5, drain_s=0.2,
                              probe_fn=lambda m: False, max_probes=0)
        t0 = time.monotonic()
        view.poll_once(t0)
        view.poll_once(t0 + 0.8)      # suspect
        view.poll_once(t0 + 1.0)      # evicted
        assert view.get("h1") is None
        # the corpse record (same generation) still sits in the store:
        # it must NOT resurrect the member
        view.poll_once(t0 + 1.2)
        assert view.get("h1") is None
        # a real re-registration bumps the generation -> rejoin
        gen = lease.register()
        assert gen == 1
        view.poll_once(t0 + 1.4)
        m = view.get("h1")
        assert m is not None and m.generation == 1 and m.state == "alive"
        assert view.counters["rejoins"] == 1

    def test_transient_store_blip_readmits_on_seq_advance(self):
        """A flapping store read that momentarily hides the registry
        records a wrongful 'leave' — the host's advancing heartbeat
        seq (frozen on a real corpse) must readmit it."""
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        view = MembershipView(st, lease_s=5.0)
        view.poll_once()
        assert view.alive()
        idx = st.kv.pop("fabric/hosts")   # one bad index read
        view.poll_once()
        assert view.get("h1") is None
        assert view.counters["leaves"] == 1
        st.kv["fabric/hosts"] = idx
        view.poll_once()   # record back but seq frozen: still blocked
        assert view.get("h1") is None
        lease._beat_once()                # proof of life
        view.poll_once()
        m = view.get("h1")
        assert m is not None and m.state == "alive"
        assert view.counters["rejoins"] == 1

    def test_graceful_leave_skips_ladder(self):
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        view = MembershipView(st, lease_s=1.0, drain_s=1.0)
        t0 = time.monotonic()
        view.poll_once(t0)
        lease.deregister()
        view.poll_once(t0 + 0.1)
        assert view.get("h1") is None
        assert view.counters["leaves"] == 1
        assert view.counters["evictions"] == 0

    def test_draining_host_not_routed(self):
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        view = MembershipView(st, lease_s=5.0)
        view.poll_once()
        assert len(view.alive()) == 1
        lease.mark_draining(True)
        view.poll_once()
        assert view.alive() == []
        assert view.get("h1").state == "alive"  # alive, just draining

    def test_heartbeat_chaos_survives(self):
        st = FakeStore()
        lease = _mk_lease(st, "h1")
        lease.register()
        chaos.add_rule("fabric.heartbeat", "raise_n", 2)
        for _ in range(2):
            with pytest.raises(chaos.ChaosError):
                lease._beat_once()
        assert lease.counters["heartbeat_errors"] == 0  # loop-level
        lease._beat_once()  # healed
        view = MembershipView(st, lease_s=1.0)
        view.poll_once()
        assert [m.host_id for m in view.alive()] == ["h1"]


# ===================================================================
# router policy over dummy HTTP members
# ===================================================================
class _DummyMember:
    """Stdlib HTTP member: /healthz, /predict (echoes which host
    served), /generate with proper chunked ndjson."""

    def __init__(self, name, tokens=(1, 2, 3)):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)

        member = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def do_GET(self):
                body = json.dumps({"status": "ok"}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(n) or b"{}")
                member.hits += 1
                if self.path == "/generate" and payload.get("stream"):
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/x-ndjson")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()

                    def chunk(obj):
                        data = (json.dumps(obj) + "\n").encode()
                        self.wfile.write(f"{len(data):X}\r\n".encode()
                                         + data + b"\r\n")

                    # honor the replay-resume contract the real engine
                    # implements: resume_from=n suppresses the first n
                    # tokens (the deterministic key-chain makes the
                    # suffix identical, so slicing the canned list IS
                    # the faithful mini-engine)
                    toks = member.tokens[
                        int(payload.get("resume_from") or 0):]
                    for i, t in enumerate(toks):
                        if member.token_delay:
                            time.sleep(member.token_delay)
                        if member.die_after is not None and \
                                i >= member.die_after:
                            self.wfile.flush()
                            # close() alone defers the FIN while
                            # rfile/wfile still hold the socket's io
                            # refcount — shutdown() sends it NOW, like
                            # a SIGKILL'd host's kernel does
                            import socket as _socket
                            try:
                                self.connection.shutdown(
                                    _socket.SHUT_RDWR)
                            except OSError:
                                pass
                            self.close_connection = True
                            return
                        chunk({"token": int(t)})
                    chunk({"done": True, "who": member.name})
                    self.wfile.write(b"0\r\n\r\n")
                    return
                body = json.dumps({"who": member.name,
                                   "path": self.path}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.name = name
        self.tokens = list(tokens)
        self.die_after = None
        self.token_delay = 0.0
        self.hits = 0
        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.srv.daemon_threads = True
        threading.Thread(target=self.srv.serve_forever,
                         name=f"dummy-member-{name}",
                         daemon=True).start()

    @property
    def endpoint(self):
        return f"127.0.0.1:{self.srv.server_address[1]}"

    def kill(self):
        self.srv.shutdown()
        self.srv.server_close()


def _fleet_of(st, members, lease_s=5.0, **view_kw):
    leases = []
    for i, mem in enumerate(members):
        lease = HostLease(st, mem.name, mem.endpoint,
                          pools=["predict", "generate"],
                          heartbeat_s=3600)
        lease.register()
        leases.append(lease)
    view = MembershipView(st, lease_s=lease_s, **view_kw)
    view.poll_once()
    return view, leases


class TestRouterPolicy:
    def test_least_loaded_uses_reported_depth(self):
        st = FakeStore()
        a, b = _DummyMember("a"), _DummyMember("b")
        view, (la, lb) = _fleet_of(st, [a, b])
        router = FabricRouter(view)
        # host a reports a deep queue -> picks must prefer b
        la.load_fn = lambda: {"queue_depth": 50}
        la._beat_once()
        lb.load_fn = lambda: {"queue_depth": 0}
        lb._beat_once()
        view.poll_once()
        for _ in range(4):
            st_, _, data = router.forward("/predict", b"{}",
                                          "application/json")
            assert st_ == 200
            assert json.loads(data)["who"] == "b"
        a.kill(), b.kill()

    def test_affinity_is_stable_and_remaps_on_loss(self):
        st = FakeStore()
        members = [_DummyMember(n) for n in ("a", "b", "c")]
        view, _ = _fleet_of(st, members)
        router = FabricRouter(view)
        key = b"session-42"
        first = router.pick("generate", affinity_key=key).host_id
        assert all(router.pick("generate",
                               affinity_key=key).host_id == first
                   for _ in range(5))
        # losing the affinity host remaps deterministically to another
        others = router.pick("generate", exclude=[first],
                             affinity_key=key).host_id
        assert others != first
        for m in members:
            m.kill()

    def test_kv_aware_pick_weighs_slot_occupancy(self):
        st = FakeStore()
        a, b = _DummyMember("a"), _DummyMember("b")
        view, (la, lb) = _fleet_of(st, [a, b])
        router = FabricRouter(view)
        # equal queue depth; a's 64-class KV pool is full, b's empty
        # -> the KV-aware score must prefer b for a generate pick
        la.load_fn = lambda: {"queue_depth": 0,
                              "kv": {"64": {"free": 0, "slots": 4}}}
        la._beat_once()
        lb.load_fn = lambda: {"queue_depth": 0,
                              "kv": {"64": {"free": 4, "slots": 4}}}
        lb._beat_once()
        view.poll_once()
        req = {"input_ids": [1, 2, 3], "max_new_tokens": 8}
        for _ in range(4):
            assert router.pick("generate", gen_req=req).host_id == "b"
        # a host without the digest (pre-upgrade, mid-rollout) falls
        # back to the queue score instead of being starved: idle a
        # beats a b drowning in queued long decodes
        la.load_fn = lambda: {"queue_depth": 0}
        la._beat_once()
        lb.load_fn = lambda: {"queue_depth": 9,
                              "kv": {"64": {"free": 4, "slots": 4}}}
        lb._beat_once()
        view.poll_once()
        assert router.pick("generate", gen_req=req).host_id == "a"
        a.kill(), b.kill()

    def test_streamed_affinity_prefers_residency_over_ring(self):
        st = FakeStore()
        a, b = _DummyMember("a"), _DummyMember("b")
        view, (la, lb) = _fleet_of(st, [a, b])
        router = FabricRouter(view)
        prompt = list(range(1, 14))          # 13 ids: boundary 8 fits
        dig = f"8:{handoff.prefix_hash(prompt, 8)[:8]}"
        key = b"session-7"
        # ring baseline for this key with NO digest anywhere
        ring = router.pick("generate", affinity_key=key).host_id
        other = "b" if ring == "a" else "a"
        # the NON-ring host advertises residency -> it wins the pick
        (lb if other == "b" else la).load_fn = \
            lambda: {"queue_depth": 0, "prefix": [dig]}
        (lb if other == "b" else la)._beat_once()
        view.poll_once()
        req = {"input_ids": prompt, "max_new_tokens": 4}
        for _ in range(4):
            got = router.pick("generate", affinity_key=key,
                              gen_req=req).host_id
            assert got == other, (got, ring)
        # a prompt no digest matches falls back to the same ring host
        miss = {"input_ids": [9, 9, 9], "max_new_tokens": 4}
        assert router.pick("generate", affinity_key=key,
                           gen_req=miss).host_id == ring
        # both advertising the same boundary breaks on LOWEST host id
        la.load_fn = lambda: {"queue_depth": 0, "prefix": [dig]}
        la._beat_once()
        lb.load_fn = lambda: {"queue_depth": 0, "prefix": [dig]}
        lb._beat_once()
        view.poll_once()
        assert router.pick("generate", affinity_key=key,
                           gen_req=req).host_id == "a"
        a.kill(), b.kill()

    def test_retry_on_dead_host_then_passthrough(self):
        st = FakeStore()
        a, b = _DummyMember("a"), _DummyMember("b")
        view, _ = _fleet_of(st, [a, b])
        router = FabricRouter(view, hop_timeout_s=2.0)
        a.kill()  # transport faults on a -> retried on b
        winners = set()
        for _ in range(4):
            st_, _, data = router.forward("/predict", b"{}",
                                          "application/json")
            assert st_ == 200
            winners.add(json.loads(data)["who"])
        assert winners == {"b"}
        assert router.metrics.retries_total >= 1
        b.kill()

    def test_forward_chaos_rule_burns_retry(self):
        st = FakeStore()
        a, b = _DummyMember("a"), _DummyMember("b")
        view, _ = _fleet_of(st, [a, b])
        router = FabricRouter(view)
        chaos.add_rule("fabric.forward", "raise_n", 1)
        st_, _, data = router.forward("/predict", b"{}",
                                      "application/json")
        assert st_ == 200
        assert router.metrics.retries_total == 1
        a.kill(), b.kill()

    def test_no_hosts_is_503_with_lease_retry_after(self):
        st = FakeStore()
        view = MembershipView(st, lease_s=2.5)
        router = FabricRouter(view)
        with pytest.raises(ServingError) as ei:
            router.forward("/predict", b"{}", "application/json")
        assert ei.value.status == 503
        assert ei.value.retry_after == 2.5
        assert router.metrics.no_host_total == 1

    def test_stream_break_after_tokens_no_survivor_is_terminal(self):
        """Host loss mid-stream with NO survivor: strict prefix plus
        one terminal 503 line — never a duplicate token (the resume
        path needs somewhere to resume; an empty fleet has none)."""
        st = FakeStore()
        a = _DummyMember("a", tokens=(5, 6, 7, 8))
        a.die_after = 2
        view, _ = _fleet_of(st, [a])
        router = FabricRouter(view, stream_idle_timeout_s=5.0)
        lines = []
        router.stream_generate(b'{"stream": true}', b"k", lines.append)
        toks = [json.loads(ln)["token"] for ln in lines
                if ln.startswith(b'{"token"')]
        assert toks == [5, 6]          # prefix only, no duplicates
        last = json.loads(lines[-1])
        assert last.get("status") == 503 and "error" in last
        assert router.metrics.streams_broken_total == 1
        assert router.metrics.streams_resumed_total == 1
        a.kill()

    def test_stream_break_after_tokens_resumes_on_survivor(self):
        """Host loss mid-stream WITH a survivor: the router replays
        the request with resume_from=<relayed> and the client's wire
        is the uninterrupted token sequence — zero duplicates, zero
        gaps, terminal 'done' (the disaggregated-serving resume)."""
        st = FakeStore()
        a = _DummyMember("a", tokens=(5, 6, 7, 8))
        b = _DummyMember("b", tokens=(5, 6, 7, 8))
        a.die_after = 2
        view, _ = _fleet_of(st, [a, b])
        router = FabricRouter(view, stream_idle_timeout_s=5.0)
        got = []
        for key in (b"k0", b"k1", b"k2", b"k3"):
            lines = []
            router.stream_generate(b'{"stream": true}', key,
                                   lines.append)
            toks = [json.loads(ln)["token"] for ln in lines
                    if ln.startswith(b'{"token"')]
            assert toks == [5, 6, 7, 8], toks
            assert json.loads(lines[-1]).get("done") is True
            got.append(json.loads(lines[-1])["who"])
        # whichever host affinity chose first, every stream completed;
        # the ones that started on the dying host resumed on b
        assert "b" in got
        assert router.metrics.streams_resumed_total >= 1
        assert router.metrics.streams_broken_total == 0
        a.kill(), b.kill()

    def test_stream_break_before_tokens_retries(self):
        st = FakeStore()
        a = _DummyMember("a", tokens=(5, 6))
        b = _DummyMember("b", tokens=(5, 6))
        a.die_after = 0   # dies before the first token
        b.die_after = None
        view, _ = _fleet_of(st, [a, b])
        router = FabricRouter(view, stream_idle_timeout_s=5.0)
        got = {"a": 0, "b": 0}
        for _ in range(4):   # whatever affinity picks, a is broken
            lines = []
            router.stream_generate(b'{"stream": true}', b"k2",
                                   lines.append)
            done = json.loads(lines[-1])
            assert done.get("done") is True
            got[done["who"]] += 1
            toks = [json.loads(ln)["token"] for ln in lines
                    if ln.startswith(b'{"token"')]
            assert toks == [5, 6]
        assert got["b"] == 4 and got["a"] == 0
        a.kill(), b.kill()

    def test_merge_expositions_injects_host_label(self):
        merged = merge_expositions({
            "h1": "# HELP m x\n# TYPE m counter\nm 1\n"
                  'm2{k="v"} 7\n',
            "h2": "# HELP m x\n# TYPE m counter\nm 5\n",
        })
        assert merged.count("# HELP m x") == 1
        assert 'm{host="h1"} 1' in merged
        assert 'm{host="h2"} 5' in merged
        assert 'm2{host="h1",k="v"} 7' in merged


# ===================================================================
# N front doors: per-observer convergence + client-side door failover
# ===================================================================
class TestMultiFrontDoor:
    def _two_doors(self, st, members, lease_s=5.0):
        view_a, leases = _fleet_of(st, members, lease_s=lease_s)
        view_b = MembershipView(st, lease_s=lease_s)
        view_b.poll_once()
        router_a, router_b = FabricRouter(view_a), FabricRouter(view_b)
        fd_a = FabricHTTPServer(router_a).start()
        fd_b = FabricHTTPServer(router_b).start()
        return (view_a, view_b, router_a, router_b, fd_a, fd_b, leases)

    @staticmethod
    def _table(view):
        """The convergence-relevant projection of a member table (ages
        are observer-local by design and excluded)."""
        return [(r["host"], r["state"], r["generation"], r["draining"])
                for r in view.rows()]

    def test_member_tables_and_rings_converge_across_doors(self):
        """Doors share only the registry, yet every observer derives
        the SAME member table and the SAME affinity ring — the
        no-coordination contract N front doors rest on."""
        st = FakeStore()
        members = [_DummyMember(n) for n in ("a", "b", "c")]
        (view_a, view_b, router_a, router_b,
         fd_a, fd_b, leases) = self._two_doors(st, members, lease_s=0.8)
        try:
            assert self._table(view_a) == self._table(view_b)
            keys = [f"session-{i}".encode() for i in range(24)]
            picks_a = [router_a.pick("generate", affinity_key=k).host_id
                       for k in keys]
            picks_b = [router_b.pick("generate", affinity_key=k).host_id
                       for k in keys]
            assert picks_a == picks_b
            assert len(set(picks_a)) > 1  # the ring actually spreads
            # a member goes silent: BOTH doors walk the same ladder on
            # their own clocks and converge to the same table
            t0 = time.monotonic()
            leases[0].deregister()   # graceful leave of "a"
            for v in (view_a, view_b):
                v.poll_once(t0 + 0.1)
            assert self._table(view_a) == self._table(view_b)
            assert [r[0] for r in self._table(view_a)] == ["b", "c"]
            # the shrunk ring still maps identically from either door
            picks_a2 = [router_a.pick("generate", affinity_key=k).host_id
                        for k in keys]
            picks_b2 = [router_b.pick("generate", affinity_key=k).host_id
                        for k in keys]
            assert picks_a2 == picks_b2
            # minimal remap: only sessions that lived on "a" moved
            moved = [i for i, (p, q) in enumerate(zip(picks_a, picks_a2))
                     if p != q]
            assert all(picks_a[i] == "a" for i in moved)
        finally:
            fd_a.stop()
            fd_b.stop()
            for m in members:
                m.kill()

    def test_client_rotates_to_surviving_door(self):
        """FleetClient: a dead door costs a rotate, not a request —
        and a door's HTTP answer is returned as-is (no retry storm)."""
        from paddle_tpu.inference.fabric import FleetClient

        st = FakeStore()
        members = [_DummyMember(n) for n in ("a", "b")]
        (view_a, view_b, _ra, _rb,
         fd_a, fd_b, _leases) = self._two_doors(st, members)
        try:
            client = FleetClient([f"127.0.0.1:{fd_a.port}",
                                  f"http://127.0.0.1:{fd_b.port}"],
                                 timeout_s=10.0)
            for _ in range(4):
                status, body = client.predict({"x": 1})
                assert status == 200 and body["who"] in ("a", "b")
            fd_a.stop()   # one of N doors dies
            for _ in range(4):
                status, body = client.predict({"x": 1})
                assert status == 200
            assert client.counters_snapshot()["door_retries"] >= 1
            status, health = client.healthz()
            assert status == 200 and health["hosts_alive"] == 2
        finally:
            fd_b.stop()
            for m in members:
                m.kill()

    def test_stream_via_client_completes_and_member_loss_resumes(self):
        """The client stream contract over doors: a healthy stream
        relays token-identically; a MEMBER dying mid-stream is
        absorbed by the door's replay-resume — the client's wire is
        the uninterrupted sequence, zero duplicates, terminal done."""
        from paddle_tpu.inference.fabric import FleetClient

        st = FakeStore()
        members = [_DummyMember(n, tokens=(5, 6, 7, 8))
                   for n in ("a", "b")]
        (view_a, view_b, _ra, _rb,
         fd_a, fd_b, _leases) = self._two_doors(st, members)
        try:
            client = FleetClient([f"127.0.0.1:{fd_a.port}",
                                  f"127.0.0.1:{fd_b.port}"],
                                 timeout_s=10.0)
            recs = list(client.stream_generate({"session": "s1"}))
            assert [r["token"] for r in recs if "token" in r] == \
                [5, 6, 7, 8]
            assert recs[-1].get("done") is True
            for m in members:
                m.die_after = 2
            recs = list(client.stream_generate({"session": "s1"}))
            toks = [r["token"] for r in recs if "token" in r]
            # whichever member the affinity chose died after two
            # tokens; the door resumed on the other with resume_from=2
            # (whose remaining suffix fits under ITS death threshold)
            assert toks == [5, 6, 7, 8], toks
            assert recs[-1].get("done") is True
        finally:
            fd_a.stop()
            fd_b.stop()
            for m in members:
                m.kill()

    def test_sigkill_door_mid_stream_strict_prefix(self):
        """A REAL front-door process (python -m paddle_tpu.inference.
        fabric) is SIGKILLed mid-relay: the pinned stream ends as a
        strict prefix plus ONE terminal 503 from the client (never a
        duplicate token), non-streamed traffic rotates to the
        surviving door, and a fresh stream completes there."""
        from paddle_tpu.distributed.store import TCPStore as _TS
        from paddle_tpu.inference.fabric import FleetClient

        store = _TS(is_master=True)
        member = _DummyMember("m0", tokens=tuple(range(10, 20)))
        member.token_delay = 0.15
        lease = HostLease(store, "m0", member.endpoint,
                          pools=["predict", "generate"],
                          heartbeat_s=0.25)
        doors, procs = [], []
        try:
            lease.register()
            for _ in range(2):
                p = subprocess.Popen(
                    [sys.executable, "-m",
                     "paddle_tpu.inference.fabric",
                     "--store", f"127.0.0.1:{store.port}",
                     "--lease_s", "2.0"],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, cwd=REPO, env=cpu_subprocess_env())
                procs.append(p)
                line = p.stdout.readline().strip()
                assert line.startswith("DOOR="), line
                doors.append(line.split("=", 1)[1])
            client = FleetClient(doors, timeout_s=30.0)
            # EVERY door must have admitted the member (the rotating
            # healthz would be satisfied by one door alone)
            for d in doors:
                one = FleetClient([d], timeout_s=30.0)
                poll_until(lambda: one.healthz()[1].get(
                    "hosts_alive") == 1, timeout=30,
                    desc=f"door {d} sees m0")

            # pin a stream through door[0] only, then SIGKILL it
            solo = FleetClient([doors[0]], timeout_s=30.0)
            toks, terminal = [], []
            for rec in solo.stream_generate({"session": "pin"}):
                if "token" in rec:
                    toks.append(rec["token"])
                    if len(toks) == 2:
                        procs[0].send_signal(signal.SIGKILL)
                elif "error" in rec:
                    terminal.append(rec)
            assert toks[:2] == [10, 11]
            assert toks == list(range(10, 10 + len(toks)))  # prefix
            assert len(toks) < 10
            assert terminal and terminal[-1]["status"] == 503
            assert solo.counters_snapshot()["streams_broken"] == 1

            # the rotating client survives: non-streamed keeps
            # answering and a fresh stream completes on the survivor
            for _ in range(4):
                status, body = client.predict({"x": 1})
                assert status == 200 and body["who"] == "m0"
            recs = list(client.stream_generate({"session": "pin"}))
            assert [r["token"] for r in recs if "token" in r] == \
                list(range(10, 20))
            assert recs[-1].get("done") is True
        finally:
            lease.deregister()
            _stop_procs(procs)
            member.kill()
            store.stop()


# ===================================================================
# fleet-driven desired_world (satellite)
# ===================================================================
class TestFleetWorldFn:
    def test_world_tracks_registry(self):
        from paddle_tpu.autoscale import fleet_world_fn

        st = FakeStore()
        fn = fleet_world_fn(st, procs_per_host=2, np_range=(1, 8))
        assert fn() is None               # empty registry: no opinion
        l1 = _mk_lease(st, "h1")
        l1.register()
        _mk_lease(st, "h2").register()
        assert fn() == 4
        l1.deregister()
        assert fn() == 2

    def test_store_outage_holds_last_known_world(self):
        """ISSUE 14 satellite: a transient store-failover window —
        erroring or empty registry reads — is UNKNOWN, not a zero-member
        fleet; the desired world holds at the last known value instead
        of shrinking (which would have preempted the whole training
        world off a registry blip)."""
        from paddle_tpu.autoscale import fleet_world_fn

        class OutageStore(FakeStore):
            down = False

            def get(self, k):
                if self.down:
                    raise ConnectionError("store outage window")
                return super().get(k)

        st = OutageStore()
        leases = [_mk_lease(st, f"h{i}") for i in range(3)]
        for lease in leases:
            lease.register()
        fn = fleet_world_fn(st, procs_per_host=1, np_range=(1, 8),
                            lease_s=0.2, drain_s=0.1)
        assert fn() == 3
        st.down = True
        # hold through the whole outage — even once the view's ladder
        # has run past lease+drain and evicted every silent member
        deadline = time.monotonic() + 0.6
        while time.monotonic() < deadline:
            assert fn() == 3, "store outage shrank the desired world"
            time.sleep(0.05)
        st.down = False
        # heartbeats resume (seq advances past the evicted snapshot):
        # the first healthy polls readmit and the world tracks again
        for lease in leases:
            lease._beat_once()
        deadline = time.monotonic() + 5.0
        while fn() != 3 and time.monotonic() < deadline:
            for lease in leases:
                lease._beat_once()
            time.sleep(0.05)
        assert fn() == 3
        leases[0].deregister()
        assert fn() == 2  # a real leave still shrinks

    def test_world_autoscaler_arms_resize_from_fleet(self, tmp_path):
        from paddle_tpu.autoscale import WorldAutoscaler, fleet_world_fn

        class FakeSupervisor:
            def __init__(self):
                self.requests = []

            def request_restart(self, reason):
                self.requests.append(reason)

            def cancel_restart(self, reason):
                return False

        st = FakeStore()
        for h in ("h1", "h2", "h3"):
            _mk_lease(st, h).register()
        sup = FakeSupervisor()
        resize = str(tmp_path / "resize.json")
        wa = WorldAutoscaler(sup, world=1,
                             desired_fn=fleet_world_fn(st),
                             resize_file=resize, np_range=(1, 8))
        assert wa.maybe_resize() is True
        assert sup.requests and "1 -> 3" in sup.requests[0]
        with open(resize) as f:
            assert json.load(f)["nproc_per_node"] == 3


# ===================================================================
# real-engine integration: parity + aggregation + fleet actuation
# ===================================================================
@pytest.fixture(scope="module")
def fabric_stack():
    """One real generative host behind a real front door, plus the
    fleet adapter — shared across the integration tests below."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import (GenerativeEngine,
                                              ServingHTTPServer)
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = GenerativeEngine(model, slots=4, max_context=64,
                              max_new_tokens_cap=16)
    server = ServingHTTPServer(None, generator=engine,
                               admin=True).start()
    store = FakeStore()
    agent = HostAgent(server, store, host_id="h1",
                      heartbeat_s=0.15).start()
    view = MembershipView(store, lease_s=2.0, drain_s=1.0).start()
    router = FabricRouter(view)
    fd = FabricHTTPServer(router).start()
    fleet = FleetEngine(view, router)
    poll_until(lambda: view.alive(), timeout=10, desc="host registered")
    yield {"engine": engine, "server": server, "agent": agent,
           "view": view, "router": router, "fd": fd, "fleet": fleet,
           "url": f"http://127.0.0.1:{fd.port}"}
    agent.stop()
    fd.stop()
    server.stop()


def _post_json(url, obj, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class TestFrontDoorIntegration:
    def test_greedy_parity_through_front_door(self, fabric_stack):
        """Acceptance: token-identical greedy output through the front
        door vs direct single-host serving, both JSON and streamed."""
        eng, url = fabric_stack["engine"], fabric_stack["url"]
        prompt = [3, 7, 11, 2]
        direct = eng.generate(prompt, max_new_tokens=8,
                              timeout=120)["tokens"]
        via = _post_json(url + "/generate",
                         {"input_ids": prompt, "max_new_tokens": 8})
        assert via["tokens"] == direct
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"input_ids": prompt, "max_new_tokens": 8,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        toks, done = [], None
        with urllib.request.urlopen(req, timeout=120) as r:
            for line in r:
                obj = json.loads(line)
                if "token" in obj:
                    toks.append(obj["token"])
                else:
                    done = obj
        assert toks == direct
        assert done["done"] is True and done["n_tokens"] == len(direct)

    def test_aggregate_healthz_and_merged_metrics(self, fabric_stack):
        url = fabric_stack["url"]
        with urllib.request.urlopen(url + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert health["hosts"][0]["host"] == "h1"
        assert health["hosts"][0]["state"] == "alive"
        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        # fabric families + the member's own exposition under host=
        assert "paddle_fabric_requests_total" in text
        assert 'paddle_fabric_member_state{host="h1"' in text
        assert 'paddle_generate_requests_total{host="h1"}' in text
        with urllib.request.urlopen(url + "/fleet", timeout=30) as r:
            fleet = json.loads(r.read())
        assert fleet["hosts"][0]["queue_depth"] >= 0

    def test_fleet_actuation_add_revive_remove(self, fabric_stack):
        """The engine contract over /admin: add warms-before-admission
        on the remote host, revive bumps the remote generation, remove
        drains — all through namespaced fleet ids."""
        fleet, eng = fabric_stack["fleet"], fabric_stack["engine"]

        def active_rows():
            return [r for r in fleet.replica_states()
                    if r["state"] == "active"]

        rows = active_rows()
        assert [r["rid"] for r in rows] == ["h1|generate|0"]
        report = fleet.add_replica()
        assert report["rid"].startswith("h1|generate|")
        assert report["persistent_misses"] == 0 or \
            report["warmed_executables"] >= 0
        assert len(active_rows()) == 2
        assert len(eng._active()) == 2
        rev = fleet.revive_replica(rows[0]["rid"])
        assert rev["generation"] == 1
        rem = fleet.remove_replica(drain=True)
        assert rem["drained"] is True
        assert len(active_rows()) == 1
        with pytest.raises(ValueError):
            fleet.remove_replica(drain=True)  # last-active refusal
        with pytest.raises(ValueError):
            fleet.revive_replica("h1|generate|999")

    def test_admin_bad_fields_are_400_not_409(self, fabric_stack):
        """Request-validation failures must NOT ride the 409 channel
        FleetEngine re-raises as the engine's ValueError surface (the
        watchdog would read a typo'd field as a replica-state
        conflict)."""
        import urllib.error

        from paddle_tpu.inference.fabric import _http

        srv = fabric_stack["server"]
        ep = f"{srv.host}:{srv.port}"
        status, _ = _http.request_json(
            ep, "POST", "/admin/scale",
            {"front": "generate", "action": "remove", "timeout": "abc"})
        assert status == 400
        status, _ = _http.request_json(
            ep, "POST", "/admin/scale",
            {"front": "generate", "action": "revive", "rid": 999})
        assert status == 409      # engine surface: replica vanished
        # non-object /generate body at the front door -> 400, not 500
        req = urllib.request.Request(
            fabric_stack["url"] + "/generate", data=b"[1, 2]",
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 400

    def test_unmodified_watchdog_revives_remote_wedge(self,
                                                     fabric_stack):
        """A chaos-wedged decode worker on the (remote, as far as the
        controller knows) host trips the UNMODIFIED HealthWatchdog
        through FleetEngine rows and is revived over /admin — requests
        complete token-identically, nothing fails."""
        from paddle_tpu.autoscale import HealthWatchdog

        eng, fleet = fabric_stack["engine"], fabric_stack["fleet"]
        prompts = [[5, 9, 1], [2, 4, 8, 16], [7, 7]]
        ref = [eng.generate(p, 6, timeout=120)["tokens"]
               for p in prompts]
        w0 = eng._workers[0]
        chaos.add_rule("serving.decode_step", "delay", 8.0,
                       match={"replica": w0.rid,
                              "generation": w0.generation})
        wd = HealthWatchdog(fleet, exec_deadline_s=0.3,
                            beat_deadline_s=60.0, backoff_s=0.1)
        handles = [eng.submit(p, 6) for p in prompts]
        acted = 0
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not acted:
            acted = wd.poll_once()
            time.sleep(0.05)
        assert acted, "watchdog never fired on the wedged remote worker"
        assert wd.counters["watchdog_revives"] >= 1
        assert [h.result(120)["tokens"] for h in handles] == ref
        assert eng.metrics.failed_total == 0
        chaos.reset()

    def test_autoscaler_drives_fleet_and_stretches_breaker(self,
                                                           fabric_stack):
        from paddle_tpu.autoscale import ReplicaAutoscaler
        from paddle_tpu.autoscale.policy import ScalingPolicy

        fleet = fabric_stack["fleet"]
        router = fabric_stack["router"]
        auto = ReplicaAutoscaler(
            fleet, policy=ScalingPolicy(min_replicas=1, max_replicas=3))
        try:
            # the headroom hook landed on the ROUTER: the front door's
            # breaker stretches while fleet scale-up room remains
            assert router.scale_headroom_fn is not None
            assert int(router.scale_headroom_fn()) >= 1
            sig = auto._signals()
            assert {"replicas", "queue_depth", "p95_ms"} <= set(sig)
            assert auto.poll_once() == 0   # idle fleet: no actuation
        finally:
            auto.close()
            assert router.scale_headroom_fn is None


# ===================================================================
# slow matrix: real subprocess hosts, SIGKILL + two-node launch
# ===================================================================
def _spawn_host(store_port, host_id, extra=None, store=None):
    """`store_port` mounts one local TCPStore; `store=` passes a full
    endpoint spec (a comma list mounts the quorum store)."""
    env = cpu_subprocess_env(
        FABRIC_STORE=store if store is not None
        else f"127.0.0.1:{store_port}",
        FABRIC_HOST_ID=host_id, FABRIC_HEARTBEAT_S="0.25",
        **(extra or {}))
    return subprocess.Popen(
        [sys.executable, WORKER], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, cwd=REPO, env=env)


def _stop_procs(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            pass


@pytest.mark.slow
class TestHostLossChaos:
    def test_sigkill_host_mid_traffic(self):
        """THE acceptance matrix: two real hosts, SIGKILL one under
        live front-door traffic -> suspect -> (failed probes) ->
        evicted within the lease+drain deadline; in-flight non-streamed
        requests complete on the survivor (zero lost); the stream that
        already delivered tokens RESUMES on the survivor token-
        identically (replay-resume: the deterministic key-chain plus
        resume_from — zero duplicate tokens, zero gaps, no terminal
        error); the killed host rejoins at a bumped generation and
        serves again."""
        store = TCPStore(is_master=True)
        procs = []
        view = fd = None
        stop_traffic = threading.Event()
        try:
            procs.append(_spawn_host(store.port, "hA"))
            # the victim decodes slowly (chaos delay per step) so the
            # kill deterministically lands mid-stream / mid-request
            procs.append(_spawn_host(
                store.port, "hB",
                extra={"FLAGS_chaos_spec":
                       "serving.decode_step:delay:0.12"}))
            view = MembershipView(store, lease_s=1.5, drain_s=1.5,
                                  max_probes=2)
            view.start()
            router = FabricRouter(view, hop_timeout_s=60.0,
                                  stream_idle_timeout_s=30.0)
            fd = FabricHTTPServer(router).start()
            url = f"http://127.0.0.1:{fd.port}"
            poll_until(lambda: len(view.alive()) == 2, timeout=180,
                       desc="both hosts registered")

            # reference greedy tokens (identical weights fleet-wide)
            prompt = [3, 7, 11, 2]
            ref = _post_json(url + "/generate",
                             {"input_ids": prompt, "max_new_tokens": 10,
                              "session": "warm"})["tokens"]

            # find a session whose affinity ring lands on the victim
            sess = next(s for s in (f"s{i}" for i in range(64))
                        if router.pick(
                            "generate",
                            affinity_key=str(s).encode()).host_id
                        == "hB")

            # background non-streamed traffic (hits BOTH hosts)
            results, failures = [], []

            def pump(tag):
                i = 0
                while not stop_traffic.is_set():
                    i += 1
                    try:
                        out = _post_json(
                            url + "/generate",
                            {"input_ids": prompt, "max_new_tokens": 10,
                             "session": f"{tag}-{i}"}, timeout=120)
                        results.append(out["tokens"])
                    except Exception as e:  # noqa: BLE001
                        failures.append(repr(e))
                    time.sleep(0.02)

            pumps = [threading.Thread(target=pump, args=(t,),
                                      name=f"pump-{t}", daemon=True)
                     for t in ("t0", "t1", "t2")]
            for t in pumps:
                t.start()

            # the victim-pinned stream: read two tokens, then SIGKILL
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps({"input_ids": prompt,
                                 "max_new_tokens": 10, "stream": True,
                                 "session": sess}).encode(),
                headers={"Content-Type": "application/json"})
            stream_toks, stream_err = [], []
            r = urllib.request.urlopen(req, timeout=120)
            for line in r:
                obj = json.loads(line)
                if "token" in obj:
                    stream_toks.append(obj["token"])
                    if len(stream_toks) == 2:
                        break
            victim = procs[1]
            t_kill = time.monotonic()
            victim.send_signal(signal.SIGKILL)
            for line in r:   # drain the broken stream
                obj = json.loads(line)
                if "token" in obj:
                    stream_toks.append(obj["token"])
                elif "error" in obj:
                    stream_err.append(obj)
            r.close()

            # membership converges within the lease+drain deadline:
            # routing stops at SUSPECT, the member table drops the
            # host at EVICT (probe ladder exhausted + drain window)
            poll_until(lambda: view.get("hB") is None, timeout=30,
                       desc="victim evicted")
            t_conv = time.monotonic() - t_kill
            assert t_conv < view.lease_s + view.drain_s + 4.0, t_conv
            assert view.counters["evictions"] >= 1
            assert [m.host_id for m in view.alive()] == ["hA"]

            # keep traffic flowing a moment on the survivor, then stop
            n_before = len(results)
            poll_until(lambda: len(results) >= n_before + 5,
                       timeout=60, desc="survivor keeps serving")
            stop_traffic.set()
            for t in pumps:
                t.join(120)

            # ZERO lost non-streamed requests, all token-identical
            assert not failures, failures[:5]
            assert results and all(tk == ref for tk in results)
            # the victim-pinned stream RESUMED on the survivor: the
            # full reference sequence, zero duplicates, zero gaps,
            # and no terminal error line reached the client
            assert stream_toks == ref, (stream_toks, ref)
            assert not stream_err, stream_err
            assert router.metrics.streams_resumed_total >= 1
            assert router.metrics.streams_broken_total == 0

            # rejoin: same host_id relaunches -> bumped generation ->
            # serves again (warm-before-admission: it registers only
            # after its engine warmup)
            procs.append(_spawn_host(store.port, "hB"))
            poll_until(lambda: len(view.alive()) == 2, timeout=180,
                       desc="victim rejoined")
            assert view.get("hB").generation >= 1
            assert view.counters["rejoins"] >= 1
            out = _post_json(url + "/generate",
                             {"input_ids": prompt, "max_new_tokens": 10,
                              "session": sess}, timeout=120)
            assert out["tokens"] == ref
            # the victim-pinned affinity session routes to hB again now
            # that it is back on the ring — and the stream completes
            # token-identically (serving again, not just registered)
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps({"input_ids": prompt,
                                 "max_new_tokens": 10, "stream": True,
                                 "session": sess}).encode(),
                headers={"Content-Type": "application/json"})
            n0 = router.metrics.forwards_total.get("hB", 0)
            toks = []
            with urllib.request.urlopen(req, timeout=120) as r2:
                for line in r2:
                    obj = json.loads(line)
                    if "token" in obj:
                        toks.append(obj["token"])
            assert toks == ref
            assert router.metrics.forwards_total.get("hB", 0) > n0, \
                "rejoined host never took traffic"
        finally:
            stop_traffic.set()
            if fd is not None:
                fd.stop()
            elif view is not None:
                view.close()
            _stop_procs(procs)
            store.stop()


@pytest.mark.slow
class TestControlPlaneHAChaos:
    def test_store_primary_sigkill_under_traffic_with_two_doors(self):
        """ISSUE 14 acceptance, integration tier: a 3-member quorum
        store (real subprocesses) under 2 real serving hosts and 2
        front doors. SIGKILL the store PRIMARY mid-generation-traffic:
        zero lost non-streamed requests, no lease falsely expires
        (neither door ever suspects a host), heartbeats resume on the
        new primary, and both doors' member tables + affinity rings
        stay identical through the whole event. Then SIGKILL a host:
        both doors converge to the same shrunk table within the
        lease+drain window."""
        from paddle_tpu.distributed.store import QuorumStore
        from paddle_tpu.inference.fabric import FleetClient

        store_procs, host_procs, fds = [], [], []
        views = []
        stop_traffic = threading.Event()
        store_worker = os.path.join(REPO, "tests",
                                    "store_member_worker.py")
        try:
            eps = []
            for _ in range(3):
                p = subprocess.Popen(
                    [sys.executable, store_worker],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, cwd=REPO, env=cpu_subprocess_env())
                store_procs.append(p)
                line = p.stdout.readline().strip()
                assert line.startswith("STORE="), line
                eps.append(line.split("=", 1)[1])
            spec = ",".join(eps)
            host_procs.append(_spawn_host(None, "hA", store=spec))
            host_procs.append(_spawn_host(None, "hB", store=spec))
            lease_s, drain_s = 2.0, 1.5
            doors = []
            for _ in range(2):
                vstore = QuorumStore(eps, member_timeout=1.0,
                                     probe_interval=1.0)
                view = MembershipView(vstore, lease_s=lease_s,
                                      drain_s=drain_s, max_probes=2)
                view.start()
                views.append(view)
                router = FabricRouter(view, hop_timeout_s=60.0,
                                      stream_idle_timeout_s=30.0)
                fd = FabricHTTPServer(router).start()
                fds.append(fd)
                doors.append(f"127.0.0.1:{fd.port}")
            for view in views:
                poll_until(lambda v=view: len(v.alive()) == 2,
                           timeout=240, desc="door sees both hosts")

            def table(view):
                # host + generation only: `state` is an OBSERVER-LOCAL
                # ladder position — independent 0.5s poll clocks may
                # legitimately put one view a tick ahead (suspect vs
                # alive) for an instant; the convergence contract is
                # about membership + incarnation, and the separate
                # evictions==0 asserts pin the ladder outcome
                return [(r["host"], r["generation"])
                        for r in view.rows()]

            client = FleetClient(doors, timeout_s=120.0)
            prompt = [3, 7, 11, 2]
            status, ref = client.generate(
                {"input_ids": prompt, "max_new_tokens": 8})
            assert status == 200

            results, failures = [], []

            def pump(tag):
                i = 0
                while not stop_traffic.is_set():
                    i += 1
                    try:
                        st_, out = client.generate(
                            {"input_ids": prompt, "max_new_tokens": 8,
                             "session": f"{tag}-{i}"})
                        if st_ == 200:
                            results.append(out["tokens"])
                        else:
                            failures.append(out)
                    except Exception as e:  # noqa: BLE001
                        failures.append(repr(e))
                    time.sleep(0.02)

            pumps = [threading.Thread(target=pump, args=(t,),
                                      name=f"ha-pump-{t}", daemon=True)
                     for t in ("t0", "t1")]
            for t in pumps:
                t.start()
            time.sleep(0.6)

            # ---- SIGKILL the store PRIMARY under live traffic
            pri = views[0].store._primary_i
            store_procs[pri].send_signal(signal.SIGKILL)
            t_kill = time.monotonic()
            # through the whole failover window: no door loses a host
            while time.monotonic() - t_kill < lease_s + drain_s + 2.0:
                for view in views:
                    assert len(view.rows()) == 2, \
                        "store failover expired a serving lease"
                    assert view.counters_snapshot()["evictions"] == 0
                assert table(views[0]) == table(views[1])
                time.sleep(0.2)
            # heartbeats resumed on the new primary: lease ages are
            # fresh again on both doors
            for view in views:
                poll_until(lambda v=view: all(
                    r["lease_age_s"] < lease_s for r in v.rows()),
                    timeout=30, desc="heartbeats resumed post-failover")
            n_before = len(results)
            poll_until(lambda: len(results) >= n_before + 5,
                       timeout=120, desc="traffic flows post-failover")
            assert not failures, failures[:5]

            # ---- now SIGKILL a serving host: both doors converge to
            # the same eviction within the ladder window
            host_procs[1].send_signal(signal.SIGKILL)
            t_kill = time.monotonic()
            for view in views:
                poll_until(lambda v=view: v.get("hB") is None,
                           timeout=60, desc="victim evicted")
            assert time.monotonic() - t_kill < \
                2 * (lease_s + drain_s) + 6.0
            assert table(views[0]) == table(views[1])
            stop_traffic.set()
            for t in pumps:
                t.join(120)
            # zero lost NON-STREAMED requests across BOTH chaos events:
            # the host kill may surface as at most the in-flight hops'
            # one bounded retry — which reruns them, so still zero lost
            assert not failures, failures[:5]
            assert results and all(tk == ref["tokens"]
                                   for tk in results)
        finally:
            stop_traffic.set()
            for fd in fds:
                fd.stop()
            _stop_procs(host_procs + store_procs)


@pytest.mark.slow
class TestTwoNodeLaunch:
    def test_two_node_bringup_and_fleet_resize(self, tmp_path):
        """The long-open two-NODE exercise: one --fleet launcher per
        simulated node (--node_ips, 2-process CPU bring-up), fleet
        membership converges at the front door; --resize_file grow
        (1 -> 2 workers per node) and shrink back, each executed as
        EXIT_PREEMPTED relaunches with the worker set re-read — host
        joins/leaves flow through the router with traffic live."""
        from paddle_tpu.testing.multihost import spawn_launcher

        store = TCPStore(is_master=True)
        resize = str(tmp_path / "resize.json")
        launchers = []
        view = fd = None
        try:
            master = f"127.0.0.1:{free_port()}"
            common = dict(
                FABRIC_STORE=f"127.0.0.1:{store.port}",
                FABRIC_HEARTBEAT_S="0.25")
            for rank in (0, 1):
                launchers.append(spawn_launcher(
                    ["--fleet", "--nnodes", "2", "--node_rank",
                     str(rank), "--node_ips", "127.0.0.1,127.0.0.1",
                     "--master", master, "--nproc_per_node", "1",
                     "--resize_file", resize, "--max_restart", "2",
                     WORKER],
                    extra_env=common))
            view = MembershipView(store, lease_s=2.0, drain_s=1.5)
            view.start()
            router = FabricRouter(view, hop_timeout_s=60.0)
            fd = FabricHTTPServer(router).start()
            url = f"http://127.0.0.1:{fd.port}"
            poll_until(lambda: len(view.alive()) == 2, timeout=240,
                       desc="two-node bring-up")

            prompt = [1, 2, 3]
            ref = _post_json(url + "/generate",
                             {"input_ids": prompt,
                              "max_new_tokens": 6})["tokens"]

            # GROW the fleet: 1 -> 2 workers per node (4 hosts total)
            from paddle_tpu.autoscale import write_resize_file
            write_resize_file(resize, 2)
            poll_until(lambda: len(view.alive()) == 4, timeout=300,
                       desc="fleet grew to 4 hosts")
            out = _post_json(url + "/generate",
                             {"input_ids": prompt, "max_new_tokens": 6})
            assert out["tokens"] == ref

            # SHRINK back to 1 worker per node
            write_resize_file(resize, 1)
            poll_until(lambda: len(view.alive()) == 2, timeout=300,
                       desc="fleet shrank to 2 hosts")
            out = _post_json(url + "/generate",
                             {"input_ids": prompt, "max_new_tokens": 6})
            assert out["tokens"] == ref
            assert view.counters["evictions"] == 0  # all graceful
        finally:
            if fd is not None:
                fd.stop()
            elif view is not None:
                view.close()
            for lp in launchers:
                if lp.poll() is None:
                    lp.send_signal(signal.SIGINT)
            deadline = time.monotonic() + 20
            for lp in launchers:
                while lp.poll() is None and time.monotonic() < deadline:
                    time.sleep(0.2)
                if lp.poll() is None:
                    lp.kill()
                try:
                    lp.communicate(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
            store.stop()
