"""Elastic autoscaling & health watchdog (paddle_tpu/autoscale +
ServingEngine runtime replica APIs) — ISSUE 9.

Serving side runs in-process on the CPU backend (deterministic: chaos
rules are count/match-scoped, the policy clock is explicit). Training
side proves the resize loop over REAL coordinated processes with the
testing/multihost harness: the global device mesh is held fixed while
the process count changes, so resize-then-resume must be BITWISE the
uninterrupted run.

The whole module runs under the testing/lockcheck shim (same autouse
pattern as serving/fault-tolerance): any lock-order cycle recorded by
the new controller threads fails the module even when the fatal
interleaving never fired.
"""
import os
import sys
import time
from unittest import mock

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _cpu_env import cpu_subprocess_env  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
from paddle_tpu import jit  # noqa: E402
from paddle_tpu.autoscale import (HealthWatchdog,  # noqa: E402
                                  RankWatchdog, ReplicaAutoscaler,
                                  ScalingPolicy, WorldAutoscaler,
                                  read_resize_file, write_resize_file)
from paddle_tpu.inference.serving import (ServingEngine,  # noqa: E402
                                          ServingError)
from paddle_tpu.static import InputSpec  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402
from paddle_tpu.testing import multihost as mh  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "autoscale_worker.py")


@pytest.fixture(scope="module", autouse=True)
def _lockcheck_module():
    """Lock-order race detection across the WHOLE module: every lock
    the engine pool, autoscaler, watchdog and metrics create during
    these tests is shimmed; any acquisition-order cycle fails here."""
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
def _chaos_clean():
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 4))
    model.eval()
    prefix = str(tmp_path_factory.mktemp("autoscale") / "model")
    jit.save(model, prefix, input_spec=[InputSpec([None, 8], "float32")])
    return prefix


def make_engine(prefix, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("batch_timeout_ms", 10)
    kw.setdefault("replicas", 1)
    return ServingEngine(prefix, **kw)


def req(seed=0, rows=1):
    return [np.random.RandomState(seed).randn(rows, 8).astype("float32")]


# ---------------------------------------------------------------- policy --
class TestScalingPolicy:
    def test_up_needs_consecutive_overload_and_respects_max(self):
        p = ScalingPolicy(min_replicas=1, max_replicas=2,
                          up_queue_per_replica=2.0, up_consecutive=2,
                          up_cooldown_s=0.0)
        hot = {"replicas": 1, "queue_depth": 10, "busy_replicas": 1}
        assert p.observe(0.0, hot) == 0      # first hit: hysteresis
        assert p.observe(0.1, hot) == 1      # second consecutive: up
        hot2 = {"replicas": 2, "queue_depth": 10, "busy_replicas": 2}
        assert p.observe(0.2, hot2) == 0
        assert p.observe(0.3, hot2) == 0     # at max: never exceeds

    def test_spike_does_not_scale(self):
        p = ScalingPolicy(max_replicas=4, up_consecutive=3)
        hot = {"replicas": 1, "queue_depth": 100, "busy_replicas": 1}
        calm = {"replicas": 1, "queue_depth": 0, "busy_replicas": 1}
        assert p.observe(0.0, hot) == 0
        assert p.observe(0.1, calm) == 0     # streak broken
        assert p.observe(0.2, hot) == 0
        assert p.observe(0.3, hot) == 0

    def test_up_cooldown_blocks_back_to_back(self):
        p = ScalingPolicy(max_replicas=8, up_consecutive=1,
                          up_cooldown_s=10.0)
        hot = {"replicas": 1, "queue_depth": 50, "busy_replicas": 1}
        assert p.observe(100.0, hot) == 1
        assert p.observe(100.5, hot) == 0    # inside cooldown
        assert p.observe(111.0, hot) == 1    # cooldown elapsed

    def test_down_needs_idle_and_floor(self):
        p = ScalingPolicy(min_replicas=1, max_replicas=4,
                          down_consecutive=2, down_cooldown_s=0.0,
                          down_busy_frac=0.34)
        idle2 = {"replicas": 2, "queue_depth": 0, "busy_replicas": 0}
        busy2 = {"replicas": 2, "queue_depth": 0, "busy_replicas": 2}
        assert p.observe(0.0, idle2) == 0
        assert p.observe(0.1, busy2) == 0    # busy replicas block down
        assert p.observe(0.2, idle2) == 0
        assert p.observe(0.3, idle2) == -1
        idle1 = {"replicas": 1, "queue_depth": 0, "busy_replicas": 0}
        for t in range(10):
            assert p.observe(1.0 + t, idle1) == 0  # min floor holds

    def test_headroom(self):
        p = ScalingPolicy(min_replicas=1, max_replicas=3)
        assert p.headroom(1) == 2
        assert p.headroom(3) == 0
        assert ScalingPolicy(max_replicas=None).headroom(99) == 1


# ------------------------------------------------------- runtime replicas --
class TestDynamicReplicas:
    def test_add_replica_warms_before_admission(self, saved_model):
        """A replica added at runtime is warmed through the compile
        cache BEFORE it can see traffic: its report says so, and the
        traffic that follows records only bucket HITS (zero new
        compiles) — the executables were all pre-built."""
        # The added replica lands on a second device, and a cold
        # persistent cache (a clean checkout's first run) has never held
        # that device's executables: it would compile them anew, rightly.
        # "Never an XLA re-compile" is a warm cache's property, so build
        # them once first, as an earlier life of the server would have —
        # then the verdict no longer depends on what an untracked
        # directory held when the run began.
        make_engine(saved_model, replicas=2).shutdown()
        eng = make_engine(saved_model)
        # shut down whatever the verdict: a failed assertion that left the
        # engine's threads running made every later test of this xdist
        # worker share its process with them (schedcheck then reports
        # nondeterminism, the compile counters count their compiles)
        try:
            base = eng.metrics.snapshot()
            compiles_before = sum(st["compiles"]
                                  for st in base["buckets"].values())
            rep = eng.add_replica()
            assert rep["admitted_after_warmup"]
            assert rep["warmed_executables"] == len(eng._boundaries)
            assert rep["persistent_misses"] == 0  # never an XLA re-compile
            assert eng.health()["replicas"] == 2
            futs = [eng.submit(req(i)) for i in range(12)]
            for f in futs:
                f.result(60)
            snap = eng.metrics.snapshot()
            compiles_after = sum(st["compiles"]
                                 for st in snap["buckets"].values())
            assert compiles_after == compiles_before
            assert sum(st["hits"] for st in snap["buckets"].values()) > 0
        finally:
            eng.shutdown()

    def test_remove_replica_drains_without_losing_requests(self,
                                                           saved_model):
        """Drain-then-retire: requests queued on the retiring replica
        all complete; zero are lost or failed."""
        eng = make_engine(saved_model, replicas=2, auto_start=False)
        futs = [eng.submit(req(i)) for i in range(12)]
        eng.start()
        r = eng.remove_replica(drain=True, timeout=30)
        assert r["drained"] and r["state"] == "retired"
        for f in futs:
            assert len(f.result(60)) == 1
        snap = eng.metrics.snapshot()
        assert snap["failed_total"] == 0
        assert snap["responses_total"] == 12
        assert eng.health()["replicas"] == 1
        eng.shutdown()

    def test_remove_last_replica_refused(self, saved_model):
        eng = make_engine(saved_model, replicas=1)
        with pytest.raises(ValueError, match="last active replica"):
            eng.remove_replica()
        eng.shutdown()

    def test_chaos_raise_during_drain_leaves_no_stranded_future(
            self, saved_model):
        """A fault injected at the scale.drain site aborts the removal
        cleanly: the pool is unchanged and every in-flight request
        still completes."""
        eng = make_engine(saved_model, replicas=2, auto_start=False)
        futs = [eng.submit(req(i)) for i in range(8)]
        chaos.add_rule("scale.drain", "raise_n", "1")
        with pytest.raises(chaos.ChaosError):
            eng.remove_replica(drain=True)
        eng.start()
        for f in futs:
            f.result(60)
        assert eng.health()["replicas"] == 2
        assert eng.metrics.snapshot()["failed_total"] == 0
        eng.shutdown()

    def test_future_completion_is_idempotent(self, saved_model):
        from paddle_tpu.inference.serving.engine import Future

        f = Future()
        assert f.set_result([1]) is True
        assert f.set_error(RuntimeError("late zombie")) is False
        assert f.result(1) == [1]


# ------------------------------------------------------------ retry-after --
class TestDerivedRetryAfter:
    def test_retry_after_tracks_drain_rate_and_clamps(self, saved_model):
        eng = make_engine(saved_model, auto_start=False,
                          retry_after_s=0.2, retry_after_max_s=5.0)
        # empty queue: floor
        assert eng._retry_after() == 0.2
        for _ in range(8):
            eng._queue.append(object())  # only len() is consulted
        with mock.patch.object(eng.metrics, "qps", return_value=16.0):
            assert eng._retry_after() == pytest.approx(0.5)  # 8/16
        with mock.patch.object(eng.metrics, "qps", return_value=0.1):
            assert eng._retry_after() == 5.0   # clamped to max
        with mock.patch.object(eng.metrics, "qps", return_value=1e9):
            assert eng._retry_after() == 0.2   # clamped to floor
        eng._queue.clear()
        eng.shutdown(drain=False)

    def test_shed_carries_derived_retry_after(self, saved_model):
        eng = make_engine(saved_model, auto_start=False,
                          max_queue_depth=4, retry_after_s=0.1,
                          retry_after_max_s=9.0)
        for i in range(4):
            eng.submit(req(i))
        with mock.patch.object(eng.metrics, "qps", return_value=2.0):
            with pytest.raises(ServingError) as e:
                eng.submit(req(99))
        assert e.value.status == 503
        assert e.value.retry_after == pytest.approx(4 / 2.0)
        eng.shutdown(drain=False)


# ------------------------------------------------------ scale before shed --
class TestScaleBeforeShed:
    def test_headroom_stretches_breaker_then_autoscaler_grows(
            self, saved_model):
        """Degrade order scale -> queue -> shed: with scale-up headroom
        the breaker queues past max_queue_depth instead of shedding,
        and the autoscaler grows the pool; only with the pool maxed
        does the original bound shed."""
        eng = make_engine(saved_model, replicas=1, auto_start=False,
                          max_queue_depth=4, overload_queue_factor=2.0)
        policy = ScalingPolicy(min_replicas=1, max_replicas=2,
                               up_queue_per_replica=2.0,
                               up_consecutive=1, up_cooldown_s=0.0)
        scaler = ReplicaAutoscaler(eng, policy=policy)  # not started:
        # poll_once below owns the clock — no thread, no sleeps
        for i in range(6):  # beyond max_queue_depth, below 2x stretch
            eng.submit(req(i))
        assert eng.metrics.snapshot()["shed_total"] == 0  # queued, not shed
        assert scaler.poll_once(now=0.0) == 1             # scaled UP
        assert scaler.counters["scale_ups"] == 1
        assert eng.health()["replicas"] == 2
        # pool maxed: headroom 0 -> bound reverts -> now it sheds
        assert scaler._headroom() == 0
        for i in range(3):
            try:
                eng.submit(req(i))
            except ServingError:
                pass
        assert eng.metrics.snapshot()["shed_total"] > 0
        eng.start()
        time.sleep(0.1)
        eng.shutdown()  # drains the queued requests


# ---------------------------------------------------------- health watchdog --
class TestHealthWatchdog:
    def test_hung_replica_replaced_within_deadline_no_collateral(
            self, saved_model):
        """Chaos hang-injection wedges ONE replica mid-execute; the
        watchdog detects it within its deadline and replaces it; every
        request — including the hung batch, requeued to a healthy
        replica — completes; zero failures."""
        eng = make_engine(saved_model, replicas=2, auto_start=False)
        sick_rid = eng._replicas[0].rid
        # the rule is match-scoped to the sick replica's rid: its
        # REPLACEMENT gets a fresh rid and runs clean (deterministic —
        # no mid-test healing needed)
        chaos.add_rule("serving.execute", "delay", "3.0",
                       match={"replica": str(sick_rid)})
        wd = HealthWatchdog(eng, exec_deadline_s=0.4,
                            poll_interval_s=0.05, max_revives=0,
                            backoff_s=0.2)
        futs = [eng.submit(req(i)) for i in range(10)]
        eng.start()
        t0 = time.monotonic()
        deadline = t0 + 20.0
        while wd.counters["watchdog_replacements"] == 0 and \
                time.monotonic() < deadline:
            wd.poll_once()
            time.sleep(0.05)
        detect_s = time.monotonic() - t0
        assert wd.counters["watchdog_replacements"] == 1
        # detection within deadline + polling slack (generous for CI)
        assert detect_s < 0.4 + 3.0
        for f in futs:
            assert len(f.result(60)) == 1   # nothing lost, nothing 500d
        assert eng.metrics.snapshot()["failed_total"] == 0
        assert eng.health()["replicas"] == 2  # replacement admitted
        states = {s["rid"]: s["state"] for s in eng.replica_states()}
        assert states[sick_rid] == "retired"
        eng.shutdown()

    def test_revive_replaces_worker_in_place(self, saved_model):
        """First strikes revive (fresh worker generation, same replica)
        rather than retiring: cheaper, keeps the warm device."""
        eng = make_engine(saved_model, replicas=2, auto_start=False)
        sick_rid = eng._replicas[1].rid
        chaos.add_rule("serving.execute", "delay", "3.0",
                       match={"replica": str(sick_rid)})
        wd = HealthWatchdog(eng, exec_deadline_s=0.3,
                            poll_interval_s=0.05, max_revives=2,
                            backoff_s=0.2)
        futs = [eng.submit(req(i)) for i in range(6)]
        eng.start()
        deadline = time.monotonic() + 20.0
        while wd.counters["watchdog_revives"] == 0 and \
                time.monotonic() < deadline:
            wd.poll_once()
            time.sleep(0.05)
        assert wd.counters["watchdog_revives"] >= 1
        # heal the device (rules off) so the revived generation is clean
        chaos.reset()
        for f in futs:
            assert len(f.result(60)) == 1
        assert eng.metrics.snapshot()["failed_total"] == 0
        eng.shutdown()


# ------------------------------------------------------------- world side --
class _FakeStore:
    def __init__(self):
        self.kv = {}

    def set(self, k, v):
        self.kv[k] = v.encode() if isinstance(v, str) else v

    def get(self, k):
        return self.kv.get(k)


class _FakeSupervisor:
    def __init__(self):
        self.reasons = []

    def request_restart(self, reason):
        self.reasons.append(reason)

    def cancel_restart(self, reason):
        if self.reasons and self.reasons[-1] == reason:
            self.reasons.pop()
            return True
        return False


class TestWorldAutoscaler:
    def test_resize_armed_once_and_file_written(self, tmp_path):
        sup = _FakeSupervisor()
        rf = str(tmp_path / "resize.json")
        desired = {"n": None}
        wa = WorldAutoscaler(sup, world=2, desired_fn=lambda: desired["n"],
                             resize_file=rf)
        assert wa.maybe_resize() is False          # no opinion yet
        desired["n"] = 2
        assert wa.maybe_resize() is False          # already that size
        desired["n"] = 4
        assert wa.maybe_resize() is True
        assert sup.reasons == ["world resize 2 -> 4 (autoscale)"]
        assert read_resize_file(rf) == 4
        # already armed: polling every step until the boundary fires
        # must not re-arm, rewrite the file, or inflate the counter
        assert wa.maybe_resize() is False
        assert wa.counters["world_resizes_requested"] == 1
        assert len(sup.reasons) == 1
        # explicit revert BEFORE the boundary: the armed restart is
        # withdrawn and the resize file restored to the current world
        desired["n"] = 2
        assert wa.maybe_resize() is False
        assert sup.reasons == []            # our request cancelled
        assert read_resize_file(rf) == 2    # file restored
        desired["n"] = 4
        assert wa.maybe_resize() is True    # can re-arm afterwards
        assert wa.counters["world_resizes_requested"] == 2

    def test_store_source_and_range_clamp(self, tmp_path):
        sup = _FakeSupervisor()
        store = _FakeStore()
        wa = WorldAutoscaler(sup, world=2, store=store, np_range=(1, 8))
        assert wa.maybe_resize() is False
        store.set("autoscale/desired_world", "64")  # outside range
        assert wa.maybe_resize() is False
        store.set("autoscale/desired_world", "not-a-number")
        assert wa.maybe_resize() is False
        store.set("autoscale/desired_world", "1")
        assert wa.maybe_resize() is True
        assert sup.reasons and "2 -> 1" in sup.reasons[0]

    def test_resize_file_roundtrip(self, tmp_path):
        from paddle_tpu.distributed.launch.main import _read_resize_nproc

        rf = str(tmp_path / "rf.json")
        write_resize_file(rf, 3)
        # the launcher's import-light reader agrees with the package one
        assert _read_resize_nproc(rf) == 3
        assert read_resize_file(rf) == 3
        assert _read_resize_nproc(str(tmp_path / "missing.json")) is None


class TestRankWatchdog:
    def test_wedge_detected_when_peers_advance(self):
        store = _FakeStore()
        fired = []
        mgr = mock.Mock()
        wd = RankWatchdog(step_fn=lambda: 5, store=store, rank=0,
                          stall_after_s=10.0, lead_steps=2,
                          manager=mgr, on_wedged=lambda: fired.append(1))
        assert wd.poll_once(now=0.0) is False      # baseline
        store.set("autoscale/progress/1", "9")     # peer raced ahead
        assert wd.poll_once(now=5.0) is False      # not stalled long enough
        assert wd.poll_once(now=11.0) is True      # stalled + peer lead
        assert fired == [1] and wd.wedged
        mgr.exit.assert_called_once()              # de-registered
        assert store.kv["autoscale/progress/0"] == b"5"

    def test_global_stall_is_not_a_wedge(self):
        """Peers equally stuck = outage (store down, data stall): the
        watchdog must NOT kill the rank and make it worse."""
        store = _FakeStore()
        fired = []
        wd = RankWatchdog(step_fn=lambda: 5, store=store, rank=0,
                          stall_after_s=10.0, lead_steps=2,
                          on_wedged=lambda: fired.append(1))
        store.set("autoscale/progress/1", "5")     # peer at same step
        assert wd.poll_once(now=0.0) is False
        assert wd.poll_once(now=60.0) is False
        assert fired == []

    def test_progress_resets_the_clock(self):
        store = _FakeStore()
        steps = iter([1, 2, 3, 4])
        wd = RankWatchdog(step_fn=lambda: next(steps), store=store,
                          rank=0, stall_after_s=10.0,
                          on_wedged=lambda: (_ for _ in ()).throw(
                              AssertionError("must not fire")))
        store.set("autoscale/progress/1", "100")
        for t in range(4):
            assert wd.poll_once(now=t * 8.0) is False  # always advancing


# ---------------------------------------------------- launcher resize path --
class TestLauncherResize:
    def test_relaunch_rereads_resize_file(self, tmp_path):
        """EXIT_PREEMPTED relaunch re-reads --resize_file and spawns the
        new world: incarnation 1 runs 1 proc, writes nproc=2, exits 17;
        incarnation 2 runs 2 procs. Plain-python trainer (no jax)."""
        from paddle_tpu.distributed.launch.main import launch

        rf = str(tmp_path / "resize.json")
        marker = str(tmp_path / "marker.txt")
        script = str(tmp_path / "trainer.py")
        with open(script, "w") as f:
            f.write(
                "import json, os, sys\n"
                "n = int(os.environ['PADDLE_TRAINERS_NUM'])\n"
                "tid = os.environ['PADDLE_TRAINER_ID']\n"
                "with open(os.environ['MARKER'], 'a') as m:\n"
                "    m.write(f'{tid}/{n}\\n')\n"
                "if n == 1:\n"
                "    with open(os.environ['RF'], 'w') as r:\n"
                "        json.dump({'nproc_per_node': 2}, r)\n"
                "    sys.exit(17)\n"
                "sys.exit(0)\n")
        env = cpu_subprocess_env(RF=rf, MARKER=marker)
        with mock.patch.dict(os.environ, env, clear=True):
            rc = launch(["--resize_file", rf, "--nproc_per_node", "1",
                         "--master", "127.0.0.1:45117", script])
        assert rc == 0
        lines = open(marker).read().split()
        assert lines[0] == "0/1"                  # first world: 1 proc
        assert sorted(lines[1:]) == ["0/2", "1/2"]  # resized world


# ------------------------------------------------- multihost resize (slow) --
@pytest.mark.slow  # ~55s of real-process resize relaunches (ISSUE 14
# budget trim); the resize contract stays tier-1-covered in-process
# (TestWorldAutoscaler) and end-to-end in test_fabric's --fleet tier
class TestElasticResizeMultihost:
    """THE tentpole acceptance: grow and shrink resize-then-resume over
    real coordinated processes, bitwise vs the uninterrupted run; a
    SIGKILL in the middle of the resize checkpoint never corrupts."""

    def _params(self, path):
        return np.load(path)

    def test_grow_shrink_resume_bitwise_and_kill_during_resize(
            self, tmp_path):
        total, gb = "6", "8"
        # uninterrupted reference: 1 process x 2 devices (global mesh
        # dp=2 — held fixed across every phase; elasticity is the
        # PROCESS layout changing, the reshard-on-load contract)
        ref = str(tmp_path / "ref.npz")
        mh.run_multihost(WORKER, 1, devices_per_proc=2, timeout=200,
                         extra_env={"CKPT_DIR": str(tmp_path / "ck0"),
                                    "OUT": ref, "TOTAL": total,
                                    "GLOBAL_BS": gb})

        # GROW 1 -> 2 processes at step 4: the worker's WorldAutoscaler
        # arms the resize, records it for the relauncher, checkpoints
        # and exits EXIT_PREEMPTED
        ck1 = str(tmp_path / "ck1")
        rf1 = str(tmp_path / "rf1.json")
        r = mh.run_multihost(
            WORKER, 1, devices_per_proc=2, ok_codes=(17,), retries=0,
            timeout=200,
            extra_env={"CKPT_DIR": ck1, "TOTAL": total, "GLOBAL_BS": gb,
                       "RESIZE_AT": "4", "DESIRED": "2",
                       "RESIZE_FILE": rf1})
        assert r[0].value("RESIZED") == "1"
        assert read_resize_file(rf1) == 2          # relauncher's input
        out1 = str(tmp_path / "grown.npz")
        r = mh.run_multihost(WORKER, 2, timeout=200,
                             extra_env={"CKPT_DIR": ck1, "OUT": out1,
                                        "TOTAL": total, "GLOBAL_BS": gb})
        assert r[0].value("RESUMED") == "4"
        assert r[0].value("DONE") == total
        a, b = self._params(ref), self._params(out1)
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"grow {k}")

        # SHRINK 2 -> 1 at step 4, mirror of the above
        ck2 = str(tmp_path / "ck2")
        r = mh.run_multihost(
            WORKER, 2, ok_codes=(17,), retries=0, timeout=200,
            extra_env={"CKPT_DIR": ck2, "TOTAL": total, "GLOBAL_BS": gb,
                       "RESIZE_AT": "4", "DESIRED": "1"})
        assert all(x.returncode == 17 for x in r)
        out2 = str(tmp_path / "shrunk.npz")
        r = mh.run_multihost(WORKER, 1, devices_per_proc=2, timeout=200,
                             extra_env={"CKPT_DIR": ck2, "OUT": out2,
                                        "TOTAL": total, "GLOBAL_BS": gb})
        assert r[0].value("RESUMED") == "4"
        c = self._params(out2)
        for k in a.files:
            np.testing.assert_array_equal(a[k], c[k],
                                          err_msg=f"shrink {k}")

        # CHAOS: SIGKILL lands mid-write of the resize checkpoint. The
        # previous verified checkpoint survives (manifest-verified
        # restore walks past the torn write) and the resumed new world
        # still finishes bitwise identical.
        ck3 = str(tmp_path / "ck3")
        r = mh.run_multihost(
            WORKER, 1, devices_per_proc=2, ok_codes=(-9,), retries=0,
            timeout=200,
            extra_env={"CKPT_DIR": ck3, "TOTAL": total, "GLOBAL_BS": gb,
                       "RESIZE_AT": "4", "DESIRED": "2",
                       "CHAOS_RESIZE_KILL": "1"})
        assert r[0].returncode == -9               # really SIGKILLed
        out3 = str(tmp_path / "killed_resized.npz")
        r = mh.run_multihost(WORKER, 2, timeout=200,
                             extra_env={"CKPT_DIR": ck3, "OUT": out3,
                                        "TOTAL": total, "GLOBAL_BS": gb})
        resumed = int(r[0].value("RESUMED"))
        assert resumed in (2, 4)   # a VERIFIED step, never a torn one
        assert r[0].value("DONE") == total
        d = self._params(out3)
        for k in a.files:
            np.testing.assert_array_equal(a[k], d[k],
                                          err_msg=f"chaos {k}")


# ----------------------------------------------------------- bus provider --
class TestBusProvider:
    def test_autoscale_section_rides_summary(self, saved_model):
        from paddle_tpu.observability import bus

        sup = _FakeSupervisor()
        wa = WorldAutoscaler(sup, world=1, desired_fn=lambda: 2)
        assert wa.maybe_resize() is True
        section = bus.collect().get("autoscale")
        assert section is not None
        assert section["world_resizes_requested"] >= 1
