"""Test env: run everything on a virtual 8-device CPU mesh (the fake-TPU CI
pattern — analog of the reference's custom_cpu plug-in testing,
/root/reference/test/custom_runtime/test_custom_cpu_plugin.py). The chip is
reached through chip_smoke.py, never through the tests."""
import os

# Force the CPU backend with eight virtual devices, before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# No pytest-timeout in the image: a watchdog dumps all stacks and aborts
# the process if ONE TEST outlasts the window (re-armed at every test
# start and cancelled at every test end — not a limit on the whole suite,
# nor on a worker that has run out of tests and waits for the others).
# The window sits well below the
# tier-1 command's own time limit (1,470 s as the driver runs it), so a
# hang dumps stacks and frees its worker instead of eating the run; every
# subprocess wait inside a test is bounded below it. Under xdist only the
# workers carry it: the controller starts no test itself, and would
# otherwise abort the whole session — and lose every result — while its
# last worker sits in a long test.
import faulthandler as _fh

_WEDGE_WINDOW_S = 600
_armed = False


def pytest_configure(config):
    global _armed
    is_controller = bool(getattr(config.option, "numprocesses", None)) \
        and not hasattr(config, "workerinput")
    _armed = not is_controller
    if _armed:
        _fh.dump_traceback_later(_WEDGE_WINDOW_S, exit=True)
    # Build cpp/ (git-ignored output) BEFORE collection, through the one
    # function that builds it under cpp/.build_lock: test_pd_infer_capi.py
    # and test_shm_channel.py decide their skips at collection, and a
    # library that only some later test's store builds lazily would skip
    # them on a clean tree's first run and pass them on every later one.
    # Under xdist the controller gets here before it starts a worker; the
    # workers' calls find the library fresh. Without make or a compiler
    # this returns None and those tests skip. The NATIVE_* snapshots below
    # were taken at import, before this.
    from paddle_tpu.distributed.store import _load_lib

    _load_lib()


def pytest_runtest_logstart(nodeid, location):
    # dump_traceback_later replaces the previous timer, so re-arming is
    # a single call
    if _armed:
        _fh.dump_traceback_later(_WEDGE_WINDOW_S, exit=True)


def pytest_runtest_logfinish(nodeid, location):
    # between tests nothing can wedge: an xdist worker with no test left
    # must not be aborted 600 s after its last one began (ROADMAP D0(b))
    if _armed:
        _fh.cancel_dump_traceback_later()


# ----------------------------------------------------------- native libs --
# When cpp/ WAS built (the Makefile leaves a .native_built
# stamp next to the .so), a missing/unloadable native runtime is a test
# FAILURE, not a skip — a build regression must turn the suite red.
# The .so presence is snapshotted at session start, BEFORE any test can
# trigger fleet_executor._load_lib's lazy rebuild: "the artifact was
# deleted but a rebuild papered over it" still fails.
import glob as _glob

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_LIB_DIR = os.path.join(_REPO_ROOT, "paddle_tpu", "lib")
NATIVE_SO_AT_START = bool(
    _glob.glob(os.path.join(_NATIVE_LIB_DIR, "*.so")))
NATIVE_BUILD_STAMP = os.path.exists(
    os.path.join(_NATIVE_LIB_DIR, ".native_built"))


def require_native(loaded: bool) -> None:
    """Gate for native-backed tests: pass through when the runtime is
    usable, pytest.fail when cpp/ was built but the runtime is gone,
    pytest.skip only when it was never built here."""
    import pytest

    if NATIVE_BUILD_STAMP and not NATIVE_SO_AT_START:
        pytest.fail(
            "cpp/ was built (paddle_tpu/lib/.native_built) but "
            "libpaddletpu_runtime.so was missing at session start — "
            "build artifact deleted or build regression")
    if not loaded:
        if NATIVE_BUILD_STAMP:
            pytest.fail(
                "cpp/ was built but the native runtime failed to "
                "load/rebuild — C++ build regression")
        pytest.skip("native library unavailable (cpp/ never built here)")


# -------------------------------------------------------- fabric threads --
import pytest as _pytest


@_pytest.fixture
def fabric_threads_stopped(monkeypatch):
    """Stops, when the test ends, every HostLease heartbeat and
    MembershipView poll loop the test started (inference/fabric/
    membership.py `_loop`): the policy tests register leases on dict
    stores and never leave, and a daemon thread left behind lives on in
    the xdist worker under every later test. Module-scoped fixtures are
    set up before this one and keep their threads."""
    from paddle_tpu.inference.fabric.membership import (HostLease,
                                                        MembershipView)

    started = []

    def recording(method):
        def wrapped(self, *args, **kwargs):
            started.append(self)
            return method(self, *args, **kwargs)
        return wrapped

    monkeypatch.setattr(HostLease, "register",
                        recording(HostLease.register))
    monkeypatch.setattr(MembershipView, "start",
                        recording(MembershipView.start))
    yield
    for obj in started:
        obj._stop.set()
    for obj in started:
        if obj._thread is not None:
            obj._thread.join(5.0)
            assert not obj._thread.is_alive()
