"""Subprocess multi-host harness: real N-process SPMD on CPU.

The multi-host behaviors that matter — coordination-service rendezvous,
cross-process collectives, per-rank shard writes behind the checkpoint
commit barrier, preemption fan-out — only exist BETWEEN processes, so
they are tested with real processes (the tests/ft_worker.py pattern,
widened to a world): ``run_multihost`` spins N python workers, each
holding one slot of the ``PADDLE_TRAINER_*`` env contract against one
fresh coordination-service port, and collects per-rank results.

CPU-ready: worker envs are scrubbed of device selection and pinned to
``JAX_PLATFORMS=cpu`` (the tests/_cpu_env.py scrub, repeated here
because the harness ships in the package, not the test tree);
mesh_runtime.initialize inside the worker arms gloo collectives, so the
processes form a REAL multi-process world with working cross-process
programs — tier-1 testable on any dev box.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def clean_cpu_env(**extra) -> Dict[str, str]:
    """os.environ minus device selection / stale PADDLE_* identity, plus
    JAX_PLATFORMS=cpu and the repo on PYTHONPATH."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_PLATFORM"))}
    parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    if _REPO not in parts:
        parts.insert(0, _REPO)
    env["PYTHONPATH"] = os.pathsep.join(parts)
    env["JAX_PLATFORMS"] = "cpu"
    env.update({k: str(v) for k, v in extra.items()})
    return env


def worker_env(rank: int, nproc: int, port: int,
               devices_per_proc: int = 1, **extra) -> Dict[str, str]:
    """The launch contract one worker consumes (what
    distributed/launch's build_env_matrix emits, single-node form)."""
    env = clean_cpu_env(**extra)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_LOCAL_RANK": str(rank),
        "PADDLE_TRAINERS_NUM": str(nproc),
        "PADDLE_NNODES": str(nproc),
        "PADDLE_NODE_RANK": str(rank),
        "PADDLE_LOCAL_SIZE": "1",
        "PADDLE_MASTER": f"127.0.0.1:{port}",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={devices_per_proc}",
    })
    return env


class WorkerResult:
    def __init__(self, rank: int, returncode: int, stdout: str,
                 stderr: str):
        self.rank = rank
        self.returncode = returncode
        self.stdout = stdout
        self.stderr = stderr

    def values(self, key: str) -> List[str]:
        """All `KEY=value` report lines this rank printed."""
        out = []
        for line in self.stdout.splitlines():
            if line.startswith(key + "="):
                out.append(line[len(key) + 1:].strip())
        return out

    def value(self, key: str) -> Optional[str]:
        vals = self.values(key)
        return vals[-1] if vals else None

    def __repr__(self):
        return (f"WorkerResult(rank={self.rank}, "
                f"rc={self.returncode})")


class RankProc:
    """One rank of a process world, writing to its own temp files, never
    to pipes: the ranks are waited for one after another, and a rank
    whose 64 KB pipe fills while another is being waited for blocks in
    write() — its peers then wait for it in a collective and the world
    deadlocks until the timeout. (XLA logs ~1.7 KB to stderr per
    executable loaded from a warm persistent cache, so warm runs got
    there.)"""

    def __init__(self, argv: Sequence[str], env: Dict[str, str]):
        self._out = tempfile.TemporaryFile("w+")
        self._err = tempfile.TemporaryFile("w+")
        self.proc = subprocess.Popen(list(argv), stdout=self._out,
                                     stderr=self._err, cwd=_REPO, env=env)

    def result(self) -> tuple:
        """(returncode, stdout, stderr) of the exited process."""
        texts = []
        for f in (self._out, self._err):
            f.seek(0)
            texts.append(f.read())
            f.close()
        return (self.proc.returncode, *texts)


def wait_ranks(procs: Sequence[RankProc], timeout: float) -> List[tuple]:
    """Wait for every rank under ONE deadline; on expiry the whole world
    is killed. Returns [(returncode, stdout, stderr)] in rank order."""
    deadline = time.monotonic() + timeout
    for p in procs:
        try:
            p.proc.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                if q.proc.poll() is None:
                    q.proc.kill()
            p.proc.wait()
    return [p.result() for p in procs]


def run_multihost(script: str, nproc: int,
                  extra_env: Optional[Dict[str, str]] = None,
                  per_rank_env: Optional[Sequence[Dict[str, str]]] = None,
                  devices_per_proc: int = 1, timeout: float = 240.0,
                  ok_codes: Sequence[int] = (0,), retries: int = 1
                  ) -> List[WorkerResult]:
    """Run `script` as `nproc` coordinated CPU processes; returns one
    WorkerResult per rank (rank order).

    `extra_env` applies to every rank; `per_rank_env[r]` overlays rank r
    (how a chaos spec targets ONE rank). Exit codes outside `ok_codes`
    — or a wedge past `timeout` — retry once on a fresh port
    (coordination-service startup can starve under CI load; the same
    hardening tests/test_multiprocess carries), then raise with the
    offending ranks' stderr tails."""
    last: List[WorkerResult] = []
    for attempt in range(retries + 1):
        port = free_port()
        procs = []
        for r in range(nproc):
            env = worker_env(r, nproc, port,
                             devices_per_proc=devices_per_proc,
                             **(extra_env or {}))
            if per_rank_env and r < len(per_rank_env) and per_rank_env[r]:
                env.update({k: str(v)
                            for k, v in per_rank_env[r].items()})
            procs.append(RankProc([sys.executable, script], env))
        results = [WorkerResult(r, *out)
                   for r, out in enumerate(wait_ranks(procs, timeout))]
        last = results
        if all(res.returncode in ok_codes for res in results):
            return results
    bad = [res for res in last if res.returncode not in ok_codes]
    detail = "\n".join(
        f"--- rank {res.rank} rc={res.returncode} ---\n"
        f"{res.stdout[-1500:]}\n{res.stderr[-2500:]}" for res in bad)
    raise AssertionError(
        f"multihost run of {os.path.basename(script)} failed "
        f"(want rc in {tuple(ok_codes)}):\n{detail}")


def poll_until(fn, timeout: float = 30.0, interval: float = 0.05,
               desc: str = "condition"):
    """Deadline-poll `fn` until it returns a truthy value (returned) —
    the deflaked alternative to fixed sleeps for cross-process
    assertions (membership convergence, fleet resize, port liveness)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"timed out after {timeout}s waiting for {desc}")


def spawn_launcher(launch_args: Sequence[str],
                   extra_env: Optional[Dict[str, str]] = None
                   ) -> subprocess.Popen:
    """Spawn `python -m paddle_tpu.distributed.launch <args>` under the
    clean CPU env — the two-NODE exercises drive one launcher per
    simulated node (each owning its local worker set), exactly the
    production shape."""
    env = clean_cpu_env(**(extra_env or {}))
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch"]
        + list(launch_args),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=_REPO, env=env)


__all__ = ["run_multihost", "worker_env", "clean_cpu_env", "free_port",
           "poll_until", "spawn_launcher", "WorkerResult", "RankProc",
           "wait_ranks"]
