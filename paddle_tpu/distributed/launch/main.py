"""Launch CLI (analog of python/paddle/distributed/launch/main.py:18).

    python -m paddle_tpu.distributed.launch [--nnodes N] [--node_rank R]
        [--master host:port] [--nproc_per_node P] train.py [args...]

TPU-native process model: ONE controller process per host drives all local
chips (the reference forks one proc per GPU; XLA's single-controller model
makes that per-device fork unnecessary). The launcher only PUBLISHES the
PADDLE_TRAINER_* env contract (build_env_matrix); the master port itself
belongs to trainer rank 0 — it binds the rendezvous for whichever stack
it runs (jax.distributed's coordination service via
mesh_runtime.initialize, or the rpc/elastic TCPStore), so the launcher
must not hold a socket there (reference launch/controllers/collective.py
+ controllers/master.py).

--elastic_level / --max_restart enable the elastic supervisor
(paddle_tpu.distributed.elastic): the trainer is restarted on failure with
refreshed membership. A trainer exiting EXIT_PREEMPTED (17 — the
fault-tolerance supervisor's "checkpointed after SIGTERM, relaunch me")
is ALWAYS relaunched and never counts toward --max_restart: preemption
is the platform reclaiming capacity, not the job crashing.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

# keep in sync with distributed.fault_tolerance.EXIT_PREEMPTED (the
# launcher stays import-light: no jax / framework imports before fork)
EXIT_PREEMPTED = 17


def build_parser():
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER", ""))
    p.add_argument("--node_ips", type=str,
                   default=os.environ.get("PADDLE_NODE_IPS", ""),
                   help="comma list of every node's address (one per "
                        "--nnodes, node_rank order) for the endpoint "
                        "list; default derives all endpoints from the "
                        "master host (single-host legacy)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="controller processes per host (1 drives all chips)")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--max_restart", type=int, default=0)
    p.add_argument("--elastic_level", type=int, default=0)
    p.add_argument("--resize_file", type=str,
                   default=os.environ.get("PADDLE_RESIZE_FILE", ""),
                   help="elastic resize channel: a JSON file "
                        "({'nproc_per_node': N}) the trainer (autoscale."
                        "WorldAutoscaler) writes before exiting "
                        "EXIT_PREEMPTED; every relaunch re-reads it and "
                        "spawns that many local processes, so a resize "
                        "is just a preemption with a new world size")
    p.add_argument("--devices", type=str, default=None)
    p.add_argument("--fleet", action="store_true",
                   help="serving-fleet process model: local workers are "
                        "INDEPENDENT hosts, not one collective — a "
                        "crashed worker is relaunched ALONE (the other "
                        "local hosts keep serving; --max_restart still "
                        "bounds it), a worker exiting 0 is done, and "
                        "EXIT_PREEMPTED from ANY worker relaunches the "
                        "node's whole set after re-reading --resize_file "
                        "(fleet grow/shrink = a preemption with a new "
                        "host count, exactly the training resize "
                        "contract)")
    p.add_argument("--store_endpoints", type=str,
                   default=os.environ.get("PADDLE_STORE_ENDPOINTS", ""),
                   help="elastic/registry store endpoints published to "
                        "workers as FABRIC_STORE: one host:port for a "
                        "single TCPStore, a comma list mounts a "
                        "QuorumStore over the members — the --fleet "
                        "control plane survives losing a registry "
                        "host (store.make_store consumes the spec)")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p


def _terminate_all(procs, grace=10.0):
    """SIGTERM, then SIGKILL after a grace period (a trainer ignoring
    SIGTERM must not hang the launcher)."""
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.monotonic() + grace
    for p in procs:
        while p.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def build_env_matrix(ns):
    """The multi-host env contract this node emits: one dict per LOCAL
    rank, each carrying the global identity (PADDLE_TRAINER_ID over
    nnodes x nproc_per_node), the node coordinates
    (PADDLE_NNODES/PADDLE_NODE_RANK/PADDLE_LOCAL_RANK/PADDLE_LOCAL_SIZE)
    and the rendezvous (PADDLE_MASTER — what
    mesh_runtime.initialize/init_parallel_env consume). Pure function
    of the parsed args, unit-testable without forking anything."""
    master = ns.master or "127.0.0.1:49170"
    host, _, port = master.partition(":")
    nproc = max(1, ns.nproc_per_node)
    if not (0 <= ns.node_rank < ns.nnodes):
        raise ValueError(
            f"--node_rank {ns.node_rank} outside [0, {ns.nnodes})")
    world = ns.nnodes * nproc
    if ns.node_ips:
        ips = [s.strip() for s in ns.node_ips.split(",") if s.strip()]
        if len(ips) != ns.nnodes:
            raise ValueError(
                f"--node_ips lists {len(ips)} hosts for --nnodes "
                f"{ns.nnodes}")
        endpoints = ",".join(f"{ips[n]}:{int(port) + lr}"
                             for n in range(ns.nnodes)
                             for lr in range(nproc))
    else:
        endpoints = ",".join(f"{host}:{int(port) + i}"
                             for i in range(world))
    base = {
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_NNODES": str(ns.nnodes),
        "PADDLE_NODE_RANK": str(ns.node_rank),
        "PADDLE_LOCAL_SIZE": str(nproc),
        "PADDLE_MASTER": master,
        "PADDLE_JOB_ID": ns.job_id,
        "PADDLE_TRAINER_ENDPOINTS": endpoints,
    }
    out = []
    for lr in range(nproc):
        env = dict(base)
        env["PADDLE_TRAINER_ID"] = str(ns.node_rank * nproc + lr)
        env["PADDLE_LOCAL_RANK"] = str(lr)
        out.append(env)
    return out


def _monitor_fleet(procs, spawn, max_restart, restarts):
    """--fleet monitor: workers are independent serving hosts.

    Per-worker semantics (vs the collective monitor's first-failure-
    kills-all): exit 0 = done (not respawned); a crash relaunches JUST
    that worker while the others keep serving, bounded by the shared
    --max_restart budget; EXIT_PREEMPTED from ANY worker gracefully
    stops the node set and reports it for a whole-set relaunch (the
    resize path — the relauncher re-reads --resize_file first).

    Returns (code, restarts): code 0 = all workers finished,
    EXIT_PREEMPTED = relaunch the set, anything else = budget
    exhausted on a crash loop."""
    pending = dict(enumerate(procs))
    while pending:
        time.sleep(0.2)
        for lr, p in list(pending.items()):
            rc = p.poll()
            if rc is None:
                continue
            if rc == 0:
                del pending[lr]
            elif rc == EXIT_PREEMPTED:
                _terminate_all(list(pending.values()))
                for q in pending.values():
                    q.wait()
                return EXIT_PREEMPTED, restarts
            else:
                restarts += 1
                if restarts > max_restart:
                    _terminate_all(list(pending.values()))
                    for q in pending.values():
                        q.wait()
                    return rc, restarts
                replacement = spawn(lr)
                procs.append(replacement)  # _terminate_all visibility
                pending[lr] = replacement
    return 0, restarts


def _read_resize_nproc(path):
    """Desired nproc_per_node from the autoscale resize file (written by
    autoscale.write_resize_file — keep the schema in sync; the launcher
    stays import-light so the reader is duplicated here), or None."""
    import json

    try:
        with open(path) as f:
            n = int(json.load(f)["nproc_per_node"])
        return n if n >= 1 else None
    except (OSError, ValueError, KeyError, TypeError):
        return None


def launch(args=None):
    ns = build_parser().parse_args(args)
    # NOTE: no launcher-side store here. Trainer rank 0 binds the
    # PADDLE_MASTER port itself (jax coordination service under
    # mesh_runtime, or the rpc/elastic TCPStore) — a launcher socket on
    # that port would EADDRINUSE the world's rendezvous on node 0.

    restarts = 0
    incarnation = 0
    while True:
        # the env contract is rebuilt EVERY RELAUNCH: an elastic resize
        # (trainer exited EXIT_PREEMPTED after writing the resize file)
        # changes the world size between incarnations. The FIRST launch
        # honors --nproc_per_node verbatim — a stale file left by a
        # previous job must not silently shrink a fresh one.
        if ns.resize_file and incarnation > 0:
            desired = _read_resize_nproc(ns.resize_file)
            if desired is not None and desired != ns.nproc_per_node:
                ns.nproc_per_node = desired
        incarnation += 1
        nproc = max(1, ns.nproc_per_node)
        env_matrix = build_env_matrix(ns)

        def trainer_env(local_rank):
            env = dict(os.environ)
            env.update(env_matrix[local_rank])
            if ns.resize_file:
                env["PADDLE_RESIZE_FILE"] = ns.resize_file
            if ns.store_endpoints:
                # the registry spec rides both names: FABRIC_STORE for
                # serving-host workers, PADDLE_STORE_ENDPOINTS for
                # trainers mounting the elastic store themselves
                env["FABRIC_STORE"] = ns.store_endpoints
                env["PADDLE_STORE_ENDPOINTS"] = ns.store_endpoints
            return env

        procs, logs = [], []

        def spawn(lr):
            cmd = [sys.executable, "-u", ns.training_script] + \
                ns.training_script_args
            logf = None
            if ns.log_dir:
                os.makedirs(ns.log_dir, exist_ok=True)
                logf = open(os.path.join(
                    ns.log_dir,
                    f"worker.{ns.node_rank * nproc + lr}.log"), "ab")
            logs.append(logf)
            return subprocess.Popen(cmd, env=trainer_env(lr),
                                    stdout=logf, stderr=logf)

        for lr in range(nproc):
            procs.append(spawn(lr))
        bad = 0
        try:
            if ns.fleet:
                bad, restarts = _monitor_fleet(procs, spawn,
                                               ns.max_restart, restarts)
            else:
                # collective monitor: the FIRST failure kills the
                # remaining trainers (reference collective controller
                # semantics) — a sequential wait would deadlock when
                # rank k crashes while rank j blocks in rendezvous
                # waiting for it
                pending = list(procs)
                while pending and bad == 0:
                    time.sleep(0.2)
                    still = []
                    for p in pending:
                        rc = p.poll()
                        if rc is None:
                            still.append(p)
                        elif rc != 0:
                            bad = rc
                    pending = still
                if bad != 0:
                    _terminate_all(procs)
                for p in procs:
                    p.wait()
        except KeyboardInterrupt:
            _terminate_all(procs)
            for p in procs:
                p.wait()
            break
        finally:
            for lf in logs:
                if lf:
                    lf.close()
        if bad == 0:
            break
        if ns.fleet and bad not in (0, EXIT_PREEMPTED):
            return bad  # fleet restart budget exhausted
        if bad == EXIT_PREEMPTED:
            # graceful preemption: state is checkpointed — relaunch
            # without burning restart budget (a preempt-heavy fleet
            # would otherwise exhaust --max_restart without one crash)
            time.sleep(0.5)
            continue
        restarts += 1
        if restarts > ns.max_restart:
            return bad
        time.sleep(2)
    return 0


def hard_exit(code: int) -> None:
    """Exit without waiting on stray non-daemon threads (a jax backend's
    own among them), which would otherwise keep the launcher alive after
    its child has finished."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    hard_exit(launch())
