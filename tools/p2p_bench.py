"""p2p transport microbenchmark: pickle-over-TCP (rpc agent) vs the
shared-memory ring (cpp/shm_channel.cc) for pipeline-sized activation
payloads. Spawns one receiver process; prints MB/s for each path.

    python tools/p2p_bench.py [--mb 4 --iters 50]
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_RECEIVER = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu.distributed.rpc as rpc

rpc.init_rpc("rx", rank=1, world_size=2, master_endpoint="127.0.0.1:{port}")
n = int(sys.argv[1])
for i in range(2 * n + 2):          # warmup + tcp iters + shm iters
    rpc.p2p_recv(f"bench/{{i}}", timeout=120)
rpc.p2p_send("tx", "done", np.zeros(1))
time.sleep(0.5)
rpc.shutdown()
os._exit(0)
"""


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=4.0)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()

    import socket

    import numpy as np

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo
    rx = subprocess.Popen(
        [sys.executable, "-c",
         _RECEIVER.format(repo=repo, port=port), str(args.iters)],
        env=env)

    import paddle_tpu.distributed.rpc as rpc
    from paddle_tpu.distributed.rpc import shm

    rpc.init_rpc("tx", rank=0, world_size=2,
                 master_endpoint=f"127.0.0.1:{port}")
    payload = np.random.RandomState(0).randn(
        int(args.mb * (1 << 20) / 4)).astype("float32")
    idx = 0

    # warmup both paths (handshake + first connects)
    os.environ["PADDLE_P2P_SHM"] = "0"
    shm._LIB_TRIED = False
    rpc.p2p_send("rx", f"bench/{idx}", payload); idx += 1

    t0 = time.perf_counter()
    for _ in range(args.iters):
        rpc.p2p_send("rx", f"bench/{idx}", payload); idx += 1
    tcp_s = time.perf_counter() - t0

    os.environ["PADDLE_P2P_SHM"] = "1"
    shm._LIB_TRIED = False
    shm._LIB = None
    rpc.p2p_send("rx", f"bench/{idx}", payload); idx += 1  # handshake
    t0 = time.perf_counter()
    for _ in range(args.iters):
        rpc.p2p_send("rx", f"bench/{idx}", payload); idx += 1
    shm_s = time.perf_counter() - t0

    rpc.p2p_recv("done", timeout=60)
    total_mb = args.mb * args.iters
    print(f"tcp : {total_mb / tcp_s:9.1f} MB/s  ({tcp_s * 1e3 / args.iters:.2f} ms/msg)")
    print(f"shm : {total_mb / shm_s:9.1f} MB/s  ({shm_s * 1e3 / args.iters:.2f} ms/msg)")
    print(f"speedup: {tcp_s / shm_s:.2f}x")
    rx.wait(timeout=30)
    rpc.shutdown()


if __name__ == "__main__":
    main()
