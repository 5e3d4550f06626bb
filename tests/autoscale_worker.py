"""Elastic-resize worker: one rank of a Supervisor-driven dp training
run whose WORLD changes mid-run through the autoscale path.

Driven by paddle_tpu.testing.multihost. The global device mesh is held
FIXED (total devices = processes x devices_per_proc) while the process
count changes between incarnations — the CPU analog of hosts joining /
leaving an elastic job. Because the global batch math is identical for
any process layout of the same mesh (PR 7's bitwise-dp contract), a
resize-then-resume run must match the uninterrupted run bitwise.

env:
  CKPT_DIR      (required) checkpoint directory shared across phases
  OUT           rank0 final-params npz
  TOTAL         total optimizer steps (default 8)
  GLOBAL_BS     global batch rows (default 8)
  RESIZE_AT     host_step at which the desired world flips (optional)
  DESIRED       desired world (process count) after RESIZE_AT
  RESIZE_FILE   autoscale resize file (launch CLI --resize_file schema)
  CHAOS_RESIZE_KILL  "1": SIGKILL this process on the first checkpoint
                blob write AFTER the resize is armed — proves a kill
                mid-resize-save never corrupts (previous checkpoint
                stays restorable, resume stays bitwise)

Report lines: RESUMED=, RESIZED=, LOSSES=, DONE=.
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.optimizer as opt  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from paddle_tpu.autoscale import WorldAutoscaler  # noqa: E402
from paddle_tpu.distributed import mesh_runtime  # noqa: E402
from paddle_tpu.distributed.fault_tolerance import (  # noqa: E402
    EXIT_PREEMPTED, RestartRequired, Supervisor)
from paddle_tpu.jit import TrainStep  # noqa: E402
from paddle_tpu.testing import chaos  # noqa: E402


def main():
    ckpt_dir = os.environ["CKPT_DIR"]
    out = os.environ.get("OUT")
    total = int(os.environ.get("TOTAL", "8"))
    global_bs = int(os.environ.get("GLOBAL_BS", "8"))
    resize_at = os.environ.get("RESIZE_AT")
    desired = os.environ.get("DESIRED")
    resize_file = os.environ.get("RESIZE_FILE")

    rt = mesh_runtime.initialize({"dp": -1})
    per = rt.local_batch_rows(global_bs)
    world = jax.process_count()
    rank = rt.rank

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4))
    o = opt.AdamW(1e-2, parameters=model.parameters())
    lossf = nn.MSELoss()
    step = TrainStep(model, o, lambda m, x, y: lossf(m(x), y),
                     mesh=rt.mesh, batch_sharding=(P("dp"), P("dp")))

    sup = Supervisor(step, ckpt_dir, save_every=2, keep=3,
                     grace_secs=30.0)
    wa = None
    if resize_at is not None and desired is not None:
        at, want = int(resize_at), int(desired)

        # deterministic, rank-agnostic desired-world source: every rank
        # arms the SAME resize at the SAME boundary, so the collective
        # restart checkpoint is entered together
        def desired_fn():
            return want if step._host_step >= at else None

        wa = WorldAutoscaler(sup, world=world, desired_fn=desired_fn,
                             resize_file=resize_file)

    start = sup.restore()
    print(f"RESUMED={start}", flush=True)

    losses = []
    try:
        for i in range(start, total):
            rng = np.random.RandomState(7000 + i)
            x = rng.randn(global_bs, 16).astype("float32")
            y = rng.randn(global_bs, 4).astype("float32")
            off = rank * per
            loss = sup.step(x[off:off + per], y[off:off + per])
            losses.append(float(loss.numpy()))
            if wa is not None and wa.maybe_resize():
                print("RESIZED=1", flush=True)
                if os.environ.get("CHAOS_RESIZE_KILL") == "1":
                    # die on the next checkpoint blob write — i.e. in
                    # the MIDDLE of the resize checkpoint the next
                    # sup.step() is about to take
                    chaos.add_rule("ckpt.write", "kill_after", "1")
    except RestartRequired:
        # state is checkpointed; the relauncher brings up the new world
        sys.exit(EXIT_PREEMPTED)

    print("LOSSES=" + json.dumps(losses), flush=True)
    if out and rank == 0:
        np.savez(out, **{n: np.asarray(jax.device_get(v))
                         for n, v in step._params.items()})
    sup.close()  # flush pending async checkpoint writes
    print(f"DONE={step._host_step}", flush=True)


if __name__ == "__main__":
    main()
    # hard exit: backend threads must not abort interpreter
    # teardown after the work is done (same pattern as launch.hard_exit)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
