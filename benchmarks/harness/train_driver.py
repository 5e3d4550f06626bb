"""The training cells: `bench.build_train_step`'s program, fed a ring of
seeded batches, measured over whole steps.

The window starts drained and ends at the host read of the last step's
loss. Inside it the host stays at most RUN_AHEAD steps ahead of the
device — it waits for the loss of two steps back before it dispatches the
next, as a training loop with asynchronous logging does — so the window
ends within a step of --seconds and no queue of dispatched work outlives
it. Losses of the steps between are fetched after the window.

What differs by architecture comes from the configuration's file: the
reference (`reference`, loaded by cells.resolve), the vocabulary the ring
draws its ids from (`architecture.vocab_size`, the vocabulary HELD) and
the first loss's tolerance with its reason (`train.check`).
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from . import common, trace_reduce

RUN_AHEAD = 2
WARM_STEPS = 3


def make_ring(seed: int, vocab: int, ring: int, batch: int, seq: int):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (ring, batch, seq), dtype=np.int64)
    return [(x, np.roll(x, -1, axis=1)) for x in ids]


def drained_step(step, batch) -> tuple:
    t0 = time.monotonic()
    loss = float(step(*batch).numpy())
    return loss, time.monotonic() - t0


def run_steps(step, ring, start: int, seconds: float, step_est: float):
    """Dispatch whole steps for about `seconds` -> (losses as device
    values, elapsed seconds at the read of the last loss)."""
    losses = []
    t0 = time.monotonic()
    while True:
        losses.append(step(*ring[(start + len(losses)) % len(ring)]))
        if len(losses) > RUN_AHEAD:
            losses[-1 - RUN_AHEAD].numpy()
        # the steps in flight end about RUN_AHEAD steps from now; would
        # one more overrun the window?
        if time.monotonic() - t0 + (RUN_AHEAD + 1) * step_est > seconds:
            break
    losses[-1].numpy()
    return losses, time.monotonic() - t0


def run(res: dict, seed: int, seconds: float, trace: bool,
        t_proc0: float, devices, trace_dir: str) -> dict:
    """t_proc0: time.monotonic() when the process started; set-up is
    counted from it."""
    import bench
    from paddle_tpu.core import compile_cache as cc

    cfg, traffic = res["config"]["train"], res["traffic"]
    batch, seq = int(traffic["batch"]), int(traffic["seq"])
    vocab = int(res["config"]["architecture"]["vocab_size"])
    loss_rtol = float(cfg["check"]["loss_rtol"])
    mesh = None
    if cfg.get("mesh"):
        mesh = bench.dp_tp_mesh(devices, tp=int(cfg["mesh"]["tp"]))
    with contextlib.ExitStack() as stack:
        if mesh is not None:
            stack.enter_context(mesh)
        with common.seeded_weights(seed):
            step, _, _, n_params = bench.build_train_step(
                cfg["preset"], batch, seq, mesh=mesh)
        build_s = time.monotonic() - t_proc0
        ring = make_ring(seed, vocab, int(traffic["ring"]), batch, seq)

        # correctness, before any update: the reference on the system's
        # own weights and the first batch, then the step's first loss
        t0 = time.monotonic()
        ref_loss = res["reference"].train_loss(
            step.state()[0], *ring[0], res["config"])
        ref_s = time.monotonic() - t0
        first_loss, first_s = drained_step(step, ring[0])
        rel = abs(first_loss - ref_loss) / abs(ref_loss)
        warm = [drained_step(step, ring[i % len(ring)])
                for i in range(1, 1 + WARM_STEPS)]
        step_est = min(s for _, s in warm)
        temp_bytes = int(step.compiled_memory_report(
            *ring[0])["temp_size_in_bytes"])
        common.log(phase="setup", build_s=build_s, reference_s=ref_s,
                   reference_loss=ref_loss, first_loss=first_loss,
                   loss_rel_diff=rel, first_step_s=first_s,
                   warm_step_s=[s for _, s in warm],
                   program_temp_bytes=temp_bytes, n_params=n_params,
                   compile_report=step.compile_report,
                   cache=cc.stats())

        setup_misses = int(cc.stats()["misses"])
        start = 1 + WARM_STEPS
        lookups0 = common.lookups(cc)
        setup_s = time.monotonic() - t_proc0
        # ---- the window ----
        budget = seconds if not trace else seconds * 0.5
        losses, elapsed = run_steps(step, ring, start, budget, step_est)
        n_async = len(losses)
        drained, drained_losses, traced, reduced = [], [], [], None
        if trace:
            t_b = time.monotonic()
            while time.monotonic() - t_b < seconds * 0.25 or len(drained) < 3:
                loss, s = drained_step(
                    step, ring[(start + n_async + len(drained)) % len(ring)])
                drained_losses.append(loss)
                drained.append(s * 1e3)
            import jax

            common.start_profile(trace_dir)
            try:
                traced, _ = run_steps(
                    step, ring, start + n_async + len(drained),
                    (RUN_AHEAD + 3.5) * step_est, step_est)
            finally:
                jax.profiler.stop_trace()
            reduced = trace_reduce.reduce_file(
                trace_reduce.find_xplane(trace_dir))
        in_window_lookups = common.lookups(cc) - lookups0
        # ---- after the window ----
        values = [float(x.numpy()) for x in losses] + drained_losses \
            + [float(x.numpy()) for x in traced]
        # while the train state is still on the device
        device = common.device_dict(devices, temp_bytes)

    tokens_per_s_per_chip = n_async * batch * seq / elapsed / len(devices)
    n_bad = int(sum(1 for v in values if not np.isfinite(v)))
    # each number the verdict rests on, beside its limit
    compared = {"first_loss_rel_diff": (rel, loss_rtol),
                "losses_not_finite": (n_bad, 0),
                "compiles_in_window": (in_window_lookups, 0)}
    common.log(phase="window", steps=len(values), async_steps=n_async,
               elapsed_s=elapsed, first_losses=values[:3],
               last_loss=values[-1], in_window_lookups=in_window_lookups,
               compared=compared)
    return {
        "correct": common.verdict(compared), "compared": compared,
        "attempted": len(values), "failed": n_bad,
        "setup_s": setup_s, "window_s": elapsed,
        "device": device,
        "end_to_end": {"train_tokens_per_s_per_chip": tokens_per_s_per_chip},
        "run": {
            "setup": {"build_s": build_s, "cache_misses": setup_misses},
            "train": {"tokens_per_s_per_chip": tokens_per_s_per_chip,
                      "n_params": n_params, "step_ms": drained,
                      "tokens_per_step": batch * seq},
            "trace": reduced, "spans": [],
        },
    }
