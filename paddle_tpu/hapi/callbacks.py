"""hapi callbacks (analog of python/paddle/hapi/callbacks.py)."""
from __future__ import annotations

import time


class Callback:
    def set_model(self, model):
        self.model = model

    def set_params(self, params):
        self.params = params

    def on_train_begin(self, logs=None):
        pass

    def on_train_end(self, logs=None):
        pass

    def on_epoch_begin(self, epoch, logs=None):
        pass

    def on_epoch_end(self, epoch, logs=None):
        pass

    def on_train_batch_begin(self, step, logs=None):
        pass

    def on_train_batch_end(self, step, logs=None):
        pass

    def on_eval_begin(self, logs=None):
        pass

    def on_eval_end(self, logs=None):
        pass


class CallbackList:
    def __init__(self, callbacks):
        self.callbacks = list(callbacks)

    def set_model(self, model):
        for c in self.callbacks:
            c.set_model(model)

    def __getattr__(self, name):
        if name.startswith("on_"):
            def call(*args, **kwargs):
                for c in self.callbacks:
                    getattr(c, name)(*args, **kwargs)

            return call
        raise AttributeError(name)


class ProgBarLogger(Callback):
    def __init__(self, log_freq=10, verbose=2):
        self.log_freq = log_freq
        self.verbose = verbose

    def on_epoch_begin(self, epoch, logs=None):
        self._epoch = epoch
        self._start = time.perf_counter()

    def on_train_batch_end(self, step, logs=None):
        if self.verbose and step % self.log_freq == 0:
            print(f"epoch {self._epoch} step {step}: "
                  f"loss {logs.get('loss', 0):.4f}")

    def on_epoch_end(self, epoch, logs=None):
        if self.verbose:
            print(f"epoch {epoch} done in "
                  f"{time.perf_counter() - self._start:.1f}s "
                  f"loss {logs.get('loss', 0):.4f}")


class ModelCheckpoint(Callback):
    def __init__(self, save_freq=1, save_dir=None):
        self.save_freq = save_freq
        self.save_dir = save_dir

    def on_epoch_end(self, epoch, logs=None):
        if self.save_dir and epoch % self.save_freq == 0:
            self.model.save(f"{self.save_dir}/{epoch}")


class EarlyStopping(Callback):
    def __init__(self, monitor="loss", mode="min", patience=0, min_delta=0,
                 baseline=None, save_best_model=True):
        self.monitor = monitor
        self.mode = mode
        self.patience = patience
        self.min_delta = abs(min_delta)
        self.best = None
        self.wait = 0

    def on_epoch_end(self, epoch, logs=None):
        v = (logs or {}).get(self.monitor)
        if v is None:
            return
        better = (self.best is None or
                  (self.mode == "min" and v < self.best - self.min_delta) or
                  (self.mode == "max" and v > self.best + self.min_delta))
        if better:
            self.best = v
            self.wait = 0
        else:
            self.wait += 1
            if self.wait > self.patience:
                self.model.stop_training = True


class LRScheduler(Callback):
    def __init__(self, by_step=True, by_epoch=False):
        self.by_step = by_step
        self.by_epoch = by_epoch

    def on_train_batch_end(self, step, logs=None):
        if self.by_step:
            sched = getattr(self.model._optimizer, "_lr_scheduler", None)
            if sched is not None:
                sched.step()


class ReduceLROnPlateau(Callback):
    """Reduce optimizer LR when a monitored metric plateaus (reference
    hapi/callbacks.py ReduceLROnPlateau)."""

    def __init__(self, monitor="loss", factor=0.1, patience=10, verbose=1,
                 mode="auto", min_delta=1e-4, cooldown=0, min_lr=0):
        super().__init__()
        self.monitor = monitor
        self.factor = factor
        self.patience = patience
        self.min_delta = min_delta
        self.cooldown = cooldown
        self.min_lr = min_lr
        self.mode = "min" if mode in ("auto", "min") else "max"
        self.best = None
        self.wait = 0
        self.cooldown_counter = 0

    def on_eval_end(self, logs=None):
        self._check(logs)

    def on_epoch_end(self, epoch, logs=None):
        self._check(logs)

    def _check(self, logs):
        logs = logs or {}
        cur = logs.get(self.monitor)
        if cur is None:
            return
        cur = float(cur[0] if isinstance(cur, (list, tuple)) else cur)
        if self.cooldown_counter > 0:
            self.cooldown_counter -= 1
            self.wait = 0
        better = (self.best is None
                  or (self.mode == "min" and cur < self.best - self.min_delta)
                  or (self.mode == "max" and cur > self.best + self.min_delta))
        if better:
            self.best = cur
            self.wait = 0
            return
        self.wait += 1
        if self.wait >= self.patience:
            opt = getattr(self.model, "_optimizer", None)
            if opt is not None and not callable(
                    getattr(opt, "_learning_rate", None)):
                try:
                    new_lr = max(opt.get_lr() * self.factor, self.min_lr)
                    opt.set_lr(new_lr)
                except RuntimeError:
                    pass  # scheduler-driven LR: scheduler owns it
            self.cooldown_counter = self.cooldown
            self.wait = 0


class ProfilerCallback(Callback):
    """Drive a paddle_tpu.profiler.Profiler through a hapi fit loop:
    start on train begin, mark a profiler step per batch, stop and print
    the statistics summary (per-op/per-layer/step/memory tables) at train
    end. Analog of the reference hapi Profiler callback wiring.

    Pass an existing Profiler, or kwargs for a new one (defaults:
    timer_only=True so no device trace is written, profile_memory=True,
    with_flops=True).
    """

    def __init__(self, profiler=None, print_summary=True, **profiler_kwargs):
        from .. import profiler as prof_mod

        if profiler is None:
            profiler_kwargs.setdefault("timer_only", True)
            profiler_kwargs.setdefault("profile_memory", True)
            profiler_kwargs.setdefault("with_flops", True)
            profiler = prof_mod.Profiler(**profiler_kwargs)
        self.profiler = profiler
        self.print_summary = print_summary
        self.last_summary = None

    def on_train_begin(self, logs=None):
        self.profiler.start()

    def on_train_batch_end(self, step, logs=None):
        self.profiler.step()

    def on_train_end(self, logs=None):
        self.profiler.stop()
        if self.print_summary:
            self.last_summary = self.profiler.summary()


class TelemetryCallback(Callback):
    """Feed the run-wide metrics bus (observability.bus) from a fit
    loop: one row per train step carrying loss, step time, MFU, input-
    pipeline queue depth/starvation and checkpoint stall — the per-step
    time series the profiler's aggregate tables never had. With
    ``FLAGS_metrics_dir`` set the series lands as
    ``<dir>/metrics.jsonl`` plus a Prometheus textfile
    (``<dir>/metrics.prom``) rewritten every `flush_every` steps, so a
    *training* run exposes the same metrics surface the serving tier
    serves at ``/metrics``. ``Model.fit`` installs this automatically
    when FLAGS_metrics_dir is set.

    MFU comes from an internally-driven ``timer_only`` Profiler; if a
    profiler session is already recording (e.g. ProfilerCallback), this
    callback rides it instead of starting a second one (the host event
    buffer is process-global): it reads the owner's step records without
    stepping or stopping the owner's profiler, and reports MFU only for
    batches where a fresh step record landed."""

    def __init__(self, flush_every: int = 50):
        from .. import profiler as prof_mod

        self._prof_mod = prof_mod
        self.flush_every = max(1, int(flush_every))
        self._prof = None
        self._owns_prof = False
        self._started = False
        self._seen_records = 0
        self._t_last = None
        self._rows = 0

    def on_train_begin(self, logs=None):
        self._started = False
        self._t_last = time.perf_counter()

    def on_train_batch_begin(self, step, logs=None):
        if self._started:
            return
        # decide ride-vs-own HERE, not in on_train_begin: by the first
        # batch every callback's on_train_begin has run, so a
        # ProfilerCallback is detected regardless of list order (the
        # host event buffer is process-global — starting a second
        # profiler would clear it and double-step the records)
        self._started = True
        if self._prof_mod._enabled:
            from ..profiler import stats as _stats

            sess = _stats.active()
            self._prof = getattr(sess, "profiler", None)
            self._owns_prof = False
        else:
            self._prof = self._prof_mod.Profiler(timer_only=True,
                                                 with_flops=True)
            self._prof.start()
            self._owns_prof = True
        self._seen_records = len(getattr(self._prof, "step_records", []))

    def _sections(self):
        """Pipeline + fault-tolerance scalars, best-effort (both
        providers return None until anything moved)."""
        out = {}
        try:
            from ..io.pipeline import metrics as pipe_metrics

            snap = pipe_metrics.summary_snapshot() or {}
            out["queue_depth"] = snap.get("host_queue_depth", 0) + \
                snap.get("device_queue_depth", 0)
            out["starvation_fraction"] = snap.get("starvation_fraction",
                                                  0.0)
        except Exception:  # noqa: BLE001 — telemetry must not fail a step
            pass
        try:
            from ..distributed import fault_tolerance as ft

            snap = ft.summary_snapshot() or {}
            out["ckpt_stall_s"] = snap.get("ckpt_stall_s", 0.0)
            out["bad_steps"] = snap.get("bad_steps", 0)
        except Exception:  # noqa: BLE001
            pass
        return out

    def on_train_batch_end(self, step, logs=None):
        from ..observability import bus

        now = time.perf_counter()
        dt_ms = (now - self._t_last) * 1e3 if self._t_last is not None \
            else 0.0
        self._t_last = now
        row = {"step": step, "step_time_ms": round(dt_ms, 3),
               "mfu": 0.0, "flops": 0}
        logs = logs or {}
        if "loss" in logs:
            try:
                row["loss"] = float(logs["loss"])
            except (TypeError, ValueError):
                pass
        if "epoch" in logs:
            row["epoch"] = logs["epoch"]
        prof = self._prof
        if prof is not None:
            if self._owns_prof:
                prof.step()
            # use the newest step record only if one LANDED since the
            # last batch (a ridden profiler is stepped by its owner —
            # ProfilerCallback runs earlier in the list; if the owner
            # doesn't step per batch, stale MFU must not be re-reported)
            recs = getattr(prof, "step_records", [])
            if len(recs) > self._seen_records:
                rec = recs[-1]
                row["mfu"] = rec["mfu"]
                row["flops"] = rec["flops"]
                row["step_time_ms"] = round(rec["time_ms"], 3)
            self._seen_records = len(recs)
        row.update(self._sections())
        bus.record_step(**row)
        self._rows += 1
        if self._rows % self.flush_every == 0:
            bus.flush()

    def on_train_end(self, logs=None):
        from ..observability import bus

        if self._owns_prof and self._prof is not None:
            self._prof.stop()
            self._owns_prof = False
        bus.flush()


class VisualDL(Callback):
    """VisualDL scalar logging (reference hapi/callbacks.py VisualDL);
    requires the visualdl package — raises with guidance if absent."""

    def __init__(self, log_dir):
        super().__init__()
        try:
            from visualdl import LogWriter
        except ImportError as e:
            raise ImportError(
                "VisualDL callback needs the `visualdl` package "
                "(not bundled in this image)") from e
        self.writer = LogWriter(log_dir)
        self._step = 0

    def on_train_batch_end(self, step, logs=None):
        for k, v in (logs or {}).items():
            try:
                self.writer.add_scalar(f"train/{k}", float(
                    v[0] if isinstance(v, (list, tuple)) else v),
                    self._step)
            except (TypeError, ValueError):
                continue
        self._step += 1


class WandbCallback(Callback):
    """Weights&Biases logging (reference hapi/callbacks.py
    WandbCallback); requires the wandb package."""

    def __init__(self, project=None, **kwargs):
        super().__init__()
        try:
            import wandb
        except ImportError as e:
            raise ImportError(
                "WandbCallback needs the `wandb` package "
                "(not bundled in this image)") from e
        self.run = wandb.init(project=project, **kwargs)

    def on_train_batch_end(self, step, logs=None):
        clean = {}
        for k, v in (logs or {}).items():
            try:
                clean[k] = float(v[0] if isinstance(v, (list, tuple))
                                 else v)
            except (TypeError, ValueError):
                continue
        self.run.log(clean)
