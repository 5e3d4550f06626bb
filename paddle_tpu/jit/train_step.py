"""Compiled train step — the TPU answer to per-op eager training.

One `jax.jit` program fuses forward + backward + optimizer update with buffer
donation (params/opt-state update in place in HBM). This is what the
reference approximates with 229k LoC of executor machinery + fused CUDA
optimizer kernels (SURVEY.md §7: "this is where TPU wins").

Sharded training: pass `mesh` + `shard_fn(name, array) -> PartitionSpec`;
parameters are device_put onto the mesh before compilation and GSPMD inserts
the collectives (DP gradient all-reduce becomes reduce-scatter/all-gather
chosen by XLA over ICI).
"""
from __future__ import annotations

import sys
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..core import rng as _rng
from ..core.tensor import Tensor
from .functional import functional_call, swap_state
from ..core import state as _st
from .. import profiler as _prof
from ..observability import trace as _tracer
from ..testing import chaos as _chaos


def _device_budget(mesh):
    """(bytes of memory on the step's least roomy device, its row of
    published peaks), or None where either is unknown — the CPU, a device
    that is not in the table: no remat plan is made there."""
    from ..profiler.stats.flops import device_peaks

    devices = [d for d in (jax.local_devices()[:1] if mesh is None
                           else mesh.devices.flat)
               if d.process_index == jax.process_index()]
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    try:
        return (min(limits), device_peaks(devices[0].device_kind)) \
            if limits and all(limits) else None
    except LookupError:
        return None


def _need_bytes(mem) -> int:
    """What a compiled program holds on one device while it runs, from
    its memory_analysis(): donated arguments are counted once."""
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes
               + mem.generated_code_size_in_bytes)


def _mp_put(value, sharding, full: bool = True):
    """device_put that also works when `sharding` spans multiple processes
    (launch-CLI multi-host training). Canonical implementation lives in
    distributed.mesh_runtime.placement.put_global (lazy import: the
    distributed package pulls in nn layers)."""
    from ..distributed.mesh_runtime.placement import put_global

    return put_global(value, sharding, full=full)


class TrainStep:
    """train_step = TrainStep(model, opt, loss_fn); loss = train_step(*batch).

    loss_fn(model, *batch) -> scalar loss Tensor. If None, the model itself
    must return the loss. Batch elements may be Tensors or arrays.

    A model whose scanned block names values worth keeping for the
    backward offers `remat_candidates(ids_shape, mesh, param_specs,
    batch_spec, peaks)` and reads `remat_save` when it is traced
    (models/gpt.py): before its first compile the step fits a plan to the
    device's memory by the compiler's own report (`jit/remat_plan.py`,
    `_plan_remat`) and says what it kept in `compile_report` /
    `compiled_memory_report()` under `remat_saved`.
    """

    def __init__(self, model, optimizer, loss_fn: Optional[Callable] = None,
                 mesh=None, shard_fn=None, batch_sharding=None,
                 donate: bool = True, zero_stage: int = 0,
                 dp_axis: str = "dp", accumulate_steps: int = 1,
                 param_sync_every: int = 0,
                 skip_bad_steps: Optional[bool] = None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        self._step_fn = None
        self._donate = donate
        # graceful numeric degradation (FLAGS_skip_nan_steps / the fault-
        # tolerance supervisor): the compiled step keeps the previous
        # params/buffers/opt-state when loss or grads are non-finite —
        # the bad update is SKIPPED in-program and counted on the host
        # instead of raising. Settable as an attribute until first call.
        if skip_bad_steps is None:
            from ..core.flags import flag as _flag

            skip_bad_steps = bool(_flag("skip_nan_steps"))
        self.skip_bad_steps = bool(skip_bad_steps)
        # bad_step_count = optimizer updates actually SKIPPED;
        # bad_micro_count = poisoned micro-batches dropped from the
        # accumulator while their window's update still applied
        self.bad_step_count = 0
        self.bad_micro_count = 0
        self.last_step_finite = True
        # per-micro finite flags held as DEVICE scalars until the apply
        # boundary (whose own sync makes bool() free) — consulting them
        # per micro-call would block the async-dispatch pipeline
        self._pending_mfinite = []
        if zero_stage == 0:
            # honor the reference group_sharded_parallel API (reference
            # python/paddle/distributed/sharding/group_sharded.py): the
            # wrapper records the requested stage on model/optimizer and
            # the compiled step is where it takes effect
            zero_stage = int(getattr(model, "_zero_stage", 0) or
                             getattr(optimizer, "_zero_stage", 0) or 0)
        if zero_stage and mesh is None:
            raise ValueError(
                f"ZeRO stage {zero_stage} requested (via zero_stage= or "
                f"group_sharded_parallel) but no mesh was given; pass "
                f"mesh= (e.g. fleet's hybrid mesh) so the dp axis exists "
                f"to shard optimizer state/gradients over")
        self._zero_stage = zero_stage
        self._dp_axis = dp_axis
        # gradient accumulation (paddle gradient_merge semantics: the
        # optimizer applies the MEAN of k successive batches' grads every
        # k-th call; non-boundary calls only touch the accumulator)
        self._acc_steps = int(accumulate_steps)
        self._acc_fn = None
        self._apply_fn = None
        self._grad_acc = None
        self._micro = 0
        # LocalSGD (reference fleet/meta_optimizers/localsgd_optimizer.py):
        # average parameters across the dp axis every k-th optimizer
        # update. In the single-controller GSPMD formulation replicas
        # cannot drift (the dp gradient mean is implicit in the sharded
        # batch), so the periodic average is numerically the identity —
        # but the REAL compiled all-reduce program runs on cadence,
        # which is the structure multi-process deployments sync on.
        self._param_sync_every = int(param_sync_every)
        self._param_sync_fn = None
        self.param_sync_count = 0
        params, buffers = model.functional_state()
        if mesh is not None and shard_fn is None:
            # default sharding: per-parameter PartitionSpec tags set by the
            # TP layers (paddle_tpu.distributed.mp_layers) via _sharding_spec;
            # under ZeRO-3 untagged params fall back to dp-dim sharding
            from jax.sharding import PartitionSpec

            from ..distributed.models_shard import default_shard_fn

            specs = {n: getattr(p, "_sharding_spec", None)
                     for n, p in model.named_parameters()}
            zstage, daxis = zero_stage, dp_axis

            def shard_fn(name, value):  # noqa: F811
                sp = specs.get(name)
                if sp is not None:
                    return sp
                return default_shard_fn(mesh, name, value, zstage,
                                        dp_axis=daxis)

        # frozen params (stop_gradient) ride with buffers: no grad, no update
        trainable_names = {n for n, p in model.named_parameters()
                           if not p.stop_gradient}
        self._frozen = {n: v for n, v in params.items()
                        if n not in trainable_names}
        params = {n: v for n, v in params.items() if n in trainable_names}
        if mesh is not None and shard_fn is not None:
            from jax.sharding import NamedSharding

            params = {
                n: _mp_put(v, NamedSharding(mesh, shard_fn(n, v)))
                for n, v in params.items()
            }
            rep = jax.sharding.PartitionSpec()
            buffers = {n: _mp_put(v, NamedSharding(mesh, rep))
                       for n, v in buffers.items()}
            self._frozen = {n: _mp_put(v, NamedSharding(mesh, rep))
                            for n, v in self._frozen.items()}
        self._params = params
        self._buffers = buffers
        self._opt_state = optimizer.functional_init(params)
        self._batch_sharding = batch_sharding
        self._host_step = 0
        self._fwd_flops = None  # analytic forward FLOPs (profiler)
        # persistent-compilation-cache accounting of the first (compiling)
        # call — {first_call_s, persistent_hits, persistent_misses}; a warm
        # FLAGS_compile_cache_dir shows hits>0 and a fast first call
        self.compile_report = None
        # what the remat plan kept (jit/remat_plan.py::saved_report), once
        # the first call or lowering has made it
        self._remat_saved = None
        # batch-shape signatures already compiled: the donated-program
        # cache guard (compile_cache.suspend_if) costs ~50 µs, so it
        # wraps only calls that can trigger a compile
        self._compiled_sigs = set()

        # declared param shardings — compiled-step outputs are pinned to
        # these so updated params keep their declared layout (replicated
        # under ZeRO-1/2: XLA all-gathers after the sharded update)
        self._param_specs = None
        if mesh is not None:
            from jax.sharding import PartitionSpec

            self._param_specs = {
                n: (shard_fn(n, v) if shard_fn is not None
                    else PartitionSpec())
                for n, v in params.items()}

        # ZeRO-1/2 (reference: dygraph_sharding_optimizer.py:29 optimizer-
        # state partition; group_sharded_stage2.py:46 gradient partition).
        # GSPMD formulation: optimizer moments (stage>=1) and gradients
        # (stage>=2) get their own dp-sharded PartitionSpecs while params
        # stay replicated; XLA then emits reduce-scatter for the grads and
        # all-gather for the updated params instead of a plain all-reduce.
        self._opt_specs = None
        self._grad_specs = None
        if mesh is not None and zero_stage in (1, 2):
            from jax.sharding import NamedSharding, PartitionSpec

            param_specs = {n: (shard_fn(n, v) if shard_fn is not None
                               else PartitionSpec())
                           for n, v in params.items()}

            def zspec(pspec, shape):
                """Shard the largest dp-divisible, not-already-sharded dim."""
                dp = mesh.shape[dp_axis]
                entries = list(pspec) + [None] * (len(shape) - len(pspec))
                for i in sorted(range(len(shape)), key=lambda i: -shape[i]):
                    if entries[i] is None and shape[i] % dp == 0 \
                            and shape[i] >= dp:
                        entries[i] = dp_axis
                        return PartitionSpec(*entries)
                return PartitionSpec(*entries)

            def leaf_spec(n, leaf):
                pspec = param_specs.get(n, PartitionSpec())
                if tuple(leaf.shape) == tuple(params[n].shape):
                    return zspec(pspec, leaf.shape)
                return zspec(PartitionSpec(), leaf.shape)

            (state,) = self._opt_state
            self._opt_specs = ({n: {k: leaf_spec(n, v) for k, v in st.items()}
                                for n, st in state.items()},)
            self._opt_state = ({
                n: {k: _mp_put(
                        v, NamedSharding(mesh, self._opt_specs[0][n][k]))
                    for k, v in st.items()}
                for n, st in state.items()},)
            if zero_stage >= 2:
                self._grad_specs = {
                    n: zspec(param_specs.get(n, PartitionSpec()), v.shape)
                    for n, v in params.items()}

    # ------------------------------------------------------------------
    def _build(self):
        model, optimizer, loss_fn = self.model, self.optimizer, self.loss_fn

        frozen = self._frozen
        mesh = self.mesh
        opt_specs, grad_specs = self._opt_specs, self._grad_specs
        param_specs = self._param_specs
        from jax.sharding import NamedSharding

        from ..core.flags import flag

        check_nan = bool(flag("check_nan_inf"))
        self._check_nan = check_nan
        skip_bad = bool(self.skip_bad_steps)
        self._skip_bad = skip_bad
        need_finite = check_nan or skip_bad

        def keep_if_finite(finite, new_tree, old_tree):
            # skip-bad-steps: a non-finite step keeps the previous state
            # (the old operands are donated inputs — XLA handles the
            # aliasing; the select is a data dependency, not a copy)
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old),
                new_tree, old_tree)

        def grads_of(params, buffers, key, batch):
            def compute_loss(p):
                full = {**p, **frozen}
                # the scope is what the device trace's split into forward,
                # backward and recompute is read by (PERF.md §3)
                with jax.named_scope("train.loss"), \
                        _st.functional_trace(), \
                        swap_state(model, full, buffers) as (_, nb):
                    targs = [Tensor(a) for a in batch]
                    with _rng.rng_key_scope(key):
                        if loss_fn is not None:
                            loss_t = loss_fn(model, *targs)
                        else:
                            loss_t = model(*targs)
                    new_buffers = {n: t._data for n, t in nb.items()}
                loss = loss_t._data if isinstance(loss_t, Tensor) else loss_t
                return jnp.asarray(loss, jnp.float32), new_buffers

            (loss, new_buffers), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(params)
            return loss, new_buffers, grads

        def step(params, buffers, opt_state, lr, step_idx, key, batch):
            loss, new_buffers, grads = grads_of(params, buffers, key, batch)
            if grad_specs is not None:
                # ZeRO-2: dp-sharded grads — XLA lowers the dp gradient
                # reduction to reduce-scatter instead of all-reduce
                grads = {n: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, grad_specs[n]))
                    for n, g in grads.items()}
            elif opt_specs is not None and param_specs is not None:
                # ZeRO-1: pin grads to the PARAM layout so the dp
                # reshard happens at the update boundary, not inside the
                # backward pass. Without this GSPMD propagates the
                # dp-sharded moment layout back into the backward
                # scan-over-layers accumulator; sharding the scan (layer)
                # axis there makes the partitioner emit s32 per-shard
                # bounds checks against the s64 (x64) loop counter — an
                # XLA verifier failure ("compare s64[] vs s32[]").
                grads = {n: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, param_specs[n]))
                    for n, g in grads.items()}
            with jax.named_scope("train.optimizer"):
                new_params, new_opt_state = optimizer.functional_update(
                    params, grads, opt_state, lr=lr, step=step_idx)
            if param_specs is not None:
                new_params = {n: jax.lax.with_sharding_constraint(
                    p, NamedSharding(mesh, param_specs[n]))
                    for n, p in new_params.items()}
            if opt_specs is not None:
                # ZeRO-1: keep the updated moments dp-sharded
                new_opt_state = jax.tree_util.tree_map(
                    lambda x, sp: jax.lax.with_sharding_constraint(
                        x, NamedSharding(mesh, sp)),
                    new_opt_state, opt_specs)
            if need_finite:
                # FLAGS_check_nan_inf on the path that matters: one fused
                # finiteness reduction over loss+grads inside the compiled
                # program (reference checks after every kernel,
                # paddle/fluid/framework/operator.cc:2010; here the whole
                # step is one kernel). Grads are f32-cast first, so the
                # check is AMP-aware: a bf16 overflow is caught post-cast.
                finite = jnp.isfinite(loss) & jnp.all(jnp.stack(
                    [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                     for g in grads.values()]))
            else:
                finite = jnp.asarray(True)
            if skip_bad:
                new_params = keep_if_finite(finite, new_params, params)
                new_buffers = keep_if_finite(finite, new_buffers, buffers)
                new_opt_state = keep_if_finite(finite, new_opt_state,
                                               opt_state)
            return loss, new_params, new_buffers, new_opt_state, finite

        # donation stays on under skip_bad here: XLA aliases through the
        # fused scalar select in the monolithic step program (verified —
        # no "donated buffers were not usable" warning on this path,
        # unlike acc_step/apply_step below where the select defeats
        # aliasing and donation is stripped)
        donate = (0, 1, 2) if self._donate else ()
        self._step_fn = jax.jit(step, donate_argnums=donate)

        if self._acc_steps > 1:
            def acc_step(params, buffers, acc, key, batch):
                loss, new_buffers, grads = grads_of(params, buffers, key,
                                                    batch)
                new_acc = {n: acc[n] + g for n, g in grads.items()}
                if grad_specs is not None:
                    # ZeRO-2: the ACCUMULATOR is the partitioned grad store
                    new_acc = {n: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, grad_specs[n]))
                        for n, g in new_acc.items()}
                elif opt_specs is not None and param_specs is not None:
                    # ZeRO-1: same pin as the monolithic step — the
                    # accumulator must stay in the PARAM layout so a
                    # dp-sharded layout (e.g. riding in on the acc
                    # input arrays) can never propagate into the
                    # backward scan (the s64/s32 partitioner failure)
                    new_acc = {n: jax.lax.with_sharding_constraint(
                        g, NamedSharding(mesh, param_specs[n]))
                        for n, g in new_acc.items()}
                # gated on skip_bad alone: check_nan-only accumulation
                # keeps its boundary-only check (apply_step) — a per-
                # micro reduction nobody consumes would be pure waste
                if skip_bad:
                    mfinite = jnp.isfinite(loss) & jnp.all(jnp.stack(
                        [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                         for g in grads.values()]))
                else:
                    mfinite = jnp.asarray(True)
                if skip_bad:
                    # a poisoned micro-batch must not contaminate the
                    # accumulator: its contribution is dropped whole
                    new_acc = keep_if_finite(mfinite, new_acc, acc)
                    new_buffers = keep_if_finite(mfinite, new_buffers,
                                                 buffers)
                return loss, new_buffers, new_acc, mfinite

            k = float(self._acc_steps)

            def apply_step(params, acc, opt_state, lr, step_idx):
                grads = {n: g / k for n, g in acc.items()}
                with jax.named_scope("train.optimizer"):
                    new_params, new_opt_state = \
                        optimizer.functional_update(
                            params, grads, opt_state, lr=lr, step=step_idx)
                if param_specs is not None:
                    new_params = {n: jax.lax.with_sharding_constraint(
                        p, NamedSharding(mesh, param_specs[n]))
                        for n, p in new_params.items()}
                if opt_specs is not None:
                    new_opt_state = jax.tree_util.tree_map(
                        lambda x, sp: jax.lax.with_sharding_constraint(
                            x, NamedSharding(mesh, sp)),
                        new_opt_state, opt_specs)
                finite = jnp.all(jnp.stack(
                    [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                     for g in grads.values()])) if need_finite else \
                    jnp.asarray(True)
                if skip_bad:
                    new_params = keep_if_finite(finite, new_params, params)
                    new_opt_state = keep_if_finite(finite, new_opt_state,
                                                   opt_state)
                return new_params, new_opt_state, finite

            # under skip-bad-steps the old accumulator feeds the
            # mfinite select, so XLA cannot alias it anyway — donating
            # would only emit "donated buffers were not usable" warnings
            self._acc_fn = jax.jit(
                acc_step,
                donate_argnums=(2,) if self._donate and not skip_bad
                else ())
            # skip-bad-steps feeds params/opt_state into the finite
            # select, so XLA cannot alias them in apply — donate only
            # the accumulator there (params/opt keep one extra copy at
            # the boundary; the per-micro acc_fn dominates memory anyway)
            apply_donate = () if not self._donate else \
                ((1,) if skip_bad else (0, 1, 2))
            self._apply_fn = jax.jit(apply_step,
                                     donate_argnums=apply_donate)

    def _build_param_sync(self):
        """Compiled LocalSGD parameter averaging: pmean over the dp axis
        for every param NOT sharded on it (a dp-sharded leaf — ZeRO-3 —
        holds disjoint slices; averaging those would be wrong, so it
        passes through)."""
        mesh, axis = self.mesh, self._dp_axis
        if mesh is None or axis not in getattr(mesh, "shape", {}) or \
                mesh.shape[axis] <= 1:
            return None
        from jax.sharding import PartitionSpec

        from ..distributed.collective import shard_map

        specs = {n: ((self._param_specs or {}).get(n) or PartitionSpec())
                 for n in self._params}

        def uses_dp(sp):
            flat = []
            for e in sp:
                flat.extend(e if isinstance(e, (tuple, list)) else [e])
            return axis in flat

        def body(params):
            return {n: (v if uses_dp(specs[n])
                        else jax.lax.pmean(v, axis))
                    for n, v in params.items()}

        spec_tree = {n: specs[n] for n in self._params}
        return jax.jit(shard_map(body, mesh, in_specs=(spec_tree,),
                                 out_specs=spec_tree, check=False))

    def _maybe_sync_params(self):
        if self._param_sync_every <= 0 or \
                self._host_step % self._param_sync_every:
            return
        if self._param_sync_fn is None:
            # False (not None) caches the "no dp axis to sync over"
            # verdict so it isn't re-derived every k-th step
            self._param_sync_fn = self._build_param_sync() or False
        if self._param_sync_fn:
            self._params = self._param_sync_fn(self._params)
            self.param_sync_count += 1

    @staticmethod
    def _poison_nan(vals):
        """Chaos `step:nan:K` directive: corrupt the first floating batch
        element (dtype-preserving, so no recompile) — the natural way a
        bad batch/overflow surfaces as a non-finite loss."""
        vals = list(vals)
        for i, v in enumerate(vals):
            if jnp.issubdtype(v.dtype, jnp.floating):
                vals[i] = v * jnp.asarray(float("nan"), v.dtype)
                break
        return tuple(vals)

    def _init_grad_acc(self):
        from jax.sharding import NamedSharding, PartitionSpec

        def zero(n, v):
            z = jnp.zeros(v.shape, jnp.float32)
            if self.mesh is not None:
                spec = (self._grad_specs or {}).get(n, PartitionSpec())
                z = jax.device_put(z, NamedSharding(self.mesh, spec))
            return z

        return {n: zero(n, v) for n, v in self._params.items()}

    # ------------------------------------------------------- profiling --
    def donation_report(self):
        """Buffer-donation metadata of the compiled step: which argument
        groups XLA updates in place in HBM, and their sizes (feeds the
        profiler's memory tracer)."""
        def total(tree):
            return sum(int(getattr(l, "nbytes", 0))
                       for l in jax.tree_util.tree_leaves(tree))

        return {
            "donated": bool(self._donate),
            "donate_argnums": (0, 1, 2) if self._donate else (),
            "params_bytes": total(self._params),
            "buffers_bytes": total(self._buffers),
            "opt_state_bytes": total(self._opt_state),
        }

    def compiled_memory_report(self, *batch):
        """XLA's own accounting of the compiled step — cost analysis
        (flops, bytes accessed) + memory analysis (argument/output/temp
        bytes). Compiles the AOT path; best-effort per backend."""
        out = {}
        try:
            from ..core import compile_cache as _cc

            with _cc.donated_cpu_guard(self._donate):
                compiled = self.lowered(*batch).compile()
        except Exception as e:  # noqa: BLE001
            return {"error": repr(e)}
        out["remat_saved"] = self._remat_saved
        if _tracer.enabled():
            # with the compiled program in hand: which scope each of its
            # instructions was traced under, for readers of a device trace
            _tracer.note_op_scopes(compiled.as_text())
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            if cost:
                for k in ("flops", "bytes accessed"):
                    if k in cost:
                        out[k.replace(" ", "_")] = float(cost[k])
        except Exception:  # noqa: BLE001
            pass
        try:
            mem = compiled.memory_analysis()
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    out[k] = int(v)
        except Exception:  # noqa: BLE001
            pass
        return out

    def _abstract_fwd_flops(self, sess, vals):
        """Forward-pass analytic FLOPs of one step via an abstract
        re-trace (jax.eval_shape): the dispatch hook books traced-op
        FLOPs into sess.trace_flops, and the delta is the program's
        forward count. No compile, no execution."""
        lr = jnp.asarray(0.0, jnp.float32)
        si = jnp.asarray(1, jnp.int32)
        key = jax.random.key(0)
        t0 = sess.trace_flops
        try:
            if self._acc_steps > 1:
                acc = self._grad_acc or self._init_grad_acc()
                jax.eval_shape(self._acc_fn, self._params, self._buffers,
                               acc, key, vals)
            else:
                jax.eval_shape(self._step_fn, self._params, self._buffers,
                               self._opt_state, lr, si, key, vals)
        except Exception:  # noqa: BLE001 — profiling must not fail a step
            return 0
        return sess.trace_flops - t0

    # ------------------------------------------------------------------
    def __call__(self, *batch):
        if not _prof._enabled:
            return self._call_impl(*batch)
        from ..profiler import stats as _stats

        sess = _stats.active()
        trace_mark = sess.trace_flops if sess is not None else 0
        with _prof.RecordEvent("TrainStep.step",
                               _prof.TracerEventType.ProfileStep):
            out = self._call_impl(*batch)
        if sess is not None:
            if sess.profile_memory and sess.memory.donation is None:
                sess.memory.note_donation(self.donation_report())
            if sess.with_flops:
                traced = sess.trace_flops - trace_mark
                if traced > 0:
                    # this call traced/compiled the program: its trace IS
                    # the forward count
                    self._fwd_flops = traced
                fwd = self._fwd_flops
                if fwd is None:
                    vals = tuple(b._data if isinstance(b, Tensor)
                                 else jnp.asarray(b) for b in batch)
                    fwd = self._abstract_fwd_flops(sess, vals)
                    if fwd > 0:
                        # cache only a successful count — a transient
                        # eval_shape failure must not pin FLOPs to 0 for
                        # the rest of the profile window
                        self._fwd_flops = fwd
                # fwd + ~2x bwd: standard training-step accounting
                sess.add_step_flops(3 * fwd)
        return out

    def _call_impl(self, *batch):
        if self._remat_saved is None:
            self._plan_remat(batch)
        # dispatch span: child of the fit loop's train.step root (same
        # thread), so the step trace reads data_wait -> dispatch ->
        # ckpt.snapshot -> (writer thread) ckpt.write. No-op when off.
        with _tracer.span("train.dispatch", "train",
                          {"step": self._host_step + 1,
                           "remat_saved_bytes": self._remat_saved["bytes"]}):
            return self._dispatch_impl(*batch)

    # ------------------------------------------------------ remat plan --
    def _batch_avals(self, batch) -> tuple:
        """The batch as the step's program takes it: shapes and dtypes,
        laid out by `batch_sharding` over the mesh."""
        from jax.sharding import NamedSharding

        vals = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]
        specs = [None] * len(vals)
        if self.mesh is not None and self._batch_sharding is not None:
            specs = [NamedSharding(self.mesh, s)
                     for s in self._batch_sharding]
        return tuple(jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
                     for v, s in zip(vals, specs))

    def _lower(self, avals):
        """The monolithic step lowered for `avals`; consumes no key of
        the random stream."""
        if self._step_fn is None:
            self._build()
        return self._step_fn.lower(
            self._params, self._buffers, self._opt_state,
            jax.ShapeDtypeStruct((), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32), jax.random.key(0), avals)

    def _plan_remat(self, batch):
        """Choose what the model's scanned block keeps of its forward
        (the class docstring's protocol), once, before the first compile.
        An empty plan leaves the program the policy-free one. The plan is
        kept beside the compile cache under the policy-free program's
        text, so a warm start lowers, looks up and compiles once."""
        from ..core import compile_cache as _cc
        from . import remat_plan as _rp

        self._remat_saved = _rp.saved_report(())
        candidates_of = getattr(self.model, "remat_candidates", None)
        budget = _device_budget(self.mesh)
        if candidates_of is None or budget is None or self._acc_steps > 1:
            return
        device_bytes, peaks = budget
        avals = self._batch_avals(batch)
        candidates = candidates_of(
            avals[0].shape, self.mesh, self._param_specs,
            (self._batch_sharding or (None,))[0], peaks)
        if not candidates:
            return
        limit = device_bytes - _rp.SPARE_BYTES

        def lowered_under(names):
            self.model.remat_save = tuple(names)
            self._build()
            return self._lower(avals)

        policy_free = lowered_under(())

        def need_of(names):
            lowered = lowered_under(names) if names else policy_free
            with _cc.donated_cpu_guard(self._donate):
                return _need_bytes(lowered.compile().memory_analysis())

        key = f"{policy_free.as_text()}\n{limit}\n{candidates}"
        report = _cc.plan_lookup(key)
        if report is None:
            report = _rp.fit_remat_plan(candidates, limit, need_of)
            _cc.plan_store(key, report)
        if tuple(report["names"]) != self.model.remat_save:
            self.model.remat_save = tuple(report["names"])
            self._build()
        self._remat_saved = report
        print(f"TrainStep: remat keeps {report['names'] or 'nothing'} "
              f"({report['bytes'] / 2**30:.2f} GiB by shape; the step needs "
              f"{(report['need_bytes'] or 0) / 2**30:.2f} of "
              f"{limit / 2**30:.2f} GiB)", file=sys.stderr, flush=True)

    def _dispatch_impl(self, *batch):
        if self._step_fn is None:
            self._build()
        vals = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                     for b in batch)
        if _chaos.active():
            # the `step` injection site: `step:nan:K` poisons the K-th
            # batch (exercising the skip-bad-steps path end to end);
            # raise/kill/sigterm rules fire BEFORE the RNG stream is
            # consumed, so a supervisor retry replays the same stream
            if _chaos.hit("step", step=self._host_step + 1) == "nan":
                vals = self._poison_nan(vals)
        if self.mesh is not None and self._batch_sharding is not None:
            from jax.sharding import NamedSharding

            if len(vals) != len(self._batch_sharding):
                raise ValueError(
                    f"train step got {len(vals)} batch args but "
                    f"batch_sharding declares {len(self._batch_sharding)}")
            vals = tuple(
                _mp_put(v, NamedSharding(self.mesh, s), full=False)
                for v, s in zip(vals, self._batch_sharding))
        key = _rng.next_key()

        from ..core import compile_cache as _cc

        sig = tuple((tuple(v.shape), str(v.dtype)) for v in vals)
        may_compile = sig not in self._compiled_sigs
        guard = _cc.donated_cpu_guard(self._donate and may_compile)

        if self._acc_steps > 1:
            if self._grad_acc is None:
                self._grad_acc = self._init_grad_acc()
            finish = self._start_compile_report()
            with guard:
                loss, self._buffers, self._grad_acc, mfinite = self._acc_fn(
                    self._params, self._buffers, self._grad_acc, key, vals)
            if finish:
                finish()
            self._compiled_sigs.add(sig)
            if self._skip_bad:
                self._pending_mfinite.append(mfinite)
            self._micro += 1
            if self._micro % self._acc_steps == 0:
                self._host_step += 1
                all_bad = False
                if self._skip_bad and self._pending_mfinite:
                    # micro programs finished long before this boundary —
                    # reading their scalar flags here stalls ~nothing
                    flags = [bool(f) for f in self._pending_mfinite]
                    self._pending_mfinite.clear()
                    bad = sum(1 for ok in flags if not ok)
                    self.bad_micro_count += bad
                    all_bad = bad > 0 and bad == len(flags)
                if all_bad:
                    self.bad_step_count += 1
                    # every micro was dropped: the accumulator is its
                    # zero init, but an optimizer update on zero grads
                    # still MOVES params (AdamW weight/moment decay) —
                    # skip the whole update instead
                    self._grad_acc = None
                    self.last_step_finite = False
                    self.optimizer._global_step = self._host_step
                    return Tensor(loss)
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                step_idx = jnp.asarray(self._host_step, jnp.int32)
                apply_first = "__apply__" not in self._compiled_sigs
                with _cc.donated_cpu_guard(self._donate and apply_first):
                    self._params, self._opt_state, finite = self._apply_fn(
                        self._params, self._grad_acc, self._opt_state, lr,
                        step_idx)
                self._compiled_sigs.add("__apply__")
                self._grad_acc = None
                if (self._check_nan or self._skip_bad) and \
                        not bool(finite):
                    self.last_step_finite = False
                    if self._skip_bad:
                        self.bad_step_count += 1
                    else:
                        raise FloatingPointError(
                            f"FLAGS_check_nan_inf: nan/inf in accumulated "
                            f"gradients at step {self._host_step}")
                else:
                    self.last_step_finite = True
                self._maybe_sync_params()
                self.model.load_functional_state(self._params, self._buffers)
                self.optimizer._global_step = self._host_step
            return Tensor(loss)

        self._host_step += 1
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        step_idx = jnp.asarray(self._host_step, jnp.int32)
        finish = self._start_compile_report()
        with guard:
            (loss, self._params, self._buffers, self._opt_state,
             finite) = self._step_fn(
                self._params, self._buffers, self._opt_state, lr, step_idx,
                key, vals)
        self._compiled_sigs.add(sig)
        if finish:
            finish()
        # only sync on `finite` when a mode needs it: bool() of a program
        # output blocks until the step completes, which would serialize
        # the default async-dispatch pipeline
        if (self._check_nan or self._skip_bad) and not bool(finite):
            self.last_step_finite = False
            if self._skip_bad:
                # graceful numeric degradation: the compiled program kept
                # the previous params/buffers/opt-state; book the skip
                self.bad_step_count += 1
            else:
                raise FloatingPointError(
                    f"FLAGS_check_nan_inf: nan/inf in loss or gradients at "
                    f"step {self._host_step}")
        else:
            self.last_step_finite = True
        self._maybe_sync_params()
        # keep the live model view in sync (rebind only, no copies)
        self.model.load_functional_state(self._params, self._buffers)
        self.optimizer._global_step = self._host_step
        if self.optimizer._lr_scheduler is not None:
            pass  # user steps the scheduler; lr is re-read next call
        return Tensor(loss)

    # ------------------------------------------------------------------
    def _start_compile_report(self):
        """First (compiling) call accounting: returns a finish() callback
        that fills self.compile_report with {first_call_s,
        persistent_hits, persistent_misses}, or None once reported."""
        if self.compile_report is not None:
            return None
        import time as _time

        from ..core import compile_cache as _cc

        pre = _cc.stats()
        t0 = _time.perf_counter()

        def finish():
            post = _cc.stats()
            self.compile_report = {
                "first_call_s": round(_time.perf_counter() - t0, 3),
                "persistent_hits": post["hits"] - pre["hits"],
                "persistent_misses": post["misses"] - pre["misses"],
                "remat_saved": self._remat_saved,
            }

        return finish

    def state(self):
        return self._params, self._buffers, self._opt_state

    def lowered(self, *batch):
        """The ``jax.stages.Lowered`` step program (cost/memory analysis).
        Note: callers that .compile() this on CPU should hold
        core.compile_cache.donated_cpu_guard(self._donate) — see
        compile_cache.suspend_if."""
        if self._remat_saved is None:
            self._plan_remat(batch)
        return self._lower(self._batch_avals(batch))

    def lower_hlo(self, *batch):
        """Return the StableHLO text of the compiled step (debug/inspection)."""
        return self.lowered(*batch).as_text()


class EvalStep:
    """Compiled inference step: out = EvalStep(model)(*batch)."""

    def __init__(self, model, mesh=None, batch_sharding=None):
        self.model = model
        self.mesh = mesh
        self._batch_sharding = batch_sharding
        self._fn = None

    def _build(self):
        model = self.model

        def run(params, buffers, batch):
            out, _ = functional_call(model, params, buffers, batch,
                                     training=False)
            return out

        self._fn = jax.jit(run)

    def __call__(self, *batch):
        if self._fn is None:
            self._build()
        params, buffers = self.model.functional_state()
        vals = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                     for b in batch)
        out = self._fn(params, buffers, vals)
        return jax.tree_util.tree_map(Tensor, out)
