"""Op dispatch.

TPU-native replacement for the reference's per-op dispatch chain
(`_C_ops` → generated ad_func → `KernelFactory::SelectKernelOrThrowError`,
`paddle/phi/core/kernel_factory.cc:167`). There is no kernel registry to
search: every op is a pure JAX function. Dispatch decides only *how* to run
it:

- functional-trace mode (inside a compiled train step / to_static capture):
  apply the pure fn directly to the tracers — the op fuses into the enclosing
  XLA program;
- eager + grad: run under `jax.vjp`, recording a GradNode on the tape
  (analog of the generated `<op>_ad_func` + GradNode pair,
  `eager/auto_code_generator/generator/eager_gen.py`);
- eager inference: run a jit-compiled, shape-specialized executable from a
  process-wide cache (the compilation-cache answer to per-op CUDA launch).

AMP autocast (analog of `paddle/fluid/eager/amp_auto_cast.h`) rewrites
floating inputs of allow-listed ops to bf16 *through a differentiable cast*,
so grads flow back to fp32 master values.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import tree_util

from . import state as _st
from .autograd import GradNode
from .flags import _REGISTRY as _FLAGS
from .flags import flag, flags_epoch
from .tensor import Tensor, _wrap_array

# ---------------------------------------------------------------- AMP lists
# Analog of python/paddle/amp/amp_lists.py (O1 white/black lists), bf16-first.
AMP_WHITE_LIST = {
    "matmul", "mm", "bmm", "einsum", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "addmm", "attention", "flash_attention",
}
AMP_BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "pow", "square", "sqrt", "rsqrt",
    "softmax_with_cross_entropy", "cross_entropy", "log_softmax", "cumsum",
    "logsumexp", "erf", "erfinv", "sum", "mean", "norm", "cos_sim",
    "layer_norm",
}


def _is_tensor(x):
    return isinstance(x, Tensor)


def _is_arraylike(x):
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _call_pure(fn, treedef, leaves_template, t_pos, tvals, kwstatic):
    leaves = list(leaves_template)
    for i, v in zip(t_pos, tvals):
        leaves[i] = v
    args = tree_util.tree_unflatten(treedef, leaves)
    return fn(*args, **dict(kwstatic))


_jit_cache = None


def _get_jitted(fn, treedef, leaves_template, t_pos, kwstatic, fepoch):
    """fepoch = flags_epoch() at call time: op bodies read FLAGS at trace
    time, so a program traced under one flag value must not serve a call
    made after set_flags changed it (the epoch busts the cache entry)."""
    global _jit_cache
    if _jit_cache is None:
        # cache sized by FLAGS_eager_jit_cache_size at first use
        @functools.lru_cache(maxsize=int(flag("eager_jit_cache_size")))
        def _build(fn, treedef, leaves_template, t_pos, kwstatic, fepoch):
            def run(*tvals):
                return _call_pure(fn, treedef, leaves_template, t_pos, tvals,
                                  kwstatic)

            return jax.jit(run)

        _jit_cache = _build
    return _jit_cache(fn, treedef, leaves_template, t_pos, kwstatic, fepoch)


_vjp_cache = None


def _get_vjp_jitted(fn, treedef, leaves_template, t_pos, kwstatic, diff_idx,
                    fepoch):
    """Compiled pullback for the eager grad path: bwd(tvals, ct) re-derives
    jax.vjp INSIDE jit (XLA dead-code-eliminates the primal where the vjp
    doesn't need it) so steady-state eager training re-traces nothing —
    the round-2 verdict's 'no shape-keyed caching of traced vjps' fix.
    Keyed by op identity + static structure; jax.jit's own cache handles
    shape/dtype specialization. Reference role: the generated, compiled
    GradNode bodies (eager_gen.py) that make the reference's eager mode
    fast."""
    global _vjp_cache
    if _vjp_cache is None:
        @functools.lru_cache(maxsize=int(flag("eager_jit_cache_size")))
        def _build(fn, treedef, leaves_template, t_pos, kwstatic, diff_idx,
                   fepoch):
            def bwd(tvals, ct):
                fixed = list(tvals)

                def closed(*dvals):
                    vals = list(fixed)
                    for k, j in enumerate(diff_idx):
                        vals[j] = dvals[k]
                    return _call_pure(fn, treedef, leaves_template, t_pos,
                                      vals, kwstatic)

                _, vjp_fn = jax.vjp(closed, *[tvals[j] for j in diff_idx])
                return vjp_fn(ct)

            return jax.jit(bwd)

        _vjp_cache = _build
    return _vjp_cache(fn, treedef, leaves_template, t_pos, kwstatic,
                      diff_idx, fepoch)


def vjp_cache_info():
    """(hits, misses, maxsize, currsize) of the eager-pullback cache —
    None until the first eager grad-mode dispatch."""
    return _vjp_cache.cache_info() if _vjp_cache is not None else None


# (op, structure, dtypes) keys whose outputs include non-differentiable
# leaves — their pullbacks can't ride the jit cache (float0 cotangents),
# so the grad path skips the compiled-forward attempt entirely
_NOT_VJP_JITTABLE: set = set()


# ------------------------------------------------------- dispatch fast path
# Per-op call-plan cache (the ~110 µs/op lever, DESIGN.md "Dispatch fast
# path"): keyed by (op, input avals, stop_gradient bits, static kwargs,
# grad mode, flags epoch), a hit skips pytree flattening, dtype-promotion
# re-derivation and jit re-dispatch entirely — the stored plan carries the
# precomputed flatten/canonicalize artifacts plus AOT-compiled executables
# (jax.jit(...).lower().compile(), so they also land in the persistent
# compilation cache; core/compile_cache.py). The general `_apply` path
# below stays the source of truth for every case a plan can't serve
# (autocast rewrites, nested tensor containers, data-dependent-shape ops,
# unhashable statics, functional trace).
class _Plan:
    # t_idx doubles as the general path's t_pos: _build_plan rejects
    # nested containers, so leaf positions == top-level arg positions
    __slots__ = ("name", "fn", "t_idx", "treedef", "template",
                 "kwstatic", "fwd", "single", "out_treedef", "out_avals",
                 "diff_idx", "bwd_aot", "bwd_jit", "check_nan")


_PLAN_BYPASS = object()   # sentinel: this key must take the general path
_PLAN_CACHE: dict = {}
_PLAN_STATS = {"hits": 0, "misses": 0, "bypass": 0}

# scalar arg types the plan key can carry verbatim (the op bakes them as
# static constants, exactly like leaves_template in the general path);
# the value's class rides along so 2, 2.0 and True stay distinct keys
_KEY_SCALARS = (int, float, bool, str, bytes, type(None))


def plan_cache_info() -> dict:
    """Fast-path plan cache counters: hits (full fast path), misses
    (plan built), bypass (call shape the planner refuses)."""
    return dict(_PLAN_STATS, size=len(_PLAN_CACHE))


def clear_plan_cache():
    _PLAN_CACHE.clear()


def dispatch_cache_stats() -> dict:
    """Hit/miss/size counters of every dispatch-layer cache — the plan
    cache, the jitted-forward and vjp-pullback builder caches, and the
    process-level persistent (on-disk) compilation cache. Consumed by
    profiler.summary()/summary_dict() and tools/eager_bench.py."""
    out = {"plan": plan_cache_info()}
    for label, cache in (("jit", _jit_cache), ("vjp", _vjp_cache)):
        if cache is not None:
            i = cache.cache_info()
            out[label] = {"hits": i.hits, "misses": i.misses,
                          "size": i.currsize, "maxsize": i.maxsize}
    from . import compile_cache

    out["persistent"] = compile_cache.stats()
    return out


def _plan_key(fn, args, kwargs, grad_on):
    """None when this call shape can't be fast-path keyed (nested
    containers, exotic scalar types); raises TypeError/AttributeError on
    unhashable kwargs / non-jax tensor payloads — callers treat both as
    a bypass."""
    parts = [fn, grad_on, flags_epoch()]
    ap = parts.append
    for a in args:
        if type(a) is Tensor or isinstance(a, Tensor):
            ap(a._data.aval)
            ap(a.stop_gradient)
        elif isinstance(a, _KEY_SCALARS):
            ap(a)
            ap(a.__class__)
        else:
            return None
    if kwargs:
        for k, v in sorted(kwargs.items()):
            ap(k)
            ap(v)
            ap(v.__class__)
    return tuple(parts)


def _build_plan(fn, args, kwargs, grad_on):
    """One-time plan construction (the cache-miss path): precompute the
    flatten plan and AOT-compile the forward (and, in grad mode, the vjp
    pullback via the shared shape-keyed builder cache). Returns None when
    the call must stay on the general path."""
    leaves, treedef = tree_util.tree_flatten(args, is_leaf=_is_tensor)
    if len(leaves) != len(args):
        return None   # nested containers — general path
    t_idx = tuple(i for i, a in enumerate(args) if isinstance(a, Tensor))
    tensors = [args[i] for i in t_idx]
    tvals = [t._data for t in tensors]
    template = tuple(None if isinstance(l, Tensor) else l for l in leaves)
    kwstatic = tuple(sorted(kwargs.items()))
    fepoch = flags_epoch()

    meta = {}

    def run_flat(*tv):
        out = _call_pure(fn, treedef, template, t_idx, tv, kwstatic)
        out_leaves, otd = tree_util.tree_flatten(out)
        meta["otd"] = otd
        meta["avals"] = [(tuple(int(s) for s in l.shape), jnp.dtype(l.dtype))
                         for l in out_leaves]
        return tuple(out_leaves)

    fwd = jax.jit(run_flat).lower(*tvals).compile()
    otd, out_avals = meta["otd"], meta["avals"]

    plan = _Plan()
    plan.name = getattr(fn, "_op_name", fn.__name__)
    plan.fn = fn
    plan.t_idx = t_idx
    plan.treedef = treedef
    plan.template = template
    plan.kwstatic = kwstatic
    plan.fwd = fwd
    plan.single = len(out_avals) == 1 and otd.num_leaves == 1 \
        and tree_util.treedef_is_leaf(otd)
    plan.out_treedef = otd
    plan.out_avals = out_avals
    plan.diff_idx = None
    plan.bwd_aot = plan.bwd_jit = None
    plan.check_nan = bool(flag("check_nan_inf"))

    if grad_on:
        diff_idx = tuple(j for j, t in enumerate(tensors)
                         if not t.stop_gradient
                         and _differentiable_dtype(t._data.dtype))
        if diff_idx:
            if not all(_differentiable_dtype(d) for _, d in out_avals):
                # float0 cotangents — keep the general path's
                # _NOT_VJP_JITTABLE handling for this key
                return None
            plan.diff_idx = diff_idx
            plan.bwd_jit = _get_vjp_jitted(fn, treedef, template, t_idx,
                                           kwstatic, diff_idx, fepoch)
            ct_proto = tree_util.tree_unflatten(
                otd, [jax.ShapeDtypeStruct(s, d) for s, d in out_avals])
            plan.bwd_aot = plan.bwd_jit.lower(tuple(tvals),
                                              ct_proto).compile()
    return plan


def _run_plan(plan, args, key=None):
    tvals = [args[i]._data for i in plan.t_idx]
    try:
        outs = plan.fwd(*tvals)
    except Exception:
        # aval/sharding drift the key didn't capture (e.g. arrays moved
        # to a different device) — evict so the next call re-plans for
        # the new placement instead of paying a failed invocation + the
        # general path forever, and re-book the tallied hit as a bypass
        # so reported hit rates reflect what the fast path delivered
        if key is not None:
            _PLAN_CACHE.pop(key, None)
            _PLAN_STATS["hits"] -= 1
            _PLAN_STATS["bypass"] += 1
        return _apply(plan.fn, *args, **dict(plan.kwstatic))
    if plan.check_nan:
        _check_nan_inf(plan.name, outs)
    diff_idx = plan.diff_idx
    if diff_idx is None:
        if plan.single:
            return _wrap_array(outs[0])
        return tree_util.tree_unflatten(plan.out_treedef,
                                        [_wrap_array(l) for l in outs])
    tv = tuple(tvals)

    def vjp_fn(ct, _tv=tv, _a=plan.bwd_aot, _j=plan.bwd_jit):
        try:
            return _a(_tv, ct)
        except Exception:   # cotangent avals differ from the AOT build
            return _j(_tv, ct)

    node = GradNode(plan.name, vjp_fn,
                    [args[plan.t_idx[j]] for j in diff_idx],
                    plan.out_avals, plan.out_treedef)
    node.recompute = (plan.fn, plan.treedef, plan.template, plan.t_idx,
                      plan.kwstatic, tv, diff_idx)
    if plan.single:
        t = _wrap_array(outs[0], stop_gradient=False)
        t._grad_node = node
        return t
    wrapped = []
    for i, l in enumerate(outs):
        t = _wrap_array(l, stop_gradient=False)
        t._grad_node = node
        t._out_index = i
        wrapped.append(t)
    return tree_util.tree_unflatten(plan.out_treedef, wrapped)


def _plan_miss(fn, args, kwargs, grad_on, key):
    if len(_PLAN_CACHE) >= int(flag("eager_jit_cache_size")):
        # evict the oldest-inserted half (dicts iterate in insertion
        # order; the hit path re-inserts, making this LRU): zero per-hit
        # bookkeeping, and a varying-scalar workload that churns keys
        # can't wipe the whole hot set in one stall
        for k in list(_PLAN_CACHE)[:len(_PLAN_CACHE) // 2]:
            _PLAN_CACHE.pop(k, None)
    try:
        plan = _build_plan(fn, args, kwargs, grad_on)
    except Exception:
        plan = None   # genuine op errors re-raise (with full detail) below
    if plan is None:
        _PLAN_CACHE[key] = _PLAN_BYPASS
        return _apply(fn, *args, **kwargs)
    _PLAN_CACHE[key] = plan
    return _run_plan(plan, args)


def _dispatch(fn, args, kwargs):
    """Fast-path front door: try the plan cache, else the general path."""
    st = _st.STATE
    if (st.func_trace > 0 or st.autocast_enabled or _OP_STATS is not None
            or not st.eager_jit or not _FLAGS["eager_op_jit"]
            or getattr(fn, "_no_jit", False)):
        # _no_jit covers data-dependent-shape ops AND the per-backward
        # grad_op closures _grad_op_of creates (fresh fn objects that
        # would pollute the plan cache with one-shot keys)
        return _apply(fn, *args, **kwargs)
    grad_on = st.grad_enabled
    try:
        key = _plan_key(fn, args, kwargs, grad_on)
        plan = _PLAN_CACHE.get(key) if key is not None else None
    except (TypeError, AttributeError):
        key = plan = None
    if plan is None:
        if key is None:
            _PLAN_STATS["bypass"] += 1
            return _apply(fn, *args, **kwargs)
        _PLAN_STATS["misses"] += 1
        return _plan_miss(fn, args, kwargs, grad_on, key)
    if plan is _PLAN_BYPASS:
        _PLAN_STATS["bypass"] += 1
        return _apply(fn, *args, **kwargs)
    _PLAN_STATS["hits"] += 1
    # refresh insertion order (dicts iterate oldest-first, so eviction in
    # _plan_miss is LRU only if hits re-insert): one dict pop+set, ~0.2 µs;
    # pop() not del — concurrent dispatch threads may race the removal
    _PLAN_CACHE.pop(key, None)
    _PLAN_CACHE[key] = plan
    return _run_plan(plan, args, key)


def _differentiable_dtype(d):
    d = jnp.dtype(d)
    return jnp.issubdtype(d, jnp.floating) or jnp.issubdtype(d, jnp.complexfloating)


def _autocast_rewrite(name, args, kwargs):
    """Cast floating tensor leaves through the differentiable cast op."""
    from ..ops import cast as cast_op

    target = _st.STATE.autocast_dtype

    if name in AMP_WHITE_LIST:
        def conv(x):
            if isinstance(x, Tensor) and jnp.dtype(x._data.dtype) == jnp.float32:
                return cast_op(x, target)
            return x
    elif name in AMP_BLACK_LIST:
        def conv(x):
            if isinstance(x, Tensor) and jnp.dtype(x._data.dtype) == jnp.dtype(target):
                return cast_op(x, jnp.float32)
            return x
    else:
        return args, kwargs
    args = tree_util.tree_map(conv, args, is_leaf=_is_tensor)
    kwargs = tree_util.tree_map(conv, kwargs, is_leaf=_is_tensor)
    return args, kwargs


def _check_nan_inf(name, leaves):
    for v in leaves:
        if _is_arraylike(v) and _differentiable_dtype(v.dtype):
            a = np.asarray(v)
            if not np.isfinite(a).all():
                raise FloatingPointError(f"op '{name}' produced nan/inf")


# amp.debugging operator-stats collection: when enabled, every dispatch
# records (op name, dtype) counts. None = disabled (zero overhead).
_OP_STATS = None


def _record_op_stat(name, args):
    for a in tree_util.tree_leaves(args):
        if _is_tensor(a):
            key = (name, str(a._data.dtype))
            _OP_STATS[key] = _OP_STATS.get(key, 0) + 1
            return
    _OP_STATS[(name, "-")] = _OP_STATS.get((name, "-"), 0) + 1


# ------------------------------------------------------- FLOPs accounting
# Per-defop analytic-FLOPs table (role of the reference's @op_flops
# registry consumed by profiler_statistic.gen_layer_flops): each entry maps
# an op name to fn(invals, outvals, **static_kwargs) -> int, where
# invals/outvals are the op's array-like leaves (shapes may be abstract
# tracers). Ops without an entry default to one FLOP per output element
# (the elementwise convention). Counts are FORWARD flops; the profiler
# applies the standard 3x multiplier for fwd+bwd training steps.
FLOPS_REGISTRY: dict = {}


def defflops(name: str):
    """Register an analytic FLOPs formula for op `name`."""

    def deco(fn):
        FLOPS_REGISTRY[name] = fn
        return fn

    return deco


def _numel(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def flops_for(name: str, invals, outvals, kwargs) -> int:
    """Analytic FLOPs of one op call (0 on any formula failure — FLOPs
    accounting must never take down the dispatched op)."""
    fn = FLOPS_REGISTRY.get(name)
    try:
        if fn is not None:
            return int(fn(invals, outvals, **dict(kwargs)))
        return sum(_numel(v.shape) for v in outvals if _is_arraylike(v))
    except Exception:  # noqa: BLE001 — profiling-only path
        return 0


def _matmul_flops(invals, outvals, transpose_x=False, transpose_y=False,
                  **kw):
    x = invals[0]
    k = x.shape[-2] if transpose_x and len(x.shape) > 1 else x.shape[-1]
    return 2 * _numel(outvals[0].shape) * int(k)


FLOPS_REGISTRY["matmul"] = _matmul_flops
FLOPS_REGISTRY["bmm"] = lambda iv, ov, **kw: \
    2 * _numel(ov[0].shape) * int(iv[0].shape[-1])
FLOPS_REGISTRY["mv"] = lambda iv, ov, **kw: \
    2 * _numel(ov[0].shape) * int(iv[0].shape[-1])
FLOPS_REGISTRY["dot"] = lambda iv, ov, **kw: 2 * _numel(iv[0].shape)
FLOPS_REGISTRY["addmm"] = lambda iv, ov, **kw: \
    2 * _numel(ov[0].shape) * (int(iv[1].shape[-1]) + 1)


@defflops("linear")
def _linear_flops(invals, outvals, **kw):
    # x @ W (+ bias): W is invals[1] with shape [in, out]
    f = 2 * _numel(outvals[0].shape) * int(invals[1].shape[0])
    if len(invals) > 2:
        f += _numel(outvals[0].shape)
    return f


def _conv_flops(invals, outvals, groups=1, **kw):
    # out_numel * 2 * (Cin/groups * prod(kernel spatial)); weight is
    # O,I/g,*spatial so that factor is prod(weight.shape[1:])
    w = invals[1]
    return 2 * _numel(outvals[0].shape) * _numel(w.shape[1:])


for _cname in ("conv1d", "conv2d", "conv3d", "conv1d_transpose",
               "conv2d_transpose", "conv3d_transpose"):
    FLOPS_REGISTRY[_cname] = _conv_flops


def _attention_flops(invals, outvals, is_causal=False, **kw):
    # q,k,v are [B, L, H, D]: QK^T and PV each cost 2*B*H*L*S*D; a causal
    # mask halves the scored pairs
    q, k = invals[0], invals[1]
    b, l, h, d = (int(s) for s in q.shape)
    s = int(k.shape[1])
    f = 4 * b * h * l * s * d
    return f // 2 if is_causal else f


FLOPS_REGISTRY["scaled_dot_product_attention"] = _attention_flops
FLOPS_REGISTRY["flash_attention"] = _attention_flops


@defflops("fused_linear_cross_entropy")
def _fused_ce_flops(invals, outvals, transpose_y=False, **kw):
    # hidden [B, L, H] x weight: the head matmul dominates
    h = invals[0]
    w = invals[1]
    vocab = int(w.shape[0] if transpose_y else w.shape[-1])
    return 2 * _numel(h.shape) * vocab


# Profiler hook (installed by paddle_tpu.profiler.stats while a Profiler
# is recording): hook(name, begin_ns, end_ns, args, kwargs, out). None =>
# zero dispatch overhead.
_PROFILE_HOOK = None


def set_profile_hook(hook):
    """Install/remove the per-dispatch profiling hook; returns the
    previous hook."""
    global _PROFILE_HOOK
    prev = _PROFILE_HOOK
    _PROFILE_HOOK = hook
    return prev


def apply(fn: Callable, *args, **kwargs) -> Any:
    """Dispatch pure fn over args/kwargs that may contain Tensors anywhere.

    kwargs are static (compile-time attributes); Tensors may only appear in
    positional args (possibly nested in lists/tuples, e.g. concat's input
    list).
    """
    hook = _PROFILE_HOOK
    if hook is None:
        return _dispatch(fn, args, kwargs)
    t0 = time.perf_counter_ns()
    out = _dispatch(fn, args, kwargs)
    t1 = time.perf_counter_ns()
    hook(getattr(fn, "_op_name", fn.__name__), t0, t1, args, kwargs, out)
    return out


def _apply(fn: Callable, *args, **kwargs) -> Any:
    name = getattr(fn, "_op_name", fn.__name__)

    if _OP_STATS is not None:
        _record_op_stat(name, args)

    if _st.STATE.autocast_enabled and (name in AMP_WHITE_LIST
                                       or name in AMP_BLACK_LIST):
        args, kwargs = _autocast_rewrite(name, args, kwargs)

    leaves, treedef = tree_util.tree_flatten(args, is_leaf=_is_tensor)
    t_pos = tuple(i for i, l in enumerate(leaves) if isinstance(l, Tensor))
    tensors = [leaves[i] for i in t_pos]
    tvals = [t._data for t in tensors]
    leaves_template = tuple(None if isinstance(l, Tensor) else l for l in leaves)
    kwstatic = tuple(sorted(kwargs.items()))

    # ---- functional trace: fuse into enclosing XLA program ----
    if _st.STATE.func_trace > 0:
        out = _call_pure(fn, treedef, leaves_template, t_pos, tvals, kwstatic)
        any_diff = any(not t.stop_gradient for t in tensors)
        return _wrap_outputs(out, node=None, stop_gradient=not any_diff)

    diff_idx = [j for j, t in enumerate(tensors)
                if not t.stop_gradient and _differentiable_dtype(t._data.dtype)]

    # ---- eager + autograd recording ----
    if _st.STATE.grad_enabled and diff_idx:
        out = vjp_fn = None
        cache_key = (fn, treedef, leaves_template, t_pos, kwstatic,
                     tuple(str(v.dtype) for v in tvals))
        use_cache = (flag("eager_op_jit") and _st.STATE.eager_jit
                     and not getattr(fn, "_no_jit", False))
        if use_cache:
            try:
                use_cache = cache_key not in _NOT_VJP_JITTABLE
            except TypeError:
                use_cache = False  # unhashable static arg (e.g. list)
        if use_cache:
            # compiled fwd + compiled pullback from the shape-keyed caches:
            # zero re-tracing in steady-state eager training
            try:
                fep = flags_epoch()
                out = _get_jitted(fn, treedef, leaves_template, t_pos,
                                  kwstatic, fep)(*tvals)
                if all(_differentiable_dtype(l.dtype)
                       for l in tree_util.tree_leaves(out)
                       if _is_arraylike(l)):
                    bwd = _get_vjp_jitted(fn, treedef, leaves_template,
                                          t_pos, kwstatic,
                                          tuple(diff_idx), fep)
                    tv = tuple(tvals)

                    def vjp_fn(ct, _b=bwd, _tv=tv):
                        return _b(_tv, ct)
                else:
                    # integer outputs take float0 cotangents, which jit
                    # can't take as arguments — remember the verdict so
                    # later calls skip the wasted jitted forward and go
                    # straight to eager vjp (which must recompute out)
                    _NOT_VJP_JITTABLE.add(cache_key)
                    out = None
            except TypeError as e:
                if "unhashable" not in str(e):
                    raise
                out = None

        if vjp_fn is None:
            fixed = list(tvals)

            def closed(*diff_vals):
                vals = list(fixed)
                for k, j in enumerate(diff_idx):
                    vals[j] = diff_vals[k]
                return _call_pure(fn, treedef, leaves_template, t_pos, vals,
                                  kwstatic)

            out, vjp_fn = jax.vjp(closed, *[tvals[j] for j in diff_idx])
        out_leaves, out_treedef = tree_util.tree_flatten(out)
        node = GradNode(name, vjp_fn, [tensors[j] for j in diff_idx],
                        [(tuple(v.shape), v.dtype) for v in out_leaves],
                        out_treedef)
        # create_graph support: enough info to RE-derive the vjp as a
        # differentiable function of the node's inputs (second order must
        # differentiate through the residuals, which vjp_fn froze)
        node.recompute = (fn, treedef, leaves_template, t_pos, kwstatic,
                          tuple(tvals), tuple(diff_idx))
        if flag("check_nan_inf"):
            _check_nan_inf(name, out_leaves)
        return _wrap_outputs(out, node=node, stop_gradient=False)

    # ---- eager inference: cached jit executable ----
    try:
        if flag("eager_op_jit") and _st.STATE.eager_jit \
                and not getattr(fn, "_no_jit", False):
            out = _get_jitted(fn, treedef, leaves_template, t_pos, kwstatic,
                              flags_epoch())(*tvals)
        else:
            out = _call_pure(fn, treedef, leaves_template, t_pos, tvals, kwstatic)
    except TypeError as e:
        if "unhashable" in str(e):
            out = _call_pure(fn, treedef, leaves_template, t_pos, tvals, kwstatic)
        else:
            raise
    if flag("check_nan_inf"):
        _check_nan_inf(name, tree_util.tree_leaves(out))
    return _wrap_outputs(out, node=None, stop_gradient=True)


def _wrap_outputs(out, node, stop_gradient):
    out_leaves, out_treedef = tree_util.tree_flatten(out)
    wrapped = []
    for i, l in enumerate(out_leaves):
        if _is_arraylike(l):
            t = _wrap_array(l, stop_gradient=stop_gradient)
            if node is not None and _differentiable_dtype(l.dtype):
                t._grad_node = node
                t._out_index = i
            elif node is not None:
                t.stop_gradient = True
            wrapped.append(t)
        else:
            wrapped.append(l)
    return tree_util.tree_unflatten(out_treedef, wrapped)


def primitive(name: str):
    """Tag a pure function with its op name (used by AMP lists & profiling)."""

    def deco(fn):
        fn._op_name = name
        return fn

    return deco


# Every defop-registered op name -> pure fn. The reference's yaml codegen
# guarantees systematic op+grad coverage by construction; here the registry
# is what makes that guarantee CHECKABLE (tests/test_op_coverage.py walks
# it and requires each differentiable op to appear in the gradient sweep
# or carry an explicit, justified exemption).
OP_REGISTRY: dict = {}


def defop(name: str, jit: bool = True):
    """Decorator: pure jax fn -> user-facing op taking/returning Tensors.

    jit=False marks data-dependent-shape ops (nonzero, unique, masked_select…)
    that must run eagerly — the XLA analog of the reference's dynamic-shape
    kernels; under a compiled trace they raise naturally unless given a static
    size hint.
    """

    def deco(fn):
        fn._op_name = name
        if not jit:
            fn._no_jit = True

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if kwargs:
                kwargs.pop("name", None)
            if _PROFILE_HOOK is None:
                return _dispatch(fn, args, kwargs)
            return apply(fn, *args, **kwargs)

        wrapper._pure_fn = fn
        wrapper._op_name = name
        OP_REGISTRY[name] = fn
        return wrapper

    return deco
