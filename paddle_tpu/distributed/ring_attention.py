"""Ring attention — sequence/context parallelism over the mesh.

The reference has NO sequence parallelism (SURVEY.md §5: exhaustive grep
empty); this is designed from the ring-attention literature (blockwise
attention with K/V blocks rotated around the ring via collective-permute;
see PAPERS.md). TPU-native: the ring step is `jax.lax.ppermute` over the
"sep" mesh axis inside shard_map — XLA schedules the permute over ICI
overlapping with the local block attention.

Numerics: streaming softmax (running max m, normalizer l, accumulator o),
exactly flash-attention's update rule, so the result matches full attention.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor

SEP_AXIS = "sep"


def _block_attn(q, k, v, scale, mask=None):
    """One q-block x kv-block attention with streaming stats.

    q: [B, H, Lq, Dh]; k/v: [B, H, Lk, Dh]. Returns (o, m, l) partials.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)                                   # [B,H,Lq]
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = p.sum(-1)                                             # [B,H,Lq]
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, m_safe, l, jnp.isfinite(m)


def _merge(o1, m1, l1, o2, m2, l2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = l1 * a1 + l2 * a2
    o = o1 * a1[..., None] + o2 * a2[..., None]
    return o, m, l


def _flash_block(qh, kh, vh, scale, causal, interpret):
    """Local block attention through the Pallas flash kernel, returning
    streaming partials (o_normalized, lse) for ring merging. qh/kh/vh:
    [B, H, L, D]."""
    from ..ops.pallas.flash_attention import _fwd, _resolve_dot_impl

    B, H, L, D = qh.shape
    q2 = qh.reshape(B * H, L, D)
    k2 = kh.reshape(B * H, L, D)
    v2 = vh.reshape(B * H, L, D)
    out, lse = _fwd(q2, k2, v2, scale, causal, interpret,
                    _resolve_dot_impl())
    return (out.reshape(B, H, L, D),
            lse.reshape(B, H, L))


def _merge_lse(o1, lse1, o2, lse2):
    """Merge two NORMALIZED partial outputs by their logsumexps;
    -inf lse (empty partial) contributes exactly zero."""
    lse = jnp.logaddexp(lse1, lse2)
    denom = jnp.where(jnp.isfinite(lse), lse, 0.0)
    w1 = jnp.where(jnp.isfinite(lse1), jnp.exp(lse1 - denom), 0.0)
    w2 = jnp.where(jnp.isfinite(lse2), jnp.exp(lse2 - denom), 0.0)
    return o1 * w1[..., None] + o2 * w2[..., None], lse


def ring_attention_local(q, k, v, axis_name=SEP_AXIS, causal=True,
                         scale=None, use_flash=False,
                         flash_interpret=False):
    """Per-shard body (call inside shard_map): q/k/v are the LOCAL sequence
    blocks [B, Lblk, H, Dh]; the full sequence is sharded over axis_name.

    use_flash=True runs each ring step's local block attention through the
    Pallas flash kernel (O(Lblk·D) HBM traffic instead of the [Lq, Lk]
    score tensor) and merges steps by logsumexp — the long-context fast
    path on TPU. flash_interpret runs the kernel in interpret mode (CPU
    tests)."""
    if use_flash:
        return _ring_flash_impl(q, k, v, axis_name, causal, scale,
                                flash_interpret)
    nblocks = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    # [B, H, L, D] layout for the inner loops
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    Lq = qh.shape[2]

    def make_mask(q_blk, kv_blk):
        if not causal:
            return None
        # global positions
        qpos = q_blk * Lq + jnp.arange(Lq)
        kpos = kv_blk * Lq + jnp.arange(Lq)
        return qpos[:, None] >= kpos[None, :]

    def step(carry, _):
        o, m, l, kv, kv_blk = carry
        k_cur, v_cur = kv
        mask = make_mask(idx, kv_blk)
        o2, m2, l2, _ = _block_attn(qh, k_cur, v_cur, scale, mask)
        o, m, l = _merge(o, m, l, o2, m2, l2)
        # rotate kv to the next rank in the ring
        perm = [(i, (i + 1) % nblocks) for i in range(nblocks)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_blk_nxt = jax.lax.ppermute(kv_blk, axis_name, perm)
        return (o, m, l, (k_nxt, v_nxt), kv_blk_nxt), None

    o0 = jnp.zeros_like(qh)
    m0 = jnp.full(qh.shape[:-1], -jnp.inf, qh.dtype)
    l0 = jnp.zeros(qh.shape[:-1], qh.dtype)
    # fresh constants are device-invariant under shard_map; the carry becomes
    # device-varying after the first ppermute, so tag them varying up front
    def _vary(x):
        try:
            if axis_name in getattr(jax.typeof(x), "vma", ()):
                return x
            return jax.lax.pcast(x, axis_name, to="varying")
        except (AttributeError, TypeError):
            return x

    o0, m0, l0, idx = _vary(o0), _vary(m0), _vary(l0), _vary(idx)
    carry = (o0, m0, l0, (_vary(kh), _vary(vh)), idx)
    (o, m, l, _, _), _ = jax.lax.scan(step, carry, None, length=nblocks)
    out = o / jnp.maximum(l[..., None], 1e-20)
    return jnp.swapaxes(out, 1, 2)       # back to [B, L, H, D]


def _ring_flash_impl(q, k, v, axis_name, causal, scale, interpret):
    """Flash-kernel ring body: per step, the local block runs through the
    Pallas kernel; cross-step combination is logsumexp merging. Three pair
    kinds: kv_blk < q_blk → full (non-causal) block; kv_blk == q_blk →
    causal block; kv_blk > q_blk → fully masked (skipped via -inf lse)."""
    nblocks = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)

    def step(carry, _):
        o, lse, kv, kv_blk = carry
        k_cur, v_cur = kv
        if causal:
            o_c, lse_c = _flash_block(qh, k_cur, v_cur, scale, True,
                                      interpret)
            o_f, lse_f = _flash_block(qh, k_cur, v_cur, scale, False,
                                      interpret)
            is_diag = kv_blk == idx
            is_past = kv_blk < idx
            o2 = jnp.where(is_diag, o_c, o_f)
            lse2 = jnp.where(is_diag, lse_c, lse_f)
            # future blocks contribute nothing
            lse2 = jnp.where(is_diag | is_past, lse2, -jnp.inf)
            o2 = jnp.where((is_diag | is_past), o2, 0.0)
        else:
            o2, lse2 = _flash_block(qh, k_cur, v_cur, scale, False,
                                    interpret)
        o, lse = _merge_lse(o, lse, o2, lse2)
        perm = [(i, (i + 1) % nblocks) for i in range(nblocks)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_blk_nxt = jax.lax.ppermute(kv_blk, axis_name, perm)
        return (o, lse, (k_nxt, v_nxt), kv_blk_nxt), None

    o0 = jnp.zeros(qh.shape, jnp.float32)
    lse0 = jnp.full(qh.shape[:-1], -jnp.inf, jnp.float32)

    def _vary(x):
        try:
            if axis_name in getattr(jax.typeof(x), "vma", ()):
                return x
            return jax.lax.pcast(x, axis_name, to="varying")
        except (AttributeError, TypeError):
            return x

    carry = (_vary(o0), _vary(lse0), (_vary(kh), _vary(vh)), _vary(idx))
    (o, lse, _, _), _ = jax.lax.scan(step, carry, None, length=nblocks)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis_name=SEP_AXIS, causal=True,
                   use_flash=False, flash_interpret=False):
    """Host-level API: q/k/v [B, L, H, Dh] with L sharded over axis_name.

    Runs the ring under shard_map on `mesh` (default: the global mesh).
    Inside an outer compiled program, call ring_attention_local directly.
    use_flash routes each ring step through the Pallas flash kernel
    (long-context fast path; flash_interpret for CPU validation).
    """
    from .env import get_mesh

    mesh = mesh or get_mesh()

    qv = q._data if isinstance(q, Tensor) else q
    kv = k._data if isinstance(k, Tensor) else k
    vv = v._data if isinstance(v, Tensor) else v
    prog = _ring_program(mesh, axis_name, causal, use_flash,
                         flash_interpret)
    out = prog(qv, kv, vv)
    return Tensor(out) if isinstance(q, Tensor) else out


# compiled ring programs memoized per static config: a fresh
# shard_map closure per call re-traced EVERY forward (the PR 7
# collectives bug class — the retrace-risk lint exists because of this
# shape). Meshes are few per process, so the map stays tiny.
_RING_PROGRAMS = {}


def _ring_program(mesh, axis_name, causal, use_flash, flash_interpret):
    from .collective import shard_map

    key = (mesh, axis_name, causal, use_flash, flash_interpret)
    prog = _RING_PROGRAMS.get(key)
    if prog is None:
        spec = P(None, axis_name, None, None)
        # use_flash: pallas_call can't declare vma on its outputs, so
        # the static varying-axes checker must be off for the flash body
        fn = shard_map(
            partial(ring_attention_local, axis_name=axis_name,
                    causal=causal, use_flash=use_flash,
                    flash_interpret=flash_interpret),
            mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check=not use_flash)
        prog = jax.jit(fn)
        _RING_PROGRAMS[key] = prog
    return prog
