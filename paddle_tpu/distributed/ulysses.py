"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all attention.

The reference has no sequence parallelism (SURVEY.md §5); this implements
the second canonical SP design from the literature (see PAPERS.md):
sequence-sharded activations are all-to-all'd so each device holds the FULL
sequence for a SLICE of heads, runs ordinary (exact) attention locally, and
all-to-all's back to sequence sharding. Complements ring attention
(ring_attention.py): Ulysses moves 2 all-to-alls of activation size and
needs heads % sp == 0; ring moves K/V around the ring and has no head
constraint. Both ride ICI inside shard_map-compiled programs.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor

SEP_AXIS = "sep"


def _local_attention(q, k, v, scale, causal, use_flash=False,
                     flash_interpret=False):
    """Exact attention on full-sequence, head-sliced blocks.
    q/k/v: [B, L, h_local, D]. use_flash runs the Pallas kernel (the
    long-context fast path: no [L, L] score tensor in HBM)."""
    if use_flash:
        from ..ops.pallas.flash_attention import _fwd, _resolve_dot_impl

        B, L, h, D = q.shape
        q2 = jnp.swapaxes(q, 1, 2).reshape(B * h, L, D)
        k2 = jnp.swapaxes(k, 1, 2).reshape(B * h, L, D)
        v2 = jnp.swapaxes(v, 1, 2).reshape(B * h, L, D)
        out, _ = _fwd(q2, k2, v2, scale, causal, flash_interpret,
                      _resolve_dot_impl())
        return jnp.swapaxes(out.reshape(B, h, L, D), 1, 2)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        L = s.shape[-1]
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.swapaxes(out, 1, 2)


def _ulysses_body(q, k, v, *, scale, causal, axis_name, use_flash=False,
                  flash_interpret=False):
    """shard_map body. Inputs sequence-sharded: [B, L/sp, H, D] per device.

    all_to_all axis 1<->2: gather sequence, scatter heads -> local
    [B, L, H/sp, D]; exact attention; inverse all_to_all restores
    sequence sharding."""
    qg = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    kg = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    vg = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1,
                            tiled=True)
    out = _local_attention(qg, kg, vg, scale, causal, use_flash,
                           flash_interpret)
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


_FN_CACHE = {}


def ulysses_attention(q, k, v, mesh=None, axis_name=SEP_AXIS, causal=True,
                      scale=None, use_flash=False,
                      flash_interpret=False):
    """Sequence-parallel exact attention via head/sequence all-to-all.

    q, k, v: [B, L, H, D] (paddle flash_attention layout), L sharded over
    `axis_name` inside the compiled program; H must divide by the axis
    size. Returns [B, L, H, D] with the same sharding. causal defaults
    True to match ring_attention (drop-in swap safety).
    """
    from .env import get_mesh

    mesh = mesh if mesh is not None else get_mesh()
    qv = q._data if isinstance(q, Tensor) else jnp.asarray(q)
    kv = k._data if isinstance(k, Tensor) else jnp.asarray(k)
    vv = v._data if isinstance(v, Tensor) else jnp.asarray(v)
    B, L, H, D = qv.shape
    sp = mesh.shape[axis_name]
    if H % sp != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"'{axis_name}' axis size ({sp}); use ring_attention otherwise")
    if L % sp != 0:
        raise ValueError(f"sequence {L} not divisible by sp={sp}")
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    # compiled-program cache: partial() has identity equality, so building
    # the jit wrapper per call would retrace every step
    key = (mesh, axis_name, bool(causal), float(scale), bool(use_flash),
           bool(flash_interpret))
    fn = _FN_CACHE.get(key)
    if fn is None:
        from .collective import shard_map as _shard_map

        body = partial(_ulysses_body, scale=scale, causal=causal,
                       axis_name=axis_name, use_flash=use_flash,
                       flash_interpret=flash_interpret)
        spec = P(None, axis_name, None, None)
        fn = jax.jit(_shard_map(body, mesh=mesh,
                                in_specs=(spec, spec, spec),
                                out_specs=spec, check=not use_flash))
        _FN_CACHE[key] = fn
    out = fn(qv, kv, vv)
    return Tensor(out) if isinstance(q, Tensor) else out


__all__ = ["ulysses_attention"]
