"""Decode attention over the serving engine's K/V pool, where it lies.

One query position per decoding row against the row's cached prefix. The
pool is `[rows, L, cap, H*Dh]` (the engine's `_pool_shape`): a row of the
pool is a SLOT, and the rows a step decodes are named by `slots`, in any
order and with repeats (padding rows all name the scratch slot). The
kernel never sees a gathered copy: `slots`, each row's last live position
and the layer index are scalar-prefetched, and the K/V `BlockSpec` index
map picks block `(slots[i], layer, j)` of the pool in HBM. A block that
lies wholly past the row's last position is not computed (`pl.when`) and
not copied (the index map stays on the row's last live block, so the
pipeline sees an unchanged index and issues no DMA). Inside the last live
block positions past `last` are masked in the scores, and V's rows there
are zeroed — whatever the pool holds past a row's length, a NaN included,
never reaches the output.

The heads stay folded in the minor dimension (H*Dh dense lanes, no lane
padding in HBM or VMEM, one DMA a block). The per-head scores come from a
block-diagonal query `[H, H*Dh]` (row h holds head h's query in its own
Dh lanes) against the K block `[block, H*Dh]`; the per-head outputs are
the diagonal blocks of `p @ V`. That spends H times the products a
per-head layout would, on an MXU that the thin (H-row) operand leaves
mostly idle anyway: the step is bound by the bytes of K/V it reads.

Softmax is online, in float32, across a row's blocks. Every dot states
its precision (`flash_attention._dot`): `HIGHEST` for float32 operands —
the package-wide "highest" must neither leak into nor be lost from a
Mosaic kernel.

The block size is `block_plan(cap, num_heads, head_dim, itemsize)`'s, from
the shape alone: no flag, no environment variable.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NN, NT, _NEG_INF, _dot, _im

# One K (or V) block a DMA: large enough that a copy runs near the HBM
# rate, small enough that a short row does not pay for a long block.
_BLOCK_BYTES = 2**20
# What a grid step may hold in VMEM by `DecodePlan.vmem_bytes`; the kernel
# asks Mosaic for `_VMEM_LIMIT` (the score and product tiles are the
# compiler's own).
_VMEM_BUDGET = 12 * 2**20
_VMEM_LIMIT = 32 * 2**20


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """block: pool positions a grid step reads of one row, K and V each.
    vmem_bytes: the step's pipelined blocks (double-buffered) and scratch."""
    block: int
    vmem_bytes: int

    def positions_read(self, length: int, cap: int) -> int:
        """Pool positions the kernel copies for a row whose new token sits
        at `length`: whole blocks up to the one that holds it."""
        return (min(int(length), cap - 1) // self.block + 1) * self.block


def _step_vmem_bytes(block, num_heads, width, itemsize):
    """K and V blocks double-buffered; q and the output row pad to 8
    sublanes (double-buffered); the f32 accumulator [H, width] and the two
    [H, 1] statistics, which pad to 128 lanes."""
    heads = -(-num_heads // 8) * 8
    kv = 2 * 2 * block * width * itemsize
    rows = 2 * 2 * 8 * width * 4
    acc = heads * width * 4 + 2 * heads * 128 * 4
    return kv + rows + acc


def block_plan(cap: int, num_heads: int, head_dim: int,
               itemsize: int) -> DecodePlan | None:
    """The schedule for a pool of `cap` positions of `num_heads` heads of
    `head_dim`, or None where the kernel does not serve the shape: folded
    heads that do not fill whole 128-lane tiles, a capacity that is not a
    multiple of 8 positions, or a step that does not fit VMEM. The block
    is the largest power-of-two divisor of `cap` whose K block is at most
    `_BLOCK_BYTES`, and at least 8 positions (one sublane tile)."""
    width = num_heads * head_dim
    if width % 128 or cap % 8 or cap < 8:
        return None
    block = 8
    while (cap % (block * 2) == 0
           and block * 2 * width * itemsize <= _BLOCK_BYTES):
        block *= 2
    need = _step_vmem_bytes(block, num_heads, width, itemsize)
    if need > _VMEM_BUDGET:
        return None
    return DecodePlan(block, need)


def _kernel(slots_ref, last_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block, num_heads, head_dim):
    """Grid (row i, block j). q/o [1, H*Dh]; k/v [block, H*Dh] of pool
    row slots[i] at the layer; m, l [H, 1] and acc [H, H*Dh] in scratch."""
    del slots_ref, layer_ref          # the index maps read them
    i, j = pl.program_id(0), pl.program_id(1)
    last = last_ref[i]
    # the block that holds `last` (lax.div: under the package's x64 a
    # `//` traces a convert that Mosaic's lowering recurses on)
    live = jax.lax.div(last, jnp.int32(block))
    width = num_heads * head_dim
    lane = jax.lax.broadcasted_iota(jnp.int32, (num_heads, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (num_heads, width), 0)
    own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        def run():
            q = q_ref[...]
            qbd = jnp.where(own, jnp.broadcast_to(q, (num_heads, width)),
                            jnp.zeros((), q.dtype))
            k = k_ref[...]
            v = v_ref[...]
            s = _dot(qbd, k, NT, None) * jnp.float32(
                1.0 / math.sqrt(head_dim))               # [H, block]
            if masked:
                kpos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(kpos <= last, s, jnp.float32(_NEG_INF))
                vpos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 0)
                v = jnp.where(vpos <= last, v, jnp.zeros((), v.dtype))
            m_prev = m_ref[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)
            alpha = jnp.exp(m_prev - m)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + _dot(
                p if v.dtype == jnp.float32 else p.astype(v.dtype), v, NN,
                None)                                    # [H, H*Dh]
            m_ref[...] = m
        return run

    # whole blocks take no mask; blocks past `live` are neither computed
    # nor (the index map) copied
    pl.when(j < live)(step(False))
    pl.when(j == live)(step(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] / l_ref[...]
        o_ref[...] = jnp.sum(jnp.where(own, out, jnp.float32(0)), axis=0,
                             keepdims=True).astype(o_ref.dtype)


def decode_attention(q, pool_k, pool_v, layer, slots, lengths, *,
                     num_heads: int, interpret: bool = False,
                     plan: DecodePlan | None = None):
    """Attention of one query a row over the pool, in place.

    q [b, H*Dh]; pool_k, pool_v [rows, L, cap, H*Dh]; layer a scalar;
    slots [b] the pool row of each query; lengths [b] the position of each
    row's newest token, which the pool already holds: row i attends
    positions [0, min(lengths[i], cap - 1)] of pool row slots[i]. Returns
    [b, H*Dh] in q's dtype."""
    b, width = q.shape
    cap = pool_k.shape[2]
    head_dim = width // num_heads
    if plan is None:
        plan = block_plan(cap, num_heads, head_dim, pool_k.dtype.itemsize)
    if plan is None:
        raise ValueError(
            f"decode attention does not serve cap {cap} x {num_heads} heads"
            f" of {head_dim} ({pool_k.dtype}): see block_plan")
    block = plan.block
    last = jnp.minimum(lengths.astype(jnp.int32), cap - 1)
    kv_spec = pl.BlockSpec(
        (None, None, block, width),
        _im(lambda i, j, slots, last, layer: (
            slots[i], layer[0],
            jnp.minimum(j, jax.lax.div(last[i], jnp.int32(block))), 0)))
    row_spec = pl.BlockSpec((None, 1, width),
                            _im(lambda i, j, *_: (i, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, block=block, num_heads=num_heads,
                          head_dim=head_dim),
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, cap // block),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, 1, width), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(slots.astype(jnp.int32), last,
      jnp.asarray(layer, jnp.int32).reshape(1), q[:, None, :], pool_k,
      pool_v)
    return out[:, 0, :]


__all__ = ["DecodePlan", "block_plan", "decode_attention"]
