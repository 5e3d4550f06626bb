"""Absorbed-form decode attention over a LATENT pool, where it lies.

Latent attention (DeepSeek-V2's MLA) caches, a position and layer, one
low-rank latent `c` and one rotated key `k_r` that every head shares; the
per-head keys and values are `c`'s products with up-projections. In the
absorbed form a decoding row's query is taken into the latent's space
before the read (`q̂_h = W_UK,hᵀ q_n,h`), so a step reads the latent rows
alone: `s_h(τ) = σ · ([q̂_h ‖ q_r,h] · [c(τ) ‖ k_r(τ)])`, softmax over τ,
`ô_h = Σ_τ p_h(τ) c(τ)`; the caller applies `W_UV,h` after. All H heads
read ONE shared row a position: a real `[H, W] × [W, block]` product and
a `[H, block] × [block, C]` product a block — no fold, no head a lane
group (decode_attention.py's layout would spend H times the products and
does not take a single shared head under grouped queries).

The pool is `[rows, L, cap, W]`: a row of it is a SLOT (the engine's
`_pool_shape`), a position `[c ‖ k_r ‖ 0]`, `W` the latent and the rotary
key padded to whole 128-lane tiles (a TPU lays a narrower minor dimension
out padded to them in HBM anyway; the padding lanes hold zeros, so the
query's zeros there add nothing). `slots`, each row's last live position
and the layer are scalar-prefetched, and the `BlockSpec` index map picks
block `(slots[i], layer, j)` of the pool in HBM: a block wholly past the
row's last position is neither computed (`pl.when`) nor copied (the index
stays on the row's last live block, so the pipeline issues no DMA). Inside
the last live block positions past `last` are masked in the scores and
zeroed in the values — whatever the pool holds there, a NaN included,
never reaches the output. A padding row (its slot the scratch slot)
computes nothing and moves nothing: its index map holds the block of the
last real row before it, and its output is nought.

Softmax is online, in float32, across a row's blocks; the products take
the pool's type and accumulate in float32. The block is
`latent_block_plan`'s, from the shape alone. Elsewhere than a TPU the
`jax.numpy` twin computes the same (`mla_decode_twin`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NN, NT, _NEG_INF, _dot, _im

# One latent block a DMA, as decode_attention.py sizes its K blocks.
_BLOCK_BYTES = 2**20
_VMEM_BUDGET = 12 * 2**20
_VMEM_LIMIT = 32 * 2**20


@dataclasses.dataclass(frozen=True)
class LatentPlan:
    """block: latent positions a grid step reads of one row.
    vmem_bytes: the step's pipelined blocks (double-buffered) and scratch."""
    block: int
    vmem_bytes: int

    def positions_read(self, length: int, cap: int) -> int:
        """Pool positions the kernel copies for a row whose new token sits
        at `length`: whole blocks up to the one that holds it."""
        return (min(int(length), cap - 1) // self.block + 1) * self.block


def _step_vmem_bytes(block, heads, width, value_width, itemsize):
    """The latent block double-buffered; the query and the output rows
    (double-buffered, heads padded to 8 sublanes); the float32 accumulator
    [H, C] and the two [H, 1] statistics, which pad to 128 lanes."""
    rows = -(-heads // 8) * 8
    kv = 2 * block * width * itemsize
    io = 2 * rows * (width * itemsize + value_width * 4)
    acc = rows * value_width * 4 + 2 * rows * 128 * 4
    return kv + io + acc


def latent_block_plan(cap: int, heads: int, width: int, value_width: int,
                      itemsize: int) -> LatentPlan | None:
    """The schedule for a latent pool of `cap` positions of `width` lanes
    (the first `value_width` of them the latent) read by `heads` query
    heads, or None where the kernel does not serve the shape: a row or a
    latent that does not fill whole 128-lane tiles, a capacity that is no
    multiple of 8 positions, or a step over VMEM. The block is the largest
    power-of-two divisor of `cap` of at most `_BLOCK_BYTES`, and at least
    8 positions (one sublane tile)."""
    if width % 128 or value_width % 128 or value_width > width \
            or cap % 8 or cap < 8:
        return None
    block = 8
    while cap % (block * 2) == 0 and \
            block * 2 * width * itemsize <= _BLOCK_BYTES:
        block *= 2
    need = _step_vmem_bytes(block, heads, width, value_width, itemsize)
    if need > _VMEM_BUDGET:
        return None
    return LatentPlan(block, need)


def _kernel(slots_ref, last_ref, real_ref, layer_ref, q_ref, kv_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block, value_width, sm_scale):
    """Grid (row i, block j). q [H, W]; kv [block, W] of pool row
    slots[i] at the layer; o [H, C]; m, l [H, 1] and acc [H, C] in
    scratch."""
    del slots_ref, layer_ref          # the index maps read them
    i, j = pl.program_id(0), pl.program_id(1)
    last = last_ref[i]
    real = real_ref[i] != 0
    # the block that holds `last` (lax.div: under the package's x64 a `//`
    # traces a convert that Mosaic's lowering recurses on)
    live = jax.lax.div(last, jnp.int32(block))

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked):
        def run():
            q = q_ref[...]
            kv = kv_ref[...]
            s = _dot(q, kv, NT, None) * jnp.float32(sm_scale)  # [H, block]
            c = kv[:, :value_width]
            if masked:
                kpos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                s = jnp.where(kpos <= last, s, jnp.float32(_NEG_INF))
                cpos = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, c.shape, 0)
                c = jnp.where(cpos <= last, c, jnp.zeros((), c.dtype))
            m_prev = m_ref[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)
            alpha = jnp.exp(m_prev - m)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + _dot(
                p if c.dtype == jnp.float32 else p.astype(c.dtype), c, NN,
                None)                                       # [H, C]
            m_ref[...] = m
        return run

    # whole blocks take no mask; blocks past `live` are neither computed
    # nor (the index map) copied; a padding row computes nothing
    pl.when(real & (j < live))(step(False))
    pl.when(real & (j == live))(step(True))

    end = j == pl.num_programs(1) - 1

    @pl.when(end & real)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(end & jnp.logical_not(real))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _row_plan(slots, last, scratch: int):
    """(block slot, last position, real) of each row: a real row reads its
    own slot up to its position; a padding row shows the index map the
    last real row's last block (no copy) — or, before any real row, block
    0 of its own slot."""
    real = slots != scratch
    idx = jnp.arange(slots.shape[0], dtype=jnp.int32)
    prev = jax.lax.cummax(jnp.where(real, idx, -1), axis=0)
    src = jnp.where(real | (prev < 0), idx, prev)
    return slots[src], jnp.where(real | (prev < 0), last, last[src]), \
        real.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("value_width", "sm_scale",
                                             "scratch", "interpret", "block"))
def mla_decode(q, pool, layer, slots, lengths, *, value_width: int,
               sm_scale: float, scratch: int, interpret: bool = False,
               block: int | None = None):
    """One query a row and head over the latent pool, in place. (Jitted,
    the layer an argument: a program of L layers traces and lowers the
    kernel once.) `block` overrides the plan's, for the tests' short
    pools.

    q [b, H, W] in the pool's type: each head's absorbed query and rotary
    query, `[q̂ ‖ q_r ‖ 0]`; pool [rows, L, cap, W]; layer a scalar; slots
    [b] the pool row of each query (`scratch`: a padding row); lengths [b]
    the position of each row's newest token, which the pool already holds:
    row i attends positions [0, min(lengths[i], cap - 1)] of pool row
    slots[i]. -> ô [b, H, value_width] float32 (nought for a padding
    row)."""
    b, heads, width = q.shape
    cap = pool.shape[2]
    plan = latent_block_plan(cap, heads, width, value_width,
                             pool.dtype.itemsize)
    if plan is None or pool.shape[3] != width:
        raise ValueError(
            f"mla_decode does not serve a pool {pool.shape} ({pool.dtype}) "
            f"under {heads} heads of {width} lanes, latent {value_width}: "
            f"see latent_block_plan")
    block = block or plan.block
    last = jnp.minimum(lengths.astype(jnp.int32), cap - 1)
    bslot, blast, real = _row_plan(slots.astype(jnp.int32), last, scratch)

    def kv_index(i, j, slot, last, real, layer):
        at = jax.lax.div(last[i], jnp.int32(block))
        return (slot[i], layer[0],
                jnp.where(real[i] != 0, jnp.minimum(j, at), at), 0)

    kv_spec = pl.BlockSpec((None, None, block, width), _im(kv_index))
    return pl.pallas_call(
        functools.partial(_kernel, block=block, value_width=value_width,
                          sm_scale=sm_scale),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, cap // block),
            in_specs=[pl.BlockSpec((None, heads, width),
                                   _im(lambda i, j, *_: (i, 0, 0))),
                      kv_spec],
            out_specs=pl.BlockSpec((None, heads, value_width),
                                   _im(lambda i, j, *_: (i, 0, 0))),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, value_width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), jnp.float32),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(bslot, blast, real, jnp.asarray(layer, jnp.int32).reshape(1),
      q, pool)


@functools.partial(jax.jit, static_argnames=("value_width", "sm_scale",
                                             "scratch"))
def mla_decode_twin(q, pool, layer, slots, lengths, *, value_width: int,
                    sm_scale: float, scratch: int):
    """`mla_decode` in plain jax.numpy: one layer of the rows
    (`pool[slots, layer]`) and a masked softmax over all `cap` positions;
    operands in the pool's type, scores, softmax and sums in float32. A
    padding row's output is nought, as the kernel's."""
    cap = pool.shape[2]
    rows = pool[slots, layer]                               # [b, cap, W]
    prec = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT
    seen = jnp.arange(cap, dtype=jnp.int32)[None, :] <= jnp.minimum(
        lengths.astype(jnp.int32), cap - 1)[:, None]        # [b, cap]
    s = jnp.einsum("bhw,bmw->bhm", q.astype(rows.dtype), rows,
                   precision=prec, preferred_element_type=jnp.float32)
    s = jnp.where(seen[:, None], s * jnp.float32(sm_scale), _NEG_INF)
    c = jnp.where(seen[:, :, None], rows[..., :value_width],
                  jnp.zeros((), rows.dtype))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhm,bmc->bhc", p.astype(rows.dtype), c, precision=prec,
                     preferred_element_type=jnp.float32)
    return jnp.where((slots != scratch)[:, None, None], out, 0.0)


def latent_attention(q, pool, layer, slots, lengths, *, value_width: int,
                     sm_scale: float, scratch: int):
    """The absorbed read over the pool where it lies: the kernel where the
    program is lowered for a TPU and the kernel serves the shape
    (`latent_block_plan`), the twin elsewhere — decided at lowering, so a
    compile for a described chip takes the kernel."""
    kw = dict(value_width=value_width, sm_scale=float(sm_scale),
              scratch=int(scratch))
    args = (q.astype(pool.dtype), pool, jnp.asarray(layer, jnp.int32),
            slots, lengths)
    if latent_block_plan(pool.shape[2], q.shape[1], q.shape[2], value_width,
                         pool.dtype.itemsize) is None:
        return mla_decode_twin(*args, **kw)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(mla_decode, **kw),
        default=functools.partial(mla_decode_twin, **kw))


__all__ = ["LatentPlan", "latent_block_plan", "mla_decode",
           "mla_decode_twin", "latent_attention"]
