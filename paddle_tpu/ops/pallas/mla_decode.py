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
query's zeros there add nothing).

**The copy loop.** The pool stays in HBM (`memory_space=pl.ANY`) and the
kernel copies what it reads itself, by `make_async_copy` into
`_IN_FLIGHT + 1` VMEM buffers with a DMA semaphore each. The grid is one
step a row, never one a block. A row whose new token sits at `last`
reads `pool[slot, layer, 0 : (last // chunk + 1)·chunk]` and nothing past
it: each whole block before its last in one copy, its last block in one
copy a chunk, up to the chunk that holds `last`. The real rows' blocks
form one chain, and a cursor in SMEM walks it `_IN_FLIGHT` blocks ahead
of the products: while a block is computed the next ones are copied,
and while a row's last block is computed the next REAL row's first is
already in flight, so the copies run on from row to row without a
pause. Each row's slot and last position, the blocks of the rows before
it (which name its buffers) and the next real row are scalar-prefetched
with the layer. Only the last block is masked: positions past `last` are
masked in the scores and zeroed in the values — whatever the buffer holds
there (the pool's own positions to the chunk's end, then what the buffer
held before), a NaN included, never reaches the output. A padding row (its slot the scratch slot) is left
out of the chain: it copies and computes nothing, and its output is
nought.

Softmax is online, in float32, across a row's blocks; the products take
the pool's type and accumulate in float32. The block and the chunk are
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

# A block is what one pass of the products takes of a row, a chunk what one
# copy reads of a row's last block: a row copies less than a chunk past its
# position, and the products pay their fixed cost a block. Chosen on a v5e
# at the latent cell's shapes (`tools/mla_chunk_bench.py`; the table in
# PERF.md): smaller blocks pay that cost more often, and a third copy in
# flight gained nothing measurable.
_BLOCK_BYTES = 2**20
_CHUNK_BYTES = 2**17
_IN_FLIGHT = 2
_VMEM_BUDGET = 12 * 2**20
_VMEM_LIMIT = 32 * 2**20


@dataclasses.dataclass(frozen=True)
class LatentPlan:
    """block: latent positions of one row the products take at once;
    chunk: positions one copy of a row's last block reads (a whole block
    before it is one copy). vmem_bytes: the copy buffers, the pipelined
    query and output rows, and the statistics."""
    block: int
    chunk: int
    vmem_bytes: int

    def positions_read(self, length: int, cap: int) -> int:
        """Pool positions the kernel copies for a row whose new token sits
        at `length`: whole chunks up to the one that holds it."""
        return (min(int(length), cap - 1) // self.chunk + 1) * self.chunk


def _step_vmem_bytes(block, heads, width, value_width, itemsize):
    """The copy buffers; the query and the output rows (double-buffered,
    heads padded to 8 sublanes); the two [H, 1] float32 statistics, which
    pad to 128 lanes."""
    rows = -(-heads // 8) * 8
    kv = (_IN_FLIGHT + 1) * block * width * itemsize
    io = 2 * rows * (width * itemsize + value_width * 4)
    return kv + io + 2 * rows * 128 * 4


def _pow2_under(cap: int, nbytes: int, row_bytes: int) -> int:
    """The largest power-of-two divisor of `cap` whose rows fill at most
    `nbytes`, and at least 8 (one sublane tile)."""
    n = 8
    while cap % (n * 2) == 0 and n * 2 * row_bytes <= nbytes:
        n *= 2
    return n


def latent_block_plan(cap: int, heads: int, width: int, value_width: int,
                      itemsize: int) -> LatentPlan | None:
    """The schedule for a latent pool of `cap` positions of `width` lanes
    (the first `value_width` of them the latent) read by `heads` query
    heads, or None where the kernel does not serve the shape: a row or a
    latent that does not fill whole 128-lane tiles, a capacity that is no
    multiple of 8 positions, or a step over VMEM. The block is the largest
    power-of-two divisor of `cap` of at most `_BLOCK_BYTES`, the chunk that
    of the block of at most `_CHUNK_BYTES`; each at least 8 positions."""
    if width % 128 or value_width % 128 or value_width > width \
            or cap % 8 or cap < 8:
        return None
    block = _pow2_under(cap, _BLOCK_BYTES, width * itemsize)
    chunk = _pow2_under(block, _CHUNK_BYTES, width * itemsize)
    need = _step_vmem_bytes(block, heads, width, value_width, itemsize)
    if need > _VMEM_BUDGET:
        return None
    return LatentPlan(block, chunk, need)


def _kernel(slots_ref, last_ref, before_ref, next_ref, layer_ref, q_ref,
            pool_ref, o_ref, buf_ref, sem_ref, m_ref, l_ref, cur_ref, *,
            block, chunk, value_width, sm_scale):
    """Grid (row i,). q [H, W]; pool [rows, L, cap, W] in HBM; o [H, C],
    the float32 sum until the row's end; buf [d + 1, block, W] and a DMA
    semaphore each, d = `_IN_FLIGHT`; m, l [H, 1]; cur the (row, block)
    the next copy reads. Block g of the chain (the real rows' blocks one
    after another) lies in buffer g % (d + 1): while it is computed the d
    after it are copied. A row's last block is copied by the chunks up to
    the one that holds `last`, the rest of its buffer left as it was."""
    i = pl.program_id(0)
    last = last_ref[i]                      # -1: a padding row
    rows = pl.num_programs(0)
    layer = layer_ref[0]
    B, S = jnp.int32(block), jnp.int32(chunk)
    nbuf = jnp.int32(_IN_FLIGHT + 1)

    def copy(row, at, size, g):
        """Positions [at, at + size) of `row` into the buffer of block g,
        at the same offset in the block."""
        b = jax.lax.rem(g, nbuf)
        return pltpu.make_async_copy(
            pool_ref.at[slots_ref[row], layer,
                        pl.ds(pl.multiple_of(at, chunk), size)],
            buf_ref.at[b, pl.ds(pl.multiple_of(jax.lax.rem(at, B), chunk),
                                size)],
            sem_ref.at[b])

    def copies(row, n, g, act):
        """Start (or wait for) the copies of block n of `row`: one of the
        whole block, or, the row's last, one a chunk up to its position
        (lax.div: under the package's x64 a `//` traces a convert that
        Mosaic's lowering recurses on)."""
        end = last_ref[row]
        whole = n < jax.lax.div(end, B)

        @pl.when(whole)
        def _():
            act(copy(row, n * B, block, g))

        @pl.when(jnp.logical_not(whole))
        def _():
            def one(k, carry):
                act(copy(row, n * B + k * S, chunk, g))
                return carry
            jax.lax.fori_loop(jnp.int32(0),
                              jax.lax.div(jax.lax.rem(end, B), S) + 1, one,
                              jnp.int32(0))

    def issue(g):
        """Start the copies of chain block g, the one `cur` names, and move
        `cur` on: to the row's next block, or past its last to the next
        real row's first."""
        row, n = cur_ref[0], cur_ref[1]

        @pl.when(row < rows)
        def _():
            copies(row, n, g, lambda c: c.start())
            more = n < jax.lax.div(last_ref[row], B)
            cur_ref[0] = jnp.where(more, row, next_ref[row])
            cur_ref[1] = jnp.where(more, n + 1, jnp.int32(0))

    @pl.when(last >= 0)
    def _():
        blocks = jax.lax.div(last, B) + 1
        g0 = before_ref[i]

        @pl.when(g0 == 0)                   # the first real row starts
        def _():
            cur_ref[0] = i
            cur_ref[1] = jnp.int32(0)
            for g in range(_IN_FLIGHT):
                issue(jnp.int32(g))

        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

        def products(n, masked):
            g = g0 + n
            issue(g + _IN_FLIGHT)           # before this block's products
            copies(i, n, g, lambda c: c.wait())
            kv = buf_ref[jax.lax.rem(g, nbuf)]
            s = _dot(q_ref[...], kv, NT, None) * jnp.float32(sm_scale)
            c = kv[:, :value_width]                         # s [H, block]
            if masked:
                # past `last`: the pool's positions to the chunk's end,
                # then whatever the buffer held before — any a NaN
                kpos = n * B + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        1)
                s = jnp.where(kpos <= last, s, jnp.float32(_NEG_INF))
                cpos = n * B + jax.lax.broadcasted_iota(jnp.int32, c.shape,
                                                        0)
                c = jnp.where(cpos <= last, c, jnp.zeros((), c.dtype))
            m_prev = m_ref[...]
            m = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m)
            alpha = jnp.exp(m_prev - m)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            o_ref[...] = o_ref[...] * alpha + _dot(
                p if c.dtype == jnp.float32 else p.astype(c.dtype), c, NN,
                None)                                       # [H, C]
            m_ref[...] = m

        # whole blocks take no mask; the last one does
        jax.lax.fori_loop(jnp.int32(0), blocks - 1,
                          lambda n, carry: (products(n, False), carry)[1],
                          jnp.int32(0))
        products(blocks - 1, True)
        o_ref[...] = o_ref[...] / l_ref[...]

    @pl.when(last < 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _chain(slots, lengths, cap: int, block: int, scratch: int):
    """(last position, blocks before, next real row) of each row: a
    padding row's last position is -1 and it holds no block; a row's
    blocks follow those of every real row before it; after the last real
    row the next is `b`, none."""
    b = slots.shape[0]
    real = slots != scratch
    last = jnp.where(real, jnp.minimum(lengths.astype(jnp.int32), cap - 1),
                     -1)
    blocks = jnp.where(real, last // block + 1, 0)
    before = jnp.cumsum(blocks) - blocks
    after = jax.lax.cummin(
        jnp.where(real, jnp.arange(b, dtype=jnp.int32), b), axis=0,
        reverse=True)
    nxt = jnp.concatenate([after[1:], jnp.full((1,), b, jnp.int32)])
    return last, before.astype(jnp.int32), nxt


@functools.partial(jax.jit, static_argnames=("value_width", "sm_scale",
                                             "scratch", "interpret"))
def mla_decode(q, pool, layer, slots, lengths, *, value_width: int,
               sm_scale: float, scratch: int, interpret: bool = False):
    """One query a row and head over the latent pool, in place. (Jitted,
    the layer an argument: a program of L layers traces and lowers the
    kernel once.)

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
    slots = slots.astype(jnp.int32)
    last, before, nxt = _chain(slots, lengths, cap, plan.block, scratch)
    row = pl.BlockSpec((None, heads, width), _im(lambda i, *_: (i, 0, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, block=plan.block, chunk=plan.chunk,
                          value_width=value_width, sm_scale=sm_scale),
        name="mla_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, heads, value_width),
                                   _im(lambda i, *_: (i, 0, 0))),
            scratch_shapes=[
                pltpu.VMEM((_IN_FLIGHT + 1, plan.block, width), pool.dtype),
                pltpu.SemaphoreType.DMA((_IN_FLIGHT + 1,)),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.VMEM((heads, 1), jnp.float32),
                pltpu.SMEM((2,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, value_width), jnp.float32),
        interpret=interpret,
        # the copies run on across rows: the grid is walked in order
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(slots, last, before, nxt, jnp.asarray(layer, jnp.int32).reshape(1),
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
