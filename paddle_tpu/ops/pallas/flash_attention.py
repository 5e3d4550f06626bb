"""FlashAttention forward + backward as Pallas TPU kernels.

Role of paddle/phi/kernels/gpu/flash_attn_kernel.cu (+flash_attn_grad_kernel)
in the reference — tiled attention that never materializes the [L, L]
probability matrix in HBM. Streaming softmax over K tiles (the memory win:
O(L·D) HBM traffic instead of O(L²)); backward rematerializes P from the
saved per-row logsumexp, the standard flash backward.

Layout: kernels run on [BH, L, D]; the public wrapper takes paddle's
[B, L, H, D] flash_attention layout. All matmuls accumulate in f32
(preferred_element_type); inputs may be bf16.

The schedule is chosen from the shape (`tile_plan`), not by a flag. A grid
step owns `bh` batch-heads and one `block` x `block` square of the score
matrix; inside it the square is walked in `tile_q` x `tile_k` tiles by
Python loops, so every trip count is static and the compiler sees one
basic block in which the MXU's products, the vector unit's softmax and the
loads of different tiles overlap. A sequence no longer than the largest
block is one square: no grid axis over it, no scratch, no branch. Longer
sequences put the squares on two grid axes with f32 accumulators in VMEM
scratch; under a causal mask the squares above the diagonal are skipped
(`pl.when`, and the index maps stay where they were so that nothing is
copied for them). Which tiles need the mask is known while tracing: tiles
wholly below the diagonal get no iota, compare or select, tiles above it
are not emitted, and only tiles the diagonal crosses are masked.

Row statistics never change layout inside a loop. The forward carries the
running max and sum as [tile_q, 1] columns (keepdims) and turns the
log-sum-exp into a row once per q tile, for the [BH, 1, L] residual — by a
select against the identity and a sum over sublanes, which costs the vector
unit a tenth of what Mosaic's own column-to-row relayout does. The
backward is one kernel and works on the transposed tile S^T = K·Q^T, where
the log-sum-exp and delta rows broadcast along sublanes as they are stored:
dV += P^T·dO and dK += dS^T·Q are canonical products, dQ += (dS^T)^T·K is
the one transposed-lhs product, and S, dP and P are computed once (five
matmuls a tile, where separate dQ and dK/dV kernels need seven). It keeps
the name `flash_bwd_dkv`.

The softmax scale is folded into q, once per q tile, where that is exact
(a power of two, as at head_dim 64: bf16 keeps its mantissa); otherwise the
f32 score tile is scaled.

Dot operands (FLAGS_flash_dot_impl). Every dot states its own precision:
the package sets jax_default_matmul_precision="highest" for f32 parity
outside the kernels, and that default would otherwise ride into Mosaic as
contract_precision<fp32> on a bf16 x bf16 -> f32 tpu.matmul, which the TPU
compiler refuses ("Bad lhs type"). bf16 operands with f32 accumulation is
what the kernel means; f32 operands keep true-f32 passes.
  bf16  storage-dtype operands straight into the MXU — what 'auto' picks.
  f32   cast blocks to f32 before every dot (~4x slower MXU rate).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

NT = (((1,), (1,)), ((), ()))   # a[m,k] @ b[n,k]^T
NN = (((1,), (0,)), ((), ()))   # a[m,k] @ b[k,n]
TN = (((0,), (0,)), ((), ()))   # a[k,m]^T @ b[k,n]


def _im(f):
    """Pin a BlockSpec index map's outputs to int32. The package enables
    jax_enable_x64 (paddle's int64 default), so a literal `0` in an index
    map traces as a weak i64 constant — and Mosaic then fails to legalize
    the index-map function's `func.return` on real TPU hardware (observed
    on-chip: "failed to legalize operation 'func.return' (i32, i32,
    i64)"). CPU cross-lowering does NOT catch this; only the real backend
    does."""
    return lambda *a: tuple(jnp.asarray(v, jnp.int32) for v in f(*a))


def _dot(a, b, dims, impl):
    """f32-accumulated MXU dot. The precision is stated here so the
    package-wide "highest" default never reaches Mosaic on bf16 operands
    (see module docstring)."""
    if impl == "f32":
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- plan --
# What a grid step may hold in VMEM: its pipelined blocks (double-buffered)
# and scratch accumulators, by `_step_vmem_bytes`. The kernels ask Mosaic
# for `_VMEM_LIMIT`; the difference is the compiler's own (score tiles that
# do not fit the vector registers).
_VMEM_BUDGET = 20 * 2**20
_VMEM_LIMIT = 48 * 2**20
_MAX_BLOCK_BYTES = 2048     # block rows x itemsize: 1,024 bf16, 512 f32
_MAX_TILE_BYTES = 512       # tile edge x itemsize: 256 bf16, 128 f32
_MAX_BH = 2


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """block: rows of q and of k/v a grid step owns (one edge: the causal
    diagonal then crosses only the squares i == j). tile_q x tile_k: the
    score tile one pass of the unrolled loop computes. bh: the most
    batch-heads a grid step takes (`bh_per_step` picks a divisor of the
    call's)."""
    block: int
    tile_q: int
    tile_k: int
    bh: int

    def bh_per_step(self, batch_heads: int) -> int:
        return max(n for n in range(1, self.bh + 1) if batch_heads % n == 0)


def _divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is a multiple of 128 and at most cap; n
    itself where n is below 128 (one tile)."""
    if n <= 128:
        return n
    return max(d for d in range(128, min(n, max(cap, 128)) + 1, 128)
               if n % d == 0)


def _step_vmem_bytes(seq_len, head_dim, itemsize, block, bh):
    """Bytes of VMEM one grid step's blocks and scratch take, the larger
    of forward and backward. A [rows, head_dim] block pads its lanes to
    128; a [1, rows] f32 row pads to 8 sublanes; a [rows, 1] f32 column
    pads to 128 lanes."""
    lanes = -(-head_dim // 128) * 128
    io = 2 * bh * block * lanes * itemsize            # double-buffered
    row = 2 * bh * 8 * block * 4
    acc = bh * block * lanes * 4
    col = bh * block * 128 * 4
    if seq_len == block:
        return max(4 * io + row, 7 * io + 2 * row)
    dq_out = 2 * bh * seq_len * lanes * itemsize
    dq_acc = bh * seq_len * lanes * 4
    return max(4 * io + row + acc + 2 * col,
               6 * io + 2 * row + dq_out + dq_acc + 2 * acc)


def tile_plan(seq_len: int, head_dim: int, itemsize: int,
              causal: bool) -> TilePlan | None:
    """The schedule for [*, seq_len, head_dim] operands of `itemsize`
    bytes, or None where the kernels do not serve the shape: a sequence
    that is neither a multiple of 128 nor a single tile of a multiple of
    8 rows, a head wider than 256, or a step that does not fit VMEM."""
    del causal  # the same squares and tiles; the mask only skips some
    if head_dim > 256 or seq_len < 8 or seq_len % 8:
        return None
    if seq_len > 128 and seq_len % 128:
        return None
    block = _divisor(seq_len, _MAX_BLOCK_BYTES // itemsize)
    # wider heads move more bytes a tile: keep tile_k x head_dim constant
    tile = _MAX_TILE_BYTES // itemsize
    tile_q = _divisor(block, tile)
    tile_k = _divisor(block, tile * 128 // max(head_dim, 128))
    for bh in range(_MAX_BH, 0, -1):
        if _step_vmem_bytes(seq_len, head_dim, itemsize, block,
                            bh) <= _VMEM_BUDGET:
            return TilePlan(block, tile_q, tile_k, bh)
    return None


def _causal_span(r0, rows, c0, cols, diag):
    """(width, clear) for the score tile of rows r0.. and columns c0.. of
    a square on the causal diagonal (`diag`): only the leading `width`
    columns hold an entry the mask leaves (0: the tile is skipped), and
    the leading `clear` of those hold none it removes. Every tile of any
    other square is whole and clear."""
    if not diag:
        return cols, cols
    width = max(0, min(cols, r0 + rows - c0))
    return width, max(0, min(width, r0 - c0))


def _mask_tail(x, clear, k_axis, q0, k0):
    """The score tile x (queries q0.., keys k0.. along `k_axis`) with the
    entries whose key comes after their query at -inf; the first `clear`
    keys hold none and are left alone."""
    tail = jax.lax.slice_in_dim(x, clear, x.shape[k_axis], axis=k_axis)
    keys = k0 + clear + jax.lax.broadcasted_iota(jnp.int32, tail.shape,
                                                 k_axis)
    queries = q0 + jax.lax.broadcasted_iota(jnp.int32, tail.shape,
                                            1 - k_axis)
    tail = jnp.where(queries >= keys, tail, jnp.float32(_NEG_INF))
    if not clear:
        return tail
    return jnp.concatenate(
        [jax.lax.slice_in_dim(x, 0, clear, axis=k_axis), tail], axis=k_axis)


def _compiler_params(interpret, semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _scale_on_q(sm_scale: float) -> bool:
    """Folding the scale into q is exact when it is a power of two."""
    return math.frexp(sm_scale)[0] == 0.5


# ------------------------------------------------------------- forward --
def _fwd_tiles(q_ref, k_ref, v_ref, b, carry, *, sm_scale, diag, plan,
               impl):
    """One batch-head's square: for each q tile, the streaming softmax
    over the k tiles the mask leaves. `carry(qt)` gives the (m, l, acc) a
    q tile starts from, or None for a fresh one. Yields (qt, m, l, acc)."""
    block = q_ref.shape[1]
    tq, tk = plan.tile_q, plan.tile_k
    on_q = _scale_on_q(sm_scale)
    for qt in range(block // tq):
        r0 = qt * tq
        q = q_ref[b, r0:r0 + tq, :]
        if on_q:
            q = q * jnp.asarray(sm_scale, q.dtype)
        state = carry(qt)
        for kt in range(block // tk):
            c0 = kt * tk
            width, clear = _causal_span(r0, tq, c0, tk, diag)
            if not width:
                continue
            k = k_ref[b, c0:c0 + width, :]
            v = v_ref[b, c0:c0 + width, :]
            s = _dot(q, k, NT, impl)                     # (tq, width) f32
            if not on_q:
                s = s * jnp.float32(sm_scale)
            if clear < width:       # the columns the diagonal crosses
                s = _mask_tail(s, clear, 1, r0, c0)
            m = jnp.max(s, axis=1, keepdims=True)           # (tq, 1)
            if state is not None:
                m = jnp.maximum(state[0], m)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = _dot(p if impl == "f32" else p.astype(v.dtype), v, NN,
                       impl)
            if state is not None:
                alpha = jnp.exp(state[0] - m)
                l = state[1] * alpha + l
                acc = state[2] * alpha + acc
            state = (m, l, acc)
        yield (qt, *state)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, sm_scale,
                causal, plan, impl):
    """q/o blocks [bh, block, D] at square row i, k/v at column j, lse
    [bh, 1, block]. With one square there is no scratch and no branch."""
    bh, block, _ = q_ref.shape
    tq = plan.tile_q
    tiles = functools.partial(_fwd_tiles, q_ref, k_ref, v_ref,
                              sm_scale=sm_scale, plan=plan, impl=impl)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1))

    def rows(qt):
        return slice(qt * tq, (qt + 1) * tq)

    def finish(b, qt, m, l, acc):
        o_ref[b, rows(qt), :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        # column -> row by the identity's diagonal: what Mosaic makes of
        # `[:, 0]` is a quarter of the whole kernel's bundles
        lse_ref[b, :, rows(qt)] = jnp.sum(
            jnp.where(eye, m + jnp.log(l), jnp.float32(0)), axis=0,
            keepdims=True)

    if not scratch:
        for b in range(bh):
            for qt, m, l, acc in tiles(b, lambda qt: None, diag=causal):
                finish(b, qt, m, l, acc)
        return

    m_ref, l_ref, acc_ref = scratch
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def carried(b, qt):
        return (m_ref[b, rows(qt), :], l_ref[b, rows(qt), :],
                acc_ref[b, rows(qt), :])

    def square(diag):
        def run():
            for b in range(bh):
                for qt, m, l, acc in tiles(
                        b, functools.partial(carried, b), diag=diag):
                    m_ref[b, rows(qt), :] = m
                    l_ref[b, rows(qt), :] = l
                    acc_ref[b, rows(qt), :] = acc
        return run

    # under the mask a row of squares ends on the diagonal (i == j)
    if causal:
        pl.when(j < i)(square(False))
        pl.when(j == i)(square(True))
    else:
        square(False)()

    @pl.when(j == (i if causal else pl.num_programs(2) - 1))
    def _():
        for b in range(bh):
            for qt in range(block // tq):
                finish(b, qt, *carried(b, qt))


def _fwd(q, k, v, sm_scale, causal, interpret, impl, plan=None):
    """[BH, L, D] q, k, v -> (normalised output [BH, L, D], log-sum-exp
    of the scaled scores per row [BH, 1, L] f32)."""
    batch_heads, L, d = q.shape
    if plan is None:
        plan = tile_plan(L, d, q.dtype.itemsize, causal)
    if plan is None:
        raise ValueError(
            f"flash attention does not serve seq {L} x head_dim {d} "
            f"({q.dtype}): see flash_attention_supported")
    bh, block = plan.bh_per_step(batch_heads), plan.block
    n = L // block
    if causal:       # squares above the diagonal copy nothing
        kv_map = _im(lambda b, i, j: (b, jnp.minimum(i, j), 0))
    else:
        kv_map = _im(lambda b, i, j: (b, j, 0))
    scratch = [] if n == 1 else [
        pltpu.VMEM((bh, block, 1), jnp.float32),
        pltpu.VMEM((bh, block, 1), jnp.float32),
        pltpu.VMEM((bh, block, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          plan=plan, impl=impl),
        name="flash_fwd",
        grid=(batch_heads // bh, n, n),
        in_specs=[
            pl.BlockSpec((bh, block, d), _im(lambda b, i, j: (b, i, 0))),
            pl.BlockSpec((bh, block, d), kv_map),
            pl.BlockSpec((bh, block, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((bh, block, d), _im(lambda b, i, j: (b, i, 0))),
            pl.BlockSpec((bh, 1, block), _im(lambda b, i, j: (b, 0, i))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch_heads, L, d), q.dtype),
            jax.ShapeDtypeStruct((batch_heads, 1, L), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, ("parallel", "parallel", "arbitrary")),
    )(q, k, v)


# ------------------------------------------------------------ backward --
def _bwd_tiles(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, b, dq, dk,
               dv, *, sm_scale, diag, plan, impl):
    """One batch-head's square of the backward on transposed tiles.
    dq (per q tile) and dk, dv (per k tile) are lists of f32 values or
    None for "nothing added yet"; they are updated in place. dq and dk
    leave the scale out: q comes in scaled (or the scores are), dk is
    dS^T·(scale·q) and the caller scales dq once."""
    block = q_ref.shape[1]
    tq, tk = plan.tile_q, plan.tile_k
    on_q = _scale_on_q(sm_scale)

    def add(acc, x):
        """acc + x over x's rows (fewer than acc's where the mask cut the
        tile, more where it cut the earlier ones)."""
        if acc is None:
            return x
        n, m = x.shape[0], acc.shape[0]
        if n == m:
            return acc + x
        if n < m:
            return jnp.concatenate([acc[:n] + x, acc[n:]], axis=0)
        return jnp.concatenate([acc + x[:m], x[m:]], axis=0)

    for kt in range(block // tk):
        c0 = kt * tk
        for qt in range(block // tq):
            r0 = qt * tq
            # rows of the transposed tile are k's: the mask keeps the
            # leading `width`, and none of the leading `clear` is touched
            width, clear = _causal_span(r0, tq, c0, tk, diag)
            if not width:
                continue
            k = k_ref[b, c0:c0 + width, :]
            v = v_ref[b, c0:c0 + width, :]
            q = q_ref[b, r0:r0 + tq, :]
            do = do_ref[b, r0:r0 + tq, :]
            if on_q:
                q = q * jnp.asarray(sm_scale, q.dtype)
            st = _dot(k, q, NT, impl)                    # (width, tq) f32
            if not on_q:
                st = st * jnp.float32(sm_scale)
            if clear < width:
                st = _mask_tail(st, clear, 0, r0, c0)
            # lse and delta are rows: they broadcast along sublanes
            pt = jnp.exp(st - lse_ref[b, :, r0:r0 + tq])
            dpt = _dot(v, do, NT, impl)
            dst = pt * (dpt - delta_ref[b, :, r0:r0 + tq])
            if impl != "f32":
                pt = pt.astype(do.dtype)
                dst = dst.astype(q.dtype)
            dv[kt] = add(dv[kt], _dot(pt, do, NN, impl))
            dk[kt] = add(dk[kt], _dot(dst, q, NN, impl))
            dq[qt] = add(dq[qt], _dot(dst, k, TN, impl))


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, *scratch, sm_scale, causal, plan, impl):
    """Grid (batch-heads, k square j, q square i): q/do/lse/delta at i,
    k/v and dk/dv at j, dq whole [bh, L, D]. dk and dv gather over the
    inner axis, dq over both — in f32 scratch where there is more than one
    square."""
    bh, block, _ = q_ref.shape
    tq, tk = plan.tile_q, plan.tile_k
    nq, nk = block // tq, block // tk
    on_q = _scale_on_q(sm_scale)
    tiles = functools.partial(_bwd_tiles, q_ref, k_ref, v_ref, do_ref,
                              lse_ref, delta_ref, sm_scale=sm_scale,
                              plan=plan, impl=impl)
    # dk = scale·dS^T·q: already in when q came scaled
    k_scale = jnp.float32(1.0 if on_q else sm_scale)

    if not scratch:
        for b in range(bh):
            # every q tile and every k tile of a square meets a tile the
            # mask leaves, so no None survives
            dq, dk, dv = [None] * nq, [None] * nk, [None] * nk
            tiles(b, dq, dk, dv, diag=causal)
            for qt in range(nq):
                dq_ref[b, qt * tq:(qt + 1) * tq, :] = (
                    dq[qt] * jnp.float32(sm_scale)).astype(dq_ref.dtype)
            for kt in range(nk):
                cols = slice(kt * tk, (kt + 1) * tk)
                dk_ref[b, cols, :] = (dk[kt] * k_scale).astype(dk_ref.dtype)
                dv_ref[b, cols, :] = dv[kt].astype(dv_ref.dtype)
        return

    dq_acc, dk_acc, dv_acc = scratch
    j, i = pl.program_id(1), pl.program_id(2)
    n = pl.num_programs(2)

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    # a k square's first q square: the diagonal's under the mask
    @pl.when(i == (j if causal else 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def square(diag):
        def run():
            base = pl.multiple_of(i * block, block)
            for b in range(bh):
                dq = [dq_acc[b, pl.ds(base + qt * tq, tq), :]
                      for qt in range(nq)]
                dk = [dk_acc[b, kt * tk:(kt + 1) * tk, :]
                      for kt in range(nk)]
                dv = [dv_acc[b, kt * tk:(kt + 1) * tk, :]
                      for kt in range(nk)]
                tiles(b, dq, dk, dv, diag=diag)
                for qt in range(nq):
                    dq_acc[b, pl.ds(base + qt * tq, tq), :] = dq[qt]
                for kt in range(nk):
                    cols = slice(kt * tk, (kt + 1) * tk)
                    dk_acc[b, cols, :] = dk[kt]
                    dv_acc[b, cols, :] = dv[kt]
        return run

    if causal:
        pl.when(i == j)(square(True))
        pl.when(i > j)(square(False))
    else:
        square(False)()

    @pl.when(i == n - 1)
    def _():
        dk_ref[...] = (dk_acc[...] * k_scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((j == n - 1) & (i == n - 1))
    def _():
        dq_ref[...] = (dq_acc[...] * jnp.float32(sm_scale)
                       ).astype(dq_ref.dtype)


def _bwd(sm_scale, causal, interpret, impl, res, g, plan=None):
    q, k, v, o, lse = res
    batch_heads, L, d = q.shape
    if plan is None:
        plan = tile_plan(L, d, q.dtype.itemsize, causal)
    bh, block = plan.bh_per_step(batch_heads), plan.block
    n = L // block
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if causal:       # squares above the diagonal copy nothing
        q_map = _im(lambda b, j, i: (b, jnp.maximum(i, j), 0))
        row_map = _im(lambda b, j, i: (b, 0, jnp.maximum(i, j)))
    else:
        q_map = _im(lambda b, j, i: (b, i, 0))
        row_map = _im(lambda b, j, i: (b, 0, i))
    kv_spec = pl.BlockSpec((bh, block, d), _im(lambda b, j, i: (b, j, 0)))
    q_spec = pl.BlockSpec((bh, block, d), q_map)
    row_spec = pl.BlockSpec((bh, 1, block), row_map)
    scratch = [] if n == 1 else [
        pltpu.VMEM((bh, L, d), jnp.float32),
        pltpu.VMEM((bh, block, d), jnp.float32),
        pltpu.VMEM((bh, block, d), jnp.float32)]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, causal=causal,
                          plan=plan, impl=impl),
        name="flash_bwd_dkv",
        grid=(batch_heads // bh, n, n),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((bh, L, d), _im(lambda b, j, i: (b, 0, 0))),
            kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((batch_heads, L, d), q.dtype),
            jax.ShapeDtypeStruct((batch_heads, L, d), k.dtype),
            jax.ShapeDtypeStruct((batch_heads, L, d), v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=_compiler_params(
            interpret, ("parallel", "arbitrary", "arbitrary")),
    )(q, k, v, g, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, sm_scale, causal, interpret, impl):
    out, _ = _fwd(q, k, v, sm_scale, causal, interpret, impl)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, interpret, impl):
    out, lse = _fwd(q, k, v, sm_scale, causal, interpret, impl)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


# ------------------------------------------------- dot-impl resolution --
_DOT_IMPLS = ("bf16", "f32")


def _resolve_dot_impl() -> str:
    """Map FLAGS_flash_dot_impl to a concrete strategy; 'auto' is 'bf16'."""
    from ...core.flags import flag

    impl = flag("flash_dot_impl")
    if impl == "auto":
        return "bf16"
    if impl not in _DOT_IMPLS:
        raise ValueError(
            f"FLAGS_flash_dot_impl must be auto|{'|'.join(_DOT_IMPLS)}, "
            f"got {impl!r}")
    return impl


def flash_attention_supported(q_shape, dtype, causal: bool) -> bool:
    """Whether `tile_plan` admits [B, L, H, D] operands of `dtype`."""
    return tile_plan(q_shape[1], q_shape[3], jnp.dtype(dtype).itemsize,
                     causal) is not None


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    interpret: bool = False, impl: str | None = None):
    """q, k, v: [B, L, H, D] (paddle flash_attention layout) -> [B, L, H, D].

    Self/cross attention with equal q/k lengths; bf16 or f32 inputs,
    f32 MXU accumulation. `impl` overrides the FLAGS_flash_dot_impl
    resolution (see module docstring) for tests."""
    B, L, H, D = q.shape
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if impl is None:
        impl = _resolve_dot_impl()

    def to_bh(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * H, x.shape[1], D)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), float(sm_scale), bool(causal),
                 bool(interpret), str(impl))
    return jnp.swapaxes(out.reshape(B, H, L, D), 1, 2)
