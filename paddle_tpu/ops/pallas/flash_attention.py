"""FlashAttention forward + backward as Pallas TPU kernels.

Role of paddle/phi/kernels/gpu/flash_attn_kernel.cu (+flash_attn_grad_kernel)
in the reference — tiled attention that never materializes the [L, L]
probability matrix in HBM. Streaming softmax over K blocks (the memory win:
O(L·D) HBM traffic instead of O(L²)); backward rematerializes P from the
saved per-row logsumexp, the standard flash backward.

Layout: kernels run on [BH, L, D]; the public wrapper takes paddle's
[B, L, H, D] flash_attention layout. All matmuls accumulate in f32
(preferred_element_type); inputs may be bf16.

Dot strategies (FLAGS_flash_dot_impl). Every dot states its own
precision: the package sets jax_default_matmul_precision="highest" for
f32 parity outside the kernels, and that default would otherwise ride
into Mosaic as contract_precision<fp32> on a bf16 x bf16 -> f32
tpu.matmul, which the TPU compiler refuses ("Bad lhs type"). bf16
operands with f32 accumulation is what the kernel means; f32 operands
keep true-f32 passes.
  bf16  storage-dtype operands straight into NT/TN dots — the form
        'auto' picks.
  nn    every dot in canonical NN form: K and V arrive pre-transposed
        ([BH, D, L], a cheap XLA transpose outside the kernel) and the
        backward's P^T/dS^T products transpose the f32 block in-kernel
        before the MXU dot.
  nn2   nn without ANY in-kernel transpose: the dK/dV kernel additionally
        takes Q^T/dO^T ([BH, D, L], XLA transposes outside) and emits
        dK^T/dV^T, which XLA transposes back — dv^T = do^T·P and
        dk^T = q^T·dS are already canonical NN.
  f32   cast blocks to f32 before every dot (~4x slower MXU rate).
  auto  bf16.
All four compile for the v5e (tests/test_chip_compile.py); which of the
three alternatives survive is ROADMAP D3's call.
"""
from __future__ import annotations

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


def _causal_mask(qi, kj, bq, bk):
    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return rows >= cols


def _dot(a, b, dims, impl):
    """f32-accumulated MXU dot under the chosen strategy. For impl='nn'
    the CALLER must already present the operands in canonical NN form —
    this helper only handles the bf16-vs-f32 operand question. The
    precision is stated here so the package-wide "highest" default never
    reaches Mosaic on bf16 operands (see module docstring)."""
    if impl == "f32":
        a = a.astype(jnp.float32)
        b = b.astype(jnp.float32)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


# ------------------------------------------------------------- forward --
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, seq_len, impl):
    """impl 'bf16'/'f32': k_ref/v_ref are [1, L, D]. impl 'nn': k_ref is
    K^T [1, D, L] so the score dot is canonical NN; v stays [1, L, D]
    (p@v is already NN)."""
    qi = pl.program_id(1)
    # keep q/k/v in their storage dtype (bf16) INTO the dots: the MXU
    # runs bf16 inputs at 4x its f32 rate and still accumulates f32 via
    # preferred_element_type
    q = q_ref[0]  # (bq, D)
    num_k = seq_len // block_k
    # all loop bounds pinned to int32: the package enables jax_enable_x64
    # (paddle's int64 default) and Mosaic cannot lower 64-bit indices
    kmax = jnp.minimum(
        ((qi + 1) * block_q + block_k - 1) // jnp.int32(block_k),
        num_k).astype(jnp.int32) if causal else jnp.int32(num_k)

    def body(j, carry):
        m, l, acc = carry
        if impl in ("nn", "nn2"):
            kt = k_ref[0, :, pl.ds(j * block_k, block_k)]   # (D, bk)
            s = _dot(q, kt, NN, impl)
        else:
            k = k_ref[0, pl.ds(j * block_k, block_k), :]    # (bk, D)
            s = _dot(q, k, NT, impl)
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = s * sm_scale  # scale in f32 (bf16 q*scale loses precision)
        if causal:
            s = jnp.where(_causal_mask(qi, j, block_q, block_k), s,
                          jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + _dot(
            p.astype(v.dtype) if impl != "f32" else p, v, NN, impl)
        return m_new, l_new, acc_new

    d = q_ref.shape[-1]
    init = (jnp.full((block_q,), _NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32))
    m, l, acc = jax.lax.fori_loop(jnp.int32(0), kmax, body, init)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret, impl):
    bh, L, d = q.shape
    grid = (bh, L // block_q)
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             block_q=block_q, block_k=block_k, seq_len=L,
                             impl=impl)
    if impl in ("nn", "nn2"):
        k_in = jnp.swapaxes(k, 1, 2)  # [bh, D, L], XLA transpose (cheap)
        k_spec = pl.BlockSpec((1, d, L), _im(lambda b, i: (b, 0, 0)))
    else:
        k_in = k
        k_spec = pl.BlockSpec((1, L, d), _im(lambda b, i: (b, 0, 0)))
    return pl.pallas_call(
        kern,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), _im(lambda b, i: (b, i, 0))),
            k_spec,
            pl.BlockSpec((1, L, d), _im(lambda b, i: (b, 0, 0))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), _im(lambda b, i: (b, i, 0))),
            pl.BlockSpec((1, 1, block_q), _im(lambda b, i: (b, 0, i))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, L), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k_in, v)


# ------------------------------------------------------------ backward --
def _dq_kmax(qi, block_q, block_k, seq_len, causal):
    num_k = seq_len // block_k
    return jnp.minimum(
        ((qi + 1) * block_q + block_k - 1) // jnp.int32(block_k),
        num_k).astype(jnp.int32) if causal else jnp.int32(num_k)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               sm_scale, causal, block_q, block_k, seq_len, impl):
    """bf16/f32 impls: k_ref/v_ref are [1, L, D]; s and dp run NT."""
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    kmax = _dq_kmax(qi, block_q, block_k, seq_len, causal)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]        # (bk, D)
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k, NT, impl) * sm_scale
        dp = _dot(do, v, NT, impl)
        if causal:
            s = jnp.where(_causal_mask(qi, j, block_q, block_k), s,
                          jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + _dot(ds.astype(k.dtype) if impl != "f32" else ds,
                         k, NN, impl)

    d = q_ref.shape[-1]
    dq = jax.lax.fori_loop(jnp.int32(0), kmax, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dq_kernel_nn(q_ref, k_ref, kt_ref, vt_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, *, sm_scale, causal, block_q, block_k, seq_len):
    """nn impl: kt_ref/vt_ref are the [1, D, L] transposes feeding the
    canonical-NN s/dp dots; k_ref keeps [1, L, D] for the ds@k dot."""
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    kmax = _dq_kmax(qi, block_q, block_k, seq_len, causal)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]        # (bk, D)
        kt = kt_ref[0, :, pl.ds(j * block_k, block_k)]      # (D, bk)
        vt = vt_ref[0, :, pl.ds(j * block_k, block_k)]
        s = _dot(q, kt, NN, "nn") * sm_scale
        dp = _dot(do, vt, NN, "nn")
        if causal:
            s = jnp.where(_causal_mask(qi, j, block_q, block_k), s,
                          jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse[:, None])
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(k.dtype)
        return dq + _dot(ds, k, NN, "nn")

    d = q_ref.shape[-1]
    dq = jax.lax.fori_loop(jnp.int32(0), kmax, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, sm_scale, causal, block_q, block_k, seq_len,
                impl):
    """impl 'bf16'/'f32': k_ref/v_ref are [1, block_k, D] blocks, the
    P^T/dS^T dots run TN. impl 'nn': k_ref/v_ref are K^T/V^T blocks
    [1, D, block_k]; P^T and dS^T materialize via an in-kernel f32
    transpose, keeping every MXU dot canonical NN."""
    kj = pl.program_id(1)
    num_q = seq_len // block_q
    qstart = ((kj * block_k) // jnp.int32(block_q)).astype(jnp.int32) \
        if causal else jnp.int32(0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        if impl == "nn":
            kt = k_ref[0]                                   # (D, bk)
            vt = v_ref[0]
            s = _dot(q, kt, NN, impl) * sm_scale
            dp = _dot(do, vt, NN, impl)
        else:
            k = k_ref[0]                                    # (bk, D)
            v = v_ref[0]
            s = _dot(q, k, NT, impl) * sm_scale
            dp = _dot(do, v, NT, impl)
        if causal:
            s = jnp.where(_causal_mask(i, kj, block_q, block_k), s,
                          jnp.float32(_NEG_INF))
        p32 = jnp.exp(s - lse[:, None])  # (bq, bk) f32
        # keep the f32 p/ds for the second factor's precision (the bf16
        # roundtrip would drop mantissa bits for free)
        ds32 = p32 * (dp - delta[:, None]) * sm_scale
        if impl == "nn":
            # f32 transpose in-VMEM, then cast -> canonical NN bf16 dots
            pt = p32.T.astype(do.dtype)                     # (bk, bq)
            dst = ds32.T.astype(q.dtype)
            dv_new = dv + _dot(pt, do, NN, impl)
            dk_new = dk + _dot(dst, q, NN, impl)
        else:
            p = p32.astype(do.dtype) if impl != "f32" else p32
            ds = ds32.astype(q.dtype) if impl != "f32" else ds32
            dv_new = dv + _dot(p, do, TN, impl)
            dk_new = dk + _dot(ds, q, TN, impl)
        return dk_new, dv_new

    d = q_ref.shape[-1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(qstart, jnp.int32(num_q), body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dkv_kernel_nn2(q_ref, qt_ref, kt_ref, vt_ref, do_ref, dot_ref,
                    lse_ref, delta_ref, dkt_ref, dvt_ref, *, sm_scale,
                    causal, block_q, block_k, seq_len):
    """Transpose-free canonical-NN dK/dV: besides K^T/V^T blocks, the
    kernel receives Q^T and dO^T ([1, D, L], XLA transposes outside) and
    writes dK^T/dV^T (transposed back outside) — dv^T = do^T @ P and
    dk^T = q^T @ dS are NN with no in-kernel vector transpose at all."""
    kj = pl.program_id(1)
    num_q = seq_len // block_q
    qstart = ((kj * block_k) // jnp.int32(block_q)).astype(jnp.int32) \
        if causal else jnp.int32(0)
    kt = kt_ref[0]                                          # (D, bk)
    vt = vt_ref[0]

    def body(i, carry):
        dkt, dvt = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]        # (bq, D)
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        qt = qt_ref[0, :, pl.ds(i * block_q, block_q)]      # (D, bq)
        dot_ = dot_ref[0, :, pl.ds(i * block_q, block_q)]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = _dot(q, kt, NN, "nn2") * sm_scale
        dp = _dot(do, vt, NN, "nn2")
        if causal:
            s = jnp.where(_causal_mask(i, kj, block_q, block_k), s,
                          jnp.float32(_NEG_INF))
        p32 = jnp.exp(s - lse[:, None])                     # (bq, bk) f32
        ds = (p32 * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
        dvt_new = dvt + _dot(dot_, p32.astype(do.dtype), NN, "nn2")
        dkt_new = dkt + _dot(qt, ds, NN, "nn2")
        return dkt_new, dvt_new

    d = q_ref.shape[-1]
    init = (jnp.zeros((d, block_k), jnp.float32),
            jnp.zeros((d, block_k), jnp.float32))
    dkt, dvt = jax.lax.fori_loop(qstart, jnp.int32(num_q), body, init)
    dkt_ref[0] = dkt.astype(dkt_ref.dtype)
    dvt_ref[0] = dvt.astype(dvt_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, interpret, impl, res, g):
    q, k, v, o, lse = res
    bh, L, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    if impl in ("nn", "nn2"):
        kt = jnp.swapaxes(k, 1, 2)   # [bh, D, L] (cheap XLA transpose)
        vt = jnp.swapaxes(v, 1, 2)
        t_spec = pl.BlockSpec((1, d, L), _im(lambda b, i: (b, 0, 0)))
        dq_kern = functools.partial(
            _dq_kernel_nn, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=L)
        dq_kv_specs = [pl.BlockSpec((1, L, d), _im(lambda b, i: (b, 0, 0))),
                       t_spec, t_spec]
        dq_kv = (k, kt, vt)
        dkv_k_spec = pl.BlockSpec((1, d, block_k),
                                  _im(lambda b, j: (b, 0, j)))
        dkv_kv = (kt, vt)
    else:
        dq_kern = functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=L, impl=impl)
        full_spec = pl.BlockSpec((1, L, d), _im(lambda b, i: (b, 0, 0)))
        dq_kv_specs = [full_spec, full_spec]
        dq_kv = (k, v)
        dkv_k_spec = pl.BlockSpec((1, block_k, d),
                                  _im(lambda b, j: (b, j, 0)))
        dkv_kv = (k, v)

    dq = pl.pallas_call(
        dq_kern,
        name="flash_bwd_dq",
        grid=(bh, L // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), _im(lambda b, i: (b, i, 0))),
            *dq_kv_specs,
            pl.BlockSpec((1, block_q, d), _im(lambda b, i: (b, i, 0))),
            pl.BlockSpec((1, 1, block_q), _im(lambda b, i: (b, 0, i))),
            pl.BlockSpec((1, 1, block_q), _im(lambda b, i: (b, 0, i))),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), _im(lambda b, i: (b, i, 0))),
        out_shape=jax.ShapeDtypeStruct((bh, L, d), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, *dq_kv, g, lse, delta)

    full_ld = pl.BlockSpec((1, L, d), _im(lambda b, j: (b, 0, 0)))
    row_l = pl.BlockSpec((1, 1, L), _im(lambda b, j: (b, 0, 0)))
    if impl == "nn2":
        # no in-kernel transposes at all: hand the kernel Q^T/dO^T too
        # and take dK^T/dV^T back (all four transposes are XLA's)
        qt = jnp.swapaxes(q, 1, 2)
        dot_g = jnp.swapaxes(g, 1, 2)
        full_dl = pl.BlockSpec((1, d, L), _im(lambda b, j: (b, 0, 0)))
        dkt, dvt = pl.pallas_call(
            functools.partial(_dkv_kernel_nn2, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, seq_len=L),
            name="flash_bwd_dkv",
            grid=(bh, L // block_k),
            in_specs=[full_ld, full_dl, dkv_k_spec, dkv_k_spec,
                      full_ld, full_dl, row_l, row_l],
            out_specs=[
                pl.BlockSpec((1, d, block_k), _im(lambda b, j: (b, 0, j))),
                pl.BlockSpec((1, d, block_k), _im(lambda b, j: (b, 0, j))),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, d, L), k.dtype),
                jax.ShapeDtypeStruct((bh, d, L), v.dtype),
            ],
            interpret=interpret,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
        )(q, qt, *dkv_kv, g, dot_g, lse, delta)
        return dq, jnp.swapaxes(dkt, 1, 2), jnp.swapaxes(dvt, 1, 2)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_len=L,
                          impl=impl),
        name="flash_bwd_dkv",
        grid=(bh, L // block_k),
        in_specs=[
            full_ld,
            dkv_k_spec,
            dkv_k_spec,
            full_ld,
            row_l,
            row_l,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), _im(lambda b, j: (b, j, 0))),
            pl.BlockSpec((1, block_k, d), _im(lambda b, j: (b, j, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, d), k.dtype),
            jax.ShapeDtypeStruct((bh, L, d), v.dtype),
        ],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, *dkv_kv, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret, impl):
    out, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                  impl)
    return out


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
               impl):
    out, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    impl)
    return out, (q, k, v, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, impl, res, g):
    return _bwd(sm_scale, causal, block_q, block_k, interpret, impl, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- dot-impl resolution --
_DOT_IMPLS = ("bf16", "nn", "nn2", "f32")


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


def flash_attention_supported(q_shape, d_model_last: int, causal: bool,
                              block_q: int = 128, block_k: int = 128) -> bool:
    """Shape gate: seq divisible by both blocks, head_dim sane."""
    L = q_shape[1]
    return (L % block_q == 0 and L % block_k == 0 and L >= block_q
            and d_model_last <= 256)


def flash_attention(q, k, v, causal: bool = False, sm_scale=None,
                    block_q: int = 128, block_k: int = 128,
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
                 int(block_q), int(block_k), bool(interpret), str(impl))
    return jnp.swapaxes(out.reshape(B, H, L, D), 1, 2)
