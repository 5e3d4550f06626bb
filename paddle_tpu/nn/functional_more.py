"""nn.functional widening: 3-D/adaptive/unpool pooling, transposed convs,
fold, geometry (affine_grid/grid_sample), and the remaining loss family.

Reference: python/paddle/nn/functional/{pooling,conv,common,loss,input}.py.
Everything is pure-JAX (XLA reduce_window / conv_general_dilated / gather),
no custom kernels — these ops are memory-bound glue, not MXU hot spots.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as _rng
from ..core.dispatch import defop
from ..core.state import STATE
from ..core.tensor import Tensor
from ..ops.common import _t


def _ntuple(v, n):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * n


# ------------------------------------------------------------ 3-D pooling --
@defop("max_pool3d")
def _max_pool3d_p(x, kernel_size=(2, 2, 2), stride=(2, 2, 2),
                  padding=(0, 0, 0), ceil_mode=False):
    from .functional import _pool_pads

    pads = [(0, 0), (0, 0)] + _pool_pads(x.shape[2:], kernel_size, stride,
                                         padding, ceil_mode)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1) + kernel_size, (1, 1) + stride,
        pads)


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    ks = _ntuple(kernel_size, 3)
    st = _ntuple(stride, 3) if stride is not None else ks
    if return_mask:
        if ceil_mode:
            raise NotImplementedError(
                "max_pool3d: return_mask with ceil_mode is not supported")
        return _pool_with_mask(_t(x), ks, st, _ntuple(padding, 3), "max")
    return _max_pool3d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_ntuple(padding, 3),
                         ceil_mode=bool(ceil_mode))


@defop("avg_pool3d")
def _avg_pool3d_p(x, kernel_size=(2, 2, 2), stride=(2, 2, 2),
                  padding=(0, 0, 0), exclusive=True, ceil_mode=False,
                  divisor=None):
    from .functional import _pool_pads

    sp = _pool_pads(x.shape[2:], kernel_size, stride, padding, ceil_mode)
    pads = [(0, 0), (0, 0)] + sp
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 1) + kernel_size, (1, 1) + stride, pads)
    if divisor is not None:
        return s / divisor
    if exclusive and any(lo or hi for lo, hi in sp):
        counts = jax.lax.reduce_window(
            jnp.ones_like(x), 0.0, jax.lax.add, (1, 1) + kernel_size,
            (1, 1) + stride, pads)
        return s / counts
    return s / math.prod(kernel_size)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCDHW",
               name=None):
    ks = _ntuple(kernel_size, 3)
    st = _ntuple(stride, 3) if stride is not None else ks
    return _avg_pool3d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_ntuple(padding, 3),
                         exclusive=bool(exclusive),
                         ceil_mode=bool(ceil_mode),
                         divisor=divisor_override)


# ------------------------------------------------------- adaptive pooling --
def _adaptive_reduce(x, output_size, nd, op):
    spatial = x.shape[2:]
    out_size = _ntuple(output_size, nd)
    out_size = tuple(o if o is not None else s
                     for o, s in zip(out_size, spatial))
    if all(s % o == 0 for s, o in zip(spatial, out_size)):
        shape = list(x.shape[:2])
        axes = []
        for i, (s, o) in enumerate(zip(spatial, out_size)):
            shape.extend([o, s // o])
            axes.append(2 + 2 * i + 1)
        y = x.reshape(shape)
        return y.max(axis=tuple(axes)) if op == "max" else \
            y.mean(axis=tuple(axes))
    # general interval pooling (static unrolled — output sizes are small)
    def intervals(s, o):
        return [((i * s) // o, -(-((i + 1) * s) // o)) for i in range(o)]

    grids = [intervals(s, o) for s, o in zip(spatial, out_size)]

    def reduce_block(idx):
        sl = (slice(None), slice(None)) + tuple(
            slice(lo, hi) for lo, hi in idx)
        blk = x[sl]
        ax = tuple(range(2, 2 + nd))
        return blk.max(axis=ax) if op == "max" else blk.mean(axis=ax)

    import itertools

    blocks = [reduce_block(idx) for idx in itertools.product(*grids)]
    out = jnp.stack(blocks, axis=-1)
    return out.reshape(x.shape[:2] + out_size)


@defop("adaptive_max_pool1d")
def _adaptive_max_pool1d_p(x, output_size=1):
    return _adaptive_reduce(x, output_size, 1, "max")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    return _adaptive_max_pool1d_p(_t(x), output_size=int(output_size))


@defop("adaptive_max_pool3d")
def _adaptive_max_pool3d_p(x, output_size=(1, 1, 1)):
    return _adaptive_reduce(x, output_size, 3, "max")


def adaptive_max_pool3d(x, output_size, return_mask=False, name=None):
    return _adaptive_max_pool3d_p(_t(x), output_size=_ntuple(output_size, 3))


@defop("adaptive_avg_pool3d")
def _adaptive_avg_pool3d_p(x, output_size=(1, 1, 1)):
    return _adaptive_reduce(x, output_size, 3, "mean")


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_avg_pool3d_p(_t(x), output_size=_ntuple(output_size, 3))


# ----------------------------------------------------------- max unpooling --
@defop("max_pool_with_mask")
def _pool_mask_p(x, ks=(2, 2), st=(2, 2), pad=(0, 0)):
    """Patch-extraction max pooling returning (pooled, flat-spatial
    indices) — paddle's return_mask contract (indices into the flattened
    unpadded spatial dims)."""
    nd = len(ks)
    spatial = x.shape[2:]
    if any(pad):
        x = jnp.pad(x, [(0, 0), (0, 0)] + [(p, p) for p in pad],
                    constant_values=-jnp.inf)
    out_sp = [(x.shape[2 + i] - ks[i]) // st[i] + 1 for i in range(nd)]
    idx_grids = []
    for i in range(nd):
        starts = jnp.arange(out_sp[i]) * st[i]
        offs = jnp.arange(ks[i])
        idx_grids.append(starts[:, None] + offs[None, :])  # (out, k)
    patches = x
    for i in range(nd):
        patches = jnp.take(patches, idx_grids[i], axis=2 + 2 * i)
    # patches: (N, C, o1, k1, o2, k2, ...) -> (N, C, o..., k1*k2*...)
    perm = [0, 1] + [2 + 2 * i for i in range(nd)] + \
        [3 + 2 * i for i in range(nd)]
    patches = patches.transpose(perm)
    flat = patches.reshape(patches.shape[:2 + nd] + (-1,))
    pooled = flat.max(axis=-1)
    am = flat.argmax(axis=-1)
    # local patch index -> global flat spatial index (in the PADDED frame,
    # then mapped back to unpadded coordinates)
    locs = jnp.unravel_index(am, ks)  # nd arrays of (N, C, o...)
    strides_sp = []
    acc = 1
    for s in reversed(spatial):
        strides_sp.insert(0, acc)
        acc *= s
    flat_idx = jnp.zeros(am.shape, jnp.int64)
    for i in range(nd):
        starts = (jnp.arange(out_sp[i]) * st[i]).reshape(
            (1, 1) + tuple(out_sp[j] if j == i else 1 for j in range(nd)))
        coord = locs[i] + starts - pad[i]
        flat_idx = flat_idx + coord.astype(jnp.int64) * strides_sp[i]
    return pooled, flat_idx


def _pool_with_mask(x, ks, st, pad, op):
    return _pool_mask_p(_t(x), ks=tuple(ks), st=tuple(st), pad=tuple(pad))


@defop("max_unpool")
def _max_unpool_p(x, indices, out_sp=(1, 1)):
    n, c = x.shape[:2]
    total = int(np.prod(out_sp))
    flat = jnp.zeros((n, c, total), x.dtype)
    flat_idx = indices.reshape(n, c, -1)
    flat = flat.at[
        jnp.arange(n)[:, None, None], jnp.arange(c)[None, :, None],
        flat_idx].set(x.reshape(n, c, -1))
    return flat.reshape((n, c) + tuple(out_sp))


def _max_unpool(x, indices, nd, kernel_size, stride, padding, output_size):
    ks = _ntuple(kernel_size, nd)
    st = _ntuple(stride, nd) if stride is not None else ks
    pad = _ntuple(padding, nd)
    in_sp = _t(x).shape[2:]
    if output_size is None:
        out_sp = tuple((in_sp[i] - 1) * st[i] - 2 * pad[i] + ks[i]
                       for i in range(nd))
    else:
        out_sp = tuple(int(s) for s in output_size[-nd:])
    return _max_unpool_p(_t(x), _t(indices), out_sp=out_sp)


def max_unpool1d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCL", output_size=None, name=None):
    """Inverse of max_pool1d(return_mask=True) (reference
    nn/functional/pooling.py max_unpool1d)."""
    return _max_unpool(x, indices, 1, kernel_size, stride, padding,
                       output_size)


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    return _max_unpool(x, indices, 2, kernel_size, stride, padding,
                       output_size)


def max_unpool3d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCDHW", output_size=None, name=None):
    return _max_unpool(x, indices, 3, kernel_size, stride, padding,
                       output_size)


# ------------------------------------------------------- transposed convs --
def _conv_transpose_nd(x, weight, bias, stride, padding, output_padding,
                       dilation, groups, nd):
    k = weight.shape[2:]
    pad = [(dilation[i] * (k[i] - 1) - padding[i],
            dilation[i] * (k[i] - 1) - padding[i] + output_padding[i])
           for i in range(nd)]
    w = jnp.flip(weight, tuple(range(2, 2 + nd)))
    if groups > 1:
        gi = weight.shape[0] // groups
        w = w.reshape((groups, gi) + w.shape[1:])
        w = jnp.moveaxis(w, 2, 1)
        w = w.reshape((groups * w.shape[1], gi) + tuple(k))
    else:
        w = jnp.swapaxes(w, 0, 1)
    fmt = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"),
           3: ("NCDHW", "OIDHW", "NCDHW")}[nd]
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, fmt)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,) * nd, padding=pad, lhs_dilation=stride,
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


@defop("conv1d_transpose")
def _conv1d_transpose_p(x, weight, bias=None, stride=(1,), padding=(0,),
                        output_padding=(0,), dilation=(1,), groups=1):
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 1)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv1d_transpose_p(
        *args, stride=_ntuple(stride, 1), padding=_ntuple(padding, 1),
        output_padding=_ntuple(output_padding, 1),
        dilation=_ntuple(dilation, 1), groups=int(groups))


@defop("conv3d_transpose")
def _conv3d_transpose_p(x, weight, bias=None, stride=(1, 1, 1),
                        padding=(0, 0, 0), output_padding=(0, 0, 0),
                        dilation=(1, 1, 1), groups=1):
    return _conv_transpose_nd(x, weight, bias, stride, padding,
                              output_padding, dilation, groups, 3)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv3d_transpose_p(
        *args, stride=_ntuple(stride, 3), padding=_ntuple(padding, 3),
        output_padding=_ntuple(output_padding, 3),
        dilation=_ntuple(dilation, 3), groups=int(groups))


# ------------------------------------------------------------- fold & pads --
@defop("fold")
def _fold_p(x, output_sizes=(1, 1), kernel_sizes=(1, 1), strides=(1, 1),
            paddings=(0, 0), dilations=(1, 1)):
    # x: (N, C*kh*kw, L) -> (N, C, H, W); scatter-add of unfold patches
    n, ckk, L = x.shape
    kh, kw = kernel_sizes
    c = ckk // (kh * kw)
    oh, ow = output_sizes
    ph, pw = paddings
    sh, sw = strides
    dh, dw = dilations
    nh = (oh + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    nw = (ow + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    cols = x.reshape(n, c, kh, kw, nh, nw)
    out = jnp.zeros((n, c, oh + 2 * ph, ow + 2 * pw), x.dtype)
    for i in range(kh):
        for j in range(kw):
            out = out.at[:, :, i * dh:i * dh + nh * sh:sh,
                         j * dw:j * dw + nw * sw:sw].add(cols[:, :, i, j])
    return out[:, :, ph:ph + oh, pw:pw + ow]


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im — inverse of unfold (reference nn/functional/common.py fold)."""
    return _fold_p(_t(x), output_sizes=_ntuple(output_sizes, 2),
                   kernel_sizes=_ntuple(kernel_sizes, 2),
                   strides=_ntuple(strides, 2),
                   paddings=_ntuple(paddings, 2),
                   dilations=_ntuple(dilations, 2))


@defop("zeropad2d")
def _zeropad2d_p(x, padding=(0, 0, 0, 0)):
    l, r, t, b = padding
    return jnp.pad(x, [(0, 0), (0, 0), (t, b), (l, r)])


def zeropad2d(x, padding, data_format="NCHW", name=None):
    if isinstance(padding, Tensor):
        padding = [int(v) for v in padding.numpy().tolist()]
    return _zeropad2d_p(_t(x), padding=tuple(int(p) for p in padding))


@defop("channel_shuffle")
def _channel_shuffle_p(x, groups=1):
    n, c, h, w = x.shape
    return x.reshape(n, groups, c // groups, h, w).swapaxes(1, 2).reshape(
        n, c, h, w)


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    return _channel_shuffle_p(_t(x), groups=int(groups))


@defop("pixel_unshuffle")
def _pixel_unshuffle_p(x, downscale_factor=1):
    n, c, h, w = x.shape
    r = downscale_factor
    y = x.reshape(n, c, h // r, r, w // r, r)
    return y.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * r * r, h // r, w // r)


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    return _pixel_unshuffle_p(_t(x), downscale_factor=int(downscale_factor))


# -------------------------------------------------------- geometry & misc --
@defop("affine_grid")
def _affine_grid_p(theta, out_shape=(1, 1, 1, 1), align_corners=True):
    n, _, h, w = out_shape

    def axis_coords(size):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, size)
        return (jnp.arange(size) * 2 + 1) / size - 1.0

    ys = axis_coords(h)
    xs = axis_coords(w)
    gx, gy = jnp.meshgrid(xs, ys)  # (h, w)
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1).astype(theta.dtype)  # (h,w,3)
    # (n,2,3) x (h,w,3) -> (n,h,w,2)
    return jnp.einsum("nij,hwj->nhwi", theta, base)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """Sampling grid from batched 2x3 affine matrices (reference
    nn/functional/vision.py affine_grid)."""
    if isinstance(out_shape, Tensor):
        out_shape = [int(v) for v in out_shape.numpy().tolist()]
    return _affine_grid_p(_t(theta), out_shape=tuple(int(s) for s in
                                                     out_shape),
                          align_corners=bool(align_corners))


@defop("grid_sample")
def _grid_sample_p(x, grid, mode="bilinear", padding_mode="zeros",
                   align_corners=True):
    n, c, h, w = x.shape
    gx = grid[..., 0]
    gy = grid[..., 1]
    if align_corners:
        fx = (gx + 1) * (w - 1) / 2
        fy = (gy + 1) * (h - 1) / 2
    else:
        fx = ((gx + 1) * w - 1) / 2
        fy = ((gy + 1) * h - 1) / 2

    def reflect(v, size):
        if align_corners:
            span = 2 * (size - 1)
            v = jnp.abs(v) % span
            return jnp.where(v > size - 1, span - v, v)
        span = 2 * size
        v = (v + 0.5) % span
        v = jnp.where(v > size, span - v, v) - 0.5
        return jnp.clip(v, 0, size - 1)

    if padding_mode == "reflection":
        fx = reflect(fx, w)
        fy = reflect(fy, h)
    elif padding_mode == "border":
        fx = jnp.clip(fx, 0, w - 1)
        fy = jnp.clip(fy, 0, h - 1)

    def sample(ix, iy):
        valid = (ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1)
        ixc = jnp.clip(ix, 0, w - 1).astype(jnp.int32)
        iyc = jnp.clip(iy, 0, h - 1).astype(jnp.int32)
        # x: (n,c,h,w); iyc/ixc: (n,gh,gw) -> out (n,c,gh,gw)
        out = x[jnp.arange(n)[:, None, None, None],
                jnp.arange(c)[None, :, None, None],
                iyc[:, None], ixc[:, None]]
        if padding_mode == "zeros":
            out = out * valid[:, None].astype(x.dtype)
        return out

    if mode == "nearest":
        return sample(jnp.round(fx), jnp.round(fy))
    x0 = jnp.floor(fx)
    y0 = jnp.floor(fy)
    wx = (fx - x0)[:, None]
    wy = (fy - y0)[:, None]
    v00 = sample(x0, y0)
    v01 = sample(x0 + 1, y0)
    v10 = sample(x0, y0 + 1)
    v11 = sample(x0 + 1, y0 + 1)
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    return top * (1 - wy) + bot * wy


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """Bilinear/nearest sampling at grid locations (reference
    nn/functional/vision.py grid_sample)."""
    return _grid_sample_p(_t(x), _t(grid), mode=mode,
                          padding_mode=padding_mode,
                          align_corners=bool(align_corners))


@defop("gumbel_softmax")
def _gumbel_softmax_p(x, g, temperature=1.0, hard=False, axis=-1):
    y = jax.nn.softmax(
        (x.astype(jnp.float32) + g.astype(jnp.float32)) / temperature,
        axis=axis).astype(x.dtype)
    if hard:
        oh = jax.nn.one_hot(jnp.argmax(y, axis=axis), y.shape[axis],
                            axis=axis, dtype=y.dtype)
        # straight-through: hard value, soft gradient
        return oh + y - jax.lax.stop_gradient(y)
    return y


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    """Gumbel-softmax sampling with optional straight-through (reference
    nn/functional/activation.py gumbel_softmax)."""
    t = _t(x)
    g = Tensor(jax.random.gumbel(_rng.next_key(),
                                 tuple(t._data.shape), jnp.float32))
    return _gumbel_softmax_p(t, g, temperature=float(temperature),
                             hard=bool(hard), axis=int(axis))


@defop("rrelu")
def _rrelu_p(x, slope):
    return jnp.where(x >= 0, x, slope.astype(x.dtype) * x)


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=True, name=None):
    """Randomized leaky ReLU (reference nn/functional/activation.py rrelu)."""
    t = _t(x)
    if training:
        a = jax.random.uniform(_rng.next_key(), tuple(t._data.shape),
                               jnp.float32, lower, upper)
    else:
        a = jnp.full(tuple(t._data.shape), (lower + upper) / 2.0,
                     jnp.float32)
    return _rrelu_p(t, Tensor(a))


@defop("pairwise_distance")
def _pairwise_distance_p(x, y, p=2.0, epsilon=1e-6, keepdim=False):
    d = x - y + epsilon
    return jnp.power(jnp.sum(jnp.power(jnp.abs(d), p), axis=-1,
                             keepdims=keepdim), 1.0 / p)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    return _pairwise_distance_p(_t(x), _t(y), p=float(p),
                                epsilon=float(epsilon),
                                keepdim=bool(keepdim))


@defop("bilinear")
def _bilinear_p(x1, x2, weight, bias=None):
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


def bilinear(x1, x2, weight, bias=None, name=None):
    """x1^T W x2 bilinear form (reference nn/functional/common.py
    bilinear)."""
    args = (_t(x1), _t(x2), _t(weight))
    if bias is not None:
        args = args + (_t(bias),)
    return _bilinear_p(*args)


@defop("gather_tree")
def _gather_tree_p(ids, parents):
    # ids/parents: (T, B, beam). Backtrace from the last step.
    T = ids.shape[0]

    def step(beams, t):
        # beams: (B, beam) current beam index per slot
        tok = jnp.take_along_axis(ids[t], beams, axis=-1)
        par = jnp.take_along_axis(parents[t], beams, axis=-1)
        return par, tok

    init = jnp.broadcast_to(jnp.arange(ids.shape[2]), ids.shape[1:])
    _, toks = jax.lax.scan(step, init, jnp.arange(T - 1, -1, -1))
    return jnp.flip(toks, axis=0)


def gather_tree(ids, parents):
    """Beam-search ancestor backtrace (reference nn/functional/input.py?
    gather_tree custom op): full token sequences from per-step ids and
    parent beam indices."""
    return _gather_tree_p(_t(ids), _t(parents))


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention (reference GPU-only custom op
    nn/functional/sparse_attention.py): computed here by materializing the
    CSR mask — eager/debug utility, not the TPU hot path (use
    scaled_dot_product_attention / the Pallas flash kernel instead)."""
    q, k, v = _t(query), _t(key), _t(value)
    off = np.asarray(_t(sparse_csr_offset)._data)
    cols = np.asarray(_t(sparse_csr_columns)._data)
    b, h, L, d = q._data.shape
    mask = np.zeros((b, h, L, L), bool)
    for bi in range(b):
        for hi in range(h):
            for r in range(L):
                lo, hi_ = off[bi, hi, r], off[bi, hi, r + 1]
                mask[bi, hi, r, cols[bi, hi, lo:hi_]] = True
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q._data, k._data) * scale
    s = jnp.where(jnp.asarray(mask), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return Tensor(jnp.einsum("bhqk,bhkd->bhqd", p, v._data))


# ------------------------------------------------------------------ losses --
def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


@defop("square_error_cost")
def _square_error_cost_p(input, label):
    return jnp.square(input - label)


def square_error_cost(input, label, name=None):
    return _square_error_cost_p(_t(input), _t(label))


@defop("log_loss")
def _log_loss_p(input, label, epsilon=1e-4):
    return -label * jnp.log(input + epsilon) \
        - (1.0 - label) * jnp.log(1.0 - input + epsilon)


def log_loss(input, label, epsilon=1e-4, name=None):
    return _log_loss_p(_t(input), _t(label), epsilon=float(epsilon))


@defop("dice_loss")
def _dice_loss_p(input, label, epsilon=1e-5):
    # input: (N, ..., C) probabilities; label: (N, ..., 1) class ids
    lab = jax.nn.one_hot(label.squeeze(-1), input.shape[-1],
                         dtype=input.dtype)
    red = tuple(range(1, input.ndim))
    inter = jnp.sum(input * lab, axis=red)
    union = jnp.sum(input, axis=red) + jnp.sum(lab, axis=red)
    return jnp.mean(1.0 - (2.0 * inter + epsilon) / (union + epsilon))


def dice_loss(input, label, epsilon=1e-5, name=None):
    return _dice_loss_p(_t(input), _t(label), epsilon=float(epsilon))


@defop("soft_margin_loss")
def _soft_margin_loss_p(input, label, reduction="mean"):
    return _reduce_loss(jnp.log1p(jnp.exp(-label * input)), reduction)


def soft_margin_loss(input, label, reduction="mean", name=None):
    return _soft_margin_loss_p(_t(input), _t(label), reduction=reduction)


@defop("cosine_embedding_loss")
def _cosine_embedding_loss_p(input1, input2, label, margin=0.0,
                             reduction="mean"):
    cos = jnp.sum(input1 * input2, -1) / jnp.maximum(
        jnp.linalg.norm(input1, axis=-1) * jnp.linalg.norm(input2, axis=-1),
        1e-12)
    loss = jnp.where(label > 0, 1.0 - cos, jnp.maximum(0.0, cos - margin))
    return _reduce_loss(loss, reduction)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean", name=None):
    return _cosine_embedding_loss_p(_t(input1), _t(input2), _t(label),
                                    margin=float(margin),
                                    reduction=reduction)


@defop("poisson_nll_loss")
def _poisson_nll_loss_p(input, label, log_input=True, full=False,
                        epsilon=1e-8, reduction="mean"):
    if log_input:
        loss = jnp.exp(input) - label * input
    else:
        loss = input - label * jnp.log(input + epsilon)
    if full:
        stirling = label * jnp.log(label) - label \
            + 0.5 * jnp.log(2 * jnp.pi * label)
        loss = loss + jnp.where(label > 1, stirling, 0.0)
    return _reduce_loss(loss, reduction)


def poisson_nll_loss(input, label, log_input=True, full=False,
                     epsilon=1e-8, reduction="mean", name=None):
    return _poisson_nll_loss_p(_t(input), _t(label), log_input=bool(log_input),
                               full=bool(full), epsilon=float(epsilon),
                               reduction=reduction)


@defop("gaussian_nll_loss")
def _gaussian_nll_loss_p(input, label, variance, full=False, epsilon=1e-6,
                         reduction="mean"):
    var = jnp.maximum(variance, epsilon)
    loss = 0.5 * (jnp.log(var) + jnp.square(input - label) / var)
    if full:
        loss = loss + 0.5 * jnp.log(2 * jnp.asarray(jnp.pi, input.dtype))
    return _reduce_loss(loss, reduction)


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    return _gaussian_nll_loss_p(_t(input), _t(label), _t(variance),
                                full=bool(full), epsilon=float(epsilon),
                                reduction=reduction)


@defop("multi_label_soft_margin_loss")
def _mlsm_loss_p(input, label, weight=None, reduction="mean"):
    logsig = jax.nn.log_sigmoid
    loss = -(label * logsig(input) + (1 - label) * logsig(-input))
    if weight is not None:
        loss = loss * weight
    return _reduce_loss(loss.mean(axis=-1), reduction)


def multi_label_soft_margin_loss(input, label, weight=None,
                                 reduction="mean", name=None):
    args = (_t(input), _t(label)) + \
        (() if weight is None else (_t(weight),))
    return _mlsm_loss_p(*args, reduction=reduction)


@defop("multi_margin_loss")
def _multi_margin_loss_p(input, label, p=1, margin=1.0, weight=None,
                         reduction="mean"):
    n, c = input.shape
    xy = jnp.take_along_axis(input, label[:, None], axis=1)  # (n,1)
    m = jnp.maximum(0.0, margin - xy + input)
    if p != 1:
        m = jnp.power(m, p)
    if weight is not None:
        m = m * weight[label][:, None]
    oh = jax.nn.one_hot(label, c, dtype=input.dtype)
    loss = jnp.sum(m * (1 - oh), axis=1) / c
    return _reduce_loss(loss, reduction)


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    args = (_t(input), _t(label)) + \
        (() if weight is None else (_t(weight),))
    return _multi_margin_loss_p(*args, p=int(p), margin=float(margin),
                                reduction=reduction)


@defop("triplet_margin_loss")
def _triplet_margin_loss_p(input, positive, negative, margin=1.0, p=2.0,
                           epsilon=1e-6, swap=False, reduction="mean"):
    def dst(a, b):
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a - b + epsilon), p),
                                 axis=-1), 1.0 / p)

    dp = dst(input, positive)
    dn = dst(input, negative)
    if swap:
        dn = jnp.minimum(dn, dst(positive, negative))
    return _reduce_loss(jnp.maximum(0.0, dp - dn + margin), reduction)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    return _triplet_margin_loss_p(_t(input), _t(positive), _t(negative),
                                  margin=float(margin), p=float(p),
                                  epsilon=float(epsilon), swap=bool(swap),
                                  reduction=reduction)


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean",
                                      name=None):
    """Triplet loss with a user distance function (reference
    nn/functional/loss.py triplet_margin_with_distance_loss)."""
    if distance_function is None:
        return triplet_margin_loss(input, positive, negative, margin=margin,
                                   swap=swap, reduction=reduction)
    a, pz, n = _t(input), _t(positive), _t(negative)
    dp = distance_function(a, pz)
    dn = distance_function(a, n)
    if swap:
        alt = distance_function(pz, n)
        dn = dn.minimum(alt) if hasattr(dn, "minimum") else dn
    import paddle_tpu as paddle

    loss = paddle.maximum(dp - dn + margin,
                          paddle.zeros_like(dp._data if hasattr(dp, "_data")
                                            else dp))
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


@defop("sigmoid_focal_loss")
def _sigmoid_focal_loss_p(logit, label, normalizer=None, alpha=0.25,
                          gamma=2.0, reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = -(label * jax.nn.log_sigmoid(logit)
           + (1 - label) * jax.nn.log_sigmoid(-logit))
    pt = p * label + (1 - p) * (1 - label)
    at = alpha * label + (1 - alpha) * (1 - label)
    loss = at * jnp.power(1 - pt, gamma) * ce
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce_loss(loss, reduction)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    args = (_t(logit), _t(label)) + \
        (() if normalizer is None else (_t(normalizer),))
    return _sigmoid_focal_loss_p(*args, alpha=float(alpha),
                                 gamma=float(gamma), reduction=reduction)


@defop("npair_loss")
def _npair_loss_p(anchor, positive, labels, l2_reg=0.002):
    # labels: (n,) — same label => positive pair target
    n = anchor.shape[0]
    sim = anchor @ positive.T  # (n, n)
    tgt = (labels[:, None] == labels[None, :]).astype(anchor.dtype)
    tgt = tgt / jnp.sum(tgt, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    xe = -jnp.sum(tgt * logp, axis=1).mean()
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(anchor), 1))
                    + jnp.mean(jnp.sum(jnp.square(positive), 1))) * 0.25
    return xe + reg


def npair_loss(anchor, positive, labels, l2_reg=0.002, name=None):
    return _npair_loss_p(_t(anchor), _t(positive), _t(labels),
                         l2_reg=float(l2_reg))


@defop("hsigmoid_loss")
def _hsigmoid_loss_p(input, label, weight, bias=None, num_classes=2):
    # default complete-binary-tree codes (reference hierarchical_sigmoid
    # kernel's default path when no custom tree is passed): internal node
    # ids from the classic (label + num_classes) >> k walk
    depth = int(np.ceil(np.log2(num_classes)))
    codes = []
    node_ids = []
    node = label + num_classes
    for _ in range(depth):
        codes.append((node % 2).astype(input.dtype))  # bit: left/right
        node = node // 2
        node_ids.append(node - 1)  # internal node index
    code = jnp.stack(codes, axis=-1)          # (n, depth)
    nid = jnp.stack(node_ids, axis=-1)        # (n, depth)
    valid = (nid >= 0) & (nid < num_classes - 1)
    nid = jnp.clip(nid, 0, weight.shape[0] - 1)
    w = weight[nid]                           # (n, depth, d)
    logits = jnp.einsum("nd,nkd->nk", input, w)
    if bias is not None:
        logits = logits + bias.reshape(-1)[nid]
    # sigmoid CE against the path bit
    ce = jnp.maximum(logits, 0) - logits * code + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    return jnp.sum(ce * valid.astype(input.dtype), axis=-1, keepdims=True)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid over the default complete binary tree
    (reference nn/functional/loss.py hsigmoid_loss; custom trees
    unsupported — pass path_table=None)."""
    if path_table is not None or path_code is not None:
        raise NotImplementedError(
            "custom hsigmoid trees are not supported; use the default tree")
    args = (_t(input), _t(label), _t(weight)) + \
        (() if bias is None else (_t(bias),))
    return _hsigmoid_loss_p(*args, num_classes=int(num_classes))


@defop("ctc_loss_core")
def _ctc_loss_core_p(log_probs, labels, input_lengths, label_lengths,
                     blank=0):
    """CTC forward (alpha) recursion in log space via lax.scan over time.

    log_probs: (T, B, C) raw scores, normalized internally; labels: (B, S)
    padded targets. Reference: warpctc-backed ctc_loss
    (nn/functional/loss.py ctc_loss).
    """
    log_probs = jax.nn.log_softmax(log_probs.astype(jnp.float32), -1)
    T, B, C = log_probs.shape
    S = labels.shape[1]
    ext = 2 * S + 1  # blank-interleaved target length

    # extended target: [blank, l1, blank, l2, ..., blank]
    ext_labels = jnp.full((B, ext), blank, labels.dtype)
    ext_labels = ext_labels.at[:, 1::2].set(labels)

    # transition permission: alpha[s] <- alpha[s] + alpha[s-1] (+ alpha[s-2]
    # when ext[s] != blank and ext[s] != ext[s-2])
    same_as_two_back = jnp.concatenate(
        [jnp.ones((B, 2), bool),
         ext_labels[:, 2:] == ext_labels[:, :-2]], axis=1)
    can_skip = (ext_labels != blank) & (~same_as_two_back)

    neg_inf = jnp.asarray(-1e30, log_probs.dtype)
    alpha0 = jnp.full((B, ext), neg_inf)
    alpha0 = alpha0.at[:, 0].set(log_probs[0, :, blank])
    first_lab = jnp.take_along_axis(
        log_probs[0], ext_labels[:, 1:2].astype(jnp.int32), axis=1)[:, 0]
    alpha0 = alpha0.at[:, 1].set(jnp.where(S > 0, first_lab, neg_inf))

    def lse(a, b):
        m = jnp.maximum(a, b)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        return jnp.where(
            jnp.maximum(a, b) <= neg_inf / 2, neg_inf,
            m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m)))

    def step(alpha, t):
        prev1 = jnp.concatenate([jnp.full((B, 1), neg_inf),
                                 alpha[:, :-1]], axis=1)
        prev2 = jnp.concatenate([jnp.full((B, 2), neg_inf),
                                 alpha[:, :-2]], axis=1)
        acc = lse(alpha, prev1)
        acc = jnp.where(can_skip, lse(acc, prev2), acc)
        emit = jnp.take_along_axis(log_probs[t],
                                   ext_labels.astype(jnp.int32), axis=1)
        new_alpha = acc + emit
        # frozen once past this sample's input length
        new_alpha = jnp.where((t < input_lengths)[:, None], new_alpha,
                              alpha)
        return new_alpha, None

    alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
    # loss = -logaddexp(alpha[2*len], alpha[2*len - 1]) per sample
    endl = (2 * label_lengths).astype(jnp.int32)
    last_blank = jnp.take_along_axis(alpha, endl[:, None], axis=1)[:, 0]
    last_lab = jnp.take_along_axis(
        alpha, jnp.maximum(endl - 1, 0)[:, None], axis=1)[:, 0]
    ll = lse(last_blank, jnp.where(label_lengths > 0, last_lab, neg_inf))
    return -ll


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False, name=None):
    """Connectionist temporal classification loss (reference
    nn/functional/loss.py ctc_loss over the warpctc kernel). log_probs:
    (T, B, C) raw or log-softmax scores (normalized internally)."""
    loss = _ctc_loss_core_p(_t(log_probs), _t(labels), _t(input_lengths),
                            _t(label_lengths), blank=int(blank))
    if norm_by_times:
        loss = loss / _t(input_lengths).astype("float32")
    if reduction == "mean":
        # paddle: mean over batch of loss / label_length
        return (loss / _t(label_lengths).astype("float32")).mean()
    if reduction == "sum":
        return loss.sum()
    return loss


@defop("rnnt_loss_core")
def _rnnt_loss_core_p(logits, labels, input_lengths, label_lengths,
                      blank=0):
    """RNN-T (transducer) alpha recursion (Graves 2012) — scan over T with
    an inner scan over U. logits: (B, T, U+1, V); labels: (B, U)."""
    B, T, U1, V = logits.shape
    U = U1 - 1
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    blank_lp = lp[..., blank]  # (B, T, U+1)
    lab_lp = jnp.take_along_axis(
        lp[:, :, :U, :], labels[:, None, :, None].astype(jnp.int32),
        axis=3)[..., 0]  # (B, T, U)
    neg_inf = jnp.asarray(-1e30, jnp.float32)

    def lse(a, b):
        m = jnp.maximum(a, b)
        safe = jnp.where(jnp.isfinite(m), m, 0.0)
        return jnp.where(m <= neg_inf / 2, neg_inf,
                         safe + jnp.log(jnp.exp(a - safe)
                                        + jnp.exp(b - safe)))

    # alpha[0, :] along u: emit labels at t=0
    def u_scan_first(carry, u):
        val = carry + lab_lp[:, 0, u]
        return val, val

    a00 = jnp.zeros((B,), jnp.float32)
    _, firsts = jax.lax.scan(u_scan_first, a00, jnp.arange(U))
    alpha0 = jnp.concatenate([a00[None], firsts], axis=0).T  # (B, U+1)

    def t_step(alpha_prev, t):
        # horizontal move: blank from (t-1, u)
        horiz = alpha_prev + blank_lp[:, t - 1, :]

        def u_step(carry, u):
            # carry = alpha[t, u-1]; vertical move consumes label u-1 at t
            vert = carry + lab_lp[:, t, u - 1]
            val = lse(horiz[:, u], vert)
            return val, val

        a_t0 = horiz[:, 0]
        _, rest = jax.lax.scan(u_step, a_t0, jnp.arange(1, U + 1))
        alpha_t = jnp.concatenate([a_t0[None], rest], axis=0).T
        alpha_t = jnp.where((t < input_lengths)[:, None], alpha_t,
                            alpha_prev)
        return alpha_t, None

    alphaT, _ = jax.lax.scan(t_step, alpha0, jnp.arange(1, T))
    # terminal: alpha[T-1, U] + blank(T-1, U) per-sample lengths
    tl = (input_lengths - 1).astype(jnp.int32)
    ul = label_lengths.astype(jnp.int32)
    a_end = jnp.take_along_axis(alphaT, ul[:, None], axis=1)[:, 0]
    b_end = jnp.take_along_axis(
        jnp.take_along_axis(blank_lp, tl[:, None, None], axis=1)[:, 0],
        ul[:, None], axis=1)[:, 0]
    return -(a_end + b_end)


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-T transducer loss (reference nn/functional/loss.py rnnt_loss
    over warprnnt). input: (B, T, U+1, V) joint-network logits."""
    loss = _rnnt_loss_core_p(_t(input), _t(label), _t(input_lengths),
                             _t(label_lengths), blank=int(blank))
    if reduction == "mean":
        return loss.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


@defop("margin_cross_entropy_core")
def _margin_ce_p(logits, label, margin1=1.0, margin2=0.5, margin3=0.0,
                 scale=64.0, return_softmax=False):
    # ArcFace-family margin softmax: cos(m1*theta + m2) - m3 on the target
    theta = jnp.arccos(jnp.clip(logits, -1 + 1e-7, 1 - 1e-7))
    oh = jax.nn.one_hot(label, logits.shape[-1], dtype=logits.dtype)
    target = jnp.cos(margin1 * theta + margin2) - margin3
    adj = jnp.where(oh > 0, target, logits) * scale
    logp = jax.nn.log_softmax(adj, axis=-1)
    loss = -jnp.sum(oh * logp, axis=-1, keepdims=True)
    if return_softmax:
        return loss, jnp.exp(logp)
    return loss


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean", name=None):
    """ArcFace/CosFace margin softmax CE (reference nn/functional/loss.py
    margin_cross_entropy; the model-parallel `group` variant collapses into
    GSPMD sharding of the class dim)."""
    out = _margin_ce_p(_t(logits), _t(label), margin1=float(margin1),
                       margin2=float(margin2), margin3=float(margin3),
                       scale=float(scale), return_softmax=bool(return_softmax))
    loss = out[0] if return_softmax else out
    if reduction == "mean":
        loss = loss.mean()
    elif reduction == "sum":
        loss = loss.sum()
    return (loss, out[1]) if return_softmax else loss


def class_center_sample(label, num_classes, num_samples, group=None):
    """Sample negative class centers (PartialFC; reference
    nn/functional/common.py class_center_sample). Data-dependent sizes —
    eager only, like the reference's dynamic-shape kernel."""
    import paddle_tpu as paddle

    if STATE.func_trace:
        raise RuntimeError(
            "class_center_sample is data-dependent and cannot be traced; "
            "call it eagerly (outside jit/TrainStep)")
    lab = np.asarray(_t(label)._data)
    pos = np.unique(lab)
    need = max(0, num_samples - pos.size)
    rest = np.setdiff1d(np.arange(num_classes), pos)
    rng = np.random.RandomState(int(lab.sum()) % (2 ** 31))
    neg = rng.choice(rest, size=min(need, rest.size), replace=False)
    sampled = np.sort(np.concatenate([pos, neg]))
    remap = -np.ones((num_classes,), "int64")
    remap[sampled] = np.arange(sampled.size)
    return (paddle.to_tensor(remap[lab]),
            paddle.to_tensor(sampled.astype("int64")))


# ------------------------------------------------- in-place activations --
def relu_(x, name=None):
    from . import functional as F

    x._data = F.relu(x)._data
    return x


def elu_(x, alpha=1.0, name=None):
    from . import functional as F

    x._data = F.elu(x, alpha)._data
    return x


def softmax_(x, axis=-1, dtype=None, name=None):
    from . import functional as F

    x._data = F.softmax(x, axis=axis, dtype=dtype)._data
    return x


def tanh_(x, name=None):
    import paddle_tpu as paddle

    x._data = paddle.tanh(x)._data
    return x


from ..ops.creation import diag_embed  # noqa: E402,F401 (paddle parity)


# ----------------------------------------------- fused big-vocab CE head --
@defop("fused_linear_cross_entropy")
def _fused_linear_ce_p(h, weight, labels, transpose_y=True, chunk=2048,
                       ignore_index=-100):
    """Chunked fused LM-head + softmax-CE (the bench lever:
    'fused CE-from-bf16-logits').

    Never materializes the [T, vocab] logits: a lax.scan walks token
    chunks, each iteration computes its [chunk, vocab] logits on the MXU
    (bf16 inputs, f32 accumulation via preferred_element_type), reduces
    them to logsumexp + label-logit, and jax.checkpoint rematerializes
    the chunk in backward — peak HBM for the head drops from
    O(T*vocab) (824 MB for GPT-medium at fp32) to O(chunk*vocab).

    h: [T, H]; weight: [V, H] when transpose_y (tied wte) else [H, V];
    labels: [T] int. Returns the mean CE over non-ignored tokens (f32).
    Reference role: softmax_with_cross_entropy's fused CUDA kernel
    (paddle/phi/kernels/gpu/cross_entropy_kernel.cu) scaled to
    TPU-memory terms.
    """
    T, H = h.shape
    chunk = int(min(chunk, T))
    pad = (-T) % chunk
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad),
                         constant_values=ignore_index)
    n = (T + pad) // chunk
    hc = h.reshape(n, chunk, H)
    yc = labels.reshape(n, chunk)
    w = weight.T if transpose_y else weight  # [H, V]

    @jax.checkpoint
    def body(carry, inp):
        hcb, ycb = inp
        logits = jnp.dot(hcb, w, preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        own = jnp.take_along_axis(
            logits, jnp.maximum(ycb, 0)[:, None], axis=-1)[:, 0]
        mask = (ycb != ignore_index).astype(jnp.float32)
        total, count = carry
        return (total + jnp.sum((lse - own) * mask),
                count + jnp.sum(mask)), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hc, yc))
    return total / jnp.maximum(count, 1.0)


def fused_linear_cross_entropy(hidden, weight, labels, transpose_y=True,
                               chunk=2048, ignore_index=-100, name=None):
    """Mean CE of linear(hidden, weight) against labels without
    materializing the logits; hidden may be [..., H] (flattened
    internally), labels the matching integer ids."""
    h = _t(hidden)
    y = _t(labels)
    hv = h._data if isinstance(h, Tensor) else h
    size = 1
    for s in hv.shape[:-1]:
        size *= s
    return _fused_linear_ce_p(
        h.reshape([size, hv.shape[-1]]), _t(weight),
        y.reshape([size]), transpose_y=bool(transpose_y),
        chunk=int(chunk), ignore_index=int(ignore_index))
