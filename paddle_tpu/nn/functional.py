"""paddle.nn.functional analog.

Pure-JAX bodies dispatched through the core dispatcher; convolutions and
pooling use lax primitives (NCHW, paddle's default layout) which XLA maps
onto the MXU; everything fuses. References cite the op's yaml/kernels in the
reference repo for parity checks.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import rng as _rng
from ..core.dispatch import apply, defop
from ..core.state import STATE
from ..core.tensor import Tensor, to_tensor
from ..ops.common import _t

# ------------------------------------------------------------- activations
_ACT = {}


def _unary_act(name, fn):
    pure = defop(name)(fn)

    def op(x, name=None):
        return pure(_t(x))

    op.__name__ = name
    _ACT[name] = op
    return op


relu = _unary_act("relu", lambda x: jax.nn.relu(x))
relu6 = _unary_act("relu6", lambda x: jax.nn.relu6(x))
sigmoid = _unary_act("sigmoid", lambda x: jax.nn.sigmoid(x))
tanh = _unary_act("tanh", lambda x: jnp.tanh(x))
silu = _unary_act("silu", lambda x: jax.nn.silu(x))
swish = silu
log_sigmoid = _unary_act("log_sigmoid", lambda x: jax.nn.log_sigmoid(x))
mish = _unary_act("mish", lambda x: x * jnp.tanh(jax.nn.softplus(x)))
softsign = _unary_act("softsign", lambda x: jax.nn.soft_sign(x))
tanhshrink = _unary_act("tanhshrink", lambda x: x - jnp.tanh(x))
hardswish = _unary_act("hardswish", lambda x: x * jnp.clip(x + 3, 0, 6) / 6)
hardsigmoid = _unary_act("hardsigmoid", lambda x: jnp.clip(x / 6 + 0.5, 0, 1))


@defop("gelu")
def _gelu_p(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


def gelu(x, approximate=False, name=None):
    return _gelu_p(_t(x), approximate=bool(approximate))


@defop("leaky_relu")
def _leaky_relu_p(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def leaky_relu(x, negative_slope=0.01, name=None):
    return _leaky_relu_p(_t(x), negative_slope=float(negative_slope))


@defop("elu")
def _elu_p(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def elu(x, alpha=1.0, name=None):
    return _elu_p(_t(x), alpha=float(alpha))


@defop("celu")
def _celu_p(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def celu(x, alpha=1.0, name=None):
    return _celu_p(_t(x), alpha=float(alpha))


@defop("selu")
def _selu_p(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return _selu_p(_t(x), scale=float(scale), alpha=float(alpha))


@defop("hardtanh")
def _hardtanh_p(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return _hardtanh_p(_t(x), min=float(min), max=float(max))


@defop("hardshrink")
def _hardshrink_p(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def hardshrink(x, threshold=0.5, name=None):
    return _hardshrink_p(_t(x), threshold=float(threshold))


@defop("softshrink")
def _softshrink_p(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def softshrink(x, threshold=0.5, name=None):
    return _softshrink_p(_t(x), threshold=float(threshold))


@defop("softplus")
def _softplus_p(x, beta=1.0, threshold=20.0):
    bx = beta * x
    return jnp.where(bx > threshold, x, jax.nn.softplus(bx) / beta)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return _softplus_p(_t(x), beta=float(beta), threshold=float(threshold))


@defop("thresholded_relu")
def _thresholded_relu_p(x, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


def thresholded_relu(x, threshold=1.0, name=None):
    return _thresholded_relu_p(_t(x), threshold=float(threshold))


@defop("softmax")
def _softmax_p(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def softmax(x, axis=-1, dtype=None, name=None):
    out = _softmax_p(_t(x), axis=int(axis))
    if dtype is not None:
        out = out.astype(dtype)
    return out


@defop("log_softmax")
def _log_softmax_p(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def log_softmax(x, axis=-1, dtype=None, name=None):
    out = _log_softmax_p(_t(x), axis=int(axis))
    if dtype is not None:
        out = out.astype(dtype)
    return out


@defop("prelu")
def _prelu_p(x, weight):
    w = weight
    if w.ndim == 1 and w.shape[0] > 1 and x.ndim > 1:
        # per-channel (NCHW: channel axis 1)
        w = w.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x > 0, x, w * x)


def prelu(x, weight, data_format="NCHW", name=None):
    return _prelu_p(_t(x), _t(weight))


@defop("glu")
def _glu_p(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


def glu(x, axis=-1, name=None):
    return _glu_p(_t(x), axis=int(axis))


@defop("maxout")
def _maxout_p(x, groups=2, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


def maxout(x, groups, axis=1, name=None):
    return _maxout_p(_t(x), groups=int(groups), axis=int(axis))


# ---------------------------------------------------------------- linear --
@defop("linear")
def _linear_p(x, weight, bias=None):
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


def linear(x, weight, bias=None, name=None):
    if bias is None:
        return _linear_p(_t(x), _t(weight))
    return _linear_p(_t(x), _t(weight), _t(bias))


@defop("embedding")
def _embedding_p(x, weight, padding_idx=None):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None:
        mask = (x != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return out


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    return _embedding_p(_t(x), _t(weight), padding_idx=padding_idx)


@defop("one_hot")
def _one_hot_p(x, num_classes=-1):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


def one_hot(x, num_classes, name=None):
    return _one_hot_p(_t(x), num_classes=int(num_classes))


# ------------------------------------------------------------ convolution --
def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _conv_padding(padding, nd):
    """paddle padding: int, list of ints, list of pairs, or SAME/VALID."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nd
    padding = list(padding)
    if len(padding) == nd and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nd:
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nd)]
    return [tuple(p) for p in padding]


@defop("conv2d")
def _conv2d_p(x, weight, bias=None, stride=(1, 1), padding="VALID",
              dilation=(1, 1), groups=1, data_format="NCHW"):
    dn = ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else \
         ("NHWC", "OIHW", "NHWC")
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=jax.lax.conv_dimension_numbers(
            x.shape, weight.shape, dn))
    if bias is not None:
        b = bias.reshape((1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1))
        out = out + b
    return out


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """Reference kernel: paddle/phi/kernels/gpu(dnn)/conv_kernel; here a
    single lax.conv_general_dilated lowered to MXU convolutions."""
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv2d_p(*args, stride=_pair(stride), padding=_conv_padding(padding, 2),
                     dilation=_pair(dilation), groups=int(groups),
                     data_format=data_format)


@defop("conv1d")
def _conv1d_p(x, weight, bias=None, stride=(1,), padding="VALID", dilation=(1,),
              groups=1, data_format="NCL"):
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        ("NCH", "OIH", "NCH"))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilation, feature_group_count=groups, dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv1d_p(*args, stride=_pair(stride, 1),
                     padding=_conv_padding(padding, 1),
                     dilation=_pair(dilation, 1), groups=int(groups))


@defop("conv3d")
def _conv3d_p(x, weight, bias=None, stride=(1, 1, 1), padding="VALID",
              dilation=(1, 1, 1), groups=1):
    dn = jax.lax.conv_dimension_numbers(x.shape, weight.shape,
                                        ("NCDHW", "OIDHW", "NCDHW"))
    out = jax.lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=padding,
        rhs_dilation=dilation, feature_group_count=groups, dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1, 1)
    return out


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv3d_p(*args, stride=_pair(stride, 3),
                     padding=_conv_padding(padding, 3),
                     dilation=_pair(dilation, 3), groups=int(groups))


@defop("conv2d_transpose")
def _conv2d_transpose_p(x, weight, bias=None, stride=(1, 1), padding=(0, 0),
                        output_padding=(0, 0), dilation=(1, 1), groups=1):
    # weight layout: [in, out//groups, kh, kw] (paddle); lax transposed conv
    # via conv_general_dilated with lhs_dilation
    kh, kw = weight.shape[2], weight.shape[3]
    ph, pw = padding if isinstance(padding, tuple) else (padding, padding)
    oph, opw = output_padding
    pad = [(dilation[0] * (kh - 1) - ph, dilation[0] * (kh - 1) - ph + oph),
           (dilation[1] * (kw - 1) - pw, dilation[1] * (kw - 1) - pw + opw)]
    # flip + transpose kernel to OIHW with swapped in/out
    w = jnp.flip(weight, (2, 3))
    if groups > 1:
        gi = weight.shape[0] // groups
        w = w.reshape(groups, gi, *w.shape[1:])
        w = jnp.moveaxis(w, 2, 1).reshape(groups * w.shape[2], gi, kh, kw)
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad, lhs_dilation=stride,
        rhs_dilation=dilation, feature_group_count=groups,
        dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, output_size=None,
                     data_format="NCHW", name=None):
    args = (_t(x), _t(weight)) + (() if bias is None else (_t(bias),))
    return _conv2d_transpose_p(
        *args, stride=_pair(stride), padding=_pair(padding),
        output_padding=_pair(output_padding), dilation=_pair(dilation),
        groups=int(groups))


# ---------------------------------------------------------------- pooling --
def _pool_pads(spatial, ks, st, padding, ceil_mode):
    """Per-dim (lo, hi) reduce_window pads. ceil_mode adds the trailing
    padding that grows the output to ceil((s+2p-k)/st)+1, with the
    paddle/torch clamp that the last window must start inside
    input+left-pad (reference python/paddle/nn/functional/pooling.py)."""
    pads = []
    for s_in, k, stp, p in zip(spatial, ks, st, padding):
        hi = p
        if ceil_mode:
            out = -(-(s_in + 2 * p - k) // stp) + 1
            if (out - 1) * stp >= s_in + p:
                out -= 1
            need = (out - 1) * stp + k - (s_in + 2 * p)
            if need > 0:
                hi = p + need
        pads.append((p, hi))
    return pads


@defop("max_pool2d")
def _max_pool2d_p(x, kernel_size=(2, 2), stride=(2, 2), padding=(0, 0),
                  ceil_mode=False):
    pads = [(0, 0), (0, 0)] + _pool_pads(x.shape[2:], kernel_size, stride,
                                         padding, ceil_mode)
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
        jnp.iinfo(x.dtype).min
    return jax.lax.reduce_window(
        x, init, jax.lax.max, (1, 1) + kernel_size, (1, 1) + stride, pads)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    ks = _pair(kernel_size)
    st = _pair(stride) if stride is not None else ks
    if return_mask:
        if ceil_mode:
            raise NotImplementedError(
                "max_pool2d: return_mask with ceil_mode is not supported")
        from .functional_more import _pool_with_mask

        return _pool_with_mask(_t(x), ks, st, _pair(padding), "max")
    return _max_pool2d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_pair(padding), ceil_mode=bool(ceil_mode))


@defop("avg_pool2d")
def _avg_pool2d_p(x, kernel_size=(2, 2), stride=(2, 2), padding=(0, 0),
                  exclusive=True, ceil_mode=False, divisor=None):
    sp = _pool_pads(x.shape[2:], kernel_size, stride, padding, ceil_mode)
    pads = [(0, 0), (0, 0)] + sp
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 1) + kernel_size, (1, 1) + stride, pads)
    if divisor is not None:
        return summed / divisor
    if exclusive and any(lo or hi for lo, hi in sp):
        ones = jnp.ones_like(x)
        counts = jax.lax.reduce_window(
            ones, 0.0, jax.lax.add, (1, 1) + kernel_size, (1, 1) + stride, pads)
        return summed / counts
    return summed / (kernel_size[0] * kernel_size[1])


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None, data_format="NCHW",
               name=None):
    ks = _pair(kernel_size)
    st = _pair(stride) if stride is not None else ks
    return _avg_pool2d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_pair(padding), exclusive=bool(exclusive),
                         ceil_mode=bool(ceil_mode),
                         divisor=divisor_override)


@defop("max_pool1d")
def _max_pool1d_p(x, kernel_size=(2,), stride=(2,), padding=(0,),
                  ceil_mode=False):
    pads = [(0, 0), (0, 0)] + _pool_pads(x.shape[2:], kernel_size, stride,
                                         padding, ceil_mode)
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1) + kernel_size, (1, 1) + stride, pads)


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, name=None):
    ks = _pair(kernel_size, 1)
    st = _pair(stride, 1) if stride is not None else ks
    if return_mask:
        if ceil_mode:
            raise NotImplementedError(
                "max_pool1d: return_mask with ceil_mode is not supported")
        from .functional_more import _pool_with_mask

        return _pool_with_mask(_t(x), ks, st, _pair(padding, 1), "max")
    return _max_pool1d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_pair(padding, 1), ceil_mode=bool(ceil_mode))


@defop("avg_pool1d")
def _avg_pool1d_p(x, kernel_size=(2,), stride=(2,), padding=(0,),
                  exclusive=True, ceil_mode=False):
    sp = _pool_pads(x.shape[2:], kernel_size, stride, padding, ceil_mode)
    pads = [(0, 0), (0, 0)] + sp
    s = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 1) + kernel_size, (1, 1) + stride, pads)
    if exclusive and any(lo or hi for lo, hi in sp):
        counts = jax.lax.reduce_window(
            jnp.ones_like(x), 0.0, jax.lax.add, (1, 1) + kernel_size,
            (1, 1) + stride, pads)
        return s / counts
    return s / kernel_size[0]


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True,
               ceil_mode=False, name=None):
    ks = _pair(kernel_size, 1)
    st = _pair(stride, 1) if stride is not None else ks
    return _avg_pool1d_p(_t(x), kernel_size=ks, stride=st,
                         padding=_pair(padding, 1), exclusive=bool(exclusive),
                         ceil_mode=bool(ceil_mode))


@defop("adaptive_avg_pool2d")
def _adaptive_avg_pool2d_p(x, output_size=(1, 1)):
    n, c, h, w = x.shape
    oh, ow = output_size
    if h % oh == 0 and w % ow == 0:
        return x.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
    # general case: interval averaging
    out = jnp.zeros((n, c, oh, ow), x.dtype)
    hs = [(i * h) // oh for i in range(oh + 1)]
    ws = [(j * w) // ow for j in range(ow + 1)]
    rows = []
    for i in range(oh):
        cols = []
        for j in range(ow):
            cols.append(x[:, :, hs[i]:hs[i + 1] or h, ws[j]:ws[j + 1] or w]
                        .mean(axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_avg_pool2d_p(_t(x), output_size=_pair(output_size))


@defop("adaptive_avg_pool1d")
def _adaptive_avg_pool1d_p(x, output_size=1):
    n, c, l = x.shape
    if l % output_size == 0:
        return x.reshape(n, c, output_size, l // output_size).mean(axis=3)
    ls = [(i * l) // output_size for i in range(output_size + 1)]
    return jnp.stack([x[:, :, ls[i]:ls[i + 1] or l].mean(axis=2)
                      for i in range(output_size)], axis=-1)


def adaptive_avg_pool1d(x, output_size, name=None):
    return _adaptive_avg_pool1d_p(_t(x), output_size=int(output_size))


@defop("adaptive_max_pool2d")
def _adaptive_max_pool2d_p(x, output_size=(1, 1)):
    n, c, h, w = x.shape
    oh, ow = output_size
    if h % oh == 0 and w % ow == 0:
        return x.reshape(n, c, oh, h // oh, ow, w // ow).max(axis=(3, 5))
    hs = [(i * h) // oh for i in range(oh + 1)]
    ws = [(j * w) // ow for j in range(ow + 1)]
    rows = []
    for i in range(oh):
        cols = []
        for j in range(ow):
            cols.append(x[:, :, hs[i]:hs[i + 1] or h, ws[j]:ws[j + 1] or w]
                        .max(axis=(2, 3)))
        rows.append(jnp.stack(cols, axis=-1))
    return jnp.stack(rows, axis=-2)


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return _adaptive_max_pool2d_p(_t(x), output_size=_pair(output_size))


# ----------------------------------------------------------------- norms --
@defop("batch_norm_infer")
def _bn_infer_p(x, mean, var, weight, bias, epsilon=1e-5, data_format="NCHW"):
    shape = (1, -1) + (1,) * (x.ndim - 2) if data_format.startswith("NC") \
        else (1,) * (x.ndim - 1) + (-1,)
    inv = jax.lax.rsqrt(var.reshape(shape) + epsilon)
    out = (x - mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


@defop("batch_norm_train")
def _bn_train_p(x, mean, var, weight, bias, epsilon=1e-5, momentum=0.9,
                data_format="NCHW"):
    axes = tuple(i for i in range(x.ndim) if i != (1 if data_format.startswith("NC") else x.ndim - 1))
    batch_mean = jnp.mean(x, axis=axes)
    batch_var = jnp.var(x, axis=axes)
    shape = (1, -1) + (1,) * (x.ndim - 2) if data_format.startswith("NC") \
        else (1,) * (x.ndim - 1) + (-1,)
    inv = jax.lax.rsqrt(batch_var.reshape(shape) + epsilon)
    out = (x - batch_mean.reshape(shape)) * inv
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    new_mean = momentum * mean + (1 - momentum) * batch_mean
    new_var = momentum * var + (1 - momentum) * batch_var
    return out, new_mean, new_var


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Functional batch_norm. In training mode returns output AND updates the
    running-stat tensors in place (their ._data is rebound — under a compiled
    trace these become traced values collected by TrainStep)."""
    x = _t(x)
    if use_global_stats:
        training = False
    if not training:
        return _bn_infer_p(x, _t(running_mean), _t(running_var),
                           None if weight is None else _t(weight),
                           None if bias is None else _t(bias),
                           epsilon=float(epsilon), data_format=data_format)
    out, new_mean, new_var = _bn_train_p(
        x, _t(running_mean), _t(running_var),
        None if weight is None else _t(weight),
        None if bias is None else _t(bias),
        epsilon=float(epsilon), momentum=float(momentum),
        data_format=data_format)
    if isinstance(running_mean, Tensor):
        running_mean._data = new_mean._data
        running_var._data = new_var._data
    return out


@defop("layer_norm")
def _layer_norm_p(x, weight=None, bias=None, epsilon=1e-5, begin_axis=-1):
    axes = tuple(range(begin_axis % x.ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight.reshape(x.shape[begin_axis % x.ndim:])
    if bias is not None:
        out = out + bias.reshape(x.shape[begin_axis % x.ndim:])
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    x = _t(x)
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    begin = x.ndim - len(normalized_shape)
    args = [x]
    return _layer_norm_p(x, None if weight is None else _t(weight),
                         None if bias is None else _t(bias),
                         epsilon=float(epsilon), begin_axis=begin)


@defop("group_norm")
def _group_norm_p(x, weight=None, bias=None, epsilon=1e-5, groups=1):
    n, c = x.shape[:2]
    g = groups
    xs = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xs.ndim))
    mean = jnp.mean(xs, axis=axes, keepdims=True)
    var = jnp.var(xs, axis=axes, keepdims=True)
    out = ((xs - mean) * jax.lax.rsqrt(var + epsilon)).reshape(x.shape)
    shape = (1, c) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None):
    return _group_norm_p(_t(x), None if weight is None else _t(weight),
                         None if bias is None else _t(bias),
                         epsilon=float(epsilon), groups=int(num_groups))


@defop("instance_norm")
def _instance_norm_p(x, weight=None, bias=None, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + epsilon)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        out = out * weight.reshape(shape)
    if bias is not None:
        out = out + bias.reshape(shape)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    return _instance_norm_p(_t(x), None if weight is None else _t(weight),
                            None if bias is None else _t(bias),
                            epsilon=float(eps))


@defop("normalize")
def _normalize_p(x, p=2.0, axis=1, epsilon=1e-12):
    n = jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis, keepdims=True),
                  1.0 / p)
    return x / jnp.maximum(n, epsilon)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return _normalize_p(_t(x), p=float(p), axis=int(axis),
                        epsilon=float(epsilon))


@defop("local_response_norm")
def _lrn_p(x, size=5, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[1]
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (x.ndim - 2))
    acc = sum(padded[:, i:i + c] for i in range(size))
    return x / jnp.power(k + alpha * acc, beta)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return _lrn_p(_t(x), size=int(size), alpha=float(alpha), beta=float(beta),
                  k=float(k))


# ---------------------------------------------------------------- dropout --
def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    """Stateless-PRNG dropout (reference RNG analog: phi Generator/Philox;
    here keys derive from the global generator so compiled traces can rebase
    them — see core/rng.py)."""
    x = _t(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1 - p)
        return x
    key = _rng.next_key()

    def fn(v, k):
        shape = list(v.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(k, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)
        return jnp.where(keep, v, 0.0).astype(v.dtype)

    fn._op_name = "dropout"
    fn._no_jit = True  # key is a fresh value each call; jit would recompile
    return apply(fn, x, key)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    return dropout(x, p=p, axis=[0, 1] if data_format == "NCHW" else [0, 3],
                   training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    return dropout(x, p=p, axis=[0, 1], training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = _t(x)
    if not training or p == 0.0:
        return x
    key = _rng.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def fn(v, k):
        keep = jax.random.bernoulli(k, 1.0 - p, v.shape)
        a = (1.0 / math.sqrt((1 - p) * (1 + p * alpha_p ** 2))) if p < 1 else 1.0
        b = -a * alpha_p * p
        return (a * jnp.where(keep, v, alpha_p) + b).astype(v.dtype)

    fn._op_name = "alpha_dropout"
    fn._no_jit = True
    return apply(fn, x, key)


# ------------------------------------------------------------------ losses --
@defop("mse_loss")
def _mse_loss_p(input, label, reduction="mean"):
    out = jnp.square(input - label)
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def mse_loss(input, label, reduction="mean", name=None):
    return _mse_loss_p(_t(input), _t(label), reduction=reduction)


@defop("l1_loss")
def _l1_loss_p(input, label, reduction="mean"):
    out = jnp.abs(input - label)
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def l1_loss(input, label, reduction="mean", name=None):
    return _l1_loss_p(_t(input), _t(label), reduction=reduction)


@defop("smooth_l1_loss")
def _smooth_l1_p(input, label, reduction="mean", delta=1.0):
    d = jnp.abs(input - label)
    out = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return _smooth_l1_p(_t(input), _t(label), reduction=reduction,
                        delta=float(delta))


@defop("softmax_with_cross_entropy")
def _softmax_ce_p(logits, label, soft_label=False, ignore_index=-100,
                  axis=-1):
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        return -jnp.sum(label * logp, axis=axis, keepdims=True)
    lab = label
    squeeze = False
    if lab.ndim == logits.ndim:
        lab = jnp.squeeze(lab, axis=axis)
        squeeze = True
    nll = -jnp.take_along_axis(logp, jnp.expand_dims(lab, axis), axis=axis)
    mask = (lab != ignore_index)
    nll = jnp.where(jnp.expand_dims(mask, axis), nll, 0.0)
    return nll


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    out = _softmax_ce_p(_t(logits), _t(label), soft_label=bool(soft_label),
                        ignore_index=int(ignore_index), axis=int(axis))
    if return_softmax:
        return out, softmax(logits, axis=axis)
    return out


@defop("cross_entropy")
def _cross_entropy_p(input, label, weight=None, soft_label=False,
                     ignore_index=-100, reduction="mean", axis=-1,
                     label_smoothing=0.0, use_softmax=True):
    if use_softmax:
        logp = jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(input, 1e-30))
    n_classes = input.shape[axis]
    if soft_label:
        tgt = label
        if label_smoothing > 0:
            tgt = tgt * (1 - label_smoothing) + label_smoothing / n_classes
        loss = -jnp.sum(tgt * logp, axis=axis)
        valid = jnp.ones(loss.shape, bool)
    else:
        lab = label
        if lab.ndim == input.ndim:
            lab = jnp.squeeze(lab, axis=axis)
        valid = lab != ignore_index
        safe_lab = jnp.where(valid, lab, 0)
        if label_smoothing > 0:
            onehot = jax.nn.one_hot(safe_lab, n_classes, axis=axis,
                                    dtype=logp.dtype)
            tgt = onehot * (1 - label_smoothing) + label_smoothing / n_classes
            loss = -jnp.sum(tgt * logp, axis=axis)
        else:
            loss = -jnp.squeeze(
                jnp.take_along_axis(logp, jnp.expand_dims(safe_lab, axis),
                                    axis=axis), axis)
        if weight is not None:
            w = jnp.take(weight, safe_lab)
            loss = loss * w
        loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
        if weight is not None and not soft_label:
            lab2 = label
            if lab2.ndim == input.ndim:
                lab2 = jnp.squeeze(lab2, axis=axis)
            wsum = jnp.sum(jnp.where(valid, jnp.take(weight,
                                                     jnp.where(valid, lab2, 0)),
                                     0.0))
            denom = jnp.maximum(wsum, 1e-12)
        return jnp.sum(loss) / denom
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Reference: python/paddle/nn/functional/loss.py cross_entropy."""
    args = (_t(input), _t(label)) + (() if weight is None else (_t(weight),))
    return _cross_entropy_p(*args, soft_label=bool(soft_label),
                            ignore_index=int(ignore_index),
                            reduction=reduction, axis=int(axis),
                            label_smoothing=float(label_smoothing),
                            use_softmax=bool(use_softmax))


@defop("nll_loss")
def _nll_loss_p(input, label, weight=None, ignore_index=-100,
                reduction="mean"):
    # input: log-probabilities [N, C, ...]
    lab = label
    valid = lab != ignore_index
    safe = jnp.where(valid, lab, 0)
    ll = -jnp.take_along_axis(input, jnp.expand_dims(safe, 1), axis=1)
    ll = jnp.squeeze(ll, 1)
    if weight is not None:
        w = jnp.take(weight, safe)
        ll = ll * w
    ll = jnp.where(valid, ll, 0.0)
    if reduction == "mean":
        denom = jnp.sum(jnp.where(valid, jnp.take(weight, safe), 0.0)) \
            if weight is not None else jnp.maximum(
                jnp.sum(valid.astype(ll.dtype)), 1.0)
        return jnp.sum(ll) / denom
    if reduction == "sum":
        return jnp.sum(ll)
    return ll


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    args = (_t(input), _t(label)) + (() if weight is None else (_t(weight),))
    return _nll_loss_p(*args, ignore_index=int(ignore_index),
                       reduction=reduction)


@defop("binary_cross_entropy")
def _bce_p(input, label, weight=None, reduction="mean"):
    out = -(label * jnp.log(jnp.maximum(input, 1e-12))
            + (1 - label) * jnp.log(jnp.maximum(1 - input, 1e-12)))
    if weight is not None:
        out = out * weight
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    args = (_t(input), _t(label)) + (() if weight is None else (_t(weight),))
    return _bce_p(*args, reduction=reduction)


@defop("binary_cross_entropy_with_logits")
def _bce_logits_p(logit, label, weight=None, pos_weight=None,
                  reduction="mean"):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1) * label + 1
        out = (1 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        out = (1 - label) * logit + max_val + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
    if weight is not None:
        out = out * weight
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    x = [_t(logit), _t(label)]
    if weight is not None:
        x.append(_t(weight))
    kw = {}
    if pos_weight is not None:
        # pass positionally through pytree (tensor), weight slot may be None
        if weight is None:
            return _bce_logits_p(_t(logit), _t(label), None, _t(pos_weight),
                                 reduction=reduction)
        return _bce_logits_p(_t(logit), _t(label), _t(weight), _t(pos_weight),
                             reduction=reduction)
    return _bce_logits_p(*x, reduction=reduction)


@defop("kl_div")
def _kl_div_p(input, label, reduction="mean"):
    out = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "batchmean":
        return jnp.sum(out) / input.shape[0]
    if reduction == "sum":
        return jnp.sum(out)
    return out


def kl_div(input, label, reduction="mean", name=None):
    return _kl_div_p(_t(input), _t(label), reduction=reduction)


@defop("cosine_similarity")
def _cos_sim_axis_p(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.sqrt(jnp.sum(jnp.square(x1), axis=axis))
    n2 = jnp.sqrt(jnp.sum(jnp.square(x2), axis=axis))
    return dot / jnp.maximum(n1 * n2, eps)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    return _cos_sim_axis_p(_t(x1), _t(x2), axis=int(axis), eps=float(eps))


@defop("margin_ranking_loss")
def _margin_rank_p(input, other, label, margin=0.0, reduction="mean"):
    out = jnp.maximum(-label * (input - other) + margin, 0.0)
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return _margin_rank_p(_t(input), _t(other), _t(label),
                          margin=float(margin), reduction=reduction)


@defop("hinge_embedding_loss")
def _hinge_embed_p(input, label, margin=1.0, reduction="mean"):
    out = jnp.where(label == 1, input, jnp.maximum(margin - input, 0.0))
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return _hinge_embed_p(_t(input), _t(label), margin=float(margin),
                          reduction=reduction)


# ------------------------------------------------------------- attention --
@defop("scaled_dot_product_attention")
def _sdpa_p(q, k, v, mask=None, dropout_p=0.0, is_causal=False, scale=None):
    """Fused attention. On TPU, unmasked/causal attention runs the Pallas
    flash kernel (paddle_tpu/ops/pallas/flash_attention.py — role of the
    reference's flash_attn_kernel.cu): O(L·D) HBM traffic instead of the
    materialized [L,L] probability matrix. Other shapes fall back to the
    XLA-fused softmax(QK^T)V path."""
    from ..core.flags import flag

    # backend gate: the Mosaic kernel is TPU-only;
    # force_flash_attention opts in regardless, for cross-lowering
    # jax.export tests on CPU hosts. A shape the gate admits runs the
    # kernel or raises — a compile failure never gives way to einsum.
    if (flag("use_flash_attention") and mask is None
            and dropout_p == 0.0 and q.shape == k.shape == v.shape
            and (jax.default_backend() == "tpu"
                 or flag("force_flash_attention"))):
        from ..ops.pallas import (
            flash_attention as _flash, flash_attention_supported)

        if flash_attention_supported(q.shape, q.dtype, bool(is_causal)):
            return _flash(q, k, v, causal=bool(is_causal), sm_scale=scale)
    # pure-XLA chunked path (no Pallas) for shapes and backends the
    # gate does not admit: the einsum path materializes [B,H,L,L] scores
    # in HBM. Scanning query chunks with per-chunk remat bounds live
    # attention memory at [B,H,chunk,L] and lets XLA fuse mask+softmax
    # into the chunk matmuls, while staying exact (full-row softmax per
    # chunk).
    chunk = int(flag("attention_chunk"))
    L = q.shape[1]
    if (chunk > 0 and mask is None and dropout_p == 0.0
            and q.shape[1] == k.shape[1] and L >= 1024
            and L % chunk == 0 and L > chunk):
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        return _chunked_attention(jnp.swapaxes(q, 1, 2),
                                  jnp.swapaxes(k, 1, 2),
                                  jnp.swapaxes(v, 1, 2),
                                  bool(is_causal), jnp.float32(s), chunk)
    probs, vh = _attention_probs(q, k, v, mask, is_causal, scale)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _chunked_attention(qh, kh, vh, causal, s, chunk):
    """Exact attention as a lax.scan over query chunks ([B,H,L,D] in/out,
    chunk-local full-row softmax; jax.checkpoint per chunk so backward
    rematerializes chunk scores instead of storing them all)."""
    B, H, L, D = qh.shape
    n = L // chunk
    qs = qh.reshape(B, H, n, chunk, D)
    kpos = jnp.arange(L, dtype=jnp.int32)

    @jax.checkpoint
    def one_chunk(i, qc):
        logits = jnp.einsum("bhqd,bhkd->bhqk", qc, kh,
                            preferred_element_type=jnp.float32) * s
        if causal:
            qpos = i * jnp.int32(chunk) + jnp.arange(chunk,
                                                     dtype=jnp.int32)
            m = kpos[None, :] <= qpos[:, None]
            logits = jnp.where(m[None, None], logits,
                               jnp.float32(-1e30))
        p = jax.nn.softmax(logits, axis=-1).astype(vh.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vh)

    def body(_, xs):
        i, qc = xs
        return None, one_chunk(i, qc)

    _, outs = jax.lax.scan(
        body, None,
        (jnp.arange(n, dtype=jnp.int32), jnp.moveaxis(qs, 2, 0)))
    out = jnp.moveaxis(outs, 0, 2).reshape(B, H, L, D)
    return jnp.swapaxes(out, 1, 2)


def _attention_probs(q, k, v, mask, is_causal, scale):
    """Shared einsum-attention core ([B,L,H,D] in): softmax probabilities
    + head-major V — ONE copy of the mask/scale/softmax semantics for
    the deterministic and dropout paths (they must never diverge)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if is_causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask
    return jax.nn.softmax(logits, axis=-1), vh


def _sdpa_dropout_fn(q, k, v, rng_key, mask=None, dropout_p=0.1,
                     is_causal=False, scale=None):
    """Attention WITH dropout on the probabilities (reference applies
    dropout post-softmax, flash_attn_kernel.cu / F.sdpa semantics). The
    rng key threads the stateless-PRNG machinery exactly like
    F.dropout — sdpa_dropout is the op the coverage gate sees."""
    probs, vh = _attention_probs(q, k, v, mask, is_causal, scale)
    keep = jax.random.bernoulli(rng_key, 1.0 - dropout_p, probs.shape)
    probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(
        probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


_sdpa_dropout_fn._op_name = "sdpa_dropout"
_sdpa_dropout_fn._no_jit = True  # fresh PRNG key arg per call (F.dropout)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    args = (_t(query), _t(key), _t(value))
    if dropout_p and training:
        # dropout really applies (was silently ignored before r4): the
        # key rides as an arg so compiled traces can rebase it
        rng_key = _rng.next_key()
        if attn_mask is not None:
            return apply(_sdpa_dropout_fn, *args, rng_key, _t(attn_mask),
                         dropout_p=float(dropout_p),
                         is_causal=bool(is_causal))
        return apply(_sdpa_dropout_fn, *args, rng_key,
                     dropout_p=float(dropout_p), is_causal=bool(is_causal))
    if attn_mask is not None:
        return _sdpa_p(*args, _t(attn_mask), is_causal=bool(is_causal))
    return _sdpa_p(*args, is_causal=bool(is_causal))


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention analog (reference
    python/paddle/nn/functional/flash_attention.py:flash_attention)."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen flash attention over cu_seqlens-packed sequences (reference
    flash_attention.py:flash_attn_unpadded). TPU formulation: the packed
    [total, H, D] tokens are re-segmented by cu_seqlens (host-static) and
    each sequence attends within its own segment — equivalent to the
    varlen kernel's block-diagonal masking."""
    import numpy as np

    q = _t(query)
    k = _t(key)
    v = _t(value)
    cq = np.asarray(_t(cu_seqlens_q)._data).astype("int64")
    ck = np.asarray(_t(cu_seqlens_k)._data).astype("int64")
    if len(cq) != len(ck):
        raise ValueError("cu_seqlens_q and cu_seqlens_k must align")
    outs = []
    for i in range(len(cq) - 1):
        qs = q[int(cq[i]):int(cq[i + 1])].unsqueeze(0)   # [1, Lq, H, D]
        ks = k[int(ck[i]):int(ck[i + 1])].unsqueeze(0)
        vs = v[int(ck[i]):int(ck[i + 1])].unsqueeze(0)
        if scale is not None:
            # fold the custom scale into q (sdpa uses 1/sqrt(D))
            import math as _m

            qs = qs * (scale * _m.sqrt(qs.shape[-1]))
        o = scaled_dot_product_attention(qs, ks, vs, None, dropout,
                                         causal, training)
        outs.append(o.squeeze(0))
    from ..ops.manipulation import concat

    res = concat(outs, axis=0)
    return (res, None) if return_softmax else (res, None)


# ------------------------------------------------------------------ misc --
@defop("interpolate_nearest")
def _interp_nearest_p(x, out_hw=(1, 1)):
    n, c, h, w = x.shape
    oh, ow = out_hw
    ri = (jnp.arange(oh) * h // oh).astype(jnp.int32)
    ci = (jnp.arange(ow) * w // ow).astype(jnp.int32)
    return x[:, :, ri][:, :, :, ci]


@defop("interpolate_bilinear")
def _interp_bilinear_p(x, out_hw=(1, 1), align_corners=False):
    n, c, h, w = x.shape
    oh, ow = out_hw
    if not align_corners:
        return jax.image.resize(x, (n, c, oh, ow), method="bilinear")
    # corner-aligned: src = i * (S-1)/(O-1); jax.image.resize has no
    # align_corners mode, so gather+lerp explicitly
    def coords(o, s):
        if o == 1:
            return jnp.zeros((1,), x.dtype)
        return jnp.arange(o, dtype=jnp.float32) * ((s - 1) / (o - 1))

    ys, xs = coords(oh, h), coords(ow, w)
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = (ys - y0).astype(x.dtype)[None, None, :, None]
    wx = (xs - x0).astype(x.dtype)[None, None, None, :]
    g = lambda yi, xi: x[:, :, yi][:, :, :, xi]
    top = g(y0, x0) * (1 - wx) + g(y0, x1) * wx
    bot = g(y1, x0) * (1 - wx) + g(y1, x1) * wx
    return top * (1 - wy) + bot * wy


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    x = _t(x)
    h, w = x.shape[2], x.shape[3]
    if size is not None:
        if isinstance(size, Tensor):
            size = size.tolist()
        oh, ow = int(size[0]), int(size[1])
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
            else (scale_factor, scale_factor)
        oh, ow = int(h * sf[0]), int(w * sf[1])
    if mode == "nearest":
        return _interp_nearest_p(x, out_hw=(oh, ow))
    return _interp_bilinear_p(x, out_hw=(oh, ow),
                              align_corners=bool(align_corners))


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, name=None):
    return interpolate(x, size, scale_factor, mode, align_corners)


@defop("pixel_shuffle")
def _pixel_shuffle_p(x, upscale_factor=2):
    n, c, h, w = x.shape
    r = upscale_factor
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    return _pixel_shuffle_p(_t(x), upscale_factor=int(upscale_factor))


@defop("unfold")
def _unfold_p(x, kernel_sizes=(1, 1), strides=(1, 1), paddings=(0, 0),
              dilations=(1, 1)):
    n, c, h, w = x.shape
    kh, kw = kernel_sizes
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), strides, [(paddings[0], paddings[0]),
                               (paddings[1], paddings[1])],
        rhs_dilation=dilations,
        dimension_numbers=jax.lax.conv_dimension_numbers(
            x.shape, (1, c, kh, kw), ("NCHW", "OIHW", "NCHW")))
    return patches.reshape(n, c * kh * kw, -1)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    return _unfold_p(_t(x), kernel_sizes=_pair(kernel_sizes),
                     strides=_pair(strides), paddings=_pair(paddings),
                     dilations=_pair(dilations))


@defop("sequence_mask")
def _sequence_mask_p(lengths, maxlen=1, dtype="int64"):
    return (jnp.arange(maxlen)[None, :] < lengths[..., None]).astype(dtype)


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    lengths = _t(lengths)
    ml = int(maxlen) if maxlen is not None else int(lengths.numpy().max())
    return _sequence_mask_p(lengths, maxlen=ml, dtype=str(dtype))


from ..ops.manipulation import pad  # noqa: E402,F401  (re-export, paddle parity)

label_smooth = None  # placeholder replaced below


@defop("label_smooth")
def _label_smooth_p(label, epsilon=0.1):
    n = label.shape[-1]
    return label * (1 - epsilon) + epsilon / n


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):  # noqa: F811
    return _label_smooth_p(_t(label), epsilon=float(epsilon))


@defop("temporal_shift")
def _temporal_shift_p(x, seg_num=1, shift_ratio=0.25):
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([xr[:, 1:, :fold], jnp.zeros_like(xr[:, :1, :fold])], 1)
    right = jnp.concatenate([jnp.zeros_like(xr[:, :1, fold:2 * fold]),
                             xr[:, :-1, fold:2 * fold]], 1)
    rest = xr[:, :, 2 * fold:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None, data_format="NCHW"):
    return _temporal_shift_p(_t(x), seg_num=int(seg_num),
                             shift_ratio=float(shift_ratio))

from .functional_more import *  # noqa: E402,F401,F403 (surface widening)
