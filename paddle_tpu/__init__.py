"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities (see SURVEY.md for the blueprint; reference mounted at
/root/reference).

Not a port: eager tensors wrap jax.Array, autograd is a tape of jax.vjp
pullbacks, the op library is pure-JAX functions fused by XLA, distributed
training is SPMD over a named `jax.sharding.Mesh` (collectives ride ICI), and
the static path traces whole train steps into single compiled programs.
"""
from __future__ import annotations

import jax as _jax

# int64/float64 support (paddle defaults int64 indices); creation ops still
# default floats to float32 — f64 never reaches TPU unless explicitly asked.
_jax.config.update("jax_enable_x64", True)
# fp32 matmuls stay true fp32 (loss-curve parity with the GPU reference);
# MXU speed comes from explicit bf16 dtypes via AMP, not degraded fp32.
_jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache (JAX_COMPILATION_CACHE_DIR where set, else
# FLAGS_compile_cache_dir, default <checkout>/.jax_cache): compiled
# eager-op plans and TrainStep programs survive process restarts
# (core/compile_cache.py).
from .core import compile_cache as _compile_cache  # noqa: E402

_compile_cache.setup()

from .core import autograd  # noqa: E402
from .core.autograd import grad  # noqa: E402
from .core.dtype import (  # noqa: E402
    bfloat16, bool_, complex64, complex128, float16, float32, float64, int8,
    int16, int32, int64, uint8)
from .core.flags import get_flags, set_flags  # noqa: E402
from .core.place import (  # noqa: E402
    CPUPlace, Place, TPUPlace, get_device, is_compiled_with_tpu, set_device)
from .core.rng import seed  # noqa: E402
from .core.state import enable_grad, is_grad_enabled, no_grad  # noqa: E402
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: E402
from .ops import *  # noqa: E402,F401,F403
from .ops import abs, all, any, max, min, pow, round, sum  # noqa: E402,F401

CUDAPlace = TPUPlace  # alias: device place on the accelerator
CUDAPinnedPlace = CPUPlace  # host staging memory is plain host memory here
bool = bool_  # paddle.bool
dtype = type(float32)  # paddle.dtype: the canonical dtype class


def get_default_dtype():
    from . import framework as _fw

    return _fw.get_default_dtype()


def set_default_dtype(d):
    from . import framework as _fw

    return _fw.set_default_dtype(d)


def in_dynamic_mode():
    from . import framework as _fw

    return not _static_mode and _fw.in_dynamic_mode()


_static_mode = False


def enable_static():
    """Static-graph mode toggle kept for parity: the static path here is
    trace-and-compile (paddle_tpu.static Executor over compiled callables),
    so this only flips the mode flag that in_dynamic_mode reports."""
    global _static_mode
    _static_mode = True


def disable_static():
    global _static_mode
    _static_mode = False


def is_compiled_with_cuda() -> bool:  # API parity; TPU build has no CUDA
    return False


def is_grad_enabled_():
    return is_grad_enabled()


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """paddle.create_parameter analog (bias -> zeros, else Xavier-normal)."""
    import math as _math

    import jax

    from .core import rng as _rng
    from .core.dtype import convert_dtype

    shape = [int(s) for s in shape]
    dt = convert_dtype(dtype)
    if default_initializer is None:
        if is_bias:
            p = Parameter(_jax.numpy.zeros(shape, dt), name=name)
        else:
            fan_in = shape[0] if shape else 1
            fan_out = shape[1] if len(shape) > 1 else 1
            # NB: `max` here is paddle's reduction op (module-level *-import);
            # use arithmetic to avoid the builtin shadowing hazard
            denom = fan_in + fan_out if fan_in + fan_out > 0 else 1
            std = _math.sqrt(2.0 / denom)
            p = Parameter(
                (std * jax.random.normal(_rng.next_key(), shape)).astype(dt),
                name=name)
    else:
        from .ops import zeros

        p = Parameter(zeros(shape, dtype)._data, name=name)
        default_initializer(p)
    return p


def broadcast_shape(x_shape, y_shape):
    """Result shape of broadcasting two shapes (reference
    python/paddle/tensor/manipulation.py broadcast_shape)."""
    import numpy as _np

    return list(_np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr formatting (reference framework set_printoptions);
    delegates to numpy since Tensor repr prints via numpy()."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


class set_grad_enabled:
    """Context manager / immediate switch for autograd recording
    (reference python/paddle/autograd/py_layer.py set_grad_enabled)."""

    def __init__(self, mode: bool):
        from .core import state as _st

        self._prev = _st.is_grad_enabled()
        _st.set_grad_enabled(bool(mode))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        from .core import state as _st

        _st.set_grad_enabled(self._prev)
        return False


def get_rng_state(device=None):
    """Opaque RNG state: (seed, counter) of the stateless Philox generator
    (reference get_rng_state returns GeneratorState list)."""
    from .core import rng as _rng

    return [_rng.default_generator().get_state()]


def set_rng_state(state_list, device=None):
    from .core import rng as _rng

    _rng.default_generator().set_state(tuple(state_list[0]))


def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state_list):
    set_rng_state(state_list)


def disable_signal_handler():
    """No-op: signal handling is owned by the Python runtime here
    (the reference installs C++ fatal-signal handlers)."""


class LazyGuard:
    """Parameter-init deferral scope. The TPU design initializes eagerly on
    host/device via stateless keys (cheap, no graph rewrite), so the guard
    is a transparent scope kept for API parity (reference
    python/paddle/fluid/lazy_init.py LazyGuard)."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def batch(reader, batch_size, drop_last=False):
    """Wrap a sample reader into a batch reader (reference
    python/paddle/batch.py:18)."""

    def batch_reader():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batch_reader


# Heavy re-exports resolved lazily (reference exposes these at top level)
_LAZY_ALIASES = {
    "Model": ("hapi", "Model"),
    "summary": ("hapi", "summary"),
    "flops": ("hapi", "flops"),
    "ParamAttr": ("nn", "ParamAttr"),
    "DataParallel": ("distributed", "DataParallel"),
    "signal": ("ops.signal", None),
}


def __getattr__(name):
    # Lazy subpackages (nn, optimizer, amp, io, jit, distributed, …) so that
    # `import paddle_tpu` stays light and circular imports are impossible.
    import importlib

    if name == "fft":
        mod = importlib.import_module(".ops.fft", __name__)
        globals()[name] = mod
        return mod
    if name in _LAZY_ALIASES:
        modname, attr = _LAZY_ALIASES[name]
        mod = importlib.import_module(f".{modname}", __name__)
        obj = getattr(mod, attr) if attr else mod
        globals()[name] = obj
        return obj
    if name in ("nn", "optimizer", "amp", "io", "jit", "distributed", "vision",
                "metric", "hapi", "profiler", "incubate", "static", "models",
                "framework", "autograd_api", "device", "sparse", "distribution",
                "text", "audio", "onnx", "quantization", "inference",
                "observability", "geometric"):
        mod = importlib.import_module(f".{name}" if name != "autograd_api"
                                      else ".autograd_api", __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


from .framework_io import load, save  # noqa: E402
from .core.methods import monkey_patch_tensor as _mpt  # noqa: E402

_mpt()


def sigmoid(x, name=None):
    from .nn import functional as _F

    return _F.sigmoid(x)


def _lift_inplace(name):
    def fn(x, *args, **kwargs):
        return getattr(x, name)(*args, **kwargs)

    fn.__name__ = name
    fn.__doc__ = f"In-place variant (paddle.{name}); rebinds x's storage."
    return fn


for _n in ("exp_", "sqrt_", "rsqrt_", "reciprocal_", "ceil_", "floor_",
           "round_", "tanh_", "erfinv_", "remainder_", "lerp_", "squeeze_",
           "unsqueeze_", "flatten_", "scatter_", "put_along_axis_",
           "index_add_", "sigmoid_", "uniform_", "exponential_", "zero_",
           "fill_", "masked_fill_"):
    if hasattr(Tensor, _n) and _n not in globals():
        globals()[_n] = _lift_inplace(_n)
del _n

def check_shape(shape):
    """Validate a shape argument (reference utils/layers_utils.py:463)."""
    if isinstance(shape, (list, tuple)):
        if not shape:
            raise ValueError("shape must not be empty")
        for s in shape:
            if not isinstance(s, int) and not hasattr(s, "_data"):
                raise TypeError(f"shape element must be int/Tensor, got {type(s)}")
            if isinstance(s, int) and s < -1:
                raise ValueError(f"invalid dim {s} in shape")
    elif not hasattr(shape, "_data"):
        raise TypeError("shape must be a list/tuple/Tensor")


def __dir__():
    return sorted(set(globals()) | set(_LAZY_ALIASES) |
                  {"nn", "optimizer", "amp", "io", "jit", "distributed",
                   "vision", "metric", "hapi", "profiler", "incubate",
                   "static", "models", "framework", "device", "sparse",
                   "distribution", "text", "audio", "onnx", "quantization",
                   "inference", "fft"})


__version__ = "0.2.0"
