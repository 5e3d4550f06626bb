"""Quantized serving tier: int8 KV-cache pool + weight-only int8 params.

The serving engine's KV pool is where generation memory actually goes:
per capacity class a [rows, L, cap, H*Dh] float32 buffer pair (the
heads folded into the minor dimension: dense 128-lane tiles on a TPU)
whose rows are decode slots, scratch, and prefix-cache entries. This
module re-types that buffer as a ``QuantizedKV`` — int8 data plus a
per-(row, layer) float32 absmax scale tensor — and provides the
quantize-on-write / dequantize-on-read primitives the generation
program bodies fuse in-trace. The bodies carry the pool through their
scan over layers and touch it where it lies: ``write_layer`` puts a
layer's new positions into the (donated) pool, ``read_layer`` reads one
layer of the rows a step decodes — no program gathers whole rows.
Because ``QuantizedKV`` is a NamedTuple (a jax pytree), it rides the
existing program signatures, ``donate_argnums`` sets, ``device_put``
paths and the persistent compile cache exactly like the float32 array
it replaces; the float path's helpers reduce to the plain ops.

Scale scheme (per (pool row, layer), symmetric, no zero point):

- ``store_block`` (prefill) RESETS the row's scale from the scattered
  block's per-layer absmax (floored at ``_ABSMAX_FLOOR`` so an all-zero
  warmup block cannot divide by zero), then quantizes the block.
- ``write_layer`` (decode / verify / extend) quantizes new positions
  with the row's EXISTING scale — clip semantics: a late outlier
  saturates at +-127 rather than rescaling (and thus requantizing) the
  whole row. This is the documented long-context error source
  (DESIGN.md "Quantized serving").
- Write first, read after: a body writes a layer's new positions into
  the pool and only then attends over ``read_layer``, so a verify
  program attending freshly-written block positions sees the SAME
  values a plain decode step would read back next iteration — pool-
  consistent by construction, which is what keeps spec-on output
  bitwise-equal to spec-off under the int8 pool.
- ``copy_row`` copies raw int8 rows plus their scale row: a prefix-
  cache hit is bit-exact, never a requantization.

Weight-only int8 (``quantize_stacked_params``) reuses the quantization
package's absmax machinery (``quantize_absmax`` — the same formula
``QuantizedLinear.from_float`` bakes) over the stacked scan params:
matmul weights become ``name__q`` (int8) + ``name__s`` (float32,
broadcast-ready) pairs and the float entry is dropped, so the params at
rest on the device are int8 — that is the density win. The program
bodies call ``dequant_params`` at trace time (dequant-in-matmul; XLA
fuses the multiply into the consumer). Embeddings, layer norms and
biases stay float; a tied ``lm_head`` (``wte.T``) stays float too.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import numpy as np

_QMAX = 127.0
# absmax floor before the /127: a zero block (warmup, or a pathological
# prompt) quantizes against this instead of dividing by zero
_ABSMAX_FLOOR = 1e-6

# stacked-scan matmul weights eligible for weight-only int8; everything
# else (wte/wpe embeddings, norms, biases) stays float32
_QUANT_WEIGHT_KEYS = ("qkv_w", "out_w", "fc1_w", "fc2_w", "lm_head")


class QuantizedKV(NamedTuple):
    """One KV pool buffer quantized to int8 with per-(row, layer)
    absmax scales. A jax pytree, so it flows through jit signatures,
    donation sets and device placement like the float array it
    replaces."""

    data: Any    # int8 [rows, L, cap, H*Dh]
    scale: Any   # f32  [rows, L] — absmax/127 per pool row per layer

    def block_until_ready(self):
        self.data.block_until_ready()
        return self

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes) + int(self.scale.nbytes)


def is_quantized(buf) -> bool:
    return isinstance(buf, QuantizedKV)


def _bscale(s, x):
    """Right-pad scale s with singleton dims so it broadcasts over x's
    trailing axes (s indexes x's LEADING axes)."""
    return s.reshape(s.shape + (1,) * (x.ndim - s.ndim))


def quant(x, s):
    """Symmetric int8 grid values for x under scale s (float result —
    callers .astype(int8) for storage)."""
    import jax.numpy as jnp

    return jnp.clip(jnp.round(x / _bscale(s, x)), -_QMAX, _QMAX)


def block_scale(ks):
    """Per-layer absmax scale [L] for a fresh [L, S, H, Dh] K/V block
    (floored: an all-zero warmup block must not divide by zero)."""
    import jax.numpy as jnp

    a = jnp.max(jnp.abs(ks), axis=(1, 2, 3))
    return jnp.maximum(a, _ABSMAX_FLOOR) / _QMAX


def alloc(shape, device, kv_dtype: str):
    """Zeroed pool buffer of `shape` committed to `device`: a plain
    float32 array for kv_dtype='f32', a QuantizedKV (int8 zeros + unit
    scales) for 'int8'."""
    import jax
    import jax.numpy as jnp

    if kv_dtype == "f32":
        return jax.device_put(jnp.zeros(shape, jnp.float32), device)
    return QuantizedKV(
        jax.device_put(jnp.zeros(shape, jnp.int8), device),
        jax.device_put(jnp.ones((shape[0], shape[1]), jnp.float32),
                       device))


def pool_nbytes(shape, kv_dtype: str) -> int:
    """Bytes one pool buffer of `shape` allocates — matches alloc()
    exactly (int8 data + the f32 per-(row, layer) scale tensor)."""
    n = int(np.prod(shape))
    if kv_dtype == "f32":
        return n * 4
    return n + int(shape[0]) * int(shape[1]) * 4


def capacity(buf) -> int:
    """Positions one pool row holds a layer (the class cap)."""
    return (buf.data if is_quantized(buf) else buf).shape[2]


def store_block(buf, slot, ks):
    """Prefill-style full-block store: ks [L, S, H, Dh] lands, heads
    folded, at positions [0, S) of pool row `slot` (S <= cap).
    Quantized pool: the row's scale is RESET from this block's per-layer
    absmax, then the block is quantized with it."""
    import jax
    import jax.numpy as jnp

    z = jnp.int32(0)
    fold = ks.shape[:2] + (-1,)
    if not is_quantized(buf):
        return jax.lax.dynamic_update_slice(
            buf, ks.reshape(fold)[None].astype(buf.dtype), (slot, z, z, z))
    s = block_scale(ks)                                        # [L]
    q = quant(ks, s).astype(jnp.int8).reshape(fold)
    data = jax.lax.dynamic_update_slice(buf.data, q[None],
                                        (slot, z, z, z))
    scale = jax.lax.dynamic_update_slice(buf.scale, s[None], (slot, z))
    return QuantizedKV(data, scale)


def read_layer(buf, slots, layer):
    """One layer of the pool rows `slots` (an array), dequantized:
    f32 [..., cap, H*Dh]. The only read a program makes of rows it
    decodes — a layer at a time, never the whole rows."""
    if not is_quantized(buf):
        return buf[slots, layer]
    s = buf.scale[slots, layer]
    return buf.data[slots, layer].astype(s.dtype) * s[..., None, None]


def write_layer(buf, layer, wslot, wpos, vals):
    """New positions of one layer into the pool, in place where the pool
    is donated: vals, of shape wslot.shape + (H*Dh,), land at
    buf[wslot, layer, wpos]. Quantized writes use each target row's
    EXISTING scale (clip semantics — no rescaling)."""
    import jax.numpy as jnp

    if not is_quantized(buf):
        return buf.at[wslot, layer, wpos].set(vals.astype(buf.dtype))
    q = quant(vals, buf.scale[wslot, layer]).astype(jnp.int8)
    return buf._replace(data=buf.data.at[wslot, layer, wpos].set(q))


def copy_row(buf, src, dst):
    """Pool-row copy (prefix-cache admit / hit): int8 rows copy raw
    plus their scale row — bit-exact, never a requantization."""
    if not is_quantized(buf):
        return buf.at[dst].set(buf[src])
    return QuantizedKV(buf.data.at[dst].set(buf.data[src]),
                       buf.scale.at[dst].set(buf.scale[src]))


def row_raw(buf, slot):
    """One pool row in its STORED dtype and layout: ``(data
    [L, cap, H*Dh], scale [L] | None)``. The KV-handoff export path — an int8 row
    ships as int8 bytes plus its scale row (half the f32 wire bytes)
    and never round-trips through float."""
    if not is_quantized(buf):
        return buf[slot], None
    return buf.data[slot], buf.scale[slot]


def set_row_raw(buf, slot, data, scale=None):
    """Install raw row bytes (the ``row_raw`` counterpart) into pool
    row ``slot`` — bit-exact like ``copy_row``, never a
    requantization. ``scale`` is required for a quantized pool."""
    if not is_quantized(buf):
        return buf.at[slot].set(data.astype(buf.dtype))
    return QuantizedKV(buf.data.at[slot].set(data.astype(buf.data.dtype)),
                       buf.scale.at[slot].set(
                           scale.astype(buf.scale.dtype)))


def quantize_stacked_params(params: dict) -> dict:
    """Weight-only int8 over a stacked scan-param dict (host-side, once
    per engine — replica warmup device_puts the int8 result). Matmul
    weights get per-layer (leading-axis) absmax scales via the
    quantization package's ``quantize_absmax``; an unstacked lm_head is
    per-tensor. Returns a NEW dict; float matmul entries are dropped."""
    import jax.numpy as jnp

    from . import quantize_absmax

    out = {}
    for k, v in params.items():
        if k not in _QUANT_WEIGHT_KEYS:
            out[k] = v
            continue
        w = np.asarray(v, np.float32)
        axis = tuple(range(1, w.ndim)) if k != "lm_head" else None
        q, s = quantize_absmax(w, axis=axis)
        out[k + "__q"] = jnp.asarray(q)
        out[k + "__s"] = jnp.asarray(s, jnp.float32)
    return out


def dequant_params(p: dict) -> dict:
    """Reconstruct float matmul weights from __q/__s pairs at trace
    time (dequant-in-matmul: the device-resident params stay int8).
    Identity for an unquantized dict — the float path's programs trace
    exactly as before."""
    if not any(k.endswith("__q") for k in p):
        return p
    out = {k: v for k, v in p.items() if not k.endswith(("__q", "__s"))}
    for k in p:
        if k.endswith("__q"):
            base = k[:-3]
            out[base] = (p[k].astype(p[base + "__s"].dtype)
                         * p[base + "__s"])
    return out


__all__ = ["QuantizedKV", "is_quantized", "alloc", "pool_nbytes",
           "quant", "block_scale", "capacity", "store_block",
           "read_layer", "write_layer", "copy_row", "row_raw",
           "set_row_raw", "quantize_stacked_params", "dequant_params"]
