"""The plain reference: Brumby-14B-Base's forward pass (`brumby`), in
jax.numpy and float32 at "highest" matmul precision, in the ATTENTION form.

Written from the model's public `config.json`
(https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json:
the dense grouped-query block's keys), Manifest AI's description of power
retention ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239, and the Brumby-14B-Base release note, 2025-10) and the
shape of the public `modeling_brumby` code. With
`N(x; w) = x · rsqrt(mean(x²) + rms_norm_eps) · w` (RMSNorm over the last
axis), per position t, K/V head j (Hkv), query head h (H; G = H / Hkv to a
K/V head), Dh the head size:

- Block i: `x ← x + W_o [y_h]`, the retention below on `u = N(x;
  operator_norm_i)`; then `x ← x + W₂(silu(W₁ n) ⊙ W₃ n)`, `n = N(x;
  ffn_norm_i)`. After the last block `N(x; final_norm)` and the untied head.
- `q_h = rot(N((W_q u)_h; q_norm))`, `k_j = rot(N((W_k u)_j; k_norm))` per
  head over its Dh, `v_j = (W_v u)_j`; no bias; rotary positions on q and k
  (theta `rope_theta`, the rotate-half convention).
- `log g_j = log sigmoid((W_g u)_j)`, W_g: D → Hkv, no bias: one decay a
  K/V head and position. `G_t = Σ_{r≤t} log g_r`.
- `a_ts = (q_t · k_s / √Dh)² · exp(G_t − G_s)` for s ≤ t, nought after:
  never negative; no softmax, no max-subtraction.
  `y_t = Σ_s a_ts v_s / Σ_s a_ts`, query head h on K/V head h // G.

What the config does not give is listed under the configuration's
`assumed`: the degree (2) and the normalised form, the gate as a bias-free
projection to the K/V heads through a log-sigmoid, the scale 1/√Dh inside
the power, the per-head norms. The weight LAYOUT is the system's own (the
parameters compared are the engine's, as they lie on the device): one array
a layer and a name, `"<layer>.<name>"`, projections stored [in, out].

No kernel, no cache, no state, no batching tricks, no import of the system
under test: the recurrent form the system decodes with is nowhere here. The
layers are a Python loop over jitted functions; the bfloat16 weights are
widened to float32 a layer at a time.

`precision` (default None = float32 at "highest") is for the CONTROL of the
benchmark's comparison, never for a measured run: the same equations with
every product's operands rounded. "bfloat16" is what the program itself
does: bfloat16 operands in the projections, the MLP and the head, float32
in the gate and the retention. "float8" is one step below what the
configuration states, everywhere: e4m3 operands (a power-of-two scale a
tensor) where it states bfloat16, bfloat16 operands where it states float32
(the gate's projection, the scores, the weighted sum of values).
Accumulation is float32 throughout.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # float8_e4m3fn's largest


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _operand(x, precision: Optional[str]):
    """An operand of a product the configuration states in bfloat16."""
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16)
    if precision == "float8":
        amax = jnp.max(jnp.abs(x))
        scale = jnp.exp2(jnp.floor(jnp.log2(
            F8_MAX / jnp.maximum(amax, 1e-30))))
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return (q / scale).astype(jnp.bfloat16)
    return x


def _mm(spec: str, a, b, precision: Optional[str]):
    """einsum of a product stated in bfloat16: at "highest", or with the
    operands rounded to the control's."""
    return jnp.einsum(spec, _operand(_f32(a), precision),
                      _operand(_f32(b), precision),
                      precision=jax.lax.Precision.HIGHEST if precision is None
                      else jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


def _mm32(spec: str, a, b, precision: Optional[str]):
    """einsum of a product stated in float32: at "highest", but for the
    control one step below ("float8"), which rounds its operands to
    bfloat16."""
    if precision == "float8":
        return jnp.einsum(spec, _f32(a).astype(jnp.bfloat16),
                          _f32(b).astype(jnp.bfloat16),
                          precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, _f32(a), _f32(b),
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def rotary(x, theta: float):
    """x [B, S, heads, Dh] at positions 0..S-1, rotate-half convention."""
    S, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]   # [S, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def power_retention(q, k, v, log_g, precision=None):
    """y [B, S, H, Dh] of q [B, S, H, Dh] over k, v [B, S, Hkv, Dh] under
    log_g [B, S, Hkv]: weights (q_t·k_s/√Dh)² exp(G_t − G_s), s ≤ t,
    normalised by their sum; query head h on K/V head h // (H / Hkv)."""
    B, S, H, Dh = q.shape
    n_kv = k.shape[2]
    G = jnp.cumsum(log_g, axis=1)                               # [B, S, Hkv]
    s = _mm32("btjgd,bsjd->bjgts", q.reshape(B, S, n_kv, H // n_kv, Dh), k,
              precision) / jnp.sqrt(float(Dh))
    Gt = jnp.swapaxes(G, 1, 2)                                  # [B, Hkv, S]
    causal = jnp.tril(jnp.ones((S, S), bool))
    decay = jnp.exp(jnp.where(causal, Gt[..., :, None] - Gt[..., None, :],
                              -jnp.inf))                        # [B, Hkv, t, s]
    a = s * s * decay[:, :, None]
    num = _mm32("bjgts,bsjd->btjgd", a, v, precision)
    den = jnp.moveaxis(a.sum(-1), -1, 1)                        # [B, t, j, g]
    return (num / den[..., None]).reshape(B, S, H, Dh)


def retention_operator(u, q_w, k_w, v_w, g_w, o_w, q_norm, k_norm, *,
                       n_heads, n_kv, head_dim, eps, theta, precision=None):
    Bz, S, _ = u.shape
    q = _mm("bsd,de->bse", u, q_w, precision).reshape(Bz, S, n_heads,
                                                      head_dim)
    k = _mm("bsd,de->bse", u, k_w, precision).reshape(Bz, S, n_kv, head_dim)
    v = _mm("bsd,de->bse", u, v_w, precision).reshape(Bz, S, n_kv, head_dim)
    q = rotary(rms_norm(q, q_norm, eps), theta)
    k = rotary(rms_norm(k, k_norm, eps), theta)
    log_g = jax.nn.log_sigmoid(_mm32("bsd,dj->bsj", u, g_w, precision))
    y = power_retention(q, k, v, log_g, precision)
    return _mm("bsd,de->bse", y.reshape(Bz, S, n_heads * head_dim), o_w,
               precision)


def swiglu(x, w1, w3, w2, precision=None):
    a = _mm("bsd,df->bsf", x, w1, precision)
    b = _mm("bsd,df->bsf", x, w3, precision)
    return _mm("bsf,fd->bsd", jax.nn.silu(a) * b, w2, precision)


# ---------------------------------------------------------- jitted layers --
@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "head_dim", "eps", "theta", "precision"))
def _retention_layer(h, norm_w, q_w, k_w, v_w, g_w, o_w, q_norm, k_norm,
                     n_heads, n_kv, head_dim, eps, theta, precision=None):
    return h + retention_operator(
        rms_norm(h, norm_w, eps), q_w, k_w, v_w, g_w, o_w, q_norm, k_norm,
        n_heads=n_heads, n_kv=n_kv, head_dim=head_dim, eps=eps, theta=theta,
        precision=precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _ffn_layer(h, norm_w, w1, w3, w2, eps, precision=None):
    return h + swiglu(rms_norm(h, norm_w, eps), w1, w3, w2, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(h, norm_w, head, eps, precision=None):
    return _mm("bsd,dv->bsv", rms_norm(h, norm_w, eps), head, precision)


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


def sizes(config: dict) -> dict:
    """What the forward pass reads of a configuration's `architecture`."""
    a = config["architecture"]
    return {"layers": int(a["num_hidden_layers"]),
            "n_heads": int(a["num_attention_heads"]),
            "n_kv": int(a["num_key_value_heads"]),
            "head_dim": int(a["head_dim"]),
            "eps": float(a["rms_norm_eps"]),
            "theta": float(a["rope_theta"])}


def serve_logits(params: Dict[str, jax.Array], ids, config: dict,
                 precision: Optional[str] = None):
    """Logits [B, S, V] of the whole sequences ids [B, S]: the full causal
    forward pass in the attention form, no cache, no state."""
    z = sizes(config)
    eps = z["eps"]
    h = _embed(params["embed"], jnp.asarray(ids, jnp.int32))
    for i in range(z["layers"]):
        def p(name):
            return params[f"{i}.{name}"]

        h = _retention_layer(
            h, p("operator_norm"), p("q_w"), p("k_w"), p("v_w"), p("g_w"),
            p("o_w"), p("q_norm"), p("k_norm"), n_heads=z["n_heads"],
            n_kv=z["n_kv"], head_dim=z["head_dim"], eps=eps,
            theta=z["theta"], precision=precision)
        h = _ffn_layer(h, p("ffn_norm"), p("w1"), p("w3"), p("w2"), eps=eps,
                       precision=precision)
    return _logits(h, params["final_norm"], params["head"], eps=eps,
                   precision=precision)
