"""The plain reference: Mistral-Small-4-119B-2603's text forward pass
(`mistral4`), in jax.numpy and float32 at "highest" matmul precision.

Written from the model's public `config.json`
(https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/main/config.json)
and the latent attention and routed experts it names (DeepSeek-V2,
arXiv:2405.04434; YaRN, arXiv:2309.00071). With
`N(x; w) = x · rsqrt(mean(x²) + rms_norm_eps) · w` over the last axis, per
position t, head h (H), `d_n` = qk_nope_head_dim, `d_r` =
qk_rope_head_dim, `d_v` = v_head_dim:

- Attention on `u = N(x; attn_norm)`: `c_q = N(u W_qa; q_a_norm)`,
  `[q_n,h ‖ q_r,h] = (c_q W_qb)_h`; `[c̃ ‖ k_r] = u W_kva`, `c = N(c̃;
  kv_a_norm)`; `[k_n,h ‖ v_h] = (c W_kvb)_h`; q_r,h and k_r (one for all
  heads) rotated. `s_h(t, τ) = σ (q_n,h · k_n,h(τ) + q_r,h · k_r(τ))` for
  τ ≤ t, softmax over τ, `x ← x + W_o [Σ_τ p_h v_h(τ)]_h`. Only the expanded
  form: no latent cache, no absorption.
- Rotation: YaRN on the d_r dims, pairs (2i, 2i+1) rotating together
  (`rope_interleave`) — here as complex numbers times e^{iθ_i t} — with
  the inverse frequencies blended between interpolation (÷ factor) and
  extrapolation over a ramp between the correction dims of β_fast and
  β_slow; cos and sin unscaled (mscale = mscale_all_dim).
  `σ = m² / √(d_n + d_r)`, m = 1 + 0.1 · mscale_all_dim · ln(factor). The
  query is multiplied by `1 + llama_4_scaling_beta · ln(1 + ⌊t /
  original_max_position_embeddings⌋)`.
- Experts on `n = N(x; ffn_norm)`: `π = softmax(n W_r)` over every routed
  expert (float32), the top k of π chosen, their weights π normalised over
  the chosen, times routed_scaling_factor; `x ← x + S(n) + Σ w_i E_i(n)`,
  each SwiGLU `W₂(silu(W₁ n) ⊙ W₃ n)`. The layer holds `experts_held`
  (first, count) of the routed experts; what another holder's experts
  would add is left out here as in the program.
- After the last layer `N(x; final_norm)` and the untied head.

What the config does not give is listed under the configuration's
`assumed`: the scoring function (softmax, greedy top-k), the attention
factor, the llama-4 factor, the initialisers. The weight LAYOUT is the
system's own (the parameters compared are the engine's, as they lie on the
device): one array a layer and a name, `"<layer>.<name>"`, projections
stored [in, out], a layer's held experts stacked.

No kernel, no cache, no batching tricks, no import of the system under
test. The layers are a Python loop over jitted functions; the attention
runs QUERY_BLOCK queries at a time against every key, the experts one at a
time over every token; the bfloat16 weights are widened to float32 inside.

`precision` (default None = float32 at "highest") is for the CONTROL of the
benchmark's comparison, never for a measured run: "bfloat16" rounds the
operands of every product the configuration states in bfloat16 (the
projections, the attention's two products, the experts, the head), as the
program does; "float8" is one step below, everywhere: e4m3 operands (a
power-of-two scale a tensor) where bfloat16 is stated, bfloat16 operands
where float32 is (the router). Accumulation is float32 throughout.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # float8_e4m3fn's largest
QUERY_BLOCK = 128       # queries a pass of the attention


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
    """einsum of a product stated in bfloat16."""
    return jnp.einsum(spec, _operand(_f32(a), precision),
                      _operand(_f32(b), precision),
                      precision=jax.lax.Precision.HIGHEST if precision is None
                      else jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


def _mm32(spec: str, a, b, precision: Optional[str]):
    """einsum of a product stated in float32 (the router): at "highest",
    but for the control one step below ("float8"): bfloat16 operands."""
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


def yarn_frequencies(d: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """[d / 2] inverse frequencies of the rotary dims under YaRN."""
    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), d - 1)
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    # ramp 0: extrapolation (the frequency as it is), 1: interpolation
    return freq * (1.0 - ramp) + freq / factor * ramp


def attention_factor(z: dict) -> float:
    m = 1.0 + 0.1 * z["mscale_all_dim"] * math.log(z["factor"]) \
        if z["factor"] > 1 else 1.0
    return m * m / math.sqrt(z["dn"] + z["dr"])


def rotate(x, inv):
    """x [B, S, ..., d_r] at positions 0..S-1: pair (2i, 2i+1) as the
    complex number x_2i + i x_2i+1, times e^{i θ_i t}; the pairs stay
    interleaved."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]   # [S, d/2]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + ang.shape[-1:])
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * ang)
    return jnp.stack([jnp.real(z), jnp.imag(z)], -1).reshape(x.shape)


def latent_attention(u, w, z, precision=None):
    """The attention sublayer's output (before the residual add) of the
    normed stream u [B, S, D]; w the layer's attention weights."""
    B, S, _ = u.shape
    H, dn, dr = z["heads"], z["dn"], z["dr"]
    inv = yarn_frequencies(dr, z["theta"], z["factor"], z["original"],
                           z["beta_fast"], z["beta_slow"])
    t = jnp.arange(S, dtype=jnp.float32)
    q_scale = 1.0 + z["llama4_beta"] * jnp.log(
        1.0 + jnp.floor(t / z["original"]))                        # [S]
    cq = rms_norm(_mm("bsd,dr->bsr", u, w["q_a"], precision), w["q_a_norm"],
                  z["eps"])
    q = _mm("bsr,re->bse", cq, w["q_b"], precision).reshape(B, S, H, dn + dr)
    q = q * q_scale[None, :, None, None]
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], inv)
    ckv = _mm("bsd,dr->bsr", u, w["kv_a"], precision)
    c = rms_norm(ckv[..., :z["kv_lora"]], w["kv_a_norm"], z["eps"])
    k_r = rotate(ckv[..., z["kv_lora"]:], inv)                     # [B, S, dr]
    kv = _mm("bsc,ce->bse", c, w["kv_b"], precision).reshape(B, S, H, -1)
    k_n, v = kv[..., :dn], kv[..., dn:]
    sigma = attention_factor(z)
    qb = min(QUERY_BLOCK, S)
    kpos = jnp.arange(S)

    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, qb, 1)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, qb, 1)
        s = sigma * (_mm("bqhn,bkhn->bhqk", qn, k_n, precision)
                     + _mm("bqhr,bkr->bhqk", qr, k_r, precision))
        qpos = start + jnp.arange(qb)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        return _mm("bhqk,bkhv->bqhv", jax.nn.softmax(s, -1), v, precision)

    o = jax.lax.map(block, jnp.arange(0, S, qb))         # [S/qb, B, qb, H, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, -1)
    return _mm("bse,ed->bsd", o, w["o_w"], precision)


def swiglu(x, w1, w3, w2, precision=None):
    a = _mm("td,df->tf", x, w1, precision)
    b = _mm("td,df->tf", x, w3, precision)
    return _mm("tf,fd->td", jax.nn.silu(a) * b, w2, precision)


def expert_layer(n, w, z, precision=None):
    """S(n) + Σ w_i E_i(n) over the HELD routed experts, n [T, D]."""
    logits = _mm32("td,de->te", n, w["gate_w"], precision)
    pi = jax.nn.softmax(logits, -1)
    k = z["top_k"]
    # the k largest, by a sort: the first k of each row's descending order
    chosen = jnp.argsort(-pi, axis=-1)[:, :k]
    weight = jnp.take_along_axis(pi, chosen, -1)
    if z["norm_topk_prob"]:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * z["routed_scaling"]
    first, count = z["held"]
    out = swiglu(n, w["w1"], w["w3"], w["w2"], precision)

    def one(acc, e):
        on = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), -1)  # [T]
        y = swiglu(n, w["e_w1"][e], w["e_w3"][e], w["e_w2"][e], precision)
        return acc + on[:, None] * y, None

    out, _ = jax.lax.scan(one, out, jnp.arange(count))
    return out


# ---------------------------------------------------------- jitted layers --
_STATIC = ("z", "precision")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _attention_step(h, w, z, precision=None):
    return h + latent_attention(rms_norm(h, w["attn_norm"], z["eps"]), w,
                                z, precision)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _expert_step(h, w, z, precision=None):
    B, S, D = h.shape
    n = rms_norm(h, w["ffn_norm"], z["eps"]).reshape(B * S, D)
    return h + expert_layer(n, w, z, precision).reshape(B, S, D)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(h, norm_w, head, eps, precision=None):
    return _mm("bsd,dv->bsv", rms_norm(h, norm_w, eps), head, precision)


@jax.jit
def _embed(embed, ids):
    return _f32(embed[ids])


class _Sizes(dict):
    """The sizes as a hashable static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def sizes(config: dict) -> dict:
    """What the forward pass reads of a configuration's `architecture`."""
    a = config["architecture"]
    rope = a["rope_parameters"]
    return _Sizes(
        layers=int(a["num_hidden_layers"]),
        heads=int(a["num_attention_heads"]),
        kv_lora=int(a["kv_lora_rank"]), dn=int(a["qk_nope_head_dim"]),
        dr=int(a["qk_rope_head_dim"]), top_k=int(a["num_experts_per_tok"]),
        held=tuple(int(x) for x in a["experts_held"]),
        norm_topk_prob=bool(a["norm_topk_prob"]),
        routed_scaling=float(a["routed_scaling_factor"]),
        eps=float(a["rms_norm_eps"]), theta=float(rope["rope_theta"]),
        factor=float(rope["factor"]),
        original=int(rope["original_max_position_embeddings"]),
        beta_fast=float(rope["beta_fast"]), beta_slow=float(rope["beta_slow"]),
        mscale_all_dim=float(rope["mscale_all_dim"]),
        llama4_beta=float(rope["llama_4_scaling_beta"]))


_ATTENTION = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
              "kv_b", "o_w")
_EXPERTS = ("ffn_norm", "gate_w", "e_w1", "e_w3", "e_w2", "w1", "w3", "w2")


def serve_logits(params: Dict[str, jax.Array], ids, config: dict,
                 precision: Optional[str] = None):
    """Logits [B, S, V] of the whole sequences ids [B, S]: the full causal
    forward pass in the expanded form, no cache."""
    z = sizes(config)
    h = _embed(params["embed"], jnp.asarray(ids, jnp.int32))
    for i in range(z["layers"]):
        h = _attention_step(h, {k: params[f"{i}.{k}"] for k in _ATTENTION},
                            z=z, precision=precision)
        h = _expert_step(h, {k: params[f"{i}.{k}"] for k in _EXPERTS}, z=z,
                         precision=precision)
    return _logits(h, params["final_norm"], params["head"], eps=z["eps"],
                   precision=precision)
