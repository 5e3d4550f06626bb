"""The plain reference: Ling-3.0-flash's forward pass (`bailing_hybrid`),
in jax.numpy and float32 at "highest" matmul precision.

Written from the model's public `config.json`
(https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json),
Kimi delta attention as Kimi Linear states it (arXiv:2510.26692), latent
attention as DeepSeek-V2 does (arXiv:2405.04434) and DeepSeek-V3's
group-limited routing (`noaux_tc`, arXiv:2412.19437). With
`N(x; w) = x · rsqrt(mean(x²) + rms_norm_eps) · w` over the last axis and
`u = N(x; attn_norm)`:

- A KDA layer (`layer_types` "kda"), H heads of d_k = d_v, Conv4 a
  depthwise causal convolution of width `short_conv_kernel_size` over
  time: `q = L2(SiLU(Conv4(u W_q))_h) / √d_k`, `k = L2(SiLU(Conv4(u
  W_k))_h)`, `v = SiLU(Conv4(u W_v))_h`; a decay a channel `g = lb ·
  σ(exp(A_log_h) (u W_f)_h + dt_bias_h)` (lb = `kda_lower_bound`),
  `β = σ(u W_β)_h`; the state `S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t})
  S_{t−1} + β_t k_t v_tᵀ` from S_0 = 0, `o_t = S_tᵀ q_t` — HERE THE
  RECURRENT FORM, one position at a time (`lax.scan`), the definition,
  where the system runs a chunked form in a prefill and a kernel in
  decode; then `x ← x + W_o [N(o_h; o_norm_h) · σ(u W_g)_h]_h`.
- An MLA layer (no low-rank query): `[q_n,h ‖ q_r,h] = (u W_q)_h`,
  `[c̃ ‖ k_r] = u W_kva`, `c = N(c̃; kv_a_norm)`, `[k_n,h ‖ v_h] = (c
  W_kvb)_h`; q_r,h and k_r (one for all heads) rotated by pairs (2i, 2i+1)
  at θ_i = rope_theta^(−2i/d_r) — here as complex numbers; scores
  `(q_n,h · k_n,h + q_r,h · k_r) / √(d_n + d_r)`, causal softmax, then
  `x ← x + W_o [Σ p_h v_h · σ(u W_g)_h]_h`. Only the expanded form.
- Then on `n = N(x; ffn_norm)` the dense SwiGLU (the first
  `num_dense_layers`), or `x ← x + S(n) + Σ w_i E_i(n)`: scores
  `s = σ(n W_r)` (float32) over every routed expert; the selection by
  `s + expert_bias` within the `topk_group` groups (of `n_group`,
  contiguous) with the largest sum of their two best, the top k of those;
  weights `s[sel]` normalised over the chosen, times routed_scaling_factor;
  S the shared expert, each a SwiGLU `W₂(silu(W₁ n) ⊙ W₃ n)`. The layer
  holds `experts_held` (first, count); what another holder's experts
  would add is left out here as in the program.
- After the last layer `N(x; final_norm)` and the untied head.

What the config does not give is listed under the configuration's
`assumed`. The weight LAYOUT is the system's own (the parameters compared
are the engine's, as they lie on the device): one array a layer and a
name, `"<layer>.<name>"`, projections stored [in, out], a layer's held
experts stacked.

No kernel, no cache, no chunks, no import of the system under test. The
layers are a Python loop over jitted functions; the attention runs
QUERY_BLOCK queries at a time against every key, the experts one at a time
over every token; the bfloat16 weights are widened to float32 inside.

`precision` (default None = float32 at "highest") is for the CONTROL of the
benchmark's comparison, never for a measured run: "bfloat16" rounds the
operands of every product the configuration states in bfloat16 (the
projections, the attention's two products, the experts, the head), as the
program does; "float8" is one step below, everywhere: e4m3 operands (a
power-of-two scale a tensor) where bfloat16 is stated, bfloat16 where
float32 is (the router's logits; the convolutions; the delta rule's state,
rounded to bfloat16 at every position). Any other name changes nothing.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F8_MAX = 448.0          # float8_e4m3fn's largest
QUERY_BLOCK = 128       # queries a pass of the attention
L2_EPS = 1e-6
_LOWER = ("bfloat16", "float8")


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _bf16(x):
    return _f32(x).astype(jnp.bfloat16).astype(jnp.float32)


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
                      precision=jax.lax.Precision.DEFAULT
                      if precision in _LOWER else jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _stated32(x, precision: Optional[str]):
    """A value the configuration states in float32: under "float8", one
    step below, rounded to bfloat16."""
    return _bf16(x) if precision == "float8" else _f32(x)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def causal_conv(x, w, precision=None):
    """Depthwise causal convolution over time: x [B, S, C], w [C, K];
    out_t = Σ_j w[:, j] x_{t − K + 1 + j}, inputs before 0 nought."""
    K, S = w.shape[1], x.shape[1]
    x, w = _stated32(x, precision), _stated32(w, precision)
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(K):
        out = out + w[:, j] * xp[:, j:j + S]
    return out


def delta_rule(q, k, v, g, beta, precision=None):
    """The recurrence, position by position: q, k, g [B, S, H, d_k], v
    [B, S, H, d_v], beta [B, S, H] -> o [B, S, H, d_v]. S_0 = 0;
    `S_t = (I − β k kᵀ) Diag(e^g) S_{t−1} + β k vᵀ`, `o_t = S_tᵀ q_t`."""
    B, _, H, dk = q.shape
    dv = v.shape[-1]
    hi = jax.lax.Precision.HIGHEST

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        decayed = jnp.exp(g_t)[..., None] * S                  # Diag(α) S
        ktS = jnp.einsum("bhc,bhcv->bhv", k_t, decayed, precision=hi)
        S = decayed - b_t[..., None, None] * k_t[..., None] * ktS[:, :, None] \
            + b_t[..., None, None] * k_t[..., None] * v_t[:, :, None]
        S = _stated32(S, precision)
        return S, jnp.einsum("bhcv,bhc->bhv", S, q_t, precision=hi)

    xs = tuple(jnp.moveaxis(_f32(x), 1, 0) for x in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1)


def head_gate(u, w, precision):
    """σ(u W_g) [B, S, H, 1]: one output gate a head."""
    return jax.nn.sigmoid(_mm("bsd,dh->bsh", u, w["g_w"], precision))[
        ..., None]


def kda_attention(u, w, z, precision=None):
    """The KDA sublayer's output (before the residual add) of the normed
    stream u [B, S, D]."""
    B, S, _ = u.shape
    H, d = z["heads"], z["head_dim"]

    def proj(name):
        x = _mm("bsd,de->bse", u, w[f"{name}_w"], precision)
        return jax.nn.silu(causal_conv(x, w[f"{name}_conv"], precision)
                           ).reshape(B, S, H, d)

    q = l2_norm(proj("q")) / math.sqrt(d)
    k, v = l2_norm(proj("k")), proj("v")
    f = _mm("bsd,de->bse", u, w["f_w"], precision).reshape(B, S, H, d)
    a = jnp.exp(_f32(w["A_log"]))[:, None]
    g = z["lower_bound"] * jax.nn.sigmoid(
        a * f + _f32(w["dt_bias"]).reshape(H, d))
    beta = jax.nn.sigmoid(_mm("bsd,dh->bsh", u, w["b_w"], precision))
    o = delta_rule(q, k, v, g, beta, precision)
    o = rms_norm(o, _f32(w["o_norm"]).reshape(H, d), z["eps"]) \
        * head_gate(u, w, precision)
    return _mm("bse,ed->bsd", o.reshape(B, S, -1), w["o_w"], precision)


def rotate(x, theta):
    """x [B, S, ..., d_r] at positions 0..S-1: pair (2i, 2i+1) as the
    complex number x_2i + i x_2i+1, times e^{i θ_i t}; the pairs stay
    interleaved."""
    d, S = x.shape[-1], x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + ang.shape[-1:])
    zx = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(1j * ang)
    return jnp.stack([jnp.real(zx), jnp.imag(zx)], -1).reshape(x.shape)


def latent_attention(u, w, z, precision=None):
    """The MLA sublayer's output (before the residual add) of u [B, S, D]."""
    B, S, _ = u.shape
    H, dn, dr = z["heads"], z["dn"], z["dr"]
    q = _mm("bsd,de->bse", u, w["q_w"], precision).reshape(B, S, H, dn + dr)
    q_n, q_r = q[..., :dn], rotate(q[..., dn:], z["theta"])
    ckv = _mm("bsd,dr->bsr", u, w["kv_a"], precision)
    c = rms_norm(ckv[..., :z["kv_lora"]], w["kv_a_norm"], z["eps"])
    k_r = rotate(ckv[..., z["kv_lora"]:], z["theta"])              # [B, S, dr]
    kv = _mm("bsc,ce->bse", c, w["kv_b"], precision).reshape(B, S, H, -1)
    k_n, v = kv[..., :dn], kv[..., dn:]
    sigma = 1.0 / math.sqrt(dn + dr)
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

    # queries past S (a last block cut short) are computed and dropped
    pad = -S % qb
    q_n = jnp.pad(q_n, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q_r = jnp.pad(q_r, ((0, 0), (0, pad), (0, 0), (0, 0)))
    o = jax.lax.map(block, jnp.arange(0, S + pad, qb))   # [n, B, qb, H, dv]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S + pad, H, -1)[:, :S] \
        * head_gate(u, w, precision)
    return _mm("bse,ed->bsd", o.reshape(B, S, -1), w["o_w"], precision)


def swiglu(x, w1, w3, w2, precision=None):
    a = _mm("td,df->tf", x, w1, precision)
    b = _mm("td,df->tf", x, w3, precision)
    return _mm("tf,fd->td", jax.nn.silu(a) * b, w2, precision)


def route(n, gate_w, bias, z, precision=None):
    """-> (chosen [T, k], weights [T, k]): sigmoid scores, the selection by
    score + bias within the best groups, weights of the scores alone."""
    logits = jnp.einsum("td,de->te", _stated32(n, precision),
                        _stated32(gate_w, precision),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    choice = s + _f32(bias)
    T, E = choice.shape
    per = E // z["n_group"]
    grouped = choice.reshape(T, z["n_group"], per)
    # a group's score: its two largest, by a sort
    two = -jnp.sort(-grouped, axis=-1)[..., :2].sum(-1)          # [T, G]
    best_groups = jnp.argsort(-two, axis=-1)[:, :z["topk_group"]]
    in_best = (jnp.arange(z["n_group"])[None, None, :]
               == best_groups[:, :, None]).any(1)                # [T, G]
    masked = jnp.where(jnp.repeat(in_best, per, axis=1), choice, -jnp.inf)
    chosen = jnp.argsort(-masked, axis=-1)[:, :z["top_k"]]
    weight = jnp.take_along_axis(s, chosen, -1)
    if z["norm_topk_prob"]:
        weight = weight / weight.sum(-1, keepdims=True)
    return chosen, weight * z["routed_scaling"]


def expert_layer(n, w, z, precision=None):
    """S(n) + Σ w_i E_i(n) over the HELD routed experts, n [T, D]."""
    chosen, weight = route(n, w["gate_w"], w["expert_bias"], z, precision)
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
def _kda_step(h, w, z, precision=None):
    return h + kda_attention(rms_norm(h, w["attn_norm"], z["eps"]), w, z,
                             precision)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _mla_step(h, w, z, precision=None):
    return h + latent_attention(rms_norm(h, w["attn_norm"], z["eps"]), w, z,
                                precision)


@functools.partial(jax.jit, static_argnames=_STATIC + ("dense",))
def _ffn_step(h, w, z, dense, precision=None):
    B, S, D = h.shape
    n = rms_norm(h, w["ffn_norm"], z["eps"]).reshape(B * S, D)
    out = swiglu(n, w["w1"], w["w3"], w["w2"], precision) if dense \
        else expert_layer(n, w, z, precision)
    return h + out.reshape(B, S, D)


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
    return _Sizes(
        layer_types=tuple(a["layer_types"]),
        dense=int(a["num_dense_layers"]),
        heads=int(a["num_attention_heads"]), head_dim=int(a["head_dim"]),
        lower_bound=float(a["kda_lower_bound"]),
        kv_lora=int(a["kv_lora_rank"]), dn=int(a["qk_nope_head_dim"]),
        dr=int(a["qk_rope_head_dim"]), theta=float(a["rope_theta"]),
        top_k=int(a["num_experts_per_tok"]), n_group=int(a["n_group"]),
        topk_group=int(a["topk_group"]),
        held=tuple(int(x) for x in a["experts_held"]),
        norm_topk_prob=bool(a["norm_topk_prob"]),
        routed_scaling=float(a["routed_scaling_factor"]),
        eps=float(a["rms_norm_eps"]))


_KDA = ("attn_norm", "q_w", "k_w", "v_w", "q_conv", "k_conv", "v_conv",
        "f_w", "b_w", "A_log", "dt_bias", "o_norm", "g_w", "o_w")
_MLA = ("attn_norm", "q_w", "kv_a", "kv_a_norm", "kv_b", "g_w", "o_w")
_DENSE = ("ffn_norm", "w1", "w3", "w2")
_EXPERTS = _DENSE + ("gate_w", "expert_bias", "e_w1", "e_w3", "e_w2")


def serve_logits(params: Dict[str, jax.Array], ids, config: dict,
                 precision: Optional[str] = None):
    """Logits [B, S, V] of the whole sequences ids [B, S]: the full causal
    forward pass, the delta rule in its recurrent form, no cache."""
    z = sizes(config)
    h = _embed(params["embed"], jnp.asarray(ids, jnp.int32))
    for i, kind in enumerate(z["layer_types"]):
        names, step = (_KDA, _kda_step) if kind == "kda" \
            else (_MLA, _mla_step)
        h = step(h, {k: params[f"{i}.{k}"] for k in names}, z=z,
                 precision=precision)
        dense = i < z["dense"]
        h = _ffn_step(h, {k: params[f"{i}.{k}"]
                          for k in (_DENSE if dense else _EXPERTS)},
                      z=z, dense=dense, precision=precision)
    return _logits(h, params["final_norm"], params["head"], eps=z["eps"],
                   precision=precision)
