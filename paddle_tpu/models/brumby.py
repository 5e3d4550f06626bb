"""Brumby (`brumby`): the dense block of a grouped-query transformer whose
attention is POWER RETENTION — the layer Manifest AI publishes as
Brumby-14B-Base (https://huggingface.co/manifestai/Brumby-14B-Base;
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239). Every
layer is a retention layer: a decoding row's cache is no K/V row at all but
a fixed-size float32 state a layer and K/V head, whatever its context.

This module is what the serving engine runs for such a model, in pure JAX:
the configuration and its preset table, the parameters made on the device
from `paddle.seed`'s state, the retention's parts (each taking `log_g` as
an argument, so that a test can set a slow decay), and `ServingPasses`,
the step the model supplies to `GenerativeEngine`.
`benchmarks/references/brumby.py` states the equations; the names of the
parameters are its names. The block's other parts (`rms_norm`, `rotary`,
`attn_qkv` with its per-head norm, `dense_ffn`, `mm`, the seeded draw) are
models/lfm2.py's own.

With `u = N(x; operator_norm)`, per position t, K/V head j, query head h
(G = H / Hkv to a K/V head), Dh the head size:

    q_h = rot(N(W_q u)_h),  k_j = rot(N(W_k u)_j),  v_j = (W_v u)_j
    log g_j = log sigmoid((W_g u)_j)            one decay a K/V head
    a_ts = (q_t . k_s / sqrt(Dh))^2 exp(G_t - G_s),  G_t = sum_{r<=t} log g_r
    y_t = sum_{s<=t} a_ts v_s / sum_{s<=t} a_ts

— attention whose weight is the second power of the scaled score under a
decay: never negative, no softmax, no max-subtraction. It has an exact
recurrent form over the state `S_t = g_t S_{t-1} + phi(k_t) v_t^T`,
`z_t = g_t z_{t-1} + phi(k_t)`, `y_t = phi(q_t)^T S_t / phi(q_t)^T z_t`,
`phi` the second symmetric power (ops/pallas/retention_step.py has the
state's layout and the kernel). A prefill runs the attention form over the
prompt and stores the state after its last REAL position; a decode step
runs the recurrent form in place, by slot.

Precision, as the configuration states it: weights in `dtype` (bfloat16),
the gate's projection in float32; every projection, the MLP and the head
take bfloat16 operands and accumulate in float32; in float32, and its
products at full precision: the residual stream, every norm's statistics,
the rotation, the gate, and ALL of the retention — scores, decay, `phi`,
the state, the normaliser and both contractions (`phi(q)^T z` is a sum of
thousands of signed terms that cancels to a small number).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..ops.pallas import retention_step as _rs
from .lfm2 import (INIT_STD, NORM_STD, _draw_jit, attn_qkv, dense_ffn, mm,
                   rms_norm)

_HI = jax.lax.Precision.HIGHEST


class BrumbyConfig:
    def __init__(self, vocab_size=151936, hidden_size=5120,
                 intermediate_size=17408, num_hidden_layers=8,
                 num_attention_heads=40, num_key_value_heads=8,
                 head_dim=128, norm_eps=1e-6, rope_theta=1e6,
                 max_seq_len=1024, dtype="bfloat16"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.norm_eps = norm_eps
        self.rope_theta = rope_theta
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        # the degree of the retention: the state is the second symmetric
        # power of a key, and nothing here computes another
        self.power = 2
        if num_attention_heads % num_key_value_heads or head_dim % 8:
            raise ValueError(
                f"{num_attention_heads} query heads over "
                f"{num_key_value_heads} K/V heads of {head_dim}: the query "
                f"heads are a multiple of the K/V heads, a head of eights")

    def serving_passes(self) -> "ServingPasses":
        """The step this model supplies to the serving engine."""
        return ServingPasses(self)


PRESETS = {
    # one stage of a five-chip pipeline of the published model, 8 of its 40
    # layers (every layer is alike): every width, head and the vocabulary
    # uncut (benchmarks/configs/brumby-14b-base.json)
    "brumby-14b-base": BrumbyConfig(),
    # the tests': the same block at toy widths
    "brumby-tiny": BrumbyConfig(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_seq_len=128),
}


# ----------------------------------------------------------- parameters --
def param_shapes(cfg: BrumbyConfig) -> dict:
    """name -> (shape, kind): every array of the model, one a layer and a
    name ("<layer>.<name>"); kind "matrix" | "norm" | "gate"."""
    D, Dh, F = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    out = {"embed": ((cfg.vocab_size, D), "matrix")}
    for i in range(cfg.num_hidden_layers):
        for name, shape, kind in (
                ("operator_norm", (D,), "norm"), ("ffn_norm", (D,), "norm"),
                ("q_w", (D, H * Dh), "matrix"),
                ("k_w", (D, Hkv * Dh), "matrix"),
                ("v_w", (D, Hkv * Dh), "matrix"),
                ("g_w", (D, Hkv), "gate"), ("o_w", (H * Dh, D), "matrix"),
                ("q_norm", (Dh,), "norm"), ("k_norm", (Dh,), "norm"),
                ("w1", (D, F), "matrix"), ("w3", (D, F), "matrix"),
                ("w2", (F, D), "matrix")):
            out[f"{i}.{name}"] = (shape, kind)
    out["final_norm"] = ((D,), "norm")
    out["head"] = ((D, cfg.vocab_size), "matrix")       # untied
    return out


def n_params(cfg: BrumbyConfig) -> int:
    return sum(math.prod(shape) for shape, _ in param_shapes(cfg).values())


def init_params(cfg: BrumbyConfig, device=None) -> dict:
    """The model's parameters, seeded: each array drawn on `device` (default
    jax's first) with its own key from `paddle.seed`'s state — one array at
    a time, so neither a host nor a float32 copy of the model ever exists.
    Matrices N(0, INIT_STD²) and norm weights 1 + N(0, NORM_STD²) in
    `cfg.dtype`, as models/lfm2.py draws them; the gate's projection
    N(0, INIT_STD²) in float32. A seeded gate has no sign: `W_g u` is
    N(0, ~2), so g = sigmoid(.) lies in 0.1 .. 0.9 and a state remembers a
    few tokens — the tests that need a long memory set `log_g` themselves."""
    from ..core import rng as _rng

    device = device or jax.devices()[0]
    dtype = jnp.dtype(cfg.dtype)
    draw = {"matrix": (0.0, INIT_STD, dtype), "norm": (1.0, NORM_STD, dtype),
            "gate": (0.0, INIT_STD, jnp.float32)}
    out = {}
    with jax.default_device(device):
        for name, (shape, kind) in param_shapes(cfg).items():
            mean, std, dt = draw[kind]
            out[name] = _draw_jit(_rng.next_key(), shape, dt, mean, std)
    return out


# ------------------------------------------------------ the retention's parts --
def log_gate(u, g_w):
    """log g [..., Hkv] of the normed stream u [..., D]: the log-sigmoid
    of a bias-free projection, in float32 at full precision."""
    return jax.nn.log_sigmoid(jnp.dot(u.astype(jnp.float32),
                                      g_w.astype(jnp.float32), precision=_HI))


def retention_seq(q, k, v, log_g):
    """The attention form within one sequence: q [S, H, Dh] over k, v
    [S, Hkv, Dh] under log_g [S, Hkv], query head i on K/V head
    i // (H / Hkv) -> [S, H, Dh]. float32 throughout."""
    S, H, Dh = q.shape
    Hkv = k.shape[1]
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=0)           # [S, Hkv]
    s = jnp.einsum("tjgd,sjd->jgts", q.reshape(S, Hkv, H // Hkv, Dh), k,
                   precision=_HI) / math.sqrt(Dh)
    pos = jnp.arange(S, dtype=jnp.int32)
    seen = pos[None, :] <= pos[:, None]                         # [t, s]
    decay = jnp.exp(jnp.where(seen, G.T[:, :, None] - G.T[:, None, :],
                              -jnp.inf))                        # [Hkv, t, s]
    a = s * s * decay[:, None]
    y = jnp.einsum("jgts,sjd->tjgd", a, v, precision=_HI) \
        / a.sum(-1).transpose(2, 0, 1)[..., None]
    return y.reshape(S, H, Dh)


def state_after(k, v, log_g, length):
    """The state [Hkv, R, Dh] after the first `length` positions of
    k, v [S, Hkv, Dh] under log_g [S, Hkv] — the last REAL position of a
    prompt padded to S, whatever lies behind it: position s weighs
    exp(G_{length-1} - G_s), nought from `length` on. One
    `[R, S] x [S, Dh]` product a head, a head at a time."""
    S = k.shape[0]
    G = jnp.cumsum(log_g.astype(jnp.float32), axis=0)
    last = jax.lax.dynamic_index_in_dim(G, length - 1, axis=0,
                                        keepdims=False)         # [Hkv]
    real = jnp.arange(S, dtype=jnp.int32)[:, None] < length
    c = jnp.exp(jnp.where(real, last[None] - G, -jnp.inf))      # [S, Hkv]

    def head(xs):
        k_j, v_j, c_j = xs                      # [S, Dh], [S, Dh], [S]
        kc = k_j * c_j[:, None]
        return jnp.concatenate([
            jnp.einsum("sr,sd->rd", _rs.phi_k(k_j) * c_j[:, None], v_j,
                       precision=_HI),
            jnp.einsum("sa,sb->ab", kc, k_j, precision=_HI)
            / k_j.shape[-1]], axis=0)

    return jax.lax.map(head, (jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1),
                              c.T))


# ------------------------------------------------- the step the engine runs --
class ServingPasses:
    """What `GenerativeEngine` asks of a model (models/lfm2.py's and
    `serving/generate.py::GPTPasses` are the others): the geometry of its
    cache — no K/V layer, a state `[rows, L, Hkv, R, Dh]` of its own type —
    a prefill of one prompt into a slot, one pass of rows over the state,
    the head."""

    # engine features that move K/V rows; this model has none to move
    refuses = {
        "prefix_cache_slots": "the prefix cache copies K/V rows at a prompt "
                              "boundary; a retention state would need a "
                              "snapshot taken at that boundary, 36 MB a layer",
        "draft": "speculative decode rolls rejected positions back by "
                 "length; a recurrent state cannot be rolled back",
        "kv_dtype=int8": "there is no K/V pool to quantize, and the state is "
                         "float32 by the model's numerics",
        "quantize_weights": "weight-only int8 names the GPT family's "
                            "matrices",
        "handoff": "the handoff wire (export, import, prefill_only, drain "
                   "migration) carries K/V rows only",
    }

    name = "brumby"
    # its programs carry names ("brumby_decode_c1024_b16"): what joins a
    # device operation to its scope is keyed by the program's name
    program_prefix = "brumby"
    # no K/V layer: the engine allocates no pool, and `kv_dtype` names none
    kv_dtype = "f32"
    kv_layers = 0
    state_dtype = "f32"
    expert_layers = 0

    def __init__(self, cfg: BrumbyConfig):
        self.cfg = cfg
        self.vocab_size = cfg.vocab_size
        self.max_seq_len = cfg.max_seq_len
        self.kv_heads = cfg.num_key_value_heads
        self.query_heads = cfg.num_attention_heads
        self.head_dim = cfg.head_dim

    def state_shape(self, rows: int) -> tuple:
        """The whole cache: a state a row, layer and K/V head."""
        return (rows, self.cfg.num_hidden_layers, self.kv_heads,
                _rs.state_rows(self.head_dim), self.head_dim)

    def state_step_bytes(self, real_rows: int, bucket: int) -> int:
        """State bytes one decode step moves: each REAL row's state read
        and written once a layer; a padding row's is not touched."""
        del bucket
        return 2 * 4 * real_rows * math.prod(self.state_shape(1))

    def head(self, p, h):
        return mm(h, p["head"])

    def _qkvg(self, u, p, i, pos):
        q, k, v = attn_qkv(u, p, i, pos, self.cfg)
        return q, k, v, log_gate(u, p[f"{i}.g_w"])

    def _out(self, h, y, p, i):
        """The block after the retention: the output projection, then the
        MLP, each on the residual stream."""
        h = h + mm(y.reshape(y.shape[0], -1), p[f"{i}.o_w"])
        return h + dense_ffn(
            rms_norm(h, p[f"{i}.ffn_norm"], self.cfg.norm_eps), p, i)

    def prefill(self, p, buf_k, buf_v, rec, slot, ids, length):
        """The whole (padded) prompt ids [1, S] through the layers in the
        attention form; each layer's state after the last REAL position
        into `rec[slot]`, whole — a reused slot starts clean. -> (hidden
        state at position length-1 after the final norm [D], the pools as
        they came (None), rec)."""
        cfg = self.cfg
        S = ids.shape[1]
        pos = jnp.arange(S, dtype=jnp.int32)
        h = p["embed"][ids[0]].astype(jnp.float32)             # [S, D]
        slot = slot.astype(jnp.int32)
        z0 = jnp.int32(0)
        for i in range(cfg.num_hidden_layers):
            u = rms_norm(h, p[f"{i}.operator_norm"], cfg.norm_eps)
            q, k, v, log_g = self._qkvg(u, p, i, pos)
            with jax.named_scope("retention.prefill"):
                y = retention_seq(q, k, v, log_g)
                st = state_after(k, v, log_g, length)
                rec = jax.lax.dynamic_update_slice(
                    rec, st[None, None].astype(rec.dtype),
                    (slot, jnp.int32(i), z0, z0, z0))
            h = self._out(h, y, p, i)
        h_last = jax.lax.dynamic_index_in_dim(h, length - 1, axis=0,
                                              keepdims=False)
        return (rms_norm(h_last, p["final_norm"], cfg.norm_eps), buf_k,
                buf_v, rec)

    def pool_pass(self, p, buf_k, buf_v, rec, slots, tokens, pos, scratch):
        """One token a row through the layers in the recurrent form: row
        i's token at absolute position pos[i] (the rotation's alone: the
        state has no positions) over the state of slot slots[i], advanced
        in place. Rows that name the scratch slot are padding and move no
        state. -> (hidden states after the final norm [b, D], the pools as
        they came, rec, None)."""
        cfg = self.cfg
        b = tokens.shape[0]
        Hkv, G = self.kv_heads, self.query_heads // self.kv_heads
        h = p["embed"][tokens].astype(jnp.float32)             # [b, D]
        for i in range(cfg.num_hidden_layers):
            u = rms_norm(h, p[f"{i}.operator_norm"], cfg.norm_eps)
            q, k, v, log_g = self._qkvg(u, p, i, pos)
            with jax.named_scope("retention.step"):
                y, rec = _rs.retention(
                    rec, i, slots, q.reshape(b, Hkv, G, self.head_dim), k, v,
                    jnp.exp(log_g), scratch=scratch)
            h = self._out(h, y, p, i)
        return (rms_norm(h, p["final_norm"], cfg.norm_eps), buf_k, buf_v,
                rec, None)


__all__ = ["BrumbyConfig", "PRESETS", "ServingPasses", "init_params",
           "param_shapes", "n_params"]
