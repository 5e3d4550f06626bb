"""The plain reference: GPT's forward pass and loss as the papers give
them, in jax.numpy and float32 at "highest" matmul precision.

Written from Radford et al. 2019 (GPT-2) as Brown et al. 2020 (GPT-3,
arXiv:2005.14165 §2.1) adopt it: learned token and position embeddings; L
pre-LayerNorm blocks, each `h += Attn(LN1(h)); h += MLP(LN2(h))` with
causal multi-head attention (scores scaled by 1/sqrt(head size)) and a
4x-wide MLP with the tanh-approximated GELU; a final LayerNorm; logits
through the transposed token embedding; mean token cross-entropy.

No kernel, no cache, no scan, no batching tricks, and no import of the
system under test: the layers are a Python loop over one jitted block, so
the compile is one block's, whatever the depth. Departure from the paper:
none in the mathematics; the weight LAYOUT is the checkpoint's (stacked
[L, ...] arrays, `qkv` fused as [D, 3·H·hd] in (q|k|v, head, lane) order)
because the weights compared are the system's own.

On a TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set, so every function here
sets it.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
              "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
# a TrainStep's parameter names -> the stacked layout's
TRAIN_NAMES = {"wte.weight": "wte", "wpe.weight": "wpe",
               "ln_f.weight": "lnf_w", "ln_f.bias": "lnf_b"}


def from_train_params(params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {TRAIN_NAMES.get(n, n): v for n, v in params.items()}


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(h, lp: Sequence[jax.Array], n_heads: int, eps: float):
    """One transformer block on h [B, S, D]; lp = the layer's weights in
    LAYER_KEYS order, any float dtype."""
    (ln1_w, ln1_b, qkv_w, qkv_b, out_w, out_b,
     ln2_w, ln2_b, fc1_w, fc1_b, fc2_w, fc2_b) = (_f32(x) for x in lp)
    B, S, D = h.shape
    hd = D // n_heads
    y = layer_norm(h, ln1_w, ln1_b, eps)
    qkv = (y @ qkv_w + qkv_b).reshape(B, S, 3, n_heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    h = h + att.reshape(B, S, D) @ out_w + out_b
    y = layer_norm(h, ln2_w, ln2_b, eps)
    return h + gelu_tanh(y @ fc1_w + fc1_b) @ fc2_w + fc2_b


@functools.partial(jax.jit, static_argnames=("n_heads", "eps"))
def _block_jit(h, lp, n_heads, eps):
    with jax.default_matmul_precision("highest"):
        return block(h, lp, n_heads, eps)


@jax.jit
def _embed_jit(wte, wpe, ids):
    return _f32(wte)[ids] + _f32(wpe)[jnp.arange(ids.shape[1])][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits_jit(h, lnf_w, lnf_b, wte, eps):
    with jax.default_matmul_precision("highest"):
        return layer_norm(h, _f32(lnf_w), _f32(lnf_b), eps) @ _f32(wte).T


@jax.jit
def _nll_sum_jit(logits, labels):
    logz = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return (logz - picked).sum()


def hidden(params: Dict[str, jax.Array], id_chunks: Sequence, n_heads: int,
           eps: float) -> list:
    """Final-block hidden states [B, S, D] (before the last LayerNorm) of
    each chunk of sequences. Layer by layer over all chunks, so that a
    layer's weights are sliced out of the stack once."""
    hs = [_embed_jit(params["wte"], params["wpe"],
                     jnp.asarray(ids, jnp.int32)) for ids in id_chunks]
    for layer in range(params["qkv_w"].shape[0]):
        lp = tuple(params[k][layer] for k in LAYER_KEYS)
        hs = [_block_jit(h, lp, n_heads=n_heads, eps=eps) for h in hs]
    return hs


def _head(params, h, eps: float):
    return _logits_jit(h, params["lnf_w"], params["lnf_b"], params["wte"],
                       eps=eps)


def _sizes(config: dict) -> tuple:
    """(heads, LayerNorm epsilon) of a configuration's file."""
    arch = config["architecture"]
    return int(arch["num_heads"]), float(arch["layer_norm_eps"])


def serve_logits(params, ids, config: dict):
    """[B, S, V] float32 logits of the whole sequences. `params` is what
    the engine serves (the stacked layout as it is), `config` the
    configuration's file."""
    n_heads, eps = _sizes(config)
    return _head(params, hidden(params, [ids], n_heads, eps)[0], eps)


def train_loss(params, ids, labels, config: dict, rows: int = 4) -> float:
    """Mean cross-entropy over every position of ids/labels [B, S],
    `rows` sequences at a time (the [rows, S, V] logits are the largest
    array the reference holds). `params` is a TrainStep's, under its own
    names."""
    n_heads, eps = _sizes(config)
    params = from_train_params(params)
    starts = range(0, len(ids), rows)
    hs = hidden(params, [ids[i:i + rows] for i in starts], n_heads, eps)
    total = 0.0
    for i, h in zip(starts, hs):
        lab = jnp.asarray(labels[i:i + rows], jnp.int32)
        total += float(_nll_sum_jit(_head(params, h, eps), lab))
    return total / labels.size
