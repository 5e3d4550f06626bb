"""GPT model family — the flagship decoder LM.

Paddle-style implementation (cf. PaddleNLP GPT / the auto-parallel test model
/root/reference/test/auto_parallel/get_gpt_model.py) built on paddle_tpu.nn.
TPU-first details:
- attention uses the fused scaled-dot-product body (XLA flash-fuses;
  Pallas splash kernel swaps in for long sequences),
- weights are plain Linears whose *names* drive mesh sharding (shard_fn in
  paddle_tpu.jit.TrainStep / paddle_tpu.distributed): qkv+fc1 column-parallel,
  out_proj+fc2 row-parallel, embeddings vocab-parallel — Megatron TP layout
  expressed as GSPMD PartitionSpecs instead of explicit collectives.
"""
from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

import paddle_tpu as paddle
from .. import nn
from ..core.tensor import Tensor
from ..nn import functional as F


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_hidden=None, max_seq_len=1024,
                 dropout=0.0, layer_norm_eps=1e-5, tie_embeddings=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden = ffn_hidden or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.layer_norm_eps = layer_norm_eps
        self.tie_embeddings = tie_embeddings


PRESETS = {
    "gpt3-tiny": GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                           num_heads=8, max_seq_len=256),
    # the first-party speculative-decode draft: shares gpt3-tiny's
    # vocab/tokenizer and context so `serve.py --generate gpt3-tiny
    # --draft tiny-draft` works out of the box (the draft must cover
    # every position the target can cache)
    "tiny-draft": GPTConfig(vocab_size=1024, hidden_size=64, num_layers=1,
                            num_heads=4, max_seq_len=256),
    "gpt3-small": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt3-medium": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt3-large": GPTConfig(hidden_size=1536, num_layers=24, num_heads=16),
    "gpt3-xl": GPTConfig(hidden_size=2048, num_layers=24, num_heads=16),
    # 1.3B (the BASELINE.md flagship config)
    "gpt3-1.3b": GPTConfig(hidden_size=2048, num_layers=24, num_heads=32,
                           max_seq_len=1024),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                           max_seq_len=1024),
}


from ..core.dispatch import defop


@defop("gpt_cached_attention")
def _cached_attn_p(q, k_new, v_new, k_buf, v_buf, pos):
    """Single/multi-token decode attention over a fixed-size KV cache.

    q/k_new/v_new: [B, Ln, H, D]; k_buf/v_buf: [B, max, H, D]; pos: scalar
    int (tokens already cached). Writes the new K/V at [pos, pos+Ln),
    attends causally over the valid prefix, returns
    (out [B, Ln, H, D], k_buf', v_buf')."""
    B, Ln, H, D = q.shape
    maxlen = k_buf.shape[1]
    pos = pos.astype(jnp.int32)
    z = jnp.int32(0)
    k_buf = jax.lax.dynamic_update_slice(
        k_buf, k_new.astype(k_buf.dtype), (z, pos, z, z))
    v_buf = jax.lax.dynamic_update_slice(
        v_buf, v_new.astype(v_buf.dtype), (z, pos, z, z))
    qh = jnp.swapaxes(q, 1, 2)                     # [B, H, Ln, D]
    kh = jnp.swapaxes(k_buf, 1, 2)                 # [B, H, max, D]
    vh = jnp.swapaxes(v_buf, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / math.sqrt(D)
    kpos = jnp.arange(maxlen)
    qpos = pos + jnp.arange(Ln)
    mask = kpos[None, :] <= qpos[:, None]          # causal over the prefix
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh)
    return jnp.swapaxes(out, 1, 2), k_buf, v_buf


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv_proj = nn.Linear(cfg.hidden_size, 3 * cfg.hidden_size)
        self.out_proj = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.dropout = cfg.dropout

    def forward(self, x, cache=None):
        b, l, h = x.shape
        qkv = self.qkv_proj(x)
        qkv = qkv.reshape([b, l, 3, self.num_heads, self.head_dim])
        q, k, v = qkv.unbind(axis=2)
        if cache is not None:
            out, k_buf, v_buf = _cached_attn_p(q, k, v, cache["k"],
                                               cache["v"], cache["pos"])
            cache["k"], cache["v"] = k_buf, v_buf
            out = out.reshape([b, l, h])
            return self.out_proj(out)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             dropout_p=self.dropout)
        out = out.reshape([b, l, h])
        return self.out_proj(out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = nn.Linear(cfg.hidden_size, cfg.ffn_hidden)
        self.fc2 = nn.Linear(cfg.ffn_hidden, cfg.hidden_size)

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)
        self.dropout = nn.Dropout(cfg.dropout)

    def forward(self, x, cache=None):
        x = x + self.dropout(self.attn(self.ln1(x), cache=cache))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return x


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)

    def forward(self, input_ids, caches=None, pos_offset=0):
        b, l = input_ids.shape
        pos = paddle.arange(l, dtype="int64").unsqueeze(0) + pos_offset
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        for i, blk in enumerate(self.blocks):
            x = blk(x, cache=caches[i] if caches is not None else None)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids):
        h = self.gpt(input_ids)
        if self.cfg.tie_embeddings:
            logits = paddle.matmul(h, self.gpt.wte.weight, transpose_y=True)
        else:
            logits = self.lm_head(h)
        return logits

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        return F.cross_entropy(
            logits.reshape([-1, self.cfg.vocab_size]),
            labels.reshape([-1]))

    def stacked_items(self):
        """The weights as (name, fresh array) pairs, made one at a time:
        wte, wpe, the LAYER_PARAMS stacked [L, ...], lnf_w, lnf_b and,
        untied, lm_head. A caller that hands each on as it comes
        (GPTForCausalLMScan.from_unrolled) never holds a second whole
        copy of the weights — at 1.3B on one chip that copy does not fit."""
        blocks = [GPTLayer(b.ln1.weight, b.ln1.bias,
                           b.attn.qkv_proj.weight, b.attn.qkv_proj.bias,
                           b.attn.out_proj.weight, b.attn.out_proj.bias,
                           b.ln2.weight, b.ln2.bias,
                           b.mlp.fc1.weight, b.mlp.fc1.bias,
                           b.mlp.fc2.weight, b.mlp.fc2.bias)
                  for b in self.gpt.blocks]
        layers = ((n, jnp.stack([t._data for t in ts]))
                  for n, ts in zip(LAYER_PARAMS, zip(*blocks)))
        return _stacked_items(
            self.gpt, layers, self.gpt.ln_f,
            None if self.cfg.tie_embeddings else self.lm_head.weight)

    def stacked_params(self) -> dict:
        """`stacked_items()` as the dict the scans over layers read —
        what the serving engine's programs take."""
        return dict(self.stacked_items())

    def _logits_from_hidden(self, h):
        if self.cfg.tie_embeddings:
            return paddle.matmul(h, self.gpt.wte.weight, transpose_y=True)
        return self.lm_head(h)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 top_k=0, temperature=1.0, eos_token_id=None):
        """Autoregressive decoding over a fixed-size KV cache (prefill +
        one cached-attention step per token; each step is one compiled
        program reused across steps). Returns [B, L+max_new_tokens] ids
        (greedy, or top-k sampling with do_sample=True)."""
        import numpy as np

        from ..core import rng as _rng

        ids = input_ids if isinstance(input_ids, paddle.Tensor) \
            else paddle.to_tensor(np.asarray(input_ids))
        B, L = ids.shape
        maxlen = min(self.cfg.max_seq_len, L + max_new_tokens)
        H, D = self.cfg.num_heads, self.cfg.hidden_size // self.cfg.num_heads
        caches = [
            {"k": paddle.zeros([B, maxlen, H, D]),
             "v": paddle.zeros([B, maxlen, H, D]),
             "pos": paddle.to_tensor(np.int32(0))}
            for _ in self.gpt.blocks]
        with paddle.no_grad():
            # prefill the whole prompt in one pass
            h = self.gpt(ids, caches=caches, pos_offset=0)
            logits = self._logits_from_hidden(h[:, -1:])
            out_ids = [ids]
            cur_len = L
            for _ in range(max_new_tokens):
                if cur_len >= maxlen:
                    break
                step_logits = logits[:, -1] / max(temperature, 1e-6)
                if do_sample:
                    if top_k and top_k > 0:
                        kth = paddle.topk(step_logits, top_k)[0][:, -1:]
                        step_logits = paddle.where(
                            step_logits < kth,
                            paddle.full_like(step_logits, -1e30),
                            step_logits)
                    g = jax.random.gumbel(_rng.next_key(),
                                          tuple(step_logits.shape))
                    nxt = paddle.argmax(
                        paddle.Tensor(step_logits._data + g), axis=-1)
                else:
                    nxt = paddle.argmax(step_logits, axis=-1)
                nxt = nxt.reshape([B, 1]).astype("int64")
                out_ids.append(nxt)
                if eos_token_id is not None and bool(
                        (nxt == eos_token_id).all().numpy()):
                    break
                for c in caches:
                    c["pos"] = paddle.to_tensor(np.int32(cur_len))
                h = self.gpt(nxt, caches=caches, pos_offset=cur_len)
                logits = self._logits_from_hidden(h)
                cur_len += 1
        return paddle.concat(out_ids, axis=1)


# One block's arrays, in the order every scan over layers takes them:
# GPTForCausalLMScan's parameters and the engine's param dict hold them
# stacked [L, ...] under these names, a scan body sees one layer's.
GPTLayer = collections.namedtuple("GPTLayer", (
    "ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
    "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b"))
LAYER_PARAMS = GPTLayer._fields


def layer_stack(p) -> GPTLayer:
    """A scan's per-layer input from a stacked param dict."""
    return GPTLayer(*(p[n] for n in LAYER_PARAMS))


# What a GPT block computes, as plain jax.numpy over [..., D] residual
# streams: two halves with the caller's own attention between them (the
# train scan's flash kernel, the engine's in-program causal softmax or
# its read of the K/V pool).
def layer_norm(h, w, b, eps):
    mu = h.mean(-1, keepdims=True)
    var = ((h - mu) ** 2).mean(-1, keepdims=True)
    return (h - mu) / jnp.sqrt(var + eps) * w + b


# Values of the block that a remat policy may keep for the backward, by
# the name each carries. A caller that wants them named passes
# `named=jax.ad_checkpoint.checkpoint_name` (an identity that leaves no
# instruction; only `jax.checkpoint(..., policy=save_only_these_names)`
# reads it); every other caller's trace is what it was without names.
SAVE_QKV = "gpt.qkv"                # the fused QKV projection, unreshaped
SAVE_ATTN_PROJ = "gpt.attn_proj"    # att @ out_w: over tp, AFTER its all-reduce


def block_qkv(h, lp: GPTLayer, num_heads, eps, named=None):
    """First half: norm and fused QKV projection of the residual stream
    h [..., D]; returns q, k, v, each [..., H, Dh]."""
    qkv = layer_norm(h, lp.ln1_w, lp.ln1_b, eps) @ lp.qkv_w + lp.qkv_b
    if named is not None:
        qkv = named(qkv, SAVE_QKV)
    H = int(num_heads)
    qkv = qkv.reshape(h.shape[:-1] + (3, H, h.shape[-1] // H))
    return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]


def block_out(h, att, lp: GPTLayer, eps, named=None):
    """Second half: the attention's output att [..., D] through the output
    projection, second norm and GELU MLP, onto the residual stream."""
    proj = att @ lp.out_w
    if named is not None:
        proj = named(proj, SAVE_ATTN_PROJ)
    h = h + proj + lp.out_b
    y = layer_norm(h, lp.ln2_w, lp.ln2_b, eps)
    y = jax.nn.gelu(y @ lp.fc1_w + lp.fc1_b, approximate=True) \
        @ lp.fc2_w + lp.fc2_b
    return h + y


def lm_head(p, h):
    """Logits of hidden states h [..., D] (after the final norm) under a
    stacked param dict: its own head, or the tied embedding."""
    if "lm_head" in p:
        return h @ p["lm_head"]
    return h @ p["wte"].T


def _copy(t):
    # REAL copies, not aliases: the source model's arrays die the moment
    # a donated train step updates it
    return jnp.array(t._data, copy=True)


def _stacked_items(emb, layers, ln_f, head):
    """`layers`: the (name, fresh array) pairs of the LAYER_PARAMS, lazy."""
    yield "wte", _copy(emb.wte.weight)
    yield "wpe", _copy(emb.wpe.weight)
    yield from layers
    yield "lnf_w", _copy(ln_f.weight)
    yield "lnf_b", _copy(ln_f.bias)
    if head is not None:
        yield "lm_head", _copy(head)


@defop("gpt_scan_blocks")
def _gpt_scan_blocks_p(x, *layers, num_heads=8, eps=1e-5, remat=False,
                       attn_shard=None, remat_save=()):
    """The whole transformer stack as ONE lax.scan over stacked per-layer
    params (`layers`: the LAYER_PARAMS, [L, ...] leading axis) — XLA sees
    one block body instead of L unrolled copies, so compile time drops
    ~L-fold (same math as the unrolled GPTBlock list; dropout-free path).
    remat=True checkpoints each scan iteration (activation memory ~1
    block) and keeps of it only the values named in `remat_save` (the
    SAVE_* names; what TrainStep's remat plan chose). attn_shard = (mesh,
    spec of the [B, L, H, hd] q/k/v) runs attention per shard
    (GPTForCausalLMScan.shard_attention)."""
    from ..nn.functional import _sdpa_p

    sdpa = functools.partial(_sdpa_p._pure_fn, is_causal=True)
    if attn_shard is not None:
        from ..distributed.collective import shard_map

        mesh, spec = attn_shard
        sdpa = shard_map(sdpa, mesh, in_specs=(spec,) * 3, out_specs=spec,
                         check=False)

    # names only where a policy reads them: an empty `remat_save` traces
    # the block as it was before it named anything
    named = checkpoint_name if remat and remat_save else None

    def body(h, lp):
        att = sdpa(*block_qkv(h, lp, num_heads, eps, named))
        return block_out(h, att.reshape(h.shape), lp, eps, named), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                *remat_save) if remat_save else None)
    out, _ = jax.lax.scan(body, x, GPTLayer(*layers))
    return out


class GPTForCausalLMScan(nn.Layer):
    """GPT with scan-over-layers blocks: one STACKED parameter per block
    weight, the stack executed by `gpt_scan_blocks`. Same math as
    GPTForCausalLM with dropout=0 (build via `from_unrolled` for
    bit-matching weights); the win is compile time — one block body
    traced instead of num_layers copies (reference role:
    the fused-multi-transformer static op,
    paddle/fluid/operators/fused/fused_multi_transformer_op.cu)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        if cfg.dropout:
            raise ValueError("GPTForCausalLMScan is the dropout-free "
                             "training-throughput path; use dropout=0")
        self.cfg = cfg
        L, D, Hf = cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden
        self.wte = nn.Embedding(cfg.vocab_size, D)
        self.wpe = nn.Embedding(cfg.max_seq_len, D)
        mk = self.create_parameter
        xav = nn.initializer.XavierNormal()
        one = nn.initializer.Constant(1.0)
        z = nn.initializer.Constant(0.0)
        # one STACKED parameter per LAYER_PARAMS entry: its initializer
        # and one layer's shape
        inits = GPTLayer((one, [D]), (z, [D]), (xav, [D, 3 * D]),
                         (z, [3 * D]), (xav, [D, D]), (z, [D]),
                         (one, [D]), (z, [D]), (xav, [D, Hf]), (z, [Hf]),
                         (xav, [Hf, D]), (z, [D]))
        for n, (init, shape) in zip(LAYER_PARAMS, inits):
            setattr(self, n, mk([L] + shape, default_initializer=init))
        self.ln_f = nn.LayerNorm(D, cfg.layer_norm_eps)
        if not cfg.tie_embeddings:
            self.lm_head_w = mk([D, cfg.vocab_size],
                                default_initializer=xav)
        self.remat = False
        self.remat_save = ()    # SAVE_* names the remat keeps (TrainStep)
        self._attn_shard = None

    def shard_attention(self, mesh, mesh_axes=("dp", "tp")):
        """Run attention per (batch, head) shard of `mesh` (shard_map)
        — the companion of gpt_scan_shard_fn(mesh_axes). GSPMD cannot
        partition a Pallas call: with sharded q/k/v the bare flash
        kernel does not lower ("wrap the call in a shard_map")."""
        from jax.sharding import PartitionSpec as P

        dp, tp = mesh_axes
        self._attn_shard = (mesh, P(dp, None, tp, None))

    @classmethod
    def from_unrolled(cls, model: "GPTForCausalLM") -> "GPTForCausalLMScan":
        """Stack an unrolled GPTForCausalLM's per-block weights (exact
        same function, scan execution)."""
        cfg = model.cfg
        if cfg.dropout:
            raise ValueError(
                "from_unrolled: the scan model has no dropout path; the "
                "source config uses dropout={} — converting would "
                "silently change the function".format(cfg.dropout))
        out = cls(GPTConfig(vocab_size=cfg.vocab_size,
                            hidden_size=cfg.hidden_size,
                            num_layers=cfg.num_layers,
                            num_heads=cfg.num_heads,
                            ffn_hidden=cfg.ffn_hidden,
                            max_seq_len=cfg.max_seq_len, dropout=0.0,
                            layer_norm_eps=cfg.layer_norm_eps,
                            tie_embeddings=cfg.tie_embeddings))
        dest = {"wte": out.wte.weight, "wpe": out.wpe.weight,
                "lnf_w": out.ln_f.weight, "lnf_b": out.ln_f.bias,
                **{n: getattr(out, n) for n in LAYER_PARAMS}}
        if not cfg.tie_embeddings:
            dest["lm_head"] = out.lm_head_w
        # one at a time: each set_value frees the array it replaces
        for name, value in model.stacked_items():
            dest[name].set_value(value)
        return out

    def remat_candidates(self, ids_shape, mesh, param_specs, batch_spec,
                         peaks) -> list:
        """What `remat_save` may name, priced for `jit.remat_plan`: each
        value's bytes on one device over the whole stack, and the seconds
        of second forward the backward no longer runs when it is kept —
        its product's FLOPs at the peak, and for a product whose
        contraction is split over mesh axes (a row-parallel weight, read
        off `param_specs`) the all-reduce that follows it. `ids_shape`
        [B, L] laid out by `batch_spec`; `peaks`: the device's
        `profiler.stats.flops.DEVICE_PEAKS` row. Empty without remat."""
        from ..jit.remat_plan import RematCandidate

        if not self.remat:
            return []

        def ways(entry):    # devices one PartitionSpec entry splits over
            names = entry if isinstance(entry, (tuple, list)) else (entry,)
            return math.prod(mesh.shape[a] for a in names if a is not None)

        def split(name):    # of a stacked [L, k, n] weight: (k, n) ways
            if mesh is None:
                return 1, 1
            sp = tuple((param_specs or {}).get(name) or ())
            return tuple(map(ways, (sp + (None,) * 3)[1:3]))

        cfg = self.cfg
        D, layers = cfg.hidden_size, cfg.num_layers
        itemsize = self.qkv_w._data.dtype.itemsize
        batch, seq = ids_shape
        if mesh is not None and batch_spec:
            batch //= ways(batch_spec[0])
        flops_s = peaks["bf16_flops"]

        def product(name, k, n):
            """One layer's [batch, seq, n] product on a device -> (its
            bytes, its seconds and those of the all-reduce after it)."""
            k_split, n_split = split(name)
            nbytes = batch * seq * (n // n_split) * itemsize
            seconds = 2 * batch * seq * (k // k_split) * (n // n_split) \
                / flops_s
            if k_split > 1:     # a ring sends 2 (w - 1) / w of the value
                seconds += 2 * (k_split - 1) / k_split * nbytes \
                    / peaks["ici_link_bytes_per_s"]
            return nbytes, seconds

        proj_bytes, proj_s = product("out_w", D, D)
        qkv_bytes, qkv_s = product("qkv_w", D, 3 * D)
        return [
            RematCandidate(SAVE_ATTN_PROJ, proj_bytes, layers,
                           proj_s * layers),
            RematCandidate(SAVE_QKV, qkv_bytes, layers, qkv_s * layers),
        ]

    def stacked_params(self) -> dict:
        """Copies of the weights as the stacked param dict (see
        GPTForCausalLM.stacked_params)."""
        layers = ((n, _copy(getattr(self, n))) for n in LAYER_PARAMS)
        return dict(_stacked_items(
            self, layers, self.ln_f,
            None if self.cfg.tie_embeddings else self.lm_head_w))

    def hidden(self, input_ids):
        b, l = input_ids.shape
        pos = paddle.arange(l, dtype="int64").unsqueeze(0)
        x = self.wte(input_ids) + self.wpe(pos)
        h = _gpt_scan_blocks_p(
            x, *(getattr(self, n) for n in LAYER_PARAMS),
            num_heads=self.cfg.num_heads, eps=self.cfg.layer_norm_eps,
            remat=bool(self.remat), attn_shard=self._attn_shard,
            remat_save=tuple(self.remat_save))
        return self.ln_f(h)

    def forward(self, input_ids):
        h = self.hidden(input_ids)
        if self.cfg.tie_embeddings:
            return paddle.matmul(h, self.wte.weight, transpose_y=True)
        return paddle.matmul(h, self.lm_head_w)


def gpt_shard_fn(mesh_axes=("dp", "tp")):
    """Megatron TP layout as a name->PartitionSpec mapping for TrainStep.

    qkv/fc1 column-parallel (shard output dim over tp), out_proj/fc2
    row-parallel (shard input dim), embeddings vocab/hidden-parallel,
    norms+biases replicated. XLA/GSPMD then inserts the same collectives the
    reference wires by hand in fleet/layers/mpu/mp_layers.py.
    """
    from jax.sharding import PartitionSpec as P

    dp, tp = mesh_axes

    def shard(name, value):
        if value.ndim == 2:
            if "qkv_proj.weight" in name or "fc1.weight" in name:
                return P(None, tp)
            if "out_proj.weight" in name or "fc2.weight" in name:
                return P(tp, None)
            if "wte.weight" in name:
                return P(tp, None)     # vocab-parallel embedding
            if "lm_head.weight" in name:
                return P(None, tp)
            return P()
        if value.ndim == 1:
            if "qkv_proj.bias" in name or "fc1.bias" in name:
                return P(tp)
            return P()
        return P()

    return shard


def gpt_scan_shard_fn(mesh_axes=("dp", "tp")):
    """Megatron TP layout for GPTForCausalLMScan's STACKED parameters
    (leading dim = layer): same column/row-parallel assignment as
    gpt_shard_fn, one axis to the right. Under lax.scan each per-layer
    slice inherits the stack's non-leading sharding, so GSPMD inserts
    the identical collectives inside the scan body that the unrolled
    layout gets per block."""
    from jax.sharding import PartitionSpec as P

    dp, tp = mesh_axes

    def shard(name, value):
        if value.ndim == 3:
            if "qkv_w" in name or "fc1_w" in name:
                return P(None, None, tp)   # column-parallel
            if "out_w" in name or "fc2_w" in name:
                return P(None, tp, None)   # row-parallel
            return P()
        if value.ndim == 2:
            if "qkv_b" in name or "fc1_b" in name:
                return P(None, tp)
            if "wte.weight" in name:
                return P(tp, None)         # vocab-parallel embedding
            if "lm_head_w" in name:
                return P(None, tp)
            return P()
        return P()

    return shard


# ----------------------------------------------------------- pipeline form --
class GPTEmbeddingPipe(nn.Layer):
    """First pipeline stage: tied word embedding + positions + dropout
    (reference GPTForPipeline embedding stage with SharedLayerDesc,
    fleet meta_parallel pp_layers.py:76)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn import initializer as I

        # same init as GPTModel.wte (nn.Embedding default) so pipeline and
        # single-program builds start from the same distribution
        self.shared_weight = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size],
            default_initializer=I.XavierNormal())
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids):
        b, l = input_ids.shape
        pos = paddle.arange(l, dtype="int64").unsqueeze(0)
        x = F.embedding(input_ids, self.shared_weight) + self.wpe(pos)
        return self.drop(x)


class GPTLMHeadPipe(nn.Layer):
    """Last pipeline stage: final LN + tied LM head (the shared_weight is
    re-bound to the embedding stage's by SharedLayerDesc; grads are summed
    across stages by the PP engine)."""

    def __init__(self, cfg: GPTConfig, tied: bool = True):
        super().__init__()
        self.cfg = cfg
        from ..nn import initializer as I

        self.ln_f = nn.LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        # tied: placeholder is rebound by SharedLayerDesc — zeros init
        # avoids a wasted (and RNG-stream-shifting) random draw
        self.shared_weight = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size],
            default_initializer=I.Constant(0.0) if tied
            else I.XavierNormal())

    def forward(self, x):
        h = self.ln_f(x)
        return paddle.matmul(h, self.shared_weight, transpose_y=True)


def gpt_pipeline_descs(cfg: GPTConfig):
    """LayerDescs for the real pipeline engine: embedding first stage,
    one desc per transformer block, LM-head last stage — tied across
    stages iff cfg.tie_embeddings (reference
    parallel_layers/pp_layers.py:240 segmentation input)."""
    from ..distributed.pipeline import LayerDesc, SharedLayerDesc

    if cfg.tie_embeddings:
        descs = [SharedLayerDesc("embed", GPTEmbeddingPipe, cfg,
                                 shared_weight_attr="shared_weight")]
    else:
        descs = [LayerDesc(GPTEmbeddingPipe, cfg)]
    descs += [LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)]
    if cfg.tie_embeddings:
        descs.append(SharedLayerDesc("embed", GPTLMHeadPipe, cfg,
                                     shared_weight_attr="shared_weight"))
    else:
        descs.append(LayerDesc(GPTLMHeadPipe, cfg, tied=False))
    return descs
