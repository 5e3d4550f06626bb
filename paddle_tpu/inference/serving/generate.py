"""Continuous-batching generative inference — the decode scheduler.

The predict engine (engine.py) forces single-shot traffic through a
small pre-compiled shape set; this module does the same for
AUTOREGRESSIVE traffic, where the naive approach (one decode loop per
request, batch fixed at arrival) collapses as sequence lengths diverge.
Design (Orca-style iteration-level scheduling over a vLLM-style slot
pool, re-cut for the XLA compilation contract):

- **Prefill/decode split.** Each request is exactly one prefill call
  (prompt padded to its pow2 seq bucket via io/bucketing, batch dim 1)
  plus repeated fixed-shape decode steps. Two program families total:

    prefill[S]  (params, pool_k, pool_v, slot, ids[1,S], len) ->
                (first_token, pool_k', pool_v')
    decode[b]   (params, pool_k, pool_v, slots[b], tokens[b],
                 lengths[b]) -> (next_tokens[b], lengths[b] + 1,
                                 pool_k', pool_v')

  Every program is memoized per (family, bucket) and pre-compiled
  through the persistent compile cache (core/compile_cache), so a warm
  FLAGS_compile_cache_dir restart serves generation with
  persistent_misses == 0 (the PR-2/PR-9 warm-before-admission
  contract).

- **Bucketed KV-cache pool.** Each worker owns a preallocated KV pool:
  per capacity class (pow2 slot sizes, default one class at
  max_context) a pair of [n_slots+1, L, cap, H*Dh] buffers (heads
  folded into the minor dimension: dense lanes on a TPU) whose rows
  are SLOTS handed out from a free list and reused across requests
  (the +1 row is scratch for decode-batch padding). Prefill stores
  the prompt's KV into its slot in-program. ONE pass, `_pool_pass`
  (decode, the draft burst, verify and extend are that pass plus what
  each does with the hidden states; what a block computes comes from
  models/gpt.py), works on the pool WHERE IT LIES: the scan
  over layers carries the two pools; each layer writes its new
  position(s) into the pool (`kv.write_layer`: buf[slot, layer, pos],
  a position past the cap into the scratch row) and then attends over
  pool rows addressed by slot through ONE entry, `pool_attention` —
  write first, read after, so a row sees its own token and an int8
  pool is pool-consistent by construction. Nothing gathers, transposes
  or re-materializes pool rows: with the pools donated the compiled
  program aliases its output pools to its inputs and holds no
  temporary of a pool's size. `pool_attention` has two reads, chosen
  from what the code can observe. One query a row over a float pool,
  lowered for a TPU (`lax.platform_dependent`): the Pallas kernel of
  ops/pallas/decode_attention.py, whose `BlockSpec` index map picks
  block (slots[i], layer, j) of the pool in HBM and which reads only
  the blocks up to each row's position. Every other lowering, an int8
  pool, and several queries a row (verify, extend): `buf[slots, layer]`
  — one layer of the rows, dequantized where the pool is int8 — and a
  masked softmax over the class capacity. The pool buffers are
  threaded functionally through the programs (donate-able on
  accelerators; donation stays off on CPU where the persistent cache
  must hold the programs — core/compile_cache.donated_cpu_guard).

- **In-flight batching.** The decode step runs the ACTIVE rows padded
  to their pow2 batch bucket; between steps the scheduler admits new
  requests into free slots (prefill happens right then, on the worker
  thread) and retires finished rows (EOS/max_tokens) without ever
  stalling the rest of the batch.

- **The step-to-step dependency stays on the device.** A decode program
  returns its rows advanced — next token, key, length + 1 — in the shapes
  it took them, and plain decode feeds step n+1 from step n's outputs
  (`_DeviceRows`): in the steady state a pass of the worker is LAUNCH the
  next step, BLOCK on the oldest unread one, EMIT it (`_STEPS_AHEAD`), so
  the device goes from one step into the next while the host reads and
  streams the one before. What only the host knows — slots, temperature,
  top-k, top-p — is staged when the row set changes and not otherwise; a
  row-set change reads everything launched first and stages the seven
  arrays once. The host's row.length / row.key / req.tokens are current
  as of the last step READ; whoever takes a row elsewhere (export,
  migration) settles the launched steps first, and a requeue replays
  from the tokens emitted. An EOS is learned one step late: the
  overshoot step wrote past the row's end in its own slot, its token is
  discarded and it counts as no row. The speculative loop, whose host
  decides in the middle of every step, stays closed (`_spec_step`).

- **The model supplies the step.** What a prefill and a pass over rows
  of the cache COMPUTE is the model's: its configuration's
  `serving_passes()` gives the cache's geometry (K/V rows for its
  attention layers, `[rows, kv_layers, cap, kv_heads*Dh]`, and any
  fixed-size state beside them, one row a slot), `prefill`, `pool_pass`,
  `head`, the pool's type and the engine features it `refuses` by name
  (models/lfm2.py::ServingPasses: gated short-convolution layers whose
  state rides `_ClassState.rec` — allocated, donated, carried and dropped
  with the pools; grouped-query attention through the same
  `pool_attention`; routed experts whose per-step counts come back with
  the tokens. models/brumby.py::ServingPasses: NO attention layer — the
  state, of the model's own type, is the whole cache; no K/V pool is
  allocated, the pools ride the programs' signatures as None, and a class's
  `cap` only limits positions). The GPT family's is `GPTPasses` below,
  over models/gpt.py's two halves. The worker loop, admission,
  launch-ahead, sampling, streaming and metrics are one code for every
  model.

- **Streaming.** Tokens are emitted per step onto each request's
  stream queue (GenerateHandle iterates them; server.py chunks them
  over HTTP) with TTFT/tokens-per-sec metrics on the bus and per-token
  spans riding the PR-6 tracer.

Replica lifecycle is the SHARED state machine (lifecycle.py): workers
are warming -> active -> draining -> retired with a generation counter,
so the autoscale controllers (ReplicaAutoscaler, HealthWatchdog) drive
a GenerativeEngine exactly like the predict engine — ``add_replica``
warms every program BEFORE admission, ``remove_replica(drain=True)``
stops admitting and lets in-flight sequences finish, and
``revive_replica`` supersedes a hung worker whose in-flight requests
are requeued: the requeued request RE-PREFILLS from its prompt and the
tokens it already streamed are suppressed on re-emission (greedy decode
is deterministic, so the regenerated prefix is identical and the client
stream never sees a duplicate).

Chaos site: ``serving.decode_step`` fires on the worker thread before
every decode step — a ``delay`` rule is the mid-decode hang the health
watchdog is tested against; a ``raise`` rule exercises the requeue
ladder.

Beyond greedy (PR 17), three compounding decode-path features ride the
same program inventory and slot pool:

- **Seeded sampling.** temperature / top-k / top-p ride every program
  as per-row arrays next to slots/lengths; each row carries a raw
  uint32[2] PRNG key derived from its request seed, split ONCE per
  emitted token in-program (jax.random, vmapped per row so the chain
  is independent of batch composition). Same seed => token-identical
  output across the batched, sequential, streaming and HTTP paths,
  and across a requeue re-prefill (the chain replays from the seed).
  temperature == 0 keeps the argmax path bitwise-unchanged. Top-k and
  top-p mask by VALUE, so the two thresholds (the k-th largest value,
  the nucleus cut-off) are found by a search over values, the rows of a
  step together (`_sample_token`); a batch skips what none of its rows
  asks for — the top-k search, or everything but the argmax.

- **Speculative multi-token decode.** With a ``draft=`` model, each
  scheduler iteration runs ONE fused k-step draft burst
  (``dpropose`` — lax.scan over k cheap decode steps, one dispatch)
  and ONE target ``verify`` program that scores all k positions in a
  single batched pass, sampling the target's own token at every
  position with the SAME key chain plain decode would use. The host
  accepts the longest agreed prefix (>= 1 token: rejection falls back
  to the target's own token), so output is bitwise-identical to
  non-speculative decode under greedy AND under seeded sampling.
  Block K/V is scattered in-program; positions past the class cap are
  redirected to the scratch row, never corrupting a live slot.

- **Prefix caching.** Prefill K/V is keyed by (pow2 boundary, prompt-
  prefix hash) in a bounded per-class LRU whose entries are extra pool
  rows. A hit copies the cached row into the request's slot (one
  ``pcopy`` program) and prefills only the tail block (``extend`` —
  queries attend the cached prefix), so N requests sharing a system
  prompt pay one full prefill. Misses admit the longest aligned
  prefix on the way out. The cache dies with the worker generation
  (revive/requeue reset it with the buffers).

Quantized serving (PR 18, quantization/kv.py) rides the same program
inventory:

- **int8 KV pool** (``kv_dtype="int8"``). The pool buffers become
  ``kv.QuantizedKV`` pytrees — int8 data + per-(row, layer) float32
  absmax scales — and the program bodies fuse quantize-on-write /
  dequantize-on-read through the kv helpers (prefill resets a row's
  scale from its block absmax; decode/verify/extend quantize new
  positions with the row's existing scale, clip semantics). Every
  body writes into the pool before it reads, so a verify pass reads
  bitwise what plain decode would read back — spec-on stays bitwise-
  equal to spec-off under int8. Prefix-cache rows copy as raw int8 +
  scale (bit-exact hits), so cache capacity doubles with the pool.
  Programs carry ``kv_dtype`` as a family dimension and warm before
  admission exactly like the float inventory; donation discipline is
  unchanged (the pytree donates whole).

- **Weight-only int8 replicas** (``quantize_weights=True``). The
  stacked matmul weights are absmax-quantized ONCE host-side (per
  layer, via quantization.quantize_absmax); replicas device_put the
  int8 tensors and the bodies dequantize at trace time (dequant-in-
  matmul), halving-and-halving-again what a replica's weights cost.
"""
from __future__ import annotations

import contextlib
import functools
import hashlib
import math
import threading
import time
from collections import OrderedDict, deque
from queue import Queue
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...core import compile_cache as _cc
from ...core.flags import flag
from ...io.bucketing import bucket_boundaries_pow2, bucket_for
from ...models import gpt as _gpt
from ...ops.pallas import decode_attention as _dattn
from ...quantization import kv as _kvq
from ...observability import trace as _tr
from ...testing import chaos as _chaos
from ...testing.racecheck import shared_state as _shared_state
from . import metrics as _sm
from .lifecycle import (Future, ReplicaSlot, ServingError,
                        pick_least_loaded_device, validate_sampling)

_NEG_INF = -1e30


def _seed_key(seed: int) -> np.ndarray:
    """Raw uint32[2] jax PRNG key from a 64-bit seed, built host-side
    in numpy: constructing it with jax.random.PRNGKey would run eager
    jax ops on the request path and cost the workload its misses==0."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF], np.uint32)


def _prefix_hash(prompt: np.ndarray, n: int) -> str:
    """Content key for the first n prompt tokens (prefix-cache key is
    (n, hash) so distinct boundaries never collide)."""
    return hashlib.blake2b(np.ascontiguousarray(prompt[:n]).tobytes(),
                           digest_size=16).hexdigest()


# ===================================================================
# pure program bodies (jitted per bucket; params is a dict of stacked
# per-layer arrays — one lax.scan body instead of L unrolled blocks)
# ===================================================================
_INT_MIN = np.int32(-2 ** 31)


def _order_flip(bits):
    """Between a float32's bits (as int32) and its ORDER IMAGE, the int32
    whose signed order is the floats' own: a negative float's low 31 bits
    are flipped. Its own inverse. (-0.0 lands one under +0.0; the values a
    search returns are compared as floats again, where the two are equal.)"""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


# Bits of a threshold that one pass over the rows settles, from 2**bits - 1
# candidates. Inside a `while` a pass reads the rows from HBM, so fewer,
# wider passes win until the compares outweigh the read: on a v5e 1 / 2 / 4
# / 8 bits take 521 / 330 / 326 / 3,278 us at [32, 65,536] and 142 / 95 /
# 114 / 705 at [8, 50,304] (PERF.md §6, PR 34).
_SEARCH_BITS = 2


def _largest_reaching(keys, weights, target):
    """Each row's largest int32 t with sum(weights[keys >= t]) >= target
    (`weights` None: the count of keys >= t). keys [b, V] order images,
    target [b] -> [b]. The sum falls as t rises, so t is built from its
    top bits down, `_SEARCH_BITS` a pass, in the unsigned image
    (t ^ INT_MIN) where "set a bit" is "go up": a pass weighs the
    candidates t | j << shift, rising with j, in one compare-and-reduce
    over V, and the j that still reach the target are a prefix. Where some
    key satisfies it the result is one of the row's keys; where none does
    it is INT_MIN."""
    js = jnp.arange(1, 1 << _SEARCH_BITS, dtype=jnp.int32)

    def step(i, t):
        shift = (32 - _SEARCH_BITS * (i + 1)).astype(jnp.int32)
        cand = t[:, None] | jnp.left_shift(js, shift)[None, :]   # [b, C]
        at = keys[:, None, :] >= (cand ^ _INT_MIN)[:, :, None]
        if weights is None:
            got = jnp.sum(at, axis=-1, dtype=jnp.int32)
        else:
            got = jnp.sum(jnp.where(at, weights[:, None, :], 0.0), axis=-1)
        reached = jnp.sum(got >= target[:, None], axis=-1, dtype=jnp.int32)
        return t | jnp.left_shift(reached, shift)

    t = jax.lax.fori_loop(0, 32 // _SEARCH_BITS, step,
                          jnp.zeros(keys.shape[:1], jnp.int32))
    return t ^ _INT_MIN


def _threshold_keys(scaled, k, topp, *, with_topk):
    """The order image [b] of the value under which a row's scaled logits
    [b, V] are cut: the larger of its k-th largest value (`with_topk`; an
    exact count) and its nucleus cut-off — the largest value v among the
    top-k survivors whose mass at or above v, sum(exp(scaled - max)),
    reaches topp of the survivors' (so the mass strictly above it does
    not: the smallest set reaching topp, ties at the cut-off all kept)."""
    keys = _order_flip(jax.lax.bitcast_convert_type(scaled, jnp.int32))
    if with_topk:
        kth = _largest_reaching(keys, None, k)
    else:
        kth = jnp.full(k.shape, _INT_MIN, jnp.int32)
    e = jnp.exp(scaled - jnp.max(scaled, axis=-1, keepdims=True))
    total = jnp.sum(jnp.where(keys >= kth[:, None], e, 0.0), axis=-1)
    return jnp.maximum(kth, _largest_reaching(keys, e, topp * total))


def _sample_thresholds(scaled, topks, topps, sampled):
    """[b] float32: the value a row keeps its scaled logits [b, V] at or
    above — top-k, then top-p over the survivors. `sampled` [b] marks the
    rows whose threshold will be used: where none of them asks for fewer
    than V, the top-k search is not run (the whole batch's, decided from
    its inputs: a branch per row under vmap would be a select)."""
    V = scaled.shape[-1]
    key = jax.lax.cond(
        jnp.any(sampled & (topks < V)),
        functools.partial(_threshold_keys, with_topk=True),
        functools.partial(_threshold_keys, with_topk=False),
        scaled, jnp.clip(topks, 1, V), topps)
    return jax.lax.bitcast_convert_type(_order_flip(key), jnp.float32)


def _sample_token(logits, temps, topks, topps, keys):
    """The rows' next tokens [b] from their logits [b, V]: a row's argmax
    where its temp == 0, else temperature / top-k / top-p with its raw
    uint32[2] PRNG key — one definition for every program body (a
    single-row body passes b = 1), ONE shape whatever the batch's
    sampling mix. The two thresholds are found by a search over values
    (`_sample_thresholds`), never by a sort: the mask is by VALUE, so the
    permutation was never used. The draw is `categorical` over the
    masked UNSORTED logits, a row at a time under its own key. The
    greedy value is computed first, from the logits as they came, and is
    what a temp == 0 row returns whichever branch the batch takes: it
    stays bitwise what the argmax-only program made. A batch with no
    sampled row runs the argmax alone."""
    with jax.named_scope("generate.sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = temps > 0.0

        def draw():
            scaled = (logits / jnp.maximum(temps, 1e-6)[:, None]).astype(
                jnp.float32)
            thr = _sample_thresholds(scaled, topks, topps, sampled)
            masked = jnp.where(scaled < thr[:, None], _NEG_INF, scaled)
            tok = jax.vmap(jax.random.categorical)(keys, masked)
            return jnp.where(sampled, tok.astype(jnp.int32), greedy)

        return jax.lax.cond(jnp.any(sampled), draw, lambda: greedy)


def _split_keys(keys):
    """Per-row split of raw uint32[2] keys [b, 2] -> (carry, use), each
    [b, 2]. vmapped so a row's chain is a pure function of its own key
    — independent of batch size, which is what makes sampled output
    identical across the batched and sequential paths."""
    kk = jax.vmap(lambda k: jax.random.split(k))(keys)
    return kk[:, 0], kk[:, 1]


class GPTPasses:
    """The step the GPT family supplies to the engine, from the two halves
    of models/gpt.py's block: a prefill of one prompt into a slot, and one
    pass of rows over the K/V pool (`_pool_pass`). A model of another kind
    supplies its own through its configuration's `serving_passes()` (models/
    lfm2.py::ServingPasses) — the one seam between the engine and a model:
    the geometry of the cache, `prefill`, `pool_pass`, `head`, and the
    engine features it `refuses` by name. `rec` is the fixed-size state a
    model keeps beside its K/V rows (`state_shape`); this family has none,
    and None rides the programs' signatures as an empty pytree."""

    name = "gpt"
    # `jit_gpt_<family>_c<cap>_b<bucket>` on the profiler's `XLA Modules`
    # line and in the compiled text (`_program`); the name is all of the
    # text that differs from the unnamed programs' (`jit__unknown`)
    program_prefix = "gpt"
    refuses: dict = {}
    state_dtype = None

    def __init__(self, cfg):
        self.cfg = cfg
        self.vocab_size = int(cfg.vocab_size)
        self.max_seq_len = int(cfg.max_seq_len)
        self.kv_dtype = "f32"
        self.kv_layers = int(cfg.num_layers)
        self.kv_heads = self.query_heads = int(cfg.num_heads)
        self.head_dim = int(cfg.hidden_size) // self.kv_heads
        self.eps = float(cfg.layer_norm_eps)

    def state_shape(self, rows: int):
        return None

    def state_step_bytes(self, real_rows: int, bucket: int) -> int:
        return 0

    def head(self, p, h):
        return _gpt.lm_head(p, h)

    def prefill(self, p, buf_k, buf_v, rec, slot, ids, length):
        return (*_gpt_prefill(p, buf_k, buf_v, slot, ids, length,
                              self.query_heads, self.eps), rec)

    def pool_pass(self, p, buf_k, buf_v, rec, slots, tokens, pos, scratch):
        return (*_pool_pass(p, buf_k, buf_v, slots, tokens, pos, scratch,
                            self.query_heads, self.eps), rec, None)


def _gpt_prefill(p, buf_k, buf_v, slot, ids, length, num_heads, eps):
    """GPT's full-prompt pass: causal attention within the (padded)
    prompt, per-layer K/V scattered into pool slot `slot`; returns the
    hidden state at position length-1 after the final norm, and the pools.
    Attention runs over the in-program full-precision K/V; only the POOL
    store quantizes (int8 pool), so the emitted first token is exact vs
    the float pool."""
    S = ids.shape[1]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = p["wte"][ids] + p["wpe"][pos][None]            # [1, S, D]
    causal = pos[None, :] <= pos[:, None]              # [S, S]

    def body(h, lp):
        q, k, v = _gpt.block_qkv(h, lp, num_heads, eps)
        qh = jnp.swapaxes(q, 1, 2)                     # [1, H, S, Dh]
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) \
            / math.sqrt(q.shape[-1])
        s = jnp.where(causal[None, None], s, _NEG_INF)
        att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh)
        h = _gpt.block_out(h, jnp.swapaxes(att, 1, 2).reshape(h.shape),
                           lp, eps)
        return h, (k[0], v[0])                         # [S, H, Dh]

    h, (ks, vs) = jax.lax.scan(body, x, _gpt.layer_stack(p))
    # ks/vs [L, S, H, Dh] -> pool rows are [L, cap, H, Dh]; positions
    # [length, S) hold junk from the pad — overwritten by the decode
    # steps before the mask (kpos <= length) ever admits them. An int8
    # pool resets the row's per-layer scale from this block's absmax.
    slot = slot.astype(jnp.int32)
    buf_k = _kvq.store_block(buf_k, slot, ks)
    buf_v = _kvq.store_block(buf_v, slot, vs)
    h = _gpt.layer_norm(h, p["lnf_w"], p["lnf_b"], eps)
    h_last = jax.lax.dynamic_index_in_dim(h[0], length - 1, axis=0,
                                          keepdims=False)     # [D]
    return h_last, buf_k, buf_v


def _prefill_body(p, buf_k, buf_v, slot, ids, length, temp, topk, topp,
                  key, rec=None, *, model):
    """One full-prompt pass, the model's own (`model.prefill`): the
    prompt's cache into slot `slot` — K/V rows and, where the model keeps
    one, the fixed-size state `rec` (last, so that a model without one
    has the signature it always had) — then the first token sampled (or
    argmax'd) from the logits at position length-1, one key split
    consumed. ids [1, S] int32."""
    p = _kvq.dequant_params(p)
    h_last, buf_k, buf_v, rec = model.prefill(p, buf_k, buf_v, rec, slot,
                                              ids, length)
    key, sub = jax.random.split(key)
    tok = _sample_token(model.head(p, h_last)[None], temp[None], topk[None],
                        topp[None], sub[None])[0]
    return tok, key, buf_k, buf_v, rec


def _kernel_read(q, buf_k, buf_v, layer, slots, pos, interpret=False):
    """pool_attention's read on a TPU: the Pallas kernel over the pool in
    HBM, blocks up to each row's position (ops/pallas/decode_attention).
    `interpret` is for the tests' CPU runs of the kernel."""
    b, _, H, Dh = q.shape
    return _dattn.decode_attention(
        q.reshape(b, H * Dh), buf_k, buf_v, layer, slots, pos[:, 0],
        num_heads=H, interpret=interpret)[:, None]


def _grouped_gather_read(q, buf_k, buf_v, layer, slots, pos):
    """`_gather_read` where several query heads share a K/V head (the pool
    holds Hkv < H heads): query head i on K/V head i // (H / Hkv); operands
    in the pool's type, scores and softmax in float32, the output in q's
    type, as the kernel gives it."""
    b, Q, H, Dh = q.shape
    M = _kvq.capacity(buf_k)
    k_l = _kvq.read_layer(buf_k, slots, layer)
    Hkv = k_l.shape[-1] // Dh
    k_l = k_l.reshape(b, M, Hkv, Dh)
    v_l = _kvq.read_layer(buf_v, slots, layer).reshape(b, M, Hkv, Dh)
    prec = jax.lax.Precision.HIGHEST if k_l.dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT
    qg = q.astype(k_l.dtype).reshape(b, Q, Hkv, H // Hkv, Dh)
    kpos = jnp.arange(M, dtype=jnp.int32)
    mask = kpos[None, None, :] <= pos[:, :, None]      # [b, Q, M]
    s = jnp.einsum("bqkgd,bmkd->bkgqm", qg, k_l, precision=prec,
                   preferred_element_type=jnp.float32) / math.sqrt(Dh)
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    att = jnp.einsum("bkgqm,bmkd->bqkgd",
                     jax.nn.softmax(s, -1).astype(v_l.dtype), v_l,
                     precision=prec, preferred_element_type=jnp.float32)
    return att.reshape(b, Q, H * Dh).astype(q.dtype)


def _gather_read(q, buf_k, buf_v, layer, slots, pos):
    """pool_attention's read in plain XLA: one layer of the rows
    (`buf[slots, layer]`, dequantized where the pool is int8) and a
    masked softmax over all M positions."""
    b, Q, H, Dh = q.shape
    M = _kvq.capacity(buf_k)
    if _kvq.row_width(buf_k) != H * Dh:
        return _grouped_gather_read(q, buf_k, buf_v, layer, slots, pos)
    k_l = _kvq.read_layer(buf_k, slots, layer).reshape(b, M, H, Dh)
    v_l = _kvq.read_layer(buf_v, slots, layer).reshape(b, M, H, Dh)
    kpos = jnp.arange(M, dtype=jnp.int32)
    mask = kpos[None, None, :] <= pos[:, :, None]      # [b, Q, M]
    s = jnp.einsum("bqhd,bmhd->bhqm", q, k_l) / math.sqrt(Dh)
    s = jnp.where(mask[:, None], s, _NEG_INF)
    att = jnp.einsum("bhqm,bmhd->bqhd", jax.nn.softmax(s, -1), v_l)
    return att.reshape(b, Q, H * Dh)


def _on_tpu(kernel, twin, *args):
    """`kernel` where the program is lowered for a TPU, `twin` elsewhere
    — decided at lowering, so a compile for a described chip takes the
    kernel although the host's default backend is a CPU."""
    return jax.lax.platform_dependent(*args, tpu=kernel, default=twin)


def _lowers_for_tpu(device) -> bool:
    """Whether a program placed on `device` is lowered for a TPU — what
    `_on_tpu` decides inside the trace, asked host-side for the step's
    counters and the program report."""
    return device.platform == "tpu"


# program families whose bodies attend over pool rows
_POOL_READERS = ("decode", "dpropose", "verify", "extend")


def kernel_plan(cap: int, kv_heads: int, head_dim: int, kv_dtype: str,
                query_heads: Optional[int] = None):
    """The decode kernel's plan for a pool of this geometry (`kv_heads`
    heads a position; `query_heads` of them attend, default as many), or
    None where one-query reads take the gather too: a pool that is not
    float, a shape the kernel does not serve."""
    if kv_dtype not in _kvq.POOL_ITEMSIZE:
        return None
    return _dattn.block_plan(cap, kv_heads, head_dim,
                             _kvq.POOL_ITEMSIZE[kv_dtype], query_heads)


def pool_attention(q, buf_k, buf_v, layer, slots, pos):
    """Attention of the queries q [b, Q, H, Dh] over the K/V pool where
    it lies: query (i, j) attends positions [0, pos[i, j]] of pool row
    slots[i] at `layer` (the new positions are in the pool already —
    write first, read after). Returns [b, Q, H*Dh]. One entry, two reads,
    chosen from what the code can observe: one query a row over a float
    pool takes the kernel on a TPU; every other lowering, an int8 pool
    and several queries a row take the gather."""
    _, Q, H, Dh = q.shape
    if Q != 1 or kernel_plan(_kvq.capacity(buf_k),
                             _kvq.row_width(buf_k) // Dh, Dh,
                             _kvq.kind(buf_k), H) is None:
        return _gather_read(q, buf_k, buf_v, layer, slots, pos)
    return _on_tpu(_kernel_read, _gather_read, q, buf_k, buf_v, layer,
                   slots, pos)


def _pool_writes(pos, slots, cap, scratch):
    """Where new K/V at the absolute positions `pos` (slots broadcast
    against it) land in the pool: a position past the class cap is
    redirected into the scratch row, never into a live slot."""
    safe = pos < cap
    return (jnp.where(safe, slots, jnp.int32(scratch)),
            jnp.where(safe, pos, 0))


def _pool_pass(p, buf_k, buf_v, slots, tokens, pos, scratch, num_heads,
               eps):
    """The one pass over rows of the pool that decode, the draft burst,
    verify and extend share: embed row i's tokens at their absolute
    positions pos (both [b], one query a row, or [b, Q]); per layer write
    the new K/V into the pool in place (a position past the class cap —
    a draft burst's or a bucket's overshoot — lands in the scratch row)
    and attend over pool row slots[i] up to each position, the new ones
    included: write first, read after, so that a block's causal mask sees
    its own positions bitwise as a later step would read them back (spec-on
    == spec-off under the int8 pool too). Returns the hidden states after
    the final norm, shaped like `tokens` + [D], and the pools. The scan
    over layers carries the two pools: nothing gathers, transposes or
    re-materializes pool rows. Rows are independent — padding rows target
    the scratch slot with length 0 and their outputs are discarded by the
    caller. `p` holds float weights: a caller dequantizes once
    (`kv.dequant_params`), for the pass and for its own head."""
    # one query a row carries no query axis through the matmuls
    one = tokens.ndim == 1
    x = p["wte"][tokens] + p["wpe"][jnp.minimum(
        pos, p["wpe"].shape[0] - 1)]                   # [b, (Q,) D]
    wslot, wpos = _pool_writes(pos, slots if one else slots[:, None],
                               _kvq.capacity(buf_k), scratch)
    qpos = pos[:, None] if one else pos                # [b, Q]

    def body(carry, xs):
        h, buf_k, buf_v = carry
        lp, layer = xs
        q, k_new, v_new = _gpt.block_qkv(h, lp, num_heads, eps)
        buf_k = _kvq.write_layer(buf_k, layer, wslot, wpos,
                                 k_new.reshape(h.shape))
        buf_v = _kvq.write_layer(buf_v, layer, wslot, wpos,
                                 v_new.reshape(h.shape))
        att = pool_attention(q[:, None] if one else q, buf_k, buf_v,
                             layer, slots, qpos)
        h = _gpt.block_out(h, att[:, 0] if one else att, lp, eps)
        return (h, buf_k, buf_v), None

    layers = _gpt.layer_stack(p)
    (h, buf_k, buf_v), _ = jax.lax.scan(
        body, (x, buf_k, buf_v),
        (layers, jnp.arange(layers.ln1_w.shape[0], dtype=jnp.int32)))
    return _gpt.layer_norm(h, p["lnf_w"], p["lnf_b"], eps), buf_k, buf_v


def _decode_body(p, buf_k, buf_v, slots, tokens, lengths, temps, topks,
                 topps, keys, rec=None, *, scratch, model):
    """One fixed-shape decode step: each row's pending token through the
    model's pass over the cache (`model.pool_pass`), plus the sampling
    head — one key split per row, greedy rows (temp 0) stay
    bitwise-identical to the argmax-only program. Returns the rows
    advanced — next token, key, length + 1 — in the shapes it took them,
    so the next step of the same rows takes its inputs from this one's
    outputs without a trip through the host; then the cache, and what the
    pass counted of itself (`aux`: a routed model's tokens per expert and
    distinct experts hit, read with the tokens; None for a dense one)."""
    p = _kvq.dequant_params(p)
    h, buf_k, buf_v, rec, aux = model.pool_pass(
        p, buf_k, buf_v, rec, slots, tokens, lengths, scratch)
    logits = model.head(p, h)
    keys, subs = _split_keys(keys)
    nxt = _sample_token(logits, temps, topks, topps, subs)
    return nxt, keys, lengths + 1, buf_k, buf_v, rec, aux


def _propose_body(p, buf_k, buf_v, slots, tokens, lengths, k, scratch,
                  num_heads, eps):
    """Draft proposal burst: k greedy decode steps fused into ONE
    program (lax.scan over steps) — a single dispatch proposes k tokens
    per row and leaves the draft pool's K/V advanced through all k
    consumed inputs (so a fully-accepted burst finds every cached
    position it needs on the next iteration)."""
    p = _kvq.dequant_params(p)

    def step(carry, _):
        toks, lens, bk, bv = carry
        h, bk, bv = _pool_pass(p, bk, bv, slots, toks, lens, scratch,
                               num_heads, eps)
        nxt = jnp.argmax(_gpt.lm_head(p, h), axis=-1).astype(jnp.int32)
        return (nxt, lens + 1, bk, bv), nxt

    (_, _, buf_k, buf_v), props = jax.lax.scan(
        step, (tokens, lengths, buf_k, buf_v), None, length=k)
    return jnp.swapaxes(props, 0, 1), buf_k, buf_v     # [b, k]


def _verify_body(p, buf_k, buf_v, slots, tokens, lengths, temps, topks,
                 topps, keys, scratch, num_heads, eps):
    """Speculative verification: tokens [b, k] are each row's pending
    token followed by k-1 draft proposals; ONE pool pass computes the
    target's own token at every position — sampled with exactly the key
    chain the plain decode path would consume, one split per position —
    and returns the per-position tokens plus the key chain [b, k, 2] so
    the host can accept the longest agreed prefix and carry the key
    advanced by as many splits as tokens it emitted."""
    p = _kvq.dequant_params(p)
    kk = tokens.shape[1]
    pos = lengths[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
    h, buf_k, buf_v = _pool_pass(p, buf_k, buf_v, slots, tokens, pos,
                                 scratch, num_heads, eps)
    logits = _gpt.lm_head(p, h)                        # [b, k, V]
    subs, hist = [], []
    cur = keys
    for _ in range(kk):
        cur, sub = _split_keys(cur)
        subs.append(sub)
        hist.append(cur)
    b = tokens.shape[0]
    # the b * k positions are rows of ONE sampler call, row-major [b, k]
    ys = _sample_token(
        logits.reshape(b * kk, -1), jnp.repeat(temps, kk),
        jnp.repeat(topks, kk), jnp.repeat(topps, kk),
        jnp.stack(subs, axis=1).reshape(b * kk, 2)).reshape(b, kk)
    khist = jnp.stack(hist, axis=1)                    # [b, k, 2]
    return ys, khist, buf_k, buf_v


def _extend_body(p, buf_k, buf_v, slot, ids, start, length, temp, topk,
                 topp, key, scratch, num_heads, eps):
    """Prefix-cache tail prefill: slot already holds valid K/V for
    positions [0, start); the T-token tail block ids [1, T] goes through
    the pool pass as one row's T queries at [start, start+T), and the
    first token comes from the hidden state at absolute position
    length-1. An int8 pool KEEPS the row's scale (set by the cached
    prefix's original prefill): tail positions quantize with it, clip
    semantics — the scale-granularity error source DESIGN.md documents."""
    p = _kvq.dequant_params(p)
    pos = start + jnp.arange(ids.shape[1], dtype=jnp.int32)   # absolute
    h, buf_k, buf_v = _pool_pass(
        p, buf_k, buf_v, slot.astype(jnp.int32)[None], ids, pos[None],
        scratch, num_heads, eps)
    h_last = jax.lax.dynamic_index_in_dim(h[0], length - 1 - start,
                                          axis=0, keepdims=False)
    key, sub = jax.random.split(key)
    tok = _sample_token(_gpt.lm_head(p, h_last)[None], temp[None],
                        topk[None], topp[None], sub[None])[0]
    return tok, key, buf_k, buf_v


def _copy_row_body(buf_k, buf_v, src, dst):
    """One pool-row copy (prefix-cache admit / hit): dst row becomes a
    snapshot of src — for an int8 pool, raw int8 plus the scale row
    (bit-exact; cached rows never requantize). Jitted per class so the
    workload never leans on eager per-op dispatch (the persistent-
    miss==0 contract)."""
    return (_kvq.copy_row(buf_k, src, dst),
            _kvq.copy_row(buf_v, src, dst))


def _kvget_body(buf_k, buf_v, slot):
    """KV-slot export read (disaggregated serving): pool row `slot` of
    both buffers RAW in the stored dtype — int8 rows come out as int8
    plus their per-layer scale, never a dequantization. Returns
    (k_data, k_scale|None, v_data, v_scale|None)."""
    kd, ks = _kvq.row_raw(buf_k, slot)
    vd, vs = _kvq.row_raw(buf_v, slot)
    return kd, ks, vd, vs


def _kvput_body(buf_k, buf_v, slot, kd, ks, vd, vs):
    """KV-slot import write: scatter raw row bytes (the _kvget_body
    counterpart, shipped from another host) into pool row `slot` —
    bit-exact like a pcopy, never a requantization. ks/vs are None for
    the float pool (None is an empty pytree, so the jitted signature
    stays one program per (cap, kv_dtype))."""
    return (_kvq.set_row_raw(buf_k, slot, kd, ks),
            _kvq.set_row_raw(buf_v, slot, vd, vs))


# what a pass that admits nothing runs under: no span, tracing on or off
_NO_SPAN = contextlib.nullcontext()


def _read_args(phase: Optional[dict], step: int) -> Optional[dict]:
    """The worker loop's span args (None with tracing off) for the `.wait`
    that reads step `step` and the `generate.emit` that follows it — a
    dictionary of the pair's own: the emit adds what it measured."""
    return None if phase is None else {**phase, "read_step": step}


def _named(fn, name: str):
    """`fn` under a name jax can give the jitted program."""
    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return call


def stack_gpt_params(model) -> Tuple[dict, object]:
    """A GPTForCausalLM / GPTForCausalLMScan's weights as the [L, ...]
    param dict the generation programs scan over (REAL copies — a
    donated train step elsewhere must not kill the serving arrays).
    Returns (params, cfg)."""
    if not isinstance(model, (_gpt.GPTForCausalLM,
                              _gpt.GPTForCausalLMScan)):
        raise TypeError(
            f"GenerativeEngine wants a GPTForCausalLM[Scan] (or a "
            f"(params, cfg) pair via params=); got {type(model).__name__}")
    return model.stacked_params(), model.cfg


# ===================================================================
# request / handle
# ===================================================================
@_shared_state("tokens", "streamed", "owner", "requeues", "t_first",
               "handoff")
class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos", "future", "stream",
                 "deadline", "t_enqueue", "t_enq_ns", "ctx", "requeues",
                 "tokens", "streamed", "owner", "t_first",
                 "temperature", "top_k", "top_p", "seed",
                 "prefill_only", "handoff")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos: Optional[int], deadline: Optional[float],
                 temperature: float = 0.0, top_k: int = 1,
                 top_p: float = 1.0, seed: int = 0):
        self.prompt = prompt                  # np.int32 [P]
        self.max_new = int(max_new)
        self.eos = eos
        # immutable for the request's lifetime (requeue replays the
        # same chain from the same seed)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.seed = int(seed)
        self.future = Future()
        self.stream: Queue = Queue()
        self.deadline = deadline
        self.t_enqueue = time.monotonic()
        self.t_enq_ns = time.perf_counter_ns()
        self.ctx = None
        self.requeues = 0
        self.tokens: List[int] = []   # regenerated from scratch on requeue
        self.streamed = 0             # tokens already delivered downstream
        self.owner = None             # (rid, generation) while in a slot
        self.t_first: Optional[float] = None
        # disaggregated serving: prefill_only finishes with a KV-slot
        # export instead of decoding here; handoff carries a decoded
        # (meta, arrays) payload to import instead of prefilling
        self.prefill_only = False
        self.handoff: Optional[tuple] = None


class GenerateHandle:
    """Client handle for one generation: iterate tokens as they stream,
    or block on ``result()`` for the whole thing. Events on the stream
    queue are ('tok', id) / ('done', info) / ('err', exc)."""

    def __init__(self, req: _GenRequest):
        self._req = req
        self.future = req.future

    def __iter__(self):
        for kind, val in self.events():
            if kind == "tok":
                yield int(val)

    def events(self):
        """Raw event stream: ('tok', id)*, then ('done', info) — the
        server's chunked encoder wants the final info dict too. A
        drain-with-migration ends the LOCAL stream with ('handoff',
        payload) instead of 'done': the fabric layer re-homes the slot
        and the client keeps streaming from the importer. An
        ('err', exc) event raises."""
        while True:
            kind, val = self._req.stream.get()
            if kind == "err":
                raise val
            yield kind, val
            if kind in ("done", "handoff"):
                return

    def result(self, timeout: Optional[float] = None) -> dict:
        """{"tokens": [...], "n_tokens": int, "ttft_ms": float,
        "finish_reason": "eos"|"length"}."""
        return self.future.result(timeout)


class _Row:
    __slots__ = ("req", "slot", "length", "key")

    def __init__(self, req: _GenRequest, slot: int, length: int,
                 key: Optional[np.ndarray] = None):
        self.req = req
        self.slot = slot
        self.length = length   # cached positions; pending tok = tokens[-1]
        # the row's CURRENT raw uint32[2] PRNG key — advanced one split
        # per emitted token (prefill consumed the first split)
        self.key = key if key is not None else np.zeros(2, np.uint32)


# Decode steps the worker launches beyond the oldest one it has not read.
# One: with step n+1 queued behind it the device goes from step n straight
# into n+1 while the host reads n, emits it and launches n+2 — 1 ms of
# host work and 2 ms of late notice against a program of 4.1 ms at
# gpt3-medium on a v5e (PERF.md §5: the steady pass is the device's), so
# one step ahead keeps the device fed. A second would only put every EOS
# and every stream another step behind.
_STEPS_AHEAD = 1


class _Launched:
    """One decode step on the device's queue that the host has not read:
    its rows in launch order, what they produce (device futures) and what
    the step's counters need once the rows are known to be real."""

    __slots__ = ("step", "rows", "prog_key", "kv_reads", "ahead", "nxt",
                 "nkeys", "aux")

    def __init__(self, step, rows, prog_key, kv_reads, ahead, nxt, nkeys,
                 aux=None):
        self.step = step              # the worker's number for it, at launch
        self.rows = rows
        self.prog_key = prog_key      # (device, "decode", cap, bucket)
        self.kv_reads = kv_reads      # per row: pool positions its read copies
        self.ahead = ahead            # an earlier step was still unread
        self.nxt = nxt
        self.nkeys = nkeys
        self.aux = aux                # what the pass counted of itself


class _DeviceRows:
    """A class's plain-decode state where the device keeps it. `rows[i]`
    is index i of every array, padded to the batch bucket with scratch rows.
    `fixed` — slots, temperatures, top-k, top-p — is what only the host
    knows and changes only with the row set; `toks`, `lens`, `keys` are
    each row's pending token, length and PRNG key as the last launched
    step returned them (futures while it runs). `unread` holds the
    launched steps the host has not read, oldest first: the host's
    row.length / row.key / req.tokens are current as of the last step
    READ, `len(unread)` steps behind the device. Owned by the worker
    thread; dies with the class state."""

    __slots__ = ("rows", "fixed", "toks", "lens", "keys", "unread")

    def __init__(self, rows, fixed, toks, lens, keys):
        self.rows = rows
        self.fixed = fixed
        self.toks = toks
        self.lens = lens
        self.keys = keys
        self.unread: "deque[_Launched]" = deque()

    def holds(self, rows) -> bool:
        return len(rows) == len(self.rows) and all(
            a is b for a, b in zip(rows, self.rows))


@_shared_state("free", "rows", "pcache", "pc_free")
class _ClassState:
    """Per-worker, per-capacity-class device state: the pool buffer
    pair, the slot free list, and the live rows (free/rows are
    racecheck-designated: the owning worker and the schedulers' admit/
    finish/fail paths share them under the engine lock). With
    speculation a second (cheaper-geometry) buffer pair holds the draft
    model's K/V for the same slots; with prefix caching the pool is
    allocated with ``pc_slots`` extra rows addressed by the LRU
    ``pcache`` — cache state dies with the worker generation exactly
    like the buffers (a fresh _ClassState is allocated on revive).
    `dev` is the plain-decode loop's device-held rows (None until its
    first step, and always with a draft model). `rec` is the fixed-size
    state a model keeps beside its K/V rows (`model.state_shape`: one row
    a slot, the scratch row included, in the model's `state_dtype`), None
    for a model that has none; it is allocated, donated, carried and
    dropped with the pools. A model with no attention layer has no pools:
    `buf_k` / `buf_v` are None and `rec` is its whole cache.

    `read_at`, `admits`, `restaged` are what `_emit_step` names the gap
    between two step reads from, the worker thread's own as `dev` is: when
    the class's last step was read (`time.monotonic()`; None while the
    class has held no row since), how many prefills / imports ran for it
    since, and whether its rows were staged anew since."""

    __slots__ = ("cap", "n_slots", "buf_k", "buf_v", "rec", "free", "rows",
                 "pc_slots", "pcache", "pc_free", "dbuf_k", "dbuf_v",
                 "dev", "read_at", "admits", "restaged")

    def __init__(self, cap: int, n_slots: int, buf_k, buf_v,
                 pc_slots: int = 0, dbuf_k=None, dbuf_v=None, rec=None):
        self.cap = cap
        self.n_slots = n_slots
        self.buf_k = buf_k
        self.buf_v = buf_v
        self.rec = rec
        self.free: List[int] = list(range(n_slots))
        self.rows: Dict[int, _Row] = {}
        self.pc_slots = int(pc_slots)
        # (prefix_len, blake2b hex) -> pool row index; insertion order
        # IS recency order (move_to_end on hit, popitem(last=False)
        # evicts the coldest)
        self.pcache: "OrderedDict[tuple, int]" = OrderedDict()
        self.pc_free: List[int] = list(
            range(n_slots + 1, n_slots + 1 + self.pc_slots))
        self.dbuf_k = dbuf_k
        self.dbuf_v = dbuf_v
        self.dev: Optional[_DeviceRows] = None
        self.read_at: Optional[float] = None
        self.admits = 0
        self.restaged = False

    def admitted(self) -> None:
        """A prefill or an import ran for this class (the worker thread,
        under the engine lock, before the row goes into `rows`): the next
        step read follows an admission — or, where the class held no row,
        follows nothing."""
        if not self.rows:
            self.read_at = None
        self.admits += 1


# ===================================================================
# metrics
# ===================================================================
# What the worker did between two reads of a class's steps, as `_emit_step`
# derives it: launched the step from the last one's outputs and nothing
# else ("steady"), staged the rows anew because one finished or migrated
# ("rowset"), or ran at least one prefill or import ("admission").
STEP_GAP_CAUSES = ("steady", "rowset", "admission")


def track_engine(engine) -> None:
    _REGISTRY.track(engine)


def aggregate_snapshot() -> Optional[dict]:
    """Merged generation digest over live engines (None = never ran)."""
    snaps = _REGISTRY.snapshots()
    if not snaps:
        return None
    if len(snaps) == 1:
        return snaps[0]
    out = dict(snaps[0])
    for s in snaps[1:]:
        for k, v in s.items():
            if not (isinstance(v, (int, float)) and
                    isinstance(out.get(k), (int, float))):
                continue
            if k == "max_slot_occupancy":
                # a maximum merges as a maximum — summing would report
                # an occupancy no single engine ever reached
                out[k] = max(out[k], v)
            elif k.startswith("kv_positions_") or not (
                    k.startswith(("ttft_", "latency_", "kv_", "avg_"))
                    or k.endswith(("_rate", "_share"))):
                out[k] = out[k] + v
    out["kv_read_share"] = _sm.rate(out["kv_positions_read_total"],
                                    out["kv_positions_capacity_total"])
    out["launch_ahead_share"] = _sm.rate(out["steps_ahead_total"],
                                         out["steps_total"])
    out["engines"] = len(snaps)
    return out


_REGISTRY = _sm.EngineRegistry("generative", aggregate_snapshot)


@_shared_state("requests_total", "completed_total", "failed_total",
               "shed_total", "rejected_total", "requeues_total",
               "tokens_out_total", "prompt_tokens_total",
               "prefills_total", "steps_total", "steps_ahead_total",
               "step_rows_total", "step_padded_rows_total",
               "occupancy_hist", "_ttft",
               "_latency", "_token_stamps", "draft_steps_total",
               "spec_steps_total", "spec_proposed_total",
               "spec_accepted_total", "prefix_hits_total",
               "prefix_misses_total", "prefix_evictions_total",
               "prefix_tokens_reused_total", "handoffs_out_total",
               "handoffs_in_total", "migrations_out_total",
               "handoff_bytes_total", "kv_positions_read_total",
               "kv_positions_capacity_total", "state_bytes_moved_total",
               "step_gaps_total", "step_gap_seconds_total",
               "step_gap_tokens_total", "moe_assignments_total",
               "moe_expert_tokens", "moe_distinct_experts_total",
               "moe_layer_steps_total")
class GenerativeMetrics:
    """Thread-safe metric store for one GenerativeEngine: the four
    numbers a generation tier is judged by — tokens/s, TTFT, decode
    slot occupancy, KV-pool utilization — plus the request counters the
    autoscaler policy reads (shed_total, latency percentiles)."""

    def __init__(self, ring: int = 4096, window_s: float = 30.0):
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self._window = float(window_s)
        self.requests_total = 0
        self.completed_total = 0
        self.failed_total = 0
        self.shed_total = 0
        self.rejected_total: Dict[str, int] = {}
        self.requeues_total = 0
        self.tokens_out_total = 0
        self.prompt_tokens_total = 0
        self.prefills_total = 0
        self.steps_total = 0
        self.steps_ahead_total = 0        # launched, an earlier one unread
        self.step_rows_total = 0          # real rows over all steps
        self.step_padded_rows_total = 0   # pad rows added by batch bucket
        self.kv_positions_read_total = 0      # positions the steps read
        self.kv_positions_capacity_total = 0  # real rows x class cap
        self.state_bytes_moved_total = 0  # fixed-size state, read + written
        # the gap between two reads of a class's steps, by its cause: every
        # live row of a step gets its token(s) at the read, so weighted by
        # tokens this is the engine's own inter-token gap
        self.step_gaps_total = dict.fromkeys(STEP_GAP_CAUSES, 0)
        self.step_gap_seconds_total = dict.fromkeys(STEP_GAP_CAUSES, 0.0)
        self.step_gap_tokens_total = dict.fromkeys(STEP_GAP_CAUSES, 0)
        self.draft_steps_total = 0        # fused k-step draft bursts
        self.spec_steps_total = 0         # target verify passes
        self.spec_proposed_total = 0      # draft tokens offered (k-1/row)
        self.spec_accepted_total = 0      # draft tokens accepted
        self.prefix_hits_total = 0
        self.prefix_misses_total = 0
        self.prefix_evictions_total = 0
        self.prefix_tokens_reused_total = 0   # prompt tokens not re-prefilled
        self.handoffs_out_total = 0       # KV slots exported (all causes)
        self.handoffs_in_total = 0        # KV slots imported
        self.migrations_out_total = 0     # exports caused by drain-migrate
        self.handoff_bytes_total = 0      # wire bytes, both directions
        # a routed model's decode steps (none of a dense model's)
        self.moe_assignments_total = 0    # (token, expert) pairs routed
        self.moe_expert_tokens: List[int] = []   # of them, by expert
        self.moe_distinct_experts_total = 0   # experts hit, a layer-step
        self.moe_layer_steps_total = 0    # expert layers x decode steps
        self.occupancy_hist: Dict[int, int] = {}   # active rows -> steps
        self._ttft = deque(maxlen=int(ring))       # seconds
        self._latency = deque(maxlen=int(ring))    # request total seconds
        self._token_stamps = deque(maxlen=65536)   # (monotonic, n)
        self.queue_depth_fn = lambda: 0
        self.replicas_fn = lambda: 0
        self.kv_util_fn = lambda: {"slots_used": 0, "slots_total": 0,
                                   "positions_used": 0,
                                   "positions_total": 0}
        self.quant_flags_fn = lambda: (0, 0)   # (kv int8?, weights int8?)

    # ------------------------------------------------------------ record --
    def on_accept(self):
        with self._lock:
            self.requests_total += 1

    def on_reject(self, reason: str):
        with self._lock:
            self.rejected_total[reason] = \
                self.rejected_total.get(reason, 0) + 1

    def on_shed(self):
        with self._lock:
            self.shed_total += 1

    def on_failed(self, n: int = 1):
        with self._lock:
            self.failed_total += n

    def on_requeue(self, n: int = 1):
        with self._lock:
            self.requeues_total += n

    def on_prefill(self, prompt_tokens: int):
        with self._lock:
            self.prefills_total += 1
            self.prompt_tokens_total += prompt_tokens

    def on_step(self, rows: int, bucket: int, kv_read: int = 0,
                kv_capacity: int = 0, ahead: bool = False,
                state_bytes: int = 0, cause: Optional[str] = None,
                gap_s: float = 0.0, tokens: int = 0):
        """One decode step of `rows` real rows in a batch bucket;
        `kv_read` of the rows' `kv_capacity` (rows x class cap) pool
        positions were read by the step's attention, and `state_bytes` of
        the model's fixed-size state were read and written. `ahead`: it
        was launched while an earlier step was still unread. `cause` (one
        of STEP_GAP_CAUSES; None for the first read of a class that held
        no row, which follows no read): what the worker did in the `gap_s`
        seconds since the class's previous step was read, and `tokens`
        the rows got at this one."""
        with self._lock:
            if cause is not None:
                self.step_gaps_total[cause] += 1
                self.step_gap_seconds_total[cause] += gap_s
                self.step_gap_tokens_total[cause] += tokens
            self.steps_total += 1
            self.steps_ahead_total += bool(ahead)
            self.step_rows_total += rows
            self.step_padded_rows_total += max(bucket - rows, 0)
            self.kv_positions_read_total += int(kv_read)
            self.kv_positions_capacity_total += int(kv_capacity)
            self.state_bytes_moved_total += int(state_bytes)
            self.occupancy_hist[rows] = \
                self.occupancy_hist.get(rows, 0) + 1

    def on_experts(self, per_expert, distinct: int, layers: int):
        """One decode step of a routed model: its tokens per expert summed
        over the `layers` expert layers, and the distinct experts it hit
        summed over them — as the program counted them."""
        counts = [int(c) for c in per_expert]
        with self._lock:
            if not self.moe_expert_tokens:
                self.moe_expert_tokens = [0] * len(counts)
            for e, c in enumerate(counts):
                self.moe_expert_tokens[e] += c
            self.moe_assignments_total += sum(counts)
            self.moe_distinct_experts_total += int(distinct)
            self.moe_layer_steps_total += int(layers)

    def on_spec_step(self, proposed: int, accepted: int):
        """One draft burst + one verify pass over the batch: `proposed`
        is the draft tokens offered ((k-1) per real row), `accepted`
        how many the target agreed to keep."""
        with self._lock:
            self.draft_steps_total += 1
            self.spec_steps_total += 1
            self.spec_proposed_total += int(proposed)
            self.spec_accepted_total += int(accepted)

    def on_prefix(self, hit: bool, tokens_reused: int = 0):
        with self._lock:
            if hit:
                self.prefix_hits_total += 1
                self.prefix_tokens_reused_total += int(tokens_reused)
            else:
                self.prefix_misses_total += 1

    def on_prefix_evict(self):
        with self._lock:
            self.prefix_evictions_total += 1

    def on_handoff_out(self, nbytes: int, migrated: bool = False):
        with self._lock:
            self.handoffs_out_total += 1
            self.handoff_bytes_total += int(nbytes)
            if migrated:
                self.migrations_out_total += 1

    def on_handoff_in(self, nbytes: int):
        with self._lock:
            self.handoffs_in_total += 1
            self.handoff_bytes_total += int(nbytes)

    def _evict_locked(self, now: float):
        horizon = now - self._window
        while self._token_stamps and self._token_stamps[0][0] < horizon:
            self._token_stamps.popleft()

    def on_tokens(self, n: int):
        now = time.monotonic()
        with self._lock:
            self.tokens_out_total += n
            self._evict_locked(now)
            self._token_stamps.append((now, n))

    def on_first_token(self, ttft_s: float):
        with self._lock:
            self._ttft.append(float(ttft_s))

    def on_complete(self, latency_s: float):
        with self._lock:
            self.completed_total += 1
            self._latency.append(float(latency_s))

    # ------------------------------------------------------------- query --
    _pcts = staticmethod(_sm.percentiles)

    def latency_percentiles(self) -> Dict[str, float]:
        with self._lock:
            return self._pcts(self._latency)

    def ttft_percentiles(self) -> Dict[str, float]:
        with self._lock:
            return self._pcts(self._ttft)

    def tokens_per_s(self) -> float:
        now = time.monotonic()
        with self._lock:
            self._evict_locked(now)
            n = sum(c for _, c in self._token_stamps)
        window = min(self._window, max(now - self._t0, 1e-9))
        return n / window

    def max_occupancy(self) -> int:
        with self._lock:
            return max(self.occupancy_hist) if self.occupancy_hist else 0

    def snapshot(self) -> dict:
        ttft = self.ttft_percentiles()
        lat = self.latency_percentiles()
        # gauge callbacks BEFORE our lock: replicas_fn holds the engine
        # cv, which engine record paths hold while calling into us —
        # callback-inside-lock is a lock-order cycle (lockcheck-caught)
        queue_depth = int(self.queue_depth_fn())
        replicas = int(self.replicas_fn())
        quant_kv, quant_w = self.quant_flags_fn()
        with self._lock:
            occ_n = sum(k * v for k, v in self.occupancy_hist.items())
            occ_d = sum(self.occupancy_hist.values())
            out = {
                "requests_total": self.requests_total,
                "completed_total": self.completed_total,
                "failed_total": self.failed_total,
                "shed_total": self.shed_total,
                "rejected_total": sum(self.rejected_total.values()),
                "requeues_total": self.requeues_total,
                "tokens_out_total": self.tokens_out_total,
                "prompt_tokens_total": self.prompt_tokens_total,
                "prefills_total": self.prefills_total,
                "steps_total": self.steps_total,
                "steps_ahead_total": self.steps_ahead_total,
                "launch_ahead_share": _sm.rate(self.steps_ahead_total,
                                               self.steps_total),
                "step_rows_total": self.step_rows_total,
                "step_padded_rows_total": self.step_padded_rows_total,
                "kv_positions_read_total": self.kv_positions_read_total,
                "kv_positions_capacity_total":
                    self.kv_positions_capacity_total,
                "kv_read_share": _sm.rate(
                    self.kv_positions_read_total,
                    self.kv_positions_capacity_total),
                "state_bytes_moved_total": self.state_bytes_moved_total,
                # flat scalars: aggregate_snapshot sums those, and a
                # reader subtracts two snapshots key by key
                **{f"step_gaps_{c}_total": n
                   for c, n in self.step_gaps_total.items()},
                **{f"step_gap_seconds_{c}_total": n
                   for c, n in self.step_gap_seconds_total.items()},
                **{f"step_gap_tokens_{c}_total": n
                   for c, n in self.step_gap_tokens_total.items()},
                "draft_steps_total": self.draft_steps_total,
                "spec_steps_total": self.spec_steps_total,
                "spec_proposed_total": self.spec_proposed_total,
                "spec_accepted_total": self.spec_accepted_total,
                "spec_accept_rate": _sm.rate(self.spec_accepted_total,
                                             self.spec_proposed_total),
                "prefix_hits_total": self.prefix_hits_total,
                "prefix_misses_total": self.prefix_misses_total,
                "prefix_evictions_total": self.prefix_evictions_total,
                "prefix_tokens_reused_total":
                    self.prefix_tokens_reused_total,
                "handoffs_out_total": self.handoffs_out_total,
                "handoffs_in_total": self.handoffs_in_total,
                "migrations_out_total": self.migrations_out_total,
                "handoff_bytes_total": self.handoff_bytes_total,
                "moe_assignments_total": self.moe_assignments_total,
                "moe_expert_tokens": list(self.moe_expert_tokens),
                "moe_distinct_experts_total":
                    self.moe_distinct_experts_total,
                "moe_layer_steps_total": self.moe_layer_steps_total,
                "prefix_hit_rate": _sm.rate(
                    self.prefix_hits_total,
                    self.prefix_hits_total + self.prefix_misses_total),
                "avg_slot_occupancy": round(occ_n / occ_d, 3)
                if occ_d else 0.0,
                "max_slot_occupancy": max(self.occupancy_hist)
                if self.occupancy_hist else 0,
                "occupancy_hist": dict(sorted(self.occupancy_hist.items())),
                "queue_depth": queue_depth,
                "replicas": replicas,
                "quant_kv_enabled": int(quant_kv),
                "quant_weights_enabled": int(quant_w),
            }
        out["kv_pool"] = dict(self.kv_util_fn())
        tot = out["kv_pool"].get("positions_total") or 0
        used = out["kv_pool"].get("positions_used") or 0
        if not tot:
            # a cache with no positions (a fixed-size state a slot): what
            # fills it is its slots
            tot = out["kv_pool"].get("slots_total") or 0
            used = out["kv_pool"].get("slots_used") or 0
        out["kv_pool"]["utilization"] = round(used / tot, 4) if tot else 0.0
        out["ttft_ms"] = {k: round(v * 1e3, 3) for k, v in ttft.items()}
        out["latency_ms"] = {k: round(v * 1e3, 3) for k, v in lat.items()}
        out["tokens_per_s"] = round(self.tokens_per_s(), 3)
        return out

    def prometheus_text(self) -> str:
        s = self.snapshot()
        lines: List[str] = []

        def metric(name, mtype, value, help_):
            lines.append(f"# HELP {name} {help_}")
            lines.append(f"# TYPE {name} {mtype}")
            lines.append(f"{name} {value}")

        metric("paddle_generate_requests_total", "counter",
               s["requests_total"], "generation requests accepted")
        metric("paddle_generate_completed_total", "counter",
               s["completed_total"], "generations completed")
        metric("paddle_generate_failed_total", "counter",
               s["failed_total"], "generations failed at runtime")
        metric("paddle_generate_shed_total", "counter", s["shed_total"],
               "generation requests shed by the circuit breaker (503)")
        metric("paddle_generate_tokens_total", "counter",
               s["tokens_out_total"], "tokens generated")
        metric("paddle_generate_steps_total", "counter", s["steps_total"],
               "decode steps executed")
        metric("paddle_generate_steps_ahead_total", "counter",
               s["steps_ahead_total"],
               "decode steps launched while an earlier one was unread")
        metric("paddle_generate_launch_ahead_share", "gauge",
               s["launch_ahead_share"],
               "steps launched ahead / decode steps (lifetime)")
        metric("paddle_generate_prefills_total", "counter",
               s["prefills_total"], "prefill calls executed")
        metric("paddle_generate_queue_depth", "gauge", s["queue_depth"],
               "generation queue depth")
        metric("paddle_generate_replicas", "gauge", s["replicas"],
               "active decode workers")
        metric("paddle_generate_tokens_per_s", "gauge", s["tokens_per_s"],
               "tokens/sec over the sliding window")
        metric("paddle_generate_kv_pool_utilization", "gauge",
               s["kv_pool"]["utilization"],
               "fraction of the cache holding live sequences: K/V "
               "positions, or slots where the cache has no positions")
        metric("paddle_generate_kv_positions_read_total", "counter",
               s["kv_positions_read_total"],
               "KV-pool positions the decode steps' attention read")
        metric("paddle_generate_kv_positions_capacity_total", "counter",
               s["kv_positions_capacity_total"],
               "decoded rows times their class capacity")
        metric("paddle_generate_kv_read_share", "gauge",
               s["kv_read_share"],
               "positions read / capacity over the decode steps (lifetime)")
        metric("paddle_generate_state_bytes_moved_total", "counter",
               s["state_bytes_moved_total"],
               "bytes of fixed-size state the decode steps read and wrote")
        for name, help_ in (
                ("step_gaps",
                 "reads of a decode step that followed another read of its "
                 "class, by what the worker did between them"),
                ("step_gap_seconds",
                 "seconds between two reads of a class's decode steps"),
                ("step_gap_tokens",
                 "tokens the rows got at the read that closed the gap")):
            lines.append(f"# HELP paddle_generate_{name}_total {help_}")
            lines.append(f"# TYPE paddle_generate_{name}_total counter")
            for c in STEP_GAP_CAUSES:
                lines.append(f'paddle_generate_{name}_total{{cause="{c}"}} '
                             f'{s[f"{name}_{c}_total"]}')
        metric("paddle_generate_kv_pool_bytes", "gauge",
               s["kv_pool"].get("pool_bytes", 0),
               "cache bytes across active replicas: K/V pools, a fixed-size "
               "state, or both")
        metric("paddle_generate_quant_kv_enabled", "gauge",
               s["quant_kv_enabled"],
               "1 when the engine's KV pool is int8-quantized")
        metric("paddle_generate_quant_weights_enabled", "gauge",
               s["quant_weights_enabled"],
               "1 when the engine serves weight-only int8 replicas")
        metric("paddle_generate_slot_occupancy_avg", "gauge",
               s["avg_slot_occupancy"],
               "mean active rows per executed decode step")
        metric("paddle_generate_spec_steps_total", "counter",
               s["spec_steps_total"],
               "speculative verify passes executed")
        metric("paddle_generate_spec_accepted_total", "counter",
               s["spec_accepted_total"],
               "draft-proposed tokens accepted by the target")
        metric("paddle_generate_spec_accept_rate", "gauge",
               s["spec_accept_rate"],
               "accepted / proposed draft tokens (lifetime)")
        metric("paddle_generate_prefix_hits_total", "counter",
               s["prefix_hits_total"],
               "prefills served from the prefix cache")
        metric("paddle_generate_prefix_misses_total", "counter",
               s["prefix_misses_total"],
               "prefills with no cached prefix")
        metric("paddle_generate_prefix_tokens_reused_total", "counter",
               s["prefix_tokens_reused_total"],
               "prompt tokens NOT re-prefilled thanks to the cache")
        metric("paddle_generate_handoffs_out_total", "counter",
               s["handoffs_out_total"],
               "KV slots exported for cross-host handoff")
        metric("paddle_generate_handoffs_in_total", "counter",
               s["handoffs_in_total"],
               "KV slots imported from another host")
        metric("paddle_generate_migrations_out_total", "counter",
               s["migrations_out_total"],
               "in-flight streams migrated out on drain")
        metric("paddle_generate_handoff_bytes_total", "counter",
               s["handoff_bytes_total"],
               "handoff wire bytes, exports plus imports")
        metric("paddle_generate_moe_assignments_total", "counter",
               s["moe_assignments_total"],
               "(token, expert) pairs the decode steps routed")
        metric("paddle_generate_moe_distinct_experts_total", "counter",
               s["moe_distinct_experts_total"],
               "distinct experts hit, summed over expert layers and steps")
        metric("paddle_generate_moe_layer_steps_total", "counter",
               s["moe_layer_steps_total"],
               "expert layers times decode steps")
        lines.append("# HELP paddle_generate_moe_expert_tokens_total "
                     "tokens the decode steps routed to each expert")
        lines.append("# TYPE paddle_generate_moe_expert_tokens_total counter")
        for e, n in enumerate(s["moe_expert_tokens"]):
            lines.append(f'paddle_generate_moe_expert_tokens_total'
                         f'{{expert="{e}"}} {n}')
        lines.append("# HELP paddle_generate_ttft_seconds time-to-first-"
                     "token quantiles over the recent-sample ring")
        lines.append("# TYPE paddle_generate_ttft_seconds summary")
        for q, key in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            lines.append(f'paddle_generate_ttft_seconds{{quantile="{q}"}} '
                         f'{s["ttft_ms"][key] / 1e3:.6f}')
        return "\n".join(lines) + "\n"


# ===================================================================
# the engine
# ===================================================================
@_shared_state("_queue", "_workers", "_warmed", "_live_rows",
               "_programs", "_params_by_dev", "_draft_by_dev",
               "_closing", "_abort", "_shut", "_next_rid",
               "_migrate_streams", "_pc_index")
class GenerativeEngine:
    """Continuous-batching autoregressive serving of a causal language
    model.

    `model` is a GPTForCausalLM / GPTForCausalLMScan (weights are
    copied out and stacked for the scan programs); pass a prebuilt
    ``(params, cfg)`` via ``params=`` to skip stacking — and for a model
    of another kind, whose ``cfg.serving_passes()`` supplies the step
    (models/lfm2.py). ``slots`` is
    the decode-batch capacity per worker per KV class;
    ``kv_slot_buckets`` opts into multiple pow2 slot-capacity classes
    (shorter sequences then run cheaper decode steps at the cost of one
    extra program family per class — default is one class at
    ``max_context``, which keeps the program inventory at exactly the
    prefill bucket ladder plus one decode program per batch bucket).

    Each worker thread runs ``_worker_loop``: admit, prefill what was
    admitted, then one decode pass a capacity class. Without a draft
    model that pass is ``_decode_step`` — launch the next step from the
    rows the device holds, then read and emit the step before it; with
    one it is ``_spec_step``, the closed stage / launch / read / emit
    loop a host-side accept needs. Which one is decided by what the
    engine was built with, never by an option.

    ``kv_dtype="int8"`` quantizes the KV pool (quantization/kv.py):
    ~4x the decode slots and prefix-cache rows per byte, with quantize-
    on-scatter / dequantize-on-gather fused into the same program
    inventory. ``quantize_weights=True`` stores the replicas' stacked
    matmul weights int8 (per-layer absmax) and dequantizes in-program.
    Both are engine-wide program-family dimensions: greedy output stays
    within tolerance of the float engine (the first token of a
    kv-only-quantized engine is exact — prefill attention runs on the
    in-program float K/V), and every determinism contract (seeded
    sampling path-identity, spec-on bitwise spec-off, requeue replay)
    holds AMONG quantized paths.
    """

    def __init__(self, model=None, params: Optional[tuple] = None,
                 slots: Optional[int] = None,
                 max_context: Optional[int] = None,
                 prompt_boundaries: Optional[Sequence[int]] = None,
                 kv_slot_buckets: Optional[Sequence[int]] = None,
                 replicas: int = 1,
                 max_queue_depth: Optional[int] = None,
                 max_new_tokens_cap: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 warmup: bool = True, auto_start: bool = True,
                 retry_after_s: float = 0.5,
                 retry_after_max_s: float = 30.0,
                 overload_queue_factor: float = 2.0,
                 donate: Optional[bool] = None,
                 draft=None, draft_params: Optional[tuple] = None,
                 spec_tokens: int = 4,
                 prefix_cache_slots: int = 0,
                 kv_dtype: Optional[str] = None,
                 quantize_weights: bool = False):
        if params is not None:
            self._params, self._cfg = params
        else:
            self._params, self._cfg = stack_gpt_params(model)
        # the one seam between the engine and a model: the step the
        # model's own configuration supplies, GPT's where it supplies none
        passes = getattr(self._cfg, "serving_passes", None)
        self._model = passes() if passes else GPTPasses(self._cfg)
        kv_dtype = kv_dtype or self._model.kv_dtype
        asked = {"prefix_cache_slots": int(prefix_cache_slots) > 0,
                 "draft": draft is not None or draft_params is not None,
                 "kv_dtype=int8": kv_dtype == "int8",
                 "quantize_weights": bool(quantize_weights)}
        for feature, on in asked.items():
            if on and feature in self._model.refuses:
                raise ValueError(
                    f"{self._model.name}: this model is not served with "
                    f"{feature} — {self._model.refuses[feature]}")
        # the K/V pool's geometry is the model's: a position holds
        # `kv_heads` heads (a grouped-query model's fewer than its query
        # heads) in each of `kv_layers` layers (its attention layers)
        self._H = self._model.kv_heads
        self._Dh = self._model.head_dim
        self._L = self._model.kv_layers
        self._vocab = self._model.vocab_size

        self._slots = int(slots if slots is not None
                          else flag("generate_slots"))
        self._max_ctx = int(min(max_context or self._model.max_seq_len,
                                self._model.max_seq_len))
        if kv_slot_buckets:
            caps = sorted(int(c) for c in kv_slot_buckets)
            for c in caps:
                if c & (c - 1):
                    raise ValueError(
                        f"kv_slot_buckets must be powers of two (got "
                        f"{c}) so every prompt bucket fits its class")
            if caps[-1] > self._max_ctx:
                raise ValueError(
                    f"kv_slot_buckets max {caps[-1]} exceeds max_context "
                    f"{self._max_ctx}")
        else:
            caps = [self._max_ctx]
        self._caps = caps

        # speculative decode: a cheap draft model sharing the vocab
        if draft_params is not None:
            self._draft_params, dcfg = draft_params
        elif draft is not None:
            self._draft_params, dcfg = stack_gpt_params(draft)
        else:
            self._draft_params = dcfg = None
        self._spec = self._draft_params is not None
        if self._spec:
            if int(dcfg.vocab_size) != self._vocab:
                raise ValueError(
                    f"draft vocab {int(dcfg.vocab_size)} != target vocab "
                    f"{self._vocab} — speculative decode needs a shared "
                    f"tokenizer")
            if int(dcfg.max_seq_len) < self._max_ctx:
                raise ValueError(
                    f"draft max_seq_len {int(dcfg.max_seq_len)} < engine "
                    f"max_context {self._max_ctx} — the draft must cover "
                    f"every cached position")
            if int(spec_tokens) < 2:
                raise ValueError(
                    f"spec_tokens must be >= 2 (got {spec_tokens}); 1 "
                    f"means plain decode — drop the draft instead")
            self._draft_model = GPTPasses(dcfg)
            self._dH = int(dcfg.num_heads)
            self._dL = int(dcfg.num_layers)
            self._dDh = int(dcfg.hidden_size) // self._dH
            self._deps = float(dcfg.layer_norm_eps)
            self._spec_k = int(spec_tokens)
        else:
            self._spec_k = 1
        self._pc_slots = max(0, int(prefix_cache_slots))
        if kv_dtype not in (self._model.kv_dtype, "int8"):
            raise ValueError(
                f"kv_dtype must be '{self._model.kv_dtype}' or 'int8' "
                f"(got {kv_dtype!r})")
        self._kv_dtype = str(kv_dtype)
        self._quant_w = bool(quantize_weights)
        if self._quant_w:
            # once, host-side: replicas device_put the int8 result —
            # int8 at rest on every device is the density win
            self._params = _kvq.quantize_stacked_params(self._params)
            if self._draft_params is not None:
                self._draft_params = _kvq.quantize_stacked_params(
                    self._draft_params)
        self._prompt_boundaries = sorted(prompt_boundaries) if \
            prompt_boundaries else bucket_boundaries_pow2(
                min(8, caps[-1]), caps[-1])
        self._batch_buckets = bucket_boundaries_pow2(1, self._slots)
        self._max_queue_depth = int(
            max_queue_depth if max_queue_depth is not None
            else flag("serving_max_queue_depth"))
        self._max_new_cap = int(
            max_new_tokens_cap if max_new_tokens_cap is not None
            else flag("generate_max_new_tokens"))
        self._eos_default = eos_token_id
        self._retry_after_s = float(retry_after_s)
        self._retry_after_max_s = float(retry_after_max_s)
        self._overload_queue_factor = max(1.0, float(overload_queue_factor))
        # donation is the accelerator-side in-place pool update; on CPU
        # it must stay OFF — donated programs are kept off the
        # persistent cache there (core/compile_cache.donated_cpu_guard),
        # and generation's warm-restart contract needs them cached
        self._donate = bool(donate) if donate is not None \
            else jax.default_backend() not in ("cpu",)

        self._device_pool = list(jax.local_devices())
        self._cv = threading.Condition()
        self._queue: "deque[_GenRequest]" = deque()
        # (rid, cap) -> {slot: cached positions}: the lock-protected
        # mirror of each worker's thread-local row table, feeding the
        # KV-utilization gauge and cleared on supersede
        self._live_rows: Dict[tuple, Dict[int, int]] = {}
        # disaggregated serving (fabric/handoff.py): does a drain
        # migrate in-flight streams out, and the per-(rid, cap) mirror
        # of each worker's prefix-cache keys ("F:hash8") feeding
        # load_report's residency digest
        self._migrate_streams = False
        self._pc_index: Dict[tuple, set] = {}
        self._closing = False
        self._abort = False
        self._shut = False
        self._next_rid = 0
        self._programs: dict = {}
        self._prog_lock = threading.Lock()
        self._params_by_dev: dict = {}
        self._draft_by_dev: dict = {}
        self._warmed: set = set()     # (device_key, kind, cap, bucket)
        self._workers: List[ReplicaSlot] = []
        self.scale_headroom_fn = None

        self.metrics = GenerativeMetrics()
        # approximate gauge: GIL-atomic len, scrape must not contend
        # race: allow lock-free queue-depth gauge read
        self.metrics.queue_depth_fn = lambda: len(self._queue)
        self.metrics.replicas_fn = lambda: len(self._active())
        self.metrics.kv_util_fn = self._kv_utilization
        self.metrics.quant_flags_fn = lambda: (
            int(self._kv_dtype == "int8"), int(self._quant_w))
        track_engine(self)

        for _ in range(max(int(replicas), 1)):
            self._workers.append(self._new_worker())
        self.warmup_report = None
        if warmup:
            self.warm_up()
        else:
            with self._cv:
                for w in self._workers:
                    if w.state == "warming":
                        w.state = "active"
        self._started = False
        if auto_start:
            self.start()

    # ---------------------------------------------------------- programs --
    def _program(self, kind: str, cap: int, bucket: int, k: int = 1):
        """Memoized jitted program for (family, class cap, bucket, k) —
        built once per engine; the in-loop call sites never re-trace.
        Families: prefill / decode / extend / pcopy run target geometry;
        dprefill / dpropose run draft geometry; verify is the target's
        k-position speculative pass (k > 1 only for dpropose/verify);
        kvget / kvput are the KV-slot handoff read/write (raw row pair
        in the stored dtype — the disaggregated-serving plane).
        kv_dtype is a family dimension too (engine-wide, but it changes
        the traced pool pytree, so it belongs in the key and the
        program_report inventory)."""
        key = (kind, cap, bucket, k, self._kv_dtype)
        import functools

        # always under the lock (no unlocked fast path): workers on
        # different devices race the first build of a (family, cap,
        # bucket) entry, and an uncontended acquire is noise next to a
        # decode step
        with self._prog_lock:
            prog = self._programs.get(key)
            if prog is not None:
                return prog
            scratch = self._slots
            # extend / verify / dpropose are the GPT family's alone: a
            # model that cannot take them refuses what would build them
            if kind == "prefill":
                body = functools.partial(_prefill_body, model=self._model)
            elif kind == "decode":
                body = functools.partial(_decode_body, scratch=scratch,
                                         model=self._model)
            elif kind == "extend":
                body = functools.partial(_extend_body, scratch=scratch,
                                         num_heads=self._H,
                                         eps=self._model.eps)
            elif kind == "verify":
                body = functools.partial(_verify_body, scratch=scratch,
                                         num_heads=self._H,
                                         eps=self._model.eps)
            elif kind == "dprefill":
                body = functools.partial(_prefill_body,
                                         model=self._draft_model)
            elif kind == "dpropose":
                body = functools.partial(_propose_body, k=k,
                                         scratch=scratch,
                                         num_heads=self._dH,
                                         eps=self._deps)
            elif kind == "pcopy":
                body = _copy_row_body
            elif kind == "kvget":
                body = _kvget_body
            elif kind == "kvput":
                body = _kvput_body
            else:
                raise ValueError(f"unknown program family {kind!r}")
            # kvget reads the pool without consuming it — never donate
            # its inputs; kvput/pcopy update the pool pair in place
            if not self._donate or kind == "kvget":
                donate = ()
            elif kind in ("pcopy", "kvput"):
                donate = (0, 1)
            elif kind in ("prefill", "decode") and \
                    self._state_shape() is not None:
                donate = (1, 2, 10)     # the pools and the state beside them
            else:
                donate = (1, 2)
            # a name of its own on the profiler's `XLA Modules` line and
            # in the compiled text: the device's time for a decode step or
            # a prefill is read by it, instruction names repeat between
            # programs, and what joins a device operation to its scope is
            # keyed by the program's name (trace.py)
            name = f"{self._model.program_prefix}_{kind}_c{cap}_b{bucket}"
            prog = jax.jit(_named(body, name if k == 1 else f"{name}_k{k}"),
                           donate_argnums=donate)
            self._programs[key] = prog
        return prog

    def _warm_call(self, kind: str, cap: int, bucket: int, args: tuple):
        """A warm-up call of program (kind, cap, bucket) on `args` -> its
        outputs. With tracing on it then keeps which scope each
        instruction was traced under (`observability.trace.note_op_scopes`,
        as `TrainStep` does), by the program's name: a device trace names
        an operation by its instruction and nothing else, and instruction
        names repeat between programs.

        The text is read AFTER the call, from a lowering of the call's own
        shapes and placements: the call has traced the program as an
        untraced run traces it, so the compile behind `as_text()` is the
        entry the call just made or found and compiles nothing. Lowered
        first, the program's first trace would start from another frame;
        a Pallas kernel's serialized body carries the frames it was traced
        under, so every program that holds one would get a cache key that
        only traced runs use (read on the chip, PR 33: six decode programs
        and `program_memory`'s compiled anew, 184 s of set-up for 46)."""
        noting = _tr.enabled()
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            args) if noting else None
        prog = self._program(kind, cap, bucket)
        with _cc.donated_cpu_guard(self._donate):
            out = prog(*args)
            if noting:
                _tr.note_op_scopes(
                    prog.lower(*shapes).compile().as_text())
        return out

    def _params_for(self, device):
        key = self._device_key(device)
        with self._prog_lock:
            p = self._params_by_dev.get(key)
        if p is None:
            # device_put outside the lock; a racing duplicate placement
            # is idempotent and the second write just wins
            p = {k: jax.device_put(v, device)
                 for k, v in self._params.items()}
            with self._prog_lock:
                self._params_by_dev[key] = p
        return p

    def _draft_params_for(self, device):
        key = self._device_key(device)
        with self._prog_lock:
            p = self._draft_by_dev.get(key)
        if p is None:
            p = {k: jax.device_put(v, device)
                 for k, v in self._draft_params.items()}
            with self._prog_lock:
                self._draft_by_dev[key] = p
        return p

    def _alloc_class(self, cap: int, device) -> _ClassState:
        # rows: [0, slots) live, [slots] scratch (pad/overflow sink),
        # [slots+1, slots+1+pc) prefix-cache entries
        zk = zv = None      # a model with no attention layer has no pool
        if self._L:
            zk = _kvq.alloc(self._pool_shape(cap), device, self._kv_dtype)
            zv = _kvq.alloc(self._pool_shape(cap), device, self._kv_dtype)
        dk = dv = None
        if self._spec:
            dk = _kvq.alloc(self._draft_pool_shape(cap), device,
                            self._kv_dtype)
            dv = _kvq.alloc(self._draft_pool_shape(cap), device,
                            self._kv_dtype)
        rec = self._state_shape()
        if rec is not None:
            rec = _kvq.alloc(rec, device, self._model.state_dtype)
        return _ClassState(cap, self._slots, zk, zv, self._pc_slots,
                           dk, dv, rec)

    def _state_shape(self):
        """The model's fixed-size state beside its K/V rows, one row a
        slot and the scratch row (None: the model keeps none). It does not
        grow with the class's capacity."""
        return self._model.state_shape(self._slots + 1 + self._pc_slots)

    def _pool_shape(self, cap: int) -> tuple:
        """[rows, L, cap, H*Dh]: the heads folded into the minor
        dimension, so that a TPU tiles a row's positions (8, 128) over
        (cap, H*Dh) with dense lanes — heads 64 wide as a minor
        dimension of their own pad to 128 lanes, and the compiler then
        puts a pool-sized copy in front of the decode kernel. The bodies
        cut the heads after the read; the handoff wire keeps
        [L, cap, H, Dh] by a host-side view."""
        return (self._slots + 1 + self._pc_slots, self._L, cap,
                self._H * self._Dh)

    def _draft_pool_shape(self, cap: int) -> tuple:
        return (self._slots + 1, self._dL, cap, self._dH * self._dDh)

    def _kv_plan(self, kind: str, cap: int):
        """The decode kernel's plan where a program of family `kind` is
        built with the kernel's read on this engine's devices; None
        where it takes the gather (or attends over no pool rows).
        Mirrors pool_attention's choice."""
        if kind == "decode":
            H, Dh, Hq = self._H, self._Dh, self._model.query_heads
        elif kind == "dpropose":
            H, Dh, Hq = self._dH, self._dDh, self._dH
        else:
            return None
        if not self._L or not _lowers_for_tpu(self._device_pool[0]):
            return None         # no pool to read, or not lowered for a TPU
        return kernel_plan(cap, H, Dh, self._kv_dtype, Hq)

    def kv_pool_bytes(self) -> int:
        """Bytes ONE worker's cache allocates, of either kind: the K/V
        pools (all capacity classes, K+V, target + draft geometry, scratch
        and prefix-cache rows included) and the model's fixed-size state
        beside them — or the state alone, for a model with no attention
        layer. The density denominator serve_bench's quantized gate
        divides by; int8 halves-and-halves-again the f32 figure (int8
        data + the small per-(row, layer) scale tensor)."""
        total = 0
        for cap in self._caps:
            if self._L:
                total += 2 * _kvq.pool_nbytes(self._pool_shape(cap),
                                              self._kv_dtype)
            if self._state_shape() is not None:
                total += _kvq.pool_nbytes(self._state_shape(),
                                          self._model.state_dtype)
            if self._spec:
                total += 2 * _kvq.pool_nbytes(
                    self._draft_pool_shape(cap), self._kv_dtype)
        return total

    def program_report(self) -> dict:
        """The compile-shape inventory: which programs exist and which
        (device, program) pairs have been executed at least once."""
        with self._prog_lock:
            named = sorted(
                (f"{k[0]}[cap={k[1]},b={k[2]}"
                 + ("" if k[3] == 1 else f",k={k[3]}")
                 + ("" if k[4] == "f32" else f",kv={k[4]}") + "]",
                 k[0], k[1])
                for k in self._programs)
        progs = [name for name, _, _ in named]
        with self._cv:
            warmed = len(self._warmed)
        return {
            # the read each pool-attending program was built with
            "kv_read": {
                name: "kernel" if self._kv_plan(kind, cap) else "gather"
                for name, kind, cap in named
                if kind in _POOL_READERS and self._L},
            "prefill_buckets": [b for b in self._prompt_boundaries],
            "decode_batch_buckets": list(self._batch_buckets),
            "kv_classes": list(self._caps),
            "kv_dtype": self._kv_dtype,
            # the cache by layer type: a class's K/V pool (K and V each;
            # none for a model with no attention layer) and the fixed-size
            # state (None: the model has none), in `state_dtype`
            "cache": {"model": self._model.name,
                      "kv_pool": {cap: list(self._pool_shape(cap))
                                  for cap in self._caps if self._L},
                      "state": None if self._state_shape() is None
                      else list(self._state_shape())},
            "state_dtype": self._model.state_dtype,
            "quantize_weights": self._quant_w,
            "programs": progs,
            "warmed": warmed,
        }

    def program_memory(self) -> dict:
        """The compile's own account of the largest decode program (the
        largest class, every slot): `temp_bytes`, which the allocator's
        peak leaves out, beside its argument and output bytes. Compiles
        from shapes; nothing runs."""
        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        cap, b = self._caps[-1], self._batch_buckets[-1]
        params = jax.tree.map(lambda a: sds(a.shape, a.dtype), self._params)
        pool_k, pool_v, rec = self._cache_avals(cap)
        with _cc.donated_cpu_guard(self._donate):
            mem = self._program("decode", cap, b).lower(
                params, pool_k, pool_v, sds((b,), np.int32),
                sds((b,), np.int32), sds((b,), np.int32),
                sds((b,), np.float32), sds((b,), np.int32),
                sds((b,), np.float32), sds((b, 2), np.uint32),
                rec).compile().memory_analysis()
        return {"program": f"decode[cap={cap},b={b}]",
                "temp_bytes": int(mem.temp_size_in_bytes),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes)}

    def _cache_avals(self, cap: int) -> tuple:
        """(K pool, V pool, state) of class `cap` as shapes; None what
        the model has none of."""
        pool = _kvq.aval(self._pool_shape(cap), self._kv_dtype) \
            if self._L else None
        rec = self._state_shape()
        return pool, pool, None if rec is None else _kvq.aval(
            rec, self._model.state_dtype)

    # ----------------------------------------------------------- workers --
    def _new_worker(self, device=None) -> ReplicaSlot:
        if device is None:
            device = pick_least_loaded_device(self._device_pool,
                                              self._workers)
        w = ReplicaSlot(self._next_rid, device)
        self._next_rid += 1
        return w

    def _active(self) -> List[ReplicaSlot]:
        # under _cv (reentrant Condition): the breaker's headroom probe
        # and gauges read the pool from their own threads
        with self._cv:
            return [w for w in self._workers if w.state == "active"]

    def _device_key(self, device) -> int:
        for i, d in enumerate(self._device_pool):
            if d is device or d == device:
                return i
        return -1

    def replica_states(self) -> List[dict]:
        now = time.monotonic()
        with self._cv:
            return [w.state_row(now) for w in self._workers]

    def _kv_utilization(self) -> dict:
        """Cache gauge across workers: live slots/positions over the
        ACTUAL allocated cache — every started worker carries one buffer
        pair per capacity class whether or not it has admitted yet, so
        the denominator comes from the worker count, not from which
        (rid, cap) keys happen to exist in the _live_rows mirror. A model
        with no attention layer caches no positions: both position counts
        are nought, and the gauge is slots used of slots."""
        with self._cv:
            pools = sum(1 for w in self._workers
                        if w.state in ("active", "draining"))
            snap = [dict(rows) for rows in self._live_rows.values()]
        slots_total = pools * self._slots * len(self._caps)
        positions_total = pools * self._slots * sum(self._caps) \
            if self._L else 0
        slots_used = positions_used = 0
        for rows in snap:
            slots_used += len(rows)
            positions_used += sum(rows.values()) if self._L else 0
        return {"slots_used": slots_used, "slots_total": slots_total,
                "positions_used": positions_used,
                "positions_total": positions_total,
                "pool_bytes": pools * self.kv_pool_bytes()}

    # --------------------------------------------------------- elasticity --
    def add_replica(self, device=None, warm: bool = True) -> dict:
        """Grow the worker pool at runtime; the new worker's programs
        are warmed through the compile cache BEFORE it is admitted
        (same contract as the predict engine — the autoscaler calls
        this blindly on either front)."""
        _chaos.hit("scale.add")
        with self._cv:
            if self._closing:
                raise ServingError(503, "server shutting down",
                                   retry_after=self._retry_after_s)
            w = self._new_worker(device)
            self._workers.append(w)
        t0 = time.perf_counter()
        try:
            with _cc.measure() as delta:
                warmed = self._warm_device(w.device) if warm else 0
            started = self._started
            if started:
                self._start_worker(w)
        except Exception:
            with self._cv:
                if w in self._workers:
                    self._workers.remove(w)
            raise
        with self._cv:
            w.state = "active"
            self._cv.notify_all()
        return {"rid": w.rid, "device": str(w.device),
                "warmed_executables": warmed,
                "warm_time_s": round(time.perf_counter() - t0, 3),
                "persistent_hits": delta["hits"],
                "persistent_misses": delta["misses"],
                "admitted_after_warmup": True, "worker_started": started}

    def remove_replica(self, rid: Optional[int] = None, drain: bool = True,
                       timeout: float = 60.0) -> dict:
        """Retire one worker. drain=True: it stops ADMITTING, its
        in-flight sequences run to completion, then it exits — decode
        slots empty out naturally, zero tokens lost. drain=False: the
        worker is superseded and its in-flight requests requeue onto
        the remaining workers (they re-prefill; already-streamed tokens
        are suppressed on re-emission)."""
        _chaos.hit("scale.drain", rid=rid if rid is not None else -1)
        with self._cv:
            target = None
            if rid is None:
                actives = [w for w in self._workers
                           if w.state == "active"]
                target = actives[-1] if actives else None
            else:
                for w in self._workers:
                    if w.rid == rid and w.state in ("active", "draining"):
                        target = w
            if target is None:
                raise ValueError(f"no removable worker (rid={rid})")
            n_active = sum(1 for w in self._workers
                           if w.state == "active")
            if n_active <= 1 and target.state == "active":
                raise ValueError(
                    "cannot remove the last active worker — the queue "
                    "would starve; add a replacement first")
            target.state = "draining"
            self._cv.notify_all()
        if drain:
            with self._cv:
                self._cv.wait_for(
                    lambda: target.state == "retired", timeout)
                drained = target.state == "retired"
        else:
            self._supersede(target, retire=True)
            drained = False
        with self._cv:
            return {"rid": target.rid, "drained": drained,
                    "state": target.state}

    def revive_replica(self, rid: int) -> dict:
        """Replace a (presumed hung) worker's thread in place — the
        health watchdog's move. The fresh generation gets FRESH pool
        buffers (the zombie's state is abandoned with it), and the
        stuck in-flight requests requeue for re-prefill."""
        with self._cv:
            target = None
            for w in self._workers:
                if w.rid == rid and w.state in ("active", "draining"):
                    target = w
            if target is None:
                raise ValueError(f"no live worker rid={rid}")
        self._supersede(target, retire=False)
        with self._cv:
            return {"rid": rid, "generation": target.generation}

    def _supersede(self, w: ReplicaSlot, retire: bool) -> None:
        with self._cv:
            w.generation += 1
            gen = w.generation
            stuck = list(w.inflight)
            w.inflight = []
            w.busy_since = None
            for cap in self._caps:
                self._live_rows.pop((w.rid, cap), None)
                self._pc_index.pop((w.rid, cap), None)
            for req in stuck:
                req.owner = None
            if retire:
                w.state = "retired"
                self._cv.notify_all()
        self._requeue(stuck)
        if not retire:
            with self._cv:
                w.last_beat = time.monotonic()
            self._start_worker(w, gen)

    def _requeue(self, reqs: List[_GenRequest], charge: bool = True) -> None:
        """Put incomplete requests back at the FRONT of the queue for
        re-prefill (they already waited once). One charged requeue per
        request — endless bouncing between sick workers must not mask
        an outage. The regenerated token stream is suppressed up to
        ``streamed`` so the client never sees a duplicate."""
        if not reqs:
            return
        failed = 0
        with self._cv:
            dead = self._shut or not any(
                w.state in ("warming", "active") for w in self._workers)
            for req in reversed(reqs):
                if req.future.done():
                    continue
                if (charge and req.requeues >= 1) or dead:
                    msg = ("server shutting down while generation was in "
                           "flight" if dead else
                           "worker replaced twice while generation was "
                           "in flight")
                    err = ServingError(503, msg,
                                       retry_after=self._retry_after())
                    if req.future.set_error(err):
                        req.stream.put(("err", err))
                        failed += 1
                    continue
                if charge:
                    req.requeues += 1
                    self.metrics.on_requeue()
                req.owner = None
                req.tokens = []   # regenerate; stream dedupes on streamed
                self._queue.appendleft(req)
            self._cv.notify_all()
        if failed:
            self.metrics.on_failed(failed)

    # ------------------------------------------------------------ warmup --
    def _warm_device(self, device) -> int:
        """Pre-compile the full program inventory on `device`: every
        (class, prompt-bucket) prefill and every (class, batch-bucket)
        decode step — after this, steady-state generation never sees
        an XLA compile. Inputs are committed to `device` EXACTLY like
        the execution path's (an uncommitted warm input would compile a
        sibling executable and leave the real first call cold)."""
        def put(a):
            return jax.device_put(a, device)

        p = self._params_for(device)
        n = 0
        devk = self._device_key(device)
        scratch = self._slots
        for cap in self._caps:
            cs = self._alloc_class(cap, device)
            bounds = [s for s in self._prompt_boundaries if s <= cap]
            for s in bounds:
                args = (p, cs.buf_k, cs.buf_v, put(np.int32(scratch)),
                        put(np.zeros((1, s), np.int32)), put(np.int32(1)),
                        put(np.float32(0.0)), put(np.int32(1)),
                        put(np.float32(1.0)), put(np.zeros(2, np.uint32)),
                        cs.rec)
                tok, _, cs.buf_k, cs.buf_v, cs.rec = self._warm_call(
                    "prefill", cap, s, args)
                del args
                tok.block_until_ready()
                with self._cv:
                    self._warmed.add((devk, "prefill", cap, s))
                n += 1
            for b in self._batch_buckets:
                args = (p, cs.buf_k, cs.buf_v,
                        put(np.full((b,), scratch, np.int32)),
                        put(np.zeros((b,), np.int32)),
                        put(np.zeros((b,), np.int32)),
                        put(np.zeros((b,), np.float32)),
                        put(np.ones((b,), np.int32)),
                        put(np.ones((b,), np.float32)),
                        put(np.zeros((b, 2), np.uint32)), cs.rec)
                nxt, _, _, cs.buf_k, cs.buf_v, cs.rec, _ = \
                    self._warm_call("decode", cap, b, args)
                del args
                nxt.block_until_ready()
                with self._cv:
                    self._warmed.add((devk, "decode", cap, b))
                n += 1
            if "handoff" in self._model.refuses:
                continue        # no row of this model leaves its pool
            # KV-handoff plane: the export read + import write over the
            # scratch row — warmed here so a mid-workload handoff
            # (prefill->decode, drain migration) never compiles
            with _cc.donated_cpu_guard(self._donate):
                parts = self._program("kvget", cap, 1)(
                    cs.buf_k, cs.buf_v, put(np.int32(scratch)))
            parts[0].block_until_ready()
            del parts       # a row pair: not held beside kvput's operands
            with self._cv:
                self._warmed.add((devk, "kvget", cap, 1))
            n += 1
            row_dt = np.int8 if self._kv_dtype == "int8" else np.float32
            row = np.zeros(self._pool_shape(cap)[1:], row_dt)
            scl = None if self._kv_dtype == "f32" else \
                np.ones((self._L,), np.float32)
            with _cc.donated_cpu_guard(self._donate):
                cs.buf_k, cs.buf_v = self._program("kvput", cap, 1)(
                    cs.buf_k, cs.buf_v, put(np.int32(scratch)),
                    put(row), None if scl is None else put(scl),
                    put(row), None if scl is None else put(scl))
            cs.buf_k.block_until_ready()
            with self._cv:
                self._warmed.add((devk, "kvput", cap, 1))
            n += 1
            if self._pc_slots:
                with _cc.donated_cpu_guard(self._donate):
                    cs.buf_k, cs.buf_v = self._program("pcopy", cap, 1)(
                        cs.buf_k, cs.buf_v, put(np.int32(scratch)),
                        put(np.int32(scratch)))
                cs.buf_k.block_until_ready()
                with self._cv:
                    self._warmed.add((devk, "pcopy", cap, 1))
                n += 1
                for s in bounds:
                    with _cc.donated_cpu_guard(self._donate):
                        tok, _, cs.buf_k, cs.buf_v = self._program(
                            "extend", cap, s)(
                                p, cs.buf_k, cs.buf_v,
                                put(np.int32(scratch)),
                                put(np.zeros((1, s), np.int32)),
                                put(np.int32(0)), put(np.int32(1)),
                                put(np.float32(0.0)), put(np.int32(1)),
                                put(np.float32(1.0)),
                                put(np.zeros(2, np.uint32)))
                    tok.block_until_ready()
                    with self._cv:
                        self._warmed.add((devk, "extend", cap, s))
                    n += 1
            if self._spec:
                dp = self._draft_params_for(device)
                k = self._spec_k
                for s in bounds:
                    with _cc.donated_cpu_guard(self._donate):
                        tok, _, cs.dbuf_k, cs.dbuf_v, _ = self._program(
                            "dprefill", cap, s)(
                                dp, cs.dbuf_k, cs.dbuf_v,
                                put(np.int32(scratch)),
                                put(np.zeros((1, s), np.int32)),
                                put(np.int32(1)),
                                put(np.float32(0.0)), put(np.int32(1)),
                                put(np.float32(1.0)),
                                put(np.zeros(2, np.uint32)))
                    tok.block_until_ready()
                    with self._cv:
                        self._warmed.add((devk, "dprefill", cap, s))
                    n += 1
                for b in self._batch_buckets:
                    with _cc.donated_cpu_guard(self._donate):
                        props, cs.dbuf_k, cs.dbuf_v = self._program(
                            "dpropose", cap, b, k)(
                                dp, cs.dbuf_k, cs.dbuf_v,
                                put(np.full((b,), scratch, np.int32)),
                                put(np.zeros((b,), np.int32)),
                                put(np.zeros((b,), np.int32)))
                    props.block_until_ready()
                    with self._cv:
                        self._warmed.add((devk, "dpropose", cap, b))
                    n += 1
                    with _cc.donated_cpu_guard(self._donate):
                        ys, _, cs.buf_k, cs.buf_v = self._program(
                            "verify", cap, b, k)(
                                p, cs.buf_k, cs.buf_v,
                                put(np.full((b,), scratch, np.int32)),
                                put(np.zeros((b, k), np.int32)),
                                put(np.zeros((b,), np.int32)),
                                put(np.zeros((b,), np.float32)),
                                put(np.ones((b,), np.int32)),
                                put(np.ones((b,), np.float32)),
                                put(np.zeros((b, 2), np.uint32)))
                    ys.block_until_ready()
                    with self._cv:
                        self._warmed.add((devk, "verify", cap, b))
                    n += 1
        return n

    def warm_up(self) -> None:
        t0 = time.perf_counter()
        n = 0
        with self._cv:
            warming_devices = [w.device for w in self._workers
                               if w.state == "warming"]
        with _cc.measure() as delta:
            done_devices = set()
            for device in warming_devices:
                devk = self._device_key(device)
                if devk not in done_devices:
                    n += self._warm_device(device)
                    done_devices.add(devk)
        with self._cv:
            for w in self._workers:
                if w.state == "warming":
                    w.state = "active"
            self._cv.notify_all()
            warmed_count = len(self._warmed)
            n_workers = len(self._workers)
        self.warmup_report = {
            "time_s": round(time.perf_counter() - t0, 3),
            "executables": warmed_count,
            "warm_passes": n,
            "replicas": n_workers,
            "prefill_buckets": list(self._prompt_boundaries),
            "decode_batch_buckets": list(self._batch_buckets),
            "kv_classes": list(self._caps),
            "kv_dtype": self._kv_dtype,
            "quantize_weights": self._quant_w,
            "kv_pool_bytes": self.kv_pool_bytes(),
            "cache": self.program_report()["cache"],
            "persistent_hits": delta["hits"],
            "persistent_misses": delta["misses"],
            "persistent_cache_enabled": delta["enabled"],
        }

    # --------------------------------------------------------- lifecycle --
    def start(self) -> None:
        if self._started:
            return
        self._started = True
        with self._cv:
            cold = [w for w in self._workers if w.thread is None]
        for w in cold:
            self._start_worker(w)

    def _start_worker(self, w: ReplicaSlot,
                      gen: Optional[int] = None) -> None:
        with self._cv:
            if gen is None:
                gen = w.generation
            t = threading.Thread(target=self._worker_loop, args=(w, gen),
                                 name=f"generate-worker-{w.rid}",
                                 daemon=True)
            # under the lock: a superseded zombie reads w.thread for
            # compile-flag ownership while the revive installs this
            w.thread = t
        t.start()

    def shutdown(self, drain: bool = True, timeout: float = 60.0,
                 migrate: bool = False) -> None:
        """Stop the engine. drain=True finishes in-flight work first;
        migrate=True (with drain) additionally EXPORTS every in-flight
        streamed row as a KV-handoff payload — each local stream ends
        with ('handoff', payload) for the fabric layer to re-home —
        instead of holding the drain hostage to the longest decode.
        Non-streamed requests still finish normally (their callers
        hold a plain future, not a stream to splice)."""
        if drain and migrate:
            self._refuse_handoff("shutdown(migrate=True)")
        with self._cv:
            if self._shut:
                return
            self._shut = True
            self._closing = True
            if drain and migrate:
                self._migrate_streams = True
            if not drain:
                self._abort = True
                while self._queue:
                    r = self._queue.popleft()
                    err = ServingError(503, "server shutting down",
                                       retry_after=self._retry_after_s)
                    if r.future.set_error(err):
                        r.stream.put(("err", err))
            self._cv.notify_all()
        if not self._started:
            self.start()
        with self._cv:
            threads = [w.thread for w in self._workers if w.thread]
        for t in threads:
            t.join(timeout)
        # stragglers that raced the last worker's exit
        with self._cv:
            stranded = list(self._queue)
            self._queue.clear()
        n = 0
        for r in stranded:
            err = ServingError(503, "server shutting down",
                               retry_after=self._retry_after_s)
            if r.future.set_error(err):
                r.stream.put(("err", err))
                n += 1
        if n:
            self.metrics.on_failed(n)

    def health(self) -> dict:
        with self._cv:
            states = [w.state for w in self._workers]
            return {
                "status": "draining" if self._closing else "ok",
                "replicas": states.count("active"),
                "replica_states": {s: states.count(s)
                                   for s in set(states)},
                "queue_depth": len(self._queue),
                "prefill_buckets": list(self._prompt_boundaries),
                "decode_batch_buckets": list(self._batch_buckets),
                "kv_classes": list(self._caps),
                "kv_dtype": self._kv_dtype,
                "quantize_weights": self._quant_w,
                "warmed_executables": len(self._warmed),
            }

    def load_report(self) -> dict:
        """Few-field load digest for the fabric heartbeat (keep it
        cheap — it rides every lease renewal). The KV-aware router's
        signal rides here too: per-capacity-class free-slot counts and
        a BOUNDED prefix-cache residency digest ("F:hash8" keys), both
        assembled from the lock-protected host-side mirrors — no
        device sync, so renewal cost is unchanged."""
        util = self._kv_utilization()
        with self._cv:
            depth = len(self._queue)
            replicas = sum(1 for w in self._workers
                           if w.state == "active")
            draining = self._closing
            pools = sum(1 for w in self._workers
                        if w.state in ("active", "draining"))
            used: Dict[int, int] = {}
            for (_rid, cap), rows in self._live_rows.items():
                used[cap] = used.get(cap, 0) + len(rows)
            pdig: set = set()
            for ents in self._pc_index.values():
                pdig.update(ents)
        kv = {}
        for cap in self._caps:
            total = pools * self._slots
            kv[str(cap)] = {"free": max(total - used.get(cap, 0), 0),
                            "slots": total}
        return {
            "queue_depth": depth,
            "replicas": replicas,
            "tokens_per_s": round(self.metrics.tokens_per_s(), 3),
            "kv_slots_used": int(util.get("slots_used", 0)),
            "status": "draining" if draining else "ok",
            "kv": kv,
            "prefix": sorted(pdig)[:32],
        }

    # ------------------------------------------------------------ submit --
    def _retry_after(self) -> float:
        depth = len(self._queue)
        tps = self.metrics.tokens_per_s()
        if depth <= 0 or tps <= 0.0:
            return self._retry_after_s
        # rough drain estimate: backlog * expected tokens per request
        per_req = max(self.metrics.tokens_out_total /
                      max(self.metrics.completed_total, 1), 1.0)
        est = depth * per_req / tps
        return min(max(est, self._retry_after_s), self._retry_after_max_s)

    def _queue_bound(self) -> int:
        fn = self.scale_headroom_fn
        if fn is not None:
            try:
                if int(fn()) > 0:
                    return int(self._max_queue_depth *
                               self._overload_queue_factor)
            except Exception:  # noqa: BLE001 — a sick headroom probe
                pass           # must not break the breaker itself
        return self._max_queue_depth

    def _decode_request(self, input_ids, max_new_tokens, eos_token_id,
                        deadline_ms, temperature=None, top_k=None,
                        top_p=None, seed=None) -> _GenRequest:
        try:
            samp = validate_sampling({"temperature": temperature,
                                      "top_k": top_k, "top_p": top_p,
                                      "seed": seed})
        except ServingError:
            self.metrics.on_reject("sampling")
            raise
        try:
            prompt = np.asarray(input_ids)
            if prompt.ndim == 2 and prompt.shape[0] == 1:
                prompt = prompt[0]
            prompt = prompt.astype(np.int32, casting="same_kind")
        except (TypeError, ValueError) as e:
            self.metrics.on_reject("decode")
            raise ServingError(400, f"bad input_ids: {e}") from None
        if prompt.ndim != 1 or prompt.size < 1:
            self.metrics.on_reject("shape")
            raise ServingError(
                400, f"input_ids must be a non-empty 1-D id sequence "
                     f"(got shape {tuple(prompt.shape)})")
        if int(prompt.min()) < 0 or int(prompt.max()) >= self._vocab:
            self.metrics.on_reject("vocab")
            raise ServingError(
                400, f"input_ids out of range [0, {self._vocab})")
        P = int(prompt.size)
        cap_max = self._caps[-1]
        if P > cap_max - 1:
            self.metrics.on_reject("too_long")
            raise ServingError(
                400, f"prompt length {P} exceeds the usable context "
                     f"{cap_max - 1} (largest KV slot {cap_max} minus "
                     f"one generated token)")
        try:
            want = int(max_new_tokens) if max_new_tokens is not None \
                else self._max_new_cap
            eos = eos_token_id if eos_token_id is not None else \
                self._eos_default
            eos = None if eos is None else int(eos)
            dl_s = float(deadline_ms) / 1e3 \
                if deadline_ms is not None and float(deadline_ms) > 0 \
                else None
        except (TypeError, ValueError) as e:
            self.metrics.on_reject("decode")
            raise ServingError(
                400, f"bad generation parameters: {e}") from None
        if want < 1:
            self.metrics.on_reject("decode")
            raise ServingError(
                400, f"max_new_tokens must be >= 1 (got {want})")
        max_new = max(1, min(want, self._max_new_cap, cap_max - P))
        deadline = time.monotonic() + dl_s if dl_s is not None else None
        temp = samp["temperature"] if samp["temperature"] is not None \
            else 0.0
        tk = min(samp["top_k"], self._vocab) \
            if samp["top_k"] is not None else self._vocab
        tp = samp["top_p"] if samp["top_p"] is not None else 1.0
        sd = samp["seed"] if samp["seed"] is not None else 0
        return _GenRequest(np.ascontiguousarray(prompt), max_new,
                           eos, deadline, temperature=temp, top_k=tk,
                           top_p=tp, seed=sd)

    def submit(self, input_ids, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None,
               prefill_only: bool = False,
               resume_from: int = 0) -> GenerateHandle:
        """Enqueue one generation; returns its streaming handle. Raises
        ServingError for decode rejects (400) and load shedding (503).

        Disaggregated-serving knobs: ``prefill_only`` fills a KV slot,
        samples the first token and finishes with the exported handoff
        payload (finish_reason "handoff") instead of decoding here.
        ``resume_from=n`` is the replay-resume path — the client
        already holds n tokens from a lost host, so regeneration (the
        key-chain law makes it bitwise) suppresses re-delivery of the
        first n."""
        if prefill_only:
            self._refuse_handoff("prefill_only")
        bound = self._queue_bound()
        # the authoritative re-check below holds _cv; this is a
        # race: allow deliberate lock-free fast-path read (GIL-atomic)
        if self._closing or len(self._queue) >= bound:
            with self._cv:
                if self._closing:
                    raise ServingError(503, "server shutting down",
                                       retry_after=self._retry_after_s)
                if len(self._queue) >= bound:
                    self.metrics.on_shed()
                    raise ServingError(
                        503, f"generation queue depth {len(self._queue)} "
                             f"at bound {bound} — load shed",
                        retry_after=self._retry_after())
        with _tr.span("generate.enqueue", "serving") as sp:
            req = self._decode_request(input_ids, max_new_tokens,
                                       eos_token_id, deadline_ms,
                                       temperature, top_k, top_p, seed)
            req.prefill_only = bool(prefill_only)
            if resume_from:
                try:
                    rf = int(resume_from)
                except (TypeError, ValueError):
                    rf = -1
                if rf < 0:
                    self.metrics.on_reject("decode")
                    raise ServingError(
                        400, f"bad resume_from: {resume_from!r}")
                req.streamed = min(rf, req.max_new)
            req.ctx = sp.ctx
            sp.set(prompt_tokens=int(req.prompt.size),
                   max_new=req.max_new)
            with self._cv:
                if self._closing:
                    raise ServingError(503, "server shutting down",
                                       retry_after=self._retry_after_s)
                if len(self._queue) >= bound:
                    self.metrics.on_shed()
                    raise ServingError(
                        503, f"generation queue depth {len(self._queue)} "
                             f"at bound {bound} — load shed",
                        retry_after=self._retry_after())
                self._queue.append(req)
                self.metrics.on_accept()
                self._cv.notify_all()
        return GenerateHandle(req)

    def generate(self, input_ids, max_new_tokens: Optional[int] = None,
                 eos_token_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 timeout: Optional[float] = 120.0,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None) -> dict:
        """Synchronous submit + wait; returns the result dict."""
        return self.submit(input_ids, max_new_tokens, eos_token_id,
                           deadline_ms, temperature, top_k, top_p,
                           seed).result(timeout)

    def stream(self, input_ids, max_new_tokens: Optional[int] = None,
               eos_token_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               seed: Optional[int] = None):
        """Submit and iterate tokens as they are generated."""
        return iter(self.submit(input_ids, max_new_tokens, eos_token_id,
                                deadline_ms, temperature, top_k, top_p,
                                seed))

    # ---------------------------------------------------------- scheduler --
    def _class_for(self, total_len: int) -> int:
        for cap in self._caps:
            if total_len <= cap:
                return cap
        return self._caps[-1]

    def _admit_locked(self, w: ReplicaSlot, gen: int,
                      state: Dict[int, _ClassState]) -> List[tuple]:
        """Pop queued requests into free slots (caller holds _cv).
        Expired requests 503 out; owner/slot markers are set here so a
        supersede racing the prefill sees them and requeues. A request
        whose capacity class is saturated is skipped over (order kept),
        not blocked on: with multiple kv_slot_buckets a long request at
        the head must not starve short ones that fit a free class —
        FIFO still holds within each class."""
        admitted = []
        if not any(cs.free for cs in state.values()):
            return admitted
        now = time.monotonic()
        skipped = []
        while self._queue:
            req = self._queue.popleft()
            if req.deadline is not None and now > req.deadline and \
                    req.streamed == 0:
                err = ServingError(503, "deadline exceeded while queued",
                                   retry_after=self._retry_after_s)
                if req.future.set_error(err):
                    req.stream.put(("err", err))
                    self.metrics.on_failed(1)
                continue
            cap = self._class_for(int(req.prompt.size) + req.max_new)
            cs = state.get(cap)
            if cs is None or not cs.free:
                skipped.append(req)
                if not any(c.free for c in state.values()):
                    break
                continue
            slot = cs.free.pop()
            req.owner = (w.rid, gen)
            w.inflight.append(req)
            rows = self._live_rows.setdefault((w.rid, cap), {})
            rows[slot] = int(req.prompt.size)
            admitted.append((req, cs, slot))
        for req in reversed(skipped):
            self._queue.appendleft(req)
        return admitted

    def _emit(self, w: ReplicaSlot, gen: int, req: _GenRequest,
              tok: int) -> str:
        """Record one generated token under the lock, owner-checked (a
        zombie that unwedges after a revive must not touch the stream
        its replacement now owns). Returns 'dead' | 'live' | 'done'."""
        with self._cv:
            if w.generation != gen or req.owner != (w.rid, gen) or \
                    req.future.done():
                return "dead"
            req.tokens.append(int(tok))
            fresh = len(req.tokens) > req.streamed
            if fresh:
                req.streamed = len(req.tokens)
                if req.t_first is None:
                    req.t_first = time.monotonic()
                    self.metrics.on_first_token(
                        req.t_first - req.t_enqueue)
                req.stream.put(("tok", int(tok)))
        if fresh:
            self.metrics.on_tokens(1)
            if _tr.enabled():
                now_ns = time.perf_counter_ns()
                _tr.emit_span("generate.token", now_ns, now_ns,
                              parent=req.ctx, cat="serving",
                              args={"index": len(req.tokens),
                                    "token": int(tok)})
        done = (len(req.tokens) >= req.max_new or
                (req.eos is not None and int(tok) == req.eos))
        return "done" if done else "live"

    def _finish(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                slot: int, req: _GenRequest, reason: str,
                extra: Optional[dict] = None) -> None:
        done = time.monotonic()
        with self._cv:
            cs.rows.pop(slot, None)
            cs.free.append(slot)
            rows = self._live_rows.get((w.rid, cs.cap))
            if rows is not None:
                rows.pop(slot, None)
            if req in w.inflight:
                w.inflight.remove(req)
            req.owner = None
        info = {
            "tokens": list(req.tokens),
            "n_tokens": len(req.tokens),
            "prompt_tokens": int(req.prompt.size),
            "finish_reason": reason,
            "ttft_ms": round((req.t_first - req.t_enqueue) * 1e3, 3)
            if req.t_first is not None else None,
            "latency_ms": round((done - req.t_enqueue) * 1e3, 3),
        }
        if extra:
            info.update(extra)
        if req.future.set_result(info):
            self.metrics.on_complete(done - req.t_enqueue)
            req.stream.put(("done", info))
        if _tr.enabled():
            now_ns = time.perf_counter_ns()
            _tr.emit_span("generate.finish", req.t_enq_ns, now_ns,
                          parent=req.ctx, cat="serving",
                          args={"n_tokens": len(req.tokens),
                                "reason": reason})

    def _fail_rows(self, w: ReplicaSlot, gen: int,
                   state: Dict[int, _ClassState], exc: Exception) -> None:
        """A device-level failure mid-step: every in-flight row of this
        worker requeues (one charged strike each; a second strike 503s)
        with FRESH buffers — re-prefill is the recovery, and the reset
        pool cannot leak a poisoned slot into the next batch."""
        with self._cv:
            stuck = list(w.inflight)
            w.inflight = []
            for req in stuck:
                req.owner = None
            for cap, cs in state.items():
                cs.rows.clear()
                cs.free = list(range(cs.n_slots))
                self._live_rows.pop((w.rid, cap), None)
                self._pc_index.pop((w.rid, cap), None)
        for cap, old in list(state.items()):
            # the poisoned pools are dropped before the fresh ones are
            # made: never two pool pairs beside each other on the device.
            # A step launched ahead holds them until it ends, so it is
            # waited for (and never read: a requeue replays from the
            # tokens emitted); where it failed, the wait raises what it
            # raised, and the pools go all the same
            try:
                jax.block_until_ready((old.buf_k, old.buf_v, old.rec))
            except Exception:  # noqa: BLE001
                pass
            old.dev = None
            old.buf_k = old.buf_v = old.rec = None
            old.dbuf_k = old.dbuf_v = None
            state[cap] = self._alloc_class(cap, w.device)
        self._requeue(stuck)

    def _update_liveness_locked(self, w, cs):
        rows = self._live_rows.setdefault((w.rid, cs.cap), {})
        rows.clear()
        for slot, row in cs.rows.items():
            rows[slot] = row.length

    def _prefill_one(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                     slot: int, req: _GenRequest,
                     phase: Optional[dict] = None) -> None:
        P = int(req.prompt.size)
        bounds = [b for b in self._prompt_boundaries if b <= cs.cap]
        S = bucket_for(P, bounds)
        devk = self._device_key(w.device)

        def put(a):
            return jax.device_put(a, w.device)

        samp = (np.float32(req.temperature), np.int32(req.top_k),
                np.float32(req.top_p))
        key0 = _seed_key(req.seed)

        # ---- prefix-cache probe: longest cached boundary wins; the
        # longest UNcached boundary longer than the hit is admitted on
        # the way out. F < P always — extend/sample needs >= 1 tail
        # token — so the probe is replay-stable across requeues.
        hitF = hit_row = None
        admitF = admit_h = None
        if cs.pc_slots:
            with self._cv:
                for F in reversed(bounds):
                    if F >= P:
                        continue
                    h = _prefix_hash(req.prompt, F)
                    row = cs.pcache.get((F, h))
                    if row is not None:
                        hitF, hit_row = F, row
                        cs.pcache.move_to_end((F, h))
                        break
                    if admitF is None:
                        admitF, admit_h = F, h

        prog_keys = []
        if hitF is not None:
            T = bucket_for(P - hitF, bounds)
            prog_keys.append((devk, "extend", cs.cap, T))
            prog_keys.append((devk, "pcopy", cs.cap, 1))
        else:
            prog_keys.append((devk, "prefill", cs.cap, S))
        if self._spec:
            prog_keys.append((devk, "dprefill", cs.cap, S))
        args = None
        if _tr.enabled():
            args = {"replica": w.rid, "bucket": S, "prompt_tokens": P,
                    "cap": cs.cap, "prefix_hit": hitF or 0,
                    **(phase or {})}
        if not self._busy(w, gen, prog_keys):
            return
        try:
            with _tr.span("generate.prefill", "serving", args,
                          parent=req.ctx):
                with _cc.donated_cpu_guard(self._donate):
                    p = self._params_for(w.device)
                    if hitF is not None:
                        cs.buf_k, cs.buf_v = self._program(
                            "pcopy", cs.cap, 1)(
                                cs.buf_k, cs.buf_v,
                                put(np.int32(hit_row)),
                                put(np.int32(slot)))
                        T = bucket_for(P - hitF, bounds)
                        ids = np.zeros((1, T), np.int32)
                        ids[0, :P - hitF] = req.prompt[hitF:]
                        tok, kcar, cs.buf_k, cs.buf_v = self._program(
                            "extend", cs.cap, T)(
                                p, cs.buf_k, cs.buf_v,
                                put(np.int32(slot)), put(ids),
                                put(np.int32(hitF)), put(np.int32(P)),
                                put(samp[0]), put(samp[1]),
                                put(samp[2]), put(key0))
                    else:
                        ids = np.zeros((1, S), np.int32)
                        ids[0, :P] = req.prompt
                        tok, kcar, cs.buf_k, cs.buf_v, cs.rec = \
                            self._program("prefill", cs.cap, S)(
                                p, cs.buf_k, cs.buf_v,
                                put(np.int32(slot)), put(ids),
                                put(np.int32(P)),
                                put(samp[0]), put(samp[1]),
                                put(samp[2]), put(key0), cs.rec)
                    if self._spec:
                        # the draft has no prefix cache: it always
                        # prefills the full prompt into its own pool
                        dids = np.zeros((1, S), np.int32)
                        dids[0, :P] = req.prompt
                        _dt, _dk, cs.dbuf_k, cs.dbuf_v, _ = self._program(
                            "dprefill", cs.cap, S)(
                                self._draft_params_for(w.device),
                                cs.dbuf_k, cs.dbuf_v,
                                put(np.int32(slot)), put(dids),
                                put(np.int32(P)),
                                put(np.float32(0.0)), put(np.int32(1)),
                                put(np.float32(1.0)),
                                put(np.zeros(2, np.uint32)))
                    if admitF is not None:
                        with self._cv:
                            idx = self._pc_index.setdefault(
                                (w.rid, cs.cap), set())
                            evict = not cs.pc_free
                            if evict:
                                (evF, evh), crow = cs.pcache.popitem(
                                    last=False)
                                idx.discard(f"{evF}:{evh[:8]}")
                            else:
                                crow = cs.pc_free.pop()
                            cs.pcache[(admitF, admit_h)] = crow
                            idx.add(f"{admitF}:{admit_h[:8]}")
                        cs.buf_k, cs.buf_v = self._program(
                            "pcopy", cs.cap, 1)(
                                cs.buf_k, cs.buf_v, put(np.int32(slot)),
                                put(np.int32(crow)))
                        if evict:
                            self.metrics.on_prefix_evict()
                kcar.copy_to_host_async()
                tok = int(tok)
                kcar = np.asarray(kcar)
        finally:
            self._idle(w, gen)
        with self._cv:
            for pk in prog_keys:
                self._warmed.add(pk)
        self.metrics.on_prefill(P if hitF is None else P - hitF)
        if cs.pc_slots:
            self.metrics.on_prefix(hitF is not None, hitF or 0)
        status = self._emit(w, gen, req, tok)
        if status == "dead":
            return
        with self._cv:
            if w.generation != gen:
                return
            cs.admitted()
            cs.rows[slot] = _Row(req, slot, P, key=kcar)
            self._update_liveness_locked(w, cs)
        if status == "done":
            self._finish(w, gen, cs, slot, req, "eos"
                         if req.eos is not None and tok == req.eos
                         else "length")
            return
        if req.prefill_only:
            # prefill/decode specialization: the slot is filled and the
            # first token sampled — export it for a decode host instead
            # of decoding here. The meta records streamed=0: the CLIENT
            # has seen nothing (this result IS the handoff), so the
            # importer re-emits that first token fresh.
            from ..fabric import handoff as _ho

            raw = self._export_row(w, gen, cs, slot, streamed=0)
            if raw is not None:
                self.metrics.on_handoff_out(len(raw))
                self._finish(w, gen, cs, slot, req, "handoff",
                             extra={"handoff": _ho.to_b64(raw)})

    def _busy(self, w: ReplicaSlot, gen: int, prog_keys: list) -> bool:
        """Mark worker `w` busy on the device from now (the watchdog's
        clock), compiling where a program of `prog_keys` was never run.
        False where its generation was superseded: the caller returns."""
        with self._cv:
            if w.generation != gen:
                return False
            w.busy_since = time.monotonic()
            if w.thread is threading.current_thread():
                w.compiling = any(pk not in self._warmed
                                  for pk in prog_keys)
        return True

    def _idle(self, w: ReplicaSlot, gen: int, batches: int = 0) -> None:
        with self._cv:
            if w.generation == gen:
                w.busy_since = None
                w.compiling = False
            w.batches += batches

    def _decode_step(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                     phase: Optional[dict] = None) -> None:
        """One pass of plain decode over class `cs`: launch the next step
        of the live rows, THEN block on the oldest step not yet read and
        emit it. The step-to-step dependency stays on the device
        (`_DeviceRows`): while the row set is what the last step ran, the
        launch takes each row's token, key and length from that step's
        outputs and stages nothing; when it changed (an admission, a
        finished or migrated row) every launched step is read first and
        the seven arrays are staged anew from the rows — the one restage
        a row-set change costs. The host learns of an EOS one step late:
        that row's overshoot step wrote past its end in its own slot (the
        slot's next owner is prefilled behind it on the device's queue),
        its token is discarded and it counts as no row. A finish by
        length is known ahead: where every row's last token is already on
        the queue, nothing is launched.

        `phase` is the worker loop's {"iter", "rid"} (None with tracing
        off): the args of the spans that partition this thread's time —
        one `generate.decode_step` a launched step with `.stage` (the
        restage's device_puts: nothing on a step ahead), `.launch` (the
        program call until it returns) and `.wait` (the blocking read of
        the oldest unread step, where one is due), then `generate.emit`.
        A read with no launch is a bare `.wait` + `generate.emit`. The
        launched step's span carries its number (`step`); the `.wait`
        that reads a step and the `generate.emit` after it carry that
        step's (`read_step`): a launch and its read pair by number."""
        with self._cv:
            if w.generation != gen:
                return
            rows = [cs.rows[s] for s in sorted(cs.rows)]
        if not rows:
            return
        devk = self._device_key(w.device)
        dev = stale = cs.dev
        if dev is not None and not dev.holds(rows):
            if not self._settle(w, gen, cs, phase):
                return
            # `stale` keeps the old rows' device arrays until the new
            # launch is on the queue: freeing device buffers takes the
            # host time the device would wait for
            cs.dev = dev = None
            with self._cv:     # the steps just read may have ended rows
                rows = [cs.rows[s] for s in sorted(cs.rows)]
            if not rows:
                return
        n = len(rows)
        bucket = bucket_for(n, self._batch_buckets)
        prog_key = (devk, "decode", cs.cap, bucket)
        behind = len(dev.unread) if dev is not None else 0

        # the rows are read here, before the hang-injection point below: a
        # worker that unwedges must not read rows its replacement owns
        with self._cv:
            due = [len(r.req.tokens) + behind < r.req.max_new
                   for r in rows]
            fresh = None if dev is not None else self._row_arrays(
                rows, bucket, cs.n_slots)
            positions = [r.length + behind for r in rows]
        if not any(due):
            # every row's last token is on the queue already
            self._read_oldest(w, gen, cs, phase)
            return
        # pool positions the read copies for each row: whole blocks up to
        # its position under the kernel, every position under the gather
        plan = self._kv_plan("decode", cs.cap)
        kv_reads = [0 if not self._L else cs.cap if plan is None else
                    plan.positions_read(x, cs.cap) for x in positions]
        if not self._busy(w, gen, [prog_key]):
            return
        w.launched += 1
        # the step this pass reads, where one is due: the oldest unread
        # once this pass's own is on the queue
        oldest = dev.unread[0] if behind >= _STEPS_AHEAD else None
        args, read_args = None, phase
        if _tr.enabled():
            if oldest is not None:
                read_args = _read_args(phase, oldest.step)
            args = {"replica": w.rid, "step": w.launched, "rows": n,
                    "bucket": bucket,
                    "cap": cs.cap, "kv_read": sum(kv_reads),
                    "state_bytes": self._model.state_step_bytes(n, bucket),
                    "spec_k": 0,
                    "ahead": int(behind > 0),
                    "staged": 0 if fresh is None else len(fresh),
                    "traces": [r.req.ctx.trace_id for r in rows
                               if r.req.ctx is not None]}
        read = None
        try:
            # hang/raise injection for the watchdog + requeue ladder:
            # a chaos `delay` rule here wedges this worker mid-decode
            # exactly like a stuck device; generation rides the context
            # so a rule can be scoped to ONE worker incarnation
            _chaos.hit("serving.decode_step", replica=w.rid,
                       generation=gen)
            with _tr.span("generate.decode_step", "serving", args,
                          parent=rows[0].req.ctx) as step_span, \
                    _cc.donated_cpu_guard(self._donate):
                with _tr.span("generate.decode_step.stage", "serving",
                              phase):
                    if fresh is not None:
                        slots, toks, lens, temps, topks, topps, keys = \
                            jax.device_put(fresh, w.device)
                        cs.dev = dev = _DeviceRows(
                            rows, (slots, temps, topks, topps), toks,
                            lens, keys)
                        cs.restaged = True
                with _tr.span("generate.decode_step.launch", "serving",
                              phase):
                    slots, temps, topks, topps = dev.fixed
                    nxt, nkeys, dev.lens, cs.buf_k, cs.buf_v, cs.rec, \
                        aux = self._program("decode", cs.cap, bucket)(
                            self._params_for(w.device), cs.buf_k,
                            cs.buf_v, slots, dev.toks, dev.lens, temps,
                            topks, topps, dev.keys, cs.rec)
                    dev.toks, dev.keys = nxt, nkeys
                    # on their way to the host as soon as the step ends,
                    # not when the host comes to ask
                    for out in jax.tree.leaves((nxt, nkeys, aux)):
                        out.copy_to_host_async()
                    dev.unread.append(_Launched(
                        w.launched, rows, prog_key, kv_reads, behind > 0,
                        nxt, nkeys, aux))
                    del stale
                with _tr.span("generate.decode_step.wait", "serving",
                              read_args):
                    if oldest is not None:
                        read = self._wait_oldest(dev)
                        if read[3] is not None:
                            # of the step this pass READ: the launched
                            # one's are a step away
                            step_span.set(experts_hit=int(read[3][1]))
        finally:
            self._idle(w, gen, batches=1)
        if read is not None:
            with _tr.span("generate.emit", "serving", read_args) as emit:
                self._emit_read(w, gen, cs, emit, *read)

    @staticmethod
    def _row_arrays(rows: list, bucket: int, scratch: int) -> list:
        """The seven host arrays of a decode step over `rows`, padded to
        `bucket` with rows that target the scratch slot at length 0:
        slots, pending tokens, lengths, temperatures, top-k, top-p,
        keys — the decode programs' own order (caller holds _cv)."""
        slots = np.full((bucket,), scratch, np.int32)
        toks = np.zeros((bucket,), np.int32)
        lens = np.zeros((bucket,), np.int32)
        temps = np.zeros((bucket,), np.float32)
        topks = np.ones((bucket,), np.int32)
        topps = np.ones((bucket,), np.float32)
        keys = np.zeros((bucket, 2), np.uint32)
        for i, row in enumerate(rows):
            slots[i] = row.slot
            toks[i] = row.req.tokens[-1]
            lens[i] = row.length
            temps[i] = row.req.temperature
            topks[i] = row.req.top_k
            topps[i] = row.req.top_p
            keys[i] = row.key
        return [slots, toks, lens, temps, topks, topps, keys]

    @staticmethod
    def _wait_oldest(dev: _DeviceRows) -> tuple:
        """Block on the oldest launched step: (step, tokens, keys, what
        the pass counted of itself — (tokens per expert, distinct experts
        hit) of a routed model, None of a dense one)."""
        step = dev.unread.popleft()
        return (step, np.asarray(step.nxt), np.asarray(step.nkeys),
                None if step.aux is None else
                tuple(np.asarray(a) for a in step.aux))

    def _emit_read(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                   span, step: _Launched, nxt, nkeys, experts=None) -> bool:
        return self._emit_step(
            w, gen, cs, step.rows, [step.prog_key], step.prog_key[3],
            step.kv_reads, [[int(t)] for t in nxt[:len(step.rows)]],
            nkeys, span, step.ahead, experts)

    def _read_oldest(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                     phase: Optional[dict]) -> bool:
        """Read and emit the oldest launched step with no launch beside
        it. False where the worker was superseded meanwhile."""
        oldest = cs.dev.unread[0]
        if not self._busy(w, gen, [oldest.prog_key]):
            return False
        args = _read_args(phase, oldest.step)
        try:
            with _tr.span("generate.decode_step.wait", "serving", args):
                read = self._wait_oldest(cs.dev)
        finally:
            self._idle(w, gen)
        with _tr.span("generate.emit", "serving", args) as emit:
            return self._emit_read(w, gen, cs, emit, *read)

    def _settle(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                phase: Optional[dict] = None) -> bool:
        """Read and emit every launched step of class `cs`, oldest first:
        after it row.length / row.key / req.tokens are what the device
        holds. Whoever reads a row's host state to move it elsewhere, or
        stages the rows anew, settles first. False where the worker was
        superseded meanwhile."""
        while cs.dev is not None and cs.dev.unread:
            if not self._read_oldest(w, gen, cs, phase):
                return False
        return True

    def _spec_step(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                   phase: Optional[dict] = None) -> None:
        """One speculative step of class `cs`: a fused k-step draft burst,
        the host's look at its proposals, one target verify pass. The
        host decides in the middle of every step, so this loop stays
        closed — stage, launch, read, emit, and only then the next step's
        arrays (every step stages its rows anew: none of its gaps counts
        as steady). Spans as in `_decode_step`, `.stage` / `.launch` /
        `.wait` twice each; the verify pass's `.wait` reads the step."""
        with self._cv:
            if w.generation != gen:
                return
            rows = [cs.rows[s] for s in sorted(cs.rows)]
            if not rows:
                return
            n = len(rows)
            bucket = bucket_for(n, self._batch_buckets)
            # the rows are read here, before the hang-injection point
            # below: a worker that unwedges must not read rows its
            # replacement owns
            fresh = self._row_arrays(rows, bucket, cs.n_slots)
        toks = fresh[1]
        k = self._spec_k

        def put(a):
            return jax.device_put(a, w.device)

        devk = self._device_key(w.device)
        prog_keys = [(devk, "dpropose", cs.cap, bucket),
                     (devk, "verify", cs.cap, bucket)]
        # verify reads every position of the rows (the gather)
        kv_reads = [cs.cap] * n
        if not self._busy(w, gen, prog_keys):
            return
        w.launched += 1
        args = read_args = None
        if _tr.enabled():
            args = {"replica": w.rid, "step": w.launched, "rows": n,
                    "bucket": bucket,
                    "cap": cs.cap, "kv_read": n * cs.cap, "spec_k": k,
                    "ahead": 0, "staged": len(fresh) + 1,
                    "traces": [r.req.ctx.trace_id for r in rows
                               if r.req.ctx is not None]}
            read_args = _read_args(phase, w.launched)
        try:
            _chaos.hit("serving.decode_step", replica=w.rid,
                       generation=gen)
            with _tr.span("generate.decode_step", "serving", args,
                          parent=rows[0].req.ctx), \
                    _cc.donated_cpu_guard(self._donate):
                with _tr.span("generate.decode_step.stage", "serving",
                              phase):
                    staged = [put(a) for a in fresh]
                    cs.restaged = True
                # `staged` is dropped inside the last launch: its device
                # buffers are freed while the program runs, not between
                # two steps.
                # ONE fused k-step draft burst; the draft pool advances
                # through all k inputs so a full accept finds every
                # cached position next round
                with _tr.span("generate.decode_step.launch", "serving",
                              phase):
                    props, cs.dbuf_k, cs.dbuf_v = self._program(
                        "dpropose", cs.cap, bucket, k)(
                            self._draft_params_for(w.device),
                            cs.dbuf_k, cs.dbuf_v, *staged[:3])
                with _tr.span("generate.decode_step.wait", "serving",
                              phase):
                    props = np.asarray(props)      # [bucket, k]
                with _tr.span("generate.decode_step.stage", "serving",
                              phase):
                    staged[1] = put(np.concatenate(
                        [toks[:, None], props[:, :k - 1]],
                        axis=1).astype(np.int32))
                with _tr.span("generate.decode_step.launch", "serving",
                              phase):
                    ys, khist, cs.buf_k, cs.buf_v = self._program(
                        "verify", cs.cap, bucket, k)(
                            self._params_for(w.device),
                            cs.buf_k, cs.buf_v, *staged)
                    del staged
                with _tr.span("generate.decode_step.wait", "serving",
                              read_args):
                    ys = np.asarray(ys)            # [bucket, k]
                    khist = np.asarray(khist)      # [bucket, k, 2]
        finally:
            self._idle(w, gen, batches=1)
        with _tr.span("generate.emit", "serving", read_args) as emit:
            # accept the longest agreed prefix per row: ys[i, j] is
            # the target's OWN token at position j (same key chain as
            # plain decode), valid while every earlier draft proposal
            # matched — rejection still yields ys[i, m-1] (>= 1 token
            # per burst, never slower than plain decode in tokens)
            ms = []
            for i in range(n):
                m = 1
                while m < k and props[i, m - 1] == ys[i, m - 1]:
                    m += 1
                ms.append(m)
            self.metrics.on_spec_step(
                proposed=n * (k - 1),
                accepted=sum(m - 1 for m in ms))
            self._emit_step(
                w, gen, cs, rows, prog_keys, bucket, kv_reads,
                [[int(t) for t in ys[i, :m]] for i, m in enumerate(ms)],
                [khist[i, m - 1] for i, m in enumerate(ms)], emit)

    def _emit_step(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                   rows: list, prog_keys: list, bucket: int,
                   kv_reads: list, toks: list, keys, span,
                   ahead: bool = False, experts=None) -> bool:
        """What follows a decode step's read on the worker thread: row i
        takes the tokens toks[i] (one, or a speculative burst's accepted
        prefix) and the key keys[i] that follows them — the rows'
        bookkeeping under the lock, every token to its stream, finished
        rows out of their slots. A row that left its slot before this
        step was read (it ended on an earlier step's EOS while this one
        was on the queue) was launched in vain: its token is discarded
        and the step's counters leave it out. False where the worker was
        superseded.

        This is where a row is handed its next token, so the gap between
        a row's tokens is measured here: the time since the class's
        previous step was read, with its cause as the class state holds
        it (STEP_GAP_CAUSES) — to the counters with the step, and with
        the live rows onto `span`, the `generate.emit` this runs under.
        A step that emits to no row hands nothing over and closes no gap;
        neither does the first read of a class that had held no row."""
        now = time.monotonic()
        with self._cv:
            for pk in prog_keys:
                self._warmed.add(pk)
            if w.generation != gen:
                return False
            live = [i for i, row in enumerate(rows)
                    if cs.rows.get(row.slot) is row]
            for i in live:
                rows[i].length += len(toks[i])
                rows[i].key = np.array(keys[i], np.uint32)
            self._update_liveness_locked(w, cs)
        if live:
            cause, gap = None, 0.0
            if cs.read_at is not None:
                gap = now - cs.read_at
                cause = "admission" if cs.admits else \
                    "rowset" if cs.restaged else "steady"
            cs.read_at, cs.admits, cs.restaged = now, 0, False
            # the state moved is the launched rows': one that had left its
            # slot by the read was stepped all the same
            self.metrics.on_step(len(live), bucket,
                                 sum(kv_reads[i] for i in live),
                                 len(live) * cs.cap if self._L else 0, ahead,
                                 self._model.state_step_bytes(len(rows),
                                                              bucket),
                                 cause=cause, gap_s=gap,
                                 tokens=sum(len(toks[i]) for i in live))
            if _tr.enabled():
                span.set(rows=len(live))
                if cause is not None:
                    span.set(cause=cause, gap_ms=gap * 1e3)
        if experts is not None:
            # the program counted the rows it was launched with; a row
            # that had left its slot by then was one of them
            self.metrics.on_experts(experts[0], int(experts[1]),
                                    self._model.expert_layers)
        finished = []
        for i in live:
            row = rows[i]
            for tok in toks[i]:
                status = self._emit(w, gen, row.req, tok)
                if status == "dead":
                    return False
                if status == "done":
                    finished.append(row)
                    break
        for row in finished:
            self._finish(w, gen, cs, row.slot, row.req,
                         "eos" if row.req.eos is not None and
                         row.req.tokens[-1] == row.req.eos else "length")
        return True

    # ------------------------------------------------- KV-slot handoff --
    def _export_row(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                    slot: int,
                    streamed: Optional[int] = None) -> Optional[bytes]:
        """Serialize one live row's decode state (fabric/handoff.py
        wire format): the pool row pair RAW in the stored dtype plus
        the metadata that makes the continuation bitwise — position,
        emitted tokens, the PRNG key-chain cursor, sampling params and
        prefix-cache lineage. Runs the warmed kvget program on the
        owning worker thread, OUTSIDE the engine lock. The row's host
        state must be what the device holds: a caller that takes a row
        out of the decode loop has settled its launched steps
        (`_settle`); a row just prefilled is in none. None when the
        row vanished under us (supersede race)."""
        from ..fabric import handoff as _ho

        with self._cv:
            row = cs.rows.get(slot)
            if row is None or w.generation != gen or \
                    row.req.owner != (w.rid, gen):
                return None
            req = row.req
            length = int(row.length)
            key = np.array(row.key, np.uint32, copy=True)
            tokens = [int(t) for t in req.tokens]
            sent = int(req.streamed if streamed is None else streamed)
        with _cc.donated_cpu_guard(self._donate):
            kd, ks, vd, vs = self._program("kvget", cs.cap, 1)(
                cs.buf_k, cs.buf_v,
                jax.device_put(np.int32(slot), w.device))
        # the pool stores heads folded; the wire keeps
        # [L, cap, H, Dh] (a view, host-side)
        wire = (self._L, int(cs.cap), self._H, self._Dh)
        arrays = {"prompt": np.asarray(req.prompt, np.int32),
                  "key": key, "k": np.asarray(kd).reshape(wire),
                  "v": np.asarray(vd).reshape(wire)}
        if ks is not None:
            arrays["k_scale"] = np.asarray(ks)
            arrays["v_scale"] = np.asarray(vs)
        P = int(req.prompt.size)
        lineage = []
        for F in reversed([b for b in self._prompt_boundaries
                           if b <= cs.cap]):
            if F < P:
                lineage.append([int(F), _prefix_hash(req.prompt, F)])
                break
        meta = {"cap": int(cs.cap), "kv_dtype": self._kv_dtype,
                "shape": list(wire),
                "length": length, "tokens": tokens,
                "streamed": sent, "max_new": int(req.max_new),
                "eos": None if req.eos is None else int(req.eos),
                "temperature": float(req.temperature),
                "top_k": int(req.top_k),
                "top_p": float(req.top_p), "seed": int(req.seed),
                "requeues": int(req.requeues), "lineage": lineage}
        return _ho.encode(meta, arrays)

    def _refuse_handoff(self, call: str) -> None:
        """409 where the model's cache cannot ride the handoff wire: a row
        of it never leaves its pool with its K/V alone."""
        why = self._model.refuses.get("handoff")
        if why:
            self.metrics.on_reject("handoff")
            raise ServingError(
                409, f"{self._model.name}: this model is not served with "
                     f"handoff ({call}) — {why}")

    def import_handoff(self, raw: bytes) -> GenerateHandle:
        """Admit one exported KV slot (the /admin/kv plane's POST).
        Geometry and kv_dtype must match this engine exactly — 409
        otherwise (the fabric router treats that as "this host refuses
        the handoff" and tries the next one); malformed payloads 400.
        The request re-enters the scheduler carrying its payload; a
        worker scatters the row into a free slot with the warmed kvput
        program and decode continues bitwise (the key-chain cursor
        rides the payload). Tokens up to meta["streamed"] are
        suppressed on re-emission — zero duplicates downstream."""
        from ..fabric import handoff as _ho

        self._refuse_handoff("import_handoff")
        try:
            meta, arrays = _ho.decode(raw)
        except ValueError as e:
            self.metrics.on_reject("handoff")
            raise ServingError(400, f"bad handoff payload: {e}") \
                from None
        try:
            cap = int(meta["cap"])
            dtype = str(meta["kv_dtype"])
            shape = [int(d) for d in meta["shape"]]
            length = int(meta["length"])
            tokens = [int(t) for t in meta["tokens"]]
            streamed = int(meta["streamed"])
            max_new = int(meta["max_new"])
            eos = meta.get("eos")
            eos = None if eos is None else int(eos)
        except (KeyError, TypeError, ValueError) as e:
            self.metrics.on_reject("handoff")
            raise ServingError(
                400, f"bad handoff meta: {e!r}"[:300]) from None
        if dtype != self._kv_dtype:
            self.metrics.on_reject("handoff")
            raise ServingError(
                409, f"handoff kv_dtype {dtype!r} != engine "
                     f"{self._kv_dtype!r}")
        if cap not in self._caps or \
                shape != [self._L, cap, self._H, self._Dh]:
            self.metrics.on_reject("handoff")
            raise ServingError(
                409, f"handoff geometry cap={cap} shape={shape} does "
                     f"not match this engine (caps {self._caps})")
        want = {"prompt", "key", "k", "v"}
        row_dt = "float32"
        if self._kv_dtype == "int8":
            want |= {"k_scale", "v_scale"}
            row_dt = "int8"
        if set(arrays) != want:
            self.metrics.on_reject("handoff")
            raise ServingError(
                400, f"handoff arrays {sorted(arrays)} != "
                     f"{sorted(want)}")
        bad = any(arrays[nm].shape != tuple(shape) or
                  arrays[nm].dtype.name != row_dt for nm in ("k", "v"))
        if self._kv_dtype == "int8":
            bad = bad or any(
                arrays[nm].shape != (self._L,) or
                arrays[nm].dtype.name != "float32"
                for nm in ("k_scale", "v_scale"))
        prompt = arrays["prompt"]
        P = int(prompt.size)
        bad = bad or prompt.ndim != 1 or P < 1 or \
            arrays["key"].shape != (2,) or \
            arrays["key"].dtype.name != "uint32"
        if not bad:
            bad = int(prompt.min()) < 0 or \
                int(prompt.max()) >= self._vocab or \
                not (1 <= len(tokens) <= max_new) or \
                not (0 <= streamed <= len(tokens)) or \
                length != P + len(tokens) - 1 or length >= cap or \
                any(not (0 <= t < self._vocab) for t in tokens)
        if bad:
            self.metrics.on_reject("handoff")
            raise ServingError(400, "handoff arrays fail validation")
        if self._class_for(P + max_new) != cap:
            self.metrics.on_reject("handoff")
            raise ServingError(
                409, f"this engine's capacity ladder classes "
                     f"P+max_new={P + max_new} at "
                     f"{self._class_for(P + max_new)}, payload wants "
                     f"{cap}")
        try:
            samp = validate_sampling(
                {"temperature": meta.get("temperature"),
                 "top_k": meta.get("top_k"),
                 "top_p": meta.get("top_p"), "seed": meta.get("seed")})
        except ServingError:
            self.metrics.on_reject("sampling")
            raise
        temp = samp["temperature"] if samp["temperature"] is not None \
            else 0.0
        tk = min(samp["top_k"], self._vocab) \
            if samp["top_k"] is not None else self._vocab
        tp = samp["top_p"] if samp["top_p"] is not None else 1.0
        sd = samp["seed"] if samp["seed"] is not None else 0
        req = _GenRequest(
            np.ascontiguousarray(prompt.astype(np.int32)), max_new,
            eos, None, temperature=temp, top_k=tk, top_p=tp, seed=sd)
        req.requeues = int(meta.get("requeues", 0))
        req.streamed = streamed
        req.handoff = (meta, arrays)
        bound = self._queue_bound()
        with _tr.span("generate.import", "serving") as sp:
            req.ctx = sp.ctx
            sp.set(prompt_tokens=P, length=length)
            with self._cv:
                if self._closing:
                    raise ServingError(503, "server shutting down",
                                       retry_after=self._retry_after_s)
                if len(self._queue) >= bound:
                    self.metrics.on_shed()
                    raise ServingError(
                        503, f"generation queue depth "
                             f"{len(self._queue)} at bound {bound} — "
                             f"load shed",
                        retry_after=self._retry_after())
                self._queue.append(req)
                self.metrics.on_accept()
                self._cv.notify_all()
        self.metrics.on_handoff_in(len(raw))
        return GenerateHandle(req)

    def _import_one(self, w: ReplicaSlot, gen: int, cs: _ClassState,
                    slot: int, req: _GenRequest) -> None:
        """Scatter an imported handoff payload into pool slot `slot`
        and install its row — the admission-side twin of _prefill_one.
        The continuation is bitwise: raw KV bytes land via the warmed
        kvput program and the key-chain cursor comes off the payload.
        With speculation the draft pool is rebuilt with a warmed
        dprefill over the generated history (draft state is bitwise-
        invisible to output — only the acceptance rate could shift),
        and the payload's prefix lineage is admitted into the local
        cache so follow-up prompts hit it."""
        meta, arrays = req.handoff
        P = int(req.prompt.size)
        length = int(meta["length"])
        toks = [int(t) for t in meta["tokens"]]
        bounds = [b for b in self._prompt_boundaries if b <= cs.cap]
        devk = self._device_key(w.device)

        def put(a):
            return jax.device_put(a, w.device)

        admitF = admit_h = None
        if cs.pc_slots:
            with self._cv:
                for ent in meta.get("lineage") or ():
                    try:
                        F, h = int(ent[0]), str(ent[1])
                    except (TypeError, ValueError, IndexError):
                        continue
                    if F in bounds and F < P and \
                            (F, h) not in cs.pcache:
                        admitF, admit_h = F, h
                        break
        prog_keys = [(devk, "kvput", cs.cap, 1)]
        S = bucket_for(length, bounds) if self._spec else 0
        if self._spec:
            prog_keys.append((devk, "dprefill", cs.cap, S))
        if admitF is not None:
            prog_keys.append((devk, "pcopy", cs.cap, 1))
        if not self._busy(w, gen, prog_keys):
            return
        try:
            with _cc.donated_cpu_guard(self._donate):
                # the wire's [L, cap, H, Dh] row, heads folded as
                # the pool stores them (a view, host-side)
                row = self._pool_shape(cs.cap)[1:]
                kd = put(arrays["k"].reshape(row))
                vd = put(arrays["v"].reshape(row))
                if self._kv_dtype == "int8":
                    kparts = (kd, put(arrays["k_scale"]),
                              vd, put(arrays["v_scale"]))
                else:
                    kparts = (kd, None, vd, None)
                cs.buf_k, cs.buf_v = self._program(
                    "kvput", cs.cap, 1)(
                        cs.buf_k, cs.buf_v, put(np.int32(slot)),
                        *kparts)
                if self._spec:
                    # the draft never ships: rebuild its pool from
                    # the generated history (prompt + all tokens
                    # but the pending one) — dprefill at this
                    # bucket is always in the warmed inventory
                    hist = np.zeros((1, S), np.int32)
                    hist[0, :P] = req.prompt
                    if len(toks) > 1:
                        hist[0, P:length] = np.asarray(
                            toks[:-1], np.int32)
                    _dt, _dk, cs.dbuf_k, cs.dbuf_v, _ = self._program(
                        "dprefill", cs.cap, S)(
                            self._draft_params_for(w.device),
                            cs.dbuf_k, cs.dbuf_v,
                            put(np.int32(slot)), put(hist),
                            put(np.int32(length)),
                            put(np.float32(0.0)), put(np.int32(1)),
                            put(np.float32(1.0)),
                            put(np.zeros(2, np.uint32)))
                if admitF is not None:
                    with self._cv:
                        idx = self._pc_index.setdefault(
                            (w.rid, cs.cap), set())
                        evict = not cs.pc_free
                        if evict:
                            (evF, evh), crow = cs.pcache.popitem(
                                last=False)
                            idx.discard(f"{evF}:{evh[:8]}")
                        else:
                            crow = cs.pc_free.pop()
                        cs.pcache[(admitF, admit_h)] = crow
                        idx.add(f"{admitF}:{admit_h[:8]}")
                    cs.buf_k, cs.buf_v = self._program(
                        "pcopy", cs.cap, 1)(
                            cs.buf_k, cs.buf_v,
                            put(np.int32(slot)),
                            put(np.int32(crow)))
                    if evict:
                        self.metrics.on_prefix_evict()
        finally:
            self._idle(w, gen)
        with self._cv:
            for pk in prog_keys:
                self._warmed.add(pk)
            if w.generation != gen or req.owner != (w.rid, gen) or \
                    req.future.done():
                return
            req.handoff = None
            cs.admitted()
            # re-emit everything past the exporter's delivered count
            # through the normal _emit path (a prefill handoff records
            # streamed=0 — the client saw nothing yet; a migration
            # records the delivered total — nothing re-emits)
            pending = toks[req.streamed:]
            req.tokens = toks[:req.streamed]
            cs.rows[slot] = _Row(req, slot, length,
                                 key=np.array(arrays["key"], np.uint32,
                                              copy=True))
            self._update_liveness_locked(w, cs)
        status = "live"
        for t in pending:
            status = self._emit(w, gen, req, int(t))
            if status == "dead":
                return
            if status == "done":
                break
        if status == "done":
            self._finish(w, gen, cs, slot, req,
                         "eos" if req.eos is not None and
                         req.tokens[-1] == req.eos else "length")

    def _migrate_rows(self, w: ReplicaSlot, gen: int,
                      state: Dict[int, _ClassState],
                      phase: Optional[dict] = None) -> None:
        """Drain-with-migration sweep: export every in-flight STREAMED
        row (the client is mid-stream — finishing locally would hold
        the drain hostage to the longest decode) and end each local
        stream with ('handoff', payload) for the fabric layer to
        re-home. Stream-queue FIFO guarantees every counted token
        crossed the wire before the handoff terminal, so the importer
        re-emits nothing. Non-streamed rows keep decoding to a normal
        completion — their callers hold a plain future, not a stream
        that can be spliced."""
        from ..fabric import handoff as _ho

        for cs in state.values():
            # a row leaves with the state the device holds: every launched
            # step is read and emitted before any row is exported
            if not self._settle(w, gen, cs, phase):
                return
            with self._cv:
                if w.generation != gen:
                    return
                victims = [s for s, row in cs.rows.items()
                           if row.req.streamed > 0 and
                           not row.req.prefill_only]
            for slot in victims:
                with self._cv:
                    row = cs.rows.get(slot)
                    req = row.req if row is not None else None
                if req is None:
                    continue
                raw = self._export_row(w, gen, cs, slot)
                if raw is None:
                    continue
                self.metrics.on_handoff_out(len(raw), migrated=True)
                done = time.monotonic()
                obj = {"handoff": _ho.to_b64(raw),
                       "streamed": int(req.streamed),
                       "n_tokens": len(req.tokens)}
                with self._cv:
                    cs.rows.pop(slot, None)
                    cs.free.append(slot)
                    rows = self._live_rows.get((w.rid, cs.cap))
                    if rows is not None:
                        rows.pop(slot, None)
                    if req in w.inflight:
                        w.inflight.remove(req)
                    req.owner = None
                info = {"tokens": list(req.tokens),
                        "n_tokens": len(req.tokens),
                        "prompt_tokens": int(req.prompt.size),
                        "finish_reason": "migrated",
                        "handoff": obj["handoff"],
                        "ttft_ms": round(
                            (req.t_first - req.t_enqueue) * 1e3, 3)
                        if req.t_first is not None else None,
                        "latency_ms": round(
                            (done - req.t_enqueue) * 1e3, 3)}
                if req.future.set_result(info):
                    req.stream.put(("handoff", obj))

    def _worker_loop(self, w: ReplicaSlot, gen: int) -> None:
        # per-GENERATION device state: a revived worker starts from
        # fresh zeroed pools; the zombie's buffers die with its frame
        state: Dict[int, _ClassState] = {
            cap: self._alloc_class(cap, w.device) for cap in self._caps}
        # with tracing on, this thread's time is a partition of spans
        # (admit | prefill | decode_step{stage, launch, wait} | emit |
        # idle, and a bare decode_step.wait where a pass reads a step and
        # launches none); those of one pass carry the same `iter`, so a
        # reader adds up a pass's phases without guessing from times. A
        # pass that admits runs, from the admit's end on, under ONE span,
        # `generate.admission`: its self time is the worker's own code
        # between the others, and an idle gap of the device during an
        # admission has a name even where this thread was not the one
        # running. A steady pass has no such bracket. A class's launched,
        # unread steps live in its state (`cs.dev`) from one pass to the
        # next and die with it
        it = 0
        while True:
            it += 1
            phase = {"iter": it, "rid": w.rid} if _tr.enabled() else None
            with _tr.span("generate.admit", "serving", phase), self._cv:
                if w.generation != gen:
                    return
                w.last_beat = time.monotonic()
                admit_ok = w.state == "active" and not self._abort
                admitted = self._admit_locked(w, gen, state) \
                    if admit_ok else []
                depth = len(self._queue)
            admission = _NO_SPAN
            if admitted:
                args = None
                if phase is not None:
                    now_ns = time.perf_counter_ns()
                    for req, _cs, _slot in admitted:
                        _tr.emit_span(
                            "generate.queue_wait", req.t_enq_ns, now_ns,
                            parent=req.ctx, cat="serving",
                            args={"prompt_tokens": int(req.prompt.size),
                                  "queue_depth": depth, **phase})
                    args = {**phase, "admitted": len(admitted),
                            "prompt_tokens": sum(
                                int(req.prompt.size)
                                for req, _cs, _slot in admitted)}
                admission = _tr.span("generate.admission", "serving", args)
            try:
                with admission:
                    if not self._pass(w, gen, state, phase, admitted):
                        return
            except Exception as e:  # noqa: BLE001 — last line of
                # defense: the worker thread must NEVER die (its slots
                # would leak and the queue would starve); requeue the
                # in-flight sequences and keep serving
                with self._cv:
                    owned = w.generation == gen
                if owned:
                    self._fail_rows(w, gen, state, e)

    def _pass(self, w: ReplicaSlot, gen: int, state: Dict[int, _ClassState],
              phase: Optional[dict], admitted: List[tuple]) -> bool:
        """The rest of one pass of the worker loop once `admitted` is
        known: the admitted requests' prefills / imports, a drain's
        migrations, then one step of every class that holds rows. False
        where the worker is to end (superseded, retired)."""
        for req, cs, slot in admitted:
            if req.handoff is not None:
                self._import_one(w, gen, cs, slot, req)
            else:
                self._prefill_one(w, gen, cs, slot, req, phase)
        with self._cv:
            migrating = self._migrate_streams and w.generation == gen
        if migrating:
            self._migrate_rows(w, gen, state, phase)
        if not any(cs.rows for cs in state.values()):
            with self._cv:
                if w.generation != gen:
                    return False
                queue_live = bool(self._queue) and not self._abort
                if w.state in ("draining", "retired") or \
                        (self._closing and not queue_live):
                    w.state = "retired"
                    self._cv.notify_all()
                    return False
                if not queue_live:
                    with _tr.span("generate.idle", "serving", phase):
                        self._cv.wait(0.05)
            return True
        with self._cv:
            aborting = self._abort
        if aborting:
            self._fail_rows(w, gen, state,
                            ServingError(503, "server shutting down"))
            return True
        step = self._spec_step if self._spec else self._decode_step
        for cs in state.values():
            if cs.rows:
                step(w, gen, cs, phase)
        return True


__all__ = ["GenerativeEngine", "GenerateHandle", "GenerativeMetrics",
           "stack_gpt_params", "aggregate_snapshot"]
