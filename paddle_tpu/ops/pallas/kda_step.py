"""One decode step of Kimi delta attention (KDA) over the serving engine's
state, where it lies.

A KDA layer's cache is a float32 matrix state a head, `S [d_k, d_v]`,
whatever the row's context. A token advances it by the gated delta rule
with a decay per key channel (Kimi Linear, arXiv:2510.26692):

    S' = Diag(α) S,   u = S'ᵀ k,   S ← S' + β k (v − u)ᵀ,   o = Sᵀ q

— `(I − β k kᵀ) Diag(α) S + β k vᵀ` in the order that reads S once: a
read and a write of the whole state a step and nothing else of any size,
so the kernel is bound by the state's bytes. `o = S'ᵀ q + (q·k) β (v − u)`
is read off the same pass over S'.

**The state's layout.** One array `[rows, L, T, d_k, d_v]` float32 (the
engine's state: a row a slot, the scratch row included), the value
dimension on the lanes, the key channel a sublane: tiles `[0, H)` are the
heads' matrix states; the tiles after them are the model's own (the
convolutions' windows, models/ling.py) and the kernel neither reads nor
writes them — its block covers the first H tiles alone.

**The kernel** (`kda_step`, one `pallas_call`, the state aliased input to
output): grid (row,); a grid step holds one row's whole state of the
layer in VMEM (H heads, 2 MiB at 32 heads of 128, double-buffered in and
out). A row's operand is eight rows a head — q, k, v, α, β (every lane),
q·k (every lane) — transposed once a group of 16 heads so that each
head's q, k and α reach the sublanes as column forms (a key channel a
sublane, the same value on every lane), kept in VMEM scratch. Then each
head in two passes over its 16 vregs of state: the first accumulates
`S'ᵀ k` and `S'ᵀ q` over the sublanes, the second stores `S' + k dᵀ`
with `d = β (v − u)` — all on the VPU, in float32. The rows' slots, what
each row does and the layer are scalar-prefetched; a padding row (one
that names the scratch slot) MOVES NOTHING, as `retention_step`'s: its
block index stays on the last real row's, so the pipeline copies nothing
in and nothing out and the step computes nothing.

Elsewhere than on a TPU the same mathematics in `jax.numpy`
(`kda_step_twin`), on the rows gathered by slot; the kernel is tested
against it in interpret mode. The plan is a function, `kda_plan`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _im
from .retention_step import _COPY, _HOLD, _STEP, _row_plan

_VMEM_LIMIT = 48 * 2**20
# what a grid step may hold: its pipelined blocks (double-buffered, in and
# out) and scratch
_VMEM_BUDGET = 32 * 2**20
# operand rows a head: q, k, v, α, β, q·k and two of padding
_OPERAND_ROWS = 8
# heads whose operand rows make one 128 x 128 transpose
_GROUP = 128 // _OPERAND_ROWS
_Q, _K, _V, _A, _B, _QK = range(6)


@dataclasses.dataclass(frozen=True)
class KDAPlan:
    """operand_rows: rows of a row's operand (eight a head, padded to whole
    groups)."""
    operand_rows: int


def kda_plan(heads: int, key_dim: int, value_dim: int) -> KDAPlan | None:
    """The kernel's schedule for `heads` heads of `key_dim` x `value_dim`
    states, or None where it does not serve the shape (the twin does): a
    head that does not fill one 128 x 128 tile of lanes and sublanes, or
    a row's state that does not fit VMEM twice over."""
    if key_dim != 128 or value_dim != 128:
        return None
    rows = -(-heads * _OPERAND_ROWS // 128) * 128
    need = (4 * heads * key_dim * value_dim * 4       # state in and out, x2
            + 4 * rows * value_dim * 4                # operand and output
            + 3 * _GROUP * key_dim * value_dim * 4)   # column forms
    if need > _VMEM_BUDGET:
        return None
    return KDAPlan(rows)


# ------------------------------------------------------- in plain jax.numpy --
def advance(state, q, k, v, alpha, beta):
    """One token: the states [..., d_k, d_v] decayed by alpha [..., d_k],
    corrected by the delta rule with beta [...], and read by q.
    -> (o [..., d_v], the states advanced). float32, exact sums."""
    s = state * alpha[..., :, None]
    u = jnp.sum(s * k[..., :, None], axis=-2)
    s = s + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


@functools.partial(jax.jit, static_argnames=("scratch",))
def kda_step_twin(state, layer, slots, q, k, v, alpha, beta, scratch: int):
    """`kda_step` in jax.numpy: the rows' states gathered by slot,
    advanced, read, and written back; a row that names the scratch slot
    writes nothing (its output is nought)."""
    real = slots != scratch
    heads = q.shape[1]
    o, rows = advance(state[slots, layer, :heads], q, k, v, alpha, beta)
    o = jnp.where(real[:, None, None], o, 0.0)
    # a padding row's write lands out of range and is dropped
    wslot = jnp.where(real, slots, state.shape[0])
    return o, state.at[wslot, layer, :heads].set(rows.astype(state.dtype),
                                                 mode="drop")


# ---------------------------------------------------------------- the kernel --
def _kernel(slot_ref, mode_ref, layer_ref, x_ref, s_ref, y_ref, o_ref,
            cols_ref, *, heads, dk):
    """Grid (row i,). x [rows, d_v]: head h's eight operand rows at 8h;
    s / o [H, d_k, d_v]: pool row slot[i]'s states at the layer; y
    [rows, d_v]: head h's output on its eight rows; cols [3·GROUP, d_k,
    d_v] scratch: q, k, α of a group's heads in column form."""
    del slot_ref, layer_ref               # the index maps read them
    f32 = jnp.float32
    tiles = dk // 8
    mode = mode_ref[pl.program_id(0)]

    @pl.when(mode == jnp.int32(_HOLD))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(mode == jnp.int32(_COPY))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)
        o_ref[...] = s_ref[...]

    @pl.when(mode == jnp.int32(_STEP))
    def _():
        for g in range(-(-heads // _GROUP)):
            in_group = min(_GROUP, heads - g * _GROUP)
            xt = x_ref[g * 128:(g + 1) * 128, :].T       # [d_k, 128]
            for hh in range(in_group):
                for j, kind in enumerate((_Q, _K, _A)):
                    c = _OPERAND_ROWS * hh + kind
                    cols_ref[3 * hh + j] = jnp.broadcast_to(
                        xt[:, c:c + 1], (dk, dk))

            def head(hh, carry, g=g):
                h = jnp.int32(g * _GROUP) + hh
                at = pl.multiple_of(h * jnp.int32(_OPERAND_ROWS), 8)
                x = x_ref[pl.ds(at, _OPERAND_ROWS), :]
                qc, kc, ac = (3 * hh, 3 * hh + 1, 3 * hh + 2)

                def decayed(r):
                    return s_ref[h, r * 8:r * 8 + 8, :] \
                        * cols_ref[ac, r * 8:r * 8 + 8, :]

                u = jnp.zeros((8, dk), f32)
                w = jnp.zeros((8, dk), f32)
                for r in range(tiles):
                    s = decayed(r)
                    u = u + s * cols_ref[kc, r * 8:r * 8 + 8, :]
                    w = w + s * cols_ref[qc, r * 8:r * 8 + 8, :]
                u = jnp.sum(u, axis=0, keepdims=True)            # [1, d_v]
                w = jnp.sum(w, axis=0, keepdims=True)
                d = x[_B:_B + 1] * (x[_V:_V + 1] - u)
                for r in range(tiles):
                    o_ref[h, r * 8:r * 8 + 8, :] = decayed(r) \
                        + cols_ref[kc, r * 8:r * 8 + 8, :] * d
                y_ref[pl.ds(at, _OPERAND_ROWS), :] = jnp.broadcast_to(
                    w + x[_QK:_QK + 1] * d, (_OPERAND_ROWS, dk))
                return carry

            jax.lax.fori_loop(jnp.int32(0), jnp.int32(in_group), head,
                              jnp.int32(0))


def _operand(q, k, v, alpha, beta, rows: int):
    """[b, rows, d_v]: head h's q, k, v, α, β (every lane), q·k (every
    lane) at rows 8h .. 8h + 5, float32."""
    b, heads, dk = q.shape
    f32 = jnp.float32
    lanes = (b, heads, 1, dk)
    x = jnp.concatenate([
        q[:, :, None], k[:, :, None], v[:, :, None], alpha[:, :, None],
        jnp.broadcast_to(beta[:, :, None, None], lanes),
        jnp.broadcast_to(jnp.sum(q * k, -1)[:, :, None, None], lanes),
        jnp.zeros((b, heads, _OPERAND_ROWS - 6, dk), f32)],
        axis=2).astype(f32).reshape(b, heads * _OPERAND_ROWS, dk)
    return jnp.pad(x, ((0, 0), (0, rows - heads * _OPERAND_ROWS), (0, 0)))


@functools.partial(jax.jit, static_argnames=("scratch", "interpret"))
def kda_step(state, layer, slots, q, k, v, alpha, beta, *, scratch: int,
             interpret: bool = False):
    """One token a row through one KDA layer, in place. (Jitted, the layer
    an argument: a program of L layers traces and lowers the kernel once.)

    state [rows, L, T, d_k, d_v] float32 (T ≥ H: the first H tiles are
    the heads' states); layer a scalar; slots [b] the state's row of each
    decoding row (`scratch`: padding); q, k [b, H, d_k], v [b, H, d_v],
    alpha [b, H, d_k] the decay, beta [b, H], float32.
    -> (o [b, H, d_v], the state with the real rows' slots advanced)."""
    b, heads, dk = q.shape
    plan = kda_plan(heads, dk, v.shape[-1])
    if plan is None or state.shape[2] < heads or state.shape[3] != dk:
        raise ValueError(
            f"kda_step does not serve {heads} heads of {dk} x "
            f"{v.shape[-1]} over a state {state.shape}: see kda_plan")
    x = _operand(q, k, v, alpha, beta, plan.operand_rows)
    block_slot, mode = _row_plan(slots.astype(jnp.int32), scratch)
    state_spec = pl.BlockSpec(
        (None, None, heads, dk, dk),
        _im(lambda i, slot, mode, layer: (slot[i], layer[0], 0, 0, 0)))
    operand = pl.BlockSpec((None, plan.operand_rows, dk),
                           _im(lambda i, *_: (i, 0, 0)))
    y, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads, dk=dk),
        name="kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[operand, state_spec],
            out_specs=[operand, state_spec],
            scratch_shapes=[pltpu.VMEM((3 * _GROUP, dk, dk), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, plan.operand_rows, dk), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: three prefetched scalars, x, then the state
        input_output_aliases={4: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(block_slot, mode, jnp.asarray(layer, jnp.int32).reshape(1), x, state)
    o = y[:, :heads * _OPERAND_ROWS].reshape(b, heads, _OPERAND_ROWS, dk)
    return o[:, :, 0], state


def kda(state, layer, slots, q, k, v, alpha, beta, *, scratch: int):
    """One token a row through one KDA layer over the state where it lies:
    the kernel where the program is lowered for a TPU and the kernel
    serves the shape (`kda_plan`), the twin elsewhere — decided at
    lowering, so a compile for a described chip takes the kernel."""
    args = (state, jnp.asarray(layer, jnp.int32), slots, q, k, v, alpha,
            beta)
    if kda_plan(q.shape[1], q.shape[2], v.shape[2]) is None:
        return kda_step_twin(*args, scratch=scratch)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kda_step, scratch=scratch),
        default=functools.partial(kda_step_twin, scratch=scratch))


__all__ = ["KDAPlan", "kda_plan", "advance", "kda", "kda_step",
           "kda_step_twin"]
