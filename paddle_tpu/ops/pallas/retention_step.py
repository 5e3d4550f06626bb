"""One decode step of power retention (degree 2) over the serving engine's
state, where it lies.

A row's cache in a retention layer is no K/V row but a fixed-size state a
K/V head: `S = sum_s decay(s..t) phi(k_s) v_s^T` and the normaliser
`z = sum_s decay(s..t) phi(k_s)`, with `phi(x)` the second symmetric power
of `x / sqrt(Dh)`, so that `phi(q) . phi(k) = (q . k)^2 / Dh`. A step is

    S <- g S + phi(k) v^T,   z <- g z + phi(k),
    y_h = phi(q_h)^T S / phi(q_h)^T z      for the G query heads h of the head

— a read and a write of the whole state and nothing else of any size: the
kernel is bound by the state's bytes.

**The state's layout** (`state_rows`; one array `[rows, L, Hkv, R, Dh]`
float32, the value dimension on the lanes). The Dh x Dh products `x_a x_b`
are kept as the upper triangle of 8 x 8 tiles: tile column `B` holds, for
every `a < 8B + 8`, the eight products with `b = 8B .. 8B + 7` on eight
sublanes (one vreg of eight rows of the state): `32 nb (nb + 1)` rows with
`nb = Dh / 8` — 8,704 for Dh = 128, where the bare triangle has 8,256
(+5.4 %: the diagonal tiles hold both `(a, b)` and `(b, a)`). The weights
fold into the K side: `phi_k(k)` carries `1 / Dh`, twice where the tile
lies off the diagonal, and `phi_q(q)` is the bare product, so
`phi_q(q) . phi_k(k) = (q . k)^2 / Dh` exactly as with `sqrt(2)` on both
sides. The normaliser is kept as the full square `Z = sum decay k k^T / Dh`
(`q^T Z q = phi(q)^T z`), the last Dh rows of the same array: R = 8,832.

**The kernel** (`retention_step`, one `pallas_call`, the state aliased
input to output): grid (row, K/V head); a grid step holds one head's state
of one row in VMEM (4.5 MB, double-buffered in and out), and for each tile
column walks its vregs once: load, scale by the row's `g`, add
`phi_k(k) v^T`, store, and add the vreg into the five query heads' sums —
on the VPU, in float32: `phi(q)^T z` is a sum of thousands of signed terms
that cancels to a small number, and bfloat16 operands there cost tenths of
the result. The rows' slots, what each row does and the layer are
scalar-prefetched, as `decode_attn`'s are; a row's `k_a`, `q_{h,a}` reach
a vreg as one row of the operand's column form, read over the eight
sublanes (a scalar a vreg through SMEM was bound by the scalar unit: 25
bundles a vreg of state against 8.5). A padding row (one that names the
scratch slot) MOVES NOTHING: its block index stays on the last block of
the real row before it, so the pipeline sees an unchanged index, copies
nothing in and nothing out, and the step computes nothing. (Padding rows
before any real row — the engine puts real rows first — copy the scratch
row's blocks through unchanged.)

Elsewhere than on a TPU the same mathematics in `jax.numpy`
(`retention_step_twin`), on the rows gathered by slot; the kernel is tested
against it in interpret mode. The plan is a function, `state_block_plan`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _im

_VMEM_LIMIT = 48 * 2**20
# what a grid step may hold by `StatePlan.vmem_bytes`
_VMEM_BUDGET = 32 * 2**20

_HOLD, _STEP, _COPY = 0, 1, 2     # what a row's grid steps do


def tile_columns(head_dim: int) -> int:
    return head_dim // 8


def product_rows(head_dim: int) -> int:
    """Rows of the state that hold `phi(k) v^T`: the upper triangle of
    8 x 8 tiles, eight rows a (tile column, a)."""
    nb = tile_columns(head_dim)
    return 32 * nb * (nb + 1)


def state_rows(head_dim: int) -> int:
    """Rows `R` of one head's state `[R, Dh]`: the products, then the
    normaliser's full square."""
    return product_rows(head_dim) + head_dim


def _column_base(B: int) -> int:
    return 32 * B * (B + 1)


@dataclasses.dataclass(frozen=True)
class StatePlan:
    """rows: one head's state `[rows, head_dim]`, the block a grid step
    holds; operand_rows: rows of the step's small operand (the G query
    heads, k, v, g); vmem_bytes: the step's pipelined blocks
    (double-buffered, in and out) and scratch."""
    rows: int
    operand_rows: int
    vmem_bytes: int


def state_block_plan(head_dim: int, groups: int) -> StatePlan | None:
    """The kernel's schedule for heads of `head_dim` under `groups` query
    heads a K/V head, or None where the kernel does not serve the shape
    (the twin does): a head that does not fill whole 128-lane tiles, or a
    head's state that does not fit VMEM twice over."""
    if head_dim % 128:
        return None
    rows = state_rows(head_dim)
    operand_rows = -(-(groups + 3) // 8) * 8
    need = (4 * rows * head_dim * 4               # state in and out, twice
            + (groups + 1) * head_dim * head_dim * 4     # column forms
            + 6 * operand_rows * head_dim * 4)
    if need > _VMEM_BUDGET:
        return None
    return StatePlan(rows, operand_rows, need)


# ------------------------------------------------------- in plain jax.numpy --
def phi_q(x):
    """The bare products of x [..., Dh] in the state's row order
    [..., product_rows]."""
    nb = tile_columns(x.shape[-1])
    return jnp.concatenate([
        (x[..., :8 * B + 8, None] * x[..., None, 8 * B:8 * B + 8]).reshape(
            x.shape[:-1] + (-1,)) for B in range(nb)], axis=-1)


def phi_k(x):
    """`phi_q` with the weights folded in: `1 / Dh`, twice off the
    diagonal tiles, so that `phi_q(q) . phi_k(k) = (q . k)^2 / Dh`."""
    Dh = x.shape[-1]
    w = np.concatenate([
        np.repeat(np.where(np.arange(8 * B + 8) < 8 * B, 2.0, 1.0), 8)
        for B in range(tile_columns(Dh))]).astype(np.float32) / Dh
    return phi_q(x) * w


def advance(state, k, v, g):
    """The state [..., R, Dh] after one token: decayed by g [...], plus
    the token's `phi_k(k) v^T` and `k k^T / Dh` (k, v [..., Dh])."""
    Dh = k.shape[-1]
    add = jnp.concatenate([phi_k(k)[..., :, None] * v[..., None, :],
                           k[..., :, None] * k[..., None, :] / Dh], axis=-2)
    return state * g[..., None, None] + add


def read(state, q):
    """y [..., G, Dh] of the query heads q [..., G, Dh] over the state
    [..., R, Dh]: `phi_q(q)^T S / q^T Z q`, in float32 at full precision."""
    rs = product_rows(q.shape[-1])
    hi = jax.lax.Precision.HIGHEST
    num = jnp.einsum("...gd,...dv->...gv", phi_q(q), state[..., :rs, :],
                     precision=hi)
    den = jnp.einsum("...ga,...ab,...gb->...g", q, state[..., rs:, :], q,
                     precision=hi)
    return num / den[..., None]


@functools.partial(jax.jit, static_argnames=("scratch",))
def retention_step_twin(state, layer, slots, q, k, v, g, scratch: int):
    """`retention_step` in jax.numpy: the rows' states gathered by slot,
    advanced, read, and written back; a row that names the scratch slot
    writes nothing (its output is nought)."""
    real = slots != scratch
    rows = advance(state[slots, layer], k, v, g)
    y = jnp.where(real[:, None, None, None], read(rows, q), 0.0)
    # a padding row's write lands out of range and is dropped
    wslot = jnp.where(real, slots, state.shape[0])
    return y, state.at[wslot, layer].set(rows, mode="drop")


# ---------------------------------------------------------------- the kernel --
def _kernel(slot_ref, mode_ref, layer_ref, x_ref, s_ref, y_ref,
            o_ref, cols_ref, *, head_dim, groups):
    """Grid (row i, K/V head j). x [rows8, Dh]: the row's q_0 .. q_{G-1},
    k, v of this head and g (row G+2, every lane); s / o [R, Dh]: the
    head's state of pool row slot[i] at the layer; y [rows8, Dh]: the G
    outputs; cols [G+1, Dh, Dh] scratch."""
    del slot_ref, layer_ref               # the index maps read them
    f32 = jnp.float32
    Dh, G = head_dim, groups
    nb, rs = tile_columns(Dh), product_rows(Dh)
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
        x = x_ref[...]
        g = jnp.broadcast_to(x[G + 2:G + 3, :], (8, Dh))
        # column forms: cols[r][b, :] = x[r, b] on every lane
        xt = jnp.concatenate(
            [x, jnp.zeros((Dh - x.shape[0], Dh), f32)], axis=0).T
        for r in range(G + 1):
            cols_ref[r] = jnp.broadcast_to(xt[:, r:r + 1], (Dh, Dh))
        v_rows = jnp.broadcast_to(x[G + 1:G + 2, :], (8, Dh))
        acc = [jnp.zeros((8, Dh), f32) for _ in range(G)]
        for B in range(nb):
            base = _column_base(B)
            # k_b v^T / Dh for the column's eight b: one vreg, the part of
            # phi_k(k) v^T that does not change with a
            kv = cols_ref[G, 8 * B:8 * B + 8, :] * v_rows * f32(1.0 / Dh)

            def one(a, u, weight, base=base, kv=kv):
                """The vreg of (tile column B, a): advanced, stored, and
                added into the G heads' sums with q_{h,a}. x[r, a] on every
                sublane and lane is row a of r's column form, read over
                the eight sublanes."""
                at = pl.multiple_of(jnp.int32(base) + a * jnp.int32(8), 8)

                def uniform(r):
                    return jnp.broadcast_to(cols_ref[r, pl.ds(a, 1), :],
                                            (8, Dh))

                new = s_ref[pl.ds(at, 8), :] * g \
                    + kv * (uniform(G) * weight)
                o_ref[pl.ds(at, 8), :] = new
                return tuple(u[h] + new * uniform(h) for h in range(G))

            def tile(i, u, B=B, one=one):
                # eight values of `a` a pass; tile B is the diagonal one,
                # whose products are kept on both sides
                weight = jnp.where(i < jnp.int32(B), f32(2.0), f32(1.0))
                for c in range(8):
                    u = one(i * jnp.int32(8) + jnp.int32(c), u, weight)
                return u

            u = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(B + 1), tile,
                tuple(jnp.zeros((8, Dh), f32) for _ in range(G)))
            for h in range(G):
                acc[h] = acc[h] + cols_ref[h, 8 * B:8 * B + 8, :] * u[h]
        # the normaliser's square
        k_rows = jnp.broadcast_to(x[G:G + 1, :], (Dh, Dh))
        z = s_ref[rs:rs + Dh, :] * x[G + 2:G + 3, :] \
            + cols_ref[G] * k_rows * f32(1.0 / Dh)
        o_ref[rs:rs + Dh, :] = z
        out = []
        for h in range(G):
            num = jnp.sum(acc[h], axis=0, keepdims=True)          # [1, Dh]
            q_rows = jnp.broadcast_to(x[h:h + 1, :], (Dh, Dh))
            den = jnp.sum(jnp.sum(cols_ref[h] * q_rows * z, axis=0,
                                  keepdims=True), axis=1, keepdims=True)
            out.append(num / den)
        out.append(jnp.zeros((y_ref.shape[0] - G, Dh), f32))
        y_ref[...] = jnp.concatenate(out, axis=0)


def _row_plan(slots, scratch: int):
    """(block slot, mode) of each row: a real row steps its own slot; a
    padding row holds the block of the last real row before it; padding
    rows before any real row copy the scratch row through."""
    real = slots != scratch
    idx = jnp.arange(slots.shape[0], dtype=jnp.int32)
    last_real = jax.lax.cummax(jnp.where(real, idx, -1), axis=0)
    held = slots[jnp.maximum(last_real, 0)]
    block_slot = jnp.where(real | (last_real < 0), slots, held)
    mode = jnp.where(real, _STEP, jnp.where(last_real < 0, _COPY, _HOLD))
    return block_slot.astype(jnp.int32), mode.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scratch", "interpret"))
def retention_step(state, layer, slots, q, k, v, g, *, scratch: int,
                   interpret: bool = False):
    """One token a row through one retention layer, in place. (Jitted, the
    layer an argument: a program of L layers traces and lowers the kernel
    once, not L times — its body is some thousands of equations.)

    state [rows, L, Hkv, R, Dh] float32 (`state_rows`); layer a scalar;
    slots [b] the state's row of each decoding row (`scratch`: padding);
    q [b, Hkv, G, Dh], k, v [b, Hkv, Dh], g [b, Hkv] the decay, float32.
    -> (y [b, Hkv, G, Dh], the state with the real rows' slots advanced)."""
    b, kv_heads, groups, head_dim = q.shape
    plan = state_block_plan(head_dim, groups)
    if plan is None or state.shape[3] != plan.rows:
        raise ValueError(
            f"retention step does not serve heads of {head_dim} under "
            f"{groups} query heads over a state {state.shape}: see "
            f"state_block_plan")
    rows8 = plan.operand_rows
    x = jnp.concatenate([
        q, k[:, :, None], v[:, :, None],
        jnp.broadcast_to(g[:, :, None, None], (b, kv_heads, 1, head_dim)),
        jnp.zeros((b, kv_heads, rows8 - groups - 3, head_dim), q.dtype)],
        axis=2).astype(jnp.float32)
    block_slot, mode = _row_plan(slots.astype(jnp.int32), scratch)
    state_spec = pl.BlockSpec(
        (None, None, None, plan.rows, head_dim),
        _im(lambda i, j, slot, mode, layer: (
            slot[i], layer[0],
            jnp.where(mode[i] == jnp.int32(_HOLD), jnp.int32(kv_heads - 1),
                      j), 0, 0)))
    operand = pl.BlockSpec((None, None, rows8, head_dim),
                           _im(lambda i, j, *_: (i, j, 0, 0)))

    y, state = pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim, groups=groups),
        name="retention_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, kv_heads),
            in_specs=[operand, state_spec],
            out_specs=[operand, state_spec],
            scratch_shapes=[
                pltpu.VMEM((groups + 1, head_dim, head_dim), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, kv_heads, rows8, head_dim),
                                 jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: three prefetched scalars, x, then the state
        input_output_aliases={4: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )(block_slot, mode, jnp.asarray(layer, jnp.int32).reshape(1), x, state)
    return y[:, :, :groups], state


def retention(state, layer, slots, q, k, v, g, *, scratch: int):
    """One token a row through one retention layer over the state where it
    lies: the kernel where the program is lowered for a TPU and the kernel
    serves the shape (`state_block_plan`), the twin elsewhere — decided at
    lowering, so a compile for a described chip takes the kernel."""
    if state_block_plan(q.shape[-1], q.shape[-2]) is None:
        return retention_step_twin(state, jnp.asarray(layer, jnp.int32),
                                   slots, q, k, v, g, scratch=scratch)
    return jax.lax.platform_dependent(
        state, jnp.asarray(layer, jnp.int32), slots, q, k, v, g,
        tpu=functools.partial(retention_step, scratch=scratch),
        default=functools.partial(retention_step_twin, scratch=scratch))


__all__ = ["StatePlan", "state_block_plan", "state_rows", "product_rows",
           "phi_q", "phi_k", "advance", "read", "retention",
           "retention_step", "retention_step_twin"]
