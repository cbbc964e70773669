"""Mamba2 SSD chunked scan as one Pallas kernel pair, forward and backward.

The forward sweeps the chunks of each sequence in order. Per chunk it
builds, for every head, the decay tile L[i, j] = exp(cum_i - cum_j)
(i >= j) and the scores C B^T o L in VMEM, adds the carried state's
contribution and the D skip, and updates the (N, P) state, also in VMEM.
Only the chunk's inputs and outputs and the f32 state at the start of
each chunk (the backward's residual, ``(B, n_chunks, H/g, g*P, N)``)
reach HBM; the XLA twin (``ref.ssd_chunked``) writes the (Q, Q, H) tiles
and, under autodiff, stacks them per chunk. The backward sweeps the
chunks in reverse, carries the state's cotangent in VMEM, rebuilds each
decay tile from the saved inputs and emits dx, d(cum), d(dt), dD, dB and
dC; dB and dC are shared by the heads of a group, so they accumulate over
all head blocks of a chunk. ``jax.custom_vjp`` joins the pair.

B and C come in G groups, head h reading group h // (H/G). The groups
are folded into the grid's batch axis: after the sequence-minor
transposes, x's ``(B, H*P, S)`` is ``(B*G, H/G*P, S)`` and B's
``(B, G*N, S)`` is ``(B*G, N, S)`` with no copy, so each folded row is a
one-group problem of H/G heads; only D is indexed by the group. At one
group nothing is folded.

Each grid chunk is swept in sub-chunks of 128 steps, one lane tile: the
SSD result does not depend on the chunk length, and a (128, 128) decay
tile holds half the elements per step of a (256, 256) one.

Layout: sequence-minor throughout. x, y and their cotangents are
(B, H*P, S), B, C and their gradients (B, N, S), the per-step log decays
and dt scales (B, H, S): the layouts the causal convolutions, the gated
norm and the out-projection around the mixer keep them in, so XLA writes
no relayout of them to HBM, and the kernels transpose no (Q, H*P) tile.
In VMEM a head is P rows and a step one lane; the state of a head is
(P, N). The state's cotangent is also kept as (N, P), so that the
gradients of B and C are plain matmuls over a lane group of g = 128/P
heads.
Mosaic has no cumsum: the sub-chunk-local cumulative sum of the log
decays stays an XLA op on its (B, H, S) array.

Numerics follow the XLA twin: cumsums, exponents, decays, the state and
y before its cast are f32; every matmul takes f32 operands at the default
precision, as XLA's f32 dots do; the exponent is masked above the
diagonal before ``exp``, so strong decays give no inf and no NaN
gradient. The grid runs (batch, chunk, head block) with the head blocks
innermost: C, B and their products are loaded once per chunk, and the
head-shared gradients accumulate in a resident output block.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 1024            # target rows (heads x P) of a head block
STATE_VMEM_BYTES = 8 << 20   # f32 state of one sequence, held in VMEM
VMEM_LIMIT = 64 << 20


class Plan(NamedTuple):
    """Static block sizes of one SSD problem."""
    q: int          # grid chunk length
    sq: int         # sub-chunk length
    hb: int         # heads per block
    g: int          # heads per lane group of 128 rows
    p: int          # head width
    interpret: bool


def plan_blocks(x_shape, n: int, chunk: int, interpret: bool = False,
                groups: int = 1) -> Optional[Plan]:
    """Block sizes for x of ``(B, S, H, P)`` and state width ``n`` with
    B/C in ``groups`` groups (planned per group of H/G heads), or None
    where the blocks cannot tile the input (the caller then runs the XLA
    twin). Compiled, a block's last two dims are multiples of (8, 128)
    (16 rows for bf16) or whole array dims; interpreted, any size goes."""
    _, s, h, p = x_shape
    if h % groups:
        return None
    h //= groups
    q = min(chunk, s)
    tiled = lambda size, unit, full: interpret or size % unit == 0 or size == full
    if s % q or not (tiled(q, LANES, s) and tiled(q, 16, s)):
        return None
    g = min(h, max(1, LANES // p))
    if h % g or not (tiled(g * p, LANES, h * p) and tiled(p, 16, h * p)):
        return None
    if h * n * p * 4 > STATE_VMEM_BYTES:
        return None
    blocks = [d for d in range(g, h + 1, g) if h % d == 0 and tiled(d, 8, h)]
    fit = [d for d in blocks if d * p <= BLOCK_ROWS]
    sq = LANES if q % LANES == 0 else q
    return Plan(q, sq, max(fit or blocks[:1]), g, p, interpret)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_t(a, b):
    """a @ b^T: contracts the lanes of both."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _t_dot(a, b):
    """a^T @ b: contracts the rows of both."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lanes_of(plan: Plan, rows):
    """(1, g*P) from g (1, width) rows that each hold one value: lane l
    takes head l // P's."""
    gw = plan.g * plan.p
    head = jax.lax.broadcasted_iota(jnp.int32, (1, gw), 1) // plan.p
    out = jnp.broadcast_to(rows[-1][:, :1], (1, gw))
    for g in range(plan.g - 2, -1, -1):
        out = jnp.where(head == g, jnp.broadcast_to(rows[g][:, :1], (1, gw)), out)
    return out


class _Head(NamedTuple):
    """One head over one sub-chunk: rows of P, lanes of sq steps."""
    cum: jnp.ndarray        # (1, sq) sub-chunk-local cumsum of the log decays
    dt: jnp.ndarray         # (1, sq)
    decay_in: jnp.ndarray   # (1, sq) exp(cum): decay from the sub-chunk's start
    decay_out: jnp.ndarray  # (1, sq) exp(last - cum): decay to its end
    decay_all: jnp.ndarray  # (1, sq) exp(last) in every lane
    x: jnp.ndarray          # (P, sq) inputs, f32
    xs: jnp.ndarray         # (P, sq) dt-scaled inputs
    d: jnp.ndarray          # () the head's D


def _head(plan: Plan, x_ref, cum_ref, dt_ref, d_ref, h: int, r: slice,
          d_group=None) -> _Head:
    """``d_group``: (G, H/G) where the grid's batch axis folds G groups,
    so that a row's D is its group's."""
    sq = plan.sq
    cum = cum_ref[0, h:h + 1, r]
    dt = dt_ref[0, h:h + 1, r]
    last = jnp.broadcast_to(cum[:, sq - 1:sq], (1, sq))
    x = x_ref[0, h * plan.p:(h + 1) * plan.p, r].astype(jnp.float32)
    di = pl.program_id(2) * plan.hb + h
    if d_group is not None:
        di = di + (pl.program_id(0) % d_group[0]) * d_group[1]
    d = d_ref[di]
    return _Head(cum, dt, jnp.exp(cum), jnp.exp(last - cum), jnp.exp(last), x, x * dt, d)


def _decay(tri, cols, head: _Head, h: int):
    """One head's decay tile L[i, j] = exp(cum_i - cum_j) for i >= j, else
    0, (sq, sq); the exponent is masked before ``exp``, since above the
    diagonal it overflows for strong decays."""
    return jnp.exp(jnp.where(tri, cols[:, h:h + 1] - head.cum, -jnp.inf))


def _rows(plan: Plan, heads, width: int):
    """(g*P, width) of per-head (1, ·) rows, each repeated over its head's
    P rows; a row of one value (its first lane) is spread over ``width``."""
    return jnp.concatenate([jnp.broadcast_to(r if r.shape[1] == width else r[:, :1],
                                             (plan.p, width)) for r in heads], axis=0)


def _fwd_kernel(x_ref, cum_ref, dt_ref, bt_ref, ct_ref, d_ref, y_ref, final_ref, *rest,
                plan: Plan, save_states: bool, d_group):
    states_ref, cb_ref = rest if save_states else (None, rest[0])
    ci, hi = pl.program_id(1), pl.program_id(2)
    sq, p, n = plan.sq, plan.p, final_ref.shape[-1]
    groups, subs = plan.hb // plan.g, plan.q // plan.sq
    chunk = lambda ref, s: ref[0, :, s * sq:(s + 1) * sq].astype(jnp.float32)

    @pl.when((ci == 0) & (hi == 0))
    def _zero_state():
        final_ref[...] = jnp.zeros_like(final_ref)

    @pl.when(hi == 0)
    def _cb():
        for s in range(subs):
            cb_ref[s] = _t_dot(chunk(ct_ref, s), chunk(bt_ref, s))      # C B^T

    if save_states:
        states_ref[0, 0] = final_ref[0, pl.ds(hi * groups, groups)]
    ii = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
    tri = ii >= jj
    for s in range(subs):
        r = slice(s * sq, (s + 1) * sq)
        ct, bt = chunk(ct_ref, s), chunk(bt_ref, s)                     # (N, sq)
        cols = cum_ref[0, :, r].T                                       # (sq, hb)
        for k in range(groups):
            j, heads = hi * groups + k, range(k * plan.g, (k + 1) * plan.g)
            hs = [_head(plan, x_ref, cum_ref, dt_ref, d_ref, h, r, d_group) for h in heads]
            state = final_ref[0, j]                                     # (g*P, N)
            y_off = _dot(state, ct) * _rows(plan, [hd.decay_in for hd in hs], sq)
            for g, (h, hd) in enumerate(zip(heads, hs)):
                y = (y_off[g * p:(g + 1) * p] + hd.d * hd.x
                     + _dot_t(hd.xs, cb_ref[s] * _decay(tri, cols, hd, h)))
                y_ref[0, h * p:(h + 1) * p, r] = y.astype(y_ref.dtype)
            xs_out = jnp.concatenate([hd.xs * hd.decay_out for hd in hs], axis=0)
            final_ref[0, j] = (state * _rows(plan, [hd.decay_all for hd in hs], n)
                               + _dot_t(xs_out, bt))


def _bwd_kernel(x_ref, dy_ref, cum_ref, dt_ref, bt_ref, ct_ref, d_ref, states_ref,
                dfinal_ref, dfinal_s_ref, dx_ref, dcum_ref, ddt_ref, dd_ref, dbt_ref, dct_ref,
                dstate_ref, dstate_s_ref, cb_ref, dcb_ref, cols_ref, *, plan: Plan, d_group):
    ri, hi = pl.program_id(1), pl.program_id(2)
    sq, p, n = plan.sq, plan.p, dstate_ref.shape[-1]
    groups, subs = plan.hb // plan.g, plan.q // plan.sq
    chunk = lambda ref, s: ref[0, :, s * sq:(s + 1) * sq].astype(jnp.float32)
    block = pl.ds(hi * groups, groups)

    @pl.when(ri == 0)
    def _final_cotangent():
        dstate_ref[block] = dfinal_ref[0, block]
        dstate_s_ref[block] = dfinal_s_ref[0, block]

    @pl.when(hi == 0)
    def _per_chunk():
        for s in range(subs):
            cb_ref[s] = _t_dot(chunk(ct_ref, s), chunk(bt_ref, s))      # C B^T
        dcb_ref[...] = jnp.zeros_like(dcb_ref)
        dbt_ref[...] = jnp.zeros_like(dbt_ref)
        dct_ref[...] = jnp.zeros_like(dct_ref)

    ii = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
    tri = ii >= jj
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, sq), 1) == sq - 1
    for s in range(subs):
        cols_ref[s] = cum_ref[0, :, s * sq:(s + 1) * sq].T              # (sq, hb)

    for k in range(groups):
        j, heads = hi * groups + k, range(k * plan.g, (k + 1) * plan.g)
        # The states at each sub-chunk's start, rows (T) and columns (S) per head.
        state_t = [states_ref[0, 0, k]]                                 # (g*P, N)
        state_s = [state_t[0].T]                                        # (N, g*P)
        for s in range(subs - 1):
            r = slice(s * sq, (s + 1) * sq)
            hs = [_head(plan, x_ref, cum_ref, dt_ref, d_ref, h, r, d_group) for h in heads]
            xs_out = jnp.concatenate([hd.xs * hd.decay_out for hd in hs], axis=0)
            bt = chunk(bt_ref, s)
            state_t.append(state_t[-1] * _rows(plan, [hd.decay_all for hd in hs], n)
                           + _dot_t(xs_out, bt))
            state_s.append(state_s[-1] * _lanes_of(plan, [hd.decay_all for hd in hs])
                           + _dot_t(bt, xs_out))

        for s in reversed(range(subs)):
            r = slice(s * sq, (s + 1) * sq)
            ct, bt = chunk(ct_ref, s), chunk(bt_ref, s)
            hs = [_head(plan, x_ref, cum_ref, dt_ref, d_ref, h, r, d_group) for h in heads]
            state, dstate = state_t[s], dstate_ref[j]                   # start; end's cotangent
            dy = dy_ref[0, k * plan.g * p:(k + 1) * plan.g * p, r].astype(jnp.float32)
            dy_in = dy * _rows(plan, [hd.decay_in for hd in hs], sq)
            xs_out = jnp.concatenate([hd.xs * hd.decay_out for hd in hs], axis=0)
            bd = _dot(dstate, bt)                                       # (g*P, sq)
            t_in = dy_in * _dot(state, ct)
            t_out = xs_out * bd
            t_state = state * dstate
            dcb = dcb_ref[s]
            for g, (h, hd) in enumerate(zip(heads, hs)):
                rows = slice(g * p, (g + 1) * p)
                dy_g = dy[rows]
                decay = _decay(tri, cols_ref[s], hd, h)
                scores = cb_ref[s] * decay                                # S[i, j]
                dxs_diag = _dot(dy_g, scores)
                dxs = bd[rows] * hd.decay_out + dxs_diag
                dcb = dcb + _t_dot(dy_g, hd.xs) * decay                   # dS o L
                # d(cum) of the scores' exponent, sum_j dS o S minus its
                # transpose's, without the (sq, sq) product: the row sums
                # are dy . y_diag and the column sums xs . dxs_diag.
                end = (jnp.sum(t_out[rows])
                       + jnp.sum(t_state[rows]) * hd.decay_all[:, :1])
                dcum_ref[0, h:h + 1, r] = (
                    jnp.sum(t_in[rows] - t_out[rows] + dy_g * _dot_t(hd.xs, scores)
                            - hd.xs * dxs_diag, axis=0, keepdims=True)
                    + jnp.where(at_end, end, 0.0))
                ddt_ref[0, h:h + 1, r] = jnp.sum(dxs * hd.x, axis=0, keepdims=True)
                dd_ref[0, h:h + 1, r] = jnp.sum(dy_g * hd.x, axis=0, keepdims=True)
                dx_ref[0, h * p:(h + 1) * p, r] = (dxs * hd.dt + hd.d * dy_g).astype(dx_ref.dtype)
            dcb_ref[s] = dcb
            dstate_s = dstate_s_ref[j]
            dct_ref[0, :, r] = dct_ref[0, :, r] + _dot(state_s[s], dy_in)
            dbt_ref[0, :, r] = dbt_ref[0, :, r] + _dot(dstate_s, xs_out)
            dstate_ref[j] = (dstate * _rows(plan, [hd.decay_all for hd in hs], n)
                             + _dot_t(dy_in, ct))
            dstate_s_ref[j] = (dstate_s * _lanes_of(plan, [hd.decay_all for hd in hs])
                               + _dot_t(ct, dy_in))

    @pl.when(hi == pl.num_programs(2) - 1)
    def _shared():
        for s in range(subs):
            r = slice(s * sq, (s + 1) * sq)
            dct_ref[0, :, r] = dct_ref[0, :, r] + _dot_t(chunk(bt_ref, s), dcb_ref[s])
            dbt_ref[0, :, r] = dbt_ref[0, :, r] + _dot(chunk(ct_ref, s), dcb_ref[s])


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT)


class _Specs(NamedTuple):
    seq: pl.BlockSpec       # x, y and their cotangents, (B, H*P, S)
    row: pl.BlockSpec       # per head and step, (B, H, S)
    nq: pl.BlockSpec        # B, C and their gradients, (B, N, S)
    d: pl.BlockSpec         # D, (H,), in SMEM
    states: pl.BlockSpec    # a chunk's start state, (B, n_chunks, H/g, g*P, N)
    whole: pl.BlockSpec     # a sequence's state, (B, H/g, g*P, N) or (B, H/g, N, g*P)


def _specs(plan: Plan, n: int, ng: int, chunk_of) -> _Specs:
    q, hb, gw = plan.q, plan.hb, plan.g * plan.p
    return _Specs(
        pl.BlockSpec((1, hb * plan.p, q), lambda b, c, h: (b, h, chunk_of(c))),
        pl.BlockSpec((1, hb, q), lambda b, c, h: (b, h, chunk_of(c))),
        pl.BlockSpec((1, n, q), lambda b, c, h: (b, 0, chunk_of(c))),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, hb // plan.g, gw, n), lambda b, c, h: (b, chunk_of(c), h, 0, 0)),
        pl.BlockSpec((1, ng, gw, n), lambda b, c, h: (b, 0, 0, 0)))


def _grid(plan: Plan, x_t):
    b, hp, s = x_t.shape
    return b, s // plan.q, hp // plan.p // plan.hb, hp // (plan.g * plan.p)


def _d_group(cum, D):
    """(G, H/G) where the batch axis folds G > 1 groups of B/C, else None."""
    groups = D.shape[0] // cum.shape[1]
    return (groups, cum.shape[1]) if groups > 1 else None


def _fwd(plan: Plan, x_t, cum, dts, bt, ct, D, save_states: bool):
    b, nc, nh, ng = _grid(plan, x_t)
    n, gw = bt.shape[1], plan.g * plan.p
    sp = _specs(plan, n, ng, lambda c: c)
    out_specs = [sp.seq, sp.whole]
    out_shape = [jax.ShapeDtypeStruct(x_t.shape, x_t.dtype),
                 jax.ShapeDtypeStruct((b, ng, gw, n), jnp.float32)]
    if save_states:
        out_specs.append(sp.states)
        out_shape.append(jax.ShapeDtypeStruct((b, nc, ng, gw, n), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan, save_states=save_states,
                          d_group=_d_group(cum, D)),
        grid=(b, nc, nh),
        in_specs=[sp.seq, sp.row, sp.row, sp.nq, sp.nq, sp.d],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((plan.q // plan.sq, plan.sq, plan.sq), jnp.float32)],
        compiler_params=_params(),
        interpret=plan.interpret,
    )(x_t, cum, dts, bt, ct, D)


def _bwd(plan: Plan, x_t, dy_t, cum, dts, bt, ct, D, states, dfinal):
    b, nc, nh, ng = _grid(plan, x_t)
    n, gw, subs = bt.shape[1], plan.g * plan.p, plan.q // plan.sq
    sp = _specs(plan, n, ng, lambda c: nc - 1 - c)
    g, p = plan.g, plan.p
    dfinal_s = dfinal.reshape(b, ng, g, p, n).transpose(0, 1, 4, 2, 3).reshape(b, ng, n, gw)
    whole_s = pl.BlockSpec((1, ng, n, gw), lambda b, c, h: (b, 0, 0, 0))
    f32 = lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan, d_group=_d_group(cum, D)),
        grid=(b, nc, nh),
        in_specs=[sp.seq, sp.seq, sp.row, sp.row, sp.nq, sp.nq, sp.d, sp.states,
                  sp.whole, whole_s],
        out_specs=[sp.seq, sp.row, sp.row, sp.row, sp.nq, sp.nq],
        out_shape=[jax.ShapeDtypeStruct(x_t.shape, x_t.dtype), f32(cum), f32(cum), f32(cum),
                   f32(bt), f32(ct)],
        scratch_shapes=[pltpu.VMEM((ng, gw, n), jnp.float32),
                        pltpu.VMEM((ng, n, gw), jnp.float32),
                        pltpu.VMEM((subs, plan.sq, plan.sq), jnp.float32),
                        pltpu.VMEM((subs, plan.sq, plan.sq), jnp.float32),
                        pltpu.VMEM((subs, plan.sq, plan.hb), jnp.float32)],
        compiler_params=_params(),
        interpret=plan.interpret,
    )(x_t, dy_t, cum, dts, bt, ct, D, states, dfinal, dfinal_s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(plan: Plan, x_t, cum, dts, bt, ct, D):
    return tuple(_fwd(plan, x_t, cum, dts, bt, ct, D, save_states=False))


def _scan_fwd(plan, x_t, cum, dts, bt, ct, D):
    y_t, final, states = _fwd(plan, x_t, cum, dts, bt, ct, D, save_states=True)
    return (y_t, final), (x_t, cum, dts, bt, ct, D, states)


def _scan_bwd(plan, res, cts):
    x_t, cum, dts, bt, ct, D, states = res
    dy_t, dfinal = cts
    dx_t, dcum, ddt, dd, dbt, dct = _bwd(plan, x_t, dy_t.astype(x_t.dtype), cum, dts, bt, ct,
                                         D, states, dfinal)
    dd = dd.reshape(-1, D.shape[0], dd.shape[-1])              # groups unfolded
    return (dx_t, dcum, ddt, dbt.astype(bt.dtype), dct.astype(ct.dtype),
            dd.sum(axis=(0, 2)).astype(D.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,      # (B, S, H, P)
    dtA: jnp.ndarray,    # (B, S, H) log decay per step
    dt: jnp.ndarray,     # (B, S, H) input scale
    B_: jnp.ndarray,     # (B, S, G, N)
    C_: jnp.ndarray,     # (B, S, G, N)
    D: jnp.ndarray,      # (H,) skip
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """y = SSD(x) + D x in x's dtype, (B, S, H, P), and the final state
    (B, H, N, P) f32: ``ref.ssd_chunked`` with no initial state, plus the
    skip; differentiable."""
    b, s, h, p = x.shape
    g, n = B_.shape[-2:]
    plan = plan_blocks(x.shape, n, chunk, interpret, groups=g)
    if plan is None:
        raise ValueError(f"SSD blocks cannot tile x {x.shape} with {g} groups of N {n}, "
                         f"chunk {chunk}")
    cum = jnp.cumsum(dtA.astype(jnp.float32).reshape(b, s // plan.sq, plan.sq, h), axis=2)
    cum = cum.reshape(b, s, h).transpose(0, 2, 1)                  # (B, H, S)
    dts = dt.astype(jnp.float32).transpose(0, 2, 1)
    fold = lambda a: a.reshape(b * g, a.shape[1] // g, s)            # (B*G, ./G, S)
    seq_minor = lambda bc: bc.transpose(0, 2, 3, 1).reshape(b * g, n, s)
    y_t, final = _scan(plan, fold(x.reshape(b, s, h * p).transpose(0, 2, 1)), fold(cum),
                       fold(dts), seq_minor(B_), seq_minor(C_), D.astype(jnp.float32))
    final = final.reshape(b, h, p, n).transpose(0, 1, 3, 2)
    return y_t.reshape(b, h * p, s).transpose(0, 2, 1).reshape(b, s, h, p), final
