"""Gated delta rule (Gated DeltaNet, arXiv:2412.06464): a linear-attention
recurrence whose update READS the state it writes, so it has neither the
scalar-decay matmul form of ``ops/ssd.py`` nor the elementwise form of
``ops/selective_scan.py``.

Per head (state ``S`` [K, V] float32; ``q``, ``k`` [K] with ``k`` of unit
length, ``v`` [V]; ``alpha`` in (0, 1] the decay, ``beta`` in [0, 2] the
write strength)::

    u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)
    S_t = alpha_t S_{t-1} + k_t u_t^T
    o_t = S_t^T q_t

A token with ``beta = 0`` and ``alpha = 1`` leaves the state as it found it:
that is what a token past a row's length is given.

THE LAYOUT, which the kernels and the state store agree on: a row's state
of ``H`` heads is ``[H / p, K, p * V]`` — the value axis on the lanes, ``p``
heads side by side where one head's ``V`` lanes do not fill whole registers
(``state_shape``: V = 192 pads to 256 lanes, in HBM and in VMEM alike, a
third more bytes for every walk; two heads are 384 = 3 x 128).  The key axis
lies on the sublanes, so ``S^T k`` and ``S^T q`` are sums over sublanes of
``S`` times a lane-broadcast column, and the rank-one update is a
lane-broadcast column times a sublane-broadcast row: no transpose, no
matmul, float32 throughout (DESIGN.md section 31; step-0 times in PERF.md).

- :func:`gated_delta_reference` — the recurrence token by token
  (``lax.scan``) in ``jax.numpy``: what the kernels are tested against.
- :func:`gated_delta_chunk` — a run of up to ``T`` tokens of ONE row whose
  state sits in a slot of the store, in blocks of ``CHUNK_BLOCK`` = 64: inside
  a block the recurrence is a triangular solve and five matmuls (the WY /
  UT form), between blocks the state is carried in VMEM; read from the
  slot, written back to it, in place.  ``gated_delta_chunk`` on the device
  trace.
- :func:`gated_delta_slots` — one token for each LIVE slot of the store
  (``ops.ssd.live_slot_list``), in place; no other slot is read or
  written.  ``gated_delta_decode`` on the device trace.

The chunk form of one block of ``C`` tokens, ``g_t = sum_{i<=t} log alpha_i``
inside the block, ``D[t, i] = exp(g_t - g_i)``::

    A = strict_lower(diag(beta) (K K^T * D))        [C, C]
    U = (I + A)^-1 diag(beta) (V - diag(exp g) K S_0)      the u_t, [C, V]
    O = diag(exp g) Q S_0 + lower(Q K^T * D) U
    S_C = exp(g_C) S_0 + (diag(exp(g_C - g)) K)^T U

``(I + A)^-1`` is built by BLOCK FORWARD SUBSTITUTION on whole ``[C, C]``
matrices: from the identity (the inverse of the 1 x 1 diagonal blocks), the
inverse ``X`` of the diagonal blocks of size ``b`` gives that of size ``2b``
as ``X - X L X``, ``L`` the part of ``A`` below-left inside each ``2b`` block
— ``log2 C`` steps of two matmuls, no slicing, and as stable as the row-by-
row substitution (a Neumann product ``(I - A)(I + A^2)...`` is not: its
terms grow like ``(1 + |a|)^C`` before they cancel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas import on_tpu

F32 = jnp.float32
LANES = 128
CHUNK_BLOCK = 64                # tokens of one solve
# the chunk form's matmuls: float32 operands in full (the state is float32)
_PRECISION = lax.Precision.HIGHEST


def heads_packed(heads: int, v_dim: int) -> int:
    """Heads that lie side by side on the lanes of one state block."""
    return 2 if v_dim % LANES and heads % 2 == 0 else 1


def state_shape(heads: int, k_dim: int, v_dim: int):
    """A row's state in the store: ``(H / p, K, p * V)``."""
    p = heads_packed(heads, v_dim)
    return (heads // p, int(k_dim), p * int(v_dim))


def pack_state(state, p: int):
    """``[..., H, K, V]`` -> ``[..., H / p, K, p * V]``."""
    *lead, h, k, v = state.shape
    s = state.reshape(*lead, h // p, p, k, v)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, h // p, k, p * v)


def unpack_state(state, p: int):
    """``[..., H / p, K, p * V]`` -> ``[..., H, K, V]``."""
    *lead, g, k, pv = state.shape
    s = state.reshape(*lead, g, k, p, pv // p)
    return jnp.swapaxes(s, -3, -2).reshape(*lead, g * p, k, pv // p)


def gated_delta_reference(q, k, v, alpha, beta, state, length=None):
    """``q``, ``k`` [T, H, K], ``v`` [T, H, V], ``alpha`` / ``beta`` [T, H],
    ``state`` [H, K, V] float32.  A token at or past ``length`` leaves the
    state as it found it.  Returns ``(o [T, H, V] float32, final state)``."""
    t = q.shape[0]
    n = t if length is None else length

    def step(s, inp):
        q_t, k_t, v_t, a_t, b_t, on = inp
        dec = a_t[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", dec, k_t))
        new = dec + k_t[:, :, None] * u[:, None, :]
        return jnp.where(on, new, s), jnp.einsum("hkv,hk->hv", new, q_t)

    s, o = lax.scan(step, state.astype(F32), (
        q.astype(F32), k.astype(F32), v.astype(F32), alpha.astype(F32),
        beta.astype(F32), jnp.arange(t) < n))
    return o, s


def _same_part(a, wa: int, b, wb: int, parts: int):
    """``a // wa == b // wb`` over ``parts`` runs, by compares and logical
    and / or alone (Mosaic has no vector division, and does not compare
    masks)."""
    out = None
    for j in range(parts):
        both = (a >= j * wa) & (a < (j + 1) * wa) & \
            (b >= j * wb) & (b < (j + 1) * wb)
        out = both if out is None else out | both
    return out


def _by_lane(parts, dv: int, lane):
    """``parts`` (one a packed head, each broadcastable against ``lane``
    [1, p * V]) laid over the heads' lane ranges."""
    out = parts[0]
    for j, part in enumerate(parts[1:], 1):
        out = jnp.where(lane >= j * dv, part, out)
    return out


# -- decode form: one token for every live slot -------------------------------

def _decode_kernel(slots_ref, n_ref, fresh_ref, alpha_ref, beta_ref, kq_ref,
                   qt_ref, kt_ref, v_ref, s_ref, o0_ref, o_ref, new_ref, *,
                   heads: int, p: int):
    """Grid step ``i``: the ``i``-th live slot, whole.  A head's ``alpha``,
    ``beta`` and ``k . q`` are scalars in SMEM; ``q`` and ``k`` come as
    columns ``[K, H]``."""
    del o0_ref                          # the zeros o is aliased to
    i = pl.program_id(0)
    n = n_ref[0]
    groups, _, lanes = s_ref.shape[1:]
    dv = lanes // p
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    @pl.when(i < n)
    def _walk():
        slot = slots_ref[i]
        fresh = fresh_ref[slot] > 0
        for g in range(groups):
            hs = range(g * p, (g + 1) * p)
            at = lambda ref: _by_lane(                       # noqa: E731
                [ref[slot * heads + h] for h in hs], dv, lane)
            col = lambda ref: _by_lane(                      # noqa: E731
                [ref[0, :, h:h + 1] for h in hs], dv, lane)
            alpha, beta, kq = at(alpha_ref), at(beta_ref), at(kq_ref)
            kc = col(kt_ref)
            # a fresh slot starts from zeros whatever it holds
            s = jnp.where(fresh, 0.0, s_ref[0, g])
            sk = jnp.sum(s * kc, axis=0, keepdims=True)
            sq = jnp.sum(s * col(qt_ref), axis=0, keepdims=True)
            u = beta * (v_ref[0, g:g + 1, :] - alpha * sk)
            o_ref[0, g:g + 1, :] = alpha * sq + kq * u
            new_ref[0, g] = alpha * s + kc * u

    # no live slot at all: the blocks the walk maps are put back as they are
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _none():
        new_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_slots(q, k, v, alpha, beta, store, slots, n_live, fresh, *,
                      interpret: Optional[bool] = None):
    """One token for each LIVE slot of ``store`` [S, H / p, K, p * V]
    float32 and no other: ``q`` / ``k`` [S, H, K], ``v`` [S, H, V], ``alpha``
    / ``beta`` [S, H] and ``fresh`` [S] are in SLOT order, ``slots`` /
    ``n_live`` come from ``ops.ssd.live_slot_list``.  A listed slot is read
    (zeros where ``fresh``), updated and written back, in place where the
    caller donates the store; a slot outside the list is neither read nor
    written and its ``o`` is zeros.  Returns ``(o [S, H, V] float32, the
    store)``."""
    if interpret is None:
        interpret = not on_tpu()
    s_n, h, dk = q.shape
    dv = v.shape[-1]
    groups, _, lanes = store.shape[1:]
    p = h // groups
    qf, kf = q.astype(F32), k.astype(F32)
    flat = lambda a: a.astype(F32).reshape(s_n * h)          # noqa: E731

    def at_slot(i, slots, *_):
        return slots[i], 0, 0

    cols = pl.BlockSpec((1, dk, h), at_slot)
    rows = pl.BlockSpec((1, groups, lanes), at_slot)
    block = pl.BlockSpec((1, groups, dk, lanes),
                         lambda i, slots, *_: (slots[i], 0, 0, 0))
    with jax.named_scope("gated_delta_decode"):
        o, new = pl.pallas_call(
            functools.partial(_decode_kernel, heads=h, p=p),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=6, grid=(s_n,),
                in_specs=[cols, cols, rows, block,
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[rows, block]),
            out_shape=[jax.ShapeDtypeStruct((s_n, groups, lanes), F32),
                       jax.ShapeDtypeStruct(store.shape, F32)],
            input_output_aliases={9: 1, 10: 0},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=4 * groups * dk * (-(-lanes // LANES) * LANES)
                * 4 + (16 << 20)),
            interpret=interpret,
            name="gated_delta_decode",
        )(slots, n_live, fresh.astype(jnp.int32), flat(alpha), flat(beta),
          flat(jnp.sum(qf * kf, -1)), jnp.swapaxes(qf, 1, 2),
          jnp.swapaxes(kf, 1, 2), v.astype(F32).reshape(s_n, groups, lanes),
          store, jnp.zeros((s_n, groups, lanes), F32))
    return o.reshape(s_n, h, dv), new


# -- chunk form: a run of one row's tokens, its state carried in its slot -----

def _nt(a, b):
    """``a @ b.T`` in float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=_PRECISION, preferred_element_type=F32)


def _mm(a, b):
    return jnp.dot(a, b, precision=_PRECISION, preferred_element_type=F32)


def _unit_lower_inverse(a, cb: int):
    """``(I + a)^-1`` for ``a`` [M, M] strictly lower triangular inside
    diagonal blocks of ``cb`` (a power of two) and zero outside them: block
    forward substitution, ``log2 cb`` doublings on the whole matrix."""
    m = a.shape[0]
    row = lax.broadcasted_iota(jnp.int32, (m, m), 0)
    col = lax.broadcasted_iota(jnp.int32, (m, m), 1)
    x = (row == col).astype(F32)
    for lb in range(cb.bit_length() - 1):
        # rows of the odd block of size 2^lb, columns of the even one
        # before it, inside one block of twice the size
        below = ((row >> (lb + 1)) == (col >> (lb + 1))) & \
            (((row >> lb) & 1) == 1) & (((col >> lb) & 1) == 0)
        x = x - _mm(x, _mm(jnp.where(below, a, 0.0), x))
    return x


def _chunk_kernel(slot_ref, len_ref, fresh_ref, q_ref, k_ref, kt_ref, v_ref,
                  gb_ref, grow_ref, s_ref, o_ref, new_ref, *, p: int,
                  cb: int):
    """Grid step ``(g, j)``: token block ``j`` of head group ``g``.  The
    group's ``p`` heads lie side by side on the state's lanes and ONE UNDER
    THE OTHER on the rows of every token operand (``p * cb`` rows: head 0's
    ``cb`` tokens, then head 1's), so a block's matmuls are the MXU's own
    128 rows wide and the heads' solves are one block-diagonal solve; what
    a head's rows produce on another head's lanes is masked or never read.
    The group's state block stays in VMEM over its token blocks."""
    del slot_ref
    j = pl.program_id(1)
    m, lanes = p * cb, s_ref.shape[-1]
    dv = lanes // p

    @pl.when(j == 0)
    def _take():                        # a fresh row starts from zeros
        new_ref[...] = jnp.where(fresh_ref[0] > 0, 0.0, s_ref[...])

    live = len_ref[0] - j * cb

    @pl.when(live <= 0)
    def _dead():                        # a block past the row's length
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live > 0)
    def _block():
        s0 = new_ref[0, 0]                              # [K, p * V]
        q, k, kt = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0]   # [M, K] x 2, [K, M]
        gc, beta = gb_ref[0, 0, :, 0:1], gb_ref[0, 0, :, 1:2]     # [M, 1]
        gr = grow_ref[0, 0]                                       # [1, M]
        v = jnp.concatenate([v_ref[0]] * p, axis=0)     # [M, p * V]
        row = lax.broadcasted_iota(jnp.int32, (m, m), 0)
        col = lax.broadcasted_iota(jnp.int32, (m, m), 1)
        same = _same_part(row, cb, col, cb, p)          # one head's tokens
        # D[t, i] = exp(g_t - g_i) at or below the diagonal of a head
        decay = jnp.exp(jnp.where(same & (row >= col), gc - gr, -jnp.inf))
        x = _unit_lower_inverse(
            jnp.where(row > col, beta * _nt(k, k) * decay, 0.0), cb)
        eg = jnp.exp(gc)
        u = _mm(x, beta * (v - eg * _mm(k, s0)))        # [M, p * V]
        o = eg * _mm(q, s0) + _mm(_nt(q, k) * decay, u)
        lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
        o_ref[0] = _by_lane([o[a * cb:(a + 1) * cb] for a in range(p)], dv,
                            lane)
        # a head's rows write its own lanes of the state
        own = _same_part(
            lax.broadcasted_iota(jnp.int32, (m, lanes), 0), cb,
            lax.broadcasted_iota(jnp.int32, (m, lanes), 1), dv, p)
        g_end = [gr[:, (a + 1) * cb - 1:(a + 1) * cb] for a in range(p)]
        to_end = jnp.exp(_by_lane(
            g_end, cb, lax.broadcasted_iota(jnp.int32, (1, m), 1)) - gr)
        new_ref[0, 0] = jnp.exp(_by_lane(g_end, dv, lane)) * s0 + \
            _mm(kt * to_end, jnp.where(own, u, 0.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_chunk(q, k, v, alpha, beta, store, slot, length, fresh, *,
                      interpret: Optional[bool] = None):
    """A run of ``length`` (<= T) consecutive tokens of ONE row whose state
    is slot ``slot`` of ``store`` [S, H / p, K, p * V] float32: ``q`` / ``k``
    [T, H, K], ``v`` [T, H, V], ``alpha`` / ``beta`` [T, H]; ``T`` a multiple
    of ``CHUNK_BLOCK`` (or less than one).  The slot is read (zeros in its
    place where ``fresh``), carried over the live tokens and written back,
    in place where the caller donates the store; ``o`` of a token past
    ``length`` is not defined (finite; zeros in a block wholly past it).
    Returns ``(o [T, H, V] float32, the store)``."""
    if interpret is None:
        interpret = not on_tpu()
    t, h, dk = q.shape
    dv = v.shape[-1]
    groups, _, lanes = store.shape[1:]
    p = h // groups
    cb = min(CHUNK_BLOCK, t)
    if t % cb or cb & (cb - 1):
        raise ValueError(f"{t} tokens do not divide into blocks of "
                         f"{CHUNK_BLOCK} (or make one power-of-two block)")
    nb, m = t // cb, p * cb
    on = (jnp.arange(t) < length)[:, None]
    # log decay summed inside each block; a dead token neither decays nor
    # writes
    g = jnp.cumsum(jnp.where(on, jnp.log(alpha.astype(F32)), 0.0).reshape(
        nb, cb, h), axis=1).reshape(t, h)
    gb = jnp.stack([g, jnp.where(on, beta.astype(F32), 0.0)], -1)

    def stacked(a):
        """``[T, H, w]`` -> ``[H / p, nb, p * cb, w]``: a group's heads one
        under the other, block by block."""
        a = a.astype(F32).reshape(nb, cb, groups, p, a.shape[-1])
        return jnp.transpose(a, (2, 0, 3, 1, 4)).reshape(
            groups, nb, m, a.shape[-1])

    qs, ks = stacked(q), stacked(k)
    vg = jnp.swapaxes(v.astype(F32).reshape(t, groups, lanes), 0, 1)
    one = lambda a: jnp.asarray(a, jnp.int32).reshape(1)     # noqa: E731

    def block(*shape):
        return pl.BlockSpec((1, 1) + shape, lambda g, j, *_: (g, j, 0, 0))

    state = pl.BlockSpec((1, 1, dk, lanes),
                         lambda g, j, slot, *_: (slot[0], g, 0, 0))
    tokens = pl.BlockSpec((1, cb, lanes), lambda g, j, *_: (g, j, 0))
    with jax.named_scope("gated_delta_chunk"):
        o, new = pl.pallas_call(
            functools.partial(_chunk_kernel, p=p, cb=cb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(groups, nb),
                in_specs=[block(m, dk), block(m, dk), block(dk, m), tokens,
                          block(m, 2), block(1, m), state],
                out_specs=[tokens, state]),
            out_shape=[jax.ShapeDtypeStruct((groups, t, lanes), F32),
                       jax.ShapeDtypeStruct(store.shape, F32)],
            input_output_aliases={3 + 6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name="gated_delta_chunk",
        )(one(slot), one(length), one(fresh), qs, ks,
          jnp.swapaxes(ks, 2, 3), vg, stacked(gb),
          jnp.swapaxes(stacked(g[..., None]), 2, 3), store)
    return jnp.swapaxes(o, 0, 1).reshape(t, h, dv), new
