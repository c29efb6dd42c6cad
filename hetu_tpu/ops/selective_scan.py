"""Selective scan (Mamba-1): the recurrence with a decay for every
(channel, state) pair, which has no matmul form (``ops/ssd.py`` is
Mamba-2's: a scalar decay a head, two matmuls against a decay mask).

Per channel ``c`` of ``C`` and state ``n`` of ``N`` (``h`` float32)::

    h_t[n, c] = exp(dt_t[c] * A[n, c]) * h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

THE LAYOUT, which the kernel and the state store agree on: a row's state
is ``[N, C / 128, 128]`` — the CHANNELS on the lanes (and, 8 x 128 at a
time, on the sublanes), the ``N`` states along a leading axis that is
walked.  With the states on the lanes (``[C, N]``, N = 16) a vector
register is an eighth full and a store's rows do not fill the 128 lanes,
so XLA re-lays the store out on every entry and exit of the step; with
them on the sublanes (``[N, C]``) every ``y_t`` is a reduction across
sublanes and ``B_t`` / ``C_t`` have to be spread over lanes token by
token.  Here a token's work on 1,024 channels is, for each ``n``, plain
full-register arithmetic with ``B_t[n]`` / ``C_t[n]`` as SCALARS out of
SMEM: no reduction, no broadcast, no transpose, and the 16 state
registers of a channel group stay in registers over the token loop.

- :func:`selective_scan_reference` — the recurrence as a ``lax.scan``
  over tokens in ``jax.numpy`` (what the kernel is tested against).
- :func:`selective_scan_chunk` — a run of up to ``T`` tokens of ONE row
  whose state sits in a slot of the store: read, carried over the run's
  ``length`` live tokens, written back, in place.
- :func:`selective_scan_slots` — one token for each LIVE slot of the
  store (``ops.ssd.live_slot_list``), in place; no other slot is read or
  written.

Both are one Pallas call (``selective_scan`` on the device trace).
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
_SUBLANES = 8
# tokens of a run that are in VMEM at a time (x, dt and y, each double
# buffered: 6 x 64 x C x 4 bytes = 7.9 MB at C = 5,120)
_TOKEN_BLOCK = 64


def state_shape(channels: int, state_dim: int):
    """A row's scan state in the store: ``(N, C / 128, 128)``."""
    if channels % LANES or (channels // LANES > _SUBLANES
                            and channels // LANES % _SUBLANES):
        raise ValueError(
            f"the selective scan lays {channels} channels over 128 lanes x "
            "whole groups of 8 sublanes: a multiple of 1,024 (or of 128, up "
            "to 1,024)")
    return (int(state_dim), channels // LANES, LANES)


def selective_scan_reference(x, dt, a, b, c, d, state, length=None):
    """``x``, ``dt`` [T, C] (``dt`` after softplus), ``a`` [N, C]
    (negative), ``b`` / ``c`` [T, N], ``d`` [C], ``state`` [N, C] float32.
    A token at or past ``length`` leaves the state as it found it.
    Returns ``(y [T, C] float32, final state [N, C])``."""
    t = x.shape[0]
    n = t if length is None else length
    af, df = a.astype(F32), d.astype(F32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t, on = inp
        new = h * jnp.exp(dt_t[None, :] * af) + \
            (dt_t * x_t)[None, :] * b_t[:, None]
        y_t = jnp.sum(new * c_t[:, None], 0) + df * x_t
        return jnp.where(on, new, h), y_t

    h, y = lax.scan(step, state.astype(F32), (
        x.astype(F32), dt.astype(F32), b.astype(F32), c.astype(F32),
        jnp.arange(t) < n))
    return y, h


def _scan_kernel(rows_ref, slots_ref, n_ref, len_ref, fresh_ref,
                 b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, s_ref, *rest,
                 states: int, tb: int):
    """Grid step ``(i, j)``: token block ``j`` of the ``i``-th walked row.
    The row's state block stays in VMEM over its token blocks; a channel
    group's ``states`` registers are carried over the block's live
    tokens."""
    y_ref, o_ref = rest[-2:]
    i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    cs = s_ref.shape[2]
    gs = min(cs, _SUBLANES)

    @pl.when(i < n)
    def _walk():
        row = rows_ref[i]
        fresh = fresh_ref[row] > 0
        live = jnp.clip(len_ref[row] - j * tb, 0, tb)

        @pl.when(j == 0)
        def _take():                # a fresh row starts from zeros
            o_ref[...] = jnp.where(fresh, 0.0, s_ref[...])

        @pl.when(live < tb)
        def _dead():                # tokens past the row's length
            y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        for g in range(cs // gs):
            at = pl.ds(g * gs, gs)
            a = [a_ref[k, at, :] for k in range(states)]
            dd = d_ref[at, :]

            def token(t, hs, at=at, a=a, dd=dd):
                dt, xv = dt_ref[0, t, at, :], x_ref[0, t, at, :]
                dtx, y, new = dt * xv, dd * xv, []
                for k in range(states):
                    h = hs[k] * jnp.exp(dt * a[k]) + \
                        dtx * b_ref[t * states + k]
                    y = y + h * c_ref[t * states + k]
                    new.append(h)
                y_ref[0, t, at, :] = y
                return tuple(new)

            hs = lax.fori_loop(
                0, live, token, tuple(o_ref[0, k, at, :]
                                      for k in range(states)))
            for k in range(states):
                o_ref[0, k, at, :] = hs[k]

    # no row walked at all: the blocks the grid maps go back as they are
    @pl.when(jnp.logical_and(n == 0, jnp.logical_and(i == 0, j == 0)))
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


def _scan_call(x, dt, a, b, c, d, store, rows, slots, n_rows, lengths,
               fresh, *, zero_skipped: bool, interpret: bool):
    """``x`` / ``dt`` [R, T, C], ``b`` / ``c`` [R, T, N], ``store`` [S, N,
    C/128, 128]; the ``n_rows[0]`` first entries of ``rows`` (into R) and
    ``slots`` (into S) are walked, ``lengths`` / ``fresh`` [R]."""
    r, t, ch = x.shape
    _, n, cs, _ = store.shape
    tb = min(_TOKEN_BLOCK, t)
    if t % tb:
        raise ValueError(f"{t} tokens do not divide into blocks of {tb}")
    nt = t // tb
    # a token block's scalars: whole tiles of a flat float32 array (XLA
    # lays it out by 1,024 words, and Mosaic takes blocks of that)
    seg = -(-tb * n // 1024) * 1024
    tile = lambda v: v.astype(F32).reshape(r, t, cs, LANES)   # noqa: E731
    flat = lambda v: jnp.pad(                                 # noqa: E731
        v.astype(F32).reshape(r * nt, tb * n),
        ((0, 0), (0, seg - tb * n))).reshape(r * nt * seg)

    def tokens(i, j, rows, *_):
        return rows[i], j, 0, 0

    def scalars(i, j, rows, *_):
        return (rows[i] * nt + j,)

    def slot(i, j, rows, slots, *_):
        return slots[i], 0, 0, 0

    tok = pl.BlockSpec((1, tb, cs, LANES), tokens)
    smem = pl.BlockSpec((seg,), scalars, memory_space=pltpu.SMEM)
    block = pl.BlockSpec((1, n, cs, LANES), slot)
    args = [flat(b), flat(c), tile(x), tile(dt),
            a.astype(F32).reshape(n, cs, LANES),
            d.astype(F32).reshape(cs, LANES), store]
    in_specs = [smem, smem, tok, tok,
                pl.BlockSpec((n, cs, LANES), lambda *_: (0, 0, 0)),
                pl.BlockSpec((cs, LANES), lambda *_: (0, 0)), block]
    aliases = {5 + 6: 1}                    # the store, in place
    if zero_skipped:                        # y of a row not walked: zeros
        args.append(jnp.zeros((r, t, cs, LANES), F32))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        aliases[5 + 7] = 0
    with jax.named_scope("selective_scan"):
        y, new = pl.pallas_call(
            functools.partial(_scan_kernel, states=n, tb=tb),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5, grid=(rows.shape[0], nt),
                in_specs=in_specs, out_specs=[tok, block]),
            out_shape=[jax.ShapeDtypeStruct((r, t, cs, LANES), F32),
                       jax.ShapeDtypeStruct(store.shape, F32)],
            input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=(6 * tb + 6 * n + 2) * ch * 4 + (16 << 20)),
            interpret=interpret,
            name="selective_scan",
        )(rows.astype(jnp.int32), slots.astype(jnp.int32),
          n_rows.astype(jnp.int32), lengths.astype(jnp.int32),
          fresh.astype(jnp.int32), *args)
    return y.reshape(r, t, ch), new


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_chunk(x, dt, a, b, c, d, store, slot, length, fresh, *,
                         interpret: Optional[bool] = None):
    """A run of ``length`` (<= T) consecutive tokens of ONE row whose
    state is slot ``slot`` of ``store`` [S, N, C/128, 128] float32: ``x``
    / ``dt`` [T, C], ``b`` / ``c`` [T, N], ``a`` [N, C], ``d`` [C].  The
    slot is read (zeros in its place where ``fresh``), carried over the
    live tokens and written back, in place where the caller donates the
    store; a token past ``length`` costs nothing and its ``y`` is zeros.
    Returns ``(y [T, C] float32, the store)``."""
    if interpret is None:
        interpret = not on_tpu()
    one = lambda v: jnp.asarray(v).reshape(1)                 # noqa: E731
    y, new = _scan_call(
        x[None], dt[None], a, b[None], c[None], d, store,
        jnp.zeros((1,), jnp.int32), one(slot), jnp.ones((1,), jnp.int32),
        one(length), one(fresh), zero_skipped=False, interpret=interpret)
    return y[0], new


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_slots(x, dt, a, b, c, d, store, slots, n_live, fresh, *,
                         interpret: Optional[bool] = None):
    """One token for each LIVE slot of ``store`` [S, N, C/128, 128] and no
    other: ``x`` / ``dt`` [S, C], ``b`` / ``c`` [S, N] and ``fresh`` [S]
    are in SLOT order, ``slots`` / ``n_live`` come from
    ``ops.ssd.live_slot_list``.  A listed slot is read (zeros where
    ``fresh``), updated and written back in place; a slot outside the
    list is neither read nor written and its ``y`` is zeros.  Returns
    ``(y [S, C] float32, the store)``."""
    if interpret is None:
        interpret = not on_tpu()
    y, new = _scan_call(
        x[:, None], dt[:, None], a, b[:, None], c[:, None], d, store,
        slots, slots, n_live, jnp.ones((x.shape[0],), jnp.int32), fresh,
        zero_skipped=True, interpret=interpret)
    return y[:, 0], new
