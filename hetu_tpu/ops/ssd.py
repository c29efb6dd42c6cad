"""State-space duality (Mamba-2): the state-space vocabulary of the
serving step (``serving/decode.py``).

Plain ``jax.numpy`` / ``lax``:

- :func:`causal_conv` — depthwise causal convolution over a run of
  tokens with a carried tail (the last ``K - 1`` inputs of the row).
- :func:`ssd_chunk_scan` — the matmul form of the selective scan over a
  run of tokens, cut into chunks of ``chunk`` (arXiv:2405.21060 §6):
  inside a chunk the recurrence is two matmuls against a decay mask,
  between chunks a short sequential pass carries the state.  Takes the
  row's initial state and returns its final one.
- :func:`ssd_decode_step` — the one-token recurrence, batched over rows:
  the reference of the walk below.

One Pallas call:

- :func:`ssd_decode_slots` — the one-token recurrence over the LIVE
  slots of a state store (:func:`live_slot_list`), in place: what the
  serving step runs.

The recurrence, per head ``h`` (state ``S_h`` [P, N], float32)::

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t C_t + D_h * x_t

``B`` and ``C`` are shared by the ``H / G`` heads of a group.  A token
past ``length`` (padding of a ragged chunk) is given ``dt = 0``: its
decay is 1 and its input 0, so it leaves the state as it found it.
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


def causal_conv(x, w, b, tail, length=None):
    """Depthwise causal conv: ``x`` [T, C] new inputs, ``w`` [K, C]
    (tap ``K - 1`` multiplies the current token), ``b`` [C] or None,
    ``tail`` [K - 1, C] the inputs before ``x``.  Returns ``(y [T, C]
    float32, new_tail [K - 1, C])`` where the new tail ends at token
    ``length`` (default: all ``T``)."""
    t, k = x.shape[0], w.shape[0]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=0)  # [K-1+T, C]
    y = sum(full[j: j + t].astype(F32) * w[j].astype(F32)
            for j in range(k))
    if b is not None:
        y = y + b.astype(F32)
    n = t if length is None else length
    new_tail = lax.dynamic_slice_in_dim(full, n, k - 1, axis=0)
    return y, new_tail.astype(tail.dtype)


def _segsum(a):
    """``a`` [..., Q] -> [..., Q, Q] with ``out[t, s] = sum(a[s+1..t])``
    for ``s <= t`` and ``-inf`` above the diagonal."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunk_scan(x, dt, a, b, c, d, state, chunk: int, length=None):
    """``x`` [T, H, P], ``dt`` [T, H] (after softplus), ``a`` [H]
    (negative), ``b`` / ``c`` [T, G, N], ``d`` [H], ``state`` [H, P, N]
    float32.  ``T`` must be a multiple of ``chunk`` (pad with any finite
    values and pass ``length``).  Returns ``(y [T, H, P] float32,
    final state [H, P, N] float32)``."""
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    if t % chunk:
        raise ValueError(f"{t} tokens do not divide into chunks of {chunk}")
    nc, hg = t // chunk, h // g
    xf, bf, cf = x.astype(F32), b.astype(F32), c.astype(F32)
    dt = dt.astype(F32)
    if length is not None:
        dt = jnp.where(jnp.arange(t)[:, None] < length, dt, 0.0)
    # chunked views; heads split as [G, hg] so B/C broadcast over a group
    xc = (xf * dt[..., None]).reshape(nc, chunk, g, hg, p)   # dt * x
    bc = bf.reshape(nc, chunk, g, n)
    cc = cf.reshape(nc, chunk, g, n)
    da = (dt * a.astype(F32)).reshape(nc, chunk, g, hg)      # log decay
    da = jnp.moveaxis(da, 1, -1)                             # [nc, G, hg, Q]
    cum = jnp.cumsum(da, axis=-1)
    # inside each chunk: y[t] = sum_{s<=t} exp(sum a[s+1..t]) (C_t.B_s) dt_s x_s
    decay = jnp.exp(_segsum(da))                             # [nc,G,hg,Q,Q]
    cb = jnp.einsum("ztgn,zsgn->zgts", cc, bc)               # [nc,G,Q,Q]
    y_in = jnp.einsum("zgts,zgkts,zsgkp->ztgkp", cb, decay, xc)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)                    # [nc,G,hg,Q]
    s_chunk = jnp.einsum("zgks,zsgkp,zsgn->zgkpn", to_end, xc, bc)
    total = jnp.exp(cum[..., -1])                            # [nc,G,hg]

    def carry(s, inp):
        s_c, tot = inp
        return s * tot[..., None, None] + s_c, s             # state BEFORE
    s0 = state.astype(F32).reshape(g, hg, p, n)
    s_end, s_before = lax.scan(carry, s0, (s_chunk, total))
    # what the state at the chunk's start adds: exp(cum_t) * C_t . S
    y_st = jnp.einsum("ztgn,zgkpn,zgkt->ztgkp", cc, s_before, jnp.exp(cum))
    y = (y_in + y_st).reshape(t, h, p) + xf * d.astype(F32)[:, None]
    return y, s_end.reshape(h, p, n)


def ssd_decode_step(x, dt, a, b, c, d, state):
    """One token for each of ``R`` rows: ``x`` [R, H, P], ``dt`` [R, H],
    ``b`` / ``c`` [R, G, N], ``state`` [R, H, P, N] float32.  Returns
    ``(y [R, H, P] float32, new state)``."""
    r, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    hg = h // g
    dt = dt.astype(F32)
    xf = x.astype(F32).reshape(r, g, hg, p)
    s = state.reshape(r, g, hg, p, n)
    decay = jnp.exp(dt * a.astype(F32)).reshape(r, g, hg)
    upd = (dt.reshape(r, g, hg)[..., None] * xf)[..., None] * \
        b.astype(F32)[:, :, None, None, :]
    new = s * decay[..., None, None] + upd
    y = jnp.einsum("rgkpn,rgn->rgkp", new, c.astype(F32))
    y = y.reshape(r, h, p) + x.astype(F32) * d.astype(F32)[:, None]
    return y, new.reshape(r, h, p, n)


def live_slot_list(slot_live):
    """``slot_live`` [S] bool -> ``(slots [S] int32, n [1] int32)``: the
    ``n[0]`` live slots first (in slot order), every entry past them a
    repeat of the last live one (slot 0 where none is live), so a walk's
    block index stops moving after the last live slot.  Computed once a
    step; every mamba2 layer's :func:`ssd_decode_slots` walks the same
    list."""
    n = jnp.sum(slot_live, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(slot_live), stable=True)
    last = jnp.maximum(n, 1) - 1
    slots = order[jnp.minimum(jnp.arange(order.shape[0]), last)]
    return slots.astype(jnp.int32), n[None]


# the walk moves a slot in pieces of at most this many bytes: in and out,
# each double-buffered, are four such pieces of VMEM
_WALK_BLOCK_BYTES = 4 << 20


def _decode_slots_kernel(slots_ref, n_ref, fresh_ref, decay_ref, dtx_ref,
                         xd_ref, b_ref, c_ref, s_ref, y0_ref, y_ref, o_ref,
                         *, heads: int, hg: int):
    """Grid step ``(i, j)``: piece ``j`` (``hb`` heads: whole groups) of
    the ``i``-th live slot; a head's decay is a scalar in SMEM."""
    del y0_ref                          # the zeros y is aliased to
    i, j = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[1]
    n = n_ref[0]

    @pl.when(i < n)
    def _walk():
        slot = slots_ref[i]
        fresh = fresh_ref[slot] > 0

        def group(g, carry):
            at = pl.ds(pl.multiple_of(g * hg, hg), hg)
            for k in range(hg):
                # a fresh slot starts from zeros whatever it holds
                s = jnp.where(fresh, 0.0, s_ref[0, g * hg + k])
                o_ref[0, g * hg + k] = s * decay_ref[
                    slot * heads + j * hb + g * hg + k]
            bg = b_ref[0, pl.ds(j * (hb // hg) + g, 1), :]
            cg = c_ref[0, pl.ds(j * (hb // hg) + g, 1), :]
            new = o_ref[0, at] + dtx_ref[0, at][:, :, None] * bg[None]
            o_ref[0, at] = new
            y_ref[0, at] = jnp.sum(new * cg[None], axis=-1) + xd_ref[0, at]
            return carry
        lax.fori_loop(0, hb // hg, group, 0)

    # no live slot at all: the blocks the walk maps are put back as they are
    @pl.when(jnp.logical_and(n == 0, i == 0))
    def _none():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_slots(x, dt, a, b, c, d, state, slots, n_live, fresh, *,
                     interpret: Optional[bool] = None):
    """:func:`ssd_decode_step` over the LIVE slots of a state store and
    no other: ``state`` [S, H, P, N] float32 is the whole store, ``x``
    [S, H, P], ``dt`` [S, H], ``b`` / ``c`` [S, G, N] and ``fresh`` [S]
    are in SLOT order, ``slots`` / ``n_live`` come from
    :func:`live_slot_list`.  A slot in the list is read (zeros taken in
    its place where ``fresh``), updated and written back, in place where
    the caller donates the store; a slot outside it is neither read nor
    written and its ``y`` is zeros.  Returns ``(y [S, H, P] float32, the
    store)``.  The Mosaic call is ``ssd_decode_slots`` on the device
    trace: grid ``(S, pieces of a slot)``, a piece as many whole groups
    of heads as 4 MB hold (the published widths: one slot a step), the
    steps past ``n_live`` skipped with their block indices left on the
    last live piece, so nothing moves for them."""
    if interpret is None:
        interpret = not on_tpu()
    s_n, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    hg = h // g
    gb = max(k for k in range(1, g + 1)
             if g % k == 0 and (k == 1 or k * hg * p * n * 4
                                <= _WALK_BLOCK_BYTES))
    hb, nb = gb * hg, g // gb
    dt, xf = dt.astype(F32), x.astype(F32)
    decay = jnp.exp(dt * a.astype(F32)).reshape(s_n * h)

    def at_slot(i, j, slots, n, *_):
        return slots[i], 0, 0

    def piece(i, j, slots, n, *_):
        # past the live slots: stay on the last live piece
        return slots[i], jnp.where(i < jnp.maximum(n[0], 1), j, nb - 1), 0

    rows = pl.BlockSpec((1, hb, p), piece)
    groups = pl.BlockSpec((1, g, n), at_slot)
    block = pl.BlockSpec((1, hb, p, n), lambda *a: piece(*a) + (0,))
    with jax.named_scope("ssd_decode_slots"):
        y, new = pl.pallas_call(
            functools.partial(_decode_slots_kernel, heads=h, hg=hg),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(s_n, nb),
                in_specs=[rows, rows, groups, groups, block,
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[rows, block]),
            out_shape=[jax.ShapeDtypeStruct((s_n, h, p), F32),
                       jax.ShapeDtypeStruct(state.shape, F32)],
            input_output_aliases={8: 1, 9: 0},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=4 * hb * p * n * 4 + (16 << 20)),
            interpret=interpret,
            name="ssd_decode_slots",
        )(slots, n_live, fresh.astype(jnp.int32), decay,
          dt[..., None] * xf, xf * d.astype(F32)[:, None],
          b.astype(F32), c.astype(F32), state, jnp.zeros((s_n, h, p), F32))
    return y, new
