"""State-space duality (Mamba-2) in plain ``jax.numpy`` / ``lax``.

Three functions, the whole state-space vocabulary of the serving step
(``serving/decode.py``):

- :func:`causal_conv` — depthwise causal convolution over a run of
  tokens with a carried tail (the last ``K - 1`` inputs of the row).
- :func:`ssd_chunk_scan` — the matmul form of the selective scan over a
  run of tokens, cut into chunks of ``chunk`` (arXiv:2405.21060 §6):
  inside a chunk the recurrence is two matmuls against a decay mask,
  between chunks a short sequential pass carries the state.  Takes the
  row's initial state and returns its final one.
- :func:`ssd_decode_step` — the one-token recurrence, batched over rows.

The recurrence, per head ``h`` (state ``S_h`` [P, N], float32)::

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (x) B_t
    y_t = S_t C_t + D_h * x_t

``B`` and ``C`` are shared by the ``H / G`` heads of a group.  A token
past ``length`` (padding of a ragged chunk) is given ``dt = 0``: its
decay is 1 and its input 0, so it leaves the state as it found it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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
