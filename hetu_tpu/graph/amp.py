"""AMP: autocast dtype context + dynamic-loss-scaling GradScaler.

TPU-native re-expression of the reference's AMP stack
(``hetu/graph/autocast/*``: dtype context stack consulted per op;
``GradScaler`` with inf-check via the ``CheckFinite`` kernel and the
``update_scale`` op, ``hetu/impl/kernel/CheckFinite.cu``).

* :class:`autocast` — a graph-construction context: ops created inside it
  record a compute dtype; matmul-class ops cast their floating inputs down
  (bf16/fp16 ride the MXU), numerically-sensitive ops (losses, softmax,
  norms) cast up to fp32.  The cast is folded into the op's impl at trace
  time so XLA fuses it into the surrounding computation.
* :class:`GradScaler` — dynamic loss scaling for fp16: scales the loss,
  unscales grads, skips the update when any grad is non-finite, and grows /
  backs off the scale (reference ``update_scale`` semantics).  On TPU bf16
  autocast normally needs no scaler; it exists for fp16 parity.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.dtype import canonicalize_dtype

# Ops whose inputs are cast DOWN to the autocast dtype (MXU-bound).
_LOW_PRECISION_OPS = frozenset({
    "matmul", "batch_matmul", "linear", "einsum", "conv2d",
    "fused_lm_cross_entropy",
    "attention", "attention_qkv", "parallel_attention", "flash_attention",
})
# Ops whose floating inputs are cast UP to fp32 (numerically sensitive).
_FULL_PRECISION_OPS = frozenset({
    "softmax_cross_entropy", "nll_loss", "mse_loss", "kl_div",
    "bce", "vocab_parallel_cross_entropy",
    "log_softmax", "layer_norm", "rms_norm", "batch_norm",
})

_autocast_stack: List[Any] = []


class autocast:
    """``with ht.autocast(ht.bfloat16):`` (reference
    ``python/hetu/__init__.py:141``)."""

    def __init__(self, dtype="bfloat16", enabled: bool = True):
        self.dtype = canonicalize_dtype(dtype)
        self.enabled = enabled

    def __enter__(self):
        _autocast_stack.append(self if self.enabled else None)
        return self

    def __exit__(self, *exc):
        _autocast_stack.pop()


def current_autocast() -> Optional[autocast]:
    return _autocast_stack[-1] if _autocast_stack else None


def _cast_floats(args, dtype):
    out = []
    for a in args:
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating) \
                and a.dtype != dtype:
            out.append(a.astype(dtype))
        else:
            out.append(a)
    return out


def wrap_impl(op_type: str, impl):
    """Fold the ambient autocast policy into an op impl (consulted by the
    op factory at graph-construction time, like the reference's per-op
    dtype deduction under AutoCast)."""
    ac = current_autocast()
    if ac is None:
        return impl
    if op_type in _LOW_PRECISION_OPS:
        lo = ac.dtype.to_jnp()

        def low(*args, **kw):
            return impl(*_cast_floats(args, lo), **kw)
        return low
    if op_type in _FULL_PRECISION_OPS:
        def full(*args, **kw):
            return impl(*_cast_floats(args, jnp.float32), **kw)
        return full
    return impl


# ---------------------------------------------------------------------------
# GradScaler
# ---------------------------------------------------------------------------

def check_finite(grads) -> jax.Array:
    """True iff every leaf of ``grads`` is finite (reference CheckFinite
    kernel: writes a flag consumed by update_scale)."""
    leaves = jax.tree_util.tree_leaves(grads)
    ok = jnp.bool_(True)
    for g in leaves:
        if jnp.issubdtype(g.dtype, jnp.floating):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
    return ok


class GradScaler:
    """Dynamic loss scaling (reference ``hetu/graph/autocast/grad_scaler.*``).

    State lives with the optimizer state so the scale update compiles into
    the same XLA step program as the parameter update.
    """

    def __init__(self, init_scale: float = 2.0 ** 16,
                 growth_factor: float = 2.0, backoff_factor: float = 0.5,
                 growth_interval: int = 2000, enabled: bool = True):
        self.init_scale = float(init_scale)
        self.growth_factor = float(growth_factor)
        self.backoff_factor = float(backoff_factor)
        self.growth_interval = int(growth_interval)
        self.enabled = enabled
        self._host_state: Optional[Dict[str, Any]] = None

    # state pytree: {"scale": f32[], "good_steps": i32[]}
    def init_state(self) -> Dict[str, jax.Array]:
        if self._host_state is None:
            self._host_state = {
                "scale": jnp.float32(self.init_scale),
                "good_steps": jnp.int32(0),
            }
        return self._host_state

    def store_state(self, state: Dict[str, jax.Array]) -> None:
        self._host_state = state

    @property
    def scale(self) -> float:
        return float(self.init_state()["scale"])

    def scale_loss(self, loss, state):
        if not self.enabled:
            return loss
        # scale in fp32: casting the scale into an fp16 loss would overflow
        # (default 2**16 > fp16 max)
        return loss.astype(jnp.float32) * state["scale"]

    def unscale_loss(self, loss, state):
        if not self.enabled:
            return loss
        return loss.astype(jnp.float32) / state["scale"]

    def unscale_grads(self, grads, state):
        if not self.enabled:
            return grads
        inv = (1.0 / state["scale"])
        return jax.tree_util.tree_map(
            lambda g: (g * inv.astype(g.dtype))
            if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)

    def update_state(self, state, finite) -> Dict[str, jax.Array]:
        """The ``update_scale`` op: grow after `growth_interval` consecutive
        finite steps, back off immediately on overflow."""
        if not self.enabled:
            return state
        good = jnp.where(finite, state["good_steps"] + 1, 0)
        grow = good >= self.growth_interval
        scale = jnp.where(
            finite,
            jnp.where(grow, state["scale"] * self.growth_factor,
                      state["scale"]),
            state["scale"] * self.backoff_factor)
        good = jnp.where(grow, 0, good)
        return {"scale": scale.astype(jnp.float32),
                "good_steps": good.astype(jnp.int32)}
