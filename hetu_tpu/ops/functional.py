"""Op library — the graph-level operator surface.

TPU-native re-expression of the reference's op library
(``hetu/graph/ops/*`` — 188 files of ``XxxOpImpl`` + ``MakeXxxOp``
factories, backed by 172 CUDA kernel files in ``hetu/impl/kernel/``).
Here every op is a thin symbolic wrapper over jnp/lax: XLA fuses
elementwise chains into matmuls (replacing hand-written fused CUDA
kernels), and the handful of genuinely custom kernels (flash attention,
ring attention) live in ``hetu_tpu/ops/pallas``.

Ops accept graph ``Tensor`` handles or raw arrays; results are Tensors on
the current graph (eager graph executes immediately).
"""
from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core.dtype import canonicalize_dtype
from ..graph import amp
from ..graph.graph import Graph, get_default_graph
from ..graph.tensor import Tensor

TensorLike = Union[Tensor, jnp.ndarray, float, int]


def _graph_of(*xs) -> Graph:
    for x in xs:
        if isinstance(x, Tensor) and x.graph is not None:
            return x.graph
    return get_default_graph()


def _op(op_type: str, impl, inputs: Sequence[Any], attrs=None, name="",
        num_outputs: int = 1):
    if amp._autocast_stack:
        impl = amp.wrap_impl(op_type, impl)
    g = _graph_of(*inputs)
    return g.make_op(op_type, impl, inputs, attrs or {}, name,
                     num_outputs=num_outputs)


# ---------------------------------------------------------------------------
# arithmetic / unary / binary  (ops/Arithmetics.cc, ops/Unary*.cc)
# ---------------------------------------------------------------------------

def add(a, b):       return _op("add", jnp.add, [a, b])
def sub(a, b):       return _op("sub", jnp.subtract, [a, b])
def mul(a, b):       return _op("mul", jnp.multiply, [a, b])
def div(a, b):       return _op("div", jnp.divide, [a, b])
def neg(a):          return _op("neg", jnp.negative, [a])
def reciprocal(a):   return _op("reciprocal", jnp.reciprocal, [a])
def abs(a):          return _op("abs", jnp.abs, [a])  # noqa: A001
def exp(a):          return _op("exp", jnp.exp, [a])
def log(a):          return _op("log", jnp.log, [a])
def sqrt(a):         return _op("sqrt", jnp.sqrt, [a])
def rsqrt(a):        return _op("rsqrt", lax.rsqrt, [a])
def ceil(a):         return _op("ceil", jnp.ceil, [a])
def floor(a):        return _op("floor", jnp.floor, [a])
def round(a):        return _op("round", jnp.round, [a])  # noqa: A001
def sin(a):          return _op("sin", jnp.sin, [a])
def cos(a):          return _op("cos", jnp.cos, [a])
def tanh(a):         return _op("tanh", jnp.tanh, [a])
def sigmoid(a):      return _op("sigmoid", jax.nn.sigmoid, [a])
def maximum(a, b):   return _op("maximum", jnp.maximum, [a, b])
def minimum(a, b):   return _op("minimum", jnp.minimum, [a, b])


def pow(a, exponent):  # noqa: A001
    return _op("pow", lambda x, e=None: jnp.power(x, e), [a],
               {"e": exponent})


def clamp(a, min=None, max=None):  # noqa: A002
    return _op("clamp", lambda x, lo=None, hi=None: jnp.clip(x, lo, hi),
               [a], {"lo": min, "hi": max})


def where(cond, a, b):
    return _op("where", jnp.where, [cond, a, b])


def cast(a, dtype):
    jdt = canonicalize_dtype(dtype).to_jnp()
    return _op("cast", lambda x, dt=None: x.astype(dt), [a], {"dt": jdt})


# ---------------------------------------------------------------------------
# activations (ops/Relu.cc, Gelu.cc, SwiGLU kernel, ...)
# ---------------------------------------------------------------------------

def relu(a):         return _op("relu", jax.nn.relu, [a])
def leaky_relu(a, alpha=0.01):
    return _op("leaky_relu",
               lambda x, alpha=0.01: jax.nn.leaky_relu(x, alpha),
               [a], {"alpha": alpha})
def gelu(a, approximate=True):
    return _op("gelu",
               lambda x, approximate=True: jax.nn.gelu(x, approximate=approximate),
               [a], {"approximate": approximate})
def silu(a):         return _op("silu", jax.nn.silu, [a])
swish = silu
def elu(a):          return _op("elu", jax.nn.elu, [a])
def softplus(a):     return _op("softplus", jax.nn.softplus, [a])


def swiglu(a):
    """SwiGLU fused activation (reference ``impl/kernel/SwiGLU.cu``):
    input is [..., 2H]; out = silu(x1) * x2.  XLA fuses this chain."""
    def _impl(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jax.nn.silu(x1) * x2
    return _op("swiglu", _impl, [a])


# ---------------------------------------------------------------------------
# matmul family (ops/MatMul.cc, Linear.cc, BatchMatMul.cc) — MXU ops
# ---------------------------------------------------------------------------

def matmul(a, b, trans_a=False, trans_b=False):
    def _impl(x, y, trans_a=False, trans_b=False):
        if trans_a:
            x = jnp.swapaxes(x, -1, -2)
        if trans_b:
            y = jnp.swapaxes(y, -1, -2)
        return jnp.matmul(x, y)
    return _op("matmul", _impl, [a, b],
               {"trans_a": trans_a, "trans_b": trans_b})


batch_matmul = matmul


def linear(x, w, bias=None, trans_b=True):
    """y = x @ w^T + b (reference ops/Linear.cc convention)."""
    if bias is None:
        return matmul(x, w, trans_b=trans_b)
    def _impl(x, w, b, trans_b=True):
        if trans_b:
            w = jnp.swapaxes(w, -1, -2)
        return jnp.matmul(x, w) + b
    return _op("linear", _impl, [x, w, bias], {"trans_b": trans_b})


def einsum(equation: str, *operands):
    return _op("einsum",
               lambda *xs, eq=None: jnp.einsum(eq, *xs),
               list(operands), {"eq": equation})


# ---------------------------------------------------------------------------
# reductions (ops/Reduce*.cc)
# ---------------------------------------------------------------------------

def _norm_axis(axis):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(axis)
    return (axis,)


def reduce_sum(a, axis=None, keepdims=False):
    return _op("reduce_sum",
               lambda x, axis=None, keepdims=False: jnp.sum(x, axis=axis, keepdims=keepdims),
               [a], {"axis": _norm_axis(axis), "keepdims": keepdims})


def reduce_mean(a, axis=None, keepdims=False):
    return _op("reduce_mean",
               lambda x, axis=None, keepdims=False: jnp.mean(x, axis=axis, keepdims=keepdims),
               [a], {"axis": _norm_axis(axis), "keepdims": keepdims})


def reduce_max(a, axis=None, keepdims=False):
    return _op("reduce_max",
               lambda x, axis=None, keepdims=False: jnp.max(x, axis=axis, keepdims=keepdims),
               [a], {"axis": _norm_axis(axis), "keepdims": keepdims})


def reduce_min(a, axis=None, keepdims=False):
    return _op("reduce_min",
               lambda x, axis=None, keepdims=False: jnp.min(x, axis=axis, keepdims=keepdims),
               [a], {"axis": _norm_axis(axis), "keepdims": keepdims})


def argmax(a, axis=-1):
    return _op("argmax", lambda x, axis=-1: jnp.argmax(x, axis=axis),
               [a], {"axis": axis})


def cumsum(a, axis=-1):
    return _op("cumsum", lambda x, axis=-1: jnp.cumsum(x, axis=axis),
               [a], {"axis": axis})


def topk(a, k, axis=-1):
    def _impl(x, k=1, axis=-1):
        if axis in (-1, x.ndim - 1):
            return lax.top_k(x, k)
        xm = jnp.moveaxis(x, axis, -1)
        vals, idx = lax.top_k(xm, k)
        return jnp.moveaxis(vals, -1, axis), jnp.moveaxis(idx, -1, axis)
    return _op("topk", _impl, [a], {"k": k, "axis": axis}, num_outputs=2)


# ---------------------------------------------------------------------------
# shape/view ops (ops/Views.h, Reshape/Transpose/Slice/Split/Concat)
# ---------------------------------------------------------------------------

def reshape(a, shape):
    return _op("reshape", lambda x, shape=None: jnp.reshape(x, shape),
               [a], {"shape": tuple(shape)})


def transpose(a, perm=None):
    return _op("transpose", lambda x, perm=None: jnp.transpose(x, perm),
               [a], {"perm": tuple(perm) if perm is not None else None})


def getitem(a, idx):
    return _op("getitem", lambda x, idx=None: x[idx], [a], {"idx": idx})


def slice(a, begin, size):  # noqa: A001
    """Static slice (reference ops/Slice.cc)."""
    return _op("slice",
               lambda x, begin=None, size=None: lax.slice(
                   x, begin, [b + s for b, s in zip(begin, size)]),
               [a], {"begin": tuple(begin), "size": tuple(size)})


def as_strided(a, shape, strides, storage_offset=0):
    """Strided view over ``a``'s flattened storage (reference
    ``ops/Views.h`` AsStrided / ``impl/kernel`` AsStrided).  ``strides``
    are element strides into the flattened input, as in torch.  XLA has
    no aliasing views, so this materializes a gather — overlapping
    windows are supported (the reference's main AsStrided use case)."""
    ash = a.concrete_shape() if hasattr(a, "concrete_shape") else a.shape
    size = 1
    for d in ash:
        size *= int(d)
    lo = int(storage_offset) + sum(
        (d - 1) * st for d, st in zip(shape, strides) if st < 0)
    hi = int(storage_offset) + sum(
        (d - 1) * st for d, st in zip(shape, strides) if st > 0)
    if lo < 0 or hi >= size:
        raise ValueError(
            f"as_strided window [{lo}, {hi}] exceeds storage of {size} "
            f"elements (shape={tuple(shape)}, strides={tuple(strides)}, "
            f"storage_offset={storage_offset})")

    def _impl(x, shape=None, strides=None, offset=0):
        flat = x.reshape(-1)
        idx = jnp.asarray(offset, jnp.int32)
        for dim, st in zip(shape, strides):
            idx = idx[..., None] + jnp.arange(dim, dtype=jnp.int32) * st
        return flat[idx.reshape(shape)]
    return _op("as_strided", _impl, [a],
               {"shape": tuple(shape), "strides": tuple(strides),
                "offset": int(storage_offset)})


def split(a, num_chunks, axis=0):
    return _op("split",
               lambda x, n=2, axis=0: tuple(jnp.split(x, n, axis=axis)),
               [a], {"n": num_chunks, "axis": axis}, num_outputs=num_chunks)


def concat(tensors, axis=0):
    return _op("concat",
               lambda *xs, axis=0: jnp.concatenate(xs, axis=axis),
               list(tensors), {"axis": axis})


concatenate = concat


def stack(tensors, axis=0):
    return _op("stack", lambda *xs, axis=0: jnp.stack(xs, axis=axis),
               list(tensors), {"axis": axis})


def pad(a, paddings, value=0.0):
    return _op("pad",
               lambda x, paddings=None, value=0.0: jnp.pad(
                   x, paddings, constant_values=value),
               [a], {"paddings": tuple(map(tuple, paddings)), "value": value})


def broadcast_to(a, shape):
    return _op("broadcast_to",
               lambda x, shape=None: jnp.broadcast_to(x, shape),
               [a], {"shape": tuple(shape)})


def triu(a, k=0):
    return _op("triu", lambda x, k=0: jnp.triu(x, k), [a], {"k": k})


def tril(a, k=0):
    return _op("tril", lambda x, k=0: jnp.tril(x, k), [a], {"k": k})


# ---------------------------------------------------------------------------
# indexing (ops/Gather.cc, Scatter, Embedding*)
# ---------------------------------------------------------------------------

def gather(a, indices, axis=0):
    return _op("gather",
               lambda x, idx, axis=0: jnp.take_along_axis(x, idx, axis=axis),
               [a, indices], {"axis": axis})


def index_select(a, indices, axis=0):
    return _op("index_select",
               lambda x, idx, axis=0: jnp.take(x, idx, axis=axis),
               [a, indices], {"axis": axis})


def embedding_lookup(table, ids):
    """Embedding (reference ops/EmbeddingLookup.cc); grads are dense on TPU
    (XLA scatter-add), matching the reference's dense embedding grad."""
    return _op("embedding_lookup", lambda t, i: jnp.take(t, i, axis=0),
               [table, ids])


def one_hot(ids, num_classes, dtype=jnp.float32):
    return _op("one_hot",
               lambda i, n=None, dt=None: jax.nn.one_hot(i, n, dtype=dt),
               [ids], {"n": num_classes, "dt": dtype})


# ---------------------------------------------------------------------------
# softmax & losses (ops/Softmax.cc, *Loss.cc)
# ---------------------------------------------------------------------------

def softmax(a, axis=-1):
    return _op("softmax", lambda x, axis=-1: jax.nn.softmax(x, axis=axis),
               [a], {"axis": axis})


def log_softmax(a, axis=-1):
    return _op("log_softmax",
               lambda x, axis=-1: jax.nn.log_softmax(x, axis=axis),
               [a], {"axis": axis})


def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def nll_loss(log_probs, target, reduction="mean"):
    def _impl(lp, t, reduction="mean"):
        picked = jnp.take_along_axis(lp, t[..., None].astype(jnp.int32),
                                     axis=-1)[..., 0]
        return _reduce_loss(-picked, reduction)
    return _op("nll_loss", _impl, [log_probs, target],
               {"reduction": reduction})


def softmax_cross_entropy(logits, target, reduction="mean",
                          ignore_index: Optional[int] = None):
    """Dense-label or sparse-label softmax CE
    (ops/SoftmaxCrossEntropy[Sparse].cc)."""
    def _impl(lg, t, reduction="mean", ignore_index=None):
        lp = jax.nn.log_softmax(lg, axis=-1)
        if t.dtype in (jnp.int32, jnp.int64):
            picked = jnp.take_along_axis(
                lp, t[..., None].astype(jnp.int32), axis=-1)[..., 0]
            loss = -picked
            if ignore_index is not None:
                mask = (t != ignore_index)
                loss = loss * mask
                if reduction == "mean":
                    return jnp.sum(loss) / jnp.maximum(jnp.sum(mask), 1)
        else:
            loss = -jnp.sum(t * lp, axis=-1)
        return _reduce_loss(loss, reduction)
    return _op("softmax_cross_entropy", _impl, [logits, target],
               {"reduction": reduction, "ignore_index": ignore_index})


sparse_softmax_cross_entropy = softmax_cross_entropy


def mse_loss(pred, target, reduction="mean"):
    return _op("mse_loss",
               lambda p, t, reduction="mean": _reduce_loss((p - t) ** 2, reduction),
               [pred, target], {"reduction": reduction})


def binary_cross_entropy(pred, target, reduction="mean", with_logits=False):
    def _impl(p, t, reduction="mean", with_logits=False):
        if with_logits:
            loss = jnp.maximum(p, 0) - p * t + jnp.log1p(jnp.exp(-jnp.abs(p)))
        else:
            eps = 1e-12
            loss = -(t * jnp.log(p + eps) + (1 - t) * jnp.log(1 - p + eps))
        return _reduce_loss(loss, reduction)
    return _op("bce", _impl, [pred, target],
               {"reduction": reduction, "with_logits": with_logits})


def kl_div(log_probs, target, reduction="mean"):
    def _impl(lp, t, reduction="mean"):
        loss = t * (jnp.log(jnp.maximum(t, 1e-12)) - lp)
        return _reduce_loss(loss, reduction)
    return _op("kl_div", _impl, [log_probs, target], {"reduction": reduction})


# ---------------------------------------------------------------------------
# normalization (ops/LayerNorm.cc, RMSNorm kernel, BatchNorm, InstanceNorm)
# ---------------------------------------------------------------------------

def layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm over the last dim (reference FusedLayerNorm.cu — XLA fuses
    the reduction+normalize chain on TPU)."""
    def _impl(x, s, b, eps=1e-5):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
        inv = lax.rsqrt(var + eps)
        return (x - mean) * inv * s + b
    return _op("layer_norm", _impl, [x, scale, bias], {"eps": eps})


def rms_norm(x, scale, eps=1e-6):
    """RMSNorm (reference impl/kernel/RMSNorm.cu)."""
    def _impl(x, s, eps=1e-6):
        # compute in fp32 for stability, cast back (matches fused kernel)
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * lax.rsqrt(var + eps)
        return (out * s.astype(jnp.float32)).astype(x.dtype)
    return _op("rms_norm", _impl, [x, scale], {"eps": eps})


def batch_norm(x, scale, bias, running_mean=None, running_var=None,
               training=True, eps=1e-5):
    """BatchNorm over NCHW/NC (reference ops/BatchNorm.cc).

    Training (or no stats provided): normalize with batch statistics.
    Eval with stats: normalize with running_mean/running_var.  Running-stat
    *updates* are handled by the nn.BatchNorm2d layer (see
    ``batch_norm_stats``), not here — this op is pure.
    """
    use_batch_stats = training or running_mean is None

    def _norm(x, s, b, mean, var, eps):
        shape = [1, -1] + [1] * (x.ndim - 2)
        inv = lax.rsqrt(var.reshape(shape) + eps)
        return (x - mean.reshape(shape)) * inv * s.reshape(shape) \
            + b.reshape(shape)

    if use_batch_stats:
        def _impl(x, s, b, eps=1e-5):
            axes = (0,) + tuple(range(2, x.ndim))
            return _norm(x, s, b, jnp.mean(x, axis=axes),
                         jnp.var(x, axis=axes), eps)
        return _op("batch_norm", _impl, [x, scale, bias], {"eps": eps})

    def _impl(x, s, b, rm, rv, eps=1e-5):
        return _norm(x, s, b, rm, rv, eps)
    return _op("batch_norm", _impl, [x, scale, bias, running_mean,
                                     running_var], {"eps": eps})


def batch_norm_stats(x):
    """Batch mean/var over the non-channel axes of NCHW/NC input — used by
    nn.BatchNorm2d to maintain running statistics."""
    def _impl(x):
        axes = (0,) + tuple(range(2, x.ndim))
        return jnp.mean(x, axis=axes), jnp.var(x, axis=axes)
    return _op("batch_norm_stats", _impl, [x], num_outputs=2)


def instance_norm(x, eps=1e-7):
    def _impl(x, eps=1e-7):
        axes = tuple(range(2, x.ndim))
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.var(x, axis=axes, keepdims=True)
        return (x - mean) * lax.rsqrt(var + eps)
    return _op("instance_norm", _impl, [x], {"eps": eps})


# ---------------------------------------------------------------------------
# conv / pool (ops/Conv2d.cc, MaxPool.cc, AvgPool.cc) — MXU convs
# ---------------------------------------------------------------------------

def conv2d(x, w, bias=None, stride=1, padding=0):
    """NCHW conv2d (reference ops/Conv2d.cc / cuDNN)."""
    strides = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        pads = [(padding, padding), (padding, padding)]
    else:
        pads = [tuple(p) if isinstance(p, (list, tuple)) else (p, p)
                for p in padding]

    def _impl(x, w, b=None, strides=None, pads=None):
        out = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=pads,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if b is not None:
            out = out + b.reshape(1, -1, 1, 1)
        return out
    inputs = [x, w] if bias is None else [x, w, bias]
    if bias is None:
        return _op("conv2d",
                   lambda x, w, strides=None, pads=None: _impl(
                       x, w, None, strides, pads),
                   inputs, {"strides": strides, "pads": tuple(map(tuple, pads))})
    return _op("conv2d", _impl, inputs,
               {"strides": strides, "pads": tuple(map(tuple, pads))})


def max_pool(x, kernel_size, stride=None, padding=0):
    k = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
    s = k if stride is None else ((stride, stride) if isinstance(stride, int) else tuple(stride))
    p = (padding, padding) if isinstance(padding, int) else tuple(padding)

    def _impl(x, k=None, s=None, p=None):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, 1) + k, (1, 1) + s,
            [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
    return _op("max_pool", _impl, [x], {"k": k, "s": s, "p": p})


def avg_pool(x, kernel_size, stride=None, padding=0):
    k = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
    s = k if stride is None else ((stride, stride) if isinstance(stride, int) else tuple(stride))
    p = (padding, padding) if isinstance(padding, int) else tuple(padding)

    def _impl(x, k=None, s=None, p=None):
        summed = lax.reduce_window(
            x, 0.0, lax.add, (1, 1) + k, (1, 1) + s,
            [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
        counts = lax.reduce_window(
            jnp.ones_like(x), 0.0, lax.add, (1, 1) + k, (1, 1) + s,
            [(0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])])
        return summed / counts
    return _op("avg_pool", _impl, [x], {"k": k, "s": s, "p": p})


# ---------------------------------------------------------------------------
# dropout (ops/Dropout.cc) — stateless RNG via graph-fed key
# ---------------------------------------------------------------------------

_dropout_salt = [0]


def dropout(x, p=0.5, training=True, rng_key=None):
    if not training or p == 0.0:
        return x if isinstance(x, Tensor) else _op("identity", lambda v: v, [x])
    g = _graph_of(x)
    if rng_key is None:
        rng_key = g.next_rng_tensor()
    _dropout_salt[0] += 1

    def _impl(x, key, p=0.5, salt=0):
        keep = 1.0 - p
        key = jax.random.fold_in(key, salt)  # distinct mask per dropout op
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    return _op("dropout", _impl, [x, rng_key],
               {"p": p, "salt": _dropout_salt[0]})


def repeat_kv(x, n_rep: int):
    """Repeat KV heads for GQA: [b, s, kv_heads, d] -> [b, s, kv_heads*n_rep, d]."""
    if n_rep == 1:
        return x
    def _impl(x, n=1):
        b, s, h, d = x.shape
        return jnp.broadcast_to(x[:, :, :, None, :],
                                (b, s, h, n, d)).reshape(b, s, h * n, d)
    return _op("repeat_kv", _impl, [x], {"n": n_rep})


# ---------------------------------------------------------------------------
# rotary embedding (impl/kernel/Rotary.cu)
# ---------------------------------------------------------------------------

def rotary_embed(x, cos, sin, interleaved=False):
    """Apply rotary position embedding to [..., seq, heads, dim] or
    [..., seq, dim] tensors."""
    def _impl(x, cos, sin, interleaved=False):
        if interleaved:
            x1 = x[..., ::2]
            x2 = x[..., 1::2]
            rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
        else:
            half = x.shape[-1] // 2
            x1, x2 = x[..., :half], x[..., half:]
            rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * cos + rot * sin
    return _op("rotary", _impl, [x, cos, sin], {"interleaved": interleaved})


# ---------------------------------------------------------------------------
# attention (ops/Attention.cc; pallas flash kernel on TPU)
# ---------------------------------------------------------------------------

def attention(q, k, v, causal=True, softmax_scale=None, use_flash=None,
              segment_ids=None):
    """Scaled-dot-product attention on [batch, seq, heads, head_dim]
    (reference ops/Attention.cc wrapping flash-attn2).

    On platform ``tpu`` this is the Pallas flash-attention kernel (an
    error in it propagates); elsewhere it is the jnp reference.
    ``segment_ids`` ([b, s] int, -1 pad) gives packed/varlen masking —
    the reference's cu_seqlens path (ops/Attention.h:286).

    A Mosaic call is opaque to the SPMD partitioner ("cannot be
    automatically partitioned"), so under a multi-device graph mesh the
    kernel runs inside a ``shard_map`` placed on the batch/head sharding
    ``q`` was annotated with — what the context-parallel paths do for
    the same kernel.  The reference needs no such help from GSPMD.
    """
    from jax.sharding import PartitionSpec as P
    from .attention import sdpa  # local import to avoid cycle
    from .pallas import on_tpu
    g = _graph_of(q, k, v)
    q_tensor = q

    def _impl(q, k, v, segs=None, causal=True, softmax_scale=None):
        def attn(q, k, v, segs=None):
            return sdpa(q, k, v, causal=causal, softmax_scale=softmax_scale,
                        use_flash=use_flash, segment_ids=segs)
        mesh = g.mesh
        flash = on_tpu() if use_flash is None else use_flash
        if not flash or mesh is None or mesh.size == 1:
            return attn(q, k, v, segs)
        spec = tuple(g._pspec_for(q_tensor) or ())
        b_ax, s_ax, h_ax, d_ax = spec + (None,) * (4 - len(spec))
        if s_ax is not None or d_ax is not None:
            raise ValueError(
                f"attention needs seq and head_dim unsharded, got q "
                f"pspec {spec}; sequence sharding is parallel_attention")
        from ..parallel.comm import shard_map
        spec = P(b_ax, None, h_ax, None)
        if segs is None:
            return shard_map(attn, mesh, (spec, spec, spec), spec)(q, k, v)
        return shard_map(attn, mesh, (spec, spec, spec, P(b_ax, None)),
                         spec)(q, k, v, segs)

    inputs = [q, k, v] if segment_ids is None else [q, k, v, segment_ids]
    return _op("attention", _impl, inputs,
               {"causal": causal, "softmax_scale": softmax_scale})


def attention_qkv(qkv, num_heads: int, causal=True, softmax_scale=None,
                  use_flash=None, segment_ids=None):
    """Self-attention straight off the fused projection: ``qkv``
    [batch, seq, 3 * heads * head_dim] (q | k | v on the last axis) ->
    [batch, seq, heads * head_dim].  What :func:`attention` computes for
    the three slices, without cutting them out: on platform ``tpu`` the
    flash kernels take q, k and v from the one array by block index
    (``ops.pallas.flash_attention.flash_attention_qkv``).

    Under a multi-device mesh the kernel runs in a ``shard_map`` over the
    batch axis ``qkv`` was annotated with, as in :func:`attention`.  Where
    the mesh shards the fused axis (tensor parallelism cuts it across
    q | k | v) the slices are cut after all and the heads sharded over
    that axis.
    """
    from jax.sharding import PartitionSpec as P
    from .attention import sdpa, sdpa_qkv  # local import to avoid cycle
    from .pallas import on_tpu
    g = _graph_of(qkv)
    qkv_tensor = qkv

    def _impl(qkv, segs=None, causal=True, softmax_scale=None):
        kw = dict(causal=causal, softmax_scale=softmax_scale,
                  use_flash=use_flash)
        mesh = g.mesh
        flash = on_tpu() if use_flash is None else use_flash
        if not flash or mesh is None or mesh.size == 1:
            return sdpa_qkv(qkv, num_heads, segment_ids=segs, **kw)
        spec = tuple(g._pspec_for(qkv_tensor) or ())
        b_ax, s_ax, f_ax = spec + (None,) * (3 - len(spec))
        if s_ax is not None:
            raise ValueError(
                f"attention_qkv needs seq unsharded, got qkv pspec {spec}; "
                f"sequence sharding is parallel_attention")
        from ..parallel.comm import shard_map
        seg_spec = () if segs is None else (P(b_ax, None),)
        seg_arg = () if segs is None else (segs,)
        if f_ax is None or math.prod(
                mesh.shape[a] for a in (f_ax if isinstance(f_ax, tuple)
                                        else (f_ax,))) == 1:
            spec = P(b_ax, None, None)
            return shard_map(
                lambda x, s=None: sdpa_qkv(x, num_heads, segment_ids=s, **kw),
                mesh, (spec,) + seg_spec, spec)(qkv, *seg_arg)
        b, s, w = qkv.shape
        spec = P(b_ax, None, f_ax, None)
        out = shard_map(
            lambda q, k, v, s=None: sdpa(q, k, v, segment_ids=s, **kw),
            mesh, (spec,) * 3 + seg_spec, spec)(
                *(x.reshape(b, s, num_heads, -1)
                  for x in jnp.split(qkv, 3, axis=-1)), *seg_arg)
        return out.reshape(b, s, w // 3)

    inputs = [qkv] if segment_ids is None else [qkv, segment_ids]
    return _op("attention_qkv", _impl, inputs,
               {"causal": causal, "softmax_scale": softmax_scale})


def parallel_attention(q, k, v, causal=True, softmax_scale=None,
                       cp_axis: str = "cp", batch_axis: str = "dp",
                       head_axis: str = "tp", segment_ids=None,
                       cp_impl: str = "ring"):
    """Context-parallel attention op (reference ParallelAttentionOp,
    ops/ParallelAttention.h:425): sequence sharded over ``cp_axis``.
    Requires the owning graph to carry a mesh with the cp axis; otherwise
    falls back to plain attention.
    ``segment_ids`` ([b, s] global doc ids, -1 pad) rides the KV ring —
    the reference's packed/varlen path (``ParallelAttention.cc:1061``).

    ``cp_impl``: "ring" (KV ring via ppermute + online LSE correction,
    the reference's AttnCommRing) or "ulysses" (all-to-all head scatter;
    no reference counterpart — TPU-native extension; indivisible head
    counts are zero-padded up to the cp(x tp) multiple).
    """
    g = _graph_of(q, k, v)
    mesh = getattr(g, "mesh", None)
    if mesh is None or cp_axis not in mesh.axis_names:
        raise ValueError(
            f"parallel_attention requires a graph mesh with axis "
            f"{cp_axis!r}; got mesh={mesh}. Use ops.attention for non-CP "
            f"runs instead of silently dropping context parallelism.")
    if cp_impl not in ("ring", "ulysses"):
        raise ValueError(f"cp_impl must be 'ring' or 'ulysses', "
                         f"got {cp_impl!r}")
    if mesh.shape[cp_axis] == 1:
        # degenerate ring: identical semantics, skip the shard_map
        return attention(q, k, v, causal=causal, softmax_scale=softmax_scale,
                         segment_ids=segment_ids)
    from ..parallel.ring_attention import ring_attention_sharded
    from ..parallel.ulysses import ulysses_attention_sharded
    sharded_attn = ring_attention_sharded if cp_impl == "ring" \
        else ulysses_attention_sharded

    def _impl(q, k, v, segment_ids=None, causal=True, softmax_scale=None):
        return sharded_attn(q, k, v, mesh, axis_name=cp_axis,
                            causal=causal,
                            softmax_scale=softmax_scale,
                            batch_axis=batch_axis,
                            head_axis=head_axis,
                            segment_ids=segment_ids)
    inputs = [q, k, v] if segment_ids is None else [q, k, v, segment_ids]
    if segment_ids is None:
        impl = lambda q, k, v, causal=True, softmax_scale=None: _impl(
            q, k, v, None, causal, softmax_scale)
    else:
        impl = _impl
    return _op("parallel_attention", impl, inputs,
               {"causal": causal, "softmax_scale": softmax_scale})


def fused_lm_cross_entropy(x, weight, labels, ignore_index=-100,
                           num_chunks: int = 8, reduction: str = "mean"):
    """LM-head matmul + CE fused, logits never materialized whole (the
    reference's VocabParallelCrossEntropyLoss pipeline collapsed into one
    chunked op — see ops/fused_ce.py).  x: [b, s, h] or [n, h];
    weight: [vocab, h]; labels match x's leading dims."""
    from .fused_ce import fused_linear_cross_entropy

    def _impl(x, w, lbl, ignore_index=-100, num_chunks=8,
              reduction="mean"):
        n = 1
        for d in x.shape[:-1]:
            n *= d
        return fused_linear_cross_entropy(
            x.reshape(n, x.shape[-1]), w, lbl.reshape(n),
            ignore_index, num_chunks, reduction)

    return _op("fused_lm_cross_entropy", _impl, [x, weight, labels],
               {"ignore_index": ignore_index, "num_chunks": num_chunks,
                "reduction": reduction})


# ---------------------------------------------------------------------------
# AMP helpers (ops/CheckFinite, update_scale)
# ---------------------------------------------------------------------------

def check_finite(x):
    return _op("check_finite",
               lambda v: jnp.all(jnp.isfinite(v)).astype(jnp.float32), [x])


def arange(start, stop=None, step=1, dtype=jnp.int32):
    g = get_default_graph()
    if stop is None:
        start, stop = 0, start
    return _op("arange",
               lambda start=0, stop=None, step=1, dt=None: jnp.arange(
                   start, stop, step, dtype=dt),
               [], {"start": start, "stop": stop, "step": step, "dt": dtype})


def full(shape, fill_value, dtype=jnp.float32):
    return _op("full",
               lambda shape=None, v=0, dt=None: jnp.full(shape, v, dtype=dt),
               [], {"shape": tuple(shape), "v": fill_value, "dt": dtype})


def zeros(shape, dtype=jnp.float32):
    return full(shape, 0.0, dtype)


def ones(shape, dtype=jnp.float32):
    return full(shape, 1.0, dtype)
