"""Plain reference for every cell's ``correct``: the GPT-2 block forward
and the next-token loss in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` — no kernel, no cache, no
batching, one sequence at a time.  It follows the GPT-2 architecture as
``transformers.GPT2LMHeadModel`` computes it (pre-norm blocks, learned
positions, fused qkv projection with bias, tied LM head), with ONE noted
departure: the MLP uses the tanh approximation of gelu, because that is
what the program under test computes (``hetu_tpu.ops.gelu``); the
published config says ``"activation_function": "gelu"`` (erf).  The
configuration files list this under ``assumed``.

Weights come in as the program's own tensors (``h3.attn.qkv.weight``:
``[out, in]``, used as ``x @ W.T``) so that nothing is converted between
the system and its reference but the dtype.

Tolerances, and why.  The system computes in bf16 (8 mantissa bits, one
rounding ~ 2**-9 = 0.2 % of a value); the reference in float32.
* ``LOSS_TOL``: at random initial weights the loss is ~ ln(vocab) = 10.8
  and the logits are O(0.5), so bf16 rounding of hidden states and logits
  moves a token's loss by a few 1e-3 and the mean over 2048 tokens by
  less; 0.03 leaves a 5-10x margin and is far below what a wrong mask,
  head split or position table does (order 0.1-1).  A system that returns
  its loss IN bf16 adds half the bf16 spacing at 8-16, ``BF16_LOSS_STEP``.
* ``LOGIT_GAP_TOL``: a served greedy token must score within this many
  logit units of the reference's best token, teacher-forced.  Random-
  weight logits spread ~0.5 over the vocabulary and the winner leads the
  runner-up by ~0.1, so bf16 paths may swap near-ties (allowed) but never
  pick an ordinary token, ~2 below the top.  (Rule and number copied from
  ``chip_smoke.LOGIT_TOL``; PR 21 measured 0.0066 on the chip.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LOSS_TOL = 0.03
BF16_LOSS_STEP = 0.0625
LOGIT_GAP_TOL = 0.05

F32 = jnp.float32


def normalize_names(state: dict) -> dict:
    """``transformer.h.0.attn.qkv.weight`` (module paths) and
    ``h0.attn.qkv.weight`` (tensor names) -> the latter."""
    out = {}
    for key, val in state.items():
        if key.startswith("transformer."):
            key = key[len("transformer."):]
        if key.startswith("h."):
            idx, _, tail = key[2:].partition(".")
            key = f"h{idx}.{tail}"
        out[key] = val
    return out


def _layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _block(x, p, n_head: int, eps: float):
    """One pre-norm GPT-2 block on one sequence ``x [s, h]``."""
    with jax.default_matmul_precision("highest"):
        p = {k: v.astype(F32) for k, v in p.items()}
        s, h = x.shape
        hd = h // n_head
        a = _layer_norm(x, p["ln_1.weight"], p["ln_1.bias"], eps)
        qkv = a @ p["attn.qkv.weight"].T + p["attn.qkv.bias"]
        q, k, v = (t.reshape(s, n_head, hd) for t in jnp.split(qkv, 3, -1))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
        x = x + attn @ p["attn.out.weight"].T + p["attn.out.bias"]
        m = _layer_norm(x, p["ln_2.weight"], p["ln_2.bias"], eps)
        m = jax.nn.gelu(m @ p["mlp.up.weight"].T + p["mlp.up.bias"],
                        approximate=True)
        return x + m @ p["mlp.down.weight"].T + p["mlp.down.bias"]


@jax.jit
def _embed(ids, wte, wpe):
    return wte.astype(F32)[ids] + wpe.astype(F32)[:ids.shape[0]]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, b, wte, eps: float):
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, w.astype(F32), b.astype(F32), eps)
        return x @ wte.astype(F32).T


def logits(params: dict, ids, n_layer: int, n_head: int,
           eps: float = 1e-5, positions=None):
    """float32 logits ``[len(positions) or s, vocab]`` of ONE sequence
    ``ids [s]``; the blocks run one jitted call each (same shapes, one
    compile), so only one layer's float32 weights are live at a time."""
    p = normalize_names(params)
    x = _embed(jnp.asarray(ids, jnp.int32), p["wte.weight"], p["wpe"])
    for i in range(n_layer):
        pre = f"h{i}."
        layer = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        x = _block(x, layer, n_head=n_head, eps=eps)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, p["ln_f.weight"], p["ln_f.bias"], p["wte.weight"], eps=eps)


def loss(params: dict, ids, labels, n_layer: int, n_head: int,
         eps: float = 1e-5) -> float:
    """Mean next-token cross entropy of one sequence, float32."""
    lg = logits(params, ids, n_layer, n_head, eps)
    lp = jax.nn.log_softmax(lg, -1)
    labels = jnp.asarray(labels, jnp.int32)
    return float(-jnp.take_along_axis(lp, labels[:, None], -1).mean())


def greedy_logit_gaps(params: dict, seq, prompt_len: int, n_layer: int,
                      n_head: int, pad_to: int, max_new: int,
                      eps: float = 1e-5):
    """How far each generated token's logit lies below the reference's
    best token, teacher-forced on the system's own output: ``seq`` is
    prompt + generated tokens.  The sequence is right-padded to
    ``pad_to`` (causal attention keeps padding out of every position
    read) and the positions read to ``max_new``, so every request shares
    one compiled shape.  Returns a list,
    one gap per generated token."""
    n_new = len(seq) - prompt_len
    ids = list(seq[:-1]) + [0] * (pad_to - (len(seq) - 1))
    pos = [prompt_len - 1 + j for j in range(n_new)]
    pos_padded = pos + [pos[-1]] * (max_new - n_new)
    lg = logits(params, ids, n_layer, n_head, eps, positions=pos_padded)
    lg = lg[:n_new]
    picked = jnp.asarray(seq[prompt_len:], jnp.int32)
    mine = jnp.take_along_axis(lg, picked[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]
