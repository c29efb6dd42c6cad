"""Plain reference for the Mamba-1 / attention stack with dense
feed-forwards (``model_type: jamba``, ``num_experts: 1``): the forward pass
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``
— no kernel, no cache, no chunking of the recurrence, no batching; one
sequence at a time.  It imports nothing of the program under test.

Layer ``i`` of ``num_hidden_layers``: ``x = x + mixer_i(RMSNorm(x))``, then
``x = x + mlp_i(RMSNorm(x))``; a final RMSNorm; logits ``x @ wte.T`` (the
head is tied).  The equations, with every departure from the published
description:

* Mamba-1 (every layer but the attention ones): ``[xs | z] = W_in u``;
  ``xc = silu(conv_K(xs) + b)`` (causal, depthwise, over the x channels
  alone); ``[dt_r | B | C] = W_x xc``, each through its own RMSNorm (weight,
  the model's eps); ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``;
  the recurrence ``h_t = exp(dt_t (x) A) h_{t-1} + (dt_t xc_t) (x) B_t``,
  ``y_t = h_t C_t + D xc_t`` as a SEQUENTIAL ``lax.scan`` over tokens, the
  state float32 (the program walks it in a kernel, a chunk at a time); out
  ``W_out (y * silu(z))``: no norm before the way out.  DEPARTURE: ``A_log``
  comes in as ``[N, C]``, the program's tensor (the published one is ``[C,
  N]``); the arithmetic is the same.
* attention (``i % attn_layer_period == attn_layer_offset``): ``q`` of
  ``num_attention_heads`` heads on ``num_key_value_heads`` K/V heads of
  ``hidden / heads`` lanes, no bias, causal softmax at ``head_dim ** -0.5``,
  NO positional encoding (the family applies none).  Computed in blocks of
  ``Q_BLOCK`` query rows so that 33k positions fit: the same arithmetic row
  by row.
* feed-forward: ``down(silu(gate u) * up u)``, in blocks of rows likewise.
* ASSUMED: the order of the layer types from ``attn_layer_offset`` /
  ``attn_layer_period`` (the family's rule); ``expert_layer_*`` read by
  nothing, every feed-forward being dense.

Weights come in as the program's own tensors (names in
``hetu_tpu/models/hybrid.py``: the published layer ``i`` is ``h{2i}`` — its
mixer — and ``h{2i+1}`` — its feed-forward; a projection ``W`` is ``[out,
in]`` used as ``x @ W.T``), in whatever dtype they are served in and wherever they lie
(the cell hands them over as host arrays, a sublayer's going to the device
with its call), and are upcast one sublayer at a time.

Tolerances, and why (the cell's ``correct``; the CPU tests state their
own).  The system computes in bf16 with a float32 scan state; this file in
float32.  A served greedy token is BEYOND when it scores more than
``LOGIT_GAP_TOL`` logit units below the reference's best token,
teacher-forced on the served sequence; the run is correct when at most
``GAP_SHARE_TOL`` of the checked tokens are beyond (the rule of the other
long-document cells).  A share and not the worst token, because a context
of 16-33k tokens under 28 layers of bf16 leaves each logit with a noise of
its own and greedy picks among near-ties: the worst of ~550 tokens is the
tail of that noise and moves by the seed, the share beyond a gap well above
it does not.  First reading, the system in bf16 on the chip (four requests,
542-550 tokens a run): **no token beyond 0.3** in any run, worst gap
0.026-0.085, mean 0.0002-0.0009 (my chip runs, PR 53; PERF.md section 6
keeps the list).  Second reading, :func:`lowp_choice_gaps` — this file
itself computed as a float8 deployment would (e4m3, the nearest precision
below bf16: every weight matrix and every sublayer's input and output
rounded, scaled per tensor; ``A_log`` kept, a recurrence's decay is never
stored in 8 bits), scored the same way: **19.8 % beyond 0.3, worst 1.36**
(seed 4000000007) and **30.5 %, worst 1.10** (seed 2999888777) — not
correct.  ``GAP_SHARE_TOL`` 5 % stands four times under the second reading
and, at 550 tokens, 27 tokens over the first (a run that reads 0 has fewer
than 0.2 % beyond).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOGIT_GAP_TOL = 0.3
GAP_SHARE_TOL = 0.05
Q_BLOCK = 256           # query rows of the attention at a time
ROW_BLOCK = 1024        # rows of a feed-forward at a time

F32 = jnp.float32


def spec_from_config(config: dict) -> dict:
    """The sizes this file needs, from the published ``config.json`` keys."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    return {
        "layers": config["num_hidden_layers"],
        "period": config["attn_layer_period"],
        "offset": config["attn_layer_offset"],
        "hidden": hidden, "heads": heads,
        "kv_heads": config["num_key_value_heads"],
        "head_dim": hidden // heads,
        "inner": config["mamba_expand"] * hidden,
        "state": config["mamba_d_state"],
        "dt_rank": config["mamba_dt_rank"],
        "conv_kernel": config["mamba_d_conv"],
        "eps": float(config["rms_norm_eps"]),
    }


def is_attention(spec: dict, i: int) -> bool:
    return i % spec["period"] == spec["offset"]


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(p: dict) -> dict:
    return {k: v.astype(F32) for k, v in p.items()}


def _by_rows(f, block: int, *xs):
    """``f(*xs)`` over blocks of ``block`` rows of every ``x`` (whole where
    the rows do not divide): rows are independent, the result is the
    same."""
    t = xs[0].shape[0]
    if t <= block or t % block:
        return f(*xs)
    out = lax.map(lambda a: f(*a), tuple(
        x.reshape(t // block, block, *x.shape[1:]) for x in xs))
    return out.reshape(t, *out.shape[2:])


# -- the sublayers ------------------------------------------------------------

def scan_inputs(u, p: dict, spec: dict):
    """Everything before the recurrence, on ``u`` [T, hidden] (normed):
    ``(xc [T, C], dt [T, C], B [T, N], C [T, N])``."""
    ch, n, r, k = (spec["inner"], spec["state"], spec["dt_rank"],
                   spec["conv_kernel"])
    t = u.shape[0]
    xs = u @ p["in_proj.weight"][:ch].T
    pad = jnp.concatenate([jnp.zeros((k - 1, ch), F32), xs], 0)
    conv = sum(pad[j: j + t] * p["conv.weight"][j] for j in range(k))
    xc = jax.nn.silu(conv + p["conv.bias"])
    dt_r, b, c = jnp.split(xc @ p["x_proj.weight"].T, [r, r + n], axis=-1)
    dt_r = _rms(dt_r, p["dt_norm.weight"], spec["eps"])
    b = _rms(b, p["b_norm.weight"], spec["eps"])
    c = _rms(c, p["c_norm.weight"], spec["eps"])
    dt = jax.nn.softplus(dt_r @ p["dt_proj.weight"].T + p["dt_proj.bias"])
    return xc, dt, b, c


def recurrence(xc, dt, b, c, a, d, state=None):
    """The selective scan, token by token: ``a`` [N, C], ``d`` [C], ``state``
    [N, C] (zeros by default).  Returns ``(y [T, C], final state)``."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = h * jnp.exp(dt_t[None, :] * a) + \
            (dt_t * x_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], 0) + d * x_t

    h0 = jnp.zeros(a.shape, F32) if state is None else state
    h, y = lax.scan(step, h0, (xc, dt, b, c), unroll=8)
    return y, h


def mamba1(u, p: dict, spec: dict):
    """``u`` [T, hidden] (already normed) -> [T, hidden]."""
    xc, dt, b, c = scan_inputs(u, p, spec)
    y, _ = recurrence(xc, dt, b, c, -jnp.exp(p["A_log"]), p["D"])
    z = u @ p["in_proj.weight"][spec["inner"]:].T
    return (y * jax.nn.silu(z)) @ p["out_proj.weight"].T


def attention(u, p: dict, spec: dict):
    nh, kv, hd = spec["heads"], spec["kv_heads"], spec["head_dim"]
    t = u.shape[0]
    qkv = u @ p["qkv.weight"].T
    q, k, v = jnp.split(qkv, [nh * hd, (nh + kv) * hd], axis=-1)
    k, v = k.reshape(t, kv, hd), v.reshape(t, kv, hd)

    def block(qb, pos):
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(hd)
        seen = jnp.arange(t)[None, :] <= pos[:, None]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("hgqk,khd->qhgd", pr, v).reshape(-1, nh * hd)

    o = _by_rows(block, Q_BLOCK, q.reshape(t, kv, nh // kv, hd),
                 jnp.arange(t))
    return o @ p["out.weight"].T


def mlp(u, p: dict):
    def block(ub):
        return (jax.nn.silu(ub @ p["gate.weight"].T) *
                (ub @ p["up.weight"].T)) @ p["down.weight"].T
    return _by_rows(block, ROW_BLOCK, u)


# -- the stack ----------------------------------------------------------------

def _fp8(v):
    """Through float8 (e4m3: 3 mantissa bits) and back, scaled per tensor
    so that its largest entry sits at the format's largest (448)."""
    s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 448.0
    return (v / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@functools.partial(jax.jit, static_argnames=("kind", "spec_items", "lowp"))
def _sublayer(x, p, kind: str, spec_items, lowp: bool = False):
    """``x + f(RMSNorm(x))`` for one sublayer.  ``lowp``: what a float8
    deployment rounds — every weight matrix, and the sublayer's input and
    output — goes through ``_fp8``."""
    spec = dict(spec_items)
    rnd = _fp8 if lowp else (lambda v: v)
    if lowp:
        p = {k: _fp8(v.astype(F32)).astype(v.dtype)
             if v.ndim >= 2 and not k.endswith("A_log") else v
             for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        u = rnd(_rms(x, p["norm.weight"].astype(F32), spec["eps"]))
        if kind == "mamba1":
            return x + rnd(mamba1(u, _f32(_sub(p, "mamba.")), spec))
        if kind == "attention":
            return x + rnd(attention(u, _f32(_sub(p, "attn.")), spec))
        return x + rnd(mlp(u, _f32(_sub(p, "mlp."))))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, head, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w.astype(F32), eps) @ head.astype(F32).T


def _sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _freeze(spec: dict):
    return tuple(sorted(spec.items()))


def hidden_states(params: dict, ids, spec: dict, lowp: bool = False):
    """The residual stream ``[T, hidden]`` behind the last layer of ONE
    sequence ``ids [T]``: one jitted call a sublayer, each waited for, so
    only one sublayer's float32 weights and temporaries are live at a time
    (calls enqueued ahead of the device each hold theirs: 56 of them at
    33k tokens were 8 GB more than one, my chip runs, PR 53)."""
    x = jnp.asarray(params["wte.weight"][np.asarray(ids, np.int32)], F32)
    for i in range(spec["layers"]):
        kind = "attention" if is_attention(spec, i) else "mamba1"
        for j, k in ((2 * i, kind), (2 * i + 1, "mlp")):
            x = _sublayer(x, _sub(params, f"h{j}."), kind=k,
                          spec_items=_freeze(spec), lowp=lowp)
            x.block_until_ready()
    return x


def logits(params: dict, ids, spec: dict, positions=None,
           lowp: bool = False):
    """float32 logits ``[len(positions) or T, vocab]`` of ONE sequence."""
    x = hidden_states(params, ids, spec, lowp)
    if positions is not None:
        x = x[jnp.asarray(positions, jnp.int32)]
    return _head(x, params["ln_f.weight"], params["wte.weight"],
                 eps=spec["eps"])


def _padded(seq, prompt_len: int, pad_to: int, max_new: int):
    n_new = len(seq) - prompt_len
    ids = list(seq[:-1]) + [0] * (pad_to - (len(seq) - 1))
    pos = [prompt_len - 1 + j for j in range(n_new)]
    return n_new, ids, pos + [pos[-1]] * (max_new - n_new)


def lowp_choice_gaps(params: dict, seq, prompt_len: int, spec: dict,
                     pad_to: int, max_new: int):
    """The second reading of the limits: at each generated position of
    ``seq``, the token the float8-rounded forward pass would pick, scored
    against this file's float32 logits."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos)[:n_new]
    low = logits(params, ids, spec, positions=pos, lowp=True)[:n_new]
    mine = jnp.take_along_axis(lg, low.argmax(-1)[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]


def greedy_logit_gaps(params: dict, seq, prompt_len: int, spec: dict,
                      pad_to: int, max_new: int):
    """How far each generated token's logit lies below the reference's
    best token, teacher-forced on the system's own output: ``seq`` is
    prompt + generated tokens, right-padded to ``pad_to`` (every sublayer
    is causal, so padding reaches no position read) and the positions read
    padded to ``max_new``, so every request shares one compiled shape.
    Returns one gap per generated token."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos)[:n_new]
    picked = jnp.asarray(seq[prompt_len:], jnp.int32)
    mine = jnp.take_along_axis(lg, picked[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]
