"""Plain reference for the indexed / window latent-attention stack with a
leading dense layer (``model_type: dots3_note``): the forward pass in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")`` —
no kernel, no cache, no batching, one sequence at a time, the attention
NOT absorbed (every token's latent is decompressed into per-head keys and
values), the selection and the window as MASKS over the whole sequence,
and its own top-k over its own float32 index scores.  It imports nothing
of the program under test.  ``hetu_tpu/models/dots3_reference.py`` is a
byte-for-byte copy (a test holds them equal): the CPU tests use that one,
the benchmark cell this one.

Every published layer ``l`` is ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))`` (tensors ``h{2l}.`` then ``h{2l + 1}.``); then a final
RMSNorm and an untied head.  The equations, with every departure and each
ASSUMED convention (the configuration file lists the same four):

* Full layer (``layer_types[l] == "full_attention"``).  MLA as
  ``deepseek_v3``: ``c_q = a_q RMSNorm(W_qa u)``; ``q = W_qb c_q`` -> heads
  x (nope | rope); ``[c_kv | k_r] = W_kva u``; ``c_kv = a_kv RMSNorm(c_kv)``;
  ``k_h = [W_uk,h c_kv | rot k_r]`` (one rotated ``k_r`` for all heads),
  ``v_h = W_uv,h c_kv``; rotary on interleaved pairs ``(2i, 2i + 1)``, base
  ``rope_theta``, unscaled (``rope_scaling: null``); softmax scale ``(nope +
  rope) ** -0.5``.  ASSUMED (a) ``apply_mla_qkv_lora_rescale``: ``a_q =
  sqrt(hidden / q_lora_rank)``, ``a_kv = sqrt(hidden / kv_lora_rank)``
  behind the norms (the LongCat-Flash ``mla_scale_q_lora / kv_lora``
  convention).  INDEXER, ASSUMED (c) DeepSeek-V3.2's, whose three
  ``index_*`` keys the config carries: ``qI = W_Iq c_q`` (``index_n_heads``
  x ``index_head_dim``), ``kI = LayerNorm(W_Ik u)`` (eps 1e-5, one a token),
  rotary by halves on the first ``qk_rope_head_dim`` of both, ``w = W_Iw u
  * index_n_heads ** -0.5 * index_head_dim ** -0.5``; ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` = the
  ``index_topk`` largest (``lax.top_k`` of the float32 scores; every ``s
  <= t`` while ``t < index_topk``); the softmax runs over ``s in S_t``
  only.  That kernel's Hadamard rotation and FP8 keys are its numerics
  (orthogonal: the scores are unchanged) and are not built.  HEAD GATE,
  ASSUMED (b): ``g = sigmoid(W_g u)`` (one scalar a head) on the mixer's
  normed input, ``o_h <- g_h o_h`` before ``W_o`` (the gated-attention
  form ``attention_gate_type: headwise`` is named after).
* Window layer (``"sliding_attention"``): the same MLA at the ``swa_*``
  sizes and base, keys ``t - (W - 1) ... t`` — ASSUMED (d)
  ``sliding_window_size`` W = 513 counts the query itself — no indexer, a
  head gate of its own width.
* FFN: the first ``first_k_dense_replace`` layers ``W_down (silu(W_gate
  u) * W_up u)``, ``intermediate_size`` wide.  After them ``s = sigmoid(W_r
  u)`` over ALL routed experts in float32; the ``num_experts_per_tok``
  largest of ``s + b`` (``noaux_tc``, one group); weights ``s / sum s``
  over the chosen (``norm_topk_prob``) x ``routed_scaling_factor``; gated
  silu experts over the experts HELD here (``n_routed_experts`` from
  ``expert_offset``: what the absent experts would add is left out, as in
  the program); plus one shared expert, unweighted (``shared=False``
  leaves it out, for the share test).
* The vocabulary is the slice the weights hold; the vision tower, the
  audio encoder and MTP are left out.

Sized to run a 33k-token sequence beside the served weights: attention in
groups of ``HEAD_GROUP`` heads and blocks of ``Q_BLOCK`` query rows (the
indexer ``INDEX_BLOCK``; a window layer's block reads the ``Q_BLOCK +
window`` keys that hold its queries' windows, the mask is the same), the
FFNs in blocks of ``TOKEN_BLOCK`` tokens; the arithmetic is the whole
softmax's and the whole layer's.  Two savings change which ROWS are
computed and never the arithmetic of a row (a test holds both equal to
the whole evaluation): ``tail`` (only what the read positions depend on)
and ``document_state`` (the stream a shared document's positions give the
full layers, computed once a distinct document).  A third changes WHEN
the compiler runs and nothing else: ``compile_ahead`` walks the same two
evaluations over shapes alone and compiles every call they will make (on
the chip the float32 ``highest`` matmuls and the top-k of 33k scores take
some 70 s to compile and 20 s to run; the cell's driver compiles them on
a thread beside its warm-up).

Weights come in as the program's own tensors (names in
``hetu_tpu/models/hybrid.py``; a projection ``W`` is ``[out, in]`` used as
``x @ W.T``; expert stacks ``w1`` (gate), ``w3`` (up) ``[E, in, out]``,
``w2`` (down) ``[E, out, in]``), in whatever dtype they are served in, and
are upcast one layer (one expert) at a time.

Tolerances, and why (the cell's ``correct``; the CPU tests state their
own).  The system computes in bf16; this file in float32.  A served greedy
token is BEYOND when it scores more than ``LOGIT_GAP_TOL`` (0.3) logit
units below the reference's best token, teacher-forced on the served
sequence; the run is correct when at most ``GAP_SHARE_TOL`` of the checked
tokens are beyond — a share and not the worst token for the reason
``reference_mistral4`` gives: top-8 of a 256-wide sigmoid router over
seeded weights flips on a rounding, a flipped expert enters at weight
~1/8, and four expert layers and a selection of 2,048 positions amplify
it, so ANY 8-bit-mantissa arithmetic puts some tokens far off.  First
reading, the system in bf16 on the chip: 12.76-17.39 % of 481-774 tokens
beyond over 33 runs (worst gaps 1.07-4.49); second reading,
this file rounded to float8 (e4m3, the nearest precision below bf16:
every weight matrix and every mixer's input and output, scaled per
tensor): 63.87 and 65.23 % beyond on two seeds, which has to fail.  35 %
lies between, with about a factor of two of room on both sides.
``index_select_overlap`` is the share of the positions the program's
indexer arithmetic (bf16: ``hybrid.index_positions``, on this file's own
layer input) selects that this file's float32 indexer selects for the
same query, the least over the checked requests and full layers: first
reading 0.9947-0.9953 (a bf16 score swaps about 10 of 2,048 places with
its neighbours across the 2,048th), second (this file's indexer rounded
to float8) 0.9377 and 0.9465; the limit ``SELECT_OVERLAP_TOL`` 0.97.
All readings: my chip runs, PR 39 (``PERF.md`` section 4).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LOGIT_GAP_TOL = 0.3
GAP_SHARE_TOL = 0.35
SELECT_OVERLAP_TOL = 0.97
Q_BLOCK = 128
INDEX_BLOCK = 32           # query rows a score tile (x index heads x T)
TOPK_BLOCK = 256           # rows a ``lax.top_k``: 8 score tiles (the TPU
#                            compiler takes 19 s over 32 rows of 33k, 5 s
#                            over 256: another algorithm, the same result)
HEAD_GROUP = 8
TOKEN_BLOCK = 4096
LAYER_NORM_EPS = 1e-5

F32 = jnp.float32
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def spec_from_config(config: dict) -> dict:
    """The sizes this file needs, from the published ``config.json`` keys
    (and ``n_routed_experts`` / ``expert_offset`` / ``layer_types`` as
    cut).  Flat, so that it can key a compiled layer."""
    hidden = config["hidden_size"]
    rescale = bool(config.get("apply_mla_qkv_lora_rescale"))
    spec = {
        "kinds": tuple(KINDS[t] for t in config["layer_types"]),
        "dense": int(config.get("first_k_dense_replace", 0)),
        "routed": config.get("moe_router_outputs",
                             config["n_routed_experts"]),
        "top_k": config["num_experts_per_tok"],
        "route_scale": float(config["routed_scaling_factor"]),
        "held": config["n_routed_experts"],
        "offset": config.get("expert_offset", 0),
        "eps": float(config["rms_norm_eps"]),
        "index_heads": config["index_n_heads"],
        "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "window.window": config["sliding_window_size"],
    }
    for kind, pre in (("full", ""), ("window", "swa_")):
        g = lambda k: config[pre + k]  # noqa: E731
        nope, rope = g("qk_nope_head_dim"), g("qk_rope_head_dim")
        spec.update({
            f"{kind}.heads": g("num_attention_heads"),
            f"{kind}.nope": nope, f"{kind}.rope": rope,
            f"{kind}.v": g("v_head_dim"), f"{kind}.latent": g("kv_lora_rank"),
            f"{kind}.theta": float(g("rope_theta")),
            f"{kind}.scale": (nope + rope) ** -0.5,
            f"{kind}.a_q": math.sqrt(hidden / g("q_lora_rank"))
            if rescale else 1.0,
            f"{kind}.a_kv": math.sqrt(hidden / g("kv_lora_rank"))
            if rescale else 1.0})
    return spec


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(p: dict) -> dict:
    return {k: v.astype(F32) for k, v in p.items()}


def _angles(pos, width: int, theta: float):
    """``pos [T]`` (host integers) x the ``width / 2`` frequencies, made on
    the host in float64 (a device's float32 ``pow`` is some 1e-6 off, which
    33,000 positions turn into 0.03 rad)."""
    inv = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    return np.asarray(pos, np.float64)[:, None] * inv


def rotate_pairs(x, pos, theta: float):
    """``x [T, ..., rope]``: the pair ``(x[2i], x[2i + 1])`` turned by
    ``pos * freq_i``, in place."""
    ang = _angles(pos, x.shape[-1], theta)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = (jnp.asarray(f(ang), F32) for f in (np.cos, np.sin))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def rotate_halves(x, pos, theta: float):
    """``x [T, ..., r]``: the pair ``(x[i], x[i + r / 2])`` turned by ``pos
    * freq_i`` (the indexer's layout)."""
    ang = _angles(pos, x.shape[-1], theta)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = (jnp.asarray(f(ang), F32) for f in (np.cos, np.sin))
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(f, arrays, blk: int):
    """``f`` over blocks of ``blk`` leading rows of ``arrays`` (a tuple of
    arrays of one length), the results (an array or a tuple of them)
    joined; the tail block is padded with zeros and cut."""
    t = arrays[0].shape[0]
    blk = min(blk, t)
    pad = -t % blk
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)  # noqa: E731
                            ).reshape((-1, blk) + a.shape[1:])
    out = lax.map(f, tuple(cut(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((-1,) + o.shape[2:])[:t], out)


# -- the mixers ---------------------------------------------------------------

def _low_rank_q(u, p: dict, spec: dict, kind: str):
    return _rms(u @ p["q_a.weight"].T, p["q_a_norm.weight"],
                spec["eps"]) * spec[f"{kind}.a_q"]


def select(u, p: dict, spec: dict, first: int = 0):
    """The indexer: for the queries at positions ``first ..`` of ``u`` [T,
    hidden] (the full layer's normed input, positions 0 .. T - 1) the
    positions ``S_t``, twice: ``[T - first, k]`` int32 with ``k =
    min(index_topk, T)``, a place a query has no position for (``t + 1 <
    k``) holding ``T``; and the same set as a mask over the T positions,
    packed 8 to a byte (``jnp.packbits``), which is what ``attention``
    reads.  A tie at the k-th score goes to the lower position, as
    ``lax.top_k`` breaks it."""
    t = u.shape[0]
    nh, d, r = spec["index_heads"], spec["index_dim"], spec["full.rope"]
    theta, pos, qpos = spec["full.theta"], np.arange(t), np.arange(first, t)
    uq = u[first:]
    c_q = _low_rank_q(uq, p, spec, "full")
    q = (c_q @ p["index.q.weight"].T).reshape(t - first, nh, d)
    k = u @ p["index.k.weight"].T
    k = (k - k.mean(-1, keepdims=True)) * lax.rsqrt(
        k.var(-1, keepdims=True) + LAYER_NORM_EPS)
    k = k * p["index.k_norm.weight"] + p["index.k_norm.bias"]
    q = jnp.concatenate([rotate_halves(q[..., :r], qpos, theta), q[..., r:]],
                        -1)
    k = jnp.concatenate([rotate_halves(k[..., :r], pos, theta), k[..., r:]], -1)
    w = (uq @ p["index.w.weight"].T) * (nh ** -0.5 * d ** -0.5)
    kk = min(spec["index_topk"], t)
    kpos = jnp.arange(t)

    def scores(args):
        qb, wb = args
        s = jnp.einsum("qjd,sd->qjs", qb, k)
        return jnp.einsum("qjs,qj->qs", jax.nn.relu(s), wb)

    def rows(args):
        qb, wb, qp = args
        score = _blocks(scores, (qb, wb), INDEX_BLOCK)
        seen = kpos[None, :] <= qp[:, None]
        score = jnp.where(seen, score, -jnp.inf)
        val, idx = lax.top_k(score, kk)
        above = score > val[:, -1:]
        tie = (score == val[:, -1:]) & seen
        room = kk - above.sum(-1, keepdims=True)
        member = above | (tie & (jnp.cumsum(tie, -1) <= room))
        return (jnp.where(val > -jnp.inf, idx, t).astype(jnp.int32),
                jnp.packbits(member, axis=-1))

    return _blocks(rows, (q, w, jnp.asarray(qpos)), TOPK_BLOCK)


def attention(u, p: dict, spec: dict, kind: str, member=None, lo: int = 0,
              first: int = 0):
    """``u`` [T, hidden] (already normed; positions ``lo .. lo + T - 1``)
    -> the mixer's output for the queries at positions ``first ..``,
    ``[lo + T - first, hidden]``; not absorbed.  ``kind`` "full" reads the
    positions of ``member`` (``select``'s packed mask; ``lo`` 0), "window"
    the ``window`` keys up to the query."""
    g = lambda k: spec[f"{kind}.{k}"]  # noqa: E731
    nh, n, r, v, d_c = g("heads"), g("nope"), g("rope"), g("v"), g("latent")
    t = u.shape[0]
    pos, qpos = np.arange(lo, lo + t), np.arange(first, lo + t)
    uq = u[first - lo:]
    tq = uq.shape[0]
    c_q = _low_rank_q(uq, p, spec, kind)
    kv = u @ p["kv_a.weight"].T
    c_kv = _rms(kv[:, :d_c], p["kv_a_norm.weight"], spec["eps"]) * g("a_kv")
    k_r = rotate_pairs(kv[:, d_c:], pos, g("theta"))            # [T, r]
    kpos = jnp.asarray(pos)
    hg = min(HEAD_GROUP, nh)
    blk = min(Q_BLOCK, tq)
    span = min(blk + spec["window.window"], t) if kind == "window" else t

    def heads(y, w):
        """One group of ``hg`` heads added into ``y``; a scan, so that one
        group's keys and values are live at a time."""
        q_b, k_up, v_up, out, gate_w = w
        q = (c_q @ q_b.reshape(hg * (n + r), -1).T).reshape(tq, hg, n + r)
        q_r = rotate_pairs(q[..., n:], qpos, g("theta"))
        k_nope = jnp.einsum("tc,hdc->thd", c_kv, k_up)
        val = jnp.einsum("tc,hdc->thd", c_kv, v_up)

        def rows(args):
            qn, qr, qp, *rest = args               # [blk, hg, n], .., [blk]
            # the keys a block can see: all, or the band that holds its
            # queries' windows (a block's rows are consecutive positions)
            k0 = jnp.clip(qp[0] + blk - span - lo, 0, t - span)
            cut = lambda a: a if span == t else \
                lax.dynamic_slice_in_dim(a, k0, span)        # noqa: E731
            kn, kr, vv, kp = cut(k_nope), cut(k_r), cut(val), cut(kpos)
            s = (jnp.einsum("qhd,khd->hqk", qn, kn) +
                 jnp.einsum("qhd,kd->hqk", qr, kr)) * g("scale")
            keep = kp[None, :] <= qp[:, None]
            if kind == "window":
                keep &= kp[None, :] > qp[:, None] - spec["window.window"]
            else:
                keep &= jnp.unpackbits(rest[0], axis=-1, count=t).astype(bool)
            s = jnp.where(keep[None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vv)

        args = (q[..., :n], q_r, jnp.asarray(qpos)) + (
            (member,) if kind == "full" else ())
        gate = jax.nn.sigmoid(uq @ gate_w.T)                    # [Tq, hg]
        o = _blocks(rows, args, Q_BLOCK) * gate[:, :, None]
        return y + o.reshape(tq, hg * v) @ jnp.moveaxis(out, 0, 1).reshape(
            -1, hg * v).T, None

    grouped = lambda a: a.reshape((nh // hg, hg) + a.shape[1:])  # noqa: E731
    y, _ = lax.scan(heads, jnp.zeros_like(uq), (
        grouped(p["q_b.weight"].reshape(nh, n + r, -1)),
        grouped(p["k_up.weight"]), grouped(p["v_up.weight"]),
        grouped(jnp.moveaxis(p["out.weight"].reshape(-1, nh, v), 1, 0)),
        grouped(p["gate.weight"])))
    return y


def mlp(u, p: dict):
    """The dense gated MLP of a leading layer."""
    def rows(args):
        ub, = args
        return (jax.nn.silu(ub @ p["gate.weight"].T) *
                (ub @ p["up.weight"].T)) @ p["down.weight"].T
    return _blocks(rows, (u,), TOKEN_BLOCK)


def route(u, p: dict, spec: dict):
    """Combine weights ``[T, routed]`` over ALL routed experts (zero where
    an expert was not chosen)."""
    s = jax.nn.sigmoid(u @ p["router.weight"].T)
    _, idx = lax.top_k(s + p["router.bias"], spec["top_k"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * spec["route_scale"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(w)


def moe(u, p: dict, spec: dict, shared: bool = True, routed: bool = True):
    """``p`` keeps its expert stacks in the served dtype: they are upcast
    one expert at a time inside the scan."""
    small = _f32({k: v for k, v in p.items() if not k.startswith("experts.")})

    def rows(args):
        ub, = args
        out = jnp.zeros_like(ub)
        if routed:
            w = route(ub, small, spec)
            w = lax.dynamic_slice_in_dim(w, spec["offset"], spec["held"], 1)

            def one(acc, inp):
                w1, w3, w2, w_e = inp          # [H, F], [H, F], [F, H], [T]
                hid = jax.nn.silu(ub @ w1.astype(F32)) * (ub @ w3.astype(F32))
                return acc + w_e[:, None] * (hid @ w2.astype(F32)), None

            r, _ = lax.scan(one, out, (p["experts.w1"], p["experts.w3"],
                                       p["experts.w2"], w.T))
            out = out + r
        if shared:
            hid = jax.nn.silu(ub @ small["shared.gate.weight"].T) * \
                (ub @ small["shared.up.weight"].T)
            out = out + hid @ small["shared.down.weight"].T
        return out

    return _blocks(rows, (u,), TOKEN_BLOCK)


# -- the stack ----------------------------------------------------------------

def _fp8(v):
    """Through float8 (e4m3: 3 mantissa bits) and back, scaled per tensor
    so that its largest entry sits at the format's largest (448)."""
    s = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 448.0
    return (v / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _bf16(v):
    return v.astype(jnp.bfloat16).astype(F32)


_ROUND = {None: lambda v: v, "float8": _fp8, "bfloat16": _bf16}


def _sub(p: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _freeze(spec: dict):
    return tuple(sorted(spec.items()))


def _rounded(p: dict, lowp):
    """lowp: what a deployment in that precision rounds of the weights —
    every matrix."""
    if not lowp:
        return p
    rnd = _ROUND[lowp]
    return {k: rnd(v.astype(F32)).astype(v.dtype) if v.ndim >= 2 else v
            for k, v in p.items()}


@functools.partial(jax.jit, static_argnames=("spec_items", "lowp"))
def _normed(x, w, spec_items, lowp=None):
    with jax.default_matmul_precision("highest"):
        return _ROUND[lowp](_rms(x, w.astype(F32), dict(spec_items)["eps"]))


@functools.partial(jax.jit, static_argnames=("spec_items", "lowp", "first"))
def select_positions(u, p, spec_items, lowp=None, first: int = 0):
    """``select`` on a full layer's normed input ``u`` with the layer's
    tensors ``p`` (``attn.`` names), compiled; ``lowp`` rounds the
    matrices and the input (the overlap's second reading)."""
    p = _rounded(p, lowp)
    with jax.default_matmul_precision("highest"):
        return select(_ROUND[lowp](u), _f32(_sub(p, "attn.")),
                      dict(spec_items), first)


@functools.partial(jax.jit, static_argnames=("kind", "spec_items", "lowp",
                                             "lo", "first"))
def _mix(x, u, p, member, kind: str, spec_items, lowp=None, lo: int = 0,
         first: int = 0):
    """``x + mixer(u)`` for the positions ``first ..`` (``x`` and ``u``
    hold the positions ``lo ..``): lowp rounds the matrices and the
    mixer's output (its input came rounded)."""
    spec, rnd = dict(spec_items), _ROUND[lowp]
    p = _rounded(p, lowp)
    x, uq = x[first - lo:], u[first - lo:]
    with jax.default_matmul_precision("highest"):
        if kind in ("full", "window"):
            return x + rnd(attention(u, _f32(_sub(p, "attn.")), spec, kind,
                                     member, lo, first))
        if kind == "mlp":
            return x + rnd(mlp(uq, _f32(_sub(p, "mlp."))))
        return x + rnd(moe(uq, _sub(p, "moe."), spec))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, w, head, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms(x, w.astype(F32), eps) @ head.astype(F32).T


def layer_kinds(spec: dict):
    """The stack's mixers in tensor order: a published layer's attention
    kind, then "mlp" or "moe"."""
    return [k for l, kind in enumerate(spec["kinds"])
            for k in (kind, "mlp" if l < spec["dense"] else "moe")]


def first_needed(spec: dict, last: int, known=()):
    """For each mixer, the first position its OUTPUT has to hold so that
    the stack's output holds the positions ``last ..``: an FFN needs its
    own positions, a window layer the ``window - 1`` before them, a full
    layer (keys from everywhere) all — or, where the stream entering it
    is ``known`` up to some position (tensor index -> length: a
    document's, ``document_state``), the positions from there on; the
    list has one more entry, the stack's own output."""
    kinds = layer_kinds(spec)
    need = [last]
    for i, kind in reversed(list(enumerate(kinds))):
        out = need[0]
        need.insert(0, dict(known).get(i, 0) if kind == "full" else max(
            0, out - (spec["window.window"] - 1)) if kind == "window"
            else out)
    need = need[1:]
    # behind the last full layer every mixer computes the rows the first
    # of them needs, and no mixer in front fewer than the one behind it
    # (both full layers of a request on a known document): rows nobody
    # reads come out with cut windows, the rows that are read see whole
    # ones, and the layers of a kind share one compiled shape
    full = [i for i, k in enumerate(kinds) if k == "full"]
    if full:
        need[full[-1]:] = [need[full[-1]]] * (len(need) - full[-1])
    for i in range(len(need) - 2, -1, -1):
        need[i] = min(need[i], need[i + 1])
    return need


@jax.jit
def _embed(wte, ids):
    return wte[ids].astype(F32)


@functools.partial(jax.jit, static_argnames=("have",))
def _behind(known, x, have: int):
    """The stream at every position: a document's own pass in front of
    the positions ``have ..`` computed here."""
    return jnp.concatenate([known[:have], x])


@jax.jit
def _rows(x, at):
    return x[at]


_AHEAD = {}      # (call, statics, argument shapes) -> compiled (compile_ahead)


def _run(f, *args, **static):
    """``f(*args, **static)``, one of this file's compiled calls.  Given
    SHAPES (``jax.ShapeDtypeStruct``: ``compile_ahead``) it compiles the
    call for them, keeps the executable and returns the result's shapes;
    given arrays it runs the executable kept for their shapes, if any."""
    leaves = jax.tree_util.tree_leaves(args)
    key = (f.__name__, _freeze(static), jax.tree_util.tree_structure(args),
           tuple((a.shape, str(a.dtype)) for a in leaves))
    if any(isinstance(a, jax.ShapeDtypeStruct) for a in leaves):
        if key not in _AHEAD:
            _AHEAD[key] = f.lower(*args, **static).compile()
        return jax.eval_shape(functools.partial(f, **static), *args)
    return _AHEAD[key](*args) if key in _AHEAD else f(*args, **static)


def _stack(params: dict, ids, spec: dict, need, lowp=None, probe=None,
           known=None, until=None):
    """The layers' loop: the residual stream after mixer ``until - 1``
    (default: after the last), holding the positions ``have ..``; returns
    ``(x, have, entering)`` with ``entering`` = {tensor index: the stream
    entering that full layer, every position} for the full layers past
    the first."""
    items, kinds = _freeze(spec), layer_kinds(spec)
    x = _run(_embed, params["wte.weight"], ids)
    have, entering = 0, {}
    for i, kind in enumerate(kinds[:until]):
        p = _sub(params, f"h{i}.")
        if kind == "full" and have:
            # the positions in front come from the document's own pass
            x, have = _run(_behind, known[i], x, have=have), 0
        if kind == "full" and i:
            entering[i] = x
        u = _run(_normed, x, p["norm.weight"], spec_items=items, lowp=lowp)
        member = None
        if kind == "full":
            sel, member = _run(select_positions, u, p, spec_items=items,
                               lowp=lowp, first=need[i])
            if probe is not None:
                probe(i, u, sel, need[i])
        x = _run(_mix, x, u, p, member, kind=kind, spec_items=items,
                 lowp=lowp, lo=have, first=need[i])
        have = need[i]
    if until is not None and until < len(kinds) and kinds[until] == "full":
        entering[until] = x
    return x, have, entering


def document_state(params: dict, doc_ids, spec: dict, lowp=None) -> dict:
    """What the requests on ONE document share: the residual stream
    entering every full layer past the first, at the document's positions
    (causal: a suffix changes none of it).  ``logits(..., known=)`` then
    computes a request's own positions alone; the document's pass runs
    once a distinct document."""
    kinds = layer_kinds(spec)
    last_full = max(i for i, k in enumerate(kinds) if k == "full")
    return _stack(params, _ints(doc_ids), spec, [0] * len(kinds), lowp=lowp,
                  until=last_full)[2] if last_full else {}


def logits(params: dict, ids, spec: dict, positions=None, lowp=None,
           probe=None, tail=None, known=None):
    """float32 logits ``[len(positions) or T, vocab]`` of ONE sequence
    ``ids [T]``: a few jitted calls a half-layer, so only one's float32
    weights are live at a time.  ``tail`` (default: everything) says that
    only the last ``tail`` positions are read: every mixer then computes
    the positions those depend on and no others (``first_needed``: the
    same arithmetic on fewer rows); ``known`` (``document_state`` of the
    sequence's first tokens, computed at the same ``lowp``) spares the
    document's positions in the layers before a full layer too.
    ``probe(i, u, sel, first)`` is called at every full layer with its
    tensor index, its normed input ``[T, hidden]`` and this file's
    selection ``[T - first, k]`` for the queries ``first ..`` (the overlap
    check)."""
    ids, positions = _ints(ids), _ints(positions)
    need = first_needed(spec, ids.shape[0] - tail if tail else 0,
                        {i: v.shape[0] for i, v in (known or {}).items()})
    x, have, _ = _stack(params, ids, spec, need, lowp, probe, known)
    if positions is not None:
        x = _run(_rows, x, positions if isinstance(
            positions, jax.ShapeDtypeStruct) else positions - have)
    return _run(_head, x, params["ln_f.weight"], params["lm_head.weight"],
                eps=spec["eps"])


def _ints(v):
    return v if v is None or isinstance(v, jax.ShapeDtypeStruct) else \
        jnp.asarray(v, jnp.int32)


def compile_ahead(params: dict, spec: dict, doc_len: int, pad_to: int,
                  max_new: int, tail: int) -> int:
    """Compiles, and keeps for ``_run``, every call that ``document_state``
    of a ``doc_len``-token document and ``logits`` of a request on it
    (``pad_to`` ids, ``max_new`` positions read, ``tail``) will make:
    the same two functions walked over shapes, nothing computed.  Returns
    the number of executables kept."""
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    ints = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)    # noqa: E731
    params = {k: shape(v) for k, v in params.items()}
    known = document_state(params, ints(doc_len), spec)
    logits(params, ints(pad_to), spec, positions=ints(max_new), tail=tail,
           known=known)
    return len(_AHEAD)


def select_overlap(mine, theirs, sentinel: int) -> float:
    """The share of the positions ``mine [n, k]`` selects that ``theirs
    [n, k]`` selects for the same query; ``sentinel`` marks an empty
    place in either."""
    mine, theirs = np.asarray(mine), np.asarray(theirs)
    hit = total = 0
    for a, b in zip(mine, theirs):
        a = a[a != sentinel]
        hit += np.isin(a, b[b != sentinel]).sum()
        total += a.size
    return float(hit) / max(total, 1)


def _padded(seq, prompt_len: int, pad_to: int, max_new: int):
    n_new = len(seq) - prompt_len
    ids = list(seq[:-1]) + [0] * (pad_to - (len(seq) - 1))
    pos = [prompt_len - 1 + j for j in range(n_new)]
    return n_new, ids, pos + [pos[-1]] * (max_new - n_new)


def lowp_choice_gaps(params: dict, seq, prompt_len: int, spec: dict,
                     pad_to: int, max_new: int, lowp: str = "float8",
                     tail=None, known=None, known_lowp=None):
    """The second reading of the tolerance: at each generated position of
    ``seq``, the token the forward pass rounded to ``lowp`` (``float8``,
    or ``bfloat16``: what the served precision alone does to this file)
    would pick, scored against this file's float32 logits."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos, tail=tail,
                known=known)[:n_new]
    low = logits(params, ids, spec, positions=pos, lowp=lowp, tail=tail,
                 known=known_lowp)[:n_new]
    mine = jnp.take_along_axis(lg, low.argmax(-1)[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]


def greedy_logit_gaps(params: dict, seq, prompt_len: int, spec: dict,
                      pad_to: int, max_new: int, probe=None, tail=None,
                      known=None):
    """How far each generated token's logit lies below the reference's
    best token, teacher-forced on the system's own output: ``seq`` is
    prompt + generated tokens, right-padded to ``pad_to`` (every mixer is
    causal, so padding reaches no position read) and the positions read
    padded to ``max_new``, so every request shares one compiled shape;
    ``tail`` and ``known`` as in ``logits`` (the tail has to hold every
    generated position).
    Returns one gap per generated token."""
    n_new, ids, pos = _padded(seq, prompt_len, pad_to, max_new)
    lg = logits(params, ids, spec, positions=pos, probe=probe, tail=tail,
                known=known)[:n_new]
    picked = jnp.asarray(seq[prompt_len:], jnp.int32)
    mine = jnp.take_along_axis(lg, picked[:, None], -1)[:, 0]
    return [float(g) for g in (lg.max(-1) - mine)]
