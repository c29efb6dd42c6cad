"""The unified serving executable: one jit for ragged prefill + decode.

v1 (PR 2) compiled a GRID of programs — one bucketed prefill executable
per power-of-two prompt length, one decode executable per power-of-two
batch size — and ran every admitted request's prefill as its own call.
That bounded compiles logarithmically but still paid
O(prefill buckets x batch buckets) compiles and serialized prefills:
every admitted request's prompt ran as a call of its own, ahead of
the decode batch.

``build_unified_step_fn`` replaces the whole grid with ONE executable
over a fixed-shape **ragged token batch** (DESIGN.md §12):

- the token axis ``[T]`` = ``max_seqs`` single-token slots (decode — the
  degenerate 1-query-token case) followed by ``prefill_rows`` chunk
  slots of ``chunk_size`` tokens each (Sarathi-style prefill chunks);
- raggedness is described per row by ``(q_lens, cu_q, page_tables,
  ctx_lens)`` — the same scalar arrays the
  :mod:`~hetu_tpu.ops.ragged_paged_attention` kernel prefetches;
- every layer runs the projections/MLP over the WHOLE token axis (one
  MXU-shaped matmul for mixed prefill+decode, the core RPA win),
  writes the LIVE tokens' k/v into their pages at ``(token_page,
  token_off)`` — on TPU as page-runs, one Pallas call a layer for K and
  V (:mod:`~hetu_tpu.ops.paged_kv_write`; padding slots and idle rows
  write nothing), off TPU as the plain scatter over every slot (padding
  lands in the trash page) — and attends
  raggedly, one call per REGION of the layout (decode slots, chunk
  slots, verify slots): the Pallas kernel on TPU, its query window the
  region's own width, or — off TPU — a split dense reference whose
  decode half IS ``paged_attention_reference`` (the bit-for-bit-proven
  v1 decode math) and whose chunk half is the same gather+masked-dense
  attention with a causal in-row mask;
- sampling is ON DEVICE for every mode: greedy argmax (bit-for-bit the
  ``jnp.argmax`` solo ``generate()`` runs), or temperature / top-k /
  top-p (nucleus) from a per-row params vector, keyed by
  ``fold_in(PRNGKey(seed), ctx_len)`` so a request's sample at token
  position ``n`` is identical regardless of batching, chunking or
  preemption.  The engine fetches ``[rows]`` int32 — never a ``[B, V]``
  logits matrix (``host_logit_fetches`` stays 0 on mixed traffic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..models.generate import (_act, _lm_head, _moe_mlp, _norm_apply,
                               _Params, _rotary_tables)
from ..models.gpt import STATE_MIXERS, GPTConfig
from ..obs.phases import phase
from ..ops.paged_attention import gather_pages, paged_attention_reference
from ..ops.paged_kv_write import (kv_write_plan, paged_kv_write,
                                  paged_kv_write_reference, write_tile)
from ..ops.quantization import quantize_rows
from ..ops.ssd import live_slot_list
from .kv_pool import window_table_pages
from ..ops.ragged_paged_attention import (_dequant_latent,
                                          latent_paged_attention_reference,
                                          latent_ragged_paged_attention_pallas,
                                          ragged_paged_attention_pallas,
                                          ragged_paged_attention_reference,
                                          sample_row, sample_rows,
                                          speculative_verify_head)

def _params_view(cfg: GPTConfig, params) -> _Params:
    p = _Params.__new__(_Params)
    p.s, p.cfg = params, cfg
    return p


def _rope_tok(x, cos_g, sin_g):
    """Rotary embedding at per-token positions: x [T, h, d], cos_g/sin_g
    [T, d] (already position-gathered).  Same arithmetic as
    ``generate._rope`` — the flat token axis just has a DIFFERENT
    position per row, so the table gather happens outside."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    c = cos_g[:, None, :].astype(x.dtype)
    s = sin_g[:, None, :].astype(x.dtype)
    return x * c + rot * s


def _chunk_slots(max_seqs: int, prefill_rows: int, chunk: int,
                 spec_k: int, block: int = 0):
    """The multi-token slot layout shared by the region map, the
    split-attention fallback and the engine's ``cu_q``: a list of
    ``(row_index, token_start, width)``.  Plain prefill chunk slots
    come first; in spec mode (``spec_k > 0``) every decode-capable
    request additionally owns a DEDICATED verify slot of width
    ``spec_k + 1`` — a verify row is structurally a prefill chunk, but
    giving it its own narrow slot means verifying k drafts prices
    ``k + 1`` tokens of compute, not a whole ``chunk``-wide slot, and
    verify traffic never competes with prompt prefills for slots.  A
    model that generates by diffusion over blocks (``block`` positions,
    ``GPTConfig.diffusion_block``) owns the same ``max_seqs`` narrow slots
    at width ``2 * block``: a generating row's step is its open block in
    the slot's first half, or (a FUSED row) the block it commits and,
    behind it, the next one's first denoise pass."""
    slots = [(max_seqs + r, max_seqs + r * chunk, chunk)
             for r in range(prefill_rows)]
    if spec_k or block:
        base = max_seqs + prefill_rows * chunk
        vk = 2 * block or spec_k + 1
        slots += [(max_seqs + prefill_rows + j, base + j * vk, vk)
                  for j in range(max_seqs)]
    return slots


def _split_ragged_attention(cfg: GPTConfig, q, kp, vp, q_lens,
                            page_tables, ctx_lens, max_seqs: int,
                            prefill_rows: int, chunk: int,
                            spec_k: int = 0):
    """Off-TPU ragged attention over the structured serving layout.

    The flat batch's FIRST ``max_seqs`` tokens are the single-token
    decode slots: they run through :func:`paged_attention_reference` —
    literally the v1 decode math, so temperature-0 decode stays
    bit-for-bit with solo ``generate()``.  Each multi-token slot
    (prefill chunk or — spec mode — verify row) then runs
    gather+masked-dense attention over its own page table with the
    causal in-row mask (query j at absolute position
    ``ctx - q_len + j``).  Padding decode slots attend one trash-page
    slot (``max(ctx, 1)``) and padding chunk rows attend trash pages —
    finite junk, never NaN, discarded by the engine."""
    c = cfg
    hd, nh, kvh = c.head_dim, c.num_heads, c.kv_heads
    g = nh // kvh
    maxp = page_tables.shape[1]
    ps = kp.shape[2]
    scale = hd ** -0.5
    # decode slots: [S] one-token rows (v1 math, bitwise-proven)
    outs = [paged_attention_reference(
        q[:max_seqs], kp, vp, page_tables[:max_seqs],
        jnp.maximum(ctx_lens[:max_seqs], 1))]
    # power-of-two page-window levels: a chunk whose context spans n
    # pages attends only the first level >= n pages of its table.  The
    # dropped tail slots are exactly the ones the causal mask would zero
    # (trailing exact-zero softmax terms — removing them is the same
    # width-invariance the decode path already relies on, so chunk
    # numerics stay bit-for-bit with the full-width form).  Level 0 is
    # the idle slot: decode-only steps skip the chunk region entirely —
    # the CPU analogue of the Pallas kernel's pl.when page skipping.
    levels = [0]
    n = 1
    while n < maxp:
        levels.append(n)
        n *= 2
    levels.append(maxp)
    levels_arr = jnp.asarray(levels, jnp.int32)

    def make_chunk_attn(npages, width_q):
        if npages == 0:
            return lambda qc, pt_row, ctx, qlen: jnp.zeros(
                (width_q, nh, hd), q.dtype)

        # near-twin of ops.ragged_paged_attention_reference's per-row
        # body, but NOT shared on purpose: this path masks with -inf
        # (exact-zero softmax terms — the bit-for-bit-vs-solo contract),
        # while the ops reference mirrors the kernel's finite
        # DEFAULT_MASK_VALUE for interpret-mode parity
        def attn(qc, pt_row, ctx, qlen):
            width = npages * ps
            qg = qc.reshape(width_q, kvh, g, hd).astype(jnp.float32)
            k = gather_pages(kp, pt_row[:npages])      # [width, kvh, hd]
            v = gather_pages(vp, pt_row[:npages])
            s = jnp.einsum("qhgd,khd->qhgk", qg,
                           k.astype(jnp.float32)) * scale
            qpos = (ctx - qlen) + jnp.arange(width_q)
            valid = jnp.arange(width)[None, :] <= qpos[:, None]
            s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("qhgk,khd->qhgd", pr, v.astype(jnp.float32))
            return o.reshape(width_q, nh, hd).astype(q.dtype)

        return attn

    branch_sets = {}                     # per slot width
    for row, start, width_q in _chunk_slots(max_seqs, prefill_rows,
                                            chunk, spec_k):
        if width_q not in branch_sets:
            branch_sets[width_q] = [make_chunk_attn(npages, width_q)
                                    for npages in levels]
        qc = q[start: start + width_q]
        need = -(-ctx_lens[row] // ps)              # pages ctx spans
        lvl = jnp.searchsorted(levels_arr, need)
        lvl = jnp.where(q_lens[row] > 0, lvl, 0)    # idle -> level 0
        outs.append(lax.switch(lvl, branch_sets[width_q], qc,
                               page_tables[row], ctx_lens[row],
                               q_lens[row]))
    return jnp.concatenate(outs, axis=0)


def _split_latent_ragged_attention(cfg: GPTConfig, q_cat, cp, rp, q_lens,
                                   page_tables, ctx_lens, max_seqs: int,
                                   prefill_rows: int, chunk: int,
                                   spec_k: int = 0, scale_pages=None,
                                   quant=None, scale=None, dims=None):
    """Latent (MLA) twin of :func:`_split_ragged_attention`: absorbed
    ``q_cat [T, nh, d_c+d_r]`` against the single latent stream ``cp``
    (+ optional rope stream ``rp`` / absmax sidecar ``scale_pages``),
    returning the LATENT attention output ``[T, nh, d_c]`` fp32 — the
    caller applies the ``v_up`` fold.  Decode slots run
    :func:`latent_paged_attention_reference` and chunk/verify slots run
    the same pow2 page-window ``lax.switch`` with ``-inf`` masking, so
    temp-0 latent serving stays bit-for-bit with the solo MLA oracle
    (``models.generate._mla_attn_step``).  ``scale`` is the softmax
    scale where the model states one (default: the converted model's
    ``(head_dim + d_r) ** -0.5``), ``dims`` the pool's ``(d_c, d_r)``
    where they are not the config's two streams."""
    c = cfg
    hd, nh = c.head_dim, c.num_heads
    d_c, d_r = dims or (c.kv_latent_dim, c.rope_dim)
    maxp = page_tables.shape[1]
    ps = cp.shape[2]
    if scale is None:
        scale = (hd + d_r) ** -0.5
    outs = [latent_paged_attention_reference(
        q_cat[:max_seqs], cp, rp, page_tables[:max_seqs],
        jnp.maximum(ctx_lens[:max_seqs], 1), softmax_scale=scale,
        scale_pages=scale_pages, quant=quant, latent_dim=d_c)]
    levels = [0]
    n = 1
    while n < maxp:
        levels.append(n)
        n *= 2
    levels.append(maxp)
    levels_arr = jnp.asarray(levels, jnp.int32)

    def make_chunk_attn(npages, width_q):
        if npages == 0:
            return lambda qc, pt_row, ctx, qlen: jnp.zeros(
                (width_q, nh, d_c), jnp.float32)

        def attn(qc, pt_row, ctx, qlen):
            width = npages * ps
            qf = qc.astype(jnp.float32)
            cw = cp[pt_row[:npages]].reshape(width, cp.shape[-1])
            sw = None if scale_pages is None else \
                scale_pages[pt_row[:npages]].reshape(width, 1)
            cd = _dequant_latent(cw, sw, quant, d_c)   # [width, d_c]
            if d_r:
                r = rp[pt_row[:npages]].reshape(width, d_r)
                k = jnp.concatenate([cd, r.astype(jnp.float32)], -1)
            else:
                k = cd
            s = jnp.einsum("qhc,kc->qhk", qf, k) * scale
            qpos = (ctx - qlen) + jnp.arange(width_q)
            valid = jnp.arange(width)[None, :] <= qpos[:, None]
            s = jnp.where(valid[:, None, :], s, -jnp.inf)
            pr = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("qhk,kc->qhc", pr, cd)

        return attn

    branch_sets = {}
    for row, start, width_q in _chunk_slots(max_seqs, prefill_rows,
                                            chunk, spec_k):
        if width_q not in branch_sets:
            branch_sets[width_q] = [make_chunk_attn(npages, width_q)
                                    for npages in levels]
        qc = q_cat[start: start + width_q]
        need = -(-ctx_lens[row] // ps)
        lvl = jnp.searchsorted(levels_arr, need)
        lvl = jnp.where(q_lens[row] > 0, lvl, 0)
        outs.append(lax.switch(lvl, branch_sets[width_q], qc,
                               page_tables[row], ctx_lens[row],
                               q_lens[row]))
    return jnp.concatenate(outs, axis=0)


def _regions(max_seqs: int, prefill_rows: int, chunk: int, spec_k: int,
             block: int = 0):
    """The static token axis as regions of equal-width rows: a list of
    ``(tag, first_row, first_token, rows, width)`` — the decode slots,
    the chunk slots and the narrow slots behind them: (spec mode) the
    verify slots or (a block-wise model) the block slots.  Attention and
    the KV write are both issued per region; nothing selects one at run
    time."""
    slots = _chunk_slots(max_seqs, prefill_rows, chunk, spec_k, block)
    regions = [("decode", 0, 0, max_seqs, 1)]
    for tag, part in (("chunk", slots[:prefill_rows]),
                      ("block" if block else "verify",
                       slots[prefill_rows:])):
        if part:
            row, tok, width = part[0]
            regions.append((tag, row, tok, len(part), width))
    return regions


class StepLayout:
    """The serving step's control data as ONE int32 buffer each way.

    In: every small host array of a step, end to end in one ``[size]``
    int32 buffer, so the engine makes one host-to-device transfer a step
    and the step cuts the buffer at static offsets (``unpack``).  The
    fields, in order (``t = n_tokens``, ``r = n_rows``)::

        tokens [t]  token_pos [t]  token_page [t]  token_off [t]
        q_lens [r]  page_tables [r, max_pages]  ctx_lens [r]
        temps [r] f32  top_ps [r] f32  top_ks [r]  seeds [r]
        spec_lens [r]      (a speculative build, ``spec_k > 0``)
        next_tok [r]       (a self-drafting build: the token that follows
                           the row's last fed one where the host knows it —
                           a prompt's chunk — and -1 where the step itself
                           samples it)
        unmask_k [r]  unmask_tau [r] f32
                           (a block-wise model, ``cfg.diffusion_block``: how
                           many of a block row's masked positions this pass
                           unmasks by rank — of confidence, or, negative, of
                           position; 0 on a plain commit pass, the next
                           block's first pass's on a fused row (``q_lens``
                           2B) — and the confidence
                           above which a masked position is unmasked
                           whatever its rank, 2.0 where the rule is static:
                           ``serving/request.py::DenoiseRule.unmask``)
        state_slots [r]    (a hybrid stack)
        win_tables [r, window pages]  win_base [r]  win_token_page [t]
                           (a stack with window layers: a row's pages in
                           the window space, the position its table's
                           first slot holds, and the write plan there;
                           ``token_off`` serves both spaces)

    ``temps`` and ``top_ps`` ride as their float32 bit patterns
    (``.view(np.float32)`` on the host, ``lax.bitcast_convert_type`` in
    the step).  ``cu_q [r + 1]`` (each row's first token) follows from
    the regions alone and is a constant of the build, not a field.

    Out: the step's host-bound results as one int32 vector
    (``join`` in the step, ``split`` on the host): ``next_tokens [r]``,
    then ``moe_load [expert layers, held experts]`` flattened (a hybrid
    stack; a self-drafting build adds ``mtp_load``, its MTP module's),
    then ``accepted [r]`` (a speculative build), then ``draft [r]`` (a
    self-drafting build: the token its MTP module proposes behind the
    row's last committed one); a block-wise model adds, a block slot and
    of its OPEN block (a fused row's second half),
    ``block_tokens [max_seqs, B]`` (the pass's choice at a masked
    position, the fed token elsewhere), ``block_flags [max_seqs]`` (bit
    ``j``: position ``j`` was unmasked by this pass) and ``block_conf
    [max_seqs, B]`` (the choices' confidences, float32 by bit pattern)."""

    F32 = ("temps", "top_ps", "unmask_tau")

    def __init__(self, cfg: GPTConfig, max_seqs: int, chunk: int,
                 prefill_rows: int, max_pages: int, spec_k: int = 0,
                 page_size: int = 0):
        block = cfg.diffusion_block
        regions = _regions(max_seqs, prefill_rows, chunk, spec_k, block)
        _, row, tok, n, width = regions[-1]
        self.n_rows, self.n_tokens = row + n, tok + n * width
        self.cu_q = np.concatenate(
            [tok + width * np.arange(n) for _, _, tok, n, width in regions]
            + [[self.n_tokens]]).astype(np.int32)
        t, r = self.n_tokens, self.n_rows
        shapes = {"tokens": (t,), "token_pos": (t,), "token_page": (t,),
                  "token_off": (t,), "q_lens": (r,),
                  "page_tables": (r, max_pages), "ctx_lens": (r,),
                  "temps": (r,), "top_ps": (r,), "top_ks": (r,),
                  "seeds": (r,)}
        self_draft = bool(spec_k and cfg.is_hybrid)
        if spec_k:
            shapes["spec_lens"] = (r,)
        if self_draft:
            shapes["next_tok"] = (r,)
        if block:
            shapes.update(unmask_k=(r,), unmask_tau=(r,))
        if cfg.is_hybrid:
            shapes["state_slots"] = (r,)
        if cfg.is_hybrid and cfg.window_tokens:
            wp = window_table_pages(cfg.window_tokens, chunk, page_size)
            shapes.update(win_tables=(r, wp), win_base=(r,),
                          win_token_page=(t,))
        self.fields, self.size = self._offsets(shapes)
        outs = {"next_tokens": (r,)}
        if cfg.is_hybrid:
            outs["moe_load"] = (len(cfg.layers_of("moe")),
                                max(cfg.held_experts, 1))
        if self_draft:
            outs["mtp_load"] = (cfg.mtp_pattern.count("moe"),
                                max(cfg.held_experts, 1))
        if spec_k:
            outs["accepted"] = (r,)
        if self_draft:
            outs["draft"] = (r,)
        if block:
            outs.update(block_tokens=(max_seqs, block),
                        block_flags=(max_seqs,),
                        block_conf=(max_seqs, block))
        self.outs, self.out_size = self._offsets(outs)

    @staticmethod
    def _offsets(shapes):
        """name -> (offset, shape) in dict order, and the total size."""
        table, off = {}, 0
        for name, shape in shapes.items():
            table[name] = (off, shape)
            off += int(np.prod(shape))
        return table, off

    @staticmethod
    def _cut(table, vec, as_f32):
        out = {}
        for name, (off, shape) in table.items():
            a = vec[off: off + int(np.prod(shape))].reshape(shape)
            out[name] = as_f32(a) if name in StepLayout.F32 else a
        return out

    def views(self, buf):
        """The fields of a host buffer as NumPy views on it (writes
        land in ``buf``)."""
        return self._cut(self.fields, buf, lambda a: a.view(np.float32))

    def unpack(self, packed):
        """The fields of the device buffer, inside the step: static
        slices, which XLA fuses into their consumers."""
        return self._cut(
            self.fields, packed,
            lambda a: lax.bitcast_convert_type(a, jnp.float32))

    def join(self, **outs):
        """The step's host-bound results as the one output vector."""
        for name, (_, shape) in self.outs.items():
            if outs[name].shape != shape:
                raise ValueError(f"{name} is {outs[name].shape}, the "
                                 f"layout holds {shape}")
        return jnp.concatenate(
            [outs[name].astype(jnp.int32).reshape(-1) for name in self.outs])

    def split(self, vec):
        """The fetched output vector as its named arrays (host)."""
        return self._cut(self.outs, vec, None)

    def abstract(self):
        """The packed input's shape, for lowering without running."""
        return jax.ShapeDtypeStruct((self.size,), jnp.int32)


def _attend_by_region(kernel, name: str, q, q_lens, cu_q, page_tables,
                      ctx_lens, max_seqs: int, prefill_rows: int,
                      chunk: int, spec_k: int = 0, kv_base=None,
                      block: int = 0):
    """On-TPU ragged attention over the structured serving layout: one
    ``kernel`` call per REGION of the static token axis, so a grid step
    computes a query window of the region's own width — one token for
    the ``max_seqs`` decode slots, ``chunk`` for the prefill chunk
    slots, ``spec_k + 1`` for the verify slots — and not the widest
    region's for every row.  ``kernel`` is a ragged Pallas op with its
    pages bound (``functools.partial``); each call gets, by keyword, its
    slice of the token and row axes with ``cu_q`` rebased to the slice,
    and the outputs are concatenated on the token axis.  On the device
    trace the calls are ``<name>_decode`` / ``_chunk`` / ``_verify`` /
    ``_block`` (a block-wise model's generating rows, two blocks wide).
    ``kv_base [rows]`` (a window layer's ``kernel``: the position each
    row's table starts at) is sliced like the other per-row arrays."""
    outs = []
    for tag, row, tok, n, width in _regions(max_seqs, prefill_rows, chunk,
                                            spec_k, block):
        rows, toks = slice(row, row + n), slice(tok, tok + n * width)
        based = {} if kv_base is None else {"kv_base": kv_base[rows]}
        outs.append(kernel(
            q=q[toks], q_lens=q_lens[rows],
            cu_q=cu_q[row: row + n + 1] - tok,
            page_tables=page_tables[rows], ctx_lens=ctx_lens[rows],
            max_q=width, name=f"{name}_{tag}", **based))
    return jnp.concatenate(outs, axis=0)


def _verify_rows(p, x, tokens, cu_q, spec_lens, sampling, ctx_lens,
                 next_tokens, v0: int, spec_k: int):
    """The verify head over the dedicated verify slots (rows ``v0 ..``;
    decode slots and prefill chunks never stage drafts).  Verify position
    j of a row starting at cu sits at token cu + j and its logits verify
    the draft fed at cu + j + 1 (all K windows are computed — fixed
    shapes — and masked by spec_lens; a spec_len of 0 yields accepted ==
    0 and the per-row sample stands, which is exactly the non-spec path,
    bit-for-bit).  ``x`` is the normed hidden state ``[T, H]``,
    ``sampling`` the rows' ``(temps, top_ps, top_ks, seeds)``.  Returns
    ``(next_tokens [rows], accepted [rows], draft_next [R, K])``: a verify
    row's next token is its bonus — the first-rejection alternative, or,
    on full acceptance, the last-position per-row sample (whose sampling
    index ctx_lens[r] is exactly the emitted token's index)."""
    t_tokens, n_rows = tokens.shape[0], next_tokens.shape[0]
    starts = cu_q[v0:n_rows]                 # [R = verify rows]
    widx = jnp.clip(starts[:, None] + jnp.arange(spec_k)[None, :],
                    0, t_tokens - 1)                   # [R, K]
    with phase("lm_head_ce"):
        vlogits = _lm_head(p, x[widx.reshape(-1)]).reshape(
            n_rows - v0, spec_k, -1)
    draft_next = tokens[jnp.clip(widx + 1, 0, t_tokens - 1)]
    spec_v = spec_lens[v0:]
    with phase("sample"):
        acc_v, alt_v = speculative_verify_head(
            vlogits, draft_next, spec_v, *(a[v0:] for a in sampling),
            ctx_lens[v0:])
    bonus_alt = jnp.take_along_axis(
        alt_v, jnp.minimum(acc_v, spec_k - 1)[:, None], axis=1)[:, 0]
    verify_next = jnp.where(acc_v < spec_v, bonus_alt, next_tokens[v0:])
    return (jnp.concatenate([next_tokens[:v0], verify_next]),
            jnp.concatenate([jnp.zeros(v0, jnp.int32), acc_v]), draft_next)


# the on-device per-row sampler lives next to the verify head in
# ops/ragged_paged_attention.py (ONE implementation: the speculative
# accept rule is "the draft matches this sampler's keyed choice", which
# is only sound if verify and non-verify rows draw identically); the
# old name stays importable here
_sample_row = sample_row


def build_unified_step_fn(cfg: GPTConfig, max_seqs: int, chunk: int,
                          prefill_rows: int, max_pages: int,
                          page_size: int, use_kernel: bool = False,
                          spec_k: int = 0, page_quant=None):
    """Compile THE serving executable: one ragged prefill+decode step.

    Token-axis layout (static)::

        [0 .. max_seqs)                    decode slots, 1 token each
        [max_seqs .. max_seqs + R*chunk)   R = prefill_rows chunk slots

    fn(params, packed [layout.size] i32, k_pages, v_pages)
      -> (out [layout.out_size] i32, new k_pages, new v_pages)

    ``packed`` is the step's control data in ONE buffer, the engine's one
    host-to-device transfer a step, cut here at the static offsets of
    :class:`StepLayout` (the only home of the format):
    the flat token arrays ``tokens`` / ``token_pos`` and the KV write
    plan ``token_page`` / ``token_off`` (where each token's k/v goes;
    trash page, offset 0 for padding), all ``[T]``; the ragged
    descriptors ``q_lens [rows]``, ``page_tables [rows, max_pages]``,
    ``ctx_lens [rows]``; the sampling parameters ``temps``, ``top_ps``
    (float32, carried by bit pattern), ``top_ks``, ``seeds``, all
    ``[rows]``.  ``cu_q [rows + 1]`` follows from the layout alone and
    is a constant of the build.  ``out`` is the one array the engine
    fetches: ``next_tokens [rows]``, here its whole content.

    ``rows = max_seqs + prefill_rows`` and ``T = max_seqs +
    prefill_rows * chunk``.  Every row gets a next-token sample at its
    LAST query token; the engine commits it only when the row reached
    the end of its accumulated sequence (``pos + q_len == len(tokens)``
    — i.e. the final prefill chunk or a decode step).  ALL shapes are
    fixed: the engine compiles this exactly once.

    Attention is issued per region of that layout, not once over all
    rows (:func:`_attend_by_region` with the kernel,
    ``_split_ragged_attention`` without): a decode slot's grid steps
    compute a one-token query tile, a chunk slot's a ``chunk``-token
    one.  The regions are the layout itself — nothing selects them.
    The KV write follows the same regions: with the kernel, a row's
    tokens (consecutive positions of one sequence) are written as the
    page-runs they fall into, cut into the pool's packed tiles
    (``kv_write_plan`` once a step, ``paged_kv_write`` once a layer),
    and a slot that holds no token writes nothing; without it, the
    plain scatter writes every slot and padding lands in the trash page.

    ``spec_k > 0`` (speculative serving, DESIGN.md §20) grows BOTH the
    layout and the two buffers.  The token axis gains ``max_seqs``
    dedicated VERIFY slots of ``spec_k + 1`` tokens each (after the
    prefill chunk slots), so every decode-capable request can verify a
    draft burst every step — structurally a prefill chunk, but priced
    at ``k + 1`` tokens of compute instead of a ``chunk``-wide slot,
    and never competing with prompt prefills for chunk slots.  An
    extra ``spec_lens [rows] i32`` field of ``packed`` marks live
    verify rows (feeding the last committed token plus the drafts),
    and ``out`` gains ``accepted [rows] i32`` after the tokens — the
    longest-accepted-prefix length from the on-device verify head
    (:func:`~hetu_tpu.ops.ragged_paged_attention.speculative_verify_head`).
    For rows with ``spec_len == 0`` (every decode slot, every plain
    prefill chunk, every idle verify slot) ``accepted`` is 0 and
    ``next_tokens`` is computed by the IDENTICAL per-row sampler as
    the non-speculative build — mixed spec/non-spec traffic shares the
    one executable.
    """
    if prefill_rows < 1:
        raise ValueError(f"prefill_rows must be >= 1, got {prefill_rows}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if spec_k < 0:
        raise ValueError(f"spec_k must be >= 0, got {spec_k}")
    c = cfg
    if c.is_hybrid:
        # one mixer per layer, recurrent state beside the pages: its own
        # layer loop and signature (below), the same layout and regions
        if page_quant is not None:
            raise ValueError(
                "a pattern stack (layer_pattern) is built without page "
                "quantization: its page layouts have no quantized form")
        if spec_k and (spec_k > 1 or not c.mtp_pattern or set(
                c.layer_pattern) & {*STATE_MIXERS, "mla", "dsa", "swa"}):
            raise ValueError(
                "speculative verify rows on a pattern stack are fed by its "
                "own MTP module, one draft a row (spec_k 1, "
                "cfg.mtp_pattern), over plain K/V attention layers: a "
                "rejected draft cannot be rolled out of recurrent "
                f"({' / '.join(STATE_MIXERS)}) state, and the latent layers' "
                "by-region calls have no verify region")
        _refuse_unbuilt_block(c, chunk, page_size, spec_k)
        return _build_hybrid_step_fn(c, max_seqs, chunk, prefill_rows,
                                     max_pages, page_size, use_kernel,
                                     spec_k)
    if page_quant is not None and (not c.is_mla or c.rope_dim):
        raise ValueError("page_quant requires the latent (MLA) layout "
                         "with rope_dim == 0")
    layout = StepLayout(c, max_seqs, chunk, prefill_rows, max_pages, spec_k)
    t_tokens, n_rows = layout.n_tokens, layout.n_rows
    max_len = max_pages * page_size
    cdt = jnp.bfloat16 if c.dtype == "bfloat16" else jnp.float32
    cos, sin = (_rotary_tables(c, max_len) if c.position == "rotary"
                else (None, None))
    hd, nh, nkv = c.head_dim, c.num_heads, c.kv_heads
    write_regions = tuple(
        (row, n, width) for _, row, _, n, width
        in _regions(max_seqs, prefill_rows, chunk, spec_k))

    def region_map(f, h, q_lens, f_chunk=None):
        """Apply a row-wise map ``f`` per region: unconditionally over
        the decode slots, under ``lax.cond`` per chunk slot — an idle
        chunk slot (no prompt in flight) contributes zeros without
        paying its ``[chunk, ...]`` matmul.  Row-wise means per-token
        results are unchanged by the split (bit-for-bit).  ``f_chunk``
        overrides ``f`` for the chunk slots (MoE keeps v1's per-phase
        expert paths: dense per-token mix for decode, dispatched
        group-GEMM for prefill chunks).  The spec-mode VERIFY region
        (``max_seqs`` rows of ``spec_k + 1`` tokens) runs
        unconditionally like the decode slots: the whole region is a
        few dozen tokens, cheaper than the per-slot conditional thunks
        would be, and idle verify tokens are trash-page padding the
        engine discards."""
        fc = f_chunk or f
        parts = [f(h[:max_seqs])]
        for row, start, width in _chunk_slots(max_seqs, prefill_rows,
                                              chunk, 0)[:prefill_rows]:
            sl = h[start: start + width]
            zero = jax.eval_shape(fc, sl)
            parts.append(lax.cond(
                q_lens[row] > 0, fc,
                lambda s, z=zero: jnp.zeros(z.shape, z.dtype), sl))
        if spec_k:
            parts.append(f(h[max_seqs + prefill_rows * chunk:]))
        return jnp.concatenate(parts, axis=0)

    # pages are donated: the pool replaces them wholesale every call, so
    # the KV write updates them in place
    @functools.partial(jax.jit, donate_argnums=(2, 3))
    def run(params, packed, k_pages, v_pages):
        (tokens, token_pos, token_page, token_off, q_lens, page_tables,
         ctx_lens, temps, top_ps, top_ks, seeds,
         *spec_lens) = layout.unpack(packed).values()
        spec_lens = spec_lens[0] if spec_k else None
        cu_q = jnp.asarray(layout.cu_q)
        p = _params_view(c, params)
        # model phases (obs/phases.py): names on the HLO op_name
        # metadata only, the compiled program is what it was
        with phase("embed"):
            x = p("wte.weight")[tokens].astype(cdt)        # [T, H]
            if c.position == "learned":
                x = x + p("wpe")[token_pos].astype(x.dtype)
        if use_kernel:
            # the write's page-run pieces, once for every layer
            tile = write_tile((k_pages[0], v_pages[0]))
            with phase("kv_scatter"):
                plan = kv_write_plan(token_page, token_off, q_lens, cu_q,
                                     regions=write_regions,
                                     page_size=page_size, tile=tile)

        def write_kv(pools, news):
            """This step's new rows into a layer's pools: the live
            tokens' page-runs with the kernel, every slot of the token
            axis (padding to the trash page) with the reference."""
            with phase("kv_scatter"):
                if use_kernel:
                    return paged_kv_write(pools, news, plan, tile=tile)
                return paged_kv_write_reference(pools, news, token_page,
                                                token_off)

        new_k, new_v = [], []
        for i in range(c.num_layers):
            with phase("norm"):
                h = _norm_apply(c, p.layer(i, "ln_1.weight"),
                                p.layer(i, "ln_1.bias"), x)

            if c.is_mla:
                d_c, d_r = c.kv_latent_dim, c.rope_dim

                def q_proj(hh, i=i):
                    out = hh @ p.layer(i, "attn.q.weight").T
                    qb = p.layer(i, "attn.q.bias")
                    return out + qb if qb is not None else out

                def kv_proj(hh, i=i):
                    out = hh @ p.layer(i, "attn.kv_a.weight").T
                    kb = p.layer(i, "attn.kv_a.bias")
                    return out + kb if kb is not None else out

                with phase("attn_proj"):
                    qh = region_map(q_proj, h, q_lens).reshape(
                        t_tokens, nh, hd + d_r)
                    kv = region_map(kv_proj, h, q_lens)  # [T, d_c + d_r]
                with phase("attn_core"):
                    c_kv = kv[..., :d_c]
                    k_up = p.layer(i, "attn.k_up.weight")  # [nh, hd, d_c]
                    v_up = p.layer(i, "attn.v_up.weight")
                    # FlashMLA-ETAP absorption: fold W_UK into q so scores
                    # are MQA dot products against the latent stream
                    q_abs = jnp.einsum("thd,hdc->thc",
                                       qh[..., :hd].astype(jnp.float32),
                                       k_up.astype(jnp.float32))
                    if d_r:
                        q_rope = _rope_tok(qh[..., hd:], cos[token_pos],
                                           sin[token_pos])
                        k_rope = _rope_tok(kv[..., d_c:][:, None, :],
                                           cos[token_pos],
                                           sin[token_pos])[:, 0]
                        q_cat = jnp.concatenate(
                            [q_abs, q_rope.astype(jnp.float32)], -1)
                    else:
                        q_cat = q_abs
                if page_quant:
                    kp, vp = write_kv(
                        (k_pages[i], v_pages[i]),
                        [x[:, None] for x in quantize_rows(c_kv,
                                                           page_quant)])
                elif d_r:
                    kp, vp = write_kv(
                        (k_pages[i], v_pages[i]),
                        (c_kv.astype(cdt)[:, None],
                         k_rope.astype(cdt)[:, None]))
                else:
                    kp, = write_kv((k_pages[i],),
                                   (c_kv.astype(cdt)[:, None],))
                    vp = v_pages[i]                # width-0 rope stream
                with phase("attn_core"):
                    rp = None if (page_quant or not d_r) else vp
                    sp = vp if page_quant else None
                    if use_kernel:
                        o_lat = _attend_by_region(
                            functools.partial(
                                latent_ragged_paged_attention_pallas,
                                c_pages=kp, r_pages=rp,
                                softmax_scale=(hd + d_r) ** -0.5,
                                scale_pages=sp, quant=page_quant,
                                latent_dim=d_c),
                            "latent_ragged_paged_attention", q_cat,
                            q_lens, cu_q, page_tables, ctx_lens,
                            max_seqs, prefill_rows, chunk, spec_k)
                    else:
                        o_lat = _split_latent_ragged_attention(
                            c, q_cat, kp, rp, q_lens, page_tables, ctx_lens,
                            max_seqs, prefill_rows, chunk, spec_k=spec_k,
                            scale_pages=sp, quant=page_quant)
                    # the W_UV fold: one up-projection per QUERY token —
                    # cached tokens are never decompressed
                    attn = jnp.einsum("thc,hdc->thd", o_lat,
                                      v_up.astype(jnp.float32))
                    attn = attn.reshape(t_tokens, nh * hd).astype(x.dtype)
            else:
                def qkv_proj(hh, i=i):
                    out = hh @ p.layer(i, "attn.qkv.weight").T
                    qb = p.layer(i, "attn.qkv.bias")
                    return out + qb if qb is not None else out

                with phase("attn_proj"):
                    qkv = region_map(qkv_proj, h, q_lens)
                q_size, kv_size = nh * hd, nkv * hd
                with phase("attn_core"):
                    q = qkv[..., :q_size].reshape(t_tokens, nh, hd)
                    k = qkv[..., q_size:q_size + kv_size].reshape(
                        t_tokens, nkv, hd)
                    v = qkv[..., q_size + kv_size:].reshape(t_tokens, nkv,
                                                            hd)
                    if c.position == "rotary":
                        q = _rope_tok(q, cos[token_pos], sin[token_pos])
                        k = _rope_tok(k, cos[token_pos], sin[token_pos])
                kp, vp = write_kv((k_pages[i], v_pages[i]),
                                  (k.astype(cdt), v.astype(cdt)))
                with phase("attn_core"):
                    if use_kernel:
                        # a page of ONE kv head a grid step, as the call
                        # was (at a group of 1 `kv_heads_per_block` keeps
                        # a head a block, so the one argument pins both):
                        # in groups it is 2-3 times shorter here, with a
                        # slot's 12 heads in one block shorter again, and
                        # the accepted cell `cgpt590m.serve-prefix` then
                        # drains its 400 requests inside the window and
                        # reads `correct: false`, the traced
                        # `cgpt590m.serve-chat` run passes its limit
                        # (PERF.md section 6, PR 42 / 44).  The pin goes
                        # once the `benchmark` PR of ROADMAP B0 has
                        # lengthened that replay (S1b ii)
                        attn = _attend_by_region(
                            functools.partial(
                                ragged_paged_attention_pallas,
                                k_pages=kp, v_pages=vp, pages_per_step=1),
                            "ragged_paged_attention", q, q_lens, cu_q,
                            page_tables, ctx_lens, max_seqs,
                            prefill_rows, chunk, spec_k)
                    else:
                        attn = _split_ragged_attention(
                            c, q, kp, vp, q_lens, page_tables, ctx_lens,
                            max_seqs, prefill_rows, chunk, spec_k=spec_k)
                    attn = attn.reshape(t_tokens, nh * hd).astype(x.dtype)

            def out_proj(aa, i=i):
                out = aa @ p.layer(i, "attn.out.weight").T
                ob = p.layer(i, "attn.out.bias")
                return out + ob if ob is not None else out

            with phase("attn_proj"):
                x = x + region_map(out_proj, attn, q_lens)
            with phase("norm"):
                h = _norm_apply(c, p.layer(i, "ln_2.weight"),
                                p.layer(i, "ln_2.bias"), x)
            if c.is_moe_layer(i):
                # decode slots: [T', 1, H] -> s=1 dense per-token mix
                # (v1 decode path); chunk slots: [1, C, H] -> dispatched
                # blocked group-GEMM (v1 prefill path) — both exactly
                # equivalent, each matching its v1 phase
                mlp = lambda hh, i=i: _moe_mlp(c, p, i,  # noqa: E731
                                               hh[:, None, :])[:, 0]
                mlp_chunk = lambda hh, i=i: _moe_mlp(c, p, i,  # noqa: E731
                                                     hh[None])[0]
            else:
                mlp_chunk = None

                def mlp(hh, i=i):
                    hh = _act(c, hh @ p.layer(i, "mlp.up.weight").T +
                              (p.layer(i, "mlp.up.bias")
                               if p.layer(i, "mlp.up.bias") is not None
                               else 0.0))
                    hh = hh @ p.layer(i, "mlp.down.weight").T
                    db = p.layer(i, "mlp.down.bias")
                    return hh + db if db is not None else hh

            with phase("mlp"):
                x = x + region_map(mlp, h, q_lens, f_chunk=mlp_chunk)
            new_k.append(kp)
            new_v.append(vp)
        with phase("norm"):
            x = _norm_apply(c, p("ln_f.weight"), p("ln_f.bias"), x)
        # per-row last TRUE query token -> [rows, V] fp32 logits
        last = jnp.clip(cu_q[:n_rows] + jnp.maximum(q_lens, 1) - 1, 0,
                        t_tokens - 1)
        with phase("lm_head_ce"):
            logits = _lm_head(p, x[last])
        # batched sampler: the sort-based sampled path runs under ONE
        # any(temps > 0) branch — all-greedy steps (the temp-0 bitwise
        # contract's case) never pay a vocab argsort per row
        with phase("sample"):
            next_tokens = sample_rows(logits, temps, top_ps, top_ks,
                                      seeds, ctx_lens)
        if spec_k == 0:
            return (layout.join(next_tokens=next_tokens), tuple(new_k),
                    tuple(new_v))
        next_tokens, accepted, _ = _verify_rows(
            p, x, tokens, cu_q, spec_lens, (temps, top_ps, top_ks, seeds),
            ctx_lens, next_tokens, max_seqs + prefill_rows, spec_k)
        return (layout.join(next_tokens=next_tokens, accepted=accepted),
                tuple(new_k), tuple(new_v))

    return run


def _block_head(cfg: GPTConfig, p, x, tokens, token_pos, live, q_lens,
                sampling, unmask_k, unmask_tau):
    """The head of a block-wise model's step over its block slots: ``x [S
    * 2B, H]`` (normed), the fed ``tokens`` and their positions, ``live``
    (the slot holds a row there), the slots' ``q_lens``, ``(temps, top_ps,
    top_ks, seeds)`` and unmask rule, all ``[S]``.  Logits at the OPEN
    block's B positions of each slot and nowhere else: the slot's first B
    for a plain row, its second B for a FUSED one (``q_len`` 2B: the
    committed block's clean tokens, whose K/V the pass writes and whose
    tokens nothing reads, then the next block all masks; the rule a fused
    row sends is pass 0's of that next block).  The logits
    score the token AT the position, the mask id's left out (a position
    is never unmasked INTO the mask id: with seeded weights it would be the
    arg-max once in a vocabulary's worth of positions, and the block would
    never close); ``x0`` from the repo's one per-row
    sampler, keyed by ``(seed, the position's index)`` — greedy at
    temperature 0 — and ``c = softmax(logits / T)[x0]`` (the whole
    tempered distribution: a top-k / top-p cut moves the draw, not ``c``);
    then, a row, of the MASKED positions the ``|unmask_k|`` first by rank —
    of highest ``c`` (a stable sort: ties to the lower position; fewer
    where fewer are masked) or, ``unmask_k < 0``, of lowest position (the
    sequential rule) — together with every masked one whose ``c >
    unmask_tau``.  A plain commit pass sends 0 and 2.0 and holds no mask:
    nothing is selected.
    Returns the layout's ``block_tokens`` (``x0`` where masked, the fed
    token elsewhere), ``block_flags`` and ``block_conf`` (bit pattern), of
    the open block."""
    b = cfg.diffusion_block
    n = unmask_k.shape[0]

    def open_block(a):
        halves = a.reshape((n, 2, b) + a.shape[1:])
        fused = (q_lens > b).reshape((n,) + (1,) * a.ndim)
        return jnp.where(fused, halves[:, 1], halves[:, 0]).reshape(
            (n * b,) + a.shape[1:])

    x, tokens, token_pos, live = (
        open_block(a) for a in (x, tokens, token_pos, live))
    head = p("lm_head.weight")
    head = head if head is not None else p("wte.weight")
    # the stack's dtype on both sides, float32 sums: no float32 copy of a
    # 150k-row head
    logits = lax.dot_general(x.astype(head.dtype), head,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    logits = jnp.where(jnp.arange(logits.shape[1])[None, :] ==
                       cfg.mask_token_id, -jnp.inf, logits)
    temps, top_ps, top_ks, seeds = (jnp.repeat(a, b) for a in sampling)
    x0 = sample_rows(logits, temps, top_ps, top_ks, seeds, token_pos)
    scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
    chosen = jnp.take_along_axis(scaled, x0[:, None], axis=1)[:, 0]
    conf = jnp.exp(chosen - jax.nn.logsumexp(scaled, axis=-1))
    masked = ((tokens == cfg.mask_token_id) & live).reshape(n, b)
    conf = conf.reshape(n, b)
    by_conf = jnp.where(unmask_k[:, None] < 0, 0.0, conf)
    order = jnp.argsort(-jnp.where(masked, by_conf, -1.0), axis=1,
                        stable=True)
    rank = jnp.argsort(order, axis=1)
    pick = masked & ((rank < jnp.abs(unmask_k)[:, None]) |
                     (conf > unmask_tau[:, None]))
    flags = jnp.sum(pick.astype(jnp.int32) << jnp.arange(b)[None, :], axis=1)
    return dict(
        block_tokens=jnp.where(masked, x0.reshape(n, b),
                               tokens.reshape(n, b)),
        block_flags=flags,
        block_conf=lax.bitcast_convert_type(conf, jnp.int32))


def _refuse_unbuilt_block(cfg: GPTConfig, chunk: int, page_size: int,
                          spec_k: int) -> None:
    """What is not built with a block-wise model (``cfg.diffusion_block``),
    refused where the step is built, by name."""
    b = cfg.diffusion_block
    if not b:
        return
    other = sorted(set(cfg.layer_pattern) - {"attention", "moe", "mlp"})
    if other or cfg.window_tokens:
        raise ValueError(
            "block-wise generation (diffusion_block) is built over full "
            "plain K/V attention layers: a denoise pass's provisional state "
            "cannot be rolled out of recurrent (mamba2) state, the latent "
            "(mla / dsa / swa) calls have no block mask, and a window layer "
            f"would slide inside an open block; the stack has "
            f"{other or 'window layers (attn_window)'}")
    if spec_k or cfg.mtp_pattern:
        raise ValueError(
            "speculative decoding is not built with block-wise generation "
            "(diffusion_block): a block row's step is its open block, "
            "there is no next token to draft")
    if chunk % b or page_size % b:
        raise ValueError(
            f"a chunk ({chunk}) and a page ({page_size}) are multiples of "
            f"the block length ({b}): a prompt's chunks and a cached page "
            "end where a block ends")


def _build_hybrid_step_fn(cfg: GPTConfig, max_seqs: int, chunk: int,
                          prefill_rows: int, max_pages: int, page_size: int,
                          use_kernel: bool, spec_k: int = 0):
    """The unified step of a hybrid stack (``cfg.layer_pattern``): the
    same token axis, regions and K/V write, one mixer per layer, and the
    recurrent state carried beside the pages.  The pages belong to the
    pattern's attention layers or to its latent-attention (``mla``)
    layers (``cfg.paged_layers``; one pool holds one layout): the latter
    write ``c_kv`` and ``k_r`` (zero lanes up to the pool's padded
    width, ``cfg.latent_page_dims``: q's rotary part is padded alike, so
    the scores are what they were) and attend them with ``W_kvb``
    absorbed, decode rows and prefill chunk alike.

    fn(params, packed [layout.size] i32,   # the dense step's fields and
                                           # state_slots [rows] i32: the
                                           # row's slot in the store
       k_pages, v_pages,                # paged layers only
       conv_states, ssm_states)         # state layers: [slots, ...]
      -> (out [layout.out_size] i32,    # next_tokens [rows], then
                                        # moe_load [moe layers, held]
          new k_pages, v_pages, conv_states, ssm_states)

    Latent layers whose geometry hangs on their kind (``cfg.geometry``;
    the pool is laid out by layer, ``cfg.page_layers``: ``k_pages[a]`` is
    layer ``a``'s one stream ``c_kv | k_r``, ``v_pages`` the index keys of
    the dsa layers): a ``dsa`` layer writes ``c_kv | k_r`` and its
    indexer's key of every token under the row's full-space page ids,
    scores each query against every index key of its context,
    keeps the ``index_topk`` best positions (on the device: the
    selection feeds the same layer's attention and nothing of it leaves
    the step) and attends those alone (``hy.indexed_attention``); a
    ``swa`` layer writes into the WINDOW space's pages (``win_tables``,
    ``win_token_page``: a row holds only the pages its window reaches)
    and attends the window (``hy.window_attention``); both multiply a
    head's output by its gate.  An ``mlp`` layer is a dense gated MLP.

    The store has ``max_seqs`` slots, one per running sequence.  Decode
    rows run SLOT-major: the rows' projections are permuted into slot
    order and the outputs permuted back.  The slots with a live decode
    row this step are listed once a step (``ops.ssd.live_slot_list``) and
    every recurrent layer's recurrence WALKS that list (``ops.ssd.
    ssd_decode_slots``, ``hy.IN_SLOT_MIXERS``): a listed slot's state is read,
    updated and written back in place, once, never gathered; a slot
    outside the list is neither read nor written (the conv tails, 1.5 %
    of the store's bytes, are passed over whole).  A chunk slot takes its
    row's state out of the store, carries it through the chunk (conv
    tail, then the chunked scan from the state it found) and puts it
    back; an idle chunk slot puts back what it took.  A row whose first
    token sits at position 0 starts from zeros whatever the slot holds.
    ``moe_load`` counts, per expert layer, the live tokens each held
    expert was chosen by.

    Plain K/V layers of a pool laid out by layer (``PageLayer.kv_heads``:
    an attention mixer with a geometry of its own, ``cfg.attn_*``): q and
    k behind their head-wise RMSNorm, rotated where ``cfg.attn_rope``
    says; a window layer writes into the WINDOW space's pages and attends
    ``win_tables`` from ``win_base`` on with one XLA gather and a masked
    softmax a region (``hy.kv_window_attention``), a full layer the row's
    full-space table with the ragged Pallas call (over a table of a few
    slots the gather is the faster: DESIGN.md §28).

    ``spec_k`` = 1 (SELF-DRAFTING, ``cfg.mtp_pattern``; DESIGN.md §28):
    the layout gains the verify slots and ``spec_lens`` of the dense
    step; the stack scores a verify row's two tokens ``[t, d]``, the
    accept head (``_verify_rows``) keeps ``d`` iff the stack's own choice
    at ``t`` is ``d`` and gives the bonus token; THEN, in the same
    executable, the MTP module runs over the token axis — position ``i``
    on ``[RMSNorm(Emb(t_{i+1})) | RMSNorm(x_L,i)] W_eh``, where ``t_{i+1}``
    is the next token of the row, ``next_tok`` behind a prompt chunk's
    last, the token just sampled at a row's tip, and, in a verify row,
    what the accept head committed — writes ITS attention layer's K/V
    under the row's full-space pages and proposes, at each row's last
    committed position, the draft of the row's next step (``draft``).  A
    verify row's position behind a rejected draft is dead to the module's
    experts and its K/V is overwritten by the next step, as the stack's.

    ``cfg.diffusion_block`` = B (BLOCK-WISE generation, DESIGN.md §29): the
    narrow slots behind the chunk slots are the BLOCK slots, ``max_seqs``
    rows of 2B positions on the verify slots' scaffolding (the same
    regions, write plan and one pass over the weights with the decode
    slots).  A generating row feeds its open block — known tokens, and
    ``cfg.mask_token_id`` where a position is not yet known — at the
    block's positions, the slot's first B (``q_len`` B: the second half is
    dead, as the tail of a part-filled chunk — routed to no expert,
    written to no page, skipped by the kernel); every layer writes the
    pass's K/V under the row's
    own pages there (a later pass overwrites it, as a rejected draft's;
    the commit pass's stands) and attends under the block-wise mask
    (``mask_block=B``: every region, the prompt's chunks too).  A FUSED
    row (``q_len`` 2B) feeds the block it commits and, behind it, the next
    block all masks: under that mask the first half sees what a commit
    pass of its own sees and the second half what the next block's first
    denoise pass sees, so one row is the two forwards.  The step
    ends in the BLOCK HEAD, not the next-token head: logits at each slot's
    OPEN block (position ``p``'s score the token AT ``p``), the
    per-row sampler's choice ``x0`` and its confidence ``c`` there, and
    the selection — of a row's masked positions the ``unmask_k`` of
    highest ``c`` (ties to the lower position) and every one with ``c >
    unmask_tau`` — all on the device; ``block_tokens`` / ``block_flags`` /
    ``block_conf`` ride the one output vector."""
    from ..models import hybrid as hy
    c = cfg
    block = c.diffusion_block
    wide = bool(spec_k or block)            # narrow slots behind the chunks
    mask_block = block or 1
    layout = StepLayout(c, max_seqs, chunk, prefill_rows, max_pages, spec_k,
                        page_size=page_size)
    t_tokens, n_rows = layout.n_tokens, layout.n_rows
    cdt = jnp.bfloat16 if c.dtype == "bfloat16" else jnp.float32
    hd, nh, nkv = c.head_dim, c.num_heads, c.kv_heads
    slots = _chunk_slots(max_seqs, prefill_rows, chunk, 0)
    regions = _regions(max_seqs, prefill_rows, chunk, spec_k, block)
    write_regions = tuple((row, n, width) for _, row, _, n, width in regions)
    v0 = max_seqs + prefill_rows            # the first verify / block row
    _, _, vtok, _, vk = regions[-1]         # ... its first token, its width
    attn_of = {i: a for a, i in enumerate(c.paged_layers)}
    by_layer_kv = c.page_layers is not None and c.page_layers[0].kv_heads
    if by_layer_kv and c.position == "rotary":
        rope_cos, rope_sin = hy.rotary_tables(c.rope_theta, hd,
                                              max_pages * page_size)
    if c.layers_of("mla"):
        cos, sin, q_scale = hy.mla_rotary_tables(c, max_pages * page_size)
        # the rotary stream's zero lanes, key and query alike
        rope_pad = ((0, 0), (0, c.latent_page_dims[1] - c.rope_dim))
    mamba_of = {i: m for m, i in enumerate(
        c.layers_of(c.state_mixer) if c.state_mixer else ())}
    # a layer's second page array (index keys; a K/V layer's V), in order
    index_of = {i: n for n, i in enumerate(
        i for i, l in zip(c.paged_layers, c.page_layers or ())
        if l.index or l.kv_heads)}
    # a dsa / swa layer's geometry and its rotary tables, by kind
    geo_of = {k: c.geometry(k) for k in ("dsa", "swa") if c.layers_of(k)}
    rot_of = {k: hy.mla_rotary_tables(c, max_pages * page_size, g)[:2]
              for k, g in geo_of.items()}

    def by_region(f, h, q_lens):
        """``f(tokens) -> per-token`` over the decode slots and, under
        ``lax.cond``, each chunk slot; both sides are arrays ``[n, ...]``
        or trees of them.  An idle chunk slot gives zeros and pays
        nothing; the verify or block slots (a few dozen to a few hundred
        tokens) run WITH the decode slots, as one group: one pass over
        ``f``'s weights, not two (a self-drafting step's rows are nearly
        all in the verify slots and its decode slots idle; a block-wise
        model's decode slots always are)."""
        tmap = jax.tree_util.tree_map
        if wide:
            both = f(tmap(lambda a: jnp.concatenate(
                [a[:max_seqs], a[vtok:]], axis=0), h))
            outs = [tmap(lambda a: a[:max_seqs], both)]
        else:
            outs = [f(tmap(lambda a: a[:max_seqs], h))]
        for row, start, width in slots:
            sl = tmap(lambda a: a[start: start + width], h)
            zero = jax.eval_shape(f, sl)
            outs.append(lax.cond(
                q_lens[row] > 0, f,
                lambda s, z=zero: tmap(
                    lambda a: jnp.zeros(a.shape, a.dtype), z), sl))
        if wide:
            outs.append(tmap(lambda a: a[max_seqs:], both))
        return tmap(lambda *a: jnp.concatenate(a, axis=0), *outs)

    def run(params, packed, k_pages, v_pages, conv_states, ssm_states):
        fields = layout.unpack(packed)
        (tokens, token_pos, token_page, token_off, q_lens, page_tables,
         ctx_lens, temps, top_ps, top_ks, seeds) = list(fields.values())[:11]
        state_slots = fields["state_slots"]
        cu_q = jnp.asarray(layout.cu_q)
        p = _params_view(c, params)
        with phase("embed"):
            x = p("wte.weight")[tokens].astype(cdt)
        # a token is live when its row holds it
        tok_row = jnp.concatenate(
            [jnp.arange(max_seqs)] +
            [jnp.full((w,), r) for r, _, w in slots] +
            ([jnp.repeat(jnp.arange(v0, n_rows), vk)] if wide else []))
        tok_idx = jnp.arange(t_tokens) - cu_q[tok_row]
        live = tok_idx < q_lens[tok_row]
        fresh_row = (ctx_lens - q_lens) == 0
        with phase("state_io"):
            # slot -> the decode row that feeds it this step (-1: none)
            dec = jnp.where(q_lens[:max_seqs] > 0, state_slots[:max_seqs],
                            max_seqs)
            slot_row = jnp.full((max_seqs,), -1, jnp.int32).at[dec].set(
                jnp.arange(max_seqs, dtype=jnp.int32), mode="drop")
            slot_live = slot_row >= 0
            slot_src = jnp.maximum(slot_row, 0)
            slot_fresh = slot_live & fresh_row[slot_src]
            # the live slots, compact: what every recurrent layer's
            # recurrence walks (hy.mamba_rows, hy.IN_SLOT_MIXERS' rows)
            walk = live_slot_list(slot_live) if mamba_of else None
        if use_kernel and attn_of:
            tile = write_tile((k_pages[0],) + tuple(v_pages[:1]))
            with phase("kv_scatter"):
                plan = kv_write_plan(token_page, token_off, q_lens, cu_q,
                                     regions=write_regions,
                                     page_size=page_size, tile=tile)
        new_k, new_v = list(k_pages), list(v_pages)
        new_conv, new_ssm = list(conv_states), list(ssm_states)
        loads = []
        # the write plans by (page-id space, tile): one a step each
        plans = {("full", tile): plan} if use_kernel and attn_of else {}

        def write_pages(a, news, index=None):
            """This step's new rows into paged layer ``a``'s pools, in the
            page-id space the layer allocates from: its two, or (a pool
            laid out by layer) its one stream and index stream ``index``
            where it has one."""
            if c.page_layers is None:
                pools = (k_pages[a], v_pages[a])
            else:
                pools = (k_pages[a],) + (
                    () if index is None else (v_pages[index],))
            window = c.page_layers is not None and \
                c.page_layers[a].space == "window"
            page = fields["win_token_page"] if window else token_page
            with phase("kv_scatter"):
                if not use_kernel:
                    return paged_kv_write_reference(pools, news, page,
                                                    token_off)
                key = ("window" if window else "full", write_tile(pools))
                if key not in plans:
                    plans[key] = kv_write_plan(
                        page, token_off, q_lens, cu_q,
                        regions=write_regions, page_size=page_size,
                        tile=key[1])
                return paged_kv_write(pools, news, plans[key], tile=key[1])

        def latent_by_region(f, *per_token, per_row=()):
            """``f(per-token slices..., per-row values...)`` -> the latent
            attention output, over the decode slots (a row a query) and
            each chunk slot (one row's queries), each under ``lax.cond``:
            a region with no live row pays nothing (a document's prefill
            steps hold no decode row)."""
            regions = [(jnp.any(q_lens[:max_seqs] > 0),
                        tuple(a[:max_seqs] for a in per_token + per_row))]
            for row, start, width in slots:
                regions.append((q_lens[row] > 0, tuple(
                    a[start: start + width] for a in per_token) + tuple(
                        a[row] for a in per_row)))
            outs = []
            for live_region, args in regions:
                zero = jax.eval_shape(f, *args)
                outs.append(lax.cond(
                    live_region, f,
                    lambda *_, z=zero: jnp.zeros(z.shape, z.dtype), *args))
            return jnp.concatenate(outs, axis=0)

        def kv_attention(i, h):
            """A plain K/V layer of a pool laid out by layer on ``h``
            (normed): its projections, the write into its page-id space
            and the ragged call over that space's table (the MTP module's
            layer runs the same call under its own phase)."""
            a, win = attn_of[i], c.window_of(i)
            with phase("attn_proj"):
                q, k, v = by_region(
                    lambda hh: hy.attention_qkv(c, params, i, hh), h, q_lens)
            if c.position == "rotary" and (c.attn_rope == "all" or win):
                with phase("attn_core"):
                    cos_t, sin_t = rope_cos[token_pos], rope_sin[token_pos]
                    q = hy.rotate_halves(q, cos_t, sin_t)
                    k = hy.rotate_halves(k, cos_t, sin_t)
            kp, vp = write_pages(a, (k.astype(cdt), v.astype(cdt)),
                                 index_of[i])
            new_k[a], new_v[index_of[i]] = kp, vp
            if win:
                # a window layer's table is a few slots: one XLA gather
                # and a masked softmax beat a Pallas call with a lower
                # bound over it (PERF.md section 6, PR 42)
                def kernel(q, q_lens, page_tables, ctx_lens, max_q,
                           kv_base, **_):
                    qpos = (ctx_lens - q_lens)[:, None] + jnp.arange(max_q)
                    return hy.kv_window_attention(
                        q, qpos.reshape(-1), page_tables, kv_base, kp, vp,
                        win, hd ** -0.5).astype(q.dtype)
            elif use_kernel:
                kernel = functools.partial(
                    ragged_paged_attention_pallas, k_pages=kp, v_pages=vp,
                    mask_block=mask_block)
            else:
                def kernel(name, **kw):
                    return ragged_paged_attention_reference(
                        k_pages=kp, v_pages=vp, mask_block=mask_block, **kw)

            def call(**kw):
                # a region with no live row (the decode slots, when every
                # decode row rides a verify slot) pays nothing
                return lax.cond(
                    jnp.any(kw["q_lens"] > 0), lambda: kernel(**kw),
                    lambda: jnp.zeros(kw["q"].shape, kw["q"].dtype))
            with phase("attn_window" if win else "attn_core"):
                attn = _attend_by_region(
                    call, "ragged_paged_attention", q, q_lens, cu_q,
                    fields["win_tables"] if win else page_tables, ctx_lens,
                    max_seqs, prefill_rows, chunk, spec_k,
                    kv_base=fields["win_base"] if win else None,
                    block=block)
                attn = attn.reshape(t_tokens, nh * hd).astype(h.dtype)
            with phase("attn_proj"):
                return by_region(
                    lambda aa: aa @ p.layer(i, "attn.out.weight").T, attn,
                    q_lens)

        def expert_layer(i, h, live):
            """Router, latent and shared expert region by region; the
            routed experts ONCE over the token axis, dead tokens
            carrying no assignment: a chunk step reads an expert's
            weights once, not once a region."""
            idx, wts, lat = by_region(
                lambda hh: hy.moe_route_down(c, params, i, hh), h, q_lens)
            r, load = hy.moe_routed(c, params, i, lat, idx, wts, live)
            return by_region(
                lambda hr: hy.moe_up_shared(c, params, i, *hr), (h, r),
                q_lens), load

        for i, mixer in enumerate(c.layer_pattern):
            with phase("norm"):
                h = norm_in(p.layer(i, "norm.weight"), x)
            if mixer == "attention" and by_layer_kv:
                out = kv_attention(i, h)
            elif mixer == "attention":
                a = attn_of[i]
                with phase("attn_proj"):
                    qkv = by_region(
                        lambda hh, i=i: hh @ p.layer(i, "attn.qkv.weight").T,
                        h, q_lens)
                q_size, kv_size = nh * hd, nkv * hd
                with phase("attn_core"):
                    q = qkv[..., :q_size].reshape(t_tokens, nh, hd)
                    k = qkv[..., q_size:q_size + kv_size].reshape(
                        t_tokens, nkv, hd)
                    v = qkv[..., q_size + kv_size:].reshape(t_tokens, nkv, hd)
                kp, vp = write_pages(a, (k.astype(cdt), v.astype(cdt)))
                with phase("attn_core"):
                    if use_kernel:
                        attn = _attend_by_region(
                            functools.partial(ragged_paged_attention_pallas,
                                              k_pages=kp, v_pages=vp),
                            "ragged_paged_attention", q, q_lens, cu_q,
                            page_tables, ctx_lens, max_seqs, prefill_rows,
                            chunk, 0)
                    else:
                        attn = _split_ragged_attention(
                            c, q, kp, vp, q_lens, page_tables, ctx_lens,
                            max_seqs, prefill_rows, chunk)
                    attn = attn.reshape(t_tokens, nh * hd).astype(x.dtype)
                with phase("attn_proj"):
                    out = by_region(
                        lambda aa, i=i: aa @ p.layer(i, "attn.out.weight").T,
                        attn, q_lens)
                new_k[a], new_v[a] = kp, vp
            elif mixer == "mla":
                a = attn_of[i]
                with phase("attn_proj"):
                    q, c_kv, k_r = by_region(
                        lambda hh, i=i: hy.mla_in(c, params, i, hh),
                        h, q_lens)
                with phase("attn_core"):
                    cos_t, sin_t = cos[token_pos], sin[token_pos]
                    if c.q_pos_scale:
                        q = q * q_scale[token_pos][:, None, None].astype(
                            q.dtype)
                    q_rot = hy.mla_rotate(c, q[..., c.nope_dim:], cos_t,
                                          sin_t)
                    k_rot = hy.mla_rotate(c, k_r, cos_t, sin_t)
                with phase("mla_absorb"):
                    q_cat = hy.mla_absorb_q(c, params, i, q, q_rot)
                kp, vp = write_pages(a, (
                    c_kv.astype(cdt)[:, None],
                    jnp.pad(k_rot.astype(cdt), rope_pad)[:, None]))
                with phase("attn_core"):
                    q_cat = jnp.pad(q_cat, ((0, 0),) + rope_pad)
                    if use_kernel:
                        o_lat = _attend_by_region(
                            functools.partial(
                                latent_ragged_paged_attention_pallas,
                                c_pages=kp, r_pages=vp,
                                softmax_scale=c.mla_softmax_scale,
                                latent_dim=c.kv_latent_dim),
                            "latent_ragged_paged_attention", q_cat, q_lens,
                            cu_q, page_tables, ctx_lens, max_seqs,
                            prefill_rows, chunk, 0)
                    else:
                        o_lat = _split_latent_ragged_attention(
                            c, q_cat, kp, vp, q_lens, page_tables, ctx_lens,
                            max_seqs, prefill_rows, chunk,
                            scale=c.mla_softmax_scale,
                            dims=c.latent_page_dims)
                with phase("mla_absorb"):
                    attn = hy.mla_absorb_out(c, params, i, o_lat, x.dtype)
                with phase("attn_proj"):
                    out = by_region(
                        lambda aa, i=i: aa @ p.layer(i, "attn.out.weight").T,
                        attn, q_lens)
                new_k[a], new_v[a] = kp, vp
            elif mixer in ("dsa", "swa"):
                a, geo = attn_of[i], geo_of[mixer]
                cos_k, sin_k = rot_of[mixer]
                with phase("attn_proj"):
                    q, c_kv, k_r, c_q = by_region(
                        lambda hh, i=i, geo=geo: hy.latent_in(
                            c, params, i, hh, geo), h, q_lens)
                with phase("attn_gate"):
                    gate = by_region(
                        lambda hh, i=i: hy.head_gate(params, i, hh),
                        h, q_lens)
                with phase("attn_core"):
                    cos_t, sin_t = cos_k[token_pos], sin_k[token_pos]
                    q_rot = hy.mla_rotate(c, q[..., geo.nope:], cos_t,
                                          sin_t, geo.interleave)
                    k_rot = hy.mla_rotate(c, k_r, cos_t, sin_t,
                                          geo.interleave)
                    # the page's row: c_kv | k_r | zero lanes; q's rotary
                    # part is padded alike, so the scores are what they
                    # were
                    lanes = ((0, 0), (0, geo.rope_lanes - geo.rope))
                    news = (jnp.concatenate(
                        [c_kv.astype(cdt), jnp.pad(k_rot.astype(cdt),
                                                   lanes)], -1)[:, None],)
                with phase("mla_absorb"):
                    q_cat = jnp.pad(hy.mla_absorb_q(
                        c, params, i, q, q_rot, geo.nope), ((0, 0),) + lanes)
                if mixer == "dsa":
                    with phase("attn_index"):
                        iq, iw = by_region(
                            lambda hc, i=i, geo=geo: hy.index_queries(
                                params, i, *hc, geo), (h, c_q), q_lens)
                        ik = by_region(
                            lambda hh, i=i: hy.index_keys(params, i, hh),
                            h, q_lens)
                        iq = hy.rotate_index(iq, cos_t, sin_t)
                        ik = hy.rotate_index(ik, cos_t, sin_t)
                    kp, xp = write_pages(
                        a, news + (ik.astype(cdt)[:, None],), index_of[i])
                    new_v[index_of[i]] = xp
                    # a chunk slot's selection and read run the blocks up
                    # to its row's live tokens (a suffix's last chunk is
                    # part-filled); the decode region's per-row tables
                    # take no count
                    o_lat = latent_by_region(
                        lambda iq_, iw_, qc, qp, tab, ql, geo=geo, pools=(
                            kp, xp): hy.indexed_attention(
                                geo, iq_, iw_, qc, qp, tab, pools,
                                use_kernel=use_kernel, live=ql),
                        iq, iw, q_cat, token_pos,
                        per_row=(page_tables, q_lens))
                else:
                    (kp,) = write_pages(a, news)
                    with phase("attn_window"):
                        o_lat = latent_by_region(
                            lambda qc, qp, tab, base, geo=geo, kp=kp:
                            hy.window_attention(geo, qc, qp, tab, base, kp),
                            q_cat, token_pos,
                            per_row=(fields["win_tables"],
                                     fields["win_base"]))
                with phase("mla_absorb"):
                    attn = hy.mla_absorb_out(c, params, i, o_lat, jnp.float32)
                with phase("attn_gate"):
                    attn = (attn.reshape(t_tokens, geo.heads, geo.v) *
                            gate[:, :, None]).reshape(
                                t_tokens, -1).astype(x.dtype)
                with phase("attn_proj"):
                    out = by_region(
                        lambda aa, i=i: aa @ p.layer(i, "attn.out.weight").T,
                        attn, q_lens)
                new_k[a] = kp
            elif mixer == "mlp":
                with phase("mlp_dense"):
                    out = by_region(
                        lambda hh, i=i: hy.gated_mlp(c, params, i, hh),
                        h, q_lens)
            elif mixer == "mamba2":
                m = mamba_of[i]
                w = hy.MambaWeights(params, i)
                with phase("ssm_proj"):
                    zxd = by_region(
                        lambda hh, w=w: hh @ w.in_proj.T, h, q_lens)
                with phase("state_io"):
                    zxd_slots = zxd[:max_seqs][slot_src]
                y_slots, conv_s, ssm_s = hy.mamba_rows(
                    c, w, zxd_slots, new_conv[m], new_ssm[m], slot_live,
                    slot_fresh, walk)
                with phase("state_io"):
                    ys = [y_slots[state_slots[:max_seqs]]]
                for row, start, width in slots:
                    slot, qlen = state_slots[row], q_lens[row]
                    with phase("state_io"):
                        tail0, s0 = conv_s[slot], ssm_s[slot]
                    y_c, tail1, s1 = lax.cond(
                        qlen > 0,
                        lambda z, t, s, n, f, w=w: hy.mamba_chunk(
                            c, w, z, t, s, n, f),
                        lambda z, t, s, n, f: (
                            jnp.zeros((width, c.mamba_inner), jnp.float32),
                            t, s),
                        zxd[start: start + width], tail0, s0, qlen,
                        fresh_row[row])
                    with phase("state_io"):
                        conv_s = conv_s.at[slot].set(tail1)
                        ssm_s = ssm_s.at[slot].set(s1)
                    ys.append(y_c)
                with phase("ssm_scan"):
                    y = hy.mamba_gate_norm(
                        c, w, jnp.concatenate(ys, axis=0),
                        zxd[..., :c.mamba_inner], x.dtype)
                with phase("ssm_proj"):
                    out = by_region(
                        lambda yy, w=w: yy @ w.out_proj.T, y, q_lens)
                new_conv[m], new_ssm[m] = conv_s, ssm_s
            elif mixer in hy.IN_SLOT_MIXERS:
                m = mamba_of[i]
                weights, rows_of, chunk_of, gate = hy.IN_SLOT_MIXERS[mixer]
                w = weights(params, i)
                with phase("ssm_proj"):
                    xz = by_region(
                        lambda hh, w=w: hh @ w.in_proj.T, h, q_lens)
                with phase("state_io"):
                    xz_slots = xz[:max_seqs][slot_src]
                y_slots, conv_s, ssm_s = rows_of(
                    c, w, xz_slots, new_conv[m], new_ssm[m], slot_live,
                    slot_fresh, walk)
                with phase("state_io"):
                    ys = [y_slots[state_slots[:max_seqs]]]
                for row, start, width in slots:
                    # the row's state stays in its slot: the chunk form
                    # carries it through the chunk in place
                    y_c, conv_s, ssm_s = lax.cond(
                        q_lens[row] > 0,
                        lambda z, cs, ss, sl, n, f, w=w: chunk_of(
                            c, w, z, cs, ss, sl, n, f),
                        lambda z, cs, ss, sl, n, f: (
                            jnp.zeros((width, y_slots.shape[1]),
                                      jnp.float32), cs, ss),
                        xz[start: start + width], conv_s, ssm_s,
                        state_slots[row], q_lens[row], fresh_row[row])
                    ys.append(y_c)
                with phase("ssm_scan"):
                    y = gate(c, w, jnp.concatenate(ys, axis=0), xz, x.dtype)
                with phase("ssm_proj"):
                    out = by_region(
                        lambda yy, w=w: yy @ w.out_proj.T, y, q_lens)
                new_conv[m], new_ssm[m] = conv_s, ssm_s
            else:
                out, load = expert_layer(i, h, live)
                loads.append(load)
            x = x + norm_out(p.layer(i, "norm.weight"), out.astype(x.dtype))
        x_last = x
        with phase("norm"):
            x = _norm_apply(c, p("ln_f.weight"), None, x)
        stack = lambda ls: jnp.stack(ls) if ls else \
            jnp.zeros((0, max(c.held_experts, 1)), jnp.int32)  # noqa: E731
        if block:
            # no row has a next token: the block head is the step's head
            with phase("block_head"):
                heads = _block_head(
                    c, p, x[vtok:], tokens[vtok:], token_pos[vtok:],
                    live[vtok:], q_lens[v0:], tuple(a[v0:] for a in (
                        temps, top_ps, top_ks, seeds)),
                    fields["unmask_k"][v0:], fields["unmask_tau"][v0:])
            return (layout.join(next_tokens=jnp.zeros((n_rows,), jnp.int32),
                                moe_load=stack(loads), **heads),
                    tuple(new_k), tuple(new_v), tuple(new_conv),
                    tuple(new_ssm))
        last = jnp.clip(cu_q[:n_rows] + jnp.maximum(q_lens, 1) - 1, 0,
                        t_tokens - 1)
        with phase("lm_head_ce"):
            logits = _lm_head(p, x[last])
        with phase("sample"):
            next_tokens = sample_rows(logits, temps, top_ps, top_ks, seeds,
                                      ctx_lens)
        outs = dict(next_tokens=next_tokens, moe_load=stack(loads))
        if spec_k:
            next_tokens, accepted, draft_next = _verify_rows(
                p, x, tokens, cu_q, fields["spec_lens"],
                (temps, top_ps, top_ks, seeds), ctx_lens, next_tokens, v0,
                spec_k)
            # what follows each fed token, as the MTP module embeds it: the
            # row's next token; behind its last, the host's or the sample;
            # in a verify row, what the accept head committed (in-row index
            # j: the accepted drafts, then the bonus)
            acc_v = accepted[v0:, None]
            j = jnp.arange(vk)[None, :]
            emit = jnp.where(j < acc_v, jnp.pad(draft_next, ((0, 0), (0, 1))),
                             next_tokens[v0:, None])
            tip = jnp.where(fields["next_tok"] >= 0, fields["next_tok"],
                            next_tokens)
            follows = jnp.where(tok_idx == q_lens[tok_row] - 1, tip[tok_row],
                                jnp.roll(tokens, -1))
            follows = jnp.concatenate([follows[:vtok], emit.reshape(-1)])
            mtp_live = live & jnp.concatenate(
                [jnp.ones((vtok,), bool), (j <= acc_v).reshape(-1)])
            with phase("mtp_proj"):
                emb = p("wte.weight")[follows].astype(cdt)
                z = by_region(lambda ex: hy.mtp_in(c, params, *ex),
                              (emb, x_last), q_lens).astype(cdt)
            mtp_loads = []
            for i, mixer in enumerate(c.mtp_pattern, c.num_layers):
                with phase("mtp_attn" if mixer == "attention"
                           else "mtp_moe"):
                    h = _norm_apply(c, p.layer(i, "norm.weight"), None, z)
                    if mixer == "attention":
                        out = kv_attention(i, h)
                    else:
                        out, load = expert_layer(i, h, mtp_live)
                        mtp_loads.append(load)
                z = z + out.astype(z.dtype)
            # the draft behind a row's last committed position
            at = jnp.where(jnp.arange(n_rows) >= v0, accepted,
                           jnp.maximum(q_lens, 1) - 1)
            with phase("mtp_head"):
                zl = _norm_apply(c, p("mtp.norm.weight"), None,
                                 z[jnp.clip(cu_q[:n_rows] + at, 0,
                                            t_tokens - 1)])
                draft = jnp.argmax(_lm_head(p, zl), -1)
            outs.update(next_tokens=next_tokens, accepted=accepted,
                        draft=draft, mtp_load=stack(mtp_loads))
        return (layout.join(**outs), tuple(new_k), tuple(new_v),
                tuple(new_conv), tuple(new_ssm))

    # the sublayer's one norm, where the configuration puts it: on the
    # sublayer's input ("pre", under the loop's own phase) or on its output
    # ("post": ``x + Norm(f(x))``).  Defined behind ``run`` so that no line
    # of it moves: a Pallas kernel's payload carries its call stack, line
    # numbers and all, and a shifted line is another compile-cache key for
    # every stack this builder serves
    post_norm = c.norm_position == "post"

    def norm_in(w, x):
        return x if post_norm else _norm_apply(c, w, None, x)

    def norm_out(w, out):
        if not post_norm:
            return out
        with phase("norm"):
            return _norm_apply(c, w, None, out)

    return jax.jit(run, donate_argnums=(2, 3, 4, 5))
