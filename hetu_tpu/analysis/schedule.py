"""Cross-rank collective-schedule verifier (DESIGN.md §25).

Every other pass in this package prices ONE executable — the program a
single mesh runs.  This module reasons about ALL ranks at once: it
extracts a per-rank *symbolic schedule* of communication operations —
the ordered list of collectives, p2p sends/recvs and hot-switch repack
transfers each rank issues over one training step — and verifies the
cross-rank consistency invariants that a process-local CPU harness can
never exercise but that decide whether the program hangs on a pod:

* **order**    — every rank in a communicator group issues the same
  collectives in the same order.  A rank that reaches collective #7
  while its peers sit at #6 of a different kind blocks forever.
* **group**    — the group tuples agree.  Two ranks that disagree on
  who participates in an all-reduce each wait for a member that never
  arrives.
* **payload**  — shape/dtype/reduction agree.  Mismatched payloads are
  the silent-corruption twin of the hang (and with EQuARX-style
  quantized collectives, dtype is one more way ranks can diverge).
* **pairing**  — every p2p send has a matching recv on the destination
  rank (and vice versa), per channel, by (tag, payload, dtype).
* **acyclicity** — a wait-for graph over pipeline stages x collectives
  has no cycle: the schedules are simulated under rendezvous collective
  / buffered-send / blocking-recv semantics and must run to completion.
* **repack**   — hot-switch repack transfers (``parallel/switch``)
  agree between the sending and receiving side of a dp resize.

Schedules are extracted from the SAME predictors the runtime uses:
dp grad buckets and ZeRO-2/3 ``param_gather`` chains from
``dstates.predict_update_step_collectives`` (the predictor
``optim/optimizer.py``'s flat path is verified against), communicator
groups from ``DistributedStates.get_group_indices_by_dim``, tp/cp
collectives modeled on ``parallel/ulysses`` / ``ring_attention``,
pipeline p2p from ``parallel/schedule`` task lists (via
:func:`~hetu_tpu.parallel.schedule.p2p_events`, the same projection the
MPMD runtime's executed-order tap is checked against) and
``parallel/pipeline.spmd_hop_schedule``, and switch repacks from
``parallel.switch.symbolic_repack_transfers``.

Verification gating: the deadlock simulation runs ONLY when the
pairwise checks are clean — an order/group/pairing divergence trivially
implies a hang, and reporting both would bury the root cause (and make
the seeded-bug corpus's "found by exactly its rule" contract
impossible).  Cascade suppression keeps one violation per implicated
rank set, mirroring the protocol verifier's first-violation-per-subject
poisoning.
"""
from __future__ import annotations

import dataclasses
from collections import Counter, deque
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

RULE_ORDER = "collective-order-mismatch"
RULE_GROUP = "collective-group-mismatch"
RULE_PAYLOAD = "collective-payload-mismatch"
RULE_UNPAIRED = "p2p-unpaired"
RULE_DEADLOCK = "pipeline-deadlock"
RULE_SWITCH = "switch-repack-divergence"

SCHEDULE_RULES: Tuple[str, ...] = (
    RULE_ORDER, RULE_GROUP, RULE_PAYLOAD, RULE_UNPAIRED, RULE_DEADLOCK,
    RULE_SWITCH)

COLLECTIVE_KINDS = ("all_reduce", "all_gather", "reduce_scatter",
                    "all_to_all", "ppermute")
P2P_KINDS = ("send", "recv")


@dataclasses.dataclass(frozen=True)
class CommOp:
    """One communication operation a rank issues, in program order."""
    kind: str                      # COLLECTIVE_KINDS | send | recv | copy
    group: Tuple[int, ...]         # participating ranks ((src, dst) for p2p)
    payload_bytes: int
    dtype: str = "float32"
    reduction: str = ""            # "sum" where a reduction rides the op
    tag: str = ""                  # provenance (grad_comm/bucket0, ...)
    peer: int = -1                 # p2p only: the other rank

    def describe(self) -> str:
        red = f" {self.reduction}" if self.reduction else ""
        return (f"{self.kind}{red} {self.tag or 'untagged'} "
                f"group={self.group} {self.payload_bytes}B {self.dtype}")


@dataclasses.dataclass
class ScheduleViolation:
    """One cross-rank divergence, with the per-rank subtraces that show
    it side by side (printed by the CLI's ``--schedule --explain``)."""
    rule: str
    subject: str
    message: str
    ranks: Tuple[int, ...] = ()
    subtrace: Dict[int, List[str]] = dataclasses.field(default_factory=dict)
    provenance: str = "schedule"

    def format_subtrace(self) -> str:
        blocks = []
        for r in sorted(self.subtrace):
            lines = "\n".join("    " + l for l in self.subtrace[r])
            blocks.append(f"  rank {r}:\n{lines}")
        return "\n".join(blocks)


# ---------------------------------------------------------------------------
# program specification
# ---------------------------------------------------------------------------

_DEFAULT_ENTRIES = (("w_qkv", (64, 192), "float32"),
                    ("w_mlp", (64, 256), "float32"))


@dataclasses.dataclass
class ProgramSpec:
    """Symbolic description of one multi-rank training program.

    Rank layout: ``rank = ((p * dp + d) * cp + c) * tp + t`` — pipeline
    stage outermost (MPMD submeshes are disjoint per stage), then data-,
    context-, tensor-parallel innermost, matching the gate meshes.
    """
    dp: int = 1
    tp: int = 1
    pp: int = 1
    cp: int = 1
    zero: int = 0
    flat: bool = False
    transport: str = "fp32"
    bucket_mb: float = 4.0
    clip: bool = False
    scalar_fetches: int = 1
    opt_extra: Optional[Dict[str, int]] = None
    entries: Tuple = _DEFAULT_ENTRIES
    num_micro_batches: int = 2
    per_pipe_micro: Optional[Tuple[int, ...]] = None    # MPMD Malleus
    pipeline_mode: str = "auto"        # auto | none | spmd | mpmd
    pipeline_schedule: str = "1f1b"    # 1f1b | gpipe
    cp_mode: str = "ulysses"           # ulysses | ring
    layers: int = 2
    seq: int = 128
    hidden: int = 64
    # mid-run dp resize of the flat optimizer layout: {"numel", "itemsize",
    # "new_dp"} — repack transfers appended after the step
    switch: Optional[Dict[str, int]] = None

    def __post_init__(self):
        if self.pipeline_mode == "auto":
            self.pipeline_mode = "none" if self.pp <= 1 else "mpmd"
        if self.pp <= 1:
            self.pipeline_mode = "none"

    @property
    def world(self) -> int:
        return self.pp * self.dp * self.cp * self.tp

    @property
    def block(self) -> int:
        return self.dp * self.cp * self.tp


def spec_from_meta(meta: Dict[str, Any],
                   mesh_axes: Optional[Dict[str, int]] = None
                   ) -> Optional[ProgramSpec]:
    """Derive a :class:`ProgramSpec` from an executable registration's
    meta (the same record sites the other passes consume): an explicit
    ``schedule_spec`` dict wins; otherwise a ``grad_comm`` plan (dp
    width, transport, zero, entries) and/or a ``pipeline`` record
    (stage count, hops>0 = the SPMD ppermute pipeline).  Returns None
    for executables that make no multi-rank claim (serving steps)."""
    ss = meta.get("schedule_spec")
    if ss:
        return ProgramSpec(**ss)
    mesh_axes = dict(mesh_axes or meta.get("mesh_axes") or {})
    tp = int(mesh_axes.get("tp", 1))
    cp = int(mesh_axes.get("cp", mesh_axes.get("sp", 1)))
    gc = meta.get("grad_comm")
    pl = meta.get("pipeline")
    if gc:
        entries = tuple((n, tuple(s), d) for n, s, d in gc["entries"])
        return ProgramSpec(
            dp=int(gc["device_num"]), tp=tp, cp=cp,
            zero=int(gc.get("zero", 2) or 2),
            flat=bool(gc.get("flat", False)),
            transport=gc.get("transport", "fp32"),
            bucket_mb=float(gc.get("bucket_mb", 4.0)),
            clip=bool(gc.get("clip", False)),
            scalar_fetches=int(gc.get("scalar_fetches", 1)),
            opt_extra=gc.get("opt_extra"), entries=entries)
    if pl:
        # MPMD registrations carry num_stages; the SPMD pipeline's stage
        # count is its pp mesh extent (every rank runs the same program)
        S = int(pl.get("num_stages", 0)
                or mesh_axes.get(pl.get("pp_axis", "pp"), 1))
        if S <= 1:
            return None
        hops = int(pl.get("hops", 0))
        mode = "spmd" if hops > 0 else "mpmd"
        M = max(1, hops - S + 1) if hops > 0 else 2
        dp = int(mesh_axes.get("dp", 1))
        return ProgramSpec(dp=dp, tp=tp, cp=cp, pp=S, entries=(),
                           num_micro_batches=M, pipeline_mode=mode)
    return None


# ---------------------------------------------------------------------------
# extraction
# ---------------------------------------------------------------------------


def _groups(spec: ProgramSpec):
    """(dp_group, cp_group, tp_group, pp_group) closures over global
    ranks, built on ``DistributedStates.get_group_indices_by_dim`` —
    the SAME interval/macro arithmetic the runtime's comm deduction
    uses, so the verifier's communicator groups are the deduction's."""
    from ..parallel.dstates import DistributedStates
    B = spec.block
    dims = {0: spec.dp, 1: spec.cp, 2: spec.tp}
    ds = DistributedStates(B, dict(dims), [0, 1, 2]) if B > 1 else None

    def grp(dim: int, rank: int) -> Tuple[int, ...]:
        if dims[dim] <= 1 or ds is None:
            return (rank,)
        p, local = divmod(rank, B)
        return tuple(p * B + g
                     for g in ds.get_group_indices_by_dim(dim, local))

    def pp_group(rank: int) -> Tuple[int, ...]:
        local = rank % B
        return tuple(p * B + local for p in range(spec.pp))

    return (lambda r: grp(0, r), lambda r: grp(1, r),
            lambda r: grp(2, r), pp_group)


def _grad_sections(spec: ProgramSpec, dp_group):
    """(front_ops, tail_ops) per-rank closures for the grad/param sync:
    the ZeRO-3 just-in-time ``param_gather`` chain runs at the FRONT of
    the step (before any forward math — PR 19's at-rest sharding), the
    reduce-scatter / param_comm / scalar-fetch chain at the END."""
    if spec.dp <= 1 or not spec.entries:
        return [], []
    from ..parallel.dstates import predict_update_step_collectives
    entries = [(n, tuple(s), d) for n, s, d in spec.entries]
    preds, extra = predict_update_step_collectives(
        entries, spec.dp, transport=spec.transport,
        bucket_mb=spec.bucket_mb, scalar_fetches=spec.scalar_fetches,
        flat=spec.flat, clip=spec.clip, zero=spec.zero,
        opt_extra=spec.opt_extra)
    front, tail = [], []
    bucket = 0
    for p in preds:
        tag = p.get("tag")
        if tag is None:
            tag = f"grad_comm/bucket{bucket}"
            bucket += 1
        red = "sum" if p["kind"] in ("all_reduce", "reduce_scatter") else ""
        proto = (p["kind"], int(p["payload_bytes"]), p["dtype"], red, tag)
        (front if p.get("tag") == "param_gather" else tail).append(proto)
    for kind, n in sorted((extra or {}).items()):
        for _ in range(int(n)):
            tail.append((kind, 4, "float32",
                         "sum" if kind == "all_reduce" else "",
                         "fetch/scalar"))
    return front, tail


def _compute_ops(spec: ProgramSpec, rank: int, cp_group, tp_group,
                 phase: str) -> List[CommOp]:
    """tp/cp collectives of one micro-batch's forward (or backward)
    through this rank's layer slice — Megatron-style two all-reduces
    per layer over the tp group; Ulysses head/seq all-to-all pair (plus
    the segment-id all-gather) or the ring-attention ppermute chain
    over the cp group."""
    ops: List[CommOp] = []
    act = (spec.seq // max(spec.cp, 1)) * spec.hidden * 4
    for layer in range(spec.layers):
        if spec.cp > 1:
            g = cp_group(rank)
            if spec.cp_mode == "ulysses":
                for half in ("scatter", "gather"):
                    ops.append(CommOp("all_to_all", g, act, "float32",
                                      tag=f"ulysses/l{layer}/{phase}/"
                                          f"{half}"))
                if phase == "fwd":
                    ops.append(CommOp("all_gather", g,
                                      (spec.seq // spec.cp) * 4, "int32",
                                      tag=f"ulysses/l{layer}/segids"))
            else:
                for hop in range(spec.cp - 1):
                    ops.append(CommOp("ppermute", g, act, "float32",
                                      tag=f"ring/l{layer}/{phase}/"
                                          f"hop{hop}"))
        if spec.tp > 1:
            g = tp_group(rank)
            for site in ("attn", "mlp"):
                ops.append(CommOp("all_reduce", g, act, "float32",
                                  reduction="sum",
                                  tag=f"tp/l{layer}/{phase}/{site}"))
    return ops


def _switch_ops(spec: ProgramSpec, dp_group) -> Dict[int, List[CommOp]]:
    """Hot-switch repack transfers of the flat dp-sharded optimizer
    layout under a mid-run dp resize: per dp group, the 1-D symbolic
    twin of ``SwitchPlan.transfers`` decides who sends which interval
    to whom; every member derives the SAME transfer list and emits its
    own sends/recvs (divergence here = ``switch-repack-divergence``)."""
    from ..parallel.switch import symbolic_repack_transfers
    sw = spec.switch or {}
    numel = int(sw.get("numel", 1 << 16))
    itemsize = int(sw.get("itemsize", 4))
    new_dp = max(1, int(sw.get("new_dp", max(1, spec.dp // 2))))
    out: Dict[int, List[CommOp]] = {r: [] for r in range(spec.world)}
    seen = set()
    for r in range(spec.world):
        g = dp_group(r)
        if g in seen:
            continue
        seen.add(g)
        old_ranges = _even_ranges(numel, g[:spec.dp])
        new_ranges = _even_ranges(numel, g[:new_dp])
        transfers = symbolic_repack_transfers(numel, itemsize,
                                              old_ranges, new_ranges)
        for i, (dst, src, (lo, hi), nbytes) in enumerate(transfers):
            tag = f"switch/repack/t{i}"
            if src == dst:
                out[dst].append(CommOp("copy", (dst,), nbytes, "float32",
                                       tag=tag))
                continue
            out[src].append(CommOp("send", (src, dst), nbytes, "float32",
                                   tag=tag, peer=dst))
            out[dst].append(CommOp("recv", (src, dst), nbytes, "float32",
                                   tag=tag, peer=src))
    return out


def _even_ranges(numel: int, ranks: Sequence[int]) -> Dict[int, Tuple[int, int]]:
    n = len(ranks)
    per = -(-numel // n)
    return {r: (min(i * per, numel), min((i + 1) * per, numel))
            for i, r in enumerate(ranks)}


def extract_schedules(spec: ProgramSpec) -> Dict[int, List[CommOp]]:
    """Per-rank symbolic schedule of one training step (plus the
    optional mid-run switch): ``{rank: [CommOp, ...]}`` in issue
    order."""
    from ..parallel.pipeline import spmd_hop_schedule
    from ..parallel.schedule import (generate_gpipe_schedule,
                                     generate_pipedream_flush_schedule)
    dp_group, cp_group, tp_group, pp_group = _groups(spec)
    front, tail = _grad_sections(spec, dp_group)
    sched: Dict[int, List[CommOp]] = {r: [] for r in range(spec.world)}
    act = (spec.seq // max(spec.cp, 1)) * spec.hidden * 4
    B = spec.block

    def emit_protos(rank: int, protos) -> None:
        g = dp_group(rank)
        for kind, payload, dtype, red, tag in protos:
            sched[rank].append(CommOp(kind, g, payload, dtype,
                                      reduction=red, tag=tag))

    # (1) ZeRO-3 just-in-time weight gathers, before any forward math
    for r in range(spec.world):
        emit_protos(r, front)

    # (2) forward/backward compute collectives + pipeline p2p/hops
    if spec.pipeline_mode == "mpmd":
        gen = (generate_pipedream_flush_schedule
               if spec.pipeline_schedule == "1f1b"
               else generate_gpipe_schedule)
        micro = spec.per_pipe_micro or \
            tuple([spec.num_micro_batches] * spec.dp)
        assert len(micro) == spec.dp, (micro, spec.dp)
        pipe_scheds = {d: gen(spec.pp, m) for d, m in enumerate(micro)}
        for r in range(spec.world):
            s, local = divmod(r, B)
            d = local // (spec.cp * spec.tp)
            for t in pipe_scheds[d][s]:
                m = t.micro_batch
                if t.kind == "F":
                    if s > 0:
                        peer = (s - 1) * B + local
                        sched[r].append(CommOp("recv", (peer, r), act,
                                               "float32",
                                               tag=f"pipe{d}/F{m}",
                                               peer=peer))
                    sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                             "fwd")
                    if s < spec.pp - 1:
                        peer = (s + 1) * B + local
                        sched[r].append(CommOp("send", (r, peer), act,
                                               "float32",
                                               tag=f"pipe{d}/F{m}",
                                               peer=peer))
                else:
                    if s < spec.pp - 1:
                        peer = (s + 1) * B + local
                        sched[r].append(CommOp("recv", (peer, r), act,
                                               "float32",
                                               tag=f"pipe{d}/B{m}",
                                               peer=peer))
                    sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                             "bwd")
                    if s > 0:
                        peer = (s - 1) * B + local
                        sched[r].append(CommOp("send", (r, peer), act,
                                               "float32",
                                               tag=f"pipe{d}/B{m}",
                                               peer=peer))
    elif spec.pipeline_mode == "spmd":
        # every rank runs the SAME scanned program: per-micro-batch
        # compute collectives, then the tick-loop ppermute hops and the
        # output-collect psums (parallel/pipeline.py's comm_tag sites)
        for r in range(spec.world):
            for m in range(spec.num_micro_batches):
                sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                         "fwd")
                sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                         "bwd")
            g = pp_group(r)
            for kind, tag in spmd_hop_schedule(spec.num_micro_batches,
                                               spec.pp):
                red = "sum" if kind == "all_reduce" else ""
                sched[r].append(CommOp(kind, g, act, "float32",
                                       reduction=red, tag=tag))
    else:
        for r in range(spec.world):
            for m in range(spec.num_micro_batches):
                sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                         "fwd")
                sched[r] += _compute_ops(spec, r, cp_group, tp_group,
                                         "bwd")

    # (3) gradient sync + updated-param gather + scalar fetches
    for r in range(spec.world):
        emit_protos(r, tail)

    # (4) mid-run hot-switch repack
    if spec.switch is not None:
        for r, ops in _switch_ops(spec, dp_group).items():
            sched[r] += ops
    return sched


# ---------------------------------------------------------------------------
# verification
# ---------------------------------------------------------------------------


def _fmt_window(ops: List[CommOp], center: int, radius: int = 2
                ) -> List[str]:
    lines = []
    lo = max(0, center - radius)
    hi = min(len(ops), center + radius + 1)
    for i in range(lo, hi):
        mark = ">" if i == center else " "
        lines.append(f"{mark} {i:3d}  {ops[i].describe()}")
    if center >= len(ops):
        lines.append(f"> {center:3d}  <end of schedule>")
    return lines


def _is_switch(op: CommOp) -> bool:
    return op.tag.startswith("switch/")


def _verify_p2p(schedules: Dict[int, List[CommOp]], switch: bool
                ) -> List[ScheduleViolation]:
    """Per-channel multiset pairing: sends from ``src`` to ``dst`` must
    equal recvs on ``dst`` from ``src`` by (tag, payload, dtype).
    ``switch=True`` checks the repack plane (its own rule)."""
    chans: Dict[Tuple[int, int], Dict[str, List[Tuple[int, CommOp]]]] = {}
    for r, ops in schedules.items():
        for i, o in enumerate(ops):
            if o.kind not in P2P_KINDS or _is_switch(o) != switch:
                continue
            ch = (r, o.peer) if o.kind == "send" else (o.peer, r)
            side = chans.setdefault(ch, {"send": [], "recv": []})
            side[o.kind].append((i, o))
    rule = RULE_SWITCH if switch else RULE_UNPAIRED
    out: List[ScheduleViolation] = []
    for (src, dst), side in sorted(chans.items()):
        key = lambda io: (io[1].tag, io[1].payload_bytes, io[1].dtype)
        sends = Counter(key(io) for io in side["send"])
        recvs = Counter(key(io) for io in side["recv"])
        if sends == recvs:
            continue
        extra_s = sends - recvs
        extra_r = recvs - sends
        parts = []
        for k in list(extra_s):
            parts.append(f"send {k[0]} ({k[1]}B {k[2]}) x{extra_s[k]} "
                         f"never received by rank {dst}")
        for k in list(extra_r):
            parts.append(f"recv {k[0]} ({k[1]}B {k[2]}) x{extra_r[k]} "
                         f"never sent by rank {src}")
        sub = {}
        for r, lst in ((src, side["send"]), (dst, side["recv"])):
            idx = lst[0][0] if lst else 0
            sub[r] = _fmt_window(schedules[r], idx)
        noun = "switch repack" if switch else "p2p"
        out.append(ScheduleViolation(
            rule=rule, subject=f"{'switch:' if switch else ''}"
                               f"{src}->{dst}",
            message=f"{noun} channel rank {src} -> rank {dst} diverges: "
                    + "; ".join(parts)
                    + (" — the unmatched side blocks forever on real "
                       "hardware" if not switch else
                       " — the resize leaves stale or missing shards"),
            ranks=(src, dst), subtrace=sub))
    return out


def _verify_collectives(schedules: Dict[int, List[CommOp]]
                        ) -> List[ScheduleViolation]:
    """Positional per-group alignment: project each rank's schedule to
    the ops it issues on each group; members of a group must agree at
    every position on kind (order), group tuple (membership) and
    payload/dtype/reduction (payload)."""
    streams: Dict[Tuple[int, ...], Dict[int, List[Tuple[int, CommOp]]]] = {}
    colls: Dict[int, List[Tuple[int, CommOp]]] = {}
    for r, ops in schedules.items():
        mine = [(i, o) for i, o in enumerate(ops)
                if o.kind in COLLECTIVE_KINDS and len(o.group) > 1]
        colls[r] = mine
        for i, o in mine:
            streams.setdefault(o.group, {}).setdefault(r, []).append((i, o))
    cands: List[Tuple[int, ScheduleViolation]] = []
    for G in sorted(streams, key=lambda g: (min(g), len(g))):
        per_rank = streams[G]
        broke = False
        for r in per_rank:
            if r not in G:
                i, o = per_rank[r][0]
                cands.append((i, ScheduleViolation(
                    rule=RULE_GROUP, subject=f"group{G}",
                    message=f"rank {r} issues {o.kind} ({o.tag}) on "
                            f"group {G} it is not a member of",
                    ranks=tuple(sorted(set(G) | {r})),
                    subtrace={r: _fmt_window(schedules[r], i)})))
                broke = True
        if broke:
            continue
        maxlen = max(len(v) for v in per_rank.values())
        for pos in range(maxlen):
            at = {r: (per_rank[r][pos] if pos < len(per_rank.get(r, ()))
                      else None) for r in G}
            present = {r: io for r, io in at.items() if io is not None}
            if not present:
                continue
            ref_r = min(present)
            ref_i, ref = present[ref_r]
            missing = [r for r in G if at.get(r) is None]
            if missing:
                r = missing[0]
                # same-tag op under a DIFFERENT group on the straggler:
                # a membership divergence, not a count divergence
                alt = next(((i, o) for i, o in colls.get(r, ())
                            if o.tag == ref.tag and o.group != G), None)
                sub = {ref_r: _fmt_window(schedules[ref_r], ref_i)}
                if alt is not None:
                    ai, ao = alt
                    sub[r] = _fmt_window(schedules[r], ai)
                    cands.append((ref_i, ScheduleViolation(
                        rule=RULE_GROUP, subject=f"{ref.tag}@{pos}",
                        message=f"group mismatch on {ref.kind} "
                                f"({ref.tag}): rank {ref_r} uses group "
                                f"{G}, rank {r} uses group {ao.group} — "
                                f"each side waits for members that "
                                f"never arrive",
                        ranks=(ref_r, r), subtrace=sub)))
                else:
                    sub[r] = _fmt_window(schedules[r],
                                         len(schedules[r]))
                    cands.append((ref_i, ScheduleViolation(
                        rule=RULE_ORDER, subject=f"{ref.tag}@{pos}",
                        message=f"order mismatch on group {G}: rank "
                                f"{ref_r} issues collective #{pos} "
                                f"({ref.kind} {ref.tag}) but rank {r} "
                                f"issues only {len(per_rank.get(r, ()))} "
                                f"collective(s) on this group — rank "
                                f"{ref_r} blocks forever",
                        ranks=(ref_r, r), subtrace=sub)))
                break
            kinds = {o.kind for _, o in present.values()}
            if len(kinds) > 1:
                bad = next(r for r in sorted(present)
                           if present[r][1].kind != ref.kind)
                bi, bo = present[bad]
                # a kind divergence where one side issues the other's
                # tag under a DIFFERENT group is a membership re-route
                # (group skew shifts the whole stream), not an order bug
                regroup = None
                for (ra, oa), (rb, ob) in (((ref_r, ref), (bad, bo)),
                                           ((bad, bo), (ref_r, ref))):
                    alt = next(((i, o) for i, o in colls.get(rb, ())
                                if o.tag == oa.tag and o.group != G),
                               None)
                    if alt is not None:
                        regroup = (ra, oa, rb, alt)
                        break
                if regroup is not None:
                    ra, oa, rb, (ai, ao) = regroup
                    cands.append((ref_i, ScheduleViolation(
                        rule=RULE_GROUP, subject=f"{oa.tag}@{pos}",
                        message=f"group mismatch on {oa.kind} "
                                f"({oa.tag}): rank {ra} uses group "
                                f"{oa.group}, rank {rb} uses group "
                                f"{ao.group} — each side waits for "
                                f"members that never arrive",
                        ranks=(ref_r, bad),
                        subtrace={ref_r: _fmt_window(schedules[ref_r],
                                                     ref_i),
                                  bad: _fmt_window(schedules[bad],
                                                   bi)})))
                    break
                cands.append((ref_i, ScheduleViolation(
                    rule=RULE_ORDER, subject=f"{ref.tag}@{pos}",
                    message=f"order mismatch on group {G} at position "
                            f"{pos}: rank {ref_r} issues {ref.kind} "
                            f"({ref.tag}) while rank {bad} issues "
                            f"{bo.kind} ({bo.tag}) — mismatched "
                            f"collective kinds rendezvous never "
                            f"completes",
                    ranks=(ref_r, bad),
                    subtrace={ref_r: _fmt_window(schedules[ref_r], ref_i),
                              bad: _fmt_window(schedules[bad], bi)})))
                break
            payloads = {(o.payload_bytes, o.dtype, o.reduction)
                        for _, o in present.values()}
            if len(payloads) > 1:
                bad = next(r for r in sorted(present)
                           if (present[r][1].payload_bytes,
                               present[r][1].dtype,
                               present[r][1].reduction)
                           != (ref.payload_bytes, ref.dtype,
                               ref.reduction))
                bi, bo = present[bad]
                cands.append((ref_i, ScheduleViolation(
                    rule=RULE_PAYLOAD, subject=f"{ref.tag}@{pos}",
                    message=f"payload mismatch on {ref.kind} ({ref.tag},"
                            f" group {G}): rank {ref_r} contributes "
                            f"{ref.payload_bytes}B {ref.dtype}"
                            f"{('/' + ref.reduction) if ref.reduction else ''}"
                            f" but rank {bad} contributes "
                            f"{bo.payload_bytes}B {bo.dtype}"
                            f"{('/' + bo.reduction) if bo.reduction else ''}"
                            f" — shape/dtype disagreement hangs or "
                            f"corrupts the exchange",
                    ranks=(ref_r, bad),
                    subtrace={ref_r: _fmt_window(schedules[ref_r], ref_i),
                              bad: _fmt_window(schedules[bad], bi)})))
                break
    cands.sort(key=lambda c: c[0])
    return [v for _, v in cands]


def _suppress_cascades(violations: List[ScheduleViolation]
                       ) -> List[ScheduleViolation]:
    """One violation per implicated rank set: a single divergent rank
    breaks every group it sits in; only the earliest report survives."""
    out: List[ScheduleViolation] = []
    poisoned: set = set()
    for v in violations:
        if poisoned & set(v.ranks):
            continue
        poisoned |= set(v.ranks)
        out.append(v)
    return out


def _find_deadlock(schedules: Dict[int, List[CommOp]]
                   ) -> List[ScheduleViolation]:
    """Simulate the schedules under rendezvous collectives, buffered
    (non-blocking) sends and blocking recvs — the semantics of XLA's
    async dispatch + the MPMD controller's eager ``device_put``.  A
    stall is a wait-for cycle over pipeline stages x collectives; the
    cycle (or stall set) is reported with each stuck rank's subtrace."""
    pc = {r: 0 for r in schedules}
    chans: Dict[Tuple[int, int], deque] = {}
    ranks = sorted(schedules)

    def done(r):
        return pc[r] >= len(schedules[r])

    while True:
        progressed = False
        for r in ranks:
            while not done(r):
                o = schedules[r][pc[r]]
                if o.kind == "send":
                    chans.setdefault(o.group, deque()).append(o)
                    pc[r] += 1
                    progressed = True
                    continue
                if o.kind == "copy":
                    pc[r] += 1
                    progressed = True
                    continue
                if o.kind == "recv":
                    q = chans.get(o.group)
                    if q:
                        q.popleft()
                        pc[r] += 1
                        progressed = True
                        continue
                    break
                # collective: rendezvous — every member's head op must
                # be the matching (kind, group) op
                heads = {}
                for s in o.group:
                    if done(s):
                        heads = None
                        break
                    ho = schedules[s][pc[s]]
                    if ho.kind != o.kind or ho.group != o.group:
                        heads = None
                        break
                    heads[s] = ho
                if heads is None:
                    break
                for s in o.group:
                    pc[s] += 1
                progressed = True
        if all(done(r) for r in ranks):
            return []
        if not progressed:
            break

    # stalled: build the wait-for graph and pull out a cycle
    stuck = [r for r in ranks if not done(r)]
    waits: Dict[int, List[int]] = {}
    for r in stuck:
        o = schedules[r][pc[r]]
        if o.kind == "recv":
            waits[r] = [o.peer]
        elif o.kind in COLLECTIVE_KINDS:
            waits[r] = [s for s in o.group if s != r and
                        (done(s) or schedules[s][pc[s]].kind != o.kind
                         or schedules[s][pc[s]].group != o.group)]
        else:
            waits[r] = []
    cycle = _find_cycle(waits)
    show = cycle or stuck[:6]
    sub = {r: _fmt_window(schedules[r], pc[r]) for r in show}
    arrows = " -> ".join(str(r) for r in (cycle + [cycle[0]])) \
        if cycle else ", ".join(str(r) for r in show)
    kindof = "wait-for cycle" if cycle else "stall"
    return [ScheduleViolation(
        rule=RULE_DEADLOCK, subject=f"deadlock:{arrows}",
        message=f"schedules deadlock: {kindof} over ranks {arrows} — "
                f"each rank's next operation waits on a rank that is "
                f"itself blocked ({len(stuck)} rank(s) stuck, "
                f"{sum(len(schedules[r]) - pc[r] for r in stuck)} "
                f"op(s) unexecuted)",
        ranks=tuple(show), subtrace=sub)]


def _find_cycle(waits: Dict[int, List[int]]) -> List[int]:
    color: Dict[int, int] = {}
    stack: List[int] = []

    def dfs(u) -> Optional[List[int]]:
        color[u] = 1
        stack.append(u)
        for v in waits.get(u, ()):
            if color.get(v, 0) == 1:
                return stack[stack.index(v):]
            if color.get(v, 0) == 0:
                c = dfs(v)
                if c:
                    return c
        color[u] = 2
        stack.pop()
        return None

    for u in list(waits):
        if color.get(u, 0) == 0:
            c = dfs(u)
            if c:
                return c
    return []


def verify_schedules(schedules: Dict[int, List[CommOp]]
                     ) -> List[ScheduleViolation]:
    """Run all cross-rank checks.  Pairwise consistency first; the
    deadlock simulation only over schedules the pairwise checks pass
    (any divergence already implies a hang — see module docstring)."""
    if not schedules:
        return []
    v: List[ScheduleViolation] = []
    v += _verify_p2p(schedules, switch=False)
    v += _verify_p2p(schedules, switch=True)
    v += _verify_collectives(schedules)
    v = _suppress_cascades(v)
    if not v:
        v += _find_deadlock(schedules)
    return v


# ---------------------------------------------------------------------------
# context plumbing (analysis gate)
# ---------------------------------------------------------------------------


def context_schedules(ctx) -> Dict[int, List[CommOp]]:
    """Extract (and memoize on the context) the per-rank schedules for
    one analyzed executable; ``{}`` when the registration makes no
    multi-rank claim."""
    cached = getattr(ctx, "_rank_schedules", None)
    if cached is not None:
        return cached
    spec = spec_from_meta(ctx.meta, ctx.mesh_axes)
    sched = extract_schedules(spec) if spec is not None else {}
    try:
        ctx._rank_schedules = sched
    except Exception:
        pass
    return sched


def verify_context(ctx) -> List[ScheduleViolation]:
    """Verify the context's schedules ONCE (memoized — the six schedule
    rules share one replay, like the lifecycle rules share one)."""
    cached = getattr(ctx, "_schedule_violations", None)
    if cached is not None:
        return cached
    sched = context_schedules(ctx)
    violations = verify_schedules(sched) if sched else []
    try:
        ctx._schedule_violations = violations
    except Exception:
        pass
    return violations


def schedule_summary(ctx) -> Dict[str, Any]:
    """The per-executable ``schedule`` meta/baseline section: rank
    count, op inventory by kind, plane sizes, violation verdict, and
    the rule vocabulary available at freeze time (the gate fails when a
    pinned rule later vanishes from the registry)."""
    sched = context_schedules(ctx)
    violations = verify_context(ctx)
    kinds = Counter(o.kind for ops in sched.values() for o in ops)
    n_coll = sum(c for k, c in kinds.items() if k in COLLECTIVE_KINDS)
    n_p2p = sum(c for k, c in kinds.items() if k in P2P_KINDS)
    n_switch = sum(1 for ops in sched.values() for o in ops
                   if _is_switch(o))
    return {
        "ranks": len(sched),
        "ops": int(sum(kinds.values())),
        "kinds": {k: int(v) for k, v in sorted(kinds.items())},
        "collectives": int(n_coll),
        "p2p": int(n_p2p),
        "switch": int(n_switch),
        "violations": len(violations),
        "violation_rules": sorted({v.rule for v in violations}),
        "rules_available": sorted(SCHEDULE_RULES),
    }


# ---------------------------------------------------------------------------
# strategy grid + seeded-bug corpus (the tier-1 schedule gate)
# ---------------------------------------------------------------------------


def strategy_grid() -> Iterator[Tuple[str, ProgramSpec]]:
    """The clean sweep: dp x tp x pp x cp layouts x zero in {0, 2, 3}
    x {SPMD-1F1B, MPMD} pipeline modes x with/without a mid-run dp
    resize switch.  Every spec must verify with ZERO violations."""
    shapes = [(2, 1, 1, 1), (4, 2, 1, 1), (2, 2, 1, 2), (1, 2, 2, 2),
              (2, 1, 2, 1), (2, 2, 2, 1)]
    for dp, tp, pp, cp in shapes:
        for zero in (0, 2, 3):
            flat = zero >= 2
            modes = ["spmd", "mpmd"] if pp > 1 else ["none"]
            for mode in modes:
                for with_switch in (False, True):
                    if with_switch and dp <= 1:
                        continue      # a dp resize needs dp > 1
                    per_pipe = None
                    if mode == "mpmd" and dp > 1:
                        # Malleus apportionment: uneven per-pipe counts
                        per_pipe = tuple([3] + [1] * (dp - 1))
                    spec = ProgramSpec(
                        dp=dp, tp=tp, pp=pp, cp=cp, zero=zero, flat=flat,
                        transport="int8" if zero >= 2 else "fp32",
                        pipeline_mode=mode, per_pipe_micro=per_pipe,
                        switch=({"numel": 1 << 14, "itemsize": 4,
                                 "new_dp": max(1, dp // 2)}
                                if with_switch else None))
                    label = (f"dp{dp}_tp{tp}_pp{pp}_cp{cp}_z{zero}"
                             f"_{mode}{'_switch' if with_switch else ''}")
                    yield label, spec


def _reference_spec() -> ProgramSpec:
    """The corpus substrate: 8 ranks, pp2 x dp2 x tp2, ZeRO-3 flat,
    MPMD 1F1B with uneven per-pipe micro-batches and a mid-run dp
    resize — every op plane (front gathers, tp collectives, pipeline
    p2p, grad tail, switch repack) is populated so each rule has
    something to catch."""
    return ProgramSpec(dp=2, tp=2, pp=2, cp=1, zero=3, flat=True,
                       transport="fp32", pipeline_mode="mpmd",
                       per_pipe_micro=(3, 1),
                       switch={"numel": 1 << 14, "itemsize": 4,
                               "new_dp": 1})


def _clone(schedules: Dict[int, List[CommOp]]) -> Dict[int, List[CommOp]]:
    return {r: list(ops) for r, ops in schedules.items()}


def seeded_bug_corpus() -> List[Dict[str, Any]]:
    """>= 6 injected cross-rank divergences, one per rule.  Each entry's
    mutated schedules must be flagged by EXACTLY its rule (asserted by
    the vacuity meta-test and ``test_schedule_gate_grid_and_corpus``)."""
    base = extract_schedules(_reference_spec())
    corpus: List[Dict[str, Any]] = []

    def _mut(name, rule, note, fn):
        sch = _clone(base)
        fn(sch)
        corpus.append({"name": name, "rule": rule, "note": note,
                       "schedules": sch})

    def order_swap(sch):
        # swap two adjacent same-group collectives of different kinds
        # on one rank: positional kind divergence for its group peers
        for r in sorted(sch):
            ops = sch[r]
            for i in range(len(ops) - 1):
                a, b = ops[i], ops[i + 1]
                if (a.kind in COLLECTIVE_KINDS and b.kind in
                        COLLECTIVE_KINDS and a.group == b.group
                        and len(a.group) > 1 and a.kind != b.kind):
                    ops[i], ops[i + 1] = b, a
                    return
        raise AssertionError("no adjacent swap site in reference spec")

    def group_skew(sch):
        # one rank re-routes a dp collective onto its tp group, same
        # tag: membership divergence (each side waits forever)
        for r in sorted(sch):
            groups = {o.group for o in sch[r]
                      if o.kind in COLLECTIVE_KINDS and len(o.group) > 1}
            for i, o in enumerate(sch[r]):
                if o.kind not in COLLECTIVE_KINDS or len(o.group) <= 1:
                    continue
                alt = next((g for g in groups
                            if g != o.group and r in g), None)
                if alt is not None:
                    sch[r][i] = dataclasses.replace(o, group=alt)
                    return
        raise AssertionError("no group-skew site in reference spec")

    def payload_skew(sch):
        # EQuARX-style divergence: one rank runs a quantized collective
        # its peers run in full precision — dtype disagreement
        for r in sorted(sch):
            for i, o in enumerate(sch[r]):
                if (o.kind in COLLECTIVE_KINDS and len(o.group) > 1
                        and o.dtype == "float32"):
                    sch[r][i] = dataclasses.replace(
                        o, dtype="bfloat16",
                        payload_bytes=o.payload_bytes // 2)
                    return
        raise AssertionError("no payload-skew site in reference spec")

    def missing_recv(sch):
        for r in sorted(sch):
            for i, o in enumerate(sch[r]):
                if o.kind == "recv" and not _is_switch(o):
                    del sch[r][i]
                    return
        raise AssertionError("no pipeline recv in reference spec")

    def recv_inversion(sch):
        # a stage-0 rank waits for its backward grad BEFORE sending its
        # first forward: recv/recv wait-for cycle across the stage pair
        for r in sorted(sch):
            ops = sch[r]
            si = next((i for i, o in enumerate(ops)
                       if o.kind == "send" and not _is_switch(o)), None)
            ri = next((i for i, o in enumerate(ops)
                       if o.kind == "recv" and not _is_switch(o)), None)
            if si is not None and ri is not None and si < ri:
                op = ops.pop(ri)
                ops.insert(si, op)
                return
        raise AssertionError("no recv-inversion site in reference spec")

    def repack_skew(sch):
        # the receiving side of one repack transfer expects a different
        # source rank than the plan's sender
        for r in sorted(sch):
            for i, o in enumerate(sch[r]):
                if o.kind == "recv" and _is_switch(o):
                    other = next(s for s in sorted(sch)
                                 if s not in (r, o.peer))
                    sch[r][i] = dataclasses.replace(
                        o, peer=other, group=(other, r))
                    return
        raise AssertionError("no switch recv in reference spec")

    _mut("order_swap", RULE_ORDER,
         "adjacent collective swap on one rank", order_swap)
    _mut("group_skew", RULE_GROUP,
         "dp collective re-routed onto the tp group", group_skew)
    _mut("payload_skew", RULE_PAYLOAD,
         "one rank quantizes a collective its peers run fp32",
         payload_skew)
    _mut("missing_recv", RULE_UNPAIRED,
         "a pipeline recv dropped from one stage", missing_recv)
    _mut("recv_inversion", RULE_DEADLOCK,
         "stage waits for backward grad before first forward send",
         recv_inversion)
    _mut("repack_skew", RULE_SWITCH,
         "repack recv expects the wrong source rank", repack_skew)
    return corpus
