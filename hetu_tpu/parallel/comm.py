"""Collective communication primitives.

TPU-native equivalent of the reference's communication backend
(``hetu/impl/communication/comm_group.h:27-144`` virtual collective set and
the graph-level comm ops in ``hetu/graph/ops/Communication.h``).  Instead of
NCCL groups on dedicated CUDA streams, collectives here are XLA ops emitted
inside ``shard_map``/pjit over a named mesh axis; XLA schedules them onto
ICI/DCN and overlaps with compute (async collectives).

Mapping table (reference -> ours):

==============================  =====================================
``AllReduce``                   :func:`all_reduce` (``lax.psum``)
``AllGather(gather_dim)``       :func:`all_gather`
``ReduceScatter(scatter_dim)``  :func:`reduce_scatter` (``lax.psum_scatter``)
``AlltoAll``                    :func:`all_to_all`
``Broadcast/Reduce``            :func:`broadcast` / :func:`reduce`
``Send/Recv/BatchedISendIRecv`` :func:`ppermute` rings/sets
``AllReduceCoalesce``           :func:`all_reduce_coalesced` (fused
                                size-capped buckets, optional EQuARX
                                bf16/int8 quantized transport)
``Barrier``                     :func:`barrier`
==============================  =====================================

All functions must be called *inside* a ``shard_map``-ed function with the
named axis in scope (the usual jax idiom); the graph layer and the parallel
nn layers arrange that.
"""
from __future__ import annotations

import contextlib
from typing import (Dict, List, Mapping, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def shard_map(f, mesh, in_specs, out_specs, check_rep: bool = False,
              axis_names=None):
    """``jax.shard_map`` under the argument names this package grew up
    with: ``check_rep`` is jax's ``check_vma``; ``axis_names``, when
    given, restricts manual mode to those mesh axes (partial-manual)."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep, **kw)


def _operand_bytes(x) -> int:
    return int(np.prod(np.shape(x))) * np.dtype(jnp.result_type(x)).itemsize


def all_reduce(x: jax.Array, axis: str, op: str = "sum") -> jax.Array:
    if _STATS_STACK:
        _record("all_reduce", _operand_bytes(x), jnp.result_type(x),
                axis_size(axis), axis)
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op {op!r}")


def all_gather(x: jax.Array, axis: str, gather_dim: int = 0,
               tiled: bool = True) -> jax.Array:
    """Gather shards along ``gather_dim`` (reference AllGather, comm_group.h:95)."""
    if _STATS_STACK:
        n = axis_size(axis)
        _record("all_gather", _operand_bytes(x) * n, jnp.result_type(x),
                n, axis)
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: str, scatter_dim: int = 0) -> jax.Array:
    """Sum-reduce then scatter along ``scatter_dim`` (comm_group.h:101)."""
    if _STATS_STACK:
        _record("reduce_scatter", _operand_bytes(x), jnp.result_type(x),
                axis_size(axis), axis)
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def all_to_all(x: jax.Array, axis: str, split_dim: int,
               concat_dim: int, tiled: bool = True) -> jax.Array:
    """AlltoAll (comm_group.h:77) — the EP/MoE dispatch primitive."""
    if _STATS_STACK:
        _record("all_to_all", _operand_bytes(x), jnp.result_type(x),
                axis_size(axis), axis)
    return lax.all_to_all(x, axis, split_axis=split_dim,
                          concat_axis=concat_dim, tiled=tiled)


def broadcast(x: jax.Array, axis: str, root: int = 0) -> jax.Array:
    """Broadcast from ``root`` along ``axis`` (comm_group.h:63)."""
    idx = lax.axis_index(axis)
    n = axis_size(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def reduce(x: jax.Array, axis: str, root: int = 0) -> jax.Array:
    """Reduce to ``root`` (others receive zeros) (comm_group.h:85)."""
    s = lax.psum(x, axis)
    idx = lax.axis_index(axis)
    return jnp.where(idx == root, s, jnp.zeros_like(s))


def ppermute(x: jax.Array, axis: str,
             perm: Sequence[Tuple[int, int]]) -> jax.Array:
    """Point-to-point permutation — the reference's ``BatchedISendIRecv``
    (comm_group.h:120): an arbitrary set of (src, dst) pairs exchanged as one
    grouped transfer."""
    return lax.ppermute(x, axis, perm)


def ring_shift(x: jax.Array, axis: str, shift: int = 1) -> jax.Array:
    """Shift shards around the ring formed by ``axis`` — the KV-ring exchange
    of ring attention (``ops/ParallelAttention.cc:611``)."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Static size of a named axis (jax<0.6 lacks lax.axis_size; the
    psum-of-1 constant folds to the axis size at trace time)."""
    if hasattr(lax, "axis_size"):
        return int(lax.axis_size(axis))
    return int(lax.psum(1, axis))


def barrier(coordinator=None, name: str = "default",
            world_size: Optional[int] = None,
            timeout: float = 60.0) -> None:
    """Host-level barrier (reference gRPC Barrier, heturpc.proto:44).

    Within a single jit program XLA collectives are self-synchronizing;
    this is only for host-side coordination between programs.

    Single-host: a tiny device all-reduce (drains in-flight programs on
    all local devices).  Multi-host: pass the process's
    ``rpc.CoordinatorClient`` as ``coordinator`` — the barrier then goes
    through its cross-host rendezvous (``CoordinatorClient.barrier``),
    the way the reference routes Barrier through heturpc.  When a client
    has been registered via :func:`set_coordinator` it is used
    automatically.
    """
    coord = coordinator if coordinator is not None else _COORDINATOR[0]
    if coord is not None:
        # an unresolvable world size would make the server release the
        # barrier immediately (n=0) — a silent no-op; fail loudly instead
        ws = world_size if world_size is not None \
            else getattr(coord, "world_size", None)
        if not ws:
            raise ValueError(
                "coordinator barrier needs a world_size (pass it here or "
                "start the CoordinatorServer with world_size=N)")
        coord.barrier(name=name, world_size=ws, timeout=timeout)
        return
    # Tiny all-reduce over all devices, blocking until complete.
    n = jax.device_count()
    if n > 1:
        x = jnp.ones((n,))
        jax.block_until_ready(
            jax.pmap(lambda v: lax.psum(v, "i"), axis_name="i")(x))


def partial_reduce(x: jax.Array, axis: str, participating,
                   op: str = "mean") -> jax.Array:
    """Partial (asynchronous-DP) reduce — v1's ``PartialReduce``
    (``v1/python/hetu/preduce.py:8``): only the *ready* subset of ranks
    contributes; everyone receives the subset's mean (or sum).

    ``participating`` is a per-rank scalar (bool/0-1, may be traced):
    unlike the reference, which forms an ad-hoc NCCL group from the ranks
    that arrived within a time window, XLA groups are static — so the
    subset is expressed as a mask and lowered to one full-axis ``psum``
    of masked contributions plus a participant count.  Ranks outside the
    subset still receive the reduced value (the v1 semantics: stale
    workers adopt the fresh average on their next partial round).
    """
    p = jnp.asarray(participating, x.dtype)
    total = lax.psum(x * p, axis)
    if op == "sum":
        return total
    if op == "mean":
        count = lax.psum(p, axis)
        return total / jnp.maximum(count, 1)
    raise ValueError(f"unsupported partial_reduce op {op!r}")


_COORDINATOR: list = [None]


def set_coordinator(client) -> None:
    """Register the process's CoordinatorClient so :func:`barrier` (and
    other host-level sync points) route through the cross-host
    coordinator instead of the local-device fallback."""
    _COORDINATOR[0] = client


# -- split collectives (hetero ZeRO, ops/Communication.h:655-845) -----------
#
# The reference defines SplitAllGather/SplitAllReduce/SplitReduceScatter that
# run a collective independently over *sub-groups* of unequal sizes (needed
# when hetero pipelines give parameter shards different replication factors).
# ``groups`` is a static partition of the axis indices, e.g. [[0,1,2],
# [3,4,5,6,7]] — subgroup sizes may differ.  Without ``groups`` the whole
# axis is one group (the homogeneous case).
#
# XLA's AllReduce takes unequal replica groups natively (axis_index_groups);
# AllGather/ReduceScatter are shape-uniform in SPMD, so the unequal cases
# pad to the largest subgroup: split_all_gather returns
# max_group_size*shard rows per rank (rows beyond the own group's
# contribution are zero), split_reduce_scatter returns L//min(group sizes)
# rows (rows beyond the own rank's L//group_size chunk are zero).  The
# per-rank valid extents are static, derivable from ``groups`` — the same
# contract as the reference's per-group tensor lists.


def _norm_groups(groups, n: int):
    """Validate + normalize a static group partition of range(n)."""
    gs = [list(map(int, g)) for g in groups]
    flat = sorted(i for g in gs for i in g)
    if flat != list(range(n)):
        raise ValueError(
            f"groups {gs} must partition the {n} axis indices exactly")
    return gs


def _group_tables(groups, n: int):
    """(group_id [n], members [n_groups, max_g] padded with -1,
    rank_in_group [n], group_size [n]) as numpy arrays."""
    import numpy as np
    gid = np.zeros(n, np.int32)
    rin = np.zeros(n, np.int32)
    gsz = np.zeros(n, np.int32)
    max_g = max(len(g) for g in groups)
    members = np.full((len(groups), max_g), -1, np.int32)
    for g_i, g in enumerate(groups):
        for r, dev in enumerate(g):
            gid[dev] = g_i
            rin[dev] = r
            gsz[dev] = len(g)
            members[g_i, r] = dev
    return gid, members, rin, gsz


def split_all_reduce(x: jax.Array, subgroup_axis: str,
                     groups: Optional[Sequence[Sequence[int]]] = None
                     ) -> jax.Array:
    """AllReduce within each (possibly unequal) subgroup
    (SplitAllReduceOp, ops/Communication.h:718)."""
    if groups is None:
        return lax.psum(x, subgroup_axis)
    n = axis_size(subgroup_axis)
    gs = _norm_groups(groups, n)
    return lax.psum(x, subgroup_axis,
                    axis_index_groups=[tuple(g) for g in gs])


def split_all_gather(x: jax.Array, subgroup_axis: str,
                     gather_dim: int = 0,
                     groups: Optional[Sequence[Sequence[int]]] = None
                     ) -> jax.Array:
    """AllGather within each subgroup (SplitAllGatherOp,
    ops/Communication.h:655).  With unequal ``groups`` the result is
    padded to max group size: shape[gather_dim] ==
    max_g * x.shape[gather_dim]; each rank's first
    own_group_size * shard rows are its group's concatenated shards, the
    rest zeros."""
    if groups is None:
        return lax.all_gather(x, subgroup_axis, axis=gather_dim, tiled=True)
    gather_dim = gather_dim % x.ndim
    n = axis_size(subgroup_axis)
    gs = _norm_groups(groups, n)
    sizes = {len(g) for g in gs}
    if len(sizes) == 1:
        return lax.all_gather(x, subgroup_axis, axis=gather_dim, tiled=True,
                              axis_index_groups=[tuple(g) for g in gs])
    gid_t, members_t, _, _ = _group_tables(gs, n)
    my = lax.axis_index(subgroup_axis)
    # full-axis gather, then select own group's members (padded to max_g)
    allx = lax.all_gather(x, subgroup_axis, axis=0, tiled=False)  # [n, ...]
    members = jnp.asarray(members_t)[jnp.asarray(gid_t)[my]]      # [max_g]
    picked = jnp.take(allx, jnp.maximum(members, 0), axis=0)
    mask_shape = [members.shape[0]] + [1] * (picked.ndim - 1)
    picked = jnp.where((members >= 0).reshape(mask_shape), picked, 0)
    # tile into gather_dim:  [max_g, ..., s, ...] -> [..., max_g*s, ...]
    picked = jnp.moveaxis(picked, 0, gather_dim)
    shape = list(x.shape)
    shape[gather_dim] = members.shape[0] * x.shape[gather_dim]
    return picked.reshape(shape)


# -- coalesced + quantized gradient collectives ------------------------------
#
# Reference AllReduceCoalesce (comm_group.h:27-144): per-tensor gradient
# allreduce leaves link bandwidth on the table, so same-dtype gradients are
# flattened into size-capped fused buckets and synced with ONE collective
# per bucket.  On top of the bucketing sits a quantized transport (EQuARX,
# PAPERS.md): the payload crosses the wire as bf16 or blockwise-absmax int8
# while the *reduction* accumulates in fp32, via the two-phase
#
#   quantize -> all_to_all (reduce-scatter exchange) -> dequantize ->
#   accumulate fp32 -> [mean] -> quantize -> all_gather -> dequantize
#
# so each element is quantized exactly twice regardless of group size and
# the reduction error stays bounded per absmax block.  fp32 transport uses
# a single psum per bucket, which is bit-identical to per-tensor psum
# (elementwise reduction over the same rank order).

GRAD_COMM_TRANSPORTS = ("fp32", "bf16", "int8")

#: default blockwise-absmax block for the int8 transport (elements/block;
#: scale sidecar overhead = 4 bytes per block)
INT8_BLOCK = 256


class Bucket(NamedTuple):
    """One fused bucket: same-dtype tensors flattened back to back."""
    keys: Tuple             # caller keys, flatten order
    shapes: Tuple           # original shapes, same order
    numels: Tuple[int, ...]
    dtype: str              # canonical numpy dtype name
    nbytes: int             # payload bytes (sum of tensor bytes)


class CommRecord(NamedTuple):
    kind: str               # all_reduce | reduce_scatter | all_gather | all_to_all
    payload_bytes: int      # logical payload size (global, pre-sharding)
    wire_bytes: float       # per-rank bytes on the wire (ring algorithm)
    dtype: str
    axis: str
    tag: str = ""           # attribution tag (ambient comm_tag scope)


class CommStats:
    """Trace-time collective accounting (bytes-on-wire bookkeeping).

    Collectives recorded while a :func:`comm_stats` scope is active
    correspond 1:1 to collective ops in the traced XLA program — tracing
    a jitted function (or ``.lower()``-ing it) under the scope counts
    exactly what the program will launch per step.
    """

    def __init__(self):
        self.records: List[CommRecord] = []

    @property
    def num_collectives(self) -> int:
        return len(self.records)

    @property
    def total_wire_bytes(self) -> float:
        return sum(r.wire_bytes for r in self.records)

    @property
    def total_payload_bytes(self) -> int:
        return sum(r.payload_bytes for r in self.records)

    def by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for r in self.records:
            out[r.kind] = out.get(r.kind, 0) + 1
        return out

    def summary(self) -> dict:
        return {"num_collectives": self.num_collectives,
                "wire_bytes_per_rank": round(self.total_wire_bytes, 1),
                "payload_bytes": self.total_payload_bytes,
                "by_kind": self.by_kind()}


_STATS_STACK: List[CommStats] = []
_TAG_STACK: List[str] = []


@contextlib.contextmanager
def comm_stats():
    """``with comm_stats() as s:`` — record collectives traced inside."""
    s = CommStats()
    _STATS_STACK.append(s)
    try:
        yield s
    finally:
        _STATS_STACK.remove(s)


@contextlib.contextmanager
def comm_tag(tag: str):
    """Attribute collectives emitted inside to ``tag``.

    Dual-plane tagging: the tag is (1) pushed onto the ambient stack so
    trace-time :class:`CommRecord` s carry it, and (2) entered as a jax
    ``named_scope`` so it lands on the eqn name-stack in the traced
    jaxpr — the static analyzer (``hetu_tpu/analysis``) reads it back
    from the program itself, with no side channel.
    """
    _TAG_STACK.append(tag)
    try:
        with jax.named_scope(tag):
            yield
    finally:
        _TAG_STACK.pop()


def current_comm_tag() -> str:
    return "/".join(_TAG_STACK)


def ring_wire_bytes(kind: str, payload_bytes: float, n: int) -> float:
    """Per-rank bytes sent over the wire by the ring algorithm for a
    collective moving ``payload_bytes`` across ``n`` ranks (the standard
    bandwidth-optimal accounting; ICI all-reduce = RS + AG)."""
    if n <= 1:
        return 0.0
    frac = (n - 1) / n
    if kind == "all_reduce":
        return 2.0 * payload_bytes * frac
    if kind in ("reduce_scatter", "all_gather", "all_to_all"):
        return payload_bytes * frac
    if kind == "ppermute":
        # one hop: every rank sends its full local payload once; a
        # K-hop chain (pipeline ticks, ring attention) is K records (or
        # one record with count=K), so totals come out as hops x payload
        return float(payload_bytes)
    raise ValueError(f"unknown collective kind {kind!r}")


def _record(kind: str, payload_bytes: int, dtype, n: int, axis: str) -> None:
    if not _STATS_STACK:
        return
    rec = CommRecord(kind, int(payload_bytes),
                     ring_wire_bytes(kind, payload_bytes, n),
                     np.dtype(dtype).name, axis, current_comm_tag())
    for s in _STATS_STACK:
        s.records.append(rec)


def plan_buckets(entries: Sequence[Tuple],
                 bucket_mb: float = 4.0) -> List[Bucket]:
    """Greedy size-capped bucketing of ``(key, shape, dtype)`` entries.

    Order-preserving within each dtype (gradients arrive roughly in
    reverse-layer order, so adjacent buckets stay adjacent in the
    backward schedule — the overlap-friendly property of the reference's
    AllReduceCoalesce grouping).  A tensor larger than the cap gets its
    own bucket.
    """
    cap = max(1, int(float(bucket_mb) * (1 << 20)))
    buckets: List[Bucket] = []
    open_idx: Dict[str, int] = {}   # dtype -> index into buckets
    for key, shape, dtype in entries:
        dt = np.dtype(dtype)
        numel = int(np.prod(shape)) if len(tuple(shape)) else 1
        nbytes = numel * dt.itemsize
        i = open_idx.get(dt.name)
        if i is not None and buckets[i].nbytes + nbytes <= cap:
            b = buckets[i]
            buckets[i] = Bucket(b.keys + (key,), b.shapes + (tuple(shape),),
                                b.numels + (numel,), b.dtype,
                                b.nbytes + nbytes)
        else:
            buckets.append(Bucket((key,), (tuple(shape),), (numel,),
                                  dt.name, nbytes))
            open_idx[dt.name] = len(buckets) - 1
    return buckets


def _normalize_tree(xs):
    """(items [(key, arr)], rebuild) for dict / list / tuple inputs."""
    if isinstance(xs, Mapping):
        items = list(xs.items())
        return items, (lambda vals: dict(zip([k for k, _ in items], vals)))
    items = list(enumerate(xs))
    return items, (lambda vals: list(vals))


def _flatten_bucket(bucket: Bucket, lookup) -> jax.Array:
    return jnp.concatenate([jnp.ravel(lookup[k]) for k in bucket.keys])


def _unflatten_bucket(flat: jax.Array, bucket: Bucket) -> List[jax.Array]:
    out, off = [], 0
    for shape, numel in zip(bucket.shapes, bucket.numels):
        out.append(lax.dynamic_slice_in_dim(flat, off, numel).reshape(shape))
        off += numel
    return out


def quantized_chunk(numel: int, n: int, block: int = INT8_BLOCK) -> int:
    """Per-rank chunk length for the two-phase quantized path: the padded
    flat buffer is ``n * chunk`` with ``chunk`` a block multiple, so int8
    absmax blocks never straddle rank boundaries."""
    per = -(-numel // n)             # ceil
    return -(-per // block) * block


def _quantize_rows(rows: jax.Array, block: int):
    """Blockwise int8 absmax quantize of ``[r, chunk]`` rows
    (chunk % block == 0, so blocks stay within rows).  Reuses the
    checkpoint-path quantizer (ops/quantization.py)."""
    from ..ops.quantization import quantize_int8   # lazy: avoid pkg cycle
    r, chunk = rows.shape
    q, scales = quantize_int8(rows, blocksize=block)
    return q.reshape(r, chunk), scales.reshape(r, chunk // block)


def _dequantize_rows(codes: jax.Array, scales: jax.Array,
                     block: int) -> jax.Array:
    from ..ops.quantization import dequantize_int8   # lazy: avoid pkg cycle
    return dequantize_int8(codes.reshape(-1), scales.reshape(-1),
                           codes.shape, blocksize=block)


def _axis_groups(groups, n):
    if groups is None:
        return None, n
    gs = _norm_groups(groups, n)
    sizes = {len(g) for g in gs}
    if len(sizes) != 1:
        raise ValueError(
            "quantized transports need equal-size subgroups (XLA "
            f"all_to_all/all_gather are shape-uniform); got {gs}. "
            "Use transport='fp32' for unequal groups.")
    return [tuple(g) for g in gs], sizes.pop()


def _qreduce_scatter_flat(flat: jax.Array, axis: str, op: str,
                          transport: str, block: int,
                          groups=None) -> jax.Array:
    """Phase 1 of the EQuARX two-phase reduction on a flat fp32 buffer:
    each rank ends up owning the fully-reduced (fp32-accumulated) chunk
    at its own rank offset.  Returns the ``[chunk]`` fp32 shard."""
    n_axis = axis_size(axis)
    idx_groups, n = _axis_groups(groups, n_axis)
    N = flat.shape[0]
    chunk = quantized_chunk(N, n, block)
    flat = jnp.pad(flat.astype(jnp.float32), (0, n * chunk - N))
    rows = flat.reshape(n, chunk)
    if transport == "bf16":
        payload = rows.astype(jnp.bfloat16)
        ex = lax.all_to_all(payload, axis, split_axis=0, concat_axis=0,
                            tiled=False, axis_index_groups=idx_groups)
        _record("all_to_all", n * chunk * 2, jnp.bfloat16, n, axis)
        acc = jnp.sum(ex.astype(jnp.float32), axis=0)
    elif transport == "int8":
        codes, scales = _quantize_rows(rows, block)
        exc = lax.all_to_all(codes, axis, split_axis=0, concat_axis=0,
                             tiled=False, axis_index_groups=idx_groups)
        _record("all_to_all", n * chunk, jnp.int8, n, axis)
        with comm_tag("scales"):
            exs = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                                 tiled=False, axis_index_groups=idx_groups)
            _record("all_to_all", n * (chunk // block) * 4, jnp.float32, n,
                    axis)
        acc = jnp.sum(_dequantize_rows(exc, exs, block), axis=0)
    else:
        raise ValueError(f"unknown quantized transport {transport!r}")
    if op == "mean":
        acc = acc / n
    elif op != "sum":
        raise ValueError(f"unsupported op {op!r} for quantized transport")
    return acc


def _qall_gather_flat(chunk_arr: jax.Array, axis: str, transport: str,
                      block: int, numel: int, groups=None) -> jax.Array:
    """Phase 2: broadcast each rank's reduced chunk through the quantized
    transport; returns the full flat fp32 buffer (length ``numel``)."""
    n_axis = axis_size(axis)
    idx_groups, n = _axis_groups(groups, n_axis)
    chunk = chunk_arr.shape[0]
    if transport == "bf16":
        g = lax.all_gather(chunk_arr.astype(jnp.bfloat16), axis,
                           tiled=False, axis_index_groups=idx_groups)
        _record("all_gather", n * chunk * 2, jnp.bfloat16, n, axis)
        full = g.astype(jnp.float32)
    elif transport == "int8":
        codes, scales = _quantize_rows(chunk_arr.reshape(1, chunk), block)
        gc = lax.all_gather(codes[0], axis, tiled=False,
                            axis_index_groups=idx_groups)
        _record("all_gather", n * chunk, jnp.int8, n, axis)
        with comm_tag("scales"):
            gs = lax.all_gather(scales[0], axis, tiled=False,
                                axis_index_groups=idx_groups)
            _record("all_gather", n * (chunk // block) * 4, jnp.float32, n,
                    axis)
        full = _dequantize_rows(gc, gs, block)
    else:
        raise ValueError(f"unknown quantized transport {transport!r}")
    return full.reshape(-1)[:numel]


def _reduce_flat(flat: jax.Array, axis: str, op: str, transport: str,
                 block: int, groups) -> jax.Array:
    """All-reduce one flat bucket through the selected transport."""
    n = axis_size(axis)
    # wire accounting: grouped collectives move data within each
    # subgroup only — record with the largest group's ring factor, not
    # the full axis's
    n_rec = n if groups is None else max(len(g) for g in groups)
    if transport == "fp32":
        _record("all_reduce", flat.shape[0] * np.dtype(flat.dtype).itemsize,
                flat.dtype, n_rec, axis)
        if groups is not None:
            red = split_all_reduce(flat, axis, groups)
            if op == "mean":
                red = red / _own_group_size(axis, groups, n)
            elif op != "sum":
                raise ValueError(f"unsupported coalesced op {op!r}")
            return red
        if op == "sum":
            return lax.psum(flat, axis)
        if op == "mean":
            return lax.pmean(flat, axis)
        raise ValueError(f"unsupported coalesced op {op!r}")
    orig_dtype = flat.dtype
    shard = _qreduce_scatter_flat(flat, axis, op, transport, block, groups)
    full = _qall_gather_flat(shard, axis, transport, block, flat.shape[0],
                             groups)
    return full.astype(orig_dtype)


def _own_group_size(axis: str, groups, n: int):
    gs = _norm_groups(groups, n)
    _gid, _members, _rin, gsz = _group_tables(gs, n)
    return jnp.asarray(gsz, jnp.float32)[lax.axis_index(axis)]


def all_reduce_coalesced(xs, axis: str, op: str = "sum",
                         bucket_mb: float = 4.0,
                         transport: str = "fp32",
                         block: int = INT8_BLOCK,
                         groups: Optional[Sequence[Sequence[int]]] = None):
    """Bucketed (optionally quantized) all-reduce of a gradient pytree.

    ``xs``: dict or list of arrays; returns the same structure.  Arrays
    are flattened into same-dtype buckets capped at ``bucket_mb`` MiB and
    reduced with ONE collective chain per bucket (reference
    AllReduceCoalesce, comm_group.h:27; EQuARX quantized transport).

    transport:
      - ``"fp32"`` — one ``psum`` per bucket; bit-identical to per-tensor
        ``psum`` (elementwise reduction, same rank order).
      - ``"bf16"`` — payload cast to bf16, fp32 accumulation (two-phase).
      - ``"int8"`` — blockwise-absmax int8 payload + fp32 scale sidecar,
        fp32 accumulation; each element quantized exactly twice.

    ``groups``: optional static subgroup partition (SplitAllReduce
    semantics).  fp32 supports unequal groups; quantized transports need
    equal-size groups.  Must be called inside shard_map with ``axis``.
    """
    if transport not in GRAD_COMM_TRANSPORTS:
        raise ValueError(f"transport must be one of {GRAD_COMM_TRANSPORTS}, "
                         f"got {transport!r}")
    items, rebuild = _normalize_tree(xs)
    lookup = dict(items)
    buckets = plan_buckets(
        [(k, np.shape(v), jnp.result_type(v)) for k, v in items], bucket_mb)
    out: Dict = {}
    for bi, b in enumerate(buckets):
        with comm_tag(f"grad_comm/bucket{bi}"):
            flat = _flatten_bucket(b, lookup)
            red = _reduce_flat(flat, axis, op, transport, block, groups)
        for k, arr in zip(b.keys, _unflatten_bucket(red, b)):
            out[k] = arr.astype(lookup[k].dtype)
    return rebuild([out[k] for k, _ in items])


class CoalescedLayout(NamedTuple):
    """Static layout of a reduce-scattered coalesced gradient set: one
    entry per bucket, enough to all-gather + unflatten later (the
    per-group tensor-list contract of the reference's coalesce ops)."""
    buckets: Tuple[Bucket, ...]
    chunks: Tuple[int, ...]      # per-bucket per-rank chunk length
    list_input: bool = False     # rebuild a list (not a dict) on gather
    groups: Optional[Tuple[Tuple[int, ...], ...]] = None  # split variant


def reduce_scatter_coalesced(xs, axis: str, op: str = "sum",
                             bucket_mb: float = 4.0,
                             transport: str = "fp32",
                             block: int = INT8_BLOCK):
    """Bucketed reduce-scatter: each rank ends up owning the reduced
    chunk of every bucket at its own rank offset (ZeRO grad sync,
    reference SplitReduceScatter under zero, Communication.h:583).

    Returns ``(chunks, layout)``: ``chunks[i]`` is this rank's fp32
    shard of bucket i; complete with :func:`all_gather_coalesced`.
    """
    if transport not in GRAD_COMM_TRANSPORTS:
        raise ValueError(f"transport must be one of {GRAD_COMM_TRANSPORTS}, "
                         f"got {transport!r}")
    items, _rebuild = _normalize_tree(xs)
    lookup = dict(items)
    buckets = plan_buckets(
        [(k, np.shape(v), jnp.result_type(v)) for k, v in items], bucket_mb)
    n = axis_size(axis)
    chunks, chunk_lens = [], []
    for bi, b in enumerate(buckets):
        with comm_tag(f"grad_comm/bucket{bi}"):
            flat = _flatten_bucket(b, lookup)
            chunk = quantized_chunk(flat.shape[0], n, block)
            if transport == "fp32":
                padded = jnp.pad(flat.astype(jnp.float32),
                                 (0, n * chunk - flat.shape[0]))
                _record("reduce_scatter",
                        padded.shape[0] * np.dtype(padded.dtype).itemsize,
                        padded.dtype, n, axis)
                shard = lax.psum_scatter(padded, axis, scatter_dimension=0,
                                         tiled=True)
                if op == "mean":
                    shard = shard / n
                elif op != "sum":
                    raise ValueError(f"unsupported coalesced op {op!r}")
            else:
                shard = _qreduce_scatter_flat(flat, axis, op, transport,
                                              block)
        chunks.append(shard)
        chunk_lens.append(chunk)
    return chunks, CoalescedLayout(tuple(buckets), tuple(chunk_lens),
                                   not isinstance(xs, Mapping))


def all_gather_coalesced(chunks, layout: CoalescedLayout, axis: str,
                         transport: str = "fp32",
                         block: int = INT8_BLOCK,
                         tag: str = "grad_comm"):
    """Inverse of :func:`reduce_scatter_coalesced`: gather every rank's
    chunks and unflatten back to the original container (dict keyed like
    the input mapping, or a list when the input was a sequence).

    The plain (non-quantized) path gathers in the BUCKET dtype, not the
    chunk dtype: casting the fp32 chunk before the collective is
    elementwise-identical to casting after, so a bf16 parameter set
    crosses the wire as bf16 — the ZeRO-2 updated-param all-gather rides
    the weight dtype instead of fp32 (half the gather bytes).  ``tag``
    names the attribution scope: the flat-optimizer path tags its param
    gather ``param_comm`` so byte accounting (and the
    grad-allgather-under-zero2 lint) can tell parameter traffic from
    gradient traffic."""
    if layout.groups is not None:
        # grouped shards are padded per-rank to the largest chunk; a
        # full-axis gather would interleave groups and padding into
        # garbage — fail loudly (per-rank valid extents are derivable
        # from layout.groups, the split_reduce_scatter contract)
        raise NotImplementedError(
            "all_gather_coalesced does not support grouped layouts "
            "(from split_reduce_scatter_coalesced); consume the shards "
            "with the per-group valid extents from layout.groups")
    n = axis_size(axis)
    out: Dict = {}
    for bi, (shard, b, chunk) in enumerate(zip(chunks, layout.buckets,
                                               layout.chunks)):
        numel = sum(b.numels)
        with comm_tag(f"{tag}/bucket{bi}"):
            if transport == "fp32":
                wire_dt = np.dtype(b.dtype)
                _record("all_gather", n * chunk * wire_dt.itemsize,
                        wire_dt, n, axis)
                full = lax.all_gather(shard.astype(wire_dt), axis,
                                      tiled=True)[:numel]
            else:
                full = _qall_gather_flat(shard, axis, transport, block,
                                         numel)
        for k, arr in zip(b.keys, _unflatten_bucket(full, b)):
            out[k] = arr.astype(np.dtype(b.dtype))
    if layout.list_input:
        return [out[i] for i in range(len(out))]
    return out


def split_all_reduce_coalesced(xs, subgroup_axis: str,
                               groups: Optional[Sequence[Sequence[int]]] = None,
                               op: str = "sum", bucket_mb: float = 4.0,
                               transport: str = "fp32",
                               block: int = INT8_BLOCK):
    """Coalesced SplitAllReduce: one fused collective per bucket, run
    independently over (possibly unequal) subgroups.  fp32 handles
    unequal groups natively (psum axis_index_groups); quantized
    transports require equal-size groups."""
    return all_reduce_coalesced(xs, subgroup_axis, op=op,
                                bucket_mb=bucket_mb, transport=transport,
                                block=block, groups=groups)


def split_reduce_scatter_coalesced(xs, subgroup_axis: str,
                                   groups: Optional[Sequence[Sequence[int]]]
                                   = None,
                                   bucket_mb: float = 4.0):
    """Coalesced SplitReduceScatter over (possibly unequal) subgroups:
    flattens each bucket, pads to a common multiple of every subgroup
    size, and runs one :func:`split_reduce_scatter` per bucket.  Returns
    ``(flat_shards, layout)`` with the padded-to-largest-chunk contract
    of :func:`split_reduce_scatter`."""
    items, _rebuild = _normalize_tree(xs)
    lookup = dict(items)
    buckets = plan_buckets(
        [(k, np.shape(v), jnp.result_type(v)) for k, v in items], bucket_mb)
    n = axis_size(subgroup_axis)
    sizes = [len(g) for g in groups] if groups is not None else [n]
    lcm = int(np.lcm.reduce(np.asarray(sizes, np.int64)))
    shards, chunk_lens = [], []
    for b in buckets:
        flat = _flatten_bucket(b, lookup)
        pad = (-flat.shape[0]) % lcm
        padded = jnp.pad(flat, (0, pad))
        _record("reduce_scatter",
                padded.shape[0] * np.dtype(padded.dtype).itemsize,
                padded.dtype, max(sizes), subgroup_axis)
        shards.append(split_reduce_scatter(padded, subgroup_axis, 0, groups))
        chunk_lens.append(padded.shape[0] // min(sizes))
    gtuple = tuple(tuple(int(i) for i in g) for g in groups) \
        if groups is not None else None
    return shards, CoalescedLayout(tuple(buckets), tuple(chunk_lens),
                                   not isinstance(xs, Mapping), gtuple)


def split_reduce_scatter(x: jax.Array, subgroup_axis: str,
                         scatter_dim: int = 0,
                         groups: Optional[Sequence[Sequence[int]]] = None
                         ) -> jax.Array:
    """ReduceScatter within each subgroup (SplitReduceScatterOp,
    ops/Communication.h:782).  With unequal ``groups`` the result is
    padded to the largest chunk (L // min group size); each rank's first
    L // own_group_size rows are its chunk of the group-reduced tensor,
    the rest zeros."""
    if groups is None:
        return lax.psum_scatter(x, subgroup_axis,
                                scatter_dimension=scatter_dim, tiled=True)
    scatter_dim = scatter_dim % x.ndim
    n = axis_size(subgroup_axis)
    gs = _norm_groups(groups, n)
    sizes = {len(g) for g in gs}
    if len(sizes) == 1:
        return lax.psum_scatter(x, subgroup_axis,
                                scatter_dimension=scatter_dim, tiled=True,
                                axis_index_groups=[tuple(g) for g in gs])
    L = x.shape[scatter_dim]
    for g in gs:
        if L % len(g) != 0:
            raise ValueError(
                f"scatter dim {L} not divisible by subgroup size {len(g)}")
    max_chunk = L // min(sizes)
    gid_t, _, rin_t, gsz_t = _group_tables(gs, n)
    my = lax.axis_index(subgroup_axis)
    reduced = lax.psum(x, subgroup_axis,
                       axis_index_groups=[tuple(g) for g in gs])
    chunk = L // jnp.asarray(gsz_t)[my]                # traced per-rank
    offset = jnp.asarray(rin_t)[my] * chunk
    # static-size slice of max_chunk starting at offset (pad tail so the
    # slice never clamps into another rank's chunk), then mask the excess
    pad = [(0, 0)] * x.ndim
    pad[scatter_dim] = (0, max_chunk)
    padded = jnp.pad(reduced, pad)
    starts = [jnp.int32(0)] * x.ndim
    starts[scatter_dim] = offset
    sizes_out = list(x.shape)
    sizes_out[scatter_dim] = max_chunk
    out = lax.dynamic_slice(padded, starts, sizes_out)
    pos_shape = [1] * x.ndim
    pos_shape[scatter_dim] = max_chunk
    pos = jnp.arange(max_chunk).reshape(pos_shape)
    return jnp.where(pos < chunk, out, 0)
