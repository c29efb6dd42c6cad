"""KV-page streaming between replica pools: the disaggregation wire.

Disaggregated serving (hydraulis-style, SURVEY.md) runs prefill and
decode on DIFFERENT engines: a prefill replica computes the prompt's KV
pages, then the pages move to a decode replica's pool and generation
resumes there.  :class:`PageTransport` is the interface that move goes
through; two phases, matching how a real wire behaves:

* :meth:`~PageTransport.extract` — serialize the source pages off the
  source pool (host staging here; a DMA ring or RDMA read on hardware).
  Extraction happens the instant the prefill finishes, while the pages
  are still owned — the source engine is then free to retire them into
  its prefix cache.
* :meth:`~PageTransport.inject` — land the staged pages into
  already-allocated destination pages and record the handoff.

:class:`LocalPageTransport` is the process-local implementation: it
really copies page contents between pools (bit-for-bit — the decode
replica reads KV identical to what a monolithic engine would hold, the
cluster tests assert temp-0 output equality), while the WIRE cost the
copy stands in for is priced through the planner's own alpha-beta
formulas (:func:`hetu_tpu.planner.cost_model.collective_time`, p2p/
ppermute rate — the same single implementation the step-time linter and
the DP solver use).  Every handoff therefore carries a **priced edge
claim**: a ``CommEdge``-shaped dict plus the predicted seconds on the
modeled interconnect.  The ``kv-handoff-unpriced`` analysis rule
(``analysis/rules.py``) fails CI for any cross-replica page move whose
record lacks that claim — the CPU-honest gate that keeps the
disaggregation design priced before TPU hardware exists.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..kv_pool import PagedKVPool, protocol_seq


class PageTransport:
    """Interface for moving KV pages between replica pools.

    Implementations must be bit-exact (the disaggregation correctness
    contract rides on it) and must append a priced handoff record per
    :meth:`inject` — see :class:`LocalPageTransport` for the record
    schema the ``kv-handoff-unpriced`` rule audits."""

    def extract(self, src_pool: PagedKVPool,
                src_pages: Sequence[int]) -> Any:
        raise NotImplementedError

    def inject(self, dst_pool: PagedKVPool, staged: Any,
               dst_pages: Sequence[int], src_replica: int = -1,
               dst_replica: int = -1,
               epoch: Optional[int] = None) -> Dict[str, Any]:
        raise NotImplementedError

    def records_for(self, dst_replica: int) -> List[Dict[str, Any]]:
        raise NotImplementedError


class LocalPageTransport(PageTransport):
    """Process-local page copy with alpha-beta wire pricing.

    ``cluster_spec`` (a :class:`~hetu_tpu.planner.cost_model.ClusterSpec`)
    models the interconnect the handoff would cross on hardware; the
    predicted seconds per handoff use the p2p/ppermute rate — a
    prefill→decode page stream is a point-to-point send, not a
    collective.  The measured host-copy wall time rides along in the
    record so the obs plane can reconcile prediction vs (CPU) reality.
    """

    def __init__(self, cluster_spec=None):
        if cluster_spec is None:
            from ...planner.cost_model import ClusterSpec
            cluster_spec = ClusterSpec()
        self.cluster_spec = cluster_spec
        self.records: List[Dict[str, Any]] = []
        # wire.extract events ``(seq, src_pages)`` for the protocol
        # verifier: extraction reads the source pages, so a page that
        # was already reclaimed at extract time ships garbage KV
        self.extract_log: List[Any] = []

    # -- the two wire phases -------------------------------------------------

    def extract(self, src_pool: PagedKVPool,
                src_pages: Sequence[int]) -> Dict[str, Any]:
        """Pull ``src_pages`` off the source pool into host staging
        buffers (one ``[n, kvh, page, hd]`` array per layer per k/v).
        ``np.asarray`` forces the device values — the staging copy is
        taken NOW, so the source engine may free/retire the pages the
        moment this returns."""
        idx = np.asarray(list(src_pages), np.int32)
        self.extract_log.append((protocol_seq(),
                                 tuple(int(p) for p in idx)))
        k = [np.asarray(p[idx]) for p in src_pool.k_pages]
        v = [np.asarray(p[idx]) for p in src_pool.v_pages]
        return {"k": k, "v": v, "n_pages": len(idx),
                # page_bytes derives from kv_pool.page_shape_bytes, so
                # a latent/quantized pool's smaller pages are priced at
                # their true wire size automatically
                "payload_bytes": len(idx) * src_pool.page_bytes,
                "layout": src_pool.layout_tag}

    def inject(self, dst_pool: PagedKVPool, staged: Dict[str, Any],
               dst_pages: Sequence[int], src_replica: int = -1,
               dst_replica: int = -1,
               epoch: Optional[int] = None) -> Dict[str, Any]:
        """Land staged pages into ``dst_pages`` (already allocated in
        ``dst_pool``) and append the priced handoff record.  ``epoch``
        is the fence token: the cluster's per-handoff staging epoch
        (fresh on every re-stage).  It deliberately has NO usable
        default — a call site that omits it records ``epoch: None``
        and the ``unfenced-handoff`` rule fails CI, which is exactly
        how a regression to the unfenced PR-11 signature gets
        caught."""
        idx = jnp.asarray(list(dst_pages), jnp.int32)
        if int(idx.shape[0]) != int(staged["n_pages"]):
            raise ValueError(
                f"staged {staged['n_pages']} pages but got "
                f"{int(idx.shape[0])} destination pages")
        src_layout = staged.get("layout")
        if src_layout is not None and \
                src_layout != dst_pool.layout_tag:
            # bit-exactness is the handoff contract: page bytes from a
            # different layout (latent vs full-head, other quant/
            # geometry) are not the destination's KV, even when shapes
            # happen to broadcast
            raise ValueError(
                f"page layout mismatch: staged {src_layout} vs "
                f"destination pool {dst_pool.layout_tag}")
        t0 = time.perf_counter()
        new_k = tuple(p.at[idx].set(jnp.asarray(s))
                      for p, s in zip(dst_pool.k_pages, staged["k"]))
        new_v = tuple(p.at[idx].set(jnp.asarray(s))
                      for p, s in zip(dst_pool.v_pages, staged["v"]))
        dst_pool.set_pages(new_k, new_v)
        wall = time.perf_counter() - t0
        rec = self._price(int(staged["n_pages"]),
                          int(staged["payload_bytes"]),
                          src_replica, dst_replica, wall)
        rec["epoch"] = None if epoch is None else int(epoch)
        rec["seq"] = protocol_seq()
        self.records.append(rec)
        return rec

    # -- pricing -------------------------------------------------------------

    def _price(self, n_pages: int, payload_bytes: int, src: int,
               dst: int, wall_s: float) -> Dict[str, Any]:
        """The priced edge claim: a CommEdge-shaped dict (the
        ``analysis/edges`` vocabulary — kind/payload/count/tag) plus
        the alpha-beta predicted seconds through the ONE
        ``collective_time`` implementation the planner and the
        step-time linter share."""
        from ...planner.cost_model import collective_time
        edge = {"kind": "ppermute", "tensor": "kv_pages",
                "producer": f"prefill r{src}",
                "consumer": f"decode r{dst}",
                "src_spec": f"pool@r{src}", "dst_spec": f"pool@r{dst}",
                "axes": ("replica",), "payload_bytes": payload_bytes,
                "count": 1, "tag": "kv_handoff", "origin": "declared"}
        predicted_s = collective_time("ppermute", float(payload_bytes),
                                      2, self.cluster_spec)
        return {"src": int(src), "dst": int(dst), "pages": n_pages,
                "payload_bytes": payload_bytes, "edge": edge,
                "predicted_s": float(predicted_s),
                "wall_s": float(wall_s)}

    def records_for(self, dst_replica: int) -> List[Dict[str, Any]]:
        """The handoff records landing on ``dst_replica`` — the decode
        engine's registration exposes exactly these to the
        ``kv-handoff-unpriced`` rule."""
        return [r for r in self.records if r["dst"] == int(dst_replica)]

    @property
    def total_payload_bytes(self) -> int:
        return sum(r["payload_bytes"] for r in self.records)

    @property
    def total_predicted_s(self) -> float:
        return sum(r["predicted_s"] for r in self.records)
