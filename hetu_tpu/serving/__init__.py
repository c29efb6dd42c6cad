"""Serving subsystem: paged KV-cache pool + continuous-batching engine
driving ONE unified ragged prefill+decode executable.

    from hetu_tpu.serving import Engine

    eng = Engine(state, cfg, num_pages=128, page_size=64, max_batch=8,
                 chunk_size=64, prefill_rows=1)
    req = eng.add_request(prompt_ids, max_new_tokens=64,
                          temperature=0.8, top_p=0.95, seed=7)
    outputs = eng.run()            # {req_id: generated token list}

See DESIGN.md §8 for the page-size/TP-tiling rationale, §12 for the
unified ragged step (token-budget packing, chunked prefill, on-device
temperature/top-k/top-p sampling, the one-executable compile contract),
§13 for copy-on-write prefix caching (chained page hashing, refcounted
read-only pages, LRU eviction — on by default, disable with
``Engine(..., prefix_cache=False)``), §17 for the cluster plane
(``serving.cluster.EngineCluster``: prefix-aware routing over N
replicas, disaggregated prefill/decode, priced KV-page streaming), and
§20 for draft-model speculative decoding
(``Engine(spec=SpecConfig(draft_state, draft_cfg, k=4))``: ragged
verify rows, on-device accept, temp-0 output still bit-for-bit), and
§29 for block-wise generation (a ``cfg.diffusion_block`` model: a
generating request's step is its open block, denoised in passes under
``Engine(denoise=DenoiseRule(steps=, rule=, tau=))`` and then committed).
"""
from .cluster import (ClusterRequest, EngineCluster, LocalPageTransport,
                      PageTransport, Replica, Router)
from .engine import Engine
from .kv_pool import PagedKVPool, TRASH_PAGE
from .prefix_cache import CacheEntry, PrefixCache
from .request import (FINISHED, RUNNING, WAITING, DenoiseRule, Request,
                      RequestQueue)
from .scheduler import Scheduler
from .spec import SpecConfig, SpecDecoder

__all__ = ["Engine", "PagedKVPool", "TRASH_PAGE", "PrefixCache",
           "CacheEntry", "Request", "RequestQueue", "Scheduler",
           "WAITING", "RUNNING", "FINISHED",
           "SpecConfig", "SpecDecoder", "DenoiseRule",
           "EngineCluster", "ClusterRequest", "Replica", "Router",
           "PageTransport", "LocalPageTransport"]
