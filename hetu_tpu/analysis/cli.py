"""``python -m hetu_tpu.analysis`` — the lint-graph CI gate.

Builds the canonical executables — five gated families, all scaled down
so the gate runs on CPU in CI:

* ``gate_train``   — GPT-2-small-shaped train step, pure-dp mesh,
  ZeRO-2 + flat state + explicit int8 grad sync, PLUS the same model
  under ZeRO-3 params-sharded-at-rest (``gate_train@zero3``): the flat
  masters keep the only parameter copy, the forward all-gathers each
  bucket just-in-time (priced ``param_gather`` edges), and the memory
  section pins the at-rest saving;
* ``gate_serving`` — the unified ragged prefill+decode step of a small
  continuous-batching engine over the paged KV pool (ONE executable;
  the v1 bucketed prefill/decode grid is gone), PLUS a disaggregated
  2-replica serving cluster whose prefill and decode engines register
  under distinct per-replica names (``gate_serving@r{i}/unified``) and
  whose prefill→decode KV-page handoffs must carry priced edge claims
  (``kv-handoff-unpriced``);
* ``gate_tp``      — a TP/SP train graph (dp=2 x tp=4, Megatron-SP
  layers from ``nn/parallel.py``), implicit GSPMD sync;
* ``gate_pipe``    — a pipeline run, both ways: MPMD per-stage programs
  (``models/gpt_mpmd.py`` on dp=2 x tp=2 submeshes) and the SPMD
  collective-permute pipeline (``parallel/pipeline.py`` ppermute hop
  chain inside the tick scan);
* ``gate_moe``     — a dropless-MoE train step (``nn/moe.py`` +
  ``ops/moe_dispatch.py`` blocked group-GEMM) with the explicit int8
  sync.

Every family registers a per-edge claim, so the per-edge attribution
pass (``analysis/edges.py``) must explain 100% of what each program
emits; then:

* ``--check`` (default): compare against ``ANALYSIS_BASELINE.json`` —
  exit 1 when a collective count grows, payload/wire bytes grow beyond
  ``--tolerance``, edge coverage drops, a new lint finding appears, or
  the grad-comm emission no longer matches the DistributedStates
  prediction.  Exit 2 when the baseline file is missing entirely.
* ``--update-baseline``: re-freeze the baseline after an INTENTIONAL
  perf change (review the printed diff before committing it).
* ``--format json`` (or legacy ``--json``): dump the full report (with
  per-collective records and edge coverage) to stdout for CI artifacts.
* ``--explain``: after the summary, print each finding's offending
  edge/record plus a concrete remediation hint (pspec change, donation,
  narrower transport, capacity factor).
* ``--memory``: print the static peak-HBM section per executable
  (predicted peak, per-kind breakdown, XLA cross-check delta; with
  ``--explain``, the top-contributor attribution table).  The numbers
  are always computed and gated — the flag only controls the text
  section; ``--format json`` always carries them.
* ``--cost``: print the static step-time section per executable
  (FLOP/HBM roofline verdict, comm time, XLA ``cost_analysis()``
  deltas; with ``--explain``, the top-contributor attribution table).
  Same contract as ``--memory``: always computed and gated, the flag
  only controls the text section, ``--format json`` always carries the
  ``cost`` dict.
* ``--protocol``: print the serving-protocol verifier section per
  executable (normalized event-stream size, observed kind vocabulary,
  lifecycle-machine coverage, violation count — DESIGN.md §23).  Like
  ``--memory``/``--cost`` the numbers are always computed and gated
  (the baseline pins per-executable protocol coverage); the flag only
  controls the text section, ``--format json`` always carries the
  ``protocol`` dict.  Lifecycle findings carry the violating event
  subtrace, printed by ``--explain``.
* ``--schedule``: print the cross-rank schedule verifier section per
  executable (per-rank symbolic op inventory, collective/p2p/switch
  plane sizes, hang-freedom verdict — DESIGN.md §25).  Same contract
  again: always computed and gated (the baseline pins per-executable
  schedule coverage and the rule vocabulary); the flag only controls
  the text section, ``--format json`` always carries the ``schedule``
  dict.  Schedule findings carry the divergent per-rank subtraces side
  by side, printed by ``--explain``.
* ``--hbm-budget``: device HBM budget in GiB for the ``oom-risk`` rule
  (default: the rule's v5p budget).

The gate collects two kinds of problem and keeps them apart.

*Structural* problems decide the exit code: collective counts by kind,
GSPMD-inserted collectives, unexplained edges, lint findings, grad-comm
emission, protocol and schedule coverage, the baseline-pinned predicted
peak bytes / FLOPs / HBM bytes / step time (may not grow beyond
``--tolerance``), and a static pass or an XLA cross-check that produced
no report at all.

*Predictor accuracy* does not: every compiled executable's static peak
is compared with XLA's own ``compiled.memory_analysis()`` total, and its
comparable FLOP and bytes-accessed totals with
``compiled.cost_analysis()``, at ±10% (absolute floors for toy-scale
programs).  What falls outside the band is printed under its own
heading and carried in the JSON payload (``predictor_drift``); the
tier-1 test ``tests/test_analysis.py::
test_static_predictors_within_band_of_xla`` holds that list to empty
(``xfail(strict=True)`` while the predictors are off).  These are the
numbers ``planner.cost_model.calibrate_layer_time`` feeds the DP solver.

Exit codes (stable, documented for CI): **0** clean, **1** findings or
structural / baseline regressions, **2** baseline missing (run
``--update-baseline`` to create it — the missing-baseline check runs
*before* the expensive build, so a misconfigured CI path fails fast).

The model shapes are deliberately frozen: the baseline pins exact
collective counts, so any change to the lowering path (a new implicit
reshard, a lost donation, a widened transport) trips the gate even when
tests still pass numerically.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BASELINE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "ANALYSIS_BASELINE.json")


def _force_cpu_mesh() -> None:
    """The gate needs >= 8 devices; CPU CI gets them virtually.  Must
    run before jax initializes a backend (import is fine, first device
    query is not)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    if os.environ["JAX_PLATFORMS"] == "cpu":
        jax.config.update("jax_platforms", "cpu")


def build_gate_executables():
    """Build + register the gate's executables; returns their names.

    Deterministic by construction: fixed seeds, fixed shapes, fixed
    request schedule — the baseline pins the exact collective counts.
    """
    import numpy as np
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    import hetu_tpu as ht
    from hetu_tpu import optim, ops
    from hetu_tpu.graph.graph import (DefineAndRunGraph, clear_executables,
                                      register_executable)
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel, llama_config
    from hetu_tpu.parallel import create_mesh
    from hetu_tpu.serving import Engine

    clear_executables("gate_")
    devices = jax.devices()[:8]
    names = []

    # -- train step: GPT-2-small-shaped (12-head/768-wide ratios scaled
    # to CI size), dp=8, ZeRO-2, explicit int8 grad sync over FLAT
    # dp-sharded optimizer state (reduce-scatter-only: one RS chain +
    # one bf16 param all-gather per bucket, ZERO grad all-gathers) -----
    ht.set_seed(0)
    mesh = create_mesh({"dp": 8}, devices)
    cfg = llama_config(vocab_size=256, hidden_size=64, num_layers=2,
                       num_heads=4, max_seq_len=32, sp=False,
                       dtype="bfloat16")
    g = DefineAndRunGraph("gate_train")
    g.mesh = mesh
    with ht.graph(g):
        ids = ht.parallel_placeholder("int32", (8, 32),
                                      pspec=P("dp", None), name="ids")
        labels = ht.parallel_placeholder("int32", (8, 32),
                                         pspec=P("dp", None), name="labels")
        model = GPTLMHeadModel(cfg)
        loss = model(ids, labels)
        train_op = optim.AdamOptimizer(lr=1e-2, zero=2, grad_comm="int8",
                                       flat_state=True).minimize(loss)
        rng = np.random.RandomState(0)
        IDS = rng.randint(0, 256, (8, 32)).astype(np.int32)
        g.run(loss, [loss, train_op], {ids: IDS,
                                       labels: np.roll(IDS, -1, axis=1)})
        assert g._grad_comm_active, g._grad_comm_fallback
    names.append("gate_train/plan0")

    # -- ZeRO-3 train step: the SAME model and shapes with the params
    # sharded at rest — the flat fp32 masters hold the only copy, the
    # forward all-gathers each bucket just-in-time (tagged
    # param_gather), and after the chunk-local update only the 1/dp
    # shard remains.  The baseline pins the new priced edge family and
    # the memory section's at-rest param bytes (zero vs gate_train's
    # replicated set) --------------------------------------------------
    ht.set_seed(0)
    g3 = DefineAndRunGraph("gate_train@zero3")
    g3.mesh = create_mesh({"dp": 8}, devices)
    with ht.graph(g3):
        ids = ht.parallel_placeholder("int32", (8, 32),
                                      pspec=P("dp", None), name="ids")
        labels = ht.parallel_placeholder("int32", (8, 32),
                                         pspec=P("dp", None), name="labels")
        model = GPTLMHeadModel(cfg)
        loss = model(ids, labels)
        train_op = optim.AdamOptimizer(lr=1e-2, zero=3, grad_comm="int8",
                                       flat_state=True).minimize(loss)
        rng = np.random.RandomState(0)
        IDS = rng.randint(0, 256, (8, 32)).astype(np.int32)
        g3.run(loss, [loss, train_op], {ids: IDS,
                                        labels: np.roll(IDS, -1, axis=1)})
        assert g3._grad_comm_active, g3._grad_comm_fallback
    names.append("gate_train@zero3/plan0")

    # -- TP/SP train graph: dp=2 x tp=4, Megatron-SP parallel layers,
    # implicit GSPMD sync — every GSPMD-inserted collective must be
    # explained by the graph's pspec edges ----------------------------
    ht.set_seed(4)
    tp_mesh = create_mesh({"dp": 2, "tp": 4}, devices)
    tp_cfg = llama_config(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, max_seq_len=32, sp=True,
                          dtype="bfloat16")
    gt = DefineAndRunGraph("gate_tp")
    gt.mesh = tp_mesh
    with ht.graph(gt):
        ids = ht.parallel_placeholder("int32", (8, 32),
                                      pspec=P("dp", None), name="ids")
        labels = ht.parallel_placeholder("int32", (8, 32),
                                         pspec=P("dp", None), name="labels")
        model = GPTLMHeadModel(tp_cfg)
        loss = model(ids, labels)
        train_op = optim.AdamOptimizer(lr=1e-2).minimize(loss)
        rng = np.random.RandomState(4)
        IDS = rng.randint(0, 256, (8, 32)).astype(np.int32)
        gt.run(loss, [loss, train_op], {ids: IDS,
                                        labels: np.roll(IDS, -1, axis=1)})
    names.append("gate_tp/plan0")

    # -- pipeline, MPMD: per-stage programs on dp=2 x tp=2 submeshes,
    # declared stage edges (models/gpt_mpmd.stage_comm_edges) ---------
    from hetu_tpu.models.gpt_mpmd import MPMDGPT
    devs = np.array(devices).reshape(2, 2, 2)
    pipe_cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                         num_heads=4, max_seq_len=16, dropout=0.0,
                         activation="gelu", norm="layernorm",
                         position="learned", sp=False)
    mpmd = MPMDGPT(pipe_cfg, stage_layers=[[1, 1]],
                   meshes=[[Mesh(devs[0], ("dp", "tp")),
                            Mesh(devs[1], ("dp", "tp"))]], seed=5)
    names += mpmd.register_analysis("gate_pipe_mpmd", batch=4, seq=16)

    # -- pipeline, SPMD: the collective-permute pipeline — ppermute hop
    # chain (M + S - 1 hops) inside the tick scan, tagged pipeline/hop
    from hetu_tpu.parallel.pipeline import pipeline_spmd
    pp_mesh = create_mesh({"pp": 4}, devices[:4])
    S, d, M, B = 4, 16, 2, 8

    def _stage_fn(p, v):
        import jax.numpy as jnp
        return jnp.tanh(v @ p["w"][0])

    pp_fn = jax.jit(lambda pr, x: pipeline_spmd(_stage_fn, pr, x, M,
                                                pp_mesh))
    pp_params = {"w": jax.ShapeDtypeStruct((S, 1, d, d), np.float32)}
    register_executable(
        "gate_pipe_spmd/fwd", pp_fn,
        (pp_params, jax.ShapeDtypeStruct((B, d), np.float32)),
        {"kind": "forward", "mesh_axes": {"pp": 4}, "params": [],
         "scalar_fetches": 0,
         "pipeline": {
             "pp_axis": "pp", "hops": M + S - 1,
             "payload_bytes": (B // M) * d * 4,
             "extra_edges": [
                 {"kind": "all_reduce", "tensor": "out_collect",
                  "producer": "last stage",
                  "consumer": "out broadcast + aux micro-batch mean",
                  "axes": ("pp",), "count": 2, "tag": "pipeline",
                  "payload_bytes": B * d * 4}]}})
    names.append("gate_pipe_spmd/fwd")

    # -- dropless-MoE train step: capacity-free blocked group-GEMM
    # (every assignment computes), explicit int8 sync -----------------
    from hetu_tpu.nn.moe import make_moe_layer
    ht.set_seed(6)
    moe_mesh = create_mesh({"dp": 8}, devices)
    gm = DefineAndRunGraph("gate_moe")
    gm.mesh = moe_mesh
    with ht.graph(gm):
        x = ht.parallel_placeholder("float32", (16, 32),
                                    pspec=P("dp", None), name="x")
        moe = make_moe_layer(32, 64, num_experts=4, gate_type="topk",
                             k=2, dispatch_mode="dropless", name="moe")
        out, aux = moe(x)
        loss = ops.reduce_mean(out ** 2) + 0.01 * aux
        train_op = optim.AdamOptimizer(lr=1e-2, zero=1,
                                       grad_comm="int8").minimize(loss)
        rng = np.random.RandomState(6)
        gm.run(loss, [loss, train_op],
               {x: rng.randn(16, 32).astype(np.float32)})
        assert gm._grad_comm_active, gm._grad_comm_fallback
    names.append("gate_moe/plan0")

    # -- serving: ONE unified ragged prefill+decode executable ---------
    ht.set_seed(1)
    scfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=4, max_seq_len=64)
    with ht.graph("eager", create_new=True):
        smodel = GPTLMHeadModel(scfg)
        smodel.logits(np.zeros((1, 4), np.int32))
        state = {k: np.asarray(v) for k, v in
                 smodel.state_dict().items()}
    clock = [0.0]
    eng = Engine(state, scfg, num_pages=16, page_size=8, max_batch=4,
                 chunk_size=4, name="gate_serving",
                 time_fn=lambda: clock[0])
    eng.add_request([1, 2, 3, 4, 5], max_new_tokens=4)
    eng.add_request([7, 8, 9], max_new_tokens=4)
    while eng.has_work:
        eng.step()
        clock[0] += 1.0
    eng.pool.check_invariants(force=True)
    assert eng.compile_count == 1, "the bucket grid came back"
    names += sorted(f"gate_serving/{k}" for k in eng._compiled)

    # -- MLA latent serving: the SAME checkpoint converted to the
    # weight-absorbed latent-KV schema (models.gpt.mla_state_from) on a
    # latent-layout pool — the standing pool lints (trash-page-write,
    # cow-page-write via the shared-header cache hit below) now audit
    # compressed pages, and analysis/memory classifies the asymmetric
    # latent k/v page shapes as kv-page operands ---------------------
    from hetu_tpu.models.gpt import mla_state_from
    mstate, mcfg = mla_state_from(state, scfg, kv_latent_dim=12)
    mclock = [0.0]
    meng = Engine(mstate, mcfg, num_pages=16, page_size=8, max_batch=4,
                  chunk_size=4, name="gate_serving@mla",
                  time_fn=lambda: mclock[0])
    header = list(range(1, 10))          # one full cached page at ps=8
    meng.add_request(header + [11, 12], max_new_tokens=4)
    while meng.has_work:
        meng.step()
        mclock[0] += 1.0
    meng.add_request(header + [21, 22], max_new_tokens=4)
    while meng.has_work:
        meng.step()
        mclock[0] += 1.0
    meng.pool.check_invariants(force=True)
    assert meng.pool.is_latent, "MLA gate engine built a full-head pool"
    assert meng.compile_count == 1, \
        "the latent path retraced the unified executable"
    assert meng.counters["prefix_cache_hits"].value >= 1, \
        "MLA gate trace never hit the prefix cache — the cow-page " \
        "lint would be vacuous over latent pages"
    names.append("gate_serving@mla/unified")

    # -- speculative serving: the SAME model behind a spec-mode engine
    # (truncated 1-layer self-draft, k=3) — the unified executable
    # grows the on-device verify/accept head and registers under
    # gate_serving@spec/unified; the spec-rewind-leak rule audits the
    # trace's tap (rewinds asserted non-vacuous so the rule has real
    # records to chew), and the draft programs join the compile pin ---
    from hetu_tpu.models import draft_state_from
    from hetu_tpu.serving import SpecConfig
    dstate, dcfg = draft_state_from(state, scfg, 1)
    spclock = [0.0]
    speng = Engine(state, scfg, num_pages=16, page_size=8, max_batch=4,
                   chunk_size=8, name="gate_serving@spec",
                   time_fn=lambda: spclock[0],
                   spec=SpecConfig(dstate, dcfg, k=3))
    speng.add_request([1, 2, 3, 4, 5], max_new_tokens=6)
    speng.add_request([7, 8, 9], max_new_tokens=6)
    while speng.has_work:
        speng.step()
        spclock[0] += 1.0
    speng.pool.check_invariants(force=True)
    assert speng.compile_count == 4, \
        "spec engine = unified + draft prefill/propose/insert, pinned"
    prop = speng.counters["spec_proposed"].value
    acc = speng.counters["spec_accepted"].value
    assert prop > 0, "spec gate trace never speculated"
    assert acc < prop, \
        "spec gate trace never rewound — the rewind lint is vacuous"
    names.append("gate_serving@spec/unified")

    # -- serving cluster: a disaggregated 2-replica fleet (1 prefill +
    # 1 decode) over the SAME model — each replica's unified executable
    # registers under its own name (gate_serving@r{i}/unified), the
    # prefill→decode KV-page handoff must carry a priced edge claim
    # (kv-handoff-unpriced audits the records the decode replica's
    # meta exposes), and both replicas share ONE compiled program -----
    from hetu_tpu.serving import EngineCluster
    cclock = [0.0]
    cl = EngineCluster(state, scfg, num_replicas=2,
                       mode="disaggregated", num_prefill=1,
                       name="gate_serving", num_pages=16, page_size=8,
                       max_batch=4, chunk_size=4,
                       time_fn=lambda: cclock[0], ttl=3600.0)
    cl.add_request([1, 2, 3, 4, 5, 6, 7, 8, 9], max_new_tokens=3)
    cl.add_request([1, 2, 3, 4, 5, 6, 7, 8, 11], max_new_tokens=3)
    guard = 0
    while cl.has_work:
        cl.step()
        cclock[0] += 1.0
        guard += 1
        assert guard < 200, "cluster gate trace did not drain"
    assert len(cl.transport.records) == 2, "prefill->decode handoff gone"
    assert all(r["predicted_s"] > 0 for r in cl.transport.records), \
        "handoff lost its alpha-beta pricing"
    for r in cl.replicas:
        r.engine.pool.check_invariants(force=True)
    cl.close()

    # -- SLO traffic plane: an engine with the host-RAM tier for cold
    # prefix-cache pages — a warmed cache is forcibly swept to host,
    # then a same-header request refetches through the priced
    # device↔host path (host-offload-unpriced audits the records the
    # host_offload meta exposes; both directions asserted non-vacuous
    # so the rule has real evicts AND refetches to chew) --------------
    hclock = [0.0]
    heng = Engine(state, scfg, num_pages=16, page_size=8, max_batch=4,
                  chunk_size=4, name="gate_serving@slo",
                  time_fn=lambda: hclock[0], prefix_cache=True,
                  host_tier=True)
    header = list(range(1, 18))          # two full cached pages at ps=8
    heng.add_request(header + [21, 22], max_new_tokens=4,
                     slo_class="interactive")
    while heng.has_work:
        heng.step()
        hclock[0] += 1.0
    heng.prefix_cache.evict(16)          # cold sweep -> host staging
    heng.add_request(header + [31, 32], max_new_tokens=4,
                     slo_class="batch")
    while heng.has_work:
        heng.step()
        hclock[0] += 1.0
    heng.pool.check_invariants(force=True)
    heng.prefix_cache.check_invariants()
    assert heng.host_tier.evictions >= 2, \
        "host-tier gate trace evicted nothing — the rule is vacuous"
    assert heng.host_tier.hits >= 2, \
        "host-tier gate trace never refetched — the refetch half of " \
        "the rule is vacuous"
    assert all(r["predicted_s"] > 0 for r in heng.host_tier.records), \
        "host-tier move lost its alpha-beta pricing"
    names.append("gate_serving@slo/unified")

    # -- hybrid stack: one mixer per layer (attention, latent MoE over a
    # share of the experts, mamba2), K/V pages for the attention layer
    # and a state slot per sequence for the mamba2 layers carried through
    # the SAME one executable; a pool squeezed into a preemption so the
    # trace holds a slot dropped and taken again ----------------------
    from hetu_tpu.models.hybrid import hybrid_config, init_state
    ycfg = hybrid_config(dict(
        hybrid_override_pattern="*EMEM", num_hidden_layers=5,
        hidden_size=32, num_attention_heads=4, head_dim=8,
        num_key_value_heads=2, vocab_size=128, max_position_embeddings=64,
        mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
        tie_word_embeddings=False, mamba_num_heads=4, mamba_head_dim=8,
        n_groups=2, ssm_state_size=8, conv_kernel=4, chunk_size=4,
        n_routed_experts=4, moe_router_outputs=16, expert_offset=4,
        num_experts_per_tok=6, routed_scaling_factor=5,
        moe_intermediate_size=16, moe_latent_size=16,
        moe_shared_expert_intermediate_size=32, n_shared_experts=1,
        dtype="float32"))
    yclock = [0.0]
    yeng = Engine(init_state(ycfg, 1), ycfg, num_pages=6, page_size=8,
                  max_batch=4, chunk_size=4, name="gate_serving@hybrid",
                  time_fn=lambda: yclock[0], prefix_cache=False)
    yeng.add_request(list(range(1, 14)), max_new_tokens=12)
    yeng.add_request(list(range(20, 33)), max_new_tokens=12)
    yeng.add_request([7, 8, 9], max_new_tokens=4)
    while yeng.has_work:
        yeng.step()
        yclock[0] += 1.0
    yeng.pool.check_invariants(force=True)
    assert yeng.compile_count == 1, \
        "the hybrid stack retraced the unified executable"
    assert yeng.state_store.in_use == 0, "a state slot leaked"
    assert yeng.counters["preemptions"].value >= 1, \
        "hybrid gate trace never preempted — no slot was dropped"
    names.append("gate_serving@hybrid/unified")
    return names + [f"gate_serving@r{i}/unified" for i in range(2)]


def explain_report(report, out=sys.stdout, memory: bool = False,
                   cost: bool = False) -> None:
    """--explain: per finding, the offending edge/record and a concrete
    remediation hint; per executable, the predicted edge list (and, with
    --memory / --cost, the peak-HBM / step-time attribution tables)."""
    for name, rep in sorted(report.executables.items()):
        cov = rep.meta.get("edge_coverage")
        edges = rep.meta.get("edges")
        print(f"\n=== {name} ===", file=out)
        if cov:
            print(f"  edge coverage: {cov['explained']}/{cov['total']} "
                  f"collectives explained", file=out)
        if edges is not None:
            print(f"  predicted edges ({len(edges)}):", file=out)
            for e in edges:
                print(f"    . {e.describe()}", file=out)
        mem = rep.meta.get("memory")
        if memory and mem is not None:
            print(f"  peak-HBM attribution (top contributors):", file=out)
            for b in mem.top(10):
                src = f"  [{b.source}]" if b.source else ""
                print(f"    . {b.kind:10s} {b.nbytes:>12d} B  "
                      f"{b.name} {b.detail}{src}", file=out)
        co = rep.meta.get("cost")
        if cost and co is not None:
            print(f"  step-time attribution (top contributors):",
                  file=out)
            for e in co.top(10):
                src = f"  [{e.source}]" if e.source else ""
                print(f"    . {e.prim:18s} "
                      f"{int((e.flops + e.transcendentals) * e.count):>12d}"
                      f" FLOP {int(e.bytes * e.count):>10d} B"
                      f"  {e.detail}{src}", file=out)
            for c in sorted(co.comm, key=lambda c: -c.total_s)[:6]:
                ov = " (overlapped)" if c.overlapped else ""
                print(f"    . comm {c.kind:13s} {c.payload_bytes:>10d} B"
                      f" x{c.count} over {c.group} chips -> "
                      f"{c.total_s * 1e6:.1f}us{ov}", file=out)
        if not rep.findings:
            print("  no findings", file=out)
            continue
        for f in rep.findings:
            print(f"  ! {f}", file=out)
            if not f.hint:
                continue
            if "\n" in f.hint:
                # lifecycle findings carry the violating event subtrace
                # (protocol.Violation.format_subtrace) — print it as a
                # block, not jammed onto one "fix:" line
                for ln in f.hint.splitlines():
                    print(f"    {ln}", file=out)
            else:
                print(f"    fix: {f.hint}", file=out)


def memory_section(report, out=sys.stdout) -> None:
    """--memory: the static peak-HBM model per executable — predicted
    peak, per-kind breakdown, and the XLA cross-check delta."""
    print("\nstatic peak-HBM model (analysis/memory):", file=out)
    for name, rep in sorted(report.executables.items()):
        mem = rep.meta.get("memory")
        if mem is None:
            print(f"  {name}: (memory pass unavailable)", file=out)
            continue
        print(f"  {name}: {mem.summary()}", file=out)


def cost_section(report, out=sys.stdout) -> None:
    """--cost: the static step-time model per executable — FLOP/HBM
    roofline verdict, comm time, and the XLA cost_analysis deltas."""
    print("\nstatic step-time model (analysis/cost):", file=out)
    for name, rep in sorted(report.executables.items()):
        co = rep.meta.get("cost")
        if co is None:
            print(f"  {name}: (cost pass unavailable)", file=out)
            continue
        print(f"  {name}: {co.summary()}", file=out)


def protocol_section(report, out=sys.stdout) -> None:
    """--protocol: the serving-protocol verifier per executable — the
    normalized event stream's size and kind vocabulary, the lifecycle
    machines' coverage, and the violation count (DESIGN.md §23)."""
    print("\nserving-protocol verifier (analysis/protocol):", file=out)
    for name, rep in sorted(report.executables.items()):
        p = rep.meta.get("protocol")
        if p is None:
            print(f"  {name}: (protocol pass unavailable)", file=out)
            continue
        m = p.get("machines", {})
        lost = f", LOST hooks {p['lost_hooks']}" \
            if p.get("lost_hooks") else ""
        print(f"  {name}: {p['events']} events / "
              f"{len(p.get('kinds', {}))} kinds, machines saw "
              f"{m.get('pages', 0)} pages / {m.get('requests', 0)} "
              f"requests / {m.get('replicas', 0)} replicas, "
              f"{p['violations']} violations{lost}", file=out)
        if p.get("kinds"):
            ks = ", ".join(f"{k} x{v}"
                           for k, v in sorted(p["kinds"].items()))
            print(f"    kinds: {ks}", file=out)


def schedule_section(report, out=sys.stdout) -> None:
    """--schedule: the cross-rank schedule verifier per executable —
    rank count, op inventory, plane sizes and the hang-freedom verdict
    (DESIGN.md §25).  Divergent per-rank subtraces ride --explain: each
    schedule finding's hint is the side-by-side window around the
    divergence point on every implicated rank."""
    print("\ncross-rank schedule verifier (analysis/schedule):",
          file=out)
    for name, rep in sorted(report.executables.items()):
        s = rep.meta.get("schedule")
        if s is None:
            print(f"  {name}: (schedule pass unavailable)", file=out)
            continue
        if not s.get("ranks"):
            print(f"  {name}: no multi-rank claim", file=out)
            continue
        verdict = "hang-free" if not s["violations"] \
            else f"{s['violations']} VIOLATION(S) {s['violation_rules']}"
        print(f"  {name}: {s['ranks']} ranks x {s['ops']} ops "
              f"({s['collectives']} collective, {s['p2p']} p2p, "
              f"{s['switch']} switch) — {verdict}", file=out)
        if s.get("kinds"):
            ks = ", ".join(f"{k} x{v}"
                           for k, v in sorted(s["kinds"].items()))
            print(f"    kinds: {ks}", file=out)


def run_gate(baseline_path: str = BASELINE_DEFAULT,
             tolerance: float = 0.1, update: bool = False,
             as_json: bool = False, compile: bool = True,
             explain: bool = False, memory: bool = False,
             cost: bool = False, protocol: bool = False,
             schedule: bool = False,
             hbm_budget_gib: float = None, out=sys.stdout) -> int:
    """Build, analyze, gate.  Returns the process exit code
    (0 clean / 1 findings or structural regressions / 2 baseline
    missing); predictor drift against XLA is reported, not gated."""
    from . import (AnalysisReport, analyze_handle, get_executable,
                   load_baseline, save_baseline, verify_grad_comm)

    baseline = None
    if not update:
        # fail fast BEFORE the expensive build: a missing baseline is a
        # CI configuration error, not a lint finding
        baseline = load_baseline(baseline_path)
        if baseline is None:
            print(f"no baseline at {baseline_path} — run "
                  f"`python -m hetu_tpu.analysis --update-baseline` "
                  f"and commit the result", file=out)
            return 2

    # rule options: the peak-memory-regression rule reads the frozen
    # per-executable peaks straight from the baseline, so the rule and
    # the baseline gate agree on what "regressed" means
    options = {"memory_tolerance": tolerance,
               "step_time_tolerance": tolerance}
    if baseline is not None:
        options["baseline_peak_bytes"] = {
            name: ex["memory"]["peak_bytes"]
            for name, ex in baseline.get("executables", {}).items()
            if "memory" in ex}
        # predicted-step-regression reads the frozen per-executable
        # step times the same way (baseline pins microseconds)
        options["baseline_step_time_s"] = {
            name: float(ex["cost"]["step_time_us"]) * 1e-6
            for name, ex in baseline.get("executables", {}).items()
            if "cost" in ex}
    if hbm_budget_gib is not None:
        options["hbm_budget_bytes"] = float(hbm_budget_gib) * (1 << 30)

    names = build_gate_executables()
    report = AnalysisReport()
    # ``problems`` are structural and decide the exit code; ``drift`` is
    # predictor accuracy against XLA, reported only (module docstring) —
    # an inaccurate predictor must not switch the sharding gate off
    problems, drift = [], []
    for name in names:
        handle = get_executable(name)
        rep = report.add(analyze_handle(handle, compile=compile,
                                        options=options))
        if handle.meta.get("grad_comm"):
            # PR-1 grad-comm emission assertions, via the general pass
            try:
                verify_grad_comm(handle)
            except AssertionError as e:
                problems.append(f"{name}: grad-comm emission drifted "
                                f"from the DS prediction: {e}")
        # XLA cross-check: the static model is compared with
        # compiled.memory_analysis() at ±10% (abs floor for tiny
        # programs).  Being outside the band is predictor drift;
        # LOSING the cross-check (memory pass or memory_analysis gone)
        # is structural and fails the gate
        if compile:
            mem = rep.meta.get("memory")
            if mem is None:
                problems.append(f"{name}: static memory pass produced "
                                f"no report (walk failure?)")
            elif mem.xla is None:
                problems.append(f"{name}: compiled.memory_analysis() "
                                f"unavailable — XLA cross-check lost")
            elif not mem.xla_within(rel=0.1):
                drift.append(
                    f"{name}: static peak {mem.cmp_peak_bytes} B drifted "
                    f"{mem.xla_delta():+.1%} from XLA's "
                    f"{mem.xla_total} B (±10% cross-check)")
            # step-time cross-check, same stance: FLOP and
            # bytes-accessed totals against cost_analysis() at ±10%
            # (absolute floors for toy-scale programs) are drift, and
            # LOSING the accounting is itself a gate failure
            co = rep.meta.get("cost")
            if co is None:
                problems.append(f"{name}: static cost pass produced "
                                f"no report (walk failure?)")
            elif co.xla is None:
                problems.append(f"{name}: compiled.cost_analysis() "
                                f"unavailable — XLA cross-check lost")
            elif not co.xla_within(rel=0.1):
                fd, bd = co.xla_flops_delta(), co.xla_bytes_delta()
                drift.append(
                    f"{name}: static cost drifted from XLA's "
                    f"cost_analysis (flops "
                    f"{fd:+.1%}, bytes "
                    f"{bd:+.1%}; ±10% cross-check)"
                    if fd is not None and bd is not None else
                    f"{name}: static cost cross-check unavailable")
    if as_json:
        payload = report.to_dict(records=True)
        payload["predictor_drift"] = drift
        print(json.dumps(payload, indent=1, sort_keys=True), file=out)
    else:
        print(report.summary(), file=out)
        if memory:
            memory_section(report, out=out)
        if cost:
            cost_section(report, out=out)
        if protocol:
            protocol_section(report, out=out)
        if schedule:
            schedule_section(report, out=out)
    if explain:
        explain_report(report, out=out, memory=memory, cost=cost)
    if drift:
        print("\nSTATIC PREDICTORS OUTSIDE ±10% OF XLA "
              "(reported, not gated):", file=out)
        for d in drift:
            print(f"  ~ {d}", file=out)
    if update:
        save_baseline(baseline_path, report)
        print(f"baseline written to {baseline_path}", file=out)
        return 0
    problems += report.check_against_baseline(baseline,
                                              tolerance=tolerance)
    if problems:
        print("\nLINT-GRAPH GATE FAILED:", file=out)
        for p in problems:
            print(f"  ! {p}", file=out)
        print(f"\n(intentional change? review and re-freeze with "
              f"`python -m hetu_tpu.analysis --update-baseline`)",
              file=out)
        return 1
    print("\nlint-graph gate OK (baseline "
          f"{os.path.basename(baseline_path)})", file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m hetu_tpu.analysis",
        description="jaxpr/HLO sharding & collectives linter + CI gate "
                    "(exit 0 clean / 1 findings / 2 baseline missing)")
    ap.add_argument("--check", action="store_true",
                    help="gate against the baseline (default action)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-freeze ANALYSIS_BASELINE.json")
    ap.add_argument("--baseline", default=BASELINE_DEFAULT,
                    help=f"baseline path (default {BASELINE_DEFAULT})")
    ap.add_argument("--tolerance", type=float, default=0.1,
                    help="relative byte-regression tolerance (default 0.1;"
                         " collective COUNTS are always exact)")
    ap.add_argument("--format", choices=("text", "json"), default="text",
                    dest="fmt",
                    help="report output format (json: full report with "
                         "records + edge coverage, for CI artifacts)")
    ap.add_argument("--json", action="store_true",
                    help="legacy alias for --format json")
    ap.add_argument("--explain", action="store_true",
                    help="print each finding's offending edge plus a "
                         "suggested remediation (pspec change, donation,"
                         " narrower transport, capacity factor)")
    ap.add_argument("--memory", action="store_true",
                    help="print the static peak-HBM section (predicted "
                         "peak, per-kind breakdown, XLA cross-check "
                         "delta; with --explain, the attribution table)")
    ap.add_argument("--cost", action="store_true",
                    help="print the static step-time section (FLOP/HBM "
                         "roofline verdict, comm time, XLA cost_analysis"
                         " deltas; with --explain, the attribution "
                         "table)")
    ap.add_argument("--protocol", action="store_true",
                    help="print the serving-protocol verifier section "
                         "(event stream size, kind vocabulary, machine "
                         "coverage, lifecycle violations; --explain "
                         "prints each violation's event subtrace)")
    ap.add_argument("--schedule", action="store_true",
                    help="print the cross-rank schedule verifier "
                         "section (per-rank op inventory, hang-freedom "
                         "verdict; --explain prints each divergence's "
                         "per-rank subtraces side by side)")
    ap.add_argument("--hbm-budget", type=float, default=None,
                    metavar="GIB",
                    help="device HBM budget in GiB for the oom-risk "
                         "rule (default: the rule's v5p budget)")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip post-SPMD compilation (disables GSPMD "
                         "accounting: implicit-reshard, the GSPMD half "
                         "of unexplained-collective, and the XLA "
                         "memory cross-check)")
    args = ap.parse_args(argv)
    _force_cpu_mesh()
    return run_gate(baseline_path=args.baseline,
                    tolerance=args.tolerance,
                    update=args.update_baseline,
                    as_json=args.json or args.fmt == "json",
                    compile=not args.no_compile,
                    explain=args.explain,
                    memory=args.memory,
                    cost=args.cost,
                    protocol=args.protocol,
                    schedule=args.schedule,
                    hbm_budget_gib=args.hbm_budget)


if __name__ == "__main__":
    sys.exit(main())
