"""ZeRO-3 on the flat layout: params sharded at rest (PR 19).

Pins down: working parameters live ONLY as each rank's P(dp) chunk of
the per-bucket flat fp32 master; the forward all-gathers every bucket
just-in-time in the weight dtype (tag ``param_gather``) and after the
chunk-local update only the 1/dp shard remains.  Losses are BITWISE the
``flat_state=True, zero=2`` run's on every transport — the gathered
weights are the same fp32 master chunks ZeRO-2's post-update regather
produced, just fetched one step later.  The analysis tripod sees all of
it: the gather is a priced ``param_gather`` edge family
(``param-gather-unpriced``), the at-rest side is policed by
``grad-allgather-under-zero2`` / ``replicated-state-under-shard``, the
memory pass predicts the at-rest saving, and the planner's DP search
gains ZeRO-3 as a searchable stage.  Adafactor joins the flat path with
factored row/col stats (1-D/small params fall back to the full second
moment) and exactly the declared extra psums per bucket.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu import analysis, ops, optim
from hetu_tpu.parallel import create_mesh

UNEVEN = [(7, 5), (13,), (3,), (11, 3)]     # nothing divisible by dp=8


def _train(devices8, transport="fp32", zero=3, flat=True, steps=4,
           shapes=(), opt_cls=optim.AdamOptimizer, opt_kw=None):
    """Linear regression on the virtual-8 mesh (same harness as
    test_flat_zero2); returns (losses, graph, optimizer, w)."""
    mesh = create_mesh({"dp": 8}, devices8)
    with ht.graph("define_and_run", create_new=True, mesh=mesh) as g:
        x = ht.parallel_placeholder("float32", (16, 8),
                                    pspec=P("dp", None), name="x")
        y = ht.parallel_placeholder("float32", (16, 1),
                                    pspec=P("dp", None), name="y")
        rng = np.random.RandomState(7)
        w = ht.parameter((0.1 * rng.randn(8, 1)).astype(np.float32),
                         name="w")
        b = ht.parameter(np.zeros((1,), np.float32), name="b")
        extras = [ht.parameter(
            (0.1 * rng.randn(*s)).astype(np.float32), name=f"p{i}")
            for i, s in enumerate(shapes)]
        loss = ops.reduce_mean((ops.matmul(x, w) + b - y) ** 2)
        for p in extras:
            loss = loss + ops.reduce_mean(p ** 2)
        op = opt_cls(lr=1e-2, zero=zero, grad_comm=transport,
                     flat_state=flat, **(opt_kw or {})).minimize(loss)
        X = np.random.RandomState(0).randn(16, 8).astype(np.float32)
        Y = np.random.RandomState(1).randn(16, 1).astype(np.float32)
        losses = []
        for _ in range(steps):
            o = g.run(loss, [loss, op], {x: X, y: Y})
            losses.append(float(np.asarray(o[0])))
        if flat:
            assert g._grad_comm_active, g._grad_comm_fallback
        return losses, g, op.producer.attrs["optimizer"], w


class TestZero3LossEquivalence:
    @pytest.mark.parametrize("transport", ["fp32", "bf16", "int8"])
    def test_bitwise_matches_flat_zero2(self, devices8, transport):
        """ZeRO-3's just-in-time gather reads the SAME fp32 master
        chunks ZeRO-2's post-update regather broadcast — losses and
        params are bitwise equal on every transport."""
        l2, _, _, _ = _train(devices8, transport, zero=2)
        l3, g3, opt3, w = _train(devices8, transport, zero=3)
        assert l2 == l3                       # bitwise, not allclose
        # reading a param goes through the stale-refresh path: the
        # working copy rematerializes from the flat master exactly
        w3 = np.asarray(g3.get_tensor_value(w))
        assert w3.shape == (8, 1) and np.isfinite(w3).all()

    def test_uneven_params_and_padding(self, devices8):
        l2, _, _, _ = _train(devices8, "fp32", zero=2, shapes=UNEVEN)
        l3, _, opt3, _ = _train(devices8, "fp32", zero=3, shapes=UNEVEN)
        assert l2 == l3
        lay = opt3._flat_layout
        assert all(sz % 8 == 0 for sz in lay.padded_sizes)

    def test_matches_per_param_baseline(self, devices8):
        """Against the implicit all-reduce baseline the curve matches to
        fp32 reduction-order tolerance."""
        base, g0, _, _ = _train(devices8, None, zero=0, flat=False)
        assert not g0._grad_comm_active
        got, _, _, _ = _train(devices8, "fp32", zero=3)
        np.testing.assert_allclose(got, base, rtol=1e-5)

    def test_params_dropped_from_step_outputs(self, devices8):
        """After a step only the 1/dp master chunks are authoritative:
        trainables are not among the jitted step's var outputs, and the
        resident working copies stay dp-sharded."""
        _, g, opt, w = _train(devices8, "fp32", zero=3, steps=2)
        assert opt.zero == 3
        sh = g._var_data[w.id].sharding
        assert tuple(sh.spec)[:1] == ("dp",)   # dim-0 dp-sharded at rest


class TestZero3Emission:
    @pytest.mark.parametrize("transport", ["fp32", "bf16", "int8"])
    def test_param_gather_predicted_and_emitted(self, devices8,
                                                transport):
        _, g, _, _ = _train(devices8, transport, zero=3, steps=1)
        (handle,) = g.analysis_handles()
        gc = handle.meta["grad_comm"]
        assert gc["flat"] is True and gc["zero"] == 3
        assert handle.meta["allowed_gspmd"] == {}
        analysis.verify_grad_comm(handle)
        pred, _ = analysis.grad_comm_prediction(handle)
        gathers = [p for p in pred if p["kind"] == "all_gather"]
        # exactly the per-bucket weight gathers, all tagged param_gather
        # (no post-update param_comm regather remains)
        assert gathers and all(p.get("tag") == "param_gather"
                               for p in gathers)
        rep = analysis.analyze_handle(handle)
        pg = [r for r in rep.records if "param_gather" in r.scope]
        pc = [r for r in rep.records if "param_comm" in r.scope]
        assert len(pg) == len(gathers) and pc == []
        assert all(r.kind == "all_gather" for r in pg)

    def test_clean_under_all_rules(self, devices8):
        _, g, _, _ = _train(devices8, "fp32", zero=3, steps=1)
        (handle,) = g.analysis_handles()
        full = analysis.analyze_handle(handle, compile=True)
        assert full.findings == [], full.findings
        # the param_gather edge family is priced: payload bytes > 0
        em = full.meta["edge_match"]
        priced = [e for e in full.meta["edges"]
                  if e.tag == "param_gather"]
        assert priced and all(e.payload_bytes > 0 for e in priced)

    def test_param_gather_unpriced_fires_without_edge(self, devices8):
        """Misdeclaring the plan as zero=2 removes the priced
        param_gather edge while the program still emits the gathers —
        the new rule must fail it."""
        _, g, _, _ = _train(devices8, "fp32", zero=3, steps=1)
        (handle,) = g.analysis_handles()
        ctx = analysis.build_context(handle)
        assert analysis.run_rules(
            ctx, only=["param-gather-unpriced"]) == []
        handle.meta["grad_comm"]["zero"] = 2
        try:
            ctx2 = analysis.build_context(handle)
            fnds = analysis.run_rules(ctx2,
                                      only=["param-gather-unpriced"])
            assert fnds and all(f.rule == "param-gather-unpriced"
                                for f in fnds)
        finally:
            handle.meta["grad_comm"]["zero"] = 3

    def test_replicated_state_rule_learns_zero3(self, devices8):
        """Under zero>=3 the rule also polices the at-rest claim:
        resident param bytes at the full replicated size mean the
        saving never materializes."""
        _, g, _, _ = _train(devices8, "fp32", zero=3, steps=1)
        (handle,) = g.analysis_handles()
        ctx = analysis.build_context(
            handle, options={"param_bytes_threshold": 1})
        assert analysis.run_rules(
            ctx, only=["replicated-state-under-shard"]) == []
        # simulate the broken contract: full trainable set resident
        full = sum(p.nbytes for p in ctx.params if p.trainable)
        ctx.memory.by_kind["param"] = full
        fnds = analysis.run_rules(ctx,
                                  only=["replicated-state-under-shard"])
        assert len(fnds) == 1 and "sharded at rest" in fnds[0].message


class TestZero3Memory:
    def test_at_rest_param_bytes_drop(self, devices8):
        """The memory pass sees the params leave the at-rest set: the
        zero-2 plan keeps every trainable replicated per rank, the
        zero-3 plan keeps none (>=2x saving on the param class)."""
        _, g2, _, _ = _train(devices8, "fp32", zero=2, steps=1)
        (h2,) = g2.analysis_handles()
        m2 = analysis.predict_memory(h2)
        _, g3, _, _ = _train(devices8, "fp32", zero=3, steps=1)
        (h3,) = g3.analysis_handles()
        m3 = analysis.predict_memory(h3)
        p2 = int(m2.by_kind.get("param", 0))
        p3 = int(m3.by_kind.get("param", 0))
        assert p2 > 0 and p3 == 0             # params absent at rest
        assert p2 >= 2 * max(p3, 1) or p3 == 0
        assert m3.resident_bytes < m2.resident_bytes


class TestZero3Adafactor:
    SHAPES = [(8, 6), (8, 8), (13,), (6, 4), (3,)]
    KW = dict(min_dim_size_to_factor=4)

    def _run(self, devices8, zero, flat, **kw):
        return _train(devices8, "fp32", zero=zero, flat=flat,
                      shapes=self.SHAPES, steps=5,
                      opt_cls=optim.AdafactorOptimizer,
                      opt_kw={**self.KW, **kw})

    @pytest.mark.parametrize("kw", [{}, {"momentum": 0.9},
                                    {"clipping_threshold": None}])
    def test_flat_matches_optax_reference(self, devices8, kw):
        """The flat reimplementation follows optax.adafactor's exact
        chain; z2 and z3 apply the same update bitwise: every loss an
        update has touched, the flat fp32 master (every parameter) and
        the factored stats.  The loss of step 0 is taken BEFORE any
        update, by two different XLA programs whose forward reductions
        nothing holds to one order (it differs by a float32 ulp under
        jaxlib 0.9.0), so it is held to rtol only."""
        ref, _, _, _ = self._run(devices8, 0, False, **kw)
        l2, g2, o2, w2 = self._run(devices8, 2, True, **kw)
        l3, g3, o3, w3 = self._run(devices8, 3, True, **kw)
        assert l2[1:] == l3[1:], kw
        np.testing.assert_allclose(l2, l3, rtol=1e-6, atol=0)
        for slot in ("flat_master", "flat_v", "fac_row", "fac_col"):
            for a, b in zip(jax.tree_util.tree_leaves(o2._state[slot]),
                            jax.tree_util.tree_leaves(o3._state[slot])):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b), slot)
        np.testing.assert_array_equal(
            np.asarray(g2.get_tensor_value(w2)),
            np.asarray(g3.get_tensor_value(w3)))
        np.testing.assert_allclose(l2, ref, rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(l3, ref, rtol=2e-4, atol=1e-6)

    def test_factored_lanes_keep_zero_v(self, devices8):
        """Factored matrices ride the replicated row/col EMAs; their
        lanes of the flat v slot stay exactly zero (1-D params keep the
        full second moment there)."""
        _, _, opt, _ = self._run(devices8, 2, True)
        lay = opt._flat_layout
        per = lay.unpack(opt._state["flat_v"])
        by_shape = {tuple(np.shape(v)): np.asarray(v)
                    for v in per.values()}
        assert np.all(by_shape[(8, 6)] == 0)        # factored
        assert np.abs(by_shape[(13,)]).max() > 0    # 1-D fallback
        assert any(np.abs(np.asarray(v)).max() > 0
                   for v in opt._state["fac_row"])

    def test_declared_psums_verify_exactly(self, devices8):
        _, g, opt, _ = self._run(devices8, 3, True)
        (handle,) = g.analysis_handles()
        extra = opt._flat_comm_extra()
        nb = len(opt._flat_layout.buckets)
        assert extra == {"all_reduce": 2 * nb}   # stats + clip psum
        assert handle.meta["grad_comm"]["opt_extra"] == extra
        analysis.verify_grad_comm(handle)
        full = analysis.analyze_handle(handle, compile=True)
        assert full.findings == [], full.findings


class TestZero3Planner:
    def test_dp_search_gains_zero3_stage(self):
        from hetu_tpu.planner import (ChipSpec, ClusterSpec,
                                      SearchEngine, Strategy,
                                      layer_memory,
                                      transformer_layer_spec)
        cluster = ClusterSpec(chip=ChipSpec(hbm_bytes=95e9), num_chips=8)
        layers = [transformer_layer_spec(8, 1024, 1024, 4096,
                                         name=f"blocks{i}")
                  for i in range(4)]
        eng = SearchEngine(cluster, layers, global_batch=64,
                           micro_batch=8)
        cands = eng._mem_variants(8, 1)
        assert any(st.zero == 3 for st in cands)
        # dp=1 has nothing to shard: zero stages collapse to 0
        assert all(st.zero == 0 for st in eng._mem_variants(1, 8))
        # the cost model prices the extra saving: zero-3 beats zero-2
        # on per-rank memory for the same layout
        m2 = layer_memory(layers[0], Strategy(dp=8, tp=1, zero=2),
                          cluster)
        m3 = layer_memory(layers[0], Strategy(dp=8, tp=1, zero=3),
                          cluster)
        assert m3 < m2
