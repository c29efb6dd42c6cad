"""Auto-parallel planner tests: native DP core vs Python fallback, the
Galvatron-style search engine, and the v1-style searching strategies."""
import numpy as np
import pytest

from hetu_tpu.csrc.build import load_dp_core
from hetu_tpu.planner import (ChipSpec, ClusterSpec, FlexFlowSearching,
                              GPipeSearching, LayerSpec, OptCNNSearching,
                              PipeDreamSearching, PipeOptSearching,
                              SearchEngine, Strategy,
                              solve_layer_strategies,
                              solve_pipeline_partition,
                              transformer_layer_spec)
from hetu_tpu.nn.parallel import config2ds


def _cluster(chips=8, hbm=95e9):
    return ClusterSpec(chip=ChipSpec(hbm_bytes=hbm), num_chips=chips)


class TestNativeCore:
    def test_native_library_builds(self):
        lib = load_dp_core()
        assert lib is not None, "g++ is available in this image; the " \
            "native DP core must build"

    def test_strategy_solver_native_matches_python(self):
        rng = np.random.RandomState(0)
        for _ in range(5):
            L, S, M = 6, 4, 16
            mem = rng.randint(0, 5, (L, S)).astype(np.int32)
            intra = rng.rand(L, S)
            inter = rng.rand(L, S, S) * 0.1
            cn, rn = solve_layer_strategies(mem, intra, inter, M,
                                            use_native=True)
            cp, rp = solve_layer_strategies(mem, intra, inter, M,
                                            use_native=False)
            assert np.isclose(cn, cp), (cn, cp)
            assert rn == rp

    def test_strategy_solver_respects_memory(self):
        # two strategies: fast-but-fat vs slow-but-lean
        L = 4
        mem = np.array([[4, 1]] * L, np.int32)
        intra = np.array([[1.0, 3.0]] * L)
        inter = np.zeros((L, 2, 2))
        # generous budget -> all fast
        c, r = solve_layer_strategies(mem, intra, inter, max_mem=17)
        assert r == [0] * L and np.isclose(c, 4.0)
        # tight budget -> forced lean
        c, r = solve_layer_strategies(mem, intra, inter, max_mem=5)
        assert r == [1] * L and np.isclose(c, 12.0)
        # infeasible
        c, r = solve_layer_strategies(mem, intra, inter, max_mem=2)
        assert r is None and np.isinf(c)

    def test_strategy_solver_transition_cost(self):
        # strategy switch costs 10 -> stick to one strategy even if the
        # per-layer optimum alternates
        L = 4
        mem = np.zeros((L, 2), np.int32)
        intra = np.array([[1.0, 1.1], [1.1, 1.0]] * 2)
        inter = np.zeros((L, 2, 2))
        for i in range(1, L):
            inter[i] = np.array([[0.0, 10.0], [10.0, 0.0]])
        _, r = solve_layer_strategies(mem, intra, inter, max_mem=1)
        assert len(set(r)) == 1  # no switching

    def test_pipeline_partition_native_matches_python(self):
        rng = np.random.RandomState(1)
        for _ in range(5):
            costs = rng.rand(12)
            comm = rng.rand(12) * 0.1
            bn, sn = solve_pipeline_partition(costs, 4, comm,
                                              use_native=True)
            bp, sp_ = solve_pipeline_partition(costs, 4, comm,
                                               use_native=False)
            assert np.isclose(bn, bp), (bn, bp)
            assert sn == sp_

    def test_pipeline_partition_balances(self):
        costs = [1.0] * 8
        bottleneck, stages = solve_pipeline_partition(costs, 4)
        assert [len(s) for s in stages] == [2, 2, 2, 2]
        assert np.isclose(bottleneck, 2.0)
        # uneven: one heavy layer gets isolated
        costs = [1.0, 1.0, 1.0, 10.0, 1.0, 1.0]
        _, stages = solve_pipeline_partition(costs, 3)
        heavy_stage = [s for s in stages if 3 in s][0]
        assert heavy_stage == [3]

    def test_pipeline_partition_covers_all_layers(self):
        _, stages = solve_pipeline_partition([1.0] * 7, 3)
        flat = [i for s in stages for i in s]
        assert flat == list(range(7))


def _gpt_layers(n=12, batch=8, seq=1024, hidden=1024):
    return [transformer_layer_spec(batch, seq, hidden, 4 * hidden,
                                   name=f"blocks{i}") for i in range(n)]


class TestSearchEngine:
    def test_finds_feasible_plan(self):
        eng = SearchEngine(_cluster(), _gpt_layers(), global_batch=64,
                           micro_batch=8)
        plan = eng.search()
        assert np.isfinite(plan.time) and plan.time > 0
        assert len(plan.layer_strategies) == 12
        assert sum(len(s) for s in plan.stages) == 12
        for st in plan.layer_strategies:
            assert st.dp * st.tp == 8 // plan.pp

    def test_tight_memory_forces_memory_savers(self):
        """On a tiny-HBM chip the plan must reach for recompute/zero/pp."""
        small = _cluster(hbm=3e9)
        eng = SearchEngine(small, _gpt_layers(hidden=2048), global_batch=64,
                           micro_batch=8)
        plan = eng.search()
        assert any(st.recompute or st.zero > 0
                   for st in plan.layer_strategies) or plan.pp > 1

    def test_infeasible_raises(self):
        nano = _cluster(hbm=1e6)  # 1 MB HBM: nothing fits
        eng = SearchEngine(nano, _gpt_layers(), global_batch=64,
                           micro_batch=8)
        with pytest.raises(RuntimeError, match="no feasible plan"):
            eng.search()

    def test_memory_cap_fed_by_analysis_backed_model(self):
        """ISSUE 8: the planner's HBM budget check runs on the numbers
        the static peak-HBM pass validated — a MemoryCalibration from
        ``calibrate_layer_memory`` (ratio of ``analysis.predict_memory``
        over the closed form on a lowered single-layer train-step
        probe) scales every ``layer_memory`` byte the DP solver sees."""
        from hetu_tpu.planner import (MemoryCalibration, layer_memory,
                                      calibrate_layer_memory)
        cal = calibrate_layer_memory()
        # the calibration really comes from the static pass: both sides
        # measured, scale is their ratio
        assert cal.static_bytes > 0 and cal.model_bytes > 0
        assert cal.scale == pytest.approx(
            cal.static_bytes / cal.model_bytes)
        spec = transformer_layer_spec(64, 1024, 1024, 4096, 2)
        base = layer_memory(spec, Strategy(), _cluster())
        got = layer_memory(spec, Strategy(), _cluster(), calibration=cal)
        assert got == pytest.approx(base * cal.scale)
        # the engine threads it into the budget check it hands the DP
        eng = SearchEngine(_cluster(), _gpt_layers(), global_batch=64,
                           micro_batch=8, memory_calibration=cal)
        assert eng.memory_calibration is cal
        plan = eng.search()
        assert np.isfinite(plan.time)

    def test_solver_rejects_plan_exceeding_static_peak(self):
        """ISSUE 8: a plan whose ANALYSIS-PREDICTED peak exceeds the
        chip HBM budget must be rejected even when the closed-form
        heuristic would have accepted it — the cap is enforced on the
        calibrated numbers."""
        from hetu_tpu.planner import MemoryCalibration
        cluster = _cluster(hbm=30e9)
        layers = _gpt_layers(hidden=2048)
        # uncalibrated closed form: fits comfortably
        eng = SearchEngine(cluster, layers, global_batch=64,
                           micro_batch=8, allow_recompute=False,
                           allow_zero=False)
        eng.search()
        # static pass says every layout needs 100x what the heuristic
        # thought: the same search must now reject every plan
        bloat = MemoryCalibration(scale=100.0, static_bytes=1,
                                  model_bytes=1.0)
        eng2 = SearchEngine(cluster, layers, global_batch=64,
                            micro_batch=8, allow_recompute=False,
                            allow_zero=False, memory_calibration=bloat)
        with pytest.raises(RuntimeError, match="no feasible plan"):
            eng2.search()

    def test_layer_time_fed_by_analysis_backed_model(self):
        """ISSUE 10: the planner's step-time scoring runs on the
        numbers the static cost pass validated — a TimeCalibration
        from ``calibrate_layer_time`` (ratio of
        ``analysis.predict_cost`` over the closed form on a lowered
        single-layer train-step probe) scales every ``layer_time``
        roofline the DP solver ranks with, exactly as
        ``calibrate_layer_memory`` does for bytes."""
        from hetu_tpu.planner import (TimeCalibration, calibrate_layer_time,
                                      layer_time)
        cal = calibrate_layer_time()
        # the calibration really comes from the static pass: counted
        # probe FLOPs are real (close to the closed form's 3x-fwd
        # estimate), and the scale is the measured ratio
        assert cal.static_s > 0 and cal.model_s > 0
        assert cal.scale == pytest.approx(cal.static_s / cal.model_s)
        assert cal.static_flops == pytest.approx(cal.model_flops,
                                                 rel=0.5)
        spec = transformer_layer_spec(64, 1024, 1024, 4096, 2)
        base = layer_time(spec, Strategy(), _cluster(),
                          include_grad_sync=False)
        got = layer_time(spec, Strategy(), _cluster(),
                         include_grad_sync=False, calibration=cal)
        assert got == pytest.approx(base * cal.scale)
        # comm terms are added AFTER the scaled roofline (the probe is
        # single-device: it cannot calibrate collectives)
        st = Strategy(dp=8)
        with_sync = layer_time(spec, st, _cluster(), calibration=cal)
        no_sync = layer_time(spec, st, _cluster(),
                             include_grad_sync=False, calibration=cal)
        from hetu_tpu.planner import grad_sync_time
        assert with_sync - no_sync == pytest.approx(
            grad_sync_time(spec, st, _cluster()))
        # the engine threads it into every candidate it scores
        eng = SearchEngine(_cluster(), _gpt_layers(), global_batch=64,
                           micro_batch=8,
                           time_calibration=TimeCalibration(scale=3.0))
        l0 = self_time = eng._layer_time(_gpt_layers()[0], Strategy())
        eng_plain = SearchEngine(_cluster(), _gpt_layers(),
                                 global_batch=64, micro_batch=8)
        assert self_time == pytest.approx(
            3.0 * eng_plain._layer_time(_gpt_layers()[0], Strategy()))
        assert np.isfinite(l0)

    def test_planner_beats_every_hand_written_gate_family_plan(self):
        """ISSUE 10 acceptance: the searched plan must beat (or tie)
        every hand-written gate-family layout on predicted step time,
        scored with the SAME calibrated model — the search covers a
        superset of the hand layouts, so losing to one would mean the
        scorer and the search disagree."""
        from hetu_tpu.models.gpt import GPTConfig
        from hetu_tpu.planner import hand_plan_times, plan_for_gpt
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=64, dtype="bfloat16")
        # calibration=None keeps the test fast (no probe lowering);
        # both sides then score with the identical uncalibrated model,
        # which is the property under test
        plan = plan_for_gpt(cfg, global_batch=16, seq=64, n_chips=8,
                            memory_calibration=None,
                            time_calibration=None)
        hand = hand_plan_times(cfg, global_batch=16, seq=64, n_chips=8,
                               time_calibration=None)
        assert set(hand) == {"dp8_zero2_flat", "dp2_tp4_sp", "pp4_dp2",
                             "pp2_dp2_tp2"}
        for name, t in hand.items():
            assert plan.time <= t * (1 + 1e-9), (name, plan.time, t)

    def test_measured_links_feed_the_shared_alpha_beta_formulas(self):
        """ISSUE 10 satellite: Calibration.to_cluster_spec folds the
        measured per-link (alpha, beta) fits into the SAME formulas
        the solver and the analysis linter price collectives with."""
        from hetu_tpu.planner import (Calibration, all_gather_time,
                                      all_reduce_time, collective_time)
        cal = Calibration(matmul_flops={512: 50e12}, hbm_bw=500e9,
                          collectives={"all_reduce": (2e-6, 1e-9),
                                       "p2p": (1e-6, 5e-10)},
                          device_kind="v5p", platform="tpu")
        cluster = cal.to_cluster_spec(num_chips=4)
        assert cluster.link_alpha_beta["all_reduce"] == (2e-6, 1e-9)
        want = 2e-6 + 1e-9 * 1e6
        assert all_reduce_time(1e6, 4, cluster) == pytest.approx(want)
        assert collective_time("all_reduce", 1e6, 4, cluster) == \
            pytest.approx(want)
        # kinds without a fit keep the ring model
        ring = all_gather_time(1e6, 4, ClusterSpec(chip=cluster.chip,
                                                   num_chips=4))
        assert all_gather_time(1e6, 4, cluster) == pytest.approx(ring)
        # the chip side still folds the measured roofline numbers
        assert cluster.chip.hbm_bw == 500e9

    def test_plan_for_gpt_closes_the_loop(self):
        """plan_for_gpt: GPTConfig -> layer chain -> searched plan with a
        micro-batch sweep (the train_gpt --auto-parallel entry,
        reference hybrid_parallel_config.py:13)."""
        from hetu_tpu.models.gpt import GPTConfig
        from hetu_tpu.planner import plan_for_gpt, plan_summary
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024, sp=False,
                        dtype="bfloat16")
        # single chip: the only legal layout
        p1 = plan_for_gpt(cfg, global_batch=32, seq=1024, n_chips=1)
        s1 = plan_summary(p1)
        assert (s1["pp"], s1["dp"], s1["tp"]) == (1, 1, 1)
        assert s1["micro_batch"] is not None
        assert 32 % s1["micro_batch"] == 0
        # 8 chips: plan must use the whole grid
        p8 = plan_for_gpt(cfg, global_batch=64, seq=1024, n_chips=8)
        s8 = plan_summary(p8)
        assert s8["pp"] * s8["dp"] * s8["tp"] == 8
        for key in ("zero", "recompute_layers", "est_step_time_ms",
                    "num_microbatches"):
            assert key in s8
        # calibration folds into the chip spec without breaking the search
        from hetu_tpu.planner import Calibration
        cal = Calibration(matmul_flops={1024: 100e12}, hbm_bw=700e9,
                          device_kind="v5 lite", platform="tpu")
        pc = plan_summary(plan_for_gpt(cfg, global_batch=32, seq=1024,
                                       n_chips=1, calibration=cal))
        assert (pc["pp"], pc["dp"], pc["tp"]) == (1, 1, 1)

    def test_ds_parallel_config_roundtrip(self):
        eng = SearchEngine(_cluster(), _gpt_layers(n=8), global_batch=64,
                           micro_batch=8)
        plan = eng.search()
        cfg = plan.to_ds_parallel_config()
        assert len(cfg["layers"]) == 8

        def _leaf_entries(d):
            if "type" in d:
                yield d
                return
            for v in d.values():
                if isinstance(v, dict):
                    yield from _leaf_entries(v)

        # every emitted per-weight entry parses through config2ds, with
        # the generator schema's shard dims (col-parallel dim 1,
        # row-parallel dim 0)
        for name, entry in cfg["layers"].items():
            leaves = list(_leaf_entries(entry))
            assert len(leaves) == 6  # ln1, qkv, dense, ln2, fc1, fc2
            for leaf in leaves:
                ds_union, dgs = config2ds(leaf)
                ds = ds_union.get(0)
                assert ds.device_num == len(dgs[0])
            assert entry["attn"]["qkv"]["split"].keys() <= {"1"}
            assert entry["attn"]["dense"]["split"].keys() <= {"0"}


class TestV1Strategies:
    def test_optcnn_prefers_tp_free_layers_consistent(self):
        layers = _gpt_layers(n=6, hidden=512)
        r = OptCNNSearching(layers, _cluster()).searching()
        assert len(r.strategies) == 6
        assert np.isfinite(r.cost)
        # all-devices factorization respected
        for st in r.strategies:
            assert st.dp * st.tp == 8

    def test_flexflow_beats_or_ties_worst_random(self):
        layers = _gpt_layers(n=6, hidden=512)
        ff = FlexFlowSearching(layers, _cluster(), round_budget=300, seed=3)
        r = ff.searching()
        # the MCMC result can't be worse than every candidate: compare
        # against the single worst uniform assignment
        worst = max(ff.simulate([st] * 6)
                    for st in ff._device_factor_candidates())
        assert r.cost <= worst + 1e-12

    def test_flexflow_close_to_optcnn_optimum(self):
        layers = _gpt_layers(n=6, hidden=512)
        opt = OptCNNSearching(layers, _cluster()).searching()
        ff = FlexFlowSearching(layers, _cluster(), round_budget=800,
                               seed=0).searching()
        assert ff.cost <= opt.cost * 1.5 + 1e-9

    def test_gpipe_contiguous_stages(self):
        layers = _gpt_layers(n=8, hidden=512)
        r = GPipeSearching(layers, _cluster(), num_stages=4).searching()
        assert r.stages is not None and len(r.stages) == 4
        flat = [i for s in r.stages for i in s]
        assert flat == list(range(8))

    def test_pipedream_replicates_heavy_stages(self):
        # one very heavy layer among light ones: PipeDream should give the
        # heavy layer('s stage) more devices
        layers = [transformer_layer_spec(8, 256, 256, 1024)
                  for _ in range(5)]
        layers.insert(2, transformer_layer_spec(8, 256, 1024, 8192))
        r = PipeDreamSearching(layers, _cluster(chips=4)).searching()
        repl = r.meta["replication"]
        heavy_stage = [k for k, sg in enumerate(r.stages) if 2 in sg][0]
        assert repl[heavy_stage] == max(repl)

    def test_pipeopt_picks_best_stage_count(self):
        layers = _gpt_layers(n=8, hidden=512)
        r = PipeOptSearching(layers, _cluster(),
                             stage_options=[1, 2, 4]).searching()
        per_stage_costs = [GPipeSearching(layers, _cluster(), p).searching().cost
                           for p in (1, 2, 4)]
        assert r.meta["num_stages"] in (1, 2, 4)
        assert np.isfinite(r.cost)
        assert r.cost <= min(per_stage_costs) + 1e-12
