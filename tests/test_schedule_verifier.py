"""Cross-rank collective-schedule verifier (ISSUE 20).

The verifier extracts per-rank symbolic communication schedules (ZeRO-3
front gathers, dp grad buckets, tp/cp collectives, pipeline p2p,
hot-switch repack transfers) and proves cross-rank consistency: the
full strategy grid verifies with ZERO violations, every seeded
divergence in the bug corpus is flagged by EXACTLY its rule with a
per-rank explanatory subtrace, the vacuity registry keeps each rule
honest about the op kinds it inspects, and the MPMD runtime's executed
p2p order matches the symbolic projection the verifier checks.
"""
import json
import os

import numpy as np
import pytest

from hetu_tpu.analysis.rules import RULES, SCHEDULE_RULE_OP_KINDS
from hetu_tpu.analysis.schedule import (COLLECTIVE_KINDS, P2P_KINDS,
                                        SCHEDULE_RULES, CommOp, ProgramSpec,
                                        _reference_spec, extract_schedules,
                                        seeded_bug_corpus, spec_from_meta,
                                        strategy_grid, verify_schedules)
from hetu_tpu.parallel.schedule import (generate_gpipe_schedule,
                                        generate_pipedream_flush_schedule,
                                        p2p_events)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# spec construction is symbolic and cheap; extraction happens in-test
GRID = list(strategy_grid())
CORPUS = seeded_bug_corpus()


def _load_baseline():
    with open(os.path.join(REPO, "ANALYSIS_BASELINE.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# extraction: every op plane lands, in the documented order
# ---------------------------------------------------------------------------


class TestExtraction:
    def test_reference_spec_populates_every_plane(self):
        sched = extract_schedules(_reference_spec())
        assert sorted(sched) == list(range(8))
        tags = {o.tag for ops in sched.values() for o in ops}
        kinds = {o.kind for ops in sched.values() for o in ops}
        # ZeRO-3 front gathers lead every rank's program (PR 19's
        # at-rest sharding: weights materialize before any forward math)
        for r, ops in sched.items():
            assert ops and ops[0].kind == "all_gather"
            assert ops[0].tag == "param_gather", (r, ops[0])
        assert any(t.startswith("tp/") for t in tags)          # tp plane
        assert any(t.startswith("pipe") for t in tags)         # p2p plane
        assert any(t.startswith("grad_comm/") or t == "fetch/scalar"
                   for t in tags)                              # grad tail
        assert any(t.startswith("switch/repack/") for t in tags)
        assert {"send", "recv"} <= kinds
        assert verify_schedules(sched) == []

    def test_uneven_per_pipe_micro_batches_differ(self):
        """Malleus apportionment: pipe 0 runs 3 micro-batches, pipe 1
        runs 1 — their p2p inventories differ but still pair up."""
        sched = extract_schedules(_reference_spec())
        # rank = ((p*dp + d)*cp + c)*tp + t: stage outermost, so the
        # pipe index is the dp coordinate — pipe 1's stage 0 is rank 2
        pipe0 = [o for o in sched[0] if o.tag.startswith("pipe")]
        pipe1 = [o for o in sched[2] if o.tag.startswith("pipe")]
        assert len(pipe0) > len(pipe1) > 0

    def test_grad_plane_matches_optimizer_contract(self):
        """The schedule's grad ops ARE the optimizer's predicted step
        collectives — Optimizer.predicted_step_collectives is the single
        source of truth, so the two planes cannot drift."""
        from hetu_tpu.optim import AdamOptimizer
        spec = ProgramSpec(dp=2, zero=3, flat=True, transport="fp32")
        opt = AdamOptimizer(lr=1e-3, zero=3, grad_comm="fp32",
                            flat_state=True)
        preds, extra = opt.predicted_step_collectives(spec.entries,
                                                      spec.dp)
        want = [(p["kind"], int(p["payload_bytes"]), p["dtype"])
                for p in preds]
        want += [(k, 4, "float32") for k, n in sorted(extra.items())
                 for _ in range(int(n))]
        sched = extract_schedules(spec)
        for r, ops in sched.items():
            got = [(o.kind, o.payload_bytes, o.dtype) for o in ops]
            assert sorted(got) == sorted(want), r

    def test_ring_cp_emits_hop_chain(self):
        spec = ProgramSpec(dp=1, cp=4, cp_mode="ring", entries=())
        sched = extract_schedules(spec)
        hops = [o for o in sched[0] if o.kind == "ppermute"]
        # cp-1 hops per layer per phase (fwd+bwd), 2 layers, 2 mbs
        assert len(hops) == 3 * 2 * 2 * spec.num_micro_batches
        assert verify_schedules(sched) == []


class TestSpecFromMeta:
    def test_explicit_schedule_spec_wins(self):
        spec = spec_from_meta({"schedule_spec": {"dp": 2, "tp": 4},
                               "grad_comm": {"device_num": 8,
                                             "entries": []}}, {})
        assert (spec.dp, spec.tp) == (2, 4)

    def test_grad_comm_meta(self):
        meta = {"grad_comm": {"device_num": 4, "zero": 3, "flat": True,
                              "transport": "int8",
                              "entries": [("w", (8, 8), "float32")]}}
        spec = spec_from_meta(meta, {"tp": 2})
        assert (spec.dp, spec.tp, spec.zero, spec.flat) == (4, 2, 3, True)
        sched = extract_schedules(spec)
        assert len(sched) == 8 and verify_schedules(sched) == []

    def test_spmd_pipeline_meta_uses_mesh_extent(self):
        """The SPMD pipeline registration has no num_stages key — its
        stage count is the pp mesh extent (the PR 20 gate regression:
        gate_pipe_spmd must make a multi-rank claim)."""
        spec = spec_from_meta({"pipeline": {"pp_axis": "pp", "hops": 5}},
                              {"pp": 4})
        assert spec is not None and spec.pp == 4
        assert spec.pipeline_mode == "spmd"
        sched = extract_schedules(spec)
        assert len(sched) == 4
        assert any(o.kind == "ppermute" for o in sched[0])
        assert verify_schedules(sched) == []

    def test_no_multi_rank_claim_is_none(self):
        assert spec_from_meta({}, {}) is None
        assert spec_from_meta({"pipeline": {"num_stages": 1}}, {}) is None


# ---------------------------------------------------------------------------
# the clean grid: every strategy point verifies hang-free
# ---------------------------------------------------------------------------


class TestCleanGrid:
    def test_grid_spans_the_strategy_axes(self):
        labels = [l for l, _ in GRID]
        assert len(GRID) >= 40
        for probe in ("z0", "z2", "z3", "_spmd", "_mpmd", "_switch",
                      "cp2", "tp2", "pp2"):
            assert any(probe in l for l in labels), probe

    @pytest.mark.parametrize("label,spec", GRID,
                             ids=[l for l, _ in GRID])
    def test_grid_point_verifies_clean(self, label, spec):
        sched = extract_schedules(spec)
        assert sorted(sched) == list(range(spec.world))
        violations = verify_schedules(sched)
        assert violations == [], \
            [f"{v.rule}: {v.message}" for v in violations]


# ---------------------------------------------------------------------------
# seeded-bug corpus: each divergence found by EXACTLY its rule
# ---------------------------------------------------------------------------


class TestSeededCorpus:
    def test_corpus_covers_every_rule(self):
        assert len(CORPUS) >= 6
        assert {e["rule"] for e in CORPUS} == set(SCHEDULE_RULES)

    @pytest.mark.parametrize("entry", CORPUS,
                             ids=[e["name"] for e in CORPUS])
    def test_seeded_divergence_found_by_exactly_its_rule(self, entry):
        violations = verify_schedules(entry["schedules"])
        assert violations, entry["name"]
        assert {v.rule for v in violations} == {entry["rule"]}, \
            [f"{v.rule}: {v.message}" for v in violations]
        for v in violations:
            assert v.ranks and v.subtrace
            sub = v.format_subtrace()
            assert "rank " in sub and sub.count("rank ") >= 2, \
                "subtrace must show the divergent ranks side by side"


# ---------------------------------------------------------------------------
# vacuity: every schedule rule demonstrably sees its op kinds
# ---------------------------------------------------------------------------


def _gate_and_grid_kinds():
    kinds = set()
    for exe in _load_baseline().get("executables", {}).values():
        kinds |= set((exe.get("schedule") or {}).get("kinds", {}))
    for _, spec in GRID:
        for ops in extract_schedules(spec).values():
            kinds |= {o.kind for o in ops}
    return kinds


class TestVacuity:
    def test_registry_matches_rule_registry(self):
        assert set(SCHEDULE_RULE_OP_KINDS) == set(SCHEDULE_RULES)
        unknown = set(SCHEDULE_RULE_OP_KINDS) - set(RULES)
        assert not unknown, f"registry names unregistered rules: {unknown}"
        vocab = set(COLLECTIVE_KINDS) | set(P2P_KINDS) | {"copy"}
        for name, kinds in SCHEDULE_RULE_OP_KINDS.items():
            assert kinds and set(kinds) <= vocab, (name, kinds)

    @pytest.mark.parametrize("rule_name", sorted(SCHEDULE_RULE_OP_KINDS))
    def test_rule_is_not_vacuous_over_gate_and_grid(self, rule_name):
        """The op kinds a rule inspects occur in the frozen gate
        schedules or the strategy grid — otherwise its green verdict
        never saw its input."""
        seen = _gate_and_grid_kinds()
        assert seen, "no schedule kinds anywhere — extraction collapsed"
        assert seen & set(SCHEDULE_RULE_OP_KINDS[rule_name]), rule_name

    @pytest.mark.parametrize("rule_name", sorted(SCHEDULE_RULE_OP_KINDS))
    def test_rule_sees_its_kinds_in_its_corpus_entry(self, rule_name):
        entries = [e for e in CORPUS if e["rule"] == rule_name]
        assert entries, f"no corpus entry seeds {rule_name}"
        kinds = set(SCHEDULE_RULE_OP_KINDS[rule_name])
        for e in entries:
            got = {o.kind for ops in e["schedules"].values() for o in ops}
            assert got & kinds, (e["name"], rule_name)


# ---------------------------------------------------------------------------
# gate wiring: baseline sections + regression detection (satellite 5)
# ---------------------------------------------------------------------------


class TestGateWiring:
    def test_baseline_pins_schedule_coverage(self):
        exes = _load_baseline()["executables"]
        scheds = {n: e.get("schedule") for n, e in exes.items()}
        assert all(s is not None for s in scheds.values()), \
            [n for n, s in scheds.items() if s is None]
        claimed = {n: s for n, s in scheds.items() if s["ranks"] > 0}
        # the train, pipeline and MoE families all make multi-rank claims
        assert len(claimed) >= 4, sorted(claimed)
        for n, s in scheds.items():
            assert s["violations"] == 0, n
            assert s["rules_available"] == sorted(SCHEDULE_RULES), n
        for n, s in claimed.items():
            assert s["ops"] > 0 and s["kinds"], n

    def _report_with(self, schedule_meta):
        from hetu_tpu.analysis.report import (AnalysisReport,
                                              ExecutableReport)
        rep = AnalysisReport()
        rep.add(ExecutableReport(name="x", meta={"schedule":
                                                 schedule_meta}))
        return rep

    def _baseline_for(self, schedule_meta):
        rep = self._report_with(schedule_meta)
        return rep.to_dict()

    def test_new_violation_fails_the_gate(self):
        clean = {"ranks": 4, "ops": 40, "kinds": {"send": 20},
                 "collectives": 0, "p2p": 40, "switch": 0,
                 "violations": 0, "violation_rules": [],
                 "rules_available": sorted(SCHEDULE_RULES)}
        base = self._baseline_for(clean)
        dirty = dict(clean, violations=1,
                     violation_rules=["pipeline-deadlock"])
        probs = self._report_with(dirty).check_against_baseline(base)
        assert any("schedule violations regressed" in p for p in probs)

    def test_vanished_rule_fails_the_gate(self):
        pinned = {"ranks": 0, "ops": 0, "kinds": {}, "collectives": 0,
                  "p2p": 0, "switch": 0, "violations": 0,
                  "violation_rules": [],
                  "rules_available": sorted(SCHEDULE_RULES)
                  + ["ghost-rule"]}
        base = self._baseline_for(pinned)
        now = dict(pinned, rules_available=sorted(SCHEDULE_RULES))
        probs = self._report_with(now).check_against_baseline(base)
        assert any("vanished" in p and "ghost-rule" in p for p in probs)

    def test_collapsed_extraction_fails_the_gate(self):
        full = {"ranks": 8, "ops": 160, "kinds": {"send": 80},
                "collectives": 0, "p2p": 160, "switch": 0,
                "violations": 0, "violation_rules": [],
                "rules_available": sorted(SCHEDULE_RULES)}
        base = self._baseline_for(full)
        gone = dict(full, ranks=0, ops=0, kinds={}, p2p=0)
        probs = self._report_with(gone).check_against_baseline(base)
        assert any("collapsed" in p for p in probs)

    def test_cli_schedule_section_renders_verdict(self):
        import io
        from hetu_tpu.analysis.cli import schedule_section
        rep = self._report_with({
            "ranks": 8, "ops": 160, "kinds": {"send": 80, "recv": 80},
            "collectives": 0, "p2p": 160, "switch": 0, "violations": 0,
            "violation_rules": [],
            "rules_available": sorted(SCHEDULE_RULES)})
        buf = io.StringIO()
        schedule_section(rep, buf)
        out = buf.getvalue()
        assert "8 ranks" in out and "hang-free" in out
        rep2 = self._report_with({
            "ranks": 0, "ops": 0, "kinds": {}, "collectives": 0,
            "p2p": 0, "switch": 0, "violations": 0,
            "violation_rules": [],
            "rules_available": sorted(SCHEDULE_RULES)})
        buf2 = io.StringIO()
        schedule_section(rep2, buf2)
        assert "no multi-rank claim" in buf2.getvalue()

    @pytest.mark.lint_graph
    def test_schedule_gate_grid_and_corpus(self):
        """The tier-1 schedule gate: the full strategy grid verifies
        hang-free and every corpus divergence is caught by exactly its
        rule."""
        dirty = []
        for label, spec in GRID:
            if verify_schedules(extract_schedules(spec)):
                dirty.append(label)
        assert dirty == []
        for e in CORPUS:
            vs = verify_schedules(e["schedules"])
            assert vs and {v.rule for v in vs} == {e["rule"]}, e["name"]


# ---------------------------------------------------------------------------
# planner hook: searched plans carry a hang-freedom verdict
# ---------------------------------------------------------------------------


class TestPlannerHook:
    def test_plan_summary_reports_hang_free(self):
        from hetu_tpu.planner import (plan_for_gpt, plan_summary,
                                      verify_plan_schedule)
        from hetu_tpu.models.gpt import llama_config
        cfg = llama_config(vocab_size=96, hidden_size=64, num_layers=4,
                           num_heads=4, max_seq_len=64)
        plan = plan_for_gpt(cfg, global_batch=8, seq=64, n_chips=8)
        assert verify_plan_schedule(plan) == []
        assert plan_summary(plan)["schedule_hang_free"] is True


# ---------------------------------------------------------------------------
# satellite 2: the MPMD runtime's executed p2p order matches the
# symbolic projection the verifier checks
# ---------------------------------------------------------------------------


def _tap_by_stage(runtime, num_pipes):
    S = runtime.num_stages
    out = [[[] for _ in range(S)] for _ in range(num_pipes)]
    for (d, k, p, s, m, peer) in runtime.p2p_log:
        out[p][s].append((d, k, m, peer))
    return out


def _assert_tap_matches(model, counts):
    rt = model.runtime
    got = _tap_by_stage(rt, len(rt.pipes))
    for p, m_p in enumerate(counts):
        want = p2p_events(rt._schedule(m_p))
        for s in range(rt.num_stages):
            assert got[p][s] == want[s], (p, s, got[p][s], want[s])


class TestMPMDTapMatchesProjection:
    """``p2p_events`` is the projection three consumers share: the
    schedule generator, the runtime tap, and the cross-rank verifier.
    A tap/projection divergence means the verifier proves the wrong
    program hang-free."""

    def _model(self, stage_layers, seed=3):
        from hetu_tpu.models.gpt import llama_config
        from hetu_tpu.models.gpt_mpmd import MPMDGPT
        cfg = llama_config(vocab_size=32, hidden_size=16, num_layers=3,
                           num_heads=2, max_seq_len=8, dtype="float32")
        return MPMDGPT(cfg, stage_layers=stage_layers, seed=seed)

    def _step(self, model, counts, seed=0):
        cfg = model.cfg
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, cfg.vocab_size,
                          (sum(counts), cfg.max_seq_len)).astype(np.int32)
        data = model.split_micro_batches(ids, np.roll(ids, -1, axis=1),
                                         list(counts))
        model.train_step(data)
        return model

    def test_uneven_stages_and_malleus_counts(self):
        """2 pipes x 2 stages with UNEVEN per-stage layer counts [1, 2]
        and uneven micro-batch apportionment [3, 1]: the executed p2p
        log equals the 1F1B projection per (pipe, stage)."""
        model = self._model([[1, 2], [1, 2]])
        self._step(model, [3, 1])
        assert model.runtime.p2p_log, "tap recorded nothing"
        _assert_tap_matches(model, [3, 1])

    def test_tap_resets_and_tracks_reapportionment(self):
        """A second step with a different apportionment must match its
        OWN projection — the tap resets per train_step."""
        model = self._model([[1, 2], [1, 2]])
        self._step(model, [3, 1])
        self._step(model, [2, 2], seed=1)
        _assert_tap_matches(model, [2, 2])

    def test_mid_run_dp_resize_to_one_pipe(self):
        """The mid-run dp resize: the surviving single pipe absorbs the
        whole batch, and its executed order still matches the
        projection (the hot-switch path's post-resize invariant)."""
        model = self._model([[1, 2]], seed=5)
        self._step(model, [4])
        _assert_tap_matches(model, [4])

    def test_projection_covers_gpipe_too(self):
        """Projection sanity without a runtime: every send has exactly
        one matching recv on the peer stage, for both schedules."""
        for gen in (generate_pipedream_flush_schedule,
                    generate_gpipe_schedule):
            ev = p2p_events(gen(4, 6))
            sends = [(s, m, k, peer) for s, evs in enumerate(ev)
                     for (d, k, m, peer) in evs if d == "send"]
            recvs = [(peer, m, k, s) for s, evs in enumerate(ev)
                     for (d, k, m, peer) in evs if d == "recv"]
            assert sorted(sends) == sorted(recvs)
