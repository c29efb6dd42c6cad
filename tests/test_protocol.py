"""Serving-protocol verifier tests (DESIGN.md §23).

Covers the four layers of ISSUE 18's tentpole:

* the lifecycle state machines (page / request / fence) over hand-built
  minimal event streams — clean streams replay clean, each violation
  class fires exactly once with provenance and a subtrace;
* the typed event stream + the four lifecycle lint rules through the
  standard ``AnalysisContext`` idiom (seeded fire-once tests, like every
  other rule in tests/test_analysis.py);
* mutation tests: ONE recorded clean chaos fuzz trace, ~8 seeded
  single-event mutations (drop a free, duplicate an adopt, decrement a
  refcount, regress an epoch, stage-to-host without evict, write
  post-finish, ...) — each flagged EXACTLY once with the right rule and
  provenance;
* the bounded interleaving explorer: the clean model is violation-free
  over an exhaustively-explored config, and each seeded interaction-bug
  class (including the real autoscaler drain-vs-inflight-handoff bug
  this PR fixes) is FOUND and attributed to the right rule;
* the vacuity meta-test over :data:`TRACE_RULE_EVENT_KINDS`: every
  trace-replay rule's input vocabulary actually occurs in the frozen
  gate executables' traces (ANALYSIS_BASELINE.json ``protocol.kinds``)
  — a rule whose event kinds never appear is vacuously green.
"""
import json
import os

import pytest

from hetu_tpu.analysis import events as pe
from hetu_tpu.analysis.events import Event
from hetu_tpu.analysis.protocol import (
    RULE_FENCE, RULE_PAGE, RULE_REFCOUNT, RULE_REQUEST, ExploreConfig,
    FenceMachine, PageMachine, RequestMachine, explore, fuzz_trace,
    replay)
from hetu_tpu.analysis.rules import (TRACE_RULE_EVENT_KINDS,
                                     AnalysisContext, run_rules)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# explorer config small enough for tier-1 (exhausts in <1s) while still
# covering both replicas, a handoff, chaos, eviction and a drain
SMALL = ExploreConfig(n_requests=1, tokens_per_request=2, max_evicts=1)


def E(kind, key, step=0, epoch=None, prov="test", **attrs):
    return Event(kind=kind, key=key, step=step, epoch=epoch,
                 attrs=attrs, provenance=prov, seq=step)


# ---------------------------------------------------------------------------
# lifecycle state machines over hand-built streams
# ---------------------------------------------------------------------------


class TestMachines:
    def test_clean_page_lifecycle_replays_clean(self):
        evs = [E(pe.PAGE_ALLOC, "p1", 0, page=1),
               E(pe.PAGE_CACHE, "p1", 1, page=1),
               E(pe.PAGE_SHARE, "p1", 2, page=1),
               E(pe.PAGE_UNSHARE, "p1", 3, page=1),
               E(pe.PAGE_UNCACHE, "p1", 4, page=1)]
        assert replay(evs) == []

    def test_clean_request_and_fence_lifecycle(self):
        evs = [E(pe.FENCE_BUMP, "r0", 0, epoch=1),
               E(pe.REQ_QUEUED, "req:1", 1),
               E(pe.REQ_ADMIT, "req:1", 2),
               E(pe.REQ_WRITE, "req:1", 3, tap_step=0),
               E(pe.REQ_PREEMPT, "req:1", 4),
               E(pe.REQ_ADMIT, "req:1", 5),
               E(pe.REQ_STAGE, "req:1", 6, epoch=1),
               E(pe.REQ_ADOPT, "req:1", 7, epoch=1),
               E(pe.REQ_FINISH, "req:1", 8),
               E(pe.FENCE_COMPLETE, "r0", 9, epoch=1),
               E(pe.FENCE_BUMP, "r0", 10, epoch=2),
               E(pe.FENCE_STALE_DROP, "r0", 11, epoch=1)]
        assert replay(evs) == []

    def test_double_alloc_fires_once_with_subtrace(self):
        evs = [E(pe.PAGE_ALLOC, "p1", 0, page=1, prov="pool[0]"),
               E(pe.PAGE_ALLOC, "p1", 1, page=1, prov="pool[1]"),
               # poisoned subject: the cascade is suppressed
               E(pe.PAGE_ALLOC, "p1", 2, page=1, prov="pool[2]")]
        vs = replay(evs)
        assert len(vs) == 1
        assert vs[0].rule == RULE_PAGE
        assert vs[0].subject == "p1"
        assert vs[0].provenance == "pool[1]"
        assert "only a free page" in vs[0].message
        assert vs[0].subtrace and "pool[1]" in vs[0].format_subtrace()

    def test_trash_page_is_immutable(self):
        vs = replay([E(pe.PAGE_ALLOC, "p0", 0, page=0)])
        assert len(vs) == 1 and vs[0].rule == RULE_PAGE
        assert "trash" in vs[0].message

    def test_unshare_below_zero_is_refcount_leak(self):
        evs = [E(pe.PAGE_ALLOC, "p2", 0, page=2),
               E(pe.PAGE_CACHE, "p2", 1, page=2),
               E(pe.PAGE_UNSHARE, "p2", 2, page=2)]
        vs = replay(evs)
        assert len(vs) == 1 and vs[0].rule == RULE_REFCOUNT

    def test_terminal_open_share_is_refcount_leak(self):
        evs = [E(pe.PAGE_ALLOC, "p2", 0, page=2),
               E(pe.PAGE_CACHE, "p2", 1, page=2),
               E(pe.PAGE_SHARE, "p2", 2, page=2)]
        # live traces end mid-flight: non-strict replay is clean
        assert replay(evs, strict_terminal=False) == []
        vs = replay(evs)          # complete trace: conservation enforced
        assert len(vs) == 1 and vs[0].rule == RULE_REFCOUNT
        assert "ends the trace" in vs[0].message

    def test_fence_regression_and_stale_completion(self):
        vs = replay([E(pe.FENCE_BUMP, "r0", 0, epoch=2),
                     E(pe.FENCE_BUMP, "r0", 1, epoch=1)])
        assert len(vs) == 1 and vs[0].rule == RULE_FENCE
        assert "monotone" in vs[0].message
        vs2 = replay([E(pe.FENCE_BUMP, "r0", 0, epoch=2),
                      E(pe.FENCE_COMPLETE, "r0", 1, epoch=1)])
        assert len(vs2) == 1 and vs2[0].rule == RULE_FENCE
        assert "stale" in vs2[0].message

    def test_double_adopt_and_post_finish_write(self):
        evs = [E(pe.REQ_STAGE, "creq:1", 0, epoch=3),
               E(pe.REQ_ADOPT, "creq:1", 1, epoch=3),
               E(pe.REQ_ADOPT, "creq:1", 2, epoch=3)]
        vs = replay(evs)
        assert len(vs) == 1 and vs[0].rule == RULE_REQUEST
        assert "TWICE" in vs[0].message
        vs2 = replay([E(pe.REQ_FINISH, "req:1", 0),
                      E(pe.REQ_WRITE, "req:1", 1, tap_step=7)])
        assert len(vs2) == 1 and vs2[0].rule == RULE_REQUEST
        assert "AFTER" in vs2[0].message

    def test_machines_are_independent_instances(self):
        pm, rm, fm = PageMachine(), RequestMachine(), FenceMachine()
        for m in (pm, rm, fm):
            assert m.violations == []


# ---------------------------------------------------------------------------
# the four lifecycle rules through the AnalysisContext idiom
# ---------------------------------------------------------------------------


class TestLifecycleRules:
    def test_page_lifecycle_rule_fires_once_per_seed(self):
        # seeded: double alloc in the pool event log
        ctx = AnalysisContext(
            name="t_plc",
            serving={"pool_log": [(1, "alloc", 2), (2, "alloc", 2),
                                  (3, "alloc", 2)]})
        fired = run_rules(ctx, only=[RULE_PAGE])
        assert len(fired) == 1 and fired[0].severity == "error"
        assert fired[0].subject == "p2"
        assert "only a free page" in fired[0].message
        assert "subtrace" in fired[0].hint     # --explain payload
        assert fired[0].source.startswith("pool[")
        # clean log: silent
        ctx2 = AnalysisContext(
            name="t_plc2",
            serving={"pool_log": [(1, "alloc", 2), (2, "free", 2)]})
        assert not run_rules(ctx2, only=[RULE_PAGE])

    def test_request_lifecycle_rule_fires_once_per_seed(self):
        log = [{"ev": pe.REQ_QUEUED, "key": "req:1", "seq": 1},
               {"ev": pe.REQ_ADMIT, "key": "req:1", "seq": 2},
               {"ev": pe.REQ_FINISH, "key": "req:1", "seq": 3},
               {"ev": pe.REQ_FINISH, "key": "req:1", "seq": 4}]
        ctx = AnalysisContext(name="t_rlc", serving={"protocol": log})
        fired = run_rules(ctx, only=[RULE_REQUEST])
        assert len(fired) == 1
        assert "delivered twice" in fired[0].message
        assert fired[0].source.startswith("engine[")
        assert not run_rules(
            AnalysisContext(name="t_rlc2",
                            serving={"protocol": log[:3]}),
            only=[RULE_REQUEST])

    def test_fence_regression_rule_fires_once_per_seed(self):
        log = [{"ev": pe.FENCE_BUMP, "key": "r0", "seq": 1, "epoch": 2},
               {"ev": pe.FENCE_BUMP, "key": "r0", "seq": 2, "epoch": 1}]
        ctx = AnalysisContext(name="t_fr", meta={"protocol": log})
        fired = run_rules(ctx, only=[RULE_FENCE])
        assert len(fired) == 1 and "monotone" in fired[0].message
        assert fired[0].source.startswith("cluster[")
        assert not run_rules(
            AnalysisContext(name="t_fr2", meta={"protocol": log[:1]}),
            only=[RULE_FENCE])

    def test_refcount_leak_rule_fires_once_per_seed(self):
        ctx = AnalysisContext(
            name="t_rc",
            serving={"pool_log": [(1, "alloc", 3), (2, "cache", 3),
                                  (3, "unshare", 3), (4, "unshare", 3)]})
        fired = run_rules(ctx, only=[RULE_REFCOUNT])
        assert len(fired) == 1 and "negative" in fired[0].message
        # live trace ending with an open share: NOT flagged here
        # (terminal conservation belongs to complete traces — the
        # explorer and the fuzz gate)
        ctx2 = AnalysisContext(
            name="t_rc2",
            serving={"pool_log": [(1, "alloc", 3), (2, "cache", 3),
                                  (3, "share", 3)]})
        assert not run_rules(ctx2, only=[RULE_REFCOUNT])

    def test_one_replay_shared_across_the_four_rules(self):
        ctx = AnalysisContext(
            name="t_shared",
            serving={"pool_log": [(1, "alloc", 2), (2, "alloc", 2)]})
        fired = run_rules(ctx, only=[RULE_PAGE, RULE_REQUEST,
                                     RULE_FENCE, RULE_REFCOUNT])
        assert len(fired) == 1 and fired[0].rule == RULE_PAGE
        assert getattr(ctx, "_protocol_violations", None) is not None


# ---------------------------------------------------------------------------
# mutation tests: one recorded clean trace, single-event corruptions
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def clean_trace():
    ev = fuzz_trace(seed=0, n_events=300)
    assert len(ev) >= 250
    assert replay(ev) == [], "the recorded chaos trace must be clean"
    return ev


def _one(violations, rule):
    assert len(violations) == 1, \
        [f"{v.rule}({v.subject}): {v.message}" for v in violations]
    v = violations[0]
    assert v.rule == rule, (v.rule, rule, v.message)
    assert v.provenance, "violations must carry provenance"
    assert v.subtrace, "violations must carry the event subtrace"
    return v


class TestMutations:
    def test_drop_a_free(self, clean_trace):
        ev = clean_trace
        i = next(i for i, e in enumerate(ev)
                 if e.kind == pe.PAGE_FREE
                 and any(e2.kind == pe.PAGE_ALLOC and e2.key == e.key
                         for e2 in ev[i + 1:]))
        v = _one(replay(ev[:i] + ev[i + 1:]), RULE_PAGE)
        assert v.subject == ev[i].key
        assert "only a free page" in v.message
        assert v.provenance.startswith("fuzz[")

    def test_duplicate_a_free(self, clean_trace):
        ev = clean_trace
        i = next(i for i, e in enumerate(ev)
                 if e.kind == pe.PAGE_FREE)
        v = _one(replay(ev[:i + 1] + [ev[i]] + ev[i + 1:]), RULE_PAGE)
        assert v.subject == ev[i].key and "free of page" in v.message

    def test_duplicate_an_adopt(self, clean_trace):
        ev = clean_trace
        i = next(i for i, e in enumerate(ev)
                 if e.kind == pe.REQ_ADOPT)
        v = _one(replay(ev[:i + 1] + [ev[i]] + ev[i + 1:]),
                 RULE_REQUEST)
        assert v.subject == ev[i].key and "TWICE" in v.message

    def test_decrement_a_refcount(self, clean_trace):
        # one extra unshare at end of trace: the refcount it decrements
        # was already conserved to zero
        ev = clean_trace
        extra = next(e for e in ev if e.kind == pe.PAGE_UNSHARE)
        v = _one(replay(list(ev) + [extra]), RULE_REFCOUNT)
        assert v.subject == extra.key

    def test_regress_an_epoch(self, clean_trace):
        ev = list(clean_trace)
        bumps = {}
        for i, e in enumerate(ev):
            if e.kind == pe.FENCE_BUMP:
                bumps.setdefault(e.key, []).append(i)
        key, idxs = next((k, v) for k, v in bumps.items()
                         if len(v) >= 2)
        last, first = ev[idxs[-1]], ev[idxs[0]]
        ev[idxs[-1]] = Event(kind=last.kind, key=last.key,
                             step=last.step, epoch=first.epoch,
                             attrs=last.attrs,
                             provenance="mut[epoch-regress]",
                             seq=last.seq)
        v = _one(replay(ev), RULE_FENCE)
        assert v.subject == key and "monotone" in v.message
        assert v.provenance == "mut[epoch-regress]"

    def test_stage_to_host_without_evict(self, clean_trace):
        # a host-stage naming a page that was never cached (never went
        # through the evict path)
        bad = E(pe.HOST_STAGE, "hh:mut", step=len(clean_trace),
                prov="mut[host-stage]", page=1)
        v = _one(replay(list(clean_trace) + [bad]), RULE_PAGE)
        assert "only a cached page is staged" in v.message
        assert v.provenance == "mut[host-stage]"

    def test_refetch_without_stage(self, clean_trace):
        bad = E(pe.HOST_REFETCH, "hh:mut", step=len(clean_trace),
                prov="mut[refetch]")
        v = _one(replay(list(clean_trace) + [bad]), RULE_PAGE)
        assert "never staged" in v.message

    def test_write_post_finish(self, clean_trace):
        ev = clean_trace
        fin = next(e for e in ev if e.kind == pe.REQ_FINISH)
        bad = E(pe.REQ_WRITE, fin.key, step=len(ev),
                prov="mut[post-finish-write]", tap_step=999)
        v = _one(replay(list(ev) + [bad]), RULE_REQUEST)
        assert v.subject == fin.key and "AFTER" in v.message
        assert v.provenance == "mut[post-finish-write]"

    def test_duplicate_a_finish(self, clean_trace):
        ev = clean_trace
        i = next(i for i, e in enumerate(ev)
                 if e.kind == pe.REQ_FINISH)
        v = _one(replay(ev[:i + 1] + [ev[i]] + ev[i + 1:]),
                 RULE_REQUEST)
        assert "delivered twice" in v.message


# ---------------------------------------------------------------------------
# the bounded interleaving explorer
# ---------------------------------------------------------------------------


class TestExplorer:
    def test_clean_model_exhausts_with_zero_violations(self):
        res = explore(SMALL, stop_at_first=False)
        assert res.ok, [v.message for v in res.violations]
        # the memoized DAG count recovers the true path count — far
        # beyond what leaf-enumeration could visit in tier-1 time
        assert res.interleavings > 10_000
        assert res.states > 500
        assert res.events_checked > res.states
        assert res.max_depth > 10

    @pytest.mark.parametrize("bug,rule", [
        ("drain_inflight", RULE_FENCE),
        ("double_adopt", RULE_REQUEST),
        ("stale_accept", RULE_FENCE),
        ("free_shared", RULE_PAGE),
    ])
    def test_seeded_interaction_bugs_are_found(self, bug, rule):
        res = explore(bug=bug)          # default cfg, stop at first
        assert len(res.violations) == 1, \
            [f"{v.rule}: {v.message}" for v in res.violations]
        v = res.violations[0]
        assert v.rule == rule, (bug, v.rule, v.message)
        assert v.provenance.startswith("explore:")
        assert v.subtrace

    def test_fuzz_traces_replay_clean_across_seeds(self):
        for seed in (0, 1, 2):
            ev = fuzz_trace(seed=seed, n_events=300)
            assert len(ev) >= 250, (seed, len(ev))
            assert replay(ev) == [], seed

    def test_fuzz_trace_covers_the_vocabulary(self):
        kinds = set(pe.kind_counts(fuzz_trace(seed=0, n_events=300)))
        # every plane is represented: pages, host tier, requests,
        # adoption, fencing, wire, chaos
        for k in (pe.PAGE_ALLOC, pe.PAGE_FREE, pe.PAGE_SHARE,
                  pe.HOST_STAGE, pe.HOST_REFETCH, pe.REQ_ADMIT,
                  pe.REQ_ADOPT, pe.REQ_PREEMPT, pe.REQ_SHED,
                  pe.REQ_FINISH, pe.FENCE_BUMP, pe.FENCE_COMPLETE,
                  pe.WIRE_INJECT, pe.CHAOS_INJECT):
            assert k in kinds, k
        assert len(kinds) >= 18

    def test_fuzz_bug_flag_is_caught_by_replay(self):
        # the fuzz walk drives the SAME model as the explorer: a seeded
        # bug eventually corrupts the trace and strict replay flags it
        found = 0
        for seed in range(5):
            ev = fuzz_trace(seed=seed, n_events=300, bug="free_shared")
            if any(v.rule in (RULE_PAGE, RULE_REFCOUNT)
                   for v in replay(ev)):
                found += 1
        assert found >= 1

    @pytest.mark.slow
    def test_default_config_exhausts(self):
        # the full default bound:
        # ~365k distinct states, tens of trillions of interleavings
        res = explore(stop_at_first=False)
        assert res.ok, [v.message for v in res.violations]
        assert res.states > 100_000
        assert res.interleavings > 10 ** 12


# ---------------------------------------------------------------------------
# vacuity meta-test: every trace rule sees real events in the gate
# ---------------------------------------------------------------------------


def _baseline_kind_union():
    path = os.path.join(REPO, "ANALYSIS_BASELINE.json")
    with open(path) as f:
        data = json.load(f)
    kinds = set()
    per_exe = {}
    for name, exe in data.get("executables", {}).items():
        got = set((exe.get("protocol") or {}).get("kinds", {}))
        per_exe[name] = got
        kinds |= got
    return kinds, per_exe


@pytest.mark.parametrize("rule_name",
                         sorted(TRACE_RULE_EVENT_KINDS))
def test_trace_rule_is_not_vacuous_over_gate_traces(rule_name):
    """Each trace rule's registered gate executables' frozen traces
    contain >= 1 event of a kind the rule inspects — otherwise the
    rule's green on the gate is vacuous (it never saw its input)."""
    kinds = TRACE_RULE_EVENT_KINDS[rule_name]
    if kinds is None:
        pytest.skip(f"{rule_name} replays a record plane (meta hook), "
                    f"not the event stream")
    seen, _ = _baseline_kind_union()
    assert seen, "baseline carries no protocol.kinds — re-freeze it"
    assert seen & set(kinds), \
        (f"{rule_name} inspects {kinds} but no gate executable's "
         f"frozen trace contains any of them — the rule is vacuous "
         f"over the gate")


def test_vacuity_registry_matches_rule_registry():
    from hetu_tpu.analysis.rules import RULES
    unknown = set(TRACE_RULE_EVENT_KINDS) - set(RULES)
    assert not unknown, f"registry names unregistered rules: {unknown}"
    for name, kinds in TRACE_RULE_EVENT_KINDS.items():
        if kinds is not None:
            assert kinds, name
            assert all(k in pe.ALL_KINDS for k in kinds), (name, kinds)


# ---------------------------------------------------------------------------
# tier-1 gate: explorer + fuzz ride the lint_graph marker
# ---------------------------------------------------------------------------


@pytest.mark.lint_graph
def test_protocol_gate_explorer_and_fuzz():
    """The tier-1 protocol gate (ISSUE 18): the bounded explorer
    exhausts a two-replica config with ZERO violations on the clean
    model, and a seeded ~300-event chaos fuzz trace replays through
    the lifecycle machines with strict terminal conservation.  The
    full default-config exhaustion is the `slow`
    TestExplorer::test_default_config_exhausts."""
    res = explore(SMALL, stop_at_first=False)
    assert res.ok, [f"{v.rule}: {v.message}" for v in res.violations]
    assert res.interleavings > 10_000
    ev = fuzz_trace(seed=0, n_events=300)
    assert len(ev) >= 250
    assert replay(ev) == []
