"""Serving-plane fault-tolerance tests (ServeFaultPlan + recovery paths).

The contracts pinned here:

  * poisoned-stream isolation — a non-finite logit burst (on-device
    guard) or a step-poisoning request (service bisection) terminates
    ONLY the offending stream; every co-batched neighbour finishes
    bit-identical to a clean run, and the program inventory stays at
    exactly two compiles
  * per-request deadlines — deadline_ms validates at admission (400),
    sheds when infeasible against the backlog (429), reaps expired
    streams in the queue AND in slots, and every release restores the
    page free list exactly
  * supervised recovery — a dead or wedged serving loop is detected by
    the watchdog, the engine is rebuilt, and in-flight streams resume
    mid-generation with bit-identical continuations (per-(seed, pos)
    sampling keys); the recovered pager passes its invariant audit
  * graceful drain — admission flips to 503 + Retry-After, in-flight
    streams finish within the grace budget, stragglers force-release
    with an attributable error
  * every injection is coordinate-driven (tools/check_fault_tests.py
    lints this file, and its serve-kind coverage check rides along)
"""

import numpy as np
import pytest

pytestmark = [pytest.mark.serving, pytest.mark.faults]


def _nano():
    import jax

    from kubeml_tpu.models import get_builtin
    model = get_builtin("gpt-nano")()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, module.max_len), np.int32)})
    return model, module, variables


def _drive(engine, limit=10_000):
    finished = []
    while engine.active():
        finished.extend(engine.step())
        limit -= 1
        assert limit > 0, "engine failed to drain"
    return finished


def _clean_tokens(module, variables, specs):
    """Reference run: the same request specs on a fault-free engine."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    engine = DecodeEngine(module, variables, slots=4, page=4)
    reqs = [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                            seed=s) for p, n, t, s in specs]
    for r in reqs:
        engine.attach(r)
    _drive(engine)
    assert all(r.outcome == "ok" for r in reqs)
    return [r.tokens for r in reqs]


SPECS = [([5, 6, 7], 6, 0.0, 0),
         ([9, 10, 11, 12], 6, 0.7, 1),
         ([3, 4], 6, 1.3, 7)]


# ------------------------------------------------------ poisoned streams

def test_nan_guard_isolates_poisoned_stream_bit_identically():
    """serve_nan_logits raises the poison lane for ONE slot: only that
    request errors, neighbours match a clean run token-for-token, and
    the isolation costs zero extra compiles."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    clean = _clean_tokens(module, variables, SPECS)

    plan = ServeFaultPlan.parse([{"kind": "serve_nan_logits", "slot": 1}])
    engine = DecodeEngine(module, variables, slots=4, page=4,
                          fault_plan=plan)
    reqs = [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                            seed=s) for p, n, t, s in SPECS]
    for r in reqs:
        engine.attach(r)          # attach order == slot order
    _drive(engine)

    assert plan.injected["serve_nan_logits"] == 1
    assert reqs[1].outcome == "error"
    assert "poisoned and isolated" in reqs[1].error
    assert "non-finite logits" in reqs[1].error
    # blast radius is exactly one slot: survivors are bit-identical
    assert reqs[0].outcome == "ok" and reqs[2].outcome == "ok"
    assert reqs[0].tokens == clean[0]
    assert reqs[2].tokens == clean[2]
    # the guard is data in the decode program, not a third program
    assert engine.stats["compiles"] == 1
    assert engine.stats["prefill_compiles"] == 1
    assert engine.stats["poisoned"] == 1


def test_bisection_quarantines_step_poisoning_request():
    """serve_step_crash is rid-sticky: the service's bisection retries
    the failed step with suspect lanes masked, converges on the
    poisoning request, quarantines it, and every survivor finishes
    bit-identical — no engine restart, no recompile."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService

    _model, module, variables = _nano()
    clean = _clean_tokens(module, variables, SPECS)

    plan = ServeFaultPlan.parse([{"kind": "serve_step_crash", "slot": 0}])
    engine = DecodeEngine(module, variables, slots=4, page=4,
                          fault_plan=plan)
    svc = ServeService("crash-m", engine, supervise=False).start()
    try:
        reqs = [svc.submit(list(p), max_new_tokens=n, temperature=t,
                           seed=s) for p, n, t, s in SPECS]
        for r in reqs:
            assert r.wait(120), "request never reached a terminal state"
    finally:
        svc.stop()

    assert plan.injected["serve_step_crash"] >= 1
    # the first submission binds slot 0 and is the quarantined poisoner
    assert reqs[0].outcome == "error"
    assert "serve_step_crash" in reqs[0].error
    assert "quarantined" in reqs[0].error
    assert reqs[1].outcome == "ok" and reqs[2].outcome == "ok"
    assert reqs[1].tokens == clean[1]
    assert reqs[2].tokens == clean[2]
    # isolation, not restart: same engine, same two compiled programs
    assert svc.engine is engine
    assert svc.restarts_total == 0
    assert svc.poisoned_total == 1
    assert engine.stats["compiles"] == 1
    assert engine.stats["prefill_compiles"] == 1


def test_crash_event_is_rid_sticky_not_slot_sticky():
    from kubeml_tpu.faults import ServeFaultPlan

    plan = ServeFaultPlan.parse(
        [{"kind": "serve_step_crash", "step": 5, "slot": 2}])
    plan.check_crash(4, [(2, "aaaa")])          # before its step: quiet
    with pytest.raises(RuntimeError) as ei:
        plan.check_crash(5, [(2, "aaaa"), (0, "bbbb")])
    assert "serve_step_crash" in str(ei.value)
    plan.check_crash(7, [(0, "bbbb")])          # bound rid masked: quiet
    with pytest.raises(RuntimeError):
        plan.check_crash(9, [(1, "aaaa")])      # follows the rid, not slot


def test_serve_fault_plan_parse_and_once_only_nan():
    from kubeml_tpu.faults import ServeFaultPlan

    plan = ServeFaultPlan.parse(
        '{"events": [{"kind": "serve_nan_logits", "step": 3, "slot": 1}]}')
    assert plan.has("serve_nan_logits")
    assert plan.nan_hits(2, [0, 1]) == set()    # wrong step
    assert plan.nan_hits(3, [0]) == set()       # target absent: unconsumed
    assert plan.nan_hits(3, [0, 1]) == {1}
    assert plan.nan_hits(3, [0, 1]) == set()    # once per event
    with pytest.raises(ValueError):
        ServeFaultPlan.parse([{"kind": "bogus"}])
    with pytest.raises(ValueError):
        ServeFaultPlan.parse({"events": 3})


# -------------------------------------------------------------- deadlines

def test_deadline_reaps_slot_and_restores_free_list():
    """An expired stream releases with the terminal `deadline` outcome,
    carries its partial tokens to the client, and gives every KV page
    back — the free list is exactly restored."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    clk = {"t": 0.0}
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          prefix_cache=False, clock=lambda: clk["t"])
    req = GenerateRequest([5, 6, 7], max_new_tokens=32, deadline_ms=50)
    req.deadline_at = 0.05
    assert engine.pager.in_use == 0
    engine.attach(req)
    engine.step()
    engine.step()       # the first step's dispatch is read by the second
    assert req.outcome is None and len(req.tokens) >= 1
    clk["t"] = 0.2
    finished = engine.step()
    assert finished == [req]
    assert req.outcome == "deadline"
    assert "deadline of 50ms exceeded" in req.error
    assert engine.stats["deadline_expired"] == 1
    assert engine.active() == 0
    assert engine.pager.in_use == 0          # free list exactly restored
    assert engine.pager.check_invariants() == []
    # the flight record for the reaping step counts it
    assert engine.flight.snapshot()[-1]["deadlines"] == 1
    # the closing event carries the partial tokens the client paid for
    evs = []
    while not req.events.empty():
        evs.append(req.events.get_nowait())
    assert evs[-1].get("deadline") is True
    assert evs[-1]["tokens"] == req.tokens and req.tokens


def test_deadline_validates_at_admission_and_sheds_infeasible():
    from kubeml_tpu.models.base import InferenceInputError
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.serve.slots import ServeSaturated

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8)
    svc = ServeService("dl-m", engine, supervise=False)  # loop not started

    for bad in (0, -5, float("nan"), float("inf"), "soon"):
        with pytest.raises(InferenceInputError):
            svc.submit([5, 6], max_new_tokens=2, deadline_ms=bad)

    # a generous deadline admits fine against an empty backlog...
    ok = svc.submit(list(range(2, 42)), max_new_tokens=4,
                    deadline_ms=10_000)
    assert ok.deadline_at is not None
    # ...but now ~39 queued prompt tokens (~0.15s at the drain rate)
    # make a 100ms deadline a guaranteed expiry: shed at the door
    with pytest.raises(ServeSaturated) as ei:
        svc.submit([5, 6], max_new_tokens=4, deadline_ms=100)
    assert "infeasible" in str(ei.value)
    assert ei.value.status_code == 429
    assert ei.value.retry_after_s > 1.0
    assert svc.rejected_total == 1


def test_queue_deadline_expires_before_slot_frees():
    """With one slot held by a (fault-slowed) stream, a queued request
    whose deadline lapses is reaped by the service sweep — it never
    waits on capacity it cannot get in time."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService

    _model, module, variables = _nano()
    plan = ServeFaultPlan.parse(
        [{"kind": "serve_slow_step", "duration_s": 0.02}])
    engine = DecodeEngine(module, variables, slots=1, page=8,
                          fault_plan=plan)
    svc = ServeService("q-m", engine, supervise=False).start()
    try:
        a = svc.submit([5, 6, 7], max_new_tokens=6)
        b = svc.submit([9, 10], max_new_tokens=4, deadline_ms=30)
        assert b.wait(60) and a.wait(60)
    finally:
        svc.stop()
    assert plan.injected["serve_slow_step"] >= 1
    assert a.outcome == "ok"
    assert b.outcome == "deadline"
    assert "before a slot was free" in b.error
    assert svc.deadline_total == 1


# ----------------------------------------------------- supervised recovery

def test_wedge_recovery_resumes_streams_bit_identically():
    """serve_loop_wedge freezes the serving loop mid-burst; the watchdog
    detects the stale beat, rebuilds the engine, and the resumed streams
    finish with EXACTLY the tokens of an uninterrupted run."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    clean = _clean_tokens(module, variables, SPECS)

    plan = ServeFaultPlan.parse([{"kind": "serve_loop_wedge", "step": 2}])
    engine = DecodeEngine(module, variables, slots=4, page=4,
                          fault_plan=plan)
    tracer = Tracer()
    svc = ServeService("wedge-m", engine, tracer=tracer,
                       wedge_timeout_s=0.2, watchdog_interval_s=0.05)
    svc.start()
    try:
        reqs = [svc.submit(list(p), max_new_tokens=n, temperature=t,
                           seed=s) for p, n, t, s in SPECS]
        for r in reqs:
            assert r.wait(120), "stream never resumed after the wedge"
    finally:
        svc.stop()

    assert plan.injected["serve_loop_wedge"] == 1
    assert all(r.outcome == "ok" for r in reqs)
    assert [r.tokens for r in reqs] == clean
    assert svc.restarts_total == 1
    assert svc.engine is not engine            # rebuilt, not resuscitated
    svc.engine.check_pager()                   # recovered pager is sound
    restarts = [e for e in tracer.events() if e["name"] == "engine_restart"]
    assert len(restarts) == 1 and restarts[0]["name"] == "engine_restart"
    assert "wedged" in restarts[0]["args"]["reason"]
    assert restarts[0]["args"]["resumed"] >= 1
    # the old engine's black box rode into the trace before the swap
    snaps = [e for e in tracer.events() if e["name"] == "flight_snapshot"]
    assert any(str(s["args"].get("reason", "")).startswith(
        "engine_restart:") for s in snaps)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dead_loop_thread_recovery_resumes_bit_identically():
    """A loop thread that dies outright (uncaught exception outside the
    step) is detected by the watchdog and replaced; in-flight streams
    continue bit-identically."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService

    _model, module, variables = _nano()
    clean = _clean_tokens(module, variables, SPECS)

    engine = DecodeEngine(module, variables, slots=4, page=4)
    svc = ServeService("dead-m", engine, wedge_timeout_s=5.0,
                       watchdog_interval_s=0.05)
    orig_publish = svc._publish
    state = {"killed": False}

    def bomb():
        if not state["killed"] and svc._inflight > 0:
            state["killed"] = True
            raise RuntimeError("injected loop death")
        orig_publish()

    svc._publish = bomb
    svc.start()
    try:
        reqs = [svc.submit(list(p), max_new_tokens=n, temperature=t,
                           seed=s) for p, n, t, s in SPECS]
        for r in reqs:
            assert r.wait(120), "stream never resumed after loop death"
    finally:
        svc.stop()

    assert state["killed"]
    assert svc.restarts_total == 1
    assert all(r.outcome == "ok" for r in reqs)
    assert [r.tokens for r in reqs] == clean
    svc.engine.check_pager()


# ----------------------------------------------------------- graceful drain

def test_drain_closes_admission_and_finishes_in_flight():
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.serve.slots import ServeDraining
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8)
    tracer = Tracer()
    svc = ServeService("drain-m", engine, tracer=tracer,
                       supervise=False).start()
    try:
        a = svc.submit([5, 6, 7], max_new_tokens=4)
        assert svc.drain(grace_s=60.0) is True
        assert a.outcome == "ok"               # in-flight stream finished
        with pytest.raises(ServeDraining) as ei:
            svc.submit([9, 10], max_new_tokens=2)
        assert ei.value.status_code == 503
        assert ei.value.retry_after_s >= 1.0
        assert "another replica" in str(ei.value)
    finally:
        svc.stop()
    drains = [e for e in tracer.events() if e["name"] == "drain"]
    assert len(drains) == 1 and drains[0]["name"] == "drain"
    assert drains[0]["args"]["grace_s"] == 60.0


def test_drain_force_releases_streams_past_grace_budget():
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService

    _model, module, variables = _nano()
    plan = ServeFaultPlan.parse(
        [{"kind": "serve_slow_step", "duration_s": 0.05}])
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          fault_plan=plan)
    svc = ServeService("force-m", engine, supervise=False).start()
    r = svc.submit([5, 6], max_new_tokens=32)
    # ~31 decode rounds at 50ms each vastly outlast a 150ms budget
    svc.stop(grace_s=0.15)
    assert r.wait(60)
    assert r.outcome == "error"
    assert "grace budget exhausted" in r.error


# -------------------------------------------------- stall guard + pager

def test_stalled_stream_guard_cancels_and_frees_pages():
    """events_iter's stall timeout CANCELS the request (not just the
    HTTP thread walking away), so the next engine step reaps the slot
    and the page free list is fully restored."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          prefix_cache=False)
    req = GenerateRequest([5, 6, 7], max_new_tokens=8)
    engine.attach(req)
    engine.step()
    assert engine.pager.in_use > 0
    evs = list(req.events_iter(timeout=0.05))
    assert any("stream stalled" in str(e.get("error", "")) for e in evs)
    assert req.cancelled
    engine.step()                              # loop reaps the cancel
    assert req.outcome == "cancelled"
    assert engine.active() == 0
    assert engine.pager.in_use == 0
    assert engine.pager.check_invariants() == []


def test_pager_invariant_audit_strict_and_production_postures():
    from kubeml_tpu.serve.engine import DecodeEngine

    _model, module, variables = _nano()
    strict = DecodeEngine(module, variables, slots=2, page=8)
    assert strict.pager.check_invariants() == []
    strict.check_pager()                       # healthy: no-op
    # simulate a leaked release path: a referenced page vanishes from
    # the refcount map without returning to any list
    pid = strict.pager.alloc()
    del strict.pager._refs[pid]
    problems = strict.pager.check_invariants()
    assert problems and any("conservation" in p for p in problems)
    with pytest.raises(AssertionError, match="pager invariants"):
        strict.check_pager()

    prod = DecodeEngine(module, variables, slots=2, page=8,
                        strict_pager=False)
    pid = prod.pager.alloc()
    del prod.pager._refs[pid]
    prod.check_pager()                         # logs + counts, no raise
    assert prod.stats["page_leaks"] == 1
    # the release paths' quick form: a healthy production pool is
    # passed on its constant-time count alone, a broken count gets the
    # whole audit; strict mode always audits in full
    assert not prod.pager.conserved()
    prod.check_pager(quick=True)
    assert prod.stats["page_leaks"] == 2
    healthy = DecodeEngine(module, variables, slots=2, page=8,
                           strict_pager=False)
    assert healthy.pager.conserved()
    healthy.pager.check_invariants = None      # must not be called
    healthy.check_pager(quick=True)
    with pytest.raises(AssertionError, match="pager invariants"):
        strict.check_pager(quick=True)


# ----------------------------------------------------------- observability

def test_fault_metric_families_and_deadline_outcome():
    from kubeml_tpu.metrics.prom import MetricsRegistry
    from tools.check_metrics import validate_exposition

    reg = MetricsRegistry()
    reg.note_serve_engine_restart("m")
    reg.note_serve_poisoned("m")
    reg.note_serve_page_leaks("m", 2)
    reg.observe_serve_request("m", "deadline")
    expo = reg.exposition()
    assert "# TYPE kubeml_serve_engine_restarts_total counter" in expo
    assert "# TYPE kubeml_serve_poisoned_requests_total counter" in expo
    assert "# TYPE kubeml_serve_page_leaks_total counter" in expo
    assert 'kubeml_serve_engine_restarts_total{model="m"} 1' in expo
    assert 'kubeml_serve_page_leaks_total{model="m"} 2' in expo
    assert 'outcome="deadline"' in expo
    assert validate_exposition(expo) == []
    reg.clear_serve("m")
    assert 'model="m"' not in reg.exposition()


def test_serve_crash_loop_health_rule():
    """Critical when restarts grew by 2+ within the window; one restart
    is recovery working; a lone high sample has no in-window delta."""
    from kubeml_tpu.control.health import HealthEvaluator

    ev = HealthEvaluator()
    assert not [f for f in ev.observe(
        {"job_id": "serve:m", "serve_engine_restarts": 0})
        if f["rule"] == "serve_crash_loop"]
    fired = [f for f in ev.observe(
        {"job_id": "serve:m", "serve_engine_restarts": 2})
        if f["rule"] == "serve_crash_loop"]
    assert fired and fired[0]["severity"] == "critical"
    assert "crash-looping" in fired[0]["detail"]

    single = HealthEvaluator()
    assert not [f for f in single.observe(
        {"job_id": "serve:n", "serve_engine_restarts": 0})
        if f["rule"] == "serve_crash_loop"]
    assert not [f for f in single.observe(
        {"job_id": "serve:n", "serve_engine_restarts": 1})
        if f["rule"] == "serve_crash_loop"]

    lone = HealthEvaluator()
    assert not [f for f in lone.observe(
        {"job_id": "serve:o", "serve_engine_restarts": 7})
        if f["rule"] == "serve_crash_loop"]


def test_top_renders_serve_faults_line():
    from kubeml_tpu.cli.main import _render_top

    latest = {"serve_active_slots": 1, "serve_slot_cap": 2,
              "serve_queue_depth": 0, "serve_queue_cap": 4,
              "serve_kv_page_utilization": 0.25,
              "serve_ttft_p50": 0.030, "serve_ttft_p99": 0.090,
              "serve_rejected_total": 0,
              "serve_prefill_backlog_tokens": 0,
              "serve_prefix_hit_pct": 50.0,
              "serve_engine_restarts": 1,
              "serve_poisoned_total": 2,
              "serve_deadline_total": 3}
    out = _render_top({"id": "serve:m", "state": "healthy", "reasons": [],
                       "latest": latest})
    assert "serve faults: restarts 1  poisoned 2  deadline 3" in out
    # a replica predating the fault telemetry renders without the line
    del latest["serve_engine_restarts"]
    out = _render_top({"id": "serve:m", "state": "healthy", "reasons": [],
                       "latest": latest})
    assert "serve faults" not in out


# ------------------------------------------------------------------- lint

def test_fault_lint_serve_kind_coverage_passes_on_this_repo():
    import tools.check_fault_tests as lint
    assert lint.main(["check_fault_tests"]) == 0


def test_fault_lint_serve_kind_coverage_self_test(tmp_path):
    """The coverage check parses SERVE_KINDS from the declaration site,
    demands the QUOTED kind on an assert line, and fails loudly when a
    kind has no test."""
    import tools.check_fault_tests as lint

    root = tmp_path
    (root / "kubeml_tpu").mkdir()
    (root / "tests").mkdir()
    faults = root / "kubeml_tpu" / "faults.py"
    faults.write_text('SERVE_KINDS = ("zz_boom", "zz_hang")\n'
                      'FLEET_KINDS = ()\n'
                      'CONTROL_KINDS = ()\n')
    tests_dir = str(root / "tests")

    assert lint.serve_kinds(str(faults)) == ["zz_boom", "zz_hang"]
    assert lint.unasserted_serve_kinds(str(faults), tests_dir) == \
        ["zz_boom", "zz_hang"]
    assert lint.main(["x", tests_dir]) == 1

    # a mention in a plan spec (no assert) does NOT count as coverage
    t = root / "tests" / "test_zz.py"
    t.write_text('plan = [{"kind": "zz_boom"}]\nkinds = ["zz_hang"]\n')
    assert lint.unasserted_serve_kinds(str(faults), tests_dir) == \
        ["zz_boom", "zz_hang"]

    t.write_text('kinds = ["zz_boom", "zz_hang"]\n'
                 'assert "zz_boom" in kinds\n'
                 'assert "zz_hang" in kinds\n')
    assert lint.unasserted_serve_kinds(str(faults), tests_dir) == []
    assert lint.main(["x", tests_dir]) == 0

    # a miswired tuple (faults.py refactor) fails loudly, not silently
    faults.write_text("RENAMED = ()\n")
    with pytest.raises(SystemExit):
        lint.serve_kinds(str(faults))
