"""One decode dispatch ahead (serve/engine.py, module docstring): the
same requests through the engine that enqueues the next decode dispatch
before it reads the last one back, and through the serial sequence, give
the same streams and the same terminal outcomes.

The serial sequence is reached by a condition that disengages the
regime, a fault plan that injects nothing ("one_ahead" is the path
variant's name in SERVE_PATH_VARIANTS). Every case runs on a small GPT
and a small DeepSeek-V2 module, greedy and sampled lanes side by side,
and leaves the pager's audit clean.
"""

import queue

import jax
import numpy as np
import pytest

from kubeml_tpu.faults import ServeFaultPlan
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.pager import PageGeometry
from kubeml_tpu.serve.slots import GenerateRequest

pytestmark = pytest.mark.serving

PAGE = 8
CHUNK = 8


@pytest.fixture(scope="module", params=["gpt", "deepseek_v2"])
def served(request):
    """(module, variables, other variables of the same shapes)."""
    if request.param == "gpt":
        from kubeml_tpu.models import gpt
        model = gpt.GPTNano()
        module = model.module
        shape = {"x": np.ones((1, module.max_len), np.int32)}
        return (module,
                model.init_variables(jax.random.PRNGKey(0), shape),
                model.init_variables(jax.random.PRNGKey(1), shape))
    from kubeml_tpu.models import deepseek_v2 as ds
    module = ds.DeepSeekV2Module()
    return (module, module.init(jax.random.PRNGKey(0)),
            module.init(jax.random.PRNGKey(1)))


def _engine(served, serial, **kw):
    module, variables, _other = served
    if serial:
        kw["fault_plan"] = ServeFaultPlan([])
    kw.setdefault("slots", 4)
    return DecodeEngine(module, variables, page=PAGE, prefill_chunk=CHUNK,
                        **kw)


def _req(start, n_prompt, n_new, temp=0.0, seed=0, **kw):
    return GenerateRequest(list(range(start, start + n_prompt)),
                           max_new_tokens=n_new, temperature=temp,
                           seed=seed, **kw)


def _steps(eng, n, finished):
    for _ in range(n):
        finished.extend(eng.step())


def _finish(eng, finished, limit=2000):
    """Step until every slot is empty, then what is still unread."""
    while eng.active():
        finished.extend(eng.step())
        limit -= 1
        assert limit > 0, "engine failed to drain"
    finished.extend(eng.drain())
    eng.flush_events()
    eng.check_pager()
    assert eng.pager.in_use == 0
    np.testing.assert_array_equal(eng._tables, 0)


def _events(req):
    """(tokens of the token events, the terminal event) of a stream."""
    toks, last = [], None
    while True:
        try:
            ev = req.events.get_nowait()
        except queue.Empty:
            return toks, last
        assert last is None, "an event after the terminal one"
        if "token" in ev:
            toks.append(ev["token"])
        else:
            last = ev


def _both(served, case, **kw):
    """Run `case(engine)` -> (requests, note) on both sequences."""
    out = {}
    for serial in (False, True):
        eng = _engine(served, serial, **kw)
        reqs, note = case(eng)
        out[serial] = (eng, reqs, note)
        # every stream's events are its tokens, then one terminal event
        for r in reqs:
            toks, last = _events(r)
            assert toks == r.tokens and last is not None
    (ahead, a_reqs, a_note), (serial, s_reqs, s_note) = out[False], out[True]
    assert serial.stats["ahead_dispatches"] == 0
    assert serial.stats["overrun_lane_steps"] == 0
    assert ahead.stats["compiles"] == 1 and serial.stats["compiles"] == 1
    return ahead, a_reqs, a_note, serial, s_reqs, s_note


def _same_streams(a_reqs, s_reqs):
    np.testing.assert_array_equal(
        np.asarray([len(r.tokens) for r in a_reqs]),
        np.asarray([len(r.tokens) for r in s_reqs]))
    for a, s in zip(a_reqs, s_reqs):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(s.tokens))
    assert [(r.outcome, r.error) for r in a_reqs] \
        == [(r.outcome, r.error) for r in s_reqs]


# ------------------------------------------------------------------ cases

def test_one_ahead_joins_and_budgets(served):
    """Lanes join from a prefill chunk in the very step that ran it,
    budgets end on different steps, a freed slot is taken again: the
    "one_ahead" streams are the serial sequence's, token for token,
    greedy and sampled."""

    def case(eng):
        fin = []
        reqs = [_req(3, 3, 5), _req(40, 12, 9, 0.8, 1), _req(90, 20, 4, 1.2, 2)]
        eng.attach(reqs[0])
        _steps(eng, 2, fin)
        eng.attach(reqs[1])
        _steps(eng, 1, fin)
        eng.attach(reqs[2])
        _steps(eng, 3, fin)
        reqs.append(_req(150, 17, 6, 0.5, 3))
        assert eng.attach(reqs[3]) == 0     # the first request's slot
        _steps(eng, 2, fin)
        reqs.append(_req(200, 9, 7))
        eng.attach(reqs[4])
        _finish(eng, fin)
        assert sorted(r.rid for r in fin) == sorted(r.rid for r in reqs)
        return reqs, None

    ahead, a_reqs, _, serial, s_reqs, _ = _both(served, case)
    _same_streams(a_reqs, s_reqs)
    assert all(r.outcome == "ok" for r in a_reqs)
    assert [len(r.tokens) for r in a_reqs] == [5, 9, 4, 6, 7]
    st = ahead.stats
    assert st["overrun_lane_steps"] == 0
    assert st["dispatches"] == serial.stats["dispatches"]
    assert st["occupancy_sum"] == serial.stats["occupancy_sum"]
    assert st["generated_tokens"] == serial.stats["generated_tokens"] == 31
    assert st["decode_tokens"] == serial.stats["decode_tokens"]
    assert st["kv_bytes"] == serial.stats["kv_bytes"]
    # every decode dispatch but the one that opened the regime
    assert st["ahead_dispatches"] == st["dispatches"] - 1


def test_one_ahead_eos_drops_the_overrun_row(served):
    """An end only the result shows: the lane is a member of the next
    dispatch already, its row there is dropped (overrun_lane_steps 1),
    neither emitted nor counted, and the request that takes the slot
    next never sees it."""
    probe = _engine(served, True)
    long = _req(5, 6, 12, 1.0, 7)
    probe.attach(long)
    _finish(probe, [])
    k = next(i for i in range(2, 11) if long.tokens[i] not in long.tokens[:i])

    def case(eng):
        fin = []
        reqs = [_req(5, 6, 12, 1.0, 7, eos_id=long.tokens[k]),
                _req(60, 5, 14, 0.0, 1)]
        for r in reqs:
            eng.attach(r)
        while reqs[0].outcome is None:
            fin.extend(eng.step())
        # the slot is free while the overrun dispatch is still unread
        reqs.append(_req(120, 4, 5, 0.9, 3))
        assert eng.attach(reqs[2]) == 0
        _finish(eng, fin)
        return reqs, None

    ahead, a_reqs, _, serial, s_reqs, _ = _both(served, case)
    _same_streams(a_reqs, s_reqs)
    assert a_reqs[0].tokens == long.tokens[:k + 1]
    assert all(r.outcome == "ok" for r in a_reqs)
    assert ahead.stats["overrun_lane_steps"] == 1
    want = (k + 1) + 14 + 5
    assert ahead.stats["generated_tokens"] == want
    assert serial.stats["generated_tokens"] == want
    assert ahead.stats["decode_tokens"] == serial.stats["decode_tokens"]
    # the overrun lane-step was a member of a dispatch the serial
    # sequence never packed it into
    assert ahead.stats["occupancy_sum"] == serial.stats["occupancy_sum"] + 1


def test_one_ahead_copy_on_write_split(served):
    """A prompt wholly in the prefix cache joins on a shared page: the
    split is packed into the dispatch that runs ahead."""

    def case(eng):
        fin = []
        first = _req(7, 2 * PAGE, 3)
        eng.attach(first)
        _finish(eng, fin)
        busy = _req(300, 5, 12, 0.7, 5)
        eng.attach(busy)
        _steps(eng, 3, fin)
        again = [_req(7, 2 * PAGE, 6), _req(7, 2 * PAGE, 4, 0.9, 2)]
        for r in again:
            eng.attach(r)
        _finish(eng, fin)
        return [first, busy] + again, None

    ahead, a_reqs, _, serial, s_reqs, _ = _both(served, case)
    _same_streams(a_reqs, s_reqs)
    assert a_reqs[2].tokens[:3] == a_reqs[0].tokens
    assert ahead.stats["cow_splits"] == serial.stats["cow_splits"] == 2
    assert ahead.stats["prefix_hits"] == serial.stats["prefix_hits"] == 4
    assert ahead.stats["prefill_dispatches"] \
        == serial.stats["prefill_dispatches"]
    assert ahead.stats["ahead_dispatches"] > 0


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_one_ahead_release_while_a_dispatch_is_unread(served, how):
    """A cancel or a deadline lands while a dispatch is unread: its row
    reaches neither the request that left nor the one that takes the
    slot, whose stream is the serial sequence's."""
    now = [0.0]
    whole = _req(5, 6, 12, 0.8, 7)
    probe = _engine(served, True)
    probe.attach(whole)
    _finish(probe, [])

    def case(eng):
        fin = []
        reqs = [_req(5, 6, 12, 0.8, 7), _req(60, 5, 16, 0.0, 1)]
        for r in reqs:
            eng.attach(r)
        _steps(eng, 4, fin)
        unread = eng._unread is not None
        emitted = len(reqs[0].tokens)
        if how == "cancel":
            reqs[0].cancel()
        else:
            reqs[0].deadline_ms = 1.0
            reqs[0].deadline_at = 0.5
            now[0] = 1.0
        _steps(eng, 1, fin)
        assert reqs[0].outcome == ("cancelled" if how == "cancel"
                                   else "deadline")
        assert len(reqs[0].tokens) == emitted
        reqs.append(_req(120, 4, 6, 0.9, 3))
        assert eng.attach(reqs[2]) == 0
        _finish(eng, fin)
        assert len(reqs[0].tokens) == emitted
        now[0] = 0.0
        return reqs, unread

    ahead, a_reqs, a_unread, serial, s_reqs, s_unread = _both(
        served, case, clock=lambda: now[0])
    assert a_unread and not s_unread
    # the request that left holds a prefix of its stream: one token
    # fewer than the serial sequence had handed it by then
    assert len(a_reqs[0].tokens) == len(s_reqs[0].tokens) - 1
    for r in (a_reqs[0], s_reqs[0]):
        np.testing.assert_array_equal(
            np.asarray(r.tokens), np.asarray(whole.tokens[:len(r.tokens)]))
    _same_streams(a_reqs[1:], s_reqs[1:])
    assert [r.outcome for r in a_reqs] == [r.outcome for r in s_reqs]
    assert ahead.stats["overrun_lane_steps"] == 1
    assert ahead.stats["generated_tokens"] == sum(
        len(r.tokens) for r in a_reqs)


def test_one_ahead_pool_running_dry_falls_back(served):
    """With the pool nearly spent the engine reads the unread dispatch
    first (a grant could fail that its releases would have covered):
    stalls and the shed stream are the serial sequence's, and no page
    leaks."""
    module = served[0]
    geom = PageGeometry.for_module(slots=4, page=PAGE, max_len=module.max_len,
                                   pages=13)

    def case(eng):
        fin = []
        reqs = [_req(10 + 50 * i, 6, 34, 0.6 * (i % 2), i) for i in range(4)]
        for r in reqs:
            eng.attach(r)
        _finish(eng, fin)
        return reqs, None

    ahead, a_reqs, _, serial, s_reqs, _ = _both(served, case, geom=geom)
    _same_streams(a_reqs, s_reqs)
    shed = [r for r in a_reqs if r.outcome == "error"]
    assert shed and all("shed" in r.error for r in shed)
    assert len(shed) + sum(r.outcome == "ok" for r in a_reqs) == 4
    assert ahead.stats["stalls"] == serial.stats["stalls"] > 0
    assert 0 < ahead.stats["ahead_dispatches"] < ahead.stats["dispatches"]
    assert ahead.stats["overrun_lane_steps"] == 0


@pytest.mark.parametrize("how", ["evacuate", "spawn_recovered", "abandon"])
def test_one_ahead_state_taken_with_a_dispatch_unread(served, how):
    """evacuate and spawn_recovered read and emit the unread dispatch
    before they take state away, abandon drops it: either way the
    streams resumed on another engine are the uninterrupted ones."""
    whole = [_req(5, 6, 12, 0.8, 7), _req(60, 11, 9, 0.0, 1)]
    probe = _engine(served, True)
    for r in whole:
        probe.attach(r)
    _finish(probe, [])

    def case(eng):
        fin = []
        reqs = [_req(5, 6, 12, 0.8, 7), _req(60, 11, 9, 0.0, 1)]
        for r in reqs:
            eng.attach(r)
        _steps(eng, 5, fin)
        unread = eng._unread is not None
        before = [len(r.tokens) for r in reqs]
        gens = [eng._slots[s].gen for s in (0, 1)]
        if how == "evacuate":
            moved = [eng.evacuate(s) for s in (0, 1)]
            assert moved == reqs
            other = _engine(served, eng.fault_plan is not None)
        else:
            if how == "abandon":
                eng.abandon()
                assert eng.step() == []
            other = eng.spawn_recovered()
        assert eng._unread is None
        after = [len(r.tokens) for r in reqs]
        # what service._recover and the fleet do with a stream: the same
        # request object, pinned to its generation, attached elsewhere
        for r, g in zip(reqs, gens):
            r.resume_gen = g
            other.attach(r)
        _finish(other, fin)
        eng.check_pager()
        assert eng.pager.in_use == (0 if how == "evacuate" else 4)
        return reqs, (unread, before, after)

    ahead, a_reqs, a_note, serial, s_reqs, s_note = _both(served, case)
    _same_streams(a_reqs, s_reqs)
    for r, w in zip(a_reqs, whole):
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      np.asarray(w.tokens))
        assert r.outcome == "ok"
    assert a_note[0] and not s_note[0]
    # serial: nothing to settle. Ahead: the unread dispatch's tokens
    # reach their streams before the state goes, or not at all
    assert s_note[1] == s_note[2]
    if how == "abandon":
        assert a_note[2] == a_note[1] == [n - 1 for n in s_note[1]]
    else:
        assert a_note[2] == s_note[2] == [n + 1 for n in a_note[1]]


def test_one_ahead_install_weights_with_a_dispatch_unread(served):
    """A weight swap settles the unread dispatch first (a request it
    finishes is returned by the next step), the two generations then
    run the serial sequence, and the regime resumes when one is left."""
    other = served[2]

    def case(eng):
        fin = []
        reqs = [_req(5, 6, 3, 0.8, 7), _req(60, 5, 12, 0.0, 1)]
        for r in reqs:
            eng.attach(r)
        _steps(eng, 3, fin)
        early = [r.rid for r in fin]
        unread = eng._unread is not None
        assert eng.install_weights(other) == 2
        assert eng._unread is None and reqs[0].outcome == "ok"
        reqs.append(_req(120, 4, 16, 0.9, 3))
        eng.attach(reqs[2])
        fin.extend(eng.step())
        back = [r.rid for r in fin]
        mark = eng.stats["ahead_dispatches"]
        _finish(eng, fin)
        assert eng.active_generations() == [2]
        return reqs, (unread, early, back,
                      eng.stats["ahead_dispatches"] - mark)

    ahead, a_reqs, a_note, serial, s_reqs, s_note = _both(served, case)
    _same_streams(a_reqs, s_reqs)
    assert all(r.outcome == "ok" for r in a_reqs)
    # the first request's third token was in the unread dispatch: the
    # install's drain finished it, the step after handed it back
    assert a_note[0] and a_note[1] == [] and a_note[2] == [a_reqs[0].rid]
    assert not s_note[0] and s_note[1] == s_note[2] == [s_reqs[0].rid]
    # generation 1 retires with its last reader: one ahead again
    assert a_note[3] > 0 and s_note[3] == 0
    assert ahead.stats["generations_retired"] == 1


# ------------------------------------------- the records the readers read

def test_one_ahead_phase_records_feed_the_benchmarks_reader():
    """A service on the CPU with the phase ring on, its loop thread's
    records through benchmark/metrics/serve_loop_phases.py: every decode
    iteration is found, each with one serve.step.enqueue, one
    serve.step.readback and one serve.step.emit record of its step, the
    serve.step.* phases still tile serve.loop.step, and the share of
    dispatches enqueued with one unread is what the traffic implies:
    all but the one that opened the regime."""
    import time

    from benchmark.metrics.serve_loop_phases import iterations, table
    from kubeml_tpu.models import gpt
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.utils.trace import phases

    model = gpt.GPTNano()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": np.ones((1, module.max_len), np.int32)})
    engine = DecodeEngine(module, variables, slots=4, page=PAGE,
                          prefill_chunk=CHUNK)
    t_before = time.monotonic()
    svc = ServeService("one-ahead-phases", engine, max_queue=4).start()
    try:
        reqs = [svc.submit(list(range(2, 2 + n)), max_new_tokens=k,
                           temperature=t, seed=i)
                for i, (n, k, t) in enumerate(
                    [(20, 30, 0.0), (3, 40, 0.7), (11, 24, 1.1), (27, 36, 0.0)])]
        for r in reqs:
            assert r.wait(120) and r.outcome == "ok"
    finally:
        svc.stop()
    tid = svc._thread.ident
    recs = [r for r in phases(t0=t_before) if r.tid == tid]
    st = engine.stats
    assert st["generated_tokens"] == 30 + 40 + 24 + 36
    assert st["overrun_lane_steps"] == 0
    assert st["ahead_dispatches"] == st["dispatches"] - 1

    its = iterations(recs)
    assert len(its) == st["dispatches"]
    by_step = {}
    for r in recs:
        if r.name.startswith("serve.step."):
            by_step.setdefault(r.args["step"], []).append(r)
    for it in its:
        names = [r.name for r in by_step[it["step"]]]
        for name in ("serve.step.enqueue", "serve.step.readback",
                     "serve.step.emit"):
            assert names.count(name) == 1, (it["step"], names)
        # enqueue, then the wait for the dispatch before, then its walk
        assert names.index("serve.step.enqueue") \
            < names.index("serve.step.readback") \
            < names.index("serve.step.emit")
        assert it["engine_s"] > 0 and it["service_s"] > 0
    enqueues = [r for r in recs if r.name == "serve.step.enqueue"]
    emits = [r for r in recs if r.name == "serve.step.emit"]
    assert sum(r.args["ahead"] for r in enqueues) == st["ahead_dispatches"]
    assert [r.args["ahead"] for r in enqueues][:2] == [0, 1]
    assert sum(r.args["overrun"] for r in emits) == 0
    # the step that read the last dispatch enqueued none: one emit more
    assert len(emits) == len(enqueues) + 1
    # tokens a step handed over follow its emit record's step
    _by_name, loop_share, step_share = table(recs, recs[0].t0, recs[-1].t1)
    assert step_share >= 0.99 and loop_share >= 0.9
