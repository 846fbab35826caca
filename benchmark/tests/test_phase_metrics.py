"""The four per-layer metrics that read the program's own phases and
counters, each on a hand-made `measured`; the two span metrics on a
phase ring the test fills with known times."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run  # noqa: E402

CTX = {"config": {"geometry": {"slots": 8},
                  "programs": {"decode": "jit_step",
                               "prefill": "jit_prefill"}}}
MS = 1e-3


def read(name, measured, ctx=CTX):
    spec = run.metric_specs()[name]
    return run.read_metric(name, spec, ctx, measured)


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture()
def ring(monkeypatch):
    """A phase ring on a clock the test sets, in the process ring's
    place. `put(name, t0, t1, **args)` records one phase, in ms."""
    from kubeml_tpu.utils import trace
    clock = Clock()
    ring = trace.PhaseRing(maxlen=256, clock=clock)
    monkeypatch.setattr(trace, "phases", ring.phases)

    def put(name, t0, t1, **args):
        clock.t = t0 * MS
        with ring.phase(name, **args):
            clock.t = t1 * MS
    return put


def iteration(put, t, step, admit, pack, enqueue, readback, emit,
              terminal, publish, prefill=0.0):
    """One loop iteration starting at t ms with the given phase times;
    returns where it ends."""
    put("serve.loop.admit", t, t + admit, model="m", step=step)
    s = t + admit
    e = s
    for name, dur in (("serve.step.reap", 0.1), ("serve.step.prefill",
                      prefill), ("serve.step.pages", 0.1),
                      ("serve.step.pack", pack),
                      ("serve.step.enqueue", enqueue),
                      ("serve.step.readback", readback),
                      ("serve.step.emit", emit)):
        if dur and not (name == "serve.step.enqueue" and enqueue is None):
            put(name, e, e + dur, step=step)
            e += dur
    put("serve.loop.step", s, e, model="m", step=step, active_slots=8,
        tokens=8)
    put("serve.loop.terminal", e, e + terminal, model="m", step=step)
    put("serve.loop.publish", e + terminal, e + terminal + publish,
        model="m", step=step)
    return e + terminal + publish


def three_iterations(put):
    # engine host = 0.2 + pack + enqueue + emit: 2.2, 3.2, 4.2
    # service = admit + terminal + publish:      1.5, 2.5, 6.5
    t = 1000.0
    t = iteration(put, t, 1, admit=0.5, pack=1.0, enqueue=0.5,
                  readback=44.0, emit=0.5, terminal=0.5, publish=0.5)
    t = iteration(put, t, 2, admit=0.5, pack=1.5, enqueue=0.5,
                  readback=44.0, emit=1.0, terminal=0.5, publish=1.5)
    t = iteration(put, t, 3, admit=0.5, pack=2.0, enqueue=1.0,
                  readback=44.0, emit=1.0, terminal=1.0, publish=5.0)
    return t


def test_three_iterations_give_the_known_medians(ring):
    end = three_iterations(ring)
    m = {"trace_span": (999 * MS, (end + 1) * MS),
         "window": (0.0, 10.0)}
    assert read("serve_engine_host_ms", m) == pytest.approx(3.2)
    assert read("serve_service_host_ms", m) == pytest.approx(2.5)


def test_only_iterations_that_enqueued_a_decode_program_count(ring):
    end = three_iterations(ring)
    # a prefill-only iteration (no serve.step.enqueue) and an idle one
    # (no step at all), both slow: neither moves a median
    t = iteration(ring, end, 4, admit=0.5, pack=0.0, enqueue=0.0,
                  readback=0.0, emit=0.0, terminal=0.5, publish=30.0,
                  prefill=31.0)
    ring("serve.loop.admit", t, t + 0.5, model="m", step=5)
    ring("serve.loop.publish", t + 0.5, t + 9.5, model="m", step=4)
    ring("serve.loop.wait", t + 9.5, t + 500.0, model="m", step=5)
    m = {"trace_span": (999 * MS, (t + 501) * MS), "window": (0.0, 10.0)}
    assert read("serve_engine_host_ms", m) == pytest.approx(3.2)
    assert read("serve_service_host_ms", m) == pytest.approx(2.5)
    # the span ends before the third iteration's publish began: an
    # iteration whose records the cut leaves incomplete does not count
    m["trace_span"] = (999 * MS, (end - 5.5) * MS)
    assert read("serve_engine_host_ms", m) == pytest.approx(2.7)
    assert read("serve_service_host_ms", m) == pytest.approx(2.0)


def test_the_note_tiles_the_span(ring, capsys):
    import json
    end = three_iterations(ring)
    ring("serve.trace.flush", 1010.0, 1022.0, model="m", events=512,
         bytes=70000)
    m = {"trace_span": (1000 * MS, end * MS), "window": (0.0, 10.0)}
    read("serve_engine_host_ms", m)
    (line,) = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()
               if '"serve_loop_phases"' in ln]
    assert line["decode_iterations"] == 3
    assert line["loop_phases_tile"] == pytest.approx(1.0)
    assert line["step_phases_tile"] == pytest.approx(1.0)
    assert line["phases"]["serve.step.readback"] == {
        "count": 3, "total_s": pytest.approx(0.132),
        "median_s": pytest.approx(0.044)}
    assert line["flushes"] == [[512, 70000, pytest.approx(12.0)]]


def test_no_iteration_reads_none(ring):
    m = {"trace_span": (0.0, 1.0), "window": (0.0, 1.0)}
    assert read("serve_engine_host_ms", m) is None
    assert read("serve_service_host_ms", m) is None
    # an untraced run has no span to cut by
    assert read("serve_engine_host_ms", {"trace_span": None}) is None


def test_a_program_without_the_phase_ring_reads_none(monkeypatch):
    from kubeml_tpu.utils import trace
    monkeypatch.delattr(trace, "phases")
    m = {"trace_span": (0.0, 1.0), "window": (0.0, 1.0)}
    assert read("serve_engine_host_ms", m) is None


def test_batch_occupancy_is_lanes_that_carried_a_stream():
    m = {"counters": {"occupancy_sum": 14, "dispatches": 2}}
    assert read("serve_batch_occupancy_pct", m) == 87.5
    assert read("serve_batch_occupancy_pct",
                {"counters": {"occupancy_sum": 0, "dispatches": 0}}) is None


def test_prefill_share_of_the_busy_time():
    trace = {"busy_s": 2.5, "programs": {
        "jit_step": {"count": 50, "total_s": 2.2, "median_s": 0.044},
        "jit_prefill": {"count": 12, "total_s": 0.375,
                        "median_s": 0.031}}}
    assert read("serve_prefill_device_pct", {"trace": trace}) == \
        pytest.approx(15.0)
    del trace["programs"]["jit_prefill"]
    assert read("serve_prefill_device_pct", {"trace": trace}) is None
    assert read("serve_prefill_device_pct", {"trace": None}) is None
