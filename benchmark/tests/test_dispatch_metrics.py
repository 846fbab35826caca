"""The five per-layer metrics that read what each dispatch says of itself
(metrics/serve_dispatch_records.py), on a phase ring the test fills
with known times and arguments: both kinds of iteration, a readback of
a waiting result against a wait, the notes' sums, and a ring from
before PR 35."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import run  # noqa: E402

MS = 1e-3
NAMES = ("serve_starved_dispatch_pct", "serve_ahead_engaged_pct",
         "serve_iter_host_ms.decode", "serve_iter_host_ms.chunk",
         "serve_h2d_transfers_per_dispatch")


def read(name, measured):
    spec = run.metric_specs()[name]
    return run.read_metric(name, spec, {"config": {}}, measured)


class Clock:
    t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture()
def ring(monkeypatch):
    """A phase ring on a clock the test sets, in the process ring's
    place. `put(name, t0, t1, **args)` records one phase, in ms; an
    outer phase is put after its children, as a `with` block exits."""
    from kubeml_tpu.utils import trace
    clock = Clock()
    ring = trace.PhaseRing(maxlen=512, clock=clock)
    monkeypatch.setattr(trace, "phases", ring.phases)

    def put(name, t0, t1, **args):
        clock.t = t0 * MS
        with ring.phase(name, **args):
            clock.t = t1 * MS
    return put


def note_of(capsys, name):
    (line,) = [json.loads(ln) for ln in capsys.readouterr().err.splitlines()
               if f'"name": "{name}"' in ln]
    return line


def iteration(put, t, step, *, readback, ready, chunk=0.0, starved=0,
              chunk_starved=0, ahead=1, serial=None, new=True, pack=2.0,
              transfers=8, publish=1.0, chunk_compiled=0):
    """One loop iteration from t ms: admit 0.5, reap 0.1, a chunk of
    `chunk` ms (pages 0.2, pack 0.3, enqueue the rest but 0.1 of emit),
    pages 0.1, pack, enqueue 1.0 of which the call is 0.6, readback,
    emit 0.3, terminal 0.2, publish. `new` False leaves the arguments
    PR 35 added away. Returns where the iteration ends."""
    extra = (lambda **kw: kw) if new else (lambda **kw: {})
    put("serve.loop.admit", t, t + 0.5, step=step)
    s = e = t + 0.5
    put("serve.step.reap", e, e + 0.1, step=step)
    e += 0.1
    if chunk:
        c = e
        if new:
            put("serve.chunk.pages", c, c + 0.2, step=step)
            put("serve.chunk.pack", c + 0.2, c + 0.5, step=step,
                transfers=7, h2d_bytes=12000)
            put("serve.chunk.enqueue", c + 0.5, c + chunk - 0.1, step=step,
                compiled=chunk_compiled, starved=chunk_starved,
                call_s=0.5 * MS)
            put("serve.chunk.emit", c + chunk - 0.1, c + chunk, step=step)
        put("serve.step.prefill", c, c + chunk, step=step, tokens=16)
        e += chunk
    put("serve.step.pages", e, e + 0.1, step=step)
    e += 0.1
    put("serve.step.pack", e, e + pack, step=step,
        **extra(transfers=transfers, h2d_bytes=2400))
    e += pack
    more = {"serial": serial} if serial else {}
    put("serve.step.enqueue", e, e + 1.0, step=step, compiled=0, ahead=ahead,
        **extra(starved=starved, call_s=0.6 * MS, **more))
    e += 1.0
    put("serve.step.readback", e, e + readback, step=step,
        **extra(ready=ready))
    e += readback
    put("serve.step.emit", e, e + 0.3, step=step, overrun=0)
    e += 0.3
    put("serve.loop.step", s, e, step=step, active_slots=8, tokens=8)
    put("serve.loop.terminal", e, e + 0.2, step=step)
    put("serve.loop.publish", e + 0.2, e + 0.2 + publish, step=step)
    return e + 0.2 + publish


def five_iterations(put, new=True):
    """Without a chunk, whole / host: 6.2 / 6.2 (a ready readback of
    1.0 stays in), 35.2 / 5.2 (a wait of 30 goes out), 7.2 / 7.2
    (publish 2.0). With one: 9.2 / 9.2 and 38.2 / 10.2 (chunk 5.0, a
    wait of 28)."""
    t = 1000.0
    t = iteration(put, t, 1, readback=1.0, ready=1, starved=1, ahead=0,
                  new=new)
    t = iteration(put, t, 2, readback=30.0, ready=0, new=new)
    t = iteration(put, t, 3, readback=1.0, ready=1, starved=1, publish=2.0,
                  transfers=6, new=new)
    t = iteration(put, t, 4, readback=1.0, ready=1, chunk=3.0, starved=0,
                  chunk_starved=1, new=new)
    t = iteration(put, t, 5, readback=28.0, ready=0, chunk=5.0, ahead=0,
                  serial="pages", chunk_compiled=1, new=new)
    return t


def span(end, start=999.0):
    """A traced span over [start, end] ms that is the whole measured
    window too: nothing to widen to."""
    cut = (start * MS, (end + 1) * MS)
    return {"trace_span": cut, "window": cut}


def test_both_kinds_of_iteration_give_their_known_medians(ring, capsys):
    m = span(five_iterations(ring))
    assert read("serve_iter_host_ms.decode", m) == pytest.approx(6.2)
    line = note_of(capsys, "serve_iter_host_ms.decode")
    assert line["iterations"] == 3 and line["of_decode_iterations"] == 5
    assert line["whole_ms"] == pytest.approx(7.2)
    assert line["wait_ms"] == pytest.approx(0.0)
    assert line["phase_ms"]["serve.step.pack"] == pytest.approx(2.0)
    assert line["phase_ms"]["serve.loop.publish"] == pytest.approx(1.0)
    assert "serve.step.prefill" not in line["phase_ms"]
    # three readbacks of five found their result waiting
    assert (line["readbacks"], line["readbacks_ready"]) == (5, 3)
    assert line["ready_readback_ms"] == pytest.approx(1.0)
    assert line["waiting_readback_ms"] == pytest.approx(29.0)
    assert line["call_ms"] == {"decode": pytest.approx(0.6),
                               "chunk": pytest.approx(0.5)}
    # one chunk's call compiled inside the span
    assert line["compiled"] == {"decode": 0, "chunk": 1}
    assert read("serve_iter_host_ms.chunk", m) == pytest.approx(9.7)
    line = note_of(capsys, "serve_iter_host_ms.chunk")
    assert line["iterations"] == 2
    assert line["whole_ms"] == pytest.approx(23.7)
    assert line["phase_ms"]["serve.step.prefill"] == pytest.approx(4.0)
    assert line["phase_ms"]["serve.chunk.enqueue"] == pytest.approx(3.4)
    assert line["phase_ms"]["serve.chunk.pages"] == pytest.approx(0.2)


def test_a_ready_readback_is_host_time_and_a_wait_is_not(ring):
    t = iteration(ring, 1000.0, 1, readback=4.0, ready=1)
    assert read("serve_iter_host_ms.decode", span(t)) == pytest.approx(9.2)
    t = iteration(ring, 2000.0, 2, readback=4.0, ready=0)
    m = span(t, 1999.0)
    assert read("serve_iter_host_ms.decode", m) == pytest.approx(5.2)
    # no iteration of the other kind in either span
    assert read("serve_iter_host_ms.chunk", m) is None


def test_a_span_with_few_of_a_kind_reads_the_whole_window(ring, capsys):
    """The MoE cells' 3 s hold about a hundred iterations, and on some
    seeds every one has a chunk (the driver's K-EXAONE run of PR 35):
    the kind's iterations of the measured window are read then, and the
    note says so."""
    from benchmark.metrics.serve_dispatch_records import FEW
    t = 1000.0
    for step in range(1, FEW + 1):      # before the span: no chunk
        t = iteration(ring, t, step, readback=1.0, ready=1,
                      publish=1.0 + 0.1 * step)
    cut = t
    for step in range(FEW + 1, 2 * FEW + 1):    # the span: a chunk each
        t = iteration(ring, t, step, readback=1.0, ready=1, chunk=3.0)
    m = {"trace_span": ((cut - 0.5) * MS, (t + 1) * MS),
         "window": (999 * MS, (t + 1) * MS)}
    assert read("serve_iter_host_ms.chunk", m) == pytest.approx(9.2)
    line = note_of(capsys, "serve_iter_host_ms.chunk")
    assert (line["over"], line["iterations"]) == ("trace_span", FEW)
    assert line["of_decode_iterations"] == FEW
    # publish 1.1 .. 2.0 over the ten: the median iteration is 6.75
    assert read("serve_iter_host_ms.decode", m) == pytest.approx(6.75)
    line = note_of(capsys, "serve_iter_host_ms.decode")
    assert (line["over"], line["iterations"]) == ("window", FEW)
    assert line["of_decode_iterations"] == 2 * FEW
    assert line["readbacks"] == 2 * FEW
    # one of the kind in the span and no more in the window: that one
    m["trace_span"] = (999 * MS, (1000.0 + 8.0) * MS)
    m["window"] = m["trace_span"]
    assert read("serve_iter_host_ms.decode", m) == pytest.approx(6.3)
    line = note_of(capsys, "serve_iter_host_ms.decode")
    assert (line["over"], line["iterations"]) == ("window", 1)
    # a measured window the caller did not give: the span's, as it is
    del m["window"]
    assert read("serve_iter_host_ms.decode", m) == pytest.approx(6.3)


def test_starved_share_and_what_the_host_did_before(ring, capsys):
    m = span(five_iterations(ring))
    # seven dispatches: five decode (steps 1 and 3 starved), two chunks
    # (step 4's starved)
    assert read("serve_starved_dispatch_pct", m) == pytest.approx(300 / 7)
    line = note_of(capsys, "serve_starved_dispatch_pct")
    decode, chunk = line["by_kind"]["decode"], line["by_kind"]["chunk"]
    assert (decode["dispatches"], decode["starved"]) == (5, 2)
    assert (chunk["dispatches"], chunk["starved"]) == (2, 1)
    # step 1 has no dispatch before it. Step 3's starved call: from the
    # end of step 2's call (0.4 of its enqueue phase left) to step 3's
    # enqueue: readback 30, emit 0.3, terminal 0.2, publish 1.0, then
    # admit 0.5, reap 0.1, pages 0.1, pack 2.0
    before = decode["before_starved_by_phase_s"]
    assert before["serve.step.readback"] == pytest.approx(30.0 * MS)
    assert before["serve.step.enqueue"] == pytest.approx(0.4 * MS)
    assert before["serve.step.pack"] == pytest.approx(2.0 * MS)
    assert before["serve.loop.publish"] == pytest.approx(1.0 * MS)
    assert "serve.loop.step" not in before      # its children hold it all
    assert decode["host_before_starved_s"] == pytest.approx(34.6 * MS)
    # step 4's starved chunk: what step 3 left after its call (0.4),
    # readback 1.0, emit, terminal, publish 2.0, admit, reap, then the
    # chunk's own pages 0.2 and pack 0.3
    before = chunk["before_starved_by_phase_s"]
    assert before["serve.chunk.pack"] == pytest.approx(0.3 * MS)
    assert before["serve.loop.publish"] == pytest.approx(2.0 * MS)
    assert "serve.step.prefill" not in before
    assert chunk["host_before_starved_s"] == pytest.approx(5.0 * MS)
    longest = line["longest_before_starved"]
    assert [(c["kind"], c["step"]) for c in longest] == [
        ("decode", 3), ("chunk", 4)]
    assert longest[0]["seconds"] == pytest.approx(34.6 * MS)
    assert longest[0]["by_phase_s"]["serve.step.readback"] == \
        pytest.approx(30.0 * MS)


def test_ahead_share_counts_the_reasons(ring, capsys):
    m = span(five_iterations(ring))
    assert read("serve_ahead_engaged_pct", m) == pytest.approx(60.0)
    line = note_of(capsys, "serve_ahead_engaged_pct")
    assert line["decode_dispatches"] == 5 and line["ahead"] == 3
    assert line["serial_by_reason"] == {"pages": 1}
    assert line["opened"] == 1


def test_transfers_a_dispatch(ring, capsys):
    m = span(five_iterations(ring))
    assert read("serve_h2d_transfers_per_dispatch", m) == \
        pytest.approx((4 * 8 + 6) / 5)
    line = note_of(capsys, "serve_h2d_transfers_per_dispatch")
    assert (line["decode_packs"], line["chunk_packs"]) == (5, 2)
    assert line["chunk_transfers"] == pytest.approx(7.0)
    assert line["decode_h2d_bytes"] == pytest.approx(2400.0)
    assert line["chunk_h2d_bytes"] == pytest.approx(12000.0)


def test_a_ring_from_before_the_arguments_reads_none(ring):
    """The parent's program: phases and `ahead`, none of PR 35's
    arguments, no serve.chunk.* records."""
    m = span(five_iterations(ring, new=False))
    for name in NAMES:
        if name != "serve_ahead_engaged_pct":
            assert read(name, m) is None, name
    # `ahead` is on the ring since PR 30: the parent reads it too
    assert read("serve_ahead_engaged_pct", m) == pytest.approx(60.0)


def test_nothing_to_cut_or_to_read_is_none(ring, monkeypatch):
    from kubeml_tpu.utils import trace
    for name in NAMES:
        assert read(name, {"trace_span": None}) is None
        assert read(name, {"trace_span": (0.0, 1.0),
                           "window": (0.0, 1.0)}) is None
    monkeypatch.delattr(trace, "phases")
    for name in NAMES:
        assert read(name, {"trace_span": (0.0, 1.0)}) is None
