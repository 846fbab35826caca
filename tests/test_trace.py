"""Tracing subsystem: span accounting, Chrome-trace timelines, per-epoch
summaries in job logs, and the per-job trace directory + merger."""

import json
import re
import threading

import pytest

from kubeml_tpu.utils.trace import (DURATIONS_KEPT, PHASE_RING_SIZE, PhaseRing,
                                    TraceSink, Tracer, get_trace_context,
                                    make_trace_id, merge_job_trace,
                                    trace_context, trace_dir)


class FakeClock:
    """Advances 1.0s on every read — span trees become exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_tracer_spans_and_summary():
    tr = Tracer()
    with tr.span("a"):
        pass
    with tr.span("a"):
        pass
    tr.add("b", 0.5)
    s = tr.summary()
    assert s["a"]["count"] == 2
    assert s["b"]["total_s"] == 0.5
    txt = tr.format_summary()
    assert "a=" in txt and "b=0.500s/1" in txt
    assert tr.reset()["a"]["count"] == 2
    assert tr.summary() == {}


def test_fake_clock_exact_span_tree():
    """Injected clock -> deterministic timeline: exact ts/dur in µs,
    parent links following the per-thread nesting, caller args (including
    ones attached mid-span through the yielded dict) on the event."""
    tid = make_trace_id()
    tr = Tracer(clock=FakeClock(), trace_id=tid)
    with tr.span("epoch", epoch=0):
        with tr.span("round", round=0):
            with tr.span("dispatch") as sp:
                sp["workers"] = 4
    ev = {e["name"]: e for e in tr.events()}
    # clock reads: epoch@1, round@2, dispatch@3, then ends at 4, 5, 6
    assert ev["dispatch"]["ts"] == 3_000_000
    assert ev["dispatch"]["dur"] == 1_000_000
    assert ev["round"]["ts"] == 2_000_000
    assert ev["round"]["dur"] == 3_000_000
    assert ev["epoch"]["ts"] == 1_000_000
    assert ev["epoch"]["dur"] == 5_000_000
    assert all(e["ph"] == "X" for e in ev.values())
    assert ev["dispatch"]["args"] == {"trace_id": tid, "parent": "round",
                                      "workers": 4}
    assert ev["round"]["args"]["parent"] == "epoch"
    assert "parent" not in ev["epoch"]["args"]
    assert ev["epoch"]["args"]["epoch"] == 0
    assert tr.summary()["epoch"] == {"count": 1, "total_s": 5.0,
                                     "mean_s": 5.0}


def test_reset_keeps_timeline_events():
    tr = Tracer(clock=FakeClock())
    with tr.span("a"):
        pass
    tr.reset()
    with tr.span("b"):
        pass
    assert tr.summary() == {"b": {"count": 1, "total_s": 1.0,
                                  "mean_s": 1.0}}
    assert [e["name"] for e in tr.events()] == ["a", "b"]


def test_event_cap_drops_but_keeps_summary():
    tr = Tracer(clock=FakeClock(), max_events=2)
    for _ in range(3):
        with tr.span("a"):
            pass
    assert len(tr.events()) == 2
    assert tr.dropped_events == 1
    assert tr.summary()["a"]["count"] == 3  # the log summary never drops


def test_tracer_thread_safety():
    """Concurrent spans from many threads: no lost updates, and parent
    links never cross threads (each thread has its own nesting stack)."""
    tr = Tracer()
    n_threads, n_spans = 8, 200

    def work():
        for _ in range(n_spans):
            with tr.span("outer"):
                with tr.span("inner"):
                    pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = tr.summary()
    assert s["outer"]["count"] == n_threads * n_spans
    assert s["inner"]["count"] == n_threads * n_spans
    inner = [e for e in tr.events() if e["name"] == "inner"]
    assert len(inner) == n_threads * n_spans
    assert all(e["args"]["parent"] == "outer" for e in inner)


def test_trace_context_binds_and_restores():
    assert get_trace_context() is None
    with trace_context("aaaa000011112222"):
        assert get_trace_context() == "aaaa000011112222"
        with trace_context("bbbb000011112222"):
            assert get_trace_context() == "bbbb000011112222"
        assert get_trace_context() == "aaaa000011112222"
    assert get_trace_context() is None


def test_trace_sink_and_merge(tmp_home):
    tid = make_trace_id()
    t1 = Tracer(clock=FakeClock(), trace_id=tid)
    with t1.span("ps.start_task"):
        pass
    t2 = Tracer(clock=FakeClock(), trace_id=tid)
    with t2.span("epoch"):
        pass
    TraceSink("mergejob1", "ps").write(t1)
    path = TraceSink("mergejob1", "job").write(t2)
    assert json.load(open(path))["metadata"]["trace_id"] == tid
    # a torn/foreign file in the directory is skipped, not fatal
    with open(f"{trace_dir('mergejob1')}/bad.trace.json", "w") as f:
        f.write("{not json")
    doc = merge_job_trace("mergejob1")
    assert sorted(doc["metadata"]["sources"]) == [
        f"job-{__import__('os').getpid()}.trace.json",
        f"ps-{__import__('os').getpid()}.trace.json"]
    assert doc["metadata"]["trace_ids"] == [tid]
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in spans} == {"ps.start_task", "epoch"}
    assert all(e["args"]["trace_id"] == tid for e in spans)
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert procs == {"ps:mergejob1", "job:mergejob1"}
    with pytest.raises(FileNotFoundError):
        merge_job_trace("nosuchjob1")


def test_unreset_tracer_stays_bounded():
    """A serve tracer is never reset(): the summary stays exact (count
    and total) while the kept durations are the newest DURATIONS_KEPT."""
    tr = Tracer(clock=FakeClock(), max_events=4)
    n = DURATIONS_KEPT + 10
    for i in range(n):
        tr.add_span("decode", 0.0, float(i))
    s = tr.summary()["decode"]
    assert s["count"] == n
    assert s["total_s"] == sum(range(n))
    kept = tr.durations()["decode"]
    assert len(kept) == DURATIONS_KEPT
    assert kept[0] == 10.0 and kept[-1] == float(n - 1)
    tr.reset()
    assert tr.summary() == {} and tr.durations() == {}


# ----------------------------------------------------------- loop phases

def test_phase_ring_keeps_newest_maxlen_records():
    ring = PhaseRing(maxlen=4, clock=FakeClock())
    for i in range(10):
        with ring.phase("serve.step.pack", step=i):
            pass
    recs = ring.phases()
    assert [r.args["step"] for r in recs] == [6, 7, 8, 9]
    # t0 and t1 are consecutive readings of the ring's clock
    assert [(r.t0, r.t1) for r in recs] == [
        (13.0, 14.0), (15.0, 16.0), (17.0, 18.0), (19.0, 20.0)]
    assert all(r.tid == threading.get_ident() for r in recs)
    # the process ring holds five minutes of the busiest loop measured:
    # about 20 iterations a second x 12 phases
    assert PHASE_RING_SIZE >= 300 * 20 * 12


def test_phases_cut_by_overlap_and_return_copies():
    ring = PhaseRing(maxlen=16, clock=FakeClock())
    for name in ("a", "b", "c", "d"):       # (1,2) (3,4) (5,6) (7,8)
        with ring.phase(name):
            pass
    names = lambda recs: [r.name for r in recs]
    assert names(ring.phases()) == ["a", "b", "c", "d"]
    # a record that only overlaps the cut is in it; one that ends
    # before it starts or starts after it ends is not
    assert names(ring.phases(3.5, 5.5)) == ["b", "c"]
    assert names(ring.phases(t0=6.0)) == ["c", "d"]
    assert names(ring.phases(t1=2.5)) == ["a"]
    assert ring.phases(2.1, 2.9) == []
    # copies: a reader that edits its records leaves the ring alone
    ring.phases()[0].args["x"] = 1
    assert ring.phases()[0].args == {}


def test_phase_args_are_read_at_exit_and_survive_an_exception():
    ring = PhaseRing(maxlen=4, clock=FakeClock())
    with ring.phase("serve.step.enqueue", step=7) as args:
        args["compiled"] = 1
    with pytest.raises(KeyError):
        with ring.phase("serve.step.emit", step=7):
            raise KeyError("boom")
    enq, emit = ring.phases()
    assert enq.args == {"step": 7, "compiled": 1}
    assert emit.name == "serve.step.emit" and emit.t1 > emit.t0


def test_phase_ring_concurrent_appends_lose_nothing():
    """Loop threads append while another thread reads, with no lock:
    every record of every writer arrives whole."""
    import sys

    ring = PhaseRing(maxlen=100_000)
    n_threads, n_each = 8, 2_000
    stop = threading.Event()
    seen = []

    def write(k):
        for i in range(n_each):
            with ring.phase("w", k=k, i=i):
                pass

    def read():
        while not stop.is_set():
            seen.append(len(ring.phases()))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        writers = [threading.Thread(target=write, args=(k,))
                   for k in range(n_threads)]
        reader.start()
        for t in writers:
            t.start()
        for t in writers:
            t.join(60)
        stop.set()
        reader.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not reader.is_alive() and not any(t.is_alive() for t in writers)
    recs = ring.phases()
    assert len(recs) == n_threads * n_each
    for k in range(n_threads):
        assert [r.args["i"] for r in recs if r.args["k"] == k] == \
            list(range(n_each))
    assert seen == sorted(seen)


def test_sink_writes_phases_beside_the_request_trees(tmp_home):
    """write_phases lands in its own <process>-<pid>.phases.trace.json;
    the merged document shows the phases as X events of the same
    process as the request trees, on the same clock."""
    clk = FakeClock()
    ring = PhaseRing(maxlen=8, clock=clk)
    tr = Tracer(clock=clk)
    with ring.phase("serve.loop.step", model="m", step=3):
        tr.add_span("generate", 1.25, 1.75, rid="r1")
    sink = TraceSink("serve:m", "fleet")
    sink.write(tr)
    path = sink.write_phases(ring.phases())
    assert path.endswith(".phases.trace.json") and path != sink.path
    doc = merge_job_trace("serve:m")
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(spans) == {"generate", "serve.loop.step"}
    assert spans["serve.loop.step"]["ts"] == 1_000_000 \
        and spans["serve.loop.step"]["dur"] == 1_000_000
    assert spans["serve.loop.step"]["args"] == {"model": "m", "step": 3}
    assert spans["serve.loop.step"]["pid"] == spans["generate"]["pid"]
    # the request span lies inside the phase on the shared timeline
    st = spans["serve.loop.step"]
    assert st["ts"] <= spans["generate"]["ts"] \
        and spans["generate"]["ts"] + spans["generate"]["dur"] \
        <= st["ts"] + st["dur"]


def test_job_logs_trace_summary(tmp_path, tmp_home, mesh8):
    from tests.test_job import ToyDataset, make_blobs, make_task
    from kubeml_tpu.data.registry import DatasetRegistry
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.job import TrainJob

    reg = DatasetRegistry()
    make_blobs(reg)
    log = tmp_path / "job.log"
    job = TrainJob(make_task(job_id="tracejob1", epochs=2),
                   get_builtin("mlp")(hidden=16, num_classes=4),
                   ToyDataset(), mesh8, registry=reg, log_file=str(log))
    job.train()
    text = log.read_text()
    # every epoch line carries the phase breakdown (the cache_upload
    # span precedes it on epochs where the device dataset cache laid
    # out or verified its slabs)
    assert len(re.findall(
        r"\[(?:cache_upload=\S+ )?data_wait=\S+ dispatch=\S+ "
        r"epoch=\S+ (?:merge_overlap=\S+ )?merge_wait=\S+ "
        r"round=\S+\]", text)) == 2

    # the same run left a whole-job Chrome timeline in the trace dir:
    # one trace id, round spans nested under epoch spans, dispatch
    # spans nested under rounds
    doc = merge_job_trace("tracejob1")
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    tids = doc["metadata"]["trace_ids"]
    assert len(tids) == 1 and job.task.trace_id == tids[0]
    assert all(e["args"]["trace_id"] == tids[0] for e in spans)
    epochs = [e for e in spans if e["name"] == "epoch"]
    assert [e["args"]["epoch"] for e in epochs] == [0, 1]
    rounds = [e for e in spans if e["name"] == "round"]
    assert rounds and all(e["args"]["parent"] == "epoch" for e in rounds)
    # the exhaustion probe round carries the tail marker, real rounds
    # carry their worker count
    assert [e for e in rounds if e["args"].get("tail")]
    assert [e for e in rounds if e["args"].get("workers")]
    dispatches = [e for e in spans if e["name"] == "dispatch"]
    assert dispatches
    assert all(e["args"]["parent"] == "round" for e in dispatches)
