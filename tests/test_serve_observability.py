"""Serving-plane observability tests (tracing + flight recorder + TTFT).

The contracts pinned here:

  * span-tree exactness — one /generate request yields queue_wait ->
    admit -> prefill_chunk per chunk -> a first_token instant ->
    sampled decode spans -> one terminal instant, all parented to the
    request's root span, timestamped on the engine clock (fake-clock
    verified to the tick)
  * TTFT attribution — queue + prefill + interleave == TTFT exactly
    (interleave is the remainder by construction), both in the trace
    args and in the kubeml_serve_ttft_breakdown_seconds histograms
  * flight recorder — always-on fixed-size ring, O(1) per step, decode
    output bit-identical with it (and tracing) on or off; wraparound
    keeps the newest records oldest-first; auto-snapshot on shed onset
  * trace plumbing — client X-KubeML-Trace-Id rides every span of its
    request through the merged GET /trace?id=serve:<model> document;
    serving-sink drops land in kubeml_trace_events_dropped_total under
    the serve pseudo-job id and in the merge metadata
  * loop phases — utils/trace.py phase(): serve.loop.* tile an iteration
    of the serving loop, serve.step.* tile its engine step, every record
    of an iteration carries that step's number, the ring answers after
    stop(), a profiler session finds the same names in its file, GET
    /trace shows them beside the request trees, and none of it changes
    a decoded token
  * lint — tools/check_serve_spans.py holds every SERVE_SPAN_KINDS and
    SERVE_PHASE_KINDS name to a quoted assertion in tests/ (this file
    carries them)
"""

import itertools
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

pytestmark = pytest.mark.serving


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


def _fake_clock():
    """Deterministic clock: each call is one second after the last, so
    every span endpoint is an exact integer and the additive-breakdown
    arithmetic has no float slop to hide behind."""
    counter = itertools.count(1)
    return lambda: float(next(counter))


def _by_name(events, name):
    return [e for e in events if e["name"] == name]


# ------------------------------------------------------------ flight ring

def test_flight_ring_wraparound_keeps_newest_oldest_first():
    from kubeml_tpu.serve.flight import FlightRecorder

    fl = FlightRecorder(capacity=4)
    assert len(fl) == 0 and fl.total == 0 and fl.snapshot() == []
    for i in range(10):
        fl.record({"step": i})
    assert fl.total == 10
    assert len(fl) == 4
    assert [r["step"] for r in fl.snapshot()] == [6, 7, 8, 9]
    # snapshot returns copies: mutating them never corrupts the ring
    fl.snapshot()[0]["step"] = -1
    assert [r["step"] for r in fl.snapshot()] == [6, 7, 8, 9]
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_flight_records_schema_and_kinds():
    """Every engine step — prefill, decode, idle — leaves exactly one
    record with the documented field set."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.flight import FLIGHT_FIELDS
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          prefill_chunk=16)
    req = GenerateRequest(list(range(2, 36)), max_new_tokens=4)
    engine.attach(req)
    _drive(engine)
    engine.step()  # idle step records too
    records = engine.flight.snapshot()
    assert len(records) == engine.flight.total
    for rec in records:
        assert set(rec) == set(FLIGHT_FIELDS)
    kinds = {r["kind"] for r in records}
    assert "prefill" in kinds and "decode" in kinds and "idle" in kinds
    steps = [r["step"] for r in records]
    assert steps == sorted(steps)  # oldest first, monotone


def test_decode_bit_identical_with_recorder_and_tracer_on_or_off():
    """The observability plane is host-side only: identical tokens with
    the flight recorder + tracer enabled and with both disabled."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    specs = [([5, 6, 7], 6, 0.0, 0),
             ([9, 10, 11, 12], 8, 0.7, 1)]

    def run(**kw):
        engine = DecodeEngine(module, variables, slots=4, page=4, **kw)
        reqs = [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                                seed=s) for p, n, t, s in specs]
        for r in reqs:
            engine.attach(r)
        _drive(engine)
        return [r.tokens for r in reqs]

    instrumented = run(tracer=Tracer(), flight_steps=8,
                       decode_span_every=1)
    bare = run(tracer=None, flight_steps=0)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(t) for t in instrumented]),
        np.concatenate([np.asarray(t) for t in bare]))


# -------------------------------------------------------- span-tree shape

def test_request_span_tree_exact_under_fake_clock():
    """One chunked-prefill request's full tree, to the tick: the fake
    clock advances 1s per reading, so every duration and the additive
    TTFT identity are exact."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    clk = _fake_clock()
    tracer = Tracer(clock=clk)
    engine = DecodeEngine(module, variables, slots=2, page=8, clock=clk,
                          prefill_chunk=16, tracer=tracer,
                          decode_span_every=2)
    prompt = list(range(2, 42))  # 40 tokens -> chunks of 16, 16, 7
    req = GenerateRequest(prompt, max_new_tokens=6,
                          trace_id="cafecafe00000001")
    req.submitted_at = clk()  # what ServeService.submit records
    engine.attach(req)
    _drive(engine)
    assert req.outcome == "ok" and len(req.tokens) == 6
    events = tracer.events()

    # every span/instant of the tree carries the request's trace_id and
    # parents to the root "generate" span
    for ev in events:
        assert ev["args"]["trace_id"] == "cafecafe00000001"
        assert ev["args"]["parent"] == "generate"
        assert ev["args"]["rid"] == req.rid

    (qw,) = _by_name(events, "queue_wait")
    assert qw["ph"] == "X"
    assert qw["ts"] == round(req.submitted_at * 1e6)
    (admit,) = _by_name(events, "admit")
    assert admit["args"]["prompt_tokens"] == 40
    assert admit["ts"] == qw["ts"] + qw["dur"]  # queue ends where admit starts
    chunks = _by_name(events, "prefill_chunk")
    assert [c["args"]["tokens"] for c in chunks] == [16, 16, 7]
    assert all(c["dur"] > 0 for c in chunks)
    (ft,) = _by_name(events, "first_token")
    assert ft["ph"] == "i"
    decodes = _by_name(events, "decode")
    assert [d["args"]["token_index"] for d in decodes] == [2, 4, 6]
    (fin,) = _by_name(events, "finish")
    assert fin["args"]["outcome"] == "ok" and fin["args"]["tokens"] == 6
    assert fin["ts"] == round(req.finished_at * 1e6)

    # additive TTFT attribution: queue + prefill + interleave == TTFT,
    # and the components match the timeline they claim to decompose
    bd = req.ttft_breakdown
    ttft = ft["args"]["ttft"]
    assert ttft == req.first_token_at - req.submitted_at
    assert bd["queue"] == req.admitted_at - req.submitted_at
    # prefill-compute = the three chunk dispatches + the first-token
    # decode dispatch (it consumes the last prompt position); under
    # this clock every dispatch is exactly one tick, so any decode
    # span's dur stands in for the first-token dispatch's
    assert bd["prefill"] * 1e6 == pytest.approx(
        sum(c["dur"] for c in chunks) + decodes[0]["dur"], abs=1)
    assert bd["queue"] + bd["prefill"] + bd["interleave"] == \
        pytest.approx(ttft, abs=1e-9)
    assert ft["args"]["queue"] == bd["queue"]


def test_engine_cancel_and_shed_emit_terminal_instants():
    """'cancel' on mid-stream cancellation; 'shed' (not 'finish') when
    KV exhaustion sheds the newest stream."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    tracer = Tracer()
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          prefill_chunk=0, tracer=tracer)
    req = GenerateRequest([5, 6, 7], max_new_tokens=30)
    engine.attach(req)
    engine.step()
    req.cancel()
    engine.step()
    assert req.outcome == "cancelled"
    (c,) = _by_name(tracer.events(), "cancel")
    assert c["args"]["outcome"] == "cancelled"

    # 2 usable pages of 4 tokens, each request needing 2: the newest
    # stream stalls on page exhaustion and sheds
    from kubeml_tpu.serve.pager import PageGeometry
    tracer2 = Tracer()
    tight = DecodeEngine(module, variables,
                         geom=PageGeometry(slots=2, page=4, pages=3,
                                           pages_per_slot=2),
                         tracer=tracer2)
    old = GenerateRequest([5, 6, 7, 8], max_new_tokens=4)
    new = GenerateRequest([9, 10, 11, 12], max_new_tokens=4)
    tight.attach(old)
    tight.attach(new)
    _drive(tight)
    assert new.outcome == "error" and "shed" in new.error
    sheds = _by_name(tracer2.events(), "shed")
    assert len(sheds) == 1 and sheds[0]["args"]["rid"] == new.rid
    flight_kinds = [r["kind"] for r in tight.flight.snapshot()]
    assert "shed" in flight_kinds


# ----------------------------------------------- service-level incidents

def test_shed_onset_snapshots_flight_ring_once_per_episode():
    """Admission saturation: the FIRST shed dumps the flight ring into
    the trace; sustained shedding does not re-snapshot until a publish
    pass with no sheds re-arms the episode."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.serve.slots import ServeSaturated
    from kubeml_tpu.utils.trace import Tracer

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8)
    tracer = Tracer()
    svc = ServeService("m", engine, max_queue=0, tracer=tracer)
    # loop thread NOT started: submissions sit pending, so capacity
    # (slots 2 + queue 0) saturates deterministically
    svc.submit([5, 6, 7], max_new_tokens=2)
    svc.submit([8, 9], max_new_tokens=2)
    for _ in range(3):
        with pytest.raises(ServeSaturated):
            svc.submit([1, 2], max_new_tokens=2)
    events = tracer.events()
    assert len(_by_name(events, "shed")) == 3
    snaps = _by_name(events, "flight_snapshot")
    assert len(snaps) == 1  # onset only, not per shed
    assert snaps[0]["args"]["reason"] == "shed_onset"
    assert snaps[0]["args"]["total_steps"] == engine.flight.total

    svc._publish()  # sheds happened since last pass: episode stays hot
    with pytest.raises(ServeSaturated):
        svc.submit([1, 2], max_new_tokens=2)
    assert len(_by_name(tracer.events(), "flight_snapshot")) == 1
    svc._publish()  # shed-free pass? no — one shed above keeps it hot
    svc._publish()  # now a clean pass re-arms
    with pytest.raises(ServeSaturated):
        svc.submit([1, 2], max_new_tokens=2)
    assert len(_by_name(tracer.events(), "flight_snapshot")) == 2


def test_serve_trace_drops_counted_and_merged(tmp_home):
    """Serving-sink drops reach kubeml_trace_events_dropped_total under
    the serve pseudo-job id, and the merged trace metadata reports the
    timeline as partial."""
    from kubeml_tpu.metrics.prom import MetricsRegistry
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.serve.slots import ServeSaturated
    from kubeml_tpu.utils.trace import TraceSink, Tracer, merge_job_trace

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=1, page=8)
    reg = MetricsRegistry()
    tracer = Tracer(max_events=2)
    svc = ServeService("m", engine, max_queue=0, metrics=reg,
                       tracer=tracer,
                       trace_sink=TraceSink("serve:m", "serve"))
    svc.submit([5, 6], max_new_tokens=2)
    for _ in range(4):  # shed + snapshot fill the 2-event cap; rest drop
        with pytest.raises(ServeSaturated):
            svc.submit([1, 2], max_new_tokens=2)
    assert tracer.dropped_events > 0
    svc._publish()
    text = reg.exposition()
    assert (f'kubeml_trace_events_dropped_total{{jobid="serve:m"}} '
            f"{float(tracer.dropped_events)}") in text
    svc._flush_trace(force=True)
    merged = merge_job_trace("serve:m")
    assert merged["metadata"]["dropped_events"] == tracer.dropped_events


# ------------------------------------------------------------ end to end

@pytest.fixture()
def serve_ps(tmp_home):
    from kubeml_tpu.control.ps import ParameterServer
    from kubeml_tpu.train.checkpoint import save_checkpoint

    model, _module, variables = _nano()
    save_checkpoint("obsnano", variables,
                    {"model": "gpt-nano", "function": "gpt-nano",
                     "parallelism": 1, "epoch": 0})
    ps = ParameterServer(serve_slots=2, serve_queue_depth=1)
    ps.start()
    yield ps, model, variables
    ps.stop()


def _post(url, body, timeout=60.0, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json", **(headers or {})})
    return urllib.request.urlopen(req, timeout=timeout)


def _get_json(url, timeout=30.0):
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


def test_trace_id_propagates_to_merged_serve_trace(serve_ps):
    """A chunked-prefill request over real HTTP with a client-minted
    trace id: the response echoes the id, and the merged serve trace
    carries the full span tree under it, with the TTFT breakdown
    summing to the TTFT."""
    from kubeml_tpu.utils.trace import TRACE_HEADER

    ps, _model, _variables = serve_ps
    tid = "feedbeef00000042"
    prompt = list(range(2, 42))  # 40 tokens -> 3 chunks at chunk=16
    resp = _post(f"{ps.url}/generate",
                 {"model_id": "obsnano", "prompt": prompt,
                  "max_new_tokens": 4},
                 headers={TRACE_HEADER: tid})
    assert resp.headers.get(TRACE_HEADER) == tid
    events = [json.loads(line) for line in resp.read().splitlines()]
    assert "done" in events[-1]

    # the serve loop flushes the sink on its publish cadence: poll the
    # merged document until this request's spans land
    deadline = time.time() + 15
    mine = []
    while time.time() < deadline:
        try:
            doc = _get_json(f"{ps.url}/trace?id=serve:obsnano")
        except urllib.error.HTTPError:
            time.sleep(0.05)
            continue
        mine = [e for e in doc["traceEvents"]
                if e.get("args", {}).get("trace_id") == tid]
        if any(e["name"] == "generate" for e in mine):
            break
        time.sleep(0.05)
    assert tid in doc["metadata"]["trace_ids"]
    names = [e["name"] for e in mine]
    assert names.count("generate") == 1
    assert "queue_wait" in names and "admit" in names
    assert names.count("prefill_chunk") >= 2
    assert "first_token" in names and "finish" in names
    (ft,) = [e for e in mine if e["name"] == "first_token"]
    bd_sum = (ft["args"]["queue"] + ft["args"]["prefill"]
              + ft["args"]["interleave"])
    assert bd_sum == pytest.approx(ft["args"]["ttft"], abs=1e-6)
    # the root brackets the whole request
    (root,) = [e for e in mine if e["name"] == "generate"]
    assert root["ts"] <= ft["ts"] <= root["ts"] + root["dur"]

    # a second client id lands in the SAME merged doc alongside
    resp = _post(f"{ps.url}/generate",
                 {"model_id": "obsnano", "prompt": [5, 6, 7],
                  "max_new_tokens": 2, "stream": False},
                 headers={TRACE_HEADER: "feedbeef00000043"})
    assert resp.headers.get(TRACE_HEADER) == "feedbeef00000043"
    assert json.loads(resp.read())["tokens"]
    deadline = time.time() + 15
    while time.time() < deadline:
        doc = _get_json(f"{ps.url}/trace?id=serve:obsnano")
        if "feedbeef00000043" in doc["metadata"]["trace_ids"]:
            break
        time.sleep(0.05)
    assert set(doc["metadata"]["trace_ids"]) >= {tid, "feedbeef00000043"}


def test_flight_endpoint_and_breakdown_exposition(serve_ps):
    """GET /flight drains the live ring; the TTFT-breakdown and
    stream-duration histogram families pass the exposition lint."""
    from tools.check_metrics import validate_exposition

    from kubeml_tpu.serve.flight import FLIGHT_FIELDS

    ps, _model, _variables = serve_ps
    _post(f"{ps.url}/generate",
          {"model_id": "obsnano", "prompt": [5, 6, 7, 8],
           "max_new_tokens": 4}).read()
    doc = _get_json(f"{ps.url}/flight?id=serve:obsnano")
    assert doc["id"] == "serve:obsnano" and doc["model"] == "obsnano"
    assert doc["capacity"] > 0
    assert doc["total_steps"] >= 1 and doc["records"]
    # the fleet router stamps each record with the replica it came from
    assert doc["replicas"] == [0]
    for rec in doc["records"]:
        assert set(rec) == set(FLIGHT_FIELDS) | {"replica"}
        assert rec["replica"] == 0
    # bare model id resolves too
    assert _get_json(f"{ps.url}/flight?id=obsnano")["id"] == \
        "serve:obsnano"
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{ps.url}/flight?id=serve:nosuch")
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{ps.url}/flight")
    assert ei.value.code == 400

    wanted = ("kubeml_serve_ttft_breakdown_seconds",
              "kubeml_serve_stream_duration_seconds")
    # the families expose immediately; the breakdown SAMPLES land when
    # the serve loop observes the finished request — poll for those
    deadline = time.time() + 10
    while time.time() < deadline:
        text = urllib.request.urlopen(f"{ps.url}/metrics").read().decode()
        if 'component="queue"' in text:
            break
        time.sleep(0.05)
    for family in wanted:
        assert f"# TYPE {family}" in text, family
    assert 'component="queue"' in text
    assert 'component="prefill"' in text
    assert 'component="interleave"' in text
    assert validate_exposition(text) == []

    # health snapshot carries the breakdown means for `kubeml top`
    deadline = time.time() + 10
    while time.time() < deadline:
        doc = _get_json(f"{ps.url}/health?id=serve:obsnano")
        if doc.get("latest", {}).get("serve_ttft_queue_s") is not None:
            break
        time.sleep(0.05)
    latest = doc["latest"]
    for field in ("serve_ttft_queue_s", "serve_ttft_prefill_s",
                  "serve_ttft_interleave_s"):
        assert field in latest
    assert latest["serve_ttft_queue_s"] + latest["serve_ttft_prefill_s"] \
        + latest["serve_ttft_interleave_s"] == \
        pytest.approx(latest["serve_ttft_p50"], rel=0.5, abs=0.05)


def test_top_renders_ttft_breakdown_line():
    from kubeml_tpu.cli.main import _render_top

    out = _render_top({
        "id": "serve:m", "state": "healthy", "reasons": [],
        "latest": {"serve_active_slots": 1, "serve_slot_cap": 2,
                   "serve_queue_depth": 0, "serve_queue_cap": 4,
                   "serve_kv_page_utilization": 0.25,
                   "serve_ttft_p50": 0.030, "serve_ttft_p99": 0.090,
                   "serve_rejected_total": 0,
                   "serve_prefill_backlog_tokens": 0,
                   "serve_prefix_hit_pct": 50.0,
                   "serve_ttft_queue_s": 0.010,
                   "serve_ttft_prefill_s": 0.015,
                   "serve_ttft_interleave_s": 0.005}})
    assert "ttft breakdown: queue 10ms  prefill 15ms  interleave 5ms" \
        in out
    # without breakdown fields the serve pane renders without the line
    out = _render_top({"id": "serve:m", "state": "healthy", "reasons": [],
                       "latest": {"serve_slot_cap": 2}})
    assert "ttft breakdown" not in out


# ----------------------------------------------------------- loop phases

def _phase_service(model_id, tmp_home=None):
    """A started ServeService on gpt-nano (chunked prefill on, so a
    long prompt costs prefill dispatches) and what it served: two
    requests, awaited. Returns (service, requests, t_before)."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from kubeml_tpu.utils.trace import TraceSink, Tracer

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=8,
                          prefill_chunk=16)
    sink = TraceSink(f"serve:{model_id}", "serve") if tmp_home else None
    t_before = time.monotonic()
    svc = ServeService(model_id, engine, max_queue=4,
                       tracer=Tracer(clock=time.monotonic),
                       trace_sink=sink).start()
    reqs = [svc.submit(list(range(2, 42)), max_new_tokens=6),
            svc.submit([5, 6, 7], max_new_tokens=9, temperature=0.7,
                       seed=3)]
    for r in reqs:
        assert r.wait(120) and r.outcome == "ok"
    return svc, reqs, t_before


def _loop_records(svc, t_before):
    """The service's loop-thread phase records since t_before, oldest
    first (the ring is the process's: other tests' services wrote to it
    too)."""
    from kubeml_tpu.utils.trace import phases

    tid = svc._thread.ident
    return [r for r in phases(t0=t_before) if r.tid == tid]


def test_loop_phases_tile_the_iteration_and_the_step(tmp_home):
    svc, _reqs, t_before = _phase_service("phase-tile", tmp_home)
    try:
        # the loop parks once both streams finish
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not any(
                r.name == "serve.loop.wait"
                for r in _loop_records(svc, t_before)):
            time.sleep(0.01)
        svc.flush_trace()       # a forced sink write, on this thread
    finally:
        svc.stop()
    from kubeml_tpu.utils.trace import phases

    recs = _loop_records(svc, t_before)
    names = {r.name for r in recs}
    # every registered phase name, by its literal
    assert "serve.loop.wait" in names
    assert "serve.loop.admit" in names
    assert "serve.loop.step" in names
    assert "serve.loop.terminal" in names
    assert "serve.loop.publish" in names
    assert "serve.step.reap" in names
    assert "serve.step.prefill" in names
    assert "serve.step.pages" in names
    assert "serve.step.pack" in names
    assert "serve.step.enqueue" in names
    assert "serve.step.readback" in names
    assert "serve.step.emit" in names
    # the forced flush ran on this test's thread, under the same name
    mine = [r for r in phases(t0=t_before)
            if r.args.get("model") == "phase-tile"]
    assert "serve.trace.flush" in {r.name for r in mine}
    assert all(r.args["events"] > 0 and r.args["bytes"] > 0
               for r in mine if r.name == "serve.trace.flush")

    loop = [r for r in recs if r.name.startswith("serve.loop.")]
    steps = [r for r in loop if r.name == "serve.loop.step"]
    inner = [r for r in recs if r.name.startswith("serve.step.")]
    assert all(r.args["model"] == "phase-tile" for r in loop)
    # tiling: on the loop thread no serve.loop.* phase starts before
    # the previous one ended, and together they cover the thread's
    # time from the first to the last (the gaps are a `with` header)
    assert all(b.t0 >= a.t1 for a, b in zip(loop, loop[1:]))
    covered = sum(r.t1 - r.t0 for r in loop)
    assert covered >= 0.9 * (loop[-1].t1 - loop[0].t0)
    # every serve.step.* phase lies inside the serve.loop.step of its
    # step number, they do not overlap, and they cover it
    by_step = {r.args["step"]: r for r in steps}
    for r in inner:
        outer = by_step[r.args["step"]]
        assert outer.t0 <= r.t0 and r.t1 <= outer.t1
    assert all(b.t0 >= a.t1 for a, b in zip(inner, inner[1:]))
    busy = [s for s in steps if s.args["active_slots"] or s.args["tokens"]]
    assert sum(r.t1 - r.t0 for r in inner) >= \
        0.8 * sum(s.t1 - s.t0 for s in busy)
    # what the phases say they did
    assert sum(s.args["tokens"] for s in steps) == 6 + 9
    chunks = [r for r in inner if r.name == "serve.step.prefill"]
    assert sorted(r.args["tokens"] for r in chunks) == [2, 7, 16, 16]
    assert all(r.args["compiled"] in (0, 1) for r in inner
               if r.name == "serve.step.enqueue")


def test_iteration_phases_share_the_step_of_its_flight_record():
    svc, _reqs, t_before = _phase_service("phase-step")
    svc.stop()
    recs = _loop_records(svc, t_before)
    flight = {r["step"]: r for r in svc.engine.flight.snapshot()}
    steps = [r for r in recs if r.name == "serve.loop.step"]
    assert steps
    for st in steps:
        n = st.args["step"]
        # the flight recorder's record of that engine step exists and
        # agrees on what the step produced
        assert flight[n]["tokens"] == st.args["tokens"]
        assert flight[n]["active_slots"] == st.args["active_slots"]
        mine = [r for r in recs if st.t0 <= r.t0 and r.t1 <= st.t1]
        assert {r.args["step"] for r in mine} == {n}
    # the iteration around a step carries the same number: its admit
    # before, its terminal and publish after
    for name in ("serve.loop.admit", "serve.loop.terminal",
                 "serve.loop.publish"):
        around = {r.args["step"] for r in recs if r.name == name}
        assert {st.args["step"] for st in steps} <= around, name


def test_phases_answer_after_the_service_stopped():
    from kubeml_tpu.utils.trace import phases

    svc, _reqs, t_before = _phase_service("phase-stop")
    svc.stop()
    assert not svc._thread.is_alive()
    del svc
    t_after = time.monotonic()
    recs = [r for r in phases(t_before, t_after)
            if r.args.get("model") == "phase-stop"]
    assert [r for r in recs if r.name == "serve.loop.step"]
    # nothing of this service is newer than its stop
    assert phases(t0=t_after + 1.0) == [] or all(
        r.args.get("model") != "phase-stop"
        for r in phases(t0=t_after + 1.0))


# One dispatch sequence for the four programs (DecodeEngine._enqueue and
# ._read):
# kind -> (engine knobs, prompt, the program's name in the tracker and
# the cost ledger, its own dispatch counter, its compile counter). The
# decode-lane kinds start from 1-token prompts, so every step is an
# all-decode steady state and the accelerated programs engage at once;
# the prefill kind's 20-token prompt owes three chunks of 8.
DISPATCH_KINDS = {
    "single_step": (dict(prefill_chunk=0), [5], "serve.decode",
                    "dispatches", "compiles"),
    "multi_step": (dict(decode_steps=4), [5], "serve.multi_step",
                   "multi_step_dispatches", "multi_step_compiles"),
    "verify": (dict(draft=True), [5], "serve.spec_verify",
               "verify_dispatches", "verify_compiles"),
    "prefill": (dict(prefill_chunk=8), list(range(2, 22)), "serve.prefill",
                "prefill_dispatches", "prefill_compiles"),
}
LANE_COUNTERS = ("dispatches", "occupancy_sum", "live_page_entries_sum",
                 "page_entries_sum")


@pytest.mark.parametrize("kind", sorted(DISPATCH_KINDS))
def test_one_dispatch_leaves_the_same_records_whatever_the_program(kind):
    """Two dispatches of each program through the one sequence: the
    phase records a dispatch leaves (a decode-lane dispatch of any kind:
    pages, pack, enqueue, readback, emit, in that order; a prefill
    dispatch its one serve.step.prefill and NO enqueue or readback,
    which is how benchmark/metrics/serve_loop_phases.py tells a decode
    iteration), the decode lane's shared counters moved alike by the
    three kinds and not at all by a prefill dispatch, the kind's own
    dispatch counter up by one a dispatch, and exactly one compile."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    from kubeml_tpu.utils.trace import phases

    knobs, prompt, program, n_key, c_key = DISPATCH_KINDS[kind]
    knobs = dict(knobs)
    _model, module, variables = _nano()
    if knobs.pop("draft", False):
        knobs.update(draft_module=module, draft_variables=variables)
    engine = DecodeEngine(module, variables, slots=4, page=4, **knobs)
    n_reqs = 1 if kind == "prefill" else 2
    for seed in range(n_reqs):
        engine.attach(GenerateRequest(list(prompt), max_new_tokens=12,
                                      temperature=0.0, seed=seed))
    pages = ["serve.step.pages"] * (1 if kind == "single_step" else 2)
    lane = ["serve.step.reap", *pages, "serve.step.pack",
            "serve.step.enqueue", "serve.step.readback", "serve.step.emit"]
    tid = threading.get_ident()
    for nth in (1, 2):
        before = dict(engine.stats)
        t0 = time.monotonic()
        engine.step()
        recs = [r for r in phases(t0=t0) if r.tid == tid
                and r.name.startswith("serve.step.")
                and r.args.get("step") == engine._step_count]
        names = [r.name for r in recs]
        moved = {k: engine.stats[k] - before[k] for k in LANE_COUNTERS}
        assert engine.stats[n_key] - before[n_key] == 1
        if kind == "prefill":
            assert names == ["serve.step.reap", "serve.step.prefill",
                             "serve.step.pages"]
            assert recs[1].args["tokens"] == 8
            assert moved == dict.fromkeys(LANE_COUNTERS, 0)
            assert engine.stats["prefill_tokens"] == 8 * nth
            assert engine.stats["generated_tokens"] == 0
            continue
        assert names == lane
        enqueue = recs[names.index("serve.step.enqueue")]
        assert enqueue.args["compiled"] == (1 if nth == 1 else 0)
        # the single-step program runs one dispatch ahead: the first
        # step's dispatch stays unread (its readback and emit records
        # are empty), the second enqueues with it unread and reads it
        ahead = kind == "single_step"
        assert enqueue.args["ahead"] == int(ahead and nth == 2)
        assert recs[-1].args.get("overrun", 0) == 0
        assert all(b.t0 >= a.t1 for a, b in zip(recs, recs[1:]))
        assert moved["dispatches"] == 1
        assert moved["occupancy_sum"] == n_reqs
        assert moved["page_entries_sum"] == \
            n_reqs * engine.geom.pages_per_slot
        assert n_reqs <= moved["live_page_entries_sum"] \
            <= moved["page_entries_sum"]
        assert (engine.stats["generated_tokens"]
                > before["generated_tokens"]) == (not ahead or nth == 2)
        assert engine.stats["kv_bytes"] == \
            engine.stats["decode_tokens"] * engine.kv_bytes_per_token
    assert engine.stats[c_key] == 1
    assert (engine._unread is not None) == (kind == "single_step")
    engine.drain()
    assert engine.stats["ahead_dispatches"] == int(kind == "single_step")
    # nobody else compiled, or dispatched under this kind's name
    for other, (*_, o_c) in DISPATCH_KINDS.items():
        if other != kind and o_c != c_key:
            assert engine.stats[o_c] == 0, (other, o_c)
    # the tracker and the cost ledger heard of both, under one name
    assert list(engine.compile_tracker._recent) == [program]
    snap = engine.compile_tracker.snapshot()
    assert (snap["jit_dispatches"], snap["jit_compiles"]) == (2, 1)
    totals = engine.ledger.totals(program)
    assert totals["dispatches"] == 2
    assert totals["tokens"] == engine.stats["generated_tokens"]
    engine.flush_events()


def _profiled_decode(tmp_path, profile: bool):
    """Three requests through a bare engine, with a profiler session
    around the steps or without; returns (tokens, trace dir)."""
    import jax

    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=4, page=4)
    reqs = [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                            seed=sd)
            for p, n, t, sd in (([5, 6, 7], 6, 0.0, 0),
                                ([9, 10, 11, 12], 8, 0.7, 1),
                                ([3, 4], 5, 1.1, 2))]
    for r in reqs:
        engine.attach(r)
    engine.step()               # compile outside the session
    out = str(tmp_path / ("prof" if profile else "noprof"))
    if profile:
        jax.profiler.start_trace(out)
    try:
        _drive(engine)
    finally:
        if profile:
            jax.profiler.stop_trace()
    return [list(r.tokens) for r in reqs], out


def test_decode_bit_identical_with_profiler_session_on_or_off(tmp_path):
    """The phases' TraceAnnotation records only while a session is on:
    either way the decoded tokens are the same."""
    on, _ = _profiled_decode(tmp_path, profile=True)
    off, _ = _profiled_decode(tmp_path, profile=False)
    assert on == off and all(on)


def test_profiler_session_finds_the_phases_in_its_file(tmp_path):
    """Anybody's jax.profiler.start_trace gets the program's phases on
    the profiler's own clock: the .xplane.pb of a session around three
    or more engine steps holds serve.step.readback events."""
    import glob

    from jax.profiler import ProfileData

    _tokens, out = _profiled_decode(tmp_path, profile=True)
    paths = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    if not paths:
        pytest.skip("this build's profiler wrote no .xplane.pb")
    host = [p for p in ProfileData.from_file(paths[-1]).planes
            if p.name.startswith("/host:CPU")]
    if not host:
        pytest.skip("this build's profiler wrote no host plane")
    names = [e.name for p in host for line in p.lines
             for e in line.events]
    assert names.count("serve.step.readback") >= 3
    # one of each a decode iteration, and the step that reads the last
    # dispatch enqueues none
    assert names.count("serve.step.enqueue") + 1 == \
        names.count("serve.step.readback")


def test_get_trace_shows_loop_phases_beside_request_trees(serve_ps):
    ps, _model, _variables = serve_ps
    _post(f"{ps.url}/generate",
          {"model_id": "obsnano", "prompt": list(range(2, 30)),
           "max_new_tokens": 4}).read()
    doc = _get_json(f"{ps.url}/trace?id=serve:obsnano")
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    steps = [e for e in spans if e["name"] == "serve.loop.step"]
    assert steps and all(e["args"]["model"] == "obsnano" for e in steps)
    assert sum(e["args"]["tokens"] for e in steps) >= 4
    assert [e for e in spans if e["name"] == "serve.step.readback"]
    # same process as the request tree, and the request's prefill
    # chunk lies inside a serve.step.prefill phase of the loop thread
    (chunk,) = [e for e in spans if e["name"] == "prefill_chunk"][:1]
    assert chunk["pid"] == steps[0]["pid"]
    assert any(e["ts"] <= chunk["ts"] and chunk["ts"] + chunk["dur"]
               <= e["ts"] + e["dur"] + 1
               for e in spans if e["name"] == "serve.step.prefill")
    assert any(s.endswith(".phases.trace.json")
               for s in doc["metadata"]["sources"])
    # the ring as it is: the chunk's children and every dispatch's own
    # account of itself, with no code of the endpoint's for either
    enqueues = [e for e in spans if e["name"] in (
        "serve.step.enqueue", "serve.chunk.enqueue")]
    assert {e["name"] for e in enqueues} == {
        "serve.step.enqueue", "serve.chunk.enqueue"}
    assert all(e["args"]["starved"] in (0, 1) and e["args"]["call_s"] >= 0
               for e in enqueues)
    assert all(e["args"]["transfers"] > 0 for e in spans
               if e["name"] in ("serve.step.pack", "serve.chunk.pack"))
    assert all(e["args"]["ready"] in (0, 1) for e in spans
               if e["name"] == "serve.step.readback")


def test_paged_programs_carry_named_scopes():
    """Metadata only (the bit-identity suites pin the math): the
    lowered decode and prefill programs name their parts."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.models.gpt import (PAGED_SCOPES,
                                       build_paged_decode_step,
                                       build_paged_prefill_step)
    from kubeml_tpu.serve.pager import KVPageSlab, PageGeometry

    _model, module, variables = _nano()
    geom = PageGeometry.for_module(slots=2, page=8, max_len=module.max_len)
    slab = KVPageSlab(geom, module.layers, module.heads,
                      module.hidden // module.heads, module.dtype)
    S, P = geom.slots, geom.pages_per_slot
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)
    slabs = (variables["params"], slab.k, slab.v, slab.k_scale,
             slab.v_scale, slab.valid)
    decode = jax.jit(build_paged_decode_step(module)).lower(
        *slabs, i32(S), i32(S), i32(S, P), i32(S), i32(S), f32(S), f32(S),
        jnp.zeros((S, 2), jnp.uint32), i32(S), i32(S), f32(S)
    ).as_text(debug_info=True)
    prefill = jax.jit(build_paged_prefill_step(module, 16)).lower(
        *slabs, i32(16), i32(16), i32(P), i32(16), i32(16), f32(16)
    ).as_text(debug_info=True)
    # the prefill program returns pages only, so its last layer's
    # attention and MLP are dead code: look at its first layer
    for text, layer, scopes in ((decode, module.layers - 1, PAGED_SCOPES),
                                (prefill, 0, PAGED_SCOPES[1:-2])):
        for name in scopes:
            per_layer = name in ("qkv", "kv_write", "attn", "proj", "mlp")
            want = f"/layer_{layer}/{name}/" if per_layer else f"/{name}/"
            assert want in text, want
    assert "cow_split" not in prefill and "sample" not in prefill


# ------------------------------------------------------------------- lint

def test_serve_span_lint_passes_on_this_repo():
    import tools.check_serve_spans as lint
    assert lint.main(["check_serve_spans.py"]) == 0


def test_serve_phase_registry_is_linted():
    """The seventeen loop-phase names are a registry the lint reads
    (the benchmark's readers key on them), dotted names included."""
    import os

    import tools.check_serve_spans as lint
    from kubeml_tpu.serve.engine import SERVE_PHASE_KINDS

    engine_py = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "kubeml_tpu", "serve", "engine.py")
    assert lint.phase_kinds(engine_py) == list(SERVE_PHASE_KINDS)
    assert len(SERVE_PHASE_KINDS) == 17
    assert {k.rsplit(".", 1)[0] for k in SERVE_PHASE_KINDS} == {
        "serve.loop", "serve.step", "serve.chunk", "serve.trace"}


def test_serve_span_lint_holds_phase_kinds(tmp_path):
    import tools.check_serve_spans as lint

    (tmp_path / "kubeml_tpu" / "serve").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    eng = tmp_path / "kubeml_tpu" / "serve" / "engine.py"
    eng.write_text('SERVE_SPAN_KINDS = ("zz_alpha",)\n'
                   'SERVE_PHASE_KINDS = ("zz.loop.beta",)\n')
    t = tmp_path / "tests" / "test_spans.py"
    t.write_text('assert "zz_alpha" in kinds\n')
    assert lint.main(["x", str(tmp_path)]) == 1
    t.write_text('assert "zz_alpha" in kinds\n'
                 'assert "zz.loop.beta" in names\n')
    assert lint.main(["x", str(tmp_path)]) == 0


def test_serve_span_lint_self_test(tmp_path):
    """The lint catches an unasserted kind, accepts a quoted assert
    line, and ignores names that only appear in comments."""
    import tools.check_serve_spans as lint

    root = tmp_path
    (root / "kubeml_tpu" / "serve").mkdir(parents=True)
    (root / "tests").mkdir()
    eng = root / "kubeml_tpu" / "serve" / "engine.py"
    eng.write_text('SERVE_SPAN_KINDS = ("zz_alpha", "zz_beta")\n')

    # nothing asserted -> both missing, exit 1
    assert lint.main(["x", str(root)]) == 1
    assert lint.unasserted_kinds(str(eng), str(root / "tests")) == \
        ["zz_alpha", "zz_beta"]

    # a comment mention and a non-assert use do NOT count
    t = root / "tests" / "test_spans.py"
    t.write_text('# zz_alpha is great\nkinds = ["zz_alpha"]\n'
                 'assert "zz_beta" in kinds\n')
    assert lint.unasserted_kinds(str(eng), str(root / "tests")) == \
        ["zz_alpha"]
    assert lint.main(["x", str(root)]) == 1

    # a quoted name on an assert line satisfies the lint
    t.write_text('kinds = ["zz_alpha", "zz_beta"]\n'
                 'assert "zz_alpha" in kinds\n'
                 'assert "zz_beta" in kinds\n')
    assert lint.main(["x", str(root)]) == 0

    # a miswired tuple (engine refactor) fails loudly, not silently
    eng.write_text("RENAMED = ()\n")
    assert lint.main(["x", str(root)]) == 1
