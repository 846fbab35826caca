"""What every device dispatch says of itself on the phase ring
(serve/engine.py _enqueue and _read, utils/trace.py `phase`): whether
the device had run dry when the host enqueued it (`starved`), whether
its result was waiting when it was read (`ready`), why a step could not
run one dispatch ahead (`serial`), what the pack sent (`transfers`,
`h2d_bytes`), and a prefill chunk tiled by its serve.chunk.* children.
benchmark/metrics/serve_dispatch_records.py and serve_iter_host_ms.py
read these records; benchmark/tests/test_dispatch_metrics.py (by hand)
holds the readers to a hand-made ring.
"""

import math
import threading
import time

import jax
import numpy as np
import pytest

from kubeml_tpu.faults import ServeFaultPlan
from kubeml_tpu.serve import engine as engine_mod
from kubeml_tpu.serve.engine import SERVE_PHASE_KINDS, DecodeEngine
from kubeml_tpu.serve.pager import PageGeometry
from kubeml_tpu.serve.slots import GenerateRequest
from kubeml_tpu.utils import trace
from kubeml_tpu.utils.trace import PhaseRing, phases

pytestmark = pytest.mark.serving

PAGE = 4


@pytest.fixture(scope="module")
def nano():
    """(module, variables, other variables of the same shapes)."""
    from kubeml_tpu.models import gpt
    model = gpt.GPTNano()
    module = model.module
    shape = {"x": np.ones((1, module.max_len), np.int32)}
    return (module, model.init_variables(jax.random.PRNGKey(0), shape),
            model.init_variables(jax.random.PRNGKey(1), shape))


def _req(n_prompt, n_new, temp=0.0, seed=0, start=5):
    return GenerateRequest(list(range(start, start + n_prompt)),
                           max_new_tokens=n_new, temperature=temp, seed=seed)


def _mine(t0, *names):
    """This thread's ring records since t0 under the given names,
    oldest first."""
    tid = threading.get_ident()
    return [r for r in phases(t0=t0) if r.tid == tid and r.name in names]


def _finish(eng, limit=2000):
    while eng.active():
        eng.step()
        limit -= 1
        assert limit > 0, "engine failed to drain"
    eng.drain()
    eng.flush_events()


# ------------------------------------------------------- starved and ready

def test_every_dispatch_says_starved_and_every_readback_ready(nano):
    """Decode dispatches and prefill chunks alike: `starved` is 0 or 1
    on every enqueue record and sums to stats["starved_dispatches"],
    `call_s` is the call's own time inside the phase, `compiled` rides
    on both kinds, and every serve.step.readback record carries
    `ready`."""
    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=4, page=PAGE,
                       prefill_chunk=8)
    t0 = time.monotonic()
    for i, (n, k, temp) in enumerate([(20, 9, 0.0), (3, 12, 0.8),
                                      (11, 7, 0.0)]):
        eng.attach(_req(n, k, temp, i))
    _finish(eng)
    decode = _mine(t0, "serve.step.enqueue")
    chunk = _mine(t0, "serve.chunk.enqueue")
    st = eng.stats
    assert len(decode) == st["dispatches"]
    assert len(chunk) == st["prefill_dispatches"] > 0
    for r in decode + chunk:
        assert r.args["starved"] in (0, 1)
        assert r.args["compiled"] in (0, 1)
        assert 0.0 <= r.args["call_s"] <= r.t1 - r.t0
    assert sum(r.args["starved"] for r in decode + chunk) \
        == st["starved_dispatches"]
    # the engine's first dispatch finds a device with nothing queued
    assert min(decode + chunk, key=lambda r: r.t0).args["starved"] == 1
    assert sum(r.args["compiled"] for r in decode) == 1
    assert sum(r.args["compiled"] for r in chunk) == 1
    reads = _mine(t0, "serve.step.readback")
    assert reads and all(r.args["ready"] in (0, 1) for r in reads)
    # a chunk reads nothing back; the step that ends the regime reads
    # the last dispatch and enqueues none
    assert len(reads) == len(decode) + 1


def test_ready_is_one_once_the_dispatch_has_run(nano):
    """A dispatch that has run reads `ready` 1: its readback phase is
    the fetch's own cost, not a wait. The record of the step that opens
    the regime, with nothing to read, says 1 too."""
    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=0)
    eng.attach(_req(1, 6))
    t0 = time.monotonic()
    eng.step()
    (opening,) = _mine(t0, "serve.step.readback")
    assert opening.args["ready"] == 1 and eng._unread is not None
    jax.block_until_ready(eng._unread.out)
    t1 = time.monotonic()
    eng.step()
    (read,) = _mine(t1, "serve.step.readback")
    assert read.args["ready"] == 1
    _finish(eng)


# ------------------------------------------------- why a step ran serial

def _serial_case(nano, why):
    """(engine, the exclude set of its steps, a callable run once the
    streams are under way) for each of _why_serial's five conditions."""
    module, variables, other = nano
    kw, exclude, midway = dict(prefill_chunk=0), frozenset(), None
    if why == "fault_plan":
        kw["fault_plan"] = ServeFaultPlan([])
    elif why == "accelerated":
        kw["decode_steps"] = 4
    elif why == "pages":
        kw = dict(prefill_chunk=8, geom=PageGeometry.for_module(
            slots=4, page=8, max_len=module.max_len, pages=13))
    eng = DecodeEngine(module, variables, slots=4, page=PAGE, **kw)
    n_new = 34 if why == "pages" else 10
    reqs = [_req(6 if why == "pages" else 1, n_new, 0.0, i, 10 + 50 * i)
            for i in range(4 if why == "pages" else 2)]
    for r in reqs:
        eng.attach(r)
    if why == "exclude":
        exclude = frozenset({reqs[1].rid})
    elif why == "generations":
        # the old streams stay on generation 1, a new one takes 2
        midway = lambda: (eng.install_weights(other),
                          eng.attach(_req(1, 6, 0.0, 9, 200)))
    return eng, reqs, exclude, midway


@pytest.mark.parametrize("why", ["exclude", "fault_plan", "accelerated",
                                 "generations", "pages"])
def test_serial_names_the_reason_and_runs_ahead_keeps_its_truth(nano, why):
    """Each of the five conditions that hold a step back puts its name
    on that step's enqueue record, and a step runs ahead (_why_serial
    says "") exactly where PR 30's _runs_ahead expression said yes."""
    eng, reqs, exclude, midway = _serial_case(nano, why)

    def parents_answer():
        return (not exclude and eng.fault_plan is None
                and eng._multi is None and eng._verify is None
                and len(eng._params_by_gen) == 1
                and eng.pager.free_pages + eng.pager.evictable_pages
                >= eng._step_pages)

    t0 = time.monotonic()
    answers = []
    for n in range(400):
        if not eng.active():
            break
        if n == 3 and midway is not None:
            midway()
        if n == 6:
            exclude = frozenset()
        want = parents_answer()
        assert (eng._why_serial(exclude) == "") is want
        answers.append(want)
        eng.step(exclude)
        assert (eng._serial == "") is want
    _finish(eng)
    recs = _mine(t0, "serve.step.enqueue")
    serial = [r for r in recs if "serial" in r.args]
    assert serial and {r.args["serial"] for r in serial} == {why}
    assert all(r.args["ahead"] == 0 for r in serial)
    assert all("serial" not in r.args for r in recs if r.args["ahead"])
    if why in ("fault_plan", "accelerated"):
        assert not any(answers) and len(serial) == len(recs)
        assert eng.stats["ahead_dispatches"] == 0
    else:
        # the regime engages wherever the condition does not hold
        assert any(answers) and eng.stats["ahead_dispatches"] > 0
    if why == "generations":
        # one dispatch a resident generation in the step after the swap
        by_step = {}
        for r in serial:
            by_step.setdefault(r.args["step"], []).append(r)
        assert max(len(v) for v in by_step.values()) == 2


def test_the_step_that_opens_the_regime_says_no_serial(nano):
    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=0)
    eng.attach(_req(1, 5))
    t0 = time.monotonic()
    _finish(eng)
    recs = _mine(t0, "serve.step.enqueue")
    assert [r.args["ahead"] for r in recs] == [0, 1, 1, 1, 1]
    assert all("serial" not in r.args for r in recs)
    assert eng.stats["ahead_dispatches"] == 4


# ------------------------------------------------------------- transfers

def test_transfers_count_what_the_pack_sent(nano):
    """One greedy stream from a 1-token prompt, token by token: every
    decode pack sends ONE buffer, whatever its lanes hold (the prompt
    token at position 0, the unread dispatch's pick after it, a page's
    first row, all-zero lanes alike), and `h2d_bytes` is the decode
    layout's bytes: the 10 per-lane columns, the key pair and the page
    table, a row a lane."""
    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=0)
    S, P = eng.geom.slots, eng.geom.pages_per_slot
    sent = []
    h2d = eng._h2d
    eng._h2d = lambda host: (sent.append(host.shape), h2d(host))[1]
    eng.attach(_req(1, 8))
    t0 = time.monotonic()
    _finish(eng)
    packs = _mine(t0, "serve.step.pack")
    assert len(packs) == 8
    assert eng._packings["decode"].shape == (S, 10 + 2 + P)
    for r in packs:
        assert r.args["transfers"] == 1
        assert r.args["h2d_bytes"] == 4 * S * (10 + 2 + P)
    # what crossed: one [lanes, columns] buffer a dispatch, no other
    assert sent == [(S, 10 + 2 + P)] * 8


def test_a_chunks_pack_is_one_transfer(nano):
    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=8)
    eng.attach(_req(20, 2))
    t0 = time.monotonic()
    _finish(eng)
    packs = _mine(t0, "serve.chunk.pack")
    assert len(packs) == 3
    P = eng.geom.pages_per_slot
    for r in packs:
        # one vector: tokens, pos, write pages, write offsets and the
        # in-chunk mask, 8 each, and the slot's table row (GPT keeps no
        # per-slot state, so no slot index)
        assert r.args["transfers"] == 1
        assert r.args["h2d_bytes"] == 5 * 4 * 8 + 4 * P
        assert eng._packings["prefill"].shape == (5 * 8 + P,)


@pytest.mark.parametrize("kind", ["decode", "prefill", "multi", "verify"])
def test_every_kinds_pack_is_one_transfer(nano, kind):
    """Each of the four programs' packs sends one buffer of its own
    layout's bytes: as many pack records of that size as the kind's
    dispatches, and no pack record that sent more than one."""
    module, variables, _ = nano
    kw = {"multi": dict(decode_steps=2),
          "verify": dict(draft_module=module, draft_variables=variables)
          }.get(kind, {})
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=8, **kw)
    sent = []
    h2d = eng._h2d
    eng._h2d = lambda host: (sent.append(host.shape), h2d(host))[1]
    eng.attach(_req(11, 6))
    eng.attach(_req(3, 5, start=30))
    t0 = time.monotonic()
    _finish(eng)
    packs = _mine(t0, "serve.step.pack", "serve.chunk.pack")
    assert len(packs) == len(sent)
    assert all(r.args["transfers"] == 1 for r in packs)
    layout = eng._packings[kind]
    mine = [r for r, shape in zip(packs, sent) if shape == layout.shape]
    assert len(mine) == {
        "decode": eng.stats["dispatches"]
        - eng.stats["multi_step_dispatches"]
        - eng.stats["verify_dispatches"],
        "prefill": eng.stats["prefill_dispatches"],
        "multi": eng.stats["multi_step_dispatches"],
        "verify": eng.stats["verify_dispatches"]}[kind] > 0
    assert all(r.args["h2d_bytes"] == 4 * math.prod(layout.shape)
               for r in mine)


# ---------------------------------------------------------- the chunk tiled

class WorkClock:
    """Stands still but for what the test calls work: reading it costs
    a nanosecond, so records keep their order."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-9
        return self.t

    def costs(self, obj, attr, seconds):
        fn = getattr(obj, attr)

        def worked(*a, **kw):
            self.t += seconds
            return fn(*a, **kw)
        setattr(obj, attr, worked)


def test_chunk_phases_tile_the_prefill_phase(nano, monkeypatch):
    """On a clock that only the chunk's work moves (page grants,
    transfers, the enqueue, the prefix registration), the four
    serve.chunk.* children cover their serve.step.prefill, in order and
    without overlap, under the prefill record's step."""
    module, variables, _ = nano
    clock = WorkClock()
    ring = PhaseRing(maxlen=4096, clock=clock)
    monkeypatch.setattr(engine_mod, "phase", ring.phase)
    eng = DecodeEngine(module, variables, slots=2, page=PAGE,
                       prefill_chunk=8)
    clock.costs(eng.pager, "alloc", 1.0)
    clock.costs(eng, "_h2d", 1.0)
    clock.costs(eng, "_ledger_capture", 5.0)
    clock.costs(eng, "_register_full_pages", 1.0)
    eng.attach(_req(20, 2))
    _finish(eng)
    recs = ring.phases()
    outer = [r for r in recs if r.name == "serve.step.prefill"]
    assert len(outer) == 3
    for o in outer:
        kids = [r for r in recs if r.name.startswith("serve.chunk.")
                and o.t0 <= r.t0 and r.t1 <= o.t1]
        names = [r.name for r in kids]
        assert names == ["serve.chunk.pages", "serve.chunk.pack",
                         "serve.chunk.enqueue", "serve.chunk.emit"]
        assert "serve.chunk.pages" in names and "serve.chunk.pack" in names
        assert "serve.chunk.enqueue" in names
        assert "serve.chunk.emit" in names
        assert all(r.args["step"] == o.args["step"] for r in kids)
        assert all(b.t0 >= a.t1 for a, b in zip(kids, kids[1:]))
        covered = sum(r.t1 - r.t0 for r in kids)
        # a page grant, the one transfer and the registration at least
        assert covered >= 0.99 * (o.t1 - o.t0) > 2.5
    for name in ("serve.chunk.pages", "serve.chunk.pack",
                 "serve.chunk.enqueue", "serve.chunk.emit"):
        assert name in SERVE_PHASE_KINDS


def test_the_accepted_reader_reads_as_before(nano):
    """benchmark/metrics/serve_loop_phases.py sums serve.step.* for
    step_phases_tile and takes a step with a serve.step.enqueue record
    for a decode iteration: with the serve.chunk.* records in the ring
    or taken out of it, its table and its iterations are the same."""
    from benchmark.metrics.serve_loop_phases import iterations, table
    from kubeml_tpu.serve.service import ServeService

    module, variables, _ = nano
    eng = DecodeEngine(module, variables, slots=4, page=8, prefill_chunk=8)
    t_before = time.monotonic()
    svc = ServeService("dispatch-records", eng, max_queue=4).start()
    try:
        reqs = [svc.submit(list(range(2, 2 + n)), max_new_tokens=k,
                           temperature=0.0, seed=i)
                for i, (n, k) in enumerate([(20, 12), (3, 16), (27, 9)])]
        for r in reqs:
            assert r.wait(120) and r.outcome == "ok"
    finally:
        svc.stop()
    tid = svc._thread.ident
    recs = [r for r in phases(t0=t_before) if r.tid == tid]
    chunks = [r for r in recs if r.name.startswith("serve.chunk.")]
    assert len(chunks) == 4 * eng.stats["prefill_dispatches"] > 0
    without = [r for r in recs if not r.name.startswith("serve.chunk.")]
    a, b = recs[0].t0, recs[-1].t1
    with_names, loop_share, step_share = table(recs, a, b)
    names, loop_without, step_without = table(without, a, b)
    assert (loop_share, step_share) == (loop_without, step_without)
    assert step_share >= 0.99 and loop_share >= 0.9
    assert {n: v for n, v in with_names.items()
            if not n.startswith("serve.chunk.")} == names
    assert iterations(recs) == iterations(without)
    assert len(iterations(recs)) == eng.stats["dispatches"]
    # the service's counter for the operator: the engine's, by delta
    assert eng.stats["starved_dispatches"] == sum(
        r.args["starved"] for r in recs
        if r.name in ("serve.step.enqueue", "serve.chunk.enqueue"))


# ------------------------------------------- the annotation and its step

class FakeAnnotation:
    """jax.profiler.TraceAnnotation's surface as `phase` uses it."""

    session = False
    built = []

    def __init__(self, name, **kwargs):
        FakeAnnotation.built.append((name, kwargs))

    @staticmethod
    def is_enabled():
        return FakeAnnotation.session

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_session_builds_no_annotation_and_a_session_gets_the_step(
        monkeypatch):
    """With no profiler session a phase is the flag test and the ring
    append, with a `step` or without; in a session the annotation is
    built under the phase's own name and carries the step."""
    monkeypatch.setattr(trace, "_annotation", FakeAnnotation)
    monkeypatch.setattr(FakeAnnotation, "built", [])
    ring = PhaseRing(maxlen=8)
    with ring.phase("serve.step.pack", step=7):
        pass
    with ring.phase("serve.loop.wait", model="m"):
        pass
    assert FakeAnnotation.built == []
    monkeypatch.setattr(FakeAnnotation, "session", True)
    with ring.phase("serve.step.pack", step=7) as args:
        args["transfers"] = 8
    with ring.phase("serve.trace.flush", model="m"):
        pass
    assert FakeAnnotation.built == [("serve.step.pack", {"step": 7}),
                                    ("serve.trace.flush", {})]
    assert [r.name for r in ring.phases()] == [
        "serve.step.pack", "serve.loop.wait", "serve.step.pack",
        "serve.trace.flush"]
    assert ring.phases()[2].args == {"step": 7, "transfers": 8}


def test_a_sessions_annotation_lies_inside_the_phases_clock_pair(
        monkeypatch):
    """What the annotation costs while a session is on is the phase's
    own time: consecutive phases leave no hole between them for it, so
    a traced run's step_phases_tile reads what an untraced one would."""
    clock = WorkClock()

    class Costly(FakeAnnotation):
        def __init__(self, name, **kwargs):
            clock.t += 1.0          # built, with its step= formatted

        def __exit__(self, *exc):
            clock.t += 0.5
            return False

    monkeypatch.setattr(trace, "_annotation", Costly)
    monkeypatch.setattr(FakeAnnotation, "session", True)
    ring = PhaseRing(maxlen=8, clock=clock)
    with ring.phase("serve.loop.step", step=3):
        with ring.phase("serve.step.pack", step=3):
            pass
        with ring.phase("serve.step.enqueue", step=3):
            pass
    pack, enqueue, step = ring.phases()
    assert pack.t1 - pack.t0 >= 1.5 and enqueue.t1 - enqueue.t0 >= 1.5
    assert enqueue.t0 - pack.t1 < 1e-6
    inner = (pack.t1 - pack.t0) + (enqueue.t1 - enqueue.t0)
    # the step's own annotation is all its children do not cover
    assert step.t1 - step.t0 - inner == pytest.approx(1.5, abs=1e-6)


def test_the_profilers_file_names_a_phase_by_its_name_alone(tmp_path):
    """The keyword becomes a stat of the host event: benchmark/lib/
    xplane.py reads `e.name`, which stays the phase's name."""
    import glob

    from jax.profiler import ProfileData

    ring = PhaseRing(maxlen=8)
    out = str(tmp_path / "prof")
    jax.profiler.start_trace(out)
    try:
        for n in (41, 42):
            with ring.phase("serve.chunk.enqueue", step=n):
                time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    if not paths:
        pytest.skip("this build's profiler wrote no .xplane.pb")
    events = [e for p in ProfileData.from_file(paths[-1]).planes
              if p.name.startswith("/host:CPU")
              for line in p.lines for e in line.events
              if e.name.startswith("serve.chunk.")]
    if not events:
        pytest.skip("this build's profiler wrote no host plane")
    assert [e.name for e in events] == ["serve.chunk.enqueue"] * 2
    assert [dict(e.stats)["step"] for e in events] == [41, 42]


# --------------------------------------------------- the operator's counter

def test_starved_dispatches_metric_family():
    """kubeml_serve_starved_dispatches_total passes the metrics lint
    and the service advances it by delta from the engine's counter."""
    from kubeml_tpu.metrics.prom import MetricsRegistry
    from kubeml_tpu.models import gpt
    from kubeml_tpu.serve.service import ServeService
    from tools.check_metrics import validate_exposition

    m = MetricsRegistry()
    m.note_serve_starved_dispatches("m1", 7)
    text = m.exposition()
    assert validate_exposition(text) == []
    assert 'kubeml_serve_starved_dispatches_total{model="m1"} 7' in text
    m.clear_serve("m1")
    assert 'model="m1"' not in m.exposition()

    model = gpt.GPTNano()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": np.ones((1, module.max_len), np.int32)})
    eng = DecodeEngine(module, variables, slots=1, page=8)
    m2 = MetricsRegistry()
    svc = ServeService("m2", eng, max_queue=1, metrics=m2)   # no loop
    eng.stats["starved_dispatches"] = 30
    svc._publish()
    svc._publish()      # same cumulative value: no double count
    assert 'kubeml_serve_starved_dispatches_total{model="m2"} 30' \
        in m2.exposition()
    eng.stats["starved_dispatches"] = 45
    svc._publish()
    assert 'kubeml_serve_starved_dispatches_total{model="m2"} 45' \
        in m2.exposition()
