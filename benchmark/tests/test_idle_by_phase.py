"""tools/idle_by_phase.py on hand-made spans: the innermost-span
partition, idle time split by overlap, the join of calls to device
programs at a window's edge, and what `starved` is checked against."""

import collections
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.tools import idle_by_phase as tool  # noqa: E402

Rec = collections.namedtuple("Rec", "name t0 t1 tid args")
US = 1000


def test_innermost_gives_every_instant_to_the_deepest_span():
    spans = [("serve.loop.step", 0, 100, 1),
             ("serve.step.reap", 0, 10, 1),
             ("serve.step.prefill", 10, 60, 1),
             ("serve.chunk.pages", 12, 20, 1),
             ("serve.chunk.enqueue", 20, 55, 1),
             ("serve.step.pack", 70, 100, 1),
             ("serve.loop.publish", 105, 120, 1)]
    assert tool.innermost(spans) == [
        ("serve.step.reap", 0, 10),
        ("serve.step.prefill", 10, 12),
        ("serve.chunk.pages", 12, 20),
        ("serve.chunk.enqueue", 20, 55),
        ("serve.step.prefill", 55, 60),
        ("serve.loop.step", 60, 70),
        ("serve.step.pack", 70, 100),
        ("serve.loop.publish", 105, 120)]


def test_idle_is_split_by_overlap_and_the_rows_sum_to_it():
    segments = [("a", 0, 10), ("b", 10, 30), ("c", 40, 50)]
    idle = [(5, 15), (25, 45), (60, 70)]
    got = tool.split_by_overlap(idle, segments)
    assert got == {"a": 5, "b": 10, "c": 5, "(no phase)": 20}
    assert sum(got.values()) == sum(b - a for a, b in idle)
    assert tool.complement([(10, 20), (30, 40)], (0, 50)) == [
        (0, 10), (20, 30), (40, 50)]
    assert tool.complement([(0, 20)], (0, 20)) == []


def dispatch(kind, t0, step):
    name = {v: k for k, v in tool.ENQUEUES.items()}[kind]
    return (kind, t0, name, step)


def test_the_join_skips_the_programs_of_calls_before_the_window():
    programs_of = {"decode": "jit_step", "prefill": "jit_prefill"}
    # two programs of calls made before the window, then one a call;
    # the device idles 300 us before the third and the fifth
    modules = [("jit_prefill", 0, 400 * US), ("jit_step", 400 * US, 900 * US),
               ("jit_step", 1200 * US, 1700 * US),
               ("jit_prefill", 1701 * US, 2100 * US),
               ("jit_step", 2400 * US, 2900 * US)]
    calls = [dispatch("decode", 1100 * US, 7),
             dispatch("prefill", 1300 * US, 8),
             dispatch("decode", 2300 * US, 8)]
    records = [Rec(c[2], i, i + 0.5, 1, {"step": c[3], "starved": s})
               for i, (c, s) in enumerate(zip(calls, (1, 0, 1)))]
    joined, skipped, differ = tool.join_dispatches(
        calls, modules, programs_of, records)
    assert (skipped, differ) == (2, 0)
    assert tool.device_clock_early(joined) == 0
    assert [(d["kind"], d["step"], d["starved"]) for d in joined] == [
        ("decode", 7, 1), ("prefill", 8, 0), ("decode", 8, 1)]
    assert [d["idle_before_ns"] for d in joined] == [
        300 * US, 1 * US, 300 * US]
    assert [d["launch_ns"] for d in joined] == [100 * US, 401 * US, 100 * US]
    got = tool.reduce_dispatches(joined)
    assert got["joined"] == 3 and got["starved_agrees_pct"] == 100.0
    assert got["decode.starved"]["dispatches"] == 2
    assert got["prefill.fed"]["idle_before_median_us"] == 1.0
    # a flag that says the opposite of the trace is counted against it
    joined[1]["starved"] = 1
    got = tool.reduce_dispatches(joined)
    assert got["starved_agrees_pct"] == 100.0 * 2 / 3
    # a predecessor that ends during the launch: not starved, idle all
    # the same, and counted against the flag (it is a lower bound)
    joined[1].update(starved=0, idle_before_ns=500 * US)
    got = tool.reduce_dispatches(joined)
    assert got["starved_agrees_pct"] == 100.0 * 2 / 3
    # a device timeline that runs 1.2 ms early: the join holds (times
    # play no part in it) and says by how much at least
    early = [(n, a - 1200 * US, b - 1200 * US) for n, a, b in modules]
    joined, skipped, differ = tool.join_dispatches(
        calls, early, programs_of,
        [Rec(c[2], i, i + 0.5, 1, {"step": c[3], "starved": 1})
         for i, c in enumerate(calls)])
    assert (skipped, differ) == (2, 0)
    assert tool.device_clock_early(joined) == 1100 * US
    # kinds that never line up: every pair differs
    assert tool.join_dispatches(
        [dispatch("prefill", 0, 1)] * 3,
        [("jit_step", i, i + 1) for i in range(12)], programs_of, [])[2] == 3


def test_analyse_on_a_hand_made_trace(monkeypatch):
    """Both tables from one synthetic trace: two programs of 1 ms, the
    second starved behind 2 ms of the host's pack."""
    from benchmark.lib import xplane
    from kubeml_tpu.utils import trace as ring_mod

    MS = 1000 * US
    dev = "/device:TPU:0"
    tr = xplane.Trace(
        ops={dev: [("fusion.1", 1 * MS, 2 * MS), ("fusion.1", 5 * MS, 6 * MS)]},
        modules={dev: [("jit_step(7)", 1 * MS, 2 * MS),
                       ("jit_step(7)", 5 * MS, 6 * MS)]},
        host=[("main", "bench.window", 0, 8 * MS)])
    spans = {"loop#0": [
        ("serve.loop.step", 0, 7 * MS, 3),
        ("serve.step.enqueue", int(0.5 * MS), 1 * MS, 3),
        ("serve.step.pack", 2 * MS, 4 * MS, 4),
        ("serve.step.enqueue", 4 * MS, int(5.5 * MS), 4)]}
    monkeypatch.setattr(xplane, "load", lambda path: tr)
    monkeypatch.setattr(tool, "host_spans", lambda path: spans)
    ring = [Rec("serve.loop.step", 0.0, 7e-3, 1, {"step": 3}),
            Rec("serve.step.enqueue", 0.5e-3, 1e-3, 1,
                {"step": 3, "starved": 1, "call_s": 0.4e-3}),
            Rec("serve.step.pack", 2e-3, 4e-3, 1, {"step": 4}),
            Rec("serve.step.enqueue", 4e-3, 5.5e-3, 1,
                {"step": 4, "starved": 1, "call_s": 1e-3})]
    monkeypatch.setattr(ring_mod, "phases", lambda a, b: list(ring))
    out = tool.analyse("unused", {"programs": {"decode": "jit_step",
                                               "prefill": "jit_prefill"}},
                       (0.0, 8e-3))
    assert out["device_clock_early_us"] == 0
    assert out["idle_s"] == 6e-3 and out["busy_s"] == 2e-3
    assert sum(out["idle_by_phase"].values()) == out["idle_s"]
    assert out["idle_by_phase"]["serve.step.pack"] == 2e-3
    assert out["idle_by_phase"]["(no phase)"] == 1e-3      # 7 to 8 ms
    assert out["idle_inside_programs_s"] == 0
    # the first program has none before it; the second stood 3 ms idle
    assert out["dispatches"]["joined"] == 1
    assert out["dispatches"]["starved_agrees_pct"] == 100.0
    assert out["dispatches"]["decode.starved"]["launch_median_us"] == 1000.0
    assert out["ring_dispatches"] == 2 and out["ring_starved"] == 2
    # from the first call's end (0.9 ms) to the second phase's start
    assert abs(out["host_before_starved_s"] - 3.1e-3) < 1e-9


def test_analyse_moves_an_early_device_timeline_later(monkeypatch):
    """The second program "starts" 0.5 ms before its call: the device's
    timeline is moved 0.5 ms later before the tables are made."""
    from benchmark.lib import xplane
    from kubeml_tpu.utils import trace as ring_mod

    MS = 1000 * US
    dev = "/device:TPU:0"
    tr = xplane.Trace(
        ops={dev: [("fusion.1", 1 * MS, 2 * MS),
                   ("fusion.1", int(3.5 * MS), int(4.5 * MS))]},
        modules={dev: [("jit_step(7)", 1 * MS, 2 * MS),
                       ("jit_step(7)", int(3.5 * MS), int(4.5 * MS))]},
        host=[("main", "bench.window", 0, 8 * MS)])
    spans = {"loop#0": [("serve.loop.step", 0, 7 * MS, 3),
                        ("serve.step.enqueue", int(0.5 * MS), 1 * MS, 3),
                        ("serve.step.enqueue", 4 * MS, 5 * MS, 4)]}
    ring = [Rec("serve.loop.step", 0.0, 7e-3, 1, {"step": 3}),
            Rec("serve.step.enqueue", 0.5e-3, 1e-3, 1,
                {"step": 3, "starved": 1, "call_s": 0.4e-3}),
            Rec("serve.step.enqueue", 4e-3, 5e-3, 1,
                {"step": 4, "starved": 1, "call_s": 1e-3})]
    monkeypatch.setattr(xplane, "load", lambda path: tr)
    monkeypatch.setattr(tool, "host_spans", lambda path: spans)
    monkeypatch.setattr(ring_mod, "phases", lambda a, b: list(ring))
    out = tool.analyse("unused", {"programs": {"decode": "jit_step"}},
                       (0.0, 8e-3))
    assert out["device_clock_early_us"] == 500.0
    assert out["dispatches"]["decode.starved"]["launch_median_us"] == 0.0
    assert out["busy_s"] == 2e-3
    assert abs(sum(out["idle_by_phase"].values()) - out["idle_s"]) < 1e-12
    # the programs now run from 1.5 to 2.5 and from 4.0 to 5.0 ms: the
    # first enqueue phase (0.5 to 1.0) is idle throughout, the second
    # (4.0 to 5.0) not at all
    assert out["idle_by_phase"]["serve.step.enqueue"] == 0.5e-3
