"""Analytic cost-ledger tests (kubeml_tpu/metrics/ledger.py).

The contracts pinned here are the ones the ledger is built around:

  * determinism — the canonical program inventory produces a
    byte-identical snapshot JSON in two FRESH processes (identical HLO
    yields bit-identical cost analysis; the budget gate depends on it)
  * fallback — when a backend exposes no cost analysis the caller's
    closed-form estimate stands in, tagged source="fallback"
  * replay — `totals == dispatches x per-dispatch cost` holds exactly
    for stable programs, tampering raises, and recaptures (shape
    changes) exempt a program from the global invariant
  * reconciliation — the serve engine's `pager.decode_kv` record
    equals `KVPageSlab.decode_bytes_per_token` EXACTLY, so the paged
    attention proxy and the ledger can never drift apart
  * the budget gate itself — tools/check_cost_budgets.py passes
    against the committed tools/cost_budgets.json and FAILS loudly on
    a perturbed budget, an unbudgeted program, and a stale entry
  * plumbing — per-program storm attribution, delta-advanced
    kubeml_cost_* counters, and the MetricUpdate wire round-trip
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.cost

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_REPO_ROOT, "tools")


def _gate():
    """Import tools/check_cost_budgets.py as a module."""
    if _TOOLS not in sys.path:
        sys.path.insert(0, _TOOLS)
    import check_cost_budgets
    return check_cost_budgets


# -------------------------------------------------------- determinism

_CANONICAL_SNIPPET = """
import sys
sys.path.insert(0, {tools!r})
import check_cost_budgets
from kubeml_tpu.metrics.ledger import snapshot_to_json
ledger = check_cost_budgets.build_canonical_ledger()
for name in ledger.programs():
    ledger.note_dispatch(name, 3, samples=8, tokens=4)
print(snapshot_to_json(ledger.snapshot()))
"""


def test_snapshot_bit_identical_across_two_fresh_processes():
    """Two cold processes compiling the same canonical inventory emit
    byte-identical snapshot JSON — the determinism contract that makes
    per-program cost a CI-gateable number rather than a profile."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", KUBEML_COST_LEDGER="1")
    code = _CANONICAL_SNIPPET.format(tools=_TOOLS)
    outs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", code], cwd=_REPO_ROOT,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout)
    assert outs[0] == outs[1]
    snap = json.loads(outs[0])
    assert snap, "canonical ledger produced no programs"
    for entry in snap.values():
        assert entry["dispatches"] == 3
        assert entry["flops_total"] == 3 * entry["flops"]
        assert entry["hbm_bytes_total"] == 3 * entry["hbm_bytes"]


# ----------------------------------------------------------- fallback

def test_fallback_when_backend_has_no_cost_analysis(monkeypatch):
    """With XLA's analysis unavailable, the caller's closed form stands
    in and is tagged so budgets/reconciliation treat it correctly."""
    import jax.numpy as jnp

    from kubeml_tpu.metrics import ledger as ledger_mod

    monkeypatch.setattr(ledger_mod, "extract_xla_cost",
                        lambda *a, **k: None)
    led = ledger_mod.CostLedger(capture_enabled=True)
    rec = led.capture("fb.prog", "train", lambda x: x, jnp.zeros((2,)),
                      fallback={"flops": 12.0, "hbm_bytes": 34.0,
                                "transcendentals": 5.0})
    assert rec.source == "fallback"
    assert (rec.flops, rec.hbm_bytes, rec.transcendentals) == (12.0, 34.0, 5.0)
    # totals still attribute off the fallback record
    led.note_dispatch("fb.prog", 4, samples=16)
    assert led.totals("fb.prog")["flops_total"] == 48.0
    led.replay_check()


def test_env_gate_disables_xla_capture(monkeypatch):
    """KUBEML_COST_LEDGER=0 skips the extra AOT compile entirely and
    uses the fallback path (source=fallback, no XLA call)."""
    from kubeml_tpu.metrics import ledger as ledger_mod

    monkeypatch.setenv("KUBEML_COST_LEDGER", "0")

    def _boom(*a, **k):  # must not be reached when gated off
        raise AssertionError("extract_xla_cost called despite gate")

    monkeypatch.setattr(ledger_mod, "extract_xla_cost", _boom)
    led = ledger_mod.CostLedger()
    rec = led.capture("gated.prog", "serve", None,
                      fallback={"hbm_bytes": 7.0})
    assert rec.source == "fallback" and rec.hbm_bytes == 7.0


# -------------------------------------------------------------- replay

def test_replay_invariant_tamper_and_recapture_exemption():
    from kubeml_tpu.metrics.ledger import (CostLedger,
                                           CostReconciliationError)

    led = CostLedger()
    led.capture_analytic("a", "kernel", flops=10.0, hbm_bytes=100.0)
    led.note_dispatch("a", 7)
    led.replay_check()

    # tampering with a total breaks the invariant loudly
    led._totals["a"]["flops_total"] += 1.0
    with pytest.raises(CostReconciliationError, match="replay mismatch"):
        led.replay_check()
    led._totals["a"]["flops_total"] -= 1.0
    led.replay_check()

    # a recapture (shape change → new per-dispatch cost) makes the
    # global invariant per-segment; the replay check must skip it
    led.capture_analytic("a", "kernel", flops=20.0, hbm_bytes=100.0)
    led.note_dispatch("a", 1)
    assert led.totals("a")["recaptures"] == 1
    led.replay_check()  # mixed-record totals, but exempted


def test_reconcile_exact_and_tolerant():
    from kubeml_tpu.metrics.ledger import (CostLedger,
                                           CostReconciliationError)

    led = CostLedger()
    led.capture_analytic("p", "serve", hbm_bytes=1000.0)
    led.reconcile("p", "hbm_bytes", 1000.0, tolerance=0.0)
    with pytest.raises(CostReconciliationError):
        led.reconcile("p", "hbm_bytes", 1001.0, tolerance=0.0)
    led.reconcile("p", "hbm_bytes", 1100.0, tolerance=0.2)
    with pytest.raises(CostReconciliationError):
        led.reconcile("p", "hbm_bytes", 2000.0, tolerance=0.2)
    with pytest.raises(CostReconciliationError, match="no record"):
        led.reconcile("missing", "hbm_bytes", 1.0)


# ---------------------------------------------------- serve reconcile

def test_decode_engine_kv_record_reconciles_exactly(monkeypatch):
    """The engine's pager.decode_kv record IS the slab's
    decode_bytes_per_token — the acceptance-criterion reconciliation,
    checked at the engine level (not just the canonical inventory).
    Capture is forced ON (the suite defaults it off for speed) so this
    is also the one in-suite drive of `_ledger_capture`'s XLA path,
    including its decode-bytes-vs-proxy tolerance sanity check."""
    import jax
    import numpy as np

    monkeypatch.setenv("KUBEML_COST_LEDGER", "1")

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    model = get_builtin("gpt-nano")()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, module.max_len), np.int32)})
    engine = DecodeEngine(module, variables, slots=4, page=4)

    rec = engine.ledger.record("pager.decode_kv")
    assert rec is not None and rec.source == "analytic"
    assert rec.hbm_bytes == float(engine.slab.decode_bytes_per_token)
    assert rec.plane == "serve"

    # drive one request: serve-plane tokens attribute, replay holds
    engine.attach(GenerateRequest([5, 6, 7], max_new_tokens=4))
    guard = 10_000
    while engine.active():
        engine.step()
        guard -= 1
        assert guard > 0
    engine.ledger.replay_check()
    dec = engine.ledger.record("serve.decode")
    assert dec is not None and dec.source == "xla"
    att = engine.ledger.attributed()
    assert att["serve"]["tokens"] > 0
    assert att["serve"]["bytes_per_token"] > 0.0


def test_engine_cost_fallback_counts_weights_not_bytes():
    """The closed-form stand-in (backends without XLA cost analysis)
    reckons 2 FLOPs a weight a lane-step from the tree's ELEMENTS and
    its parameter traffic from the tree's BYTES: the tree held in
    bfloat16 (the engine's own form of a bfloat16 module's float32
    tree) and the same tree held in float32 do the same arithmetic and
    move half the parameter bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.serve.engine import DecodeEngine

    model = get_builtin("gpt-nano")()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, module.max_len), np.int32)})
    leaves = jax.tree_util.tree_leaves(variables["params"])
    weights = sum(int(a.size) for a in leaves)
    engine = DecodeEngine(module, variables, slots=4, page=4)
    kv = 4 * engine.slab.decode_bytes_per_token
    held = engine._cost_fallback()
    assert engine.stats["param_bytes"] < 4 * weights
    assert held == {"flops": 2.0 * weights * 4,
                    "hbm_bytes": float(engine.stats["param_bytes"] + kv)}
    assert engine._cost_fallback(steps=3) == {
        "flops": 3 * held["flops"],
        "hbm_bytes": float(engine.stats["param_bytes"] + 3 * kv)}
    # the float32-held tree and an all-bfloat16 one, in the same engine
    as_held = engine._params_by_gen[1]
    for dtype, itemsize in ((jnp.float32, 4), (jnp.bfloat16, 2)):
        engine._params_by_gen[1] = jax.tree_util.tree_map(
            lambda a: a.astype(dtype), as_held)
        assert engine._cost_fallback() == {
            "flops": held["flops"],
            "hbm_bytes": float(itemsize * weights + kv)}


# ---------------------------------------------------------- budget gate

def test_budget_gate_passes_committed_and_fails_perturbed():
    """The regression gate's self-test: the committed budgets pass,
    and a deliberately broken budget file produces every violation
    class (exceeded-exact, unbudgeted, stale, source mismatch)."""
    gate = _gate()
    with open(gate.DEFAULT_BUDGETS) as f:
        budgets = json.load(f)
    assert gate.check(budgets) == []

    perturbed = json.loads(json.dumps(budgets))  # deep copy
    progs = perturbed["programs"]
    # exceeded: an analytic program's bytes are exact — off by one fails
    assert progs["pager.decode_kv"]["source"] == "analytic"
    progs["pager.decode_kv"]["hbm_bytes"] += 1.0
    # source mismatch: lint.train is compiler-derived
    progs["lint.train"]["source"] = "analytic"
    # unbudgeted: drop a canonical program from the file
    del progs["merge.monolithic"]
    # stale: budget an entry no canonical program produces
    progs["ghost.prog"] = {"plane": "train", "source": "analytic",
                           "flops": 1.0, "hbm_bytes": 1.0,
                           "transcendentals": 0.0}
    problems = "\n".join(gate.check(perturbed))
    assert "pager.decode_kv.hbm_bytes" in problems
    assert "lint.train.source" in problems
    assert "merge.monolithic: unbudgeted" in problems
    assert "ghost.prog: stale" in problems


def test_budget_gate_cli_passes_in_suite():
    """tier-1 wiring: the gate script itself exits 0 against the
    committed file, run exactly as CI would run it."""
    r = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "check_cost_budgets.py")],
        cwd=_REPO_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "cost budgets OK" in r.stdout


# ------------------------------------------------------- merge helpers

def test_merge_snapshots_and_attribution():
    from kubeml_tpu.metrics.ledger import (attributed_from_snapshot,
                                           merge_cost_snapshots)

    a = {"p": {"program": "p", "plane": "serve", "flops": 2.0,
               "hbm_bytes": 10.0, "source": "analytic", "dispatches": 3,
               "flops_total": 6.0, "hbm_bytes_total": 30.0,
               "transcendentals_total": 0.0, "samples": 0, "tokens": 12,
               "recaptures": 0}}
    b = json.loads(json.dumps(a))
    b["p"].update(dispatches=1, flops_total=2.0, hbm_bytes_total=10.0,
                  tokens=4)
    merged = merge_cost_snapshots([a, b, {}])
    assert merged["p"]["dispatches"] == 4
    assert merged["p"]["flops_total"] == 8.0
    assert merged["p"]["tokens"] == 16
    assert merged["p"]["flops"] == 2.0  # record from first snapshot

    att = attributed_from_snapshot(merged)
    assert att["serve"]["flops_per_token"] == 8.0 / 16
    assert att["serve"]["bytes_per_token"] == 40.0 / 16


# ------------------------------------------------- storm attribution

def test_recompile_storm_names_the_guilty_program():
    from kubeml_tpu.metrics.runtime import JitCompileTracker

    t = JitCompileTracker()
    # program "healthy" dispatches without compiling; "churny" hits the
    # storm threshold — attribution must separate them
    for _ in range(20):
        t.note(False, program="healthy")
    for _ in range(3):
        t.note(True, 0.1, program="churny")
    assert t.storms_by_program.get("churny") == 1
    assert "healthy" not in t.storms_by_program
    assert t.storm


# --------------------------------------------------------- prom wiring

def test_update_cost_delta_advances_counters():
    """kubeml_cost_* counters advance by snapshot deltas per owner:
    repeats are no-ops, dips (engine restart resets a ledger) are
    absorbed, and two owners sum into one (program, plane) series."""
    from kubeml_tpu.metrics.prom import MetricsRegistry

    reg = MetricsRegistry()

    def snap(flops, disp):
        return {"p": {"plane": "serve", "flops_total": flops,
                      "hbm_bytes_total": 2 * flops, "dispatches": disp}}

    key = ("p", "serve")
    reg.update_cost("job-1", snap(100.0, 2))
    assert reg.cost_flops_total.value(key) == 100.0
    assert reg.cost_dispatches_total.value(key) == 2.0
    reg.update_cost("job-1", snap(100.0, 2))   # repeat: no-op
    assert reg.cost_flops_total.value(key) == 100.0
    reg.update_cost("job-1", snap(150.0, 3))   # advance by delta
    assert reg.cost_flops_total.value(key) == 150.0
    reg.update_cost("job-1", snap(40.0, 1))    # restart dip: absorbed
    assert reg.cost_flops_total.value(key) == 150.0
    reg.update_cost("serve:m", snap(60.0, 1))  # second owner sums
    assert reg.cost_flops_total.value(key) == 210.0
    assert reg.cost_hbm_bytes_total.value(key) == 420.0

    # clear_job drops only the seen baseline; counters are PS-lifetime
    reg.clear_job("job-1")
    assert reg.cost_flops_total.value(key) == 210.0
    assert not [k for k in reg._cost_seen if k[0] == "job-1"]
    assert [k for k in reg._cost_seen if k[0] == "serve:m"]

    # the families are part of the exposition (metrics lint surface)
    text = reg.exposition()
    assert "kubeml_cost_flops_total" in text
    assert "kubeml_cost_dispatches_total" in text


# ----------------------------------------------------------- wire types

def test_metric_update_cost_programs_roundtrip():
    from kubeml_tpu.api.types import MetricUpdate

    snap = {"kavg.train": {"program": "kavg.train", "plane": "train",
                           "flops": 5.0, "hbm_bytes": 9.0,
                           "dispatches": 2, "flops_total": 10.0,
                           "hbm_bytes_total": 18.0, "samples": 64,
                           "tokens": 0, "recaptures": 0,
                           "transcendentals": 0.0,
                           "transcendentals_total": 0.0,
                           "source": "xla"}}
    m = MetricUpdate(job_id="j", validation_loss=0.1, accuracy=0.9,
                     train_loss=0.2, parallelism=2, epoch_duration=1.0,
                     cost_programs=snap)
    d = json.loads(json.dumps(m.to_dict()))  # through the JSON wire
    m2 = MetricUpdate.from_dict(d)
    assert m2.cost_programs == snap
    # absent on the wire (old sender) → empty dict, not None
    del d["cost_programs"]
    assert MetricUpdate.from_dict(d).cost_programs == {}
