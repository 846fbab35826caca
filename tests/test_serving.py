"""Inference-plane tests (kubeml_tpu/serve/ + the PS /generate route).

The contracts pinned here are the ones the subsystem is built around:

  * bit-identity — a request generates the SAME tokens continuously
    batched with neighbours as it does running alone (slot math is
    row-independent, pages disjoint, sampling keys per (seed, pos))
  * compile pinning — joins/leaves/EOS churn slot membership as DATA;
    the decode program compiles exactly once per engine
    (JitCompileTracker), never per membership change
  * page accounting — KV pages free on EOS/cancel and return to the
    pool; exhaustion sheds the newest stream instead of deadlocking
  * admission control — past slots+queue the PS answers 429 with
    Retry-After; bad prompts 400 before costing a slot
  * telemetry — serve histogram/gauge families pass the metrics lint
    from the live PS exposition, and serve:<model> snapshots flow
    through the health-rule pipeline into `kubeml top`
"""

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
    """Step the engine until every slot drains; returns finished reqs."""
    finished = []
    while engine.active():
        finished.extend(engine.step())
        # the per-slot count behind stats live_page_entries_sum
        np.testing.assert_array_equal(
            engine._live_entries, np.count_nonzero(engine._tables, axis=1))
        limit -= 1
        assert limit > 0, "engine failed to drain"
    return finished


# ------------------------------------------------------------------ engine

def test_concurrent_decode_bit_identical_to_sequential():
    """Greedy and sampled requests produce identical tokens whether
    they share the engine with neighbours or run one at a time."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    specs = [([5, 6, 7], 6, 0.0, 0),
             ([9, 10, 11, 12], 8, 0.7, 1),
             ([3], 4, 1.3, 7)]

    def make():
        return [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                                seed=s) for p, n, t, s in specs]

    packed = DecodeEngine(module, variables, slots=4, page=4)
    reqs_packed = make()
    for r in reqs_packed:
        packed.attach(r)
    _drive(packed)

    alone = DecodeEngine(module, variables, slots=4, page=4)
    reqs_alone = make()
    for r in reqs_alone:
        alone.attach(r)
        _drive(alone)

    assert all(r.outcome == "ok" for r in reqs_packed + reqs_alone)
    assert [r.tokens for r in reqs_packed] == [r.tokens for r in reqs_alone]
    # sampled rows really sampled (different seeds diverge from greedy)
    assert reqs_packed[1].tokens != reqs_packed[0].tokens[:8]


def test_greedy_engine_matches_generate():
    """The paged decode path reproduces the model's own KV-cache
    generate() exactly for greedy decoding."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    model, module, variables = _nano()
    prompt = [5, 6, 7, 8]
    n_new = 6
    ref = model.generate(variables, np.asarray([prompt], np.int32),
                         max_new_tokens=n_new, temperature=0.0)
    engine = DecodeEngine(module, variables, slots=2, page=8)
    req = GenerateRequest(prompt, max_new_tokens=n_new)
    engine.attach(req)
    _drive(engine)
    assert req.outcome == "ok"
    assert req.tokens == ref[0, len(prompt):].tolist()


def test_join_leave_never_recompiles():
    """Membership churn — join mid-generation, cancel, EOS — is pure
    data; the engine compiles exactly TWO programs (prefill + decode),
    each once, no matter how requests churn or prompt lengths vary."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=4, page=4)

    a = GenerateRequest([5, 6, 7], max_new_tokens=12)
    engine.attach(a)
    for _ in range(4):
        engine.step()
    assert engine.stats["compiles"] == 1          # decode compiled once
    assert engine.stats["prefill_compiles"] == 1  # prefill compiled once

    b = GenerateRequest([9, 10], max_new_tokens=8, temperature=0.5, seed=3)
    engine.attach(b)  # join mid-generation (different prompt length)
    for _ in range(3):
        engine.step()
    b.cancel()  # leave mid-generation
    engine.step()
    assert b.outcome == "cancelled"

    c = GenerateRequest([11], max_new_tokens=4)
    engine.attach(c)  # join after a leave
    _drive(engine)
    assert a.outcome == "ok" and c.outcome == "ok"
    assert engine.stats["compiles"] == 1
    assert engine.stats["prefill_compiles"] == 1
    assert engine.compile_tracker.compiles == 2   # two programs, total
    assert engine.compile_tracker.dispatches == \
        engine.stats["dispatches"] + engine.stats["prefill_dispatches"]


def _leaf_types(tree):
    import jax
    return {jax.tree_util.keystr(k): (a.shape, str(a.dtype))
            for k, a in jax.tree_util.tree_leaves_with_path(tree)}


def test_every_generation_is_held_in_the_serve_form():
    """An engine built from a float32 tree holds it as its family's
    programs read it (models/gpt.py serve_params: the norms float32,
    every other leaf in the module's bfloat16, a layer's norms and
    biases stacked over the layers), says so in its stats, and holds an
    installed generation and a rebuilt engine's tree in exactly that
    form: a hot swap compiles nothing."""
    import jax

    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    model, module, variables = _nano()
    other = model.init_variables(
        jax.random.PRNGKey(1), {"x": np.ones((1, module.max_len), np.int32)})
    handed = _leaf_types(variables["params"])
    assert {d for _s, d in handed.values()} == {"float32"}
    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=4)
    family = engine.family
    types = _leaf_types(engine._params_by_gen[1])
    assert _leaf_types(family.module_params(engine._params_by_gen[1])) \
        == {k: (s, "float32" if "LayerNorm" in k else "bfloat16")
            for k, (s, _d) in handed.items()}
    assert {s[0] for k, (s, _d) in types.items()
            if k.startswith("['layers']")} == {module.layers}
    norms = sum(4 * int(np.prod(s)) for k, (s, _d) in handed.items()
                if "LayerNorm" in k)
    whole = sum(4 * int(np.prod(s)) for s, _d in handed.values())
    held = {"param_bytes": norms + (whole - norms) // 2,
            # the two tables, and a layer's six kernels and six biases
            "param_leaves_cast": 2 + 12 * module.layers,
            # the two tables and the final norm's two, a layer's norms
            # and biases each stacked over the layers, its six kernels
            "param_leaves": 4 + 10 + 6 * module.layers}
    assert {k: engine.stats[k] for k in held} == held
    assert held["param_leaves_cast"] == sum(
        "LayerNorm" not in k for k in _leaf_types(
            family.module_params(engine._params_by_gen[1])))
    assert held["param_leaves"] == len(types)

    a = GenerateRequest([5, 6, 7, 8, 9, 10], max_new_tokens=8)
    engine.attach(a)
    for _ in range(4):
        engine.step()
    compiled = (engine.stats["compiles"], engine.stats["prefill_compiles"])
    assert compiled == (1, 1)
    assert engine.install_weights(other) == 2
    assert engine.active_generations() == [1, 2]
    assert _leaf_types(engine._params_by_gen[2]) == types
    b = GenerateRequest([5, 6, 7, 8, 9, 10], max_new_tokens=8)
    engine.attach(b)
    _drive(engine)
    assert a.outcome == "ok" and b.outcome == "ok" and a.tokens != b.tokens
    assert (engine.stats["compiles"],
            engine.stats["prefill_compiles"]) == compiled
    assert engine.compile_tracker.compiles == 2
    assert {k: engine.stats[k] for k in held} == held

    rebuilt = engine.spawn_recovered()
    assert rebuilt.active_generations() == [2]
    resident = jax.tree_util.tree_leaves(engine._params_by_gen[2])
    assert all(x is y for x, y in zip(resident, jax.tree_util.tree_leaves(
        rebuilt._params_by_gen[2])))
    # handed the held form, the family's function changes nothing
    assert {k: rebuilt.stats[k] for k in held} == {
        **held, "param_leaves_cast": 0}
    c = GenerateRequest([5, 6, 7, 8, 9, 10], max_new_tokens=8)
    rebuilt.attach(c)
    _drive(rebuilt)
    assert c.tokens == b.tokens
    assert (rebuilt.stats["compiles"], rebuilt.stats["prefill_compiles"]) \
        == (1, 1)


@pytest.mark.parametrize("layers", [1, 3, 5])
def test_a_call_binds_the_held_leaves_at_any_depth(layers):
    """GPT's held tree is 4 + 10 + 6 L leaves (the module's own is
    4 + 16 L), the cast still counts in the module's layout, and every
    enqueue record's `args` is what the jitted call binds: the
    parameter leaves, the slab's 5 arrays, `prev` for a decode call and
    the one packed buffer."""
    import time

    import jax

    from kubeml_tpu.models.gpt import GPTModule
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    from kubeml_tpu.utils.trace import phases

    module = GPTModule(vocab_size=64, max_len=32, hidden=16, layers=layers,
                       heads=2, ffn=32, dropout=0.0)
    variables = module.init(jax.random.PRNGKey(0), np.ones((1, 8), np.int32))
    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=4)
    held = 4 + 10 + 6 * layers
    assert engine.stats["param_leaves"] == held
    assert engine.stats["param_leaves_cast"] == 2 + 12 * layers
    assert len(jax.tree_util.tree_leaves(variables["params"])) \
        == 4 + 16 * layers
    t0 = time.monotonic()
    for prompt in ([5, 6, 7, 8, 9, 10], [3]):
        engine.attach(GenerateRequest(prompt, max_new_tokens=4))
    _drive(engine)
    engine.drain()
    recs = [r for r in phases(t0=t0) if r.name in (
        "serve.step.enqueue", "serve.chunk.enqueue")]
    decode = [r.args["args"] for r in recs
              if r.name == "serve.step.enqueue"]
    chunk = [r.args["args"] for r in recs
             if r.name == "serve.chunk.enqueue"]
    assert len(decode) == engine.stats["dispatches"]
    assert len(chunk) == engine.stats["prefill_dispatches"] > 0
    assert set(decode) == {held + 5 + 2} and set(chunk) == {held + 5 + 1}
    assert engine._bound == {"decode": held + 7, "prefill": held + 6}


@pytest.mark.parametrize("how", ["solo", "batched"])
def test_streams_equal_the_float32_held_forward(how):
    """What an engine built from a float32 tree serves is, token for
    token, what the module gives on that float32 tree by its plain
    forward (the cast at each use: every engine's way before the tree
    was held cast), alone in the engine and among neighbours."""
    import jax

    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    forward = jax.jit(lambda p, x: module.apply({"params": p}, x))
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17],
               list(range(40, 60))]
    n_new = 10

    def reference(prompt):
        toks = list(prompt)
        for _ in range(n_new):
            x = np.zeros((1, module.max_len), np.int32)
            x[0, :len(toks)] = toks
            logits = np.array(forward(variables["params"], x))
            row = logits[0, len(toks) - 1]
            row[module.serve_family().pad_id] = -np.inf
            toks.append(int(row.argmax()))
        return toks[len(prompt):]

    engine = DecodeEngine(module, variables, slots=4, page=8,
                          prefill_chunk=8)
    assert engine.stats["param_leaves_cast"] > 0
    reqs = [GenerateRequest(list(p), max_new_tokens=n_new) for p in prompts]
    for r in reqs:
        engine.attach(r)
        if how == "solo":
            _drive(engine)
    _drive(engine)
    assert [r.outcome for r in reqs] == ["ok"] * 3
    assert [r.tokens for r in reqs] == [reference(p) for p in prompts]


def test_pages_free_on_eos_and_return_to_pool():
    """EOS finishes the stream early, its pages free, and the pool
    drains back to zero in-use after every stream completes."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=4)
    total_pages = engine.pager.free_pages

    probe = GenerateRequest([5, 6, 7], max_new_tokens=6)
    engine.attach(probe)
    _drive(engine)
    assert probe.outcome == "ok"
    assert engine.pager.in_use == 0
    assert engine.pager.free_pages == total_pages
    assert (engine._tables == 0).all()

    # same stream with eos_id = its own first token: one token, done
    eos = GenerateRequest([5, 6, 7], max_new_tokens=6,
                          eos_id=probe.tokens[0])
    engine.attach(eos)
    _drive(engine)
    assert eos.outcome == "ok"
    assert eos.tokens == probe.tokens[:1]
    assert engine.pager.in_use == 0
    assert engine.kv_utilization() == 0.0


def _drain_events(req):
    out = []
    while not req.events.empty():
        out.append(req.events.get_nowait())
    return out


def test_deferred_token_events_wait_for_the_next_decode_enqueue():
    """A step's tokens join `req.tokens` in its emit phase and reach the
    stream once the NEXT step has enqueued its decode program, so the
    handler threads wake while the device works; a request's own events
    are handed over before its terminal event, in order; flush_events()
    and abandon() hand over the rest."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=0)
    short = GenerateRequest([5, 6, 7], max_new_tokens=2)
    long = GenerateRequest([9, 8, 7], max_new_tokens=5)
    engine.attach(short)
    engine.attach(long)
    delivered, trail = [], []
    while short.outcome is None:
        before = len(long.tokens)
        engine.step()
        delivered += _drain_events(long)
        trail.append((before, len(delivered), len(long.tokens)))
    # a step delivers what was emitted before it, and holds its own
    assert all(got == before for before, got, _after in trail), trail
    assert trail[-1][2] == trail[-1][1] + 1
    assert delivered == [{"token": t} for t in long.tokens[:-1]]
    # the finished request: its tokens, then its terminal event
    assert _drain_events(short) == \
        [{"token": t} for t in short.tokens] \
        + [{"done": True, "tokens": short.tokens}]
    engine.flush_events()
    assert _drain_events(long) == [{"token": long.tokens[-1]}]
    engine.step()
    assert _drain_events(long) == []
    engine.abandon()
    assert _drain_events(long) == [{"token": long.tokens[-1]}]


def test_kv_exhaustion_sheds_newest_stream():
    """With every runnable slot stalled on an empty page pool, the
    NEWEST stream is shed with an error and the oldest finishes."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.pager import PageGeometry
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    # 2 usable pages of 4 tokens; each request spans 8 tokens = 2 pages
    geom = PageGeometry(slots=2, page=4, pages=3, pages_per_slot=2)
    engine = DecodeEngine(module, variables, geom=geom)
    old = GenerateRequest([5, 6, 7, 8], max_new_tokens=4)
    new = GenerateRequest([9, 10, 11, 12], max_new_tokens=4)
    engine.attach(old)
    engine.attach(new)
    _drive(engine)
    assert old.outcome == "ok" and len(old.tokens) == 4
    assert new.outcome == "error"
    assert "pages exhausted" in (new.error or "")
    assert engine.stats["stalls"] > 0
    assert engine.pager.in_use == 0  # everything returned to the pool


# ------------------------------------------------------------- PS /generate

@pytest.fixture()
def serve_ps(tmp_home):
    """A live PS with a gpt-nano checkpoint published for serving.
    Tiny slot pool (2) + queue (1) so saturation is reachable."""
    from kubeml_tpu.control.ps import ParameterServer
    from kubeml_tpu.train.checkpoint import save_checkpoint

    model, _module, variables = _nano()
    save_checkpoint("servenano", variables,
                    {"model": "gpt-nano", "function": "gpt-nano",
                     "parallelism": 1, "epoch": 0})
    ps = ParameterServer(serve_slots=2, serve_queue_depth=1)
    ps.start()
    yield ps, model, variables
    ps.stop()


def _post(url, body, timeout=60.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_generate_stream_e2e(serve_ps):
    """POST /generate streams ndjson per-token chunks, the terminal
    event carries the full token list, and the non-stream mode and the
    model's own generate() agree with it."""
    ps, model, variables = serve_ps
    prompt, n_new = [5, 6, 7, 8], 6
    ref = model.generate(variables, np.asarray([prompt], np.int32),
                         max_new_tokens=n_new, temperature=0.0)
    expected = ref[0, len(prompt):].tolist()

    resp = _post(f"{ps.url}/generate",
                 {"model_id": "servenano", "prompt": prompt,
                  "max_new_tokens": n_new})
    assert resp.headers.get("Content-Type") == "application/x-ndjson"
    events = [json.loads(line) for line in resp.read().splitlines()]
    assert [e["token"] for e in events[:-1]] == expected
    assert events[-1] == {"done": True, "tokens": expected}

    doc = json.loads(_post(f"{ps.url}/generate",
                           {"model_id": "servenano", "prompt": prompt,
                            "max_new_tokens": n_new,
                            "stream": False}).read())
    assert doc == {"tokens": expected}


def test_generate_validates_before_costing_a_slot(serve_ps):
    ps, _model, _variables = serve_ps
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{ps.url}/generate",
              {"model_id": "servenano", "prompt": [0, 0]})
    assert ei.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{ps.url}/generate", {"model_id": "servenano"})
    assert ei.value.code == 400


def test_generate_saturation_sheds_429_with_retry_after(serve_ps):
    """Slots 2 + queue 1 = capacity 3: a burst of 6 concurrent streams
    sheds the overflow with 429 + Retry-After while admitted streams
    complete normally."""
    ps, _model, _variables = serve_ps
    results = [None] * 6

    def client(i):
        try:
            resp = _post(f"{ps.url}/generate",
                         {"model_id": "servenano", "prompt": [5, 6, 7, 8],
                          "max_new_tokens": 40})
            resp.read()
            results[i] = (resp.status, None)
        except urllib.error.HTTPError as e:
            results[i] = (e.code, e.headers.get("Retry-After"))

    # serialize the first request alone so the decode service exists
    # (and its one compile lands) before the burst measures admission
    client(0)
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(1, 6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    codes = [r[0] for r in results]
    assert codes.count(200) >= 3
    shed = [r for r in results if r[0] == 429]
    assert shed, f"no request shed at capacity 3 with 6 offered: {codes}"
    assert all(int(retry) >= 1 for _, retry in shed)


def test_live_exposition_and_serve_health(serve_ps):
    """After serving traffic the PS /metrics passes the lint with the
    serve + infer-cache families present, and the serve:<model> pseudo
    job carries its snapshot through GET /health."""
    from tools.check_metrics import validate_exposition

    ps, _model, _variables = serve_ps
    _post(f"{ps.url}/generate",
          {"model_id": "servenano", "prompt": [5, 6, 7],
           "max_new_tokens": 4}).read()
    text = urllib.request.urlopen(f"{ps.url}/metrics").read().decode()
    assert validate_exposition(text) == []
    for family in ("kubeml_serve_ttft_seconds", "kubeml_serve_tpot_seconds",
                   "kubeml_serve_e2e_seconds", "kubeml_serve_active_slots",
                   "kubeml_serve_kv_page_utilization",
                   "kubeml_serve_requests_total",
                   "kubeml_serve_tokens_total",
                   "kubeml_infer_cache_entries",
                   "kubeml_infer_cache_misses_total"):
        assert f"# TYPE {family}" in text, family

    deadline = time.time() + 10
    while time.time() < deadline:
        doc = json.loads(urllib.request.urlopen(
            f"{ps.url}/health?id=serve:servenano").read())
        if doc.get("latest", {}).get("serve_slot_cap") is not None:
            break
        time.sleep(0.05)
    assert doc["state"] in ("healthy", "warning")
    latest = doc["latest"]
    assert latest["serve_slot_cap"] == 2
    assert latest["serve_queue_cap"] == 1
    assert "serve_ttft_p99" in latest
    assert latest["serve_prefill_backlog_tokens"] == 0
    assert "serve_prefix_hit_pct" in latest

    # the prefill/decode token counters publish as deltas right after
    # the request drains; poll the scrape briefly for the new families
    wanted = ("kubeml_serve_prefill_tokens_total",
              "kubeml_serve_decode_tokens_total",
              "kubeml_serve_prefill_backlog_tokens")
    deadline = time.time() + 10
    while time.time() < deadline:
        text = urllib.request.urlopen(f"{ps.url}/metrics").read().decode()
        if all(f"# TYPE {family}" in text for family in wanted):
            break
        time.sleep(0.05)
    for family in wanted:
        assert f"# TYPE {family}" in text, family
    assert validate_exposition(text) == []


# ------------------------------------------------- infer cache + batcher

def test_infer_cache_entry_cap_evicts_lru(tmp_home):
    from kubeml_tpu.control.ps import ParameterServer
    from kubeml_tpu.train.checkpoint import save_checkpoint

    _model, _module, variables = _nano()
    for i in range(3):
        save_checkpoint(f"nano{i}", variables,
                        {"model": "gpt-nano", "function": "gpt-nano",
                         "parallelism": 1, "epoch": 0})
    ps = ParameterServer(infer_cache_size=2)
    for i in range(3):
        ps._load_for_infer(f"nano{i}")
    assert list(ps._infer_cache) == ["nano1", "nano2"]
    # hit refreshes recency; metrics reflect the traffic
    ps._load_for_infer("nano1")
    assert list(ps._infer_cache) == ["nano2", "nano1"]
    text = ps.metrics.exposition()
    assert 'kubeml_infer_cache_entries{cache="checkpoints"} 2' in text
    assert 'kubeml_infer_cache_hits_total{cache="checkpoints"} 1' in text
    assert 'kubeml_infer_cache_misses_total{cache="checkpoints"} 3' in text


def test_infer_cache_yields_to_hbm_budget(tmp_home):
    """With the serving HBM budget exhausted, the cache keeps only the
    freshest entry (the request that just loaded it is using it)."""
    from kubeml_tpu.control.ps import ParameterServer
    from kubeml_tpu.train.checkpoint import save_checkpoint

    _model, _module, variables = _nano()
    for i in range(2):
        save_checkpoint(f"tiny{i}", variables,
                        {"model": "gpt-nano", "function": "gpt-nano",
                         "parallelism": 1, "epoch": 0})
    ps = ParameterServer(infer_cache_size=4, serve_hbm_budget_mb=0.0)
    ps._load_for_infer("tiny0")
    ps._load_for_infer("tiny1")
    assert list(ps._infer_cache) == ["tiny1"]


def test_infer_batcher_follower_timeout_leaves_no_dead_row():
    """A follower that times out removes its row from the pending
    bucket, so the leader's flush only serves live waiters."""
    from kubeml_tpu.api.errors import KubeMLException
    from kubeml_tpu.control.ps import InferBatcher

    b = InferBatcher(window_s=0.3, max_batch=8, timeout_s=0.05)
    key = ("m", (2,), "float32")
    b._last_arrival[key] = time.monotonic()  # force the dense window
    stacked_sizes = []

    def run(stacked):
        stacked_sizes.append(len(stacked))
        return np.zeros((len(stacked), 1))

    leader_done = threading.Event()

    def leader():
        leader_out.append(b.submit(key, np.zeros((1, 2)), run))
        leader_done.set()

    leader_out = []
    t = threading.Thread(target=leader)
    t.start()
    time.sleep(0.05)  # leader is inside its 0.3s collection window
    with pytest.raises(KubeMLException) as ei:
        b.submit(key, np.zeros((1, 2)), run)  # follower, times out
    assert "timed out" in ei.value.message
    assert leader_done.wait(5.0)
    t.join()
    # the flush saw ONLY the leader's row — the dead row left the bucket
    assert stacked_sizes == [1]
    assert len(leader_out[0]) == 1
    assert key not in b._groups


# --------------------------------------------------- health rules + top

def test_serve_health_rules_fire_on_onset():
    from kubeml_tpu.control.health import HealthEvaluator

    t = [0.0]
    ev = HealthEvaluator(clock=lambda: t[0])
    base = {"job_id": "serve:m", "serve_active_slots": 1,
            "serve_slot_cap": 2, "serve_queue_depth": 0,
            "serve_queue_cap": 2, "serve_kv_page_utilization": 0.1,
            "serve_rejected_total": 0, "serve_ttft_p50": 0.01,
            "serve_ttft_p99": 0.02}
    assert ev.observe(dict(base)) == []
    t[0] += 1.0
    fired = ev.observe(dict(base, serve_rejected_total=3))
    assert [f["rule"] for f in fired] == ["serve_saturation"]
    assert "429" in fired[0]["detail"]
    t[0] += 1.0
    # shedding stopped, but the queue sits at cap -> still saturated;
    # p99 TTFT above the 2s SLO newly fires
    fired = ev.observe(dict(base, serve_rejected_total=3,
                            serve_queue_depth=2, serve_ttft_p99=5.0))
    assert [f["rule"] for f in fired] == ["serve_ttft_slo"]
    doc = ev.verdict("serve:m")
    assert doc["state"] == "warning"
    assert {r["rule"] for r in doc["reasons"]} == {"serve_saturation",
                                                   "serve_ttft_slo"}


def test_serve_rules_ignore_training_samples():
    from kubeml_tpu.control.health import HealthEvaluator

    ev = HealthEvaluator(clock=lambda: 0.0)
    fired = ev.observe({"job_id": "job1", "train_loss": 0.4,
                        "grad_norms": [0.5], "loss_spread": 0.01})
    assert [f["rule"] for f in fired] == []
    assert "serve_queue_cap" not in ev.verdict("job1")["latest"]


# ----------------------------------- chunked prefill + prefix cache (PR 8)
#
# Bit-identity matrix for the serving-path variants registered in
# engine.SERVE_PATH_VARIANTS — every quoted name below is load-bearing:
# tools/check_serve_parity.py fails unless each variant name appears in
# a test file that also asserts exactness.

def _run_engine(module, variables, specs, **engine_kw):
    """Run request specs [(prompt, n_new, temp, seed)] through a fresh
    engine, attached together; returns the finished requests."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    engine = DecodeEngine(module, variables, **engine_kw)
    reqs = [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                            seed=s) for p, n, t, s in specs]
    for r in reqs:
        engine.attach(r)
    _drive(engine)
    return engine, reqs


def test_chunked_prefill_bit_identical_to_token_by_token():
    """'prefill_chunked' == 'prefill_token_by_token' == generate(),
    token for token, for greedy AND sampled streams — with the chunk
    size deliberately not a multiple of the page size so chunks span
    page boundaries."""
    model, module, variables = _nano()
    prompt = list(range(5, 25))              # 20 tokens, pages of 4
    specs = [(prompt, 8, 0.0, 0), (prompt[2:], 6, 0.9, 11)]
    ref = model.generate(variables, np.asarray([prompt], np.int32),
                         max_new_tokens=8, temperature=0.0)

    tbt_engine, tbt = _run_engine(module, variables, specs, slots=2,
                                  page=4, prefill_chunk=0,
                                  prefix_cache=False)
    chk_engine, chk = _run_engine(module, variables, specs, slots=2,
                                  page=4, prefill_chunk=6)
    assert all(r.outcome == "ok" for r in tbt + chk)
    for a, b in zip(tbt, chk):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))
    np.testing.assert_array_equal(np.asarray(chk[0].tokens),
                                  ref[0, len(prompt):])
    # the chunked engine really chunked: 19+17 prefill positions at
    # C=6 is 4+3 dispatches, vs 36 token-by-token decode dispatches
    assert tbt_engine.stats["prefill_dispatches"] == 0
    assert chk_engine.stats["prefill_dispatches"] == 7
    assert chk_engine.stats["prefill_tokens"] == 36
    assert chk_engine.stats["prefill_compiles"] == 1


def test_prefix_cache_hit_and_miss_bit_identical():
    """'prefix_cache_miss' (cold) and 'prefix_cache_hit' (warm, shared
    pages) both reproduce the cache-off tokens exactly; a fully cached
    prompt costs ZERO prefill dispatches."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    prompt = list(range(30, 46))             # 16 tokens = 4 full pages
    _, ref = _run_engine(module, variables, [(prompt, 6, 0.0, 0)],
                         slots=2, page=4, prefill_chunk=0,
                         prefix_cache=False)

    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=4, prefix_cache=True)
    cold = GenerateRequest(prompt, max_new_tokens=6)
    engine.attach(cold)
    _drive(engine)
    assert engine.stats["prefix_hits"] == 0
    assert engine.stats["prefix_misses"] == 1
    dispatches_cold = engine.stats["prefill_dispatches"]
    assert dispatches_cold > 0

    warm = GenerateRequest(prompt, max_new_tokens=6)
    engine.attach(warm)
    _drive(engine)
    assert engine.stats["prefix_hits"] == 4          # all 4 pages shared
    assert engine.stats["prefill_dispatches"] == dispatches_cold  # zero new
    assert engine.stats["cow_splits"] >= 1   # final page split for decode

    np.testing.assert_array_equal(np.asarray(cold.tokens),
                                  np.asarray(ref[0].tokens))
    np.testing.assert_array_equal(np.asarray(warm.tokens),
                                  np.asarray(ref[0].tokens))
    # everything drains: cached pages park in the LRU, nothing leaks
    assert engine.pager.in_use == 0
    assert engine.pager.free_pages + engine.pager.evictable_pages == \
        engine.geom.usable_pages


def test_prefix_cow_split_bit_identical_under_sharing():
    """'prefix_cow_split': a stream whose decode write lands in a page
    it shares with a live neighbour gets a private copy inside the same
    dispatch — both streams produce exactly their solo tokens."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    prompt = list(range(100, 112))           # 12 tokens = 3 full pages
    solo_specs = [(prompt, 8, 0.0, 0), (prompt, 8, 1.1, 5)]
    solo = [_run_engine(module, variables, [spec], slots=2, page=4,
                        prefill_chunk=0, prefix_cache=False)[1][0]
            for spec in solo_specs]

    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=4, prefix_cache=True)
    first = GenerateRequest(prompt, max_new_tokens=8)
    engine.attach(first)
    # run until the prompt pages are registered (first token emitted)
    guard = 100
    while not first.tokens:
        engine.step()
        guard -= 1
        assert guard > 0
    second = GenerateRequest(prompt, max_new_tokens=8, temperature=1.1,
                             seed=5)
    engine.attach(second)   # attaches to first's pages while it decodes
    assert engine.stats["prefix_hits"] == 3
    _drive(engine)
    assert engine.stats["cow_splits"] >= 1
    np.testing.assert_array_equal(np.asarray(first.tokens),
                                  np.asarray(solo[0].tokens))
    np.testing.assert_array_equal(np.asarray(second.tokens),
                                  np.asarray(solo[1].tokens))
    assert engine.pager.in_use == 0


def test_pager_refcount_share_cow_evict_readmit_cycle():
    """Allocator state machine: register -> share -> CoW-split -> park
    in LRU -> re-admit -> evict, with the double-free guard intact."""
    from kubeml_tpu.serve.pager import (PageAllocator, PageGeometry,
                                        chain_hash)

    geom = PageGeometry(slots=2, page=4, pages=6, pages_per_slot=4)
    pager = PageAllocator(geom)
    p1 = pager.alloc()
    assert p1 == 1 and pager.writable(p1)

    digest = chain_hash(b"", [7, 8, 9, 10])
    assert pager.register_prefix(p1, digest)
    assert not pager.writable(p1)            # registered => read-only
    assert not pager.register_prefix(p1, digest)  # idempotent no-op

    # share: a second stream attaches to the cached page
    assert pager.lookup_prefix(digest) == p1
    assert pager.refcount(p1) == 2
    # CoW split: the sharer takes a private page, drops its shared ref
    dst = pager.alloc()
    pager.free([p1])
    assert pager.refcount(p1) == 1 and pager.writable(dst)

    # last ref gone: the page PARKS in the LRU, it does not free
    pager.free([p1])
    assert pager.refcount(p1) == 0
    assert pager.evictable_pages == 1
    with pytest.raises(ValueError):
        pager.free([p1])                     # double free still guarded

    # re-admit: a warm lookup revives it from the LRU
    assert pager.lookup_prefix(digest) == p1
    assert pager.refcount(p1) == 1 and pager.evictable_pages == 0
    pager.free([p1])                          # park again

    # eviction: exhaust the free list, next alloc takes the LRU page
    while pager.free_pages:
        pager.alloc()
    evicted = pager.alloc()
    assert evicted == p1 and pager.evictions == 1
    assert pager.lookup_prefix(digest) is None   # unregistered on evict
    assert pager.alloc() is None                 # now truly exhausted


def test_exhaustion_evicts_cached_pages_before_shedding():
    """A full pool with unreferenced cached pages evicts them instead
    of shedding the stream — the cache never costs capacity."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.pager import PageGeometry
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    geom = PageGeometry(slots=2, page=4, pages=3, pages_per_slot=2)
    engine = DecodeEngine(module, variables, geom=geom, prefill_chunk=4)
    first = GenerateRequest([5, 6, 7, 8], max_new_tokens=4)
    engine.attach(first)
    _drive(engine)
    assert first.outcome == "ok"
    assert engine.pager.evictable_pages == 1   # its full prompt page

    # needs both usable pages; only one is free -> must evict, not shed
    second = GenerateRequest([9, 10, 11, 12], max_new_tokens=4)
    engine.attach(second)
    _drive(engine)
    assert second.outcome == "ok" and len(second.tokens) == 4
    assert engine.pager.evictions >= 1


def test_cancel_during_prefill_restores_free_list():
    """Client cancel mid-prefill releases the partially-written pages:
    the free list returns to its pre-request size (cache off), and with
    the cache on every prefix ref is dropped too."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=2, prefix_cache=False)
    pre = engine.pager.free_pages
    req = GenerateRequest(list(range(1, 20)), max_new_tokens=4)
    engine.attach(req)
    engine.step()                      # budget C=2: one chunk, mid-prefill
    assert engine.stats["prefill_dispatches"] == 1
    assert engine._slots[0].pos < len(req.prompt) - 1   # still mid-prefill
    assert engine.pager.free_pages < pre
    req.cancel()
    engine.step()
    assert req.outcome == "cancelled"
    assert engine.pager.free_pages == pre
    assert (engine._tables == 0).all()

    # cache on: a canceled sharer drops its refs; the cached pages stay
    cached = DecodeEngine(module, variables, slots=2, page=4,
                          prefill_chunk=2, prefix_cache=True)
    warmup = GenerateRequest(list(range(1, 13)), max_new_tokens=2)
    cached.attach(warmup)
    _drive(cached)
    assert cached.pager.evictable_pages == 3
    sharer = GenerateRequest(list(range(1, 13)) + [40, 41, 42, 43],
                             max_new_tokens=2)
    cached.attach(sharer)              # takes 3 prefix refs
    assert cached.pager.in_use == 3
    cached.step()                      # mid-prefill of the tail
    sharer.cancel()
    cached.step()
    assert sharer.outcome == "cancelled"
    assert cached.pager.in_use == 0
    assert cached.pager.evictable_pages == 3
    assert cached.pager.free_pages + cached.pager.evictable_pages == \
        cached.geom.usable_pages


def test_prefill_backlog_in_retry_after_and_snapshot():
    """Saturation's Retry-After grows with the queued prompt work, and
    the snapshot carries backlog + prefix-hit% for health/top."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import (PREFILL_DRAIN_TOKENS_PER_S,
                                          ServeService)
    from kubeml_tpu.serve.slots import ServeSaturated

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=1, page=8)
    svc = ServeService("m", engine, max_queue=1)   # loop NOT started
    svc.submit(list(range(1, 41)), max_new_tokens=8)
    svc.submit(list(range(1, 41)), max_new_tokens=8)
    with pytest.raises(ServeSaturated) as ei:
        svc.submit(list(range(1, 41)), max_new_tokens=8)
    expect = 1.0 + (2 * 39) / PREFILL_DRAIN_TOKENS_PER_S
    assert abs(ei.value.retry_after_s - expect) < 1e-9
    snap = svc.snapshot()
    assert snap["serve_prefill_backlog_tokens"] == 2 * 39
    assert snap["serve_prefix_hit_pct"] == 0.0


def test_serve_prefill_metric_families_lint_clean():
    from kubeml_tpu.metrics.prom import MetricsRegistry
    from tools.check_metrics import validate_exposition

    m = MetricsRegistry()
    m.note_serve_prefill("m1", 32)
    m.note_serve_decode("m1", 5)
    m.note_serve_prefix_hits("m1", 3)
    m.note_serve_prefix_misses("m1", 1)
    m.set_serve_state("m1", 1, 0, 0.5, prefill_backlog=7)
    text = m.exposition()
    assert validate_exposition(text) == []
    assert 'kubeml_serve_prefill_tokens_total{model="m1"} 32' in text
    assert 'kubeml_serve_decode_tokens_total{model="m1"} 5' in text
    assert 'kubeml_serve_prefix_cache_hits_total{model="m1"} 3' in text
    assert 'kubeml_serve_prefix_cache_misses_total{model="m1"} 1' in text
    assert 'kubeml_serve_prefill_backlog_tokens{model="m1"} 7' in text
    m.clear_serve("m1")
    assert 'model="m1"' not in m.exposition()


def test_check_serve_parity_lint_passes_on_repo():
    """The lint itself, run over the real tree: every registered
    serving-path variant is covered by this file's tests."""
    import os

    from kubeml_tpu.serve.engine import SERVE_PATH_VARIANTS
    from tools.check_serve_parity import main, path_variants

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    engine_path = os.path.join(root, "kubeml_tpu", "serve", "engine.py")
    assert tuple(path_variants(engine_path)) == SERVE_PATH_VARIANTS
    assert main(["check_serve_parity.py", root]) == 0


def test_check_serve_parity_lint_selftest(tmp_path):
    """The lint catches an uncovered variant, ignores comment-only
    mentions, and fails loudly when the registry is missing."""
    from tools.check_serve_parity import main, uncovered_variants

    eng_dir = tmp_path / "kubeml_tpu" / "serve"
    eng_dir.mkdir(parents=True)
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    engine = eng_dir / "engine.py"
    engine.write_text(
        'SERVE_PATH_VARIANTS = (\n    "covered_path",\n'
        '    "naked_path",\n)\n')
    (tests_dir / "test_ok.py").write_text(
        'import numpy as np\n'
        'def test_covered():\n'
        '    # naked_path mentioned in a comment only: does not count\n'
        '    variant = "covered_path"\n'
        '    np.testing.assert_array_equal([1], [1])\n')
    assert uncovered_variants(str(engine), str(tests_dir)) == ["naked_path"]
    assert main(["lint", str(tmp_path)]) == 1
    (tests_dir / "test_fix.py").write_text(
        'import numpy as np\n'
        'def test_naked():\n'
        '    assert "naked_path"\n'
        '    np.testing.assert_array_equal([2], [2])\n')
    assert main(["lint", str(tmp_path)]) == 0
    engine.write_text("SERVE_PATH_VARIANTS = ()\n")
    assert main(["lint", str(tmp_path)]) == 1


def test_top_renders_serving_pane():
    from kubeml_tpu.cli.main import _render_top

    doc = {"id": "serve:m1", "state": "healthy", "reasons": [],
           "latest": {"serve_active_slots": 2, "serve_slot_cap": 8,
                      "serve_queue_depth": 1, "serve_queue_cap": 16,
                      "serve_kv_page_utilization": 0.25,
                      "serve_rejected_total": 3,
                      "serve_ttft_p50": 0.010, "serve_ttft_p99": 0.020}}
    out = _render_top(doc)
    assert "serve: slots 2/8" in out
    assert "queue 1/16" in out
    assert "kv pages 25%" in out
    assert "ttft p50/p99 10ms/20ms" in out
    assert "shed 3" in out
    # a training job's screen has no serving pane
    plain = _render_top({"id": "job1", "state": "healthy", "reasons": [],
                         "latest": {"train_loss": 0.5}})
    assert "serve:" not in plain
