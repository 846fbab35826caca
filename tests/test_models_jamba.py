"""The Jamba serving family (models/jamba.py) against its plain float32
reference (tests/helpers/ref_jamba.py, the same file as
benchmark/refs/jamba.py), at a small size on the CPU: two Mamba and two
attention layers, seeded weights of unit gain and LONG memory (A_log =
log(1..16), step sizes in 1e-3..1e-1), so that a dropped hand-over of
either per-slot state, a state kept in bfloat16 or a state that leaks
from one stream to the next moves a logit by far more than a tolerance.
Chunked prefill then decode go through DecodeEngine itself; the logits
are tapped out of the decode program it runs.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models import jamba
from kubeml_tpu.models.base import SlotState, sample_tokens
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.slots import GenerateRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, PAGE = 16, 16

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def ref():
    """The tier-1 copy of the reference. It imports benchmark.refs.quant
    (the int8 control) by that name, as the benchmark's copy does."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ref_jamba", os.path.join(REPO, "tests", "helpers", "ref_jamba.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cfg_of(m: jamba.JambaModule) -> dict:
    """The reference's configuration (published keys) of a module."""
    return {"hidden_size": m.hidden, "num_hidden_layers": m.layers,
            "attn_layer_period": m.attn_period,
            "attn_layer_offset": m.attn_offset,
            "num_attention_heads": m.heads,
            "num_key_value_heads": m.kv_heads,
            "intermediate_size": m.intermediate_size,
            "mamba_expand": m.expand, "mamba_d_state": m.d_state,
            "mamba_d_conv": m.d_conv, "mamba_dt_rank": m.dt_rank,
            "rms_norm_eps": m.rms_eps, "vocab_size": m.vocab_size,
            "max_position_embeddings": m.max_len}


def flat_weights(variables) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]:
        out["/".join(str(p.key) for p in path)] = leaf
    return out


def seeded(m: jamba.JambaModule, seed: int = 0):
    """Weights of unit gain, so that every sublayer moves the residual
    by about its own size and every leaf carries signal: kernels
    normal / sqrt(fan-in), the convolution's taps 0.5, norm scales off
    1; the recurrence keeps the module's own long-memory A_log and step
    bias (JambaModule.init)."""
    variables = m.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4096))

    def stir(path, leaf):
        names = [p.key for p in path]
        key = next(noise)
        if names[-2:] in (["a_log", "kernel"], ["dt_proj", "bias"]):
            return leaf
        if names[-1] == "scale":
            new = 1.0 + 0.1 * jax.random.normal(key, leaf.shape)
        elif names[-2:] == ["conv", "kernel"]:
            new = 0.5 * jax.random.normal(key, leaf.shape)
        elif names[-1] == "embedding":
            new = 0.1 * jax.random.normal(key, leaf.shape)
        elif names[-1] == "bias":
            new = 0.1 * jax.random.normal(key, leaf.shape)
        else:
            new = jax.random.normal(key, leaf.shape) / np.sqrt(leaf.shape[0])
        return new.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(stir, variables)


PUBLISHED = jamba.JambaModule(
    vocab_size=65536, max_len=6144, hidden=2560, layers=28, attn_period=14,
    attn_offset=7, heads=20, kv_heads=1, intermediate_size=8192, expand=2,
    d_state=16, d_conv=4, dt_rank=160)


# ------------------------------------------------- tapping the engine

class _Tapped:
    """A module whose family's decode program also hands its logits to
    `sink(logits [S, V], pos [S], active [S])`, in dispatch order: what
    DecodeEngine runs is jamba's own decode program with one callback
    in it. `cache` replaces the family's declaration (the negative
    tests)."""

    def __init__(self, module, sink, cache=None):
        self.module, self.sink, self.cache = module, sink, cache

    def serve_family(self):
        tapped, m = self, self.module

        class Family(jamba.JambaServeFamily):
            def decode_step(self, kv_dtype, attn_impl, attn_interpret):
                logits_of = jamba.build_decode_logits(m, attn_impl,
                                                      attn_interpret)

                def step(params, k, v, ssm, conv, tokens, pos, tables, wp,
                         wo, active, temps, key_data, cs, cd, poison):
                    logits, counts, *state = logits_of(
                        params, k, v, ssm, conv, tokens, pos, tables, wp,
                        wo, active, cs, cd)
                    jax.debug.callback(tapped.sink, logits, pos, active,
                                       ordered=True)
                    nxt, bad = sample_tokens(logits, active, temps,
                                             key_data, poison, jamba.PAD_ID)
                    return (jnp.concatenate([nxt, counts]), bad, *state)

                return step

        fam = Family(m)
        if self.cache is not None:
            fam.cache = self.cache
        return fam


class _Sink:
    """{(slot, pos): logits row} of every active lane-step, the last
    write winning (a slot's next stream overwrites its last one's)."""

    def __init__(self):
        self.rows = {}

    def __call__(self, logits, pos, active):
        logits, pos = np.asarray(logits), np.asarray(pos)
        for s in np.nonzero(np.asarray(active) > 0)[0]:
            self.rows[(int(s), int(pos[s]))] = logits[s].copy()

    def served(self, slot, req):
        """The logits rows the request's tokens were picked from."""
        n = len(req.prompt)
        return np.stack([self.rows[(slot, n - 1 + j)]
                         for j in range(len(req.tokens))])


def _finish(eng, limit=5000):
    while eng.active():
        eng.step()
        limit -= 1
        assert limit > 0, "engine failed to drain"
    eng.drain()
    eng.flush_events()
    eng.check_pager()


def _request(rng, m, n_prompt, n_new=6, temp=0.0, seed=0):
    return GenerateRequest(rng.integers(1, m.vocab_size, n_prompt).tolist(),
                           max_new_tokens=n_new, temperature=temp, seed=seed)


def _serve(m, variables, requests, slots=4, cache=None, chunk=CHUNK, **kw):
    """Attach every request at once (one slot each), run to the end;
    returns (engine, [served logits of each request])."""
    sink = _Sink()
    eng = DecodeEngine(_Tapped(m, sink, cache), variables, slots=slots,
                       page=PAGE, prefill_chunk=chunk, **kw)
    where = [eng.attach(r) for r in requests]
    _finish(eng)
    assert all(r.outcome == "ok" for r in requests)
    return eng, [sink.served(s, r) for s, r in zip(where, requests)]


def _reference_logits(ref, m, variables, req):
    ids = list(req.prompt) + list(req.tokens)
    positions = np.arange(len(req.prompt) - 1, len(ids) - 1)
    return ref.logits(flat_weights(variables), cfg_of(m), ids, positions)


# ------------------------------------------------------------ the files

def test_the_two_copies_of_the_reference_are_one_file():
    with open(os.path.join(REPO, "benchmark", "refs", "jamba.py"),
              "rb") as f:
        bench = f.read()
    with open(os.path.join(REPO, "tests", "helpers", "ref_jamba.py"),
              "rb") as f:
        assert f.read() == bench


def test_module_and_reference_name_the_same_leaves(ref):
    for m in (jamba.JambaModule(), PUBLISHED):
        spec = ref.weight_spec(cfg_of(m))
        shapes = {"params/" + k: v for k, v in m.param_shapes().items()}
        assert {k: tuple(s) for k, (s, _d) in spec.items()} == shapes
        assert all(d == jnp.bfloat16 for _s, d in spec.values())
        # lib/weights.py has rules for these leaf names and no others
        assert {k.rsplit("/", 1)[1] for k in shapes} \
            == {"kernel", "embedding", "scale", "bias"}
    assert PUBLISHED.attn_layers == (7, 21)
    assert len(PUBLISHED.mamba_layers) == 26 and PUBLISHED.d_inner == 5120
    shapes = jax.eval_shape(lambda: PUBLISHED.init(jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    # ISSUE 31's count: 26 Mamba layers of 104.1M, 2 attention layers of
    # 76.7M and the 167.8M embedding, 3.03B parameters, 6.06 GB
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 3_028.5e6) < 1e6, n
    cache = PUBLISHED.serve_family().cache
    assert (cache.layers, cache.planes, cache.lanes) == (2, 2, 128)
    assert [(st.name, st.layers, st.shape) for st in cache.slot_state] \
        == [("ssm", 26, (16, 5120)), ("conv", 26, (15360,))]
    # a slot's recurrent state is 327,680 bytes a layer
    assert cache.slot_state[0].slot_bytes() == 26 * 327_680
    assert cache.slot_state_bytes == 26 * (327_680 + 3 * 5120 * 2)


def test_init_gives_the_recurrence_long_memory():
    m = jamba.JambaModule(dtype=jnp.float32)
    p = m.init(jax.random.PRNGKey(0))["params"]["layer_0"]
    np.testing.assert_allclose(
        np.exp(np.asarray(p["a_log"]["kernel"]))[:, 0], np.arange(1, 17),
        rtol=1e-5)
    step = np.asarray(jax.nn.softplus(p["dt_proj"]["bias"]))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01


# ------------------------------------------- the module and the engine

# What differs between the program and the reference in float32 is the
# order of the sums alone (a chunk's convolution as a stack of shifted
# rows, the recurrence in blocks, a running softmax, XLA's matmuls
# against `highest`): measured 2.6e-6 of the largest logit (7.0); 2e-5 holds
# with room, and the faults the negative tests plant move a logit by
# 2e-3 and more of it.
F32_RTOL = 2e-5
# In bfloat16 every matmul's input is rounded to 8 bits of mantissa
# (2^-9 relative) through 4 layers: measured 1.5e-2 of the largest
# logit; 4e-2 holds with room.
BF16_RTOL = 4e-2


def _close(got, want, rtol):
    scale = np.abs(want[:, 1:]).max()
    np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=0,
                               atol=rtol * scale)


def test_module_apply_is_the_reference_forward(ref):
    m = jamba.JambaModule(dtype=jnp.float32)
    variables = seeded(m)
    ids = np.random.default_rng(5).integers(1, m.vocab_size, 70)
    got = np.asarray(jax.jit(m.apply)(variables, jnp.asarray(ids)))
    want = ref.logits(flat_weights(variables), cfg_of(m), ids,
                      np.arange(len(ids)))
    _close(got, want, F32_RTOL)


# prompts of 1 token (no prefill chunk at all), 2 and 3 (a chunk of one
# token, of two), one that is no multiple of the chunk, one whose
# prefill ends on a chunk boundary (33 = 2 * 16 + the token the decode
# step takes) and one that is a whole number of chunks
PROMPTS = (1, 2, 3, 41, 33, 32)


@pytest.mark.parametrize("dtype,rtol", [("float32", F32_RTOL),
                                        ("bfloat16", BF16_RTOL)])
def test_engine_prefill_then_decode_against_the_reference(ref, dtype, rtol):
    """Chunked prefill then decode through DecodeEngine, six streams of
    the lengths above in one batch, one dispatch ahead: the logits of
    every served position against the reference's full forward of
    prompt + served tokens."""
    m = jamba.JambaModule(dtype=getattr(jnp, dtype))
    variables = seeded(m)
    rng = np.random.default_rng(7)
    reqs = [_request(rng, m, n, n_new=8) for n in PROMPTS]
    eng, served = _serve(m, variables, reqs, slots=len(reqs))
    assert eng.stats["ahead_dispatches"] > 0
    assert eng.stats["prefill_dispatches"] == 0 + 1 + 1 + 3 + 2 + 2
    assert eng.stats["ssm_lane_updates"] == eng.stats["occupancy_sum"]
    assert eng.stats["slot_state_bytes"] == eng.stats["occupancy_sum"] \
        * 2 * eng.family.cache.slot_state_bytes
    for r, got in zip(reqs, served):
        _close(got, _reference_logits(ref, m, variables, r), rtol)


@pytest.mark.parametrize("fault", ["bf16_state", "conv_tail_dropped",
                                   "ssm_state_dropped"])
def test_a_planted_fault_fails_the_float32_comparison(ref, fault,
                                                      monkeypatch):
    """The comparison above has the power it claims: a recurrent state
    kept in bfloat16, a convolution tail that is not handed from chunk
    to chunk and a recurrent state that is not each move a served logit
    by more than fifty times the float32 tolerance (read: 1.9e-3 of
    the largest logit for the bfloat16 state, more for the other two)."""
    m = jamba.JambaModule(dtype=jnp.float32)
    variables = seeded(m)
    cache = None
    if fault == "bf16_state":
        fam = m.serve_family()
        cache = dataclasses.replace(fam.cache, slot_state=(
            dataclasses.replace(fam.cache.slot_state[0],
                                dtype=jnp.bfloat16),
            fam.cache.slot_state[1]))
        real = jamba.scan.selective_scan

        def through_bf16(state, *a, **kw):
            new, y = real(state.astype(jnp.float32), *a, **kw)
            return new.astype(jnp.bfloat16), y

        monkeypatch.setattr(jamba.scan, "selective_scan", through_bf16)
    else:
        # the prefill program starts every chunk from zeros in one state
        real = jamba._mamba

        def forgetful(m_, i, p, h, ssm, conv, *, batched, **kw):
            if not batched and fault == "conv_tail_dropped":
                _, ssm, new = real(m_, i, p, h, ssm, jnp.zeros_like(conv),
                                   batched=batched, **kw)
                h, _, _ = real(m_, i, p, h, ssm, jnp.zeros_like(conv),
                               batched=batched, **kw)
                return h, ssm, new
            if not batched:
                kw = {**kw, "fresh": jnp.int32(1)}
            return real(m_, i, p, h, ssm, conv, batched=batched, **kw)

        monkeypatch.setattr(jamba, "_mamba", forgetful)
    rng = np.random.default_rng(7)
    req = _request(rng, m, 41, n_new=4)
    _eng, (got,) = _serve(m, variables, [req], cache=cache)
    want = _reference_logits(ref, m, variables, req)
    off = np.abs(got[:, 1:] - want[:, 1:]).max() / np.abs(want[:, 1:]).max()
    assert off > 50 * F32_RTOL, off


# --------------------------------------------------------- bit identity

def _case(m, variables, specs, **kw):
    rng = np.random.default_rng(9)
    reqs = [_request(rng, m, n, n_new, temp, seed)
            for n, n_new, temp, seed in specs]
    eng, served = _serve(m, variables, reqs, **kw)
    return eng, reqs, served


SPECS = [(41, 7, 0.0, 0), (3, 9, 0.9, 1), (33, 5, 1.3, 7), (20, 8, 0.7, 3)]


@pytest.fixture(scope="module")
def tiny():
    m = jamba.JambaModule()
    return m, seeded(m)


def test_solo_against_batched_is_bit_identical(tiny):
    """A stream's logits and tokens are the same bits alone in the
    engine and packed with three neighbours: lanes and slots are rows,
    a slot's state is its own."""
    m, variables = tiny
    _e, reqs, served = _case(m, variables, SPECS)
    for k, spec in enumerate(SPECS):
        # the case draws its prompts in order: rebuild request k's
        rng = np.random.default_rng(9)
        for n, *_ in SPECS[:k]:
            rng.integers(1, m.vocab_size, n)
        solo = _request(rng, m, *spec)
        _e2, (lg,) = _serve(m, variables, [solo])
        assert solo.tokens == reqs[k].tokens
        np.testing.assert_array_equal(lg, served[k])


def test_a_reused_slot_gives_the_second_stream_as_if_alone(tiny):
    """Slot 0 serves stream A and then stream B: B's logits are the
    bits B gives alone in a fresh engine. Nothing zeroes the slot's
    state on the host: B's first position is 0, and the program starts
    from zeros there. B is tried with a chunked prompt (the prefill
    program resets) and with one token (the decode program does)."""
    m, variables = tiny
    rng = np.random.default_rng(13)
    for n_b in (29, 1):
        a = _request(rng, m, 37, n_new=6, temp=0.8, seed=2)
        b = _request(rng, m, n_b, n_new=6, temp=0.8, seed=4)
        twin = GenerateRequest(list(b.prompt), max_new_tokens=6,
                               temperature=0.8, seed=4)
        sink = _Sink()
        eng = DecodeEngine(_Tapped(m, sink), variables, slots=1, page=PAGE,
                           prefill_chunk=CHUNK)
        assert eng.attach(a) == 0
        _finish(eng)
        assert float(jnp.abs(eng.slab.state[2]).max()) > 0   # A's state
        assert eng.attach(b) == 0
        _finish(eng)
        _e, (alone,) = _serve(m, variables, [twin], slots=1)
        assert b.tokens == twin.tokens
        np.testing.assert_array_equal(sink.served(0, b), alone)


def test_one_ahead_against_the_serial_sequence(tiny):
    """The state arrays follow from dispatch to dispatch on the device:
    one dispatch ahead and the serial sequence (reached by an empty
    fault plan, as tests/test_one_ahead.py reaches it) give the same
    bits, and the regime engages."""
    from kubeml_tpu.faults import ServeFaultPlan
    m, variables = tiny
    ahead, a_reqs, a_lg = _case(m, variables, SPECS)
    serial, s_reqs, s_lg = _case(m, variables, SPECS,
                                 fault_plan=ServeFaultPlan([]))
    assert serial.stats["ahead_dispatches"] == 0
    assert ahead.stats["ahead_dispatches"] >= ahead.stats["dispatches"] - 2
    assert ahead.stats["overrun_lane_steps"] == 0
    assert [r.tokens for r in a_reqs] == [r.tokens for r in s_reqs]
    for a, s in zip(a_lg, s_lg):
        np.testing.assert_array_equal(a, s)
    assert ahead.stats["ssm_lane_updates"] \
        == serial.stats["ssm_lane_updates"]
    assert ahead.stats["compiles"] == 1 and ahead.stats["prefill_compiles"] == 1


def test_token_by_token_prefill_is_the_chunked_prefill(ref, tiny):
    """prefill_chunk 0: every prompt position rides the decode program,
    which starts from zeros at position 0. Another order of sums than
    the chunked path's, and in bfloat16 a greedy pick between two logits
    closer than that rounding may go either way (stream 0's 7th token:
    a margin of 0.0042 against logits that move by 0.020 between the two
    orders), so the streams are compared by their logits: each served
    row, of the chunked and the token-by-token path, against the
    reference's forward of its own stream within the bfloat16
    tolerance; the two paths' rows alike wherever their streams still
    share every earlier token."""
    m, variables = tiny
    _e, reqs, chunked = _case(m, variables, SPECS)
    rng = np.random.default_rng(9)
    again = [_request(rng, m, *spec) for spec in SPECS]
    eng, token = _serve(m, variables, again, chunk=0)
    assert eng.stats["prefill_dispatches"] == 0
    for a, b, la, lb in zip(reqs, again, chunked, token):
        for r, got in ((a, la), (b, lb)):
            _close(got, _reference_logits(ref, m, variables, r), BF16_RTOL)
        same = next((j for j, (x, y) in enumerate(zip(a.tokens, b.tokens))
                     if x != y), len(a.tokens))
        _close(lb[:same + 1], la[:same + 1], 2 * BF16_RTOL)


def test_one_prompt_twice_prefills_twice_and_agrees(tiny):
    """A family with per-slot state registers and matches no prefix,
    whatever the option says: pages would come WITHOUT the state at
    their boundary. Two requests with one prompt of three full pages
    both prefill in full, and agree."""
    m, variables = tiny
    prompt = np.random.default_rng(3).integers(1, m.vocab_size, 50).tolist()
    eng = DecodeEngine(m, variables, slots=2, page=PAGE,
                       prefill_chunk=CHUNK, prefix_cache=True)
    assert eng.prefix_cache is False
    first = GenerateRequest(list(prompt), max_new_tokens=6,
                            temperature=0.9, seed=5)
    eng.attach(first)
    _finish(eng)
    second = GenerateRequest(list(prompt), max_new_tokens=6,
                             temperature=0.9, seed=5)
    eng.attach(second)
    _finish(eng)
    assert first.tokens == second.tokens
    assert eng.stats["prefix_hits"] == 0 and eng.stats["prefix_misses"] == 0
    assert eng.stats["prefill_tokens"] == 2 * 49
    assert eng.pager.cached_pages == 0
    # a recovered engine inherits the decision
    assert eng.spawn_recovered().prefix_cache is False


def test_a_resumed_stream_re_prefills_to_the_same_tokens(ref, tiny,
                                                         monkeypatch):
    """The replica is replaced mid-stream (a wedged loop, the watchdog,
    spawn_recovered: tests/test_serve_faults.py's way): the resumed
    streams re-prefill prompt + emitted tokens from position 0 into the
    new engine's zeroed state and finish. The re-prefill sums the
    emitted tokens in another order than the decode steps that made
    them, so (as above) the streams are compared by their logits: every
    served row, from whichever engine served it, against the
    reference's forward of the stream's own tokens within the bfloat16
    tolerance, and the tokens of an uninterrupted run up to the wedge."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.service import ServeService
    m, variables = tiny
    _e, clean, _lg = _case(m, variables, SPECS)
    stints = []
    real_attach = DecodeEngine.attach

    def attach(self, req):
        slot = real_attach(self, req)
        stints.append((self.family.sink, req, slot, len(req.tokens)))
        return slot

    class EachEngineItsSink:
        """The replacement engine taps into a sink of its own."""
        module = m

        def serve_family(self):
            sink = _Sink()
            fam = _Tapped(m, sink).serve_family()
            fam.sink = sink
            return fam

    monkeypatch.setattr(DecodeEngine, "attach", attach)
    plan = ServeFaultPlan.parse([{"kind": "serve_loop_wedge", "step": 6}])
    engine = DecodeEngine(EachEngineItsSink(), variables, slots=4,
                          page=PAGE, prefill_chunk=CHUNK, fault_plan=plan)
    svc = ServeService("jamba-wedge", engine, wedge_timeout_s=0.2,
                       watchdog_interval_s=0.05)
    svc.start()
    try:
        reqs = [svc.submit(list(c.prompt), max_new_tokens=spec[1],
                           temperature=spec[2], seed=spec[3])
                for c, spec in zip(clean, SPECS)]
        for r in reqs:
            assert r.wait(120), "stream never resumed after the wedge"
    finally:
        svc.stop()
    assert plan.injected["serve_loop_wedge"] == 1
    assert svc.restarts_total == 1 and svc.engine is not engine
    assert all(r.outcome == "ok" for r in reqs)
    resumed = 0
    for r, c in zip(reqs, clean):
        mine = [(sink, slot, had) for sink, q, slot, had in stints
                if q is r]
        resumed += len(mine) > 1
        n, rows = len(r.prompt), []
        for j in range(len(r.tokens)):
            sink, slot, _had = [s for s in mine if s[2] <= j][-1]
            rows.append(sink.rows[(slot, n - 1 + j)])
        _close(np.stack(rows), _reference_logits(ref, m, variables, r),
               BF16_RTOL)
        wedged = mine[-1][2]
        assert r.tokens[:wedged] == c.tokens[:wedged]
    assert resumed >= 1


def test_a_state_one_step_ahead_of_its_stream_ends_the_stream(tiny):
    """A dispatch that raised between its enqueue and its walk has
    advanced the lanes' recurrent state and not their cursors. A page
    write could be made again; a recurrence cannot: the next step ends
    those streams with an error and never advances a state twice."""
    from kubeml_tpu.faults import ServeFaultPlan
    m, variables = tiny
    rng = np.random.default_rng(17)
    eng = DecodeEngine(m, variables, slots=2, page=PAGE,
                       prefill_chunk=CHUNK, fault_plan=ServeFaultPlan([]))
    reqs = [_request(rng, m, n, n_new=8) for n in (20, 3)]
    for r in reqs:
        eng.attach(r)
    for _ in range(3):
        eng.step()
    assert all(len(r.tokens) >= 1 for r in reqs)
    real, calls = eng._walk_emitted, []

    def fails_once(*a, **kw):
        if not calls:
            calls.append(1)
            raise RuntimeError("walk failed")
        return real(*a, **kw)

    eng._walk_emitted = fails_once
    with pytest.raises(RuntimeError, match="walk failed"):
        eng.step()
    n_before = [len(r.tokens) for r in reqs]
    finished = eng.step()
    assert sorted(id(r) for r in finished) == sorted(id(r) for r in reqs)
    assert all(r.outcome == "error" and "per-slot state" in r.error
               for r in reqs)
    assert [len(r.tokens) for r in reqs] == n_before
    assert eng.active() == 0
    eng.check_pager()


def test_optional_programs_and_int8_pages_are_refused_by_name(tiny):
    m, variables = tiny
    with pytest.raises(ValueError, match="'jamba' provides no multi-step"):
        DecodeEngine(m, variables, slots=2, page=PAGE, decode_steps=4)
    from kubeml_tpu.models import gpt
    draft = gpt.GPTNano()
    dv = draft.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, draft.module.max_len), np.int32)})
    with pytest.raises(ValueError, match="'jamba' provides no speculative"):
        DecodeEngine(m, variables, slots=2, page=PAGE,
                     draft_module=draft.module, draft_variables=dv)
    with pytest.raises(ValueError, match="no int8"):
        DecodeEngine(m, variables, slots=2, page=PAGE, kv_dtype="int8")


def test_the_engine_names_nothing_of_the_family():
    with open(os.path.join(REPO, "kubeml_tpu", "serve", "engine.py")) as f:
        source = f.read()
    assert "models.jamba" not in source and "import jamba" not in source
    assert "ssm" not in source.replace("ssm_lane_updates", "")


# -------------------------------------------------------------- the slab

def test_slab_builds_names_and_counts_the_slot_state():
    from kubeml_tpu.models.base import CacheSpec
    from kubeml_tpu.serve.pager import KVPageSlab, PageGeometry
    geom = PageGeometry(slots=3, page=16, pages=9, pages_per_slot=4)
    cache = CacheSpec(
        layers=2, planes=2, lanes=128, dtype=jnp.bfloat16,
        slot_state=(SlotState("ssm", 5, (16, 256), jnp.float32),
                    SlotState("conv", 5, (768,), jnp.bfloat16)))
    slab = KVPageSlab(geom, cache)
    assert slab.state_names == ("plane_0", "plane_1", "ssm", "conv")
    assert [a.shape for a in slab.state] == [
        (2, 9, 16, 128), (2, 9, 16, 128), (5, 3, 16, 256), (5, 3, 768)]
    assert [str(a.dtype) for a in slab.state] == [
        "bfloat16", "bfloat16", "float32", "bfloat16"]
    assert all(not np.asarray(a, np.float32).any() for a in slab.state)
    pages = 2 * 2 * 9 * 16 * 128 * 2
    assert cache.slot_state_bytes == 5 * (16 * 256 * 4 + 768 * 2)
    assert slab.device_bytes == pages + 3 * cache.slot_state_bytes
    # the two families that are here declare none, and their slabs are
    # what they were
    from kubeml_tpu.models import deepseek_v2, gpt
    for module in (gpt.GPTNano().module, deepseek_v2.DeepSeekV2Module()):
        spec = module.serve_family().cache
        assert spec.slot_state == () and spec.slot_state_bytes == 0
        assert not set(KVPageSlab(geom, spec).state_names) & {"ssm", "conv"}


# ------------------------------------------------------------ the kernels

def _scan_operands(batch, steps, d_inner=256, slots=8, layers=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n = 16
    return dict(
        state=jax.random.normal(ks[0], (layers, slots, n, d_inner)),
        x=jax.random.normal(ks[1], (batch, steps, d_inner)),
        delta=jax.nn.softplus(jax.random.normal(
            ks[2], (batch, steps, d_inner)) - 3.0),
        b=jax.random.normal(ks[3], (batch, steps, n)),
        c=jax.random.normal(ks[4], (batch, steps, n)),
        a=-jnp.exp(jax.random.normal(ks[5], (n, d_inner))),
        d=jax.random.normal(ks[6], (d_inner,)))


@pytest.mark.parametrize("batch,steps,slot0", [(8, 1, 0), (1, 16, 5),
                                               (1, 64, 2)])
def test_selective_scan_kernel_against_the_plain_scan(batch, steps, slot0):
    """The kernel (interpret mode) against the `lax.scan` path at T = 1
    over a batch of lanes, one of them inactive and two fresh, and at T
    = a chunk with a masked tail: y where a step is real and the final
    state agree to float32 rounding; the rows of every other layer and
    slot, and an inactive lane's own, come back bit for bit."""
    from kubeml_tpu.ops.pallas.selective_scan import selective_scan
    ops = _scan_operands(batch, steps)
    state = ops.pop("state")
    valid = np.ones((batch, steps), np.float32)
    if steps == 1:
        valid[2] = 0.0
    else:
        valid[:, steps - 5:] = 0.0
    fresh = (np.arange(batch) % 3 == 1).astype(np.int32)
    kw = dict(layer=jnp.int32(1), slot0=jnp.int32(slot0))
    args = (state, ops["x"], ops["delta"], ops["b"], ops["c"], ops["a"],
            ops["d"], jnp.asarray(valid), jnp.asarray(fresh))
    k_state, k_y = jax.jit(lambda *a: selective_scan(
        *a, impl="pallas", interpret=True, **kw))(*args)
    p_state, p_y = jax.jit(lambda *a: selective_scan(
        *a, impl="gather", **kw))(*args)
    np.testing.assert_allclose(k_state, p_state, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(k_y) * valid[:, :, None],
                               np.asarray(p_y) * valid[:, :, None],
                               rtol=1e-5, atol=1e-5)
    for got in (k_state, p_state):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[0], state[0])
        np.testing.assert_array_equal(got[2], state[2])
        touched = np.zeros(state.shape[1], bool)
        touched[slot0:slot0 + batch] = True
        np.testing.assert_array_equal(got[1][~touched], state[1][~touched])
        if steps == 1:
            np.testing.assert_array_equal(got[1, 2], state[1, 2])
    # a fresh sequence starts from zeros whatever the slot held, NaNs too
    poisoned = state.at[1, slot0 + 1].set(jnp.nan)
    again, _ = jax.jit(lambda *a: selective_scan(
        *a, impl="pallas", interpret=True, **kw))(poisoned, *args[1:])
    if batch > 1:
        np.testing.assert_array_equal(again[1, slot0 + 1],
                                      k_state[1, slot0 + 1])


def test_selective_scan_validates_impl_and_geometry():
    from kubeml_tpu.ops.pallas.selective_scan import (resolve_impl,
                                                      scan_eligible,
                                                      selective_scan)
    geom = dict(batch=1, d_inner=256, d_state=16)
    assert scan_eligible(steps=1, **geom) and scan_eligible(steps=16, **geom)
    assert not scan_eligible(steps=12, **geom)
    assert not scan_eligible(steps=8, batch=1, d_inner=200, d_state=16)
    assert resolve_impl("auto", False, steps=8, **geom) == "gather"   # CPU
    assert resolve_impl("auto", True, steps=8, **geom) == "pallas"
    assert resolve_impl("auto", True, steps=12, **geom) == "gather"
    ops = _scan_operands(1, 12)
    args = (ops["state"], ops["x"], ops["delta"], ops["b"], ops["c"],
            ops["a"], ops["d"], jnp.ones((1, 12)), jnp.zeros(1, jnp.int32))
    with pytest.raises(ValueError, match="impl must be one of"):
        selective_scan(*args, layer=0, impl="mosaic")
    with pytest.raises(ValueError, match="whole blocks of 8"):
        selective_scan(*args, layer=0, impl="pallas", interpret=True)


@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("q_len", [1, 8])
def test_paged_attention_grouped_queries_against_the_gather_path(kv_heads,
                                                                 q_len):
    """Four query heads over one and over two KV heads of 128: the
    kernel (interpret mode) against the gather path, slots at different
    lengths, with no scale sidecars."""
    import functools

    from kubeml_tpu.ops.pallas.paged_attention import (paged_attention,
                                                       paged_eligible,
                                                       resolve_impl)
    S, H, D, G, pmax = (3 if q_len == 1 else 1), 4, 128, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (S, q_len, H, D))
    k = jax.random.normal(ks[1], (2, S * pmax + 1, G, kv_heads * D))
    v = jax.random.normal(ks[2], (2, S * pmax + 1, G, kv_heads * D))
    C = pmax * G
    lengths = np.minimum(C, (np.arange(S) + 1) * (C // S) - 3)
    tables = np.zeros((S, pmax), np.int32)
    for s in range(S):
        n = -(-int(lengths[s]) // G)
        tables[s, :n] = 1 + s * pmax + np.arange(n)
    q_pos = lengths[:, None] - q_len + np.arange(q_len)[None, :]
    bias = jnp.where(jnp.arange(C)[None, None, :] <= q_pos[:, :, None],
                     0.0, -1e9)[:, None]
    args = (q, k, v, None, None, jnp.asarray(tables), bias)
    ker = jax.jit(functools.partial(paged_attention, layer=1, impl="pallas",
                                    interpret=True))(*args)
    ref = jax.jit(functools.partial(paged_attention, layer=1,
                                    impl="gather"))(*args)
    np.testing.assert_allclose(ker, ref, rtol=1e-5, atol=1e-5)
    geom = dict(page=G, q_len=q_len, heads=H, head_dim=D, max_pages=pmax,
                dtype=jnp.float32)
    assert paged_eligible(kv_heads=kv_heads, **geom)
    assert resolve_impl("auto", True, kv_heads=kv_heads, **geom) == "pallas"
    # a row of one KV head of 64 is half a lane tile: the gather path
    assert not paged_eligible(kv_heads=1, **{**geom, "head_dim": 64})
    if kv_heads == 2:
        with pytest.raises(ValueError, match="multiple of kv_heads"):
            paged_attention(q[:, :, :3], k, v, None, None,
                            jnp.asarray(tables), bias, layer=1)


def test_engine_takes_the_kernels_in_interpret_mode(ref):
    """Both kernels inside the two programs, through the engine
    (attn_impl 'pallas', interpret): against the reference, float32."""
    m = jamba.JambaModule(dtype=jnp.float32)
    variables = seeded(m)
    rng = np.random.default_rng(21)
    reqs = [_request(rng, m, n, n_new=3) for n in (19, 2)]
    eng, served = _serve(m, variables, reqs, slots=2, attn_impl="pallas",
                         attn_interpret=True)
    assert eng.stats["attn_impl_decode"] == "pallas"
    assert eng.family.scan_impls(2, CHUNK, "pallas", True) \
        == ("pallas", "pallas")
    for r, got in zip(reqs, served):
        _close(got, _reference_logits(ref, m, variables, r), F32_RTOL)


# ---------------------------------------------- the other two families

def test_deepseek_programs_are_the_builders_unchanged_behind_the_seam():
    """PR 27's pin of GPT's programs (tests/test_models_deepseek_v2.py),
    for DeepSeek-V2's two: what the engine jits is each builder's own
    function, argument for argument (no slot index reaches a family
    that declares no slot state), and the slab's state is the one plane."""
    from kubeml_tpu.models import deepseek_v2 as ds
    module = ds.DeepSeekV2Module()
    variables = module.init(jax.random.PRNGKey(0))
    eng = DecodeEngine(module, variables, slots=2, page=16, prefill_chunk=32)
    S, pmax, C = 2, eng.geom.pages_per_slot, 32
    state = eng.slab.state
    assert len(state) == 1 and eng.slab.state_names == ("plane_0",)
    i32, f32 = jnp.int32, jnp.float32
    decode_args = (variables["params"], *state, jnp.zeros(S, i32),
                   jnp.zeros(S, i32), jnp.zeros((S, pmax), i32),
                   jnp.zeros(S, i32), jnp.zeros(S, i32), jnp.zeros(S, f32),
                   jnp.zeros(S, f32), jnp.zeros((S, 2), jnp.uint32),
                   jnp.zeros(S, i32), jnp.zeros(S, i32), jnp.zeros(S, f32))
    prefill_args = (variables["params"], *state, jnp.zeros(C, i32),
                    jnp.zeros(C, i32), jnp.zeros(pmax, i32),
                    jnp.zeros(C, i32), jnp.zeros(C, i32), jnp.zeros(C, f32))
    assert str(jax.make_jaxpr(eng._step_raw)(*decode_args)) == str(
        jax.make_jaxpr(ds.build_decode_step(module))(*decode_args))
    served = eng.family.prefill_step(C, "f32", "auto", False)
    assert str(jax.make_jaxpr(served)(*prefill_args)) == str(
        jax.make_jaxpr(ds.build_prefill_step(module, C))(*prefill_args))
    # and the engine hands the prefill program exactly those arguments:
    # the parameters, the state and one buffer that unpacks to the rest
    seen = []
    real = eng._prefill
    eng._prefill = lambda *a: (seen.append(len(a)), real(*a))[1]
    eng._prefill._cache_size = real._cache_size
    req = GenerateRequest(list(range(1, 40)), max_new_tokens=2,
                          temperature=0.0, seed=0)
    eng.attach(req)
    _finish(eng)
    assert seen and set(seen) == {1 + len(state) + 1}
    assert [(v.shape, v.dtype) for v in eng._packings["prefill"].host()[1]] \
        == [(a.shape, a.dtype) for a in prefill_args[1 + len(state):]]
