"""The EXAONE-MoE serving family (models/exaone_moe.py) against its plain
float32 reference (benchmark/refs/exaone_moe.py, loaded from there), at
a small size on the CPU with a window of 8: prefill then decode through
the global layers' pages and the window layers' per-slot ring, the ring
itself, positions, the sigmoid router, the shares' sum, the engine's
rules for a family with per-slot state, and the kernel in interpret
mode.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models import exaone_moe as ex
from kubeml_tpu.models.base import sample_tokens
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.slots import GenerateRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, PAGE = 32, 16        # a chunk four times the window

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference itself. It imports benchmark.refs.quant
    (the int8 control) by that name and nothing of kubeml_tpu."""
    import importlib.util
    path = os.path.join(REPO, "benchmark", "refs", "exaone_moe.py")
    with open(path) as f:
        assert not [line for line in f if "import" in line
                    and "kubeml_tpu" in line]
    spec = importlib.util.spec_from_file_location("ref_exaone_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cfg_of(m: ex.ExaoneMoEModule) -> dict:
    """The reference's configuration (published keys) of a module."""
    return {
        "hidden_size": m.hidden, "num_attention_heads": m.heads,
        "num_key_value_heads": m.kv_heads, "head_dim": m.head_dim,
        "intermediate_size": m.intermediate_size,
        "moe_intermediate_size": m.moe_intermediate_size,
        "num_shared_experts": m.n_shared_experts,
        "num_experts": m.n_held_experts,
        "ep": {"size": m.n_experts // m.n_held_experts, "rank": m.ep_rank,
               "router_outputs": m.n_experts},
        "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid",
        "num_experts_per_tok": m.experts_per_tok,
        "routed_scaling_factor": m.routed_scaling_factor,
        "num_hidden_layers": m.layers, "first_k_dense_replace": m.first_dense,
        "sliding_windows": list(m.sliding_windows),
        "sliding_window": m.window,
        "vocab_size": m.vocab_size, "max_position_embeddings": m.max_len,
        "rope_parameters": {"rope_theta": m.rope_theta,
                            "rope_type": "default"},
        "rms_norm_eps": m.rms_eps}


def flat_weights(variables) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]:
        out["/".join(str(p.key) for p in path)] = leaf
    return out


def seeded(m: ex.ExaoneMoEModule, seed: int = 0):
    """Weights of unit gain, so that every sublayer moves the residual
    by about its own size and every leaf carries signal: kernels normal
    / sqrt(fan-in), norm scales off 1, a selection bias wide enough to
    move the choice."""
    variables = m.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4096))

    def stir(path, leaf):
        name, key = path[-1].key, next(noise)
        if name == "scale":
            new = 1.0 + 0.1 * jax.random.normal(key, leaf.shape)
        elif name == "bias":
            new = 0.1 * jax.random.normal(key, leaf.shape)
        elif name == "embedding":
            new = 0.5 * jax.random.normal(key, leaf.shape)
        else:
            new = jax.random.normal(key, leaf.shape) \
                / np.sqrt(leaf.shape[-2])
        return new.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(stir, variables)


PUBLISHED = ex.ExaoneMoEModule(
    vocab_size=19200, max_len=18432, hidden=6144, layers=5,
    sliding_windows=(128, 128, 128, 0, 128), first_dense=1, heads=64,
    kv_heads=8, head_dim=128, intermediate_size=18432,
    moe_intermediate_size=2048, n_shared_experts=1, n_experts=128,
    n_held_experts=16, ep_rank=0, experts_per_tok=8,
    routed_scaling_factor=2.5, rope_theta=1e6, rms_eps=1e-5)


# ------------------------------------------------- tapping the engine

class _Tapped:
    """A module whose family's decode program also hands its logits to
    `sink(logits [S, V], pos [S], active [S])`, in dispatch order: what
    DecodeEngine runs is the family's own decode program with one
    callback in it."""

    def __init__(self, module, sink):
        self.module, self.sink = module, sink

    def serve_family(self):
        tapped, m = self, self.module

        class Family(ex.ExaoneMoEServeFamily):
            def decode_step(self, kv_dtype, attn_impl, attn_interpret):
                logits_of = ex.build_decode_logits(m, attn_impl,
                                                   attn_interpret)

                def step(params, k, v, wk, wv, tokens, pos, tables, wp, wo,
                         active, temps, key_data, cs, cd, poison):
                    logits, counts, *state = logits_of(
                        params, k, v, wk, wv, tokens, pos, tables, wp, wo,
                        active, cs, cd)
                    jax.debug.callback(tapped.sink, logits, pos, active,
                                       ordered=True)
                    nxt, bad = sample_tokens(logits, active, temps,
                                             key_data, poison, ex.PAD_ID)
                    return (jnp.concatenate([nxt, counts]), bad, *state)

                return step

        return Family(m)


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


def _serve(m, variables, requests, slots=4, chunk=CHUNK, **kw):
    """Attach every request at once (one slot each), run to the end;
    returns (engine, [served logits of each request])."""
    sink = _Sink()
    eng = DecodeEngine(_Tapped(m, sink), variables, slots=slots, page=PAGE,
                       prefill_chunk=chunk, **kw)
    where = [eng.attach(r) for r in requests]
    _finish(eng)
    assert all(r.outcome == "ok" for r in requests)
    return eng, [sink.served(s, r) for s, r in zip(where, requests)]


def _reference_logits(ref, m, variables, req):
    ids = list(req.prompt) + list(req.tokens)
    positions = np.arange(len(req.prompt) - 1, len(ids) - 1)
    return ref.logits(flat_weights(variables), cfg_of(m), ids, positions)


# ------------------------------------------------------------ the files

def test_module_and_reference_name_the_same_leaves(ref):
    for m in (ex.ExaoneMoEModule(), PUBLISHED):
        spec = ref.weight_spec(cfg_of(m))
        shapes = {"params/" + k: v for k, v in m.param_shapes().items()}
        assert {k: tuple(s) for k, (s, _d) in spec.items()} == shapes
        assert all(d == jnp.bfloat16 for _s, d in spec.values())
        # lib/weights.py has rules for these leaf names and no others
        assert {k.rsplit("/", 1)[1] for k in shapes} \
            == {"kernel", "embedding", "scale", "bias"}
    shapes = jax.eval_shape(lambda: PUBLISHED.init(jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    # ISSUE 33's count of the share: 3,711.9M parameters, 7.42 GB
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 3_711.9e6) < 0.2e6
    fam = PUBLISHED.serve_family()
    assert (fam.cache.layers, fam.cache.planes, fam.cache.lanes) \
        == (1, 2, 1024)
    assert [(s.name, s.layers, s.shape) for s in fam.cache.slot_state] \
        == [("win_k", 4, (128, 1024)), ("win_v", 4, (128, 1024))]
    # the ring of 64 slots: 4 layers x 64 x 2 planes x 256 KB
    assert 64 * fam.cache.slot_state_bytes == 4 * 64 * 2 * 256 * 1024


def test_a_module_of_one_kind_of_layer_or_two_windows_is_refused():
    for windows in ((8, 8, 8, 8, 8), (0, 0, 0, 0, 0), (8, 4, 8, 0, 8),
                    (8, 8, 0)):
        with pytest.raises(ValueError, match="sliding_windows"):
            ex.ExaoneMoEModule(sliding_windows=windows)
    with pytest.raises(ValueError, match="not a share"):
        ex.ExaoneMoEModule(ep_rank=4)


# --------------------------------------- the engine against the reference

F32_RTOL = 2e-5
# bfloat16 parameters and matmul inputs against the float32 reference on
# the same (bfloat16) values: the same reading as the other families'
BF16_RTOL = 4e-2


def _close(got, want, rtol):
    scale = np.abs(want[:, 1:]).max()
    np.testing.assert_allclose(got[:, 1:], want[:, 1:], rtol=0,
                               atol=rtol * scale)


# prompts of 1 token (no prefill chunk at all), 2 and 3 (a chunk of one
# token, of two), 41 (a chunk boundary inside a window: positions 32..38
# attend rows the chunk before left in the ring), 65 (two whole chunks)
# and 70 (over eight windows of context before the first decode step)
PROMPTS = (1, 2, 3, 41, 65, 70)


@pytest.mark.parametrize("dtype,rtol,chunk", [
    ("float32", F32_RTOL, CHUNK), ("bfloat16", BF16_RTOL, CHUNK),
    # a chunk that is no whole number of windows: one block of queries
    ("float32", F32_RTOL, 12)])
def test_engine_prefill_then_decode_against_the_reference(ref, dtype, rtol,
                                                          chunk):
    """Chunked prefill then decode through DecodeEngine, six streams of
    the lengths above in one batch, one dispatch ahead: the logits of
    every served position (contexts up to ten windows) against the
    reference's full forward of prompt + served tokens under its band
    and causal masks."""
    m = ex.ExaoneMoEModule(dtype=getattr(jnp, dtype))
    variables = seeded(m)
    rng = np.random.default_rng(7)
    reqs = [_request(rng, m, n, n_new=12) for n in PROMPTS]
    eng, served = _serve(m, variables, reqs, slots=len(reqs), chunk=chunk)
    assert eng.stats["ahead_dispatches"] > 0
    assert eng.stats["prefill_dispatches"] == sum(
        -(-(n - 1) // chunk) for n in PROMPTS)
    assert eng.stats["slot_state_bytes"] == eng.stats["occupancy_sum"] \
        * 2 * eng.family.cache.slot_state_bytes
    assert eng.family.cache.slot_state_bytes \
        == 2 * 4 * m.window * m.kv_lanes * jnp.dtype(m.dtype).itemsize
    # every lane-step attended min(pos + 1, W) ring rows in each of the
    # four window layers
    lane_steps = [p for n in PROMPTS for p in range(n - 1, n - 1 + 12)]
    assert eng.stats["window_rows_read"] == 4 * sum(
        min(p + 1, m.window) for p in lane_steps)
    assert eng.stats["moe_assignments"] == 4 * m.experts_per_tok \
        * eng.stats["occupancy_sum"]
    if dtype == "float32":
        for r, got in zip(reqs, served):
            _close(got, _reference_logits(ref, m, variables, r), rtol)
        return
    # bfloat16 rounding flips a top-4-of-16 near-tie now and then, and
    # a stream that chose another expert differs from there on, both
    # rightly: most rows agree to bfloat16 rounding, and the benchmark's
    # own statistic (how far the served token lies below the
    # reference's best, near-ties evaluated both ways) stays small
    w, cfg = flat_weights(variables), dict(cfg_of(m), route_eps=0.2)
    off, gaps = [], []
    for r, got in zip(reqs, served):
        want = _reference_logits(ref, m, variables, r)
        off.extend(np.abs(got[:, 1:] - want[:, 1:]).max(-1)
                   / np.abs(want[:, 1:]).max())
        gaps.extend(ref.served_gaps(w, cfg, r.prompt, r.tokens)["gaps"])
    assert np.median(off) < rtol and np.mean(np.asarray(off) < rtol) > 0.8
    assert np.mean(gaps) < 0.01 and np.max(gaps) < 0.5


def _faults(monkeypatch, fault):
    """Plant one fault in the program's own functions."""
    if fault == "window_layers_without_positions":
        monkeypatch.setattr(ex, "_rope", lambda x, cos, sin: x)
    elif fault == "positions_on_the_global_layer":
        real = ex._qkv

        def all_rotated(m, i, p, h, cos, sin):
            q, k, v = real(m, i, p, h, cos, sin)
            if m.sliding_windows[i]:
                return q, k, v
            k = ex._rope(k.reshape(h.shape[0], m.kv_heads, m.head_dim), cos,
                         sin).reshape(k.shape)
            return ex._rope(q, cos, sin), k, v

        monkeypatch.setattr(ex, "_qkv", all_rotated)
    elif fault == "no_norm_of_q_and_k":
        real = ex.rms_norm
        monkeypatch.setattr(
            ex, "rms_norm", lambda x, scale, eps: x.astype(jnp.float32)
            if x.ndim == 3 else real(x, scale, eps))
    elif fault == "bias_in_the_weights":
        def route(m, logits, bias):
            s = jax.nn.sigmoid(logits) + bias.astype(jnp.float32)[None, :]
            chosen, experts = jax.lax.top_k(s, m.experts_per_tok)
            return experts, chosen / chosen.sum(-1, keepdims=True)

        monkeypatch.setattr(ex, "route", route)
    elif fault == "weights_not_renormalised":
        def route(m, logits, bias):
            s = jax.nn.sigmoid(logits)
            _, experts = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :],
                                       m.experts_per_tok)
            return experts, jnp.take_along_axis(s, experts, axis=1)

        monkeypatch.setattr(ex, "route", route)
    elif fault == "a_window_one_position_wider":
        real = ex._softmax_over

        def wider(m, q, k, v, seen):
            # the decode lanes' view of the ring: rows of every position
            return real(m, q, k, v, jnp.ones_like(seen)
                        if k.ndim == 3 else seen)

        monkeypatch.setattr(ex, "_softmax_over", wider)
    else:
        raise AssertionError(fault)


@pytest.mark.parametrize("fault", [
    "window_layers_without_positions", "positions_on_the_global_layer",
    "no_norm_of_q_and_k", "bias_in_the_weights", "weights_not_renormalised",
    "a_window_one_position_wider"])
def test_a_planted_fault_fails_the_float32_comparison(ref, fault,
                                                      monkeypatch):
    """The comparison above has the power it claims: positions left off
    the window layers or put on the global one, q and k without their
    norm, a selection bias that reaches the weights, weights that are
    not re-normalised, and a decode lane that attends ring rows of
    positions it does not own yet (a stale stream's: the slot is used
    twice) each move a served logit by more than fifty times the
    float32 tolerance."""
    _faults(monkeypatch, fault)
    m = ex.ExaoneMoEModule(dtype=jnp.float32)
    variables = seeded(m)
    rng = np.random.default_rng(7)
    sink = _Sink()
    eng = DecodeEngine(_Tapped(m, sink), variables, slots=1, page=PAGE,
                       prefill_chunk=CHUNK)
    assert eng.attach(_request(rng, m, 41, n_new=4)) == 0
    _finish(eng)
    req = _request(rng, m, 3, n_new=4)      # the slot's second stream
    assert eng.attach(req) == 0
    _finish(eng)
    got = sink.served(0, req)
    want = _reference_logits(ref, m, variables, req)
    off = np.abs(got[:, 1:] - want[:, 1:]).max() / np.abs(want[:, 1:]).max()
    assert off > 50 * F32_RTOL, off


def test_the_ring_after_wrap_around_is_the_band(ref):
    """After a stream of 61 positions (seven times round a ring of 8)
    row j of every window layer's ring holds the K, rotated, and the V
    of the newest position p with p % 8 == j: the eight positions the
    band mask shows the next query, as the reference computes them."""
    m = ex.ExaoneMoEModule(dtype=jnp.float32)
    variables = seeded(m)
    rng = np.random.default_rng(3)
    req = _request(rng, m, 50, n_new=12)
    eng = DecodeEngine(m, variables, slots=2, page=PAGE, prefill_chunk=CHUNK)
    assert eng.attach(req) == 0
    _finish(eng)
    ids = list(req.prompt) + list(req.tokens)
    n = len(ids) - 1            # positions 0..60 went through a program
    assert n == 61
    w, cfg = flat_weights(variables), cfg_of(m)
    names = eng.slab.state_names
    win_k = np.asarray(eng.slab.state[names.index("win_k")])
    win_v = np.asarray(eng.slab.state[names.index("win_v")])
    padded = np.zeros(m.max_len, np.int32)
    padded[:len(ids)] = ids
    h = w["params/embed/embedding"][padded].astype(jnp.float32)
    pos = jnp.arange(m.max_len)
    dims = tuple(sorted(ref._dims(cfg).items()))
    freq = jnp.asarray(ref.inv_freq(cfg))
    newest = [max(p for p in range(n) if p % m.window == j)
              for j in range(m.window)]
    assert sorted(newest) == list(range(n - m.window, n))
    for i in range(m.layers):
        lw = ref._layer_weights(w, i)
        window = m.sliding_windows[i]
        h_mid, k, v = ref._attn(h, pos, lw, freq, "f32", dims, window)
        if window:
            row = m.window_layers.index(i)
            for got, want in ((win_k, k), (win_v, v)):
                np.testing.assert_allclose(
                    got[row, 0], np.asarray(want)[newest].reshape(
                        m.window, -1), atol=2e-5, rtol=0)
        h = ref._dense_ffn(h_mid, lw, "f32", m.rms_eps) \
            if i < m.first_dense else ref.moe_ffn(h_mid, lw, cfg)[0]
    # the other slot's ring was never written
    assert not win_k[:, 1].any() and not win_v[:, 1].any()


def test_positions_are_on_the_window_layers_only(ref):
    """The rotation reaches q and k of a window layer and of no global
    one: the same token at two positions gives a global layer the same
    q and k rows and a window layer rotated ones, by the angles worked
    by hand (theta 1e6 over 64 dimensions, pair j with j + 32)."""
    m = ex.ExaoneMoEModule(dtype=jnp.float32)
    p = seeded(m)["params"]
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(1, m.hidden)).astype(np.float32))
    h2 = jnp.concatenate([h, h])
    cos, sin = ex._angles(m, jnp.asarray([0, 37]))
    want = 1e6 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(ex.inv_freq(m), want, rtol=1e-6)
    np.testing.assert_allclose(ref.inv_freq(cfg_of(m)), want, rtol=1e-6)
    for i in range(m.layers):
        q, k, v = ex._qkv(m, i, p[f"layer_{i}"], h2, cos, sin)
        np.testing.assert_array_equal(v[0], v[1])
        if not m.sliding_windows[i]:
            np.testing.assert_array_equal(q[0], q[1])
            np.testing.assert_array_equal(k[0], k[1])
            continue
        a, b = np.asarray(q[0, :, :32]), np.asarray(q[0, :, 32:])
        ang = 37 * want
        np.testing.assert_allclose(
            np.asarray(q[1]), np.concatenate(
                [a * np.cos(ang) - b * np.sin(ang),
                 b * np.cos(ang) + a * np.sin(ang)], -1), atol=1e-5)
        assert np.abs(np.asarray(k[0] - k[1])).max() > 1e-2


# ----------------------------------------------------------- the router

@pytest.mark.parametrize("case", ["against_the_reference",
                                  "bias_moves_the_choice_not_the_weights",
                                  "weights_sum_to_one_over_the_chosen",
                                  "the_layer_scales_by_the_factor"])
def test_sigmoid_router(ref, case):
    m = ex.ExaoneMoEModule()
    cfg = cfg_of(m)
    rng = np.random.default_rng(11)
    logits = rng.normal(size=(64, m.n_experts)).astype(np.float32)
    bias = (0.3 * rng.normal(size=m.n_experts)).astype(np.float32)
    experts, scores = (np.asarray(a) for a in ex.route(
        m, jnp.asarray(logits), jnp.asarray(bias)))
    s = 1.0 / (1.0 + np.exp(-logits))
    if case == "against_the_reference":
        r = ref.route(logits, cfg, bias=bias)
        np.testing.assert_array_equal(experts, r["experts"])
        np.testing.assert_allclose(scores, r["scores"], rtol=1e-6)
        assert (r["margin_expert"] >= 0).all() \
            and np.isinf(r["margin_group"]).all()
        # the neighbouring choice: the next expert in the last one's place
        flipped = ref.route(logits, cfg, "expert", bias)
        np.testing.assert_array_equal(flipped["experts"][:, :-1],
                                      r["experts"][:, :-1])
        np.testing.assert_array_equal(flipped["experts"][:, -1],
                                      r["next_expert"])
        # the selection logits alone give the same choice and margins
        z = ref.selection_logits(logits, bias)
        again = ref.route(z, cfg)
        np.testing.assert_array_equal(again["experts"], r["experts"])
        np.testing.assert_allclose(again["margin_expert"],
                                   r["margin_expert"], atol=1e-5)
    elif case == "bias_moves_the_choice_not_the_weights":
        plain, _ = (np.asarray(a) for a in ex.route(
            m, jnp.asarray(logits), jnp.zeros(m.n_experts)))
        assert (np.sort(plain, -1) != np.sort(experts, -1)).any()
        rows = np.arange(64)[:, None]
        np.testing.assert_array_equal(
            experts, np.argsort(-(s + bias), -1, kind="stable")[:, :4])
        chosen = s[rows, experts]
        np.testing.assert_allclose(
            scores, chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    elif case == "weights_sum_to_one_over_the_chosen":
        np.testing.assert_allclose(scores.sum(-1), 1.0, rtol=1e-6)
        assert (scores > 0).all()
    else:
        # one expert layer: doubling the factor doubles the routed part
        f32 = dataclasses.replace(m, dtype=jnp.float32, n_held_experts=16)
        p = seeded(f32)["params"]["layer_1"]
        h = jnp.asarray(rng.normal(size=(24, m.hidden)).astype(np.float32))
        live = jnp.ones(24)
        zero = dataclasses.replace(f32, routed_scaling_factor=0.0)
        twice = dataclasses.replace(f32, routed_scaling_factor=5.0)
        base = ex._ffn(zero, 1, h, p, live)[0]
        once = ex._ffn(f32, 1, h, p, live)[0] - base
        np.testing.assert_allclose(ex._ffn(twice, 1, h, p, live)[0] - base,
                                   2 * once, atol=1e-4)
        assert np.abs(np.asarray(once)).max() > 0.1


@pytest.mark.parametrize("tokens,impl,interpret", [
    (48, "auto", False), (80, "auto", False), (80, "pallas", True)],
    ids=["48", "80", "80-kernel"])
def test_the_shares_sum_to_the_uncut_layer(ref, tokens, impl, interpret):
    """At a small size: the routed parts of all four shares plus the
    shared expert counted once are the uncut reference's layer output,
    and the program's layer on each share (the dense-mask form at 48
    tokens, ragged_dot at 80, and at 80 the grouped-matmul kernel
    forced through the interpreter) is the reference's on that
    share."""
    uncut = ex.ExaoneMoEModule(dtype=jnp.float32, n_held_experts=16)
    cfg_all = cfg_of(uncut)
    assert cfg_all["ep"]["size"] == 1
    w = flat_weights(seeded(uncut, seed=4))
    lw = ref._layer_weights(w, 1)
    h = jnp.asarray(np.random.default_rng(9).normal(
        size=(tokens, uncut.hidden)).astype(np.float32))
    whole, chosen = ref.moe_ffn(h, lw, cfg_all)
    total = np.zeros_like(np.asarray(whole))
    shared_once = None
    held_pairs = 0
    for rank in range(4):
        share = dataclasses.replace(uncut, n_held_experts=4, ep_rank=rank)
        cfg = cfg_of(share)
        assert cfg["ep"] == {"size": 4, "rank": rank, "router_outputs": 16}
        lw_r = dict(lw)
        for name in ("gate", "up", "down"):
            key = f"experts/{name}/kernel"
            lw_r[key] = lw[key][4 * rank:4 * rank + 4]
        logits = np.asarray(ref._router_logits(h, lw_r, cfg["rms_norm_eps"]))
        r = ref.route(logits, cfg, bias=ref._bias(lw_r))
        np.testing.assert_array_equal(r["experts"], chosen["experts"])
        np.testing.assert_allclose(r["scores"].sum(-1), 1.0, rtol=1e-6)
        local, weight = ref.local_weights(r, cfg)
        shared, routed = ref._moe_parts(h, local, weight, lw_r, "f32",
                                        cfg["rms_norm_eps"])
        total += np.asarray(routed)
        shared_once = np.asarray(shared)
        p = {"ffn_norm": {"scale": lw["ffn_norm/scale"]},
             "router": {"kernel": lw["router/kernel"],
                        "bias": lw["router/bias"]},
             "shared": {n: {"kernel": lw[f"shared/{n}/kernel"]}
                        for n in ("gate", "up", "down")},
             "experts": {n: {"kernel": lw_r[f"experts/{n}/kernel"]}
                         for n in ("gate", "up", "down")}}
        # one program, read before anything else is dispatched: the
        # interpreter's callbacks run JAX operations of their own
        got, counts = jax.block_until_ready(jax.jit(
            lambda h, p, live: ex._ffn(share, 1, h, p, live, impl,
                                       interpret))(h, p, jnp.ones(tokens)))
        assert share.serve_family().moe_impl(tokens, impl, interpret) == (
            "dense" if tokens == 48 else
            "pallas" if interpret else "gather")
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(h + shared + routed),
                                   atol=2e-5, rtol=0)
        assert int(counts[0]) == tokens * share.experts_per_tok
        assert int(counts[1]) == int((weight > 0).sum())
        held_pairs += int(counts[1])
    assert held_pairs == tokens * uncut.experts_per_tok
    np.testing.assert_allclose(np.asarray(h) + shared_once + total,
                               np.asarray(whole), atol=2e-5, rtol=0)


def test_near_tie_rule_takes_the_smallest_gap_and_leaves_nothing_out(ref):
    """A token the NEIGHBOURING routing puts first reads a gap of 0 at a
    near-tie position once the rule is on, every position keeps a gap,
    and with the rule off nothing else is evaluated."""
    m = ex.ExaoneMoEModule(dtype=jnp.float32)
    w, cfg = flat_weights(seeded(m, seed=2)), cfg_of(m)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, m.vocab_size, 40).tolist()
    served = rng.integers(1, m.vocab_size, 24).tolist()
    off = ref.served_gaps(w, cfg, prompt, served)["gaps"]
    on_cfg = dict(cfg, route_eps=0.5)       # wide: most positions treated
    ids = prompt + served
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    main, alt_row, alt_logits, alt_margin, treated = ref.forward(
        w, on_cfg, ids, positions, route_eps=0.5)
    assert treated.any() and len(alt_row) > treated.sum()
    assert alt_margin.shape == alt_row.shape
    assert (alt_margin >= 0).all() and (alt_margin < 0.5).all()
    on = ref.served_gaps(w, on_cfg, prompt, served)["gaps"]
    assert on.shape == off.shape == (24,)
    assert (on <= off + 1e-6).all() and (on < off - 1e-3).any()
    # the alternative's own best token has gap 0 there
    row = int(alt_row[0])
    tokens = np.asarray(served)
    tokens[row] = int(alt_logits[0].argmax())
    assert ref._gaps(main, alt_row, alt_logits, tokens)[row] == 0.0
    _, none_row, _, _, none = ref.forward(w, cfg, ids, positions)
    assert len(none_row) == 0 and not none.any()
    # the int8 control departs from the float32 reading, bfloat16 less
    low = ref.served_gaps(w, cfg, prompt, served, control=True)
    assert (low["control_gaps"] > 0).any()
    assert low["altered_gaps"].mean() > 10 * low["control_gaps"].mean()
    taps = []
    ref.forward(w, cfg, ids, positions, mode="bf16", tap=taps)
    assert len(taps) == m.layers - m.first_dense
    assert taps[0].shape == (24, m.n_experts)


# --------------------------------------------------------- bit identity

def _case(m, variables, specs, **kw):
    rng = np.random.default_rng(9)
    reqs = [_request(rng, m, n, n_new, temp, seed)
            for n, n_new, temp, seed in specs]
    eng, served = _serve(m, variables, reqs, **kw)
    return eng, reqs, served


SPECS = [(41, 7, 0.0, 0), (3, 9, 0.9, 1), (65, 5, 1.3, 7), (20, 12, 0.7, 3)]


@pytest.fixture(scope="module")
def tiny():
    m = ex.ExaoneMoEModule()
    return m, seeded(m)


def test_solo_against_batched_is_bit_identical(tiny):
    """A stream's logits and tokens are the same bits alone in the
    engine and packed with three neighbours: lanes and slots are rows,
    a slot's ring is its own."""
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
    bits B gives alone in a fresh engine. Nothing empties the slot's
    ring: a row is valid by position, and B at position p owns rows
    0..p until it has gone round. B is tried with a chunked prompt (the
    prefill program masks the rows under position 0) and with one token
    (the decode program's `rows <= pos`)."""
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
        assert float(jnp.abs(eng.slab.state[2]).max()) > 0   # A's ring
        assert eng.attach(b) == 0
        _finish(eng)
        _e, (alone,) = _serve(m, variables, [twin], slots=1)
        assert b.tokens == twin.tokens
        np.testing.assert_array_equal(sink.served(0, b), alone)


def test_one_ahead_against_the_serial_sequence(tiny):
    """The rings follow from dispatch to dispatch on the device: one
    dispatch ahead and the serial sequence (reached by an empty fault
    plan, as tests/test_one_ahead.py reaches it) give the same bits,
    and the regime engages."""
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
    for name in ex.STEP_COUNTERS:
        assert ahead.stats[name] == serial.stats[name] > 0
    assert ahead.stats["compiles"] == 1
    assert ahead.stats["prefill_compiles"] == 1


def test_token_by_token_prefill_is_the_chunked_prefill(tiny):
    """prefill_chunk 0: every prompt position rides the decode program,
    one ring row a step; tokens agree with the chunked path's (another
    order of sums, so no bit identity)."""
    m, variables = tiny
    _e, reqs, _lg = _case(m, variables, SPECS)
    rng = np.random.default_rng(9)
    again = [_request(rng, m, *spec) for spec in SPECS]
    eng = DecodeEngine(m, variables, slots=4, page=PAGE, prefill_chunk=0)
    for r in again:
        eng.attach(r)
    _finish(eng)
    assert eng.stats["prefill_dispatches"] == 0
    assert [r.tokens for r in again] == [r.tokens for r in reqs]


def test_one_prompt_twice_prefills_twice_and_agrees(tiny):
    """A family with per-slot state registers and matches no prefix,
    whatever the option says: the global layers' pages would come
    WITHOUT the window layers' rows at their boundary. Two requests
    with one prompt of three full pages both prefill in full, and
    agree."""
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
    assert eng.spawn_recovered().prefix_cache is False


def test_a_resumed_stream_re_prefills_to_the_same_tokens(tiny):
    """The replica is replaced mid-stream (a wedged loop, the watchdog,
    spawn_recovered: tests/test_serve_faults.py's way): the resumed
    streams re-prefill prompt + emitted tokens from position 0 into the
    new engine's rings and finish with the tokens of an uninterrupted
    run."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.service import ServeService
    m, variables = tiny
    _e, clean, _lg = _case(m, variables, SPECS)
    plan = ServeFaultPlan.parse([{"kind": "serve_loop_wedge", "step": 6}])
    engine = DecodeEngine(m, variables, slots=4, page=PAGE,
                          prefill_chunk=CHUNK, fault_plan=plan)
    svc = ServeService("exaone-wedge", engine, wedge_timeout_s=0.2,
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
    assert [r.tokens for r in reqs] == [c.tokens for c in clean]


def test_a_ring_one_step_ahead_of_its_stream_ends_the_stream(tiny):
    """The engine's rule is per family with slot state and stays as it
    is: a dispatch that raised between its enqueue and its walk ends
    the lanes' streams with an error (a ring write is idempotent by
    position, a recurrence is not; the engine does not tell them
    apart)."""
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
    real, calls = eng._walk_emitted, []

    def fails_once(*a, **kw):
        if not calls:
            calls.append(1)
            raise RuntimeError("walk failed")
        return real(*a, **kw)

    eng._walk_emitted = fails_once
    with pytest.raises(RuntimeError, match="walk failed"):
        eng.step()
    finished = eng.step()
    assert sorted(id(r) for r in finished) == sorted(id(r) for r in reqs)
    assert all(r.outcome == "error" and "per-slot state" in r.error
               for r in reqs)
    assert eng.active() == 0
    eng.check_pager()


def test_optional_programs_and_int8_pages_are_refused_by_name(tiny):
    m, variables = tiny
    with pytest.raises(ValueError,
                       match="'exaone_moe' provides no multi-step"):
        DecodeEngine(m, variables, slots=2, page=PAGE, decode_steps=4)
    with pytest.raises(ValueError, match="no int8"):
        DecodeEngine(m, variables, slots=2, page=PAGE, kv_dtype="int8")
    with pytest.raises(ValueError, match="attn_impl"):
        DecodeEngine(m, variables, slots=2, page=PAGE, attn_impl="flash")


def test_the_engine_names_nothing_of_the_family():
    with open(os.path.join(REPO, "kubeml_tpu", "serve", "engine.py")) as f:
        source = f.read()
    assert "models.exaone_moe" not in source and "import exaone" not in source
    assert "win_k" not in source and "window_rows" not in source


def test_engine_takes_the_kernel_in_interpret_mode(ref):
    """attn_impl 'pallas' in interpret mode: the global layer's decode
    read goes through the paged kernel's grouped-query form (4 query
    heads over 2 KV heads of 64), the window layers stay plain JAX."""
    m = ex.ExaoneMoEModule(dtype=jnp.float32)
    variables = seeded(m)
    rng = np.random.default_rng(21)
    reqs = [_request(rng, m, n, n_new=5) for n in (41, 3)]
    eng, served = _serve(m, variables, reqs, slots=2, attn_impl="pallas",
                         attn_interpret=True)
    assert eng.stats["attn_impl_decode"] == "pallas"
    assert eng.stats["attn_impl_prefill"] == "gather"
    assert eng.stats["moe_impl_prefill"] == "dense"     # a chunk of 32
    for r, got in zip(reqs, served):
        _close(got, _reference_logits(ref, m, variables, r), F32_RTOL)
