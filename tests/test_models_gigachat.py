"""The GigaChat3.5 serving family (models/gigachat.py) and its gated
delta-rule kernels (ops/pallas/gated_delta.py) against the plain
float32 reference (benchmark/refs/gigachat.py, loaded from there), at a
small size on the CPU: one dense GatedDeltaNet layer, then two
GatedDeltaNet layers, one MLA layer and one more GatedDeltaNet layer
with experts (4 of 16 held), seeded weights of unit gain and LONG
memory (the module's own A_log and step bias), so that a state kept in
bfloat16, a dropped gate or a state that leaks from one stream to the
next moves a logit by far more than a tolerance. Chunked prefill then
decode go through DecodeEngine itself; the logits are tapped out of the
decode program it runs.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models import gigachat as gc
from kubeml_tpu.models.base import sample_tokens
from kubeml_tpu.ops.pallas import gated_delta as gd
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.slots import GenerateRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK, PAGE = 16, 16

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def ref():
    """The benchmark's reference itself. It imports benchmark.refs (the
    int8 control and the sigmoid router) by that name and nothing of
    kubeml_tpu."""
    import importlib.util
    path = os.path.join(REPO, "benchmark", "refs", "gigachat.py")
    with open(path) as f:
        assert not [line for line in f if "import" in line
                    and "kubeml_tpu" in line]
    spec = importlib.util.spec_from_file_location("ref_gigachat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cfg_of(m: gc.GigaChatModule, route_eps: float = 0.0) -> dict:
    """The reference's configuration (published keys) of a module."""
    return {
        "hidden_size": m.hidden, "num_hidden_layers": m.layers,
        "first_k_dense_replace": m.first_dense,
        "full_attention_layers": list(m.full_attention_layers),
        "num_attention_heads": m.heads, "q_lora_rank": m.q_lora_rank,
        "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "gated_attention": m.gated_attention,
        "linear_num_key_heads": m.linear_key_heads,
        "linear_num_value_heads": m.linear_value_heads,
        "linear_key_head_dim": m.linear_key_head_dim,
        "linear_value_head_dim": m.linear_value_head_dim,
        "linear_conv_kernel_dim": m.linear_conv,
        "linear_sigmoid_gate_scale": m.linear_gate_scale,
        "linear_attn_o_norm_eps": m.linear_norm_eps,
        "intermediate_size": m.intermediate_size,
        "moe_intermediate_size": m.moe_intermediate_size,
        "n_shared_experts": m.n_shared_experts,
        "n_routed_experts": m.n_held_experts,
        "ep": {"size": m.n_routed_experts // m.n_held_experts,
               "rank": m.ep_rank, "router_outputs": m.n_routed_experts},
        "num_experts_per_tok": m.experts_per_tok, "norm_topk_prob": True,
        "routed_scaling_factor": m.routed_scaling_factor,
        "swiglu_limit": m.swiglu_limit, "rope_theta": m.rope_theta,
        "rope_scaling": {"factor": m.rope_factor,
                         "original_max_position_embeddings":
                         m.rope_original_max,
                         "beta_fast": m.rope_beta_fast,
                         "beta_slow": m.rope_beta_slow,
                         "mscale": m.rope_mscale,
                         "mscale_all_dim": m.rope_mscale_all_dim},
        "rms_norm_eps": m.rms_eps, "vocab_size": m.vocab_size,
        "max_position_embeddings": m.max_len, "route_eps": route_eps}


def flat_weights(variables) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]:
        out["/".join(str(p.key) for p in path)] = leaf
    return out


def seeded(m: gc.GigaChatModule, seed: int = 0):
    """Weights of unit gain, so that every sublayer moves the residual
    by about its own size and every leaf carries signal: kernels normal
    / sqrt(fan-in), the convolution's taps 0.5, the zero-centred norms'
    w and the latents' scales off their centres by 0.1; the recurrence
    keeps the module's own long-memory A_log and step bias."""
    variables = m.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4096))

    def stir(path, leaf):
        names, key = [p.key for p in path], next(noise)
        if names[-2:] in (["a_log", "kernel"], ["dt", "bias"]):
            return leaf
        if names[-1] == "scale":
            new = leaf + 0.1 * jax.random.normal(key, leaf.shape)
        elif names[-2:] == ["conv", "kernel"]:
            new = 0.5 * jax.random.normal(key, leaf.shape)
        elif names[-1] == "embedding":
            new = 0.5 * jax.random.normal(key, leaf.shape)
        else:
            new = jax.random.normal(key, leaf.shape) \
                / np.sqrt(leaf.shape[-2])
        return new.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(stir, variables)


PUBLISHED = gc.GigaChatModule(
    vocab_size=16032, max_len=4096, hidden=7168, layers=5, first_dense=1,
    full_attention_layers=(3,), heads=64, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, linear_key_heads=32, linear_value_heads=64,
    intermediate_size=18432, moe_intermediate_size=2048,
    n_routed_experts=256, n_held_experts=16, experts_per_tok=8)


# ------------------------------------------------- tapping the engine

class _Tapped:
    """A module whose family's decode program also hands its logits to
    `sink(logits [S, V], pos [S], active [S])`, in dispatch order; each
    engine built on it gets a sink of its own (`family.sink`)."""

    def __init__(self, module):
        self.module = module

    def serve_family(self):
        m, sink = self.module, _Sink()

        class Family(gc.GigaChatServeFamily):
            def decode_step(self, kv_dtype, attn_impl, attn_interpret):
                self._check(kv_dtype, attn_impl)
                logits_of = gc.build_decode_logits(m, attn_impl,
                                                   attn_interpret)

                def step(params, c, st, cv, tokens, pos, tables, wp, wo,
                         active, temps, key_data, cs, cd, poison):
                    logits, counts, *cache = logits_of(
                        params, c, st, cv, tokens, pos, tables, wp, wo,
                        active, cs, cd)
                    jax.debug.callback(sink, logits, pos, active,
                                       ordered=True)
                    nxt, bad = sample_tokens(logits, active, temps,
                                             key_data, poison, gc.PAD_ID)
                    return (jnp.concatenate([nxt, counts]), bad, *cache)

                return step

        fam = Family(m)
        fam.sink = sink
        return fam


class _Sink:
    """{(slot, pos): logits row} of every active lane-step, the last
    write winning."""

    def __init__(self):
        self.rows = {}

    def __call__(self, logits, pos, active):
        logits, pos = np.asarray(logits), np.asarray(pos)
        for s in np.nonzero(np.asarray(active) > 0)[0]:
            self.rows[(int(s), int(pos[s]))] = logits[s].copy()

    def served(self, slot, req):
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
    eng = DecodeEngine(_Tapped(m), variables, slots=slots, page=PAGE,
                       prefill_chunk=chunk, **kw)
    where = [eng.attach(r) for r in requests]
    _finish(eng)
    assert all(r.outcome == "ok" for r in requests)
    return eng, [eng.family.sink.served(s, r)
                 for s, r in zip(where, requests)]


def _reference_logits(ref, m, variables, req):
    ids = list(req.prompt) + list(req.tokens)
    positions = np.arange(len(req.prompt) - 1, len(ids) - 1)
    return ref.logits(flat_weights(variables), cfg_of(m), ids, positions)


# What differs between the program and the reference in float32 is the
# order of the sums alone (the convolution as a stack of shifted rows,
# XLA's matmuls against `highest`, the absorbed latent attention):
# measured 4.3e-6 of the largest logit (2.0e-6 with the three kernels in
# interpret mode); 2e-5 holds with room, and each fault below moves a
# logit by a hundred times that and more.
F32_RTOL = 2e-5
# In bfloat16 every matmul's input is rounded to 8 bits of mantissa
# through 5 layers: most rows within 4e-2 of the largest logit, as
# Jamba's and LongCat's; a router near-tie flips a row now and then.
BF16_RTOL = 4e-2


def _off(got, want):
    """Largest distance of two sets of logit rows over the largest
    logit (id 0 left out: never emitted)."""
    return np.abs(got[:, 1:] - want[:, 1:]).max() / np.abs(want[:, 1:]).max()


# ------------------------------------------------------------ the files

def test_module_and_reference_name_the_same_leaves(ref):
    for m in (gc.GigaChatModule(), PUBLISHED):
        spec = ref.weight_spec(cfg_of(m))
        shapes = {"params/" + k: v for k, v in m.param_shapes().items()}
        assert {k: tuple(s) for k, (s, _d) in spec.items()} == shapes
        assert all(d == jnp.bfloat16 for _s, d in spec.values())
        # lib/weights.py has rules for these leaf names and no others
        assert {k.rsplit("/", 1)[1] for k in shapes} \
            == {"kernel", "embedding", "scale", "bias"}
    assert PUBLISHED.attn_layers == (3,)
    assert PUBLISHED.linear_layers == (0, 1, 2, 4)
    shapes = jax.eval_shape(lambda: PUBLISHED.init(jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    # ISSUE 41's count: the dense layer 632.2M, GDN MoE layers 986.4M,
    # the MLA MoE layer 910.4M, embedding and head 229.8M: 4.73B
    by = {}
    for path, shape in PUBLISHED.param_shapes().items():
        top = path.split("/")[0]
        by[top] = by.get(top, 0) + int(np.prod(shape))
    assert abs(by["layer_0"] - 632.2e6) < 0.1e6
    assert abs(by["layer_1"] - 986.4e6) < 0.1e6
    assert abs(by["layer_3"] - 910.4e6) < 0.1e6
    assert abs(by["embed"] + by["head"] - 229.8e6) < 0.1e6
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 4_731.6e6) < 1e6, n
    cache = PUBLISHED.serve_family().cache
    assert (cache.layers, cache.planes, cache.lanes, cache.width) \
        == (1, 1, 576, 640)
    assert [(st.name, st.layers, st.shape) for st in cache.slot_state] \
        == [("gdn", 4, (64, 128, 128)), ("gdn_conv", 4, (3 * 16384,))]
    # four states of 4.19 MB and the convolutions' tails: 17.2 MB a slot
    assert cache.slot_state[0].slot_bytes() == 4 * 4_194_304
    assert abs(cache.slot_state_bytes - 17.17e6) < 0.01e6


def test_a_module_that_is_no_share_is_refused():
    with pytest.raises(ValueError, match="whole fraction"):
        gc.GigaChatModule(n_held_experts=5)
    with pytest.raises(ValueError, match="whole fraction"):
        gc.GigaChatModule(ep_rank=4)
    with pytest.raises(ValueError, match="does not divide"):
        gc.GigaChatModule(linear_value_heads=3)
    with pytest.raises(ValueError, match="no layer of one kind"):
        gc.GigaChatModule(full_attention_layers=())


# --------------------------------------------------- the kernels alone

def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _operands(rng, n, heads, decay, dk=128, dv=128, corr=0.0,
              beta=(0.01, 0.99)):
    """q (scaled), k unit length, v, a log decay g whose exp(g) lies in
    `decay`, beta across `beta`. corr > 0 draws every token's key of a
    head near one shared direction (k . k' about corr^2 / (corr^2 + (1 -
    corr)^2)): with beta and decay near 1, what gives (I + A)^-1 its
    largest entries."""
    lo, hi = decay
    q = _unit(rng.normal(size=(n, heads, dk))) * dk ** -0.5
    k = rng.normal(size=(n, heads, dk))
    if corr:
        k = corr * rng.normal(size=(1, heads, dk)) + (1 - corr) * k
    return (jnp.asarray(q, jnp.float32),
            jnp.asarray(_unit(k), jnp.float32),
            jnp.asarray(rng.normal(size=(n, heads, dv)), jnp.float32),
            jnp.asarray(np.log(rng.uniform(lo, hi, size=(n, heads))),
                        jnp.float32),
            jnp.asarray(rng.uniform(*beta, size=(n, heads)), jnp.float32))


def _recurrence(s, q, k, v, g, beta):
    """The rule token by token in numpy float64: (state, o [T, H, dv])."""
    s = np.asarray(s, np.float64).copy()
    outs = []
    for t in range(q.shape[0]):
        s *= np.exp(np.asarray(g[t], np.float64))[:, None, None]
        kt, qt = np.asarray(k[t], np.float64), np.asarray(q[t], np.float64)
        u = np.asarray(beta[t], np.float64)[:, None] * (
            np.asarray(v[t], np.float64) - np.einsum("hk,hkv->hv", kt, s))
        s += kt[:, :, None] * u[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", qt, s))
    return s, np.stack(outs)


# the chunked kernel's tolerance: the same float32 rule in another order
# of sums (a block's blocked inverse, its products at `highest`) against
# float64 token by token: measured 4.1e-7 of the largest output or state
# entry over the three cases of independent keys, 1.9e-6 over correlated
# keys written with beta near 1 (a 64-step substitution read 1.7e-6
# there: float32's own rounding of that recurrence); 4e-6 holds, and a
# state rounded to bfloat16 once is off by 4e-3
KERNEL_RTOL = 4e-6


@pytest.mark.parametrize("tokens,valid,decay,corr", [
    (128, 100, (0.9, 0.999), 0.0),    # ends mid-block, long memory
    (64, 64, (0.999, 1.0), 0.0),      # decays near 1: one whole block
    (192, 131, (1e-4, 0.05), 0.0),    # near 0: a block forgets itself
    (256, 250, (0.999, 1.0), 0.9)],   # correlated keys, 4 blocks
    ids=["mid-block", "near-1", "near-0", "correlated"])
def test_prefill_kernel_is_the_token_by_token_recurrence(tokens, valid,
                                                         decay, corr):
    """The chunked kernel (interpret mode) from a slot's state over a
    chunk whose tail is padding (g = 0, beta = 0): the state it writes
    back and every real row's output against the rule token by token;
    no other slot or layer of the array is touched. The correlated case
    writes with beta near 1 along keys near one direction, where the
    blocks' (I + A)^-1 have their largest entries."""
    rng = np.random.default_rng(tokens)
    L, S, H = 2, 4, 4
    state = jnp.asarray(0.1 * rng.normal(size=(L, S, H, 128, 128)),
                        jnp.float32)
    q, k, v, g, beta = _operands(rng, tokens, H, decay, corr=corr,
                                 beta=(0.9, 1.0) if corr else (0.01, 0.99))
    live = (np.arange(tokens) < valid)[:, None]
    g, beta = jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)
    for fresh in (0, 1):
        s0 = np.zeros((H, 128, 128)) if fresh else np.asarray(state[1, 2])
        want_s, want_o = _recurrence(s0, q[:valid], k[:valid], v[:valid],
                                     g[:valid], beta[:valid])
        got, o = jax.block_until_ready(gd.gated_delta_prefill(
            state, q, k, v, g, beta, jnp.int32(fresh), layer=1, slot=2,
            impl="pallas", interpret=True))
        scale = np.abs(want_o).max()
        np.testing.assert_allclose(np.asarray(o[:valid]), want_o, rtol=0,
                                   atol=KERNEL_RTOL * scale)
        np.testing.assert_allclose(np.asarray(got[1, 2]), want_s, rtol=0,
                                   atol=KERNEL_RTOL * np.abs(want_s).max())
        others = np.ones((L, S), bool)
        others[1, 2] = False
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(state)[others])


@pytest.mark.parametrize("corr,decay,beta", [
    (0.0, (0.9, 0.999), (0.01, 0.99)),
    (0.9, (0.999, 1.0), (0.95, 1.0)),
    (0.99, (0.999, 1.0), (0.99, 1.0))],
    ids=["independent", "correlated", "nearly-parallel"])
def test_unit_lower_inverse_against_numpy(corr, decay, beta):
    """The chunked kernel's blocked inverse, as plain JAX over a batch of
    blocks built as the kernel builds them (A[t, s] = beta_t exp(gamma_t
    - gamma_s) k_t . k_s, s < t), against numpy's inverse of I + A in
    float64; the last block's tail is padding (beta = 0, no decay), and
    its rows come back exactly rows of I."""
    rng = np.random.default_rng(11)
    n, bt, pad = 8, gd.BLOCK, 24
    _q, k, _v, g, b = _operands(rng, n * bt, 1, decay, corr=corr,
                                beta=beta)
    k = np.asarray(k, np.float32).reshape(n, bt, -1)
    g, b = (np.array(x, np.float32).reshape(n, bt) for x in (g, b))
    g[-1, -pad:], b[-1, -pad:] = 0.0, 0.0
    gamma = np.cumsum(g, axis=1)
    a = np.tril(b[:, :, None] * np.exp(gamma[:, :, None] - gamma[:, None, :])
                * np.einsum("ntd,nsd->nts", k, k), -1).astype(np.float32)
    blk = np.arange(bt) // gd.SUB
    same = blk[:, None] == blk[None, :]
    got = np.asarray(gd.unit_lower_inverse(
        jnp.asarray(np.where(same, 0.0, a)),
        jnp.asarray(np.swapaxes(np.where(same, a, 0.0), 1, 2))))
    want = np.linalg.inv(np.eye(bt) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=KERNEL_RTOL * np.abs(want).max())
    np.testing.assert_array_equal(got[-1, -pad:], np.eye(bt)[-pad:])


def test_decode_kernel_is_one_step_of_the_recurrence():
    """The decode kernel (interpret mode): every slot one step, a fresh
    slot from zeros, an inactive lane (g = 0, beta = 0) left as it was,
    the other layer untouched."""
    rng = np.random.default_rng(5)
    L, S, H = 2, 8, 4
    state = jnp.asarray(0.3 * rng.normal(size=(L, S, H, 128, 128)),
                        jnp.float32)
    q, k, v, g, beta = _operands(rng, S, H, (0.2, 0.999))
    active = np.ones(S, bool)
    active[5] = False
    g, beta = (jnp.where(active[:, None], x, 0.0) for x in (g, beta))
    fresh = np.zeros(S, np.int32)
    fresh[[1, 6]] = 1
    got, o = jax.block_until_ready(gd.gated_delta_decode(
        state, q, k, v, g, beta, jnp.asarray(fresh), layer=0,
        impl="pallas", interpret=True))
    for s in range(S):
        s0 = np.zeros((H, 128, 128)) if fresh[s] else np.asarray(state[0, s])
        want_s, want_o = _recurrence(s0, q[s:s + 1], k[s:s + 1],
                                     v[s:s + 1], g[s:s + 1],
                                     beta[s:s + 1])
        np.testing.assert_allclose(np.asarray(o[s]), want_o[0], rtol=0,
                                   atol=1e-6 * np.abs(want_o).max())
        np.testing.assert_allclose(np.asarray(got[0, s]), want_s, rtol=0,
                                   atol=1e-6 * np.abs(want_s).max())
    np.testing.assert_array_equal(np.asarray(got[0, 5]),
                                  np.asarray(state[0, 5]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(state[1]))
    plain = gd.gated_delta_decode(state, q, k, v, g, beta,
                                  jnp.asarray(fresh), layer=0, impl="gather")
    np.testing.assert_allclose(np.asarray(plain[1]), np.asarray(o), rtol=0,
                               atol=1e-6)


# --------------------------------------- the engine against the reference

# prompts of 1 token (no prefill chunk), 2 and 3 (a chunk of one token,
# of two), 41 (mid-chunk), 33 (ends on a chunk boundary) and 32
PROMPTS = (1, 2, 3, 41, 33, 32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_engine_prefill_then_decode_against_the_reference(ref, dtype):
    """Chunked prefill then decode through DecodeEngine, six streams in
    one batch, one dispatch ahead: the logits of every served position
    against the reference's full forward of prompt + served tokens; the
    step's four counts in engine.stats."""
    m = gc.GigaChatModule(dtype=getattr(jnp, dtype))
    variables = seeded(m)
    rng = np.random.default_rng(7)
    reqs = [_request(rng, m, n, n_new=10) for n in PROMPTS]
    eng, served = _serve(m, variables, reqs, slots=len(reqs))
    assert eng.stats["ahead_dispatches"] > 0
    assert eng.stats["prefill_dispatches"] == sum(
        -(-(n - 1) // CHUNK) for n in PROMPTS)
    occ = eng.stats["occupancy_sum"]
    assert eng.stats["gdn_lane_updates"] == occ
    assert eng.stats["slot_state_bytes"] \
        == occ * 2 * eng.family.cache.slot_state_bytes
    assert eng.stats["moe_assignments"] == 4 * m.experts_per_tok * occ
    # a quarter of the choices are held here (4 of 16)
    local = eng.stats["moe_local_assignments"] / eng.stats["moe_assignments"]
    assert 0.1 < local < 0.45, local
    if dtype == "float32":
        for r, got in zip(reqs, served):
            assert _off(got, _reference_logits(ref, m, variables, r)) \
                < F32_RTOL
        return
    # bfloat16 rounding flips a top-4-of-16 near-tie now and then: most
    # rows agree to bfloat16 rounding, and the benchmark's own statistic
    # (near-ties evaluated both ways) stays small
    w, cfg = flat_weights(variables), cfg_of(m, route_eps=0.2)
    off, gaps = [], []
    for r, got in zip(reqs, served):
        want = _reference_logits(ref, m, variables, r)
        off.extend(np.abs(got[:, 1:] - want[:, 1:]).max(-1)
                   / np.abs(want[:, 1:]).max())
        gaps.extend(ref.served_gaps(w, cfg, r.prompt, r.tokens)["gaps"])
    assert np.median(off) < BF16_RTOL \
        and np.mean(np.asarray(off) < BF16_RTOL) > 0.8
    assert np.mean(gaps) < 0.01 and np.max(gaps) < 0.5


@pytest.mark.parametrize("fault", ["bf16_state", "gdn_gate_dropped",
                                   "attn_gate_dropped", "conv_tail_dropped"])
def test_a_planted_fault_fails_the_float32_comparison(ref, fault,
                                                      monkeypatch):
    """The comparison above has the power it claims: a recurrent state
    kept in bfloat16, the linear layers' output gate or the MLA layer's
    gate left out, or a convolution tail not handed from chunk to chunk
    each move a served logit by more than a hundred times the float32
    tolerance."""
    m = gc.GigaChatModule(dtype=jnp.float32)
    variables = seeded(m)
    if fault == "bf16_state":
        for name in ("gated_delta_decode", "gated_delta_prefill"):
            real = getattr(gd, name)

            def through_bf16(state, *a, real=real, **kw):
                new, o = real(state, *a, **kw)
                return new.astype(jnp.bfloat16).astype(jnp.float32), o

            monkeypatch.setattr(gd, name, through_bf16)
    elif fault == "gdn_gate_dropped":
        monkeypatch.setattr(gc, "_output_gate",
                            lambda m_, z: jnp.ones_like(z))
    elif fault == "attn_gate_dropped":
        m = dataclasses.replace(m, gated_attention=False)
    else:
        real = gc._gdn

        def forgetful(m_, i, p, h, state, conv, *, batched, **kw):
            if not batched:
                conv = jnp.zeros_like(conv)
            return real(m_, i, p, h, state, conv, batched=batched, **kw)

        monkeypatch.setattr(gc, "_gdn", forgetful)
    rng = np.random.default_rng(7)
    req = _request(rng, m, 41, n_new=4)
    _eng, (got,) = _serve(m, variables, [req])
    monkeypatch.undo()
    want = _reference_logits(ref, gc.GigaChatModule(dtype=jnp.float32),
                             seeded(gc.GigaChatModule(dtype=jnp.float32)),
                             req)
    assert _off(got, want) > 100 * F32_RTOL, _off(got, want)


# --------------------------------------------------------- bit identity

SPECS = [(41, 7, 0.0, 0), (3, 9, 0.9, 1), (33, 5, 1.3, 7), (20, 8, 0.7, 3)]


@pytest.fixture(scope="module")
def tiny():
    m = gc.GigaChatModule()
    return m, seeded(m)


@pytest.fixture(scope="module")
def tiny32():
    """In float32, where a stream's logits are held to F32_RTOL."""
    m = gc.GigaChatModule(dtype=jnp.float32)
    return m, seeded(m)


def _case(m, variables, specs, **kw):
    rng = np.random.default_rng(9)
    reqs = [_request(rng, m, n, n_new, temp, seed)
            for n, n_new, temp, seed in specs]
    eng, served = _serve(m, variables, reqs, **kw)
    return eng, reqs, served


def test_a_reused_slot_gives_the_second_stream_as_if_alone(tiny):
    """Slot 0 serves stream A and then stream B: B's logits are the
    bits B gives alone in a fresh engine. Nothing zeroes the slot's
    states on the host: B's first position is 0, and the program starts
    from zeros there. B is tried with a chunked prompt (the prefill
    program resets) and with one token (the decode program does)."""
    m, variables = tiny
    rng = np.random.default_rng(13)
    for n_b in (29, 1):
        a = _request(rng, m, 37, n_new=6, temp=0.8, seed=2)
        b = _request(rng, m, n_b, n_new=6, temp=0.8, seed=4)
        twin = GenerateRequest(list(b.prompt), max_new_tokens=6,
                               temperature=0.8, seed=4)
        eng = DecodeEngine(_Tapped(m), variables, slots=1, page=PAGE,
                           prefill_chunk=CHUNK)
        assert eng.attach(a) == 0
        _finish(eng)
        assert float(jnp.abs(eng.slab.state[1]).max()) > 0   # A's state
        assert eng.attach(b) == 0
        _finish(eng)
        _e, (alone,) = _serve(m, variables, [twin], slots=1)
        assert b.tokens == twin.tokens
        np.testing.assert_array_equal(eng.family.sink.served(0, b), alone)


def test_token_by_token_prefill_against_the_reference(ref, tiny32):
    """prefill_chunk 0: every prompt position rides the decode program,
    which starts from zeros at position 0. Another order of sums than
    the chunked path's, so the streams are compared by their logits:
    each against the reference's forward of its own tokens."""
    m, variables = tiny32
    _e, reqs, _lg = _case(m, variables, SPECS)
    rng = np.random.default_rng(9)
    again = [_request(rng, m, *spec) for spec in SPECS]
    eng, served = _serve(m, variables, again, chunk=0)
    assert eng.stats["prefill_dispatches"] == 0
    for r, got in zip(again, served):
        assert _off(got, _reference_logits(ref, m, variables, r)) \
            < F32_RTOL
    assert [r.tokens for r in again] == [r.tokens for r in reqs]


def test_a_resumed_stream_re_prefills_from_zero_state(ref, tiny32,
                                                      monkeypatch):
    """The replica is replaced mid-stream (a wedged loop, the watchdog,
    spawn_recovered: tests/test_serve_faults.py's way): the resumed
    streams re-prefill prompt + emitted tokens from position 0 into the
    new engine's zeroed states and finish; every served logit row, from
    whichever engine served it, agrees with the reference's forward of
    the stream's own tokens."""
    from kubeml_tpu.faults import ServeFaultPlan
    from kubeml_tpu.serve.service import ServeService
    m, variables = tiny32
    rng = np.random.default_rng(9)
    clean = [_request(rng, m, *spec) for spec in SPECS]
    where = []
    real_attach = DecodeEngine.attach

    def attach(self, req):
        slot = real_attach(self, req)
        where.append((self.family.sink, req, slot, len(req.tokens)))
        return slot

    monkeypatch.setattr(DecodeEngine, "attach", attach)
    plan = ServeFaultPlan.parse([{"kind": "serve_loop_wedge", "step": 6}])
    engine = DecodeEngine(_Tapped(m), variables, slots=4, page=PAGE,
                          prefill_chunk=CHUNK, fault_plan=plan)
    svc = ServeService("gigachat-wedge", engine, wedge_timeout_s=0.2,
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
    for r in reqs:
        # the rows of each position from the engine that served it: the
        # last attach at or before the position's emitted count
        rows, n = [], len(r.prompt)
        stints = [(sink, slot, had) for sink, q, slot, had in where
                  if q is r]
        resumed += len(stints) > 1
        for j in range(len(r.tokens)):
            sink, slot, _had = [s for s in stints if s[2] <= j][-1]
            rows.append(sink.rows[(slot, n - 1 + j)])
        want = _reference_logits(ref, m, variables, r)
        assert _off(np.stack(rows), want) < F32_RTOL
    assert resumed >= 1


# ----------------------------------------------- the expert layer's share

@pytest.mark.parametrize("tokens,impl,interpret", [
    (48, "auto", False), (80, "auto", False), (80, "pallas", True)],
    ids=["48", "80", "80-kernel"])
def test_the_shares_sum_to_the_uncut_layer(ref, tokens, impl, interpret):
    """At a small size: the routed parts of all four shares, with the
    shared expert counted once, are the uncut reference's expert layer,
    and the program's layer on each share (the dense mask at 48 tokens,
    ragged_dot at 80, and at 80 the grouped-matmul kernel through the
    interpreter) is the reference's on that share."""
    uncut = gc.GigaChatModule(dtype=jnp.float32, n_held_experts=16)
    cfg_all = cfg_of(uncut)
    assert cfg_all["ep"]["size"] == 1
    lw = ref._layer_weights(flat_weights(seeded(uncut, seed=4)), 1)
    x = jnp.asarray(np.random.default_rng(9).normal(
        size=(tokens, uncut.hidden)).astype(np.float32))
    whole, chosen = ref.moe_layer(x, lw, cfg_all)
    shared = np.asarray(ref._gated(x, lw, "shared", "f32",
                                   uncut.swiglu_limit))
    total, pairs = np.zeros_like(shared), 0
    for rank in range(4):
        share = dataclasses.replace(uncut, n_held_experts=4, ep_rank=rank)
        lw_r = dict(lw)
        for name in ("gate", "up", "down"):
            key = f"experts/{name}/kernel"
            lw_r[key] = lw[key][4 * rank:4 * rank + 4]
        part, r = ref.moe_layer(x, lw_r, cfg_of(share))
        np.testing.assert_array_equal(r["experts"], chosen["experts"])
        total += np.asarray(part) - shared
        p = {"router": {"kernel": lw["router/kernel"]},
             "shared": {n: {"kernel": lw[f"shared/{n}/kernel"]}
                        for n in ("gate", "up", "down")},
             "experts": {n: {"kernel": lw_r[f"experts/{n}/kernel"]}
                         for n in ("gate", "up", "down")}}
        bias = jnp.zeros((share.n_routed_experts,), jnp.float32)
        # one program, read before anything else is dispatched: the
        # interpreter's callbacks run JAX operations of their own
        got, counts = jax.block_until_ready(jax.jit(
            lambda x, p, live: gc.held_expert_layer(
                x, p, live, lambda lg: gc.route(share, lg, bias), held=4,
                rank=share.ep_rank, scaling=share.routed_scaling_factor,
                dtype=share.dtype, dense=tokens <= gc.DENSE_MOE_TOKENS,
                impl=impl, interpret=interpret,
                limit=share.swiglu_limit))(x, p, jnp.ones(tokens)))
        assert share.serve_family().moe_impl(tokens, impl, interpret) == (
            "dense" if tokens == 48 else "pallas" if interpret else "gather")
        np.testing.assert_allclose(np.asarray(got), np.asarray(part),
                                   atol=2e-5, rtol=0)
        assert int(counts[0]) == tokens * share.experts_per_tok
        pairs += int(counts[1])
    # every pair is held by exactly one share
    assert pairs == tokens * uncut.experts_per_tok
    np.testing.assert_allclose(total + shared, np.asarray(whole), atol=2e-5,
                               rtol=0)


def test_the_swiglu_clamp_bites():
    """Inputs large enough that gate and up pass `swiglu_limit`: the
    clamped MLP is (silu(min(g, 10)) * clip(u, -10, 10)) W_down, it
    differs from the unclamped one, and no limit is the bare SwiGLU; the
    grouped kernel (interpret mode) clamps alike."""
    from kubeml_tpu.models.base import gated_mlp
    from kubeml_tpu.ops.pallas.grouped_matmul import grouped_mlp
    rng = np.random.default_rng(3)
    d, f = 128, 128
    x = jnp.asarray(rng.normal(size=(16, d)) * 4, jnp.float32)
    p = {n: {"kernel": jnp.asarray(rng.normal(size=s) / np.sqrt(s[0]) * 4,
                                   jnp.float32)}
         for n, s in (("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))}
    g = np.asarray(x @ p["gate"]["kernel"])
    u = np.asarray(x @ p["up"]["kernel"])
    assert (g > 10).mean() > 0.05 and (np.abs(u) > 10).mean() > 0.05
    gc_ = np.minimum(g, 10.0)
    want = (gc_ / (1 + np.exp(-gc_)) * np.clip(u, -10, 10)) \
        @ np.asarray(p["down"]["kernel"])
    got = np.asarray(gated_mlp(x, p, 10.0))
    bare = np.asarray(gated_mlp(x, p))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert np.abs(got - bare).max() > 1.0
    np.testing.assert_allclose(
        bare, (g / (1 + np.exp(-g)) * u) @ np.asarray(p["down"]["kernel"]),
        rtol=1e-4, atol=1e-3)
    stack = {n: p[n]["kernel"][None].astype(jnp.bfloat16)
             for n in ("gate", "up", "down")}
    rows = x.astype(jnp.bfloat16)
    sizes = jnp.asarray([16], jnp.int32)
    kernel = np.asarray(grouped_mlp(rows, stack["gate"], stack["up"],
                                    stack["down"], sizes, impl="pallas",
                                    interpret=True, limit=10.0))
    plain = np.asarray(grouped_mlp(rows, stack["gate"], stack["up"],
                                   stack["down"], sizes, impl="gather",
                                   limit=10.0))
    np.testing.assert_allclose(kernel, plain, rtol=2e-2, atol=2e-1)
    assert np.abs(kernel - np.asarray(grouped_mlp(
        rows, stack["gate"], stack["up"], stack["down"], sizes,
        impl="gather"))).max() > 1.0


# --------------------------------------------------------- the engine

def test_optional_programs_and_int8_pages_are_refused_by_name():
    m = gc.GigaChatModule()
    variables = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="'gigachat' provides no multi-step"):
        DecodeEngine(m, variables, slots=2, page=PAGE, decode_steps=4)
    with pytest.raises(ValueError, match="no int8"):
        DecodeEngine(m, variables, slots=2, page=PAGE, kv_dtype="int8")
    with pytest.raises(ValueError, match="attn_impl"):
        DecodeEngine(m, variables, slots=2, page=PAGE, attn_impl="flash")
    eng = DecodeEngine(m, variables, slots=2, page=PAGE, prefill_chunk=16,
                       prefix_cache=True)
    assert eng.prefix_cache is False


def test_the_engine_names_nothing_of_the_family():
    with open(os.path.join(REPO, "kubeml_tpu", "serve", "engine.py")) as f:
        source = f.read()
    assert "gigachat" not in source.lower() and "gdn" not in source


def test_engine_takes_the_kernels_in_interpret_mode(ref):
    """attn_impl 'pallas' in interpret mode, 8 slots and chunks of 64:
    every linear layer's decode step goes through the decode kernel,
    every chunk through the chunked one, the MLA layer's decode read
    through the latent-page kernel, and the streams match the
    reference."""
    m = gc.GigaChatModule(dtype=jnp.float32)
    variables = seeded(m, seed=9)
    fam = m.serve_family()
    assert fam.gdn_impls(8, 64, "pallas", True) == ("pallas", "pallas")
    assert fam.gdn_impls(8, 64, "auto", False) == ("gather", "gather")
    rng = np.random.default_rng(21)
    reqs = [_request(rng, m, n, n_new=4) for n in (3, 100)]
    eng, served = _serve(m, variables, reqs, slots=8, chunk=64,
                         attn_impl="pallas", attn_interpret=True)
    assert eng.stats["attn_impl_decode"] == "pallas"
    for r, got in zip(reqs, served):
        assert _off(got, _reference_logits(ref, m, variables, r)) < F32_RTOL
