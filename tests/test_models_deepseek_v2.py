"""The DeepSeek-V2 serving family (models/deepseek_v2.py) against its
plain float32 reference (tests/helpers/ref_deepseek_v2.py, the same
file as benchmark/refs/deepseek_v2.py), at a small size on the CPU:
prefill then decode through the paged latent cache, the routing, the
absorbed form, the four shares' sum, /generate end to end, and the
bfloat16 checkpoint.
"""

import dataclasses
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models import deepseek_v2 as ds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def ref():
    """The tier-1 copy of the reference. It imports benchmark.refs.quant
    (the int8 control) by that name, as the benchmark's copy does."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "ref_deepseek_v2",
        os.path.join(REPO, "tests", "helpers", "ref_deepseek_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cfg_of(m: ds.DeepSeekV2Module) -> dict:
    """The reference's configuration (published keys) of a module."""
    return {
        "hidden_size": m.hidden, "num_attention_heads": m.heads,
        "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "intermediate_size": m.intermediate_size,
        "moe_intermediate_size": m.moe_intermediate_size,
        "n_shared_experts": m.n_shared_experts,
        "n_routed_experts": m.n_held_experts,
        "ep": {"size": m.n_routed_experts // m.n_held_experts,
               "rank": m.ep_rank, "router_outputs": m.n_routed_experts},
        "n_group": m.n_group, "topk_group": m.topk_group,
        "num_experts_per_tok": m.experts_per_tok,
        "routed_scaling_factor": m.routed_scaling_factor,
        "num_hidden_layers": m.layers, "first_k_dense_replace": m.first_dense,
        "vocab_size": m.vocab_size, "max_position_embeddings": m.max_len,
        "rope_theta": m.rope_theta, "rms_norm_eps": m.rms_eps,
        "rope_scaling": {
            "beta_fast": m.rope_beta_fast, "beta_slow": m.rope_beta_slow,
            "factor": m.rope_factor, "mscale": m.rope_mscale,
            "mscale_all_dim": m.rope_mscale_all_dim,
            "original_max_position_embeddings": m.rope_original_max,
            "type": "yarn"}}


def flat_weights(variables) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0]:
        out["/".join(str(p.key) for p in path)] = leaf
    return out


def seeded(m: ds.DeepSeekV2Module, seed: int = 0):
    """Seeded random weights with every leaf carrying signal (norm
    scales off 1), as a trained checkpoint has."""
    variables = m.init(jax.random.PRNGKey(seed))
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4096))

    def stir(path, leaf):
        if path[-1].key != "scale":
            return leaf
        return (1.0 + 0.1 * jax.random.normal(next(noise), leaf.shape)
                ).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(stir, variables)


PUBLISHED = ds.DeepSeekV2Module(
    vocab_size=25600, max_len=4096, hidden=5120, layers=5, heads=128,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, intermediate_size=12288,
    moe_intermediate_size=1536, n_routed_experts=160, n_held_experts=40,
    n_group=8, topk_group=3, experts_per_tok=6)


def test_the_two_copies_of_the_reference_are_one_file():
    with open(os.path.join(REPO, "benchmark", "refs",
                           "deepseek_v2.py"), "rb") as f:
        bench = f.read()
    with open(os.path.join(REPO, "tests", "helpers",
                           "ref_deepseek_v2.py"), "rb") as f:
        assert f.read() == bench


def test_module_and_reference_name_the_same_leaves(ref):
    for m in (ds.DeepSeekV2Module(), PUBLISHED):
        spec = ref.weight_spec(cfg_of(m))
        shapes = {"params/" + k: v for k, v in m.param_shapes().items()}
        assert {k: tuple(s) for k, (s, _d) in spec.items()} == shapes
        assert all(d == jnp.bfloat16 for _s, d in spec.values())
        # lib/weights.py has rules for these leaf names and no others
        assert {k.rsplit("/", 1)[1] for k in shapes} \
            == {"kernel", "embedding", "scale"}
    shapes = jax.eval_shape(lambda: PUBLISHED.init(jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert {str(a.dtype) for a in leaves} == {"bfloat16"}
    # ISSUE 27's count of the share: 5,163.8M parameters, 10.33 GB
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert abs(n - 5_163.9e6) < 0.2e6


def test_yarn_frequencies_and_scale_against_numbers_worked_by_hand(ref):
    """Published keys: theta 10000, factor 40, original 4096, beta 32/1
    on 64 dims. 64 ln(4096 / (2 pi b)) / (2 ln 10000) is 10.47 for b =
    32 and 22.51 for b = 1, so lo, hi = 10, 23: frequencies 0..10 are
    kept, 23..31 divided by 40, and between them the ramp (i - 10) / 13
    of the division applies. m = 0.1 * 0.707 * ln 40 + 1 = 1.26081 and
    scale = 192^-0.5 * m^2 = 0.114722."""
    want = 10000.0 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    want = want / 40.0 * ramp + want * (1 - ramp)
    for got in (ds.yarn_inv_freq(PUBLISHED),
                ref.yarn_inv_freq(cfg_of(PUBLISHED))):
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert got[0] == 1.0 and got[10] == np.float32(want[10])
        np.testing.assert_allclose(got[31], 10000.0 ** (-31 / 32) / 40,
                                   rtol=1e-6)
        np.testing.assert_allclose(got[16], want[16], rtol=1e-6)
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.26081) < 1e-5
    for got in (ds.softmax_scale(PUBLISHED),
                ref.softmax_scale(cfg_of(PUBLISHED))):
        assert abs(got - 192 ** -0.5 * m * m) < 1e-9
        assert abs(got - 0.114722) < 1e-6
    assert ref.rope_mscale(cfg_of(PUBLISHED)) == 1.0


def test_group_limited_greedy_against_the_reference_ties_included(ref):
    m = ds.DeepSeekV2Module(n_routed_experts=32, n_held_experts=8,
                            n_group=8, topk_group=3, experts_per_tok=6)
    rng = np.random.default_rng(3)
    logits = rng.normal(size=(256, 32)).astype(np.float32)
    # ties: whole rows of few distinct values, equal groups, and a row
    # of all-equal logits
    logits[:64] = np.round(logits[:64])
    logits[64:72] = np.tile(rng.normal(size=(8, 4)), (1, 8))
    logits[72] = 0.5
    experts, scores = jax.jit(lambda x: ds.route(m, x))(logits)
    want = ref.route(logits, cfg_of(m))
    np.testing.assert_array_equal(np.asarray(experts), want["experts"])
    np.testing.assert_allclose(np.asarray(scores), want["scores"],
                               rtol=1e-6)
    # the weights a share gives its own experts, and 0 to the others'
    local, weight = ref.local_weights(want, cfg_of(m))
    here = (want["experts"] >= 0) & (want["experts"] < 8)
    np.testing.assert_allclose(weight, np.where(here, want["scores"] * 16.0,
                                                0.0), rtol=1e-6)
    assert ((local >= 0) & (local < 8)).all()
    # the neighbouring choices differ from the choice in one expert or
    # in one group, and the margins are what separates them
    flip = ref.route(logits[100:], cfg_of(m), flip="expert")
    assert (flip["experts"][:, :5] == want["experts"][100:, :5]).all()
    assert (flip["experts"][:, 5] == want["next_expert"][100:]).all()
    assert (want["margin_expert"][100:] >= 0).all()
    assert (want["margin_group"][100:] >= 0).all()


# ----------------------------------------------- the paged programs

def _drive(m, variables, prompts, n_new, attn_impl, interpret, chunk=32,
           page=16):
    """Prefill then decode S requests through the family's own two
    programs over a paged latent slab, teacher-forcing `n_new` greedy
    tokens of the program itself; returns (tokens [S][n_new], logits
    [S][n_new, V], counts summed over steps)."""
    fam = m.serve_family()
    S = len(prompts)
    pmax = m.max_len // page
    slab = jnp.zeros((m.layers, S * pmax + 1, page, m.row_lanes), m.dtype)
    tables = np.zeros((S, pmax), np.int32)
    for s in range(S):
        tables[s] = 1 + s * pmax + np.arange(pmax)
    prefill = jax.jit(fam.prefill_step(chunk, "f32", attn_impl, interpret))
    logits_of = jax.jit(ds.build_decode_logits(m, attn_impl, interpret))
    params = variables["params"]
    for s, prompt in enumerate(prompts):
        for start in range(0, len(prompt) - 1, chunk):
            n = min(chunk, len(prompt) - 1 - start)
            pos = np.zeros(chunk, np.int32)
            pos[:n] = start + np.arange(n)
            tok = np.zeros(chunk, np.int32)
            tok[:n] = prompt[start:start + n]
            live = (np.arange(chunk) < n).astype(np.float32)
            (slab,) = prefill(
                params, slab, jnp.asarray(tok), jnp.asarray(pos),
                jnp.asarray(tables[s]),
                jnp.asarray(np.where(live > 0, tables[s][pos // page], 0)),
                jnp.asarray(np.where(live > 0, pos % page, 0)),
                jnp.asarray(live))
            # a chunk's kernel through the interpreter runs JAX
            # operations of its own in callbacks: nothing that waits
            # for the chunk is dispatched behind it
            jax.block_until_ready(slab)
    seqs = [list(p) for p in prompts]
    out_logits = [[] for _ in prompts]
    counts = np.zeros(3, np.int64)
    zeros = jnp.zeros(S, jnp.int32)
    for _ in range(n_new):
        pos = np.asarray([len(q) - 1 for q in seqs], np.int32)
        tok = np.asarray([q[-1] for q in seqs], np.int32)
        lg, c, slab = logits_of(
            params, slab, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(tables),
            jnp.asarray(tables[np.arange(S), pos // page]),
            jnp.asarray(pos % page), jnp.ones(S, jnp.float32), zeros, zeros)
        lg = np.array(lg, np.float32)
        lg[:, 0] = -np.inf
        counts += np.asarray(c)
        for s in range(S):
            out_logits[s].append(lg[s])
            seqs[s].append(int(lg[s].argmax()))
    return ([q[len(p):] for q, p in zip(seqs, prompts)],
            [np.stack(x) for x in out_logits], counts)


@pytest.mark.parametrize("attn_impl,interpret,chunk", [
    ("gather", False, 32), ("pallas", True, 32), ("gather", False, 80),
    ("pallas", True, 80)])
def test_paged_prefill_then_decode_against_the_full_forward(
        ref, attn_impl, interpret, chunk):
    """Float32 throughout, so what differs is the order of the sums:
    absorbed against up-projected attention, a running softmax in
    blocks against one softmax, experts under a mask (or, in a prefill
    chunk of more than 64 tokens, sorted into groups for ragged_dot
    or, forced through the interpreter, for the grouped-matmul kernel)
    against the reference's loop. Logits are O(1) and those
    differences stay under 2e-4 absolute (measured: 3e-5); a misplaced
    page, a wrong position or a dropped expert moves them by 1e-1."""
    m = ds.DeepSeekV2Module(dtype=jnp.float32, ep_rank=1)
    assert (chunk > ds.DENSE_MOE_TOKENS) == (chunk == 80)
    assert m.serve_family().moe_impl(chunk, attn_impl, interpret) == (
        "dense" if chunk == 32 else attn_impl)
    variables = seeded(m)
    w, cfg = flat_weights(variables), cfg_of(m)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, m.vocab_size, n).tolist() for n in (100, 21)]
    n_new = 6
    tokens, logits, counts = _drive(m, variables, prompts, n_new, attn_impl,
                                    interpret, chunk=chunk)
    local = 0
    for prompt, toks, lg in zip(prompts, tokens, logits):
        ids = prompt + toks
        positions = np.arange(len(prompt) - 1, len(ids) - 1)
        want = ref.logits(w, cfg, ids, positions)
        np.testing.assert_allclose(lg[:, 1:], want[:, 1:], atol=2e-4, rtol=0)
        assert toks == want.argmax(-1).tolist()
        # the program's counts are the reference's routing, counted
        taps = []
        ref.forward(w, cfg, ids, positions, tap=taps)
        for layer in taps:
            chosen = ref.route(layer, cfg)["experts"]
            local += int(((chosen >= 4) & (chosen < 8)).sum())
    moe_layers = m.layers - m.first_dense
    assert counts[0] == 2 * n_new * moe_layers * m.experts_per_tok
    assert counts[1] == local
    assert 0 < counts[2] <= n_new * moe_layers * m.n_held_experts


def test_absorbed_attention_is_the_up_projected_attention(ref):
    """One layer, one query per slot over a written context: the
    absorbed products over the latent rows (plain path and kernel)
    against softmax(q k^T) v with k, v up-projected from the same
    latents, float32."""
    from kubeml_tpu.ops.pallas.mla_paged_attention import \
        mla_paged_attention
    m = ds.DeepSeekV2Module(dtype=jnp.float32)
    rng = np.random.default_rng(5)
    S, G, pmax, H = 3, 16, 8, m.heads
    lengths = np.asarray([100, 1, 37], np.int32)
    slab = np.zeros((2, S * pmax + 1, G, m.row_lanes), np.float32)
    slab[1, 1:, :, :m.latent_lanes] = rng.normal(
        size=(S * pmax, G, m.latent_lanes))
    tables = 1 + np.arange(S * pmax, dtype=np.int32).reshape(S, pmax)
    kv_b = rng.normal(size=(m.kv_lora_rank, H, m.qk_nope_head_dim
                            + m.v_head_dim)).astype(np.float32) * 0.1
    w_uk, w_uv = kv_b[..., :m.qk_nope_head_dim], kv_b[..., m.qk_nope_head_dim:]
    q_nope = rng.normal(size=(S, H, m.qk_nope_head_dim)).astype(np.float32)
    q_pe = rng.normal(size=(S, H, m.qk_rope_head_dim)).astype(np.float32)
    scale = ds.softmax_scale(m)
    q_cat = np.concatenate(
        [np.einsum("shd,chd->shc", q_nope, w_uk), q_pe,
         np.zeros((S, H, m.row_lanes - m.latent_lanes), np.float32)], -1)
    want = np.zeros((S, H, m.v_head_dim), np.float32)
    for s in range(S):
        rows = slab[1, tables[s]].reshape(pmax * G, -1)[:lengths[s]]
        c_kv, k_pe = rows[:, :m.kv_lora_rank], \
            rows[:, m.kv_lora_rank:m.latent_lanes]
        k = np.einsum("tc,chd->thd", c_kv, w_uk)
        v = np.einsum("tc,chd->thd", c_kv, w_uv)
        sc = (np.einsum("hd,thd->ht", q_nope[s], k)
              + np.einsum("hd,td->ht", q_pe[s], k_pe)) * scale
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want[s] = np.einsum("ht,thd->hd", p, v)
    for impl, interpret in (("gather", False), ("pallas", True)):
        o_lat = mla_paged_attention(
            jnp.asarray(q_cat), jnp.asarray(slab), jnp.asarray(tables),
            jnp.asarray(lengths), layer=1, value_lanes=m.kv_lora_rank,
            scale=scale, impl=impl, interpret=interpret)
        got = np.einsum("shc,chd->shd", np.asarray(o_lat), w_uv)
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


def test_the_four_shares_sum_to_the_uncut_layer(ref):
    """At a small size: the four shares' routed parts plus the shared
    experts counted once are the uncut reference's layer output."""
    uncut = ds.DeepSeekV2Module(dtype=jnp.float32, n_held_experts=16)
    cfg_all = cfg_of(uncut)
    assert cfg_all["ep"]["size"] == 1
    w = flat_weights(seeded(uncut, seed=4))
    lw = ref._layer_weights(w, 1)
    h = jnp.asarray(np.random.default_rng(9).normal(
        size=(48, uncut.hidden)).astype(np.float32))
    whole, chosen = ref.moe_ffn(h, lw, cfg_all)
    total = np.zeros_like(np.asarray(whole))
    shared_once = None
    for rank in range(4):
        share = dataclasses.replace(uncut, n_held_experts=4, ep_rank=rank)
        cfg = cfg_of(share)
        lw_r = dict(lw)
        for name in ("gate", "up", "down"):
            key = f"experts/{name}/kernel"
            lw_r[key] = lw[key][4 * rank:4 * rank + 4]
        logits = np.asarray(ref._router_logits(h, lw_r, cfg["rms_norm_eps"]))
        r = ref.route(logits, cfg)
        np.testing.assert_array_equal(r["experts"], chosen["experts"])
        local, weight = ref.local_weights(r, cfg)
        shared, routed = ref._moe_parts(h, local, weight, lw_r, "f32",
                                        cfg["rms_norm_eps"])
        total += np.asarray(routed)
        shared_once = np.asarray(shared)
        # and the program's layer on that share is the reference's
        p = {"ffn_norm": {"scale": lw["ffn_norm/scale"]},
             "router": {"kernel": lw["router/kernel"]},
             "shared": {n: {"kernel": lw[f"shared/{n}/kernel"]}
                        for n in ("gate", "up", "down")},
             "experts": {n: {"kernel": lw_r[f"experts/{n}/kernel"]}
                         for n in ("gate", "up", "down")}}
        got, _counts = ds._ffn(share, 1, h, p, jnp.ones(48))
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(h + shared + routed),
                                   atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(h) + shared_once + total,
                               np.asarray(whole), atol=2e-5, rtol=0)


def test_near_tie_rule_takes_the_smallest_gap_and_leaves_nothing_out(ref):
    """A token the NEIGHBOURING routing puts first reads a gap of 0 at a
    near-tie position once the rule is on, every position keeps a gap,
    and with the rule off nothing else is evaluated."""
    m = ds.DeepSeekV2Module(dtype=jnp.float32)
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


# ------------------------------------------------- engine, /generate

def test_engine_refuses_what_the_family_does_not_provide():
    from kubeml_tpu.serve.engine import DecodeEngine
    m = ds.DeepSeekV2Module()
    variables = m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="deepseek_v2.*no multi-step"):
        DecodeEngine(m, variables, slots=2, decode_steps=4)
    with pytest.raises(ValueError, match="no int8"):
        DecodeEngine(m, variables, slots=2, kv_dtype="int8")
    with pytest.raises(ValueError, match="deepseek_v2.*no speculative"):
        DecodeEngine(m, variables, slots=2, draft_module=m,
                     draft_variables=variables)
    with pytest.raises(ValueError, match="declares no serve family"):
        DecodeEngine(object(), variables, slots=2)
    eng = DecodeEngine(m, variables, slots=2)
    assert len(eng.slab.state) == 1
    assert eng.slab.state[0].shape == (3, 2 * 16 + 1, 16, 256)
    assert eng.slab.state[0].dtype == jnp.bfloat16
    # 192 lanes a token a layer: what the roofline's closed form reads
    assert eng.kv_bytes_per_token == 3 * (256 + 1) * 192 * 2


MODEL_FILE = '''
import jax.numpy as jnp
from kubeml_tpu.models.deepseek_v2 import DeepSeekV2, DeepSeekV2Module


class TinyShare(DeepSeekV2):
    name = "ds-tiny-f32"

    def build(self):
        return DeepSeekV2Module(dtype=jnp.float32, ep_rank=2)
'''


def test_generate_end_to_end_greedy_tokens_equal_the_reference(
        ref, tmp_home, tmp_path):
    """Deployed as a user function from a model file and served by
    model_id from a checkpoint, through POST /generate -> ServeFleet ->
    ServeService -> DecodeEngine -> pager, at float32: the greedy
    tokens are the reference's, and the step's counters reached the
    engine's stats and the phase ring."""
    from kubeml_tpu.control.ps import ParameterServer
    from kubeml_tpu.train.checkpoint import save_checkpoint
    from kubeml_tpu.utils.trace import phases
    import time
    path = tmp_path / "ds_tiny.py"
    path.write_text(MODEL_FILE)
    ps = ParameterServer(serve_slots=2, serve_prefill_chunk=32)
    ps.fn_registry.create("ds-tiny-f32", str(path))
    model_cls, _ = ps.fn_registry.resolve("ds-tiny-f32")
    m = model_cls().module
    variables = seeded(m, seed=6)
    save_checkpoint("ds-served", variables,
                    {"model": "ds-tiny-f32", "function": "ds-tiny-f32",
                     "parallelism": 1, "epoch": 0})
    ps.start()
    t0 = time.monotonic()
    try:
        rng = np.random.default_rng(2)
        w, cfg = flat_weights(variables), cfg_of(m)
        for n in (50, 3):
            prompt = rng.integers(1, m.vocab_size, n).tolist()
            req = urllib.request.Request(
                f"{ps.url}/generate", method="POST",
                data=json.dumps({"model_id": "ds-served", "prompt": prompt,
                                 "max_new_tokens": 8, "temperature": 0.0,
                                 "stream": False}).encode(),
                headers={"Content-Type": "application/json"})
            got = json.loads(urllib.request.urlopen(req, timeout=120)
                             .read())["tokens"]
            ids = prompt + got
            want = ref.logits(w, cfg, ids,
                              np.arange(len(prompt) - 1, len(ids) - 1))
            assert got == want.argmax(-1).tolist()
        (_idx, eng), = ps._serve_service("ds-served").engines()
        assert eng.stats["moe_assignments"] \
            == 16 * (m.layers - m.first_dense) * m.experts_per_tok
        assert 0 < eng.stats["moe_local_assignments"] \
            < eng.stats["moe_assignments"]
        assert eng.stats["moe_experts_touched"] > 0
        assert eng.stats["prefill_dispatches"] == 3    # 49 + 2 tokens, chunk 32
        # (the step that starts running one dispatch ahead walks none,
        # and its emit record holds no counts)
        emits = [r.args for r in phases(t0, time.monotonic())
                 if r.name == "serve.step.emit"
                 and "moe_assignments" in r.args]
        assert sum(a["moe_assignments"] for a in emits) \
            == eng.stats["moe_assignments"]
        assert sum(a["moe_local_assignments"] for a in emits) \
            == eng.stats["moe_local_assignments"]
    finally:
        ps.stop()


def test_bfloat16_checkpoint_round_trip_keeps_dtype_and_bits(tmp_path):
    from kubeml_tpu.train.checkpoint import load_checkpoint, save_checkpoint
    m = ds.DeepSeekV2Module()
    variables = seeded(m, seed=8)
    mixed = {"params": variables["params"],
             "extra": {"count": jnp.arange(5, dtype=jnp.int32),
                       "f": jnp.linspace(0, 1, 7, dtype=jnp.float32)}}
    save_checkpoint("bf", mixed, {"model": "x"}, root=str(tmp_path))
    loaded, manifest = load_checkpoint("bf", root=str(tmp_path))
    before, after = flat_weights(mixed), flat_weights(loaded)
    assert sorted(before) == sorted(after)
    for k, a in before.items():
        assert after[k].dtype == a.dtype, k
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint8), np.asarray(after[k]).view(np.uint8))
    assert "params/head/kernel" in manifest["bfloat16_leaves"]
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(
        jax.device_put(loaded["params"]))} == {"bfloat16"}


# --------------------------------------- the seam leaves GPT as it was

def test_gpt_programs_are_the_builders_unchanged_behind_the_seam():
    """The engine reaches GPT's programs through module.serve_family():
    what it jits is each builder's own function, argument for argument
    (the jaxprs are equal), the slab's state is the five arrays in the
    builders' order, and the cache it declares is the slab GPT had."""
    from kubeml_tpu.models import gpt
    from kubeml_tpu.serve.engine import DecodeEngine
    model = gpt.GPTNano()
    module = model.module
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": np.ones((1, module.max_len), np.int32)})
    eng = DecodeEngine(module, variables, slots=2, page=8, prefill_chunk=8)
    assert type(eng.family) is gpt.GPTServeFamily
    assert eng.family.step_counters == ()
    S, pmax, C = 2, eng.geom.pages_per_slot, 8
    state = eng.slab.state
    assert [a.shape for a in state] == [
        eng.slab.k.shape, eng.slab.v.shape, eng.slab.k_scale.shape,
        eng.slab.v_scale.shape, eng.slab.valid.shape]
    assert eng.slab.k.shape == (module.layers, eng.geom.pages, 8,
                                module.hidden)
    i32, f32 = jnp.int32, jnp.float32
    decode_args = (variables["params"], *state, jnp.zeros(S, i32),
                   jnp.zeros(S, i32), jnp.zeros((S, pmax), i32),
                   jnp.zeros(S, i32), jnp.zeros(S, i32), jnp.zeros(S, f32),
                   jnp.zeros(S, f32), jnp.zeros((S, 2), jnp.uint32),
                   jnp.zeros(S, i32), jnp.zeros(S, i32), jnp.zeros(S, f32))
    prefill_args = (variables["params"], *state, jnp.zeros(C, i32),
                    jnp.zeros(C, i32), jnp.zeros(pmax, i32),
                    jnp.zeros(C, i32), jnp.zeros(C, i32), jnp.zeros(C, f32))
    assert len(decode_args) == 17 and len(prefill_args) == 12
    assert str(jax.make_jaxpr(eng._step_raw)(*decode_args)) == str(
        jax.make_jaxpr(gpt.build_paged_decode_step(module))(*decode_args))
    served = eng.family.prefill_step(C, "f32", "auto", False)
    assert str(jax.make_jaxpr(served)(*prefill_args)) == str(
        jax.make_jaxpr(gpt.build_paged_prefill_step(module, C))(
            *prefill_args))
    # the engine's own entry is that function behind one select (the
    # dispatch before's token row, and which lanes take their token
    # there), its host arguments unpacked from one buffer
    buf, views = eng._packings["decode"].host()
    assert [v.shape for v in views[1:]] == [
        a.shape for a in decode_args[1 + len(state):]]
    out = eng._step(variables["params"], *state, jnp.zeros(S, i32), buf)
    assert out[0].shape == (S,) and len(out) == 2 + len(state)
    # serve/engine.py names no builder of models/gpt.py
    with open(os.path.join(REPO, "kubeml_tpu", "serve", "engine.py")) as f:
        source = f.read()
    assert "models.gpt" not in source and "build_paged" not in source


def test_both_families_decode_steps_end_in_the_one_sampling_function(
        monkeypatch):
    """GPT's and DeepSeek-V2's decode programs reach the SAME function
    of models/base.py for the poison lane, the non-finite guard, the
    PAD mask and the pick: traced with it wrapped by a recorder, each
    program calls it once, with its [S, V] float32 logits, the step's
    own per-lane arguments and its family's pad id. models/deepseek_v2.py
    takes nothing from models/gpt.py."""
    from kubeml_tpu.models import base, gpt
    assert gpt.sample_tokens is base.sample_tokens is ds.sample_tokens
    assert gpt.cow_split_pages is base.cow_split_pages is ds.cow_split_pages
    with open(ds.__file__) as f:
        assert "models.gpt" not in f.read()     # no import of it
    calls = []

    def recorder(logits, active, temps, key_data, poison, pad_id):
        calls.append((logits.shape, logits.dtype, active.shape,
                      temps.shape, key_data.shape, poison.shape, pad_id))
        return base.sample_tokens(logits, active, temps, key_data,
                                  poison, pad_id)

    monkeypatch.setattr(gpt, "sample_tokens", recorder)
    monkeypatch.setattr(ds, "sample_tokens", recorder)
    S, G, pmax = 3, 8, 4
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    lanes = [sds((S,), i32), sds((S,), i32), sds((S, pmax), i32),
             sds((S,), i32), sds((S,), i32), sds((S,), f32),
             sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
             sds((S,), i32), sds((S,), f32)]

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    P = S * pmax + 1
    nano = gpt.GPTNano().module
    rows = sds((nano.layers, P, G, nano.hidden), nano.dtype)
    scales = sds((nano.layers, P), f32)
    gpt_params = abstract(jax.eval_shape(lambda: nano.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), i32))["params"]))
    tiny = ds.DeepSeekV2Module()
    ds_params = abstract(jax.eval_shape(
        lambda: tiny.init(jax.random.PRNGKey(0)))["params"])
    for family, args, vocab in (
            (nano.serve_family(),
             [gpt_params, rows, rows, scales, scales, sds((P, G), f32)],
             nano.vocab_size),
            (tiny.serve_family(),
             [ds_params, sds((tiny.layers, P, G, tiny.row_lanes),
                             tiny.dtype)], tiny.vocab_size)):
        del calls[:]
        out = jax.eval_shape(family.decode_step("f32", "gather", False),
                             *args, *lanes)
        assert calls == [((S, vocab), f32, (S,), (S,), (S, 2), (S,),
                          family.pad_id)], family.name
        assert out[0].shape == (S + len(family.step_counters),)
        assert out[1].shape == (S,) and out[1].dtype == f32
