"""The parts every family's paged programs share, on their own
(models/base.py, beside ServeFamily): `sample_tokens`, the last block
of a decode program (poison lane -> non-finite guard -> never-emit-PAD
-> greedy or categorical under the lane's own key), and
`cow_split_pages`, the copy-on-write lane over one slab plane. GPT's
and DeepSeek-V2's decode steps both end in the first and begin with the
second (tests/test_models_deepseek_v2.py holds them to it). And the
form a family's programs read their parameters in
(`ServeFamily.serve_params`): GPT's four programs give the same bits on
the tree held in the module's dtype as on the float32 one. And the
expert layer every expert-parallel family shares (`held_expert_layer`):
under DeepSeek-V2's route it is the layer that family had to itself,
jaxpr for jaxpr, and no other family's program reaches it. And the
MLA blocks the latent families share (models/latent_attention.py):
under DeepSeek-V2 they are the inline bodies that family had, jaxpr for
jaxpr."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models.base import cow_split_pages, sample_tokens

pytestmark = pytest.mark.serving

S, V, PAD = 4, 32, 0


def _lanes(seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        logits=jnp.asarray(rng.normal(size=(S, V)), jnp.float32),
        active=jnp.ones(S, jnp.float32),
        temps=jnp.asarray([0.0, 0.8, 1.3, 0.0], jnp.float32),
        key_data=jnp.asarray(rng.integers(0, 2**31, size=(S, 2)),
                             jnp.uint32),
        poison=jnp.zeros(S, jnp.float32))


def _sample(lanes, pad_id=PAD):
    nxt, bad = jax.jit(sample_tokens, static_argnums=5)(
        lanes["logits"], lanes["active"], lanes["temps"],
        lanes["key_data"], lanes["poison"], pad_id)
    assert nxt.dtype == jnp.int32 and bad.dtype == jnp.float32
    return np.asarray(nxt), np.asarray(bad)


@pytest.mark.parametrize("lane", range(S))
def test_poisoned_lane_is_flagged_alone_and_picks_zero(lane):
    lanes = _lanes()
    clean, clean_bad = _sample(lanes)
    assert not clean_bad.any()
    lanes["poison"] = lanes["poison"].at[lane].set(1.0)
    nxt, bad = _sample(lanes)
    assert bad[lane] == 1.0 and nxt[lane] == 0
    others = [s for s in range(S) if s != lane]
    assert not bad[others].any()
    np.testing.assert_array_equal(nxt[others], clean[others])


@pytest.mark.parametrize("what", ["nan", "inf", "inactive"])
def test_guard_reads_the_logits_themselves_of_active_lanes_only(what):
    lanes = _lanes()
    value = jnp.inf if what == "inf" else jnp.nan
    lanes["logits"] = lanes["logits"].at[1, 7].set(value)
    if what == "inactive":
        lanes["active"] = lanes["active"].at[1].set(0.0)
    nxt, bad = _sample(lanes)
    assert bad.tolist() == [0.0, 0.0 if what == "inactive" else 1.0,
                            0.0, 0.0]
    assert what == "inactive" or nxt[1] == 0


@pytest.mark.parametrize("pad_id", [0, 5])
def test_pad_is_never_picked_even_with_the_largest_logit(pad_id):
    lanes = _lanes()
    lanes["logits"] = lanes["logits"].at[:, pad_id].set(1e4)
    nxt, bad = _sample(lanes, pad_id)
    assert not bad.any() and (nxt != pad_id).all()
    # the greedy lanes take the best of what is left
    masked = np.asarray(lanes["logits"]).copy()
    masked[:, pad_id] = -np.inf
    assert nxt[0] == masked[0].argmax() and nxt[3] == masked[3].argmax()


@pytest.mark.parametrize("temp", [0.0, -1.0])
def test_non_positive_temperature_is_argmax_whatever_the_key(temp):
    lanes = _lanes()
    lanes["temps"] = jnp.full(S, temp, jnp.float32)
    nxt, _ = _sample(lanes)
    masked = np.asarray(lanes["logits"]).copy()
    masked[:, PAD] = -np.inf
    np.testing.assert_array_equal(nxt, masked.argmax(-1))
    lanes["key_data"] = lanes["key_data"] + 1
    again, _ = _sample(lanes)
    np.testing.assert_array_equal(again, nxt)


def test_a_lanes_draw_depends_on_its_own_key_and_logits_only():
    lanes = _lanes()
    lanes["temps"] = jnp.full(S, 1.0, jnp.float32)
    nxt, _ = _sample(lanes)
    # lane 1 among other neighbours (logits, keys, temperatures), and
    # in another row of the batch: the same pick
    other = _lanes(seed=9)
    for name in ("logits", "key_data"):
        other[name] = other[name].at[2].set(lanes[name][1])
    other["temps"] = other["temps"].at[2].set(1.0)
    moved, _ = _sample(other)
    assert moved[2] == nxt[1]
    # and it is a draw: over keys it does not always take the argmax
    picks = set()
    for k in range(16):
        lanes["key_data"] = lanes["key_data"].at[1].set(
            jnp.asarray([k, 3], jnp.uint32))
        picks.add(int(_sample(lanes)[0][1]))
    assert len(picks) > 1


def test_cow_split_reads_every_source_before_any_write():
    L, P, G, W = 2, 6, 4, 8
    pages = jnp.arange(L * P * G * W, dtype=jnp.float32).reshape(L, P, G, W)
    # slot 0 splits 2 -> 4, slot 1 has nothing to split (0 -> 0), slot 2
    # splits 4 -> 5: page 4 is a destination AND a source in one step,
    # and the source read is the page as it was
    src = jnp.asarray([2, 0, 4], jnp.int32)
    dst = jnp.asarray([4, 0, 5], jnp.int32)
    out = np.asarray(jax.jit(cow_split_pages)(pages, src, dst))
    want = np.asarray(pages.at[:, dst].set(pages[:, src]))
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(out[:, 5], np.asarray(pages[:, 4]))


# ------------------------- ServeFamily.serve_params: the form a family's
# programs read their parameters in

GS, GG, GPMAX, GC, GW = 3, 8, 4, 8, 24     # slots, page, pages a slot,
GPAGES = 1 + GS * GPMAX                     # chunk, verify window


def _gpt(dtype, seed=0):
    from kubeml_tpu.models.gpt import GPTModule
    module = GPTModule(vocab_size=512, max_len=64, hidden=32, layers=2,
                       heads=2, ffn=64, dropout=0.0, dtype=dtype)
    params = module.init(jax.random.PRNGKey(seed),
                         np.ones((1, 8), np.int32))["params"]
    # biases and scales off their constant initial values, so a cast of
    # them would show
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 1000))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape), params)
    return module, params


def _gpt_state(module, kv_dtype, rng):
    """A slab part full: slot s holds 5 + 6 s tokens in its own pages."""
    L, H = module.layers, module.hidden
    if kv_dtype == "int8":
        planes = [jnp.asarray(rng.integers(-127, 128, (L, GPAGES, GG, H)),
                              jnp.int8) for _ in range(2)]
        scales = [jnp.asarray(rng.uniform(0.01, 0.05, (L, GPAGES)),
                              jnp.float32) for _ in range(2)]
    else:
        planes = [jnp.asarray(rng.normal(size=(L, GPAGES, GG, H)),
                              module.dtype) for _ in range(2)]
        scales = [jnp.ones((L, GPAGES), jnp.float32) for _ in range(2)]
    tables = 1 + np.arange(GS * GPMAX, dtype=np.int32).reshape(GS, GPMAX)
    pos = np.asarray([5 + 6 * s for s in range(GS)], np.int32)
    valid = np.zeros((GPAGES, GG), np.float32)
    for s in range(GS):
        for t in range(pos[s]):
            valid[tables[s, t // GG], t % GG] = 1.0
    return [*planes, *scales, jnp.asarray(valid)], tables, pos


def _gpt_program(program, kv_dtype, module, draft_module):
    """(jitted program, its arguments after the parameter trees)."""
    from kubeml_tpu.models import gpt
    rng = np.random.default_rng(7)
    state, tables, pos = _gpt_state(module, kv_dtype, rng)
    i32, f32 = jnp.int32, jnp.float32
    tokens = jnp.asarray(rng.integers(1, 512, GS), i32)
    temps = jnp.asarray([0.0, 0.8, 1.3], f32)
    live = jnp.ones(GS, i32)
    seeds = jnp.asarray([11, 12, 13], jnp.uint32)
    build = dict(kv_dtype=kv_dtype, attn_impl="gather")
    if program == "decode":
        fn = gpt.build_paged_decode_step(module, **build)
        args = [tokens, jnp.asarray(pos), jnp.asarray(tables),
                jnp.asarray(tables[np.arange(GS), pos // GG]),
                jnp.asarray(pos % GG), jnp.ones(GS, f32), temps,
                jnp.asarray(rng.integers(0, 2**31, (GS, 2)), jnp.uint32),
                jnp.zeros(GS, i32), jnp.zeros(GS, i32), jnp.zeros(GS, f32)]
    elif program == "prefill":
        fn = gpt.build_paged_prefill_step(module, GC, **build)
        at = pos[1] + np.arange(GC)          # slot 1's next chunk
        args = [jnp.asarray(rng.integers(1, 512, GC), i32),
                jnp.asarray(at, i32), jnp.asarray(tables[1]),
                jnp.asarray(tables[1, at // GG]), jnp.asarray(at % GG, i32),
                jnp.asarray([1.0] * (GC - 2) + [0.0] * 2, f32)]
    elif program == "multi_step":
        fn = gpt.build_paged_multi_step_decode(module, 3, **build)
        args = [tokens, jnp.asarray(pos), jnp.asarray(tables), live, temps,
                seeds, jnp.full(GS, -1, i32), jnp.full(GS, 3, i32)]
    else:
        fn = gpt.build_paged_spec_verify_step(module, draft_module, 2, GW,
                                              **build)
        window = np.zeros((GS, GW), np.int32)
        for s in range(GS):
            window[s, :pos[s] + 1] = rng.integers(1, 512, pos[s] + 1)
        args = [jnp.asarray(window), jnp.asarray(pos), jnp.asarray(tables),
                live, temps, seeds, jnp.full(GS, 3, i32)]
    return jax.jit(fn), [*state, *args]


def _bits(tree):
    return [np.asarray(a).view(np.uint8).tolist() if a.dtype == jnp.bfloat16
            else np.asarray(a).tolist()
            for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("program", ["decode", "prefill", "multi_step",
                                     "verify"])
def test_gpt_program_gives_the_same_bits_on_the_held_form(program,
                                                           kv_dtype):
    """Next tokens, `bad`, accepted counts and every returned state
    array: equal bit for bit whether the program casts the float32
    leaves itself, is handed them cast in the module's layout, or is
    handed the held form, cast and stacked over the layers (a draft's
    tree too); the held form is what the docstring of serve_params
    says, and is a fixed point; a float32 module's casts nothing, and
    its tree in the module's layout traces to the text it did."""
    module, params = _gpt(jnp.bfloat16)
    draft, draft_params = _gpt(jnp.bfloat16, seed=5)
    family = module.serve_family()
    held = family.serve_params(params)
    flat = {jax.tree_util.keystr(k): a for k, a in
            jax.tree_util.tree_leaves_with_path(held)}
    assert {k: str(a.dtype) for k, a in flat.items()} == {
        k: "float32" if "LayerNorm" in k else "bfloat16" for k in flat}
    # the final norm's two leaves, and a layer's four stacked over both;
    # a layer's other leaves but its six kernels stacked the same way,
    # the kernels one leaf a layer
    assert sum("LayerNorm" in k for k in flat) == 2 + 2 * 2
    assert len(flat) == 4 + 10 + 6 * 2
    stacked = {k: a for k, a in flat.items() if k.startswith("['layers']")}
    assert len(stacked) == 10 and all(a.shape[0] == 2
                                      for a in stacked.values())
    assert all(k.endswith("['kernel']") for k in flat
               if k.startswith("['layer_"))
    # in the module's layout: every leaf of the module's tree, cast
    per_layer = family.module_params(held)
    by_path = {jax.tree_util.keystr(k): a for k, a in
               jax.tree_util.tree_leaves_with_path(per_layer)}
    assert sum("LayerNorm" in k for k in by_path) == 2 * (2 * 2 + 1)
    assert {k: a.shape for k, a in by_path.items()} == {
        jax.tree_util.keystr(k): a.shape
        for k, a in jax.tree_util.tree_leaves_with_path(params)}
    again = family.serve_params(held)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(held),
                                      jax.tree_util.tree_leaves(again)))
    # a host tree (a checkpoint's) is cast and stacked on the host: the
    # same bits
    host = family.serve_params(jax.tree_util.tree_map(np.asarray, params))
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree_util.tree_leaves(host))
    assert _bits(host) == _bits(held)

    fn, args = _gpt_program(program, kv_dtype, module, draft)
    draft_family = draft.serve_family()
    draft_held = draft_family.serve_params(draft_params)
    trees = [[params], [per_layer], [held]] if program != "verify" else [
        [params, draft_params],
        [per_layer, draft_family.module_params(draft_held)],
        [held, draft_held]]
    cast_inside, *handed_cast = (fn(*p, *args) for p in trees)
    for out in handed_cast:
        assert [a.dtype for a in jax.tree_util.tree_leaves(cast_inside)] \
            == [a.dtype for a in jax.tree_util.tree_leaves(out)]
        assert _bits(cast_inside) == _bits(out)

    module32, params32 = _gpt(jnp.float32)
    family32 = module32.serve_family()
    same = family32.serve_params(params32)
    unstacked = family32.module_params(same)
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(same)} \
        == {"float32"}
    assert _bits(unstacked) == _bits(params32)
    fn32, args32 = _gpt_program(program, kv_dtype, module32, module32)
    trees32 = [[params32], [unstacked]] if program != "verify" else [
        [params32, params32], [unstacked, unstacked]]
    assert str(jax.make_jaxpr(fn32)(*trees32[0], *args32)) == str(
        jax.make_jaxpr(fn32)(*trees32[1], *args32))
    stacked32 = [same] if program != "verify" else [same, same]
    assert _bits(fn32(*trees32[0], *args32)) == _bits(
        fn32(*stacked32, *args32))


@pytest.mark.parametrize("chunked", [False, True])
def test_gpt_head_logits_are_the_same_bits_on_the_held_form(chunked):
    """The trunk's float32 logits themselves (a program returns only
    the pick made from them), after the embedding and both layers: a
    decode step's [S, V] and, through the same head, a chunk's."""
    from kubeml_tpu.models import gpt
    from kubeml_tpu.ops.attention import NEG_INF
    module, params = _gpt(jnp.bfloat16)
    held = module.serve_family().serve_params(params)
    embed, layers, head = gpt._paged_trunk(module, "f32", "gather", False,
                                           chunked=chunked)
    rng = np.random.default_rng(3)
    state, tables, pos = _gpt_state(module, "f32", rng)
    if chunked:      # slot 1's next chunk: rows of one slot, one table
        at, own = pos[1] + np.arange(GC, dtype=np.int32), tables[1]
        where = (own[at // GG], at % GG, np.ones(GC, np.float32))
    else:            # a row a slot, each with its own table
        at, own = pos, tables
        where = (own[np.arange(GS), at // GG], at % GG)
    tokens = rng.integers(1, 512, len(at)).astype(np.int32)
    seen = np.arange(GPMAX * GG)[None, :] <= at[:, None]
    bias = np.where(seen, 0.0, NEG_INF).astype(np.float32)
    bias = bias[None, None] if chunked else bias[:, None, None, :]

    @jax.jit
    def logits(p):
        h = embed(p, tokens, at)
        h = layers(p, h, *state[:4], own, bias, *where)[0]
        return head(p, jnp.swapaxes(h, 0, 1) if chunked else h)

    a, b = logits(params), logits(held)
    assert a.dtype == jnp.float32 and a.shape == (len(at), 512)
    assert np.isfinite(np.asarray(a)).all() and np.asarray(a).std() > 0
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["deepseek_v2", "jamba", "exaone_moe",
                                  "longcat_flash"])
def test_other_families_hand_back_the_very_tree(name):
    """Their leaves are bfloat16 from the checkpoint on and their
    float32 ones are read in float32: nothing to hold otherwise. Their
    expert stacks and scan parameters feed Pallas calls whole, so no
    layout of theirs is stacked either: the held tree is the module's."""
    import importlib
    mod = importlib.import_module(f"kubeml_tpu.models.{name}")
    family = next(v for v in vars(mod).values() if isinstance(v, type)
                  and issubclass(v, mod.ServeFamily)
                  and v is not mod.ServeFamily)
    assert "serve_params" not in vars(family)
    assert "module_params" not in vars(family)
    tree = {"a": {"kernel": np.ones((2, 2), np.float32)}}
    assert mod.ServeFamily.serve_params(object(), tree) is tree
    assert mod.ServeFamily.module_params(object(), tree) is tree


# ------------------------------------------------- the shared expert layer

def _parents_moe(m, x, p, live, dense: bool, impl="gather",
                 interpret=False):
    """models/deepseek_v2.py `_moe` as it stood before its body moved to
    models/base.py `held_expert_layer` (PR 27's text, verbatim; the
    kernel choice it is handed since PR 34 it never had: its grouped
    form IS the 'gather' path, three `lax.ragged_dot` calls)."""
    from jax import lax

    from kubeml_tpu.models import deepseek_v2 as ds
    from kubeml_tpu.models.base import gated_mlp as _gated
    F32, HI, route = jnp.float32, lax.Precision.HIGHEST, ds.route
    n, k, held = x.shape[0], m.experts_per_tok, m.n_held_experts
    with jax.named_scope("router"):
        logits = jnp.dot(x, p["router"]["kernel"].astype(F32), precision=HI)
        experts, scores = route(m, logits)
        local = experts - held * m.ep_rank
        here = (local >= 0) & (local < held) & (live[:, None] > 0)
        weight = jnp.where(here, scores * m.routed_scaling_factor, 0.0)
        local = jnp.where(here, local, held)        # held: nowhere
        per_expert = jnp.zeros((n, held + 1), F32).at[
            jnp.arange(n)[:, None], local].add(weight)[:, :held]
        tokens_of = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
        counts = jnp.stack([
            jnp.sum(live > 0).astype(jnp.int32) * k,
            jnp.sum(here).astype(jnp.int32),
            jnp.sum(tokens_of > 0).astype(jnp.int32)])
    xb = x.astype(m.dtype)
    e = p["experts"]
    with jax.named_scope("experts"):
        if dense:
            g = jnp.einsum("nd,edf->enf", xb, e["gate"]["kernel"],
                           preferred_element_type=F32)
            u = jnp.einsum("nd,edf->enf", xb, e["up"]["kernel"],
                           preferred_element_type=F32)
            a = (jax.nn.silu(g) * u * per_expert.T[:, :, None]
                 ).astype(m.dtype)
            routed = jnp.einsum("enf,efd->nd", a, e["down"]["kernel"],
                                preferred_element_type=F32)
        else:
            flat = local.reshape(n * k)
            order = jnp.argsort(flat, stable=True)
            rows = xb[order // k]
            g = lax.ragged_dot(rows, e["gate"]["kernel"], tokens_of,
                               preferred_element_type=F32)
            u = lax.ragged_dot(rows, e["up"]["kernel"], tokens_of,
                               preferred_element_type=F32)
            a = (jax.nn.silu(g) * u).astype(m.dtype)
            y = lax.ragged_dot(a, e["down"]["kernel"], tokens_of,
                               preferred_element_type=F32)
            y = jnp.where((jnp.arange(n * k) < tokens_of.sum())[:, None],
                          y * weight.reshape(n * k)[order][:, None], 0.0)
            routed = y[jnp.argsort(order)].reshape(n, k, -1).sum(1)
    with jax.named_scope("shared_expert"):
        shared = _gated(xb, p["shared"])
    return shared + routed, counts


def _family_programs(module, chunk, slots=2, page=16, impl="gather",
                     interpret=False):
    """(decode jaxpr, prefill jaxpr) of a family's two programs at a
    small geometry, traced from shapes alone."""
    family = module.serve_family()
    pmax = module.max_len // page
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    cache = family.cache
    state = [sds((cache.layers, slots * pmax + 1, page, cache.width),
                 cache.dtype)] * cache.planes
    if cache.sidecars:
        state += [sds((cache.layers, slots * pmax + 1), f32)] * cache.planes
    if cache.validity:
        state.append(sds((slots * pmax + 1, page), f32))
    state += [sds((st.layers, slots) + tuple(st.shape), st.dtype)
              for st in cache.slot_state]
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0)))["params"]
    S, C = slots, chunk
    decode = (params, *state, sds((S,), i32), sds((S,), i32),
              sds((S, pmax), i32), sds((S,), i32), sds((S,), i32),
              sds((S,), f32), sds((S,), f32), sds((S, 2), jnp.uint32),
              sds((S,), i32), sds((S,), i32), sds((S,), f32))
    prefill = [params, *state, sds((C,), i32), sds((C,), i32),
               sds((pmax,), i32), sds((C,), i32), sds((C,), i32),
               sds((C,), f32)]
    if cache.slot_state:
        prefill.append(sds((), i32))
    return (str(jax.make_jaxpr(family.decode_step("f32", impl, interpret))(
        *decode)), str(jax.make_jaxpr(family.prefill_step(
            C, "f32", impl, interpret))(*prefill)))


@pytest.mark.parametrize("chunk", [32, 80], ids=["dense", "ragged"])
def test_lifted_expert_layer_gives_deepseek_the_parents_jaxprs(chunk,
                                                               monkeypatch):
    """DeepSeek-V2's decode and prefill programs trace to the same text
    with `held_expert_layer` under its route as with the body `_moe`
    had before the lift (a prefill chunk of 80 takes the ragged_dot
    form, the decode batch and a chunk of 32 the dense mask): the
    order of traced operations is part of that text."""
    from kubeml_tpu.models import deepseek_v2 as ds
    module = ds.DeepSeekV2Module()
    assert (chunk > ds.DENSE_MOE_TOKENS) == (chunk == 80)
    lifted = _family_programs(module, chunk)
    monkeypatch.setattr(ds, "_moe", _parents_moe)
    parents = _family_programs(module, chunk)
    assert lifted[0] == parents[0] and lifted[1] == parents[1]
    assert "ragged_dot" in lifted[1] or chunk == 32


# ------------------------------------------------- the shared MLA blocks

def _parents_queries_and_row(m, h, p, cos, sin):
    """models/deepseek_v2.py `_queries_and_row` (with its `_rope`) as it
    stood before it moved to models/latent_attention.py (PR 36's text,
    verbatim)."""
    from kubeml_tpu.models.base import dot_f32 as _dot
    from kubeml_tpu.models.base import rms_norm as _rms
    F32 = jnp.float32

    def _rope(x, cos, sin):
        half = x.shape[-1] // 2
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
        cos, sin = cos.reshape(shape), sin.reshape(shape)
        a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    n, H = h.shape[0], m.heads
    x = _rms(h, p["attn_norm"]["scale"], m.rms_eps).astype(m.dtype)
    c_q = _rms(_dot(x, p["q_a"]["kernel"]), p["q_a_norm"]["scale"],
               m.rms_eps)
    q = _dot(c_q, p["q_b"]["kernel"]).reshape(
        n, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim].astype(m.dtype)
    q_pe = _rope(q[..., m.qk_nope_head_dim:], cos, sin).astype(m.dtype)
    kv = _dot(x, p["kv_a"]["kernel"])
    c_kv = _rms(kv[:, :m.kv_lora_rank], p["kv_a_norm"]["scale"], m.rms_eps)
    k_pe = _rope(kv[:, m.kv_lora_rank:], cos, sin)
    row = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((n, m.row_lanes - m.latent_lanes), F32)],
        -1).astype(m.dtype)
    return q_nope, q_pe, row


def _parents_kv_b(m, p):
    w = p["kv_b"]["kernel"].reshape(
        m.kv_lora_rank, m.heads, m.qk_nope_head_dim + m.v_head_dim)
    return w[..., :m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]


def _parents_mla_decode(m, h, p, c_pages, i, scope, cos, sin, page_tables,
                        lengths, write_page, write_off, scale, attn_impl,
                        attn_interpret, q_scale=None, kv_scale=None):
    """The MLA half of a layer of DeepSeek-V2's `logits_of` as it stood
    inline before the lift (PR 36's text, verbatim but for the return)."""
    from kubeml_tpu.models.base import dot_f32 as _dot
    from kubeml_tpu.ops.pallas import mla_paged_attention as mla
    assert q_scale is None and kv_scale is None
    F32 = jnp.float32
    w_uk, w_uv = _parents_kv_b(m, p)
    with jax.named_scope(f"layer_{i}/mla_q"):
        q_nope, q_pe, row = _parents_queries_and_row(m, h, p, cos, sin)
        q_lat = jnp.einsum("shd,chd->shc", q_nope, w_uk,
                           preferred_element_type=F32)
        q_cat = jnp.concatenate(
            [q_lat.astype(m.dtype), q_pe,
             jnp.zeros(q_pe.shape[:2]
                       + (m.row_lanes - m.latent_lanes,), m.dtype)],
            -1)
    with jax.named_scope(f"layer_{i}/mla_kv_write"):
        c_pages = c_pages.at[i, write_page, write_off].set(row)
    with jax.named_scope(f"layer_{i}/mla_attn"):
        o_lat = mla.mla_paged_attention(
            q_cat, c_pages, page_tables, lengths, layer=i,
            value_lanes=m.kv_lora_rank, scale=scale,
            impl=attn_impl, interpret=attn_interpret)
    with jax.named_scope(f"layer_{i}/mla_out"):
        o = jnp.einsum("shc,chd->shd", o_lat, w_uv,
                       preferred_element_type=F32)
        o = o.reshape(o.shape[0], -1)
        h = h + _dot(o, p["o"]["kernel"])
    return h, c_pages


def _parents_mla_prefill(m, h, p, c_pages, i, scope, cos, sin, pos,
                         page_table, write_pages, write_offs, n_blocks,
                         per_block, scale, q_scale=None, kv_scale=None):
    """The MLA half of a layer of DeepSeek-V2's `prefill` as it stood
    inline before the lift (PR 36's text, verbatim but for the return)."""
    from jax import lax

    from kubeml_tpu.models.base import dot_f32 as _dot
    from kubeml_tpu.ops.pallas import mla_paged_attention as mla
    assert q_scale is None and kv_scale is None
    F32 = jnp.float32
    block = per_block * c_pages.shape[2]
    w_uk, w_uv = _parents_kv_b(m, p)
    with jax.named_scope(f"layer_{i}/mla_q"):
        q_nope, q_pe, rows = _parents_queries_and_row(m, h, p, cos, sin)
    with jax.named_scope(f"layer_{i}/mla_kv_write"):
        c_pages = c_pages.at[i, write_pages, write_offs].set(rows)
    with jax.named_scope(f"layer_{i}/mla_attn"):
        q_cat = jnp.concatenate([q_nope, q_pe], -1)

        def one_block(b, carry):
            mx, den, acc = carry
            ids = lax.dynamic_slice_in_dim(
                page_table, b * per_block, per_block)
            ctx = c_pages[i, ids].reshape(block, -1)
            c_kv = ctx[:, :m.kv_lora_rank]
            k_pe = ctx[:, m.kv_lora_rank:m.latent_lanes]
            k_nope = jnp.einsum("kc,chd->khd", c_kv, w_uk,
                                preferred_element_type=F32
                                ).astype(m.dtype)
            k_cat = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_pe[:, None, :],
                    (block, m.heads, k_pe.shape[-1]))], -1)
            v = jnp.einsum("kc,chd->khd", c_kv, w_uv,
                           preferred_element_type=F32
                           ).astype(m.dtype)
            sc = jnp.einsum("qhd,khd->hqk", q_cat, k_cat,
                            preferred_element_type=F32) * scale
            key_pos = b * block + jnp.arange(block)
            seen = (key_pos[None, :] <= pos[:, None])[None]
            sc = jnp.where(seen, sc, mla.NEG)
            mx_new = jnp.maximum(mx, sc.max(-1))
            w = jnp.where(seen, jnp.exp(sc - mx_new[..., None]), 0.0)
            alpha = jnp.exp(mx - mx_new)
            den = alpha * den + w.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "hqk,khd->hqd", w.astype(m.dtype), v,
                preferred_element_type=F32)
            return mx_new, den, acc

        C = h.shape[0]
        _, den, acc = lax.fori_loop(
            0, n_blocks, one_block,
            (jnp.full((m.heads, C), mla.NEG, F32),
             jnp.zeros((m.heads, C), F32),
             jnp.zeros((m.heads, C, m.v_head_dim), F32)))
        o = acc / jnp.where(den > 0, den, 1.0)[..., None]
    with jax.named_scope(f"layer_{i}/mla_out"):
        o = o.transpose(1, 0, 2).reshape(C, -1)
        h = h + _dot(o, p["o"]["kernel"])
    return h, c_pages


@pytest.mark.parametrize("chunk", [32, 80], ids=["dense", "ragged"])
def test_lifted_latent_blocks_give_deepseek_the_parents_jaxprs(chunk,
                                                               monkeypatch):
    """DeepSeek-V2's decode and prefill programs trace to the same text
    with the MLA blocks of models/latent_attention.py (which LongCat-Flash
    shares, with its lora scales) as with the inline bodies they had
    before the lift; `_parents_moe` in `_moe`'s place besides, so the
    whole layer is the parent's text."""
    from kubeml_tpu.models import deepseek_v2 as ds
    from kubeml_tpu.models import latent_attention as latent
    module = ds.DeepSeekV2Module()
    lifted = _family_programs(module, chunk)
    called = []

    def counted(body):
        def run(*a, **kw):
            called.append(body.__name__)
            return body(*a, **kw)
        return run

    monkeypatch.setattr(latent, "decode_attention",
                        counted(_parents_mla_decode))
    monkeypatch.setattr(latent, "prefill_attention",
                        counted(_parents_mla_prefill))
    monkeypatch.setattr(ds, "_moe", _parents_moe)
    parents = _family_programs(module, chunk)
    assert sorted(set(called)) == ["_parents_mla_decode",
                                   "_parents_mla_prefill"]
    assert len(called) == 2 * module.layers
    assert lifted[0] == parents[0] and lifted[1] == parents[1]


# sha256 (the first 16 hex digits) of the decode and the prefill
# program's jaxpr text of each latent or expert-parallel family's tiny
# preset, at `_family_programs`' geometry, traced on the tree before
# the expert layer took its optional SwiGLU clamp and the MLA blocks
# their optional input, output gate and post-norm (GigaChat3.5's):
# (family, prefill chunk, impl) -> (decode, prefill). A chunk of 32
# takes the dense mask, 80 ragged_dot, and 'pallas' in interpret mode
# the paged, latent and grouped-matmul kernels.
PARENT_JAXPRS = {
    ("deepseek_v2", 32, "gather"): ("af1eae7ee230ee2a", "394f9779a7b539fc"),
    ("deepseek_v2", 80, "gather"): ("af1eae7ee230ee2a", "0eadae3f1744c026"),
    ("deepseek_v2", 80, "pallas"): ("eee681c7dae80d9d", "3f0e468ad1f5c3ce"),
    ("exaone_moe", 32, "gather"): ("b712a28c8dbdcafe", "9d16664c77b6292a"),
    ("exaone_moe", 80, "gather"): ("b712a28c8dbdcafe", "f4d297533a96b45c"),
    ("exaone_moe", 80, "pallas"): ("5a9666a0260bee64", "6584f235b085e142"),
    ("longcat_flash", 32, "gather"): ("eeb77826dcb437c9",
                                      "1d4e017c598a365a"),
    ("longcat_flash", 80, "gather"): ("eeb77826dcb437c9",
                                      "04c645a89b455173"),
    ("longcat_flash", 80, "pallas"): ("de4ce901a8fb0e9a",
                                      "94a91eec678c9e6d"),
}


@pytest.mark.parametrize("family,chunk,impl", sorted(PARENT_JAXPRS))
def test_the_optional_clamp_and_gate_leave_other_families_jaxprs(
        family, chunk, impl):
    """DeepSeek-V2's, EXAONE-MoE's and LongCat-Flash's decode and
    prefill programs trace to the very text they had before the shared
    expert layer and MLA blocks grew GigaChat3.5's options: with none of
    them given, nothing of them is traced."""
    import hashlib
    import importlib
    mod = importlib.import_module(f"kubeml_tpu.models.{family}")
    module = {"deepseek_v2": "DeepSeekV2Module", "exaone_moe":
              "ExaoneMoEModule", "longcat_flash": "LongCatFlashModule"}
    programs = _family_programs(getattr(mod, module[family])(), chunk,
                                impl=impl, interpret=impl == "pallas")
    assert tuple(hashlib.sha256(p.encode()).hexdigest()[:16]
                 for p in programs) == PARENT_JAXPRS[family, chunk, impl]


@pytest.mark.parametrize("name", ["gpt", "jamba"])
def test_families_without_experts_never_reach_the_expert_layer(name,
                                                               monkeypatch):
    """GPT's and Jamba's programs trace with the shared expert layer
    taken away: nothing of it can have moved their jaxprs."""
    from kubeml_tpu.models import base, deepseek_v2, exaone_moe, jamba

    def gone(*a, **kw):
        raise AssertionError("a family without experts reached the "
                             "expert layer")

    for mod in (base, deepseek_v2, exaone_moe):
        monkeypatch.setattr(mod, "held_expert_layer", gone)
    if name == "gpt":
        module, params = _gpt(jnp.float32)
        decode, prefill = (
            str(jax.make_jaxpr(fn)(params, *args)) for fn, args in (
                _gpt_program(which, "f32", module, None)
                for which in ("decode", "prefill")))
    else:
        decode, prefill = _family_programs(jamba.JambaModule(), 16)
    assert "dot_general" in decode and "dot_general" in prefill
    with pytest.raises(AssertionError, match="reached the expert layer"):
        _family_programs(deepseek_v2.DeepSeekV2Module(), 32)

