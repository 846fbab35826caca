"""Decode-bandwidth tests (PR 15): pallas paged attention + int8 KV.

The two new serving-path variants registered in
engine.SERVE_PATH_VARIANTS are pinned here, quoted, next to exactness
assertions (tools/check_serve_parity.py enforces this coupling):

  * 'pallas_paged' — the paged-attention kernel (interpret mode on CPU)
    matches the gather-based reference programs (within one rounding of
    the output in bf16 and float32 rounding in f32 at the op level,
    whatever share of a slot's table is live; token-identical through a
    full engine lifecycle: joins, leaves, mixed prompt lengths,
    copy-on-write splits), with the same dispatch and compile counts —
    the kernel is a bandwidth lever, not a math change.
  * 'int8_kv' — quantized KV pages keep the row-independence contract:
    a stream's tokens are identical solo vs continuously batched, the
    prefix cache serves quantized pages, CoW splits carry per-page
    scales, and the pager invariants hold through hot-swap retirement.

Plus the deterministic bytes-per-token comm proxy (page geometry x
storage dtype, never a timer): slab/engine/stat/metric/snapshot all
agree on the same number, and int8 cuts it >= 3.5x for an f32 model.
"""

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
        # the per-slot count behind stats live_page_entries_sum
        np.testing.assert_array_equal(
            engine._live_entries, np.count_nonzero(engine._tables, axis=1))
        limit -= 1
        assert limit > 0, "engine failed to drain"
    return finished


# the kernel suites read plane 1 of a 2-layer slab, so a kernel that
# ignored its static layer index would read the wrong pages
_LAYER = 1


def _rand_paged(key, S, Pmax, G, H, D, dtype, T, quantized, live=None):
    """Random paged-attention operands with realistic masking, in the
    slab's shape (pages [L, P, G, H*D], scales [L, P]): page 0 reserved
    (tails), per-slot valid prefix, NEG_INF bias. `live` gives each
    slot's number of live pages (default s + 1) and then deals the page
    ids in no order, as copy-on-write and the prefix cache leave them;
    a slot's last live page is attended up to its last 3 rows."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.attention import NEG_INF
    P = S * Pmax + 1
    C = Pmax * G
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (S, T, H, D), jnp.float32).astype(dtype)

    def slab(plane):
        # the plane the kernel is asked for is drawn [.., H, D] and
        # merged to rows of H*D lanes; the other plane holds its
        # negation, finite and wrong
        if plane.ndim == 4:
            plane = plane.reshape(P, G, H * D)
        return jnp.stack([-plane, plane])

    if quantized:
        k_pages = slab(jax.random.randint(
            ks[1], (P, G, H, D), -127, 128, jnp.int32).astype(jnp.int8))
        v_pages = slab(jax.random.randint(
            ks[2], (P, G, H, D), -127, 128, jnp.int32).astype(jnp.int8))
        k_scale = slab(jax.random.uniform(ks[3], (P,), jnp.float32,
                                          0.001, 0.1))
        v_scale = slab(jax.random.uniform(ks[4], (P,), jnp.float32,
                                          0.001, 0.1))
    else:
        k_pages = slab(jax.random.normal(ks[1], (P, G, H, D),
                                         jnp.float32).astype(dtype))
        v_pages = slab(jax.random.normal(ks[2], (P, G, H, D),
                                         jnp.float32).astype(dtype))
        k_scale = jnp.zeros((2, P), jnp.float32)
        v_scale = jnp.zeros((2, P), jnp.float32)
    # slot s holds s+1 pages, the rest of its table points at null 0
    tables = np.zeros((S, Pmax), np.int32)
    if live is None:
        for s in range(S):
            for j in range(min(s + 1, Pmax)):
                tables[s, j] = 1 + s * Pmax + j
        n_valid = np.minimum(np.arange(1, S + 1) * G, C)
    else:
        ids = np.random.default_rng(sum(live)).permutation(
            np.arange(1, P))
        for s, n in enumerate(live):
            tables[s, :n], ids = ids[:n], ids[n:]
        n_valid = np.maximum(np.asarray(live) * G - 3, 0)
    keep = (np.arange(C)[None, :] < n_valid[:, None]).astype(np.float32)
    bias = ((1.0 - keep) * NEG_INF)[:, None, None, :]
    bias = np.broadcast_to(bias, (S, 1, T, C))
    return (q, k_pages, v_pages, k_scale, v_scale,
            jnp.asarray(tables), jnp.asarray(bias))


# ------------------------------------------------------- kernel parity

def _assert_kernel_matches_gather(ker, ref, dtype):
    """Since PR 28 the kernel's products follow the live context (a
    block-diagonal q against lane-dense rows, a running float32 softmax
    over blocks), so its sums are the gather path's in another order
    and its probabilities are cast to the compute dtype BEFORE the
    division by their sum, not after: bit-identity became a bound, in
    units of the spacing of the largest output magnitude in the compute
    dtype. bfloat16: 1 (one rounding of the output; measured: <= 1 over
    these cases). float32: 32 (float32 rounding through the exponential
    of int8 pages' large scores; measured: <= 6.0 with float pages,
    <= 16.5 with int8 pages)."""
    import jax.numpy as jnp
    ker, ref = np.asarray(ker, np.float32), np.asarray(ref, np.float32)
    top = np.float32(np.abs(ref).max())
    atol = 32 * np.spacing(top) if dtype == jnp.float32 \
        else np.float32(jnp.finfo(dtype).eps) * 2.0 ** np.floor(np.log2(top))
    np.testing.assert_allclose(ker, ref, rtol=0, atol=atol)


def _unreferenced_nan(args, quantized):
    """The operands with every page that no table entry names, and the
    null page that only the tails name, made NaN in both planes (an int8
    page through its scale): a kernel that copies a slot's live pages
    only never sees one, and a row it never wrote must not reach a
    product."""
    import jax.numpy as jnp
    q, k_pages, v_pages, k_scale, v_scale, tables, bias = args
    dead = np.ones(k_pages.shape[1], bool)
    dead[np.asarray(tables)[np.asarray(tables) > 0]] = False
    if quantized:
        k_scale = jnp.where(dead[None, :], jnp.nan, k_scale)
        v_scale = jnp.where(dead[None, :], jnp.nan, v_scale)
    else:
        k_pages = jnp.where(dead[None, :, None, None], jnp.nan, k_pages)
        v_pages = jnp.where(dead[None, :, None, None], jnp.nan, v_pages)
    return q, k_pages, v_pages, k_scale, v_scale, tables, bias


# live pages of each slot, of 10 table entries: one page, a fifth, all
# but one and all; several slots with one that holds nothing (decode),
# and one slot at each share (a prefill chunk). Then of 80 entries, a
# context of 4 softmax blocks of 20 pages: slots that walk 1, 2, 0, 4,
# 4 and 2 blocks, so every hand-over of the landing buffers' halves
# (block to block, slot to slot, past an idle slot) is taken
_LIVE_DECODE = (1, 2, 0, 9, 10)
_LIVE_BLOCKS = (1, 21, 0, 79, 80, 40)
_LIVE_CASES = [(_LIVE_DECODE, 1), ((1,), 16), ((2,), 16), ((9,), 16),
               ((10,), 16), (_LIVE_BLOCKS, 1), ((45,), 16)]


def _check_kernel_against_gather(seed, dtype_name, T, live, quantized,
                                 **geometry):
    """The kernel in the interpreter against the gather path. With
    `live`, the kernel's slab has NaN wherever no table points, the
    reference's has not, and only occupied slots are compared: an idle
    slot's row is unspecified and has to be finite."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.paged_attention import paged_attention

    dtype = getattr(jnp, dtype_name)
    if live is not None:
        geometry.update(S=len(live), Pmax=10 if max(live) <= 10 else 80)
    args = _rand_paged(jax.random.PRNGKey(seed), dtype=dtype, T=T,
                       quantized=quantized, live=live, **geometry)
    attend = functools.partial(paged_attention, layer=_LAYER,
                               quantized=quantized, compute_dtype=dtype)
    ker = jax.jit(functools.partial(
        attend, impl="pallas", interpret=True))(
            *(args if live is None else _unreferenced_nan(args, quantized)))
    ref = jax.jit(functools.partial(attend, impl="gather"))(*args)
    assert np.isfinite(np.asarray(ker, np.float32)).all()
    occupied = np.ones(len(ker), bool) if live is None \
        else np.asarray(live) > 0
    _assert_kernel_matches_gather(np.asarray(ker)[occupied],
                                  np.asarray(ref)[occupied], dtype)


@pytest.mark.parametrize("seed,dtype_name,T,live", [
    (0, "float32", 1, None), (1, "float32", 16, None),
    (2, "bfloat16", 1, None), (3, "bfloat16", 16, None)] + [
    (10 + i, dtype_name, T, live)
    for i, (live, T) in enumerate(_LIVE_CASES)
    for dtype_name in ("float32", "bfloat16")])
def test_pallas_paged_kernel_matches_gather(seed, dtype_name, T, live):
    """'pallas_paged': the kernel (interpret) reproduces the gather
    reference — within one bf16 rounding of the output in bf16, float32
    rounding in f32 (_assert_kernel_matches_gather) — for single-token
    decode and chunked-prefill query shapes, whatever share of a slot's
    table is live."""
    _check_kernel_against_gather(seed, dtype_name, T, live, False,
                                 S=4, Pmax=4, G=8, H=4, D=64)


@pytest.mark.parametrize("seed,dtype_name,T,live", [
    (4, "float32", 1, None), (5, "bfloat16", 16, None)] + [
    (20 + i, "bfloat16", T, live)
    for i, (live, T) in enumerate(_LIVE_CASES)])
def test_pallas_paged_kernel_int8_dequant_matches_gather(seed, dtype_name,
                                                         T, live):
    """int8 pages: the kernel's in-VMEM dequant and the gather path's
    pre-gather dequant are ONE expression — outputs within the bounds
    above, the null page's and every unnamed page's scale NaN."""
    _check_kernel_against_gather(seed, dtype_name, T, live, True,
                                 S=3, Pmax=3, G=8, H=2, D=32)


def test_paged_attention_validates_impl_and_geometry():
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.paged_attention import (VMEM_BUDGET,
                                                       paged_attention,
                                                       paged_eligible,
                                                       paged_vmem_bytes,
                                                       resolve_impl)
    # alignment: a page is the landing buffers' sublane extent.
    # The VMEM bound is part of the gate too. The kernel lands a
    # softmax block at a time, so a long context costs bias rows only
    # (gpt-mini's serve geometry fits, and so do 131,072 tokens at 16
    # heads of 128); what grows is the query rows: a chunk of 128 at
    # that width holds 2,048 rows of block-diagonal q and float32
    # accumulator (3 * 2048 * 2048 * 4 B = 48 MiB with the update)
    mini = dict(q_len=16, heads=4, head_dim=64, max_pages=32,
                dtype=jnp.bfloat16)
    assert paged_eligible(8, **mini) and paged_eligible(16, **mini)
    assert not paged_eligible(4, **mini)
    assert paged_vmem_bytes(16, 4, 64, 16, 32, jnp.bfloat16, False) \
        >= 2 * 2 * 256 * 256 * 2
    assert paged_eligible(16, q_len=1, heads=16, head_dim=128,
                          max_pages=8192, dtype=jnp.bfloat16)
    long = dict(q_len=128, heads=16, head_dim=128, max_pages=512,
                dtype=jnp.bfloat16)
    assert paged_vmem_bytes(128, 16, 128, 16, 512, jnp.bfloat16, False) \
        > VMEM_BUDGET
    assert not paged_eligible(16, **long)
    # 'auto' resolves from the same rule; a forced impl passes through
    assert resolve_impl("auto", True, page=16, **mini) == "pallas"
    assert resolve_impl("auto", True, page=16, **long) == "gather"
    assert resolve_impl("auto", False, page=16, **mini) == "gather"  # CPU
    assert resolve_impl("gather", True, page=16, **mini) == "gather"
    args = _rand_paged(jax.random.PRNGKey(0), S=2, Pmax=2, G=4, H=2,
                       D=8, dtype=jnp.float32, T=1, quantized=False)
    with pytest.raises(ValueError, match="impl"):
        paged_attention(*args, layer=_LAYER, impl="mosaic")
    with pytest.raises(ValueError, match="sublane"):
        paged_attention(*args, layer=_LAYER, impl="pallas",
                        interpret=True)


# --------------------------------------------------- engine-level parity

def _staggered_run(module, variables, **engine_kw):
    """A lifecycle covering joins, leaves, mixed prompt lengths, a
    prefix-cache hit, and a CoW split; returns (engine, requests)."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    engine = DecodeEngine(module, variables, slots=4, page=8,
                          prefill_chunk=8, **engine_kw)
    shared = list(range(5, 21))                    # 16 tokens = 2 pages
    a = GenerateRequest(list(shared), max_new_tokens=6, temperature=0.0,
                        seed=0)
    b = GenerateRequest(list(range(40, 43)), max_new_tokens=10,
                        temperature=0.9, seed=3)
    engine.attach(a)
    engine.attach(b)
    for _ in range(4):                              # join mid-flight
        engine.step()
    # same prompt -> prefix-cache hit over shared pages; its first
    # generated token writes into a shared page -> CoW split
    c = GenerateRequest(list(shared), max_new_tokens=6, temperature=0.0,
                        seed=0)
    engine.attach(c)
    _drive(engine)
    return engine, [a, b, c]


def _nano_f32():
    """gpt-nano's blocks in float32 (the registered gpt-nano is bf16)."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.models.gpt import GPTMini, GPTModule

    class F32Nano(GPTMini):
        def build(self):
            return GPTModule(vocab_size=512, max_len=64, hidden=32,
                             layers=2, heads=2, ffn=64, dropout=0.0,
                             dtype=jnp.float32)

    model = F32Nano()
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        {"x": np.ones((1, model.module.max_len), np.int32)})
    return model.module, variables


def test_pallas_paged_engine_bit_identical_across_lifecycle():
    """'pallas_paged' at engine scope: forcing the kernel (interpret)
    changes NOTHING observable vs the gather programs — identical
    tokens through joins/leaves/prompt lengths/cache hits/CoW, and
    identical dispatch/compile counts (still exactly two programs).

    Tokens are compared in float32, where the kernel and the gather
    path agree to rounding. Since PR 28 the kernel casts its
    probabilities to the compute dtype before dividing by their sum, so
    in the registered bf16 gpt-nano a layer's attention output differs
    by one bf16 rounding, which a stream sampled at temperature 0.9 from
    a random model's flat logits does not survive: there the counts are
    compared, and the kernel's engine with itself."""
    for module, variables in (_nano_f32(), _nano()[1:]):
        g_eng, g_reqs = _staggered_run(module, variables)
        p_eng, p_reqs = _staggered_run(module, variables,
                                       attn_impl="pallas",
                                       attn_interpret=True)
        assert all(r.outcome == "ok" for r in g_reqs + p_reqs)
        if module.dtype == np.float32:
            for a, b in zip(g_reqs, p_reqs):
                np.testing.assert_array_equal(np.asarray(a.tokens),
                                              np.asarray(b.tokens))
        else:
            _, again = _staggered_run(module, variables,
                                      attn_impl="pallas",
                                      attn_interpret=True)
            for a, b in zip(p_reqs, again):
                np.testing.assert_array_equal(np.asarray(a.tokens),
                                              np.asarray(b.tokens))
            # a and c share a prompt, greedy: one stream twice
            assert p_reqs[0].tokens == p_reqs[2].tokens
        # the lifecycle really exercised the cache + CoW paths
        assert g_eng.stats["prefix_hits"] > 0
        assert g_eng.stats["cow_splits"] >= 1
        for stat in ("dispatches", "compiles", "prefill_dispatches",
                     "prefill_compiles", "cow_splits", "prefix_hits",
                     "live_page_entries_sum", "page_entries_sum"):
            assert p_eng.stats[stat] == g_eng.stats[stat], stat
        assert p_eng.stats["compiles"] == 1
        assert p_eng.stats["prefill_compiles"] == 1
        g_eng.check_pager()
        p_eng.check_pager()
        # the walk counters: every decode-lane dispatch adds its occupied
        # slots' whole tables and their non-null entries, at least one a
        # slot; the per-slot counts they sum are back at zero with the
        # tables once every request has left
        entries = p_eng.stats["page_entries_sum"]
        assert entries == p_eng.stats["occupancy_sum"] \
            * p_eng.geom.pages_per_slot
        assert p_eng.stats["occupancy_sum"] \
            <= p_eng.stats["live_page_entries_sum"] < entries
        assert not p_eng._tables.any() and not p_eng._live_entries.any()


def test_int8_kv_bit_identical_solo_vs_concurrent():
    """'int8_kv': quantized pages keep the row-independence contract —
    a stream's tokens are identical whether it shares the engine with
    neighbours or runs alone (pages disjoint, per-page scales private,
    sampling keys per (seed, pos))."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    specs = [([5, 6, 7, 8, 9], 6, 0.0, 0),
             ([9, 10, 11, 12], 8, 0.7, 1),
             ([3, 4], 4, 1.3, 7)]

    def make():
        return [GenerateRequest(list(p), max_new_tokens=n, temperature=t,
                                seed=s) for p, n, t, s in specs]

    packed = DecodeEngine(module, variables, slots=4, page=8,
                          prefill_chunk=8, kv_dtype="int8")
    reqs_packed = make()
    for r in reqs_packed:
        packed.attach(r)
    _drive(packed)

    alone = DecodeEngine(module, variables, slots=4, page=8,
                         prefill_chunk=8, kv_dtype="int8")
    reqs_alone = make()
    for r in reqs_alone:
        alone.attach(r)
        _drive(alone)

    assert all(r.outcome == "ok" for r in reqs_packed + reqs_alone)
    for a, b in zip(reqs_packed, reqs_alone):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))


def test_int8_kv_page_lifecycle_and_invariants():
    """int8 pages through the whole pager lifecycle: the slab stores
    int8 with [L, P] f32 scale sidecars, the prefix cache serves
    QUANTIZED pages (hit tokens == miss tokens exactly), CoW splits
    carry scales with their page, and the pager invariants hold
    strictly through release and hot-swap generation retirement."""
    import jax.numpy as jnp

    _model, module, variables = _nano()
    eng, reqs = _staggered_run(module, variables, kv_dtype="int8")
    assert eng.slab.k.dtype == jnp.int8
    assert eng.slab.v.dtype == jnp.int8
    assert eng.slab.k_scale.shape == (module.layers, eng.geom.pages)
    assert eng.slab.k_scale.dtype == jnp.float32
    assert eng.stats["prefix_hits"] > 0          # hit served int8 pages
    assert eng.stats["cow_splits"] >= 1          # split carried scales
    assert all(r.outcome == "ok" for r in reqs)
    # cache-hit stream (same greedy prompt) decoded the SAME tokens
    # from shared quantized pages as the cold stream wrote
    np.testing.assert_array_equal(np.asarray(reqs[0].tokens),
                                  np.asarray(reqs[2].tokens))
    eng.check_pager()                            # strict: raises on leak
    assert eng.stats["page_leaks"] == 0
    # hot-swap: old generation's pages (and their scale state) retire
    # cleanly once the last pre-swap stream drains
    from kubeml_tpu.serve.slots import GenerateRequest
    pre = GenerateRequest(list(range(5, 13)), max_new_tokens=4)
    eng.attach(pre)
    eng.step()
    eng.install_weights(variables)
    post = GenerateRequest(list(range(20, 26)), max_new_tokens=4)
    eng.attach(post)
    _drive(eng)
    assert eng.stats["generations_retired"] >= 1
    eng.check_pager()
    # nothing is referenced once every stream drained: what remains
    # resident is only reclaimable prefix-cache pages
    assert eng.pager.in_use == 0
    assert eng.pager.cached_pages == eng.pager.evictable_pages


def test_int8_quantize_roundtrip_per_page_scales():
    """The quantize-on-write helper's contract, directly: round-trip
    within half a quantization step, scale growth requantizes earlier
    rows under the new scale, and an offset-0 write WIPES a reused
    page's stale scale instead of maxing against it."""
    import jax.numpy as jnp

    from kubeml_tpu.models.gpt import _int8_write_decode

    L, P, G, H, D = 1, 3, 4, 2, 4
    pages = jnp.zeros((L, P, G, H * D), jnp.int8)
    scales = jnp.zeros((L, P), jnp.float32)
    row0 = jnp.full((1, H * D), 0.5, jnp.float32)
    pages, scales = _int8_write_decode(
        pages, scales, 0, row0, jnp.array([1]), jnp.array([0]))
    s0 = float(scales[0, 1])
    assert s0 == pytest.approx(0.5 / 127.0)
    got = np.asarray(pages[0, 1, 0], np.float32) * s0
    np.testing.assert_allclose(got, np.asarray(row0[0]), atol=s0 / 2)
    # a larger row grows the scale; row 0 is requantized, still within
    # half of the NEW step
    row1 = jnp.full((1, H * D), 2.0, jnp.float32)
    pages, scales = _int8_write_decode(
        pages, scales, 0, row1, jnp.array([1]), jnp.array([1]))
    s1 = float(scales[0, 1])
    assert s1 == pytest.approx(2.0 / 127.0)
    got0 = np.asarray(pages[0, 1, 0], np.float32) * s1
    np.testing.assert_allclose(got0, np.asarray(row0[0]), atol=s1 / 2)
    got1 = np.asarray(pages[0, 1, 1], np.float32) * s1
    np.testing.assert_allclose(got1, np.asarray(row1[0]), atol=s1 / 2)
    # page reuse: the first write of a page always lands at offset 0,
    # which resets the stale scale (no max against dead data)
    tiny = jnp.full((1, H * D), 0.01, jnp.float32)
    pages, scales = _int8_write_decode(
        pages, scales, 0, tiny, jnp.array([1]), jnp.array([0]))
    assert float(scales[0, 1]) == pytest.approx(0.01 / 127.0)


def test_kv_dtype_validated_everywhere():
    import jax.numpy as jnp

    from kubeml_tpu.models.gpt import (build_paged_decode_step,
                                       build_paged_prefill_step)
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.pager import KVPageSlab, PageGeometry

    _model, module, variables = _nano()
    with pytest.raises(ValueError, match="kv_dtype"):
        build_paged_decode_step(module, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        build_paged_prefill_step(module, 8, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        KVPageSlab(PageGeometry(slots=2, page=8, pages=5,
                                pages_per_slot=2),
                   1, 2, 4, jnp.float32, kv_dtype="fp8")
    with pytest.raises(ValueError, match="kv_dtype"):
        DecodeEngine(module, variables, kv_dtype="fp8")


# ------------------------------------------------- bytes-per-token proxy

def test_kv_bytes_per_token_proxy_pinned():
    """The comm proxy is pure geometry x dtype: pinned against the
    closed form for both storage modes, and int8 cuts an f32 model's
    per-token KV traffic >= 3.5x."""
    import jax.numpy as jnp

    from kubeml_tpu.serve.pager import KVPageSlab, PageGeometry

    geom = PageGeometry(slots=4, page=16, pages=33, pages_per_slot=8)
    L, H, D = 3, 4, 64
    C = geom.context
    f32 = KVPageSlab(geom, L, H, D, jnp.float32)
    i8 = KVPageSlab(geom, L, H, D, jnp.float32, kv_dtype="int8")
    assert f32.decode_bytes_per_token == L * 2 * (C + 1) * H * D * 4
    assert i8.decode_bytes_per_token == L * (
        2 * (C + 1) * H * D * 1 + 2 * 4 * (geom.pages_per_slot + 1))
    ratio = f32.decode_bytes_per_token / i8.decode_bytes_per_token
    assert ratio >= 3.5
    # sidecars are accounted in device residency too
    assert i8.device_bytes >= f32.k_scale.nbytes + f32.v_scale.nbytes


def test_engine_kv_bytes_stat_is_deterministic():
    """stats['kv_bytes'] advances by exactly decode-lanes x proxy —
    replayable from dispatch accounting, no timers involved."""
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest

    _model, module, variables = _nano()
    eng = DecodeEngine(module, variables, slots=2, page=8,
                       prefill_chunk=8)
    req = GenerateRequest(list(range(5, 14)), max_new_tokens=5)
    eng.attach(req)
    _drive(eng)
    assert eng.stats["kv_bytes"] == \
        eng.stats["decode_tokens"] * eng.kv_bytes_per_token
    assert eng.kv_bytes_per_token == eng.slab.decode_bytes_per_token


# ------------------------------------------------- metrics / snapshot / CLI

def test_kv_bytes_metric_family_and_snapshot():
    """kubeml_serve_kv_bytes_total passes the metrics lint, the service
    delta-advances it from the cumulative engine stat, and the snapshot
    carries the proxy + storage mode for health/top."""
    from kubeml_tpu.metrics.prom import MetricsRegistry
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.service import ServeService
    from tools.check_metrics import validate_exposition

    m = MetricsRegistry()
    m.note_serve_kv_bytes("m1", 4096)
    text = m.exposition()
    assert validate_exposition(text) == []
    assert 'kubeml_serve_kv_bytes_total{model="m1"} 4096' in text
    m.clear_serve("m1")
    assert 'model="m1"' not in m.exposition()

    _model, module, variables = _nano()
    engine = DecodeEngine(module, variables, slots=1, page=8,
                          kv_dtype="int8")
    m2 = MetricsRegistry()
    svc = ServeService("m2", engine, max_queue=1, metrics=m2)  # no loop
    snap = svc.snapshot()
    assert snap["serve_kv_dtype"] == "int8"
    assert snap["serve_kv_bytes_per_token"] == engine.kv_bytes_per_token
    engine.stats["kv_bytes"] = 1000
    svc._publish()
    svc._publish()   # same cumulative value: no double count
    assert 'kubeml_serve_kv_bytes_total{model="m2"} 1000' \
        in m2.exposition()
    engine.stats["kv_bytes"] = 1500
    svc._publish()
    assert 'kubeml_serve_kv_bytes_total{model="m2"} 1500' \
        in m2.exposition()


def test_top_renders_decode_bw_line():
    from kubeml_tpu.cli.main import _render_top

    doc = {"id": "serve:m1", "state": "healthy", "reasons": [],
           "latest": {"serve_active_slots": 1, "serve_slot_cap": 4,
                      "serve_queue_depth": 0, "serve_queue_cap": 8,
                      "serve_kv_page_utilization": 0.5,
                      "serve_kv_bytes_per_token": 16640,
                      "serve_kv_dtype": "int8"}}
    out = _render_top(doc)
    assert "decode bw: 16640 B/token" in out
    assert "kv dtype int8" in out


def test_serve_kv_dtype_knob_threading(monkeypatch):
    """--serve-kv-dtype and KUBEML_SERVE_KV_DTYPE reach the PS; an
    unknown value surfaces as a client error via the replica factory's
    ValueError -> InvalidArgsError translation (engine validates)."""
    from kubeml_tpu.cli.main import build_parser
    from kubeml_tpu.control.ps import ParameterServer

    args = build_parser().parse_args(
        ["serve", "--role", "ps", "--serve-kv-dtype", "int8"])
    assert args.serve_kv_dtype == "int8"
    monkeypatch.setenv("KUBEML_SERVE_KV_DTYPE", "int8")
    ps = ParameterServer(port=0)
    assert ps.serve_kv_dtype == "int8"
    ps2 = ParameterServer(port=0, serve_kv_dtype="f32")
    assert ps2.serve_kv_dtype == "f32"


def test_engine_records_attention_impl_per_program():
    """engine.stats names the implementation each attention call site
    took, from the same rule paged_attention dispatches on: CPU 'auto'
    is the gather path, a forced interpret kernel reads 'pallas', and a
    disabled prefill program reads 'off'."""
    from kubeml_tpu.serve.engine import DecodeEngine
    _model, module, variables = _nano()
    auto = DecodeEngine(module, variables, slots=2, page=8,
                        prefill_chunk=8)
    assert auto.stats["attn_impl_decode"] == "gather"
    assert auto.stats["attn_impl_prefill"] == "gather"
    forced = DecodeEngine(module, variables, slots=2, page=8,
                          prefill_chunk=0, attn_impl="pallas",
                          attn_interpret=True)
    assert forced.stats["attn_impl_decode"] == "pallas"
    assert forced.stats["attn_impl_prefill"] == "off"
