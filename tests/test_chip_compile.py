"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with jaxlib/libtpu
compiles for a topology that is described, not present, and raises what
the chip's compiler would raise (Mosaic verification errors, scoped-VMEM
exhaustion, unaligned slices) — faults the interpret-mode suites cannot
see. A compile that passes is a compile, never a run: results and times
come from `chip_smoke.py` on the chip.

This is the ONLY file that describes a topology, and it does so inside
module-scoped fixtures: libtpu may be loaded by one process at a time,
so nothing here may touch it at import, in a `skipif`, in `parametrize`
arguments or in conftest — every xdist worker imports this file, only
the worker that runs it loads the library. The persistent compile cache
is off around the compiles (an entry written for a described chip cannot
be read back without one, and warns).
"""

import functools

import pytest


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower + compile `fn` for the described chip from (shape, dtype)
    pairs; returns the compiled HLO text."""
    import jax
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# ---------------------------------------------------- paged attention

# (slots, q_len, heads, head_dim, page, max_pages, compute dtype, int8)
# — the decode and prefill programs DecodeEngine builds for gpt-mini
# (8 slots, page 16, Pmax = 512/16), for each page storage mode, plus
# one wide point so the layout is not fitted to a toy. The kernel's
# page operands are the slab whole, [layers, P, page, heads*head_dim]
# (serve/pager.py KVPageSlab), read at a static layer
PAGED_GEOMETRIES = {
    "decode-bf16": (8, 1, 4, 64, 16, 32, "bfloat16", False),
    "decode-f32": (8, 1, 4, 64, 16, 32, "float32", False),
    "decode-int8": (8, 1, 4, 64, 16, 32, "bfloat16", True),
    "prefill-bf16": (1, 16, 4, 64, 16, 32, "bfloat16", False),
    "prefill-f32": (1, 16, 4, 64, 16, 32, "float32", False),
    "prefill-int8": (1, 16, 4, 64, 16, 32, "bfloat16", True),
    "wide-decode-bf16": (8, 1, 16, 128, 16, 64, "bfloat16", False),
    # the kernel lands a softmax block at a time, so its VMEM does not
    # grow with the context but for the bias rows: 4,352 tokens (the
    # gate's edge while a slot's whole context landed at once) and
    # 32,768
    "wide-context-bf16": (2, 1, 16, 128, 16, 272, "bfloat16", False),
    "wide-long-context-bf16": (2, 1, 16, 128, 16, 2048, "bfloat16",
                               False),
    # the most query rows the VMEM gate still sends to the kernel at
    # the wide geometry, a prefill chunk of 80 (bound 38.7 of the 40 MiB
    # budget): what paged_eligible admits, the compiler must accept
    "wide-budget-edge-bf16": (1, 80, 16, 128, 16, 272, "bfloat16", False),
    # the benchmark's serve cell (GPT-2 large: 20 heads of 64; 8 slots
    # x 64 pages of 16, bf16 pages), its decode and prefill programs,
    # and the 32 slots the slab has room for
    "cell-decode-bf16": (8, 1, 20, 64, 16, 64, "bfloat16", False),
    "cell-prefill-bf16": (1, 16, 20, 64, 16, 64, "bfloat16", False),
    "cell-decode-32-slots-bf16": (32, 1, 20, 64, 16, 64, "bfloat16",
                                  False),
}
_PAGED_LAYERS = 2


def _paged_shapes(name):
    import jax.numpy as jnp
    S, T, H, D, G, Pmax, dtype_name, quantized = PAGED_GEOMETRIES[name]
    dtype = getattr(jnp, dtype_name)
    L, P = _PAGED_LAYERS, S * Pmax + 1
    page_dtype = jnp.int8 if quantized else dtype
    return (((S, T, H, D), dtype), ((L, P, G, H * D), page_dtype),
            ((L, P, G, H * D), page_dtype), ((L, P), jnp.float32),
            ((L, P), jnp.float32), ((S, Pmax), jnp.int32),
            ((S, 1, T, Pmax * G), jnp.float32))


@pytest.mark.parametrize("name", sorted(PAGED_GEOMETRIES))
def test_paged_attention_compiles_for_v5e(one_chip, name):
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.paged_attention import (paged_attention,
                                                       paged_eligible)
    S, T, H, D, G, Pmax, dtype_name, quantized = PAGED_GEOMETRIES[name]
    dtype = getattr(jnp, dtype_name)
    # the kernel 'auto' would pick on the chip is the one compiled here
    assert paged_eligible(G, q_len=T, heads=H, head_dim=D,
                          max_pages=Pmax, dtype=dtype, quantized=quantized)
    hlo = _compile(
        functools.partial(paged_attention, layer=_PAGED_LAYERS - 1,
                          quantized=quantized, compute_dtype=dtype,
                          impl="pallas"),
        one_chip, *_paged_shapes(name))
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo


@pytest.mark.parametrize("name", sorted(PAGED_GEOMETRIES))
def test_paged_vmem_bound_covers_the_compilers_figure(one_chip, name):
    """`paged_vmem_bytes` is what `paged_eligible` gates on and what the
    call raises its scoped-VMEM limit to, so it may not be under what
    the compiler allocates. The compiled call says both: the limit it
    was given (`scoped_memory_configs`) and what Mosaic used of it
    (`used_scoped_memory_configs`). The bound reads 1.2-2.2 times the
    compiler's figure over these geometries (sandbox compile, PR 28)."""
    import re

    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.paged_attention import (paged_attention,
                                                       paged_vmem_bytes)
    S, T, H, D, G, Pmax, dtype_name, quantized = PAGED_GEOMETRIES[name]
    dtype = getattr(jnp, dtype_name)
    bound = paged_vmem_bytes(T, H, D, G, Pmax, dtype, quantized)
    hlo = _compile(
        functools.partial(paged_attention, layer=_PAGED_LAYERS - 1,
                          quantized=quantized, compute_dtype=dtype,
                          impl="pallas"),
        one_chip, *_paged_shapes(name))
    call, = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    limit, used = (
        int(re.search(key + r'":\[\{[^}]*"size":"(\d+)"', call).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert 0 < used <= bound <= limit
    assert "tpu_custom_call" in hlo


# ------------------------------------------------ the serve programs

# The four programs of serve/engine.py at the widths of the benchmark's
# serve cell (GPT-2 large: 20 heads of 64, FFN 5120, vocabulary 50257,
# context 1024; 8 slots, page 16, 513 pages), cut to a few layers so a
# compile stays under 10 s. What they guard is the slab's ONE layout
# (serve/pager.py KVPageSlab): before PR 26 every decode program relaid
# the whole slab out six times and every prefill program four times
# (71% of the device's busy time, PERF.md), and each layer's plane was
# copied out for the kernel.
_SERVE = dict(heads=20, head_dim=64, ffn=5120, vocab=50257, max_len=1024,
              slots=8, page=16, chunk=16, steps=4, window=128)
# name -> (builder, layers, bound in bytes on the temporaries the
# program may ask for beside its arguments, whether the parameters come
# in the form the engine holds them in). The engine hands a program
# ServeFamily.serve_params' tree (PR 32: every matmul and embedding leaf
# bfloat16, the norms float32): the "-held" cases, the cell's programs
# as they run. The others take the float32 tree and cast it at every
# use, as the train plane's forward does and every engine did until
# PR 32; they guard the slab the same way and say what the held form
# saves. Read (sandbox compile, PR 26 and again PR 32), bf16 / int8
# pages, float32 tree: decode 133 / 133 MB, prefill 134 / 136,
# multi-step 141 / 140, verify 430 / 355 (at 2 layers the compiler also
# stages the 42 MB bf16 slab in VMEM), the cell's 36-layer decode
# program 183.8: 129 MB of each is the bf16 copy of the float32
# embedding [50257, 1280] that the lookup and the tied head read; the
# verify program adds the draft's float32 logits over its window
# (206 MB) and, by design, a second copy of K and V (it scans twice
# from the input slab). Held form (sandbox compile, PR 32): decode
# 4.35 / 4.39 MB, prefill 5.68 / 0, the 36-layer decode program 73.3,
# each bound a quarter above its reading; arguments 3.06 GB at 36
# layers where the float32 tree's are 4.61. The engine now holds that
# tree with a layer's norms and biases stacked over the layers
# (serve_params; the "-held-stacked" cases), the "-held" ones being the
# same leaves in the module's layout: decode 4.13 MB, prefill 5.19, the
# 36-layer decode program 63.0 compiled for the described chip, under
# the same bounds. The 5-D slab read 363 MB
# for decode at 2 layers and 8.65 GB at 36: a relayout of either slab
# is at least 42 MB here (int8, 2 layers), 756 MB at 36. The deep cases
# are there because depth changes what the compiler does: at 24 layers
# and more it served the copy-on-write gather of whole pages by copying
# the slab in lane chunks ([36, 513, 16, 384] x 3 and [.., 128]), which
# no 2-layer compile shows.
SERVE_PROGRAMS = {
    "decode": ("decode", 2, 160e6, None),
    "prefill": ("prefill", 4, 160e6, None),
    "multi": ("multi", 3, 165e6, None),
    "verify": ("verify", 2, 470e6, None),
    "decode-36-layers": ("decode", 36, 400e6, None),
    "decode-held": ("decode", 2, 5.5e6, "leaves"),
    "prefill-held": ("prefill", 4, 7.1e6, "leaves"),
    "decode-36-layers-held": ("decode", 36, 92e6, "leaves"),
    "decode-held-stacked": ("decode", 2, 5.5e6, "stacked"),
    "prefill-held-stacked": ("prefill", 4, 7.1e6, "stacked"),
    "decode-36-layers-held-stacked": ("decode", 36, 92e6, "stacked"),
}
SERVE_CASES = [(name, kv) for name in SERVE_PROGRAMS
               for kv in ("f32", "int8")
               if not (name.startswith("decode-36-layers") and kv == "int8")]


def _serve_program(which, L, kv_dtype, sds, held=None):
    """(fn, args, donate_argnums, slab shape) of one serve program at
    the cell's widths and L layers, arguments as shapes on the
    described chip; `held` "stacked": the parameter trees as the engine
    puts them there (the family's serve_params), "leaves": the same
    leaves in the module's layout, None: float32 as initialised."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.models import gpt
    c = _SERVE

    def trunk(hidden, heads, ffn, layers):
        module = gpt.GPTModule(
            vocab_size=c["vocab"], max_len=c["max_len"], hidden=hidden,
            layers=layers, heads=heads, ffn=ffn, dropout=0.0,
            dtype=jnp.bfloat16)
        params = jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
        if held:
            family = module.serve_family()
            params = jax.eval_shape(family.serve_params, params)
            if held == "leaves":
                params = jax.eval_shape(family.module_params, params)
        return module, jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), params)

    module, params = trunk(c["heads"] * c["head_dim"], c["heads"],
                           c["ffn"], L)
    params = [params]                 # the leading, undonated arguments
    S, G = c["slots"], c["page"]
    Pmax = c["max_len"] // G
    P = S * Pmax + 1
    store = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    rows = (L, P, G, c["heads"] * c["head_dim"])
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    slab = [sds(rows, store), sds(rows, store), sds((L, P), f32),
            sds((L, P), f32), sds((P, G), f32)]
    kw = dict(kv_dtype=kv_dtype, attn_impl="pallas")
    if which == "decode":
        fn = gpt.build_paged_decode_step(module, **kw)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), u32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    elif which == "prefill":
        C = c["chunk"]
        fn = gpt.build_paged_prefill_step(module, C, **kw)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32)]
    elif which == "multi":
        fn = gpt.build_paged_multi_step_decode(module, c["steps"], **kw)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), f32), sds((S,), u32),
                sds((S,), i32), sds((S,), i32)]
    else:
        draft, draft_params = trunk(256, 4, 1024, 2)
        draft_params = [draft_params]
        W = c["window"]
        fn = gpt.build_paged_spec_verify_step(module, draft, c["steps"],
                                              W, **kw)
        params = params + draft_params
        rest = [sds((S, W), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), f32), sds((S,), u32),
                sds((S,), i32)]
    donate = tuple(range(len(params), len(params) + 5))
    return fn, params + slab + rest, donate, rows


def _result_shapes(hlo):
    """(name, element type, dims, layout, opcode) of every array-valued
    instruction in a compiled HLO text; layout is the braces' content up
    to the memory space, e.g. '3,2,1,0:T(8,128)(2,1)'."""
    import re
    pat = re.compile(
        r"^\s*(?:ROOT )?(%\S+) = (\w+)\[([0-9,]*)\]\{([^}]*)\} "
        r"([\w-]+)\(", re.M)
    return [(n, t, tuple(int(d) for d in dims.split(",") if d),
             re.sub(r"S\(\d+\)$", "", lay), op)
            for n, t, dims, lay, op in pat.findall(hlo)]


@pytest.mark.parametrize("name,kv_dtype", SERVE_CASES,
                         ids=["-".join(c) for c in SERVE_CASES])
def test_serve_program_keeps_the_slab_in_place_on_v5e(one_chip, name,
                                                      kv_dtype):
    """Compiled for the described chip, a serve program (a) yields no
    slab-shaped array in a layout other than its entry parameter's — no
    relayout of the slab, anywhere; (b) materializes no per-layer
    [pages, page_tokens, H*D] plane — the kernel indexes the slab by
    layer — and no cut of every page of every layer (the slab copied
    in lane chunks); (c) asks for temporaries under the stated bound; and still holds the
    Pallas kernel."""
    import jax

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = _SERVE
    which, layers, temp_bound, held = SERVE_PROGRAMS[name]
    fn, args, donate, rows = _serve_program(which, layers, kv_dtype, sds,
                                            held)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    el = "s8" if kv_dtype == "int8" else "bf16"
    shapes = [x for x in _result_shapes(hlo) if x[1] == el]
    entry = {lay for _, _, dims, lay, op in shapes
             if op == "parameter" and dims == rows}
    assert len(entry) == 1, entry
    relaid = [(n, lay, op) for n, _, dims, lay, op in shapes
              if dims == rows and lay not in entry]
    assert not relaid, relaid
    # row-major and unpadded: heads*head_dim is the minor dimension
    assert next(iter(entry)).startswith("3,2,1,0"), entry
    planes = [(n, op) for n, _, dims, _, op in shapes
              if dims == rows[1:] and op != "parameter"]
    assert not planes, planes
    chunks = [(n, dims, op) for n, _, dims, _, op in shapes
              if dims != rows and dims[:2] == rows[:2]]
    assert not chunks, chunks
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_bound, temp
    # (d) handed the held form, a program converts no parameter again:
    # nothing in it (an entry parameter, a fusion's) is a float32 array
    # of an embedding's or a kernel's whole shape, and no convert yields
    # a bfloat16 one. The float32 tree's programs do both, for every
    # such leaf: the check can fail.
    whole = {a.shape for path, a
             in jax.tree_util.tree_leaves_with_path(args[0])
             if path[-1].key in ("kernel", "embedding")}
    assert (c["vocab"], c["heads"] * c["head_dim"]) in whole \
        and len(whole) == 6, whole
    converted = [(n, t, dims, op) for n, t, dims, _, op
                 in _result_shapes(hlo) if dims in whole
                 and (t == "f32" or (t == "bf16" and op == "convert"))]
    assert bool(converted) != bool(held), converted[:8]


def _entry_arrays(hlo):
    """(opcode, dims, on chip) of every array an instruction of the
    ENTRY computation yields, a tuple's elements each; on chip: in
    memory space 1, the core's own memory, not HBM."""
    import re
    entry = re.search(r"^ENTRY .*?^}", hlo, re.M | re.S).group(0)
    out = []
    for types, op in re.findall(r"^\s*(?:ROOT )?%\S+ = (.*?) ([\w-]+)\(",
                                entry, re.M):
        for dims, lay in re.findall(r"\w+\[([0-9,]*)\]\{([^}]*)\}", types):
            out.append((op, tuple(int(d) for d in dims.split(",") if d),
                        lay.endswith("S(1)")))
    return out


@pytest.mark.parametrize("name", [n for n in SERVE_PROGRAMS
                                  if n.endswith("-held-stacked")])
def test_the_stacked_form_fetches_every_kernel_as_before_on_v5e(one_chip,
                                                                name):
    """The engine's held tree (a layer's norms and biases stacked over
    the layers, each kernel an argument: serve_params) against the same
    leaves in the module's layout, compiled for the described chip: no
    instruction but the entry parameter yields a stacked leaf's whole
    shape except into the core's own memory (the compiler fetches a
    small stack whole, as it fetches a bias); no more instructions yield
    one layer's kernel shape, and none a slice of one ([1, *kernel]:
    what a kernel stacked over the layers was read through); and no
    more temporaries. A kernel stacked over 36 layers was read inside
    its product or copied out synchronously, and the decode step took
    3.21 ms where it takes 2.71 per leaf (TPU v5e)."""
    import collections

    import jax

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    which, layers, temp_bound, _ = SERVE_PROGRAMS[name]
    compiled, arrays, trees = {}, {}, {}
    for held in ("leaves", "stacked"):
        fn, args, donate, _ = _serve_program(which, layers, "f32", sds, held)
        compiled[held] = jax.jit(fn, donate_argnums=donate).lower(
            *args).compile()
        arrays[held] = _entry_arrays(compiled[held].as_text())
        trees[held] = args[0]
    # one layer's kernel shapes, as the module's layout has them
    kernels = {a.shape for path, a
               in jax.tree_util.tree_leaves_with_path(trees["leaves"])
               if path[-1].key == "kernel"}
    assert len(kernels) == 4, kernels
    stacks = {a.shape for a in jax.tree_util.tree_leaves(
        trees["stacked"]["layers"])}
    # (an async copy's start names its source among its results)
    whole = [(op, dims) for op, dims, on_chip in arrays["stacked"]
             if dims in stacks and not on_chip
             and op not in ("parameter", "copy-start", "slice-start")]
    assert not whole, whole
    sliced = [(op, dims) for op, dims, _ in arrays["stacked"]
              if dims[:1] == (1,) and dims[1:] in kernels]
    assert not sliced, sliced[:8]

    def kernel_shaped(held):
        return collections.Counter(op for op, dims, _ in arrays[held]
                                   if dims in kernels and op != "parameter")
    assert sum(kernel_shaped("stacked").values()) \
        <= sum(kernel_shaped("leaves").values()), (
            kernel_shaped("stacked"), kernel_shaped("leaves"))
    temps = {k: c.memory_analysis().temp_size_in_bytes
             for k, c in compiled.items()}
    assert temps["stacked"] <= temps["leaves"] < temp_bound, temps
    # and the held tree is what serve_params' docstring says: ten kinds
    # stacked over the layers, each kernel its own leaf
    held = trees["stacked"]
    assert len(jax.tree_util.tree_leaves(held["layers"])) == 10
    assert all(s[0] == layers for s in stacks), stacks
    assert len(jax.tree_util.tree_leaves(held)) == 4 + 10 + 6 * layers


@pytest.mark.parametrize("name", ["decode-held", "prefill-held",
                                  "decode-held-stacked",
                                  "prefill-held-stacked"])
def test_the_packed_entry_adds_next_to_nothing_on_v5e(one_chip, name):
    """What the engine jits (serve/engine.py _packed_entry): the same
    program behind the slices and bitcasts of its ONE host buffer, and
    for decode the `from_prev` select. Compiled for the described chip
    it still holds the kernel, keeps the slab where the bare program
    keeps it (donated in place) and asks for temporaries within 64 KB
    of the bare program's (read, sandbox compile, PR 36: decode
    4,354,560 -> 4,096,512 bytes at 2 layers, prefill 5,677,056 ->
    5,644,800 at 4: fewer, as it happens)."""
    import jax

    from kubeml_tpu.models import gpt
    from kubeml_tpu.serve import engine as engine_mod
    from kubeml_tpu.serve.pager import PageGeometry

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = _SERVE
    which, layers, _, held = SERVE_PROGRAMS[name]
    fn, args, donate, _ = _serve_program(which, layers, "f32", sds, held)
    Pmax = c["max_len"] // c["page"]
    geom = PageGeometry(slots=c["slots"], page=c["page"],
                        pages=c["slots"] * Pmax + 1, pages_per_slot=Pmax)
    family = gpt.GPTModule(vocab_size=8, max_len=8, hidden=8, layers=1,
                           heads=1, ffn=8).serve_family()
    packing = engine_mod._packing(which, family, geom, c["chunk"])
    lead = args[:1 + 5]
    if which == "decode":
        lead = lead + [sds((c["slots"],), "int32")]        # prev
    entry = engine_mod._packed_entry(
        fn, packing, c["slots"] if which == "decode" else 0)
    assert entry.__name__ == fn.__name__
    bare = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    packed = jax.jit(entry, donate_argnums=donate).lower(
        *lead, sds(packing.shape, "int32")).compile()
    hlo = packed.as_text()
    assert "tpu_custom_call" in hlo
    t_bare = bare.memory_analysis().temp_size_in_bytes
    t_packed = packed.memory_analysis().temp_size_in_bytes
    assert t_packed <= t_bare + 64 * 1024, (t_bare, t_packed)
    assert packed.memory_analysis().alias_size_in_bytes \
        == bare.memory_analysis().alias_size_in_bytes > 0


# ------------------------------------------- DeepSeek-V2 (latent pages)

# (slots, heads, row lanes, value lanes, page, max_pages): the cell
# deepseek-v2-ep4-serve.decode-heavy (64 slots, 128 heads against one
# shared row of 576 lanes padded to 640, 256 pages of 16 a slot) and the
# tiny preset the CPU tests run
MLA_GEOMETRIES = {
    "cell": (64, 128, 640, 512, 16, 256),
    "tiny": (4, 4, 256, 128, 16, 16),
}
_DS_CELL = dict(slots=64, page=16, pages_per_slot=256, chunk=512)


@pytest.mark.parametrize("name", sorted(MLA_GEOMETRIES))
def test_mla_paged_attention_compiles_for_v5e(one_chip, name):
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.mla_paged_attention import (
        mla_paged_attention, mla_paged_eligible)
    S, H, lanes, value_lanes, G, Pmax = MLA_GEOMETRIES[name]
    assert mla_paged_eligible(heads=H, row_lanes=lanes,
                              value_lanes=value_lanes, page=G,
                              max_pages=Pmax, dtype=jnp.bfloat16)
    L, P = 2, S * Pmax + 1
    hlo = _compile(
        functools.partial(mla_paged_attention, layer=L - 1,
                          value_lanes=value_lanes, scale=0.1,
                          impl="pallas"),
        one_chip, ((S, H, lanes), jnp.bfloat16),
        ((L, P, G, lanes), jnp.bfloat16), ((S, Pmax), jnp.int32),
        ((S,), jnp.int32))
    assert "tpu_custom_call" in hlo and "mla_paged_attention" in hlo


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_deepseek_v2_program_compiles_for_v5e_at_the_cells_sizes(
        one_chip, which):
    """Both programs of models/deepseek_v2.py at the PUBLISHED widths
    and the cell's geometry (5 layers, 40 of 160 experts, 64 slots, 256
    pages a slot, prefill chunk 512), bfloat16 parameters: the chip's
    compiler accepts them, they hold their kernel (the latent-page
    kernel in decode, the grouped expert product in prefill), the slab
    keeps its one layout, and arguments and temporaries together fit
    the chip's 16.9 GB. Read (sandbox compile, PR 27): decode 12.01 GB
    of arguments + 13 MB of temporaries, prefill 9.47 GB (the last
    layer's feed-forward and the head are dead code in a program that
    returns pages only) + 152 MB."""
    import importlib.util
    import os

    import jax
    import jax.numpy as jnp
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "models",
        "deepseek_v2_ep4.py")
    spec = importlib.util.spec_from_file_location("ds_ep4_model", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.DeepSeekV2EP4().module
    assert (m.hidden, m.heads, m.kv_lora_rank, m.n_held_experts) \
        == (5120, 128, 512, 40)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))["params"])
    c = _DS_CELL
    S, G, Pmax, C = c["slots"], c["page"], c["pages_per_slot"], c["chunk"]
    rows = (m.layers, S * Pmax + 1, G, m.row_lanes)
    i32, f32 = jnp.int32, jnp.float32
    family = m.serve_family()
    if which == "decode":
        fn = family.decode_step("f32", "pallas", False)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    else:
        fn = family.prefill_step(C, "f32", "pallas", False)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32)]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, sds(rows, jnp.bfloat16), *rest).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert ("mla_paged_attention" in hlo) == (which == "decode")
    shapes = [x for x in _result_shapes(hlo) if x[1] == "bf16"]
    entry = {lay for _, _, dims, lay, op in shapes
             if op == "parameter" and dims == rows}
    assert len(entry) == 1 and next(iter(entry)).startswith("3,2,1,0"), entry
    relaid = [(n, lay, op) for n, _, dims, lay, op in shapes
              if dims == rows and lay not in entry]
    assert not relaid, relaid
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 400e6, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.9e9
    if which == "prefill":
        # the expert layers' grouped products are the repo's kernel
        # (gate and up in one call, then down: PR 34), and no
        # `ragged-dot` call of the compiler's is left beside it
        assert family.moe_impl(C, "pallas", False) == "pallas"
        assert "grouped_matmul_gated" in hlo
        assert "ragged-dot" not in hlo and "ragged_dot" not in hlo
        # the attention loop's float32 scores [heads, chunk, keys] stay
        # in VMEM (memory space 1) at PREFILL_KEY_BLOCK keys a step; at
        # 512 keys they went through HBM three times a step and the loop
        # took twice as long on the chip (PERF.md, PR 27)
        from kubeml_tpu.models.deepseek_v2 import PREFILL_KEY_BLOCK
        import re
        shape = re.compile(
            rf"f32\[{m.heads},{C},{PREFILL_KEY_BLOCK}\]\{{[^}}]*\}}")
        scores = [x for line in hlo.splitlines() if " fusion(" in line
                  for x in shape.findall(line.split(" fusion(")[0])]
        assert len(scores) == m.layers - 1 and \
            all("S(1)" in x for x in scores), scores


# ------------------------- the prefill expert layer's grouped products

# (token-expert rows of a chunk of 512, hidden, expert width, held
# experts) of the two MoE cells
GROUPED_GEOMETRIES = {
    "deepseek-v2-ep4": (3072, 5120, 1536, 40),
    "k-exaone-ep8": (4096, 6144, 2048, 16),
}


@pytest.mark.parametrize("name", sorted(GROUPED_GEOMETRIES))
def test_grouped_matmul_compiles_for_v5e_within_the_vmem_it_declares(
        one_chip, name):
    """The expert MLP of a prefill chunk (ops/pallas/grouped_matmul.py:
    gate and up in one kernel, down in a second, one visit plan) at the
    cell's shapes: the chip's compiler accepts both calls, each is
    given the scoped-VMEM limit `grouped_vmem_bytes` computes (what
    `grouped_eligible` gates on) and uses no more than that. Read
    (sandbox compile, PR 34): 36.1-57.1 MB used of 40.2-71.3 MB declared."""
    import re

    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas import grouped_matmul as gm
    rows, d, f, held = GROUPED_GEOMETRIES[name]
    bf16 = jnp.bfloat16
    assert gm.resolve_mlp_impl("pallas", False, rows=rows, d=d, f=f) \
        == "pallas"
    hlo = _compile(
        functools.partial(gm.grouped_mlp, impl="pallas"), one_chip,
        ((rows, d), bf16), ((held, d, f), bf16), ((held, d, f), bf16),
        ((held, f, d), bf16), ((held,), jnp.int32))
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and "ragged-dot" not in hlo
    bounds = {"grouped_matmul_gated": gm.grouped_vmem_bytes(
                  rows, d, f, stacks=2),
              "grouped_matmul": gm.grouped_vmem_bytes(rows, f, d)}
    for call in calls:
        limit, used = (
            int(re.search(key + r'":\[\{[^}]*"size":"(\d+)"', call).group(1))
            for key in ("scoped_memory_configs",
                        "used_scoped_memory_configs"))
        kernel = re.search(r"%(grouped_matmul\w*?)(?:\.\d+)? =", call)
        assert kernel, call[:200]
        assert 0 < used <= limit == bounds[kernel.group(1)] \
            <= gm.VMEM_BUDGET


# ------------------------------- Jamba (per-slot state, grouped queries)

# the cell jamba2-3b-serve.decode-heavy: 128 slots, 384 pages of 16 a
# slot, prefill chunk 512, 26 Mamba layers of d_inner 5120 x d_state 16
_JAMBA_CELL = dict(slots=128, page=16, pages_per_slot=384, chunk=512)

# (batch, steps): every slot one step (the decode program), one slot a
# chunk (the prefill program), and the tiny preset's chunk
SCAN_GEOMETRIES = {
    "cell-decode": (128, 1, 5120, 26),
    "cell-prefill": (1, 512, 5120, 26),
    "tiny-prefill": (1, 16, 512, 2),
}


@pytest.mark.parametrize("name", sorted(SCAN_GEOMETRIES))
def test_selective_scan_compiles_for_v5e_in_place(one_chip, name):
    """The selective-scan kernel at the cell's two geometries: the
    chip's compiler accepts it, the state array is read and written in
    place (the result aliases the argument and the program holds no
    temporary: a copy of the array would be 1.09 GB of it), and no
    instruction yields a copy of the array."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.selective_scan import (scan_eligible,
                                                      selective_scan)
    batch, steps, d, layers = SCAN_GEOMETRIES[name]
    S, n = _JAMBA_CELL["slots"], 16
    assert scan_eligible(batch=batch, steps=steps, d_inner=d, d_state=n)
    f32, i32 = jnp.float32, jnp.int32

    def fn(state, x, dt, b, c, a, dd, valid, fresh, layer, slot0):
        return selective_scan(state, x, dt, b, c, a, dd, valid, fresh,
                              layer=layer, slot0=slot0, impl="pallas")

    shapes = (((layers, S, n, d), f32), ((batch, steps, d), f32),
              ((batch, steps, d), f32), ((batch, steps, n), f32),
              ((batch, steps, n), f32), ((n, d), f32), ((d,), f32),
              ((batch, steps), f32), ((batch,), i32), ((), i32), ((), i32))
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "selective_scan" in hlo
    mem = compiled.memory_analysis()
    state_bytes = layers * S * n * d * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 8
    whole = [x for x in _result_shapes(hlo)
             if x[2] == (layers, S, n, d) and x[4] in ("copy", "copy-start")]
    assert not whole, whole


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_paged_attention_grouped_queries_compile_for_v5e(one_chip,
                                                         kv_heads):
    """The cell's decode attention (20 query heads over ONE KV head of
    128, 128 slots, 384 pages a slot, no scale sidecars) and a two-KV-
    head point: eligible, accepted by the chip's compiler, and inside
    the VMEM bound the call is given."""
    import re

    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.paged_attention import (paged_attention,
                                                       paged_eligible,
                                                       paged_vmem_bytes)
    c = _JAMBA_CELL
    S, G, Pmax, H, D = c["slots"], c["page"], c["pages_per_slot"], 20, 128
    bf = jnp.bfloat16
    assert paged_eligible(G, q_len=1, heads=H, head_dim=D, max_pages=Pmax,
                          dtype=bf, kv_heads=kv_heads)
    bound = paged_vmem_bytes(1, H, D, G, Pmax, bf, False, kv_heads)
    rows = (2, S * Pmax + 1, G, kv_heads * D)

    def fn(q, k, v, tables, bias):
        return paged_attention(q, k, v, None, None, tables, bias, layer=1,
                               impl="pallas")

    hlo = _compile(fn, one_chip, ((S, 1, H, D), bf), (rows, bf), (rows, bf),
                   ((S, Pmax), jnp.int32), ((S, 1, 1, Pmax * G), jnp.float32))
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    call, = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    limit, used = (
        int(re.search(key + r'":\[\{[^}]*"size":"(\d+)"', call).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert 0 < used <= bound <= limit


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_jamba_serve_programs_compile_at_published_widths_for_v5e(
        one_chip, which):
    """Both programs of models/jamba.py at the PUBLISHED widths and the
    cell's geometry (28 layers, 128 slots, 384 pages a slot, prefill
    chunk 512), bfloat16 parameters: the chip's compiler accepts them,
    they hold the selective-scan kernel (and the decode program the
    paged-attention kernel), NO instruction yields a copy of either
    per-slot state array or of a page plane (a gather of pages once
    compiled to a copy of the whole slab: PERF.md, PR 26; here a
    reshape of the convolution's tail did, both ways, until its taps
    became lane slices), and arguments and temporaries together fit the
    chip's 16.9 GB. Read (sandbox compile, PR 31): decode 8.06 GB of
    arguments + 44 MB of temporaries, prefill 7.90 GB + 73 MB."""
    import importlib.util
    import os

    import jax
    import jax.numpy as jnp
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "models", "jamba2_3b.py")
    spec = importlib.util.spec_from_file_location("jamba2_3b_model", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.Jamba2_3B().module
    assert (m.hidden, m.layers, m.attn_layers, m.d_inner, m.kv_heads) \
        == (2560, 28, (7, 21), 5120, 1)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))["params"])
    c = _JAMBA_CELL
    S, G, Pmax, C = c["slots"], c["page"], c["pages_per_slot"], c["chunk"]
    family = m.serve_family()
    cache = family.cache
    plane = (cache.layers, S * Pmax + 1, G, cache.width)
    slot_arrays = [(st.layers, S) + tuple(st.shape)
                   for st in cache.slot_state]
    state = [sds(plane, cache.dtype)] * cache.planes + [
        sds(shape, st.dtype)
        for shape, st in zip(slot_arrays, cache.slot_state)]
    i32, f32 = jnp.int32, jnp.float32
    if which == "decode":
        fn = family.decode_step("f32", "pallas", False)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    else:
        fn = family.prefill_step(C, "f32", "pallas", False)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32),
                sds((), i32)]
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *rest).compile()
    hlo = compiled.as_text()
    # the kernels by their instructions' names (file names may appear
    # in a module's metadata whatever it calls)
    assert "%selective_scan" in hlo
    assert ("%paged_attention" in hlo) == (which == "decode")
    copies = [(n, dims, op) for n, _, dims, _, op in _result_shapes(hlo)
              if dims in [plane] + slot_arrays
              and op in ("copy", "copy-start", "gather")]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 200e6, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.9e9
    assert mem.alias_size_in_bytes >= sum(
        int(jnp.dtype(a.dtype).itemsize) * int(__import__("math").prod(
            a.shape)) for a in state)


_EXAONE_CELL = dict(slots=64, page=16, pages_per_slot=1152, chunk=512)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_exaone_moe_serve_programs_compile_at_published_widths_for_v5e(
        one_chip, which):
    """Both programs of models/exaone_moe.py at the PUBLISHED widths and
    the cell's geometry (5 layers L L L G L, 16 of 128 experts held, 64
    slots, 1,152 pages a slot, prefill chunk 512), bfloat16 parameters:
    the chip's compiler accepts them, the decode program holds the
    paged-attention kernel for its one global layer (64 query heads
    over 8 KV heads of 128), NO instruction yields a copy of either
    ring array or of a page plane (the decode lanes' row writes are a
    scatter in place, the chunk's a dynamic-update-slice of one slot's
    ring), and arguments and temporaries together fit the chip's
    16.9 GB beside every slot's pages at its cap. Read (sandbox compile,
    PR 33): decode 12.39 GB of arguments + 32 MB of temporaries,
    prefill 10.67 GB + 126 MB (the last layer's feed-forward and the
    head feed no state the chunk returns, so the compiler drops them
    and their parameters)."""
    import importlib.util
    import math
    import os

    import jax
    import jax.numpy as jnp
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "models",
        "k_exaone_ep8.py")
    spec = importlib.util.spec_from_file_location("k_exaone_ep8_model", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.KExaoneEP8().module
    assert (m.hidden, m.layers, m.window_layers, m.global_layers,
            m.kv_lanes, m.n_held_experts) \
        == (6144, 5, (0, 1, 2, 4), (3,), 1024, 16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))["params"])
    c = _EXAONE_CELL
    S, G, Pmax, C = c["slots"], c["page"], c["pages_per_slot"], c["chunk"]
    family = m.serve_family()
    cache = family.cache
    plane = (cache.layers, S * Pmax + 1, G, cache.width)
    rings = [(st.layers, S) + tuple(st.shape) for st in cache.slot_state]
    assert rings == [(4, 64, 128, 1024)] * 2
    state = [sds(plane, cache.dtype)] * cache.planes + [
        sds(shape, st.dtype) for shape, st in zip(rings, cache.slot_state)]
    i32, f32 = jnp.int32, jnp.float32
    if which == "decode":
        assert family.attn_impls(G, Pmax, C, "f32", "pallas", False) \
            == ("pallas", "gather")
        fn = family.decode_step("f32", "pallas", False)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    else:
        fn = family.prefill_step(C, "f32", "pallas", False)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32),
                sds((), i32)]
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *rest).compile()
    hlo = compiled.as_text()
    assert ("%paged_attention" in hlo) == (which == "decode")
    # a chunk's expert layers run the grouped-matmul kernel (PR 34); a
    # decode batch of 64 tokens takes the dense mask form
    assert family.moe_impl(C, "pallas", False) == "pallas"
    assert ("grouped_matmul_gated" in hlo) == (which == "prefill")
    assert "ragged-dot" not in hlo and "ragged_dot" not in hlo
    copies = [(n, dims, op) for n, _, dims, _, op in _result_shapes(hlo)
              if dims in [plane] + rings
              and op in ("copy", "copy-start", "gather")]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 300e6, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.7e9
    assert mem.alias_size_in_bytes >= sum(
        int(jnp.dtype(a.dtype).itemsize) * math.prod(a.shape)
        for a in state)


_LONGCAT_CELL = dict(slots=64, page=16, pages_per_slot=256, chunk=512)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_longcat_flash_serve_programs_compile_at_published_widths_for_v5e(
        one_chip, which):
    """Both programs of models/longcat_flash.py at the PUBLISHED widths
    and the cell's geometry (4 double layers, two MLA blocks of 64 heads
    each, 16 of 512 routed experts held beside 256 zero-compute ones, 64
    slots, 256 pages a slot, prefill chunk 512), bfloat16 parameters:
    the chip's compiler accepts them, the latent-page kernel passes its
    eligibility and VMEM gate at 64 heads and is in the decode program,
    the grouped expert product in the prefill one, NO instruction yields
    a copy or a gather of the latent plane array (8 rows a token), and
    arguments and temporaries together fit the chip's 16.9 GB."""
    import importlib.util
    import math
    import os

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.mla_paged_attention import (
        mla_paged_eligible, mla_vmem_bytes)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "models",
        "longcat_flash_ep32.py")
    spec = importlib.util.spec_from_file_location("longcat_ep32_model", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.LongCatFlashEP32().module
    assert (m.hidden, m.layers, m.heads, m.n_held_experts,
            m.router_outputs, m.moe_topk) == (6144, 4, 64, 16, 768, 12)
    c = _LONGCAT_CELL
    S, G, Pmax, C = c["slots"], c["page"], c["pages_per_slot"], c["chunk"]
    geom = dict(heads=m.heads, row_lanes=m.row_lanes,
                value_lanes=m.kv_lora_rank, page=G, max_pages=Pmax,
                dtype=jnp.bfloat16)
    assert mla_paged_eligible(**geom)
    assert mla_vmem_bytes(m.heads, m.row_lanes, m.kv_lora_rank, G * Pmax,
                          2) <= 40 * 2 ** 20

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))["params"])
    family = m.serve_family()
    cache = family.cache
    plane = (cache.layers, S * Pmax + 1, G, cache.width)
    assert plane == (8, 16385, 16, 640)
    i32, f32 = jnp.int32, jnp.float32
    if which == "decode":
        assert family.attn_impls(G, Pmax, C, "f32", "pallas", False) \
            == ("pallas", "gather")
        fn = family.decode_step("f32", "pallas", False)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    else:
        fn = family.prefill_step(C, "f32", "pallas", False)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32)]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, sds(plane, cache.dtype), *rest).compile()
    hlo = compiled.as_text()
    assert ("mla_paged_attention" in hlo) == (which == "decode")
    assert family.moe_impl(C, "pallas", False) == "pallas"
    assert ("grouped_matmul_gated" in hlo) == (which == "prefill")
    assert "ragged-dot" not in hlo and "ragged_dot" not in hlo
    copies = [(n, dims, op) for n, _, dims, _, op in _result_shapes(hlo)
              if dims == plane and op in ("copy", "copy-start", "gather")]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 400e6, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert mem.alias_size_in_bytes >= 2 * math.prod(plane)
    print(which, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# ------------------------- GigaChat3.5 (latent pages, matrix slot state)

# the cell gigachat3.5-ep16-serve.decode-heavy: 128 slots, 256 pages of
# 16 a slot, prefill chunk 512, 4 GatedDeltaNet layers of 64 value heads
# of [128 x 128] float32 state
_GIGACHAT_CELL = dict(slots=128, page=16, pages_per_slot=256, chunk=512)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_gated_delta_compiles_for_v5e_in_place(one_chip, which):
    """Both gated-delta kernels at the cell's geometry: the chip's
    compiler accepts them, within the scoped VMEM they declare, the
    state array (2.15 GB) is read and written in place (aliased, no
    temporary of its size, no instruction yields a copy of it)."""
    import re

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas import gated_delta as gd
    S, C = _GIGACHAT_CELL["slots"], _GIGACHAT_CELL["chunk"]
    L, H, D = 4, 64, 128
    f32, i32 = jnp.float32, jnp.int32
    rows = S if which == "decode" else C
    shapes = [((L, S, H, D, D), f32)] + [((rows, H, D), f32)] * 3 \
        + [((rows, H), f32)] * 2
    if which == "decode":
        assert gd.decode_eligible(slots=S, heads=H, dk=D, dv=D)

        def fn(state, q, k, v, g, beta, fresh, layer):
            return gd.gated_delta_decode(state, q, k, v, g, beta, fresh,
                                         layer=layer, impl="pallas")
        shapes += [((S,), i32), ((), i32)]
    else:
        assert gd.prefill_eligible(tokens=C, heads=H, dk=D, dv=D)

        def fn(state, q, k, v, g, beta, fresh, layer, slot):
            return gd.gated_delta_prefill(state, q, k, v, g, beta, fresh,
                                          layer=layer, slot=slot,
                                          impl="pallas")
        shapes += [((), i32)] * 3
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    hlo = compiled.as_text()
    name = "gated_delta" if which == "decode" else "gated_delta_chunk"
    assert f"%{name}" in hlo
    call, = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    limit, used = (
        int(re.search(key + r'":\[\{[^}]*"size":"(\d+)"', call).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert 0 < used <= limit == gd.VMEM_LIMIT
    state_bytes = L * S * H * D * D * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 16
    whole = [x for x in _result_shapes(hlo)
             if x[2] == (L, S, H, D, D) and x[4] in ("copy", "copy-start")]
    assert not whole, whole


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_gigachat_serve_programs_compile_at_published_widths_for_v5e(
        one_chip, which):
    """Both programs of models/gigachat.py at the PUBLISHED widths and
    the cell's geometry (5 layers: GatedDeltaNet at 0, 1, 2, 4, MLA at
    3; 16 of 256 experts held; 128 slots, 256 pages a slot, prefill
    chunk 512), bfloat16 parameters: the chip's compiler accepts them,
    they hold their kernels (the gated-delta kernel in both, the
    latent-page kernel in decode, the grouped expert product in both),
    NO instruction yields a copy or a gather of the latent
    plane or of either per-slot state array, and arguments and
    temporaries together fit the chip's 16.9 GB."""
    import importlib.util
    import math
    import os

    import jax
    import jax.numpy as jnp
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "models",
        "gigachat35_ep16.py")
    spec = importlib.util.spec_from_file_location("gigachat_ep16", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    m = mod.GigaChat35EP16().module
    assert (m.hidden, m.layers, m.linear_layers, m.attn_layers,
            m.n_held_experts) == (7168, 5, (0, 1, 2, 4), (3,), 16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))["params"])
    c = _GIGACHAT_CELL
    S, G, Pmax, C = c["slots"], c["page"], c["pages_per_slot"], c["chunk"]
    family = m.serve_family()
    cache = family.cache
    plane = (cache.layers, S * Pmax + 1, G, cache.width)
    slot_arrays = [(st.layers, S) + tuple(st.shape)
                   for st in cache.slot_state]
    assert plane == (1, 32769, 16, 640)
    assert slot_arrays == [(4, 128, 64, 128, 128), (4, 128, 49152)]
    state = [sds(plane, cache.dtype)] + [
        sds(shape, st.dtype)
        for shape, st in zip(slot_arrays, cache.slot_state)]
    i32, f32 = jnp.int32, jnp.float32
    assert family.gdn_impls(S, C, "pallas", False) == ("pallas", "pallas")
    if which == "decode":
        assert family.attn_impls(G, Pmax, C, "f32", "pallas", False) \
            == ("pallas", "gather")
        fn = family.decode_step("f32", "pallas", False)
        rest = [sds((S,), i32), sds((S,), i32), sds((S, Pmax), i32),
                sds((S,), i32), sds((S,), i32), sds((S,), f32),
                sds((S,), f32), sds((S, 2), jnp.uint32), sds((S,), i32),
                sds((S,), i32), sds((S,), f32)]
    else:
        fn = family.prefill_step(C, "f32", "pallas", False)
        rest = [sds((C,), i32), sds((C,), i32), sds((Pmax,), i32),
                sds((C,), i32), sds((C,), i32), sds((C,), f32),
                sds((), i32)]
    compiled = jax.jit(fn, donate_argnums=(1, 2, 3)).lower(
        params, *state, *rest).compile()
    hlo = compiled.as_text()
    assert ("%gated_delta_chunk" in hlo) == (which == "prefill")
    assert ("%gated_delta." in hlo or "%gated_delta =" in hlo) \
        == (which == "decode")
    assert ("mla_paged_attention" in hlo) == (which == "decode")
    # a decode batch of 128 tokens is past DENSE_MOE_TOKENS too: both
    # programs take the grouped expert product
    assert family.moe_impl(C, "pallas", False) == "pallas" \
        == family.moe_impl(S, "pallas", False)
    assert "grouped_matmul_gated" in hlo
    copies = [(n, dims, op) for n, _, dims, _, op in _result_shapes(hlo)
              if dims in [plane] + slot_arrays
              and op in ("copy", "copy-start", "gather")]
    assert not copies, copies
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 400e6, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    assert mem.alias_size_in_bytes >= sum(
        int(jnp.dtype(a.dtype).itemsize) * math.prod(a.shape)
        for a in state)
    print(which, mem.argument_size_in_bytes, mem.temp_size_in_bytes)


# ---------------------------------------------------- flash attention

FLASH_SHAPES = {
    "B16-T128-f32": (16, 128, 4, 64, "float32"),
    "B4-T2048-bf16": (4, 2048, 4, 64, "bfloat16"),
}


@pytest.mark.parametrize("backward", [False, True],
                         ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_attention_compiles_for_v5e(one_chip, name, backward):
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.flash_attention import flash_attention
    B, T, H, D, dtype_name = FLASH_SHAPES[name]
    dtype = getattr(jnp, dtype_name)

    def fwd(q, k, v, pad_mask):
        return flash_attention(q, k, v, pad_mask, True)

    def loss(q, k, v, pad_mask):
        return fwd(q, k, v, pad_mask).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    hlo = _compile(fn, one_chip, ((B, T, H, D), dtype),
                   ((B, T, H, D), dtype), ((B, T, H, D), dtype),
                   ((B, T), jnp.float32))
    assert "tpu_custom_call" in hlo


# -------------------------------------------------------- fused merge

@pytest.mark.parametrize("n", [2 ** 20, 11_173_962],
                         ids=["1Mi", "resnet18"])
@pytest.mark.parametrize("mode", ["avg", "sgd"])
def test_fused_merge_compiles_for_v5e(one_chip, mode, n):
    """One flat f32 merge bucket: 2^20 elements (a 4 MB bucket) and the
    whole ResNet-18 parameter vector (11,173,962, not lane-aligned)."""
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.fused_merge import (fused_avg_select,
                                                   fused_sgd_select)
    scalar = ((), jnp.float32)
    if mode == "avg":
        fn = functools.partial(fused_avg_select, fused=True)
        shapes = (((n,), jnp.float32), ((n,), jnp.float32), scalar, scalar)
    else:
        fn = functools.partial(fused_sgd_select, fused=True)
        shapes = (((n,), jnp.float32), ((n,), jnp.float32), scalar, scalar,
                  scalar)
    assert "tpu_custom_call" in _compile(fn, one_chip, *shapes)
