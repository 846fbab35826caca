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
# one wide point so the layout is not fitted to a toy
PAGED_GEOMETRIES = {
    "decode-bf16": (8, 1, 4, 64, 16, 32, "bfloat16", False),
    "decode-f32": (8, 1, 4, 64, 16, 32, "float32", False),
    "decode-int8": (8, 1, 4, 64, 16, 32, "bfloat16", True),
    "prefill-bf16": (1, 16, 4, 64, 16, 32, "bfloat16", False),
    "prefill-f32": (1, 16, 4, 64, 16, 32, "float32", False),
    "prefill-int8": (1, 16, 4, 64, 16, 32, "bfloat16", True),
    "wide-decode-bf16": (8, 1, 16, 128, 16, 64, "bfloat16", False),
    # the largest context the VMEM gate still sends to the kernel at the
    # wide geometry (bound 39.0 of the 40 MiB budget): what
    # paged_eligible admits, the compiler must accept
    "wide-budget-edge-bf16": (2, 1, 16, 128, 16, 272, "bfloat16", False),
}


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
    P = S * Pmax + 1
    page_dtype = jnp.int8 if quantized else dtype
    hlo = _compile(
        functools.partial(paged_attention, quantized=quantized,
                          compute_dtype=dtype, impl="pallas"),
        one_chip,
        ((S, T, H, D), dtype), ((P, G, H, D), page_dtype),
        ((P, G, H, D), page_dtype), ((P,), jnp.float32),
        ((P,), jnp.float32), ((S, Pmax), jnp.int32),
        ((S, 1, T, Pmax * G), jnp.float32))
    assert "tpu_custom_call" in hlo


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
