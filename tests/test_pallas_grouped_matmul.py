"""The grouped-matmul kernel (ops/pallas/grouped_matmul.py) through the
pallas interpreter: against `lax.ragged_dot`, which it replaces under a
prefill chunk's expert layers and which stays its fallback, and against
a float32 product of every row with its own group's matrix. A compile
for the chip is tests/test_chip_compile.py's; a time is the chip's
(tools/bench_grouped_matmul.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from kubeml_tpu.ops.pallas import grouped_matmul as gm

BF16, F32 = jnp.bfloat16, jnp.float32


def _operands(m, k, n, groups, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (m, k), F32).astype(dtype)
    rhs = (jax.random.normal(ks[1], (groups, k, n), F32) * k ** -0.5
           ).astype(dtype)
    return lhs, rhs


def _masked_dense(lhs, rhs, sizes):
    """Every row times its own group's matrix, in float32; rows past
    the groups' sum are zero."""
    ends = np.cumsum(sizes)
    group_of = np.searchsorted(ends, np.arange(lhs.shape[0]), side="right")
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    lhs, rhs = np.asarray(lhs, np.float32), np.asarray(rhs, np.float32)
    for g in range(rhs.shape[0]):
        rows = group_of == g
        out[rows] = lhs[rows] @ rhs[g]
    return out


# (m, k, n, group sizes, dtype): the row tile is the largest divisor of
# m up to 128 in steps of 16, so m = 256 walks tiles of 128 and m = 96
# tiles of 96 / 48
CASES = {
    "empty-groups-first": (256, 128, 256, [0, 0, 40, 100], BF16),
    "empty-groups-last": (256, 128, 256, [90, 33, 0, 0], BF16),
    "empty-groups-in-the-middle": (256, 128, 256, [17, 0, 0, 60, 0, 5], BF16),
    "a-group-spans-three-tiles": (512, 128, 128, [100, 300, 12], BF16),
    "many-groups-in-one-tile": (256, 128, 128, [3, 1, 7, 2, 5, 4, 9, 6],
                                BF16),
    "sum-zero": (256, 128, 256, [0, 0, 0, 0], BF16),
    "sum-a-quarter": (512, 128, 256, [19, 25, 11, 30, 16, 27], BF16),
    "sum-all": (256, 128, 256, [64, 64, 100, 28], BF16),
    "one-group-all-rows": (256, 128, 128, [256], BF16),
    "tile-boundaries-exact": (384, 128, 128, [128, 128, 128], BF16),
    "float32-operands": (256, 128, 256, [10, 0, 70, 33], F32),
    "unaligned-widths-interpreter-only": (96, 40, 24, [5, 50, 0, 9], F32),
    "deepseek-v2-widths-up": (256, 5120, 1536, [19, 0, 23], BF16),
    "deepseek-v2-widths-down": (256, 1536, 5120, [19, 0, 23], BF16),
    "k-exaone-widths-up": (256, 6144, 2048, [32, 31], BF16),
    "k-exaone-widths-down": (256, 2048, 6144, [32, 31], BF16),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_ragged_dot_on_every_real_row(name):
    m, k, n, sizes, dtype = CASES[name]
    lhs, rhs = _operands(m, k, n, len(sizes), dtype)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    real = int(sum(sizes))
    got = jax.block_until_ready(gm.grouped_matmul(
        lhs, rhs, group_sizes, impl="pallas", interpret=True))
    assert got.shape == (m, n) and got.dtype == F32
    got = np.asarray(got)[:real]
    ragged = np.asarray(lax.ragged_dot(
        lhs, rhs, group_sizes, preferred_element_type=F32))[:real]
    dense = _masked_dense(lhs, rhs, sizes)[:real]
    # the same operands and float32 sums in another order
    np.testing.assert_allclose(got, ragged, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got, dense, atol=2e-4, rtol=0)
    assert real == 0 or np.abs(dense).max() > 0.5
    # the fallback is the op as it stood, bit for bit
    np.testing.assert_array_equal(
        np.asarray(gm.grouped_matmul(lhs, rhs, group_sizes, impl="gather")),
        np.asarray(lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=F32)))


@pytest.mark.parametrize("sizes", [[0, 0, 0, 0], [19, 25, 0, 30],
                                   [100, 0, 28, 128]],
                         ids=["sum-zero", "sum-a-quarter", "sum-all"])
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
def test_the_expert_mlp_is_the_three_ragged_dots(sizes, dtype):
    """gate and up in one visit, silu(g) * u rounded to the operands'
    dtype where the op chain rounds it, then down under the same plan."""
    m, d, f = 256, 256, 128
    rows, w_gate = _operands(m, d, f, len(sizes), dtype, seed=1)
    _, w_up = _operands(m, d, f, len(sizes), dtype, seed=2)
    _, w_down = _operands(m, f, d, len(sizes), dtype, seed=3)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    real = int(sum(sizes))
    got = jax.block_until_ready(gm.grouped_mlp(
        rows, w_gate, w_up, w_down, group_sizes, impl="pallas",
        interpret=True))
    want = gm.grouped_mlp(rows, w_gate, w_up, w_down, group_sizes,
                          impl="gather")
    assert got.shape == (m, d) and got.dtype == F32
    # bfloat16: one rounding of the activation may fall the other way
    np.testing.assert_allclose(np.asarray(got)[:real],
                               np.asarray(want)[:real],
                               atol=2e-5 if dtype == F32 else 2e-2, rtol=0)


def test_visit_plan_lists_each_group_and_tile_pair_once_in_order():
    sizes = np.asarray([0, 100, 0, 30, 300, 0, 2, 0], np.int32)
    m, tm = 512, 128
    offsets, group_of, tile_of, visits = (
        np.asarray(a) for a in gm.visit_plan(jnp.asarray(sizes), m, tm))
    ends = np.cumsum(sizes)
    want = [(g, t) for g in range(len(sizes)) if sizes[g]
            for t in range((ends[g] - sizes[g]) // tm,
                           (ends[g] - 1) // tm + 1)]
    n = int(visits[0])
    assert list(zip(group_of[:n], tile_of[:n])) == want
    assert list(offsets) == [0] + list(ends)
    # the list is as long as the most visits there can be, and the
    # entries past the last visit stay inside the operands
    assert len(group_of) == len(tile_of) == m // tm + len(sizes) - 1
    assert ((group_of >= 0) & (group_of < len(sizes))).all()
    assert ((tile_of >= 0) & (tile_of < m // tm)).all()
    # no real row: no visit, and the entries stay inside the operands
    _, group_of, tile_of, visits = (np.asarray(a) for a in gm.visit_plan(
        jnp.zeros(8, jnp.int32), m, tm))
    assert int(visits[0]) == 0
    assert ((group_of >= 0) & (group_of < 8)).all()
    assert ((tile_of >= 0) & (tile_of < m // tm)).all()


def test_dispatch_follows_the_package_contract():
    lhs, rhs = _operands(256, 128, 256, 4, BF16)
    sizes = jnp.asarray([10, 20, 30, 40], jnp.int32)
    cells = {"deepseek_v2": dict(rows=3072, d=5120, f=1536),
             "exaone_moe": dict(rows=4096, d=6144, f=2048)}
    # on the CPU 'auto' is the fallback, whatever the shapes
    assert gm.resolve_impl("auto", False, m=256, k=128, n=256) == "gather"
    assert gm.resolve_impl("auto", True, m=256, k=128, n=256) == "pallas"
    for cell in cells.values():
        assert gm.resolve_mlp_impl("auto", False, **cell) == "gather"
        assert gm.resolve_mlp_impl("auto", True, **cell) == "pallas"
        assert gm.resolve_mlp_impl("pallas", False, **cell) == "pallas"
        rows, d, f = cell["rows"], cell["d"], cell["f"]
        for geom in (dict(m=rows, k=d, n=f, stacks=2),
                     dict(m=rows, k=f, n=d, stacks=1)):
            assert gm.grouped_eligible(**geom)
            tm, tn = gm.geometry(**geom)
            assert tm == 128 and tn % 128 == 0 and geom["n"] % tn == 0
            assert gm.grouped_vmem_bytes(**geom) <= gm.VMEM_BUDGET
    # widths that are no whole lane tiles: 'auto' falls back even under
    # the interpreter, a forced kernel is refused outside it
    assert gm.resolve_impl("auto", True, m=96, k=40, n=24) == "gather"
    assert gm.resolve_mlp_impl("auto", True, rows=256, d=128, f=72) \
        == "gather"
    with pytest.raises(ValueError, match="whole lane tiles"):
        gm.grouped_matmul(*_operands(96, 40, 24, 4, F32), sizes,
                          impl="pallas")
    with pytest.raises(ValueError, match="impl must be one of"):
        gm.grouped_matmul(lhs, rhs, sizes, impl="ragged")
    with pytest.raises(ValueError, match="group_sizes"):
        gm.grouped_matmul(lhs, rhs, sizes[:3], impl="pallas",
                          interpret=True)
    with pytest.raises(ValueError, match="one dtype"):
        gm.grouped_matmul(lhs, rhs.astype(F32), sizes, impl="pallas",
                          interpret=True)


def test_the_layers_select_leaves_nothing_of_the_rows_past_the_groups():
    """The kernel writes no row past the last group (the interpreter
    leaves NaN there, the chip whatever the buffer held): after
    held_expert_layer's select the layer's output is finite and is the
    fallback's, with three quarters of the pairs choosing an absent
    expert and with every token idle."""
    from kubeml_tpu.models import exaone_moe as ex
    m = dataclasses.replace(ex.ExaoneMoEModule(dtype=F32), ep_rank=2)
    p = m.init(jax.random.PRNGKey(3))["params"]["layer_1"]
    tokens = 80
    h = jnp.asarray(np.random.default_rng(2).normal(
        size=(tokens, m.hidden)).astype(np.float32))
    assert tokens > ex.DENSE_MOE_TOKENS
    rows = tokens * m.experts_per_tok
    for live in (jnp.ones(tokens), jnp.zeros(tokens),
                 (jnp.arange(tokens) < 50).astype(F32)):
        # one program, read before anything else is dispatched: the
        # interpreter's callbacks run JAX operations of their own
        got, counts = jax.block_until_ready(jax.jit(
            lambda h, p, live: ex._ffn(m, 1, h, p, live, "pallas", True))(
                h, p, live))
        want, want_counts = ex._ffn(m, 1, h, p, live, "gather", False)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(want_counts))
        assert int(counts[1]) < rows // 2       # most rows are no group's
    # what the select has to clean: the kernel alone leaves rows past
    # the groups unwritten
    lhs, rhs = _operands(512, 128, 128, 4, F32)
    raw = np.asarray(gm.grouped_matmul(
        lhs, rhs, jnp.asarray([5, 0, 9, 3], jnp.int32), impl="pallas",
        interpret=True))
    assert np.isfinite(raw[:17]).all()
    assert not np.isfinite(raw[128:]).all()
