"""ring_psum: the ppermute ring all-reduce used for compressed merges on
meshes whose inner axes stay Auto (a partially-manual sub-f32 lax.psum
is a fatal partitioner miscompile — parallel/collectives.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeml_tpu.parallel.collectives import ring_psum
from kubeml_tpu.parallel.mesh import DATA_AXIS, make_mesh


def run_ring(mesh, x, wire_dtype, **shmap_kw):
    return jax.jit(jax.shard_map(
        lambda v: ring_psum(v, DATA_AXIS, wire_dtype), mesh=mesh,
        in_specs=P(DATA_AXIS), out_specs=P(), check_vma=False,
        **shmap_kw))(jnp.asarray(x))


@pytest.mark.parametrize("n", [37, 64, 1])  # incl. padding + degenerate
def test_ring_matches_psum_f32(mesh8, n):
    x = np.random.RandomState(0).randn(8, n).astype(np.float32)
    out = run_ring(mesh8, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(out)[0], x.sum(axis=0),
                               rtol=1e-5, atol=1e-6)


def test_ring_bf16_wire_tolerance(mesh8):
    x = np.random.RandomState(1).randn(8, 257).astype(np.float32)
    out = run_ring(mesh8, x, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(out, np.float32)[0],
                               x.sum(axis=0), rtol=5e-2, atol=5e-2)
    # really compressed: not bit-equal to the f32 reduction
    assert not np.allclose(np.asarray(out, np.float32)[0], x.sum(axis=0),
                           rtol=1e-6, atol=0)


def test_ring_on_partially_manual_mesh(mesh4x2):
    """THE case the builtin cannot do: bf16 wire, data manual, model
    Auto. A direct sub-f32 psum here kills the process."""
    x = np.random.RandomState(2).randn(4, 100).astype(np.float32)
    out = run_ring(mesh4x2, x, jnp.bfloat16,
                   axis_names={DATA_AXIS})
    np.testing.assert_allclose(np.asarray(out, np.float32)[0],
                               x.sum(axis=0), rtol=5e-2, atol=5e-2)


def test_ring_single_lane_passthrough():
    mesh = make_mesh(n_data=1, devices=jax.devices()[:1])
    x = np.random.RandomState(3).randn(1, 16).astype(np.float32)
    out = run_ring(mesh, x, jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out), x)


@pytest.mark.parametrize("wire", [jnp.bfloat16, jnp.float32])
def test_ring_lane_identity(mesh8, wire):
    """The replicated out-spec contract: EVERY lane must hold the
    bit-identical reduced value, including the 1/D chunk each rank owns
    (which, pre-fix, the owner kept in unrounded f32 while everyone
    else stored the wire-rounded copy)."""
    x = np.random.RandomState(5).randn(8, 193).astype(np.float32)
    per_lane = jax.jit(jax.shard_map(
        lambda v: ring_psum(v, DATA_AXIS, wire)[None],
        mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        check_vma=False))(jnp.asarray(x))
    out = np.asarray(per_lane, np.float32)            # [8, 193]
    for lane in range(1, 8):
        np.testing.assert_array_equal(out[lane], out[0])


def test_ring_multidim_leaves(mesh8):
    """Weight-shaped (non-flat) leaves reduce correctly through the
    flatten/pad path."""
    x = np.random.RandomState(4).randn(8, 3, 5, 2).astype(np.float32)
    out = run_ring(mesh8, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(out)[0], x.sum(axis=0),
                               rtol=1e-5, atol=1e-6)
