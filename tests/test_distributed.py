"""Multi-slice mesh construction + K-avg over a slice-major data axis.

Emulates a 2-slice x 4-chip cluster on the 8 virtual CPU devices
(n_slices forces the contiguous split, since virtual devices carry no
slice_index). Checks the layout contract of
kubeml_tpu/parallel/distributed.py: data axis slice-major, inner axes
confined to a slice, and the unchanged KAvgEngine running end-to-end
over the resulting mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.parallel import distributed
from kubeml_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def test_group_by_slice_forced_split():
    devs = jax.devices()
    slices = distributed.group_by_slice(devs, n_slices=2)
    assert [len(s) for s in slices] == [4, 4]
    assert slices[0] == devs[:4] and slices[1] == devs[4:]


def test_group_by_slice_rejects_uneven():
    with pytest.raises(ValueError):
        distributed.group_by_slice(jax.devices(), n_slices=3)


def test_multislice_mesh_slice_major_data_axis():
    mesh = distributed.make_multislice_mesh(n_slices=2)
    assert mesh.shape[DATA_AXIS] == 8
    devs = jax.devices()
    # data lane d = slice * 4 + in-slice lane: first 4 lanes on slice 0
    flat = list(mesh.devices.reshape(8))
    assert flat[:4] == devs[:4] and flat[4:] == devs[4:]


def test_multislice_mesh_inner_axis_within_slice():
    mesh = distributed.make_multislice_mesh(n_model=2, n_slices=2)
    assert mesh.shape[DATA_AXIS] == 4 and mesh.shape[MODEL_AXIS] == 2
    # every model-axis pair must live inside one slice
    devs = jax.devices()
    slice_of = {d: 0 for d in devs[:4]} | {d: 1 for d in devs[4:]}
    grid = mesh.devices.reshape(4, 2)
    for row in grid:
        assert slice_of[row[0]] == slice_of[row[1]]


def test_multislice_mesh_rejects_inner_crossing_slice():
    with pytest.raises(ValueError):
        distributed.make_multislice_mesh(n_model=8, n_slices=2)


def test_kavg_round_over_multislice_mesh():
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.parallel.kavg import KAvgEngine

    mesh = distributed.make_multislice_mesh(n_slices=2)
    model = get_builtin("lenet")()
    rng = np.random.RandomState(0)
    W, S, B = 8, 2, 4
    x = rng.rand(W, S, B, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, size=(W, S, B)).astype(np.int32)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x[0, 0])})
    engine = KAvgEngine(mesh, model.loss, model.metrics,
                        model.configure_optimizers, donate=False)
    new_vars, stats = engine.train_round(
        variables, {"x": jnp.asarray(x), "y": jnp.asarray(y)},
        sample_mask=np.ones((W, S, B), np.float32),
        step_mask=np.ones((W, S), np.float32),
        worker_mask=np.ones(W, np.float32),
        rngs=rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32),
        lr=0.05, epoch=0)
    assert stats.contributors == W
    # params actually moved
    before = jax.tree_util.tree_leaves(variables["params"])[0]
    after = jax.tree_util.tree_leaves(new_vars["params"])[0]
    assert not np.allclose(np.asarray(before), np.asarray(after))


def test_initialize_single_process_noop():
    # must not raise or hang on a single-process host
    distributed.initialize()
    assert distributed.is_coordinator()


def test_initialize_bare_environment_never_dials(monkeypatch):
    """No arguments, no KUBEML_COORDINATOR_ADDRESS, no cluster
    environment: initialize() returns WITHOUT calling
    jax.distributed.initialize at all — on a TPU host with no network
    JAX's own cluster detection may wait on a metadata server, and
    `kubeml serve` must start regardless."""
    for var in distributed.CLUSTER_ENV_VARS:
        monkeypatch.delenv(var, raising=False)

    def dial(*a, **kw):
        raise AssertionError("jax.distributed.initialize was called")

    monkeypatch.setattr(distributed.jax.distributed, "initialize", dial)
    distributed.initialize()
    distributed.initialize(None, None, None)   # cli/main.py's call


def test_initialize_cluster_environment_still_dials(monkeypatch):
    """An environment that names a multi-host cluster is joined as
    before (and a failed join propagates)."""
    for var in distributed.CLUSTER_ENV_VARS:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("KUBEML_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("KUBEML_NUM_PROCESSES", "2")
    monkeypatch.setenv("KUBEML_PROCESS_ID", "1")
    calls = []
    monkeypatch.setattr(distributed.jax.distributed, "initialize",
                        lambda **kw: calls.append(kw) or (_ for _ in ())
                        .throw(RuntimeError("no coordinator")))
    with pytest.raises(RuntimeError, match="no coordinator"):
        distributed.initialize()
    assert calls == [{"coordinator_address": "127.0.0.1:1",
                      "num_processes": 2, "process_id": 1}]


def test_initialize_explicit_args_failure_raises():
    # explicit bring-up must not silently degrade to single-process: here
    # the backend is already initialized, so the join fails immediately
    # and must propagate instead of being swallowed.
    with pytest.raises((RuntimeError, ValueError)):
        distributed.initialize(coordinator_address="127.0.0.1:1",
                               num_processes=2, process_id=1)
