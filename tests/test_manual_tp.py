"""Manual (fully-manual shard_map) tensor parallelism — parallel/manual.py.

Pins the round-3 composability matrix: manual Megatron TP equals the
dense forward/grads, trains through the K-avg engine, composes with
sequence parallelism in ONE round (round 2's exclusion), and with the
compressed (sub-f32) merge on fully-manual meshes.

Runs on the 8-virtual-CPU-device mesh (conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from kubeml_tpu.models import get_builtin
from kubeml_tpu.parallel.kavg import KAvgEngine
from kubeml_tpu.parallel.mesh import MODEL_AXIS, SEQ_AXIS, make_mesh


@pytest.fixture(scope="module")
def tp2_mesh():
    return make_mesh(n_data=1, n_model=2, devices=jax.devices()[:2])


def _bert_fixture(dropout=0.0):
    model = get_builtin("bert-tiny")()
    model._module = model.module.clone(dropout=dropout)
    return model


def _tiny_gpt(dropout=0.0):
    from tests.test_models_gpt import TinyGPT
    model = TinyGPT()
    model._module = model.module.clone(dropout=dropout)
    return model


def _manual_forward(model, variables, x, mesh):
    """Dense-variables forward through the manual-TP module inside a
    fully-manual shard_map (explicit psums make the output replicated)."""
    tp_module = model.module.clone(tp_axis=MODEL_AXIS)

    def fwd(v, x):
        return tp_module.apply(v, x, train=False)

    return jax.jit(jax.shard_map(
        fwd, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False))(variables, x)


@pytest.mark.parametrize("dtype,rtol,atol", [
    # f32: the TP decomposition is mathematically exact (pins the
    # collective placement); bf16: production dtype, rounding-order noise
    (jnp.float32, 1e-5, 1e-5),
    (jnp.bfloat16, 5e-2, 2e-2),
])
def test_bert_manual_tp_forward_matches_dense(tp2_mesh, dtype, rtol, atol):
    model = _bert_fixture()
    model._module = model.module.clone(dtype=dtype)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(1, 1000, size=(4, 16)).astype(np.int32))
    variables = model.init_variables(jax.random.PRNGKey(0), {"x": x})
    ref = model.module.apply(variables, x, train=False)
    out = _manual_forward(model, variables, x, tp2_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 1e-5, 1e-5),
    (jnp.bfloat16, 5e-2, 2e-2),
])
def test_gpt_manual_tp_forward_matches_dense(tp2_mesh, dtype, rtol, atol):
    model = _tiny_gpt()
    model._module = model.module.clone(dtype=dtype)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randint(1, 63, size=(2, 16)).astype(np.int32))
    variables = model.init_variables(jax.random.PRNGKey(0), {"x": x})
    ref = model.module.apply(variables, x, train=False)
    out = _manual_forward(model, variables, x, tp2_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)


def test_manual_tp_init_matches_dense_shapes(tp2_mesh):
    """Initializing THROUGH the TP module (a job that starts tensor-
    parallel) yields the same tree paths/shapes as the dense module."""
    model = _bert_fixture()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(1, 1000, size=(2, 8)).astype(np.int32))
    dense_vars = model.init_variables(jax.random.PRNGKey(0), {"x": x})

    tp_model = _bert_fixture()
    tp_model.enable_tensor_parallel()
    # init goes through init_module (the dense clone) exactly like the
    # job's _init_model does
    tp_vars = tp_model.init_variables(jax.random.PRNGKey(0), {"x": x})
    ref_shapes = jax.tree_util.tree_map(lambda a: a.shape, dense_vars)
    tp_shapes = jax.tree_util.tree_map(lambda a: a.shape, tp_vars)
    assert ref_shapes == tp_shapes


def test_manual_tp_grads_match_dense(tp2_mesh):
    """vma tracking assembles the full parameter gradients across model
    lanes (the invariant->varying psums) — grads equal the dense run."""
    model = _bert_fixture()
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randint(1, 1000, size=(4, 16)).astype(np.int32))
    y = jnp.asarray(rng.randint(0, 2, size=(4,)).astype(np.int32))
    variables = model.init_variables(jax.random.PRNGKey(0), {"x": x})
    key = jax.random.PRNGKey(3)
    ones = jnp.ones(x.shape[0])

    def scalar(model_, v, x, y):
        per_ex, _ = model_.loss(v, {"x": x, "y": y}, key, ones)
        return per_ex.mean()

    g_ref = jax.grad(lambda v: scalar(model, v, x, y))(variables)

    tp_model = _bert_fixture()
    tp_model._module = tp_model.module.clone(tp_axis=MODEL_AXIS)

    def tp_grads(v, x, y):
        return jax.grad(lambda v: scalar(tp_model, v, x, y))(v)

    g_tp = jax.jit(jax.shard_map(
        tp_grads, mesh=tp2_mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=True))(variables, x, y)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_tp)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-2, atol=5e-3)


# ----------------------------------------------------- engine integration


def _round_inputs(rng, W, S, B, T, vocab_hi, with_labels):
    x = rng.randint(1, vocab_hi, size=(W, S, B, T)).astype(np.int32)
    batch = {"x": x}
    if with_labels:
        batch["y"] = rng.randint(0, 2, size=(W, S, B)).astype(np.int32)
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
    return batch, masks, rngs


def _engine_compare(make_model, enable, mesh_kwargs, with_labels=True,
                    vocab_hi=1000, engine_kwargs=None, ref=None):
    """One K-avg round on the parallel mesh vs pure-DP (data=2); returns
    (ref_out, out) after asserting weight/loss/eval parity."""
    rng = np.random.RandomState(0)
    W, S, B, T = 2, 2, 4, 16
    batch, masks, rngs = _round_inputs(rng, W, S, B, T, vocab_hi,
                                       with_labels)

    model0 = make_model()
    variables = model0.init_variables(
        jax.random.PRNGKey(0),
        jax.tree_util.tree_map(lambda a: jnp.asarray(a[0, 0]), batch))

    def run(mesh, model, **kw):
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         lambda lr, e: optax.sgd(lr), donate=False,
                         **kw)
        jb = jax.tree_util.tree_map(jnp.asarray, batch)
        out, stats = eng.train_round(variables, jb, rngs=rngs, lr=1e-2,
                                     epoch=0, **masks)
        ev = eng.eval_round(out, jb, masks["sample_mask"])
        return out, float(np.asarray(stats.loss_sum).sum()), ev

    if ref is None:
        ref_model = make_model()
        ref = run(make_mesh(n_data=2, devices=jax.devices()[:2]),
                  ref_model)
    ref_out, loss_ref, ev_ref = ref

    par_model = make_model()
    enable(par_model)
    kw = dict(engine_kwargs or {})
    if par_model.seq_batch_dims is not None and \
            mesh_kwargs.get("n_seq", 1) > 1:
        kw["batch_seq_dims"] = par_model.seq_batch_dims
    out, loss_par, ev_par = run(make_mesh(**mesh_kwargs), par_model, **kw)

    for a, b in zip(jax.tree_util.tree_leaves(ref_out),
                    jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-2, atol=2e-3)
    # thresholds are wider than the SP-only equivalence test's: manual TP
    # SPLITS the bf16 contractions (different rounding order per lane),
    # SP only re-orders the sequence — measured noise here is ~1e-2 on a
    # loss of ~1, pure bf16 (the f32 forward parity test pins exactness)
    assert abs(loss_ref - loss_par) < 2e-2 * max(1.0, abs(loss_ref))
    assert abs(ev_ref["loss"] - ev_par["loss"]) < 2e-2
    assert ev_ref["n"] == ev_par["n"]
    return ref, out


def test_kavg_trains_manual_tp_bert():
    _engine_compare(
        _bert_fixture,
        lambda m: m.enable_tensor_parallel(),
        dict(n_data=2, n_model=2, devices=jax.devices()[:4]),
        engine_kwargs=dict(manual_inner=True))


def test_kavg_trains_tp_sp_combined():
    """Round 2's exclusion, cleared: TP and SP in ONE fully-manual round
    (heads sharded over `model`, KV ring over `seq`)."""

    def enable(m):
        m.enable_tensor_parallel()
        m.enable_seq_parallel("ring")

    _engine_compare(
        _bert_fixture, enable,
        dict(n_data=2, n_model=2, n_seq=2, devices=jax.devices()[:8]),
        engine_kwargs=dict(manual_inner=True))


def test_kavg_trains_tp_sp_combined_gpt():
    def enable(m):
        m.enable_tensor_parallel()
        m.enable_seq_parallel("ring")

    _engine_compare(
        _tiny_gpt, enable,
        dict(n_data=2, n_model=2, n_seq=2, devices=jax.devices()[:8]),
        with_labels=False, vocab_hi=63,
        engine_kwargs=dict(manual_inner=True))


def test_kavg_manual_tp_compressed_merge():
    """merge_dtype composes with the fully-manual round (the sub-f32
    psum miscompile is partial-manual-only): bf16-merged weights track
    the f32 merge within wire precision."""
    rng = np.random.RandomState(0)
    W, S, B, T = 2, 2, 4, 16
    batch, masks, rngs = _round_inputs(rng, W, S, B, T, 1000, True)
    model = _bert_fixture()
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        jax.tree_util.tree_map(lambda a: jnp.asarray(a[0, 0]), batch))

    def run(merge_dtype):
        m = _bert_fixture()
        m.enable_tensor_parallel()
        eng = KAvgEngine(make_mesh(n_data=2, n_model=2,
                                   devices=jax.devices()[:4]),
                         m.loss, m.metrics, lambda lr, e: optax.sgd(lr),
                         donate=False, manual_inner=True,
                         merge_dtype=merge_dtype)
        out, _ = eng.train_round(
            variables, jax.tree_util.tree_map(jnp.asarray, batch),
            rngs=rngs, lr=1e-2, epoch=0, **masks)
        return out

    f32 = run(None)
    bf16 = run(jnp.bfloat16)
    for a, b in zip(jax.tree_util.tree_leaves(f32),
                    jax.tree_util.tree_leaves(bf16)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(b, a, rtol=2e-2, atol=2e-2)


def test_kavg_sp_compressed_merge():
    """Round 2 rejected merge compression x SP-training; the fully-manual
    round now carries it."""
    from tests.test_models_gpt import TinyGPT

    rng = np.random.RandomState(0)
    W, S, B, T = 2, 1, 2, 16
    batch, masks, rngs = _round_inputs(rng, W, S, B, T, 63, False)
    model = TinyGPT()
    model._module = model.module.clone(dropout=0.0)
    variables = model.init_variables(
        jax.random.PRNGKey(0),
        jax.tree_util.tree_map(lambda a: jnp.asarray(a[0, 0]), batch))

    m = TinyGPT()
    m._module = m.module.clone(dropout=0.0)
    m.enable_seq_parallel("ring")
    eng = KAvgEngine(make_mesh(n_data=2, n_seq=2,
                               devices=jax.devices()[:4]),
                     m.loss, m.metrics, lambda lr, e: optax.sgd(lr),
                     donate=False, merge_dtype=jnp.bfloat16,
                     batch_seq_dims=m.seq_batch_dims)
    out, _ = eng.train_round(
        variables, jax.tree_util.tree_map(jnp.asarray, batch),
        rngs=rngs, lr=1e-2, epoch=0, **masks)
    for leaf in jax.tree_util.tree_leaves(out):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_validate_tp_geometry():
    """Pure-python geometry gate (the smoke-tier representative for this
    subsystem — every other test here compiles multi-axis shard_maps)."""
    from kubeml_tpu.parallel.manual import validate_tp_geometry

    validate_tp_geometry(heads=4, ffn=512, n_model=2)
    with pytest.raises(ValueError, match="heads do not divide"):
        validate_tp_geometry(heads=3, ffn=512, n_model=2)
    with pytest.raises(ValueError, match="FFN width"):
        validate_tp_geometry(heads=4, ffn=511, n_model=2)


def test_manual_tp_rejects_indivisible_heads(tp2_mesh):
    """3 heads on a 2-way model axis: readable trace-time error."""
    from kubeml_tpu.models.bert import BertModule

    module = BertModule(hidden=24, heads=3, ffn=48, layers=1,
                        tp_axis=MODEL_AXIS, dropout=0.0)
    x = jnp.ones((2, 8), jnp.int32)

    def fwd(x):
        return module.init(jax.random.PRNGKey(0), x)

    with pytest.raises(ValueError, match="heads do not divide"):
        jax.jit(jax.shard_map(fwd, mesh=tp2_mesh, in_specs=P(),
                              out_specs=P(), check_vma=False))(x)
