"""Tensor + sequence parallelism: ring attention exactness, TP shardings.

Runs on the 8-virtual-CPU-device mesh (conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeml_tpu.models import get_builtin
from kubeml_tpu.ops.attention import multi_head_attention, padding_bias
from kubeml_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, SEQ_AXIS,
                                      make_mesh)
from kubeml_tpu.parallel.ring_attention import ring_self_attention
from kubeml_tpu.parallel.tp import (BERT_TP_RULES, shard_variables,
                                    spec_for, tree_specs)

B, T, H, D = 2, 32, 4, 8


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh(n_data=1, n_model=1, n_seq=8)


def _qkv(rng):
    q = rng.randn(B, T, H, D).astype(np.float32)
    k = rng.randn(B, T, H, D).astype(np.float32)
    v = rng.randn(B, T, H, D).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_ring_attention_matches_full(seq_mesh):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    pad = np.ones((B, T), np.float32)
    pad[0, 20:] = 0.0  # ragged padding crossing block boundaries
    pad[1, 5:9] = 0.0  # interior masked tokens
    ref = multi_head_attention(q, k, v, padding_bias(jnp.asarray(pad)))
    out = ring_self_attention(q, k, v, jnp.asarray(pad), seq_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_causal(seq_mesh):
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng)
    pad = jnp.ones((B, T))
    causal_bias = jnp.where(
        jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], 0.0,
        -1e9)[None, None]
    ref = multi_head_attention(q, k, v, causal_bias)
    out = ring_self_attention(q, k, v, pad, seq_mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_causal_with_padding(seq_mesh):
    """Causal AND padding together: doubly-masked positions (pad inside
    the causal window, stacked -2e9 bias) stay exact and finite."""
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng)
    pad = np.ones((B, T), np.float32)
    pad[0, 10:] = 0.0
    pad[1, 3:7] = 0.0
    causal_part = jnp.where(
        jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], 0.0,
        -1e9)[None, None]
    bias = causal_part + padding_bias(jnp.asarray(pad))
    ref = multi_head_attention(q, k, v, bias)
    out = ring_self_attention(q, k, v, jnp.asarray(pad), seq_mesh,
                              causal=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_match(seq_mesh):
    """The ring is differentiable and its grads equal full attention's."""
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng)
    pad = jnp.ones((B, T))

    def loss_ref(q, k, v):
        return (multi_head_attention(q, k, v,
                                     padding_bias(pad)) ** 2).sum()

    def loss_ring(q, k, v):
        return (ring_self_attention(q, k, v, pad, seq_mesh) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ ulysses


@pytest.fixture(scope="module")
def seq4_mesh():
    # 4-way seq axis so heads (4) divide it — ulysses' requirement
    return make_mesh(n_data=1, n_model=1, n_seq=4)


def test_ulysses_matches_full(seq4_mesh):
    from kubeml_tpu.parallel.ulysses import ulysses_self_attention
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    pad = np.ones((B, T), np.float32)
    pad[0, 20:] = 0.0  # ragged padding crossing block boundaries
    pad[1, 5:9] = 0.0  # interior masked tokens
    ref = multi_head_attention(q, k, v, padding_bias(jnp.asarray(pad)))
    out = ulysses_self_attention(q, k, v, jnp.asarray(pad), seq4_mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_causal_with_padding(seq4_mesh):
    from kubeml_tpu.ops.attention import composed_bias
    from kubeml_tpu.parallel.ulysses import ulysses_self_attention
    rng = np.random.RandomState(3)
    q, k, v = _qkv(rng)
    pad = np.ones((B, T), np.float32)
    pad[0, 10:] = 0.0
    pad[1, 3:7] = 0.0
    ref = multi_head_attention(q, k, v,
                               composed_bias(jnp.asarray(pad), True, T))
    out = ulysses_self_attention(q, k, v, jnp.asarray(pad), seq4_mesh,
                                 causal=True)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_grads_match(seq4_mesh):
    """Both all-to-alls are differentiable; grads equal full attention's."""
    from kubeml_tpu.parallel.ulysses import ulysses_self_attention
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng)
    pad = jnp.ones((B, T))

    def loss_ref(q, k, v):
        return (multi_head_attention(q, k, v,
                                     padding_bias(pad)) ** 2).sum()

    def loss_uly(q, k, v):
        return (ulysses_self_attention(q, k, v, pad, seq4_mesh) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_uly):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_indivisible_heads_raises(seq_mesh):
    """H=4 on an 8-way seq axis cannot head-shard: loud error, not a
    wrong answer."""
    from kubeml_tpu.parallel.ulysses import ulysses_self_attention
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    with pytest.raises(ValueError, match="head count"):
        ulysses_self_attention(q, k, v, jnp.ones((B, T)), seq_mesh)


# ----------------------------------------------------------------- TP


def test_spec_rules():
    assert spec_for("layer_0/q/kernel", BERT_TP_RULES) == \
        jax.sharding.PartitionSpec(None, MODEL_AXIS, None)
    assert spec_for("layer_1/out/kernel", BERT_TP_RULES) == \
        jax.sharding.PartitionSpec(MODEL_AXIS, None, None)
    assert spec_for("tok_embed/embedding", BERT_TP_RULES) == \
        jax.sharding.PartitionSpec()


def test_bert_tp_forward_matches_replicated():
    """BERT forward with Megatron-sharded params == replicated forward."""
    mesh = make_mesh(n_data=2, n_model=2, n_seq=2)
    model = get_builtin("bert-tiny")()
    rng = np.random.RandomState(0)
    x = rng.randint(1, 1000, size=(4, 16)).astype(np.int32)
    x[:, 12:] = 0
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    ref = model.module.apply(variables, jnp.asarray(x), train=False)

    sharded_vars = shard_variables(variables, mesh, BERT_TP_RULES)
    # at least one param actually got a non-trivial sharding
    shardings = [v.sharding.spec for v in
                 jax.tree_util.tree_leaves(sharded_vars)
                 if hasattr(v, "sharding")]
    assert any(s != jax.sharding.PartitionSpec() for s in shardings)

    # jit infers the partitioning from the input NamedShardings; XLA's
    # SPMD partitioner inserts the TP collectives
    out = jax.jit(lambda v, x: model.module.apply(v, x, train=False))(
        sharded_vars, jnp.asarray(x))
    # bf16 compute: sharded matmuls change reduction order; one-ulp scale
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_tp_fallback_replicates_indivisible():
    """A dim not divisible by the axis falls back to replication instead
    of crashing (2 heads on a 4-way model axis)."""
    mesh = make_mesh(n_data=2, n_model=4, n_seq=1)
    tree = {"layer_0": {"q": {"kernel": jnp.zeros((8, 2, 4))}}}
    out = shard_variables(tree, mesh, BERT_TP_RULES)
    spec = out["layer_0"]["q"]["kernel"].sharding.spec
    assert spec == jax.sharding.PartitionSpec()


def test_kavg_trains_tp_sharded_variables():
    """DP x TP training: the K-avg round on a 4x2 mesh with Megatron-
    sharded BERT variables must produce the same averaged weights as the
    fully-replicated run on a pure-DP mesh (same worker count, same
    data) — GSPMD handles the model axis inside each DP lane while the
    merge psums over `data` only."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.parallel.kavg import KAvgEngine
    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.parallel.tp import BERT_TP_RULES, shard_variables

    model = get_builtin("bert-tiny")()
    rng = np.random.RandomState(0)
    W, S, B, T = 4, 2, 4, 16
    x = rng.randint(1, 1000, size=(W, S, B, T)).astype(np.int32)
    y = rng.randint(0, 2, size=(W, S, B)).astype(np.int32)
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x[0, 0])})

    def run(mesh, variables):
        # plain SGD: adamw's g/(sqrt(v)+eps) amplifies bf16 layout noise
        # on near-zero grads, which would make exact comparison
        # ill-conditioned without changing what this test proves
        import optax
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         lambda lr, epoch: optax.sgd(lr), donate=False)
        out, stats = eng.train_round(variables, batch, rngs=rngs,
                                     lr=1e-2, epoch=0, **masks)
        assert stats.contributors == W
        return out

    ref = run(make_mesh(n_data=4), variables)

    mesh_tp = make_mesh(n_data=4, n_model=2)
    sharded = shard_variables(variables, mesh_tp, BERT_TP_RULES)
    out_tp = run(mesh_tp, sharded)

    for pr, pt in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(out_tp)):
        np.testing.assert_allclose(np.asarray(pr), np.asarray(pt),
                                   rtol=2e-2, atol=2e-3)


def test_gpt_tp_forward_matches_replicated():
    """The decoder blocks share the BERT blocks' param layout, so the
    same Megatron rule table (GPT_TP_RULES) TP-shards the causal model."""
    from kubeml_tpu.parallel.tp import GPT_TP_RULES

    mesh = make_mesh(n_data=2, n_model=2, n_seq=2)
    from tests.test_models_gpt import TinyGPT
    model = TinyGPT()
    rng = np.random.RandomState(0)
    x = rng.randint(1, 64, size=(4, 16)).astype(np.int32)
    x[:, 12:] = 0
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    ref = model.module.apply(variables, jnp.asarray(x), train=False)

    sharded_vars = shard_variables(variables, mesh, GPT_TP_RULES)
    shardings = [v.sharding.spec for v in
                 jax.tree_util.tree_leaves(sharded_vars)
                 if hasattr(v, "sharding")]
    assert any(s != jax.sharding.PartitionSpec() for s in shardings)

    out = jax.jit(lambda v, x: model.module.apply(v, x, train=False))(
        sharded_vars, jnp.asarray(x))
    # per-token vocab logits: same bf16 tolerance as the SP parity tests
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=6e-2)


def test_kavg_trains_tp_sharded_gpt():
    """DP x TP K-avg training of the causal LM: loss falls with
    Megatron-sharded variables on a 4x2 mesh."""
    from kubeml_tpu.parallel.kavg import KAvgEngine
    from kubeml_tpu.parallel.tp import GPT_TP_RULES, shard_variables
    from tests.test_models_gpt import TinyGPT, make_lm_task

    mesh = make_mesh(n_data=4, n_model=2)
    model = TinyGPT()
    rng = np.random.RandomState(0)
    W, S, B, T = 4, 2, 8, 16
    x = make_lm_task(rng, W * S * B).reshape(W, S, B, T)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x[0, 0])})
    variables = shard_variables(variables, mesh, GPT_TP_RULES)
    engine = KAvgEngine(mesh, model.loss, model.metrics,
                        model.configure_optimizers, donate=False)
    batch = {"x": jnp.asarray(x)}
    masks = dict(sample_mask=np.ones((W, S, B)), step_mask=np.ones((W, S)),
                 worker_mask=np.ones(W))
    first = last = None
    for _ in range(6):
        rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
        variables, stats = engine.train_round(
            variables, batch, rngs=rngs, lr=3e-3, epoch=0, **masks)
        last = stats.loss_sum.sum() / stats.step_count.sum()
        if first is None:
            first = last
    assert last < first, (first, last)


# ----------------------------------------------- seq-parallel TRAINING


def _sp_train_compare(make_model, make_batch, impl):
    """One K-avg round + eval on (data=2, seq=2) vs pure-DP (data=2):
    averaged weights, round loss, and eval metrics must match the dense
    run to bf16 reduction-order noise. Exercises loss AND grads through
    the ring/all-to-all attention inside the engine path (check_vma=True
    round — see KAvgEngine.batch_seq_dims)."""
    import optax

    from kubeml_tpu.parallel.kavg import KAvgEngine

    rng = np.random.RandomState(0)
    W, S, B, T = 2, 2, 4, 32
    batch = make_batch(rng, W, S, B, T)
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)

    model0 = make_model()
    variables = model0.init_variables(
        jax.random.PRNGKey(0),
        jax.tree_util.tree_map(lambda a: jnp.asarray(a[0, 0]), batch))

    def run(mesh, model):
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         lambda lr, e: optax.sgd(lr), donate=False,
                         batch_seq_dims=model.seq_batch_dims)
        jb = jax.tree_util.tree_map(jnp.asarray, batch)
        out, stats = eng.train_round(variables, jb, rngs=rngs, lr=1e-2,
                                     epoch=0, **masks)
        ev = eng.eval_round(out, jb, masks["sample_mask"])
        return out, float(np.asarray(stats.loss_sum).sum()), ev

    # dropout 0 for determinism: local seq blocks draw different dropout
    # masks than the dense layout, which is fine in production but would
    # blur this equality test
    ref_model = make_model()
    ref_model._module = ref_model.module.clone(dropout=0.0)
    ref, loss_ref, ev_ref = run(
        make_mesh(n_data=2, devices=jax.devices()[:2]), ref_model)

    sp_model = make_model()
    sp_model._module = sp_model.module.clone(dropout=0.0)
    sp_model.enable_seq_parallel(impl)
    sp, loss_sp, ev_sp = run(
        make_mesh(n_data=2, n_seq=2, devices=jax.devices()[:4]), sp_model)

    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(sp)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-2, atol=2e-3)
    assert abs(loss_ref - loss_sp) < 5e-3 * max(1.0, abs(loss_ref))
    assert abs(ev_ref["loss"] - ev_sp["loss"]) < 5e-3
    assert ev_ref["n"] == ev_sp["n"]


def _bert_sp_batch(rng, W, S, B, T):
    return {"x": rng.randint(1, 1000, size=(W, S, B, T)).astype(np.int32),
            "y": rng.randint(0, 2, size=(W, S, B)).astype(np.int32)}


def _lm_sp_batch(rng, W, S, B, T):
    start = rng.randint(1, 63, size=(W * S * B, 1))
    seq = (start + np.arange(T)[None, :] - 1) % 63 + 1
    return {"x": seq.reshape(W, S, B, T).astype(np.int32)}


def test_kavg_trains_seq_parallel_bert_ring():
    _sp_train_compare(lambda: get_builtin("bert-tiny")(), _bert_sp_batch,
                      "ring")


def test_kavg_trains_seq_parallel_gpt_ring():
    from tests.test_models_gpt import TinyGPT
    _sp_train_compare(TinyGPT, _lm_sp_batch, "ring")


def test_kavg_trains_seq_parallel_gpt_ulysses():
    from tests.test_models_gpt import TinyGPT
    _sp_train_compare(TinyGPT, _lm_sp_batch, "ulysses")


def test_sp_loss_handles_padding_across_shards():
    """Right-padded rows: the SP LM loss (ppermute boundary target +
    global-last masking) must equal the dense loss exactly."""
    from jax.sharding import PartitionSpec as P

    from kubeml_tpu.models.gpt import (_lm_per_example, _lm_per_example_sp)
    from tests.test_models_gpt import TinyGPT

    model = TinyGPT()
    # f32 modules so dense-vs-ring attention noise cannot blur the
    # boundary/masking logic this test pins down
    model._module = model.module.clone(dtype=jnp.float32)
    rng = np.random.RandomState(1)
    B, T = 4, 32
    x = rng.randint(1, 63, size=(B, T)).astype(np.int32)
    x[0, 20:] = 0   # right padding ending inside shard 2 (of 4)
    x[1, 8:] = 0    # ends inside shard 1
    x[2, :] = 0     # fully padded row
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    dense_logits = model.module.apply(variables, jnp.asarray(x),
                                      train=False)
    ref = np.asarray(_lm_per_example(dense_logits, jnp.asarray(x)))

    mesh = make_mesh(n_data=1, n_seq=4)
    sp_module = model.module.clone(seq_axis=SEQ_AXIS)

    def body(v, x_local):
        logits = sp_module.apply(v, x_local, train=False)
        return _lm_per_example_sp(logits, x_local, SEQ_AXIS)

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P(None, SEQ_AXIS)),
        out_specs=P(), check_vma=False))(variables, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)
