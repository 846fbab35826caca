"""Pipeline (stage axis) and expert (MoE) parallelism tests.

Runs on the 8-virtual-CPU-device mesh from conftest. Correctness is
checked against unpipelined / per-token dense references.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from kubeml_tpu.parallel.ep import init_moe_params, make_dispatch, moe_apply
from kubeml_tpu.parallel.mesh import make_mesh
from kubeml_tpu.parallel.pp import (pipeline_apply, sequential_apply,
                                    stack_stage_params)


def _dense_stage(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _make_stages(rng, n, f):
    ps = []
    for i in range(n):
        kw, rng = jax.random.split(rng)
        ps.append({"w": jax.random.normal(kw, (f, f)) / np.sqrt(f),
                   "b": jnp.full((f,), 0.01 * i)})
    return stack_stage_params(ps)


@pytest.fixture(scope="module")
def pp_mesh():
    return make_mesh(n_data=2, n_stage=4)


def test_pipeline_matches_sequential(pp_mesh):
    rng = jax.random.PRNGKey(0)
    stages = _make_stages(rng, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 8))  # M=6 microbatches
    got = pipeline_apply(_dense_stage, stages, x, pp_mesh)
    want = sequential_apply(_dense_stage, stages, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_grads_match(pp_mesh):
    stages = _make_stages(jax.random.PRNGKey(2), 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(3), (5, 2, 8))
    tgt = jax.random.normal(jax.random.PRNGKey(4), (5, 2, 8))

    def loss_pp(p):
        return jnp.mean((pipeline_apply(_dense_stage, p, x, pp_mesh) - tgt) ** 2)

    def loss_seq(p):
        return jnp.mean((sequential_apply(_dense_stage, p, x) - tgt) ** 2)

    g_pp = jax.grad(loss_pp)(stages)
    g_seq = jax.grad(loss_seq)(stages)
    for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                    jax.tree_util.tree_leaves(g_seq)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_pipeline_jits_under_mesh(pp_mesh):
    stages = _make_stages(jax.random.PRNGKey(5), 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 2, 8))
    f = jax.jit(lambda p, x: pipeline_apply(_dense_stage, p, x, pp_mesh))
    got = f(stages, x)
    want = sequential_apply(_dense_stage, stages, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ MoE / EP

def test_dispatch_top1_routes_to_argmax():
    logits = jnp.array([[2.0, 0.0, -1.0],
                        [0.0, 3.0, 0.0],
                        [0.0, 0.1, 4.0],
                        [5.0, 0.0, 0.0]])
    dispatch, combine, _ = make_dispatch(logits, capacity=2, k=1)
    probs = jax.nn.softmax(logits, axis=-1)
    for tok, exp in enumerate([0, 1, 2, 0]):
        assert float(dispatch[tok, exp].sum()) == 1.0
        np.testing.assert_allclose(float(combine[tok, exp].sum()),
                                   float(probs[tok, exp]), rtol=1e-6)
    # each token routed exactly once
    np.testing.assert_allclose(np.asarray(dispatch.sum(axis=(1, 2))),
                               np.ones(4))


def test_dispatch_capacity_drops_overflow():
    # all four tokens prefer expert 0; capacity 2 keeps the first two
    logits = jnp.tile(jnp.array([[5.0, 0.0]]), (4, 1))
    dispatch, _, _ = make_dispatch(logits, capacity=2, k=1)
    kept = np.asarray(dispatch[:, 0].sum(axis=-1))
    np.testing.assert_allclose(kept, [1, 1, 0, 0])


def test_dispatch_top2_uses_distinct_experts():
    logits = jnp.array([[1.0, 0.5, -2.0]] * 3)
    dispatch, _, _ = make_dispatch(logits, capacity=4, k=2)
    per_tok = np.asarray(dispatch.sum(axis=2))  # [T, E]
    np.testing.assert_allclose(per_tok[:, 0], 1)
    np.testing.assert_allclose(per_tok[:, 1], 1)
    np.testing.assert_allclose(per_tok[:, 2], 0)


def test_moe_matches_per_token_reference():
    d, ff, e, t = 6, 12, 4, 16
    params = init_moe_params(jax.random.PRNGKey(0), d, ff, e)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    # huge capacity => nothing dropped => exact per-token semantics
    y, _ = moe_apply(params, x, mesh=None, k=1, capacity_factor=float(e))

    probs = jax.nn.softmax(x @ params["router"], axis=-1)
    choice = jnp.argmax(probs, axis=-1)
    want = []
    for i in range(t):
        ei = int(choice[i])
        h = jax.nn.gelu(x[i] @ params["wi"][ei] + params["bi"][ei])
        want.append((h @ params["wo"][ei] + params["bo"][ei]) *
                    probs[i, ei])
    np.testing.assert_allclose(np.asarray(y), np.asarray(jnp.stack(want)),
                               rtol=1e-4, atol=1e-5)


def test_moe_sharded_matches_unsharded():
    mesh = make_mesh(n_data=2, n_expert=4)
    d, ff, e, t = 8, 16, 4, 32
    params = init_moe_params(jax.random.PRNGKey(2), d, ff, e)
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d))

    y_plain, aux_plain = moe_apply(params, x, mesh=None, k=2)
    f = jax.jit(lambda p, x: moe_apply(p, x, mesh=mesh, k=2))
    y_shard, aux_shard = f(params, x)
    np.testing.assert_allclose(np.asarray(y_shard), np.asarray(y_plain),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_shard), float(aux_plain), rtol=1e-5)


def test_moe_grads_finite():
    d, ff, e, t = 6, 12, 4, 16
    params = init_moe_params(jax.random.PRNGKey(4), d, ff, e)
    x = jax.random.normal(jax.random.PRNGKey(5), (t, d))

    def loss(p):
        y, aux = moe_apply(p, x, k=2)
        return jnp.mean(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_pipeline_training_converges():
    """GPipe is trainable end-to-end: grads through the ppermute ring
    train a stacked-stage trunk to fit a fixed regression target."""
    mesh = make_mesh(n_data=1, n_stage=4)
    rng = np.random.RandomState(0)
    feat, P_, M, B = 8, 4, 8, 4
    stages = stack_stage_params([
        {"w": jnp.asarray(rng.randn(feat, feat) / np.sqrt(feat),
                          jnp.float32),
         "b": jnp.zeros((feat,), jnp.float32)} for _ in range(P_)])

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"] + p["b"])

    x = jnp.asarray(rng.randn(M, B, feat), jnp.float32)
    target = jnp.asarray(np.tanh(rng.randn(M, B, feat)), jnp.float32)

    def loss_fn(stages):
        y = pipeline_apply(stage_fn, stages, x, mesh)
        return jnp.mean((y - target) ** 2)

    tx = optax.adam(3e-2)
    opt = tx.init(stages)

    @jax.jit
    def step(stages, opt):
        loss, grads = jax.value_and_grad(loss_fn)(stages)
        updates, opt = tx.update(grads, opt, stages)
        return optax.apply_updates(stages, updates), opt, loss

    l0 = float(loss_fn(stages))
    for _ in range(60):
        stages, opt, loss = step(stages, opt)
    assert float(loss) < l0 * 0.5, (l0, float(loss))


def test_moe_training_converges():
    """The sharded MoE block is trainable: router + experts fit a
    classification toy under the aux load-balancing loss."""
    mesh = make_mesh(n_data=1, n_expert=4)
    rng = np.random.RandomState(0)
    T, D = 64, 8
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    head_w = jnp.asarray(rng.randn(D, 4) * 0.1, jnp.float32)
    y = jnp.asarray(rng.randint(0, 4, T))

    params = init_moe_params(jax.random.PRNGKey(0), d_model=D, d_ff=16,
                             n_experts=4)
    params = dict(params, head=head_w)

    def loss_fn(params):
        moe_p = {k: v for k, v in params.items() if k != "head"}
        h, aux = moe_apply(moe_p, x, mesh, k=2)
        logits = (x + h) @ params["head"]
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
        return ce.mean() + 0.01 * aux

    tx = optax.adam(1e-2)
    opt = tx.init(params)

    @jax.jit
    def step(params, opt):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    l0 = float(loss_fn(params))
    for _ in range(80):
        params, opt, loss = step(params, opt)
    assert float(loss) < l0 * 0.7, (l0, float(loss))


def test_pipeline_aux_matches_sequential():
    """has_aux: per-stage scalar outputs accumulate over REAL
    (stage, microbatch) pairs only — fill/drain garbage ticks masked —
    and equal the sequential reference exactly."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.parallel.pp import (pipeline_apply, sequential_apply,
                                        stack_stage_params)

    rng = np.random.RandomState(0)
    P_, M, B, F = 4, 6, 2, 8
    mesh = make_mesh(n_data=1, n_stage=P_)
    stages = stack_stage_params([
        {"w": jnp.asarray(rng.randn(F, F).astype(np.float32) / 3)}
        for _ in range(P_)])
    x = jnp.asarray(rng.randn(M, B, F).astype(np.float32))

    def stage_fn(p, a):
        out = jnp.tanh(a @ p["w"])
        return out, (out ** 2).sum()  # nonzero aux per real tick

    ys_ref, aux_ref = sequential_apply(stage_fn, stages, x, has_aux=True)
    ys, aux = pipeline_apply(stage_fn, stages, x, mesh, has_aux=True)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ys_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)
    # grads flow through the aux path too
    g = jax.grad(lambda s: pipeline_apply(
        stage_fn, s, x, mesh, has_aux=True)[1])(stages)
    g_ref = jax.grad(lambda s: sequential_apply(
        stage_fn, s, x, has_aux=True)[1])(stages)
    np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(g_ref["w"]),
                               rtol=1e-4, atol=1e-5)


def test_moe_trunk_pipelines():
    """Pipelined MoE (round 2, lifting the r1 restriction): the MoE
    decoder trunk rides the stage pipeline with per-microbatch routing
    capacity, equal to the per-microbatch sequential reference, with
    the load-balance aux accumulated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.parallel.mesh import make_mesh
    from tests.test_models_gpt import TinyMoE, make_lm_task

    model = TinyMoE()
    rng = np.random.RandomState(0)
    B, T, M = 8, 16, 4
    x = make_lm_task(rng, B)[:, :T]
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    mesh = make_mesh(n_data=4, n_stage=2)
    logits, aux = model.forward_pipelined(variables, jnp.asarray(x), mesh,
                                          microbatches=M)
    assert logits.shape == (B, T, 64)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0.0  # load-balance loss accumulated

    # per-microbatch sequential reference: same capacity semantics by
    # construction -> near-exact parity (bf16 noise only)
    from kubeml_tpu.models.gpt import DecoderBlock
    module = model.module
    block = DecoderBlock(module.hidden, module.heads, module.ffn, 0.0,
                         module.dtype, n_experts=module.n_experts,
                         moe_k=module.moe_k,
                         capacity_factor=module.capacity_factor)
    params = variables["params"]
    emb = params["tok_embed"]["embedding"].astype(module.dtype)
    h = emb[jnp.asarray(x)] + params["pos_embed"]["embedding"][
        jnp.arange(T)].astype(module.dtype)[None]
    h = h.reshape(M, B // M, T, module.hidden)

    outs, aux_ref = [], 0.0
    for mb in range(M):
        a = h[mb]
        ones = jnp.ones(a.shape[:2], jnp.float32)
        for l in range(module.layers):
            a, st = block.apply({"params": params[f"layer_{l}"]}, a,
                                ones, False, mutable=["intermediates"])
            # match the pipeline's carry dtype (activations ride the
            # ring in the module compute dtype)
            a = a.astype(module.dtype)
            aux_ref += float(sum(jax.tree_util.tree_leaves(st)))
        outs.append(a)
    hr = jnp.stack(outs).reshape(B, T, module.hidden)
    import flax.linen as nn
    hr = nn.LayerNorm(dtype=jnp.float32).apply(
        {"params": params["LayerNorm_0"]}, hr)
    ref_logits = (hr.astype(module.dtype) @ emb.T).astype(jnp.float32)

    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(float(aux),
                               aux_ref / (module.layers * M), rtol=1e-3)


def test_moe_trunk_pipelines_expert_sharded():
    """PP x EP (round 3, lifting the r2 restriction): the pipelined MoE
    trunk with experts sharded over the mesh expert axis (manual
    ep_partial_ffn psum inside the stage shard_map) equals the
    replicated-expert pipeline bit-for-bit up to bf16 psum noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.parallel.mesh import make_mesh
    from tests.test_models_gpt import TinyMoE, make_lm_task

    model = TinyMoE()
    rng = np.random.RandomState(0)
    B, T, M = 8, 16, 4
    x = make_lm_task(rng, B)[:, :T]
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})

    rep_mesh = make_mesh(n_data=4, n_stage=2)
    ref_logits, ref_aux = model.forward_pipelined(
        variables, jnp.asarray(x), rep_mesh, microbatches=M)

    ep_model = TinyMoE()  # fresh instance: the pp cache keys on mesh
    ep_mesh = make_mesh(n_data=2, n_stage=2, n_expert=2)
    logits, aux = ep_model.forward_pipelined(
        variables, jnp.asarray(x), ep_mesh, microbatches=M)

    assert logits.shape == ref_logits.shape
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-3)


def test_ep_alltoall_ffn_matches_dense():
    """Token-sharded expert dispatch (VERDICT r3 item 7): inside a
    4-way manual expert axis, ep_alltoall_ffn — local routing, two
    tiled all_to_alls moving slot payloads to the experts and back —
    equals the dense full-expert math applied per token shard."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from kubeml_tpu.parallel.ep import route_tokens
    from kubeml_tpu.parallel.manual import ep_alltoall_ffn
    from kubeml_tpu.parallel.mesh import EXPERT_AXIS, make_mesh

    rng = np.random.RandomState(5)
    n, Tl, d, f, E = 4, 16, 8, 16, 8
    x = jnp.asarray(rng.randn(n * Tl, d).astype(np.float32))
    mask = np.ones(n * Tl, np.float32)
    mask[10:14] = 0.0  # pad tokens inside shard 0
    mask = jnp.asarray(mask)
    router = jnp.asarray(rng.randn(d, E).astype(np.float32) * 0.3)
    wi = jnp.asarray(rng.randn(E, d, f).astype(np.float32) * 0.2)
    bi = jnp.asarray(rng.randn(E, f).astype(np.float32) * 0.1)
    wo = jnp.asarray(rng.randn(E, f, d).astype(np.float32) * 0.2)
    bo = jnp.asarray(rng.randn(E, d).astype(np.float32) * 0.1)
    mesh = make_mesh(n_data=1, n_expert=n)

    def body(x_l, m_l, router, wi, bi, wo, bo):
        disp, comb, _ = route_tokens(router, x_l, k=2,
                                     capacity_factor=2.0, token_mask=m_l)
        return ep_alltoall_ffn(wi, bi, wo, bo, disp, comb, x_l,
                               EXPERT_AXIS, dtype=jnp.float32)

    y = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(EXPERT_AXIS), P(EXPERT_AXIS), P(), P(), P(), P(), P()),
        out_specs=P(EXPERT_AXIS), check_vma=False))(
        x, mask, router, wi, bi, wo, bo)

    # dense reference: the same local routing + FULL expert set, one
    # token shard at a time
    refs = []
    for i in range(n):
        x_l = x[i * Tl:(i + 1) * Tl]
        disp, comb, _ = route_tokens(router, x_l, k=2, capacity_factor=2.0,
                                     token_mask=mask[i * Tl:(i + 1) * Tl])
        ein = jnp.einsum("tec,td->ecd", disp, x_l)
        hh = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ein, wi)
                         + bi[:, None, :])
        out = jnp.einsum("ecf,efd->ecd", hh, wo) + bo[:, None, :]
        refs.append(jnp.einsum("tec,ecd->td", comb, out))
    ref = jnp.concatenate(refs, axis=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_moe_pipeline_alltoall_matches_replicated():
    """Model-level: the pipelined expert-sharded MoE trunk with
    ep_impl='alltoall' (token-sharded dispatch) equals the replicated-
    token ep_partial_ffn path at overflow-free capacity — per-shard
    routing changes the slot GROUPING, not the combine, when nothing
    drops."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeml_tpu.parallel.mesh import make_mesh
    from tests.test_models_gpt import TinyMoE, make_lm_task

    rng = np.random.RandomState(0)
    B, T, M = 8, 16, 4

    class RoomyMoE(TinyMoE):
        # capacity 4x: no expert overflows under either grouping, so
        # the two dispatch strategies must agree exactly
        def build(self):
            m = super().build()
            return m.clone(capacity_factor=4.0)

    x = make_lm_task(rng, B)[:, :T]
    model = RoomyMoE()
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    ep_mesh = make_mesh(n_data=2, n_stage=2, n_expert=2)
    ref_logits, _ = model.forward_pipelined(
        variables, jnp.asarray(x), ep_mesh, microbatches=M)

    # SAME model instance: the pp cache keys on the module config, so
    # the clone must compile a fresh program, not reuse the replicated
    # path's (regression guard for the cache-key fix)
    model._module = model.module.clone(ep_impl="alltoall")
    logits, _ = model.forward_pipelined(
        variables, jnp.asarray(x), ep_mesh, microbatches=M)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=5e-2, atol=5e-2)


def test_kavg_sp_ep_round_matches_sp_only():
    """One K-avg SP training round with experts ALSO sharded over a
    2-way expert axis (SP x EP — round 4's last matrix cell) produces
    the same merged variables as the SP-only round with replicated
    experts: routing runs on expert-replicated tokens, ep_partial_ffn's
    psum assembles the identical FFN output, and the vma backward psums
    each lane's partial expert-weight grads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeml_tpu.parallel.kavg import KAvgEngine
    from kubeml_tpu.parallel.mesh import make_mesh
    from tests.test_models_gpt import VOCAB, TinyMoE

    rng = np.random.RandomState(4)
    W, S, B, T = 2, 2, 4, 32
    start = rng.randint(1, VOCAB - 1, size=(W * S * B, 1))
    x = ((start + np.arange(T)[None, :] - 1) % (VOCAB - 1) + 1) \
        .astype(np.int32).reshape(W, S, B, T)
    batch = {"x": jnp.asarray(x)}
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)

    model0 = TinyMoE()
    variables = model0.init_variables(jax.random.PRNGKey(0),
                                      {"x": jnp.asarray(x[0, 0])})

    def run(mesh, enable_ep):
        model = TinyMoE()
        model._module = model.module.clone(dropout=0.0)
        model.enable_seq_parallel("ring")
        if enable_ep:
            model.enable_expert_parallel()
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         lambda lr, e: optax.sgd(lr), donate=False,
                         batch_seq_dims=model.seq_batch_dims)
        out, stats = eng.train_round(variables, batch, rngs=rngs,
                                     lr=1e-2, epoch=0, **masks)
        return out, float(np.asarray(stats.loss_sum).sum())

    ref, loss_ref = run(
        make_mesh(n_data=2, n_seq=2, devices=jax.devices()[:4]), False)
    ep, loss_ep = run(
        make_mesh(n_data=2, n_seq=2, n_expert=2), True)

    assert abs(loss_ref - loss_ep) < 1e-3 * max(1.0, abs(loss_ref))
    for a, b in zip(jax.tree_util.tree_leaves(ref),
                    jax.tree_util.tree_leaves(ep)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-3, atol=5e-4)


def test_moe_pipeline_rejects_indivisible_experts():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest

    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.models.gpt import GPTModule, GPTMoEMini
    from tests.test_models_gpt import make_lm_task

    class ThreeExpertMoE(GPTMoEMini):
        def build(self):
            return GPTModule(vocab_size=64, max_len=32, hidden=32,
                             layers=2, heads=2, ffn=32, dropout=0.0,
                             n_experts=3)

    model = ThreeExpertMoE()
    rng = np.random.RandomState(0)
    x = make_lm_task(rng, 4)[:, :16]
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x)})
    mesh = make_mesh(n_data=2, n_stage=2, n_expert=2)
    with pytest.raises(ValueError, match="experts do not divide"):
        model.forward_pipelined(variables, jnp.asarray(x), mesh,
                                microbatches=2)
