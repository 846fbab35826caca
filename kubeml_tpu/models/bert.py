"""BERT-tiny encoder for sequence classification (SST-2 — BASELINE config 5).

Net-new relative to the reference (no transformer exists there; SURVEY.md
§2a lists transformer workloads as absent). Geometry follows the public
"BERT-tiny" point: 2 layers, hidden 128, 2 heads, FFN 512.

TPU-first:
  - attention goes through ops.masked_attention (bf16 matmuls, f32
    softmax), which auto-dispatches to the pallas flash kernel on TPU and
    the jnp reference path elsewhere; the ring-attention sequence-parallel
    path swaps in at the same primitive;
  - LayerNorm params stay float32; all matmuls bfloat16 (MXU);
  - padding flows as a [B, T] keep-mask with static shapes; each
    implementation composes its own additive bias from it
    (ops.attention.composed_bias is the semantics definition).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax

from kubeml_tpu.models import register_model
from kubeml_tpu.models.base import ClassifierModel, InferenceInputError
from kubeml_tpu.parallel.tp import TRANSFORMER_TP_RULES
from kubeml_tpu.ops.attention import masked_attention

PAD_ID = 0


class EncoderBlock(nn.Module):
    hidden: int
    heads: int
    ffn: int
    dropout: float
    dtype: jnp.dtype
    # set to the mesh seq-axis name for sequence parallelism: the block
    # then runs inside shard_map with [B, T_local, ...] activations and
    # attention becomes the ppermute ring (parallel/ring_attention.py)
    # or, with seq_impl="ulysses", the all-to-all head-sharded scheme
    # (parallel/ulysses.py — needs heads % seq-axis == 0)
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    # set to the mesh model-axis name for MANUAL tensor parallelism: the
    # block then runs inside a fully-manual shard_map with Megatron
    # column/row-parallel matmuls and hand-placed psums
    # (parallel/manual.py). Composes with seq_axis (ring impl).
    tp_axis: Optional[str] = None
    # attention implementation: 'auto' (flash on TPU when tiling allows,
    # for BOTH the dense path and the differentiable seq-parallel ring),
    # 'flash', or 'reference'
    attn_impl: str = "auto"
    flash_interpret: bool = False  # pallas interpreter (CPU tests)

    @nn.compact
    def __call__(self, h, pad_mask, train: bool, pos=None):
        head_dim = self.hidden // self.heads
        x = nn.LayerNorm(dtype=jnp.float32)(h)
        if self.tp_axis is not None:
            from kubeml_tpu.parallel.manual import (TPHeadsDense,
                                                    validate_tp_geometry)
            validate_tp_geometry(self.heads, self.ffn,
                                 jax.lax.axis_size(self.tp_axis))
            mk_qkv = partial(TPHeadsDense, self.heads, head_dim,
                             self.tp_axis, self.dtype)
        else:
            mk_qkv = partial(nn.DenseGeneral, (self.heads, head_dim),
                             dtype=self.dtype)
        q = mk_qkv(name="q")(x)
        k = mk_qkv(name="k")(x)
        v = mk_qkv(name="v")(x)
        if self.seq_impl not in ("ring", "ulysses"):  # static field
            raise ValueError(f"unknown seq_impl {self.seq_impl!r}; "
                             f"expected 'ring' or 'ulysses'")
        if self.tp_axis is not None and self.seq_axis is not None \
                and self.seq_impl == "ulysses":
            raise ValueError(
                "tensor parallelism composes with seq_impl='ring' only "
                "(ulysses re-shards the head axis the TP split owns)")
        if self.seq_axis is not None and self.seq_impl == "ulysses":
            # long-context path B: two all-to-alls re-shard seq->heads,
            # stock full attention per head group (flash-eligible)
            from kubeml_tpu.parallel.ulysses import ulysses_attention
            attn = ulysses_attention(q, k, v, kv_mask=pad_mask,
                                     causal=False,
                                     axis_name=self.seq_axis,
                                     impl=self.attn_impl,
                                     interpret=self.flash_interpret)
        elif self.seq_axis is not None:
            # long-context path A: KV blocks rotate around the seq ring;
            # O(block) HBM on the flash path, O(T_local^2) on reference
            from kubeml_tpu.ops.attention import ring_flash_eligible
            from kubeml_tpu.parallel.ring_attention import ring_attention
            use_flash = (ring_flash_eligible(q.shape[1])
                         if self.attn_impl == "auto"
                         else self.attn_impl == "flash")
            attn = ring_attention(q, k, v, q_pos=pos, kv_pos=pos,
                                  kv_mask=pad_mask, causal=False,
                                  axis_name=self.seq_axis,
                                  use_flash=use_flash,
                                  interpret=self.flash_interpret)
        else:
            # auto-dispatch: pallas flash kernel on TPU, jnp ref on CPU
            attn = masked_attention(q, k, v, pad_mask,
                                    impl=self.attn_impl,
                                    interpret=self.flash_interpret)
        # one scaffolding path for both execution modes: only the three
        # Dense constructors differ (manual-TP mirrors share the dense
        # modules' param tree paths — checkpoint/merge parity)
        if self.tp_axis is not None:
            from kubeml_tpu.parallel.manual import (TPColumnDense,
                                                    TPOutDense, TPRowDense)
            mk_out = partial(TPOutDense, self.heads, head_dim,
                             self.hidden, self.tp_axis, self.dtype)
            mk_d0 = partial(TPColumnDense, self.ffn, self.tp_axis,
                            self.dtype)
            mk_d1 = partial(TPRowDense, self.hidden, self.ffn,
                            self.tp_axis, self.dtype)
        else:
            mk_out = partial(nn.DenseGeneral, self.hidden, axis=(-2, -1),
                             dtype=self.dtype)
            mk_d0 = partial(nn.Dense, self.ffn, dtype=self.dtype)
            mk_d1 = partial(nn.Dense, self.hidden, dtype=self.dtype)
        attn = mk_out(name="out")(attn)
        attn = nn.Dropout(self.dropout, deterministic=not train)(attn)
        h = h + attn
        x = nn.LayerNorm(dtype=jnp.float32)(h)
        x = mk_d0(name="Dense_0")(x)
        x = nn.gelu(x)
        x = mk_d1(name="Dense_1")(x)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return h + x


class BertModule(nn.Module):
    vocab_size: int = 30522
    max_len: int = 128
    hidden: int = 128
    layers: int = 2
    heads: int = 2
    ffn: int = 512
    num_classes: int = 2
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    seq_axis: Optional[str] = None  # sequence-parallel mode (see below)
    seq_impl: str = "ring"          # 'ring' | 'ulysses'
    tp_axis: Optional[str] = None   # manual tensor-parallel mode
    attn_impl: str = "auto"         # 'auto' | 'flash' | 'reference'
    flash_interpret: bool = False   # pallas interpreter (CPU tests)

    @nn.compact
    def __call__(self, x, train: bool = False):
        # x: int32 token ids [B, T], T <= max_len, pad id 0.
        # With seq_axis set, this runs inside shard_map: x is the LOCAL
        # [B, T/n] sequence block, positions are offset by the shard
        # index, attention rides the ppermute ring, and the mean-pool
        # reduces over the seq axis — so the module computes exactly the
        # global-sequence forward while no chip ever holds the full T.
        B, T = x.shape
        n_shards = 1 if self.seq_axis is None \
            else jax.lax.axis_size(self.seq_axis)
        if T * n_shards > self.max_len:  # static trace-time guard.
            # InferenceInputError (a ValueError) so the serving layer
            # returns 4xx when the overlong sequence came from a client
            raise InferenceInputError(
                f"sequence length {T * n_shards} exceeds max_len "
                f"{self.max_len}")
        pad_mask = (x != PAD_ID).astype(jnp.float32)
        if self.seq_axis is None:
            pos_ids = jnp.arange(T)
        else:
            pos_ids = lax.axis_index(self.seq_axis) * T + jnp.arange(T)
        h = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     name="tok_embed")(x)
        pos = nn.Embed(self.max_len, self.hidden, dtype=self.dtype,
                       name="pos_embed")(pos_ids[None, :])
        h = h + pos
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        for i in range(self.layers):
            h = EncoderBlock(self.hidden, self.heads, self.ffn, self.dropout,
                             self.dtype, seq_axis=self.seq_axis,
                             seq_impl=self.seq_impl, tp_axis=self.tp_axis,
                             attn_impl=self.attn_impl,
                             flash_interpret=self.flash_interpret,
                             name=f"layer_{i}")(h, pad_mask, train,
                                                pos=pos_ids)
        h = nn.LayerNorm(dtype=jnp.float32)(h)
        # masked mean-pool (robust without a trained [CLS]); in
        # seq-parallel mode the pool is a psum over the seq ring, after
        # which the logits are replicated across shards
        num = (h * pad_mask[..., None]).sum(axis=1)
        den = pad_mask.sum(axis=1)
        if self.seq_axis is not None:
            num = lax.psum(num, self.seq_axis)
            den = lax.psum(den, self.seq_axis)
        pooled = num / jnp.maximum(den, 1.0)[..., None]
        out = nn.Dense(self.num_classes, dtype=self.dtype,
                       name="classifier")(pooled.astype(self.dtype))
        return out.astype(jnp.float32)


@register_model("bert-tiny")
class BertTiny(ClassifierModel):
    name = "bert-tiny"
    num_classes = 2

    # job-surface parallelism: Megatron TP over the encoder blocks, and
    # ring/ulysses SP over the token dim of 'x' (the base
    # enable_seq_parallel serves any model declaring seq_batch_dims).
    # The classifier's per-example loss is already seq-invariant (the
    # module psums its mean-pool over the ring), so the engine's
    # seq-parallel round needs no loss changes for this family.
    seq_batch_dims = {"x": 0}
    tp_rules = TRANSFORMER_TP_RULES

    def build(self):
        return BertModule(num_classes=self.num_classes)

    def configure_optimizers(self, lr, epoch):
        return optax.adamw(lr, weight_decay=0.01)

    # --------------------------------------------- pipeline-parallel training

    def enable_pipeline_parallel(self, n_stage: int,
                                 microbatches: int = 0) -> None:
        """Route TRAINING through the GPipe body over the mesh `stage`
        axis (--pipeline-parallel; same design as the GPT family,
        models/gpt.py): the encoder trunk splits into stage-axis groups
        of L/P consecutive blocks, the module stays DENSE (per-layer
        params stacked in-trace, each stage axis_slices its group —
        tree paths/shapes unchanged, so checkpoints/merge/inference
        apply as-is), and vma backward assembles the stage psums."""
        if self.module.seq_axis is not None or \
                getattr(self.module, "tp_axis", None) is not None:
            raise ValueError(
                "pipeline parallelism composes with expert parallelism "
                "only (not --seq-parallel/--tensor-parallel)")
        L = self.module.layers
        if L % n_stage:
            raise ValueError(
                f"{L} layers do not split over a {n_stage}-stage axis")
        self._pp_microbatches = int(microbatches) or 2 * int(n_stage)

    def loss(self, variables, batch, rng, sample_mask):
        if getattr(self, "_pp_microbatches", 0):
            return self._pp_forward_loss(variables, batch, rng)
        return super().loss(variables, batch, rng, sample_mask)

    def _pp_forward_loss(self, variables, batch, rng):
        """Pipelined classifier loss: embed + final LN/pool/head run
        replicated on every stage; the L encoder blocks pipeline with
        pad masks and per-microbatch dropout keys riding as consts.
        Equal to the dense loss up to bf16 noise (pinned by
        tests/test_job.py's PP-vs-dense BERT history parity)."""
        from kubeml_tpu.parallel.manual import axis_slice
        from kubeml_tpu.parallel.mesh import STAGE_AXIS
        from kubeml_tpu.parallel.pp import pipeline_lane

        module = self.module
        params = variables["params"]
        x = batch["x"]
        B, T = x.shape
        if T > module.max_len:
            raise InferenceInputError(
                f"sequence length {T} exceeds max_len {module.max_len}")
        n_stage = jax.lax.axis_size(STAGE_AXIS)
        per = module.layers // n_stage
        M = self._pp_microbatches
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by {M} microbatches")
        pad_mask = (x != PAD_ID).astype(jnp.float32)
        emb = params["tok_embed"]["embedding"].astype(module.dtype)
        h = emb[x] + params["pos_embed"]["embedding"][
            jnp.arange(T)].astype(module.dtype)[None]
        k_embed, k_blocks = jax.random.split(rng)
        if module.dropout > 0.0:  # the dense path's post-embed dropout
            keep = jax.random.bernoulli(k_embed, 1.0 - module.dropout,
                                        h.shape)
            h = jnp.where(keep, h / (1.0 - module.dropout), 0.0).astype(
                module.dtype)

        block = EncoderBlock(module.hidden, module.heads, module.ffn,
                             module.dropout, module.dtype,
                             attn_impl=module.attn_impl,
                             flash_interpret=module.flash_interpret)

        def stage_fn(p, act, const):
            mask, kdata = const  # [B/M, T] pad mask, [2] key data
            key = jax.random.wrap_key_data(kdata)
            sid = lax.axis_index(STAGE_AXIS)

            def body(a, xs_l):
                pj, j = xs_l
                kj = jax.random.fold_in(key, sid * per + j)
                return block.apply({"params": pj}, a, mask, True,
                                   rngs={"dropout": kj}), None

            act, _ = lax.scan(body, act, (p, jnp.arange(per)))
            return act

        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0),
            *[params[f"layer_{i}"] for i in range(module.layers)])
        local = jax.tree_util.tree_map(
            lambda leaf: axis_slice(leaf, STAGE_AXIS, 0), stacked)

        keys = jax.random.key_data(jax.random.split(k_blocks, M))
        hm = h.reshape(M, B // M, T, module.hidden)
        masks = pad_mask.reshape(M, B // M, T)
        ys, _ = pipeline_lane(stage_fn, local, hm, STAGE_AXIS,
                              consts=(masks, keys), vma=True)
        h = ys.reshape(B, T, module.hidden)
        h = nn.LayerNorm(dtype=jnp.float32).apply(
            {"params": params["LayerNorm_0"]}, h)
        # masked mean-pool + classifier head, replicated (dense parity)
        num = (h * pad_mask[..., None]).sum(axis=1)
        den = pad_mask.sum(axis=1)
        pooled = num / jnp.maximum(den, 1.0)[..., None]
        logits = nn.Dense(module.num_classes, dtype=module.dtype).apply(
            {"params": params["classifier"]},
            pooled.astype(module.dtype)).astype(jnp.float32)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"])
        return per_ex, {}

    def forward_seq_parallel(self, variables, x, mesh, impl="ring"):
        """Long-context forward over the mesh `seq` axis.

        x: [B, T] with T divisible by the seq-axis size; the same
        variables as the dense module (shapes are identical, only the
        execution is sharded). Returns [B, num_classes] logits equal to
        the dense forward — no chip ever materializes the full sequence
        or an O(T^2) score tensor.

        impl: 'ring' (ppermute KV rotation) or 'ulysses' (all-to-all
        head sharding; needs heads % seq-axis == 0).
        """
        from jax.sharding import PartitionSpec as P

        from kubeml_tpu.parallel.mesh import SEQ_AXIS

        if impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq-parallel impl {impl!r}; "
                             f"expected 'ring' or 'ulysses'")
        n_seq = mesh.shape[SEQ_AXIS]
        if x.shape[1] % n_seq:
            raise ValueError(
                f"sequence length {x.shape[1]} not divisible by the "
                f"seq-axis size {n_seq}")
        # module in the key: a clone (attn_impl, ...) must not silently
        # reuse the previous configuration's compiled program
        key = (self.module, mesh, x.shape[1] // n_seq, impl)
        if not hasattr(self, "_sp_cache"):
            self._sp_cache = {}
        if key not in self._sp_cache:
            # clone copies every dense-module field, overriding only the
            # execution mode — dense/seq-parallel parity by construction
            sp_module = self.module.clone(seq_axis=SEQ_AXIS, seq_impl=impl)

            def fwd(variables, x_local):
                return sp_module.apply(variables, x_local, train=False)

            self._sp_cache[key] = jax.jit(jax.shard_map(
                fwd, mesh=mesh, in_specs=(P(), P(None, SEQ_AXIS)),
                out_specs=P(), check_vma=False))
        return self._sp_cache[key](variables, x)
