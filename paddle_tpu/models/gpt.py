"""GPT: the flagship decoder-only transformer.

Reference capability target: GPT-3-style static-graph training with Fleet
pipeline+recompute (BASELINE.json config 5) and ERNIE/BERT-style encoders
(configs 3-4). The reference builds these from python/paddle/nn/layer/
transformer.py primitives + fleet meta-optimizers; here the model is written
sharded-by-default (SPMD annotations are no-ops without a mesh):

- tensor parallel: QKV/MLP-up as ColumnParallel, attn-out/MLP-down as
  RowParallel over the 'tp' axis (Megatron layout: one psum per block pair)
- sequence parallel: activations between blocks sharded over 'sp' on the
  sequence dim (ring-free: XLA chooses all-gather/reduce-scatter points)
- attention: nn.functional.scaled_dot_product_attention (pallas flash on
  TPU for long sequences)
- recompute: per-block jax.checkpoint via fleet.utils.recompute
- pipeline: the stacked-parameter variant lives in
  paddle_tpu.parallel.pipeline (shard_map + ppermute microbatch schedule)
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..ops import manipulation as M
from ..parallel.api import (shard_activation, shard_batch_activation,
                            mark_sharding)
from ..distributed.tp_layers import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_mult: int = 4
    dropout: float = 0.0
    # False/"none": no remat. True: legacy per-block recompute inside
    # GPTBlock.forward. "full": per-block remat applied by the GPT-level
    # loop. "group:<k>": contiguous groups of k blocks, each wrapped in
    # ONE jax.checkpoint (k trades recompute FLOPs against live bytes).
    # "auto": the policy committed by the static planner
    # (analysis/jaxplan.py, jaxplan.json) — pick the cheapest policy
    # whose predicted peak fits the HBM envelope instead of hand-tuning.
    use_recompute: object = False
    # NOTE: block outputs are unconditionally constrained to the canonical
    # [batch=(dp,sharding), seq=sp] layout regardless of this flag; on
    # build_mesh meshes sp defaults to size 1 so this is a no-op, but a
    # custom mesh with sp>1 gets sequence-sharded activations even with
    # sequence_parallel=False. This flag still controls the ln/dropout
    # scatter-gather placement choices.
    sequence_parallel: bool = False
    # context parallelism: attention itself runs ring-sharded over the
    # 'sp' mesh axis (parallel/ring_attention.py) — the long-context path
    # where even one layer's [T, T] scores don't fit a chip
    context_parallel: bool = False
    # mixture-of-experts: >0 replaces the dense MLP with an
    # expert-parallel MoEMLP (distributed/moe.py, 'ep' mesh axis) in
    # every moe_every-th block; load-balance aux added to loss()
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    # presets (reference marketing targets: BASELINE.json configs)
    @staticmethod
    def gpt3_1p3b():
        return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                         num_heads=16, max_seq_len=2048)

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                         num_heads=4, max_seq_len=64)


def sliced_qkv(x, qkv_layer, num_heads: int, head_dim: int,
               pack_pairs: bool = False):
    """q/k/v heads-major [B, H, T, D] from a fused qkv projection.

    tp == 1 (the single-chip/dp fast path): THREE F.linear calls against
    trace-time slices of the fused weight (same parameters, identical
    math) — each output goes straight to [B, H, T, D] with a small
    transpose XLA fuses into the matmul epilogue. The packed alternative
    (one [B,T,3HD] matmul -> reshape -> 5-D transpose -> unstack) left
    ~20 ms/step of materialised layout copies around the pallas
    custom-call at the GPT bench geometry; this form measured +8.7% step
    throughput (r4). F.linear keeps the bias add inside the AMP
    white-listed op, so O1 autocast emits bf16 q/k/v exactly like the
    fused layer would.

    tp > 1: the fused ColumnParallelLinear path — its shard boundaries
    split the 3*HD columns evenly across 'tp', so thirds-slicing would
    force per-layer resharding.
    """
    from ..parallel.mesh import get_global_mesh
    B, T = x.shape[0], x.shape[1]
    mesh = get_global_mesh()
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        qkv = M.reshape(qkv_layer(x), [B, T, 3, num_heads, head_dim])
        qkv = M.transpose(qkv, [2, 0, 3, 1, 4])
        return M.unstack(qkv, axis=0)
    HD = num_heads * head_dim
    w, bias = qkv_layer.weight, qkv_layer.bias
    out = []
    for i in range(3):
        o = F.linear(x, w[:, i * HD:(i + 1) * HD],
                     bias[i * HD:(i + 1) * HD])
        if pack_pairs:
            # adjacent head pairs stay merged on the 128-lane minor dim:
            # [B,T,H,64] -> [B,T,H/2,128] is a pure view, and THIS
            # transpose fuses (128-minor), unlike the d=64 one —
            # ops/pallas/packed_flash.py consumes the packed layout
            o = M.reshape(o, [B, T, num_heads // 2, 2 * head_dim])
        else:
            o = M.reshape(o, [B, T, num_heads, head_dim])
        out.append(M.transpose(o, [0, 2, 1, 3]))  # [B, H(, /2), T, D(*2)]
    return out


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv = ColumnParallelLinear(cfg.hidden_size,
                                        3 * cfg.hidden_size,
                                        gather_output=False)
        self.out = RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                     input_is_parallel=True)

    def _pack_gate(self, T: int) -> bool:
        """Packed-pair flash (head pairs on 128 lanes, ops/pallas/
        packed_flash.py): at head_dim 64 it removes the layout copies the
        custom-call boundary forces on 64-minor tensors. Shared routing
        gate: packed_flash.route_gate (flash conditions + kernel scope +
        unpacked-tp exclusion)."""
        from ..ops.pallas import packed_flash
        return packed_flash.route_gate(
            self.head_dim, self.num_heads, T, T,
            dropout_active=self.cfg.dropout > 0.0 and self.training)

    def forward(self, x):
        B, T = x.shape[0], x.shape[1]
        use_ring = False
        if self.cfg.context_parallel:
            from ..parallel.mesh import ensure_global_mesh
            use_ring = ensure_global_mesh().shape.get("sp", 1) > 1
        pack = not use_ring and self._pack_gate(T)
        q, k, v = sliced_qkv(x, self.qkv, self.num_heads, self.head_dim,
                             pack_pairs=pack)
        if use_ring:
            out = self._ring_attention(q, k, v)  # [B, H, T, D]
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.cfg.dropout,
                training=self.training, _heads_major=True,
                _packed_pairs=pack)  # [B, H, T, D] (packed: [B,H/2,T,2D])
        # the [0,2,1,3] transpose + reshape maps BOTH layouts to [B, T, C]
        # with heads in natural order (packed pairs are lane-adjacent)
        out = M.reshape(M.transpose(out, [0, 2, 1, 3]), [B, T, -1])
        return self.out(out)

    def _ring_attention(self, q, k, v):
        """Attention sequence-sharded over the 'sp' mesh axis: Q resident,
        K/V rotating over ICI (parallel/ring_attention.py). Manual over
        'sp' only — dp/tp/sharding stay in GSPMD auto mode so context
        parallelism composes with the other degrees."""
        from jax.sharding import PartitionSpec as P
        from ..core.dispatch import dispatch
        from ..parallel import shard_map
        from ..parallel.mesh import ensure_global_mesh
        from ..parallel.ring_attention import ring_attention
        if self.cfg.dropout > 0.0 and self.training:
            raise NotImplementedError(
                "attention dropout under context_parallel is not "
                "implemented (per-chunk RNG across the rotating ring); "
                "set dropout=0.0 or context_parallel=False")
        mesh = ensure_global_mesh()
        # ptlint: disable=PT-S001  the sequence-parallel contract of
        # ring attention: heads stay local, sequence shards over 'sp' —
        # jaxshard budgets this exact layout in collective.ring_attention
        spec = P(None, None, "sp", None)
        fn = shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, "sp",
                                              causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={"sp"})
        return dispatch("ring_attention", fn, (q, k, v), {}, True)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        inner = cfg.ffn_mult * cfg.hidden_size
        self.up = ColumnParallelLinear(cfg.hidden_size, inner,
                                       gather_output=False)
        self.down = RowParallelLinear(inner, cfg.hidden_size,
                                      input_is_parallel=True)

    def forward(self, x):
        return self.down(F.gelu(self.up(x), approximate=True))


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.cfg = cfg
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        use_moe = (cfg.moe_experts > 0
                   and layer_idx % max(cfg.moe_every, 1)
                   == max(cfg.moe_every, 1) - 1)
        if use_moe:
            from ..distributed.moe import MoEMLP
            self.mlp = MoEMLP(cfg.hidden_size, cfg.moe_experts,
                              ffn_hidden_size=cfg.ffn_mult * cfg.hidden_size,
                              top_k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)

    def _body(self, x):
        x = x + self.attn(self.ln1(x))
        x = x + self.mlp(self.ln2(x))
        x = shard_batch_activation(x)
        return x

    def forward(self, x):
        # `is True` on purpose: planned policies ("auto"/"full"/
        # "group:k" — truthy strings) are applied by the GPT-level
        # block loop, which may wrap SEVERAL blocks in one checkpoint;
        # only the legacy boolean keeps the per-block path here.
        if self.cfg.use_recompute is True:
            from ..distributed.fleet.utils import recompute
            from ..distributed.moe import MoEMLP
            if isinstance(self.mlp, MoEMLP):
                # aux loss must ride the checkpointed return — a Tensor
                # stashed on the layer inside jax.checkpoint would leak
                # its tracer into the outer trace
                def body_with_aux(x_):
                    out = self._body(x_)
                    return out, self.mlp.aux_loss
                out, aux = recompute(body_with_aux, x)
                self.mlp.aux_loss = aux
                return out
            return recompute(self._body, x)
        return self._body(x)


def _resolve_remat_group(cfg: GPTConfig) -> int:
    """Map cfg.use_recompute to the GPT-level checkpoint group size
    (0 = no GPT-level remat). Booleans resolve to 0 — False is off and
    True keeps the legacy per-block path inside GPTBlock.forward.
    "auto" resolves through the committed plan (jaxplan.json); explicit
    "none"/"full"/"group:<k>" policies are what the planner itself uses
    to build scoring candidates."""
    pol = cfg.use_recompute
    if pol is True or pol is False or pol is None:
        return 0
    if isinstance(pol, str):
        from ..analysis import jaxplan
        if pol == "auto":
            pol = jaxplan.committed_remat_policy()
        return jaxplan.remat_group_size(pol, cfg.num_layers)
    raise ValueError(
        f"use_recompute must be a bool, 'auto', 'none', 'full' or "
        f"'group:<k>', got {pol!r}")


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig = None, **kwargs):
        super().__init__()
        cfg = cfg or GPTConfig(**kwargs)
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg, layer_idx=i)
                                    for i in range(cfg.num_layers)])
        # planned remat: group size applied by forward()'s block loop
        # (0 = off; legacy use_recompute=True stays inside GPTBlock)
        self._remat_group = _resolve_remat_group(cfg)
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        # column-parallel LM head over vocab (untied: its own V x H
        # matrix; the bench FLOPs formula counts the unembed matmul once
        # either way)
        self.lm_head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size,
                                            has_bias=False,
                                            gather_output=True)

    def serving_spec(self):
        """The `ModelSpec` `LLMEngine.from_model` serves this model
        through (models/generation.py is the family's decode path)."""
        from .generation import serving_spec
        cfg = self.cfg
        return serving_spec((cfg.num_layers, cfg.num_heads,
                             cfg.hidden_size // cfg.num_heads,
                             cfg.max_seq_len))

    def forward(self, input_ids):
        B, T = input_ids.shape[0], input_ids.shape[1]
        import jax.numpy as jnp
        pos = Tensor(jnp.arange(T, dtype=jnp.int32)[None, :])
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        x = shard_batch_activation(x)
        g = self._remat_group
        if g:
            blocks = list(self.blocks)
            for s in range(0, len(blocks), g):
                x = self._run_group_rematted(blocks[s:s + g], x)
        else:
            for blk in self.blocks:
                x = blk(x)
        x = self.ln_f(x)
        return self.lm_head(x)

    def _run_group_rematted(self, group, x):
        """One checkpointed segment of `group` consecutive blocks. MoE
        aux losses must ride the checkpointed return — a Tensor stashed
        on a layer inside jax.checkpoint would leak its tracer into the
        outer trace — so they come back as extra outputs and are
        restored onto their layers afterwards."""
        from ..distributed.fleet.utils import recompute
        from ..distributed.moe import MoEMLP
        moe_blocks = [b for b in group if isinstance(b.mlp, MoEMLP)]

        def segment(x_):
            for b in group:
                x_ = b(x_)
            return (x_, *[b.mlp.aux_loss for b in moe_blocks])

        if not moe_blocks:
            return recompute(lambda x_: segment(x_)[0], x)
        out, *auxes = recompute(segment, x)
        for b, aux in zip(moe_blocks, auxes):
            b.mlp.aux_loss = aux
        return out

    def loss(self, input_ids, labels):
        logits = self(input_ids)
        loss = F.cross_entropy(
            M.reshape(logits, [-1, self.cfg.vocab_size]),
            M.reshape(labels, [-1]))
        if self.cfg.moe_experts > 0 and self.cfg.moe_aux_weight > 0:
            from ..distributed.moe import MoEMLP
            for blk in self.blocks:
                if isinstance(blk.mlp, MoEMLP) and blk.mlp.aux_loss is not None:
                    loss = loss + self.cfg.moe_aux_weight * blk.mlp.aux_loss
        return loss


def gpt_loss_fn(model, input_ids, labels):
    """loss_fn signature for jit.TrainStep / parallel.ShardedTrainStep."""
    return model.loss(input_ids, labels)
