"""BERT/ERNIE-style bidirectional encoders with pretraining heads.

Reference capability target: BASELINE.md configs 3-4 (BERT-base
pretraining over Fleet DP, ERNIE-large with ZeRO-2 + AMP). The reference
builds these from python/paddle/nn/layer/transformer.py encoder layers;
ERNIE shares the BERT architecture (the differences are pretraining data
and masking strategy), so `ernie_large()` is a preset of the same model.

Written sharded-by-default like models/gpt.py: QKV/MLP-up as
ColumnParallel, attn-out/MLP-down as RowParallel over 'tp', vocab-
parallel embeddings, flash attention (non-causal) on TPU via
nn.functional.scaled_dot_product_attention.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .. import nn
from ..nn import functional as F
from ..core.tensor import Tensor
from ..ops import manipulation as M
from ..ops.linalg import matmul
from ..distributed.tp_layers import (ColumnParallelLinear, RowParallelLinear,
                                     VocabParallelEmbedding)

__all__ = ["BertConfig", "Bert", "BertForPretraining",
           "bert_pretrain_loss_fn", "bert_base", "ernie_large",
           "make_bert_pretrain_batch"]


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position: int = 512
    type_vocab_size: int = 2
    ffn_mult: int = 4
    dropout: float = 0.0
    layer_norm_eps: float = 1e-12


def bert_base():
    return BertConfig()


def ernie_large():
    """ERNIE-large (BASELINE config 4): same architecture, 24L/1024H/16H,
    the config the reference trains with Fleet sharding + AMP."""
    return BertConfig(vocab_size=18000, hidden_size=1024, num_layers=24,
                      num_heads=16, max_position=512, type_vocab_size=4)


class BertSelfAttention(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv = ColumnParallelLinear(cfg.hidden_size,
                                        3 * cfg.hidden_size,
                                        gather_output=False)
        self.out = RowParallelLinear(cfg.hidden_size, cfg.hidden_size,
                                     input_is_parallel=True)

    def _pack_gate(self, T: int, attn_mask) -> bool:
        """Packed-pair flash routing (ops/pallas/packed_flash.route_gate).
        At ERNIE-large geometry (T=512, d=64, 16 heads) the upstream
        flash kernel pads head_dim 64->128 AND stages an f32 output —
        128 MB/layer of HLO temps (an earlier builder saw bs=32 run out
        of HBM on it); the packed kernel keeps pairs on the 128 lanes with bf16
        in/out."""
        from ..ops.pallas import packed_flash
        return packed_flash.route_gate(
            self.head_dim, self.num_heads, T, T,
            dropout_active=self.cfg.dropout > 0.0 and self.training,
            masked=attn_mask is not None)

    def forward(self, x, attn_mask=None):
        from .gpt import sliced_qkv
        B, T = x.shape[0], x.shape[1]
        pack = self._pack_gate(T, attn_mask)
        q, k, v = sliced_qkv(x, self.qkv, self.num_heads, self.head_dim,
                             pack_pairs=pack)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=False,
            dropout_p=self.cfg.dropout, training=self.training,
            _heads_major=True, _packed_pairs=pack)
        out = M.reshape(M.transpose(out, [0, 2, 1, 3]), [B, T, -1])
        return self.out(out)


class BertLayer(nn.Layer):
    """Post-LN encoder block (the BERT/reference transformer layout:
    residual then LayerNorm, unlike GPT's pre-LN)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attn = BertSelfAttention(cfg)
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        inner = cfg.ffn_mult * cfg.hidden_size
        self.up = ColumnParallelLinear(cfg.hidden_size, inner,
                                       gather_output=False)
        self.down = RowParallelLinear(inner, cfg.hidden_size,
                                      input_is_parallel=True)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, attn_mask=None):
        from ..parallel.api import shard_batch_activation
        x = self.ln1(x + self.drop(self.attn(x, attn_mask)))
        h = self.down(F.gelu(self.up(x), approximate=True))
        return shard_batch_activation(self.ln2(x + self.drop(h)))


class Bert(nn.Layer):
    """Encoder trunk: embeddings + N bidirectional blocks + pooler."""

    def __init__(self, cfg: BertConfig = None, **kwargs):
        super().__init__()
        cfg = cfg or BertConfig(**kwargs)
        self.cfg = cfg
        self.word_emb = VocabParallelEmbedding(cfg.vocab_size,
                                               cfg.hidden_size)
        self.pos_emb = nn.Embedding(cfg.max_position, cfg.hidden_size)
        self.type_emb = nn.Embedding(cfg.type_vocab_size, cfg.hidden_size)
        self.emb_ln = nn.LayerNorm(cfg.hidden_size,
                                   epsilon=cfg.layer_norm_eps)
        self.drop = nn.Dropout(cfg.dropout)
        self.layers = nn.LayerList([BertLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        import jax.numpy as jnp
        B, T = input_ids.shape[0], input_ids.shape[1]
        pos = Tensor(jnp.arange(T, dtype=jnp.int32)[None, :])
        x = self.word_emb(input_ids) + self.pos_emb(pos)
        if token_type_ids is not None:
            x = x + self.type_emb(token_type_ids)
        x = self.drop(self.emb_ln(x))
        from ..parallel.api import shard_batch_activation
        x = shard_batch_activation(x)
        for layer in self.layers:
            x = layer(x, attn_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (the reference pretraining objective). The MLM
    decoder IS weight-tied to the word embedding — logits come from
    h @ word_emb.weight^T plus a per-vocab bias, the standard BERT
    parameterization (no separate V x H decoder matrix)."""

    def __init__(self, cfg: BertConfig = None, **kwargs):
        super().__init__()
        cfg = cfg or BertConfig(**kwargs)
        self.cfg = cfg
        self.bert = Bert(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_ln = nn.LayerNorm(cfg.hidden_size,
                                   epsilon=cfg.layer_norm_eps)
        self.mlm_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self.nsp = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None,
                masked_positions=None):
        """masked_positions: optional [B, P] int positions of the masked
        tokens. When given, the MLM transform + tied unembed run only on
        those P rows ([B, P, V] logits instead of [B, T, V]) — the
        reference design (bert_dygraph_model.py:335 gathers mask_pos
        before PretrainingHeads; ernie/static BERT do the same). At the
        standard 15% masking this cuts the dominant V x H matmul and its
        logits traffic ~6x. Omit it for dense whole-sequence logits."""
        seq, pooled = self.bert(input_ids, token_type_ids, attn_mask)
        if masked_positions is not None:
            idx = M.unsqueeze(masked_positions, -1)
            seq = M.take_along_axis(seq, idx, axis=1)  # [B, P, H]
        h = self.mlm_ln(F.gelu(self.mlm_transform(seq), approximate=True))
        logits = matmul(h, self.bert.word_emb.weight,
                        transpose_y=True) + self.mlm_bias
        return logits, self.nsp(pooled)

    def loss(self, input_ids, token_type_ids, mlm_labels,
             nsp_labels=None, masked_positions=None):
        """mlm_labels: [B, T] with -100 at unmasked positions (the
        standard ignore_index contract the fused CE honours) — or [B, P]
        labels aligned with masked_positions when those are passed
        (ragged batches pad with -100);
        nsp_labels: [B] int64 or None."""
        logits, nsp_logits = self(input_ids, token_type_ids,
                                  masked_positions=masked_positions)
        mlm = F.cross_entropy(
            M.reshape(logits, [-1, self.cfg.vocab_size]),
            M.reshape(mlm_labels, [-1]), ignore_index=-100)
        if nsp_labels is None:
            return mlm
        return mlm + F.cross_entropy(nsp_logits, nsp_labels)


def bert_pretrain_loss_fn(model, input_ids, token_type_ids, mlm_labels,
                          nsp_labels, masked_positions=None):
    """loss_fn signature for jit.TrainStep / parallel.ShardedTrainStep."""
    return model.loss(input_ids, token_type_ids, mlm_labels, nsp_labels,
                      masked_positions=masked_positions)


def make_bert_pretrain_batch(rng, vocab_size, bs, seq, mask_rate=0.15):
    """Synthetic MLM+NSP pretraining batch in the masked-position layout
    the head expects (examples/bert_pretrain.py and the tests share this
    recipe — keep the contract in one place).

    Returns numpy arrays (input_ids, token_type_ids, mlm_labels,
    nsp_labels, masked_positions); P = round(mask_rate*seq) positions per
    row, chosen without replacement and SORTED (the gather head's
    contract)."""
    x = rng.randint(0, vocab_size, (bs, seq), dtype=np.int32)
    tt = rng.randint(0, 2, (bs, seq), dtype=np.int32)
    P = max(1, int(round(seq * mask_rate)))
    pos = np.stack([rng.choice(seq, P, replace=False) for _ in range(bs)])
    pos.sort(axis=1)
    mlm = rng.randint(0, vocab_size, (bs, P)).astype(np.int64)
    nsp = rng.randint(0, 2, (bs,)).astype(np.int64)
    return x, tt, mlm, nsp, pos.astype(np.int32)
