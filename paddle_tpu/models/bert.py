# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""BERT (BERT-base pretraining, Fleet data-parallel).

Architecture per the original BERT; built from the framework's transformer
layers so it exercises MultiHeadAttention/TransformerEncoder the way
PaddleNLP's BertModel does (the reference tree itself hosts the nn layers,
python/paddle/nn/layer/transformer.py).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12

    @staticmethod
    def base(**overrides):
        cfg = BertConfig()
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg

    @staticmethod
    def tiny(**overrides):
        cfg = BertConfig(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, intermediate_size=128,
                         max_position_embeddings=64, type_vocab_size=2)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size,
                                            config.hidden_size)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size,
                                                  config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size,
                                       config.layer_norm_eps)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        from .. import ops

        T = input_ids.shape[1]
        pos = ops.arange(T, dtype="int32").unsqueeze(0)
        emb = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is not None:
            emb = emb + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(emb))


class BertModel(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        enc_layer = nn.TransformerEncoderLayer(
            config.hidden_size, config.num_attention_heads,
            config.intermediate_size, dropout=config.hidden_dropout_prob,
            activation=config.hidden_act,
            attn_dropout=config.attention_probs_dropout_prob,
            layer_norm_eps=config.layer_norm_eps)
        self.encoder = nn.TransformerEncoder(enc_layer,
                                             config.num_hidden_layers)
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        emb = self.embeddings(input_ids, token_type_ids)
        mask = None
        if attention_mask is not None:
            def _expand_mask(m):
                # [B, T] (1 = keep) → additive [B, 1, 1, T]
                return (1.0 - m.astype(jnp.float32))[:, None, None, :] * -1e9
            mask = apply("bert_mask", _expand_mask, attention_mask,
                         _differentiable=False)
        seq = self.encoder(emb, mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.bert = BertModel(config)
        self.mlm_transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.mlm_norm = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.mlm_bias = self.create_parameter([config.vocab_size],
                                              is_bias=True)
        self.nsp_head = nn.Linear(config.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_lm_labels=None, next_sentence_labels=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.mlm_norm(F.gelu(self.mlm_transform(seq)))

        def _mlm_logits(hv, emb_w, bias):
            return hv @ emb_w.T + bias
        logits = apply("mlm_logits", _mlm_logits, h,
                       self.bert.embeddings.word_embeddings.weight,
                       self.mlm_bias)
        nsp_logits = self.nsp_head(pooled)
        if masked_lm_labels is not None:
            mlm_loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                masked_lm_labels.reshape([-1]), ignore_index=-100)
            total = mlm_loss
            if next_sentence_labels is not None:
                total = total + F.cross_entropy(nsp_logits,
                                                next_sentence_labels)
            return total, logits, nsp_logits
        return logits, nsp_logits


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config: BertConfig, num_classes=2):
        super().__init__()
        self.bert = BertModel(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            return F.cross_entropy(logits, labels), logits
        return logits
