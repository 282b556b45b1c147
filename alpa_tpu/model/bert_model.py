"""BERT-style bidirectional encoder with MLM / NSP / classification heads.

Analog of ref ``alpa/model/bert_model.py`` (884 LoC flax BERT incl.
``FlaxBertForPreTrainingModule``).  Reuses the shared transformer blocks
(gpt_model) with ``causal=False`` — the reference inverts this
relationship (its GPT wraps BERT with a causal mask, ref gpt_model.py:151);
either way one block implementation serves both.

Coverage vs the reference heads:

* ``BertModel`` — trunk: word/position/segment embeddings + encoder +
  pooler (ref FlaxBertModule:557), with attention-mask support
  (padding masks threaded as an additive fp32 score bias).
* ``BertForPreTraining`` — MLM + NSP heads over one trunk, decoder
  optionally tied to the word-embedding table
  (ref FlaxBertForPreTrainingModule:609, FlaxBertPreTrainingHeads:541,
  tied decoder FlaxBertLMPredictionHead:486).
* ``BertForMaskedLM`` (ref :665), ``BertForSequenceClassification``
  (ref :718).
* ``bert_pretraining_loss`` — masked-LM + NSP loss with label weights.
"""
import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from alpa_tpu.model.gpt_model import GPTConfig, TransformerBlock


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    seq_len: int = 512
    type_vocab_size: int = 2
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    layer_norm_eps: float = 1e-12   # HF BERT default
    tie_word_embeddings: bool = True

    def gpt(self) -> GPTConfig:
        return GPTConfig(vocab_size=self.vocab_size,
                         hidden_size=self.hidden_size,
                         num_layers=self.num_layers,
                         num_heads=self.num_heads,
                         seq_len=self.seq_len,
                         mlp_ratio=self.mlp_ratio,
                         dtype=self.dtype,
                         layer_norm_eps=self.layer_norm_eps,
                         causal=False)


def attention_mask_to_bias(attention_mask) -> jnp.ndarray:
    """(B, S) 1/0 padding mask -> (B, 1, 1, S) additive fp32 score bias
    (ref FlaxBertSelfAttention mask handling, bert_model.py:142)."""
    bias = jnp.where(attention_mask > 0, 0.0, -1e9)
    return bias[:, None, None, :].astype(jnp.float32)


class BertModel(nn.Module):
    """Encoder trunk: token + position + segment embeddings, N blocks,
    optional tanh pooler over [CLS] (ref FlaxBertModule:557)."""
    config: BertConfig
    add_pooling_layer: bool = True

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        cfg = self.config
        gcfg = cfg.gpt()
        b, s = input_ids.shape
        pos = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        tok_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                           name="word_embeddings")
        x = tok_emb(input_ids)
        x = x + nn.Embed(cfg.seq_len, cfg.hidden_size, dtype=cfg.dtype,
                         name="position_embeddings")(pos)
        x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype,
                         name="token_type_embeddings")(token_type_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="embeddings_ln")(x).astype(cfg.dtype)
        bias = (attention_mask_to_bias(attention_mask)
                if attention_mask is not None else None)
        for i in range(cfg.num_layers):
            x, _ = TransformerBlock(gcfg, name=f"layer_{i}")(
                x, None, True, padding_bias=bias)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="final_ln")(x).astype(cfg.dtype)
        pooled = None
        if self.add_pooling_layer:
            pooled = nn.tanh(
                nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         name="pooler")(x[:, 0]))
        return x, pooled, tok_emb


class BertLMPredictionHead(nn.Module):
    """transform -> gelu -> LN -> decoder(+bias); decoder weights tied to
    the word-embedding table when configured
    (ref FlaxBertLMPredictionHead:486)."""
    config: BertConfig

    @nn.compact
    def __call__(self, hidden, tok_emb):
        cfg = self.config
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="transform")(
            hidden)
        x = nn.gelu(x, approximate=True)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="transform_ln")(x).astype(cfg.dtype)
        if cfg.tie_word_embeddings and tok_emb is not None:
            logits = tok_emb.attend(x)
        else:
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              use_bias=False, name="decoder")(x)
        bias = self.param("decoder_bias", nn.initializers.zeros,
                          (cfg.vocab_size,), cfg.dtype)
        return logits + bias


class BertForPreTraining(nn.Module):
    """MLM + NSP pretraining heads over one trunk
    (ref FlaxBertForPreTrainingModule:609)."""
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        cfg = self.config
        x, pooled, tok_emb = BertModel(cfg, add_pooling_layer=True,
                                       name="bert")(input_ids,
                                                    attention_mask,
                                                    token_type_ids)
        mlm_logits = BertLMPredictionHead(cfg, name="mlm_head")(x, tok_emb)
        nsp_logits = nn.Dense(2, dtype=cfg.dtype,
                              name="nsp_head")(pooled)
        return mlm_logits, nsp_logits


class BertForMaskedLM(nn.Module):
    """MLM head over the trunk (ref FlaxBertForMaskedLMModule:665)."""
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        cfg = self.config
        x, _, tok_emb = BertModel(cfg, add_pooling_layer=False,
                                  name="bert")(input_ids, attention_mask,
                                               token_type_ids)
        return BertLMPredictionHead(cfg, name="mlm_head")(x, tok_emb)


class BertForSequenceClassification(nn.Module):
    """(ref FlaxBertForSequenceClassificationModule:718)"""
    config: BertConfig
    num_labels: int = 2

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None):
        _, pooled, _ = BertModel(self.config, name="bert")(
            input_ids, attention_mask, token_type_ids)
        return nn.Dense(self.num_labels, dtype=self.config.dtype,
                        name="classifier")(pooled)


def bert_pretraining_loss(mlm_logits, nsp_logits, mlm_labels,
                          mlm_weights, nsp_labels):
    """Masked-LM (weighted over masked positions) + NSP cross-entropy,
    fp32 accumulation (the loss the reference's pretraining benchmark
    computes around FlaxBertForPreTrainingModule)."""
    logp = jax.nn.log_softmax(mlm_logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, mlm_labels[..., None], axis=-1)[..., 0]
    w = mlm_weights.astype(jnp.float32)
    mlm_loss = -(ll * w).sum() / jnp.maximum(w.sum(), 1.0)
    nsp_logp = jax.nn.log_softmax(nsp_logits.astype(jnp.float32), axis=-1)
    nsp_ll = jnp.take_along_axis(nsp_logp, nsp_labels[:, None],
                                 axis=-1)[:, 0]
    return mlm_loss - nsp_ll.mean()
