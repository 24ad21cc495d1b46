"""BERT encoder, TPU-first flax implementation.

The reference's headline large-model benchmark is BERT-Large pretraining
with fp16 fused allreduce (BASELINE.json config 3; Horovod `examples/` has
the TF/torch BERT scripts).  This is the equivalent model for this
framework, shaped for the MXU:

- all projections are single fused matmuls over [hidden, 3*hidden]-style
  shapes (multiples of 128);
- bfloat16 activations, fp32 params, fp32 softmax accumulation;
- attention runs through the Pallas flash kernels (``use_flash``, on by
  default as in ``GPTConfig``; dense off-TPU) when padding is given as
  ``lengths``, one key length per sequence (BERT's tail padding), or not at
  all; an ``attention_mask`` alone may have any shape of holes and takes
  the dense path, as it always did;
- the pretraining head is the published one (Devlin et al., arXiv:1810.04805
  and ``run_pretraining.py``): the masked positions are gathered *before*
  the transform, the decoder is the word-embedding matrix transposed plus a
  bias, and a ``tanh`` pooler over position 0 feeds a 2-way next-sentence
  classifier.  The vocabulary is padded to a multiple of 128 rows; the
  padded logits are masked out of the softmax;
- attention can run sequence-parallel over a mesh axis via
  ``horovod_tpu.parallel.ring_attention`` (pass ``sp_axis_name``) — the
  long-context path the reference lacks (SURVEY.md §5 "long-context").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .flat_dense import FlatDenseGeneral
from .losses import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024          # BERT-Large
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    sp_axis_name: Optional[str] = None  # sequence-parallel mesh axis
    sp_use_flash: bool = False          # flash kernel per ring hop
    use_flash: bool = True              # Pallas kernel on TPU

    @property
    def padded_vocab_size(self) -> int:
        """Rows of the word-embedding matrix (= width of the logits): the
        vocabulary rounded up to whole 128-lane tiles."""
        return -(-self.vocab_size // 128) * 128


BERT_BASE = BertConfig(hidden_size=768, num_layers=12, num_heads=12,
                       intermediate_size=3072)
BERT_LARGE = BertConfig()
BERT_TINY = BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                       num_heads=2, intermediate_size=512,
                       max_position_embeddings=128)


class SelfAttention(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True,
                 lengths=None):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        # One fused QKV projection: [B, S, H] @ [H, 3H] keeps the MXU at a
        # single large matmul instead of three small ones.
        with jax.named_scope("hvd_attn_proj"):
            qkv = FlatDenseGeneral((3, cfg.num_heads, head_dim),
                                   dtype=cfg.dtype,
                                   name="qkv")(x)       # [B, S, 3 * H * D]
        # The kernels stay outside the products' scope: a kernel call's HLO
        # instruction is named for its innermost scope, this module's.
        q, k, v = (part.reshape(*x.shape[:-1], cfg.num_heads, head_dim)
                   for part in jnp.split(qkv, 3, axis=-1))
        if cfg.sp_axis_name is not None:
            from ..parallel.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, axis_name=cfg.sp_axis_name,
                                 causal=False,
                                 use_flash=cfg.sp_use_flash)
        elif cfg.use_flash and (lengths is not None or mask is None):
            from ..ops.flash_attention import flash_attention

            # The kernels take tail padding only, as a length per sequence;
            # a mask alone goes to the dense path, which honours any mask.
            ctx = flash_attention(q, k, v, causal=False, kv_lens=lengths)
        else:
            if mask is None and lengths is not None:
                mask = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
            scale = head_dim ** -0.5
            # fp32 logits/softmax regardless of activation dtype.
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) * scale
            if mask is not None:
                big_neg = jnp.finfo(jnp.float32).min
                logits = jnp.where(mask[:, None, None, :], logits, big_neg)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        with jax.named_scope("hvd_attn_proj"):
            return FlatDenseGeneral(cfg.hidden_size, axis=(-2, -1),
                                    dtype=cfg.dtype, name="out")(ctx)


class TransformerLayer(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True,
                 lengths=None):
        cfg = self.config
        with jax.named_scope("hvd_block"):
            with jax.named_scope("hvd_attn"):
                attn = SelfAttention(cfg, name="attention")(
                    x, mask, deterministic, lengths)
            attn = nn.Dropout(cfg.dropout_rate)(attn,
                                                deterministic=deterministic)
            # Post-LN like original BERT; LN in fp32 for stability.
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(
                (x + attn).astype(jnp.float32)).astype(cfg.dtype)
            with jax.named_scope("hvd_mlp"):
                h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                             name="mlp_in")(x)
                h = nn.gelu(h)
                h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                             name="mlp_out")(h)
            h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
            return nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(
                (x + h).astype(jnp.float32)).astype(cfg.dtype)


class BertEncoder(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, lengths=None):
        """``lengths`` (int32 [B]: the real tokens of a sequence are its
        first ``lengths[b]``) or ``attention_mask`` (bool [B, S], any
        pattern) keep padded keys out of every softmax.  The flash kernels
        take ``lengths`` only (rows beyond a length come out zero); with a
        mask and no ``lengths`` attention is dense and the mask is honoured
        key by key.  Where both are given they must mean the same: the
        kernels read ``lengths`` and the dense path the mask."""
        cfg = self.config
        seq_len = input_ids.shape[-1]
        with jax.named_scope("hvd_embed"):
            x = nn.Embed(cfg.padded_vocab_size, cfg.hidden_size,
                         dtype=cfg.dtype, name="word_embeddings")(input_ids)
            if cfg.sp_axis_name is not None:
                # Sequence-parallel: this shard holds a contiguous chunk of
                # the global sequence; position ids are global.
                offset = jax.lax.axis_index(cfg.sp_axis_name) * seq_len
            else:
                offset = 0
            pos = (offset + jnp.arange(seq_len))[None, :]
            x = x + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                             dtype=cfg.dtype, name="position_embeddings")(pos)
            if token_type_ids is not None:
                x = x + nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                                 dtype=cfg.dtype,
                                 name="token_type_embeddings")(token_type_ids)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_embed")(
                x.astype(jnp.float32)).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = TransformerLayer(cfg, name=f"layer_{i}")(
                x, attention_mask, deterministic, lengths)
        return x


class BertForPreTraining(nn.Module):
    """Encoder + the published pretraining heads (masked LM with the tied
    decoder, next-sentence prediction).

    With ``masked_positions`` (int32 [B, P]) the call returns
    ``(mlm_logits [B, P, V], nsp_logits [B, 2])``: the hidden states at
    those positions are gathered before the transform, so the vocabulary
    matmul runs over P rows a sequence and not S.  Without it the call
    returns the masked-LM logits at every position, ``[B, S, V]``, as it
    always did.  V is ``config.padded_vocab_size``; the logits of the
    padding rows are -1e30.  Logits are float32."""

    config: BertConfig

    def setup(self):
        # Attributes, not ``nn.compact``, so that ``decode`` can be applied
        # on its own (``method="decode"``); the names are the parameters'.
        cfg = self.config
        self.encoder = BertEncoder(cfg)
        self.mlm_transform = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.mlm_ln = nn.LayerNorm(dtype=jnp.float32)
        self.mlm_bias = self.param("mlm_bias", nn.initializers.zeros,
                                   (cfg.padded_vocab_size,), jnp.float32)
        self.pooler = nn.Dense(cfg.hidden_size, dtype=cfg.dtype)
        self.nsp_head = nn.Dense(2, dtype=jnp.float32)

    def decode(self, h):
        """The float32 end of the masked-LM head: layer norm of the
        transformed hidden states ``h`` [..., H], then the decoder, which is
        the word-embedding matrix itself (its gradient is the sum of the
        lookup's and this matmul's) plus a bias."""
        cfg = self.config
        h = self.mlm_ln(h.astype(jnp.float32))
        embedding = self.encoder.variables["params"]["word_embeddings"][
            "embedding"]
        logits = jnp.einsum("...h,vh->...v", h, embedding,
                            preferred_element_type=jnp.float32) + self.mlm_bias
        if cfg.padded_vocab_size != cfg.vocab_size:
            real = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
            logits = jnp.where(real, logits, -1e30)
        return logits

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True, lengths=None,
                 masked_positions=None):
        hidden = self.encoder(input_ids, token_type_ids, attention_mask,
                              deterministic, lengths)
        with jax.named_scope("hvd_mlm_head"):
            h = hidden
            if masked_positions is not None:
                h = jnp.take_along_axis(
                    hidden, masked_positions[..., None], axis=1)
            logits = self.decode(nn.gelu(self.mlm_transform(h)))
        with jax.named_scope("hvd_nsp_head"):
            pooled = jnp.tanh(self.pooler(hidden[:, 0]))
            nsp_logits = self.nsp_head(pooled.astype(jnp.float32))
        if masked_positions is None:
            return logits
        return logits, nsp_logits


def mlm_loss(logits, labels, label_weights):
    """Masked-LM cross-entropy: mean over positions where weight == 1."""
    w = label_weights.astype(jnp.float32)
    nll = softmax_cross_entropy(logits, labels)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1.0)


def nsp_loss(nsp_logits, nsp_labels):
    """Next-sentence cross-entropy, mean over the batch."""
    return softmax_cross_entropy(nsp_logits, nsp_labels).mean()


def pretraining_loss(mlm_logits, nsp_logits, mlm_labels, mlm_weights,
                     nsp_labels):
    """The paper's objective: the masked-LM mean over the weighted
    positions plus the next-sentence mean."""
    with jax.named_scope("hvd_mlm_head"):
        masked_lm = mlm_loss(mlm_logits, mlm_labels, mlm_weights)
    with jax.named_scope("hvd_nsp_head"):
        next_sentence = nsp_loss(nsp_logits, nsp_labels)
    with jax.named_scope("hvd_mlm_head"):
        return masked_lm + next_sentence
