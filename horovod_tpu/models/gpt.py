"""Decoder-only (GPT-style) causal language model, TPU-first flax.

Extends the model-family coverage beyond the reference's benchmark pair
(ResNet/BERT — SURVEY.md §6) with the decoder architecture the long-context
requirement targets: causal attention runs through the Pallas flash kernel
on-chip, or ring attention over a sequence-parallel mesh axis
(``sp_axis_name``) for sequences longer than one chip's memory.
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
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a multiple of 128
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    sp_axis_name: Optional[str] = None   # sequence-parallel mesh axis
    sp_use_flash: bool = False           # flash kernel per ring hop
    use_flash: bool = True               # Pallas kernel on TPU
    remat: bool = False                  # jax.checkpoint each block


GPT_SMALL = GPTConfig()
GPT_TINY = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=4, max_seq_len=256, use_flash=False,
                     dtype=jnp.float32)


class CausalSelfAttention(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_heads
        with jax.named_scope("hvd_attn_proj"):
            qkv = FlatDenseGeneral((3, cfg.num_heads, head_dim),
                                   dtype=cfg.dtype,
                                   name="qkv")(x)       # [B, S, 3 * H * D]
        q, k, v = (part.reshape(*x.shape[:-1], cfg.num_heads, head_dim)
                   for part in jnp.split(qkv, 3, axis=-1))
        # The kernels stay outside the products' scope: a kernel call's HLO
        # instruction is named for its innermost scope, this module's.
        if cfg.sp_axis_name is not None:
            from ..parallel.ring_attention import ring_attention

            ctx = ring_attention(q, k, v, axis_name=cfg.sp_axis_name,
                                 causal=True,
                                 use_flash=cfg.sp_use_flash)
        elif cfg.use_flash:
            from ..ops.flash_attention import flash_attention

            ctx = flash_attention(q, k, v, causal=True)
        else:
            from ..ops.flash_attention import dense_attention

            ctx = dense_attention(q, k, v, causal=True)
        with jax.named_scope("hvd_attn_proj"):
            return FlatDenseGeneral(cfg.hidden_size, axis=(-2, -1),
                                    dtype=cfg.dtype, name="out")(ctx)


class GPTBlock(nn.Module):
    config: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        with jax.named_scope("hvd_block"):
            # Pre-LN (GPT-2 style); LN in fp32.
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(
                x.astype(jnp.float32)).astype(cfg.dtype)
            with jax.named_scope("hvd_attn"):
                a = CausalSelfAttention(cfg, name="attn")(h, deterministic)
            x = x + nn.Dropout(cfg.dropout_rate)(
                a, deterministic=deterministic)
            h = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(
                x.astype(jnp.float32)).astype(cfg.dtype)
            with jax.named_scope("hvd_mlp"):
                m = nn.Dense(4 * cfg.hidden_size, dtype=cfg.dtype,
                             name="mlp_in")(h)
                m = nn.gelu(m)
                m = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                             name="mlp_out")(m)
            return x + nn.Dropout(cfg.dropout_rate)(
                m, deterministic=deterministic)


class GPT(nn.Module):
    """Causal LM: returns next-token logits [B, S, V] (fp32)."""

    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        cfg = self.config
        seq_len = input_ids.shape[-1]
        with jax.named_scope("hvd_embed"):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                         name="wte")(input_ids)
            if cfg.sp_axis_name is not None:
                offset = jax.lax.axis_index(cfg.sp_axis_name) * seq_len
            else:
                offset = 0
            pos = (offset + jnp.arange(seq_len))[None, :]
            x = x + nn.Embed(cfg.max_seq_len, cfg.hidden_size,
                             dtype=cfg.dtype, name="wpe")(pos)
        block = GPTBlock
        if cfg.remat:
            block = nn.remat(GPTBlock, static_argnums=(2,))
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"h_{i}")(x, deterministic)
        with jax.named_scope("hvd_lm_head"):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(
                x.astype(jnp.float32))
            return nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=jnp.float32, name="lm_head")(x)


def lm_loss(logits, input_ids):
    """Next-token cross entropy (shifted), mean over positions."""
    with jax.named_scope("hvd_lm_head"):
        return softmax_cross_entropy(logits[:, :-1],
                                     input_ids[:, 1:]).mean()
