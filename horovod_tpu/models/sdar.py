"""SDAR-MoE: a Qwen3-MoE decoder trained by block diffusion, TPU-first flax.

The architecture of ``JetLM/SDAR-30B-A3B-Chat`` (``model_type: sdar_moe``;
SDAR, arXiv:2510.06303): pre-RMSNorm decoder blocks of grouped-query
attention (a per-head RMSNorm on q and k, rotary positions in Qwen's
half-split form) and a mixture of SwiGLU experts (softmax router over all
experts, top-k, weights renormalised over the chosen k, no shared expert),
an untied head.  Training follows BD3-LM (arXiv:2503.09573), which SDAR
adopts: a sequence ``x0`` of L tokens is cut into blocks, each block draws a
noise level ``t`` and each of its tokens is replaced by the mask token with
probability ``t``; the model sees ``[x0 ; xt]``, 2L positions, both copies at
rotary positions 0 .. L-1, under the block-diffusion mask
(``ops/flash_attention.py:block_diffusion_mask``), and the loss is the masked
tokens' cross-entropy weighted ``1 / t`` on the noised half.

A chip may hold a share of the model: ``num_experts_held`` consecutive
experts from ``first_expert`` on (``parallel/moe.py:routed_experts`` computes
their part of each token's sum; the router stays whole) and ``vocab_size``
rows of the embedding and of the head (a slice of the published vocabulary,
its last row the mask token's).  Attention runs through the Pallas flash
kernels on-chip (``use_flash``), the dense oracle elsewhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.embedding import embed_lookup
from ..ops.flash_attention import dense_attention, flash_attention
from ..ops.qk_norm_rope import dense_qk_norm_rope, qk_norm_rope
from ..parallel.moe import routed_experts
from .losses import softmax_cross_entropy


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    vocab_size: int = 151936         # rows of the embedding and the head held
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128           # the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    block_length: int = 4
    num_experts_held: Optional[int] = None   # None: every expert
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    @property
    def mask_token_id(self) -> int:
        """The last row held: data ids are drawn below it."""
        return self.vocab_size - 1

    @property
    def experts_held(self) -> int:
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)


# The expert layer's row buffer over an even router's rows
# (``parallel/moe.py:row_buffer``).  A quarter of the positions are the mask
# token, alike until attention tells them apart, and go to the same experts
# together: 3/4 + j/4 of the even rows where j of the mask token's 8 experts
# are among 16 of 128 held.  2.25 is j = 6, which one layer in 12,000 draws
# at initialisation; a step that routes more drops nothing and costs more.
# What the buffer's size costs a step whose rows fit it: its products, its
# gather and its sum back into the tokens follow the rows routed
# (``parallel/moe.py:rows_walked``); only what is paid by the byte (the zeros
# the buffer starts from, the weighting, the d rows' sum) is paid by its rows.
EXPERT_CAPACITY_FACTOR = 2.25

# The published sizes (config.json of JetLM/SDAR-30B-A3B-Chat), whole.
SDAR_30B_A3B = SDARConfig()
SDAR_TINY = SDARConfig(vocab_size=512, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, head_dim=16,
                       num_experts=8, num_experts_per_tok=2,
                       moe_intermediate_size=32, dtype=jnp.float32,
                       use_flash=False)


class RMSNorm(nn.Module):
    """``then(x / sqrt(mean(x^2) + eps) * scale)`` over the last axis, the
    arithmetic in float32, the result in ``dtype``.  Under ``jax.checkpoint``:
    the backward keeps the input as it came (bfloat16 in the blocks) and not
    its float32 copy, which at 16,384 positions is a quarter of a gigabyte a
    norm; what it recomputes is a few elementwise passes."""
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, then=None):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))

        def norm(x, scale):
            x = x.astype(jnp.float32)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + self.eps) * scale
            return (x if then is None else then(x)).astype(self.dtype)

        return jax.checkpoint(norm)(x, scale)


def rotary(x, positions, theta: float, width: Optional[int] = None):
    """Rotary position embedding in Qwen's half-split form: the pairs are
    (i, i + D/2).  x [..., S, H, D], positions [S]; float32 in and out.
    ``width``: only the first ``width`` of a head's D are rotated (the pairs
    lie within them), the rest pass as they are (a partial rotary factor);
    None rotates the whole head."""
    if width is not None and width != x.shape[-1]:
        return jnp.concatenate([
            rotary(x[..., :width], positions, theta), x[..., width:]], -1)
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]   # [S, D/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class HeadNormRope(nn.Module):
    """Each head of a flat projection [B, S, heads * head_dim] under one
    RMSNorm (``scale`` [head_dim], as :class:`RMSNorm` holds it) and then
    turned by its position, as one op on that layout
    (``ops/qk_norm_rope.py``: one pass forward and one backward where
    ``RMSNorm(...)(x, rope)`` on [B, S, heads, head_dim] compiles to a dozen,
    two of them relayouts and two in float32)."""
    config: SDARConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones, (cfg.head_dim,))
        op = qk_norm_rope if cfg.use_flash else dense_qk_norm_rope
        return op(x, scale, positions, heads=x.shape[-1] // cfg.head_dim,
                  head_dim=cfg.head_dim, eps=cfg.rms_norm_eps,
                  theta=cfg.rope_theta)


class SDARAttention(nn.Module):
    config: SDARConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        length = x.shape[1] // 2
        lead, d = x.shape[:-1], cfg.head_dim

        def proj(name, heads):
            return nn.Dense(heads * d, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        with jax.named_scope("hvd_attn_proj"):
            q, k = (proj("q_proj", cfg.num_heads),
                    proj("k_proj", cfg.num_kv_heads))
            v = proj("v_proj", cfg.num_kv_heads)
        # The clean and the noised copy carry the same positions.
        positions = jnp.tile(jnp.arange(length), 2)
        q = HeadNormRope(cfg, name="q_norm")(q, positions)
        k = HeadNormRope(cfg, name="k_norm")(k, positions)
        # q, k and v are [B, S, heads * d] from the projections on: the heads
        # are a view at the kernels' door, which read that layout.
        attend = flash_attention if cfg.use_flash else dense_attention
        ctx = attend(*(t.reshape(*lead, -1, d) for t in (q, k, v)),
                     block_diffusion=(length, cfg.block_length))
        with jax.named_scope("hvd_attn_proj"):
            return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                            name="o_proj")(ctx.reshape(*lead, -1))


def _expert_init(key, shape, dtype=jnp.float32):
    """lecun-normal, an expert at a time: [experts, fan_in, fan_out]."""
    return nn.initializers.variance_scaling(
        1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=(0,))(
            key, shape, dtype)


class SDARExperts(nn.Module):
    """The held experts' part of the layer (``parallel/moe.py``).  The rows
    routed to each held expert and every token's chosen experts are sown
    under ``intermediates`` (``expert_load``, ``chosen_experts``) for
    whoever asks for them."""
    config: SDARConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, cfg.num_experts))
        w_gate = self.param("w_gate", _expert_init, (held, d, f))
        w_up = self.param("w_up", _expert_init, (held, d, f))
        w_down = self.param("w_down", _expert_init, (held, f, d))
        y, routing = routed_experts(
            x.reshape(-1, d), router, w_gate, w_up, w_down,
            top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
            renormalize=cfg.norm_topk_prob,
            capacity_factor=EXPERT_CAPACITY_FACTOR)
        self.sow("intermediates", "expert_load", routing.load)
        self.sow("intermediates", "chosen_experts", routing.experts)
        return y.reshape(x.shape)


class SDARBlock(nn.Module):
    config: SDARConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype,  # noqa: E731
                                    name=name)
        with jax.named_scope("hvd_block"):
            h = norm("input_norm")(x)
            with jax.named_scope("hvd_attn"):
                a = SDARAttention(cfg, name="attn")(h)
            x = x + a
            return x + SDARExperts(cfg, name="moe")(norm("post_attn_norm")(x))


class SDAR(nn.Module):
    """``logits = SDAR(cfg)(clean_ids, noised_ids)``: float32 logits
    [B, L, vocab_size] of the **noised** copy's positions, the only ones the
    block-diffusion loss reads."""

    config: SDARConfig

    @nn.compact
    def __call__(self, clean_ids, noised_ids):
        cfg = self.config
        length = clean_ids.shape[-1]
        # Unit-variance embeddings (torch's default): a token's own identity
        # is then the largest part of its hidden state at initialisation, as
        # it is in a trained model.  With rows of unit norm an untrained
        # attention's near-uniform averages make all positions alike and the
        # routers send them to the same few experts.
        with jax.named_scope("hvd_embed"):
            ids = jnp.concatenate([clean_ids, noised_ids], -1)   # [B, 2L]
            embed = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                             embedding_init=nn.initializers.normal(stddev=1.0),
                             name="embed")
            x = embed_lookup(embed.embedding, ids, cfg.dtype)
        for i in range(cfg.num_layers):
            x = SDARBlock(cfg, name=f"layer_{i}")(x)
        with jax.named_scope("hvd_lm_head"):
            x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x[:, length:])
            return nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                            name="lm_head")(x)


def noise_blocks(ids, levels, draws, block_length: int, mask_token_id: int):
    """BD3-LM's forward process on ``ids`` [B, L]: block ``b`` has the level
    ``levels[:, b]`` in (0, 1] and a token of it is replaced by the mask
    token where its uniform ``draws`` [B, L] lies below that level.  Returns
    ``(noised_ids, masked [B, L] bool, level of each token [B, L])``."""
    t = jnp.repeat(levels, block_length, axis=-1)
    masked = draws < t
    return jnp.where(masked, mask_token_id, ids), masked, t


def block_diffusion_loss(logits, clean_ids, masked, levels):
    """Sum over the masked positions of the cross-entropy of the clean
    token, weighted ``1 / t`` of its block (the linear schedule's weight),
    over all ``B x L`` positions.  ``levels`` [B, L] as ``noise_blocks``
    returns them."""
    with jax.named_scope("hvd_lm_head"):
        nll = softmax_cross_entropy(logits, clean_ids)
        return jnp.sum(jnp.where(masked, nll / levels, 0.0)) / masked.size
