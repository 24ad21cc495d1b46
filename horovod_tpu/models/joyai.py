"""JoyAI-LLM-Flash: multi-head latent attention and a mixture of SwiGLU
experts chosen by bias-corrected sigmoid scores beside a shared one; TPU-first
flax.

The architecture of ``jdopensource/JoyAI-LLM-Flash`` (``model_type:
joyai_llm_flash``; DeepSeek-V3's keys one for one).  With ``x`` [S, d], RMSNorm
at eps 1e-6 with learnt scales, no bias anywhere, everything causal::

    block l:  h = x + Attn(RMSNorm(x));  y = h + FF_l(RMSNorm(h))
    after the last block the final RMSNorm and an untied head

**Attention** (every layer alike), a low-rank latent between the stream and
the heads::

    c_q = RMSNorm(x W_qa)                  [S, q_lora_rank]
    q   = c_q W_qb                         a head [q_nope 128 | q_rope 64]
    [c | r] = x W_kva                      [S, kv_lora_rank + 64]
    c_kv = RMSNorm(c)      k_rope = rope(r)     one rotary key for all heads
    [k_nope | v] = c_kv W_kvb              a head 128 + 128
    scores = (q_nope . k_nope + rope(q_rope) . k_rope) / sqrt(192)
    Attn = softmax(scores, causal) v W_o

The scores' two products are the two operands of ``ops/flash_attention.py``'s
``q_rope`` / ``k_rope`` call: ``k_rope`` stays its one [B, S, 64] array, v and
the output are 128 wide.  Rotary positions pair lanes ``(2i, 2i + 1)`` as
published (``rope_interleave``), ``inv_freq_i = theta^(-2i / 64)``, no scaling.

**Feed-forward**: layers below ``first_k_dense_replace`` a dense SwiGLU of
``intermediate_size`` columns; the others ``s = sigmoid(h W_r)`` over all
experts in float32, the **choice** the top-k of ``s + b`` (``b`` a balancing
bias: no gradient, no decay, a leaf of the ``balancing`` collection and no
parameter), the **weights** ``s`` without ``b`` at the chosen, divided by their
sum (``norm_topk_prob``); ``routed_scaling_factor x`` the weighted sum of the
chosen SwiGLU experts plus one shared expert that every token takes.

**Stored layouts.**  ``W_qb`` is two kernels, all heads' nope columns
``q_b_nope`` [q_lora_rank, heads x 128] apart from all rotary columns
``q_b_rope`` [q_lora_rank, heads x 64]; ``W_kvb`` a ``PairedDense``
[kv_lora_rank, 2 x heads x 128] (k_nope half, v half); ``W_kva`` [d,
kv_lora_rank + 64]; gate/up pairs [d, 2 x held].  The rotary columns of
``q_b_rope`` (a head's 64) and of ``W_kva`` are stored **even lanes then
odd**: stored lane ``i < 32`` is the published lane ``2i`` and stored lane ``32
+ i`` the published ``2i + 1``, so the half-split turn of
``models/sdar.py:rotary`` (lane i with lane i + 32) is the published
interleaved one and the scores are the same sum (:func:`stored_rope_order`).
Everything between the projections and the kernels stays ``[B, S, heads x
width]``: the turn rolls the lanes in place (:func:`rotary_lanes`).

Float32: parameters, the router's product (highest precision), sigmoid and
weights, RMSNorm arithmetic, rotary angles and the turn, softmax statistics,
logits and loss; ``dtype`` activations and matmul operands.

**A chip's share of a layer.**  ``num_heads_held`` heads (their columns of
``W_qb`` and ``W_kvb``, rows of ``W_o``), ``dense_columns_held`` columns of
the dense feed-forward, ``num_experts_held`` consecutive experts from
``first_expert`` on (the router, its bias and its k a token whole),
``vocab_size_held`` rows of the embedding and the head; ``W_qa``, ``W_kva``,
both latent norms, the layer norms, the residual stream, the router and the
shared expert are whole on every chip.  Every layer takes ``axis_name``:
``W_o`` and the dense ``down`` sum over it (their kernels drawn at the whole
layer's fan-in); the experts' exchange, and the head and the loss over a
vocabulary split across chips, are not built and raise by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.embedding import embed_lookup
from ..ops.flash_attention import dense_attention, flash_attention
from ..parallel.moe import dispatch_experts, expert_load
from ..parallel.tensor_parallel import vocab_parallel_embedding
from .jamba import PairedDense, RowParallel
from .laguna import EMBEDDING_STDDEV, mixture_sum
from .losses import head_cross_entropy
from .sdar import RMSNorm, _expert_init
from .zaya import balancing_bias


@dataclasses.dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    intermediate_size: int = 7168
    first_k_dense_replace: int = 1
    num_experts: int = 256              # n_routed_experts, the router's width
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # What this chip holds of each layer; None: the whole.
    vocab_size_held: Optional[int] = None
    num_heads_held: Optional[int] = None
    dense_columns_held: Optional[int] = None
    num_experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    @property
    def heads_held(self) -> int:
        return self.num_heads_held or self.num_heads

    @property
    def columns_held(self) -> int:
        return self.dense_columns_held or self.intermediate_size

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def rows_held(self) -> int:
        return self.vocab_size_held or self.vocab_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def sparse(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace


# The expert layer's row buffer over an even router's rows
# (``parallel/moe.py:row_buffer``; the configuration's
# ``assumed.expert_capacity_factor`` says how it was chosen).
EXPERT_CAPACITY_FACTOR = 2.0

# The published sizes (config.json of jdopensource/JoyAI-LLM-Flash), whole.
JOYAI_LLM_FLASH = JoyAIConfig()
JOYAI_TINY = JoyAIConfig(
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=96, num_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32, dtype=jnp.float32,
    use_flash=False)


def stored_rope_order(width: int) -> np.ndarray:
    """[width] int: the published lane that each stored lane of a rotary part
    holds, even lanes then odd (``stored[i] = published[order[i]]``)."""
    return np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])


def rotary_lanes(x, positions, theta: float, width: int):
    """Rotary positions on every ``width``-lane group of ``x`` [B, S, n x
    width] as it lies, in the half-split pairing of ``models/sdar.py:rotary``
    (lane i of a group with lane i + width / 2, ``theta^(-2i / width)``):
    float32 inside, ``x``'s dtype out.  A lane's partner comes by rolling all
    the lanes ``width / 2`` either way and taking, lane by lane, the roll that
    stayed inside the group, so no head is cut out of the array."""
    half, groups = width // 2, x.shape[-1] // width
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    cos = jnp.tile(jnp.concatenate([cos, cos], -1), (1, groups))
    sin = jnp.tile(jnp.concatenate([-sin, sin], -1), (1, groups))
    first = jnp.arange(x.shape[-1]) % width < half
    wide = x.astype(jnp.float32)
    partner = jnp.where(first, jnp.roll(wide, -half, -1),
                        jnp.roll(wide, half, -1))
    return (wide * cos + partner * sin).astype(x.dtype)


def _dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class JoyAIAttention(nn.Module):
    """Latent attention on ``h = RMSNorm(x)``, this chip's heads."""
    config: JoyAIConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        seq, heads = h.shape[1], cfg.heads_held
        nope, rope, wide = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim)
        if nope != wide:
            raise NotImplementedError(
                f"qk_nope_head_dim {nope} against v_head_dim {wide}: k_nope "
                "and v are the two halves of one paired kernel")

        def norm(name):
            return RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype, name=name)

        with jax.named_scope("hvd_attn_proj"):
            c_q = _dense(cfg.q_lora_rank, cfg.dtype, "q_a")(h)
            kv = _dense(cfg.kv_lora_rank + rope, cfg.dtype, "kv_a")(h)
        with jax.named_scope("hvd_mla_latent"):
            c_q = norm("q_a_norm")(c_q)
            c_kv = norm("kv_a_norm")(kv[..., :cfg.kv_lora_rank])
        with jax.named_scope("hvd_attn_proj"):
            q_nope = _dense(heads * nope, cfg.dtype, "q_b_nope")(c_q)
            q_rope = _dense(heads * rope, cfg.dtype, "q_b_rope")(c_q)
            k_nope, v = PairedDense(heads * nope, cfg.dtype,
                                    name="kv_b")(c_kv)
        with jax.named_scope("hvd_rope"):
            positions = jnp.arange(seq)
            q_rope = rotary_lanes(q_rope, positions, cfg.rope_theta, rope)
            k_rope = rotary_lanes(kv[..., cfg.kv_lora_rank:], positions,
                                  cfg.rope_theta, rope)
        # Every operand is [B, S, heads x width] from the projections on: the
        # heads are a view at the kernels' door, which read that layout.
        attend = flash_attention if cfg.use_flash else dense_attention
        by_head = lambda t, n: t.reshape(*t.shape[:2], n, -1)  # noqa: E731
        ctx = attend(by_head(q_nope, heads), by_head(k_nope, heads),
                     by_head(v, heads), causal=True,
                     scale=cfg.qk_head_dim ** -0.5,
                     q_rope=by_head(q_rope, heads), k_rope=by_head(k_rope, 1))
        ctx = ctx.reshape(*ctx.shape[:2], -1)
        # For whoever applies the layer with ``mutable=["intermediates"]``
        # (the benchmark holds the two latents and the kernels' output of
        # their own operands to the reference's through it); in a step
        # nothing is kept.
        self.sow("intermediates", "attention", {
            "c_q": c_q, "c_kv": c_kv, "q_nope": q_nope, "q_rope": q_rope,
            "k_nope": k_nope, "k_rope": k_rope, "v": v, "ctx": ctx})
        with jax.named_scope("hvd_attn_proj"):
            return RowParallel(
                cfg.hidden_size, cfg.num_heads * wide, self.axis_name,
                cfg.dtype, name="o_proj")(ctx)


class JoyAIMLP(nn.Module):
    """The dense SwiGLU of a leading layer, this chip's columns."""
    config: JoyAIConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        gate, up = PairedDense(cfg.columns_held, cfg.dtype,
                               name="gate_up")(h)
        return RowParallel(cfg.hidden_size, cfg.intermediate_size,
                           self.axis_name, cfg.dtype, name="down")(
                               jax.nn.silu(gate) * up)


class JoyAIRouter(nn.Module):
    """``(scores [T, experts], chosen [T, k], weights [T, k])`` of tokens
    ``x`` [T, d]: sigmoid scores in float32 (the product at "highest"
    precision: the choice is discrete), the top-k of ``scores + bias`` and the
    unbiased scores of the chosen, divided by their sum under
    ``norm_topk_prob``.  The bias is in the choice and not in the weights, and
    no parameter: a leaf of the ``balancing`` collection that nothing
    differentiates or decays, zeros until a caller that makes the collection
    mutable has it set from these tokens' loads
    (``models/zaya.py:balancing_bias``)."""
    config: JoyAIConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], cfg.num_experts))
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), kernel.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        bias = self.variable("balancing", "bias", jnp.zeros,
                             (cfg.num_experts,), jnp.float32)
        if (self.is_mutable_collection("balancing")
                and not self.is_initializing()):
            bias.value = balancing_bias(scores, cfg.num_experts_per_tok)
        chosen = lax.top_k(lax.stop_gradient(scores) + bias.value,
                           cfg.num_experts_per_tok)[1]
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return scores, chosen, weights


class JoyAIMoE(nn.Module):
    """``routed_scaling_factor x`` the held experts' part of the token's
    top-k sum (``parallel/moe.py:dispatch_experts`` on the router's own
    choices) plus the shared expert, whole on every chip.  The rows routed to
    each held expert and every token's chosen experts are sown under
    ``intermediates`` (``expert_load``, ``chosen_experts``)."""
    config: JoyAIConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        if self.axis_name is not None:
            raise NotImplementedError(
                f"JoyAIMoE over {self.axis_name!r}: the experts' exchange "
                "is not built (ROADMAP Reach B1); attention and the dense "
                "feed-forward sum over the axis")
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        tokens = h.reshape(-1, d)
        with jax.named_scope("hvd_moe_route"):
            _, chosen, weights = JoyAIRouter(cfg, name="router")(tokens)
        w_gate = self.param("w_gate", _expert_init, (held, d, f))
        w_up = self.param("w_up", _expert_init, (held, d, f))
        w_down = self.param("w_down", _expert_init, (held, f, d))
        y = dispatch_experts(
            tokens, chosen, weights, w_gate, w_up, w_down,
            first_expert=cfg.first_expert, experts_total=cfg.num_experts,
            capacity_factor=EXPERT_CAPACITY_FACTOR)
        self.sow("intermediates", "expert_load",
                 expert_load(chosen, cfg.first_expert, held))
        self.sow("intermediates", "chosen_experts", chosen)
        with jax.named_scope("hvd_moe_shared"):
            gate, up = PairedDense(
                cfg.n_shared_experts * cfg.moe_intermediate_size, cfg.dtype,
                name="shared_gate_up")(h)
            shared = _dense(d, cfg.dtype, "shared_down")(
                jax.nn.silu(gate) * up)
            return mixture_sum(y.reshape(h.shape), shared,
                               cfg.routed_scaling_factor)


class JoyAIBlock(nn.Module):
    config: JoyAIConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype,  # noqa: E731
                                    name=name)

        def add(x, y):
            return (x.astype(jnp.float32)
                    + y.astype(jnp.float32)).astype(cfg.dtype)

        with jax.named_scope("hvd_block"):
            h = norm("input_norm")(x)
            with jax.named_scope("hvd_attn"):
                y = JoyAIAttention(cfg, self.axis_name, name="attn")(h)
            x = add(x, y)
            h = norm("post_attn_norm")(x)
            if cfg.sparse(self.layer):
                y = JoyAIMoE(cfg, self.axis_name, name="moe")(h)
            else:
                with jax.named_scope("hvd_mlp"):
                    y = JoyAIMLP(cfg, self.axis_name, name="mlp")(h)
            return add(x, y)


class JoyAI(nn.Module):
    """``JoyAI(cfg)(ids)``: float32 logits [B, S, rows held].
    ``method="hidden"``: what the head reads, [B, S, d] after the final norm;
    ``method="head"``: the logits of some of its rows; ``method="loss"``: the
    next-token cross-entropy over every position but the last.  With
    ``axis_name`` attention and the dense feed-forward sum over that mesh
    axis and the embedding is looked up across it; the experts' exchange and
    the head and the loss over a vocabulary split across chips are not
    built."""

    config: JoyAIConfig
    axis_name: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.rows_held, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        self.layers = [JoyAIBlock(cfg, i, self.axis_name, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype)
        self.lm_head = self.param(
            "lm_head", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.rows_held))

    def hidden(self, ids):
        with jax.named_scope("hvd_embed"):
            if self.axis_name is None:
                x = embed_lookup(self.embed.embedding, ids,
                                 self.config.dtype)
            else:
                x = vocab_parallel_embedding(
                    ids, self.embed.embedding.astype(self.config.dtype),
                    self.axis_name)
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope("hvd_lm_head"):
            return self.final_norm(x)

    def head(self, x):
        """Float32 logits of rows ``x`` [..., d] of :meth:`hidden`: the
        product takes ``x``'s dtype and accumulates in float32."""
        self._one_chip_s_rows("head")
        with jax.named_scope("hvd_lm_head"):
            return jnp.dot(x, self.lm_head.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    def __call__(self, ids):
        return self.head(self.hidden(ids))

    def loss(self, ids):
        """Mean over the ``B x (S - 1)`` predicting positions of the next
        token's negative log-likelihood over the rows held, through the
        blocked head (``losses.head_cross_entropy``: :meth:`head`'s logits and
        their row statistics from one kernel on a TPU, ``d logits`` made on
        the way into the two backward products; no ``[B x S, V]`` array but
        the float32 logits themselves)."""
        self._one_chip_s_rows("loss")
        x = self.hidden(ids)
        batch, seq = ids.shape
        with jax.named_scope("hvd_lm_head"):
            predicts = jnp.arange(seq) < seq - 1
            weights = jnp.broadcast_to(predicts / (batch * (seq - 1.0)),
                                       ids.shape)
            return head_cross_entropy(
                x.reshape(batch * seq, -1), self.lm_head,
                jnp.roll(ids, -1, axis=1).reshape(-1),
                weights.reshape(-1).astype(jnp.float32))

    def _one_chip_s_rows(self, what: str) -> None:
        if self.axis_name is not None:
            raise NotImplementedError(
                f"JoyAI.{what} over a vocabulary split across "
                f"{self.axis_name!r}: the softmax's exchange is not built "
                "(ROADMAP Reach B9); attention and the dense feed-forward "
                "(method='hidden' of a model without expert layers) sum over "
                "the axis")


def lm_loss(model: JoyAI, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S]."""
    return model.apply(variables, ids, method="loss")
