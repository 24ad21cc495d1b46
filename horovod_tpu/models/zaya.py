"""ZAYA1: compressed convolutional attention and a top-1 mixture of experts
whose router is a small MLP with a state carried down the layers; TPU-first
flax.

The architecture of ``Zyphra/ZAYA1-8B`` (``model_type: zaya``; the model's
report is arXiv:2511.17127, compressed convolutional attention
arXiv:2510.04476).  With ``x`` [S, d] and RMSNorm at eps 1e-5, all of it
causal:

**Block**, three streams handed on, the residual ``r``, the previous block's
expert output ``y`` and the router's state ``s``; learnt scale and bias on
both arms of every residual sum (``a, b`` start at ones, ``c, e`` at zeros,
which is the plain pre-norm residual)::

    r1 = a1 * (r0 + c1) + b1 * (y0 + e1)        none in block 0: r1 = r0
    y1 = CCA(RMSNorm(r1))
    r2 = a2 * (r1 + c2) + b2 * (y1 + e2)
    y2, s' = MoE(RMSNorm(r2), s)
    hand on (r2, y2, s')

and after the last block one more scaled sum with leaves of its own, the
final RMSNorm and the head, which is the embedding transposed.

**CCA**, in the latent of ``heads x head_dim`` query and ``kv_heads x
head_dim`` key channels::

    q0 = x Wq    k0 = x Wk                                       no bias
    u  = conv1(conv0([q0 | k0]))     causal (left-padded), no activation
         conv0: depthwise over the sequence, kernel cca_time0
         conv1: over the sequence, kernel cca_time1, grouped by head
    q  = u[:, :q] + (q0 + k0 of the head's group) / 2            "q-k mean"
    k  = u[:, q:] + (mean of q0 over the group's heads + k0) / 2
    v  = [ x_t Wv1 | x_(t-1) Wv2 ]   key/value head 0 is the token's, head 1
                                     the token before's (x_(-1) = 0)
    q  = sqrt(d) q / |q|     k = temp_g sqrt(d) k / |k|          float32
    rotary on the first ``partial_rotary_factor`` of each head
    o  = softmax(q k^T / sqrt(d), causal) v                      grouped-query
    y  = o Wo

**MoE**, router over all experts whatever is held::

    s' = x Wd + bd (+ gamma * s in every block but the first)
    p  = softmax(MLP(RMSNorm(s')))   d_r -> d_r GELU -> d_r GELU -> experts
    e  = top-k of (p + bias)         bias: no gradient, a leaf of the
                                     ``balancing`` collection, not a parameter;
                                     zeros, or set once from some tokens' loads
                                     (``apply(..., mutable=["balancing"])``)
    y  = sum over e of p[e] * down_e(silu(gate_e x) * up_e x)    not renormalised

The whole router runs in float32, its products at "highest" precision: the
choice is discrete.  The dropless dispatch is ``parallel/moe.py:
dispatch_experts``; attention is the Pallas flash kernels on a TPU
(``use_flash``), the dense oracle elsewhere; the head and the loss run a block
of tokens at a time (``losses.tied_head_cross_entropy``: a block's logits and
their log-sum-exp are one Pallas call on a TPU, ``hvd_head_logits`` of
``ops/tied_head.py``, and the two backward products XLA's).  With
``checkpoint_blocks`` each block is under ``jax.checkpoint`` and keeps two
arrays, the flash kernel's output and its row statistics
(``ops/flash_attention.py:CHECKPOINT_NAMES``): the backward runs the rest of
the block's forward again, projections, mixing, router and experts, and not
the kernel.

A chip may hold a share of the model, as ``models/sdar.py``:
``num_experts_held`` consecutive experts from ``first_expert`` on and
``vocab_size`` rows of the embedding.  A token whose expert is absent gets
zero from the layer.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops.embedding import embed_lookup
from ..ops.flash_attention import (
    CHECKPOINT_NAMES, dense_attention, flash_attention)
from ..parallel.moe import dispatch_experts, expert_load
from .losses import tied_head_cross_entropy
from .sdar import RMSNorm, _expert_init, rotary


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272         # rows of the embedding held
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2               # conv0's kernel, depthwise
    cca_time1: int = 2               # conv1's kernel, grouped by head
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5e6
    num_experts: int = 16            # the router's width
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    rms_norm_eps: float = 1e-5
    num_experts_held: Optional[int] = None   # None: every expert
    first_expert: int = 0
    # jax.checkpoint around each block, which keeps its flash kernel's output
    # and row statistics alone ([B, S, heads * head_dim] in ``dtype`` and a
    # float32 a row and head: 34 MB a layer at one sequence of 16,384); no
    # key chooses what is kept.
    checkpoint_blocks: bool = False
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    @property
    def experts_held(self) -> int:
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)


# The expert layer's row buffer over an even router's rows
# (``parallel/moe.py:row_buffer``).  Top-1 with half the experts held: twice
# the even rows is every row a router can send, so the layer never walks its
# rows in parts and its program holds no conditional.  The kernels, the
# gather and the sum back into the tokens skip the buffer's empty tail
# (``parallel/moe.py:rows_walked``: 8,192 of 16,384 rows where the load is
# even), so what is paid by the row follows the rows routed.
EXPERT_CAPACITY_FACTOR = 2.0

# The tied embedding's standard deviation at initialisation (the
# configuration's ``assumed.initializers`` says why).
EMBEDDING_STDDEV = 1.0

# The published sizes (config.json of Zyphra/ZAYA1-8B), whole.
ZAYA1_8B = ZayaConfig()
ZAYA_TINY = ZayaConfig(vocab_size=512, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, head_dim=16,
                       num_experts=8, moe_intermediate_size=48,
                       router_hidden_size=32, dtype=jnp.float32,
                       use_flash=False)


def shift(x, steps: int = 1):
    """``x`` [B, S, ...] moved ``steps`` positions later along the sequence,
    zeros in front: row t holds what row t - steps held."""
    if steps == 0:
        return x
    pad = [(0, 0), (steps, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def l2_normalize(x, to: float):
    """``to * x / |x|`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * (to * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                               + 1e-12))


def _taps_init(key, shape, dtype=jnp.float32):
    """lecun-normal for a convolution over the sequence: fan-in is the taps
    (depthwise, [taps, channels]) or taps x a group's channels (grouped,
    [taps, groups, in, out])."""
    fan_in = shape[0] * (shape[2] if len(shape) == 4 else 1)
    return jax.random.normal(key, shape, dtype) * fan_in ** -0.5


# Rounds of the balancing bias's rule, and the first round's step as a share
# of the tokens' surplus; a round's step is ``BALANCE_DECAY`` of the one
# before.
BALANCE_ROUNDS, BALANCE_STEP, BALANCE_DECAY = 400, 0.5, 0.985


def balancing_bias(probs, top_k: int):
    """The bias [experts] under which the top-k of ``probs + bias`` loads
    every expert alike on these tokens (``probs`` [T, experts]): the rule of
    the mixtures that balance without an auxiliary loss, run to its resting
    point.  After each round the bias of an expert chosen more often than
    the mean falls and of one chosen less often rises, by its surplus as a
    share of the tokens times a step that shrinks from round to round.  No
    gradient passes."""
    probs = lax.stop_gradient(probs)
    tokens, experts = probs.shape

    def round_(i, bias):
        chosen = lax.top_k(probs + bias, top_k)[1]
        load = jnp.sum(jax.nn.one_hot(chosen, experts, dtype=jnp.float32),
                       axis=(0, 1))
        surplus = (load - tokens * top_k / experts) / tokens
        return bias - BALANCE_STEP * BALANCE_DECAY ** i * surplus

    return lax.fori_loop(0, BALANCE_ROUNDS, round_,
                         jnp.zeros((experts,), jnp.float32))


class ResidualScale(nn.Module):
    """``a * (r + c) + b * (y + e)``: learnt scale and bias on both arms of
    a residual sum, the arithmetic in float32, the result in ``dtype``."""
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, r, y):
        width = (r.shape[-1],)
        a = self.param("a", nn.initializers.ones, width)
        b = self.param("b", nn.initializers.ones, width)
        c = self.param("c", nn.initializers.zeros, width)
        e = self.param("e", nn.initializers.zeros, width)
        with jax.named_scope("hvd_residual_scale"):
            return (a * (r.astype(jnp.float32) + c)
                    + b * (y.astype(jnp.float32) + e)).astype(self.dtype)


class CCA(nn.Module):
    """Compressed convolutional attention."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        batch, seq = x.shape[:2]
        h, g, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        group = h // g

        def proj(name, width):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        with jax.named_scope("hvd_attn_proj"):
            q0, k0 = proj("q_proj", h * d), proj("k_proj", g * d)
            v_now, v_before = proj("v_proj", d), proj("v_shift_proj", d)
        conv0 = self.param("conv0", _taps_init, (cfg.cca_time0, (h + g) * d))
        conv1 = self.param("conv1", _taps_init,
                           (cfg.cca_time1, h + g, d, d))
        temp = self.param("temp", nn.initializers.ones, (g,))
        with jax.named_scope("hvd_cca_mix"):
            z = jnp.concatenate([q0, k0], axis=-1).astype(jnp.float32)
            # Causal: tap j of a kernel of n reads the row n - 1 - j before.
            z = sum(conv0[j] * shift(z, cfg.cca_time0 - 1 - j)
                    for j in range(cfg.cca_time0))
            z = z.astype(cfg.dtype).reshape(batch, seq, h + g, d)
            u = sum(jnp.einsum("bsgc,gcd->bsgd",
                               shift(z, cfg.cca_time1 - 1 - j),
                               conv1[j].astype(cfg.dtype),
                               preferred_element_type=jnp.float32)
                    for j in range(cfg.cca_time1))
            q0 = q0.astype(jnp.float32).reshape(batch, seq, g, group, d)
            k0 = k0.astype(jnp.float32).reshape(batch, seq, g, 1, d)
            q = u[:, :, :h] + ((q0 + k0) / 2).reshape(batch, seq, h, d)
            k = u[:, :, h:] + (jnp.mean(q0, axis=3) + k0[:, :, :, 0]) / 2
            v = jnp.stack([v_now, shift(v_before)], axis=2)
            positions, rotated = jnp.arange(seq), int(
                d * cfg.partial_rotary_factor)
            q = rotary(l2_normalize(q, d ** 0.5), positions, cfg.rope_theta,
                       rotated).astype(cfg.dtype)
            k = rotary(l2_normalize(k, d ** 0.5) * temp[:, None], positions,
                       cfg.rope_theta, rotated).astype(cfg.dtype)
        attend = flash_attention if cfg.use_flash else dense_attention
        ctx = attend(q, k, v, causal=True)
        with jax.named_scope("hvd_attn_proj"):
            return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                            name="o_proj")(ctx.reshape(batch, seq, h * d))


class ZayaRouter(nn.Module):
    """``(probs [T, experts], state [T, d_r])`` of tokens ``x`` [T, d] and
    the state ``s`` handed down (None in the first block): float32
    throughout, every product at "highest" precision."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, s):
        cfg, width = self.config, self.config.router_hidden_size

        def dense(name, features, use_bias=True):
            return nn.Dense(features, use_bias=use_bias, dtype=jnp.float32,
                            precision=lax.Precision.HIGHEST, name=name)

        state = dense("down", width)(x.astype(jnp.float32))
        if s is not None:
            gamma = self.param("gamma", nn.initializers.ones, (width,))
            state = state + gamma * s
        z = RMSNorm(cfg.rms_norm_eps, name="norm")(state)
        z = jax.nn.gelu(dense("mlp_0", width)(z), approximate=False)
        z = jax.nn.gelu(dense("mlp_1", width)(z), approximate=False)
        z = dense("mlp_2", cfg.num_experts, use_bias=False)(z)
        return jax.nn.softmax(z, axis=-1), state


class ZayaExperts(nn.Module):
    """The router and the held experts' part of the layer.  The rows routed
    to each held expert and every token's chosen experts are sown under
    ``intermediates`` (``expert_load``, ``chosen_experts``) for whoever asks
    for them."""
    config: ZayaConfig

    @nn.compact
    def __call__(self, x, s):
        cfg = self.config
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        tokens = x.reshape(-1, d)
        with jax.named_scope("hvd_moe_router"):
            probs, state = ZayaRouter(cfg, name="router")(
                tokens, None if s is None else s.reshape(tokens.shape[0], -1))
            # The balancing bias: in the choice, not in the gate, and no
            # parameter (nothing differentiates or decays it).  A caller
            # that makes the collection mutable has it set from these
            # tokens' loads (``balancing_bias``).
            held_bias = self.variable("balancing", "bias", jnp.zeros,
                                      (cfg.num_experts,), jnp.float32)
            if (self.is_mutable_collection("balancing")
                    and not self.is_initializing()):
                held_bias.value = balancing_bias(probs,
                                                 cfg.num_experts_per_tok)
            chosen = lax.top_k(lax.stop_gradient(probs) + held_bias.value,
                               cfg.num_experts_per_tok)[1]
            gate = jnp.take_along_axis(probs, chosen, axis=-1)
        w_gate = self.param("w_gate", _expert_init, (held, d, f))
        w_up = self.param("w_up", _expert_init, (held, d, f))
        w_down = self.param("w_down", _expert_init, (held, f, d))
        y = dispatch_experts(
            tokens, chosen, gate, w_gate, w_up, w_down,
            first_expert=cfg.first_expert, experts_total=cfg.num_experts,
            capacity_factor=EXPERT_CAPACITY_FACTOR)
        self.sow("intermediates", "expert_load",
                 expert_load(chosen, cfg.first_expert, held))
        self.sow("intermediates", "chosen_experts", chosen)
        return y.reshape(x.shape), state.reshape(*x.shape[:-1], -1)


class ZayaBlock(nn.Module):
    config: ZayaConfig
    first: bool = False

    @nn.compact
    def __call__(self, r, y, s):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype,  # noqa: E731
                                    name=name)
        with jax.named_scope("hvd_block"):
            if not self.first:
                r = ResidualScale(cfg.dtype, name="res_attn")(r, y)
            h = norm("input_norm")(r)
            with jax.named_scope("hvd_attn"):
                y = CCA(cfg, name="attn")(h)
            r = ResidualScale(cfg.dtype, name="res_moe")(r, y)
            y, s = ZayaExperts(cfg, name="moe")(norm("post_attn_norm")(r), s)
            return r, y, s


class Zaya(nn.Module):
    """``Zaya(cfg)(ids)``: float32 logits [B, S, vocab_size] (every one of
    them: for small sizes).  ``method="hidden"``: what the head reads, [B, S,
    d] after the final norm; ``method="head"``: the logits of some of its
    rows; ``method="loss"``: the next-token cross-entropy through the
    blocked head, no logits kept."""

    config: ZayaConfig

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        block = ZayaBlock
        if cfg.checkpoint_blocks:
            # With ``use_flash`` off nothing carries the names: nothing kept.
            kept = jax.checkpoint_policies.save_only_these_names(
                *CHECKPOINT_NAMES)
            block = nn.remat(ZayaBlock, policy=kept)
        self.layers = [block(cfg, first=i == 0, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.res_final = ResidualScale(cfg.dtype)
        self.final_norm = RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype)

    def hidden(self, ids):
        with jax.named_scope("hvd_embed"):
            r = embed_lookup(self.embed.embedding, ids, self.config.dtype)
        y = s = None
        for layer in self.layers:
            r, y, s = layer(r, y, s)
        with jax.named_scope("hvd_lm_head"):
            return self.final_norm(self.res_final(r, y))

    def head(self, x):
        """Float32 logits of rows ``x`` [..., d] of :meth:`hidden`: the
        embedding transposed, the product in ``x``'s dtype."""
        with jax.named_scope("hvd_lm_head"):
            return lax.dot_general(
                x, self.embed.embedding.astype(x.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    def __call__(self, ids):
        return self.head(self.hidden(ids))

    def loss(self, ids):
        """Mean over the ``B x (S - 1)`` predicting positions of the next
        token's negative log-likelihood."""
        x = self.hidden(ids)
        batch, seq = ids.shape
        with jax.named_scope("hvd_lm_head"):
            predicts = jnp.arange(seq) < seq - 1
            weights = jnp.broadcast_to(predicts / (batch * (seq - 1.0)),
                                       ids.shape)
            return tied_head_cross_entropy(
                x.reshape(batch * seq, -1), self.embed.embedding,
                jnp.roll(ids, -1, axis=1).reshape(-1),
                weights.reshape(-1).astype(jnp.float32))


def lm_loss(model: Zaya, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S] through the blocked
    head."""
    return model.apply(variables, ids, method="loss")
