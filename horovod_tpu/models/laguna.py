"""Laguna: sliding-window attention layers among global ones, a gate a head,
a top-k mixture of SwiGLU experts beside a shared one; TPU-first flax.

The architecture of ``poolside/Laguna-S-2.1`` (``model_type: laguna``).  With
``x`` [S, d], RMSNorm at eps 1e-6, no bias anywhere, everything causal::

    block l:  h = x + Attn_l(RMSNorm(x));  y = h + FF_l(RMSNorm(h))
    after the last block the final RMSNorm and an untied head

**Attention**, by ``layer_types[l]``: ``num_attention_heads_per_layer[l]``
query heads (48 on a ``full_attention`` layer, 72 on a ``sliding_attention``
one) on 8 key/value heads of 128; rotary positions in the half-split pairing
of ``models/sdar.py:rotary``, by the kind's own rule (``RopeParameters``):
a sliding layer turns the whole head at theta 1e4, a full layer the first
half of it at YaRN's blended frequencies (:func:`yarn_inv_freq`) with cos and
sin scaled by ``attention_factor``; scores ``q . k / sqrt(128)``; query i sees
key j iff ``j <= i`` and, on a sliding layer, ``i - j < sliding_window``
(``ops/flash_attention.py``'s ``window``); **a gate a head**: ``g =
sigmoid(RMSNorm(x) W_g)``, ``W_g`` [d, heads], head h's output times ``g[...,
h]`` before ``W_o``.

**Feed-forward**, by ``mlp_layer_types[l]``: a dense SwiGLU of
``intermediate_size`` columns, or ``routed_scaling_factor x`` a softmax top-k
mixture of SwiGLU experts with renormalised weights
(``parallel/moe.py:routed_experts``) **plus one shared expert** that every
token takes.

Everything between the projections and the kernels stays ``[B, S, heads x
head_dim]``: the rotary turn rolls a head's lanes in place
(:func:`rotary_flat`) and the gate reaches a head's 128 lanes through a 0/1
product (:func:`gate_heads`), so nothing of q's size is copied or
transposed.  Float32: parameters, the router, RMSNorm arithmetic, rotary
angles and the turn itself, the gate's sigmoid, softmax statistics, logits and
loss; ``dtype`` activations and matmul operands.

**A chip's share of a layer.**  A chip may hold ``*_held`` of a layer:
``num_kv_heads_held`` key/value heads with ``num_heads_per_layer_held[l]``
query heads on them and their gate columns, ``dense_columns_held`` columns of
the dense feed-forward, ``num_experts_held`` consecutive experts from
``first_expert`` on (the router whole), ``vocab_size_held`` rows of the
embedding and the head; norms, the residual stream, the router and the shared
expert are whole on every chip.  Every layer takes ``axis_name``: attention's
output projection and the dense ``down`` sum over it
(``parallel/tensor_parallel.py:row_parallel_dense``; their kernels are drawn
at the whole layer's fan-in); the experts' exchange, and the head and the loss
over a vocabulary split across chips, are not built and raise by name.  With
``axis_name=None`` a layer computes this chip's part of each sum and nothing
stands in for the others'.  Gate/up pairs are ``models/jamba.py:PairedDense``
leaves ``[d, 2 x held]``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.embedding import embed_lookup
from ..ops.flash_attention import dense_attention, flash_attention
from ..parallel.moe import routed_experts
from ..parallel.tensor_parallel import vocab_parallel_embedding
from .jamba import PairedDense, RowParallel
from .losses import head_cross_entropy
from .sdar import RMSNorm, _expert_init

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """One layer kind's entry of the configuration's ``rope_parameters``."""
    rope_theta: float
    partial_rotary_factor: float = 1.0
    rope_type: str = "default"          # or "yarn"
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    num_layers: int = 48
    # None: the published pattern, a full layer at every index i mod 4 = 0
    # with 48 query heads, sliding layers of 72 at the other three; layer 0
    # dense and every other one sparse.
    layer_types: Optional[Tuple[str, ...]] = None
    num_heads_per_layer: Optional[Tuple[int, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_full: RopeParameters = RopeParameters(
        rope_theta=5e5, partial_rotary_factor=0.5, rope_type="yarn",
        factor=128.0, original_max_position_embeddings=8192,
        attention_factor=1.4852030263919618)
    rope_sliding: RopeParameters = RopeParameters(rope_theta=1e4)
    intermediate_size: int = 12288
    num_experts: int = 256              # the router's width
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    # What this chip holds of each layer; None: the whole.
    vocab_size_held: Optional[int] = None
    num_kv_heads_held: Optional[int] = None
    num_heads_per_layer_held: Optional[Tuple[int, ...]] = None
    dense_columns_held: Optional[int] = None
    num_experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    def __post_init__(self):
        for name, default in (
                ("layer_types", lambda i: SLIDING if i % 4 else FULL),
                ("num_heads_per_layer", lambda i: 72 if i % 4 else 48),
                ("mlp_layer_types", lambda i: SPARSE if i else DENSE)):
            given = getattr(self, name)
            given = (tuple(default(i) for i in range(self.num_layers))
                     if given is None else tuple(given)[:self.num_layers])
            if len(given) != self.num_layers:
                raise ValueError(f"LagunaConfig.{name} names {len(given)} "
                                 f"layers of {self.num_layers}")
            object.__setattr__(self, name, given)
        held = self.num_heads_per_layer_held
        if held is not None:
            object.__setattr__(self, "num_heads_per_layer_held",
                               tuple(held)[:self.num_layers])
        for layer in range(self.num_layers):
            if self.heads_held(layer) % self.kv_heads_held:
                raise ValueError(
                    f"layer {layer}: {self.heads_held(layer)} query heads "
                    f"held on {self.kv_heads_held} key/value heads")

    @property
    def kv_heads_held(self) -> int:
        return self.num_kv_heads_held or self.num_kv_heads

    def heads_held(self, layer: int) -> int:
        return (self.num_heads_per_layer if self.num_heads_per_layer_held
                is None else self.num_heads_per_layer_held)[layer]

    @property
    def columns_held(self) -> int:
        return self.dense_columns_held or self.intermediate_size

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def rows_held(self) -> int:
        return self.vocab_size_held or self.vocab_size

    def window(self, layer: int) -> Optional[int]:
        """The keys a query of ``layer`` sees, its own among them; None on a
        full layer."""
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    def rope(self, layer: int) -> RopeParameters:
        return (self.rope_sliding if self.layer_types[layer] == SLIDING
                else self.rope_full)


# The expert layer's row buffer over an even router's rows
# (``parallel/moe.py:row_buffer``; the configuration's
# ``assumed.expert_capacity_factor`` says how it was chosen).
EXPERT_CAPACITY_FACTOR = 2.0
# The embedding's standard deviation at initialisation (the configuration's
# ``assumed.initializers``).
EMBEDDING_STDDEV = 0.02

# The published sizes (config.json of poolside/Laguna-S-2.1), whole.
LAGUNA_S_2_1 = LagunaConfig()
LAGUNA_TINY = LagunaConfig(
    vocab_size=512, hidden_size=64, num_layers=5,
    num_heads_per_layer=(4, 6, 6, 6, 4), num_kv_heads=2, head_dim=16,
    sliding_window=8, intermediate_size=96, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=32,
    rope_full=dataclasses.replace(LAGUNA_S_2_1.rope_full,
                                  original_max_position_embeddings=16,
                                  factor=4.0),
    dtype=jnp.float32, use_flash=False)


def yarn_inv_freq(width: int, rope: RopeParameters) -> np.ndarray:
    """[width / 2] float64: the rotary frequencies of a ``width``-wide turn.
    ``default``: ``theta ** (-2 i / width)``.  ``yarn`` (arXiv:2309.00071,
    "NTK-by-parts"): a pair that turns more than ``beta_fast`` times over the
    original context keeps its frequency, one that turns less than
    ``beta_slow`` times has it divided by ``factor``, and between the two
    pair indices ``low`` and ``high`` the two are blended linearly."""
    i = np.arange(width // 2, dtype=np.float64)
    extrapolated = rope.rope_theta ** (-2.0 * i / width)
    if rope.rope_type == "default":
        return extrapolated
    if rope.rope_type != "yarn":
        raise ValueError(f"rope_type {rope.rope_type!r} is not built")

    def pair_turning(times: float) -> float:
        return (width * math.log(rope.original_max_position_embeddings
                                 / (times * 2 * math.pi))
                / (2 * math.log(rope.rope_theta)))

    low = max(math.floor(pair_turning(rope.beta_fast)), 0)
    high = min(math.ceil(pair_turning(rope.beta_slow)), width - 1)
    kept = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return extrapolated / rope.factor * (1.0 - kept) + extrapolated * kept


def rope_tables(positions, head_dim: int, rope: RopeParameters):
    """``(cos, sin, half)``: float32 [S, head_dim] tables for
    :func:`rotary_flat` and half the width that turns.  ``sin`` carries
    ``rotate_half``'s sign (minus on a pair's first lane); both carry
    ``attention_factor``; the lanes that pass are 1 and 0."""
    width = int(head_dim * rope.partial_rotary_factor)
    freq = jnp.asarray(yarn_inv_freq(width, rope), jnp.float32)
    angle = positions.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(angle) * rope.attention_factor
    sin = jnp.sin(angle) * rope.attention_factor
    passing = (len(positions), head_dim - width)
    return (jnp.concatenate([cos, cos, jnp.ones(passing)], -1),
            jnp.concatenate([-sin, sin, jnp.zeros(passing)], -1), width // 2)


def rotary_flat(x, cos, sin, half: int):
    """Rotary positions on ``x`` [B, S, heads x head_dim] as it lies, in the
    half-split pairing (lane i of a head with lane i + ``half``): ``x cos +
    rotate_half(x) sin`` with the tables of :func:`rope_tables`, float32
    inside, ``x``'s dtype out.  A head at a time on its own lanes (a slice of
    whole lane tiles at 128; the tables are never repeated over the heads, an
    array of q's size in float32): a lane's partner comes by rolling the
    head's lanes ``half`` either way and taking, lane by lane, the roll that
    stayed inside the turning width."""
    head_dim = cos.shape[-1]
    first = jnp.arange(head_dim) < half

    def turn(head):
        wide = head.astype(jnp.float32)
        partner = jnp.where(first, jnp.roll(wide, -half, -1),
                            jnp.roll(wide, half, -1))
        return (wide * cos + partner * sin).astype(x.dtype)

    return jnp.concatenate([
        turn(x[..., h * head_dim:(h + 1) * head_dim])
        for h in range(x.shape[-1] // head_dim)], axis=-1)


def gate_heads(ctx, gate, head_dim: int):
    """``ctx`` [B, S, heads x head_dim] with head h's lanes times ``gate[...,
    h]`` (float32 [B, S, heads]).  The gate reaches a head's lanes through a
    product with the 0/1 matrix [heads, heads x head_dim] that repeats each
    column ``head_dim`` times: one exact term a lane, on the MXU, and its
    transpose in the backward is the sum over a head's lanes; a broadcast
    over a [B, S, heads, head_dim] view would relay ``ctx`` out."""
    heads = gate.shape[-1]
    spread = jnp.repeat(jnp.eye(heads, dtype=ctx.dtype), head_dim, axis=1)
    return ctx * jnp.dot(gate.astype(ctx.dtype), spread)


def mixture_sum(routed, shared, scale: float):
    """``scale x`` the routed experts' weighted sum plus the shared expert's
    output, summed in float32, in ``shared``'s dtype."""
    return (scale * routed.astype(jnp.float32)
            + shared.astype(jnp.float32)).astype(shared.dtype)


def _dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class LagunaAttention(nn.Module):
    """Layer ``layer``'s attention on ``h = RMSNorm(x)``: its kind, head
    count and rotary rule by the layer's index."""
    config: LagunaConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        seq, d = h.shape[1], cfg.head_dim
        heads, groups = cfg.heads_held(self.layer), cfg.kv_heads_held
        with jax.named_scope("hvd_attn_proj"):
            q = _dense(heads * d, cfg.dtype, "q_proj")(h)
            k = _dense(groups * d, cfg.dtype, "k_proj")(h)
            v = _dense(groups * d, cfg.dtype, "v_proj")(h)
        with jax.named_scope("hvd_rope"):
            cos, sin, half = rope_tables(jnp.arange(seq), d,
                                         cfg.rope(self.layer))
            q, k = rotary_flat(q, cos, sin, half), rotary_flat(k, cos, sin,
                                                               half)
        # q, k and v are [B, S, heads * d] from the projections on: the heads
        # are a view at the kernels' door, which read that layout.
        attend = flash_attention if cfg.use_flash else dense_attention
        ctx = attend(*(t.reshape(*t.shape[:2], -1, d) for t in (q, k, v)),
                     causal=True, window=cfg.window(self.layer))
        # For whoever applies the layer with ``mutable=["intermediates"]``
        # (the benchmark holds the step's own band to the reference's through
        # it); in a step nothing is kept.
        self.sow("intermediates", "attention", {
            "q": q, "k": k, "v": v,
            "ctx": ctx.reshape(*ctx.shape[:2], heads * d)})
        with jax.named_scope("hvd_attn_gate"):
            gate = jax.nn.sigmoid(jnp.dot(
                h.astype(cfg.dtype),
                self.param("gate_proj", nn.linear.default_kernel_init,
                           (h.shape[-1], heads)).astype(cfg.dtype),
                preferred_element_type=jnp.float32))
            ctx = gate_heads(ctx.reshape(*ctx.shape[:2], heads * d), gate, d)
        with jax.named_scope("hvd_attn_proj"):
            return RowParallel(
                cfg.hidden_size, cfg.num_heads_per_layer[self.layer] * d,
                self.axis_name, cfg.dtype, name="o_proj")(ctx)


class LagunaMLP(nn.Module):
    """The dense SwiGLU of a ``dense`` layer, this chip's columns."""
    config: LagunaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        gate, up = PairedDense(cfg.columns_held, cfg.dtype,
                               name="gate_up")(h)
        return RowParallel(cfg.hidden_size, cfg.intermediate_size,
                           self.axis_name, cfg.dtype, name="down")(
                               jax.nn.silu(gate) * up)


class LagunaMoE(nn.Module):
    """``routed_scaling_factor x`` the held experts' part of the token's
    top-k sum (``parallel/moe.py:routed_experts``; the router whole) plus the
    shared expert, whole on every chip.  The rows routed to each held expert
    and every token's chosen experts are sown under ``intermediates``
    (``expert_load``, ``chosen_experts``)."""
    config: LagunaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        if self.axis_name is not None:
            raise NotImplementedError(
                f"LagunaMoE over {self.axis_name!r}: the experts' exchange "
                "is not built (ROADMAP Reach B1); attention and the dense "
                "feed-forward sum over the axis")
        d, f, held = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.experts_held
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, cfg.num_experts))
        w_gate = self.param("w_gate", _expert_init, (held, d, f))
        w_up = self.param("w_up", _expert_init, (held, d, f))
        w_down = self.param("w_down", _expert_init, (held, f, d))
        y, routing = routed_experts(
            h.reshape(-1, d), router, w_gate, w_up, w_down,
            top_k=cfg.num_experts_per_tok, first_expert=cfg.first_expert,
            renormalize=cfg.norm_topk_prob,
            capacity_factor=EXPERT_CAPACITY_FACTOR)
        self.sow("intermediates", "expert_load", routing.load)
        self.sow("intermediates", "chosen_experts", routing.experts)
        with jax.named_scope("hvd_moe_shared"):
            gate, up = PairedDense(cfg.shared_expert_intermediate_size,
                                   cfg.dtype, name="shared_gate_up")(h)
            shared = _dense(d, cfg.dtype, "shared_down")(
                jax.nn.silu(gate) * up)
            return mixture_sum(y.reshape(h.shape), shared,
                               cfg.routed_scaling_factor)


class LagunaBlock(nn.Module):
    config: LagunaConfig
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
                y = LagunaAttention(cfg, self.layer, self.axis_name,
                                    name="attn")(h)
            x = add(x, y)
            h = norm("post_attn_norm")(x)
            if cfg.mlp_layer_types[self.layer] == SPARSE:
                y = LagunaMoE(cfg, self.axis_name, name="moe")(h)
            else:
                with jax.named_scope("hvd_mlp"):
                    y = LagunaMLP(cfg, self.axis_name, name="mlp")(h)
            return add(x, y)


class Laguna(nn.Module):
    """``Laguna(cfg)(ids)``: float32 logits [B, S, rows held].
    ``method="hidden"``: what the head reads, [B, S, d] after the final norm;
    ``method="head"``: the logits of some of its rows; ``method="loss"``: the
    next-token cross-entropy over every position but the last.  With
    ``axis_name`` attention and the dense feed-forward sum over that mesh
    axis and the embedding is looked up across it; the experts' exchange and
    the head and the loss over a vocabulary split across chips are not
    built."""

    config: LagunaConfig
    axis_name: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.rows_held, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        self.layers = [LagunaBlock(cfg, i, self.axis_name, name=f"layer_{i}")
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
                f"Laguna.{what} over a vocabulary split across "
                f"{self.axis_name!r}: the softmax's exchange is not built "
                "(ROADMAP Reach B9); attention and the dense feed-forward "
                "(method='hidden' of a model without sparse layers) sum over "
                "the axis")


def lm_loss(model: Laguna, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S]."""
    return model.apply(variables, ids, method="loss")
