"""Jamba: Mamba-1 selective-scan layers with an attention layer every
``attn_layer_period``, a dense SwiGLU feed-forward in every block; TPU-first
flax.

The architecture of ``ai21labs/AI21-Jamba2-3B`` (``model_type: jamba``).
With ``x`` [S, d], RMSNorm at eps 1e-6, everything causal::

    block i:  x += mixer_i(RMSNorm(x));  x += down(silu(gate h) * up h), h = RMSNorm(x)
              mixer_i is attention where i mod attn_layer_period ==
              attn_layer_offset and the Mamba mixer otherwise
    after the last block the final RMSNorm and the head, the embedding
    transposed

**Mamba mixer**, ``d_inner = mamba_expand x d`` channels, state N =
``mamba_d_state``, no bias on a projection, one on the convolution::

    [u | z] = h W_in                  W_in stored [d, 2 d_inner], u first
    u  = silu(causal depthwise conv over mamba_d_conv steps of u, + b)
    [dt | B | C] = u W_x                        mamba_dt_rank + N + N
    dt, B, C = RMSNorm each, a learnt scale each               (Jamba's own)
    dt = softplus(dt W_dt + b_dt)                              [S, d_inner]
    A  = -exp(A_log)                                           [d_inner, N]
    s_t = exp(dt_t (x) A) * s_(t-1) + (dt_t * u_t) (x) B_t
    y_t = s_t . C_t + D * u_t
    out = (y * silu(z)) W_out

**Attention**: ``num_attention_heads`` query heads on ``num_key_value_heads``
key/value heads (20 on 1: multi-query), causal, no bias, **no positional
encoding of any kind** (the recurrence carries the order), scale
``head_dim ** -0.5``.

``mamba_norms`` False is Mamba-1 as published, without Jamba's three norms
(``models/phi4flash.py`` runs this mixer so, and with ``memory=True`` takes
the scan's output ``y`` beside the mixer's own).

Everything between the projections and the kernels stays channel-minor ``[B,
S, C]``: the convolution is ``mamba_d_conv`` shifted multiply-adds
(``models/zaya.py:shift``), the scan is ``ops/selective_scan.py`` on that
layout, attention is the Pallas flash kernels on a TPU (``use_flash``).
Float32: parameters, the scan's state, ``dt``, ``A``, the softplus, the three
norms, RMSNorm arithmetic, residual sums, softmax statistics, logits and
loss; ``dtype`` activations and matmul operands.

**A share of a tensor-parallel layer.**  A chip may hold ``*_held`` of each
layer's width, Megatron-style: ``mamba_d_inner_held`` of the Mamba channels
(a channel is a head of the scan: its own ``A``, ``D``, ``dt``),
``num_attention_heads_held`` query heads with the key/value heads whole,
``intermediate_size_held`` feed-forward columns and ``vocab_size_held`` rows
of the embedding.  Every layer takes ``axis_name``: with a mesh axis it sums
over it at its row-parallel points (``parallel/tensor_parallel.py:
row_parallel_dense``): ``W_x``'s result **inside** the mixer, before the three
norms, and ``W_out``; attention's output projection; the feed-forward's
``down``.  With ``axis_name=None`` a layer computes this chip's part of each
sum and nothing stands in for the others'.  The kernels of row-parallel
products are drawn at the whole layer's fan-in, so a share is a slice of the
whole model's initial weights in distribution.

**The paired kernels are stored flat** (``PairedDense``): ``in_proj`` is one
float32 ``[d, 2 x held]`` kernel with columns ``[u | z]`` and ``gate_up``
one with columns ``[gate | up]`` (as the published checkpoint stores
``in_proj``: one linear of ``2 d_inner`` outputs), and so are their gradients
and an optimizer's moments; each half is a product of its own.  A chip's
share of either kernel is **its own channels' columns of both halves**: the
whole layer's kernel viewed ``[d, 2, width]``, cut on the last axis,
flattened again (not a run of neighbouring columns of the flat kernel).
``q_proj`` ``[d, heads, head_dim]`` and ``kv_proj`` ``[d, 2, groups,
head_dim]`` keep their head axes, which the plain reference reads the head
counts off.

With ``checkpoint_blocks`` each block is under ``jax.checkpoint`` and keeps
what ``CHECKPOINT_NAMES`` lists: the two halves of ``in_proj`` (``u``, ``z``)
or, in an attention block, the flash kernel's output and its row statistics
(``ops/flash_attention.py:CHECKPOINT_NAMES``), and the two halves of
``gate_up``, each in ``dtype``.  The backward runs the rest of the block's
forward again, the norms, the convolution, ``x_proj``, the scan's forward,
``out_proj``, the attention layer's projections, and neither paired product
nor the flash kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.collectives import vary_like
from ..ops.embedding import embed_lookup
from ..ops.flash_attention import (
    CHECKPOINT_NAMES as FLASH_CHECKPOINT_NAMES, dense_attention,
    flash_attention)
from ..ops.selective_scan import selective_scan
from ..parallel.tensor_parallel import (
    row_parallel_dense, vocab_parallel_embedding)
from .flat_dense import FlatDenseGeneral
from .losses import tied_head_cross_entropy
from .sdar import RMSNorm
from .zaya import _taps_init, shift


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    hidden_size: int = 2560
    num_layers: int = 28
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    num_heads: int = 20
    num_kv_heads: int = 1
    head_dim: int = 128
    intermediate_size: int = 8192
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_norms: bool = True         # Jamba's own three, on dt, B and C
    rms_norm_eps: float = 1e-6
    # What this chip holds of each layer's width; None: the whole.
    vocab_size_held: Optional[int] = None
    num_heads_held: Optional[int] = None
    intermediate_size_held: Optional[int] = None
    mamba_d_inner_held: Optional[int] = None
    checkpoint_blocks: bool = False  # jax.checkpoint around each block
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    def __post_init__(self):
        if self.mamba_proj_bias:
            raise ValueError(
                "JambaConfig: mamba_proj_bias = True is not built: in_proj, "
                "x_proj, dt_proj and out_proj carry no bias (Jamba publishes "
                "False)")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def channels_held(self) -> int:
        return self.mamba_d_inner_held or self.d_inner

    @property
    def heads_held(self) -> int:
        return self.num_heads_held or self.num_heads

    @property
    def columns_held(self) -> int:
        return self.intermediate_size_held or self.intermediate_size

    @property
    def rows_held(self) -> int:
        return self.vocab_size_held or self.vocab_size

    def is_attention(self, layer: int) -> bool:
        """The layer order, from ``attn_layer_period`` and ``_offset``."""
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def layer_kinds(self) -> tuple:
        return tuple("attention" if self.is_attention(i) else "mamba"
                     for i in range(self.num_layers))


# The tied embedding's standard deviation at initialisation (the
# configuration's ``assumed.initializers`` says why).
EMBEDDING_STDDEV = 0.02
# The step ``dt`` the bias of ``W_dt`` starts at: log-uniform between these,
# through the inverse of the softplus (Mamba's own initialiser).
DT_INIT_MIN, DT_INIT_MAX, DT_INIT_FLOOR = 1e-3, 1e-1, 1e-4

# The published sizes (config.json of ai21labs/AI21-Jamba2-3B), whole.
JAMBA2_3B = JambaConfig()
JAMBA_TINY = JambaConfig(vocab_size=512, hidden_size=64, num_layers=4,
                         attn_layer_period=2, attn_layer_offset=1,
                         num_heads=4, num_kv_heads=1, head_dim=16,
                         intermediate_size=96, mamba_d_state=8,
                         mamba_dt_rank=8, dtype=jnp.float32, use_flash=False)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1 .. N)`` a channel: S4D's real initialiser."""
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(DT_INIT_MAX) - math.log(DT_INIT_MIN))
                 + math.log(DT_INIT_MIN))
    dt = jnp.maximum(dt, DT_INIT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def _uniform_init(bound: float):
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


def _scaled(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


# What a checkpointed block keeps for its backward (``jax.checkpoint``'s
# ``save_only_these_names``): the paired projections' four halves, named where
# ``MambaMixer`` and ``JambaMLP`` receive them in ``dtype`` ([B, S, held]
# each), and the flash kernel's output and row statistics.  Under no
# checkpoint a name is an identity that lowers to nothing.
CHECKPOINT_NAMES = ("hvd_ssm_in_u", "hvd_ssm_in_z", "hvd_mlp_gate",
                    "hvd_mlp_up") + FLASH_CHECKPOINT_NAMES


class RowParallel(nn.Module):
    """``x_local @ kernel`` summed over ``axis_name`` (None: this chip's part
    of the sum), the kernel ``[held, features]`` drawn at the whole layer's
    fan-in ``fan_in``, the result in ``out_dtype``."""
    features: int
    fan_in: int
    axis_name: Optional[str] = None
    dtype: Any = jnp.bfloat16
    out_dtype: Any = None

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.normal(self.fan_in ** -0.5),
            (x.shape[-1], self.features))
        return row_parallel_dense(
            x.astype(self.dtype), kernel.astype(self.dtype),
            axis_name=self.axis_name, dtype=self.out_dtype or self.dtype)


def _halves(x, kernel, dtype):
    held = kernel.shape[1] // 2
    x, kernel = x.astype(dtype), kernel.astype(dtype)
    return jnp.dot(x, kernel[:, :held]), jnp.dot(x, kernel[:, held:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def paired_dot(x, kernel, dtype):
    """``(x @ W[:, :held], x @ W[:, held:])`` in ``dtype`` of a kernel ``[d,
    2 x held]``, whose gradient leaves its two products in the kernel's own
    type (the accumulator's float32, never rounded to ``dtype``) and in the
    kernel's row-major layout, the one an optimizer's update reads it in."""
    return _halves(x, kernel, dtype)


def _paired_fwd(x, kernel, dtype):
    return _halves(x, kernel, dtype), (x, kernel)


def _paired_bwd(dtype, residuals, cotangents):
    x, kernel = residuals
    held = kernel.shape[1] // 2
    rows = x.astype(dtype).reshape(-1, x.shape[-1])
    first, second = (c.reshape(len(rows), held) for c in cotangents)
    rounded = kernel.astype(dtype)
    dx = (jnp.dot(first, rounded[:, :held].T)
          + jnp.dot(second, rounded[:, held:].T))
    dw = jnp.concatenate([jax.lax.dot_general(
        rows, c, (((0,), (0,)), ((), ())),
        preferred_element_type=kernel.dtype) for c in (first, second)], axis=1)
    # Left to itself a TPU's compiler lays a float32 product of this shape
    # out column-major, and then relays the kernel and an optimizer's moments
    # to match where they cross the step's boundary.
    return (dx.reshape(x.shape).astype(x.dtype),
            with_layout_constraint(dw, Layout(major_to_minor=(0, 1))))


paired_dot.defvjp(_paired_fwd, _paired_bwd)


class PairedDense(nn.Module):
    """Two column-parallel products of one kernel stored flat ``[d, 2 x
    held]`` (lecun-normal over ``d``: ``nn.DenseGeneral``'s draw at that
    shape), columns ``[first | second]``: ``paired_dot`` in ``dtype``."""
    held: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.linear.default_kernel_init,
                            (x.shape[-1], 2 * self.held))
        return paired_dot(vary_like(x, kernel), vary_like(kernel, x),
                          self.dtype)


class MambaMixer(nn.Module):
    """The Mamba-1 mixer of a configuration with ``JambaConfig``'s ``mamba_*``
    keys (``models/phi4flash.py`` brings its own).  ``mamba_norms`` False
    leaves ``dt``, ``B`` and ``C`` as ``x_proj`` made them (Mamba-1 as
    published: no scale is created).  ``memory``: the mixer returns ``(out,
    y)``, its scan's output ``y`` [B, S, held] (with the ``D u`` term, before
    the gate) beside its own, for the layers that read it as their memory."""
    config: JambaConfig
    axis_name: Optional[str] = None
    memory: bool = False

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        held, n, rank = cfg.channels_held, cfg.mamba_d_state, \
            cfg.mamba_dt_rank
        with jax.named_scope("hvd_ssm_proj"):
            u, z = PairedDense(held, cfg.dtype, name="in_proj")(h)
            u = checkpoint_name(u, "hvd_ssm_in_u")
            z = checkpoint_name(z, "hvd_ssm_in_z")
        taps = self.param("conv", _taps_init, (cfg.mamba_d_conv, held))
        conv_bias = self.param("conv_bias", nn.initializers.zeros, (held,)) \
            if cfg.mamba_conv_bias else 0.0
        a_log = self.param("A_log", _a_log_init, (held, n))
        d = self.param("D", nn.initializers.ones, (held,))
        scales = {k: self.param(f"{k}_norm", nn.initializers.ones, (width,))
                  for k, width in (("dt", rank), ("b", n), ("c", n))
                  } if cfg.mamba_norms else None
        dt_kernel = self.param("dt_proj", _uniform_init(rank ** -0.5),
                               (rank, held))
        dt_bias = self.param("dt_bias", _dt_bias_init, (held,))
        with jax.named_scope("hvd_ssm_mix"):
            # Causal: tap j of a kernel of K reads the row K - 1 - j before.
            u32 = u.astype(jnp.float32)
            u = jax.nn.silu(sum(
                taps[j] * shift(u32, cfg.mamba_d_conv - 1 - j)
                for j in range(cfg.mamba_d_conv)) + conv_bias
            ).astype(cfg.dtype)
            # Row-parallel inside the layer: the channels are contracted,
            # and the three norms want the whole sum.
            dbc = RowParallel(rank + 2 * n, cfg.d_inner, self.axis_name,
                              cfg.dtype, jnp.float32, name="x_proj")(u)
            dt, b, c = jnp.split(dbc, [rank, rank + n], -1)
            if scales:
                dt, b, c = (_scaled(part, scales[k], cfg.rms_norm_eps)
                            for k, part in (("dt", dt), ("b", b), ("c", c)))
            dt = jax.nn.softplus(jnp.dot(
                dt.astype(cfg.dtype), dt_kernel.astype(cfg.dtype),
                preferred_element_type=jnp.float32) + dt_bias)
            a = -jnp.exp(a_log)
        y = selective_scan(u, dt, a, b, c, d)
        # For whoever applies the mixer with ``mutable=["intermediates"]``
        # (the benchmark holds the step's own dt and scan to the reference's
        # through it); in a step nothing is kept.
        self.sow("intermediates", "scan", {
            "x_proj": dbc, "operands": (u, dt, a, b, c, d), "y": y})
        with jax.named_scope("hvd_ssm_mix"):
            gated = (y.astype(jnp.float32)
                     * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.dtype)
        with jax.named_scope("hvd_ssm_proj"):
            out = RowParallel(cfg.hidden_size, cfg.d_inner, self.axis_name,
                              cfg.dtype, name="out_proj")(gated)
        return (out, y) if self.memory else out


class JambaAttention(nn.Module):
    """Multi-query (grouped-query) causal attention without positions."""
    config: JambaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        batch, seq = h.shape[:2]
        heads, groups, d = cfg.heads_held, cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("hvd_attn_proj"):
            q = FlatDenseGeneral((heads, d), dtype=cfg.dtype, use_bias=False,
                                 name="q_proj")(h)
            # Whole on every chip: its gradient is this chip's heads' part.
            kv = FlatDenseGeneral((2, groups, d), dtype=cfg.dtype,
                                  use_bias=False, name="kv_proj")(h)
        k, v = kv[..., :groups * d], kv[..., groups * d:]
        attend = flash_attention if cfg.use_flash else dense_attention
        ctx = attend(q.reshape(batch, seq, heads, d),
                     k.reshape(batch, seq, groups, d),
                     v.reshape(batch, seq, groups, d), causal=True)
        with jax.named_scope("hvd_attn_proj"):
            return RowParallel(cfg.hidden_size, cfg.num_heads * d,
                               self.axis_name, cfg.dtype, name="o_proj")(
                                   ctx.reshape(batch, seq, heads * d))


class JambaMLP(nn.Module):
    config: JambaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, h):
        cfg = self.config
        held = cfg.columns_held
        gate, up = PairedDense(held, cfg.dtype, name="gate_up")(h)
        gate = checkpoint_name(gate, "hvd_mlp_gate")
        up = checkpoint_name(up, "hvd_mlp_up")
        # An array of its own.  With both halves kept for the backward a
        # TPU's compiler computes this product inside ``down``'s, as one of
        # its operands, and that product then runs at half its speed; held
        # apart it leaves ``up``'s product as a second result.
        hidden = jax.lax.optimization_barrier(jax.nn.silu(gate) * up)
        return RowParallel(cfg.hidden_size, cfg.intermediate_size,
                           self.axis_name, cfg.dtype, name="down")(hidden)


class JambaBlock(nn.Module):
    """One block; ``attention`` says which mixer it holds."""
    config: JambaConfig
    attention: bool = False
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
            if self.attention:
                with jax.named_scope("hvd_attn"):
                    y = JambaAttention(cfg, self.axis_name, name="attn")(h)
            else:
                y = MambaMixer(cfg, self.axis_name, name="mamba")(h)
            x = add(x, y)
            h = norm("pre_ff_norm")(x)
            with jax.named_scope("hvd_mlp"):
                y = JambaMLP(cfg, self.axis_name, name="mlp")(h)
            return add(x, y)


class Jamba(nn.Module):
    """``Jamba(cfg)(ids)``: float32 logits [B, S, rows held] (every one of
    them: for small sizes).  ``method="hidden"``: what the head reads, [B, S,
    d] after the final norm; ``method="head"``: the logits of some of its
    rows; ``method="loss"``: the next-token cross-entropy through the
    blocked head, no logits kept.  With ``axis_name`` the blocks sum over
    that mesh axis and the embedding is looked up across it; the head and
    the loss over a vocabulary split across chips are not built."""

    config: JambaConfig
    axis_name: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.rows_held, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        block = JambaBlock
        if cfg.checkpoint_blocks:
            kept = jax.checkpoint_policies.save_only_these_names(
                *CHECKPOINT_NAMES)
            block = nn.remat(JambaBlock, policy=kept)
        self.layers = [block(cfg, attention=cfg.is_attention(i),
                             axis_name=self.axis_name, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype)

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
        embedding transposed, the product in ``x``'s dtype."""
        self._one_chip_s_rows("head")
        with jax.named_scope("hvd_lm_head"):
            return jax.lax.dot_general(
                x, self.embed.embedding.astype(x.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    def __call__(self, ids):
        return self.head(self.hidden(ids))

    def loss(self, ids):
        """Mean over the ``B x (S - 1)`` predicting positions of the next
        token's negative log-likelihood over the rows held."""
        self._one_chip_s_rows("loss")
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

    def _one_chip_s_rows(self, what: str) -> None:
        if self.axis_name is not None:
            raise NotImplementedError(
                f"Jamba.{what} over a vocabulary split across "
                f"{self.axis_name!r}: the softmax's exchange is not built; "
                "the blocks (method='hidden') sum over the axis")


def lm_loss(model: Jamba, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S] through the blocked
    head."""
    return model.apply(variables, ids, method="loss")
