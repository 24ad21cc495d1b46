"""MiniCPM-SALA: lightning linear-attention layers and block-selected softmax
attention layers by a published list, in MiniCPM's scaled frame; TPU-first
flax.

The architecture of ``openbmb/MiniCPM-SALA`` (``model_type: minicpm_sala``).
With ``x`` a token's ``hidden_size``-wide state, RMSNorm at eps 1e-6 with
learnt scales, no bias anywhere, everything causal::

    h0 = scale_emb x E[id]
    block l:  h += s x Mixer_l(RMSNorm(h));  h += s x MLP(RMSNorm(h))
              s = scale_depth / sqrt(published num_hidden_layers)
              MLP(u) = W_down(silu(W_gate u) * W_up u)
    logits = W_head (RMSNorm(h) / (hidden_size / dim_model_base)), untied

``Mixer_l`` is what ``mixer_types[l]`` names.

**``lightning-attn``** (Lightning Attention-2, arXiv:2401.04658)::

    q = rope(N(W_q u))   k = rope(N(W_k u))   v = W_v u      heads of 128
    o_t = sum_{s <= t} lambda_h^(t - s) (q_t . k_s / sqrt(128)) v_s
    y = W_o (N_o(o) * sigmoid(W_g u))

``N`` an RMSNorm a head over its lanes with a learnt scale (``qk_norm``),
``N_o`` the same on the output (``use_output_norm``), no softmax, as many
key/value heads as query heads, ``lambda_h = exp(-a_h)`` with the slopes
``a_h = 2^(-8 (h + 1) / heads) x (1 - l / (layers - 1) + 1e-5)`` for head h of
published layer l (:func:`lightning_slopes`), rotary positions in the
half-split pairing (lane i with lane i + 64).  The recurrence is
``ops/lightning_attention.py``.

**``minicpm4``** (InfLLM-V2, arXiv:2506.07900)::

    q = N(W_q u)   k = N(W_k u)   v = W_v u      H heads on G, no rotary
    y = W_o (softmax-attention(q, k, v, the keys t may see) * sigmoid(W_g u))

Up to ``dense_len`` tokens every query sees every key at or before it; in a
longer sequence the keys inside the ``topk`` blocks of ``block_size`` that
``ops/flash_select.py:sparse_select`` chooses for it (the first block and the
``window_size / block_size`` blocks up to its own always; the others by the
group's summed softmax over mean-pooled keys), every head of a group under
the same choice, which passes no gradient.  The length of the sequence
switches the form and nothing else does.

Everything between the projections and the kernels stays ``[B, S, heads x
128]``: the head norms and the rotary turn are one op on that layout
(``ops/qk_norm_rope.py``, SDAR's), the heads are a view at the kernels'
door.  Float32: parameters, norms, rotary angles, decay powers, the lightning
state between chunks, softmax statistics, the selection from its softmax on,
residual sums, logits and loss; ``dtype`` activations and matmul operands.

**A chip's share of a layer**, Megatron-style: ``lightning_heads_held``
lightning heads from ``first_lightning_head`` on (their columns of W_q, W_k,
W_v, W_g, rows of W_o, **their own slopes**), ``num_heads_held`` query heads
of a sparse layer on the ``num_kv_heads_held`` key/value heads they read,
``intermediate_size_held`` feed-forward columns (``gate_up`` one flat ``[d, 2
x held]`` leaf: ``models/jamba.py:PairedDense``), ``vocab_size_held`` rows of
the embedding and the head.  Every layer takes ``axis_name``: ``W_o`` and
``down`` sum over it (their kernels drawn at the whole layer's fan-in).  The
selection's sum over a group whose heads lie on several chips, and the head
and the loss over a vocabulary split across chips, are not built and raise by
name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import flash_select
from ..ops.embedding import embed_lookup
from ..ops.flash_attention import dense_attention, flash_attention
from ..ops.lightning_attention import (
    lightning_attention, lightning_attention_scan)
from ..ops.qk_norm_rope import dense_qk_norm_rope, qk_norm_rope
from ..parallel.tensor_parallel import vocab_parallel_embedding
from .jamba import PairedDense, RowParallel
from .laguna import EMBEDDING_STDDEV
from .losses import head_cross_entropy
from .sdar import RMSNorm

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# The published order of the 32 layers (config.json: ``mixer_types``).
MIXER_TYPES = tuple(SPARSE if i in (0, 9, 16, 17, 22, 29, 30, 31)
                    else LIGHTNING for i in range(32))


@dataclasses.dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    num_layers: int = 32             # layers run: the first of mixer_types
    published_layers: int = 32       # in s and in the slopes
    mixer_types: tuple = MIXER_TYPES
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    intermediate_size: int = 16384
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # MiniCPM4's sparse_config.
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # What this chip holds of each layer; None: the whole.
    vocab_size_held: Optional[int] = None
    num_heads_held: Optional[int] = None
    num_kv_heads_held: Optional[int] = None
    lightning_heads_held: Optional[int] = None
    first_lightning_head: int = 0
    intermediate_size_held: Optional[int] = None
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    def __post_init__(self):
        if len(self.mixer_types) < self.num_layers or any(
                m not in (LIGHTNING, SPARSE) for m in self.mixer_types):
            raise ValueError(
                f"SalaConfig: mixer_types names {len(self.mixer_types)} "
                f"layers of {set(self.mixer_types)} for {self.num_layers}; "
                f"each is {LIGHTNING!r} or {SPARSE!r}")
        if self.heads_held % self.kv_heads_held:
            raise ValueError(
                f"SalaConfig: {self.heads_held} query heads held on "
                f"{self.kv_heads_held} key/value heads")

    @property
    def heads_held(self) -> int:
        return self.num_heads_held or self.num_heads

    @property
    def kv_heads_held(self) -> int:
        return self.num_kv_heads_held or self.num_kv_heads

    @property
    def lightning_held(self) -> int:
        return self.lightning_heads_held or self.lightning_heads

    @property
    def columns_held(self) -> int:
        return self.intermediate_size_held or self.intermediate_size

    @property
    def rows_held(self) -> int:
        return self.vocab_size_held or self.vocab_size

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.published_layers ** 0.5

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @property
    def local_blocks(self) -> int:
        return self.sparse_window_size // self.sparse_block_size

    def selects(self, seq: int) -> bool:
        """Whether a sparse layer chooses blocks at this length."""
        return seq > self.sparse_dense_len

    @property
    def selection(self) -> dict:
        """``ops/flash_select.py:sparse_select``'s constants."""
        return {"kernel_size": self.sparse_kernel_size,
                "stride": self.sparse_kernel_stride,
                "block": self.sparse_block_size, "topk": self.sparse_topk,
                "init_blocks": self.sparse_init_blocks,
                "local_blocks": self.local_blocks}


# The published sizes (config.json of openbmb/MiniCPM-SALA), whole.
MINICPM_SALA = SalaConfig()
SALA_TINY = SalaConfig(
    vocab_size=512, hidden_size=64, num_layers=4, published_layers=8,
    mixer_types=(SPARSE, LIGHTNING, LIGHTNING, SPARSE), num_heads=4,
    num_kv_heads=2, head_dim=16, lightning_heads=4, lightning_head_dim=16,
    intermediate_size=96, dim_model_base=16, sparse_kernel_size=8,
    sparse_kernel_stride=4, sparse_block_size=8, sparse_topk=4,
    sparse_window_size=16, sparse_dense_len=32, dtype=jnp.float32,
    use_flash=False)


def lightning_slopes(cfg: SalaConfig, layer: int):
    """float32 [heads held]: ``a_h = 2^(-8 (h + 1) / heads) x (1 - layer /
    (layers - 1) + 1e-5)`` of the held heads of published layer ``layer``
    (the family's convention: ALiBi's geometric slopes a head, flattened with
    depth)."""
    h = cfg.first_lightning_head + jnp.arange(cfg.lightning_held,
                                              dtype=jnp.float32)
    depth = 1.0 - layer / max(cfg.published_layers - 1, 1) + 1e-5
    return 2.0 ** (-8.0 * (h + 1.0) / cfg.lightning_heads) * depth


def _dense(features: int, dtype, name: str):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class HeadNorm(nn.Module):
    """RMSNorm of each ``width``-lane head of ``x`` [B, S, heads x width] as
    it lies, one learnt ``scale`` [width] for all heads, and then, with
    ``theta``, the head turned by its position (half-split pairs); float32
    inside, ``dtype`` out.  q and k take ``ops/qk_norm_rope.py``'s op, one
    pass over the array each way on a TPU (``kernel``); **without ``theta``
    that op is given every position as 0, a turn by nothing**, which leaves
    the norm alone.  A lightning layer's output takes the plain form
    (``kernel`` False), which the compiler fuses with the gate after it."""
    width: int
    eps: float
    dtype: Any = jnp.bfloat16
    theta: Optional[float] = None
    kernel: bool = True

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (self.width,))
        if self.kernel or self.theta is not None:
            seq = x.shape[1]
            positions = (jnp.arange(seq) if self.theta is not None
                         else jnp.zeros((seq,), jnp.int32))
            op = qk_norm_rope if self.kernel else dense_qk_norm_rope
            return op(x.astype(self.dtype), scale, positions,
                      heads=x.shape[-1] // self.width, head_dim=self.width,
                      eps=self.eps, theta=self.theta or 1.0)

        def norm(x, scale):
            heads = x.astype(jnp.float32).reshape(*x.shape[:-1], -1,
                                                  self.width)
            heads = heads * jax.lax.rsqrt(
                jnp.mean(heads * heads, axis=-1, keepdims=True)
                + self.eps) * scale
            return heads.reshape(x.shape).astype(self.dtype)

        return jax.checkpoint(norm)(x, scale)


def lightning_mix(cfg: SalaConfig, q, k, v, slopes):
    """The lightning layers' mix of q, k, v [B, S, H, D] under ``slopes``:
    the kernels (``use_flash``; on a TPU) or the scan form."""
    mix = lightning_attention if cfg.use_flash else lightning_attention_scan
    return mix(q, k, v, slopes, cfg.lightning_head_dim ** -0.5)


def sparse_mix(cfg: SalaConfig, q, k, v, chosen=None):
    """The sparse layers' attention of q [B, S, H, D] on k, v [B, S, G, D]:
    over the key blocks the bits ``chosen`` name, or (None) over every key at
    or before the query."""
    scale = cfg.head_dim ** -0.5
    if chosen is None:
        attend = flash_attention if cfg.use_flash else dense_attention
        return attend(q, k, v, causal=True, scale=scale)
    select = flash_select.Selection(chosen, cfg.sparse_block_size)
    walk = (flash_select.flash_select if cfg.use_flash
            else flash_select.dense_select)
    return walk(q, k, v, select, scale)[0]


def _gated(ctx, gate, dtype):
    """``ctx * sigmoid(gate)``, float32 inside."""
    return (ctx.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)


class LightningAttention(nn.Module):
    """A ``lightning-attn`` mixer on ``u = RMSNorm(h)``, this chip's heads
    with the slopes of published layer ``layer``."""
    config: SalaConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        heads, width = cfg.lightning_held, cfg.lightning_head_dim
        norm = lambda name, **kw: HeadNorm(  # noqa: E731
            width, cfg.rms_norm_eps, cfg.dtype, name=name, **kw)
        with jax.named_scope("hvd_attn_proj"):
            q = _dense(heads * width, cfg.dtype, "q_proj")(u)
            k = _dense(heads * width, cfg.dtype, "k_proj")(u)
            v = _dense(heads * width, cfg.dtype, "v_proj")(u)
            gate = _dense(heads * width, cfg.dtype, "gate_proj")(u)
        with jax.named_scope("hvd_lightning_prep"):
            turned = dict(theta=cfg.rope_theta, kernel=cfg.use_flash)
            q, k = norm("q_norm", **turned)(q), norm("k_norm", **turned)(k)
            slopes = lightning_slopes(cfg, self.layer)
        by_head = lambda t: t.reshape(*t.shape[:2], heads, width)  # noqa: E731
        ctx = lightning_mix(cfg, by_head(q), by_head(k), by_head(v),
                            slopes).reshape(q.shape)
        self.sow("intermediates", "attention",
                 {"q": q, "k": k, "v": v, "ctx": ctx})
        with jax.named_scope("hvd_attn_gate"):
            ctx = _gated(norm("o_norm", kernel=False)(ctx), gate, cfg.dtype)
        with jax.named_scope("hvd_attn_proj"):
            return RowParallel(
                cfg.hidden_size, cfg.lightning_heads * width, self.axis_name,
                cfg.dtype, name="o_proj")(ctx)


class SparseAttention(nn.Module):
    """A ``minicpm4`` mixer on ``u = RMSNorm(h)``, this chip's query heads on
    the key/value heads they read.  ``chosen`` (int32 bits, ``ops/
    flash_select.py:Selection``'s layout) stands in for the layer's own
    selection where a caller has one (a reference's, another share's)."""
    config: SalaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, u, chosen=None):
        cfg = self.config
        heads, groups, width = cfg.heads_held, cfg.kv_heads_held, cfg.head_dim
        seq = u.shape[1]
        norm = lambda name: HeadNorm(  # noqa: E731
            width, cfg.rms_norm_eps, cfg.dtype, kernel=cfg.use_flash,
            name=name)
        with jax.named_scope("hvd_attn_proj"):
            q = _dense(heads * width, cfg.dtype, "q_proj")(u)
            k = _dense(groups * width, cfg.dtype, "k_proj")(u)
            v = _dense(groups * width, cfg.dtype, "v_proj")(u)
            gate = _dense(heads * width, cfg.dtype, "gate_proj")(u)
        with jax.named_scope("hvd_qk_norm"):
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        by_head = lambda t, n: t.reshape(*t.shape[:2], n, width)  # noqa: E731
        q4, k4, v4 = by_head(q, heads), by_head(k, groups), by_head(v, groups)
        if cfg.selects(seq):
            if chosen is None:
                if self.axis_name is not None and (
                        heads * cfg.num_kv_heads < cfg.num_heads * groups):
                    raise NotImplementedError(
                        f"SparseAttention over {self.axis_name!r}: a "
                        "key/value head's group lies on several chips and "
                        "the selection's sum over the group's heads is not "
                        "exchanged (ROADMAP Reach B11)")
                chosen = flash_select.sparse_select(
                    q4, k4, scale=width ** -0.5, **cfg.selection).bits
            self.sow("intermediates", "chosen", chosen)
        else:
            chosen = None
        ctx = sparse_mix(cfg, q4, k4, v4, chosen)
        ctx = ctx.reshape(q.shape)
        self.sow("intermediates", "attention",
                 {"q": q, "k": k, "v": v, "ctx": ctx})
        with jax.named_scope("hvd_attn_gate"):
            ctx = _gated(ctx, gate, cfg.dtype)
        with jax.named_scope("hvd_attn_proj"):
            return RowParallel(
                cfg.hidden_size, cfg.num_heads * width, self.axis_name,
                cfg.dtype, name="o_proj")(ctx)


class SalaMLP(nn.Module):
    """The SwiGLU, this chip's columns (``models/jamba.py:JambaMLP``'s
    form: the product an array of its own)."""
    config: SalaConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, u):
        cfg = self.config
        gate, up = PairedDense(cfg.columns_held, cfg.dtype,
                               name="gate_up")(u)
        hidden = jax.lax.optimization_barrier(jax.nn.silu(gate) * up)
        return RowParallel(cfg.hidden_size, cfg.intermediate_size,
                           self.axis_name, cfg.dtype, name="down")(hidden)


class SalaBlock(nn.Module):
    config: SalaConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, chosen=None):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            cfg.rms_norm_eps, dtype=cfg.dtype, name=name)

        def add(x, y):
            return (x.astype(jnp.float32) + cfg.residual_scale
                    * y.astype(jnp.float32)).astype(cfg.dtype)

        with jax.named_scope("hvd_block"):
            u = norm("input_norm")(x)
            with jax.named_scope("hvd_attn"):
                if cfg.mixer_types[self.layer] == SPARSE:
                    y = SparseAttention(cfg, self.axis_name, name="attn")(
                        u, chosen)
                else:
                    y = LightningAttention(cfg, self.layer, self.axis_name,
                                           name="attn")(u)
            x = add(x, y)
            u = norm("post_attn_norm")(x)
            with jax.named_scope("hvd_mlp"):
                y = SalaMLP(cfg, self.axis_name, name="mlp")(u)
            return add(x, y)


class Sala(nn.Module):
    """``Sala(cfg)(ids)``: float32 logits [B, S, rows held].
    ``method="hidden"``: what the head's product reads, [B, S, d] after the
    final norm and the division; ``method="head"``: the logits of some of its
    rows; ``method="loss"``: the next-token cross-entropy over every position
    but the last.  ``chosen`` ({layer number: bits}) stands in for the sparse
    layers' own selections.  With ``axis_name`` the blocks sum over that mesh
    axis and the embedding is looked up across it."""

    config: SalaConfig
    axis_name: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.rows_held, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        self.layers = [SalaBlock(cfg, i, self.axis_name, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.final_norm = RMSNorm(cfg.rms_norm_eps, dtype=cfg.dtype)
        self.lm_head = self.param(
            "lm_head", nn.initializers.lecun_normal(),
            (cfg.hidden_size, cfg.rows_held))

    def hidden(self, ids, chosen=None):
        cfg = self.config
        with jax.named_scope("hvd_embed"):
            if self.axis_name is None:
                x = embed_lookup(self.embed.embedding, ids, cfg.dtype)
            else:
                x = vocab_parallel_embedding(
                    ids, self.embed.embedding.astype(cfg.dtype),
                    self.axis_name)
            x = (x.astype(jnp.float32) * cfg.scale_emb).astype(cfg.dtype)
        for i, layer in enumerate(self.layers):
            x = layer(x, (chosen or {}).get(i))
        with jax.named_scope("hvd_lm_head"):
            return self.final_norm(x, lambda y: y / cfg.logit_divisor)

    def head(self, x):
        """Float32 logits of rows ``x`` [..., d] of :meth:`hidden`."""
        self._one_chip_s_rows("head")
        with jax.named_scope("hvd_lm_head"):
            return jnp.dot(x, self.lm_head.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    def __call__(self, ids, chosen=None):
        return self.head(self.hidden(ids, chosen))

    def loss(self, ids, chosen=None):
        """Mean over the ``B x (S - 1)`` predicting positions of the next
        token's negative log-likelihood over the rows held, through the
        blocked head (``losses.head_cross_entropy``)."""
        self._one_chip_s_rows("loss")
        x = self.hidden(ids, chosen)
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
                f"Sala.{what} over a vocabulary split across "
                f"{self.axis_name!r}: the softmax's exchange is not built "
                "(ROADMAP Reach B9); the blocks (method='hidden') sum over "
                "the axis")


def lm_loss(model: Sala, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S]."""
    return model.apply(variables, ids, method="loss")
