"""Phi-4-mini-flash (SambaY): a self-decoder of Mamba-1 and sliding-window
differential-attention layers, then a cross-decoder whose layers read **one
layer's keys and values and one scan's output** instead of making their own;
TPU-first flax.

The architecture of ``microsoft/Phi-4-mini-flash-reasoning`` (``model_type:
phi4flash``; arXiv:2507.06607 with the differential attention of
arXiv:2410.05258).  With ``x`` [S, d], ``l`` the **published** layer index,
``half = published_layers // 2``, LayerNorm with a learnt scale and bias at
``layer_norm_eps``, everything causal and **no positional encoding
anywhere**::

    block l:  x += mixer_l(LN(x));  x += W_2 (silu(g) * y), [g | y] = W_1 LN(x)
    after the last block the final LN and the head, the embedding transposed

    mixer_l, l even:  l <  half   Mamba-1
                      l == half   Mamba-1 that hands out its memory M
                      l >  half   a Gated Memory Unit on M
             l odd:   l <  half   differential attention under a band of
                                  ``sliding_window`` keys
                      l == half+1 differential attention over the whole causal
                                  context that hands out its k, v
                      l >  half+1 differential cross-attention onto that k, v

**Mamba** is ``models/jamba.py:MambaMixer`` as Mamba-1 publishes it
(``mamba_norms`` False: no norm on ``dt``, ``B``, ``C``); ``M = y``, the
scan's output with its ``D u`` term, before the output gate.

**Gated Memory Unit**: ``out = W_2 (M * silu(W_1 u))``, no bias, the gate in
float32; ``M`` is the same array for every such layer.

**Differential attention**, heads of ``head_dim`` paired by neighbours: query
pair p = heads (2p, 2p + 1) = ``(q1, q2)``, key/value pair r = heads (2r, 2r
+ 1) = ``(k1, k2)`` with ``V_r = [v_2r | v_2r+1]`` twice as wide; query pair
p reads pair ``p // (query pairs / key/value pairs)``::

    A1 = softmax(q1 k1^T / sqrt(head_dim) + mask) V     A2 likewise of q2, k2
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l)
    lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
    O = (1 - lambda_init(l)) x RMSNorm_2d(A1 - lambda A2)     one [2d] scale
    out = W_o [O_0 | O_1 | ..] + b_o          q, k, v carry biases too

**The two maps on the flash kernels**: each map is one grouped call of the
kernels at the value's width, q and k zero-padded from ``head_dim`` to ``2
head_dim`` lanes (at 64 the zeros ride in the half of the MXU a 64-wide head
leaves idle; PERF.md section 6, PR 60 has the other forms' readings), banded
(``window=``) below ``half``.  The maps leave the kernels in ``dtype``, as
every call's output does, and **the subtraction, the pair norm and the scale
are float32**: at the cell's size ``A1 - lambda A2`` of bfloat16 maps lies
0.31 to 0.34 % from the float32 reference's and of float32 maps 0.15 to 0.17
% (the kernels round p to bfloat16 before its product with V either way), so
a wider output buys nothing a check can read (PERF.md section 6, PR 60).
The lambdas, the padding, the subtraction, the pair norm and the scale are
the scope ``hvd_attn_diff``, the gate layer ``hvd_gmu``.

**The carry.**  A block takes and returns ``(x, M, (k, v))``; blocks before
the two sources pass None on.  With ``checkpoint_blocks`` each block is under
``jax.checkpoint`` and keeps ``CHECKPOINT_NAMES`` (``models/jamba.py``'s: the
paired projections' halves and both maps' outputs and row statistics); ``M``
and ``k, v`` are block outputs, so they are kept once whatever reads them,
and their cotangents are the sums over their readers.

**A share of a tensor-parallel layer**, as ``models/jamba.py``: ``*_held`` of
the Mamba and gate channels (a gate layer's channels are the memory's),
whole pairs of query and key/value heads, feed-forward columns and rows of
the embedding; ``axis_name`` sums over a mesh axis at the row-parallel points
(``x_proj`` inside the mixer, ``out_proj``, ``o_proj``, ``down``) and adds
``b_o`` once, after the sum; None computes this chip's part.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.embedding import embed_lookup
from ..ops.flash_attention import dense_attention, flash_attention
from ..parallel.tensor_parallel import vocab_parallel_embedding
from .flat_dense import FlatDenseGeneral
from .jamba import (
    CHECKPOINT_NAMES, EMBEDDING_STDDEV, JambaMLP, MambaMixer, RowParallel)
from .losses import tied_head_cross_entropy

MAMBA, MAMBA_MEMORY, GMU = "mamba", "mamba+memory", "gmu"
BANDED, FULL, CROSS = "banded", "full+kv", "cross"
LAMBDA_STDDEV = 0.1


def lambda_init(layer: int) -> float:
    """``0.8 - 0.6 exp(-0.3 l)`` at the published layer index ``l``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def lambda_of(lq1, lk1, lq2, lk2, start: float):
    """``exp(lq1 . lk1) - exp(lq2 . lk2) + start``: the second map's weight
    from a layer's four learnt vectors."""
    return (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
            + start)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32             # layers run, from first_layer on
    first_layer: int = 0             # the published index of the first
    published_layers: int = 32       # in the layer order and lambda_init
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    intermediate_size: int = 10240
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_d_state: int = 16
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_norms: bool = False        # Mamba-1 as published
    # What this chip holds of each layer's width; None: the whole.
    vocab_size_held: Optional[int] = None
    num_heads_held: Optional[int] = None
    num_kv_heads_held: Optional[int] = None
    intermediate_size_held: Optional[int] = None
    mamba_d_inner_held: Optional[int] = None
    checkpoint_blocks: bool = False  # jax.checkpoint around each block
    dtype: Any = jnp.bfloat16
    use_flash: bool = True           # Pallas kernels on TPU

    def __post_init__(self):
        heads, groups = self.heads_held, self.kv_heads_held
        if heads % 2 or groups % 2 or (heads // 2) % (groups // 2):
            raise ValueError(
                f"Phi4FlashConfig: {heads} query heads on {groups} key/value "
                "heads held: differential attention splits by pairs of "
                "neighbouring heads, a whole number of query pairs a "
                "key/value pair")
        kinds = self.layer_kinds
        for reader, source in ((GMU, MAMBA_MEMORY), (CROSS, FULL)):
            if reader in kinds and source not in kinds[:kinds.index(reader)]:
                raise ValueError(
                    f"Phi4FlashConfig: layers {self.first_layer} to "
                    f"{self.first_layer + self.num_layers - 1} hold a "
                    f"{reader!r} layer and not the {source!r} layer it reads")

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
    def kv_heads_held(self) -> int:
        return self.num_kv_heads_held or self.num_kv_heads

    @property
    def columns_held(self) -> int:
        return self.intermediate_size_held or self.intermediate_size

    @property
    def rows_held(self) -> int:
        return self.vocab_size_held or self.vocab_size

    def kind(self, layer: int) -> str:
        """The mixer of published layer ``layer``."""
        half = self.published_layers // 2
        if layer % 2 == 0:
            return (MAMBA if layer < half else MAMBA_MEMORY if layer == half
                    else GMU)
        return (BANDED if layer < half + 1 else FULL if layer == half + 1
                else CROSS)

    @property
    def layers(self) -> tuple:
        """The published indices of the layers run."""
        return tuple(range(self.first_layer,
                           self.first_layer + self.num_layers))

    @property
    def layer_kinds(self) -> tuple:
        return tuple(self.kind(i) for i in self.layers)


# The published sizes (config.json of microsoft/Phi-4-mini-flash-reasoning).
PHI4_MINI_FLASH = Phi4FlashConfig()
PHI4FLASH_TINY = Phi4FlashConfig(
    vocab_size=512, hidden_size=64, num_layers=8, published_layers=8,
    num_heads=8, num_kv_heads=4, head_dim=8, intermediate_size=96,
    sliding_window=8, mamba_d_state=8, mamba_dt_rank=8, dtype=jnp.float32,
    use_flash=False)


class LayerNorm(nn.Module):
    """``(x - mean) / sqrt(var + eps) * scale + bias`` over the last axis,
    the arithmetic in float32, the result in ``dtype``; under
    ``jax.checkpoint`` as ``models/sdar.py:RMSNorm`` is, for its reason."""
    eps: float
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))

        def norm(x, scale, bias):
            x = x.astype(jnp.float32)
            x = x - jnp.mean(x, axis=-1, keepdims=True)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                                  + self.eps)
            return (x * scale + bias).astype(self.dtype)

        return jax.checkpoint(norm)(x, scale, bias)


def two_maps(cfg: Phi4FlashConfig, q, k, v, window: Optional[int],
             interpret: Optional[bool] = None):
    """Both softmax maps of q [B, S, heads x d] on k, v [B, S, groups x d],
    ``(A1, A2)`` [B, S, pairs, 2 d] each, in q's dtype: map m takes the m-th
    head of every pair of q and of k, zero-padded to the value's ``2 d``
    lanes, on the pairs' values as they lie.  ``interpret``: ``flash_attention``'s
    (tests force the kernels through the Pallas interpreter with it)."""
    batch, seq = q.shape[:2]
    d = cfg.head_dim
    attend = (functools.partial(flash_attention, interpret=interpret)
              if cfg.use_flash else dense_attention)

    def halves(x):
        with jax.named_scope("hvd_attn_diff"):
            pairs = x.reshape(batch, seq, -1, 2, d)
            wide = jnp.pad(pairs, [(0, 0)] * 4 + [(0, d)])
            return wide[:, :, :, 0], wide[:, :, :, 1]

    values = v.reshape(batch, seq, -1, 2 * d)
    return tuple(
        attend(q_m, k_m, values, causal=True, window=window, scale=d ** -0.5)
        for q_m, k_m in zip(halves(q), halves(k)))


class DiffAttention(nn.Module):
    """Differential attention of published layer ``layer`` on ``u = LN(x)``,
    this chip's pairs.  ``kv``: the ``(k, v)`` of the layer this one reads
    (a cross layer; it then holds no key or value projection); None makes
    its own.  Returns ``(out, (k, v))``."""
    config: Phi4FlashConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, u, kv=None):
        cfg = self.config
        heads, groups, d = cfg.heads_held, cfg.kv_heads_held, cfg.head_dim
        start = lambda_init(self.layer)
        project = lambda n, name: FlatDenseGeneral(  # noqa: E731
            (n, d), dtype=cfg.dtype, name=name)
        with jax.named_scope("hvd_attn_proj"):
            q = project(heads, "q_proj")(u)
            if kv is None:
                kv = project(groups, "k_proj")(u), project(groups,
                                                           "v_proj")(u)
        vectors = [self.param(f"lambda_{name}",
                              nn.initializers.normal(LAMBDA_STDDEV), (d,))
                   for name in ("q1", "k1", "q2", "k2")]
        scale = self.param("pair_norm", nn.initializers.ones, (2 * d,))
        window = (cfg.sliding_window if cfg.kind(self.layer) == BANDED
                  else None)
        a1, a2 = two_maps(cfg, q, *kv, window)
        with jax.named_scope("hvd_attn_diff"):
            lam = lambda_of(*vectors, start)
            diff = a1.astype(jnp.float32) - lam * a2.astype(jnp.float32)
            # For whoever applies the layer with ``mutable=["intermediates"]``
            # (the benchmark holds the step's own maps and their difference
            # to the reference's through it); in a step nothing is kept.
            self.sow("intermediates", "maps", {
                "q": q, "k": kv[0], "v": kv[1], "a1": a1, "a2": a2,
                "difference": diff})
            normed = diff * jax.lax.rsqrt(
                jnp.mean(diff * diff, axis=-1, keepdims=True)
                + cfg.layer_norm_eps) * (scale * (1.0 - start))
            ctx = normed.astype(cfg.dtype).reshape(*u.shape[:2], heads * d)
        with jax.named_scope("hvd_attn_proj"):
            out = RowParallel(cfg.hidden_size, cfg.num_heads * d,
                              self.axis_name, cfg.dtype, name="o_proj")(ctx)
            # Added once, after the sum over the chips.
            out = out + self.param("o_proj_bias", nn.initializers.zeros,
                                   (cfg.hidden_size,)).astype(cfg.dtype)
        return out, kv


class GatedMemoryUnit(nn.Module):
    """``W_2 (M * silu(W_1 u))`` on this chip's channels of the memory ``M``
    [B, S, held]: two products round an elementwise gate."""
    config: Phi4FlashConfig
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.config
        gate = nn.Dense(cfg.channels_held, use_bias=False, dtype=cfg.dtype,
                        name="in_proj")(u)
        gated = (memory.astype(jnp.float32)
                 * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
        return RowParallel(cfg.hidden_size, cfg.d_inner, self.axis_name,
                           cfg.dtype, name="out_proj")(gated)


class Phi4FlashBlock(nn.Module):
    """Published layer ``layer``: ``(x, M, kv) -> (x, M, kv)``."""
    config: Phi4FlashConfig
    layer: int
    axis_name: Optional[str] = None

    @nn.compact
    def __call__(self, x, memory=None, kv=None):
        cfg = self.config
        kind = cfg.kind(self.layer)
        norm = lambda name: LayerNorm(cfg.layer_norm_eps, dtype=cfg.dtype,  # noqa: E731
                                      name=name)

        def add(x, y):
            return (x.astype(jnp.float32)
                    + y.astype(jnp.float32)).astype(cfg.dtype)

        with jax.named_scope("hvd_block"):
            u = norm("input_norm")(x)
            if kind == MAMBA:
                y = MambaMixer(cfg, self.axis_name, name="mamba")(u)
            elif kind == MAMBA_MEMORY:
                y, memory = MambaMixer(cfg, self.axis_name, memory=True,
                                       name="mamba")(u)
            elif kind == GMU:
                with jax.named_scope("hvd_gmu"):
                    y = GatedMemoryUnit(cfg, self.axis_name, name="gmu")(
                        u, memory)
            else:
                with jax.named_scope("hvd_attn"):
                    y, made = DiffAttention(
                        cfg, self.layer, self.axis_name, name="attn")(
                            u, kv if kind == CROSS else None)
                if kind == FULL:
                    kv = made
            x = add(x, y)
            u = norm("post_mixer_norm")(x)
            with jax.named_scope("hvd_mlp"):
                y = JambaMLP(cfg, self.axis_name, name="mlp")(u)
            return add(x, y), memory, kv


class Phi4Flash(nn.Module):
    """``Phi4Flash(cfg)(ids)``: float32 logits [B, S, rows held] (every one
    of them: for small sizes).  ``method="hidden"``: what the head reads, [B,
    S, d] after the final norm; ``method="head"``: the logits of some of its
    rows; ``method="loss"``: the next-token cross-entropy through the
    blocked head, no logits kept.  A block's parameters are ``layer_<its
    published index>``.  With ``axis_name`` the blocks sum over that mesh
    axis and the embedding is looked up across it; the head and the loss over
    a vocabulary split across chips are not built."""

    config: Phi4FlashConfig
    axis_name: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embed = nn.Embed(
            cfg.rows_held, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.initializers.normal(stddev=EMBEDDING_STDDEV))
        block = Phi4FlashBlock
        if cfg.checkpoint_blocks:
            kept = jax.checkpoint_policies.save_only_these_names(
                *CHECKPOINT_NAMES)
            block = nn.remat(Phi4FlashBlock, policy=kept)
        self.blocks = [block(cfg, i, self.axis_name, name=f"layer_{i}")
                       for i in cfg.layers]
        self.final_norm = LayerNorm(cfg.layer_norm_eps, dtype=cfg.dtype)

    def hidden(self, ids):
        with jax.named_scope("hvd_embed"):
            if self.axis_name is None:
                x = embed_lookup(self.embed.embedding, ids,
                                 self.config.dtype)
            else:
                x = vocab_parallel_embedding(
                    ids, self.embed.embedding.astype(self.config.dtype),
                    self.axis_name)
        memory = kv = None
        for block in self.blocks:
            x, memory, kv = block(x, memory, kv)
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
                f"Phi4Flash.{what} over a vocabulary split across "
                f"{self.axis_name!r}: the softmax's exchange is not built "
                "(ROADMAP Reach B9); the blocks (method='hidden') sum over "
                "the axis")


def lm_loss(model: Phi4Flash, variables, ids):
    """``model``'s next-token loss on ``ids`` [B, S] through the blocked
    head."""
    return model.apply(variables, ids, method="loss")
