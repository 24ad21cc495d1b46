"""The plain reference of family ``sala``: MiniCPM-SALA's decoder (lightning
linear-attention layers and block-selected softmax-attention layers by a
published list, in MiniCPM's scaled frame), written out in ``jax.numpy`` in
float32.  Nothing of the program is imported: the layer equations are ISSUE
58's, restated here.

Sizes come with ``cfg`` (a dict: ``rms_norm_eps``, ``rope_theta``,
``scale_emb``, ``scale_depth``, ``published_layers``, ``dim_model_base``,
``mixer_types``, ``lightning_heads``, ``first_lightning_head``, and the
selection's ``kernel_size``, ``kernel_stride``, ``block_size``, ``topk``,
``init_blocks``, ``window_size``, ``dense_len``) and with the parameters'
shapes (a head is as wide as a head norm's scale), so the whole model and one
chip's share of a layer (sliced weights: its heads, columns, rows) run alike.
The parameters are a tree of plain arrays; one sequence at a time, ``x`` [S,
d].

- Frame: ``h0 = scale_emb E[id]``; block l: ``h += s Mixer_l(RMSNorm(h))``,
  ``h += s MLP(RMSNorm(h))``, ``s = scale_depth / sqrt(published_layers)``,
  ``MLP(u) = W_down(silu(W_gate u) * W_up u)``; ``logits = W_head
  (RMSNorm(h) / (d / dim_model_base))``.
- ``lightning-attn``: ``q = rope(N(W_q u))``, ``k = rope(N(W_k u))``, ``v =
  W_v u``, heads as wide as ``N``'s scale; ``o_t = sum_{s <= t} exp(-a_h (t -
  s)) (q_t . k_s / sqrt(D)) v_s`` as the quadratic form ``((Q K^T) * D) V`` a
  block of queries at a time; ``y = W_o (N_o(o) * sigmoid(W_g u))``; ``a_h =
  2^(-8 (h + 1) / lightning_heads) (1 - l / (published_layers - 1) + 1e-5)``;
  rotary pairs lane i with lane i + D / 2.
- ``minicpm4``: ``q = N(W_q u)``, ``k = N(W_k u)``, ``v = W_v u``, H query
  heads on G key/value heads, no rotary; softmax attention at ``D^-1/2`` over
  the keys a query may see, a masked dense softmax a block of queries at a
  time; ``y = W_o (o * sigmoid(W_g u))``.  A sequence longer than
  ``dense_len`` chooses (:func:`block_scores`, :func:`choice`): compressed
  keys ``mean(k[stride j : stride j + kernel_size])``; per query and head the
  softmax over the compressed keys that end at or before it; summed over the
  group's heads; a block's score the largest of the compressed keys that
  overlap its tokens; the first ``init_blocks`` blocks and the ``window_size
  / block_size`` up to the query's own always, the highest-scoring others up
  to ``topk`` in all; the keys at or before the query inside chosen blocks
  are seen, every head of a group under the same choice.
- Loss: mean over the S - 1 predicting positions of the next token's negative
  log-likelihood.

Everything that grows with the square of the sequence runs a block of queries
at a time (``lax.map``) under ``jax.checkpoint``, and the head in blocks of
rows, so that 16,384 positions fit beside the program's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_CHUNK = 256
SELECT_CHUNK = 128
HEAD_ROWS = 1024
NEG = -1e30
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, gate_up, down):
    """``gate_up`` [d, 2 x columns], columns ``[gate | up]``."""
    columns = gate_up.shape[1] // 2
    return (silu(x @ gate_up[:, :columns]) * (x @ gate_up[:, columns:])) @ down


def by_head(x, width: int):
    return x.reshape(x.shape[0], -1, width)


def rotary(x, theta: float):
    """x [S, H, D]: lane i turns with lane i + D / 2 by ``t theta^(-2i /
    D)``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def slopes(cfg: dict, layer: int, held: int):
    """[held]: ``a_h`` of the held heads of published layer ``layer``."""
    h = cfg["first_lightning_head"] + jnp.arange(held, dtype=jnp.float32)
    depth = 1.0 - layer / max(cfg["published_layers"] - 1, 1) + 1e-5
    return 2.0 ** (-8.0 * (h + 1.0) / cfg["lightning_heads"]) * depth


def lightning_scale(width: int) -> float:
    return width ** -0.5


def lightning(q, k, v, a):
    """q, k, v [S, H, D], a [H] -> [S, H, D]: ``((Q K^T) * D) V`` with ``D_ts
    = exp(-a (t - s))`` at and under the diagonal, scores over ``sqrt(D)``."""
    seq, _, width = q.shape
    keys = jnp.arange(seq)
    size = math.gcd(seq, QUERY_CHUNK)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        gap = (first_row + jnp.arange(size))[:, None] - keys[None, :]
        decay = jnp.where(gap >= 0, jnp.exp(
            -a[:, None, None] * jnp.maximum(gap, 0).astype(jnp.float32)), 0.0)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * lightning_scale(width) * decay
        return jnp.einsum("hqk,khd->qhd", s, v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(q.shape)


def head_norm(x, scale, eps):
    """``qk_norm``: an RMSNorm a head over its lanes, x [S, H, D]."""
    return rms_norm(x, scale, eps)


def output_norm(o, scale, eps):
    """``use_output_norm``: a lightning layer's output, a head at a time."""
    return rms_norm(o, scale, eps)


def gated(o, logits):
    """The output gate: ``o * sigmoid(W_g u)``."""
    return o * jax.nn.sigmoid(logits)


def positions(kind: str, x, cfg: dict):
    """Rotary positions on a lightning layer's q and k, none on a sparse
    layer's."""
    return rotary(x, cfg["rope_theta"]) if kind == LIGHTNING else x


def lightning_attn(p, u, cfg: dict, layer: int):
    width = p["q_norm"].shape[0]
    eps = cfg["rms_norm_eps"]
    q = positions(LIGHTNING, head_norm(by_head(u @ p["q"], width),
                                       p["q_norm"], eps), cfg)
    k = positions(LIGHTNING, head_norm(by_head(u @ p["k"], width),
                                       p["k_norm"], eps), cfg)
    v = by_head(u @ p["v"], width)
    o = lightning(q, k, v, slopes(cfg, layer, q.shape[1]))
    o = output_norm(o, p["o_norm"], eps).reshape(u.shape[0], -1)
    return gated(o, u @ p["gate"]) @ p["o"]


def compressed_keys(k, cfg: dict):
    """k [S, G, D] -> [n, G, D]: the mean of ``kernel_size`` keys every
    ``kernel_stride``."""
    size, stride = cfg["kernel_size"], cfg["kernel_stride"]
    n = (k.shape[0] - size) // stride + 1
    rows = stride * jnp.arange(n)[:, None] + jnp.arange(size)[None, :]
    return jnp.mean(k[rows], axis=1)


def over_the_group(p):
    """Step 3: ``p`` [G, R, T, n] summed over the R query heads of each
    key/value head."""
    return jnp.sum(p, axis=1)


def block_scores(q, k, cfg: dict):
    """q [S, H, D], k [S, G, D] -> [G, S, blocks]: every block's score for
    every query, steps 1 to 4."""
    seq, heads, width = q.shape
    groups = k.shape[1]
    size, block = cfg["kernel_size"], cfg["block_size"]
    kc = compressed_keys(k, cfg)
    n, blocks = kc.shape[0], seq // block
    starts = cfg["kernel_stride"] * jnp.arange(n)
    # compressed key j overlaps block b where their tokens meet
    first = block * jnp.arange(blocks)[:, None]
    overlap = (starts[None, :] < first + block) & (starts[None, :] + size
                                                   > first)    # [blocks, n]
    q = q.reshape(seq, groups, heads // groups, width)
    chunk_rows = math.gcd(seq, SELECT_CHUNK)

    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, chunk_rows)
        t = first_row + jnp.arange(chunk_rows)
        visible = (starts + size - 1)[None, :] <= t[:, None]   # [T, n]
        s = jnp.einsum("tgrd,ngd->grtn", qc, kc) * width ** -0.5
        s = jnp.where(visible, s, -jnp.inf)
        top = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.where(visible, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        summed = over_the_group(p)                             # [G, T, n]
        return jnp.max(jnp.where(overlap, summed[:, :, None, :], 0.0),
                       axis=-1)                                # [G, T, blocks]

    out = jax.lax.map(chunk, jnp.arange(0, seq, chunk_rows))
    return jnp.moveaxis(out, 0, 1).reshape(groups, seq, blocks)


def forced(seq: int, cfg: dict):
    """bool [S, blocks]: the blocks step 5 always chooses, the first ones and
    the ``window_size / block_size`` up to the query's own."""
    block = cfg["block_size"]
    own = (jnp.arange(seq) // block)[:, None]
    blk = jnp.arange(seq // block)[None, :]
    local = cfg["window_size"] // block
    return (blk < cfg["init_blocks"]) | ((blk > own - local) & (blk <= own))


def choice(scores, cfg: dict):
    """[G, S, blocks] scores -> bool [G, S, blocks], step 5."""
    seq, blocks = scores.shape[1:]
    own = (jnp.arange(seq) // cfg["block_size"])[:, None]
    seen = jnp.arange(blocks)[None, :] <= own
    key = jnp.where(seen, jnp.where(forced(seq, cfg), jnp.inf, scores), -1.0)
    picked = jax.lax.top_k(key, min(cfg["topk"], blocks))[1]
    chosen = jnp.zeros(scores.shape, bool)
    chosen = jax.vmap(jax.vmap(lambda c, i: c.at[i].set(True)))(chosen, picked)
    return chosen & seen


def selects(seq: int, cfg: dict) -> bool:
    return seq > cfg["dense_len"]


def attention(q, k, v, chosen, block: int):
    """q [S, H, D], k, v [S, G, D] -> [S, H, D]: causal softmax attention, a
    block of queries at a time; ``chosen`` bool [G, S, blocks] (None: every
    block) says which key blocks each query sees."""
    seq, heads, width = q.shape
    groups = k.shape[1]
    keys = jnp.arange(seq)
    size = math.gcd(seq, QUERY_CHUNK)
    q = q.reshape(seq, groups, heads // groups, width)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        rows = first_row + jnp.arange(size)
        sees = keys[None, :] <= rows[:, None]                  # [T, S]
        if chosen is not None:
            mine = jax.lax.dynamic_slice_in_dim(chosen, first_row, size, 1)
            sees = sees[None] & jnp.repeat(mine, block, axis=-1)[..., :seq]
        else:
            sees = sees[None]
        s = jnp.einsum("tgrd,kgd->grtk", qc, k) * width ** -0.5
        s = jnp.where(sees[:, None], s, NEG)
        return jnp.einsum("grtk,kgd->tgrd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(
        seq, heads, width)


def sparse_attn(p, u, cfg: dict, chosen=None):
    """``(y, seen)``: the layer's result and, where the sequence chooses, its
    q and k, the block scores and the choice made (``chosen`` given: the
    choice taken)."""
    width = p["q_norm"].shape[0]
    eps = cfg["rms_norm_eps"]
    q = positions(SPARSE, head_norm(by_head(u @ p["q"], width), p["q_norm"],
                                    eps), cfg)
    k = positions(SPARSE, head_norm(by_head(u @ p["k"], width), p["k_norm"],
                                    eps), cfg)
    v = by_head(u @ p["v"], width)
    seen = None
    if selects(u.shape[0], cfg):
        scores = jax.lax.stop_gradient(block_scores(q, k, cfg))
        own = choice(scores, cfg)
        chosen = own if chosen is None else chosen
        seen = {"q": q, "k": k, "scores": scores, "chosen": own,
                "taken": chosen}
    o = attention(q, k, v, chosen if seen else None, cfg["block_size"])
    o = o.reshape(u.shape[0], -1)
    return gated(o, u @ p["gate"]) @ p["o"], seen


def residual_scale(cfg: dict) -> float:
    return cfg["scale_depth"] / cfg["published_layers"] ** 0.5


def block(p, x, cfg: dict, layer: int, chosen=None):
    """One block on x [S, d]: ``(y, seen)``."""
    s, eps = residual_scale(cfg), cfg["rms_norm_eps"]
    u = rms_norm(x, p["input_norm"], eps)
    if cfg["mixer_types"][layer] == SPARSE:
        y, seen = sparse_attn(p["attn"], u, cfg, chosen)
    else:
        y, seen = lightning_attn(p["attn"], u, cfg, layer), None
    x = x + s * y
    u = rms_norm(x, p["post_attn_norm"], eps)
    return x + s * swiglu(u, p["mlp"]["gate_up"], p["mlp"]["down"]), seen


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def embed(params, ids, cfg: dict):
    return cfg["scale_emb"] * params["embed"][ids]


def hidden(params, ids, cfg: dict, chosen=None):
    """One sequence: ids [S] -> what the head's product reads [S, d] (after
    the final norm and the division), and per layer what a sparse layer
    scored and chose (None on the others).  ``chosen`` {layer: bool [G, S,
    blocks]}: the blocks each query takes; None: the layers' own."""
    x = embed(params, ids, cfg)
    seen = []
    for i in range(layers_of(params)):
        x, s = jax.checkpoint(
            lambda p, x, c, i=i: block(p, x, cfg, i, c))(
                params[f"layer_{i}"], x, (chosen or {}).get(i))
        seen.append(s)
    return final_norm(params, x, cfg), seen


def logit_divisor(width: int, cfg: dict) -> float:
    return width / cfg["dim_model_base"]


def final_norm(params, x, cfg: dict):
    return rms_norm(x, params["final_norm"],
                    cfg["rms_norm_eps"]) / logit_divisor(x.shape[-1], cfg)


def head(params, x):
    """The untied head on rows [.., d]."""
    return x @ params["lm_head"]


def next_tokens(ids):
    """What each row but the last is to predict: the token after it."""
    return ids[1:]


def next_token_nll(params, x, ids):
    """[S - 1]: row t's negative log-likelihood of token t + 1, the head a
    block of rows at a time (zero rows fill the last block and are cut off
    again)."""
    rows = x.shape[0] - 1
    size = min(HEAD_ROWS, rows)
    pad = -rows % size
    x = jnp.concatenate([x[:-1], jnp.zeros((pad, x.shape[1]), x.dtype)])
    labels = jnp.concatenate([next_tokens(ids),
                              jnp.zeros((pad,), ids.dtype)])

    @jax.checkpoint
    def part(args):
        xb, lb = args
        logp = jax.nn.log_softmax(head(params, xb), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(part, (x.reshape(-1, size, x.shape[1]),
                             labels.reshape(-1, size)))
    return nll.reshape(-1)[:rows]


def loss_sum(params, x, ids):
    """One sequence's sum of next-token negative log-likelihoods, before
    the division by ``sequences x (S - 1)``."""
    return jnp.sum(next_token_nll(params, x, ids))
