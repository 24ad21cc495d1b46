"""The plain reference of family ``joyai``: JoyAI-LLM-Flash's decoder
(multi-head latent attention, experts chosen by bias-corrected sigmoid scores
beside a shared one), written out in ``jax.numpy`` in float32.  Nothing of the
program is imported: the layer equations are ISSUE 54's, restated here.

Sizes come with ``cfg`` (a dict: ``rms_norm_eps``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta``,
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
``first_expert``) and with the parameters' shapes, so the whole model and one
chip's share of a layer (sliced weights: its heads, columns, experts, rows)
run alike.  The parameters are a tree of plain arrays **in the published
layout**: ``q_b`` [q_lora_rank, H x 192], a head ``[nope 128 | rope 64]``,
``kv_a`` [d, kv_lora_rank + 64], ``kv_b`` [kv_lora_rank, H x 256], a head
``[k_nope 128 | v 128]``, the rotary lanes paired ``(2i, 2i + 1)``; the
program stores other column orders and ``families/joyai.py:published`` maps
its tree to this one.  A block's feed-forward is the mixture where it holds
``moe`` and the dense SwiGLU where it holds ``mlp``.  One sequence at a time,
``x`` [S, d].

- Block l: ``h = x + Attn(RMSNorm(x))``, ``y = h + FF_l(RMSNorm(h))``; after
  the last block the final RMSNorm and the untied head.
- Attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` [S, H, 192]; ``[c |
  r] = x W_kva``, ``c_kv = RMSNorm(c)``, ``k_rope = rope(r)`` one key for all
  heads; ``[k_nope | v] = c_kv W_kvb``; per head the literal concatenations
  ``q = [q_nope | rope(q_rope)]`` and ``k = [k_nope | k_rope]``, one 192-wide
  dot, ``/ sqrt(192)``, causal softmax, ``P v``, then ``W_o``.  Rotary:
  ``inv_freq_i = theta^(-2i / 64)``, lanes ``(2i, 2i + 1)`` a pair.
- Mixture: ``s = sigmoid(x W_r)`` over every expert; the choice is the top-k
  of ``s + b``; the weights are ``s`` at the chosen divided by their sum;
  ``scale x sum_e w_e E_e(x) + E_shared(x)``, each ``E`` a SwiGLU.
- Loss: mean over the S - 1 predicting positions of the next token's
  negative log-likelihood.

Attention runs in chunks of queries, the experts one at a time over every row
(``lax.map``: the rows not routed to an expert weigh zero) and the head in
blocks of rows, each block under ``jax.checkpoint``, so that 16,384 positions
fit beside the program's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_CHUNK = 512
HEAD_ROWS = 1024
NEG = -1e30


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, gate_up, down):
    """``down(silu(gate x) * up x)`` of a kernel pair stored ``[d, 2 f]``,
    columns ``[gate | up]``."""
    f = down.shape[0]
    gu = x @ gate_up
    return (silu(gu[:, :f]) * gu[:, f:]) @ down


def rotary(x, theta: float):
    """x [S, ..., D]: lanes ``(2i, 2i + 1)`` turned by ``position x theta^(-2i
    / D)``, the published interleaved pairing."""
    width = x.shape[-1]
    freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape(x.shape[0], *([1] * (x.ndim - 2)), width // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def rope_of_the_key(r, cfg: dict):
    """The one rotary key [S, 1, 64] of the latent projection's last lanes."""
    return rotary(r[:, None, :], cfg["rope_theta"])


def rope_of_the_nope(q_nope, cfg: dict):
    """What the 128 lanes without positions get: nothing."""
    return q_nope


def score_scale(cfg: dict) -> float:
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def attention(q, k, v, scale: float):
    """q, k [S, H, 192], v [S, H, 128] -> [S, H, 128], causal, in chunks of
    queries."""
    seq = q.shape[0]
    keys = jnp.arange(seq)
    size = math.gcd(seq, QUERY_CHUNK)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        rows = (first_row + jnp.arange(size))[:, None]
        s = jnp.where((keys[None, :] <= rows)[None], s, NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(
        seq, *v.shape[1:])


def kv_latent(p, c, cfg: dict):
    """``c_kv``: the key/value latent under its own norm."""
    return rms_norm(c, p["kv_a_norm"], cfg["rms_norm_eps"])


def attn(p, h, cfg: dict):
    seq = h.shape[0]
    nope, rope, wide = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    c_q = rms_norm(h @ p["q_a"], p["q_a_norm"], cfg["rms_norm_eps"])
    q = (c_q @ p["q_b"]).reshape(seq, -1, nope + rope)
    kv = h @ p["kv_a"]
    k_rope = rope_of_the_key(kv[:, rank:], cfg)
    kv_b = (kv_latent(p, kv[:, :rank], cfg) @ p["kv_b"]).reshape(
        seq, -1, nope + wide)
    q = jnp.concatenate([rope_of_the_nope(q[..., :nope], cfg),
                         rotary(q[..., nope:], cfg["rope_theta"])], axis=-1)
    k = jnp.concatenate([
        rope_of_the_nope(kv_b[..., :nope], cfg),
        jnp.broadcast_to(k_rope, (seq, q.shape[1], rope))], axis=-1)
    out = attention(q, k, kv_b[..., nope:], score_scale(cfg))
    return out.reshape(seq, -1) @ p["o_proj"]


def router_scores(p, x):
    return jax.nn.sigmoid(x @ p["router"])


def choice_scores(p, scores):
    """What the top-k is taken of: the scores and the balancing bias."""
    return scores + jax.lax.stop_gradient(p["bias"])


def weight_scores(p, scores):
    """What the chosen experts are weighed by: the scores without the
    bias."""
    return scores


def top_k_weights(scores, chosen, renormalize: bool):
    """The weights of the ``chosen`` experts [T, k] in each token's sum."""
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) if renormalize else w


def held_experts(cfg: dict, held: int):
    """The numbers of the experts whose kernels the tree holds."""
    return cfg["first_expert"] + jnp.arange(held)


def routed(p, x, cfg: dict, chosen=None):
    """x [T, d] -> ``(y, scores, chosen)``: the held experts' part of each
    token's weighted sum, an expert at a time over every row."""
    scores = router_scores(p, x)
    if chosen is None:
        chosen = jax.lax.top_k(choice_scores(p, scores),
                               cfg["num_experts_per_tok"])[1]
    weights = top_k_weights(weight_scores(p, scores), chosen,
                            cfg["norm_topk_prob"])

    @jax.checkpoint
    def expert(args):
        e, w_gate, w_up, w_down = args
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return mine[:, None] * ((silu(x @ w_gate) * (x @ w_up)) @ w_down)

    parts = jax.lax.map(expert, (
        held_experts(cfg, p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return jnp.sum(parts, axis=0), scores, chosen


def routed_scale(cfg: dict) -> float:
    return cfg["routed_scaling_factor"]


def shared(p, x):
    """The expert every token takes, ungated."""
    return swiglu(x, p["shared_gate_up"], p["shared_down"])


def moe(p, x, cfg: dict, chosen=None):
    y, scores, chosen = routed(p, x, cfg, chosen)
    return routed_scale(cfg) * y + shared(p, x), scores, chosen


def block(p, x, cfg: dict, chosen=None):
    """``(y, seen)``: ``seen`` is what an expert layer's router saw, its
    scores and its choices; None on a dense layer."""
    eps = cfg["rms_norm_eps"]
    x = x + attn(p["attn"], rms_norm(x, p["input_norm"], eps), cfg)
    h = rms_norm(x, p["post_attn_norm"], eps)
    if "mlp" in p:
        return x + swiglu(h, p["mlp"]["gate_up"], p["mlp"]["down"]), None
    y, scores, chosen = moe(p["moe"], h, cfg, chosen)
    return x + y, {"routed": h, "scores": scores, "chosen": chosen}


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def hidden(params, ids, cfg: dict, chosen=None):
    """One sequence: ids [S] -> what the head reads [S, d], after the final
    norm, and per layer what its router saw and chose (None on a dense
    layer).  ``chosen`` [layers, S, k]: the experts each token takes, layer
    by layer (a dense layer's row is not read); None: the routers' own."""
    x = params["embed"][ids]
    seen = []
    for i in range(layers_of(params)):
        x, routed_ = jax.checkpoint(lambda p, x, c: block(p, x, cfg, c))(
            params[f"layer_{i}"], x, None if chosen is None else chosen[i])
        seen.append(routed_)
    return final_norm(params, x, cfg), seen


def final_norm(params, x, cfg: dict):
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


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
