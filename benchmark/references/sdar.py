"""The plain reference of family ``sdar``: a Qwen3-MoE decoder trained by
block diffusion (SDAR, arXiv:2510.06303; BD3-LM, arXiv:2503.09573), written
out in ``jax.numpy`` in float32.  Nothing of the program is imported: the
layer equations are ISSUE 34's, restated here.

Sizes come with ``cfg`` (a dict: ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``num_experts_per_tok``,
``norm_topk_prob``, ``rms_norm_eps``, ``rope_theta``, ``block_length``,
``first_expert``) and with the parameters' shapes; the parameters are the
flax tree of ``horovod_tpu.models.SDAR`` (``tree["params"]``).

- Input: ``[x0 ; xt]``, 2L positions; both copies carry positions 0 .. L-1.
- Block: ``h += Attn(RMSNorm(h))``; ``h += MoE(RMSNorm(h))``.
- Attn: q (H heads), k, v (Hkv heads) projections without bias, RMSNorm over
  each head's width on q and k, rotary in the half-split form, query head i
  on key/value head ``i // (H / Hkv)``, the block-diffusion mask, o.
- MoE: ``p = softmax(x W_r)`` over all experts; S = top-k of p;
  ``w_e = p_e / sum_S p`` (``norm_topk_prob``); the sum runs over the experts
  of S that are **held** (``first_expert`` .. ``+ held``): what the absent
  ones would add is left out.  ``chosen`` (``[tokens, k]`` expert numbers)
  replaces the reference's own top-k by another's, with the reference's own
  probabilities at those experts: top-k is discrete, and what follows a
  flipped choice is simply different.
- Head and loss: RMSNorm and the head on the noised half;
  ``sum_masked CE(logits_i, x0_i) / t_blk(i) / (sequences x L)``.

Attention runs in chunks of queries (``QUERY_CHUNK``, ``lax.map``) so that a
sequence of 8192 positions fits: the scores of all heads at once would not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_CHUNK = 1024
NEG = -1e30


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def positions(length: int):
    """Rotary positions of the 2L rows: both copies count from 0."""
    return jnp.concatenate([jnp.arange(length), jnp.arange(length)])


def rotary(x, pos, theta):
    """x [S, H, D]; pairs (i, i + D/2)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(qi, kj, length: int, block: int):
    """Whether query row ``qi`` sees key row ``kj`` (rows of ``[x0 ; xt]``)."""
    qb, kb = (qi % length) // block, (kj % length) // block
    q_noised, k_noised = qi >= length, kj >= length
    return jnp.where(q_noised,
                     jnp.where(k_noised, kb == qb, kb < qb),
                     jnp.logical_and(~k_noised, kb <= qb))


def kv_head_of(head, group: int):
    return head // group


def attention(q, k, v, length: int, block: int):
    """q [2L, H, D], k, v [2L, Hkv, D] -> [2L, H, D], in chunks of queries."""
    heads, kv_heads = q.shape[1], k.shape[1]
    of = kv_head_of(jnp.arange(heads), heads // kv_heads)
    k, v = k[:, of], v[:, of]                              # [2L, H, D]
    rows = jnp.arange(2 * length)
    scale = q.shape[-1] ** -0.5

    @jax.checkpoint
    def chunk(first_row):
        q_rows = first_row + jnp.arange(size)
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        s = jnp.where(visible(q_rows[:, None], rows[None, :], length,
                              block)[None], s, NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    size = min(QUERY_CHUNK, 2 * length)
    assert (2 * length) % size == 0, (length, size)
    out = jax.lax.map(chunk, jnp.arange(0, 2 * length, size))
    return out.reshape(2 * length, heads, -1)


def attn(p, x, cfg, length: int):
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    eps, pos = cfg["rms_norm_eps"], positions(length)
    q = (x @ p["q_proj"]["kernel"]).reshape(-1, heads, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(-1, kv_heads, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(-1, kv_heads, d)
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), pos, cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), pos, cfg["rope_theta"])
    out = attention(q, k, v, length, cfg["block_length"])
    return out.reshape(out.shape[0], -1) @ p["o_proj"]["kernel"]


def router_probs(p, x):
    return jax.nn.softmax(x @ p["router"], axis=-1)


def top_k_weights(probs, chosen, renormalize: bool):
    """The weights of the ``chosen`` experts [T, k] in each token's sum."""
    w = jnp.take_along_axis(probs, chosen, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) if renormalize else w


def held_experts(cfg, held: int):
    """The numbers of the experts whose kernels the tree holds."""
    return cfg["first_expert"] + jnp.arange(held)


def moe(p, x, cfg, chosen=None):
    """x [T, d] -> ``(y, probs, chosen)``: the held experts' part of the
    layer, a loop over them (``lax.scan``, so that the program holds one
    expert's code: each computes every row; the rows not routed to it weigh
    zero)."""
    probs = router_probs(p, x)
    if chosen is None:
        chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
    weights = top_k_weights(probs, chosen, cfg["norm_topk_prob"])

    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        h = jax.nn.silu(x @ w_gate) * (x @ w_up)
        return y + mine[:, None] * (h @ w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        held_experts(cfg, p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, probs, chosen


def block(p, h, cfg, length: int, chosen=None):
    eps = cfg["rms_norm_eps"]
    h = h + attn(p["attn"], rms_norm(h, p["input_norm"]["scale"], eps), cfg,
                 length)
    routed = rms_norm(h, p["post_attn_norm"]["scale"], eps)
    y, probs, chosen = moe(p["moe"], routed, cfg, chosen)
    return h + y, {"routed": routed, "probs": probs, "chosen": chosen}


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def hidden(params, clean_ids, noised_ids, cfg, chosen=None):
    """One sequence: ids [L] each -> the last block's output [2L, d] and
    what each layer's router saw and chose.  ``chosen`` [layers, 2L, k]."""
    length = clean_ids.shape[0]
    h = params["embed"]["embedding"][jnp.concatenate([clean_ids,
                                                      noised_ids])]
    seen = []
    for i in range(layers_of(params)):
        h, routed = jax.checkpoint(
            lambda p, h, c: block(p, h, cfg, length, c))(
                params[f"layer_{i}"], h, None if chosen is None else chosen[i])
        seen.append(routed)
    return h, seen


def head(params, h, cfg):
    """Final norm and the head over the held vocabulary, on rows [.., d]."""
    return rms_norm(h, params["final_norm"]["scale"],
                    cfg["rms_norm_eps"]) @ params["lm_head"]["kernel"]


def logits(params, clean_ids, noised_ids, cfg, chosen=None):
    """[L, V] of the noised half, and what the routers saw and chose."""
    h, seen = hidden(params, clean_ids, noised_ids, cfg, chosen)
    return head(params, h[clean_ids.shape[0]:], cfg), seen


def loss_weight(levels):
    """The linear schedule's weight of a masked token: 1 / t."""
    return 1.0 / levels


def loss_sum(logits_, clean_ids, masked, levels):
    """Sum over the masked positions of CE x weight(t): one sequence's
    share of the loss before the division by ``sequences x L``."""
    logp = jax.nn.log_softmax(logits_, axis=-1)
    nll = -jnp.take_along_axis(logp, clean_ids[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(masked, nll * loss_weight(levels), 0.0))
