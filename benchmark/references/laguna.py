"""The plain reference of family ``laguna``: Laguna-S-2.1's decoder (sliding-
window attention layers among global ones, a gate a head, a top-k mixture of
SwiGLU experts beside a shared one), written out in ``jax.numpy`` in float32.
Nothing of the program is imported: the layer equations are ISSUE 51's,
restated here.

Sizes come with ``cfg`` (a dict: ``rms_norm_eps``, ``head_dim``,
``layer_types``, ``sliding_window``, ``rope_parameters`` by layer kind,
``num_experts_per_tok``, ``norm_topk_prob``, ``moe_routed_scaling_factor``,
``first_expert``) and with the parameters' shapes, so the whole model and one
chip's share of a layer (sliced weights: its heads, columns, experts, rows)
run alike; the parameters are the flax tree of ``horovod_tpu.models.laguna.
Laguna`` (``tree["params"]``); a block's feed-forward is the mixture where it
holds ``moe`` and the dense SwiGLU where it holds ``mlp``.  One sequence at a
time, ``x`` [S, d].

- Block l: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FF_l(RMSNorm(h))``;
  after the last block the final RMSNorm and the untied head.
- Attention: ``q = x W_q`` [S, H, 128], ``k = x W_k``, ``v = x W_v`` [S, G,
  128], query head ``i`` on key/value head ``i // (H / G)``; rotary positions
  in the half-split pairing (lane i with lane i + width / 2) by the layer's
  kind: a ``sliding_attention`` layer turns the whole head at ``theta ** (-2
  i / 128)``, a ``full_attention`` layer the first ``partial_rotary_factor``
  of it at YaRN's blended frequencies (``inv_freq``), cos and sin times
  ``attention_factor``, the other lanes passing; scores ``q . k / sqrt(128)``;
  query i sees key j iff ``j <= i`` and, on a sliding layer, ``i - j <
  sliding_window``; ``g = sigmoid(x W_g)`` [S, H], head h's output times
  ``g[:, h]``; then ``W_o``.
- Mixture: ``p = softmax(x W_r)`` over every expert, top-k, the chosen
  weights divided by their sum, ``scale x sum_e w_e E_e(x) + E_shared(x)``,
  each ``E`` a SwiGLU.
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
SLIDING = "sliding_attention"


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


def inv_freq(width: int, rope: dict):
    """[width / 2]: the frequencies of a ``width``-wide turn under one layer
    kind's ``rope_parameters``."""
    i = jnp.arange(width // 2, dtype=jnp.float32)
    extrapolated = rope["rope_theta"] ** (-2.0 * i / width)
    if rope["rope_type"] == "default":
        return extrapolated

    def pair_turning(times):
        return (width * math.log(rope["original_max_position_embeddings"]
                                 / (times * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), width - 1)
    kept = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extrapolated / rope["factor"] * (1.0 - kept) + extrapolated * kept


def rope_of(kind: str, cfg: dict) -> dict:
    return cfg["rope_parameters"][kind]


def rotary(x, rope: dict):
    """x [S, H, D]: the first ``partial_rotary_factor x D`` lanes of every
    head turned by the row's position, the rest as they are."""
    width = int(x.shape[-1] * rope["partial_rotary_factor"])
    half = width // 2
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq(
        width, rope)[None, :]
    scale = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def window_of(kind: str, cfg: dict):
    """The keys a query sees, its own among them; None: every key before
    it."""
    return cfg["sliding_window"] if kind == SLIDING else None


def kv_head_of(head, group: int):
    return head // group


def attention(q, k, v, window):
    """q [S, H, D], k, v [S, G, D] -> [S, H, D], causal and banded, in chunks
    of queries."""
    seq, heads = q.shape[:2]
    of = kv_head_of(jnp.arange(heads), heads // k.shape[1])
    k, v = k[:, of], v[:, of]
    keys = jnp.arange(seq)
    scale = q.shape[-1] ** -0.5
    size = math.gcd(seq, QUERY_CHUNK)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        rows = (first_row + jnp.arange(size))[:, None]
        seen = keys[None, :] <= rows
        if window is not None:
            seen = seen & (rows - keys[None, :] < window)
        s = jnp.where(seen[None], s, NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(q.shape)


def head_gate(p, h):
    """[S, H]: the gate of each head's output."""
    return jax.nn.sigmoid(h @ p["gate_proj"])


def attn(p, h, kind: str, cfg: dict):
    d = cfg["head_dim"]
    q = (h @ p["q_proj"]["kernel"]).reshape(h.shape[0], -1, d)
    k = (h @ p["k_proj"]["kernel"]).reshape(h.shape[0], -1, d)
    v = (h @ p["v_proj"]["kernel"]).reshape(h.shape[0], -1, d)
    rope = rope_of(kind, cfg)
    out = attention(rotary(q, rope), rotary(k, rope), v,
                    window_of(kind, cfg))
    out = out * head_gate(p, h)[:, :, None]
    return out.reshape(out.shape[0], -1) @ p["o_proj"]["kernel"]


def router_probs(p, x):
    return jax.nn.softmax(x @ p["router"], axis=-1)


def top_k_weights(probs, chosen, renormalize: bool):
    """The weights of the ``chosen`` experts [T, k] in each token's sum."""
    w = jnp.take_along_axis(probs, chosen, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) if renormalize else w


def held_experts(cfg: dict, held: int):
    """The numbers of the experts whose kernels the tree holds."""
    return cfg["first_expert"] + jnp.arange(held)


def routed(p, x, cfg: dict, chosen=None):
    """x [T, d] -> ``(y, probs, chosen)``: the held experts' part of each
    token's weighted sum, an expert at a time over every row."""
    probs = router_probs(p, x)
    if chosen is None:
        chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
    weights = top_k_weights(probs, chosen, cfg["norm_topk_prob"])

    @jax.checkpoint
    def expert(args):
        e, w_gate, w_up, w_down = args
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        return mine[:, None] * ((silu(x @ w_gate) * (x @ w_up)) @ w_down)

    parts = jax.lax.map(expert, (
        held_experts(cfg, p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return jnp.sum(parts, axis=0), probs, chosen


def routed_scale(cfg: dict) -> float:
    return cfg["moe_routed_scaling_factor"]


def shared(p, x):
    """The expert every token takes, ungated."""
    return swiglu(x, p["shared_gate_up"]["kernel"],
                  p["shared_down"]["kernel"])


def moe(p, x, cfg: dict, chosen=None):
    y, probs, chosen = routed(p, x, cfg, chosen)
    return routed_scale(cfg) * y + shared(p, x), probs, chosen


def block(p, x, kind: str, cfg: dict, chosen=None):
    """``(y, seen)``: ``seen`` is what a sparse layer's router saw, its
    probabilities and its choices; None on a dense layer."""
    eps = cfg["rms_norm_eps"]
    x = x + attn(p["attn"], rms_norm(x, p["input_norm"]["scale"], eps), kind,
                 cfg)
    h = rms_norm(x, p["post_attn_norm"]["scale"], eps)
    if "mlp" in p:
        return x + swiglu(h, p["mlp"]["gate_up"]["kernel"],
                          p["mlp"]["down"]["kernel"]), None
    y, probs, chosen = moe(p["moe"], h, cfg, chosen)
    return x + y, {"routed": h, "probs": probs, "chosen": chosen}


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def hidden(params, ids, cfg: dict, chosen=None):
    """One sequence: ids [S] -> what the head reads [S, d], after the final
    norm, and per layer what its router saw and chose (None on a dense
    layer).  ``chosen`` [layers, S, k]: the experts each token takes, layer
    by layer (a dense layer's row is not read); None: the routers' own."""
    x = params["embed"]["embedding"][ids]
    seen = []
    for i in range(layers_of(params)):
        x, routed_ = jax.checkpoint(
            lambda p, x, c, kind=cfg["layer_types"][i]: block(
                p, x, kind, cfg, c))(
                    params[f"layer_{i}"], x,
                    None if chosen is None else chosen[i])
        seen.append(routed_)
    return final_norm(params, x, cfg), seen


def final_norm(params, x, cfg: dict):
    return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


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
