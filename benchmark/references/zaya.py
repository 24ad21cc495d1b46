"""The plain reference of family ``zaya``: ZAYA1's decoder (compressed
convolutional attention, arXiv:2510.04476; a top-1 mixture of experts whose
router is an MLP with a state carried down the layers, arXiv:2511.17127),
written out in ``jax.numpy`` in float32.  Nothing of the program is imported:
the layer equations are ISSUE 36's, restated here.

Sizes come with ``cfg`` (a dict: ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``partial_rotary_factor``,
``rope_theta``, ``rms_norm_eps``, ``num_experts_per_tok``, ``first_expert``)
and with the parameters' shapes; the parameters are the flax tree of
``horovod_tpu.models.Zaya`` (``tree["params"]``), the balancing biases its
``tree["balancing"]``.  One sequence at a time, ``x`` [S, d].

- Block: ``r1 = a1 (r0 + c1) + b1 (y0 + e1)`` (none in block 0), ``y1 =
  CCA(RMSNorm(r1))``, ``r2 = a2 (r1 + c2) + b2 (y1 + e2)``, ``y2, s' =
  MoE(RMSNorm(r2), s)``; after the last block one more scaled sum, the final
  RMSNorm, the head ``E^T``.
- CCA: ``q0 = x Wq``, ``k0 = x Wk``; ``u = conv1(conv0([q0 | k0]))``, conv0
  depthwise over the sequence, conv1 grouped by head, both causal, neither
  with a bias, no activation between; ``q = u_q + (q0 + k0 of the group) /
  2``, ``k = u_k + (mean of q0 over the group + k0) / 2``; ``v = [x_t Wv1 |
  x_(t-1) Wv2]``; q and k scaled to norm sqrt(d) a head, k times its head's
  temperature; rotary on the first ``partial_rotary_factor`` of a head;
  causal grouped-query softmax attention; ``Wo``.
- MoE: ``s' = x Wd + bd (+ gamma s)``; ``p = softmax(MLP(RMSNorm(s')))``
  with two GELU (erf) layers; ``e = top-k(p + bias)``; ``y = sum_e p[e]
  expert_e(x)`` over the chosen experts that are **held**
  (``first_expert`` .. ``+ held``), not renormalised.  ``chosen`` (``[S, k]``
  expert numbers) replaces the reference's own choice by another's, with the
  reference's own probabilities at those experts.
- Loss: mean over the S - 1 predicting positions of the next token's
  negative log-likelihood.

Attention runs in chunks of queries and the head in blocks of rows
(``lax.map``), the experts one at a time (``lax.scan``) and each block under
``jax.checkpoint``, so that 16,384 positions fit beside the program's state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_CHUNK = 1024
HEAD_ROWS = 1024
NEG = -1e30


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def before(x, steps: int = 1):
    """Row t holds what row t - steps held; zeros in front."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def rotated_width(head_dim: int, cfg) -> int:
    """How many of a head's channels carry the rotary positions."""
    return int(head_dim * cfg["partial_rotary_factor"])


def rotary(x, theta, width: int):
    """x [S, H, D]: the first ``width`` channels of each head rotated in
    half-split pairs (i, i + width / 2), the rest as they are."""
    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:width], x[..., width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def depthwise(z, taps):
    """conv0: z [S, C], taps [n, C]; tap j reads the row n - 1 - j before."""
    n = taps.shape[0]
    return sum(taps[j] * before(z, n - 1 - j) for j in range(n))


def by_head(z, taps):
    """conv1: z [S, G, D], taps [n, G, D, D]: each head's channels mixed
    among themselves."""
    n = taps.shape[0]
    return sum(jnp.einsum("sgc,gcd->sgd", before(z, n - 1 - j), taps[j])
               for j in range(n))


def mix(p, q0, k0):
    """``u = conv1(conv0([q0 | k0]))`` on [S, H + G, D]."""
    z = jnp.concatenate([q0, k0], axis=1)
    return by_head(depthwise(z.reshape(z.shape[0], -1),
                             p["conv0"]).reshape(z.shape), p["conv1"])


def qk_mean(u, q0, k0):
    """q [S, H, D] and k [S, G, D] from the mixed and the plain latents."""
    heads, groups = q0.shape[1], k0.shape[1]
    per = heads // groups
    q = u[:, :heads] + (q0 + jnp.repeat(k0, per, axis=1)) / 2
    k = u[:, heads:] + (q0.reshape(-1, groups, per, q0.shape[-1]).mean(2)
                        + k0) / 2
    return q, k


def values(p, x):
    """[S, 2, D]: key/value head 0 is the token's, head 1 the token
    before's."""
    return jnp.stack([x @ p["v_proj"]["kernel"],
                      before(x) @ p["v_shift_proj"]["kernel"]], axis=1)


def unit(x, to):
    return x * (to * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                   + 1e-12))


def kv_head_of(head, group: int):
    return head // group


def attention(q, k, v):
    """q [S, H, D], k, v [S, G, D] -> [S, H, D], causal, in chunks of
    queries."""
    seq, heads = q.shape[:2]
    of = kv_head_of(jnp.arange(heads), heads // k.shape[1])
    k, v = k[:, of], v[:, of]
    rows = jnp.arange(seq)
    scale = q.shape[-1] ** -0.5
    size = min(QUERY_CHUNK, seq)
    assert seq % size == 0, (seq, size)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        seen = rows[None, :] <= (first_row + jnp.arange(size))[:, None]
        s = jnp.where(seen[None], s, NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(q.shape)


def cca(p, x, cfg):
    heads, groups, d = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
    q0 = (x @ p["q_proj"]["kernel"]).reshape(-1, heads, d)
    k0 = (x @ p["k_proj"]["kernel"]).reshape(-1, groups, d)
    q, k = qk_mean(mix(p, q0, k0), q0, k0)
    width = rotated_width(d, cfg)
    q = rotary(unit(q, d ** 0.5), cfg["rope_theta"], width)
    k = rotary(unit(k, d ** 0.5) * p["temp"][:, None], cfg["rope_theta"],
               width)
    out = attention(q, k, values(p, x))
    return out.reshape(out.shape[0], -1) @ p["o_proj"]["kernel"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def dense(p, x):
    return x @ p["kernel"] + p.get("bias", 0.0)


def router_state(p, x, s):
    """``s' = x Wd + bd + gamma s``; a block without ``gamma`` (the first)
    takes no state."""
    state = dense(p["down"], x)
    return state + p["gamma"] * s if "gamma" in p else state


def router_probs(p, state, cfg):
    z = rms_norm(state, p["norm"]["scale"], cfg["rms_norm_eps"])
    z = gelu(dense(p["mlp_0"], z))
    z = gelu(dense(p["mlp_1"], z))
    return jax.nn.softmax(dense(p["mlp_2"], z), axis=-1)


def choose(probs, bias, top_k: int):
    """The experts of the largest ``probs + bias``: the bias is in the
    choice and not in the gate."""
    return jax.lax.top_k(probs + bias, top_k)[1]


def gates(probs, bias, chosen):
    """The chosen experts' weights: their probabilities, not renormalised,
    and without the bias that chose them."""
    return jnp.take_along_axis(probs, chosen, axis=-1)


def held_experts(cfg, held: int):
    return cfg["first_expert"] + jnp.arange(held)


def moe(p, x, s, cfg, bias, chosen=None):
    """x [S, d] -> ``(y, s', probs, chosen)``: the held experts' part of the
    layer, a loop over them (each computes every row; the rows not routed
    to it weigh zero)."""
    state = router_state(p["router"], x, s)
    probs = router_probs(p["router"], state, cfg)
    if chosen is None:
        chosen = choose(probs, bias, cfg["num_experts_per_tok"])
    weights = gates(probs, bias, chosen)

    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        h = jax.nn.silu(x @ w_gate) * (x @ w_up)
        return y + mine[:, None] * (h @ w_down), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        held_experts(cfg, p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y, state, probs, chosen


def scaled_sum(p, r, y):
    """``a (r + c) + b (y + e)``: the biases lie inside the scales."""
    return p["a"] * (r + p["c"]) + p["b"] * (y + p["e"])


def block(p, r, y, s, cfg, bias, chosen=None):
    eps = cfg["rms_norm_eps"]
    if "res_attn" in p:
        r = scaled_sum(p["res_attn"], r, y)
    y = cca(p["attn"], rms_norm(r, p["input_norm"]["scale"], eps), cfg)
    r = scaled_sum(p["res_moe"], r, y)
    routed = rms_norm(r, p["post_attn_norm"]["scale"], eps)
    y, state, probs, chosen = moe(p["moe"], routed, s, cfg, bias, chosen)
    return r, y, state, {"routed": routed, "state_in": s, "probs": probs,
                         "chosen": chosen}


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def hidden(params, balancing, ids, cfg, chosen=None):
    """One sequence: ids [S] -> what the head reads [S, d] (after the last
    scaled sum and the final norm), and what each layer's router saw and
    chose.  ``chosen`` [layers, S, k]."""
    r, y, s = params["embed"]["embedding"][ids], None, None
    seen = []
    for i in range(layers_of(params)):
        name = f"layer_{i}"
        r, y, s, routed = jax.checkpoint(
            lambda p, r, y, s, c, bias: block(p, r, y, s, cfg, bias, c))(
                params[name], r, y, s,
                None if chosen is None else chosen[i],
                balancing[name]["moe"]["bias"])
        seen.append(routed)
    r = scaled_sum(params["res_final"], r, y)
    return rms_norm(r, params["final_norm"]["scale"],
                    cfg["rms_norm_eps"]), seen


def head(params, x):
    """The tied head on rows [.., d]: the embedding transposed."""
    return x @ params["embed"]["embedding"].T


def next_tokens(ids):
    """What each row but the last is to predict: the token after it."""
    return ids[1:]


def next_token_nll(params, x, ids):
    """[S - 1]: row t's negative log-likelihood of token t + 1, the head a
    block of rows at a time (S - 1 is odd: zero rows fill the last block
    and are cut off again)."""
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
