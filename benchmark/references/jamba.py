"""The plain reference of family ``jamba``: AI21-Jamba2-3B's decoder (Mamba-1
selective-scan layers, an attention layer every ``attn_layer_period``, a dense
SwiGLU feed-forward in every block), written out in ``jax.numpy`` in float32.
Nothing of the program is imported: the layer equations are ISSUE 47's,
restated here.

Sizes come with ``cfg`` (a dict: ``rms_norm_eps``, ``mamba_dt_rank``,
``mamba_d_state``) and with the parameters' shapes, so the whole model and one
chip's share of a tensor-parallel layer (sliced weights) run alike; the
parameters are the flax tree of ``horovod_tpu.models.Jamba``
(``tree["params"]``); a block is a Mamba block where it holds ``mamba`` and an
attention block where it holds ``attn``.  One sequence at a time, ``x`` [S,
d].

- Block: ``x += mixer(RMSNorm(x))``, ``x += down(silu(gate h) * up h)`` with
  ``h = RMSNorm(x)``; after the last block the final RMSNorm, the head
  ``E^T``.
- Mamba mixer: ``[u | z] = h W_in``; ``u = silu(conv(u) + b)``, the
  convolution depthwise over the sequence, causal; ``[dt | B | C] = u W_x``;
  ``dt``, ``B``, ``C`` each RMS-normed with a learnt scale; ``dt =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t (x) A) *
  s_(t-1) + (dt_t * u_t) (x) B_t``; ``y_t = s_t . C_t + D * u_t``; ``out = (y
  * silu(z)) W_out``.
- Attention: query heads on fewer key/value heads (query head ``i`` reads
  key/value head ``i // group``), causal softmax at scale ``head_dim ** -0.5``,
  no bias and **no positions**.
- Loss: mean over the S - 1 predicting positions of the next token's
  negative log-likelihood.

The scan is a ``lax.scan`` over time (in chunks, each under
``jax.checkpoint``), attention runs in chunks of queries and the head in
blocks of rows (``lax.map``), and each block under ``jax.checkpoint``, so that
16,384 positions fit beside the program's state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_CHUNK = 1024
HEAD_ROWS = 1024
SCAN_CHUNK = 128
NEG = -1e30


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def before(x, steps: int = 1):
    """Row t holds what row t - steps held; zeros in front."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:steps]), x[:-steps]], axis=0)


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(u, taps, bias):
    """u [S, C], taps [n, C]; tap j reads the row n - 1 - j before."""
    n = taps.shape[0]
    return sum(taps[j] * before(u, n - 1 - j) for j in range(n)) + bias


def split_dt_b_c(dbc, cfg):
    rank, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return dbc[:, :rank], dbc[:, rank:rank + n], dbc[:, rank + n:]


def three_norms(p, dt, b, c, eps):
    """Jamba's own: ``dt``, ``B`` and ``C`` each RMS-normed, a learnt scale
    each."""
    return (rms_norm(dt, p["dt_norm"], eps), rms_norm(b, p["b_norm"], eps),
            rms_norm(c, p["c_norm"], eps))


def step_size(p, dt):
    """[S, rank] -> [S, C]: the step the recurrence takes."""
    return jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])


def decay_rate(p):
    return -jnp.exp(p["A_log"])


def decay(dt_t, rate):
    """One step's decay [C, N] of the step sizes [C] and the rate [C, N]."""
    return jnp.exp(dt_t[:, None] * rate)


def carried(state):
    """The state as the next step takes it: float32, as it is."""
    return state


def recurrence(u, dt, rate, b, c, d):
    """``s_t = decay_t * s_(t-1) + (dt_t u_t) (x) B_t``, ``y_t = s_t . C_t +
    D u_t``: u, dt [S, C], rate [C, N], b, c [S, N], d [C] -> y [S, C]."""
    seq = u.shape[0]
    size = math.gcd(seq, SCAN_CHUNK)

    def step(state, row):
        u_t, dt_t, b_t, c_t = row
        state = carried(decay(dt_t, rate) * state
                        + (dt_t * u_t)[:, None] * b_t[None])
        return state, state @ c_t + d * u_t

    @jax.checkpoint
    def chunk(state, rows):
        return jax.lax.scan(step, state, rows)

    rows = tuple(x.reshape(seq // size, size, -1) for x in (u, dt, b, c))
    _, y = jax.lax.scan(chunk, jnp.zeros(rate.shape, u.dtype), rows)
    return y.reshape(u.shape)


def gated(y, u, z):
    return y * silu(z)


def skip(p):
    return p["D"]


def scan_operands(p, h, cfg):
    """``(u, dt, rate, B, C, D)`` as the recurrence takes them, and the gate
    ``z``, of the mixer's input ``h`` [S, d]."""
    held = p["conv"].shape[1]
    uz = h @ p["in_proj"]["kernel"].reshape(h.shape[1], 2 * held)
    u, z = uz[:, :held], uz[:, held:]
    u = silu(causal_conv(u, p["conv"], p.get("conv_bias", 0.0)))
    dt, b, c = three_norms(
        p, *split_dt_b_c(u @ p["x_proj"]["kernel"], cfg), cfg["rms_norm_eps"])
    return (u, step_size(p, dt), decay_rate(p), b, c, skip(p)), z


def mamba(p, h, cfg):
    operands, z = scan_operands(p, h, cfg)
    return gated(recurrence(*operands), operands[0], z) @ p["out_proj"][
        "kernel"]


def kv_head_of(head, group: int):
    return head // group


def positioned(q, k):
    """No positional encoding of any kind: the recurrence carries order."""
    return q, k


def attention(q, k, v):
    """q [S, H, D], k, v [S, G, D] -> [S, H, D], causal, in chunks of
    queries."""
    seq, heads = q.shape[:2]
    of = kv_head_of(jnp.arange(heads), heads // k.shape[1])
    k, v = k[:, of], v[:, of]
    rows = jnp.arange(seq)
    scale = q.shape[-1] ** -0.5
    size = math.gcd(seq, QUERY_CHUNK)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        seen = rows[None, :] <= (first_row + jnp.arange(size))[:, None]
        s = jnp.where(seen[None], s, NEG)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(chunk, jnp.arange(0, seq, size)).reshape(q.shape)


def attn(p, h, cfg):
    d_model, heads, d = p["q_proj"]["kernel"].shape
    groups = p["kv_proj"]["kernel"].shape[2]
    q = (h @ p["q_proj"]["kernel"].reshape(d_model, -1)).reshape(-1, heads, d)
    kv = (h @ p["kv_proj"]["kernel"].reshape(d_model, -1)).reshape(
        -1, 2, groups, d)
    q, k = positioned(q, kv[:, 0])
    out = attention(q, k, kv[:, 1])
    return out.reshape(out.shape[0], -1) @ p["o_proj"]["kernel"]


def mlp(p, h):
    held = p["down"]["kernel"].shape[0]
    gu = h @ p["gate_up"]["kernel"].reshape(h.shape[1], 2 * held)
    return (silu(gu[:, :held]) * gu[:, held:]) @ p["down"]["kernel"]


def block(p, x, cfg):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["input_norm"]["scale"], eps)
    x = x + (attn(p["attn"], h, cfg) if "attn" in p
             else mamba(p["mamba"], h, cfg))
    return x + mlp(p["mlp"], rms_norm(x, p["pre_ff_norm"]["scale"], eps))


def layers_of(params) -> int:
    return sum(name.startswith("layer_") for name in params)


def hidden(params, ids, cfg):
    """One sequence: ids [S] -> what the head reads [S, d], after the final
    norm."""
    x = params["embed"]["embedding"][ids]
    for i in range(layers_of(params)):
        x = jax.checkpoint(lambda p, x: block(p, x, cfg))(
            params[f"layer_{i}"], x)
    return rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def head(params, x):
    """The tied head on rows [.., d]: the embedding transposed."""
    return x @ params["embed"]["embedding"].T


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
