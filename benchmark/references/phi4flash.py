"""The plain reference of family ``phi4flash``: Phi-4-mini-flash's decoder
(SambaY, arXiv:2507.06607, with the differential attention of
arXiv:2410.05258), written out in ``jax.numpy`` in float32.  Nothing of the
program is imported: the layer equations are ISSUE 60's, restated here.

Sizes come with ``cfg`` (a dict: ``layer_norm_eps``, ``mamba_dt_rank``,
``mamba_d_state``, ``sliding_window``, ``published_num_hidden_layers``) and
with the parameters' shapes, so the whole model and one
chip's share of a tensor-parallel layer (sliced weights) run alike.  The
parameters are the flax tree of ``horovod_tpu.models.Phi4Flash``
(``tree["params"]``); block ``layer_<l>`` is the **published** layer ``l``.
One sequence at a time, ``x`` [S, d], everything causal, no positions.

- Block l: ``x += mixer_l(LN(x))``, ``x += W_2 (silu(g) * y)`` with ``[g | y]
  = W_1 LN(x)``; LN subtracts the mean and has a scale and a bias; after the
  last block the final LN, the head ``E^T``.
- With ``half = published layers // 2``.  Even l: Mamba below ``half``, Mamba
  whose scan output is the memory ``M`` at ``half``, a Gated Memory Unit on
  ``M`` above.  Odd l: differential attention under a band of
  ``sliding_window`` keys below ``half``, over the whole causal context at
  ``half + 1`` (whose k and v are kept), cross-attention onto those k, v
  above.
- Mamba-1 as published: ``[u | z] = h W_in``; ``u = silu(conv(u) + b)``;
  ``[dt | B | C] = u W_x`` with **no norm**; ``dt = softplus(dt W_dt +
  b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(dt_t (x) A) * s_(t-1) + (dt_t *
  u_t) (x) B_t``; ``y_t = s_t . C_t + D * u_t``; ``out = (y * silu(z))
  W_out``; ``M = y``.
- Gated Memory Unit: ``out = (M * silu(h W_1)) W_2``.
- Differential attention: heads pair by neighbours, query pair p reads
  key/value pair ``p // group``, ``V`` the pair's two value heads side by
  side; ``A_m = softmax(q_m k_m^T / sqrt(d) + mask) V``; ``lambda = exp(lq1
  . lk1) - exp(lq2 . lk2) + lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6
  exp(-0.3 l)``; ``O = (1 - lambda_init(l)) RMSNorm_2d(A1 - lambda A2)``;
  ``out = O W_o + b_o``; q, k and v carry biases.
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

QUERY_CHUNK = 512
HEAD_ROWS = 1024
SCAN_CHUNK = 128
NEG = -1e30


def layer_norm(x, p, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * p["scale"] + p["bias"]


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


# --------------------------------------------------------------------------
# The layer order
# --------------------------------------------------------------------------


def half_of(cfg) -> int:
    return cfg["published_num_hidden_layers"] // 2


def kind_of(layer: int, cfg) -> str:
    """The mixer of published layer ``layer``."""
    half = half_of(cfg)
    if layer % 2 == 0:
        return ("mamba" if layer < half else "mamba+memory" if layer == half
                else "gmu")
    return ("banded" if layer < half + 1 else "full+kv" if layer == half + 1
            else "cross")


def layers_of(params) -> list:
    """The published indices of the blocks held, in order."""
    return sorted(int(name[len("layer_"):]) for name in params
                  if name.startswith("layer_"))


# --------------------------------------------------------------------------
# Mamba-1 and the gate on its memory
# --------------------------------------------------------------------------


def causal_conv(u, taps, bias):
    """u [S, C], taps [n, C]; tap j reads the row n - 1 - j before."""
    n = taps.shape[0]
    return sum(taps[j] * before(u, n - 1 - j) for j in range(n)) + bias


def split_dt_b_c(dbc, cfg):
    rank, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return dbc[:, :rank], dbc[:, rank:rank + n], dbc[:, rank + n:]


def normed_dt_b_c(p, dt, b, c, cfg):
    """Mamba-1 as published: ``dt``, ``B`` and ``C`` as ``x_proj`` made
    them."""
    return dt, b, c


def step_size(p, dt):
    """[S, rank] -> [S, C]: the step the recurrence takes."""
    return jax.nn.softplus(dt @ p["dt_proj"] + p["dt_bias"])


def recurrence(u, dt, rate, b, c, d):
    """``s_t = exp(dt_t (x) A) * s_(t-1) + (dt_t u_t) (x) B_t``, ``y_t = s_t
    . C_t + D u_t``: u, dt [S, C], rate [C, N], b, c [S, N], d [C] -> y [S,
    C]."""
    seq = u.shape[0]
    size = math.gcd(seq, SCAN_CHUNK)

    def step(state, row):
        u_t, dt_t, b_t, c_t = row
        state = (jnp.exp(dt_t[:, None] * rate) * state
                 + (dt_t * u_t)[:, None] * b_t[None])
        return state, state @ c_t + d * u_t

    @jax.checkpoint
    def chunk(state, rows):
        return jax.lax.scan(step, state, rows)

    rows = tuple(x.reshape(seq // size, size, -1) for x in (u, dt, b, c))
    _, y = jax.lax.scan(chunk, jnp.zeros(rate.shape, u.dtype), rows)
    return y.reshape(u.shape)


def memory_of(y, z):
    """What the gate layers read of the scan: its output ``y`` (with the ``D
    u`` term), before the output gate."""
    return y


def mamba(p, h, cfg):
    """``(out, M)`` of the mixer's input ``h`` [S, d]."""
    held = p["conv"].shape[1]
    uz = h @ p["in_proj"]["kernel"].reshape(h.shape[1], 2 * held)
    u, z = uz[:, :held], uz[:, held:]
    u = silu(causal_conv(u, p["conv"], p.get("conv_bias", 0.0)))
    dt, b, c = normed_dt_b_c(
        p, *split_dt_b_c(u @ p["x_proj"]["kernel"], cfg), cfg)
    y = recurrence(u, step_size(p, dt), -jnp.exp(p["A_log"]), b, c, p["D"])
    return (y * silu(z)) @ p["out_proj"]["kernel"], memory_of(y, z)


def gate_on(memory, g):
    return memory * silu(g)


def gmu(p, h, memory, cfg):
    """A Gated Memory Unit: two products round a gate on ``memory``."""
    return gate_on(memory, h @ p["in_proj"]["kernel"]) @ p["out_proj"][
        "kernel"]


# --------------------------------------------------------------------------
# Differential attention
# --------------------------------------------------------------------------


def lambda_index(layer: int, cfg) -> int:
    """The index ``lambda_init`` takes: the published one."""
    return layer


def lambda_init(layer: int, cfg) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * lambda_index(layer, cfg))


def lambda_of(p, start):
    return (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
            - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + start)


def pairs_of(x):
    """[S, H, D] -> the first and the second head of each pair of
    neighbours, [S, H / 2, D] each."""
    return x[:, 0::2], x[:, 1::2]


def values_of(v):
    """[S, G, D] -> [S, G / 2, 2 D]: a pair's two value heads side by
    side."""
    return v.reshape(v.shape[0], v.shape[1] // 2, -1)


def positioned(q, k):
    """No positional encoding of any kind."""
    return q, k


def seen(rows, cols, window):
    """[rows, cols] bool: query i sees key j where ``j <= i`` and, under a
    window, ``i - window < j``: ``window`` keys, its own among them."""
    causal = cols[None, :] <= rows[:, None]
    if window is None:
        return causal
    return causal & (cols[None, :] > rows[:, None] - window)


def softmax_map(q, k, v, window):
    """q [S, P, D], k [S, R, D], v [S, R, W] -> [S, P, W]: query head p on
    key/value head ``p // (P / R)``, in chunks of queries."""
    seq, heads = q.shape[:2]
    of = jnp.arange(heads) // (heads // k.shape[1])
    k, v = k[:, of], v[:, of]
    cols = jnp.arange(seq)
    scale = q.shape[-1] ** -0.5
    size = math.gcd(seq, QUERY_CHUNK)

    @jax.checkpoint
    def chunk(first_row):
        qc = jax.lax.dynamic_slice_in_dim(q, first_row, size)
        s = jnp.einsum("qhd,khd->hqk", qc, k) * scale
        s = jnp.where(seen(first_row + jnp.arange(size), cols, window)[None],
                      s, NEG)
        return jnp.einsum("hqk,khw->qhw", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(chunk, jnp.arange(0, seq, size))
    return out.reshape(seq, heads, v.shape[-1])


def pair_norm(x, scale, eps):
    """RMSNorm over a pair's ``2 D`` outputs."""
    return rms_norm(x, scale, eps)


def difference_of(a1, a2, lam):
    return a1 - lam * a2


def output_scale(start):
    return 1.0 - start


def window_of(kind: str, cfg):
    return cfg["sliding_window"] if kind == "banded" else None


def bias_of(bias):
    return bias


def projected(p, h, name):
    kernel = p[name]["kernel"]
    heads, d = kernel.shape[1:]
    y = h @ kernel.reshape(kernel.shape[0], -1)
    if "bias" in p[name]:
        y = y + bias_of(p[name]["bias"]).reshape(-1)
    return y.reshape(-1, heads, d)


def keys_and_values(p, h):
    return projected(p, h, "k_proj"), projected(p, h, "v_proj")


def two_maps(q, k, v, window):
    """``(A1, A2)`` [S, H / 2, 2 D] each of q [S, H, D] on k, v [S, G, D]."""
    q, k = positioned(q, k)
    (q1, q2), (k1, k2) = pairs_of(q), pairs_of(k)
    values = values_of(v)
    return (softmax_map(q1, k1, values, window),
            softmax_map(q2, k2, values, window))


def attn(p, h, kv, layer, kind, cfg):
    """``(out, (k, v))``: differential attention of ``h`` [S, d] on its own
    keys and values, or (``kv``) on another layer's."""
    q = projected(p, h, "q_proj")
    k, v = keys_and_values(p, h) if kv is None else kv
    a1, a2 = two_maps(q, k, v, window_of(kind, cfg))
    start = lambda_init(layer, cfg)
    out = output_scale(start) * pair_norm(
        difference_of(a1, a2, lambda_of(p, start)), p["pair_norm"],
        cfg["layer_norm_eps"])
    out = out.reshape(out.shape[0], -1) @ p["o_proj"]["kernel"]
    if "o_proj_bias" in p:
        out = out + bias_of(p["o_proj_bias"])
    return out, (k, v)


# --------------------------------------------------------------------------
# The model
# --------------------------------------------------------------------------


def mlp(p, h):
    held = p["down"]["kernel"].shape[0]
    gu = h @ p["gate_up"]["kernel"].reshape(h.shape[1], 2 * held)
    return (silu(gu[:, :held]) * gu[:, held:]) @ p["down"]["kernel"]


def carried_memory(memory):
    """What the gate layers read of the memory layer's scan, as it is."""
    return memory


def carried_kv(kv):
    """What the cross layers read of the full layer's k and v, as it is."""
    return kv


def read_memory(memory, h, params):
    """The memory a gate layer reads: the one handed down."""
    return memory


def read_kv(kv, h, params):
    """The keys and values a cross layer reads: the pair handed down."""
    return kv


def block(params, x, memory, kv, layer, cfg):
    """Published layer ``layer``: ``(x, M, kv) -> (x, M, kv)``."""
    p = params[f"layer_{layer}"]
    eps = cfg["layer_norm_eps"]
    kind = kind_of(layer, cfg)
    h = layer_norm(x, p["input_norm"], eps)
    if kind == "mamba":
        y, _ = mamba(p["mamba"], h, cfg)
    elif kind == "mamba+memory":
        y, memory = mamba(p["mamba"], h, cfg)
        memory = carried_memory(memory)
    elif kind == "gmu":
        y = gmu(p["gmu"], h, read_memory(memory, h, params), cfg)
    else:
        y, made = attn(
            p["attn"], h,
            read_kv(kv, h, params) if kind == "cross" else None, layer, kind,
            cfg)
        if kind == "full+kv":
            kv = carried_kv(made)
    x = x + y
    return (x + mlp(p["mlp"], layer_norm(x, p["post_mixer_norm"], eps)),
            memory, kv)


def blocks_of(params) -> dict:
    return {k: v for k, v in params.items() if k.startswith("layer_")}


def hidden(params, ids, cfg):
    """One sequence: ids [S] -> what the head reads [S, d], after the final
    norm."""
    x = params["embed"]["embedding"][ids]
    memory = kv = None
    for i in layers_of(params):
        x, memory, kv = jax.checkpoint(
            lambda p, x, m, kv, i=i: block(p, x, m, kv, i, cfg))(
                blocks_of(params), x, memory, kv)
    return layer_norm(x, params["final_norm"], cfg["layer_norm_eps"])


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
