"""Expert parallelism: mixtures of experts, two ways.

Absent in the reference (SURVEY.md §2.7 — its alltoall is the primitive EP
would need).

:func:`switch_moe` is the switch layer over a mesh axis: one expert (or
expert group) per ep rank; top-1 routing with a fixed capacity per expert so
every shape is static; the token dispatch and return are each ONE
``lax.all_to_all`` on ICI, the canonical MoE communication pattern.
Dropped tokens (over capacity) pass through with a zero expert output,
scaled by their gate as usual: the standard switch-transformer behavior.

:func:`routed_experts` is one chip's share of a layer of many experts
(Qwen3-MoE's form: softmax router over all experts, top-k, weights
renormalised over the chosen k, SwiGLU experts).  The chip is told which
consecutive experts it holds; it routes over all of them and computes the
part of each token's sum that its own experts contribute.  Nothing is
dropped: the rows routed here are sorted by expert into a row buffer of the
caller's ``capacity_factor`` times what an even router sends here and
multiplied in grouped matrix products, a group an expert.  The buffer's rows
past the routed ones are in no group, and neither the products nor the
gather into the buffer nor the sum back into the tokens computes anything
for them (:func:`take_rows`: trips of :data:`WALK_ROWS` rows over the routed
prefix, :func:`rows_walked`; :func:`add_rows`: on a TPU a segment sum in
token order, the rows gathered by their token and those routed summed run by
run in one kernel call, a token tile written once; elsewhere a scatter-add in
such trips).  A step whose rows do not fit the buffer walks every row a
router can send, in parts (a ``lax.cond``, taken while the step runs).  It
has no exchange: what the other chips' experts would add is not there
(ROADMAP Reach B1 keeps the all-to-all).

**What is kept between forward and backward** (:func:`_dropless`): a step
whose rows fit keeps the sorted choices, the groups' sizes, the rows'
weights, their order by token (on a TPU: what both sums back start from) and
the ``gate`` and ``up`` products (two ``[rows, f]`` arrays a layer), and its
backward runs no sort and no product of the forward again;
it gathers the ``[rows, d]`` rows again and takes ``d weights`` from the
product it runs for ``d h``.  A step in parts keeps nothing of a buffer's
size and its backward makes each part's forward again
(:func:`forward_kept`).

**Who routes.**  :func:`routed_experts` makes the choice itself
(:func:`route`: a ``[d, experts]`` matrix, softmax, top-k, renormalised or
not) and is what ``models/sdar.py`` calls.  :func:`dispatch_experts` is the
dropless layer alone, for a caller that routes for itself and brings each
token's chosen experts and their weights: ``models/zaya.py``, whose router is
a small MLP with a state carried down the layers, chooses under a balancing
bias and gates by the unbiased probability, top-1 and not renormalised; and
``models/joyai.py``, whose scores are sigmoids: the top-8 of the scores plus
a balancing bias, weighed by the scores without it, renormalised.
``routed_experts`` is ``route`` followed by that call.

**Which product runs where**: on a TPU the three products, forward and
backward, are the Pallas kernels of ``ops/grouped_matmul.py``
(:func:`~horovod_tpu.ops.grouped_matmul.grouped_dot`; ``hvd_moe_gmm`` /
``hvd_moe_tgmm`` in a trace): they walk the rows routed and nothing else, so
their time follows the rows and not the buffer, and they read the float32
kernels as they are, cast a group at a time in VMEM; and the sum back is
``hvd_moe_sum_rows`` (:func:`~horovod_tpu.ops.grouped_matmul.sum_by_token`).
Elsewhere ``grouped_dot`` is ``jax.lax.ragged_dot`` and the sum a
scatter-add.  The backend is what it looks at, as
``flash_attention`` does; no argument chooses.  (megablox's ``gmm``, which
ships with jax and whose scheme the kernels follow, declares no ``vma`` on its
outputs, so ``shard_map`` refuses it under ``check_vma``; ``ragged_dot`` on a
TPU costs 9.7 ms a layer where the kernels cost 6.8 and ``gmm`` 7.5: PERF.md,
PR 35.)
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import axis_size, ensure_varying, vary_like
from ..ops.grouped_matmul import (TILE_ROWS, TokenOrder, grouped_dot,
                                  grouped_dot_grads, sum_by_token,
                                  token_order)


def switch_moe(x, router_kernel, expert_fn: Callable, axis_name: str = "ep",
               capacity_factor: float = 1.25):
    """Top-1 MoE layer with one expert per ep rank.

    Args:
      x: [tokens_local, d] — this shard's tokens.
      router_kernel: [d, n_experts] router weights (replicated).
      expert_fn: this rank's expert, [cap_total, d] -> [cap_total, d]
        (applied to the tokens routed to THIS rank's expert).
      axis_name: expert-parallel mesh axis; n_experts == axis size.
      capacity_factor: per-expert capacity = ceil(T/E * factor).

    Returns [tokens_local, d].
    """
    x = ensure_varying(x, axis_name)
    tokens, d = x.shape
    n_expert = axis_size(axis_name)
    capacity = int(-(-tokens * capacity_factor // n_expert))  # ceil

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_kernel)
    gates = jax.nn.softmax(logits, axis=-1)                 # [T, E]
    expert_idx = jnp.argmax(gates, axis=-1)                 # [T]
    gate = jnp.max(gates, axis=-1)                          # [T]

    # Position of each token within its expert's capacity bucket.
    onehot = jax.nn.one_hot(expert_idx, n_expert, dtype=jnp.int32)  # [T, E]
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1)        # [T, E]
    pos = jnp.take_along_axis(pos_in_expert, expert_idx[:, None],
                              axis=1)[:, 0]                 # [T]
    keep = pos < capacity

    # Scatter tokens into the dispatch buffer [E, C, d].
    dispatch = jnp.zeros((n_expert, capacity, d), x.dtype)
    safe_pos = jnp.where(keep, pos, 0)
    dispatch = dispatch.at[expert_idx, safe_pos].add(
        jnp.where(keep[:, None], x, 0))

    # One all_to_all: shard e of every rank -> rank e. Received layout:
    # [E_src, C, d] = each peer's tokens for THIS rank's expert.
    received = lax.all_to_all(dispatch, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)

    expert_out = expert_fn(received.reshape(n_expert * capacity, d))
    expert_out = expert_out.reshape(n_expert, capacity, d).astype(x.dtype)

    # Return trip: chunk s goes back to source rank s.
    returned = lax.all_to_all(expert_out, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)   # [E, C, d]

    # Gather each kept token's expert output back to token order.
    out = returned[expert_idx, safe_pos]                    # [T, d]
    out = jnp.where(keep[:, None], out, 0)
    return (out * gate[:, None].astype(x.dtype))


def moe_ffn(w_in_local, w_out_local, activation=jax.nn.gelu):
    """Build an expert_fn for :func:`switch_moe` from this rank's FFN
    weights ([d, hidden], [hidden, d])."""

    def fn(tokens):
        h = activation(jnp.einsum("td,dh->th", tokens, w_in_local))
        return jnp.einsum("th,hd->td", h, w_out_local)

    return fn


def load_balancing_loss(x, router_kernel, axis_name: str = "ep"):
    """Switch-transformer auxiliary load-balance loss: E * sum_e f_e * P_e
    (fraction of tokens routed to e times mean router prob of e)."""
    n_expert = axis_size(axis_name)
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_kernel)
    gates = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert_idx, n_expert, dtype=jnp.float32),
                    axis=0)
    prob = jnp.mean(gates, axis=0)
    return n_expert * jnp.sum(frac * prob)


# ---------------------------------------------------------------------------
# One chip's share of a layer of many experts
# ---------------------------------------------------------------------------


class Routing(NamedTuple):
    """What the router decided for [T] tokens (float32, int32)."""
    probs: jax.Array        # [T, E] softmax over all experts
    experts: jax.Array      # [T, k] the chosen experts, best first
    weights: jax.Array      # [T, k] their weights in the token's sum
    load: jax.Array         # [held] rows routed to each held expert


def route(x, router_kernel, top_k: int, first_expert: int, held: int,
          renormalize: bool = True) -> Routing:
    """Softmax router over every expert, in float32 whatever ``x`` is kept
    in (the product at "highest" precision: top-k is discrete, and a
    bfloat16 pass flips the choices whose probabilities nearly tie)."""
    logits = jnp.dot(x.astype(jnp.float32), router_kernel.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, top_k)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return Routing(probs, experts, weights,
                   expert_load(experts, first_expert, held))


def expert_load(experts, first_expert: int, held: int):
    """[held] int32: the rows that the choices ``experts`` [T, k] send to
    each held expert."""
    return jnp.sum(jax.nn.one_hot(_local(experts, first_expert, held), held,
                                  dtype=jnp.int32), axis=(0, 1))


def _local(experts, first_expert: int, held: int):
    """The chosen experts as numbers among the held ones, ``held`` itself
    for one that is absent (it sorts last and is no one-hot class)."""
    local = experts - first_expert
    return jnp.where((local >= 0) & (local < held), local, held)


def row_buffer(tokens: int, top_k: int, held: int, experts: int,
               capacity_factor: float) -> int:
    """The rows of the buffer a call takes while the rows routed here fit
    it: ``capacity_factor`` times what an even router sends here
    (``tokens x top_k x held / experts``) in whole 128-row tiles, and never
    more than any router can send (every token's ``min(top_k, held)``
    choices)."""
    even = tokens * top_k * held / experts
    return min(tokens * min(top_k, held),
               math.ceil(even * capacity_factor / 128) * 128)


def _swiglu_rows(rows, group_sizes, w_gate, w_up, w_down):
    """``down(silu(gate(x)) * up(x))`` of rows sorted by expert, and the
    ``gate(x)`` and ``up(x)`` it was made of: three grouped products, over
    the rows in a group and no others (the kernels are read cast to the
    rows' dtype)."""
    with jax.named_scope("hvd_moe_experts"):
        gate = grouped_dot(rows, w_gate, group_sizes)
        up = grouped_dot(rows, w_up, group_sizes)
        return (grouped_dot(jax.nn.silu(gate) * up, w_down, group_sizes),
                gate, up)


def _swiglu_rows_grads(rows, group_sizes, gate, up, scale, g, w_gate, w_up,
                       w_down):
    """The reverse of ``scale x _swiglu_rows(rows, ...)`` under ``g``, the
    cotangent of the weighted rows, from the ``gate`` and ``up`` the forward
    made: ``(d rows, d scale, dW_gate, dW_up, dW_down)`` in six grouped
    products, none of them the forward's.  ``down``'s result is not made
    again for ``d scale = <out, g>``: that is ``<h, g . W_down^T>``, and
    ``g . W_down^T`` is the product that ``d h = scale x`` it needs anyway."""
    with jax.named_scope("hvd_moe_experts"):
        h, h_vjp = jax.vjp(lambda gate, up: jax.nn.silu(gate) * up, gate, up)
        weighted = (h.astype(jnp.float32) * scale[:, None]).astype(h.dtype)
        back, dw_down = grouped_dot_grads(weighted, w_down, group_sizes, g)
        back = back.astype(jnp.float32)
        d_scale = jnp.sum(h.astype(jnp.float32) * back, axis=1)
        d_gate, d_up = h_vjp((back * scale[:, None]).astype(h.dtype))
        by_gate, dw_gate = grouped_dot_grads(rows, w_gate, group_sizes, d_gate)
        by_up, dw_up = grouped_dot_grads(rows, w_up, group_sizes, d_up)
        return by_gate + by_up, d_scale, dw_gate, dw_up, dw_down


# Rows a trip of :func:`take_rows` / :func:`add_rows`: one of the kernels'
# tiles.  Chosen on a v5e in both cells (PERF.md, PR 37: 2048 / 1024 / 512
# rows read 405.2 / 401.8 / 400.8 ms a step in SDAR's, 453.1 / 451.0 / 450.3
# in ZAYA's: a trip costs little, the half trip walked past the last row
# routed costs what its rows do; 4096 no longer fits what the sum keeps in
# VMEM and costs twice as much a row).
WALK_ROWS = TILE_ROWS


def rows_walked(load_sum: int, capacity: int) -> int:
    """The buffer's rows that a pass of :func:`take_rows` (and, off the TPU,
    of :func:`add_rows`) visits when ``load_sum`` rows are routed into
    ``capacity``: whole trips of :data:`WALK_ROWS`, up to the one that holds
    the last routed row."""
    tile = min(WALK_ROWS, capacity)
    return min(capacity, -(-load_sum // tile) * tile)


def _tiles(n, capacity: int):
    """``(tile, trips, place)``: the rows a trip takes, the trips that hold
    the buffer's first ``n`` rows, and the first row of trip i (a buffer
    that is no whole number of tiles ends in a tile that overlaps the one
    before it).  The trips are counted on the device, so a loop over them has
    no reverse mode: each of the two ops below is the other's."""
    tile = min(WALK_ROWS, capacity)
    return (tile, (n + tile - 1) // tile,
            lambda i: jnp.minimum(i * tile, capacity - tile))


def _rows_from(lo, tile: int):
    return lo + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)


def _zeros(shape, dtype, *like):
    """Zeros a loop starts from, varying as its operands ``like`` do (a
    carry keeps its type under ``shard_map``'s ``check_vma``)."""
    return functools.reduce(vary_like, like, jnp.zeros(shape, dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def take_rows(x, token, n, single: bool = False):
    """``[C, d]``: row i is ``x[token[i]]`` for i < ``n`` and zeros past it
    (x [T, d], token [C] int32, n int32).  Linear in ``x``; its transpose is
    :func:`add_rows`, which ``single`` is for.  Costs what ``n`` rows cost,
    not what C do."""
    tile, trips, place = _tiles(n, token.shape[0])

    def trip(i, rows):
        lo = place(i)
        return lax.dynamic_update_slice(
            rows, x[lax.dynamic_slice(token, (lo,), (tile,))], (lo, 0))

    rows = lax.fori_loop(0, trips, trip, _zeros(
        (token.shape[0], x.shape[1]), x.dtype, x, token))
    # The last trip may have gone past row n: zeros there too.
    lo = place(jnp.maximum(trips - 1, 0))
    last = lax.dynamic_slice(rows, (lo, 0), (tile, x.shape[1]))
    return lax.dynamic_update_slice(
        rows, jnp.where(_rows_from(lo, tile) < n, last,
                        jnp.zeros_like(last)), (lo, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def add_rows(rows, token, n, tokens: int, single: bool = False,
             by_token: TokenOrder | None = None):
    """``[tokens, d]``: the sum of ``rows[i]`` into row ``token[i]`` for
    i < ``n``, what ``zeros.at[token[:n]].add(rows[:n])`` is.  Linear in
    ``rows``; its transpose is :func:`take_rows`.

    On a TPU it is a segment sum in token order (``ops/grouped_matmul.py:
    sum_by_token``, ``hvd_moe_sum_rows`` in a trace): the n rows, gathered
    into the order of their tokens, are summed run by run by a one-hot
    product, in float32 with one rounding to ``rows.dtype``, and a tile of
    tokens is written once, zeros where a token has no row.  ``by_token`` is
    that order (``token_order(token, n)``) where the caller has it already;
    the layer makes it once and keeps it for its backward.  A scatter-add
    there is a serial read-modify-write of some 100 ns a row whatever the row
    holds, 130 in a loop (PERF.md, PR 37, PR 59).  Off the TPU it is that
    scatter-add, in ``rows.dtype`` and in row order, a trip's rows at a time.
    A row that is not finite reaches every token of its tile of
    ``SUM_TOKENS`` tokens on a TPU (``0 x inf`` in the one-hot product is
    NaN) and its own token alone off it; the rows past ``n`` are masked and
    reach nothing on either.

    ``single``: no token has more than one of the n rows (top-1, or one
    expert held), so its sum is that row and is gathered, on any backend."""
    if single:
        capacity = token.shape[0]
        at = jnp.arange(capacity, dtype=jnp.int32)
        row_of = jnp.full((tokens,), capacity, jnp.int32).at[
            jnp.where(at < n, token, tokens)].set(at, mode="drop")
        mine = rows[jnp.minimum(row_of, capacity - 1)]
        return jnp.where((row_of < capacity)[:, None], mine,
                         jnp.zeros_like(mine))
    if by_token is None:
        by_token = token_order(token, n)
    if by_token is None:
        return _scatter_add_rows(rows, token, n, tokens)
    return sum_by_token(rows, by_token, tokens)


def _scatter_add_rows(rows, token, n, tokens: int):
    """:func:`add_rows` off the TPU: ``zeros.at[token[:n]].add(rows[:n])`` in
    ``rows.dtype`` and in row order, a trip's rows at a time.  (On a v5e it
    cost 130 ns a row, 21 of ``sdar-moe-ep8-s4096``'s 314 ms a step;
    ``chip_smoke.py --grouped-products`` still times it beside the sum.)"""
    tile, trips, place = _tiles(n, token.shape[0])

    def trip(i, total):
        lo = place(i)
        row = _rows_from(lo, tile)      # below n, and in no earlier trip
        part = lax.dynamic_slice(rows, (lo, 0), (tile, rows.shape[1]))
        return total.at[lax.dynamic_slice(token, (lo,), (tile,))].add(
            jnp.where((row < n) & (row >= i * tile), part,
                      jnp.zeros_like(part)))

    return lax.fori_loop(0, trips, trip, _zeros(
        (tokens, rows.shape[1]), rows.dtype, rows, token))


take_rows.defvjp(
    lambda x, token, n, single: (take_rows(x, token, n, single),
                                 (token, n, x.shape[0])),
    lambda single, saved, g: (add_rows(g, *saved, single), None, None))
add_rows.defvjp(
    lambda rows, token, n, tokens, single, by_token=None: (
        add_rows(rows, token, n, tokens, single, by_token), (token, n)),
    lambda tokens, single, saved, g: (take_rows(g, *saved, single), None,
                                      None, None))


class _Kept(NamedTuple):
    """What a forward through a buffer of [capacity] rows hands its
    backward."""
    order: jax.Array        # [capacity] int32: the choices, sorted by expert
    sizes: jax.Array        # [held] int32: the rows routed to each expert
    scale: jax.Array        # [capacity] float32: a row's weight, 0 past them
    gate: jax.Array         # [capacity, f]
    up: jax.Array           # [capacity, f]
    # The rows by their token, for the two sums back into the tokens (None
    # where ``add_rows`` asks for none: off the TPU, or a row a token).
    by_token: TokenOrder | None


def _held_part(capacity: int, x, local, weights, w_gate, w_up, w_down):
    """The held experts' part of every token's sum through a row buffer of
    ``capacity`` rows (at least as many as are routed here), and what its
    reverse (:func:`_held_grads`) starts from.  What is paid by the row (the
    gather into the buffer, the sum back into the tokens, and their
    transposes) walks the rows routed, as the products do."""
    tokens, top_k = local.shape
    held = w_gate.shape[0]
    with jax.named_scope("hvd_moe_route"):
        flat = local.reshape(-1)                    # absent experts: ``held``
        order = jnp.argsort(flat, stable=True)[:capacity]
        sizes = jnp.sum(jax.nn.one_hot(flat, held, dtype=jnp.int32), axis=0)
        routed = jnp.sum(sizes)
        token = order // top_k
        # The buffer's rows past the routed ones are no token's and in no
        # group: zeros on the way in, no cotangent on the way back, and no
        # product is computed for them.
        single = min(top_k, held) == 1      # a token has one row at most
        rows = take_rows(x, token, routed, single)
    out, gate, up = _swiglu_rows(rows, sizes, w_gate, w_up, w_down)
    with jax.named_scope("hvd_moe_route"):
        # Weighted in float32, summed back in the activations' dtype: a
        # token's sum has at most min(top_k, held) terms.
        scale = jnp.where(jnp.arange(capacity) < routed,
                          weights.reshape(-1)[order], 0.0)
        out = (out.astype(jnp.float32) * scale[:, None]).astype(x.dtype)
        by_token = None if single else token_order(token, routed)
        return (add_rows(out, token, routed, tokens, single, by_token),
                _Kept(order, sizes, scale, gate, up, by_token))


def _held_grads(x, weights, kept: _Kept, g, w_gate, w_up, w_down):
    """``(dx, d weights, dW_gate, dW_up, dW_down)`` of :func:`_held_part`
    under ``g`` [tokens, d], from what its forward kept: no sort, no count of
    the groups and no product of the forward runs again.  The rows are
    gathered again (a gather from ascending rows costs what its bytes do,
    and the buffer they fill is the widest thing the forward makes)."""
    top_k, held = weights.shape[1], w_gate.shape[0]
    with jax.named_scope("hvd_moe_route"):
        routed, token = jnp.sum(kept.sizes), kept.order // top_k
        single = min(top_k, held) == 1
        rows = take_rows(x, token, routed, single)
        g = take_rows(g, token, routed, single)
    d_rows, d_scale, *d_kernels = _swiglu_rows_grads(
        rows, kept.sizes, kept.gate, kept.up, kept.scale, g, w_gate, w_up,
        w_down)
    with jax.named_scope("hvd_moe_route"):
        live = jnp.arange(kept.order.shape[0]) < routed
        d_weights = jnp.zeros((weights.size,), weights.dtype).at[
            kept.order].add(jnp.where(live, d_scale, 0.0).astype(
                weights.dtype))
        return (add_rows(d_rows, token, routed, x.shape[0], single,
                         kept.by_token),
                d_weights.reshape(weights.shape), *d_kernels)


def _in_parts(parts: int, fn, summed: int, token_args, kernels):
    """``fn(*token_args, *kernels)`` a ``1 / parts`` of the tokens at a time
    (a scan; ``parts`` divides them): its per-token results laid end to end,
    its last ``summed`` results (the kernels' gradients) added up.  A part's
    temporaries are a part's size; under ``jax.vjp`` a part is recomputed
    rather than kept."""
    def split(a):
        return a.reshape(parts, a.shape[0] // parts, *a.shape[1:])

    def body(total, part):
        out = jax.checkpoint(fn)(*part, *kernels)
        out = out if isinstance(out, tuple) else (out,)
        mine = len(out) - summed
        return (tuple(t + o for t, o in zip(total, out[mine:])), out[:mine])

    start = tuple(_zeros(k.shape, k.dtype, *token_args, *kernels)
                  for k in kernels[len(kernels) - summed:])
    total, per_token = lax.scan(body, start, tuple(map(split, token_args)))
    joined = tuple(a.reshape(-1, *a.shape[2:]) for a in per_token)
    return (*joined, *total) if summed or len(joined) > 1 else joined[0]


def forward_kept(load_sum, capacity: int):
    """Whether a layer's backward starts from what its forward made:
    ``load_sum`` rows routed here fit a row buffer of ``capacity`` rows.
    (Where they do not, the step walks every row a router can send in parts,
    forward and backward, and keeps nothing of a buffer's size.)"""
    return load_sum <= capacity


def _sides(rows: int, local, held: int):
    """``(worst, parts, fits)``: every row a router can send here; the fewest
    parts no larger than a buffer of ``rows`` rows that they are walked in
    where more are routed here than it holds, so that a side that hardly
    ever runs is not what the step's memory is sized by; and whether the rows
    routed here fit that buffer (:func:`forward_kept`, known while the step
    runs).  ``parts`` and ``fits`` are None where the buffer holds every row
    a router can send and nothing is chosen."""
    tokens, top_k = local.shape
    worst = tokens * min(top_k, held)
    if rows >= worst:
        return worst, None, None
    parts = next(p for p in range(-(-worst // rows), tokens + 1)
                 if tokens % p == 0)
    return worst, parts, forward_kept(jnp.sum(local < held), rows)


# Inlined jits, as the flash kernels' (``ops/flash_attention.py``): a model
# calls the layer once a block with the same shapes, and jit's cache then
# traces each pass of it, its two sides and their kernels, once a process and
# not once a block; ``inline`` leaves no call in the jaxpr, so an op keeps the
# scope of the block that made it.
@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _forward(rows: int, x, local, weights, *kernels):
    """``(y, kept)`` through a buffer of ``rows`` rows where the rows routed
    here fit it, chosen while the step runs (``lax.cond``: the work done is
    the taken side's alone); in parts where they do not, and then ``kept`` is
    zeros, which cost a side that hardly ever runs nothing worth counting."""
    worst, parts, fits = _sides(rows, local, kernels[0].shape[0])
    if parts is None:
        return _held_part(worst, x, local, weights, *kernels)
    whole = functools.partial(_held_part, rows)

    def in_parts(*args):
        y = _in_parts(parts, lambda *a: _held_part(worst // parts, *a)[0], 0,
                      args[:3], args[3:])
        return y, jax.tree.map(
            lambda k: ensure_varying(jnp.zeros(k.shape, k.dtype),
                                     sorted(k.vma or ())),
            jax.eval_shape(whole, *args)[1])

    return lax.cond(fits, whole, in_parts, x, local, weights, *kernels)


@functools.partial(jax.jit, static_argnums=(0,), inline=True)
def _backward(rows: int, x, local, weights, kept: _Kept, g, *kernels):
    """The gradients of :func:`_forward`'s ``y`` under ``g``: from ``kept``
    where the rows fit, and where they do not a part at a time, each part's
    forward made again."""
    worst, parts, fits = _sides(rows, local, kernels[0].shape[0])
    if parts is None:
        return _held_grads(x, weights, kept, g, *kernels)

    def part_grads(x, local, weights, g, *kernels):
        _, vjp = jax.vjp(
            lambda x, w, *k: _held_part(worst // parts, x, local, w, *k)[0],
            x, weights, *kernels)
        return vjp(g)

    return lax.cond(
        fits,
        lambda x, local, weights, kept, g, *kernels: _held_grads(
            x, weights, kept, g, *kernels),
        lambda x, local, weights, kept, g, *kernels: _in_parts(
            parts, part_grads, len(kernels), (x, local, weights, g), kernels),
        x, local, weights, kept, g, *kernels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kept_between(rows: int, x, local, weights, w_gate, w_up, w_down):
    return _forward(rows, x, local, weights, w_gate, w_up, w_down)[0]


def _kept_between_fwd(rows, *args):
    y, kept = _forward(rows, *args)
    return y, (args, kept)


def _kept_between_bwd(rows, saved, g):
    (x, local, weights, *kernels), kept = saved
    dx, dweights, *dkernels = _backward(rows, x, local, weights, kept, g,
                                        *kernels)
    return (dx, None, dweights, *dkernels)


_kept_between.defvjp(_kept_between_fwd, _kept_between_bwd)


def _dropless(rows: int, x, local, weights, w_gate, w_up, w_down):
    """``_held_part`` through a buffer of ``rows`` rows, or in parts over
    all a router can send where more are routed here.  Forward and backward
    each look at the rows themselves.  Between them a step whose rows fit
    keeps the sorted choices, the groups' sizes, the rows' weights and the
    ``gate`` and ``up`` products (:class:`_Kept`: two ``[rows, f]`` arrays
    and a few ``[rows]`` ones), and its backward runs neither the sort nor a
    product of the forward again; it gathers the rows again, which is the
    one ``[rows, d]`` array it would otherwise keep.  A step in parts keeps
    zeros in their place and its backward makes each part's forward again,
    so its temporaries are a part's size."""
    operands = (x, local, weights, w_gate, w_up, w_down)
    # One type for all, cast outside the custom_vjp (``vary_like``): what is
    # kept varies as they do, and so do both sides of each ``cond``.
    return _kept_between(rows, *(functools.reduce(vary_like, operands, a)
                                 for a in operands))


def dispatch_experts(x, experts, weights, w_gate, w_up, w_down, *,
                     first_expert: int, experts_total: int,
                     capacity_factor: float):
    """The held experts' part of every token's sum, for a caller that has
    routed: ``sum over the token's chosen experts held here of weight x
    expert(x)``, nothing dropped.

    Args:
      x: [tokens, d], in the activations' dtype.
      experts: [tokens, k] int32, each token's chosen experts among all
        ``experts_total`` of the layer; weights: [tokens, k], their weights
        in the token's sum (differentiated; the choice is not).
      w_gate, w_up: [held, d, f]; w_down: [held, f, d]: experts
        ``first_expert`` .. ``first_expert + held``.
      capacity_factor: the row buffer over what an even router sends here
        (:func:`row_buffer`); see :func:`routed_experts`."""
    (tokens, top_k), held = experts.shape, w_gate.shape[0]
    with jax.named_scope("hvd_moe_route"):
        local = _local(experts, first_expert, held)
    rows = row_buffer(tokens, top_k, held, experts_total, capacity_factor)
    return _dropless(rows, x, local, weights.astype(jnp.float32),
                     w_gate, w_up, w_down)


def routed_experts(x, router_kernel, w_gate, w_up, w_down, *, top_k: int,
                   capacity_factor: float, first_expert: int = 0,
                   renormalize: bool = True):
    """One chip's share of a top-k mixture of SwiGLU experts.

    Args:
      x: [tokens, d], in the activations' dtype.
      router_kernel: [d, experts], the router over ALL experts.
      w_gate, w_up: [held, d, f]; w_down: [held, f, d]: the experts this
        chip holds, experts ``first_expert`` .. ``first_expert + held``.
      top_k: experts a token; ``renormalize``: the chosen k weights are
        divided by their sum (``norm_topk_prob``).
      capacity_factor: the row buffer over what an even router sends here
        (:func:`row_buffer`), as :func:`switch_moe`'s, but nothing is
        dropped past it: a step that routes more rows here takes them all,
        in parts, at the cost of every row a router can send.  The caller
        knows how alike its tokens are, and so how uneven its router.

    Returns ``(y, routing)``: y [tokens, d] is the sum over the token's
    chosen experts **that are held here** of weight x expert(x) (with every
    expert held, the whole layer); the weights come from the router over all
    experts.  No token is dropped whatever the imbalance."""
    with jax.named_scope("hvd_moe_route"):
        routing = route(x, router_kernel, top_k, first_expert,
                        w_gate.shape[0], renormalize)
    y = dispatch_experts(x, routing.experts, routing.weights, w_gate, w_up,
                         w_down, first_expert=first_expert,
                         experts_total=router_kernel.shape[1],
                         capacity_factor=capacity_factor)
    return y, routing
