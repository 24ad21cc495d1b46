"""The operations and bytes of family ``sala``: the step's multiply-adds as the
algorithm needs them, the least work of the lightning-attention kernels
(``hvd_lightning_fwd`` / ``_dq`` / ``_dkv``) over their four products a chunk,
and of the selected walk's flash kernels (``hvd_flash_sel_fwd`` / ``_dq`` /
``_dkv``) over **the chosen, causally visible pairs**, whatever the walk
visits.

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of a time by scope or name are
``trace_reduce``'s, named by the metric files; the walk's counters are read
here (:func:`visited_over_chosen`) from what ``families/sala.py`` counts on
the first batch.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.zaya_flops import _least, _peaks, causal_pairs

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` and its ``sparse_config`` folded in) and the
    traffic's.  The kinds of the layers run are the first
    ``num_hidden_layers`` of ``mixer_types`` (all of them where the count is
    not given, as ``laguna_flops`` reads ``layer_types``)."""
    assumed = cfg.get("assumed", {})
    c = {**assumed, **assumed.get("sparse_config", {}), **cfg}
    kinds = c["mixer_types"][:c.get("num_hidden_layers")]
    return {
        "d": c["hidden_size"],
        "lightning_layers": sum(k == LIGHTNING for k in kinds),
        "sparse_layers": sum(k == SPARSE for k in kinds),
        "heads": c.get("num_attention_heads_held", c["num_attention_heads"]),
        "kv_heads": c.get("num_key_value_heads_held",
                          c["num_key_value_heads"]),
        "head_dim": c["head_dim"],
        "lightning_heads": c.get("lightning_heads_held", c["lightning_nh"]),
        "lightning_dim": c["lightning_head_dim"],
        "chunk": c["lightning_chunk"],
        "columns": c.get("feed_forward_columns_held", c["intermediate_size"]),
        "vocab": c.get("vocab_size_held", c["vocab_size"]),
        "block": c["block_size"], "topk": c["topk"],
        "dense_len": c["dense_len"],
        "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def chosen_blocks(length: int, block: int, topk: int) -> int:
    """(query, key block) pairs a sequence chooses: a query in block b sees
    b + 1 blocks and takes ``topk`` of them, or all."""
    return sum(min(t // block + 1, topk) for t in range(length))


def visible_pairs(s: dict) -> int:
    """(query, key) pairs of one sequence and head of a sparse layer: every
    causal pair up to ``dense_len`` tokens; in a longer sequence the keys of
    the chosen blocks, the query's own block up to the query itself."""
    length, block = s["length"], s["block"]
    if length <= s["dense_len"]:
        return causal_pairs(length)
    return sum((min(t // block + 1, s["topk"]) - 1) * block + t % block + 1
               for t in range(length))


def lightning_chunk_macs(s: dict) -> dict:
    """Multiply-adds of one chunk of one head, by kernel: the forward's four
    products (``Q K^T`` and ``P V`` at the causal half of their square, ``Q
    S`` and ``K^T V`` whole), dq's the same on other operands, dkv's seven
    (four half squares, ``K dS``, ``V dS^T``, ``Q^T dO``)."""
    c, d = min(s["chunk"], s["length"]), s["lightning_dim"]
    half, state = c * (c + 1) // 2 * d, c * d * d
    return {"fwd": 2 * half + 2 * state, "dq": 2 * half + 2 * state,
            "dkv": 4 * half + 3 * state}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part.  The five projections of
    each mixer (q, k, v, gate, o; a sparse layer's k and v at its key/value
    heads); the selected attention over the visible pairs at two products a
    pair and head; the lightning layers over the forward's four products a
    chunk; the SwiGLU; the head over the positions that predict and the held
    vocabulary.  The selection's own scores and recomputation are not
    counted."""
    s = _sizes(cfg, traffic)
    positions = s["length"] * s["batch"]
    chunks = positions // min(s["chunk"], s["length"])
    wide = s["heads"] * s["head_dim"]
    return {
        "lightning_projections": s["lightning_layers"] * positions * s["d"]
        * 5 * s["lightning_heads"] * s["lightning_dim"],
        "sparse_projections": s["sparse_layers"] * positions * s["d"]
        * (3 * wide + 2 * s["kv_heads"] * s["head_dim"]),
        "sparse_attention": s["sparse_layers"] * visible_pairs(s) * s["batch"]
        * 2 * wide,
        "lightning": s["lightning_layers"] * chunks * s["lightning_heads"]
        * lightning_chunk_macs(s)["fwd"],
        "feed_forward": (s["lightning_layers"] + s["sparse_layers"])
        * positions * 3 * s["d"] * s["columns"],
        "head": (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]}


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def _summed(kernels: dict) -> dict:
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": kernels}
    for kernel in kernels.values():
        for key in ("seconds", "flops", "bytes"):
            out[key] += kernel[key]
    return out


def lightning_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the three lightning kernels of
    one step (per kernel the larger of operations over peak FLOP/s and bytes
    over peak bytes/s): :func:`lightning_chunk_macs` over every chunk and
    held head of every lightning layer, against each operand read once and
    each result written once (forward and dq: three in, one out; dkv: four
    in, two out), all in the configuration's dtype."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    calls = s["lightning_layers"] * s["batch"]
    chunk_heads = calls * s["lightning_heads"] * (
        s["length"] // min(s["chunk"], s["length"]))
    array = calls * s["length"] * s["lightning_heads"] * s["lightning_dim"] \
        * item
    macs = lightning_chunk_macs(s)
    return _summed({name: _least(2.0 * chunk_heads * macs[name],
                                 arrays * array, peaks)
                    for name, arrays in (("fwd", 4), ("dq", 4), ("dkv", 6))})


def flash_sel_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the selected walk's three
    kernels of one step, over :func:`visible_pairs` a sequence and query head
    of every sparse layer, **what was chosen and not what the tiles visit**.
    Operations a pair and head: the forward two products of the head's width
    (s, p v), dq three (s, dp, ds k), dkv four (s, dp, p dO, ds q).  Bytes:
    the query-side arrays (q, o or dO, dq) over the rows of every query head;
    k and v, dk and dv over the rows of every key/value head, once a group;
    the float32 row statistics; the bits once a key/value head.  The float32
    part of dk and dv a query head that the dkv kernel leaves is its cost,
    not the algorithm's."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    calls = s["sparse_layers"] * s["batch"]
    pair_heads = calls * s["heads"] * visible_pairs(s)
    q_rows = calls * s["heads"] * s["length"]
    kv_rows = calls * s["kv_heads"] * s["length"]
    bits = kv_rows * (s["length"] // s["block"]) / 8.0
    width = s["head_dim"]
    # products a pair; query-side arrays, key-side arrays, statistics a row
    kernels = {"fwd": (2, 2, 2, 1), "dq": (3, 3, 2, 2), "dkv": (4, 2, 4, 2)}
    return _summed({name: _least(
        2.0 * pair_heads * products * width,
        (q_side * q_rows + k_side * kv_rows) * width * item
        + stats * q_rows * 4 + bits, peaks)
        for name, (products, q_side, k_side, stats) in kernels.items()})


def visited_over_chosen(trace, ctx: dict):
    """(query, key block) pairs the forward's walk visits (a tile's listed
    key steps x the tile's rows x the step's blocks) over the pairs chosen,
    all sparse layers of the first batch together, from the counters
    ``families/sala.py`` reads there (``cell["walk"]``): 1 is a walk that
    visits only what was chosen.  None where the cell has no counters."""
    del trace
    walk = (ctx.get("cell") or {}).get("walk") or {}
    if not walk.get("chosen"):
        return None
    return walk["visited"] / walk["chosen"]
