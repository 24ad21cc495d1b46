"""The operations and bytes of family ``laguna``: the step's multiply-adds as
the algorithm needs them, the least work of the flash kernels on the sliding
layers (over the band's own pairs) and on the full layers (over the causal
pairs), and of the expert layers' grouped products from the rows routed.

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of a time by scope or name and of the
expert-load counters are ``trace_reduce``'s and ``sdar_flops``'s, named by
the metric files; a least time is this family's own, since its layers are of
two kinds with different head counts.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.zaya_flops import _least, _peaks, causal_pairs

SLIDING = "sliding_attention"


def band_pairs(length: int, window: int) -> int:
    """(query, key) pairs of one causal sequence and head under a band of
    ``window`` keys, the query's own among them: the first ``window`` rows
    see 1 .. window keys, every later row ``window``."""
    window = min(window, length)
    return window * (window + 1) // 2 + (length - window) * window


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's; per layer its kind, the
    query heads held and the pairs a head computes."""
    kinds = cfg["layer_types"]
    layers = min(cfg.get("num_hidden_layers", len(kinds)), len(kinds))
    heads = cfg.get("num_attention_heads_per_layer_held",
                    cfg.get("num_attention_heads_per_layer"))
    sparse = cfg.get("mlp_layer_types", ["sparse"] * layers)
    length = traffic["seq_len"]
    return {
        "d": cfg["hidden_size"], "head_dim": cfg["head_dim"],
        "kinds": list(kinds[:layers]), "heads": list(heads[:layers]),
        "sparse": [kind == "sparse" for kind in sparse[:layers]],
        "kv_heads": cfg.get("num_key_value_heads_held",
                            cfg.get("num_key_value_heads")),
        "pairs": [band_pairs(length, cfg["sliding_window"])
                  if kind == SLIDING else causal_pairs(length)
                  for kind in kinds[:layers]],
        "columns": cfg.get("feed_forward_columns_held",
                           cfg.get("intermediate_size", 0)),
        "experts": cfg["num_experts"],
        "held": cfg.get("num_experts_held", cfg["num_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
        "shared": cfg.get("shared_expert_intermediate_size", 0),
        "vocab": cfg.get("vocab_size_held", cfg["vocab_size"]),
        "length": length, "batch": traffic["batch_per_chip"]}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part.  The projections (q, k,
    v, o and the gate's columns); attention over the band's pairs on a
    sliding layer and the causal pairs on a full one; the dense feed-forward
    of a dense layer; on a sparse layer the router, the experts over
    ``positions x top_k x held / experts`` rows (what an even router sends
    to the held experts) and the shared expert over every position; the head
    over the positions that predict and the held vocabulary.  Recomputation
    and the 0/1 product that spreads the gate are not counted."""
    s = _sizes(cfg, traffic)
    positions = s["length"] * s["batch"]
    rows = positions * s["top_k"] * s["held"] / s["experts"]
    out = dict.fromkeys(("projections", "attention", "dense", "router",
                         "experts", "shared"), 0.0)
    for heads, pairs, sparse in zip(s["heads"], s["pairs"], s["sparse"]):
        out["projections"] += positions * s["d"] * (
            2 * heads * s["head_dim"] + 2 * s["kv_heads"] * s["head_dim"]
            + heads)
        out["attention"] += pairs * s["batch"] * heads * s["head_dim"] * 2
        if sparse:
            out["router"] += positions * s["d"] * s["experts"]
            out["experts"] += rows * 3 * s["d"] * s["f"]
            out["shared"] += positions * 3 * s["d"] * s["shared"]
        else:
            out["dense"] += positions * 3 * s["d"] * s["columns"]
    out["head"] = (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]
    return out


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def _flash_step_least(ctx: dict, sliding: bool) -> dict:
    """The least time one chip could spend in the three flash kernels of one
    step on its sliding layers or on its full ones (``flops.
    flash_least_seconds``'s rule, per kernel the larger of operations over
    peak FLOP/s and bytes over peak bytes/s).  Operations: 2 x pairs x head
    width per matmul over the pairs of every query head held.  Bytes: the
    query-side arrays (q, o or dO, dq) over the rows of every query head; k
    and v, dk and dv over the rows of every key/value head, once a group
    however many query heads read them; the float32 row statistics."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    wide = s["head_dim"] * jnp.dtype(cfg["dtype"]).itemsize
    mine = [(heads, pairs) for kind, heads, pairs in zip(
        s["kinds"], s["heads"], s["pairs"]) if (kind == SLIDING) == sliding]
    pair_heads = sum(heads * pairs for heads, pairs in mine) * s["batch"]
    q_rows = sum(heads for heads, _ in mine) * s["length"] * s["batch"]
    kv_rows = len(mine) * s["kv_heads"] * s["length"] * s["batch"]
    # matmuls; query-side arrays, key-side arrays, float32 statistics a row
    kernels = {"fwd": (2, 2, 2, 1),      # q | o; k v; lse
               "dq": (3, 3, 2, 2),       # q dO | dq; k v; lse, delta
               "dkv": (4, 2, 4, 2)}      # q dO; k v | dk dv; lse, delta
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": {}}
    for name, (matmuls, q_arrays, kv_arrays, stats) in kernels.items():
        kernel = _least(
            matmuls * 2.0 * pair_heads * s["head_dim"],
            q_arrays * q_rows * wide + kv_arrays * kv_rows * wide
            + stats * q_rows * 4, peaks)
        out["kernels"][name] = kernel
        for key in ("seconds", "flops", "bytes"):
            out[key] += kernel[key]
    return out


def flash_swa_step_least(ctx: dict) -> dict:
    """The banded kernels (``hvd_flash_swa_*``) over the band's own pairs,
    ``W (W + 1) / 2 + (L - W) W`` a sequence and query head: what the tiles
    that straddle the band's two edges compute outside it is their cost."""
    return _flash_step_least(ctx, sliding=True)


def flash_full_step_least(ctx: dict) -> dict:
    """The un-banded kernels (``hvd_flash_*``) of the full layers over the
    causal pairs, ``L (L + 1) / 2`` a sequence and query head."""
    return _flash_step_least(ctx, sliding=False)


def experts_step_least(ctx: dict) -> dict:
    """The least time of the sparse layers' grouped products in one step, as
    ``sdar_flops.experts_step_least`` counts it: three products forward over
    the rows routed (gate, up, down: 3 d f multiply-adds a row) and twice
    that backward, against every held expert's three kernels read once
    forward and once backward and their gradients written once, and each
    row's input, hidden and output crossing once each way.  The rows are the
    probe's counters of the first batch where the cell has them, else what
    an even router sends."""
    import jax.numpy as jnp

    cfg = ctx["cfg"]
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    load = (ctx.get("cell") or {}).get("expert_load")
    layers = len(load) if load else sum(s["sparse"])
    rows = (float(sum(sum(layer) for layer in load)) if load else
            1.0 * s["length"] * s["batch"] * s["top_k"] * s["held"]
            / s["experts"] * layers)
    kernels = layers * s["held"] * 3 * s["d"] * s["f"]
    return {"rows": rows, **_least(
        3 * 2.0 * rows * 3 * s["d"] * s["f"],
        3 * kernels * itemsize
        + 2 * rows * (2 * s["d"] + 2 * s["f"]) * itemsize, _peaks(ctx))}
