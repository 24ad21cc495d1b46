"""The operations and bytes of family ``joyai``: the step's multiply-adds as
the algorithm needs them, the least work of the three flash kernels with a
second score operand (``hvd_flash_mla_*``) over the causal pairs, and of the
expert layers' grouped products from the rows routed.

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of a time by scope or name and of the
expert-load counters are ``trace_reduce``'s and ``sdar_flops``'s, named by
the metric files.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.zaya_flops import _least, _peaks, causal_pairs


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's."""
    layers = cfg["num_hidden_layers"]
    dense = min(cfg.get("first_k_dense_replace", 0), layers)
    return {
        "d": cfg["hidden_size"], "layers": layers, "dense": dense,
        "sparse": layers - dense,
        "heads": cfg.get("num_attention_heads_held",
                         cfg["num_attention_heads"]),
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"],
        "columns": cfg.get("feed_forward_columns_held",
                           cfg["intermediate_size"]),
        "experts": cfg["n_routed_experts"],
        "held": cfg.get("num_experts_held", cfg["n_routed_experts"]),
        "top_k": cfg["num_experts_per_tok"],
        "f": cfg["moe_intermediate_size"],
        "shared": cfg.get("n_shared_experts", 0)
        * cfg["moe_intermediate_size"],
        "vocab": cfg.get("vocab_size_held", cfg["vocab_size"]),
        "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part.  The five latent
    projections (W_qa, W_qb at 192 a head, W_kva, W_kvb at 256 a head, W_o);
    attention over the causal pairs at ``192 + 128`` multiply-adds a pair and
    head; the dense feed-forward of a dense layer; on an expert layer the
    router, the experts over ``positions x top_k x held / experts`` rows
    (what an even router sends to the held experts) and the shared expert
    over every position; the head over the positions that predict and the
    held vocabulary.  Recomputation is not counted."""
    s = _sizes(cfg, traffic)
    positions = s["length"] * s["batch"]
    rows = positions * s["top_k"] * s["held"] / s["experts"]
    qk = s["nope"] + s["rope"]
    return {
        "projections": s["layers"] * positions * (
            s["d"] * s["q_rank"] + s["q_rank"] * s["heads"] * qk
            + s["d"] * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * s["heads"] * (s["nope"] + s["v"])
            + s["heads"] * s["v"] * s["d"]),
        "attention": s["layers"] * causal_pairs(s["length"]) * s["batch"]
        * s["heads"] * (qk + s["v"]),
        "dense": s["dense"] * positions * 3 * s["d"] * s["columns"],
        "router": s["sparse"] * positions * s["d"] * s["experts"],
        "experts": s["sparse"] * rows * 3 * s["d"] * s["f"],
        "shared": s["sparse"] * positions * 3 * s["d"] * s["shared"],
        "head": (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]}


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def flash_mla_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the three ``hvd_flash_mla_*``
    kernels of one step (``flops.flash_least_seconds``'s rule, per kernel the
    larger of operations over peak FLOP/s and bytes over peak bytes/s), over
    the causal pairs ``L (L + 1) / 2`` a sequence and head.  Operations a
    pair and head: a score is 192 multiply-adds (128 + 64) and a product with
    v or dO 128, so the forward is 192 + 128, dq two scores' worth and one
    128 (s, dp 128, ds . [k | k_rope] 192), dkv 192 + 128 + 128 + 192 (s, dp,
    p . dO, ds . [q | q_rope]); padded lanes are the kernels' cost.  Bytes:
    the arrays 128 wide a head (q, k, v, o, dO and their gradients), q_rope
    and dq_rope 64 wide a head, **k_rope read once a call and not once a
    head** (dk_rope written once), the float32 row statistics."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    calls = s["layers"] * s["batch"]
    pair_heads = calls * s["heads"] * causal_pairs(s["length"])
    head_rows = calls * s["heads"] * s["length"]
    rows = calls * s["length"]
    qk = s["nope"] + s["rope"]
    # multiply-adds a pair; 128-wide arrays a head, 64-wide arrays a head,
    # k_rope-sized arrays a call, float32 statistics a head row
    kernels = {"fwd": (qk + s["v"], 4, 1, 1, 1),        # q k v | o; q_rope
               "dq": (2 * qk + s["v"], 5, 2, 1, 2),     # q k v dO | dq
               "dkv": (2 * qk + 2 * s["v"], 6, 1, 2, 2)}  # q k v dO | dk dv
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": {}}
    for name, (macs, wide, narrow, shared, stats) in kernels.items():
        kernel = _least(
            2.0 * pair_heads * macs,
            head_rows * (wide * s["v"] + narrow * s["rope"]) * item
            + shared * rows * s["rope"] * item + stats * head_rows * 4, peaks)
        out["kernels"][name] = kernel
        for key in ("seconds", "flops", "bytes"):
            out[key] += kernel[key]
    return out


def experts_step_least(ctx: dict) -> dict:
    """The least time of the expert layers' grouped products in one step, as
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
    layers = len(load) if load else s["sparse"]
    rows = (float(sum(sum(layer) for layer in load)) if load else
            1.0 * s["length"] * s["batch"] * s["top_k"] * s["held"]
            / s["experts"] * layers)
    kernels = layers * s["held"] * 3 * s["d"] * s["f"]
    return {"rows": rows, **_least(
        3 * 2.0 * rows * 3 * s["d"] * s["f"],
        3 * kernels * itemsize
        + 2 * rows * (2 * s["d"] + 2 * s["f"]) * itemsize, _peaks(ctx))}
