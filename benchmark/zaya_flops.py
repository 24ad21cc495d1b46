"""The operations and bytes of family ``zaya``: the step's multiply-adds as the
algorithm needs them, the least work of the causal grouped-query flash
kernels, of the expert layer's grouped products from the rows routed, and of
the tied head's three products, and the reader that holds the grouped
products' device time against their least time (``trace_reduce.roofline_pct``
holds the others).

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of the counters and of a time by scope or
name are ``sdar_flops.py``'s, named by the metric files; a least time is this
family's own, since its sizes are (one copy of the sequence, not two; experts
as wide as the model; a head over every position).
"""

from __future__ import annotations

from typing import Optional

from benchmark import flops, sdar_flops


def causal_pairs(length: int) -> int:
    """(query, key) pairs of one causal sequence and head: the diagonal and
    all below it."""
    return length * (length + 1) // 2


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's."""
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "layers": cfg["num_hidden_layers"],
            "taps1": cfg.get("cca_time1", 2),
            "experts": cfg["num_experts"],
            "held": cfg.get("num_experts_held", cfg["num_experts"]),
            "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "router": cfg.get("router_hidden_size", 0),
            "vocab": cfg.get("vocab_size_held", cfg["vocab_size"]),
            "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part.  The projections (q, k,
    the two values, o); the grouped convolution over the sequence (the
    depthwise one is no matrix product); attention over the causal pairs;
    the router's down-projection and its MLP; the experts over ``positions x
    top_k x held / experts`` rows, what an even router sends to the held
    experts; the head over the positions that predict and the held
    vocabulary.  Recomputation is not counted."""
    s = _sizes(cfg, traffic)
    positions = s["length"] * s["batch"]
    latent = (s["heads"] + s["kv_heads"]) * s["head_dim"]
    values = 2 * s["head_dim"]
    rows = positions * s["top_k"] * s["held"] / s["experts"]
    per_layer = {
        "projections": positions * s["d"] * (
            latent + values + s["heads"] * s["head_dim"]),
        "cca_conv1": positions * s["taps1"] * latent * s["head_dim"],
        "attention": (causal_pairs(s["length"]) * s["batch"] * s["heads"]
                      * s["head_dim"] * 2),
        "router": positions * s["router"] * (
            s["d"] + 2 * s["router"] + s["experts"]),
        "experts": rows * 3 * s["d"] * s["f"]}
    out = {k: v * s["layers"] for k, v in per_layer.items()}
    out["head"] = (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]
    return out


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def _peaks(ctx: dict) -> dict:
    peaks = ctx["peaks"]
    if not peaks.get("hbm_bytes_per_s"):
        raise ValueError(f"no HBM peak on record for {peaks['source']!r}: "
                         "enter it in benchmark/peaks.json with its source")
    return peaks


def _least(ops: float, nbytes: float, peaks: dict) -> dict:
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": ops, "bytes": nbytes,
            "bound": "flops" if t_ops >= t_bytes else "bytes",
            "seconds": max(t_ops, t_bytes)}


def flash_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the three flash kernels of one
    step (``flops.flash_least_seconds``'s rule, per kernel the larger of
    operations over peak FLOP/s and bytes over peak bytes/s), causal with
    grouped key/value heads.  Operations: 2 x pairs x head width per matmul
    over the causal pairs of every query head.  Bytes: the query-side arrays
    (q, o or dO, dq) over the rows of every query head; k and v, dk and dv
    over the rows of every key/value head, once a group however many query
    heads read them; the float32 row statistics."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    pairs = causal_pairs(s["length"])
    q_rows = s["length"] * s["heads"] * s["batch"] * s["layers"]
    kv_rows = s["length"] * s["kv_heads"] * s["batch"] * s["layers"]
    wide = s["head_dim"] * itemsize
    # matmuls; query-side arrays, key-side arrays, float32 statistics a row
    kernels = {"fwd": (2, 2, 2, 1),      # q | o; k v; lse
               "dq": (3, 3, 2, 2),       # q dO | dq; k v; lse, delta
               "dkv": (4, 2, 4, 2)}      # q dO; k v | dk dv; lse, delta
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": {}}
    for name, (matmuls, q_arrays, kv_arrays, stats) in kernels.items():
        kernel = _least(
            matmuls * 2.0 * pairs * s["head_dim"] * s["heads"] * s["batch"]
            * s["layers"],
            q_arrays * q_rows * wide + kv_arrays * kv_rows * wide
            + stats * q_rows * 4, peaks)
        out["kernels"][name] = kernel
        for key in ("seconds", "flops", "bytes"):
            out[key] += kernel[key]
    return out


def routed_rows(ctx: dict) -> float:
    """Rows routed to the held experts in one step, all layers: the probe's
    counters of the first batch where the cell has them (the batch is the
    same every step), else what an even router sends."""
    load = (ctx.get("cell") or {}).get("expert_load")
    if load:
        return float(sum(sum(layer) for layer in load))
    s = _sizes(ctx["cfg"], ctx["traffic"])
    return (1.0 * s["length"] * s["batch"] * s["top_k"] * s["held"]
            / s["experts"] * s["layers"])


def experts_step_least(ctx: dict) -> dict:
    """The least time of the expert layers' grouped products in one step, as
    ``sdar_flops.experts_step_least`` counts it: three products forward over
    the rows routed (gate, up, down: 3 d f multiply-adds a row) and twice
    that backward, against every held expert's three kernels read once
    forward and once backward and their gradients written once, and each
    row's input, hidden and output crossing once each way."""
    import jax.numpy as jnp

    cfg = ctx["cfg"]
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    rows = routed_rows(ctx)
    kernels = s["layers"] * s["held"] * 3 * s["d"] * s["f"]
    return {"rows": rows, **_least(
        3 * 2.0 * rows * 3 * s["d"] * s["f"],
        3 * kernels * itemsize
        + 2 * rows * (2 * s["d"] + 2 * s["f"]) * itemsize, _peaks(ctx))}


def experts_roofline_pct(trace, ctx: dict, scope: str, pattern: str,
                         **_) -> Optional[float]:
    """``experts_step_least`` over ``sdar_flops.scope_or_name_ms``, in per
    cent."""
    took = sdar_flops.scope_or_name_ms(trace, ctx, scope, pattern)
    if not took:
        return None
    return 100.0 * experts_step_least(ctx)["seconds"] * 1e3 / took


def head_step_least(ctx: dict) -> dict:
    """The least time of the tied head's three products in one step by their
    FLOPs: the logits, ``d logits . E`` and ``d logits^T . x``, each 2 x
    positions x d x held rows, over every position of the sequence (the
    blocked head computes the last one's too, whose weight is zero)."""
    s = _sizes(ctx["cfg"], ctx["traffic"])
    ops = 3 * 2.0 * s["length"] * s["batch"] * s["d"] * s["vocab"]
    return {"flops": ops, "bound": "flops",
            "seconds": ops / _peaks(ctx)["bf16_flops_per_s"]}
