"""The operations and bytes of family ``jamba``: the step's multiply-adds as
the algorithm needs them, and the least time of the selective-scan kernels,
which is **bytes over the HBM peak**, not FLOPs over the MXU's: the scan is
elementwise and exponential work over a state that never leaves the chip's
fast memory, ``peaks.json`` has no VPU peak on record, and what a call has to
move whatever implements it is its operands once and its results once.

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of a time by scope or by name are
``trace_reduce.py``'s, named by the metric files.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.zaya_flops import causal_pairs

# dt, B and C reach the scan in float32 (the model's own norms and softplus
# make them); u and y in the configuration's dtype.
FLOAT32_BYTES = 4


def layer_kinds(cfg: dict) -> list:
    """``"attention"`` where ``i mod attn_layer_period == attn_layer_offset``
    and ``"mamba"`` otherwise, for the layers the configuration runs."""
    return ["attention" if i % cfg["attn_layer_period"]
            == cfg["attn_layer_offset"] else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's; a ``*_held`` key says
    what one chip holds of a width and defaults to the whole."""
    kinds = layer_kinds(cfg)
    heads = cfg["num_attention_heads"]
    return {
        "d": cfg["hidden_size"],
        "mamba_layers": kinds.count("mamba"),
        "attention_layers": kinds.count("attention"),
        "channels": cfg.get("mamba_d_inner_held",
                            cfg.get("mamba_expand", 2) * cfg["hidden_size"]),
        "states": cfg.get("mamba_d_state", 16),
        "rank": cfg.get("mamba_dt_rank", 0),
        "heads": cfg.get("num_attention_heads_held", heads),
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "f": cfg.get("feed_forward_columns_held",
                     cfg.get("intermediate_size", 0)),
        "vocab": cfg.get("vocab_size_held", cfg["vocab_size"]),
        "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part: the Mamba mixers' four
    products (in, x, dt, out: the depthwise convolution and the scan's own
    arithmetic are no matrix products and are not counted, as softmax is
    not), the attention layers' projections and their causal pairs, every
    block's feed-forward, the head over the positions that predict and the
    rows held.  Recomputation is not counted."""
    s = _sizes(cfg, traffic)
    positions = s["length"] * s["batch"]
    width = s["heads"] * s["head_dim"]
    return {
        "mamba_projections": s["mamba_layers"] * positions * s["channels"] * (
            2 * s["d"] + s["rank"] + 2 * s["states"] + s["rank"] + s["d"]),
        "attention_projections": s["attention_layers"] * positions * s["d"]
        * (2 * width + 2 * s["kv_heads"] * s["head_dim"]),
        "attention": (s["attention_layers"] * causal_pairs(s["length"])
                      * s["batch"] * width * 2),
        "feed_forward": ((s["mamba_layers"] + s["attention_layers"])
                         * positions * 3 * s["d"] * s["f"]),
        "head": (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]}


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def scan_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the selective scans of one
    step, forward and backward, **by their bytes alone**: a call reads each
    operand once and writes each result once (``u``, ``y`` and their
    cotangents ``[B, S, C]`` in the configuration's dtype, ``dt`` and its
    cotangent in float32, ``B``, ``C`` and theirs ``[B, S, N]`` in float32,
    ``A`` and ``D`` and theirs once), over the HBM peak, times the calls the
    model's mathematics needs: one forward and one backward a Mamba layer.
    A checkpointed block runs its forward twice: the reading holds both runs
    against the work of one."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], ctx["peaks"]
    if not peaks.get("hbm_bytes_per_s"):
        raise ValueError(f"no HBM peak on record for {peaks['source']!r}: "
                         "enter it in benchmark/peaks.json with its source")
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    rows = s["length"] * s["batch"]
    wide = rows * s["channels"]                  # elements of u, dt, y
    narrow = rows * s["states"] * FLOAT32_BYTES  # bytes of B or of C
    leaves = s["channels"] * (s["states"] + 1) * FLOAT32_BYTES   # A and D
    kernels = {
        # u, dt | B, C | A, D in; y out
        "fwd": wide * (itemsize + FLOAT32_BYTES + itemsize) + 2 * narrow
        + leaves,
        # u, dt, dy | B, C | A, D in; du, ddt | dB, dC | dA, dD out
        "bwd": wide * (3 * itemsize + 2 * FLOAT32_BYTES) + 4 * narrow
        + 2 * leaves}
    out = {"seconds": 0.0, "bytes": 0.0, "kernels": {}}
    for name, nbytes in kernels.items():
        nbytes *= s["mamba_layers"]
        kernel = {"bytes": nbytes, "bound": "bytes",
                  "seconds": nbytes / peaks["hbm_bytes_per_s"]}
        out["kernels"][name] = kernel
        out["seconds"] += kernel["seconds"]
        out["bytes"] += nbytes
    return out
