"""The operations and bytes of family ``sdar``: the step's multiply-adds as the
algorithm needs them, the least work of the flash kernels under the
block-diffusion mask with grouped key/value heads, the least work of the
expert layer's grouped products from the rows routed, and two readers: of
the probe's expert-load counters, and of the time of ops found by a scope or
by a name (``zaya_flops`` and ZAYA's metric files use it; this family's
products are found by their scope alone, through ``trace_reduce``'s readers).

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  A jaxpr walk would not do here: ``jax.lax.ragged_dot``
is no ``dot_general``, the dense fallback's attention is the whole 2L x 2L
square, and the program recomputes (the expert layer's backward, a
checkpointed block), which is its cost and not the algorithm's.
"""

from __future__ import annotations

from typing import Optional

from benchmark import flops


def live_pairs(length: int, block: int) -> dict:
    """(query, key) pairs a sequence of L tokens in blocks of B needs under
    the block-diffusion mask over ``[clean ; noised]``, by piece, with n =
    L / B: clean on clean B^2 n (n + 1) / 2, noised on the clean blocks
    before B^2 n (n - 1) / 2, noised on its own noised block n B^2.  Together
    L^2 (1 + 1 / n) of the (2L)^2 square; the first two are the flash
    kernels' main walk (L^2 exactly, ``kernels``); the third is L x B, 0.1 %
    of them at L 4096 and B 4, and since PR 45 runs inside the same three
    kernels as 128 x 128 squares on the diagonal."""
    n = length // block
    assert n * block == length, (length, block)
    pairs = {"clean_on_clean": block * block * n * (n + 1) // 2,
             "noised_on_clean": block * block * n * (n - 1) // 2,
             "noised_on_own_block": n * block * block}
    pairs["kernels"] = pairs["clean_on_clean"] + pairs["noised_on_clean"]
    pairs["all"] = pairs["kernels"] + pairs["noised_on_own_block"]
    return pairs


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's."""
    return {"d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "layers": cfg["num_hidden_layers"],
            "experts": cfg["num_experts"],
            "held": cfg.get("num_experts_held", cfg["num_experts"]),
            "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "vocab": cfg.get("vocab_size_held", cfg["vocab_size"]),
            "block": traffic.get("block_length", cfg["block_length"]),
            "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part.  Attention over the live
    pairs of the mask only; the experts over ``positions x top_k x held /
    experts`` rows, what an even router sends to the held experts; the head
    over the noised half and the held vocabulary.  Recomputation is not
    counted."""
    s = _sizes(cfg, traffic)
    positions = 2 * s["length"] * s["batch"]
    qkvo = s["d"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    rows = positions * s["top_k"] * s["held"] / s["experts"]
    per_layer = {
        "projections": positions * qkvo,
        "attention": (live_pairs(s["length"], s["block"])["all"] * s["batch"]
                      * s["heads"] * s["head_dim"] * 2),
        "router": positions * s["d"] * s["experts"],
        "experts": rows * 3 * s["d"] * s["f"]}
    out = {k: v * s["layers"] for k, v in per_layer.items()}
    out["head"] = s["length"] * s["batch"] * s["d"] * s["vocab"]
    return out


def flash_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the three flash kernels of one
    step (``flops.flash_least_seconds``'s rule, per kernel the larger of
    operations over peak FLOP/s and bytes over peak bytes/s).  Operations:
    2 x pairs x head width per matmul over ``live_pairs(...)["kernels"]``,
    L^2 a sequence and a query head.  The noised copy's own L x B pairs,
    which the kernels have computed themselves since PR 45 (a 128 x 128
    square where a 4 x 4 block is needed), are left out of the least work:
    they are 0.1 % of it, and what the squares take (6.4 of 125 ms a step,
    PERF.md section 6, PR 45) reads in the three rooflines as cost.  Bytes:
    the query-side arrays
    (q, o or dO, dq) over the 2L rows of every query head; k and v, dk and
    dv over the clean copy's L rows of every key/value head, once a group
    however many query heads read them; the float32 row statistics."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], ctx["peaks"]
    if not peaks.get("hbm_bytes_per_s"):
        raise ValueError(f"no HBM peak on record for {peaks['source']!r}: "
                         "enter it in benchmark/peaks.json with its source")
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    pairs = live_pairs(s["length"], s["block"])["kernels"]
    q_rows = 2 * s["length"] * s["heads"] * s["batch"] * s["layers"]
    kv_rows = s["length"] * s["kv_heads"] * s["batch"] * s["layers"]
    wide = s["head_dim"] * itemsize
    # matmuls; query-side arrays, key-side arrays, float32 statistics a row
    kernels = {"fwd": (2, 2, 2, 1),      # q | o; k v; lse
               "dq": (3, 3, 2, 2),       # q dO | dq; k v; lse, delta
               "dkv": (4, 2, 4, 2)}      # q dO; k v | dk dv; lse, delta
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": {}}
    for name, (matmuls, q_arrays, kv_arrays, stats) in kernels.items():
        ops = (matmuls * 2.0 * pairs * s["head_dim"] * s["heads"]
               * s["batch"] * s["layers"])
        nbytes = (q_arrays * q_rows * wide + kv_arrays * kv_rows * wide
                  + stats * q_rows * 4)
        t_ops = ops / peaks["bf16_flops_per_s"]
        t_bytes = nbytes / peaks["hbm_bytes_per_s"]
        out["kernels"][name] = {
            "flops": ops, "bytes": nbytes,
            "bound": "flops" if t_ops >= t_bytes else "bytes",
            "seconds": max(t_ops, t_bytes)}
        out["seconds"] += max(t_ops, t_bytes)
        out["flops"] += ops
        out["bytes"] += nbytes
    return out


def routed_rows(ctx: dict) -> float:
    """Rows routed to the held experts in one step, all layers: the probe's
    counters of the first batch where the cell has them (the batch is the
    same every step), else what an even router sends."""
    load = (ctx.get("cell") or {}).get("expert_load")
    if load:
        return float(sum(sum(layer) for layer in load))
    s = _sizes(ctx["cfg"], ctx["traffic"])
    return (2.0 * s["length"] * s["batch"] * s["top_k"] * s["held"]
            / s["experts"] * s["layers"])


def experts_step_least(ctx: dict) -> dict:
    """The least time of the expert layers' grouped products in one step:
    three products forward over the rows routed (gate, up, down: 3 d f
    multiply-adds a row) and twice that backward, against every held
    expert's three kernels read once forward and once backward and their
    gradients written once, and each row's input, hidden and output
    crossing once each way."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], ctx["peaks"]
    s = _sizes(cfg, ctx["traffic"])
    itemsize = jnp.dtype(cfg["dtype"]).itemsize
    rows = routed_rows(ctx)
    ops = 3 * 2.0 * rows * 3 * s["d"] * s["f"]
    kernels = s["layers"] * s["held"] * 3 * s["d"] * s["f"]
    nbytes = (3 * kernels * itemsize
              + 2 * rows * (2 * s["d"] + 2 * s["f"]) * itemsize)
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": ops, "bytes": nbytes, "rows": rows,
            "bound": "flops" if t_ops >= t_bytes else "bytes",
            "seconds": max(t_ops, t_bytes)}


def scope_or_name_ms(trace, ctx: dict, scope: str, pattern: str,
                     **_) -> Optional[float]:
    """Device time a step spends in the core's ops whose scope matches
    ``scope`` **or** whose name matches ``pattern`` (the union of their
    intervals, mean over the chips), where ``trace_reduce.op_time_ms`` takes
    one conjunction.  Written when XLA's expansion of a ``ragged_dot`` left
    the products without any scope (``%ragged-dot-none.N``, PR 34); the
    kernels of ``ops/grouped_matmul.py`` carry the caller's scope, so since
    PR 46 this family's two metrics select by the scope alone and only
    ZAYA's files name this reader.  None when nothing matches."""
    import re

    from benchmark import trace_reduce

    by_scope, by_name = re.compile(scope), re.compile(pattern)
    busy = [trace_reduce.busy_ns(
        [e for e in trace_reduce.sync_ops(events)
         if by_scope.search(e.scope) or by_name.search(e.name)],
        trace.window) for _, events in sorted(trace.devices.items())]
    if not any(busy):
        return None
    return trace_reduce.per_step(sum(busy) / len(busy), trace.steps)


def expert_load_max_over_mean(trace, ctx: dict, **_) -> Optional[float]:
    """The busiest held expert's rows over the mean of the held experts',
    the largest over the layers: what ``families/sdar.py:probe`` counted on
    the first batch (``cell["expert_load"]``, rows by layer and expert).  1
    is an even router; the grouped products wait for the busiest.  None
    where the cell has no such counter."""
    load = (ctx.get("cell") or {}).get("expert_load")
    if not load:
        return None
    return max(max(layer) * len(layer) / max(1, sum(layer)) for layer in load)


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips
