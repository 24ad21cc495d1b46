"""The least work of the flash kernels in a BERT step: non-causal, one key
length per sequence (``horovod_tpu/ops/flash_attention.py`` with ``kv_lens``).

``flops.flash_step_least`` reads GPT's keys and halves the square for the
causal mask; this reads the ``bert`` family's and sums over the lengths the
traffic file fixes.  The counts are ``flops.flash_least_seconds``'s.
"""

from __future__ import annotations

from benchmark import flops
from benchmark.families import bert


def flash_step_least(ctx: dict) -> dict:
    """The least time one chip could spend in the three kernels of one step.
    Work: per sequence of L real tokens, L real keys x L real queries (a
    padded key is seen by no query and a padded row's output is read by
    nothing, so neither is work the algorithm needs; the kernels compute
    whole 256-row steps and all 512 rows of a short sequence, which is
    their cost), over every head and layer, each of a sequence's arrays read
    or written once at its real rows.  A kernel call holds the whole batch,
    and one sequence's bytes can move while another's products run, so per
    kernel the bound is the larger of the batch's operations over peak
    FLOP/s and the batch's bytes over peak bytes/s, not the sum of each
    sequence's larger; the step's least time is the three kernels' sum.
    What ``layer_metrics/bert_flash*_roofline.json`` name as their
    ``least``; a chip whose HBM peak is not on record raises."""
    import jax.numpy as jnp

    cfg, traffic, peaks = ctx["cfg"], ctx["traffic"], ctx["peaks"]
    if not peaks.get("hbm_bytes_per_s"):
        raise ValueError(f"no HBM peak on record for {peaks['source']!r}: "
                         "enter it in benchmark/peaks.json with its source")
    seq = traffic.get("seq_len", cfg["max_position_embeddings"])
    kernels = {}
    for length in bert.chip_lengths(traffic, seq):
        one = flops.flash_least_seconds(
            batch=1, heads=cfg["num_attention_heads"], seq=length,
            head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
            layers=cfg["num_hidden_layers"], causal=False,
            itemsize=jnp.dtype(cfg["dtype"]).itemsize,
            peak_flops=peaks["bf16_flops_per_s"],
            peak_bytes_per_s=peaks["hbm_bytes_per_s"])
        for name, kernel in one["kernels"].items():
            mine = kernels.setdefault(name, {"flops": 0.0, "bytes": 0.0})
            mine["flops"] += kernel["flops"]
            mine["bytes"] += kernel["bytes"]
    for k in kernels.values():
        t_flops = k["flops"] / peaks["bf16_flops_per_s"]
        t_bytes = k["bytes"] / peaks["hbm_bytes_per_s"]
        k["bound"] = "flops" if t_flops >= t_bytes else "bytes"
        k["seconds"] = max(t_flops, t_bytes)
    return {**{key: sum(k[key] for k in kernels.values())
               for key in ("seconds", "flops", "bytes")}, "kernels": kernels}
