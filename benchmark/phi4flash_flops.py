"""The operations and bytes of family ``phi4flash``: the step's multiply-adds
as the algorithm needs them, the least work of differential attention's two
softmax maps (whatever form they take on the kernels: **a map's visible
(query, key) pairs once, its scores ``head_dim`` wide and its value twice as
wide**), and the selective scans' least time by their bytes, as
``jamba_flops.py`` counts it.

Everything is computed from shapes (``flops.py``'s rule): nothing reads
``cost_analysis()``.  The readers of a time by scope or name are
``trace_reduce``'s and ``scope_ledger``'s, named by the metric files.
"""

from __future__ import annotations

from benchmark import flops, jamba_flops
from benchmark.jamba_flops import FLOAT32_BYTES
from benchmark.laguna_flops import band_pairs
from benchmark.sala_flops import _summed
from benchmark.zaya_flops import _least, _peaks, causal_pairs

MAMBA, MAMBA_MEMORY, GMU = "mamba", "mamba+memory", "gmu"
BANDED, FULL, CROSS = "banded", "full+kv", "cross"


def layer_kinds(cfg: dict) -> list:
    """The mixer of each layer run, by its published index (``layers_held``,
    of ``published_num_hidden_layers``; without the list the first
    ``num_hidden_layers``): even the Mamba side, odd the attention side; the
    memory at the half, the kept k and v one after."""
    layers = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    half = cfg.get("published_num_hidden_layers", len(layers)) // 2

    def kind(layer):
        if layer % 2 == 0:
            return (MAMBA if layer < half else MAMBA_MEMORY if layer == half
                    else GMU)
        return (BANDED if layer < half + 1 else FULL if layer == half + 1
                else CROSS)

    return [kind(i) for i in layers]


def _sizes(cfg: dict, traffic: dict) -> dict:
    """The sizes the counts below need, from the configuration's own keys
    (with ``assumed`` folded in) and the traffic's; a ``*_held`` key says
    what one chip holds of a width and defaults to the whole."""
    c = {**cfg.get("assumed", {}), **cfg}
    kinds = layer_kinds(c)
    heads = c["num_attention_heads"]
    return {
        "d": c["hidden_size"], "kinds": kinds,
        "channels": c.get("mamba_d_inner_held",
                          c.get("mamba_expand", 2) * c["hidden_size"]),
        "states": c.get("mamba_d_state", 16),
        "rank": c.get("mamba_dt_rank", 0),
        "heads": c.get("num_attention_heads_held", heads),
        "kv_heads": c.get("num_key_value_heads_held",
                          c["num_key_value_heads"]),
        "head_dim": c.get("head_dim", c["hidden_size"] // heads),
        "window": c["sliding_window"],
        "f": c.get("feed_forward_columns_held", c["intermediate_size"]),
        "vocab": c.get("vocab_size_held", c["vocab_size"]),
        "length": traffic["seq_len"], "batch": traffic["batch_per_chip"]}


def map_pairs(s: dict, banded: bool) -> int:
    """(query, key) pairs one sequence and map head sees."""
    return (band_pairs(s["length"], s["window"]) if banded
            else causal_pairs(s["length"]))


# Products a visible pair and map head, in units of ``head_dim``: the scores
# are ``head_dim`` wide and the value two of them.  fwd: s (1), p V (2); dq:
# s (1), dO V^T (2), ds k (1); dkv: s (1), dO V^T (2), p^T dO (2), ds^T q (1).
MAP_WIDTHS = {"fwd": 3, "dq": 4, "dkv": 6}


def forward_macs(cfg: dict, traffic: dict) -> dict:
    """Multiply-adds of one chip's forward, by part: the Mamba mixers' four
    products (in, x, dt, out), the gate layers' two, the attention layers'
    projections (a cross layer's q and o alone), both maps of every
    attention layer over their visible pairs (each map once: scores
    ``head_dim`` wide, value twice that), every block's feed-forward, the
    head over the positions that predict and the rows held.  The
    convolution, the scan's own arithmetic, the softmax and recomputation are
    not counted."""
    s = _sizes(cfg, traffic)
    kinds = s["kinds"]
    positions = s["length"] * s["batch"]
    wide, kv_wide = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    mamba = sum(k in (MAMBA, MAMBA_MEMORY) for k in kinds)
    own_kv = sum(k in (BANDED, FULL) for k in kinds)
    # heads map heads a layer: two maps of heads / 2 pairs.
    maps = s["batch"] * s["heads"] * MAP_WIDTHS["fwd"] * s["head_dim"] * (
        kinds.count(BANDED) * map_pairs(s, True)
        + (kinds.count(FULL) + kinds.count(CROSS)) * map_pairs(s, False))
    return {
        "mamba_projections": mamba * positions * s["channels"] * (
            2 * s["d"] + s["rank"] + 2 * s["states"] + s["rank"] + s["d"]),
        "gate_layers": kinds.count(GMU) * positions * 2 * s["d"]
        * s["channels"],
        "attention_projections": positions * s["d"] * (
            own_kv * (2 * wide + 2 * kv_wide) + kinds.count(CROSS) * 2 * wide),
        "attention": maps,
        "feed_forward": len(kinds) * positions * 3 * s["d"] * s["f"],
        "head": (s["length"] - 1) * s["batch"] * s["d"] * s["vocab"]}


def model_flops(cfg: dict, traffic: dict, chips: int) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch."""
    return flops.train_flops(sum(forward_macs(cfg, traffic).values())) * chips


def _maps_least(ctx: dict, banded: bool) -> dict:
    """The least time one chip could spend in the three flash kernels of the
    banded (or the full-context) differential layers of one step, per kernel
    the larger of operations over peak FLOP/s and bytes over peak bytes/s.
    Operations: ``MAP_WIDTHS`` x ``head_dim`` multiply-adds a visible pair
    and map head, two maps of ``heads / 2`` pairs a layer, **whatever the
    kernels' operands are padded to**.  Bytes: q and dq over every query
    head's ``head_dim``, k, v, dk, dv over every key/value head's, once a
    group; both maps' outputs and their cotangents in the configuration's
    dtype, ``heads / 2`` pairs of ``2 head_dim`` a map; one float32
    statistic a row and map head (two in the backward)."""
    import jax.numpy as jnp

    cfg, peaks = ctx["cfg"], _peaks(ctx)
    s = _sizes(cfg, ctx["traffic"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    layers = (s["kinds"].count(BANDED) if banded
              else s["kinds"].count(FULL) + s["kinds"].count(CROSS))
    calls = layers * s["batch"]
    pair_heads = calls * s["heads"] * map_pairs(s, banded)
    rows = calls * s["length"]
    q = rows * s["heads"] * s["head_dim"] * item
    kv = rows * s["kv_heads"] * s["head_dim"] * item          # k or v
    outs = rows * s["heads"] * 2 * s["head_dim"] * item       # both maps
    stats = rows * s["heads"] * FLOAT32_BYTES
    nbytes = {
        "fwd": q + 2 * kv + outs + stats,
        "dq": 2 * q + 2 * kv + 2 * outs + 2 * stats,
        "dkv": q + 4 * kv + 2 * outs + 2 * stats}
    return _summed({name: _least(
        2.0 * pair_heads * MAP_WIDTHS[name] * s["head_dim"], nbytes[name],
        peaks) for name in MAP_WIDTHS})


def flash_diff_step_least(ctx: dict) -> dict:
    """:func:`_maps_least` of the full-context layers (the one that keeps its
    k and v and the cross layers that read them)."""
    return _maps_least(ctx, banded=False)


def flash_swa_step_least(ctx: dict) -> dict:
    """:func:`_maps_least` of the banded layers."""
    return _maps_least(ctx, banded=True)


def scan_step_least(ctx: dict) -> dict:
    """``jamba_flops.scan_step_least`` at this family's Mamba layers (each
    one of them, the one that hands out its memory among them: its ``y`` is
    written once whoever reads it) and channels: the configuration restated
    in that function's keys, a layer order under which every layer counted
    is a Mamba layer."""
    s = _sizes(ctx["cfg"], ctx["traffic"])
    mamba = sum(k in (MAMBA, MAMBA_MEMORY) for k in s["kinds"])
    as_jamba = {
        "hidden_size": s["d"], "num_hidden_layers": mamba,
        "attn_layer_period": mamba + 1, "attn_layer_offset": mamba,
        "mamba_d_inner_held": s["channels"], "mamba_d_state": s["states"],
        "mamba_dt_rank": s["rank"], "num_attention_heads": s["heads"],
        "num_key_value_heads": s["kv_heads"], "head_dim": s["head_dim"],
        "vocab_size": s["vocab"], "dtype": ctx["cfg"]["dtype"]}
    return jamba_flops.scan_step_least({**ctx, "cfg": as_jamba})
