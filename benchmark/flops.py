"""The operations and bytes the benchmark's utilizations are measured against.

Everything here is computed from shapes.  Nothing reads ``cost_analysis()``:
that counts what XLA executes (optimizer, recompute) and reads zero for a
Pallas ``tpu_custom_call``.

Model FLOPs: 2 x the multiply-accumulates of every ``dot_general`` and
``conv_general_dilated`` in the jaxpr of the model's *forward* at the cell's
shapes, x 3 for forward + backward (each matmul of the forward has two
matmuls of the same size in the backward).  Optimizer arithmetic, elementwise
work and recomputation are excluded; a convolution counts its whole window at
every output position, padding included, as published MAC counts do.
"""

from __future__ import annotations

import math
from typing import Iterator, NamedTuple


class Contraction(NamedTuple):
    """One ``dot_general`` or ``conv_general_dilated`` of a jaxpr."""

    primitive: str
    macs: int
    batched: bool  # a dot_general with batch dimensions (attention's two)


def _sub_jaxprs(params: dict) -> Iterator:
    for value in params.values():
        for item in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(item, "jaxpr", item)  # ClosedJaxpr -> Jaxpr
            if hasattr(inner, "eqns"):
                yield inner


def _dot_macs(eqn) -> Contraction:
    lhs, rhs = (v.aval.shape for v in eqn.invars)
    (lhs_contract, _), (lhs_batch, _) = eqn.params["dimension_numbers"]
    out = math.prod(eqn.outvars[0].aval.shape)
    contract = math.prod(lhs[d] for d in lhs_contract)
    return Contraction("dot_general", out * contract, bool(lhs_batch))


def _conv_macs(eqn) -> Contraction:
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    # rhs_spec is (out-channel dim, in-channel dim, *spatial dims); the
    # kernel's in-channel dim is already C_in / feature_group_count.
    window = math.prod(rhs[d] for d in dn.rhs_spec[1:])
    out = math.prod(eqn.outvars[0].aval.shape)
    return Contraction("conv_general_dilated", out * window, False)


def contractions(jaxpr) -> list[Contraction]:
    """Every matmul and convolution in ``jaxpr``, nested jaxprs included."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            found.append(_dot_macs(eqn))
        elif name == "conv_general_dilated":
            found.append(_conv_macs(eqn))
        for inner in _sub_jaxprs(eqn.params):
            found.extend(contractions(inner))
    return found


def forward_macs(fn, *args, batched_scale: float = 1.0) -> float:
    """MACs of ``fn(*args)`` (shapes only; nothing runs).  ``batched_scale``
    weighs the dot_generals that have batch dimensions: 0.5 for causal
    attention, of whose S x S products only the lower triangle is needed."""
    import jax

    total = 0.0
    for c in contractions(jax.make_jaxpr(fn)(*args)):
        total += c.macs * (batched_scale if c.batched else 1.0)
    return total


def train_flops(forward_macs_: float) -> float:
    """Forward + backward FLOPs of a step whose forward has these MACs."""
    return 2.0 * forward_macs_ * 3.0


# ---------------------------------------------------------------------------
# The flash-attention kernels (horovod_tpu/ops/flash_attention.py)
# ---------------------------------------------------------------------------

# Matmuls of size S x S x D each kernel call needs given its inputs:
#   fwd:  QK^T, PV                       -> out (+ lse)
#   dq:   QK^T, dO V^T, dS K             -> dq
#   dkv:  QK^T, dO V^T, P^T dO, dS^T Q   -> dk, dv
# and the [B*H, S, D] arrays each must read or write at least once.
_FLASH_KERNELS = {
    "fwd": {"matmuls": 2, "arrays": 4},   # q k v | o
    "dq": {"matmuls": 3, "arrays": 5},    # q k v do | dq
    "dkv": {"matmuls": 4, "arrays": 6},   # q k v do | dk dv
}


def flash_least_seconds(batch: int, heads: int, seq: int, head_dim: int,
                        layers: int, causal: bool, itemsize: int,
                        peak_flops: float, peak_bytes_per_s: float) -> dict:
    """The least time one chip could spend in the three flash kernels of one
    training step of ``layers`` attention layers, and which peak bounds it.

    Operations: 2 x S x S x D per matmul, halved under a causal mask (the
    lower triangle; what a kernel computes above the diagonal because its
    tiles are rectangles is its cost, not the algorithm's).  Bytes: each array
    once, plus the float32 row statistics (lse, delta) where they cross the
    kernel boundary.  Per kernel the bound is the larger of operations over
    peak FLOP/s and bytes over peak bytes/s; the step's least time is the sum.
    """
    bh = batch * heads
    mask = 0.5 if causal else 1.0
    out = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "kernels": {}}
    for name, k in _FLASH_KERNELS.items():
        flops = k["matmuls"] * 2.0 * seq * seq * head_dim * bh * mask * layers
        rows = (1 if name == "fwd" else 2) * bh * seq * 4
        nbytes = (k["arrays"] * bh * seq * head_dim * itemsize + rows) * layers
        t_flops, t_bytes = flops / peak_flops, nbytes / peak_bytes_per_s
        out["kernels"][name] = {
            "flops": flops, "bytes": nbytes,
            "bound": "flops" if t_flops >= t_bytes else "bytes",
            "seconds": max(t_flops, t_bytes)}
        out["seconds"] += max(t_flops, t_bytes)
        out["flops"] += flops
        out["bytes"] += nbytes
    return out


def flash_step_least(ctx: dict) -> dict:
    """``flash_least_seconds`` at the shapes of the run's cell: what
    ``layer_metrics/flash_roofline.json`` names as its ``least``.  Reads the
    configuration's own keys (``n_head``, ``n_embd``, ``n_layer``,
    ``n_positions``, ``dtype``), the traffic's ``batch_per_chip`` and
    ``seq_len`` and the chip's peaks; a chip whose HBM peak is not on record
    raises."""
    import jax.numpy as jnp

    cfg, traffic, peaks = ctx["cfg"], ctx["traffic"], ctx["peaks"]
    if not peaks.get("hbm_bytes_per_s"):
        raise ValueError(f"no HBM peak on record for {peaks['source']!r}: "
                         "enter it in benchmark/peaks.json with its source")
    return flash_least_seconds(
        batch=traffic["batch_per_chip"], heads=cfg["n_head"],
        seq=traffic.get("seq_len", cfg["n_positions"]),
        head_dim=cfg["n_embd"] // cfg["n_head"], layers=cfg["n_layer"],
        causal=True, itemsize=jnp.dtype(cfg["dtype"]).itemsize,
        peak_flops=peaks["bf16_flops_per_s"],
        peak_bytes_per_s=peaks["hbm_bytes_per_s"])


# ---------------------------------------------------------------------------
# Peaks
# ---------------------------------------------------------------------------


def chip_peaks(device_kind: str, table: list) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``'s table
    (first matching prefix).  An unknown kind is an error, never a default."""
    for row in table:
        if device_kind.startswith(row["device_kind_prefix"]):
            return row
    raise ValueError(
        f"no published peaks on record for device kind {device_kind!r}: add "
        "it to benchmark/peaks.json with its source before reporting a "
        "utilization")
