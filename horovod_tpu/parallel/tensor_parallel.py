"""Tensor (model) parallelism primitives over a mesh axis.

The reference has NO tensor parallelism (SURVEY.md §2.7) — its process sets
are the substrate users would hand-roll TP on.  On TPU the substrate is a
mesh axis, and these are the Megatron-style building blocks, written for
``shard_map``: each shard holds a slice of the weight, and the pair
column→row costs exactly one psum on ICI per MLP block.

Layout convention (scaling-book recipe):
- **column parallel**: kernel sharded on the OUTPUT dim; input replicated
  (or varying over data axes only); output varies over the tp axis.
- **row parallel**: kernel sharded on the INPUT dim; input is the
  column-parallel output (tp-sharded features); the matmul's partial sums
  are combined with one ``psum``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import axis_size, ensure_varying


def column_parallel_dense(x, kernel_local, bias_local=None,
                          axis_name: str = "tp",
                          gather_output: bool = False):
    """y_local = x @ W[:, shard] (+ b[shard]).

    Args:
      x: [..., d_in], replicated across the tp axis (invariant or varying —
        both accepted).
      kernel_local: [d_in, d_out / tp] — this shard's column slice.
      bias_local: [d_out / tp] or None.
      gather_output: all_gather the feature dim back to [..., d_out]
        (costs bandwidth; usually keep sharded and feed a row-parallel op).
    """
    x = ensure_varying(x, axis_name)
    y = jnp.einsum("...i,ij->...j", x, kernel_local,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if bias_local is not None:
        y = y + bias_local
    if gather_output:
        y = lax.all_gather(y, axis_name, axis=-1, tiled=True)
    return y


def row_parallel_dense(x_local, kernel_local, bias=None,
                       axis_name: Optional[str] = "tp", dtype=None):
    """y = psum_tp(x_local @ W[shard, :]) (+ b).

    Args:
      x_local: [..., d_in / tp] — tp-sharded features (e.g. a column-parallel
        output).
      kernel_local: [d_in / tp, d_out] — this shard's row slice.
      bias: [d_out], logically replicated; added once AFTER the psum.
      axis_name: None leaves the sum out: the result is this shard's part
        of it (one chip's share of a layer run alone).
      dtype: the result's; None is ``x_local``'s.  The partial products and
        their sum over the axis are float32 either way, so a sum that feeds
        float32 arithmetic (a norm) asks for float32 and is never rounded.
    """
    partial = jnp.einsum("...i,ij->...j", x_local, kernel_local,
                         preferred_element_type=jnp.float32)
    if axis_name is not None:
        partial = lax.psum(partial, axis_name)
    y = partial.astype(dtype or x_local.dtype)
    if bias is not None:
        y = y + bias
    return y


def tp_mlp(x, w_in_local, w_out_local, b_in_local=None, b_out=None,
           axis_name: str = "tp", activation=jax.nn.gelu):
    """The canonical TP transformer MLP: column → act → row, one psum total."""
    h = column_parallel_dense(x, w_in_local, b_in_local, axis_name)
    h = activation(h)
    return row_parallel_dense(h, w_out_local, b_out, axis_name)


def vocab_parallel_embedding(ids, table_local, axis_name: str = "tp"):
    """Embedding with the vocab dim sharded: each shard looks up its own
    vocab range and the results are psum-combined (out-of-range rows
    contribute zeros)."""
    vocab_local = table_local.shape[0]
    start = lax.axis_index(axis_name) * vocab_local
    local_ids = ids - start
    in_range = (local_ids >= 0) & (local_ids < vocab_local)
    safe_ids = jnp.clip(local_ids, 0, vocab_local - 1)
    emb = jnp.take(table_local, safe_ids, axis=0)
    emb = jnp.where(in_range[..., None], emb, 0)
    return lax.psum(emb, axis_name)


def shard_kernel(kernel, axis_name: str, dim: int):
    """Slice a replicated kernel to this shard's piece along ``dim`` —
    convenience for loading non-TP checkpoints into TP layers."""
    n = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if kernel.shape[dim] % n != 0:
        raise ValueError(
            f"shard_kernel: dim {dim} of shape {kernel.shape} is not "
            f"divisible by axis {axis_name!r} size {n}")
    size = kernel.shape[dim] // n
    return lax.dynamic_slice_in_dim(kernel, idx * size, size, axis=dim)
