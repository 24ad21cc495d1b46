"""The lookup of a held embedding table, with a gradient of its own.

``embed_lookup(table, ids, dtype)`` is ``nn.Embed(dtype=dtype)``'s value: the
rows ``table[ids]`` in ``dtype``.  Two things are this module's own.  **The
rows of a large table are taken from it as it is kept and cast after**, so a
step casts the rows it reads (16,384 of them) and not the whole float32 table
to take them from; a cast commutes with a gather, so the values are the same
bit for bit.  (A small table, :data:`CAST_FIRST_BYTES`, is cast first as
before; and a tied head that reads the table in ``dtype`` casts it whole for
itself: the lookup's own cast is then saved and the head's stays.)  And **the
table's gradient on a TPU is a segment sum in id order**,
``dE[v] = sum of g[t] over the t with ids[t] = v``: one sort of the ids with
their positions, the cotangent rows gathered into that order, and
``ops/grouped_matmul.py``'s one-hot product a tile of 128 table rows
(``embed_grad_sum_rows`` in a trace), a float32 accumulator rounded once to
the cotangent's dtype, every tile of the table written once, zeros where no
id fell.  XLA's own form is a scatter over the table, sorted or plain by a
rule of the compiler's: a serial read-modify-write that cost 1.8 to 39.3 ms a
step for the same 16,384 rows (PERF.md, PR 62).

The kernel's name does not begin with ``hvd_``: ``benchmark/scope_ledger.py``
makes a kernel so named a layer of its own, and this one belongs to the layer
that calls it (``hvd_embed``), whose metrics read it there.

Off the TPU the gradient is the scatter-add it was, as ``parallel/moe.py:
add_rows``; the kernel is unit-tested in interpret mode
(``tests/single/test_embedding.py``) and held against a float32 scatter-add on
the chip by ``chip_smoke.py --embed-grad``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import vary_like
from .grouped_matmul import sum_ordered_rows

KERNEL_NAME = "embed_grad_sum_rows"
# A table this small in the lookup's dtype is cast whole before the lookup,
# as ``nn.Embed`` does: XLA's gather on a v5e takes 6 to 9 ns a row from a
# bfloat16 table of 66 to 84 MB and 28 to 61 from a float32 or a larger one
# (150 MB and up), so the cast's pass (0.2 ms) is repaid by 16,384 rows.
# Read in the seven cells' steps and alone (PERF.md, PR 62); where between
# 84 and 150 MB the gather turns slow was not read.
CAST_FIRST_BYTES = 96 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, ids, dtype, interpret):
    return jnp.take(table, ids, axis=0).astype(dtype)


def _lookup_fwd(table, ids, dtype, interpret):
    # (the table for its shape and dtype alone: nothing of it is read again)
    return _lookup(table, ids, dtype, interpret), (table, ids)


def _lookup_bwd(dtype, interpret, saved, g):
    table, ids = saved
    rows, d = table.shape
    g, ids = g.reshape(-1, d), ids.reshape(-1).astype(jnp.int32)
    if interpret is None and jax.default_backend() != "tpu":
        d_table = jnp.zeros((rows, d), g.dtype).at[ids].add(g)
    else:
        by_id, at = lax.sort((ids, jnp.arange(ids.shape[0], dtype=jnp.int32)),
                             num_keys=1, is_stable=True)
        d_table = sum_ordered_rows(
            g.at[at].get(mode="promise_in_bounds", unique_indices=True),
            by_id, rows, name=KERNEL_NAME, interpret=interpret or False)
    return d_table.astype(table.dtype), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embed_lookup(table, ids, dtype=None, *, interpret=None):
    """``table[ids]`` in ``dtype``: ``[..., d]`` for a table ``[V, d]`` and
    integer ``ids [...]`` in ``[0, V)``, the value of ``flax.linen.Embed(V,
    d, dtype=dtype)`` on the same table bit for bit: the rows cast after
    they are taken, or the table cast first where it is small
    (:data:`CAST_FIRST_BYTES`).  ``dtype`` None keeps the table's.

    Its gradient to the table, in the table's dtype: on a TPU the cotangent's
    rows summed by id in id order by ``embed_grad_sum_rows`` (one sort, one
    gather, a one-hot product a tile of 128 table rows: exact products, a
    float32 accumulator, one rounding to the cotangent's dtype, each tile
    written once and as zeros where no id fell); off it
    ``zeros.at[ids].add(g)`` in the cotangent's dtype, a duplicate at a
    time.  A cotangent row that is not finite reaches the 128 table rows of
    its tile on a TPU (``0 x inf`` in the one-hot product is NaN) and its
    own row alone off it.  ``interpret`` as ``grouped_dot``'s: None runs the
    kernel on a TPU and the scatter-add elsewhere; True, or a
    ``pltpu.InterpretParams``, forces the kernel through a Pallas
    interpreter (tests)."""
    dtype = jnp.dtype(dtype or table.dtype)
    if table.size * dtype.itemsize <= CAST_FIRST_BYTES:
        table = table.astype(dtype)
    # (inside ``shard_map`` a table held replicated meets a chip's own ids:
    # the cast's transpose sums the chips' gradients, as ``jnp.take``'s does)
    return _lookup(vary_like(table, ids), vary_like(ids, table), dtype,
                   interpret)
