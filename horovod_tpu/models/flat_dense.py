"""``nn.DenseGeneral`` computed as one flat matmul.

The projections around attention are ``DenseGeneral`` layers over head
axes: ``[B, S, hidden] -> [B, S, 3, heads, head_dim]`` going in,
``[B, S, heads, head_dim] -> [B, S, hidden]`` coming out.  As
``dot_general``s over those shapes they leave tensors whose minor dimension
is ``head_dim``; at 64 that fills half a 128-lane tile, so XLA's TPU layout
assignment stores them with the *sequence* minor and relayouts them (a copy
of the whole tensor each) wherever something wants heads side by side, as
the flash kernels do (ops/flash_attention.py reads and writes
``[B, S, heads * head_dim]``).  Computed flat, every tensor between the
matmuls and the kernels has ``heads * head_dim`` lanes and nothing is
turned round.
"""

from __future__ import annotations

import math
from typing import Any, Sequence, Union

import flax.linen as nn
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype


class FlatDenseGeneral(nn.Module):
    """``nn.DenseGeneral(features, axis)`` over trailing input axes with the
    same parameters (``kernel`` [*in_axes, *features], ``bias``
    [*features]: names, shapes and initial values are DenseGeneral's, so
    checkpoints and references interchange), applied as one 2-D matmul.

    The result is **flat**: ``[..., prod(features)]``, for the caller to
    slice and reshape (a [..., 3, H, D] result would bring the narrow minor
    dimension back)."""
    features: Union[int, Sequence[int]]
    axis: Union[int, Sequence[int]] = -1
    dtype: Any = None
    use_bias: bool = True

    @nn.compact
    def __call__(self, x):
        features = ((self.features,) if isinstance(self.features, int)
                    else tuple(self.features))
        axis = (self.axis,) if isinstance(self.axis, int) else tuple(self.axis)
        n_in = len(axis)
        if axis != tuple(range(-n_in, 0)):
            raise ValueError(f"axis {axis}: only trailing axes flatten")
        lead, in_shape = x.shape[:-n_in], x.shape[-n_in:]
        flat = (math.prod(in_shape), math.prod(features))

        # DenseGeneral draws the kernel from the flat shape and reshapes.
        kernel = self.param(
            "kernel", lambda rng, shape: jnp.reshape(
                nn.linear.default_kernel_init(rng, flat), shape),
            in_shape + features)
        bias = (self.param("bias", nn.initializers.zeros_init(), features)
                if self.use_bias else None)
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = jnp.dot(x.reshape(*lead, flat[0]), kernel.reshape(flat))
        return y if bias is None else y + bias.reshape(flat[1])
