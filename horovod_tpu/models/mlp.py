"""MNIST-scale MLP — the minimum end-to-end slice (BASELINE.json config 1;
reference analog: horovod `examples/*mnist*` scripts)."""

from __future__ import annotations

from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from .losses import softmax_cross_entropy


class MLP(nn.Module):
    features: Sequence[int] = (512, 256, 10)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.reshape((x.shape[0], -1)).astype(self.dtype)
        for i, f in enumerate(self.features[:-1]):
            x = nn.relu(nn.Dense(f, dtype=self.dtype, name=f"dense_{i}")(x))
        return nn.Dense(self.features[-1], dtype=jnp.float32, name="head")(
            x.astype(jnp.float32))


def xent_loss(logits, labels):
    """A classifier's loss, under the classifier's scope (``hvd_head``, as
    ``models/resnet.py`` names its pool and product)."""
    with jax.named_scope("hvd_head"):
        return softmax_cross_entropy(logits, labels).mean()
