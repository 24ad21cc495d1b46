"""What the flash-attention kernel tests share: the toy tile schedule, seeded
q / k / v and the table of schedules every forward and gradient case walks.
Not a test file; test_flash_attention.py, test_flash_attention_grads.py and
test_flash_attention_plan.py import it (pytest puts ``tests/single`` on the
path)."""

import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.ops import flash_attention as fa


@pytest.fixture
def small_tiles(monkeypatch):
    """The schedule of a long sequence at a toy size: resident tiles of 32
    rows walked in steps of 8, so a 64-long block holds two tiles and the
    diagonal crosses each in four steps."""
    monkeypatch.setattr(fa, "_MAX_TILE", 32)
    monkeypatch.setattr(fa, "_MAX_STEP", 8)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it: a checkpoint's,
    a ``custom_vjp``'s, a ``pjit``'s, a Pallas kernel's body, its loops and
    branches."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def kernel_calls(jaxpr, name=None) -> int:
    """The ``pallas_call`` equations among them; with ``name``, those of the
    kernel so named."""
    return sum(eqn.primitive.name == "pallas_call"
               and name in (None, eqn.params["name"])
               for eqn in equations(jaxpr))


def _qkv(shape, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


# (shape, block_q, block_k): explicit 16-wide blocks; one block of several
# tiles and steps (with small_tiles); blocks that differ; a tail-padded
# length whose last block is partly dead; the plan's own default.
SCHEDULES = {
    "16x16": ((1, 32, 2, 16), 16, 16),
    "16x16-b2h4": ((2, 64, 4, 32), 16, 16),
    "one-block": ((1, 128, 2, 16), 128, 128),
    "two-blocks": ((1, 128, 2, 16), 64, 64),
    "bq>bk": ((1, 128, 2, 16), 64, 32),
    "bq<bk": ((1, 128, 2, 16), 32, 64),
    "padded-tail": ((1, 100, 2, 16), 64, 64),
    "padded-bq>bk": ((1, 75, 2, 8), 64, 16),
    "default-plan": ((1, 200, 2, 16), None, None),
    # The block layout (PR 28).  The cases above run the fallback
    # ([B * H, S, D]: two heads of 16 or 8 fill no lane tile) except
    # 16x16-b2h4, four heads of 32 a block.  Lane-dense blocks of the
    # model's [B, S, H * D]: two heads of 64 a grid step, streamed in small
    # blocks, tail-padded, and on the default plan; one head of 128; and the
    # fallback where no whole number of heads fills 128 lanes.
    "g2-two-blocks": ((1, 128, 2, 64), 64, 64),
    "g2-bq>bk-b2h4": ((2, 64, 4, 64), 32, 16),
    "g2-padded-tail": ((1, 100, 2, 64), 64, 64),
    "g2-default-plan": ((1, 200, 4, 64), None, None),
    "g1-head_dim-128": ((1, 64, 2, 128), 32, 32),
    "fallback-head_dim-80": ((1, 64, 2, 80), 32, 32),
    "fallback-3-heads-of-64": ((1, 64, 3, 64), 32, 32),
}
