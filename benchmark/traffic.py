"""The one generator of training traffic: device batches from a traffic file.

A traffic mix is a data file ``traffic/<traffic>.json`` of parameters; a
later PR adds a mix by adding a file.  The parameters:

``batch_per_chip``     examples each chip gets in a step
``distinct_batches``   how many different seeded batches the loop cycles
                       through (1: one batch reused, no input pipeline)
``seq_len``            tokens per sequence, for a family whose inputs have a
                       sequence axis (absent: the configuration's own length)
``warmup_steps``       untimed steps after the first, before the window
``trace_steps``        steps of the traced window (``--trace 1``)
``rehearse``           overrides of the above for ``run.py --rehearse``

What an example looks like is the family's business: ``family.inputs(cell,
traffic)`` returns one ``Input`` per argument of the step.  Same seed, same
batches; every batch of a cell has the same shapes, so one compiled step
serves them all.
"""

from __future__ import annotations

from typing import NamedTuple, Optional


class Input(NamedTuple):
    """One argument of the step, per example."""

    shape: tuple                 # without the batch axis
    dtype: object
    draw: str                    # "normal" | "randint"
    high: Optional[int] = None   # randint: values in [0, high)


def resolve(traffic: dict, rehearse: bool) -> dict:
    """The traffic's parameters, with its ``rehearse`` overrides applied."""
    out = {k: v for k, v in traffic.items() if k != "rehearse"}
    if rehearse:
        out.update(traffic.get("rehearse", {}))
    return out


def make_batches(traffic: dict, inputs: list, mesh, seed: int) -> list:
    """``distinct_batches`` batches (tuples of arrays split over ``hvd``),
    made on the device in one jitted call.  The key is an argument, not a
    constant of the program: one program for every seed, so a new seed finds
    it in the compile cache."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch = traffic["batch_per_chip"] * mesh.size
    count = traffic["distinct_batches"]

    def draw_one(key, b, i, spec):
        """Argument i of batch b: the values of fold_in(fold_in(key, b), i)."""
        k = jax.random.fold_in(jax.random.fold_in(key, b), i)
        shape = (batch, *spec.shape)
        if spec.draw == "normal":
            return jax.random.normal(k, shape, spec.dtype)
        if spec.draw == "randint":
            return jax.random.randint(k, shape, 0, spec.high, spec.dtype)
        raise ValueError(f"unknown draw {spec.draw!r}")

    def draw(key):
        # One draw an argument over all the batches (a mix of 64 batches
        # traced 192 draws one by one, 8 s of set-up), then a batch's slice.
        every = [jax.vmap(lambda b, i=i, spec=spec: draw_one(key, b, i, spec))(
            jnp.arange(count)) for i, spec in enumerate(inputs)]
        return [tuple(x[b] for x in every) for b in range(count)]

    # fold_in(…, 1): a family's weights take fold_in(…, 0), of the same seed
    # in every family but sdar, whose configuration names its weights' seed
    # (families/sdar.py:weights_seed): there --seed draws the traffic alone.
    key = jax.random.fold_in(jax.random.key(seed), 1)
    return jax.jit(draw, out_shardings=NamedSharding(mesh, P("hvd")))(key)
