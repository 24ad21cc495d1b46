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
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch = traffic["batch_per_chip"] * mesh.size
    count = traffic["distinct_batches"]

    def draw(key):
        out = []
        for b in range(count):
            args = []
            for i, spec in enumerate(inputs):
                k = jax.random.fold_in(jax.random.fold_in(key, b), i)
                shape = (batch, *spec.shape)
                if spec.draw == "normal":
                    args.append(jax.random.normal(k, shape, spec.dtype))
                elif spec.draw == "randint":
                    args.append(jax.random.randint(k, shape, 0, spec.high,
                                                   spec.dtype))
                else:
                    raise ValueError(f"unknown draw {spec.draw!r}")
            out.append(tuple(args))
        return out

    # fold_in(…, 1): the weights take fold_in(…, 0) of the same seed.
    key = jax.random.fold_in(jax.random.key(seed), 1)
    return jax.jit(draw, out_shardings=NamedSharding(mesh, P("hvd")))(key)
