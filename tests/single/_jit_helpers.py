"""What the in-jit collective tests share: the ``hvd`` mesh over the eight
virtual devices (or the first ``world`` of them), the compiled ``shard_map``
wrappers over it, and the device codecs' error bounds. The collectives under
test are trace-time code that a user reaches only inside ``jax.jit``, so the
wrappers compile: one program a call, where an un-jitted ``shard_map``
(``_smap_eager``, for the four cases that need one) compiles and dispatches
each of an eight-rank ring's ~600 primitives by itself. Not a test file; the
``test_ops_jit*`` files and ``test_convergence_ef.py`` import it (pytest puts
``tests/single`` on the path; ``tests/conftest.py`` registers it for assertion
rewriting)."""

import numpy as np

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

N_DEV = 8
_DEV_CODECS = ("int8", "int4")
_Q_BOUND = {"int8": 0.5, "int4": 8.0}  # scale/2 per element


def _mesh(world=N_DEV):
    return Mesh(np.asarray(jax.devices()[:world]), ("hvd",))


def _run_per_rank(fn, x_per_rank, out_spec=P("hvd")):
    """Run fn under shard_map: x_per_rank has leading dim N_DEV, each shard
    sees one rank's slice (rank-major), like one Horovod process per device."""
    mesh = _mesh()
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("hvd"),
                             out_specs=out_spec))(x_per_rank)


def _smap(fn, in_specs=P("hvd"), out_specs=P("hvd"), world=N_DEV):
    return jax.jit(shard_map(fn, mesh=_mesh(world), in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))


def _smap_eager(fn, world=N_DEV):
    """``shard_map`` un-jitted: every primitive is compiled and dispatched by
    itself, about a minute a call at eight ranks.  Only for the cases that
    need it: the one whose subject is the un-jitted call, and int4's exact
    parity.  In one compiled program XLA's algebraic simplifier (CPU and TPU
    alike) turns the scale's ``max|x| / 7`` into a multiply by the rounded
    reciprocal 0.142857149, so 21 / 7 reads 3.0000002 and a sum that should
    be 112 reads 112.00003; a primitive compiled alone receives the 7 as a
    run-time operand and divides.  ``max|x| / 127`` has the same rewrite and
    is exact at the parity payload's values (PERF.md section 7)."""
    return shard_map(fn, mesh=_mesh(world), in_specs=P("hvd"),
                     out_specs=P("hvd"), check_vma=False)
