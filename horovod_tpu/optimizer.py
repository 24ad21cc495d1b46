"""DistributedOptimizer: gradient averaging wrapped around optax.

Reference analogs (SURVEY.md §2.4, §3.3): horovod/torch/optimizer.py
(_DistributedOptimizer — per-parameter grad hooks → async allreduce,
``backward_passes_per_step`` local aggregation, ``gradient_predivide_factor``)
and horovod/tensorflow/__init__.py (DistributedOptimizer /
DistributedGradientTape → _allreduce_grads).

TPU-first design: an optax ``GradientTransformation`` is the JAX-native
"optimizer", so ``hvd.DistributedOptimizer(tx)`` returns a new
GradientTransformation whose ``update`` first averages gradients across
ranks:

- **inside jit / shard_map** (tracers): gradients compile to XLA
  collectives over the named mesh axis — one fused psum per dtype after XLA's
  collective combining, riding ICI.  This is the recommended path: the
  whole train step is one compiled program with compute/communication
  overlap scheduled by XLA;
- **eager**: every leaf is enqueued async into the core runtime and then
  synchronized — the reference's hook-then-synchronize overlap, with
  tensor fusion in the core.  Device-resident (jax.Array) gradients
  execute on the eager device plane (``ops.device_plane`` — cached jitted
  fused collectives, no host copies) once negotiation confirms every rank
  can; host numpy gradients (or a rank without a device mesh) ride the
  host TCP plane, and device tensors demoted to it warn once on TPU.

``backward_passes_per_step`` accumulates gradients locally and only
communicates (and applies the inner optimizer) every k-th call, built with
``lax.cond`` so it stays jittable.
"""

from __future__ import annotations

import contextvars
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax._src import config as _jax_config
from jax._src.lax.parallel import all_gather_invariant

from .compression import Compression
from .mpi_ops import allreduce_async, synchronize, _is_traced
from .ops import collectives as _jit_ops
from .ops import hlo_inspect as _hlo
from .parallel import mesh as _mesh
from .process_sets import ProcessSet, _resolve_psid
from .wire import ReduceOp


# The mesh axis a shard_optimizer_states chunk is split over, set while that
# optimizer traces its inner transform (None elsewhere).  The chunk is
# already summed over every other axis, and clip_by_global_norm must know.
_inner_shard_axis = contextvars.ContextVar("hvd_inner_shard_axis",
                                           default=None)


def _resolve_axes(axis_name):
    ax = axis_name if axis_name is not None else _mesh.mesh_axis_name()
    return (ax,) if isinstance(ax, str) else tuple(ax)


def _vma_tracked() -> bool:
    """Whether the current trace types values by the mesh axes they vary
    over (shard_map's ``check_vma``, on by default).  With it on, an empty
    vma means "invariant: already reduced" — the gradient of replicated
    parameters arrives that way, psummed by autodiff.  With it off every
    value reports an empty vma and gradients are still per-shard.  Asked of
    the trace itself: guessing it from the leaves (all invariant => off)
    reduced the plain data-parallel step's gradients twice."""
    return bool(_jax_config._check_vma.value)


def _leaf_vma(leaf):
    try:
        return jax.typeof(leaf).vma
    except Exception:
        return None


def _axes_bound(axis_name) -> bool:
    """True when every resolved mesh axis is bound in the current trace
    (shard_map / pmap context) — the discriminator between the two in-jit
    calling conventions: bound axes mean per-shard gradients that still
    need the explicit reduction; unbound means plain jit over sharded
    arrays, where backprop already inserted it (the gspmd plane)."""
    try:
        for a in _resolve_axes(axis_name):
            _jit_ops.axis_size(a)
        return True
    except (NameError, KeyError):
        return False


def _reduce_grad_leaf(leaf, axes, op: ReduceOp,
                      prescale_factor: float, postscale_factor: float,
                      vma_tracked: bool):
    """Gradient-context allreduce of one leaf over ``axes``.

    Unlike the classic collective (which casts invariant inputs to varying),
    a gradient leaf that is *invariant* over some requested axis was already
    reduced over it — the backward pass of sequence/tensor-parallel models
    (e.g. ring attention's ppermute/pcast transposes) psums such grads.  So:
    SUM psums only the still-varying axes; AVERAGE additionally divides by
    the FULL axis-size product, which equals the mean over all shards for
    both pre-reduced and varying leaves.

    ``vma_tracked=False`` (shard_map check_vma=False, where every value
    reports an empty vma) falls back to classic semantics.
    """
    from jax import lax

    vma = _leaf_vma(leaf)
    if vma is None or not vma_tracked:
        varying = axes
    else:
        varying = tuple(a for a in axes if a in vma)
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        if prescale_factor != 1.0:
            leaf = leaf * jnp.asarray(prescale_factor, leaf.dtype)
        out = lax.psum(leaf, varying) if varying else leaf
        if op == ReduceOp.AVERAGE:
            total = 1
            for a in axes:
                total *= _jit_ops.axis_size(a)
            out = out / total
        if postscale_factor != 1.0:
            out = out * jnp.asarray(postscale_factor, out.dtype)
        return out
    return _jit_ops.allreduce(leaf, axes, op, prescale_factor,
                              postscale_factor)


def _tree_allreduce(grads, op: ReduceOp, compression,
                    prescale_factor: float, postscale_factor: float,
                    process_set: Optional[ProcessSet],
                    axis_name: Optional[str], name_prefix: str = "grad"):
    """Allreduce a pytree of gradients (traced → XLA; eager → fused async)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if _is_traced(leaves[0]):
        axes = _resolve_axes(axis_name)
        vma_tracked = _vma_tracked()
        out = []
        with jax.named_scope("hvd_exchange"):
            for leaf in leaves:
                comp, ctx = compression.compress(leaf)
                red = _reduce_grad_leaf(comp, axes, op, prescale_factor,
                                        postscale_factor, vma_tracked)
                out.append(compression.decompress(red, ctx))
        return jax.tree_util.tree_unflatten(treedef, out)
    # Eager: enqueue everything first (negotiation fuses the bucket), then wait.
    handles, ctxs = [], []
    with jax.profiler.TraceAnnotation("hvd_exchange"):
        for i, leaf in enumerate(leaves):
            comp, ctx = compression.compress(leaf)
            ctxs.append(ctx)
            handles.append(
                allreduce_async(comp, name=f"{name_prefix}.{i}", op=op,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                process_set=process_set))
        out = [compression.decompress(synchronize(h), ctx)
               for h, ctx in zip(handles, ctxs)]
    return jax.tree_util.tree_unflatten(treedef, out)


def allreduce_gradients(grads, op: ReduceOp = ReduceOp.AVERAGE,
                        compression=Compression.none,
                        process_set: Optional[ProcessSet] = None,
                        axis_name: Optional[str] = None):
    """Average a pytree of gradients across ranks.

    JAX analog of the reference's DistributedGradientTape._allreduce_grads:
    use it directly around ``jax.grad`` when not going through optax.
    """
    return _tree_allreduce(grads, op, compression, 1.0, 1.0, process_set,
                           axis_name)


class DistributedOptState(NamedTuple):
    inner_state: Any
    accum: Any          # local gradient accumulator (backward_passes_per_step)
    counter: jnp.ndarray  # int32 scalar
    # Error-feedback residual tree (device_compression="int8"): per leaf,
    # the local quantization error carried into the next step so the int8
    # codec's bias cancels over time instead of accumulating.  None when no
    # device codec is engaged (the default), keeping the state pytree
    # identical to pre-codec checkpoints.
    residual: Any = None


class ShardedOptState(NamedTuple):
    inner_state: Any      # inner optax state over the rank's flat shard
    master: jnp.ndarray   # fp32 master copy of the rank's parameter shard


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         named_parameters=None,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         op: ReduceOp = ReduceOp.AVERAGE,
                         gradient_predivide_factor: float = 1.0,
                         process_set: Optional[ProcessSet] = None,
                         axis_name: Optional[str] = None,
                         shard_optimizer_states: bool = False,
                         device_compression: Optional[str] = None,
                         plane: Optional[str] = None,
                         mesh=None
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer with cross-rank gradient averaging.

    ``named_parameters`` is accepted for reference-signature parity and
    ignored (JAX pytrees carry structure already).  With
    ``backward_passes_per_step > 1``, gradients accumulate locally and the
    collective + inner update run every k-th call; other calls return zero
    updates (parameters unchanged), matching the reference's local gradient
    aggregation semantics.

    ``shard_optimizer_states=True`` (beyond parity; ZeRO-1 analog) shards
    the inner optimizer's states over the reduction axis: gradients are
    reduce-scattered, each rank updates its 1/n flat fp32 shard, and the
    updates are all-gathered — the same communication volume as the
    allreduce with n× less optimizer memory per chip.  In-jit only;
    incompatible with compression/backward_passes_per_step/predivide.

    ``device_compression`` selects the in-jit device-plane codec for the
    traced gradient reduction: ``"int8"``/``"int4"`` routes
    eligible leaves (fp32, at least HOROVOD_WIRE_COMPRESSION_MIN_BYTES of
    payload) through the block-scaled ring of that codec
    (``ops.collectives.quantized_allreduce``) with
    **error feedback**: the state carries a residual tree holding each
    leaf's local quantization error, added back into the next step's
    gradient before quantizing, so the codec's per-step bias cancels
    instead of compounding (docs/compression.md).  ``None`` (default)
    follows ``HOROVOD_WIRE_COMPRESSION``'s ``device=`` plane; ``"none"``
    disables regardless of the environment.  Ineligible leaves demote to
    the uncompressed collective bit-identically; the eager path never
    quantizes (the host ring has its own coordinator-negotiated codec).

    ``plane`` selects the in-jit gradient-exchange plane
    (``ops.gspmd_plane``): ``"eager"`` is today's explicit path
    (shard_map + psum); ``"gspmd"`` expects the *gspmd calling
    convention* — the train step runs under plain ``jax.jit`` with
    batch-sharded inputs and a global-mean loss, so backprop has already
    globally reduced the gradients — and the optimizer only annotates
    them with ``jax.lax.with_sharding_constraint`` over ``mesh``
    (default: the 1-D batch mesh over all devices), letting XLA insert
    and overlap the collectives.  ``None`` reads ``HOROVOD_DATA_PLANE``;
    ``"auto"`` (the default) adapts per trace: the explicit path whenever
    the mesh axis is bound (shard_map), the annotation path otherwise.
    Requests that cannot compose (single-device mesh, an active
    ``device=<codec>``, accumulation, process sets, ZeRO-1 sharding,
    predivide) demote deterministically to eager with a counter
    recording why (``ops.gspmd_plane.plane_counters()``) — demotion is
    bit-identical, since the annotations never change the math.

    In a profiler trace the gradient exchange is named ``hvd_exchange`` and
    the inner update ``hvd_update``: ``jax.named_scope``s in a compiled step,
    host spans in the eager loop (docs/observability.md, "Names on the
    profiler's clock").
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    from .ops import quantize as _qz
    dev_codec = device_compression
    if dev_codec is None:
        dev_codec = _jit_ops._device_codec_defaults()[0]
    dev_codec = (dev_codec or "none").lower()
    if dev_codec not in _qz.DEVICE_WIRE_CODECS:
        raise ValueError(
            "device_compression must be one of "
            f"{_qz.DEVICE_WIRE_CODECS}, got {dev_codec!r}")
    ef_active = dev_codec != "none"
    if ef_active and shard_optimizer_states:
        if device_compression is not None:
            raise ValueError(
                f"device_compression={dev_codec!r} is incompatible with "
                "shard_optimizer_states (the sharded path reduce-scatters "
                "exactly once; quantizing it is future work)")
        ef_active = False  # env-driven codec: sharded path just opts out
    if ef_active:
        if compression is not Compression.none:
            raise ValueError(
                f"device_compression={dev_codec!r} already quantizes the "
                "wire; combine it with Compression.none")
        if backward_passes_per_step != 1:
            raise ValueError(
                f"device_compression={dev_codec!r} requires "
                "backward_passes_per_step=1 (error feedback needs to see "
                "every communicated gradient)")
        if process_set is not None:
            raise ValueError(
                "device_compression='int8' runs the full-axis ring; "
                "process_set subsets are not supported")
        if gradient_predivide_factor != 1.0:
            raise ValueError(
                "device_compression='int8' does not support "
                "gradient_predivide_factor")
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                "device_compression='int8' supports op=Average or Sum")
    from .ops import gspmd_plane as _gspmd
    from .utils.env import DATA_PLANES
    plane_req = plane if plane is not None else _gspmd.data_plane_default()
    plane_req = (plane_req or "auto").strip().lower()
    if plane_req not in DATA_PLANES:
        raise ValueError(
            f"plane must be one of {DATA_PLANES}, got {plane_req!r}")
    # Resolve once, at construction: demotions are deterministic in the
    # mesh/codec config, and an explicit 'gspmd' request that cannot
    # compose records why (auto probes silently).  gspmd_mesh None means
    # the update runs today's eager plane end to end.
    gspmd_mesh = None
    if plane_req != "eager":
        explicit = plane_req == "gspmd"

        def _demote(reason):
            if explicit:
                _gspmd.note_demotion(reason)

        if shard_optimizer_states:
            _demote("demote_sharded")
        elif backward_passes_per_step != 1:
            _demote("demote_accum")
        elif process_set is not None:
            _demote("demote_process_set")
        elif gradient_predivide_factor != 1.0:
            _demote("demote_predivide")
        else:
            resolved, gspmd_mesh = _gspmd.resolve_plane(
                plane_req, mesh=mesh, device_codec=dev_codec,
                count=explicit)
            if resolved != "gspmd":
                gspmd_mesh = None
    if shard_optimizer_states:
        if compression is not Compression.none:
            raise ValueError(
                "shard_optimizer_states is incompatible with compression "
                "(the shard math runs in fp32 anyway)")
        if backward_passes_per_step != 1:
            raise ValueError("shard_optimizer_states requires "
                             "backward_passes_per_step=1")
        if gradient_predivide_factor != 1.0:
            raise ValueError("shard_optimizer_states does not support "
                             "gradient_predivide_factor")
        if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
            raise ValueError(
                "shard_optimizer_states supports op=Average or Sum")
        if process_set is not None:
            raise ValueError(
                "shard_optimizer_states does not support process_set; "
                "pass the sub-mesh axis via axis_name instead")
        return _sharded_distributed_optimizer(optimizer, op, axis_name)
    if gradient_predivide_factor != 1.0:
        if op != ReduceOp.AVERAGE:
            raise ValueError(
                "gradient_predivide_factor is only supported with op=Average")
        prescale = 1.0 / gradient_predivide_factor
    else:
        prescale = 1.0

    def reduce_grads(grads, divisor: int):
        # Split averaging around the wire like the reference: prescale by
        # 1/predivide before the sum, finish the average after.
        if gradient_predivide_factor != 1.0:
            eff_op = ReduceOp.SUM
            post = gradient_predivide_factor  # completes 1/size with psum below
            reduced = _tree_allreduce(grads, eff_op, compression, prescale,
                                      post, process_set, axis_name)
            n = _ps_world_size(process_set, axis_name, grads)
            reduced = jax.tree_util.tree_map(lambda g: g / n, reduced)
        else:
            reduced = _tree_allreduce(grads, op, compression, 1.0, 1.0,
                                      process_set, axis_name)
        if divisor > 1:
            reduced = jax.tree_util.tree_map(lambda g: g / divisor, reduced)
        return reduced

    def reduce_grads_ef(grads, residual):
        # Error-feedback quantized reduction (traced only): each eligible
        # leaf communicates corrected = grad + residual through the int8
        # ring and keeps its own local quantization error for next step.
        # Ineligible leaves take the plain collective bit-identically and
        # leave their residual untouched (it stays zero).
        from .ops import quantize as _qz

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        rleaves = treedef.flatten_up_to(residual)
        axes = _resolve_axes(axis_name)
        world = 1
        for a in axes:
            world *= _jit_ops.axis_size(a)
        min_bytes = _jit_ops._device_codec_defaults()[1]
        vma_tracked = _vma_tracked()
        out, new_res = [], []
        with jax.named_scope("hvd_exchange"):
            for leaf, res in zip(leaves, rleaves):
                vma = _leaf_vma(leaf)
                varying = (vma is None or not vma_tracked
                           or all(a in vma for a in axes))
                if (len(axes) == 1 and varying
                        and _jit_ops.quantized_allreduce_eligible(
                            leaf, world, min_bytes)):
                    corrected = leaf + res
                    out.append(_jit_ops.quantized_allreduce(
                        corrected, axes[0], op=op, codec=dev_codec))
                    new_res.append(
                        corrected - _qz.fake_quantize(corrected, dev_codec))
                else:
                    out.append(_reduce_grad_leaf(leaf, axes, op, 1.0, 1.0,
                                                 vma_tracked))
                    new_res.append(res)
        return (jax.tree_util.tree_unflatten(treedef, out),
                jax.tree_util.tree_unflatten(treedef, new_res))

    def init_fn(params):
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        residual = None
        if ef_active:
            # fp32 like the codec: only fp32 leaves ever touch it, and a
            # zero residual is exact for everything that demotes.
            residual = jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
        return DistributedOptState(
            inner_state=optimizer.init(params),
            accum=zeros,
            counter=jnp.zeros((), dtype=jnp.int32),
            residual=residual,
        )

    def update_fn(grads, state: DistributedOptState, params=None):
        # Plane mark for compiled-collective introspection + the sticky
        # step-trace plane tag (ops/hlo_inspect.py): trace-time only for
        # traced paths, memo-deduplicated for the eager per-step path.
        # The gspmd branch below overrides the tag within its trace.
        _hlo.mark_plane("eager")
        leaves = jax.tree_util.tree_leaves(grads)
        traced = bool(leaves) and _is_traced(leaves[0])

        def inner_update(reduced, inner_state):
            # The update's name in whichever trace sees it: a scope in the
            # op_name of a compiled step's ops, a host span on the profiler's
            # clock around the eager dispatches.
            with (jax.named_scope if traced
                  else jax.profiler.TraceAnnotation)("hvd_update"):
                return optimizer.update(reduced, inner_state, params)

        if backward_passes_per_step == 1:
            if (gspmd_mesh is not None and traced
                    and not _axes_bound(axis_name)):
                # GSPMD plane: no explicit collective.  The grads of a
                # batch-sharded global-mean loss arrive globally reduced
                # (backprop inserted the reduction); the constraint pins
                # them replicated so GSPMD schedules that reduce where it
                # overlaps the optimizer math below.
                _hlo.mark_plane("gspmd")
                with jax.named_scope("hvd_exchange"):
                    reduced = _gspmd.constrain_grads(grads, gspmd_mesh)
                updates, inner = inner_update(reduced, state.inner_state)
                return updates, DistributedOptState(inner, state.accum,
                                                    state.counter,
                                                    state.residual)
            if ef_active and state.residual is not None and traced:
                reduced, residual = reduce_grads_ef(grads, state.residual)
            else:
                reduced = reduce_grads(grads, 1)
                residual = state.residual
            updates, inner = inner_update(reduced, state.inner_state)
            return updates, DistributedOptState(inner, state.accum,
                                                state.counter, residual)

        accum = jax.tree_util.tree_map(jnp.add, state.accum, grads)
        counter = state.counter + 1
        k = backward_passes_per_step

        if traced:
            ax = axis_name if axis_name is not None else _mesh.mesh_axis_name()

            def _vary(tree):
                # lax.cond requires both branches to agree on varying-manual-
                # axes types; psum outputs are axis-invariant while held
                # accumulators are varying, so cast everything to varying.
                return jax.tree_util.tree_map(
                    lambda x: _jit_ops.ensure_varying(x, ax), tree)

            def communicate(acc_inner):
                acc, inner_state = acc_inner
                reduced = reduce_grads(acc, k)
                updates, inner = inner_update(reduced, inner_state)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return _vary((updates, zeros, inner))

            def hold(acc_inner):
                acc, inner_state = acc_inner
                zero_upd = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return _vary((zero_upd, acc, inner_state))

            updates, accum, inner = jax.lax.cond(
                counter % k == 0, communicate, hold, (accum, state.inner_state))
            counter = jnp.where(counter % k == 0, 0, counter)
            return updates, DistributedOptState(inner, accum, counter,
                                                state.residual)

        # Eager: plain Python control flow.
        if int(counter) % k == 0:
            reduced = reduce_grads(accum, k)
            updates, inner = inner_update(reduced, state.inner_state)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return updates, DistributedOptState(inner, zeros,
                                                jnp.zeros((), jnp.int32),
                                                state.residual)
        zero_upd = jax.tree_util.tree_map(jnp.zeros_like, grads)
        return zero_upd, DistributedOptState(state.inner_state, accum, counter,
                                             state.residual)

    return optax.GradientTransformation(init_fn, update_fn)


def _sharded_distributed_optimizer(optimizer: optax.GradientTransformation,
                                   op: ReduceOp,
                                   axis_name) -> optax.GradientTransformation:
    """ZeRO-1 analog: optimizer states sharded over the reduction axis.

    Beyond-parity (the reference replicates optimizer state on every rank;
    SURVEY.md §2.7 — DP only).  Inside shard_map, gradients are
    reduce-scattered over the shard axis instead of allreduced, the inner
    optimizer updates only this rank's 1/n flat shard (so its m/v/momentum
    live once across the axis, n× smaller per chip), and the updates are
    all-gathered back — the same ring bytes as one allreduce.

    Mechanics: all gradient leaves are flattened into one fp32 vector,
    padded to axis_size × chunk; each rank owns chunk elements.  The state
    additionally keeps the rank's fp32 PARAMETER shard as true master
    weights: updates accumulate there in fp32 and the emitted pytree
    update is exactly ``cast(master) - current_param``, so bf16 models
    never lose sub-ulp updates to rounding.  Correct for every elementwise
    optimizer (sgd/momentum/adam/adamw/rmsprop-style per-element math);
    transforms needing tree structure or global stats (clip_by_global_norm)
    belong outside the wrapper or in the unsharded path.  Parameters must
    only evolve through this optimizer's updates (a broadcast or manual
    edit desynchronizes the master copy — re-init afterwards).

    Pre-reduced leaves (sequence/tensor-parallel backward passes psum some
    grads already) are normalized by the sizes of their already-reduced
    axes before the uniform reduce-scatter, which reproduces the vma-aware
    per-leaf semantics of the unsharded path.
    """

    _axes = lambda: _resolve_axes(axis_name)  # noqa: E731

    def _flatten(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            raise ValueError(
                "shard_optimizer_states=True needs a non-empty parameter/"
                "gradient pytree (nothing to shard)")
        return jnp.concatenate(
            [jnp.ravel(x).astype(jnp.float32) for x in leaves])

    def _shard_geometry(total):
        from jax import lax

        axes = _axes()
        shard_ax = axes[0]
        try:
            n = _jit_ops.axis_size(shard_ax)
        except NameError as exc:
            raise ValueError(
                "shard_optimizer_states=True runs inside jit/shard_map "
                "only (the shards live on the mesh axis); use the default "
                "replicated path eagerly") from exc
        chunk = -(-total // n)
        return axes, shard_ax, n, chunk

    def _param_shard(params):
        from jax import lax

        vec = _flatten(params)
        axes, shard_ax, n, chunk = _shard_geometry(vec.size)
        vec = jnp.pad(vec, (0, n * chunk - vec.size))
        idx = lax.axis_index(shard_ax)
        return jax.lax.dynamic_slice(vec, (idx * chunk,), (chunk,))

    def init_fn(params):
        shard = _param_shard(params)
        return ShardedOptState(inner_state=optimizer.init(shard),
                               master=shard)

    def update_fn(grads, state, params=None):
        from jax import lax

        if params is None:
            raise ValueError(
                "shard_optimizer_states=True needs params in update() "
                "(the rank's parameter shard feeds the inner optimizer)")
        leaves = jax.tree_util.tree_leaves(grads)
        axes = _axes()
        vma_tracked = _vma_tracked()

        def normalize(leaf):
            # A leaf invariant over some reduction axes was already summed
            # over them; dividing by those sizes makes one uniform psum
            # across all axes correct for every leaf.
            vma = _leaf_vma(leaf)
            if vma is None or not vma_tracked:
                return leaf
            pre = 1
            for a in axes:
                if a not in vma:
                    pre *= _jit_ops.axis_size(a)
            leaf = leaf if pre == 1 else leaf / pre
            return _jit_ops.ensure_varying(leaf, axes)

        grads = jax.tree_util.tree_map(normalize, grads)
        gvec = _flatten(grads)
        pleaves, ptreedef = jax.tree_util.tree_flatten(params)
        total = gvec.size
        _, shard_ax, n, chunk = _shard_geometry(total)
        pad = n * chunk - total
        gvec = jnp.pad(gvec, (0, pad))
        # Reduce over the non-shard axes in one combined psum, then
        # reduce-SCATTER over the shard axis: each rank ends with the
        # fully-summed gradient for its chunk.
        with jax.named_scope("hvd_exchange"):
            if len(axes) > 1:
                gvec = lax.psum(gvec, tuple(axes[1:]))
            gshard = lax.psum_scatter(gvec, shard_ax, scatter_dimension=0,
                                      tiled=True)
            if op == ReduceOp.AVERAGE:
                total_ranks = 1
                for a in axes:
                    total_ranks *= _jit_ops.axis_size(a)
                gshard = gshard / total_ranks
        token = _inner_shard_axis.set(shard_ax)
        try:
            with jax.named_scope("hvd_update"):
                upd_shard, new_inner = optimizer.update(
                    gshard, state.inner_state, state.master)
        finally:
            _inner_shard_axis.reset(token)
        # fp32 master weights: the update lands on the master shard, and
        # the pytree update emitted is cast(new master) - current param, so
        # params track the master exactly (no bf16 sub-ulp loss).
        new_master = state.master + upd_shard
        # Varying -> Invariant gather: every rank assembles the identical
        # full master vector, and its type says so (out_specs expecting
        # replicated params keep working).
        with jax.named_scope("hvd_exchange"):
            master_vec = all_gather_invariant(new_master, shard_ax,
                                              tiled=True)[:total]
        updates = []
        offset = 0
        for leaf in pleaves:
            piece = master_vec[offset:offset + leaf.size]
            new_leaf = piece.reshape(leaf.shape).astype(leaf.dtype)
            updates.append(new_leaf - leaf)
            offset += leaf.size
        return (jax.tree_util.tree_unflatten(ptreedef, updates),
                ShardedOptState(inner_state=new_inner, master=new_master))

    return optax.GradientTransformation(init_fn, update_fn)


def clip_by_global_norm(max_norm: float, axis_name=None
                        ) -> optax.GradientTransformation:
    """Global-norm gradient clipping that can see across mesh ranks.

    Without ``axis_name`` this is optax.clip_by_global_norm over whatever
    tree it receives.  With ``axis_name`` the squared norm is additionally
    psummed over those axes — required as the INNER transform of
    ``shard_optimizer_states=True`` (each rank holds only its 1/n chunk,
    so a local norm would misclip):

        tx = hvd.DistributedOptimizer(
            optax.chain(hvd.clip_by_global_norm(1.0, axis_name="dp"),
                        optax.adam(1e-3)),
            axis_name="dp", shard_optimizer_states=True)
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        from jax import lax

        del params
        leaves = jax.tree_util.tree_leaves(updates)
        local = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                    for l in leaves)
        if axis_name is not None:
            # As the inner transform of shard_optimizer_states the chunk
            # is already summed over every non-shard axis: psumming the
            # squared norm there too would inflate it by their sizes and
            # over-clip, so only the shard axis counts.
            axes = _resolve_axes(axis_name)
            shard_ax = _inner_shard_axis.get()
            if shard_ax is not None:
                axes = tuple(a for a in axes if a == shard_ax)
            if axes:
                local = lax.psum(local, axes)
        norm = jnp.sqrt(local)
        scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
        return (jax.tree_util.tree_map(
            lambda l: l * scale.astype(l.dtype), updates), state)

    return optax.GradientTransformation(init_fn, update_fn)


# Reference-name alias: the TF binding calls the same concept a
# DistributedGradientTape; in optax terms both are gradient transformations.
DistributedGradientTransformation = DistributedOptimizer


def _ps_world_size(process_set, axis_name, grads) -> Any:
    leaves = jax.tree_util.tree_leaves(grads)
    if leaves and _is_traced(leaves[0]):
        ax = axis_name if axis_name is not None else _mesh.mesh_axis_name()
        return _jit_ops.axis_size(ax)
    from .context import HorovodContext

    return len(HorovodContext.instance().core.process_set_ranks(
        _resolve_psid(process_set)))
