"""Family ``jamba``: ``horovod_tpu.models.Jamba`` (AI21-Jamba2-3B's decoder:
Mamba-1 selective-scan layers with a multi-query attention layer every
``attn_layer_period``, a dense SwiGLU feed-forward in every block, a head tied
to the embedding) trained on the next token, one chip's share of a layer
spread over several, tensor-parallel: ``mamba_d_inner_held`` of the Mamba
channels, ``num_attention_heads_held`` query heads on the key/value head whole,
``feed_forward_columns_held`` feed-forward columns, ``vocab_size_held`` rows of
the embedding.  On one chip the layers run with ``axis_name=None``: what the
other chips would add to each row-parallel sum is left out, in the program and
in the reference alike.

The step has the shape of ``families/zaya.py``'s: a jitted ``shard_map`` over
the ``hvd`` axis, the optimizer wrapped in ``hvd.DistributedOptimizer``, the
loss averaged over the axis; its state is ``(variables, optimizer state)``.
Nothing in it follows the weights' values (no routing), so the weights and the
traffic both follow the run's seed.

The reference is ``benchmark/references/jamba.py``: plain float32
``jax.numpy``, one device, "highest" matmul precision, one sequence at a
time, the scan a ``lax.scan`` over time.  Besides the loss, the sample's
logits and the first moments, **the scan is compared on its own** (``probe``):
the program's ``selective_scan`` on the reference's float32 operands of the
first block (``u``, ``dt``, ``A``, ``B``, ``C``, ``D`` as its own projections,
convolution, norms and softplus make them) against the reference's
recurrence, **and the model's own first mixer as the step runs it** (bfloat16
``u``, its own ``dt``, the kernel as the step specialises it) against the
reference's equations of the mixer's own numbers a stage at a time, so that
the precision of the state, of ``dt``, of the softplus and of the three norms
is held on the timed path, where bfloat16 activations do not drown it.
"""

from __future__ import annotations

from benchmark import common, jamba_flops
# The sample's positions (spread evenly, so that the late ones have a long
# past behind them) and the rows of the embedding that are compared are
# ZAYA's.
from benchmark.families.zaya import (  # noqa: F401
    EMBEDDING_ROWS, SAMPLE_POSITIONS, _cut, sample_positions)
from benchmark.references import jamba as reference_jamba

# How a limit is set: the rule of benchmark/testdata/check_rule.json, held on
# the readings in benchmark/testdata/check_readings/jamba.json.  Readings:
# TPU v5 lite, the cell jamba2-ssm-tp4-s16384, PR 47: 26 sound runs over 26
# seeds (1001 to 1004, 2001 to 2005, 2147483753, 2147483999, 2147484001,
# 3000000077, 3000000203, and with (e) and (f) 3000000401, 3000000402,
# 2147484123, 2147484124, 3000000517, 3000000518, 3000000621, 3000000622,
# 2147484231, 2147484232, 3000000733, 3000000734; all but the first four
# from archives of the tree).  The faults are ISSUE
# 47's list, made in the plain reference and read against the plain reference
# itself in each check's own measure at the cell's own size
# (tests/benchmark/jamba_faults.py, seeds 1 to 3; with --grads seeds 4 and
# 5).  The embedding starts at a deviation of 0.02, so the blocks' outputs,
# not the token's own embedding, are what the head reads and a fault of a
# Mamba block reads 0.5 to 1.4 on the logits.
#
# What tells what apart.  A sound step's logits lie 4 % and its first
# moments 5 to 11 % from the float32 reference's: bfloat16 operands through
# 14 blocks each way (PERF.md section 6, PR 47 says how that was told from a
# fault).  The scan's state or dt in bfloat16 reads 0.04 to 0.14 on (b) and
# 0.06 to 0.2 on (d), among the sound readings: (c) holds the kernels'
# float32 on float32 operands, where they read 0.0, bit for bit XLA's own
# scan, and (e), (f) hold the step's own mixer and kernel at the timed
# dtypes.
# The one attention block's faults read under (b)'s sound readings (rotary
# positions 0.029, a key/value head cut per query head 0.09): (d) holds them
# on that block's own projections.  Faults that a one or a zero hides at
# initialisation (the norms' scales, the convolution's bias, D taken as one)
# read nothing here at any size: the CPU tests hold them on weights where
# those leaves are moved (tests/single/test_jamba.py,
# tests/benchmark/test_jamba_cell.py).
#
# (a) First loss of the compiled step against the reference's.  Sound:
# 9.3e-8 to 8.5e-5 over 26 seeds.  Fault: the loss on the token itself (labels not
# shifted) 0.075 to 0.077; a state that grows without bound (A without its
# sign, the softplus left out) is not finite.  Every other fault reads 1e-6
# to 1.6e-3 (the loss at initialisation is log 16,384 and a little, whatever
# the blocks compute) and is not this check's.  Kept where SDAR's and ZAYA's
# stand: 24 x over the sound, 37 x under the fault.
TOL_FIRST_LOSS = 2e-3
# (b) Logits of the sample (SAMPLE_POSITIONS positions spread over the first
# sequence, all 16,384 held rows), L2 error.  Sound: 0.0383 to 0.0413.  Faults:
# the norm of dt left out 0.46 to 0.49, of B or of C 0.76 to 0.88, B and C
# swapped 1.02 to 1.09, a tap short 1.24 to 1.26, the convolution reading
# ahead, D left out, the gate on u, A without its exp 1.38 to 1.41.  Middle:
# 3.3 x from either.
TOL_SAMPLE_LOGITS = 0.138
# (c) The scan alone: the program's selective_scan on the reference's
# float32 operands of the first block against the reference's recurrence,
# max |a - b| / max |b| over 16384 x 1280.  Sound: 0.0 on every seed (the
# kernels and XLA's lax.scan of the same operands agree bit for bit; both lie
# 7.7e-6 from float64 by hand).  Faults: dt in bfloat16 1.7e-3 to 3.5e-3,
# the state in bfloat16 3.2e-3 to 6.2e-2.  17 x under the nearest fault.
TOL_SCAN = 1e-4
# (d) The first moment after one step is (1 - b1) x the gradient: leaves
# that only a right backward of the scan gives (A_log, D, the bias of dt, the
# convolution's taps, the dt norm's scale, x_proj, in_proj) in the first and
# the last Mamba block, the attention block's query and key/value
# projections, rows of the embedding; L2 error over the leaf.  Sound: 0.047
# to 0.113 (the last Mamba block's dt norm reads most).  Faults, on the attention block's projections: rotary positions
# added 0.60 (key/value) and 1.14 (query), the key/value head cut per query
# head 1.24 to 1.28; on a Mamba block's leaves: the norm of dt left out 0.64
# to 1.0, B and C swapped 1.19 to 1.59, the gate on u 1.02 to 2.48.  Middle
# of the sound and the nearest: 2.3 x from either.
TOL_FIRST_MOMENT = 0.26
# (e), (f) The step's own path held to the configuration's float32 (c reads
# the kernel alone, on float32 operands that no step hands it): the model's
# own first mixer as the step runs it (first_mixer: bfloat16 u, its own dt,
# the kernel specialised as in the step), compared a stage at a time with
# the reference's equations of the mixer's own numbers
# (first_mixer_errors), so that the bfloat16 before a stage is on both
# sides.  Readings: seeds 11 to 13 and 21 to 23 (jamba_faults.py --program)
# and the twelve sound runs from seed 3000000401 on.  Why not against the reference's own first
# block: the mixer's dt lies 2.63e-3 to 2.66e-3 and its y 4.62e-3 to 4.66e-3
# (L2) from the float32 reference's, bfloat16 operands before them, and the
# faults below read 2.78e-3 to 4.80e-3 and 4.60e-3 to 2.95e-2 there: from
# 1.005 x the sound (the kernel's dt) and 1.05 x (the norms) to 1.8 x (the
# softplus), no room for a limit.
# (e) dt, A, B, C of the mixer against the reference's three norms, dt
# product (its two operands rounded to bfloat16, as the configuration's
# precision states), softplus and -exp of the mixer's own x_proj result;
# the largest of the four L2 errors.  Sound: 0.0 on every seed.  Faults, made
# in the program: the mixer's norms rounded to bfloat16 2.25e-3 to 2.26e-3,
# its softplus 3.96e-3 to 4.02e-3.  22 x under the nearest.
TOL_MIXER_STEP = 1e-4
# (f) The kernel's y at the step's dtypes against the reference's
# recurrence of the operands the mixer handed it, rounded once to the
# activations' dtype; L2.  Sound: 0.0 on every seed.  Faults, made in the
# program: the scan rounding the dt it is handed to bfloat16 1.09e-3 to
# 1.12e-3, its state after every step 4.1e-3 to 2.9e-2.  11 x under the
# nearest.
TOL_MIXER_SCAN = 1e-4


def _sizes(cfg: dict, rehearse: bool) -> dict:
    return {**cfg["assumed"], **cfg, **(cfg["rehearse"] if rehearse else {})}


def _jamba_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu import models

    c = _sizes(cfg, rehearse)
    return models.JambaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        intermediate_size=c["intermediate_size"],
        mamba_expand=c["mamba_expand"], mamba_d_conv=c["mamba_d_conv"],
        mamba_d_state=c["mamba_d_state"], mamba_dt_rank=c["mamba_dt_rank"],
        mamba_conv_bias=c["mamba_conv_bias"],
        mamba_proj_bias=c["mamba_proj_bias"],
        rms_norm_eps=c["rms_norm_eps"],
        vocab_size_held=c["vocab_size_held"],
        num_heads_held=c["num_attention_heads_held"],
        intermediate_size_held=c["feed_forward_columns_held"],
        mamba_d_inner_held=c["mamba_d_inner_held"],
        checkpoint_blocks=c["checkpoint_blocks"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"])


def reference_config(jcfg) -> dict:
    """What ``references/jamba.py`` reads of a configuration."""
    return {"rms_norm_eps": jcfg.rms_norm_eps,
            "mamba_dt_rank": jcfg.mamba_dt_rank,
            "mamba_d_state": jcfg.mamba_d_state}


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and seeded variables (replicated), made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import models

    jcfg = _jamba_config(cfg, rehearse)
    model = models.Jamba(jcfg)
    # The key is an argument, not a constant of the program (families/gpt.py).
    key = jax.random.fold_in(jax.random.key(seed), 0)
    params = jax.jit(
        lambda k: model.init(k, jnp.zeros((1, 16), jnp.int32)),
        out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "jcfg": jcfg,
            "rehearse": rehearse, "params": params}


def inputs(cell: dict, traffic: dict) -> list:
    """The one drawn argument of the step, per sequence: token ids of the
    held slice."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    return [Input((traffic["seq_len"],), jnp.int32, "randint",
                  cell["jcfg"].rows_held)]


def _loss(model, variables, ids):
    from horovod_tpu.models import jamba

    return jamba.lm_loss(model, variables, ids)


# Of a Mamba mixer, the leaves that only a right backward of the scan (and
# of what feeds it) gives.
MAMBA_LEAVES = ("A_log", "D", "dt_bias", "conv", "dt_norm")


def _checked_tree(tree, jcfg) -> dict:
    """The leaves check (d) compares, as a sub-tree with the whole tree's
    paths: in the first and the last Mamba block ``A_log``, ``D``, the bias
    of ``dt``, the convolution's taps, the ``dt`` norm's scale and the
    kernels of ``x_proj`` and ``in_proj``; the first attention block's query
    and key/value projections; rows of the tied embedding."""
    p = tree["params"]
    kinds = jcfg.layer_kinds
    mamba = [i for i, k in enumerate(kinds) if k == "mamba"]
    cut = {"embed": {"embedding": p["embed"]["embedding"][:EMBEDDING_ROWS]}}
    for i in sorted({mamba[0], mamba[-1]}):
        mixer = p[f"layer_{i}"]["mamba"]
        cut[f"layer_{i}"] = {"mamba": {
            **{k: mixer[k] for k in MAMBA_LEAVES},
            "x_proj": {"kernel": mixer["x_proj"]["kernel"]},
            "in_proj": {"kernel": mixer["in_proj"]["kernel"]}}}
    if "attention" in kinds:
        attn = p[f"layer_{kinds.index('attention')}"]["attn"]
        cut[f"layer_{kinds.index('attention')}"] = {"attn": {
            "q_proj": {"kernel": attn["q_proj"]["kernel"]},
            "kv_proj": {"kernel": attn["kv_proj"]["kernel"]}}}
    return {"params": cut}


def _system_logits(cell: dict, variables, ids, positions):
    """The system's forward on ``ids`` under the cell's precision and
    kernels: the logits at ``positions`` of the first sequence."""
    import jax

    model = cell["model"]

    def forward(v, ids):
        x = model.apply(v, ids, method="hidden")
        return model.apply(v, x[0, positions], method="head")

    return jax.jit(forward)(variables, ids)


def first_scan(p, row, rcfg) -> tuple:
    """The reference's first block as far as its scan, on one sequence
    ``row`` [S]: the operands ``(u, dt, A, B, C, D)`` its projections,
    convolution, norms and softplus make, and what its recurrence makes of
    them."""
    p = p["params"]
    block = p["layer_0"]
    h = reference_jamba.rms_norm(p["embed"]["embedding"][row],
                                 block["input_norm"]["scale"],
                                 rcfg["rms_norm_eps"])
    operands, _ = reference_jamba.scan_operands(block["mamba"], h, rcfg)
    return operands, reference_jamba.recurrence(*operands)


def reference(cell: dict) -> dict:
    """The plain float32 reference on the first global batch, a sequence at
    a time: its loss, its gradient of the named leaves and the first moment
    one plain optax update of them leaves behind; on the sample (the first
    sequence) its logits at the sample's positions, and the first block's
    scan operands with what its recurrence makes of them (kept in
    ``cell["sample"]`` for ``probe``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    jcfg, mesh = cell["jcfg"], cell["mesh"]
    device = mesh.devices.flat[0]
    variables = common.first_shard(cell["params"])
    ids = jax.device_put(cell["batches"][0][0], device)
    sequences, length = ids.shape
    rcfg = reference_config(jcfg)
    positions = sample_positions(length)

    def part(p, ids):
        x = reference_jamba.hidden(p["params"], ids, rcfg)
        loss = reference_jamba.loss_sum(p["params"], x, ids) / (
            sequences * (length - 1))
        return loss, reference_jamba.head(p["params"], x[positions])

    def part_and_leaf_grads(p, ids):
        (loss, logits), grads = jax.value_and_grad(part, has_aux=True)(p, ids)
        return loss, logits, _checked_tree(grads, jcfg)

    fn = jax.jit(part_and_leaf_grads)
    params = {"params": variables["params"]}
    loss, grads, sample = 0.0, None, None
    with jax.default_matmul_precision("highest"):
        for i in range(sequences):
            part_loss, logits, part_grads = fn(params, ids[i])
            loss += float(part_loss)
            grads = part_grads if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, part_grads)
            if sample is None:
                sample = {"ids": ids[:1], "positions": positions,
                          "logits": np.asarray(logits)}
                if jcfg.layer_kinds[0] == "mamba":
                    sample["scan"] = jax.jit(
                        lambda p, row: first_scan(p, row, rcfg))(params,
                                                                 ids[0])
    cell["sample"] = sample
    leaves = _checked_tree(params, jcfg)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    _, opt_state = tx.update(grads, tx.init(leaves), leaves)
    return {"loss": loss, "leaves": {
        k: {"first_moment": np.asarray(common.first_moments(opt_state, k)[0])}
        for k in common.leaf_paths(leaves)}}


def scan_error(operands, want) -> float:
    """The program's ``selective_scan`` on ``operands`` (one sequence's, as
    the reference makes them: float32, ``[S, C]`` and ``[S, N]``) against
    the reference's recurrence of them."""
    import jax

    from horovod_tpu.ops.selective_scan import selective_scan

    u, dt, rate, b, c, d = operands
    got = jax.jit(lambda *a: selective_scan(*a))(
        u[None], dt[None], rate, b[None], c[None], d)[0]
    return common.rel_err(got, want)


def first_mixer(cell: dict, variables, ids) -> dict:
    """The model's own first block on ``ids`` [1, S] as the step runs it: the
    model cut to that block (its embedding, norm and mixer at the cell's
    dtypes, the kernels on a TPU, ``u`` as the mixer hands it over and the
    mixer's own ``dt``), applied with its ``intermediates`` kept: what
    ``x_proj`` made, the operands the scan took and what the scan made of
    them."""
    import dataclasses

    import jax

    from horovod_tpu import models

    model = models.Jamba(dataclasses.replace(
        cell["jcfg"], num_layers=1, checkpoint_blocks=False))
    p = variables["params"]
    cut = {"params": {k: p[k] for k in ("embed", "layer_0", "final_norm")}}

    def kept(v, ids):
        _, state = model.apply(v, ids, method="hidden",
                               mutable=["intermediates"])
        return state["intermediates"]["layer_0"]["mamba"]["scan"][0]

    return jax.jit(kept)(cut, ids)


def first_mixer_errors(cell: dict, variables, kept: dict) -> dict:
    """What :func:`first_mixer` kept against the plain reference's equations
    **of the program's own numbers**, a stage at a time, so that the
    bfloat16 of the activations before a stage is on both sides and what the
    stage itself must keep in float32 is all that is left:

    - ``step_and_norms``: the mixer's ``dt``, ``B``, ``C`` (and ``A``)
      against the reference's three norms, ``dt`` product, softplus (and
      ``-exp``) of the mixer's own ``x_proj`` result, the product's two
      operands rounded to the activations' dtype as the configuration's
      precision states; the largest of the four L2 errors.
    - ``scan``: what the kernel (at the step's own dtypes) made of the
      operands the mixer handed it against the reference's recurrence of
      those operands, rounded once to the dtype the kernel hands on; L2.

    The errors are reduced on the device (float32): 21 M numbers a pair do
    not lie beside the step's state twice.
    """
    import jax
    import jax.numpy as jnp

    jcfg = cell["jcfg"]
    rcfg = reference_config(jcfg)
    info = jnp.finfo(jcfg.dtype)
    wide = lambda x: x.astype(jnp.float32)  # noqa: E731
    # Not a cast there and back: the TPU's compiler takes that pair out.
    low = lambda x: jax.lax.reduce_precision(  # noqa: E731
        wide(x), info.nexp, info.nmant)

    def l2(got, want):
        return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(
            want.ravel())

    def errors(p, kept):
        u, dt, rate, b, c, d = (x[0] if x.ndim == 3 else x
                                for x in kept["operands"])
        normed, b_ref, c_ref = reference_jamba.three_norms(
            p, *reference_jamba.split_dt_b_c(kept["x_proj"][0], rcfg),
            rcfg["rms_norm_eps"])
        dt_ref = reference_jamba.step_size(
            {**p, "dt_proj": low(p["dt_proj"])}, low(normed))
        y = reference_jamba.recurrence(wide(u), dt, rate, b, c, d)
        return {"step_and_norms": jnp.max(jnp.stack([
                    l2(dt, dt_ref), l2(rate, reference_jamba.decay_rate(p)),
                    l2(b, b_ref), l2(c, c_ref)])),
                "scan": l2(wide(kept["y"][0]), low(y))}

    with jax.default_matmul_precision("highest"):
        return {k: float(v) for k, v in jax.jit(errors)(
            variables["params"]["layer_0"]["mamba"], kept).items()}


def probe(cell: dict, step, state) -> list:
    """(b) The system's forward on the sample before the first step moves
    the weights: its logits against the reference's; (c) the program's scan
    on the reference's float32 operands of the first block against the
    reference's recurrence; (e), (f) the model's own first mixer as the step
    runs it, its ``dt`` and norms and its kernel's ``y``, against the
    reference's equations of the mixer's own numbers."""
    import numpy as np

    sample = cell.pop("sample")
    variables = common.first_shard(state[0])
    logits = _system_logits(cell, variables, sample["ids"],
                            sample["positions"])
    out = [common.check("sample_logits_vs_reference", common.l2_rel_err(
               logits, sample["logits"]), TOL_SAMPLE_LOGITS),
           {"name": "logits_are_float32",
            "ok": bool(logits.dtype == np.float32)}]
    if "scan" in sample:
        out.append(common.check(
            "scan_of_the_reference_s_operands_vs_reference",
            scan_error(*sample.pop("scan")), TOL_SCAN))
        errors = first_mixer_errors(
            cell, variables, first_mixer(cell, variables, sample["ids"]))
        out += [common.check("first_mixer_step_and_norms_vs_reference",
                             errors["step_and_norms"], TOL_MIXER_STEP),
                common.check("first_mixer_scan_vs_reference",
                             errors["scan"], TOL_MIXER_SCAN)]
    return out


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    model, mesh = cell["model"], cell["mesh"]
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(variables, opt_state, ids):
        loss, grads = jax.value_and_grad(
            lambda v: _loss(model, v, ids))(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    drawn = cell["batches"][0]
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), *(P("hvd") for _ in drawn)),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    state = (cell["params"], opt_state)
    compiled = step.lower(*state, *drawn).compile()
    cell["kernel_calls"] = kernel_calls(compiled.as_text())
    note_ssm(cell)
    return compiled, state


KERNELS = ("hvd_ssm_scan_fwd", "hvd_ssm_scan_bwd", "hvd_flash_fwd",
           "hvd_flash_dq", "hvd_flash_dkv")


def kernel_calls(hlo: str) -> dict:
    """Calls of each named Pallas kernel in a compiled step's text: an
    instruction is ``%<name> = ... custom-call(...)
    custom_call_target="tpu_custom_call"`` and carries the kernel's name in
    its own."""
    import re

    return {k: len(re.findall(
        rf"{k}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
        for k in KERNELS}


def least_calls(jcfg) -> dict:
    """The fewest calls of each kernel a sound step holds: a backward a
    Mamba layer, a forward a layer and one more where the block is
    checkpointed; the three flash kernels an attention layer."""
    mamba = jcfg.layer_kinds.count("mamba")
    attention = jcfg.layer_kinds.count("attention")
    return {"hvd_ssm_scan_fwd": mamba * (2 if jcfg.checkpoint_blocks else 1),
            "hvd_ssm_scan_bwd": mamba, "hvd_flash_fwd": attention,
            "hvd_flash_dq": attention, "hvd_flash_dkv": attention}


def note_ssm(cell: dict) -> None:
    """The ``"note": "ssm"`` line: what this chip holds, the scan kernels'
    plan at the cell's shapes and each kernel's calls in the step."""
    import json

    from horovod_tpu.ops import selective_scan as ss

    jcfg = cell["jcfg"]
    batch, length = cell["batches"][0][0].shape
    chunk, padded, block = ss.plan(length, jcfg.channels_held, None, None)
    print(json.dumps({
        "note": "ssm", "layer_kinds": list(jcfg.layer_kinds),
        "held": {"mamba_channels": jcfg.channels_held,
                 "query_heads": jcfg.heads_held,
                 "key_value_heads": jcfg.num_kv_heads,
                 "feed_forward_columns": jcfg.columns_held,
                 "vocabulary_rows": jcfg.rows_held},
        "scan": {"chunk": chunk, "block": block, "padded_length": padded,
                 "states": jcfg.mamba_d_state},
        "kernel_calls": cell["kernel_calls"],
        "least_calls": least_calls(jcfg)}), flush=True)


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    import jax
    import jax.numpy as jnp

    jcfg = cell["jcfg"]
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if jax.default_backend() == "tpu":
        # The Pallas kernels, not their jax.numpy forms, are in the step.
        least = least_calls(jcfg)
        scans = {k: v for k, v in least.items() if "ssm" in k}
        for name, count in scans.items():
            out.append(common.at_least(f"calls_of_{name}",
                                       cell["kernel_calls"][name], count))
        if jcfg.use_flash:
            out.append(common.at_least(
                "calls_of_hvd_flash_kernels",
                sum(v for k, v in cell["kernel_calls"].items()
                    if "flash" in k),
                sum(v for k, v in least.items() if "flash" in k)))
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    variables, opt_state = state
    for k, want in ref["leaves"].items():
        moments = common.first_moments(opt_state, k)
        assert len(moments) == 1, (k, len(moments))
        out.append(common.check(f"first_moment{k}", common.l2_rel_err(
            jax.device_get(_cut(k, moments[0])), want["first_moment"]),
            TOL_FIRST_MOMENT))
    inexact = [x.dtype for x in jax.tree_util.tree_leaves(state)
               if jnp.issubdtype(x.dtype, jnp.inexact)]
    out.append({"name": "parameters_and_moments_are_float32",
                "ok": bool(inexact) and all(
                    d == jnp.float32 for d in inexact)})
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch, as
    the algorithm needs them (``jamba_flops.forward_macs``); recomputation
    and the scan's own arithmetic are not counted."""
    cfg = _sizes(cell["cfg"], cell["rehearse"])
    return jamba_flops.model_flops(cfg, cell["traffic"], cell["mesh"].size)


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
