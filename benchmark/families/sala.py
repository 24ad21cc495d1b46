"""Family ``sala``: ``horovod_tpu.models.sala.Sala`` (MiniCPM-SALA's decoder:
lightning linear-attention layers and block-selected softmax-attention layers
by the published ``mixer_types``, output gates on both, q/k norms, MiniCPM's
scaled embedding, residual and head, a dense SwiGLU in every block, an untied
head) trained on the next token, one chip's share of a layer shared four ways:
``lightning_heads_held`` lightning heads with their own slopes,
``num_attention_heads_held`` query heads of a sparse layer on the
``num_key_value_heads_held`` key/value heads they read,
``feed_forward_columns_held`` SwiGLU columns, ``vocab_size_held`` rows of the
embedding and the head.  On one chip the layers run with ``axis_name=None``:
what the other chips would add to each sum is left out, in the program and in
the reference alike.

The step has the shape of ``families/joyai.py``'s: a jitted ``shard_map`` over
the ``hvd`` axis, the optimizer wrapped in ``hvd.DistributedOptimizer``, the
loss averaged over the axis; its state is ``(variables, optimizer state,
chosen)``, the last what the step's sparse layers chose, as packed bits
(``ops/flash_select.py:Selection``'s layout, [sparse layers, B, G, words, S]:
half a megabyte a layer at 16,384 queries of 256 blocks).  The weights are
one draw, named in the configuration (``assumed.weights_seed``); ``--seed``
draws the traffic.

The reference is ``benchmark/references/sala.py``: plain float32
``jax.numpy``, one device, "highest" matmul precision, one sequence at a time.
**The selection is a discrete choice**, as a router's is: a bfloat16 step and
a float32 reference part on near-ties.  So the selection is compared on its
own (the program's ``sparse_select`` on the reference's float32 q and k of
the first sparse layer: its block scores against the reference's; the share
of the system's (query, block) choices that the reference's own choice does
not hold) and everything downstream is compared with the reference **run on
the choices of the very program it is compared with**: the sample's logits
with the reference on the choices of the forward that made them, the step's
loss, moments and update with the reference on the choices the step itself
hands out; of the step's backward, beside the first moments of the leaves
that one position's rounding cannot swamp, **the embedding's first moment a
sampled position at a time** (``errors_by_row``: d loss / d h0 through every
block and both mixers' kernels).  **The kernels are compared on their own**
too: the first sparse
layer's walk and the first lightning layer's chunked recurrence in that
forward (their own bfloat16 operands) against the reference's masked dense
softmax on the system's choice and its quadratic form with the layer's
slopes.  The walk's counters of the first batch (pairs chosen, pairs the
tiles visit, queries that leave a visible block out) ride on the choices'
check and are what ``sala_sparse_visited_over_chosen`` reads.
"""

from __future__ import annotations

from benchmark import common, sala_flops
from benchmark.families import bert
from benchmark.families.laguna import weights_seed  # noqa: F401
from benchmark.families.sdar import moment_error
from benchmark.families.zaya import (  # noqa: F401
    EMBEDDING_ROWS, _cut, sample_positions)
from benchmark.references import sala as reference_sala

# How a limit is set: the rule of benchmark/testdata/check_rule.json, held on
# the readings in benchmark/testdata/check_readings/sala.json.  Readings: TPU
# v5 lite, the cell sala-sparse-linear-tp4-s16384, PR 58: the file's sound
# runs, a seed each, of the tree as it is.  The faults are ISSUE 58's and one
# of the backward alone (dS not carried into dk and dv), made in
# the plain reference and read against the plain reference itself on the
# fault's own choices, in each check's own measure at the cell's own size
# (tests/benchmark/sala_faults.py, seeds 1 and 2, gradients included).  Every
# fault but one reads the rule's margin over one limit at least, and the file
# says which; the one, the lightning mix in bfloat16 end to end, reads 1.6
# times a sound run on (f): the kernels round the decayed scores and the
# state to bfloat16 where they are a product's operands, as a flash kernel
# rounds p, so that is the stated precision's neighbour by less than the
# rule's room.  The fault of precision that is refused is the selection's.
#
# What tells what apart.  A sound step's logits lie 0.65 % and its first
# moments 0.6 to 1.8 % from the float32 reference's: bfloat16 operands through
# four blocks each way.  **The selected walk replaced by plain causal
# attention reads 0.6 % on the logits, a sound run's own reading** (at
# initialisation a query's softmax over 16,384 near-equal scores and over its
# 4,096 chosen keys average the same v): (f) holds the walk on its own
# operands, where that fault reads 40 %.  1 / sqrt(128) left out of the
# lightning scores is divided out again by the output norm and reads nothing
# downstream: (f) reads it before the norm.
#
# (a) First loss of the compiled step against the reference's on the step's
# choices.  Sound: 1e-7 to 4e-7.  Under an untied head at initialisation the
# loss is log 18,362 and a little whatever the blocks compute, so the faults
# read 1e-6 to 4e-5 here, all but the head not divided by 16: 0.051.  Kept
# where the other long cells' stand.
TOL_FIRST_LOSS = 1e-4
# (b) Logits of the sample (256 positions spread over the first sequence, all
# 18,362 held rows), L2 error.  Sound: 0.0065 on every seed.  Faults: the
# state not carried 0.226, the slopes of the cut's depth 0.314, the output
# gate left out 0.415, rotary off the lightning layers 0.441, decay missing
# 0.537, the wrong head's slopes 0.541, s of the cut's depth 0.78, the output
# norm left out 1.15, the head not divided 15.  (q/k norm left out 0.029 and
# rotary on the sparse layer 0.015 are (d)'s and (c)'s.)  4.6 x over the one,
# 7.5 x under the other.
TOL_SAMPLE_LOGITS = 0.03
# (c) The selection alone, on the reference's float32 q and k of the first
# sparse layer: the program's block scores against the reference's, max |a -
# b| / max |b| over 16384 x 256.  Sound: 3e-8 to 6e-8.  Faults: the group's
# sum in bfloat16 (tests/benchmark/sala_faults.py), a stride of 32 under the
# mean pool 0.50, the first head's p for the group's sum 0.875.  Kept where
# the routers' scores stand in the expert families.
TOL_BLOCK_SCORES = 1e-4
# (c) The share of the system's (query, block) choices, the forced blocks left
# aside, that the reference's own choice of the same query does not hold:
# near-ties that bfloat16 activations flip.  Sound: 0.0038 to 0.0039.  Faults
# (their own choices against the sound reference's): q/k norm left out 0.056,
# the stride 0.28, the local blocks not forced 0.34, selection by a head
# 0.535, rotary on the sparse layer 0.63.  Middle: 3.8 x from either.
TOL_CHOICES_DIFFERING = 0.015
# (c) The share of the sample's queries whose choice breaks step 5 as the
# reference keeps it: another number of blocks than the reference's own choice
# of that query holds, or one of the blocks the reference always chooses left
# out.  Whole numbers on both sides: a sound run reads 0.  Faults: top-k of
# 63 and the local blocks not forced, 0.75 each (every query past the 64th
# block).
TOL_CHOICE_RULE = 1e-3
# (d) The first moment after one step is (1 - b1) x the gradient, L2 error
# over the leaf, **of the leaves above every lightning layer's output norm**
# (``above_every_output_norm`` says why: the head, the last block's gate/up
# pair and down).  Sound: 0.0063 to 0.0121.  Faults, the largest of those
# leaves (check_readings/sala.json): the head not divided by 16 and what
# changes the stream the last block reads.  The other checked leaves (both
# mixers' five kernels and norms' scales of the first layer of each kind, the
# first block's pair, rows of the embedding) read 0.011 to 0.025 on seven
# sound runs of ten and 0.06 / 0.13 / 0.98 on three, by the rounding of the
# sequence's first two positions: they ride on the last check for the
# record, (g) holds the backward they come from a position at a time, and (e)
# holds all.  Kept where Laguna's and JoyAI's stand.
TOL_FIRST_MOMENT = 0.09
# (g) The step's own backward a position at a time: the first moment of the
# embedding's rows of the sample's tokens is (1 - b1) x scale_emb x d loss /
# d h0 at 255 positions spread over the sequence, through all four blocks and
# both mixers' kernels; a row's L2 error, and of those **what nine rows in
# ten lie within** (``nine_in_ten``).  Sound: 0.01101 to 0.01105 on seven
# runs, two of them seeds on which (d)'s other leaves read 0.35 and 0.98 (the
# median 0.0107; the largest row 0.0113 to 0.0114, and 0.041 on the one run
# where a sampled token also stands among the first positions).
# Faults: the backward's dk and dv without the chunks after (dS not carried,
# the forward sound) 0.486, the state not carried 0.538.  Plain causal
# attention for the walk reads 0.012 here, a sound run's own (the sparse
# layer's part of d h0 is small at initialisation, as its part of the logits
# is): the walk's backward is (f)'s.  Middle: 6.6 x from either.
TOL_HIDDEN_GRADIENT = 0.073
# (f) The two kernels' gradients on those operands: dq, dk and dv of one drawn
# cotangent, the system's kernels again against the reference's forms
# differentiated, L2 over the three together (a pass of the check's own: the
# step's backward is (g)'s).  Sound: 0.0029 and 0.0030 on every seed.
# Faults, on unit-normal operands: plain causal attention for the walk 0.431;
# dS not carried in dk and dv 0.459, the state not carried 0.562.  8 x over
# the one, 18 x under the other.  Kept where (f)'s stand.
TOL_FIRST_SPARSE_GRADS = 0.024
TOL_FIRST_LIGHTNING_GRADS = 0.024
# (e) What the first step did to the same leaves against plain AdamW of the
# moments the step itself left behind (``bert.adamw_first_update``, float64):
# the L2 error of the change.  Sound: 1.0e-3 to 3.2e-3.  Fault: the
# parameters kept in bfloat16 lose the update whole, 1.0.  Kept where the
# other families' stand.
TOL_FIRST_UPDATE = 0.1
# A norm's scale starts at one: the rate 2e-7 is two to four float32 ulps of
# such an entry and the update itself is rounded.  Sound: 0.20 to 0.23 on
# every seed.  Fault: as above, 1.0.  Kept where ``families/zaya.py``'s
# stands.
TOL_FIRST_UPDATE_UNIT = 0.57
# (f) The kernels alone: the first sparse layer's walk in the system's
# forward (its own bfloat16 q, k, v after the head norms, the system's own
# choice) against the reference's masked dense softmax of those operands on
# that choice, L2 over the first sequence's output.  Sound: 0.0021.  Fault:
# plain causal attention for the selected walk 0.404.  11 x over the one,
# 17 x under the other.
TOL_FIRST_SPARSE_ATTENTION = 0.024
# (f) The first lightning layer's chunked recurrence (its own bfloat16 q, k
# after norm and rotary, v) against the reference's quadratic form of those
# operands under the layer's slopes.  Sound: 0.0025.  Faults: the slopes of
# the cut's depth 0.285, the state not carried across a chunk 0.56, the wrong
# head's slopes 0.97, decay missing 10.1, 1 / sqrt(128) left out 10.3.  (In
# bfloat16 end to end: 0.0041, refused by nothing; see above.)  9.6 x over
# the one, 12 x under the other.
TOL_FIRST_LIGHTNING = 0.024


def _sizes(cfg: dict, rehearse: bool) -> dict:
    return {**cfg["assumed"], **cfg["assumed"]["sparse_config"], **cfg,
            **(cfg["rehearse"] if rehearse else {})}


def _sala_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu.models import sala
    from horovod_tpu.ops import lightning_attention

    c = _sizes(cfg, rehearse)
    if not rehearse and c["lightning_chunk"] != lightning_attention.CHUNK:
        # What benchmark/sala_flops.py counts by is what the program runs.
        raise ValueError(
            f"{cfg['name']}: lightning_chunk {c['lightning_chunk']} against "
            f"the kernels' {lightning_attention.CHUNK}")
    return sala.SalaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        published_layers=c["published_num_hidden_layers"],
        mixer_types=tuple(c["mixer_types"]),
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"],
        intermediate_size=c["intermediate_size"],
        rope_theta=float(c["rope_theta"]), rms_norm_eps=c["rms_norm_eps"],
        scale_emb=float(c["scale_emb"]), scale_depth=float(c["scale_depth"]),
        dim_model_base=c["dim_model_base"],
        sparse_kernel_size=c["kernel_size"],
        sparse_kernel_stride=c["kernel_stride"],
        sparse_block_size=c["block_size"], sparse_topk=c["topk"],
        sparse_init_blocks=c["init_blocks"],
        sparse_window_size=c["window_size"], sparse_dense_len=c["dense_len"],
        vocab_size_held=c["vocab_size_held"],
        num_heads_held=c["num_attention_heads_held"],
        num_kv_heads_held=c["num_key_value_heads_held"],
        lightning_heads_held=c["lightning_heads_held"],
        first_lightning_head=c["first_lightning_head"],
        intermediate_size_held=c["feed_forward_columns_held"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"])


def reference_config(scfg) -> dict:
    """What ``references/sala.py`` reads of a configuration."""
    return {"rms_norm_eps": scfg.rms_norm_eps, "rope_theta": scfg.rope_theta,
            "scale_emb": scfg.scale_emb, "scale_depth": scfg.scale_depth,
            "published_layers": scfg.published_layers,
            "dim_model_base": scfg.dim_model_base,
            "mixer_types": scfg.mixer_types,
            "lightning_heads": scfg.lightning_heads,
            "first_lightning_head": scfg.first_lightning_head,
            "kernel_size": scfg.sparse_kernel_size,
            "kernel_stride": scfg.sparse_kernel_stride,
            "block_size": scfg.sparse_block_size, "topk": scfg.sparse_topk,
            "init_blocks": scfg.sparse_init_blocks,
            "window_size": scfg.sparse_window_size,
            "dense_len": scfg.sparse_dense_len}


def published(variables) -> dict:
    """The program's variables as the tree of plain arrays
    ``references/sala.py`` reads.  Only selections: a gradient taken through
    it lies in the program's own leaves."""
    p = variables["params"]

    def attn(a):
        out = {short: a[f"{short}_proj"]["kernel"]
               for short in ("q", "k", "v", "gate", "o")}
        return {**out, **{name: a[name]["scale"]
                          for name in ("q_norm", "k_norm", "o_norm")
                          if name in a}}

    def block(b):
        return {"attn": attn(b["attn"]),
                "input_norm": b["input_norm"]["scale"],
                "post_attn_norm": b["post_attn_norm"]["scale"],
                "mlp": {"gate_up": b["mlp"]["gate_up"]["kernel"],
                        "down": b["mlp"]["down"]["kernel"]}}

    return {"embed": p["embed"]["embedding"],
            "final_norm": p["final_norm"]["scale"], "lm_head": p["lm_head"],
            **{name: block(b) for name, b in p.items()
               if name.startswith("layer_")}}


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and weights (replicated), made on the device in one jitted call
    from the configuration's key.  ``seed``, the run's, is not read here: it
    draws the traffic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import sala

    scfg = _sala_config(cfg, rehearse)
    model = sala.Sala(scfg)
    key = jax.random.fold_in(jax.random.key(weights_seed(cfg)), 0)
    params = jax.jit(
        lambda k: model.init(k, jnp.zeros((1, 16), jnp.int32)),
        out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "scfg": scfg,
            "rehearse": rehearse, "params": params}


def inputs(cell: dict, traffic: dict) -> list:
    """The one drawn argument of the step, per sequence: token ids of the
    held slice."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    return [Input((traffic["seq_len"],), jnp.int32, "randint",
                  cell["scfg"].rows_held)]


def _layers(scfg, kind: str) -> list:
    return [i for i in range(scfg.num_layers) if scfg.mixer_types[i] == kind]


def _selecting(cell: dict) -> list:
    """The sparse layers that choose blocks at the cell's sequence length."""
    from horovod_tpu.models import sala

    scfg = cell["scfg"]
    seq = cell["batches"][0][0].shape[1]
    return _layers(scfg, sala.SPARSE) if scfg.selects(seq) else []


def _chosen(intermediates: dict, layers: list):
    """int32 [sparse layers, B, G, words, S]: the bits each selecting layer
    handed its kernels, as ``models/sala.py:SparseAttention`` sows them."""
    import jax.numpy as jnp

    if not layers:
        return jnp.zeros((0,), jnp.int32)
    return jnp.stack([intermediates[f"layer_{i}"]["attn"]["chosen"][0]
                      for i in layers])


def _loss_and_choices(model, layers: list, variables, ids):
    loss, seen = model.apply(variables, ids, method="loss",
                             mutable=["intermediates"])
    return loss, _chosen(seen["intermediates"], layers)


def _checked_tree(tree, scfg) -> dict:
    """The leaves checks (d) and (e) compare, as a sub-tree with the whole
    tree's paths: a leaf of every kind the family brings."""
    from horovod_tpu.models import sala

    p = tree["params"]
    cut = {"embed": {"embedding": p["embed"]["embedding"][:EMBEDDING_ROWS]},
           "lm_head": p["lm_head"]}

    def into(layer: int, *path):
        src, dst = p[f"layer_{layer}"], cut.setdefault(f"layer_{layer}", {})
        for name in path[:-1]:
            src, dst = src[name], dst.setdefault(name, {})
        dst[path[-1]] = src[path[-1]]

    for kind, norms in ((sala.SPARSE, ("q_norm",)),
                        (sala.LIGHTNING, ("q_norm", "o_norm"))):
        layers = _layers(scfg, kind)
        if not layers:
            continue
        for name in ("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"):
            into(layers[0], "attn", name, "kernel")
        for name in norms:
            into(layers[0], "attn", name, "scale")
    into(0, "mlp", "gate_up", "kernel")
    into(scfg.num_layers - 1, "mlp", "gate_up", "kernel")
    into(scfg.num_layers - 1, "mlp", "down", "kernel")
    return {"params": cut}


def above_every_output_norm(path: str, scfg) -> bool:
    """Whether the leaf at ``path`` gets its gradient from above the last
    lightning layer's mixer: the head and the last block's SwiGLU, where that
    block's mixer is a lightning one (else every leaf).  **What check (d)
    holds.**  A lightning layer's output at a sequence's first positions is a
    sum of one, two, three terms (``z v_0`` at the first, ``z = q_0 . k_0 /
    sqrt(D)``), and now and then as good as nothing: its rms read 0.004 to
    0.14 a head at positions 0 and 1 on three seeds of ten where a late
    position reads 1 to 3 (my chip runs, PR 58: ``_scratch/look58.py``,
    PERF.md section 6).  The output norm divides by that rms; its backward
    divides again and projects the cotangent's part along the output away,
    which in bfloat16 leaves the rounding of the kernel's output and of the
    cotangent, 2^-9 of a term up to 250 times a late position's.  A causal
    mixer's backward hands a position's cotangent to that position and to
    earlier ones only, so the noise stays on the first positions: ``d loss /
    d h0`` reads 0.12 to 0.98 from the reference at positions 0 and 1 on
    those seeds and 0.0108 to 0.0122 at 999 positions of 1,000 on every
    seed.  But a weight's gradient sums over the positions, and those one or
    two rows then read 6 to 98 % on the first moments of every leaf below
    that norm (the sparse layer's gate, o and v first, whose own gradients
    nearly cancel at initialisation), while loss and logits stand.  No limit
    parts that tail from a fault, so (d) compares the leaves the noise cannot
    reach, (g) holds the backward below them a position at a time, (f) the
    two kernels' own gradients on their own operands, and the other leaves'
    readings ride on the head's check for the record."""
    from horovod_tpu.models import sala

    lightning = _layers(scfg, sala.LIGHTNING)
    if not lightning:
        return True
    last = lightning[-1]
    return "['lm_head']" in path or f"['layer_{last}']['mlp']" in path or any(
        f"['layer_{i}']" in path for i in range(last + 1, scfg.num_layers))


def _unpacked(bits, blocks: int):
    """The packed bits of some sparse layers, [..., G, words, S] -> bool
    [..., G, S, blocks]."""
    from horovod_tpu.ops import flash_select

    return flash_select.unpack_bits(bits, blocks)


def _system_forward(cell: dict, variables, ids, positions):
    """The system's forward on ``ids`` under the cell's precision and
    kernels: the logits at ``positions`` of the first sequence, the selecting
    layers' bits, and what the first sparse and the first lightning layer's
    attention took and made (q, k after their norms and the rotary turn, v,
    the kernels' ``ctx``)."""
    import jax

    from horovod_tpu.models import sala

    model, scfg = cell["model"], cell["scfg"]
    layers = _selecting(cell)

    def forward(v, ids):
        x, seen = model.apply(v, ids, method="hidden",
                              mutable=["intermediates"])
        seen = seen["intermediates"]
        logits = model.apply(v, x[0, positions], method="head")
        kept = {kind: seen[f"layer_{_layers(scfg, kind)[0]}"]["attn"][
            "attention"][0] for kind in (sala.SPARSE, sala.LIGHTNING)
            if _layers(scfg, kind)}
        return logits, _chosen(seen, layers), kept

    return jax.jit(forward)(variables, ids)


def _by_layer(chosen, layers: list, blocks: int, sequence: int) -> dict:
    """{layer: bool [G, S, blocks]} of one sequence from the stacked bits."""
    return {layer: _unpacked(chosen[n, sequence], blocks)
            for n, layer in enumerate(layers)}


def _blocks(cell: dict) -> int:
    return cell["batches"][0][0].shape[1] // cell["scfg"].sparse_block_size


def reference(cell: dict) -> dict:
    """Before the step, what ``probe`` compares: on the sample (the first
    sequence of the first batch) the plain float32 reference's forward on the
    choices the system's forward makes there (its logits at the sample's
    positions; the first sparse layer's q and k and block scores; the
    reference's own choices), kept in ``cell["sample"]``; the walk's counters
    of the first batch in ``cell["walk"]``.  What is compared with the step
    itself waits in ``checks`` for the step's own choices
    (:func:`reference_of_the_step`); the step donates its state, so the
    variables it starts from wait on the host, in ``cell["initial"]``."""
    import jax
    import numpy as np

    scfg = cell["scfg"]
    variables = common.first_shard(cell["params"])
    ids = jax.device_put(cell["batches"][0][0], cell["mesh"].devices.flat[0])
    rcfg = reference_config(scfg)
    positions = sample_positions(ids.shape[1])
    layers, blocks = _selecting(cell), _blocks(cell)
    _, chosen, _ = _system_forward(cell, variables, ids, positions)
    cell["walk"] = walk_counters(cell, chosen)

    def on_those_choices(v, ids, chosen):
        p = published(v)
        x, seen = reference_sala.hidden(
            p, ids, rcfg, _by_layer(chosen, layers, blocks, 0))
        out = {"logits": reference_sala.head(p, x[positions])}
        if layers:
            first = seen[layers[0]]
            out.update(
                reference_chose={i: seen[i]["chosen"] for i in layers},
                # The first selecting layer's float32 q and k: what the
                # program's selection is run on.
                scores=first["scores"], qk=(first["q"], first["k"]))
        return out

    cell["initial"] = jax.device_get(variables)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(on_those_choices)(variables, ids[0], chosen)
        cell["sample"] = {
            "ids": ids[:1], "positions": positions,
            "logits": np.asarray(out["logits"])}
        if layers:
            cell["sample"].update(
                qk=out["qk"], scores=out["scores"],
                system_chose={i: np.asarray(c) for i, c in _by_layer(
                    chosen, layers, blocks, 0).items()},
                reference_chose={i: np.asarray(c) for i, c in
                                 out["reference_chose"].items()})
    return {}


def walk_counters(cell: dict, chosen) -> dict:
    """The selected walk's counters over the first batch, all selecting
    layers together (``ops/flash_select.py:walk_counters``), in (query, key
    block) pairs; nothing where no layer selects."""
    import jax

    from horovod_tpu.ops import flash_select

    layers = _selecting(cell)
    if not layers:
        return {}
    seq = cell["batches"][0][0].shape[1]
    block = cell["scfg"].sparse_block_size
    tile, step = (min(flash_select.TILE_Q, seq), min(flash_select.STEP_K, seq))

    def count(chosen):
        return flash_select.walk_counters(
            flash_select.Selection(chosen.reshape(-1, *chosen.shape[2:]),
                                   block), seq, tile, step)

    return {k: float(v) for k, v in jax.jit(count)(chosen).items()}


def choices_differing(system: dict, reference_: dict, scfg) -> float:
    """The share of the system's (query, block) choices, the forced blocks
    (the first ones and the local ones, alike on both sides) left aside, that
    the reference's own choice of the same query does not hold."""
    import numpy as np

    differing = free = 0
    for layer, chose in system.items():
        seq, blocks = chose.shape[-2:]
        own = (np.arange(seq) // scfg.sparse_block_size)[:, None]
        blk = np.arange(blocks)[None, :]
        forced = (blk < scfg.sparse_init_blocks) | (
            (blk > own - scfg.local_blocks) & (blk <= own))
        mine = np.asarray(chose) & ~forced
        free += int(mine.sum())
        differing += int((mine & ~np.asarray(reference_[layer])).sum())
    return differing / max(free, 1)


def choice_rule_breaks(system: dict, reference_: dict, forced) -> float:
    """The share of queries, all selecting layers and key/value heads, whose
    choice holds another number of blocks than the reference's own choice of
    that query, or leaves out a block of ``forced`` (bool [S, blocks]: what
    the reference always chooses)."""
    import numpy as np

    forced = np.asarray(forced)
    broken = total = 0
    for layer, chose in system.items():
        chose, want = np.asarray(chose), np.asarray(reference_[layer])
        bad = (chose.sum(-1) != want.sum(-1)) | (forced & ~chose).any(-1)
        broken += int(bad.sum())
        total += bad.size
    return broken / max(total, 1)


def errors_by_row(got, want):
    """``||a - b|| / ||b||`` of each row of two [rows, d] arrays, the rows
    where ``want`` is nothing left out (the sequence's last position predicts
    nothing and reaches no later one).  **A row of the embedding's gradient
    is ``scale_emb x d loss / d h0`` summed over the positions that hold its
    token**, one position as a rule (16,384 uniform draws of 18,362 rows):
    the step's whole backward, through every mixer, a position at a time."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    size = np.linalg.norm(want, axis=-1)
    return (np.linalg.norm(got - want, axis=-1)[size > 0]) / size[size > 0]


def nine_in_ten(errors) -> float:
    """What nine of ten of the rows' errors lie within: the measure of check
    (g).  A fault of a backward reaches every position after the first chunk
    or block; the rounding that :func:`above_every_output_norm` describes
    stays on the sequence's first positions (a causal mixer's backward hands
    a position's cotangent to that position and to earlier ones only), and
    reaches a sampled row only where its token stands there too, so it
    cannot move this."""
    import numpy as np

    return float(np.quantile(errors, 0.9))


def reference_of_the_step(cell: dict, chosen) -> dict:
    """The plain float32 reference on the first global batch, a sequence at a
    time, from the variables the step started from (``cell["initial"]``) and
    **on the choices the step's own sparse layers made** (``chosen``, the
    step's third result): its loss, its gradient of the named leaves and the
    first moment one plain optax update of them leaves behind, beside those
    leaves as they were (``families/laguna.py:reference_of_the_step`` says why
    the step's own choices); and its gradient of the embedding's rows of the
    sample's tokens (``tokens``, ``embedding_rows``: what
    :func:`errors_by_row` compares)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    scfg, device = cell["scfg"], cell["mesh"].devices.flat[0]
    variables = jax.device_put(cell.pop("initial"), device)
    params = {"params": variables["params"]}
    ids = jax.device_put(cell["batches"][0][0], device)
    sequences, length = ids.shape
    rcfg = reference_config(scfg)
    layers, blocks = _selecting(cell), _blocks(cell)
    chosen = jnp.asarray(jax.device_get(chosen))
    tokens = ids[0, sample_positions(length)]

    def part(p, ids, chosen):
        tree = published(p)
        x, _ = reference_sala.hidden(tree, ids, rcfg, {
            layer: _unpacked(chosen[n], blocks)
            for n, layer in enumerate(layers)})
        return reference_sala.loss_sum(tree, x, ids) / (
            sequences * (length - 1))

    def part_and_leaf_grads(p, ids, chosen):
        loss, grads = jax.value_and_grad(part)(p, ids, chosen)
        return loss, (_checked_tree(grads, scfg),
                      grads["params"]["embed"]["embedding"][tokens])

    fn = jax.jit(part_and_leaf_grads)
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(sequences):
            part_loss, part_grads = fn(
                params, ids[i], chosen[:, i] if layers else chosen)
            loss += float(part_loss)
            grads = part_grads if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, part_grads)
    grads, rows = grads
    leaves = _checked_tree(params, scfg)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    _, opt_state = tx.update(grads, tx.init(leaves), leaves)
    return {"loss": loss, "tokens": np.asarray(tokens),
            "embedding_rows": np.asarray(rows), "leaves": {
        k: {"first_moment": np.asarray(common.first_moments(opt_state, k)[0]),
            "before": np.array(v)}
        for k, v in common.leaf_paths(leaves).items()}}


def kernel_errors(cell: dict, kept: dict, chosen) -> dict:
    """What :func:`_system_forward` kept of the first sparse and the first
    lightning layer against the plain reference's forms of the layers' own
    operands (float32 of what the kernels took): the masked dense softmax on
    the system's own choice, the quadratic form with the layer's slopes.  L2
    over the first sequence's output (``sparse``, ``lightning``) and over the
    three gradients dq, dk, dv of one drawn cotangent, which the system's
    kernels take again on those operands (``sparse_grads``,
    ``lightning_grads``), reduced on the device."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import sala

    scfg = cell["scfg"]
    rcfg = reference_config(scfg)
    layers, blocks = _selecting(cell), _blocks(cell)

    def l2(got, want):
        got, want = (jnp.concatenate([x.astype(jnp.float32).ravel()
                                      for x in xs]) for xs in (got, want))
        return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)

    def by_head(kept, width):
        return tuple(kept[n].reshape(*kept[n].shape[:2], -1, width)
                     for n in ("q", "k", "v"))

    def cotangent(q):
        """One drawn cotangent of a mixer's output, [S, heads, width]."""
        return jax.random.normal(jax.random.key(7), q.shape[1:], jnp.float32)

    def mixers(chosen):
        """Per kind: the width of a head, the system's mix of its kept
        operands [1, S, heads, width] and the reference's of [S, heads,
        width] in float32."""
        out = {}
        if _layers(scfg, sala.SPARSE):
            first = _layers(scfg, sala.SPARSE)[0]
            bits = (chosen[layers.index(first), :1] if first in layers
                    else None)
            taken = None if bits is None else _unpacked(bits[0], blocks)
            out[sala.SPARSE] = (
                "sparse", scfg.head_dim,
                lambda q, k, v: sala.sparse_mix(scfg, q, k, v, bits),
                lambda q, k, v: reference_sala.attention(
                    q, k, v, taken, scfg.sparse_block_size))
        if _layers(scfg, sala.LIGHTNING):
            first = _layers(scfg, sala.LIGHTNING)[0]
            out[sala.LIGHTNING] = (
                "lightning", scfg.lightning_head_dim,
                lambda q, k, v: sala.lightning_mix(
                    scfg, q, k, v, sala.lightning_slopes(scfg, first)),
                lambda q, k, v: reference_sala.lightning(
                    q, k, v, reference_sala.slopes(rcfg, first, q.shape[1])))
        return out

    def system_grads(kept, chosen):
        """dq, dk, dv of the cotangent through the system's own mixes (the
        kernels on a TPU): under the program's own matmul precision."""
        out = {}
        for kind, (_, width, system, _) in mixers(chosen).items():
            q, k, v = by_head(kept[kind], width)
            w = cotangent(q)[None].astype(q.dtype)
            out[kind] = jax.vjp(system, q, k, v)[1](w)
        return out

    def errors(kept, chosen, grads):
        out = {}
        for kind, (name, width, _, plain) in mixers(chosen).items():
            q, k, v = by_head(kept[kind], width)
            want, pull = jax.vjp(plain, *(x[0].astype(jnp.float32)
                                          for x in (q, k, v)))
            ctx = reference_sala.by_head(
                kept[kind]["ctx"][0].astype(jnp.float32), width)
            out[name] = l2([ctx], [want])
            out[f"{name}_grads"] = l2([g[0] for g in grads[kind]],
                                      pull(cotangent(q)))
        return out

    grads = jax.jit(system_grads)(kept, chosen)
    with jax.default_matmul_precision("highest"):
        return {k: float(v) for k, v in jax.jit(errors)(
            kept, chosen, grads).items()}


def probe(cell: dict, step, state) -> list:
    """(b) The system's forward on the sample before the first step moves
    the weights: its logits against the reference's on its own choices; (c)
    the program's selection on the reference's float32 q and k of the first
    sparse layer: its block scores against the reference's, and the share of
    the system's free choices the reference does not make (the walk's
    counters of the first batch ride on it); (f) the first sparse layer's
    walk and the first lightning layer's recurrence as the step runs them
    against the reference's forms of their own operands."""
    import jax
    import numpy as np

    from horovod_tpu.ops import flash_select

    sample, scfg = cell.pop("sample"), cell["scfg"]
    variables = common.first_shard(state[0])
    logits, chosen, kept = _system_forward(cell, variables, sample["ids"],
                                           sample["positions"])
    out = [
        common.check("sample_logits_vs_reference", common.l2_rel_err(
            logits, sample["logits"]), TOL_SAMPLE_LOGITS),
        {"name": "logits_are_float32",
         "ok": bool(logits.dtype == np.float32)}]
    if "qk" in sample:
        q, k = sample["qk"]
        with jax.default_matmul_precision("highest"):
            _, scores = jax.jit(lambda q, k: flash_select.sparse_select(
                q[None], k[None], scale=scfg.head_dim ** -0.5,
                with_scores=True, **scfg.selection))(q, k)
        out += [
            common.check(
                "block_scores_of_the_reference_s_q_and_k_vs_reference",
                common.rel_err(np.asarray(scores[0]),
                               np.asarray(sample["scores"])),
                TOL_BLOCK_SCORES),
            {**common.check("choices_differing_from_the_reference",
                            choices_differing(sample["system_chose"],
                                              sample["reference_chose"],
                                              scfg), TOL_CHOICES_DIFFERING),
             "walk": cell["walk"]},
            common.check(
                "choices_breaking_the_reference_s_rule", choice_rule_breaks(
                    sample["system_chose"], sample["reference_chose"],
                    reference_sala.forced(q.shape[0],
                                          reference_config(scfg))),
                TOL_CHOICE_RULE)]
    errors = kernel_errors(cell, kept, chosen)
    for kind, tol, grads_tol in (
            ("sparse", TOL_FIRST_SPARSE_ATTENTION, TOL_FIRST_SPARSE_GRADS),
            ("lightning", TOL_FIRST_LIGHTNING, TOL_FIRST_LIGHTNING_GRADS)):
        if kind in errors:
            out += [
                common.check(f"first_{kind}_attention_of_its_own_operands_vs_"
                             "reference", errors[kind], tol),
                common.check(f"first_{kind}_attention_s_gradients_of_its_own_"
                             "operands_vs_reference", errors[f"{kind}_grads"],
                             grads_tol)]
    return out


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell.  The
    state is ``(variables, optimizer state, chosen)``; a step hands out what
    its sparse layers chose, as packed bits [sparse layers, sequences, G,
    words, S], and ``checks`` reads the reference on the first step's."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import flash_select

    model, mesh, scfg = cell["model"], cell["mesh"], cell["scfg"]
    layers = _selecting(cell)
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(variables, opt_state, chosen, ids):
        del chosen          # the step before's: this one writes its own
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: _loss_and_choices(model, layers, p, ids),
            has_aux=True)(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state, chosen,
                hvd.allreduce(loss, axis_name="hvd"))

    drawn = cell["batches"][0]
    sequences, seq = drawn[0].shape
    by_sequence = P(None, "hvd") if layers else P()
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), by_sequence, *(P("hvd") for _ in drawn)),
        out_specs=(P(), P(), by_sequence, P())), donate_argnums=(0, 1, 2))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    words = -(-(seq // scfg.sparse_block_size) // flash_select.WORD)
    chosen = jax.device_put(
        jnp.zeros((len(layers), sequences, scfg.kv_heads_held, words, seq)
                  if layers else (0,), jnp.int32),
        NamedSharding(mesh, by_sequence))
    state = (cell["params"], opt_state, chosen)
    compiled = step.lower(*state, *drawn).compile()
    cell["kernel_calls"] = kernel_calls(compiled.as_text())
    note_attention(cell, compiled)
    return compiled, state


EMBEDDING = "['params']['embed']['embedding']"
KERNELS = ("hvd_lightning_fwd", "hvd_lightning_dq", "hvd_lightning_dkv",
           "hvd_flash_sel_fwd", "hvd_flash_sel_dq", "hvd_flash_sel_dkv")


def kernel_calls(hlo: str) -> dict:
    """Calls of each named Pallas kernel in a compiled step's text
    (``families/jamba.py:kernel_calls``'s rule)."""
    import re

    return {k: len(re.findall(
        rf"{k}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
        for k in KERNELS}


def least_calls(cell: dict) -> dict:
    """The fewest calls of each kernel a sound step holds: one a layer of
    its kind (a selecting layer's for the selected walk)."""
    from horovod_tpu.models import sala

    lightning = len(_layers(cell["scfg"], sala.LIGHTNING))
    return {k: lightning if "lightning" in k else len(_selecting(cell))
            for k in KERNELS}


def note_attention(cell: dict, compiled) -> None:
    """The ``"note": "attention"`` line: the layers' kinds and what this chip
    holds of each, each kernel's calls in the step beside their least, the
    walk's counters of the first batch and the compiler's memory reading."""
    import json

    scfg = cell["scfg"]
    memory = compiled.memory_analysis()
    print(json.dumps({
        "note": "attention",
        "layers": [{"mixer": scfg.mixer_types[i]}
                   for i in range(scfg.num_layers)],
        "lightning_heads_held": scfg.lightning_held,
        "sparse_heads_held": [scfg.heads_held, scfg.kv_heads_held],
        "kernel_calls": cell["kernel_calls"],
        "least_calls": least_calls(cell), "walk": cell.get("walk", {}),
        "memory": None if memory is None else {
            "arguments": memory.argument_size_in_bytes,
            "temporaries": memory.temp_size_in_bytes,
            "outputs": memory.output_size_in_bytes,
            "aliased": memory.alias_size_in_bytes}}), flush=True)


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    scfg = cell["scfg"]
    del ref             # reference() keeps what probe compares in the cell
    variables, opt_state, chosen = state
    ref = reference_of_the_step(cell, chosen)
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if jax.default_backend() == "tpu" and scfg.use_flash:
        # The Pallas kernels, not their fallbacks, are in the step.
        for name, count in least_calls(cell).items():
            out.append(common.at_least(f"calls_of_{name}",
                                       cell["kernel_calls"][name], count))
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    # (g) The backward a position at a time: the first moment of the
    # embedding's rows of the sample's tokens is (1 - b1) x scale_emb x the
    # step's own d loss / d h0 there.
    moments = common.first_moments(opt_state, EMBEDDING)
    assert len(moments) == 1, len(moments)
    errors = errors_by_row(
        jax.device_get(moments[0][ref["tokens"]])
        / (1.0 - cell["cfg"]["optimizer"]["args"]["b1"]),
        ref["embedding_rows"])
    out.append({**common.check("hidden_gradient_by_position_vs_reference",
                               nine_in_ten(errors), TOL_HIDDEN_GRADIENT),
                "median": float(np.median(errors)),
                "largest": float(errors.max())})
    leaves = common.leaf_paths({"params": variables["params"]})
    below = {}          # (d)'s readings under an output norm: for the record
    for k, want in ref["leaves"].items():
        moments = common.first_moments(opt_state, k)
        assert len(moments) == 1, (k, len(moments))
        mu = jax.device_get(_cut(k, moments[0]))
        error = moment_error(k, mu, want["first_moment"])
        if above_every_output_norm(k, scfg):
            out.append(common.check(f"first_moment{k}", error,
                                    TOL_FIRST_MOMENT))
        else:
            below[k] = error
        nu = jax.device_get(_cut(k, bert._second_moment(opt_state, k)))
        after = np.asarray(jax.device_get(_cut(k, leaves[k])), np.float64)
        out.append(common.check(f"first_update{k}", common.l2_rel_err(
            after - want["before"], bert.adamw_first_update(
                want["before"], mu, nu,
                **cell["cfg"]["optimizer"]["args"])),
            TOL_FIRST_UPDATE_UNIT if k.endswith("['scale']")
            else TOL_FIRST_UPDATE))
    inexact = [x.dtype for x in jax.tree_util.tree_leaves(state)
               if jnp.issubdtype(x.dtype, jnp.inexact)]
    out.append({"name": "parameters_and_moments_are_float32",
                "ok": bool(inexact) and all(
                    d == jnp.float32 for d in inexact),
                "first_moments_below_an_output_norm": below})
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch, as
    the algorithm needs them (``sala_flops.forward_macs``): the selected
    attention over the chosen, causally visible pairs, the lightning layers
    over their four products a chunk; recomputation is not counted."""
    cfg = _sizes(cell["cfg"], cell["rehearse"])
    return sala_flops.model_flops(cfg, cell["traffic"], cell["mesh"].size)


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
