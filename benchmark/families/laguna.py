"""Family ``laguna``: ``horovod_tpu.models.laguna.Laguna`` (Laguna-S-2.1's
decoder: sliding-window attention layers among global ones with different
head counts and rotary rules, a gate a head, a leading dense SwiGLU layer and
then a top-10 mixture of 256 SwiGLU experts beside a shared one, an untied
head) trained on the next token, one chip's share of a layer spread over 32:
``num_experts_held`` of the experts, ``num_key_value_heads_held`` key/value
heads with ``num_attention_heads_per_layer_held`` query heads on them,
``feed_forward_columns_held`` dense columns, ``vocab_size_held`` rows of the
embedding and the head.  On one chip the layers run with ``axis_name=None``:
what the other chips would add to each sum is left out, in the program and in
the reference alike.

The step has the shape of ``families/jamba.py``'s: a jitted ``shard_map`` over
the ``hvd`` axis, the optimizer wrapped in ``hvd.DistributedOptimizer``, the
loss averaged over the axis; its state is ``(variables, optimizer state,
chosen)``, the last what the step's routers chose (``build``).
The weights are one draw, named in the configuration (``assumed.
weights_seed``): which of the held experts a router favours is drawn with
them, the rows routed here follow it, and a step's time follows the rows;
``--seed`` draws the traffic.

The reference is ``benchmark/references/laguna.py``: plain float32
``jax.numpy``, one device, "highest" matmul precision, one sequence at a
time.  **Top-k is discrete**, so, as ``families/sdar.py`` does, the router is
compared on its own (the program's ``parallel/moe.py:route`` on the
reference's float32 input of the first sparse block against the reference's
probabilities; the share of the system's choices that differ from the
reference's) and everything downstream is compared with the reference run on
the choices of the very program it is compared with: the sample's logits
with the reference on the choices of the forward that made them
(``_system_forward``), the step's loss, moments and update with the reference
on the choices the step itself hands out (``reference_of_the_step``).  **The
band is compared on its own** too (``probe``): the first sliding layer of
that same forward of the cell's model (its bfloat16 q, k and v after the
rotary turn, the banded kernels' output on a TPU, as the layer sows them)
against the reference's attention of those very operands, where a band one
key short or long reads 1 / sqrt(window) and bfloat16 activations do not
drown it.
"""

from __future__ import annotations

from benchmark import common, laguna_flops
from benchmark.families import bert
# The sample's positions (spread evenly, so that the late ones attend over a
# long context), the rows of the embedding that are compared and the measure
# of a leaf with an expert axis are ZAYA's and SDAR's.
from benchmark.families.sdar import choices_differing, moment_error
from benchmark.families.zaya import (  # noqa: F401
    EMBEDDING_ROWS, SAMPLE_POSITIONS, _cut, sample_positions)
from benchmark.references import laguna as reference_laguna

# How a limit is set: the rule of benchmark/testdata/check_rule.json, held on
# the readings in benchmark/testdata/check_readings/laguna.json.  Readings:
# TPU v5 lite, the cell laguna-swa-ep32-s16384, PR 51: the file's sound runs,
# a seed each, of the tree as it is (the weights the configuration's one
# draw, the reference on the step's own choices).  The faults are ISSUE 51's
# list and one more, made in the plain reference and read against the plain
# reference itself on the fault's own choices, in each check's own measure at
# the cell's own size (tests/benchmark/laguna_faults.py, seeds 1 to 3,
# gradients included).  The head is untied and lecun-normal, so what it reads
# is the blocks' outputs and a fault of a block reads on the logits.
#
# What tells what apart.  A sound step's logits lie 2.3 % and its first
# moments 2.4 to 3.7 % from the float32 reference's, routed leaves and the
# router among them, once the reference runs on the step's own choices (on a
# forward-only program's the routed experts' read 11 to 14 % and the router's
# 8 to 11 %: PERF.md section 6, PR 51): bfloat16 operands through five blocks
# each way.  A window one key short reads 0.7 % on (b) and 4 % on (d), among
# the sound readings: (f) holds the band on the forward's own operands, where
# it reads 3.1 %.  The router in bfloat16 reads 0.06 % on (b): (c) holds its
# float32 on the reference's input.
#
# (a) First loss of the compiled step against the reference's on the step's
# choices.  Sound: 1e-6 to 3.6e-5.  Fault: the final norm left out 0.0247 to
# 0.0256.  Under an untied head the loss at initialisation is log 12,544 and
# a little whatever the blocks compute and whichever token a row is asked for
# (labels not shifted is one more draw of the same mean over 16,383 rows; not
# read on the chip): what reads here is the logits' scale.  Kept where
# SDAR's, ZAYA's and Jamba's stand: 55 x over the sound, 12 x under the fault.
TOL_FIRST_LOSS = 2e-3
# (b) Logits of the sample (SAMPLE_POSITIONS positions spread over the first
# sequence, all 12,544 held rows), L2 error.  Sound: 0.0227 to 0.0233.
# Faults: the 2.5 left out 0.154 to 0.171, a sliding layer run global 0.160 to
# 0.162, the final norm left out 0.228 to 0.232, the gate left out 0.96 to
# 0.98, plain rotary on a full layer 1.10 to 1.12, the shared expert left out
# 1.29.  Middle: 2.6 x from either.
TOL_SAMPLE_LOGITS = 0.06
# (c) The router alone: the program's route() on the reference's float32
# input of the first sparse block against the reference's probabilities, max
# |a - b| / max |b| over 16384 x 256.  Sound: 0.0 on every seed.  Fault: the
# router's product in bfloat16 2.5e-3 to 4.1e-3.  Kept where SDAR's and
# ZAYA's stand: 25 x under the fault.
TOL_ROUTER_PROBS = 1e-4
# (c) The share of the system's (token, expert) choices, all sparse layers,
# that the reference's own top-10 of the same token does not hold: near-ties
# that bfloat16 activations flip.  Sound: 0.0180 to 0.0186.  Faults (their
# own choices against the sound reference's): a sliding layer run global
# 0.104 to 0.106, the gate left out 0.60, the shared expert left out 0.67,
# plain rotary on a full layer 0.70 (the 2.5 left out reads 0.050 and is
# (b)'s and (d)'s).  Middle: 2.4 x from either.
TOL_CHOICES_DIFFERING = 0.044
# (d) The first moment after one step is (1 - b1) x the gradient: a full and
# a sliding layer's query and gate kernels, the sliding layer's key kernel,
# the dense layer's pair, the first sparse block's router (the median over
# its columns) and shared pair, the last block's shared down kernel and the
# held experts' down kernels (the median over the experts,
# ``families/sdar.py:moment_error``), rows of the embedding, the head; L2
# error over the leaf.  Sound: 0.0238 to 0.0367 over every leaf, the routed
# one 0.0358 to 0.0365.  Faults, the largest of the leaves: the 2.5 left out
# 0.68 to 0.69 (0.61 on the routed leaf), a sliding layer run global 0.90,
# plain rotary on a full layer 1.35 to 1.37, the shared expert left out 1.53
# to 1.63, the gate left out 1.83.  Placed near the sound side, 2.5 x over
# the largest sound reading and 7.6 x under the nearest fault, so that an
# error of a tenth in one leaf's gradient, which no fault on the list makes,
# reads over it (sqrt(0.037^2 + 0.1^2) = 0.107).
TOL_FIRST_MOMENT = 0.09
# (e) What the first step did to the same leaves against plain AdamW of the
# moments the step itself left behind (``bert.adamw_first_update``, float64):
# the L2 error of the change.  Sound: 2.6e-3 to 4.1e-3.  Fault: the
# parameters kept in bfloat16 lose the update whole, 1.0.  Kept where SDAR's
# and ZAYA's stand: 24 x over the sound, 10 x under the fault.
TOL_FIRST_UPDATE = 0.1
# (f) The band alone: the first sliding layer's attention in the system's
# forward (its own bfloat16 q, k and v after the rotary turn, the banded
# kernels' output) against the reference's attention of those operands, L2
# over 16384 x 1152.  Sound: 1.97e-3 to 2.00e-3.  Faults: a window one key
# short 0.0310 to 0.0326 (a row's output is the mean of 512 value rows; one
# fewer moves it by 1 / sqrt(512) of itself), the layer run global 0.75 to
# 0.76.  Middle: 3.9 x from either.
TOL_SLIDING_ATTENTION = 0.0079


def _sizes(cfg: dict, rehearse: bool) -> dict:
    return {**cfg["assumed"], **cfg, **(cfg["rehearse"] if rehearse else {})}


def _rope(entry: dict, **over):
    import dataclasses

    from horovod_tpu.models import laguna

    fields = {f.name for f in dataclasses.fields(laguna.RopeParameters)}
    return laguna.RopeParameters(**{k: v for k, v in {**entry, **over}.items()
                                    if k in fields})


def _laguna_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu.models import laguna

    c = _sizes(cfg, rehearse)
    ropes = c["rope_parameters"]
    # A rehearsal's sequence is shorter than the published original context:
    # its own puts YaRN's blend inside the tiny head.
    original = ({"original_max_position_embeddings":
                 c["original_max_position_embeddings"]}
                if "original_max_position_embeddings" in c else {})
    return laguna.LagunaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        layer_types=tuple(c["layer_types"]),
        num_heads_per_layer=tuple(c["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(c["mlp_layer_types"]),
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        sliding_window=c["sliding_window"],
        rope_full=_rope(ropes[laguna.FULL], **original),
        rope_sliding=_rope(ropes[laguna.SLIDING]),
        intermediate_size=c["intermediate_size"],
        num_experts=c["num_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        shared_expert_intermediate_size=c["shared_expert_intermediate_size"],
        norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["moe_routed_scaling_factor"],
        rms_norm_eps=c["rms_norm_eps"],
        vocab_size_held=c["vocab_size_held"],
        num_kv_heads_held=c["num_key_value_heads_held"],
        num_heads_per_layer_held=tuple(
            c["num_attention_heads_per_layer_held"]),
        dense_columns_held=c["feed_forward_columns_held"],
        num_experts_held=c["num_experts_held"],
        first_expert=c["first_expert"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"])


def reference_config(lcfg) -> dict:
    """What ``references/laguna.py`` reads of a configuration."""
    import dataclasses

    from horovod_tpu.models import laguna

    return {"rms_norm_eps": lcfg.rms_norm_eps, "head_dim": lcfg.head_dim,
            "layer_types": lcfg.layer_types,
            "sliding_window": lcfg.sliding_window,
            "rope_parameters": {
                laguna.FULL: dataclasses.asdict(lcfg.rope_full),
                laguna.SLIDING: dataclasses.asdict(lcfg.rope_sliding)},
            "num_experts_per_tok": lcfg.num_experts_per_tok,
            "norm_topk_prob": lcfg.norm_topk_prob,
            "moe_routed_scaling_factor": lcfg.routed_scaling_factor,
            "first_expert": lcfg.first_expert}


def weights_seed(cfg: dict) -> int:
    """The integer the weights' key is made from: the configuration's
    ``assumed.weights_seed``, which says in ``weights_seed_why`` why the
    weights of this family are one draw and which draw (as
    ``families/sdar.py:weights_seed``)."""
    seed = cfg["assumed"].get("weights_seed")
    if not isinstance(seed, int) or isinstance(seed, bool):
        raise KeyError(
            f"configuration {cfg.get('name')!r} names no whole number as "
            "assumed.weights_seed: family laguna makes its weights from the "
            "configuration's key, not from the run's seed (which draws the "
            "traffic), and will not make one up")
    return seed


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and weights (replicated), made on the device in one jitted call
    from the configuration's key.  ``seed``, the run's, is not read here: it
    draws the traffic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import laguna

    lcfg = _laguna_config(cfg, rehearse)
    model = laguna.Laguna(lcfg)
    # The key is an argument, not a constant of the program (families/gpt.py).
    key = jax.random.fold_in(jax.random.key(weights_seed(cfg)), 0)
    params = jax.jit(
        lambda k: model.init(k, jnp.zeros((1, 16), jnp.int32)),
        out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "lcfg": lcfg,
            "rehearse": rehearse, "params": params}


def inputs(cell: dict, traffic: dict) -> list:
    """The one drawn argument of the step, per sequence: token ids of the
    held slice."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    return [Input((traffic["seq_len"],), jnp.int32, "randint",
                  cell["lcfg"].rows_held)]


def _chosen(intermediates: dict, sparse: list):
    """[sparse layers, tokens, k]: what each sparse layer's router chose, as
    ``models/laguna.py:LagunaMoE`` sows it."""
    import jax.numpy as jnp

    return jnp.stack([intermediates[f"layer_{i}"]["moe"]["chosen_experts"][0]
                      for i in sparse])


def _loss_and_choices(model, sparse: list, variables, ids):
    """The next-token loss on ``ids`` [B, S] and what the routers chose on
    the way to it."""
    loss, seen = model.apply(variables, ids, method="loss",
                             mutable=["intermediates"])
    return loss, _chosen(seen["intermediates"], sparse)


def _layers_of_kind(lcfg) -> dict:
    """The first full layer, the first sliding one, the first sparse one and
    the last layer, by index."""
    from horovod_tpu.models import laguna

    return {"full": lcfg.layer_types.index(laguna.FULL),
            "sliding": lcfg.layer_types.index(laguna.SLIDING),
            "sparse": lcfg.mlp_layer_types.index(laguna.SPARSE),
            "dense": lcfg.mlp_layer_types.index(laguna.DENSE),
            "last": lcfg.num_layers - 1}


def _sparse_layers(lcfg) -> list:
    from horovod_tpu.models import laguna

    return [i for i, kind in enumerate(lcfg.mlp_layer_types)
            if kind == laguna.SPARSE]


def _row_buffer(cell: dict) -> int:
    """The rows of the expert layer's buffer at the cell's batch
    (``parallel/moe.py:row_buffer``)."""
    import numpy as np

    from horovod_tpu.models import laguna
    from horovod_tpu.parallel import moe

    lcfg = cell["lcfg"]
    tokens = int(np.prod(cell["batches"][0][0].shape)) // cell["mesh"].size
    return moe.row_buffer(tokens, lcfg.num_experts_per_tok, lcfg.experts_held,
                          lcfg.num_experts, laguna.EXPERT_CAPACITY_FACTOR)


def _checked_tree(tree, lcfg) -> dict:
    """The leaves checks (d) and (e) compare, as a sub-tree with the whole
    tree's paths: a leaf of every kind the family brings."""
    p = tree["params"]
    at = _layers_of_kind(lcfg)
    cut = {"embed": {"embedding": p["embed"]["embedding"][:EMBEDDING_ROWS]},
           "lm_head": p["lm_head"]}

    def into(layer: int, *path):
        """``p[layer_<layer>]<path>`` into the cut, its path kept."""
        src, dst = p[f"layer_{layer}"], cut.setdefault(f"layer_{layer}", {})
        for name in path[:-1]:
            src, dst = src[name], dst.setdefault(name, {})
        dst[path[-1]] = src[path[-1]]

    for kind in ("full", "sliding"):
        into(at[kind], "attn", "q_proj", "kernel")
        into(at[kind], "attn", "gate_proj")
    into(at["sliding"], "attn", "k_proj", "kernel")
    into(at["dense"], "mlp", "gate_up", "kernel")
    into(at["sparse"], "moe", "router")
    into(at["sparse"], "moe", "shared_gate_up", "kernel")
    into(at["last"], "moe", "w_down")
    into(at["last"], "moe", "shared_down", "kernel")
    return {"params": cut}


def _system_forward(cell: dict, variables, ids, positions):
    """The system's forward on ``ids`` under the cell's precision and
    kernels: the logits at ``positions`` of the first sequence, per sparse
    layer what its router chose and the rows it sent to each held expert,
    and what the first sliding layer's attention took and made (its q, k and
    v after the rotary turn, the banded kernels' ``ctx``)."""
    import jax
    import jax.numpy as jnp

    model, lcfg = cell["model"], cell["lcfg"]
    sparse, sliding = _sparse_layers(lcfg), _layers_of_kind(lcfg)["sliding"]

    def forward(v, ids):
        x, seen = model.apply(v, ids, method="hidden",
                              mutable=["intermediates"])
        seen = seen["intermediates"]
        logits = model.apply(v, x[0, positions], method="head")
        return (logits, _chosen(seen, sparse),
                jnp.stack([seen[f"layer_{i}"]["moe"]["expert_load"][0]
                           for i in sparse]),
                seen[f"layer_{sliding}"]["attn"]["attention"][0])

    return jax.jit(forward)(variables, ids)


def _by_layer(chosen, lcfg):
    """The sparse layers' choices [sparse, ...] as one row a layer [layers,
    ...], a dense layer's row zeros that nothing reads."""
    import jax.numpy as jnp

    from horovod_tpu.models import laguna

    rows, zeros = iter(chosen), jnp.zeros_like(chosen[0])
    return jnp.stack([next(rows) if kind == laguna.SPARSE else zeros
                      for kind in lcfg.mlp_layer_types])


def reference(cell: dict) -> dict:
    """Before the step, what ``probe`` compares: on the sample (the first
    sequence of the first batch) the plain float32 reference's forward on the
    choices the system's forward makes there (its logits at the sample's
    positions, what the first sparse block's router saw and made of it) and
    the reference's own choices, kept in ``cell["sample"]``; the rows the
    batch sends to each held expert in ``cell["expert_load"]``.  What is
    compared with the step itself (loss, first moments, first update) is the
    reference on the step's own choices and waits in ``checks`` for them
    (:func:`reference_of_the_step`); the step donates its state, so the
    weights it starts from wait on the host, in ``cell["initial"]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lcfg = cell["lcfg"]
    variables = common.first_shard(cell["params"])
    ids = jax.device_put(cell["batches"][0][0], cell["mesh"].devices.flat[0])
    rcfg = reference_config(lcfg)
    positions = sample_positions(ids.shape[1])
    first_sparse = _layers_of_kind(lcfg)["sparse"]
    _, chosen, load, _ = _system_forward(cell, variables, ids, positions)
    cell["expert_load"] = np.asarray(load).tolist()
    # [sparse layers, sequences * S, k] -> the first sequence's, by layer
    chosen = _by_layer(chosen, lcfg)[:, :ids.shape[1]]

    def on_those_choices(p, ids, chosen):
        x, seen = reference_laguna.hidden(p, ids, rcfg, chosen)
        return (reference_laguna.head(p, x[positions]),
                seen[first_sparse]["routed"], seen[first_sparse]["probs"])

    def own_choices(p, ids):
        _, seen = reference_laguna.hidden(p, ids, rcfg)
        return jnp.stack([s["chosen"] for s in seen if s is not None])

    params = variables["params"]
    cell["initial"] = jax.device_get(params)
    with jax.default_matmul_precision("highest"):
        logits, routed, probs = jax.jit(on_those_choices)(params, ids[0],
                                                          chosen)
        cell["sample"] = {
            "ids": ids[:1], "positions": positions,
            "logits": np.asarray(logits), "routed": routed,
            "probs": np.asarray(probs), "system_chose": np.asarray(chosen),
            "reference_chose": np.asarray(_by_layer(
                jax.jit(own_choices)(params, ids[0]), lcfg))}
    return {}


def reference_of_the_step(cell: dict, chosen) -> dict:
    """The plain float32 reference on the first global batch, a sequence at
    a time, from the weights the step started from (``cell["initial"]``, the
    host's copy) and **on the choices the step's own routers made**
    (``chosen`` [sparse layers, tokens, k], the step's third result): its
    loss, its gradient of the named leaves and the first moment one plain
    optax update of them leaves behind, beside those leaves as they were.
    Top-k is discrete and two compiled programs of the same bfloat16
    arithmetic cut near-ties differently (1.1 % of the choices between a
    forward-only program and one that makes the gradients too, which moves a
    routed expert's gradient by 12 %: PERF.md section 6, PR 51), so the
    reference takes its choices from the very program whose gradients it is
    compared with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lcfg, device = cell["lcfg"], cell["mesh"].devices.flat[0]
    params = {"params": jax.device_put(cell.pop("initial"), device)}
    ids = jax.device_put(cell["batches"][0][0], device)
    sequences, length = ids.shape
    rcfg = reference_config(lcfg)
    chosen = _by_layer(jnp.asarray(jax.device_get(chosen)), lcfg).reshape(
        lcfg.num_layers, sequences, length, -1)

    def part(p, ids, chosen):
        x, _ = reference_laguna.hidden(p["params"], ids, rcfg, chosen)
        return reference_laguna.loss_sum(p["params"], x, ids) / (
            sequences * (length - 1))

    def part_and_leaf_grads(p, ids, chosen):
        loss, grads = jax.value_and_grad(part)(p, ids, chosen)
        return loss, _checked_tree(grads, lcfg)

    fn = jax.jit(part_and_leaf_grads)
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(sequences):
            part_loss, part_grads = fn(params, ids[i], chosen[:, i])
            loss += float(part_loss)
            grads = part_grads if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, part_grads)
    leaves = _checked_tree(params, lcfg)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    _, opt_state = tx.update(grads, tx.init(leaves), leaves)
    return {"loss": loss, "leaves": {
        k: {"first_moment": np.asarray(common.first_moments(opt_state, k)[0]),
            "before": np.array(v)}
        for k, v in common.leaf_paths(leaves).items()}}


def sliding_attention_error(cell: dict, kept: dict) -> float:
    """What :func:`_system_forward` kept of the first sliding layer against
    the plain reference's attention of the layer's own q, k and v (float32 of
    what the kernels took), L2 over the first sequence's output, reduced on
    the device."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import laguna

    lcfg = cell["lcfg"]
    window = reference_laguna.window_of(laguna.SLIDING,
                                        reference_config(lcfg))

    def error(kept):
        q, k, v, ctx = (kept[name][0].astype(jnp.float32).reshape(
            kept[name].shape[1], -1, lcfg.head_dim)
            for name in ("q", "k", "v", "ctx"))
        want = reference_laguna.attention(q, k, v, window)
        return jnp.linalg.norm((ctx - want).ravel()) / jnp.linalg.norm(
            want.ravel())

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(error)(kept))


def probe(cell: dict, step, state) -> list:
    """(b) The system's forward on the sample before the first step moves
    the weights: its logits against the reference's; (c) the program's router
    on the reference's float32 input of the first sparse block against the
    reference's probabilities, and the share of the system's choices the
    reference does not make; (f) the first sliding layer's attention as the
    step runs it against the reference's attention of its own operands.  The
    rows each held expert got on the first batch ride on (c) as
    ``expert_load``."""
    import jax
    import numpy as np

    from horovod_tpu.parallel import moe

    sample, lcfg = cell.pop("sample"), cell["lcfg"]
    variables = common.first_shard(state[0])
    logits, _, _, band = _system_forward(cell, variables, sample["ids"],
                                         sample["positions"])
    at = _layers_of_kind(lcfg)
    router = variables["params"][f"layer_{at['sparse']}"]["moe"]["router"]
    probs = jax.jit(lambda r, x: moe.route(
        x, r, lcfg.num_experts_per_tok, lcfg.first_expert,
        lcfg.experts_held, lcfg.norm_topk_prob).probs)(
            router, sample["routed"])
    load, sparse = np.asarray(cell["expert_load"]), _sparse_layers(lcfg)
    return [
        common.check("sample_logits_vs_reference", common.l2_rel_err(
            logits, sample["logits"]), TOL_SAMPLE_LOGITS),
        {"name": "logits_are_float32",
         "ok": bool(logits.dtype == np.float32)},
        common.check("router_probs_of_the_reference_s_input_vs_reference",
                     common.rel_err(np.asarray(probs), sample["probs"]),
                     TOL_ROUTER_PROBS),
        {**common.check("choices_differing_from_the_reference",
                        choices_differing(sample["system_chose"][sparse],
                                          sample["reference_chose"][sparse]),
                        TOL_CHOICES_DIFFERING),
         "expert_load": {"row_buffer": _row_buffer(cell),
                         "rows_by_layer": load.sum(axis=1).tolist(),
                         "largest_by_layer": load.max(axis=1).tolist(),
                         "mean_by_layer": load.mean(axis=1).tolist()}},
        common.check(
            "first_sliding_attention_of_its_own_operands_vs_reference",
            sliding_attention_error(cell, band), TOL_SLIDING_ATTENTION)]


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell.  The
    state is ``(variables, optimizer state, chosen)``: a step hands out what
    its routers chose, [sparse layers, tokens, k] (what a job logs its
    experts' load from; 2.6 MB a step in the cell), and ``checks`` reads the
    reference on the first step's."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    model, mesh, lcfg = cell["model"], cell["mesh"], cell["lcfg"]
    sparse = _sparse_layers(lcfg)
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(variables, opt_state, chosen, ids):
        del chosen          # the step before's: this one writes its own
        (loss, chosen), grads = jax.value_and_grad(
            lambda v: _loss_and_choices(model, sparse, v, ids),
            has_aux=True)(variables)
        updates, opt_state = tx.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state, chosen,
                hvd.allreduce(loss, axis_name="hvd"))

    drawn = cell["batches"][0]
    by_token = P(None, "hvd")       # [sparse layers, this chip's tokens, k]
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), by_token, *(P("hvd") for _ in drawn)),
        out_specs=(P(), P(), by_token, P())), donate_argnums=(0, 1, 2))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    chosen = jax.device_put(
        jnp.zeros((len(sparse), drawn[0].size, lcfg.num_experts_per_tok),
                  jnp.int32), NamedSharding(mesh, by_token))
    state = (cell["params"], opt_state, chosen)
    compiled = step.lower(*state, *drawn).compile()
    cell["kernel_calls"] = kernel_calls(compiled.as_text())
    note_attention(cell)
    note_expert_load(cell)
    return compiled, state


KERNELS = ("hvd_flash_swa_fwd", "hvd_flash_swa_dq", "hvd_flash_swa_dkv",
           "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv")


def kernel_calls(hlo: str) -> dict:
    """Calls of each named Pallas kernel in a compiled step's text
    (``families/jamba.py:kernel_calls``'s rule)."""
    import re

    return {k: len(re.findall(
        rf"{k}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
        for k in KERNELS}


def least_calls(lcfg) -> dict:
    """The fewest calls of each kernel a sound step holds: the three banded
    kernels a sliding layer, the three un-banded ones a full layer."""
    from horovod_tpu.models import laguna

    sliding = lcfg.layer_types.count(laguna.SLIDING)
    full = lcfg.layer_types.count(laguna.FULL)
    return {k: sliding if "_swa_" in k else full for k in KERNELS}


def note_attention(cell: dict) -> None:
    """The ``"note": "attention"`` line: per layer its kind, the heads this
    chip holds and its window; each kernel's calls in the step beside their
    least."""
    import json

    lcfg = cell["lcfg"]
    print(json.dumps({
        "note": "attention",
        "layers": [{"kind": lcfg.layer_types[i],
                    "query_heads_held": lcfg.heads_held(i),
                    "key_value_heads_held": lcfg.kv_heads_held,
                    "window": lcfg.window(i),
                    "feed_forward": lcfg.mlp_layer_types[i]}
                   for i in range(lcfg.num_layers)],
        "kernel_calls": cell["kernel_calls"],
        "least_calls": least_calls(lcfg)}), flush=True)


def note_expert_load(cell: dict) -> None:
    """The ``"note": "expert_load"`` line: the rows the cell's batch sends
    to each held expert, by sparse layer, beside the row buffer."""
    import json

    import numpy as np

    load = np.asarray(cell.get("expert_load", []))
    print(json.dumps({
        "note": "expert_load", "row_buffer": _row_buffer(cell),
        "rows_by_layer": load.sum(axis=-1).tolist(),
        "rows_by_held_expert": load.tolist()}), flush=True)


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    lcfg = cell["lcfg"]
    del ref             # reference() keeps what probe compares in the cell
    variables, opt_state, chosen = state
    ref = reference_of_the_step(cell, chosen)
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if jax.default_backend() == "tpu" and lcfg.use_flash:
        # The Pallas kernels, not the dense fallback, are in the step, the
        # banded ones on the sliding layers.
        for name, count in least_calls(lcfg).items():
            out.append(common.at_least(f"calls_of_{name}",
                                       cell["kernel_calls"][name], count))
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    leaves = common.leaf_paths({"params": variables["params"]})
    for k, want in ref["leaves"].items():
        moments = common.first_moments(opt_state, k)
        assert len(moments) == 1, (k, len(moments))
        mu = jax.device_get(_cut(k, moments[0]))
        out.append(common.check(f"first_moment{k}", moment_error(
            k, mu, want["first_moment"]), TOL_FIRST_MOMENT))
        nu = jax.device_get(_cut(k, bert._second_moment(opt_state, k)))
        after = np.asarray(jax.device_get(_cut(k, leaves[k])), np.float64)
        out.append(common.check(f"first_update{k}", common.l2_rel_err(
            after - want["before"], bert.adamw_first_update(
                want["before"], mu, nu,
                **cell["cfg"]["optimizer"]["args"])), TOL_FIRST_UPDATE))
    inexact = [x.dtype for x in jax.tree_util.tree_leaves(state)
               if jnp.issubdtype(x.dtype, jnp.inexact)]
    out.append({"name": "parameters_and_moments_are_float32",
                "ok": bool(inexact) and all(
                    d == jnp.float32 for d in inexact)})
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch, as
    the algorithm needs them (``laguna_flops.forward_macs``): attention over
    the band's pairs on a sliding layer and the causal pairs on a full one,
    the experts over the rows an even router sends to the held ones;
    recomputation is not counted."""
    cfg = _sizes(cell["cfg"], cell["rehearse"])
    return laguna_flops.model_flops(cfg, cell["traffic"], cell["mesh"].size)


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
