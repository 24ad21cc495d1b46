"""Family ``joyai``: ``horovod_tpu.models.joyai.JoyAI`` (JoyAI-LLM-Flash's
decoder: multi-head latent attention, a leading dense SwiGLU layer and then a
top-8 mixture of 256 SwiGLU experts chosen by bias-corrected sigmoid scores
beside a shared one, an untied head) trained on the next token, one chip's
share of a layer spread over 16: ``num_experts_held`` of the experts,
``num_attention_heads_held`` heads, ``feed_forward_columns_held`` dense
columns, ``vocab_size_held`` rows of the embedding and the head.  On one chip
the layers run with ``axis_name=None``: what the other chips would add to
each sum is left out, in the program and in the reference alike.

The step has the shape of ``families/laguna.py``'s: a jitted ``shard_map``
over the ``hvd`` axis, the optimizer wrapped in ``hvd.DistributedOptimizer``,
the loss averaged over the axis; its state is ``(variables, optimizer state,
chosen)``, the last what the step's routers chose (``build``).  The variables
are the model's ``params`` and its ``balancing`` collection (a bias a layer in
the routers' choice, which nothing updates); the optimizer sees the
``params`` alone.  The weights are one draw, named in the configuration
(``assumed.weights_seed``); ``--seed`` draws the traffic.  The biases are set
once, when the reference is made and before the step is built, on the cell's
own first batch (``balance``, as ``families/zaya.py``): the model's balancing
rule run to its resting point on the sigmoid scores there, so that they are
not zero and a fault in where they enter reads.

The reference is ``benchmark/references/joyai.py``: plain float32
``jax.numpy``, one device, "highest" matmul precision, one sequence at a
time, **parameters in the published layout** (:func:`published` maps the
program's stored column orders to it, and a gradient taken through the map
comes back in the stored one).  **Top-k is discrete**, so the router is
compared on its own (the program's ``JoyAIRouter`` on the reference's float32
input of the first expert block: its scores against the reference's, and its
weights of its own choices against the reference's weights of those choices;
the share of the system's choices that differ from the reference's) and
everything downstream is compared with the reference run on the choices of
the very program it is compared with: the sample's logits with the reference
on the choices of the forward that made them, the step's loss, moments and
update with the reference on the choices the step itself hands out
(``families/laguna.py:reference_of_the_step``'s rule).  **The kernels are
compared on their own** too: the first layer's attention in that forward (its
bfloat16 operands after the rotary turn, the paired kernels' output on a TPU)
against the reference's 192-wide attention of those very operands; and so are
**the two latents** of the first layer, which at initialisation have a root
mean square of one before their norms, so that a norm left out reads on
nothing downstream.
"""

from __future__ import annotations

from benchmark import common, joyai_flops
from benchmark.families import bert
from benchmark.families.laguna import weights_seed  # noqa: F401
from benchmark.families.sdar import choices_differing
from benchmark.families.sdar import moment_error as _leaf_error
from benchmark.families.zaya import (  # noqa: F401
    EMBEDDING_ROWS, SAMPLE_POSITIONS, _cut, sample_positions)
from benchmark.references import joyai as reference_joyai

# How a limit is set: the rule of benchmark/testdata/check_rule.json, held on
# the readings in benchmark/testdata/check_readings/joyai.json.  Readings: TPU
# v5 lite, the cell joyai-mla-ep16-s16384, PR 54: the file's sound runs, a
# seed each, of the tree as it is (the weights the configuration's one draw,
# the biases set on the seed's own batch, the reference on the step's own
# choices).  The faults are ISSUE 54's ten, made in the plain reference and
# read against the plain reference itself on the fault's own choices, in each
# check's own measure at the cell's own size (tests/benchmark/joyai_faults.py,
# seeds 1 to 3, gradients included).  Every fault reads the rule's margin
# over one limit at least; the file says which.
#
# What tells what apart.  A sound step's logits lie 2.1 % and its first
# moments 2.2 to 3.1 % from the float32 reference's: bfloat16 operands through
# five blocks each way.  The bias in the weights as well reads 0.8 % on (b)
# and 2.8 % on (d), among the sound readings, and the router in bfloat16
# 0.008 %: (c) holds scores and weights in float32 on the reference's input.
# The key/value latent's norm left out reads 8.7 % on (b), too near the
# limit's far side to be listed there (at initialisation the latent's root
# mean square is one before its norm): (g) holds the latents themselves.
#
# (a) First loss of the compiled step against the reference's on the step's
# choices.  Sound: 1.9e-6 to 3.0e-5.  Under an untied head the loss at
# initialisation is log 16,160 and a little whatever the blocks compute, so
# the faults read small here, and three of them still ten times a sound run
# on every seed: the shared expert left out 3.1e-4 (to 2.0e-3), the turn on
# the nope lanes 3.4e-4, the turn left off k_rope 3.9e-4 (to 1.0e-3).  The
# others read 1.4e-5 to 7.8e-4 from seed to seed and are not listed here.
# Middle: 3.3 x over the largest sound reading, 3.1 x under the nearest
# fault.
TOL_FIRST_LOSS = 1e-4
# (b) Logits of the sample (SAMPLE_POSITIONS positions spread over the first
# sequence, all 16,160 held rows), L2 error.  Sound: 0.0210 to 0.0216.
# Faults: softmax for sigmoid 0.177, the 2.5 left out 0.230, the scale of the
# nope width 0.41, the turn left off k_rope 0.63, the renormalisation left
# out 0.92, the turn on the nope lanes 1.07, the shared expert left out
# 1.30.  Middle: 2.8 x over the one, 3.0 x under the other.
TOL_SAMPLE_LOGITS = 0.06
# (c) The router alone, on the reference's float32 input of the first expert
# block: the program's scores against the reference's, max |a - b| / max |b|
# over 16384 x 256.  Sound: 0.0 on every seed.  Faults: the router's product
# in bfloat16 2.9e-3, softmax for sigmoid 0.92.  Kept where SDAR's, ZAYA's
# and Laguna's stand: 29 x under the nearest fault.
TOL_ROUTER_SCORES = 1e-4
# (c) Its weights of its own choices (top-8 of scores + bias) against the
# reference's weights of those choices (the scores without the bias, divided
# by their sum), max |a - b| / max |b| over 16384 x 8.  Sound: 0.0 on every
# seed.  Faults: the router in bfloat16 1.1e-3, the bias in the weights as
# well 0.082, softmax for sigmoid 1.6, the renormalisation left out 6.0.
# Kept beside the scores': 11 x under the nearest fault.
TOL_ROUTER_WEIGHTS = 1e-4
# (c) The share of the system's (token, expert) choices, all expert layers,
# that the reference's own top-8 of the same token does not hold: near-ties
# that bfloat16 activations flip.  Sound: 0.0214 to 0.0231.  Faults (their
# own choices against the sound reference's): the key/value latent's norm
# left out 0.076, the 2.5 left out 0.095, the scale 0.29, the
# renormalisation 0.35, the turns 0.42 and 0.72, the shared expert 0.68,
# softmax 0.78.  Middle: 1.8 x from either.
TOL_CHOICES_DIFFERING = 0.042
# (d) The first moment after one step is (1 - b1) x the gradient: the first
# layer's W_qa, both parts of W_qb, W_kva, W_kvb, W_o, both latent norms'
# scales and the dense pair; the first expert block's router (the median over
# its columns) and shared pair; the last block's shared down kernel and held
# experts' down kernels (the median over the experts); rows of the embedding,
# the head; L2 error over the leaf.  Sound: 0.0215 to 0.0309 over every leaf.
# Faults, the largest of the leaves: the key/value latent's norm left out
# 0.153, the 2.5 left out 0.69, the scale 0.74, the renormalisation 1.04,
# the turns 1.19 and 1.26, the shared expert 1.35, softmax 12.9.  Kept where
# Laguna's stands, 2.9 x over the largest sound reading and 1.7 x under the
# nearest fault, so that an error of a tenth in one leaf's gradient reads
# over it (sqrt(0.031^2 + 0.1^2) = 0.105).
TOL_FIRST_MOMENT = 0.09
# (e) What the first step did to the same leaves against plain AdamW of the
# moments the step itself left behind (``bert.adamw_first_update``, float64):
# the L2 error of the change.  Sound: 2.2e-3 to 5.5e-3.  Fault: the
# parameters kept in bfloat16 lose the update whole, 1.0.  Kept where the
# other families' stand.
TOL_FIRST_UPDATE = 0.1
# The two latent norms' scales start at one: the rate 2e-7 is two to four
# float32 ulps of such an entry and the update itself is rounded.  Sound:
# 0.214 to 0.222 on every seed (a leaf of ones reads the same 0.21 to 0.325
# in ZAYA's cell).  Fault: as above, 1.0.  Kept where ``families/zaya.py``'s
# stands.
TOL_FIRST_UPDATE_UNIT = 0.57
# (f) The kernels alone: the first layer's attention in the system's forward
# (its own bfloat16 q_nope, q_rope, k_nope, k_rope and v after the rotary
# turn, the paired kernels' output) against the reference's attention of those
# operands (a head's literal [nope | rope] concatenation, one 192-wide dot,
# 192^-1/2), L2 over the first sequence's output.  Sound: 1.87e-3 on every
# seed.  Fault: the scale of the nope width 0.303.  Middle: 12.7 x from
# either.
TOL_FIRST_ATTENTION = 0.024
# (g) The first layer's two latents after their norms, as the system's forward
# makes them, against the reference's of the same ids, L2 over 16384 x 1536
# and 16384 x 512.  Sound: 3.72e-3 to 3.73e-3 (bfloat16 activations).  Fault:
# the key/value latent's norm left out 0.0307.  Middle: 2.9 x from either.
TOL_FIRST_LATENTS = 0.0107


def _sizes(cfg: dict, rehearse: bool) -> dict:
    return {**cfg["assumed"], **cfg, **(cfg["rehearse"] if rehearse else {})}


def _joyai_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu.models import joyai

    c = _sizes(cfg, rehearse)
    return joyai.JoyAIConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]),
        intermediate_size=c["intermediate_size"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=c["n_routed_experts"],
        num_experts_per_tok=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        n_shared_experts=c["n_shared_experts"],
        norm_topk_prob=c["norm_topk_prob"],
        routed_scaling_factor=c["routed_scaling_factor"],
        rms_norm_eps=c["rms_norm_eps"],
        vocab_size_held=c["vocab_size_held"],
        num_heads_held=c["num_attention_heads_held"],
        dense_columns_held=c["feed_forward_columns_held"],
        num_experts_held=c["num_experts_held"],
        first_expert=c["first_expert"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"])


def reference_config(jcfg) -> dict:
    """What ``references/joyai.py`` reads of a configuration."""
    return {"rms_norm_eps": jcfg.rms_norm_eps,
            "kv_lora_rank": jcfg.kv_lora_rank,
            "qk_nope_head_dim": jcfg.qk_nope_head_dim,
            "qk_rope_head_dim": jcfg.qk_rope_head_dim,
            "v_head_dim": jcfg.v_head_dim, "rope_theta": jcfg.rope_theta,
            "num_experts_per_tok": jcfg.num_experts_per_tok,
            "norm_topk_prob": jcfg.norm_topk_prob,
            "routed_scaling_factor": jcfg.routed_scaling_factor,
            "first_expert": jcfg.first_expert}


def published(variables, jcfg) -> dict:
    """The program's variables (``params`` and ``balancing``) as the tree of
    plain arrays ``references/joyai.py`` reads, **in the published column
    orders**: a head's ``[nope | rope]`` of ``W_qb`` from the two stored
    kernels, a head's ``[k_nope | v]`` of ``W_kvb`` from the paired kernel's
    halves, and the rotary lanes of ``W_qb`` and ``W_kva`` back from even
    lanes then odd to the interleaved pairs (``models/joyai.py:
    stored_rope_order``).  Only selections and concatenations: a gradient
    taken through it lies in the stored orders."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import joyai

    nope, rope, wide = (jcfg.qk_nope_head_dim, jcfg.qk_rope_head_dim,
                        jcfg.v_head_dim)
    back = np.argsort(joyai.stored_rope_order(rope))
    p, biases = variables["params"], variables["balancing"]

    def attn(a):
        heads = a["q_b_nope"]["kernel"].shape[1] // nope
        rank_q, rank = a["q_b_nope"]["kernel"].shape[0], jcfg.kv_lora_rank
        q_b = jnp.concatenate([
            a["q_b_nope"]["kernel"].reshape(rank_q, heads, nope),
            a["q_b_rope"]["kernel"].reshape(rank_q, heads, rope)[..., back]],
            axis=-1).reshape(rank_q, -1)
        kv_a = a["kv_a"]["kernel"]
        kv_a = jnp.concatenate([kv_a[:, :rank], kv_a[:, rank:][:, back]], 1)
        kv_b = a["kv_b"]["kernel"]
        kv_b = jnp.concatenate([
            kv_b[:, :heads * nope].reshape(rank, heads, nope),
            kv_b[:, heads * nope:].reshape(rank, heads, wide)],
            axis=-1).reshape(rank, -1)
        return {"q_a": a["q_a"]["kernel"], "q_a_norm": a["q_a_norm"]["scale"],
                "q_b": q_b, "kv_a": kv_a,
                "kv_a_norm": a["kv_a_norm"]["scale"], "kv_b": kv_b,
                "o_proj": a["o_proj"]["kernel"]}

    def block(name, b):
        out = {"attn": attn(b["attn"]), "input_norm": b["input_norm"]["scale"],
               "post_attn_norm": b["post_attn_norm"]["scale"]}
        if "mlp" in b:
            out["mlp"] = {"gate_up": b["mlp"]["gate_up"]["kernel"],
                          "down": b["mlp"]["down"]["kernel"]}
        else:
            m = b["moe"]
            out["moe"] = {
                "router": m["router"]["kernel"],
                "bias": biases[name]["moe"]["router"]["bias"],
                "w_gate": m["w_gate"], "w_up": m["w_up"],
                "w_down": m["w_down"],
                "shared_gate_up": m["shared_gate_up"]["kernel"],
                "shared_down": m["shared_down"]["kernel"]}
        return out

    return {"embed": p["embed"]["embedding"],
            "final_norm": p["final_norm"]["scale"], "lm_head": p["lm_head"],
            **{name: block(name, b) for name, b in p.items()
               if name.startswith("layer_")}}


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and weights (replicated), made on the device in one jitted call
    from the configuration's key.  ``seed``, the run's, is not read here: it
    draws the traffic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import joyai

    jcfg = _joyai_config(cfg, rehearse)
    model = joyai.JoyAI(jcfg)
    # The key is an argument, not a constant of the program (families/gpt.py).
    key = jax.random.fold_in(jax.random.key(weights_seed(cfg)), 0)
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


def _sparse_layers(jcfg) -> list:
    return [i for i in range(jcfg.num_layers) if jcfg.sparse(i)]


def _chosen(intermediates: dict, sparse: list):
    """[expert layers, tokens, k]: what each expert layer's router chose, as
    ``models/joyai.py:JoyAIMoE`` sows it."""
    import jax.numpy as jnp

    return jnp.stack([intermediates[f"layer_{i}"]["moe"]["chosen_experts"][0]
                      for i in sparse])


def _loss_and_choices(model, sparse: list, variables, ids):
    """The next-token loss on ``ids`` [B, S] and what the routers chose on
    the way to it."""
    loss, seen = model.apply(variables, ids, method="loss",
                             mutable=["intermediates"])
    return loss, _chosen(seen["intermediates"], sparse)


def _row_buffer(cell: dict) -> int:
    """The rows of the expert layer's buffer at the cell's batch
    (``parallel/moe.py:row_buffer``)."""
    import numpy as np

    from horovod_tpu.models import joyai
    from horovod_tpu.parallel import moe

    jcfg = cell["jcfg"]
    tokens = int(np.prod(cell["batches"][0][0].shape)) // cell["mesh"].size
    return moe.row_buffer(tokens, jcfg.num_experts_per_tok, jcfg.experts_held,
                          jcfg.num_experts, joyai.EXPERT_CAPACITY_FACTOR)


def _checked_tree(tree, jcfg) -> dict:
    """The leaves checks (d) and (e) compare, as a sub-tree with the whole
    tree's paths: a leaf of every kind the family brings."""
    p = tree["params"]
    sparse = _sparse_layers(jcfg)
    cut = {"embed": {"embedding": p["embed"]["embedding"][:EMBEDDING_ROWS]},
           "lm_head": p["lm_head"]}

    def into(layer: int, *path):
        """``p[layer_<layer>]<path>`` into the cut, its path kept."""
        src, dst = p[f"layer_{layer}"], cut.setdefault(f"layer_{layer}", {})
        for name in path[:-1]:
            src, dst = src[name], dst.setdefault(name, {})
        dst[path[-1]] = src[path[-1]]

    for name in ("q_a", "q_b_nope", "q_b_rope", "kv_a", "kv_b", "o_proj"):
        into(0, "attn", name, "kernel")
    for name in ("q_a_norm", "kv_a_norm"):
        into(0, "attn", name, "scale")
    if not jcfg.sparse(0):
        into(0, "mlp", "gate_up", "kernel")
    if sparse:
        into(sparse[0], "moe", "router", "kernel")
        into(sparse[0], "moe", "shared_gate_up", "kernel")
        into(sparse[-1], "moe", "w_down")
        into(sparse[-1], "moe", "shared_down", "kernel")
    return {"params": cut}


def moment_error(path: str, got, want) -> float:
    """``families/sdar.py:moment_error`` with this family's router leaf: the
    median over its columns, an expert each."""
    return _leaf_error("['router']" if path.endswith("['router']['kernel']")
                       else path, got, want)


def _system_forward(cell: dict, variables, ids, positions):
    """The system's forward on ``ids`` under the cell's precision and
    kernels: the logits at ``positions`` of the first sequence, per expert
    layer what its router chose and the rows it sent to each held expert,
    and what the first layer's attention took and made (its two latents, its
    operands after the rotary turn, the paired kernels' ``ctx``)."""
    import jax
    import jax.numpy as jnp

    model, sparse = cell["model"], _sparse_layers(cell["jcfg"])

    def forward(v, ids):
        x, seen = model.apply(v, ids, method="hidden",
                              mutable=["intermediates"])
        seen = seen["intermediates"]
        logits = model.apply(v, x[0, positions], method="head")
        return (logits, _chosen(seen, sparse),
                jnp.stack([seen[f"layer_{i}"]["moe"]["expert_load"][0]
                           for i in sparse]),
                seen["layer_0"]["attn"]["attention"][0])

    return jax.jit(forward)(variables, ids)


def _by_layer(chosen, jcfg):
    """The expert layers' choices [expert layers, ...] as one row a layer
    [layers, ...], a dense layer's row zeros that nothing reads."""
    import jax.numpy as jnp

    rows, zeros = iter(chosen), jnp.zeros_like(chosen[0])
    return jnp.stack([next(rows) if jcfg.sparse(i) else zeros
                      for i in range(jcfg.num_layers)])


def balance(cell: dict) -> None:
    """Set the routers' balancing biases on the first global batch, layer by
    layer in one forward (``JoyAI`` with its ``balancing`` collection
    mutable), and put them into the cell's variables."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    model, mesh = cell["model"], cell["mesh"]
    ids = jax.device_put(cell["batches"][0][0], mesh.devices.flat[0])
    _, settled = jax.jit(lambda v, ids: model.apply(
        v, ids, method="hidden", mutable=["balancing"]))(
            common.first_shard(cell["params"]), ids)
    cell["params"] = {**cell["params"], **jax.device_put(
        settled, NamedSharding(mesh, P()))}


def reference(cell: dict) -> dict:
    """Sets the balancing biases (``balance``); then, before the step, what
    ``probe`` compares: on the sample (the first sequence of the first batch)
    the plain float32 reference's forward on the choices the system's forward
    makes there (its logits at the sample's positions, what the first expert
    block's router saw and made of it, the first layer's two latents) and the
    reference's own choices, kept in ``cell["sample"]``; the rows the batch
    sends to each held expert in ``cell["expert_load"]``.  What is compared
    with the step itself (loss, first moments, first update) is the reference
    on the step's own choices and waits in ``checks`` for them
    (:func:`reference_of_the_step`); the step donates its state, so the
    variables it starts from wait on the host, in ``cell["initial"]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    balance(cell)
    jcfg = cell["jcfg"]
    variables = common.first_shard(cell["params"])
    ids = jax.device_put(cell["batches"][0][0], cell["mesh"].devices.flat[0])
    rcfg = reference_config(jcfg)
    positions = sample_positions(ids.shape[1])
    sparse = _sparse_layers(jcfg)
    _, chosen, load, _ = _system_forward(cell, variables, ids, positions)
    cell["expert_load"] = np.asarray(load).tolist()
    # [expert layers, sequences * S, k] -> the first sequence's, by layer
    chosen = _by_layer(chosen, jcfg)[:, :ids.shape[1]]

    def on_those_choices(v, ids, chosen):
        p = published(v, jcfg)
        x, seen = reference_joyai.hidden(p, ids, rcfg, chosen)
        first = p["layer_0"]["attn"]
        h = reference_joyai.rms_norm(p["embed"][ids],
                                     p["layer_0"]["input_norm"],
                                     rcfg["rms_norm_eps"])
        latents = (reference_joyai.rms_norm(h @ first["q_a"],
                                            first["q_a_norm"],
                                            rcfg["rms_norm_eps"]),
                   reference_joyai.kv_latent(
                       first, (h @ first["kv_a"])[:, :jcfg.kv_lora_rank],
                       rcfg))
        return (reference_joyai.head(p, x[positions]),
                seen[sparse[0]]["routed"], seen[sparse[0]]["scores"], latents)

    def own_choices(v, ids):
        _, seen = reference_joyai.hidden(published(v, jcfg), ids, rcfg)
        return jnp.stack([s["chosen"] for s in seen if s is not None])

    cell["initial"] = jax.device_get(variables)
    with jax.default_matmul_precision("highest"):
        logits, routed, scores, latents = jax.jit(on_those_choices)(
            variables, ids[0], chosen)
        cell["sample"] = {
            "ids": ids[:1], "positions": positions,
            "logits": np.asarray(logits), "routed": routed,
            "scores": scores, "latents": latents,
            "system_chose": np.asarray(chosen),
            "reference_chose": np.asarray(_by_layer(
                jax.jit(own_choices)(variables, ids[0]), jcfg))}
    return {}


def reference_of_the_step(cell: dict, chosen) -> dict:
    """The plain float32 reference on the first global batch, a sequence at
    a time, from the variables the step started from (``cell["initial"]``,
    the host's copy) and **on the choices the step's own routers made**
    (``chosen`` [expert layers, tokens, k], the step's third result): its
    loss, its gradient of the named leaves (taken through :func:`published`,
    so in the stored orders) and the first moment one plain optax update of
    them leaves behind, beside those leaves as they were
    (``families/laguna.py:reference_of_the_step`` says why the step's own
    choices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    jcfg, device = cell["jcfg"], cell["mesh"].devices.flat[0]
    variables = jax.device_put(cell.pop("initial"), device)
    rest = {k: v for k, v in variables.items() if k != "params"}
    params = {"params": variables["params"]}
    ids = jax.device_put(cell["batches"][0][0], device)
    sequences, length = ids.shape
    rcfg = reference_config(jcfg)
    chosen = _by_layer(jnp.asarray(jax.device_get(chosen)), jcfg).reshape(
        jcfg.num_layers, sequences, length, -1)

    def part(p, ids, chosen):
        tree = published({**rest, **p}, jcfg)
        x, _ = reference_joyai.hidden(tree, ids, rcfg, chosen)
        return reference_joyai.loss_sum(tree, x, ids) / (
            sequences * (length - 1))

    def part_and_leaf_grads(p, ids, chosen):
        loss, grads = jax.value_and_grad(part)(p, ids, chosen)
        return loss, _checked_tree(grads, jcfg)

    fn = jax.jit(part_and_leaf_grads)
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(sequences):
            part_loss, part_grads = fn(params, ids[i], chosen[:, i])
            loss += float(part_loss)
            grads = part_grads if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, part_grads)
    leaves = _checked_tree(params, jcfg)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    _, opt_state = tx.update(grads, tx.init(leaves), leaves)
    return {"loss": loss, "leaves": {
        k: {"first_moment": np.asarray(common.first_moments(opt_state, k)[0]),
            "before": np.array(v)}
        for k, v in common.leaf_paths(leaves).items()}}


def first_attention_error(cell: dict, kept: dict) -> float:
    """What :func:`_system_forward` kept of the first layer against the plain
    reference's attention of the layer's own operands (float32 of what the
    kernels took; a head's literal ``[nope | rope]`` concatenation, the one
    rotary key repeated a head), L2 over the first sequence's output, reduced
    on the device."""
    import jax
    import jax.numpy as jnp

    jcfg = cell["jcfg"]
    rcfg = reference_config(jcfg)

    def error(kept):
        seq = kept["ctx"].shape[1]
        by_head = {name: kept[name][0].astype(jnp.float32).reshape(
            seq, -1, width) for name, width in (
                ("q_nope", jcfg.qk_nope_head_dim),
                ("q_rope", jcfg.qk_rope_head_dim),
                ("k_nope", jcfg.qk_nope_head_dim),
                ("k_rope", jcfg.qk_rope_head_dim), ("v", jcfg.v_head_dim),
                ("ctx", jcfg.v_head_dim))}
        q = jnp.concatenate([by_head["q_nope"], by_head["q_rope"]], -1)
        k = jnp.concatenate([by_head["k_nope"], jnp.broadcast_to(
            by_head["k_rope"], by_head["q_rope"].shape)], -1)
        want = reference_joyai.attention(q, k, by_head["v"],
                                         reference_joyai.score_scale(rcfg))
        return jnp.linalg.norm((by_head["ctx"] - want).ravel()) \
            / jnp.linalg.norm(want.ravel())

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(error)(kept))


def probe(cell: dict, step, state) -> list:
    """(b) The system's forward on the sample before the first step moves
    the weights: its logits against the reference's; (c) the program's router
    on the reference's float32 input of the first expert block: its scores
    against the reference's, its weights of its own choices against the
    reference's weights of those, and the share of the system's choices the
    reference does not make; (f) the first layer's attention as the step runs
    it against the reference's attention of its own operands; (g) the first
    layer's two latents against the reference's.  The rows each held expert
    got on the first batch ride on (c) as ``expert_load``."""
    import jax
    import numpy as np

    from horovod_tpu.models import joyai

    sample, jcfg = cell.pop("sample"), cell["jcfg"]
    variables = common.first_shard(state[0])
    logits, _, _, first = _system_forward(cell, variables, sample["ids"],
                                          sample["positions"])
    sparse = _sparse_layers(jcfg)
    # The first expert block's router alone: its kernel and its bias.
    router = {c: variables[c][f"layer_{sparse[0]}"]["moe"]["router"]
              for c in ("params", "balancing")}
    scores, chose, weights = jax.jit(
        lambda v, x: joyai.JoyAIRouter(jcfg).apply(v, x))(
            router, sample["routed"])
    with jax.default_matmul_precision("highest"):
        want_weights = jax.jit(lambda s, c: reference_joyai.top_k_weights(
            s, c, jcfg.norm_topk_prob))(sample["scores"], chose)
    load = np.asarray(cell["expert_load"])
    return [
        common.check("sample_logits_vs_reference", common.l2_rel_err(
            logits, sample["logits"]), TOL_SAMPLE_LOGITS),
        {"name": "logits_are_float32",
         "ok": bool(logits.dtype == np.float32)},
        common.check("router_scores_of_the_reference_s_input_vs_reference",
                     common.rel_err(np.asarray(scores),
                                    np.asarray(sample["scores"])),
                     TOL_ROUTER_SCORES),
        common.check("router_weights_of_its_own_choices_vs_reference",
                     common.rel_err(np.asarray(weights),
                                    np.asarray(want_weights)),
                     TOL_ROUTER_WEIGHTS),
        {**common.check("choices_differing_from_the_reference",
                        choices_differing(sample["system_chose"][sparse],
                                          sample["reference_chose"][sparse]),
                        TOL_CHOICES_DIFFERING),
         "expert_load": {"row_buffer": _row_buffer(cell),
                         "rows_by_layer": load.sum(axis=1).tolist(),
                         "largest_by_layer": load.max(axis=1).tolist(),
                         "mean_by_layer": load.mean(axis=1).tolist()}},
        common.check("first_attention_of_its_own_operands_vs_reference",
                     first_attention_error(cell, first), TOL_FIRST_ATTENTION),
        *(common.check(f"first_latent_{name}_vs_reference", common.l2_rel_err(
            np.asarray(first[name][0], np.float32), np.asarray(want)),
            TOL_FIRST_LATENTS)
          for name, want in zip(("c_q", "c_kv"), sample["latents"]))]


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell.  The
    state is ``(variables, optimizer state, chosen)``: the variables are the
    ``params``, which the optimizer sees, and the ``balancing`` biases, which
    nothing updates; a step hands out what its routers chose, [expert layers,
    tokens, k] (what a job logs its experts' load from), and ``checks`` reads
    the reference on the first step's."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    model, mesh, jcfg = cell["model"], cell["mesh"], cell["jcfg"]
    sparse = _sparse_layers(jcfg)
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(variables, opt_state, chosen, ids):
        del chosen          # the step before's: this one writes its own
        rest = {k: v for k, v in variables.items() if k != "params"}
        params = {"params": variables["params"]}
        (loss, chosen), grads = jax.value_and_grad(
            lambda p: _loss_and_choices(model, sparse, {**rest, **p}, ids),
            has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return ({**rest, **optax.apply_updates(params, updates)}, opt_state,
                chosen, hvd.allreduce(loss, axis_name="hvd"))

    drawn = cell["batches"][0]
    by_token = P(None, "hvd")       # [expert layers, this chip's tokens, k]
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), by_token, *(P("hvd") for _ in drawn)),
        out_specs=(P(), P(), by_token, P())), donate_argnums=(0, 1, 2))
    opt_state = jax.jit(
        lambda v: tx.init({"params": v["params"]}),
        out_shardings=NamedSharding(mesh, P()))(cell["params"])
    chosen = jax.device_put(
        jnp.zeros((len(sparse), drawn[0].size, jcfg.num_experts_per_tok),
                  jnp.int32), NamedSharding(mesh, by_token))
    state = (cell["params"], opt_state, chosen)
    compiled = step.lower(*state, *drawn).compile()
    cell["kernel_calls"] = kernel_calls(compiled.as_text())
    note_attention(cell)
    note_expert_load(cell)
    return compiled, state


KERNELS = ("hvd_flash_mla_fwd", "hvd_flash_mla_dq", "hvd_flash_mla_dkv")


def kernel_calls(hlo: str) -> dict:
    """Calls of each named Pallas kernel in a compiled step's text
    (``families/jamba.py:kernel_calls``'s rule)."""
    import re

    return {k: len(re.findall(
        rf"{k}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo))
        for k in KERNELS}


def least_calls(jcfg) -> dict:
    """The fewest calls of each kernel a sound step holds: one a layer."""
    return dict.fromkeys(KERNELS, jcfg.num_layers)


def note_attention(cell: dict) -> None:
    """The ``"note": "attention"`` line: per layer the heads this chip holds
    and the two widths; each kernel's calls in the step beside their
    least."""
    import json

    jcfg = cell["jcfg"]
    print(json.dumps({
        "note": "attention",
        "layers": [{"heads_held": jcfg.heads_held,
                    "qk_width": jcfg.qk_head_dim,
                    "v_width": jcfg.v_head_dim,
                    "feed_forward": "sparse" if jcfg.sparse(i) else "dense"}
                   for i in range(jcfg.num_layers)],
        "kernel_calls": cell["kernel_calls"],
        "least_calls": least_calls(jcfg)}), flush=True)


def note_expert_load(cell: dict) -> None:
    """The ``"note": "expert_load"`` line: the rows the cell's batch sends
    to each held expert, by expert layer, beside the row buffer."""
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

    jcfg = cell["jcfg"]
    del ref             # reference() keeps what probe compares in the cell
    variables, opt_state, chosen = state
    ref = reference_of_the_step(cell, chosen)
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if jax.default_backend() == "tpu" and jcfg.use_flash:
        # The paired Pallas kernels, not the dense fallback, are in the step.
        for name, count in least_calls(jcfg).items():
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
                **cell["cfg"]["optimizer"]["args"])),
            TOL_FIRST_UPDATE_UNIT if k.endswith("['scale']")
            else TOL_FIRST_UPDATE))
    inexact = [x.dtype for x in jax.tree_util.tree_leaves(state)
               if jnp.issubdtype(x.dtype, jnp.inexact)]
    out.append({"name": "parameters_and_moments_are_float32",
                "ok": bool(inexact) and all(
                    d == jnp.float32 for d in inexact)})
    return out


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch, as
    the algorithm needs them (``joyai_flops.forward_macs``): attention over
    the causal pairs at 192 + 128 multiply-adds a pair and head, the experts
    over the rows an even router sends to the held ones; recomputation is not
    counted."""
    cfg = _sizes(cell["cfg"], cell["rehearse"])
    return joyai_flops.model_flops(cfg, cell["traffic"], cell["mesh"].size)


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
