"""Family ``bert``: ``horovod_tpu.models.BertForPreTraining`` pretrained as
published (Devlin et al., arXiv:1810.04805): masked LM over the gathered
positions with the tied decoder plus next-sentence prediction, post-LN
blocks, padded sequences with one key length each through the flash
kernels (``flash_attention(causal=False, kv_lens=...)``).

The step has the shape of ``families/gpt.py``'s: a jitted ``shard_map``
over the ``hvd`` axis, the optimizer wrapped in ``hvd.DistributedOptimizer``,
the loss averaged over the axis.  It takes five drawn arguments (token ids,
token types, position draws, labels, next-sentence labels: ``traffic.py``'s
``randint``s) and shapes them itself; the lengths come from the traffic
file and never from the seed, so that no seed changes the work:

``short_sequences``          ``{"<index in a chip's batch>": <real tokens>}``;
                             every other sequence is ``seq_len`` long
``max_predictions_per_seq``  masked positions a sequence (P)
``masked_lm_prob``           a sequence of L real tokens weighs its first
                             ``round(masked_lm_prob * L)`` (at most P)
                             positions and gives the rest weight 0

The reference is ``benchmark/references/bert.py``: plain float32
``jax.numpy``, one device, "highest" matmul precision, ``jax.grad`` for the
gradients.
"""

from __future__ import annotations

from benchmark import common, flops
from benchmark.references import bert as reference_bert

# How a limit is set.  One rule for every TOL_* of every family;
# tests/benchmark/test_check_limits.py holds it on the readings kept in
# benchmark/testdata/check_readings/<family>.json, one file a family:
#
#   A limit stands between the largest reading a sound tree has given on the
#   chip, over every seed on record, and the smallest reading of each fault
#   the check is there to catch, a factor 1.5 or more from either.  One that
#   has to move goes to their geometric middle ("Middle" below); one that
#   stands inside stays where its first readings put it ("Kept").
#
# A reading is a draw, (b) and (e) the largest of ten million rounding errors,
# and one refused run refuses a PR: (e) stood at 1e-6 over 17 seeds that read
# at most 6.3e-7, the 20th read 2.06e-6 and refused PR 29.  No limit is a
# small multiple of a small sample's largest.
#
# Readings: TPU v5 lite.  "PR 27": 34 runs over 33 seeds for (a) to (c), 17
# runs for (d) and (e).  "PR 30": seeds 0 to 31, 2147483693 and 3000000019,
# every check (PERF.md section 6; with PR 29's three seeds, each run is a line
# of check_readings/bert.json).  The faults: two lower precisions of the plain
# reference against the float32 one, "bf16 throughout" (float32 given up for
# the parameters, layer norms, softmax and logits: the nearest below what the
# configuration states) and "e4m3" (every parameter and every function's
# output rounded to float8_e4m3), and faults of structure made in the plain
# reference, read at the cell's own size over 11 seeds in PR 30
# (tests/benchmark/bert_faults.py) where a limit moved, else at BERT_TINY's
# sizes on the CPU in PR 27.  The cell's size reads them lower: after 24
# post-LN blocks at initialisation what differs between one position's hidden
# state and another's is 3.4e-4 of it (0.81 after the embeddings, x 0.7 a
# block), so a padded key adds to a softmax nearly what the real keys hold,
# and a gather one position off, which reads 1 at BERT_TINY's two layers,
# reads 1e-3 on (b) and (c): nothing at initialisation tells which position
# the head read.
#
# What tells what apart.  At initialisation the bfloat16 noise of 24 post-LN
# blocks' activations is all that (a) to (c) read: bf16 throughout reads there
# what the system reads (logits 2.1e-2, gradients 1.8e-2 to 4.3e-2), so no
# limit on them can hold the float32 parts.  (d) and (e) do, each where that
# noise does not reach, and bf16 throughout comes out as not correct by both,
# as does a step that keeps its parameters, its moments or its logits in
# bfloat16 by the two dtype checks.  (a) to (c) are there for the structure
# (the mask, the tying, the kernels' backward) and for e4m3.
#
# (a) First loss of the compiled step on the whole batch (bf16 activations,
# flash kernels) against the reference's over the same 32 sequences in
# micro-batches: a signed difference around zero, two decades wide, which
# tells no precision from another (e4m3 5.8e-4 and 1.0e-3).  Sound: PR 27
# 1.3e-5 to 6.0e-4, PR 30 1.5e-5 to 6.0e-4.  Faults (CPU, BERT_TINY; not read
# at the cell's size): a missing mask moves it by 9e-3, a gather one position
# off by 3e-2.  Kept.
TOL_FIRST_LOSS = 2e-3
# (b) Both logits and the loss of the system's forward on the sample (the
# reference's micro-batch that holds a short sequence) against the
# reference's.
# Masked-LM logits, max |a - b| / max |b| over 4 x 80 x 30,522.  Sound: PR 27
# 1.65e-2 to 2.73e-2, PR 30 1.61e-2 to 2.73e-2 (bf16 throughout 2.1e-2).
# Faults at the cell's size: a missing mask 0.145 to 0.27, e4m3 0.28 to 0.36
# (PR 27: 0.30, 0.39).  Middle: 2.3 x from either.
TOL_SAMPLE_MLM_LOGITS = 6.3e-2
# Next-sentence logits, max |a - b| / max(1, max |b|), eight numbers of order
# one.  Sound: PR 27 7.2e-3 to 3.0e-2, PR 30 6.1e-3 to 3.5e-2 (the seeds
# spread 6 x).  Faults: e4m3 0.24 and 0.37, a missing mask 0.49 (CPU,
# BERT_TINY).  Less than a decade: kept, 1.7 x the largest (4 standard
# deviations of the readings' logarithm above their mean).
TOL_SAMPLE_NSP_LOGITS = 6e-2
# Loss on the sample, signed like (a).  Sound: PR 27 1.8e-5 to 1.2e-3, PR 30
# 7.8e-6 to 1.2e-3.  No fault on record (e4m3 2.1e-3 and 4.2e-3 is none of
# its: it tells no precision from another).  Kept.
TOL_SAMPLE_LOSS = 2.5e-3
# (c) The first moment after one step is (1 - b1) x the exchanged gradient of
# the whole batch: bf16 backward through 24 post-LN blocks and the dq / dkv
# kernels under the mask, the gather's transpose and the tying.  The error is
# taken over the root of the summed squares of the reference's micro-batch
# gradients, not over the norm of their sum: rounding noise adds in
# quadrature over the sequences whether or not their gradients cancel, and
# with random next-sentence labels they do (||sum|| / root of squares read
# 0.12 to 2.1 over the seeds; the plain L2 error of the sum read 1.4e-2 to
# 0.23 with it).
# The tied word embeddings, a shallow path, an L2 error over 31 M elements.
# Sound: PR 27 1.80e-2 to 2.61e-2, PR 30 1.67e-2 to 2.33e-2.  Faults at the
# cell's size: a missing mask 0.067 to 0.20 (2.6 x the largest sound reading:
# this leaf hardly sees the mask, (b) sees it better), e4m3 0.28 to 0.38
# (PR 27: 0.36), the decoder's gradient left out of the tied matrix 0.97 to
# 1.01.  Middle: 1.6 x from either.
TOL_FIRST_MOMENT_TIED = 4.2e-2
# layer_0 qkv, layer_23 mlp_out, the next-sentence kernel.  Sound: PR 27
# 1.6e-2 to 9.0e-2 (medians 4.7e-2, 3.6e-2, 3.2e-2; the 24th seed read 0.089
# on a leaf whose first 17 had read at most 0.063), PR 30 1.5e-2 to
# 7.1e-2 (bf16 throughout 1.8e-2 to 4.3e-2).  Faults: e4m3 0.36 to 2.8; a
# missing mask 0.42 to 0.64, a gather one off 0.85 to 0.99 (CPU, BERT_TINY).
# Less than a decade: kept, 1.8 x the largest (4.6 standard deviations of
# the logarithm or more).
TOL_FIRST_MOMENT_DEEP = 0.16
# (d) What the first step did to the same four leaves, against plain AdamW
# (``adamw_first_update``, float64 numpy) of the moments the step itself left
# behind: the L2 error of the change.  The activations' noise is in the
# moments on both sides and cancels; what is left is the arithmetic of the
# update and the precision the parameters are kept in.  The learning rate
# 1e-4 is below a bfloat16 ulp of a weight near 0.03 (1.2e-4), a float32 ulp
# there is 1.9e-9.  Sound: PR 27 7.8e-6 to 1.07e-5, PR 30 7.8e-6 to 1.06e-5,
# the same to three digits on every seed (half of it optax's float32 bias
# correction, 1 - 0.999 = 0.00099999); 1.3e-5 to 2.5e-5 at --rehearse's
# sizes, where a weight is 0.09 (CPU).  Fault: the parameters kept in
# bfloat16 (the system's own update added to rounded parameters and rounded)
# 0.26 to 0.62.  Kept: above the rehearsal's readings too.
TOL_FIRST_UPDATE = 3e-5
# (e) The float32 end of the masked-LM head alone (``decode``: layer norm, the
# tied decoder, the bias) on the reference's float32 transformed hidden states
# of the sample, compiled under "highest" so that a float32 product is whole,
# against the reference's logits, max |a - b| / max |b|: what 1024-term
# float32 dots summed in two orders differ by, at the worst of 4 x 80 x
# 30,522.  Sound: PR 27 4.6e-7 to 6.3e-7, PR 29 2.06e-6 at seed 1, PR 30
# 4.4e-7 to 2.06e-6 (median 5.6e-7; 2.6e-7 to 3.4e-7 at BERT_TINY's sizes,
# CPU).  Faults: the logits alone rounded to bfloat16 1.9e-3 and 2.9e-3,
# ``decode`` as the step compiles it, at the chip's default precision of one
# bfloat16 pass for a float32 product, 2.8e-3 and 3.7e-3 (hence "highest"
# here), the reference's own head in bfloat16 6.2e-3 and 6.5e-3.  Middle: 30 x
# from either.
# (The encoder's 49 layer norms write bfloat16 either way and no comparison
# at initialisation sees past that rounding: PERF.md section 7.)
TOL_DECODE = 6e-5
# The micro-batch of the reference: what one chip holds in float32 with the
# scores of every layer kept for the backward.
REF_MICRO_BATCH = 4
POSITION_DRAWS = 1 << 20   # a position is a draw modulo the sequence's length


def _sizes(cfg: dict, rehearse: bool) -> dict:
    return {**cfg["assumed"], **cfg, **(cfg["rehearse"] if rehearse else {})}


def _bert_config(cfg: dict, rehearse: bool):
    import jax.numpy as jnp

    from horovod_tpu import models

    c = _sizes(cfg, rehearse)
    return models.BertConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"], num_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        max_position_embeddings=c["max_position_embeddings"],
        type_vocab_size=c["type_vocab_size"], dropout_rate=c["dropout"],
        dtype=jnp.dtype(c["dtype"]), use_flash=c["use_flash"])


def setup(cfg: dict, mesh, seed: int, rehearse: bool = False) -> dict:
    """Model and seeded weights (replicated), made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import models

    bcfg = _bert_config(cfg, rehearse)
    if bcfg.padded_vocab_size != _sizes(cfg, rehearse)["vocab_size_padded"]:
        raise ValueError("the configuration's vocab_size_padded is not what "
                         f"the model pads to: {bcfg.padded_vocab_size}")
    model = models.BertForPreTraining(bcfg)

    def init(key):
        ids = jnp.zeros((1, 16), jnp.int32)
        return model.init(key, ids, ids, lengths=jnp.full((1,), 16),
                          masked_positions=ids[:, :4])

    # The key is an argument, not a constant of the program (families/gpt.py).
    key = jax.random.fold_in(jax.random.key(seed), 0)
    params = jax.jit(init, out_shardings=NamedSharding(mesh, P()))(key)
    return {"cfg": cfg, "mesh": mesh, "model": model, "bcfg": bcfg,
            "rehearse": rehearse, "params": params}


def _seq_len(bcfg, traffic: dict) -> int:
    seq = traffic.get("seq_len", bcfg.max_position_embeddings)
    if seq > bcfg.max_position_embeddings:
        raise ValueError(f"traffic asks for {seq} tokens a sequence, the "
                         "configuration has "
                         f"{bcfg.max_position_embeddings} positions")
    return seq


def chip_lengths(traffic: dict, seq: int) -> list:
    """Real tokens of each sequence of one chip's batch: fixed by position."""
    lengths = [seq] * traffic["batch_per_chip"]
    for index, real in traffic.get("short_sequences", {}).items():
        if not 0 < real <= seq:
            raise ValueError(f"short sequence {index}: {real} tokens of {seq}")
        lengths[int(index)] = real
    return lengths


def weighted_positions(traffic: dict, length: int) -> int:
    """How many of a sequence's masked positions carry weight."""
    return min(traffic["max_predictions_per_seq"],
               round(traffic["masked_lm_prob"] * length))


def inputs(cell: dict, traffic: dict) -> list:
    """The five drawn arguments of the step, per sequence: token ids, token
    types, a draw per masked position (``shape_batch`` folds it into the
    sequence's length), the masked tokens' labels, the next-sentence label."""
    import jax.numpy as jnp

    from benchmark.traffic import Input

    bcfg = cell["bcfg"]
    seq, p = _seq_len(bcfg, traffic), traffic["max_predictions_per_seq"]
    return [Input((seq,), jnp.int32, "randint", bcfg.vocab_size),
            Input((seq,), jnp.int32, "randint", bcfg.type_vocab_size),
            Input((p,), jnp.int32, "randint", POSITION_DRAWS),
            Input((p,), jnp.int32, "randint", bcfg.vocab_size),
            Input((), jnp.int32, "randint", 2)]


def shape_batch(traffic: dict, ids, types, draws, labels, nsp, chips=1):
    """The model's and the loss's arguments from the drawn ones, for
    ``chips`` chips' batches laid end to end (1 inside the step): lengths by
    position, masked positions inside them, weights on the first
    ``weighted_positions`` of each."""
    import numpy as np

    per_chip = chip_lengths(traffic, ids.shape[1])
    assert ids.shape[0] == len(per_chip) * chips, (ids.shape, chips)
    lengths = np.asarray(per_chip * chips, np.int32)
    weighted = np.asarray([weighted_positions(traffic, n) for n in lengths])
    weights = (np.arange(draws.shape[1])[None, :]
               < weighted[:, None]).astype(np.float32)
    return {"input_ids": ids, "token_type_ids": types, "lengths": lengths,
            "masked_positions": draws % lengths[:, None],
            "mlm_labels": labels, "mlm_weights": weights, "nsp_labels": nsp}


def _loss(model, params, b: dict):
    from horovod_tpu import models

    mlm, nsp = model.apply(params, b["input_ids"], b["token_type_ids"],
                           lengths=b["lengths"],
                           masked_positions=b["masked_positions"])
    return models.pretraining_loss(mlm, nsp, b["mlm_labels"],
                                   b["mlm_weights"], b["nsp_labels"])


def _checked_tree(tree, layers: int) -> dict:
    """The leaves check (c) compares, as a sub-tree with the whole tree's
    paths: the tied word embeddings (the lookup's gradient plus the
    decoder's), the first block's fused qkv kernel (it has passed through
    every layer's dq and dkv), the last block's second feed-forward kernel
    and the next-sentence classifier."""
    p = tree["params"]
    enc, last = p["encoder"], f"layer_{layers - 1}"
    return {"params": {
        "encoder": {
            "word_embeddings": {
                "embedding": enc["word_embeddings"]["embedding"]},
            "layer_0": {"attention": {"qkv": {
                "kernel": enc["layer_0"]["attention"]["qkv"]["kernel"]}}},
            last: {"mlp_out": {"kernel": enc[last]["mlp_out"]["kernel"]}}},
        "nsp_head": {"kernel": p["nsp_head"]["kernel"]}}}


def _micro_batches(b: dict, micro: int):
    n = b["input_ids"].shape[0]
    assert n % micro == 0, (n, micro)
    for i in range(0, n, micro):
        yield {k: v[i:i + micro] for k, v in b.items()}


def reference(cell: dict) -> dict:
    """The plain float32 reference on the first global batch, in micro-batches
    of ``REF_MICRO_BATCH`` sequences: (a) its loss, (c) its gradient of the
    four named leaves and the first moment one plain optax update of them
    leaves behind, (b) both its logits and its loss on the sample, the first
    micro-batch that holds a short sequence (kept in ``cell["sample"]`` for
    ``probe``).  The loss is a ratio of sums over the batch, whose
    denominators the traffic fixes, so the micro-batches' gradients add up
    exactly; how far they cancel in the sum is kept beside each leaf, for
    the measure of (c)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bcfg, traffic, mesh = cell["bcfg"], cell["traffic"], cell["mesh"]
    device = mesh.devices.flat[0]
    params = common.first_shard(cell["params"])
    drawn = [jax.device_put(x, device) for x in cell["batches"][0]]
    batch = shape_batch(traffic, *drawn, chips=mesh.size)
    total_weight = float(batch["mlm_weights"].sum())
    sequences = len(batch["lengths"])
    micro = min(REF_MICRO_BATCH, traffic["batch_per_chip"])
    vocab = bcfg.vocab_size

    def part(p, b):
        mlm, nsp = reference_bert.pretraining_logits(
            p["params"], vocab, b["input_ids"], b["token_type_ids"],
            b["lengths"], b["masked_positions"])
        mlm_sum, _, nsp_sum = reference_bert.loss_sums(
            mlm, nsp, b["mlm_labels"], b["mlm_weights"], b["nsp_labels"])
        return mlm_sum / total_weight + nsp_sum / sequences, (mlm, nsp)

    def part_and_leaf_grads(p, b):
        (loss, logits), grads = jax.value_and_grad(part, has_aux=True)(p, b)
        return loss, logits, _checked_tree(grads, bcfg.num_layers)

    def sample_loss(logits, b):
        mlm_sum, weight, nsp_sum = reference_bert.loss_sums(
            *logits, b["mlm_labels"], b["mlm_weights"], b["nsp_labels"])
        return float(mlm_sum / weight + nsp_sum / len(b["lengths"]))

    def sample_transformed(p, b):
        hidden = reference_bert.encoder(
            p["params"]["encoder"], b["input_ids"], b["token_type_ids"],
            b["lengths"])
        return reference_bert.transformed(p["params"], hidden,
                                          b["masked_positions"])

    fn = jax.jit(part_and_leaf_grads)
    loss, grads, squares, sample = 0.0, None, None, None
    with jax.default_matmul_precision("highest"):
        for b in _micro_batches(batch, micro):
            b = {k: jnp.asarray(v) for k, v in b.items()}
            part_loss, logits, part_grads = fn(params, b)
            loss += float(part_loss)
            part_squares = jax.tree_util.tree_map(
                lambda g: jnp.sum(jnp.square(g)), part_grads)
            grads, squares = (part_grads, part_squares) if grads is None else (
                jax.tree_util.tree_map(jnp.add, grads, part_grads),
                jax.tree_util.tree_map(jnp.add, squares, part_squares))
            short = bool(np.any(np.asarray(b["lengths"])
                                < b["input_ids"].shape[1]))
            if sample is None and (short or not traffic.get(
                    "short_sequences")):
                sample = {"batch": b, "loss": sample_loss(logits, b),
                          "mlm": np.asarray(logits[0]),
                          "nsp": np.asarray(logits[1]),
                          "transformed": jax.jit(sample_transformed)(
                              params, b)}
    cell["sample"] = sample
    leaves = _checked_tree(params, bcfg.num_layers)
    tx = common.make_optimizer(cell["cfg"]["optimizer"])
    _, opt_state = tx.update(grads, tx.init(leaves), leaves)
    norms = {k: float(jnp.linalg.norm(g))
             for k, g in common.leaf_paths(grads).items()}
    # The step donates the parameters: the leaves as they are before it go
    # to the host here, for (d).
    before = {k: np.array(v) for k, v in common.leaf_paths(leaves).items()}
    return {"loss": loss, "leaves": {
        k: {"first_moment": np.asarray(
                common.first_moments(opt_state, k)[0]),
            "before": before[k],
            # ||sum of the micro-batches' gradients|| over the root of the
            # sum of their squares: below 1 where they cancel.
            "sum_over_parts": norms[k] / float(np.sqrt(rss))}
        for k, rss in common.leaf_paths(squares).items()}}


def _unit_err(a, b) -> float:
    """max |a - b| / max(1, max |b|): the eight next-sentence logits of the
    sample are of order one by construction (a tanh-bounded vector times a
    unit-variance kernel) and all of them can be small, where an error
    relative to the largest would measure the draw and not the model."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(1.0, float(np.max(np.abs(b)))))


def probe(cell: dict, step, state) -> list:
    """(b) The system's forward on the sample under the cell's precision and
    kernels, before the first step moves the weights: both logits and the
    loss against the reference's; (e) its ``decode`` alone on the reference's
    float32 input."""
    import jax
    import numpy as np

    from horovod_tpu import models

    sample, model = cell.pop("sample"), cell["model"]
    b, vocab = sample["batch"], cell["bcfg"].vocab_size

    def forward(p, b):
        mlm, nsp = model.apply(p, b["input_ids"], b["token_type_ids"],
                               lengths=b["lengths"],
                               masked_positions=b["masked_positions"])
        return mlm, nsp, models.pretraining_loss(
            mlm, nsp, b["mlm_labels"], b["mlm_weights"], b["nsp_labels"])

    params = common.first_shard(state[0])
    mlm, nsp, loss = jax.jit(forward)(params, b)
    # (e) The float32 end of the head alone, on the reference's float32
    # input and with whole float32 products, so that no bfloat16 rounding
    # upstream can hide what it is computed in.
    with jax.default_matmul_precision("highest"):
        decoded = jax.jit(lambda p, h: model.apply(p, h, method="decode"))(
            params, sample["transformed"])
    mlm = np.asarray(mlm)
    return [
        common.check("sample_mlm_logits_vs_reference", common.rel_err(
            mlm[..., :vocab], sample["mlm"]), TOL_SAMPLE_MLM_LOGITS),
        {"name": "padded_vocabulary_is_out_of_the_softmax",
         "ok": bool(mlm[..., vocab:].size == 0
                    or mlm[..., vocab:].max() <= -1e30)},
        common.check("sample_nsp_logits_vs_reference", _unit_err(
            np.asarray(nsp), sample["nsp"]), TOL_SAMPLE_NSP_LOGITS),
        common.check("sample_loss_vs_reference", common.rel_err(
            float(loss), sample["loss"]), TOL_SAMPLE_LOSS),
        {"name": "logits_are_float32",
         "ok": bool(mlm.dtype == np.float32 and nsp.dtype == np.float32)},
        common.check("decode_of_the_reference_s_hidden_vs_reference",
                     common.rel_err(np.asarray(decoded)[..., :vocab],
                                    sample["mlm"]), TOL_DECODE)]


def build(cell: dict):
    """``(compiled step, state)``, compiled ahead of time.
    ``*state, loss = step(*state, *batch)`` for each batch of the cell."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    model, mesh, traffic = cell["model"], cell["mesh"], cell["traffic"]
    tx = hvd.DistributedOptimizer(
        common.make_optimizer(cell["cfg"]["optimizer"]), axis_name="hvd")

    def train_step(params, opt_state, *drawn):
        batch = shape_batch(traffic, *drawn)
        loss, grads = jax.value_and_grad(
            lambda p: _loss(model, p, batch))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, axis_name="hvd"))

    drawn = cell["batches"][0]
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), *(P("hvd") for _ in drawn)),
        out_specs=(P(), P(), P())), donate_argnums=(0, 1))
    opt_state = jax.jit(tx.init, out_shardings=NamedSharding(mesh, P()))(
        cell["params"])
    state = (cell["params"], opt_state)
    return step.lower(*state, *drawn).compile(), state


def checks(cell: dict, ref: dict, first_loss: float, state, hlo: dict) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    bcfg = cell["bcfg"]
    out = [common.check("first_loss_vs_reference",
                        common.rel_err(first_loss, ref["loss"]),
                        TOL_FIRST_LOSS)]
    if bcfg.use_flash:
        # forward, dq and dkv per layer: the Pallas kernels, not the dense
        # fallback, are in the compiled step.
        out.append(common.at_least("tpu_custom_calls",
                                   hlo["tpu_custom_call"],
                                   3 * bcfg.num_layers))
    if cell["mesh"].size > 1:
        out.append(common.at_least("all_reduce_ops",
                                   hlo.get("all-reduce", 0), 1))
    params, opt_state = state
    leaves = common.leaf_paths(params)
    for k, want in ref["leaves"].items():
        moments = common.first_moments(opt_state, k)
        assert len(moments) == 1, (k, len(moments))
        mu = jax.device_get(moments[0])
        of_the_sum = common.l2_rel_err(mu, want["first_moment"])
        out.append({**common.check(
            f"first_moment{k}", of_the_sum * want["sum_over_parts"],
            TOL_FIRST_MOMENT_TIED if "word_embeddings" in k
            else TOL_FIRST_MOMENT_DEEP), "l2_of_the_sum": of_the_sum,
            "sum_over_parts": want["sum_over_parts"]})
        # (d) What the step did to the leaf against plain AdamW of the
        # moments the step itself left behind.
        nu = jax.device_get(_second_moment(opt_state, k))
        after = np.asarray(jax.device_get(leaves[k]), np.float64)
        out.append(common.check(f"first_update{k}", common.l2_rel_err(
            after - want["before"], adamw_first_update(
                want["before"], mu, nu, **cell["cfg"]["optimizer"]["args"])),
            TOL_FIRST_UPDATE))
    inexact = [x.dtype for x in jax.tree_util.tree_leaves(state)
               if jnp.issubdtype(x.dtype, jnp.inexact)]
    out.append({"name": "parameters_and_moments_are_float32",
                "ok": bool(inexact) and all(
                    d == jnp.float32 for d in inexact)})
    return out


def _second_moment(opt_state, param_path: str):
    """optax's ``nu`` of the parameter at ``param_path`` (Adam's second
    moment; ``common.first_moments`` finds ``mu``)."""
    import re

    found = [leaf for path, leaf in common.leaf_paths(opt_state).items()
             if path.endswith(param_path)
             and re.search(r"\.nu\b", path[:-len(param_path)])]
    assert len(found) == 1, (param_path, len(found))
    return found[0]


def adamw_first_update(before, mu, nu, learning_rate: float, b1: float,
                       b2: float, weight_decay: float, eps: float = 1e-8):
    """The first step of AdamW (Loshchilov & Hutter, arXiv:1711.05101,
    algorithm 2 at t = 1) written out in float64 numpy: the change of a
    parameter that was ``before`` and whose moments after the step are
    ``mu`` and ``nu``."""
    import numpy as np

    before, mu, nu = (np.asarray(x, np.float64) for x in (before, mu, nu))
    m_hat, v_hat = mu / (1.0 - b1), nu / (1.0 - b2)
    return -learning_rate * (m_hat / (np.sqrt(v_hat) + eps)
                             + weight_decay * before)


def model_flops(cell: dict) -> float:
    """Forward + backward FLOPs of one step of the whole (global) batch,
    traced with dense attention (a Pallas call shows no dot_general).
    Padded positions count, as the published step computes them: every
    sequence is ``seq_len`` long to the matmuls, attention's two S x S
    products are whole squares (no causal half), and the decoder runs over
    the padded vocabulary at the P gathered positions."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    model = models.BertForPreTraining(
        dataclasses.replace(cell["bcfg"], use_flash=False))
    ids, _, draws, _, _ = cell["batches"][0]
    batch, seq = ids.shape
    one = jax.ShapeDtypeStruct((1, seq), jnp.int32)

    def forward(p, ids, types, lengths, positions):
        return model.apply(p, ids, types, lengths=lengths,
                           masked_positions=positions)

    macs = flops.forward_macs(
        forward, cell["params"], one, one,
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1, draws.shape[1]), jnp.int32))
    return flops.train_flops(macs) * batch


def units(cell: dict) -> tuple:
    """What one step processes, for the tokens/s line (padding included)."""
    batch, seq = cell["batches"][0][0].shape
    return "tokens", batch * seq
