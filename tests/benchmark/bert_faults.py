"""The faults that checks (b) and (c) of ``benchmark/families/bert.py`` are
there to catch, made in the plain reference and read in those checks' own
measures against the plain reference itself: what a limit on the sample's
masked-LM logits and on the tied word embeddings' first moment must stay
under (``benchmark/testdata/check_readings/bert.json`` keeps the readings).

    python tests/benchmark/bert_faults.py --seeds 1 2 3

reads them at ``bert-large-s512``'s own size on the machine it is started on
(a TPU: about a minute a seed after five compilations) and prints one JSON
line a seed.  ``test_check_limits.py`` runs one fault at ``--rehearse``'s
sizes.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import bert  # noqa: E402
from benchmark.references import bert as reference_bert  # noqa: E402

CELL = "bert-large-s512"
FAULTS = {
    "missing_mask": "padded keys take part in every softmax",
    "gather_one_off": "the head reads the position after each masked one",
    "e4m3": "every parameter and every function's output (layer norm, "
            "dense, GELU, attention, decode) rounded to float8_e4m3; "
            "gradients pass the rounding unrounded",
    "untied_decoder": "the decoder's share of the tied matrix's gradient is "
                      "left out (the lookup's alone arrives)",
}


@contextlib.contextmanager
def reference_with(**attributes):
    """The plain reference with some of its module's names replaced."""
    kept = {k: getattr(reference_bert, k) for k in attributes}
    try:
        for k, v in attributes.items():
            setattr(reference_bert, k, v)
        yield
    finally:
        for k, v in kept.items():
            setattr(reference_bert, k, v)


def _e4m3():
    import jax
    import jax.numpy as jnp

    @jax.custom_jvp
    def rounded(x):
        return x.astype(jnp.float8_e4m3fn).astype(x.dtype)

    rounded.defjvp(lambda primals, tangents: (rounded(primals[0]),
                                              tangents[0]))

    def wrap(fn):
        return lambda *a, **kw: rounded(fn(*a, **kw))

    return rounded, {k: wrap(getattr(reference_bert, k)) for k in (
        "layer_norm", "gelu", "dense", "attention", "decode")}


def readings(faults: list, params, vocab: int, batch: dict,
             micro: int) -> dict:
    """``{fault: {"sample_mlm_logits", "first_moment_tied"}}``: what (b)
    reads on the masked-LM logits of the sample (the first micro-batch that
    holds a short sequence) and (c) on the tied word embeddings' gradient of
    the whole batch when the reference with ``fault`` stands in the system's
    place.  ``params`` is the model's ``"params"`` tree, ``batch`` what
    ``bert.shape_batch`` returns."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    weight = float(np.sum(batch["mlm_weights"]))
    sequences = len(batch["lengths"])
    embedding = params["encoder"]["word_embeddings"]["embedding"]

    def loss_and_logits(emb, p, b, decoder=None):
        p = {**p, "encoder": {**p["encoder"],
                              "word_embeddings": {"embedding": emb}}}
        mlm, nsp = reference_bert.pretraining_logits(
            p, vocab, b["input_ids"], b["token_type_ids"], b["lengths"],
            b["masked_positions"], decoder=decoder)
        mlm_sum, _, nsp_sum = reference_bert.loss_sums(
            mlm, nsp, b["mlm_labels"], b["mlm_weights"], b["nsp_labels"])
        return mlm_sum / weight + nsp_sum / sequences, mlm

    logits_and_tied_grad = jax.value_and_grad(loss_and_logits, has_aux=True)

    def sound(emb, p, b):
        (_, mlm), grad = logits_and_tied_grad(emb, p, b)
        return mlm, grad

    def faulty(fault):
        def fn(emb, p, b):
            changed, decoder, b = {}, None, dict(b)
            if fault == "missing_mask":
                changed = {"MASKED": 0.0}
            elif fault == "gather_one_off":
                b["masked_positions"] = (b["masked_positions"] + 1) % b[
                    "lengths"][:, None]
            elif fault == "e4m3":
                rounded, changed = _e4m3()
                emb, p = rounded(emb), jax.tree_util.tree_map(rounded, p)
            elif fault == "untied_decoder":
                decoder = jax.lax.stop_gradient(emb)
            else:
                raise ValueError(f"{fault!r} is none of {sorted(FAULTS)}")
            with reference_with(**changed):
                (_, mlm), grad = logits_and_tied_grad(emb, p, b, decoder)
            return mlm, grad
        return jax.jit(fn)

    sound, programs = jax.jit(sound), {f: faulty(f) for f in faults}
    out, diff, squares = {f: {} for f in faults}, {f: 0.0 for f in faults}, 0.0
    with jax.default_matmul_precision("highest"):
        for b in bert._micro_batches(batch, micro):
            short = bool(np.any(np.asarray(b["lengths"])
                                < b["input_ids"].shape[1]))
            b = {k: jnp.asarray(v) for k, v in b.items()}
            want_mlm, want = sound(embedding, params, b)
            squares += float(jnp.sum(jnp.square(want)))
            for f, program in programs.items():
                got_mlm, got = program(embedding, params, b)
                if short and "sample_mlm_logits" not in out[f]:
                    out[f]["sample_mlm_logits"] = common.rel_err(got_mlm,
                                                                 want_mlm)
                diff[f] = diff[f] + (got - want)
    # (c)'s measure: the L2 error of the batch's gradient over the root of
    # the summed squares of the reference's micro-batch gradients.
    for f in faults:
        out[f]["first_moment_tied"] = (float(jnp.linalg.norm(diff[f]))
                                       / squares ** 0.5)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=sorted(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("bert_faults: no TPU; --rehearse reads BERT_TINY's sizes",
              file=sys.stderr)
        return 1
    mesh = common.hvd_mesh([device])
    for seed in args.seeds:
        cell = bert.setup(cfg, mesh, seed, rehearse=args.rehearse)
        drawn = traffic_gen.make_batches(
            traffic, bert.inputs(cell, traffic), mesh, seed)[0]
        got = readings(
            args.faults, common.first_shard(cell["params"])["params"],
            cell["bcfg"].vocab_size, bert.shape_batch(traffic, *drawn),
            micro=min(bert.REF_MICRO_BATCH, traffic["batch_per_chip"]))
        print(json.dumps({"workload": CELL, "seed": seed,
                          "device": device.device_kind,
                          "rehearse": args.rehearse, "faults": got}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
