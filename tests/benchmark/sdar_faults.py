"""The faults that the checks of ``benchmark/families/sdar.py`` are there to
catch, made in the plain reference and read in those checks' own measures
against the plain reference itself: what a limit must stay under
(``benchmark/testdata/check_readings/sdar.json`` keeps the readings).

    python tests/benchmark/sdar_faults.py --seeds 1 2 3 [--grads]

reads them at ``sdar-moe-ep8-s4096``'s own size on the machine it is started
on (a TPU) and prints one JSON line a seed: per fault the sample's logits,
the first sequence's loss, the first block's router probabilities and the
share of the choices that differ; with ``--grads`` also the named leaves'
gradients.  ``test_sdar_cell.py`` runs them at ``--rehearse``'s sizes.
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
from benchmark.families import sdar  # noqa: E402
from benchmark.references import sdar as reference_sdar  # noqa: E402

CELL = "sdar-moe-ep8-s4096"
FAULTS = {
    "noised_sees_its_own_clean_block": "a noised row also sees the clean "
        "copy of its own block (kb <= qb where kb < qb stands): it reads "
        "the tokens it is to predict",
    "clean_sees_noised": "a clean row also sees the noised copy of its own "
                         "block",
    "key_head_i_mod_kv": "query head i reads key/value head i mod Hkv "
                         "(i // group stands)",
    "noised_positions_offset_by_L": "the noised copy's rotary positions run "
                                    "L .. 2L - 1",
    "no_norm_topk": "the chosen k weights are not divided by their sum",
    "held_range_off_by_one": "the held kernels are taken for experts "
                             "first + 1 .. first + held",
    "absent_experts_added": "a chosen expert that is not held is computed "
                            "too, by the held kernel e mod held",
    "no_loss_weight": "the masked tokens' cross-entropy is not weighted "
                      "1 / t",
    "bf16_throughout": "every parameter and every function's output "
                       "(norm, rotary, attention, router, experts, head) "
                       "rounded to bfloat16; gradients pass unrounded",
    "e4m3": "the same rounding to float8_e4m3",
}


@contextlib.contextmanager
def reference_with(**attributes):
    """The plain reference with some of its module's names replaced."""
    kept = {k: getattr(reference_sdar, k) for k in attributes}
    try:
        for k, v in attributes.items():
            setattr(reference_sdar, k, v)
        yield
    finally:
        for k, v in kept.items():
            setattr(reference_sdar, k, v)


def _rounding(dtype):
    import jax

    @jax.custom_jvp
    def rounded(x):
        return x.astype(dtype).astype(x.dtype)

    rounded.defjvp(lambda primals, tangents: (rounded(primals[0]),
                                              tangents[0]))

    def wrap(fn):
        def wrapped(*a, **kw):
            out = fn(*a, **kw)
            return (tuple(rounded(o) if hasattr(o, "astype")
                          and o.dtype.kind == "f" else o for o in out)
                    if isinstance(out, tuple) else rounded(out))
        return wrapped

    return rounded, {k: wrap(getattr(reference_sdar, k)) for k in (
        "rms_norm", "rotary", "attention", "router_probs", "moe", "head")}


def _fault(name: str):
    """``(replaced names of the reference, what to do to the parameters)``"""
    import jax
    import jax.numpy as jnp

    same = lambda p: p  # noqa: E731
    if name == "noised_sees_its_own_clean_block":
        def visible(qi, kj, length, block):
            qb, kb = (qi % length) // block, (kj % length) // block
            return jnp.where(qi >= length,
                             jnp.where(kj >= length, kb == qb, kb <= qb),
                             jnp.logical_and(kj < length, kb <= qb))
        return {"visible": visible}, same
    if name == "clean_sees_noised":
        def visible(qi, kj, length, block):
            qb, kb = (qi % length) // block, (kj % length) // block
            return jnp.where(qi >= length,
                             jnp.where(kj >= length, kb == qb, kb < qb),
                             jnp.where(kj >= length, kb == qb, kb <= qb))
        return {"visible": visible}, same
    if name == "key_head_i_mod_kv":
        return {"kv_head_of": lambda head, group: head % (
            head.shape[0] // group)}, same
    if name == "noised_positions_offset_by_L":
        return {"positions": lambda length: jnp.arange(2 * length)}, same
    if name == "no_norm_topk":
        return {"top_k_weights": lambda probs, chosen, renormalize:
                jnp.take_along_axis(probs, chosen, axis=-1)}, same
    if name == "held_range_off_by_one":
        return {"held_experts": lambda cfg, held:
                cfg["first_expert"] + 1 + jnp.arange(held)}, same
    if name == "absent_experts_added":
        def added(p, x, cfg, chosen=None):
            held = p["w_gate"].shape[0]
            probs = reference_sdar.router_probs(p, x)
            if chosen is None:
                chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]
            weights = reference_sdar.top_k_weights(
                probs, chosen, cfg["norm_topk_prob"])
            folded = chosen % held
            y = jnp.zeros_like(x)
            for i in range(held):
                mine = jnp.sum(jnp.where(folded == i, weights, 0.0), -1)
                h = jax.nn.silu(x @ p["w_gate"][i]) * (x @ p["w_up"][i])
                y = y + mine[:, None] * (h @ p["w_down"][i])
            return y, probs, chosen
        return {"moe": added}, same
    if name == "no_loss_weight":
        return {"loss_weight": lambda levels: jnp.ones_like(levels)}, same
    dtype = {"bf16_throughout": jnp.bfloat16, "e4m3": jnp.float8_e4m3fn}[name]
    rounded, wrapped = _rounding(dtype)
    return wrapped, lambda p: jax.tree_util.tree_map(rounded, p)


def readings(faults: list, params, scfg, batch: dict, grads: bool = False,
             sequences: int = 1) -> dict:
    """``{fault: {measure: reading}}`` on the first sequence of ``batch``
    (``sdar.shape_batch``'s), each fault's reference against the sound one,
    both on the sound reference's own choices (what follows a flipped choice
    is not the fault's).  ``choices_differing`` is the fault's own top-k
    against the sound one's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rcfg = sdar.reference_config(scfg)
    b = {k: v[0] for k, v in batch.items()}
    length = b["clean"].shape[0]
    positions = min(sdar.SAMPLE_POSITIONS, length)

    def run_(prepare, chosen):
        def part(p):
            logits, seen = reference_sdar.logits(
                prepare(p)["params"], b["clean"], b["noised"], rcfg, chosen)
            loss = reference_sdar.loss_sum(
                logits, b["clean"], b["masked"], b["levels"]) / (sequences
                                                                 * length)
            return loss, (logits[:positions], seen[0]["probs"],
                          jnp.stack([s["chosen"] for s in seen]))

        if not grads:
            return (*jax.jit(part)(params), None)
        (loss, aux), g = jax.jit(jax.value_and_grad(part, has_aux=True))(
            params)
        return loss, aux, common.leaf_paths(sdar._checked_tree(g, scfg))

    with jax.default_matmul_precision("highest"):
        _, (_, _, chose), _ = run_(lambda p: p, None)
        loss, (logits, probs, _), leaf_grads = run_(lambda p: p, chose)
        out = {}
        for name in faults:
            replaced, prepare = _fault(name)
            with reference_with(**replaced):
                f_loss, (f_logits, f_probs, _), f_grads = run_(prepare, chose)
                _, (_, _, f_chose), _ = run_(prepare, None)
            out[name] = {
                "sample_logits": common.rel_err(np.asarray(f_logits),
                                                np.asarray(logits)),
                "first_loss": common.rel_err(float(f_loss), float(loss)),
                "router_probs": common.rel_err(np.asarray(f_probs),
                                               np.asarray(probs)),
                "choices_differing": sdar.choices_differing(f_chose, chose)}
            if grads:
                out[name]["first_moment"] = {
                    common_name: sdar.moment_error(
                        common_name, np.asarray(f_grads[common_name]),
                        np.asarray(g))
                    for common_name, g in leaf_grads.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="+", default=list(FAULTS),
                    choices=list(FAULTS))
    ap.add_argument("--grads", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    mesh = common.hvd_mesh(jax.devices()[:1])
    for seed in args.seeds:
        cell = sdar.setup(cfg, mesh, seed, rehearse=args.rehearse)
        drawn = traffic_gen.make_batches(
            traffic, sdar.inputs(cell, traffic), mesh, seed)[0]
        batch = jax.jit(lambda *d: sdar.shape_batch(cell["scfg"], *d))(*drawn)
        got = readings(args.faults, cell["params"], cell["scfg"], batch,
                       grads=args.grads,
                       sequences=traffic["batch_per_chip"])
        print(json.dumps({"seed": seed, "device": jax.devices()[0].device_kind,
                          "cell": CELL, "readings": got}), flush=True)
        del cell, drawn, batch
    return 0


if __name__ == "__main__":
    sys.exit(main())
