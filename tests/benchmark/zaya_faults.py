"""The faults that the checks of ``benchmark/families/zaya.py`` are there to
catch, made in the plain reference and read in those checks' own measures
against the plain reference itself: what a limit must stay under
(``benchmark/testdata/check_readings/zaya.json`` keeps the readings).

    python tests/benchmark/zaya_faults.py --seeds 1 2 3 [--grads]

reads them at ``zaya1-moe-ep2-s16384``'s own size on the machine it is
started on (a TPU) and prints one JSON line a seed and fault: the sample's
logits, the first sequence's loss, the second block's router probabilities
and the share of the choices that differ; with ``--grads`` also the named
leaves' gradients.  ``test_zaya_cell.py`` runs them at ``--rehearse``'s sizes,
where the scales, ``gamma`` and the temperatures are moved off one, so that
the faults initialisation hides (``HIDDEN_AT_INITIALISATION``) read too.
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
from benchmark.families import sdar, zaya  # noqa: E402
from benchmark.references import zaya as reference_zaya  # noqa: E402

CELL = "zaya1-moe-ep2-s16384"
FAULTS = {
    "value_shift_left_out": "the second key/value head's values are the "
                            "token's own, not the token before's",
    "qk_mean_left_out": "q and k are the convolutions' output alone: the "
                        "mean of the plain latents is not added",
    "conv1_not_grouped_by_head": "the second convolution's groups are made "
                                 "of every tenth channel, not of a head's "
                                 "128",
    "rotary_on_the_whole_head": "all 128 channels of a head are rotated, "
                                "not the first 64",
    "l2_norm_left_out": "q and k go to the kernels as they are, not scaled "
                        "to norm sqrt(d)",
    "gate_renormalised": "the chosen expert's weight is divided by the sum "
                         "over the chosen: top-1, so it is 1",
    "bias_added_into_the_gate": "the gate is the biased score, p + bias",
    "state_not_handed_on": "a block's router does not add gamma x the state "
                           "of the block above",
    "residual_bias_outside_its_scale": "a r + c + b y + e where a (r + c) + "
                                       "b (y + e) stands",
    "gamma_left_out": "the state of the block above is added as it is, "
                      "without gamma",
    "temperature_left_out": "k is not multiplied by its head's temperature",
    "loss_on_the_token_itself": "row t's loss is of token t, not of token "
                                "t + 1: the labels are not shifted",
    "router_in_bfloat16": "the router's input, state, kernels and every "
                          "function's output rounded to bfloat16",
    "bf16_throughout": "every parameter and every function's output rounded "
                       "to bfloat16; gradients pass unrounded",
}
# A scale, a gamma or a temperature that is 1 and a bias that is 0 hide
# these at initialisation, whatever the size: the CPU tests hold them
# (tests/single/test_zaya.py compares model and reference with all of them
# moved; test_zaya_cell.py reads these faults on such weights).
HIDDEN_AT_INITIALISATION = ("residual_bias_outside_its_scale",
                            "gamma_left_out", "temperature_left_out")


@contextlib.contextmanager
def reference_with(**attributes):
    """The plain reference with some of its module's names replaced."""
    kept = {k: getattr(reference_zaya, k) for k in attributes}
    try:
        for k, v in attributes.items():
            setattr(reference_zaya, k, v)
        yield
    finally:
        for k, v in kept.items():
            setattr(reference_zaya, k, v)


def _rounding(dtype, names):
    import jax

    @jax.custom_jvp
    def rounded(x):
        return x.astype(dtype).astype(x.dtype)

    rounded.defjvp(lambda primals, tangents: (rounded(primals[0]),
                                              tangents[0]))

    def wrap(fn):
        def wrapped(*a, **kw):
            a = tuple(rounded(x) if hasattr(x, "astype")
                      and x.dtype.kind == "f" else x for x in a)
            out = fn(*a, **kw)
            return (tuple(rounded(o) if hasattr(o, "astype")
                          and o.dtype.kind == "f" else o for o in out)
                    if isinstance(out, tuple) else rounded(out))
        return wrapped

    return rounded, {k: wrap(getattr(reference_zaya, k)) for k in names}


def _fault(name: str):
    """``(replaced names of the reference, what to do to the parameters)``"""
    import jax
    import jax.numpy as jnp

    ref = reference_zaya
    same = lambda p: p  # noqa: E731
    if name == "value_shift_left_out":
        return {"values": lambda p, x: jnp.stack(
            [x @ p["v_proj"]["kernel"], x @ p["v_shift_proj"]["kernel"]],
            axis=1)}, same
    if name == "qk_mean_left_out":
        return {"qk_mean": lambda u, q0, k0: (u[:, :q0.shape[1]],
                                              u[:, q0.shape[1]:])}, same
    if name == "conv1_not_grouped_by_head":
        def strided(z, taps):
            seq, groups, d = z.shape
            mixed = z.reshape(seq, d, groups).transpose(0, 2, 1)
            n = taps.shape[0]
            out = sum(jnp.einsum("sgc,gcd->sgd", ref.before(mixed, n - 1 - j),
                                 taps[j]) for j in range(n))
            return out.transpose(0, 2, 1).reshape(seq, groups, d)
        return {"by_head": strided}, same
    if name == "rotary_on_the_whole_head":
        return {"rotated_width": lambda head_dim, cfg: head_dim}, same
    if name == "l2_norm_left_out":
        return {"unit": lambda x, to: x}, same
    if name == "gate_renormalised":
        def renormalised(probs, bias, chosen):
            w = jnp.take_along_axis(probs, chosen, axis=-1)
            return w / jnp.sum(w, axis=-1, keepdims=True)
        return {"gates": renormalised}, same
    if name == "bias_added_into_the_gate":
        return {"gates": lambda probs, bias, chosen: jnp.take_along_axis(
            probs + bias, chosen, axis=-1)}, same
    if name == "state_not_handed_on":
        return {"router_state": lambda p, x, s: ref.dense(p["down"], x)}, same
    if name == "residual_bias_outside_its_scale":
        return {"scaled_sum": lambda p, r, y: (
            p["a"] * r + p["c"] + p["b"] * y + p["e"])}, same
    if name == "gamma_left_out":
        return {"router_state": lambda p, x, s: (
            ref.dense(p["down"], x) + (s if "gamma" in p else 0.0))}, same
    if name == "temperature_left_out":
        sound = ref.cca
        return {"cca": lambda p, x, cfg: sound(
            {**p, "temp": jnp.ones_like(p["temp"])}, x, cfg)}, same
    if name == "loss_on_the_token_itself":
        return {"next_tokens": lambda ids: ids[:-1]}, same
    if name == "router_in_bfloat16":
        _, wrapped = _rounding(jnp.bfloat16, ("router_state", "router_probs",
                                              "dense", "gelu"))
        return wrapped, same
    assert name == "bf16_throughout", name
    rounded, wrapped = _rounding(jnp.bfloat16, (
        "rms_norm", "rotary", "mix", "attention", "cca", "router_state",
        "router_probs", "moe", "scaled_sum", "head"))
    return wrapped, lambda p: jax.tree_util.tree_map(rounded, p)


def readings(faults: list, variables, zcfg, ids, grads: bool = False,
             sequences: int = 1) -> dict:
    """``{fault: {measure: reading}}`` on the first sequence of ``ids``,
    each fault's reference against the sound one, both on the sound
    reference's own choices (what follows a flipped choice is not the
    fault's).  ``choices_differing`` is the fault's own choices against the
    sound one's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rcfg = zaya.reference_config(zcfg)
    row, balancing = ids[0], variables["balancing"]
    params = {"params": variables["params"]}
    length = row.shape[0]
    positions = zaya.sample_positions(length)
    own = np.asarray(row)[positions]

    def run_(prepare, chosen):
        def part(p):
            p = prepare(p)["params"]
            x, seen = reference_zaya.hidden(p, balancing, row, rcfg, chosen)
            loss = reference_zaya.loss_sum(p, x, row) / (
                sequences * (length - 1))
            return loss, (reference_zaya.head(p, x[positions]),
                          seen[1]["probs"],
                          jnp.stack([s["chosen"] for s in seen]))

        if not grads:
            return (*jax.jit(part)(params), None)
        (loss, aux), g = jax.jit(jax.value_and_grad(part, has_aux=True))(
            params)
        return loss, aux, common.leaf_paths(zaya._checked_tree(g, zcfg))

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
                "sample_logits": zaya.sample_error(f_logits, logits, own),
                "first_loss": common.rel_err(float(f_loss), float(loss)),
                "router_probs": common.rel_err(np.asarray(f_probs),
                                               np.asarray(probs)),
                "choices_differing": sdar.choices_differing(f_chose, chose)}
            if grads:
                out[name]["first_moment"] = {
                    path: sdar.moment_error(path, np.asarray(f_grads[path]),
                                            np.asarray(g))
                    for path, g in leaf_grads.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="+", default=[
        f for f in FAULTS if f not in HIDDEN_AT_INITIALISATION],
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
        cell = zaya.setup(cfg, mesh, seed, rehearse=args.rehearse)
        cell["batches"] = traffic_gen.make_batches(
            traffic, zaya.inputs(cell, traffic), mesh, seed)
        zaya.balance(cell)
        for fault in args.faults:
            got = readings([fault], common.first_shard(cell["params"]),
                           cell["zcfg"], cell["batches"][0][0],
                           grads=args.grads,
                           sequences=traffic["batch_per_chip"])
            print(json.dumps({"seed": seed, "cell": CELL,
                              "device": jax.devices()[0].device_kind,
                              "readings": got}), flush=True)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
