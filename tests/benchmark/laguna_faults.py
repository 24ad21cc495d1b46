"""The faults that the checks of ``benchmark/families/laguna.py`` are there to
catch: made in the plain reference and read in those checks' own measures
against the plain reference itself (what a limit must stay under;
``benchmark/testdata/check_readings/laguna.json`` keeps the readings), and
made in the program, for ``test_laguna_cell.py`` to run the timed path on.

    python tests/benchmark/laguna_faults.py --seeds 1 2 3

reads them at ``laguna-swa-ep32-s16384``'s own size on the machine it is
started on (a TPU) and prints one JSON line a seed and fault.  The faulty
reference stands where the system stands in a run: it makes its own choices
of experts, and the sound reference is read on those choices, as
``families/laguna.py:reference`` reads it on the system's.
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
from benchmark.families import laguna  # noqa: E402
from benchmark.families.sdar import choices_differing, moment_error  # noqa: E402
from benchmark.references import laguna as reference_laguna  # noqa: E402

CELL = "laguna-swa-ep32-s16384"
FAULTS = {
    "sliding_layer_run_global": "a sliding layer sees every key before it: "
                                "no window",
    "window_one_key_short": "a sliding layer's query sees 511 keys, not 512",
    "gate_left_out": "a head's output goes to W_o as attention made it: no "
                     "gate",
    "shared_expert_left_out": "the mixture is the routed sum alone",
    "routed_scale_left_out": "the routed sum is added as it is, not times "
                             "2.5",
    "plain_rotary_on_a_full_layer": "a full layer turns its whole head at "
                                    "theta 5e5: no YaRN, no partial factor, "
                                    "no attention_factor",
    "router_in_bfloat16": "the router's product takes bfloat16 operands",
    "final_norm_left_out": "the head reads the last block's output as it "
                           "is: no final RMSNorm",
}


def _reference_fault(name: str) -> dict:
    """The names of ``references/laguna.py`` that make fault ``name``."""
    import jax
    import jax.numpy as jnp

    r = reference_laguna
    return {
        "sliding_layer_run_global": {"window_of": lambda kind, cfg: None},
        "window_one_key_short": {"window_of": lambda kind, cfg: (
            cfg["sliding_window"] - 1 if kind == r.SLIDING else None)},
        "gate_left_out": {"head_gate": lambda p, h: jnp.ones(
            (h.shape[0], p["gate_proj"].shape[1]), h.dtype)},
        "shared_expert_left_out": {"shared": lambda p, x: jnp.zeros_like(x)},
        "routed_scale_left_out": {"routed_scale": lambda cfg: 1.0},
        "plain_rotary_on_a_full_layer": {"rope_of": lambda kind, cfg: (
            cfg["rope_parameters"][kind] if kind == r.SLIDING else {
                "rope_theta": cfg["rope_parameters"][kind]["rope_theta"],
                "rope_type": "default", "partial_rotary_factor": 1.0})},
        "router_in_bfloat16": {"router_probs": lambda p, x: jax.nn.softmax(
            jnp.dot(x.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32), axis=-1)},
        "final_norm_left_out": {"final_norm": lambda params, x, cfg: x},
    }[name]


@contextlib.contextmanager
def _replaced(module, names: dict):
    kept = {k: getattr(module, k) for k in names}
    for k, v in names.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in kept.items():
            setattr(module, k, v)


def reference_with(fault: str):
    """The plain reference with fault ``fault`` in it ("sound": as it is)."""
    return _replaced(reference_laguna,
                     {} if fault == "sound" else _reference_fault(fault))


@contextlib.contextmanager
def program_with(fault: str):
    """The program with fault ``fault`` in it: the model's and the expert
    layer's own functions replaced, for a whole run of the timed path."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import laguna as model
    from horovod_tpu.parallel import moe

    config = model.LagunaConfig

    def route_in_bfloat16(x, router_kernel, top_k, first_expert, held,
                          renormalize=True):
        probs = jax.nn.softmax(jnp.dot(
            x.astype(jnp.bfloat16), router_kernel.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32), axis=-1)
        weights, experts = jax.lax.top_k(probs, top_k)
        if renormalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return moe.Routing(probs, experts, weights,
                           moe.expert_load(experts, first_expert, held))

    sound_window, sound_rope = config.window, config.rope
    patches = {
        "sliding_layer_run_global": [(config, {
            "window": lambda self, layer: None})],
        "window_one_key_short": [(config, {"window": lambda self, layer: (
            None if sound_window(self, layer) is None
            else sound_window(self, layer) - 1)})],
        "gate_left_out": [(model, {
            "gate_heads": lambda ctx, gate, head_dim: ctx})],
        "shared_expert_left_out": [(model, {
            "mixture_sum": lambda routed, shared, scale: (
                scale * routed.astype(jnp.float32)).astype(shared.dtype)})],
        "routed_scale_left_out": [(model, {
            "mixture_sum": lambda routed, shared, scale: (
                routed.astype(jnp.float32)
                + shared.astype(jnp.float32)).astype(shared.dtype)})],
        "plain_rotary_on_a_full_layer": [(config, {
            "rope": lambda self, layer: (
                sound_rope(self, layer) if self.window(layer) is not None
                else model.RopeParameters(
                    rope_theta=self.rope_full.rope_theta))})],
        "router_in_bfloat16": [(moe, {"route": route_in_bfloat16})],
    }[fault]
    with contextlib.ExitStack() as stack:
        for module, names in patches:
            stack.enter_context(_replaced(module, names))
        yield


PROGRAM_FAULTS = tuple(k for k in FAULTS if k != "final_norm_left_out")


def _forward_and_grads(lcfg, rcfg, sequences: int, length: int):
    """``fn(params, ids, chosen)`` of one sequence under whatever the
    reference's module holds when it is first called: the loss, the
    sample's logits, the first sparse block's input and probabilities, every
    sparse layer's choices, the first sliding layer's attention of the
    operands ``first_sliding_operands`` makes, and the checked leaves'
    gradients."""
    import jax
    import jax.numpy as jnp

    positions = laguna.sample_positions(length)
    at = laguna._layers_of_kind(lcfg)

    def part(p, ids, chosen):
        x, seen = reference_laguna.hidden(p["params"], ids, rcfg, chosen)
        loss = reference_laguna.loss_sum(p["params"], x, ids) / (
            sequences * (length - 1))
        chose = jnp.stack([s["chosen"] if s is not None else jnp.zeros_like(
            seen[at["sparse"]]["chosen"]) for s in seen])
        return loss, (reference_laguna.head(p["params"], x[positions]),
                      seen[at["sparse"]]["routed"],
                      seen[at["sparse"]]["probs"], chose)

    def fn(p, ids, chosen):
        (loss, aux), grads = jax.value_and_grad(part, has_aux=True)(
            p, ids, chosen)
        return loss, aux, laguna._checked_tree(grads, lcfg)

    return fn


def first_sliding_operands(params, ids, lcfg, rcfg):
    """The sound reference's q, k and v [S, H, D] of the first sliding
    layer, after the rotary turn."""
    from horovod_tpu.models import laguna as model

    r = reference_laguna
    at = laguna._layers_of_kind(lcfg)["sliding"]
    x = params["embed"]["embedding"][ids]
    for i in range(at):
        x, _ = r.block(params[f"layer_{i}"], x, rcfg["layer_types"][i], rcfg)
    p = params[f"layer_{at}"]
    h = r.rms_norm(x, p["input_norm"]["scale"], rcfg["rms_norm_eps"])
    d = rcfg["head_dim"]
    q, k, v = ((h @ p["attn"][name]["kernel"]).reshape(h.shape[0], -1, d)
               for name in ("q_proj", "k_proj", "v_proj"))
    rope = r.rope_of(model.SLIDING, rcfg)
    return r.rotary(q, rope), r.rotary(k, rope), v


def readings(faults, params, lcfg, ids) -> dict:
    """``{fault: {measure: value}}`` on the first sequence of ``ids`` [B, S]
    with the reference's weights ``params`` (``tree["params"]``).  Each
    measure is its check's: the first loss (a); the sample's logits (b); the
    fault's router on the sound reference's input and the share of its
    choices the sound reference does not make (c); the checked leaves' first
    moments, the largest of the dense ones and the routed one (d); the first
    sliding layer's attention of the sound operands (f)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import laguna as model

    rcfg = laguna.reference_config(lcfg)
    sequences, length = ids.shape
    row, tree = ids[0], {"params": params}
    out = {}
    with jax.default_matmul_precision("highest"):
        sound = jax.jit(_forward_and_grads(lcfg, rcfg, sequences, length))
        operands = jax.jit(lambda p, i: first_sliding_operands(
            p, i, lcfg, rcfg))(params, row)
        attend = lambda: jax.jit(lambda q, k, v: reference_laguna.attention(  # noqa: E731
            q, k, v, reference_laguna.window_of(model.SLIDING, rcfg)))(
                *operands)
        sound_attention = attend()
        _, (_, routed, sound_probs, sound_chose), _ = sound(tree, row, None)
        for fault in faults:
            with reference_with(fault):
                faulty = jax.jit(_forward_and_grads(lcfg, rcfg, sequences,
                                                    length))
                loss, (logits, _, _, chose), grads = faulty(tree, row, None)
                probs = jax.jit(reference_laguna.router_probs)(
                    params[f"layer_{laguna._layers_of_kind(lcfg)['sparse']}"][
                        "moe"], routed)
                attention = attend()
            # The sound reference on the fault's choices, as a run reads it.
            want_loss, (want_logits, _, _, _), want = sound(tree, row, chose)
            moments = {
                k: moment_error(k, np.asarray(g), np.asarray(
                    common.leaf_paths(want)[k]))
                for k, g in common.leaf_paths(grads).items()}
            routed_leaf = [k for k in moments if k.endswith("['w_down']")]
            sparse = laguna._sparse_layers(lcfg)
            out[fault] = {
                "first_loss": common.rel_err(float(loss), float(want_loss)),
                "sample_logits": common.l2_rel_err(logits, want_logits),
                "router_probs": common.rel_err(np.asarray(probs),
                                               np.asarray(sound_probs)),
                "choices_differing": choices_differing(
                    np.asarray(chose)[sparse], np.asarray(sound_chose)[sparse]),
                "sliding_attention": common.l2_rel_err(attention,
                                                       sound_attention),
                "first_moment": max(v for k, v in moments.items()
                                    if k not in routed_leaf),
                "first_moment_routed": max(moments[k] for k in routed_leaf),
                "first_moments": moments}
            del faulty, grads, want
    return out


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="+", default=list(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    mesh = common.hvd_mesh(jax.devices()[:1])
    for seed in args.seeds:
        cell = laguna.setup(cfg, mesh, seed, rehearse=args.rehearse)
        ids = traffic_gen.make_batches(
            traffic, laguna.inputs(cell, traffic), mesh, seed)[0][0]
        got = readings(args.faults, common.first_shard(
            cell["params"])["params"], cell["lcfg"], ids)
        for fault, read in got.items():
            print(json.dumps({"seed": seed, "fault": fault,
                              "device": jax.devices()[0].device_kind,
                              **read}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
