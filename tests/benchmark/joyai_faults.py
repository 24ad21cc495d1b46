"""The faults that the checks of ``benchmark/families/joyai.py`` are there to
catch: made in the plain reference and read in those checks' own measures
against the plain reference itself (what a limit must stay under;
``benchmark/testdata/check_readings/joyai.json`` keeps the readings), and
made in the program, for ``test_joyai_cell.py`` to run the timed path on.

    python tests/benchmark/joyai_faults.py --seeds 1 2 3

reads them at ``joyai-mla-ep16-s16384``'s own size on the machine it is
started on (a TPU) and prints one JSON line a seed and fault.  The faulty
reference stands where the system stands in a run: it makes its own choices
of experts, and the sound reference is read on those choices, as
``families/joyai.py:reference`` reads it on the system's.
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
from benchmark.families import joyai  # noqa: E402
from benchmark.families.sdar import choices_differing  # noqa: E402
from benchmark.references import joyai as reference_joyai  # noqa: E402

CELL = "joyai-mla-ep16-s16384"
FAULTS = {
    "scale_of_the_nope_width": "the scores are scaled by 128^-1/2, not "
                               "192^-1/2",
    "rotary_left_off_k_rope": "the shared rotary key goes to the scores as "
                              "the projection made it: no turn",
    "rotary_on_the_nope_lanes": "the 128 lanes without positions are turned "
                                "too, q's and k's",
    "kv_latent_norm_left_out": "W_kvb reads the key/value latent as W_kva "
                               "made it: no RMSNorm (its scale kept)",
    "softmax_for_sigmoid": "the router's scores are a softmax over the 256 "
                           "experts",
    "bias_in_the_weights": "the chosen experts are weighed by scores + bias",
    "renormalisation_left_out": "the chosen experts' scores are not divided "
                                "by their sum",
    "routed_scale_left_out": "the routed sum is added as it is, not times "
                             "2.5",
    "shared_expert_left_out": "the mixture is the routed sum alone",
    "router_in_bfloat16": "the router's product takes bfloat16 operands",
}


def _reference_fault(name: str) -> dict:
    """The names of ``references/joyai.py`` that make fault ``name``."""
    import jax
    import jax.numpy as jnp

    r = reference_joyai
    return {
        "scale_of_the_nope_width": {
            "score_scale": lambda cfg: cfg["qk_nope_head_dim"] ** -0.5},
        "rotary_left_off_k_rope": {
            "rope_of_the_key": lambda x, cfg: x[:, None, :]},
        "rotary_on_the_nope_lanes": {
            "rope_of_the_nope": lambda x, cfg: r.rotary(x,
                                                        cfg["rope_theta"])},
        "kv_latent_norm_left_out": {
            "kv_latent": lambda p, c, cfg: c * p["kv_a_norm"]},
        "softmax_for_sigmoid": {"router_scores": lambda p, x: jax.nn.softmax(
            x @ p["router"], axis=-1)},
        "bias_in_the_weights": {"weight_scores": r.choice_scores},
        "renormalisation_left_out": {
            "top_k_weights": lambda scores, chosen, renormalize:
                jnp.take_along_axis(scores, chosen, axis=-1)},
        "routed_scale_left_out": {"routed_scale": lambda cfg: 1.0},
        "shared_expert_left_out": {"shared": lambda p, x: jnp.zeros_like(x)},
        "router_in_bfloat16": {"router_scores": lambda p, x: jax.nn.sigmoid(
            jnp.dot(x.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32))},
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
    return _replaced(reference_joyai,
                     {} if fault == "sound" else _reference_fault(fault))


def _faulty_router(model, fault: str):
    """``JoyAIRouter`` with one of the router's four faults."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax import lax

    class Router(model.JoyAIRouter):
        @nn.compact
        def __call__(self, x):
            cfg = self.config
            kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                (x.shape[-1], cfg.num_experts))
            if fault == "router_in_bfloat16":
                logits = jnp.dot(x.astype(jnp.bfloat16),
                                 kernel.astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32)
            else:
                logits = jnp.dot(x.astype(jnp.float32), kernel,
                                 precision=lax.Precision.HIGHEST)
            scores = (jax.nn.softmax(logits, axis=-1)
                      if fault == "softmax_for_sigmoid"
                      else jax.nn.sigmoid(logits))
            bias = self.variable("balancing", "bias", jnp.zeros,
                                 (cfg.num_experts,), jnp.float32)
            if (self.is_mutable_collection("balancing")
                    and not self.is_initializing()):
                bias.value = model.balancing_bias(scores,
                                                  cfg.num_experts_per_tok)
            chosen = lax.top_k(lax.stop_gradient(scores) + bias.value,
                               cfg.num_experts_per_tok)[1]
            weights = jnp.take_along_axis(
                scores + bias.value if fault == "bias_in_the_weights"
                else scores, chosen, axis=-1)
            if fault != "renormalisation_left_out":
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            return scores, chosen, weights

    Router.__name__ = "JoyAIRouter"
    return Router


ROUTER_FAULTS = ("softmax_for_sigmoid", "bias_in_the_weights",
                 "renormalisation_left_out", "router_in_bfloat16")


@contextlib.contextmanager
def program_with(fault: str):
    """The program with fault ``fault`` in it: the model's own names
    replaced, for a whole run of the timed path."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import joyai as model
    from horovod_tpu.models.sdar import rotary

    config = model.JoyAIConfig
    sound_turn, sound_norm = model.rotary_lanes, model.RMSNorm

    def turned_nope(attend):
        def run(q, k, v, **kw):
            positions = jnp.arange(q.shape[1])
            turn = lambda t: rotary(  # noqa: E731
                t.astype(jnp.float32), positions,
                model.JOYAI_LLM_FLASH.rope_theta).astype(t.dtype)
            return attend(turn(q), turn(k), v, **kw)
        return run

    class NoKvNorm(sound_norm):
        """``RMSNorm`` whose ``kv_a_norm`` keeps its scale and divides by
        nothing."""
        @nn.compact
        def __call__(self, x, then=None):
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            x = x.astype(jnp.float32)
            if self.name != "kv_a_norm":
                x = x * jax.lax.rsqrt(
                    jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
            x = x * scale
            return (x if then is None else then(x)).astype(self.dtype)

    patches = {
        "scale_of_the_nope_width": [(config, {"qk_head_dim": property(
            lambda self: self.qk_nope_head_dim)})],
        "rotary_left_off_k_rope": [(model, {
            "rotary_lanes": lambda x, positions, theta, width: (
                x if x.shape[-1] == width
                else sound_turn(x, positions, theta, width))})],
        "rotary_on_the_nope_lanes": [(model, {
            "flash_attention": turned_nope(model.flash_attention),
            "dense_attention": turned_nope(model.dense_attention)})],
        "kv_latent_norm_left_out": [(model, {"RMSNorm": NoKvNorm})],
        "routed_scale_left_out": [(model, {
            "mixture_sum": lambda routed, shared, scale: (
                routed.astype(jnp.float32)
                + shared.astype(jnp.float32)).astype(shared.dtype)})],
        "shared_expert_left_out": [(model, {
            "mixture_sum": lambda routed, shared, scale: (
                scale * routed.astype(jnp.float32)).astype(shared.dtype)})],
        **{name: [(model, {"JoyAIRouter": _faulty_router(model, name)})]
           for name in ROUTER_FAULTS},
    }[fault]
    with contextlib.ExitStack() as stack:
        for module, names in patches:
            stack.enter_context(_replaced(module, names))
        yield


def _forward_and_grads(jcfg, rcfg, sequences: int, length: int):
    """``fn(variables, ids, chosen)`` of one sequence under whatever the
    reference's module holds when it is first called: the loss, the sample's
    logits, the first expert block's input, scores and weights of its own
    choices, every expert layer's choices, the first layer's two latents and
    its attention of the operands ``first_operands`` makes, and the checked
    leaves' gradients."""
    import jax
    import jax.numpy as jnp

    positions = joyai.sample_positions(length)
    first = joyai._sparse_layers(jcfg)[0]

    def part(p, rest, ids, chosen):
        tree = joyai.published({**rest, **p}, jcfg)
        x, seen = reference_joyai.hidden(tree, ids, rcfg, chosen)
        loss = reference_joyai.loss_sum(tree, x, ids) / (
            sequences * (length - 1))
        chose = jnp.stack([s["chosen"] if s is not None else jnp.zeros_like(
            seen[first]["chosen"]) for s in seen])
        return loss, (reference_joyai.head(tree, x[positions]),
                      seen[first]["routed"], seen[first]["scores"], chose)

    def fn(variables, ids, chosen):
        rest = {k: v for k, v in variables.items() if k != "params"}
        (loss, aux), grads = jax.value_and_grad(part, has_aux=True)(
            {"params": variables["params"]}, rest, ids, chosen)
        return loss, aux, joyai._checked_tree(grads, jcfg)

    return fn


def first_layer(tree, ids, jcfg, rcfg):
    """Under whatever the reference's module holds: the first layer's two
    latents, and its attention of the **sound** float32 operands (q and k a
    head's [nope | rope], v) under the module's scale."""
    r, p = reference_joyai, tree["layer_0"]
    a = p["attn"]
    h = r.rms_norm(tree["embed"][ids], p["input_norm"], rcfg["rms_norm_eps"])
    c_q = r.rms_norm(h @ a["q_a"], a["q_a_norm"], rcfg["rms_norm_eps"])
    c_kv = r.kv_latent(a, (h @ a["kv_a"])[:, :jcfg.kv_lora_rank], rcfg)
    q, k, v = (_unit_operands(ids.shape[0], width, i) for i, width in enumerate(
        (jcfg.qk_head_dim, jcfg.qk_head_dim, jcfg.v_head_dim)))
    return c_q, c_kv, r.attention(q, k, v, r.score_scale(rcfg))


def _unit_operands(seq: int, width: int, i: int, heads: int = 2):
    """Unit-normal operands [S, heads, width] for the attention read."""
    import jax

    return jax.random.normal(jax.random.key(100 + i), (seq, heads, width))


def readings(faults, variables, jcfg, ids) -> dict:
    """``{fault: {measure: value}}`` on the first sequence of ``ids`` [B, S]
    with the program's variables ``variables`` (``params`` and
    ``balancing``).  Each measure is its check's: the first loss (a); the
    sample's logits (b); the fault's router scores on the sound reference's
    input, its weights of its own choices against the sound weights of those,
    and the share of its choices the sound reference does not make (c); the
    checked leaves' first moments, the largest of the dense ones and the
    routed one (d); attention of the same unit-normal operands (f); the first
    layer's two latents (g)."""
    import jax
    import numpy as np

    rcfg = joyai.reference_config(jcfg)
    sequences, length = ids.shape
    row = ids[0]
    sparse = joyai._sparse_layers(jcfg)
    out = {}
    with jax.default_matmul_precision("highest"):
        sound = jax.jit(_forward_and_grads(jcfg, rcfg, sequences, length))
        layer = lambda: jax.jit(lambda v, i: first_layer(  # noqa: E731
            joyai.published(v, jcfg), i, jcfg, rcfg))(variables, row)
        sound_layer = layer()
        _, (_, routed, sound_scores, sound_chose), _ = sound(variables, row,
                                                             None)
        router = joyai.published(variables, jcfg)[f"layer_{sparse[0]}"]["moe"]
        for fault in faults:
            with reference_with(fault):
                faulty = jax.jit(_forward_and_grads(jcfg, rcfg, sequences,
                                                    length))
                loss, (logits, _, _, chose), grads = faulty(variables, row,
                                                            None)

                def routing(p, x):
                    scores = reference_joyai.router_scores(p, x)
                    picked = jax.lax.top_k(
                        reference_joyai.choice_scores(p, scores),
                        jcfg.num_experts_per_tok)[1]
                    return scores, picked, reference_joyai.top_k_weights(
                        reference_joyai.weight_scores(p, scores), picked,
                        jcfg.norm_topk_prob)

                scores, picked, weights = jax.jit(routing)(router, routed)
                faulty_layer = layer()
            want_weights = jax.jit(
                lambda s, c: reference_joyai.top_k_weights(
                    s, c, jcfg.norm_topk_prob))(sound_scores, picked)
            # The sound reference on the fault's choices, as a run reads it.
            want_loss, (want_logits, _, _, _), want = sound(variables, row,
                                                            chose)
            moments = {
                k: joyai.moment_error(k, np.asarray(g), np.asarray(
                    common.leaf_paths(want)[k]))
                for k, g in common.leaf_paths(grads).items()}
            routed_leaf = [k for k in moments if k.endswith("['w_down']")]
            out[fault] = {
                "first_loss": common.rel_err(float(loss), float(want_loss)),
                "sample_logits": common.l2_rel_err(logits, want_logits),
                "router_scores": common.rel_err(np.asarray(scores),
                                                np.asarray(sound_scores)),
                "router_weights": common.rel_err(np.asarray(weights),
                                                 np.asarray(want_weights)),
                "choices_differing": choices_differing(
                    np.asarray(chose)[sparse], np.asarray(sound_chose)[sparse]),
                "first_attention": common.l2_rel_err(faulty_layer[2],
                                                     sound_layer[2]),
                "first_latents": max(
                    common.l2_rel_err(faulty_layer[i], sound_layer[i])
                    for i in (0, 1)),
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
        cell = joyai.setup(cfg, mesh, seed, rehearse=args.rehearse)
        cell["batches"] = traffic_gen.make_batches(
            traffic, joyai.inputs(cell, traffic), mesh, seed)
        joyai.balance(cell)
        got = readings(args.faults, common.first_shard(cell["params"]),
                       cell["jcfg"], cell["batches"][0][0])
        for fault, read in got.items():
            print(json.dumps({"seed": seed, "fault": fault,
                              "device": jax.devices()[0].device_kind,
                              **read}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
