"""The faults that the checks of ``benchmark/families/phi4flash.py`` are there
to catch: made in the plain reference and read in those checks' own measures
against the plain reference itself (what a limit must stay under;
``benchmark/testdata/check_readings/phi4flash.json`` keeps the readings), and
some of them made in the program, for ``test_phi4flash_cell.py`` to run the
timed path on.

    python tests/benchmark/phi4flash_faults.py --seeds 1 2

reads them at ``phi4flash-sambay-tp2-s16384``'s own size on the machine it is
started on (a TPU) and prints one JSON line a seed and fault: each measure,
and what the family's limits make of them (``refused_by``, ``correct``: the
fault's numbers put in the program's place).  ``--lambda-look`` prints, a seed
and attention layer, the scalar d loss / d lambda beside the sum of its
terms' sizes (the plain reference in float32); ``--program-fault NAME`` runs
the whole cell through ``benchmark/run.py`` with fault NAME planted in the
program (``PROGRAM_FAULTS``).
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
from benchmark.families import phi4flash as family  # noqa: E402
from benchmark.references import phi4flash as reference_phi  # noqa: E402

CELL = "phi4flash-sambay-tp2-s16384"
FAULTS = {
    "loss_on_the_token_itself": "row t's loss is of token t, not of token "
                                "t + 1: the labels are not shifted",
    "lambda_init_of_the_cuts_index": "lambda_init takes the layer's index in "
                                     "the cut (0 ..), not the published one",
    "lambda_left_out": "A1 - A2: lambda is 1",
    "lambda_fixed_at_its_start": "lambda = lambda_init: the four vectors do "
                                 "nothing and get no gradient",
    "stop_gradient_on_the_lambda_vectors": "the forward as it is; the four "
                                           "vectors of every attention layer "
                                           "get no gradient",
    "output_scale_left_out": "the (1 - lambda_init) scale is left out",
    "pair_norm_left_out": "A1 - lambda A2 keeps the norm's scale and is "
                          "divided by nothing",
    "pair_norm_a_head_at_a_time": "the norm is over each head's 64, not over "
                                  "the pair's 128",
    "second_map_dropped": "plain attention: A1 alone",
    "pairs_by_halves": "head p pairs with head p + H / 2, not with its "
                       "neighbour",
    "band_of_511": "a banded query sees 511 keys",
    "band_of_513": "a banded query sees 513 keys",
    "no_band_on_the_banded_layers": "the banded layers see every causal key",
    "a_band_on_the_full_layer": "the layer whose k, v are handed down sees "
                                "512 keys",
    "cross_layer_on_its_own_keys": "the cross layer applies the full layer's "
                                   "W_k, W_v to its own input",
    "stop_gradient_on_the_carried_kv": "the cross layers' gradient does not "
                                       "reach the full layer's k and v",
    "stop_gradient_on_the_carried_memory": "the gate layers' gradient does "
                                           "not reach the memory layer",
    "memory_after_the_gate": "M = y * silu(z), not y",
    "gate_on_the_memory_side": "silu(M) * W_1 u, not M * silu(W_1 u)",
    "gate_layer_recomputes_the_scan": "a gate layer reads the memory layer's "
                                      "mixer run on its own input",
    "jamba_norms_left_on": "dt, B and C are RMS-normed (Jamba's three)",
    "rms_norm_for_layer_norm": "no mean subtracted and no bias",
    "a_bias_s_gradient_lost": "the projections' biases get no gradient",
    "rotary_on_q_and_k": "q and k are turned by their positions",
    "difference_in_bfloat16": "both maps rounded to bfloat16, the "
                              "subtraction, the pair norm and the scale in "
                              "bfloat16 end to end",
    "reference_in_bfloat16": "the precision below the stated one: the plain "
                             "reference with bfloat16 wherever the "
                             "configuration states float32 (parameters and "
                             "gradients, LayerNorm, the scan's state and "
                             "steps, softmax, the subtraction and the pair "
                             "norm, the gates, residual sums, logits, loss)",
}
# The fault that is no replaced name of the reference but the reference as it
# is, on bfloat16 parameters: every array after them is bfloat16.
LOW_PRECISION = "reference_in_bfloat16"
ROPE_THETA = 10000.0


def _reference_fault(name: str, pcfg) -> dict:
    """The names of ``references/phi4flash.py`` that make fault ``name``."""
    import jax
    import jax.numpy as jnp

    r = reference_phi
    rcfg = family.reference_config(pcfg)
    half = r.half_of(rcfg)
    sound_window, sound_rms, sound_lambda = r.window_of, r.rms_norm, r.lambda_of
    low = lambda x: jnp.asarray(x, jnp.bfloat16)  # noqa: E731
    wide = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731

    def window_plus(more):
        def window_of(kind, cfg):
            window = sound_window(kind, cfg)
            return None if window is None else window + more
        return window_of

    def rotary(q, k):
        def turn(x):
            seq, _, d = x.shape
            inv = ROPE_THETA ** (-jnp.arange(0, d, 2) / d)
            angle = jnp.arange(seq)[:, None] * inv[None]
            cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
            a, b = x[..., :d // 2], x[..., d // 2:]
            return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
        return turn(q), turn(k)

    def head_norm(x, scale, eps):
        heads = x.reshape(*x.shape[:-1], 2, -1)
        return sound_rms(heads, scale.reshape(2, -1), eps).reshape(x.shape)

    def low_norm(x, scale, eps):
        x = low(x)
        mean = jnp.mean(x * x, axis=-1, keepdims=True, dtype=jnp.bfloat16)
        return wide(x * jax.lax.rsqrt(mean + low(eps)) * low(scale))

    return {
        "loss_on_the_token_itself": {"next_tokens": lambda ids: ids[:-1]},
        "lambda_init_of_the_cuts_index": {
            "lambda_index": lambda layer, cfg: layer - pcfg.first_layer},
        "lambda_left_out": {"lambda_of": lambda p, start: 1.0},
        "lambda_fixed_at_its_start": {"lambda_of": lambda p, start: start},
        "stop_gradient_on_the_lambda_vectors": {
            "lambda_of": lambda p, start: jax.lax.stop_gradient(
                sound_lambda(p, start))},
        "output_scale_left_out": {"output_scale": lambda start: 1.0},
        "pair_norm_left_out": {"pair_norm": lambda x, scale, eps: x * scale},
        "pair_norm_a_head_at_a_time": {"pair_norm": head_norm},
        "second_map_dropped": {"difference_of": lambda a1, a2, lam: a1},
        "pairs_by_halves": {"pairs_of": lambda x: (
            x[:, :x.shape[1] // 2], x[:, x.shape[1] // 2:])},
        "band_of_511": {"window_of": window_plus(-1)},
        "band_of_513": {"window_of": window_plus(1)},
        "no_band_on_the_banded_layers": {"window_of": lambda kind, cfg: None},
        "a_band_on_the_full_layer": {"window_of": lambda kind, cfg: (
            cfg["sliding_window"] if kind in ("banded", "full+kv") else None)},
        "cross_layer_on_its_own_keys": {
            "read_kv": lambda kv, h, params: r.keys_and_values(
                params[f"layer_{half + 1}"]["attn"], h)},
        "stop_gradient_on_the_carried_kv": {
            "carried_kv": jax.lax.stop_gradient},
        "stop_gradient_on_the_carried_memory": {
            "carried_memory": jax.lax.stop_gradient},
        "memory_after_the_gate": {"memory_of": lambda y, z: y * r.silu(z)},
        "gate_on_the_memory_side": {"gate_on": lambda m, g: r.silu(m) * g},
        "gate_layer_recomputes_the_scan": {
            "read_memory": lambda memory, h, params: r.mamba(
                params[f"layer_{half}"]["mamba"], h, rcfg)[1]},
        "jamba_norms_left_on": {"normed_dt_b_c": lambda p, dt, b, c, cfg: tuple(
            sound_rms(x, 1.0, 1e-6) for x in (dt, b, c))},
        "rms_norm_for_layer_norm": {
            "layer_norm": lambda x, p, eps: sound_rms(x, p["scale"], eps)},
        "a_bias_s_gradient_lost": {"bias_of": jax.lax.stop_gradient},
        "rotary_on_q_and_k": {"positioned": rotary},
        "difference_in_bfloat16": {
            "difference_of": lambda a1, a2, lam: wide(
                low(a1) - low(lam) * low(a2)),
            "pair_norm": low_norm,
            "output_scale": lambda start: wide(low(1.0 - start))},
        LOW_PRECISION: {},
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


def reference_with(fault: str, pcfg):
    """The plain reference with fault ``fault`` in it ("sound": as it is)."""
    return _replaced(reference_phi, {} if fault == "sound"
                     else _reference_fault(fault, pcfg))


# The faults test_phi4flash_cell.py makes in the program.
PROGRAM_FAULTS = ("second_map_dropped", "lambda_init_of_the_cuts_index",
                  "stop_gradient_on_the_lambda_vectors",
                  "stop_gradient_on_the_carried_kv",
                  "stop_gradient_on_the_carried_memory")


@contextlib.contextmanager
def program_with(fault: str):
    """The program with fault ``fault`` in it: the model's own names
    replaced, for a whole run of the timed path at the rehearsal's sizes."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import phi4flash as model

    sound_maps, sound_block = model.two_maps, model.Phi4FlashBlock.__call__
    sound_start, sound_lambda = model.lambda_init, model.lambda_of

    def second_map_dropped(*args):
        a1, a2 = sound_maps(*args)
        return a1, jnp.zeros_like(a2)

    def carried(stop_memory: bool, stop_kv: bool):
        @nn.compact
        def call(self, x, memory=None, kv=None):
            kind = self.config.kind(self.layer)
            if stop_memory and kind == model.GMU:
                memory = jax.lax.stop_gradient(memory)
            if stop_kv and kind == model.CROSS:
                kv = jax.lax.stop_gradient(kv)
            return sound_block(self, x, memory, kv)
        return call

    patches = {
        "second_map_dropped": (model, {"two_maps": second_map_dropped}),
        "stop_gradient_on_the_lambda_vectors": (model, {
            "lambda_of": lambda *args: jax.lax.stop_gradient(
                sound_lambda(*args))}),
        # The rehearsal's cut starts at published layer 1.
        "lambda_init_of_the_cuts_index": (model, {
            "lambda_init": lambda layer: sound_start(layer - 1)}),
        "stop_gradient_on_the_carried_kv": (model.Phi4FlashBlock, {
            "__call__": carried(False, True)}),
        "stop_gradient_on_the_carried_memory": (model.Phi4FlashBlock, {
            "__call__": carried(True, False)}),
    }[fault]
    with _replaced(*patches):
        yield


def _forward_and_grads(pcfg, rcfg, sequences: int, length: int):
    """``fn(variables, ids)`` of one sequence under whatever the reference's
    module holds when it is first called: the loss, the sample's logits and
    the checked leaves' gradients."""
    import jax

    positions = family.sample_positions(length)

    def part(p, ids):
        x = reference_phi.hidden(p["params"], ids, rcfg)
        loss = reference_phi.loss_sum(p["params"], x, ids) / (
            sequences * (length - 1))
        return loss, reference_phi.head(p["params"], x[positions])

    def fn(variables, ids):
        p = {"params": variables["params"]}
        (loss, logits), grads = jax.value_and_grad(part, has_aux=True)(p, ids)
        return loss, logits, family._checked_tree(grads, pcfg)

    return fn


def _first_attention_inputs(pcfg, rcfg):
    """``fn(params, ids) -> (q, k, v)`` [S, heads, d] of the first attention
    layer run, by the sound reference."""
    from horovod_tpu.models import phi4flash as model

    layer = family._first(pcfg, model.BANDED, model.FULL)

    def fn(params, ids):
        x = params["embed"]["embedding"][ids]
        memory = kv = None
        for i in range(pcfg.first_layer, layer):
            x, memory, kv = reference_phi.block(
                reference_phi.blocks_of(params), x, memory, kv, i, rcfg)
        p = params[f"layer_{layer}"]
        h = reference_phi.layer_norm(x, p["input_norm"],
                                     rcfg["layer_norm_eps"])
        return (reference_phi.projected(p["attn"], h, "q_proj"),
                *reference_phi.keys_and_values(p["attn"], h))

    return layer, fn


def _difference(layer: int, rcfg):
    """``fn(attention's params, q, k, v)``: ``A1 - lambda A2`` of layer
    ``layer`` under whatever the reference's module holds."""
    def fn(p, q, k, v):
        kind = reference_phi.kind_of(layer, rcfg)
        a1, a2 = reference_phi.two_maps(q, k, v, reference_phi.window_of(
            kind, rcfg))
        return reference_phi.difference_of(a1, a2, reference_phi.lambda_of(
            p, reference_phi.lambda_init(layer, rcfg)))
    return fn


def _is_lambda(path: str) -> bool:
    return any(name in path for name in family.LAMBDA_LEAVES)


def _lowered(tree):
    """``tree``'s float arrays in bfloat16."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


# Which limit of the family holds which measure of ``readings``.
LIMITS = {"first_loss": "TOL_FIRST_LOSS", "sample_logits": "TOL_SAMPLE_LOGITS",
          "first_difference": "TOL_DIFFERENCE",
          "first_moment": "TOL_FIRST_MOMENT",
          "cancelling_moment": "TOL_CANCELLING_MOMENT",
          "lambda_vectors": "TOL_LAMBDA_MOMENT"}


def verdict(read: dict) -> dict:
    """What ``correct`` makes of a fault's numbers put in the program's
    place: each measure through ``common.check`` under the family's limit
    (``first_moment`` and ``cancelling_moment`` are their largest leaf's, so
    each fails where any leaf's check would)."""
    checks = [common.check(measure, read[measure], getattr(family, limit))
              for measure, limit in LIMITS.items()]
    return {"refused_by": {c["name"]: round(c["value"] / c["tol"], 3)
                           for c in checks if not c["ok"]},
            "correct": all(c["ok"] for c in checks)}


def readings(faults, variables, pcfg, ids, each=None) -> dict:
    """``{fault: {measure: value}}`` on the first sequence of ``ids`` [B, S]
    with the program's variables.  Each measure is its check's: the first
    loss (a); the sample's logits (b); the first attention layer's
    difference of its maps of the sound reference's q, k and v (c); the
    checked leaves' first moments, each, and the largest of (d)'s and of
    (d')'s; the lambda vectors' as one vector (e); and ``verdict`` of
    them.  ``each(fault,
    read)`` is called as a fault is read (a run cut short keeps what it
    had)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rcfg = family.reference_config(pcfg)
    sequences, length = ids.shape
    row = ids[0]
    params = variables["params"]
    out = {}
    with jax.default_matmul_precision("highest"):
        want_loss, want_logits, want = jax.jit(_forward_and_grads(
            pcfg, rcfg, sequences, length))(variables, row)
        want = {k: np.asarray(v) for k, v in common.leaf_paths(want).items()}
        layer, inputs = _first_attention_inputs(pcfg, rcfg)
        qkv = jax.jit(inputs)(params, row)
        attn = params[f"layer_{layer}"]["attn"]
        want_difference = jax.jit(_difference(layer, rcfg))(attn, *qkv)
        for fault in faults:
            to = _lowered if fault == LOW_PRECISION else (lambda tree: tree)
            with reference_with(fault, pcfg):
                loss, logits, grads = jax.jit(_forward_and_grads(
                    pcfg, rcfg, sequences, length))(to(variables), row)
                difference = jax.jit(_difference(layer, rcfg))(
                    to(attn), *to(qkv))
            got = {k: np.asarray(g.astype(jnp.float32))
                   for k, g in common.leaf_paths(grads).items()}
            by_leaf = {k: common.l2_rel_err(g, want[k])
                       for k, g in got.items() if not _is_lambda(k)}
            sums = {k for k in by_leaf if family.cancels(k, pcfg)}
            finite = bool(np.isfinite(float(loss)))
            out[fault] = {
                "first_loss": common.rel_err(float(loss), float(want_loss))
                if finite else 1e30,
                "sample_logits": common.l2_rel_err(
                    logits.astype(jnp.float32), want_logits),
                "first_difference": common.l2_rel_err(
                    difference.astype(jnp.float32), want_difference),
                "first_moment": max(v for k, v in by_leaf.items()
                                    if k not in sums),
                "cancelling_moment": max(by_leaf[k] for k in sums),
                "lambda_vectors": common.l2_rel_err(*(
                    np.concatenate([tree[k].ravel() for k in sorted(got)
                                    if _is_lambda(k)])
                    for tree in (got, want))),
                "first_moments": by_leaf}
            out[fault].update(verdict(out[fault]))
            if each is not None:
                each(fault, out[fault])
            del grads
    return out


def lambda_look(variables, pcfg, ids) -> dict:
    """``{layer: {...}}`` of every attention layer, by the plain reference in
    float32 on the first sequence of ``ids``: ``scalar`` = d loss / d lambda,
    the one number a layer's four vectors' gradients carry; ``sum_of_sizes``
    = the sum of its ``S x pairs x 2 d`` terms' absolute values (a term is
    the difference's cotangent times ``-A2``, an element); ``root_sum_of_
    squares`` of them, which times 2^-9 is what terms rounded to bfloat16
    independently would leave of noise on the scalar."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import phi4flash as model

    rcfg = family.reference_config(pcfg)
    params = variables["params"]
    sequences, length = ids.shape
    layers = [i for i, kind in zip(pcfg.layers, pcfg.layer_kinds)
              if kind in (model.BANDED, model.FULL, model.CROSS)]
    shape = (length, pcfg.heads_held // 2, 2 * pcfg.head_dim)
    sound = reference_phi.lambda_of

    def loss(probes, params, row):
        probed = dict(params)
        for i in layers:
            block = params[f"layer_{i}"]
            probed[f"layer_{i}"] = {**block, "attn": {
                **block["attn"], "lambda_probe": probes[str(i)]}}
        x = reference_phi.hidden(probed, row, rcfg)
        return reference_phi.loss_sum(probed, x, row) / (
            sequences * (length - 1))

    def look(params, row):
        terms = jax.grad(loss)({str(i): jnp.zeros(shape) for i in layers},
                               params, row)
        return {i: {"scalar": jnp.sum(t), "sum_of_sizes": jnp.sum(jnp.abs(t)),
                    "root_sum_of_squares": jnp.sqrt(jnp.sum(t * t))}
                for i, t in terms.items()}

    # lambda + an array of zeros, an element a term: the gradient of the
    # zeros is the terms.
    with _replaced(reference_phi, {"lambda_of": lambda p, start: (
            sound(p, start) + p["lambda_probe"])}), \
            jax.default_matmul_precision("highest"):
        out = jax.device_get(jax.jit(look)(params, ids[0]))
    return {f"layer_{i}": {
        **{k: float(v) for k, v in read.items()},
        "scalar_over_sum_of_sizes": abs(float(read["scalar"])) / float(
            read["sum_of_sizes"]),
        "bfloat16_noise_over_scalar": 2.0 ** -9 * float(
            read["root_sum_of_squares"]) / abs(float(read["scalar"]))}
        for i, read in out.items()}


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="+", default=list(FAULTS))
    ap.add_argument("--lambda-look", action="store_true")
    ap.add_argument("--program-fault", choices=PROGRAM_FAULTS)
    ap.add_argument("--seconds", default="5")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.program_fault:
        with program_with(args.program_fault):
            return max(run.main(
                ["--workload", CELL, "--seed", str(seed), "--seconds",
                 args.seconds, "--trace", "0",
                 *(["--rehearse"] if args.rehearse else [])])
                for seed in args.seeds)
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    mesh = common.hvd_mesh(jax.devices()[:1])
    for seed in args.seeds:
        cell = family.setup(cfg, mesh, seed, rehearse=args.rehearse)
        cell["batches"] = traffic_gen.make_batches(
            traffic, family.inputs(cell, traffic), mesh, seed)
        if args.lambda_look:
            print(json.dumps({
                "seed": seed, "look": "lambda",
                "device": jax.devices()[0].device_kind,
                "layers": lambda_look(
                    common.first_shard(cell["params"]), cell["pcfg"],
                    cell["batches"][0][0])}), flush=True)
            continue
        readings(args.faults, common.first_shard(cell["params"]),
                 cell["pcfg"], cell["batches"][0][0],
                 each=lambda fault, read, seed=seed: print(json.dumps({
                     "seed": seed, "fault": fault,
                     "device": jax.devices()[0].device_kind, **read}),
                     flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
