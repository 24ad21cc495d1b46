"""The faults that the checks of ``benchmark/families/jamba.py`` are there to
catch, made in the plain reference and read in those checks' own measures
against the plain reference itself: what a limit must stay under
(``benchmark/testdata/check_readings/jamba.json`` keeps the readings).

    python tests/benchmark/jamba_faults.py --seeds 1 2 3 [--grads]
    python tests/benchmark/jamba_faults.py --seeds 1 2 3 --program

reads them at ``jamba2-ssm-tp4-s16384``'s own size on the machine it is
started on (a TPU) and prints one JSON line a seed and fault: the sample's
logits, the first sequence's loss, the first block's scan; with ``--grads``
also the named leaves' gradients.  ``test_jamba_cell.py`` runs them at
``--rehearse``'s sizes on weights with every leaf that starts at a one, a
zero or a constant moved, so that the faults initialisation hides
(``HIDDEN_AT_INITIALISATION``) read too.  A reading that is not finite (a
state that grows without bound) is printed as ``NOT_FINITE``.
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
from benchmark.families import jamba  # noqa: E402
from benchmark.references import jamba as reference_jamba  # noqa: E402

CELL = "jamba2-ssm-tp4-s16384"
NOT_FINITE = 1e30
FAULTS = {
    "state_in_bfloat16": "the scan's state is rounded to bfloat16 after "
                         "every step",
    "dt_in_bfloat16": "the step dt is rounded to bfloat16 after its "
                      "softplus",
    "a_without_its_sign": "A = exp(A_log): the state grows",
    "a_without_its_exp": "A = -A_log",
    "b_and_c_swapped": "the scan reads C where B stands and B where C "
                       "stands",
    "dt_norm_left_out": "dt goes to its projection as x_proj makes it",
    "b_norm_left_out": "B goes to the scan as x_proj makes it",
    "c_norm_left_out": "C goes to the scan as x_proj makes it",
    "conv_not_causal": "tap j of the convolution reads the row 3 - j "
                       "AFTER, not before",
    "conv_a_tap_short": "the convolution's furthest tap is left out: 3 "
                        "taps of 4",
    "softplus_left_out": "dt = dt W_dt + b_dt as it is",
    "d_left_out": "y_t = s_t . C_t, without D u_t",
    "gate_on_u": "out = (y * silu(u)) W_out, not silu(z)",
    "rotary_added_to_attention": "q and k are turned by rotary positions "
                                 "(theta 1e4) before attention",
    "kv_head_read_per_query_head": "query head i reads the one key/value "
                                   "head's channels moved round by i d / heads, "
                                   "as if it had a slice of its own",
    "loss_on_the_token_itself": "row t's loss is of token t, not of token "
                                "t + 1: the labels are not shifted",
    "norm_scales_left_out": "dt, B and C are normed without their learnt "
                            "scales",
    "conv_bias_left_out": "the convolution's bias is not added",
    "d_taken_as_one": "y_t = s_t . C_t + u_t whatever D holds",
}
# A scale or a D of one and a bias of zero hide these at initialisation,
# whatever the size: the CPU tests hold them (tests/single/test_jamba.py
# compares model and reference with all of them moved; test_jamba_cell.py
# reads these faults on such weights).
HIDDEN_AT_INITIALISATION = ("norm_scales_left_out", "conv_bias_left_out",
                            "d_taken_as_one")


@contextlib.contextmanager
def reference_with(**attributes):
    """The plain reference with some of its module's names replaced."""
    kept = {k: getattr(reference_jamba, k) for k in attributes}
    try:
        for k, v in attributes.items():
            setattr(reference_jamba, k, v)
        yield
    finally:
        for k, v in kept.items():
            setattr(reference_jamba, k, v)


def _rounded(dtype):
    """``x`` rounded to ``dtype``'s exponent and mantissa; the gradient
    passes unrounded.  ``lax.reduce_precision``, not a cast there and back:
    the TPU's compiler takes a pair of casts out (``xla_allow_excess_
    precision``), and the fault with them (both read 0.0 on the chip)."""
    import jax
    import jax.numpy as jnp

    info = jnp.finfo(dtype)

    @jax.custom_jvp
    def rounded(x):
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    rounded.defjvp(lambda primals, tangents: (rounded(primals[0]),
                                              tangents[0]))
    return rounded


def _fault(name: str) -> dict:
    """The names of the reference a fault replaces."""
    import jax
    import jax.numpy as jnp

    ref = reference_jamba
    if name == "state_in_bfloat16":
        return {"carried": _rounded(jnp.bfloat16)}
    if name == "dt_in_bfloat16":
        sound, low = ref.step_size, _rounded(jnp.bfloat16)
        return {"step_size": lambda p, dt: low(sound(p, dt))}
    if name == "a_without_its_sign":
        return {"decay_rate": lambda p: jnp.exp(p["A_log"])}
    if name == "a_without_its_exp":
        return {"decay_rate": lambda p: -p["A_log"]}
    if name == "b_and_c_swapped":
        sound = ref.three_norms

        def swapped(p, dt, b, c, eps):
            dt, b, c = sound(p, dt, b, c, eps)
            return dt, c, b
        return {"three_norms": swapped}
    if name in ("dt_norm_left_out", "b_norm_left_out", "c_norm_left_out"):
        sound, which = ref.three_norms, "dbc".index(name[0])

        def one_left_out(p, dt, b, c, eps):
            plain = (dt, b, c)
            normed = sound(p, dt, b, c, eps)
            return tuple(plain[i] if i == which else normed[i]
                         for i in range(3))
        return {"three_norms": one_left_out}
    if name == "conv_not_causal":
        def after(x, steps):
            if steps == 0:
                return x
            return jnp.concatenate([x[steps:], jnp.zeros_like(x[:steps])])

        def ahead(u, taps, bias):
            n = taps.shape[0]
            return sum(taps[j] * after(u, n - 1 - j) for j in range(n)) + bias
        return {"causal_conv": ahead}
    if name == "conv_a_tap_short":
        sound = ref.causal_conv
        return {"causal_conv": lambda u, taps, bias: sound(u, taps[1:],
                                                           bias)}
    if name == "softplus_left_out":
        return {"step_size": lambda p, dt: dt @ p["dt_proj"] + p["dt_bias"]}
    if name == "d_left_out":
        return {"skip": lambda p: jnp.zeros_like(p["D"])}
    if name == "gate_on_u":
        return {"gated": lambda y, u, z: y * ref.silu(u)}
    if name == "rotary_added_to_attention":
        def rotary(x):
            half = x.shape[-1] // 2
            freq = 1e4 ** (-jnp.arange(half, dtype=jnp.float32) / half)
            angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
            cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * cos - x2 * sin,
                                    x2 * cos + x1 * sin], -1)
        return {"positioned": lambda q, k: (rotary(q), rotary(k))}
    if name == "kv_head_read_per_query_head":
        sound = ref.attention

        def sliced(q, k, v):
            heads = q.shape[1]
            moved = lambda x: jnp.stack(  # noqa: E731
                [jnp.roll(x[:, 0], i * x.shape[-1] // heads, axis=-1)
                 for i in range(heads)],
                axis=1)
            return sound(q, moved(k), moved(v))
        return {"attention": sliced}
    if name == "loss_on_the_token_itself":
        return {"next_tokens": lambda ids: ids[:-1]}
    if name == "norm_scales_left_out":
        sound = ref.three_norms
        ones = lambda p: {k: jnp.ones_like(v) if k.endswith("_norm")  # noqa: E731
                          else v for k, v in p.items()}
        return {"three_norms": lambda p, dt, b, c, eps: sound(
            ones(p), dt, b, c, eps)}
    if name == "conv_bias_left_out":
        sound = ref.causal_conv
        return {"causal_conv": lambda u, taps, bias: sound(u, taps, 0.0)}
    assert name == "d_taken_as_one", name
    return {"skip": lambda p: jnp.ones_like(p["D"])}


# Made in the PROGRAM (names of ``horovod_tpu.models.jamba`` replaced while
# the model's own first mixer runs), not in the reference: what a step that
# did not keep the configuration's float32 would hand to checks (e) and (f)
# of ``families/jamba.py``.
PROGRAM_FAULTS = {
    "mixer_softplus_in_bfloat16": "the mixer rounds what goes into its "
                                  "softplus and what comes out of it to "
                                  "bfloat16",
    "mixer_norms_in_bfloat16": "the mixer rounds what goes into each of "
                               "its three norms and what comes out to "
                               "bfloat16",
    "kernel_dt_in_bfloat16": "the scan rounds the dt it is handed to "
                             "bfloat16 before its first step",
    "kernel_state_in_bfloat16": "the scan rounds its state to bfloat16 "
                                "after every step",
}


@contextlib.contextmanager
def program_with(fault: str):
    """``horovod_tpu.models.jamba`` with ``fault`` of :data:`PROGRAM_FAULTS`
    made in it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models import jamba as program
    from horovod_tpu.ops import selective_scan as ss

    low = _rounded(jnp.bfloat16)
    names = {}
    if fault == "mixer_softplus_in_bfloat16":
        sound = jax.nn.softplus
        names = {(jax.nn, "softplus"): lambda x: low(sound(low(x)))}
    elif fault == "mixer_norms_in_bfloat16":
        sound = program._scaled
        names = {(program, "_scaled"): lambda x, scale, eps: low(
            sound(low(x), scale, eps))}
    elif fault == "kernel_dt_in_bfloat16":
        sound = program.selective_scan
        names = {(program, "selective_scan"): lambda u, dt, *rest: sound(
            u, low(dt), *rest)}
    else:
        assert fault == "kernel_state_in_bfloat16", fault

        def low_state(u, dt, a, b, c, d):
            f = lambda x: x.astype(jnp.float32)  # noqa: E731

            def step(state, row):
                u_t, dt_t, b_t, c_t = row
                state = low(jnp.exp(dt_t[..., None] * a) * state
                            + (dt_t * u_t)[..., None] * b_t[:, None, :])
                return state, jnp.sum(state * c_t[:, None, :],
                                      axis=-1) + d * u_t

            rows = tuple(jnp.swapaxes(f(x), 0, 1) for x in (u, dt, b, c))
            start = ss._vary_like(
                jnp.zeros((u.shape[0], *a.shape), jnp.float32), u)
            return jnp.swapaxes(lax.scan(step, start, rows)[1], 0,
                                1).astype(u.dtype)
        names = {(program, "selective_scan"): low_state}
    kept = {k: getattr(*k) for k in names}
    try:
        for (owner, name), v in names.items():
            setattr(owner, name, v)
        yield
    finally:
        for (owner, name), v in kept.items():
            setattr(owner, name, v)


def program_readings(cell: dict, faults=tuple(PROGRAM_FAULTS),
                     said=None) -> dict:
    """``{"sound" or fault: {measure: reading}}`` of the model's own first
    mixer on the first sequence of ``cell``'s first batch: checks (e) and
    (f)'s own measures (``step_and_norms``, ``scan``), and beside them what
    a comparison with the reference's OWN first block reads (its float32
    ``dt`` and ``y`` from the float32 embedding on: ``dt_vs_the_reference_s``,
    ``y_vs_the_reference_s``, L2), which bfloat16 activations drown."""
    import jax
    import numpy as np

    variables = common.first_shard(cell["params"])
    ids = cell["batches"][0][0][:1]
    rcfg = jamba.reference_config(cell["jcfg"])
    with jax.default_matmul_precision("highest"):
        operands, own_y = jax.jit(lambda p, row: jamba.first_scan(
            p, row, rcfg))({"params": variables["params"]}, ids[0])
    own_dt, own_y = np.asarray(operands[1]), np.asarray(own_y)
    out = {}
    for name in ("sound", *faults):
        with (contextlib.nullcontext() if name == "sound"
              else program_with(name)):
            kept = jax.block_until_ready(
                jamba.first_mixer(cell, variables, ids))
        got = jamba.first_mixer_errors(cell, variables, kept)
        got["dt_vs_the_reference_s"] = common.l2_rel_err(
            np.asarray(kept["operands"][1][0]), own_dt)
        got["y_vs_the_reference_s"] = common.l2_rel_err(
            np.asarray(kept["y"][0].astype(np.float32)), own_y)
        out[name] = {k: _number(v) for k, v in got.items()}
        if said is not None:
            said(name, out[name])
    return out


def _number(x) -> float:
    import math

    x = float(x)
    return x if math.isfinite(x) else NOT_FINITE


def readings(faults: list, variables, jcfg, ids, grads: bool = False,
             sequences: int = 1, said=None) -> dict:
    """``{fault: {measure: reading}}`` on the first sequence of ``ids``:
    each fault's reference against the sound one.  ``said(fault, reading)``
    is called as each comes."""
    import jax
    import numpy as np

    rcfg = jamba.reference_config(jcfg)
    row = ids[0]
    params = {"params": variables["params"]}
    length = row.shape[0]
    positions = jamba.sample_positions(length)
    first_is_mamba = jcfg.layer_kinds[0] == "mamba"

    def run_():
        def part(p):
            p = p["params"]
            x = reference_jamba.hidden(p, row, rcfg)
            loss = reference_jamba.loss_sum(p, x, row) / (
                sequences * (length - 1))
            return loss, reference_jamba.head(p, x[positions])

        scan = jax.jit(lambda p: jamba.first_scan(p, row, rcfg)[1])(
            params) if first_is_mamba else None
        if not grads:
            return (*jax.jit(part)(params), scan, None)
        (loss, logits), g = jax.jit(jax.value_and_grad(part, has_aux=True))(
            params)
        return loss, logits, scan, common.leaf_paths(
            jamba._checked_tree(g, jcfg))

    with jax.default_matmul_precision("highest"):
        loss, logits, scan, leaf_grads = run_()
        out = {}
        for name in faults:
            with reference_with(**_fault(name)):
                f_loss, f_logits, f_scan, f_grads = run_()
            out[name] = {
                "sample_logits": _number(common.l2_rel_err(f_logits,
                                                           logits)),
                "first_loss": _number(common.rel_err(float(f_loss),
                                                     float(loss)))}
            if scan is not None:
                out[name]["scan"] = _number(common.rel_err(
                    np.asarray(f_scan), np.asarray(scan)))
            if grads:
                out[name]["first_moment"] = {
                    path: _number(common.l2_rel_err(
                        np.asarray(f_grads[path]), np.asarray(g)))
                    for path, g in leaf_grads.items()}
            if said is not None:
                said(name, out[name])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--faults", nargs="+", default=[
        f for f in FAULTS if f not in HIDDEN_AT_INITIALISATION],
        choices=list(FAULTS))
    ap.add_argument("--grads", action="store_true")
    ap.add_argument("--program", action="store_true",
                    help="PROGRAM_FAULTS, made in the model's own first "
                         "mixer, in checks (e) and (f)'s measures; and no "
                         "fault of the reference")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    mesh = common.hvd_mesh(jax.devices()[:1])
    for seed in args.seeds:
        cell = jamba.setup(cfg, mesh, seed, rehearse=args.rehearse)
        cell["batches"] = traffic_gen.make_batches(
            traffic, jamba.inputs(cell, traffic), mesh, seed)
        said = lambda fault, got, seed=seed: print(json.dumps({  # noqa: E731
            "seed": seed, "cell": CELL,
            "device": jax.devices()[0].device_kind,
            "readings": {fault: got}}), flush=True)
        if args.program:
            program_readings(cell, said=said)
            del cell
            continue
        readings(args.faults, common.first_shard(cell["params"]),
                 cell["jcfg"], cell["batches"][0][0], grads=args.grads,
                 sequences=traffic["batch_per_chip"], said=said)
        del cell
    return 0


if __name__ == "__main__":
    sys.exit(main())
