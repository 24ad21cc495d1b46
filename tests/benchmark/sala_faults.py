"""The faults that the checks of ``benchmark/families/sala.py`` are there to
catch: made in the plain reference and read in those checks' own measures
against the plain reference itself (what a limit must stay under;
``benchmark/testdata/check_readings/sala.json`` keeps the readings), and some
of them made in the program, for ``test_sala_cell.py`` to run the timed path
on.

    python tests/benchmark/sala_faults.py --seeds 1 2 3

reads them at ``sala-sparse-linear-tp4-s16384``'s own size on the machine it
is started on (a TPU) and prints one JSON line a seed and fault.  The faulty
reference stands where the system stands in a run: it makes its own choice of
blocks, and the sound reference is read on that choice, as
``families/sala.py:reference`` reads it on the system's.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import sala  # noqa: E402
from benchmark.references import sala as reference_sala  # noqa: E402

CELL = "sala-sparse-linear-tp4-s16384"
FAULTS = {
    "decay_of_the_wrong_head": "the slopes of heads 0 to 7, not of the held "
                               "heads 24 to 31",
    "decay_of_the_cuts_depth": "the slopes' depth factor 1 - l / 3 from the "
                               "cut's 4 layers, not 1 - l / 31",
    "decay_missing": "lambda is 1: every earlier row weighs as the last",
    "state_not_carried": "a chunk of 256 rows starts from an empty state",
    "dkv_state_not_carried": "the backward's dk and dv take nothing from the "
                             "chunks after their own (dS is not carried): "
                             "the forward and dq are sound",
    "rotary_on_the_sparse_layer": "the sparse layer's q and k are turned by "
                                  "their positions too",
    "rotary_off_the_lightning_layer": "the lightning layers' q and k are not "
                                      "turned",
    "qk_norm_left_out": "q and k keep their head norms' scales and are "
                        "divided by nothing",
    "output_gate_left_out": "both mixers hand W_o their output ungated",
    "output_norm_left_out": "the lightning output keeps its norm's scale "
                            "and is divided by nothing",
    "lightning_scale_left_out": "the lightning scores are q . k, not over "
                                "sqrt(128)",
    "residual_scale_of_the_cuts_depth": "s = 1.4 / sqrt(4), not 1.4 / "
                                        "sqrt(32)",
    "head_not_divided": "the head reads the final norm as it is, not over "
                        "16",
    "topk_of_63": "a query takes 63 blocks, not 64",
    "forced_local_blocks_dropped": "only the first block is always chosen: "
                                   "the 32 up to the query's own compete",
    "selection_by_a_head": "a block's score is the first head's p, not the "
                           "group's sum",
    "mean_pool_stride_32": "the compressed keys lie 32 apart, not 16",
    "plain_causal_for_selected": "the sparse layer attends over every key at "
                                 "or before the query",
    "lightning_in_bfloat16": "the lightning scores, their decay and both "
                             "products in bfloat16 end to end",
    "selection_sum_in_bfloat16": "a head's p is rounded to bfloat16 and the "
                                 "group's sum is made in bfloat16",
}
# Read, and refused by no limit: the kernels round the decayed scores and the
# state to bfloat16 where they are operands of a product, as a flash kernel
# rounds p, so on (f) "bfloat16 end to end" reads 1.6 times a sound run, less
# than the rule's room twice over (check_readings/sala.json keeps the
# readings).  The fault of precision that the cell's limits do refuse is the
# selection's (``selection_sum_in_bfloat16``, by the block scores).
NOT_REFUSED = ("lightning_in_bfloat16",)
CHUNK = 256     # of state_not_carried: the kernels' chunk at the cell's size


def _reference_fault(name: str, chunk: int = CHUNK) -> tuple:
    """``(names, cfg)``: the names of ``references/sala.py`` and the keys of
    its configuration that make fault ``name``."""
    import jax
    import jax.numpy as jnp

    r = reference_sala
    sound_slopes, sound_attention = r.slopes, r.attention
    sound_lightning = r.lightning

    def lightning_by_chunk(q, k, v, a):
        seq = q.shape[0]
        size = min(chunk, seq)
        parts = [x.reshape(seq // size, size, *x.shape[1:]) for x in (q, k, v)]
        return jax.lax.map(lambda qkv: sound_lightning(*qkv, a),
                           tuple(parts)).reshape(q.shape)

    @jax.custom_vjp
    def lightning_dkv_by_chunk(q, k, v, a):
        return sound_lightning(q, k, v, a)

    def dkv_by_chunk_bwd(saved, g):
        *qkv, a = saved
        dq = jax.vjp(lambda *x: sound_lightning(*x, a), *qkv)[1](g)[0]
        _, dk, dv = jax.vjp(lambda *x: lightning_by_chunk(*x, a), *qkv)[1](g)
        return dq, dk, dv, jnp.zeros_like(a)

    lightning_dkv_by_chunk.defvjp(
        lambda q, k, v, a: (sound_lightning(q, k, v, a), (q, k, v, a)),
        dkv_by_chunk_bwd)

    def lightning_in_bfloat16(q, k, v, a):
        seq, _, width = q.shape
        low = jnp.bfloat16
        q, k, v = (x.astype(low) for x in (q, k, v))
        gap = jnp.arange(seq)[:, None] - jnp.arange(seq)[None, :]

        @jax.checkpoint
        def head(args):
            qh, kh, vh, ah = args                       # [S, D] each
            decay = jnp.where(gap >= 0, jnp.exp(
                -ah * jnp.maximum(gap, 0).astype(jnp.float32)), 0.0)
            s = jnp.dot(qh, kh.T, preferred_element_type=low) * (
                decay * width ** -0.5).astype(low)
            return jnp.dot(s, vh, preferred_element_type=low)

        out = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                                 v.swapaxes(0, 1), a))
        return out.swapaxes(0, 1).astype(jnp.float32)

    names, cfg = {
        "decay_of_the_wrong_head": ({}, {"first_lightning_head": 0}),
        "decay_of_the_cuts_depth": ({"slopes": lambda cfg, layer, held: (
            sound_slopes({**cfg, "published_layers": 4}, layer, held))}, {}),
        "decay_missing": ({"slopes": lambda cfg, layer, held: jnp.zeros(
            (held,), jnp.float32)}, {}),
        "state_not_carried": ({"lightning": lightning_by_chunk}, {}),
        "dkv_state_not_carried": ({"lightning": lightning_dkv_by_chunk}, {}),
        "rotary_on_the_sparse_layer": ({"positions": lambda kind, x, cfg: (
            r.rotary(x, cfg["rope_theta"]))}, {}),
        "rotary_off_the_lightning_layer": (
            {"positions": lambda kind, x, cfg: x}, {}),
        "qk_norm_left_out": (
            {"head_norm": lambda x, scale, eps: x * scale}, {}),
        "output_gate_left_out": ({"gated": lambda o, logits: o}, {}),
        "output_norm_left_out": (
            {"output_norm": lambda o, scale, eps: o * scale}, {}),
        "lightning_scale_left_out": (
            {"lightning_scale": lambda width: 1.0}, {}),
        "residual_scale_of_the_cuts_depth": ({"residual_scale": lambda cfg: (
            cfg["scale_depth"] / 4 ** 0.5)}, {}),
        "head_not_divided": ({"logit_divisor": lambda width, cfg: 1.0}, {}),
        "topk_of_63": ({}, {"topk": lambda cfg: cfg["topk"] - 1}),
        "forced_local_blocks_dropped": ({}, {"window_size": 0}),
        "selection_by_a_head": ({"over_the_group": lambda p: p[:, 0]}, {}),
        "mean_pool_stride_32": ({}, {"kernel_stride": lambda cfg: (
            2 * cfg["kernel_stride"])}),
        "plain_causal_for_selected": ({
            "attention": lambda q, k, v, chosen, block: sound_attention(
                q, k, v, None, block)}, {}),
        "lightning_in_bfloat16": ({"lightning": lightning_in_bfloat16}, {}),
        "selection_sum_in_bfloat16": ({"over_the_group": lambda p: jnp.sum(
            p.astype(jnp.bfloat16), axis=1,
            dtype=jnp.bfloat16).astype(jnp.float32)}, {}),
    }[name]
    return names, cfg


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


def reference_with(fault: str, chunk: int = CHUNK):
    """The plain reference with fault ``fault`` in it ("sound": as it is)."""
    return _replaced(reference_sala, {} if fault == "sound"
                     else _reference_fault(fault, chunk)[0])


def config_with(fault: str, rcfg: dict) -> dict:
    """The reference's configuration under fault ``fault``."""
    changed = {} if fault == "sound" else _reference_fault(fault)[1]
    return {**rcfg, **{k: v(rcfg) if callable(v) else v
                       for k, v in changed.items()}}


# The faults test_sala_cell.py makes in the program.
PROGRAM_FAULTS = ("plain_causal_for_selected", "state_not_carried",
                  "dkv_state_not_carried", "topk_of_63")


@contextlib.contextmanager
def program_with(fault: str, chunk: int = 16):
    """The program with fault ``fault`` in it: the model's own names
    replaced, for a whole run of the timed path at the rehearsal's sizes
    (off the TPU: the scan form and the masked dense softmax stand where the
    kernels do)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import sala as model
    from horovod_tpu.ops import flash_select
    from horovod_tpu.ops.flash_attention import _dense

    sound_scan = model.lightning_attention_scan

    def scan_by_chunk(q, k, v, slopes, scale):
        batch, seq = q.shape[:2]
        cut = lambda x: x.reshape(batch * seq // chunk, chunk,  # noqa: E731
                                  *x.shape[2:])
        return sound_scan(cut(q), cut(k), cut(v), slopes, scale).reshape(
            q.shape)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def scan_dkv_by_chunk(q, k, v, slopes, scale):
        return sound_scan(q, k, v, slopes, scale)

    def scan_dkv_by_chunk_bwd(scale, saved, g):
        *qkv, slopes = saved
        dq = jax.vjp(lambda *x: sound_scan(*x, slopes, scale), *qkv)[1](g)[0]
        _, dk, dv = jax.vjp(lambda *x: scan_by_chunk(*x, slopes, scale),
                            *qkv)[1](g)
        return dq, dk, dv, jnp.zeros_like(slopes)

    scan_dkv_by_chunk.defvjp(
        lambda q, k, v, slopes, scale: (
            sound_scan(q, k, v, slopes, scale), (q, k, v, slopes)),
        scan_dkv_by_chunk_bwd)

    config = model.SalaConfig
    sound_selection = config.selection
    patches = {
        "plain_causal_for_selected": [(flash_select, {
            "dense_select": lambda q, k, v, select, scale: _dense(
                q, k, v, True, scale, None)})],
        "state_not_carried": [(model, {
            "lightning_attention_scan": scan_by_chunk})],
        "dkv_state_not_carried": [(model, {
            "lightning_attention_scan": scan_dkv_by_chunk})],
        "topk_of_63": [(config, {"selection": property(lambda self: {
            **sound_selection.fget(self), "topk": self.sparse_topk - 1})})],
    }[fault]
    with contextlib.ExitStack() as stack:
        for module, names in patches:
            stack.enter_context(_replaced(module, names))
        yield


def _forward_and_grads(scfg, rcfg, sequences: int, length: int,
                       moments: bool = True):
    """``fn(variables, ids, chosen)`` of one sequence under whatever the
    reference's module holds when it is first called and configuration
    ``rcfg``: the loss, the sample's logits, the selecting layers' own
    choices, and (``moments``) the checked leaves' gradients beside the
    gradient of the embedding's rows of the sample's tokens."""
    import jax

    positions = sala.sample_positions(length)

    def part(p, ids, chosen):
        tree = sala.published(p)
        x, seen = reference_sala.hidden(tree, ids, rcfg, chosen)
        loss = reference_sala.loss_sum(tree, x, ids) / (
            sequences * (length - 1))
        chose = {i: s["chosen"] for i, s in enumerate(seen) if s is not None}
        return loss, (reference_sala.head(tree, x[positions]), chose)

    def fn(variables, ids, chosen):
        p = {"params": variables["params"]}
        if not moments:
            return (*part(p, ids, chosen), ({}, None))
        (loss, aux), grads = jax.value_and_grad(part, has_aux=True)(
            p, ids, chosen)
        return loss, aux, (
            sala._checked_tree(grads, scfg),
            grads["params"]["embed"]["embedding"][ids[positions]])

    return fn


def _unit_operands(seq: int, heads: int, width: int, i: int):
    """Unit-normal operands [S, heads, width] for the kernels' reads."""
    import jax

    return jax.random.normal(jax.random.key(100 + i), (seq, heads, width))


def first_layers(tree, ids, scfg, rcfg, chosen):
    """Under whatever the reference's module holds and configuration
    ``rcfg``: the first sparse layer's block scores of the **sound** float32
    q and k handed in as ``tree["qk"]``, its attention of unit-normal
    operands on the choice ``chosen``, and the first lightning layer's mix of
    unit-normal operands under its slopes, each with its dq, dk, dv of one
    drawn cotangent (``*_grads``)."""
    import jax

    from horovod_tpu.models import sala as model

    r = reference_sala
    seq = ids.shape[0]
    out = {}

    def with_grads(name, mix, *operands):
        out[name], pull = jax.vjp(mix, *operands)
        out[f"{name}_grads"] = pull(jax.random.normal(
            jax.random.key(7), out[name].shape))

    lightning = sala._layers(scfg, model.LIGHTNING)
    if lightning:
        q, k, v = (_unit_operands(seq, scfg.lightning_held,
                                  scfg.lightning_head_dim, i)
                   for i in range(3))
        a = r.slopes(rcfg, lightning[0], scfg.lightning_held)
        with_grads("lightning", lambda *x: r.lightning(*x, a), q, k, v)
    if chosen is not None:
        q = _unit_operands(seq, scfg.heads_held, scfg.head_dim, 3)
        k, v = (_unit_operands(seq, scfg.kv_heads_held, scfg.head_dim, i)
                for i in (4, 5))
        with_grads("attention", lambda *x: r.attention(
            *x, chosen, rcfg["block_size"]), q, k, v)
        out["scores"] = r.block_scores(*tree["qk"], rcfg)
    return out


def _together(got, want) -> float:
    """L2 error over several arrays as one (dq, dk, dv)."""
    import numpy as np

    flat = lambda xs: np.concatenate(  # noqa: E731
        [np.asarray(x, np.float64).ravel() for x in xs])
    return common.l2_rel_err(flat(got), flat(want))


def readings(faults, variables, scfg, ids, chunk: int = CHUNK,
             moments: bool = True) -> dict:
    """``{fault: {measure: value}}`` on the first sequence of ``ids`` [B, S]
    with the program's variables.  Each measure is its check's: the first
    loss (a); the sample's logits (b); the fault's block scores of the sound
    reference's q and k, the share of its free choices the sound reference
    does not make and the share of its queries whose choice breaks the sound
    reference's rule (c); the checked leaves' first moments, the largest
    (d), and what nine of ten of the embedding's rows of the sample's tokens
    lie within (g); the selected attention and the lightning mix of the same
    unit-normal operands, and their dq, dk, dv of one drawn cotangent (f).
    ``moments`` False leaves (d) and (g) out: no gradient of the model is
    taken."""
    import jax
    import numpy as np

    from horovod_tpu.models import sala as model

    rcfg = sala.reference_config(scfg)
    sequences, length = ids.shape
    row = ids[0]
    selects = reference_sala.selects(length, rcfg)
    first_sparse = (sala._layers(scfg, model.SPARSE) or [None])[0]
    out = {}
    with jax.default_matmul_precision("highest"):
        sound = jax.jit(_forward_and_grads(scfg, rcfg, sequences, length,
                                           moments))
        _, (_, sound_chose), _ = sound(variables, row, None)

        def sound_qk(v, ids):
            first = reference_sala.hidden(sala.published(v), ids, rcfg)[1][
                first_sparse]
            return first["q"], first["k"]

        qk = jax.jit(sound_qk)(variables, row) if selects else None
        taken = sound_chose[first_sparse] if selects else None
        def layers(cfg):
            return jax.jit(lambda qk, i, c: first_layers(
                {"qk": qk}, i, scfg, cfg, c))(qk, row, taken)

        sound_layers = layers(rcfg)
        forced = (np.asarray(reference_sala.forced(length, rcfg))
                  if selects else None)
        for fault in faults:
            fcfg = config_with(fault, rcfg)
            with reference_with(fault, chunk):
                faulty = jax.jit(_forward_and_grads(scfg, fcfg, sequences,
                                                    length, moments))
                loss, (logits, chose), grads = faulty(variables, row, None)
                faulty_layers = layers(fcfg)
            # The sound reference on the fault's choices, as a run reads it.
            want_loss, (want_logits, _), want = sound(
                variables, row, chose if selects else None)
            (grads, rows), (want, want_rows) = grads, want
            by_leaf = {
                k: sala.moment_error(k, np.asarray(g), np.asarray(
                    common.leaf_paths(want)[k]))
                for k, g in common.leaf_paths(grads).items()}
            read = {
                "first_loss": common.rel_err(float(loss), float(want_loss)),
                "sample_logits": common.l2_rel_err(logits, want_logits)}
            if moments:
                # (d) holds the leaves above every output norm; the others'
                # readings are kept beside them.
                read.update(first_moment=max(
                    v for k, v in by_leaf.items()
                    if sala.above_every_output_norm(k, scfg)),
                    first_moments=by_leaf,
                    hidden_gradient=sala.nine_in_ten(
                        sala.errors_by_row(rows, want_rows)))
            if "lightning" in sound_layers:
                read.update(
                    first_lightning=common.l2_rel_err(
                        faulty_layers["lightning"], sound_layers["lightning"]),
                    first_lightning_grads=_together(
                        faulty_layers["lightning_grads"],
                        sound_layers["lightning_grads"]))
            if selects:
                read.update(
                    block_scores=common.rel_err(
                        np.asarray(faulty_layers["scores"]),
                        np.asarray(sound_layers["scores"])),
                    choices_differing=sala.choices_differing(
                        {i: np.asarray(c) for i, c in chose.items()},
                        sound_chose, scfg),
                    choice_rule=sala.choice_rule_breaks(
                        chose, sound_chose, forced),
                    first_sparse_attention=common.l2_rel_err(
                        faulty_layers["attention"],
                        sound_layers["attention"]),
                    first_sparse_grads=_together(
                        faulty_layers["attention_grads"],
                        sound_layers["attention_grads"]))
            out[fault] = read
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
        cell = sala.setup(cfg, mesh, seed, rehearse=args.rehearse)
        cell["batches"] = traffic_gen.make_batches(
            traffic, sala.inputs(cell, traffic), mesh, seed)
        got = readings(args.faults, common.first_shard(cell["params"]),
                       cell["scfg"], cell["batches"][0][0],
                       16 if args.rehearse else CHUNK)
        for fault, read in got.items():
            print(json.dumps({"seed": seed, "fault": fault,
                              "device": jax.devices()[0].device_kind,
                              **read}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
