"""The cell ``bert-large-s512``: its rehearsal on the CPU, BERT-Large's
analytic multiply-adds and the flash kernels' least work at the traffic
file's lengths, each against numbers worked out by hand, and the shapes the
family gives the drawn arguments.  Nothing here measures anything."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import bert_flops, flops, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import bert  # noqa: E402

CELL = "bert-large-s512"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _files():
    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), False)
    return entry, cfg, traffic


def test_rehearsal_prints_the_contract_keys_and_no_metric(tmp_path):
    """``run.py --rehearse`` at BERT_TINY's sizes (vocabulary 1000 in a
    1024-row matrix, one short sequence of four): every check against the
    plain reference passes and no CPU number is written as a metric."""
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="7",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS="--xla_force_host_platform_device_count=1 "
                         "--xla_cpu_multi_thread_eigen=false "
                         "intra_op_parallelism_threads=1")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1",
         "--rehearse"], env=env, cwd=REPO, capture_output=True, text=True,
        timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.strip().splitlines()]
    result = lines[-1]
    # The keys the driver reads, then every compared number and its limit.
    assert set(result) == RESULT_KEYS | {"checks"}
    assert list(result)[-1] == "checks" and all(
        {"value", "limit"} <= set(c) or {"value", "least"} <= set(c)
        for c in result["checks"].values())
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    checks = {c["name"] for c in lines[0]["checks"]}
    assert {"first_loss_vs_reference", "sample_mlm_logits_vs_reference",
            "sample_nsp_logits_vs_reference", "sample_loss_vs_reference",
            "padded_vocabulary_is_out_of_the_softmax", "logits_are_float32",
            "decode_of_the_reference_s_hidden_vs_reference",
            "parameters_and_moments_are_float32"} <= checks
    assert len([c for c in checks if c.startswith("first_update")]) == 4
    moments = [c for c in checks if c.startswith("first_moment")]
    assert len(moments) == 4
    for leaf in ("word_embeddings", "layer_0']['attention']['qkv",
                 "layer_1']['mlp_out", "nsp_head"):
        assert any(leaf in m for m in moments), (leaf, moments)


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1, "bert-phase2-32x512x1")
    assert cfg["reduced"] == []
    published = {"hidden_size": 1024, "num_hidden_layers": 24,
                 "num_attention_heads": 16, "intermediate_size": 4096,
                 "vocab_size": 30522, "max_position_embeddings": 512,
                 "type_vocab_size": 2}
    assert {k: cfg[k] for k in published} == published
    assert cfg["optimizer"] == {"name": "adamw", "args": {
        "learning_rate": 1e-4, "b1": 0.9, "b2": 0.999, "weight_decay": 0.01}}
    assert cfg["assumed"]["vocab_size_padded"] == 239 * 128
    lengths = bert.chip_lengths(traffic, traffic["seq_len"])
    assert len(lengths) == 32 and sorted(set(lengths)) == [128, 256, 384, 512]
    assert [lengths[i] for i in (9, 19, 29)] == [128, 256, 384]
    assert sum(lengths) / (32 * 512) == pytest.approx(0.953, abs=5e-4)
    # 15 % of a sequence's real tokens carry weight, at most 80.
    assert [bert.weighted_positions(traffic, n)
            for n in (512, 384, 256, 128)] == [77, 58, 38, 19]


def test_model_flops_of_bert_large_by_hand():
    """Padded positions count: the published step computes them.  Every
    sequence is 512 long to the matmuls, attention's products are whole
    squares, and the decoder runs over the 30592 padded rows at the 80
    gathered positions."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    _, cfg, traffic = _files()
    bcfg = bert._bert_config(cfg, rehearse=False)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(
        lambda k: models.BertForPreTraining(bcfg).init(
            k, ids, ids, lengths=jnp.full((1,), 16),
            masked_positions=ids[:, :4]), jax.random.key(0))
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params)) == 336_297_858
    cell = {"bcfg": bcfg, "params": params, "batches": [tuple(
        jax.ShapeDtypeStruct((32, *i.shape), i.dtype)
        for i in bert.inputs({"bcfg": bcfg}, traffic))]}
    h, i, s, p, v = 1024, 4096, 512, 80, 30592
    per_token_per_layer = (3 * h * h + h * h      # qkv, out
                           + 2 * h * i            # feed-forward
                           + 2 * s * h)           # QK^T and PV, all heads
    assert per_token_per_layer == 13_631_488
    per_sequence = (24 * per_token_per_layer * s
                    + p * (h * h + h * v)         # transform, tied decoder
                    + h * h + 2 * h)              # pooler, classifier
    assert per_sequence == 170_094_757_888        # 332.2 M a token
    assert bert.model_flops(cell) == pytest.approx(
        2 * 3 * per_sequence * 32, rel=1e-12)     # 32.66 TFLOP a step
    assert bert.units(cell) == ("tokens", 32 * 512)


def test_flash_step_least_by_hand_at_the_traffics_lengths():
    """Non-causal, real keys x real queries of each sequence: 29 sequences
    of 512 and one each of 128, 256 and 384, 16 heads, 24 layers, bf16.  A
    call holds the whole batch, so each kernel is bound by the larger of
    the batch's FLOPs and the batch's bytes (on a v5e by its FLOPs), not by
    the sum of each sequence's larger, which would count a short sequence's
    bytes as if nothing else ran beside them."""
    _, cfg, traffic = _files()
    peaks = flops.chip_peaks("TPU v5 lite", run.load_json("peaks.json")[
        "peaks"])
    got = bert_flops.flash_step_least(
        {"cfg": {**cfg["assumed"], **cfg}, "traffic": traffic,
         "peaks": peaks})
    heads, d, layers = 16, 64, 24
    lengths = [512] * 29 + [128, 256, 384]
    squares, tokens = sum(n * n for n in lengths), sum(lengths)
    assert (squares, tokens) == (7_831_552, 15_616)   # of 8,388,608, 16,384
    per_sequence_max = 0.0
    for name, matmuls, arrays, stats in (("fwd", 2, 4, 1), ("dq", 3, 5, 2),
                                         ("dkv", 4, 6, 2)):
        by_flops = matmuls * 2 * squares * d * heads * layers / 197e12
        by_bytes = (arrays * d * 2 + stats * 4) * tokens * heads * layers \
            / 819e9
        assert by_flops > by_bytes
        kernel = got["kernels"][name]
        assert kernel["bound"] == "flops"
        assert kernel["seconds"] == pytest.approx(by_flops, rel=1e-12)
        assert kernel["bytes"] == pytest.approx(by_bytes * 819e9, rel=1e-12)
        per_sequence_max += sum(
            max(matmuls * 2 * n * n * d * heads * layers / 197e12,
                (arrays * d * 2 + stats * 4) * n * heads * layers / 819e9)
            for n in lengths)
    # 9 matmuls over the sum of L^2: 17.6 ms a step.
    square = 9 * 2 * squares * d * heads * layers / 197e12
    assert got["seconds"] == pytest.approx(square, rel=1e-12)
    assert got["flops"] == pytest.approx(square * 197e12, rel=1e-12)
    assert 0.0175 < got["seconds"] < 0.0177
    # The sum of each sequence's larger bound lies above it: no lower bound.
    assert got["seconds"] < per_sequence_max < 1.02 * got["seconds"]


def _bf16(x):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def test_the_first_update_tells_float32_parameters_from_bfloat16():
    """Check (d): the learning rate 1e-4 is below a bfloat16 ulp of a weight
    near 0.03 (2^-13 = 1.2e-4), so parameters kept in bfloat16 lose the first
    update to rounding, whatever the activations' noise; float32 ones follow
    plain AdamW to their own rounding (an ulp of 1.9e-9 on a step of 1e-4)."""
    import jax.numpy as jnp
    import optax

    from benchmark import common

    args = _files()[1]["optimizer"]["args"]
    rng = np.random.default_rng(0)
    before = (rng.standard_normal((256, 1024)) / 32).astype(np.float32)
    grad = (rng.standard_normal(before.shape) * 1e-3).astype(np.float32)
    tx = optax.adamw(**args)
    updates, state = tx.update(jnp.asarray(grad), tx.init(before), before)
    mu, nu = np.asarray(state[0].mu), np.asarray(state[0].nu)
    after = np.asarray(optax.apply_updates(jnp.asarray(before), updates))

    def reading(before, after):
        return common.l2_rel_err(
            after.astype(np.float64) - before,
            bert.adamw_first_update(before, mu, nu, **args))

    assert reading(before, after) < bert.TOL_FIRST_UPDATE
    kept_in_bf16 = _bf16(_bf16(before) + np.asarray(updates))
    assert reading(_bf16(before), kept_in_bf16) > 0.3
    # ... and a second moment that is not this gradient's is seen too.
    assert common.l2_rel_err(
        after.astype(np.float64) - before, bert.adamw_first_update(
            before, mu, 4.0 * nu, **args)) > 0.3


def test_the_decode_check_tells_a_float32_head_from_bfloat16():
    """Check (e): the system's ``decode`` (layer norm, tied decoder, bias)
    on a float32 input against the reference's, under the cell's bfloat16
    activations, reads a hundredth of the limit; the reference itself
    computed in bfloat16 reads some eighty times the limit, and its float32
    logits rounded to bfloat16, the nearest fault, some forty times."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmark import common
    from benchmark.references import bert as reference_bert
    from horovod_tpu import models

    cfg = dataclasses.replace(models.BERT_TINY, vocab_size=1000,
                              dtype=jnp.bfloat16, use_flash=False)
    model = models.BertForPreTraining(cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.key(5), ids, ids,
                        lengths=jnp.full((1,), 16),
                        masked_positions=ids[:, :4])
    params["params"]["mlm_bias"] = jax.random.normal(
        jax.random.key(6), params["params"]["mlm_bias"].shape)
    h = jax.random.normal(jax.random.key(7), (2, 8, cfg.hidden_size))
    want = np.asarray(reference_bert.decode(params["params"], 1000, h))
    got = np.asarray(model.apply(params, h, method="decode"))
    assert got.dtype == np.float32 and got.shape == (2, 8, 1024)
    assert got[..., 1000:].max() <= -1e30
    assert common.rel_err(got[..., :1000], want) < bert.TOL_DECODE
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                 params["params"])
    in_bf16 = np.asarray(reference_bert.decode(
        low, 1000, h.astype(jnp.bfloat16)).astype(jnp.float32))
    assert common.rel_err(in_bf16, want) > 30 * bert.TOL_DECODE
    assert common.rel_err(_bf16(want), want) > 10 * bert.TOL_DECODE


def test_the_family_shapes_what_the_generator_draws():
    """Lengths by position and never by the seed; masked positions inside
    each sequence's length; weights on the first 15 % of a length."""
    import jax
    import jax.numpy as jnp

    from benchmark import common

    _, cfg, _ = _files()
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "bert-phase2-32x512x1.json"), True)
    cell = {"bcfg": bert._bert_config(cfg, rehearse=True)}
    mesh = common.hvd_mesh(jax.devices()[:1])
    shaped = [bert.shape_batch(traffic, *traffic_gen.make_batches(
        traffic, bert.inputs(cell, traffic), mesh, seed)[0])
        for seed in (3000000019, 5)]
    for b in shaped:
        assert list(b["lengths"]) == [64, 24, 64, 64]
        assert b["masked_positions"].shape == (4, 8)
        assert bool(jnp.all(b["masked_positions"] < b["lengths"][:, None]))
        assert b["mlm_weights"].sum(axis=1).tolist() == [8, 4, 8, 8]
        assert b["nsp_labels"].shape == (4,)
    assert not np.array_equal(shaped[0]["masked_positions"],
                              shaped[1]["masked_positions"])
    # Two chips' batches laid end to end repeat the lengths.
    two = bert.shape_batch(traffic, *(jnp.concatenate([x, x]) for x in (
        shaped[0]["input_ids"], shaped[0]["token_type_ids"],
        shaped[0]["masked_positions"], shaped[0]["mlm_labels"],
        shaped[0]["nsp_labels"])), chips=2)
    assert list(two["lengths"]) == [64, 24, 64, 64] * 2
