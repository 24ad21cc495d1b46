"""The cell ``zaya1-moe-ep2-s16384``: its rehearsal on the CPU, the published
widths in its configuration, its parameter count, its analytic multiply-adds
and its kernels' least work against numbers worked out by hand, the faults its
limits are there to catch, and its timed path broken underneath.  Nothing here
measures anything."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, run, zaya_flops  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import zaya  # noqa: E402

import zaya_faults  # noqa: E402  (beside this file)

CELL = "zaya1-moe-ep2-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of Zyphra/ZAYA1-8B, as the catalog of the model-configs guide
# holds it.
PUBLISHED = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048,
    "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
    "max_position_embeddings": 131072, "model_type": "zaya",
    "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"},
    "router_hidden_size": 256, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 262272}
CHECKED_LEAVES = ("embed", "conv0", "conv1", "temp", "q_proj", "v_shift_proj",
                  "res_attn']['b", "res_attn']['c", "gamma", "down", "mlp_1",
                  "w_down")


def _files(rehearse=False):
    entry = run.cell_entry(run.load_spec(), CELL)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), rehearse)
    return entry, cfg, traffic


def _context():
    _, cfg, traffic = _files()
    peaks = flops.chip_peaks("TPU v5 lite",
                             run.load_json("peaks.json")["peaks"])
    return {"cfg": {**cfg["assumed"], **cfg}, "traffic": traffic,
            "peaks": peaks}


def test_rehearsal_prints_the_contract_keys_and_no_metric(tmp_path):
    """``run.py --rehearse`` at tiny sizes (4 of 8 experts held from the
    third on, 4 query heads on 2): every check against the plain reference
    passes and no CPU number is written as a metric."""
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
    assert set(result) == RESULT_KEYS | {"checks"}
    assert list(result)[-1] == "checks" and all(
        {"value", "limit"} <= set(c) or {"value", "least"} <= set(c)
        for c in result["checks"].values())
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    noted = {c["name"]: c for c in lines[0]["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "router_probs_of_the_reference_s_input_vs_reference",
            "choices_differing_from_the_reference", "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    for kind in ("first_moment", "first_update"):
        leaves = [c for c in noted if c.startswith(kind)]
        assert len(leaves) == len(CHECKED_LEAVES), leaves
        for leaf in CHECKED_LEAVES:
            assert any(leaf in c for c in leaves), (leaf, leaves)
    # The counters of the first batch ride on the cell note: the balancing
    # biases, set on that batch, send each of the 8 experts 2 x 64 / 8 rows,
    # so the 4 held ones get half the tokens, a row or two apart.
    load = noted["choices_differing_from_the_reference"]["expert_load"]
    assert load["row_buffer"] == 128 and len(load["rows_by_layer"]) == 2
    assert all(abs(rows - 64) <= 4 for rows in load["rows_by_layer"]), load
    assert all(big <= 18 for big in load["largest_by_layer"]), load


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1, "zaya-causal-1x16384x1")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size_held"]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 40} and cfg[
        "num_hidden_layers"] == 4
    assert (cfg["num_experts_held"], cfg["vocab_size_held"]) == (
        16 // 2, 262272 // 2)
    assert "2 chips share each layer" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    for key in ("cca_conv_biases", "cca_conv_order", "cca_value_shift",
                "cca_temperature", "cca_qk_norm", "residual_scaling",
                "router_gamma", "router_gelu", "skip_expert",
                "balancing_bias", "auxiliary_loss", "precision",
                "parameters", "learning_rate", "initializers",
                "recomputation"):
        assert len(cfg["assumed"][key]) >= 20, key
    zcfg = zaya._zaya_config(cfg, rehearse=False)
    assert (zcfg.vocab_size, zcfg.num_layers) == (131136, 4)
    assert (zcfg.num_experts, zcfg.experts_held, zcfg.first_expert,
            zcfg.num_experts_per_tok) == (16, 8, 0, 1)
    assert (zcfg.num_heads, zcfg.num_kv_heads, zcfg.head_dim,
            zcfg.moe_intermediate_size, zcfg.router_hidden_size,
            zcfg.cca_time0, zcfg.cca_time1) == (8, 2, 128, 2048, 256, 2, 2)
    assert (zcfg.rope_theta, zcfg.partial_rotary_factor,
            zcfg.rms_norm_eps) == (5e6, 0.5, 1e-5)


def test_parameter_count_of_one_chips_share():
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    _, cfg, _ = _files()
    zcfg = zaya._zaya_config(cfg, rehearse=False)
    variables = jax.eval_shape(
        lambda k: models.Zaya(zcfg).init(k, jnp.zeros((1, 16), jnp.int32)),
        jax.random.key(0))
    attention = 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
    convolutions = 2 * 1280 + 2 * 10 * 128 * 128
    router = 2048 * 256 + 256 + 256 + 2 * (256 * 256 + 256) + 256 * 16
    experts = 8 * 3 * 2048 * 2048
    assert (attention, convolutions, router, experts) == (
        5_242_880, 330_240, 660_480, 100_663_296)
    first = attention + convolutions + 2 + router + 2 * 2048 + 4 * 2048 \
        + experts
    later = first + 256 + 4 * 2048              # gamma, a second scaled sum
    want = 131136 * 2048 + first + 3 * later + 4 * 2048 + 2048
    assert want == 696_238_856                         # 11.14 GB at 16 bytes
    leaves = jax.tree_util.tree_leaves(variables["params"])
    assert sum(int(np.prod(x.shape)) for x in leaves) == want
    assert all(x.dtype == jnp.float32 for x in leaves)
    assert str(want) in cfg["assumed"]["parameters"].replace(",", "")
    # the balancing biases are state, not parameters
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        variables["balancing"])) == 4 * 16


def test_model_flops_by_hand():
    """What the algorithm needs: attention over the causal pairs, the
    experts over the rows an even router sends to the 8 held ones, the head
    over the positions that predict and the held slice."""
    _, cfg, traffic = _files()
    macs = zaya_flops.forward_macs({**cfg["assumed"], **cfg}, traffic)
    positions, pairs = 16384, 16384 * 16385 // 2
    assert zaya_flops.causal_pairs(16384) == pairs
    want = {"projections": 4 * positions * 2048 * (1280 + 256 + 1024),
            "cca_conv1": 4 * positions * 2 * 1280 * 128,
            "attention": 4 * pairs * 8 * 128 * 2,
            "router": 4 * positions * 256 * (2048 + 512 + 16),
            "experts": 4 * (positions // 2) * 3 * 2048 * 2048,
            "head": (positions - 1) * 2048 * 131136}
    assert macs == pytest.approx(want, rel=1e-12)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert zaya.model_flops(cell) == pytest.approx(
        6 * sum(want.values()), rel=1e-12)
    # 37.7 TFLOP a step, the head 70 % of it
    assert 37.8e12 < zaya.model_flops(cell) < 38.0e12      # 37.9 TFLOP a step
    assert 0.69 < want["head"] / sum(want.values()) < 0.71


def test_flash_step_least_by_hand():
    """L (L + 1) / 2 pairs a query head; q-side arrays over the rows of 8
    heads, key/value-side arrays over the rows of 2, once a group."""
    got = zaya_flops.flash_step_least(_context())
    pairs, d, heads, kv, layers = 16384 * 16385 // 2, 128, 8, 2, 4
    q_rows, kv_rows = 16384 * heads * layers, 16384 * kv * layers
    for name, matmuls, q_arrays, kv_arrays, stats in (
            ("fwd", 2, 2, 2, 1), ("dq", 3, 3, 2, 2), ("dkv", 4, 2, 4, 2)):
        by_flops = matmuls * 2 * pairs * d * heads * layers / 197e12
        nbytes = (q_arrays * q_rows + kv_arrays * kv_rows) * d * 2 \
            + stats * q_rows * 4
        assert by_flops > nbytes / 819e9
        kernel = got["kernels"][name]
        assert kernel["bound"] == "flops"
        assert kernel["seconds"] == pytest.approx(by_flops, rel=1e-12)
        assert kernel["bytes"] == pytest.approx(nbytes, rel=1e-12)
    # 9 matmuls over the causal pairs: 50.2 ms a step at the peak.
    assert 0.0501 < got["seconds"] < 0.0503


def test_experts_and_head_least_by_hand():
    ctx = _context()
    even = zaya_flops.experts_step_least(ctx)
    assert even["rows"] == 4 * 8192
    assert even["flops"] == pytest.approx(18 * 4 * 8192 * 2048 * 2048,
                                          rel=1e-12)
    assert even["bound"] == "flops" and 0.0125 < even["seconds"] < 0.0126
    ctx["cell"] = {"expert_load": [[1000] * 7 + [1600], [1024] * 8]}
    assert zaya_flops.routed_rows(ctx) == 8600 + 8192
    assert zaya_flops.experts_step_least(ctx)["rows"] == 16792
    head = zaya_flops.head_step_least(ctx)
    assert head["flops"] == pytest.approx(6 * 16384 * 2048 * 131136,
                                          rel=1e-12)
    assert 0.1339 < head["seconds"] < 0.1341            # 134 ms at the peak


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model, its seeded variables with every scale, bias,
    ``gamma`` and temperature moved off what it starts at (a fault in how
    one enters is not hidden by a one or a zero), its first batch, the
    balancing biases set on it."""
    import jax

    _, cfg, traffic = _files(rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = zaya.setup(cfg, mesh, seed=7, rehearse=True)

    def stir(path, leaf):
        if leaf.ndim != 1:
            return leaf
        key = jax.random.fold_in(jax.random.key(9), len(
            jax.tree_util.keystr(path)) + leaf.shape[0])
        return leaf + 0.3 * jax.random.normal(key, leaf.shape)

    cell["params"] = {**cell["params"],
                      "params": jax.tree_util.tree_map_with_path(
                          stir, cell["params"]["params"])}
    cell["batches"] = traffic_gen.make_batches(
        traffic, zaya.inputs(cell, traffic), mesh, 7)
    zaya.balance(cell)
    return cell, traffic


def test_the_family_draws_ids_of_the_held_slice_and_balances_on_them(tiny):
    import jax

    cell, traffic = tiny
    ids = np.asarray(cell["batches"][0][0])
    assert ids.shape == (2, 64) and 0 <= ids.min() and ids.max() < 512
    bias = jax.tree_util.tree_leaves(cell["params"]["balancing"])
    assert len(bias) == 2 and all(np.any(b) for b in bias)
    assert list(zaya.sample_positions(16384)[[0, 1, -1]]) == [63, 127, 16383]
    assert len(zaya.sample_positions(64)) == 64


def test_the_samples_error_leaves_the_own_tokens_logit_out():
    """Under a tied head a token's own logit dwarfs the others and does not
    move with the blocks: left in, it hides a fault of theirs."""
    rng = np.random.default_rng(0)
    want = rng.standard_normal((4, 50))
    own = np.array([3, 7, 7, 49])
    want[np.arange(4), own] = 2048.0
    got = want + 0.1 * rng.standard_normal(want.shape)
    with_own = common.l2_rel_err(got, want)
    assert with_own < 1e-3 < 0.05 < zaya.sample_error(got, want, own) < 0.2
    spiked = got.copy()
    spiked[np.arange(4), own] += 100.0
    assert zaya.sample_error(spiked, want, own) == zaya.sample_error(
        got, want, own)


# Which limit is there to catch which fault (check_readings/zaya.json holds
# what each reads at the cell's own size on the chip).
CAUGHT_BY = {
    "value_shift_left_out": "sample_logits",
    "qk_mean_left_out": "sample_logits",
    "conv1_not_grouped_by_head": "sample_logits",
    "rotary_on_the_whole_head": "sample_logits",
    "l2_norm_left_out": "sample_logits",
    "gate_renormalised": "sample_logits",
    "bias_added_into_the_gate": "sample_logits",
    "residual_bias_outside_its_scale": "sample_logits",
    "temperature_left_out": "sample_logits",
    "loss_on_the_token_itself": "first_loss",
    "state_not_handed_on": "router_probs",
    "gamma_left_out": "router_probs",
    "router_in_bfloat16": "router_probs",
    "bf16_throughout": "router_probs"}
LIMIT_OF = {"sample_logits": zaya.TOL_SAMPLE_LOGITS,
            "first_loss": zaya.TOL_FIRST_LOSS,
            "router_probs": zaya.TOL_ROUTER_PROBS}


@pytest.fixture(scope="module")
def fault_readings(tiny):
    cell, traffic = tiny
    return zaya_faults.readings(
        list(CAUGHT_BY), common.first_shard(cell["params"]), cell["zcfg"],
        cell["batches"][0][0], sequences=traffic["batch_per_chip"])


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_fault_reads_over_the_limit_that_is_there_to_catch_it(
        fault, fault_readings):
    """Each fault of ISSUE 36's list, and the three that a one or a zero
    hides at initialisation, made in the plain reference at ``--rehearse``'s
    sizes on weights whose scales, biases, ``gamma`` and temperatures are
    moved: refused by its check with the rule's room."""
    assert set(CAUGHT_BY) == set(zaya_faults.FAULTS)
    assert set(zaya_faults.HIDDEN_AT_INITIALISATION) < set(CAUGHT_BY)
    measure = CAUGHT_BY[fault]
    assert fault_readings[fault][measure] > MARGIN * LIMIT_OF[measure], (
        fault, fault_readings[fault])


def test_parameters_kept_in_bfloat16_read_over_the_first_updates_limit():
    """Check (e): the learning rate is far below a bfloat16 ulp of a weight
    near 0.02, so parameters kept in bfloat16 lose the first update to
    rounding; float32 ones follow plain AdamW to their own rounding, a
    kernel's entries closely and an entry of order one by a fifth."""
    import jax.numpy as jnp
    import optax

    from benchmark.families import bert

    args = _files()[1]["optimizer"]["args"]
    rng = np.random.default_rng(0)
    tx = optax.adamw(**args)

    def bf16(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    def one_step(before):
        grad = (rng.standard_normal(before.shape) * 1e-3).astype(np.float32)
        updates, state = tx.update(jnp.asarray(grad), tx.init(before), before)
        mu, nu = np.asarray(state[0].mu), np.asarray(state[0].nu)
        after = np.asarray(optax.apply_updates(jnp.asarray(before), updates))

        def reading(before, after):
            return common.l2_rel_err(
                after.astype(np.float64) - before,
                bert.adamw_first_update(before, mu, nu, **args))

        return (reading(before, after), reading(
            bf16(before), bf16(bf16(before) + np.asarray(updates))))

    kernel = (rng.standard_normal((256, 2048)) / 45).astype(np.float32)
    sound, kept_in_bf16 = one_step(kernel)
    assert MARGIN * sound < zaya.TOL_FIRST_UPDATE
    assert kept_in_bf16 > MARGIN * zaya.TOL_FIRST_UPDATE_UNIT
    for unit in (rng.standard_normal((256, 2048)).astype(np.float32),
                 np.ones((2048,), np.float32)):
        sound, kept_in_bf16 = one_step(unit)
        assert MARGIN * sound < zaya.TOL_FIRST_UPDATE_UNIT
        assert kept_in_bf16 > MARGIN * zaya.TOL_FIRST_UPDATE_UNIT


def _rehearsal_in_this_process(monkeypatch, capsys, seed) -> dict:
    """The whole of a run past its look for a chip (``--rehearse``), in this
    process, so that what a test has patched underneath is what runs: the
    result line."""
    import jax

    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    settings = ("jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
    kept = {k: getattr(jax.config, k) for k in settings}
    try:
        code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.2", "--trace", "0", "--rehearse"])
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch,
                                                                capsys):
    """The compiled step wrapped so that it hands back the state it was
    given: ``correct`` comes out false, and the last line names the first
    updates that did not happen and the losses that did not fall."""
    import jax
    import jax.numpy as jnp

    class Stuck:
        def __init__(self, step):
            self.step = step

        def __call__(self, variables, opt_state, *batch):
            kept = jax.tree_util.tree_map(jnp.copy, (variables, opt_state))
            *_, loss = self.step(variables, opt_state, *batch)
            return (*kept, loss)

        def __getattr__(self, name):    # as_text, memory_analysis
            return getattr(self.step, name)

    def build(cell, real=zaya.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(zaya, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=5)
    assert result["correct"] is False
    refused = {n for n, e in result["checks"].items() if e.get("ok") is False}
    assert "losses_finite_and_falling" in refused
    assert len([n for n in refused if n.startswith("first_moment")]) == len(
        CHECKED_LEAVES)
    # A leaf of zeros whose moments stayed zero has no update to miss: plain
    # AdamW of what the stuck step left behind moves ``c`` nowhere either.
    assert {n for n in result["checks"] if n.startswith("first_update")
            and n not in refused} == {"first_update.layer_1.res_attn.c"}
    # What the broken step leaves alone still reads sound.
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  "router_probs_of_the_reference_s_input_vs_reference",
                  "choices_differing_from_the_reference"):
        assert "ok" not in result["checks"][sound]


def test_a_program_whose_router_is_in_bfloat16_is_not_correct(monkeypatch,
                                                              capsys):
    """The program's router (``models/zaya.py:ZayaRouter``, which the
    model's expert layers and check (c) both call) with its input, the state
    handed down and what it hands on rounded to bfloat16, in the program's
    place through a whole run: ``correct`` comes out false by the router's
    own check, with the rule's room."""
    import jax.numpy as jnp

    from horovod_tpu.models import zaya as model_zaya

    def low(a):
        return None if a is None else a.astype(jnp.bfloat16).astype(
            jnp.float32)

    def rounded(self, x, s, real=model_zaya.ZayaRouter.__call__):
        probs, state = real(self, low(x), low(s))
        return low(probs), low(state)

    monkeypatch.setattr(model_zaya.ZayaRouter, "__call__", rounded)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=6)
    assert result["correct"] is False
    refused = {n for n, e in result["checks"].items() if e.get("ok") is False}
    assert "router_probs_of_the_reference_s_input_vs_reference" in refused
    entry = result["checks"][
        "router_probs_of_the_reference_s_input_vs_reference"]
    assert entry["value"] > MARGIN * zaya.TOL_ROUTER_PROBS
