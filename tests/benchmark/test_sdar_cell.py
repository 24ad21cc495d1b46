"""The cell ``sdar-moe-ep8-s4096``: its rehearsal on the CPU, the published
widths in its configuration, its analytic multiply-adds and its kernels'
least work against numbers worked out by hand, the model against the plain
reference, the faults its limits are there to catch, and its timed path
broken underneath.  Nothing here measures anything."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, run, sdar_flops  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import sdar  # noqa: E402
from benchmark.references import sdar as reference_sdar  # noqa: E402

import sdar_faults  # noqa: E402  (beside this file)

CELL = "sdar-moe-ep8-s4096"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of JetLM/SDAR-30B-A3B-Chat, as the catalog of the model-configs
# guide holds it.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


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


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One ``run.py --rehearse`` of the cell in a process of its own."""
    tmp_path = tmp_path_factory.mktemp("rehearsal")
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
    return done


def test_rehearsal_prints_the_contract_keys_and_no_metric(rehearsal):
    """``run.py --rehearse`` at tiny sizes (4 of 8 experts held from the
    third on, top-2, 4 query heads on 2): every check against the plain
    reference passes and no CPU number is written as a metric."""
    done = rehearsal
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
        assert len(leaves) == 7, leaves
        for leaf in ("embed", "q_proj", "k_proj", "v_proj", "router",
                     "layer_1']['moe']['w_down", "lm_head"):
            assert any(leaf in c for c in leaves), (leaf, leaves)
    # The counters of the first batch ride on the cell note: rows routed to
    # the 4 held experts of each of the 2 layers.
    load = noted["choices_differing_from_the_reference"]["expert_load"]
    assert len(load["rows_by_layer"]) == 2
    assert all(0 < rows <= load["row_buffer"] == 2 * 128 * 2
               for rows in load["rows_by_layer"])


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (
        1, "sdar-blockdiff-2x4096x1")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size_held"]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 48} and cfg[
        "num_hidden_layers"] == 5
    assert (cfg["num_experts_held"], cfg["vocab_size_held"]) == (
        128 // 8, 151936 // 8)
    assert "8 chips share each layer" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["block_length"], traffic["distinct_batches"],
            traffic["warmup_steps"], traffic["trace_steps"]) == (
                2, 4096, 4, 64, 3, 10)
    assert cfg["assumed"]["block_length"] == traffic["block_length"]
    scfg = sdar._sdar_config(cfg, rehearse=False)
    assert (scfg.vocab_size, scfg.mask_token_id) == (18992, 18991)
    assert (scfg.num_experts, scfg.experts_held, scfg.first_expert) == (
        128, 16, 0)
    assert (scfg.num_heads, scfg.num_kv_heads, scfg.head_dim,
            scfg.moe_intermediate_size, scfg.num_experts_per_tok) == (
                32, 4, 128, 768, 8)


def test_parameter_count_of_one_chips_share():
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    _, cfg, _ = _files()
    scfg = sdar._sdar_config(cfg, rehearse=False)
    ids = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(lambda k: models.SDAR(scfg).init(k, ids, ids),
                            jax.random.key(0))
    per_layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128
                 + 2 * 2048 + 2048 * 128)
    assert per_layer == 19_140_864
    expert = 3 * 2048 * 768
    assert expert == 4_718_592
    want = 2 * 18992 * 2048 + 5 * (per_layer + 16 * expert) + 2048
    assert want == 550_984_960                       # 8.82 GB at 16 bytes
    assert sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params)) == want
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(params))


def test_model_flops_by_hand():
    """What the algorithm needs: attention over the live pairs of the mask,
    the experts over the rows an even router sends to the 16 held ones, the
    head over the noised half and the held slice."""
    _, cfg, traffic = _files()
    macs = sdar_flops.forward_macs({**cfg["assumed"], **cfg}, traffic)
    positions = 2 * 2 * 4096
    live = 4096 * 4096 + 4096 * 4                    # L^2 (1 + 1 / n)
    assert sdar_flops.live_pairs(4096, 4) == {
        "clean_on_clean": 16 * 1024 * 1025 // 2,
        "noised_on_clean": 16 * 1024 * 1023 // 2,
        "noised_on_own_block": 4096 * 4, "kernels": 4096 * 4096,
        "all": live}
    assert live == 16_793_600
    want = {"projections": 5 * positions * 18_874_368,
            "attention": 5 * 2 * live * 32 * 128 * 2,
            "router": 5 * positions * 2048 * 128,
            "experts": 5 * positions * 4_718_592,    # 8 x 16 / 128 = 1 a row
            "head": 2 * 4096 * 2048 * 18992}
    assert macs == pytest.approx(want, rel=1e-12)
    assert sum(want.values()) == pytest.approx(3.65e12, rel=5e-3)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert sdar.model_flops(cell) == pytest.approx(
        6 * sum(want.values()), rel=1e-12)           # 21.9 TFLOP a step
    assert 21.8e12 < sdar.model_flops(cell) < 22.0e12


def test_flash_step_least_by_hand():
    """L^2 pairs a sequence and a query head; q-side arrays over 2L rows of
    32 heads, key/value-side arrays over the clean copy's L rows of 4 heads,
    once a group."""
    got = sdar_flops.flash_step_least(_context())
    pairs, d, heads, kv, layers, batch = 4096 * 4096, 128, 32, 4, 5, 2
    q_rows, kv_rows = 8192 * heads * batch * layers, 4096 * kv * batch * layers
    for name, matmuls, q_arrays, kv_arrays, stats in (
            ("fwd", 2, 2, 2, 1), ("dq", 3, 3, 2, 2), ("dkv", 4, 2, 4, 2)):
        by_flops = matmuls * 2 * pairs * d * heads * batch * layers / 197e12
        nbytes = (q_arrays * q_rows + kv_arrays * kv_rows) * d * 2 \
            + stats * q_rows * 4
        assert by_flops > nbytes / 819e9
        kernel = got["kernels"][name]
        assert kernel["bound"] == "flops"
        assert kernel["seconds"] == pytest.approx(by_flops, rel=1e-12)
        assert kernel["bytes"] == pytest.approx(nbytes, rel=1e-12)
    # 9 matmuls over L^2: 62.8 ms a step at the peak.
    assert got["seconds"] == pytest.approx(
        9 * 2 * pairs * d * heads * batch * layers / 197e12, rel=1e-12)
    assert 0.0627 < got["seconds"] < 0.0629


def test_experts_least_and_the_load_reader_by_hand():
    ctx = _context()
    even = sdar_flops.experts_step_least(ctx)
    assert even["rows"] == 5 * 16384
    assert even["flops"] == pytest.approx(18 * 5 * 16384 * 2048 * 768,
                                          rel=1e-12)
    assert even["bound"] == "flops" and 0.0117 < even["seconds"] < 0.0119
    assert sdar_flops.expert_load_max_over_mean(None, ctx) is None
    load = [[1000] * 15 + [1600], [1024] * 16]
    ctx["cell"] = {"expert_load": load}
    assert sdar_flops.routed_rows(ctx) == 16600 + 16384
    assert sdar_flops.experts_step_least(ctx)["rows"] == 32984
    assert sdar_flops.expert_load_max_over_mean(None, ctx) == pytest.approx(
        1600 * 16 / 16600)


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model, its seeded weights and its first batch."""
    import jax

    _, cfg, traffic = _files(rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = sdar.setup(cfg, mesh, seed=7, rehearse=True)
    drawn = traffic_gen.make_batches(traffic, sdar.inputs(cell, traffic),
                                     mesh, 7)[0]
    return cell, sdar.shape_batch(cell["scfg"], *drawn), traffic


def _reference_loss(p, batch, rcfg):
    sequences, length = batch["clean"].shape
    total = 0.0
    for i in range(sequences):
        logits, _ = reference_sdar.logits(
            p["params"], batch["clean"][i], batch["noised"][i], rcfg)
        total = total + reference_sdar.loss_sum(
            logits, batch["clean"][i], batch["masked"][i],
            batch["levels"][i])
    return total / (sequences * length)


def test_the_model_against_the_plain_reference(tiny):
    """Loss and every leaf's gradient on seeded weights: the program's
    model (grouped-query attention under the mask, q/k norms, rotary, the
    dropless share of the experts, the head on the noised half, the 1/t
    loss) against ``references/sdar.py``, which imports nothing of it."""
    import jax

    cell, batch, _ = tiny
    rcfg = sdar.reference_config(cell["scfg"])
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(
            lambda p: sdar._loss(cell["model"], p, batch))(cell["params"])
        want = jax.value_and_grad(
            lambda p: _reference_loss(p, batch, rcfg))(cell["params"])
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    got, want = common.leaf_paths(got[1]), common.leaf_paths(want[1])
    assert len(got) == 2 * 12 + 3
    for path, g in want.items():
        assert common.l2_rel_err(got[path], g) < 1e-4, path
        assert float(np.linalg.norm(g)) > 0, path


def test_the_family_shapes_what_the_generator_draws(tiny):
    cell, batch, traffic = tiny
    scfg = cell["scfg"]
    clean, noised = np.asarray(batch["clean"]), np.asarray(batch["noised"])
    masked, levels = np.asarray(batch["masked"]), np.asarray(batch["levels"])
    assert clean.shape == (2, 64) and clean.max() < scfg.mask_token_id
    assert ((noised == scfg.mask_token_id) == masked).all()
    assert (noised[~masked] == clean[~masked]).all()
    assert (levels > 0).all() and (levels <= 1).all()
    # one level a block of 4, and about a level's share of a block masked
    assert (levels.reshape(2, 16, 4) == levels.reshape(2, 16, 4)[..., :1]
            ).all()
    assert 0.2 < masked.mean() < 0.8
    with pytest.raises(ValueError, match="block_length"):
        sdar.inputs(cell, {**traffic, "block_length": 8})


RUN_SEEDS = (7, 3000000019)     # the ``tiny`` fixture's and the rehearsal's


def test_setup_at_two_run_seeds_gives_the_same_parameters_leaf_for_leaf(tiny):
    """The weights' key comes from the configuration (``--rehearse``'s from
    its ``rehearse`` group): the run's seed moves no leaf, another
    ``weights_seed`` moves every one."""
    cell, _, _ = tiny                                 # seed=RUN_SEEDS[0]
    cfg = cell["cfg"]
    other = sdar.setup(cfg, cell["mesh"], seed=RUN_SEEDS[1], rehearse=True)
    here = common.leaf_paths(cell["params"])
    there = common.leaf_paths(other["params"])
    assert list(here) == list(there) and len(here) == 2 * 12 + 3
    for path, leaf in here.items():
        assert np.array_equal(np.asarray(leaf), np.asarray(there[path])), path
    redrawn = {**cfg, "rehearse": {**cfg["rehearse"], "weights_seed": 8}}
    drawn = common.leaf_paths(sdar.setup(
        redrawn, cell["mesh"], seed=RUN_SEEDS[0], rehearse=True)["params"])
    scales = [p for p in here if p.endswith("['scale']")]   # ones, any key
    assert len(scales) == 2 * 4 + 1
    for path, leaf in here.items():
        assert (path in scales) == np.array_equal(
            np.asarray(leaf), np.asarray(drawn[path])), path


@pytest.mark.parametrize("argument", [0, 1, 2],
                         ids=["ids", "levels", "token_draws"])
def test_make_batches_at_two_run_seeds_draws_other_traffic(argument, tiny):
    """``--seed`` still reaches the generator: ids, the blocks' levels and
    the tokens' draws all differ between two run seeds, and the same seed
    gives the same batch."""
    cell, _, traffic = tiny
    drawn = [traffic_gen.make_batches(traffic, sdar.inputs(cell, traffic),
                                      cell["mesh"], seed)[0][argument]
             for seed in (*RUN_SEEDS, RUN_SEEDS[0])]
    a, b, again = (np.asarray(x) for x in drawn)
    assert a.shape == b.shape and (a != b).mean() > 0.9
    assert np.array_equal(a, again)


def test_the_configuration_names_its_weights_seed_and_says_why():
    _, cfg, _ = _files()
    seed = cfg["assumed"]["weights_seed"]
    assert isinstance(seed, int) and 0 <= seed < 2 ** 32
    assert sdar.weights_seed(cfg) == seed
    assert sdar.weights_seed(cfg, rehearse=True) == cfg["rehearse"][
        "weights_seed"]
    why = cfg["assumed"]["weights_seed_why"]
    for said in ("checkpoint", "--seed", "traffic", "rows", str(seed)):
        assert said in why, said
    # the top level of the file stays the published config's keys
    assert "weights_seed" not in cfg


@pytest.mark.parametrize("named", ["nothing", True, "2147485001", 1.0],
                         ids=["nothing", "a_bool", "a_string", "a_float"])
def test_the_family_refuses_a_configuration_without_a_weights_seed(named):
    """By name, before any weight is made: it makes no key up."""
    import jax

    _, cfg, _ = _files(rehearse=True)
    assumed = {k: v for k, v in cfg["assumed"].items() if k != "weights_seed"}
    if named != "nothing":
        assumed["weights_seed"] = named
    broken = {**cfg, "assumed": assumed}
    for rehearse in (False, True):
        with pytest.raises(KeyError, match="assumed.weights_seed"):
            sdar.weights_seed(broken, rehearse)
    with pytest.raises(KeyError, match="sdar-30b-a3b-ep8.*weights_seed"):
        sdar.setup(broken, common.hvd_mesh(jax.devices()[:1]), seed=7,
                   rehearse=True)


def test_two_run_seeds_rehearse_correct_from_different_first_losses(
        rehearsal, monkeypatch, capsys):
    """A whole rehearsal at each of two run seeds, the fixture's in its own
    process and one here: both end ``correct`` on the same weights, and the
    first losses differ because the batches do."""
    runs = [[json.loads(x) for x in rehearsal.stdout.strip().splitlines()],
            _rehearsal_lines(monkeypatch, capsys, seed=8)]
    cells = [next(x for x in lines if x.get("note") == "cell")
             for lines in runs]
    assert [c["seed"] for c in cells] == [3000000019, 8]
    assert all(lines[-1]["correct"] is True for lines in runs)
    losses = [c["first_loss"] for c in cells]
    assert all(np.isfinite(losses)) and abs(losses[0] - losses[1]) > 1e-3
    rows = [next(c["expert_load"]["rows_by_layer"] for c in cell["checks"]
                 if "expert_load" in c) for cell in cells]
    assert rows[0] != rows[1]


@pytest.mark.parametrize("steps,distinct,at", [
    (153, 1, 152), (153, 64, 128), (10, 64, 0), (64, 64, 0), (65, 64, 64),
    (7, 3, 6), (1, 3, 0)])
def test_the_first_loss_is_held_against_the_last_step_on_the_first_batch(
        steps, distinct, at):
    """Step i takes batch i modulo their number, and the first loss is the
    first batch's: with the cell's 64 batches the window's last loss is
    another batch's, and says nothing of whether the first one's fell."""
    losses = [100.0 - i for i in range(steps)]
    assert run.last_loss_of_the_first_batch(losses, distinct) == losses[at]
    assert at % distinct == 0 and at + distinct > steps - 1
    assert run.last_loss_of_the_first_batch([], distinct) is None


def test_choices_differing_counts_the_choices_the_reference_does_not_make():
    system = np.array([[[0, 1], [2, 3]], [[4, 5], [6, 7]]])
    reference_ = np.array([[[1, 0], [2, 9]], [[4, 5], [8, 9]]])
    assert sdar.choices_differing(system, system) == 0.0
    assert sdar.choices_differing(system, reference_) == pytest.approx(3 / 8)


def test_a_routed_leafs_moment_error_is_the_median_over_its_experts():
    """One heavy row sent to another expert moves two experts' gradients and
    leaves the median where it was; a fault that reaches every expert moves
    it; a dense leaf is compared whole."""
    rng = np.random.default_rng(0)
    want = rng.standard_normal((16, 8, 4))
    got = want * (1 + 1e-2 * rng.standard_normal(want.shape))
    path = "['params']['layer_4']['moe']['w_down']"
    sound = sdar.moment_error(path, got, want)
    flipped = got.copy()
    flipped[3] += 5.0
    flipped[7] -= 5.0
    assert sdar.moment_error(path, flipped, want) == pytest.approx(
        sound, rel=0.2)
    assert common.l2_rel_err(flipped, want) > 10 * sound
    assert sdar.moment_error(path, 2 * got, want) > 0.9
    router = "['params']['layer_0']['moe']['router']"
    assert sdar.moment_error(router, np.moveaxis(flipped, 0, 1),
                             np.moveaxis(want, 0, 1)) == pytest.approx(
                                 sound, rel=0.2)
    dense = "['params']['lm_head']['kernel']"
    assert sdar.moment_error(dense, flipped, want) == common.l2_rel_err(
        flipped, want)
    # an expert that got no row has no gradient and no say
    want[5] = 0
    assert np.isfinite(sdar.moment_error(path, got, want))


# Which limit is there to catch which fault (check_readings/sdar.json holds
# what each reads at the cell's own size on the chip).
CAUGHT_BY = {
    "noised_sees_its_own_clean_block": "sample_logits",
    "clean_sees_noised": "sample_logits",
    "key_head_i_mod_kv": "sample_logits",
    "noised_positions_offset_by_L": "sample_logits",
    "no_norm_topk": "sample_logits",
    "held_range_off_by_one": "sample_logits",
    "absent_experts_added": "sample_logits",
    "no_loss_weight": "first_loss",
    "bf16_throughout": "router_probs",
    "e4m3": "router_probs"}
LIMIT_OF = {"sample_logits": sdar.TOL_SAMPLE_LOGITS,
            "first_loss": sdar.TOL_FIRST_LOSS,
            "router_probs": sdar.TOL_ROUTER_PROBS}


@pytest.fixture(scope="module")
def fault_readings(tiny):
    cell, batch, traffic = tiny
    return sdar_faults.readings(list(CAUGHT_BY), cell["params"],
                                cell["scfg"], batch,
                                sequences=traffic["batch_per_chip"])


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_fault_reads_over_the_limit_that_is_there_to_catch_it(
        fault, fault_readings):
    """Each fault of ISSUE 34's list, made in the plain reference at
    ``--rehearse``'s sizes, is refused by its check with the rule's room."""
    assert set(CAUGHT_BY) == set(sdar_faults.FAULTS)
    measure = CAUGHT_BY[fault]
    assert fault_readings[fault][measure] > MARGIN * LIMIT_OF[measure], (
        fault, fault_readings[fault])


def test_parameters_kept_in_bfloat16_read_over_the_first_updates_limit():
    """Check (e): the learning rate 2e-7 is far below a bfloat16 ulp of a
    weight near 0.02, so parameters kept in bfloat16 lose the first update
    to rounding; float32 ones follow plain AdamW to their own rounding."""
    import jax.numpy as jnp
    import optax

    from benchmark.families import bert

    args = _files()[1]["optimizer"]["args"]
    rng = np.random.default_rng(0)
    before = (rng.standard_normal((256, 2048)) / 45).astype(np.float32)
    grad = (rng.standard_normal(before.shape) * 1e-3).astype(np.float32)
    tx = optax.adamw(**args)
    updates, state = tx.update(jnp.asarray(grad), tx.init(before), before)
    mu, nu = np.asarray(state[0].mu), np.asarray(state[0].nu)
    after = np.asarray(optax.apply_updates(jnp.asarray(before), updates))

    def bf16(x):
        return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))

    def reading(before, after):
        return common.l2_rel_err(
            after.astype(np.float64) - before,
            bert.adamw_first_update(before, mu, nu, **args))

    assert MARGIN * reading(before, after) < sdar.TOL_FIRST_UPDATE
    kept_in_bf16 = bf16(bf16(before) + np.asarray(updates))
    assert reading(bf16(before), kept_in_bf16) > MARGIN * max(
        sdar.TOL_FIRST_UPDATE, sdar.TOL_FIRST_UPDATE_EMBEDDING)
    # An embedding entry is of order one: 2e-7 is two or three of its ulps,
    # and the float32 update itself is rounded by a tenth to a quarter.
    wide = (rng.standard_normal((256, 2048))).astype(np.float32)
    updates, state = tx.update(jnp.asarray(grad), tx.init(wide), wide)
    mu, nu = np.asarray(state[0].mu), np.asarray(state[0].nu)
    moved = np.asarray(optax.apply_updates(jnp.asarray(wide), updates))
    assert sdar.TOL_FIRST_UPDATE < reading(wide, moved)
    assert MARGIN * reading(wide, moved) < sdar.TOL_FIRST_UPDATE_EMBEDDING


def _rehearsal_in_this_process(monkeypatch, capsys, seed) -> dict:
    """The result line of ``_rehearsal_lines``."""
    return _rehearsal_lines(monkeypatch, capsys, seed)[-1]


def _rehearsal_lines(monkeypatch, capsys, seed) -> list:
    """The whole of a run past its look for a chip (``--rehearse``), in this
    process, so that what a test has patched underneath is what runs: every
    line it prints, the result last."""
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
    return [json.loads(x)
            for x in capsys.readouterr().out.strip().splitlines()]


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

        def __call__(self, params, opt_state, *batch):
            kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
            *_, loss = self.step(params, opt_state, *batch)
            return (*kept, loss)

        def __getattr__(self, name):    # as_text, memory_analysis
            return getattr(self.step, name)

    def build(cell, real=sdar.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(sdar, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=5)
    assert result["correct"] is False
    refused = {n for n, e in result["checks"].items() if e.get("ok") is False}
    assert "losses_finite_and_falling" in refused
    assert len([n for n in refused if n.startswith("first_update")]) == 7
    assert len([n for n in refused if n.startswith("first_moment")]) == 7
    # What the broken step leaves alone still reads sound.
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  "router_probs_of_the_reference_s_input_vs_reference",
                  "choices_differing_from_the_reference"):
        assert "ok" not in result["checks"][sound]


@pytest.mark.parametrize("low", ["bfloat16", "float8_e4m3fn"])
def test_a_program_whose_router_is_not_float32_is_not_correct(
        low, monkeypatch, capsys):
    """The program's router (``parallel/moe.py:route``, which the model's
    expert layers and check (c) both call) with its input and its kernel
    rounded to a lower precision and its product made there, in the
    program's place through a whole run: ``correct`` comes out false by the
    router's own check.  At initialisation nothing downstream of the
    activations' noise tells a bfloat16 router from a float32 one (the
    sample's logits and the moments read sound), which is why (c) is
    there."""
    import jax.numpy as jnp

    from horovod_tpu.parallel import moe

    def route(x, router_kernel, *args, real=moe.route, **kwargs):
        logits = jnp.dot(x.astype(low), router_kernel.astype(low))
        # ``real`` casts to float32 and multiplies by the identity kernel:
        # the rounded logits go through its softmax and top-k as they are.
        return real(logits.astype(jnp.float32),
                    jnp.eye(logits.shape[-1], dtype=jnp.float32), *args,
                    **kwargs)

    monkeypatch.setattr(moe, "route", route)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=6)
    assert result["correct"] is False
    refused = {n for n, e in result["checks"].items() if e.get("ok") is False}
    assert "router_probs_of_the_reference_s_input_vs_reference" in refused
    entry = result["checks"][
        "router_probs_of_the_reference_s_input_vs_reference"]
    assert entry["value"] > MARGIN * sdar.TOL_ROUTER_PROBS
    if low == "bfloat16":
        # what only the router's own check sees
        assert refused <= {
            "router_probs_of_the_reference_s_input_vs_reference",
            "choices_differing_from_the_reference"}, refused
