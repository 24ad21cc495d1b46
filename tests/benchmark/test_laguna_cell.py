"""The cell ``laguna-swa-ep32-s16384``: its rehearsal on the CPU, the published
sizes in its configuration, its analytic multiply-adds and its kernels' least
work against numbers worked out by hand, and its timed path broken underneath:
each fault of ISSUE 51 in the program's place through a whole run comes out
``correct`` false.  Nothing here measures anything."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, laguna_flops, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import laguna  # noqa: E402

import laguna_faults  # noqa: E402  (beside this file)

CELL = "laguna-swa-ep32-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of poolside/Laguna-S-2.1, as the catalog of the model-configs
# guide holds it: every number at the top level, and the nested groups.
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention", "sliding_attention",
                    "sliding_attention", "sliding_attention"] * 12,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}
HELD = {"num_experts_held": 8, "num_key_value_heads_held": 1,
        "num_attention_heads_per_layer_held": [6, 9, 9, 9, 6],
        "feed_forward_columns_held": 1536, "vocab_size_held": 12544}
# The leaves checks (d) and (e) compare: rows of the embedding, the head, a
# full and a sliding layer's q and gate kernels, the sliding layer's k, the
# dense pair, the first sparse block's router and shared pair, the last
# block's routed and shared down kernels.
CHECKED_LEAVES = 12


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
    """``run.py --rehearse`` at tiny sizes (the first two layers of the
    published order, a quarter of the key/value heads and of the experts
    held): every check
    against the plain reference passes, the notes say what is held and what
    the routers sent here, and no CPU number is written as a metric."""
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
    attention = next(x for x in lines if x.get("note") == "attention")
    assert [(x["kind"], x["query_heads_held"], x["window"],
             x["feed_forward"]) for x in attention["layers"]] == [
        ("full_attention", 2, None, "dense"),
        ("sliding_attention", 3, 8, "sparse")]
    # Off the TPU attention is its dense form: no kernel is in the step.
    assert set(attention["kernel_calls"].values()) == {0}
    assert attention["least_calls"] == {
        "hvd_flash_swa_fwd": 1, "hvd_flash_swa_dq": 1, "hvd_flash_swa_dkv": 1,
        "hvd_flash_fwd": 1, "hvd_flash_dq": 1, "hvd_flash_dkv": 1}
    load = next(x for x in lines if x.get("note") == "expert_load")
    # 2 x 48 tokens, 3 choices each, 4 of 16 experts held, 1 sparse layer.
    assert len(load["rows_by_held_expert"]) == 1
    assert all(len(layer) == 4 for layer in load["rows_by_held_expert"])
    assert load["rows_by_layer"] == [sum(layer) for layer in
                                     load["rows_by_held_expert"]]
    assert all(0 < rows <= 2 * 48 * 3 for rows in load["rows_by_layer"])
    noted = {c["name"]: c for c in next(
        x for x in lines if x.get("note") == "cell")["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "router_probs_of_the_reference_s_input_vs_reference",
            "choices_differing_from_the_reference",
            "first_sliding_attention_of_its_own_operands_vs_reference",
            "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    for kind in ("first_moment", "first_update"):
        assert len([c for c in noted
                    if c.startswith(kind)]) == CHECKED_LEAVES
    assert not any(c.startswith("calls_of_") for c in noted)


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1,
                                                  "laguna-causal-1x16384x1")
    assert "quarter" in entry["why"] and len(entry["why"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", *HELD]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 48} and cfg[
        "num_hidden_layers"] == 5
    assert {k: cfg[k] for k in HELD} == HELD
    # No reduced key is a published width's own key with _held on it.
    assert not any(k.endswith("_held") and k[:-5] in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "shared_expert_intermediate_size", "head_dim")
        for k in cfg["reduced"])
    assert "32-chip v5e slice" in cfg["deployment"]
    assert "further pipeline stages" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    for key in (*cfg["reduced"], "gate", "q_k_norm", "hidden_act", "router",
                "shared_expert", "rotary", "window", "weights_seed_why",
                "initializers",
                "precision", "parameters", "learning_rate", "optimizer_args",
                "expert_capacity_factor", "recomputation"):
        assert len(cfg["assumed"][key]) >= 20, key
    assert laguna.weights_seed(cfg) == cfg["assumed"]["weights_seed"] == 1002
    with pytest.raises(KeyError, match="names no whole number"):
        laguna.weights_seed({**cfg, "assumed": {}})
    lcfg = laguna._laguna_config(cfg, rehearse=False)
    assert lcfg.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3 + ("full_attention",)
    assert lcfg.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert lcfg.num_heads_per_layer == (48, 72, 72, 72, 48)
    assert [lcfg.heads_held(i) for i in range(5)] == [6, 9, 9, 9, 6]
    assert (lcfg.kv_heads_held, lcfg.columns_held, lcfg.experts_held,
            lcfg.first_expert, lcfg.rows_held) == (1, 1536, 8, 0, 12544)
    assert (lcfg.hidden_size, lcfg.head_dim, lcfg.sliding_window,
            lcfg.intermediate_size, lcfg.moe_intermediate_size,
            lcfg.shared_expert_intermediate_size, lcfg.num_experts,
            lcfg.num_experts_per_tok, lcfg.routed_scaling_factor,
            lcfg.rms_norm_eps) == (
                3072, 128, 512, 12288, 1024, 1024, 256, 10, 2.5, 1e-6)
    assert (lcfg.rope_full.rope_type, lcfg.rope_full.factor,
            lcfg.rope_full.original_max_position_embeddings,
            lcfg.rope_sliding.rope_theta) == ("yarn", 128, 8192, 10000)


def test_model_flops_by_hand():
    """What the algorithm needs: the projections with the gate's columns,
    attention over the band's pairs on the three sliding layers and the
    causal pairs on the two full ones, the dense layer, the routers, the
    experts over the rows an even router sends here, the shared expert over
    every position, the head over the positions that predict."""
    _, cfg, traffic = _files()
    macs = laguna_flops.forward_macs({**cfg["assumed"], **cfg}, traffic)
    positions, causal = 16384, 16384 * 16385 // 2
    band = 512 * 513 // 2 + (16384 - 512) * 512
    assert laguna_flops.band_pairs(16384, 512) == band == 8_257_792
    assert laguna_flops.band_pairs(16384, 16384) == causal
    assert laguna_flops.band_pairs(100, 1) == 100
    want = {"projections": positions * 3072 * (
                2 * (2 * 768 + 256 + 6) + 3 * (2 * 1152 + 256 + 9)),
            "attention": (2 * causal * 6 + 3 * band * 9) * 128 * 2,
            "dense": positions * 3 * 3072 * 1536,
            "router": 4 * positions * 3072 * 256,
            "experts": 4 * (positions * 10 * 8 / 256) * 3 * 3072 * 1024,
            "shared": 4 * positions * 3 * 3072 * 1024,
            "head": (positions - 1) * 3072 * 12544}
    assert macs == pytest.approx(want, rel=1e-12)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert laguna.model_flops(cell) == pytest.approx(
        6 * sum(want.values()), rel=1e-12)
    assert 16.5e12 < laguna.model_flops(cell) < 16.7e12  # 16.6 TFLOP a step
    # The band is a sixteenth of the triangle, to the first window's rows.
    assert 16.2 < causal / band < 16.3


def test_flash_least_work_by_hand():
    """The banded kernels over the band's own pairs of 3 x 9 query heads,
    the un-banded ones over the causal pairs of 2 x 6; key/value arrays once
    a group; bfloat16 at the v5e's peaks: all six bound by their FLOPs."""
    ctx = _context()
    band = 8_257_792
    swa = laguna_flops.flash_swa_step_least(ctx)
    full = laguna_flops.flash_full_step_least(ctx)
    assert swa["kernels"]["fwd"]["flops"] == 2 * 2.0 * band * 27 * 128
    assert swa["kernels"]["dkv"]["flops"] == 4 * 2.0 * band * 27 * 128
    rows, wide = 16384, 128 * 2
    assert swa["kernels"]["fwd"]["bytes"] == (
        2 * 27 * rows * wide + 2 * 3 * rows * wide + 27 * rows * 4)
    assert full["kernels"]["dq"]["flops"] == (
        3 * 2.0 * (16384 * 16385 // 2) * 12 * 128)
    assert full["kernels"]["dq"]["bytes"] == (
        3 * 12 * rows * wide + 2 * 2 * rows * wide + 2 * 12 * rows * 4)
    assert {k["bound"] for k in (*swa["kernels"].values(),
                                 *full["kernels"].values())} == {"flops"}
    assert swa["seconds"] == pytest.approx(
        9 * 2.0 * band * 27 * 128 / 197e12)
    # 2.6 ms a step in the band, 18.8 ms under the two triangles.
    assert 0.0026 < swa["seconds"] < 0.00262
    assert 0.0188 < full["seconds"] < 0.0189
    # The experts from the counters where the cell has them.
    even = laguna_flops.experts_step_least(ctx)
    assert even["rows"] == 4 * 5120
    counted = laguna_flops.experts_step_least({**ctx, "cell": {
        "expert_load": [[700] * 8, [600] * 8]}})
    assert counted["rows"] == 8 * 1300
    assert counted["flops"] == 3 * 2.0 * 10400 * 3 * 3072 * 1024


def test_the_step_hands_out_what_its_routers_chose():
    """The state's third slot after a step holds, per sparse layer, what the
    routers chose on that step's batch: at the rehearsal's float32 the
    choices of the system's forward on the same weights (no near-tie for
    bfloat16 to cut differently), ten different experts a token; ``checks``
    runs the reference on them."""
    import jax
    import numpy as np

    _, cfg, _ = _files()
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "laguna-causal-1x16384x1.json"),
        rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = laguna.setup(cfg, mesh, seed=4, rehearse=True)
    cell["batches"] = traffic_gen.make_batches(
        traffic, laguna.inputs(cell, traffic), mesh, seed=4)
    (ids,) = cell["batches"][0]
    _, want, _, _ = laguna._system_forward(
        cell, common.first_shard(cell["params"]), ids,
        laguna.sample_positions(ids.shape[1]))
    step, state = laguna.build(cell)
    assert not np.asarray(state[2]).any()           # nothing chosen yet
    *state, _ = step(*state, ids)
    lcfg = cell["lcfg"]
    assert state[2].shape == (len(laguna._sparse_layers(lcfg)), ids.size,
                              lcfg.num_experts_per_tok)
    assert np.array_equal(np.asarray(state[2]), np.asarray(want))
    assert all(len(set(row)) == lcfg.num_experts_per_tok
               for row in np.asarray(state[2])[0, :8].tolist())


def test_kernel_calls_are_counted_by_the_instructions_own_names():
    call = ' custom-call(f32[8] %u), custom_call_target="tpu_custom_call"'
    hlo = "\n".join([
        "%jvp_hvd_flash_swa_fwd_.1 = (bf16[8], f32[8])" + call,
        "%jvp_hvd_flash_swa_fwd_.2 = (bf16[8], f32[8])" + call,
        "%transpose_jvp_hvd_flash_swa_dq__.3 = bf16[8]" + call,
        "%hvd_flash_swa_dkv.4 = (bf16[8], bf16[8])" + call,
        "%jvp_hvd_flash_fwd_.2 = bf16[8]" + call,
        "%hvd_flash_dq.5 = bf16[8]" + call, "%hvd_flash_dkv.5 = bf16[8]" + call,
        "%attn.3 = bf16[8]" + call,
        "%fusion.9 = f32[8] fusion(f32[8] %hvd_flash_swa_fwd.1), kind=kLoop"])
    assert laguna.kernel_calls(hlo) == {
        "hvd_flash_swa_fwd": 2, "hvd_flash_swa_dq": 1, "hvd_flash_swa_dkv": 1,
        "hvd_flash_fwd": 1, "hvd_flash_dq": 1, "hvd_flash_dkv": 1}


# ---------------------------------------------------------------------------
# The timed path broken underneath
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compile_cache_of_these_runs(tmp_path_factory):
    """One compile cache for this file's whole runs in this process, gone
    with the test's directory: the plain reference's programs and the
    optimizer's, the same from run to run, compile once (12 s a run without,
    7 with)."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def _rehearsal_in_this_process(monkeypatch, capsys, seed, cache) -> dict:
    """The whole of a run past its look for a chip (``--rehearse``), in this
    process, so that what a test has patched underneath is what runs: the
    result line.  The process's own cache settings come back after it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: cache)
    settings = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
    kept = {k: getattr(jax.config, k) for k in settings}
    try:
        jax.config.update("jax_compilation_cache_dir", cache)
        compilation_cache.reset_cache()
        code = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.2", "--trace", "0", "--rehearse"])
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert code == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _refused(result) -> set:
    return {n for n, e in result["checks"].items() if e.get("ok") is False}


# The check that is there to catch each fault, with its limit: the run must
# refuse it by that check, with the rule's room.
CAUGHT_BY = {
    "sliding_layer_run_global": (
        "first_sliding_attention_of_its_own_operands_vs_reference",
        laguna.TOL_SLIDING_ATTENTION),
    "window_one_key_short": (
        "first_sliding_attention_of_its_own_operands_vs_reference",
        laguna.TOL_SLIDING_ATTENTION),
    "gate_left_out": ("sample_logits_vs_reference",
                      laguna.TOL_SAMPLE_LOGITS),
    "shared_expert_left_out": ("sample_logits_vs_reference",
                               laguna.TOL_SAMPLE_LOGITS),
    "routed_scale_left_out": ("sample_logits_vs_reference",
                              laguna.TOL_SAMPLE_LOGITS),
    "plain_rotary_on_a_full_layer": ("sample_logits_vs_reference",
                                     laguna.TOL_SAMPLE_LOGITS),
    "router_in_bfloat16": (
        "router_probs_of_the_reference_s_input_vs_reference",
        laguna.TOL_ROUTER_PROBS)}


def test_every_fault_of_the_issue_is_run():
    assert set(CAUGHT_BY) == set(laguna_faults.PROGRAM_FAULTS)


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_program_with_a_fault_is_not_correct(
        fault, monkeypatch, capsys, compile_cache_of_these_runs):
    """The fault in the program's place (the model's and the expert layer's
    own functions, the reference untouched) through a whole run:
    ``correct`` comes out false, by the check that is there to catch it and
    with the rule's room."""
    with laguna_faults.program_with(fault):
        result = _rehearsal_in_this_process(
            monkeypatch, capsys, 11 + len(fault), compile_cache_of_these_runs)
    assert result["correct"] is False
    name, limit = CAUGHT_BY[fault]
    assert name in _refused(result), (fault, _refused(result))
    assert result["checks"][name]["value"] > MARGIN * limit
    # What the fault leaves alone still reads sound.
    assert "ok" not in result["checks"]["first_update.lm_head"]
    assert "ok" not in result["checks"]["parameters_and_moments_are_float32"]
    if "window" not in fault and "global" not in fault:
        assert "ok" not in result["checks"][
            "first_sliding_attention_of_its_own_operands_vs_reference"]
    if fault != "router_in_bfloat16":
        assert "ok" not in result["checks"][
            "router_probs_of_the_reference_s_input_vs_reference"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys, compile_cache_of_these_runs):
    """The compiled step wrapped so that it hands back the weights and the
    optimizer's state it was given: ``correct`` comes out false, and the last line names the first
    moments that were never written and the losses that did not fall."""
    import jax
    import jax.numpy as jnp

    class Stuck:
        def __init__(self, step):
            self.step = step

        def __call__(self, variables, opt_state, chosen, *batch):
            kept = jax.tree_util.tree_map(jnp.copy, (variables, opt_state))
            *_, chosen, loss = self.step(variables, opt_state, chosen, *batch)
            return (*kept, chosen, loss)

        def __getattr__(self, name):    # as_text, memory_analysis
            return getattr(self.step, name)

    def build(cell, real=laguna.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(laguna, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, 5,
                                        compile_cache_of_these_runs)
    assert result["correct"] is False
    refused = _refused(result)
    assert "losses_finite_and_falling" in refused
    for kind in ("first_moment", "first_update"):
        assert len([n for n in refused
                    if n.startswith(kind)]) == CHECKED_LEAVES
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  "first_sliding_attention_of_its_own_operands_vs_reference"):
        assert "ok" not in result["checks"][sound]
