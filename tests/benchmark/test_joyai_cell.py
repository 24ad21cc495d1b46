"""The cell ``joyai-mla-ep16-s16384``: its rehearsal on the CPU, the published
sizes in its configuration, its analytic multiply-adds and its kernels' least
work against numbers worked out by hand, and its timed path broken underneath:
each fault of ISSUE 54 in the program's place through a whole run comes out
``correct`` false.  Nothing here measures anything."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import common, flops, joyai_flops, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import joyai  # noqa: E402

import joyai_faults  # noqa: E402  (beside this file)

CELL = "joyai-mla-ep16-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of jdopensource/JoyAI-LLM-Flash, as the catalog of the
# model-configs guide holds it.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}
HELD = {"num_experts_held": 16, "num_attention_heads_held": 4,
        "feed_forward_columns_held": 896, "vocab_size_held": 16160}
# The leaves checks (d) and (e) compare: rows of the embedding, the head, the
# first layer's six attention kernels, two latent scales and dense pair, the
# first expert block's router and shared pair, the last block's routed and
# shared down kernels.
CHECKED_LEAVES = 15


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


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1, "joyai-causal-1x16384x1")
    assert "half" in entry["why"] and "2x" in entry["why"]
    assert len(entry["why"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers",
                              *HELD]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 40, "num_nextn_predict_layers": 1}
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) == (5,
                                                                           0)
    assert {k: cfg[k] for k in HELD} == HELD
    # No reduced key is a published width's own key with _held on it.
    assert not any(k.endswith("_held") and k[:-5] in PUBLISHED
                   and k[:-5] not in ("num_attention_heads", "vocab_size")
                   for k in cfg["reduced"])
    assert "16-chip v5e slice" in cfg["deployment"]
    assert "further pipeline stages" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    for key in (*cfg["reduced"], "balancing_bias", "multi_token_prediction",
                "rotary", "scale", "stored_layouts", "hidden_act", "router",
                "shared_expert", "weights_seed_why", "initializers",
                "precision", "parameters", "learning_rate", "optimizer_args",
                "expert_capacity_factor", "recomputation"):
        assert len(cfg["assumed"][key]) >= 20, key
    assert joyai.weights_seed(cfg) == cfg["assumed"]["weights_seed"] == 1002
    jcfg = joyai._joyai_config(cfg, rehearse=False)
    assert [jcfg.sparse(i) for i in range(5)] == [False] + [True] * 4
    assert (jcfg.heads_held, jcfg.columns_held, jcfg.experts_held,
            jcfg.first_expert, jcfg.rows_held) == (4, 896, 16, 0, 16160)
    assert (jcfg.hidden_size, jcfg.q_lora_rank, jcfg.kv_lora_rank,
            jcfg.qk_nope_head_dim, jcfg.qk_rope_head_dim, jcfg.qk_head_dim,
            jcfg.v_head_dim, jcfg.intermediate_size,
            jcfg.moe_intermediate_size, jcfg.num_experts,
            jcfg.num_experts_per_tok, jcfg.routed_scaling_factor,
            jcfg.rope_theta, jcfg.rms_norm_eps) == (
                2048, 1536, 512, 128, 64, 192, 128, 7168, 768, 256, 8, 2.5,
                32e6, 1e-6)


def test_model_flops_and_least_work_by_hand():
    """What the algorithm needs: the five latent projections, attention over
    the causal pairs at 192 + 128 a pair and head, the dense layer, the
    routers, the experts over the rows an even router sends here, the shared
    expert over every position, the head over the positions that predict;
    and the three paired kernels' least work, k_rope's bytes once a call."""
    _, cfg, traffic = _files()
    macs = joyai_flops.forward_macs({**cfg["assumed"], **cfg}, traffic)
    positions, causal = 16384, 16384 * 16385 // 2
    want = {"projections": 5 * positions * (
                2048 * 1536 + 1536 * 4 * 192 + 2048 * 576 + 512 * 4 * 256
                + 512 * 2048),
            "attention": 5 * causal * 4 * (192 + 128),
            "dense": positions * 3 * 2048 * 896,
            "router": 4 * positions * 2048 * 256,
            "experts": 4 * (positions * 8 * 16 / 256) * 3 * 2048 * 768,
            "shared": 4 * positions * 3 * 2048 * 768,
            "head": (positions - 1) * 2048 * 16160}
    assert macs == pytest.approx(want, rel=1e-12)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert joyai.model_flops(cell) == pytest.approx(
        6 * sum(want.values()), rel=1e-12)
    assert 15.4e12 < joyai.model_flops(cell) < 15.45e12  # 15.4 TFLOP a step
    # Latent attention, kernels and projections, is 56 % of the step.
    assert 0.55 < (want["attention"] + want["projections"]) / sum(
        want.values()) < 0.57
    ctx = _context()
    least = joyai_flops.flash_mla_step_least(ctx)
    pair_heads, rows = 5 * 4 * causal, 5 * 4 * 16384
    assert least["kernels"]["fwd"]["flops"] == 2.0 * pair_heads * 320
    assert least["kernels"]["dq"]["flops"] == 2.0 * pair_heads * 512
    assert least["kernels"]["dkv"]["flops"] == 2.0 * pair_heads * 640
    assert least["kernels"]["fwd"]["bytes"] == (
        rows * (4 * 128 + 64) * 2 + 5 * 16384 * 64 * 2 + rows * 4)
    assert least["kernels"]["dkv"]["bytes"] == (
        rows * (6 * 128 + 64) * 2 + 2 * 5 * 16384 * 64 * 2 + 2 * rows * 4)
    assert {k["bound"] for k in least["kernels"].values()} == {"flops"}
    assert 0.0401 < least["seconds"] < 0.0402     # 40.1 ms a step at the peak
    even = joyai_flops.experts_step_least(ctx)
    assert even["rows"] == 4 * 8192
    counted = joyai_flops.experts_step_least({**ctx, "cell": {
        "expert_load": [[500] * 16, [600] * 16]}})
    assert counted["rows"] == 16 * 1100
    assert counted["flops"] == 3 * 2.0 * 17600 * 3 * 2048 * 768


def test_kernel_calls_are_counted_by_the_instructions_own_names():
    call = ' custom-call(f32[8] %u), custom_call_target="tpu_custom_call"'
    hlo = "\n".join([
        "%jvp_hvd_flash_mla_fwd_.1 = (bf16[8], f32[8])" + call,
        "%jvp_hvd_flash_mla_fwd_.2 = (bf16[8], f32[8])" + call,
        "%transpose_jvp_hvd_flash_mla_dq__.3 = (bf16[8], bf16[8])" + call,
        "%hvd_flash_mla_dkv.4 = (bf16[8], bf16[8], f32[8])" + call,
        "%jvp_hvd_flash_fwd_.2 = bf16[8]" + call, "%attn.3 = bf16[8]" + call,
        "%fusion.9 = f32[8] fusion(f32[8] %hvd_flash_mla_fwd.1), kind=kLoop"])
    assert joyai.kernel_calls(hlo) == {
        "hvd_flash_mla_fwd": 2, "hvd_flash_mla_dq": 1, "hvd_flash_mla_dkv": 1}
    jcfg = joyai._joyai_config(_files()[1], rehearse=False)
    assert joyai.least_calls(jcfg) == dict.fromkeys(joyai.KERNELS, 5)


# ---------------------------------------------------------------------------
# Whole runs in this process: the sound one, and the timed path broken
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def compile_cache_of_these_runs(tmp_path_factory):
    """One compile cache for this file's whole runs in this process, gone
    with the test's directory: the plain reference's programs and the
    optimizer's, the same from run to run, compile once."""
    return str(tmp_path_factory.mktemp("jax_cache"))


def _rehearsal_in_this_process(monkeypatch, capsys, seed, cache) -> list:
    """The whole of a run past its look for a chip (``--rehearse``), in this
    process, so that what a test has patched underneath is what runs: the
    lines it printed.  The process's own cache settings come back after."""
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
    return [json.loads(x) for x in
            capsys.readouterr().out.strip().splitlines()]


def _refused(result) -> set:
    return {n for n, e in result["checks"].items() if e.get("ok") is False}


def test_rehearsal_is_correct_and_says_what_is_held(
        monkeypatch, capsys, compile_cache_of_these_runs):
    """``run.py --rehearse`` at tiny sizes (the dense layer and an expert
    layer, half of the heads and a quarter of the experts held): every check
    against the plain reference passes under a bias off zero, the notes say
    what is held and what the routers sent here, and no CPU number is written
    as a metric.  (``test_benchmark.py`` runs the same rehearsal in a process
    of its own.)"""
    lines = _rehearsal_in_this_process(monkeypatch, capsys, 3000000019,
                                       compile_cache_of_these_runs)
    result = lines[-1]
    assert set(result) == RESULT_KEYS | {"checks"}
    assert result["correct"] is True, result["checks"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    attention = next(x for x in lines if x.get("note") == "attention")
    assert attention["layers"] == [
        {"heads_held": 2, "qk_width": 24, "v_width": 16,
         "feed_forward": "dense"},
        {"heads_held": 2, "qk_width": 24, "v_width": 16,
         "feed_forward": "sparse"}]
    # Off the TPU attention is its dense form: no kernel is in the step.
    assert set(attention["kernel_calls"].values()) == {0}
    assert attention["least_calls"] == dict.fromkeys(joyai.KERNELS, 2)
    load = next(x for x in lines if x.get("note") == "expert_load")
    # 2 x 48 tokens, 3 choices each, 4 of 16 experts held, 1 expert layer.
    assert [len(layer) for layer in load["rows_by_held_expert"]] == [4]
    assert 0 < load["rows_by_layer"][0] <= 2 * 48 * 3
    noted = {c["name"]: c for c in next(
        x for x in lines if x.get("note") == "cell")["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "router_scores_of_the_reference_s_input_vs_reference",
            "router_weights_of_its_own_choices_vs_reference",
            "choices_differing_from_the_reference",
            "first_attention_of_its_own_operands_vs_reference",
            "first_latent_c_q_vs_reference", "first_latent_c_kv_vs_reference",
            "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    for kind in ("first_moment", "first_update"):
        assert len([c for c in noted
                    if c.startswith(kind)]) == CHECKED_LEAVES
    assert not any(c.startswith("calls_of_") for c in noted)


# The check that is there to catch each fault, with its limit: the run must
# refuse it by that check, with the rule's room.
CAUGHT_BY = {
    "scale_of_the_nope_width": (
        "first_attention_of_its_own_operands_vs_reference",
        joyai.TOL_FIRST_ATTENTION),
    "rotary_left_off_k_rope": ("sample_logits_vs_reference",
                               joyai.TOL_SAMPLE_LOGITS),
    "rotary_on_the_nope_lanes": ("sample_logits_vs_reference",
                                 joyai.TOL_SAMPLE_LOGITS),
    "kv_latent_norm_left_out": ("first_latent_c_kv_vs_reference",
                                joyai.TOL_FIRST_LATENTS),
    "softmax_for_sigmoid": (
        "router_scores_of_the_reference_s_input_vs_reference",
        joyai.TOL_ROUTER_SCORES),
    "bias_in_the_weights": ("router_weights_of_its_own_choices_vs_reference",
                            joyai.TOL_ROUTER_WEIGHTS),
    "renormalisation_left_out": (
        "router_weights_of_its_own_choices_vs_reference",
        joyai.TOL_ROUTER_WEIGHTS),
    "routed_scale_left_out": ("sample_logits_vs_reference",
                              joyai.TOL_SAMPLE_LOGITS),
    "shared_expert_left_out": ("sample_logits_vs_reference",
                               joyai.TOL_SAMPLE_LOGITS),
    "router_in_bfloat16": (
        "router_scores_of_the_reference_s_input_vs_reference",
        joyai.TOL_ROUTER_SCORES)}


def test_every_fault_of_the_issue_is_run():
    assert set(CAUGHT_BY) == set(joyai_faults.FAULTS)


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_program_with_a_fault_is_not_correct(
        fault, monkeypatch, capsys, compile_cache_of_these_runs):
    """The fault in the program's place (the model's own names, the
    reference untouched) through a whole run: ``correct`` comes out false, by
    the check that is there to catch it and with the rule's room."""
    with joyai_faults.program_with(fault):
        result = _rehearsal_in_this_process(
            monkeypatch, capsys, 11 + len(fault),
            compile_cache_of_these_runs)[-1]
    assert result["correct"] is False
    name, limit = CAUGHT_BY[fault]
    assert name in _refused(result), (fault, _refused(result))
    assert result["checks"][name]["value"] > MARGIN * limit
    # What the fault leaves alone still reads sound.
    assert "ok" not in result["checks"]["first_update.lm_head"]
    assert "ok" not in result["checks"]["parameters_and_moments_are_float32"]
    assert "ok" not in result["checks"]["first_latent_c_q_vs_reference"]
    if fault not in joyai_faults.ROUTER_FAULTS:
        assert "ok" not in result["checks"][
            "router_scores_of_the_reference_s_input_vs_reference"]
    # (The turn on the nope lanes is made at the kernels' door, past what the
    # layer sows as its operands: (f) reads it as well.)
    if fault not in ("scale_of_the_nope_width", "rotary_on_the_nope_lanes"):
        assert "ok" not in result["checks"][
            "first_attention_of_its_own_operands_vs_reference"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys, compile_cache_of_these_runs):
    """The compiled step wrapped so that it hands back the variables and the
    optimizer's state it was given: ``correct`` comes out false, and the last
    line names the first moments that were never written and the losses that
    did not fall."""
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

    def build(cell, real=joyai.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(joyai, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, 5,
                                        compile_cache_of_these_runs)[-1]
    assert result["correct"] is False
    refused = _refused(result)
    assert "losses_finite_and_falling" in refused
    for kind in ("first_moment", "first_update"):
        assert len([n for n in refused
                    if n.startswith(kind)]) == CHECKED_LEAVES
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  "first_attention_of_its_own_operands_vs_reference"):
        assert "ok" not in result["checks"][sound]
