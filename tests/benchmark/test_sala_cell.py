"""The cell ``sala-sparse-linear-tp4-s16384``: its rehearsal on the CPU, the
published sizes in its configuration, its analytic multiply-adds and its
kernels' least work against numbers worked out by hand, the faults of ISSUE 58
read at the rehearsal's sizes, and its timed path broken underneath: a fault
in the program's place through a whole run comes out ``correct`` false.
Nothing here measures anything."""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import common, flops, run, sala_flops  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import sala  # noqa: E402

import sala_faults  # noqa: E402  (beside this file)

CELL = "sala-sparse-linear-tp4-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of openbmb/MiniCPM-SALA, as the catalog of the model-configs
# guide holds it (mixer_types apart: the list is compared below).
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 16384,
    "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala",
    "num_attention_heads": 32, "num_hidden_layers": 32,
    "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
    "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
SPARSE_LAYERS = (0, 9, 16, 17, 22, 29, 30, 31)
HELD = {"lightning_heads_held": 8, "num_attention_heads_held": 8,
        "num_key_value_heads_held": 1, "feed_forward_columns_held": 4096,
        "vocab_size_held": 18362}
# The leaves check (e) compares: rows of the embedding, the head, the first
# sparse layer's five kernels and q norm, the first lightning layer's five
# kernels, q norm and output norm, the first block's gate/up pair and the
# last block's pair and down; (d) holds those of them that lie above every
# lightning layer's output norm (``sala.above_every_output_norm``): the head
# and the last block's two.
CHECKED_LEAVES = 18
HELD_BY_D = 3


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
    return {"cfg": cfg, "traffic": traffic, "peaks": peaks}


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1, "sala-causal-1x16384x1")
    assert "2.5 %" in entry["why"] and len(entry["why"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", *HELD]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 4
    assert cfg["mixer_types"] == [
        "minicpm4" if i in SPARSE_LAYERS else "lightning-attn"
        for i in range(32)]
    assert {k: cfg[k] for k in HELD} == HELD
    # No reduced key is a width: a count of heads, columns or rows held.
    assert not any(k.endswith(("_dim", "_rank", "_size"))
                   for k in cfg["reduced"])
    assert "four-chip v5e host" in cfg["deployment"]
    assert "further pipeline stages" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    assert cfg["assumed"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64, "topk": 64,
        "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    for key in (*cfg["reduced"], "sparse_config_why", "slopes", "lightning",
                "gates", "sparse_attention", "frame", "weights_seed_why",
                "initializers", "precision", "parameters", "learning_rate",
                "optimizer_args", "recomputation",
                "published_num_hidden_layers_why", "lightning_chunk_why"):
        assert len(cfg["assumed"][key]) >= 20, key
    assert sala.weights_seed(cfg) == cfg["assumed"]["weights_seed"] == 1003
    scfg = sala._sala_config(cfg, rehearse=False)
    assert (scfg.lightning_held, scfg.first_lightning_head, scfg.heads_held,
            scfg.kv_heads_held, scfg.columns_held, scfg.rows_held) == (
                8, 24, 8, 1, 4096, 18362)
    assert (scfg.hidden_size, scfg.head_dim, scfg.lightning_head_dim,
            scfg.intermediate_size, scfg.num_heads, scfg.num_kv_heads,
            scfg.lightning_heads, scfg.published_layers, scfg.rope_theta,
            scfg.rms_norm_eps, scfg.scale_emb, scfg.scale_depth,
            scfg.dim_model_base) == (4096, 128, 128, 16384, 32, 2, 32, 32,
                                     1e4, 1e-6, 12.0, 1.4, 256)
    assert scfg.selects(traffic["seq_len"]) and not scfg.selects(8192)
    # A file whose restated chunk parts from the kernels' is refused.
    broken = {**cfg, "assumed": {**cfg["assumed"], "lightning_chunk": 128}}
    with pytest.raises(ValueError, match="lightning_chunk 128"):
        sala._sala_config(broken, rehearse=False)


def test_model_flops_and_least_work_by_hand():
    """What the algorithm needs: both mixers' five projections, the selected
    attention over the chosen, causally visible pairs, the lightning layers'
    four products a chunk, the SwiGLU, the head; and the six kernels' least
    work."""
    _, cfg, traffic = _files()
    macs = sala_flops.forward_macs(cfg, traffic)
    positions = 16384
    # A query in block b takes min(b + 1, 64) blocks of 64, its own up to
    # itself: 64 queries a block.
    blocks = 64 * sum(min(b + 1, 64) for b in range(256))
    pairs = (blocks - positions) * 64 + positions * 65 // 2
    assert sala_flops.chosen_blocks(16384, 64, 64) == blocks == 919552
    chunk = 2 * (256 * 257 // 2) * 128 + 2 * 256 * 128 * 128
    want = {"lightning_projections": 3 * positions * 4096 * 5 * 1024,
            "sparse_projections": positions * 4096 * (3 * 1024 + 2 * 128),
            "sparse_attention": pairs * 2 * 1024,
            "lightning": 3 * 64 * 8 * chunk,
            "feed_forward": 4 * positions * 3 * 4096 * 4096,
            "head": (positions - 1) * 4096 * 18362}
    assert pairs == 58_335_232
    assert macs == pytest.approx(want, rel=1e-12)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert sala.model_flops(cell) == pytest.approx(6 * sum(want.values()),
                                                   rel=1e-12)
    assert 35.5e12 < sala.model_flops(cell) < 35.7e12   # 35.6 TFLOP a step
    # The two mechanisms the cell is for are 2.5 % of the step's FLOPs.
    mixers = want["sparse_attention"] + want["lightning"]
    assert 0.024 < mixers / sum(want.values()) < 0.025
    # Under dense_len the sparse layer is plain causal attention.
    short = sala_flops._sizes(cfg, {**traffic, "seq_len": 8192})
    assert sala_flops.visible_pairs(short) == 8192 * 8193 // 2
    ctx = _context()
    sel = sala_flops.flash_sel_step_least(ctx)
    assert sel["kernels"]["fwd"]["flops"] == 2.0 * 8 * pairs * 2 * 128
    assert sel["kernels"]["dq"]["flops"] == 2.0 * 8 * pairs * 3 * 128
    assert sel["kernels"]["dkv"]["flops"] == 2.0 * 8 * pairs * 4 * 128
    rows, bits = 8 * 16384, 16384 * 256 / 8
    assert sel["kernels"]["fwd"]["bytes"] == (
        (2 * rows + 2 * 16384) * 128 * 2 + rows * 4 + bits)
    assert sel["kernels"]["dkv"]["bytes"] == (
        (2 * rows + 4 * 16384) * 128 * 2 + 2 * rows * 4 + bits)
    assert {k["bound"] for k in sel["kernels"].values()} == {"flops"}
    light = sala_flops.lightning_step_least(ctx)
    array = 3 * 16384 * 1024 * 2
    assert light["kernels"]["fwd"]["flops"] == 2.0 * 3 * 8 * 64 * chunk
    assert light["kernels"]["dkv"]["flops"] == 2.0 * 3 * 8 * 64 * (
        4 * (256 * 257 // 2) * 128 + 3 * 256 * 128 * 128)
    assert (light["kernels"]["fwd"]["bytes"], light["kernels"]["dq"]["bytes"],
            light["kernels"]["dkv"]["bytes"]) == (4 * array, 4 * array,
                                                  6 * array)
    # At the chip's rates the lightning kernels are bound by their bytes.
    assert {k["bound"] for k in light["kernels"].values()} == {"bytes"}
    assert sala_flops.visited_over_chosen(None, {"cell": {"walk": {
        "chosen": 4.0, "visited": 10.0}}}) == 2.5
    assert sala_flops.visited_over_chosen(None, {"cell": {}}) is None


def test_kernel_calls_are_counted_by_the_instructions_own_names():
    call = ' custom-call(f32[8] %u), custom_call_target="tpu_custom_call"'
    hlo = "\n".join([
        "%jvp_hvd_lightning_fwd_.1 = bf16[8]" + call,
        "%jvp_hvd_lightning_fwd_.2 = bf16[8]" + call,
        "%transpose_jvp_hvd_lightning_dq__.3 = bf16[8]" + call,
        "%hvd_lightning_dkv.4 = (bf16[8], bf16[8])" + call,
        "%jvp_hvd_flash_sel_fwd_.5 = (bf16[8], f32[8])" + call,
        "%hvd_flash_sel_dkv.6 = (f32[8], f32[8])" + call,
        "%jvp_hvd_flash_fwd_.2 = bf16[8]" + call, "%attn.3 = bf16[8]" + call,
        "%fusion.9 = f32[8] fusion(f32[8] %hvd_lightning_fwd.1), kind=kLoop"])
    assert sala.kernel_calls(hlo) == {
        "hvd_lightning_fwd": 2, "hvd_lightning_dq": 1, "hvd_lightning_dkv": 1,
        "hvd_flash_sel_fwd": 1, "hvd_flash_sel_dq": 0, "hvd_flash_sel_dkv": 1}


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
    """``run.py --rehearse`` at tiny sizes (the first four layers, half of
    the heads held, a sequence of twice the shrunken ``dense_len``): every
    check against the plain reference passes, the notes say what is held and
    what the walk would visit, and no CPU number is written as a metric."""
    lines = _rehearsal_in_this_process(monkeypatch, capsys, 3000000019,
                                       compile_cache_of_these_runs)
    result = lines[-1]
    assert set(result) == RESULT_KEYS | {"checks"}
    assert result["correct"] is True, result["checks"]
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    attention = next(x for x in lines if x.get("note") == "attention")
    assert [x["mixer"] for x in attention["layers"]] == [
        "minicpm4"] + ["lightning-attn"] * 3
    assert (attention["lightning_heads_held"],
            attention["sparse_heads_held"]) == (2, [2, 1])
    # Off the TPU the mixers are their plain forms: no kernel is in the step.
    assert set(attention["kernel_calls"].values()) == {0}
    assert attention["least_calls"] == {
        "hvd_lightning_fwd": 3, "hvd_lightning_dq": 3, "hvd_lightning_dkv": 3,
        "hvd_flash_sel_fwd": 1, "hvd_flash_sel_dq": 1, "hvd_flash_sel_dkv": 1}
    # 2 sequences of 64 queries in blocks of 8, 4 blocks a query at most.
    walk = attention["walk"]
    assert walk["chosen"] == 2 * 8 * (1 + 2 + 3 + 4 * 5)
    assert walk["visited"] >= walk["chosen"] and walk["left_out"] == 2 * 32
    noted = {c["name"]: c for c in next(
        x for x in lines if x.get("note") == "cell")["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "block_scores_of_the_reference_s_q_and_k_vs_reference",
            "choices_differing_from_the_reference",
            "choices_breaking_the_reference_s_rule",
            "first_sparse_attention_of_its_own_operands_vs_reference",
            "first_lightning_attention_of_its_own_operands_vs_reference",
            "first_sparse_attention_s_gradients_of_its_own_operands_vs_"
            "reference",
            "first_lightning_attention_s_gradients_of_its_own_operands_vs_"
            "reference",
            "hidden_gradient_by_position_vs_reference",
            "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    by_position = noted["hidden_gradient_by_position_vs_reference"]
    assert by_position["median"] <= by_position["value"] <= by_position[
        "largest"] < 1e-4
    assert noted["choices_differing_from_the_reference"]["walk"] == walk
    assert len([c for c in noted if c.startswith("first_moment")]) == HELD_BY_D
    assert len([c for c in noted
                if c.startswith("first_update")]) == CHECKED_LEAVES
    below = noted["parameters_and_moments_are_float32"][
        "first_moments_below_an_output_norm"]
    assert len(below) == CHECKED_LEAVES - HELD_BY_D and max(
        below.values()) < 1e-4
    assert not any(c.startswith("calls_of_") for c in noted)


def test_every_fault_of_the_issue_reads_over_a_limit_at_rehearsal_sizes():
    """``sala_faults.py``'s twenty faults, made in the plain reference and
    read against the plain reference itself at the rehearsal's sizes in
    float32 (no gradient of the model taken: the first moments and the
    gradient by position are read on the chip; the two mixers' own gradients
    are): each reads the rule's margin over one limit at least, the fault of
    the backward alone over a limit of a gradient and over no other (what
    they read at the cell's own size, on the chip, is in
    check_readings/sala.json)."""
    import jax

    _, cfg, traffic = _files(rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = sala.setup(cfg, mesh, seed=11, rehearse=True)
    ids = traffic_gen.make_batches(traffic, sala.inputs(cell, traffic), mesh,
                                   11)[0][0]
    got = sala_faults.readings(list(sala_faults.FAULTS),
                               common.first_shard(cell["params"]),
                               cell["scfg"], ids, chunk=16, moments=False)
    limits = {"first_loss": sala.TOL_FIRST_LOSS,
              "sample_logits": sala.TOL_SAMPLE_LOGITS,
              "block_scores": sala.TOL_BLOCK_SCORES,
              "choices_differing": sala.TOL_CHOICES_DIFFERING,
              "choice_rule": sala.TOL_CHOICE_RULE,
              "first_sparse_attention": sala.TOL_FIRST_SPARSE_ATTENTION,
              "first_lightning": sala.TOL_FIRST_LIGHTNING,
              "first_sparse_grads": sala.TOL_FIRST_SPARSE_GRADS,
              "first_lightning_grads": sala.TOL_FIRST_LIGHTNING_GRADS}
    assert set(got) == set(sala_faults.FAULTS) and len(got) == 20
    for fault, read in got.items():
        over = [m for m, limit in limits.items() if read[m] > MARGIN * limit]
        if fault in sala_faults.NOT_REFUSED:
            # The kernels' own rounding and this fault's are neighbours: it is
            # read, and refused by no limit (check_readings/sala.json).
            assert read["first_lightning"] > 1e-3 and not over
            continue
        assert over, (fault, {m: read[m] for m in limits})
    assert got["plain_causal_for_selected"]["first_sparse_attention"] > (
        MARGIN * sala.TOL_FIRST_SPARSE_ATTENTION)
    assert got["topk_of_63"]["choice_rule"] > 0.5
    backward = got["dkv_state_not_carried"]
    assert [m for m in limits if backward[m] > 0] == ["first_lightning_grads"]


# The check that is there to catch each fault made in the program, with its
# limit: the run must refuse it by that check, with the rule's room.
CAUGHT_BY = {
    "plain_causal_for_selected": (
        "first_sparse_attention_of_its_own_operands_vs_reference",
        sala.TOL_FIRST_SPARSE_ATTENTION),
    "state_not_carried": (
        "first_lightning_attention_of_its_own_operands_vs_reference",
        sala.TOL_FIRST_LIGHTNING),
    # The forward is sound and (f) reads the kernels' names, not the step's
    # backward: what the step itself did is held by position.
    "dkv_state_not_carried": ("hidden_gradient_by_position_vs_reference",
                              sala.TOL_HIDDEN_GRADIENT),
    "topk_of_63": ("choices_breaking_the_reference_s_rule",
                   sala.TOL_CHOICE_RULE)}


def test_the_faults_made_in_the_program_are_the_module_s():
    assert set(CAUGHT_BY) == set(sala_faults.PROGRAM_FAULTS) <= set(
        sala_faults.FAULTS)


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_program_with_a_fault_is_not_correct(
        fault, monkeypatch, capsys, compile_cache_of_these_runs):
    """The fault in the program's place (the model's own names, the
    reference untouched) through a whole run: ``correct`` comes out false, by
    the check that is there to catch it and with the rule's room."""
    with sala_faults.program_with(fault):
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
    assert "ok" not in result["checks"][
        "block_scores_of_the_reference_s_q_and_k_vs_reference"]
    if fault != "topk_of_63":
        assert "ok" not in result["checks"][
            "choices_breaking_the_reference_s_rule"]


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

    def build(cell, real=sala.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(sala, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, 5,
                                        compile_cache_of_these_runs)[-1]
    assert result["correct"] is False
    refused = _refused(result)
    assert "losses_finite_and_falling" in refused
    assert "hidden_gradient_by_position_vs_reference" in refused
    assert len([n for n in refused
                if n.startswith("first_moment")]) == HELD_BY_D
    assert len([n for n in refused
                if n.startswith("first_update")]) == CHECKED_LEAVES
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  *(f"first_{kind}_attention_of_its_own_operands_vs_reference"
                    for kind in ("sparse", "lightning"))):
        assert "ok" not in result["checks"][sound]
