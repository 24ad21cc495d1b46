"""The cell ``phi4flash-sambay-tp2-s16384``: its rehearsal on the CPU, the
published widths in its configuration, its parameter count, its analytic
multiply-adds and its kernels' least work against numbers worked out by hand,
the walks' and the carry's counters, the faults its limits are there to
catch, and its timed path broken underneath.  Nothing here measures
anything."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, phi4flash_flops  # noqa: E402
from benchmark import run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import phi4flash  # noqa: E402

import phi4flash_faults  # noqa: E402  (beside this file)

CELL = "phi4flash-sambay-tp2-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of microsoft/Phi-4-mini-flash-reasoning, as the catalog of the
# model-configs guide holds it.
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
HELD = {"mamba_d_inner_held": 2560, "num_attention_heads_held": 20,
        "num_key_value_heads_held": 10, "feed_forward_columns_held": 5120,
        "vocab_size_held": 100032}
# The leaves check (d) compares at --rehearse's sizes (published layers 1 to
# 7 of 8): the embedding; layer 1's four kernels, three biases, pair norm,
# LayerNorm's two and MLP's two; layer 5's k and v; layer 7's q and o; the
# eight leaves of layers 2 and 4; layer 6's two.  (The four lambda vectors of
# layers 1, 3, 5 and 7 are one check of their own.)  Of them the sums that
# cancel, under their own limit: layer 1's three biases and its LayerNorm's,
# the two biases of layers 2 and 4, layer 5's k.
CHECKED_LEAVES = 1 + (4 + 3 + 1 + 2 + 2) + 2 + 2 + 2 * 8 + 2
CANCELLING_LEAVES = 3 + 1 + 2 * 2 + 1
PHI_METRICS = (
    "phi_flash_diff_ms", "phi_flash_diff_fwd_roofline",
    "phi_flash_diff_dq_roofline", "phi_flash_diff_dkv_roofline",
    "phi_flash_swa_ms", "phi_flash_swa_fwd_roofline",
    "phi_flash_swa_dq_roofline", "phi_flash_swa_dkv_roofline",
    "phi_attn_diff_ms", "phi_ssm_scan_ms", "phi_ssm_scan_fwd_roofline",
    "phi_ssm_scan_bwd_roofline", "phi_ssm_mix_ms", "phi_ssm_proj_ms",
    "phi_gmu_ms")
# What the cell reads under the names every family shares (PR 65 folded the
# family's seven copies of them onto these).
SHARED_METRICS = (
    "attn_proj_ms", "mlp_ms", "lm_head_ms", "embed_ms", "block_rest_ms",
    "step_unattributed_pct", "optimizer_update_ms")


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
    """``run.py --rehearse`` at tiny sizes (half of each width held,
    published layers 1 to 7 of 8, the window under the sequence): every check
    against the plain reference passes, the ``sambay`` note says what is
    held, carried and walked, and no CPU number is written as a metric."""
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
    note = next(x for x in lines if x.get("note") == "sambay")
    assert list(note["layers"].values()) == [
        "banded", "mamba", "banded", "mamba+memory", "full+kv", "gmu",
        "cross"]
    assert note["held"] == {
        "mamba_and_gate_channels": 64, "query_heads": 4,
        "key_value_heads": 2, "feed_forward_columns": 64,
        "vocabulary_rows": 512}
    # float32, 2 x 48 rows: the memory's 64 channels, k and v of 2 x 8.
    assert note["carry"] == {
        "memory_bytes": 2 * 48 * 64 * 4, "kv_bytes": 2 * 2 * 48 * 16 * 4,
        "bytes": 2 * 48 * (64 + 32) * 4, "memory_readers": 2,
        "kv_readers": 2}
    assert note["walks"]["banded"]["visible"] == 8 * 9 // 2 + 40 * 8
    # Off the TPU the kernels are their jax.numpy forms: none is in the step.
    assert set(note["kernel_calls"].values()) == {0}
    assert note["least_calls"] == {
        "hvd_ssm_scan_fwd": 4, "hvd_ssm_scan_bwd": 2, "hvd_flash_fwd": 4,
        "hvd_flash_dq": 4, "hvd_flash_dkv": 4, "hvd_flash_swa_fwd": 4,
        "hvd_flash_swa_dq": 4, "hvd_flash_swa_dkv": 4}
    noted = {c["name"]: c for c in next(
        x for x in lines if x.get("note") == "cell")["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "first_difference_of_the_maps_vs_reference",
            "lambda_vectors_first_moment_vs_reference", "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    assert len([c for c in noted if c.startswith("first_moment")]) == (
        CHECKED_LEAVES - CANCELLING_LEAVES)
    assert len([c for c in noted if c.startswith(
        "cancelling_moment")]) == CANCELLING_LEAVES
    assert not any(c.startswith("calls_of_") for c in noted)
    vectors = next(x for x in lines if x.get("note") == "lambda_vectors")
    assert set(vectors["layers"]) == {"layer_1", "layer_3", "layer_5",
                                      "layer_7"}
    assert vectors["pooled_error"] == noted[
        "lambda_vectors_first_moment_vs_reference"]["value"]


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (
        1, "phi4flash-causal-1x16384x1")
    assert len(entry["why"]) <= 200
    assert cfg["reduced"] == ["num_hidden_layers", *HELD]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == len(cfg["assumed"]["layers_held"]) == 6
    assert {k: cfg[k] for k in HELD} == HELD
    assert all(PUBLISHED[k] == 2 * v for k, v in (
        ("num_attention_heads", 20), ("num_key_value_heads", 10),
        ("intermediate_size", 5120), ("vocab_size", 100032)))
    assert 2 * 2560 == 2 * cfg["mamba_d_inner_held"]
    assert "2 chips share each layer" in cfg["deployment"]
    assert "further pipeline stages" in cfg["deployment"]
    assert len(cfg["source"]) <= 200
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    for key in (*cfg["reduced"], "mamba_why", "layer_order", "memory", "gmu",
                "differential", "lambda_init", "pair_norm",
                "attention_bias_why", "window", "positions", "initializers",
                "precision", "maps_on_the_kernels", "parameters",
                "learning_rate", "optimizer_args", "recomputation",
                "head_dim_why"):
        assert len(cfg["assumed"][key]) >= 20, key
    pcfg = phi4flash._phi_config(cfg, rehearse=False)
    assert pcfg.layers == (14, 15, 16, 17, 18, 19)
    assert pcfg.layer_kinds == ("mamba", "banded", "mamba+memory", "full+kv",
                                "gmu", "cross")
    assert (pcfg.rows_held, pcfg.channels_held, pcfg.heads_held,
            pcfg.kv_heads_held, pcfg.columns_held) == (
                100032, 2560, 20, 10, 5120)
    assert not pcfg.mamba_norms and pcfg.checkpoint_blocks


def test_layers_held_must_be_the_layers_counted():
    _, cfg, _ = _files()
    with pytest.raises(ValueError, match="in a row"):
        phi4flash._phi_config({**cfg, "num_hidden_layers": 5}, False)


def test_the_metrics_stand_after_the_two_witnesses_in_the_order_asked():
    """The family's own fifteen, in their order and of this cell alone; the
    seven shared names list the cell; the cell reports those and what every
    cell reports, and nothing else."""
    spec = run.load_spec()
    names = [m["name"] for m in spec["per_layer"]]
    first = names.index(PHI_METRICS[0])
    assert tuple(names[first:first + len(PHI_METRICS)]) == PHI_METRICS
    assert names.index("host_alive_gap_max_ms") < first
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in PHI_METRICS:
        assert (by_name[name]["workloads"], by_name[name]["moves"]) == (
            [CELL], "step_ms")
    for name in SHARED_METRICS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "step_ms"
    assert {m["name"] for m in spec["per_layer"]
            if CELL in m.get("workloads", ())} == {*PHI_METRICS,
                                                   *SHARED_METRICS}
    assert not any(n.startswith("phi_") for n in names
                   if n not in PHI_METRICS)


def test_parameter_count_of_one_chips_share():
    import jax
    import jax.numpy as jnp

    _, cfg, _ = _files()
    from horovod_tpu import models

    pcfg = phi4flash._phi_config(cfg, False)
    shapes = jax.eval_shape(models.Phi4Flash(pcfg).init, jax.random.key(0),
                            jnp.zeros((1, 16), jnp.int32))
    count = sum(int(np.prod(x.shape))
                for x in jax.tree_util.tree_leaves(shapes))
    mlp = 2560 * 2 * 5120 + 5120 * 2560 + 4 * 2560
    mamba = (2560 * 2 * 2560 + 2560 * 192 + 160 * 2560 + 2560 * 2560
             + 2560 * 16 + 4 * 2560 + 3 * 2560)
    own = (2560 * 1280 + 1280 + 2 * (2560 * 640 + 640) + 1280 * 2560 + 2560
           + 4 * 64 + 128)
    cross = 2560 * 1280 + 1280 + 1280 * 2560 + 2560 + 4 * 64 + 128
    want = (100032 * 2560 + 6 * mlp + 2 * mamba + 2 * own + cross
            + 2 * 2560 * 2560 + 2 * 2560)
    assert count == want == 572656512
    assert "572,656,512" in cfg["assumed"]["parameters"]


def test_model_flops_by_hand():
    ctx = _context()
    macs = phi4flash_flops.forward_macs(ctx["cfg"], ctx["traffic"])
    s = 16384
    causal, band = s * (s + 1) // 2, 512 * 513 // 2 + (s - 512) * 512
    assert macs == {
        "mamba_projections": 2 * s * 2560 * (2 * 2560 + 160 + 32 + 160 + 2560),
        "gate_layers": s * 2 * 2560 * 2560,
        "attention_projections": s * 2560 * (2 * (2 * 1280 + 2 * 640)
                                             + 2 * 1280),
        # 20 map heads a layer, 64 + 128 a pair: one banded, two full.
        "attention": 20 * 192 * (band + 2 * causal),
        "feed_forward": 6 * s * 3 * 2560 * 5120,
        "head": (s - 1) * 2560 * 100032}
    assert phi4flash_flops.model_flops(ctx["cfg"], ctx["traffic"], 1) == (
        6.0 * sum(macs.values()))


def test_the_maps_least_work_counts_each_map_once_by_hand():
    ctx = _context()
    s, causal = 16384, 16384 * 16385 // 2
    full = phi4flash_flops.flash_diff_step_least(ctx)
    banded = phi4flash_flops.flash_swa_step_least(ctx)
    for name, widths in (("fwd", 3), ("dq", 4), ("dkv", 6)):
        assert full["kernels"][name]["flops"] == (
            2.0 * 2 * 20 * causal * widths * 64)
        assert full["kernels"][name]["bound"] == "flops"
        assert banded["kernels"][name]["flops"] == 2.0 * 20 * (
            512 * 513 // 2 + (s - 512) * 512) * widths * 64
    # fwd: q, k, v and both maps out in bf16, a float32 statistic a row and
    # map head.
    rows = 2 * s
    assert full["kernels"]["fwd"]["bytes"] == rows * (
        1280 * 2 + 2 * 640 * 2 + 20 * 128 * 2 + 20 * 4)


def test_the_scans_least_time_is_jamba_s_count_at_these_channels():
    ctx = _context()
    got = phi4flash_flops.scan_step_least(ctx)
    rows, wide = 16384, 16384 * 2560
    fwd = wide * (2 + 4 + 2) + 2 * rows * 16 * 4 + 2560 * 17 * 4
    assert got["kernels"]["fwd"]["bytes"] == 2 * fwd
    assert got["kernels"]["fwd"]["bound"] == "bytes"
    assert set(got["kernels"]) == {"fwd", "bwd"}


def test_the_walks_counters_by_hand():
    """A banded tile of 512 rows in steps of 256 keys visits 896 keys a row
    (``flash_attention._band_tile``'s own figure) but for the first tile; a
    causal tile of 1,024 the rows' past and 5 / 8 of its own square."""
    from horovod_tpu.ops.flash_attention import tile_plan

    plan = tile_plan(16384, 128, 2, True, heads=1, window=512)
    assert (plan.tile_q, plan.step_k) == (512, 256)
    assert phi4flash.pairs_visited(plan, 16384, 512) == (
        31 * 512 * 896 + 512 * 384)
    plan = tile_plan(16384, 128, 2, True, heads=1)
    assert (plan.tile_q, plan.step_k) == (1024, 256)
    assert phi4flash.pairs_visited(plan, 16384, None) == sum(
        1024 * row0 + 1024 * 1024 * 5 // 8 for row0 in range(0, 16384, 1024))


def test_kernel_calls_are_counted_by_the_instructions_own_names():
    call = ('%{0}.3 = (f32[1,16384,1280]{{2,1,0}}, f32[10,1,16384]{{2,1,0}}) '
            'custom-call(bf16[1,16384,1280]{{2,1,0}} %q), '
            'custom_call_target="tpu_custom_call"\n')
    text = (call.format("jvp_hvd_flash_fwd_") * 2
            + call.format("jvp_hvd_flash_swa_fwd_")
            + call.format("transpose_jvp_hvd_ssm_scan_bwd_")
            + '%fusion.1 = bf16[8] fusion(bf16[8] %hvd_flash_dq), kind=kLoop\n')
    got = phi4flash.kernel_calls(text)
    assert (got["hvd_flash_fwd"], got["hvd_flash_swa_fwd"],
            got["hvd_ssm_scan_bwd"], got["hvd_flash_dq"]) == (2, 1, 1, 0)
    _, cfg, _ = _files()
    pcfg = phi4flash._phi_config(cfg, False)
    assert phi4flash.least_calls(pcfg, 16384) == {
        "hvd_ssm_scan_fwd": 4, "hvd_ssm_scan_bwd": 2, "hvd_flash_fwd": 4,
        "hvd_flash_dq": 4, "hvd_flash_dkv": 4, "hvd_flash_swa_fwd": 2,
        "hvd_flash_swa_dq": 2, "hvd_flash_swa_dkv": 2}
    # A window of the whole sequence is the causal call.
    assert phi4flash.least_calls(pcfg, 512)["hvd_flash_swa_fwd"] == 0


@pytest.fixture(scope="module")
def tiny():
    import jax

    _, cfg, traffic = _files(rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = phi4flash.setup(cfg, mesh, 11, rehearse=True)
    cell["batches"] = traffic_gen.make_batches(
        traffic, phi4flash.inputs(cell, traffic), mesh, 11)
    return cell


def test_the_family_draws_ids_of_the_held_slice(tiny):
    ids = np.asarray(tiny["batches"][0][0])
    assert ids.shape == (2, 48) and ids.max() < 512 <= 1024


# At --rehearse's sizes, the measure that reads each fault over its limit
# (the readings that set the limits are the chip's:
# testdata/check_readings/phi4flash.json).
CAUGHT_BY = {
    "second_map_dropped": ("sample_logits", "TOL_SAMPLE_LOGITS"),
    "band_of_511": ("first_difference", "TOL_DIFFERENCE"),
    "stop_gradient_on_the_lambda_vectors": ("lambda_vectors",
                                            "TOL_LAMBDA_MOMENT"),
    # The precision below the stated one, in the program's place: over its
    # limit here and by the rule's margin at the cell's size only (a scan
    # of 48 steps gathers little rounding; one of 16,384 reads 18 x).
    "reference_in_bfloat16": ("first_moment", "TOL_FIRST_MOMENT"),
}


@pytest.fixture(scope="module")
def fault_readings(tiny):
    return phi4flash_faults.readings(
        list(CAUGHT_BY), common.first_shard(tiny["params"]), tiny["pcfg"],
        tiny["batches"][0][0])


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_fault_reads_over_the_limit_that_is_there_to_catch_it(
        fault, fault_readings):
    measure, limit = CAUGHT_BY[fault]
    read = fault_readings[fault]
    margin = 1.0 if fault == phi4flash_faults.LOW_PRECISION else MARGIN
    assert read[measure] > margin * getattr(phi4flash, limit), (fault, read)
    if fault == "second_map_dropped":   # plain attention: by every measure
        assert read["first_difference"] > MARGIN * phi4flash.TOL_DIFFERENCE
        assert read["first_moment"] > MARGIN * phi4flash.TOL_FIRST_MOMENT
    assert read["correct"] is False and measure in read["refused_by"]
    if fault == "stop_gradient_on_the_lambda_vectors":
        # The forward and every other gradient as they are: (e) alone.
        assert read[measure] == 1.0 and set(read["refused_by"]) == {measure}


def test_every_fault_has_its_reference_and_the_programs_are_among_them(tiny):
    for fault in phi4flash_faults.FAULTS:
        assert (phi4flash_faults._reference_fault(fault, tiny["pcfg"])
                or fault == phi4flash_faults.LOW_PRECISION)
    assert set(phi4flash_faults.PROGRAM_FAULTS) <= set(
        phi4flash_faults.FAULTS)


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


def _refused(result) -> set:
    return {n for n, e in result["checks"].items() if e.get("ok") is False}


def test_gradients_that_stop_short_with_the_forward_sound_are_not_correct(
        monkeypatch, capsys):
    """``stop_gradient`` on the k and v the cross layer reads, on the memory
    the gate layer reads and on every layer's lambda vectors, through a
    whole run: the forward is sound and ``correct`` comes out false by the
    moments of the layer that makes each carried array (published 5's k and
    v, 4's Mamba leaves), which lack their readers' part of the sum, and by
    the lambda vectors' own check, which reads 1: no gradient at all."""
    with phi4flash_faults.program_with("stop_gradient_on_the_carried_kv"), \
            phi4flash_faults.program_with(
                "stop_gradient_on_the_carried_memory"), \
            phi4flash_faults.program_with(
                "stop_gradient_on_the_lambda_vectors"):
        result = _rehearsal_in_this_process(monkeypatch, capsys, seed=7)
    assert result["correct"] is False
    refused = _refused(result)
    vectors = "lambda_vectors_first_moment_vs_reference"
    assert vectors in refused and result["checks"][vectors]["value"] == 1.0
    assert all(n.startswith(("first_moment", "cancelling_moment"))
               for n in refused - {vectors})
    for leaf, limit in (
            ("cancelling_moment.layer_5.attn.k_proj.kernel",
             phi4flash.TOL_CANCELLING_MOMENT),
            ("first_moment.layer_5.attn.v_proj.kernel",
             phi4flash.TOL_FIRST_MOMENT),
            ("first_moment.layer_4.mamba.in_proj.kernel",
             phi4flash.TOL_FIRST_MOMENT),
            ("first_moment.layer_4.mamba.A_log",
             phi4flash.TOL_FIRST_MOMENT)):
        assert leaf in refused
        assert result["checks"][leaf]["value"] > MARGIN * limit
    assert "ok" not in result["checks"]["sample_logits_vs_reference"]
