"""The cell ``jamba2-ssm-tp4-s16384``: its rehearsal on the CPU, the published
widths in its configuration, its parameter count, its analytic multiply-adds
and its scan kernels' least bytes against numbers worked out by hand, the
faults its limits are there to catch, and its timed path broken underneath.
Nothing here measures anything."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

from benchmark import common, flops, jamba_flops, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import jamba  # noqa: E402

import jamba_faults  # noqa: E402  (beside this file)

CELL = "jamba2-ssm-tp4-s16384"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# config.json of ai21labs/AI21-Jamba2-3B, as the catalog of the
# model-configs guide holds it.
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
HELD = {"mamba_d_inner_held": 1280, "num_attention_heads_held": 5,
        "feed_forward_columns_held": 2048, "vocab_size_held": 16384}
# The leaves check (d) compares at --rehearse's sizes: the first and the
# last Mamba block's seven, the attention block's two, the embedding.
CHECKED_LEAVES = 1 + 2 * 7 + 2


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
    """``run.py --rehearse`` at tiny sizes (a quarter of each width held,
    three Mamba blocks and an attention block at index 2): every check
    against the plain reference passes, the ``ssm`` note says what is held,
    and no CPU number is written as a metric."""
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
    ssm = next(x for x in lines if x.get("note") == "ssm")
    assert ssm["layer_kinds"] == ["mamba", "mamba", "attention", "mamba"]
    assert ssm["held"] == {"mamba_channels": 128, "query_heads": 2,
                           "key_value_heads": 1, "feed_forward_columns": 32,
                           "vocabulary_rows": 512}
    assert ssm["scan"] == {"chunk": 48, "block": 128, "padded_length": 48,
                           "states": 8}
    # Off the TPU the scan is its jax.numpy form: no kernel is in the step.
    assert set(ssm["kernel_calls"].values()) == {0}
    assert ssm["least_calls"] == {
        "hvd_ssm_scan_fwd": 6, "hvd_ssm_scan_bwd": 3, "hvd_flash_fwd": 1,
        "hvd_flash_dq": 1, "hvd_flash_dkv": 1}
    noted = {c["name"]: c for c in next(
        x for x in lines if x.get("note") == "cell")["checks"]}
    assert {"first_loss_vs_reference", "sample_logits_vs_reference",
            "scan_of_the_reference_s_operands_vs_reference",
            "first_mixer_step_and_norms_vs_reference",
            "first_mixer_scan_vs_reference", "logits_are_float32",
            "parameters_and_moments_are_float32"} <= set(noted)
    assert len([c for c in noted
                if c.startswith("first_moment")]) == CHECKED_LEAVES
    assert not any(c.startswith("calls_of_") for c in noted)


def test_the_cell_is_the_published_model_at_one_chips_share():
    entry, cfg, traffic = _files()
    assert (entry["chips"], entry["traffic"]) == (1, "jamba-causal-1x16384x1")
    assert cfg["reduced"] == ["num_hidden_layers", *HELD]
    changed = {k: v for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers": 28} and cfg[
        "num_hidden_layers"] == 14
    assert {k: cfg[k] for k in HELD} == HELD
    assert all(PUBLISHED[k] == 4 * v for k, v in (
        ("num_attention_heads", 5), ("intermediate_size", 2048),
        ("vocab_size", 16384))) and 2 * 2560 == 4 * 1280
    assert "4 chips share each layer" in cfg["deployment"]
    assert "second pipeline stage" in cfg["deployment"]
    assert (traffic["batch_per_chip"], traffic["seq_len"],
            traffic["distinct_batches"], traffic["warmup_steps"],
            traffic["trace_steps"]) == (1, 16384, 1, 3, 10)
    for key in (*cfg["reduced"], "layer_order", "mamba_layout", "mamba_norms",
                "initializers", "precision", "parameters", "learning_rate",
                "optimizer_args", "recomputation", "head_dim_why"):
        assert len(cfg["assumed"][key]) >= 20, key
    jcfg = jamba._jamba_config(cfg, rehearse=False)
    assert jcfg.layer_kinds == ("mamba",) * 7 + ("attention",) + (
        "mamba",) * 6
    assert (jcfg.rows_held, jcfg.channels_held, jcfg.heads_held,
            jcfg.columns_held) == (16384, 1280, 5, 2048)
    assert (jcfg.vocab_size, jcfg.d_inner, jcfg.num_heads, jcfg.num_kv_heads,
            jcfg.head_dim, jcfg.intermediate_size) == (65536, 5120, 20, 1,
                                                       128, 8192)
    assert (jcfg.mamba_d_conv, jcfg.mamba_d_state, jcfg.mamba_dt_rank,
            jcfg.rms_norm_eps, jcfg.checkpoint_blocks) == (4, 16, 160, 1e-6,
                                                           True)


@pytest.mark.parametrize("name", ["device_gap_max_ms",
                                  "host_alive_gap_max_ms"])
def test_the_stall_witnesses_stand_as_they_did_before_this_cells_metrics(
        name):
    """What ``test_stall_witness.py`` holds of the two entries, but for their
    place at the very end: this cell's seven metrics are appended after them
    (tests/conftest.py:APPENDED_AFTER).  Each is still reported by every
    cell, this one too, has no list of cells, and the pair stands together
    in its order.  Nothing here says what follows the pair: the next PR
    appends too, and may not edit this file."""
    spec = run.load_spec()
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    assert (entry["layer"], entry["moves"]) == ("device", "step_ms")
    assert run.load_json("layer_metrics", name + ".json")["reducer"] == \
        f"stall_witness.{name}"
    for cell in spec["workloads"]:
        assert name in {m["name"] for m in run.metrics_of(
            spec, "per_layer", cell["name"])}
    names = [m["name"] for m in spec["per_layer"]]
    at = names.index("device_gap_max_ms")
    assert names[at + 1] == "host_alive_gap_max_ms"


def test_parameter_count_of_one_chips_share():
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    _, cfg, _ = _files()
    jcfg = jamba._jamba_config(cfg, rehearse=False)
    variables = jax.eval_shape(
        lambda k: models.Jamba(jcfg).init(k, jnp.zeros((1, 16), jnp.int32)),
        jax.random.key(0))
    mixer = (2560 * 2 * 1280 + 1280 * 192 + 160 * 1280 + 1280 * 2560
             + 1280 * 16 + 4 * 1280 + 3 * 1280 + 160 + 16 + 16)
    feed_forward = 3 * 2560 * 2048
    attention = 2560 * 5 * 128 + 2560 * 2 * 128 + 5 * 128 * 2560
    assert (mixer, feed_forward, attention) == (10_310_592, 15_728_640,
                                                3_932_160)
    want = (16384 * 2560 + 13 * (mixer + feed_forward + 2 * 2560)
            + attention + feed_forward + 2 * 2560 + 2560)
    assert want == 400_188_096                          # 6.40 GB at 16 bytes
    leaves = jax.tree_util.tree_leaves(variables["params"])
    assert sum(int(np.prod(x.shape)) for x in leaves) == want
    assert all(x.dtype == jnp.float32 for x in leaves)
    assert str(want) in cfg["assumed"]["parameters"].replace(",", "")
    # The whole model by the same count: 3,029 M parameters.
    whole = (65536 * 2560 + 26 * (4 * mixer + 4 * feed_forward - 3 * 192
                                  + 2 * 2560)
             + 2 * (4 * attention - 3 * 2560 * 2 * 128 + 4 * feed_forward
                    + 2 * 2560) + 2560)
    assert 3.02e9 < whole < 3.04e9


def test_model_flops_by_hand():
    """What the algorithm needs: the mixers' four products, attention over
    the causal pairs of the held heads, every block's feed-forward, the head
    over the positions that predict and the held slice; the scan's own
    arithmetic is not counted."""
    _, cfg, traffic = _files()
    macs = jamba_flops.forward_macs({**cfg["assumed"], **cfg}, traffic)
    positions, pairs = 16384, 16384 * 16385 // 2
    assert jamba_flops.causal_pairs(16384) == pairs
    want = {"mamba_projections": 13 * positions * 1280 * (
                2 * 2560 + 192 + 160 + 2560),
            "attention_projections": positions * 2560 * (2 * 640 + 2 * 128),
            "attention": pairs * 5 * 128 * 2,
            "feed_forward": 14 * positions * 3 * 2560 * 2048,
            "head": (positions - 1) * 2560 * 16384}
    assert macs == pytest.approx(want, rel=1e-12)
    cell = {"cfg": cfg, "rehearse": False, "traffic": traffic,
            "mesh": common.hvd_mesh([0])}
    assert jamba.model_flops(cell) == pytest.approx(
        6 * sum(want.values()), rel=1e-12)
    assert 40.2e12 < jamba.model_flops(cell) < 40.4e12   # 40.3 TFLOP a step
    kinds = jamba_flops.layer_kinds({**cfg["assumed"], **cfg})
    assert kinds.index("attention") == 7 and kinds.count("mamba") == 13


def test_scan_step_least_by_hand():
    """A call's operands once and its results once over the HBM peak: u and
    y (and their cotangents) in bfloat16, dt (and its) in float32, B and C
    (and theirs) in float32, A and D once, 13 calls each way."""
    got = jamba_flops.scan_step_least(_context())
    wide, narrow, leaves = 16384 * 1280, 16384 * 16 * 4, 1280 * 17 * 4
    fwd = 13 * (wide * (2 + 4 + 2) + 2 * narrow + leaves)
    bwd = 13 * (wide * (2 + 4 + 2 + 2 + 4) + 4 * narrow + 2 * leaves)
    assert got["kernels"]["fwd"]["bytes"] == fwd
    assert got["kernels"]["bwd"]["bytes"] == bwd
    assert got["kernels"]["fwd"]["seconds"] == pytest.approx(fwd / 819e9)
    assert got["seconds"] == pytest.approx((fwd + bwd) / 819e9)
    assert {k["bound"] for k in got["kernels"].values()} == {"bytes"}
    # 2.70 ms forward and 4.73 ms backward a step at the HBM peak.
    assert 0.00269 < got["kernels"]["fwd"]["seconds"] < 0.00271
    assert 0.00472 < got["kernels"]["bwd"]["seconds"] < 0.00474
    with pytest.raises(ValueError, match="no HBM peak"):
        jamba_flops.scan_step_least({**_context(), "peaks": {
            "hbm_bytes_per_s": None, "source": "a chip without one"}})


def test_kernel_calls_are_counted_by_the_instructions_own_names():
    call = ' custom-call(f32[8] %u), custom_call_target="tpu_custom_call"'
    hlo = "\n".join([
        "%jvp_hvd_ssm_scan_fwd_.1 = (bf16[8], f32[8])" + call,
        "%checkpoint_hvd_ssm_scan_fwd_.7 = (bf16[8], f32[8])" + call,
        "%transpose_jvp_hvd_ssm_scan_bwd__.3 = (bf16[8], f32[8])" + call,
        "%jvp_hvd_flash_fwd_.2 = bf16[8]" + call,
        "%hvd_flash_dq.5 = bf16[8]" + call, "%hvd_flash_dkv.5 = bf16[8]" + call,
        "%attn.3 = bf16[8]" + call,
        "%fusion.9 = f32[8] fusion(f32[8] %hvd_ssm_scan_fwd.1), kind=kLoop"])
    assert jamba.kernel_calls(hlo) == {
        "hvd_ssm_scan_fwd": 2, "hvd_ssm_scan_bwd": 1, "hvd_flash_fwd": 1,
        "hvd_flash_dq": 1, "hvd_flash_dkv": 1}


@pytest.fixture(scope="module")
def tiny():
    """The rehearsal's model, its seeded variables with every leaf that
    starts at a one, a zero or a constant moved (a fault in how one enters
    is not hidden), and its first batch."""
    import jax

    _, cfg, traffic = _files(rehearse=True)
    mesh = common.hvd_mesh(jax.devices()[:1])
    cell = jamba.setup(cfg, mesh, seed=7, rehearse=True)

    def stir(path, leaf):
        name = jax.tree_util.keystr(path)
        if leaf.ndim != 1 and not name.endswith("['A_log']"):
            return leaf
        key = jax.random.fold_in(jax.random.key(9), len(name) + leaf.shape[0])
        return leaf + 0.3 * jax.random.normal(key, leaf.shape)

    cell["params"] = jax.tree_util.tree_map_with_path(stir, cell["params"])
    cell["batches"] = traffic_gen.make_batches(
        traffic, jamba.inputs(cell, traffic), mesh, 7)
    return cell, traffic


def test_the_family_draws_ids_of_the_held_slice(tiny):
    cell, traffic = tiny
    ids = np.asarray(cell["batches"][0][0])
    assert ids.shape == (2, 48) and 0 <= ids.min() and ids.max() < 512
    assert list(jamba.sample_positions(16384)[[0, 1, -1]]) == [63, 127, 16383]
    assert len(jamba.sample_positions(48)) == 48


# Which limit is there to catch which fault (check_readings/jamba.json holds
# what each reads at the cell's own size on the chip): a measure, or the
# first moment of a leaf.
CAUGHT_BY = {
    "state_in_bfloat16": "scan", "dt_in_bfloat16": "scan",
    "a_without_its_sign": "sample_logits",
    "a_without_its_exp": "sample_logits",
    "b_and_c_swapped": "sample_logits",
    "dt_norm_left_out": "['layer_0']['mamba']['dt_norm']",
    "b_norm_left_out": "sample_logits", "c_norm_left_out": "sample_logits",
    "conv_not_causal": "sample_logits", "conv_a_tap_short": "sample_logits",
    "softplus_left_out": "sample_logits", "d_left_out": "sample_logits",
    "gate_on_u": "sample_logits",
    "rotary_added_to_attention": "['attn']['q_proj']['kernel']",
    "kv_head_read_per_query_head": "['attn']['kv_proj']['kernel']",
    "loss_on_the_token_itself": "first_loss",
    "norm_scales_left_out": "['layer_0']['mamba']['dt_norm']",
    "conv_bias_left_out": "sample_logits",
    "d_taken_as_one": "['layer_0']['mamba']['D']"}
LIMIT_OF = {"sample_logits": jamba.TOL_SAMPLE_LOGITS,
            "first_loss": jamba.TOL_FIRST_LOSS, "scan": jamba.TOL_SCAN}


@pytest.fixture(scope="module")
def fault_readings(tiny):
    cell, traffic = tiny
    return jamba_faults.readings(
        list(CAUGHT_BY), common.first_shard(cell["params"]), cell["jcfg"],
        cell["batches"][0][0], grads=True,
        sequences=traffic["batch_per_chip"])


@pytest.mark.parametrize("fault", CAUGHT_BY)
def test_a_fault_reads_over_the_limit_that_is_there_to_catch_it(
        fault, fault_readings):
    """Each fault of ISSUE 47's list, and the three that a one or a zero
    hides at initialisation, made in the plain reference at ``--rehearse``'s
    sizes on weights whose scales, biases, ``D`` and ``A_log`` are moved:
    refused by its check with the rule's room."""
    assert set(CAUGHT_BY) == set(jamba_faults.FAULTS)
    assert set(jamba_faults.HIDDEN_AT_INITIALISATION) < set(CAUGHT_BY)
    by, read = CAUGHT_BY[fault], fault_readings[fault]
    if by in LIMIT_OF:
        assert read[by] > MARGIN * LIMIT_OF[by], (fault, read)
        return
    (value,) = [v for leaf, v in read["first_moment"].items()
                if leaf.endswith(by)]
    assert value > MARGIN * jamba.TOL_FIRST_MOMENT, (fault, by, read)


# Which limit of the program's own first mixer catches which fault made in
# the PROGRAM: the measure of ``first_mixer_errors`` and its limit.
PROGRAM_CAUGHT_BY = {
    "mixer_softplus_in_bfloat16": ("step_and_norms", jamba.TOL_MIXER_STEP),
    "mixer_norms_in_bfloat16": ("step_and_norms", jamba.TOL_MIXER_STEP),
    "kernel_dt_in_bfloat16": ("scan", jamba.TOL_MIXER_SCAN),
    "kernel_state_in_bfloat16": ("scan", jamba.TOL_MIXER_SCAN)}


@pytest.fixture(scope="module")
def program_readings(tiny):
    return jamba_faults.program_readings(tiny[0])


@pytest.mark.parametrize("fault", ["sound", *PROGRAM_CAUGHT_BY])
def test_the_program_s_own_mixer_is_held_to_float32(fault, program_readings):
    """The model's own first mixer as the step runs it, with its softplus,
    its three norms, the ``dt`` its scan takes or its scan's state rounded
    to bfloat16 underneath: refused by (e) or (f) with the rule's room,
    where the sound mixer stands as far inside both."""
    assert set(PROGRAM_CAUGHT_BY) == set(jamba_faults.PROGRAM_FAULTS)
    read = program_readings[fault]
    if fault == "sound":
        assert read["step_and_norms"] * MARGIN < jamba.TOL_MIXER_STEP, read
        assert read["scan"] * MARGIN < jamba.TOL_MIXER_SCAN, read
        return
    measure, limit = PROGRAM_CAUGHT_BY[fault]
    assert read[measure] > MARGIN * limit, (fault, read)


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


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch,
                                                                capsys):
    """The compiled step wrapped so that it hands back the state it was
    given: ``correct`` comes out false, and the last line names the first
    moments that were never written and the losses that did not fall."""
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

    def build(cell, real=jamba.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(jamba, "build", build)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=5)
    assert result["correct"] is False
    refused = _refused(result)
    assert "losses_finite_and_falling" in refused
    assert len([n for n in refused
                if n.startswith("first_moment")]) == CHECKED_LEAVES
    # What the broken step leaves alone still reads sound.
    for sound in ("sample_logits_vs_reference", "first_loss_vs_reference",
                  "scan_of_the_reference_s_operands_vs_reference",
                  "first_mixer_step_and_norms_vs_reference",
                  "first_mixer_scan_vs_reference"):
        assert "ok" not in result["checks"][sound]


def test_a_program_whose_scan_keeps_its_state_in_bfloat16_is_not_correct(
        monkeypatch, capsys):
    """The program's scan (off the TPU ``selective_scan_reference``, which
    the model's mixers and check (c) both reach through ``selective_scan``)
    with its state rounded to bfloat16 after every step, in the program's
    place through a whole run: ``correct`` comes out false by the scan's own
    check and by the first mixer's (f), each with the rule's room."""
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.ops import selective_scan as ss

    def low_state(u, dt, a, b, c, d):
        f = lambda x: x.astype(jnp.float32)  # noqa: E731

        def step(state, row):
            u_t, dt_t, b_t, c_t = row
            state = (jnp.exp(dt_t[..., None] * a) * state
                     + (dt_t * u_t)[..., None] * b_t[:, None, :])
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d * u_t

        rows = tuple(jnp.swapaxes(f(x), 0, 1) for x in (u, dt, b, c))
        start = ss._vary_like(jnp.zeros((u.shape[0], *a.shape), jnp.float32),
                              u)
        return jnp.swapaxes(lax.scan(step, start, rows)[1], 0, 1).astype(
            u.dtype)

    monkeypatch.setattr(ss, "selective_scan_reference", low_state)
    result = _rehearsal_in_this_process(monkeypatch, capsys, seed=6)
    assert result["correct"] is False
    for name, limit in (
            ("scan_of_the_reference_s_operands_vs_reference", jamba.TOL_SCAN),
            ("first_mixer_scan_vs_reference", jamba.TOL_MIXER_SCAN)):
        assert name in _refused(result)
        assert result["checks"][name]["value"] > MARGIN * limit


def test_a_step_whose_mixers_norm_in_bfloat16_is_not_correct(monkeypatch,
                                                             capsys):
    """The model's three norms of ``dt``, ``B`` and ``C`` rounded to
    bfloat16 going in and coming out, in every mixer through a whole run (the
    kernels and the reference untouched): ``correct`` comes out false by the
    first mixer's (e), which reads the step's own path, and check (c), which
    reads the kernel alone, stays sound."""
    with jamba_faults.program_with("mixer_norms_in_bfloat16"):
        result = _rehearsal_in_this_process(monkeypatch, capsys, seed=8)
    assert result["correct"] is False
    name = "first_mixer_step_and_norms_vs_reference"
    assert name in _refused(result)
    assert result["checks"][name]["value"] > MARGIN * jamba.TOL_MIXER_STEP
    assert "ok" not in result["checks"][
        "scan_of_the_reference_s_operands_vs_reference"]
