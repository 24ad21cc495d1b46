"""chip_smoke.py: the script refuses a machine without a TPU, and each of
its phase functions passes at a tiny size on the virtual CPU mesh (Pallas
kernels in the interpreter).  What the phases prove, they prove on the chip;
this file keeps their control flow from rotting between chip runs."""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_exits_nonzero_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no tpu" in proc.stderr
    assert proc.stdout.strip() == ""          # no phase ran, no result line
    assert not (tmp_path / "cache").exists()  # and nothing was compiled


def test_device_phase_reports_what_jax_reports(capsys):
    info = chip_smoke.device(expect_count=8, platform="cpu")
    assert info == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                    "count": 8}
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "device" and line["jax"] == jax.__version__


def test_native_core_and_eager_phases():
    import horovod_tpu as hvd

    try:
        # clean=False: other test processes share this tree's library.
        report = chip_smoke.native_core(clean=False)
        assert report["core"] == "NativeCore"
        report = chip_smoke.eager(n=4096, steps=3, batch=64)
        assert report["host_fallback"] == 0
        assert report["device_plane_moved"]["identity"] > 0
    finally:
        hvd.shutdown()


def test_grouped_products_phase_tiny():
    """The expert layer against a loop over its experts (values, gradients),
    the repo's kernels (interpreted) against ``ragged_dot`` and the timing
    table's keys; megablox's kernels want a TPU."""
    report = chip_smoke.grouped_products(tokens=256, d=32, f=16, held=4,
                                         experts=16, top_k=2, repeats=1,
                                         megablox=False, interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        f"{case}/{n}" for case in ("routed_experts", "routed_experts_in_parts")
        for n in ("y", "dx", "drouter", "dgate", "dup", "ddown")] + [
        f"kept_forward/{n}"
        for n in ("dx", "dweights", "dgate", "dup", "ddown")] + [
        f"{dot}/{n}" for dot in ("hvd_grouped_dot",
                                 "hvd_grouped_dot_cast_first")
        for n in ("drows_tail_is_zero", "drows", "dgate", "dup", "ddown")]
    assert all(c["ok"] for c in report["checks"])
    assert sum(report["load"]) <= report["buffer"] < sum(
        report["load/in_parts"]) <= report["worst"]
    assert {"layer_fwd_bwd_ms", "layer_fwd_bwd_ms/in_parts",
            "layer_fwd_bwd_ms/routed=quarter", "layer_fwd_bwd_ms/routed=half",
            "layer_fwd_bwd_ms/routed=all",
            f"ragged_dot_fwd_bwd_ms/rows={report['buffer']}/half",
            f"hvd_grouped_dot_fwd_bwd_ms/rows={report['buffer']}",
            f"hvd_grouped_dot_cast_first_fwd_bwd_ms/rows={report['worst']}"
            "/all_routed"
            } <= set(report)


def test_sum_rows_phase_tiny():
    """The sum back into the tokens by the kernel (interpreted) against the
    scatter-add in trips, and the timing table's keys."""
    report = chip_smoke.sum_rows(shapes={"toy": (256, 128, 128, 4, 4, 16)},
                                 repeats=1, chain=2, interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        f"sum_rows/toy/routed={share}" for share in ("quarter", "half", "all")]
    assert all(c["ok"] for c in report["checks"])
    assert {f"{what}/toy/routed={share}"
            for what in ("sum_rows_ms", "token_order_ms", "scatter_add_ms")
            for share in ("quarter", "half", "all")} <= set(report)


def test_embed_grad_phase_tiny():
    """The embedding lookup's gradient by the kernel (interpreted) against a
    float32 scatter-add at a table with a partial last tile, and the timing
    table's keys."""
    report = chip_smoke.embed_grad(shapes={"toy": (300, 128, 160)}, repeats=1,
                                   chain=2, interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        "embed_grad/toy/value", "embed_grad/toy/d_table"]
    assert all(c["ok"] for c in report["checks"])
    assert {f"{what}/toy" for what in (
        "embed_grad_ms", "sorted_scatter_ms", "cast_then_take_ms",
        "take_then_cast_ms")} <= set(report)


def test_kernels_phase_interpreted():
    # 160 pads to 256: the padding path.  One dtype and one mask here; the
    # chip runs the product.
    report = chip_smoke.kernels(interpret=True, seqs=(64, 160), heads=2,
                                head_dim=32, codec_elems=5000,
                                dtypes=("bfloat16",), causals=(True,),
                                grouped=((4, 2, 32, 96, 4),))
    assert all(c["ok"] for c in report["checks"])
    assert sum(c["name"].startswith("gqa/") for c in report["checks"]) == 8
    assert [c["codec"] for c in report["codecs"]] == ["int8", "int4"]


def test_qk_norm_rope_phase_tiny():
    """The q/k norm + rotary kernels (interpreted) against the ``jax.numpy``
    form at two head counts, and the timing table's keys."""
    report = chip_smoke.qk_norm_rope(batch=1, length=48, heads=(2, 1),
                                     repeats=1, chain=2, interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        f"heads={h}/{n}" for h in (2, 1) for n in ("out", "dx", "dscale")]
    assert all(c["ok"] for c in report["checks"])
    assert {f"{form}_{p}_{unit}/heads={h}" for form in ("kernels", "dense")
            for p in ("fwd", "bwd") for unit in ("ms", "gb_s")
            for h in (2, 1)} <= set(report)


def test_wide_expert_products_phase_tiny(monkeypatch):
    """The kernels (interpreted) where a whole matrix does not fit their
    budget, against a loop over the experts, and each call's time."""
    from horovod_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(gm, "_VMEM_BUDGET", gm._gmm_bytes(512, 256, 128, 2,
                                                          4))
    report = chip_smoke.wide_expert_products(
        rows=1024, routed=640, d=256, f=256, held=4, repeats=1,
        interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        "wide/out", "wide/drows", "wide/dw"]
    assert all(c["ok"] for c in report["checks"])
    assert report["gmm_block"] == 128 and report["routed"] == 640
    assert {"gmm_ms", "gmm_t_ms", "tgmm_ms",
            "hvd_grouped_dot_fwd_bwd_ms/rows=1024"} <= set(report)


def test_flash_window_phase_tiny():
    """The banded flash kernels (interpreted) against ``dense_attention`` a
    query head at a time, at two group sizes, with a band and without, the
    timing table's keys and the banded call's schedule beside them."""
    report = chip_smoke.flash_window(length=160, heads=(3, 2), head_dim=16,
                                     windows=(24, None), repeats=1, chain=2,
                                     interpret=True)
    tags = [f"heads={h}/window={w}" for h in (3, 2) for w in (24, None)]
    assert [c["name"] for c in report["checks"]] == [
        f"{tag}/{n}" for tag in tags for n in ("out", "dq", "dk", "dv")]
    assert all(c["ok"] for c in report["checks"])
    assert {f"{p}_ms/{tag}" for p in ("fwd", "fwd_bwd")
            for tag in tags} <= set(report)
    # 160 rows pad to one block of 256 rows and one step: a band of 24 keys
    # takes the diagonal's step and the one before it.
    assert report["schedule/window=24"] == {
        "grid_steps_a_head": 1, "keys_a_row": 512, "block": 256, "step": 256,
        "chains": 1, "rows_beside": 256}
    assert "schedule/window=None" not in report


def test_flash_mla_phase_tiny():
    """The flash kernels with a second score operand (interpreted) against
    ``dense_attention`` a head at a time and against the kernels without the
    pair, and the two times."""
    report = chip_smoke.flash_mla(length=160, heads=2, repeats=1, chain=2,
                                  interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        "out", "dq", "dk", "dv", "dq_rope", "dk_rope", "concat192/out"]
    assert all(c["ok"] for c in report["checks"])
    assert {"fwd_ms", "fwd_bwd_ms"} <= set(report)


def test_flash_diff_phase_tiny():
    """Differential attention's two maps on the flash kernels (interpreted),
    as one padded call a map and as four calls, causal and banded, against
    ``dense_attention``, with the difference's error and the padded form's
    times."""
    report = chip_smoke.flash_diff(length=128, heads=4, kv_heads=2,
                                   head_dim=64, window=64, repeats=1,
                                   chain=2, interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        f"{mask}/{form}/{name}" for mask in ("causal", "band")
        for form in ("padded", "four_calls")
        for name in ("a1", "a2", "dq", "dk", "dv")]
    assert all(c["ok"] for c in report["checks"])
    assert set(report["times"]) == {"causal/padded", "band/padded"}
    assert set(report["difference_error"]) == {
        f"{mask}/{form}" for mask in ("causal", "band")
        for form in ("padded", "four_calls")}
    assert all(e < 1e-2 for e in report["difference_error"].values())


def test_lightning_phase_tiny():
    """The lightning-attention kernels (interpreted) against the quadratic
    form and the scan form, by chunk size, and the timing table's keys."""
    report = chip_smoke.lightning(length=256, heads=2, chunks=(128, 64),
                                  repeats=1, chain=2, check_rows=128,
                                  interpret=True)
    assert [c["name"] for c in report["checks"]] == [
        f"chunk{c}/{form}/{n}" for c in (128, 64)
        for n in ("out", "dq", "dk", "dv") for form in ("quadratic", "scan")]
    assert all(c["ok"] for c in report["checks"])
    assert set(report["times"]) == {"chunk128", "chunk64"}
    assert all({"fwd_ms", "fwd_bwd_ms"} <= set(t)
               for t in report["times"].values())


def test_flash_select_phase_tiny():
    """The selected walk's kernels (interpreted) on the selection of drawn q
    and k against the masked dense softmax a head at a time, by tile sizes,
    with the walk's counters, and the selection's and plain causal times."""
    sparse = {"kernel_size": 8, "stride": 4, "block": 16, "topk": 8,
              "init_blocks": 1, "local_blocks": 2}
    forms = ((128, 128, 128, 128), (256, 128, 128, 256))
    report = chip_smoke.flash_select(length=512, heads=2, sparse=sparse,
                                     forms=forms, repeats=1, chain=2,
                                     check_rows=256, interpret=True)
    names = ["x".join(map(str, f)) for f in forms]
    assert [c["name"] for c in report["checks"]] == [
        f"{name}/{n}" for name in names for n in ("out", "dq", "dk", "dv")]
    assert all(c["ok"] for c in report["checks"])
    assert set(report["times"]) == set(names)
    for times in report["times"].values():
        assert {"fwd_ms", "fwd_bwd_ms", "counters"} <= set(times)
        assert times["counters"]["visited"] >= times["counters"]["chosen"] > 0
    assert {"select_ms", "plain_causal_fwd_bwd_ms"} <= set(report)


def test_tied_head_phase_tiny():
    """The blocked head's kernel (interpreted) against the ``jax.numpy``
    product and statistics, alone and inside the whole head, at a tied table
    of whole tiles and at a head of its own with a last tile in part, by the
    block, and the timing table's keys."""
    report = chip_smoke.tied_head(
        heads=((128, 1024, True), (256, 584, False)), tokens=256,
        blocks=(128, 256), repeats=1, chain=2, interpret=True)
    tags = [f"d={d}/rows={rows}/block={block}"
            for d, rows in ((128, 1024), (256, 584)) for block in (128, 256)]
    heads = ["d=128/rows=1024", "d=256/rows=584"]
    names = [c["name"] for c in report["checks"]]
    assert sorted(names) == sorted(
        [f"{tag}/{n}" for tag in tags
         for n in ("logits", "lse", "loss", "dx", "dmatrix")]
        + [f"{head}/whole/{n}" for head in heads
           for n in ("loss", "dx", "dmatrix")]), names
    assert all(c["ok"] for c in report["checks"]), report["checks"]
    assert {f"{form}_{unit}/{tag}" for form in ("kernel", "dense")
            for unit in ("ms", "tflop_s") for tag in tags} | {
                f"head_{form}_ms/{tag}" for form in ("kernel", "dense")
                for tag in tags} | {
                    f"head_whole_ms/{head}" for head in heads} <= set(report)
