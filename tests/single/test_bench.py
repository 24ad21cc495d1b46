"""bench.py: one process, one JSON line that names its device, non-zero exit
instead of a stand-in number."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench.py")


def _run(tmp_path, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"), **env_extra)
    return subprocess.run([sys.executable, BENCH], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=420)


def test_end_to_end_tiny_cpu(tmp_path):
    # Every phase (headline, flash appendix in interpret mode, BERT, device
    # codec, compiled-collective inventory) at toy sizes on the CPU backend.
    proc = _run(tmp_path, _HVD_TPU_BENCH_TINY="1")
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-1500:])
    assert len(lines) == 1
    result = json.loads(lines[0])
    assert result["metric"] == "resnet50_train_images_per_sec_per_chip"
    assert result["value"] > 0
    # The line says what it ran on, and a CPU run carries no device metric.
    assert result["platform"] == "cpu" and result["n_devices"] >= 1
    assert "mfu" not in result
    # The flash appendix must have run (interpret mode on CPU) and matched
    # dense math.
    assert result["flash_attn_max_abs_err"] < 0.05
    assert not any(k.endswith("_error") for k in result)


def test_full_size_refuses_a_machine_without_tpu(tmp_path):
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    import bench

    assert bench._chip_peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no published bf16 peak"):
        bench._chip_peak_flops("TPU v9 imaginary")
