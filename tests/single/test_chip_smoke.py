"""chip_smoke.py: the script refuses a machine without a TPU, and each of
its phase functions passes at a tiny size on the virtual CPU mesh (Pallas
kernels in the interpreter).  What the phases prove, they prove on the chip;
this file keeps their control flow from rotting between chip runs."""

import dataclasses
import json
import os
import subprocess
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from horovod_tpu import models  # noqa: E402


class _TinyNet(nn.Module):
    """The smallest model with what the ResNet phases lean on: a conv, a
    cross-replica BatchNorm over ``hvd``, ``num_classes`` and ``dtype``."""
    num_classes: int = 10
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(8, (3, 3), strides=2, use_bias=False)(x)
        x = nn.BatchNorm(use_running_average=not train, axis_name="hvd")(x)
        return nn.Dense(self.num_classes)(nn.relu(x).mean(axis=(1, 2)))


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


def test_resnet_train_and_sync_phases():
    report, run = chip_smoke.resnet50_train(
        model=_TinyNet(), devices=jax.devices()[:1], batch=8, image=16,
        steps=5)
    assert report["losses"][-1] < report["losses"][0]
    report = chip_smoke.sync(run, n=2)
    assert report["block_until_ready_ms"] > 0


def test_gpt_flash_train_phase():
    cfg = dataclasses.replace(models.GPT_TINY, use_flash=True, num_layers=1,
                              max_seq_len=64, dtype=jnp.bfloat16)
    # Off the TPU flash_attention dispatches to its dense fallback, so the
    # kernel count is the chip's to assert.
    report = chip_smoke.gpt_flash_train(cfg=cfg, devices=jax.devices()[:1],
                                        batch=2, steps=2, expect_kernel=False)
    assert all(c["ok"] for c in report["checks"])


def test_kernels_phase_interpreted():
    # 160 pads to 256: the padding path.  One dtype and one mask here; the
    # chip runs the product.
    report = chip_smoke.kernels(interpret=True, seqs=(64, 160), heads=2,
                                head_dim=32, codec_elems=5000,
                                dtypes=("bfloat16",), causals=(True,))
    assert all(c["ok"] for c in report["checks"])
    assert [c["codec"] for c in report["codecs"]] == ["int8", "int4", "int8g"]


def test_spmd_dp4_phase_on_four_virtual_devices():
    report = chip_smoke.spmd_dp4(model=_TinyNet(),
                                 devices=jax.devices()[:4], batch=16,
                                 image=16, steps=2)
    assert report["per_chip"] == 4 and report["all_reduce_ops"] > 0
    assert len(report["param_devices"]) == 4


def test_spmd_dp4_refuses_fewer_devices():
    with pytest.raises(AssertionError):
        chip_smoke.spmd_dp4(model=_TinyNet(), devices=jax.devices()[:2],
                            batch=8, image=16, steps=1)
