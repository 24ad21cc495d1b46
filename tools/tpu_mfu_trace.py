"""Device-trace account of the ResNet-50 headline MFU.

Runs the same ResNet-50 train step bench.py measures, wrapped in
``hvd.start_device_trace`` (jax.profiler), then parses the captured
``*.xplane.pb`` with tensorboard_plugin_profile to attribute step time to
op categories (conv/fusion/copy/infeed/...), answering "where does the
other ~70% of the chip go" for the ~0.30 MFU figure.

Prints a JSON summary line starting with "RESULT ".  If the trace holds no
device TraceMes, says so (host-only planes).
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

import horovod_tpu as hvd
from horovod_tpu import models

LOGDIR = os.environ.get("MFU_TRACE_DIR", "/tmp/hvd_mfu_trace")
BATCH = int(os.environ.get("MFU_TRACE_BATCH", "256"))
STEPS = int(os.environ.get("MFU_TRACE_STEPS", "6"))


def build_step(mesh):
    model = models.ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                            bn_axis_name="hvd")
    rng = jax.random.PRNGKey(0)
    images = jax.random.normal(rng, (BATCH, 224, 224, 3), jnp.bfloat16)
    labels = jnp.zeros((BATCH,), jnp.int32)
    variables = jax.jit(lambda: model.init(rng, images[:8], train=False))()
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                  axis_name="hvd")
    opt_state = tx.init(params)

    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            return models.xent_loss(logits, labels), updates["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, hvd.allreduce(loss,
                                                           axis_name="hvd")

    step = jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P("hvd"), P("hvd")),
                  out_specs=(P(), P(), P(), P())),
        donate_argnums=(0, 1, 2))
    return step, params, batch_stats, opt_state, images, labels


def parse_xplane(logdir):
    """Pull per-op-category self-time out of the trace via the tensorboard
    profiler plugin's own converters."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {"error": "no xplane.pb captured"}
    path = max(paths, key=os.path.getmtime)
    try:
        from tensorboard_plugin_profile.convert import raw_to_tool_data
    except Exception as exc:
        return {"error": f"tensorboard_plugin_profile unavailable: {exc}",
                "xplane": path}
    out = {"xplane": path}
    try:
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "op_profile", {})
        out["op_profile"] = json.loads(data) if isinstance(data, str) else data
    except Exception as exc:
        out["op_profile_error"] = str(exc)[:300]
    try:
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [path], "overview_page", {})
        out["overview"] = json.loads(data) if isinstance(data, str) else data
    except Exception as exc:
        out["overview_error"] = str(exc)[:300]
    return out


def summarize_op_profile(op_profile):
    """Flatten the op_profile tree into (category -> fraction of total)."""
    try:
        root = op_profile["byCategory"]
        total = root["metrics"]["time"]
        cats = {}
        for child in root.get("children", []):
            t = child.get("metrics", {}).get("time", 0.0)
            cats[child.get("name", "?")] = round(t / max(total, 1e-9), 4)
        return dict(sorted(cats.items(), key=lambda kv: -kv[1]))
    except Exception as exc:
        return {"parse_error": str(exc)[:200]}


def main():
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices), ("hvd",))
    hvd.init()
    step, params, batch_stats, opt_state, images, labels = build_step(mesh)
    # warmup/compile
    for _ in range(2):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    print(json.dumps({"phase": "warmup_done", "loss": float(loss)}),
          flush=True)

    os.makedirs(LOGDIR, exist_ok=True)
    hvd.start_device_trace(LOGDIR)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    float(loss)  # scalar readback bounds the enqueued work
    dt = time.perf_counter() - t0
    hvd.stop_device_trace()
    img_s = BATCH * STEPS / dt

    parsed = parse_xplane(LOGDIR)
    summary = {
        "img_per_sec_traced": round(img_s, 1),
        "step_ms_traced": round(dt / STEPS * 1e3, 2),
        "xplane": parsed.get("xplane"),
        "categories": summarize_op_profile(parsed.get("op_profile", {})),
    }
    for k in ("error", "op_profile_error", "overview_error"):
        if k in parsed:
            summary[k] = parsed[k]
    # The overview's device-time breakdown (infeed %, idle %) if present.
    try:
        ov = parsed["overview"]
        ia = ov.get("inputPipelineAnalysis", {})
        summary["infeed_pct"] = ia.get("infeedPercentAverage")
        gen = ov.get("generalAnalysis", {})
        summary["idle_ratio"] = gen.get("deviceIdleTimePercent")
        summary["mxu_util_pct"] = gen.get("mxuUtilizationPercent")
    except Exception:
        pass
    print("RESULT " + json.dumps(summary), flush=True)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
