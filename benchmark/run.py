"""One run of one cell of the benchmark (see BENCHMARK.json, benchmark/README.md).

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run: compile cache on, ``hvd.init()``, the cell built
from its entry in BENCHMARK.json and the files that entry names
(``configs/<config>.json`` -> ``families/<family>.py``,
``traffic/<traffic>.json`` -> ``traffic.py``), weights and batches made on
the device from ``--seed``, the step compiled ahead of time, the correctness
pass, the warm-up, then the measured window.  The last line of stdout is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and, last, ``checks``: every number that
``correct`` compared beside its limit, the refused ones at the end and marked.
The same lines close standard error.  Earlier lines are JSON notes.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result: there is no CPU fallback.  ``--rehearse`` runs the
cell's control flow at the configuration's tiny sizes on whatever backend is
there; its result line names that platform and carries no metric.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import common, traffic as traffic_gen  # noqa: E402

TRACE_DIR = os.path.join(HERE, "_trace")


class Phases:
    """Seconds since the process started at which each set-up phase ended:
    where ``setup_s`` goes (a note, not a metric)."""

    def __init__(self):
        self.ends = {}

    def done(self, phase: str) -> None:
        self.ends[phase] = round(time.perf_counter() - _PROCESS_START, 3)


def note(kind: str, **kv) -> None:
    print(json.dumps({"note": kind, **kv}), flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{os.path.relpath(path, ROOT)} not found: a configuration, a "
            "traffic mix and a per-layer metric named in BENCHMARK.json are "
            "each a file of that name (benchmark/README.md)")
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_family(name: str):
    return importlib.import_module(f"benchmark.families.{name}")


def cell_entry(spec: dict, name: str) -> dict:
    """The cell's entry in BENCHMARK.json: its configuration, traffic and
    chips are stated there and nowhere else."""
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"cell {name!r} not found under workloads in "
                   f"BENCHMARK.json: {[w['name'] for w in spec['workloads']]}")


def metrics_of(spec: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` that ``cell`` reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


# ---------------------------------------------------------------------------
# The loop
# ---------------------------------------------------------------------------


def run_steps(step, state, batches, seconds: float, max_steps=None) -> dict:
    """Steps for ``seconds`` (or ``max_steps``), one kept in flight as a
    training loop that logs its loss one step late does: dispatch step i+1,
    then wait for the loss of step i and stamp the host clock.  Step i takes
    batch i modulo their number.  No readback inside the loop; the losses
    come back after it.

    Returns the stamps (the first is the start), the host time inside each
    ``step(...)`` call, the losses, the final state and a failure if a step
    raised."""
    import jax
    from jax.profiler import TraceAnnotation

    def dispatch(state):
        batch = batches[len(dispatch_s) % len(batches)]
        t = time.perf_counter()
        with TraceAnnotation("bench_dispatch"):
            *state, loss = step(*state, *batch)
        dispatch_s.append(time.perf_counter() - t)
        return state, loss

    stamps, dispatch_s, losses, error = [time.perf_counter()], [], [], None
    try:
        state, pending = dispatch(state)
        while pending is not None:
            more = (time.perf_counter() - stamps[0] < seconds
                    and (max_steps is None or len(dispatch_s) < max_steps))
            state, coming = dispatch(state) if more else (state, None)
            with TraceAnnotation("bench_wait"):
                jax.block_until_ready(pending)
            stamps.append(time.perf_counter())
            losses.append(pending)
            pending = coming
    except Exception:  # the run's boundary: report the failed step, go on
        error = traceback.format_exc()
        print(error, file=sys.stderr, flush=True)
    losses = [float(x) for x in jax.device_get(losses)]
    return {"stamps": stamps, "dispatch_s": dispatch_s, "losses": losses,
            "state": state, "error": error}


def last_loss_of_the_first_batch(losses: list, distinct: int):
    """The loss of the window's last step that took the first batch (step i
    takes batch i modulo ``distinct``): what the first loss, which is the
    first batch's, is held against.  With one batch it is the last loss."""
    return losses[(len(losses) - 1) // distinct * distinct] if losses else None


def step_samples_ms(run: dict) -> list:
    """Completion-to-completion times of the window's steps."""
    stamps = run["stamps"]
    return [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]


class GcWatch:
    """The interpreter's garbage collections while it is in ``gc.callbacks``:
    how many, and how long the loop stood still for them.  A note beside the
    longest steps, so that a stall in a timed window (which has no trace)
    can be told from the device's."""

    def __init__(self):
        self.pauses_s, self._start = [], None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses_s.append(time.perf_counter() - self._start)

    def summary(self) -> dict:
        return {"collections": len(self.pauses_s),
                "seconds": sum(self.pauses_s),
                "longest_s": max(self.pauses_s, default=0.0)}


class CompileWatch:
    """What jax itself records of compiling while this is entered: each
    ``/jax/core/compile/*`` duration (a trace, a lowering, a backend compile
    or load) and each ``/jax/compilation_cache/*`` event (a read, a hit, a
    miss and the write that follows it).  A warm window has none; a note
    beside the longest steps, so that a stall in a timed window can be told
    from a program that compiled there.  Nothing runs per step."""

    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        self.seen = {}

    def __call__(self, event: str, seconds: float = 0.0, **_) -> None:
        if event.startswith(self.PREFIXES):
            count, total = self.seen.get(event, (0, 0.0))
            self.seen[event] = (count + 1, total + seconds)

    def __enter__(self):
        from jax import monitoring

        monitoring.register_event_listener(self)
        monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_listener(self)
        monitoring.unregister_event_duration_listener(self)

    def summary(self) -> dict:
        def count(event):
            return self.seen.get(event, (0, 0.0))[0]

        return {"compilations": count(
                    "/jax/core/compile/backend_compile_duration"),
                "cache_reads": count(
                    "/jax/compilation_cache/compile_requests_use_cache"),
                "cache_writes": count("/jax/compilation_cache/cache_misses"),
                "seconds": sum((s for _, s in self.seen.values()), 0.0),
                "events": {e.rsplit("/", 1)[1]: n
                           for e, (n, _) in sorted(self.seen.items())}}


def end_to_end(run: dict, flops_per_step: float, chips: int,
               peak_flops: float) -> dict:
    """From the completion stamps of one window: ``step_ms`` and ``mfu_pct``
    over the whole window and all its steps, so every stall counts, and
    ``step_ms_p90``, the tail of all the steps' completion-to-completion
    times."""
    samples = step_samples_ms(run)
    window_s = run["stamps"][-1] - run["stamps"][0]
    return {
        "step_ms": 1e3 * window_s / len(samples),
        "step_ms_p90": statistics.quantiles(samples, n=10,
                                            method="inclusive")[-1],
        "mfu_pct": 100.0 * len(samples) * flops_per_step
        / (window_s * chips * peak_flops),
    }


# ---------------------------------------------------------------------------
# The traced window
# ---------------------------------------------------------------------------


def traced_window(step, state, batches, steps: int, out_dir: str) -> tuple:
    """``steps`` steps under the profiler, inside one ``bench_window`` span.
    Returns the run and the path of the ``.xplane.pb`` it wrote."""
    import glob

    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the loop's own spans are enough
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            run = run_steps(step, state, batches, math.inf, max_steps=steps)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {out_dir}: {found}")
    return run, found[0]


def per_layer(spec: dict, cell_name: str, trace, ctx: dict) -> dict:
    """Each per-layer metric of the cell through the reader its own file
    names; a reader that finds nothing leaves the metric out."""
    out = {}
    for m in metrics_of(spec, "per_layer", cell_name):
        meta = load_json("layer_metrics", m["name"] + ".json")
        value = common.load_function(meta["reducer"])(
            trace, ctx, **meta.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def memory_peak_bytes(stats: list) -> int:
    """Peak device memory on the fullest chip, from the runtime's own
    counters.  ``peak_bytes_in_use`` is the peak of live arrays (state,
    batch); the temporaries a program allocates while it runs are counted
    apart, as ``peak_bytes_reserved`` (ResNet-50 b256 on a TPU v5 lite: 0.47
    GB in use, 9.02 GB reserved, against 9.06 GB of compiled temporaries), so
    the peak is their sum."""
    return max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)


# ---------------------------------------------------------------------------
# The result line
# ---------------------------------------------------------------------------


def short_name(check: str) -> str:
    """``first_moment['params']['nsp_head']['kernel']`` ->
    ``first_moment.nsp_head.kernel``: a leaf's path as a plain name."""
    return re.sub(r"\['([^']+)'\]", r".\1", check.replace("['params']", ""))


def compared(checks: list) -> dict:
    """Each check as ``{"value", "limit"}`` (sound below the limit),
    ``{"value", "least"}`` (sound from it upward; a check that is only true
    or false reads 1 or 0 against 1), the refused ones last with ``"ok":
    false``: what a record that keeps only a line's end still holds."""
    out = {}
    for c in sorted(checks, key=lambda c: not c["ok"]):
        entry = ({"value": c["value"], "limit": c["tol"]} if "tol" in c else
                 {"value": c.get("value", int(c["ok"])),
                  "least": c.get("least", 1)})
        out[short_name(c["name"])] = entry if c["ok"] else {**entry,
                                                            "ok": False}
    return out


def result_line(checks: list, attempted: int, failed: int, metrics: dict,
                device: dict, breakdown=None) -> dict:
    """The last line of a run.  ``checks`` comes last: the driver reads the
    keys before it and keeps the line's numbers when a run is refused."""
    result = {"correct": all(c["ok"] for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = compared(checks)
    return result


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend: control flow only, "
                         "no metric in the result")
    return ap.parse_args(argv)


def set_up(args, spec: dict, phases: Phases) -> dict:
    """Everything before the window, in the order the memory allows: the
    reference runs and frees what it made before the optimizer state and the
    step's temporaries exist.  Returns None (after saying why on stderr)
    where the machine cannot run the cell."""
    entry = cell_entry(spec, args.workload)
    cfg = load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        load_json("traffic", entry["traffic"] + ".json"), args.rehearse)
    chips = entry["chips"]

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # Small programs (weights, batches, reference) go to the cache too, so
    # that a second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    phases.done("import_jax_and_reach_devices")
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"benchmark: JAX found no TPU (platform={platform!r}); there "
              "is no CPU fallback (--rehearse checks control flow only)",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"benchmark: cell {args.workload!r} needs {chips} chip(s), JAX "
              f"reports {len(devices)}", file=sys.stderr)
        return None
    devices = devices[:chips]
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": chips}

    import horovod_tpu as hvd

    hvd.init()
    phases.done("hvd_init")
    family = load_family(cfg["family"])
    mesh = common.hvd_mesh(devices)
    cell = family.setup(cfg, mesh, args.seed, rehearse=args.rehearse)
    cell["traffic"] = traffic
    cell["batches"] = batches = traffic_gen.make_batches(
        traffic, family.inputs(cell, traffic), mesh, args.seed)
    jax.block_until_ready((batches, cell["params"]))
    phases.done("weights_and_batches")
    flops_per_step = family.model_flops(cell)
    phases.done("model_flops")
    ref = family.reference(cell)
    phases.done("reference")
    step, state = family.build(cell)
    phases.done("build_and_compile")
    hlo = common.hlo_counts(step.as_text())
    memory = step.memory_analysis()
    phases.done("hlo_counts")
    # A family whose first loss cannot see all of its model probes the
    # compiled step once more, on a state of its own making.
    probed = family.probe(cell, step, state) if hasattr(family, "probe") else []
    *state, loss = step(*state, *batches[0])
    first_loss = float(loss)
    checks = family.checks(cell, ref, first_loss, state, hlo) + probed
    del ref
    phases.done("first_step_and_checks")
    for _ in range(traffic["warmup_steps"]):
        *state, loss = step(*state, *batches[0])
    jax.block_until_ready(loss)
    setup_s = time.perf_counter() - _PROCESS_START
    phases.done("warmup")
    note("cell", workload=args.workload, config=cfg["name"],
         traffic=traffic["name"], seed=args.seed,
         device=device, compile_cache=cache_dir, hlo=hlo,
         flops_per_step=flops_per_step, first_loss=first_loss,
         setup_phases_end_s=phases.ends,
         compiled_bytes={k: getattr(memory, k, None) for k in (
             "argument_size_in_bytes", "output_size_in_bytes",
             "alias_size_in_bytes", "temp_size_in_bytes")} if memory else None,
         checks=checks)
    return {"family": family, "cell": cell, "cfg": cfg, "traffic": traffic,
            "step": step, "state": state, "devices": devices,
            "device": device, "checks": checks, "first_loss": first_loss,
            "setup_s": setup_s, "flops_per_step": flops_per_step}


def reader_context(up: dict, run: dict, peaks: dict, xplane: str = None,
                   workload: str = None) -> dict:
    """What a per-layer reader may read besides the trace: the traced run
    (stamps, host time inside each ``step(...)`` call, losses), the cell as
    its family built it, the configuration with its ``assumed`` keys folded
    in, the traffic's parameters, the chip's row of peaks.json, the path of
    the ``.xplane.pb`` (for a reader that wants more of the file than
    ``Trace`` holds) and the cell's name."""
    cfg = {**up["cfg"].get("assumed", {}), **up["cfg"]}
    return {"run": run, "cell": up["cell"], "cfg": cfg,
            "traffic": up["traffic"], "peaks": peaks, "xplane": xplane,
            "workload": workload}


def traced_metrics(args, spec: dict, up: dict, run: dict, xplane: str,
                   peaks: dict) -> tuple:
    """Per-layer metrics, ``busy_s``/``window_s`` and the breakdown of one
    traced window."""
    from benchmark import trace_reduce

    started = time.perf_counter()
    trace = trace_reduce.read_xplane(xplane, steps=len(run["losses"]))
    read_s = time.perf_counter() - started
    metrics = per_layer(spec, args.workload, trace,
                        reader_context(up, run, peaks, xplane, args.workload))
    window = trace.window
    seen = {"window_s": (window[1] - window[0]) / 1e9,
            "busy_s": trace_reduce.mean_over_devices(
                trace, lambda ev: trace_reduce.busy_ns(
                    trace_reduce.sync_ops(ev), window)) / 1e9}
    first = trace.devices[min(trace.devices)]
    breakdown = {
        "device_ops": trace_reduce.top_ops(first, window),
        "idle_gaps": trace_reduce.idle_gaps(first, trace.host, window)}
    note("trace", xplane=os.path.relpath(xplane, ROOT), steps=trace.steps,
         devices=sorted(trace.devices), read_xplane_s=read_s,
         ops_with_scope=sum(bool(e.scope) for e in first), ops=len(first),
         host_spans=len(trace.host))
    return metrics, seen, breakdown


def timed_metrics(args, spec: dict, up: dict, run: dict, peaks: dict) -> dict:
    """The cell's end-to-end metrics of one timed window."""
    chips = up["device"]["count"]
    values = end_to_end(run, up["flops_per_step"], chips,
                        peaks["bf16_flops_per_s"])
    values["setup_s"] = up["setup_s"]
    what, per_step = up["family"].units(up["cell"])
    steps = len(run["losses"])
    window_s = run["stamps"][-1] - run["stamps"][0]
    samples, dispatch_s = step_samples_ms(run), run["dispatch_s"]
    longest = sorted(enumerate(samples), key=lambda kv: -kv[1])[:5]
    note("throughput", **{
        f"{what}_per_s_per_chip": per_step * steps / window_s / chips,
        "steps": steps, "window_s": window_s,
        "step_ms_median": statistics.median(samples),
        "longest_steps_ms": longest,
        # Between the stamps of steps i and i+1 the loop dispatches step i+1
        # and waits for step i: a long step whose dispatch is short stood
        # still in the wait (the device, the runtime or a descheduled host).
        "dispatch_ms_in_longest_steps": [
            1e3 * dispatch_s[i + 1] if i + 1 < len(dispatch_s) else None
            for i, _ in longest],
        "gc_in_window": run["gc"],
        "compiles_in_window": run["compiles"],
        "host_dispatch_ms_mean": 1e3 * statistics.mean(run["dispatch_s"]),
        "first_loss": up["first_loss"], "last_loss": run["losses"][-1]})
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(spec, "end_to_end", args.workload)}


def main(argv=None) -> int:
    args = parse(argv)
    spec = load_spec()
    peaks_table = load_json("peaks.json")["peaks"]
    up = set_up(args, spec, Phases())
    if up is None:
        return 1

    import horovod_tpu as hvd
    from benchmark import flops

    xplane = None
    if args.trace:
        run, xplane = traced_window(
            up["step"], up["state"], up["cell"]["batches"],
            up["traffic"]["trace_steps"],
            os.path.join(TRACE_DIR, args.workload))
    else:
        watch = GcWatch()
        gc.callbacks.append(watch)
        with CompileWatch() as compiles:
            run = run_steps(up["step"], up["state"], up["cell"]["batches"],
                            args.seconds)
        gc.callbacks.remove(watch)
        run["gc"], run["compiles"] = watch.summary(), compiles.summary()
    hvd.shutdown()

    losses, first_loss, checks = run["losses"], up["first_loss"], up["checks"]
    finite = [x for x in losses if math.isfinite(x)]
    attempted = len(losses) + (1 if run["error"] else 0)
    failed = attempted - len(finite)
    last = last_loss_of_the_first_batch(losses, len(up["cell"]["batches"]))
    checks.append({"name": "losses_finite_and_falling", "ok": bool(
        failed == 0 and math.isfinite(first_loss) and finite
        and last < first_loss),
        "value": last if failed == 0 else None, "tol": first_loss})
    if not all(c["ok"] for c in checks):
        note("failed_checks", checks=[c for c in checks if not c["ok"]])

    device = up["device"]
    stats = [d.memory_stats() or {} for d in up["devices"]]
    device["memory_peak_bytes"] = memory_peak_bytes(stats)
    note("memory", stats=stats[0])
    metrics, breakdown = {}, None
    if args.rehearse:
        device["rehearsal"] = True  # and no metric: not a measurement
    else:
        peaks = flops.chip_peaks(device["kind"], peaks_table)
        if args.trace:
            metrics, seen, breakdown = traced_metrics(args, spec, up, run,
                                                      xplane, peaks)
            device.update(seen)
        else:
            metrics = timed_metrics(args, spec, up, run, peaks)

    result = result_line(checks, attempted, failed, metrics, device, breakdown)
    for name, entry in result["checks"].items():
        print(f"check {name}: {json.dumps(entry)}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
