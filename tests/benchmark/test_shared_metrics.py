"""The per-layer metrics that several families share (PR 65 folded 36
per-family copies onto twelve names): ``{family}`` in a roofline's ``least``,
and, for every cell a shared metric lists, that the family's step traces what
the metric reads, so that a list cannot name a cell with nothing to read.
Tiny sizes on the CPU: which scopes a step is traced under, not a time."""

import functools
import inspect
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import common, run, trace_reduce  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.trace_reduce import Event, Trace  # noqa: E402

SPEC = run.load_spec()
KERNEL = "%hvd_moe_gmm.1 = bf16[8] custom-call(bf16[8] %x)"
TRACE = Trace({0: [Event(
    KERNEL, 0, 200, "XLA Ops",
    "jit(step)/jvp(M)/layer_0/moe/hvd_moe_experts/hvd_moe_gmm/pallas_call:")]},
    [], (0.0, 1000.0), 2)


def _shared():
    """``[(metric, cell), ...]``: each cell of each metric whose file holds
    ``examples``, one of each cell that reads it."""
    pairs = []
    for m in SPEC["per_layer"]:
        if "examples" in run.load_json("layer_metrics", m["name"] + ".json"):
            pairs += [(m["name"], cell) for cell in m["workloads"]]
    return pairs


SHARED = _shared()


# ---------------------------------------------------------------------------
# ``{family}`` in ``least``
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["sdar", "laguna", "joyai"])
def test_family_in_least_is_the_configurations_family(family, monkeypatch):
    """The placeholder is filled from ``ctx["cfg"]["family"]``: the
    family's own function counts the least time, from the same context."""
    asked = []

    def load(dotted):
        asked.append(dotted)
        return lambda ctx: {"seconds": 25e-9 * len(ctx["cfg"]["family"])}

    monkeypatch.setattr(common, "load_function", load)
    got = trace_reduce.roofline_pct(
        TRACE, {"cfg": {"family": family}}, pattern="",
        scope="hvd_moe_experts[/)]", line="sync",
        least="{family}_flops.experts_step_least")
    assert asked == [f"{family}_flops.experts_step_least"]
    assert got == pytest.approx(100 * 25 * len(family) / 100)


@pytest.mark.parametrize("family", ["sdar", "laguna", "joyai"])
def test_each_familys_least_time_of_its_experts_is_a_function(family):
    fn = common.load_function(f"{family}_flops.experts_step_least")
    assert callable(fn) and list(inspect.signature(fn).parameters) == ["ctx"]


def test_a_least_without_the_placeholder_reads_as_it_did(monkeypatch):
    asked = []

    def load(dotted):
        asked.append(dotted)
        return lambda ctx: {"seconds": 50e-9}

    monkeypatch.setattr(common, "load_function", load)
    # No configuration in the context at all: nothing is looked up in it.
    assert trace_reduce.roofline_pct(
        TRACE, {}, pattern="^%hvd_moe", least="sdar_flops.experts_step_least"
    ) == pytest.approx(50.0)
    assert asked == ["sdar_flops.experts_step_least"]


def test_a_family_without_the_module_fails_and_names_what_was_looked_for():
    with pytest.raises(ModuleNotFoundError, match="benchmark.toy_flops"):
        trace_reduce.roofline_pct(
            TRACE, {"cfg": {"family": "toy"}}, pattern="",
            scope="hvd_moe_experts[/)]",
            least="{family}_flops.experts_step_least")
    # Nothing to read comes first: a cell without the scope asks for no
    # module (the reader returns None and the metric is left out).
    assert trace_reduce.roofline_pct(
        TRACE, {"cfg": {"family": "toy"}}, pattern="", scope="hvd_no_such",
        least="{family}_flops.experts_step_least") is None


def test_the_placeholder_needs_the_configurations_family():
    with pytest.raises(KeyError, match="family"):
        trace_reduce.roofline_pct(
            TRACE, {"cfg": {}}, pattern="", scope="hvd_moe_experts[/)]",
            least="{family}_flops.experts_step_least")


def test_the_shared_roofline_reads_each_familys_example_by_its_family():
    """The three examples of ``moe_experts_roofline`` differ in ``cfg.family``
    and in the family's own sizes; each reads 50 % through its own module."""
    meta = run.load_json("layer_metrics", "moe_experts_roofline.json")
    assert meta["params"]["least"] == "{family}_flops.experts_step_least"
    families = [e["context"]["cfg"]["family"] for e in meta["examples"]]
    assert families == ["sdar", "laguna", "joyai"]
    for example in meta["examples"]:
        cfg = run.load_json("configs", run.cell_entry(
            SPEC, example["cell"])["config"] + ".json")
        assert cfg["family"] == example["context"]["cfg"]["family"]


# ---------------------------------------------------------------------------
# A list names a cell only where the family's step has what the metric reads
# ---------------------------------------------------------------------------

OP_NAME = re.compile(r'op_name="([^"]*)"')


@functools.lru_cache(maxsize=None)
def _step_of(cell_name: str) -> tuple:
    """``(family module, scopes)`` of the cell's step built at the
    configuration's tiny sizes: the ``op_name`` of every instruction of the
    compiled text, as the profiler would write it into a trace."""
    import jax

    import horovod_tpu as hvd

    hvd.init()
    entry = run.cell_entry(SPEC, cell_name)
    cfg = run.load_json("configs", entry["config"] + ".json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", entry["traffic"] + ".json"), True)
    family = run.load_family(cfg["family"])
    mesh = common.hvd_mesh(jax.devices()[:entry["chips"]])
    cell = family.setup(cfg, mesh, 3000000019, rehearse=True)
    cell["traffic"] = traffic
    cell["batches"] = traffic_gen.make_batches(
        traffic, family.inputs(cell, traffic), mesh, 3000000019)
    step, _ = family.build(cell)
    return family, tuple(sorted(set(OP_NAME.findall(step.as_text()))))


def _reads(meta: dict, scopes: tuple) -> bool:
    """Whether the metric's own reader finds something in a trace of one op
    under each of ``scopes`` (a roofline selects as ``op_time_ms`` does; its
    least time is not asked for)."""
    events = [Event("%op = f32[8] fusion()", i, 1, "XLA Ops", scope)
              for i, scope in enumerate(scopes)]
    trace = Trace({0: events}, [], (0.0, float(len(events))), 1)
    reducer, params = meta["reducer"], dict(meta.get("params", {}))
    if reducer == "trace_reduce.roofline_pct":
        reducer = "trace_reduce.op_time_ms"
        params = {k: v for k, v in params.items()
                  if k not in ("least", "least_key")}
    return common.load_function(reducer)(trace, {}, **params) is not None


@pytest.mark.parametrize("metric,cell", SHARED)
def test_a_shared_metric_lists_a_cell_whose_step_has_what_it_reads(metric,
                                                                   cell):
    meta = run.load_json("layer_metrics", metric + ".json")
    family, scopes = _step_of(cell)
    if meta["reducer"].endswith("expert_load_max_over_mean"):
        # A counter of the family's probe, not a scope of the step.
        assert 'cell["expert_load"] =' in inspect.getsource(family), (
            f"{metric} lists {cell}, whose family counts no expert_load")
        return
    assert _reads(meta, scopes), (
        f"{metric} lists {cell}, and no instruction of its step is traced "
        f"under what {meta['params']} selects")


def test_a_cell_without_the_scope_would_be_refused():
    """The test above can fail: ``resnet50-1chip`` has no embedding and no
    expert, and the selections of ``embed_ms`` and ``moe_route_ms`` find
    nothing in its step."""
    _, scopes = _step_of("resnet50-1chip")
    for metric in ("embed_ms", "moe_route_ms", "lm_head_ms"):
        assert not _reads(run.load_json("layer_metrics", metric + ".json"),
                          scopes), metric
    assert _reads(run.load_json("layer_metrics", "block_rest_ms.json"),
                  scopes)


def test_every_retired_name_is_an_example_of_the_name_that_took_it():
    """36 copies went; each one's example is kept, under ``was``, by the file
    whose name reads the cell now."""
    was = {}
    for m in SPEC["per_layer"]:
        meta = run.load_json("layer_metrics", m["name"] + ".json")
        for example in meta.get("examples", ()):
            assert example["cell"] in m["workloads"], (m["name"], example)
            if "was" in example:
                was[example["was"]] = m["name"]
    assert len(was) >= 36
    assert not set(was) & {m["name"] for m in SPEC["per_layer"]}
    assert not any(os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", name + ".json")) for name in was)
