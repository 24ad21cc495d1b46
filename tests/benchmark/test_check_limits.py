"""Where the limits of ``correct`` stand, held against the readings on record
(``benchmark/testdata/check_readings/<family>.json``, one file a family, by
the rule of ``benchmark/testdata/check_rule.json``); the two faults that read
nearest to the limits of ``bert``'s (b) and (c), made at ``--rehearse``'s
sizes; the last line a run prints; a run whose step is broken.  Nothing here measures anything and nothing
starts a process."""

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import common, run  # noqa: E402
from benchmark import traffic as traffic_gen  # noqa: E402
from benchmark.families import bert  # noqa: E402

import bert_faults  # noqa: E402  (beside this file)

MARGIN = run.load_json("testdata", "check_rule.json")["rule"]["margin"]
# A family's readings are the file of its name: a later PR adds a family's
# file and edits none (test_a_new_family.py does so in a copy).
READINGS = {os.path.splitext(f)[0]: run.load_json("testdata",
                                                  "check_readings", f)
            for f in sorted(os.listdir(os.path.join(
                REPO, "benchmark", "testdata", "check_readings")))}
CONSTANTS = [(family, name) for family, readings in READINGS.items()
             for name in readings["limits"]]
FAMILIES = sorted(os.path.splitext(f)[0] for f in os.listdir(os.path.join(
    REPO, "benchmark", "families")) if not f.startswith("_"))


def _family(cell: str) -> str:
    """The family of a cell's configuration, as BENCHMARK.json leads to it."""
    entry = run.cell_entry(run.load_spec(), cell)
    return run.load_json("configs", entry["config"] + ".json")["family"]


def _sound_readings(family: str, pattern: str) -> list:
    """``(value, where)`` of every reading on record of the checks that
    ``pattern`` names, in the runs of ``family``'s cells."""
    return [(value, f"PR {r['pr']}, {r['cell']}, seed {r['seed']}: {check}")
            for r in READINGS[family]["runs"]
            for check, value in r["checks"].items()
            if re.search(pattern, check)]


@pytest.mark.parametrize("family,name", CONSTANTS,
                         ids=[f"{f}.{n}" for f, n in CONSTANTS])
def test_a_limit_stands_between_the_sound_readings_and_the_faults(family,
                                                                  name):
    """The rule at the head of ``families/bert.py``.  A ``benchmark`` PR adds
    a larger sound reading than any on record to the family's file as one
    more run, and this says which limit has gone thin."""
    entry = READINGS[family]["limits"][name]
    limit = getattr(importlib.import_module(f"benchmark.families.{family}"),
                    name)
    largest, where = max(_sound_readings(family, entry["checks"]) + [
        (e["largest"], f"PR {e['pr']}, largest of {e['runs']} runs")
        for e in entry["earlier"]])
    assert limit >= MARGIN * largest, (
        f"{family}.{name} = {limit} is under {MARGIN} x the largest sound "
        f"reading {largest} ({where})")
    for fault in entry["faults"]:
        assert limit * MARGIN <= min(fault["readings"]), (
            f"{family}.{name} = {limit} is not {MARGIN} x under "
            f"{min(fault['readings'])}, what {fault['fault']!r} reads")


def test_every_limit_and_every_reading_is_on_record():
    """A family comes with the file of its readings, a ``TOL_*`` that a
    family gains with its entry there, and a check that a recorded run
    compared is some limit's: none is passed over."""
    for family in FAMILIES:
        rel = f"benchmark/testdata/check_readings/{family}.json"
        assert family in READINGS, (
            f"benchmark/families/{family}.py has no readings on record: add "
            f"{rel} with its `limits` and the `runs` they were set from "
            "(benchmark/README.md, \"A family\")")
        limits, runs = READINGS[family]["limits"], READINGS[family]["runs"]
        module = importlib.import_module(f"benchmark.families.{family}")
        assert set(limits) == {n for n in vars(module)
                               if n.startswith("TOL_")}, rel
        assert runs, f"{rel} has no run"
        for cell in {r["cell"] for r in runs}:
            assert _family(cell) == family, (
                f"{rel}: a run of {cell}, whose configuration's family is "
                f"{_family(cell)}")
        for check in {c for r in runs for c in r["checks"]}:
            owners = [n for n, e in limits.items()
                      if re.search(e["checks"], check)]
            assert len(owners) == 1, (rel, check, owners)
    assert sorted(READINGS) == FAMILIES, (
        "a file under benchmark/testdata/check_readings/ is named for no "
        "file under benchmark/families/")


# ---------------------------------------------------------------------------
# The last line
# ---------------------------------------------------------------------------

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
SOUND = [common.check("first_loss_vs_reference", 2.7e-5, 2e-3),
         common.at_least("tpu_custom_calls", 72, 72),
         common.check("first_moment['params']['nsp_head']['kernel']",
                      2.0e-2, 0.16),
         {"name": "logits_are_float32", "ok": True},
         {"name": "losses_finite_and_falling", "ok": True,
          "value": 8.4, "tol": 11.5}]
REFUSED = common.check("decode_of_the_reference_s_hidden_vs_reference",
                       2.0623831993965525e-06, 1e-06)


@pytest.mark.parametrize("checks,correct", [(SOUND, True),
                                            ([REFUSED, *SOUND], False)],
                         ids=["sound", "refused"])
def test_the_last_line_names_each_check_with_its_number_and_its_limit(
        checks, correct):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = run.result_line(checks, 188, 0, {}, device,
                           breakdown={"device_ops": [], "idle_gaps": []})
    # The keys the driver reads, then the compared numbers, last.
    assert list(line) == [*RESULT_KEYS, "breakdown", "checks"]
    assert list(run.result_line(checks, 188, 0, {}, device)) == [
        *RESULT_KEYS, "checks"]
    assert line["correct"] is correct
    got = json.loads(json.dumps(line))["checks"]
    assert got["first_loss_vs_reference"] == {"value": 2.7e-5, "limit": 2e-3}
    assert got["tpu_custom_calls"] == {"value": 72, "least": 72}
    assert got["first_moment.nsp_head.kernel"] == {"value": 2.0e-2,
                                                   "limit": 0.16}
    assert got["logits_are_float32"] == {"value": 1, "least": 1}
    assert got["losses_finite_and_falling"] == {"value": 8.4, "limit": 11.5}
    if correct:
        assert not any("ok" in entry for entry in got.values())
    else:
        # The refused check closes the line: a record of its end holds it.
        assert list(got)[-1] == REFUSED["name"]
        assert got[REFUSED["name"]] == {
            "value": 2.0623831993965525e-06, "limit": 1e-06, "ok": False}
        assert [n for n, e in got.items() if "ok" in e] == [REFUSED["name"]]


# ---------------------------------------------------------------------------
# The faults' readings (bert_faults.py reads them at the cell's size)
# ---------------------------------------------------------------------------


def test_a_missing_mask_and_e4m3_read_over_the_limits_that_moved():
    """(b) on the masked-LM logits and (c) on the tied embeddings at
    ``--rehearse``'s sizes: the two faults that read nearest to those limits
    in the cell, padded keys left in the softmax and the reference in
    float8_e4m3, are refused by both with the rule's room.  (What they read
    at the cell's own size, on the chip, is in check_readings/bert.json; a
    gather one position off reads 1 here and 1e-3 there.)"""
    import jax

    cfg = run.load_json("configs", "bert-large.json")
    traffic = traffic_gen.resolve(
        run.load_json("traffic", "bert-phase2-32x512x1.json"), True)
    cell = bert.setup(cfg, common.hvd_mesh(jax.devices()[:1]), seed=11,
                      rehearse=True)
    drawn = traffic_gen.make_batches(traffic, bert.inputs(cell, traffic),
                                     cell["mesh"], 11)[0]
    got = bert_faults.readings(
        ["missing_mask", "e4m3"], cell["params"]["params"],
        cell["bcfg"].vocab_size, bert.shape_batch(traffic, *drawn), micro=4)
    for fault, read in got.items():
        assert read["sample_mlm_logits"] > MARGIN * bert.TOL_SAMPLE_MLM_LOGITS, (
            fault, read)
        assert read["first_moment_tied"] > MARGIN * bert.TOL_FIRST_MOMENT_TIED, (
            fault, read)


# ---------------------------------------------------------------------------
# A run whose timed path is broken
# ---------------------------------------------------------------------------


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch,
                                                                capsys):
    """The whole of a run past its look for a chip (``--rehearse``), in this
    process, with the compiled step wrapped so that it hands back the state
    it was given: ``correct`` comes out false, and the last line names the
    first updates that did not happen and the losses that did not fall."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.utils import compile_cache

    class Stuck:
        def __init__(self, step):
            self.step = step

        def __call__(self, params, opt_state, *batch):
            kept = jax.tree_util.tree_map(jnp.copy, (params, opt_state))
            *_, loss = self.step(params, opt_state, *batch)
            return (*kept, loss)

        def __getattr__(self, name):    # as_text, memory_analysis
            return getattr(self.step, name)

    def build(cell, real=bert.build):
        step, state = real(cell)
        return Stuck(step), state

    monkeypatch.setattr(bert, "build", build)
    # A test leaves no compile cache behind and no setting for the next one.
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    settings = ("jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")
    kept = {k: getattr(jax.config, k) for k in settings}
    try:
        code = run.main(["--workload", "bert-large-s512", "--seed", "5",
                         "--seconds", "0.2", "--trace", "0", "--rehearse"])
    finally:
        for k, v in kept.items():
            jax.config.update(k, v)
    assert code == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is False
    refused = {n for n, e in result["checks"].items() if e.get("ok") is False}
    assert "losses_finite_and_falling" in refused
    assert len([n for n in refused if n.startswith("first_update")]) == 4
    assert len([n for n in refused if n.startswith("first_moment")]) == 4
    # What the broken step leaves alone still reads sound.
    assert "ok" not in result["checks"]["sample_mlm_logits_vs_reference"]
    assert "ok" not in result["checks"]["first_loss_vs_reference"]
