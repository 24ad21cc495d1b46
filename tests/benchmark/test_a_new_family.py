"""A later PR adds a family with new files and entries and edits no file
the benchmark has (``benchmark/README.md``, "A family"): in a copy of
``BENCHMARK.json``, ``benchmark/`` and ``tests/benchmark/`` with a family
``toy`` added that way, the copy's own tests of files, names, the contract
and the limits pass as they stand; with the family's readings file taken out
they fail and name the file to add.  Nothing here starts a process: the
copy's modules are imported in this one, in place of the tree's."""

import contextlib
import hashlib
import importlib
import importlib.util
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TESTS = os.path.join("tests", "benchmark")
READINGS = os.path.join("benchmark", "testdata", "check_readings")

# What a new family touches, by test file: files and names, the contract,
# the cell's metrics; the limits.
A_DATA_FILE = "test_data_file_loads_and_is_named_for_its_content"
A_CELL = "test_per_layer_goes_through_each_metric_file_of_the_cell"
A_LIMIT = "test_a_limit_stands_between_the_sound_readings_and_the_faults"
TESTS_OF_THE_COPY = {
    "test_benchmark": (A_DATA_FILE, A_CELL,
                       "test_every_name_resolves_to_a_file_and_back",
                       "test_benchmark_json_keeps_the_contract"),
    "test_check_limits": (A_LIMIT,
                          "test_every_limit_and_every_reading_is_on_record")}


def _files(root) -> dict:
    """``{relative path: sha256}`` of every file under ``root``, but what
    an import leaves behind."""
    found = {}
    for folder, below, names in os.walk(root):
        below[:] = [d for d in below if d != "__pycache__"]
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                found[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return found


def _json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _add_toy(root) -> set:
    """The family ``toy`` as a later PR brings one: ``gpt``'s functions and
    limits under a new name, a configuration, a traffic mix, the readings
    its limits stand on, a cell.  Returns the files it wrote."""
    bench = root / "benchmark"
    new = {
        bench / "families" / "toy.py":
            '"""Family ``toy``: ``gpt`` under another name."""\n'
            "from benchmark.families.gpt import (  # noqa: F401\n"
            "    TOL_FIRST_LOSS, TOL_FIRST_MOMENT, TOL_PARAM_DELTA, build,\n"
            "    checks, inputs, model_flops, reference, setup, units)\n",
    }
    config = {**_json(bench / "configs" / "gpt2-medium.json"),
              "name": "toy", "family": "toy"}
    new[bench / "configs" / "toy.json"] = json.dumps(config)
    traffic = {**_json(bench / "traffic" / "fixed-batch-8x1024x1.json"),
               "name": "toy-batch-4x1024x1", "batch_per_chip": 4}
    new[bench / "traffic" / "toy-batch-4x1024x1.json"] = json.dumps(traffic)
    gpt = _json(root / READINGS / "gpt.json")
    one_run = {**gpt["runs"][0], "pr": 99, "cell": "toy-1chip"}
    new[root / READINGS / "toy.json"] = json.dumps(
        {"what": "toy's readings", "limits": gpt["limits"],
         "runs": [one_run]})
    for path, text in new.items():
        assert not path.exists(), path
        path.write_text(text)
    spec = _json(root / "BENCHMARK.json")
    spec["configs"].append({
        "name": "toy", "source": config["source"],
        "file": "benchmark/configs/toy.json", "reduced": config["reduced"],
        "why": "a family added as new files"})
    spec["workloads"].append({
        "name": "toy-1chip", "config": "toy",
        "traffic": "toy-batch-4x1024x1", "chips": 1,
        "why": "a cell of the new family"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return {os.path.relpath(p, root) for p in new} | {"BENCHMARK.json"}


def _ours(name: str) -> bool:
    return name.split(".")[0] in ("benchmark", "bert_faults")


@contextlib.contextmanager
def _imports_from(root):
    """``benchmark`` and ``bert_faults`` are what ``root`` holds while this
    is entered, imported anew; the tree's own come back after it."""
    kept_modules = {n: m for n, m in sys.modules.items() if _ours(n)}
    kept_path = sys.path[:]
    for name in kept_modules:
        del sys.modules[name]
    sys.path[:0] = [str(root), str(root / TESTS)]
    importlib.invalidate_caches()
    try:
        yield
    finally:
        for name in [n for n in sys.modules if _ours(n)]:
            del sys.modules[name]
        sys.modules.update(kept_modules)
        sys.path[:] = kept_path
        importlib.invalidate_caches()


def _test_module(root, name: str):
    """``tests/benchmark/<name>.py`` of the copy, run as a module of its
    own: its ``REPO`` is the copy, found from its ``__file__``."""
    spec = importlib.util.spec_from_file_location(
        f"_copy_{name}", root / TESTS / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.REPO == str(root)
    return module


def _run_every_case(test) -> list:
    """Calls a test function with each case of its one ``parametrize``, as
    pytest would; returns the cases."""
    marks = [m for m in getattr(test, "pytestmark", ())
             if m.name == "parametrize"]
    if not marks:
        test()
        return [()]
    (names, cases), = [m.args for m in marks]
    cases = [c if "," in names else (c,) for c in cases]
    for case in cases:
        test(*case)
    return cases


def _tests_of_the_copy(root) -> dict:
    """Runs the copy's tests of files, names and limits on the copy;
    ``{test: its cases}``."""
    with _imports_from(root):
        ran = {}
        for name, tests in TESTS_OF_THE_COPY.items():
            module = _test_module(root, name)
            for test in tests:
                ran[test] = _run_every_case(getattr(module, test))
        return ran


def test_a_new_family_is_new_files_and_entries_only(tmp_path):
    for rel in ("BENCHMARK.json", "benchmark", TESTS):
        src, dst = os.path.join(REPO, rel), tmp_path / rel
        if os.path.isdir(src):
            shutil.copytree(src, dst, ignore=shutil.ignore_patterns(
                "_trace", "__pycache__"))
        else:
            shutil.copy(src, dst)
    before = _files(tmp_path)
    limits_before = sum(len(_json(tmp_path / rel)["limits"]) for rel in before
                        if os.path.dirname(rel) == READINGS)
    added = _add_toy(tmp_path)

    ran = _tests_of_the_copy(tmp_path)
    # The copy's tests saw the new family: they found its files by name.
    assert ("configs/toy.json",) in ran[A_DATA_FILE]
    assert ("traffic/toy-batch-4x1024x1.json",) in ran[A_DATA_FILE]
    assert ("toy-1chip",) in ran[A_CELL]
    assert {("toy", n) for n in ("TOL_FIRST_LOSS", "TOL_FIRST_MOMENT",
                                 "TOL_PARAM_DELTA")} <= set(ran[A_LIMIT])
    assert len(ran[A_LIMIT]) == limits_before + 3
    # No file that was there has changed but BENCHMARK.json, which gained
    # its two entries; nothing else was added.
    after = _files(tmp_path)
    assert set(after) - set(before) == added - {"BENCHMARK.json"}
    assert {rel for rel in before if after.get(rel) != before[rel]} == {
        "BENCHMARK.json"}
    # The tree's own modules are back in place.
    from benchmark import run
    assert run.ROOT == REPO and "benchmark.families.toy" not in sys.modules

    # Without its readings the family is refused, by the name of the file.
    os.remove(tmp_path / READINGS / "toy.json")
    with pytest.raises(AssertionError) as refused:
        _tests_of_the_copy(tmp_path)
    assert ("benchmark/families/toy.py has no readings on record: add "
            "benchmark/testdata/check_readings/toy.json") in str(refused.value)
