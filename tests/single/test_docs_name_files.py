"""The documents send a reader only to files that exist: every
``python <path>.py`` command and every back-quoted ``*.py`` path that
README.md, docs/*.md and the verify skill name is a tracked file, and so is
every file ``tests/file_seconds.json`` has seconds for."""

import glob
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOCS = (["README.md", ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md"))))
# Scripts the reader is told to write, not to find.
PLACEHOLDERS = {"train.py", "worker.py", "your_script.py"}


def _tracked():
    try:
        out = subprocess.run(["git", "ls-files"], cwd=REPO, check=True,
                             capture_output=True, text=True).stdout.split()
    except (OSError, subprocess.CalledProcessError):
        out = []
    if out:  # minus what the working tree has deleted and not yet staged
        return {p for p in out if os.path.exists(os.path.join(REPO, p))}
    return {os.path.relpath(os.path.join(d, f), REPO)  # an export: no .git
            for d, _, fs in os.walk(REPO) for f in fs}


def test_documents_name_tracked_files():
    tracked = _tracked()
    # `runner/util.py` means horovod_tpu/runner/util.py: a path names a file
    # when it ends one, whole components only.
    ends = {"/".join(p.split("/")[i:]) for p in tracked
            for i in range(p.count("/") + 1)}
    missing = []
    for doc in DOCS:
        with open(os.path.join(REPO, doc)) as f:
            text = f.read()
        for path in re.findall(
                r"python3? +(?:-m +pytest +)?([\w./-]+\.py)\b", text):
            if path not in tracked and path not in PLACEHOLDERS:
                missing.append(f"{doc}: python {path}")
        for path in re.findall(r"`([\w./-]+\.py)`", text):
            if path not in ends and path not in PLACEHOLDERS:
                missing.append(f"{doc}: `{path}`")
    assert not missing, missing


def _conftest():
    sys.path.insert(0, os.path.join(REPO, "tests"))
    try:
        import conftest
    finally:
        sys.path.pop(0)
    return conftest


def test_files_the_gate_has_seconds_for_exist():
    tracked = _tracked()
    assert [p for p in _conftest().FILE_SECONDS if p not in tracked] == []


def test_a_file_without_seconds_starts_first_then_the_longest():
    conftest = _conftest()
    longest = max(conftest.FILE_SECONDS, key=conftest.FILE_SECONDS.get)
    shortest = min(conftest.FILE_SECONDS, key=conftest.FILE_SECONDS.get)
    new = "tests/single/test_a_new_family.py"
    assert new not in conftest.FILE_SECONDS
    cases = [f"{shortest}::test_b", f"{longest}::test_a[x::y]",
             f"{new}::test_c", f"{shortest}::test_a"]
    assert sorted(cases, key=conftest.start_order) == [
        f"{new}::test_c", f"{longest}::test_a[x::y]",
        f"{shortest}::test_b", f"{shortest}::test_a"]
