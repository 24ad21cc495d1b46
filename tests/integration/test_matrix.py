"""The controller/config matrix runner stays green (VERDICT r2 #9).

CI runs the covering subset (--quick: both cores, np 1/2/3, fusion and
cache on/off, both data planes all appear at least once); the full
product is `python tools/test_matrix.py`.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_matrix_quick():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "test_matrix.py"),
         "--quick"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ALL PASS" in proc.stdout
    assert proc.stdout.count("PASS") >= 4
