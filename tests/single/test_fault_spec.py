"""HOROVOD_FAULT_INJECT: the deterministic fault-injection spec contract.

The native parser (cpp/fault_injection.cc ParseFaultSpec) is the single
source of truth; Python reaches it through `_core.check_fault_spec`, the
same entry `horovodrun --fault-inject` pre-validates with.  Covered here:
well-formed specs accepted, every malformed shape rejected with an
actionable message naming the valid vocabulary, and the init-time
contract — a malformed spec in the environment fails hvd.init() fast
with the parse error, while a well-formed but off-path spec is inert.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu import _core

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def native_lib():
    try:
        lib = _core._load_library()
    except Exception as exc:  # build-environment dependent
        pytest.skip(f"native core unavailable: {exc}")
    if not hasattr(lib, "hvd_fault_spec_check"):
        pytest.skip("stale native library predates hvd_fault_spec_check")
    return lib


VALID = [
    "",  # unset/empty: injection disabled
    "ring-send:*:*:drop",
    "ring-recv:0:2:truncate",
    "shm-fence:*:1:drop",
    "frame-header:3:0:corrupt-tag",
    "coordinator-recv:0:1:drop",
    "rendezvous-accept:0:1:drop",
    "ring-send:*:1:delay:250",
    "ring-send:7:1:die",
    "ring-send:7:1:die:/tmp/latch.flag",
    # die's flag-file arg may itself contain colons (fields rejoined)
    "ring-send:7:1:die:/tmp/with:colon.flag",
    # several rules; trailing/empty entries tolerated
    "ring-send:*:1:delay:250,frame-header:3:0:corrupt-tag,,",
]


@pytest.mark.parametrize("spec", VALID)
def test_valid_specs_accepted(native_lib, spec):
    assert _core.check_fault_spec(spec) == ""


MALFORMED = [
    ("nosite:*:*:drop",
     ["unknown site", "valid sites", "ring-send", "shm-fence"]),
    ("ring-send:*:*",
     ["expected site:cycle:rank:action"]),
    ("ring-send:x:*:drop",
     ["cycle 'x'", "non-negative"]),
    ("ring-send:*:x:drop",
     ["rank 'x'", "non-negative"]),
    ("ring-send:*:*:explode",
     ["unknown action 'explode'", "valid actions", "corrupt-tag"]),
    ("ring-send:*:*:delay",
     ["delay requires a numeric millisecond arg"]),
    ("ring-send:*:*:drop:arg",
     ["takes no arg"]),
]


@pytest.mark.parametrize("spec,needles", MALFORMED,
                         ids=[m[0] for m in MALFORMED])
def test_malformed_specs_rejected_with_actionable_message(
        native_lib, spec, needles):
    msg = _core.check_fault_spec(spec)
    assert msg, spec
    assert spec in msg  # names the offending entry verbatim
    for needle in needles:
        assert needle in msg, (needle, msg)


def test_one_bad_rule_taints_the_whole_spec(native_lib):
    msg = _core.check_fault_spec(
        "ring-send:*:1:delay:250,nosite:*:*:drop")
    assert "unknown site" in msg, msg


INIT_PROBE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import horovod_tpu as hvd
    try:
        hvd.init(build_mesh=False)
    except Exception as exc:
        print("INIT-REFUSED:", exc, flush=True)
    else:
        out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                            name="probe")
        np.testing.assert_allclose(out, 1.0)
        hvd.shutdown()
        print("INIT-ACCEPTED", flush=True)
""")


def _probe_init(spec: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["HOROVOD_FAULT_INJECT"] = spec
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, "-c", INIT_PROBE], env=env,
                          capture_output=True, text=True, timeout=180)


def test_malformed_spec_fails_init_with_parse_error(native_lib):
    # The abort-path contract starts at init: a bad spec must fail fast
    # with the parser's message, not arm a half-parsed rule set.
    proc = _probe_init("ring-send:*:*:explode")
    assert "INIT-REFUSED:" in proc.stdout, proc.stdout + proc.stderr
    assert "unknown action 'explode'" in proc.stdout, proc.stdout
    assert "valid actions" in proc.stdout, proc.stdout


def test_armed_but_off_path_spec_is_inert(native_lib):
    # The np=1 local controller never touches the ring sites: an armed,
    # well-formed spec must not disturb init or results.
    proc = _probe_init("ring-send:*:*:drop")
    assert "INIT-ACCEPTED" in proc.stdout, proc.stdout + proc.stderr
