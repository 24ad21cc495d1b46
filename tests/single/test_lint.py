"""hvd_lint: the cross-layer ABI/env/protocol checker.

Two layers of coverage:
- the real repo must lint clean against the committed (empty) baseline —
  pure text analysis, no native build, so this is tier-1;
- each pass is unit-tested on small fixture snippets, including seeded
  mismatches (dropped argtype, bumped kProtocolVersion, undocumented env
  var) that MUST produce findings — proving the passes can actually fail.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))

import hvd_lint  # noqa: E402


# ---------------------------------------------------------------------------
# The repo itself
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    findings = hvd_lint.run_repo(REPO)
    assert findings == [], "\n".join(
        f"{f.key}: {f.message}" for f in findings)


def test_baseline_is_empty():
    """Policy: drift gets fixed, not baselined."""
    with open(os.path.join(REPO, "tools", "hvd_lint_baseline.json")) as f:
        assert json.load(f)["findings"] == []


def test_cli_exits_zero_on_repo():
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hvd_lint.py")],
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "0 new vs baseline" in run.stdout


# ---------------------------------------------------------------------------
# ABI pass fixtures
# ---------------------------------------------------------------------------

CPP_OK = """
extern "C" {

static void helper(int x) {}

int hvd_frob(int rank, const char* name, long long nbytes) {
  return 0;
}

long long hvd_ticket(void) {
  return 0;
}

const char* hvd_oops(void) {
  return "";
}

void hvd_poke(void) {
}

}  // extern "C"
"""

PY_OK = """
def _declare(lib):
    import ctypes as c
    lib.hvd_frob.restype = c.c_int
    lib.hvd_frob.argtypes = [c.c_int, c.c_char_p, c.c_longlong]
    lib.hvd_ticket.restype = c.c_longlong
    lib.hvd_oops.restype = c.c_char_p
    lib.hvd_poke.restype = None
"""


def _abi(cpp, py):
    return hvd_lint.abi_pass(cpp, {"horovod_tpu/_core.py": py})


def test_abi_clean_fixture():
    assert _abi(CPP_OK, PY_OK) == []


def test_abi_parser_extracts_exports_not_statics():
    exports = hvd_lint.parse_extern_c(CPP_OK)
    assert set(exports) == {"hvd_frob", "hvd_ticket", "hvd_oops", "hvd_poke"}
    assert exports["hvd_frob"] == ("int", ["int", "char*", "long long"])
    assert exports["hvd_ticket"] == ("long long", [])


def test_abi_dropped_argtype_is_found():
    py = PY_OK.replace(", c.c_longlong]", "]")  # drop hvd_frob's 3rd arg
    keys = {f.key for f in _abi(CPP_OK, py)}
    assert "ABI-ARITY:hvd_frob" in keys


def test_abi_wrong_type_is_found():
    py = PY_OK.replace("c.c_char_p, c.c_longlong", "c.c_int, c.c_longlong")
    keys = {f.key for f in _abi(CPP_OK, py)}
    assert "ABI-TYPE:hvd_frob:1" in keys


def test_abi_missing_longlong_restype_is_found():
    # ctypes' default c_int restype silently truncates a long long return.
    py = PY_OK.replace("lib.hvd_ticket.restype = c.c_longlong\n", "")
    keys = {f.key for f in _abi(CPP_OK, py)}
    assert any(k.startswith("ABI-") and "hvd_ticket" in k for k in keys)


def test_abi_callsite_without_argtypes_is_found():
    py = PY_OK + "\n    rc = lib.hvd_poke()\n    lib.hvd_gone(1)\n"
    cpp = CPP_OK.replace("void hvd_poke(void) {",
                         "void hvd_poke(int style) {")
    keys = {f.key for f in _abi(cpp, py)}
    assert "ABI-CALLSITE:hvd_poke" in keys   # called, args, no argtypes
    assert "ABI-UNKNOWN-CALL:hvd_gone" in keys  # called, never exported


# ---------------------------------------------------------------------------
# env pass fixtures
# ---------------------------------------------------------------------------

ENV_PY = """
IGNORED_VARS = (
    "HOROVOD_GPU_OPERATIONS",
)

def from_env():
    return get_int("HOROVOD_FUSION_THRESHOLD", 64)
"""

DOC_OK = """
| Variable | Meaning |
|---|---|
| `HOROVOD_FUSION_THRESHOLD` | fusion bytes |
| `HOROVOD_NATIVE_KNOB` | native thing |
"""


def _env(py_extra="", cc="", doc=DOC_OK):
    py_files = {"horovod_tpu/utils/env.py": ENV_PY,
                "horovod_tpu/other.py": py_extra}
    cc_files = {"horovod_tpu/cpp/x.cc": cc}
    return hvd_lint.env_pass(
        py_files, cc_files, {"docs/api.md": doc},
        native_read_vars={"HOROVOD_NATIVE_KNOB"} if cc else set(),
        py_direct_vars=set(), internal_vars=set())


def test_env_clean_fixture():
    assert _env(cc='getenv("HOROVOD_NATIVE_KNOB")') == []


def test_env_unmanaged_read_is_found():
    findings = _env(py_extra='x = os.environ.get("HOROVOD_MYSTERY")',
                    cc='getenv("HOROVOD_NATIVE_KNOB")')
    assert {f.key for f in findings} == {"ENV-UNMANAGED:HOROVOD_MYSTERY"}


def test_env_undocumented_native_var_is_found():
    doc = DOC_OK.replace("| `HOROVOD_NATIVE_KNOB` | native thing |\n", "")
    keys = {f.key for f in _env(cc='getenv("HOROVOD_NATIVE_KNOB")', doc=doc)}
    assert "ENV-UNDOCUMENTED:HOROVOD_NATIVE_KNOB" in keys


def test_env_unwhitelisted_cpp_getenv_is_found():
    findings = hvd_lint.env_pass(
        {"horovod_tpu/utils/env.py": ENV_PY},
        {"horovod_tpu/cpp/x.cc": 'getenv("HOROVOD_SNEAKY")'},
        {"docs/api.md": DOC_OK.replace("HOROVOD_NATIVE_KNOB",
                                       "HOROVOD_FUSION_THRESHOLD")},
        native_read_vars=set(), py_direct_vars=set(), internal_vars=set())
    assert "ENV-NATIVE-UNLISTED:HOROVOD_SNEAKY" in {f.key for f in findings}


def test_env_stale_doc_is_found():
    doc = DOC_OK + "\n| `HOROVOD_IMAGINARY` | does not exist |\n"
    keys = {f.key for f in _env(cc='getenv("HOROVOD_NATIVE_KNOB")', doc=doc)}
    assert "ENV-STALE-DOC:HOROVOD_IMAGINARY" in keys


def test_env_line_wrapped_var_prefix_not_flagged():
    # "HOROVOD_FUSION_\nTHRESHOLD" wrapped mid-name must not register a
    # phantom HOROVOD_FUSION doc mention.
    doc = DOC_OK + "\nprose mentioning `HOROVOD_FUSION_\nTHRESHOLD` split\n"
    keys = {f.key for f in _env(cc='getenv("HOROVOD_NATIVE_KNOB")', doc=doc)}
    assert not any("HOROVOD_FUSION:" in k or k.endswith("HOROVOD_FUSION")
                   for k in keys)


def test_env_data_plane_knob_coverage():
    """HOROVOD_DATA_PLANE (PR 17) is a managed public knob: parsed in
    utils/env.py and documented in a table row is clean; dropping the doc
    row flags ENV-UNDOCUMENTED, and a read outside env.py (without the
    central parse) flags ENV-UNMANAGED."""
    parse = ('\n\ndef get_data_plane():\n'
             '    return os.environ.get("HOROVOD_DATA_PLANE", "auto")\n')
    doc = DOC_OK + "| `HOROVOD_DATA_PLANE` | gradient-exchange plane |\n"

    def run(env_py, py_extra="", doc_text=doc):
        return hvd_lint.env_pass(
            {"horovod_tpu/utils/env.py": env_py,
             "horovod_tpu/other.py": py_extra},
            {"horovod_tpu/cpp/x.cc": 'getenv("HOROVOD_NATIVE_KNOB")'},
            {"docs/api.md": doc_text},
            native_read_vars={"HOROVOD_NATIVE_KNOB"}, py_direct_vars=set(),
            internal_vars=set())

    assert run(ENV_PY + parse) == []
    keys = {f.key for f in run(ENV_PY + parse, doc_text=DOC_OK)}
    assert "ENV-UNDOCUMENTED:HOROVOD_DATA_PLANE" in keys
    keys = {f.key for f in run(
        ENV_PY, py_extra='p = os.environ.get("HOROVOD_DATA_PLANE")',
        doc_text=DOC_OK)}
    assert "ENV-UNMANAGED:HOROVOD_DATA_PLANE" in keys


# ---------------------------------------------------------------------------
# protocol pass fixtures
# ---------------------------------------------------------------------------

SC_OK = """
constexpr uint32_t kProtocolMagic = 0x48565354;
constexpr int kProtocolVersion = 7;
constexpr int32_t kTagBarrier = 0x7000;
constexpr int32_t kTagShmSize = 0x8000;
constexpr int32_t kTagShmWrite = 0x9000;
"""

WIRE_OK = """
enum class WireCodec : int32_t { kNone = 0, kBf16 = 1, kInt8 = 2 };
"""

CORE_OK = 'codec = {"none": 0, "bf16": 1, "int8": 2}.get(name, 0)'
RUNTIME_OK = "PROTOCOL_VERSION = 7\n"
ENV_CODECS_OK = 'WIRE_COMPRESSION_CODECS = ("none", "bf16", "int8")\n'
DOC_PROTO_OK = {"docs/architecture.md": "currently `kProtocolVersion = 7`"}


def _proto(sc=SC_OK, wire=WIRE_OK, core=CORE_OK, runtime=RUNTIME_OK,
           env=ENV_CODECS_OK, docs=None):
    return hvd_lint.protocol_pass(
        sc, wire, core, runtime, env,
        DOC_PROTO_OK if docs is None else docs)


def test_protocol_clean_fixture():
    assert _proto() == []


def test_protocol_bumped_version_is_found():
    # C++ bumped to v8, Python mirror and docs left at 7: both must flag.
    keys = {f.key for f in _proto(sc=SC_OK.replace(
        "kProtocolVersion = 7", "kProtocolVersion = 8"))}
    assert "PROTO-VERSION-MIRROR" in keys
    assert "PROTO-VERSION-DOC:docs/architecture.md" in keys


def test_protocol_missing_mirror_is_found():
    keys = {f.key for f in _proto(runtime="")}
    assert "PROTO-NO-MIRROR" in keys


def test_protocol_duplicate_tag_is_found():
    sc = SC_OK + "constexpr int32_t kTagRogue = 0x9000;\n"
    keys = {f.key for f in _proto(sc=sc)}
    assert "PROTO-TAG-DUP:0x9000" in keys


def test_protocol_abort_tag_collision_is_found():
    # A kTagAbort seeded onto an existing tag value (the v8 fast-abort
    # frame must own its own tag) is caught as a duplicate.
    sc = SC_OK + "constexpr int32_t kTagAbort = 0x9000;\n"
    keys = {f.key for f in _proto(sc=sc)}
    assert "PROTO-TAG-DUP:0x9000" in keys


def test_protocol_fence_tag_below_threshold_is_found():
    sc = SC_OK.replace("kTagShmWrite = 0x9000", "kTagShmWrite = 0x7800")
    keys = {f.key for f in _proto(sc=sc)}
    assert "PROTO-TAG-RANGE:kTagShmWrite" in keys


def test_protocol_codec_mismatch_is_found():
    keys = {f.key for f in _proto(core=CORE_OK.replace('"int8": 2',
                                                       '"int8": 3'))}
    assert "PROTO-CODEC-MIRROR" in keys


# ---------------------------------------------------------------------------
# flight pass fixtures
# ---------------------------------------------------------------------------

FR_H_OK = """
enum FlightType : uint16_t {
  kFlightCtrlSend = 1,
  kFlightRingHop = 2,
  kFlightTreeAgg = 3,
};
"""

FR_CC_OK = r"""
static const char kFlightTypesLegend[] =
    "{\"1\":\"ctrl_send\",\"2\":\"ring_hop\","
    "\"3\":\"tree_aggregate\"}";
"""

PM_OK = """
FLIGHT_TYPES = {
    1: "ctrl_send", 2: "ring_hop", 3: "tree_aggregate",
}
"""

DOC_FLIGHT_OK = """
<!-- hvd_lint:flight-types -->
| id | name | a | b |
|---|---|---|---|
| 1 | `ctrl_send` | 0 | bytes |
| 2 | `ring_hop` | hop | bytes |
| 3 | `tree_aggregate` | fan-in | bytes |

prose after the table
"""


def _flight(h=FR_H_OK, cc=FR_CC_OK, pm=PM_OK, doc=DOC_FLIGHT_OK):
    return hvd_lint.flight_pass(h, cc, pm,
                                {"docs/observability.md": doc})


def test_flight_clean_fixture():
    assert _flight() == []


def test_flight_parsers():
    assert hvd_lint.parse_flight_enum(FR_H_OK) == {
        1: "CtrlSend", 2: "RingHop", 3: "TreeAgg"}
    assert hvd_lint.parse_flight_legend(FR_CC_OK) == {
        1: "ctrl_send", 2: "ring_hop", 3: "tree_aggregate"}
    assert hvd_lint.parse_flight_py(PM_OK) == {
        1: "ctrl_send", 2: "ring_hop", 3: "tree_aggregate"}
    assert hvd_lint.parse_flight_doc(DOC_FLIGHT_OK) == {
        1: "ctrl_send", 2: "ring_hop", 3: "tree_aggregate"}
    assert hvd_lint.parse_flight_doc("no marker here") is None


def test_flight_clean_fixture_tolerates_abbreviated_enum_name():
    # kFlightTreeAgg vs tree_aggregate passes the loose prefix check; a
    # genuinely different name does not.
    cc = FR_CC_OK.replace("tree_aggregate", "barrier_wait")
    pm = PM_OK.replace("tree_aggregate", "barrier_wait")
    doc = DOC_FLIGHT_OK.replace("tree_aggregate", "barrier_wait")
    keys = {f.key for f in _flight(cc=cc, pm=pm, doc=doc)}
    assert "FLIGHT-NAME:3" in keys


def test_flight_new_enum_value_without_legend_row_is_found():
    h = FR_H_OK.replace("};", "  kFlightShmFence = 4,\n};")
    keys = {f.key for f in _flight(h=h)}
    assert "FLIGHT-ENUM-LEGEND" in keys


def test_flight_stale_py_mirror_is_found():
    pm = PM_OK.replace('2: "ring_hop", ', "")
    keys = {f.key for f in _flight(pm=pm)}
    assert "FLIGHT-PY-MIRROR" in keys


def test_flight_doc_drift_is_found():
    # Missing row, renamed row, and a row for a type the legend lacks.
    doc = DOC_FLIGHT_OK.replace("| 2 | `ring_hop` | hop | bytes |\n", "")
    assert {f.key for f in _flight(doc=doc)} == {"FLIGHT-DOC-MISSING:2"}
    doc = DOC_FLIGHT_OK.replace("`ring_hop`", "`ring_step`")
    assert {f.key for f in _flight(doc=doc)} == {"FLIGHT-DOC-RENAMED:2"}
    doc = DOC_FLIGHT_OK.replace(
        "\nprose after", "| 9 | `ghost` | 0 | 0 |\n\nprose after")
    assert {f.key for f in _flight(doc=doc)} == {"FLIGHT-DOC-STALE:9"}
    keys = {f.key for f in _flight(doc="tableless doc")}
    assert keys == {"FLIGHT-DOC-NO-TABLE"}


def test_flight_unparseable_sources_are_findings_not_crashes():
    keys = {f.key for f in _flight(h="", cc="", pm="")}
    assert keys == {"FLIGHT-NO-ENUM", "FLIGHT-NO-LEGEND", "FLIGHT-NO-PY"}


# ---------------------------------------------------------------------------
# end-to-end: a seeded mismatch makes the CLI exit non-zero
# ---------------------------------------------------------------------------

def test_cli_exits_nonzero_on_seeded_mismatch(tmp_path):
    """Copy the repo's lintable surface, bump kProtocolVersion in the C++
    only, and assert the CLI catches the drift with a non-zero exit."""
    import shutil

    for sub in ("horovod_tpu", "docs", "tools"):
        shutil.copytree(
            os.path.join(REPO, sub), tmp_path / sub,
            ignore=shutil.ignore_patterns(
                "__pycache__", "*.so", "*.o", "*selftest*"))
    shutil.copy(os.path.join(REPO, "README.md"), tmp_path / "README.md")
    sc = tmp_path / "horovod_tpu" / "cpp" / "socket_controller.cc"
    text = sc.read_text()
    m = re.search(r"kProtocolVersion = (\d+)", text)
    assert m, "kProtocolVersion definition not found"
    cur = int(m.group(1))
    sc.write_text(text.replace(f"kProtocolVersion = {cur}",
                               f"kProtocolVersion = {cur + 1}"))
    run = subprocess.run(
        [sys.executable, str(tmp_path / "tools" / "hvd_lint.py"),
         "--repo", str(tmp_path),
         "--baseline", str(tmp_path / "tools" / "hvd_lint_baseline.json")],
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 1, run.stdout + run.stderr
    assert "PROTO-VERSION-MIRROR" in run.stdout


# ---------------------------------------------------------------------------
# protocol pass: quantize.py device-plane mirror (block geometry, codec-id
# map, device codec names) against the four-codec wire_codec.h
# ---------------------------------------------------------------------------

WIRE4_OK = """
enum class WireCodec : int32_t {
  kNone = 0, kBf16 = 1, kInt8 = 2, kInt4 = 3,
};
constexpr int64_t kWireBlock = 256;
constexpr int64_t kWireScaleBytes = 4;
constexpr int64_t kWireInt4Max = 7;
"""

CORE4_OK = ('codec = {"none": 0, "bf16": 1, "int8": 2, '
            '"int4": 3}.get(name, 0)')
ENV4_OK = ('WIRE_COMPRESSION_CODECS = ("none", "bf16", "int8", "int4")\n'
           'DEVICE_WIRE_COMPRESSION_CODECS = ("none", "int8", "int4")\n')
QUANTIZE_OK = """
WIRE_BLOCK = 256
WIRE_SCALE_BYTES = 4
WIRE_INT4_MAX = 7
WIRE_CODEC_IDS = {"none": 0, "bf16": 1, "int8": 2, "int4": 3}
DEVICE_WIRE_CODECS = ("none", "int8", "int4")
"""


def _proto_q(wire=WIRE4_OK, core=CORE4_OK, env=ENV4_OK, quantize=QUANTIZE_OK):
    return hvd_lint.protocol_pass(SC_OK, wire, core, RUNTIME_OK, env,
                                  DOC_PROTO_OK, quantize_py_text=quantize)


def test_protocol_quantize_mirror_clean_fixture():
    assert _proto_q() == []


def test_protocol_qblock_drift_is_found():
    # A drift in any of the three desyncs the traced codec from the C++
    # stream: the block a scale covers, the bytes of a scale, int4's clamp.
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        "WIRE_BLOCK = 256", "WIRE_BLOCK = 128"))}
    assert "PROTO-QBLOCK:WIRE_BLOCK" in keys
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        "WIRE_SCALE_BYTES = 4", "WIRE_SCALE_BYTES = 2"))}
    assert "PROTO-QBLOCK:WIRE_SCALE_BYTES" in keys
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        "WIRE_INT4_MAX = 7", "WIRE_INT4_MAX = 8"))}
    assert "PROTO-QBLOCK:WIRE_INT4_MAX" in keys


def test_protocol_qblock_missing_constant_is_found():
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        "WIRE_INT4_MAX = 7\n", ""))}
    assert "PROTO-QBLOCK-MISSING:WIRE_INT4_MAX" in keys


def test_protocol_qcodec_id_drift_is_found():
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        '"int4": 3', '"int4": 4'))}
    assert "PROTO-QCODEC-MIRROR" in keys


def test_protocol_device_codec_names_drift_is_found():
    keys = {f.key for f in _proto_q(env=ENV4_OK.replace(
        'DEVICE_WIRE_COMPRESSION_CODECS = ("none", "int8", "int4")',
        'DEVICE_WIRE_COMPRESSION_CODECS = ("none", "int8")'))}
    assert "PROTO-DEVICE-CODEC-NAMES" in keys


def test_protocol_device_codec_without_enum_id_is_found():
    keys = {f.key for f in _proto_q(quantize=QUANTIZE_OK.replace(
        'DEVICE_WIRE_CODECS = ("none", "int8", "int4")',
        'DEVICE_WIRE_CODECS = ("none", "int8", "int4", "fp8")'))}
    assert "PROTO-DEVICE-CODEC-UNKNOWN:fp8" in keys


# ---------------------------------------------------------------------------
# atomic pass fixtures: explicit memory_order on always-on hot paths
# ---------------------------------------------------------------------------

ATOMIC_CC_OK = """
#include <atomic>

namespace hvdtpu {

std::atomic<long> g_count{0};

void Bump() {
  g_count.fetch_add(1, std::memory_order_relaxed);
}

void MultiLineExplicit() {
  g_count.store(
      0,
      std::memory_order_release);
}

}  // namespace hvdtpu
"""

# The exact pre-fix shape of the two real violations this PR fixed
# (flight_recorder.cc dumping latch): a CAS and a store with no order.
ATOMIC_CC_PREFIX_BUG = """
void FlightDumpToFile() {
  bool expected = false;
  if (!s.dumping.compare_exchange_strong(expected, true)) {
    return;
  }
  s.dumping.store(false);
}
"""


def _atomic(cc, base="flight_recorder.cc"):
    return hvd_lint.atomic_pass({f"horovod_tpu/cpp/{base}": cc})


def test_atomic_clean_fixture():
    assert _atomic(ATOMIC_CC_OK) == []


def test_atomic_implicit_order_is_found_with_file_and_symbol():
    findings = _atomic(ATOMIC_CC_PREFIX_BUG)
    keys = {f.key for f in findings}
    assert keys == {"ATOMIC-IMPLICIT:flight_recorder.cc:4",
                    "ATOMIC-IMPLICIT:flight_recorder.cc:7"}
    by_key = {f.key: f.message for f in findings}
    assert "FlightDumpToFile" in by_key[
        "ATOMIC-IMPLICIT:flight_recorder.cc:4"]
    assert "compare_exchange_strong" in by_key[
        "ATOMIC-IMPLICIT:flight_recorder.cc:4"]
    assert "store" in by_key["ATOMIC-IMPLICIT:flight_recorder.cc:7"]


def test_atomic_non_hot_file_is_ignored():
    assert _atomic(ATOMIC_CC_PREFIX_BUG, base="socket_controller.cc") == []


def test_atomic_escape_hatch_suppresses_and_goes_stale():
    excused = ATOMIC_CC_PREFIX_BUG.replace(
        "  s.dumping.store(false);",
        "  // lint: seq_cst-ok(fixture wants the full fence)\n"
        "  s.dumping.store(false);")
    keys = {f.key for f in _atomic(excused)}
    assert keys == {"ATOMIC-IMPLICIT:flight_recorder.cc:4"}

    stale = ATOMIC_CC_OK.replace(
        "void Bump() {",
        "// lint: seq_cst-ok(nothing here needs it)\nvoid Bump() {")
    keys = {f.key for f in _atomic(stale)}
    assert len(keys) == 1 and next(iter(keys)).startswith(
        "ATOMIC-STALE-OK:flight_recorder.cc:")


def test_atomic_order_in_string_or_comment_does_not_excuse():
    cc = """
void F() {
  // memory_order_relaxed (comment must not satisfy the check)
  g.store(1);
}
"""
    keys = {f.key for f in _atomic(cc)}
    assert keys == {"ATOMIC-IMPLICIT:flight_recorder.cc:4"}


# ---------------------------------------------------------------------------
# lockorder pass fixtures: acquisition-graph cycles
# ---------------------------------------------------------------------------

LOCK_CC_CYCLE = """
#include <mutex>

std::mutex a_mu;
std::mutex b_mu;

void TakeAB() {
  std::lock_guard<std::mutex> la(a_mu);
  std::lock_guard<std::mutex> lb(b_mu);
}

void TakeBA() {
  std::lock_guard<std::mutex> lb(b_mu);
  std::lock_guard<std::mutex> la(a_mu);
}
"""

LOCK_CC_SEQUENTIAL = """
#include <mutex>

std::mutex a_mu;
std::mutex b_mu;

void Sequential() {
  {
    std::lock_guard<std::mutex> la(a_mu);
  }
  std::lock_guard<std::mutex> lb(b_mu);
}

void Sequential2() {
  {
    std::lock_guard<std::mutex> lb(b_mu);
  }
  std::lock_guard<std::mutex> la(a_mu);
}
"""

LOCK_CC_VIA_CALL = """
#include <mutex>

std::mutex a_mu;
std::mutex b_mu;

void Inner() {
  std::lock_guard<std::mutex> la(a_mu);
}

void Outer() {
  std::lock_guard<std::mutex> lb(b_mu);
  Inner();
}

void Direct() {
  std::lock_guard<std::mutex> la(a_mu);
  std::lock_guard<std::mutex> lb(b_mu);
}
"""

LOCK_CC_SELF = """
#include <mutex>

std::mutex m_mu;

void Recur() {
  std::lock_guard<std::mutex> l1(m_mu);
  {
    std::lock_guard<std::mutex> l2(m_mu);
  }
}
"""


def _lock(cc, base="socket_controller.cc"):
    return hvd_lint.lockorder_pass({f"horovod_tpu/cpp/{base}": cc})


def test_lockorder_two_function_cycle_has_both_witnesses():
    findings = _lock(LOCK_CC_CYCLE)
    keys = {f.key for f in findings}
    assert keys == {"LOCKORDER-CYCLE:socket_controller.cc:a_mu->b_mu->a_mu"}
    msg = findings[0].message
    assert "TakeAB holds a_mu, acquires b_mu" in msg
    assert "TakeBA holds b_mu, acquires a_mu" in msg


def test_lockorder_scope_release_breaks_the_edge():
    # Same two orders, but the first guard's scope closes before the
    # second acquisition: no held-while-acquiring edge, no cycle.
    assert _lock(LOCK_CC_SEQUENTIAL) == []


def test_lockorder_cycle_through_callee_closure_is_found():
    findings = _lock(LOCK_CC_VIA_CALL)
    keys = {f.key for f in findings}
    assert keys == {"LOCKORDER-CYCLE:socket_controller.cc:a_mu->b_mu->a_mu"}
    msg = findings[0].message
    assert "calls Inner which may acquire a_mu" in msg


def test_lockorder_self_deadlock_is_found():
    keys = {f.key for f in _lock(LOCK_CC_SELF)}
    assert keys == {"LOCKORDER-SELF:socket_controller.cc:m_mu"}


def test_lockorder_non_target_file_is_ignored():
    assert _lock(LOCK_CC_CYCLE, base="metrics.cc") == []


# ---------------------------------------------------------------------------
# sigsafe pass fixtures: async-signal-safety of the handler call graph
# ---------------------------------------------------------------------------

SIG_CC_OK = """
#include <csignal>

void WriteAll(const char* p, long n) {
  write(2, p, n);
}

void OnFatalSignal(int signo) {
  WriteAll("boom", 4);
  _exit(1);
}

void InstallHandlers() {
  struct sigaction sa;
  sa.sa_handler = OnFatalSignal;
  sigaction(SIGSEGV, &sa, nullptr);
}
"""


def test_sigsafe_clean_fixture():
    assert hvd_lint.sigsafe_pass(SIG_CC_OK) == []


def test_sigsafe_snprintf_in_signal_path_is_found_through_helper():
    cc = SIG_CC_OK.replace(
        "  write(2, p, n);",
        "  char buf[64];\n"
        "  snprintf(buf, 64, \"%s\", p);\n"
        "  write(2, buf, n);")
    findings = hvd_lint.sigsafe_pass(cc)
    keys = {f.key for f in findings}
    assert keys == {"SIGSAFE-UNSAFE-CALL:WriteAll:snprintf"}
    assert "OnFatalSignal" in findings[0].message  # names the entry point


def test_sigsafe_new_and_lock_in_signal_path_are_found():
    cc = SIG_CC_OK.replace(
        "  _exit(1);",
        "  char* p = new char[64];\n"
        "  std::lock_guard<std::mutex> l(g_mu);\n"
        "  _exit(1);")
    keys = {f.key for f in hvd_lint.sigsafe_pass(cc)}
    assert any(k.startswith("SIGSAFE-NEW:OnFatalSignal:") for k in keys)
    assert any(k.startswith("SIGSAFE-LOCK:OnFatalSignal:") for k in keys)


def test_sigsafe_unreachable_unsafe_code_is_not_flagged():
    # malloc in a function never called from the handler: out of scope.
    cc = SIG_CC_OK + """
void BackgroundOnly() {
  char* p = static_cast<char*>(malloc(64));
  free(p);
}
"""
    assert hvd_lint.sigsafe_pass(cc) == []


def test_sigsafe_no_entry_point_is_itself_a_finding():
    keys = {f.key for f in hvd_lint.sigsafe_pass("void F() {}\n")}
    assert keys == {"SIGSAFE-NO-ENTRY:flight_recorder.cc"}


def test_sigsafe_escape_hatch_suppresses_and_goes_stale():
    excused = SIG_CC_OK.replace(
        "  _exit(1);",
        "  // lint: sigsafe-ok(fixture: provably init-time only)\n"
        "  Dumper* d = new Dumper();\n"
        "  _exit(1);")
    assert hvd_lint.sigsafe_pass(excused) == []

    stale = SIG_CC_OK.replace(
        "  _exit(1);",
        "  // lint: sigsafe-ok(excuses nothing)\n"
        "  _exit(1);")
    keys = {f.key for f in hvd_lint.sigsafe_pass(stale)}
    assert len(keys) == 1 and next(iter(keys)).startswith(
        "SIGSAFE-STALE-OK:flight_recorder.cc:")


# ---------------------------------------------------------------------------
# repo-clean per-pass + --only CLI selection
# ---------------------------------------------------------------------------

def test_repo_concurrency_passes_clean():
    for pass_name in ("atomic", "lockorder", "sigsafe"):
        findings = hvd_lint.run_repo(REPO, only=[pass_name])
        assert findings == [], "\n".join(
            f"{f.key}: {f.message}" for f in findings)


def test_cli_only_selection_and_timings():
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hvd_lint.py"),
         "--only", "atomic,sigsafe"],
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "[atomic]" in run.stdout and "[sigsafe]" in run.stdout
    assert "[abi]" not in run.stdout and "[lockorder]" not in run.stdout
    assert " ms)" in run.stdout  # per-pass wall time


def test_cli_only_rejects_unknown_pass():
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "hvd_lint.py"),
         "--only", "atomic,bogus"],
        capture_output=True, text=True, timeout=120)
    assert run.returncode == 2
    assert "bogus" in run.stderr
