#!/usr/bin/env python
"""hvd_lint: cross-layer ABI / env / protocol consistency checker.

The framework's correctness hinges on four hand-mirrored seams, each of
which drifts silently (a mismatch corrupts data or loses a knob, it does
not crash):

  ABI       the ``extern "C"`` surface in cpp/core_api.cc  vs  the ctypes
            argtypes/restype declarations in _core.py
  env       the HOROVOD_* variables read anywhere (C++ getenv, Python
            os.environ)  vs  the central parser utils/env.py and the doc
            tables
  protocol  kProtocolVersion / frame tags / wire-codec ids in C++  vs  the
            Python mirrors (runtime.PROTOCOL_VERSION, _core.py codec map,
            env.py codec names) and the docs
  flight    the flight-recorder event-type table, kept in four places:
            flight_recorder.h's FlightType enum, flight_recorder.cc's
            kFlightTypesLegend JSON, tools/postmortem.py's FLIGHT_TYPES
            fallback, and the marked table in docs/observability.md

Three further passes turn the C++ spine's concurrency discipline — the
invariants TSan can only sample dynamically — into static, fail-on-drift
checks:

  atomic    every std::atomic load/store/RMW in the always-on hot-path
            files (ATOMIC_HOT_FILES) must name an explicit memory_order;
            implicit seq_cst is a finding, escapable per site with
            `// lint: seq_cst-ok(<reason>)` (stale hatches are findings)
  lockorder mutex acquisitions per function in LOCKORDER_FILES, closed
            over the intra-file call graph into an inter-mutex acquisition
            graph; any cycle (or same-mutex re-acquisition) is reported as
            a potential deadlock with witness paths
  sigsafe   from the fatal-signal handlers installed in flight_recorder.cc,
            walk the intra-file call graph and flag any reachable call
            outside the async-signal-safe allowlist, any `new`, and any
            lock — statically pinning the PR 8 signal-dump claim;
            per-site escape: `// lint: sigsafe-ok(<reason>)`

Each pass is a pure text analysis (no build, no import of horovod_tpu), so
this runs in tier-1 CI on a bare checkout.  Output is a human report plus
optional JSON; findings are compared against a committed baseline
(tools/hvd_lint_baseline.json) so CI fails only on *new* findings.  The
baseline is empty by policy — pre-existing drift gets fixed, not baselined.

Usage:
    python tools/hvd_lint.py                # human report, exit 1 on new findings
    python tools/hvd_lint.py --json out.json
    python tools/hvd_lint.py --only atomic,lockorder   # subset, timed
    python tools/hvd_lint.py --update-baseline
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# Whitelists.  Every entry is a deliberate decision; the lint enforces that
# the lists stay honest in both directions (an entry that no longer matches
# reality is itself a finding).
# ---------------------------------------------------------------------------

# HOROVOD_* variables read directly by C++ getenv (not routed through
# utils/env.py): plane/topology knobs consumed below the ctypes ABI, where
# threading them through hvd_init would widen the init signature for no
# behavioural gain.  Each MUST be documented in a doc table.
NATIVE_READ_VARS = {
    "HOROVOD_SHM_DISABLE",
    "HOROVOD_RING_CHUNK_BYTES",
    "HOROVOD_SOCKET_BUFFER_BYTES",
    "HOROVOD_HIER_FAKE_HOSTS",
    "HOROVOD_HOSTNAME",
    "HOROVOD_WIRE_COMPRESSION_MIN_BYTES",
    "HOROVOD_METRICS_REPORT_SECONDS",
    "HOROVOD_STRAGGLER_SKEW",
    "HOROVOD_STRAGGLER_MIN_MS",
    "HOROVOD_FAULT_INJECT",
    "HOROVOD_ABORT_PROPAGATION_TIMEOUT",
    "HOROVOD_RENDEZVOUS_RETRIES",
    "HOROVOD_RENDEZVOUS_BACKOFF_BASE_MS",
    "HOROVOD_CONTROL_TREE",
    "HOROVOD_CTRL_TREE_FANOUT",
    "HOROVOD_CONTROL_TREE_DEPTH",
    "HOROVOD_RENDEZVOUS_ACCEPTORS",
    "HOROVOD_FLEET_TELEMETRY",
    "HOROVOD_SENTINEL_ZSCORE",
}

# Public knobs read in Python outside utils/env.py (module-scope or
# launcher-time concerns that never reach the core Config).  Each MUST be
# documented in a doc table.
PY_DIRECT_VARS = {
    "HOROVOD_DEVICE_PLANE",
    "HOROVOD_EXECUTOR_LANES",
    "HOROVOD_LOG_TIMESTAMP",
    "HOROVOD_SSH_COMMAND",
    "HOROVOD_TPU_WORKERS",
    "HOROVOD_TPU_PROBE_PORT",
    "HOROVOD_LSF_INCLUDE_LAUNCH_HOST",
    "HOROVOD_JAX_DISTRIBUTED",
    "HOROVOD_JAX_COORDINATOR",
    "HOROVOD_ELASTIC_DISCOVERY_INTERVAL",
    "HOROVOD_ELASTIC_FAST_FAILURE_SECS",
    "HOROVOD_ELASTIC_BLACKLIST_FAILURES",
    "HOROVOD_ELASTIC_BLACKLIST_BASE_SECS",
    "HOROVOD_AUTOPILOT",
    "HOROVOD_AUTOPILOT_EVICT_WINDOWS",
    "HOROVOD_AUTOPILOT_MIN_NP",
    "HOROVOD_AUTOPILOT_COOLDOWN_SECS",
}

# Infrastructure plumbing set by one launcher component and read by
# another (secrets, worker identity, rendezvous bootstrap).  Exempt from
# the doc-table requirement — they are not user knobs.
INTERNAL_VARS = {
    "HOROVOD_ELASTIC_SECRET",
    "HOROVOD_ELASTIC_WORKER_ID",
    "HOROVOD_ELASTIC_GENERATION",
    "HOROVOD_ELASTIC_COORD_ADDR",
    "HOROVOD_ELASTIC_COORD_PORT",
    "HOROVOD_PROBE_SECRET",
    "HOROVOD_TPU_METADATA_URL",
    "HOROVOD_RANK_FROM_JSRUN",
    # Assigned per generation by the elastic driver; the coordinator's
    # loopback policy listener binds it.  Operators never set it by hand.
    "HOROVOD_AUTOPILOT_PORT",
    # Same contract for the live-cockpit endpoint: the driver hands rank 0
    # one sticky port so SSE clients survive re-formations.  The user-facing
    # switch is HOROVOD_COCKPIT; the port is driver plumbing.
    "HOROVOD_COCKPIT_PORT",
}


@dataclasses.dataclass
class Finding:
    pass_name: str  # one of PASS_NAMES ("abi", "env", ..., "sigsafe")
    key: str        # stable id, e.g. "ABI-ARITY:hvd_init"
    message: str

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# ABI pass
# ---------------------------------------------------------------------------

# C++ parameter/return type -> the ctypes declaration _core.py must use.
CTYPE_OF = {
    "int": "c_int",
    "long long": "c_longlong",
    "double": "c_double",
    "char*": "c_char_p",
    "void*": "c_void_p",
    "void**": "POINTER(c_void_p)",
    "long long*": "POINTER(c_longlong)",
    "int*": "POINTER(c_int)",
}


def _normalize_cpp_type(decl: str) -> str:
    """'const long long* slice_counts' -> 'long long*' (identifier dropped)."""
    decl = decl.strip()
    m = re.match(r"^(.*?)\s*\b[A-Za-z_]\w*$", decl)
    if m and m.group(1).strip():
        decl = m.group(1)
    decl = decl.replace("const", " ")
    decl = re.sub(r"\s*\*\s*", "*", decl)     # glue stars to the type
    decl = re.sub(r"\s+", " ", decl).strip()
    return decl


def parse_extern_c(cpp_text: str) -> Dict[str, Tuple[str, List[str]]]:
    """Exported hvd_* symbols from core_api.cc: name -> (ret, [param types]).

    Types are normalized C++ ('long long*'); map through CTYPE_OF to get the
    expected ctypes declaration.
    """
    start = cpp_text.find('extern "C"')
    if start < 0:
        raise ValueError('no extern "C" block found')
    block = cpp_text[start:]
    out: Dict[str, Tuple[str, List[str]]] = {}
    for m in re.finditer(
            r'(?:^|\n)\s*((?:const\s+)?[A-Za-z_][\w ]*?[\s*]+)(hvd_\w+)'
            r'\s*\(([^)]*)\)\s*\{', block):
        ret_raw, name, params_raw = m.groups()
        ret = re.sub(r"\s*\*\s*", "*", ret_raw.replace("const", " "))
        ret = re.sub(r"\s+", " ", ret).strip()
        params_raw = " ".join(params_raw.split())
        params: List[str] = []
        if params_raw and params_raw != "void":
            params = [_normalize_cpp_type(p) for p in params_raw.split(",")]
        out[name] = (ret, params)
    return out


def parse_ctypes_decls(py_text: str) -> Dict[str, dict]:
    """argtypes/restype assignments from _core.py's _declare()."""
    decls: Dict[str, dict] = {}
    for m in re.finditer(r"lib\.(hvd_\w+)\.restype\s*=\s*([^\n]+)", py_text):
        name, val = m.group(1), m.group(2).strip()
        decls.setdefault(name, {})["restype"] = val.replace("c.", "")
    for m in re.finditer(r"lib\.(hvd_\w+)\.argtypes\s*=\s*\[(.*?)\]",
                         py_text, re.S):
        name, body = m.groups()
        args = [p.group(0).replace("c.", "")
                for p in re.finditer(r"c\.POINTER\(c\.\w+\)|c\.\w+", body)]
        decls.setdefault(name, {})["argtypes"] = args
    return decls


def parse_lib_calls(py_texts: Dict[str, str]) -> Dict[str, List[str]]:
    """lib.hvd_* / _lib.hvd_* attribute references per symbol -> [files]."""
    calls: Dict[str, List[str]] = {}
    for path, text in py_texts.items():
        # strip the declaration site so _declare() assignments don't count
        body = re.sub(r"lib\.hvd_\w+\.(?:argtypes|restype)[^\n]*", "", text)
        for m in re.finditer(r"\b_?lib\.(hvd_\w+)", body):
            calls.setdefault(m.group(1), []).append(path)
    return calls


def abi_pass(cpp_text: str, py_texts: Dict[str, str]) -> List[Finding]:
    findings: List[Finding] = []
    exports = parse_extern_c(cpp_text)
    core_py = py_texts.get("horovod_tpu/_core.py", "")
    decls = parse_ctypes_decls(core_py)
    calls = parse_lib_calls(py_texts)

    for name, (ret, params) in sorted(exports.items()):
        decl = decls.get(name)
        if decl is None:
            findings.append(Finding(
                "abi", f"ABI-UNDECLARED:{name}",
                f"{name} is exported by core_api.cc but has no "
                f"argtypes/restype declaration in _core.py"))
            continue
        argtypes = decl.get("argtypes")
        if argtypes is not None:
            expected = [CTYPE_OF.get(p, f"<unmapped:{p}>") for p in params]
            if len(argtypes) != len(expected):
                findings.append(Finding(
                    "abi", f"ABI-ARITY:{name}",
                    f"{name}: C++ takes {len(expected)} args, _core.py "
                    f"declares {len(argtypes)} argtypes"))
            else:
                for i, (got, want) in enumerate(zip(argtypes, expected)):
                    if got != want:
                        findings.append(Finding(
                            "abi", f"ABI-TYPE:{name}:{i}",
                            f"{name} arg {i}: C++ '{params[i]}' expects "
                            f"{want}, _core.py declares {got}"))
        elif params:
            findings.append(Finding(
                "abi", f"ABI-NOARGTYPES:{name}",
                f"{name} takes {len(params)} args but _core.py declares "
                f"no argtypes (ctypes would guess, int-truncating "
                f"pointers on LP64)"))
        restype = decl.get("restype")
        if restype is not None:
            want_ret = None if ret == "void" else CTYPE_OF.get(ret)
            if restype != (want_ret or "None"):
                findings.append(Finding(
                    "abi", f"ABI-RESTYPE:{name}",
                    f"{name}: C++ returns '{ret}' ({want_ret}), _core.py "
                    f"declares restype {restype}"))
        elif ret not in ("void", "int"):
            # ctypes defaults restype to c_int: silently truncates
            # long long returns and corrupts pointers.
            findings.append(Finding(
                "abi", f"ABI-RESTYPE:{name}",
                f"{name} returns '{ret}' but _core.py declares no restype "
                f"(ctypes default c_int truncates it)"))

    for name in sorted(set(decls) - set(exports)):
        findings.append(Finding(
            "abi", f"ABI-UNKNOWN:{name}",
            f"_core.py declares {name} which core_api.cc does not export"))
    for name, sites in sorted(calls.items()):
        if name not in exports:
            findings.append(Finding(
                "abi", f"ABI-UNKNOWN-CALL:{name}",
                f"{name} called ({sites[0]}) but not exported by "
                f"core_api.cc"))
        elif exports[name][1] and decls.get(name, {}).get("argtypes") is None:
            findings.append(Finding(
                "abi", f"ABI-CALLSITE:{name}",
                f"{name} called ({sites[0]}) with no argtypes declared"))
    return findings


# ---------------------------------------------------------------------------
# env pass
# ---------------------------------------------------------------------------

_VAR = r"HOROVOD_[A-Z0-9_]*[A-Z0-9](?![A-Z0-9_])"

# Read sites.  Writes (env["X"] = ...) are launcher plumbing and are not
# obligations; a token ending in '_' is a line-wrapped prefix, not a name.
_PY_READ_PATTERNS = [
    re.compile(r"os\.environ\.get\(\s*[\"'](" + _VAR + ")"),
    re.compile(r"os\.getenv\(\s*[\"'](" + _VAR + ")"),
    re.compile(r"\benviron\[\s*[\"'](" + _VAR + r")[\"']\s*\](?!\s*=[^=])"),
    re.compile(r"\benv\.get\(\s*[\"'](" + _VAR + ")"),
    re.compile(r"\bget_(?:bool|int|float)\(\s*[\"'](" + _VAR + ")"),
    re.compile(r"\b_env_number\(\s*\n?\s*[\"'](" + _VAR + ")"),
]
_CC_READ_PATTERN = re.compile(r"getenv\(\s*\"(" + _VAR + ")\"")


def collect_code_reads(py_files: Dict[str, str],
                       cc_files: Dict[str, str]) -> Tuple[Dict[str, List[str]],
                                                          Dict[str, List[str]]]:
    py_reads: Dict[str, List[str]] = {}
    cc_reads: Dict[str, List[str]] = {}
    for path, text in py_files.items():
        for pat in _PY_READ_PATTERNS:
            for m in pat.finditer(text):
                py_reads.setdefault(m.group(1), []).append(path)
    for path, text in cc_files.items():
        for m in _CC_READ_PATTERN.finditer(text):
            cc_reads.setdefault(m.group(1), []).append(path)
    return py_reads, cc_reads


def parse_env_py(env_py_text: str) -> Tuple[set, set]:
    """(parsed, ignored) variable sets from utils/env.py.

    'parsed' is every HOROVOD_* token in the file outside the IGNORED_VARS
    tuple — the file is the single source of truth, so a mention there IS
    the central registration.
    """
    m = re.search(r"IGNORED_VARS\s*=\s*\((.*?)\)", env_py_text, re.S)
    ignored = set(re.findall(_VAR, m.group(1))) if m else set()
    body = env_py_text
    if m:
        body = body[:m.start(1)] + body[m.end(1):]
    parsed = set(re.findall(_VAR, body)) - ignored
    return parsed, ignored


def env_pass(py_files: Dict[str, str], cc_files: Dict[str, str],
             doc_files: Dict[str, str],
             native_read_vars: Optional[set] = None,
             py_direct_vars: Optional[set] = None,
             internal_vars: Optional[set] = None) -> List[Finding]:
    native_read_vars = (NATIVE_READ_VARS if native_read_vars is None
                        else native_read_vars)
    py_direct_vars = PY_DIRECT_VARS if py_direct_vars is None else py_direct_vars
    internal_vars = INTERNAL_VARS if internal_vars is None else internal_vars

    findings: List[Finding] = []
    env_py = py_files.get("horovod_tpu/utils/env.py", "")
    parsed, ignored = parse_env_py(env_py)
    py_reads, cc_reads = collect_code_reads(py_files, cc_files)

    table_rows: set = set()
    doc_mentions: set = set()
    for _, text in doc_files.items():
        for line in text.splitlines():
            vars_here = set(re.findall(_VAR, line))
            doc_mentions |= vars_here
            if line.lstrip().startswith("|"):
                table_rows |= vars_here

    # 1. C++ getenv <-> native whitelist, exact both ways.
    for var in sorted(set(cc_reads) - native_read_vars):
        findings.append(Finding(
            "env", f"ENV-NATIVE-UNLISTED:{var}",
            f"C++ reads {var} ({cc_reads[var][0]}) but it is not in "
            f"hvd_lint's NATIVE_READ_VARS whitelist"))
    for var in sorted(native_read_vars - set(cc_reads)):
        findings.append(Finding(
            "env", f"ENV-NATIVE-STALE:{var}",
            f"{var} is whitelisted as native-read but no C++ getenv "
            f"reads it"))

    # 2. Every Python read is centrally parsed or explicitly whitelisted.
    known = parsed | ignored | native_read_vars | py_direct_vars | internal_vars
    for var, sites in sorted(py_reads.items()):
        if var not in known:
            findings.append(Finding(
                "env", f"ENV-UNMANAGED:{var}",
                f"{var} read in {sites[0]} but not parsed in utils/env.py, "
                f"not in IGNORED_VARS, and not whitelisted"))

    # 3. Whitelisted Python-direct vars must actually be read somewhere.
    for var in sorted(py_direct_vars - set(py_reads)):
        findings.append(Finding(
            "env", f"ENV-DIRECT-STALE:{var}",
            f"{var} is whitelisted as python-direct but nothing reads it"))

    # 4. Every public knob has a doc table row.
    public = (parsed | native_read_vars | py_direct_vars) - internal_vars
    for var in sorted(public - table_rows):
        findings.append(Finding(
            "env", f"ENV-UNDOCUMENTED:{var}",
            f"{var} is a public knob but appears in no markdown table row "
            f"in docs/ or README.md"))

    # 5. No doc may name a var no code knows.
    for var in sorted(doc_mentions - known):
        findings.append(Finding(
            "env", f"ENV-STALE-DOC:{var}",
            f"docs name {var} but no code reads, parses, ignores, or "
            f"whitelists it"))
    return findings


# ---------------------------------------------------------------------------
# protocol pass
# ---------------------------------------------------------------------------

def parse_protocol_constants(sc_text: str) -> Tuple[Optional[int],
                                                    Dict[str, int]]:
    """(kProtocolVersion, {kTagName: value}) from socket_controller.cc."""
    vm = re.search(r"kProtocolVersion\s*=\s*(\d+)\s*;", sc_text)
    version = int(vm.group(1)) if vm else None
    tags = {m.group(1): int(m.group(2), 0) for m in re.finditer(
        r"constexpr\s+int32_t\s+(kTag\w+)\s*=\s*(0[xX][0-9a-fA-F]+|\d+)\s*;",
        sc_text)}
    return version, tags


def parse_wire_codecs(wire_codec_text: str) -> Dict[str, int]:
    """{'none': 0, 'bf16': 1, 'int8': 2} from wire_codec.h's enum."""
    m = re.search(r"enum\s+class\s+WireCodec[^{]*\{(.*?)\}", wire_codec_text,
                  re.S)
    if not m:
        return {}
    return {em.group(1).lower(): int(em.group(2))
            for em in re.finditer(r"k(\w+)\s*=\s*(\d+)", m.group(1))}


def parse_py_codec_map(core_py_text: str) -> Dict[str, int]:
    """The {'none': 0, ...} literal _core.py passes into hvd_init."""
    m = re.search(r'\{[^{}]*"bf16"[^{}]*\}', core_py_text)
    if not m:
        return {}
    return {pm.group(1): int(pm.group(2))
            for pm in re.finditer(r'"(\w+)"\s*:\s*(\d+)', m.group(0))}


def protocol_pass(sc_text: str, wire_codec_text: str, core_py_text: str,
                  runtime_py_text: str, env_py_text: str,
                  doc_files: Dict[str, str],
                  quantize_py_text: str = "") -> List[Finding]:
    findings: List[Finding] = []
    version, tags = parse_protocol_constants(sc_text)
    if version is None:
        findings.append(Finding(
            "protocol", "PROTO-NO-VERSION",
            "kProtocolVersion not found in socket_controller.cc"))
        return findings

    # Python mirror.
    pm = re.search(r"^PROTOCOL_VERSION\s*=\s*(\d+)", runtime_py_text, re.M)
    if not pm:
        findings.append(Finding(
            "protocol", "PROTO-NO-MIRROR",
            "horovod_tpu/runtime.py defines no PROTOCOL_VERSION mirror of "
            "kProtocolVersion"))
    elif int(pm.group(1)) != version:
        findings.append(Finding(
            "protocol", "PROTO-VERSION-MIRROR",
            f"kProtocolVersion={version} but runtime.PROTOCOL_VERSION="
            f"{pm.group(1)}"))

    # Doc claims: every explicit kProtocolVersion mention must match, and
    # at least one doc must make the claim (so a bump is forced through
    # the docs).
    doc_claims = 0
    for path, text in sorted(doc_files.items()):
        for dm in re.finditer(r"kProtocolVersion\D{0,24}?(\d+)", text):
            doc_claims += 1
            if int(dm.group(1)) != version:
                findings.append(Finding(
                    "protocol", f"PROTO-VERSION-DOC:{path}",
                    f"{path} states kProtocolVersion={dm.group(1)} but C++ "
                    f"says {version}"))
    if doc_claims == 0:
        findings.append(Finding(
            "protocol", "PROTO-VERSION-UNDOCUMENTED",
            "no doc states the current kProtocolVersion (a bump would be "
            "invisible to readers)"))

    # Frame tags: unique values, fence family above the SockBarrier metric
    # threshold (kTagShmSize), op tags below it, and >=0x100 spacing so
    # per-round (+k) and per-segment (+s) offsets cannot collide.
    by_value: Dict[int, List[str]] = {}
    for name, value in tags.items():
        by_value.setdefault(value, []).append(name)
    for value, names in sorted(by_value.items()):
        if len(names) > 1:
            findings.append(Finding(
                "protocol", f"PROTO-TAG-DUP:{value:#x}",
                f"frame tag value {value:#x} duplicated: {', '.join(names)}"))
    fence_base = tags.get("kTagShmSize")
    if fence_base is None:
        findings.append(Finding(
            "protocol", "PROTO-NO-FENCE-BASE",
            "kTagShmSize (the SockBarrier fence-metric threshold) not found"))
    else:
        for name, value in sorted(tags.items()):
            is_fence = name.startswith(("kTagShm", "kTagHier"))
            if is_fence and value < fence_base:
                findings.append(Finding(
                    "protocol", f"PROTO-TAG-RANGE:{name}",
                    f"{name}={value:#x} is a shm/hier fence tag below "
                    f"kTagShmSize={fence_base:#x}; SockBarrier would not "
                    f"count it as a fence"))
            if name == "kTagBarrier" and value >= fence_base:
                findings.append(Finding(
                    "protocol", f"PROTO-TAG-RANGE:{name}",
                    f"{name}={value:#x} (the user-visible barrier) sits in "
                    f"the >= {fence_base:#x} fence-metric range"))
    values = sorted(by_value)
    for lo, hi in zip(values, values[1:]):
        if hi - lo < 0x100:
            findings.append(Finding(
                "protocol", f"PROTO-TAG-SPACING:{hi:#x}",
                f"tags {', '.join(by_value[lo])} ({lo:#x}) and "
                f"{', '.join(by_value[hi])} ({hi:#x}) are {hi - lo} apart; "
                f"round/segment offsets need >= 0x100 of headroom"))

    # Wire-codec ids: wire_codec.h enum vs _core.py init map vs env.py names.
    cpp_codecs = parse_wire_codecs(wire_codec_text)
    py_codecs = parse_py_codec_map(core_py_text)
    if cpp_codecs != py_codecs:
        findings.append(Finding(
            "protocol", "PROTO-CODEC-MIRROR",
            f"wire codec ids disagree: wire_codec.h {cpp_codecs} vs "
            f"_core.py {py_codecs}"))
    em = re.search(r"WIRE_COMPRESSION_CODECS\s*=\s*\((.*?)\)", env_py_text,
                   re.S)
    env_names = re.findall(r'"(\w+)"', em.group(1)) if em else []
    want_order = [n for n, _ in sorted(cpp_codecs.items(),
                                       key=lambda kv: kv[1])]
    if env_names != want_order:
        findings.append(Finding(
            "protocol", "PROTO-CODEC-NAMES",
            f"env.py WIRE_COMPRESSION_CODECS {env_names} does not match the "
            f"id-ordered wire_codec.h names {want_order}"))

    # Device-plane mirror: ops/quantize.py reimplements the int8 block
    # codec as traced math, so its block geometry, codec-id map, and the
    # device-codec name list must track wire_codec.h / env.py exactly —
    # a drift here desyncs the in-jit ring from the byte-stream semantics.
    if quantize_py_text:
        for py_name, cpp_name in (("WIRE_BLOCK", "kWireBlock"),
                                  ("WIRE_SCALE_BYTES", "kWireScaleBytes"),
                                  ("WIRE_INT4_MAX", "kWireInt4Max")):
            qm = re.search(r"^%s\s*=\s*(\d+)" % py_name, quantize_py_text,
                           re.M)
            cm = re.search(r"constexpr\s+int64_t\s+%s\s*=\s*(\d+)" % cpp_name,
                           wire_codec_text)
            if not qm or not cm:
                findings.append(Finding(
                    "protocol", f"PROTO-QBLOCK-MISSING:{py_name}",
                    f"block-geometry constant missing: quantize.py "
                    f"{py_name} ({'found' if qm else 'absent'}) vs "
                    f"wire_codec.h {cpp_name} "
                    f"({'found' if cm else 'absent'})"))
            elif int(qm.group(1)) != int(cm.group(1)):
                findings.append(Finding(
                    "protocol", f"PROTO-QBLOCK:{py_name}",
                    f"quantize.py {py_name}={qm.group(1)} but wire_codec.h "
                    f"{cpp_name}={cm.group(1)}"))
        qi = re.search(r"^WIRE_CODEC_IDS\s*=\s*(\{[^}]*\})", quantize_py_text,
                       re.M)
        q_codecs = ({pm.group(1): int(pm.group(2)) for pm in
                     re.finditer(r'"(\w+)"\s*:\s*(\d+)', qi.group(1))}
                    if qi else {})
        if q_codecs != cpp_codecs:
            findings.append(Finding(
                "protocol", "PROTO-QCODEC-MIRROR",
                f"wire codec ids disagree: wire_codec.h {cpp_codecs} vs "
                f"quantize.py WIRE_CODEC_IDS {q_codecs}"))
        dm = re.search(r"^DEVICE_WIRE_CODECS\s*=\s*\((.*?)\)",
                       quantize_py_text, re.M | re.S)
        dev_names = re.findall(r'"(\w+)"', dm.group(1)) if dm else []
        edm = re.search(r"DEVICE_WIRE_COMPRESSION_CODECS\s*=\s*\((.*?)\)",
                        env_py_text, re.S)
        env_dev = re.findall(r'"(\w+)"', edm.group(1)) if edm else []
        if dev_names != env_dev:
            findings.append(Finding(
                "protocol", "PROTO-DEVICE-CODEC-NAMES",
                f"quantize.py DEVICE_WIRE_CODECS {dev_names} does not match "
                f"env.py DEVICE_WIRE_COMPRESSION_CODECS {env_dev}"))
        for name in dev_names:
            if name not in cpp_codecs:
                findings.append(Finding(
                    "protocol", f"PROTO-DEVICE-CODEC-UNKNOWN:{name}",
                    f"device codec {name!r} has no wire_codec.h enum id"))
    return findings


# ---------------------------------------------------------------------------
# flight-recorder event-type pass
# ---------------------------------------------------------------------------

# The doc table is located by this marker comment so the parser never
# confuses it with other numeric markdown tables (wire codecs, phases).
FLIGHT_DOC_MARKER = "<!-- hvd_lint:flight-types -->"


def parse_flight_enum(fr_h_text: str) -> Dict[int, str]:
    """{id: CamelSuffix} from flight_recorder.h's FlightType enum."""
    m = re.search(r"enum\s+FlightType[^{]*\{(.*?)\}", fr_h_text, re.S)
    if not m:
        return {}
    return {int(em.group(2)): em.group(1)
            for em in re.finditer(r"kFlight(\w+)\s*=\s*(\d+)", m.group(1))}


def parse_flight_legend(fr_cc_text: str) -> Dict[int, str]:
    """{id: snake_name} from flight_recorder.cc's kFlightTypesLegend."""
    m = re.search(r"kFlightTypesLegend\[\]\s*=(.*?);", fr_cc_text, re.S)
    if not m:
        return {}
    return {int(p.group(1)): p.group(2)
            for p in re.finditer(r'\\"(\d+)\\":\\"(\w+)\\"', m.group(1))}


def parse_flight_py(postmortem_text: str) -> Dict[int, str]:
    """{id: snake_name} from tools/postmortem.py's FLIGHT_TYPES."""
    m = re.search(r"FLIGHT_TYPES\s*=\s*\{(.*?)\}", postmortem_text, re.S)
    if not m:
        return {}
    return {int(p.group(1)): p.group(2)
            for p in re.finditer(r'(\d+)\s*:\s*"(\w+)"', m.group(1))}


def parse_flight_doc(doc_text: str) -> Optional[Dict[int, str]]:
    """{id: snake_name} from the marked table; None when no marker."""
    idx = doc_text.find(FLIGHT_DOC_MARKER)
    if idx < 0:
        return None
    # The table ends at the first blank line after the marker's table rows.
    rows: Dict[int, str] = {}
    for line in doc_text[idx:].splitlines()[1:]:
        if rows and not line.lstrip().startswith("|"):
            break
        rm = re.match(r"\s*\|\s*(\d+)\s*\|\s*`(\w+)`\s*\|", line)
        if rm:
            rows[int(rm.group(1))] = rm.group(2)
    return rows


def flight_pass(fr_h_text: str, fr_cc_text: str, postmortem_text: str,
                doc_files: Dict[str, str]) -> List[Finding]:
    findings: List[Finding] = []
    enum = parse_flight_enum(fr_h_text)
    legend = parse_flight_legend(fr_cc_text)
    py_types = parse_flight_py(postmortem_text)
    for what, table, key in (("flight_recorder.h FlightType enum", enum,
                              "FLIGHT-NO-ENUM"),
                             ("flight_recorder.cc kFlightTypesLegend", legend,
                              "FLIGHT-NO-LEGEND"),
                             ("tools/postmortem.py FLIGHT_TYPES", py_types,
                              "FLIGHT-NO-PY")):
        if not table:
            findings.append(Finding(
                "flight", key, f"could not parse {what}"))
    if not (enum and legend and py_types):
        return findings

    if set(enum) != set(legend):
        findings.append(Finding(
            "flight", "FLIGHT-ENUM-LEGEND",
            f"FlightType enum ids {sorted(enum)} != kFlightTypesLegend ids "
            f"{sorted(legend)}"))
    else:
        for tid, camel in sorted(enum.items()):
            # Loose name check: the legend's snake name sans underscores and
            # the enum suffix must share a prefix (kFlightTreeAgg is the
            # abbreviation of tree_aggregate).
            a, b = camel.lower(), legend[tid].replace("_", "")
            if not (a.startswith(b) or b.startswith(a)):
                findings.append(Finding(
                    "flight", f"FLIGHT-NAME:{tid}",
                    f"type {tid}: enum kFlight{camel} does not match legend "
                    f"name {legend[tid]!r}"))
    if py_types != legend:
        findings.append(Finding(
            "flight", "FLIGHT-PY-MIRROR",
            f"tools/postmortem.py FLIGHT_TYPES {py_types} != "
            f"kFlightTypesLegend {legend}"))

    doc_rows = None
    doc_path = None
    for path, text in sorted(doc_files.items()):
        rows = parse_flight_doc(text)
        if rows is not None:
            doc_rows, doc_path = rows, path
            break
    if doc_rows is None:
        findings.append(Finding(
            "flight", "FLIGHT-DOC-NO-TABLE",
            f"no doc carries the {FLIGHT_DOC_MARKER} marked event-type "
            f"table"))
    else:
        for tid in sorted(set(legend) - set(doc_rows)):
            findings.append(Finding(
                "flight", f"FLIGHT-DOC-MISSING:{tid}",
                f"{doc_path}: event type {tid} ({legend[tid]}) missing from "
                f"the flight-types table"))
        for tid in sorted(set(doc_rows) - set(legend)):
            findings.append(Finding(
                "flight", f"FLIGHT-DOC-STALE:{tid}",
                f"{doc_path}: flight-types table row {tid} "
                f"({doc_rows[tid]}) names a type the C legend lacks"))
        for tid in sorted(set(doc_rows) & set(legend)):
            if doc_rows[tid] != legend[tid]:
                findings.append(Finding(
                    "flight", f"FLIGHT-DOC-RENAMED:{tid}",
                    f"{doc_path}: table calls type {tid} "
                    f"{doc_rows[tid]!r} but the legend says "
                    f"{legend[tid]!r}"))
    return findings


# ---------------------------------------------------------------------------
# Shared C++ mini-parser for the concurrency passes
#
# Pure text analysis, like every other pass: comments and string/char
# literals are blanked (length-preserving, so offsets stay line-accurate),
# then function bodies are located by brace matching.  The parser is
# deliberately scoped to this codebase's style (Google C++, no raw string
# literals, no preprocessor function definitions); it is not a general C++
# front end.
# ---------------------------------------------------------------------------

# `// lint: seq_cst-ok(<reason>)` / `// lint: sigsafe-ok(<reason>)` on the
# flagged line (or the line immediately above it) suppresses that site.
# Hatches are stale-checked like the env whitelists: one that no longer
# suppresses anything is itself a finding.
_HATCH_RE = re.compile(r"//\s*lint:\s*(seq_cst-ok|sigsafe-ok)\(([^)\n]*)\)")

_CPP_KEYWORDS = {
    "if", "for", "while", "switch", "catch", "do", "else", "return",
    "sizeof", "alignof", "decltype", "throw", "case", "default", "new",
    "delete", "static_cast", "reinterpret_cast", "const_cast",
    "dynamic_cast", "defined", "not", "and", "or", "assert",
    "static_assert", "typeid", "noexcept",
}


def strip_cpp(text: str) -> str:
    """Blank comments and string/char literals, preserving length/newlines."""
    out = list(text)
    i, n = 0, len(text)
    while i < n:
        c = text[i]
        if c == "/" and i + 1 < n and text[i + 1] == "/":
            j = text.find("\n", i)
            if j < 0:
                j = n
            for k in range(i, j):
                out[k] = " "
            i = j
        elif c == "/" and i + 1 < n and text[i + 1] == "*":
            j = text.find("*/", i + 2)
            j = n if j < 0 else j + 2
            for k in range(i, j):
                if out[k] != "\n":
                    out[k] = " "
            i = j
        elif c in "\"'":
            q = c
            j = i + 1
            while j < n and text[j] != q:
                j += 2 if text[j] == "\\" else 1
            for k in range(i + 1, min(j, n)):
                if out[k] != "\n":
                    out[k] = " "
            i = min(j, n) + 1
        else:
            i += 1
    return "".join(out)


def collect_hatches(raw_text: str) -> Dict[int, str]:
    """{1-based line: hatch kind} for every `// lint: *-ok(...)` comment."""
    hatches: Dict[int, str] = {}
    for lineno, line in enumerate(raw_text.splitlines(), 1):
        m = _HATCH_RE.search(line)
        if m:
            hatches[lineno] = m.group(1)
    return hatches


def _line_of(text: str, pos: int) -> int:
    return text.count("\n", 0, pos) + 1


def _match_brace(text: str, open_pos: int) -> int:
    """Index of the '}' matching the '{' at open_pos (len(text) if none)."""
    depth = 0
    for i in range(open_pos, len(text)):
        if text[i] == "{":
            depth += 1
        elif text[i] == "}":
            depth -= 1
            if depth == 0:
                return i
    return len(text)


def _header_function_name(header: str) -> Optional[str]:
    """Function name if `header {` opens a function body, else None.

    Containers (namespace/struct/class/enum/extern blocks), control flow,
    brace initializers, and lambdas all return None.
    """
    header = header.strip()
    # Constructor member-initializer list: cut at the single ':' that sits
    # at paren depth 0 after the parameter list ("Foo::Foo(x) : a_(x)").
    depth = 0
    for i, ch in enumerate(header):
        if ch in "(<[":
            depth += 1
        elif ch in ")>]":
            depth = max(0, depth - 1)
        elif (ch == ":" and depth == 0 and header[i - 1:i] != ":"
              and header[i + 1:i + 2] != ":" and header[:i].rstrip().endswith(")")):
            header = header[:i]
            break
    # Strip trailing qualifiers so the header ends at the param list.
    while True:
        stripped = header.rstrip()
        for qual in ("const", "noexcept", "override", "final"):
            if stripped.endswith(qual):
                header = stripped[: -len(qual)]
                break
        else:
            break
    header = header.rstrip()
    if not header.endswith(")"):
        return None
    # Backward-match the parameter list's opening paren.
    depth = 0
    open_idx = -1
    for i in range(len(header) - 1, -1, -1):
        if header[i] == ")":
            depth += 1
        elif header[i] == "(":
            depth -= 1
            if depth == 0:
                open_idx = i
                break
    if open_idx <= 0:
        return None
    before = header[:open_idx].rstrip()
    if before.endswith("]"):  # lambda introducer
        return None
    m = re.search(r"([A-Za-z_~]\w*)$", before)
    if not m:
        return None
    name = m.group(1)
    if name in _CPP_KEYWORDS:
        return None
    return name


def parse_cpp_functions(stripped: str) -> List[Tuple[str, int, int]]:
    """[(name, body_open_idx, body_close_idx)] for every function definition.

    Containers (namespaces, classes, extern "C" blocks) are descended into;
    function bodies are consumed whole, so lambdas and control-flow braces
    inside them never register as functions of their own.
    """
    funcs: List[Tuple[str, int, int]] = []
    i, n = 0, len(stripped)
    last_stmt = 0
    paren = 0
    while i < n:
        c = stripped[i]
        if c == "(":
            paren += 1
        elif c == ")":
            paren = max(0, paren - 1)
        elif c == ";" and paren == 0:
            last_stmt = i + 1
        elif c == "}" and paren == 0:
            last_stmt = i + 1
        elif c == "{" and paren == 0:
            name = _header_function_name(stripped[last_stmt:i])
            if name is not None:
                end = _match_brace(stripped, i)
                funcs.append((name, i, end))
                i = end
                last_stmt = i + 1
            else:
                last_stmt = i + 1  # container or brace-init: descend
        i += 1
    return funcs


def _enclosing_function(funcs: Sequence[Tuple[str, int, int]],
                        pos: int) -> str:
    for name, start, end in funcs:
        if start <= pos <= end:
            return name
    return "<file scope>"


# ---------------------------------------------------------------------------
# atomic pass: explicit memory_order on every hot-path atomic op
# ---------------------------------------------------------------------------

# The always-on lock-free subsystems: every atomic op here runs on the
# negotiation/record hot path (or a crash path) where an accidental
# seq_cst fence is either a silent throughput tax or an unstated ordering
# claim.  Each op must name its memory_order so the required ordering is a
# reviewed decision, not a compiler default.
ATOMIC_HOT_FILES = {
    "metrics.cc", "metrics.h",
    "flight_recorder.cc", "flight_recorder.h",
    "step_trace.cc", "step_trace.h",
    "fleet_telemetry.cc", "fleet_telemetry.h",
    "fault_injection.cc", "fault_injection.h",
}

_ATOMIC_OP_RE = re.compile(
    r"\.\s*(load|store|exchange|fetch_add|fetch_sub|fetch_and|fetch_or|"
    r"fetch_xor|compare_exchange_strong|compare_exchange_weak)\s*\(")


def _balanced_args(stripped: str, open_pos: int) -> str:
    """The argument text of the call whose '(' is at open_pos."""
    depth = 0
    for i in range(open_pos, len(stripped)):
        if stripped[i] == "(":
            depth += 1
        elif stripped[i] == ")":
            depth -= 1
            if depth == 0:
                return stripped[open_pos + 1:i]
    return stripped[open_pos + 1:]


def atomic_pass(cc_files: Dict[str, str],
                hot_files: Optional[set] = None) -> List[Finding]:
    hot_files = ATOMIC_HOT_FILES if hot_files is None else hot_files
    findings: List[Finding] = []
    for path, raw in sorted(cc_files.items()):
        base = os.path.basename(path)
        if base not in hot_files:
            continue
        stripped = strip_cpp(raw)
        funcs = parse_cpp_functions(stripped)
        hatches = collect_hatches(raw)
        used_hatches: set = set()
        for m in _ATOMIC_OP_RE.finditer(stripped):
            op = m.group(1)
            args = _balanced_args(stripped, m.end() - 1)
            if "memory_order" in args:
                continue
            lineno = _line_of(stripped, m.start())
            hatch_line = next(
                (ln for ln in (lineno, lineno - 1)
                 if hatches.get(ln) == "seq_cst-ok"), None)
            if hatch_line is not None:
                used_hatches.add(hatch_line)
                continue
            expr = re.search(r"[\w\]\[.>-]*$",
                             stripped[:m.start()].split("\n")[-1])
            site = (expr.group(0) if expr and expr.group(0) else "<expr>")
            findings.append(Finding(
                "atomic", f"ATOMIC-IMPLICIT:{base}:{lineno}",
                f"{base}:{lineno} ({_enclosing_function(funcs, m.start())}): "
                f"{site}.{op}() names no memory_order — implicit seq_cst "
                f"is an unstated ordering claim (and a fence on the hot "
                f"path); spell the required order or annotate "
                f"`// lint: seq_cst-ok(<reason>)`"))
        for ln in sorted(set(ln for ln, kind in hatches.items()
                             if kind == "seq_cst-ok") - used_hatches):
            findings.append(Finding(
                "atomic", f"ATOMIC-STALE-OK:{base}:{ln}",
                f"{base}:{ln}: `lint: seq_cst-ok` hatch suppresses nothing "
                f"(no implicit-order atomic op on this or the next line) — "
                f"remove it"))
    return findings


# ---------------------------------------------------------------------------
# lockorder pass: inter-mutex acquisition graph, cycles = deadlock risk
# ---------------------------------------------------------------------------

# The files whose mutexes guard the coordinator / ABI / shm planes.  The
# analysis is per file: these mutexes are file-local, and internal calls
# in them are unqualified member/free calls (dotted calls go to other
# objects — sockets, maps — and are excluded from the call graph).
LOCKORDER_FILES = {"socket_controller.cc", "core_api.cc", "shm_plane.cc"}

_GUARD_RE = re.compile(
    r"\b(?:std::)?(?:lock_guard|unique_lock|scoped_lock)\s*<[^;(){}]*>\s*"
    r"\w+\s*\(\s*([^(),;{}]+?)\s*[,)]")

_CALL_RE = re.compile(r"(?<![\w.>:])([A-Za-z_]\w*)\s*\(")


def _mutex_name(expr: str) -> str:
    """'g->queue_mu' / 'S().init_mu' -> trailing identifier."""
    ids = re.findall(r"\w+", expr)
    return ids[-1] if ids else expr.strip()


def _function_lock_profile(stripped: str, name: str, body: Tuple[int, int],
                           local_funcs: set):
    """(direct_edges, held_calls, acquires, callees) for one function body.

    direct_edges: [(held_mutex, acquired_mutex, lineno)]
    held_calls:   [(held_mutexes_frozenset, callee, lineno)]
    acquires:     {mutex} acquired anywhere in the body
    callees:      {local function} called anywhere in the body
    """
    start, end = body
    text = stripped[start:end + 1]
    events = []  # (offset, kind, payload)
    for m in _GUARD_RE.finditer(text):
        events.append((m.start(), "guard", _mutex_name(m.group(1))))
    for m in _CALL_RE.finditer(text):
        callee = m.group(1)
        if callee in local_funcs and callee != name \
                and callee not in _CPP_KEYWORDS:
            events.append((m.start(), "call", callee))
    events.sort()
    direct_edges, held_calls = [], []
    acquires, callees = set(), set()
    held: List[Tuple[str, int]] = []  # (mutex, depth at declaration)
    depth = 0
    ei = 0
    for i, ch in enumerate(text):
        while ei < len(events) and events[ei][0] == i:
            _, kind, payload = events[ei]
            ei += 1
            lineno = _line_of(stripped, start + i)
            if kind == "guard":
                acquires.add(payload)
                for held_mu, _ in held:
                    direct_edges.append((held_mu, payload, lineno))
                held.append((payload, depth))
            else:
                callees.add(payload)
                if held:
                    held_calls.append(
                        (frozenset(mu for mu, _ in held), payload, lineno))
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            held = [(mu, d) for mu, d in held if d <= depth]
    return direct_edges, held_calls, acquires, callees


def lockorder_pass(cc_files: Dict[str, str],
                   files: Optional[set] = None) -> List[Finding]:
    files = LOCKORDER_FILES if files is None else files
    findings: List[Finding] = []
    for path, raw in sorted(cc_files.items()):
        base = os.path.basename(path)
        if base not in files:
            continue
        stripped = strip_cpp(raw)
        funcs = parse_cpp_functions(stripped)
        local_funcs = {name for name, _, _ in funcs}
        profiles = {}
        for name, fstart, fend in funcs:
            prof = _function_lock_profile(stripped, name, (fstart, fend),
                                          local_funcs)
            if name in profiles:  # overloads: union the profiles
                old = profiles[name]
                prof = (old[0] + prof[0], old[1] + prof[1],
                        old[2] | prof[2], old[3] | prof[3])
            profiles[name] = prof

        # Transitive closure: every mutex a function may acquire, itself
        # or via any intra-file callee.
        closure = {name: set(prof[2]) for name, prof in profiles.items()}
        changed = True
        while changed:
            changed = False
            for name, prof in profiles.items():
                for callee in prof[3]:
                    extra = closure.get(callee, set()) - closure[name]
                    if extra:
                        closure[name] |= extra
                        changed = True

        # Edge set with witnesses.
        edges: Dict[Tuple[str, str], List[str]] = {}
        for name, (direct_edges, held_calls, _, _) in profiles.items():
            for held_mu, acq_mu, lineno in direct_edges:
                edges.setdefault((held_mu, acq_mu), []).append(
                    f"{name} holds {held_mu}, acquires {acq_mu} "
                    f"({base}:{lineno})")
            for held_set, callee, lineno in held_calls:
                for acq_mu in closure.get(callee, ()):
                    for held_mu in held_set:
                        edges.setdefault((held_mu, acq_mu), []).append(
                            f"{name} holds {held_mu}, calls {callee} which "
                            f"may acquire {acq_mu} ({base}:{lineno})")

        # Self-deadlock: std::mutex is non-recursive, so A -> A is an
        # immediate hang on the first path that actually nests.
        for (a, b), wits in sorted(edges.items()):
            if a == b:
                findings.append(Finding(
                    "lockorder", f"LOCKORDER-SELF:{base}:{a}",
                    f"{base}: {a} may be acquired while already held "
                    f"(std::mutex is non-recursive): {wits[0]}"))

        # Cycles: Tarjan SCC, then one witness cycle per non-trivial SCC.
        adj: Dict[str, set] = {}
        for (a, b) in edges:
            if a != b:
                adj.setdefault(a, set()).add(b)
                adj.setdefault(b, set())
        for scc in _tarjan_sccs(adj):
            if len(scc) < 2:
                continue
            cycle = _witness_cycle(adj, scc)
            key_path = "->".join(cycle + [cycle[0]])
            wit_lines = []
            for x, y in zip(cycle, cycle[1:] + [cycle[0]]):
                wit_lines.append(edges[(x, y)][0])
            findings.append(Finding(
                "lockorder", f"LOCKORDER-CYCLE:{base}:{key_path}",
                f"{base}: lock-order cycle {key_path} — potential "
                f"deadlock; witness paths: " + "; ".join(wit_lines)))
    return findings


def _tarjan_sccs(adj: Dict[str, set]) -> List[List[str]]:
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: set = set()
    stack: List[str] = []
    sccs: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str):
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        for w in sorted(adj.get(v, ())):
            if w not in index:
                strongconnect(w)
                low[v] = min(low[v], low[w])
            elif w in on_stack:
                low[v] = min(low[v], index[w])
        if low[v] == index[v]:
            scc = []
            while True:
                w = stack.pop()
                on_stack.discard(w)
                scc.append(w)
                if w == v:
                    break
            sccs.append(scc)

    for v in sorted(adj):
        if v not in index:
            strongconnect(v)
    return sccs


def _witness_cycle(adj: Dict[str, set], scc: List[str]) -> List[str]:
    """One simple cycle through the SCC, starting at its min node."""
    scc_set = set(scc)
    start = min(scc)
    # BFS back to start restricted to the SCC.
    from collections import deque
    prev = {start: None}
    dq = deque([start])
    while dq:
        v = dq.popleft()
        for w in sorted(adj.get(v, ())):
            if w == start and v != start:
                path = [v]
                while prev[path[-1]] is not None:
                    path.append(prev[path[-1]])
                return list(reversed(path))
            if w in scc_set and w not in prev:
                prev[w] = v
                dq.append(w)
    return [start]


# ---------------------------------------------------------------------------
# sigsafe pass: async-signal-safety of the fatal-signal dump path
# ---------------------------------------------------------------------------

# The file whose fatal-signal handlers this pass certifies.  Entry points
# are discovered from the handler-installation sites (`sa_handler = X`,
# `signal(SIG, X)`), so adding a handler automatically widens the audit.
SIGSAFE_FILE = "flight_recorder.cc"

_HANDLER_INSTALL_RE = re.compile(
    r"(?:\.sa_handler\s*=\s*|\bsignal\s*\(\s*\w+\s*,\s*)([A-Za-z_]\w*)")

# Callables permitted in a fatal-signal context: the POSIX
# async-signal-safe set this code actually uses, allocation-free string/
# memory primitives, and lock-free std::atomic member ops.  Everything
# else reachable from a handler is a finding.
SIGSAFE_ALLOWED_CALLS = {
    # POSIX async-signal-safe functions
    "write", "read", "open", "close", "rename", "unlink", "fsync",
    "raise", "kill", "_exit", "abort", "sigaction", "sigemptyset",
    "sigaddset", "signal", "clock_gettime", "time", "getpid",
    # allocation-free libc string/memory primitives
    "memcpy", "memmove", "memset", "strlen", "strncpy",
    # lock-free atomic member ops
    "load", "store", "exchange", "fetch_add", "fetch_sub", "fetch_and",
    "fetch_or", "fetch_xor", "compare_exchange_strong",
    "compare_exchange_weak",
    # constexpr header-inline helpers (no allocation, no locks, no errno)
    "min", "max",
}

# Tokens whose presence in a reachable body is an allocation or lock no
# matter how it is spelled as a call.
_SIGSAFE_NEW_RE = re.compile(r"\bnew\b")
_SIGSAFE_LOCK_RE = re.compile(
    r"\b(?:std::)?(?:lock_guard|unique_lock|scoped_lock)\b|\.\s*lock\s*\(")
_SIGSAFE_CALL_RE = re.compile(r"(?<![\w>])([A-Za-z_]\w*)\s*\(")


def sigsafe_pass(fr_cc_text: str,
                 filename: str = SIGSAFE_FILE) -> List[Finding]:
    findings: List[Finding] = []
    stripped = strip_cpp(fr_cc_text)
    hatches = collect_hatches(fr_cc_text)
    used_hatches: set = set()
    funcs = parse_cpp_functions(stripped)
    bodies: Dict[str, List[Tuple[int, int]]] = {}
    for name, start, end in funcs:
        bodies.setdefault(name, []).append((start, end))

    entries = sorted(set(_HANDLER_INSTALL_RE.findall(stripped))
                     & set(bodies))
    if not entries:
        findings.append(Finding(
            "sigsafe", f"SIGSAFE-NO-ENTRY:{filename}",
            f"{filename}: no fatal-signal handler installation found "
            f"(sa_handler = X / signal(SIG, X)) — the signal-dump "
            f"async-signal-safety claim has nothing to anchor to"))
        return findings

    def _body_calls(name: str) -> List[Tuple[str, int]]:
        out = []
        for start, end in bodies.get(name, ()):
            text = stripped[start:end + 1]
            for m in _SIGSAFE_CALL_RE.finditer(text):
                out.append((m.group(1), _line_of(stripped, start + m.start())))
        return out

    # Reachability over the intra-file call graph (dotted calls included:
    # SafeWriter-style local struct methods are called through a value).
    reachable: List[str] = []
    seen = set(entries)
    queue = list(entries)
    while queue:
        fn = queue.pop(0)
        reachable.append(fn)
        for callee, _ in _body_calls(fn):
            if callee in bodies and callee not in seen:
                seen.add(callee)
                queue.append(callee)

    def _excused(lineno: int) -> bool:
        for ln in (lineno, lineno - 1):
            if hatches.get(ln) == "sigsafe-ok":
                used_hatches.add(ln)
                return True
        return False

    for fn in reachable:
        for callee, lineno in _body_calls(fn):
            if callee in bodies or callee in SIGSAFE_ALLOWED_CALLS \
                    or callee in _CPP_KEYWORDS:
                continue
            if _excused(lineno):
                continue
            findings.append(Finding(
                "sigsafe", f"SIGSAFE-UNSAFE-CALL:{fn}:{callee}",
                f"{filename}:{lineno}: {fn} (reachable from fatal-signal "
                f"handler {'/'.join(entries)}) calls {callee}(), which is "
                f"not on the async-signal-safe allowlist"))
        for start, end in bodies.get(fn, ()):
            text = stripped[start:end + 1]
            for m in _SIGSAFE_NEW_RE.finditer(text):
                lineno = _line_of(stripped, start + m.start())
                if _excused(lineno):
                    continue
                findings.append(Finding(
                    "sigsafe", f"SIGSAFE-NEW:{fn}:{lineno}",
                    f"{filename}:{lineno}: {fn} (reachable from the "
                    f"fatal-signal handler) allocates with `new` — malloc "
                    f"is not async-signal-safe"))
            for m in _SIGSAFE_LOCK_RE.finditer(text):
                lineno = _line_of(stripped, start + m.start())
                if _excused(lineno):
                    continue
                findings.append(Finding(
                    "sigsafe", f"SIGSAFE-LOCK:{fn}:{lineno}",
                    f"{filename}:{lineno}: {fn} (reachable from the "
                    f"fatal-signal handler) takes a lock — a mutex held "
                    f"by the interrupted thread deadlocks the dump"))
    for ln in sorted(set(ln for ln, kind in hatches.items()
                         if kind == "sigsafe-ok") - used_hatches):
        findings.append(Finding(
            "sigsafe", f"SIGSAFE-STALE-OK:{filename}:{ln}",
            f"{filename}:{ln}: `lint: sigsafe-ok` hatch suppresses "
            f"nothing (no unsafe construct on this or the next line) — "
            f"remove it"))
    return findings


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _read(relpath: str) -> str:
    with open(os.path.join(REPO, relpath), encoding="utf-8",
              errors="replace") as f:
        return f.read()


def _collect(root: str, subdir: str, exts: Sequence[str]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    base = os.path.join(root, subdir)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if any(fn.endswith(e) for e in exts):
                full = os.path.join(dirpath, fn)
                rel = os.path.relpath(full, root)
                with open(full, encoding="utf-8", errors="replace") as f:
                    out[rel] = f.read()
    return out


PASS_NAMES = ("abi", "env", "protocol", "flight", "atomic", "lockorder",
              "sigsafe")


def run_repo(root: str = REPO, only: Optional[Sequence[str]] = None,
             timings: Optional[Dict[str, float]] = None) -> List[Finding]:
    """Run the selected passes (all by default) over the repo at `root`.

    `only` narrows to a subset of PASS_NAMES; `timings`, when given, is
    filled with {pass_name: wall_seconds} for the passes that ran.
    """
    selected = set(PASS_NAMES) if only is None else set(only)
    unknown = selected - set(PASS_NAMES)
    if unknown:
        raise ValueError(f"unknown pass(es): {sorted(unknown)}; "
                         f"valid: {', '.join(PASS_NAMES)}")
    py_files = _collect(root, "horovod_tpu", (".py",))
    cc_files = _collect(root, os.path.join("horovod_tpu", "cpp"),
                        (".cc", ".h"))
    doc_files = _collect(root, "docs", (".md",))
    readme = os.path.join(root, "README.md")
    if os.path.exists(readme):
        with open(readme, encoding="utf-8") as f:
            doc_files["README.md"] = f.read()
    pm_path = os.path.join(root, "tools", "postmortem.py")
    pm_text = ""
    if os.path.exists(pm_path):
        with open(pm_path, encoding="utf-8", errors="replace") as f:
            pm_text = f.read()

    runners = {
        "abi": lambda: abi_pass(cc_files["horovod_tpu/cpp/core_api.cc"],
                                py_files),
        "env": lambda: env_pass(py_files, cc_files, doc_files),
        "protocol": lambda: protocol_pass(
            cc_files["horovod_tpu/cpp/socket_controller.cc"],
            cc_files["horovod_tpu/cpp/wire_codec.h"],
            py_files["horovod_tpu/_core.py"],
            py_files["horovod_tpu/runtime.py"],
            py_files["horovod_tpu/utils/env.py"],
            doc_files,
            quantize_py_text=py_files.get("horovod_tpu/ops/quantize.py",
                                          "")),
        "flight": lambda: flight_pass(
            cc_files["horovod_tpu/cpp/flight_recorder.h"],
            cc_files["horovod_tpu/cpp/flight_recorder.cc"],
            pm_text, doc_files),
        "atomic": lambda: atomic_pass(cc_files),
        "lockorder": lambda: lockorder_pass(cc_files),
        "sigsafe": lambda: sigsafe_pass(
            cc_files.get("horovod_tpu/cpp/" + SIGSAFE_FILE, "")),
    }
    findings: List[Finding] = []
    for pass_name in PASS_NAMES:
        if pass_name not in selected:
            continue
        t0 = time.perf_counter()
        findings += runners[pass_name]()
        if timings is not None:
            timings[pass_name] = time.perf_counter() - t0
    return findings


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=REPO)
    ap.add_argument("--json", metavar="PATH",
                    help="also write the full machine-readable report here")
    ap.add_argument("--only", metavar="PASS[,PASS...]",
                    help="run only these passes (of: %s) — lets CI rows "
                    "run the cheap passes quickly and attribute slow ones"
                    % ", ".join(PASS_NAMES))
    ap.add_argument("--baseline",
                    default=os.path.join(REPO, "tools",
                                         "hvd_lint_baseline.json"))
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept all current findings as the new baseline")
    args = ap.parse_args(argv)

    only = None
    if args.only:
        only = [p.strip() for p in args.only.split(",") if p.strip()]
        try:
            run_names = [p for p in PASS_NAMES if p in set(only)]
            if set(only) - set(PASS_NAMES):
                raise ValueError
        except ValueError:
            ap.error(f"--only: unknown pass in {args.only!r}; valid: "
                     f"{', '.join(PASS_NAMES)}")
    else:
        run_names = list(PASS_NAMES)

    timings: Dict[str, float] = {}
    findings = run_repo(args.repo, only=only, timings=timings)
    baseline_keys: set = set()
    if os.path.exists(args.baseline):
        with open(args.baseline, encoding="utf-8") as f:
            baseline_keys = set(json.load(f).get("findings", []))
    new = [f for f in findings if f.key not in baseline_keys]

    for pass_name in run_names:
        hits = [f for f in findings if f.pass_name == pass_name]
        print(f"[{pass_name}] {len(hits)} finding(s) "
              f"({timings.get(pass_name, 0.0) * 1000:.1f} ms)")
        for f in hits:
            marker = " " if f.key in baseline_keys else "*"
            print(f"  {marker} {f.key}: {f.message}")
    print(f"hvd_lint: {len(findings)} finding(s), {len(new)} new vs baseline "
          f"({len(baseline_keys)} baselined)")

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump({"findings": [x.as_dict() for x in findings],
                       "new": [x.key for x in new]}, f, indent=2)
    if args.update_baseline:
        with open(args.baseline, "w", encoding="utf-8") as f:
            json.dump({"findings": sorted(x.key for x in findings)}, f,
                      indent=2)
        print(f"baseline updated: {args.baseline}")
        return 0
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
