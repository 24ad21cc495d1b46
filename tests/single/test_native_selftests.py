"""Native-core selftests: in-process 3-rank controller integration and the
sanitizer matrix over it — TSan (races: negotiation, metrics registry
increment-while-dump, shm fence paths), ASan (memory errors), UBSan
(undefined behaviour), all with -fno-sanitize-recover so any report is a
non-zero exit (SURVEY.md §5 — thread safety by design, made mechanically
checkable)."""

import os
import shutil
import subprocess
import sys
import time

import pytest

CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "horovod_tpu", "cpp")


def _build_and_run(target: str, timeout: int = 300) -> str:
    build = subprocess.run(["make", target], cwd=CPP_DIR,
                           capture_output=True, text=True, timeout=timeout)
    assert build.returncode == 0, build.stdout + build.stderr
    run = subprocess.run([os.path.join(CPP_DIR, target)],
                         capture_output=True, text=True, timeout=timeout)
    assert run.returncode == 0, (
        f"rc={run.returncode}\n{run.stdout}\n{run.stderr}")
    assert "PASS" in run.stdout
    return run.stdout + run.stderr


def test_core_selftest_3ranks():
    """Negotiation + ring allreduce + barriers + clean shutdown, 25 cycles,
    3 in-process ranks."""
    _build_and_run("core_selftest")


def test_core_selftest_under_tsan():
    """The same workload under TSan, now including the metrics-enabled
    phase: a dumper thread snapshots the registry while 3 rank threads
    increment it and observe shm fence / ring hop latencies."""
    out = _build_and_run("tsan_selftest")
    assert "ThreadSanitizer" not in out, out


def test_core_selftest_under_asan():
    out = _build_and_run("asan_selftest")
    assert "AddressSanitizer" not in out, out


def test_core_selftest_under_ubsan():
    # UBSan reports carry "runtime error:"; -fno-sanitize-recover also
    # makes any report fatal, which _build_and_run asserts via rc == 0.
    out = _build_and_run("ubsan_selftest")
    assert "runtime error" not in out, out


def test_chunk_exchange_selftest():
    """Randomized-geometry fuzz of ChunkedDuplexExchange (the primitive
    under the pipelined ring/chain data plane) plus its header-mismatch
    and cancellation error paths, and the wire-codec layer: bf16
    round-trip exactness, int8 block-scale error bound, incremental
    (chunk-boundary) decode equivalence, and the fp32 ring-accumulation
    bound (error <= hops x scale/2)."""
    _build_and_run("chunk_exchange_selftest")


def test_chaos_selftest():
    """Fault-injection spec parsing, calibrated hit-index triggering, and
    the v8 fast-abort machinery: kTagAbort broadcast with culprit
    attribution, bounded abort handshakes, rendezvous backoff healing a
    dropped HELLO, and benign delay injection with bit-correct results."""
    _build_and_run("chaos_selftest")


def test_chaos_selftest_under_tsan():
    """The abort paths run concurrently with executor lanes mid-collapse;
    TSan proves the collapse itself is race-free."""
    out = _build_and_run("tsan_chaos_selftest")
    assert "ThreadSanitizer" not in out, out


def test_chaos_selftest_under_asan():
    out = _build_and_run("asan_chaos_selftest")
    assert "AddressSanitizer" not in out, out


def test_chaos_selftest_under_ubsan():
    out = _build_and_run("ubsan_chaos_selftest")
    assert "runtime error" not in out, out


def test_flight_selftest():
    """Flight-recorder unit matrix: ring wraparound (oldest events evicted,
    dropped counter), slot rounding to powers of two, multi-thread
    interleave (global seq ordering across per-thread rings), JSON dump
    shape, dump-on-fatal-signal (forked child SIGABRTs and leaves a
    complete crash bundle), and test-reset isolation."""
    _build_and_run("flight_selftest")


def test_flight_selftest_under_tsan():
    """Record from many threads while a dumper snapshots the rings; TSan
    proves the relaxed-atomic slot protocol is data-race-free."""
    out = _build_and_run("tsan_flight_selftest")
    assert "ThreadSanitizer" not in out, out


def test_flight_selftest_under_asan():
    out = _build_and_run("asan_flight_selftest")
    assert "AddressSanitizer" not in out, out


def test_flight_selftest_under_ubsan():
    out = _build_and_run("ubsan_flight_selftest")
    assert "runtime error" not in out, out


def test_ctrl_soak_selftest():
    """np=256 over 16 fake hosts, ctrl_only controllers: coordinator
    inbound control messages per cycle must drop O(n) -> O(hosts)
    (255 flat vs 30 tree = 8.5x; the binary asserts the >= 8x bar and the
    exact tree topology count), with rendezvous over 8 sharded
    acceptors."""
    _build_and_run("ctrl_soak_selftest")


def test_ctrl_soak_under_tsan():
    """256 rank threads through leader aggregation, fan-down, and the
    ctrl counters concurrently; TSan proves the tree cycle race-free at
    scale."""
    out = _build_and_run("tsan_ctrl_soak_selftest")
    assert "ThreadSanitizer" not in out, out


def test_ctrl_soak_under_asan():
    out = _build_and_run("asan_ctrl_soak_selftest")
    assert "AddressSanitizer" not in out, out


def test_ctrl_soak_under_ubsan():
    out = _build_and_run("ubsan_ctrl_soak_selftest")
    assert "runtime error" not in out, out


# The child takes the first port its own pid's walk would be handed
# (selftest_port.h and runner/util.py:find_free_port start at the same
# point), listens on it, and becomes the selftest with the listener open.
_TAKE_FIRST_PORT_THEN_EXEC = """
import os, socket, sys
from horovod_tpu.runner import util
span = util._ephemeral_low() - util._PORT_FLOOR
port = util._PORT_FLOOR + ((os.getpid() * 2654435761 % 2**32) * span >> 32)
taken = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
taken.bind(("0.0.0.0", port))
taken.listen(1)
taken.set_inheritable(True)
sys.stderr.write("holding %d\\n" % port)
sys.stderr.flush()
os.execv(sys.argv[1], [sys.argv[1]])
"""


def test_ctrl_soak_passes_over_a_taken_port():
    """A rendezvous port that something else listens on cannot hang a phase
    (the gate of PR 49 lost 300 s to `bind(...) failed: 98`): the port the
    soak's first phase would have been handed is held by a listener, and the
    soak says so, goes on to the next and passes in seconds."""
    build = subprocess.run(["make", "ctrl_soak_selftest"], cwd=CPP_DIR,
                           capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stdout + build.stderr
    t0 = time.monotonic()
    run = subprocess.run(
        [sys.executable, "-c", _TAKE_FIRST_PORT_THEN_EXEC,
         os.path.join(CPP_DIR, "ctrl_soak_selftest")],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(CPP_DIR)))
    took = time.monotonic() - t0
    assert run.returncode == 0, (
        f"rc={run.returncode}\n{run.stdout}\n{run.stderr}")
    assert "PASS" in run.stdout
    held = run.stderr.split("holding ", 1)[1].split()[0]
    assert f"selftest port {held} is taken: trying the next" in run.stderr
    assert "bind(" not in run.stderr, run.stderr
    assert took < 60, f"{took:.0f} s"


def test_make_selftest_target():
    """`make selftest` builds and runs every selftest binary except the
    slow 3-rank TSan variants — the ASan/UBSan variants and the fast
    TSan ctrl-soak ARE included — in one shot: the entry point
    developers (and CI without pytest) use."""
    out = subprocess.run(["make", "selftest"], cwd=CPP_DIR,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS" in out.stdout
