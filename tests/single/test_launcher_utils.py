"""Launcher unit tests (reference shape: test/single/test_run.py — arg
parsing, host-slot math, rank assignment, secret HMAC)."""

import pytest

from horovod_tpu.runner.launch import parse_args, _tuning_env
from horovod_tpu.runner.util import (
    parse_hosts, assign_ranks, host_hash, make_secret, sign_message,
    verify_message,
)


def test_parse_hosts():
    hs = parse_hosts("a:2, b:4,c")
    assert [(h.hostname, h.slots) for h in hs] == [("a", 2), ("b", 4),
                                                   ("c", 1)]


def test_assign_ranks_block_layout():
    hs = parse_hosts("a:2,b:2")
    a = assign_ranks(hs, 3)
    assert [x["rank"] for x in a] == [0, 1, 2]
    assert [x["hostname"] for x in a] == ["a", "a", "b"]
    assert [x["local_rank"] for x in a] == [0, 1, 0]
    assert a[0]["local_size"] == 2 and a[2]["local_size"] == 1
    assert a[0]["cross_rank"] == 0 and a[2]["cross_rank"] == 1
    assert a[0]["cross_size"] == 2


def test_assign_ranks_overflow():
    with pytest.raises(ValueError):
        assign_ranks(parse_hosts("a:1"), 2)


def test_parse_args_and_tuning_env():
    args = parse_args([
        "-np", "4", "-H", "x:4", "--fusion-threshold-mb", "32",
        "--cycle-time-ms", "2.5", "--cache-capacity", "512", "--autotune",
        "--timeline-filename", "/tmp/tl.json", "--timeline-mark-cycles",
        "--stall-check-warning-time-seconds", "30",
        "--log-level", "debug", "python", "train.py"])
    env = _tuning_env(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert env["HOROVOD_CYCLE_TIME"] == "2.5"
    assert env["HOROVOD_CACHE_CAPACITY"] == "512"
    assert env["HOROVOD_AUTOTUNE"] == "1"
    assert env["HOROVOD_TIMELINE"] == "/tmp/tl.json"
    assert env["HOROVOD_TIMELINE_MARK_CYCLES"] == "1"
    assert env["HOROVOD_STALL_CHECK_TIME_SECONDS"] == "30.0"
    assert env["HOROVOD_LOG_LEVEL"] == "debug"
    assert args.command == ["python", "train.py"]


def test_secret_hmac_roundtrip():
    s = make_secret()
    assert len(s) == 32
    sig = sign_message(s, "payload")
    assert verify_message(s, "payload", sig)
    assert not verify_message(s, "payload2", sig)
    assert not verify_message(make_secret(), "payload", sig)


def test_signed_wire_messages():
    from horovod_tpu.elastic.client import signed_dumps, verified_loads

    s = make_secret()
    line = signed_dumps({"type": "ready", "n": 1}, s)
    assert verified_loads(line, s) == {"type": "ready", "n": 1}
    assert verified_loads(line, make_secret()) is None   # wrong key
    assert verified_loads('{"type":"ready"}', s) is None  # unsigned
    # no secret configured -> plain JSON passes through
    assert verified_loads('{"type":"ready"}', None) == {"type": "ready"}


def test_host_hash_stable():
    assert host_hash() == host_hash()
    assert len(host_hash()) == 16


def _ephemeral_range():
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            low, high = (int(x) for x in f.read().split())
        return low, high
    except OSError:
        return 32768, 60999


def test_find_free_port_lies_outside_the_ephemeral_range_and_binds():
    """A port the kernel cannot hand to a ``bind(0)`` or an outgoing
    connection between the probe and the listener's start."""
    import socket

    from horovod_tpu.runner.util import find_free_port

    low, high = _ephemeral_range()
    for addr in ("127.0.0.1", "0.0.0.0"):
        port = find_free_port(addr)
        assert 1024 <= port < low or high < port <= 65535, (port, low, high)
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.bind((addr, port))
            s.listen(1)


def test_find_free_port_skips_held_ports_and_never_repeats():
    """Twenty sockets held on ``bind(0)`` (what five other test workers'
    launchers and coordinators do all the time), a listener on the very
    port the next call would try first, and the port after it handed to
    another launcher that has not started its listener yet: successive
    calls give distinct ports, none of them held."""
    import os
    import socket
    import subprocess
    import sys

    from horovod_tpu.runner import util

    held, other = [], None
    try:
        for _ in range(20):
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.bind(("127.0.0.1", 0))
            held.append(s)
        first = util.find_free_port()
        span = util._ephemeral_low() - util._PORT_FLOOR
        nxt, after = (util._PORT_FLOOR + (first - util._PORT_FLOOR
                                          + i * util._PORT_STRIDE) % span
                      for i in (1, 2))
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        held.append(listener)
        listener.bind(("127.0.0.1", nxt))
        listener.listen(1)
        # Another process whose own walk stands at ``after``.
        other = subprocess.Popen(
            [sys.executable, "-c",
             "import os, sys\n"
             "from horovod_tpu.runner import util\n"
             f"util._port_cursor[os.getpid()] = {after - util._PORT_FLOOR}\n"
             "print(util.find_free_port(), flush=True)\n"
             "sys.stdin.read()\n"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, PYTHONPATH=os.path.dirname(os.path.dirname(
                os.path.dirname(util.__file__)))))
        assert int(other.stdout.readline()) == after
        taken = {s.getsockname()[1] for s in held} | {after}
        ports = [first] + [util.find_free_port() for _ in range(8)]
        assert len(set(ports)) == len(ports), ports
        assert not taken & set(ports), (sorted(taken), ports)
    finally:
        if other is not None:
            other.communicate("")
        for s in held:
            s.close()


def test_config_file_yaml(tmp_path):
    """--config-file fills launcher params; explicit CLI flags win
    (reference: horovodrun --config-file)."""
    import textwrap

    from horovod_tpu.runner.launch import parse_args

    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(textwrap.dedent("""
        num-proc: 4
        fusion-threshold-mb: 32
        cycle-time-ms: 2.5
        timeline:
            filename: /tmp/tl.json
            mark-cycles: true
        autotune:
            enabled: true
            log-file: /tmp/at.csv
        stall-check:
            warning-time-seconds: 12
    """))
    args = parse_args(["--config-file", str(cfg), "python", "t.py"])
    assert args.num_proc == 4
    assert args.fusion_threshold_mb == 32
    assert args.cycle_time_ms == 2.5
    assert args.timeline_filename == "/tmp/tl.json"
    assert args.timeline_mark_cycles is True
    assert args.autotune is True
    assert args.autotune_log_file == "/tmp/at.csv"
    assert args.stall_check_warning_time_seconds == 12

    # CLI beats file
    args = parse_args(["--config-file", str(cfg), "-np", "2",
                       "--cycle-time-ms", "9", "python", "t.py"])
    assert args.num_proc == 2
    assert args.cycle_time_ms == 9.0
    assert args.fusion_threshold_mb == 32  # still from file


def test_launcher_pins_one_chip_per_colocated_worker(tmp_path):
    """Multi-worker-per-host launches must pin each worker to its own TPU
    chip (libtpu is single-owner per chip); single-worker hosts and user
    overrides are left alone."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        "print('PIN', os.environ.get('HOROVOD_LOCAL_RANK'),\n"
        "      os.environ.get('TPU_VISIBLE_CHIPS'),\n"
        "      os.environ.get('TPU_CHIPS_PER_PROCESS_BOUNDS'))\n")
    env = dict(os.environ)
    env.pop("TPU_VISIBLE_CHIPS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def pins_from(stdout):
        # Worker lines stream as "[rank]<stdout>: PIN <lr> <chips> <bounds>".
        return sorted(ln.split("PIN", 1)[1].split()
                      for ln in stdout.splitlines() if "PIN" in ln)

    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "-H", "localhost:2", sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    pins = pins_from(proc.stdout)
    assert [p[1] for p in pins] == ["0", "1"], proc.stdout
    assert all(p[2] == "1,1,1" for p in pins), proc.stdout

    # np=1: no pinning injected.
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "1",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert pins_from(proc.stdout)[0][1] == "None", proc.stdout

    # An inherited global pin would hand every co-located worker the same
    # chip: it must be overridden per worker (with a warning).
    env_pinned = dict(env)
    env_pinned["TPU_VISIBLE_CHIPS"] = "0"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "-H", "localhost:2", sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env_pinned)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert [p[1] for p in pins_from(proc.stdout)] == ["0", "1"], proc.stdout
    assert "overriding inherited TPU chip pin" in (proc.stderr + proc.stdout)


def test_pin_tpu_chip_joins_four_colocated_workers_into_one_runtime():
    """--jax-distributed with four workers on one TPU host: besides its own
    chip each worker gets the process grid, every process's address, its own
    port and its task id — what libtpu reads to form ONE four-device runtime
    instead of four one-chip worlds."""
    from horovod_tpu.runner.util import pin_tpu_chip

    ports = [9001, 9002, 9003, 9004]
    envs = []
    for lr in range(4):
        env = {}
        pin_tpu_chip(env, lr, 4, process_ports=ports)
        envs.append(env)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
    for e in envs:
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert e["TPU_PROCESS_ADDRESSES"] == (
            "localhost:9001,localhost:9002,localhost:9003,localhost:9004")


def test_pin_tpu_chip_without_ports_keeps_one_chip_worlds():
    from horovod_tpu.runner.util import pin_tpu_chip

    env = {}
    pin_tpu_chip(env, 1, 4)
    assert env == {"TPU_VISIBLE_CHIPS": "1",
                   "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1"}
    lone = {}
    pin_tpu_chip(lone, 0, 1, process_ports=[9001])
    assert lone == {}                       # a lone worker keeps every chip


def test_pin_tpu_chip_refuses_a_grid_it_does_not_know():
    from horovod_tpu.runner.util import pin_tpu_chip

    with pytest.raises(ValueError, match="no known TPU process grid for 3"):
        pin_tpu_chip({}, 0, 3, process_ports=[1, 2, 3])
    with pytest.raises(ValueError, match="need 4 process ports"):
        pin_tpu_chip({}, 0, 4, process_ports=[1, 2])
