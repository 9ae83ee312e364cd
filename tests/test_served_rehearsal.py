"""One rehearsal of the served cell as processes: `benchmark/run.py
--workload demo4.served-open --tiny` starts node 0 (device-backed, here on
XLA:CPU), three `babble_tpu run` validators and the client, measures a 3 s
window and checks it. The run has a time limit of its own, and no process
it started outlives it, whether it ends or is killed."""

import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMAND = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", "demo4.served-open", "--seconds", "3", "--trace", "0",
           "--tiny"]
LIMIT_S = 420
# exchanges that timed out on a loaded test machine say nothing of the harness
TIMING = {"sync_errors"}


def start(seed: int, tmp_path, **popen) -> subprocess.Popen:
    # data directories under the test's own: a killed run cannot remove its
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TMPDIR": str(tmp_path)}
    env.pop("XLA_FLAGS", None)  # one CPU device, as the cell has one chip
    return subprocess.Popen(
        [*COMMAND, "--seed", str(seed)], cwd=ROOT, env=env,
        start_new_session=True, **popen)


def in_group(pgid: int) -> list:
    """(pid, command line) of the live processes of one process group."""
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            if int(fields[2]) != pgid or fields[0] == "Z":
                continue
            with open(f"/proc/{pid}/cmdline") as f:
                out.append((int(pid), f.read().replace("\0", " ").strip()))
        except (OSError, IndexError):
            continue  # it ended while we looked
    return out


def gone(pgid: int, within: float) -> list:
    """What is left of the group once it has had `within` seconds to end."""
    deadline = time.monotonic() + within
    while in_group(pgid) and time.monotonic() < deadline:
        time.sleep(0.1)
    return in_group(pgid)


def test_rehearsal_as_processes_is_correct_and_leaves_nothing(tmp_path):
    proc = start(2_147_483_659, tmp_path, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                 text=True)
    try:
        out, err = proc.communicate(timeout=LIMIT_S)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        raise
    assert proc.returncode == 0, err[-2000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["tiny"] is True
    bad = {k: c for k, c in line["compared"].items()
           if c["value"] > c["limit"] and k not in TIMING}
    assert not bad, bad
    c = line["counters"]
    assert c["validators"] == 4 and c["syncs"] > 0
    assert c["syncs_served"] == c["syncs"]
    assert c["tx_offered"] > 0 and c["tx_committed"] > 0
    assert c["blocks_compared"] > 0
    for name in ("committed_tx_per_s", "commit_latency_p50_ms",
                 "commit_latency_p95_ms", "setup_s"):
        assert line["metrics"][name]["value"] > 0
    assert gone(proc.pid, 5.0) == []
    assert os.listdir(tmp_path) == []  # and its data directories are removed


def test_killed_rehearsal_leaves_no_process(tmp_path):
    proc = start(7, tmp_path, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        # the harness, three validators and the client
        deadline = time.monotonic() + LIMIT_S
        while len([c for _, c in in_group(proc.pid)
                   if "babble_tpu run" in c or "served_client" in c]) < 4:
            assert proc.poll() is None, "the run ended before its cluster was up"
            assert time.monotonic() < deadline
            time.sleep(0.2)
        os.kill(proc.pid, signal.SIGKILL)  # the harness alone
        proc.wait()
        assert gone(proc.pid, 10.0) == []
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
