"""No silent CPU: the platform check of a "tpu" Core, the placed compile
cache (babble_tpu/tpu/runtime.py) and chip_smoke.py's refusal to run
without a chip.

This file sorts after test_tpu_differential.py on purpose: the tier-1
command counts dots inside a time limit, so new subprocess tests go last.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from babble_tpu.cli import main as cli_main
from babble_tpu.crypto import generate_key, pub_key_bytes
from babble_tpu.hashgraph import InmemStore
from babble_tpu.node import Core
from babble_tpu.peers import Peer, Peers
from babble_tpu.tpu import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_python(args, env_overrides, tmp_path, timeout=600):
    """A subprocess without conftest's pins, except what the test sets."""
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", runtime.CACHE_ENV)
    }
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=timeout,
    )


def one_core(backend):
    key = generate_key()
    peers = Peers.from_slice(
        [Peer(pub_key_hex="0x" + pub_key_bytes(key).hex().upper())]
    )
    return Core(0, key, peers, InmemStore(peers, 100),
                consensus_backend=backend)


def test_tpu_core_names_its_platform_under_the_test_pin():
    core = one_core("tpu")
    assert core.device["platform"] == "cpu"  # conftest's pin
    assert core.device_fields() == {
        "device_platform": "cpu",
        "device_kind": core.device["kind"],
        "device_count": core.device["count"],
    }
    assert core.health_digest_body()["device_platform"] == "cpu"
    cpu_core = one_core("cpu")
    assert cpu_core.device is None and cpu_core.device_fields() == {}
    assert "device_platform" not in cpu_core.health_digest_body()


def test_tpu_core_refuses_a_cpu_nobody_pinned(monkeypatch):
    monkeypatch.setattr(runtime, "cpu_pinned", lambda: False)
    with pytest.raises(RuntimeError, match="found platform 'cpu', not a TPU"):
        one_core("tpu")
    one_core("cpu")  # the host backend never asks


def test_cli_run_exits_1_without_the_chip(monkeypatch, tmp_path, caplog):
    monkeypatch.setattr(runtime, "cpu_pinned", lambda: False)
    assert cli_main(["keygen", "--datadir", str(tmp_path)]) == 0
    from babble_tpu.crypto import PemKey

    pub = pub_key_bytes(PemKey(str(tmp_path)).read_key())
    (tmp_path / "peers.json").write_text(json.dumps([
        {"NetAddr": "127.0.0.1:0", "PubKeyHex": "0x" + pub.hex().upper()},
    ]))
    with caplog.at_level("ERROR", logger="babble"):
        rc = cli_main([
            "run", "--datadir", str(tmp_path), "--listen", "127.0.0.1:0",
            "--standalone", "--consensus-backend", "tpu",
        ])
    assert rc == 1
    assert "Cannot initialize engine" in caplog.text
    assert "not a TPU" in caplog.text


def test_first_attach_failure_is_counted_and_logged_once(caplog):
    from babble_tpu.tpu.grid import GridUnsupported

    core = one_core("tpu")
    with caplog.at_level("WARNING"):
        core._note_attach_failure("live", GridUnsupported("routine"))
        assert core.device_attach_failures == 0
        core._note_attach_failure("live", MemoryError("RESOURCE_EXHAUSTED"))
        core._note_attach_failure("live", MemoryError("RESOURCE_EXHAUSTED"))
    assert core.device_attach_failures == 2
    assert caplog.text.count("live rung failed to attach") == 1


CACHE_PROBE = """
    import os, sys
    sys.path.insert(0, {repo!r})
    import jax
    from jax._src import xla_bridge
    updates = []
    real_update = jax.config.update
    def spy(name, value):
        updates.append(name)
        return real_update(name, value)
    jax.config.update = spy
    from babble_tpu.tpu.runtime import enable_compile_cache
    path = enable_compile_cache()
    assert not xla_bridge.backends_are_initialized(), "backend initialized"
    assert jax.config.jax_compilation_cache_dir == path, (
        jax.config.jax_compilation_cache_dir, path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    print(path, "jax_compilation_cache_dir" in updates)
"""


def test_compile_cache_leaves_an_external_directory_alone(tmp_path):
    outside = str(tmp_path / "placed")
    proc = run_python(
        ["-c", textwrap.dedent(CACHE_PROBE.format(repo=REPO))],
        {runtime.CACHE_ENV: outside}, tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [outside, "False"]


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    proc = run_python(
        ["-c", textwrap.dedent(CACHE_PROBE.format(repo=REPO))], {}, tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [os.path.join(REPO, ".jax_cache"), "True"]


def test_chip_smoke_refuses_to_run_on_the_cpu(tmp_path):
    proc = run_python(
        [os.path.join(REPO, "chip_smoke.py")],
        {"JAX_PLATFORMS": "cpu", runtime.CACHE_ENV: str(tmp_path / "jc")},
        tmp_path,
    )
    assert proc.returncode == 3, proc.stderr
    assert proc.stdout.strip() == ""  # no result line
    assert "phase device FAILED" in proc.stderr


@pytest.mark.slow
def test_chip_smoke_tiny_dry_run(tmp_path):
    """`make smoke-tiny`: every phase at toy sizes on XLA:CPU (minutes of
    XLA:CPU compiles — too slow for the tier-1 budget)."""
    proc = run_python(
        [os.path.join(REPO, "chip_smoke.py"), "--tiny"],
        {"JAX_PLATFORMS": "cpu", runtime.CACHE_ENV: str(tmp_path / "jc")},
        tmp_path, timeout=1800,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    report_line, verdict_line = proc.stdout.strip().splitlines()[-2:]
    # the last line is the verdict and nothing else: the driver reads it
    verdict = json.loads(verdict_line)
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    doc = json.loads(report_line)
    assert doc["tiny"] is True and doc["ok"] is False
    assert "failed" not in doc
    assert set(doc["phases"]) == {
        "device", "served4", "replay64", "cold64", "width1024", "mesh4",
    }
    assert doc["phases"]["mesh4"] == "skipped: 1 device"
    assert doc["compile_cache_dir"] == str(tmp_path / "jc")
