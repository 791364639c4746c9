"""chip_smoke.py's contract, as far as a machine without a chip can
show it: without an accelerator it fails and names the platform it
found, alone in a directory it fails, and the explicit rehearsal —
the only way it runs without a TPU — passes and is labelled "cpu".
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUMMARY = "chip_smoke summary "  # the long line before the verdict


def _run(args: list[str], cwd: str = REPO, timeout: float = 600.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # one CPU device: with several, whether a batch reaches
    # mesh_min_items at toy sizes is luck, and the run must not be
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)  # conftest turns it off
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        line = line.removeprefix(SUMMARY)
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def _verdict_is_exact(line: str, platform: str) -> dict:
    """The last line: exactly "ok" and "device", the device exactly
    "platform", "kind" and "count" — whoever runs the smoke refuses any
    other key."""
    v = json.loads(line)
    assert set(v) == {"ok", "device"}, line
    assert v["ok"] is True
    assert set(v["device"]) == {"platform", "kind", "count"}, line
    assert v["device"]["platform"] == platform
    assert isinstance(v["device"]["kind"], str)
    assert type(v["device"]["count"]) is int and v["device"]["count"] >= 1
    return v


def test_verdict_line_has_exactly_the_contract_keys():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.verdict_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    v = _verdict_is_exact(line, "tpu")
    assert v["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1}


def test_without_a_chip_it_fails_and_names_the_platform():
    r = _run([])
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr, r.stderr[-800:]
    assert _result_lines(r.stdout) == []  # no result on stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run([], cwd=str(tmp_path))
    assert r.returncode != 0
    assert "garage_tpu" in r.stderr
    assert _result_lines(r.stdout) == []


@pytest.mark.slow  # ~1 min of six forked servers: kept out of tier-1's
# 870 s so that the suite keeps its margin; run it with `-m slow`
def test_rehearsal_passes_and_is_labelled_cpu():
    r = _run(["rehearse"])
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    lines = r.stdout.strip().splitlines()
    verdict = _verdict_is_exact(lines[-1], "cpu")
    assert lines[-2].startswith(SUMMARY), lines[-2][:200]
    last = json.loads(lines[-2][len(SUMMARY):])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"] == verdict["device"]
    assert last["claim"] is None
    assert last["native"]["built"], last["native"]
    assert last["kernels"]["failed"] == []
    served = last["served"]
    assert all(served["checks"].values()), served["checks"]
    for op in ("encode_put", "hash_md5", "sha256", "decode", "parity_check"):
        assert served["device_items"].get(op, 0) > 0, served["device_items"]
    assert served["counters"]["feeder_host_reruns"] == 0
    assert served["failed_requests"] == 0
