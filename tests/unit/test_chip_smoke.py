"""``chip_smoke.py`` off the chip: the rehearsal passes and never claims a
TPU; the real form refuses to run without one."""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(*args):
    # the child gets the CPU from JAX_PLATFORMS alone (conftest set it); the
    # 8-device XLA flag is dropped so the script sees the device count it asks for
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600,
    )


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_passes_and_claims_no_tpu(chips):
    proc = _run("--rehearse", "--chips", str(chips))
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": chips}
    assert last["ok"] is False and last["rehearsal"] == "passed"
    phases = [json.loads(line)["phase"] for line in proc.stdout.splitlines()[:-1] if line.startswith("{")]
    expected = ["trainer", "server"] if chips == 1 else ["trainer_sharded", "server_sharded"]
    assert phases == ["start", *expected, "done"]


def test_without_a_chip_it_fails_and_prints_no_result():
    proc = _run()
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a tpu backend" in proc.stderr
