"""chip_smoke.py's two CPU-visible contracts: the explicit --dry-run-cpu
mode runs the whole command end to end at tiny shapes (so the command is
debugged before chip time is spent on it) and says so in its record with
no seconds in it; without the flag a missing chip is a FAILURE that prints
no result."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # the script asks for its own devices
    return subprocess.run([sys.executable, SMOKE, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=180)


def _has_seconds(obj) -> bool:
    if isinstance(obj, dict):
        return any("second" in k or _has_seconds(v) for k, v in obj.items())
    if isinstance(obj, list):
        return any(_has_seconds(v) for v in obj)
    return False


def test_dry_run_cpu_end_to_end():
    # --chips 4 (four virtual CPU devices) so leg B is debugged here too:
    # ONE subprocess covers every leg the chip command can run
    r = _run("--dry-run-cpu", "--chips", "4")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(l) for l in r.stdout.splitlines() if l.startswith("{")]
    final = lines[-1]
    assert final == {"ok": True, "dry_run": True,
                     "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
    assert [l["leg"] for l in lines if "leg" in l] == ["A", "B"]
    summary = lines[-2]
    assert summary["dry_run"] is True and summary["failures"] == []
    assert summary["legs"] == ["A", "B"]
    leg_b = lines[1]
    assert leg_b["default"]["compute_dtype"] == "bfloat16"
    assert leg_b["parity"]["compute_dtype"] == "float32"
    # a dry run's times are CPU times: none may be printed
    assert not any(_has_seconds(l) for l in lines)


def test_no_chip_no_result():
    r = _run()
    assert r.returncode != 0
    assert r.stdout.strip() == ""           # no result of any kind
    assert "need 'tpu'" in r.stderr
