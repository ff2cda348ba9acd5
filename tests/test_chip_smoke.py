"""chip_smoke.py, rehearsed on the CPU backend, and its last line guarded.

The driver runs `python3 chip_smoke.py` on a one-chip machine and reads
the last line of its stdout; a line of any other shape loses the PR.
Here the same script runs at `--size tiny` against the CPU backend
(the servers it starts inherit the suite's JAX_PLATFORMS=cpu and
TB_DEV_B=512): every phase and every oracle comparison must pass, and
the run must still FAIL — because the platform is not `tpu` — without
printing a result line.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402


def test_success_line_has_exactly_the_contract_keys():
    line = chip_smoke.success_line({
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "ids": [0], "engine": "device", "state": "healthy",
        "compile": {"seconds": 1.0},
    })
    assert "\n" not in line
    obj = json.loads(line)
    assert set(obj) == {"ok", "device"}
    assert obj["ok"] is True
    assert set(obj["device"]) == {"platform", "kind", "count"}
    assert obj["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }


def test_rehearsal_on_cpu_passes_every_comparison_and_fails_on_platform():
    # --no-rebuild: other xdist workers have the native libraries loaded.
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"),
         "--size", "tiny", "--no-rebuild"],
        capture_output=True, text=True, timeout=900, cwd=_REPO,
    )
    out = proc.stdout
    context = f"stdout:\n{out[-6000:]}\nstderr:\n{proc.stderr[-2000:]}"
    lines = out.splitlines()
    # Every phase ran and every reply equalled the oracle's.
    for server in ("device", "host"):
        done = [ln for ln in lines if ln.startswith(f"{server}: ")
                and "replies compared with the oracle" in ln]
        assert len(done) == 1 and done[0].endswith(", 0 differ"), context
        assert int(done[0].split()[1]) > 900, context
    for kind in ("plain", "linked", "two_phase", "failing"):
        assert any(ln.startswith(f"device: {kind}: ") and "(100.00%)" in ln
                   for ln in lines), context
    assert sum("checkpoint(s) ran" in ln for ln in lines) == 2, context
    assert any("restart and read-back" in ln for ln in lines), context
    # The only thing wrong with the run is the platform.
    fails = [ln for ln in lines if ln.startswith("FAIL: ")]
    assert fails, context
    assert all(ln.endswith("platform is 'cpu', not 'tpu'") for ln in fails), context
    assert proc.returncode == 1, context
    # No result line: the last line says why, and nothing follows it.
    assert out.endswith("\n") and lines[-1].startswith("chip_smoke: FAILED ("), context
    for ln in lines:
        assert not ln.lstrip().startswith('{"ok"'), context
    try:
        json.loads(lines[-1])
    except ValueError:
        pass
    else:
        raise AssertionError("the last line of a failed run parsed as JSON")
