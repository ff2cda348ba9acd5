"""Component fuzzer registry smoke tier (reference:
src/fuzz_tests.zig:24-42 — every component fuzzer runs briefly in CI;
long soaks use the same entry point with more rounds)."""

import subprocess
import sys

import pytest

from tigerbeetle_tpu.testing.fuzz import FUZZERS, SMOKE_ROUNDS


@pytest.mark.parametrize("name", sorted(FUZZERS))
@pytest.mark.parametrize("seed", [1, 77])
def test_fuzz_smoke(name, seed):
    FUZZERS[name](seed, SMOKE_ROUNDS)


def test_fuzz_cli_entry_point():
    proc = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu.testing.fuzz", "ewah",
         "--seed", "3", "--rounds", "20"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    assert "fuzz ewah: ok" in proc.stdout


def test_soak_cli_entry_point():
    """The CFO-fleet analog (testing/soak.py) runs end-to-end: a tiny
    all-kinds wave, JSONL records, zero failures expected."""
    import json
    import os
    import tempfile

    out = os.path.join(tempfile.mkdtemp(), "soak.jsonl")
    proc = subprocess.run(
        [sys.executable, "-m", "tigerbeetle_tpu.testing.soak", "all",
         "--n", "2", "--seed-base", "5", "--out", out],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    records = [json.loads(line) for line in open(out)]
    assert len(records) == 2 and all(r["ok"] for r in records)
