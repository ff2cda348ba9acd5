"""Native sanitizer wiring (round 17, slow tier).

Rebuilds both native libraries under -fsanitize=address,undefined
(`make -C native asan`) and replays the fastpath fixture differential
plus a torn-frame / oversize-frame fuzz through them in a subprocess
with the asan runtime LD_PRELOADed (Python itself isn't instrumented,
so the runtime must be injected first).  The subprocess output is
parsed for sanitizer reports — a replay that "passes" while asan
printed an error must still fail here.

Slow-marked: the rebuild + instrumented replay costs ~a minute; the
lint pass (tests/test_tbcheck.py) always runs, this rides the slow
tier (pytest -m slow).
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")

pytestmark = pytest.mark.slow


def _asan_runtime() -> str | None:
    gcc = shutil.which("gcc") or shutil.which("cc")
    if gcc is None:
        return None
    try:
        path = subprocess.run(
            [gcc, "-print-file-name=libasan.so"],
            capture_output=True, text=True, timeout=30,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return None
    return path if path and os.path.exists(path) else None


def _sanitizer_report(text: str) -> bool:
    return ("AddressSanitizer" in text
            or "runtime error:" in text          # UBSan
            or "LeakSanitizer" in text)


@pytest.mark.skipif(shutil.which("make") is None, reason="no make")
@pytest.mark.skipif(_asan_runtime() is None, reason="no asan runtime")
def test_fastpath_replay_under_asan():
    build = subprocess.run(
        ["make", "-C", NATIVE, "asan"],
        capture_output=True, text=True, timeout=300,
    )
    assert build.returncode == 0, build.stderr[-2000:]
    for lib in ("libtb_runtime.so", "libtb_fastpath.so"):
        assert os.path.exists(os.path.join(NATIVE, "asan", lib))

    env = dict(os.environ)
    env.update(
        LD_PRELOAD=_asan_runtime(),
        # Python leaks by design; the replay hunts heap/stack/UB bugs
        # in OUR libraries, not CPython allocator noise.
        ASAN_OPTIONS="detect_leaks=0:abort_on_error=1",
        UBSAN_OPTIONS="print_stacktrace=1:halt_on_error=1",
        TB_NATIVE_SANITIZE="asan",
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "asan_replay.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    combined = proc.stdout + "\n" + proc.stderr
    assert proc.returncode == 0, combined[-4000:]
    assert "ASAN-REPLAY-OK" in proc.stdout, combined[-4000:]
    # Every replay stage actually ran.
    for marker in ("fixture differential ok", "finalize parity ok",
                   "torn-frame fuzz ok", "pipeline fuzz ok",
                   "oversize-frame fuzz ok"):
        assert marker in proc.stdout, combined[-4000:]
    assert not _sanitizer_report(combined), combined[-4000:]


def test_asan_build_failure_names_flavor(tmp_path, monkeypatch):
    """runtime/native.py build-failure forensics must name the
    sanitizer flavor attempted — a broken `make asan` must never read
    as a broken release build (fast: no real build, make is stubbed
    to fail)."""
    fake_bin = tmp_path / "bin"
    fake_bin.mkdir()
    fake_make = fake_bin / "make"
    fake_make.write_text("#!/bin/sh\necho boom >&2\nexit 3\n")
    fake_make.chmod(0o755)
    monkeypatch.setenv(
        "PATH", f"{fake_bin}:{os.environ.get('PATH', '')}"
    )
    monkeypatch.setenv("TB_NATIVE_SANITIZE", "asan")
    code = (
        "from tigerbeetle_tpu.runtime import native\n"
        "try:\n"
        "    native._run_make()\n"
        "    raise SystemExit('make did not fail')\n"
        "except native.NativeBuildError as exc:\n"
        "    assert str(exc) == native.build_error()\n"
        "print(native.build_error())"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, PATH=f"{fake_bin}:{os.environ['PATH']}",
                 TB_NATIVE_SANITIZE="asan", JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout.strip()
    assert "make -C native asan failed" in out, out
    assert "sanitizer=asan" in out, out
