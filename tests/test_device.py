"""tigerbeetle_tpu/device.py: device choice and the compile cache, and
the native build's refusal to fall back (runtime/native.py)."""

import json
import os
import subprocess
import sys

import pytest

from tigerbeetle_tpu import device

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env) -> subprocess.CompletedProcess:
    full = {**os.environ, "PYTHONPATH": _REPO, **env}
    for key, value in env.items():
        if value is None:
            full.pop(key)
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=_REPO, env=full,
    )


def test_describe_reports_what_jax_holds():
    import jax

    info = device.describe()
    assert info["platform"] == jax.devices()[0].platform == "cpu"
    assert info["kind"] == jax.devices()[0].device_kind
    assert info["count"] == len(jax.devices()) == len(info["ids"])
    json.dumps(info)


@pytest.mark.parametrize("value,asked", [
    ("cpu", True), ("CPU", True), ("tpu,cpu", True),
    ("", False), ("tpu", False), (None, False),
])
def test_cpu_requested_reads_only_jax_platforms(monkeypatch, value, asked):
    if value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", value)
    assert device.cpu_requested() is asked


def test_require_accelerator_refuses_a_cpu_backend_nobody_asked_for(monkeypatch):
    assert device.require_accelerator()["platform"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as info:
        device.require_accelerator()
    assert "no accelerator" in str(info.value)


def test_start_without_a_chip_and_without_jax_platforms_cpu_exits_nonzero(tmp_path):
    data = str(tmp_path / "0.tigerbeetle")
    cli = "from tigerbeetle_tpu.cli import main; main(%r)"
    fmt = _run(cli % ["format", "--cluster=1", data])
    assert fmt.returncode == 0, fmt.stderr
    proc = _run(
        cli % ["start", "--addresses=127.0.0.1:0", data],
        JAX_PLATFORMS=None, TPU_LOG_DIR="disabled",
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert "listening" not in proc.stdout


def test_compile_cache_is_the_env_directory_where_set(tmp_path):
    want = str(tmp_path / "cache")
    proc = _run(
        "from tigerbeetle_tpu import device; import jax;"
        "print(device.enable_compile_cache());"
        "print(jax.config.jax_compilation_cache_dir)",
        JAX_COMPILATION_CACHE_DIR=want,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [want, want]
    assert os.path.isdir(want)


def test_compile_cache_is_one_fixed_ignored_path_in_the_checkout():
    proc = _run(
        "from tigerbeetle_tpu import device; import jax;"
        "print(device.enable_compile_cache());"
        "print(jax.config.jax_compilation_cache_dir)",
        JAX_COMPILATION_CACHE_DIR=None,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [device.COMPILE_CACHE_DIR] * 2
    assert device.COMPILE_CACHE_DIR == os.path.join(_REPO, ".jax_cache")
    ignored = open(os.path.join(_REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_rejected_directory_is_an_error(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    proc = _run(
        "from tigerbeetle_tpu import device; device.enable_compile_cache()",
        JAX_COMPILATION_CACHE_DIR=str(blocker / "cache"),
    )
    assert proc.returncode != 0


def test_importing_the_kernels_sets_no_cache_directory():
    proc = _run(
        "import jax; from tigerbeetle_tpu.state_machine import device_kernels;"
        "print(jax.config.jax_compilation_cache_dir)",
        JAX_COMPILATION_CACHE_DIR=None,
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "None"


def test_failed_native_build_is_an_error_on_every_load(tmp_path):
    """No prebuilt library and no pure-Python arm unless asked for."""
    fake_make = tmp_path / "make"
    fake_make.write_text("#!/bin/sh\necho boom >&2\nexit 3\n")
    fake_make.chmod(0o755)
    code = (
        "from tigerbeetle_tpu.runtime import fastpath, native\n"
        "for probe in (fastpath.available, native.native_available,\n"
        "              fastpath.available):\n"
        "    try:\n"
        "        probe()\n"
        "        raise SystemExit('load did not fail')\n"
        "    except native.NativeBuildError as exc:\n"
        "        assert 'boom' in str(exc), exc\n"
        "print('RAISED', native.build_error())\n"
    )
    proc = _run(code, PATH=f"{tmp_path}:{os.environ['PATH']}")
    assert proc.returncode == 0, proc.stderr
    assert "RAISED make -C native all failed" in proc.stdout
    # The pure-Python arm is there for whoever asks for it by name.
    asked = _run(
        "from tigerbeetle_tpu.runtime import fastpath;"
        "print(fastpath.available())",
        PATH=f"{tmp_path}:{os.environ['PATH']}", TB_FASTPATH_DISABLE="1",
    )
    assert asked.returncode == 0 and asked.stdout.strip() == "False"


def test_no_binary_is_tracked():
    tracked = subprocess.run(
        ["git", "ls-files", "native"], capture_output=True, text=True,
        cwd=_REPO,
    )
    if tracked.returncode != 0:
        pytest.skip("not a git checkout")
    assert not [f for f in tracked.stdout.split() if f.endswith(".so")]
