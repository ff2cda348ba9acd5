"""Which device this process holds, and where its compiles are kept.

One installation is supported: stock JAX, which takes the TPU when the
machine has one and the CPU backend otherwise.  ``JAX_PLATFORMS=cpu``
is the one way to ask for the CPU (tests set it, with the virtual
8-device mesh); a serving process that lands on the CPU backend
without having been told to is an error, not a slower server.

A chip belongs to one process at a time, so every process that serves
says what it holds: ``describe()`` feeds the server's start-up line
and its stats scrape, and a launcher that must not touch JAX (a
benchmark parent, ``chip_smoke.py``) reads it from there.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Fixed and inside the checkout (git-ignored): the directory's path is
# part of XLA's cache key, so a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_cache_dir: str | None = None
# Process-wide compile accounting, fed by JAX's own monitoring events:
# seconds spent in backend compiles (a cache hit's retrieval included)
# and how many of them the persistent cache answered.
_compile = {"seconds": 0.0, "count": 0, "cache_hits": 0, "cache_misses": 0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["seconds"] += duration
        _compile["count"] += 1


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compile["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _compile["cache_misses"] += 1


def compile_stats() -> dict:
    return {
        "dir": _cache_dir, **_compile,
        "seconds": round(_compile["seconds"], 3),
    }


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the
    directory in use.  Called by every process that compiles, before
    its first compile (server start-up, the in-process benchmark, the
    test suite's conftest).  Where ``JAX_COMPILATION_CACHE_DIR`` is set
    JAX reads it itself and nothing is set in code; otherwise the
    cache lives at ``COMPILE_CACHE_DIR``.  A directory that cannot be
    used is an error: a cold compile of every kernel on every start is
    not a default anyone chose."""
    global _cache_dir
    if _cache_dir is not None:
        return _cache_dir
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK | os.X_OK):
        raise RuntimeError(f"compile cache directory {path!r} is not writable")
    _cache_dir = path
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    return path


def describe() -> dict:
    """What JAX holds in this process, as JAX reports it."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "ids": [int(d.id) for d in devices],
        # JAX numbers the devices a process sees from 0; on a host
        # whose launcher gave each process its own chip, the chip is
        # named by the runtime setting that restricted it.
        "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
    }


def cpu_requested() -> bool:
    return "cpu" in os.environ.get("JAX_PLATFORMS", "").lower().split(",")


def require_accelerator() -> dict:
    """``describe()``, or SystemExit when JAX landed on the CPU backend
    although ``JAX_PLATFORMS`` did not ask for it."""
    info = describe()
    if info["platform"] == "cpu" and not cpu_requested():
        raise SystemExit(
            "error: JAX found no accelerator and fell to the CPU backend "
            "(JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); set "
            "JAX_PLATFORMS=cpu to serve from the CPU backend on purpose, "
            "or pass --cpu for the dict-backed CPU engine"
        )
    return info


if __name__ == "__main__":
    # `python -m tigerbeetle_tpu.device`: what an unrestricted process
    # on this machine would hold, as one JSON line.
    import json

    # tbcheck: allow(no-print): this entry point's stdout IS its
    # interface (one JSON line for a launcher that stays off JAX).
    print(json.dumps(describe()))
