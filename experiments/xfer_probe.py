"""Characterize the host<->TPU link: h2d/d2h latency vs size, async
transfer overlap, and compute-only time for the candidate kernel."""
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

dev = jax.devices()[0]
print("device:", dev, file=sys.stderr)


def timeit(fn, n=10):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e3


# --- h2d by size (one array per transfer)
for nbytes in (4096, 32 << 10, 256 << 10, 1 << 20, 8 << 20):
    a = np.zeros(nbytes // 8, np.uint64)
    ms = timeit(lambda: jax.block_until_ready(jax.device_put(a, dev)))
    print(f"h2d {nbytes>>10:6d} KiB: {ms:8.2f} ms")

# --- d2h by size
for nbytes in (4096, 32 << 10, 256 << 10, 1 << 20, 8 << 20):
    a = jax.block_until_ready(
        jax.device_put(np.zeros(nbytes // 8, np.uint64), dev)
    )
    ms = timeit(lambda: np.asarray(a))
    print(f"d2h {nbytes>>10:6d} KiB: {ms:8.2f} ms")

# --- d2h with async start then fetch
a = jax.block_until_ready(jax.device_put(np.zeros(4096, np.uint64), dev))
b = jax.block_until_ready(jax.device_put(np.zeros(4096, np.uint64), dev))


def async_pair():
    a.copy_to_host_async()
    b.copy_to_host_async()
    np.asarray(a)
    np.asarray(b)


ms = timeit(async_pair)
print(f"d2h 2x32KiB async-overlap: {ms:8.2f} ms (vs 2x sequential)")

# --- many small d2h in flight at once
arrs = [
    jax.block_until_ready(jax.device_put(np.zeros(4096, np.uint64), dev))
    for _ in range(16)
]


def async_16():
    for x in arrs:
        x.copy_to_host_async()
    for x in arrs:
        np.asarray(x)


ms = timeit(async_16, n=5)
print(f"d2h 16x32KiB async-overlap: {ms:8.2f} ms total -> {ms/16:.2f} ms each")

# --- dispatch+compute only (no fetch): trivial kernel chain
@jax.jit
def bump(t):
    return t + jnp.uint64(1)

t = jax.block_until_ready(jax.device_put(np.zeros((4096, 8), np.uint64), dev))


def chain():
    global t
    for _ in range(10):
        t = bump(t)
    jax.block_until_ready(t)


ms = timeit(chain, n=5)
print(f"10 chained trivial dispatches: {ms:8.2f} ms -> {ms/10:.2f} ms/dispatch")
