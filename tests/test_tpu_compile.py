"""The main path's kernels, compiled for a described v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described and not attached, so what it would refuse
on the chip (a program that does not fit, an op it cannot lower) fails
here first.  A compile that passes says nothing about results or
times; those are `chip_smoke.py`'s and `tests/test_tpu_chip.py`'s.

Shapes are the served path's at upstream's benchmark load: event
bucket B=8192, the `start` default table of 65,536 rows, a 96-batch
window; and, for the two kinds `bench1r-tpcc-pay-c4` sends, the
1,048,576-row table that cell serves.  The suite's own
`device_kernels` is imported at TB_DEV_B=512 (tests/conftest.py), so
the `dk` fixture loads a second copy of the module at the production
width.

The topology is described inside a module-scoped fixture — never at
import, where every xdist worker would race for libtpu's lock — and
the compiles run in this process with the persistent compile cache
off (an entry written for a described device cannot be read back).
"""

import importlib.util
import os

import pytest

A = 1 << 16       # cli.CACHE_DEFAULT: the served table
A_TPCC = 1 << 20  # bench1r-tpcc-pay-c4's: 960,352 accounts
B = 8192          # production event bucket
# `linked` (amounts past 2^31 a batch: a funding request) is compiled as
# a lone dispatch only; the served cells never scan it.
KINDS = ("orderfree_tight", "linked", "linked_small", "two_phase_lo")


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on one chip of a described v5e:2x2, with
    the persistent compile cache off for the module's duration."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def dk():
    """A private copy of device_kernels at the production bucket."""
    from tigerbeetle_tpu.state_machine import device_kernels

    was = os.environ.get("TB_DEV_B")
    os.environ["TB_DEV_B"] = str(B)
    try:
        spec = importlib.util.spec_from_file_location(
            "tigerbeetle_tpu.state_machine._device_kernels_b8192",
            device_kernels.__file__,
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        if was is None:
            del os.environ["TB_DEV_B"]
        else:
            os.environ["TB_DEV_B"] = was
    assert mod.B == B and device_kernels.B != B
    return mod


def _compile(fn, one_chip, *args):
    """Lower `fn` for shapes placed on the described chip and compile;
    returns the compiler's memory analysis."""
    import jax

    def place(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    compiled = fn.lower(*jax.tree_util.tree_map(place, args)).compile()
    mem = compiled.memory_analysis()
    # 16 GB of HBM; nothing on this path should come near a tenth.
    assert mem.temp_size_in_bytes < (1 << 30), mem
    return mem


def _s(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _tables(dk, rows=A):
    import jax.numpy as jnp

    return _s((rows, 8), jnp.uint64), _s((rows, 2), jnp.uint32)


@pytest.mark.parametrize("kind,rows", [
    *((kind, A) for kind in KINDS),
    ("linked_small", A_TPCC),
    ("orderfree_tight", A_TPCC),
])
def test_base_kernel_compiles_for_v5e(one_chip, dk, kind, rows):
    """One batch per launch: the lone-request dispatch."""
    import jax
    import jax.numpy as jnp

    ncols, dtype = dk.PK_SPEC[kind]
    fn = {"orderfree_tight": dk.orderfree_tight,
          "linked": dk.linked,
          "linked_small": dk.linked_small,
          "two_phase_lo": dk.two_phase_lo}[kind]
    # The link's contract: the scalars ride in the buffer's last row,
    # and the summary row comes back as an output of its own, the
    # dense codes (which stay on the device unless the row's failures
    # outrun its entries) as another.
    args = (*_tables(dk, rows), _s((dk.ROWS, ncols), dtype))
    mem = _compile(fn, one_chip, *args)
    # The sums follow the rows a batch touches: beside its copy of the
    # table a program holds nothing that grows with it (the parent's
    # one-hot product was (2B, rows); a (2B, 2B) one-hot materialised
    # would be 512 MiB).
    assert mem.temp_size_in_bytes < (1 << 28), mem
    table, row, dense = jax.eval_shape(fn, *args)
    assert table.shape == (rows, 8)
    assert (row.shape, row.dtype) == ((dk.SUMMARY_WORDS,), jnp.uint64)
    assert (dense.shape, dense.dtype) == ((dk.B,), jnp.uint32)


@pytest.mark.parametrize("kind", [k for k in KINDS if k != "linked"])
def test_window_scan_compiles_for_v5e(one_chip, dk, kind):
    """Sixteen batches per launch out of one uploaded stack; their
    summary rows come back as one (16, SUMMARY_WORDS) output."""
    import jax
    import jax.numpy as jnp

    ncols, dtype = dk.PK_SPEC[kind]
    fn = dk.scan_kernels[kind][16]
    args = (*_tables(dk), _s((16, dk.ROWS, ncols), dtype))
    _compile(fn, one_chip, *args)
    _table, rows, dense = jax.eval_shape(fn, *args)
    assert (rows.shape, rows.dtype) == ((16, dk.SUMMARY_WORDS), jnp.uint64)
    assert (dense.shape, dense.dtype) == ((16, dk.B), jnp.uint32)


def test_speculative_wave_executor_compiles_for_v5e(one_chip):
    """The executor an off-kernel window batch (duplicate ids, balance
    limits) is dispatched through: one speculative device step over
    the whole 8192-event bucket."""
    import jax.numpy as jnp

    from tigerbeetle_tpu.state_machine import waves

    _B, K, ev, idx, _chain = next(iter(waves._prewarm_shapes((B,), (B,))))
    gathered = waves._gather_events(ev, idx, K, B)
    _compile(
        waves._spec_exec, one_chip, _s((A, 8), jnp.uint64), gathered,
        _s((B,), jnp.uint32), _s((K,), jnp.bool_),
        _s((), jnp.int32), _s((), jnp.uint64),
    )
