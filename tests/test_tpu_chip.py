"""Real-chip TPU tests (VERDICT r3 #3).

The suite's conftest puts every test on a virtual CPU mesh
(JAX_PLATFORMS=cpu), so these tests exercise the REAL device in child
processes that ask for the TPU by JAX's own name.  A chip belongs to
one process at a time: the pytest process itself stays on the CPU
backend and each test's child holds the chip in turn.  They run
wherever a chip is attached (`python -m pytest tests/test_tpu_chip.py`
through the chip tool) and skip, visibly, where none is.

Covered: the exact scan kernel's parity on a real batch, DeviceTable
flush + read-back checksum, table growth, and the device-authoritative
engine end-to-end against the CPU oracle — the production device stack
on real silicon, not just the CPU twin.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.tpu

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chip_env():
    env = dict(os.environ)
    # Undo the suite's CPU settings: the TPU by name, no virtual mesh.
    env["JAX_PLATFORMS"] = "tpu"
    env["XLA_FLAGS"] = ""
    env["TB_DEV_B"] = "512"  # small bucket: keep chip compiles short
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


_PROBE = """
import jax
assert jax.devices()[0].platform == "tpu", jax.devices()
print("TPU_OK")
"""

# Every child compiles through the repo's one persistent cache, so a
# chip run that also ran chip_smoke.py shares its compiles.
_PREAMBLE = """
from tigerbeetle_tpu import device
device.enable_compile_cache()
"""


@pytest.fixture(scope="module")
def chip():
    """One reachability probe per module; skips where no chip is."""
    try:
        probe = subprocess.run(
            [sys.executable, "-c", _PROBE], env=_chip_env(),
            capture_output=True, text=True, timeout=120,
        )
    except subprocess.TimeoutExpired:
        pytest.skip("TPU probe timed out")
    if "TPU_OK" not in probe.stdout:
        pytest.skip(f"no TPU reachable: {probe.stderr[-200:]}")


def _run_on_chip(code: str, timeout: int = 420,
                 production_bucket: bool = False) -> str:
    """production_bucket=True drops the TB_DEV_B shrink so the chip
    compiles the full B=8192 geometry."""
    env = _chip_env()
    if production_bucket:
        env.pop("TB_DEV_B", None)
    proc = subprocess.run(
        [sys.executable, "-c", _PREAMBLE + code], env=env,
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"chip test failed\nstdout: {proc.stdout[-1500:]}\n"
        f"stderr: {proc.stderr[-1500:]}"
    )
    return proc.stdout


def test_exact_scan_kernel_parity_on_chip(chip):
    """kernel.py (the exact sequential-semantics scan) computes the
    same replies on the real TPU as the CPU oracle."""
    out = _run_on_chip(
        """
import numpy as np
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.types import Operation, TransferFlags as TF

sm = TpuStateMachine(account_capacity=1 << 12)
sm._native = None  # force the JAX scan kernel (device) path
h = hz.SingleNodeHarness(sm)
hc = hz.SingleNodeHarness(CpuStateMachine())
ops = [(Operation.create_accounts,
        hz.pack([hz.account(i) for i in range(1, 20)]))]
rows = []
rng = np.random.default_rng(3)
for i in range(200):
    dr = int(rng.integers(1, 20)); cr = dr % 19 + 1
    flags = int(TF.linked) if i % 5 == 0 else 0
    if i % 7 == 0:
        flags |= int(TF.pending)
    rows.append(hz.transfer(1000 + i, debit_account_id=dr,
                            credit_account_id=cr,
                            amount=int(rng.integers(1, 50)), flags=flags))
rows[-1] = hz.transfer(2000, debit_account_id=1, credit_account_id=2,
                       amount=5)
ops.append((Operation.create_transfers, hz.pack(rows)))
ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 20)))))
got = [h.submit(op, body) for op, body in ops]
exp = [hc.submit(op, body) for op, body in ops]
assert got == exp, "scan kernel diverges on chip"
print("SCAN_PARITY_OK")
""",
    )
    assert "SCAN_PARITY_OK" in out


def test_device_table_flush_readback_checksum_on_chip(chip):
    """Write-behind DeviceTable: queue deltas, flush, read back, and
    match the host mirror exactly (incl. after grow())."""
    out = _run_on_chip(
        """
import numpy as np
import jax.numpy as jnp
from tigerbeetle_tpu.state_machine.kernel_fast import DeviceTable
from tigerbeetle_tpu.state_machine.mirror import BalanceMirror

rng = np.random.default_rng(5)
dev = DeviceTable(256)
mir = BalanceMirror(256)
for batch in range(6):
    n = 500
    slots = rng.integers(0, 256, n).astype(np.int64)
    cols = rng.integers(0, 4, n).astype(np.int64)
    lo = rng.integers(0, 1 << 32, n).astype(np.uint64)
    hi = np.zeros(n, np.uint64)
    deltas = mir.try_apply_deltas(slots, cols, lo, hi)
    assert deltas is not None
    dev.enqueue(*deltas)
    if batch == 3:
        dev.grow(512)
        mir.grow(512)
tbl = np.asarray(dev.read())
exp = mir.rows8(np.arange(512, dtype=np.int64))
assert (tbl == exp).all(), "device table != mirror after flush"
print("FLUSH_READBACK_OK")
""",
    )
    assert "FLUSH_READBACK_OK" in out


def test_production_b8192_kernels_on_chip(chip):
    """The PRODUCTION event-bucket geometry (B=8192, a full request's
    shape) compiles and runs on the real chip with full-batch oracle
    parity — a served run must not be the first place this geometry
    compiles (VERDICT r4 #7).  Covers orderfree (all-success 8190-event batch),
    linked chains, and a two-phase batch at the same bucket size."""
    code = """
import numpy as np
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.types import Operation, TransferFlags as TF

from tigerbeetle_tpu.state_machine import device_kernels as dk
assert dk.B == 8192, f"production bucket expected, got {dk.B}"

sm = TpuStateMachine(engine="device", account_capacity=1 << 12,
                     transfer_capacity=64 * 1024)
h = hz.SingleNodeHarness(sm)
hc = hz.SingleNodeHarness(CpuStateMachine())
rng = np.random.default_rng(7)
ops = [(Operation.create_accounts,
        hz.pack([hz.account(i) for i in range(1, 1001)]))]

# Full production batch: 8190 order-free transfers.
tid = 1000
rows = []
for i in range(8190):
    dr = int(rng.integers(1, 1001)); cr = dr % 1000 + 1
    rows.append(hz.transfer(tid, debit_account_id=dr, credit_account_id=cr,
                            amount=int(rng.integers(1, 100))))
    tid += 1
ops.append((Operation.create_transfers, hz.pack(rows)))

# Linked chains at production size (avg len 4, last event unlinked).
rows = []
while len(rows) < 4000:
    clen = int(rng.integers(1, 8))
    for j in range(clen):
        dr = int(rng.integers(1, 1001)); cr = dr % 1000 + 1
        rows.append(hz.transfer(tid, debit_account_id=dr,
                                credit_account_id=cr,
                                amount=int(rng.integers(1, 100)),
                                flags=0 if j == clen - 1 else int(TF.linked)))
        tid += 1
ops.append((Operation.create_transfers, hz.pack(rows)))

# Two-phase pairs at the same bucket.
rows = []
for i in range(1000):
    dr = int(rng.integers(1, 1001)); cr = dr % 1000 + 1
    rows.append(hz.transfer(tid, debit_account_id=dr, credit_account_id=cr,
                            amount=int(rng.integers(1, 100)),
                            flags=int(TF.pending)))
    rows.append(hz.transfer(
        tid + 1, pending_id=tid,
        flags=int(TF.void_pending_transfer if i % 3 == 0
                  else TF.post_pending_transfer)))
    tid += 2
ops.append((Operation.create_transfers, hz.pack(rows)))
ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 1001)))))

futs = [h.submit_async(op, body) for op, body in ops]
got = [f.result() for f in futs]
exp = [hc.submit(op, body) for op, body in ops]
for i, (g, e) in enumerate(zip(got, exp)):
    assert g == e, f"B=8192 kernels diverge on chip at op {i}"
assert sm.stat_device_semantic_events >= 8190 + 4000 + 2000
sm.verify_device_mirror()
print("B8192_OK")
"""
    out = _run_on_chip(code, timeout=540, production_bucket=True)
    assert "B8192_OK" in out


def test_device_engine_oracle_parity_on_chip(chip):
    """The device-authoritative engine end-to-end on real silicon:
    codes from the chip match the CPU oracle; checkpoint checksum
    passes."""
    out = _run_on_chip(
        """
import numpy as np
from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
from tigerbeetle_tpu.state_machine.tpu import TpuStateMachine
from tigerbeetle_tpu.testing import harness as hz
from tigerbeetle_tpu.types import Operation, TransferFlags as TF

sm = TpuStateMachine(engine="device", account_capacity=1 << 12)
h = hz.SingleNodeHarness(sm)
hc = hz.SingleNodeHarness(CpuStateMachine())
rng = np.random.default_rng(1)
ops = [(Operation.create_accounts,
        hz.pack([hz.account(i) for i in range(1, 101)]))]
tid = 1000
for b in range(4):
    rows = []
    for i in range(400):
        dr = int(rng.integers(1, 101)); cr = dr % 100 + 1
        rows.append(hz.transfer(tid, debit_account_id=dr,
                                credit_account_id=cr,
                                amount=int(rng.integers(1, 50))))
        tid += 1
    ops.append((Operation.create_transfers, hz.pack(rows)))
# two-phase pair batch through the device kernel
rows = [
    hz.transfer(tid, debit_account_id=1, credit_account_id=2, amount=30,
                flags=int(TF.pending)),
    hz.transfer(tid + 1, pending_id=tid,
                flags=int(TF.post_pending_transfer)),
]
ops.append((Operation.create_transfers, hz.pack(rows)))
ops.append((Operation.lookup_accounts, hz.ids_bytes(list(range(1, 101)))))
futs = [h.submit_async(op, body) for op, body in ops]
got = [f.result() for f in futs]
exp = [hc.submit(op, body) for op, body in ops]
assert got == exp, "device engine diverges on chip"
assert sm._dev.stat_semantic_events > 0
sm.verify_device_mirror()
print("ENGINE_PARITY_OK")
""",
    )
    assert "ENGINE_PARITY_OK" in out
