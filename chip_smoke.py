#!/usr/bin/env python3
"""chip_smoke.py — the served device-engine path, end to end, on one chip.

Run with no arguments on a machine with one TPU chip.  The parent
process never imports JAX (a chip belongs to one process): it builds
the native libraries, starts real servers through the CLI
(`python -m tigerbeetle_tpu format|start`), drives them over TCP with
`tigerbeetle_tpu.client.Client`, runs the plain oracle
(`state_machine/cpu.py`) itself, and reads what each server holds from
its start-up line and its stats scrape.

Phases (no option):
  1. `make -B -C native` (forced: the chip tool's copy keeps no mtimes).
  2. One server, TB_ENGINE=device: upstream's benchmark shape (10,000
     accounts, create_transfers requests of 8,190 events): 24 plain
     requests (8 from one session, 16 from four concurrent sessions),
     one request of linked chains, one of two-phase pairs, one with
     deliberate failures; then lookups and one get_account_transfers.
     Every reply is compared with the oracle's, byte for byte
     (server-assigned timestamps masked in lookup rows).
  3. The server's scrape must say: platform tpu, engine healthy, no
     demotion, no link error or retry, and who computed the result
     codes of each request kind.
  4. Enough tiny requests to cross a checkpoint (the device/mirror
     checksum runs at its barrier), SIGTERM, restart on the same data
     file, read everything back.
  5. A short second server on the default (host) engine: the
     write-behind device table and its checksum against the mirror.

Options:
  --size tiny     the CPU rehearsal's size (256 accounts, requests of
                  500 events — fits the tests' TB_DEV_B=512); the run
                  still fails at the end because the platform is not tpu
  --no-rebuild    `make -C native` without -B (tests: other workers
                  have the libraries loaded)
  --four-chips    ONLY the replicated path: three replicas, one chip
                  each, committed through the VSR quorum
  --seed N        data seed (default 22)

Last stdout line on success, and only then:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
On any failure the reasons are printed as `FAIL:` lines, no result
line is printed, and the exit code is non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

try:
    # None of these imports JAX: the parent must never hold the chip.
    from tigerbeetle_tpu import constants as cfg
    from tigerbeetle_tpu import types
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.obs.scrape import scrape_state_root, scrape_stats
    from tigerbeetle_tpu.state_machine.cpu import CpuStateMachine
    from tigerbeetle_tpu.types import Operation
except ImportError as exc:
    sys.exit(f"chip_smoke: the tigerbeetle_tpu package is not beside this "
             f"script: {exc}")

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
CLUSTER = 22
# One op per request; a checkpoint every 960 committed ops
# (constants.PRODUCTION.vsr_checkpoint_interval).
CHECKPOINT_OPS = 960

SIZES = {
    # accounts, events per request, plain requests (serial, concurrent),
    # host-engine requests, sampled transfer lookups
    "full": dict(accounts=10_000, batch=8190, serial=8, concurrent=16,
                 host_requests=4, quorum_requests=8, sample=4000),
    "tiny": dict(accounts=256, batch=500, serial=2, concurrent=4,
                 host_requests=2, quorum_requests=3, sample=300),
}
N_LIMIT_ACCOUNTS = 16   # debits_must_not_exceed_credits, never credited
SESSIONS = 4

_children: list[subprocess.Popen] = []
_failures: list[str] = []
# --size tiny goes on after a server reports another platform than tpu
# (that is the rehearsal); at full size the CPU backend would take
# hours over the B=8192 kernels, so the run stops there.
_rehearsal = False


def say(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> bool:
    if not cond:
        _failures.append(msg)
        say(f"FAIL: {msg}")
    return bool(cond)


class SmokeError(RuntimeError):
    """A phase could not run at all (build, start-up, lost server)."""


def success_line(device: dict) -> str:
    """The one result line: exactly these keys, through json.dumps."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": device["platform"],
            "kind": device["kind"],
            "count": device["count"],
        },
    })


# ----------------------------------------------------------------------
# Children: every process goes through here, output to a file.


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(extra: dict) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.pop("TB_ENGINE", None)
    env.update(extra)
    return env


def run_cli(args: list[str], log_name: str) -> None:
    log = os.path.join(LOG_DIR, log_name)
    with open(log, "ab") as f:
        rc = subprocess.call(
            [sys.executable, "-m", "tigerbeetle_tpu", *args],
            stdout=f, stderr=subprocess.STDOUT, cwd=REPO, env=child_env({}),
        )
    if rc != 0:
        raise SmokeError(f"`tigerbeetle_tpu {' '.join(args)}` exited {rc}: "
                         + tail(log))


def tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode("utf-8", "replace")
    except OSError as exc:
        return repr(exc)


class Server:
    def __init__(self, name: str, data: str, addresses: str, replica: int,
                 env: dict, transfers: int) -> None:
        self.name = name
        self.data = data
        self.addresses = addresses
        self.address = addresses.split(",")[replica]
        self.replica = replica
        self.transfers = transfers
        self.log = os.path.join(LOG_DIR, f"{name}.log")
        self.env = {**env, "TB_FLIGHT_PATH": os.path.join(
            LOG_DIR, f"{name}_flight.json")}
        self.proc: subprocess.Popen | None = None
        self.device: dict | None = None
        self.starts = 0

    def start(self) -> None:
        self.starts += 1
        with open(self.log, "ab") as f:
            f.write(f"--- start #{self.starts}\n".encode())
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "tigerbeetle_tpu", "start",
                 f"--addresses={self.addresses}", f"--replica={self.replica}",
                 f"--cache-transfers={self.transfers}", self.data],
                stdout=f, stderr=subprocess.STDOUT, cwd=REPO, env=self.env,
            )
        _children.append(self.proc)

    def wait_listening(self, deadline_s: float = 900.0) -> dict:
        """-> the device the server says it holds (its start-up line)."""
        t0 = time.monotonic()
        marker = f"--- start #{self.starts}\n"
        while time.monotonic() - t0 < deadline_s:
            with open(self.log, "rb") as f:
                text = f.read().decode("utf-8", "replace")
            text = text[text.rindex(marker):]
            for line in text.splitlines():
                if line.startswith("listening on port") and "device=" in line:
                    self.device = json.loads(line.split("device=", 1)[1])
                    say(f"{self.name}: up in {time.monotonic() - t0:.1f}s "
                        f"device={json.dumps(self.device)}")
                    if self.device["platform"] != "tpu" and not _rehearsal:
                        raise SmokeError(
                            f"{self.name}: platform is "
                            f"{self.device['platform']!r}, not 'tpu'"
                        )
                    return self.device
            if self.proc.poll() is not None:
                raise SmokeError(
                    f"{self.name} exited {self.proc.returncode} before "
                    f"listening: {tail(self.log)}"
                )
            time.sleep(0.2)
        raise SmokeError(f"{self.name} not listening after {deadline_s:.0f}s: "
                         + tail(self.log))

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def stop(self) -> None:
        """SIGTERM and wait.  A serving `start` answers SIGTERM by
        writing its flight record and dying of the signal (exit code
        intact for supervisors, runtime/server.py); anything else —
        an exit of its own before the signal, another code — fails."""
        if self.proc is None:
            return
        died_before = self.proc.poll()
        if died_before is None:
            self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = self.proc.wait()
        self.proc = None
        check(died_before is None and rc in (0, -signal.SIGTERM),
              f"{self.name}: exit code {rc} "
              f"({'before' if died_before is not None else 'on'} SIGTERM): "
              + tail(self.log, 600))

    def scrape(self) -> dict:
        if not self.alive():
            raise SmokeError(f"{self.name} died: {tail(self.log)}")
        return scrape_stats(self.address, CLUSTER, timeout_ms=60_000)


def reap_all() -> None:
    for p in _children:
        if p.poll() is None:
            p.kill()
        p.wait()


# ----------------------------------------------------------------------
# Workload: numpy wire rows from a seed.  Account ids 1..N; account 1
# is touched by the serial requests only (get_account_transfers is
# ordered by commit time, and concurrent sessions commit in any order);
# the last N_LIMIT_ACCOUNTS carry debits_must_not_exceed_credits and
# never receive a credit, so any debit on them exceeds.


class Workload:
    def __init__(self, seed: int, size: dict) -> None:
        self.rng = np.random.default_rng(seed)
        self.n_accounts = size["accounts"]
        self.batch = size["batch"]
        self.pool = self.n_accounts - N_LIMIT_ACCOUNTS   # ids 1..pool
        self.next_id = 1
        self.all_ids: list = []

    def accounts(self):
        a = np.zeros(self.n_accounts, types.ACCOUNT_DTYPE)
        a["id_lo"] = np.arange(1, self.n_accounts + 1, dtype=np.uint64)
        a["ledger"] = 1
        a["code"] = 10
        a["flags"][self.pool:] = int(
            types.AccountFlags.debits_must_not_exceed_credits
        )
        return a

    def _ids(self, n: int):
        ids = np.arange(self.next_id, self.next_id + n, dtype=np.uint64)
        self.next_id += n
        self.all_ids.append(ids)
        return ids

    def plain(self, n: int | None = None, first_account: int = 1):
        """Unflagged transfers over the general pool; distinct ids, no
        limits, so any two such requests commute."""
        n = self.batch if n is None else n
        t = np.zeros(n, types.TRANSFER_DTYPE)
        t["id_lo"] = self._ids(n)
        span = self.pool - first_account + 1
        dr = self.rng.integers(0, span, n)
        cr = (dr + self.rng.integers(1, span, n)) % span
        t["debit_account_id_lo"] = dr + first_account
        t["credit_account_id_lo"] = cr + first_account
        t["amount_lo"] = self.rng.integers(1, 1000, n)
        t["ledger"] = 1
        t["code"] = 7
        return t

    def linked(self):
        """Chains of mean length 4; some made to fail (an unknown
        credit account mid-chain, a debit on a limit account) so that
        rollback shows: 40 chains of 2 to 4 legs, 80 failure codes or
        more, which is past the 60 entries of the device's summary
        row, so the batch's dense codes come home too."""
        TF = types.TransferFlags
        t = self.plain()
        n = len(t)
        lengths = []
        while sum(lengths) < n:
            lengths.append(int(self.rng.integers(1, 8)))
        lengths[-1] -= sum(lengths) - n
        starts = np.cumsum([0] + lengths[:-1])
        flags = np.full(n, int(TF.linked), np.uint16)
        flags[starts + np.array(lengths) - 1] = 0     # chain tails
        t["flags"] = flags
        short = [i for i, ln in enumerate(lengths) if 2 <= ln <= 4]
        picks = self.rng.choice(short, size=min(40, len(short)), replace=False)
        for k, ci in enumerate(picks):
            at = int(starts[ci]) + int(self.rng.integers(0, lengths[ci]))
            if k == 0:
                t["debit_account_id_lo"][at] = self.pool + 1   # exceeds
            else:
                t["credit_account_id_lo"][at] = self.n_accounts + 1000 + k
        return t

    def two_phase(self):
        """Pendings, then a post or a void (30%) of each, one batch."""
        TF = types.TransferFlags
        half = self.batch // 2
        t = self.plain(2 * half)
        t["flags"][:half] = int(TF.pending)
        void = self.rng.random(half) < 0.30
        t["flags"][half:] = np.where(
            void, int(TF.void_pending_transfer), int(TF.post_pending_transfer)
        )
        order = self.rng.permutation(half)
        t["pending_id_lo"][half:] = t["id_lo"][:half][order]
        for f in ("debit_account_id_lo", "credit_account_id_lo"):
            t[f][half:] = t[f][:half][order]
        t["amount_lo"][half:] = t["amount_lo"][:half][order]
        # The last few finalize a pending that an earlier event of the
        # batch already finalized: already posted / already voided.
        for f in ("pending_id_lo", "debit_account_id_lo",
                  "credit_account_id_lo", "amount_lo"):
            t[f][-6:] = t[f][half:half + 6]
        return t

    def failing(self, earlier):
        """Mostly fine, with duplicate ids (rows of an earlier request
        sent again), unknown accounts, debits past a balance limit and
        debit == credit."""
        t = self.plain()
        n = len(t)
        at = self.rng.choice(n, size=40, replace=False)
        t[at[:10]] = earlier[:10]
        t["debit_account_id_lo"][at[10:20]] = self.n_accounts + 500
        t["debit_account_id_lo"][at[20:30]] = self.pool + 2 + (at[20:30] % 8)
        t["credit_account_id_lo"][at[30:40]] = t["debit_account_id_lo"][at[30:40]]
        return t


def ids_body(ids) -> bytes:
    arr = np.zeros(len(ids), types.U128_PAIR_DTYPE)
    arr["lo"] = ids
    return arr.tobytes()


def masked(dtype, reply: bytes) -> bytes:
    """Lookup rows with the server-assigned `timestamp` zeroed: the
    server stamps its wall clock, the oracle its own counter."""
    arr = np.frombuffer(reply, dtype).copy()
    arr["timestamp"] = 0
    return arr.tobytes()


class Oracle:
    """The plain reference, in this process: CpuStateMachine behind
    the primary's prepare/prefetch/commit sequence (the loop of
    testing/harness.py, which itself would import the JAX-backed
    reply future)."""

    def __init__(self) -> None:
        self.sm = CpuStateMachine(cfg.PRODUCTION)
        self.op = 0

    def _run(self, operation, body: bytes) -> bytes:
        sm = self.sm
        sm.prepare_timestamp = max(sm.prepare_timestamp, sm.commit_timestamp) + 1
        sm.prepare(operation, body)
        timestamp = sm.prepare_timestamp
        self.op += 1
        sm.prefetch(operation, body, prefetch_timestamp=timestamp)
        return sm.commit(0, self.op, timestamp, operation, body)

    def submit(self, operation, body: bytes) -> bytes:
        # Pulses while the state machine asks for them; one that finds
        # nothing parks the next pulse in the future.
        while self.sm.pulse_needed():
            before = self.sm.pulse_next_timestamp
            self._run(Operation.pulse, b"")
            if self.sm.pulse_next_timestamp == before:
                break
        return self._run(operation, body)


class Driver:
    """Sends each request to the server, applies it to the oracle, and
    compares the two replies."""

    def __init__(self, server: Server, oracle: Oracle, w: Workload) -> None:
        self.server = server
        self.oracle = oracle
        self.w = w
        self.client = self.connect()
        self.compared = 0
        self.mismatched = 0

    def connect(self, timeout_ms: int = 900_000):
        # Long timeout: a first request may wait on a kernel compile.
        return Client(self.server.addresses, CLUSTER, timeout_ms=timeout_ms)

    def compare(self, what: str, got: bytes, want: bytes) -> bool:
        self.compared += 1
        if got == want:
            return True
        self.mismatched += 1
        dt = types.CREATE_RESULT_DTYPE
        detail = f"{len(got)} bytes vs oracle {len(want)}"
        if len(got) % dt.itemsize == 0 and len(want) % dt.itemsize == 0 and (
            what.startswith("create")
        ):
            g = np.frombuffer(got, dt)[:4].tolist()
            o = np.frombuffer(want, dt)[:4].tolist()
            detail += f"; first results {g} vs oracle {o}"
        return check(False, f"{self.server.name}: {what}: reply differs ({detail})")

    def request(self, what: str, operation, body: bytes, *, mask=None) -> bytes:
        if not self.server.alive():
            raise SmokeError(f"{self.server.name} died: {tail(self.server.log)}")
        got = self.client.request(operation, body)
        want = self.oracle.submit(operation, body)
        if mask is not None:
            got, want = masked(mask, got), masked(mask, want)
        self.compare(what, got, want)
        return got

    def create_accounts(self) -> None:
        a = self.w.accounts()
        cap = 8190
        for at in range(0, len(a), cap):
            self.request(f"create_accounts[{at}:]", Operation.create_accounts,
                         a[at:at + cap].tobytes())

    def transfers(self, what: str, rows) -> bytes:
        return self.request(f"create_transfers {what}",
                            Operation.create_transfers, rows.tobytes())

    def concurrent(self, batches: list) -> None:
        """`batches` from SESSIONS concurrent sessions.  They commute,
        so the oracle applies them in list order afterwards."""
        replies: list = [None] * len(batches)
        errors: list = []

        def session(k: int) -> None:
            client = self.connect()
            try:
                for i in range(k, len(batches), SESSIONS):
                    replies[i] = client.request(
                        Operation.create_transfers, batches[i].tobytes()
                    )
            except Exception as exc:  # noqa: BLE001 — reported below
                errors.append(f"session {k}: {exc!r}")
            finally:
                client.close()

        threads = [threading.Thread(target=session, args=(k,))
                   for k in range(SESSIONS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise SmokeError(f"{self.server.name}: {'; '.join(errors)}")
        for i, rows in enumerate(batches):
            want = self.oracle.submit(Operation.create_transfers, rows.tobytes())
            self.compare(f"create_transfers plain concurrent #{i}",
                         replies[i], want)

    def lookup_all_accounts(self, what: str) -> None:
        ids = np.arange(1, self.w.n_accounts + 1, dtype=np.uint64)
        rows = 0
        for at in range(0, len(ids), 8190):
            got = self.request(
                f"lookup_accounts {what}[{at}:]", Operation.lookup_accounts,
                ids_body(ids[at:at + 8190]),
                mask=types.ACCOUNT_DTYPE,
            )
            rows += len(got) // types.ACCOUNT_DTYPE.itemsize
        check(rows == self.w.n_accounts,
              f"{self.server.name}: lookup_accounts {what}: {rows} rows, "
              f"expected {self.w.n_accounts}")

    def lookup_transfer_sample(self, k: int) -> None:
        ids = np.concatenate(self.w.all_ids)
        ids = self.w.rng.choice(ids, size=min(k, len(ids)), replace=False)
        for at in range(0, len(ids), 8190):
            self.request("lookup_transfers sample", Operation.lookup_transfers,
                         ids_body(ids[at:at + 8190]),
                         mask=types.TRANSFER_DTYPE)

    def account_transfers(self, account: int) -> None:
        f = np.zeros(1, types.ACCOUNT_FILTER_DTYPE)
        f["account_id_lo"] = account
        f["limit"] = 8190
        f["flags"] = int(types.AccountFilterFlags.debits
                         | types.AccountFilterFlags.credits)
        got = self.request("get_account_transfers",
                           Operation.get_account_transfers, f.tobytes(),
                           mask=types.TRANSFER_DTYPE)
        check(len(got) > 0, f"{self.server.name}: get_account_transfers "
              f"of account {account} returned no rows")

    def pad_to_checkpoint(self, snap: dict) -> None:
        """One-id lookups until the next checkpoint barrier has run:
        every request is one committed op, and the device/mirror
        checksum (verify_device_mirror) runs at the barrier."""
        done = int(snap.get("vsr.commit_min", 0))
        need = CHECKPOINT_OPS - done % CHECKPOINT_OPS + 8
        ids = self.w.rng.integers(1, self.w.n_accounts + 1, need)
        t0 = time.monotonic()
        for i in ids:
            self.request("lookup_accounts pad", Operation.lookup_accounts,
                         ids_body([int(i)]),
                         mask=types.ACCOUNT_DTYPE)
        say(f"{self.server.name}: {need} one-id lookups to cross the "
            f"checkpoint in {time.monotonic() - t0:.1f}s")

    def close(self) -> None:
        self.client.close()


# ----------------------------------------------------------------------
# What the scrape must say.

_KIND_KEYS = (
    "sm.dev.semantic_events", "sm.host_semantic_events",
    "sm.fallback_events", "sm.dev.fallback_batches",
    "sm.dev_wave.batches", "sm.dev_wave.events", "sm.dev_wave.declined",
    "sm.dev.summary.dense_fetches",
)


def delta(after: dict, before: dict) -> dict:
    out = {k: after.get(k, 0) - before.get(k, 0) for k in _KIND_KEYS}
    out["declines"] = {
        k.split("decline.", 1)[1]: after[k] - before.get(k, 0)
        for k in after
        if k.startswith("sm.dev_wave.decline.") and after[k] - before.get(k, 0)
    }
    return out


def judge_kind(server: Server, kind: str, sent: int, d: dict,
               want_all: bool) -> None:
    dev = d["sm.dev.semantic_events"]
    host = d["sm.host_semantic_events"]
    share = dev / sent if sent else 0.0
    say(f"{server.name}: {kind}: {sent} events sent, result codes computed "
        f"on device for {dev} ({100 * share:.2f}%), on host for {host}; "
        f"flagged-batch fallbacks {d['sm.dev.fallback_batches']} "
        f"({d['sm.fallback_events']} events), wave-dispatched batches "
        f"{d['sm.dev_wave.batches']} ({d['sm.dev_wave.events']} events), "
        f"wave declines {d['sm.dev_wave.declined']} {d['declines'] or ''}")
    check(dev + host == sent, f"{server.name}: {kind}: device {dev} + host "
          f"{host} events do not add up to the {sent} sent")
    if want_all:
        check(dev == sent, f"{server.name}: {kind}: only {dev} of {sent} "
              "events had their result codes computed on the device")
    else:
        check(dev > 0, f"{server.name}: {kind}: no event had its result "
              "codes computed on the device")


def judge_health(server: Server, snap: dict, engine: str) -> None:
    dev = snap.get("device") or {}
    check(dev.get("platform") == "tpu",
          f"{server.name}: platform is {dev.get('platform')!r}, not 'tpu'")
    check(dev.get("engine") == engine,
          f"{server.name}: engine is {dev.get('engine')!r}, not {engine!r}")
    check(dev.get("state") == "healthy" and dev.get("last_demotion") is None,
          f"{server.name}: engine state {dev.get('state')!r}, "
          f"last demotion {dev.get('last_demotion')!r}")
    if engine == "device":
        for key in ("sm.dev.demotions", "sm.dev.link.errors",
                    "sm.dev.link.retries", "sm.dev.probe_failures",
                    "sm.dev.scrub_heals", "sm.dev.commit.repairs"):
            check(snap.get(key, 0) == 0, f"{server.name}: {key} = {snap.get(key)}")
        link = {
            stage: {"n": snap.get(f"sm.dev.link.{stage}_us.count"),
                    "p50_us": snap.get(f"sm.dev.link.{stage}_us.p50"),
                    "p99_us": snap.get(f"sm.dev.link.{stage}_us.p99")}
            for stage in ("h2d", "dispatch", "fetch")
        }
        say(f"{server.name}: link crossings (a smoke's observation, not a "
            f"benchmark): {json.dumps(link)}")
    c = dev.get("compile") or {}
    warmth = "warm" if c.get("cache_hits", 0) > c.get("cache_misses", 0) else "cold"
    say(f"{server.name}: compile {c.get('seconds')}s over {c.get('count')} "
        f"programs, persistent cache {warmth} ({c.get('cache_hits')} hits, "
        f"{c.get('cache_misses')} misses) in {c.get('dir')}")


def judge_checkpoint(server: Server, before: dict, after: dict) -> None:
    n = sum(after.get(k, 0) - before.get(k, 0)
            for k in ("vsr.ckpt.async", "vsr.ckpt.sync"))
    check(n >= 1, f"{server.name}: no checkpoint ran (device/mirror checksum "
          "not exercised)")
    say(f"{server.name}: {n} checkpoint(s) ran; the device/mirror checksum "
        "at the barrier passed (a divergence kills the server)")


# ----------------------------------------------------------------------
# Phases.


def build_native(force: bool) -> None:
    t0 = time.monotonic()
    cmd = ["make", "-C", os.path.join(REPO, "native")] + (["-B"] if force else [])
    log = os.path.join(LOG_DIR, "make.log")
    with open(log, "wb") as f:
        rc = subprocess.call(cmd, stdout=f, stderr=subprocess.STDOUT)
    if rc != 0:
        raise SmokeError(f"`{' '.join(cmd)}` exited {rc}: {tail(log)}")
    say(f"native: `{' '.join(cmd[:1] + cmd[3:]) or 'make'}` ok in "
        f"{time.monotonic() - t0:.1f}s")


def make_server(name: str, tmp: str, env: dict, transfers: int) -> Server:
    data = os.path.join(tmp, f"{name}.tigerbeetle")
    run_cli(["format", f"--cluster={CLUSTER}", "--replica=0",
             "--replica-count=1", data], f"{name}.log")
    return Server(name, data, f"127.0.0.1:{free_port()}", 0, env, transfers)


def transfer_capacity(size: dict, requests: int) -> int:
    """--cache-transfers for `requests` full requests, a power of two
    with headroom (the default, 65,536, is below the full load)."""
    need = requests * size["batch"] + 4096
    return max(1 << 16, 1 << need.bit_length())


def phase_device_engine(tmp: str, seed: int, size: dict) -> dict:
    w = Workload(seed, size)
    requests = size["serial"] + size["concurrent"] + 4
    env = child_env({
        "TB_ENGINE": "device",
        # Compile at start-up, where a refusal is an error and not a
        # demotion: the kernels this load dispatches.
        "TB_DEV_PREWARM": "orderfree_tight,linked_small,two_phase_lo",
    })
    server = make_server("device", tmp, env, transfer_capacity(size, requests))
    t0 = time.monotonic()
    server.start()
    device = server.wait_listening()
    d = Driver(server, Oracle(), w)
    try:
        d.create_accounts()
        s0 = server.scrape()
        serial = [w.plain() for _ in range(size["serial"])]
        for i, rows in enumerate(serial):
            d.transfers(f"plain serial #{i}", rows)
        d.concurrent([w.plain(first_account=2)
                      for _ in range(size["concurrent"])])
        s1 = server.scrape()
        judge_kind(server, "plain", (len(serial) + size["concurrent"])
                   * size["batch"], delta(s1, s0), want_all=True)
        for kind, rows in (("linked", w.linked()),
                           ("two_phase", w.two_phase()),
                           ("failing", w.failing(serial[0]))):
            before = server.scrape()
            reply = d.transfers(kind, rows)
            failed = len(reply) // 8
            say(f"{server.name}: {kind}: {failed} of {len(rows)} events "
                "came back with a result other than ok")
            check(failed > 0, f"{server.name}: {kind}: no failure came back")
            moved = delta(server.scrape(), before)
            judge_kind(server, kind, len(rows), moved, want_all=False)
            if kind == "linked":
                # More failures than the summary row holds: the dense
                # codes cross, and the device's verdicts stand.
                check(failed > 60 and moved["sm.dev.summary.dense_fetches"] == 1
                      and moved["sm.dev.fallback_batches"] == 0
                      and moved["sm.dev.semantic_events"] == len(rows),
                      f"{server.name}: linked: {failed} failures, "
                      f"{moved['sm.dev.summary.dense_fetches']} dense fetches, "
                      f"{moved['sm.dev.fallback_batches']} fallbacks")
        d.lookup_all_accounts("after load")
        d.lookup_transfer_sample(size["sample"])
        d.account_transfers(1)
        snap = server.scrape()
        d.pad_to_checkpoint(snap)
        d.transfers("plain after checkpoint", w.plain(first_account=2))
        after = server.scrape()
        judge_checkpoint(server, snap, after)
        judge_health(server, after, "device")
        say(f"device: load phase {time.monotonic() - t0:.1f}s wall "
            f"(start-up and compile included)")
        # Restart on the same data file: every acknowledged write is
        # there (checkpoint restore + WAL replay through the engine).
        d.client.close()
        server.stop()
        t1 = time.monotonic()
        server.start()
        server.wait_listening()
        d.client = d.connect()
        d.lookup_all_accounts("after restart")
        d.lookup_transfer_sample(size["sample"])
        judge_health(server, server.scrape(), "device")
        say(f"device: restart and read-back {time.monotonic() - t1:.1f}s wall")
    finally:
        d.close()
        server.stop()
    say(f"device: {d.compared} replies compared with the oracle, "
        f"{d.mismatched} differ")
    return device


def phase_host_engine(tmp: str, seed: int, size: dict) -> None:
    """The default engine: C computes the codes, the chip holds the
    write-behind table (kernel_fast.DeviceTable); its checksum against
    the mirror runs at the checkpoint when TB_CKPT_VERIFY=1."""
    w = Workload(seed + 1, size)
    n = size["host_requests"]
    server = make_server("host", tmp, child_env({"TB_CKPT_VERIFY": "1"}),
                         transfer_capacity(size, n + 1))
    server.start()
    server.wait_listening()
    d = Driver(server, Oracle(), w)
    try:
        d.create_accounts()
        for i in range(n):
            d.transfers(f"plain #{i}", w.plain())
        d.lookup_all_accounts("after load")
        d.lookup_transfer_sample(size["sample"])
        snap = server.scrape()
        d.pad_to_checkpoint(snap)
        after = server.scrape()
        judge_checkpoint(server, snap, after)
        judge_health(server, after, "host")
    finally:
        d.close()
        server.stop()
    say(f"host: {d.compared} replies compared with the oracle, "
        f"{d.mismatched} differ")


def chip_env(index: int) -> dict:
    """One chip for one process, by the TPU runtime's own settings:
    which chip is visible, that the process is a 1x1x1 slice of its
    own, and a runtime port no other replica uses."""
    port = free_port()
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
    }


def phase_replicated(tmp: str, seed: int, size: dict) -> dict:
    """Three replicas, TB_ENGINE=device, one chip each; the fourth chip
    stays idle.  Commits go through the VSR quorum."""
    w = Workload(seed + 2, size)
    n = size["quorum_requests"]
    addresses = ",".join(f"127.0.0.1:{free_port()}" for _ in range(3))
    servers = []
    for i in range(3):
        data = os.path.join(tmp, f"replica{i}.tigerbeetle")
        run_cli(["format", f"--cluster={CLUSTER}", f"--replica={i}",
                 "--replica-count=3", data], f"replica{i}.log")
        env = child_env({"TB_ENGINE": "device",
                         "TB_DEV_PREWARM": "orderfree_tight", **chip_env(i)})
        servers.append(Server(f"replica{i}", data, addresses, i, env,
                              transfer_capacity(size, n + 1)))
    for s in servers:
        s.start()
    devices = [s.wait_listening() for s in servers]
    d = Driver(servers[0], Oracle(), w)
    try:
        d.create_accounts()
        for i in range(n):
            d.transfers(f"plain #{i}", w.plain())
        d.lookup_all_accounts("after load")
        d.lookup_transfer_sample(size["sample"])
        # Backups commit behind the primary: wait for one commit number.
        deadline = time.monotonic() + 120
        while True:
            roots = [scrape_state_root(s.address, CLUSTER) for s in servers]
            if len({op for _root, op in roots}) == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        say("replicated: (state root, commit) per replica: "
            + json.dumps([(root.hex(), op) for root, op in roots]))
        check(len({op for _r, op in roots}) == 1,
              f"replicated: commit numbers differ: {[op for _r, op in roots]}")
        check(len({root for root, _op in roots}) == 1 and any(roots[0][0]),
              "replicated: state-commitment roots differ or are empty")
        snaps = [s.scrape() for s in servers]
        for s, snap in zip(servers, snaps):
            judge_health(s, snap, "device")
            dev = snap.get("device") or {}
            check(dev.get("count") == 1,
                  f"{s.name}: holds {dev.get('count')} devices, not 1")
            say(f"{s.name}: chip {dev.get('chips')!r}, device ids "
                f"{dev.get('ids')}, {snap.get('sm.dev.semantic_events')} events "
                "with result codes computed on its device")
            check(snap.get("sm.dev.semantic_events", 0) == n * size["batch"],
                  f"{s.name}: {snap.get('sm.dev.semantic_events')} device-"
                  f"computed events, expected {n * size['batch']}")
        chips = [(snap.get("device") or {}).get("chips") for snap in snaps]
        check(len(set(chips)) == 3 and None not in chips,
              f"replicated: replicas do not hold three distinct chips: {chips}")
    finally:
        d.close()
        for s in servers:
            s.stop()
    say(f"replicated: {d.compared} replies compared with the oracle, "
        f"{d.mismatched} differ")
    # The replicas have exited and released their chips: what the whole
    # host holds, as JAX reports it to an unrestricted process.
    log = os.path.join(LOG_DIR, "host_devices.log")
    with open(log, "wb") as err:
        out = subprocess.run(
            [sys.executable, "-m", "tigerbeetle_tpu.device"], cwd=REPO,
            stdout=subprocess.PIPE, stderr=err, env=child_env({}), timeout=300,
        )
    if out.returncode != 0:
        raise SmokeError(f"device listing exited {out.returncode}: {tail(log)}")
    host = json.loads(out.stdout.decode().strip().splitlines()[-1])
    say(f"replicated: the host holds {json.dumps(host)}")
    check(host["platform"] == devices[0]["platform"] and host["count"] == 4,
          f"replicated: the host holds {host['count']} {host['platform']} "
          "devices, not 4")
    return host


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--no-rebuild", action="store_true")
    ap.add_argument("--four-chips", action="store_true")
    args = ap.parse_args(argv)
    global _rehearsal
    _rehearsal = args.size == "tiny"
    os.makedirs(LOG_DIR, exist_ok=True)
    size = SIZES[args.size]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_")
    t0 = time.monotonic()
    device = None
    try:
        build_native(force=not args.no_rebuild)
        if args.four_chips:
            device = phase_replicated(tmp, args.seed, size)
        else:
            device = phase_device_engine(tmp, args.seed, size)
            phase_host_engine(tmp, args.seed, size)
    except SmokeError as exc:
        check(False, str(exc))
    except Exception as exc:  # noqa: BLE001 — a smoke reports, then fails
        import traceback

        traceback.print_exc()
        check(False, f"{type(exc).__name__}: {exc}")
    finally:
        reap_all()
        shutil.rmtree(tmp, ignore_errors=True)
    say(f"chip_smoke: {time.monotonic() - t0:.1f}s wall, size {args.size}, "
        f"seed {args.seed}, logs in {os.path.relpath(LOG_DIR, REPO)}/")
    assert "jax" not in sys.modules, "the parent must never import JAX"
    if _failures or device is None:
        say(f"chip_smoke: FAILED ({len(_failures)}): " + " | ".join(_failures))
        sys.stdout.flush()
        return 1
    say(success_line(device))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
