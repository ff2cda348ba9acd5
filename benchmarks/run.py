#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX (a chip belongs to one process).  It
builds the native libraries, formats and starts the configuration's
replicas through `benchmarks/harness/serve.py`, drives them over TCP
with the program's own client from the traffic's sessions, and decides
`correct` by comparing everything the served path answered with the
traffic kind's plain reference (`harness/compare.py`).

Set-up (everything before the window opens): build, format, server
start to "listening", client registration, accounts, the warm phases
(every shape the window uses), and the one-id lookups that bring the
primary's commit number to the traffic's phase of the checkpoint cycle.

Last line of standard output, through `json.dumps`, after every child
has been reaped: `correct`, `attempted`, `failed`, `metrics`, `device`
(with `--trace 1` also `breakdown`), then `info` and, last, `compared`:
every number that decided `correct`, beside its limit.  The same
numbers are the last lines of standard error.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.  `--rehearsal` (tests only) skips that
look, runs the configuration's and the traffic's `rehearsal` sizes on
whatever backend the servers find, prints the line and exits 3: a
rehearsal is never a measurement.  `--control <name>` (proving runs
only) also puts the control in the program's place and reports what
the comparison then says, under `control`.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

try:
    import numpy as np
    from benchmarks.harness import cluster as cl
    from benchmarks.harness import compare, load, manifest as mf, wire
    from tigerbeetle_tpu import constants as cfg       # no JAX in these
    from tigerbeetle_tpu.client import Client
    from tigerbeetle_tpu.types import Operation
except ImportError as exc:
    sys.exit(f"benchmarks/run.py: the checkout is not whole: {exc}")

REHEARSAL_EXIT = 3
GIVE_UP_S = 1150.0      # a first run, which compiles, may take 1200 s; none may hang
_ENGINE_FAULT_KEYS = (
    "sm.dev.demotions", "sm.dev.link.errors", "sm.dev.link.retries",
    "sm.dev.probe_failures", "sm.dev.scrub_heals", "sm.dev.commit.repairs",
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def step(what: str) -> None:
    """Where the run stands, for whoever reads a run that stopped."""
    log(f"[{time.perf_counter() - _T_START:7.1f}s] {what}")


class Run:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.manifest = mf.Manifest(args.manifest, args.data_root)
        self.cell = self.manifest.cell(args.workload)
        self.config = self.manifest.config(self.cell)
        self.traffic = self.manifest.traffic(self.cell)
        if args.rehearsal:
            self.config = {**self.config, **self.config.get("rehearsal", {})}
            self.traffic = {**self.traffic, **self.traffic.get("rehearsal", {})}
        self.platform = "cpu" if args.rehearsal else "tpu"
        self.trace = args.trace == 1
        # Data files, logs and traces of this run; the compile cache is
        # not here (it keeps its fixed place in the checkout).
        self.run_dir = args.run_dir or os.path.join(
            cl.REPO, ".bench_run", self.cell["name"])
        self.gen = mf.generator_kind(self.traffic).make(
            self.traffic, self.config, args.seed)
        self.cluster: cl.Cluster | None = None
        self.sessions: load.Sessions | None = None
        self.admin: Client | None = None
        self.win: load.Window | None = None
        self.info: dict = {}

    # ------------------------------------------------------------------
    # Set-up

    def start(self) -> None:
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        step(f"cell {self.cell['name']}, seed {self.args.seed}: build, format, start")
        self.info["native_build_s"] = cl.build_native(self.run_dir)
        replicas = int(self.config["replicas"])
        if self.cell["chips"] < replicas * int(self.config["chips_per_replica"]):
            raise cl.RunError(f"cell {self.cell['name']}: {replicas} replicas "
                              f"do not fit {self.cell['chips']} chip(s)")
        self.cluster = cl.Cluster(
            self.config, self.run_dir, self.trace,
            four_chip_host=self.cell["chips"] > 1 and not self.args.rehearsal,
        )
        self.cluster.format()
        t0 = time.perf_counter()
        devices = self.cluster.start(deadline_s=1100.0)
        self.info["servers_listening_s"] = time.perf_counter() - t0
        for s, dev in zip(self.cluster.servers, devices):
            if dev["platform"] != self.platform:
                raise cl.RunError(f"{s.name}: platform is {dev['platform']!r}, "
                                  f"not {self.platform!r}")
            if not self.args.rehearsal and dev["count"] != int(
                    self.config["chips_per_replica"]):
                raise cl.RunError(f"{s.name} holds {dev['count']} devices, not "
                                  f"{self.config['chips_per_replica']}")

    def prepare(self) -> None:
        """Accounts, warm requests, and the window's phase."""
        c = self.cluster
        step("servers listening; accounts and warm requests")
        self.admin = Client(c.addresses, c.cluster_id, timeout_ms=900_000)
        accounts = self.gen.accounts()
        for at in range(0, len(accounts), wire.REQUEST_EVENTS_MAX):
            reply = self.admin.request(
                Operation.create_accounts,
                accounts[at:at + wire.REQUEST_EVENTS_MAX].tobytes())
            if reply:
                raise cl.RunError(f"create_accounts[{at}:] answered "
                                  f"{len(reply) // 8} failures")
        self.sessions = load.Sessions(
            c.addresses, c.cluster_id, self.gen, int(self.traffic["sessions"]),
            timeout_ms=900_000)          # a first request may wait on a compile
        self.sessions.connect()
        # Every session finishes a phase before any starts the next.
        for n in self.traffic["warm_requests_per_session"]:
            bad = [r for r in self.sessions.fixed(int(n)) if r.reply is None]
            if bad:
                raise cl.RunError(f"warm request failed: {bad[0].error}")
        # Everything is compiled: from here no request may take long.
        self.sessions.set_timeout(int(self.traffic["request_timeout_ms"]))
        self.admin.timeout_ms = int(self.traffic["request_timeout_ms"])
        self.locate_primary()
        step("warm; lookups to the window's phase")
        self.pad_to_phase()

    def locate_primary(self) -> None:
        """Whose counters and trace the readers take for the primary's:
        the replica that prepares one more request now.  That is
        replica 0, unless the replicas changed the view while they came
        up or warmed.  It goes first in `cluster.servers`; the clients
        are told nothing and keep the configuration's addresses."""
        servers = self.cluster.servers
        if len(servers) == 1:
            return
        was = [int(s.get(cl.PREPARES_KEY, 0)) for s in self.scrape_all()]
        self.admin.request(Operation.lookup_accounts, wire.ids_body([1]))
        now = [int(s.get(cl.PREPARES_KEY, 0)) for s in self.scrape_all()]
        leads = [i for i in range(len(servers)) if now[i] > was[i]]
        if len(leads) != 1:
            raise cl.RunError(f"no single primary after the warm requests: "
                              f"prepares made by each replica {was} -> {now}")
        servers.insert(0, servers.pop(leads[0]))
        self.info["primary"] = servers[0].replica

    def pad_to_phase(self) -> None:
        """One-id lookups (one committed op each) until the primary's
        commit number stands `offset_ops` after a checkpoint, with
        `cross_checkpoints_before` checkpoints behind it: the programs a
        checkpoint runs are then warm, and every window of the cell
        holds the same number of checkpoints."""
        phase = self.traffic.get("phase")
        if not phase:
            return
        interval = cfg.PRODUCTION.vsr_checkpoint_interval
        offset = int(phase["offset_ops"]) % interval
        t0 = time.perf_counter()
        sent = 0
        body = wire.ids_body([1])
        for _attempt in range(4):
            at = int(self.cluster.primary.scrape()["vsr.commit_min"])
            target = max(at, int(phase["cross_checkpoints_before"]) * interval)
            target += (offset - target) % interval
            if target == at:
                break
            for _ in range(target - at):
                self.admin.request(Operation.lookup_accounts, body)
            sent += target - at
        else:
            raise cl.RunError(f"commit number {at} does not settle at the "
                              f"window's phase ({offset} mod {interval})")
        self.info.update(window_open_op=at, phase_lookups=sent,
                         phase_lookups_s=time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # The window

    def scrape_all(self) -> list[dict]:
        """Every replica at once, so that one replica's numbers are not
        later than another's by a scrape's own time."""
        servers = self.cluster.servers
        out: list = [None] * len(servers)

        def one(i: int) -> None:
            try:
                out[i] = servers[i].scrape()
            except Exception as exc:  # noqa: BLE001 — re-raised below
                out[i] = exc

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(servers))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for x in out:
            if isinstance(x, Exception):
                raise cl.RunError(f"scrape failed: {x!r}")
        return out

    def traced_slice(self, win: load.Window) -> dict:
        """Inside the window: start the profiler in every replica
        `lead_s` after the window opens, read the primary's counters
        at both ends of the slice, stop `slice_s` later."""
        servers = self.cluster.servers
        spec = self.traffic["trace"]
        t_start = win.t0 + float(spec["lead_s"])
        t_stop = t_start + float(spec["slice_s"])
        if t_stop > win.t1:
            raise cl.RunError(f"a traced slice of {spec} does not fit a "
                              f"window of {win.t1 - win.t0:.1f} s")
        time.sleep(max(0.0, t_start - time.perf_counter()))
        for s in servers:
            s.ask("trace_start")
        started = [s.answer("trace_start", 120.0) for s in servers]
        a = servers[0].scrape()
        if time.perf_counter() > t_stop:
            raise cl.RunError("the profiler started too late for its slice")
        time.sleep(max(0.0, t_stop - time.perf_counter()))
        b = servers[0].scrape()
        for s in servers:
            s.ask("trace_stop")
        stopped = [s.answer("trace_stop", 240.0) for s in servers]
        if any("error" in x for x in started + stopped):
            raise cl.RunError(f"trace control failed: {started} {stopped}")
        events = sum(b.get(k, 0) - a.get(k, 0) for k in (
            "sm.dev.semantic_events", "sm.host_semantic_events"))
        return {
            "window_s": min(x["t"] for x in stopped) - max(x["t"] for x in started),
            "requests": int(b["vsr.commit_min"] - a["vsr.commit_min"]),
            "events": int(events),
            "stop_took_s": max(x["stop_took_s"] for x in stopped),
        }

    def measure(self) -> dict:
        seconds = float(self.args.seconds)
        before = self.scrape_all()
        setup_s = time.perf_counter() - _T_START
        step(f"window of {seconds:g} s opens")
        self.win = win = self.sessions.window(
            seconds, self.traffic, mf.loop_kind(self.traffic))
        slice_ = self.traced_slice(win) if self.trace else None
        while time.perf_counter() < win.t1:
            for s in self.cluster.servers:
                if not s.alive():
                    raise cl.RunError(f"{s.name} died in the window: "
                                      + cl.tail(s.log))
            time.sleep(min(0.2, max(0.0, win.t1 - time.perf_counter())))
        at_close = self.scrape_all()
        records = win.join()
        step(f"window closed: {len(records)} requests")
        after = self.scrape_all()
        with open(os.path.join(self.run_dir, "scrapes.json"), "w") as f:
            json.dump({"before": before, "at_close": at_close, "after": after}, f)
        return {"setup_s": setup_s, "t0": win.t0, "t1": win.t1,
                "records": records, "before": before, "at_close": at_close,
                "after": after, "slice": slice_}

    # ------------------------------------------------------------------
    # After the window: what the served path holds

    def read_back(self, sample: list) -> dict:
        ids = np.arange(1, self.gen.n_accounts + 1, dtype=np.uint64)
        rows = []
        for at in range(0, len(ids), wire.REQUEST_EVENTS_MAX):
            reply = self.admin.request(
                Operation.lookup_accounts,
                wire.ids_body(ids[at:at + wire.REQUEST_EVENTS_MAX]))
            rows.append(wire.masked(wire.ACCOUNT, reply))
        transfers = []
        for r in sample:
            want_ids = self.gen.request(r.session, r.index)["id_lo"]
            reply = self.admin.request(Operation.lookup_transfers,
                                       wire.ids_body(want_ids[want_ids != 0]))
            transfers.append(wire.masked(wire.TRANSFER, reply))
        return {
            "accounts": np.concatenate(rows),
            "transfers": (np.concatenate(transfers) if transfers
                          else np.zeros(0, wire.TRANSFER)),
        }

    def replicas_disagreeing(self) -> int:
        servers = self.cluster.servers
        if len(servers) == 1:
            return 0
        deadline = time.monotonic() + 60
        while True:
            roots = [s.state_root() for s in servers]
            if len({op for _r, op in roots}) == 1 or time.monotonic() > deadline:
                break
            time.sleep(0.2)
        self.info["state_roots"] = [[root.hex(), op] for root, op in roots]
        empty = 0 if any(roots[0][0]) else 1
        return empty + sum(1 for x in roots[1:] if x != roots[0])

    def health(self, records: list, at_open: list[dict]) -> dict:
        """After the replicas agree: engine state and who computed the
        result codes, over every replica (chip_smoke.py phase 3);
        `at_open`: every replica's scrape as the window opened."""
        disagreeing = self.replicas_disagreeing()
        sent = sum(r.events for r in records if r.reply is not None)
        faults = 0
        off_device = 0
        unaccounted = 0
        for s, snap, was in zip(self.cluster.servers, self.scrape_all(), at_open):
            dev = snap.get("device") or {}
            faults += sum(int(snap.get(k, 0)) for k in _ENGINE_FAULT_KEYS)
            faults += int(dev.get("platform") != self.platform)
            faults += int(dev.get("engine") != self.config["server"]["env"].get(
                "TB_ENGINE", "host"))
            faults += int(dev.get("state") != "healthy"
                          or dev.get("last_demotion") is not None)
            # A backup that has prepared since: the view changed under
            # the window.
            faults += int(s is not self.cluster.primary
                          and int(snap.get(cl.PREPARES_KEY, 0))
                          > int(was.get(cl.PREPARES_KEY, 0)))
            on_device = int(snap.get("sm.dev.semantic_events", 0))
            # A flagged batch that falls back is counted with the host's.
            on_host = int(snap.get("sm.host_semantic_events", 0))
            # The chip has to compute the result codes of every event.
            off_device = max(off_device, sent - on_device)
            unaccounted = max(unaccounted, abs(sent - on_device - on_host))
            self.info.setdefault("compile", []).append(dev.get("compile"))
        return {"events_not_on_device": off_device,
                "events_unaccounted": unaccounted, "engine_faults": faults,
                "replicas_disagreeing": disagreeing}

    def memory_peak(self) -> int:
        for s in self.cluster.servers:
            s.ask("mem")
        stats = [s.answer("mem", 60.0) for s in self.cluster.servers]
        return max(int(x.get("peak_bytes_in_use", 0)) for x in stats)

    # ------------------------------------------------------------------

    def host_devices(self) -> subprocess.Popen:
        """What the whole host holds, as JAX reports it to an
        unrestricted process, once the replicas have released their
        chips (a four-chip cell gives each replica one)."""
        with open(os.path.join(self.run_dir, "host_devices.log"), "wb") as err:
            return subprocess.Popen(
                [sys.executable, "-m", "tigerbeetle_tpu.device"], cwd=cl.REPO,
                stdout=subprocess.PIPE, stderr=err, env=cl.child_env({}))

    def reduce_traces(self) -> list[dict]:
        """One child per replica's trace, off the chip."""
        script = os.path.join(HERE, "harness", "trace_reduce.py")
        env = cl.child_env({"JAX_PLATFORMS": "cpu"})
        procs = []
        for s in self.cluster.servers:
            out = os.path.join(s.ctl, "reduced.json")
            log_f = open(os.path.join(s.ctl, "reduce.log"), "wb")
            procs.append((out, log_f, subprocess.Popen(
                [sys.executable, script, os.path.join(s.ctl, "trace"), out],
                stdout=log_f, stderr=subprocess.STDOUT, cwd=cl.REPO, env=env)))
        results = []
        for out, log_f, p in procs:
            try:
                rc = p.wait(timeout=200)
            except subprocess.TimeoutExpired:
                p.kill()
                rc = p.wait()
            log_f.close()
            if rc != 0:
                raise cl.RunError(f"trace_reduce exited {rc}: "
                                  + cl.tail(log_f.name))
            with open(out) as f:
                results.append(json.load(f))
        return results

    def give_up(self) -> None:
        """The run's last resort against a wait that never ends: no
        result, no child left behind."""
        log(f"benchmarks/run.py: no end after {GIVE_UP_S:g} s; giving up")
        if self.cluster is not None:
            self.cluster.kill()
        os._exit(1)

    def close(self) -> None:
        if self.win is not None:
            # No client is closed under a session that still uses it.
            self.win.stop = True
            self.win.join()
        if self.sessions is not None:
            self.sessions.close()
        if self.admin is not None:
            self.admin.close()
            self.admin = None


def end_to_end(m: dict) -> tuple[dict, list]:
    """The window's metrics, on the client's clock, over all requests
    acknowledged inside it."""
    seconds = m["t1"] - m["t0"]
    inside = [r for r in m["records"]
              if r.reply is not None and m["t0"] <= r.t_reply <= m["t1"]]
    latencies = sorted(r.latency_ms for r in inside)
    out = {"setup_s": m["setup_s"]}
    if inside:
        out["commit_events_per_s"] = sum(r.events for r in inside) / seconds
        out["request_p50_ms"] = load.percentile(latencies, 0.50)
        out["request_p90_ms"] = load.percentile(latencies, 0.90)
        out["request_p95_ms"] = load.percentile(latencies, 0.95)
    return out, inside


def run(args: argparse.Namespace) -> tuple[int, dict | None]:
    r = Run(args)
    watch = threading.Timer(GIVE_UP_S, r.give_up)
    watch.daemon = True
    watch.start()
    problems: list[str] = []
    try:
        r.start()
        r.prepare()
        m = r.measure()
        peak = r.memory_peak()
        step("read-back")
        sample = compare.sample_requests(
            r.sessions.records, args.seed,
            int(r.traffic["read_back"]["transfer_sample_requests"]))
        served = r.read_back(sample)
        health = r.health(r.sessions.records, m["before"])
        devices = [s.device for s in r.cluster.servers]
    except cl.RunError as exc:
        log(f"benchmarks/run.py: {exc}")
        return 1, None
    finally:
        r.close()
        if r.cluster is not None:
            problems = r.cluster.stop()
            r.cluster.kill()
    health["servers_exited_badly"] = len(problems)
    for p in problems:
        log(f"benchmarks/run.py: {p}")

    # The program's state is freed; now the reference, and the trace.
    step("servers stopped; reference and comparison")
    listing = r.host_devices() if r.cell["chips"] > len(devices) else None
    t_ref = time.perf_counter()
    records = r.sessions.records
    ref_mod = mf.generator_kind(r.traffic)
    want = compare.reference_side(r.gen, ref_mod.reference(r.gen), records, sample)
    values = compare.numbers(served, want, records, health)
    correct, table = compare.verdict(values)
    control = None
    if args.control == "lost_ack":
        lost = compare.last_write(records)
        held = compare.reference_side(r.gen, ref_mod.reference(r.gen), records,
                                      sample, drop=lost)
        c_ok, c_table = compare.verdict(compare.numbers(
            held, want, compare.answered_by(records, held["replies"]), health))
        control = {"name": "lost_ack", "correct": c_ok, "compared": c_table}
    r.info["reference_s"] = time.perf_counter() - t_ref

    e2e, inside = end_to_end(m)
    window_records = [x for x in records if x.t_send >= m["t0"]]
    device = {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
              "count": sum(d["count"] for d in devices),
              "memory_peak_bytes": peak}
    if listing is not None:
        out, _ = listing.communicate(timeout=300)
        if listing.returncode != 0:
            log("benchmarks/run.py: device listing failed: "
                + cl.tail(os.path.join(r.run_dir, "host_devices.log")))
            return 1, None
        host = json.loads(out.decode().strip().splitlines()[-1])
        if host["count"] < r.cell["chips"] and not args.rehearsal:
            log(f"benchmarks/run.py: the host holds {host['count']} chips, "
                f"the cell asks for {r.cell['chips']}")
            return 1, None
        device.update(platform=host["platform"], kind=host["kind"],
                      count=host["count"])

    line: dict = {"correct": correct, "attempted": len(window_records),
                  "failed": sum(1 for x in window_records if x.reply is None)}
    ckpts = sum(m["after"][0].get(k, 0) - m["before"][0].get(k, 0)
                for k in ("vsr.ckpt.async", "vsr.ckpt.sync"))
    # A request that waited this long, the program's client sent again.
    r.info["requests_resent"] = sum(
        1 for x in inside if x.t_reply - x.t_send >= load.RESEND_S)
    r.info.update(requests_in_window=len(inside), checkpoints_in_window=ckpts,
                  seed=args.seed, rehearsal=bool(args.rehearsal))
    if r.trace:
        step("reducing the trace")
        reduced = r.reduce_traces()
        sl = m["slice"]
        busy = [x["busy_s"] for x in reduced if x["busy_s"]]
        if busy:
            device["busy_s"] = sum(busy) / len(busy)
            device["window_s"] = sl["window_s"]
        ctx = {"before": m["before"], "after": m["after"],
               "at_close": m["at_close"], "requests": len(inside),
               "trace": {**sl, "busy_s": reduced[0]["busy_s"],
                         "device_kind": devices[0]["kind"]}}
        line["metrics"] = mf.read_layer_metrics(r.manifest, r.cell["name"], ctx)
        line["device"] = device
        line["breakdown"] = {"device_ops": reduced[0]["programs"],
                             "idle_gaps": reduced[0]["gaps"]}
        r.info["trace"] = {**sl, "xplane_bytes": [x["xplane_bytes"] for x in reduced],
                           "device_ops": [x["device_ops"] for x in reduced],
                           "busy_s": [x["busy_s"] for x in reduced]}
        r.info["end_to_end_traced"] = e2e
    else:
        units = {x["name"]: x["unit"] for x in r.manifest.end_to_end_for(r.cell["name"])}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in e2e.items() if k in units}
        line["device"] = device
    line["info"] = r.info
    if control is not None:
        line["control"] = control
    line["compared"] = table
    if correct and not args.keep:
        shutil.rmtree(r.run_dir, ignore_errors=True)
    watch.cancel()
    return (REHEARSAL_EXIT if args.rehearsal else 0), line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=mf.MANIFEST)
    ap.add_argument("--data-root", default=mf.DATA_ROOT)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--control", choices=("lost_ack",))
    ap.add_argument("--run-dir", help="where this run keeps its data files, "
                    "logs and traces (default: .bench_run/<cell> in the checkout)")
    ap.add_argument("--keep", action="store_true",
                    help="keep the run's directory (logs, traces)")
    args = ap.parse_args(argv)
    if args.rehearsal and hasattr(os, "sched_setaffinity"):
        # A rehearsal runs beside the rest of a test suite, and three
        # servers compiling take every core they see: it and its
        # children keep to two.
        os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[-2:])
    try:
        rc, line = run(args)
    except mf.ManifestError as exc:
        log(f"benchmarks/run.py: {exc}")
        return 2
    assert "jax" not in sys.modules, "the parent must never import JAX"
    if line is None:
        return rc or 1
    for name, v in line["compared"].items():
        log(f"compared {name}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
