"""Children of a run: the native build, `format`, and the servers.

Copies of what `chip_smoke.py` proved on the chip (`Server`,
`chip_env`, `build_native`), kept here so that later PRs may edit the
smoke but not the yardstick.  Every child's output goes to a file
under the run's directory; the parent never imports JAX.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# A span only the primary feeds (vsr/multi.py: building a prepare); a
# backup's stays at nought.  The scrape names no view and no primary.
PREPARES_KEY = "vsr.prepare_us.count"


class RunError(RuntimeError):
    """A phase could not run at all (build, start-up, lost server)."""


def tail(path: str, n: int = 1500) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")
    except OSError as exc:
        return repr(exc)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(extra: dict) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.pop("TB_ENGINE", None)
    env.update({k: str(v) for k, v in extra.items()})
    return env


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


def build_native(log_dir: str) -> float:
    """`make -C native`: the libraries are built once per checkout (make
    sees them up to date afterwards).  -> seconds it took."""
    t0 = time.monotonic()
    cmd = ["make", "-C", os.path.join(REPO, "native")]
    log = os.path.join(log_dir, "make.log")
    with open(log, "wb") as f:
        rc = subprocess.call(cmd, stdout=f, stderr=subprocess.STDOUT)
    if rc != 0:
        raise RunError(f"`{' '.join(cmd)}` exited {rc}: {tail(log)}")
    return time.monotonic() - t0


class Cluster:
    """The replicas of one configuration, each a child started through
    the configuration's launcher (`harness/serve.py`)."""

    def __init__(self, config: dict, run_dir: str, trace: bool,
                 four_chip_host: bool) -> None:
        self.config = config
        self.run_dir = run_dir
        self.trace = trace
        self.cluster_id = int(config["cluster"])
        self.replicas = int(config["replicas"])
        self.addresses = ",".join(
            f"127.0.0.1:{free_port()}" for _ in range(self.replicas)
        )
        self.servers: list[Server] = []
        server = config["server"]
        launcher = os.path.join(REPO, config["launcher"])
        for i in range(self.replicas):
            env = dict(server.get("env", {}))
            if four_chip_host:
                # A chip belongs to one process: each replica gets its
                # own, the rest of the host's chips stay idle.
                env.update(chip_env(i))
            self.servers.append(Server(
                self, i, launcher, child_env(env), server.get("flags", {}),
            ))

    def format(self) -> None:
        for s in self.servers:
            log = os.path.join(self.run_dir, f"replica{s.replica}.log")
            with open(log, "ab") as f:
                rc = subprocess.call(
                    [sys.executable, "-m", "tigerbeetle_tpu", "format",
                     f"--cluster={self.cluster_id}", f"--replica={s.replica}",
                     f"--replica-count={self.replicas}", s.data],
                    stdout=f, stderr=subprocess.STDOUT, cwd=REPO,
                    env=child_env({}),
                )
            if rc != 0:
                raise RunError(f"format of replica {s.replica} exited {rc}: "
                               + tail(log))

    def start(self, deadline_s: float) -> list[dict]:
        for s in self.servers:
            s.start()
        return [s.wait_listening(deadline_s) for s in self.servers]

    @property
    def primary(self) -> "Server":
        """The first of `servers` (run.py locate_primary puts it there).
        A run in whose window another replica has prepared (the view
        changed under it) is not correct (run.py health)."""
        return self.servers[0]

    def stop(self) -> list[str]:
        """SIGTERM to every server, wait for each.  -> what went wrong."""
        problems = []
        for s in self.servers:
            s.signal_stop()
        for s in self.servers:
            problem = s.wait_stopped()
            if problem:
                problems.append(problem)
        return problems

    def kill(self) -> None:
        for s in self.servers:
            s.kill()


class Server:
    def __init__(self, cluster: Cluster, replica: int, launcher: str,
                 env: dict, flags: dict) -> None:
        self.cluster = cluster
        self.replica = replica
        self.name = f"replica{replica}"
        self.address = cluster.addresses.split(",")[replica]
        self.data = os.path.join(cluster.run_dir, f"{self.name}.tigerbeetle")
        self.log = os.path.join(cluster.run_dir, f"{self.name}.log")
        self.ctl = os.path.join(cluster.run_dir, f"{self.name}.ctl")
        os.makedirs(self.ctl, exist_ok=True)
        self.env = {**env, "TB_FLIGHT_PATH": os.path.join(
            cluster.run_dir, f"{self.name}_flight.json")}
        self.argv = [
            sys.executable, launcher, "--ctl", self.ctl,
            "--trace", "1" if cluster.trace else "0", "--",
            f"--addresses={cluster.addresses}", f"--replica={replica}",
            *[f"--{k}={v}" for k, v in flags.items()], self.data,
        ]
        self.proc: subprocess.Popen | None = None
        self.device: dict | None = None
        self._died_before: int | None = None

    def start(self) -> None:
        with open(self.log, "ab") as f:
            self.proc = subprocess.Popen(
                self.argv, stdout=f, stderr=subprocess.STDOUT, cwd=REPO,
                env=self.env,
            )

    def wait_listening(self, deadline_s: float) -> dict:
        """-> the device the server says it holds (its start-up line)."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            with open(self.log, "rb") as f:
                text = f.read().decode("utf-8", "replace")
            for line in text.splitlines():
                if line.startswith("listening on port") and "device=" in line:
                    self.device = json.loads(line.split("device=", 1)[1])
                    return self.device
            if self.proc.poll() is not None:
                raise RunError(f"{self.name} exited {self.proc.returncode} "
                               f"before listening: {tail(self.log)}")
            time.sleep(0.1)
        raise RunError(f"{self.name} not listening after {deadline_s:.0f}s: "
                       + tail(self.log))

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def scrape(self, timeout_ms: int = 60_000) -> dict:
        from tigerbeetle_tpu.obs.scrape import scrape_stats

        if not self.alive():
            raise RunError(f"{self.name} died: {tail(self.log)}")
        return scrape_stats(self.address, self.cluster.cluster_id,
                            timeout_ms=timeout_ms)

    def state_root(self) -> tuple[bytes, int]:
        from tigerbeetle_tpu.obs.scrape import scrape_state_root

        return scrape_state_root(self.address, self.cluster.cluster_id,
                                 timeout_ms=60_000)

    # The launcher's control directory: the parent touches `<what>.go`,
    # the child's control thread answers with `<what>.json`.

    def ask(self, what: str) -> None:
        with open(os.path.join(self.ctl, what + ".go"), "w"):
            pass

    def answer(self, what: str, deadline_s: float) -> dict:
        path = os.path.join(self.ctl, what + ".json")
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            if not self.alive():
                raise RunError(f"{self.name} died: {tail(self.log)}")
            time.sleep(0.005)
        raise RunError(f"{self.name}: no answer to {what!r} after "
                       f"{deadline_s:.0f}s: {tail(self.log)}")

    def signal_stop(self) -> None:
        if self.proc is None:
            return
        self._died_before = self.proc.poll()
        if self._died_before is None:
            self.proc.send_signal(signal.SIGTERM)

    def wait_stopped(self) -> str | None:
        """A serving `start` answers SIGTERM by writing its flight
        record and dying of the signal (runtime/server.py); an exit of
        its own before the signal, or another code, is a failure."""
        if self.proc is None:
            return None
        try:
            rc = self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            rc = self.proc.wait()
        self.proc = None
        if self._died_before is None and rc in (0, -signal.SIGTERM):
            return None
        when = "before" if self._died_before is not None else "on"
        return f"{self.name}: exit code {rc} ({when} SIGTERM): " + tail(self.log, 600)

    def kill(self) -> None:
        if self.proc is not None:
            if self.proc.poll() is None:
                self.proc.kill()
            self.proc.wait()
            self.proc = None
