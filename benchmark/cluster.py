"""A cluster of daemons for one run: one ``master`` process and N ``volume``
processes, each volume server with a data directory of its own and — where
the configuration gives every server a chip — its own chip of the host
(``volume -ec.chip <i>``, the program's documented way: docs/SCALING.md).
This side only speaks HTTP to them and never imports JAX.

An untraced, uncontrolled run starts ``python -m seaweedfs_tpu master`` and
``python -m seaweedfs_tpu volume ...`` and nothing else. The wrong-codec
control and the rehearsal start every volume server through
``daemon_main.py``; so does a traced run, each with a profiler control port
and a trace directory of its own, because which server the master grows the
volume on — the one that dies — is known only after the load. The profiler
is then started in ONE survivor (only the process that holds a chip can
trace it), and ``keep_trace`` moves its directory to where ``run.py`` reduces.

Ports: a daemon binds its own port, many seconds after it was started (a
volume server opens its chip first), so a port picked here can be gone by
then. They are drawn below every ephemeral range the benchmark has met
(``daemon.pick_port``), where no outgoing connection and no ``bind`` to
port 0 lands. A daemon that exits
before it serves, on ``Address already in use`` or on anything else, is
started again on other ports, ``START_TRIES`` times in all, and each such
start says so on a ``[retry]`` line: a machine without its chips ends the
run with no result all the same, four starts later. What this side asks of
a daemon that is up (``/status``, ``/dir/status``) is asked again where the
answer does not come (``asked``). Every process is a session of its own and
is killed by group when the fixture leaves, however it leaves.
"""

from __future__ import annotations

import contextlib
import http.client
import os
import signal
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

from .daemon import PORTS  # noqa: F401  (the range, under this module's name too)
from .daemon import HERE, ROOT, get_json, pick_port, post_json

START_TRIES = 4
ASK_TRIES = 3


def asked(what: str, ask):
    """``ask()``, asked again (``ASK_TRIES`` in all, a second apart) where
    no answer comes: a daemon that stood still for some seconds with its
    machine is late, not gone. The last failure is the caller's."""
    for left in range(ASK_TRIES - 1, -1, -1):
        try:
            return ask()
        except (OSError, http.client.HTTPException, ValueError) as e:
            if not left:
                raise
            print(f"[retry] {what}: {e!r}; asked again", flush=True)
            time.sleep(1.0)


def answers(path: str):
    """A readiness test: the daemon answers ``GET path`` with JSON."""
    def ready(p: "Process") -> bool:
        get_json(f"http://{p.url}{path}", timeout=5.0)
        return True
    return ready


class Process:
    """One daemon: its command (made from the port it is given), its log,
    its process group."""

    def __init__(self, name: str, log_path: str, command, ready):
        self.name, self.log_path = name, log_path
        self._command, self._ready = command, ready
        self.port = 0
        self.proc: subprocess.Popen | None = None
        self.start_wall_s = 0.0

    @property
    def url(self) -> str:
        return f"127.0.0.1:{self.port}"

    def start(self, env: dict, timeout: float = 240.0) -> "Process":
        t0 = time.monotonic()
        for tried in range(START_TRIES):
            self.port = pick_port()
            cmd = self._command(self.port)
            with open(self.log_path, "ab") as log:
                log.write(f"\n==== {' '.join(cmd)}\n".encode())
                log.flush()
                self.proc = subprocess.Popen(
                    cmd, cwd=ROOT, env=env, stdout=log,
                    stderr=subprocess.STDOUT, start_new_session=True,
                )
            if self._wait(t0 + timeout):
                self.start_wall_s = time.monotonic() - t0
                return self
            print(f"[retry] {self.name} exited with {self.proc.returncode} "
                  f"before it served, start {tried + 1} of {START_TRIES}: "
                  + " | ".join(self.log_tail(3).splitlines()), flush=True)
        raise SystemExit(
            f"{self.name} exited with {self.proc.returncode} before it "
            f"served:\n{self.log_tail()}"
        )

    def _wait(self, deadline: float) -> bool:
        """True once the daemon answers; False if it exited first."""
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                return False
            try:
                if self._ready(self):
                    return True
            except (OSError, KeyError, ValueError):
                pass
            time.sleep(0.1)
        self.kill()
        raise SystemExit(f"{self.name} not ready in time:\n{self.log_tail()}")

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def kill(self) -> None:
        """SIGKILL to the whole group: the process and its stragglers."""
        if self.proc is None:
            return
        with contextlib.suppress(ProcessLookupError):
            os.killpg(self.proc.pid, signal.SIGKILL)
        self.proc.wait(timeout=30)

    def stop(self, grace_s: float = 5.0) -> None:
        if self.alive():
            with contextlib.suppress(ProcessLookupError):
                os.killpg(self.proc.pid, signal.SIGINT)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        self.kill()

    def log_tail(self, lines: int = 30) -> str:
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-lines:]).decode(
                "utf-8", "replace"
            )


class Cluster:
    """``with Cluster(...) as c``: the master and every volume server up and
    registered; all of them gone, by group, on the way out."""

    def __init__(self, data_root: str, out_dir: str, cluster_cfg: dict,
                 trace_dir: str = "", control: str = "",
                 rehearsal: bool = False):
        self.cfg = cluster_cfg
        self.control, self.rehearsal = control, rehearsal
        self.trace_dir = trace_dir  # "" = an untraced run
        self.n = cluster_cfg["volume_servers"]
        self.control_ports = [0] * self.n  # drawn with each start
        self.dirs = [os.path.join(data_root, f"srv{i}") for i in range(self.n)]
        for d in (*self.dirs, out_dir):
            os.makedirs(d, exist_ok=True)
        self.master_proc = Process(
            "master", os.path.join(out_dir, "master.log"),
            self._master_command, answers("/dir/status"),
        )
        self.servers = [
            Process(f"volume server {i}", os.path.join(out_dir, f"volume{i}.log"),
                    lambda port, i=i: self._volume_command(i, port),
                    answers("/status"))
            for i in range(self.n)
        ]

    # -- commands ---------------------------------------------------------------
    @property
    def master(self) -> str:
        return self.master_proc.url

    def _master_command(self, port: int) -> list[str]:
        return [sys.executable, "-m", "seaweedfs_tpu", "master", "-port",
                str(port), *self.cfg["master"].get("args", [])]

    def _volume_command(self, i: int, port: int) -> list[str]:
        v = self.cfg["volume"]
        server = ["volume", "-port", str(port), "-dir", self.dirs[i],
                  "-mserver", self.master, *v.get("args", [])]
        if v.get("chip_each"):
            server += ["-ec.chip", str(i)]
        backend = "" if self.rehearsal else v.get("ec_backend", "")
        if backend:
            server += ["-ec.backend", backend]
        if not (self.trace_dir or self.control or self.rehearsal):
            return [sys.executable, "-m", "seaweedfs_tpu", *server]
        wrap = [sys.executable, os.path.join(HERE, "daemon_main.py")]
        if self.trace_dir:
            self.control_ports[i] = pick_port()
            wrap += ["--trace-dir", f"{self.trace_dir}-srv{i}",
                     "--control-port", str(self.control_ports[i])]
        if self.control:
            wrap += ["--control", self.control]
        if self.rehearsal:
            wrap += ["--rehearsal"]
        return wrap + ["--", *server]

    # -- readiness --------------------------------------------------------------

    def __enter__(self) -> "Cluster":
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        try:
            self.master_proc.start(env, timeout=60.0)
            # side by side: each opens its own chip, which takes the longest
            with ThreadPoolExecutor(self.n) as pool:
                list(pool.map(lambda p: p.start(env), self.servers))
            self._wait_registered()
        except BaseException:
            self.stop()
            raise
        return self

    def _wait_registered(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        urls: set[str] = set()
        while time.monotonic() < deadline:
            urls = self.registered()
            if urls >= {p.url for p in self.servers}:
                return
            time.sleep(0.1)
        raise SystemExit(f"the master lists {sorted(urls)} of {self.n} servers")

    def registered(self) -> set[str]:
        """The volume servers the master's topology lists now."""
        topo = asked("the master's /dir/status", lambda: get_json(
            f"http://{self.master}/dir/status", timeout=5.0))["topology"]
        return {
            node["url"]
            for dc in topo.get("data_centers", [])
            for rack in dc.get("racks", [])
            for node in rack.get("nodes", [])
        }

    def stop(self) -> None:
        """Every process gone, by group; harmless when called again."""
        for p in (*self.servers, self.master_proc):
            p.stop()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- one server ---------------------------------------------------------------
    def codec(self, i: int) -> dict:
        return asked(f"volume server {i}'s /status", lambda: get_json(
            f"http://{self.servers[i].url}/status", timeout=10.0
        ))["ec_codec"]

    def profiler(self, i: int, verb: str) -> dict:
        """start / stop the profiler inside server ``i``."""
        return post_json(
            f"http://127.0.0.1:{self.control_ports[i]}/{verb}", timeout=300.0
        )

    def keep_trace(self, i: int) -> None:
        """Server ``i``'s trace to where ``run.py`` reduces one."""
        os.rename(f"{self.trace_dir}-srv{i}", self.trace_dir)
