"""The daemon child: master + volume server in one process, which owns the
chip. This side only speaks HTTP to it and never imports JAX.

An untraced, uncontrolled run starts ``python -m seaweedfs_tpu server ...``
and nothing else. A traced run, the wrong-codec control and the rehearsal
start it through ``daemon_main.py``, which wraps the program from outside.
"""

from __future__ import annotations

import contextlib
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


# A daemon binds its ports many seconds after they were drawn (it opens its
# chip first), so they are drawn where nothing else lands meanwhile: under
# every ephemeral range the benchmark has met (Linux's begins at 32768, the
# chip machines' gVisor's at 16000: outgoing connections and binds to port
# 0), clear of libtpu's 8476..8479 (``-ec.chip``)
PORTS = (10000, 15900)
_rng = random.Random()  # seeded by the OS: two harnesses draw apart
_drawn: set[int] = set()
_drawing = threading.Lock()  # a cluster's servers are started side by side


def pick_port() -> int:
    """A port of ``PORTS`` nothing listens on right now and this run has
    not drawn before: a port drawn is bound only many seconds later."""
    with _drawing:
        for _ in range(200):
            port = _rng.randrange(*PORTS)
            if port in _drawn:
                continue
            with socket.socket() as s:
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                try:
                    s.bind(("127.0.0.1", port))
                except OSError:
                    continue
            _drawn.add(port)
            return port
    raise SystemExit(f"no free port in {PORTS}")


def get_json(url: str, timeout: float = 30.0, method: str = "GET") -> dict:
    req = urllib.request.Request(url, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read() or b"{}")


def post_json(url: str, timeout: float = 30.0) -> dict:
    return get_json(url, timeout, method="POST")


class Daemon:
    def __init__(self, data_dir: str, log_path: str, daemon_cfg: dict,
                 trace: bool = False, control: str = "",
                 rehearsal: bool = False, trace_dir: str = ""):
        self.data_dir = data_dir
        self.log_path = log_path
        self.cfg = daemon_cfg
        self.trace, self.control, self.rehearsal = trace, control, rehearsal
        self.trace_dir = trace_dir
        self.master = f"127.0.0.1:{pick_port()}"
        self.volume = f"127.0.0.1:{pick_port()}"
        self.control_port = pick_port() if trace else 0
        self.proc: subprocess.Popen | None = None
        self.start_wall_s = 0.0

    def command(self) -> list[str]:
        server = [
            self.cfg["subcommand"],
            "-dir", self.data_dir,
            "-master.port", self.master.rsplit(":", 1)[1],
            "-port", self.volume.rsplit(":", 1)[1],
            *self.cfg.get("args", []),
        ]
        backend = "" if self.rehearsal else self.cfg.get("ec_backend", "")
        if backend:
            server += ["-ec.backend", backend]
        if not (self.trace or self.control or self.rehearsal):
            return [sys.executable, "-m", "seaweedfs_tpu", *server]
        wrap = [sys.executable, os.path.join(HERE, "daemon_main.py")]
        if self.trace:
            wrap += ["--trace-dir", self.trace_dir,
                     "--control-port", str(self.control_port)]
        if self.control:
            wrap += ["--control", self.control]
        if self.rehearsal:
            wrap += ["--rehearsal"]
        return wrap + ["--", *server]

    def __enter__(self) -> "Daemon":
        cmd = self.command()
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        if self.rehearsal:
            env["JAX_PLATFORMS"] = "cpu"
        os.makedirs(self.data_dir, exist_ok=True)
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self._log = open(self.log_path, "ab")
        self._log.write(f"\n==== {' '.join(cmd)}\n".encode())
        self._log.flush()
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        try:
            self._wait_ready()
        except BaseException:
            self.__exit__(None, None, None)
            raise
        self.start_wall_s = time.monotonic() - t0
        return self

    def _wait_ready(self, timeout: float = 240.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SystemExit(
                    f"daemon exited with {self.proc.returncode} before it "
                    f"served:\n{self.log_tail()}"
                )
            try:
                self.status()
                nodes = get_json(
                    f"http://{self.master}/dir/status", timeout=2.0
                )["topology"]["data_centers"]
                if nodes:
                    return
            except (OSError, KeyError, ValueError):
                pass
            time.sleep(0.1)
        raise SystemExit(f"daemon not ready in {timeout}s:\n{self.log_tail()}")

    def status(self) -> dict:
        return get_json(f"http://{self.volume}/status", timeout=10.0)

    def codec(self) -> dict:
        return self.status()["ec_codec"]

    def profiler(self, verb: str) -> dict:
        """start / stop the profiler inside the daemon (traced runs)."""
        return post_json(
            f"http://127.0.0.1:{self.control_port}/{verb}", timeout=300.0
        )

    def log_tail(self, lines: int = 30) -> str:
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-lines:]).decode(
                "utf-8", "replace"
            )

    def __exit__(self, *exc) -> None:
        """Stop the child and wait until it is gone."""
        if self.proc is not None and self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGINT)
            try:
                # the four-device daemon never left within 20 s of a SIGINT
                # (PR 23); nothing of a run waits on a clean exit
                self.proc.wait(timeout=8)
            except subprocess.TimeoutExpired:
                pass
        if self.proc is not None:
            with contextlib.suppress(ProcessLookupError):
                os.killpg(self.proc.pid, signal.SIGKILL)  # stragglers too
            self.proc.wait(timeout=30)
        self._log.close()
