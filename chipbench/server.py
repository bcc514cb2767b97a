"""The system under test as a child process, and the machine's cores
split between it and the load generator.  (Server started as
chip_smoke.py's: default settings, ephemeral ports, talked to only over
gRPC and the HTTP debug port.)"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GENERATOR_CORES = 4


class RunFailure(Exception):
    """The run cannot produce a result; the message is its last line."""


def split_cores() -> tuple:
    """(server's cores, generator's cores): the generator gets the last
    GENERATOR_CORES of this process's affinity set, the server the
    rest.  Fewer than 8 cores (a rehearsal): both share them all."""
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 8:
        return cores, cores
    return cores[:-GENERATOR_CORES], cores[-GENERATOR_CORES:]


class Server:
    START_LINE = re.compile(r"ratelimit serving: http=(\d+) grpc=(\d+) debug=(\d+) (.*)")

    def __init__(self, work: str, runtime_root: str, env_extra: dict, cpus: list):
        self.log_path = os.path.join(work, "server.log")
        self.device_report = os.path.join(work, "device_report.json")
        env = dict(os.environ)
        env.pop("TPU_NATIVE_SO", None)
        env.update(
            RUNTIME_ROOT=runtime_root, RUNTIME_SUBDIRECTORY="ratelimit",
            HOST="127.0.0.1", GRPC_HOST="127.0.0.1", DEBUG_HOST="127.0.0.1",
            PORT="0", GRPC_PORT="0", DEBUG_PORT="0", USE_STATSD="false",
            TMPDIR=work,  # the profiler's trace lands under gettempdir()
            CHIPBENCH_CPUS=",".join(map(str, cpus)),
            CHIPBENCH_DEVICE_REPORT=self.device_report,
            **env_extra,
        )
        self.t_spawn = time.monotonic()
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "chipbench.served"],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
        )
        self.grpc_port = self.debug_port = 0
        self.start_line = ""

    def log_tail(self, n: int = 30) -> str:
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(errors="replace")

    def warnings(self) -> list:
        """The server's own ERROR lines and fault-domain warnings."""
        with open(self.log_path, "rb") as f:
            lines = f.read().decode(errors="replace").splitlines()
        return [ln[:300] for ln in lines if " ERROR ratelimit" in ln or " WARNING ratelimit.faults" in ln]

    def wait_healthy(self, timeout_s: float) -> float:
        end = time.monotonic() + timeout_s
        while time.monotonic() < end:
            if self.proc.poll() is not None:
                raise RunFailure(
                    f"server exited {self.proc.returncode} during start-up:\n{self.log_tail()}"
                )
            with open(self.log_path, "rb") as f:
                m = self.START_LINE.search(f.read().decode(errors="replace"))
            if m:
                self.grpc_port, self.debug_port = int(m.group(2)), int(m.group(3))
                self.start_line = m.group(0)
                if self.http("/healthcheck") == "OK":
                    return time.monotonic() - self.t_spawn
            time.sleep(0.1)
        raise RunFailure(f"server not healthy after {timeout_s:.0f}s:\n{self.log_tail()}")

    def http(self, path: str, timeout: float = 60) -> str:
        url = f"http://127.0.0.1:{self.debug_port}{path}"
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.read().decode()

    def json(self, path: str):
        return json.loads(self.http(path))

    def cpu_seconds(self) -> float:
        """User + system CPU time of the server process so far."""
        with open(f"/proc/{self.proc.pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")

    def stop(self) -> dict:
        """SIGTERM, require exit code 0, return the device report."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise RunFailure(f"server still running 90s after SIGTERM:\n{self.log_tail()}") from None
        if rc != 0:
            raise RunFailure(f"server exit code {rc} after SIGTERM:\n{self.log_tail()}")
        try:
            with open(self.device_report) as f:
                return json.load(f)
        except FileNotFoundError:
            return {}

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()
