"""The server child and the HTTP the benchmark speaks to it.

A copy of ``chip_smoke.py``'s ``Server`` (a later PR may change that file
and may not change the yardstick).  The parent never imports JAX while the
child lives: a chip belongs to one process at a time.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time


class BenchFailure(Exception):
    """The run cannot give a result; it exits non-zero and prints none."""


def check(cond: bool, why: str) -> None:
    if not cond:
        raise BenchFailure(why)


class Http:
    """One keep-alive connection; JSON in, (status, JSON) out.  Used from
    one thread only."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self._addr = (host, port)
        self._timeout = timeout
        self._conn: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, payload=None):
        body = None if payload is None else json.dumps(payload).encode()
        headers = {"Content-Type": "application/json"} if body else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    *self._addr, timeout=self._timeout)
            try:
                self._conn.request(method, path, body=body, headers=headers)
                resp = self._conn.getresponse()
                raw = resp.read()
                break
            except (http.client.HTTPException, ConnectionError, OSError):
                # a keep-alive connection the server closed: one new one
                self.close()
                if attempt:
                    raise
        try:
            doc = json.loads(raw) if raw else None
        except ValueError:
            doc = {"raw": raw[:500].decode("utf-8", "replace")}
        return resp.status, doc

    def get(self, path: str):
        status, doc = self.request("GET", path)
        check(status == 200, f"GET {path} answered {status}: {doc}")
        return doc

    def post(self, path: str, payload=None):
        return self.request("POST", path, payload)

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None


class Server:
    """``cli serve`` as a child whose working directory is ``cwd``: its
    ``output/`` PNGs, ``input/``, ``logs/`` and ``cluster_config.json``
    land there and not in the checkout."""

    def __init__(self, repo_root: str, cwd: str, log_path: str, env: dict):
        self.cwd = cwd
        os.makedirs(self.cwd, exist_ok=True)
        self.log_path = log_path
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self._log = open(self.log_path, "wb")
        env = dict(env)
        env["PYTHONPATH"] = repo_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "comfyui_distributed_tpu.cli", "serve",
             "--host", "127.0.0.1", "--port", str(self.port),
             "--config", os.path.join(self.cwd, "cluster_config.json")],
            cwd=self.cwd, env=env, stdout=self._log,
            stderr=subprocess.STDOUT)

    def http(self, timeout: float = 60.0) -> Http:
        return Http("127.0.0.1", self.port, timeout)

    def log_tail(self, n: int = 3000) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode("utf-8", "replace")

    def log_has_traceback(self) -> bool:
        with open(self.log_path, "rb") as f:
            return any(b"Traceback" in line for line in f)

    def require_alive(self) -> None:
        rc = self.proc.poll()
        check(rc is None,
              f"server child exited with code {rc}:\n{self.log_tail()}")

    def wait_ready(self, timeout: float = 300.0) -> dict:
        """First answer of /distributed/status (it builds the mesh)."""
        deadline = time.monotonic() + timeout
        client = self.http()
        try:
            while True:
                self.require_alive()
                try:
                    return client.get("/distributed/status")
                except (http.client.HTTPException, ConnectionError,
                        OSError):
                    check(time.monotonic() < deadline,
                          f"server not answering after {timeout:.0f}s:\n"
                          f"{self.log_tail()}")
                    time.sleep(0.25)
        finally:
            client.close()

    def shut_down(self, timeout: float = 120.0) -> int:
        """SIGTERM -> aiohttp's graceful exit.  Returns the exit code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout)
        finally:
            self.kill()

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if not self._log.closed:
            self._log.close()
