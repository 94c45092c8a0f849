"""Start, ask and stop the one child (harness/serve.py) from the jax-free
parent. Copied in substance from ``chip_smoke.py``'s ``ServerProcess``
(PR 21, proven on the chip)."""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.parse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class RunFailure(RuntimeError):
    """The run cannot give a result; the parent exits non-zero."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Client:
    """One keep-alive HTTP/1.1 connection."""

    def __init__(self, base: str, timeout: float = 600.0):
        u = urllib.parse.urlparse(base)
        self.host, self.port, self.timeout = u.hostname, u.port, timeout
        self.conn: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None) -> tuple[int, bytes]:
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body=body)
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def json(self, path: str, body: bytes | None = None):
        status, raw = self.request("POST" if body is not None else "GET", path, body)
        if status != 200:
            raise RunFailure(f"{path}: HTTP {status} {raw[:300]!r}")
        return json.loads(raw or b"{}")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def parse_metrics(text: str) -> dict:
    """/metrics text -> {family: {label string: value}}, prefix dropped."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        key, _, value = line.rpartition(" ")
        name, _, labels = key.partition("{")
        name = name.removeprefix("pilosa_tpu_")
        try:
            out.setdefault(name, {})[labels.rstrip("}")] = float(value)
        except ValueError:
            continue
    return out


class Server:
    def __init__(self, workdir: str, config_text: str, cache_dir: str):
        self.workdir = workdir
        self.cache_dir = cache_dir
        self.log_path = os.path.join(workdir, "server.log")
        self.config_path = os.path.join(workdir, "server.toml")
        with open(self.config_path, "w") as f:
            f.write(config_text)
        self.port, self.control_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.proc: subprocess.Popen | None = None
        self.spawned_at = 0.0

    def start(self, ready_timeout: float = 300.0) -> None:
        # JAX_LOG_COMPILES: the tree has no compile counter; the lines it
        # logs are counted between the window's marks.
        # The compile cache is the benchmark's to give: one fixed directory
        # inside the checkout, shared with no other checkout, and with no
        # size limit. A limit makes JAX lock the directory and scan all of
        # it on every store; the program stores a new program per wave
        # (PERF.md), so a run got slower with every run before it on the
        # machine (qps 16.5 -> 8.9 over 12 runs, my chip runs, PR 25).
        env = dict(os.environ, JAX_LOG_COMPILES="1", JAX_COMPILATION_CACHE_DIR=self.cache_dir)
        env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
        self.spawned_at = time.monotonic()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "serve.py"),
                 "--control-port", str(self.control_port), "--",
                 "server", "--bind", f"127.0.0.1:{self.port}",
                 "--data-dir", os.path.join(self.workdir, "data"),
                 "--config", self.config_path],
                cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT,
            )
        deadline = time.monotonic() + ready_timeout
        c = Client(self.base, timeout=5)
        while True:
            if self.proc.poll() is not None:
                raise RunFailure(f"server exited {self.proc.returncode} during boot:\n" + self.log_tail())
            try:
                # the listener answers before Server.open() has finished;
                # /info carries its diagnostics block once it has
                if "diagnostics" in c.json("/info"):
                    c.close()
                    return
            except (RunFailure, http.client.HTTPException, OSError):
                c.close()
            if time.monotonic() > deadline:
                raise RunFailure(f"server not answering after {ready_timeout:.0f}s:\n" + self.log_tail())
            time.sleep(0.05)

    def control(self, **req) -> dict:
        with socket.create_connection(("127.0.0.1", self.control_port), timeout=300) as s:
            s.sendall(json.dumps(req).encode() + b"\n")
            reply = json.loads(s.makefile("rb").readline())
        if not reply.get("ok"):
            raise RunFailure(f"control {req.get('cmd')}: {reply.get('error')}")
        return reply

    def device_facts(self) -> dict:
        diag = Client(self.base).json("/info")["diagnostics"]
        return {"platform": diag["backend"], "kind": diag["device_kind"],
                "count": diag["device_count"],
                "compile_cache_dir": diag.get("compile_cache_dir"),
                "router_pinned_host": diag.get("router_pinned_host")}

    def scrape(self) -> dict:
        c = Client(self.base, timeout=60)
        try:
            status, raw = c.request("GET", "/metrics")
            return {"at": time.monotonic(), "metrics": parse_metrics(raw.decode()),
                    "resources": c.json("/debug/resources"),
                    "log_offset": os.path.getsize(self.log_path)}
        finally:
            c.close()

    def stop(self) -> None:
        """SIGTERM, then wait; a server that will not go is killed."""
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.kill()

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def log_tail(self, n: int = 30) -> str:
        if not os.path.exists(self.log_path):
            return ""
        with open(self.log_path, errors="replace") as f:
            # JAX_LOG_COMPILES lines are most of the log and say little
            lines = [x for x in f if "Finished " not in x and "Compiling " not in x]
        return "".join(x[:400] for x in lines[-n:])
