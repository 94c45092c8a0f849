"""Diagnostics collector tests (reference coverage model:
diagnostics_test.go)."""

import json

import pytest

from pilosa_tpu import cli
from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config


def call(srv, method, path, body=None, raw=False):
    import urllib.request

    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}", data=data, method=method
    )
    with urllib.request.urlopen(req) as resp:
        payload = resp.read()
        return payload if raw else json.loads(payload or b"{}")


@pytest.fixture
def srv(tmp_path):
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "d"),
            anti_entropy_interval=0,
            diagnostics_interval=3600,
        )
    )
    s.open()
    yield s
    s.close()


def test_diagnostics_snapshot_written(srv, tmp_path):
    import time

    path = tmp_path / "d" / "diagnostics.json"
    # first flush runs on a background thread off the startup path
    deadline = time.time() + 30
    while not path.exists() and time.time() < deadline:
        time.sleep(0.05)
    assert path.exists()
    snap = json.loads(path.read_text())
    assert snap["num_indexes"] == 0
    assert snap["cluster_size"] == 1
    assert snap["uptime_seconds"] >= 0


def test_diagnostics_tracks_schema(srv, tmp_path):
    srv.api.create_index("i", {})
    srv.api.create_field("i", "f", {})
    srv.api.create_field("i", "v", {"type": "int", "min": 0, "max": 100})
    snap = srv.diagnostics.snapshot()
    assert snap["num_indexes"] == 1
    # _exists + f + v
    assert snap["field_types"].get("int") == 1
    assert snap["num_fields"] >= 2


def test_diagnostics_disabled(tmp_path):
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "d2"),
            anti_entropy_interval=0,
            diagnostics_interval=0,
        )
    )
    s.open()
    try:
        import time

        time.sleep(0.2)
        assert not (tmp_path / "d2" / "diagnostics.json").exists()
    finally:
        s.close()


def test_generate_config_subcommand(capsys):
    try:
        import tomllib
    except ImportError:  # Python < 3.11 — same shim as utils/config.py
        import tomli as tomllib

    assert cli.main(["generate-config"]) == 0
    out = capsys.readouterr().out
    cfg = tomllib.loads(out)
    assert cfg["bind"] == "127.0.0.1:10101"
    assert cfg["diagnostics-interval"] == 3600.0
    assert cfg["long-query-time"] == 0.0
    assert cfg["route-mode"] == "auto"


def test_pprof_profile_endpoint(srv):
    """/debug/pprof/profile samples all threads into folded-stack text
    (flamegraph input) — the reference's net/http/pprof analogue."""
    raw = call(srv, "GET", "/debug/pprof/profile?seconds=0.3", raw=True).decode()
    assert raw.startswith("#") and "samples over" in raw
    # the sampler excludes its own (handler) thread, but this in-process
    # server always has others alive — pytest's main thread blocked in
    # urlopen, the serve_forever thread — so ≥1 folded stack must appear
    stacks = [l for l in raw.splitlines()[1:] if l.strip()]
    assert stacks, "profile sampled no thread stacks"
    assert all(l.rsplit(" ", 1)[1].isdigit() for l in stacks)


def test_pprof_goroutine_endpoint(srv):
    raw = call(srv, "GET", "/debug/pprof/goroutine", raw=True).decode()
    assert "--- " in raw and "File " not in raw[:4]
    # at least the main + HTTP threads
    assert raw.count("--- ") >= 2


def test_pprof_heap_endpoint(srv):
    first = call(srv, "GET", "/debug/pprof/heap")
    assert "startedAt" in first
    # second call returns real allocation sites
    import numpy as _np
    _keep = _np.zeros(200_000, dtype=_np.uint8)
    second = call(srv, "GET", "/debug/pprof/heap?top=10")
    assert second["currentBytes"] > 0
    assert len(second["top"]) <= 10


def test_traces_chrome_export(srv):
    """/debug/traces?format=chrome emits Chrome trace-event JSON
    (loadable in chrome://tracing / Perfetto)."""
    call(srv, "GET", "/status")  # generate at least one span
    trace = call(srv, "GET", "/debug/traces?format=chrome")
    events = trace["traceEvents"]
    assert events, "no trace events exported"
    ev = events[-1]
    assert ev["ph"] == "X" and "name" in ev and "ts" in ev and "dur" in ev


def test_debug_vars_exposes_stack_cache_counters(srv):
    srv.api.create_index("sv", {})
    srv.api.create_field("sv", "f", {})
    call(srv, "POST", "/index/sv/query", b"Set(1, f=1)")
    # the cost router serves a query this small on the host path; the
    # DEVICE stack-cache counters under test need a device-routed query
    srv.api.executor.router.mode = "device"
    call(srv, "POST", "/index/sv/query", b"Count(Row(f=1))")
    v = call(srv, "GET", "/debug/vars")
    sc = v["stackCache"]
    assert sc["fullRestacks"] >= 1
    assert set(sc) >= {"deltaUpdates", "deltaRowsUploaded", "hotRowUploads", "entries"}
    # the routing snapshot rides along (docs/query-routing.md)
    assert v["queryRouting"]["mode"] == "device"


def test_statsd_emission(tmp_path):
    """metric_service=statsd emits UDP datagrams (classic statsd with
    dogstatsd tags) while /metrics keeps serving from the registry."""
    import socket

    sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sink.bind(("127.0.0.1", 0))
    sink.settimeout(5)
    port = sink.getsockname()[1]
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "sd"),
            anti_entropy_interval=0,
            metric_service="statsd",
            statsd_host=f"127.0.0.1:{port}",
        )
    )
    s.open()
    try:
        call(s, "GET", "/status")
        # the event front end emits connection/admission metrics before
        # the route counter — drain datagrams until it shows up instead
        # of assuming arrival order
        msgs = []
        for _ in range(10):
            msgs.append(sink.recv(4096).decode())
            if any(m.startswith("pilosa_tpu.http_requests:1|c") for m in msgs):
                break
        assert any(
            m.startswith("pilosa_tpu.http_requests:1|c") for m in msgs
        ), msgs
        # the registry still feeds /metrics
        text = call(s, "GET", "/metrics", raw=True).decode()
        assert "pilosa_tpu_http_requests" in text
    finally:
        sink.close()
        s.close()


def test_whole_run_sampler_sees_worker_threads(tmp_path):
    """The --cpu-profile sampler must capture NON-main threads (cProfile
    would only see the enabling thread) and bound memory by distinct
    stacks."""
    import threading
    import time

    from pilosa_tpu.utils.profiling import WholeRunSampler

    out = tmp_path / "prof.folded"
    stop = threading.Event()

    def spin_worker():
        while not stop.is_set():
            time.sleep(0.001)

    t = threading.Thread(target=spin_worker, name="spinner", daemon=True)
    t.start()
    sampler = WholeRunSampler(open(out, "w"), hz=200)
    sampler.start()
    time.sleep(0.5)
    sampler.stop()
    stop.set()
    t.join(timeout=2)
    text = out.read_text()
    assert text.startswith("#")  # header with sample count
    assert "spin_worker" in text  # the worker thread's stack was sampled
