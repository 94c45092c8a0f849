"""Shard-owning multi-process serving suite (docs/multiprocess.md).

Two halves, like the serving suite's split:

* in-process (tier-1): the SO_REUSEPORT capability probe, supervisor
  planning/backoff/state-file units, shared-listener and fd-pass
  adoption on live in-process servers, the ``/debug/processes`` fleet
  view, the saturation scale-out recommendation, and ``doctor
  --fleet``.
* real-subprocess (also marked slow, like the clusterproc and
  durability kill-9 suites): a supervised 3-process topology behind
  one public port — config8 bit-equivalence vs a solo server for
  every PQL call type, kill -9 of one child under load with zero
  failed queries and supervised rejoin, and a many-connection smoke
  across processes.
"""

import array
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.server.supervisor import (
    Supervisor,
    probe_so_reuseport,
    restart_backoff,
)
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.utils.config import Config

pytestmark = pytest.mark.multiproc


def free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def http(port, method, path, body=None, timeout=60):
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read() or b"{}")


# --------------------------------------------------------------- units


def test_probe_so_reuseport_here():
    # Linux has had SO_REUSEPORT since 3.9; the CI boxes are far newer.
    assert probe_so_reuseport() is True


def test_probe_so_reuseport_missing(monkeypatch):
    # platforms without the option raise at setsockopt — the probe
    # must answer False, not explode (the supervisor falls back to
    # accept-and-pass on False)
    monkeypatch.delattr(socket, "SO_REUSEPORT")
    assert probe_so_reuseport() is False


def test_restart_backoff_curve():
    assert restart_backoff(0, 0.5, 10.0) == 0.0
    assert [restart_backoff(n, 0.5, 10.0) for n in (1, 2, 3, 4, 5)] == [
        0.5, 1.0, 2.0, 4.0, 8.0,
    ]
    # capped, never unbounded
    assert restart_backoff(50, 0.5, 10.0) == 10.0


def test_supervisor_rejects_zero_processes(tmp_path):
    with pytest.raises(ValueError):
        Supervisor(Config(serving_processes=0, data_dir=str(tmp_path)))


def test_supervisor_plan_reuseport(tmp_path):
    cfg = Config(
        serving_processes=3,
        bind="127.0.0.1:18300",
        data_dir=str(tmp_path),
        replica_n=2,
    )
    sup = Supervisor(cfg, argv_overrides={"tls_skip_verify": "1"})
    sup.mode = "reuseport"
    children = sup.plan()
    assert len(children) == 3
    binds = [c.bind for c in children]
    assert len(set(binds)) == 3 and "127.0.0.1:18300" not in binds
    assert len({c.data_dir for c in children}) == 3
    seeds = ",".join(f"http://{b}" for b in binds)
    for i, c in enumerate(children):
        env = c.env
        # never recurse: children are solo servers
        assert env["PILOSA_TPU_SERVING_PROCESSES"] == "1"
        # node ids must derive from binds (peers derive them from the
        # seed list; ownership hashes ids — they must agree fleet-wide)
        assert "PILOSA_TPU_NAME" not in env
        assert env["PILOSA_TPU_COORDINATOR"] == ("1" if i == 0 else "0")
        assert env["PILOSA_TPU_SEEDS"] == seeds
        assert env["PILOSA_TPU_REPLICA_N"] == "2"
        # every child opens the SAME public bind via SO_REUSEPORT
        assert env["PILOSA_TPU_SHARED_BIND"] == "127.0.0.1:18300"
        assert "PILOSA_TPU_FD_PASS_SOCKET" not in env
        # CLI pass-through flags reach children as env (env < argv in
        # the child's own precedence, so argv stays the per-child layer)
        assert env["PILOSA_TPU_TLS_SKIP_VERIFY"] == "1"
        assert env["PILOSA_TPU_SUPERVISOR_STATE"] == sup.state_path


def test_supervisor_plan_fd_pass(tmp_path):
    cfg = Config(
        serving_processes=2, bind="127.0.0.1:18301", data_dir=str(tmp_path)
    )
    sup = Supervisor(cfg)
    sup.mode = "fd-pass"
    children = sup.plan()
    for i, c in enumerate(children):
        assert "PILOSA_TPU_SHARED_BIND" not in c.env
        assert c.env["PILOSA_TPU_FD_PASS_SOCKET"].endswith(f"proc{i}.sock")


def test_supervisor_state_file(tmp_path):
    cfg = Config(
        serving_processes=2, bind="127.0.0.1:18302", data_dir=str(tmp_path)
    )
    sup = Supervisor(cfg)
    sup.mode = "reuseport"
    sup.children = sup.plan()
    sup._write_state()
    state = json.loads(open(sup.state_path).read())
    assert state["mode"] == "reuseport"
    assert state["publicBind"] == "127.0.0.1:18302"
    assert state["parentPid"] == os.getpid()
    rows = state["processes"]
    assert [r["index"] for r in rows] == [0, 1]
    for r, c in zip(rows, sup.children):
        assert r["bind"] == c.bind
        assert r["uri"] == f"http://{c.bind}"
        assert r["ready"] is False and r["restarts"] == 0


# ------------------------------------------------- in-process listeners


def _make_server(tmp_path, name, **kw):
    from pilosa_tpu.server import Server

    cfg = Config(
        bind="127.0.0.1:0",
        data_dir=str(tmp_path / name),
        anti_entropy_interval=0,
        **kw,
    )
    s = Server(cfg)
    s.open()
    s.wait_mesh(60)
    return s


def test_shared_reuseport_listener_two_servers(tmp_path):
    """Two full event front ends in one process share a public port via
    SO_REUSEPORT — the supervisor topology's data plane, minus the
    process boundary.  Every connection to the shared port must be
    served by SOME member, and each member advertises the listener in
    its serving snapshot."""
    if not probe_so_reuseport():
        pytest.skip("no SO_REUSEPORT on this host")
    (shared,) = free_ports(1)
    a = _make_server(tmp_path, "a", shared_bind=f"127.0.0.1:{shared}")
    b = _make_server(tmp_path, "b", shared_bind=f"127.0.0.1:{shared}")
    try:
        for _ in range(16):
            st = http(shared, "GET", "/status")
            assert st["state"] == "NORMAL"
        for s in (a, b):
            snap = http(s.port, "GET", "/debug/vars")["serving"]
            assert snap["sharedListener"] == {
                "mode": "reuseport",
                "bind": f"127.0.0.1:{shared}",
            }
        # the private per-member bind still answers (cluster legs ride it)
        assert http(a.port, "GET", "/status")["state"] == "NORMAL"
    finally:
        a.close()
        b.close()


def test_fd_pass_adoption(tmp_path):
    """The accept-and-pass fallback: a connected TCP socket shipped
    over the child's unix control socket via SCM_RIGHTS is adopted by
    the event loop and served like any accepted connection."""
    fd_sock = str(tmp_path / "proc0.sock")
    s = _make_server(tmp_path, "a", fd_pass_socket=fd_sock)
    try:
        snap = http(s.port, "GET", "/debug/vars")["serving"]
        assert snap["sharedListener"] == {"mode": "fd-pass", "bind": fd_sock}

        ctrl = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        ctrl.connect(fd_sock)
        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        client = socket.create_connection(lst.getsockname())
        served, _ = lst.accept()
        # what the supervisor parent does per accepted connection
        ctrl.sendmsg(
            [b"c"],
            [(
                socket.SOL_SOCKET,
                socket.SCM_RIGHTS,
                array.array("i", [served.fileno()]).tobytes(),
            )],
        )
        served.close()  # parent's copy: the child owns the fd now
        client.sendall(
            b"GET /status HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
        )
        client.settimeout(30)
        buf = b""
        while True:
            chunk = client.recv(65536)
            if not chunk:
                break
            buf += chunk
        assert buf.startswith(b"HTTP/1.1 200") and b"NORMAL" in buf
        client.close()
        ctrl.close()
        lst.close()
        assert (
            http(s.port, "GET", "/debug/vars")["counters"][
                "connections_adopted"
            ]
            == 1.0
        )
    finally:
        s.close()


# ------------------------------------------------ fleet observability


def test_debug_processes_unsupervised(tmp_path):
    s = _make_server(tmp_path, "a")
    try:
        view = http(s.port, "GET", "/debug/processes")
        assert view["supervised"] is False
        (row,) = view["processes"]
        assert "binding" in row and "verdict" in row
        assert row["sharedListener"] == {"mode": "none"}
    finally:
        s.close()


def test_debug_processes_supervised(tmp_path):
    """The stitched fleet view: supervisor state + each live member's
    saturation digest fetched over localhost; dead members report an
    error row instead of poisoning the whole view."""
    s = _make_server(tmp_path, "a")
    try:
        (dead_port,) = free_ports(1)
        state = {
            "mode": "reuseport",
            "publicBind": "127.0.0.1:1",
            "publicUri": "http://127.0.0.1:1",
            "parentPid": 4242,
            "processes": [
                {
                    "index": 0,
                    "bind": f"127.0.0.1:{s.port}",
                    "uri": f"http://127.0.0.1:{s.port}",
                    "dataDir": str(tmp_path),
                    "pid": 1,
                    "ready": True,
                    "restarts": 0,
                    "lastExitCode": None,
                },
                {
                    "index": 1,
                    "bind": f"127.0.0.1:{dead_port}",
                    "uri": f"http://127.0.0.1:{dead_port}",
                    "dataDir": str(tmp_path),
                    "pid": 2,
                    "ready": False,
                    "restarts": 3,
                    "lastExitCode": -9,
                },
            ],
        }
        sp = tmp_path / "supervisor.json"
        sp.write_text(json.dumps(state))
        s.http.supervisor_state_path = str(sp)

        view = http(s.port, "GET", "/debug/processes?window=60")
        assert view["supervised"] is True
        assert view["mode"] == "reuseport"
        assert view["parentPid"] == 4242
        live, dead = view["processes"]
        assert live["index"] == 0 and "binding" in live
        assert dead["index"] == 1 and "error" in dead
        assert dead["restarts"] == 3 and dead["lastExitCode"] == -9

        with pytest.raises(urllib.error.HTTPError) as e:
            http(s.port, "GET", "/debug/processes?window=nope")
        assert e.value.code == 400
    finally:
        s.close()


def test_saturation_scale_out_recommendation(monkeypatch):
    """worker-pool/GIL saturation is a per-interpreter ceiling: the
    verdict must name the serving-processes remedy sized from host
    cores — and waive it on a core-starved box (the bench's
    MULTICHIP_r06 waiver discipline)."""
    from pilosa_tpu.utils import saturation as satmod
    from pilosa_tpu.utils.saturation import SaturationMonitor

    mon = SaturationMonitor()
    # drive GIL pressure to 1.0 (p99 >= GIL_WAIT_SATURATED_S)
    for _ in range(32):
        mon.gil.lag.observe(0.5)

    monkeypatch.setattr(satmod.os, "cpu_count", lambda: 8)
    rep = mon.report(window_s=60.0)
    assert rep["binding"] == "gil"
    rec = rep["recommendation"]
    assert rec["remedy"] == "serving-processes"
    assert rec["hostCores"] == 8
    assert rec["suggestedProcesses"] == 8
    assert "gate" not in rec

    monkeypatch.setattr(satmod.os, "cpu_count", lambda: 1)
    rec1 = mon.report(window_s=60.0)["recommendation"]
    assert rec1["suggestedProcesses"] == 2
    assert rec1["gate"].startswith("waived: 1 core")

    # an unsaturated window carries no recommendation
    assert "recommendation" not in SaturationMonitor().report(window_s=60.0)


def test_doctor_fleet(tmp_path):
    """``doctor --fleet`` bundles every co-resident process listed by
    /debug/processes — one command captures the whole box."""
    from pilosa_tpu import cli

    a = _make_server(tmp_path, "a")
    b = _make_server(tmp_path, "b")
    try:
        state = {
            "mode": "reuseport",
            "publicBind": "127.0.0.1:1",
            "publicUri": "http://127.0.0.1:1",
            "parentPid": 4242,
            "processes": [
                {
                    "index": 0,
                    "bind": f"127.0.0.1:{a.port}",
                    "uri": f"http://127.0.0.1:{a.port}",
                    "ready": True,
                },
                {
                    "index": 1,
                    "bind": f"127.0.0.1:{b.port}",
                    "uri": f"http://127.0.0.1:{b.port}",
                    "ready": True,
                },
            ],
        }
        sp = tmp_path / "supervisor.json"
        sp.write_text(json.dumps(state))
        a.http.supervisor_state_path = str(sp)

        out = tmp_path / "bundle.json"
        rc = cli.main(
            [
                "doctor",
                "--host", f"127.0.0.1:{a.port}",
                "--fleet",
                "--out", str(out),
            ]
        )
        assert rc == 0
        bundle = json.loads(out.read_text())
        assert bundle["doctorErrors"] == 0
        # the target itself is not duplicated under fleet
        fleet = bundle["fleet"]
        assert list(fleet) == [f"http://127.0.0.1:{b.port}"]
        sub = fleet[f"http://127.0.0.1:{b.port}"]
        assert sub["endpoints"]["/status"]["state"] == "NORMAL"
        assert any(p.startswith("/debug/saturation") for p in sub["endpoints"])
        # without --fleet the bundle shape is unchanged
        rc = cli.main(
            ["doctor", "--host", f"127.0.0.1:{a.port}", "--out", str(out)]
        )
        assert rc == 0
        assert "fleet" not in json.loads(out.read_text())
    finally:
        a.close()
        b.close()


# ------------------------------------------- real-subprocess topology


def _spawn_supervisor(tmp_path, n, port, replica_n=2):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # the conftest's 8-virtual-device XLA_FLAGS slows subprocess
        # startup and isn't needed here
        XLA_FLAGS="",
        PILOSA_TPU_SHARD_WIDTH_EXP=os.environ.get(
            "PILOSA_TPU_SHARD_WIDTH_EXP", "16"
        ),
        PILOSA_TPU_ANTI_ENTROPY_INTERVAL="0",
        PILOSA_TPU_DIAGNOSTICS_INTERVAL="0",
    )
    args = [
        sys.executable, "-m", "pilosa_tpu", "server",
        "--processes", str(n),
        "--bind", f"127.0.0.1:{port}",
        "--data-dir", str(tmp_path / "fleet"),
        "--replica-n", str(replica_n),
    ]
    log = open(tmp_path / "supervisor.log", "w")
    return subprocess.Popen(args, env=env, stdout=log, stderr=subprocess.STDOUT)


def wait_public_ready(port, deadline=600.0):
    # N JAX subprocesses importing concurrently on a 1-CPU CI box take
    # minutes; the supervisor only opens the public port after every
    # child reports NORMAL, so one poll loop covers the whole fleet.
    t0 = time.time()
    while time.time() - t0 < deadline:
        try:
            if http(port, "GET", "/status", timeout=5)["state"] == "NORMAL":
                return
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.5)
    raise TimeoutError(f"supervised fleet on :{port} did not come up")


def _read_state(tmp_path):
    return json.loads(open(tmp_path / "fleet" / "supervisor.json").read())


def _reap_fleet(tmp_path, sup):
    """Last-resort cleanup: if the supervisor had to be SIGKILLed, its
    children are orphaned — reap them via the state file's pids."""
    if sup.poll() is None:
        sup.send_signal(signal.SIGTERM)
        try:
            sup.wait(timeout=60)
        except subprocess.TimeoutExpired:
            sup.kill()
            sup.wait(timeout=30)
    try:
        for row in _read_state(tmp_path)["processes"]:
            if row.get("pid"):
                try:
                    os.kill(row["pid"], signal.SIGKILL)
                except (OSError, ProcessLookupError):
                    pass
    except (OSError, ValueError, KeyError):
        pass


# every PQL call type over HTTP: bitmap ops, counts, aggregates, BSI
# compares, TopN, Rows, GroupBy (the mesh-SPMD suite's coverage, at the
# wire level)
EQUIV_QUERIES = [
    b"Row(f=1)",
    b"Row(f=999)",
    b"Union(Row(f=1), Row(f=2), Row(g=0))",
    b"Intersect(Row(f=1), Row(g=2))",
    b"Difference(Row(f=1), Row(g=0))",
    b"Xor(Row(f=1), Row(g=3))",
    b"Not(Row(f=1))",
    b"All()",
    b"Count(Intersect(Row(f=1), Row(g=2)))",
    b"Count(Union(Row(f=1), Row(f=2)))",
    b"Count(Not(Row(f=1)))",
    b"Count(All())",
    b"Count(Row(v > 100))",
    b"Count(Row(v >= -50))",
    b"Count(Row(v < 0))",
    b"Count(Row(v == 7))",
    b"Count(Row(v != 7))",
    b"Row(v > 250)",
    b"TopN(f, n=3)",
    b"TopN(f)",
    b"TopN(f, ids=[1, 2, 5])",
    b"TopN(f, n=3, Row(g=1))",
    b"Sum(field=v)",
    b"Sum(Row(g=1), field=v)",
    b"Min(field=v)",
    b"Max(field=v)",
    b"Rows(f)",
    b"Rows(f, limit=3)",
    b"GroupBy(Rows(f))",
    b"GroupBy(Rows(f), Rows(g))",
    b"GroupBy(Rows(f), Rows(g), limit=7)",
    b"GroupBy(Rows(f), Rows(g), filter=Row(f=1))",
]


def _load_dataset(port):
    import numpy as np

    rng = np.random.default_rng(19)
    n_shards, n = 6, 4000
    http(port, "POST", "/index/i", {})
    http(port, "POST", "/index/i/field/f", {})
    http(port, "POST", "/index/i/field/g", {})
    http(
        port, "POST", "/index/i/field/v",
        {"options": {"type": "int", "min": -1000, "max": 1000}},
    )
    cols = rng.choice(n_shards * SHARD_WIDTH, n, replace=False)
    frows = rng.integers(0, 8, n)
    grows = rng.integers(0, 5, n)
    vals = rng.integers(-500, 500, n)
    for field, rows in (("f", frows), ("g", grows)):
        http(
            port, "POST", f"/index/i/field/{field}/import",
            {"rowIDs": [int(r) for r in rows],
             "columnIDs": [int(c) for c in cols]},
            timeout=300,
        )
    http(
        port, "POST", "/index/i/field/v/import-value",
        {"columnIDs": [int(c) for c in cols],
         "values": [int(v) for v in vals]},
        timeout=300,
    )


@pytest.mark.slow
def test_multiproc_config8_equivalence_and_kill9(tmp_path):
    """The tentpole acceptance run, one topology to amortize fleet
    startup: (1) every PQL call type answers bit-identically through a
    supervised 3-process SO_REUSEPORT topology vs a solo in-process
    server on the same dataset; (2) kill -9 of one child under a live
    query loop fails ZERO queries (replica failover inside surviving
    members) and loses zero acknowledged writes; (3) the supervisor
    restarts the child with backoff and it rejoins, re-hydrating
    ownership from its data dir; (4) graceful SIGTERM drain."""
    (public,) = free_ports(1)
    sup = _spawn_supervisor(tmp_path, n=3, port=public, replica_n=2)
    try:
        wait_public_ready(public)
        state = _read_state(tmp_path)
        assert state["mode"] in ("reuseport", "fd-pass")
        assert len(state["processes"]) == 3
        assert all(r["ready"] for r in state["processes"])

        _load_dataset(public)
        # acknowledged writes, to be re-verified after the kill
        baseline_count = http(
            public, "POST", "/index/i/query", b"Count(All())"
        )["results"][0]
        assert baseline_count > 0

        # (1) bit-equivalence vs a solo server over the same dataset
        solo = _make_server(tmp_path, "solo")
        try:
            _load_dataset(solo.port)
            for q in EQUIV_QUERIES:
                multi = http(public, "POST", "/index/i/query", q, timeout=120)
                alone = http(
                    solo.port, "POST", "/index/i/query", q, timeout=120
                )
                assert multi["results"] == alone["results"], q
        finally:
            solo.close()

        # (2) kill -9 one non-coordinator child under a live query loop
        victim = state["processes"][2]
        failures: list[str] = []
        answers: list[int] = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                try:
                    r = http(
                        public, "POST", "/index/i/query",
                        b"Count(Row(f=1))", timeout=60,
                    )
                    answers.append(r["results"][0])
                except urllib.error.HTTPError as e:
                    failures.append(f"HTTP {e.code}")
                except (urllib.error.URLError, OSError):
                    # the connection that was parked inside the killed
                    # process dies mid-flight: a transport reset, not a
                    # served-then-failed query. New connections land on
                    # live members (the dead child's listening socket
                    # closed with it).
                    pass
                time.sleep(0.05)

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(1.0)
        os.kill(victim["pid"], signal.SIGKILL)
        time.sleep(8.0)
        stop.set()
        t.join(timeout=30)
        assert failures == [], failures
        assert answers, "query loop never completed a query"
        expected = answers[0]
        assert all(a == expected for a in answers), set(answers)

        # zero acknowledged writes lost: replicas serve the full count
        assert (
            http(public, "POST", "/index/i/query", b"Count(All())")[
                "results"
            ][0]
            == baseline_count
        )

        # (3) the supervisor respawns the child and it rejoins NORMAL
        deadline = time.time() + 300
        rejoined = False
        while time.time() < deadline and not rejoined:
            st = _read_state(tmp_path)
            row = st["processes"][victim["index"]]
            if row["restarts"] >= 1 and row["ready"]:
                try:
                    child_port = int(row["bind"].rsplit(":", 1)[1])
                    rejoined = (
                        http(child_port, "GET", "/status", timeout=5)[
                            "state"
                        ]
                        == "NORMAL"
                    )
                except (urllib.error.URLError, OSError):
                    pass
            time.sleep(1.0)
        assert rejoined, "killed child did not rejoin"
        assert _read_state(tmp_path)["processes"][victim["index"]][
            "lastExitCode"
        ] == -signal.SIGKILL

        # full equivalence again through the healed topology
        for q in EQUIV_QUERIES[:8]:
            assert http(public, "POST", "/index/i/query", q, timeout=120)[
                "results"
            ]

        # (4) graceful drain
        sup.send_signal(signal.SIGTERM)
        assert sup.wait(timeout=120) == 0
    finally:
        _reap_fleet(tmp_path, sup)


@pytest.mark.slow
def test_multiproc_connection_smoke(tmp_path):
    """10k concurrent sockets spread across a 2-process fleet behind
    one public port: every connection accepted by SOME member, a
    sampled subset served, fleet connection counts add up across
    /debug/processes."""
    target = int(os.environ.get("PILOSA_TPU_SMOKE_CONNECTIONS", "10000"))
    (public,) = free_ports(1)
    sup = _spawn_supervisor(tmp_path, n=2, replica_n=1, port=public)
    socks = []
    try:
        wait_public_ready(public)
        failures = 0
        for _ in range(target):
            try:
                c = socket.create_connection(("127.0.0.1", public), timeout=10)
                socks.append(c)
            except OSError:
                failures += 1
        assert failures == 0, f"{failures}/{target} connects failed"
        # a sampled subset actually speaks HTTP end-to-end
        for c in socks[:: max(1, target // 64)]:
            c.sendall(
                b"GET /status HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
            )
            c.settimeout(60)
            buf = b""
            while True:
                chunk = c.recv(65536)
                if not chunk:
                    break
                buf += chunk
            assert b"200" in buf.split(b"\r\n", 1)[0]
        # the stitched fleet view sees connections on both members
        view = http(public, "GET", "/debug/processes", timeout=60)
        assert view["supervised"] is True
        opens = [
            r.get("connectionsOpen", 0)
            for r in view["processes"]
            if "error" not in r
        ]
        assert sum(opens) >= len(socks) * 0.9
    finally:
        for c in socks:
            try:
                c.close()
            except OSError:
                pass
        _reap_fleet(tmp_path, sup)
